"""One `_paged_folded` call is one layer's paged flash-decode attention
(`dllama_tpu/ops/pallas/paged_attention.py`) over every slot of the batch:
q[B, Hkv, rows, hd] against the K and V pools, walked page by page through
the block tables.

The floor is the rows the call NEEDS, not the pages it touches: a decode
step of a slot at position p attends p + 1 rows, each row of K and of V is
`Hkv * head * itemsize` bytes in one layer, and every slot of a step is one
call. So a call's bytes are (KV rows attended by the step, over its slots)
x 2 x Hkv x head x itemsize, plus q in (bf16) and the result out (f32);
its FLOPs are 4 x Hq x head a row (q.k and p.v). Which rows a step
attended is not in the trace. The program counts them where it builds the
launch (`dllama_launch_kv_rows_total`, `dllama_slot_steps_total`) and
`/debug/perf` gives the counts of the launches dispatched inside the
capture (`capture`): the mean over those launches' decode steps prices
every decode-shaped call of the trace. Copied arithmetic of the program's
`obs/perf.decode_step_bytes` / `experiments/hbm_traffic.py` (their
`kv_stream` term, without the rounding up to whole pages), as
`costs/q40_matmul.py` copied the matmul's.

A call whose batch is not the configuration's `serve.slots` is a prefill
slice (B = 1, a hybrid launch's prompt rows): it is priced by nothing here
and left out of both sides of the share.
"""

from __future__ import annotations

import re

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
               "s8": 1, "u8": 1}
DECODE_KINDS = ("decode", "decode_pen", "hybrid", "hybrid_pen")

# %_paged_folded.15 = (f32[12,32,8,128]{...}, bf16[67,32,128,128]{...}, ...
_RESULT = re.compile(r"= \(f32\[(\d+),(\d+),(\d+),(\d+)\](?:\{[^}]*\})?, "
                     r"(\w+)\[(\d+),(\d+),(\d+),(\d+)\]")


def shape(trace_op: dict):
    """(batch, kv heads, pool dtype) of one traced call, from its HLO text;
    None when the text does not parse."""
    m = _RESULT.search(trace_op["hlo"])
    if not m or m.group(5) not in DTYPE_BYTES or m.group(2) != m.group(7):
        return None
    return int(m.group(1)), int(m.group(2)), m.group(5)


def rows_per_step(capture: dict, slots: int):
    """Mean KV rows one decode step attended, over its slots, among the
    launches of the capture: rows of the decode and hybrid launches over
    their steps, a launch of n steps being n x slots slot-steps of one
    state or another. None when the capture holds no decode step."""
    if not capture:
        return None
    launched = capture.get("launches") or {}
    if any(n > 0 for kind, n in launched.items()
           if kind not in DECODE_KINDS and kind != "prefill_chunk"):
        return None  # a spec chunk's steps are verify cycles: not priced
    rows = sum((capture.get("kv_rows") or {}).get(k, 0.0) for k in DECODE_KINDS)
    steps = sum((capture.get("slot_steps") or {}).values()) / float(slots)
    if rows <= 0 or steps <= 0:
        return None
    return rows / steps


def cost(rows: float, slots: int, q_heads: int, kv_heads: int, head: int,
         itemsize: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call whose slots attend `rows` KV rows in all."""
    kv = 2.0 * rows * kv_heads * head * itemsize
    q_and_out = slots * q_heads * head * (2 + 4)
    return 4.0 * rows * q_heads * head, kv + q_and_out


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced `_paged_folded` call -> (FLOPs, bytes), "skip" for a
    prefill slice, or None when nothing certain can be said (the text does
    not parse, one slot only so a slice cannot be told from a step, no
    capture block): no share is then reported."""
    got = shape(trace_op)
    slots = int(config["serve"]["slots"])
    if got is None or slots < 2:
        return None
    batch, kv_heads, dtype = got
    if batch != slots:
        return "skip" if batch == 1 else None
    rows = rows_per_step(capture, slots)
    if rows is None or kv_heads != int(config["num_key_value_heads"]):
        return None
    q_heads = int(config["num_attention_heads"])
    head = int(config.get("head_dim") or config["hidden_size"] // q_heads)
    return cost(rows, slots, q_heads, kv_heads, head, DTYPE_BYTES[dtype])
