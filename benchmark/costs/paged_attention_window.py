"""The paged flash-decode kernel of a model whose attention layers are of two
kinds (`dllama_tpu/ops/pallas/paged_attention.py`): a `_paged_folded` call is
one layer that sees the whole context, over the global page pool; a
`_paged_window` call is one windowed layer, over the window pool, its walk
clipped to the pages that hold a visible row.

As in `costs/paged_attention.py` the floor is the rows a call NEEDS, not the
pages it touches, and K and V rows are `Hkv * head * itemsize` bytes each in
one layer. What differs is the rows: a decode step of a slot at position p
reads p + 1 rows in a global layer and min(p + 1, window) in a windowed one.
The program counts both where it builds the launch
(`dllama_launch_kv_rows_read_total{kind,pool}`, from the host arrays the
launch record already holds) and `/debug/perf` gives the counts of the
launches dispatched inside the capture (`capture.kv_rows_read`, keys
"kind,pool"): the mean over those launches' decode steps prices every
decode-shaped call of its pool. A call whose batch is not the
configuration's `serve.slots` is a prefill slice and is left out of both
sides of the share.
"""

from __future__ import annotations

from benchmark.costs import paged_attention as base

POOL_OF = {"_paged_folded": "global", "_paged_window": "window"}


def rows_per_step(capture: dict, slots: int, pool: str):
    """Mean KV rows one decode step read in one layer of `pool`, over its
    slots, among the launches of the capture; None when the capture holds
    no decode step or no such count (a program without windowed layers)."""
    if not capture:
        return None
    launched = capture.get("launches") or {}
    if any(n > 0 for kind, n in launched.items()
           if kind not in base.DECODE_KINDS and kind != "prefill_chunk"):
        return None  # a spec chunk's steps are verify cycles: not priced
    read = capture.get("kv_rows_read") or {}
    rows = sum(read.get(f"{k},{pool}", 0.0) for k in base.DECODE_KINDS)
    steps = sum((capture.get("slot_steps") or {}).values()) / float(slots)
    if rows <= 0 or steps <= 0:
        return None
    return rows / steps


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced call of either name -> (FLOPs, bytes), "skip" for a
    prefill slice, None when nothing certain can be said."""
    pool = POOL_OF.get(trace_op["group"])
    got = base.shape(trace_op)
    slots = int(config["serve"]["slots"])
    if pool is None or got is None or slots < 2:
        return None
    batch, kv_heads, dtype = got
    if batch != slots:
        return "skip" if batch == 1 else None
    rows = rows_per_step(capture, slots, pool)
    if rows is None or kv_heads != int(config["num_key_value_heads"]):
        return None
    q_heads = int(config["num_attention_heads"])
    head = int(config.get("head_dim") or config["hidden_size"] // q_heads)
    return base.cost(rows, slots, q_heads, kv_heads, head,
                     base.DTYPE_BYTES[dtype])
