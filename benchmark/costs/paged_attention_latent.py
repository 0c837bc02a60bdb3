"""One `_paged_latent` call is one latent-attention layer's paged
flash-decode sweep (`dllama_tpu/ops/pallas/paged_attention.py`, `latent`)
over every slot of the batch: the pool's row is ONE latent a token,
`kv_lora_rank + qk_rope_head_dim` wide, shared by all query heads and read
ONCE for the scores and for the mix.

The floor is the rows the call NEEDS at their published width (the pool
pads a row to whole 128-lane vectors: that is the kernel's cost, not the
floor's): a decode step of a slot at position p reads p + 1 rows of
`(rank + pe) * itemsize` bytes (1,152 B at the published sizes in
bfloat16), plus the absorbed queries in (bf16, heads x (rank + pe) a slot)
and the latent mix out (f32, heads x rank a slot). Its FLOPs are the
absorbed products': 2 x (rank + pe) for the score and 2 x rank for the mix,
a row and a query head. Which rows a step read is not in the trace: the
program counts them where it builds the launch
(`dllama_launch_kv_rows_read_total{kind, pool="latent"}`) and `/debug/perf`
gives the counts of the launches dispatched inside the capture; the mean
over those launches' decode steps prices every decode-shaped call, as
`paged_attention_window.rows_per_step` reads them. A call whose batch is
not the configuration's `serve.slots` is a prefill slice: "skip".
"""

from __future__ import annotations

import re

from benchmark.costs.paged_attention import DTYPE_BYTES
from benchmark.costs.paged_attention_window import rows_per_step

# %_paged_latent.3 = (f32[48,1,32,640]{...}, bf16[3199,1,128,640]{...}, ...
_RESULT = re.compile(r"= \(f32\[(\d+),1,(\d+),(\d+)\](?:\{[^}]*\})?, "
                     r"(\w+)\[(\d+),1,(\d+),(\d+)\]")


def shape(trace_op: dict):
    """(batch, folded q rows, pool dtype) of one traced call, from its HLO
    text; None when the text does not parse."""
    m = _RESULT.search(trace_op["hlo"])
    if not m or m.group(4) not in DTYPE_BYTES or m.group(3) != m.group(7):
        return None
    return int(m.group(1)), int(m.group(2)), m.group(4)


def cost(rows: float, slots: int, heads: int, rank: int, pe: int,
         itemsize: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call whose slots read `rows` latent rows."""
    q_and_out = slots * heads * ((rank + pe) * 2 + rank * 4)
    return (rows * heads * (2.0 * (rank + pe) + 2.0 * rank),
            rows * (rank + pe) * itemsize + q_and_out)


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced `_paged_latent` call -> (FLOPs, bytes), "skip" for a
    prefill slice, None when nothing certain can be said."""
    got = shape(trace_op)
    slots = int(config["serve"]["slots"])
    if got is None or slots < 2:
        return None
    batch, q_rows, dtype = got
    if batch != slots:
        return "skip" if batch == 1 else None
    heads = int(config["num_attention_heads"])
    rows = rows_per_step(capture, slots, "latent")
    if rows is None or q_rows < heads:
        return None
    return cost(rows, slots, heads, int(config["kv_lora_rank"]),
                int(config["qk_rope_head_dim"]), DTYPE_BYTES[dtype])
