"""One `_expert_call` is one projection (gate, up or down) of one expert
layer's forward call: the rows in expert order against the experts that
received a row, each expert's packed Q40 tile and scales read once from the
stacked [L, E, ...] weights (`dllama_tpu/ops/pallas/q40_matmul.py`).

The floor is the experts TOUCHED, never all of them: an expert no row was
routed to moves no bytes. Which experts a call touched is not in the trace.
The program counts, on the device, the experts with a row and the
token-expert rows of every expert layer's call and the calls themselves
(`dllama_moe_experts_touched_total`, `dllama_moe_assignments_total`,
`dllama_moe_layer_steps_total`), and `/debug/perf` gives the counts folded
in between the profiler's begin and end (`capture`). Every traced call is
priced at the capture's MEAN call: (experts touched a layer-step) x one
expert's packed bytes of this projection (18 B per 32 weights) + (rows a
layer-step) x the bf16 row in and the f32 row out; FLOPs 2 k n a row. The
share is a ratio of sums over all the traced calls, decode steps and prefill
slices alike, so the mean prices the sum exactly as far as the capture's
counts are the trace's calls (the counters are folded in when a launch's
tokens are fetched, one launch behind the device at either end).
"""

from __future__ import annotations

import re

Q_BLOCK, Q40_BLOCK_BYTES = 32, 18

# %_expert_call.73 = f32[1120,768]{...} custom-call(...), operand_layout_
# constraints={..., bf16[1120,2560]{1,0}, u8[24,64,1280,768]{...}, u16[...]}
_OUT = re.compile(r"= f32\[(\d+),(\d+)\]")
_PACKED = re.compile(r"u8\[(\d+),(\d+),(\d+),(\d+)\]")


def shape(trace_op: dict):
    """(experts, k, n) of one traced call, from its HLO text; None when the
    text does not parse."""
    out = _OUT.search(trace_op["hlo"])
    packed = _PACKED.search(trace_op["hlo"])
    if not out or not packed or out.group(2) != packed.group(4):
        return None
    return int(packed.group(2)), 2 * int(packed.group(3)), int(packed.group(4))


def per_layer_step(capture: dict):
    """(experts touched, token-expert rows) of the capture's mean expert
    layer call; None when the capture counted none."""
    def total(key):
        return sum(((capture or {}).get(key) or {}).values())

    steps = total("moe_layer_steps")
    if steps <= 0:
        return None
    return total("moe_experts_touched") / steps, total("moe_assignments") / steps


def cost(touched: float, rows: float, k: int, n: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one projection's call that reads `touched` experts
    for `rows` token-expert rows."""
    weights = touched * (k * n // Q_BLOCK * Q40_BLOCK_BYTES)
    return 2.0 * rows * k * n, weights + rows * (2 * k + 4 * n)


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced `_expert_call` -> (FLOPs, bytes), or None when nothing
    certain can be said (the text does not parse, the expert count is not
    the configuration's, the capture counted no expert layer)."""
    got = shape(trace_op)
    mean = per_layer_step(capture)
    if got is None or mean is None:
        return None
    experts, k, n = got
    if experts != int(config["moe_num_primary_experts"]):
        return None
    touched, rows = mean
    if touched > experts:  # more than there are: the counts are not these calls'
        return None
    return cost(touched, rows, k, n)
