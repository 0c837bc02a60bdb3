"""One `_kda_step` call is one delta-rule (KDA) layer's decode step
(`dllama_tpu/ops/pallas/kda_step.py`) over every slot of the batch: per slot
and head, S' = exp(g) S, S = S' + beta k (v - S'^T k)^T and o = S^T q on the
layer-stacked state [layers, slots, heads, key, value] float32, in place.

As in `costs/ssm_step.py` the floor is the slots that ADVANCED, not the
slots the state holds: a call's bytes are (slots advanced in the step) x 2 x
heads x key x value x itemsize (each advancing slot's S read once and
written once) + the step's vectors over all slots (exp(g), k, beta k and q
[heads x key] each, v in and o out [heads x value], float32); its FLOPs are
7 a state element of an advancing slot (the decay multiply, two
multiply-adds over the key axis and the rank-one multiply-add): they never
bind. Which slots advanced is not in the trace; the program's slot-step
counts over the capture's launches (`/debug/perf` `capture`) price every
decode-shaped call, as `ssm_step.advanced_per_step` reads them.

The sizes are the configuration's `linear_attn_config` (`num_heads`,
`head_dim` for key and value alike). A call whose batch is not the
configuration's `serve.slots` is not a whole-batch step: "skip".
"""

from __future__ import annotations

import re

from benchmark.costs.ssm_step import DTYPE_BYTES, advanced_per_step

# %_kda_step.10 = (f32[48,32,128]{...}, f32[20,48,32,128,128]{...}) custom-call(
_RESULT = re.compile(r"= \(f32\[(\d+),(\d+),(\d+)\](?:\{[^}]*\})?, "
                     r"(\w+)\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")


def shape(trace_op: dict):
    """(slots, heads, key, value, dtype) of one traced call, from its HLO
    text: o f32[slots, heads, value] and the stack [L, slots, heads, key,
    value]. None when the text does not parse."""
    m = _RESULT.search(trace_op["hlo"])
    if not m or m.group(4) not in DTYPE_BYTES:
        return None
    slots, heads, value = (int(m.group(i)) for i in (1, 2, 3))
    _, s_slots, s_heads, key, s_value = (int(m.group(i)) for i in range(5, 10))
    if (slots, heads, value) != (s_slots, s_heads, s_value):
        return None
    return slots, heads, key, value, m.group(4)


def cost(advanced: float, slots: int, heads: int, key: int, value: int,
         itemsize: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call in which `advanced` of `slots` advance."""
    elements = advanced * heads * key * value
    vectors = slots * 4 * heads * (4 * key + 2 * value)
    return 7.0 * elements, 2.0 * elements * itemsize + vectors


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced `_kda_step` call -> (FLOPs, bytes), "skip" for a call that
    is not a whole-batch step, or None when nothing certain can be said."""
    got = shape(trace_op)
    slots = int(config["serve"]["slots"])
    if got is None or slots < 2:
        return None
    batch, heads, key, value, dtype = got
    if batch != slots:
        return "skip" if batch == 1 else None
    lin = config["linear_attn_config"]
    if (heads, key, value) != (int(lin["num_heads"]), int(lin["head_dim"]),
                               int(lin["head_dim"])):
        return None
    advanced = advanced_per_step(capture, slots)
    if advanced is None:
        return None
    return cost(advanced, slots, heads, key, value, DTYPE_BYTES[dtype])
