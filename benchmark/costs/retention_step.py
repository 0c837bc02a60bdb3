"""One `_retention_step` call is one power-retention layer's decode step
(`dllama_tpu/ops/pallas/retention_step.py`) over every slot of the batch: per
slot and kv head, S = exp(gamma) S + [v; 1] phi(k)^T and [y; n] = S phi(q)
for each of the kv head's query heads, on the layer-stacked state [layers,
slots, kv heads, rows, lanes] float32, in place.

What is priced is the SYMMETRIC state of the published size, whatever the
kernel holds: a kv head's state is (head + 1) x head (head + 1) / 2 float32
(129 x 8,256 at a head of 128: the values and the normaliser over the
products k_i k_j, i <= j), so a kernel that keeps whole tiles (136 x 8,320,
as this one does) reads at most 94% and one that kept the full head x head
square at most half. As in `costs/ssm_step.py` the floor is the slots that
ADVANCED, not the slots the state holds: a call's bytes are (slots advanced
in the step) x 2 x kv heads x (head + 1) x head (head + 1) / 2 x 4 (each
advancing slot's S read once and written once) + the step's vectors over all
slots (q [heads x head], k and v [kv heads x head], the decay [kv heads], y
and n out [heads x (head + 1)], float32); its FLOPs are 3 + 2 J a state
element of an advancing slot (the decay multiply, the rank-one multiply-add,
a multiply-add a query head of the group): they never bind. Which slots
advanced is not in the trace; the program's slot-step counts over the
capture's launches (`/debug/perf` `capture`) price every decode-shaped call,
as `ssm_step.advanced_per_step` reads them.

The sizes are the configuration's `num_attention_heads`,
`num_key_value_heads` and `head_dim`. A call whose batch is not the
configuration's `serve.slots` is not a whole-batch step: "skip".
"""

from __future__ import annotations

import re

from benchmark.costs.ssm_step import DTYPE_BYTES, advanced_per_step

# %_retention_step.3 = (f32[24,8,136,128]{...}, f32[10,24,8,136,8320]{...}) custom-call(
_RESULT = re.compile(r"= \(f32\[(\d+),(\d+),(\d+),\d+\](?:\{[^}]*\})?, "
                     r"(\w+)\[\d+,(\d+),(\d+),(\d+),(\d+)\]")


def shape(trace_op: dict):
    """(slots, kv heads, rows, lanes, dtype) of one traced call, from its
    HLO text: o f32[slots, kv heads, rows, 128] and the stack [L, slots, kv
    heads, rows, lanes]. None when the text does not parse."""
    m = _RESULT.search(trace_op["hlo"])
    if not m or m.group(4) not in DTYPE_BYTES:
        return None
    slots, groups, rows = (int(m.group(i)) for i in (1, 2, 3))
    s_slots, s_groups, s_rows, lanes = (int(m.group(i)) for i in range(5, 9))
    if (slots, groups, rows) != (s_slots, s_groups, s_rows):
        return None
    return slots, groups, rows, lanes, m.group(4)


def cost(advanced: float, slots: int, heads: int, groups: int, head: int,
         itemsize: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call in which `advanced` of `slots` advance."""
    elements = advanced * groups * (head + 1) * (head * (head + 1) // 2)
    vectors = slots * 4 * (heads * head + 2 * groups * head + groups
                           + heads * (head + 1))
    return ((3.0 + 2.0 * heads / groups) * elements,
            2.0 * elements * itemsize + vectors)


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced `_retention_step` call -> (FLOPs, bytes), "skip" for a call
    that is not a whole-batch step, or None when nothing certain can be said."""
    got = shape(trace_op)
    slots = int(config["serve"]["slots"])
    if got is None or slots < 2:
        return None
    batch, groups, rows, lanes, dtype = got
    if batch != slots:
        return "skip" if batch == 1 else None
    heads, head = int(config["num_attention_heads"]), int(config["head_dim"])
    # the state holds AT LEAST the published symmetric size
    if (groups != int(config["num_key_value_heads"]) or rows < head + 1
            or lanes < head * (head + 1) // 2):
        return None
    advanced = advanced_per_step(capture, slots)
    if advanced is None:
        return None
    return cost(advanced, slots, heads, groups, head, DTYPE_BYTES[dtype])
