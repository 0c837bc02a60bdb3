"""One `_ssm_step` call is one state-space layer's decode step
(`dllama_tpu/ops/pallas/ssm_step.py`) over every slot of the batch: per
slot and head, S <- a S + dt x (outer) B and y = S C on the layer-stacked
state [layers, slots, heads, head, state] float32, in place.

The floor is the slots that ADVANCED, not the slots the state holds: a slot
without a request (or frozen) need not be read or written (the kernel
copies its block through today; that is its cost, not the floor's), as
`costs/paged_attention.py` prices rows needed and not pages touched. So a
call's bytes are (slots advanced in the step) x 2 x heads x head x state x
itemsize (each advancing slot's S read once and written once) + the step's
small operands over all slots (dt x [heads x head], B and C [state], the
decay [heads], y out [heads x head], float32); its FLOPs are 6 a state
element of an advancing slot (decay multiply, outer-product multiply-add,
the C multiply and its sum). Which slots advanced is not in the trace. The
program counts slot-steps where it builds the launch
(`dllama_slot_steps_total{state}`) and `/debug/perf` gives the counts of
the launches dispatched inside the capture (`capture`): advanced slot-steps
over steps prices every decode-shaped call of the trace.

A call whose batch is not the configuration's `serve.slots` is not a decode
step (the kernel serves only whole-batch steps today; a B = 1 call would be
a prefill slice): "skip", priced by nothing here.
"""

from __future__ import annotations

import re

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2}
DECODE_KINDS = ("decode", "decode_pen", "hybrid", "hybrid_pen")

# %_ssm_step.10 = (f32[48,64,64]{...}, f32[36,48,64,64,128]{...}) custom-call(
_RESULT = re.compile(r"= \(f32\[(\d+),(\d+),(\d+)\](?:\{[^}]*\})?, "
                     r"(\w+)\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")


def shape(trace_op: dict):
    """(slots, heads, head, state, dtype) of one traced call, from its HLO
    text: yT f32[slots, head, heads] and the stack [L, slots, heads, head,
    state]. None when the text does not parse."""
    m = _RESULT.search(trace_op["hlo"])
    if not m or m.group(4) not in DTYPE_BYTES:
        return None
    slots, head, heads = (int(m.group(i)) for i in (1, 2, 3))
    _, s_slots, s_heads, s_head, state = (int(m.group(i)) for i in range(5, 10))
    if (slots, heads, head) != (s_slots, s_heads, s_head):
        return None
    return slots, heads, head, state, m.group(4)


def advanced_per_step(capture: dict, slots: int):
    """Mean slots that advanced in one decode step, among the launches of
    the capture: advanced slot-steps over steps (a launch of n steps is
    n x slots slot-steps of one state or another). None when the capture
    holds no decode step or a kind of launch this does not price."""
    if not capture:
        return None
    launched = capture.get("launches") or {}
    if any(n > 0 for kind, n in launched.items()
           if kind not in DECODE_KINDS and kind != "prefill_chunk"):
        return None
    by_state = capture.get("slot_steps") or {}
    steps = sum(by_state.values()) / float(slots)
    if steps <= 0 or by_state.get("advanced", 0) <= 0:
        return None
    return by_state["advanced"] / steps


def cost(advanced: float, slots: int, heads: int, head: int, state: int,
         itemsize: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one call in which `advanced` of `slots` advance."""
    elements = advanced * heads * head * state
    small = slots * 4 * (2 * heads * head + 2 * state + heads)
    return 6.0 * elements, 2.0 * elements * itemsize + small


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced `_ssm_step` call -> (FLOPs, bytes), "skip" for a call that
    is not a whole-batch step, or None when nothing certain can be said."""
    got = shape(trace_op)
    slots = int(config["serve"]["slots"])
    if got is None or slots < 2:
        return None
    batch, heads, head, state, dtype = got
    if batch != slots:
        return "skip" if batch == 1 else None
    if (heads, head, state) != (int(config["mamba_n_heads"]),
                                int(config["mamba_d_head"]),
                                int(config["mamba_d_state"])):
        return None
    advanced = advanced_per_step(capture, slots)
    if advanced is None:
        return None
    return cost(advanced, slots, heads, head, state, DTYPE_BYTES[dtype])
