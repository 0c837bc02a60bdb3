"""`costs/moe_experts_held.py` for a configuration of the DeepSeek-V3
family's key names: the file holds ONE ROUTING GROUP of each expert layer,
so the expert count of an `_expert_call`'s packed operand is the
configuration's `n_routed_experts` (which counts the experts held;
`deployment.n_routed_experts_published` those the router chooses among in
`n_group` groups), and the program's touched / rows / longest-group counters
are counted over the held experts alone. The pricing is the accepted
file's: experts TOUCHED a layer-step x one expert's packed bytes + the rows
in and out.
"""

from __future__ import annotations

from benchmark.costs import moe_experts as base


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced `_expert_call` -> (FLOPs, bytes), or None when nothing
    certain can be said."""
    got = base.shape(trace_op)
    mean = base.per_layer_step(capture)
    if got is None or mean is None:
        return None
    experts, k, n = got
    touched, rows = mean
    if experts != int(config["n_routed_experts"]) or touched > experts:
        return None
    return base.cost(touched, rows, k, n)
