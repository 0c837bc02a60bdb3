"""`costs/moe_experts.py` for a file that holds ONE CHIP'S SHARE of each
expert layer: an `_expert_call` walks the HELD experts' stacks, so the
expert count of its packed operand is the configuration's `num_experts`
(which counts the experts held; `deployment.num_experts_published` those
the router chooses among), and the program's touched / rows / longest-group
counters are already counted over the held experts alone
(`dllama_moe_rows_held_total` of `dllama_moe_rows_routed_total` rows landed
here). The pricing is the accepted file's: experts TOUCHED a layer-step x
one expert's packed bytes + the rows in and out.
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
    if experts != int(config["num_experts"]) or touched > experts:
        return None
    return base.cost(touched, rows, k, n)
