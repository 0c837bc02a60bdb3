"""A kernel's share of its roofline, in percent: the least time the chip
could take for the calls the trace shows (per call the larger of bytes /
peak bytes/s and FLOPs / peak FLOP/s, from `benchmark/costs/<cost>.py` and
peaks.json) over the summed device durations of those calls.

params: `match` (regular expression on the device op's group name), `cost` (a
module under costs/ whose `calls(config, trace_op) -> (flops, bytes) | None`
reckons one traced call from the configuration's shapes and what the trace
says of the call). Returns None when the trace names no such op or the cost
function cannot tell which call an op is: a share is never reported on a
guess."""

import importlib
import re


def reduce(params: dict, run: dict):
    pat = re.compile(params["match"])
    ops = [op for op in (run.get("trace") or {}).get("ops", ())
           if pat.search(op["group"])]
    if not ops:
        return None
    peaks = run["peaks"]()
    cost = importlib.import_module(f"benchmark.costs.{params['cost']}")
    least = spent = 0.0
    for op in ops:
        fb = cost.calls(run["config"], op)
        if fb is None:
            return None
        flops, nbytes = fb
        least += op["count"] * max(flops / peaks["bf16_flops_per_s"],
                                   nbytes / peaks["hbm_bytes_per_s"])
        spent += op["seconds"]
    return 100.0 * least / spent if spent > 0 else None
