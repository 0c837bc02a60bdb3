"""A kernel's share of its roofline where the kernel's bytes depend on what
the program was doing, which the trace does not say: like
`trace_call_roofline`, but the cost file is also given the `capture` block
of `/debug/perf` as the window closed: the program's own count of what it
launched while the profiler ran (launches by kind, slot-steps by state, KV
rows by kind).

params: `match` (regular expression on the device op's group name), `cost`
(a module under costs/ with `calls(config, trace_op, capture) -> (flops,
bytes) | "skip" | None`). "skip" leaves a call out of both sides (a call
the cost file knows is not the priced kind); None from any call, no such
op in the trace, or no capture block (a program that has none) gives None:
a share is never reported on a guess."""

import importlib
import re


def reduce(params: dict, run: dict):
    pat = re.compile(params["match"])
    ops = [op for op in (run.get("trace") or {}).get("ops", ())
           if pat.search(op["group"])]
    capture = ((run.get("after") or {}).get("perf") or {}).get("capture")
    if not ops or not capture:
        return None
    cost = importlib.import_module(f"benchmark.costs.{params['cost']}")
    priced = []
    for op in ops:
        fb = cost.calls(run["config"], op, capture)
        if fb is None:
            return None
        if fb != "skip":
            priced.append((op, fb))
    if not priced:
        return None
    peaks = run["peaks"]()
    least = spent = 0.0
    for op, (flops, nbytes) in priced:
        least += op["count"] * max(flops / peaks["bf16_flops_per_s"],
                                   nbytes / peaks["hbm_bytes_per_s"])
        spent += op["seconds"]
    return 100.0 * least / spent if spent > 0 else None
