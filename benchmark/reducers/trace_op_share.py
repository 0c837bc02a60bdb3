"""The share of the device's busy time, in percent, that a set of device
ops took in the capture: the summed SELF time of the ops picked, over the
trace's `busy_s`.

params: `match` (regular expression on the op's group name: a kernel's
custom call), `match_hlo` (optional regular expression on the op's HLO
text, shapes included: XLA ops have no names of their own, so a layer's
fusions are found by the shapes only that layer has). An op either picks
is counted once. Returns None without a trace, or where the trace names no
such op (a program that lacks the layer): a share is never reported as 0 on
a guess."""

import re


def reduce(params: dict, run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    by_group = re.compile(params["match"])
    by_hlo = re.compile(params["match_hlo"]) if params.get("match_hlo") else None
    picked = [op for op in trace.get("ops", ())
              if by_group.search(op["group"])
              or (by_hlo is not None and by_hlo.search(op["hlo"]))]
    if not any(by_group.search(op["group"]) for op in picked):
        return None
    planes = max(1, int(trace.get("device_planes") or 1))
    return 100.0 * sum(op["seconds"] for op in picked) / planes / trace["busy_s"]
