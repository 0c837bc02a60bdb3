"""The share of the device's busy time, in percent, that the XLA ops picked
by SHAPE took in the capture: like `trace_op_share`, for a set of ops that
holds no kernel of its own (the small ops between two kernels of a layer).

params: `match_hlo` (regular expression on the op's HLO text, shapes
included: XLA ops carry no scope names on the device plane, so a layer's
fusions are found by the shapes only that layer has), `exclude` (optional
regular expression on the op's group name: ops it matches are never
picked, e.g. `^_` for the kernels' custom calls, whose operands carry the
same shapes). Loops' own lines are left out (a `while` carries every array
its body touches). Returns None without a trace, or where no op is picked
(a program that lacks the layer): a share is never reported as 0 on a
guess."""

import re


def reduce(params: dict, run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    by_hlo = re.compile(params["match_hlo"])
    exclude = re.compile(params["exclude"]) if params.get("exclude") else None
    picked = [op for op in trace.get("ops", ())
              if op["group"] not in ("while", "conditional", "call")
              and not (exclude is not None and exclude.search(op["group"]))
              and by_hlo.search(op["hlo"])]
    if not picked:
        return None
    planes = max(1, int(trace.get("device_planes") or 1))
    return 100.0 * sum(op["seconds"] for op in picked) / planes / trace["busy_s"]
