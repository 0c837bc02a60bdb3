"""A percentile of the device durations of the XLA modules (whole step
programs) whose name matches `match` (a regular expression), from the
profiler trace's device plane. params: `match`, `q`."""

import re

from benchmark.reducers import weighted_quantile


def reduce(params: dict, run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    pat = re.compile(params["match"])
    pairs = [(d * 1e3, 1) for name, durs in trace["modules"].items()
             if pat.search(name) for d in durs]
    return weighted_quantile(pairs, float(params["q"]))
