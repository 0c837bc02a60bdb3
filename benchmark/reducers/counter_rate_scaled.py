"""`counter_rate` times `scale`: (after - before) of one Prometheus family
(all label sets summed) per second of window, in the unit the metric's file
names (bytes a second as GB a second: `scale` 1e-9). params: `family`,
`scale`. None where the program has no such family."""

from benchmark.reducers import counter_rate


def reduce(params: dict, run: dict):
    rate = counter_rate.reduce(params, run)
    return None if rate is None else rate * float(params["scale"])
