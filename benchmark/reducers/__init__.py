"""One file per KIND of reduction: `reduce(params, run) -> number | None`.

`run` holds what one run collected: `records`, `t0`, `t1` (the window, on
time.monotonic()), `setup_s`, `before` / `after` (as the window opened and closed, each {"metrics": the
Prometheus families, "perf": /debug/perf, "compile": /debug/compile}),
`polls` ([(t, families)] through the window, traced runs only), `trace`
(the reduced profiler trace, traced runs only), `config`, `traffic`,
`peaks` (a call that gives this device's row of peaks.json, or stops
the run when the device is not in the table). A reducer that finds nothing to
read returns None and the harness leaves the metric out of the line. A new
metric of an existing kind is a JSON file under metrics/; a new kind is a
new file here.
"""


def weighted_quantile(pairs: list, q: float):
    """The q-th percentile (0-100) of (value, weight) pairs: the smallest
    value with at least q% of the weight at or below it. None if empty."""
    pairs = sorted(p for p in pairs if p[1] > 0)
    total = sum(w for _, w in pairs)
    if not pairs or total <= 0:
        return None
    need, acc = total * q / 100.0, 0.0
    for v, w in pairs:
        acc += w
        if acc >= need:
            return v
    return pairs[-1][0]
