"""Output tokens the clients received inside the window, per second of
window: all the work over all the time, whichever request it belonged to.
params: none."""


def reduce(params: dict, run: dict):
    t0, t1 = run["t0"], run["t1"]
    tokens = sum(k for r in run["records"] for t, k in r.events if t0 <= t < t1)
    return tokens / (t1 - t0) if tokens else None
