"""A gauge over another, polled through the window: `stat` (max | mean) of
num/den over the polls, times `scale`. params: `num`, `den`, `stat`,
`scale`."""


def reduce(params: dict, run: dict):
    vals = [m[params["num"]] / m[params["den"]] for _, m in run.get("polls") or ()
            if m.get(params["den"]) and params["num"] in m]
    if not vals:
        return None
    v = max(vals) if params.get("stat", "max") == "max" else sum(vals) / len(vals)
    return v * float(params.get("scale", 1.0))
