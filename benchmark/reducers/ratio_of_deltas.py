"""sum of deltas of `num` families / sum of deltas of `den` families, times
`scale` (default 1), optionally divided by a number of the configuration
file (`divide_by_config`: a key path, e.g. ["serve", "slots"]). None when
the denominator did not move."""


def reduce(params: dict, run: dict):
    before, after = run["before"]["metrics"], run["after"]["metrics"]

    def delta(fams):
        return sum(after.get(f, 0.0) - before.get(f, 0.0) for f in fams)

    den = delta(params["den"])
    if den <= 0:
        return None
    value = delta(params["num"]) / den * float(params.get("scale", 1.0))
    path = params.get("divide_by_config")
    if path:
        node = run["config"]
        for key in path:
            node = node[key]
        value /= float(node)
    return value
