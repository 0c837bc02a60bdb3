"""after - before of one Prometheus family (all label sets summed).
params: `family`."""


def reduce(params: dict, run: dict):
    fam = params["family"]
    before, after = run["before"]["metrics"], run["after"]["metrics"]
    if fam not in after:
        return None
    return after[fam] - before.get(fam, 0.0)
