"""(after - before) of one Prometheus family (all label sets summed) per
second of window. params: `family`. None where the program has no such
family."""


def reduce(params: dict, run: dict):
    fam = params["family"]
    before, after = run["before"]["metrics"], run["after"]["metrics"]
    seconds = run["t1"] - run["t0"]
    if fam not in after or seconds <= 0:
        return None
    return (after[fam] - before.get(fam, 0.0)) / seconds
