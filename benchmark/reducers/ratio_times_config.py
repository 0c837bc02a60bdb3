"""`ratio_of_deltas` times a number of the configuration file: sum of deltas
of `num` families / sum of deltas of `den` families, times `scale` (default
1), times the configuration's value at `times_config` (a key path, e.g.
["num_experts"]): where the factor is a size of the model, the metric's file
says where it comes from instead of writing the number. None when the
denominator did not move."""

from benchmark.reducers import ratio_of_deltas


def reduce(params: dict, run: dict):
    value = ratio_of_deltas.reduce(
        {k: v for k, v in params.items() if k != "times_config"}, run)
    if value is None:
        return None
    node = run["config"]
    for key in params["times_config"]:
        node = node[key]
    return value * float(node)
