"""`costs/paged_attention_window.py` for a model whose attention layers'
QUERY HEADS go by the layer's kind: a `_paged_folded` call is one global
layer (the configuration's `num_attention_heads`), a `_paged_window` call
one windowed layer, whose head count is what the configuration's
`num_attention_heads_per_layer` gives on a `sliding_attention` layer. The
kv heads, the row bytes and the rows are the accepted file's: rows a call
NEEDS (global: position + 1; window: min(position + 1, window), the
program's count over the capture's launches), K and V rows of `Hkv * head *
itemsize` bytes each in one layer. Only the queries in and the result out,
and the FLOPs, scale with the kind's heads.
"""

from __future__ import annotations

from benchmark.costs import paged_attention as base
from benchmark.costs.paged_attention_window import POOL_OF, rows_per_step


def heads_of(config: dict, pool: str) -> int:
    """Query heads of the layers that keep their rows in `pool`."""
    if pool == "global":
        return int(config["num_attention_heads"])
    per_layer = [int(h) for h, t in zip(config["num_attention_heads_per_layer"],
                                        config["layer_types"])
                 if t == "sliding_attention"]
    if not per_layer or len(set(per_layer)) != 1:
        raise ValueError("one head count for the windowed layers")
    return per_layer[0]


def calls(config: dict, trace_op: dict, capture: dict):
    """One traced call of either name -> (FLOPs, bytes), "skip" for a
    prefill slice, None when nothing certain can be said."""
    pool = POOL_OF.get(trace_op["group"])
    got = base.shape(trace_op)
    slots = int(config["serve"]["slots"])
    if pool is None or got is None or slots < 2:
        return None
    batch, kv_heads, dtype = got
    if batch != slots:
        return "skip" if batch == 1 else None
    rows = rows_per_step(capture, slots, pool)
    if rows is None or kv_heads != int(config["num_key_value_heads"]):
        return None
    return base.cost(rows, slots, heads_of(config, pool), kv_heads,
                     int(config["head_dim"]), base.DTYPE_BYTES[dtype])
