"""The `.m` layout of a decoder whose attention layers are of two kinds and
whose feed-forward block is routed experts (the program's `ArchType.LLAMA`
with the per-layer window and rope keys, `models/formats.py`; published
shape: huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct config.json).

Tensors: embedding f32 [vocab, dim]; per layer wq [heads x head, dim], wk /
wv [kv heads x head, dim], wo [dim, heads x head] (Q40; heads x head need not
be dim), moe_gate f32 [experts, dim] (the router), moe_w1 / moe_w3 [experts,
width, dim] and moe_w2 [experts, dim, width] (Q40: gate, up and down of every
expert), rms_att / rms_ffn f32 [dim]; final_norm f32 [dim]; wcls Q40 [vocab,
dim] (untied). Header: the LLAMA keys, the head size, the window size, that
the router reads the attention norm's output, and for every layer whether it
is windowed (2000 + i) and whether it rotates (3000 + i).

Gains (a configuration's `weights` block): every matrix keeps its input's
magnitude but wq, `attention_sharpness` times larger (the standard deviation
of the scores), and wo, `attention_out_gain` times larger: over thousands of
keys a softmax of that sharpness averages v down to a few hundredths, and
without the gain the attention layers (their window, their rotation, their
cache) would be a rounding error beside the experts.

The router. Softmax attention over thousands of random keys returns mostly
the MEAN of v, the same vector for every query, and `attention_out_gain`
feeds it back: after a few layers the residual stream of every token of
every sequence is dominated by one common direction (on the chip, with
router rows drawn over the whole stream, all sixteen rows of a decode step
chose the same expert: 15 of 64 experts touched a step, longest group 9.6 x
the mean; PERF.md section 6, PR 36). A trained router is kept even by its
auxiliary loss; a random one on such a stream is not, and WHICH experts it
favours is the seed's choice, so the seed would set how many bytes a step
moves. `router_dims` > 0 takes the common direction away at its source: the
last `router_dims` dims of the stream are written by nothing (the rows of wo
and of every expert's w2 that feed them have gain 0) and hold the token's
own embedding, drawn normal with standard deviation `router_embedding_std`
there (zero mean over the vocabulary), and the ATTENTION norm's gain is 1 on
those dims and 0 on the others: q, k, v and the router read the tokens
themselves, so a layer's attention is a softmax mix of zero-mean token
features (of magnitude 1 / sqrt(keys that count), times
`attention_out_gain`) written into the other dims, which no attention layer
reads back: nothing is fed back, no common direction grows. The experts
and the head read the whole stream (their norms' gains are 1), so the
logits depend on the context through every attention layer and every
expert, and greedy decoding does not fall onto one token for every slot
(it did: with temperature 0 a common stream means a common argmax, all
slots emit the same token and a decode step touches 6 experts). The
router's rows are zero outside those dims and normal with standard
deviation `router_gain` / sqrt(router_dims) inside: a token's top 6 of 64 is
a fixed random function of the token, sixteen slots hold sixteen different
tokens, 50.7 of 64 experts touched a step whatever the seed. `router_dims`
0 (the default, the CPU rehearsals) draws everything over the whole stream
with unit norm gains.
"""

from __future__ import annotations

import numpy as np

from benchmark import files
from benchmark.files import Entry

#: what a configuration's `weights` block may set, and the defaults
WEIGHT_DEFAULTS = {"attention_sharpness": 1.0, "attention_out_gain": 1.0,
                   "router_gain": 1.0, "router_dims": 0,
                   "router_embedding_std": 1.0}

# header keys of the `.m` format (the program's models/config.HeaderKey)
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "n_experts": 7, "n_active_experts": 8,
      "vocab_size": 9, "seq_len": 10, "hidden_act": 11, "rope_theta": 12,
      "weight_type": 13, "norm_epsilon_x1e12": 100, "head_size": 101,
      "window": 120, "router_input": 121}
_WINDOW0, _ROPE0 = 2000, 3000  # layer i: windowed? / rotates?
ARCH_LLAMA, ACT_RELU, FT_Q40 = 0xABCD00, 2, 2
_INTS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
         "n_experts", "n_active_experts", "vocab_size", "seq_len",
         "head_size", "window")


def normal(std: float):
    """An initialiser: normal with standard deviation `std`."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        return (rng.standard_normal(n, np.float32) * np.float32(std))
    return init


def by_column(dim: int, head, tail_cols: int, tail):
    """An initialiser for a row-major [rows, dim] tensor: `head(rng, n)`
    draws the first dim - tail_cols columns of every row, `tail` the last
    tail_cols (None: zeros; either may be None)."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        rows = n // dim
        out = np.zeros((rows, dim), np.float32)
        if head is not None:
            out[:, :dim - tail_cols] = head(
                rng, rows * (dim - tail_cols)).reshape(rows, -1)
        if tail is not None and tail_cols:
            out[:, dim - tail_cols:] = tail(rng, rows * tail_cols).reshape(rows, -1)
        return out.reshape(-1)
    return init


def shapes_of(config: dict) -> dict:
    """The file-level sizes of a configuration file (the published key
    names in, the `.m` header's names out). The two per-layer lists are
    published at the full depth: a configuration cut to fewer layers keeps
    them whole and the first `num_hidden_layers` entries apply."""
    if not (config["moe_primary_router_apply_softmax"] and config["norm_topk_prob"]):
        raise ValueError("this layout is for a softmax router whose top-k "
                         "weights are renormalised")
    if config.get("rope_scaling") is not None or config["tie_word_embeddings"]:
        raise ValueError("this layout holds an untied head and unscaled rope")
    n = int(config["num_hidden_layers"])
    s = {"dim": int(config["hidden_size"]),
         "hidden_dim": int(config["moe_ffn_hidden_size"]), "n_layers": n,
         "n_heads": int(config["num_attention_heads"]),
         "n_kv_heads": int(config["num_key_value_heads"]),
         "n_experts": int(config["moe_num_primary_experts"]),
         "n_active_experts": int(config["moe_num_active_primary_experts"]),
         "vocab_size": int(config["vocab_size"]),
         "seq_len": int(config["max_position_embeddings"]),
         "head_size": int(config["head_dim"]),
         "window": int(config["sliding_window_size"]),
         "rope_theta": float(config["rope_theta"]),
         "norm_epsilon": float(config["rms_norm_eps"]),
         "windowed": [int(bool(w)) for w in config["sliding_window_layout"][:n]],
         "rotates": [int(bool(r)) for r in config["rope_layout"][:n]]}
    if len(s["windowed"]) != n or len(s["rotates"]) != n:
        raise ValueError("the layer lists do not name every layer")
    return _derived(s)


def _derived(s: dict) -> dict:
    s["attn_dim"] = s["n_heads"] * s["head_size"]
    s["kv_dim"] = s["n_kv_heads"] * s["head_size"]
    return s


def header(s: dict) -> list:
    kv = [(_K["version"], 0), (_K["arch"], ARCH_LLAMA), (_K["dim"], s["dim"]),
          (_K["hidden_dim"], s["hidden_dim"]), (_K["n_layers"], s["n_layers"]),
          (_K["n_heads"], s["n_heads"]), (_K["n_kv_heads"], s["n_kv_heads"]),
          (_K["n_experts"], s["n_experts"]),
          (_K["n_active_experts"], s["n_active_experts"]),
          (_K["vocab_size"], s["vocab_size"]), (_K["seq_len"], s["seq_len"]),
          (_K["hidden_act"], ACT_RELU),
          (_K["rope_theta"], int(s["rope_theta"])),
          (_K["weight_type"], FT_Q40)]
    if abs(s["norm_epsilon"] - 1e-5) > 1e-12:
        kv.append((_K["norm_epsilon_x1e12"],
                   int(round(s["norm_epsilon"] * 1e12))))
    kv += [(_K["head_size"], s["head_size"]), (_K["router_input"], 1),
           (_K["window"], s["window"])]
    kv += [(_WINDOW0 + i, w) for i, w in enumerate(s["windowed"])]
    return kv + [(_ROPE0 + i, r) for i, r in enumerate(s["rotates"])]


def tensor_plan(s: dict, weights: dict | None = None) -> list:
    """The tensors in on-disk order. `weights` matters to the writer alone:
    shapes and kinds do not depend on it."""
    w = {**WEIGHT_DEFAULTS, **(weights or {})}
    dim, width, e = s["dim"], s["hidden_dim"], s["n_experts"]
    rd = int(w["router_dims"])
    out_gain = float(w["attention_out_gain"])
    if rd:
        # the stream's last rd dims: written by nothing, read by the router
        router = by_column(dim, None, rd,
                           normal(float(w["router_gain"]) / np.sqrt(rd)))
        embedding = by_column(dim, files.uniform(0.02), rd,
                              normal(float(w["router_embedding_std"])))
        wo_gain = ((dim - rd, out_gain), (rd, 0.0))
        w2_gain = ((dim - rd, 1.0), (rd, 0.0)) * e
        att_gain = by_column(dim, None, rd, files.ones)
    else:
        router = normal(float(w["router_gain"]) / np.sqrt(dim))
        embedding, wo_gain, w2_gain = files.uniform(0.02), out_gain, 1.0
        att_gain = files.ones
    plan = [Entry("embedding", (s["vocab_size"], dim), "f32", init=embedding)]
    for li in range(s["n_layers"]):
        p = f"layers.{li}."
        plan += [Entry(p + "wq", (s["attn_dim"], dim), "q40",
                       gain=float(w["attention_sharpness"])),
                 Entry(p + "wk", (s["kv_dim"], dim), "q40"),
                 Entry(p + "wv", (s["kv_dim"], dim), "q40"),
                 Entry(p + "wo", (dim, s["attn_dim"]), "q40", gain=wo_gain),
                 Entry(p + "moe_gate", (e, dim), "f32", init=router),
                 Entry(p + "moe_w1", (e, width, dim), "q40"),
                 # [experts, dim, width] on disk; planned as its rows so
                 # that a gain can go by block of output rows (tensor_views gives the stack's shape back)
                 Entry(p + "moe_w2", (e * dim, width), "q40", gain=w2_gain),
                 Entry(p + "moe_w3", (e, width, dim), "q40"),
                 Entry(p + "rms_att", (dim,), "f32", init=att_gain),
                 Entry(p + "rms_ffn", (dim,), "f32", init=files.ones)]
    plan += [Entry("final_norm", (dim,), "f32", init=files.ones),
             Entry("wcls", (s["vocab_size"], dim), "q40")]
    return plan


def read_header(path: str) -> tuple[dict, int]:
    """(sizes as `shapes_of` names them, header bytes) of a `.m` file."""
    raw, size = files.parse_header(path)
    if (raw.get(_K["arch"]) != ARCH_LLAMA or raw.get(_K["weight_type"]) != FT_Q40
            or raw.get(_K["hidden_act"]) != ACT_RELU
            or raw.get(_K["router_input"]) != 1):
        raise ValueError(f"{path}: this layout reads Q40 files of ReLU-gated "
                         "experts whose router reads the attention norm")
    s = {k: raw[_K[k]] for k in _INTS}
    s["rope_theta"] = float(raw[_K["rope_theta"]])
    s["norm_epsilon"] = raw.get(_K["norm_epsilon_x1e12"], 10_000_000) / 1e12
    s["windowed"] = [raw[_WINDOW0 + i] for i in range(s["n_layers"])]
    s["rotates"] = [raw[_ROPE0 + i] for i in range(s["n_layers"])]
    return _derived(s), size


def tensor_views(path: str) -> tuple[dict, dict]:
    """(sizes, {name: (uint8 memmap view, file shape, kind)}) of a `.m`;
    an expert stack's file shape is [experts, out, in]."""
    s, offset = read_header(path)
    views = files.views(path, offset, tensor_plan(s))
    for name, (raw, shape, kind) in views.items():
        if name.endswith(".moe_w2"):
            views[name] = (raw, (s["n_experts"], s["dim"], shape[-1]), kind)
    return s, views
