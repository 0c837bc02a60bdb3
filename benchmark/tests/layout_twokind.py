"""A second, TEST-ONLY model-file layout: proof that an architecture is new
files and no edit. Never a cell and never served (the program has one
`ArchType`); `test_second_architecture.py` writes it, reads it back and
resolves it through `run.py`.

Layers of two kinds in a repeating pattern (`layer_pattern`, e.g. "ssa":
two state-space-like layers, then one attention layer), one shared MLP
after each, a head tied to the embedding, scalar multipliers in the header:

  embedding f32 [vocab, dim], normal(0, 0.02)
  kind "s": in_proj Q40 [2*inner + 2*state + heads, dim] whose output rows
            are five projections side by side (z | x | B | C | dt) with a
            gain each; conv f32 [inner + 2*state, taps], normal; a_log f32
            [heads], log of uniform 1..16; dt_bias f32 [heads], the inverse
            softplus of a log-uniform time step; norm f32 [inner], ones;
            out_proj Q40 [dim, inner]
  kind "a": wq [dim, dim] (gain `attention_sharpness`), wk, wv [kv_dim,
            dim], wo [dim, dim], all Q40
  every layer then: w1, w3 Q40 [hidden, dim], w2 Q40 [dim, hidden],
            rms_in, rms_mlp f32 [dim], ones
  final_norm f32 [dim]; wcls Q40 [vocab, dim] = the embedding, quantised
"""

from __future__ import annotations

import numpy as np

from benchmark import files
from benchmark.files import Entry

ARCH_TWOKIND = 0xABCD7E
WEIGHT_DEFAULTS = {"attention_sharpness": 1.0,
                   "in_proj_gains": {"z": 1.0, "x": 1.0, "B": 1.0, "C": 1.0,
                                     "dt": 1.0}}
# header keys of this layout's own (the kinds of layer are one key each, in
# order, from 1000 on: 0 = "s", 1 = "a")
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "vocab_size": 9, "seq_len": 10,
      "state": 200, "inner": 201, "ssm_heads": 202, "conv_taps": 203,
      "embedding_multiplier_x1e6": 210, "logits_divisor_x1e6": 211}
_KIND0 = 1000
_INTS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
         "vocab_size", "seq_len", "state", "inner", "ssm_heads", "conv_taps")


def normal(std: float):
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        return (rng.standard_normal(n, np.float32) * np.float32(std))
    return init


def log_uniform(lo: float, hi: float, then=None):
    """exp(uniform(log lo, log hi)), and `then` of it."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        x = np.exp(rng.uniform(np.log(lo), np.log(hi), n))
        return (x if then is None else then(x)).astype(np.float32)
    return init


def shapes_of(config: dict) -> dict:
    dim = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    n_layers = int(config["num_hidden_layers"])
    pattern = config["layer_pattern"]
    return {"dim": dim, "hidden_dim": int(config["intermediate_size"]),
            "n_layers": n_layers, "n_heads": heads, "n_kv_heads": kv_heads,
            "vocab_size": int(config["vocab_size"]),
            "seq_len": int(config["max_position_embeddings"]),
            "state": int(config["state_size"]),
            "inner": int(config["inner_size"]),
            "ssm_heads": int(config["ssm_heads"]),
            "conv_taps": int(config["conv_taps"]),
            "embedding_multiplier": float(config["embedding_multiplier"]),
            "logits_divisor": float(config["logits_divisor"]),
            "kinds": [pattern[i % len(pattern)] for i in range(n_layers)],
            "kv_dim": dim * kv_heads // heads}


def header(s: dict) -> list:
    kv = [(_K["version"], 0), (_K["arch"], ARCH_TWOKIND)]
    kv += [(_K[k], s[k]) for k in _INTS]
    kv += [(_K["embedding_multiplier_x1e6"], round(s["embedding_multiplier"] * 1e6)),
           (_K["logits_divisor_x1e6"], round(s["logits_divisor"] * 1e6))]
    return kv + [(_KIND0 + i, "sa".index(k)) for i, k in enumerate(s["kinds"])]


def tensor_plan(s: dict, weights: dict | None = None) -> list:
    w = {**WEIGHT_DEFAULTS, **(weights or {})}
    g = {**WEIGHT_DEFAULTS["in_proj_gains"], **w["in_proj_gains"]}
    dim, hidden, kv_dim = s["dim"], s["hidden_dim"], s["kv_dim"]
    inner, state, heads = s["inner"], s["state"], s["ssm_heads"]
    blocks = (("z", inner), ("x", inner), ("B", state), ("C", state),
              ("dt", heads))
    plan = [Entry("embedding", (s["vocab_size"], dim), "f32", init=normal(0.02))]
    for li, kind in enumerate(s["kinds"]):
        p = f"layers.{li}."
        if kind == "s":
            plan += [
                Entry(p + "in_proj", (sum(r for _, r in blocks), dim), "q40",
                      gain=tuple((r, float(g[n])) for n, r in blocks)),
                Entry(p + "conv", (inner + 2 * state, s["conv_taps"]), "f32",
                      init=normal(0.5)),
                Entry(p + "a_log", (heads,), "f32",
                      init=log_uniform(1.0, 16.0, then=np.log)),
                Entry(p + "dt_bias", (heads,), "f32",  # softplus^-1(dt)
                      init=log_uniform(1e-3, 1e-1,
                                       then=lambda dt: dt + np.log(-np.expm1(-dt)))),
                Entry(p + "norm", (inner,), "f32", init=files.ones),
                Entry(p + "out_proj", (dim, inner), "q40")]
        else:
            plan += [Entry(p + "wq", (dim, dim), "q40",
                           gain=float(w["attention_sharpness"])),
                     Entry(p + "wk", (kv_dim, dim), "q40"),
                     Entry(p + "wv", (kv_dim, dim), "q40"),
                     Entry(p + "wo", (dim, dim), "q40")]
        plan += [Entry(p + "w1", (hidden, dim), "q40"),
                 Entry(p + "w2", (dim, hidden), "q40"),
                 Entry(p + "w3", (hidden, dim), "q40"),
                 Entry(p + "rms_in", (dim,), "f32", init=files.ones),
                 Entry(p + "rms_mlp", (dim,), "f32", init=files.ones)]
    plan += [Entry("final_norm", (dim,), "f32", init=files.ones),
             Entry("wcls", (s["vocab_size"], dim), "q40",
                   derived_from="embedding")]
    return plan


def read_header(path: str) -> tuple[dict, int]:
    raw, size = files.parse_header(path)
    if raw.get(_K["arch"]) != ARCH_TWOKIND:
        raise ValueError(f"{path}: not a two-kind file (arch "
                         f"{raw.get(_K['arch'], 0):#x})")
    s = {k: raw[_K[k]] for k in _INTS}
    s["embedding_multiplier"] = raw[_K["embedding_multiplier_x1e6"]] / 1e6
    s["logits_divisor"] = raw[_K["logits_divisor_x1e6"]] / 1e6
    s["kinds"] = ["sa"[raw[_KIND0 + i]] for i in range(s["n_layers"])]
    s["kv_dim"] = s["dim"] * s["n_kv_heads"] // s["n_heads"]
    return s, size


def tensor_views(path: str) -> tuple[dict, dict]:
    s, offset = read_header(path)
    return s, files.views(path, offset, tensor_plan(s))
