"""The `.m` layout of the hybrid state-space / attention decoder (the
program's `ArchType.HYBRID_SSM`, `models/formats.py`): Mamba-2 mixers
beside a few attention layers, one SwiGLU MLP after each, scalar multipliers
and a head tied to the embedding. `benchmark/files.py` writes the plan; the
reference (`reference/granite_hybrid.py`) reads it back through
`tensor_views`. Source of the shape: the `granitemoehybrid` config of
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
(`num_local_experts` 0: no routed experts, the shared MLP is the MLP).

Tensors, in order: embedding f32 [vocab, dim]; per layer by its kind
  mamba:     in_proj Q40 [2*inner + 2*state + heads, dim], output rows
             z | x | B | C | dt side by side with a gain each; conv_w f32
             [inner + 2*state, taps]; conv_b f32 [inner + 2*state]; dt_bias
             f32 [heads]; a_log f32 [heads]; d f32 [heads]; ssm_norm f32
             [inner]; out_proj Q40 [dim, inner]
  attention: wq [dim, dim], wk, wv [kv_dim, dim], wo [dim, dim], Q40
  then both: w1 (gate), w2 (down), w3 (up) Q40; rms_att, rms_ffn f32 [dim]
final_norm f32 [dim]; wcls Q40 [vocab, dim] = the embedding, quantised.

Gains are set so that every block keeps its input's magnitude WITH the
model's multipliers applied: the embedding is uniform with standard
deviation 1 / embedding_multiplier (h0 has unit RMS); the three output
projections (wo, out_proj, w2) carry 1 / residual_multiplier; wq carries
`attention_sharpness` x (1/sqrt(head)) / attention_multiplier, so the
attention scores have standard deviation `attention_sharpness` as in the
LLAMA layout. The recurrence: A log-uniform in [1, 16], the time step
through the inverse softplus of a log-uniform [0.001, 0.1] (heads whose
memory runs from under one token to a thousand), D ones, conv taps uniform
with unit output variance: a wrong state, slot or reset shows in the
logits, which an all-ones recurrence would hide.
"""

from __future__ import annotations

import numpy as np

from benchmark import files
from benchmark.files import Entry

#: what a configuration's `weights` block may set, and the defaults
WEIGHT_DEFAULTS = {"attention_sharpness": 1.0,
                   "in_proj_gains": {"z": 1.0, "x": 1.0, "B": 2.0, "C": 2.0,
                                     "dt": 0.5}}

# header keys of the `.m` format (the program's models/config.HeaderKey)
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "n_experts": 7, "n_active_experts": 8,
      "vocab_size": 9, "seq_len": 10, "hidden_act": 11, "rope_theta": 12,
      "weight_type": 13, "rope_type": 18, "norm_epsilon_x1e12": 100,
      "head_size": 101, "attn_scale_x1e6": 102, "embedding_mult_x1e6": 103,
      "residual_mult_x1e6": 104, "logits_div_x1e6": 105, "tied_head": 106,
      "ssm_heads": 110, "ssm_head_dim": 111, "ssm_state": 112,
      "ssm_groups": 113, "ssm_conv": 114, "ssm_chunk": 115}
_KIND0 = 1000  # the kind of layer i is key 1000 + i: 0 attention, 1 mamba
ARCH_HYBRID_SSM, ACT_SILU, FT_Q40, ROPE_NONE = 0xABCD10, 1, 2, 3
KINDS = ("attention", "mamba")
_INTS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
         "vocab_size", "seq_len", "head_size", "ssm_heads", "ssm_head_dim",
         "ssm_state", "ssm_groups", "ssm_conv", "ssm_chunk")
_X1E6 = {"attn_scale": "attn_scale_x1e6",
         "embedding_multiplier": "embedding_mult_x1e6",
         "residual_multiplier": "residual_mult_x1e6",
         "logits_scaling": "logits_div_x1e6"}


def log_uniform(lo: float, hi: float, then=None):
    """exp(uniform(log lo, log hi)), and `then` of it."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        x = np.exp(rng.uniform(np.log(lo), np.log(hi), n))
        return (x if then is None else then(x)).astype(np.float32)
    return init


def _inverse_softplus(dt):
    return dt + np.log(-np.expm1(-dt))


def shapes_of(config: dict) -> dict:
    """The file-level sizes of a configuration file (HF key names in, the
    `.m` header's names out)."""
    if config.get("num_local_experts") or config.get("mamba_proj_bias"):
        raise ValueError("this layout holds no routed experts and no "
                         "projection bias")
    if config["position_embedding_type"] != "nope" or not config["tie_word_embeddings"]:
        raise ValueError("this layout is for an unrotated model with a tied head")
    dim = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    ssm_heads, ssm_head = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if ssm_heads * ssm_head != int(config["mamba_expand"]) * dim:
        raise ValueError("mamba heads x head size != expand x hidden size")
    kinds = [KINDS.index(k) for k in config["layer_types"]]
    s = {"dim": dim, "hidden_dim": int(config["shared_intermediate_size"]),
         "n_layers": int(config["num_hidden_layers"]), "n_heads": heads,
         "n_kv_heads": kv_heads, "vocab_size": int(config["vocab_size"]),
         "seq_len": int(config["max_position_embeddings"]),
         "head_size": dim // heads,
         "norm_epsilon": float(config["rms_norm_eps"]),
         "attn_scale": float(config["attention_multiplier"]),
         "embedding_multiplier": float(config["embedding_multiplier"]),
         "residual_multiplier": float(config["residual_multiplier"]),
         "logits_scaling": float(config["logits_scaling"]),
         "ssm_heads": ssm_heads, "ssm_head_dim": ssm_head,
         "ssm_state": int(config["mamba_d_state"]),
         "ssm_groups": int(config["mamba_n_groups"]),
         "ssm_conv": int(config["mamba_d_conv"]),
         "ssm_chunk": int(config["mamba_chunk_size"]), "kinds": kinds}
    if len(kinds) != s["n_layers"]:
        raise ValueError("layer_types does not name every layer")
    return _derived(s)


def _derived(s: dict) -> dict:
    s["kv_dim"] = s["n_kv_heads"] * s["head_size"]
    s["inner"] = s["ssm_heads"] * s["ssm_head_dim"]
    s["conv_dim"] = s["inner"] + 2 * s["ssm_groups"] * s["ssm_state"]
    return s


def header(s: dict) -> list:
    kv = [(_K["version"], 0), (_K["arch"], ARCH_HYBRID_SSM), (_K["dim"], s["dim"]),
          (_K["hidden_dim"], s["hidden_dim"]), (_K["n_layers"], s["n_layers"]),
          (_K["n_heads"], s["n_heads"]), (_K["n_kv_heads"], s["n_kv_heads"]),
          (_K["n_experts"], 0), (_K["n_active_experts"], 0),
          (_K["vocab_size"], s["vocab_size"]), (_K["seq_len"], s["seq_len"]),
          (_K["hidden_act"], ACT_SILU), (_K["rope_theta"], 10000),
          (_K["weight_type"], FT_Q40), (_K["rope_type"], ROPE_NONE)]
    if abs(s["norm_epsilon"] - 1e-5) > 1e-12:
        kv.append((_K["norm_epsilon_x1e12"], int(round(s["norm_epsilon"] * 1e12))))
    kv.append((_K["head_size"], s["head_size"]))
    kv += [(_K[key], int(round(s[name] * 1e6))) for name, key in _X1E6.items()]
    kv.append((_K["tied_head"], 1))
    kv += [(_K[k], s[k]) for k in _INTS if k.startswith("ssm_")]
    return kv + [(_KIND0 + i, k) for i, k in enumerate(s["kinds"])]


def tensor_plan(s: dict, weights: dict | None = None) -> list:
    """The tensors in on-disk order. `weights` matters to the writer alone:
    shapes and kinds do not depend on it."""
    w = {**WEIGHT_DEFAULTS, **(weights or {})}
    g = {**WEIGHT_DEFAULTS["in_proj_gains"], **w["in_proj_gains"]}
    dim, kv_dim, hidden = s["dim"], s["kv_dim"], s["hidden_dim"]
    inner, conv_dim, heads = s["inner"], s["conv_dim"], s["ssm_heads"]
    bc = s["ssm_groups"] * s["ssm_state"]
    blocks = (("z", inner), ("x", inner), ("B", bc), ("C", bc), ("dt", heads))
    out_gain = 1.0 / s["residual_multiplier"]
    q_gain = (float(w["attention_sharpness"])
              / (s["attn_scale"] * np.sqrt(s["head_size"])))
    emb = np.sqrt(3.0) / s["embedding_multiplier"]  # uniform: std = half / sqrt 3
    taps = np.sqrt(3.0 / s["ssm_conv"])  # unit variance out of unit variance in
    plan = [Entry("embedding", (s["vocab_size"], dim), "f32",
                  init=files.uniform(emb))]
    for li, kind in enumerate(s["kinds"]):
        p = f"layers.{li}."
        if KINDS[kind] == "mamba":
            plan += [
                Entry(p + "in_proj", (sum(r for _, r in blocks), dim), "q40",
                      gain=tuple((r, float(g[n])) for n, r in blocks)),
                Entry(p + "conv_w", (conv_dim, s["ssm_conv"]), "f32",
                      init=files.uniform(taps)),
                Entry(p + "conv_b", (conv_dim,), "f32", init=files.uniform(0.1)),
                Entry(p + "dt_bias", (heads,), "f32",
                      init=log_uniform(1e-3, 1e-1, then=_inverse_softplus)),
                Entry(p + "a_log", (heads,), "f32",
                      init=log_uniform(1.0, 16.0, then=np.log)),
                Entry(p + "d", (heads,), "f32", init=files.ones),
                Entry(p + "ssm_norm", (inner,), "f32", init=files.ones),
                Entry(p + "out_proj", (dim, inner), "q40", gain=out_gain)]
        else:
            plan += [Entry(p + "wq", (dim, dim), "q40", gain=q_gain),
                     Entry(p + "wk", (kv_dim, dim), "q40"),
                     Entry(p + "wv", (kv_dim, dim), "q40"),
                     Entry(p + "wo", (dim, dim), "q40", gain=out_gain)]
        plan += [Entry(p + "w1", (hidden, dim), "q40"),
                 Entry(p + "w2", (dim, hidden), "q40", gain=out_gain),
                 Entry(p + "w3", (hidden, dim), "q40"),
                 Entry(p + "rms_att", (dim,), "f32", init=files.ones),
                 Entry(p + "rms_ffn", (dim,), "f32", init=files.ones)]
    plan += [Entry("final_norm", (dim,), "f32", init=files.ones),
             Entry("wcls", (s["vocab_size"], dim), "q40",
                   derived_from="embedding")]
    return plan


def read_header(path: str) -> tuple[dict, int]:
    """(sizes as `shapes_of` names them, header bytes) of a `.m` file."""
    raw, size = files.parse_header(path)
    if raw.get(_K["arch"]) != ARCH_HYBRID_SSM or raw.get(_K["weight_type"]) != FT_Q40:
        raise ValueError(f"{path}: this layout reads Q40 hybrid state-space "
                         f"files only (arch {raw.get(_K['arch'], 0):#x})")
    s = {k: raw[_K[k]] for k in _INTS}
    s["norm_epsilon"] = raw.get(_K["norm_epsilon_x1e12"], 10_000_000) / 1e12
    for name, key in _X1E6.items():
        s[name] = raw[_K[key]] / 1e6
    s["kinds"] = [raw[_KIND0 + i] for i in range(s["n_layers"])]
    return _derived(s), size


def tensor_views(path: str) -> tuple[dict, dict]:
    """(sizes, {name: (uint8 memmap view, file shape, kind)}) of a `.m`."""
    s, offset = read_header(path)
    return s, files.views(path, offset, tensor_plan(s))
