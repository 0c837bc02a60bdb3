"""The `.m` layout of a decoder whose every mixer is POWER RETENTION of
degree 2 on a Qwen3-shaped skeleton (the program's `ArchType.LLAMA` with
every layer `LayerKind.RETENTION`, `models/formats.py`; published shape:
huggingface.co/manifestai/Brumby-14B-Base config.json, `model_type` brumby).

Tensors, in order: embedding f32 [vocab, dim]; per layer
  wq Q40 [heads*hd, dim]; wk, wv Q40 [kv*hd, dim]; wo Q40 [dim, heads*hd];
  q_norm, k_norm f32 [hd] (the RMS norm over a head, before the rotation);
  ret_gate f32 [kv, dim]; ret_gate_bias f32 [kv] (the decay's gate, one a kv
  head: log decay = log sigmoid(n W_g + b_g));
  w1 (gate), w2 (down), w3 (up) Q40 at `hidden_dim`; rms_att, rms_ffn f32 [dim]
final_norm f32 [dim]; wcls Q40 [vocab, dim] (untied).

The header says that every layer is power retention (1000 + i: 4), its
degree (180: 2) and that it is gated (181), QK-norm (161), the head size
where heads x head size is not dim (101), and the norm's epsilon; ROPE_TYPE
is absent (q and k rotate over the whole head by the plain table of
ROPE_THETA).

Gains and draws (a configuration's `weights` block):

* The gate. b_g is the logit of a decay exp(-1 / tau) whose memory tau is
  log-uniform in [`gate_tau_lo`, `gate_tau_hi`] rows, one draw a kv head and
  layer: memories from tens to ten thousand rows lie side by side. W_g is
  normal with standard deviation `gate_gain` / sqrt(dim): the gate's input is
  a normed row (RMS 1), so a row moves its logit by about +-`gate_gain`
  around b_g (tau by that share), small beside the spread of the b_g.
* The kernel (q . k)^2 is homogeneous in q and in k: a gain on wq, wk or the
  head norms cancels between the weights and their sum, so there is no
  sharpness to set; the head norms' gains are 1.
* `ret_out_gain` on wo: a row's output is a mean of v rows under positive
  weights, so over a memory of n rows its RMS is about sqrt(3 / n) of v's
  (the weights are squares of near-normal scores, whose fourth moment is 3);
  the gain brings a layer's retention back to a few tenths of RMS at the
  cell's contexts, beside SwiGLU's few tenths at `ffn_out_gain`.
* WHICH token a greedy stream emits next is laid out as in `axk1.py`
  (`walk_embedding`, whose docstring has the why): the stream's last
  `token_dims` dims are written by NOTHING (wo and w2 have gain 0 on those
  output rows) and hold +-`token_std` with the signs of the head's row of
  the token's SUCCESSOR; the final norm's gain there is `head_token_gain`.
  Every norm inside the layers has gain 1 on every dim: the mixers read the
  token and what the layers wrote.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark import files
from benchmark.files import Entry
from benchmark.layouts.axk1 import successor, walk_embedding  # noqa: F401
from benchmark.layouts.smallthinker import by_column, normal

#: what a configuration's `weights` block may set, and the defaults
WEIGHT_DEFAULTS = {"ret_out_gain": 1.0, "ffn_out_gain": 1.0,
                   "gate_tau_lo": 16.0, "gate_tau_hi": 10000.0,
                   "gate_gain": 0.25, "token_dims": 0, "token_std": 1.0,
                   "head_token_gain": 1.0}

# header keys of the `.m` format (the program's models/config.HeaderKey)
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "n_experts": 7, "n_active_experts": 8,
      "vocab_size": 9, "seq_len": 10, "hidden_act": 11, "rope_theta": 12,
      "weight_type": 13, "rope_type": 18, "norm_epsilon_x1e12": 100,
      "head_size": 101, "qk_norm": 161, "ret_degree": 180, "ret_gate": 181}
_KIND0 = 1000  # layer i: its kind
ARCH_LLAMA, ACT_SILU, FT_Q40, KIND_RETENTION, DEGREE = 0xABCD00, 1, 2, 4, 2


def log_uniform_decay_logit(tau_lo: float, tau_hi: float):
    """An initialiser: logit(exp(-1 / tau)), tau log-uniform in
    [tau_lo, tau_hi] (the bias a sigmoid gate needs for that memory)."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        tau = np.exp(rng.uniform(np.log(tau_lo), np.log(tau_hi), n))
        decay = np.exp(-1.0 / tau)
        return np.log(decay / -np.expm1(-1.0 / tau)).astype(np.float32)
    return init


def shapes_of(config: dict) -> dict:
    """The file-level sizes of a configuration file (the published key
    names in, the `.m` header's names out)."""
    if (config["hidden_act"] != "silu" or config["tie_word_embeddings"]
            or config.get("attention_bias") or config.get("rope_scaling")
            or config.get("use_sliding_window")):
        raise ValueError("this layout holds SiLU, an untied head, no "
                         "attention bias, unscaled rope and no window")
    heads, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    return {"dim": int(config["hidden_size"]),
            "hidden_dim": int(config["intermediate_size"]),
            "n_layers": int(config["num_hidden_layers"]), "n_heads": heads,
            "n_kv_heads": int(config["num_key_value_heads"]),
            "vocab_size": int(config["vocab_size"]),
            "seq_len": int(config["max_position_embeddings"]),
            "head_size": hd, "rope_theta": float(config["rope_theta"]),
            "norm_epsilon": float(config["rms_norm_eps"])}


def header(s: dict) -> list:
    kv = [(_K["version"], 0), (_K["arch"], ARCH_LLAMA), (_K["dim"], s["dim"]),
          (_K["hidden_dim"], s["hidden_dim"]), (_K["n_layers"], s["n_layers"]),
          (_K["n_heads"], s["n_heads"]), (_K["n_kv_heads"], s["n_kv_heads"]),
          (_K["n_experts"], 0), (_K["n_active_experts"], 0),
          (_K["vocab_size"], s["vocab_size"]), (_K["seq_len"], s["seq_len"]),
          (_K["hidden_act"], ACT_SILU),
          (_K["rope_theta"], int(s["rope_theta"])),
          (_K["weight_type"], FT_Q40)]
    if abs(s["norm_epsilon"] - 1e-5) > 1e-12:
        kv.append((_K["norm_epsilon_x1e12"],
                   int(round(s["norm_epsilon"] * 1e12))))
    if s["n_heads"] * s["head_size"] != s["dim"]:
        kv.append((_K["head_size"], s["head_size"]))
    kv += [(_KIND0 + i, KIND_RETENTION) for i in range(s["n_layers"])]
    return kv + [(_K["qk_norm"], 1), (_K["ret_degree"], DEGREE),
                 (_K["ret_gate"], 1)]


def tensor_plan(s: dict, weights: dict | None = None) -> list:
    """The tensors in on-disk order. `weights` matters to the writer alone:
    shapes and kinds do not depend on it."""
    w = {**WEIGHT_DEFAULTS, **(weights or {})}
    dim, hidden, hd = s["dim"], s["hidden_dim"], s["head_size"]
    ad, kvd = s["n_heads"] * hd, s["n_kv_heads"] * hd
    td = int(w["token_dims"])
    # the stream's last td dims are written by nothing (module docstring)
    out = lambda gain: (((dim - td, float(gain)), (td, 0.0)) if td
                        else float(gain))
    gate = normal(float(w["gate_gain"]) / np.sqrt(dim))
    bias = log_uniform_decay_logit(float(w["gate_tau_lo"]),
                                   float(w["gate_tau_hi"]))
    plan = [Entry("embedding", (s["vocab_size"], dim), "f32",
                  init=files.uniform(0.02))]
    for li in range(s["n_layers"]):
        p = f"layers.{li}."
        plan += [Entry(p + "wq", (ad, dim), "q40"),
                 Entry(p + "wk", (kvd, dim), "q40"),
                 Entry(p + "wv", (kvd, dim), "q40"),
                 Entry(p + "wo", (dim, ad), "q40", gain=out(w["ret_out_gain"])),
                 Entry(p + "q_norm", (hd,), "f32", init=files.ones),
                 Entry(p + "k_norm", (hd,), "f32", init=files.ones),
                 Entry(p + "ret_gate", (s["n_kv_heads"], dim), "f32", init=gate),
                 Entry(p + "ret_gate_bias", (s["n_kv_heads"],), "f32", init=bias),
                 Entry(p + "w1", (hidden, dim), "q40"),
                 Entry(p + "w2", (dim, hidden), "q40", gain=out(w["ffn_out_gain"])),
                 Entry(p + "w3", (hidden, dim), "q40"),
                 Entry(p + "rms_att", (dim,), "f32", init=files.ones),
                 Entry(p + "rms_ffn", (dim,), "f32", init=files.ones)]
    # the head alone reads the final norm: its gain on the token dims sets
    # how far the successor's logit stands out of the row
    final_gain = lambda rng, n: np.full(n, w["head_token_gain"], np.float32)
    plan += [Entry("final_norm", (dim,), "f32",
                   init=by_column(dim, files.ones, td, final_gain)),
             Entry("wcls", (s["vocab_size"], dim), "q40")]
    if td:
        plan[0] = dataclasses.replace(plan[0], init=walk_embedding(
            dim, files.uniform(0.02), td, float(w["token_std"]),
            s["vocab_size"], len(plan) - 1, plan[-1]))
    return plan


def read_header(path: str) -> tuple[dict, int]:
    """(sizes as `shapes_of` names them, header bytes) of a `.m` file."""
    raw, size = files.parse_header(path)
    if (raw.get(_K["arch"]) != ARCH_LLAMA or raw.get(_K["weight_type"]) != FT_Q40
            or raw.get(_K["hidden_act"]) != ACT_SILU or _K["rope_type"] in raw
            or raw.get(_K["qk_norm"]) != 1 or raw.get(_K["ret_gate"]) != 1
            or raw.get(_K["ret_degree"]) != DEGREE):
        raise ValueError(f"{path}: this layout reads Q40 files of gated "
                         "power-retention layers of degree 2 with QK-norm "
                         "and plain rope over SiLU feed-forward blocks")
    s = {k: raw[_K[k]] for k in ("dim", "hidden_dim", "n_layers", "n_heads",
                                 "n_kv_heads", "vocab_size", "seq_len")}
    if any(raw.get(_KIND0 + i) != KIND_RETENTION for i in range(s["n_layers"])):
        raise ValueError(f"{path}: a layer that is not power retention")
    s["head_size"] = raw.get(_K["head_size"], s["dim"] // s["n_heads"])
    s["rope_theta"] = float(raw[_K["rope_theta"]])
    s["norm_epsilon"] = raw.get(_K["norm_epsilon_x1e12"], 10_000_000) / 1e12
    return s, size


def tensor_views(path: str) -> tuple[dict, dict]:
    """(sizes, {name: (uint8 memmap view, file shape, kind)}) of a `.m`."""
    s, offset = read_header(path)
    return s, files.views(path, offset, tensor_plan(s))
