"""The `.m` layout of the LLAMA-arch decoder (the program's `ArchType.LLAMA`,
`models/formats.py`): which header keys, which tensors, in what order, with
which gains. `benchmark/files.py` writes the plan; the reference
(`reference/llama.py`) reads it back through `tensor_views`.

Tensors: embedding f32 [vocab, dim]; per layer wq [dim, dim], wk
[kv_dim, dim], wv [kv_dim, dim], wo [dim, dim], w1 [hidden, dim], w2
[dim, hidden], w3 [hidden, dim] (Q40), rms_att f32 [dim], rms_ffn f32
[dim]; final_norm f32 [dim]; wcls Q40 [vocab, dim]. Norm gains are 1, the
embedding is uniform in +-0.02, every matrix has unit gain but wq, which is
`attention_sharpness` times larger: the standard deviation of the attention
scores (a configuration's `weights` block sets it; PERF.md section 4 has
why 1.5 at 7B).
"""

from __future__ import annotations

from benchmark import files
from benchmark.files import Entry

#: what a configuration's `weights` block may set, and the default
WEIGHT_DEFAULTS = {"attention_sharpness": 1.0}

# header keys of the `.m` format (the program's models/config.HeaderKey)
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "n_experts": 7, "n_active_experts": 8,
      "vocab_size": 9, "seq_len": 10, "hidden_act": 11, "rope_theta": 12,
      "weight_type": 13, "norm_epsilon_x1e12": 100}
ARCH_LLAMA, ACT_SILU, FT_Q40 = 0xABCD00, 1, 2


def shapes_of(config: dict) -> dict:
    """The file-level sizes of a configuration file (HF key names in, the
    `.m` header's names out)."""
    dim = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kv_heads = int(config["num_key_value_heads"])
    return {"dim": dim, "hidden_dim": int(config["intermediate_size"]),
            "n_layers": int(config["num_hidden_layers"]), "n_heads": heads,
            "n_kv_heads": kv_heads, "vocab_size": int(config["vocab_size"]),
            "seq_len": int(config["max_position_embeddings"]),
            "rope_theta": float(config["rope_theta"]),
            "norm_epsilon": float(config["rms_norm_eps"]),
            "head_size": dim // heads, "kv_dim": dim * kv_heads // heads}


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
    return kv


def tensor_plan(s: dict, weights: dict | None = None) -> list:
    """The tensors in on-disk order. `weights` matters to the writer alone:
    shapes and kinds do not depend on it."""
    w = {**WEIGHT_DEFAULTS, **(weights or {})}
    dim, kv_dim, hidden = s["dim"], s["kv_dim"], s["hidden_dim"]
    plan = [Entry("embedding", (s["vocab_size"], dim), "f32",
                  init=files.uniform(0.02))]
    for li in range(s["n_layers"]):
        p = f"layers.{li}."
        plan += [Entry(p + "wq", (dim, dim), "q40",
                       gain=float(w["attention_sharpness"])),
                 Entry(p + "wk", (kv_dim, dim), "q40"),
                 Entry(p + "wv", (kv_dim, dim), "q40"),
                 Entry(p + "wo", (dim, dim), "q40"),
                 Entry(p + "w1", (hidden, dim), "q40"),
                 Entry(p + "w2", (dim, hidden), "q40"),
                 Entry(p + "w3", (hidden, dim), "q40"),
                 Entry(p + "rms_att", (dim,), "f32", init=files.ones),
                 Entry(p + "rms_ffn", (dim,), "f32", init=files.ones)]
    plan += [Entry("final_norm", (dim,), "f32", init=files.ones),
             Entry("wcls", (s["vocab_size"], dim), "q40")]
    return plan


def read_header(path: str) -> tuple[dict, int]:
    """(sizes as `shapes_of` names them, header bytes) of a `.m` file."""
    raw, size = files.parse_header(path)
    if raw.get(_K["arch"]) != ARCH_LLAMA or raw.get(_K["weight_type"]) != FT_Q40:
        raise ValueError(f"{path}: this layout reads Q40 LLAMA files only")
    names = {v: k for k, v in _K.items()}
    kv = {names[k]: v for k, v in raw.items()}
    s = {k: kv[k] for k in ("dim", "hidden_dim", "n_layers", "n_heads",
                            "n_kv_heads", "vocab_size", "seq_len")}
    s["rope_theta"] = float(kv["rope_theta"])
    s["norm_epsilon"] = kv.get("norm_epsilon_x1e12", 10_000_000) / 1e12
    s["head_size"] = s["dim"] // s["n_heads"]
    s["kv_dim"] = s["dim"] * s["n_kv_heads"] // s["n_heads"]
    return s, size


def tensor_views(path: str) -> tuple[dict, dict]:
    """(sizes, {name: (uint8 memmap view, file shape, kind)}) of a `.m`."""
    s, offset = read_header(path)
    return s, files.views(path, offset, tensor_plan(s))
