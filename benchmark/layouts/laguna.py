"""The `.m` layout of a decoder whose attention goes by the layer's KIND:
global layers (the whole context, `num_attention_heads` query heads, a YaRN
rope over the leading half of a head) and windowed layers (a window of
`sliding_window` rows, heads of their own, a plain rope over the whole
head), both with an RMS norm over the head on q and k and a gate a head on
the attention output; over a leading dense feed-forward layer and
sigmoid-routed experts with a shared expert, of which the file holds ONE
CHIP'S SHARE (the program's `ArchType.LLAMA` with the per-kind attention
keys, `models/formats.py`; published shape:
huggingface.co/poolside/Laguna-XS.2 config.json).

Tensors, in order: embedding f32 [vocab, dim]; per layer, named by its kind
(a windowed layer's carry the suffix `_win`, so the program stacks the two
kinds apart):
  wq Q40 [heads x head, dim]; wk, wv Q40 [kv heads x head, dim]; wo Q40
  [dim, heads x head]; q_norm, k_norm f32 [head] (one gain vector each a
  layer, shared by its heads); attn_gate f32 [heads, dim]
  then a dense layer: w1 (gate), w2 (down), w3 (up) Q40 at `hidden_dim`;
  an expert layer: moe_gate f32 [experts routed among, dim]; moe_bias f32
       [experts routed among]; moe_w1 / moe_w3 Q40 [held, width, dim], moe_w2
       Q40 [held, dim, width]; shared_w1 / shared_w3 Q40 [shared width, dim],
       shared_w2 Q40 [dim, shared width]
  then both: rms_att, rms_ffn f32 [dim]
final_norm f32 [dim]; wcls Q40 [vocab, dim] (untied).

The header: the LLAMA keys with the WINDOWED layers' rope (theta, plain),
the head size, the window and which layers have it (2000 + i), which layers'
feed-forward is dense (4000 + i), the router (sigmoid, scaling factor,
shared experts, the share: `experts_held` of `n_experts` from
`expert_offset`, the expert width), the windowed layers' head count (160),
QK-norm (161), the gate (162) and the global layers' rope (170-177: type,
theta, rotated share, YaRN factor, original length, beta fast and slow,
attention factor; floats x 1e6).

Gains and draws (a configuration's `weights` block), with the lessons of
PR 33, PR 36 and PR 38 built in: the last `router_dims` dims of the
residual stream are written by NOTHING (the rows of wo, w2, every expert's
w2 and the shared w2 that feed them have gain 0) and hold the token's own
features, +-`router_embedding_std` with a random sign a dim. The ATTENTION
norm's gain is 1 on those dims and 0 elsewhere: q, k, v and the gate read
the tokens themselves, so no softmax mean is fed back. The router's rows are
zero outside those dims and normal inside; it reads the feed-forward norm's
output, whose gain is 1 everywhere, and what it reads of those dims is one
common scalar times a sign vector however that scalar rounds in bfloat16: a
token's top k is a fixed function of the token, the same in the program and
in the float32 reference, so WHICH experts a step meets, and its bytes, do
not depend on the seed. `moe_bias` is uniform in +-`router_bias` (0 in the
benchmark's configuration: PR 38's finding).

QK-norm erases wq's and wk's own magnitude, so the scores' sharpness is
drawn in the norm gains: `q_norm` and `k_norm` uniform in [`qk_gain_lo`,
`qk_gain_hi`] a dim (away from 1: a norm left out, or its gain, shows).
`attn_gate` rows are normal with `gate_gain` / sqrt(router_dims) on the
token features: the gate's argument has standard deviation about 1, so
softplus of it lies between 0.2 and 2 with mean 0.8, different for every
head and token (a gate read as 1, or per layer, shows). `attention_out_gain`
sizes what attention adds to the stream; `expert_gain` and `shared_gain`
the experts'.
"""

from __future__ import annotations

import numpy as np

from benchmark import files
from benchmark.files import Entry
from benchmark.layouts.kimi_linear import signs
from benchmark.layouts.smallthinker import by_column, normal

#: what a configuration's `weights` block may set, and the defaults
WEIGHT_DEFAULTS = {"attention_out_gain": 1.0, "router_gain": 1.0,
                   "router_dims": 0, "router_embedding_std": 1.0,
                   "router_bias": 0.05, "expert_gain": 1.0, "shared_gain": 1.0,
                   "qk_gain_lo": 0.75, "qk_gain_hi": 1.75, "gate_gain": 1.0}

# header keys of the `.m` format (the program's models/config.HeaderKey)
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "n_experts": 7, "n_active_experts": 8,
      "vocab_size": 9, "seq_len": 10, "hidden_act": 11, "rope_theta": 12,
      "weight_type": 13, "norm_epsilon_x1e12": 100, "head_size": 101,
      "window": 120, "router_kind": 150, "routed_scale_x1e6": 151,
      "n_shared": 152, "experts_held": 153, "expert_offset": 154,
      "moe_hidden_dim": 155, "window_heads": 160, "qk_norm": 161,
      "attn_gate": 162, "g_rope_type": 170, "g_rope_theta": 171,
      "g_rope_share_x1e6": 172, "g_rope_factor_x1e6": 173,
      "g_rope_orig_len": 174, "g_rope_beta_fast_x1e6": 175,
      "g_rope_beta_slow_x1e6": 176, "g_rope_attn_factor_x1e6": 177}
_WINDOW0, _FFN0 = 2000, 4000  # layer i: windowed? / 1 = a dense feed-forward
ARCH_LLAMA, ACT_SILU, FT_Q40, ROUTER_SIGMOID = 0xABCD00, 1, 2, 1
ROPE_PLAIN, ROPE_YARN = 0, 4
_INTS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
         "n_experts", "n_active_experts", "vocab_size", "seq_len", "head_size",
         "window", "n_shared", "experts_held", "expert_offset",
         "moe_hidden_dim", "window_heads")
#: the global layers' rope as the header codes it: name -> multiplier
_G_ROPE = {"type": 1, "theta": 1, "share": 1e6, "factor": 1e6, "orig_len": 1,
           "beta_fast": 1e6, "beta_slow": 1e6, "attn_factor": 1e6}


def between(lo: float, hi: float):
    """An initialiser: uniform in [lo, hi]."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        return (np.float32(lo) + rng.random(n, np.float32) * np.float32(hi - lo))
    return init


def shapes_of(config: dict) -> dict:
    """The file-level sizes of a configuration file (the published key
    names in, the `.m` header's names out). `num_experts` counts the
    experts HELD; `deployment.num_experts_published` those routed among."""
    n = int(config["num_hidden_layers"])
    types, ffn = config["layer_types"][:n], config["mlp_layer_types"][:n]
    per_layer = config["num_attention_heads_per_layer"][:n]
    if (len(types) != n or len(ffn) != n or len(per_layer) != n
            or set(types) - {"full_attention", "sliding_attention"}
            or set(ffn) - {"dense", "sparse"}):
        raise ValueError("the layer lists do not name every layer's kind")
    if (config["attention_bias"] or config["tie_word_embeddings"]
            or not config["gating"]
            or config["moe_apply_router_weight_on_input"]):
        raise ValueError("this layout holds gated attention without biases, "
                         "an untied head and router weights applied to the "
                         "experts' output")
    windowed = [int(t == "sliding_attention") for t in types]
    g_heads = int(config["num_attention_heads"])
    w_heads = {h for h, w in zip(per_layer, windowed) if w}
    if (any(h != g_heads for h, w in zip(per_layer, windowed) if not w)
            or len(w_heads) != 1):
        raise ValueError("one head count a layer kind: the global layers' "
                         "`num_attention_heads`, the windowed layers' own")
    rp = config["rope_parameters"]
    full, sliding = rp["full_attention"], rp["sliding_attention"]
    if (sliding["rope_type"] != "default" or sliding["partial_rotary_factor"] != 1
            or full["rope_type"] != "yarn"):
        raise ValueError("this layout is for windowed layers with a plain "
                         "rope over the whole head and global layers with YaRN")
    width = int(config["moe_intermediate_size"])
    if int(config["shared_expert_intermediate_size"]) % width:
        raise ValueError("the shared expert is whole experts' widths")
    dep = config.get("deployment", {})
    held = int(config["num_experts"])
    routed = int(dep.get("num_experts_published", held))
    s = {"dim": int(config["hidden_size"]),
         "hidden_dim": int(config["intermediate_size"]), "n_layers": n,
         "n_heads": g_heads, "window_heads": int(w_heads.pop()),
         "n_kv_heads": int(config["num_key_value_heads"]),
         "head_size": int(config["head_dim"]),
         "n_experts": routed,
         "n_active_experts": int(config["num_experts_per_tok"]),
         "vocab_size": int(config["vocab_size"]),
         "seq_len": int(config["max_position_embeddings"]),
         "window": int(config["sliding_window"]),
         "n_shared": int(config["shared_expert_intermediate_size"]) // width,
         "experts_held": held if held != routed else 0,
         "expert_offset": int(dep.get("expert_offset", 0)),
         "moe_hidden_dim": width,
         "routed_scale": float(config["moe_routed_scaling_factor"]),
         "rope_theta": float(sliding["rope_theta"]),
         "norm_epsilon": float(config["rms_norm_eps"]),
         "g_rope": {"type": ROPE_YARN, "theta": float(full["rope_theta"]),
                    "share": float(full["partial_rotary_factor"]),
                    "factor": float(full["factor"]),
                    "orig_len": int(full["original_max_position_embeddings"]),
                    "beta_fast": float(full["beta_fast"]),
                    "beta_slow": float(full["beta_slow"]),
                    "attn_factor": float(full["attention_factor"])},
         "windowed": windowed,
         "dense_ffn": [int(f == "dense") for f in ffn]}
    # the header codes the floats x 1e6: what a reader gets back
    s["g_rope"] = _decoded({f"g_rope_{k}" + ("_x1e6" if m != 1 else ""):
                            int(round(s["g_rope"][k] * m))
                            for k, m in _G_ROPE.items()})
    return _derived(s)


def _decoded(raw: dict) -> dict:
    """The global layers' rope from its header values, by name."""
    out = {}
    for k, m in _G_ROPE.items():
        v = raw[f"g_rope_{k}" + ("_x1e6" if m != 1 else "")]
        out[k] = v / m if m != 1 else (float(v) if k == "theta" else int(v))
    return out


def _derived(s: dict) -> dict:
    s["kv_dim"] = s["n_kv_heads"] * s["head_size"]
    s["held"] = s["experts_held"] or s["n_experts"]
    s["heads"] = [s["window_heads"] if w else s["n_heads"] for w in s["windowed"]]
    return s


def header(s: dict) -> list:
    kv = [(_K["version"], 0), (_K["arch"], ARCH_LLAMA), (_K["dim"], s["dim"]),
          (_K["hidden_dim"], s["hidden_dim"]), (_K["n_layers"], s["n_layers"]),
          (_K["n_heads"], s["n_heads"]), (_K["n_kv_heads"], s["n_kv_heads"]),
          (_K["n_experts"], s["n_experts"]),
          (_K["n_active_experts"], s["n_active_experts"]),
          (_K["vocab_size"], s["vocab_size"]), (_K["seq_len"], s["seq_len"]),
          (_K["hidden_act"], ACT_SILU),
          (_K["rope_theta"], int(s["rope_theta"])),
          (_K["weight_type"], FT_Q40)]
    if abs(s["norm_epsilon"] - 1e-5) > 1e-12:
        kv.append((_K["norm_epsilon_x1e12"],
                   int(round(s["norm_epsilon"] * 1e12))))
    kv += [(_K["head_size"], s["head_size"]), (_K["n_shared"], s["n_shared"])]
    if s["experts_held"]:
        kv += [(_K["experts_held"], s["experts_held"])]
        if s["expert_offset"]:
            kv += [(_K["expert_offset"], s["expert_offset"])]
    kv += [(_K["moe_hidden_dim"], s["moe_hidden_dim"]),
           (_K["router_kind"], ROUTER_SIGMOID),
           (_K["routed_scale_x1e6"], int(round(s["routed_scale"] * 1e6)))]
    kv += [(_FFN0 + i, f) for i, f in enumerate(s["dense_ffn"])]
    kv += [(_K["window"], s["window"])]
    kv += [(_WINDOW0 + i, w) for i, w in enumerate(s["windowed"])]
    kv += [(_K["window_heads"], s["window_heads"]), (_K["qk_norm"], 1),
           (_K["attn_gate"], 1)]
    return kv + [(_K[f"g_rope_{k}" + ("_x1e6" if m != 1 else "")],
                  int(round(s["g_rope"][k] * m))) for k, m in _G_ROPE.items()]


def tensor_plan(s: dict, weights: dict | None = None) -> list:
    """The tensors in on-disk order. `weights` matters to the writer alone:
    shapes and kinds do not depend on it."""
    w = {**WEIGHT_DEFAULTS, **(weights or {})}
    dim, width, e, held = s["dim"], s["moe_hidden_dim"], s["n_experts"], s["held"]
    head, kvd = s["head_size"], s["kv_dim"]
    rd = int(w["router_dims"])
    if rd:
        # the stream's last rd dims: written by nothing, read by attention,
        # the gate and the router, +-std with a random sign (module docstring)
        router = by_column(dim, None, rd,
                           normal(float(w["router_gain"]) / np.sqrt(rd)))
        gate = by_column(dim, None, rd,
                         normal(float(w["gate_gain"]) / np.sqrt(rd)))
        embedding = by_column(dim, files.uniform(0.02), rd,
                              signs(float(w["router_embedding_std"])))
        out = lambda gain: ((dim - rd, float(gain)), (rd, 0.0))
        att_gain = by_column(dim, None, rd, files.ones)
    else:
        router = normal(float(w["router_gain"]) / np.sqrt(dim))
        gate = normal(float(w["gate_gain"]) / np.sqrt(dim))
        embedding = files.uniform(0.02)
        out = lambda gain: float(gain)
        att_gain = files.ones
    expert_out = out(w["expert_gain"])
    qk_gain = between(float(w["qk_gain_lo"]), float(w["qk_gain_hi"]))
    plan = [Entry("embedding", (s["vocab_size"], dim), "f32", init=embedding)]
    for li in range(s["n_layers"]):
        p, sfx, heads = f"layers.{li}.", "_win" if s["windowed"][li] else "", s["heads"][li]
        plan += [Entry(p + "wq" + sfx, (heads * head, dim), "q40"),
                 Entry(p + "wk" + sfx, (kvd, dim), "q40"),
                 Entry(p + "wv" + sfx, (kvd, dim), "q40"),
                 Entry(p + "wo" + sfx, (dim, heads * head), "q40",
                       gain=out(w["attention_out_gain"])),
                 Entry(p + "q_norm" + sfx, (head,), "f32", init=qk_gain),
                 Entry(p + "k_norm" + sfx, (head,), "f32", init=qk_gain),
                 Entry(p + "attn_gate" + sfx, (heads, dim), "f32", init=gate)]
        if s["dense_ffn"][li]:
            plan += [Entry(p + "w1", (s["hidden_dim"], dim), "q40"),
                     Entry(p + "w2", (dim, s["hidden_dim"]), "q40", gain=out(1.0)),
                     Entry(p + "w3", (s["hidden_dim"], dim), "q40")]
        else:
            sw = s["n_shared"] * width
            plan += [
                Entry(p + "moe_gate", (e, dim), "f32", init=router),
                Entry(p + "moe_bias", (e,), "f32",
                      init=files.uniform(float(w["router_bias"]))),
                Entry(p + "moe_w1", (held, width, dim), "q40"),
                # [held, dim, width] on disk; planned as its rows so that a
                # gain can go by block of output rows
                Entry(p + "moe_w2", (held * dim, width), "q40",
                      gain=(expert_out * held if isinstance(expert_out, tuple)
                            else expert_out)),
                Entry(p + "moe_w3", (held, width, dim), "q40"),
                Entry(p + "shared_w1", (sw, dim), "q40"),
                Entry(p + "shared_w2", (dim, sw), "q40", gain=out(w["shared_gain"])),
                Entry(p + "shared_w3", (sw, dim), "q40"),
            ]
        plan += [Entry(p + "rms_att", (dim,), "f32", init=att_gain),
                 Entry(p + "rms_ffn", (dim,), "f32", init=files.ones)]
    plan += [Entry("final_norm", (dim,), "f32", init=files.ones),
             Entry("wcls", (s["vocab_size"], dim), "q40")]
    return plan


def read_header(path: str) -> tuple[dict, int]:
    """(sizes as `shapes_of` names them, header bytes) of a `.m` file."""
    raw, size = files.parse_header(path)
    if (raw.get(_K["arch"]) != ARCH_LLAMA or raw.get(_K["weight_type"]) != FT_Q40
            or raw.get(_K["hidden_act"]) != ACT_SILU
            or raw.get(_K["router_kind"]) != ROUTER_SIGMOID
            or raw.get(_K["qk_norm"]) != 1 or raw.get(_K["attn_gate"]) != 1
            or raw.get(_K["g_rope_type"]) != ROPE_YARN
            or raw.get(18, ROPE_PLAIN) != ROPE_PLAIN):
        raise ValueError(f"{path}: this layout reads Q40 files of gated, "
                         "QK-normed attention by layer kind (YaRN global, "
                         "plain windowed) over SiLU experts behind a sigmoid "
                         "router")
    s = {k: raw.get(_K[k], 0) for k in _INTS}
    s["routed_scale"] = raw.get(_K["routed_scale_x1e6"], 1_000_000) / 1e6
    s["rope_theta"] = float(raw[_K["rope_theta"]])
    s["norm_epsilon"] = raw.get(_K["norm_epsilon_x1e12"], 10_000_000) / 1e12
    s["g_rope"] = _decoded({k: raw[v] for k, v in _K.items()
                            if k.startswith("g_rope_")})
    s["windowed"] = [raw[_WINDOW0 + i] for i in range(s["n_layers"])]
    s["dense_ffn"] = [raw.get(_FFN0 + i, 0) for i in range(s["n_layers"])]
    return _derived(s), size


def tensor_views(path: str) -> tuple[dict, dict]:
    """(sizes, {name: (uint8 memmap view, file shape, kind)}) of a `.m`;
    an expert stack's file shape is [held, out, in]."""
    s, offset = read_header(path)
    views = files.views(path, offset, tensor_plan(s))
    for name, (raw, shape, kind) in views.items():
        if name.endswith(".moe_w2"):
            views[name] = (raw, (s["held"], s["dim"], shape[-1]), kind)
    return s, views
