"""The `.m` layout of a decoder whose layers are gated delta-rule linear
attention (KDA) or latent attention without rotation (MLA), over a leading
dense feed-forward layer and sigmoid-routed experts with a shared expert,
of which the file holds ONE CHIP'S SHARE (the program's `ArchType.LLAMA`
with the per-layer kind and feed-forward keys, `models/formats.py`;
published shape: huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct
config.json).

Tensors, in order: embedding f32 [vocab, dim]; per layer by its kind
  kda: kda_proj Q40 [3*inner + 2*rank + heads, dim], output rows q | k | v |
       fa | ga | beta side by side with a gain each; kda_conv_w f32 [3*inner,
       taps]; kda_fb, kda_gb Q40 [inner, rank]; kda_dt_bias f32 [inner];
       kda_a_log f32 [heads]; kda_norm f32 [head]; kda_o Q40 [dim, inner]
  mla: mla_q Q40 [heads*(nope+pe), dim]; mla_kva Q40 [rank+pe, dim];
       mla_kv_norm f32 [rank]; mla_kvb Q40 [heads*(nope+v), rank] (a head's
       rows: its nope key rows, then its value rows); mla_o Q40 [dim, heads*v]
  then a dense layer: w1 (gate), w2 (down), w3 (up) Q40 at `hidden_dim`;
  an expert layer: moe_gate f32 [experts routed among, dim]; moe_bias f32
       [experts routed among]; moe_w1 / moe_w3 Q40 [held, width, dim], moe_w2
       Q40 [held, dim, width]; shared_w1 / shared_w3 Q40 [shared*width, dim],
       shared_w2 Q40 [dim, shared*width]
  then both: rms_att, rms_ffn f32 [dim]
final_norm f32 [dim]; wcls Q40 [vocab, dim] (untied).

The header says what each layer is (1000 + i: 2 kda / 3 mla; 4000 + i: 1
dense feed-forward), the KDA and MLA sizes, the router's kind, scaling
factor and shared experts, and the share: `experts_held` of `n_experts`
from `expert_offset`.

Gains and draws (a configuration's `weights` block). The lessons of PR 33
and PR 36 are built in: the last `router_dims` dims of the residual stream
are written by NOTHING (the rows of kda_o, mla_o, w2, every expert's w2 and
the shared w2 that feed them have gain 0) and hold the token's own
features, +-`router_embedding_std` with a random sign a dim (zero mean over
the vocabulary). The MIXER norm's gain is 1 on those dims and 0 elsewhere:
q, k, v, the decay, the gates and the latent read the tokens themselves, so
no softmax mean is fed back and no common direction grows. The router's
rows are zero outside those dims and normal inside. Because every token
feature has the SAME magnitude, the normed features the router reads are
one common scalar times a sign vector, whatever that scalar rounds to in
bfloat16: a token's top k is a fixed function of the token, the same in the
program and in the float32 reference (no expert swapped on a near-tie), and
a random function over the experts, so a quarter of the rows land on any
quarter of the experts for every seed. `moe_bias` is uniform in
+-`router_bias`: it moves who is chosen and must stay out of the weights.
That guarantee holds for a bias of 0 only: top k of sigmoid(c z) + b is not
invariant to the common scalar c, and at bfloat16's rounding of c a bias of
+-0.002 swapped a row's eighth and ninth experts in one check of nine on the
chip (PERF.md section 6, PR 38), so the benchmark's configuration draws
none and the CPU tests hold the bias path in float32.
The feed-forward blocks and the head read the whole stream.

KDA: `kda_a_log` uniform in log [0.5, 2]; `kda_dt_bias` the inverse
softplus of a log-uniform [`kda_dt_lo`, `kda_dt_hi`] a channel: channels
whose memory runs from tens of rows to thousands, so a wrong state, slot
or reset, or a state held in fewer bits, shows in the logits. The beta rows
of kda_proj carry `kda_beta_gain`, the decay's inner rows `kda_f_gain`.
MLA: mla_q carries `attention_sharpness` (the scores' standard deviation).
`kda_out_gain`, `mla_out_gain`, `expert_gain` and `shared_gain` size what
each block adds to the stream.
"""

from __future__ import annotations

import numpy as np

from benchmark import files
from benchmark.files import Entry
from benchmark.layouts.granite_hybrid import _inverse_softplus, log_uniform
from benchmark.layouts.smallthinker import by_column, normal

#: what a configuration's `weights` block may set, and the defaults
WEIGHT_DEFAULTS = {"attention_sharpness": 1.0, "router_gain": 1.0,
                   "router_dims": 0, "router_embedding_std": 1.0,
                   "router_bias": 0.05, "kda_out_gain": 1.0,
                   "mla_out_gain": 1.0, "expert_gain": 1.0, "shared_gain": 1.0,
                   "kda_beta_gain": 1.0, "kda_f_gain": 1.0,
                   "kda_dt_lo": 2e-4, "kda_dt_hi": 0.1}

# header keys of the `.m` format (the program's models/config.HeaderKey)
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "n_experts": 7, "n_active_experts": 8,
      "vocab_size": 9, "seq_len": 10, "hidden_act": 11, "rope_theta": 12,
      "weight_type": 13, "rope_type": 18, "norm_epsilon_x1e12": 100,
      "kda_heads": 130, "kda_head_dim": 131, "kda_conv": 132, "kda_rank": 133,
      "kv_rank": 140, "nope_dim": 141, "pe_dim": 142, "v_dim": 143,
      "router_kind": 150, "routed_scale_x1e6": 151, "n_shared": 152,
      "experts_held": 153, "expert_offset": 154, "moe_hidden_dim": 155}
_KIND0, _FFN0 = 1000, 4000  # layer i: its kind / 1 = a dense feed-forward
ARCH_LLAMA, ACT_SILU, FT_Q40, ROPE_NONE = 0xABCD00, 1, 2, 3
KIND_KDA, KIND_MLA, ROUTER_SIGMOID = 2, 3, 1
_INTS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
         "n_experts", "n_active_experts", "vocab_size", "seq_len",
         "kda_heads", "kda_head_dim", "kda_conv", "kda_rank", "kv_rank",
         "nope_dim", "pe_dim", "v_dim", "n_shared", "experts_held",
         "expert_offset", "moe_hidden_dim")


def signs(magnitude: float):
    """An initialiser: +-magnitude, the sign drawn a value."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        return np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(
            np.float32) * np.float32(magnitude)
    return init


def shapes_of(config: dict) -> dict:
    """The file-level sizes of a configuration file (the published key
    names in, the `.m` header's names out). `num_experts` counts the
    experts HELD; `deployment.num_experts_published` those routed among."""
    lin = config["linear_attn_config"]
    if (config["moe_router_activation_func"] != "sigmoid"
            or not config["moe_renormalize"] or config["num_expert_group"] != 1
            or config["topk_group"] != 1 or config["moe_layer_freq"] != 1):
        raise ValueError("this layout is for a sigmoid router, renormalised, "
                         "one expert group, every layer past the dense ones "
                         "an expert layer")
    if (config.get("q_lora_rank") is not None or not config["mla_use_nope"]
            or config.get("rope_scaling") is not None
            or config["tie_word_embeddings"] or config["hidden_act"] != "silu"):
        raise ValueError("this layout holds unrotated latent attention with "
                         "no q-side low rank, SiLU and an untied head")
    n = int(config["num_hidden_layers"])
    full = set(lin["full_attn_layers"])
    kinds = []
    for i in range(1, n + 1):  # the config's layer numbers are 1-based
        if (i in full) == (i in set(lin["kda_layers"])):
            raise ValueError(f"layer {i} is not exactly one of kda / full")
        kinds.append(KIND_MLA if i in full else KIND_KDA)
    dep = config.get("deployment", {})
    held = int(config["num_experts"])
    routed = int(dep.get("num_experts_published", held))
    s = {"dim": int(config["hidden_size"]),
         "hidden_dim": int(config["intermediate_size"]), "n_layers": n,
         "n_heads": int(config["num_attention_heads"]),
         "n_kv_heads": int(config["num_key_value_heads"]),
         "n_experts": routed,
         "n_active_experts": int(config["num_experts_per_token"]),
         "vocab_size": int(config["vocab_size"]),
         "seq_len": int(config["model_max_length"]),
         "kda_heads": int(lin["num_heads"]), "kda_head_dim": int(lin["head_dim"]),
         "kda_conv": int(lin["short_conv_kernel_size"]),
         "kda_rank": int(lin["head_dim"]),
         "kv_rank": int(config["kv_lora_rank"]),
         "nope_dim": int(config["qk_nope_head_dim"]),
         "pe_dim": int(config["qk_rope_head_dim"]),
         "v_dim": int(config["v_head_dim"]),
         "n_shared": int(config["num_shared_experts"]),
         "experts_held": held if held != routed else 0,
         "expert_offset": int(dep.get("expert_offset", 0)),
         "moe_hidden_dim": int(config["moe_intermediate_size"]),
         "routed_scale": float(config["routed_scaling_factor"]),
         "rope_theta": float(config["rope_theta"]),
         "norm_epsilon": float(config["rms_norm_eps"]),
         "kinds": kinds,
         "dense_ffn": [int(i < int(config["first_k_dense_replace"]))
                       for i in range(n)]}
    return _derived(s)


def _derived(s: dict) -> dict:
    s["kda_inner"] = s["kda_heads"] * s["kda_head_dim"]
    s["kda_proj"] = 3 * s["kda_inner"] + 2 * s["kda_rank"] + s["kda_heads"]
    s["held"] = s["experts_held"] or s["n_experts"]
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
          (_K["weight_type"], FT_Q40), (_K["rope_type"], ROPE_NONE)]
    if abs(s["norm_epsilon"] - 1e-5) > 1e-12:
        kv.append((_K["norm_epsilon_x1e12"],
                   int(round(s["norm_epsilon"] * 1e12))))
    kv += [(_KIND0 + i, k) for i, k in enumerate(s["kinds"])]
    kv += [(_K[k], s[k]) for k in ("kda_heads", "kda_head_dim", "kda_conv",
                                   "kda_rank", "kv_rank", "nope_dim", "pe_dim",
                                   "v_dim", "n_shared")]
    if s["experts_held"]:
        kv += [(_K["experts_held"], s["experts_held"])]
        if s["expert_offset"]:
            kv += [(_K["expert_offset"], s["expert_offset"])]
    kv += [(_K["moe_hidden_dim"], s["moe_hidden_dim"]),
           (_K["router_kind"], ROUTER_SIGMOID),
           (_K["routed_scale_x1e6"], int(round(s["routed_scale"] * 1e6)))]
    return kv + [(_FFN0 + i, f) for i, f in enumerate(s["dense_ffn"])]


def tensor_plan(s: dict, weights: dict | None = None) -> list:
    """The tensors in on-disk order. `weights` matters to the writer alone:
    shapes and kinds do not depend on it."""
    w = {**WEIGHT_DEFAULTS, **(weights or {})}
    dim, width, e, held = s["dim"], s["moe_hidden_dim"], s["n_experts"], s["held"]
    inner, rank, heads = s["kda_inner"], s["kda_rank"], s["kda_heads"]
    rd = int(w["router_dims"])
    if rd:
        # the stream's last rd dims: written by nothing, read by the mixers
        # and the router, +-std with a random sign (module docstring)
        router = by_column(dim, None, rd,
                           normal(float(w["router_gain"]) / np.sqrt(rd)))
        embedding = by_column(dim, files.uniform(0.02), rd,
                              signs(float(w["router_embedding_std"])))
        out = lambda gain: ((dim - rd, float(gain)), (rd, 0.0))
        att_gain = by_column(dim, None, rd, files.ones)
    else:
        router = normal(float(w["router_gain"]) / np.sqrt(dim))
        embedding = files.uniform(0.02)
        out = lambda gain: float(gain)
        att_gain = files.ones
    expert_out = out(w["expert_gain"])
    dt_bias = log_uniform(float(w["kda_dt_lo"]), float(w["kda_dt_hi"]),
                          _inverse_softplus)
    taps = s["kda_conv"]
    plan = [Entry("embedding", (s["vocab_size"], dim), "f32", init=embedding)]
    for li in range(s["n_layers"]):
        p = f"layers.{li}."
        if s["kinds"][li] == KIND_KDA:
            plan += [
                Entry(p + "kda_proj", (s["kda_proj"], dim), "q40",
                      gain=((3 * inner, 1.0), (rank, float(w["kda_f_gain"])),
                            (rank, 1.0), (heads, float(w["kda_beta_gain"])))),
                # taps uniform with unit output variance on unit input
                Entry(p + "kda_conv_w", (3 * inner, taps), "f32",
                      init=files.uniform(np.sqrt(3.0 / taps))),
                Entry(p + "kda_fb", (inner, rank), "q40"),
                Entry(p + "kda_gb", (inner, rank), "q40"),
                Entry(p + "kda_dt_bias", (inner,), "f32", init=dt_bias),
                Entry(p + "kda_a_log", (heads,), "f32",
                      init=log_uniform(0.5, 2.0, np.log)),
                Entry(p + "kda_norm", (s["kda_head_dim"],), "f32", init=files.ones),
                Entry(p + "kda_o", (dim, inner), "q40", gain=out(w["kda_out_gain"])),
            ]
        else:
            h = s["n_heads"]
            plan += [
                Entry(p + "mla_q", (h * (s["nope_dim"] + s["pe_dim"]), dim), "q40",
                      gain=float(w["attention_sharpness"])),
                Entry(p + "mla_kva", (s["kv_rank"] + s["pe_dim"], dim), "q40"),
                Entry(p + "mla_kv_norm", (s["kv_rank"],), "f32", init=files.ones),
                Entry(p + "mla_kvb", (h * (s["nope_dim"] + s["v_dim"]), s["kv_rank"]),
                      "q40"),
                Entry(p + "mla_o", (dim, h * s["v_dim"]), "q40",
                      gain=out(w["mla_out_gain"])),
            ]
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
            or raw.get(_K["rope_type"]) != ROPE_NONE
            or raw.get(_K["router_kind"]) != ROUTER_SIGMOID):
        raise ValueError(f"{path}: this layout reads Q40 files of unrotated "
                         "layers over SiLU experts behind a sigmoid router")
    s = {k: raw.get(_K[k], 0) for k in _INTS}
    s["routed_scale"] = raw.get(_K["routed_scale_x1e6"], 1_000_000) / 1e6
    s["rope_theta"] = float(raw[_K["rope_theta"]])
    s["norm_epsilon"] = raw.get(_K["norm_epsilon_x1e12"], 10_000_000) / 1e12
    s["kinds"] = [raw[_KIND0 + i] for i in range(s["n_layers"])]
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
