"""The `.m` layout of a DeepSeek-V3-shaped decoder: ROTATED latent attention
(MLA with a q-side low rank, YaRN over the shared key dims) in every layer,
a leading dense feed-forward layer, then sigmoid-routed experts chosen by
GROUP-LIMITED selection beside a shared expert, of which the file holds ONE
CHIP'S SHARE (the program's `ArchType.LLAMA` with the per-layer kind and
feed-forward keys, `models/formats.py`; published shape:
huggingface.co/skt/A.X-K1 config.json, `model_type` axk1).

Tensors, in order: embedding f32 [vocab, dim]; per layer
  mla_qa Q40 [q_rank, dim]; mla_q_norm f32 [q_rank]; mla_qb Q40
       [heads*(nope+pe), q_rank]; mla_kva Q40 [rank+pe, dim]; mla_kv_norm f32
       [rank]; mla_kvb Q40 [heads*(nope+v), rank] (a head's rows: its nope key
       rows, then its value rows); mla_o Q40 [dim, heads*v]
  then a dense layer: w1 (gate), w2 (down), w3 (up) Q40 at `hidden_dim`;
  an expert layer: moe_gate f32 [experts routed among, dim]; moe_bias f32
       [experts routed among]; moe_w1 / moe_w3 Q40 [held, width, dim], moe_w2
       Q40 [held, dim, width]; shared_w1 / shared_w3 Q40 [shared*width, dim],
       shared_w2 Q40 [dim, shared*width]
  then both: rms_att, rms_ffn f32 [dim]
final_norm f32 [dim]; wcls Q40 [vocab, dim] (untied).

The header says that every layer is latent attention (1000 + i: 3), which
feed-forward blocks are dense (4000 + i), the MLA sizes with the q-side
rank (144), the router's kind, scaling factor and shared experts, the
expert groups and the groups kept (156, 157), the share (`experts_held` of
`n_experts` from `expert_offset`), and the rope: ROPE_TYPE absent (the
model rotates), and where the configuration has a `yarn` block the
layers' own table (keys 170-177: YaRN over the whole of the shared key
dims, cos and sin multiplied by mscale(factor, mscale) / mscale(factor,
mscale_all_dim)) and the score scale (102) (nope + pe)^-1/2 x
mscale(factor, mscale_all_dim)^2, mscale(f, m) = 0.1 m ln f + 1.

Gains and draws (a configuration's `weights` block) are those of
`kimi_linear.py`, whose lessons hold here: the last `router_dims` dims of
the residual stream are written by NOTHING and hold the token's own
features, +-`router_embedding_std` with a random sign a dim; the MIXER
norm's gain is 1 on those dims and 0 elsewhere, and the router's rows live
there alone. What the router reads is then one common scalar c times a sign
vector, however c rounds in bfloat16: the ORDER of a token's 192 scores is
the float32 reference's. The order of its GROUP scores (sums of a group's
two largest sigmoid(c z)) is not invariant to c, because the sigmoid bends;
`router_gain` keeps the logits small (a standard deviation of about 0.07,
scores 0.5 +- 0.03), where a sum of two sigmoids is linear in z to a part
in ten thousand and the common scalar cannot reorder two groups unless
their scores lie closer than 1e-6 (the configuration's `weights_why`).
`moe_bias` is uniform in +-`router_bias`.

WHICH tokens a long greedy stream emits is laid out too (`walk_embedding`).
A token's sign vector is not drawn: it is the signs of the HEAD's row of
the token's SUCCESSOR over those dims (the head's stream is replayed from
the file's seed; a zero weight takes a drawn sign), so the successor's
logit carries 4 r g sum |w| over the token dims (r the final norm's
scale, g its gain on the token dims, `head_token_gain`: the head alone
reads that norm, so g moves nothing inside the layers), and greedy
decoding walks the vocabulary: `successor(t)` = t + 1 around the filler ids
[256, vocab - 256), a byte id (a prompt's last letter) entering at 256 +
78 t. The signs stay independent and even (they are those of independent
random weights), so the router reads what it read before; a step that a
near-tie takes elsewhere lands on another point of the same cycle and
walks on. Without it the streams were left to the random weights and about
one seed in ten fell onto a few tokens: equal tokens meet equal experts,
the held group read a third of its weights and the run was 10-15% fast
(PERF.md section 6, PR 44).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from benchmark import files
from benchmark.files import Entry
from benchmark.layouts.smallthinker import by_column, normal

#: what a configuration's `weights` block may set, and the defaults
WEIGHT_DEFAULTS = {"attention_sharpness": 1.0, "router_gain": 1.0,
                   "router_dims": 0, "router_embedding_std": 1.0,
                   "router_bias": 0.0, "mla_out_gain": 1.0,
                   "expert_gain": 1.0, "shared_gain": 1.0,
                   "head_token_gain": 1.0}

# header keys of the `.m` format (the program's models/config.HeaderKey)
_K = {"version": 0, "arch": 1, "dim": 2, "hidden_dim": 3, "n_layers": 4,
      "n_heads": 5, "n_kv_heads": 6, "n_experts": 7, "n_active_experts": 8,
      "vocab_size": 9, "seq_len": 10, "hidden_act": 11, "rope_theta": 12,
      "weight_type": 13, "rope_type": 18, "norm_epsilon_x1e12": 100,
      "attn_scale_x1e6": 102,
      "kv_rank": 140, "nope_dim": 141, "pe_dim": 142, "v_dim": 143,
      "q_rank": 144,
      "router_kind": 150, "routed_scale_x1e6": 151, "n_shared": 152,
      "experts_held": 153, "expert_offset": 154, "moe_hidden_dim": 155,
      "n_groups": 156, "groups_kept": 157}
#: the layers' own rope table: header key -> (name, what the value is
#: multiplied by on disk)
_ROPE_KEYS = {170: ("type", 1), 171: ("theta", 1), 172: ("share", 1e6),
              173: ("factor", 1e6), 174: ("orig_len", 1),
              175: ("beta_fast", 1e6), 176: ("beta_slow", 1e6),
              177: ("attn_factor", 1e6)}
_KIND0, _FFN0 = 1000, 4000  # layer i: its kind / 1 = a dense feed-forward
ARCH_LLAMA, ACT_SILU, FT_Q40, ROPE_YARN = 0xABCD00, 1, 2, 4
KIND_MLA, ROUTER_SIGMOID = 3, 1
_INTS = ("dim", "hidden_dim", "n_layers", "n_heads", "n_kv_heads",
         "n_experts", "n_active_experts", "vocab_size", "seq_len", "kv_rank",
         "nope_dim", "pe_dim", "v_dim", "q_rank", "n_shared", "experts_held",
         "expert_offset", "moe_hidden_dim", "n_groups", "groups_kept")


def successor(tokens: np.ndarray, vocab: int) -> np.ndarray:
    """The id a greedy stream emits after each of `tokens` (module
    docstring): + 1 around the ids [lo, vocab - 256) under the tokenizer's
    256 specials, lo = 256 past the byte ids where the vocabulary has
    filler ids at all; an id outside the walk (a byte, a special) enters it
    spread over the cycle."""
    tokens = np.asarray(tokens, np.int64)
    hi = vocab - 256
    lo = 256 if hi >= 512 else 0
    span = hi - lo
    inside = (tokens >= lo) & (tokens < hi)
    step = max(1, span // 256)
    return np.where(inside, lo + (tokens - lo + 1) % span,
                    lo + (tokens * step) % span)


def walk_embedding(dim: int, head, tail_cols: int, std: float, vocab: int,
                   wcls_index: int, wcls: Entry):
    """An initialiser for the embedding [vocab, dim]: `head(rng, n)` draws
    the first dim - tail_cols columns; the last tail_cols hold +-std with
    the signs of the head matrix's row of the token's successor over those
    columns. The head (`wcls`, entry `wcls_index` of the plan) is drawn
    from its own stream as the writer will draw it: the stream is found
    again from this entry's, whose seed sequence names the file's seed."""
    def init(rng: np.random.Generator, n: int) -> np.ndarray:
        rows = n // dim
        out = np.zeros((rows, dim), np.float32)
        out[:, :dim - tail_cols] = head(
            rng, rows * (dim - tail_cols)).reshape(rows, -1)
        drawn = np.where(rng.random((rows, tail_cols)) < 0.5, -1, 1)
        root = rng.bit_generator.seed_seq.entropy
        theirs = np.random.Generator(np.random.PCG64(
            np.random.SeedSequence(root, spawn_key=(wcls_index,))))
        rec = files._random_q40(theirs, wcls).reshape(
            vocab, dim // files.Q_BLOCK, files.Q40_BLOCK_BYTES)
        packed = rec[:, -(tail_cols // files.Q_BLOCK):, 2:]
        codes = np.concatenate([packed & 0x0F, packed >> 4],
                               axis=-1).astype(np.int8) - 8
        w = np.sign(codes).reshape(vocab, tail_cols)
        w = np.where(w == 0, drawn, w)[successor(np.arange(rows), vocab)]
        out[:, dim - tail_cols:] = w * np.float32(std)
        return out.reshape(-1)
    return init


def mscale(factor: float, m: float) -> float:
    """YaRN's magnitude correction as the family computes it."""
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def shapes_of(config: dict) -> dict:
    """The file-level sizes of a configuration file (the published key
    names in, the `.m` header's names out). `n_routed_experts` counts the
    experts HELD; `deployment.n_routed_experts_published` those routed
    among."""
    if (config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]
            or config["moe_layer_freq"] != 1
            or config["topk_method"] not in ("none", "noaux_tc")):
        raise ValueError("this layout is for a sigmoid router, renormalised, "
                         "group-limited, every layer past the dense ones an "
                         "expert layer")
    if (config.get("q_lora_rank") is None or config["tie_word_embeddings"]
            or config["hidden_act"] != "silu" or config.get("attention_bias")):
        raise ValueError("this layout holds latent attention with a q-side "
                         "low rank, SiLU, no attention bias, an untied head")
    n = int(config["num_hidden_layers"])
    dep = config.get("deployment", {})
    held = int(config["n_routed_experts"])
    routed = int(dep.get("n_routed_experts_published", held))
    nope, pe = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    s = {"dim": int(config["hidden_size"]),
         "hidden_dim": int(config["intermediate_size"]), "n_layers": n,
         "n_heads": int(config["num_attention_heads"]),
         "n_kv_heads": int(config["num_key_value_heads"]),
         "n_experts": routed,
         "n_active_experts": int(config["num_experts_per_tok"]),
         "vocab_size": int(config["vocab_size"]),
         "seq_len": int(config["max_position_embeddings"]),
         "kv_rank": int(config["kv_lora_rank"]),
         "nope_dim": nope, "pe_dim": pe, "v_dim": int(config["v_head_dim"]),
         "q_rank": int(config["q_lora_rank"]),
         "n_shared": int(config["n_shared_experts"]),
         "experts_held": held if held != routed else 0,
         "expert_offset": int(dep.get("expert_offset", 0)),
         "moe_hidden_dim": int(config["moe_intermediate_size"]),
         "n_groups": int(config["n_group"]),
         "groups_kept": int(config["topk_group"]),
         "routed_scale": float(config["routed_scaling_factor"]),
         "rope_theta": float(config["rope_theta"]),
         "norm_epsilon": float(config["rms_norm_eps"]),
         "attn_scale": 0.0, "rope": None,
         "dense_ffn": [int(i < int(config["first_k_dense_replace"]))
                       for i in range(n)]}
    yarn = config.get("rope_scaling")
    if yarn is not None:
        if yarn["type"] != "yarn":
            raise ValueError("this layout's rope scaling is the yarn type")
        f = float(yarn["factor"])
        all_dim = mscale(f, float(yarn["mscale_all_dim"]))
        s["attn_scale"] = (nope + pe) ** -0.5 * all_dim * all_dim
        s["rope"] = {"type": ROPE_YARN, "theta": s["rope_theta"], "share": 1.0,
                     "factor": f,
                     "orig_len": int(yarn["original_max_position_embeddings"]),
                     "beta_fast": float(yarn["beta_fast"]),
                     "beta_slow": float(yarn["beta_slow"]),
                     "attn_factor": mscale(f, float(yarn["mscale"])) / all_dim}
    return _derived(s)


def _derived(s: dict) -> dict:
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
          (_K["weight_type"], FT_Q40)]
    if abs(s["norm_epsilon"] - 1e-5) > 1e-12:
        kv.append((_K["norm_epsilon_x1e12"],
                   int(round(s["norm_epsilon"] * 1e12))))
    if s["attn_scale"]:
        kv.append((_K["attn_scale_x1e6"], int(round(s["attn_scale"] * 1e6))))
    kv += [(_KIND0 + i, KIND_MLA) for i in range(s["n_layers"])]
    kv += [(_K[k], s[k]) for k in ("kv_rank", "nope_dim", "pe_dim", "v_dim",
                                   "q_rank", "n_shared")]
    if s["experts_held"]:
        kv += [(_K["experts_held"], s["experts_held"])]
        if s["expert_offset"]:
            kv += [(_K["expert_offset"], s["expert_offset"])]
    kv += [(_K["moe_hidden_dim"], s["moe_hidden_dim"]),
           (_K["n_groups"], s["n_groups"]), (_K["groups_kept"], s["groups_kept"]),
           (_K["router_kind"], ROUTER_SIGMOID),
           (_K["routed_scale_x1e6"], int(round(s["routed_scale"] * 1e6)))]
    if s["rope"] is not None:
        kv += [(key, int(round(s["rope"][name] * mult)))
               for key, (name, mult) in _ROPE_KEYS.items()]
    return kv + [(_FFN0 + i, f) for i, f in enumerate(s["dense_ffn"])]


def tensor_plan(s: dict, weights: dict | None = None) -> list:
    """The tensors in on-disk order. `weights` matters to the writer alone:
    shapes and kinds do not depend on it."""
    w = {**WEIGHT_DEFAULTS, **(weights or {})}
    dim, width, e, held = s["dim"], s["moe_hidden_dim"], s["n_experts"], s["held"]
    h, qr = s["n_heads"], s["q_rank"]
    rd = int(w["router_dims"])
    if rd:
        # the stream's last rd dims: written by nothing, read by the mixers
        # and the router, +-std with a random sign (module docstring)
        router = by_column(dim, None, rd,
                           normal(float(w["router_gain"]) / np.sqrt(rd)))
        embedding = None  # set below, from the head's entry (walk_embedding)
        out = lambda gain: ((dim - rd, float(gain)), (rd, 0.0))
        att_gain = by_column(dim, None, rd, files.ones)
    else:
        router = normal(float(w["router_gain"]) / np.sqrt(dim))
        embedding = files.uniform(0.02)
        out = lambda gain: float(gain)
        att_gain = files.ones
    expert_out = out(w["expert_gain"])
    plan = [Entry("embedding", (s["vocab_size"], dim), "f32", init=embedding)]
    for li in range(s["n_layers"]):
        p = f"layers.{li}."
        plan += [
            Entry(p + "mla_qa", (qr, dim), "q40"),
            Entry(p + "mla_q_norm", (qr,), "f32", init=files.ones),
            Entry(p + "mla_qb", (h * (s["nope_dim"] + s["pe_dim"]), qr), "q40",
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
    # the head alone reads the final norm: its gain on the token dims sets
    # how far the successor's logit stands out of the row (module docstring)
    final_gain = lambda rng, n: np.full(n, w["head_token_gain"], np.float32)
    plan += [Entry("final_norm", (dim,), "f32",
                   init=by_column(dim, files.ones, rd, final_gain)),
             Entry("wcls", (s["vocab_size"], dim), "q40")]
    if rd:
        plan[0] = dataclasses.replace(plan[0], init=walk_embedding(
            dim, files.uniform(0.02), rd, float(w["router_embedding_std"]),
            s["vocab_size"], len(plan) - 1, plan[-1]))
    return plan


def read_header(path: str) -> tuple[dict, int]:
    """(sizes as `shapes_of` names them, header bytes) of a `.m` file."""
    raw, size = files.parse_header(path)
    if (raw.get(_K["arch"]) != ARCH_LLAMA or raw.get(_K["weight_type"]) != FT_Q40
            or raw.get(_K["hidden_act"]) != ACT_SILU
            or _K["rope_type"] in raw or not raw.get(_K["q_rank"])
            or raw.get(_K["router_kind"]) != ROUTER_SIGMOID):
        raise ValueError(f"{path}: this layout reads Q40 files of rotated "
                         "latent layers with a q-side low rank over SiLU "
                         "experts behind a sigmoid router")
    s = {k: raw.get(_K[k], 0) for k in _INTS}
    if any(raw[_KIND0 + i] != KIND_MLA for i in range(s["n_layers"])):
        raise ValueError(f"{path}: a layer that is not latent attention")
    s["routed_scale"] = raw.get(_K["routed_scale_x1e6"], 1_000_000) / 1e6
    s["rope_theta"] = float(raw[_K["rope_theta"]])
    s["norm_epsilon"] = raw.get(_K["norm_epsilon_x1e12"], 10_000_000) / 1e12
    s["attn_scale"] = raw.get(_K["attn_scale_x1e6"], 0) / 1e6
    s["rope"] = ({name: raw[key] / mult for key, (name, mult) in _ROPE_KEYS.items()}
                 if 170 in raw else None)
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
