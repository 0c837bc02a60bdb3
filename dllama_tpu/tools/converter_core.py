"""Checkpoint conversion core: HF/Meta state dicts -> `.m` tensor plan.

Framework-agnostic (numpy in, numpy out) so the parity tests can exercise the
exact same mapping the CLI converters use. Mirrors the reference converter's
tensor plan and Q/K permutation (convert-hf.py:11-14,51-89): HF stores Q/K in
rotate-half rope layout; the `.m` format stores the Meta *interleaved-pair*
layout, related by a per-head even/odd interleave of rows.
"""

from __future__ import annotations

from typing import Mapping

import numpy as np

from dllama_tpu.models.config import (
    ArchType,
    HiddenAct,
    LayerKind,
    LlamaConfig,
    RopeSpec,
    RopeType,
)
from dllama_tpu.ops.quant import FloatType, parse_float_type


def permute_rope(w: np.ndarray, n_heads: int) -> np.ndarray:
    """HF rotate-half -> Meta interleaved layout for a [n_heads*hd, in] proj.

    Row-block view per head: [hd/2 "first halves", hd/2 "second halves"] ->
    interleaved (pair i = rows i and i+hd/2). Same transform as
    convert-hf.py:11-14.
    """
    out_dim = w.shape[0]
    return (
        w.reshape(n_heads, 2, out_dim // n_heads // 2, *w.shape[1:])
        .swapaxes(1, 2)
        .reshape(w.shape)
    )


def hf_config_to_llama(config: Mapping, weight_type: FloatType) -> LlamaConfig:
    """HF config.json -> LlamaConfig (mirrors convert-hf.py:152-195)."""
    if config["model_type"] == "granitemoehybrid":
        return _hybrid_ssm_config(config, weight_type)
    if "sliding_window_layout" in config:
        return _window_moe_config(config, weight_type)
    if config.get("kv_lora_rank") and config.get("n_group"):
        return _latent_groups_config(config, weight_type)
    arch = {
        "llama": ArchType.LLAMA,
        "mistral": ArchType.LLAMA,
        "mixtral": ArchType.LLAMA,
    }.get(config["model_type"])
    if arch is None:
        raise ValueError(f"unsupported arch type: {config['model_type']}")
    act = {"gelu": HiddenAct.GELU, "silu": HiddenAct.SILU}.get(config["hidden_act"])
    if act is None:
        raise ValueError(f"unsupported hidden act: {config['hidden_act']}")
    kwargs = dict(
        arch=arch,
        hidden_act=act,
        dim=config["hidden_size"],
        hidden_dim=config["intermediate_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        weight_type=weight_type,
        seq_len=config["max_position_embeddings"],
        vocab_size=config["vocab_size"],
        n_experts=int(config.get("num_local_experts") or 0),
        n_active_experts=int(
            config.get("num_active_local_experts") or config.get("num_experts_per_tok") or 0
        ),
        norm_epsilon=float(config.get("rms_norm_eps", 1e-5)),
    )
    if config.get("rope_theta") is not None:
        kwargs["rope_theta"] = float(config["rope_theta"])
    scaling = config.get("rope_scaling")
    if scaling is not None:
        if scaling.get("rope_type", scaling.get("type")) != "llama3":
            raise ValueError(f"unsupported rope scaling: {scaling}")
        kwargs.update(
            rope_type=RopeType.LLAMA3_1,
            rope_scaling_factor=float(scaling["factor"]),
            rope_scaling_low_freq_factor=float(scaling["low_freq_factor"]),
            rope_scaling_high_freq_factor=float(scaling["high_freq_factor"]),
            rope_scaling_orig_max_seq_len=int(scaling["original_max_position_embeddings"]),
        )
    return LlamaConfig(**kwargs)


def _window_moe_config(config: Mapping, weight_type: FloatType) -> LlamaConfig:
    """A config.json that lists, layer by layer, which attention layers are
    windowed (`sliding_window_layout`) and which rotate (`rope_layout`), with
    routed ReLU-gated experts in every layer and a router that reads the
    attention block's normed input (source of the key names:
    huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct config.json) -> a
    LLAMA header with the per-layer lists. Refused by mechanism: a router
    without softmax or without renormalised top-k, scaled rope."""
    if not (config.get("moe_primary_router_apply_softmax")
            and config.get("norm_topk_prob")):
        raise ValueError("only a softmax router with renormalised top-k runs")
    if config.get("rope_scaling") is not None:
        raise ValueError(f"unsupported rope scaling: {config['rope_scaling']}")
    n = config["num_hidden_layers"]
    return LlamaConfig(
        arch=ArchType.LLAMA, hidden_act=HiddenAct.RELU,
        dim=config["hidden_size"], hidden_dim=config["moe_ffn_hidden_size"],
        n_layers=n, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        weight_type=weight_type, seq_len=config["max_position_embeddings"],
        vocab_size=config["vocab_size"],
        n_experts=config["moe_num_primary_experts"],
        n_active_experts=config["moe_num_active_primary_experts"],
        norm_epsilon=float(config["rms_norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        window=int(config["sliding_window_size"]),
        layer_windows=tuple(config["sliding_window_layout"][:n]),
        layer_ropes=tuple(config["rope_layout"][:n]),
        router_pre_attention=True)


#: the expert tensors of a `_window_moe_config` checkpoint
WINDOW_MOE_NAME_MAP = {
    "moe_gate": "model.layers.{l}.block_sparse_moe.primary_router.weight",
    "moe_w1": "model.layers.{l}.block_sparse_moe.experts.{e}.gate.weight",
    "moe_w2": "model.layers.{l}.block_sparse_moe.experts.{e}.down.weight",
    "moe_w3": "model.layers.{l}.block_sparse_moe.experts.{e}.up.weight",
}


def _hybrid_ssm_config(config: Mapping, weight_type: FloatType) -> LlamaConfig:
    """A `GraniteMoeHybridForCausalLM` config.json (Mamba-2 mixers beside
    attention layers, one shared SwiGLU MLP after each; source of the key
    names: huggingface.co/ibm-granite/granite-4.0-h-micro config.json) ->
    an ArchType.HYBRID_SSM header. What the program does not run is refused
    by mechanism: routed experts, rotated positions, projection biases, an
    untied head."""
    refused = {
        "routed experts (num_local_experts > 0)": config.get("num_local_experts"),
        "rotary positions (position_embedding_type != nope)":
            config.get("position_embedding_type") != "nope",
        "projection or attention biases":
            config.get("mamba_proj_bias") or config.get("attention_bias"),
        "an untied output head": not config.get("tie_word_embeddings"),
        "a conv without bias": not config.get("mamba_conv_bias", True),
    }
    for what, present in refused.items():
        if present:
            raise ValueError(f"unsupported in a hybrid state-space model: {what}")
    if config["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden act: {config['hidden_act']}")
    kinds = {"mamba": LayerKind.SSM, "attention": LayerKind.ATTENTION}
    dim, heads = config["hidden_size"], config["num_attention_heads"]
    return LlamaConfig(
        arch=ArchType.HYBRID_SSM, hidden_act=HiddenAct.SILU, dim=dim,
        hidden_dim=config["shared_intermediate_size"],
        n_layers=config["num_hidden_layers"], n_heads=heads,
        n_kv_heads=config["num_key_value_heads"], weight_type=weight_type,
        seq_len=config["max_position_embeddings"],
        vocab_size=config["vocab_size"],
        norm_epsilon=float(config.get("rms_norm_eps", 1e-5)),
        rope_type=RopeType.NONE, head_dim=dim // heads,
        attn_scale=float(config["attention_multiplier"]),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]), tied_head=True,
        layer_kinds=tuple(kinds[k] for k in config["layer_types"]),
        ssm_heads=config["mamba_n_heads"], ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"], ssm_groups=config["mamba_n_groups"],
        ssm_conv=config["mamba_d_conv"], ssm_chunk=config["mamba_chunk_size"],
    )


def yarn_mscale(factor: float, m: float) -> float:
    """YaRN's magnitude correction as the DeepSeek-V3 family computes it."""
    return 0.1 * m * np.log(factor) + 1.0 if factor > 1 else 1.0


def _latent_groups_config(config: Mapping, weight_type: FloatType) -> LlamaConfig:
    """A config.json of the DeepSeek-V3 family's shape (latent attention with
    a q-side low rank in every layer, `first_k_dense_replace` leading dense
    layers, then sigmoid-routed experts under group-limited selection beside
    shared experts; source of the key names: huggingface.co/skt/A.X-K1
    config.json) -> a LLAMA header: every layer LayerKind.MLA, `q_lora_rank`
    -> MLA_Q_RANK, `n_group` / `topk_group` -> N_EXPERT_GROUPS /
    EXPERT_GROUPS_KEPT, a `yarn` block -> the layers' own rope table over the
    shared key dims (cos and sin times mscale(factor, mscale) / mscale(factor,
    mscale_all_dim)) and the score scale (nope + pe)^-1/2 x mscale(factor,
    mscale_all_dim)^2. Refused by mechanism: a softmax router, unnormalised
    weights, no q-side low rank, expert layers at a stride, projection
    biases, another rope scaling."""
    refused = {
        "a router that is not sigmoid": config.get("scoring_func") != "sigmoid",
        "top-k weights that are not renormalised": not config.get("norm_topk_prob"),
        "q without a low rank (q_lora_rank null)": not config.get("q_lora_rank"),
        "expert layers at a stride (moe_layer_freq != 1)":
            config.get("moe_layer_freq", 1) != 1,
        "projection or attention biases": config.get("attention_bias"),
        "a tied output head": config.get("tie_word_embeddings"),
    }
    for what, present in refused.items():
        if present:
            raise ValueError(f"unsupported in a latent-attention model: {what}")
    if config["hidden_act"] != "silu":
        raise ValueError(f"unsupported hidden act: {config['hidden_act']}")
    n = config["num_hidden_layers"]
    nope, pe = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rope, scale = None, 0.0
    yarn = config.get("rope_scaling")
    if yarn is not None:
        if yarn.get("rope_type", yarn.get("type")) != "yarn":
            raise ValueError(f"unsupported rope scaling: {yarn}")
        f = float(yarn["factor"])
        all_dim = yarn_mscale(f, float(yarn.get("mscale_all_dim", 0.0)))
        # (to the header's six decimals, so that the config round-trips)
        scale = round(float((nope + pe) ** -0.5 * all_dim * all_dim), 6)
        rope = RopeSpec(
            RopeType.YARN, float(config.get("rope_theta", 10000.0)), 1.0, f,
            int(yarn["original_max_position_embeddings"]),
            float(yarn.get("beta_fast", 32.0)), float(yarn.get("beta_slow", 1.0)),
            float(yarn_mscale(f, float(yarn.get("mscale", 1.0))) / all_dim))
    return LlamaConfig(
        arch=ArchType.LLAMA, hidden_act=HiddenAct.SILU,
        dim=config["hidden_size"], hidden_dim=config["intermediate_size"],
        n_layers=n, n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"], weight_type=weight_type,
        seq_len=config["max_position_embeddings"],
        vocab_size=config["vocab_size"],
        norm_epsilon=float(config["rms_norm_eps"]),
        rope_theta=float(config.get("rope_theta", 10000.0)),
        global_rope=rope, attn_scale=scale,
        layer_kinds=(LayerKind.MLA,) * n, kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=nope, qk_pe_dim=pe, v_head_dim=config["v_head_dim"],
        q_lora_rank=config["q_lora_rank"],
        n_experts=config["n_routed_experts"],
        n_active_experts=config["num_experts_per_tok"], router_sigmoid=True,
        routed_scale=float(config.get("routed_scaling_factor", 1.0)),
        n_shared_experts=int(config.get("n_shared_experts") or 0),
        moe_hidden_dim=config["moe_intermediate_size"],
        n_expert_groups=config["n_group"], expert_groups_kept=config["topk_group"],
        layer_ffn=tuple(int(i < config.get("first_k_dense_replace", 0))
                        for i in range(n)))


# the DeepSeek-V3 family's names for `_latent_groups_config`'s tensors. The
# family's checkpoints keep q_pe and k_pe as INTERLEAVED pairs (its public
# implementation de-interleaves before its rotate-half), which is the `.m`
# pairing: no row permutation. `moe_bias` is absent from a checkpoint with
# `topk_method` "none": zeros are written
LATENT_NAME_MAP = {
    "mla_qa": "model.layers.{l}.self_attn.q_a_proj.weight",
    "mla_q_norm": "model.layers.{l}.self_attn.q_a_layernorm.weight",
    "mla_qb": "model.layers.{l}.self_attn.q_b_proj.weight",
    "mla_kva": "model.layers.{l}.self_attn.kv_a_proj_with_mqa.weight",
    "mla_kv_norm": "model.layers.{l}.self_attn.kv_a_layernorm.weight",
    "mla_kvb": "model.layers.{l}.self_attn.kv_b_proj.weight",
    "mla_o": "model.layers.{l}.self_attn.o_proj.weight",
    "moe_gate": "model.layers.{l}.mlp.gate.weight",
    "moe_bias": "model.layers.{l}.mlp.gate.e_score_correction_bias",
    "moe_w1": "model.layers.{l}.mlp.experts.{e}.gate_proj.weight",
    "moe_w2": "model.layers.{l}.mlp.experts.{e}.down_proj.weight",
    "moe_w3": "model.layers.{l}.mlp.experts.{e}.up_proj.weight",
    "shared_w1": "model.layers.{l}.mlp.shared_experts.gate_proj.weight",
    "shared_w2": "model.layers.{l}.mlp.shared_experts.down_proj.weight",
    "shared_w3": "model.layers.{l}.mlp.shared_experts.up_proj.weight",
}


# `.m` plan name -> HF tensor name template (convert-hf.py:51-89 order)
HF_NAME_MAP = {
    "embedding": "model.embed_tokens.weight",
    "wq": "model.layers.{l}.self_attn.q_proj.weight",
    "wk": "model.layers.{l}.self_attn.k_proj.weight",
    "wv": "model.layers.{l}.self_attn.v_proj.weight",
    "wo": "model.layers.{l}.self_attn.o_proj.weight",
    "w1": "model.layers.{l}.mlp.gate_proj.weight",
    "w2": "model.layers.{l}.mlp.down_proj.weight",
    "w3": "model.layers.{l}.mlp.up_proj.weight",
    "rms_att": "model.layers.{l}.input_layernorm.weight",
    "rms_ffn": "model.layers.{l}.post_attention_layernorm.weight",
    "final_norm": "model.norm.weight",
    "wcls": "lm_head.weight",
    # Mixtral-style sparse MoE (convert-hf.py:66-73 wrote these tensors too,
    # but the reference runtime never consumed them)
    "moe_gate": "model.layers.{l}.block_sparse_moe.gate.weight",
    "moe_w1": "model.layers.{l}.block_sparse_moe.experts.{e}.w1.weight",
    "moe_w2": "model.layers.{l}.block_sparse_moe.experts.{e}.w2.weight",
    "moe_w3": "model.layers.{l}.block_sparse_moe.experts.{e}.w3.weight",
}

# ArchType.HYBRID_SSM (GraniteMoeHybridForCausalLM): the Mamba-2 mixer's
# tensors, and the shared MLP whose input_linear holds gate | up stacked
HYBRID_NAME_MAP = {
    "in_proj": "model.layers.{l}.mamba.in_proj.weight",
    "conv_w": "model.layers.{l}.mamba.conv1d.weight",  # [C, 1, K] -> [C, K]
    "conv_b": "model.layers.{l}.mamba.conv1d.bias",
    "dt_bias": "model.layers.{l}.mamba.dt_bias",
    "a_log": "model.layers.{l}.mamba.A_log",
    "d": "model.layers.{l}.mamba.D",
    "ssm_norm": "model.layers.{l}.mamba.norm.weight",
    "out_proj": "model.layers.{l}.mamba.out_proj.weight",
    "w1": "model.layers.{l}.shared_mlp.input_linear.weight",  # rows [:hidden]
    "w3": "model.layers.{l}.shared_mlp.input_linear.weight",  # rows [hidden:]
    "w2": "model.layers.{l}.shared_mlp.output_linear.weight",
}


def hf_tensor_for(name: str, cfg: LlamaConfig, get) -> np.ndarray:
    """Fetch + transform the HF tensor for a `.m` plan entry.

    `get(hf_name)` -> np.ndarray. Handles the Q/K rope permutation and tied
    embeddings (lm_head absent => reuse embed_tokens).
    """
    parts = name.split(".")
    if len(parts) == 3:
        _, layer, short = parts
        if cfg.latent and cfg.q_lora_rank and short in LATENT_NAME_MAP:
            at = LATENT_NAME_MAP[short]
            if short == "moe_bias":
                try:
                    return get(at.format(l=layer))
                except KeyError:
                    return np.zeros((cfg.n_experts,), np.float32)
            if short in ("moe_w1", "moe_w2", "moe_w3"):
                return np.stack([get(at.format(l=layer, e=e))
                                 for e in range(cfg.n_experts)], axis=0)
            return get(at.format(l=layer))
        if short.startswith("moe_"):
            # by what the checkpoint holds: Mixtral's names, else the
            # window-and-global family's
            def expert_tensor(**at):
                try:
                    return get(HF_NAME_MAP[short].format(l=layer, **at))
                except KeyError:
                    return get(WINDOW_MOE_NAME_MAP[short].format(l=layer, **at))

            if short == "moe_gate":
                return expert_tensor()
            return np.stack([expert_tensor(e=e) for e in range(cfg.n_experts)],
                            axis=0)
        if cfg.arch == ArchType.HYBRID_SSM:
            if short in HYBRID_NAME_MAP:
                x = get(HYBRID_NAME_MAP[short].format(l=layer))
                if short == "conv_w":
                    return x.reshape(x.shape[0], x.shape[-1])
                if short in ("w1", "w3"):
                    h = cfg.hidden_dim
                    return x[:h] if short == "w1" else x[h:]
                return x
            # attention without rotation: q and k rows stay as published
            return get(HF_NAME_MAP[short].format(l=layer))
        hf_name = HF_NAME_MAP[short].format(l=layer)
        x = get(hf_name)
        if short == "wq":
            x = permute_rope(x, cfg.n_heads)
        elif short == "wk":
            x = permute_rope(x, cfg.n_kv_heads)
        return x
    if name == "wcls":
        try:
            return get(HF_NAME_MAP["wcls"])
        except KeyError:
            return get(HF_NAME_MAP["embedding"])  # tied embeddings
    return get(HF_NAME_MAP[name])


def default_output_name(model_dir: str, weight_type_name: str) -> str:
    import os

    base = os.path.basename(os.path.normpath(model_dir)).lower().replace(" ", "-")
    return f"dllama_model_{base}_{weight_type_name.lower()}.m"


def write_model(cfg: LlamaConfig, output: str, get_tensor) -> str:
    """Stream the full tensor plan to `output`: header, then each tensor from
    ``get_tensor(plan_name) -> np.ndarray f32``, shape-checked and quantized
    per the plan. Shared by the HF and Meta converter CLIs."""
    import os
    import time

    from dllama_tpu.models.formats import tensor_plan, write_header, write_tensor

    plan = tensor_plan(cfg)
    t0 = time.time()
    with open(output, "wb") as f:
        write_header(f, cfg)
        for i, (name, shape, ft) in enumerate(plan):
            x = get_tensor(name)
            if tuple(x.shape) != tuple(shape):
                raise ValueError(f"{name}: expected shape {shape}, got {x.shape}")
            nbytes = write_tensor(f, x, ft)
            print(f"💾 [{i + 1}/{len(plan)}] {name} {tuple(shape)} -> {nbytes} bytes", flush=True)
    print(f"✅ Created {output} ({os.path.getsize(output) / 1e9:.2f} GB, {time.time() - t0:.1f}s)")
    return output
