"""ROTATED latent attention with a q-side low rank (YaRN over the shared key
dims, the score scale carrying mscale^2) and GROUP-LIMITED expert selection
(contiguous groups, a group's score the sum of its two best, the top k among
the kept groups), served as ONE CHIP'S SHARE of each expert layer, against
the plain reference (`benchmark/reference/axk1.py`).

A tiny file is written through the benchmark's layout
(`benchmark/layouts/axk1.py`, `benchmark/tests/tiny-axk1.json`): 5 layers (a
dense-FFN layer, then four expert layers), 8 heads, latent 64 + 32, q through
64, YaRN x8 from 32 positions, 16 experts in 4 groups of which 2 are kept,
4 held from offset 4 (group 1), 4 active. Weights are loaded in float32 so
that the serving path's own arithmetic reads against the reference at 1e-6
and each control stands out; the stated precision (bf16 activations, the
grouped Q40 expert kernel) reads at bf16's rounding.
"""

import dataclasses
import hashlib
import importlib
import json
import os
import struct

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import files
from benchmark.layouts import axk1 as layout
from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.models import formats
from dllama_tpu.models import llama as model
from dllama_tpu.models.config import (HeaderKey, LayerKind, LlamaConfig, RopeSpec,
                                      RopeType)
from dllama_tpu.obs import instruments as ins
from dllama_tpu.ops import layers as ops_layers
from dllama_tpu.ops.layers import (apply_rope, build_rope_cache, keep_expert_groups,
                                   moe_ffn, yarn_freqs)
from dllama_tpu.ops.quant import FloatType, QTensor
from tests import arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny-axk1.json")) as f:
    TINY = json.load(f)
#: CPU readings against arch.TOL, seed 5: sound 1.1e-6 on both routes; the
#: controls 0.01 to 1
TOL, ENGINE, _tokens = arch.TOL, arch.ENGINE, arch.tokens


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return arch.tiny_file(tmp_path_factory, "rot_latent", TINY)


# ------------------------------------------------- files, header, plan


def test_header_round_trip_and_plan(tiny):
    cfg = tiny.config
    assert cfg.layer_kinds == (int(LayerKind.MLA),) * 5
    assert cfg.layer_ffn == (1, 0, 0, 0, 0) and cfg.rope_type == RopeType.LLAMA
    assert cfg.latent and cfg.router_sigmoid and not cfg.recurrent
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_pe_dim, cfg.rope_dims) == (
        64, 64, 32, 32)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset,
            cfg.n_expert_groups, cfg.expert_groups_kept) == (16, 4, 4, 4, 2)
    assert cfg.grouped_routing and (cfg.cache_kv_heads, cfg.cache_row) == (1, 96)
    assert cfg.global_rope == RopeSpec(RopeType.YARN, 10000.0, 1.0, 8.0, 32,
                                       32.0, 1.0, 1.0)
    m = 0.1 * np.log(8.0) + 1.0
    assert abs(cfg.attn_scale - 64 ** -0.5 * m * m) < 1e-6
    assert abs(cfg.norm_epsilon - 1e-6) < 1e-12
    assert LlamaConfig.from_header_kv(cfg.to_header_kv()) == cfg
    assert "q_rank=64" in cfg.describe() and "expert_groups=2/4" in cfg.describe()
    mine, header = layout.read_header(tiny.path)
    assert header == formats.read_header(tiny.path)[1]
    assert [(n, int(np.prod(shape))) for n, shape, _ in formats.tensor_plan(cfg)] == [
        (e.name, int(np.prod(e.shape))) for e in layout.tensor_plan(mine)]
    layers = tiny.params["layers"]
    assert "mla_q" not in layers
    assert layers["mla_qa"].shape == (5, 256, 64) and layers["mla_qb"].shape == (5, 64, 512)
    assert layers["mla_q_norm"].shape == (5, 64)
    assert layers["mla_kva"].shape == (5, 256, 128)
    assert layers["mla_kvb"].shape == (5, 8, 64, 64) and layers["mla_kvb"].dtype == jnp.float32
    assert layers["moe_gate"].shape == (4, 256, 16) and layers["moe_w1"].shape == (4, 4, 256, 256)
    table = build_rope_cache(cfg, 64)
    assert isinstance(table, jax.Array) and table.shape == (64, 16, 2)


def test_a_header_without_the_new_keys_means_what_it_meant(tiny, tmp_path):
    base = dict(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                vocab_size=100, seq_len=32)
    llama = LlamaConfig(**base)
    assert max(k for k, _ in llama.to_header_kv()) < 100
    again = LlamaConfig.from_header_kv(llama.to_header_kv())
    assert (again.q_lora_rank, again.n_expert_groups, again.expert_groups_kept,
            again.grouped_routing, again.rope_dims) == (0, 0, 0, False, 16)
    # an unrotated latent model keeps no table and no route tag
    nope = LlamaConfig(**base, layer_kinds=(3, 3), kv_lora_rank=8, qk_pe_dim=4,
                       rope_type=RopeType.NONE)
    assert LlamaConfig.from_header_kv(nope.to_header_kv()) == nope
    moe = dict(base, n_experts=8, n_active_experts=2)
    with pytest.raises(ValueError):  # groups under a softmax router
        LlamaConfig(**moe, n_expert_groups=2, expert_groups_kept=1)
    with pytest.raises(ValueError):  # groups that do not divide the experts
        LlamaConfig(**moe, router_sigmoid=True, n_expert_groups=3, expert_groups_kept=1)
    with pytest.raises(ValueError):  # kept groups that cannot hold the top k
        LlamaConfig(**dict(moe, n_active_experts=5), router_sigmoid=True,
                    n_expert_groups=2, expert_groups_kept=1)
    with pytest.raises(ValueError):  # a q-side rank with no latent layer
        LlamaConfig(**base, q_lora_rank=8)
    with pytest.raises(ValueError):  # a rotation over an odd number of dims
        LlamaConfig(**base, layer_kinds=(3, 3), kv_lora_rank=8, qk_pe_dim=3)
    with pytest.raises(ValueError):  # a latent model under another scaling
        LlamaConfig(**base, layer_kinds=(3, 3), kv_lora_rank=8, qk_pe_dim=4,
                    rope_type=RopeType.LLAMA3_1)
    # a file with a key the program does not know still fails by name
    kv = tiny.config.to_header_kv() + [(158, 1)]
    path = str(tmp_path / "unknown.m")
    with open(path, "wb") as f:
        f.write(struct.pack("<ii", 0x0A00ABCD, 8 + 8 * len(kv)))
        f.write(b"".join(struct.pack("<ii", k, v) for k, v in kv))
    with pytest.raises(ValueError, match="158"):
        formats.read_header(path)
    assert 158 not in {int(k) for k in HeaderKey}


@pytest.mark.parametrize("seed,sha", [(7, "22bad9ba"), (2147483659, "b7a3628d")])
def test_the_layout_writes_the_bytes_it_wrote(seed, sha, tmp_path):
    path = str(tmp_path / "m.m")
    files.write_model(path, TINY, seed)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest().startswith(sha)


@pytest.mark.parametrize("vocab", [512, 20480])
def test_a_greedy_stream_walks_the_vocabulary(vocab):
    """`successor`: one cycle over the ids under the specials (past the byte
    ids where the vocabulary has filler ids), every other id entering it; no
    stream of fewer steps than the cycle meets a token twice."""
    ids = np.arange(vocab)
    nxt = layout.successor(ids, vocab)
    hi = vocab - 256
    lo = 256 if hi >= 512 else 0
    assert nxt.min() >= lo and nxt.max() < hi
    inside = ids[lo:hi]
    assert sorted(nxt[lo:hi]) == list(inside)  # a permutation of the walk
    t, seen = lo, set()
    for _ in range(hi - lo):
        seen.add(int(t))
        t = layout.successor(np.array([t]), vocab)[0]
    assert len(seen) == hi - lo and t == lo  # ONE cycle
    starts = layout.successor(np.arange(97, 123), vocab)  # a prompt's letters
    assert len(set(starts.tolist())) == 26


def test_a_tokens_features_are_its_successors_head_row(tmp_path):
    """The layout's `walk_embedding`: the token dims hold +-std, even and
    uncorrelated between tokens, and over those dims alone the head's
    largest logit for a token is its successor's (the written dims of a
    tiny model drown it; at the published widths it stands ten standard
    deviations out, PERF.md section 6, PR 44)."""
    path = str(tmp_path / "m.m")
    files.write_model(path, TINY, 23)
    _, views = layout.tensor_views(path)
    rd = TINY["weights"]["router_dims"]
    std = TINY["weights"]["router_embedding_std"]
    emb = np.asarray(views["embedding"][0]).view(np.float32).reshape(
        views["embedding"][1])[:, -rd:]
    assert set(np.unique(emb)) == {-std, std} and abs(emb.mean()) < 0.02 * std
    ref = importlib.import_module(TINY["reference"])
    head = np.asarray(ref._q40(views["wcls"]))[:, -rd:]
    vocab = TINY["vocab_size"]
    nxt = layout.successor(np.arange(vocab), vocab)
    assert ((head @ emb.T).argmax(0) == nxt).mean() > 0.98
    # the signs are those of the successor's row wherever its weight is not 0
    agree = np.sign(head[nxt]) * np.sign(emb)
    assert (agree >= 0).all() and (agree > 0).mean() > 0.8
    # `head_token_gain`: the final norm's gain on the token dims, which the
    # head alone reads (the tiny configuration leaves it at 1)
    louder = dict(TINY, weights=dict(TINY["weights"], head_token_gain=6.0))
    files.write_model(path, louder, 23)
    _, views = layout.tensor_views(path)
    gain = np.asarray(views["final_norm"][0]).view(np.float32)
    assert (gain[:-rd] == 1).all() and (gain[-rd:] == 6).all()
    corr = (emb[:64] @ emb[:64].T) / (rd * std * std)
    assert np.abs(corr - np.eye(64)).max() < 0.6


def test_the_converter_maps_the_familys_keys():
    """A hand-written config.json of the family's shape (no download) ->
    the header keys this architecture added."""
    from dllama_tpu.tools import converter_core

    hf = {k: v for k, v in TINY.items()
          if k not in ("deployment", "weights", "serve", "expect", "check",
                       "tolerances", "layout", "reference", "reduced", "name",
                       "source", "num_experts_routed")}
    hf["n_routed_experts"] = 16  # a checkpoint holds every expert
    cfg = converter_core.hf_config_to_llama(hf, FloatType.Q40)
    assert (cfg.q_lora_rank, cfg.n_expert_groups, cfg.expert_groups_kept,
            cfg.n_experts, cfg.experts_held) == (64, 4, 2, 16, 0)
    assert cfg.layer_kinds == (3,) * 5 and cfg.layer_ffn == (1, 0, 0, 0, 0)
    assert cfg.global_rope == RopeSpec(RopeType.YARN, 10000.0, 1.0, 8.0, 32,
                                       32.0, 1.0, 1.0)
    m = converter_core.yarn_mscale(8.0, 1.0)
    assert abs(cfg.attn_scale - 64 ** -0.5 * m * m) < 1e-6 and cfg.routed_scale == 2.5
    # mscale != mscale_all_dim lands on cos and sin, not on the score scale
    other = dict(hf, rope_scaling=dict(hf["rope_scaling"], mscale=0.707))
    spec = converter_core.hf_config_to_llama(other, FloatType.Q40).global_rope
    assert abs(spec.attn_factor - converter_core.yarn_mscale(8.0, 0.707) / m) < 1e-9
    plain = converter_core.hf_config_to_llama(dict(hf, rope_scaling=None), FloatType.Q40)
    assert plain.global_rope is None and plain.attn_scale == 0.0
    assert LlamaConfig.from_header_kv(cfg.to_header_kv()) == cfg
    with pytest.raises(ValueError, match="sigmoid"):
        converter_core.hf_config_to_llama(dict(hf, scoring_func="softmax"), FloatType.Q40)
    seen = []
    get = lambda name: seen.append(name) or np.zeros((2, 2), np.float32)
    for name, _, _ in formats.tensor_plan(cfg):
        if name.startswith("layers.1.") and "rms" not in name:
            converter_core.hf_tensor_for(name, cfg, get)
    assert "model.layers.1.self_attn.q_a_layernorm.weight" in seen
    assert "model.layers.1.mlp.experts.15.down_proj.weight" in seen
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in seen


# ------------------------------------------------------ the YaRN table


def test_the_yarn_table_against_the_formula_on_both_sides_of_the_original_length():
    """`build_rope_cache` of a latent model spans its 64 shared dims; the
    published block (factor 32 from 4,096, betas 32 / 1) keeps pair indices
    under 10 plain, divides those over 23 by 32 and ramps between; the
    reference builds the same table by itself."""
    from benchmark.reference import axk1 as ref

    spec = RopeSpec(RopeType.YARN, 10000.0, 1.0, 32.0, 4096, 32.0, 1.0, 1.0)
    cfg = LlamaConfig(dim=64, hidden_dim=128, n_layers=1, n_heads=4, n_kv_heads=4,
                      vocab_size=100, seq_len=8192, layer_kinds=(3,), kv_lora_rank=32,
                      qk_nope_dim=16, qk_pe_dim=64, v_head_dim=16, global_rope=spec)
    i = np.arange(32, dtype=np.float64)
    plain = 10000.0 ** (-2 * i / 64)
    d = lambda turns: 64 * np.log(4096 / (turns * 2 * np.pi)) / (2 * np.log(10000.0))
    low, high = int(np.floor(d(32))), int(np.ceil(d(1)))
    assert (low, high) == (10, 23)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = plain * (1 - ramp) + plain / 32 * ramp
    got = yarn_freqs(spec, 64)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(got[23:], plain[23:] / 32, rtol=1e-12)
    table = np.asarray(build_rope_cache(cfg, 8192))
    assert table.shape == (8192, 32, 2)
    s = {"pe_dim": 64, "rope_theta": 10000.0,
         "rope": dict(factor=32.0, orig_len=4096, beta_fast=32.0, beta_slow=1.0,
                      attn_factor=1.0)}
    cos, sin = (np.asarray(a) for a in ref.rope_rows(s, 8192))
    for pos in (0, 1, 100, 4095, 4096, 5000, 8191):
        angle = np.float32(pos) * want.astype(np.float32)
        np.testing.assert_allclose(table[pos, :, 0], np.cos(angle), atol=2e-6)
        np.testing.assert_allclose(table[pos, :, 1], np.sin(angle), atol=2e-6)
        np.testing.assert_allclose(table[pos, :, 0], cos[pos], atol=2e-6)
        np.testing.assert_allclose(table[pos, :, 1], sin[pos], atol=2e-6)
    # past the original length the slow dims have turned 32 times less than
    # the plain table's
    assert abs(table[8191, 31, 1] - np.sin(8191 * plain[31] / 32)) < 1e-5
    # mscale != mscale_all_dim multiplies cos and sin
    half = dataclasses.replace(cfg, global_rope=dataclasses.replace(spec, attn_factor=0.5))
    np.testing.assert_allclose(np.asarray(build_rope_cache(half, 16)), table[:16] * 0.5,
                               rtol=1e-6)
    # without a table of its own a latent model rotates by ROPE_THETA, and
    # the reference's plain table agrees
    bare = np.asarray(build_rope_cache(dataclasses.replace(cfg, global_rope=None), 64))
    np.testing.assert_allclose(bare[63, :, 1], np.sin(np.float32(63) * plain.astype(np.float32)),
                               atol=2e-6)
    cos, _ = ref.rope_rows(dict(s, rope=None), 64)
    np.testing.assert_allclose(bare[:, :, 0], np.asarray(cos), atol=2e-6)


# ---------------------------------------- absorbed against expanded, rotated


def test_absorbed_attention_with_the_rotation_is_the_expanded_form():
    """`_mla_mixer` (q through its low rank, q_pe and the one k_pe rotated
    before the row is written, W_kvb absorbed) against the textbook
    expanded form, on a prefill and then on decode steps through the cache
    (whose rows are the ROTATED ones)."""
    rng = np.random.default_rng(1)
    h_, dn, dp, dv, r, qr, d = 4, 16, 8, 16, 32, 24, 64
    cfg = LlamaConfig(dim=d, hidden_dim=128, n_layers=1, n_heads=h_, n_kv_heads=h_,
                      vocab_size=100, seq_len=64, layer_kinds=(3,), kv_lora_rank=r,
                      qk_nope_dim=dn, qk_pe_dim=dp, v_head_dim=dv, q_lora_rank=qr,
                      attn_scale=0.3, norm_epsilon=1e-6,
                      global_rope=RopeSpec(RopeType.YARN, 10000.0, 1.0, 4.0, 8, 32.0,
                                           1.0, 1.0))
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.2, jnp.float32)
    layers = {"mla_qa": w(1, d, qr), "mla_q_norm": 1 + w(1, qr),
              "mla_qb": w(1, qr, h_ * (dn + dp)), "mla_kva": w(1, d, r + dp),
              "mla_kv_norm": 1 + w(1, r), "mla_kvb": w(1, h_, dn + dv, r),
              "mla_o": w(1, h_ * dv, d)}
    x = w(1, 12, d)
    table = build_rope_cache(cfg, 64)
    mm = lambda a, wt, li=None: a @ (wt[li] if li is not None else wt)

    def expanded(x):
        t = x.shape[1]
        rope = table[:t]
        rms = lambda v, g: v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + 1e-6) * g
        q = (rms(x @ layers["mla_qa"][0], layers["mla_q_norm"][0])
             @ layers["mla_qb"][0]).reshape(1, t, h_, dn + dp)
        q = jnp.concatenate([q[..., :dn], apply_rope(q[..., dn:], rope)], -1)
        kva = x @ layers["mla_kva"][0]
        c = rms(kva[..., :r], layers["mla_kv_norm"][0])
        k_pe = apply_rope(kva[..., r:][:, :, None], rope)
        kv = jnp.einsum("btr,hnr->bthn", c, layers["mla_kvb"][0])
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (1, t, h_, dp))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.3
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., dn:])
        return o.reshape(1, t, h_ * dv) @ layers["mla_o"][0]

    with jax.default_matmul_precision("highest"):
        want = expanded(x)
        cache = jnp.zeros((1, 1, 64, r + dp), jnp.float32)
        got, cache, _ = model._mla_mixer(cfg, x[:, :8], layers, 0, cache, None,
                                         table[:8], jnp.asarray(0), None, None, mm, mm,
                                         None)
        np.testing.assert_allclose(got, want[:, :8], rtol=2e-4, atol=2e-5)
        for p in range(8, 12):  # decode through the cache of rotated rows
            step, cache, _ = model._mla_mixer(
                cfg, x[:, p:p + 1], layers, 0, cache, None, table[p:p + 1],
                jnp.asarray(p), None, None, mm, mm, None)
            np.testing.assert_allclose(step[:, 0], want[:, p], rtol=2e-4, atol=2e-5)
        # the cache holds k_pe ROTATED: row 5's shared dims are not W_kva x
        raw = (x @ layers["mla_kva"][0])[0, 5, r:]
        assert float(jnp.abs(cache[0, 0, 5, r:] - raw).max()) > 1e-3
        np.testing.assert_allclose(
            cache[0, 0, 5, r:], apply_rope(raw[None, None, None], table[5:6])[0, 0, 0],
            rtol=1e-5, atol=1e-6)


# --------------------------------------------- engine against the reference


@pytest.mark.parametrize("kernels,attn,route", [
    ("xla", "jnp", "xla/paged_gather.latent.rope_yarn+moe_jnp.groups2of4"),
    # float32 activations: the latent paged sweep in interpret mode; the
    # grouped expert kernel takes bfloat16 rows only
    ("pallas", "flash", "pallas/paged_kernel.latent.rope_yarn+moe_jnp.groups2of4"),
])
def test_prefill_decode_and_tail_match_the_reference(tiny, kernels, attn, route):
    out = arch.run_check(tiny, TINY, kernels, attn)
    assert out["route"] == route
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}
    assert out["rel_l2_max"] < 2e-5


def test_stated_precision_runs_the_kernels(tiny):
    """bfloat16 activations, every kernel in interpret mode (the latent
    sweep over rotated rows, the grouped kernel over the held group): bf16's
    own rounding."""
    out = arch.run_check(arch.loaded(tiny.path, jnp.bfloat16), TINY, "pallas", "flash",
                         tolerances={"rel_l2_mean": 0.04, "deficit_sigma_mean": 0.02})
    assert out["route"] == "pallas/paged_kernel.latent.rope_yarn+moe_grouped.groups2of4"
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}


# ------------------------------------------------------------ the controls


@pytest.fixture(scope="module")
def sixty(tiny):
    """(past the tiny model's original 32 positions)"""
    return arch.sixty(tiny, TINY)


def _k_pe_unrotated(x, rope):
    return x if x.shape[2] == 1 else apply_rope(x, rope)


def _bias_in_the_weights(cfg, h, gate, w1, w2, w3, *, logits, bias, **kw):
    s = jnp.clip(jax.nn.sigmoid(logits) + bias, 1e-6, 1 - 1e-6)
    return moe_ffn(cfg, h, gate, w1, w2, w3, logits=jnp.log(s / (1 - s)),
                   bias=None, **kw)


def _best_expert_for_the_group(choose, groups, kept):
    """The group score as its one best entry, not the sum of two."""
    by = choose.reshape(*choose.shape[:-1], groups, -1)
    _, top = jax.lax.top_k(by.max(-1), kept)
    keep = jnp.any(top[..., None] == jnp.arange(groups), axis=-2)
    return jnp.where(keep[..., None], by, -jnp.inf).reshape(choose.shape), keep


#: name -> (config fields replaced, (module, attribute, replacement) patched)
CONTROLS = {
    "plain rope in place of YaRN": (dict(global_rope=None), None),
    "the score scale without mscale^2": (dict(attn_scale=0.0), None),
    "plain top k in place of the group-limited choice": (
        dict(n_expert_groups=0, expert_groups_kept=0), None),
    "a group scored by its one best expert": (
        {}, (ops_layers, "keep_expert_groups", _best_expert_for_the_group)),
    "k_pe written unrotated": ({}, (model, "apply_rope", _k_pe_unrotated)),
    "the selection bias used in the weights": ({}, (model, "moe_ffn", _bias_in_the_weights)),
    "no rotation at all": (dict(rope_type=RopeType.NONE, global_rope=None), None),
    "the shared expert left out": (dict(n_shared_experts=0), "shared"),
}


@pytest.mark.parametrize("control", [None, *CONTROLS])
def test_each_control_fails_the_tolerance_the_sound_model_holds(
        tiny, sixty, control, monkeypatch):
    """One forward over 60 tokens on the dense jnp route: the model as the
    header says it reads 1e-6 against the reference, and each single
    departure from the equations is refused by 30 x the limit or more (the
    weakest, a group scored by its one best expert, moves the choice of a
    few of the 60 tokens only: it read 55 x on this file's draw)."""
    seq, want = sixty
    if control is None:
        assert arch.logits_rel_l2(tiny.params, tiny.config, seq, want) < TOL["rel_l2_mean"]
        return
    fields, patch = CONTROLS[control]
    params = tiny.params
    if patch == "shared":
        params = dict(params, layers={k: v for k, v in params["layers"].items()
                                      if not k.startswith("shared_")})
    elif patch is not None:
        monkeypatch.setattr(*patch)
    cfg = dataclasses.replace(tiny.config, **fields)
    err = arch.logits_rel_l2(params, cfg, seq, want)
    assert err > 30 * TOL["rel_l2_mean"], err


# ------------------------------------------- group-limited selection


@pytest.fixture(scope="module")
def experts():
    rng = np.random.default_rng(0)
    d, f, e = 256, 256, 16
    cfg = LlamaConfig(dim=d, hidden_dim=f, n_layers=2, n_heads=2, n_kv_heads=1,
                      vocab_size=64, seq_len=32, n_experts=e, n_active_experts=4,
                      router_sigmoid=True, routed_scale=2.5)

    def stack(k, n):
        one = lambda: QTensor.quantize(
            (rng.standard_normal((k, n)) * 0.05).astype(np.float32))
        layer = lambda: jax.tree.map(lambda *x: jnp.stack(x), *[one() for _ in range(e)])
        return jax.tree.map(lambda *x: jnp.stack(x), layer(), layer())

    bias = jnp.asarray(rng.uniform(-0.1, 0.1, e), jnp.float32)
    return cfg, (stack(d, f), stack(f, d), stack(d, f)), bias, rng


def _share(ws, lo, n):
    return tuple(jax.tree.map(lambda a: a[:, lo:lo + n], w) for w in ws)


@pytest.mark.parametrize("groups,kept,with_bias", [
    (1, 1, False), (1, 1, True), (2, 1, False), (4, 2, False), (4, 2, True),
    (8, 4, True), (4, 4, False)])
def test_group_limited_selection_against_the_reference(experts, groups, kept, with_bias):
    """The chosen experts are the reference's for every (groups, kept), with
    and without a selection bias; (1, 1) is today's plain top k bit for bit
    (the same program), and every group kept is the plain top k too."""
    from benchmark.reference import axk1 as ref

    cfg, ws, bias, rng = experts
    logits = jnp.asarray(rng.standard_normal((3, 7, 16)) * 1.5, jnp.float32)
    b = bias if with_bias else None
    grouped = dataclasses.replace(cfg, n_expert_groups=groups, expert_groups_kept=kept)
    score = jax.nn.sigmoid(logits).reshape(21, 16)
    zero = jnp.zeros(16, jnp.float32)
    want = np.sort(np.asarray(ref.choose_experts(
        score, zero if b is None else b, groups, kept, 4)), axis=-1)
    choose = score if b is None else score + b
    if groups > 1:
        choose, keep = keep_expert_groups(choose, groups, kept)
        assert np.asarray(keep).sum(-1).tolist() == [kept] * 21
        # a chosen expert lies in a kept group
        top = np.asarray(jax.lax.top_k(choose, 4)[1])
        assert np.take_along_axis(np.asarray(keep), top // (16 // groups), -1).all()
    got = np.sort(np.asarray(jax.lax.top_k(choose, 4)[1]), axis=-1)
    np.testing.assert_array_equal(got, want)
    h = jnp.asarray(rng.standard_normal((3, 7, cfg.dim)), jnp.float32)
    out = moe_ffn(grouped, h, None, *ws, impl="dense", logits=logits, layer=1, bias=b)
    plain = moe_ffn(cfg, h, None, *ws, impl="dense", logits=logits, layer=1, bias=b)
    if groups == 1 or kept == groups:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(plain))
    else:
        assert float(jnp.abs(out - plain).max()) > 1e-3  # the limit bites
    if groups == 1:
        assert not grouped.grouped_routing
        trace = lambda c: str(jax.make_jaxpr(lambda hh, ll: moe_ffn(
            c, hh, None, *ws, impl="dense", logits=ll, layer=1, bias=b))(h, logits))
        assert trace(grouped) == trace(cfg)
        assert model._moe_stats0(grouped).shape == model._moe_stats0(cfg).shape == (4,)


def test_the_shares_add_up_to_the_uncut_layer(experts):
    """Four chips, one routing group each: the parts they give are the
    uncut layer, every routed row lands on exactly one share, and a token is
    seen by exactly the `kept` chips whose groups it kept."""
    cfg, ws, bias, rng = experts
    cfg = dataclasses.replace(cfg, n_expert_groups=4, expert_groups_kept=2)
    h = jnp.asarray(rng.standard_normal((2, 6, cfg.dim)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((2, 6, cfg.n_experts)), jnp.float32)
    whole, stats = moe_ffn(cfg, h, None, *ws, impl="dense", logits=logits, layer=1,
                           bias=bias, stats=jnp.zeros(6, jnp.uint32))
    assert stats.tolist()[4:] == [12, 12]  # all held: every token reaches here
    parts, held_rows, kept_tokens = 0.0, 0, 0
    for lo in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=lo)
        out, stats = moe_ffn(share, h, None, *_share(ws, lo, 4), impl="auto",
                             logits=logits, layer=1, bias=bias,
                             stats=jnp.zeros(7, jnp.uint32))
        parts = parts + out
        held_rows += int(stats[0])
        kept_tokens += int(stats[6])
        assert int(stats[4]) == 2 * 6 * 4 and int(stats[5]) == 12
        assert int(stats[0]) <= 4 * int(stats[6])  # rows only from tokens that kept it
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-6)
    assert held_rows == 2 * 6 * 4 and kept_tokens == 12 * 2
    # a share of two groups sees a token that kept either
    wide = dataclasses.replace(cfg, experts_held=8, expert_offset=4)
    _, stats = moe_ffn(wide, h, None, *_share(ws, 4, 8), impl="auto", logits=logits,
                       layer=1, bias=bias, stats=jnp.zeros(7, jnp.uint32))
    assert 12 >= int(stats[6]) >= 6


def test_the_reference_shares_add_up_with_the_shared_expert_once(tmp_path):
    """The same in the reference, on an uncut tiny file: the four groups'
    routed parts and the shared expert counted once are the uncut layer."""
    ref = importlib.import_module(TINY["reference"])
    uncut = {k: v for k, v in TINY.items() if k != "deployment"}
    uncut["n_routed_experts"] = 16
    path = str(tmp_path / "uncut.m")
    files.write_model(path, uncut, 5)
    s, views = layout.tensor_views(path)
    assert s["experts_held"] == 0 and s["held"] == 16 and s["n_groups"] == 4
    h = jnp.asarray(np.random.default_rng(4).standard_normal((10, 256)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.ffn_block(s, views, 3, h) - h
        parts = sum(ref.ffn_block(s, views, 3, h, share=(lo, 4), shared=False) - h
                    for lo in (0, 4, 8, 12))
        shared = ref.ffn_block(s, views, 3, h, share=(0, 0)) - h
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(shared).max()) > 0.01 < float(jnp.abs(parts).max())
    # and the program's layer over all the experts is the reference's
    cfg, header = formats.read_header(path, 256)
    params = formats.load_params(path, cfg, header, dtype=jnp.float32)
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        b = ops_layers.rms_norm(h[None], layers["rms_ffn"][3], cfg.norm_epsilon)
        got = model._mlp(cfg, b, layers, 2, model.matmul, model.matmul, "dense", True,
                         logits=ops_layers.router_logits(b, layers["moe_gate"][2]))
    np.testing.assert_allclose(got[0], whole, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("case", ["decode batch", "a slice", "no token keeps the group"])
def test_grouped_kernel_over_the_held_group_matches_dense(experts, case):
    cfg, ws, bias, rng = experts
    share = dataclasses.replace(cfg, n_expert_groups=4, expert_groups_kept=2,
                                experts_held=4, expert_offset=8)
    b, t = {"decode batch": (6, 1), "a slice": (1, 40),
            "no token keeps the group": (2, 1)}[case]
    h = jnp.asarray(rng.standard_normal((b, t, cfg.dim)), jnp.bfloat16)
    logits = jnp.asarray(rng.standard_normal((b, t, cfg.n_experts)), jnp.float32)
    if case == "no token keeps the group":
        logits = logits.at[..., 8:12].set(-20.0)
    got, stats = moe_ffn(share, h, None, *_share(ws, 8, 4), impl="grouped",
                         logits=logits, layer=1, bias=bias,
                         stats=jnp.zeros(7, jnp.uint32))
    want = moe_ffn(share, h, None, *_share(ws, 8, 4), impl="dense",
                   logits=logits, layer=1, bias=bias)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max() + 1e-3
    choose, keep = keep_expert_groups(jax.nn.sigmoid(logits) + bias, 4, 2)
    chosen = np.asarray(jax.lax.top_k(choose, 4)[1])
    mine = chosen[(chosen >= 8) & (chosen < 12)]
    sizes = np.bincount(mine - 8, minlength=4)
    assert stats.tolist() == [len(mine), int((sizes > 0).sum()), 1, int(sizes.max()),
                              b * t * 4, b * t, int(np.asarray(keep)[..., 2].sum())]
    if case == "no token keeps the group":
        assert len(mine) == 0 and not got.any() and stats.tolist()[6] == 0


# ------------------------------------------------------------ the engine


def test_the_engine_counts_tokens_and_kept_groups(tiny):
    names = ("MOE_ROWS_ROUTED", "MOE_ROWS_HELD", "MOE_TOKENS_ROUTED",
             "MOE_TOKENS_GROUP_KEPT")
    before = {n: getattr(ins, n).value() for n in names}
    read0 = ins.LAUNCH_KV_ROWS_READ.labels(kind="decode", pool="latent").value()
    be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                     max_seq_len=256, **ENGINE)
    assert be.kernel_route == "xla/paged_gather.latent.rope_yarn+moe_jnp.groups2of4"
    assert be.cache.k.shape == (5, 121, 1, 8, 96) and be.cache.moe_stats.shape == (7,)
    assert be.rope_cache.shape == (256, 16, 2)
    for slot, n in enumerate((20, 33)):
        adm = be.add_begin(slot, _tokens(n, seed=slot))
        while not be.add_step(adm):
            pass
        be.add_commit(adm, temperature=0.0)
    be.decode(4)
    be.decode(4)
    routed, held, tokens, kept = (getattr(ins, n).value() - before[n] for n in names)
    assert routed == 4 * tokens and tokens % 4 == 0  # 4 expert layers, 4 a token
    assert 0 < kept < tokens and 0 < held <= 4 * kept and held < routed
    # a decode step at position p reads p + 1 latent rows a layer
    read = ins.LAUNCH_KV_ROWS_READ.labels(kind="decode", pool="latent").value()
    assert read - read0 == sum((p + i + 1) for p in (20, 33) for i in range(8))


@pytest.mark.parametrize("kernels,attn,named", [
    ("xla", "jnp", False), ("pallas", "flash", True)])
def test_the_engine_names_the_latent_sweeps_plan(tiny, kernels, attn, named):
    """What `/debug/perf` reports as `paged_latent_plan`: the pass `_plan`
    sizes from the engine's shapes, for the decode call and the slice, on
    the kernel's latent route; None where the rows are gathered by XLA."""
    from dllama_tpu.ops.pallas import paged_attention as pa

    be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                     max_seq_len=256, kernels=kernels, attn_impl=attn, **ENGINE)
    if not named:
        assert be.latent_plan is None
        return
    assert set(be.latent_plan) == {"decode", "slice"}
    for plan in be.latent_plan.values():
        assert plan["pages_per_pass"] == pa._LATENT_PASS_PAGES
        assert plan["ring_passes"] == 2 and 0 < plan["vmem_bytes"] <= pa._VMEM_BUDGET_BYTES
    assert be.latent_plan["slice"]["vmem_bytes"] > be.latent_plan["decode"]["vmem_bytes"]


def test_a_shared_prefix_of_rotated_rows_is_the_cold_run(tiny):
    """The radix cache over the latent pool (the first model that has both:
    a recurrent state resolves it off): a prefix's pages hold rows rotated
    at their absolute positions, so a second request that maps them, and
    copy-on-writes the boundary page it diverges in, decodes what a cold
    engine decodes."""
    mk = lambda radix: BatchEngine(
        tiny.config, tiny.params, cache_dtype=jnp.float32, max_seq_len=256,
        **dict(ENGINE, radix_cache=radix))
    eng, solo = mk("on"), mk("off")
    assert eng.radix is not None and eng.rows_reenterable
    prompt = _tokens(40, seed=9)  # exactly 5 full pages of 8 rows
    for e in (eng, solo):
        e.add(0, prompt, temperature=0.0, seed=0)
    eng.radix_insert(0, prompt)
    eng.release(0)
    solo.release(0)
    assert eng.radix_stats()["pages"] == 5
    div = prompt[:36] + [70, 71, 72]  # diverge inside the fifth page
    rows, hit = eng.radix_lookup(div)
    assert rows == 36 and hit.part == 4
    eng.radix_map(1, hit)
    eng.add(1, div[rows:], temperature=0.0, seed=1, start_pos=rows)
    solo.add(1, div, temperature=0.0, seed=1)
    np.testing.assert_array_equal(eng.decode(4)[:, 1], solo.decode(4)[:, 1])
    assert eng.pool.audit()["ok"]
