"""Delta-rule linear-attention layers (KDA) with a matrix state a head,
unrotated latent attention (MLA) over a latent-row cache, a sigmoid router
with a selection bias, a shared expert and a leading dense layer, served as
ONE CHIP'S SHARE of each expert layer, against the plain reference
(`benchmark/reference/kimi_linear.py`).

A tiny file is written through the benchmark's layout
(`benchmark/layouts/kimi_linear.py`, `benchmark/tests/tiny-kimilinear.json`):
11 layers (a dense-FFN KDA layer, then K K M, K K K M, K K M), 8 heads of
128 x 128 state, latent 64 + 32, 4 held of 16 experts from offset 4 with 4
active. Weights are loaded in float32 so that the serving path's own
arithmetic reads against the reference at 1e-6 and each control stands out;
the stated precision (bf16 activations, the grouped Q40 expert kernel)
reads at bf16's rounding.
"""

import dataclasses
import hashlib
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, files
from benchmark.layouts import kimi_linear as layout
from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.models import formats
from dllama_tpu.models import llama as model
from dllama_tpu.models.config import LayerKind, LlamaConfig, RopeType
from dllama_tpu.models.llama import KVCache, forward, layer_schedule, ragged_schedule
from dllama_tpu.obs import instruments as ins
from dllama_tpu.ops import delta
from dllama_tpu.ops.layers import build_rope_cache, latent_attention, moe_ffn
from dllama_tpu.ops.pallas.kda_step import kda_step
from dllama_tpu.ops.pallas.paged_attention import paged_decode_attention
from dllama_tpu.ops.quant import QTensor
from tests import arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny-kimilinear.json")) as f:
    TINY = json.load(f)
#: CPU readings against arch.TOL, seed 5: sound 1.5e-6 to 4e-6 on both
#: routes; the controls 0.02 to 1.2
TOL, ENGINE, _tokens = arch.TOL, arch.ENGINE, arch.tokens


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return arch.tiny_file(tmp_path_factory, "delta_latent", TINY)


# ------------------------------------------------- files, header, plan


def test_header_round_trip_and_plan(tiny):
    cfg = tiny.config
    k, m = int(LayerKind.KDA), int(LayerKind.MLA)
    assert cfg.layer_kinds == (k, k, k, m, k, k, k, m, k, k, m)
    assert cfg.layer_ffn == (1,) + (0,) * 10 and cfg.rope_type == RopeType.NONE
    assert (cfg.n_kda_layers, cfg.n_attn_layers, cfg.n_state_layers) == (8, 3, 8)
    assert cfg.recurrent and cfg.latent and cfg.router_sigmoid
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset,
            cfg.n_shared_experts, cfg.expert_width) == (16, 4, 4, 1, 256)
    assert (cfg.state_shape, cfg.state_conv) == ((8, 128, 128), (3, 3 * 1024))
    assert (cfg.cache_kv_heads, cfg.cache_row) == (1, 96)
    assert abs(cfg.routed_scale - 2.446) < 1e-9
    assert [cfg.ffn_index(i) for i in range(4)] == [0, 0, 1, 2]
    assert LlamaConfig.from_header_kv(cfg.to_header_kv()) == cfg
    mine, header = layout.read_header(tiny.path)
    assert header == formats.read_header(tiny.path)[1]
    assert [(n, tuple(np.prod(s) for s in [shape])) for n, shape, _ in
            formats.tensor_plan(cfg)] == [
        (e.name, (np.prod(e.shape),)) for e in layout.tensor_plan(mine)]
    layers = tiny.params["layers"]
    assert layers["kda_proj"].shape == (8, 256, 3584)  # 3,336 columns padded
    assert layers["mla_kva"].shape == (3, 256, 128) and layers["w1"].shape == (1, 256, 512)
    assert layers["mla_kvb"].shape == (3, 8, 64, 64) and layers["mla_kvb"].dtype == jnp.float32
    assert layers["moe_gate"].shape == (10, 256, 16) and layers["moe_bias"].shape == (10, 16)
    assert layers["moe_w1"].shape == (10, 4, 256, 256)
    assert layers["shared_w2"].shape == (10, 256, 256)


def test_a_header_without_the_new_keys_means_what_it_meant():
    llama = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=100, seq_len=32)
    assert max(k for k, _ in llama.to_header_kv()) < 100
    again = LlamaConfig.from_header_kv(llama.to_header_kv())
    assert (again.layer_ffn, again.experts_held, again.router_sigmoid,
            again.latent, again.recurrent, again.schedule_kinds) == (
        (), 0, False, False, False, ())
    assert (again.cache_kv_heads, again.cache_row) == (2, 16)
    with pytest.raises(ValueError):  # two kinds of recurrent state
        LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                    vocab_size=100, seq_len=32, layer_kinds=(1, 2), kda_heads=2,
                    kda_rank=8)
    with pytest.raises(ValueError):  # rotated latent attention with no shared dims
        LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                    vocab_size=100, seq_len=32, layer_kinds=(3, 3), kv_lora_rank=8)
    with pytest.raises(ValueError):  # a share outside the experts
        LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                    vocab_size=100, seq_len=32, n_experts=8, n_active_experts=2,
                    experts_held=4, expert_offset=6)


@pytest.mark.parametrize("seed,sha", [(7, "48256e1a"), (2147483659, "211fcac8")])
def test_the_layout_writes_the_bytes_it_wrote(seed, sha, tmp_path):
    path = str(tmp_path / "m.m")
    files.write_model(path, TINY, seed)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest().startswith(sha)


def test_schedules():
    """The accepted patterns keep their whole periods; a leading prefix and
    a cut-short period become three bodies, not fifteen."""
    a, s, g, w = 0, 1, 8, 4
    assert ragged_schedule((a,) * 30) is None
    assert ragged_schedule((s, s, s, s, s, a, s, s, s, s) * 4) is None
    assert ragged_schedule((g, w, w, w) * 6) is None
    kd, k, m = 18, 2, 3
    kinds = (kd, k, k, m) + (k, k, k, m) * 5 + (k, k, m)
    assert len(layer_schedule(kinds)[1]) == 15
    prefix, pattern, lengths = ragged_schedule(kinds)
    assert prefix == [(kd, 0, 1)] and pattern == [k, m]
    assert lengths.tolist() == [[2, 1]] + [[3, 1]] * 5 + [[2, 1]]


# ---------------------------------------- against the reference, by route


@pytest.mark.parametrize("kernels,attn,route", [
    ("xla", "jnp", "xla/paged_gather.latent+kda_jnp.float32+moe_jnp"),
    # float32 activations: the latent paged sweep and `_kda_step` in
    # interpret mode; the grouped expert kernel takes bfloat16 rows only
    ("pallas", "flash", "pallas/paged_kernel.latent+kda_step.float32+moe_jnp"),
])
def test_prefill_decode_and_tail_match_the_reference(tiny, kernels, attn, route):
    out = arch.run_check(tiny, TINY, kernels, attn)
    assert out["route"] == route
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}
    assert out["rel_l2_max"] < 2e-5


def test_stated_precision_runs_the_three_kernels(tiny):
    """bfloat16 activations, every kernel in interpret mode: bf16's own
    rounding (CPU reading 0.013; the jnp route in bf16 reads 0.015)."""
    out = arch.run_check(arch.loaded(tiny.path, jnp.bfloat16), TINY, "pallas", "flash",
                         tolerances={"rel_l2_mean": 0.04, "deficit_sigma_mean": 0.02})
    assert out["route"] == "pallas/paged_kernel.latent+kda_step.float32+moe_grouped"
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}


# ------------------------------------------------------------ the controls


@pytest.fixture(scope="module")
def sixty(tiny):
    return arch.sixty(tiny, TINY)


_decay = delta.decay


def _decay_per_head(f_raw, dt_bias, a_log, heads):
    g = _decay(f_raw, dt_bias, a_log, heads)
    return jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)


def _no_delta_step(s, q, k, v, g, beta):
    """Plain gated linear attention: the write is k v^T, nothing taken out."""
    s = jnp.exp(g)[..., None] * s + k[..., None] * v[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def _bias_in_the_weights(cfg, h, gate, w1, w2, w3, *, logits, bias, **kw):
    s = jnp.clip(jax.nn.sigmoid(logits) + bias, 1e-6, 1 - 1e-6)
    return moe_ffn(cfg, h, gate, w1, w2, w3, logits=jnp.log(s / (1 - s)),
                   bias=None, **kw)


def _k_pe_dropped(q, rows, pos_base, scale, rank):
    return latent_attention(q.at[..., rank:].set(0), rows, pos_base, scale, rank)


#: name -> (config fields replaced, (module, attribute, replacement) patched)
CONTROLS = {
    "decay per head, not per channel": ({}, (delta, "decay", _decay_per_head)),
    "beta left out (gated linear attention)": ({}, (delta, "kda_step_ref", _no_delta_step)),
    "softmax for sigmoid scores": (dict(router_sigmoid=False), None),
    "the selection bias used in the weights": ({}, (model, "moe_ffn", _bias_in_the_weights)),
    "k_pe dropped from the score": ({}, (model, "latent_attention", _k_pe_dropped)),
    "layer 1 given experts": (dict(layer_ffn=()), None),
    "the routed sum left unscaled": (dict(routed_scale=1.0), None),
    "the shared expert left out": (dict(n_shared_experts=0), "shared"),
}


@pytest.mark.parametrize("control", [None, *CONTROLS])
def test_each_control_fails_the_tolerance_the_sound_model_holds(
        tiny, sixty, control, monkeypatch):
    """One forward over 60 tokens on the dense jnp route: the model as the
    header says it reads 1e-6 against the reference, and each single
    departure from the equations is refused by 100 x the limit."""
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
    assert err > 100 * TOL["rel_l2_mean"], err


def test_a_bfloat16_state_drifts_over_256_steps(tiny):
    """S is a running product-and-sum over the whole context: held in
    bfloat16 it is rounded once a step. 256 decode steps, token by token
    through the dense cache: float32 reads 1e-6, bfloat16 past 100 x the
    limit."""
    ref = importlib.import_module(TINY["reference"])
    seq = np.asarray(_tokens(256, seed=9), np.int32)
    want = ref.logits_at(tiny.path, [seq], [[255]])[0][0]
    cfg, rope = tiny.config, build_rope_cache(tiny.config, 256)
    step = jax.jit(lambda tok, pos, cache: forward(cfg, tiny.params, tok, pos,
                                                   cache, rope))
    read = {}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        cache = KVCache.create(cfg, 1, jnp.float32, 256, state_dtype=dtype,
                               conv_dtype=jnp.float32)
        for pos in range(256):
            logits, cache = step(jnp.asarray(seq[None, pos:pos + 1]), pos, cache)
        read[name] = check.rel_l2(np.asarray(logits[0, -1]), want)
    assert read["float32"] < TOL["rel_l2_mean"], read
    assert read["bfloat16"] > 100 * TOL["rel_l2_mean"], read


# ---------------------------------------------------- the delta-rule step


@pytest.fixture(scope="module")
def kda_rows():
    rng = np.random.default_rng(1)
    b, t, h, d = 3, 12, 8, 128
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q = delta.l2norm(f(b, t, h, d)) * d ** -0.5
    k, v = delta.l2norm(f(b, t, h, d)), f(b, t, h, d)
    g = -jnp.exp(f(b, t, h, d) - 3.0)
    beta = jax.nn.sigmoid(f(b, t, h))
    return f(b, h, d, d) * 0.1, q, k, v, g, beta


def test_the_scanned_slice_is_the_step_repeated(kda_rows):
    s0, q, k, v, g, beta = kda_rows
    o_scan, s_scan = delta.kda_scan(s0, q, k, v, g, beta)
    s, outs = s0, []
    for i in range(q.shape[1]):
        o, s = delta.kda_step_ref(s, q[:, i], k[:, i], v[:, i], g[:, i], beta[:, i])
        outs.append(o)
    np.testing.assert_allclose(o_scan, jnp.stack(outs, 1), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(s_scan, s, rtol=1e-6, atol=1e-6)
    # the rule itself, written out for one head of one row
    S = np.asarray(s0[0, 0], np.float64)
    kk, vv, qq = (np.asarray(x[0, 0, 0], np.float64) for x in (k, v, q))
    S = np.exp(np.asarray(g[0, 0, 0], np.float64))[:, None] * S
    S = S + float(beta[0, 0, 0]) * np.outer(kk, vv - S.T @ kk)
    np.testing.assert_allclose(outs[0][0, 0], S.T @ qq, rtol=1e-4, atol=1e-5)


def test_the_pallas_step_is_the_step_in_place_on_the_stack(kda_rows):
    """Interpret mode: layer 1 of a 3-layer stack advances (mode 1), starts
    from zero (mode 2) or is left bit-equal (mode 0); the other layers are
    untouched."""
    s0, q, k, v, g, beta = kda_rows
    stack = jnp.stack([s0 * 2, s0, s0 * 3])
    mode = jnp.asarray([1, 2, 0], jnp.int32)
    o, new = kda_step(stack, 1, q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]),
                      beta[:, 0], mode, interpret=True)
    start = jnp.where((mode == 2)[:, None, None, None], 0.0, s0)
    o_ref, s_ref = delta.kda_step_ref(start, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0])
    np.testing.assert_allclose(o[:2], o_ref[:2], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(new[1, :2], s_ref[:2], rtol=1e-5, atol=1e-6)
    assert (new[1, 2] == s0[2]).all() and (o[2] == 0).all()
    assert (new[0] == stack[0]).all() and (new[2] == stack[2]).all()


# ------------------------------------------------------- the latent page


@pytest.mark.parametrize("pos", [(0, 5, 17), (63, 71, 160)],
                         ids=["1-3-pages", "8-21-pages"])
@pytest.mark.parametrize("t", [1, 24])
def test_the_latent_sweep_reads_one_row_for_score_and_value(t, pos):
    """The paged kernel's latent mode (interpret) against the jnp form: a
    decode step with the fused scatter (t = 1) and a prefill slice
    scattered by XLA first (t = 24), slots at different lengths: runs of
    1 to 3 pages (less than one pass of the sweep) and of 8 to 21 (a whole
    pass, one page past it, two passes and a part of the third: PR 48)."""
    rng = np.random.default_rng(2)
    b, h, rank, pe, page, nb, layers = 3, 8, 64, 32, 8, 24, 2
    w, lanes, span = rank + pe, 128, 24 * 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool = jnp.zeros((layers, b * nb + 1, 1, page, lanes), jnp.float32)
    placeholder = jnp.zeros((layers, 1, 1, 8, 128), jnp.float32)
    tables = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    pos = jnp.asarray(pos, jnp.int32)
    rows = f(b, span, w)  # the history, written row by row into layer 1
    for bi in range(b):
        n = int(pos[bi])
        paged = jnp.zeros((nb * page, lanes)).at[:n, :w].set(rows[bi, :n])
        pool = pool.at[1, tables[bi], 0].set(paged.reshape(nb, page, lanes))
    q, new = f(b, t, h, w), f(b, 1, t, w)
    out, pool2, ph2 = paged_decode_attention(
        q, pool, placeholder, tables, pos, new, None, None, layer=1,
        interpret=True, latent=rank, scale=0.125)
    hist = jnp.stack([jnp.concatenate(
        [rows[bi, :int(pos[bi])], new[bi, 0], jnp.zeros((span - int(pos[bi]), w))])[:span + t]
        for bi in range(b)])
    want = latent_attention(q, hist, pos, 0.125, rank)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert out.shape == (b, t, h, rank) and ph2.shape == placeholder.shape
    assert (pool2[0] == pool[0]).all()  # the other layer's pages are untouched
    for bi in range(b):  # the new rows stand in the pool at their positions
        r = int(pos[bi]) + t - 1
        np.testing.assert_allclose(
            pool2[1, tables[bi, r // page], 0, r % page, :w], new[bi, 0, t - 1])


# -------------------------------------------------- one chip's share


@pytest.fixture(scope="module")
def experts():
    rng = np.random.default_rng(0)
    d, f, e = 256, 256, 16
    cfg = LlamaConfig(dim=d, hidden_dim=f, n_layers=2, n_heads=2, n_kv_heads=1,
                      vocab_size=64, seq_len=32, n_experts=e, n_active_experts=4,
                      router_sigmoid=True, routed_scale=2.446)

    def stack(k, n):
        one = lambda: QTensor.quantize(
            (rng.standard_normal((k, n)) * 0.05).astype(np.float32))
        layer = lambda: jax.tree.map(lambda *x: jnp.stack(x), *[one() for _ in range(e)])
        return jax.tree.map(lambda *x: jnp.stack(x), layer(), layer())

    bias = jnp.asarray(rng.uniform(-0.1, 0.1, e), jnp.float32)
    return cfg, (stack(d, f), stack(f, d), stack(d, f)), bias, rng


def _share(ws, lo, n):
    return tuple(jax.tree.map(lambda a: a[:, lo:lo + n], w) for w in ws)


def test_the_four_shares_add_up_to_the_uncut_layer(experts):
    cfg, ws, bias, rng = experts
    h = jnp.asarray(rng.standard_normal((2, 6, cfg.dim)), jnp.float32)
    logits = jnp.asarray(rng.standard_normal((2, 6, cfg.n_experts)), jnp.float32)
    whole = moe_ffn(cfg, h, None, *ws, impl="dense", logits=logits, layer=1, bias=bias)
    parts, held_rows = 0.0, 0
    for lo in (0, 4, 8, 12):
        share = dataclasses.replace(cfg, experts_held=4, expert_offset=lo)
        out, stats = moe_ffn(share, h, None, *_share(ws, lo, 4), impl="auto",
                             logits=logits, layer=1, bias=bias,
                             stats=jnp.zeros(5, jnp.uint32))
        parts = parts + out
        held_rows += int(stats[0])
        assert int(stats[4]) == 2 * 6 * 4 and int(stats[1]) <= 4
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-6)
    assert held_rows == 2 * 6 * 4  # every routed row lands on exactly one share


def test_the_reference_shares_add_up_with_the_shared_expert_once(tmp_path):
    """The same in the reference, on an uncut tiny file: four shares' routed
    parts and the shared expert counted once are the uncut layer."""
    ref = importlib.import_module(TINY["reference"])
    uncut = {k: v for k, v in TINY.items() if k != "deployment"}
    uncut["num_experts"] = 16
    path = str(tmp_path / "uncut.m")
    files.write_model(path, uncut, 5)
    s, views = layout.tensor_views(path)
    assert s["experts_held"] == 0 and s["held"] == 16
    h = jnp.asarray(np.random.default_rng(4).standard_normal((10, 256)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.ffn_block(s, views, 3, h) - h
        parts = sum(ref.ffn_block(s, views, 3, h, share=(lo, 4), shared=False) - h
                    for lo in (0, 4, 8, 12))
        shared = ref.ffn_block(s, views, 3, h, share=(0, 0)) - h
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(shared).max()) > 0.01 < float(jnp.abs(parts).max())


@pytest.mark.parametrize("case", ["decode batch", "a slice", "no row lands here"])
def test_grouped_kernel_over_the_held_experts_matches_dense(experts, case):
    cfg, ws, bias, rng = experts
    share = dataclasses.replace(cfg, experts_held=4, expert_offset=8)
    b, t = {"decode batch": (6, 1), "a slice": (1, 40), "no row lands here": (2, 1)}[case]
    h = jnp.asarray(rng.standard_normal((b, t, cfg.dim)), jnp.bfloat16)
    logits = jnp.asarray(rng.standard_normal((b, t, cfg.n_experts)), jnp.float32)
    if case == "no row lands here":
        logits = logits.at[..., 8:12].set(-20.0)
    got, stats = moe_ffn(share, h, None, *_share(ws, 8, 4), impl="grouped",
                         logits=logits, layer=1, bias=bias,
                         stats=jnp.zeros(5, jnp.uint32))
    want = moe_ffn(share, h, None, *_share(ws, 8, 4), impl="dense",
                   logits=logits, layer=1, bias=bias)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 0.02 * np.abs(want).max() + 1e-3
    chosen = np.asarray(jax.lax.top_k(jax.nn.sigmoid(logits) + bias, 4)[1])
    mine = chosen[(chosen >= 8) & (chosen < 12)]
    sizes = np.bincount(mine - 8, minlength=4)
    assert stats.tolist() == [len(mine), int((sizes > 0).sum()), 1,
                              int(sizes.max()), b * t * 4]
    if case == "no row lands here":
        assert len(mine) == 0 and not got.any()


# ------------------------------------------------------------ the engine


def test_the_engine_counts_and_resolves_off_what_the_state_cannot_follow(tiny):
    names = ("MOE_ROWS_ROUTED", "MOE_ROWS_HELD", "MOE_ASSIGNMENTS")
    before = {n: getattr(ins, n).value() for n in names}
    read0 = ins.LAUNCH_KV_ROWS_READ.labels(kind="decode", pool="latent").value()
    be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                     max_seq_len=256, **ENGINE)
    assert be.radix is None and not be.rows_reenterable
    with pytest.raises(ValueError):
        BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                    max_seq_len=256, **dict(ENGINE, radix_cache="on"))
    state = be.cache.state
    assert state.s.shape == (8, 4, 8, 128, 128) and state.s.dtype == jnp.float32
    assert state.conv.shape == (8, 4, 3, 3072)
    assert be.cache.k.shape == (3, 121, 1, 8, 96) and be.cache.v.size == 3 * 8 * 128
    assert ins.RECURRENT_STATE_BYTES.value() == state.nbytes
    for slot, n in enumerate((20, 33)):
        adm = be.add_begin(slot, _tokens(n, seed=slot))
        while not be.add_step(adm):
            pass
        be.add_commit(adm, temperature=0.0)
    be.decode(4)
    be.decode(4)
    routed, held, assigned = (getattr(ins, n).value() - before[n] for n in names)
    assert routed > 0 and held == assigned and 0 < held < routed
    assert routed % (10 * 4) == 0  # 10 expert layers x 4 choices a row
    # a decode step at position p reads p + 1 latent rows a layer
    read = ins.LAUNCH_KV_ROWS_READ.labels(kind="decode", pool="latent").value()
    assert read - read0 == sum((p + i + 1) for p in (20, 33) for i in range(8))

