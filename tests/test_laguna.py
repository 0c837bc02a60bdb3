"""Attention whose head count, rope and window go by the layer's kind (global:
6 query heads, YaRN over the leading half of a head; windowed: 8 query
heads, a 16-row window, a plain rope), QK-norm, a gate a head on the
attention output, a leading dense layer and one chip's share of
sigmoid-routed experts with a shared expert, against the plain reference
(`benchmark/reference/laguna.py`).

A tiny file is written through the benchmark's layout
(`benchmark/layouts/laguna.py`, `benchmark/tests/tiny-laguna.json`): 12
layers (G with the dense feed-forward, W W W, then G W W W twice: the ragged
schedule of the published 40 layers, a prefix and two periods), 2 kv heads
of 32, 4 held of 16 experts from offset 4 with 4 active. Weights are loaded
in float32 so that the serving path's own arithmetic reads against the
reference at 1e-6 and each control stands out.
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

from benchmark import files
from benchmark.layouts import laguna as layout
from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.models import formats
from dllama_tpu.models import llama as model
from dllama_tpu.models.config import HeaderKey, LlamaConfig, RopeSpec, RopeType
from dllama_tpu.models.llama import layer_schedule, ragged_schedule
from dllama_tpu.obs import instruments as ins
from dllama_tpu.ops import layers as ops
from dllama_tpu.ops.layers import apply_rope, build_rope_cache, rope_table, yarn_freqs
from dllama_tpu.ops.matmul import matmul
from tests import arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny-laguna.json")) as f:
    TINY = json.load(f)
#: CPU readings against arch.TOL, seed 5: sound 4e-7 to 2e-6 on both routes;
#: the controls 0.02 to 1.3
TOL, ENGINE, _tokens = arch.TOL, arch.ENGINE, arch.tokens


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return arch.tiny_file(tmp_path_factory, "laguna", TINY)


# ------------------------------------------------- files, header, plan


def test_header_round_trip_and_plan(tiny):
    cfg = tiny.config
    assert cfg.layer_windows == (0, 1, 1, 1) * 3 and cfg.window == 16
    assert cfg.layer_ffn == (1,) + (0,) * 11 and cfg.rope_type == RopeType.LLAMA
    assert (cfg.n_heads, cfg.window_heads, cfg.n_kv_heads, cfg.head_size) == (6, 8, 2, 32)
    assert (cfg.heads_of(False), cfg.heads_of(True)) == (6, 8)
    assert (cfg.attn_dim_of(False), cfg.attn_dim_of(True), cfg.attn_dim) == (192, 256, 192)
    assert (cfg.q_per_kv_of(False), cfg.q_per_kv_of(True), cfg.q_per_kv) == (3, 4, 3)
    assert cfg.qk_norm and cfg.attn_gate and cfg.router_sigmoid
    assert cfg.global_rope == RopeSpec(RopeType.YARN, 500000.0, 0.5, 4.0, 64,
                                       4.0, 1.0, 1.138629)
    assert (cfg.n_experts, cfg.experts_held, cfg.expert_offset,
            cfg.n_shared_experts, cfg.expert_width) == (16, 4, 4, 1, 256)
    assert abs(cfg.routed_scale - 2.5) < 1e-9 and cfg.norm_epsilon == 1e-6
    assert [cfg.kind_index(i) for i in range(6)] == [0, 0, 1, 2, 1, 3]
    assert LlamaConfig.from_header_kv(cfg.to_header_kv()) == cfg
    for word in ("heads=6g,8w/2", "global rope YARN theta=500000 share=0.5 x4",
                 "theta=10000 (window)", "qk_norm", "attn_gate=per_head"):
        assert word in cfg.describe()
    mine, header = layout.read_header(tiny.path)
    assert header == formats.read_header(tiny.path)[1]
    assert [(n, int(np.prod(shape))) for n, shape, _ in formats.tensor_plan(cfg)] == [
        (e.name, int(np.prod(e.shape))) for e in layout.tensor_plan(mine)]
    layers = tiny.params["layers"]
    # the attention stacks are stacked APART by kind
    assert layers["wq"].shape == (3, 256, 192) and layers["wq_win"].shape == (9, 256, 256)
    assert layers["wo"].shape == (3, 192, 256) and layers["wo_win"].shape == (9, 256, 256)
    assert layers["wk"].shape == (3, 256, 64) and layers["wv_win"].shape == (9, 256, 64)
    assert layers["attn_gate"].shape == (3, 256, 6) and layers["attn_gate_win"].shape == (9, 256, 8)
    assert layers["q_norm"].shape == (3, 32) and layers["k_norm_win"].shape == (9, 32)
    assert layers["attn_gate"].dtype == jnp.float32
    assert layers["w1"].shape == (1, 256, 512) and layers["moe_w1"].shape == (11, 4, 256, 256)
    fused = model.fuse_layer_weights(layers)
    assert fused["wqkv"].shape == (3, 256, 320) and fused["wqkv_win"].shape == (9, 256, 384)


@pytest.mark.parametrize("key,field,value", [
    (HeaderKey.WINDOW_HEADS, "window_heads", 8),
    (HeaderKey.QK_NORM, "qk_norm", True),
    (HeaderKey.ATTN_GATE, "attn_gate", True),
    (HeaderKey.GLOBAL_ROPE_TYPE, "global_rope",
     RopeSpec(RopeType.YARN, 500000.0, 0.5, 64.0, 4096, 64.0, 1.0, 1.415888)),
    (HeaderKey.GLOBAL_ROPE_SHARE_X1E6, "global_rope", RopeSpec(share=0.25)),
])
def test_every_new_key_round_trips_and_is_absent_by_default(key, field, value):
    base = dict(dim=64, hidden_dim=128, n_layers=4, n_heads=4, n_kv_heads=2,
                vocab_size=100, seq_len=32, head_dim=16, window=8,
                layer_windows=(0, 1, 1, 1))
    plain = LlamaConfig(**base)
    assert not any(160 <= k < 180 for k, _ in plain.to_header_kv())
    cfg = LlamaConfig(**base, **{field: value})
    assert int(key) in dict(cfg.to_header_kv())
    again = LlamaConfig.from_header_kv(cfg.to_header_kv())
    assert again == cfg and getattr(again, field) == value


def test_a_header_without_the_new_keys_means_what_it_meant():
    llama = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=100, seq_len=32)
    assert max(k for k, _ in llama.to_header_kv()) < 100
    again = LlamaConfig.from_header_kv(llama.to_header_kv())
    assert (again.window_heads, again.qk_norm, again.attn_gate,
            again.global_rope) == (0, False, False, None)
    assert (again.heads_of(True), again.attn_dim_of(True), again.q_per_kv_of(True),
            again.attn_suffix(True)) == (4, 64, 2, "")
    assert [n for n, _, _ in formats.tensor_plan(again)][1:5] == [
        "layers.0.wq", "layers.0.wk", "layers.0.wv", "layers.0.wo"]
    assert isinstance(build_rope_cache(again), jax.Array)
    windowed = dict(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                    vocab_size=100, seq_len=32, head_dim=16)
    with pytest.raises(ValueError):  # heads of their own for layers that are not
        LlamaConfig(**windowed, window_heads=8)
    with pytest.raises(ValueError):  # not whole groups of kv heads
        LlamaConfig(**windowed, window=8, layer_windows=(0, 1), window_heads=5)
    with pytest.raises(ValueError):  # a second table for a model that does not rotate
        LlamaConfig(**windowed, rope_type=RopeType.NONE, global_rope=RopeSpec())
    with pytest.raises(ValueError):  # an odd number of rotated dims
        LlamaConfig(**windowed, global_rope=RopeSpec(share=0.45))


@pytest.mark.parametrize("seed,sha", [(7, "f55c399c"), (2147483659, "4380d34e")])
def test_the_layout_writes_the_bytes_it_wrote(seed, sha, tmp_path):
    path = str(tmp_path / "m.m")
    files.write_model(path, TINY, seed)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest().startswith(sha)


def test_the_published_pattern_is_a_prefix_and_two_bodies():
    """G+dense, W W W, then (G, W W W) nine times: four layer bodies, where
    whole periods would hold twenty runs."""
    g, w, dense = 0, 4, 16
    kinds = (g + dense, w, w, w) + (g, w, w, w) * 9
    assert len(layer_schedule(kinds)[1]) == 20
    prefix, pattern, lengths = ragged_schedule(kinds)
    assert prefix == [(g + dense, 0, 1), (w, 1, 3)] and pattern == [g, w]
    assert lengths.tolist() == [[1, 3]] * 9


# --------------------------------------------------------- the rope tables


def test_yarn_frequencies_at_the_published_sizes():
    """Rotary dim 64, base 5e5, factor 64 from 4,096, beta 64 / 1: plain up
    to index 5, interpolated from 16 on, the linear ramp between."""
    spec = RopeSpec(RopeType.YARN, 500000.0, 0.5, 64.0, 4096, 64.0, 1.0,
                    1.4158883083359672)
    plain = 1.0 / (5e5 ** (np.arange(32) * 2.0 / 64))
    ratio = yarn_freqs(spec, 64) / plain
    np.testing.assert_allclose(ratio[:6], 1.0)
    np.testing.assert_allclose(ratio[16:], 1 / 64)
    np.testing.assert_allclose(ratio[6:16], 1 - np.arange(1, 11) / 11 * (1 - 1 / 64))
    table = rope_table(spec, 128, 8)
    assert table.shape == (8, 32, 2)
    np.testing.assert_allclose(table[0, :, 0], 1.4158883083359672, rtol=1e-6)
    np.testing.assert_allclose(table[3, 20], 1.4158883083359672 * np.asarray(
        [np.cos(3 * plain[20] / 64), np.sin(3 * plain[20] / 64)]), rtol=1e-5)


def test_a_partial_table_rotates_the_leading_dims_and_passes_the_rest():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 5, 3, 32)), jnp.float32)
    table = rope_table(RopeSpec(theta=10000.0, share=0.5), 32, 5)
    out = apply_rope(x, table)
    assert (out[..., 16:] == x[..., 16:]).all()
    np.testing.assert_allclose(out[..., :16], apply_rope(x[..., :16], table))
    per_row = jnp.broadcast_to(table[None], (2, 5, 8, 2))
    np.testing.assert_allclose(apply_rope(x, per_row), out)
    whole = rope_table(RopeSpec(theta=10000.0), 32, 5)
    np.testing.assert_allclose(whole, build_rope_cache(LlamaConfig(
        dim=64, hidden_dim=64, n_layers=1, n_heads=2, n_kv_heads=2,
        vocab_size=8, seq_len=5)), rtol=1e-6)


# ---------------------------------------- against the reference, by route


@pytest.mark.parametrize("kernels,attn,route", [
    ("xla", "jnp", "xla/paged_gather.window.heads6g8w.ropes2+moe_jnp"),
    # float32 activations: the paged sweep at both folds in interpret mode;
    # the grouped expert kernel takes bfloat16 rows only
    ("pallas", "flash", "pallas/paged_kernel.window.heads6g8w.ropes2+moe_jnp"),
])
def test_prefill_decode_and_tail_match_the_reference(tiny, kernels, attn, route):
    """Prefill in 16-row slices, decode steps through both page pools past
    the 16-row window (pages handed back), a tail on the kept rows."""
    out = arch.run_check(tiny, TINY, kernels, attn)
    assert out["route"] == route
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}
    assert out["rel_l2_max"] < 2e-5


def test_stated_precision_runs_the_kernels_at_two_folds(tiny):
    """bfloat16 activations, every kernel in interpret mode (the paged sweep
    at folds 3 and 4, the grouped expert kernel): bf16's own rounding."""
    out = arch.run_check(arch.loaded(tiny.path, jnp.bfloat16), TINY, "pallas", "flash",
                         tolerances={"rel_l2_mean": 0.05, "deficit_sigma_mean": 0.03})
    assert out["route"] == "pallas/paged_kernel.window.heads6g8w.ropes2+moe_grouped"
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}


# ------------------------------------------------------------ the controls


@pytest.fixture(scope="module")
def sixty(tiny):
    return arch.sixty(tiny, TINY)


#: name -> (config fields replaced, what else is done)
CONTROLS = {
    "the gate left out": (dict(attn_gate=False), None),
    "QK-norm left out": (dict(qk_norm=False), None),
    "the YaRN factor ignored": ("rope", dict(factor=1.0)),
    "half rotation made whole": ("rope", dict(share=1.0)),
    "the two rope tables swapped": ({}, "swap"),
    "the window ignored": (dict(window=4096), None),
    "the scaling factor read as 1": (dict(routed_scale=1.0), None),
    "the shared expert dropped": (dict(n_shared_experts=0), "shared"),
}


@pytest.mark.parametrize("control", [None, *CONTROLS])
def test_each_control_fails_the_tolerance_the_sound_model_holds(
        tiny, sixty, control):
    """One forward over 60 tokens on the dense jnp route: the model as the
    header says it reads 1e-6 against the reference, and each single
    departure from the equations is refused by 100 x the limit."""
    seq, want = sixty
    if control is None:
        assert arch.logits_rel_l2(tiny.params, tiny.config, seq, want) < TOL["rel_l2_mean"]
        return
    fields, what = CONTROLS[control]
    params, rope = tiny.params, None
    if fields == "rope":
        fields = dict(global_rope=dataclasses.replace(tiny.config.global_rope, **what))
    elif what == "shared":
        params = dict(params, layers={k: v for k, v in params["layers"].items()
                                      if not k.startswith("shared_")})
    elif what == "swap":
        # each kind handed the other's rows: the window kind's table covers
        # the whole head, the global kind's its leading half
        g, w = build_rope_cache(tiny.config, 128)
        rope = (w, g)
    cfg = dataclasses.replace(tiny.config, **fields)
    err = arch.logits_rel_l2(params, cfg, seq, want, rope)
    assert err > 100 * TOL["rel_l2_mean"], err


# -------------------------------------------------- one chip's share


def test_the_reference_shares_add_up_with_the_shared_expert_once(tmp_path):
    """The four shares' routed parts (offsets 0 / 4 / 8 / 12 of 16: the
    published 0 / 64 / 128 / 192 of 256 at this size's scale) and the shared
    expert counted once are the uncut reference's whole layer."""
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


def test_the_program_shares_add_up_to_the_uncut_reference(tmp_path):
    """The same through the PROGRAM's expert layer: an uncut file's layer 3
    run as each of the four shares (the held range cut out of the loaded
    stacks), the shared expert once, against the uncut reference."""
    ref = importlib.import_module(TINY["reference"])
    uncut = {k: v for k, v in TINY.items() if k != "deployment"}
    uncut["num_experts"] = 16
    path = str(tmp_path / "uncut.m")
    files.write_model(path, uncut, 5)
    cfg, header = formats.read_header(path, 256)
    layers = formats.load_params(path, cfg, header, dtype=jnp.float32)["layers"]
    s, views = layout.tensor_views(path)
    h = jnp.asarray(np.random.default_rng(4).standard_normal((1, 10, 256)), jnp.float32)
    fi = cfg.ffn_index(3)
    with jax.default_matmul_precision("highest"):
        whole = ref.ffn_block(s, views, 3, h[0]) - h[0]
        n = ops.rms_norm(h, layers["rms_ffn"][3], cfg.norm_epsilon)
        logits = ops.router_logits(n, layers["moe_gate"][fi])
        total = 0.0
        for lo in (0, 4, 8, 12):
            share = dataclasses.replace(cfg, experts_held=4, expert_offset=lo,
                                        n_shared_experts=0)
            cut = {k: (jax.tree.map(lambda a: a[:, lo:lo + 4], v)
                       if k in ("moe_w1", "moe_w2", "moe_w3") else v)
                   for k, v in layers.items() if not k.startswith("shared_")}
            total = total + model._mlp(share, n, cut, fi, matmul, matmul,
                                       "auto", True, logits)
        keep = {k: v for k, v in layers.items() if k.startswith("shared_")}
        gate = jax.nn.silu(matmul(n, keep["shared_w1"], fi))
        total = total + matmul(gate * matmul(n, keep["shared_w3"], fi),
                                   keep["shared_w2"], fi)
    np.testing.assert_allclose(total[0], whole, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ the engine


def test_the_engine_counts_rows_walked_by_kind_and_pages_by_pool(tiny):
    walked = lambda kind: ins.ATTN_ROWS_WALKED.labels(kind=kind).value()
    names = ("MOE_ROWS_ROUTED", "MOE_ROWS_HELD")
    before = {n: getattr(ins, n).value() for n in names}
    g0, w0 = walked("global"), walked("window")
    be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                     max_seq_len=256, **ENGINE)
    assert be.radix is None  # two page lists a slot: no prefix is one list
    assert be.cache.k.shape == (3, 121, 2, 8, 32) and be.cache.kw.shape[0] == 9
    # the window pool: (16 + 16) / 8 + 1 pages a slot, whatever --kv-pages
    assert be.wpool.n_pages == 4 * 5 and be.cache.kw.shape[1] == 21
    for slot, n in enumerate((40, 70)):
        adm = be.add_begin(slot, _tokens(n, seed=slot))
        while not be.add_step(adm):
            pass
        be.add_commit(adm, temperature=0.0)
    g1, w1 = walked("global"), walked("window")
    be.decode(4)
    be.decode(4)
    # a decode step at position p reads p + 1 rows on each of the 3 global
    # layers and the 16 of its window on each of the 9 windowed ones
    assert walked("global") - g1 == 3 * sum(p + i + 1 for p in (40, 70) for i in range(8))
    assert walked("window") - w1 == 9 * 16 * 2 * 8
    assert (g1, w1) == (g0, w0)  # decode steps only, as the rows-read counter
    routed, held = (getattr(ins, n).value() - before[n] for n in names)
    assert routed % (11 * 4) == 0 and 0 < held < routed
    health = be.pool_report()
    assert health["global"]["pages"] == 120 and health["window"]["pages"] == 20
    assert health["global"]["bytes"] == 3 * 2 * 121 * 2 * 8 * 32 * 4
    assert health["window"]["bytes"] == 9 * 2 * 21 * 2 * 8 * 32 * 4
    assert health["window"]["layers"] == 9 and health["global"]["layers"] == 3
