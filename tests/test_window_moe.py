"""Attention layers of two kinds (windowed with rope, global without) over
routed ReLU-gated experts, through the serving path, against the plain
reference (`benchmark/reference/smallthinker.py`).

A tiny file is written through the benchmark's layout
(`benchmark/layouts/smallthinker.py`, `benchmark/tests/tiny-smallthinker.json`):
two periods of `g w w w`, window 16 over pages of 8, 8 experts with 3
active, 3 x 128 attention lanes over a 256-wide stream. Weights are loaded
in float32 so that the serving path's own arithmetic reads against the
reference at 1e-6 and each control stands out; the stated precision (bf16
activations, the grouped Q40 expert kernel) reads at bf16's rounding.

What is held: prefill + 64 batched decode steps past the window + a tail
chunk on handed-back pages against the reference, on the jnp route and on
the kernels in interpret mode; four controls that must FAIL the tolerance;
the grouped kernel against `moe_ffn(impl="dense")` at its edge cases; window
pages go back to their pool and the trash entry is never read; the hybrid
launch bit-exact against the split phases; header round trip and the
accepted layouts' bytes; the expert counters add up; what cannot follow two
page lists is refused or off.
"""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import files
from benchmark.layouts import smallthinker as layout
from dllama_tpu.engine.batch import BatchEngine, StateNotResumable
from dllama_tpu.models import formats
from dllama_tpu.models.config import (
    SCHEDULE_UNROTATED,
    SCHEDULE_WINDOWED,
    HiddenAct,
    LlamaConfig,
)
from dllama_tpu.obs import instruments as ins
from dllama_tpu.ops.layers import expert_groups, expert_rows, moe_ffn
from dllama_tpu.ops.quant import QTensor
from tests import arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny-smallthinker.json")) as f:
    TINY = json.load(f)
#: CPU readings against arch.TOL, seed 5: sound 8e-7 to 1e-6 on both routes;
#: the four controls 0.03 to 1.1 (PERF.md section 4)
TOL, ENGINE, _tokens = arch.TOL, arch.ENGINE, arch.tokens
WINDOW, PAGE = 16, 8


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return arch.tiny_file(tmp_path_factory, "window_moe", TINY)


def _prefill(be, slot, toks, start_pos=0):
    adm = be.add_begin(slot, list(toks), start_pos=start_pos)
    while not be.add_step(adm):
        pass
    return adm


# ------------------------------------------------- files, header, plan


def test_header_round_trip_and_plan(tiny):
    cfg = tiny.config
    assert cfg.layer_windows == (0, 1, 1, 1) * 2 == cfg.layer_ropes
    assert (cfg.window, cfg.head_size, cfg.attn_dim, cfg.dim) == (16, 128, 384, 256)
    assert cfg.hidden_act == HiddenAct.RELU and cfg.router_pre_attention
    assert (cfg.n_experts, cfg.n_active_experts, cfg.n_window_layers) == (8, 3, 6)
    assert cfg.schedule_kinds == (SCHEDULE_UNROTATED, *(SCHEDULE_WINDOWED,) * 3) * 2
    assert LlamaConfig.from_header_kv(cfg.to_header_kv()) == cfg
    mine, header = layout.read_header(tiny.path)
    assert header == formats.read_header(tiny.path)[1]
    assert [n for n, _, _ in formats.tensor_plan(cfg)] == [
        e.name for e in layout.tensor_plan(mine)]
    layers = tiny.params["layers"]
    assert layers["wq"].shape == (8, 256, 384) and layers["wo"].shape == (8, 384, 256)
    assert layers["moe_w1"].shape == (8, 8, 256, 256)
    assert layers["moe_gate"].shape == (8, 256, 8)


def test_a_header_without_the_new_keys_means_what_it_meant():
    llama = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=100, seq_len=32)
    assert max(k for k, _ in llama.to_header_kv()) < 100
    again = LlamaConfig.from_header_kv(llama.to_header_kv())
    assert (again.window, again.layer_windows, again.layer_ropes,
            again.router_pre_attention, again.attn_dim) == (0, (), (), False, 64)
    assert again.schedule_kinds == ()
    with pytest.raises(ValueError):  # a window flag without a size
        LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                    vocab_size=100, seq_len=32, layer_windows=(0, 1))


@pytest.mark.parametrize("name,sha", [
    ("tiny-llama", "06786fb9"), ("tiny-twokind", "216ae004")])
def test_the_accepted_layouts_write_the_bytes_they_wrote(name, sha, tmp_path):
    """Adding a layout moves no byte of another's file (each plan entry has
    its own seed stream): the benchmark's two rehearsal files at seed 7."""
    with open(os.path.join(ROOT, "benchmark", "tests", name + ".json")) as f:
        config = json.load(f)
    path = str(tmp_path / "m.m")
    files.write_model(path, config, 7)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest().startswith(sha)


# ---------------------------------------- against the reference, by route


@pytest.mark.parametrize("kernels,attn,route", [
    ("xla", "jnp", "xla/paged_gather.window+moe_jnp"),
    # float32 activations: the windowed paged kernel (interpret mode); the
    # grouped expert kernel takes bfloat16 rows only and is held below
    ("pallas", "flash", "pallas/paged_kernel.window+moe_jnp"),
])
def test_prefill_decode_past_the_window_and_tail_match_the_reference(
        tiny, kernels, attn, route):
    out = arch.run_check(tiny, TINY, kernels, attn)
    assert out["route"] == route
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}
    assert out["rel_l2_max"] < 1e-5


def test_stated_precision_runs_the_grouped_kernel_and_the_windowed_sweep(tiny):
    """bfloat16 activations, both kernels in interpret mode: bf16's own
    rounding (CPU reading 0.011; the jnp route in bf16 reads 0.016)."""
    out = arch.run_check(arch.loaded(tiny.path, jnp.bfloat16), TINY, "pallas", "flash",
                         tolerances={"rel_l2_mean": 0.04, "deficit_sigma_mean": 0.02})
    assert out["route"] == "pallas/paged_kernel.window+moe_grouped"
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}


CONTROLS = {
    "window ignored": dict(layer_windows=(0,) * 8),
    "rope on the global layer": dict(layer_ropes=(1,) * 8),
    "router fed the post-attention norm": dict(router_pre_attention=False),
    "SiLU for ReLU": dict(hidden_act=HiddenAct.SILU),
}


@pytest.fixture(scope="module")
def past_the_window(tiny):
    return arch.sixty(tiny, TINY)


@pytest.mark.parametrize("control", [None, *CONTROLS])
def test_each_control_fails_the_tolerance_the_sound_model_holds(
        tiny, past_the_window, control):
    """One forward over 60 tokens on the dense jnp route: the model as the
    header says it reads 1e-6 against the reference, and each single
    departure from the equations is refused by the limit."""
    seq, want = past_the_window
    if control is None:
        assert arch.logits_rel_l2(tiny.params, tiny.config, seq, want) < TOL["rel_l2_mean"]
        return
    wrong = dataclasses.replace(tiny.config, **CONTROLS[control])
    assert arch.logits_rel_l2(tiny.params, wrong, seq, want) > 100 * TOL["rel_l2_mean"]


# ------------------------------------------------ the grouped expert kernel


@pytest.fixture(scope="module")
def experts():
    rng = np.random.default_rng(0)
    d, f, e = 256, 256, 8
    cfg = LlamaConfig(dim=d, hidden_dim=f, n_layers=2, n_heads=2, n_kv_heads=1,
                      vocab_size=64, seq_len=32, n_experts=e, n_active_experts=3,
                      hidden_act=HiddenAct.RELU)

    def stack(k, n):
        one = lambda: QTensor.quantize(
            (rng.standard_normal((k, n)) * 0.05).astype(np.float32))
        layer = lambda: jax.tree.map(lambda *x: jnp.stack(x), *[one() for _ in range(e)])
        return jax.tree.map(lambda *x: jnp.stack(x), layer(), layer())

    return cfg, (stack(d, f), stack(f, d), stack(d, f)), rng


def _routed_to(rows, e, choices):
    """Logits that send every row to exactly `choices`."""
    logits = np.full((1, rows, e), -9.0, np.float32)
    for rank, c in enumerate(choices):
        logits[..., c] = 3.0 - rank
    return jnp.asarray(logits)


@pytest.mark.parametrize("case", ["m=1", "decode batch", "every row on one set",
                                  "a ragged slice", "a slice past a tile an expert"])
def test_grouped_kernel_matches_dense(experts, case):
    cfg, (w1, w2, w3), rng = experts
    b, t = {"m=1": (1, 1), "decode batch": (4, 1),
            "every row on one set": (1, 24), "a ragged slice": (1, 40),
            "a slice past a tile an expert": (1, 64)}[case]
    h = jnp.asarray(rng.standard_normal((b, t, cfg.dim)), jnp.bfloat16)
    if case == "every row on one set":  # five experts receive no row at all
        logits = _routed_to(t, cfg.n_experts, (2, 5, 7))
    else:
        logits = jnp.asarray(rng.standard_normal((b, t, cfg.n_experts)), jnp.float32)
    stats0 = jnp.zeros(4, jnp.uint32)
    got, stats = moe_ffn(cfg, h, None, w1, w2, w3, impl="grouped", logits=logits,
                         layer=1, stats=stats0)
    want = moe_ffn(cfg, h, None, w1, w2, w3, impl="dense", logits=logits, layer=1)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)).max()
    assert err <= 0.02 * np.abs(np.asarray(want, np.float32)).max() + 1e-3
    sizes = np.bincount(np.asarray(jax.lax.top_k(logits, 3)[1]).reshape(-1),
                        minlength=cfg.n_experts)
    assert stats.tolist() == [b * t * 3, int((sizes > 0).sum()), 1, int(sizes.max())]


def test_expert_groups_pad_each_group_to_whole_tiles_and_freeze_dead_tiles():
    topi = jnp.asarray([[0, 3], [3, 5], [3, 0], [3, 7]], jnp.int32)  # 4 rows to 3
    pos, tile_expert, tile_src, n_live, sizes = expert_groups(topi, 8, 2)
    assert sizes.tolist() == [2, 0, 0, 4, 0, 1, 0, 1]
    assert int(n_live) == 1 + 2 + 1 + 1 and len(tile_expert) == 8 + 4
    assert tile_expert.tolist()[:5] == [0, 3, 3, 5, 7]
    assert set(tile_expert.tolist()[5:]) == {7} and set(tile_src.tolist()[5:]) == {4}
    # groups in expert order, token order inside a group, whole tiles of 2
    assert np.asarray(pos).tolist() == [[0, 2], [3, 6], [4, 1], [5, 8]]
    # every (token, choice) finds its own token's row at its padded position
    h = jnp.arange(4, dtype=jnp.bfloat16)[:, None] * jnp.ones((1, 8), jnp.bfloat16) + 1
    xs = np.asarray(expert_rows(h, pos, None, 12 * 2, by_dot=True), np.float32)
    assert (xs[np.asarray(pos), 0] == np.arange(4)[:, None] + 1).all()
    assert (xs[[7, 9] + list(range(10, 24))] == 0).all()  # pad rows and dead tiles


# ----------------------------------------------------------- a pool a kind


def test_window_pages_go_back_and_no_slot_holds_more_than_a_window(tiny):
    be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                     max_seq_len=256, **ENGINE)
    w = be.wpool
    per_slot = WINDOW // PAGE + 1
    slice_pages = 16 // PAGE + 1
    # every slot can hold a window and a slice at once: the pool never runs dry
    assert w.n_pages == 4 * (per_slot + slice_pages - 1) and w.free_count == w.n_pages
    released0 = ins.KV_WINDOW_PAGES_RELEASED.value()
    held = []
    for slot, n in enumerate((40, 100)):
        adm = be.add_begin(slot, _tokens(n, seed=slot))
        while not be.add_step(adm):
            held.append(w.held(slot))
        be.add_commit(adm, temperature=0.0)
    assert max(held) <= per_slot + slice_pages - 1  # a window and a slice
    for _ in range(8):
        be.decode(4)
        assert all(w.held(s) <= per_slot for s in (0, 1))
        # what was handed back points at the trash page, what is held does not
        for s in (0, 1):
            head, n = int(w.head[s]), int(w.n_blocks[s])
            assert (w.tables[s, :head] == w.hole).all()
            assert (w.tables[s, head:n] != w.hole).all()
    assert int(be.pool.n_blocks[1]) == -(-(100 + 32) // PAGE)  # global: every row
    assert ins.KV_WINDOW_PAGES_RELEASED.value() - released0 >= (100 + 32 - WINDOW) // PAGE - 1
    stats = be.kv_page_stats()
    assert stats["used"] == stats["pools"]["global"]["used"] + stats["pools"]["window"]["used"]
    assert stats["total"] == be.pool.n_pages + w.n_pages
    assert be.pool.audit()["window"]["ok"]
    be.release(0)
    be.release(1, keep_rows=100 + 32)
    assert w.held(1) <= per_slot and be.resumable_rows(1, 132) == 132
    assert be.resumable_rows(1, 50) == 0  # its window was handed back
    with pytest.raises(StateNotResumable):
        be.add_begin(1, _tokens(4), start_pos=50)
    be.release(1)
    assert w.free_count == w.n_pages and be.pool.free_count == be.pool.n_pages
    assert be.pool.audit()["ok"]


def test_the_trash_entry_is_never_read(tiny):
    """The windowed sweep starts at the first block that holds a visible
    row: NaN in the window pool's trash page (where every handed-back
    entry points) changes no token of a slot that decodes. One engine (one
    set of interpret-mode compiles), rebuilt between the two runs."""
    be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                     max_seq_len=256, **dict(ENGINE, n_slots=2),
                     kernels="pallas", attn_impl="flash")

    def run(poison):
        be.warm_restart()
        for slot in (0, 1):
            be.add_commit(_prefill(be, slot, _tokens(40, seed=slot)), temperature=0.0)
        assert int(be.wpool.head[0]) > 0
        if poison:
            trash = be.wpool.n_pages
            be.cache = dataclasses.replace(
                be.cache, kw=be.cache.kw.at[:, trash].set(jnp.nan),
                vw=be.cache.vw.at[:, trash].set(jnp.nan))
        chunk = be.decode_dispatch(8)
        toks = be.decode_consume(chunk)
        assert not np.asarray(chunk.bad).any()
        return toks

    assert (run(False) == run(True)).all()


def test_hybrid_launch_is_bit_exact_against_the_split_phases(tiny):
    prompt0, prompt1 = _tokens(40, seed=1), _tokens(48, seed=2)

    def run(hybrid):
        be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                         max_seq_len=256, **dict(ENGINE, n_slots=2))
        be.add_commit(_prefill(be, 0, prompt0), temperature=0.0)
        adm = be.add_begin(1, prompt1)
        toks = []
        while adm.off < len(adm.toks):
            if hybrid:
                toks.append(be.decode_consume(be.hybrid_dispatch(4, adm, 16)))
            else:
                be.add_step(adm)
                toks.append(be.decode(4))
        return np.concatenate(toks)[:, 0], np.asarray(adm.logits)

    # the batch-mate's token stream is bit-exact; the admitted prompt's
    # logits come out of another XLA program (the slice fused beside the
    # decode scan) and agree to float32 reassociation, the same first token
    (t_h, l_h), (t_s, l_s) = run(True), run(False)
    assert (t_h == t_s).all()
    assert l_h.argmax() == l_s.argmax()
    np.testing.assert_allclose(l_h, l_s, rtol=0, atol=1e-4 * np.abs(l_s).max())


def test_expert_counters_add_up(tiny):
    fams = (ins.MOE_ASSIGNMENTS, ins.MOE_EXPERTS_TOUCHED, ins.MOE_LAYER_STEPS,
            ins.MOE_GROUP_ROWS_MAX)
    before = [f.value() for f in fams]
    read0 = ins.LAUNCH_KV_ROWS_READ.series()
    be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                     max_seq_len=256, **ENGINE)
    be.add_commit(_prefill(be, 0, _tokens(32)), temperature=0.0)  # 2 chunks of 16
    be.decode(4)
    rows, layers, k = 32 + 4 * 4, 8, 3  # a decode step computes every slot's row
    assign, touched, steps, longest = (f.value() - b for f, b in zip(fams, before))
    assert steps == layers * (2 + 4)
    assert assign == rows * k * layers
    assert steps <= touched <= 8 * steps and longest >= assign / touched
    read = ins.LAUNCH_KV_ROWS_READ.series()
    d = lambda key: read.get(key, 0.0) - read0.get(key, 0.0)
    assert d("decode,global") == 33 + 34 + 35 + 36  # positions 32..35 attend p + 1
    assert d("decode,window") == 4 * WINDOW


def test_what_cannot_follow_two_page_lists_is_refused_or_off(tiny):
    be = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                     max_seq_len=256, **ENGINE)
    assert be.radix is None and not be.rows_reenterable
    assert not be.supports_cross_slot_copy
    for bad in (dict(radix_cache="on"), dict(kv_host_pages=4)):
        with pytest.raises(ValueError, match="a pool a kind"):
            BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                        max_seq_len=256, **{**ENGINE, **bad})
    with pytest.raises(ValueError, match="grouped"):  # float32 rows: no kernel
        BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                    max_seq_len=256, **ENGINE, kernels="pallas", moe_impl="grouped")
    # the dense layout keeps one stack and masks the window
    dense = BatchEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                        max_seq_len=256, n_slots=2)
    assert dense.attn_route == "jnp.window+moe_jnp" and dense.wpool is None
