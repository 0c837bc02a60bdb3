"""Power-retention layers (degree 2, a gate a kv head, a [value + 1, expanded
key] state that five query heads share) in a model with NO cache rows,
against the plain reference (`benchmark/reference/brumby.py`), which
computes the ATTENTION form: the two agree only if the expansion, the decay,
the normaliser and the grouping of heads are right.

A tiny file is written through the benchmark's layout
(`benchmark/layouts/brumby.py`, `benchmark/tests/tiny-brumby.json`): 3
layers, 10 query heads on 2 states of head size 16 (136 expanded dims, held
as 24 x 256), QK-norm, rope. Weights are loaded in float32 so that the
serving path's own arithmetic reads against the reference at 1e-6 and each
control stands out.
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
from benchmark.layouts import brumby as layout
from benchmark.reference import brumby as reference
from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.engine.kernel_select import resolve_state_step
from dllama_tpu.models import formats
from dllama_tpu.models.config import LayerKind, LlamaConfig
from dllama_tpu.models.llama import KVCache, RecurrentState, forward
from dllama_tpu.obs import instruments as ins
from dllama_tpu.ops import power
from dllama_tpu.ops.layers import build_rope_cache
from dllama_tpu.ops.pallas.retention_step import retention_step, supported
from tests import arch

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "tests", "tiny-brumby.json")) as f:
    TINY = json.load(f)
#: CPU readings against arch.TOL, seed 5: sound 8e-7 to 1.2e-6 on both
#: routes; the controls 0.02 and up
TOL, ENGINE, _tokens = arch.TOL, arch.ENGINE, arch.tokens


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return arch.tiny_file(tmp_path_factory, "retention", TINY)


# ------------------------------------------------- files, header, plan


def test_header_round_trip_and_plan(tiny):
    cfg = tiny.config
    assert cfg.layer_kinds == (int(LayerKind.RETENTION),) * 3
    assert (cfg.n_retention_layers, cfg.n_attn_layers, cfg.n_state_layers) == (3, 0, 3)
    assert cfg.recurrent and cfg.qk_norm and not cfg.latent
    assert (cfg.ret_degree, cfg.ret_gate, cfg.state_kind) == (2, True, "retention")
    assert (cfg.head_size, cfg.attn_dim, cfg.q_per_kv) == (16, 160, 5)
    # 17 rows x 136 products, held in whole tiles; no conv window at all
    assert (cfg.state_shape, cfg.state_conv) == ((2, 24, 256), (0, 0))
    assert LlamaConfig.from_header_kv(cfg.to_header_kv()) == cfg
    mine, header = layout.read_header(tiny.path)
    assert header == formats.read_header(tiny.path)[1]
    assert [(n, np.prod(s)) for n, s, _ in formats.tensor_plan(cfg)] == [
        (e.name, np.prod(e.shape)) for e in layout.tensor_plan(mine)]
    layers = tiny.params["layers"]
    assert layers["wq"].shape == (3, 256, 160) and layers["wo"].shape == (3, 160, 256)
    assert layers["ret_gate"].shape == (3, 256, 2)
    assert layers["ret_gate_bias"].shape == (3, 2) and layers["q_norm"].shape == (3, 16)


def test_a_header_without_the_new_keys_means_what_it_meant():
    llama = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=100, seq_len=32)
    assert max(k for k, _ in llama.to_header_kv()) < 100
    again = LlamaConfig.from_header_kv(llama.to_header_kv())
    assert (again.ret_degree, again.ret_gate, again.recurrent,
            again.state_kind, again.n_attn_layers) == (0, False, False, "", 2)


@pytest.mark.parametrize("why,kw", [
    ("a degree the program does not compute", dict(ret_degree=3, ret_gate=True)),
    ("no gate", dict(ret_degree=2)),
    ("beside softmax attention", dict(ret_degree=2, ret_gate=True,
                                      layer_kinds=(4, 0))),
    ("beside another recurrent kind", dict(ret_degree=2, ret_gate=True,
                                           layer_kinds=(4, 2), kda_heads=2,
                                           kda_rank=8)),
])
def test_what_the_retention_keys_refuse(why, kw):
    kw = {"layer_kinds": (4, 4), **kw}
    with pytest.raises(ValueError):
        LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                    vocab_size=100, seq_len=32, **kw)


@pytest.mark.parametrize("seed,sha", [(7, "eee012f9"), (2147483659, "a686a50a")])
def test_the_layout_writes_the_bytes_it_wrote(seed, sha, tmp_path):
    path = str(tmp_path / "m.m")
    files.write_model(path, TINY, seed)
    with open(path, "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest().startswith(sha)


def test_the_gate_bias_spans_the_memories_asked_for():
    rng = np.random.default_rng(3)
    b = layout.log_uniform_decay_logit(16.0, 10000.0)(rng, 4000)
    tau = -1.0 / np.log(1.0 / (1.0 + np.exp(-b.astype(np.float64))))
    assert 15.9 < tau.min() < 17 and 9000 < tau.max() < 10001
    # log-uniform: as many memories below 400 rows (the middle) as above
    assert 0.45 < (tau < 400).mean() < 0.55


# --------------------------------------------------- the step and the slice


@pytest.fixture(scope="module")
def rows():
    """3 sequences of 12 rows, 10 query heads on 2 states of head size 16,
    memories of 2 to 20 rows, and a state to come from."""
    rng = np.random.default_rng(1)
    b, t, h, g, d = 3, 12, 10, 2, 16
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    gamma = -jnp.exp(f(b, t, g) - 2.0)
    s0 = power.retention_slice(jnp.zeros((b, g, *power.state_dims(d))),
                               f(b, 5, h, d), f(b, 5, g, d), f(b, 5, g, d),
                               gamma[:, :5])[1]
    return s0, f(b, t, h, d), f(b, t, g, d), f(b, t, g, d), gamma


def test_phi_is_the_symmetric_square(rows):
    _, q, k, _, _ = rows
    lanes = power.state_dims(16)[1]
    dots = jnp.einsum("bthd,btgd->bthg", q, k, precision="highest")
    phis = jnp.einsum("bthr,btgr->bthg", power.phi(q, lanes), power.phi(k, lanes),
                      precision="highest")
    np.testing.assert_allclose(phis, dots ** 2, rtol=2e-5, atol=1e-5)
    assert not np.asarray(power.phi(q, lanes))[..., 136:].any()  # the padding


def test_the_step_scanned_is_the_attention_form(rows):
    """From a ZERO state the recurrent form, row by row, is the reference's
    attention form over the same rows: phi, the decay between rows, the
    normaliser and the five heads a state are the same thing said twice."""
    _, q, k, v, gamma = rows
    s = jnp.zeros((3, 2, *power.state_dims(16)))
    outs = []
    for i in range(q.shape[1]):
        y, s = power.retention_step_ref(s, q[:, i], k[:, i], v[:, i], gamma[:, i])
        outs.append(power.normalise(y, 16))
    got = jnp.stack(outs, axis=1)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference._retention(q[b], k[b], v[b], gamma[b])
                          for b in range(3)])
    # (a first row whose q . k nearly cancels has a weight of eps's size:
    # float32's rounding of the dot shows at 5e-4 there, 1e-6 elsewhere)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-5)
    assert np.median(np.abs(got - want)) < 1e-6


def test_the_slice_is_the_step_repeated(rows):
    s0, q, k, v, gamma = rows
    y_slice, s_slice = power.retention_slice(s0, q, k, v, gamma)
    s, outs = s0, []
    for i in range(q.shape[1]):
        y, s = power.retention_step_ref(s, q[:, i], k[:, i], v[:, i], gamma[:, i])
        outs.append(y)
    np.testing.assert_allclose(y_slice, jnp.stack(outs, 1), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s_slice, s, rtol=1e-5, atol=1e-5)
    # two slices are one: the state carries everything across the cut
    y_a, s_a = power.retention_slice(s0, q[:, :7], k[:, :7], v[:, :7], gamma[:, :7])
    y_b, s_b = power.retention_slice(s_a, q[:, 7:], k[:, 7:], v[:, 7:], gamma[:, 7:])
    np.testing.assert_allclose(jnp.concatenate([y_a, y_b], 1), y_slice,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s_b, s_slice, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ the kernel


@pytest.fixture(scope="module")
def kernel_step():
    """The kernel in interpret mode at a head of 128: 8,256 expanded dims,
    64 whole diagonals of 128 lanes and a ragged last one of 64; five query
    heads on the one state; three slots: one advances, one is left, one
    starts from zero."""
    rng = np.random.default_rng(0)
    d, g, j, b, layers = 128, 1, 5, 3, 2
    rows_, lanes = power.state_dims(d)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = f(b, g * j, d), f(b, g, d), f(b, g, d)
    gamma = -jnp.asarray(rng.random((b, g)) * 0.3, jnp.float32)
    state = f(layers, b, g, rows_, lanes).at[..., d + 1:, :].set(0.0)
    state = state.at[..., d * (d + 1) // 2:].set(0.0)
    mode = jnp.asarray([1, 0, 2], jnp.int32)
    y, out = retention_step(state, 1, q, k, v, jnp.exp(gamma), mode,
                            interpret=True)
    y_ref, s_ref = power.retention_step_ref(state[1].at[2].set(0.0), q, k, v, gamma)
    return state, np.asarray(y), np.asarray(out), np.asarray(y_ref), np.asarray(s_ref)


def test_the_kernel_is_the_step_over_every_diagonal(kernel_step):
    _, y, out, y_ref, s_ref = kernel_step
    assert (power.state_dims(128), 64 * 128 + 64) == ((136, 8320), 8256)
    for slot in (0, 2):  # advanced, and advanced from zero
        np.testing.assert_allclose(out[1, slot], s_ref[slot], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y[slot], y_ref[slot], rtol=2e-5,
                                   atol=2e-5 * np.abs(y_ref).max())
    # the ragged last diagonal: its 64 pairs are written, its padding is not
    assert out[1, 0, 0, :129, 8192:8256].any() and not out[1, 0, 0, :, 8256:].any()
    assert not out[1, 0, 0, 129:].any()


def test_five_query_heads_read_the_one_state(kernel_step):
    _, y, _, y_ref, _ = kernel_step
    assert y.shape == (3, 5, 136)
    heads = y[0, :, :129]
    assert all(np.abs(heads[a] - heads[b]).max() > 1.0
               for a in range(5) for b in range(a))
    np.testing.assert_allclose(heads, y_ref[0, :, :129], rtol=2e-5,
                               atol=2e-5 * np.abs(y_ref).max())


def test_a_slot_that_does_not_advance_is_bit_equal(kernel_step):
    state, y, out, _, _ = kernel_step
    assert np.array_equal(out[1, 1], np.asarray(state[1, 1]))
    assert np.array_equal(out[0], np.asarray(state[0]))  # the other layer
    assert not y[1].any()


def test_the_route_names_the_kernel_where_it_serves():
    """Head 128, float32 state: the kernel; a bfloat16 state or a head that
    is not whole lane tiles: the jnp step, and the route says so."""
    cfg = LlamaConfig(dim=256, hidden_dim=512, n_layers=2, n_heads=10,
                      n_kv_heads=2, vocab_size=512, seq_len=64, head_dim=128,
                      qk_norm=True, layer_kinds=(4, 4), ret_degree=2, ret_gate=True)
    step, route = resolve_state_step(cfg, 4, "pallas")
    assert step is not None and route == "retention_step.float32"
    assert resolve_state_step(cfg, 4, "pallas", jnp.bfloat16) == (
        None, "retention_jnp.bfloat16")
    assert resolve_state_step(cfg, 4, "xla") == (None, "retention_jnp.float32")
    small = dataclasses.replace(cfg, head_dim=16)
    assert resolve_state_step(small, 4, "pallas") == (None, "retention_jnp.float32")
    assert supported((2, 4, 2, 136, 8320), jnp.float32, 128)
    assert not supported((2, 4, 2, 129, 8256), jnp.float32, 128)


# ------------------------------------------------------------ the engine


@pytest.mark.parametrize("kernels,attn,route", [
    ("xla", "jnp", "xla/no_cache_rows+retention_jnp.float32"),
    ("pallas", "flash", "pallas/no_cache_rows+retention_jnp.float32"),
])
def test_prefill_decode_and_tail_match_the_reference(tiny, kernels, attn, route):
    """Prefill slices (the slice form from the state the last slice left),
    decode steps over slots at different rows, the tail on the kept state:
    logits against the attention form's full forward."""
    out = arch.run_check(tiny, TINY, kernels, attn)
    assert out["route"] == route
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}
    assert out["rel_l2_max"] < 2e-5


def test_stated_precision_reads_bfloat16s_rounding(tiny):
    """bfloat16 activations, float32 gate, phi, state and normaliser."""
    out = arch.run_check(arch.loaded(tiny.path, jnp.bfloat16), TINY, "xla", "jnp",
                         tolerances={"rel_l2_mean": 0.04, "deficit_sigma_mean": 0.02})
    assert out["correct"], {k: out[k] for k in ("rel_l2_mean", "deficit_sigma_mean")}
    assert out["rel_l2_mean"] > 10 * TOL["rel_l2_mean"]


def test_a_bfloat16_state_fails_the_tolerance_float32_passes(tiny):
    """S is a plain running sum over the whole context (nothing is taken
    out before a write, unlike the delta rule): held in bfloat16 it is
    rounded once a step and the rounding piles up. 256 decode steps, token
    by token through the dense cache: float32 reads 1e-6, bfloat16 0.007, 74
    x the limit; and the engine builds with the narrow state, on the jnp
    step."""
    ref = importlib.import_module(TINY["reference"])
    seq = np.asarray(_tokens(256, seed=9), np.int32)
    want = ref.logits_at(tiny.path, [seq], [[255]])[0][0]
    cfg, rope = tiny.config, build_rope_cache(tiny.config, 256)
    step = jax.jit(lambda tok, pos, cache: forward(cfg, tiny.params, tok, pos,
                                                   cache, rope))
    read = {}
    for name, dtype in (("float32", jnp.float32), ("bfloat16", jnp.bfloat16)):
        cache = KVCache.create(cfg, 1, jnp.float32, 256, state_dtype=dtype)
        for pos in range(256):
            logits, cache = step(jnp.asarray(seq[None, pos:pos + 1]), pos, cache)
        read[name] = check.rel_l2(np.asarray(logits[0, -1]), want)
    assert read["float32"] < TOL["rel_l2_mean"], read
    assert read["bfloat16"] > 50 * TOL["rel_l2_mean"], read
    be = BatchEngine(cfg, tiny.params, **dict(ENGINE, kernels="pallas"),
                     state_dtype=jnp.bfloat16)
    assert be.attn_route == "no_cache_rows+retention_jnp.bfloat16"
    assert be.cache.state.s.dtype == jnp.bfloat16


def test_a_wrong_grouping_or_an_unrotated_key_fails(tiny, monkeypatch):
    """The controls the reference is there for: query head j on kv head
    j % 2 and not j // 5, and k left unrotated, each read far from the
    limit."""
    from dllama_tpu.models import llama as model

    seq, want = arch.sixty(tiny, TINY)
    assert arch.logits_rel_l2(tiny.params, tiny.config, seq, want) < TOL["rel_l2_mean"]
    real_slice, real_rope = power.retention_slice, model.apply_rope

    def interleaved(s, q, k, v, gamma):
        b, t, h, d = q.shape
        mixed = q.reshape(b, t, h // 2, 2, d).swapaxes(2, 3).reshape(b, t, h, d)
        return real_slice(s, mixed, k, v, gamma)

    monkeypatch.setattr(power, "retention_slice", interleaved)
    err = arch.logits_rel_l2(tiny.params, tiny.config, seq, want)
    assert err > 100 * TOL["rel_l2_mean"], err
    monkeypatch.setattr(power, "retention_slice", real_slice)
    monkeypatch.setattr(model, "apply_rope", lambda x, rope: (
        x if x.shape[2] == tiny.config.n_kv_heads else real_rope(x, rope)))
    err = arch.logits_rel_l2(tiny.params, tiny.config, seq, want)
    assert err > 100 * TOL["rel_l2_mean"], err


def test_an_engine_with_no_cache_rows(tiny):
    """No layer holds cache rows: the pool's layer axis is 0 and a page
    costs nothing, the block tables still stand for positions; what
    re-enters a sequence at a page boundary resolved off or is refused; the
    state's bytes and what a slice cuts and puts back are counted."""
    cfg = tiny.config
    be = BatchEngine(cfg, tiny.params, **dict(ENGINE, kernels="xla"))
    assert be.cache.k.shape[0] == 0 and be.cache.k.nbytes == 0
    assert be.cache.tables.shape == (4, 256 // 8)
    assert be.radix is None and not be.rows_reenterable
    state = be.cache.state
    assert isinstance(state, RecurrentState) and state.conv.size == 0
    assert state.s.shape == (3, 4, 2, 24, 256)
    assert state.slot_bytes == 3 * 2 * 24 * 256 * 4
    assert ins.RECURRENT_STATE_BYTES.value() == state.nbytes
    before = ins.STATE_SLICE_BYTES.value()
    adm = be.add_begin(0, _tokens(24))  # slices of 16 and 8 rows
    while not be.add_step(adm):
        pass
    assert ins.STATE_SLICE_BYTES.value() - before == 2 * 2 * state.slot_bytes
    be.add_commit(adm, temperature=0.0)
    be.decode(4)
    assert ins.STATE_SLICE_BYTES.value() - before == 2 * 2 * state.slot_bytes
    for refused in (dict(radix_cache="on"), dict(kv_host_pages=8), dict(spec=2)):
        with pytest.raises(ValueError):
            BatchEngine(cfg, tiny.params, **{**ENGINE, "kernels": "xla", **refused})


def test_the_server_serves_it_and_health_says_what_resolved_off(tiny, tmp_path):
    """`make_server` (what `serve --slots N` builds) over the tiny file: a
    completion through the scheduler, and `/health` names the kind, the
    state's bytes, that no layer holds cache rows and what resolved off."""
    import threading

    from dllama_tpu.engine.loader import load_model
    from dllama_tpu.serve.api import make_server
    from tests.test_serve import post

    tok = str(tmp_path / "t.t")
    files.write_tokenizer(tok, TINY["vocab_size"])
    loaded = load_model(tiny.path, tok, mesh=None, max_seq_len=256)
    httpd, api = make_server(loaded, host="127.0.0.1", port=0, n_slots=2,
                             kv_layout="paged", page_size=8)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        status, body = post(httpd.server_address[1], "/v1/completions",
                            {"prompt": "abcdefgh", "max_tokens": 6,
                             "temperature": 0.0})
        assert status == 200 and json.loads(body)["usage"]["completion_tokens"] == 6
        h = api.health()
        assert h["kv_cache_bytes"] == 0
        state = h["recurrent_state"]
        assert (state["kind"], state["layers"]) == ("retention", 3)
        assert state["bytes"] == h["recurrent_state_bytes"] == 3 * 2 * 2 * 24 * 256 * 4
        assert h["cache_rows"] == {"layers": 0, "resolved_off": state["resolved_off"]}
        assert {"radix_cache", "spec_k", "preempt_to_pages"} <= set(state["resolved_off"])
    finally:
        httpd.shutdown()
