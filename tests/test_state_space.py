"""The hybrid state-space / attention architecture (`ArchType.HYBRID_SSM`)
through the serving path, against the plain reference
(`benchmark/reference/granite_hybrid.py`: the recurrence step by step).

A tiny file is written through the benchmark's layout
(`benchmark/layouts/granite_hybrid.py`): two periods of `m m a m`, the real
mixer structure at small widths. Weights are loaded in float32 here so that
the serving path's own arithmetic (chunked scan, cache, state handling)
reads against the reference at 1e-6 and a state held in bfloat16 stands out;
the stated precision (bf16 activations) is drilled on the chip.

What is held: prefill + batched decode + a tail chunk against the reference
by the check's two numbers; the chunked slice form against the step-by-step
recurrence at arbitrary power-of-two splits; frozen and inactive slots'
state bit-equal; a re-used slot starts from zero state; the hybrid launch
bit-exact against the phase-split path; a rewind below the state refuses and
recomputes; a continuation where the state stands continues; the Pallas step
kernel (interpret mode) against the jnp step; the scheduler clips prefix
reuse; speculation is refused; files, header and converter.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, files
from benchmark.layouts import granite_hybrid as layout
from dllama_tpu.engine.batch import BatchEngine, StateNotResumable
from dllama_tpu.engine.engine import InferenceEngine
from dllama_tpu.models import formats
from dllama_tpu.models.config import ArchType, LayerKind, LlamaConfig, RopeType
from dllama_tpu.models.llama import KVCache, forward, layer_schedule
from dllama_tpu.obs import instruments as ins
from dllama_tpu.ops import ssm
from tests import arch

TINY = {
    "name": "tiny-hybrid", "model_type": "granitemoehybrid",
    "attention_bias": False, "attention_multiplier": 0.03125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 128,
    "intermediate_size": 256,
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "logits_scaling": 8, "mamba_chunk_size": 16, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 32, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 256,
    "num_attention_heads": 4, "num_experts_per_tok": 0,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "shared_intermediate_size": 256, "tie_word_embeddings": True,
    "vocab_size": 512, "layout": "benchmark.layouts.granite_hybrid",
    "reference": "benchmark.reference.granite_hybrid",
    "weights": {"attention_sharpness": 1.5},
}
#: CPU readings against arch.TOL, float32 weights and activations, seeds 1-3
#: (PERF.md section 4): sound 1.0e-6, S held in bfloat16 1.8e-3 to 3.1e-3.
#: 1e-4 is 100 x the worst sound reading and 1/18 of the best control reading.
TOL, _tokens = arch.TOL, arch.tokens
#: pages of 16 rows, slices up to the engine's own cap: this file's check
#: runs 32 decode steps (two pages a slot) where arch.CHECK's long one runs 64
CHECK = dict(arch.CHECK["xla"], decode_steps=32)
ENGINE = dict(n_slots=4, kv_layout="paged", page_size=16, kv_pages=40,
              radix_cache="auto")
#: sha256 of the tiny file by seed: adding a layout or editing the writer
#: must not move this layout's bytes
TINY_SHA = {
    5: "3ead3fce3e3b536363f4ac6ba5e8f5a59c2ee236597ceed0b9096bbc4c2d9589",
    2147483659: "d5ddbe7df2b5103fd12187d831b9e2327b584707a467284d33c31428507443eb",
}


def sha256(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return arch.tiny_file(tmp_path_factory, "hybrid", TINY)


_ENGINES: dict = {}


def _engine(tiny, **kw) -> BatchEngine:
    """Module-shared engines (one compile set a key), reset by warm_restart."""
    key = tuple(sorted((k, str(v)) for k, v in kw.items()))
    if key in _ENGINES:
        _ENGINES[key].warm_restart()
        return _ENGINES[key]
    args = {**ENGINE, "cache_dtype": jnp.float32, "max_seq_len": 256, **kw}
    _ENGINES[key] = BatchEngine(tiny.config, tiny.params, **args)
    return _ENGINES[key]


def _prefill(be, slot, toks, start_pos=0):
    adm = be.add_begin(slot, list(toks), start_pos=start_pos)
    while not be.add_step(adm):
        pass
    return adm


# ----------------------------------------------------- files, header, plan


def test_header_round_trip_and_plan_by_kind(tiny):
    cfg = tiny.config
    assert cfg.arch == ArchType.HYBRID_SSM and cfg.rope_type == RopeType.NONE
    assert cfg.layer_kinds == (1, 1, 0, 1) * 2
    assert (cfg.n_attn_layers, cfg.n_ssm_layers) == (2, 6)
    assert (cfg.attn_scale, cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.tied_head) == (0.03125, 12.0, 0.22, 8.0, True)
    assert cfg.head_size == 32 and cfg.ssm_in_proj == 256 + 512 + 8
    again = LlamaConfig.from_header_kv(cfg.to_header_kv())
    assert again.layer_kinds == cfg.layer_kinds and again.ssm_state == 128
    mine, header = layout.read_header(tiny.path)
    assert header == formats.read_header(tiny.path)[1]
    names = [n for n, _, _ in formats.tensor_plan(cfg)]
    assert names == [e.name for e in layout.tensor_plan(mine)]
    assert "layers.0.in_proj" in names and "layers.0.wq" not in names
    assert "layers.2.wq" in names and "layers.2.in_proj" not in names
    # in_proj: the published 776 columns on disk, whole lane tiles on device
    assert tiny.params["layers"]["in_proj"].shape[-1] == 896
    assert tiny.params["layers"]["wq"].shape[0] == 2
    assert tiny.params["layers"]["w1"].shape[0] == 8
    # a LLAMA header says nothing new and means what it meant
    llama = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4,
                        n_kv_heads=2, vocab_size=96, seq_len=64)
    assert all(k < 100 for k, _ in llama.to_header_kv())
    assert not llama.recurrent and llama.n_attn_layers == 2


@pytest.mark.parametrize("seed", sorted(TINY_SHA))
def test_hybrid_file_bytes_stand(tmp_path, seed):
    path = str(tmp_path / "m.m")
    size = files.write_model(path, TINY, seed)
    assert sha256(path) == TINY_SHA[seed]
    s, views = layout.tensor_views(path)  # raises unless the bytes add up
    assert size == layout.read_header(path)[1] + sum(len(v[0]) for v in views.values())
    a = np.exp(np.asarray(views["layers.0.a_log"][0]).view(np.float32))
    assert a.min() >= 1.0 and a.max() <= 16.0 and a.std() > 1.0


def test_layer_schedule():
    m, a = int(LayerKind.SSM), int(LayerKind.ATTENTION)
    assert layer_schedule((a,) * 30) == (1, [(a, 0, 1)])
    period = (m,) * 5 + (a,) + (m,) * 4
    assert layer_schedule(period * 4) == (10, [(m, 0, 5), (a, 5, 1), (m, 6, 4)])
    assert layer_schedule((m, a, a, m))[0] == 4


# ------------------------------------------------- the ops, form against form


def _ssm_inputs(t, b=2, h=4, p=8, n=128, seed=0):
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(0.5), (b, t, h))), jnp.float32)
    log_a = -jnp.asarray(r.uniform(1, 16, (h,)), jnp.float32) * dt
    return f(b, h, p, n), f(b, t, h, p), dt, log_a, f(b, t, n), f(b, t, n)


def _by_steps(s, x, dt, log_a, bm, cm):
    ys = []
    for i in range(x.shape[1]):
        y, s = ssm.ssm_step_ref(s, x[:, i], dt[:, i], log_a[:, i], bm[:, i], cm[:, i])
        ys.append(y)
    return jnp.stack(ys, axis=1), s


def test_chunked_scan_is_the_recurrence():
    s0, *seq = _ssm_inputs(40)
    y_ref, s_ref = _by_steps(s0, *seq)
    for chunk in (64, 16):  # one block; two blocks and a ragged tail
        y, s = ssm.ssm_chunk_scan(s0, *seq, chunk)
        np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(s, s_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("split", [(32, 8), (1, 2, 4, 1, 32), (16, 16, 8), (8, 32)])
def test_a_prompt_split_at_power_of_two_chunks_is_one_slice(split):
    s0, *seq = _ssm_inputs(40, seed=1)
    y_one, s_one = ssm.ssm_chunk_scan(s0, *seq, 64)
    ys, s, at = [], s0, 0
    for n in split:
        y, s = ssm.ssm_chunk_scan(s, *(v[:, at:at + n] for v in seq), 64)
        ys.append(y)
        at += n
    np.testing.assert_allclose(jnp.concatenate(ys, axis=1), y_one, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s, s_one, rtol=2e-5, atol=2e-5)


def test_ssm_step_kernel_interpret_against_the_jnp_step():
    from dllama_tpu.ops.pallas.ssm_step import ssm_step, supported

    r = np.random.default_rng(3)
    L, b, h, p, n = 3, 5, 8, 32, 128
    stack = jnp.asarray(r.standard_normal((L, b, h, p, n)), jnp.float32)
    assert supported(stack.shape, stack.dtype)
    assert not supported(stack.shape, jnp.bfloat16)
    _, xs, dt, log_a, bm, cm = _ssm_inputs(1, b=b, h=h, p=p, n=n, seed=4)
    x, dt, log_a, bm, cm = xs[:, 0], dt[:, 0], log_a[:, 0], bm[:, 0], cm[:, 0]
    mode = jnp.asarray([1, 0, 2, 1, 0], jnp.int32)  # advance, leave, from zero
    y, out = ssm_step(stack, jnp.int32(1), x, dt, jnp.exp(log_a), bm, cm, mode,
                      interpret=True)
    s_in = jnp.where((mode == 2)[:, None, None, None], 0.0, stack[1])
    y_ref, s_ref = ssm.ssm_step_ref(s_in, x, dt, log_a, bm, cm)
    live = np.asarray(mode) != 0
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(y_ref)[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1])[live], np.asarray(s_ref)[live],
                               rtol=1e-6, atol=1e-6)
    # slots left alone, and every other layer, are bit-equal
    assert np.array_equal(np.asarray(out[1])[~live], np.asarray(stack[1])[~live])
    assert np.array_equal(np.asarray(out[0]), np.asarray(stack[0]))
    assert np.array_equal(np.asarray(out[2]), np.asarray(stack[2]))
    # a stale non-finite state does not leak into a row that starts at zero
    bad = stack.at[1, 2].set(jnp.nan)
    _, out2 = ssm_step(bad, jnp.int32(1), x, dt, jnp.exp(log_a), bm, cm, mode,
                       interpret=True)
    assert np.isfinite(np.asarray(out2[1, 2])).all()


# ------------------------------------------ the engine against the reference


def _run_check(tiny, **engine_kw):
    cfg = dict(TINY, engine={**ENGINE, **engine_kw}, check=CHECK, tolerances=TOL)
    return check.run(tiny, cfg, tiny.path, 5)


def test_engine_prefill_decode_and_tail_against_the_reference(tiny):
    """The check's own sequence (benchmark/check.py): chunked prefill of
    prompts over one and several slices, batched decode of slots of
    different lengths, then release(keep_rows = position) and a tail chunk
    at start_pos = rows kept (the state stands there: it continues)."""
    out = _run_check(tiny)
    assert out["correct"], {k: v for k, v in out.items() if k != "per_prompt"}
    assert out["rel_l2_mean"] < 1e-5 and out["deficit_sigma_max"] == 0.0


def test_state_held_in_bfloat16_fails_the_tolerance(tiny):
    """The control: the same engine with S in bfloat16 (a constructor
    argument of the state) reads outside the tolerance the sound engine
    passes 100 times under."""
    out = _run_check(tiny, state_dtype=jnp.bfloat16)
    assert not out["correct"]
    assert out["rel_l2_mean"] > 5 * TOL["rel_l2_mean"]


def test_engine_on_the_kernel_route_against_the_reference(tiny):
    """kernels=pallas, attention flash: the Q40 matmul kernels, the paged
    flash-decode kernel over a 2-layer pool and `_ssm_step` on the stacked
    state, all in interpret mode, through the same check."""
    be = _engine(tiny, kernels="pallas", attn_impl="flash")
    assert be.kernel_route == "pallas/paged_kernel+ssm_step.float32"
    assert f"{be.backend}/{be.attn_route}" == be.kernel_route  # check.py's tag
    prompts = [np.asarray(_tokens(n, seed=n), np.int32) for n in (9, 21)]
    tails = [np.asarray(_tokens(3, seed=7 + n), np.int32) for n in (9, 21)]
    cfg = dict(TINY, engine={**ENGINE, "kernels": "pallas", "attn_impl": "flash"})
    eng = check.engine_side(tiny, cfg["engine"], prompts, tails, 8)
    out = check.compare(eng, prompts, tiny.path, TINY["reference"], 8)
    assert out["finite"] and out["rel_l2_mean"] < 1e-4, out["rel_l2_mean"]
    assert out["deficit_sigma_max"] == 0.0


@pytest.mark.parametrize("kernels,state_dtype,route", [
    ("pallas", jnp.float32, "pallas/paged_kernel+ssm_step.float32"),
    ("pallas", jnp.bfloat16, "pallas/paged_kernel+ssm_jnp.bfloat16"),
    ("xla", jnp.float32, "xla/paged_kernel+ssm_jnp.float32"),
])
def test_route_names_the_state_step_and_its_precision(tiny, kernels,
                                                      state_dtype, route):
    """The state-space decode step is chosen in engine/kernel_select beside
    the attention route, and the tag the benchmark's `expect` compares says
    which step runs and in what precision S is held: the kernel's fallback
    (a narrower state, an XLA backend) is a different route, never a silent
    one, and the state carries exactly the step the tag names."""
    from dllama_tpu.engine.kernel_select import resolve_kernels

    sel = resolve_kernels(tiny.config, 256, 4, kernels, "flash", paged=True,
                          page_size=16, cache_dtype=jnp.float32,
                          state_dtype=state_dtype)
    assert sel.bucket_tag() == route
    assert (sel.state_step is not None) == ("ssm_step." in route)
    be = _engine(tiny, kernels=kernels, attn_impl="flash",
                 state_dtype=state_dtype)
    assert be.kernel_route == route
    assert (be.cache.state.step is not None) == ("ssm_step." in route)


def test_batch1_engine_steps_and_generates(tiny):
    """The batch-1 engine (`inference` / `chat`): a 1-token prompt decodes
    at row 0 from zero state; a rewind below the state is refused."""
    from benchmark.reference import granite_hybrid as ref

    eng = InferenceEngine(tiny.config, tiny.params, cache_dtype=jnp.float32,
                          max_seq_len=256)
    toks = np.asarray(_tokens(21, seed=9), np.int32)
    eng.step(toks[None, :16])
    got = [np.asarray(eng.step(toks[None, i:i + 1]))[0] for i in range(16, 21)]
    want = ref.logits_at(tiny.path, [toks], [list(range(16, 21))])[0]
    assert check.rel_l2(np.stack(got), want) < 1e-5
    assert eng.can_resume_at(21) and eng.can_resume_at(0) and not eng.can_resume_at(10)
    with pytest.raises(ValueError, match="recurrent state"):
        eng.reset(10)
    eng.reset(0)  # a fresh sequence: row 0 zeroes the state on the device
    again = np.asarray(eng.step(toks[None, :1]))[0]
    assert check.rel_l2(again, ref.logits_at(tiny.path, [toks[:1]], [[0]])[0][0]) < 1e-5


# ------------------------------------------------- what cannot be rewound


def test_frozen_and_inactive_slots_keep_their_state_bit_equal(tiny):
    """Slots of different lengths decode together; one slot is inactive
    (released, its state kept) and one is frozen at the cache edge: their S
    and conv window are bit-equal before and after a chunk, and the two
    decoding slots emit what they emit alone."""
    be = _engine(tiny, max_seq_len=64, kv_pages=20)
    a, b, c, d = _tokens(5, 1), _tokens(20, 2), _tokens(12, 3), _tokens(61, 4)
    for slot, p in enumerate((a, b, c, d)):
        be.add_commit(_prefill(be, slot, p), temperature=0.0)
    be.decode(4)  # slot 3 reaches row 64 and freezes
    be.release(2, keep_rows=int(be.pos[2]))  # inactive, state stands
    before = jax.tree.map(np.asarray, (be.cache.state.s, be.cache.state.conv))
    got = np.asarray(be.decode(4))
    after = jax.tree.map(np.asarray, (be.cache.state.s, be.cache.state.conv))
    for x0, x1 in zip(before, after):
        assert np.array_equal(x0[:, 2], x1[:, 2]) and np.array_equal(x0[:, 3], x1[:, 3])
        assert not np.array_equal(x0[:, 0], x1[:, 0])
    alone = _engine(tiny, max_seq_len=64, kv_pages=20)
    for slot, p in enumerate((a, b)):
        alone.add_commit(_prefill(alone, slot, p), temperature=0.0)
    alone.decode(4)
    assert np.array_equal(np.asarray(alone.decode(4))[:, :2], got[:, :2])


def test_a_reused_slot_starts_from_zero_state(tiny):
    """The stale-state bug this design invites: request B in a slot that
    request A just left reads exactly what B reads in a fresh engine."""
    be = _engine(tiny)
    resets = ins.STATE_RESETS.value()
    be.add_commit(_prefill(be, 0, _tokens(50, 11)), temperature=0.0)
    be.decode(8)
    be.release(0)
    adm = _prefill(be, 0, _tokens(23, 12))
    stale = np.asarray(adm.logits)
    first = be.add_commit(adm, temperature=0.0)
    toks = np.asarray(be.decode(8))[:, 0]
    assert ins.STATE_RESETS.value() == resets + 2
    fresh_be = _engine(tiny)
    adm = _prefill(fresh_be, 0, _tokens(23, 12))
    assert np.array_equal(stale, np.asarray(adm.logits))
    assert first == fresh_be.add_commit(adm, temperature=0.0)
    assert np.array_equal(toks, np.asarray(fresh_be.decode(8))[:, 0])


def test_rewind_below_the_state_refuses_and_recomputes(tiny):
    """release(keep_rows < position): the state stands past the rows kept
    and is unknown for them. add_begin(start_pos=keep_rows) refuses;
    recomputing from row 0 reads what a fresh engine reads."""
    be = _engine(tiny)
    prompt = _tokens(30, 21)
    first = be.add_commit(_prefill(be, 1, prompt), temperature=0.0)
    decoded = np.asarray(be.decode(8))[:, 1].tolist()
    assert int(be.pos[1]) == 38
    be.release(1, keep_rows=34)  # a stop inside the chunk
    assert be.resumable_rows(1, 34) == 0
    with pytest.raises(StateNotResumable):
        be.add_begin(1, [5, 6, 7], start_pos=34)
    seq = prompt + [first] + decoded[:3] + [5, 6, 7]
    again = np.asarray(_prefill(be, 1, seq).logits)
    fresh = _engine(tiny)
    assert np.array_equal(again, np.asarray(_prefill(fresh, 1, seq).logits))


def test_continuation_where_the_state_stands(tiny):
    """release(keep_rows = position) then add_begin(start_pos = keep_rows)
    continues (the check's step 3), once: the state moves on with it."""
    from benchmark.reference import granite_hybrid as ref

    be = _engine(tiny)
    prompt, more = _tokens(19, 31), _tokens(6, 32)
    first = be.add_commit(_prefill(be, 2, prompt), temperature=0.0)
    decoded = np.asarray(be.decode(4))[:, 2].tolist()
    rows = int(be.pos[2])
    be.release(2, keep_rows=rows)
    assert be.resumable_rows(2, rows) == rows and be.resumable_rows(2, rows - 1) == 0
    assert be.resumable_rows(2, rows, donor=0) == 0  # another slot's rows
    fed = decoded[-1:] + more
    got = np.asarray(_prefill(be, 2, fed, start_pos=rows).logits)[0]
    seq = np.asarray(prompt + [first] + decoded + more)
    want = ref.logits_at(tiny.path, [seq], [[len(seq) - 1]])[0][0]
    assert check.rel_l2(got, want) < 1e-5
    assert not be.rows_reenterable and not be.supports_cross_slot_copy


def test_what_assumes_rewind_is_refused_or_off(tiny):
    with pytest.raises(ValueError, match="cannot be rewound"):
        BatchEngine(tiny.config, tiny.params, **ENGINE, spec=2)
    with pytest.raises(ValueError, match="stands at one row"):
        BatchEngine(tiny.config, tiny.params, **{**ENGINE, "radix_cache": "on"})
    be = _engine(tiny)
    assert be.radix is None and be.cache.state.s.dtype == jnp.float32
    assert be.cache.k.shape[0] == 2 and be.cache.state.s.shape[:2] == (6, 4)
    assert ins.RECURRENT_STATE_BYTES.value() == be.cache.state.nbytes > 0
    from dllama_tpu.engine.loader import build_shardings

    assert build_shardings(tiny.config, "auto") is None
    with pytest.raises(ValueError, match="one device"):
        build_shardings(tiny.config, "tp=2")


# ------------------------------------------------- scheduler, hybrid launch


def _serve(tiny, work, **sched_kw):
    from dllama_tpu.serve.scheduler import Scheduler

    sched = Scheduler(_engine(tiny), chunk=3, **sched_kw)
    try:
        return work(sched), sched
    finally:
        sched.shutdown()


def _mixed(sched):
    """A greedy decoder running, then a long sampled joiner: its admission
    rides the decode cadence where the hybrid launch is on."""
    r1 = sched.submit(_tokens(6, 41), 0.0, 0.9, 14, frozenset(), seed=1)
    it1 = r1.tokens()
    head = [next(it1), next(it1)]
    r2 = sched.submit(_tokens(40, 42), 1.1, 0.9, 8, frozenset(), seed=42)
    out2 = list(r2.tokens())
    return head + list(it1), out2


def test_hybrid_launch_is_bit_exact_against_phase_split(tiny):
    split, _ = _serve(tiny, _mixed, prefill_budget=0)
    fused, sched = _serve(tiny, _mixed, prefill_budget=8)
    assert sched.ledger.totals["hybrid"] > 0.0  # slices really rode launches
    assert fused == split
    assert not sched._preempt_on  # --preempt auto resolved off


def test_scheduler_clips_prefix_reuse_and_clamps_speculation(tiny):
    """A shared-prefix pair: the second request's prefix is in the slot's
    history, the state is not there to re-enter it: reuse is clipped to 0,
    the rows are recomputed (and counted), both streams are right."""
    shared = _tokens(24, 51)

    def pair(sched):
        r1 = sched.submit(shared + [7, 8], 0.0, 0.9, 5, frozenset(), seed=1)
        out1 = list(r1.tokens())
        r2 = sched.submit(shared + [9], 0.0, 0.9, 6, frozenset(), seed=2, spec_k=4)
        return out1, list(r2.tokens()), r2.spec_k

    again = ins.PREFIX_ROWS_RECOMPUTED.labels(reason="state_elsewhere")
    before = again.value()
    (out1, out2, spec_k), sched = _serve(tiny, pair)
    assert spec_k == 0 and sched.reused_prefix_tokens == 0
    assert again.value() >= before + len(shared)
    (alone, _), _ = _serve(tiny, lambda s: (list(s.submit(
        shared + [9], 0.0, 0.9, 6, frozenset(), seed=2).tokens()), None))
    assert out2 == alone and len(out1) == 5


# ------------------------------------------------------------ the converter


def test_convert_hf_maps_a_hybrid_state_dict(tmp_path):
    """A synthetic GraniteMoeHybridForCausalLM state dict (the names of the
    published checkpoint) converts to a file the program loads, and the
    loaded model computes the reference's function of those weights."""
    from dllama_tpu.ops.quant import FloatType
    from dllama_tpu.tools import converter_core

    hf_cfg = {k: v for k, v in TINY.items()
              if k not in ("name", "layout", "reference", "weights")}
    hf_cfg["architectures"] = ["GraniteMoeHybridForCausalLM"]
    r = np.random.default_rng(0)
    w = lambda *s: (r.standard_normal(s) / np.sqrt(s[-1])).astype(np.float32)
    sd = {"model.embed_tokens.weight": w(512, 128) * 2, "model.norm.weight": np.ones(128, np.float32)}
    for i, kind in enumerate(TINY["layer_types"]):
        p = f"model.layers.{i}."
        if kind == "mamba":
            sd.update({p + "mamba.in_proj.weight": w(776, 128),
                       p + "mamba.conv1d.weight": w(512, 1, 4),
                       p + "mamba.conv1d.bias": w(512) * 0.1,
                       p + "mamba.dt_bias": r.uniform(-5, -2, 8).astype(np.float32),
                       p + "mamba.A_log": np.log(r.uniform(1, 16, 8)).astype(np.float32),
                       p + "mamba.D": np.ones(8, np.float32),
                       p + "mamba.norm.weight": np.ones(256, np.float32),
                       p + "mamba.out_proj.weight": w(128, 256)})
        else:
            sd.update({p + "self_attn.q_proj.weight": w(128, 128),
                       p + "self_attn.k_proj.weight": w(64, 128),
                       p + "self_attn.v_proj.weight": w(64, 128),
                       p + "self_attn.o_proj.weight": w(128, 128)})
        sd.update({p + "shared_mlp.input_linear.weight": w(512, 128),
                   p + "shared_mlp.output_linear.weight": w(128, 256),
                   p + "input_layernorm.weight": np.ones(128, np.float32),
                   p + "post_attention_layernorm.weight": np.ones(128, np.float32)})
    cfg = converter_core.hf_config_to_llama(hf_cfg, FloatType.Q40)
    assert cfg.arch == ArchType.HYBRID_SSM and cfg.layer_kinds == (1, 1, 0, 1) * 2
    assert cfg.residual_multiplier == 0.22 and cfg.hidden_dim == 256
    path = str(tmp_path / "hf.m")
    converter_core.write_model(
        cfg, path, lambda n: converter_core.hf_tensor_for(n, cfg, sd.__getitem__))
    with pytest.raises(ValueError, match="routed experts"):
        converter_core.hf_config_to_llama({**hf_cfg, "num_local_experts": 8},
                                          FloatType.Q40)
    from benchmark.reference import granite_hybrid as ref

    rcfg, header = formats.read_header(path, 256)
    params = formats.load_params(path, rcfg, header, dtype=jnp.float32)
    toks = np.asarray(_tokens(20, 61), np.int32)
    cache = KVCache.create(rcfg, 1, jnp.float32, conv_dtype=jnp.float32)
    from dllama_tpu.ops.layers import build_rope_cache

    logits, _ = forward(rcfg, params, toks[None], 0, cache, build_rope_cache(rcfg))
    want = ref.logits_at(path, [toks], [[len(toks) - 1]])[0][0]
    assert check.rel_l2(np.asarray(logits)[0, -1], want) < 1e-5
