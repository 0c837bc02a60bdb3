"""Continuous-batching engine tests: slot isolation, staggered joins, parity
with the single-sequence engine, per-slot sampling params, vector-pos model
paths (the capability the reference's blocking server lacks, SURVEY §7.4.6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.engine.engine import InferenceEngine
from dllama_tpu.engine.sampling import Sampler
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import KVCache, forward, random_params
from dllama_tpu.ops.layers import build_rope_cache


CFG = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                  vocab_size=96, seq_len=64)
PARAMS = random_params(CFG, seed=9, dtype=jnp.float32, quantize=False)


def greedy_ref(prompt, n):
    eng = InferenceEngine(CFG, PARAMS, cache_dtype=jnp.float32)
    return list(eng.generate(prompt, n, Sampler(0.0, 0.9, 0)))


def test_vector_pos_forward_matches_scalar():
    """forward with pos=[p, p] must equal forward with scalar p."""
    rope = build_rope_cache(CFG)
    toks = jnp.asarray([[5, 6, 7], [8, 9, 10]], jnp.int32)
    c1 = KVCache.create(CFG, 2, jnp.float32)
    l1, c1 = forward(CFG, PARAMS, toks, jnp.int32(4), c1, rope)
    c2 = KVCache.create(CFG, 2, jnp.float32)
    l2, c2 = forward(CFG, PARAMS, toks, jnp.asarray([4, 4], jnp.int32), c2, rope)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(c1.k), np.asarray(c2.k), atol=1e-6, rtol=1e-6)


def test_active_mask_freezes_cache():
    rope = build_rope_cache(CFG)
    toks = jnp.asarray([[5], [8]], jnp.int32)
    c0 = KVCache.create(CFG, 2, jnp.float32)
    _, c1 = forward(CFG, PARAMS, toks, jnp.asarray([0, 0], jnp.int32), c0,
                    rope, active=jnp.asarray([True, False]))
    k = np.asarray(c1.k)
    assert np.abs(k[:, 0]).max() > 0  # row 0 written
    assert np.abs(k[:, 1]).max() == 0  # row 1 frozen


def test_batch_matches_single_engine_greedy():
    """Two sequences decoded together == each decoded alone."""
    p1, p2 = [1, 2, 3], [9, 8, 7, 6]
    want1, want2 = greedy_ref(p1, 8), greedy_ref(p2, 8)

    be = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32)
    f1 = be.add(0, p1, temperature=0.0)
    f2 = be.add(2, p2, temperature=0.0)  # non-adjacent slot on purpose
    assert [f1, f2] == [want1[0], want2[0]]
    toks = be.decode(7)
    assert list(toks[:, 0]) == want1[1:]
    assert list(toks[:, 2]) == want2[1:]


def test_staggered_join_does_not_disturb_running_slot():
    """Join slot 1 after slot 0 already decoded 4 tokens; slot 0's continuation
    must be unchanged (prefill writes are masked to the joining slot)."""
    p1, p2 = [1, 2, 3], [20, 21]
    want1 = greedy_ref(p1, 10)
    want2 = greedy_ref(p2, 5)

    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    got1 = [be.add(0, p1, temperature=0.0)]
    got1 += list(be.decode(4)[:, 0])
    got2 = [be.add(1, p2, temperature=0.0)]
    toks = be.decode(4)
    got1 += list(toks[:, 0])
    got2 += list(toks[:, 1])
    assert got1 == want1[:9]
    assert got2 == want2[:5]


def test_release_and_reuse_slot():
    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    be.add(0, [1, 2, 3], temperature=0.0)
    be.decode(3)
    be.release(0)
    assert be.free_slot() == 0
    # fresh request in the recycled slot equals a fresh engine
    want = greedy_ref([4, 5], 5)
    got = [be.add(0, [4, 5], temperature=0.0)]
    got += list(be.decode(4)[:, 0])
    assert got == want[:5]


def test_per_slot_temperature_zero_is_greedy():
    """Greedy slot must be exact even when batched with a sampling slot."""
    p1 = [1, 2, 3]
    want = greedy_ref(p1, 6)
    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, seed=5)
    got = [be.add(0, p1, temperature=0.0)]
    be.add(1, [7, 8], temperature=1.2, topp=0.8)
    got += list(be.decode(5)[:, 0])
    assert got == want[:6]


def test_frozen_slot_repeats_last_token():
    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    be.add(0, [1, 2], temperature=0.0)
    be.decode(2)
    be.release(0)
    be.add(1, [3, 4], temperature=0.0)
    last0 = be.last_token[0]
    pos0_before = int(be.pos[0])
    toks = be.decode(3)
    assert (toks[:, 0] == last0).all()  # frozen slot unchanged
    assert be.pos[0] == pos0_before  # frozen pos not advanced by decode


def test_flash_attention_vector_pos(rng):
    from dllama_tpu.ops.layers import gqa_attention
    from dllama_tpu.ops.pallas.flash_attention import flash_gqa_attention

    q = jnp.asarray(rng.standard_normal((2, 1, 4, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 128, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 128, 64)), jnp.float32)
    pos = jnp.asarray([3, 77], jnp.int32)
    got = flash_gqa_attention(q, k, v, pos, interpret=True)
    want = gqa_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_batch_engine_sharded_matches_unsharded():
    """BatchEngine on a tp=2 x dp-style mesh == unsharded (multi-chip serving)."""
    from dllama_tpu.parallel.mesh import MeshConfig, make_mesh
    from dllama_tpu.parallel.sharding import LlamaShardings

    be_ref = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    mesh = make_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    sh = LlamaShardings(mesh, CFG)
    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, shardings=sh)

    p1, p2 = [1, 2, 3], [9, 8]
    a = [be_ref.add(0, p1, temperature=0.0), be_ref.add(1, p2, temperature=0.0)]
    b = [be.add(0, p1, temperature=0.0), be.add(1, p2, temperature=0.0)]
    assert a == b
    ta, tb = be_ref.decode(6), be.decode(6)
    np.testing.assert_array_equal(ta, tb)


def test_per_request_seed_reproducible_across_batch_composition():
    """VERDICT r1 weak #5: a seeded request samples the same continuation
    whether it runs alone or shares the batch (per-slot PRNG keys)."""
    p = [1, 2, 3]
    be1 = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    alone = [be1.add(0, p, temperature=1.1, topp=0.95, seed=123)]
    alone += list(be1.decode(6)[:, 0])

    be2 = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, seed=9)
    got = [be2.add(0, p, temperature=1.1, topp=0.95, seed=123)]
    be2.add(1, [7, 8, 9], temperature=0.7, topp=0.8, seed=77)  # batch-mate
    got += list(be2.decode(6)[:, 0])
    assert got == alone

    # and chunk boundaries don't change the stream
    be3 = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    got3 = [be3.add(0, p, temperature=1.1, topp=0.95, seed=123)]
    got3 += list(be3.decode(2)[:, 0])
    got3 += list(be3.decode(4)[:, 0])
    assert got3 == alone


def test_batch_engine_rejects_sp_mesh():
    from dllama_tpu.parallel.mesh import MeshConfig, make_mesh
    from dllama_tpu.parallel.sharding import LlamaShardings

    mesh = make_mesh(MeshConfig(sp=2, tp=2))
    sh = LlamaShardings(mesh, CFG)
    with pytest.raises(ValueError, match="tp/dp"):
        BatchEngine(CFG, PARAMS, n_slots=2, shardings=sh)


def test_slot_prefill_matches_masked_full_width():
    """The B=1 slot-sliced admission prefill must produce the same cache rows
    and first-token logits as the masked full-width step it replaces."""
    be_slot = BatchEngine(CFG, PARAMS, n_slots=3, seed=5, cache_dtype=jnp.float32)
    be_full = BatchEngine(CFG, PARAMS, n_slots=3, seed=5, cache_dtype=jnp.float32)
    assert be_slot._use_slot_prefill
    be_full._use_slot_prefill = False

    prompt = [5, 6, 7, 8, 9]
    t1 = be_slot.add(1, prompt, temperature=0.0, seed=11)
    t2 = be_full.add(1, prompt, temperature=0.0, seed=11)
    assert t1 == t2
    np.testing.assert_allclose(
        np.asarray(be_slot.cache.k, np.float32),
        np.asarray(be_full.cache.k, np.float32), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(be_slot.cache.v, np.float32),
        np.asarray(be_full.cache.v, np.float32), atol=1e-5, rtol=1e-5)
    # untouched slots remain zero
    assert float(np.abs(np.asarray(be_slot.cache.k, np.float32)[:, 0]).max()) == 0.0
    # and decode after slot-admission continues identically
    d1 = be_slot.decode(4)
    d2 = be_full.decode(4)
    np.testing.assert_array_equal(d1[:, 1], d2[:, 1])


def test_batch_engine_fused_weights_parity():
    """BatchEngine(fuse_weights=True) must match unfused decode exactly."""
    outs = {}
    for fused in (False, True):
        be = BatchEngine(CFG, PARAMS, n_slots=2, seed=7, cache_dtype=jnp.float32,
                         fuse_weights=fused)
        first = be.add(0, [3, 4, 5], temperature=0.0, seed=1)
        toks = be.decode(6)
        outs[fused] = (first, [int(t) for t in toks[:, 0]])
    assert outs[False] == outs[True]


def test_slot_prefill_start_pos_matches_full_width():
    """Prefix-cache admissions (start_pos > 0) must agree across the
    slot-sliced and masked full-width prefill paths (same cache, same first
    token): this is the path the scheduler's NaiveCache reuse drives."""
    be_slot = BatchEngine(CFG, PARAMS, n_slots=2, seed=9, cache_dtype=jnp.float32)
    be_full = BatchEngine(CFG, PARAMS, n_slots=2, seed=9, cache_dtype=jnp.float32)
    be_full._use_slot_prefill = False

    turn1 = [3, 4, 5, 6]
    for be in (be_slot, be_full):
        be.add(0, turn1, temperature=0.0, seed=2)
        be.release(0, keep_rows=len(turn1))  # keep KV rows (prefix cache)
    delta = [7, 8]
    t1 = be_slot.add(0, delta, temperature=0.0, seed=3, start_pos=len(turn1))
    t2 = be_full.add(0, delta, temperature=0.0, seed=3, start_pos=len(turn1))
    assert t1 == t2
    np.testing.assert_allclose(
        np.asarray(be_slot.cache.k, np.float32),
        np.asarray(be_full.cache.k, np.float32), atol=1e-5, rtol=1e-5)


# ------------------------------------------------- batched speculative decode


def _drain_spec(be, slots, n_want):
    """Run spec cycles until every tracked slot has n_want tokens; returns
    ({slot: tokens}, cycles)."""
    streams = {s: [] for s in slots}
    cycles = 0
    while any(len(v) < n_want for v in streams.values()):
        emit, adv = be.spec_step()
        cycles += 1
        for s in slots:
            streams[s] += list(emit[s, : adv[s]])
        assert cycles < 20 * n_want, "spec cycles not converging"
    return {s: v[:n_want] for s, v in streams.items()}, cycles


def test_spec_batched_greedy_exact():
    """Greedy slots under batched speculation emit the bit-identical stream
    of the single-sequence greedy reference, in fewer forwards once the
    continuations settle into their own loops (the draftable pattern —
    same mechanism as test_spec_accepts_drafts_on_repetitive_text)."""
    p1 = [1, 2, 3, 1, 2, 3, 1, 2]
    p2 = [9, 8, 7, 9, 8, 7, 9]
    n = 40  # long enough for tiny-model greedy to enter a short cycle
    want1, want2 = greedy_ref(p1, n + 1), greedy_ref(p2, n + 1)

    be = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32, spec=4)
    f1 = be.add(0, p1, temperature=0.0)
    f2 = be.add(2, p2, temperature=0.0)
    assert [f1, f2] == [want1[0], want2[0]]
    streams, cycles = _drain_spec(be, (0, 2), n)
    assert streams[0] == want1[1 : n + 1]
    assert streams[2] == want2[1 : n + 1]
    # the whole point: fewer verify forwards than tokens
    assert cycles < n, f"no speculation win: {cycles} cycles for {n} tokens"


def test_spec_batched_sampled_slot_is_exact_and_reproducible():
    """A sampled slot advances exactly 1 token per cycle and its stream is
    reproducible from its seed, independent of greedy batch-mates."""

    def run():
        be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, spec=4)
        be.add(0, [1, 2, 3, 1, 2, 3], temperature=0.0)
        first = be.add(1, [5, 6, 7], temperature=0.9, seed=123)
        out = [first]
        for _ in range(6):
            emit, adv = be.spec_step()
            assert adv[1] == 1  # sampled slots never accept drafts
            out += list(emit[1, : adv[1]])
        return out

    a, b = run(), run()
    assert a == b and len(a) == 7


def test_spec_interleaves_with_decode_and_admissions():
    """decode() backfills the spec history, so alternating decode chunks,
    spec cycles, and a mid-stream admission still yields the exact greedy
    reference for every slot."""
    p1, p2 = [1, 2, 3, 1, 2, 3], [4, 5, 6, 4, 5]
    want1, want2 = greedy_ref(p1, 14), greedy_ref(p2, 9)

    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, spec=3)
    got1 = [be.add(0, p1, temperature=0.0)]
    got1 += list(be.decode(4)[:, 0])  # plain decode first
    got2 = [be.add(1, p2, temperature=0.0)]  # staggered admission
    streams, _ = _drain_spec(be, (0, 1), 8)
    got1 += streams[0]
    got2 += streams[1]
    assert got1 == want1[:13]
    assert got2 == want2[:9]


def test_spec_step_guards():
    be = BatchEngine(CFG, PARAMS, n_slots=1, cache_dtype=jnp.float32)
    with pytest.raises(ValueError, match="spec=0"):
        be.spec_step()
    be2 = BatchEngine(CFG, PARAMS, n_slots=1, cache_dtype=jnp.float32, spec=4)
    with pytest.raises(ValueError, match="no active"):
        be2.spec_step()
    # slot too close to seq_len for a K+1 window: frozen for spec, decode
    # still finishes it
    be2.add(0, list(range(1, 61)), temperature=0.0)  # pos 60 of 64, k+1=5
    with pytest.raises(ValueError, match="room"):
        be2.spec_step()
    be2.decode(2)


def test_spec_frozen_sampled_slot_keeps_seed_stream():
    """A sampled slot frozen out of spec cycles (near seq_len) must not
    consume PRNG splits while frozen: its continuation via decode() equals
    the same-seed run that never saw those cycles (the seed-pinned
    reproducibility contract, VERDICT r1 weak #5)."""

    def tail(with_spec_cycles):
        be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, spec=4)
        be.add(0, [1, 2, 3, 1, 2, 3], temperature=0.0)  # greedy batch-mate
        # sampled slot parked within k+1 of seq_len: room_ok False -> frozen
        be.add(1, list(range(1, 61)), temperature=0.9, seed=7)  # pos 60 of 64
        if with_spec_cycles:
            for _ in range(3):
                emit, adv = be.spec_step()
                assert adv[1] == 0  # frozen: emitted nothing
        return [int(t) for t in be.decode(3)[:, 1]]

    assert tail(False) == tail(True)


def test_spec_penalized_slot_rides_the_cycle():
    """A penalized slot no longer freezes spec cycles (ISSUE 11): the
    counts-carrying _spec_step_pen variant advances it exactly 1
    bit-exact penalized token per cycle while greedy batch-mates keep
    multi-token acceptance — no decode alternation needed (replaces the
    old engine-global freeze of VERDICT r4 next #6)."""
    from dllama_tpu.engine.sampling import Sampler as _S

    p_g, p_p = [1, 2, 3, 1, 2, 3, 1, 2], [7, 8, 9]
    n = 12
    want_g = greedy_ref(p_g, n + 1)
    eng1 = InferenceEngine(CFG, PARAMS, cache_dtype=jnp.float32)
    want_p = list(eng1.generate(p_p, n + 1, _S(temperature=0.0, presence=0.6,
                                               frequency=0.4)))

    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, spec=4)
    got_g = [be.add(0, p_g, temperature=0.0)]
    got_p = [be.add(1, p_p, temperature=0.0, presence=0.6, frequency=0.4)]
    cycles = 0
    while len(got_g) < n + 1 or len(got_p) < n + 1:
        emit, adv = be.spec_step()
        cycles += 1
        assert adv[1] == 1  # penalized: exactly one penalized token
        got_g += [int(t) for t in emit[0, : adv[0]]]
        got_p += [int(emit[1, 0])]
        assert cycles < 20 * n, "not converging"
    assert got_g[: n + 1] == want_g[: n + 1]
    assert got_p[: n + 1] == want_p[: n + 1]


def test_batched_penalties_match_single_engine():
    """A penalized request in the batched tier must produce the same greedy
    stream as the single-engine penalized generate (same OpenAI
    sampled-token-counts semantics), while an un-penalized batch-mate's
    stream stays untouched."""
    from dllama_tpu.engine.sampling import Sampler as _S

    p1, p2 = [1, 2, 3], [7, 8, 9]
    eng1 = InferenceEngine(CFG, PARAMS, cache_dtype=jnp.float32)
    want_pen = list(eng1.generate(p1, 9, _S(temperature=0.0, presence=0.6,
                                            frequency=0.4)))
    want_plain = greedy_ref(p2, 9)

    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    got_pen = [be.add(0, p1, temperature=0.0, presence=0.6, frequency=0.4)]
    got_plain = [be.add(1, p2, temperature=0.0)]
    toks = be.decode(8)
    got_pen += [int(t) for t in toks[:, 0]]
    got_plain += [int(t) for t in toks[:, 1]]
    assert got_pen == want_pen
    assert got_plain == want_plain[:9]
    # recycled slot must not inherit penalties
    be.release(0)
    assert be.presence[0] == 0.0 and be.frequency[0] == 0.0


def test_batched_penalized_sampled_reproducible():
    """Penalized SAMPLED requests stay seed-reproducible and differ from the
    same seed without penalties (the penalty reshapes the distribution)."""

    def run(freq):
        be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
        out = [be.add(0, [1, 2, 3], temperature=1.0, topp=0.9, seed=42,
                      frequency=freq)]
        out += [int(t) for t in be.decode(8)[:, 0]]
        return out

    a, b = run(0.9), run(0.9)
    assert a == b  # reproducible under penalties
    assert run(0.0) != a  # and the penalty actually reshapes sampling


# ------------------------- the sampler's conditional bodies (ISSUE 52)


def _eqns(jaxpr, under=()):
    """Every equation of a jaxpr and of the jaxprs in its parameters, with
    the primitives it stands under (outermost first)."""
    for eqn in jaxpr.eqns:
        yield eqn, under
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, under + (eqn.primitive.name,))


class _Traced:
    """Stands in for a jitted program in the warm worklist: `lower(...)`
    gives the program's jaxpr for the operands the worklist built."""

    def __init__(self, prog):
        self.prog = prog

    def lower(self, *args):
        return self.prog.trace(*args).jaxpr


@pytest.fixture(scope="module")
def sampling_programs():
    """fn -> the jaxpr of that step program, for every program of a
    speculating engine's warm worklist that samples."""
    be = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32, spec=2)
    for attr in ("_decode", "_decode_pen", "_hybrid", "_hybrid_pen",
                 "_spec_step", "_spec_step_pen", "_first_token"):
        setattr(be, attr, _Traced(getattr(be, attr)))
    return {fn: thunk(lower=True) for fn, key, thunk in be._warm_worklist(2, 4)
            if fn != "prefill_chunk" and key in ("n2", "b1", "p4.n2")}


SAMPLING_PROGRAMS = ("commit", "decode", "decode_pen", "hybrid", "hybrid_pen",
                     "spec", "spec_pen")


@pytest.mark.parametrize("fn", SAMPLING_PROGRAMS)
def test_step_program_holds_the_sampler_under_a_conditional(fn, sampling_programs):
    """In every program that samples, the candidates' top-k and every draw
    (`random_bits`: the gumbels of both categoricals) stand inside ONE
    `cond` of three branches whose index is a scalar of the whole batch. A
    per-row `vmap` of the sampler would have batched the index, and a `cond`
    with a batched index lowers to a select that runs every branch: no
    `cond` at all."""
    eqns = list(_eqns(sampling_programs[fn].jaxpr))
    heavy = [(e, under) for e, under in eqns
             if e.primitive.name in ("approx_top_k", "top_k", "random_bits")]
    assert {e.primitive.name for e, _ in heavy} >= {"approx_top_k", "random_bits"}
    for e, under in heavy:
        assert under.count("cond") == 1, f"{fn}: {e.primitive.name} under {under}"
    (cond, under), = [(e, u) for e, u in eqns if e.primitive.name == "cond"]
    assert cond.invars[0].aval.shape == (), cond.invars[0].aval
    # the argmax alone / + the temperature draw / + the nucleus: the first
    # branch holds no equation, only the last the candidates' top-k
    branches = [[e.primitive.name for e, _ in _eqns(b.jaxpr)]
                for b in cond.params["branches"]]
    assert branches[0] == []
    assert "random_bits" in branches[1] and "approx_top_k" not in branches[1]
    assert "approx_top_k" in branches[2]
    # the step programs sample inside their scan over the steps
    assert ("scan" in under) == (fn != "commit")


def _sampler_series():
    from dllama_tpu.obs import instruments as ins

    return dict(ins.SAMPLER_LAUNCHES.series())


@pytest.mark.parametrize("path,params", [
    ("greedy", dict(temperature=0.0, topp=0.9)),
    ("temperature", dict(temperature=0.8, topp=1.0)),
    ("nucleus", dict(temperature=0.8, topp=0.9)),
])
def test_sampler_launch_counter_moves_its_own_path_once_a_launch(path, params):
    """`dllama_sampler_launches_total{path}`: one count a launch, under the
    longest body the launch's ACTIVE slots ask for; a released slot's stale
    temperature counts for nothing, a prefill chunk moves no series."""
    from dllama_tpu.engine import launch_record

    be = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32)
    be.add(1, [7, 8, 9], temperature=1.2, topp=0.5)  # then released: stale
    be.release(1)
    before = _sampler_series()
    be.add(0, [1, 2, 3], **params)
    assert _sampler_series() == before  # prefill chunks and the commit
    be.decode(3)
    be.decode(2)
    moved = {k: v - before.get(k, 0) for k, v in _sampler_series().items()}
    assert moved == {p: (2 if p == path else 0)
                     for p in launch_record.SAMPLER_PATHS}
    # a sampled batch-mate moves the whole launch off the greedy body
    be.add(2, [4, 5], temperature=0.7, topp=0.8)
    mid = _sampler_series()
    be.decode(1)
    moved = {k: v - mid[k] for k, v in _sampler_series().items() if v != mid[k]}
    assert moved == {"nucleus": 1}


def test_spec_chunk_counts_its_sampler_path_once_at_consume():
    """A spec chunk's record is rebuilt when its rows are known: the path
    it was dispatched under rides along and is counted there, once."""
    be = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, spec=2)
    be.add(0, [1, 2, 3, 1, 2, 3], temperature=0.0)
    before = _sampler_series()
    chunk = be.decode_dispatch(2, spec=True)
    assert chunk.launch.sampler == "greedy"
    assert _sampler_series() == before
    be.decode_consume(chunk)
    moved = {k: v - before[k] for k, v in _sampler_series().items()}
    assert moved == {"greedy": 1, "temperature": 0, "nucleus": 0}
