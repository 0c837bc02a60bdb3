"""Paged KV cache tests (ISSUE 5): dense-vs-paged bit-exact parity across
sampling modes and overlap on/off, refcounted page lifecycle on release
rewinds, copy-on-write after prefix shares, capacity-aware admission
(deferral + eventual admit), and the shared-pages gauge.

The parity contract mirrors test_overlap.py's: with fixed prompts/seeds/
chunk, `--kv-layout dense` and `--kv-layout paged` (full-coverage pool)
produce BIT-IDENTICAL token streams — paging changes where KV rows live,
never what the device computes. Tiny config + memoized workloads keep this
file inside the time-budgeted tier-1 window."""

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.engine.batch import BatchEngine, PageExhausted
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params
from dllama_tpu.obs import instruments as ins
from dllama_tpu.serve.scheduler import Scheduler

CFG = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                  vocab_size=96, seq_len=64)
PARAMS = random_params(CFG, seed=3, dtype=jnp.float32, quantize=False)
PAGE = 8  # 8 blocks per 64-row context


def _engine(layout, n_slots=3, spec=0, kv_pages=0):
    return BatchEngine(CFG, PARAMS, n_slots=n_slots, cache_dtype=jnp.float32,
                       spec=spec, kv_layout=layout, page_size=PAGE,
                       kv_pages=kv_pages)


def _make_sched(layout, overlap=True, n_slots=3, chunk=3, spec=0, kv_pages=0):
    return Scheduler(_engine(layout, n_slots, spec, kv_pages), chunk=chunk,
                     overlap=overlap)


_WORKLOADS: dict = {}


def _run_workload(layout, overlap=True, spec=0):
    """Mixed workload (greedy + sampled + penalized, staggered submission);
    memoized per (layout, overlap, spec) — every parity test compares the
    same runs, and each engine costs a compile inside the tier-1 budget."""
    key = (layout, overlap, spec)
    if key in _WORKLOADS:
        return _WORKLOADS[key]
    sched = _make_sched(layout, overlap=overlap, spec=spec)
    try:
        r1 = sched.submit([1, 2, 3, 1, 2, 3], 0.0, 0.9, 12, frozenset(), seed=1)
        it1 = r1.tokens()
        head = [next(it1), next(it1)]  # r1 decodes before the others join
        r2 = sched.submit([9, 8, 7], 1.1, 0.9, 10, frozenset(), seed=42)
        r3 = sched.submit([4, 5], 0.9, 0.8, 8, frozenset(), seed=7,
                          presence=0.5, frequency=0.3)
        out2 = list(r2.tokens())
        out3 = list(r3.tokens())
        out1 = head + list(it1)
        _WORKLOADS[key] = [(out1, r1.finish_reason), (out2, r2.finish_reason),
                           (out3, r3.finish_reason)]
        return _WORKLOADS[key]
    finally:
        sched.shutdown()


# -------------------------------------------------------------------- parity


def test_paged_parity_mixed_batch():
    """Greedy + sampled + penalized requests: paged streams are bit-identical
    to dense, and paged overlap-on matches paged overlap-off."""
    dense = _run_workload("dense")
    assert _run_workload("paged") == dense
    assert _run_workload("paged", overlap=False) == dense


def test_paged_parity_with_spec():
    """Batched speculative decoding over the paged pool: same streams as the
    dense spec engine AND as the non-spec runs (spec is bit-exact greedy)."""
    dense_spec = _run_workload("dense", spec=4)
    assert _run_workload("paged", spec=4) == dense_spec
    assert dense_spec == _run_workload("dense")


def test_flash_paged_matches_jnp_gather(rng):
    """Op-level: the block-table-indexed flash kernel (interpret mode)
    matches the jnp gather reference on a shuffled page pool."""
    from dllama_tpu.ops.layers import paged_gqa_attention
    from dllama_tpu.ops.pallas.flash_attention import (
        paged_flash_gqa_attention,
        paged_supported,
    )

    b, t, hq, hkv, hd, page, nb = 2, 1, 4, 2, 64, 64, 2
    assert paged_supported((hq, hd), page)
    p = b * nb
    q = jnp.asarray(rng.standard_normal((b, t, hq, hd)), jnp.float32)
    pool_k = jnp.asarray(rng.standard_normal((p + 1, hkv, page, hd)), jnp.float32)
    pool_v = jnp.asarray(rng.standard_normal((p + 1, hkv, page, hd)), jnp.float32)
    tables = jnp.asarray(rng.permutation(p).reshape(b, nb), jnp.int32)
    for pos in ([70, 17], [0, 127]):
        pos = jnp.asarray(pos, jnp.int32)
        want = paged_gqa_attention(q, pool_k, pool_v, tables, pos)
        got = paged_flash_gqa_attention(q, pool_k, pool_v, tables, pos,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                                   atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ page lifecycle


def test_refcounted_free_on_release_rewind():
    """release(keep_rows=) returns exactly the tail pages; pages shared with
    another slot lose one reference without being freed."""
    eng = _engine("paged", n_slots=2)
    pool = eng.pool
    eng.add(0, list(range(1, 21)), temperature=0.0, seed=0)  # 20 rows
    eng.decode(8)  # pos 28 -> 4 pages
    assert pool.covered_rows(0) >= 28
    used_before = pool.stats()["used"]
    eng.release(0, keep_rows=10)  # keep 2 pages, free the rest
    st = pool.stats()
    assert st["used"] == used_before - (used_before - 2)
    assert pool.covered_rows(0) == 16 and int(eng.pos[0]) == 10

    # share the kept prefix into slot 1 (page-aligned: 8 rows = 1 full page)
    eng.copy_prefix_rows(0, 1, 8)
    shared_page = int(pool.tables[0, 0])
    assert int(pool.tables[1, 0]) == shared_page
    assert pool.refcount[shared_page] == 2 and pool.stats()["shared"] == 1
    # releasing the sharer decrements, never frees, the shared page
    free_before = pool.free_count
    eng.release(1, keep_rows=None)
    assert pool.refcount[shared_page] == 1
    assert pool.free_count == free_before  # slot 1 held no exclusive pages
    # releasing the owner finally frees it
    eng.release(0, keep_rows=None)
    assert pool.refcount[shared_page] == 0 and pool.stats()["used"] == 0


def test_cow_on_divergence_after_prefix_share():
    """An admission that diverges INSIDE a shared page copy-on-writes it:
    the donor's rows are untouched and its continuation is unchanged."""
    eng = _engine("paged", n_slots=2)
    solo = _engine("paged", n_slots=2)
    pool = eng.pool
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]  # exactly one page
    for e in (eng, solo):
        e.add(0, prompt, temperature=0.0, seed=0)
        e.release(0, keep_rows=8)
    eng.copy_prefix_rows(0, 1, 8)  # slot 1 aliases slot 0's page
    page0 = int(pool.tables[0, 0])
    assert pool.refcount[page0] == 2
    # admit into slot 1 with only 5 shared rows: rows 5.. of the SHARED page
    # are rewritten -> must copy-on-write before the scatter
    eng.add(1, [50, 51, 52], temperature=0.0, seed=2, start_pos=5)
    assert pool.refcount[page0] == 1, "divergence must un-share the page"
    assert int(pool.tables[1, 0]) != page0
    # the donor's cached rows survived: its continuation matches the engine
    # that never shared anything
    eng.release(1)
    eng.add(0, [9, 10], temperature=0.0, seed=1, start_pos=8)
    solo.add(0, [9, 10], temperature=0.0, seed=1, start_pos=8)
    np.testing.assert_array_equal(eng.decode(4)[:, 0], solo.decode(4)[:, 0])


def test_paged_capacity_exceeds_dense_footprint():
    """The acceptance-criterion capacity demo: 6 concurrent slots whose
    aggregate slot*seq_len demand (384 rows) exceeds the pool (128 rows =
    a 2-slot dense cache), all admitted and decoding AT ONCE — the dense
    layout cannot hold 6 concurrent sequences in that footprint."""
    from dllama_tpu.utils.profiling import cache_nbytes

    kv_pages = 16  # 16 * 8 = 128 rows
    eng = _engine("paged", n_slots=6, kv_pages=kv_pages)
    dense2 = _engine("dense", n_slots=2)
    # the pool's persistent footprint is at most the 2-slot dense cache (+1
    # trash page)
    assert cache_nbytes(eng.cache) <= cache_nbytes(dense2.cache) * (
        kv_pages + 1) / kv_pages
    assert 6 * CFG.seq_len > kv_pages * PAGE  # demand really overcommits
    for s in range(6):
        eng.add(s, [s + 1, s + 2, s + 3], temperature=0.0, seed=s)
    assert eng.active.all()  # all six admitted CONCURRENTLY
    toks = eng.decode(6)
    assert toks.shape == (6, 6)
    assert (eng.pos[:6] == 9).all()
    # and a prompt the pool can never hold fails loudly for direct callers
    eng.release(0)
    with pytest.raises((PageExhausted, ValueError)):
        eng.add(0, list(range(1, 60)), temperature=0.0, seed=9)


# -------------------------------------------------- capacity-aware admission


def test_admission_defers_until_pages_free():
    """Pool exhaustion defers admission (no slot assigned, no failure) and
    the request is admitted once a release frees pages — the scheduler's
    capacity = free pages, not free slots."""
    sched = _make_sched("paged", n_slots=3, chunk=3, kv_pages=8)  # 64 rows
    try:
        # r1: 40-row prompt -> 5 pages + decode reserve; its 20-token budget
        # grows it to 60 rows = ALL 8 pool pages while it runs
        r1 = sched.submit(list(range(1, 41)), 0.0, 0.9, 20, frozenset(), seed=1)
        it1 = r1.tokens()
        next(it1)
        # r2 needs ceil(30/8)+1 = 5 pages; at most 3 are ever free -> defer
        r2 = sched.submit(list(range(30, 60)), 0.0, 0.9, 4, frozenset(), seed=2)
        import time as _t

        deadline = _t.monotonic() + 30
        while not sched.health()["admission_deferred"]:
            assert _t.monotonic() < deadline, "admission never deferred"
            _t.sleep(0.01)
        assert r2.slot == -1  # parked, not admitted, not failed
        out1 = [next(it1) for _ in range(19)] + list(it1)
        out2 = list(r2.tokens())  # r1's release freed its pages
        assert r1.finish_reason == "length" and len(out1) + 1 == 20
        assert r2.finish_reason == "length" and len(out2) == 4
        assert not sched.health()["admission_deferred"]
    finally:
        sched.shutdown()


def test_oversized_prompt_rejected_not_deadlocked():
    """A prompt no empty pool could ever back fails fast with an error
    instead of deferring forever (and blocking the queue behind it)."""
    sched = _make_sched("paged", n_slots=2, chunk=3, kv_pages=8)
    try:
        # needs ceil(50/8)+1 = 8 pages... pool holds 8; make it need 9
        r = sched.submit(list(range(1, 60)), 0.0, 0.9, 4, frozenset(), seed=1)
        with pytest.raises(ValueError, match="KV pages"):
            list(r.tokens())
        assert r.finish_reason == "error"
        # the scheduler still serves well-sized requests afterwards
        ok = sched.submit([1, 2, 3], 0.0, 0.9, 4, frozenset(), seed=2)
        assert len(list(ok.tokens())) == 4
    finally:
        sched.shutdown()


def test_cross_slot_share_moves_shared_gauge():
    """Scheduler-level prefix reuse in paged mode shares pages instead of
    copying rows: the dllama_kv_pages_shared gauge goes positive when a
    request admits off an ACTIVE donor's cached prefix (the acceptance
    criterion's gauge check), and the reuse counter moves like dense."""
    sched = _make_sched("paged", n_slots=3, chunk=3)
    try:
        prompt_a = [1, 2, 3, 4, 5, 6, 7, 8]  # one full page
        ra = sched.submit(prompt_a, 0.0, 0.9, 4, frozenset(), seed=1)
        list(ra.tokens())  # slot cached with prompt_a + 4 tokens
        # rb takes the cached slot itself (longest idle prefix) and stays
        # ACTIVE while rc arrives; rc's only donor is then rb's busy slot ->
        # cross-slot page share into a fresh slot
        rb = sched.submit(prompt_a + [70], 0.0, 0.9, 30, frozenset(), seed=2)
        itb = rb.tokens()
        next(itb)
        before = sched.reused_prefix_tokens
        rc = sched.submit(prompt_a + [80], 0.0, 0.9, 4, frozenset(), seed=3)
        out_c = list(rc.tokens())
        assert len(out_c) == 4 and rc.finish_reason == "length"
        assert sched.reused_prefix_tokens - before >= len(prompt_a)
        assert ins.KV_PAGES_SHARED.value() >= 1, (
            "cross-slot prefix reuse must SHARE pages, not copy rows")
        assert sched.engine.pool.stats()["shared"] >= 1
        list(itb)
    finally:
        sched.shutdown()


def test_pool_audit_detects_corruption_and_double_free():
    """PagePool.audit(): clean on a live pool; detects a fabricated
    refcount/table mismatch (raising + counting); _decref refuses to drive
    a refcount negative (the double-release guard)."""
    from dllama_tpu.engine.batch import PoolAuditError
    from dllama_tpu.obs import metrics

    eng = _engine("paged", n_slots=2)
    pool = eng.pool
    eng.add(0, list(range(1, 20)), temperature=0.0, seed=0)
    eng.decode(4)
    assert pool.audit()["ok"]  # live pool, invariants hold
    fails0 = metrics.REGISTRY.sample("dllama_kv_audit_failures_total") or 0.0
    # fabricate corruption: bump a live page's refcount with no table ref
    page = int(pool.tables[0, 0])
    pool.refcount[page] += 1
    with pytest.raises(PoolAuditError, match="refcount"):
        pool.audit()
    report = pool.audit(raise_on_fail=False)
    assert not report["ok"] and report["problems"]
    pool.refcount[page] -= 1  # restore
    assert pool.audit()["ok"]
    # double-release guard: a second free of the same tail raises instead
    # of silently going negative
    pool.refcount[page] = 0  # as if already released (free list untouched)
    with pytest.raises(PoolAuditError, match="double release"):
        pool.free_tail(0, 0)
    fails = metrics.REGISTRY.sample("dllama_kv_audit_failures_total")
    assert fails >= fails0 + 3  # two failed audits + the double-free guard


def test_deferred_request_cut_cleanly_at_drain():
    """deferred x drain: a capacity-parked request gets a clean terminal
    finish at drain (no hang), its client sees the drain error, every page
    returns to the pool, and the audit is clean."""
    from dllama_tpu.serve.scheduler import SchedulerDraining
    from dllama_tpu.utils import faults

    sched = _make_sched("paged", n_slots=3, chunk=3, kv_pages=8)
    try:
        # slow chunks: r1 must still be running (and r2 still parked) when
        # the drain window closes
        faults.install("engine.decode", "delay", ms=30.0)
        r1 = sched.submit(list(range(1, 41)), 0.0, 0.9, 200, frozenset(),
                          seed=1)
        it1 = r1.tokens()
        next(it1)
        r2 = sched.submit(list(range(30, 60)), 0.0, 0.9, 4, frozenset(),
                          seed=2)
        import time as _t

        deadline = _t.monotonic() + 30
        while not sched.health()["admission_deferred"]:
            assert _t.monotonic() < deadline, "admission never deferred"
            _t.sleep(0.01)
        assert sched.drain(0.2) is False  # r1 outlives the window
        toks2 = []
        exc2 = None
        try:
            for t in r2.tokens():
                toks2.append(t)
        except SchedulerDraining as e:
            exc2 = e
        assert exc2 is not None and toks2 == []
        assert r2.finish_reason == "shutdown" and r2.slot == -1
        pool = sched.engine.pool
        assert pool.audit()["ok"]
        for s in range(sched.engine.n_slots):
            if not sched.engine.active[s]:
                sched.engine.drop_slot_pages(s)
        if sched.engine.radix is not None:
            # the radix tree's page refs are cache (committed prompts),
            # not leaks — drop them before the zero-leak assertion
            sched.engine.radix.clear()
        assert pool.stats()["used"] == 0, "drain leaked pages"
    finally:
        faults.clear()
        sched.shutdown()


def test_deferred_request_survives_restart():
    """deferred x restart: a worker crash with a capacity-parked head does
    not lose it — the running request resumes, the deferred one admits once
    pages free, and the rebuilt pool audits clean with zero leaks."""
    from dllama_tpu.utils import faults

    sched = _make_sched("paged", n_slots=3, chunk=3, kv_pages=8)
    sched.restart_max = 3
    sched.restart_backoff_s = 0.01
    try:
        warm = sched.submit([5, 6], 0.0, 0.9, 2, frozenset())
        list(warm.tokens())  # compile warm-up
        # slow every decode chunk a little: on a compile-warm CPU r1's whole
        # 8-token run takes ~3 fast chunks, so the window in which r2 sits
        # capacity-deferred is a few ms — narrower than the poll below, and
        # the test raced it (the pre-existing tier-1 flake this fixes). The
        # delay pins the deferred window open for ~hundreds of ms without
        # changing any scheduling semantics.
        faults.install("engine.decode", "delay", ms=30, times=40)
        # budget 8: prompt 40 + at most 7 resumed rows needs 7 pages incl.
        # the decode reserve, so the resume ALWAYS fits the 8-page pool no
        # matter how far r1 got before the crash
        r1 = sched.submit(list(range(1, 41)), 0.0, 0.9, 8, frozenset(),
                          seed=1)
        it1 = r1.tokens()
        next(it1)
        r2 = sched.submit(list(range(30, 60)), 0.0, 0.9, 4, frozenset(),
                          seed=2)
        import time as _t

        deadline = _t.monotonic() + 30
        while not sched.health()["admission_deferred"]:
            assert _t.monotonic() < deadline, "admission never deferred"
            _t.sleep(0.002)
        faults.install("scheduler.loop", "raise", times=1)
        # the plan is process-wide: another scheduler alive in this worker
        # (a fixture server's idle loop from an earlier file) can consume
        # the activation (faults.pending's docstring); re-arm until OUR
        # worker has restarted
        while sched.health()["restarts"] == 0:
            assert _t.monotonic() < deadline, "the worker never crashed"
            if not faults.pending("scheduler.loop"):
                _t.sleep(0.05)  # ours may be mid-restart, not yet counted
                if sched.health()["restarts"] == 0:
                    faults.install("scheduler.loop", "raise", times=1)
            _t.sleep(0.002)
        out1 = list(it1)
        out2 = list(r2.tokens())
        assert r1.finish_reason == "length" and len(out1) + 1 == 8
        assert r2.finish_reason == "length" and len(out2) == 4
        h = sched.health()
        assert h["live"] and h["restarts"] == 1
        assert not h["admission_deferred"]
        pool = sched.engine.pool
        assert pool.audit()["ok"]
        for s in range(sched.engine.n_slots):
            if not sched.engine.active[s]:
                sched.engine.drop_slot_pages(s)
        if sched.engine.radix is not None:
            sched.engine.radix.clear()  # tree refs are cache, not leaks
        assert pool.stats()["used"] == 0, "restart recovery leaked pages"
    finally:
        faults.clear()
        sched.shutdown()


def test_all_slots_starved_finishes_one_to_free_pages():
    """Pool dry with every active slot starved: the scheduler finishes the
    most-advanced request ('length') so its pages un-freeze the rest —
    bounded truncation instead of livelock."""
    sched = _make_sched("paged", n_slots=2, chunk=4, kv_pages=6)  # 48 rows
    try:
        # two requests wanting 40+ rows each (80 > 48): they must both still
        # FINISH (one truncated early by the starvation break)
        r1 = sched.submit([1, 2, 3], 0.0, 0.9, 40, frozenset(), seed=1)
        r2 = sched.submit([4, 5, 6], 0.0, 0.9, 40, frozenset(), seed=2)
        out1, out2 = list(r1.tokens()), list(r2.tokens())
        assert r1.finish_reason == "length" and r2.finish_reason == "length"
        assert len(out1) >= 1 and len(out2) >= 1
        # at least one was cut before its token budget by pool exhaustion
        assert len(out1) < 40 or len(out2) < 40
        st = sched.engine.pool.stats()
        assert st["used"] == 0 or st["used"] <= 6
    finally:
        sched.shutdown()


# -------------------------------------------------- host-RAM spill tier
# (ISSUE 16): radix eviction swaps cold pages d2h instead of discarding;
# a returning prompt restores them h2d at admission, byte-identical


def _host_engine(kv_pages=12, host_pages=6, n_slots=3):
    return BatchEngine(CFG, PARAMS, n_slots=n_slots, cache_dtype=jnp.float32,
                       kv_layout="paged", page_size=PAGE, kv_pages=kv_pages,
                       radix_cache="on", kv_host_pages=host_pages)


def _tree_page_map(eng):
    """{absolute token path through each page: device page index} for every
    page the radix tree currently references."""
    out = {}

    def walk(node, prefix):
        for ch in node.children.values():
            full = prefix + tuple(ch.tokens)
            start = len(prefix)
            for i, p in enumerate(ch.pages):
                out[full[:start + (i + 1) * PAGE]] = p
            walk(ch, full)

    walk(eng.radix.root, ())
    return out


def _page_bytes(eng, page):
    kpg, vpg = eng._read_page(eng.cache, jnp.int32(page))
    return np.asarray(kpg), np.asarray(vpg)


def test_host_tier_spill_restore_byte_identity():
    """Evict -> spill d2h -> returning prompt restores h2d: the restored
    device pages are byte-identical to the pre-eviction ones, the lookup
    covers every full page again (only the partial boundary page needs
    re-prefill), counters/gauges reconcile, and the token stream repeats
    bit-exact."""
    from dllama_tpu.obs import metrics

    eng = _host_engine()
    sched = Scheduler(eng, chunk=4, overlap=False)
    try:
        prompt = list(range(1, 18))  # 17 tokens -> 2 full pages of 8
        r1 = sched.submit(list(prompt), 0.0, 0.9, 6, frozenset(), seed=1)
        out1 = list(r1.tokens())
        before = {path: _page_bytes(eng, p)
                  for path, p in _tree_page_map(eng).items()}
        assert before, "radix tree should hold the finished request's pages"
        host = eng.pool.host
        out0 = ins.KV_SPILL.labels(direction="out").value()
        in0 = ins.KV_SPILL.labels(direction="in").value()
        freed = eng.radix_evict(100)
        assert freed >= len(before)
        assert host.used == len(before)
        assert host.stats()["spilled"] == len(before)
        assert ins.KV_SPILL.labels(
            direction="out").value() - out0 == len(before)
        assert metrics.REGISTRY.sample(
            "dllama_kv_host_pages_used") == float(len(before))
        assert eng.pool.audit()["ok"]
        # the returning prompt restores every FULL page from the host tier
        rows, hit = eng.radix_lookup(list(prompt))
        assert rows == ((len(prompt) - 1) // PAGE) * PAGE == 16
        assert host.used == 0
        assert host.stats()["restored"] == len(before)
        assert ins.KV_SPILL.labels(direction="in").value() - in0 \
            == len(before)
        after = _tree_page_map(eng)
        assert set(after) == set(before)
        for path, p in after.items():
            k_new, v_new = _page_bytes(eng, p)
            np.testing.assert_array_equal(k_new, before[path][0])
            np.testing.assert_array_equal(v_new, before[path][1])
        assert eng.pool.audit()["ok"]
        # the same request repeats bit-exact THROUGH the restored pages
        r2 = sched.submit(list(prompt), 0.0, 0.9, 6, frozenset(), seed=1)
        assert list(r2.tokens()) == out1
        assert eng.pool.audit()["ok"]
    finally:
        sched.shutdown()


def test_host_tier_audit_catches_leaked_page():
    """A host entry the pool didn't publish (leak stand-in: unaligned key,
    wrong payload geometry, gauge drift) must fail PagePool.audit() loudly
    and count on dllama_kv_audit_failures_total."""
    from dllama_tpu.engine.batch import PoolAuditError
    from dllama_tpu.obs import metrics

    eng = _host_engine()
    host = eng.pool.host
    assert eng.pool.audit()["ok"]
    fails0 = metrics.REGISTRY.sample("dllama_kv_audit_failures_total") or 0.0
    bogus = np.zeros((CFG.n_layers, CFG.n_kv_heads, 3,
                      CFG.dim // CFG.n_heads), np.float32)
    host._entries[(1, 2, 3)] = (bogus, bogus)  # 3-token key, 3-row payload
    with pytest.raises(PoolAuditError):
        eng.pool.audit()
    report = eng.pool.audit(raise_on_fail=False)
    assert not report["ok"]
    assert any("host" in p for p in report["problems"])
    del host._entries[(1, 2, 3)]
    host._publish()
    assert eng.pool.audit()["ok"]
    assert metrics.REGISTRY.sample("dllama_kv_audit_failures_total") \
        >= fails0 + 2


def test_warm_restart_drops_both_tiers_together():
    """Warm restart must reset the HOST tier with the device tier: stale
    host payloads surviving a restart would be restored into a rebuilt
    pool whose contents they no longer match."""
    eng = _host_engine()
    sched = Scheduler(eng, chunk=4, overlap=False)
    prompt = list(range(1, 18))
    try:
        r1 = sched.submit(list(prompt), 0.0, 0.9, 4, frozenset(), seed=1)
        list(r1.tokens())
    finally:
        sched.shutdown()
    eng.radix_evict(100)
    host = eng.pool.host
    assert host.used > 0
    eng.warm_restart()
    host2 = eng.pool.host
    assert host2 is not host, "restart must rebuild the host pool"
    assert host2.used == 0 and host2.stats()["spilled"] == 0
    rows, _hit = eng.radix_lookup(list(prompt))
    assert rows == 0  # both tiers gone: nothing to restore from
    assert eng.pool.audit()["ok"]
