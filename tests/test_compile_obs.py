"""Compile & device-traffic observability (ISSUE 13, obs/compile).

Contracts driven here:

* shape-bucket classification: declared keys are expected, allow-range
  keys are expected, anything else on a DECLARED fn is unexpected (counter
  + structured warning), and fns with no declarations never false-alarm;
* the compile ledger is ground truth (jax.monitoring events, not a host
  shape model) and thread-safe under concurrent scoped dispatches;
* warmup report correctness: --warmup auto reaches full declared bucket
  coverage and the FIRST real request after it compiles NOTHING; a second
  warmup on the same engine finds everything cached;
* the acceptance drill: a steady-state decode window records ZERO compiles
  (unexpected or otherwise) and ZERO host->device upload bytes across
  {dense, paged} x overlap {on, off} x spec — under transfer_guard=strict,
  so an implicit upload raises instead of merely moving a counter;
* the strict guard really trips on an injected per-chunk upload.

Tiny 1-layer config + memoized engines, same discipline as test_hybrid.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params
from dllama_tpu.obs import compile as cobs
from dllama_tpu.obs import metrics

CFG = LlamaConfig(dim=32, hidden_dim=64, n_layers=1, n_heads=2, n_kv_heads=1,
                  vocab_size=64, seq_len=64)
PARAMS = random_params(CFG, seed=5, dtype=jnp.float32, quantize=False)
PAGE = 8


def _fresh_contract():
    """Install an empty contract (classification 'undeclared' everywhere)
    so unit tests are isolated from whatever engine ran last; returns the
    displaced contract for restoration."""
    old = cobs.LEDGER.contract
    cobs.LEDGER.install_contract(cobs.ShapeContract())
    return old


# ------------------------------------------------------------ contract unit


def test_contract_classification_expected_unexpected_undeclared():
    c = cobs.ShapeContract()
    c.declare("decode", "n1")
    c.declare("decode", "n4", warm=True)
    c.allow("decode", BatchEngine._n_in_range(1, 4))
    assert c.classify("decode", "n1") == "expected"
    assert c.classify("decode", "n4") == "expected"
    assert c.classify("decode", "n3") == "expected"  # allow-range clamp
    assert c.classify("decode", "n9") == "unexpected"
    assert c.classify("decode", "bogus") == "unexpected"
    # a fn with no declarations has no contract to violate
    assert c.classify("spec", "n1") == "undeclared"
    with pytest.raises(ValueError, match="unknown compile fn"):
        c.declare("not_a_fn", "x")


def test_contract_hybrid_keys_and_coverage():
    c = cobs.ShapeContract()
    for p in (1, 2, 4):
        c.declare("hybrid", f"p{p}.n3")
    c.allow("hybrid", BatchEngine._hybrid_in_range((1, 2, 4), 3))
    assert c.classify("hybrid", "p4.n3") == "expected"
    assert c.classify("hybrid", "p2.n1") == "expected"  # clamped decode len
    assert c.classify("hybrid", "p8.n3") == "unexpected"  # undeclared slice
    assert c.classify("hybrid", "p4.n7") == "unexpected"  # over-chunk
    cov = c.coverage({"hybrid": {"p1.n3", "p2.n3", "p2.n1", "p9.n9"}})
    h = cov["fns"]["hybrid"]
    assert h["declared"] == 3 and h["warm_targets"] == 3
    assert h["compiled"] == 2
    assert h["missing_warm"] == ["p4.n3"]
    assert h["unexpected_seen"] == ["p9.n9"]  # p2.n1 is allowed, not flagged
    assert cov["full"] is False
    cov2 = c.coverage({"hybrid": {"p1.n3", "p2.n3", "p4.n3"}})
    assert cov2["full"] is True


def test_sig_of():
    s = cobs.sig_of(jnp.zeros((2, 3), jnp.int32), 7, True)
    assert "int32[2,3]" in s and "7" in s and "True" in s


def test_transfer_accounting_snapshot():
    cobs.reset_transfers()
    base_b = metrics.REGISTRY.sample(
        "dllama_transfer_bytes_total",
        {"direction": "h2d", "site": "vectors"}) or 0.0
    cobs.note_transfer("h2d", "vectors", 100)
    cobs.note_transfer("h2d", "vectors", 20)
    cobs.note_transfer("d2h", "decode_tokens", 64)
    snap = cobs.transfer_snapshot()
    assert snap["sites"]["h2d.vectors"] == {"count": 2, "bytes": 120}
    assert snap["h2d"] == {"count": 2, "bytes": 120}
    assert snap["d2h"] == {"count": 1, "bytes": 64}
    # the registry counters moved in lockstep (lifetime, not reset)
    assert metrics.REGISTRY.sample(
        "dllama_transfer_bytes_total",
        {"direction": "h2d", "site": "vectors"}) == base_b + 120
    cobs.reset_transfers()
    assert cobs.transfer_snapshot()["h2d"]["bytes"] == 0


# ------------------------------------------------------------- ledger unit


def test_ledger_records_real_compiles_and_is_thread_safe():
    """Concurrent scoped dispatches over distinct shapes: every compile is
    attributed to its scope's (fn, key), totals are consistent, and cached
    re-calls record nothing."""
    old = _fresh_contract()
    cobs.LEDGER.reset()
    f = jax.jit(lambda x: x * 2 + 1)
    errs: list = []

    def worker(tid):
        try:
            for i in range(3):
                with cobs.LEDGER.scope("decode", f"t{tid}i{i}"):
                    f(jnp.zeros(8 + tid * 16 + i))
        except Exception as e:  # pragma: no cover
            errs.append(e)

    try:
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        snap = cobs.LEDGER.snapshot()
        assert snap["totals"]["decode"]["compiles"] == 12
        assert len(snap["seen"]["decode"]) == 12
        assert snap["totals"]["decode"]["unexpected"] == 0  # undeclared fn
        assert all(e["total_s"] > 0 for e in snap["entries"])
        # a cached re-dispatch records nothing
        before = cobs.LEDGER.total_compiles()
        with cobs.LEDGER.scope("decode", "t0i0"):
            f(jnp.zeros(8))
        assert cobs.LEDGER.total_compiles() == before
    finally:
        cobs.LEDGER.install_contract(old)


def test_unexpected_compile_classified_counted_and_logged(caplog):
    old = cobs.LEDGER.contract
    contract = cobs.ShapeContract()
    contract.declare("decode", "n1")
    contract.allow("decode", BatchEngine._n_in_range(1, 2))
    cobs.LEDGER.install_contract(contract)
    f = jax.jit(lambda x: x - 3.0)
    base = metrics.REGISTRY.sample(
        "dllama_jit_unexpected_compiles_total", {"fn": "decode"}) or 0.0
    try:
        import logging

        with caplog.at_level(logging.WARNING, logger="dllama_tpu.obs"):
            with cobs.LEDGER.scope("decode", "n9",
                                   sig=lambda: "f32[9]"):
                f(jnp.zeros(9))
        entry = cobs.LEDGER.snapshot()["entries"][-1]
        assert entry["classification"] == "unexpected"
        assert entry["key"] == "n9" and entry["sig"] == "f32[9]"
        assert metrics.REGISTRY.sample(
            "dllama_jit_unexpected_compiles_total",
            {"fn": "decode"}) == base + 1
        assert any("unexpected jit compile" in r.message
                   for r in caplog.records), "no structured warning"
        # an allowed clamp key stays expected
        with cobs.LEDGER.scope("decode", "n2"):
            f(jnp.zeros(2))
        assert (cobs.LEDGER.snapshot()["entries"][-1]["classification"]
                == "expected")
    finally:
        cobs.LEDGER.install_contract(old)


# ------------------------------------------------------ engines & warmup


_ENGINES: dict = {}


def _engine(layout, spec=0):
    key = (layout, spec)
    if key not in _ENGINES:
        _ENGINES[key] = BatchEngine(
            CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32, spec=spec,
            kv_layout=layout, page_size=PAGE, max_prefill_chunk=4)
    return _ENGINES[key]


def test_warmup_report_full_coverage_then_zero_compile_request():
    """--warmup auto: the report covers every declared warm bucket, the
    first REAL request compiles nothing, and a second warmup on the same
    engine finds the whole universe cached."""
    from dllama_tpu.serve.scheduler import Scheduler

    cobs.LEDGER.reset()  # the ledger is process-global and earlier tests
    # deliberately recorded an unexpected compile — health() reports
    # lifetime totals, so this test wants a clean slate
    jax.clear_caches()  # so are jax's compiled eager ops: a test file run
    # earlier in this process at the same vocab (tests/test_engine.py) leaves
    # the commit bucket's sampling ops compiled, the bucket then counts as
    # cached, is never seen by the reset ledger, and coverage reads incomplete
    eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32,
                      kv_layout="paged", page_size=PAGE, max_prefill_chunk=4)
    sched = Scheduler(eng, chunk=2, warmup="auto")
    try:
        rep = sched.warmup_report
        assert rep is not None and rep["full_coverage"] is True
        assert rep["buckets"] == rep["compiled"] + rep["cached"]
        assert rep["compiled"] > 0 and rep["seconds"] > 0
        # decode + pen + prefill pow2s + commit + hybrid slices all warmed
        assert {"prefill_chunk", "commit", "decode", "decode_pen",
                "hybrid", "hybrid_pen"} <= set(rep["per_fn"])
        before = cobs.LEDGER.total_compiles()
        r = sched.submit([1, 2, 3, 4, 5], 0.0, 0.9, 5, frozenset(), seed=1)
        assert len(list(r.tokens())) == 5
        assert cobs.LEDGER.total_compiles() == before, (
            "a warmed engine's first request must pay zero compile")
        # the serving surfaces carry the record
        assert sched.latency_summary()["compile"]["warmup_mode"] == "auto"
        h = sched.health()["compile"]
        assert h["full_coverage"] is True and h["unexpected_compiles"] == 0
    finally:
        sched.shutdown()
    # second scheduler over the same engine: everything is already cached
    sched2 = Scheduler(eng, chunk=2, warmup="auto")
    try:
        rep2 = sched2.warmup_report
        assert rep2["compiled"] == 0 and rep2["cached"] == rep2["buckets"]
    finally:
        sched2.shutdown()


def test_meter_measures_without_an_entry_and_is_absorbed():
    """`LEDGER.meter()` counts compile events into itself and records
    nothing; the dispatch scope that absorbs it makes ONE entry that
    carries the meter's seconds beside its own."""
    old = _fresh_contract()
    try:
        led = cobs.LEDGER
        f = jax.jit(lambda x: jnp.cos(x) * 3.25 + 1)
        x = jnp.ones((5, 3))  # its own eager compile, before the count
        n0, c0 = len(led.entries), led.total_compiles()
        meter = led.meter()
        with meter:
            lowered = f.lower(x)

        def compile_there():  # another thread, the same meter
            with meter:
                lowered.compile()

        th = threading.Thread(target=compile_there)
        th.start()
        th.join()
        assert meter.compile_s > 0 and meter.lower_s + meter.trace_s > 0
        assert meter.n_backend == 1
        assert len(led.entries) == n0 and led.total_compiles() == c0
        with led.scope("metered", "k") as sc:
            sc.absorb(meter)
            f(x)  # finds the lowering and the executable: no second compile
        assert sc.n_backend == 1 and sc.compile_s == meter.compile_s
        assert len(led.entries) == n0 + 1
        assert led.entries[-1]["fn"] == "metered"
        assert led.entries[-1]["compile_s"] > 0
    finally:
        cobs.LEDGER.install_contract(old)


def test_warmup_compiles_ahead_one_entry_a_program():
    """warmup() lowers every program, compiles them on a pool of threads,
    and its dispatches then find the executables: each program has exactly
    one ledger entry with one backend compile's seconds in it, none is
    compiled a second time at dispatch, and the engine's state is as a
    serial warm-up leaves it."""
    cobs.LEDGER.reset()
    eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32,
                      kv_layout="paged", page_size=PAGE, max_prefill_chunk=4)
    seen: list = []
    real = eng._precompile

    def spy(work):
        meters = real(work)
        seen.extend(meters)
        return meters

    eng._precompile = spy
    rep = eng.warmup(chunk=2, hybrid_budget_hi=4)
    named = [(fn, key) for fn, key, _ in eng._warm_worklist(2, 4)]
    assert ("commit", "b1") in named  # the first-token sampling is a program
    metered = [m for m in seen if m is not None]
    assert len(metered) == len(named)  # every program of the worklist
    assert all(m.n_backend == 1 and m.compile_s > 0 for m in metered)
    entries = cobs.LEDGER.snapshot(entries=256)["entries"]
    # beside the worklist: the activation's row writes, warmed with the
    # boundary ops (`_warm_boundary_ops`)
    assert [(e["fn"], e["key"]) for e in entries if e["fn"] == "boundary"] == [
        ("boundary", "commit_rows")]
    entries = [e for e in entries if e["fn"] != "boundary"]
    assert sorted((e["fn"], e["key"]) for e in entries) == sorted(named)
    assert all(e["warmup"] and e["compile_s"] > 0 for e in entries)
    # the dispatch pass added no backend compile to a program's entry
    totals = cobs.LEDGER.snapshot()["totals"]
    assert sum(t["compiles"] for fn, t in totals.items()
               if fn not in ("boundary", "untracked")) == len(named)
    assert rep["compiled"] == len(named) and eng._counts is None
    assert eng._warmed >= set(named)
    # a second warm-up lowers nothing ahead and finds everything cached
    seen.clear()
    rep2 = eng.warmup(chunk=2, hybrid_budget_hi=4)
    assert seen and all(m is None for m in seen)
    assert rep2["compiled"] == 0 and rep2["cached"] == rep2["buckets"]


def test_first_token_sampling_is_one_program_and_warm_after_warmup(monkeypatch):
    """`add_sample` dispatches ONE program under the ("commit", "b1")
    scope, counted by the backend compiles the ledger saw on a vocabulary
    no other test compiled at: the key derivation, the split and the
    sampler were about forty programs, a few of them outside any scope.
    After warmup() the same call compiles nothing."""
    cfg = LlamaConfig(dim=32, hidden_dim=64, n_layers=1, n_heads=2,
                      n_kv_heads=1, vocab_size=104, seq_len=32)
    params = random_params(cfg, seed=5, dtype=jnp.float32, quantize=False)
    scopes: list = []
    real = cobs.LEDGER.scope

    def scope(fn, key="", sig=None):
        scopes.append(real(fn, key, sig))
        return scopes[-1]

    def sample_once(eng, seed):
        adm = eng.add_begin(0, [1, 2, 3])
        while not eng.add_step(adm):
            pass
        jax.block_until_ready(adm.logits)
        scopes.clear()
        before = cobs.LEDGER.total_compiles()
        eng.add_sample(adm, 0.8, 0.9, seed=seed)
        assert adm.sampled is not None
        return cobs.LEDGER.total_compiles() - before, adm

    monkeypatch.setattr(cobs.LEDGER, "scope", scope)
    cold = BatchEngine(cfg, params, n_slots=2, cache_dtype=jnp.float32)
    assert sample_once(cold, seed=3)[0] == 1  # scoped and untracked together
    assert [(sc.fn, sc.key, sc.n_backend) for sc in scopes] == [
        ("commit", "b1", 1)]
    assert sample_once(cold, seed=None)[0] == 0  # the other key, one program
    warm = BatchEngine(cfg, params, n_slots=2, cache_dtype=jnp.float32)
    warm.warmup(chunk=2)
    compiled, adm = sample_once(warm, seed=None)
    before = cobs.LEDGER.total_compiles()
    warm.add_commit(adm, 0.8, 0.9)  # its row writes are one warm program too
    assert compiled == 0 and cobs.LEDGER.total_compiles() == before
    assert [(sc.fn, sc.key, sc.n_backend) for sc in scopes] == [
        ("commit", "b1", 0), ("boundary", "commit_rows", 0)]


def test_warmup_rejects_busy_engine():
    eng = _engine("dense")
    if not eng.active.any():
        eng.add(0, [1, 2], temperature=0.0, seed=3)
    with pytest.raises(RuntimeError, match="before any slot is active"):
        eng.warmup(chunk=2)
    eng.release(0, None)


# --------------------------------------------------- steady-state drill


def _steady_window(eng, spec: bool, overlap: bool, chunks: int = 3) -> None:
    """Measure `chunks` steady-state decode (or spec) chunks under the
    strict transfer guard: total compiles and h2d upload bytes must both
    be exactly zero."""
    n = 2
    c0 = cobs.LEDGER.total_compiles()
    cobs.reset_transfers()
    if overlap:
        pending = eng.decode_dispatch(n, spec=spec)
        for _ in range(chunks - 1):
            nxt = eng.decode_dispatch(n, spec=spec)
            eng.decode_consume(pending)
            pending = nxt
        eng.decode_consume(pending)
    else:
        for _ in range(chunks):
            eng.decode_consume(eng.decode_dispatch(n, spec=spec))
    snap = cobs.transfer_snapshot()
    assert cobs.LEDGER.total_compiles() - c0 == 0, (
        f"steady-state window recompiled: "
        f"{cobs.LEDGER.snapshot()['entries'][-3:]}")
    assert snap["h2d"] == {"count": 0, "bytes": 0}, (
        f"steady-state host->device upload: {snap['sites']}")
    assert snap["d2h"]["bytes"] > 0  # tokens still materialize, of course


@pytest.mark.parametrize("layout,spec", [("dense", 0), ("dense", 2),
                                         ("paged", 0), ("paged", 2)])
def test_steady_state_zero_compiles_zero_uploads(layout, spec):
    """The acceptance drill: a 3-chunk steady-state decode records ZERO
    compiles and ZERO uploads — {dense, paged} x overlap {on, off} x spec,
    with transfer_guard=strict so an implicit upload raises."""
    eng = _engine(layout, spec)
    u0 = cobs.LEDGER.total_unexpected()
    if not eng.active.any():
        eng.add(0, [1, 2, 3], temperature=0.0, seed=1)
        eng.add(1, [4, 5, 6], temperature=0.0, seed=2)
    use_spec = spec > 0
    # warm past the admission boundary, then pre-provision the window's
    # pages (page allocation is an amortized boundary event, not per-chunk
    # traffic) and consume the resulting vector refresh with one chunk
    eng.decode_consume(eng.decode_dispatch(2, spec=use_spec))
    eng._alloc_decode_rows(48)
    eng.decode_consume(eng.decode_dispatch(2, spec=use_spec))
    eng.transfer_guard = "strict"
    try:
        _steady_window(eng, use_spec, overlap=False)
        _steady_window(eng, use_spec, overlap=True)
    finally:
        eng.transfer_guard = "off"
    assert cobs.LEDGER.total_unexpected() == u0, "contract flagged steady work"


def test_transfer_guard_strict_trips_on_injected_upload():
    """An injected host-resident decode carry (the exact per-chunk upload
    PR 3 eliminated) fails the dispatch loudly under strict mode. The
    engine's donated buffers are indeterminate after the failed launch, so
    the memoized engine is discarded."""
    eng = _ENGINES.pop(("dense", 0), None) or BatchEngine(
        CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32,
        kv_layout="dense", max_prefill_chunk=4)
    if not eng.active.any():
        eng.add(0, [1, 2, 3], temperature=0.0, seed=1)
    eng.decode(2)
    eng.transfer_guard = "strict"
    eng._last_dev = np.asarray(eng._last_dev)  # the injected upload
    with pytest.raises(Exception, match="(?i)transfer|disallow"):
        eng.decode_dispatch(2)
