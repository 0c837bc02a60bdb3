"""Hybrid chunked-prefill/decode fused step, SLO-driven budgets, priority/
fair-queue scheduling, and preempt-to-pages (ISSUE 12).

Contracts driven here:

* token streams are BIT-EXACT hybrid-on vs the legacy phase-split path
  (--prefill-budget 0) across {greedy, sampled, penalized, spec} x
  {dense, paged} x overlap {on, off} x radix {on, off} — fusing a prefill
  slice into the decode launch changes WHEN prompt rows are written, never
  what any slot computes;
* a preempted request's stream is BYTE-IDENTICAL to its uninterrupted run
  (greedy and sampled, incl. across a warm restart), with clean pool
  audits (DLLAMA_POOL_AUDIT=1 is armed suite-wide by conftest);
* weighted fair queueing bounds a backlogged tenant's wait (no starvation
  behind another tenant's flood) and priority classes admit strictly
  first;
* the --prefill-budget auto controller shrinks the budget when the
  windowed ITL p95 violates --slo-itl-ms and grows it under headroom.

Tiny config + memoized workloads, same discipline as test_paged_kv.py.
Engines are SESSION-SHARED across the scheduler matrix (keyed on the
shapes that force a rebuild: layout, spec, n_slots): every run after the
first reuses the resident jitted callables via engine.warm_restart() —
decode state, page pool, and radix tree rebuilt, ZERO recompiles — which
is what keeps this suite from displacing the tier-1 tail past the time
budget (the PR 11 regression ISSUE 13 calls out). Every submit is seeded,
so shared PRNG/admission counters cannot leak between runs.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.engine.sampling import sample_logits
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params
from dllama_tpu.obs import perf
from dllama_tpu.serve.scheduler import Scheduler
from dllama_tpu.utils import faults

CFG = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                  vocab_size=96, seq_len=64)
PARAMS = random_params(CFG, seed=3, dtype=jnp.float32, quantize=False)
PAGE = 8

LONG_PROMPT = [int(x) % 90 + 1 for x in range(7, 31)]  # 24 tokens: several
# budget-4 slices, so the admission really rides multiple hybrid chunks

_ENGINES: dict = {}


def _engine(layout, spec=0, n_slots=3):
    """Session-shared engine (one XLA compile set per key). Reuse goes
    through warm_restart(): decode state + pool + an EMPTY radix tree are
    rebuilt against the resident weights while the jitted callables — and
    their compiles — survive, so no run sees another run's cache."""
    key = (layout, spec, n_slots)
    eng = _ENGINES.get(key)
    if eng is None:
        eng = _ENGINES[key] = BatchEngine(
            CFG, PARAMS, n_slots=n_slots, cache_dtype=jnp.float32, spec=spec,
            kv_layout=layout, page_size=PAGE, radix_cache="auto",
            max_prefill_chunk=8)
        return eng
    if eng.pool is not None and eng.radix is None:
        # a radix="off" run disabled the tree for its scheduler's lifetime;
        # restore it so warm_restart rebuilds it against the fresh pool
        from dllama_tpu.engine.radix import RadixCache

        eng.radix = RadixCache(eng.pool)
    eng.warm_restart()
    return eng


def _sched(layout, *, overlap=True, spec=0, radix="auto", budget="auto",
           n_slots=3, chunk=3, **kw):
    eng = _engine(layout, spec, n_slots)
    if radix == "off" and eng.radix is not None:
        eng.radix = None  # per-run opt-out; _engine restores it on reuse
    return Scheduler(eng, chunk=chunk, overlap=overlap,
                     prefill_budget=budget, **kw)


def _mixed_workload(sched):
    """Greedy decoders running, then a long sampled joiner and a penalized
    one — the join paths are exactly where hybrid differs from phase-split."""
    r1 = sched.submit([1, 2, 3, 1, 2, 3], 0.0, 0.9, 12, frozenset(), seed=1)
    it1 = r1.tokens()
    head = [next(it1), next(it1)]  # r1 decodes before the others join
    r2 = sched.submit(LONG_PROMPT, 1.1, 0.9, 8, frozenset(), seed=42)
    r3 = sched.submit([4, 5], 0.9, 0.8, 6, frozenset(), seed=7,
                      presence=0.5, frequency=0.3)
    out2 = list(r2.tokens())
    out3 = list(r3.tokens())
    out1 = head + list(it1)
    return [(out1, r1.finish_reason), (out2, r2.finish_reason),
            (out3, r3.finish_reason)]


_RUNS: dict = {}


def _run(layout, overlap=True, spec=0, radix="auto", budget="auto"):
    key = (layout, overlap, spec, radix, budget)
    if key in _RUNS:
        return _RUNS[key]
    sched = _sched(layout, overlap=overlap, spec=spec, radix=radix,
                   budget=budget)
    try:
        _RUNS[key] = _mixed_workload(sched)
        if budget != 0:
            # the joiner's prefill really rode fused chunks (the whole
            # point — without this the parity below proves nothing)
            assert sched.ledger.totals["hybrid"] > 0.0
        if sched.engine.pool is not None:
            assert sched.engine.pool.audit()["ok"]
        return _RUNS[key]
    finally:
        sched.shutdown()


# ------------------------------------------------------------------- parity


def test_hybrid_bit_exact_paged():
    """Paged layout (radix on, the serving default): hybrid-on streams are
    bit-identical to --prefill-budget 0, overlap on AND off. (One legacy
    reference run serves every axis: legacy overlap-invariance is already
    test_overlap's proven contract, so hybrid runs compare transitively
    against the single overlap-on legacy baseline — each dropped engine
    build buys the time-budgeted tier-1 ~10s of tail coverage.)"""
    legacy = _run("paged", budget=0)
    assert _run("paged") == legacy
    assert _run("paged", overlap=False) == legacy


def test_hybrid_bit_exact_radix_off():
    """Radix off (per-slot prefix cache): same parity (radix on/off token
    invariance is test_radix's proven contract — the paged legacy run is
    the one reference)."""
    assert _run("paged", radix="off") == _run("paged", budget=0)


def test_hybrid_bit_exact_dense():
    """Dense layout: hybrid fuses through the batch-axis slice prefill
    (dense == paged is the PR 5 contract, so the paged legacy run is the
    reference)."""
    assert _run("dense") == _run("paged", budget=0)


def test_hybrid_bit_exact_with_spec():
    """Spec engine (K=2): hybrid chunks are plain chunks that drain the
    spec pipeline at mode switches — streams stay bit-exact vs budget 0
    and vs the non-spec run (greedy spec is exact)."""
    legacy = _run("paged", spec=2, budget=0)
    assert _run("paged", spec=2) == legacy


# --------------------------------------------------------------- preemption


def _preempt_run(seed, temperature, crash=False):
    """Low-priority request (1 slot) preempted by a high-priority arrival;
    optionally a worker crash while it sits suspended. Returns its stream."""
    sched = _sched("paged", n_slots=1, chunk=2)
    if crash:
        sched.restart_max = 3
        sched.restart_backoff_s = 0.01
    try:
        lo = sched.submit([1, 2, 3], temperature, 0.9, 18, frozenset(),
                          seed=seed, priority=0, tenant="batch")
        it = lo.tokens()
        first = next(it)
        # slow chunks so the high-pri arrival lands mid-stream, not after
        faults.install("engine.decode", "delay", ms=15, times=80)
        hi = sched.submit([9, 8, 7], 0.0, 0.9, 10 if crash else 4,
                          frozenset(), seed=6, priority=2,
                          tenant="interactive")
        hit = hi.tokens()
        first_hi = next(hit)
        if crash:
            # the crash must land while lo is PARKED: hi is still running
            # (10 slow chunks), so poll for the preempted record and then
            # arm a worker crash — lo's resume record is host-side and must
            # survive the restart (the dead radix tree just costs it a
            # re-prefill at resume)
            deadline = time.monotonic() + 30
            while not any(r.preempted for r in sched._backlog):
                assert lo.finish_reason is None, "lo finished unpreempted"
                assert time.monotonic() < deadline, "preemption never parked"
                time.sleep(0.002)
            faults.install("scheduler.loop", "raise", times=1)
        out_hi = [first_hi] + list(hit)
        assert hi.finish_reason == "length"
        assert sched.preempt_count >= 1, "high-priority arrival never preempted"
        out_lo = [first] + list(it)
        assert lo.finish_reason == "length"
        assert sched.resume_count >= 1
        if crash:
            assert sched.health()["restarts"] == 1
        assert sched.engine.pool.audit()["ok"]
        return out_lo
    finally:
        faults.clear()
        sched.shutdown()


def _uninterrupted(seed, temperature):
    sched = _sched("paged", n_slots=1, chunk=2)
    try:
        r = sched.submit([1, 2, 3], temperature, 0.9, 18, frozenset(),
                         seed=seed)
        return list(r.tokens())
    finally:
        sched.shutdown()


def test_preempt_resume_bit_exact_greedy_and_sampled():
    """Preempt -> park -> resume: the stream is byte-identical to the
    uninterrupted run — greedy trivially, sampled because the resume
    replays the recorded PRNG key advanced to the interruption point."""
    assert _preempt_run(5, 0.0) == _uninterrupted(5, 0.0)
    assert _preempt_run(11, 0.8) == _uninterrupted(11, 0.8)


@pytest.mark.slow
def test_preempt_survives_warm_restart():
    """A request preempted to pages survives a worker crash while suspended
    (its resume record is host-side; the dead tree just costs a re-prefill)
    and still resumes byte-identical."""
    assert _preempt_run(13, 0.7, crash=True) == _uninterrupted(13, 0.7)


def test_preempt_off_never_fires():
    sched = _sched("paged", n_slots=1, chunk=2, preempt="off")
    try:
        lo = sched.submit([1, 2, 3], 0.0, 0.9, 10, frozenset(), seed=5,
                          priority=0)
        it = lo.tokens()
        next(it)
        hi = sched.submit([9, 8, 7], 0.0, 0.9, 2, frozenset(), seed=6,
                          priority=2)
        list(hi.tokens())
        list(it)
        assert sched.preempt_count == 0
        # without preemption the high-pri request simply waited for the slot
        assert hi.finish_reason == "length"
    finally:
        sched.shutdown()


# ------------------------------------------------- priorities & fair queue


def _hold_worker(sched, warm_seed=99):
    """Run one request to warm compiles, then slow decode chunks so a batch
    of submissions lands in the backlog while the slot is busy."""
    w = sched.submit([5, 6], 0.0, 0.9, 2, frozenset(), seed=warm_seed)
    list(w.tokens())


def test_wfq_starvation_bound():
    """One tenant flooding the queue cannot starve another: with equal
    weights the interleave is ~1:1, so tenant B's single request admits
    before the flood's tail (the WFQ virtual-time bound)."""
    sched = _sched("paged", n_slots=1, chunk=2)
    try:
        _hold_worker(sched)
        faults.install("engine.decode", "delay", ms=10, times=200)
        runner = sched.submit([7, 7, 7], 0.0, 0.9, 10, frozenset(), seed=1,
                              tenant="A")
        it = runner.tokens()
        next(it)  # tenant A occupies the slot; everything below backlogs
        flood = [sched.submit([2, 2, 2], 0.0, 0.9, 2, frozenset(), seed=s,
                              tenant="A") for s in range(2, 6)]
        b = sched.submit([3, 3, 3], 0.0, 0.9, 2, frozenset(), seed=9,
                         tenant="B")
        list(b.tokens())
        for r in flood:
            list(r.tokens())
        list(it)
        finished = sorted(flood + [b], key=lambda r: r.finished_at)
        # B was submitted LAST but must not finish last — the bound: at
        # most one A request (the one charged before B arrived) precedes it
        assert finished.index(b) <= 1, (
            f"tenant B starved behind the flood (position "
            f"{finished.index(b)} of {len(finished)})")
    finally:
        faults.clear()
        sched.shutdown()


@pytest.mark.slow
def test_tenant_weights_skew_service():
    """A 4x-weighted tenant is charged 1/4 the virtual time per request, so
    its backlog drains ahead of an equal flood from a weight-1 tenant."""
    sched = _sched("paged", n_slots=1, chunk=2,
                   tenant_weights={"paid": 4.0, "free": 1.0})
    try:
        _hold_worker(sched)
        faults.install("engine.decode", "delay", ms=10, times=200)
        runner = sched.submit([7, 7, 7], 0.0, 0.9, 8, frozenset(), seed=1)
        it = runner.tokens()
        next(it)
        free = [sched.submit([2, 2, 2], 0.0, 0.9, 2, frozenset(), seed=s,
                             tenant="free") for s in range(2, 5)]
        paid = [sched.submit([3, 3, 3], 0.0, 0.9, 2, frozenset(), seed=s,
                             tenant="paid") for s in range(5, 8)]
        for r in free + paid + [runner]:
            list(r.tokens())
        order = sorted(free + paid, key=lambda r: r.finished_at)
        # all three paid requests finish inside the first four slots: the
        # 4x weight buys ~4 admissions per free admission
        assert sum(1 for r in order[:4] if r.tenant == "paid") >= 3
    finally:
        faults.clear()
        sched.shutdown()


def test_wfq_idle_tenant_banks_no_credit():
    """Start-time fair queueing unit (no engine): a tenant idle while
    another worked gets ONE immediate pick (smallest finish tag), then its
    tag snaps to the virtual clock — its flood alternates with the active
    tenant instead of draining first on banked credit."""
    from dllama_tpu.serve.scheduler import Request

    s = object.__new__(Scheduler)  # policy state only; worker never starts
    s._backlog, s._tenant_vt, s._vt_now = [], {}, 0.0
    s.tenant_weights = {}
    mk = lambda t: Request([1, 2, 3], 0.0, 0.9, 2, frozenset(), tenant=t)
    for _ in range(20):  # tenant A works while B idles
        s._charge_tenant(mk("A"))
    assert s._tenant_vt["A"] == 100.0 and s._vt_now == 95.0
    s._backlog = [mk("B") for _ in range(5)] + [mk("A")]
    picks = []
    for _ in range(5):
        r = s._select_next()
        s._charge_tenant(r)
        picks.append(r.tenant)
    assert picks[0] == "B"  # one immediate pick, bounded
    assert picks[1:].count("A") >= 1 and picks[1:].count("B") >= 1, (
        f"no alternation after the idle return: {picks}")
    # and B's tag really snapped past the clock, not accumulated from 0
    assert s._tenant_vt["B"] >= 95.0


def test_priority_classes_admit_strictly_first():
    sched = _sched("paged", n_slots=1, chunk=2)
    try:
        _hold_worker(sched)
        faults.install("engine.decode", "delay", ms=10, times=120)
        runner = sched.submit([7, 7], 0.0, 0.9, 6, frozenset(), seed=1,
                              priority=2)  # not preemptible by the others
        it = runner.tokens()
        next(it)
        low = sched.submit([2, 2], 0.0, 0.9, 2, frozenset(), seed=2,
                           priority=0)
        norm = sched.submit([3, 3], 0.0, 0.9, 2, frozenset(), seed=3,
                            priority=1)
        high = sched.submit([4, 4], 0.0, 0.9, 2, frozenset(), seed=4,
                            priority=2)
        for r in (low, norm, high, runner):
            list(r.tokens())
        order = sorted((low, norm, high), key=lambda r: r.admitted_at)
        assert [r.priority for r in order] == [2, 1, 0]
    finally:
        faults.clear()
        sched.shutdown()


# ------------------------------------------------------ budget controller


def test_budget_controller_shrinks_and_grows():
    """Pure controller: p95 over the ITL target halves the budget, ample
    headroom doubles it, the band between holds, and no target holds."""
    t = [0.0]
    now = lambda: t[0]
    win = perf.WindowQuantiles(window_s=60.0, now_fn=now)
    ctl = perf.PrefillBudgetController(
        perf.SloPolicy(itl_ms=50.0), lo=16, hi=256, start=64,
        interval_s=0.0, now_fn=now)
    for _ in range(20):
        win.observe(0.100)  # 100 ms >> 50 ms target
    t[0] += 1.0
    assert ctl.update(win) == 32
    t[0] += 1.0
    assert ctl.update(win) == 16
    t[0] += 1.0
    assert ctl.update(win) == 16  # floor
    win2 = perf.WindowQuantiles(window_s=60.0, now_fn=now)
    for _ in range(20):
        win2.observe(0.010)  # 10 ms << 0.6 * 50 ms
    t[0] += 1.0
    assert ctl.update(win2) == 32
    t[0] += 1.0
    assert ctl.update(win2) == 64
    win3 = perf.WindowQuantiles(window_s=60.0, now_fn=now)
    for _ in range(20):
        win3.observe(0.040)  # inside the hold band (0.6..1.0 of target)
    t[0] += 1.0
    assert ctl.update(win3) == 64
    # rate limit: updates inside interval_s hold the current value
    ctl2 = perf.PrefillBudgetController(
        perf.SloPolicy(itl_ms=50.0), start=64, interval_s=10.0, now_fn=now)
    assert ctl2.update(win) == 32  # first evaluation reacts immediately
    t[0] += 0.5
    assert ctl2.update(win) == 32  # rate-limited: no second halving yet
    t[0] += 10.0
    assert ctl2.update(win) == 16
    # no target: auto holds the start value
    ctl3 = perf.PrefillBudgetController(perf.SloPolicy(), start=64,
                                        interval_s=0.0, now_fn=now)
    assert ctl3.update(win) == 64


@pytest.mark.slow
def test_budget_honors_itl_slo_under_long_prompt_flood():
    """Integration: an impossible ITL target + a flood of long prompts
    drives the windowed p95 over target, and the auto budget SHRINKS while
    admissions keep landing — the SLO knob really steers the hybrid step."""
    sched = _sched("paged", n_slots=3, chunk=2, slo_itl_ms=1e-3)
    assert sched._budget_ctl is not None
    sched._budget_ctl.interval_s = 0.0  # every chunk may re-evaluate
    try:
        start = sched._budget_now
        bg = sched.submit([1, 2, 3], 0.0, 0.9, 40, frozenset(), seed=1)
        it = bg.tokens()
        next(it)
        deadline = time.monotonic() + 60
        shrunk = False
        s = 0
        while time.monotonic() < deadline and not shrunk:
            r = sched.submit([(7 * s + k) % 90 + 1 for k in range(20)],
                             0.0, 0.9, 2, frozenset(), seed=100 + s)
            list(r.tokens())  # each finish feeds the ITL window a violation
            s += 1
            shrunk = sched._budget_now < start
        assert shrunk, (f"budget never shrank from {start} despite ITL "
                        "violations")
        assert sched._budget_now >= sched._budget_ctl.lo
    finally:
        sched.shutdown()


# ----------------------------------------------------------- observability


def test_hybrid_ledger_state_and_summary():
    """The hybrid dispatch work is billed to the new exclusive `hybrid`
    ledger state, and latency_summary/health expose the live budget and
    preemption counters."""
    assert "hybrid" in perf.LEDGER_STATES
    sched = _sched("paged")
    try:
        _mixed_workload(sched)
        snap = sched.ledger.snapshot()
        assert snap["seconds"]["hybrid"] > 0.0
        s = sched.latency_summary()["hybrid"]
        assert s["mode"] == "auto" and s["prefill_budget"] >= 1
        h = sched.health()
        assert {"prefill_budget", "preemptions", "resumed",
                "preempted_waiting"} <= set(h)
    finally:
        sched.shutdown()


def test_commit_is_pipelined_behind_the_successor():
    """A joiner's first token is sampled behind the chunk that carried its
    last prompt rows, a successor is dispatched, and only then does the
    commit read the token: the one host read of an admission never finds
    the pipeline empty (the drain that put a second mode into the launch
    intervals, PERF.md section 6, PR 29)."""
    sched = _sched("paged")
    eng, events = sched.engine, []

    def spy(name, key=lambda a: None):
        real = getattr(eng, name)

        def wrapped(*a, **kw):
            events.append((name, key(a)))
            return real(*a, **kw)
        setattr(eng, name, wrapped)

    names = ("add_sample", "add_commit", "hybrid_dispatch", "decode_dispatch")
    try:
        spy("add_sample", lambda a: a[0].slot)
        spy("add_commit", lambda a: a[0].slot)
        spy("hybrid_dispatch")
        spy("decode_dispatch")
        assert sched._pipelined_commit
        _mixed_workload(sched)
    finally:
        for name in names:
            delattr(eng, name)  # the class's own methods again
        sched.shutdown()
    commits = [i for i, (n, _) in enumerate(events) if n == "add_commit"]
    assert len(commits) == 3
    pipelined = 0
    for i in commits:
        slot = events[i][1]
        if events[i + 1:i + 2] == [("add_sample", slot)]:
            continue  # no decoders to protect: the boundary path samples
            # inside its commit
        j = max(k for k in range(i) if events[k] == ("add_sample", slot))
        assert any(n.endswith("_dispatch") for n, _ in events[j + 1:i]), events
        pipelined += 1
    assert pipelined >= 1, events


@pytest.mark.parametrize("temperature,topp", [(0.0, 0.9), (0.8, 0.9)],
                         ids=["greedy", "t0.8-p0.9"])
@pytest.mark.parametrize("seed,admissions", [
    (11, 0), (2**31 + 11, 5), (None, 0), (None, 7)],
    ids=["seed", "seed-past-31-bits", "unseeded-first", "unseeded-eighth"])
def test_first_token_program_is_the_eager_sampler(seed, admissions,
                                                  temperature, topp):
    """`add_sample`'s one program against the arithmetic it folded: the key
    from the request's seed (as `PRNGKey` takes a Python int) or from the
    engine's key and the admission counter, one split, `sample_logits`:
    same token, same carried key."""
    eng = _engine("paged")
    adm = eng.add_begin(0, LONG_PROMPT)
    while not eng.add_step(adm):
        pass
    eng._admissions = admissions
    eng.add_sample(adm, temperature, topp, seed=seed)
    tok, carried = adm.sampled
    assert eng._admissions == admissions + 1
    key = (jax.random.PRNGKey(seed) if seed is not None
           else jax.random.fold_in(eng._base_key, admissions))
    key, sub = jax.random.split(key)
    want = sample_logits(adm.logits, sub, jnp.float32(temperature),
                         jnp.float32(topp))
    assert np.asarray(tok).tolist() == np.asarray(want).tolist()
    assert np.asarray(carried).tolist() == np.asarray(key).tolist()
    # the commit reads that token and decodes on that key
    first = eng.add_commit(adm, temperature, topp, seed)
    assert first == int(want[0]) and eng.keys[0].tolist() == np.asarray(key).tolist()
    eng.release(0)


def test_api_priority_tenant_parsing():
    """Body-field validation: ints 0..2 and low/normal/high names for
    `priority`, bounded strings for `tenant`; malformed values are clean
    ApiError 400s (prevalidate runs these before stream headers)."""
    from dllama_tpu.serve.api import (
        ApiError,
        _parse_priority,
        _parse_tenant,
    )

    assert _parse_priority({}) == 1
    assert _parse_priority({"priority": 0}) == 0
    assert _parse_priority({"priority": "high"}) == 2
    assert _parse_priority({"priority": "low"}) == 0
    # (floats truncate via int(), matching the spec_k parser's convention)
    for bad in (3, -1, "urgent", [1]):
        with pytest.raises(ApiError):
            _parse_priority({"priority": bad})
    assert _parse_tenant({}) == ""
    assert _parse_tenant({"tenant": "acme"}) == "acme"
    for bad in (7, "x" * 65, ["t"]):
        with pytest.raises(ApiError):
            _parse_tenant({"tenant": bad})


def test_prefill_budget_zero_restores_phase_split():
    """--prefill-budget 0: no hybrid chunks at all (the ledger's hybrid
    bucket stays empty) — the A/B baseline the bench record compares."""
    sched = _sched("paged", budget=0)
    try:
        _mixed_workload(sched)
        assert sched.ledger.totals["hybrid"] == 0.0
        assert sched.latency_summary()["hybrid"]["mode"] == "off"
    finally:
        sched.shutdown()
