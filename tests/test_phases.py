"""The host's launch cycle, measured inside the program (ISSUE 40): the
phase seam (`obs/perf.PhaseClock`) under a fake hook and a fake clock and
through a real scheduler run, a reason for every drained pipeline
(`Scheduler._boundary_reason`, `dllama_pipeline_drains_total`), the wait
that tells whether the host binds (`dllama_launch_waits_total`), the host's
seconds in the capture block, and a capture without the Python tracer."""

import threading
import time
import types

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params
from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import perf, trace
from dllama_tpu.serve.scheduler import Request, Scheduler
from dllama_tpu.utils import profiling

CFG = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                  vocab_size=96, seq_len=64)
PARAMS = random_params(CFG, seed=3, dtype=jnp.float32, quantize=False)


class FakeClock:
    def __init__(self, t=100.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeHook:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit."""

    def __init__(self):
        self.log = []  # (what, name, args, thread id)

    def __call__(self, name, **args):
        hook = self

        class _Ann:
            def __enter__(self):
                hook.log.append(("enter", name, args, threading.get_ident()))
                return self

            def __exit__(self, *exc):
                hook.log.append(("exit", name, args, threading.get_ident()))
                return False

        return _Ann()


@pytest.fixture()
def fake_hook(monkeypatch):
    hook = FakeHook()
    monkeypatch.setattr(trace, "PROFILER_HOOK", hook)
    return hook


def _delta(family, before):
    return {k: v - before.get(k, 0.0) for k, v in family.series().items()
            if v != before.get(k, 0.0)}


# ---------------------------------------------- the seam, clock and hook faked


@pytest.mark.parametrize("name", perf.PHASES)
def test_phase_closes_into_all_three_sinks(name, fake_hook):
    """One way to open a span, three sinks: the counters move by the
    clock's seconds and by one, the profiler's clock gets one
    `dllama.phase.<name>` annotation carrying `seq`, closed, and the ring
    gets the span of the phase's name."""
    clk = FakeClock()
    ph = perf.PhaseClock(now_fn=clk)
    secs, opens = ins.SCHEDULER_PHASE_SECONDS.series(), ins.SCHEDULER_PHASES.series()
    tr = trace.configure(64)
    try:
        with ph(name, 41) as same:
            assert same is ph and ph.current() == name
            clk.advance(0.25)
        assert ph.current() is None and ph.last_s == pytest.approx(0.25)
        spans = [e for e in tr.export_chrome()["traceEvents"]
                 if e.get("ph") == "X"]
    finally:
        trace.configure(2048)
    assert _delta(ins.SCHEDULER_PHASE_SECONDS, secs) == {
        name: pytest.approx(0.25)}
    assert _delta(ins.SCHEDULER_PHASES, opens) == {name: 1.0}
    assert [(e[0], e[1], e[2]) for e in fake_hook.log] == [
        ("enter", "dllama.phase." + name, {"seq": 41}),
        ("exit", "dllama.phase." + name, {"seq": 41})]
    assert [(e["name"], e["args"]["chunk"], e["dur"]) for e in spans] == [
        (name, 41, pytest.approx(250000.0))]
    assert name in trace.SPAN_CATALOG


def test_phases_never_nest_the_inner_one_suspends_the_outer(fake_hook):
    """`emit.finish` inside `emit.scan`: the outer phase's annotation is
    closed before the inner opens and reopened after it, its seconds are
    its own, and it is counted as opened once."""
    clk = FakeClock()
    ph = perf.PhaseClock(now_fn=clk)
    secs, opens = ins.SCHEDULER_PHASE_SECONDS.series(), ins.SCHEDULER_PHASES.series()
    with ph("emit.scan", 7):
        clk.advance(1.0)
        with ph("emit.finish", 7):
            assert ph.current() == "emit.finish"
            clk.advance(0.5)
        assert ph.current() == "emit.scan"
        clk.advance(2.0)
    assert _delta(ins.SCHEDULER_PHASE_SECONDS, secs) == {
        "emit.scan": pytest.approx(3.0), "emit.finish": pytest.approx(0.5)}
    assert _delta(ins.SCHEDULER_PHASES, opens) == {"emit.scan": 1.0,
                                                   "emit.finish": 1.0}
    short = [(e[0], e[1].removeprefix("dllama.phase.")) for e in fake_hook.log]
    assert short == [("enter", "emit.scan"), ("exit", "emit.scan"),
                     ("enter", "emit.finish"), ("exit", "emit.finish"),
                     ("enter", "emit.scan"), ("exit", "emit.scan")]
    assert ph.last_s == pytest.approx(2.0)  # the outer phase's last run


def test_phase_closes_when_its_body_raises(fake_hook):
    ph = perf.PhaseClock(now_fn=FakeClock())
    with pytest.raises(RuntimeError):
        with ph("dispatch.build", 3):
            raise RuntimeError("no active slots")
    assert ph.current() is None
    assert [e[0] for e in fake_hook.log] == ["enter", "exit"]


def test_unknown_phase_is_refused():
    with pytest.raises(ValueError, match="unknown phase"):
        perf.PhaseClock()("emit.mystery")


def test_a_drained_pipelines_phases_carry_the_reason(fake_hook):
    ph = perf.PhaseClock(now_fn=FakeClock())
    ph.drain = "arrival"
    with ph("emit.scan", 5):
        pass
    ph.drain = None
    with ph("dispatch.plan", 6):
        pass
    assert [e[2] for e in fake_hook.log if e[0] == "enter"] == [
        {"seq": 5, "drain": "arrival"}, {"seq": 6}]


def test_a_launchs_call_is_its_launch_annotation(fake_hook):
    """`dispatch.call` given the launch record: the phase counter is fed
    from the stretch of the `dllama.launch.<kind>` annotation, which keeps
    its name and arguments."""
    from dllama_tpu.engine import launch_record

    clk = FakeClock()
    ph = perf.PhaseClock(now_fn=clk)
    rec = launch_record.LaunchRecord("hybrid", 9, 4, 2, 8, 0, 4, 100, 16)
    secs = ins.SCHEDULER_PHASE_SECONDS.series()
    with ph("dispatch.call", rec.seq, rec):
        clk.advance(0.125)
    assert _delta(ins.SCHEDULER_PHASE_SECONDS, secs) == {
        "dispatch.call": pytest.approx(0.125)}
    assert [(e[0], e[1]) for e in fake_hook.log] == [
        ("enter", "dllama.launch.hybrid"), ("exit", "dllama.launch.hybrid")]
    assert fake_hook.log[0][2] == {"seq": 9, "n": 4, "active": 2,
                                   "starved": 0, "kv_rows": 100,
                                   "prefill_rows": 16}


def test_restamp_bills_and_reopens_the_open_phase_from_any_thread(fake_hook):
    clk = FakeClock()
    led = perf.TimeLedger(now_fn=clk)
    led.start("emit")
    ph = perf.PhaseClock(led, now_fn=clk)
    secs = ins.SCHEDULER_PHASE_SECONDS.series()
    with ph("consume.wait", 2):
        clk.advance(0.75)
        t = threading.Thread(target=ph.restamp)  # the capture's timer thread
        t.start()
        t.join()
        assert _delta(ins.SCHEDULER_PHASE_SECONDS, secs) == {
            "consume.wait": pytest.approx(0.75)}
        clk.advance(0.25)
    assert _delta(ins.SCHEDULER_PHASE_SECONDS, secs) == {
        "consume.wait": pytest.approx(1.0)}
    waits = [e for e in fake_hook.log if e[1] == "dllama.phase.consume.wait"]
    assert [e[0] for e in waits] == ["enter", "exit", "enter", "exit"]
    assert waits[1][3] != waits[0][3]  # closed on the other thread
    assert ph.snapshot() == {"emit": {"consume.wait": pytest.approx(1.0)}}
    perf.PhaseClock().restamp()  # nothing open: nothing to stamp


def test_restamp_races_the_worker_without_losing_a_second_or_a_span(fake_hook):
    """The one piece of state two threads share: a capture's timer thread
    restamps while the worker opens and closes phases. Bounded stress, the
    interpreter switching threads every few bytecodes: every annotation
    that was opened is closed before the next opens (no overlap, none
    lost), nothing stays open, and the seconds billed are the worker's."""
    import sys

    ph = perf.PhaseClock()
    secs = ins.SCHEDULER_PHASE_SECONDS.series()
    stop = threading.Event()

    def restamper():
        while not stop.is_set():
            ph.restamp()

    threads = [threading.Thread(target=restamper) for _ in range(3)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t0 = time.monotonic()
    try:
        for t in threads:
            t.start()
        while time.monotonic() - t0 < 0.5:
            with ph("emit.scan", 1):
                with ph("emit.finish", 1):
                    pass
            with ph("dispatch.plan", 2):
                pass
        elapsed = time.monotonic() - t0
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert ph.current() is None and ph._ann is None
    assert [e[0] for e in fake_hook.log] == ["enter", "exit"] * (
        len(fake_hook.log) // 2)
    for a, b in zip(fake_hook.log[0::2], fake_hook.log[1::2]):
        assert a[1] == b[1]
    billed = sum(_delta(ins.SCHEDULER_PHASE_SECONDS, secs).values())
    assert 0.5 * elapsed < billed <= elapsed


def test_ledger_restamp_bills_the_open_state():
    clk = FakeClock()
    led = perf.TimeLedger(counter=ins.SCHEDULER_TIME, now_fn=clk)
    led.start("decode_wait")
    before = ins.SCHEDULER_TIME.series()
    clk.advance(0.4)
    led.restamp()
    assert _delta(ins.SCHEDULER_TIME, before) == {
        "decode_wait": pytest.approx(0.4)}
    led.close()


def test_no_capture_and_ring_off_the_seam_builds_nothing(monkeypatch):
    """Tracing off: no annotation object, no ring span, no per-call
    object of the seam's own (it hands back itself)."""
    import jax

    def boom(*a, **kw):
        raise AssertionError("built with no capture running / ring off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert trace.PROFILER_HOOK is None
    tr = trace.configure(0)
    monkeypatch.setattr(type(tr), "span_at", boom)
    try:
        ph = perf.PhaseClock()
        monkeypatch.setattr(ph, "_annotation_args", boom)
        for name in perf.PHASES:
            with ph(name, 1) as a, ph("emit.finish", 1) as b:
                assert a is ph and b is ph
        assert ph._ann is None and ph.current() is None
        assert trace.profiler_annotation("dllama.sched.", "emit", boom) is None
    finally:
        trace.configure(2048)


def test_every_phase_has_its_series_from_the_first_scrape():
    text = "\n".join(f.name + str(sorted(f.series())) for f in (
        ins.SCHEDULER_PHASE_SECONDS, ins.SCHEDULER_PHASES,
        ins.PIPELINE_DRAINS, ins.LAUNCH_WAITS))
    for word in perf.PHASES + perf.DRAIN_REASONS + ("ready", "blocked"):
        assert f"'{word}'" in text, word


# ------------------------------------------------- a real scheduler run


@pytest.fixture(scope="module")
def real_run():
    """A tiny model served through hybrid admission, a phase-split pump
    (the first request finds no decoders) and a finish inside the emit
    loop, under a fake hook; every ledger transition records the phase
    that was open."""
    hook = FakeHook()
    eng = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=3)
    open_at_transition = []
    inner = sched.ledger.transition

    def transition(state):
        open_at_transition.append((state, sched.phases.current()))
        inner(state)

    sched.ledger.transition = transition
    before = {f.name: f.series() for f in (
        ins.SCHEDULER_PHASE_SECONDS, ins.SCHEDULER_PHASES, ins.LAUNCHES,
        ins.LAUNCH_WAITS, ins.SCHEDULER_TIME)}
    trace.PROFILER_HOOK = hook
    try:
        try:
            r1 = sched.submit([1, 2, 3, 4, 5], 0.0, 0.9, 12, frozenset(), seed=1)
            first = next(iter(r1.tokens()))
            r2 = sched.submit([4, 5], 0.8, 0.9, 7, frozenset(), seed=2)
            r3 = sched.submit([9, 8, 7], 0.0, 0.9, 5, frozenset(), seed=3)
            assert len([first] + list(r1.tokens())) == 12
            assert len(list(r2.tokens())) == 7
            assert len(list(r3.tokens())) == 5
        finally:
            sched.shutdown()
    finally:
        trace.PROFILER_HOOK = None
    moved = {name: {k: v - b.get(k, 0.0) for k, v in fam.series().items()}
             for fam in (ins.SCHEDULER_PHASE_SECONDS, ins.SCHEDULER_PHASES,
                         ins.LAUNCHES, ins.LAUNCH_WAITS, ins.SCHEDULER_TIME)
             for name, b in [(fam.name, before[fam.name])]}
    return types.SimpleNamespace(sched=sched, log=hook.log, moved=moved,
                                 open_at_transition=open_at_transition)


def test_every_phase_lies_inside_one_state(real_run):
    """No phase is open at any ledger transition of a real run."""
    assert len(real_run.open_at_transition) > 20
    assert {p for _, p in real_run.open_at_transition} == {None}
    assert real_run.sched.phases.current() is None  # every phase closed


def test_a_states_phases_never_exceed_the_state(real_run):
    """Beside the ledger's partition invariant: under every state the
    phases' seconds sum to no more than the state's (what is left is the
    state's self time), and every phase was billed under a state it may
    run in."""
    led = real_run.sched.ledger.snapshot()["seconds"]
    by_state = real_run.sched.phases.snapshot()
    assert set(by_state) <= set(perf.LEDGER_STATES)
    for state, phases in by_state.items():
        assert sum(phases.values()) <= led[state] + 1e-4, (state, phases, led)
    # the top of the loop's pipelined branch runs in whatever state the
    # last iteration ended in: the first-token sampling dispatched ahead,
    # the boundary decision, and a finish wherever a request ends
    anywhere = {"commit.sample", "boundary.scan", "emit.finish"}
    dispatch = {"dispatch.plan", "dispatch.build", "dispatch.call",
                "dispatch.after"}
    allowed = {
        "decode_dispatch": dispatch, "hybrid": dispatch,
        "decode_wait": {"consume.wait", "consume.fold"},
        "prefill": {"admit.pump", "dispatch.call"},
        "commit": {"commit.activate"}, "emit": {"emit.scan"},
        "admission": {"admit.start"},
    }
    for state, phases in by_state.items():
        assert set(phases) <= allowed[state] | anywhere, (state, sorted(phases))
    ran = {p for phases in by_state.values() for p in phases}
    assert ran >= {"dispatch.plan", "dispatch.build", "dispatch.call",
                   "dispatch.after", "consume.wait", "consume.fold",
                   "emit.scan", "emit.finish", "commit.sample",
                   "commit.activate", "admit.start", "admit.pump",
                   "boundary.scan"}
    # the counter family holds the same seconds as the clock's own table
    for name, secs in real_run.moved[ins.SCHEDULER_PHASE_SECONDS.name].items():
        assert secs == pytest.approx(
            sum(ph.get(name, 0.0) for ph in by_state.values()), abs=1e-4)


def test_phase_annotations_tile_without_overlap_and_carry_seq(real_run):
    phases = [e for e in real_run.log if e[1].startswith(("dllama.phase.",
                                                          "dllama.launch."))]
    assert len({e[3] for e in phases}) == 1  # the worker's own thread
    assert [e[0] for e in phases] == ["enter", "exit"] * (len(phases) // 2)
    for a, b in zip(phases[0::2], phases[1::2]):
        assert a[1] == b[1]
    seqs = [e[2]["seq"] for e in phases if e[0] == "enter"]
    assert all(isinstance(s, int) and s >= 0 for s in seqs)
    # a dispatch's three phases work for one launch, and the launches' seqs
    # never go back
    calls = [e[2]["seq"] for e in phases
             if e[0] == "enter" and e[1].startswith("dllama.launch.")
             and e[2]["seq"]]
    assert calls == sorted(calls) and len(set(calls)) == len(calls) >= 3
    i = 0
    while i < len(phases):
        if phases[i][0] == "enter" and phases[i][1] == "dllama.phase.dispatch.build":
            build, call, after = phases[i], phases[i + 2], phases[i + 4]
            assert call[1].startswith("dllama.launch.")
            assert after[1] == "dllama.phase.dispatch.after"
            assert build[2]["seq"] == call[2]["seq"] == after[2]["seq"]
        i += 1
    # a phase's annotation sits inside an open scheduler state
    for i, e in enumerate(real_run.log):
        if e[0] == "enter" and e[1].startswith("dllama.phase."):
            states = [x for x in real_run.log[:i]
                      if x[1].startswith("dllama.sched.")]
            assert states and states[-1][0] == "enter"


def test_one_wait_outcome_per_consumed_launch(real_run):
    launched = real_run.moved[ins.LAUNCHES.name]
    consumed = sum(v for k, v in launched.items() if k != "prefill_chunk")
    waits = real_run.moved[ins.LAUNCH_WAITS.name]
    assert set(waits) == {"ready", "blocked"}
    assert sum(waits.values()) == consumed >= 3
    opens = real_run.moved[ins.SCHEDULER_PHASES.name]
    assert opens["consume.wait"] == opens["consume.fold"] == consumed
    assert opens["dispatch.call"] == sum(launched.values())


def test_host_gap_summary_reads_the_histogram():
    """`latency_summary` derives its host-gap fields from the one record,
    the histogram, since the scheduler's own start (or reset)."""
    eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=2, overlap=False)
    try:
        before = ins.DECODE_HOST_GAP_SECONDS.series()
        req = sched.submit([1, 2, 3], 0.0, 0.9, 9, frozenset(), seed=0)
        assert len(list(req.tokens())) == 9
        s = sched.latency_summary()
        after = ins.DECODE_HOST_GAP_SECONDS.series()
        assert s["decode_host_gaps"] == after["count"] - before["count"] >= 3
        assert s["decode_host_gap_ms_mean"] == pytest.approx(
            1e3 * (after["sum"] - before["sum"]) / s["decode_host_gaps"])
        assert "decode_host_gap_ms_max" not in s
        sched.reset_latency_stats()
        s = sched.latency_summary()
        assert s["decode_host_gaps"] == 0
        assert s["decode_host_gap_ms_mean"] is None
    finally:
        sched.shutdown()


# ------------------------------------------- a reason for every drain


def _idle_sched(**kw):
    """A scheduler whose worker never runs: `_boundary_reason` is asked on
    hand-built state."""
    engine = dict(kw.pop("engine", {}))
    eng = BatchEngine(*engine.pop("model", (CFG, PARAMS)), n_slots=3,
                      cache_dtype=jnp.float32, **engine)
    sched = Scheduler(eng, chunk=2, **kw)
    sched.shutdown()  # the worker is gone; the state is ours
    sched._stop.clear()
    return sched


def _req(sched, max_tokens=50):
    return Request([1, 2, 3], 0.0, 0.9, max_tokens, frozenset(),
                   submitted_at=time.monotonic())


def _adm(slot=1, off=0, n=4):
    return types.SimpleNamespace(slot=slot, off=off, toks=np.zeros(n, np.int32),
                                 sampled=None)


def _decoding(sched):
    sched.slots.setdefault(0, _req(sched))
    sched.engine.active[0] = True  # a decoding slot is the engine's too
    return sched


def _stop(s):
    _decoding(s)._stop.set()


def _empty(s):
    assert not s.slots


def _deferred(s):
    _decoding(s)._deferred = _req(s)


def _recover(s):
    _decoding(s)._recover = [_req(s)]


def _backlog(s):
    _decoding(s)._backlog = [_req(s)]


def _pump_without_hybrid(s):
    _decoding(s)._hybrid_on = False
    s._inflight.append((_req(s), _adm(), 0))


def _arrival(s):
    _decoding(s).pending.put(_req(s))


def _commit(s):
    _decoding(s)._pipelined_commit = False
    s._inflight.append((_req(s), _adm(off=4), 0))


def _cancel_of_the_head(s):
    req = _req(s)
    req.cancelled.set()
    _decoding(s)._inflight.append((req, _adm(), 0))


def _cancel_of_a_stream(s):
    _decoding(s).slots[0].cancelled.set()


def _deadline_of_the_head(s):
    req = _req(s)
    req.deadline_at = time.monotonic() - 1.0
    _decoding(s)._inflight.append((req, _adm(), 0))


def _deadline_of_a_stream(s):
    _decoding(s).slots[0].deadline_at = time.monotonic() - 1.0


def _ttft_override(s):
    _decoding(s).admit_ttft_deadline_ms = 0.0
    s._inflight.append((_req(s), _adm(), 0))


def _row_limit(s):
    _decoding(s).engine.pos[0] = s.engine.seq_len


# a pool of four 8-row pages; and a model whose second layer sees 16 rows,
# so the engine keeps a window pool beside the global one
PAGED = dict(kv_layout="paged", page_size=8, kv_pages=4)
WCFG = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                   vocab_size=96, seq_len=64, window=16, layer_windows=(0, 1))
WINDOWED = dict(PAGED, kv_pages=0, model=(
    WCFG, random_params(WCFG, seed=3, dtype=jnp.float32, quantize=False)))


def _on_a_page_edge(s, rows=8):
    """Slot 0 decodes and its next row is the first of a page it has not
    got: what `pos >= limit` alone used to call a boundary."""
    eng = _decoding(s).engine
    for pool in (eng.pool, eng.wpool):
        if pool is not None:
            assert pool.grow(0, rows)
    eng.pos[0] = rows
    assert eng.pos[0] == eng._row_limit()[0] < eng.seq_len
    return eng


def _page_edge_with_a_dry_pool(s):
    eng = _on_a_page_edge(s)
    eng.pool.grow(1, 3 * 8)  # another slot holds the rest, the tree nothing
    assert eng.pool.free_count == 0


def _page_edge_with_a_free_page(s):
    eng = _on_a_page_edge(s)
    assert eng.pool.free_count == 3


def _page_edge_with_an_evictable_radix_leaf(s):
    eng = _on_a_page_edge(s)
    eng.pool.grow(1, 3 * 8)
    assert eng.radix_insert(1, list(range(1, 25))) == 3
    eng._free_tail(1, 0)  # the request went, its pages stay in the tree
    assert eng.pool.free_count == 0


def _page_edge_on_a_window_pool(s):
    eng = _on_a_page_edge(s)
    assert eng.wpool is not None and eng.wpool.free_count > 0


for _build in (_page_edge_with_a_dry_pool, _page_edge_with_a_free_page,
               _page_edge_with_an_evictable_radix_leaf):
    _build.engine = PAGED
_page_edge_on_a_window_pool.engine = WINDOWED


def _budget_ends_with_the_chunk_in_flight(s):
    _decoding(s).slots[0].produced = 48
    return types.SimpleNamespace(spec=False, n=2,
                                 advance=np.array([2, 0, 0], np.int32))


def _nothing(s):
    _decoding(s)
    return types.SimpleNamespace(spec=False, n=2,
                                 advance=np.array([2, 0, 0], np.int32))


def _pumped_head_under_the_pipelined_commit(s):
    _decoding(s)
    assert s._pipelined_commit
    s._inflight.append((_req(s), _adm(off=4), 0))
    return _nothing(s)


BOUNDARY_CASES = [
    ("stop", _stop), ("empty", _empty), ("backlog", _deferred),
    ("recover", _recover), ("backlog", _backlog),
    ("backlog", _pump_without_hybrid), ("arrival", _arrival),
    ("commit", _commit), ("cancel", _cancel_of_the_head),
    ("cancel", _cancel_of_a_stream), ("deadline", _deadline_of_the_head),
    ("deadline", _deadline_of_a_stream), ("deadline", _ttft_override),
    ("row_limit", _row_limit),
    ("empty", _budget_ends_with_the_chunk_in_flight),
    (None, _nothing), (None, _pumped_head_under_the_pipelined_commit),
    # the row_limit clause asks what the boundary is for (ISSUE 41): the
    # context edge above and a dry pool have work for it, a page to be had
    # has none
    ("row_limit", _page_edge_with_a_dry_pool),
    (None, _page_edge_with_a_free_page),
    (None, _page_edge_with_an_evictable_radix_leaf),
    (None, _page_edge_on_a_window_pool),
]


@pytest.mark.parametrize("reason,build", BOUNDARY_CASES,
                         ids=[f"{r}-{b.__name__.strip('_')}"
                              for r, b in BOUNDARY_CASES])
def test_boundary_reason_is_the_first_clause_that_asks(reason, build):
    sched = _idle_sched(engine=getattr(build, "engine", {}))
    inflight = build(sched)
    assert sched._boundary_reason(inflight) == reason
    assert reason is None or reason in perf.DRAIN_REASONS
    if hasattr(build, "engine"):  # the page-edge cases, on a paged engine
        eng = sched.engine
        # the slot got its page exactly where one was to be had, and the
        # pool's books balance after the top-up
        assert (eng.pos[0] < eng._row_limit()[0]) == (reason is None)
        assert eng.pool.audit()["ok"]


def test_boundary_reason_keeps_the_clauses_order():
    """With several clauses true the first one names the drain."""
    sched = _idle_sched()
    _arrival(sched)
    _cancel_of_a_stream(sched)
    _row_limit(sched)
    assert sched._boundary_reason(None) == "arrival"
    sched.pending.get_nowait()
    assert sched._boundary_reason(None) == "cancel"
    sched.slots[0].cancelled.clear()
    assert sched._boundary_reason(None) == "row_limit"


def test_every_drain_reason_has_a_case():
    covered = {r for r, _ in BOUNDARY_CASES if r} | {"mode_switch"}
    assert covered == set(perf.DRAIN_REASONS)


def _scripted_drains(sched, submit):
    """Run `submit` against a scheduler whose boundary decisions and mode
    switches are recorded: ({reason: drains seen}, counter deltas)."""
    seen: dict = {}
    ask, dispatch = sched._boundary_reason, sched._dispatch_chunk

    def boundary_reason(inflight_chunk=None):
        reason = ask(inflight_chunk)
        if reason is not None:
            seen[reason] = seen.get(reason, 0) + 1
        return reason

    def dispatch_chunk(*a, **kw):
        out = dispatch(*a, **kw)
        if out is None:
            seen["mode_switch"] = seen.get("mode_switch", 0) + 1
        return out

    sched._boundary_reason, sched._dispatch_chunk = boundary_reason, dispatch_chunk
    before = ins.PIPELINE_DRAINS.series()
    try:
        submit(sched)
    finally:
        sched.shutdown()
    return seen, _delta(ins.PIPELINE_DRAINS, before)


def test_drains_counter_moves_by_exactly_the_drains_of_a_run():
    """A joiner with the hybrid step off (its prefill is pumped at
    boundaries), then streams that end at different times: every launch
    the loop consumed without a successor is counted once, under the
    reason `_boundary_reason` gave."""
    eng = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=2, prefill_budget=0)

    def submit(s):
        r1 = s.submit([1, 2, 3], 0.0, 0.9, 14, frozenset(), seed=1)
        first = next(iter(r1.tokens()))
        r2 = s.submit([4, 5, 6, 7], 0.0, 0.9, 6, frozenset(), seed=2)
        assert len([first] + list(r1.tokens())) == 14
        assert len(list(r2.tokens())) == 6

    seen, counted = _scripted_drains(sched, submit)
    seen.pop("stop", None)  # asked after the run, by the shutdown
    counted.pop("stop", None)
    assert counted == {k: float(v) for k, v in seen.items()}
    assert "empty" in counted  # the last stream's budget ended the batch
    assert set(counted) & {"arrival", "backlog"}  # the joiner's admission


def test_page_edges_with_pages_to_spare_never_drain_the_pipeline():
    """Three streams on 8-row pages, two steps a launch, prompts of even
    length: every stream's position lands EXACTLY on the edge of its pages
    every fourth launch, three edges a stream. The slot's next page is
    taken with the launch in flight (`dllama_kv_page_topups_total`,
    pipeline="full") and no launch drains under `row_limit`; until ISSUE 41
    every such edge drained one."""
    eng = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32,
                      kv_layout="paged", page_size=8)
    sched = Scheduler(eng, chunk=2)
    assert eng.pool.n_pages == 3 * 8  # pages to spare: never dry
    topups = ins.KV_PAGE_TOPUPS.series()
    edges = []
    ask = eng.row_limited

    def row_limited():
        on_edge = eng.active & (eng.pos >= eng._row_limit())
        edges.extend(int(p) for p in eng.pos[on_edge])
        return ask()

    eng.row_limited = row_limited

    def submit(s):
        r1 = s.submit([1, 2, 3, 4], 0.0, 0.9, 27, frozenset(), seed=1)
        first = next(iter(r1.tokens()))
        r2 = s.submit([4, 5, 6, 7, 8, 9], 0.0, 0.9, 27, frozenset(), seed=2)
        r3 = s.submit([7, 8], 0.8, 0.9, 27, frozenset(), seed=3)
        assert len([first] + list(r1.tokens())) == 27
        assert len(list(r2.tokens())) == 27
        assert len(list(r3.tokens())) == 27

    try:
        seen, counted = _scripted_drains(sched, submit)
    finally:
        del eng.row_limited
    assert "row_limit" not in seen and not counted.get("row_limit")
    moved = _delta(ins.KV_PAGE_TOPUPS, topups)
    # the loop met a slot on the edge of its pages, each stream twice or
    # more, and every one of those pages was taken under a launch in flight
    assert len(edges) >= 6 and set(edges) >= {8, 16, 24}
    assert moved["full"] >= len(edges)
    assert eng.pool.audit()["ok"]


def test_a_mode_switch_is_a_drain_of_its_own():
    """Speculation on: a spec chunk in flight and a plain (hybrid) chunk
    to dispatch, or the other way round, consumes the chunk in flight
    first; `_dispatch_chunk` bails and the loop counts the drain."""
    eng = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32, spec=2)
    sched = Scheduler(eng, chunk=2)

    def submit(s):
        r1 = s.submit([1, 2, 3, 1, 2, 3, 1, 2], 0.0, 0.9, 20, frozenset(),
                      seed=1)
        first = next(iter(r1.tokens()))  # spec chunks are in flight
        # a joiner rides a hybrid (plain) chunk, and outlives the stream
        # that speculates without speculating itself
        r2 = s.submit([5, 6, 7], 0.0, 0.9, 40, frozenset(), seed=2, spec_k=0)
        assert len([first] + list(r1.tokens())) == 20
        assert len(list(r2.tokens())) == 40

    seen, counted = _scripted_drains(sched, submit)
    seen.pop("stop", None)
    counted.pop("stop", None)
    assert counted == {k: float(v) for k, v in seen.items()}
    assert counted["mode_switch"] >= 1


def test_the_loop_hands_the_reason_to_the_phases_until_the_next_launch():
    eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    sched = Scheduler(eng, chunk=2)
    carried = []
    consume = sched._consume_chunk

    def consume_chunk(*a):
        carried.append(sched.phases.drain)
        return consume(*a)

    sched._consume_chunk = consume_chunk
    try:
        req = sched.submit([1, 2, 3], 0.0, 0.9, 9, frozenset(), seed=1)
        assert len(list(req.tokens())) == 9
    finally:
        sched.shutdown()
    assert carried[-1] == "empty" and None in carried
    assert sched.phases.drain in (None, "empty")


# ------------------------------------------------------ the capture


def test_profiler_starts_without_the_python_tracer(monkeypatch, tmp_path):
    import jax

    seen = {}

    def start_trace(log_dir, **kw):
        seen.update(kw, log_dir=log_dir)

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    profiling._profiler_begin(str(tmp_path))
    try:
        assert trace.PROFILER_HOOK is jax.profiler.TraceAnnotation
    finally:
        profiling._profiler_end()
    options = seen["profiler_options"]
    assert isinstance(options, jax.profiler.ProfileOptions)
    assert options.python_tracer_level == 0
    # the host tracer is as jax ships it: the dllama.* annotations are its
    assert (options.host_tracer_level
            == jax.profiler.ProfileOptions().host_tracer_level >= 1)
    assert seen["log_dir"] == str(tmp_path)


def test_capture_block_brackets_the_hosts_seconds(monkeypatch, tmp_path):
    """States, phases, drains, waits and the host gap between the
    profiler's begin and end, the open state and phase billed at both
    ends by the restamp."""
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    clk = FakeClock()
    led = perf.TimeLedger(counter=ins.SCHEDULER_TIME, now_fn=clk)
    ph = perf.PhaseClock(led, now_fn=clk)

    def restamp():
        led.restamp()
        ph.restamp()

    led.start("emit")
    with ph("emit.scan", 1):
        clk.advance(5.0)  # before the capture: not in the block
        profiling._profiler_begin(str(tmp_path), restamp=restamp)
        try:
            clk.advance(0.5)
        finally:
            pass
    led.transition("decode_wait")
    with ph("consume.wait", 2):
        clk.advance(1.5)
        ins.PIPELINE_DRAINS.labels(reason="arrival").inc()
        ins.LAUNCH_WAITS.labels(outcome="blocked").inc()
        ins.DECODE_HOST_GAP_SECONDS.observe(0.004)
        restamp()
        profiling._profiler_end()
        clk.advance(7.0)  # after it
    led.close()
    cap = profiling.last_capture()
    pick = lambda d: {k: pytest.approx(v) for k, v in d.items() if v}
    assert pick(cap["sched_seconds"]) == {"emit": 0.5, "decode_wait": 1.5}
    assert pick(cap["phase_seconds"]) == {"emit.scan": 0.5, "consume.wait": 1.5}
    assert pick(cap["phases"]) == {"consume.wait": 1}
    assert pick(cap["drains"]) == {"arrival": 1}
    assert pick(cap["launch_waits"]) == {"blocked": 1}
    assert cap["host_gap"] == {"sum": pytest.approx(0.004), "count": 1.0}
