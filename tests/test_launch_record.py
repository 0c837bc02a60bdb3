"""One launch record per engine launch (ISSUE 26): the program-name table,
the record's arithmetic against a brute-force loop, the dry-pool case
against `page_starved()`, the profiler-clock annotations (a fake hook
standing in for `jax.profiler.TraceAnnotation`), and the capture block."""

import subprocess
import sys
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from dllama_tpu.engine import launch_record
from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.models.config import LlamaConfig
from dllama_tpu.models.llama import random_params
from dllama_tpu.obs import compile as compile_obs
from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import perf, trace
from dllama_tpu.serve.scheduler import Scheduler
from dllama_tpu.utils import profiling

CFG = LlamaConfig(dim=64, hidden_dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                  vocab_size=96, seq_len=64)
PARAMS = random_params(CFG, seed=3, dtype=jnp.float32, quantize=False)
PAGE = 8


@pytest.fixture(scope="module")
def spec_engine():
    """A dense engine with speculation on: it builds every program."""
    return BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32, spec=2)


def _jits(eng) -> dict:
    """{attribute: jitted callable} of everything the engine jitted."""
    return {k: v for k, v in vars(eng).items()
            if callable(v) and hasattr(v, "lower") and hasattr(v, "trace")}


# ------------------------------------------------------- the one name table


def test_table_names_every_jit_the_engine_builds(spec_engine):
    names = {v.__name__ for v in _jits(spec_engine).values()}
    assert names == set(launch_record.PROGRAMS.values())


@pytest.mark.parametrize("fn", sorted(launch_record.PROGRAMS))
def test_program_is_jitted_under_its_table_name(spec_engine, fn):
    progs = [v for v in _jits(spec_engine).values()
             if v.__name__ == launch_record.PROGRAMS[fn]]
    assert progs, f"no jit named {launch_record.PROGRAMS[fn]}"
    assert launch_record.PROGRAMS[fn] == f"dllama_{fn}"


def test_launch_kinds_are_compile_scope_words():
    """A record's kind is the word of the launch's LEDGER.scope."""
    assert set(launch_record.LAUNCH_KINDS) <= set(compile_obs.COMPILE_FNS)
    assert "boundary" in compile_obs.COMPILE_FNS
    assert not set(launch_record.LAUNCH_KINDS) & set(
        launch_record.BOUNDARY_PROGRAMS)


def test_named_jit_refuses_a_program_the_table_lacks():
    with pytest.raises(KeyError):
        launch_record.named_jit("mystery", lambda x: x)


def test_lowered_module_name_is_jit_dllama_fn(spec_engine):
    eng = spec_engine
    text = eng._copy_rows.lower(eng.cache, jnp.int32(0), jnp.int32(1),
                                jnp.int32(4)).as_text()
    assert "module @jit_dllama_copy_rows" in text
    text = eng._hist_write_batch.lower(
        eng.history, jnp.zeros((3, 2), jnp.int32), jnp.zeros(3, jnp.int32),
        jnp.ones(3, bool)).as_text()
    assert "module @jit_dllama_hist_batch" in text


def test_decode_program_module_name(spec_engine):
    eng = spec_engine
    eng._sync_vectors()
    text = eng._decode.lower(
        eng.params, eng.cache, eng._last_dev[:, None], eng._pos_dev,
        eng._active_dev, eng._keys_dev, eng._temps_dev, eng._topp_dev, 2,
        eng.rope_cache, eng._limit_dev).as_text()
    assert "module @jit_dllama_decode " in text
    assert "_unknown" not in text.split("\n", 1)[0]


# ------------------------------------------------ the record's arithmetic


def _brute(n, start_pos, active, limit, seq_len, pool_dry):
    """Step by step, slot by slot: what a launch of n steps does."""
    advanced = starved = empty = kv_rows = 0
    for s in range(len(start_pos)):
        pos = int(start_pos[s])
        for _ in range(n):
            if not active[s]:
                empty += 1
            elif pos < limit[s]:
                kv_rows += pos + 1  # the step attends its own row too
                pos += 1
                advanced += 1
            elif pos < seq_len and pool_dry:
                starved += 1
    return advanced, starved, empty, kv_rows


CASES = {
    "plain": dict(n=4, start=[3, 10, 0], active=[1, 1, 0], limit=[64, 64, 64],
                  dry=False),
    "row_limit_mid_chunk": dict(n=4, start=[62, 10, 5], active=[1, 1, 1],
                                limit=[64, 64, 64], dry=False),
    "dry_pool": dict(n=4, start=[15, 22, 40], active=[1, 1, 1],
                     limit=[16, 24, 48], dry=True),
    "dry_pool_and_context_edge": dict(n=3, start=[63, 31, 7],
                                      active=[1, 1, 0], limit=[64, 32, 8],
                                      dry=True),
    "short_pages_but_pool_not_dry": dict(n=4, start=[15, 2], active=[1, 1],
                                         limit=[16, 64], dry=False),
    "one_step": dict(n=1, start=[0, 63], active=[1, 1], limit=[64, 64],
                     dry=True),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_record_matches_brute_force(name):
    c = CASES[name]
    start = np.array(c["start"], np.int32)
    active = np.array(c["active"], bool)
    limit = np.array(c["limit"], np.int32)
    advance = np.where(active, np.clip(limit - start, 0, c["n"]), 0)
    rec = launch_record.build("decode", 7, c["n"], start, active, advance,
                              seq_len=64, pool_dry=c["dry"])
    assert (rec.advanced, rec.starved, rec.empty, rec.kv_rows) == _brute(
        c["n"], start, active, limit, 64, c["dry"])
    assert rec.active == int(active.sum()) and rec.seq == 7
    if name == "row_limit_mid_chunk":
        assert rec.advanced == 2 + 4 + 4 and rec.starved == 0
    if name == "dry_pool":
        assert rec.starved == 3 + 2 + 0


@pytest.mark.parametrize("window", [0, 5, 24], ids=["global", "w5", "w24"])
@pytest.mark.parametrize("itemsize", [2, 4], ids=["bfloat16", "float32"])
def test_rows_moved_match_a_brute_count_over_a_random_batch(window, itemsize):
    """`kv_rows_moved` (and a windowed layer's) against a walk row by row:
    a step at position p needs rows first .. p (first = 0, or p - W + 1);
    the kernel copies whole pages between the walk's two end pages, those
    by the units of `sub` rows that hold a needed row, and writes back the
    `win`-row tile of row p. Over `kv_rows` it is the new metric's ratio."""
    from dllama_tpu.ops.pallas import paged_attention as pa

    page, nb, n = 64, 8, 4
    win, sub = pa._row_tiles(page, itemsize)
    assert sub < page
    rng = np.random.default_rng(53)
    start = rng.integers(0, page * nb - n, size=16).astype(np.int32)
    active = rng.random(16) < 0.8
    advance = np.where(active, rng.integers(0, n + 1, size=16), 0)

    def moved(p, w):
        first = max(p - w + 1, 0) if w else 0
        units = {r // sub for r in range(first, p + 1)}  # units with a needed row
        for blk in range(first // page + 1, p // page):  # pages between: whole
            units |= {blk * (page // sub) + u for u in range(page // sub)}
        return len(units) * sub + win

    rec = launch_record.build("decode", 1, n, start, active, advance,
                              seq_len=page * nb, pool_dry=False, window=window,
                              paged=(page, nb, win, sub))
    steps = [(int(p) + s) for p, a, on in zip(start, advance, active) if on
             for s in range(int(a))]
    assert rec.kv_rows == sum(p + 1 for p in steps)
    assert rec.kv_rows_moved == sum(moved(p, 0) for p in steps)
    assert rec.kv_rows_moved_window == (
        sum(moved(p, window) for p in steps) if window else 0)
    assert rec.kv_rows < rec.kv_rows_moved <= rec.kv_rows + len(steps) * (
        sub + win)  # the last unit's dead rows and the tile, no page's
    # off the paged kernel's route nothing is counted
    assert launch_record.build("decode", 1, n, start, active, advance,
                               seq_len=page * nb, pool_dry=False
                               ).kv_rows_moved == 0
    if not window:
        before = ins.LAUNCH_KV_ROWS_MOVED.series()
        rec.count()
        assert _delta(ins.LAUNCH_KV_ROWS_MOVED, before) == {
            "decode": rec.kv_rows_moved}
        assert rec.args()["kv_rows_moved"] == rec.kv_rows_moved


def test_spec_record_counts_slots_that_emitted_nothing_as_frozen():
    start = np.array([10, 20, 5], np.int32)
    active = np.array([True, True, False])
    total = np.array([5, 0, 0], np.int32)  # slot 1 emitted nothing
    rec = launch_record.build("spec", 2, 3, start, active, total, seq_len=64,
                              pool_dry=True,
                              frozen=np.where(total == 0, 3, 0))
    assert rec.advanced == 5 and rec.starved == 3 and rec.empty == 3
    assert rec.kv_rows == 5 * 10 + 15


def test_unknown_kind_is_refused():
    z = np.zeros(1, np.int32)
    with pytest.raises(ValueError):
        launch_record.build("copy_rows", 1, 1, z, z.astype(bool), z,
                            seq_len=8, pool_dry=False)


def _delta(fam, before):
    return {k: v - before.get(k, 0.0) for k, v in fam.series().items()
            if v != before.get(k, 0.0)}


def test_count_moves_the_four_counters_once():
    before = {f: f.series() for f in (ins.LAUNCHES, ins.SLOT_STEPS,
                                      ins.LAUNCH_KV_ROWS,
                                      ins.LAUNCH_PREFILL_ROWS)}
    launch_record.LaunchRecord("hybrid", 1, 4, 2, 7, 1, 4, 99, 16).count()
    assert _delta(ins.LAUNCHES, before[ins.LAUNCHES]) == {"hybrid": 1}
    assert _delta(ins.SLOT_STEPS, before[ins.SLOT_STEPS]) == {
        "advanced": 7, "starved": 1, "empty": 4}
    assert _delta(ins.LAUNCH_KV_ROWS, before[ins.LAUNCH_KV_ROWS]) == {
        "hybrid": 99}
    assert _delta(ins.LAUNCH_PREFILL_ROWS,
                  before[ins.LAUNCH_PREFILL_ROWS]) == {"hybrid": 16}


# -------------------------------------------------- on a real engine


def test_dry_pool_launch_is_starved_and_page_starved_agrees():
    """Two slots on a pool too small for both: the launch that crosses a
    page boundary with no free page freezes a slot mid-chunk; the record
    says so, and page_starved() names the same slot afterwards."""
    eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32,
                      kv_layout="paged", page_size=PAGE, kv_pages=3)
    eng.add(0, list(range(1, 7)), temperature=0.0)  # 6 rows: page 1 of 3
    eng.add(1, list(range(1, 10)), temperature=0.0)  # 9 rows: pages 2, 3
    assert eng.pool.free_count == 0
    occupancy = ins.BATCH_OCCUPANCY.labels().count()
    chunk = eng.decode_dispatch(4)
    rec = chunk.launch
    eng.decode_consume(chunk)
    # slot 0 runs rows 6 and 7, then needs a second page and there is none;
    # slot 1 has its second page and runs all four steps
    assert chunk.advance.tolist() == [2, 4]
    assert rec.kind == "decode" and rec.seq == chunk.seq and rec.n == 4
    assert rec.advanced == 6 and rec.starved == 2 and rec.empty == 0
    assert rec.kv_rows == (7 + 8) + (10 + 11 + 12 + 13)
    assert eng.page_starved().tolist() == [True, False]
    # dllama_batch_occupancy is as it was: frozen slots still count there
    assert ins.BATCH_OCCUPANCY.labels().count() == occupancy + 1


def test_a_launch_that_raises_is_not_counted(monkeypatch):
    """The record goes into the counters once the jit call has returned: a
    launch refused by the transfer guard or out of memory was not made."""
    eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    eng.add(0, [1, 2, 3], temperature=0.0)
    before = {f: f.series() for f in (ins.LAUNCHES, ins.SLOT_STEPS)}

    def refuse(*a, **kw):
        raise RuntimeError("RESOURCE_EXHAUSTED")

    monkeypatch.setattr(eng, "_decode", refuse)
    with pytest.raises(RuntimeError):
        eng.decode_dispatch(2)
    assert _delta(ins.LAUNCHES, before[ins.LAUNCHES]) == {}
    assert _delta(ins.SLOT_STEPS, before[ins.SLOT_STEPS]) == {}


def test_spec_launch_is_starved_by_the_pool_it_was_dispatched_under(
        monkeypatch):
    """A spec launch's rows are known only when it is consumed, but whether
    the pool was dry is a fact of its dispatch: the record carries it and
    decode_consume does not look at the pool again."""
    eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32,
                      kv_layout="paged", page_size=PAGE, kv_pages=8, spec=2)
    eng.add(0, [1, 2, 3], temperature=0.0)
    chunk = eng.decode_dispatch(2, spec=True)
    assert chunk.launch.pool_dry is False and eng.pool.free_count > 0

    def moved_on():
        raise AssertionError("the pool was read again at consumption")

    monkeypatch.setattr(eng, "_pool_dry", moved_on)
    chunk.launch.pool_dry = True  # as if dispatched under a dry pool
    eng.decode_consume(chunk)
    rec = chunk.launch
    assert rec.kind == "spec" and rec.seq == chunk.seq and rec.pool_dry
    # slot 0 emitted, so it was not frozen: nothing starved, though dry
    assert rec.advanced > 0 and rec.starved == 0
    assert rec.empty == rec.n  # the second slot held no request


def test_launch_spans_carry_the_record_on_the_launches_track():
    tr = trace.configure(256)
    try:
        eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
        eng.add(0, [1, 2, 3], temperature=0.0)
        chunk = eng.decode_dispatch(3)
        eng.decode_consume(chunk)
        doc = tr.export_chrome()
    finally:
        trace.configure(2048)
    tracks = {e["tid"]: e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    spans = [e for e in doc["traceEvents"] if e.get("name") == "decode.device"]
    assert {tracks[e["tid"]] for e in spans} == {"launches"}
    assert "device" not in tracks.values()
    kinds = [e["args"]["kind"] for e in spans]
    assert kinds == ["prefill_chunk", "prefill_chunk", "decode"]
    dec = spans[-1]["args"]
    assert {"kind", "seq", "n", "active", "starved", "kv_rows",
            "prefill_rows", "chunk", "occupancy"} <= set(dec)
    assert dec["seq"] == chunk.seq and dec["n"] == 3 and dec["active"] == 1
    assert dec["kv_rows"] == 4 + 5 + 6
    assert [e["args"]["prefill_rows"] for e in spans[:2]] == [2, 1]


# ---------------------------------------------- the profiler's clock


class FakeHook:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit."""

    def __init__(self):
        self.log = []  # (what, name, thread id, args)

    def __call__(self, name, **args):
        hook = self

        class _Ann:
            def __enter__(self):
                hook.log.append(("enter", name, threading.get_ident(), args))
                return self

            def __exit__(self, *exc):
                hook.log.append(("exit", name, threading.get_ident(), args))
                return False

        return _Ann()


@pytest.fixture()
def fake_hook(monkeypatch):
    hook = FakeHook()
    monkeypatch.setattr(trace, "PROFILER_HOOK", hook)
    return hook


@pytest.fixture(scope="module")
def hooked_run():
    """One scheduler run under a fake hook: the hook's log and every chunk
    the engine dispatched."""
    hook = FakeHook()
    eng = BatchEngine(CFG, PARAMS, n_slots=3, cache_dtype=jnp.float32)
    chunks = []
    for name in ("decode_dispatch", "hybrid_dispatch"):
        def wrapped(*a, _inner=getattr(eng, name), **kw):
            chunk = _inner(*a, **kw)
            chunks.append(chunk)
            return chunk
        setattr(eng, name, wrapped)
    before = ins.LAUNCHES.series()
    trace.PROFILER_HOOK = hook
    try:
        sched = Scheduler(eng, chunk=3)
        try:
            r1 = sched.submit([1, 2, 3, 4, 5], 0.0, 0.9, 9, frozenset(), seed=1)
            r2 = sched.submit([4, 5], 0.8, 0.9, 7, frozenset(), seed=2)
            assert len(list(r1.tokens())) == 9
            assert len(list(r2.tokens())) == 7
        finally:
            sched.shutdown()
    finally:
        trace.PROFILER_HOOK = None
    return hook.log, chunks, _delta(ins.LAUNCHES, before)


def test_scheduler_states_tile_the_worker_thread(hooked_run):
    log, _chunks, _ = hooked_run
    sched = [e for e in log if e[1].startswith("dllama.sched.")]
    assert len(sched) >= 8
    # one thread, and enter/exit strictly alternate: no overlap
    assert len({e[2] for e in sched}) == 1
    assert [e[0] for e in sched] == ["enter", "exit"] * (len(sched) // 2)
    for a, b in zip(sched[0::2], sched[1::2]):
        assert a[1] == b[1]
    # no hole: a state's exit is followed at once by the next state's
    # enter (both inside one ledger transition), all but the last
    for i, e in enumerate(log):
        if e[0] == "exit" and e[1].startswith("dllama.sched.") \
                and e is not sched[-1]:
            nxt = log[i + 1]
            assert nxt[0] == "enter" and nxt[1].startswith("dllama.sched.")
    states = {e[1].removeprefix("dllama.sched.") for e in sched}
    assert states <= set(perf.LEDGER_STATES)
    assert {"decode_wait", "emit", "prefill"} <= states


def test_one_launch_annotation_per_launch_with_the_chunks_seq(hooked_run):
    log, chunks, launched = hooked_run
    enters = [e for e in log
              if e[0] == "enter" and e[1].startswith("dllama.launch.")]
    assert len(enters) == sum(launched.values())
    by_kind = {}
    for _, name, _, args in enters:
        by_kind.setdefault(name.removeprefix("dllama.launch."), []).append(args)
    assert {k: len(v) for k, v in by_kind.items()} == launched
    assert set(by_kind) <= set(launch_record.LAUNCH_KINDS)
    decodes = [(name.removeprefix("dllama.launch."), a["seq"])
               for _, name, _, a in enters if a["seq"]]
    assert decodes == [(c.launch.kind, c.seq) for c in chunks]
    assert len({c.seq for c in chunks}) == len(chunks) >= 3
    for _, name, _, a in enters:
        assert set(a) == {"seq", "n", "active", "starved", "kv_rows",
                          "prefill_rows"}
    # a launch's annotation sits inside the scheduler state that made it
    for i, e in enumerate(log):
        if e[0] == "enter" and e[1].startswith("dllama.launch."):
            opened = [x for x in log[:i] if x[1].startswith("dllama.sched.")]
            assert opened and opened[-1][0] == "enter"


def test_no_hook_no_annotation_nothing_allocated(monkeypatch):
    """With no capture running the launch path makes no TraceAnnotation:
    the record opens nothing and hands back None."""
    import jax

    def boom(*a, **kw):
        raise AssertionError("TraceAnnotation made with no capture running")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    assert trace.PROFILER_HOOK is None
    rec = launch_record.LaunchRecord("decode", 1, 4, 2, 8, 0, 4, 100, 0)
    assert rec.annotation() is None
    eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
    eng.add(0, [1, 2, 3], temperature=0.0)
    eng.decode_consume(eng.decode_dispatch(2))
    led = perf.TimeLedger()
    led.start("idle")
    led.transition("emit")
    led.close()
    assert led._ann is None


def test_every_ledger_state_is_stamped_under_the_one_prefix(fake_hook):
    """No option names the prefix: any ledger, in any of its states, writes
    `dllama.sched.<state>` while a capture runs, and each annotation is
    closed before the next opens."""
    led = perf.TimeLedger()
    led.start(perf.LEDGER_STATES[0])
    for state in perf.LEDGER_STATES[1:]:
        led.transition(state)
    led.close()
    assert [e[1] for e in fake_hook.log if e[0] == "enter"] == [
        "dllama.sched." + s for s in perf.LEDGER_STATES]
    assert [e[0] for e in fake_hook.log] == ["enter", "exit"] * len(
        perf.LEDGER_STATES)


def test_ledger_closes_its_open_state_after_the_hook_is_gone(fake_hook):
    led = perf.TimeLedger()
    led.start("idle")
    trace.PROFILER_HOOK = None  # the capture ended mid-state
    led.transition("emit")
    assert [(e[0], e[1]) for e in fake_hook.log] == [
        ("enter", "dllama.sched.idle"), ("exit", "dllama.sched.idle")]
    assert led._ann is None


def test_restamp_closes_and_reopens_the_open_state_from_any_thread(fake_hook):
    """The profiler drops an annotation that is open when it stops: a
    capture restamps as it begins and before it stops, from its own
    threads, so the states open at its two ends are in the trace."""
    led = perf.TimeLedger()
    worker = threading.Thread(target=lambda: led.start("commit"))
    worker.start()
    worker.join()
    led.restamp()  # this thread is not the worker
    assert [(e[0], e[1]) for e in fake_hook.log] == [
        ("enter", "dllama.sched.commit"), ("exit", "dllama.sched.commit"),
        ("enter", "dllama.sched.commit")]
    assert fake_hook.log[0][2] != fake_hook.log[1][2]
    assert led.state() == "commit"  # the ledger's own state is untouched
    led.close()
    closed = perf.TimeLedger()
    closed.restamp()  # never started: nothing to stamp
    assert len(fake_hook.log) == 4


def test_a_capture_restamps_at_both_ends(monkeypatch, tmp_path):
    import time

    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    profiling.start_profile(str(tmp_path), 0.05,
                            restamp=lambda: calls.append("restamp"))
    deadline = time.time() + 10
    while profiling.profile_status()["active"] and time.time() < deadline:
        time.sleep(0.01)
    assert calls == ["start", "restamp", "restamp", "stop"]
    assert trace.PROFILER_HOOK is None


def test_trace_buffer_zero_still_records_nothing():
    tr = trace.configure(0)
    try:
        eng = BatchEngine(CFG, PARAMS, n_slots=2, cache_dtype=jnp.float32)
        eng.add(0, [1, 2, 3], temperature=0.0)
        eng.decode_consume(eng.decode_dispatch(2))
        assert tr.export_chrome() == {"traceEvents": []}
        assert tr.stats()["events"] == 0
    finally:
        trace.configure(2048)


def test_obs_imports_no_jax():
    code = ("import sys; import dllama_tpu.obs.trace, dllama_tpu.obs.perf, "
            "dllama_tpu.obs.instruments, dllama_tpu.obs.metrics; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code]).returncode == 0


# --------------------------------------------------- the capture block


def test_capture_block_is_the_counter_deltas(monkeypatch, tmp_path):
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda log_dir, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    launch_record.LaunchRecord("decode", 1, 4, 3, 12, 0, 0, 500, 0).count()
    assert trace.PROFILER_HOOK is None
    profiling._profiler_begin(str(tmp_path))
    try:
        assert trace.PROFILER_HOOK is jax.profiler.TraceAnnotation
        launch_record.LaunchRecord("decode", 2, 4, 3, 10, 2, 0, 640, 0,
                                   sampler="greedy", kv_rows_moved=704).count()
        launch_record.LaunchRecord("hybrid", 3, 4, 2, 8, 0, 4, 300, 16,
                                   sampler="nucleus").count()
        launch_record.LaunchRecord("prefill_chunk", 0, 0, 0, 0, 0, 0, 0,
                                   8).count()
    finally:
        profiling._profiler_end()
    assert trace.PROFILER_HOOK is None
    launch_record.LaunchRecord("decode", 4, 4, 3, 12, 0, 0, 700, 0).count()
    cap = profiling.last_capture()
    pick = lambda d: {k: v for k, v in d.items() if v}
    assert pick(cap["launches"]) == {"decode": 1, "hybrid": 1,
                                     "prefill_chunk": 1}
    # a prefill chunk samples nothing: it moves no sampler series
    assert pick(cap["sampler_launches"]) == {"greedy": 1, "nucleus": 1}
    assert pick(cap["slot_steps"]) == {"advanced": 18, "starved": 2,
                                       "empty": 4}
    assert pick(cap["kv_rows"]) == {"decode": 640, "hybrid": 300}
    assert pick(cap["kv_rows_moved"]) == {"decode": 704}
    assert pick(cap["prefill_rows"]) == {"hybrid": 16, "prefill_chunk": 8}
    assert cap["seconds"] >= 0.0
    assert set(cap) == {"launches", "sampler_launches", "slot_steps",
                        "kv_rows", "kv_rows_moved", "prefill_rows",
                        "kv_rows_read", "moe_assignments",
                        "moe_experts_touched", "moe_layer_steps",
                        "moe_group_rows_max", "window_pages_released",
                        "page_topups", "sched_seconds", "phase_seconds",
                        "phases", "drains", "launch_waits", "host_gap",
                        "seconds"}


def test_failed_session_start_leaves_no_hook(monkeypatch, tmp_path):
    import jax

    def refuse(log_dir, **kw):
        raise RuntimeError("no profiler")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError):
        profiling._profiler_begin(str(tmp_path))
    assert trace.PROFILER_HOOK is None
    assert not profiling.profile_status()["active"]
