"""CPU rehearsal of `reducers/trace_idle_by_phase.py` (PR 40): the device's
idle stretches laid over the phases of the host's work, on planes built in
memory (idle under two phases, an idle stretch no phase covers, a capture
whose first phase began before the profiler, a drained pipeline), its host
tables on hand-built scrapes, its None paths, and one real (CPU) capture
read back with its arguments."""

import json
import os

import pytest

from benchmark.reducers import ratio_of_deltas, trace_idle_by_phase

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
MS = 1_000_000


def planes(phases=True, recorded=(0, 400 * MS)):
    """A device that runs 0-100, 110-200, 230-300, 304-400 ms. The worker's
    first stamped phase is the consume.wait that began at 50 ms (the one
    open at 0-50 ms began before the profiler and is not in the capture);
    launch 2 is dispatched pipelined, launch 3 into a drained pipeline
    (reason `arrival`) after a boundary; 214-222 ms are `admission`'s self
    time, under no phase."""
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ("%a = x", 0, 100 * MS, {}), ("%b = x", 110 * MS, 90 * MS, {}),
            ("%c = x", 230 * MS, 70 * MS, {}), ("%d = x", 304 * MS, 96 * MS, {})]}]}
    a = {"seq": 3, "drain": "arrival"}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ("dllama.sched.decode_wait", 50 * MS, 52 * MS, {}),
            ("dllama.phase.consume.wait", 50 * MS, 51 * MS, {"seq": 1}),
            ("dllama.phase.consume.fold", 101 * MS, 1 * MS, {"seq": 1}),
            ("dllama.sched.emit", 102 * MS, 4 * MS, {}),
            ("dllama.phase.emit.scan", 102 * MS, 3 * MS, {"seq": 1}),
            ("dllama.sched.decode_dispatch", 106 * MS, 6 * MS, {}),
            ("dllama.phase.dispatch.build", 106 * MS, 1 * MS, {"seq": 2}),
            ("dllama.launch.decode", 107 * MS, 4 * MS, {"seq": 2}),
            ("dllama.phase.dispatch.after", 111 * MS, 1 * MS, {"seq": 2}),
            ("dllama.sched.decode_wait", 112 * MS, 90 * MS, {}),
            ("dllama.phase.consume.wait", 112 * MS, 89 * MS,
             {"seq": 2, "drain": "arrival"}),
            ("dllama.sched.emit", 202 * MS, 4 * MS, {}),
            ("dllama.phase.emit.scan", 202 * MS, 2 * MS,
             {"seq": 2, "drain": "arrival"}),
            ("dllama.phase.emit.finish", 204 * MS, 2 * MS,
             {"seq": 2, "drain": "arrival"}),
            ("dllama.sched.admission", 206 * MS, 16 * MS, {}),
            ("dllama.phase.admit.start", 206 * MS, 8 * MS, a),
            ("dllama.sched.hybrid", 222 * MS, 10 * MS, {}),
            ("dllama.phase.dispatch.build", 222 * MS, 1 * MS, a),
            ("dllama.launch.hybrid", 223 * MS, 8 * MS, {"seq": 3}),
            ("dllama.sched.decode_wait", 232 * MS, 168 * MS, {}),
            ("dllama.phase.consume.wait", 232 * MS, 69 * MS, {"seq": 3}),
            ("dllama.launch.decode", 301 * MS, 2 * MS, {"seq": 4})]}]}
    if not phases:
        host["lines"][0]["events"] = [
            e for e in host["lines"][0]["events"]
            if not e[0].startswith("dllama.phase.")]
    if recorded:
        host["recorded"] = list(recorded)
    return [dev, host, {"name": "/host:metadata", "lines": []}]


def test_idle_is_laid_over_the_phases():
    """100-110 ms lies under consume.wait's tail, consume.fold, emit.scan,
    an uncovered ms of `emit`, dispatch.build and the call; 200-230 under
    the drained stretch; 300-304 under the next wait and the next call."""
    got = trace_idle_by_phase.join(planes())
    assert got["device_window_s"] == pytest.approx(0.400)
    assert got["idle_s"] == pytest.approx(0.010 + 0.030 + 0.004)
    assert got["by_phase"] == pytest.approx({
        "consume.wait": 0.001 + 0.001 + 0.001, "consume.fold": 0.001,
        "emit.scan": 0.003 + 0.002, "emit.finish": 0.002,
        "dispatch.build": 0.001 + 0.001, "dispatch.call": 0.003 + 0.007 + 0.002,
        "admit.start": 0.008})
    assert got["under_phases_s"] == pytest.approx(0.033)
    assert got["launches"] == 3 and got["phase_spans"] == 11
    assert got["outside_host_s"] == 0.0


def test_idle_no_phase_covers_is_split_by_state():
    """The emit state's last ms (105-106) and admission's 214-222 are self
    time of their states; 201-202, between the wait's end and the state's,
    and 303-304 after the call are decode_wait's."""
    got = trace_idle_by_phase.join(planes())
    assert got["uncovered_s"] == pytest.approx(0.011)
    assert got["uncovered_share"] == pytest.approx(0.011 / 0.044)
    assert got["uncovered_by_state"] == pytest.approx({
        "emit": 0.001, "decode_wait": 0.001 + 0.001, "admission": 0.008})
    # where no state is stamped either, the rest goes under "none"
    bare = planes()
    bare[1]["lines"][0]["events"] = [
        e for e in bare[1]["lines"][0]["events"]
        if e[0] != "dllama.sched.admission"]
    got = trace_idle_by_phase.join(bare)
    assert got["uncovered_by_state"] == pytest.approx({
        "emit": 0.001, "decode_wait": 0.002, "none": 0.008})


def test_idle_goes_under_the_drain_reason_the_phases_carried():
    """The launch annotation carries no reason: it runs under the reason of
    the phase before it."""
    got = trace_idle_by_phase.join(planes())
    assert got["by_drain"] == pytest.approx({
        "arrival": 0.001 + 0.002 + 0.002 + 0.008 + 0.001 + 0.007,
        "none": 0.033 - 0.021})


def test_a_first_phase_that_began_before_the_profiler_is_uncovered():
    """The device also idles at 20-30 ms, under the consume.wait that was
    open when the capture began and is not in it (the program restamps the
    open phase at a capture's two ends; a capture without that shows it as
    uncovered, under no state)."""
    early = planes()
    early[0]["lines"][0]["events"][0:1] = [("%a = x", 0, 20 * MS, {}),
                                           ("%a2 = x", 30 * MS, 70 * MS, {})]
    got = trace_idle_by_phase.join(early)
    assert got["idle_s"] == pytest.approx(0.054)
    assert got["uncovered_s"] == pytest.approx(0.021)
    assert got["uncovered_by_state"]["none"] == pytest.approx(0.010)
    assert got["by_phase"]["consume.wait"] == pytest.approx(0.003)


def test_idle_outside_the_hosts_recording_is_set_apart():
    got = trace_idle_by_phase.join(planes(recorded=(40 * MS, 250 * MS)))
    assert got["outside_host_s"] == pytest.approx(0.004)
    assert got["idle_s"] == pytest.approx(0.040)
    assert "dispatch.call" in got["by_phase"]


@pytest.mark.parametrize("why", ["no_phases", "no_device"])
def test_join_none_paths(why):
    """A program without phases (the parent of PR 40) reads nothing and
    raises nothing."""
    pl = planes(phases=(why != "no_phases"))
    if why == "no_device":
        pl = pl[1:]
    assert trace_idle_by_phase.join(pl) is None


def scrape(launches, states, phases, opens, drains, waits, gap):
    m = {}
    m.update({f'dllama_launches_total{{kind="{k}"}}': v
              for k, v in launches.items()})
    m.update({f'dllama_scheduler_time_seconds_total{{state="{k}"}}': v
              for k, v in states.items()})
    m.update({f'dllama_scheduler_phase_seconds_total{{phase="{k}"}}': v
              for k, v in phases.items()})
    m.update({f'dllama_scheduler_phase_total{{phase="{k}"}}': v
              for k, v in opens.items()})
    m.update({f'dllama_pipeline_drains_total{{reason="{k}"}}': v
              for k, v in drains.items()})
    m.update({f'dllama_launch_waits_total{{outcome="{k}"}}': v
              for k, v in waits.items()})
    m["dllama_decode_host_gap_seconds_sum"] = gap
    for fam in ("dllama_launches_total", "dllama_pipeline_drains_total"):
        m[fam] = sum(v for k, v in m.items() if k.startswith(fam + "{"))
    return m


def run_of():
    before = scrape({"decode": 10, "hybrid": 0}, {"emit": 1.0, "decode_wait": 5.0},
                    {"emit.scan": 0.5, "emit.finish": 0.1}, {"emit.scan": 10},
                    {"arrival": 1}, {"ready": 1, "blocked": 9}, 0.01)
    after = scrape({"decode": 290, "hybrid": 20},
                   {"emit": 4.0, "decode_wait": 29.0, "idle": 0.0},
                   {"emit.scan": 2.6, "emit.finish": 0.7},
                   {"emit.scan": 310}, {"arrival": 13, "empty": 0},
                   {"ready": 31, "blocked": 279}, 0.31)
    capture = {"launches": {"decode": 18, "hybrid": 2}, "seconds": 2.0,
               "sched_seconds": {"emit": 0.24, "decode_wait": 1.6, "idle": 0.0},
               "phase_seconds": {"emit.scan": 0.16, "emit.finish": 0.06},
               "phases": {"emit.scan": 20.0, "admit.pump": 0.0},
               "drains": {"arrival": 1.0, "empty": 0.0},
               "launch_waits": {"ready": 2.0, "blocked": 18.0},
               "host_gap": {"sum": 0.03, "count": 20.0}}
    return {"t0": 100.0, "t1": 130.0, "before": {"metrics": before},
            "after": {"metrics": after, "perf": {"capture": capture}}}


def test_host_tables_set_the_capture_beside_the_window():
    t = trace_idle_by_phase.host_tables(run_of())
    cap, win = t["capture"], t["window"]
    assert cap["launches"] == 20 and win["launches"] == 300
    assert cap["state_ms_per_launch"] == pytest.approx(
        {"emit": 12.0, "decode_wait": 80.0})
    assert win["state_ms_per_launch"] == pytest.approx(
        {"emit": 10.0, "decode_wait": 80.0})
    assert cap["phase_ms_per_launch"] == pytest.approx(
        {"emit.scan": 8.0, "emit.finish": 3.0})
    assert win["phase_ms_per_launch"] == pytest.approx(
        {"emit.scan": 7.0, "emit.finish": 2.0})
    assert cap["phase_opens"] == {"emit.scan": 20.0}
    assert win["phase_opens"] == {"emit.scan": 300}
    assert cap["drains"] == {"arrival": 1.0} and win["drains"] == {"arrival": 12}
    assert win["launch_waits"] == {"ready": 30, "blocked": 270}
    assert cap["host_gap_ms_per_launch"] == pytest.approx(1.5)
    assert win["host_gap_ms_per_launch"] == pytest.approx(1.0)
    assert win["seconds"] == 30.0 and cap["seconds"] == 2.0


def test_host_tables_leave_out_what_the_program_does_not_export():
    """The parent's capture block has no host seconds, and a scrape with no
    launch in the window has no table."""
    run = run_of()
    del run["after"]["perf"]["capture"]["sched_seconds"]
    assert set(trace_idle_by_phase.host_tables(run)) == {"window"}
    run["after"]["perf"] = {"capture": None}
    run["after"]["metrics"] = dict(run["before"]["metrics"])
    assert trace_idle_by_phase.host_tables(run) == {}


def test_reduce_without_a_capture_file_is_none(monkeypatch, tmp_path):
    monkeypatch.setattr(trace_idle_by_phase.trace_sched_gap, "CAPTURES",
                        str(tmp_path / "trace-*" / "*.xplane.pb"))
    assert trace_idle_by_phase.reduce({}, {"trace": {"ops": []}}) is None
    assert trace_idle_by_phase.reduce({}, {"trace": None}) is None


def test_reduce_reads_the_newest_capture_and_prints_the_line(monkeypatch,
                                                             tmp_path, capsys):
    d = tmp_path / "trace-cell"
    d.mkdir()
    (d / "a.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_idle_by_phase.trace_sched_gap, "CAPTURES",
                        str(tmp_path / "trace-*" / "*.xplane.pb"))
    monkeypatch.setattr(trace_idle_by_phase, "read", lambda path: planes())
    run = dict(run_of(), trace={"ops": []})
    got = trace_idle_by_phase.reduce({}, run)
    assert got == pytest.approx(1e3 * 0.033 / 3)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "idle_by_phase"
    assert line["by_phase"]["admit.start"] == pytest.approx(0.008)
    assert line["by_drain"]["arrival"] == pytest.approx(0.021)
    assert line["host"]["capture"]["drains"] == {"arrival": 1.0}
    # the parent: states and launches, no phases; nothing read, nothing raised
    monkeypatch.setattr(trace_idle_by_phase, "read",
                        lambda path: planes(phases=False))
    assert trace_idle_by_phase.reduce({}, run) is None
    assert capsys.readouterr().out == ""


def test_read_keeps_the_arguments_of_a_real_capture(tmp_path):
    """A real (CPU) profiler capture, started as the program starts one
    (no Python tracer): the phases come back with `seq` and `drain`, the
    `recorded` extent is kept, and a CPU has no device plane to join."""
    import glob

    import jax
    import jax.numpy as jnp

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    for i in range(3):
        with jax.profiler.TraceAnnotation("dllama.sched.emit"):
            with jax.profiler.TraceAnnotation("dllama.phase.emit.scan", seq=i,
                                              drain="arrival"):
                jnp.ones(8).sum().block_until_ready()
            with jax.profiler.TraceAnnotation("dllama.phase.emit.finish",
                                              seq=i):
                pass
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    got = trace_idle_by_phase.read(path[0])
    events = [e for pl in got for ln in pl["lines"] for e in ln["events"]]
    scans = [a for n, _, _, a in events if n == "dllama.phase.emit.scan"]
    assert [a["seq"] for a in scans] == [0, 1, 2]
    assert {a["drain"] for a in scans} == {"arrival"}
    ends = [a for n, _, _, a in events if n == "dllama.phase.emit.finish"]
    assert len(ends) == 3 and all("drain" not in a for a in ends)
    assert all(n.startswith("dllama.") for n, _, _, _ in events)
    assert not any(n.startswith("$") for n, _, _, _ in events)
    assert any(pl.get("recorded") for pl in got)
    assert trace_idle_by_phase.join(got) is None


# -------------------------------------------- the metric files and entries


NEW = {"host_work_ms_per_launch": ("ms", "lower", "program_counter", "scheduler", "out_tok_s"),
       "device_wait_ms_per_launch": ("ms", "higher", "program_counter", "scheduler", "out_tok_s"),
       "host_gap_ms_per_launch": ("ms", "lower", "program_counter", "scheduler", "out_tok_s"),
       "pipeline_drain_share": ("%", "lower", "program_counter", "scheduler", "itl_p95_ms"),
       "emit_us_per_token": ("us", "lower", "program_counter", "front end and scheduler", "out_tok_s"),
       "commit_host_ms_per_commit": ("ms", "lower", "program_counter", "engine step", "itl_p95_ms"),
       "idle_by_phase_ms_per_launch": ("ms", "lower", "device_trace", "scheduler", "out_tok_s")}
EXPECTED = {"host_work_ms_per_launch": 10.0, "device_wait_ms_per_launch": 80.0,
            "host_gap_ms_per_launch": 1.0, "pipeline_drain_share": 4.0,
            "emit_us_per_token": None, "commit_host_ms_per_commit": None}


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_file_and_manifest_entry_agree(name):
    """Appended to `per_layer`, with no `workloads` list (it reports in every
    cell), its file naming the entry's layer and end-to-end metric."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = {m["name"]: m for m in manifest["per_layer"]}
    unit, better, source, layer, moves = NEW[name]
    assert entries[name] == {"name": name, "unit": unit, "better": better,
                             "source": source, "layer": layer, "moves": moves}
    assert [m["name"] for m in manifest["per_layer"]][-7:] == list(NEW)
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert (spec["layer"], spec["moves"]) == (layer, moves)
    assert os.path.exists(os.path.join(BENCH, "reducers",
                                       spec["reducer"] + ".py"))
    if name in EXPECTED and EXPECTED[name] is not None:
        got = ratio_of_deltas.reduce(spec["params"], dict(run_of(), config={}))
        assert got == pytest.approx(EXPECTED[name])


def test_commit_and_emit_metrics_on_their_own_series():
    def spec(name):
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            return json.load(f)["params"]

    run = dict(run_of(), config={})
    b, a = run["before"]["metrics"], run["after"]["metrics"]
    b["dllama_tokens_generated_total"], a["dllama_tokens_generated_total"] = 100, 1300
    assert ratio_of_deltas.reduce(spec("emit_us_per_token"), run) == pytest.approx(
        1e6 * (2.1 + 0.6) / 1200)
    # a window without a commit reports nothing
    assert ratio_of_deltas.reduce(spec("commit_host_ms_per_commit"), run) is None
    k = 'dllama_scheduler_phase_{}{{phase="commit.{}"}}'
    a[k.format("seconds_total", "sample")] = 0.3
    a[k.format("seconds_total", "activate")] = 0.1
    a[k.format("total", "activate")] = 20
    assert ratio_of_deltas.reduce(
        spec("commit_host_ms_per_commit"), run) == pytest.approx(20.0)
