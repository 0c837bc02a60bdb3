"""CPU rehearsals of what PR 26 added to the benchmark: the paged
attention cost file, the roofline reducer that is given the capture block,
and the reducer that lays the device's idle stretches over the scheduler's
states, each on small hand-built inputs, with their None paths."""

import json
import os

import pytest

from benchmark.costs import paged_attention
from benchmark.reducers import (ratio_of_deltas, trace_capture_roofline,
                                trace_module_ms, trace_sched_gap)

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

CONFIG = {"hidden_size": 4096, "num_attention_heads": 32,
          "num_key_value_heads": 32, "num_hidden_layers": 30,
          "serve": {"slots": 12}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
DECODE_HLO = ("%_paged_folded.15 = (f32[12,32,8,128]{3,2,1,0:T(8,128)S(1)}, "
              "bf16[67,32,128,128]{3,2,1,0:T(8,128)(2,1)}, "
              "bf16[67,32,128,128]{3,2,1,0:T(8,128)(2,1)S(1)}) custom-call("
              "s32[12]{0:T(128)S(1)} %get-tuple-element.3617)")
SLICE_HLO = ("%_paged_folded.14 = (f32[1,32,16,128]{3,2,1,0:T(8,128)S(1)}, "
             "bf16[67,32,128,128]{3,2,1,0:T(8,128)(2,1)}, "
             "bf16[67,32,128,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(")
# 6 launches of 4 steps on 12 slots: 288 slot-steps; 3,840 rows a step
CAPTURE = {"launches": {"decode": 4, "hybrid": 2, "prefill_chunk": 0},
           "slot_steps": {"advanced": 270, "starved": 6, "empty": 12},
           "kv_rows": {"decode": 61440, "hybrid": 30720},
           "prefill_rows": {"hybrid": 32}, "seconds": 2.0}


def op(hlo, count, seconds):
    return {"name": hlo.split(" = ")[0].lstrip("%"), "group": "_paged_folded",
            "count": count, "seconds": seconds, "hlo": hlo}


def run_with(ops, capture=CAPTURE, config=CONFIG):
    return {"trace": {"ops": ops}, "config": config, "peaks": lambda: PEAKS,
            "after": {"perf": {"capture": capture}}}


# ------------------------------------------------------- the cost file


def test_rows_per_step_is_rows_over_steps():
    assert paged_attention.rows_per_step(CAPTURE, 12) == pytest.approx(
        92160 / 24)
    assert paged_attention.rows_per_step(None, 12) is None
    assert paged_attention.rows_per_step(
        {"launches": {"prefill_chunk": 3}, "slot_steps": {}, "kv_rows": {}},
        12) is None


def test_spec_launches_in_the_capture_are_not_priced():
    cap = dict(CAPTURE, launches={"decode": 4, "spec": 1})
    assert paged_attention.rows_per_step(cap, 12) is None


def test_a_decode_call_costs_its_rows_needed():
    flops, nbytes = paged_attention.calls(CONFIG, op(DECODE_HLO, 1, 1.0),
                                          CAPTURE)
    rows = 92160 / 24  # 3,840 rows over the 12 slots of a step
    assert nbytes == pytest.approx(rows * 2 * 32 * 128 * 2
                                   + 12 * 32 * 128 * 6)
    assert flops == pytest.approx(4 * rows * 32 * 128)
    # bytes bound at decode widths: 63 MB in 77 us against 63 MFLOP
    assert nbytes / PEAKS["hbm_bytes_per_s"] > flops / PEAKS["bf16_flops_per_s"]


def test_f8_pool_halves_the_kv_bytes():
    hlo = DECODE_HLO.replace("bf16[67", "f8e4m3fn[67")
    _, nbytes = paged_attention.calls(CONFIG, op(hlo, 1, 1.0), CAPTURE)
    assert nbytes == pytest.approx(3840 * 2 * 32 * 128 * 1 + 12 * 32 * 128 * 6)


def test_a_prefill_slice_is_skipped_and_a_strange_batch_is_none():
    assert paged_attention.calls(CONFIG, op(SLICE_HLO, 1, 1.0),
                                 CAPTURE) == "skip"
    strange = DECODE_HLO.replace("f32[12,32", "f32[7,32")
    assert paged_attention.calls(CONFIG, op(strange, 1, 1.0), CAPTURE) is None


@pytest.mark.parametrize("why", ["unparsed", "one_slot", "no_capture",
                                 "other_heads"])
def test_cost_none_paths(why):
    config, hlo, capture = CONFIG, DECODE_HLO, CAPTURE
    if why == "unparsed":
        hlo = "%_paged_folded.3 = f32[12,32,8,128] custom-call()"
    if why == "one_slot":
        config = dict(CONFIG, serve={"slots": 1})
    if why == "no_capture":
        capture = None
    if why == "other_heads":
        config = dict(CONFIG, num_key_value_heads=8)
    assert paged_attention.calls(config, op(hlo, 1, 1.0), capture) is None


# --------------------------------------------- the roofline reducer


PARAMS = {"match": "^_paged_folded$", "cost": "paged_attention"}


def test_roofline_share_prices_decode_calls_only():
    # 720 decode calls of 63.2 MB at 819 GB/s = 55.6 ms, in 0.6 s of self
    # time: 9.3%; the slices' time and count are in neither side
    ops = [op(DECODE_HLO, 720, 0.6), op(SLICE_HLO, 60, 0.05)]
    per_call = (3840 * 2 * 32 * 128 * 2 + 12 * 32 * 128 * 6) / 819e9
    got = trace_capture_roofline.reduce(PARAMS, run_with(ops))
    assert got == pytest.approx(100 * 720 * per_call / 0.6)
    assert 9.0 < got < 9.6


@pytest.mark.parametrize("why", ["no_trace", "no_such_op", "no_capture",
                                 "parent_perf", "only_slices", "unparsed"])
def test_roofline_none_paths(why):
    run = run_with([op(DECODE_HLO, 720, 0.6)])
    if why == "no_trace":
        run["trace"] = None
    if why == "no_such_op":
        run["trace"] = {"ops": [dict(op(DECODE_HLO, 1, 1.0), group="copy")]}
    if why == "no_capture":
        run["after"]["perf"]["capture"] = None
    if why == "parent_perf":  # a program without the block
        run["after"] = {"perf": {"mode": "continuous"}}
    if why == "only_slices":
        run["trace"] = {"ops": [op(SLICE_HLO, 60, 0.05)]}
    if why == "unparsed":
        run["trace"] = {"ops": [op("%_paged_folded.1 = garbage", 3, 0.1)]}
    run["peaks"] = lambda: pytest.fail("peaks asked for with nothing to price")
    assert trace_capture_roofline.reduce(PARAMS, run) is None


# ----------------------------------- idle stretches over scheduler states


MS = 1_000_000


def planes(sched=True, recorded=(0, 400 * MS)):
    """A device that runs 0-100, 110-200, 230-300, 304-400 ms, under a host
    whose worker thread is in decode_wait, emit, decode_dispatch ... The
    first 50 ms have no state (it began before the profiler); the host's
    recorder ran for `recorded`."""
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_dllama_decode(1)", 0, 100 * MS, {}),
            ("jit_dllama_hybrid(2)", 110 * MS, 90 * MS, {})]},
        {"name": "XLA Ops", "events": [
            ("%a = x", 0, 100 * MS, {}), ("%b = x", 110 * MS, 90 * MS, {}),
            ("%c = x", 230 * MS, 70 * MS, {}), ("%d = x", 304 * MS, 96 * MS, {})]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [("$threading.py:637 wait", 0, 400 * MS, {})]},
        {"name": "python3", "events": [
            ("dllama.sched.decode_wait", 50 * MS, 52 * MS, {}),
            ("dllama.sched.emit", 102 * MS, 4 * MS, {}),
            ("dllama.sched.decode_dispatch", 106 * MS, 6 * MS, {}),
            ("dllama.launch.decode", 107 * MS, 4 * MS, {}),
            ("dllama.sched.decode_wait", 112 * MS, 90 * MS, {}),
            ("dllama.sched.idle", 202 * MS, 20 * MS, {}),
            ("dllama.sched.hybrid", 222 * MS, 10 * MS, {}),
            ("dllama.launch.hybrid", 223 * MS, 8 * MS, {}),
            ("dllama.sched.decode_wait", 232 * MS, 168 * MS, {}),
            ("dllama.launch.decode", 301 * MS, 2 * MS, {})]}]}
    if not sched:
        host["lines"].pop()
    if recorded:
        host["recorded"] = list(recorded)
    return [dev, host, {"name": "/host:metadata", "lines": []}]


def test_idle_is_laid_over_the_states():
    got = trace_sched_gap.join(planes())
    assert got["device_window_s"] == pytest.approx(0.400)
    assert got["idle_s"] == pytest.approx(0.010 + 0.030 + 0.004)
    assert got["by_state"] == pytest.approx({
        "decode_wait": 0.002 + 0.002 + 0.004, "emit": 0.004,
        "decode_dispatch": 0.004, "idle": 0.020, "hybrid": 0.008})
    assert got["by_launch"] == pytest.approx({"decode": 0.003 + 0.002,
                                              "hybrid": 0.007})
    assert got["uncovered_s"] == pytest.approx(0.0, abs=1e-12)
    assert got["outside_host_s"] == 0.0 and got["host_recorded_s"] == 0.4
    assert got["host_work_s"] == pytest.approx(0.024)
    assert got["launches"] == 3 and got["sched_spans"] == 7
    # no commit in this capture: the split has nothing to set apart
    assert got["commits"] == 0 and got["commit_idle_ms_per_commit"] is None
    assert got["steady_gap_ms_per_launch"] == pytest.approx(1e3 * 0.024 / 3)


def test_idle_under_a_commit_is_split_from_the_steady_gaps():
    """The 202-222 ms stretch is an admission's commit, restamped in its
    middle (two spans in a row, one commit): its idle is set apart per
    commit, and what is left per launch no longer moves with it."""
    pl = planes()
    host = pl[1]["lines"][-1]
    i = [e[0] for e in host["events"]].index("dllama.sched.idle")
    host["events"][i:i + 1] = [("dllama.sched.commit", 202 * MS, 8 * MS, {}),
                               ("dllama.sched.commit", 210 * MS, 12 * MS, {})]
    got = trace_sched_gap.join(pl)
    assert got["commits"] == 1
    assert got["by_state"]["commit"] == pytest.approx(0.020)
    assert got["host_work_s"] == pytest.approx(0.044)
    assert got["commit_idle_ms_per_commit"] == pytest.approx(20.0)
    assert got["steady_gap_ms_per_launch"] == pytest.approx(1e3 * 0.024 / 3)


def test_what_no_state_covers_is_reported():
    early = planes()
    # the device also idles at 20-30 ms, before the first stamped state
    early[0]["lines"][1]["events"][0:1] = [("%a = x", 0, 20 * MS, {}),
                                           ("%a2 = x", 30 * MS, 70 * MS, {})]
    got = trace_sched_gap.join(early)
    assert got["uncovered_s"] == pytest.approx(0.010)
    assert got["uncovered_share"] == pytest.approx(0.010 / 0.054)


def test_idle_outside_the_hosts_recording_is_set_apart():
    """The host's recorder stopped at 250 ms (and its extent is taken from
    the annotations when the plane does not say): the 300-304 ms stretch is
    under no state and in no share."""
    for recorded in ((40 * MS, 250 * MS), None):
        pl = planes(recorded=recorded)
        host = pl[1]["lines"][-1]
        host["events"] = [e for e in host["events"] if e[1] + e[2] <= 250 * MS]
        got = trace_sched_gap.join(pl)
        assert got["outside_host_s"] == pytest.approx(0.004)
        assert got["idle_s"] == pytest.approx(0.040)
        assert got["uncovered_s"] == pytest.approx(0.0, abs=1e-12)
        assert got["by_state"]["hybrid"] == pytest.approx(0.008)
        assert "decode_wait" in got["by_state"]


@pytest.mark.parametrize("why", ["no_states", "no_device"])
def test_join_none_paths(why):
    pl = planes(sched=(why != "no_states"))
    if why == "no_device":
        pl = pl[1:]
    assert trace_sched_gap.join(pl) is None


def test_reduce_without_a_capture_file_is_none(monkeypatch, tmp_path):
    monkeypatch.setattr(trace_sched_gap, "CAPTURES",
                        str(tmp_path / "trace-*" / "*.xplane.pb"))
    assert trace_sched_gap.reduce({}, {"trace": {"ops": []}}) is None
    assert trace_sched_gap.reduce({}, {"trace": None}) is None


def test_reduce_reads_the_newest_capture_and_prints_the_line(monkeypatch,
                                                             tmp_path, capsys):
    d = tmp_path / "trace-cell"
    d.mkdir()
    (d / "a.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(trace_sched_gap, "CAPTURES",
                        str(tmp_path / "trace-*" / "*.xplane.pb"))
    monkeypatch.setattr(trace_sched_gap, "read", lambda path: planes())
    got = trace_sched_gap.reduce({}, {"trace": {"ops": []}})
    assert got == pytest.approx(1e3 * 0.024 / 3)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "idle_by_state"
    assert line["by_state"]["idle"] == pytest.approx(0.020)
    monkeypatch.setattr(trace_sched_gap, "read", lambda path: planes(False))
    assert trace_sched_gap.reduce({}, {"trace": {"ops": []}}) is None


def test_read_keeps_every_dllama_event_of_a_real_capture(tmp_path):
    """A real (CPU) profiler capture: the annotations the program would
    write come back by name, whole; other host events are dropped."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation("dllama.sched.decode_dispatch"):
            with jax.profiler.TraceAnnotation("dllama.launch.decode", seq=i,
                                              n=4):
                jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = [name for pl in trace_sched_gap.read(path[0])
             for ln in pl["lines"] for name, _, d, _ in ln["events"]]
    assert names.count("dllama.launch.decode") == 3
    assert names.count("dllama.sched.decode_dispatch") == 3
    assert all(n.startswith("dllama.") for n in names)  # a CPU: no device plane
    assert trace_sched_gap.join(trace_sched_gap.read(path[0])) is None


# ------------------------------------- the metric files on old reducers


def metric(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def test_launch_metrics_tell_the_programs_apart():
    run = {"trace": {"modules": {"jit_dllama_decode": [0.280, 0.282, 0.284],
                                 "jit_dllama_hybrid": [0.331, 0.335],
                                 "jit_dllama_decode_pen": [0.9],
                                 "jit_convert_element_type": [1e-6] * 9}}}
    assert trace_module_ms.reduce(metric("decode_launch_ms_p50")["params"],
                                  run) == pytest.approx(282.0)
    assert trace_module_ms.reduce(metric("hybrid_launch_ms_p50")["params"],
                                  run) == pytest.approx(331.0)
    parent = {"trace": {"modules": {"jit__unknown": [0.3]}}}
    assert trace_module_ms.reduce(metric("decode_launch_ms_p50")["params"],
                                  parent) is None


def test_starved_share_reads_the_labelled_counter():
    from benchmark import loadlib

    def scrape(adv, starved):
        return {"metrics": loadlib.prometheus(
            f'dllama_slot_steps_total{{state="advanced"}} {adv}\n'
            f'dllama_slot_steps_total{{state="starved"}} {starved}\n'
            f'dllama_slot_steps_total{{state="empty"}} 5000\n')}

    params = metric("starved_slot_step_share")["params"]
    run = {"before": scrape(1000, 10), "after": scrape(4900, 110),
           "config": CONFIG}
    assert ratio_of_deltas.reduce(params, run) == pytest.approx(2.5)
    parent = {"before": {"metrics": {}}, "after": {"metrics": {}},
              "config": CONFIG}
    assert ratio_of_deltas.reduce(params, parent) is None


def test_the_program_renders_the_series_the_metric_file_names():
    from benchmark import loadlib
    from dllama_tpu.engine import launch_record  # noqa: F401  (the series)
    from dllama_tpu.obs import metrics

    fams = loadlib.prometheus(metrics.render())
    for key in metric("starved_slot_step_share")["params"]["den"]:
        assert key in fams, key


def test_manifest_lists_the_five_metrics_for_the_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    added = {m["name"]: m for m in manifest["per_layer"][-5:]}
    assert list(added) == ["decode_launch_ms_p50", "hybrid_launch_ms_p50",
                           "starved_slot_step_share", "paged_attn_roofline",
                           "sched_gap_ms_per_launch"]
    layers = {m["layer"] for m in manifest["per_layer"][:-5]}
    for name, m in added.items():
        spec = metric(name)
        assert m["workloads"] == ["deepseek7b.decode_closed"]
        assert (spec["layer"], spec["moves"]) == (m["layer"], m["moves"])
        assert m["layer"] in layers
        assert os.path.exists(os.path.join(BENCH, "reducers",
                                           spec["reducer"] + ".py"))
