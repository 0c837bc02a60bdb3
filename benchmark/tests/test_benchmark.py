"""CPU rehearsals of the benchmark: the reference, the check and its
controls, the generators, the reducers, the trace reduction, and the whole
command end to end at a tiny configuration (never a cell)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, "tiny-llama.json")
MANIFEST = os.path.join(HERE, "manifest.json")


def tiny_config():
    with open(TINY) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_files(tmp_path_factory):
    from benchmark import files

    d = tmp_path_factory.mktemp("tiny")
    cfg = tiny_config()
    model, tok = str(d / "tiny.m"), str(d / "tiny.t")
    files.write_model(model, cfg, 3)
    files.write_tokenizer(tok, cfg["vocab_size"])
    return model, tok


def run_cli(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, **kw)


# ------------------------------------------------------------------ files


def test_model_file_is_deterministic_and_read_by_the_program(tmp_path, tiny_files):
    from benchmark import files
    from benchmark.layouts import llama as layout
    from dllama_tpu.models.formats import read_header
    from dllama_tpu.tokenizer.tokenizer import Tokenizer

    cfg = tiny_config()
    again = str(tmp_path / "again.m")
    files.write_model(again, cfg, 3, workers=1)
    other = str(tmp_path / "other.m")
    files.write_model(other, cfg, 2**31 + 7)  # more than 32 signed bits hold
    with open(tiny_files[0], "rb") as a, open(again, "rb") as b, open(other, "rb") as c:
        first = a.read()
        assert first == b.read()
        assert first != c.read()
    prog, header = read_header(tiny_files[0])
    mine, header2 = layout.read_header(tiny_files[0])
    assert header == header2
    assert (prog.dim, prog.hidden_dim, prog.n_layers, prog.n_kv_heads) == (
        mine["dim"], mine["hidden_dim"], mine["n_layers"], mine["n_kv_heads"])
    tk = Tokenizer.load(tiny_files[1])
    ids = tk.encode("helloworld")
    assert len(ids) == 1 + len("helloworld")  # BOS + one token a byte


# -------------------------------------------------------------- reference


def test_reference_agrees_with_the_programs_forward(tiny_files):
    from benchmark.check import rel_l2
    from benchmark.reference import llama as ref
    from dllama_tpu.engine.loader import load_model

    loaded = load_model(tiny_files[0], tiny_files[1], max_seq_len=512, mesh=None)
    toks = np.random.default_rng(0).integers(0, 700, 50).astype(np.int32)
    got = np.asarray(loaded.engine.step(toks[None]), np.float32).reshape(-1)
    want = ref.logits_at(tiny_files[0], [toks], [[len(toks) - 1]])[0][0]
    assert got.shape == want.shape
    assert rel_l2(got, want) < 0.05  # bf16 activations vs float32


# ------------------------------------------------ the check and its controls


def check_numbers(tiny_files, seed, flags=()):
    cmd = [os.path.join(BENCH, "serve_child.py"), "--config", TINY,
           "--model", tiny_files[0], "--tokenizer", tiny_files[1],
           "--seed", str(seed), "--check-only", *[f"--flag={f}" for f in flags]]
    out = run_cli(cmd)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    return next(r for r in rec if r.get("phase") == "check")


@pytest.mark.parametrize("seed", [1, 2])
def test_check_passes_at_the_stated_precision_and_fails_with_an_f8_cache(tiny_files, seed):
    """The control of the contract: the program's own lower-precision path
    (`--cache-dtype f8`) must come out as not correct under the limits the
    tiny configuration's file states (set from CPU readings over six seeds,
    as the cells' limits are set from the chip's)."""
    sound = check_numbers(tiny_files, seed)
    control = check_numbers(tiny_files, seed, flags=("--cache-dtype", "f8"))
    assert sound["correct"] is True, sound
    assert control["correct"] is False, control
    assert control["rel_l2_mean"] > 2 * sound["rel_l2_mean"]


def test_check_fails_when_one_layers_weights_differ(tiny_files, tmp_path):
    """The reference reads the file; the engine is handed another model
    (one layer's w1 redrawn): rel L2 and the token margins both show it."""
    from benchmark import check, files
    from benchmark.layouts import llama as layout
    from dllama_tpu.engine.loader import load_model

    cfg = tiny_config()
    cfg["engine"] = {"n_slots": 4, "kv_layout": "paged", "page_size": 128,
                     "kv_pages": 12, "radix_cache": "auto"}
    loaded = load_model(tiny_files[0], tiny_files[1], max_seq_len=512, mesh=None)
    other = str(tmp_path / "perturbed.m")
    with open(tiny_files[0], "rb") as f:
        data = bytearray(f.read())
    _, views = layout.tensor_views(tiny_files[0])
    raw = views["layers.1.w1"][0]
    start = raw.ctypes.data - views["embedding"][0].ctypes.data + layout.read_header(tiny_files[0])[1]
    rng = np.random.default_rng(9)
    blocks = np.frombuffer(data, np.uint8, len(raw), start).reshape(-1, files.Q40_BLOCK_BYTES)
    blocks[:, 2:] = rng.integers(0, 256, blocks[:, 2:].shape, np.uint8)
    with open(other, "wb") as f:
        f.write(data)
    sound = check.run(loaded, cfg, tiny_files[0], 3)
    broken = check.run(loaded, cfg, other, 3)
    assert sound["correct"] is True
    assert broken["correct"] is False
    assert broken["rel_l2_mean"] > 0.5 and broken["rel_l2_mean"] > 5 * sound["rel_l2_mean"]
    assert broken["deficit_sigma_max"] > 1.0 > sound["deficit_sigma_max"]
    assert broken["deficit_sigma_mean"] > 10 * cfg["tolerances"]["deficit_sigma_mean"]


# ------------------------------------------------------------- generators


def test_shapes_are_the_same_for_every_seed_and_only_the_text_differs():
    from benchmark import loadlib

    params = {"shape_seed": 5,
              "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.8, "lo": 32, "hi": 2048},
              "max_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.6, "lo": 16, "hi": 256},
              "tenants": {"count": 4, "share": 0.7,
                          "prefix_tokens": {"dist": "uniform", "lo": 256, "hi": 512}}}
    a = loadlib.build_shapes(params, 1, 200)
    b = loadlib.build_shapes(params, 1, 200)
    c = loadlib.build_shapes(params, 2**31 + 5, 200)
    assert [s.prompt for s in a] == [s.prompt for s in b]
    key = lambda shapes: [(s.prompt_tokens, s.max_tokens, s.tenant) for s in shapes]
    assert key(a) == key(c)
    assert [s.prompt for s in a] != [s.prompt for s in c]
    shared = [s for s in a if s.tenant >= 0]
    assert 0.55 < len(shared) / len(a) < 0.85
    t0 = [s.prompt for s in shared if s.tenant == shared[0].tenant]
    assert len(os.path.commonprefix(t0)) >= 256
    assert all(32 <= s.prompt_tokens <= 2048 for s in a if s.tenant < 0)


def test_percentiles_time_from_due_count_failures_and_report_lateness():
    from benchmark.loadlib import Record, Shape
    from benchmark.reducers import client_percentile, client_rate

    sh = Shape(prompt="x", prompt_tokens=2, max_tokens=8)
    ok = Record(shape=sh, t_due=10.0, t_sent=10.2, t_end=10.8, status=200, done=True,
                finish="length", events=[(10.5, 1), (10.6, 4), (10.7, 2)],
                timings={"queue_wait_ms": 3.0, "decode_tokens": 7})
    bad = Record(shape=sh, t_due=11.0, t_sent=11.0, status=503, error="http_503")
    cut = Record(shape=sh, t_due=12.0, t_sent=12.0, status=200, cut=True)
    run = {"records": [ok, bad, cut], "t0": 9.0, "t1": 13.0}
    q = lambda quantity, p: client_percentile.reduce({"quantity": quantity, "q": p}, run)
    assert q("ttft_ms", 50) == pytest.approx(500.0)  # from DUE, not from sent
    assert q("ttft_ms", 100) == float("inf")  # the failed request misses
    assert q("late_ms", 100) == pytest.approx(200.0)
    assert q("itl_ms", 50) == pytest.approx(25.0)  # 4 tokens 100 ms after
    assert q("itl_ms", 100) == pytest.approx(50.0)
    assert q("queue_wait_ms", 50) == 3.0
    assert client_rate.reduce({}, run) == pytest.approx(7 / 4.0)


def test_structure_and_failures_are_judged_over_every_request_that_ended():
    """A request born in the ramp that errors inside the window is a failed
    request; one that finishes there with a wrong token count makes the run
    not correct; what the window's end cut is neither."""
    from benchmark import run as harness
    from benchmark.loadlib import Record, Shape

    sh = Shape(prompt="x", prompt_tokens=2, max_tokens=8)
    good = dict(status=200, done=True, events=[(10.5, 4), (11.0, 4)], finish="length",
                timings={"decode_tokens": 8})
    ramp_ok = Record(shape=sh, t_due=1.0, t_sent=1.0, t_end=11.0, **good)
    ramp_err = Record(shape=sh, t_due=2.0, t_sent=2.0, t_end=12.0, status=200, done=True,
                      events=[(3.0, 4)], finish="error", error="non-finite row")
    in_flight = Record(shape=sh, t_due=12.5, t_sent=12.5, t_end=20.1, status=200, cut=True)
    counters = {"metrics": {"dllama_engine_restarts_total": 0.0}}
    run = {"t0": 10.0, "t1": 20.0, "records": [ramp_ok, ramp_err, in_flight],
           "before": counters, "final": counters}
    out = harness.structural(run, {"audit": {"ok": True}})
    assert (out["attempted"], out["failed"], out["finished"]) == (3, 1, 1)
    assert out["in_flight_at_window_end"] == 1 and out["ok"] is True
    assert out["tok_s_by_third"] == [pytest.approx(8 * 3 / 10.0), 0.0, 0.0]
    short = Record(shape=sh, t_due=3.0, t_sent=3.0, t_end=13.0,
                   **{**good, "timings": {"decode_tokens": 5}})
    run["records"].append(short)  # ended `length` with 5 of 8 tokens
    out = harness.structural(run, {"audit": {"ok": True}})
    assert out["ok"] is False and out["bad_finishes"][0]["decode_tokens"] == 5
    run["records"].pop()
    run["final"] = {"metrics": {"dllama_engine_restarts_total": 1.0}}
    assert harness.structural(run, {"audit": {"ok": True}})["ok"] is False


def test_counter_reducers_read_deltas_and_return_none_when_nothing_moved():
    from benchmark.reducers import counter_delta, gauge_ratio, ratio_of_deltas

    run = {"before": {"metrics": {"a": 1.0, "b": 10.0}},
           "after": {"metrics": {"a": 4.0, "b": 16.0}},
           "config": {"serve": {"slots": 2}},
           "polls": [(0, {"u": 1.0, "t": 4.0}), (1, {"u": 3.0, "t": 4.0})]}
    assert counter_delta.reduce({"family": "a"}, run) == 3.0
    assert counter_delta.reduce({"family": "nope"}, run) is None
    assert ratio_of_deltas.reduce({"num": ["a"], "den": ["a", "b"], "scale": 100}, run) == pytest.approx(100 * 3 / 9)
    assert ratio_of_deltas.reduce({"num": ["a"], "den": ["b"], "divide_by_config": ["serve", "slots"]}, run) == pytest.approx(0.25)
    assert ratio_of_deltas.reduce({"num": ["a"], "den": ["nope"]}, run) is None
    assert gauge_ratio.reduce({"num": "u", "den": "t", "stat": "max", "scale": 100}, run) == 75.0


# ------------------------------------------------------- the whole command


def test_missing_files_fail_by_name(tmp_path):
    with open(MANIFEST) as f:
        m = json.load(f)
    m["workloads"].append({"name": "tiny.nomix", "config": "tiny-llama",
                           "traffic": "no_such_mix", "chips": 1, "why": "x"})
    m["workloads"].append({"name": "tiny.noconfig", "config": "no-such-config",
                           "traffic": "tiny_closed", "chips": 1, "why": "x"})
    m["end_to_end"].append({"name": "no_such_metric", "unit": "ms", "better": "lower",
                            "bound": 0.1, "source": "host_clock"})
    path = str(tmp_path / "m.json")
    with open(path, "w") as f:
        json.dump(m, f)
    base = [os.path.join(BENCH, "run.py"), "--manifest", path, "--seed", "1",
            "--seconds", "1", "--trace", "0", "--workload"]
    for workload, needle in (("tiny.nomix", "traffic/no_such_mix.json"),
                             ("tiny.noconfig", "no-such-config"),
                             ("tiny.decode_closed", "metrics/no_such_metric.json"),
                             ("nope", "no workload 'nope'")):
        out = run_cli(base + [workload])
        assert out.returncode != 0 and needle in out.stderr, (workload, out.stderr[-500:])
        assert not out.stdout.strip().startswith('{"correct"')


def test_the_real_command_refuses_a_machine_without_the_chip():
    """Off-TPU a real cell exits non-zero, prints no result, builds nothing
    but the model file."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    tiny = dict(tiny_config(), expect={"platform": "tpu", "route": "pallas/paged_kernel"})
    # the real cell at the tiny size: same code path, seconds not minutes
    import tempfile
    with tempfile.TemporaryDirectory(dir=HERE) as d:
        cfg_path = os.path.join(d, "tiny-tpu.json")
        with open(cfg_path, "w") as f:
            json.dump(tiny, f)
        real["configs"] = [{**c, "file": os.path.relpath(cfg_path, ROOT)} for c in real["configs"]]
        man = os.path.join(d, "m.json")
        with open(man, "w") as f:
            json.dump(real, f)
        out = run_cli([os.path.join(BENCH, "run.py"), "--manifest", man, "--workload", cell,
                       "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "wrong device" in out.stdout or "exited 3" in out.stderr


@pytest.mark.parametrize("workload,trace", [("tiny.decode_closed", 0), ("tiny.decode_closed", 1)])
def test_whole_command_end_to_end_on_cpu(workload, trace):
    out = run_cli([os.path.join(BENCH, "run.py"), "--manifest", MANIFEST,
                   "--workload", workload, "--seed", str(2**31 + 11),
                   "--seconds", "4", "--trace", str(trace)], timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    with open(MANIFEST) as f:
        m = json.load(f)
    names = {x["name"] for x in (m["per_layer"] if trace else m["end_to_end"])
             if workload in x.get("workloads", [workload])}
    if trace:
        names -= {"q40_matmul_roofline"}  # no device plane on a CPU
        assert "breakdown" in last and "busy_s" in last["device"]
    assert set(last["metrics"]) == names
    assert all(v["value"] == v["value"] for v in last["metrics"].values())
    assert not os.path.exists(os.path.join(BENCH, "out", f"tiny-llama-seed{2**31 + 11}.m"))


# --------------------------------------------------------- trace reduction


def test_trace_reduction_of_the_recorded_trace():
    from benchmark import trace_reduce

    path = os.path.join(HERE, "recorded_trace.json.gz")
    with open(os.path.join(HERE, "recorded_trace.expected.json")) as f:
        want = json.load(f)
    got = trace_reduce.reduce_file(path)
    assert got["device_planes"] == want["device_planes"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert {k: len(v) for k, v in got["modules"].items()} == want["module_counts"]
    assert got["device_ops"][0][0] == want["top_op"]
    assert 0 < got["busy_s"] <= got["window_s"]
    # device_ops are sums by group over the (module, name) pairs: the same
    # totals as when ops were keyed by name alone (the five largest, as
    # read with PR 27's trace_reduce.py)
    assert [k for k, _ in got["device_ops"][:5]] == list(want["device_ops_top5"])
    for k, v in got["device_ops"][:5]:
        assert v == pytest.approx(want["device_ops_top5"][k], rel=1e-9)
    assert all(o["module"] for o in got["ops"])  # every op lies in a module
    calls = [o for o in got["ops"] if o["group"] == "_blockdot_call"]
    assert sum(o["count"] for o in calls) == want["blockdot_calls"]
    assert sum(o["seconds"] for o in calls) == pytest.approx(want["blockdot_seconds"], rel=1e-9)
    # the Q40 matmul's roofline share of the recorded calls, from the
    # benchmark's own cost function and peaks table: under 100%
    from benchmark.reducers import trace_call_roofline
    with open(os.path.join(BENCH, "configs", "deepseek-llm-7b.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    share = trace_call_roofline.reduce({"match": "^_blockdot_call$", "cost": "q40_matmul"},
                                       {"trace": got, "config": config, "peaks": lambda: peaks})
    assert 20 < share < 100


def test_trace_reduction_arithmetic_on_a_made_up_trace():
    from benchmark import trace_reduce

    ev = lambda n, s, d: (n, s, d, {})
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [ev("a", 0, 10), ev("b", 990, 10)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [ev("jit_step(1)", 100, 300), ev("jit_step(2)", 600, 200)]},
            # `while.3` holds a kernel's call, as the layer scan does
            {"name": "XLA Ops", "events": [ev("%while.3 = (s32[]) while(...)", 100, 300),
                                           ev("%_k.2 = f32[8,8] custom-call(...)", 150, 100),
                                           ev("%fusion.1 = f32[8] fusion(...)", 600, 200)]}]}]
    out = trace_reduce.reduce_planes(planes)
    assert out["window_s"] == pytest.approx(700e-9)  # the device plane's span
    assert out["busy_s"] == pytest.approx((300 + 200) * 1e-9)  # union, not sum
    assert out["modules"] == {"jit_step": [pytest.approx(300e-9), pytest.approx(200e-9)]}
    # self time by group: the loop's 300 less the 100 its body's call covers
    assert out["device_ops"][:2] == [["while", pytest.approx(200e-9)], ["fusion", pytest.approx(200e-9)]]
    assert {o["name"]: o["seconds"] for o in out["ops"]}["_k.2"] == pytest.approx(100e-9)
    gaps = dict((k, v) for k, v in out["idle_gaps"])
    assert gaps["before jit_step"] == pytest.approx(200e-9)
    assert gaps["trace start"] == gaps["trace end"] == 0.0


@pytest.mark.parametrize("first", ["jit_dllama_decode", "jit_dllama_hybrid"])
def test_one_op_name_in_two_modules_is_priced_at_each_modules_shape(first):
    """Instruction names are numbered per XLA module: `_blockdot_call.71` is
    a layer's matmul in the decode program and the 102,400-wide head in a
    hybrid program. Each is priced at its own shape, whichever the capture
    holds first (keyed by name alone, the first text seen priced both)."""
    from benchmark import trace_reduce
    from benchmark.costs import q40_matmul
    from benchmark.reducers import trace_call_roofline

    layer = ("%_blockdot_call.71 = f32[16,4096]{1,0} custom-call(bf16[16,4096]{1,0} %x, "
             "u8[30,2048,4096]{2,1,0} %packed, f16[30,128,4096]{2,1,0} %scales)")
    head = ("%_blockdot_call.71 = f32[16,102400]{1,0} custom-call(bf16[16,4096]{1,0} %x, "
            "u8[2048,102400]{1,0} %packed, f16[128,102400]{1,0} %scales)")
    ev = lambda n, s, d: (n, s, d, {})
    progs = {"jit_dllama_decode": (layer, 30, 40_000), "jit_dllama_hybrid": (head, 1, 900_000)}
    mods, ops, t = [], [], 1_000
    for name in (first, *(n for n in progs if n != first)):
        hlo, count, dur = progs[name]
        mods.append(ev(f"{name}(7)", t, count * dur + 10))
        ops += [ev(hlo, t + i * dur, dur) for i in range(count)]
        t += count * dur + 5_000
    out = trace_reduce.reduce_planes([{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": mods}, {"name": "XLA Ops", "events": ops}]}])
    got = {o["module"]: o for o in out["ops"]}
    assert set(got) == set(progs)
    assert got["jit_dllama_decode"]["hlo"] == layer and got["jit_dllama_hybrid"]["hlo"] == head
    assert (got["jit_dllama_decode"]["count"], got["jit_dllama_hybrid"]["count"]) == (30, 1)
    assert out["device_ops"] == [["_blockdot_call", pytest.approx((30 * 40_000 + 900_000) * 1e-9)]]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = trace_call_roofline.reduce({"match": "^_blockdot_call$", "cost": "q40_matmul"},
                                       {"trace": out, "config": {}, "peaks": lambda: peaks})
    least = (30 * q40_matmul.cost(16, 4096, 4096)[1] + q40_matmul.cost(16, 4096, 102400)[1]) / 819e9
    assert share == pytest.approx(100 * least / ((30 * 40_000 + 900_000) * 1e-9))
    assert share < 100
    # an op that starts outside every module's execution keeps "" and is not
    # merged into a module's entry
    stray = trace_reduce.reduce_planes([{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [ev("jit_a(1)", 100, 50)]},
        {"name": "XLA Ops", "events": [ev("%k.1 = f32[8] fusion()", 110, 10),
                                       ev("%k.1 = f32[9] fusion()", 400, 10)]}]}])
    assert sorted((o["module"], o["hlo"][-15:]) for o in stray["ops"]) == [
        ("", "f32[9] fusion()"), ("jit_a", "f32[8] fusion()")]
