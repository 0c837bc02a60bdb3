"""The benchmark's one command.

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

Everything that belongs to one cell is found by the names in BENCHMARK.json:
the configuration's file (which names its model-file layout under layouts/
and its reference under reference/), `traffic/<mix>.json` (which names its
generator under generators/), and `metrics/<name>.json` for every metric the
cell reports (each names its reducer under reducers/). There is no branch
on a cell, a configuration, an architecture or a metric here; a missing
file fails by name.

This process never imports JAX (a chip belongs to one process): it writes
the model files from the seed, starts `serve_child.py` (which loads once,
decides `correct` against the plain reference, then runs the program's own
`serve` command), drives it over HTTP for `--seconds`, scrapes the
program's counters before and after, stops it, and prints the result as the
last line. With `--trace 1` the serving process captures a profiler trace of
part of the window (`POST /debug/profile`) and the per-layer metrics are
reported; with `--trace 0`, the end-to-end ones.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import files, loadlib, trace_reduce  # noqa: E402

OUT = os.path.join(HERE, "out")


def say(**record) -> None:
    """One free-form JSON line (never the last line)."""
    print(json.dumps(record), flush=True)


def load_json(path: str, what: str) -> dict:
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: {what} file is missing: "
                         f"{os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, trace: bool, manifest_path: str) -> dict:
    """The cell, its files and the metrics it reports, all by name."""
    manifest = load_json(manifest_path, "manifest")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (has: {', '.join(sorted(cells))})")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}.get(cell["config"])
    if entry is None:
        raise SystemExit(f"benchmark: workload {workload!r} names the "
                         f"configuration {cell['config']!r}, which "
                         "BENCHMARK.json does not list")
    config = load_json(os.path.join(ROOT, entry["file"]), "configuration")
    files.layout_of(config)  # a missing layout fails here, by name
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"),
                        "traffic")
    wanted = manifest["per_layer"] if trace else manifest["end_to_end"]
    metrics = []
    for m in wanted:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        spec = load_json(os.path.join(HERE, "metrics", m["name"] + ".json"),
                         "metric")
        metrics.append({**spec, "name": m["name"], "unit": m["unit"]})
    return {"cell": cell, "config": config,
            "config_path": os.path.join(ROOT, entry["file"]),
            "traffic": traffic, "metrics": metrics}


def peaks_of(table: dict, kind: str) -> dict:
    """This device's row of peaks.json. A device that is not in the table
    is an error, not a default."""
    if kind not in table["devices"]:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in "
                         "peaks.json")
    return table["devices"][kind]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """The serving process and what it printed."""

    def __init__(self, argv: list, log_path: str):
        self.lines: list = []
        self.log_path = log_path
        self._log = open(log_path, "w")
        # the persistent compile cache: one fixed directory inside this
        # checkout, whatever the machine's environment names (the program
        # takes the variable and sets no directory of its own)
        env = dict(os.environ,
                   JAX_COMPILATION_CACHE_DIR=os.path.join(OUT, "jax_cache"))
        self.proc = subprocess.Popen(argv, cwd=ROOT, stdout=subprocess.PIPE,
                                     stderr=self._log, text=True, env=env)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                self.lines.append(json.loads(line))
            except ValueError:
                pass  # the program's own banner lines

    def phase(self, name: str):
        return next((r for r in self.lines if r.get("phase") == name), None)

    def stop(self, grace_s: float = 60.0) -> int | None:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(grace_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=5)
        self._log.close()
        return self.proc.returncode

    def log_tail(self, n: int = 4000) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]


def scrape(port: int) -> dict:
    host = "127.0.0.1"
    return {"metrics": loadlib.prometheus(
                loadlib.http_json(host, port, "GET", "/metrics")[1]),
            "perf": loadlib.http_json(host, port, "GET", "/debug/perf")[1],
            "compile": loadlib.http_json(host, port, "GET", "/debug/compile")[1]}


def structural(run: dict, kv: dict) -> dict:
    """What the timed traffic adds to `correct`: nothing about which tokens
    came out, only that the serving path stayed whole. Judged: every request
    of the run that ended before the window closed, born in the ramp or in
    the window (a fault in the ramp spoils the state the window opens on);
    `attempted` adds those still in flight when the window's end cut them."""
    from benchmark.reducers.client_percentile import failed

    t0, t1 = run["t0"], run["t1"]
    ended = [r for r in run["records"] if not r.cut and r.t_end < t1]
    bad_shape = []
    for r in ended:
        if failed(r):
            continue
        streamed = sum(k for _, k in r.events)
        told = (r.timings or {}).get("decode_tokens")
        if (told is None or not 1 <= told <= r.shape.max_tokens
                or streamed > told
                or (r.finish == "length" and told != r.shape.max_tokens)):
            bad_shape.append({"finish": r.finish, "streamed": streamed,
                              "decode_tokens": told,
                              "max_tokens": r.shape.max_tokens})
    b, a = run["before"]["metrics"], run["final"]["metrics"]
    moved = {k: a.get(k, 0.0) - b.get(k, 0.0)
             for k in ("dllama_engine_restarts_total",
                       "dllama_kv_audit_failures_total")}
    audit_ok = bool(isinstance(kv, dict) and (kv.get("audit") or {}).get("ok"))
    thirds = [0, 0, 0]  # is the window stationary? tokens received by third
    for r in run["records"]:
        for t, k in r.events:
            if t0 <= t < t1:
                thirds[min(2, int(3 * (t - t0) / (t1 - t0)))] += k
    return {"attempted": len(run["records"]),
            "failed": sum(failed(r) for r in ended),
            "finished": sum(not failed(r) for r in ended),
            "finished_in_window": sum(not failed(r) and r.t_end >= t0
                                      for r in ended),
            "in_flight_at_window_end": len(run["records"]) - len(ended),
            "tok_s_by_third": [3 * n / (t1 - t0) for n in thirds],
            "bad_finishes": bad_shape[:5], "counters_moved": moved,
            "kv_audit_ok": audit_ok,
            "ok": not bad_shape and not any(moved.values()) and audit_ok}


def boot(cell: dict, seed: int, label: str):
    """Write the files from the seed, start the serving process and wait
    until it has loaded, decided `correct`, built and warmed the engine.
    -> (child, port, device, check record, paths to delete at the end)."""
    port = free_port()
    t = time.monotonic()
    model, tok, size = files.write_files(cell["config"], seed, OUT)
    say(phase="files", seconds=round(time.monotonic() - t, 2), model_bytes=size)
    child = Child([sys.executable, os.path.join(HERE, "serve_child.py"),
                   "--config", cell["config_path"], "--model", model,
                   "--tokenizer", tok, "--seed", str(seed),
                   "--port", str(port), "--chips", str(cell["cell"]["chips"])],
                  os.path.join(OUT, f"serve-{label}.log"))
    try:
        t = time.monotonic()
        while True:  # ready = loaded, checked, engine built and warmed
            if child.proc.poll() is not None:
                sys.stderr.write(child.log_tail() + "\n")
                raise SystemExit(f"benchmark: the serving process exited "
                                 f"{child.proc.returncode} before it was ready")
            if time.monotonic() - t > 1100:
                raise SystemExit("benchmark: not ready after 1100 s")
            if child.phase("check") is not None:
                try:
                    if loadlib.http_json("127.0.0.1", port, "GET",
                                         "/health/ready", timeout=5)[0] == 200:
                        break
                except OSError:
                    pass
            time.sleep(0.25)
        device = {k: child.phase("device")[k]
                  for k in ("platform", "kind", "count")}
        chk = child.phase("check")
        say(phase="load", seconds=(child.phase("load") or {}).get("seconds"))
        # the comparison's numbers, each beside its limit
        say(phase="check", correct=chk["correct"], route=chk["route"],
            expected_route=chk["expected_route"], limits=chk["limits"],
            rel_l2_mean=chk["rel_l2_mean"], rel_l2_max=chk["rel_l2_max"],
            deficit_sigma_mean=chk["deficit_sigma_mean"],
            deficit_sigma_max=chk["deficit_sigma_max"],
            per_prompt=chk["per_prompt"], engine_seconds=chk["engine_seconds"],
            reference_seconds=chk["reference_seconds"])
        health = loadlib.http_json("127.0.0.1", port, "GET", "/health")[1]
        say(phase="ready", seconds=round(time.monotonic() - t, 2),
            kernels=health.get("build", {}).get("kernels"),
            model_params_bytes=health.get("model_params_bytes"),
            kv_cache_bytes=health.get("kv_cache_bytes"))
    except BaseException:
        child.stop(grace_s=20)
        os.remove(model)
        raise
    return child, port, device, chk, [model, model + ".tmp"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another manifest (the CPU rehearsal's)")
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="with --trace 1: copy the .xplane.pb here")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "dllama_tpu")):
        raise SystemExit("benchmark: the program (dllama_tpu/) is not in this "
                         "checkout; the benchmark measures it and nothing else")
    cell = resolve(args.workload, bool(args.trace), args.manifest)
    config, traffic = cell["config"], cell["traffic"]
    generator = importlib.import_module(
        f"benchmark.generators.{traffic['generator']}")
    reducers = {m["name"]: importlib.import_module(
        f"benchmark.reducers.{m['reducer']}") for m in cell["metrics"]}
    peaks_table = load_json(os.path.join(HERE, "peaks.json"), "peaks")

    trace_dir = os.path.join(OUT, f"trace-{args.workload}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    child, port, device, chk, leftovers = boot(cell, args.seed, args.workload)
    try:
        booted = scrape(port)
        say(phase="warmup", report=booted["compile"].get("warmup") or {},
            compiles=booted["metrics"].get("dllama_jit_compiles_total"),
            compile_seconds=booted["metrics"].get(
                "dllama_jit_compile_seconds_total"))

        polls: list = []
        poll_stop = threading.Event()

        def poller():
            while not poll_stop.wait(1.0):
                try:
                    polls.append((time.monotonic(), loadlib.prometheus(
                        loadlib.http_json("127.0.0.1", port, "GET",
                                          "/metrics", timeout=5)[1])))
                except OSError:
                    pass

        def profile_later(t_open: float):
            delay = t_open + float(traffic.get("trace_offset_s", 3.0)) - time.monotonic()
            if poll_stop.wait(max(0.0, delay)):
                return
            status, body = loadlib.http_json(
                "127.0.0.1", port, "POST", "/debug/profile",
                {"dir": trace_dir,
                 "duration_s": float(traffic.get("trace_seconds", 2.0))})
            say(phase="profile", status=status, body=body)

        ramp = float(traffic.get("ramp_seconds", 0.0))
        helpers = []
        if args.trace:
            helpers = [threading.Thread(target=poller, daemon=True),
                       threading.Thread(target=profile_later, daemon=True,
                                        args=(time.monotonic() + ramp,))]
            for h in helpers:
                h.start()
        before, after = {}, {}  # the counters as the window opened and closed
        window = generator.run(host="127.0.0.1", port=port, params=traffic,
                               seed=args.seed, seconds=args.seconds,
                               config=config,
                               at_open=lambda: before.update(scrape(port)),
                               at_close=lambda: after.update(scrape(port)))
        poll_stop.set()
        for h in helpers:
            h.join(timeout=10)
        final = scrape(port)
        kv = loadlib.http_json("127.0.0.1", port, "GET", "/debug/kv")[1]
        run = {**window, "setup_s": window["t0"] - T_START, "before": before,
               "after": after, "final": final,
               "polls": polls, "trace": None, "config": config,
               "traffic": traffic,
               "peaks": lambda: peaks_of(peaks_table, device["kind"])}
        if args.trace:
            pattern = os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb")
            t_wait = time.monotonic() + 120
            while not glob.glob(pattern) and time.monotonic() < t_wait:
                time.sleep(0.5)
        rc = child.stop()
        say(phase="stopped", rc=rc)
        if args.trace:
            found = sorted(glob.glob(pattern))
            if not found:
                raise SystemExit("benchmark: the profiler wrote no trace")
            run["trace"] = trace_reduce.reduce_file(found[-1])
            if args.keep_trace:
                shutil.copyfile(found[-1], args.keep_trace)
            say(phase="trace", file_bytes=os.path.getsize(found[-1]),
                window_s=run["trace"]["window_s"], busy_s=run["trace"]["busy_s"],
                modules={k: [len(v), sum(v)]
                         for k, v in run["trace"]["modules"].items()},
                ops=[[o["module"], o["name"], o["count"], o["seconds"]]
                     for o in run["trace"]["ops"][:30]])
        struct = structural(run, kv)
        say(phase="window", seconds=window["t1"] - window["t0"], **struct,
            compiled_in_window={
                fn: t["compiles"] - before["compile"]["totals"].get(fn, {}).get("compiles", 0)
                for fn, t in after["compile"]["totals"].items()
                if t["compiles"] > before["compile"]["totals"].get(fn, {}).get("compiles", 0)},
            server_exit=rc)
        values = {}
        for m in cell["metrics"]:
            v = reducers[m["name"]].reduce(m.get("params", {}), run)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        mem = (final["compile"].get("device_memory") or {})
        device["memory_peak_bytes"] = mem.get("peak_bytes_in_use")
        result = {"correct": bool(chk["correct"] and struct["ok"] and rc == 0),
                  "attempted": struct["attempted"], "failed": struct["failed"],
                  "metrics": values, "device": device}
        if args.trace:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                                   "idle_gaps": run["trace"]["idle_gaps"]}
        print(json.dumps(result), flush=True)
        # each number compared beside its limit, as the last lines of stderr
        # too: where a run is not correct, that is what the driver keeps
        for k, limit in chk["limits"].items():
            if k in chk:
                sys.stderr.write(f"check {k} = {chk[k]!r} (limit {limit!r})\n")
        sys.stderr.write(f"check route = {chk['route']} (expected "
                         f"{chk['expected_route']}); structure ok = "
                         f"{struct['ok']}; server exit = {rc}; correct = "
                         f"{result['correct']}\n")
        return 0
    finally:
        if child.proc.poll() is None:
            child.stop(grace_s=20)
        for path in leftovers:
            if os.path.exists(path):
                os.remove(path)
        shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
