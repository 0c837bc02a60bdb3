#!/usr/bin/env bash
# slo_smoke.sh — end-to-end SLO & saturation observability smoke (ISSUE 7).
#
# Boots `python -m dllama_tpu serve` (the real CLI, not an in-process
# server) on a freshly generated tiny fixture model with SLO targets armed
# (--slo-ttft-ms / --slo-itl-ms, loose enough for a CPU box), waits for
# /health/ready, runs ONE chat completion, then asserts GET /debug/perf
# shows the whole join populated:
#
#   * a TTFT window with count >= 1 and non-null p50/p95/p99,
#   * scheduler time-ledger totals that are nonzero AND partition loop
#     wall time (covered ≈ wall within 2%),
#   * the windowed token rates (goodput = throughput > 0: the one request
#     met its targets),
#   * SLO accounting against the armed targets (attainment = 1.0),
#   * process self-metrics (uptime/RSS/threads) here and on /health.
#
# This is a SMOKE TARGET, not a pytest test: exempt from the tier-1
# `-m 'not slow'` run (it lives outside tests/), meant for CI smoke stages
# or manual runs:
#
#     scripts/slo_smoke.sh
#
# CPU-only, no model download, ~1 min (XLA compile dominates). Exit 0 = PASS.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python - <<'PY'
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())
from tests.test_serve import make_tiny_files  # the tier-1 fixture model

tmp = tempfile.mkdtemp(prefix="dllama_slo_smoke_")
mpath, tpath, _cfg = make_tiny_files(__import__("pathlib").Path(tmp))

with socket.socket() as s:  # pick a free port
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]

proc = subprocess.Popen(
    [sys.executable, "-m", "dllama_tpu", "serve", "--model", mpath,
     "--tokenizer", tpath, "--slots", "2", "--port", str(port),
     "--slo-ttft-ms", "120000", "--slo-itl-ms", "120000",
     "--log-format", "json"],
    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
)


def get(path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read().decode()
    conn.close()
    return r.status, body


try:
    deadline = time.time() + 120  # first-boot XLA compiles on CPU are slow
    while True:
        try:
            if get("/health/ready")[0] == 200:
                break
        except OSError:
            pass
        if proc.poll() is not None:
            sys.exit("FAIL: server exited before becoming ready")
        if time.time() > deadline:
            sys.exit("FAIL: server never became ready")
        time.sleep(0.25)

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/chat/completions",
                 json.dumps({"messages": [{"role": "user", "content": "hi"}],
                             "max_tokens": 8, "temperature": 0.0}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    assert resp.status == 200, f"completion -> {resp.status}"
    assert body["usage"]["completion_tokens"] > 0

    st, text = get("/debug/perf")
    assert st == 200, f"/debug/perf -> {st}"
    doc = json.loads(text)
    assert doc["mode"] == "continuous", doc.get("mode")

    win = doc["window"]["ttft"]
    assert win["count"] >= 1, f"empty TTFT window: {win}"
    for p in ("p50", "p95", "p99"):
        assert win[p] is not None and win[p] > 0, f"TTFT {p} missing: {win}"

    led = doc["ledger"]
    covered, wall = led["covered_s"], led["wall_s"]
    assert wall > 0 and covered > 0, led
    resid = abs(covered - wall) / wall
    assert resid <= 0.02, f"ledger partition broken: covered={covered} wall={wall}"
    assert led["seconds"]["decode_wait"] > 0, "no decode time attributed"
    assert led["seconds"]["prefill"] > 0, "no prefill time attributed"

    roof = doc["roofline"]
    # the one request met its 2-minute targets: all its tokens are goodput
    assert roof["goodput_tok_s"] == roof["throughput_tok_s"] > 0, roof

    slo = doc["slo"]
    assert slo["enabled"] and slo["targets"]["ttft_ms"] == 120000.0, slo
    assert slo["attainment"] == 1.0, f"tiny greedy request missed a 2-min SLO? {slo}"

    proc_m = doc["process"]
    assert proc_m["uptime_s"] > 0 and proc_m["threads"] >= 2, proc_m
    st, htext = get("/health")
    assert st == 200 and json.loads(htext)["process"]["rss_bytes"] > 0

    print(f"PASS: /debug/perf joined — ttft window n={win['count']} "
          f"p50={win['p50']}ms, ledger residual {resid:.4%} "
          f"(decode_wait {led['seconds']['decode_wait']:.3f}s of "
          f"{wall:.3f}s wall), goodput {roof['goodput_tok_s']} tok/s, "
          f"slo attainment={slo['attainment']}")
finally:
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
PY
