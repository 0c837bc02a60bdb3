#!/usr/bin/env bash
# trace_smoke.sh — end-to-end request-flow-tracing smoke target.
#
# Boots `python -m dllama_tpu serve` (the real CLI, not an in-process
# server) on a freshly generated tiny fixture model with the default trace
# buffer, waits for /health/ready, runs ONE chat completion, and asserts:
#
#   * the response body carries the `timings` object;
#   * GET /debug/requests/{req_id} replays the request with a prefill
#     record and >= 1 decode chunk (the flight recorder end to end);
#   * GET /debug/trace parses as Chrome trace-event JSON, and some decode
#     `dispatch` span for chunk N+1 STARTS before chunk N's `consume` span
#     ends — the overlapped pipeline (PR 3) made visible as interleaved
#     spans, which is the whole point of the tracer.
#
# Finishes with a SIGTERM drain. This is a SMOKE TARGET, not a pytest test:
# it is exempt from the tier-1 `-m 'not slow'` pytest run (it lives outside
# tests/) and is meant for CI smoke stages or manual runs:
#
#     scripts/trace_smoke.sh
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

tmp = tempfile.mkdtemp(prefix="dllama_tsmoke_")
mpath, tpath, _cfg = make_tiny_files(__import__("pathlib").Path(tmp))

with socket.socket() as s:  # pick a free port
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]

proc = subprocess.Popen(
    [sys.executable, "-m", "dllama_tpu", "serve", "--model", mpath,
     "--tokenizer", tpath, "--slots", "2", "--overlap", "on",
     "--port", str(port), "--log-format", "json"],
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
                             "max_tokens": 16, "temperature": 0.0}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    assert resp.status == 200, f"completion -> {resp.status}"
    rid = body["request_id"]
    timings = body.get("timings")
    assert timings and timings["decode_tokens"] > 0, (
        f"timings object missing/empty: {timings!r}")
    assert timings["e2e_ms"] >= timings["ttft_ms"] > 0

    # ---- flight recorder: the request is replayable post-hoc
    st, raw = get(f"/debug/requests/{rid}")
    assert st == 200, f"/debug/requests/{rid} -> {st}"
    rec = json.loads(raw)
    assert rec["state"] == "finished", rec["state"]
    assert rec["prefill"] and rec["prefill"]["tokens"] > 0, (
        f"no prefill record: {rec.get('prefill')!r}")
    assert len(rec["chunks"]) >= 1, "no decode chunks recorded"
    st, raw = get("/debug/requests")
    assert st == 200 and rid in [r["req_id"] for r in json.loads(raw)["requests"]]

    # ---- Chrome export parses, and the overlap is VISIBLE: a dispatch
    # span for chunk N+1 starts before chunk N's consume span ends
    st, raw = get("/debug/trace")
    assert st == 200, f"/debug/trace -> {st}"
    doc = json.loads(raw)
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert evs, "trace export has no spans"
    disp = {e["args"]["chunk"]: e for e in evs if e["name"] == "decode.dispatch"}
    cons = {e["args"]["chunk"]: e for e in evs if e["name"] == "decode.consume"}
    assert disp and cons, f"decode spans missing (have {sorted({e['name'] for e in evs})})"
    overlapped = [
        k for k, c in cons.items()
        if k + 1 in disp and disp[k + 1]["ts"] < c["ts"] + c["dur"]
    ]
    assert overlapped, (
        "no chunk N+1 dispatch started before chunk N's consume ended — "
        "the overlapped pipeline is not visible in the trace "
        f"(dispatch chunks {sorted(disp)}, consume chunks {sorted(cons)})")

    # ---- every engine launch is one span with the launch record as args
    launches = [e for e in evs if e["name"] == "decode.device"]
    assert launches, "no launch spans"
    need = {"kind", "seq", "n", "active", "starved", "kv_rows", "prefill_rows"}
    assert all(need <= set(e["args"]) for e in launches), launches[0]["args"]
    assert {e["args"]["kind"] for e in launches} & {"decode", "hybrid"}, (
        "no decode launch among the launch spans")

    print(f"PASS: request {rid}: timings {timings}, "
          f"{len(rec['chunks'])} chunks in flight recorder, "
          f"overlap visible on chunk pairs {sorted(overlapped)[:4]} "
          f"({len(evs)} spans exported)")
finally:
    proc.send_signal(signal.SIGTERM)  # exercises the graceful drain path
    try:
        proc.wait(timeout=30)
    except subprocess.TimeoutExpired:
        proc.kill()
PY
