"""Telemetry-core tests: registry semantics, Prometheus text-format grammar
(HELP/TYPE lines, label escaping, histogram _bucket/_sum/_count invariants),
request-id propagation into headers/bodies/logs, and counters moving across
real request lifecycles — admit -> stream -> finish, shed (queue-full via
DLLAMA_FAULTS, draining), and the fault-crash path. All CPU-only against the
tiny fixture model; the HTTP server is module-scoped (load_model dominates)
and the crash drill runs LAST in this file because it kills its worker
(tier-1 runs files in order: -p no:randomly)."""

import http.client
import json
import logging
import re
import threading
import time

import jax
import pytest

from dllama_tpu.obs import metrics, new_request_id
from dllama_tpu.obs import instruments as ins
from dllama_tpu.utils import faults

REG = metrics.REGISTRY


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def val(name, labels=None) -> float:
    """Current value of a series, 0.0 when never touched (delta baselines)."""
    v = REG.sample(name, labels)
    if v is None:
        return 0.0
    return v["count"] if isinstance(v, dict) else v


# ------------------------------------------------------- exposition grammar

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABELS = r'\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\\\|\\"|\\n)*",?)*\}'
_VALUE = r"(?:-?\d+(?:\.\d+)?(?:e[+-]?\d+)?|\+Inf|-Inf|NaN)"
SAMPLE_RE = re.compile(rf"^({_NAME})({_LABELS})? ({_VALUE})$")


def parse_exposition(text: str):
    """Line-by-line grammar check. Returns (families: name->kind,
    samples: (name, labelstr)->value). Any line fitting neither the comment
    nor the sample grammar is an AssertionError — the scraper's contract."""
    assert text.endswith("\n")
    families, samples = {}, {}
    for line in text.splitlines():
        if line.startswith("# HELP "):
            assert re.match(rf"^# HELP {_NAME} \S.*$", line), line
        elif line.startswith("# TYPE "):
            m = re.match(rf"^# TYPE ({_NAME}) (counter|gauge|histogram)$", line)
            assert m, line
            families[m.group(1)] = m.group(2)
        else:
            m = SAMPLE_RE.match(line)
            assert m, f"bad sample line: {line!r}"
            v = m.group(3)
            samples[(m.group(1), m.group(2) or "")] = float(
                v.replace("Inf", "inf"))
    return families, samples


def check_histogram(samples: dict, name: str) -> None:
    """The _bucket/_sum/_count invariants for every label set of `name`:
    cumulative non-decreasing buckets, an le="+Inf" bucket equal to _count,
    and a _sum sample present."""
    by_labels: dict[str, list[tuple[float, float]]] = {}
    for (n, lbl), v in samples.items():
        if n != name + "_bucket":
            continue
        m = re.search(r'le="([^"]+)"', lbl)
        assert m, lbl
        base = re.sub(r',?le="[^"]+"', "", lbl).replace("{}", "")
        by_labels.setdefault(base, []).append(
            (float(m.group(1).replace("Inf", "inf")), v))
    assert by_labels, f"no buckets rendered for {name}"
    for base, buckets in by_labels.items():
        buckets.sort()
        counts = [c for _, c in buckets]
        assert counts == sorted(counts), f"{name}{base}: non-monotone buckets"
        assert buckets[-1][0] == float("inf"), f"{name}{base}: no +Inf bucket"
        count = samples[(name + "_count", base)]
        assert buckets[-1][1] == count, f"{name}{base}: +Inf != _count"
        assert (name + "_sum", base) in samples


# ----------------------------------------------------------- registry unit


def test_counter_gauge_basics():
    reg = metrics.Registry()
    c = reg.counter("t_requests_total", "help", ("reason",))
    c.labels(reason="a").inc()
    c.labels(reason="a").inc(2)
    c.labels(reason="b").inc()
    assert reg.sample("t_requests_total", {"reason": "a"}) == 3
    assert reg.sample("t_requests_total", {"reason": "b"}) == 1
    with pytest.raises(ValueError):
        c.labels(reason="a").inc(-1)  # counters only go up
    g = reg.gauge("t_depth", "help")
    g.set(7)
    g.inc()
    g.dec(3)
    assert reg.sample("t_depth") == 5
    # idempotent re-registration returns the same family; kind conflicts fail
    assert reg.counter("t_requests_total", "help", ("reason",)) is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("t_requests_total", "help", ("reason",))
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("t_requests_total", "help", ("other",))


def test_histogram_buckets_and_render_invariants():
    reg = metrics.Registry()
    h = reg.histogram("t_lat_seconds", "help", ("op",), buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 5.0):  # 0.01 lands IN the 0.01 bucket
        h.labels(op="x").observe(v)
    families, samples = parse_exposition(reg.render())
    assert families["t_lat_seconds"] == "histogram"
    assert samples[("t_lat_seconds_bucket", '{op="x",le="0.01"}')] == 2
    assert samples[("t_lat_seconds_bucket", '{op="x",le="0.1"}')] == 3
    assert samples[("t_lat_seconds_bucket", '{op="x",le="1"}')] == 4
    assert samples[("t_lat_seconds_bucket", '{op="x",le="+Inf"}')] == 5
    assert samples[("t_lat_seconds_count", '{op="x"}')] == 5
    assert samples[("t_lat_seconds_sum", '{op="x"}')] == pytest.approx(5.565)
    check_histogram(samples, "t_lat_seconds")


def test_label_escaping():
    reg = metrics.Registry()
    c = reg.counter("t_esc_total", "multi\nline \\ help", ("what",))
    c.labels(what='we"ird\\val\nue').inc()
    text = reg.render()
    assert '# HELP t_esc_total multi\\nline \\\\ help' in text
    assert 't_esc_total{what="we\\"ird\\\\val\\nue"} 1' in text
    parse_exposition(text)  # escaped line still fits the sample grammar


def test_request_id_minting():
    a, b = new_request_id(), new_request_id()
    assert a.startswith("req_") and b.startswith("req_") and a != b
    # well-formed client ids are adopted verbatim; junk is replaced
    assert new_request_id("trace-41.a_b") == "trace-41.a_b"
    assert new_request_id("bad id\n!").startswith("req_")
    assert new_request_id("x" * 200).startswith("req_")


def test_token_timer_throughput_is_total_time_based():
    from dllama_tpu.utils.profiling import TokenTimer

    t = TokenTimer()
    t.ms.extend([100.0, 300.0])  # mean 200ms -> old (wrong) formula said 5.0
    # ... which coincides here; make the asymmetry explicit instead:
    t.ms.append(200.0)  # total 600ms over 3 tokens -> 5.0 tok/s
    assert "5.0 tok/s" in t.summary() and "3 tokens" in t.summary()
    one = TokenTimer()
    one.ms.append(250.0)  # guard: a single token must not crash percentiles
    assert "1 tokens" in one.summary() and "4.0 tok/s" in one.summary()
    assert TokenTimer().summary() == "no tokens timed"
    zero = TokenTimer()
    zero.ms.extend([0.0, 0.0])  # degenerate clock: no division by zero
    assert "0.0 tok/s" in zero.summary()
    # stop() folds the sample onto the registry (one source of truth)
    before = val("dllama_token_latency_seconds")
    rec = TokenTimer()
    rec.start()
    rec.stop()
    assert val("dllama_token_latency_seconds") == before + 1


def test_json_and_text_log_formatters():
    from dllama_tpu.utils.logs import JsonFormatter, TextFormatter

    rec = logging.LogRecord("dllama_tpu.serve", logging.INFO, __file__, 1,
                            "hello %s", ("world",), None)
    rec.request_id = "req_abc"
    out = json.loads(JsonFormatter().format(rec))
    assert out["msg"] == "hello world" and out["request_id"] == "req_abc"
    assert out["level"] == "INFO" and out["logger"] == "dllama_tpu.serve"
    assert re.match(r"^\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}\.\d{3}Z$", out["ts"])
    assert "request_id=req_abc" in TextFormatter("%(message)s").format(rec)


# ------------------------------------------------------- HTTP end-to-end


@pytest.fixture(scope="module")
def mserver(tmp_path_factory):
    """Continuous-batching server for telemetry drills (module-scoped:
    load_model dominates). Warm-up completion compiles every step shape so
    the timed tests below measure telemetry, not XLA."""
    from dllama_tpu.engine.loader import load_model
    from dllama_tpu.serve.api import make_server
    from tests.test_serve import make_tiny_files, post

    tmp_path = tmp_path_factory.mktemp("mserve")
    mpath, tpath, _cfg = make_tiny_files(tmp_path)
    loaded = load_model(mpath, tpath, mesh=None)
    httpd, api = make_server(loaded, host="127.0.0.1", port=0, n_slots=2,
                             max_queue=4,
                             # loose SLO targets (CPU box): the /debug/perf
                             # and postmortem-slo drills below want armed,
                             # attainable targets — not real latency bars
                             slo_ttft_ms=120_000.0, slo_itl_ms=120_000.0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    st, _ = post(httpd.server_address[1], "/v1/chat/completions",
                 {"messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 6, "temperature": 0.0})
    assert st == 200
    yield httpd.server_address[1], api, httpd
    api.scheduler.shutdown()
    httpd.shutdown()


def _get_raw(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    headers = dict(resp.getheaders())
    conn.close()
    return resp.status, data, headers


def _post_raw(port, path, body, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    conn.request("POST", path, json.dumps(body), h)
    resp = conn.getresponse()
    data = resp.read()
    rheaders = dict(resp.getheaders())
    conn.close()
    return resp.status, data, rheaders


def test_metrics_endpoint_serves_valid_exposition(mserver):
    port, _api, _ = mserver
    st, data, headers = _get_raw(port, "/metrics")
    assert st == 200
    assert headers["Content-Type"].startswith("text/plain; version=0.0.4")
    families, samples = parse_exposition(data.decode())
    for name, kind in [
        ("dllama_requests_admitted_total", "counter"),
        ("dllama_requests_finished_total", "counter"),
        ("dllama_tokens_generated_total", "counter"),
        ("dllama_queue_depth", "gauge"),
        ("dllama_busy_slots", "gauge"),
        ("dllama_slots_total", "gauge"),
        ("dllama_model_params_bytes", "gauge"),
        ("dllama_kv_cache_bytes", "gauge"),
        ("dllama_ttft_seconds", "histogram"),
        ("dllama_itl_seconds", "histogram"),
        ("dllama_decode_chunk_seconds", "histogram"),
        ("dllama_prefill_chunk_seconds", "histogram"),
    ]:
        assert families.get(name) == kind, f"{name} missing or mistyped"
    # the warm-up completion already ran: histograms carry real samples
    for h in ("dllama_ttft_seconds", "dllama_decode_chunk_seconds",
              "dllama_prefill_chunk_seconds", "dllama_e2e_latency_seconds",
              "dllama_batch_occupancy"):
        check_histogram(samples, h)
    assert samples[("dllama_slots_total", "")] == 2


def test_request_lifecycle_moves_counters(mserver):
    from tests.test_serve import post

    port, _api, _ = mserver
    before = {
        "admitted": val("dllama_requests_admitted_total"),
        "stop": val("dllama_requests_finished_total", {"reason": "stop"}),
        "length": val("dllama_requests_finished_total", {"reason": "length"}),
        "tokens": val("dllama_tokens_generated_total"),
        "ttft": val("dllama_ttft_seconds"),
        "e2e": val("dllama_e2e_latency_seconds"),
        "http": val("dllama_http_responses_total",
                    {"endpoint": "/v1/chat/completions", "code": "200"}),
    }
    st, data = post(port, "/v1/chat/completions",
                    {"messages": [{"role": "user", "content": "count me"}],
                     "max_tokens": 8, "temperature": 0.0})
    assert st == 200
    done = json.loads(data)["usage"]["completion_tokens"]
    assert val("dllama_requests_admitted_total") == before["admitted"] + 1
    finished = (val("dllama_requests_finished_total", {"reason": "stop"})
                + val("dllama_requests_finished_total", {"reason": "length"}))
    assert finished == before["stop"] + before["length"] + 1
    assert val("dllama_tokens_generated_total") >= before["tokens"] + done
    assert val("dllama_ttft_seconds") == before["ttft"] + 1
    assert val("dllama_e2e_latency_seconds") == before["e2e"] + 1
    assert val("dllama_http_responses_total",
               {"endpoint": "/v1/chat/completions", "code": "200"}) == before["http"] + 1


def test_queue_full_shed_counts_and_correlates(mserver, monkeypatch, caplog):
    """The DLLAMA_FAULTS-armed shed path: 429 carries the would-have-been
    X-Request-Id, the shed counter moves by reason, and the shed log line
    carries the same id (structured field + message text)."""
    port, _api, _ = mserver
    monkeypatch.setenv(faults.ENV_VAR, "scheduler.queue:raise:times=1")
    faults.configure_from_env()
    before = val("dllama_requests_shed_total", {"reason": "queue_full"})
    before_fires = val("dllama_fault_fires_total",
                       {"point": "scheduler.queue", "action": "raise"})
    with caplog.at_level(logging.WARNING, logger="dllama_tpu.serve"):
        st, data, headers = _post_raw(
            port, "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "x"}], "max_tokens": 4})
    assert st == 429
    rid = headers.get("X-Request-Id")
    assert rid and rid.startswith("req_")
    assert json.loads(data)["error"]["request_id"] == rid
    assert val("dllama_requests_shed_total", {"reason": "queue_full"}) == before + 1
    assert val("dllama_fault_fires_total",
               {"point": "scheduler.queue", "action": "raise"}) == before_fires + 1
    shed_logs = [r for r in caplog.records
                 if getattr(r, "request_id", None) == rid]
    assert shed_logs and "shed" in shed_logs[0].getMessage()


def test_draining_shed_counts_by_reason(mserver):
    port, api, _ = mserver
    before = val("dllama_requests_shed_total", {"reason": "draining"})
    api.draining = True
    try:
        st, data, headers = _post_raw(
            port, "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "x"}], "max_tokens": 2})
    finally:
        api.draining = False
    assert st == 503
    assert headers.get("X-Request-Id", "").startswith("req_")
    assert val("dllama_requests_shed_total", {"reason": "draining"}) == before + 1


def test_request_id_propagation_and_logs(mserver, caplog):
    from tests.test_serve import post

    port, _api, _ = mserver
    # server-minted id: header + response JSON + completion log line agree
    with caplog.at_level(logging.INFO, logger="dllama_tpu.serve"):
        st, data, headers = _post_raw(
            port, "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}],
             "max_tokens": 4, "temperature": 0.0})
    assert st == 200
    rid = headers["X-Request-Id"]
    assert rid.startswith("req_")
    assert json.loads(data)["request_id"] == rid
    assert any(getattr(r, "request_id", None) == rid for r in caplog.records)
    # client-supplied well-formed id is adopted verbatim
    st2, data2, headers2 = _post_raw(
        port, "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "hi"}], "max_tokens": 2},
        headers={"X-Request-Id": "trace-77.abc"})
    assert st2 == 200 and headers2["X-Request-Id"] == "trace-77.abc"
    assert json.loads(data2)["request_id"] == "trace-77.abc"
    # 400s carry an id too
    st3, data3, headers3 = _post_raw(port, "/v1/chat/completions",
                                     {"messages": []})
    assert st3 == 400 and headers3.get("X-Request-Id", "").startswith("req_")
    assert json.loads(data3)["error"]["request_id"] == headers3["X-Request-Id"]


def test_stream_carries_request_id(mserver):
    port, _api, _ = mserver
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/chat/completions",
                 json.dumps({"messages": [{"role": "user", "content": "hi"}],
                             "max_tokens": 4, "temperature": 0.0,
                             "stream": True}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    raw = resp.read().decode()
    rid = resp.getheader("X-Request-Id")
    conn.close()
    assert resp.status == 200 and rid and rid.startswith("req_")
    assert "data: [DONE]" in raw


def test_health_and_metrics_expose_memory_gauges(mserver):
    port, api, _ = mserver
    st, data, _ = _get_raw(port, "/health")
    body = json.loads(data)
    assert body["model_params_bytes"] > 0
    assert body["kv_cache_bytes"] > 0
    assert val("dllama_model_params_bytes") == body["model_params_bytes"]
    assert val("dllama_kv_cache_bytes") == body["kv_cache_bytes"]
    assert body["model_params_bytes"] == api.model_params_bytes


def test_metrics_scrape_concurrent_with_generation(mserver):
    """/metrics must answer (and parse) while a completion is decoding —
    the scrape path shares no lock with the worker."""
    from tests.test_serve import post

    port, api, _ = mserver
    faults.install("engine.decode", "delay", ms=30.0)
    results = {}

    def run():
        results["resp"] = post(
            port, "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "busy"}],
             "max_tokens": 24, "temperature": 0.0})

    t = threading.Thread(target=run)
    t.start()
    try:
        deadline = time.monotonic() + 5.0
        while not api.scheduler._busy() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert api.scheduler._busy(), "completion never started"
        for _ in range(3):  # repeated scrapes while tokens are flowing
            st, data, _ = _get_raw(port, "/metrics")
            assert st == 200
            parse_exposition(data.decode())
    finally:
        faults.clear()
        t.join(timeout=60)
    assert results["resp"][0] == 200


def test_build_info_gauge_and_health_build(mserver):
    """dllama_tpu_build_info: value 1, labels carry version/jax/backend/
    overlap; the same payload rides /health as the `build` object. The
    registry is process-global, so an earlier test's single-tier server may
    have registered an overlap="n/a" series too — match THIS server's
    labelset (from /health) rather than whichever series scrapes first."""
    port, _api, _ = mserver
    st, data, _ = _get_raw(port, "/health")
    assert st == 200
    build = json.loads(data)["build"]
    assert build["overlap"] == "on"  # mserver runs the default pipeline
    assert build["backend"] == "cpu" and build["version"] and build["jax"]
    # the device as jax reports it + the resolved kernel route (what
    # chip_smoke.py reads for its last line and its route assertion)
    assert build["device_kind"] == jax.devices()[0].device_kind
    assert build["device_count"] == str(len(jax.devices()))
    assert build["kernels"] == _api.scheduler.engine.kernel_route
    st, data, _ = _get_raw(port, "/metrics")
    assert st == 200
    found = None
    for m in re.finditer(r'^dllama_tpu_build_info\{([^}]*)\} 1$',
                         data.decode(), re.M):
        labels = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1)))
        if labels == build:
            found = labels
    assert found == build, "no build_info series matches /health build"


def test_timings_object_and_flight_recorder(mserver):
    """Non-stream responses carry a span-sourced `timings` object; the same
    request is replayable from GET /debug/requests/{req_id} with prefill
    and per-chunk detail (the flight recorder)."""
    port, _api, _ = mserver
    st, data, _ = _post_raw(
        port, "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "hello there"}],
         "max_tokens": 9, "temperature": 0.0})
    assert st == 200
    body = json.loads(data)
    rid = body["request_id"]
    t = body["timings"]
    # `replica` rides along since ISSUE 15: every response is
    # attributable end to end through the router
    assert set(t) == {"queue_wait_ms", "ttft_ms", "e2e_ms",
                      "decode_tokens", "replica"}
    assert t["decode_tokens"] == body["usage"]["completion_tokens"]
    assert t["e2e_ms"] >= t["ttft_ms"] >= t["queue_wait_ms"] >= 0

    st, data, _ = _get_raw(port, f"/debug/requests/{rid}")
    assert st == 200
    rec = json.loads(data)
    assert rec["state"] == "finished"
    assert rec["finish_reason"] in ("stop", "length")
    assert rec["prompt_tokens"] > 0
    assert rec["prefill"]["tokens"] == rec["prompt_tokens"]
    assert len(rec["chunks"]) >= 1  # at least one fused decode chunk
    assert sum(c["tokens"] for c in rec["chunks"]) >= t["decode_tokens"] - 1
    assert rec["ttft_ms"] == pytest.approx(t["ttft_ms"], abs=1.0)

    st, data, _ = _get_raw(port, "/debug/requests")
    ids = [r["req_id"] for r in json.loads(data)["requests"]]
    assert rid in ids

    st, data, _ = _get_raw(port, "/debug/requests/req_nonexistent")
    assert st == 404


def test_stream_final_event_carries_timings(mserver):
    """The last SSE data event (finish_reason set) carries the same
    `timings` object non-stream responses embed."""
    port, _api, _ = mserver
    st, data, _ = _post_raw(
        port, "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "hi"}],
         "max_tokens": 6, "temperature": 0.0, "stream": True})
    assert st == 200
    payloads = [json.loads(line[len("data: "):])
                for line in data.decode().splitlines()
                if line.startswith("data: ") and line != "data: [DONE]"]
    final = [p for p in payloads
             if p.get("choices") and p["choices"][0].get("finish_reason")]
    assert final, "no finish event in the stream"
    t = final[-1]["timings"]
    # `replica` rides along since ISSUE 15: every response is
    # attributable end to end through the router
    assert set(t) == {"queue_wait_ms", "ttft_ms", "e2e_ms",
                      "decode_tokens", "replica"}
    assert t["decode_tokens"] >= 1


def test_debug_trace_exports_chrome_json_and_skips_admission_counters(mserver):
    """/debug/trace is loadable Chrome trace JSON whose decode spans expose
    the pipeline; /debug/* GETs never move the request-admission counters
    (they are observability reads, not requests)."""
    port, _api, _ = mserver
    # a fresh completion guarantees recent decode spans in the ring
    st, _, _ = _post_raw(
        port, "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "hi"}],
         "max_tokens": 8, "temperature": 0.0})
    assert st == 200
    admitted = val("dllama_requests_admitted_total")
    st, data, _ = _get_raw(port, "/debug/trace")
    assert st == 200
    doc = json.loads(data)
    evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in evs}
    assert {"decode.dispatch", "decode.consume", "decode.device",
            "prefill.chunk", "queue.wait", "request"} <= names
    # non-decreasing ts per track (the Perfetto-load contract)
    by_tid = {}
    for e in doc["traceEvents"]:
        if e.get("ph") in ("X", "i"):
            by_tid.setdefault(e["tid"], []).append(e["ts"])
    for tid, ts in by_tid.items():
        assert ts == sorted(ts)
    st, _, _ = _get_raw(port, "/debug/requests")
    assert st == 200
    assert val("dllama_requests_admitted_total") == admitted
    # the responses themselves ARE counted (http observability keeps working)
    assert val("dllama_http_responses_total",
               {"endpoint": "/debug/trace", "code": "200"}) >= 1


def test_debug_profile_starts_and_conflicts_409(mserver, tmp_path, monkeypatch):
    """POST /debug/profile starts a duration-capped capture; a second POST
    while one runs is 409 + Retry-After; the slot frees after the timer.
    The jax profiler itself is stubbed — the HTTP/session contract is what
    this test pins (the real capture is exercised by the E2E smoke)."""
    from dllama_tpu.utils import profiling

    monkeypatch.setattr(profiling.jax.profiler, "start_trace",
                        lambda log_dir, **kw: None)
    monkeypatch.setattr(profiling.jax.profiler, "stop_trace", lambda: None)
    port, _api, _ = mserver
    st, data, _ = _post_raw(port, "/debug/profile",
                            {"duration_s": 0.3, "dir": str(tmp_path / "p")})
    assert st == 200
    info = json.loads(data)["profiling"]
    assert info["duration_s"] == pytest.approx(0.3)
    assert info["dir"] == str(tmp_path / "p")
    st, data, headers = _post_raw(port, "/debug/profile", {"duration_s": 0.3})
    assert st == 409
    assert "Retry-After" in headers
    assert "already running" in json.loads(data)["error"]["message"]
    deadline = time.time() + 10
    while profiling.profile_status()["active"] and time.time() < deadline:
        time.sleep(0.02)
    assert not profiling.profile_status()["active"]
    # the session is reusable once the timer released it
    st, data, _ = _post_raw(port, "/debug/profile",
                            {"duration_s": 0.05, "dir": str(tmp_path / "p2")})
    assert st == 200
    deadline = time.time() + 10
    while profiling.profile_status()["active"] and time.time() < deadline:
        time.sleep(0.02)
    # malformed duration is a client error, not a hung session
    st, data, _ = _post_raw(port, "/debug/profile", {"duration_s": "soon"})
    assert st == 400


def test_debug_perf_capture_block_counts_the_captures_launches(
        mserver, tmp_path, monkeypatch):
    """ISSUE 26: /debug/perf's `capture` block is what the engine launched
    between the begin and the end of the last FINISHED profiler capture:
    counter deltas, by kind and by slot-step state (the profiler itself is
    stubbed, as above)."""
    from dllama_tpu.utils import profiling

    monkeypatch.setattr(profiling.jax.profiler, "start_trace",
                        lambda log_dir, **kw: None)
    monkeypatch.setattr(profiling.jax.profiler, "stop_trace", lambda: None)
    port, _api, _ = mserver
    launched = val("dllama_launches_total", {"kind": "decode"}) or 0.0
    st, _, _ = _post_raw(port, "/debug/profile",
                         {"duration_s": 30, "dir": str(tmp_path / "p")})
    assert st == 200
    try:
        st, _, _ = _post_raw(
            port, "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "capture"}],
             "max_tokens": 6, "temperature": 0.0})
        assert st == 200
    finally:
        profiling._profiler_end()  # the timer's call, early
    during = val("dllama_launches_total", {"kind": "decode"}) - launched
    st, data, _ = _get_raw(port, "/debug/perf")
    assert st == 200
    cap = json.loads(data)["capture"]
    assert cap == profiling.last_capture()
    assert set(cap) == {"launches", "sampler_launches", "slot_steps",
                        "kv_rows", "kv_rows_moved", "prefill_rows",
                        "kv_rows_read", "moe_assignments",
                        "moe_experts_touched", "moe_layer_steps",
                        "moe_group_rows_max", "window_pages_released",
                        "page_topups", "sched_seconds", "phase_seconds",
                        "phases", "drains", "launch_waits", "host_gap",
                        "seconds"}
    assert cap["launches"]["decode"] == during >= 1
    # every launch that samples is counted under the body its slots ask for
    assert sum(cap["sampler_launches"].values()) == sum(
        n for kind, n in cap["launches"].items() if kind != "prefill_chunk")
    assert cap["slot_steps"]["advanced"] >= 5  # 6 tokens, the first at commit
    assert cap["kv_rows"]["decode"] > 0
    assert sum(cap["prefill_rows"].values()) > 0
    assert cap["seconds"] > 0


def test_debug_perf_joins_windows_ledger_roofline(mserver):
    """GET /debug/perf (ISSUE 7): after at least one served request the
    join must show a populated TTFT window with p50/p95/p99, a ledger whose
    per-state seconds partition loop wall time (within 2%), the
    throughput/goodput rates, SLO accounting against the armed
    targets, and the process self-metrics — one JSON document, no tracer
    dependency."""
    port, _api, _ = mserver
    st, data, _ = _post_raw(port, "/v1/chat/completions",
                            {"messages": [{"role": "user", "content": "perf"}],
                             "max_tokens": 6, "temperature": 0.0})
    assert st == 200
    st, data, _ = _get_raw(port, "/debug/perf")
    assert st == 200
    doc = json.loads(data)
    assert doc["mode"] == "continuous"
    win = doc["window"]["ttft"]
    assert win["count"] >= 1
    assert win["p50"] is not None and win["p95"] is not None
    assert win["p99"] >= win["p50"] > 0
    led = doc["ledger"]
    assert led["wall_s"] > 0
    assert abs(led["covered_s"] - led["wall_s"]) / led["wall_s"] <= 0.02
    from dllama_tpu.obs import perf as _perf

    # the catalog is the definition site (scripts/checks.sh pins it to the
    # README table); this endpoint must expose exactly those states
    assert set(led["fractions"]) == set(_perf.LEDGER_STATES)
    assert led["seconds"]["decode_wait"] > 0  # decode actually ran
    roof = doc["roofline"]
    # the request met its 2-minute targets: all of its tokens are goodput
    assert roof["goodput_tok_s"] == roof["throughput_tok_s"] > 0
    slo = doc["slo"]
    assert slo["enabled"] and slo["targets"]["ttft_ms"] == 120_000.0
    assert slo["attainment"] == 1.0  # targets are 2 minutes on purpose
    proc = doc["process"]
    assert proc["uptime_s"] > 0 and proc["threads"] >= 2
    # the same views land on /metrics as gauges at scrape time
    st, text, _ = _get_raw(port, "/metrics")
    fams, samples = parse_exposition(text.decode())
    assert samples[("dllama_latency_window_seconds",
                    '{metric="ttft",quantile="p50"}')] > 0
    assert ("dllama_scheduler_time_seconds_total",
            '{state="decode_wait"}') in samples
    assert samples[("dllama_slo_attainment", "")] == 1.0
    assert samples[("dllama_process_uptime_seconds", "")] > 0
    assert samples[("dllama_process_rss_bytes", "")] > 0


def test_health_carries_process_self_metrics(mserver):
    port, _api, _ = mserver
    st, data, _ = _get_raw(port, "/health")
    assert st == 200
    proc = json.loads(data)["process"]
    assert proc["uptime_s"] > 0
    assert proc["rss_bytes"] > 0
    assert proc["threads"] >= 2  # worker + this handler at minimum


def test_debug_compile_ledger_transfers_and_health_object(mserver):
    """GET /debug/compile (ISSUE 13): after served traffic the document
    carries the jit ledger (per-fn totals + entries with shape sigs),
    shape-bucket contract coverage, transfer tallies (boundary uploads +
    per-chunk downloads), and live device memory; /health answers the
    compile object (recompile storms visible without a scrape) and the
    dllama_jit_* / dllama_transfer* series render on /metrics."""
    port, _api, _ = mserver
    st, data, _ = _post_raw(port, "/v1/chat/completions",
                            {"messages": [{"role": "user", "content": "jit"}],
                             "max_tokens": 6, "temperature": 0.0})
    assert st == 200
    st, data, _ = _get_raw(port, "/debug/compile")
    assert st == 200
    doc = json.loads(data)
    tot = doc["totals"]
    # the serving flow really billed its dispatch sites
    assert tot["prefill_chunk"]["compiles"] >= 1
    assert tot["decode"]["compiles"] >= 1
    assert tot["commit"]["compiles"] >= 1
    assert doc["unexpected"] == 0
    assert any(e["fn"] == "decode" and e["sig"] for e in doc["entries"])
    cov = doc["contract"]["fns"]
    assert "decode" in cov and cov["decode"]["unexpected_seen"] == []
    tr = doc["transfers"]
    assert tr["sites"]["h2d.prefill"]["bytes"] > 0  # admission uploads
    assert tr["sites"]["d2h.decode_tokens"]["bytes"] > 0  # token fetches
    assert doc["device_memory"]["buffers"] > 0
    assert doc["warmup"] is None  # mserver boots --warmup off
    # /health: the compile object rides the probe
    st, data, _ = _get_raw(port, "/health")
    h = json.loads(data)
    assert h["compile"]["unexpected_compiles"] == 0
    assert h["compile"]["compiles"] >= 1
    assert h["compile"]["warmup"] == "off"
    assert h["build"]["warmup"] == "off"
    # ... and /debug/perf folds the summary
    st, data, _ = _get_raw(port, "/debug/perf")
    assert json.loads(data)["compile"]["unexpected"] == 0
    # the series render in the exposition
    st, text, _ = _get_raw(port, "/metrics")
    fams, samples = parse_exposition(text.decode())
    assert fams["dllama_jit_compiles_total"] == "counter"
    assert fams["dllama_jit_unexpected_compiles_total"] == "counter"
    assert samples[("dllama_jit_compiles_total", '{fn="decode"}')] >= 1
    assert samples[("dllama_transfer_bytes_total",
                    '{direction="d2h",site="decode_tokens"}')] > 0
    assert samples[("dllama_device_live_buffers", "")] > 0
    assert samples[("dllama_device_live_bytes", "")] > 0


def test_postmortem_gains_slo_verdict(mserver):
    """/debug/requests/{req_id} postmortems judge the request's recorded
    marks against the configured SLOs: ttft_ok/itl_ok plus violated_by_ms,
    derived from the flight recorder's own ttft/e2e/decode_tokens."""
    port, _api, _ = mserver
    rid = new_request_id()
    st, _data, _ = _post_raw(port, "/v1/chat/completions",
                             {"messages": [{"role": "user", "content": "slo"}],
                              "max_tokens": 6, "temperature": 0.0},
                             headers={"X-Request-Id": rid})
    assert st == 200
    st, data, _ = _get_raw(port, f"/debug/requests/{rid}")
    assert st == 200
    doc = json.loads(data)
    v = doc["slo"]
    assert v["targets"] == {"ttft_ms": 120_000.0, "itl_ms": 120_000.0}
    assert v["ttft_ok"] is True  # a CPU tiny-model decode beats 2 minutes
    assert v["ok"] is True
    assert v["violated_by_ms"] == {"ttft": None, "itl": None}
    assert v["itl_ms"] == pytest.approx(  # display-rounded to 3 places
        (doc["e2e_ms"] - doc["ttft_ms"]) / (doc["decode_tokens"] - 1),
        abs=1e-3)


def test_crash_path_marks_error_and_counts_fault_fires(mserver):
    """Worker-crash telemetry: finished{reason=error} and
    fault_fires{engine.decode} advance, and /metrics still answers on a dead
    scheduler. Runs LAST against this server (the crash is terminal)."""
    port, api, _ = mserver
    before_err = val("dllama_requests_finished_total", {"reason": "error"})
    before_fires = val("dllama_fault_fires_total",
                       {"point": "engine.decode", "action": "raise"})
    faults.install("engine.decode", "raise")
    st, data, headers = _post_raw(
        port, "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "boom"}], "max_tokens": 8})
    faults.clear()
    assert st == 500
    assert headers.get("X-Request-Id", "").startswith("req_")
    assert json.loads(data)["error"]["request_id"] == headers["X-Request-Id"]
    assert val("dllama_requests_finished_total",
               {"reason": "error"}) >= before_err + 1
    assert val("dllama_fault_fires_total",
               {"point": "engine.decode", "action": "raise"}) == before_fires + 1
    st_h, data_h, _ = _get_raw(port, "/health")
    assert st_h == 503
    st_m, data_m, _ = _get_raw(port, "/metrics")  # scrapes outlive the worker
    assert st_m == 200
    parse_exposition(data_m.decode())
