"""Multi-replica router tests (ISSUE 15): affinity, least-loaded fallback,
failover (mid-queue reroute, mid-stream clean error), drain redirection,
all-saturated shedding — against controllable stub replicas for precise
failure timing, plus one end-to-end test over two REAL engine replicas.
"""

import http.client
import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from dllama_tpu.obs import instruments as ins


# --------------------------------------------------------------------------
# stub replicas: the full surface the router consumes (/health, /v1/models,
# completions stream + non-stream), with scripted failure modes
# --------------------------------------------------------------------------

class StubState:
    def __init__(self, rid, model="stub-model", version="1.0"):
        self.rid = rid
        self.model = model
        self.version = version
        self.ready = True
        self.draining = False
        self.saturated = False      # completions answer 429 + Retry-After
        self.abort_after = None     # stream: emit N events, then cut the socket
        self.ntokens = 3
        self.stream_delay = 0.0     # seconds between stream events
        self.resume_overlap = 0     # resume: re-emit N already-journaled
        #                             frames (drills the dedup seam)
        self.served = []            # parsed bodies, in arrival order
        # mesh observability surface (ISSUE 17)
        self.clock_skew = 0.0       # seconds added to the reported clock
        self.trace_epoch = None     # /health clock.trace_epoch_s
        self.metrics_text = None    # /metrics body (None = tiny default)
        self.trace_export = None    # /debug/trace payload (None = 404)
        self.timelines = {}         # req_id -> /debug/requests/{id} payload
        self.header_log = []        # inbound POST headers, lower-cased keys
        self.lock = threading.Lock()


def make_stub(state: StubState):
    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):
            pass

        def _json(self, status, payload, headers=None):
            data = json.dumps(payload).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.send_header("X-Replica-Id", state.rid)
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            if self.path.startswith("/health"):
                self._json(200, {
                    "live": True,
                    "ready": state.ready and not state.draining,
                    "draining": state.draining,
                    "queue_depth": 0, "busy_slots": 0,
                    "build": {"version": state.version},
                    "clock": {
                        "monotonic_s": time.monotonic() + state.clock_skew,
                        "trace_epoch_s": state.trace_epoch,
                    },
                })
            elif self.path == "/v1/models":
                self._json(200, {"object": "list",
                                 "data": [{"id": state.model}]})
            elif self.path == "/metrics":
                text = state.metrics_text or (
                    "# HELP dllama_stub_requests_total bodies served\n"
                    "# TYPE dllama_stub_requests_total counter\n"
                    f"dllama_stub_requests_total {len(state.served)}\n")
                data = text.encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
            elif self.path == "/debug/trace":
                if state.trace_export is None:
                    self._json(404, {"error": {"message": "tracing off"}})
                else:
                    self._json(200, state.trace_export)
            elif self.path.startswith("/debug/requests/"):
                tl = state.timelines.get(self.path.rsplit("/", 1)[1])
                if tl is None:
                    self._json(404, {"error": {"message": "unknown"}})
                else:
                    self._json(200, tl)
            else:
                self._json(404, {"error": {"message": "nope"}})

        def do_POST(self):
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            with state.lock:
                state.served.append(body)
                state.header_log.append(
                    {k.lower(): v for k, v in self.headers.items()})
            if state.saturated:
                self._json(429, {"error": {"message": "queue full"}},
                           {"Retry-After": "3"})
                return
            if body.get("stream"):
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Transfer-Encoding", "chunked")
                self.send_header("X-Replica-Id", state.rid)
                self.end_headers()

                def chunk(p: bytes):
                    self.wfile.write(f"{len(p):x}\r\n".encode() + p + b"\r\n")
                    self.wfile.flush()

                # the stream contract a real replica honors (ISSUE 16): the
                # i-th token of THIS stream is deterministic (100+i here —
                # the stub's stand-in for greedy decode), `resume` re-enters
                # at len(resume.tokens), identity (id/created) comes from
                # the resume body when present, and frames carry
                # position/token_ids when `include_token_ids` asks for them
                resume = body.get("resume") or {}
                start = len(resume.get("tokens") or [])
                if start and state.resume_overlap:
                    # a sloppy survivor replaying frames the client already
                    # has — the ROUTER's journal must suppress these
                    start = max(0, start - state.resume_overlap)
                want_ids = bool(body.get("include_token_ids"))
                cid = resume.get("id") or f"chatcmpl-{state.rid}"
                emitted = 0
                for i in range(start, state.ntokens):
                    if state.stream_delay:
                        time.sleep(state.stream_delay)
                    if state.abort_after is not None \
                            and emitted >= state.abort_after:
                        # mid-stream death: cut the connection, no [DONE].
                        # shutdown() (not close()) — rfile/wfile still hold
                        # fd refs, so close() alone would defer the FIN
                        self.connection.shutdown(socket.SHUT_RDWR)
                        return
                    ev = {"id": cid, "created": 111,
                          "choices": [{"index": 0,
                                       "delta": {"content": f"t{i}"},
                                       "finish_reason": None}]}
                    if want_ids:
                        ev["position"], ev["token_ids"] = i, [100 + i]
                    chunk(b"data: " + json.dumps(ev).encode() + b"\n\n")
                    emitted += 1
                fin = {"id": cid, "created": 111,
                       "choices": [{"index": 0, "delta": {},
                                    "finish_reason": "stop"}]}
                chunk(b"data: " + json.dumps(fin).encode() + b"\n\n")
                chunk(b"data: [DONE]\n\n")
                chunk(b"")
            else:
                self._json(200, {
                    "object": "chat.completion", "model": state.model,
                    "choices": [{"index": 0, "message":
                                 {"role": "assistant", "content": "ok"},
                                 "finish_reason": "stop"}],
                    "usage": {"prompt_tokens": 1, "completion_tokens": 1,
                              "total_tokens": 2},
                    "timings": {"replica": state.rid},
                })

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


@pytest.fixture
def mesh():
    """Two stub replicas + a started router (poller effectively inert:
    poll_s=30 — tests drive _poll_one directly when they need a refresh)."""
    from dllama_tpu.serve.router import make_router

    a, b = StubState("stub-a"), StubState("stub-b")
    ha, hb = make_stub(a), make_stub(b)
    server, router = make_router(
        [f"127.0.0.1:{ha.server_address[1]}",
         f"127.0.0.1:{hb.server_address[1]}"],
        poll_s=30.0)
    router.start()
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_address[1]
    yield port, router, (a, b), (ha, hb)
    router.stop()
    server.shutdown()
    server.server_close()
    for h in (ha, hb):
        try:
            h.shutdown()
            h.server_close()
        except OSError:
            pass


def rpost(port, path, body, timeout=30, headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, json.dumps(body),
                 dict({"Content-Type": "application/json"}, **(headers or {})))
    resp = conn.getresponse()
    data = resp.read()
    headers = dict(resp.getheaders())
    conn.close()
    return resp.status, data, headers


def rget(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


SHARED = [{"role": "system", "content":
           "You are a helpful assistant with a long shared preamble."},
          {"role": "user", "content": "hi"}]


def test_handshake_and_health(mesh):
    port, router, (a, b), _ = mesh
    st, data = rget(port, "/health")
    assert st == 200
    h = json.loads(data)
    assert h["mode"] == "router" and h["ready"]
    assert len(h["replicas"]) == 2
    assert all(r["ready"] and r["config_ok"] for r in h["replicas"])
    assert h["mesh"]["model"] == "stub-model"
    st, data = rget(port, "/v1/models")
    assert st == 200
    assert json.loads(data)["data"][0]["id"] == "stub-model"
    st, data = rget(port, "/router/replicas")
    assert st == 200 and len(json.loads(data)["replicas"]) == 2


def test_affinity_pins_shared_prefix(mesh):
    port, router, (a, b), _ = mesh
    hits0 = ins.ROUTER_AFFINITY_HITS.value()
    for i in range(4):
        msgs = [SHARED[0], {"role": "user", "content": f"turn {i}"}]
        st, data, headers = rpost(port, "/v1/chat/completions",
                                  {"messages": msgs, "max_tokens": 4})
        assert st == 200
        assert headers.get("X-Replica-Id") in ("stub-a", "stub-b")
    served = (len(a.served), len(b.served))
    # every request shares the system prompt -> one replica got ALL of them
    assert sorted(served) == [0, 4], served
    assert ins.ROUTER_AFFINITY_HITS.value() - hits0 >= 3


def test_least_loaded_spreads_distinct_prefixes(mesh):
    port, router, (a, b), _ = mesh
    for i in range(6):
        msgs = [{"role": "system", "content": f"totally distinct prefix {i}"},
                {"role": "user", "content": "hi"}]
        st, _, _ = rpost(port, "/v1/chat/completions",
                         {"messages": msgs, "max_tokens": 4})
        assert st == 200
    # distinct fingerprints have no warm pin: load-based pick with LRU
    # tie-break must use BOTH replicas
    assert len(a.served) >= 1 and len(b.served) >= 1


def test_replica_kill_mid_queue_reroutes_zero_lost(mesh):
    port, router, (a, b), (ha, hb) = mesh
    # pin the shared prefix to whichever replica answers first
    st, _, h1 = rpost(port, "/v1/chat/completions",
                      {"messages": SHARED, "max_tokens": 4})
    assert st == 200
    pinned = h1["X-Replica-Id"]
    victim, survivor = ((a, ha), (b, hb)) if pinned == "stub-a" \
        else ((b, hb), (a, ha))
    # kill the pinned replica outright: connections now refused
    victim[1].shutdown()
    victim[1].server_close()
    # every queued/new request still completes — rerouted, zero lost
    for i in range(3):
        st, data, h2 = rpost(port, "/v1/chat/completions",
                             {"messages": SHARED, "max_tokens": 4})
        assert st == 200, data
        assert h2["X-Replica-Id"] == survivor[0].rid
    # the failed attempt was counted and the replica marked down (registry
    # ids are host:port — map the victim stub through its server port)
    victim_reg = f"127.0.0.1:{victim[1].server_address[1]}"
    st, data = rget(port, "/router/replicas")
    reps = {r["id"]: r for r in json.loads(data)["replicas"]}
    assert reps[victim_reg]["ready"] is False
    assert ins.REPLICA_HEALTHY.labels(replica=victim_reg).value() == 0.0


def test_replica_death_mid_stream_fails_exactly_once(mesh):
    # --failover-max 0: the pre-ISSUE-16 exactly-once error contract must
    # survive as the explicit opt-out (and the unresumable fallback)
    port, router, (a, b), _ = mesh
    router.failover_max = 0
    # pin, then script the pinned stub to die after 2 stream events
    st, _, h1 = rpost(port, "/v1/chat/completions",
                      {"messages": SHARED, "max_tokens": 4})
    pinned = a if h1["X-Replica-Id"] == "stub-a" else b
    pinned.abort_after = 2
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/v1/chat/completions",
                 json.dumps({"messages": SHARED, "stream": True,
                             "max_tokens": 8}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200  # stream started before the death
    raw = resp.read().decode()
    conn.close()
    events = [line[6:] for line in raw.splitlines()
              if line.startswith("data: ")]
    assert events[-1] == "[DONE]"  # the stream ENDED cleanly
    finishes = [json.loads(e)["choices"][0].get("finish_reason")
                for e in events[:-1] if "choices" in e]
    # exactly one terminal finish, and it is "error"
    assert [f for f in finishes if f] == ["error"]
    # in-band error event carries the request id
    errs = [json.loads(e) for e in events[:-1] if "error" in e]
    assert errs and errs[-1]["error"].get("request_id")


def stream_raw(port, body, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/chat/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    raw = resp.read().decode()
    conn.close()
    return raw


def sse_events(raw):
    return [json.loads(line[6:]) for line in raw.splitlines()
            if line.startswith("data: ") and line[6:] != "[DONE]"]


def assemble(raw):
    """-> (content, token_ids, finish_reason, stream_ids) across all data
    frames — the client's total view of one SSE stream."""
    content, ids, finish, cids = "", [], None, set()
    for e in sse_events(raw):
        if "error" in e:
            continue
        ch = (e.get("choices") or [{}])[0]
        content += (ch.get("delta") or {}).get("content") or ""
        ids += e.get("token_ids", [])
        if ch.get("finish_reason"):
            finish = ch["finish_reason"]
        if e.get("id"):
            cids.add(e["id"])
    return content, ids, finish, cids


def test_midstream_failover_resumes_and_suppresses_duplicates(mesh):
    """ISSUE 16 journal seam: the pinned replica dies after 2 token frames;
    the survivor is scripted to REPLAY one already-delivered frame — the
    client must still see every position exactly once, one `stop` finish,
    one stream id, and at most one `: retrying` comment."""
    port, router, (a, b), _ = mesh
    st, _, h1 = rpost(port, "/v1/chat/completions",
                      {"messages": SHARED, "max_tokens": 4})
    victim, survivor = (a, b) if h1["X-Replica-Id"] == "stub-a" else (b, a)
    victim.abort_after = 2
    survivor.resume_overlap = 1
    retried0 = ins.ROUTER_FAILOVERS.labels(outcome="retried").value()
    resumed0 = ins.ROUTER_FAILOVERS.labels(outcome="resumed").value()
    raw = stream_raw(port, {"messages": SHARED, "stream": True,
                            "max_tokens": 8})
    assert raw.rstrip().splitlines()[-1] == "data: [DONE]"
    evs = sse_events(raw)
    tok = [(e["position"], e["token_ids"]) for e in evs if "token_ids" in e]
    assert [p for p, _ in tok] == list(range(a.ntokens)), tok
    assert [t for _, ids in tok for t in ids] == \
        [100 + i for i in range(a.ntokens)]
    finishes = [e["choices"][0].get("finish_reason")
                for e in evs if "choices" in e]
    assert [f for f in finishes if f] == ["stop"]
    assert len({e["id"] for e in evs if "id" in e}) == 1
    assert raw.count(": retrying") == 1
    # the survivor was handed the journaled prefix + the pinned seed
    rb = survivor.served[-1]
    assert rb["resume"]["tokens"] == [100, 101]
    assert rb["include_token_ids"] is True
    assert rb.get("seed") is not None
    assert ins.ROUTER_FAILOVERS.labels(
        outcome="retried").value() - retried0 == 1
    assert ins.ROUTER_FAILOVERS.labels(
        outcome="resumed").value() - resumed0 == 1


def test_failover_budget_exhaustion_fails_exactly_once(mesh):
    """Every replica dies on every attempt: after --failover-max resumes
    the stream must fail EXACTLY once (finish_reason=error, in-band error,
    [DONE]) with no token ever duplicated across the dead attempts."""
    port, router, (a, b), _ = mesh
    a.abort_after = 1
    b.abort_after = 1
    ex0 = ins.ROUTER_FAILOVERS.labels(outcome="exhausted").value()
    raw = stream_raw(port, {"messages": SHARED, "stream": True,
                            "max_tokens": 8})
    assert raw.rstrip().splitlines()[-1] == "data: [DONE]"
    evs = sse_events(raw)
    poss = [e["position"] for e in evs if "token_ids" in e]
    assert poss == sorted(set(poss)), f"duplicate/reordered tokens: {poss}"
    finishes = [e["choices"][0].get("finish_reason")
                for e in evs if "choices" in e]
    assert [f for f in finishes if f] == ["error"]
    assert any("error" in e for e in evs)
    assert ins.ROUTER_FAILOVERS.labels(
        outcome="exhausted").value() - ex0 == 1


def test_drain_redirects_new_traffic(mesh):
    port, router, (a, b), _ = mesh
    st, _, h1 = rpost(port, "/v1/chat/completions",
                      {"messages": SHARED, "max_tokens": 4})
    pinned, other = (a, b) if h1["X-Replica-Id"] == "stub-a" else (b, a)
    served_before = len(other.served)
    # drain the pinned replica and refresh the router's view synchronously
    pinned.draining = True
    for rep in router.replicas:
        router._poll_one(rep)
    for i in range(2):
        st, _, h2 = rpost(port, "/v1/chat/completions",
                          {"messages": SHARED, "max_tokens": 4})
        assert st == 200
        assert h2["X-Replica-Id"] == other.rid  # redirected while draining
    assert len(other.served) == served_before + 2


def test_all_saturated_sheds_with_retry_after(mesh):
    port, router, (a, b), _ = mesh
    a.saturated = b.saturated = True
    st, data, headers = rpost(port, "/v1/chat/completions",
                              {"messages": SHARED, "max_tokens": 4})
    assert st == 429
    assert int(headers.get("Retry-After", 0)) >= 3  # upstream's hint honored
    assert b"saturated" in data


def test_router_drain_sheds_503(mesh):
    port, router, _, _ = mesh
    router.drain()
    st, data, headers = rpost(port, "/v1/chat/completions",
                              {"messages": SHARED, "max_tokens": 4})
    assert st == 503 and headers.get("Retry-After")
    st, _ = rget(port, "/health/ready")
    assert st == 503


def test_stream_passthrough_forwards_tokens_incrementally(mesh):
    """The router must forward SSE frames as they arrive, not buffer the
    stream: http.client's read(n) on a chunked response blocks until n
    bytes or EOF, which would hold every token delta (and heartbeat)
    hostage until the stream ended — the read1 regression this pins."""
    port, router, (a, b), _ = mesh
    for stub in (a, b):
        stub.ntokens = 20
        stub.stream_delay = 0.1  # ~2s stream end to end
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/v1/chat/completions",
                 json.dumps({"messages": SHARED, "stream": True,
                             "max_tokens": 30}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    t0 = time.monotonic()
    first = resp.read1(4096)
    t_first = time.monotonic() - t0
    rest = resp.read()
    conn.close()
    assert first.startswith(b"data: ")
    assert t_first < 1.0, f"first frame buffered for {t_first:.2f}s"
    assert b"[DONE]" in (first + rest)


def test_health_answers_while_streams_saturate_workers():
    """Control-plane GETs ride the aio front-end's dedicated pool: /health
    and /metrics must answer even when EVERY request worker is parked on a
    long-lived proxied stream — an LB probe queued behind them would flag
    a healthy router dead and restart it, killing the streams."""
    from dllama_tpu.serve.router import make_router

    a = StubState("stub-a")
    a.ntokens = 100
    a.stream_delay = 0.05  # ~5s per stream
    ha = make_stub(a)
    server, router = make_router([f"127.0.0.1:{ha.server_address[1]}"],
                                 poll_s=30.0, workers=2)
    try:
        router.start()
        threading.Thread(target=server.serve_forever, daemon=True).start()
        port = server.server_address[1]

        def stream():
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
            conn.request("POST", "/v1/chat/completions",
                         json.dumps({"messages": SHARED, "stream": True,
                                     "max_tokens": 50}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            resp.read()
            conn.close()

        streams = [threading.Thread(target=stream, daemon=True)
                   for _ in range(2)]
        for t in streams:
            t.start()
        time.sleep(0.5)  # both workers now own a live stream
        t0 = time.monotonic()
        st, _ = rget(port, "/health/ready")
        assert st == 200
        assert time.monotonic() - t0 < 2.0, "probe starved behind streams"
        st, _ = rget(port, "/metrics")
        assert st == 200
    finally:
        router.stop()
        server.shutdown()
        server.server_close()
        ha.shutdown()
        ha.server_close()


def test_config_handshake_quarantines_mismatch():
    """A replica serving a different (model, version) than the mesh must
    never be routed to — the root/worker handshake verdict."""
    from dllama_tpu.serve.router import make_router

    a = StubState("stub-a")
    c = StubState("stub-c", model="other-model", version="9.9")
    ha, hc = make_stub(a), make_stub(c)
    server, router = make_router(
        [f"127.0.0.1:{ha.server_address[1]}",
         f"127.0.0.1:{hc.server_address[1]}"], poll_s=30.0)
    try:
        router.start()
        bad = router.replicas[1]
        assert bad.config_ok is False
        assert router.mesh_model == "stub-model"
        rep, _ = router.pick(None, exclude=set())
        assert rep is router.replicas[0]  # quarantined never picked
        router.release(rep)
    finally:
        router.stop()
        server.server_close()
        for h in (ha, hc):
            h.shutdown()
            h.server_close()


# --------------------------------------------------------------------------
# end-to-end: two REAL engine replicas behind the router
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def real_mesh(tmp_path_factory):
    from dllama_tpu.engine.loader import load_model
    from dllama_tpu.serve.api import make_server
    from dllama_tpu.serve.router import make_router
    from tests.test_serve import make_tiny_files

    tmp = tmp_path_factory.mktemp("router_real")
    mpath, tpath, _cfg = make_tiny_files(tmp)
    servers = []
    for i in range(2):
        loaded = load_model(mpath, tpath, mesh=None)
        httpd, api = make_server(loaded, host="127.0.0.1", port=0,
                                 n_slots=2, kv_layout="paged", page_size=8)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        servers.append((httpd, api))
    rserver, router = make_router(
        [f"127.0.0.1:{h.server_address[1]}" for h, _ in servers],
        poll_s=30.0)
    router.start()
    threading.Thread(target=rserver.serve_forever, daemon=True).start()
    yield rserver.server_address[1], router, servers
    router.stop()
    rserver.shutdown()
    rserver.server_close()
    for httpd, api in servers:
        try:
            if api.scheduler is not None:
                api.scheduler.shutdown()
            httpd.shutdown()
            httpd.server_close()
        except OSError:
            pass


def test_real_mesh_trace_propagation_and_postmortem(real_mesh):
    """ISSUE 17 e2e over real engines: the router mints ONE trace id for a
    proxied request, the replica adopts it from the X-Dllama-Trace hop
    header into its flight recorder, GET /router/trace merges both
    processes' spans under that id on one clock-aligned timeline, and
    GET /router/requests/{id} joins the router's routing record with the
    replica's own timeline.  Runs BEFORE the failover drill below — that
    one kills a replica for good (module-scoped mesh)."""
    from tests.test_metrics import parse_exposition

    port, router, servers = real_mesh
    for rep in router.replicas:
        router._poll_one(rep)  # poll_s=30: capture clock + trace epoch now
    rid = "req-obs-e2e-1"
    st, data, headers = rpost(
        port, "/v1/chat/completions",
        {"messages": [{"role": "user", "content": "trace me"}],
         "max_tokens": 4, "temperature": 0.0},
        headers={"X-Request-Id": rid})
    assert st == 200, data

    # cross-hop postmortem: router journal joined with the replica timeline
    # (the router notes the outcome just AFTER it has answered the client)
    for _ in range(100):
        st, data = rget(port, f"/router/requests/{rid}")
        assert st == 200, data
        pm = json.loads(data)
        if pm["router"]["outcome"] is not None:
            break
        time.sleep(0.02)
    tid = pm["trace_id"]
    assert tid and len(tid) == 16 and tid != rid
    assert pm["router"]["outcome"] == "ok"
    assert [a["kind"] for a in pm["router"]["attempts"]] == ["forward"]
    serving = pm["router"]["attempts"][0]["replica"]
    assert serving in {r.rid for r in router.replicas}
    leg = pm["replicas"][serving]
    assert leg["req_id"] == rid and leg["trace_id"] == tid
    assert leg["state"] == "finished"

    # merged mesh trace: both replicas merged, offsets aligned and tiny
    # (same host), router + replica spans under the SAME trace id
    st, data = rget(port, "/router/trace")
    assert st == 200
    merged = json.loads(data)
    assert merged["otherData"]["replicas_merged"] == 2
    clocks = merged["otherData"]["clock"]
    assert set(clocks) == {r.rid for r in router.replicas}
    for c in clocks.values():
        assert c["aligned"] is True
        assert abs(c["offset_s"]) <= max(c["uncertainty_s"], 0.25)
    body = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    assert [e["ts"] for e in body] == sorted(e["ts"] for e in body)
    traced = [e for e in body if e.get("args", {}).get("trace_id") == tid]
    pids = {e["pid"] for e in traced}
    assert 1 in pids and any(p > 1 for p in pids), pids
    names = {e["name"] for e in traced}
    assert "connect" in names        # the router's own leg
    assert "request" in names        # the replica's span joined the trace

    # federation: one grammar-clean exposition with replica-labeled series
    # and pre-aggregated fleet counters
    st, data = rget(port, "/router/metrics")
    assert st == 200
    fams, samples = parse_exposition(data.decode())
    assert fams["dllama_fleet_requests_finished_total"] == "counter"
    assert any(n == "dllama_requests_finished_total"
               and f'replica="{serving}"' in lbl
               for (n, lbl) in samples)


def test_real_mesh_affinity_and_failover(real_mesh):
    port, router, servers = real_mesh
    # (1) shared system prompt pins every request to ONE warm replica
    ids = set()
    for i in range(3):
        msgs = [{"role": "system", "content":
                 "Shared preamble for the warm-path routing test."},
                {"role": "user", "content": f"q{i}"}]
        st, data, headers = rpost(port, "/v1/chat/completions",
                                  {"messages": msgs, "max_tokens": 4,
                                   "temperature": 0.0})
        assert st == 200, data
        body = json.loads(data)
        assert body["choices"][0]["finish_reason"] in ("stop", "length")
        assert headers.get("X-Replica-Id") == body["timings"]["replica"]
        ids.add(headers["X-Replica-Id"])
    assert len(ids) == 1, f"affinity scattered the shared prefix: {ids}"
    warm_rid = ids.pop()
    # (2) kill the warm replica: same-prefix traffic fails over, zero lost
    victim = next((h, a) for h, a in servers
                  if f"127.0.0.1:{h.server_address[1]}" == warm_rid
                  or a.replica_id == warm_rid)
    victim[0].shutdown()
    victim[0].server_close()
    st, data, headers = rpost(port, "/v1/chat/completions",
                              {"messages": [
                                  {"role": "system", "content":
                                   "Shared preamble for the warm-path "
                                   "routing test."},
                                  {"role": "user", "content": "after"}],
                               "max_tokens": 4, "temperature": 0.0})
    assert st == 200, data
    survivor_rid = headers["X-Replica-Id"]
    assert survivor_rid != warm_rid
    # (3) the survivor's paged-KV allocator stayed clean through it all
    shost, sport = survivor_rid.split(":")
    conn = http.client.HTTPConnection(shost, int(sport), timeout=10)
    conn.request("GET", "/debug/kv")
    resp = conn.getresponse()
    kv = json.loads(resp.read())
    conn.close()
    assert resp.status == 200
    assert kv["layout"] == "paged" and kv["audit"]["ok"] is True


# --------------------------------------------------------------------------
# mid-stream failover over REAL engines (ISSUE 16): bit-exact resume
# --------------------------------------------------------------------------

class SeverProxy:
    """TCP forwarder that can cut the wire mid-SSE. Armed via
    cut_after_frames=N it forwards the first N data frames verbatim then
    severs the connection MID-frame — from the router's seat exactly the
    death a SIGKILLed replica produces (EOF/RST, no terminal frame), minus
    the process machinery an in-proc test can't have."""

    def __init__(self, target_port: int):
        self.target_port = target_port
        self.cut_after_frames = None  # None = fully transparent
        self.lsock = socket.socket()
        self.lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.lsock.bind(("127.0.0.1", 0))
        self.lsock.listen(16)
        self.port = self.lsock.getsockname()[1]
        self._stop = False
        threading.Thread(target=self._accept_loop, daemon=True).start()

    def _accept_loop(self):
        while not self._stop:
            try:
                cli, _ = self.lsock.accept()
            except OSError:
                return
            srv = socket.socket()
            try:
                srv.connect(("127.0.0.1", self.target_port))
            except OSError:
                cli.close()
                continue
            threading.Thread(target=self._pump_up, args=(cli, srv),
                             daemon=True).start()
            threading.Thread(target=self._pump_down, args=(srv, cli),
                             daemon=True).start()

    def _pump_up(self, cli, srv):
        try:
            while True:
                d = cli.recv(65536)
                if not d:
                    break
                srv.sendall(d)
        except OSError:
            pass
        try:
            srv.shutdown(socket.SHUT_WR)
        except OSError:
            pass

    def _pump_down(self, srv, cli):
        buf = b""
        frames = 0
        try:
            while True:
                d = srv.recv(65536)
                if not d:
                    break
                if self.cut_after_frames is None:
                    cli.sendall(d)
                    continue
                buf += d
                while True:
                    seg, sep, rest = buf.partition(b"\n\n")
                    if not sep:
                        break
                    buf = rest
                    if b"data: " in seg:
                        frames += 1
                        if frames > self.cut_after_frames:
                            # a few bytes of the doomed frame carry the
                            # previous chunk's terminator, so everything
                            # already relayed parses; then cut hard
                            cli.sendall(seg[:8])
                            cli.shutdown(socket.SHUT_RDWR)
                            srv.close()
                            return
                    cli.sendall(seg + sep)
            if buf:
                cli.sendall(buf)
        except OSError:
            pass
        try:
            cli.shutdown(socket.SHUT_WR)
        except OSError:
            pass

    def close(self):
        self._stop = True
        try:
            self.lsock.close()
        except OSError:
            pass


@pytest.fixture(scope="module")
def failover_real(tmp_path_factory):
    """Two REAL engine replicas (paged KV + a small host spill tier), one
    of them behind a severable wire, fronted by a started router."""
    from dllama_tpu.engine.loader import load_model
    from dllama_tpu.serve.api import make_server
    from dllama_tpu.serve.router import make_router
    from tests.test_serve import make_tiny_files

    tmp = tmp_path_factory.mktemp("router_failover")
    mpath, tpath, _cfg = make_tiny_files(tmp)
    servers = []
    for i in range(2):
        loaded = load_model(mpath, tpath, mesh=None)
        httpd, api = make_server(loaded, host="127.0.0.1", port=0,
                                 n_slots=2, kv_layout="paged", page_size=8,
                                 kv_host_pages=4)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        servers.append((httpd, api))
    a_port = servers[0][0].server_address[1]
    b_port = servers[1][0].server_address[1]
    proxy = SeverProxy(a_port)  # replica A is the victim behind the wire
    rserver, router = make_router(
        [f"127.0.0.1:{proxy.port}", f"127.0.0.1:{b_port}"], poll_s=30.0)
    router.start()
    threading.Thread(target=rserver.serve_forever, daemon=True).start()
    yield (rserver.server_address[1], router, a_port, b_port, proxy)
    router.stop()
    rserver.shutdown()
    rserver.server_close()
    proxy.close()
    for httpd, api in servers:
        try:
            if api.scheduler is not None:
                api.scheduler.shutdown()
            httpd.shutdown()
            httpd.server_close()
        except OSError:
            pass


RESUME_MSGS = [{"role": "system", "content":
                "Failover drill shared preamble, long enough to matter."},
               {"role": "user", "content": "continue the drill"}]


def _resume_bit_exact(a_port, b_port, body):
    """Uninterrupted stream on replica A; resume at the midpoint on
    replica B (which never saw the prompt) — the continuation must be
    bit-exact: same token ids, same text, same finish, positions picking
    up exactly where the journal stops, stream identity preserved."""
    base_raw = stream_raw(a_port, body)
    content, ids, finish, _ = assemble(base_raw)
    assert len(ids) >= 2, f"stream too short to split: {ids}"
    # split at a FRAME boundary (one frame may carry several token ids —
    # held stop-prefix bytes ride the next text-bearing frame), mid-way
    # through the token frames; the suffix is everything from that frame
    # on, finish/flush frames included
    frames = sse_events(base_raw)
    tok_idx = [i for i, e in enumerate(frames) if "token_ids" in e]
    assert len(tok_idx) >= 2, f"too few token frames: {frames}"
    mid = tok_idx[len(tok_idx) // 2]
    k = frames[mid]["position"]
    assert k >= 1
    suffix = "".join(
        ((e.get("choices") or [{}])[0].get("delta") or {}).get("content")
        or "" for e in frames[mid:])
    rbody = dict(body)
    rbody["resume"] = {"tokens": ids[:k], "id": "chatcmpl-drill",
                       "created": 1234}
    r_raw = stream_raw(b_port, rbody)
    c2, ids2, fin2, cids2 = assemble(r_raw)
    assert ids2 == ids[k:], f"resume diverged: {ids2} vs {ids[k:]}"
    assert c2 == suffix
    assert fin2 == finish
    assert cids2 == {"chatcmpl-drill"}  # identity from the resume body
    assert '"role"' not in r_raw  # the role delta is never re-sent
    first = next(e for e in sse_events(r_raw) if "token_ids" in e)
    assert first["position"] == k


def test_cross_replica_resume_bit_exact_greedy(failover_real):
    _, _, a_port, b_port, _ = failover_real
    _resume_bit_exact(a_port, b_port, {
        "messages": RESUME_MSGS, "stream": True, "max_tokens": 10,
        "temperature": 0.0, "include_token_ids": True})


def test_cross_replica_resume_bit_exact_sampled(failover_real):
    _, _, a_port, b_port, _ = failover_real
    _resume_bit_exact(a_port, b_port, {
        "messages": RESUME_MSGS, "stream": True, "max_tokens": 10,
        "temperature": 0.9, "top_p": 0.95, "seed": 7,
        "include_token_ids": True})


def test_sampled_resume_without_seed_rejected(failover_real):
    _, _, a_port, _, _ = failover_real
    st, data, _ = rpost(a_port, "/v1/chat/completions", {
        "messages": RESUME_MSGS, "stream": False, "max_tokens": 4,
        "temperature": 0.8,
        "resume": {"tokens": [1, 2], "id": "x", "created": 1}})
    assert st == 400
    assert b"seed" in data


def test_router_kill_mid_stream_bit_exact(failover_real):
    """The acceptance drill: a replica's wire dies mid-stream behind the
    router; with --failover-max >= 1 the client's completed stream is
    byte-identical to the uninterrupted run — zero duplicated, zero
    dropped tokens — and the survivor's KV audit stays clean. LAST in
    this module: it marks the proxied replica down."""
    from dllama_tpu.serve.router import Router

    rport, router, a_port, b_port, proxy = failover_real
    body = {"messages": [{"role": "system", "content":
                          "kill-drill preamble nobody else uses"},
                         {"role": "user", "content": "go"}],
            "stream": True, "max_tokens": 10, "temperature": 0.0,
            "seed": 11, "include_token_ids": True}
    # uninterrupted baseline straight off the victim replica
    content, ids, finish, _ = assemble(stream_raw(a_port, body))
    assert len(ids) >= 5, f"stream too short for a mid-stream kill: {ids}"
    # pin the prompt to the proxied victim, then arm the wire cut: the
    # role delta + 2 token frames get through, the 4th frame dies mid-byte
    fp = Router.fingerprint(body, False)
    with router._mu:
        router._affinity[fp] = f"127.0.0.1:{proxy.port}"
    resumed0 = ins.ROUTER_FAILOVERS.labels(outcome="resumed").value()
    proxy.cut_after_frames = 3
    raw = stream_raw(rport, body)
    c2, ids2, fin2, cids2 = assemble(raw)
    assert ids2 == ids, f"token loss/dup across failover: {ids2} vs {ids}"
    assert c2 == content
    assert fin2 == finish
    assert len(cids2) == 1  # one stream identity end to end
    assert raw.count(": retrying") == 1
    assert ins.ROUTER_FAILOVERS.labels(
        outcome="resumed").value() - resumed0 == 1
    # the survivor's paged-KV pool (device + host tier) reconciles
    st, data = rget(b_port, "/debug/kv")
    kv = json.loads(data)
    assert st == 200 and kv["audit"]["ok"] is True
