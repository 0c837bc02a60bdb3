"""Aio front-end drills (ISSUE 15): the ≥256-concurrent-SSE-streams
concurrency drill with a bounded thread count, event-loop disconnect
detection (no polling thread), the thread-tier mid-stream disconnect
regression, and SSE keep-alive heartbeats."""

import http.client
import json
import re
import selectors
import socket
import threading
import time

import pytest

from dllama_tpu.utils import faults


@pytest.fixture(scope="module")
def tiny_loaded(tmp_path_factory):
    from dllama_tpu.engine.loader import load_model
    from tests.test_serve import make_tiny_files

    tmp = tmp_path_factory.mktemp("aio")
    mpath, tpath, _ = make_tiny_files(tmp)
    return mpath, tpath


def _boot(mpath, tpath, **kw):
    from dllama_tpu.engine.loader import load_model
    from dllama_tpu.serve.api import make_server

    loaded = load_model(mpath, tpath, mesh=None)
    httpd, api = make_server(loaded, host="127.0.0.1", port=0, **kw)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, api


def _metric(text: str, name: str) -> float:
    m = re.search(rf"^{name} ([0-9.e+-]+)$", text, re.M)
    return float(m.group(1)) if m else 0.0


def _scrape(port: int) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/metrics")
    resp = conn.getresponse()
    text = resp.read().decode()
    conn.close()
    return text


def _stream_request_bytes(port: int, max_tokens: int = 2) -> bytes:
    body = json.dumps({"messages": [{"role": "user", "content": "hi"}],
                       "max_tokens": max_tokens, "temperature": 0.0,
                       "stream": True}).encode()
    return (b"POST /v1/chat/completions HTTP/1.1\r\n"
            b"Host: 127.0.0.1:%d\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: %d\r\n\r\n" % (port, len(body))) + body


N_STREAMS = 260  # acceptance floor is 256


def test_concurrency_drill_256_streams_bounded_threads(tiny_loaded):
    """≥256 concurrent SSE streams on the aio front-end: every stream
    completes with [DONE], and the server's thread count stays a constant
    of the configuration (asserted via dllama_process_threads mid-flight —
    thread-per-connection would sit at 256+)."""
    mpath, tpath = tiny_loaded
    httpd, api = _boot(mpath, tpath, n_slots=4, frontend="aio")
    try:
        port = httpd.server_address[1]
        req = _stream_request_bytes(port)
        sel = selectors.DefaultSelector()
        bufs: dict[socket.socket, bytearray] = {}
        for i in range(N_STREAMS):
            s = socket.create_connection(("127.0.0.1", port), timeout=60)
            s.sendall(req)
            s.setblocking(False)
            bufs[s] = bytearray()
            sel.register(s, selectors.EVENT_READ)
        # wait until every stream has its SSE headers — 260 live
        # connections, most queued behind 4 slots
        deadline = time.monotonic() + 120
        headered = set()
        done: set = set()
        threads_mid = None
        while len(done) < N_STREAMS and time.monotonic() < deadline:
            for key, _ in sel.select(timeout=1.0):
                s = key.fileobj
                try:
                    data = s.recv(65536)
                except (BlockingIOError, InterruptedError):
                    continue
                except OSError:
                    data = b""
                if not data:
                    sel.unregister(s)
                    s.close()
                    done.add(s)  # server closed after [DONE] (or died: the
                    # buffer assertion below catches that)
                    continue
                bufs[s] += data
                if b"text/event-stream" in bufs[s]:
                    headered.add(s)
                if b"data: [DONE]" in bufs[s] and s not in done:
                    done.add(s)
                    sel.unregister(s)
                    s.close()
            if threads_mid is None and len(headered) >= N_STREAMS \
                    and len(done) < N_STREAMS // 2:
                # every connection is live (headers out), most still
                # streaming/queued: THE moment thread-per-connection would
                # be at 260+ threads
                text = _scrape(port)
                threads_mid = _metric(text, "dllama_process_threads")
                # the gauge is labeled per server (the registry outlives
                # servers — earlier tests' series linger at 0): read THIS
                # server's series
                m = re.search(
                    r'^dllama_frontend_connections\{server="127\.0\.0\.1:'
                    + str(port) + r'"\} ([0-9.e+-]+)$', text, re.M)
                assert m and float(m.group(1)) >= N_STREAMS, \
                    "connections gauge never reflected the live streams"
        assert len(done) == N_STREAMS, \
            f"only {len(done)}/{N_STREAMS} streams completed"
        incomplete = [bytes(b) for b in bufs.values()
                      if b"data: [DONE]" not in b]
        assert not incomplete, \
            f"{len(incomplete)} streams closed without [DONE]"
        assert threads_mid is not None, "never observed the mid-flight state"
        # loop + pump + <=8 workers + scheduler worker/watchdog + test
        # harness threads — nowhere near one-per-connection
        assert threads_mid < 64, \
            f"{threads_mid} threads for {N_STREAMS} streams"
    finally:
        if api.scheduler is not None:
            api.scheduler.shutdown()
        httpd.shutdown()
        httpd.server_close()


def _kv_audit_ok(port: int) -> bool:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", "/debug/kv")
    resp = conn.getresponse()
    kv = json.loads(resp.read())
    conn.close()
    return resp.status == 200 and (kv["audit"] is None or kv["audit"]["ok"])


def _disconnect_mid_stream(httpd, api):
    """Open a stream with a huge budget, hang up mid-decode, and assert the
    request is cancelled and the paged pool audits clean."""
    port = httpd.server_address[1]
    before = api.scheduler.latency_summary()["completed"]
    faults.install("engine.decode", "delay", ms=50.0)
    try:
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        s.sendall(_stream_request_bytes(port, max_tokens=4096))
        # read the headers + at least one token event, then hang up
        buf = b""
        deadline = time.monotonic() + 30
        while b"data: " not in buf and time.monotonic() < deadline:
            buf += s.recv(4096)
        assert b"text/event-stream" in buf
        s.close()  # mid-stream client hangup
        deadline = time.monotonic() + 15.0
        cancelled = None
        while time.monotonic() < deadline:
            with api.scheduler._metrics_lock:
                recent = list(api.scheduler._completed)[before:]
            cancelled = next((r for r in recent
                              if r.finish_reason == "cancelled"), None)
            if cancelled is not None:
                break
            time.sleep(0.02)
    finally:
        faults.clear()
    assert cancelled is not None, "hangup did not cancel the stream"
    assert cancelled.produced < 400  # nowhere near the budget
    # pages freed, allocator clean (the /debug/kv audit reconciles
    # refcounts vs block tables vs free list)
    assert _kv_audit_ok(port)


def test_aio_disconnect_cancels_via_event_loop(tiny_loaded):
    """aio tier: the event loop's EOF signal (no polling thread) cancels a
    mid-stream hangup and frees its pages."""
    mpath, tpath = tiny_loaded
    httpd, api = _boot(mpath, tpath, n_slots=2, frontend="aio",
                       kv_layout="paged", page_size=8)
    try:
        _disconnect_mid_stream(httpd, api)
    finally:
        api.scheduler.shutdown()
        httpd.shutdown()
        httpd.server_close()


def test_threads_disconnect_regression_mid_stream(tiny_loaded):
    """threads tier (regression, ISSUE 15 satellite): the MSG_PEEK probe
    still cancels a mid-STREAM hangup and frees its pages — the pre-aio
    probe path stays covered now that aio is the default."""
    mpath, tpath = tiny_loaded
    httpd, api = _boot(mpath, tpath, n_slots=2, frontend="threads",
                       kv_layout="paged", page_size=8)
    try:
        _disconnect_mid_stream(httpd, api)
    finally:
        api.scheduler.shutdown()
        httpd.shutdown()
        httpd.server_close()


@pytest.mark.parametrize("frontend", ["aio", "threads"])
def test_sse_heartbeat_on_idle_stream(tiny_loaded, frontend):
    """A slow-decode stream emits `: keep-alive` SSE comment frames on the
    --sse-heartbeat-s cadence (both front-ends), and they terminate once
    the stream ends."""
    mpath, tpath = tiny_loaded
    httpd, api = _boot(mpath, tpath, n_slots=2, frontend=frontend,
                       sse_heartbeat_s=0.05)
    try:
        port = httpd.server_address[1]
        faults.install("engine.decode", "delay", ms=150.0)
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
            conn.request("POST", "/v1/chat/completions",
                         json.dumps({"messages": [
                             {"role": "user", "content": "hi"}],
                             "max_tokens": 3, "temperature": 0.0,
                             "stream": True}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            raw = resp.read().decode()
            conn.close()
        finally:
            faults.clear()
        assert raw.count(": keep-alive") >= 1, raw[:400]
        assert "data: [DONE]" in raw
        # heartbeats are comments — they must not disturb the event stream
        events = [ln for ln in raw.splitlines() if ln.startswith("data: ")]
        assert events[-1] == "data: [DONE]"
    finally:
        api.scheduler.shutdown()
        httpd.shutdown()
        httpd.server_close()


def test_x_replica_id_and_timings_replica(tiny_loaded):
    """Every response carries X-Replica-Id and `timings.replica` (default
    identity: host:port) for end-to-end attribution through the router."""
    mpath, tpath = tiny_loaded
    httpd, api = _boot(mpath, tpath, n_slots=2, frontend="aio",
                       replica_id="replica-7")
    try:
        port = httpd.server_address[1]
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/v1/chat/completions",
                     json.dumps({"messages": [{"role": "user",
                                               "content": "hi"}],
                                 "max_tokens": 3, "temperature": 0.0}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        assert resp.getheader("X-Replica-Id") == "replica-7"
        assert body["timings"]["replica"] == "replica-7"
        # health GETs carry it too (any response does)
        conn.request("GET", "/health")
        resp = conn.getresponse()
        resp.read()
        assert resp.getheader("X-Replica-Id") == "replica-7"
        conn.close()
    finally:
        api.scheduler.shutdown()
        httpd.shutdown()
        httpd.server_close()


# ---------------------------------------------------------------------------
# arrival order (PR 36): completions are submitted in the order their bodies
# reached the loop, whatever order the pool's workers finished tokenizing in


def test_arrival_order_turns_follow_tickets():
    from dllama_tpu.serve.api import ArrivalOrder

    order = ArrivalOrder(patience_s=10.0)
    tickets = [order.arrive() for _ in range(12)]
    passed: list = []

    def worker(t, delay):
        with order.bound(t):
            time.sleep(delay)  # the later the ticket, the sooner it is ready
            if t == 5:
                return  # a handler that ends before any submit (a 400)
            with order.turn():
                passed.append(t)

    threads = [threading.Thread(target=worker, args=(t, 0.012 * (12 - i)))
               for i, t in enumerate(tickets)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    assert passed == [t for t in tickets if t != 5]
    assert not order._open  # every ticket was left


def test_arrival_order_patience_and_no_ticket():
    from dllama_tpu.serve.api import ArrivalOrder

    order = ArrivalOrder(patience_s=0.05)
    first, second = order.arrive(), order.arrive()
    t0 = time.monotonic()
    with order.bound(second), order.turn():  # `first` never leaves
        waited = time.monotonic() - t0
    assert 0.04 <= waited < 2.0
    with order.turn():  # a thread that holds no ticket passes straight through
        pass
    with pytest.raises(RuntimeError), order.bound(first), order.turn():
        raise RuntimeError("a submit that raised")
    assert not order._open


def test_aio_submits_in_arrival_order(tiny_loaded):
    """Eight streamed completions whose bodies arrive one after another while
    the EARLIER ones take longer to tokenize: the scheduler still receives
    them in arrival order (it received them fastest-first before)."""
    mpath, tpath = tiny_loaded
    httpd, api = _boot(mpath, tpath, n_slots=2, frontend="aio")
    n = 8
    try:
        port = httpd.server_address[1]
        encode, submit = api.tokenizer.encode, api.scheduler.submit
        seen: list = []

        def slow_encode(text, *a, **kw):
            k = len(text) // 2  # "hi" * k
            if 1 <= k <= n:
                time.sleep(0.03 * (n - k))
            return encode(text, *a, **kw)

        def recording_submit(prompt, *a, **kw):
            seen.append(len(prompt))
            return submit(prompt, *a, **kw)

        api.tokenizer.encode = slow_encode
        api.scheduler.submit = recording_submit
        socks = []
        for k in range(1, n + 1):
            body = json.dumps({"prompt": "hi" * k, "max_tokens": 2,
                               "temperature": 0.0, "stream": True}).encode()
            s = socket.create_connection(("127.0.0.1", port), timeout=60)
            s.sendall(b"POST /v1/completions HTTP/1.1\r\n"
                      b"Host: 127.0.0.1:%d\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Content-Length: %d\r\n\r\n" % (port, len(body)) + body)
            socks.append(s)
            time.sleep(0.005)  # bodies complete in this order
        for s in socks:
            buf = b""
            while b"[DONE]" not in buf:
                chunk = s.recv(65536)
                assert chunk, buf[-200:]
                buf += chunk
            s.close()
        assert seen == sorted(seen) and len(seen) == n, seen
        assert not api.arrivals._open
    finally:
        api.tokenizer.encode, api.scheduler.submit = encode, submit
        api.scheduler.shutdown()
        httpd.shutdown()
        httpd.server_close()
