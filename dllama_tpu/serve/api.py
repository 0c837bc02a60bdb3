"""OpenAI-compatible HTTP API server — the `dllama-api` binary's role
(dllama-api.cpp:509-581).

Routes: POST /v1/chat/completions and the legacy POST /v1/completions (both
stream + non-stream), GET /v1/models, GET /health (+ /health/live,
/health/ready), GET /metrics (Prometheus text exposition of the process
registry — dllama_tpu/obs). Every POST mints (or adopts from an inbound
X-Request-Id) a per-request id `req_...`, propagated api -> scheduler ->
engine, returned on EVERY response (success, 4xx/5xx, SSE) as the
X-Request-Id header and attached to the request's log lines as the
structured `request_id` field. Request params override
the CLI defaults the way the reference's params do (dllama-api.cpp:455-484):
temperature, top_p, presence/frequency_penalty, seed, max_tokens, stop,
stream.

The **prefix cache** reproduces NaiveCache (dllama-api.cpp:264-309): the chat
history from the previous request is kept with its KV-cache position; when a
new request's messages extend the cached ones, only the delta is encoded and
prefilled — the engine rewinds to the cached position instead of replaying
the whole conversation. The continuous-batching tier has the same capability
per slot, at the token level, inside serve/scheduler.Scheduler.

Built on stdlib http.server (the reference hand-rolls HTTP/1.1 the same
spirit, dllama-api.cpp:104-179); requests are serialized with a lock because
one engine owns the KV cache — the reference is equally single-request
(blocking accept loop, dllama-api.cpp:522-533).
"""

from __future__ import annotations

import contextlib
import json
import logging
import select
import signal
import socket
import threading
import time
import uuid
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from dllama_tpu import __version__
from dllama_tpu.engine.sampling import Sampler
from dllama_tpu.obs import metrics, new_request_id, trace
from dllama_tpu.obs import compile as compile_obs
from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import perf as perfmod
from dllama_tpu.utils import locks
from dllama_tpu.serve.scheduler import (
    QueueFull,
    SchedulerDraining,
    SchedulerRejected,
)
from dllama_tpu.tokenizer.chat import (
    ChatItem,
    ChatTemplate,
    ChatTemplateType,
    EosDetector,
    EosResult,
    chat_stops,
)

log = logging.getLogger("dllama_tpu.serve")

#: socket errors meaning "the client went away" — never worth a stack trace,
#: never answerable with an error response (the pipe is gone)
CLIENT_GONE = (BrokenPipeError, ConnectionResetError, ConnectionAbortedError,
               TimeoutError, socket.timeout)


class ClientDisconnected(Exception):
    """Raised inside a completion when the disconnect probe sees the client
    socket closed — generation is cancelled instead of running to completion
    into a dead socket."""


def _parse_timeout(body: dict) -> float | None:
    """Per-request deadline: `timeout_s` in the request body (do_POST also
    folds an `X-Request-Timeout` header into it). Seconds from submission
    until the request is ended with finish_reason="timeout" — expired-in-
    queue requests never prefill, running ones stop at the next chunk
    boundary. None/absent = no deadline."""
    v = body.get("timeout_s")
    if v is None:
        return None
    try:
        v = float(v)
    except (TypeError, ValueError):
        raise ApiError(400, "timeout_s must be a number of seconds") from None
    if not v > 0:
        raise ApiError(400, "timeout_s must be > 0")
    return v


def _parse_spec_k(body: dict) -> int | None:
    """Per-request speculation: `spec_k` in the request body — the draft
    length this request's slot runs at (0 disables speculation for this
    request even while batch-mates speculate; values above the serving
    --spec-k capacity clamp down to it; greedy output is bit-identical
    either way). None/absent = the CLI default."""
    v = body.get("spec_k")
    if v is None:
        return None
    try:
        v = int(v)
    except (TypeError, ValueError):
        raise ApiError(400, "spec_k must be an integer >= 0") from None
    if v < 0:
        raise ApiError(400, "spec_k must be an integer >= 0")
    return v


#: named priority classes the `priority` body field accepts alongside raw
#: integers (0=low, 1=normal, 2=high) — the scheduler picks strictly
#: between classes and may preempt a lower class for a higher one
PRIORITY_NAMES = {"low": 0, "normal": 1, "high": 2}


def _parse_priority(body: dict) -> int:
    """Scheduling class: `priority` in the request body — 0/'low',
    1/'normal' (the default), 2/'high'. Higher classes admit strictly
    first and (with --preempt) may suspend a running lower-class request
    at a chunk boundary; the suspended stream resumes byte-identical."""
    v = body.get("priority")
    if v is None:
        return 1
    if isinstance(v, str):
        if v not in PRIORITY_NAMES:
            raise ApiError(400, "priority must be an integer 0..2 or one of "
                                "low|normal|high")
        return PRIORITY_NAMES[v]
    try:
        v = int(v)
    except (TypeError, ValueError):
        raise ApiError(400, "priority must be an integer 0..2 or one of "
                            "low|normal|high") from None
    if not 0 <= v <= 2:
        raise ApiError(400, "priority must be an integer 0..2 or one of "
                            "low|normal|high")
    return v


def _parse_tenant(body: dict) -> str:
    """Fair-queueing key: `tenant` in the request body — requests of the
    same tenant share one weighted-fair-queue lane at admission ("" =
    the anonymous shared tenant; weights via --tenant-weight)."""
    v = body.get("tenant")
    if v is None:
        return ""
    if not isinstance(v, str) or len(v) > 64:
        raise ApiError(400, "tenant must be a string of at most 64 chars")
    return v


@dataclass
class PrefixCache:
    """NaiveCache equivalent: remember the last conversation's messages and
    the KV position right after them."""

    messages: list[tuple[str, str]] = field(default_factory=list)
    pos: int = 0
    bos_sent: bool = False

    def resolve(self, incoming: list[tuple[str, str]]) -> tuple[list[tuple[str, str]], int, bool]:
        """-> (delta_messages, start_pos, add_bos). Matches whole-message
        prefixes only, like resolveDeltaPrompt (dllama-api.cpp:286-308)."""
        n = len(self.messages)
        if n and len(incoming) > n and incoming[:n] == self.messages:
            return incoming[n:], self.pos, False
        return incoming, 0, True

    def clear(self) -> None:
        self.messages = []
        self.pos = 0
        self.bos_sent = False


class TokenAssembler:
    """Per-stream EOS/stop-string assembly of a batched token stream — the
    detector + incremental decoder + held-prefix bookkeeping that used to
    live inline in ``_run_batched``, extracted so the blocking tier and the
    aio front-end's cooperative SSE pump (serve/aio.py) process tokens
    identically (byte-identical text deltas either way)."""

    __slots__ = ("detector", "decoder", "parts", "n", "eos", "pending_ids",
                 "taken")

    def __init__(self, tokenizer, stops):
        self.detector = EosDetector(tokenizer.eos_ids, stops,
                                    padding_left=2, padding_right=2)
        self.decoder = tokenizer.make_stream_decoder()
        self.parts: list[str] = []
        self.n = 0
        self.eos = False
        # token-id journal feed (ISSUE 16): raw ids fed since the last
        # take_ids(), and the count already taken — the (position, ids)
        # pairs SSE frames carry so the router can journal resume state
        self.pending_ids: list[int] = []
        self.taken = 0

    def feed(self, t) -> str:
        """Process one token -> the text delta to emit now ("" while the
        detector holds a possible stop prefix). Sets ``eos`` when the
        token completed an EOS/stop sequence."""
        self.n += 1
        self.pending_ids.append(int(t))
        res = self.detector.append(t, self.decoder.decode(t))
        text = self.detector.get_delta()
        if text:
            self.parts.append(text)
        if res == EosResult.EOS:
            self.eos = True
        return text

    def take_ids(self) -> tuple[int, list[int]]:
        """Drain the pending raw ids for the frame about to go out:
        ``(position, ids)`` where ``position`` counts the ids taken by all
        PRIOR frames — a journaling router appends exactly when position
        matches its journal length, which makes duplicate frames after a
        failover self-suppressing. Ids held with a stop-prefix ride the
        NEXT emitted frame (frames and the text they carry stay atomic)."""
        pos, ids = self.taken, self.pending_ids
        self.taken += len(ids)
        self.pending_ids = []
        return pos, ids

    def flush(self) -> str:
        """End of stream without EOS (budget/timeout): release any held
        stop-prefix -> the final text delta to emit."""
        text = self.detector.flush()
        if text:
            self.parts.append(text)
        return text

    def content(self) -> str:
        return "".join(self.parts)


class ArrivalOrder:
    """Completions reach the scheduler's queue in the order their bodies
    reached the front end. The event loop hands each request to a pool of
    workers that parse and tokenize side by side, so prompts sent together
    reached `Scheduler.submit` in whatever order their workers finished;
    admission is serial, so with long prompts that order decided who waited
    two seconds and who forty. The loop takes a ticket as a completion's body
    completes (`arrive`), the worker runs the handler as its holder (`bound`)
    and `turn()`, around the submit, waits until no earlier ticket is open.
    The pool is FIFO, so the lowest open ticket is always on a worker and
    never waits, and a ticket is left on every exit of its handler.
    `patience_s` bounds a wait all the same: order is a courtesy, never
    something a request hangs on. A thread that holds no ticket (the threads
    tier, the tests) passes straight through."""

    def __init__(self, patience_s: float = 2.0):
        self.patience_s = float(patience_s)
        self._cv = threading.Condition()
        self._issued = 0
        self._open: set[int] = set()
        self._mine = threading.local()

    def arrive(self) -> int:
        with self._cv:
            self._issued += 1
            self._open.add(self._issued)
            return self._issued

    def leave(self, ticket: int | None) -> None:
        with self._cv:
            if ticket in self._open:
                self._open.discard(ticket)
                self._cv.notify_all()

    @contextlib.contextmanager
    def bound(self, ticket: int | None):
        self._mine.ticket = ticket
        try:
            yield
        finally:
            self._mine.ticket = None
            self.leave(ticket)

    @contextlib.contextmanager
    def turn(self):
        ticket = getattr(self._mine, "ticket", None)
        if ticket is not None:
            deadline = time.monotonic() + self.patience_s
            with self._cv:
                while min(self._open, default=ticket) < ticket:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._cv.wait(left)
        try:
            yield
        finally:
            self.leave(ticket)


class ApiServer:
    def __init__(self, loaded, default_temperature=0.8, default_topp=0.9, default_seed=None,
                 scheduler=None, spec: int = 0,
                 slo_ttft_ms: float | None = None,
                 slo_itl_ms: float | None = None,
                 replica_id: str = "",
                 sse_heartbeat_s: float = 0.0):
        self.engine = loaded.engine
        self.tokenizer = loaded.tokenizer
        self.config = loaded.config
        self.template = ChatTemplate(
            ChatTemplateType.UNKNOWN, self.tokenizer.chat_template, ""
        )
        self.stops = chat_stops(self.tokenizer)
        self.defaults = dict(
            temperature=default_temperature, topp=default_topp, seed=default_seed
        )
        self.cache = PrefixCache()
        # multi-replica attribution (ISSUE 15): stamped on every response as
        # the X-Replica-Id header and the `replica` field of `timings`, so a
        # stream that crossed the router is attributable end to end. "" =
        # standalone (no header, no field); make_server defaults it to
        # host:port of the bound socket.
        self.replica_id = str(replica_id or "")
        # SSE keep-alive cadence (ISSUE 15): idle streams emit a `: keep-alive`
        # comment frame at this period so router/LB idle timeouts cannot kill
        # a slow-decode stream; 0 = off
        self.sse_heartbeat_s = float(sse_heartbeat_s or 0.0)
        # prompt-lookup speculative decoding for greedy single-engine serving
        # (generate() ignores it for sampled requests and the batched tier)
        self.spec = int(spec)
        self.lock = locks.make_lock("api.single")
        self.model_name = "dllama-tpu"
        # continuous-batching tier: a serve/scheduler.Scheduler over a
        # BatchEngine — concurrent requests share the device, no global lock
        self.scheduler = scheduler
        # the order completions arrived in is the order they are submitted in
        self.arrivals = ArrivalOrder()
        # flipped by the SIGTERM drain sequence: new requests get 503 while
        # in-flight ones finish (single-engine tier included — the scheduler
        # has its own draining flag for its admission queue)
        self.draining = False
        # startup HBM gauges (model_params_bytes / kv_cache_bytes): account
        # the engine that actually serves — the BatchEngine owns the slot
        # cache on the continuous tier, loaded.engine on the single tier
        from dllama_tpu.utils.profiling import set_memory_gauges, state_nbytes

        eng = scheduler.engine if scheduler is not None else self.engine
        self.model_params_bytes, self.kv_cache_bytes = set_memory_gauges(
            eng.params, eng.cache)
        self.recurrent_state_bytes = state_nbytes(eng.cache)
        # build-info gauge (value always 1; the labels ARE the payload): what
        # exactly is serving — package + jax versions, the device as JAX
        # reports it, the resolved kernel route, and whether the overlapped
        # pipeline is live. Also embedded in /health so a probe answers
        # "what is this replica running on" without a scrape.
        import jax

        devices = jax.devices()
        self.build_info = {
            "version": __version__,
            "jax": jax.__version__,
            "backend": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": str(len(devices)),
            # KernelSelection.bucket_tag() of the engine that serves:
            # 'backend/attn_route', e.g. pallas/paged_kernel
            "kernels": eng.kernel_route,
            "overlap": ("n/a" if scheduler is None
                        else ("on" if scheduler.overlap else "off")),
            # boot precompile state (ISSUE 13): whether this replica warmed
            # its compiled-shape universe before taking traffic
            "warmup": ("n/a" if scheduler is None
                       else getattr(scheduler, "warmup", "off")),
        }
        ins.BUILD_INFO.labels(**self.build_info).set(1)
        # SLO policy for the /debug/requests/{req_id} postmortem verdict —
        # ONE policy object per process: the scheduler's aggregator owns it
        # on the continuous tier (it also burns the violation counters), the
        # api holds a standalone one on the single tier so postmortems still
        # get judged
        self.slo = (scheduler.perf.slo if scheduler is not None
                    else perfmod.SloPolicy(
                        None if slo_ttft_ms is None else float(slo_ttft_ms),
                        None if slo_itl_ms is None else float(slo_itl_ms)))

    # ---------------------------------------------------------------- health

    def health(self) -> dict:
        """Liveness/readiness payload for GET /health (and the /health/live,
        /health/ready sub-probes). The continuous-batching tier forwards the
        scheduler's supervision snapshot; the single-engine tier is live as
        long as the process answers."""
        if self.scheduler is not None:
            h = self.scheduler.health()
        else:
            h = {"live": True, "ready": True, "queue_depth": 0,
                 "busy_slots": 0, "n_slots": 0, "last_step_age_s": 0.0,
                 # compile observability rides the single tier's probe too
                 # (no warmup pass there — the batched scheduler owns it)
                 "compile": {
                     "warmup": "n/a",
                     "compiles": compile_obs.LEDGER.total_compiles(),
                     "unexpected_compiles":
                         compile_obs.LEDGER.total_unexpected(),
                 }}
        if self.draining:
            h["ready"] = False
            h["draining"] = True
        h["status"] = "ok" if h["live"] else "unhealthy"
        h["mode"] = "continuous" if self.scheduler is not None else "single"
        # HBM accounting rides the ready payload (and /metrics as gauges) so
        # capacity questions don't need a restart with --report
        h["model_params_bytes"] = self.model_params_bytes
        h["kv_cache_bytes"] = self.kv_cache_bytes
        h["recurrent_state_bytes"] = self.recurrent_state_bytes
        eng = self.scheduler.engine if self.scheduler is not None else None
        if eng is not None and eng.cfg.recurrent:
            # per-slot recurrent state stands at one row: what re-enters a
            # sequence at another resolved off at start-up (engine/batch.py)
            off = ["radix_cache", "kv_host_pages", "spec_k",
                   "cross_slot_prefix_copy", "preempt_to_pages"]
            h["recurrent_state"] = {
                "kind": eng.cfg.state_kind,
                "layers": eng.cfg.n_state_layers,
                "bytes": self.recurrent_state_bytes,
                "resolved_off": off}
            if not eng.cfg.n_attn_layers:
                # no layer holds cache rows: the page pool has a layer axis
                # of 0, a page costs nothing and block tables stand for
                # positions only; the same prefix features are off
                h["cache_rows"] = {"layers": 0, "resolved_off": off}
        if getattr(eng, "wpool", None) is not None:
            # windowed layers keep a page pool of their own: what follows one
            # page list a slot resolved off at start-up (engine/batch.py)
            h["window_pool"] = {
                "window": eng.cfg.window, "pages": eng.wpool.n_pages,
                "resolved_off": ["radix_cache", "kv_host_pages",
                                 "cross_slot_prefix_copy", "preempt_to_pages"]}
        pools = eng.pool_report() if eng is not None else None
        if pools is not None:
            # the page pools, by pool: layers, usable pages, device bytes
            h["kv_pools"] = pools
        h["build"] = self.build_info
        # process self-metrics ride every probe (and /metrics as gauges):
        # uptime answers "did it just restart", RSS + threads answer "is it
        # leaking" without a scrape pipeline
        h["process"] = ins.refresh_process_gauges()
        # NTP-lite clock payload (ISSUE 17): our monotonic clock at answer
        # time is the router's offset sample; the tracer epoch lets it map
        # our Chrome-export timestamps onto the mesh timeline
        h["clock"] = {"monotonic_s": time.monotonic(),
                      "trace_epoch_s": getattr(trace.TRACER, "epoch", None)}
        return h

    def precheck_capacity(self) -> None:
        """Raise the admission-control rejection a submit() would raise,
        WITHOUT submitting. Streaming handlers call this before the
        200/chunked headers go out, so an overloaded/draining server sheds
        stream requests with a clean 429/503 instead of a corrupted stream."""
        if self.draining:
            ins.REQUESTS_SHED.labels(reason="draining").inc()
            raise SchedulerDraining("server is draining")
        if self.scheduler is not None:
            self.scheduler.check_admission()

    # ------------------------------------------------------------------ core

    def complete(self, body: dict, emit=None, probe=None, req_id: str = "") -> dict:
        """Run one chat completion. `emit(text)` streams deltas when given.
        `probe()` (optional) returns True when the client socket is gone —
        polled during batched generation so a disconnected non-streaming
        client cancels its scheduler request instead of generating to
        completion into a dead socket. `req_id` tags the scheduler request
        (and thus the admission/finish log lines) with the HTTP request id.
        Returns the non-streaming response dict (also computed when
        streaming, for the final usage accounting)."""
        t_submit = time.monotonic()
        if self.scheduler is not None:
            # continuous-batching tier: one shared body parse (the same one
            # the aio front-end's SSE machine uses), then the blocking
            # submit/stream/finish loop
            p = self.prepare_request(body, legacy=False)
            content, finish, n_generated, timings = self._run_batched(
                p, emit, probe=probe, req_id=req_id)
            return {
                "timings": timings,
                "id": f"chatcmpl-{uuid.uuid4().hex[:16]}",
                "object": "chat.completion",
                "created": int(time.time()),
                "model": body.get("model", self.model_name),
                "choices": [
                    {
                        "index": 0,
                        "message": {"role": "assistant", "content": content},
                        "finish_reason": finish,
                    }
                ],
                "usage": {
                    "prompt_tokens": len(p["prompt_tokens"]),
                    "completion_tokens": n_generated,
                    "total_tokens": len(p["prompt_tokens"]) + n_generated,
                },
            }

        if body.get("resume") is not None:
            raise ApiError(400, "resume requires the batched scheduler tier")
        messages = [(m["role"], str(m["content"])) for m in body.get("messages", [])]
        if not messages:
            raise ApiError(400, "messages must be a non-empty array")
        temperature = float(body.get("temperature", self.defaults["temperature"]))
        topp = float(body.get("top_p", self.defaults["topp"]))
        # `or 0.0`: OpenAI treats an explicit JSON null as "use default"
        presence = float(body.get("presence_penalty") or 0.0)
        frequency = float(body.get("frequency_penalty") or 0.0)
        seed = body.get("seed", self.defaults["seed"])
        max_tokens = int(body.get("max_tokens") or body.get("max_completion_tokens") or 0)
        timeout_s = _parse_timeout(body)
        spec_k = _parse_spec_k(body)
        _parse_priority(body)  # accepted-but-inert on this tier: validate only
        _parse_tenant(body)
        extra_stops = body.get("stop") or []
        if isinstance(extra_stops, str):
            extra_stops = [extra_stops]

        self._trace_single_submit(req_id, t_submit)
        with self.lock:
            t_admit = time.monotonic()
            delta, start_pos, add_bos = self._resolve_prefix(messages)
            self.engine.reset(start_pos)
            generated = self.template.generate(
                [ChatItem(r, c) for r, c in delta], append_generation_prompt=True
            )
            prompt_tokens = self.tokenizer.encode(generated.content, add_bos=add_bos)
            budget, sampler = self._budget_and_sampler(
                len(prompt_tokens), max_tokens, temperature, topp, seed,
                presence, frequency)
            content, finish, n_generated, t_first = self._run_single(
                prompt_tokens, budget, sampler,
                self.stops + list(extra_stops), emit, probe=probe,
                deadline=None if timeout_s is None else t_submit + timeout_s,
                spec_k=spec_k)
            if finish == "timeout" and n_generated == 0:
                # expired on the engine lock: _run_single returned before
                # ANY engine work, so the pre-call cache state is still the
                # truth — recording the new conversation here would claim KV
                # rows that were never prefilled and make the next turn
                # resolve past a user message the model never saw
                pass
            else:
                # cache the full conversation incl. the reply for the next turn
                self.cache.messages = messages + [("assistant", content)]
                self.cache.pos = self.engine.pos
                self.cache.bos_sent = True
        timings = self._single_tier_timings(
            req_id, t_submit, t_admit, t_first, n_generated,
            len(prompt_tokens), start_pos, finish, timeout_s=timeout_s)

        return {
            "timings": timings,
            "id": f"chatcmpl-{uuid.uuid4().hex[:16]}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": body.get("model", self.model_name),
            "choices": [
                {
                    "index": 0,
                    "message": {"role": "assistant", "content": content},
                    "finish_reason": finish,
                }
            ],
            "usage": {
                "prompt_tokens": len(prompt_tokens),
                "completion_tokens": n_generated,
                "total_tokens": len(prompt_tokens) + n_generated,
            },
        }

    @staticmethod
    def _normalize_legacy_prompt(body: dict) -> str:
        """The legacy endpoint's prompt field: a string or a 1-element list
        of strings. One definition serves prevalidate and complete_legacy."""
        prompt = body.get("prompt")
        if isinstance(prompt, list):
            if len(prompt) != 1:
                raise ApiError(400, "only a single prompt is supported")
            prompt = prompt[0]
        if not isinstance(prompt, str) or not prompt:
            raise ApiError(400, "prompt must be a non-empty string")
        return prompt

    def prevalidate(self, body: dict, legacy: bool = False) -> None:
        """Raise ApiError for request-shape problems that can be detected
        without touching the engine (used before streaming headers are
        sent — a failure after the 200/chunked headers would corrupt the
        stream). Deeper failures (context window) still surface as HTTP 4xx
        on the non-streaming path."""
        _parse_timeout(body)  # a malformed timeout_s is a clean 400 too
        _parse_spec_k(body)  # ...and a malformed spec_k
        _parse_priority(body)  # ...and a malformed priority
        _parse_tenant(body)  # ...and a malformed tenant
        if legacy:
            self._normalize_legacy_prompt(body)
            return
        messages = body.get("messages")
        if (not isinstance(messages, list) or not messages
                or not all(isinstance(m, dict) and "role" in m and "content" in m
                           for m in messages)):
            raise ApiError(400, "messages must be a non-empty array of "
                                "{role, content} objects")

    def _resolve_prefix(self, messages):
        """(delta messages, start_pos, add_bos) of a conversation against
        the prefix cache, clipped to where the engine can resume: a model
        with recurrent state continues only where that state stands, else
        the whole conversation is fed again from row 0."""
        delta, start_pos, add_bos = self.cache.resolve(messages)
        if start_pos and not self.engine.can_resume_at(start_pos):
            ins.PREFIX_ROWS_RECOMPUTED.labels(
                reason="state_elsewhere").inc(start_pos)
            delta, start_pos, add_bos = messages, 0, True
        if start_pos == 0:
            self.cache.clear()
        return delta, start_pos, add_bos

    def _budget_and_sampler(self, prompt_len, max_tokens, temperature, topp,
                            seed, presence, frequency):
        """Shared single-engine budget clamp + Sampler construction (the
        seed-or-wallclock fallback must never diverge between endpoints)."""
        budget = self.engine.seq_len - self.engine.pos - prompt_len - 1
        if budget <= 0:
            raise ApiError(400, "context window exhausted")
        if max_tokens > 0:
            budget = min(budget, max_tokens)
        sampler = Sampler(temperature, topp,
                          seed if seed is not None else int(time.time()),
                          presence=presence, frequency=frequency)
        return budget, sampler

    @staticmethod
    def _trace_single_submit(req_id: str, t_submit: float) -> None:
        """Single-engine tier flight-recorder entry: on this tier the
        'queue' is the global engine lock, so submit is the handler entry
        (the batched tier records through the scheduler instead)."""
        tr = trace.TRACER
        if tr.enabled and req_id:
            tr.req_submit(req_id, t=t_submit)

    def _single_tier_timings(self, req_id, t_submit, t_admit, t_first,
                             n_generated, prompt_len, reused, finish,
                             timeout_s=None) -> dict:
        """Build the response `timings` object for a single-engine completion
        and close out its flight-recorder record (lock wait plays the role
        of queue wait; prefill has no separate mark on this tier — TTFT
        covers it). Deadline fields mirror the batched tier's
        Request.timings(): present whenever the request carried a deadline,
        so clients keying on `deadline_exceeded` behave the same on both
        serving tiers."""
        t_done = time.monotonic()
        timings = {
            "queue_wait_ms": round((t_admit - t_submit) * 1000.0, 3),
            "ttft_ms": (None if t_first is None
                        else round((t_first - t_submit) * 1000.0, 3)),
            "e2e_ms": round((t_done - t_submit) * 1000.0, 3),
            "decode_tokens": n_generated,
        }
        if timeout_s is not None:
            timings["timeout_s"] = timeout_s
            timings["deadline_exceeded"] = finish == "timeout"
        if self.replica_id:
            timings["replica"] = self.replica_id
        tr = trace.TRACER
        if tr.enabled and req_id:
            tr.req_admitted(req_id, t=t_admit)
            tr.req_mark(req_id, prompt_tokens=prompt_len,
                        reused_tokens=reused)
            if t_first is not None:
                tr.req_first_token(req_id, t=t_first)
            if finish == "timeout":
                # same postmortem breadcrumb the scheduler leaves: on this
                # tier "queued" means the deadline expired on the lock wait
                tr.event("request.timeout", cat="deadline", track="requests",
                         req_id=req_id,
                         where="queued" if n_generated == 0 else "decoding")
            tr.req_end(req_id, finish, t=t_done, **timings)
        return timings

    def _run_single(self, prompt_tokens, budget, sampler, stops, emit,
                    probe=None, deadline=None,
                    spec_k=None) -> tuple[str, str, int, float | None]:
        """Token loop of a single-engine completion (generate + EOS/stop
        detection + held-prefix flush) -> (content, finish_reason, n_tokens,
        first_token_monotonic_or_None — the TTFT mark of the `timings`
        response object).
        Shared by the chat and legacy endpoints — caller holds self.lock and
        has positioned the engine. `probe` (dead-client check) aborts the
        generation via ClientDisconnected — on THIS tier a dead request
        holds the global engine lock, so cancelling it unblocks every other
        client, not just a slot. The engine is left mid-generation; the next
        request's reset()/prefix-cache miss rewrites those rows."""
        if deadline is not None and time.monotonic() >= deadline:
            # expired while waiting on the engine lock (this tier's
            # "queue"): return before ANY engine work — no prefill, no
            # decode — matching the batched tier's expired-in-queue shed
            return "", "timeout", 0, None
        detector = EosDetector(self.tokenizer.eos_ids, stops,
                               padding_left=2, padding_right=2)
        self.tokenizer.reset_decoder()
        parts: list[str] = []
        n_generated = 0
        finish = "length"
        t_first = None
        timed_out = False
        probe_at = time.monotonic() + 0.25
        # per-request spec_k on this tier clamps to the CLI --spec
        # capacity, same contract as the batched tier (the engine caches
        # one compiled decoder per distinct k, bounded by --spec values)
        spec = self.spec if spec_k is None else min(int(spec_k), self.spec)
        for t in self.engine.generate(prompt_tokens, budget, sampler,
                                      spec=spec):
            if t_first is None:
                t_first = time.monotonic()
            if probe is not None and time.monotonic() >= probe_at:
                probe_at = time.monotonic() + 0.25
                if probe():
                    raise ClientDisconnected()
            n_generated += 1
            res = detector.append(t, self.tokenizer.decode(t))
            text = detector.get_delta()
            if text:
                parts.append(text)
                if emit is not None:
                    emit(text)
            if res == EosResult.EOS:
                finish = "stop"
                break
            if deadline is not None and time.monotonic() >= deadline:
                # per-request deadline on the single-engine tier: the lock
                # wait (this tier's "queue") counts toward it — a clean
                # terminal finish, never an error
                finish = "timeout"
                timed_out = True
                break
        else:
            # budget exhausted mid-held-prefix: the partial stop never completes
            text = detector.flush()
            if text:
                parts.append(text)
                if emit is not None:
                    emit(text)
        if timed_out:
            # flush any held stop-prefix like the budget path: what was
            # generated is delivered, just cut short
            text = detector.flush()
            if text:
                parts.append(text)
                if emit is not None:
                    emit(text)
        return "".join(parts), finish, n_generated, t_first

    def prepare_request(self, body: dict, legacy: bool = False) -> dict:
        """Parse a completions body into submit-ready params — ONE parser
        for the blocking batched tier and the aio front-end's SSE machine
        (serve/aio.py), so the two can never drift. Raises ApiError for
        shape problems; stream callers therefore run it BEFORE response
        headers go out. Returns the kwargs of :meth:`batched_submit` plus
        ``stops`` (chat adds the template stops; the legacy raw-prompt
        endpoint uses only explicit ones)."""
        temperature = float(body.get("temperature", self.defaults["temperature"]))
        topp = float(body.get("top_p", self.defaults["topp"]))
        # `or 0.0`: OpenAI treats an explicit JSON null as "use default"
        presence = float(body.get("presence_penalty") or 0.0)
        frequency = float(body.get("frequency_penalty") or 0.0)
        seed = body.get("seed", self.defaults["seed"])
        timeout_s = _parse_timeout(body)
        spec_k = _parse_spec_k(body)
        priority = _parse_priority(body)
        tenant = _parse_tenant(body)
        extra_stops = body.get("stop") or []
        if isinstance(extra_stops, str):
            extra_stops = [extra_stops]
        if legacy:
            prompt = self._normalize_legacy_prompt(body)
            prompt_tokens = self.tokenizer.encode(prompt, add_bos=True)
            stops = list(extra_stops)
            max_tokens = int(body.get("max_tokens") or 16)  # OpenAI legacy default
        else:
            messages = [(m["role"], str(m["content"]))
                        for m in body.get("messages", [])]
            if not messages:
                raise ApiError(400, "messages must be a non-empty array")
            generated = self.template.generate(
                [ChatItem(r, c) for r, c in messages],
                append_generation_prompt=True)
            prompt_tokens = self.tokenizer.encode(generated.content,
                                                  add_bos=True)
            stops = self.stops + list(extra_stops)
            max_tokens = int(body.get("max_tokens")
                             or body.get("max_completion_tokens") or 0)
        # mid-stream failover support (ISSUE 16): `include_token_ids` makes
        # every SSE frame carry the raw (position, token_ids) it consumed
        # (the router injects it so it can journal resume state); `resume`
        # re-enters a journaled stream on THIS replica — the emitted prefix
        # re-prefills via the radix/resume_commit path and the PRNG chain is
        # replayed from the request seed, so the continuation is bit-exact
        resume = body.get("resume")
        resume_tokens = resume_id = resume_created = None
        if resume is not None:
            if not isinstance(resume, dict):
                raise ApiError(400, "resume must be an object")
            # EMPTY tokens is legal: a stream that died after its role
            # delta but before any token resumes with tokens=[] purely to
            # keep its id/created and suppress the duplicate role delta
            toks = resume.get("tokens")
            if (not isinstance(toks, list)
                    or not all(isinstance(t, int) for t in toks)):
                raise ApiError(400, "resume.tokens must be an int array")
            if temperature > 0.0 and seed is None:
                # an unseeded sampled stream has no replayable key chain —
                # the router pins a seed at first proxy precisely so its
                # journal stays resumable; reject rather than silently
                # diverge from the already-emitted prefix
                raise ApiError(
                    400, "sampled resume requires the original seed")
            resume_tokens = [int(t) for t in toks]
            resume_id = str(resume.get("id") or "")
            resume_created = int(resume.get("created") or 0)
        return dict(prompt_tokens=prompt_tokens, stops=stops,
                    temperature=temperature, topp=topp,
                    max_tokens=max_tokens, seed=seed, presence=presence,
                    frequency=frequency, timeout_s=timeout_s, spec_k=spec_k,
                    priority=priority, tenant=tenant,
                    token_ids=bool(body.get("include_token_ids")),
                    resume_tokens=resume_tokens, resume_id=resume_id,
                    resume_created=resume_created)

    def batched_submit(self, p: dict, req_id: str = ""):
        """Budget-clamp + submit one parsed request (prepare_request's dict)
        to the scheduler -> the live Request. Shared by the blocking tier
        and the aio SSE machine; raises ApiError when the context window
        cannot fit the prompt, and the SchedulerRejected family on
        admission shed."""
        prompt_tokens = p["prompt_tokens"]
        budget = self.scheduler.engine.seq_len - len(prompt_tokens) - 1
        if budget <= 0:
            raise ApiError(400, "context window exhausted")
        if p["max_tokens"] > 0:
            budget = min(budget, p["max_tokens"])
        seed = p["seed"]
        with self.arrivals.turn():
            return self.scheduler.submit(
                prompt_tokens, p["temperature"], p["topp"], budget,
                self.tokenizer.eos_ids,
                presence=p["presence"], frequency=p["frequency"],
                seed=int(seed) if seed is not None else None,
                req_id=req_id, timeout_s=p["timeout_s"],
                # None = the --spec-k serving default (the engine's compiled K);
                # the scheduler clamps explicit values to that capacity
                spec_k=p["spec_k"],
                # scheduling class + fair-queue tenant (ISSUE 12): the
                # scheduler's policy pick and preemption read these
                priority=p["priority"], tenant=p["tenant"],
                # cross-replica failover (ISSUE 16): the journaled emitted
                # prefix to re-prefill before the stream continues
                resume_tokens=p.get("resume_tokens"),
            )

    def finish_batched(self, req, ended_on_eos: bool,
                       n_generated: int) -> tuple[str, dict]:
        """Release a batched request's slot and derive the client-facing
        (finish_reason, timings) pair — the one finalization site for the
        blocking tier and the aio SSE machine. A release after the detector
        saw a string stop-sequence is a SUCCESSFUL stop, not a client
        cancellation — labeled so the finished{reason} metric matches what
        the client is told."""
        self.scheduler.cancel(
            req, reason="stop" if ended_on_eos else "cancelled")
        # scheduler reasons: stop/length/timeout pass through; a cancel here
        # means the stream ended on a string stop-sequence -> "stop"
        finish = (req.finish_reason
                  if req.finish_reason in ("stop", "length", "timeout")
                  else "stop")
        timings = req.timings()
        if timings["e2e_ms"] is None:
            # a stop-string release is finalized asynchronously by the worker;
            # from the client's seat the request is over NOW
            timings["e2e_ms"] = round(
                (time.monotonic() - req.submitted_at) * 1000.0, 3)
        # what the CLIENT received — the scheduler's `produced` may include
        # a stop-string overrun token the stream never surfaced
        timings["decode_tokens"] = n_generated
        if self.replica_id:
            # end-to-end attribution through the router (ISSUE 15): which
            # replica actually served this stream
            timings["replica"] = self.replica_id
        return finish, timings

    def _run_batched(self, p: dict, emit, probe=None,
                     req_id: str = "") -> tuple[str, str, int, dict]:
        """Token-level core of a BLOCKING batched completion: submit, stream-
        decode with EOS/stop detection, return (content, finish_reason,
        n_tokens, timings) — `timings` is the request's span-sourced latency
        object (queue wait / TTFT / e2e / token count) for the response
        body. `p` is prepare_request's dict. The aio front-end runs the same
        submit/assemble/finish seams cooperatively instead (serve/aio.py)."""
        asm = TokenAssembler(self.tokenizer, p["stops"])
        want_ids = bool(p.get("token_ids"))
        resume = p.get("resume_tokens")
        if resume:
            # failover re-entry (ISSUE 16): replay the journaled prefix
            # through a FRESH assembler so the stop detector / incremental
            # decoder reach the exact state the dead replica held — without
            # re-emitting anything (those deltas already reached the
            # client; the journal records only relayed frames). The
            # take_ids() drain keeps the position counter continuous, so
            # the continuation's first frame carries position = len(resume).
            for t in resume:
                asm.feed(t)
                if asm.eos:
                    break
            asm.take_ids()
            if asm.eos:
                # the journaled tokens already complete a stop sequence
                # (the replica died between the stop-completing frame and
                # its finish frame): the stream is over — finish now, no
                # engine work left
                timings: dict = {"e2e_ms": 0.0, "decode_tokens": 0}
                if self.replica_id:
                    timings["replica"] = self.replica_id
                return asm.content(), "stop", asm.n, timings
        req = self.batched_submit(p, req_id=req_id)
        probe_at = time.monotonic() + 0.25

        def probe_tick():
            # runs from tokens() whenever the stream goes quiet (queued,
            # mid-prefill, stalled device): a dead client cancels even
            # before its first token exists
            if probe():
                raise ClientDisconnected()

        try:
            for t in req.tokens(poll=probe_tick if probe is not None else None):
                if probe is not None and time.monotonic() >= probe_at:
                    # ...and at 4 Hz while tokens ARE flowing (a select()+
                    # MSG_PEEK syscall per token would dominate small models;
                    # this bounds wasted generation to a quarter second)
                    probe_at = time.monotonic() + 0.25
                    if probe():
                        raise ClientDisconnected()
                text = asm.feed(t)
                if text and emit is not None:
                    if want_ids:
                        emit(text, ids=asm.take_ids())
                    else:
                        emit(text)
                if asm.eos:
                    break
            if not asm.eos:
                text = asm.flush()
                if text and emit is not None:
                    if want_ids:
                        emit(text, ids=asm.take_ids())
                    else:
                        emit(text)
            finish, timings = self.finish_batched(req, asm.eos, asm.n)
        except BaseException:
            # disconnect/shed/crash: the slot must still be released, with
            # the honest "cancelled"/terminal reason (finish_batched's
            # labeling only applies to streams that ended cleanly)
            self.scheduler.cancel(
                req, reason="stop" if asm.eos else "cancelled")
            raise
        return asm.content(), finish, asm.n, timings

    def complete_legacy(self, body: dict, emit=None, probe=None,
                        req_id: str = "") -> dict:
        """POST /v1/completions — the pre-chat OpenAI surface some clients
        still speak: a RAW prompt string, no chat template, `text` in the
        choices. Shares the sampling params and generation machinery with
        the chat endpoint."""
        t_submit = time.monotonic()
        if self.scheduler is not None:
            # continuous-batching tier: one shared body parse (the same one
            # the aio SSE machine uses) — no duplicate prompt tokenization
            p = self.prepare_request(body, legacy=True)
            prompt_tokens = p["prompt_tokens"]
            content, finish, n_generated, timings = self._run_batched(
                p, emit, probe=probe, req_id=req_id)
        else:
            if body.get("resume") is not None:
                raise ApiError(
                    400, "resume requires the batched scheduler tier")
            prompt = self._normalize_legacy_prompt(body)
            temperature = float(body.get("temperature",
                                         self.defaults["temperature"]))
            topp = float(body.get("top_p", self.defaults["topp"]))
            presence = float(body.get("presence_penalty") or 0.0)
            frequency = float(body.get("frequency_penalty") or 0.0)
            seed = body.get("seed", self.defaults["seed"])
            max_tokens = int(body.get("max_tokens") or 16)  # legacy default
            timeout_s = _parse_timeout(body)
            spec_k = _parse_spec_k(body)
            _parse_priority(body)  # accepted-but-inert: validate only
            _parse_tenant(body)
            extra_stops = body.get("stop") or []
            if isinstance(extra_stops, str):
                extra_stops = [extra_stops]
            prompt_tokens = self.tokenizer.encode(prompt, add_bos=True)
            self._trace_single_submit(req_id, t_submit)
            with self.lock:
                t_admit = time.monotonic()
                # raw-prompt rows overwrite the chat prefix cache's claim
                self.cache.clear()
                self.engine.reset(0)
                budget, sampler = self._budget_and_sampler(
                    len(prompt_tokens), max_tokens, temperature, topp, seed,
                    presence, frequency)
                # legacy endpoint: no chat stop strings, only explicit ones
                content, finish, n_generated, t_first = self._run_single(
                    prompt_tokens, budget, sampler, list(extra_stops), emit,
                    probe=probe,
                    deadline=(None if timeout_s is None
                              else t_submit + timeout_s),
                    spec_k=spec_k)
            timings = self._single_tier_timings(
                req_id, t_submit, t_admit, t_first, n_generated,
                len(prompt_tokens), 0, finish, timeout_s=timeout_s)

        return {
            "timings": timings,
            "id": f"cmpl-{uuid.uuid4().hex[:16]}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": body.get("model", self.model_name),
            "choices": [
                {"index": 0, "text": content, "logprobs": None,
                 "finish_reason": finish}
            ],
            "usage": {
                "prompt_tokens": len(prompt_tokens),
                "completion_tokens": n_generated,
                "total_tokens": len(prompt_tokens) + n_generated,
            },
        }

    def models(self) -> dict:
        return {
            "object": "list",
            "data": [
                {
                    "id": self.model_name,
                    "object": "model",
                    "created": int(time.time()),
                    "owned_by": "dllama-tpu",
                }
            ],
        }


class ApiError(Exception):
    def __init__(self, status: int, message: str):
        super().__init__(message)
        self.status = status
        self.message = message


#: path -> bounded-cardinality endpoint label for the HTTP response counter
_KNOWN_PATHS = {
    "/v1/chat/completions": "/v1/chat/completions",
    "/chat/completions": "/v1/chat/completions",
    "/v1/completions": "/v1/completions",
    "/completions": "/v1/completions",
    "/v1/models": "/v1/models",
    "/health": "/health",
    "/health/live": "/health/live",
    "/health/ready": "/health/ready",
    "/metrics": "/metrics",
    "/debug/trace": "/debug/trace",
    "/debug/requests": "/debug/requests",
    "/debug/profile": "/debug/profile",
    "/debug/kv": "/debug/kv",
    "/debug/perf": "/debug/perf",
    "/debug/radix": "/debug/radix",
    "/debug/compile": "/debug/compile",
}


def _endpoint(path: str) -> str:
    """Label-safe endpoint name (unknown paths collapse to 'other' so a
    scanner can't explode the label cardinality; per-request flight-recorder
    lookups collapse their req_id for the same reason)."""
    if path.startswith("/debug/requests/"):
        return "/debug/requests/{req_id}"
    return _KNOWN_PATHS.get(path, "other")


#: SSE comment frame (spec: lines starting with ':' are ignored by
#: EventSource parsers) — the keep-alive heartbeat idle streams emit so a
#: router/LB idle timeout cannot kill a slow-decode stream (ISSUE 15)
SSE_HEARTBEAT = b": keep-alive\n\n"


def sse_chat_payload(cid: str, created: int, model: str, delta: dict,
                     finish=None, timings=None, ids=None) -> bytes:
    """One `chat.completion.chunk` SSE data frame — single definition for
    the blocking `_stream` and the aio SSE machine (byte-identical events
    on both front-ends). ``ids`` (``include_token_ids`` requests only) is
    TokenAssembler.take_ids()'s ``(position, token_ids)`` — the raw ids
    this frame's text consumed plus their stream offset, which is what the
    router journals for mid-stream failover (ISSUE 16)."""
    data = {
        "id": cid,
        "object": "chat.completion.chunk",
        "created": created,
        "model": model,
        "choices": [{"index": 0, "delta": delta, "finish_reason": finish}],
    }
    if ids is not None:
        data["position"], data["token_ids"] = ids[0], list(ids[1])
    if timings is not None:
        # the final (done) event carries the request's span-sourced
        # latency summary, like the non-stream response body
        data["timings"] = timings
    return b"data: " + json.dumps(data).encode() + b"\n\n"


def sse_text_payload(cid: str, created: int, model: str, text: str,
                     finish=None, timings=None, ids=None) -> bytes:
    """One legacy `text_completion` SSE data frame (see sse_chat_payload)."""
    data = {
        "id": cid,
        "object": "text_completion",
        "created": created,
        "model": model,
        "choices": [{"index": 0, "text": text, "finish_reason": finish}],
    }
    if ids is not None:
        data["position"], data["token_ids"] = ids[0], list(ids[1])
    if timings is not None:
        data["timings"] = timings
    return b"data: " + json.dumps(data).encode() + b"\n\n"


class RequestRoutes:
    """Transport-neutral HTTP route handling — every endpoint the serving
    surface speaks (completions, models, health probes, /metrics, the
    /debug family, SSE streaming), written against a SIX-method transport
    seam so the thread-per-connection tier (`_Handler`, stdlib
    BaseHTTPRequestHandler) and the selectors event-loop tier
    (serve/aio.py's context) serve byte-identical semantics from one
    definition site. Subclasses provide:

    * ``_send_raw(status, headers, body)`` — one complete response;
    * ``_start_sse()`` — the 200/chunked SSE response headers;
    * ``_write_chunk(payload)`` — one chunked-transfer frame (b"" ends);
    * ``_read_body()`` — the POST body bytes (may raise ValueError/OSError);
    * ``_drain_body()`` — keep-alive discipline for GETs with bodies;
    * ``_client_gone()`` — the disconnect probe.

    plus ``path``/``headers`` attributes of the current request."""

    api: ApiServer  # set by make_handler / the aio context
    _req_id: str | None = None  # minted per POST in do_POST
    path: str = ""

    def _send_json(self, status: int, payload: dict,
                   headers: dict | None = None) -> None:
        rid = self._req_id
        if rid and isinstance(payload.get("error"), dict):
            # error bodies carry the id too (429/503/500 included) so a
            # client-side report alone is enough to find the server logs
            payload["error"].setdefault("request_id", rid)
        data = json.dumps(payload).encode()
        hdrs = [("Content-Type", "application/json"),
                ("Content-Length", str(len(data)))]
        if rid:
            hdrs.append(("X-Request-Id", rid))
        if self.api.replica_id:
            # which replica answered — the router forwards it to the client
            # for end-to-end attribution (ISSUE 15)
            hdrs.append(("X-Replica-Id", self.api.replica_id))
        hdrs.extend((headers or {}).items())
        self._send_raw(status, hdrs, data)

    def do_GET(self):
        self._req_id = None
        if self.path == "/v1/models":
            self._send_json(200, self.api.models())
        elif self.path == "/metrics":
            # Prometheus text exposition of the process-global registry —
            # served from this (threaded) handler, so scrapes proceed while
            # completions run. Scrape-time refresh keeps the windowed/derived
            # gauges (latency quantiles, SLO attainment, roofline, process
            # self-metrics) current without putting their aggregation on the
            # serving hot path.
            ins.refresh_process_gauges()
            compile_obs.refresh_device_gauges()
            if self.api.scheduler is not None:
                self.api.scheduler.ledger.poke()
                self.api.scheduler.perf.refresh_gauges()
            body = metrics.REGISTRY.render().encode()
            self._send_raw(
                200,
                [("Content-Type", "text/plain; version=0.0.4; charset=utf-8"),
                 ("Content-Length", str(len(body)))],
                body)
        elif self.path.startswith("/debug/"):
            # the /debug family never touches admission (no request id is
            # minted, no scheduler counter moves) — pure read-side
            # observability plus the profiler trigger on the POST path
            self._drain_body()  # same keep-alive discipline as do_POST
            self._debug_get()
        elif self.path in ("/health", "/health/live", "/health/ready"):
            # /health: full snapshot, status by liveness (a restart signal);
            # /health/live and /health/ready: the k8s-style split probes —
            # ready goes 503 under drain/saturation while live stays 200,
            # so balancers stop routing without the supervisor killing us
            h = self.api.health()
            key = "ready" if self.path.endswith("/ready") else "live"
            self._send_json(200 if h[key] else 503, h)
        else:
            self._send_json(404, {"error": {"message": "not found"}})

    def _debug_kv(self) -> None:
        """GET /debug/kv — paged KV pool occupancy plus a full
        PagePool.audit() run on demand: the operator's allocator-integrity
        probe (refcounts vs block tables, free-list disjointness, gauge
        consistency). 200 with audit.ok=true when clean; 500 when the audit
        found corruption (alertable). Works without the span tracer."""
        sched = self.api.scheduler
        pool = (getattr(sched.engine, "pool", None)
                if sched is not None else None)
        if pool is None:
            self._send_json(200, {"layout": "dense", "pool": None,
                                  "audit": None})
            return
        report = pool.audit(raise_on_fail=False)
        self._send_json(200 if report["ok"] else 500,
                        {"layout": "paged", "page_size": pool.page_size,
                         "pool": pool.stats(), "audit": report,
                         # radix prefix-tree occupancy rides the allocator
                         # probe (the audit above already reconciled the
                         # tree's page refs against the pool refcounts)
                         "radix": sched.engine.radix_stats()
                         if hasattr(sched.engine, "radix_stats") else None})

    def _debug_radix(self) -> None:
        """GET /debug/radix — the cross-request prefix tree: cumulative
        hit/saved-token accounting plus a bounded dump of the live tree
        (page-granular edges, page ids, last-use ages). enabled=false on
        the dense layout, with --radix-cache off, or on the single-engine
        tier. Works without the span tracer."""
        sched = self.api.scheduler
        radix = (getattr(sched.engine, "radix", None)
                 if sched is not None else None)
        if radix is None:
            self._send_json(200, {"enabled": False, "stats": None,
                                  "tree": None})
            return
        self._send_json(200, {"enabled": True, "page_size": radix.page,
                              "stats": radix.stats(), "tree": radix.dump()})

    def _debug_perf(self) -> None:
        """GET /debug/perf — the ISSUE 7 join, one JSON document: sliding-
        window TTFT/ITL/e2e p50/p95/p99, SLO targets/attainment/burn totals,
        the scheduler time ledger (per-state seconds + fractions of loop
        wall time), roofline/goodput attribution of the decode path, and
        the process self-metrics. Works without the span tracer; the
        single-engine tier answers with mode=single and no scheduler views
        (it has no worker loop to ledger)."""
        sched = self.api.scheduler
        payload: dict = {"process": ins.refresh_process_gauges()}
        if sched is None:
            payload.update({
                "mode": "single",
                "slo": {"targets": {"ttft_ms": self.api.slo.ttft_ms,
                                    "itl_ms": self.api.slo.itl_ms},
                        "enabled": self.api.slo.enabled()},
            })
        else:
            sched.ledger.poke()  # bill the open span: a long idle park must
            # read as idle seconds now, not at the next state transition
            sched.perf.refresh_gauges()  # /metrics and this JSON agree
            payload["mode"] = "continuous"
            payload.update(sched.perf.snapshot(ledger=sched.ledger,
                                               phases=sched.phases))
            # saved-prefill accounting (radix prefix cache; None when off):
            # hit_tokens are prompt rows that cost zero prefill FLOPs
            payload["radix"] = (sched.engine.radix_stats()
                                if hasattr(sched.engine, "radix_stats")
                                else None)
            # speculative-decoding acceptance record (None when --spec-k
            # 0): tokens_per_cycle = realized tokens per verify forward
            payload["spec"] = (sched.engine.spec_stats()
                               if hasattr(sched.engine, "spec_stats")
                               else None)
            # the latent sweep's plan (pages a pass, the ring's passes, VMEM
            # bytes; decode call and slice), fixed when the engine was built
            # from its shapes; None where no latent call runs on the kernel
            payload["paged_latent_plan"] = getattr(sched.engine, "latent_plan",
                                                   None)
            # hybrid chunked-prefill + preemption state (ISSUE 12): the
            # live budget and the lifetime preempt/resume record
            payload["hybrid"] = {
                "prefill_budget": getattr(sched, "_budget_now", 0),
                "preemptions": getattr(sched, "preempt_count", 0),
                "resumed": getattr(sched, "resume_count", 0),
            }
        # compile-ledger summary (ISSUE 13; both tiers — the ledger is
        # process-global): compiles/seconds/unexpected + warmup state; the
        # full dump lives at GET /debug/compile
        payload["compile"] = compile_obs.LEDGER.summary()
        # what the engine launched inside the last finished profiler
        # capture (ISSUE 26): launches by kind, slot-steps by state, KV and
        # prompt rows by kind — the rows behind that trace's device plane
        from dllama_tpu.utils import profiling

        payload["capture"] = profiling.last_capture()
        self._send_json(200, payload)

    def _debug_compile(self) -> None:
        """GET /debug/compile — the ISSUE 13 join, one JSON document: the
        jit compile ledger (per-fn totals + recent entries with shape
        signatures), shape-bucket contract coverage (declared / compiled /
        missing-warm / unexpected-seen per fn), the boot warmup report,
        host<->device transfer tallies by direction+site, and live device
        memory. Works without the span tracer; tier-independent (the
        ledger and transfer counters are process-global)."""
        sched = self.api.scheduler
        self._send_json(200, compile_obs.debug_payload(
            warmup_report=(sched.warmup_report if sched is not None
                           else None)))

    def _debug_get(self) -> None:
        """GET /debug/trace (Chrome trace-event JSON for Perfetto),
        GET /debug/requests (flight-recorder summaries),
        GET /debug/requests/{req_id} (one request's full timeline), and
        GET /debug/kv (paged-pool occupancy + on-demand audit)."""
        if self.path == "/debug/kv":
            self._debug_kv()  # independent of the span tracer
            return
        if self.path == "/debug/perf":
            self._debug_perf()  # also tracer-independent (registry + ledger)
            return
        if self.path == "/debug/radix":
            self._debug_radix()  # tracer-independent (tree + counters)
            return
        if self.path == "/debug/compile":
            self._debug_compile()  # tracer-independent (ledger + counters)
            return
        tr = trace.TRACER
        if not tr.enabled:
            self._send_json(404, {"error": {
                "message": "tracing is disabled; restart with "
                           "--trace-buffer N > 0"}})
            return
        if self.path == "/debug/trace":
            self._send_json(200, tr.export_chrome())
        elif self.path == "/debug/requests":
            self._send_json(200, {"requests": tr.requests_summary()})
        elif self.path.startswith("/debug/requests/"):
            rid = self.path[len("/debug/requests/"):]
            rec = tr.request_timeline(rid)
            if rec is None:
                self._send_json(404, {"error": {
                    "message": f"no flight-recorder entry for {rid!r} "
                               "(never seen, or evicted from the ring)"}})
            else:
                # postmortem SLO verdict from the record's own latency marks
                # (ttft/e2e/decode_tokens — ITL derived the same way
                # Request.itl_ms derives it), judged against the configured
                # targets; all-None verdicts when no SLO is configured
                rec["slo"] = self.api.slo.verdict_from_marks(
                    rec.get("ttft_ms"), rec.get("e2e_ms"),
                    rec.get("decode_tokens"))
                self._send_json(200, rec)
        else:
            self._send_json(404, {"error": {"message": "not found"}})

    def _log_done(self, rid: str, result: dict) -> None:
        u = result.get("usage", {})
        log.info("completion %s done: %d prompt + %d completion tokens",
                 rid, u.get("prompt_tokens", 0), u.get("completion_tokens", 0),
                 extra=trace.log_extra(rid))

    def do_POST(self):
        # the request id is minted at ADMISSION — before any outcome is
        # known — so even a request shed with 429/503 has a correlatable id
        # in its response headers and in the shed log line below
        rid = self._req_id = new_request_id(self.headers.get("X-Request-Id"))
        chat = self.path in ("/v1/chat/completions", "/chat/completions")
        legacy = self.path in ("/v1/completions", "/completions")
        # distributed trace context (ISSUE 17): a router hop header joins
        # this replica's spans/flight record to the mesh-wide trace — the
        # mark lands before admission so even shed requests correlate
        hopctx = trace.parse_hop(self.headers.get(trace.HOP_HEADER))
        if hopctx is not None and (chat or legacy):
            trace.TRACER.req_mark(rid, trace_id=hopctx[0],
                                  parent_span=hopctx[1], hop=hopctx[2])
        # the body is consumed BEFORE any early-return response: on the
        # keep-alive (HTTP/1.1) thread tier, unread body bytes would be
        # parsed as the NEXT request line — a 404'd POST must not poison its
        # connection (the aio tier buffers the body up front; same contract)
        try:
            raw = self._read_body()
        except (ValueError, OSError):
            self._send_json(400, {"error": {"message": "invalid request"}})
            return
        if self.path == "/debug/profile":
            # not a serving request: no request id, no admission counters,
            # usable even mid-drain (that is when postmortems happen) — but
            # the body was drained above like any POST on this keep-alive
            # server
            self._req_id = None
            self._handle_profile(raw)
            return
        if not (chat or legacy):
            self._send_json(404, {"error": {"message": "not found"}})
            return
        try:
            body = json.loads(raw or b"{}")
        except (ValueError, json.JSONDecodeError):
            self._send_json(400, {"error": {"message": "invalid JSON body"}})
            return
        tmo_hdr = self.headers.get("X-Request-Timeout")
        if tmo_hdr is not None and isinstance(body, dict) \
                and "timeout_s" not in body:
            # header form of the per-request deadline (proxies/gateways set
            # it without touching the JSON body); an explicit body field wins
            body["timeout_s"] = tmo_hdr
        try:
            if self.api.draining:
                ins.REQUESTS_SHED.labels(reason="draining").inc()
                raise SchedulerDraining("server is draining")
            if body.get("stream"):
                # cheap validation BEFORE the 200/chunked headers go out — an
                # ApiError raised mid-stream would write a second status line
                # into the chunk stream (a protocol violation). Capacity is
                # prechecked for the same reason: overload sheds as a clean
                # 429/503, not a poisoned stream.
                self.api.prevalidate(body, legacy=legacy)
                self.api.precheck_capacity()
                self._stream(body, legacy=legacy)
            elif legacy:
                result = self.api.complete_legacy(
                    body, probe=self._client_gone, req_id=rid)
                result["request_id"] = rid
                self._log_done(rid, result)  # logged before the body goes out
                self._send_json(200, result)
            else:
                result = self.api.complete(
                    body, probe=self._client_gone, req_id=rid)
                result["request_id"] = rid
                self._log_done(rid, result)
                self._send_json(200, result)
        except ApiError as e:
            log.info("request %s rejected: %s", rid, e.message,
                     extra=trace.log_extra(rid))
            self._send_json(e.status, {"error": {"message": e.message}})
        except QueueFull as e:
            # load shedding: the request never entered the queue; tell the
            # client when to come back (429 per OpenAI's own rate responses).
            # The would-have-been id makes shed traffic correlatable: the
            # client got it in X-Request-Id, this line carries it too.
            log.warning("request %s shed (queue full): %s", rid, e,
                        extra=trace.log_extra(rid))
            self._send_json(429, {"error": {"message": str(e)}},
                            {"Retry-After": str(int(e.retry_after_s))})
        except SchedulerRejected as e:
            # draining or unhealthy: 503 so balancers retry elsewhere
            log.warning("request %s shed (%s): %s", rid,
                        e.__class__.__name__, e, extra=trace.log_extra(rid))
            self._send_json(503, {"error": {"message": str(e)}},
                            {"Retry-After": str(int(e.retry_after_s))})
        except ClientDisconnected:
            log.info("client disconnected; request %s cancelled", rid,
                     extra=trace.log_extra(rid))
        except CLIENT_GONE:
            log.info("client connection lost mid-response (request %s)", rid,
                     extra=trace.log_extra(rid))
        except Exception:
            log.exception("completion %s failed", rid,
                          extra=trace.log_extra(rid))
            try:
                self._send_json(500, {"error": {"message": "internal error"}})
            except CLIENT_GONE:
                pass

    def _handle_profile(self, raw: bytes) -> None:
        """POST /debug/profile — start a duration-capped jax.profiler
        capture (utils/profiling.start_profile; the same session the CLI's
        --trace uses). Body: {"duration_s": float, "dir": str}, both
        optional. 409 when a capture is already running."""
        from dllama_tpu.utils import profiling

        try:
            body = json.loads(raw or b"{}")
            if not isinstance(body, dict):
                raise ValueError
        except (ValueError, json.JSONDecodeError):
            self._send_json(400, {"error": {"message": "invalid JSON body"}})
            return
        try:
            sched = self.api.scheduler
            info = profiling.start_profile(
                log_dir=body.get("dir"),
                duration_s=body.get("duration_s", 2.0),
                restamp=sched.restamp if sched is not None else None)
        except profiling.ProfileBusy as e:
            self._send_json(409, {"error": {"message": str(e)}},
                            {"Retry-After": "2"})
            return
        except (TypeError, ValueError) as e:
            self._send_json(400, {"error": {"message": f"bad profile "
                                                       f"request: {e}"}})
            return
        log.info("device profile capture started: %.2fs -> %s",
                 info["duration_s"], info["dir"])
        self._send_json(200, {"profiling": info})

    def _stream(self, body: dict, legacy: bool = False) -> None:
        """SSE chunked streaming (dllama-api.cpp:203-223's role). `legacy`
        streams `text_completion` chunks (text field) instead of chat deltas.
        BLOCKING implementation — the thread tier runs every stream through
        it; the aio tier routes batched-tier streams to its cooperative SSE
        machine instead and uses this only for the single-engine tier
        (where the global engine lock serializes streams anyway)."""
        rid = self._req_id
        self._start_sse()
        # a failover resume keeps the dead upstream's stream identity: the
        # client already saw this id/created on the journaled frames, and a
        # mid-stream identity change would break strict SSE consumers
        resume = body.get("resume") if isinstance(body.get("resume"), dict) \
            else None
        cid = ((resume.get("id") if resume else None)
               or f"{'cmpl' if legacy else 'chatcmpl'}-{uuid.uuid4().hex[:16]}")
        created = int((resume.get("created") if resume else 0)
                      or time.time())
        model = body.get("model", self.api.model_name)
        chunk = self._write_chunk
        last_write = [time.monotonic()]

        def emit_chat(delta: dict, finish=None, timings=None,
                      ids=None) -> None:
            chunk(sse_chat_payload(cid, created, model, delta,
                                   finish=finish, timings=timings, ids=ids))
            last_write[0] = time.monotonic()

        def emit_text(text: str, finish=None, timings=None,
                      ids=None) -> None:
            chunk(sse_text_payload(cid, created, model, text,
                                   finish=finish, timings=timings, ids=ids))
            last_write[0] = time.monotonic()

        hb = self.api.sse_heartbeat_s

        def probe() -> bool:
            # the disconnect probe doubles as the keep-alive clock: it runs
            # at 4 Hz while tokens flow AND every poll interval while the
            # stream is quiet (queued, mid-prefill) — exactly the windows an
            # idle-timeout LB would kill (ISSUE 15)
            if hb and time.monotonic() - last_write[0] >= hb:
                chunk(SSE_HEARTBEAT)
                last_write[0] = time.monotonic()
            return self._client_gone()

        try:
            # streams get the disconnect probe too: a chunk write into a dead
            # socket fails on its own once tokens flow, but ONLY the probe
            # notices a client that vanished while queued / mid-prefill
            # (no tokens flowing yet)
            if legacy:
                result = self.api.complete_legacy(
                    body, emit=emit_text, probe=probe, req_id=rid)
                emit_text("", finish=result["choices"][0]["finish_reason"],
                          timings=result.get("timings"))
            else:
                if resume is None:
                    # a resumed stream's client already got the role delta
                    # from the dead upstream — re-sending it would duplicate
                    emit_chat({"role": "assistant"})
                result = self.api.complete(
                    body,
                    emit=lambda text, ids=None: emit_chat(
                        {"content": text}, ids=ids),
                    probe=probe, req_id=rid)
                emit_chat({}, finish=result["choices"][0]["finish_reason"],
                          timings=result.get("timings"))
            self._log_done(rid or "-", result)
        except (ClientDisconnected, *CLIENT_GONE):
            raise  # nothing to tell a dead socket; do_POST just logs it
        except Exception as e:
            # the 200/chunked headers are out — a second status line would
            # corrupt the stream. Emit an in-band SSE error event (the OpenAI
            # streaming error shape) and terminate the stream cleanly so the
            # client fails fast instead of hanging on a half-open stream.
            # Client-safe exception types keep their message; anything else
            # is masked like the non-stream 500 path (no internals leak).
            log.exception("streamed completion %s failed mid-stream", rid,
                          extra=trace.log_extra(rid))
            msg = (str(e) if isinstance(e, (ApiError, SchedulerRejected))
                   else "internal error")
            err = {"message": msg or e.__class__.__name__,
                   "type": "server_error"}
            if rid:
                err["request_id"] = rid  # SSE errors are correlatable too
            chunk(b"data: " + json.dumps({"error": err}).encode() + b"\n\n")
        chunk(b"data: [DONE]\n\n")
        chunk(b"")  # terminating zero-length chunk


class _Handler(RequestRoutes, BaseHTTPRequestHandler):
    """The thread-per-connection transport (`--frontend threads`): stdlib
    BaseHTTPRequestHandler provides parsing/keep-alive, RequestRoutes the
    endpoints, and this class only the six transport primitives."""

    server_version = "dllama-tpu"
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):
        log.info("%s %s", self.address_string(), fmt % args)

    def _send_raw(self, status: int, headers, body: bytes) -> None:
        self.send_response(status)
        for k, v in headers:
            self.send_header(k, v)
        self.end_headers()
        # counted before the body write: once the client has read the
        # response, the counter has already moved (no scrape-after-response
        # race for tests or tight operators)
        ins.HTTP_RESPONSES.labels(endpoint=_endpoint(self.path),
                                  code=str(status)).inc()
        self.wfile.write(body)

    def _start_sse(self) -> None:
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        self.send_header("Transfer-Encoding", "chunked")
        if self._req_id:
            self.send_header("X-Request-Id", self._req_id)
        if self.api.replica_id:
            self.send_header("X-Replica-Id", self.api.replica_id)
        self.end_headers()
        ins.HTTP_RESPONSES.labels(endpoint=_endpoint(self.path),
                                  code="200").inc()

    def _write_chunk(self, payload: bytes) -> None:
        self.wfile.write(f"{len(payload):x}\r\n".encode() + payload + b"\r\n")
        self.wfile.flush()

    def _read_body(self) -> bytes:
        length = int(self.headers.get("Content-Length", 0))
        return self.rfile.read(length)

    def _drain_body(self) -> None:
        """Read and discard any request body. The /debug endpoints answer
        early errors (404 unknown id, 404 tracing disabled, 409 profiler
        busy) on this keep-alive server, where unread body bytes would be
        parsed as the NEXT request line — the do_POST bug class, applied to
        the debug family (GETs with bodies are legal, if unusual)."""
        try:
            length = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            length = 0
        if length > 0:
            try:
                self.rfile.read(length)
            except OSError:
                pass

    def _client_gone(self) -> bool:
        """Disconnect probe for non-streamed completions: a readable socket
        that MSG_PEEKs zero bytes is a closed peer (we never read mid-
        completion, so pending bytes can only be a pipelined request — in
        which case the client is certainly still there).

        Known trade-off: a client that legally HALF-closes its write side
        after the request body (shutdown(SHUT_WR), then reads) looks
        identical to a full close at this layer and gets cancelled. That's
        the same call Starlette/uvicorn make for their disconnect probes;
        real OpenAI-style clients keep the socket open until the response."""
        try:
            r, _, _ = select.select([self.connection], [], [], 0)
            if not r:
                return False
            return self.connection.recv(1, socket.MSG_PEEK) == b""
        except (OSError, ValueError):
            return True


def make_server(loaded, host="127.0.0.1", port=0, n_slots: int = 0, **defaults):
    """-> (server, api). n_slots > 0 enables the continuous-batching tier: a
    BatchEngine with that many cache slots behind a Scheduler (concurrent
    requests share the device). n_slots == 0 keeps the single-engine tier
    with the NaiveCache prefix reuse (the reference server's semantics).
    `frontend` in **defaults picks the transport: 'aio' (default — the
    selectors event loop, serve/aio.py) or 'threads' (ThreadingHTTPServer);
    both answer the same routes and expose serve_forever/shutdown/
    server_close/server_address."""
    scheduler = None
    if n_slots <= 0 and any(
        defaults.get(k) is not None
        for k in ("admit_stall_budget_ms", "admit_ttft_deadline_ms")
    ):
        # same treatment as --spec on dp>1 meshes: an inapplicable serve
        # knob warns instead of vanishing silently
        log.warning("admission pacing flags (--admit-budget-ms / "
                    "--admit-ttft-deadline-ms) need --slots > 0; the "
                    "single-engine tier has no admission scheduler — ignored")
    if n_slots <= 0 and any(defaults.get(k) for k in ("max_queue", "stall_deadline_s")):
        log.warning("--max-queue / --stall-deadline-s need --slots > 0; the "
                    "single-engine tier has no admission queue or worker "
                    "thread to watch — ignored")
    if n_slots <= 0 and defaults.get("restart_max"):
        log.warning("--restart-max needs --slots > 0; the single-engine tier "
                    "has no scheduler worker to warm-restart — ignored")
    if n_slots <= 0 and defaults.get("kv_layout") == "paged":
        log.warning("--kv-layout paged needs --slots > 0; the single-engine "
                    "tier keeps its dense per-sequence cache — ignored")
    if n_slots <= 0 and defaults.get("radix_cache") == "on":
        log.warning("--radix-cache on needs --slots > 0; the single-engine "
                    "tier's NaiveCache has no page pool to share — ignored")
    if n_slots <= 0 and defaults.get("kv_host_pages"):
        log.warning("--kv-host-pages needs --slots > 0; the single-engine "
                    "tier has no page pool to spill from — ignored")
    if n_slots <= 0 and (defaults.get("prefill_budget") not in (None, "auto")
                         or defaults.get("preempt") not in (None, "auto")
                         or defaults.get("tenant_weights")):
        log.warning("--prefill-budget / --preempt / --tenant-weight need "
                    "--slots > 0; the single-engine tier serves one request "
                    "at a time — ignored (priority/tenant body fields are "
                    "accepted but inert)")
    if n_slots <= 0 and (defaults.get("warmup") not in (None, "off")
                         or defaults.get("transfer_guard")
                         not in (None, "off")):
        log.warning("--warmup / --transfer-guard need --slots > 0; the "
                    "single-engine tier has no BatchEngine shape contract "
                    "to precompile or guard — ignored")
    if n_slots > 0:
        from dllama_tpu.engine.batch import BatchEngine
        from dllama_tpu.serve.scheduler import Scheduler

        # batched speculation: greedy requests emit 1..K+1 tokens per verify
        # cycle, sampled requests decode exactly as before. dp meshes shard
        # the slot axis, which the per-slot history path doesn't support —
        # degrade to plain batched decode there instead of failing startup.
        spec_n = int(defaults.get("spec", 0))
        if (spec_n and loaded.shardings is not None
                and loaded.shardings.mesh.shape["dp"] > 1):
            log.warning("--spec is unavailable on dp>1 meshes; the "
                        "continuous-batching tier decodes without speculation")
            spec_n = 0
        # paged KV cache (--kv-layout): 'auto' — the serving default —
        # resolves to 'paged' on unsharded engines (the general paged
        # flash-decode kernel serves any page size, so the layout no longer
        # waits on tileability) and 'dense' on meshes (the pool has no slot
        # axis to shard; BatchEngine raises on paged+mesh — startup is the
        # right place to find an explicit 'paged' conflict out). The page
        # size shrinks to gcd(page_size, context) so short contexts stay
        # paged; a degenerate gcd (< 8 rows) falls back to dense.
        import math as _math

        kv_layout = defaults.get("kv_layout") or "auto"
        page_size = int(defaults.get("page_size") or 128)
        if kv_layout == "auto":
            if loaded.shardings is not None:
                kv_layout = "dense"
            else:
                # paged-by-default only where the flash-decode KERNEL could
                # route (paged_decode_supported): a config the kernel must
                # refuse — f8 pools, non-sublane-aligned pages — would
                # silently serve every step through the gather fallback's
                # re-materialized-view traffic, which is worse than the
                # dense default it replaced. Explicit --kv-layout paged
                # still honors the user's choice unconditionally.
                from dllama_tpu.ops.pallas.paged_attention import (
                    paged_decode_supported,
                )

                g = _math.gcd(page_size, loaded.engine.seq_len)
                capable = g >= 8 and paged_decode_supported(
                    (loaded.config.n_heads, loaded.config.head_size), g,
                    kv_dtype=loaded.engine.cache.k.dtype)
                kv_layout = "paged" if capable else "dense"
                if capable and g != page_size:
                    log.info("kv-layout auto: page size %d does not divide "
                             "context %d; using %d", page_size,
                             loaded.engine.seq_len, g)
                if capable:
                    page_size = g
            log.info("kv-layout auto -> %s", kv_layout)
        # cross-request radix prefix cache (--radix-cache, default auto = on
        # whenever the layout resolved paged): an explicit 'on' against a
        # dense resolution warns instead of failing startup — BatchEngine
        # itself raises only on the direct-library misuse
        radix_cache = defaults.get("radix_cache") or "auto"
        if radix_cache == "on" and kv_layout == "dense":
            log.warning("--radix-cache on requires the paged KV layout; this "
                        "engine resolved dense — the per-slot prefix cache "
                        "serves instead")
            radix_cache = "off"
        # host-RAM KV spill tier (--kv-host-pages, ISSUE 16): needs the
        # paged layout with the radix tree on (its token paths key the host
        # tier); warn-and-drop on an incompatible resolution rather than
        # failing startup, same policy as --radix-cache above
        kv_host_pages = int(defaults.get("kv_host_pages") or 0)
        if kv_host_pages > 0 and (kv_layout != "paged"
                                  or radix_cache == "off"):
            log.warning("--kv-host-pages requires the paged KV layout with "
                        "the radix cache on; this engine resolved "
                        "%s/radix=%s — the host spill tier stays off",
                        kv_layout, radix_cache)
            kv_host_pages = 0
        be = BatchEngine(
            loaded.config,
            loaded.engine.params,
            n_slots=n_slots,
            cache_dtype=loaded.engine.cache.k.dtype,
            max_seq_len=loaded.engine.seq_len,
            # --max-prefill-chunk caps the serving engine's prompt slices
            # too (a hybrid launch's and an admission chunk's), as it caps
            # the batch-1 engine's
            max_prefill_chunk=getattr(loaded.engine, "max_prefill_chunk", 256),
            shardings=loaded.shardings,  # multi-chip serving keeps the mesh placement
            sync=getattr(loaded, "sync", "bf16"),
            spec=spec_n,
            kv_layout=kv_layout,
            page_size=page_size,
            kv_pages=int(defaults.get("kv_pages") or 0),
            radix_cache=radix_cache,
            kv_host_pages=kv_host_pages,
            # steady-state upload enforcement (--transfer-guard): 'strict'
            # turns an implicit per-chunk host->device transfer inside the
            # decode/spec dispatch window into an error
            transfer_guard=str(defaults.get("transfer_guard") or "off"),
        )
        # admission pacing (serve/scheduler.py): budget bounds the decode
        # stall a joining prefill may insert per visit; the optional TTFT
        # deadline hard-bounds a joiner's wait (CLI: --admit-budget-ms /
        # --admit-ttft-deadline-ms)
        sched_kw = {}
        if defaults.get("admit_stall_budget_ms") is not None:
            sched_kw["admit_stall_budget_ms"] = float(defaults["admit_stall_budget_ms"])
        if defaults.get("admit_ttft_deadline_ms") is not None:
            sched_kw["admit_ttft_deadline_ms"] = float(defaults["admit_ttft_deadline_ms"])
        # supervision knobs: bounded admission (--max-queue -> 429 shedding)
        # and the stall watchdog (--stall-deadline-s -> live=false on a hung
        # device chunk)
        if defaults.get("max_queue"):
            sched_kw["max_queue"] = int(defaults["max_queue"])
        if defaults.get("stall_deadline_s"):
            sched_kw["stall_deadline_s"] = float(defaults["stall_deadline_s"])
        # self-healing (--restart-max / --restart-window-s): warm engine
        # restart on worker crash, budgeted; 0 keeps crash = permanent
        # unhealthy (external supervisor owns the restart)
        if defaults.get("restart_max"):
            sched_kw["restart_max"] = int(defaults["restart_max"])
        if defaults.get("restart_window_s") is not None:
            sched_kw["restart_window_s"] = float(defaults["restart_window_s"])
        # overlapped decode pipeline (--overlap, default on): chunk N+1
        # dispatches before chunk N's tokens are consumed; off restores the
        # lockstep loop for A/B (token streams are identical either way)
        if defaults.get("overlap") is not None:
            sched_kw["overlap"] = bool(defaults["overlap"])
        # SLO targets (--slo-ttft-ms / --slo-itl-ms): the scheduler's perf
        # aggregator judges every terminal request against them (burn
        # counters, attainment gauge, goodput accounting)
        if defaults.get("slo_ttft_ms") is not None:
            sched_kw["slo_ttft_ms"] = float(defaults["slo_ttft_ms"])
        if defaults.get("slo_itl_ms") is not None:
            sched_kw["slo_itl_ms"] = float(defaults["slo_itl_ms"])
        # hybrid chunked prefill (--prefill-budget: auto|N|0) + preemption
        # (--preempt) + tenant fair-queue weights (--tenant-weight NAME=W)
        if defaults.get("prefill_budget") is not None:
            sched_kw["prefill_budget"] = defaults["prefill_budget"]
        if defaults.get("preempt") is not None:
            sched_kw["preempt"] = str(defaults["preempt"])
        if defaults.get("tenant_weights"):
            sched_kw["tenant_weights"] = dict(defaults["tenant_weights"])
        # boot precompile (--warmup auto): the scheduler declares its
        # compiled-shape universe and warms every bucket before the worker
        # takes traffic — first-request TTFT stops paying XLA cold-start
        if defaults.get("warmup"):
            sched_kw["warmup"] = str(defaults["warmup"])
        scheduler = Scheduler(be, **sched_kw)
    api = ApiServer(
        loaded,
        default_temperature=defaults.get("default_temperature", 0.8),
        default_topp=defaults.get("default_topp", 0.9),
        default_seed=defaults.get("default_seed"),
        scheduler=scheduler,
        spec=defaults.get("spec", 0),
        slo_ttft_ms=defaults.get("slo_ttft_ms"),
        slo_itl_ms=defaults.get("slo_itl_ms"),
        replica_id=defaults.get("replica_id") or "",
        sse_heartbeat_s=defaults.get("sse_heartbeat_s") or 0.0,
    )
    # front-end selection (ISSUE 15): 'aio' (default) multiplexes every
    # connection on a selectors event loop with a small fixed thread count;
    # 'threads' keeps the thread-per-connection ThreadingHTTPServer as the
    # A/B baseline. Same routes class either way — byte-identical semantics.
    frontend = str(defaults.get("frontend") or "aio")
    if frontend == "aio":
        from dllama_tpu.serve.aio import AioHttpServer

        httpd = AioHttpServer(
            (host, port), api,
            workers=int(defaults.get("aio_workers") or 0) or None)
    elif frontend == "threads":
        handler = type("Handler", (_Handler,), {"api": api})
        httpd = ThreadingHTTPServer((host, port), handler)
    else:
        raise ValueError(f"unknown frontend {frontend!r} (aio|threads)")
    if not api.replica_id:
        # default replica identity: the bound address — unique per replica
        # of a router mesh, stable for the life of the process. A wildcard
        # bind (0.0.0.0/::) names every machine's replica identically and
        # collapses the mesh's X-Replica-Id attribution to one bucket, so
        # substitute the hostname there
        ident = host
        if host in ("0.0.0.0", "::", ""):
            import socket as _socket
            ident = _socket.gethostname()
        api.replica_id = f"{ident}:{httpd.server_address[1]}"
    return httpd, api


def graceful_drain(httpd, api, timeout_s: float = 30.0) -> bool:
    """The deploy-time shutdown sequence (SIGTERM handler body, also callable
    directly from tests/embedding code):

    1. stop admission — new requests get 503 + Retry-After, /health/ready
       goes 503 so balancers route away;
    2. let in-flight requests (and already-queued ones) finish, bounded by
       `timeout_s`;
    3. shut down the scheduler and stop the HTTP accept loop.

    Returns True when everything in flight completed inside the timeout."""
    api.draining = True
    clean = True
    if api.scheduler is not None:
        clean = api.scheduler.drain(timeout_s)
    else:
        # single-engine tier: the global lock serializes requests; waiting
        # for it (with the same deadline) means the in-flight one finished
        clean = api.lock.acquire(timeout=max(0.0, timeout_s))
        if clean:
            api.lock.release()
    httpd.shutdown()
    return clean


def install_sigterm_drain(httpd, api, timeout_s: float = 30.0) -> bool:
    """SIGTERM -> graceful_drain in a helper thread (the handler itself must
    return fast; serve_forever keeps running until httpd.shutdown()). Returns
    False when not on the main thread, where signal.signal raises."""
    fired = threading.Event()

    def _term(signum, frame):
        if fired.is_set():
            return
        fired.set()
        log.info("SIGTERM: draining (timeout %.0fs) — new requests get 503",
                 timeout_s)
        threading.Thread(target=graceful_drain, args=(httpd, api, timeout_s),
                         name="dllama-drain", daemon=True).start()

    try:
        signal.signal(signal.SIGTERM, _term)
        return True
    except ValueError:  # not the main thread (embedded/test usage)
        return False


def run_server(loaded, host="127.0.0.1", port=9990, n_slots: int = 0, **defaults) -> int:
    httpd, api = make_server(loaded, host, port, n_slots=n_slots, **defaults)
    drain_timeout_s = float(defaults.get("drain_timeout_s") or 30.0)
    install_sigterm_drain(httpd, api, drain_timeout_s)
    mode = f"continuous batching, {n_slots} slots" if n_slots else "single-request + prefix cache"
    log.info("serving on http://%s:%d (%s); telemetry at /metrics, probes "
             "at /health/live and /health/ready",
             host, httpd.server_address[1], mode)
    print(f"🚀 http://{host}:{httpd.server_address[1]}/v1/chat/completions ({mode})")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if api.scheduler is not None:
            api.scheduler.shutdown()
        httpd.server_close()
    return 0
