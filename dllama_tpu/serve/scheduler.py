"""Continuous-batching scheduler: one worker thread drives a BatchEngine,
request threads stream tokens from per-request queues.

This is the serving tier above the reference's single-request blocking server
(dllama-api.cpp:522-533): requests join a running batch whenever a slot is
free (masked single-slot prefill), decode together in fused device chunks,
and leave at EOS/budget — other requests never wait for a whole completion,
only for chunk boundaries.

Token-level stops (EOS ids, budget) are handled here; *string* stop sequences
need decoded text, so the request handler runs its EosDetector on the stream
and calls cancel() — generation overruns by at most one chunk. With the
overlapped pipeline (the default: chunk N+1 dispatches off chunk N's
device-side carry before chunk N's tokens are consumed), token-level stops
inherit the same one-chunk overrun contract: the in-flight chunk keeps
decoding a just-finished slot, its tokens are discarded at consumption, and
release(keep_rows=) rewinds the slot to the truly-emitted prefix.

**Self-healing** (ISSUE 6): with ``restart_max > 0`` a worker crash
warm-restarts the engine in-process — decode state and the KV page pool are
rebuilt against the still-resident weights (no model reload), queued
requests survive untouched, and in-flight streams resume bit-exact by
re-prefilling prompt + emitted tokens with their recorded PRNG key
(`_try_restart`). Budget-bounded (``restart_max`` within
``restart_window_s``, capped exponential backoff); budget exhausted falls
back to the PR 1 permanent-unhealthy contract. Per-request deadlines
(``timeout_s``) shed expired queued requests before prefill and finish
running ones with ``finish_reason="timeout"`` at a chunk boundary; the
decode NaN guard fails a request whose logits go non-finite without
touching its batch-mates.

**Prefix reuse** comes in two flavors, selected by the engine:

* **Radix prefix cache** (ISSUE 9, the paged default — engine/radix): a
  GLOBAL radix tree over the KV page pool replaces the resident-slot scan as
  the reuse mechanism. Admission walks the tree and maps the longest shared
  prefix by page refcount (zero copies; a partial boundary page is
  copy-on-written by the existing admission COW), commit/release insert the
  request's own prefix back, and released slots hand every page to the tree —
  so reuse survives slot churn and works across requests that never shared a
  slot. Capacity pressure reclaims LRU tree leaves before a request defers.
* **Per-slot prefix cache** (the batched-tier NaiveCache,
  dllama-api.cpp:264-309 — dense layouts / --radix-cache off): released slots
  keep their KV rows and the token history that produced them. Admission
  matches a new request's prompt against every idle slot's history and
  prefills only the delta from the matched position (BatchEngine.add's
  start_pos).

Either way, matching is at the TOKEN level, which subsumes the reference's
whole-message matching: any retokenization drift just means no reuse, never
wrong output (rows past the matched position are rewritten).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from dllama_tpu.engine.batch import BatchEngine
from dllama_tpu.obs import compile as compile_obs
from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import perf
from dllama_tpu.obs import trace
from dllama_tpu.utils import faults
from dllama_tpu.utils import locks

log = logging.getLogger("dllama_tpu.serve")

_END = object()  # sentinel on the token queue; payload = finish reason


class SchedulerRejected(RuntimeError):
    """Base of the admission-control rejections: the request never entered
    the queue and running generations are unperturbed. `retry_after_s` is the
    client hint the API tier forwards as a Retry-After header."""

    retry_after_s: float = 1.0


class QueueFull(SchedulerRejected):
    """Shed under load: pending depth reached --max-queue (HTTP 429)."""


class SchedulerDraining(SchedulerRejected):
    """Admission stopped for a graceful shutdown (HTTP 503)."""

    retry_after_s = 5.0


class SchedulerUnhealthy(SchedulerRejected):
    """The worker thread crashed or is gone; nothing can serve this
    request (HTTP 503 — readiness is down too, so balancers drain us)."""

    retry_after_s = 10.0


@dataclass
class Request:
    prompt: list[int]
    temperature: float
    topp: float
    max_tokens: int
    eos_ids: frozenset[int]
    seed: int | None = None
    presence: float = 0.0
    frequency: float = 0.0
    # per-request speculation (ISSUE 11): draft length this request's slot
    # runs at (body `spec_k` / --spec-k serving default, clamped to the
    # engine's compile-time K at submit; 0 = plain decode for this request
    # even while batch-mates speculate). spec_cycles/spec_tokens accumulate
    # the request's own acceptance record — `timings()` derives its
    # realized per-request speedup (tokens per verify forward) from them.
    spec_k: int = 0
    spec_cycles: int = 0
    spec_tokens: int = 0
    out: queue.Queue = field(default_factory=queue.Queue)
    produced: int = 0
    slot: int = -1
    finish_reason: str | None = None
    # serving-tier request id (api -> scheduler -> engine): the correlation
    # key between X-Request-Id response headers, log lines, and admissions
    req_id: str = ""
    # what finish_reason a cancel() should record: the API tier releases a
    # slot via cancel() BOTH for real client cancellations and for streams
    # that ended on a string stop-sequence — the latter is a SUCCESS and must
    # not pollute the finished{reason="cancelled"} counter
    cancel_reason: str = "cancelled"
    cancelled: threading.Event = field(default_factory=threading.Event)
    # per-request deadline (body `timeout_s` / X-Request-Timeout header):
    # expired-in-queue requests are shed before prefill, running ones finish
    # with finish_reason="timeout" at the next chunk boundary. deadline_at
    # is the absolute monotonic deadline (submit time + timeout_s).
    timeout_s: float | None = None
    deadline_at: float | None = None
    # scheduling class & tenant (ISSUE 12): `priority` (0=low, 1=normal,
    # 2=high — body `priority` field) picks strictly between classes at
    # admission AND marks a running low-priority request preemptible by a
    # higher-priority waiter; `tenant` (body field) keys the weighted
    # fair queue WITHIN a class ("" = the anonymous shared tenant).
    priority: int = 1
    tenant: str = ""
    # preempt-to-pages (ISSUE 12): True between a chunk-boundary suspension
    # and the re-commit that resumes the stream — the request sits in the
    # backlog with resume_tokens/resume_key recorded (the same machinery
    # warm-restart resume uses) while its KV pages stay referenced by the
    # radix tree (paged) or its kept slot rows (dense)
    preempted: bool = False
    # WFQ billing latch: a request's (prompt + max_tokens)/weight cost is
    # charged to its tenant's virtual time ONCE — resumes/rejoins after
    # preemption or deferral must not pay again
    wfq_charged: bool = False
    # warm-restart recovery (set by Scheduler._try_restart, consumed at
    # re-admission): resume_tokens are the tokens already emitted to the
    # client — all but the last are re-prefilled (teacher-forced), the last
    # becomes the decode carry's fed token; resume_key is the request's
    # PRNG key advanced to the interruption point, so a resumed sampled
    # stream is bit-exact. `recovered` marks the request for the
    # requests_recovered counter at its post-restart (re)commit.
    resume_tokens: list[int] | None = None
    resume_key: object | None = None
    recovered: bool = False
    # PRNG advances already baked into engine.keys[slot] at the last
    # (re)commit: 0 after a fresh add_commit (the row holds the commit-time
    # key), produced-1 after a resume_commit (the row holds a key
    # pre-advanced to the interruption point). A SECOND warm restart must
    # replay only the advances since — replaying the cumulative `produced`
    # would double-count the pre-first-crash tokens and silently break the
    # bit-exact-resume guarantee for sampled streams.
    key_advances: int = 0
    # latency marks (time.monotonic): the serving-tier observability the
    # reference's per-token console lines provide (dllama.cpp:82-87)
    submitted_at: float = 0.0
    admitted_at: float | None = None  # popped from the queue for admission
    first_token_at: float | None = None
    finished_at: float | None = None

    @property
    def ttft_ms(self) -> float | None:
        """Time to first token (includes queueing + prefill)."""
        if self.first_token_at is None:
            return None
        return (self.first_token_at - self.submitted_at) * 1000.0

    @property
    def itl_ms(self) -> float | None:
        """Mean inter-token latency after the first token."""
        if self.finished_at is None or self.first_token_at is None or self.produced < 2:
            return None
        return (self.finished_at - self.first_token_at) * 1000.0 / (self.produced - 1)

    def timings(self) -> dict:
        """The per-request latency summary clients get back (the `timings`
        object of non-stream responses and the final SSE event) and the
        flight recorder records — all from the same marks the /metrics
        histograms observe, so the three views cannot disagree. Fields not
        yet known (unadmitted, unfinished) are None."""
        qw = (None if self.admitted_at is None
              else round((self.admitted_at - self.submitted_at) * 1000.0, 3))
        ttft = self.ttft_ms
        e2e = (None if self.finished_at is None
               else round((self.finished_at - self.submitted_at) * 1000.0, 3))
        out = {"queue_wait_ms": qw,
               "ttft_ms": None if ttft is None else round(ttft, 3),
               "e2e_ms": e2e, "decode_tokens": self.produced}
        if self.timeout_s is not None:
            # deadline accounting rides the same summary: what was asked,
            # and whether the deadline (not EOS/budget) ended the request
            out["timeout_s"] = self.timeout_s
            out["deadline_exceeded"] = self.finish_reason == "timeout"
        if self.spec_k > 0:
            # per-request speculation record: tokens per verify forward IS
            # the realized speedup over one-token-per-forward decoding
            out["spec"] = {
                "spec_k": self.spec_k,
                "cycles": self.spec_cycles,
                "tokens": self.spec_tokens,
                "tokens_per_cycle": (round(self.spec_tokens
                                           / self.spec_cycles, 3)
                                     if self.spec_cycles else None),
            }
        return out

    def tokens(self, poll=None, poll_s: float = 0.25):
        """Blocking iterator over generated tokens (ends on EOS/budget/cancel).

        `poll` (optional zero-arg callable) runs every `poll_s` seconds of
        WAITING — i.e. also while no tokens are flowing at all (queued behind
        a full batch, mid-prefill, stalled device), which is exactly when a
        disconnect probe matters most. Whatever it raises propagates."""
        while True:
            if poll is None:
                item = self.out.get()
            else:
                try:
                    item = self.out.get(timeout=poll_s)
                except queue.Empty:
                    poll()
                    continue
            if item is _END or isinstance(item, Exception):
                if isinstance(item, Exception):
                    raise item
                return
            yield item

    def poll_tokens(self) -> tuple[list[int], bool]:
        """NON-blocking drain of the token queue — the aio front-end's SSE
        pump seam (one thread multiplexes every stream, so nothing may
        block). Returns ``(tokens, done)``: every token available right
        now, and whether the stream has ended (EOS/budget/cancel/timeout —
        ``finish_reason`` is authoritative once True). A queued exception
        (shed/shutdown/crash) raises exactly like :meth:`tokens`; tokens
        drained before it are lost to the caller the same way the blocking
        iterator loses them (the request is terminal either way)."""
        toks: list[int] = []
        while True:
            try:
                item = self.out.get_nowait()
            except queue.Empty:
                return toks, False
            if item is _END:
                return toks, True
            if isinstance(item, Exception):
                raise item
            toks.append(item)


class Scheduler:
    def __init__(self, engine: BatchEngine, chunk: int = 4, admit_timeout: float = 0.05,
                 admit_interleave: bool = True,
                 admit_stall_budget_ms: float = 250.0,
                 admit_ttft_deadline_ms: float | None = None,
                 max_queue: int = 0,
                 stall_deadline_s: float = 0.0,
                 overlap: bool = True,
                 restart_max: int = 0,
                 restart_window_s: float = 60.0,
                 restart_backoff_s: float = 0.5,
                 slo_ttft_ms: float | None = None,
                 slo_itl_ms: float | None = None,
                 prefill_budget: int | str = "auto",
                 preempt: str = "auto",
                 tenant_weights: dict[str, float] | None = None,
                 warmup: str = "off"):
        self.engine = engine
        self.chunk = chunk
        self.admit_timeout = admit_timeout
        # overlapped decode pipeline (--overlap): dispatch chunk N+1 off
        # chunk N's device-side carry BEFORE consuming chunk N's tokens, so
        # the per-chunk Python work (emit loops, EOS/budget checks, metrics)
        # runs while the device computes. Token-level stops then lag by at
        # most ONE chunk — the same overrun contract string stops already
        # have above — with overrun tokens discarded and release(keep_rows=)
        # rewound to the truly-emitted prefix. False restores the lockstep
        # loop (dispatch+consume per iteration); token streams are
        # bit-identical either way. Speculative cycles compose (ISSUE 11):
        # they dispatch/consume through the same split — cycle N+1's
        # propose/verify launches off cycle N's device carry, and the
        # data-dependent emit counts materialize at consumption.
        self.overlap = bool(overlap)
        # bounded admission (load shedding): submit() raises QueueFull once
        # the pending queue holds this many requests — the API tier turns it
        # into 429 + Retry-After. 0 = unbounded (the pre-supervision behavior).
        self.max_queue = int(max_queue)
        # interleaved admission (VERDICT r3 weak #5): pump prefill chunks of a
        # joining prompt BETWEEN decode chunks instead of running the whole
        # chunked prefill synchronously — a 2 Ki-token admission no longer
        # stalls every decoding slot for its full prefill. False = legacy
        # synchronous admission.
        self.admit_interleave = admit_interleave
        # pacing (VERDICT r4 weak #3: fixed 1-chunk pacing cost joiners 5-6x
        # TTFT on slow chunks): each admission visit keeps pumping prefill
        # chunks until ~budget ms elapsed, so decoders stall at most
        # budget + one chunk while joiner TTFT approaches the synchronous
        # floor whenever chunks are fast (always, on a TPU). 0 restores
        # strict one-chunk-per-decode pacing.
        self.admit_stall_budget_ms = float(admit_stall_budget_ms)
        # optional hard TTFT bound: an admission older than this pumps to
        # completion regardless of the stall budget (decoders eat one big
        # stall rather than the joiner waiting forever behind a slow batch)
        self.admit_ttft_deadline_ms = admit_ttft_deadline_ms
        self.pending: queue.Queue[Request] = queue.Queue()
        # scheduling backlog (ISSUE 12): the worker drains `pending` (the
        # thread-safe intake) into this list at every boundary and picks by
        # POLICY — priority classes strictly first, weighted fair queueing
        # across tenants within a class (virtual finish times in
        # `_tenant_vt`), FIFO within a tenant — instead of the old global
        # FIFO pop. Preempted requests also park here until capacity and
        # priority let them resume.
        self._backlog: list[Request] = []
        # per-tenant WFQ virtual finish tags + the global virtual clock
        # (start-time fair queueing): each admission is charged
        # (prompt + max_tokens) / weight from max(own tag, clock), and the
        # clock advances to that start — idle time banks no credit
        self._tenant_vt: dict[str, float] = {}
        self._vt_now = 0.0
        self.tenant_weights = dict(tenant_weights or {})
        # capacity-aware admission (paged KV layout): the head request the
        # page pool cannot yet cover, parked here (NOT back in the backlog —
        # its admission was already selected by policy and later picks wait
        # behind it). Retried every boundary; released pages / evicted idle
        # caches un-defer it.
        self._deferred: Request | None = None
        self.slots: dict[int, Request] = {}
        # admissions being pumped chunk-by-chunk: [(req, Admission), ...];
        # their slots are reserved (not engine.active) until commit
        self._inflight: list = []
        # per-slot token history whose KV rows are live (prefix-cache key
        # on the legacy path; resume-token record for warm restart on both);
        # len(slot_tokens[s]) always == engine.pos[s] for idle slots
        self.slot_tokens: dict[int, list[int]] = {}
        self.reused_prefix_tokens = 0  # total prompt tokens served from cache
        # cross-request radix prefix cache (ISSUE 9, engine/radix): when the
        # engine carries one, the GLOBAL tree replaces the resident-slot LCP
        # scan as the reuse mechanism — admission walks the tree and maps the
        # shared prefix by refcount, commit/release insert prefixes back, and
        # released slots hand every page to the tree (idle slots stay empty).
        # Dense layouts (no page pool) keep the legacy per-slot scan.
        self._radix = getattr(engine, "radix", None)
        # decode-gap observability (VERDICT r3 #4): wall-time between
        # consecutive decode chunks whenever admission work ran in between —
        # the stall decoding slots actually experienced
        self._admit_gaps_ms: list[float] = []
        # inter-chunk host gap: time from one chunk's tokens materializing to
        # the next chunk's dispatch — the device-idle window host scheduling
        # inserts. ~0 under overlap (chunk N+1 dispatches before chunk N is
        # consumed); the lockstep A/B baseline shows the real gap. Kept ONCE,
        # in the dllama_decode_host_gap_seconds histogram: latency_summary
        # reads its movement since this mark.
        self._host_gap_mark = ins.DECODE_HOST_GAP_SECONDS.series()
        self._t_consumed: float | None = None
        # gated spec/decode alternation: per-slot eligibility (ISSUE 11)
        # lets sampled, penalized, and non-spec traffic ride spec cycles
        # one token at a time, so the only slots a cycle still freezes are
        # those WITHOUT a K+1-row verify window (context edge, exhausted
        # page pool). While one of those is live, spec cycles alternate
        # with plain decode chunks (toggle state) so it still advances.
        self._spec_tick = False
        self._completed: list[Request] = []  # ring of recent requests (metrics)
        self._metrics_lock = locks.make_lock("scheduler.metrics")
        ins.SLOTS_TOTAL.set(engine.n_slots)
        self._wake = threading.Event()
        self._stop = threading.Event()
        # ---- self-healing (warm restart): on a worker crash, tear down
        # decode state + page pool, rebuild against the still-resident
        # weights (no model reload) and re-enter the loop — at most
        # --restart-max times within --restart-window-s, with exponential
        # backoff (restart_backoff_s * 2^(attempt-1)). 0 keeps the PR 1
        # behavior: any crash is permanent-unhealthy, the external
        # supervisor owns the restart.
        self.restart_max = int(restart_max)
        self.restart_window_s = float(restart_window_s)
        self.restart_backoff_s = float(restart_backoff_s)
        self._restarts: list[float] = []  # monotonic stamps inside the window
        self.restart_count = 0  # lifetime total (health/observability)
        # requests that survived a restart, awaiting re-admission at the
        # queue head (mid-stream resumes first, in submission order)
        self._recover: list[Request] = []
        # ---- supervision state (all read by health(), written by the worker
        # or watchdog; plain attribute stores are atomic under the GIL)
        self.crashed: BaseException | None = None  # worker died with this
        self.join_failed = False  # shutdown() could not join the worker
        self._draining = threading.Event()  # admission stopped for drain
        self.stalled = False  # watchdog verdict: a chunk blew the deadline
        self.stall_count = 0  # total watchdog trips (stalled may recover)
        # ---- SLO & saturation observability (ISSUE 7, obs/perf.py): the
        # time ledger attributes every second of the worker loop to one
        # exclusive state (dllama_scheduler_time_seconds_total{state} — the
        # per-state totals partition loop wall time by construction), and
        # the aggregator joins sliding-window TTFT/ITL/e2e quantiles, SLO
        # burn/attainment accounting (--slo-ttft-ms / --slo-itl-ms), and
        # goodput vs throughput. Both feed GET /debug/perf and /metrics.
        self.ledger = perf.TimeLedger(counter=ins.SCHEDULER_TIME)
        # phases under the states (obs/perf.PhaseClock): ONE clock for the
        # worker's host work, the engine's half of a dispatch included (an
        # engine without one, a test's stand-in, gets the scheduler's)
        self.phases = getattr(engine, "phases", None) or perf.PhaseClock()
        self.phases.ledger = self.ledger
        self.perf = perf.PerfAggregator(
            slo=perf.SloPolicy(
                None if slo_ttft_ms is None else float(slo_ttft_ms),
                None if slo_itl_ms is None else float(slo_itl_ms)))
        # ---- hybrid chunked prefill (ISSUE 12, --prefill-budget): when a
        # request is admitting WHILE others decode, each device chunk is a
        # FUSED hybrid step (engine.hybrid_dispatch) that co-processes up
        # to `_budget_now` prompt tokens alongside the decode rows — one
        # launch, no separate prefill dispatch stalling the decoders. This
        # replaces the interleaved-admission pacing as the mechanism that
        # protects decoders during a join ("auto"/N; the admit_interleave /
        # admit_stall_budget_ms knobs now only govern the legacy
        # prefill_budget=0 phase-split path, kept as the A/B baseline).
        if prefill_budget is None:
            prefill_budget = "auto"
        if isinstance(prefill_budget, str) and prefill_budget != "auto":
            prefill_budget = int(prefill_budget)
        self.prefill_budget = prefill_budget  # "auto" | int (0 = legacy)
        self._hybrid_on = (prefill_budget != 0
                           and getattr(engine, "supports_hybrid", False))
        # pipelined commit: an admission's first token is sampled behind
        # the chunk that carried its last prompt rows and read after that
        # chunk is consumed, with the successor already on the device, so
        # a commit costs the device nothing (the joiner decodes one chunk
        # later). Not with speculation: a slot must not be activated under
        # a spec chunk in flight (_commit_ready_inflight).
        self._pipelined_commit = (self._hybrid_on and self.overlap
                                  and hasattr(engine, "add_sample")
                                  and not getattr(engine, "spec_k", 0))
        self._budget_ctl = None
        if not self._hybrid_on:
            self._budget_now = 0
            ins.PREFILL_BUDGET.set(0)
        elif prefill_budget == "auto":
            # SLO-driven: the windowed ITL headroom against --slo-itl-ms
            # shrinks/grows the budget online (holds the start value when
            # no ITL target is configured)
            self._budget_ctl = perf.PrefillBudgetController(
                self.perf.slo,
                hi=max(64, int(getattr(engine, "max_prefill_chunk", 256))))
            self._budget_now = self._budget_ctl.current
        else:
            self._budget_now = max(1, int(prefill_budget))
            ins.PREFILL_BUDGET.set(self._budget_now)
        # ---- preempt-to-pages (ISSUE 12, --preempt): a running request may
        # be suspended at a chunk boundary when a STRICTLY higher-priority
        # request is waiting and blocked (no free slot, or the deferred
        # head is capacity-starved). Suspension releases the slot while the
        # pages stay referenced — radix tree (paged) or kept rows (dense) —
        # and the stream resumes byte-identical via the warm-restart resume
        # machinery. "auto" = on; "off" disables.
        if preempt not in ("auto", "on", "off"):
            raise ValueError(f"preempt must be auto|on|off, got {preempt!r}")
        self._preempt_on = preempt != "off"
        if self._preempt_on and not getattr(engine, "rows_reenterable", True):
            # a suspended request resumes by re-entering its kept rows in
            # whatever slot is free: recurrent state stands in ONE slot at
            # ONE row, so every resume would re-prefill the whole stream
            if preempt == "on":
                raise ValueError(
                    "--preempt on needs rows that can be re-entered in any "
                    "slot; this model's recurrent state, or its windowed "
                    "layers' own page pool, cannot (use --preempt off)")
            log.info("preempt-to-pages off: a suspended request's rows "
                     "cannot be re-entered (recurrent state, or windowed "
                     "layers with a page pool of their own)")
            self._preempt_on = False
        self.preempt_count = 0  # lifetime totals (latency_summary/health)
        self.resume_count = 0
        # ---- compile observability (ISSUE 13, obs/compile): declare THIS
        # scheduler's expected compiled-shape universe into the engine's
        # contract (decode/spec at {1, chunk}, hybrid at every pow2 budget
        # slice) so any off-contract compile classifies unexpected; with
        # --warmup auto, precompile the whole universe BEFORE the worker
        # starts — the first real request then pays zero compile.
        if warmup not in ("auto", "off"):
            raise ValueError(f"warmup must be auto|off, got {warmup!r}")
        self.warmup = warmup
        self.warmup_report: dict | None = None
        hybrid_hi = 0
        if self._hybrid_on:
            hybrid_hi = (self._budget_ctl.hi if self._budget_ctl is not None
                         else self._budget_now)
        if hasattr(engine, "declare_serving_buckets"):
            engine.declare_serving_buckets(chunk=self.chunk,
                                           hybrid_budget_hi=hybrid_hi)
        if warmup == "auto":
            if getattr(engine, "_shardings", None) is not None:
                log.warning("--warmup auto needs an unsharded engine; "
                            "skipping the precompile pass")
            elif hasattr(engine, "warmup"):
                self.warmup_report = engine.warmup(
                    chunk=self.chunk, hybrid_budget_hi=hybrid_hi)
        # worker heartbeat: stamped once per loop iteration. A device call
        # that hangs stops the heartbeat while work exists — which is exactly
        # the condition the watchdog turns into "stalled".
        self._heartbeat = time.monotonic()
        self._thread = threading.Thread(target=self._run, name="dllama-scheduler", daemon=True)
        self._thread.start()
        # stall watchdog: marks the server unhealthy when the worker goes
        # silent mid-work for longer than the deadline (a hung device chunk,
        # a hung collective). Detection only — there is no safe preemption
        # of a dispatched XLA computation; the operator (or the pod
        # supervisor watching /health) owns the restart.
        self.stall_deadline_s = float(stall_deadline_s)
        self._watchdog = None
        if self.stall_deadline_s > 0:
            self._watchdog = threading.Thread(
                target=self._watch, name="dllama-watchdog", daemon=True)
            self._watchdog.start()

    # ------------------------------------------------------------------- api

    def submit(self, prompt, temperature, topp, max_tokens, eos_ids,
               seed: int | None = None, presence: float = 0.0,
               frequency: float = 0.0, req_id: str = "",
               timeout_s: float | None = None,
               spec_k: int | None = None,
               priority: int = 1, tenant: str = "",
               resume_tokens=None) -> Request:
        self.check_admission()
        # per-request speculation: None keeps the engine default (every
        # greedy request speculates at the engine's K — the pre-ISSUE-11
        # behavior and the --spec-k serving default); explicit values clamp
        # to the compile-time K, 0 opts this request out entirely
        cap = int(getattr(self.engine, "spec_k", 0))
        spec_k = cap if spec_k is None else max(0, min(int(spec_k), cap))
        req = Request(list(prompt), float(temperature), float(topp), int(max_tokens),
                      frozenset(eos_ids), seed=seed, presence=float(presence),
                      frequency=float(frequency), submitted_at=time.monotonic(),
                      req_id=req_id, spec_k=spec_k,
                      priority=int(priority), tenant=str(tenant))
        if resume_tokens:
            # cross-replica failover (ISSUE 16): the router replays a dead
            # upstream's journal here. Stamp the same resume record a warm
            # restart builds (_record_resume), except the key chain starts
            # from the REQUEST seed: this replica never held the stream, so
            # the post-commit key is reconstructed as advance(PRNGKey(seed),
            # n) — commit's own split is advance #1, each emitted decode
            # token past the first is one more. Greedy streams ignore the
            # key entirely, so an unseeded greedy resume pins seed 0.
            n = len(resume_tokens)
            req.resume_tokens = [int(t) for t in resume_tokens]
            req.produced = n
            req.key_advances = n - 1
            req.resume_key = self._advance_key(
                jax.random.PRNGKey(int(seed) if seed is not None else 0), n)
            req.recovered = True
        if timeout_s is not None and timeout_s > 0:
            req.timeout_s = float(timeout_s)
            req.deadline_at = req.submitted_at + req.timeout_s
        # flight-recorder record BEFORE the queue put: the worker may pop and
        # admit the request before this thread runs again
        trace.TRACER.req_submit(req.req_id, prompt_tokens=len(req.prompt),
                                t=req.submitted_at)
        self.pending.put(req)
        ins.REQUESTS_ADMITTED.inc()
        ins.QUEUE_DEPTH.set(self._queue_depth())
        if self.crashed is not None or not self._thread.is_alive():
            # lost the race with a worker crash: _fail_all may already have
            # drained the queue, so this request could sit there forever —
            # raise instead of handing back a Request nobody will serve
            raise SchedulerUnhealthy(
                f"scheduler worker died during submit ({self.crashed!r})")
        self._wake.set()
        return req

    def check_admission(self) -> None:
        """Admission control, cheapest check first; raises a
        SchedulerRejected subclass when this scheduler must not take new
        work. Rejected requests never touch the queue, so running
        generations see no perturbation at all. Also used by the API tier
        to shed STREAM requests before their response headers go out."""
        if self.crashed is not None or not self._thread.is_alive():
            ins.REQUESTS_SHED.labels(reason="unhealthy").inc()
            raise SchedulerUnhealthy(
                f"scheduler worker is dead ({self.crashed!r}); refusing work")
        if self.stalled:
            # the watchdog says the worker is hung mid-chunk: queueing more
            # work would strand more clients. The flag clears if heartbeats
            # resume, and 503+Retry-After tells callers to come back then.
            ins.REQUESTS_SHED.labels(reason="unhealthy").inc()
            raise SchedulerUnhealthy(
                "scheduler worker is stalled (device chunk past "
                "--stall-deadline-s); refusing work")
        if self._draining.is_set():
            ins.REQUESTS_SHED.labels(reason="draining").inc()
            raise SchedulerDraining("scheduler is draining; no new requests")
        # a capacity-deferred head request left the queue but still owes
        # service: it counts against the shed bound, so a queue backed up
        # behind pool exhaustion sheds at the same depth as any other backlog
        depth = self._queue_depth()
        if self.max_queue and depth >= self.max_queue:
            ins.REQUESTS_SHED.labels(reason="queue_full").inc()
            raise QueueFull(
                f"admission queue full ({depth} >= "
                f"--max-queue {self.max_queue})")
        try:
            faults.fire("scheduler.queue")
        except faults.InjectedFault as e:
            # the drill impersonates overflow, so it counts as overflow
            ins.REQUESTS_SHED.labels(reason="queue_full").inc()
            raise QueueFull(str(e)) from e

    def _busy(self) -> bool:
        """Whether the worker owes anyone progress (watchdog gating: an idle
        worker parked on its wake event must never read as stalled).

        Container occupancy alone is NOT enough: during admission start and
        commit the worker briefly holds a request in NO container (popped
        from the backlog / in-flight list, slot not yet assigned) while
        doing milliseconds of device work — a cross-thread drain() polling
        exactly then used to read the system as idle and cut the request
        mid-commit (found by the DLLAMA_LOCK_AUDIT timing perturbation,
        ISSUE 14). The time ledger's exclusive state closes the window: the
        worker is only truly idle when it says so."""
        return (bool(self.slots) or bool(self._inflight)
                or bool(self._recover) or bool(self._backlog)
                or self._deferred is not None or not self.pending.empty()
                or self.ledger.state() not in ("idle", None))

    def health(self) -> dict:
        """Liveness + readiness snapshot for the API tier's /health.

        `live`   — the worker thread can still make progress (alive, not
                   crashed, not known-hung): false means restart me.
        `ready`  — admit new work here: false while draining, saturated, or
                   not live (balancers should route away, not kill).
        The rest is the observability payload: queue depth, busy slots, and
        the age of the worker's last heartbeat."""
        qdepth = self._queue_depth()
        live = (self._thread.is_alive() and self.crashed is None
                and not self.join_failed and not self.stalled)
        saturated = bool(self.max_queue) and qdepth >= self.max_queue
        return {
            "live": live,
            "ready": live and not self._draining.is_set() and not saturated,
            "queue_depth": qdepth,
            "max_queue": self.max_queue,
            "busy_slots": int(np.asarray(self.engine.active).sum()),
            "n_slots": self.engine.n_slots,
            "in_flight_admissions": len(self._inflight),
            # paged KV pool occupancy (None on the dense layout); a deferred
            # head request is the capacity-wait signal operators watch
            "kv_pages": self.engine.kv_page_stats()
            if hasattr(self.engine, "kv_page_stats") else None,
            "admission_deferred": self._deferred is not None,
            "last_step_age_s": round(time.monotonic() - self._heartbeat, 3),
            "stall_deadline_s": self.stall_deadline_s,
            "stalled": self.stalled,
            "stall_count": self.stall_count,
            "draining": self._draining.is_set(),
            "crashed": repr(self.crashed) if self.crashed is not None else None,
            "join_failed": self.join_failed,
            # warm-restart supervision: lifetime restarts, the budget, and
            # how many recovered requests still await re-admission
            "restarts": self.restart_count,
            "restart_max": self.restart_max,
            "recovering": len(self._recover),
            # hybrid chunked prefill + preemption (ISSUE 12): the live
            # per-chunk budget (0 = legacy phase-split), lifetime
            # preempt/resume totals, and how many suspended requests are
            # parked in the backlog awaiting resume
            "prefill_budget": self._budget_now,
            "preemptions": self.preempt_count,
            "resumed": self.resume_count,
            "preempted_waiting": sum(
                1 for r in list(self._backlog) if r.preempted),
            # compile observability (ISSUE 13): operators see a recompile
            # storm from the health probe without scraping /metrics —
            # `unexpected` > 0 means the compiled-shape contract broke
            "compile": {
                "warmup": self.warmup,
                "warmed_buckets": (None if self.warmup_report is None
                                   else self.warmup_report["compiled"]),
                "full_coverage": (None if self.warmup_report is None
                                  else self.warmup_report["full_coverage"]),
                "compiles": compile_obs.LEDGER.total_compiles(),
                "unexpected_compiles": compile_obs.LEDGER.total_unexpected(),
            },
        }

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful drain: stop admission (submit raises SchedulerDraining),
        let in-flight and already-queued requests finish, then shut down.
        Returns True when everything completed inside the timeout; False
        means stragglers were cut off by shutdown."""
        self._draining.set()
        self._wake.set()
        trace.TRACER.event("drain.begin", cat="lifecycle", track="scheduler",
                           timeout_s=float(timeout_s))
        deadline = time.monotonic() + max(0.0, timeout_s)
        clean = False
        while time.monotonic() < deadline:
            if not self._busy():
                clean = True
                break
            if self.crashed is not None or not self._thread.is_alive():
                break  # nothing will ever finish; stop waiting
            time.sleep(0.02)
        if not clean:
            log.warning("drain timeout (%.1fs): %d slots / %d admissions / "
                        "%d queued still in flight — shutting down anyway",
                        timeout_s, len(self.slots), len(self._inflight),
                        self.pending.qsize())
        trace.TRACER.event("drain.end", cat="lifecycle", track="scheduler",
                           clean=clean)
        pool = getattr(self.engine, "pool", None)
        if pool is not None:
            # allocator integrity check at the lifecycle boundary: a drain
            # that leaks pages (or drove refcounts inconsistent) is reported
            # here — and counted — even when the serving run looked clean
            report = pool.audit(raise_on_fail=False)
            if not report["ok"]:
                log.error("kv page-pool audit FAILED at drain: %s",
                          "; ".join(report["problems"]))
        self.shutdown()
        return clean

    def latency_summary(self) -> dict:
        """Aggregate TTFT / inter-token latency over completed requests, plus
        the admission-stall record: the max/mean decode-to-decode gap that
        admission work (prefill chunks, commits) inserted between fused decode
        chunks — what batch-mates' ITL actually degrades by during a join.

        This is the host-side per-SCHEDULER convenience view; the same marks
        feed the process-wide metrics registry (`_observe_finish`) that
        `GET /metrics` exposes as dllama_ttft_seconds / dllama_itl_seconds /
        dllama_e2e_latency_seconds histograms — one observation point, two
        read paths."""
        with self._metrics_lock:
            done = list(self._completed)
            gaps = list(self._admit_gaps_ms)
        hgap = ins.DECODE_HOST_GAP_SECONDS.series()
        n_hgaps = int(hgap["count"] - self._host_gap_mark["count"])
        ttfts = [r.ttft_ms for r in done if r.ttft_ms is not None]
        itls = [r.itl_ms for r in done if r.itl_ms is not None]
        mean = lambda xs: sum(xs) / len(xs) if xs else None
        # tail latency from the sliding-window estimator (obs/perf): a mean
        # alone hides exactly the requests the SLO work exists for
        def q_ms(w, q):
            v = w.quantile(q)
            return None if v is None else round(v * 1000.0, 3)
        return {
            "completed": len(done),
            "ttft_ms_mean": mean(ttfts),
            "ttft_ms_p50": q_ms(self.perf.ttft, 0.5),
            "ttft_ms_p95": q_ms(self.perf.ttft, 0.95),
            "itl_ms_mean": mean(itls),
            "itl_ms_p50": q_ms(self.perf.itl, 0.5),
            "itl_ms_p95": q_ms(self.perf.itl, 0.95),
            "reused_prefix_tokens": self.reused_prefix_tokens,
            "admission_gaps": len(gaps),
            "admission_stall_ms_max": max(gaps) if gaps else None,
            "admission_stall_ms_mean": mean(gaps),
            "decode_host_gaps": n_hgaps,
            "decode_host_gap_ms_mean": (
                (hgap["sum"] - self._host_gap_mark["sum"]) * 1000.0 / n_hgaps
                if n_hgaps > 0 else None),
            # paged KV pool occupancy (None on the dense layout) — the same
            # numbers the dllama_kv_pages_{total,used,shared} gauges export
            "kv_pages": self.engine.kv_page_stats()
            if hasattr(self.engine, "kv_page_stats") else None,
            # radix prefix-cache accounting (None when off/dense): hit_tokens
            # is the saved-prefill-rows total the dllama_radix_* series export
            "radix": self.engine.radix_stats()
            if hasattr(self.engine, "radix_stats") else None,
            # speculative-decoding acceptance record (None when the engine
            # was built spec=0) — the dllama_spec_* series' host-side view:
            # tokens_per_cycle is the realized batch speedup per forward
            "spec": self.engine.spec_stats()
            if hasattr(self.engine, "spec_stats") else None,
            # hybrid chunked prefill + preemption (ISSUE 12): the live
            # budget and the lifetime preempt/resume record — the host-side
            # view of dllama_prefill_budget_tokens / dllama_preemptions_
            # total / dllama_resumed_total
            "hybrid": {
                "prefill_budget": self._budget_now,
                "mode": ("off" if not self._hybrid_on
                         else ("auto" if self._budget_ctl is not None
                               else "fixed")),
                "preemptions": self.preempt_count,
                "resumed": self.resume_count,
            },
            # compile-ledger record (ISSUE 13): lifetime compiles/seconds
            # and the unexpected (off-contract) count — the host-side view
            # of the dllama_jit_* series; `warmup` names the boot mode
            "compile": dict(compile_obs.LEDGER.summary(),
                            warmup_mode=self.warmup),
        }

    def reset_latency_stats(self) -> None:
        """Drop accumulated latency/stall samples (benches call this after
        their compile-warmup phase so first-compile gaps don't pollute the
        measured record). Also rewinds the loop's decode-gap anchor so the
        first post-reset gap cannot span back to a pre-reset decode chunk."""
        with self._metrics_lock:
            self._completed.clear()
            self._admit_gaps_ms.clear()
        self._host_gap_mark = ins.DECODE_HOST_GAP_SECONDS.series()
        self._t_dec_end = None
        self._t_consumed = None
        # fresh sliding windows too: warmup-compile latencies must not sit
        # in the p95 for the next minute of a measured window (same policy;
        # attribute swap is atomic for concurrent scrapes)
        self.perf = perf.PerfAggregator(slo=self.perf.slo)

    def cancel(self, req: Request, reason: str = "cancelled") -> None:
        """Release a request's slot. `reason` becomes the finish_reason when
        the request is still live — "cancelled" for real client
        cancellations (the default), "stop" when the API tier is releasing
        a stream that already ended on a string stop-sequence (a success).
        A no-op for requests that already finished."""
        req.cancel_reason = reason
        req.cancelled.set()
        self._wake.set()

    #: how long shutdown() waits for the worker before declaring it hung
    #: (attribute, not constant: fault drills shrink it instead of sleeping)
    join_timeout_s: float = 10.0

    #: ceiling on the exponential restart backoff (attribute, not constant:
    #: the chaos soak shrinks it so hundreds of injected crashes stay fast)
    restart_backoff_max_s: float = 5.0

    def shutdown(self) -> None:
        self._stop.set()
        self._wake.set()
        self._thread.join(timeout=self.join_timeout_s)
        if self._thread.is_alive():
            # a worker that won't die is almost certainly hung inside a
            # device call; it is daemonic so the process can still exit, but
            # the engine must be considered unusable — say so loudly and let
            # /health report it instead of silently returning
            self.join_failed = True
            log.warning(
                "scheduler worker failed to join within %.1fs (thread %r, "
                "alive=%s, %d slots / %d admissions still held) — engine "
                "state is unrecoverable; /health reports live=false",
                self.join_timeout_s, self._thread.name,
                self._thread.is_alive(), len(self.slots), len(self._inflight))

    # ------------------------------------------------------------------ loop

    def _observe_finish(self, req: Request) -> None:
        """The single registry write point for a terminal request: finish
        counter + TTFT/ITL/e2e histograms from the request's latency marks —
        the same marks the `_completed` ring (latency_summary's per-scheduler
        view) records, so /metrics and the summary cannot disagree. Also the
        single flight-recorder finish point: every terminal path (normal,
        cancel, crash, shutdown, admission reject) flows through here."""
        ins.REQUESTS_FINISHED.labels(reason=req.finish_reason or "unknown").inc()
        trace.TRACER.req_end(req.req_id, req.finish_reason or "unknown",
                             t=req.finished_at, **req.timings())
        if req.first_token_at is not None:
            ins.TTFT_SECONDS.observe(req.first_token_at - req.submitted_at)
        if req.finished_at is not None:
            ins.E2E_SECONDS.observe(req.finished_at - req.submitted_at)
        itl = req.itl_ms
        if itl is not None:
            ins.ITL_SECONDS.observe(itl / 1000.0)
        # the SLO/goodput join (obs/perf): same marks as the histograms
        # above, so the windowed quantiles, the burn counters, and /metrics
        # cannot disagree about what this request experienced
        self.perf.observe_finish(
            finish_reason=req.finish_reason or "unknown",
            ttft_ms=req.ttft_ms, itl_ms=itl,
            e2e_ms=(None if req.finished_at is None
                    else (req.finished_at - req.submitted_at) * 1000.0),
            tokens=req.produced)

    def restamp(self) -> None:
        """A profiler capture's two ends (utils/profiling.start_profile):
        the open state's and the open phase's annotations are closed and
        reopened, so the capture holds both."""
        self.ledger.restamp()
        self.phases.restamp()

    def _next_seq(self) -> int:
        """The seq of the launch the worker's next dispatch will make: what
        a phase of boundary, admission or commit work is done for."""
        return int(getattr(self.engine, "chunk_seq", 0)) + 1

    def _finish(self, req: Request, reason: str, keep_rows: int | None = None) -> None:
        # one phase wherever a request ends (emit, a boundary scan, an
        # aborted admission): the phase it ends in pauses meanwhile
        with self.phases("emit.finish", self._next_seq() - 1):
            if req.slot >= 0:
                if self._radix is not None:
                    # the tree is the cache: insert the trustworthy emitted
                    # prefix (full pages adopt a tree reference), then hand the
                    # slot's every page back — idle slots stay empty, and reuse
                    # for future requests comes from the tree, not the slot.
                    # keep_rows=None means the rows are unspecified (error/NaN/
                    # crash paths): nothing enters the tree.
                    if keep_rows:
                        self.engine.radix_insert(
                            req.slot, self.slot_tokens.get(req.slot, [])[:keep_rows])
                    self.engine.release(req.slot, None)
                    self.slot_tokens[req.slot] = []
                else:
                    self.engine.release(req.slot, keep_rows)
                    if keep_rows is not None:
                        # only the first keep_rows tokens have live KV rows (the
                        # last emitted token was sampled but never fed back)
                        self.slot_tokens[req.slot] = self.slot_tokens.get(req.slot, [])[:keep_rows]
                    else:
                        self.slot_tokens[req.slot] = []  # unknown state: never reuse
                self.slots.pop(req.slot, None)
                req.slot = -1
            req.finish_reason = req.finish_reason or reason
            req.finished_at = time.monotonic()
            with self._metrics_lock:
                self._completed.append(req)
                del self._completed[:-256]  # bound the ring
            self._observe_finish(req)
            ins.BUSY_SLOTS.set(len(self.slots))
            req.out.put(_END)

    def _emit(self, req: Request, token: int, row_at_emit: int) -> bool:
        """Queue one token; returns True when the request just finished."""
        if req.first_token_at is None:
            req.first_token_at = time.monotonic()
            trace.TRACER.req_first_token(req.req_id, t=req.first_token_at)
        req.out.put(int(token))
        req.produced += 1
        ins.TOKENS_GENERATED.inc()
        if req.slot >= 0:
            self.slot_tokens.setdefault(req.slot, []).append(int(token))
        if token in req.eos_ids:
            self._finish(req, "stop", keep_rows=row_at_emit)
            return True
        if req.produced >= req.max_tokens:
            self._finish(req, "length", keep_rows=row_at_emit)
            return True
        return False

    def _pick_slot(self, prompt: list[int]) -> tuple[int | None, int, int | None]:
        """(slot, reusable_prefix_len, donor): the idle slot whose cached
        token history shares the longest full prefix with `prompt`. When a
        DIFFERENT slot (idle or actively decoding) holds a longer matching
        prefix, the cheapest idle slot is chosen and `donor` names the slot
        whose KV rows should be copied in first (cross-slot prefix share —
        e.g. a common system prompt cached once serves every slot). Slots
        reserved by in-flight admissions are neither destinations nor donors
        (their rows are mid-overwrite)."""
        reserved = {adm.slot for _, adm, _ in self._inflight}
        idle = [
            s for s in range(self.engine.n_slots)
            if not self.engine.active[s] and s not in reserved
        ]
        if not idle:
            return None, 0, None

        # cross-slot donors need the engine's slot-copy primitive (dp meshes
        # shard the batch axis, where donor search stays within idle slots)
        cross_ok = getattr(self.engine, "supports_cross_slot_copy", False)
        donors = [s for s in range(self.engine.n_slots) if s not in reserved] if cross_ok else idle
        lcp = self._lcp_lengths(prompt, donors)
        best_idle = max(idle, key=lcp.__getitem__)
        best_any = max(donors, key=lcp.__getitem__)
        if lcp[best_any] > lcp[best_idle]:
            dst = min(idle, key=lambda s: len(self.slot_tokens.get(s, [])))
            return self._resumable(dst, lcp[best_any], best_any)
        if lcp[best_idle] > 0:
            return self._resumable(best_idle, lcp[best_idle], None)
        return min(idle, key=lambda s: len(self.slot_tokens.get(s, []))), 0, None

    def _resumable(self, slot: int, reuse: int,
                   donor: int | None) -> tuple[int, int, int | None]:
        """Clip a matched prefix to what the engine can start after
        (`BatchEngine.resumable_rows`: everything for a KV-only model; for
        recurrent state only the slot's own rows, and only where the state
        stands). Rows clipped away are prefilled again and counted."""
        resume = getattr(self.engine, "resumable_rows", None)
        ok = reuse if resume is None else resume(slot, reuse, donor)
        if ok == reuse:
            return slot, reuse, donor
        cross = donor is not None and donor != slot
        ins.PREFIX_ROWS_RECOMPUTED.labels(
            reason="cross_slot" if cross else "state_elsewhere").inc(reuse - ok)
        return slot, ok, None

    def _lcp_lengths(self, prompt: list[int], donors: list[int]) -> dict[int, int]:
        """Longest-common-prefix length of `prompt` against every donor
        slot's cached token history, in ONE padded-matrix comparison (the
        per-slot np.nonzero scan was O(B·len) Python work on the admission
        path). Reusable rows = LONGEST COMMON PREFIX (not all-or-nothing: a
        shared system prompt with a divergent tail still reuses the common
        part), capped so at least one prompt token remains to prefill (stale
        rows past it are masked); an ACTIVE donor's last emitted token has
        no KV row yet, hence its extra -1 cap."""
        caps = {}
        for s in donors:
            cached = self.slot_tokens.get(s, [])
            n = min(len(cached), len(prompt) - 1)
            if self.engine.active[s]:
                n = min(n, len(cached) - 1)
            caps[s] = max(n, 0)
        width = max(caps.values(), default=0)
        if width <= 0:
            return dict.fromkeys(donors, 0)
        # pad with -1 (never a token id) so rows shorter than the widest cap
        # mismatch past their own cap by construction
        mat = np.full((len(donors), width), -1, np.int64)
        for i, s in enumerate(donors):
            if caps[s]:
                mat[i, : caps[s]] = self.slot_tokens[s][: caps[s]]
        hit = mat == np.asarray(prompt[:width], np.int64)[None, :]
        # leading run of equalities: cumprod zeroes everything at and past
        # the first mismatch, so the row sum IS the LCP length
        lens = np.cumprod(hit, axis=1).sum(axis=1)
        return {s: int(n) for s, n in zip(donors, lens)}

    def _queue_depth(self) -> int:
        """Requests owed service but not yet admitted: the pending intake
        queue, the policy backlog (incl. preempted requests awaiting
        resume), the capacity-deferred head, and any restart-recovered
        requests awaiting re-admission (one definition for the gauge,
        /health, and the --max-queue shed bound — they must not
        disagree)."""
        return (self.pending.qsize() + len(self._backlog)
                + (1 if self._deferred is not None else 0)
                + len(self._recover))

    def _reclaim_pages(self, needed: int) -> bool:
        """Free KV pages for the all-starved decode rescue: LRU radix-tree
        leaves when the tree is the cache, idle slots' retained pages on
        the legacy path. Returns True when anything came free."""
        if self._radix is not None:
            return self.engine.radix_evict(needed) > 0
        return self._evict_idle_pages(needed, set())

    def _evict_idle_pages(self, needed: int, exclude: set) -> bool:
        """Paged prefix-cache reclaim: drop idle slots' cached pages
        (smallest caches first — the cheapest reuse to lose) until `needed`
        pages came free, then STOP — a one-page shortfall must not wipe
        every cached prefix. `exclude` protects the chosen destination and
        donor. Returns True when anything was freed."""
        reserved = {adm.slot for _, adm, _ in self._inflight}
        victims = sorted(
            (s for s in range(self.engine.n_slots)
             if not self.engine.active[s] and s not in reserved
             and s not in exclude and self.slot_tokens.get(s)),
            key=lambda s: len(self.slot_tokens.get(s, [])),
        )
        freed = 0
        for s in victims:
            if freed >= needed:
                break
            freed += self.engine.drop_slot_pages(s)
            self.slot_tokens[s] = []
        return freed > 0

    def _shed_timeout(self, req: Request, where: str = "queued") -> None:
        """Terminal 'timeout' finish for a not-yet-admitted request: shed
        BEFORE prefill — no slot, no pages, no device work spent on a
        request whose client stopped waiting. A timeout is a clean terminal
        finish, not an error: the stream just ends with
        finish_reason="timeout"."""
        ins.REQUESTS_SHED.labels(reason="timeout").inc()
        trace.TRACER.event("request.timeout", cat="deadline",
                           track="requests", req_id=req.req_id, where=where)
        # _finish handles the rest (slot is -1: no release) — crucially the
        # _completed ring append, so queue-expired timeouts show up in
        # latency_summary() exactly like decode-boundary ones
        self._finish(req, "timeout")

    def _shed_expired_queued(self) -> None:
        """Deadline sweep over requests the worker has NOT admitted yet:
        the pending queue, the capacity-deferred head, and the restart-
        recover list. The pop path below also checks deadlines, but a
        saturated server (every slot busy, or a parked deferred head) can
        go entire requests without popping anything — timeout_s must bound
        the client's wait even when no slot ever frees. Runs once per
        chunk boundary, same granularity as the running-request check."""
        now = time.monotonic()

        def expired(r: Request) -> bool:
            return r.deadline_at is not None and now >= r.deadline_at

        dead: list[Request] = []
        with self.pending.mutex:
            q = self.pending.queue
            if any(expired(r) for r in q):
                dead.extend(r for r in q if expired(r))
                keep = [r for r in q if not expired(r)]
                q.clear()
                q.extend(keep)
        if any(expired(r) for r in self._backlog):
            # the policy backlog too — incl. preempted requests whose
            # deadline passed while suspended (a clean 'timeout' finish;
            # their already-emitted tokens stand)
            dead.extend(r for r in self._backlog if expired(r))
            self._backlog = [r for r in self._backlog if not expired(r)]
        if self._deferred is not None and expired(self._deferred):
            dead.append(self._deferred)
            self._deferred = None
        if any(expired(r) for r in self._recover):
            dead.extend(r for r in self._recover if expired(r))
            self._recover = [r for r in self._recover if not expired(r)]
        for req in dead:
            self._shed_timeout(req)

    # --------------------------------------- scheduling policy (ISSUE 12)

    def _drain_pending(self) -> None:
        """Move intake-queue arrivals into the policy backlog (worker-side
        only; submit() keeps the thread-safe Queue as its entry point)."""
        while True:
            try:
                self._backlog.append(self.pending.get_nowait())
            except queue.Empty:
                return

    def _tenant_weight(self, tenant: str) -> float:
        return max(float(self.tenant_weights.get(tenant, 1.0)), 1e-6)

    def _select_next(self) -> Request | None:
        """Policy pick from the backlog: the highest priority class
        present; within it the tenant with the smallest WFQ virtual time;
        within a tenant, FIFO. Pops and returns the pick (None when the
        backlog is empty). Cancelled/expired entries are popped too — the
        caller's existing terminal handling covers them."""
        if not self._backlog:
            return None
        best_i = 0
        best_key = None
        for i, r in enumerate(self._backlog):
            key = (-int(r.priority), self._tenant_vt.get(r.tenant, 0.0), i)
            if best_key is None or key < best_key:
                best_key, best_i = key, i
        return self._backlog.pop(best_i)

    def _charge_tenant(self, req: Request) -> None:
        """Start-time fair queueing charge at admission: the request's
        start tag is max(its tenant's own finish tag, the global virtual
        clock `_vt_now`), its tenant's finish tag advances by
        (prompt + max_tokens) / weight from there, and the clock advances
        to the start tag. A tenant returning from idle therefore gets one
        immediate pick and then competes from 'now' — idle time banks no
        credit, which is what bounds any backlogged tenant's wait to its
        fair share (the starvation bound the tests drive). Charged ONCE
        per request lifetime: a preempted request resuming (or a deferred
        head rejoining the backlog) was already paid for; billing it again
        would compound the very deprioritization that suspended it."""
        if req.wfq_charged:
            return
        req.wfq_charged = True
        # start-time fair queueing: the admission's start tag is
        # max(tenant's own finish tag, the global virtual clock) and the
        # clock advances to that start — a tenant returning from idle is
        # snapped to 'now' (one immediate pick, then fair share; idle time
        # banks no credit), while a fresh system stays at clock 0 so
        # weights bite from the first admission
        own = self._tenant_vt.get(req.tenant, 0.0)
        start = max(own, self._vt_now)
        cost = (len(req.prompt) + max(int(req.max_tokens), 1))
        self._tenant_vt[req.tenant] = (
            start + cost / self._tenant_weight(req.tenant))
        self._vt_now = start

    def _record_resume(self, req: Request, slot: int) -> bool:
        """Stamp `req` with its bit-exact resume record off `slot`'s
        settled state: the emitted tokens and the PRNG key advanced to the
        interruption point — advanced by the tokens emitted SINCE the last
        (re)commit only (after a prior resume, keys[slot] is already an
        advanced key; replaying the cumulative produced-1 would
        double-count and silently break sampled-stream resume). The ONE
        definition site for the resume invariant, shared by preemption and
        warm-restart recovery. Returns False when the emit records
        disagree (no trustworthy resume exists)."""
        emitted = self.slot_tokens.get(slot, [])[len(req.prompt):]
        if req.produced < 1 or len(emitted) != req.produced:
            return False
        req.resume_tokens = list(emitted)
        req.resume_key = self._advance_key(
            self.engine.keys[slot], req.produced - 1 - req.key_advances)
        req.key_advances = req.produced - 1
        return True

    def _preempt(self, req: Request, reason: str) -> bool:
        """Suspend a RUNNING request at this (settled) chunk boundary:
        record its resume point — emitted tokens + PRNG key advanced to the
        interruption, exactly the warm-restart resume record — then release
        the slot while the KV pages stay referenced: the radix tree adopts
        the written prefix on the paged layout (resume later maps it back
        by refcount, near-zero recompute — only a partial boundary page
        re-prefills), the kept slot rows serve the same role on dense. The
        request parks in the backlog; policy decides when it resumes.
        Returns False when the request has no trustworthy resume record
        (safer to let it run)."""
        slot = req.slot
        if not self._record_resume(req, slot):
            return False
        req.preempted = True
        rows = int(self.engine.pos[slot])
        if self._radix is not None:
            self.engine.radix_insert(slot, self.slot_tokens[slot][:rows])
            self.engine.release(slot, None)
            self.slot_tokens[slot] = []
        else:
            self.engine.release(slot, rows)
            self.slot_tokens[slot] = self.slot_tokens.get(slot, [])[:rows]
        self.slots.pop(slot, None)
        req.slot = -1
        self._backlog.append(req)
        self.preempt_count += 1
        ins.BUSY_SLOTS.set(len(self.slots))
        ins.PREEMPTIONS.labels(reason=reason).inc()
        trace.TRACER.event("request.preempted", cat="scheduling",
                           track="requests", req_id=req.req_id,
                           reason=reason, tokens=req.produced)
        log.info("preempted request (reason=%s, %d tokens emitted; pages "
                 "stay referenced)", reason, req.produced,
                 extra=trace.log_extra(req.req_id))
        return True

    def _maybe_preempt(self) -> None:
        """Boundary preemption check: when a STRICTLY higher-priority
        request is waiting and blocked — no free slot (reason='slot'), or
        the capacity-deferred head out-ranks a runner (reason='capacity') —
        suspend the lowest-priority running request (most recently admitted
        among ties: least sunk work lost). At most one preemption per
        boundary; admission this same boundary reuses the freed slot and
        pages."""
        if not self._preempt_on or not self.slots:
            return
        now = time.monotonic()
        waiting = [r for r in self._backlog + self._recover
                   + ([self._deferred] if self._deferred is not None else [])
                   if not r.cancelled.is_set()
                   and (r.deadline_at is None or now < r.deadline_at)]
        if not waiting:
            return
        top = max(waiting, key=lambda r: int(r.priority))
        victims = [r for r in self.slots.values()
                   if int(r.priority) < int(top.priority)
                   and not r.cancelled.is_set()]
        if not victims:
            return
        reserved = {adm.slot for _, adm, _ in self._inflight}
        free_slots = sum(1 for s in range(self.engine.n_slots)
                         if not self.engine.active[s] and s not in reserved)
        if free_slots <= 0:
            reason = "slot"
        elif (self._deferred is not None
              and int(self._deferred.priority) >= int(top.priority)):
            # a slot is free but the highest-priority waiter is parked on
            # KV-page capacity: freeing a low-priority runner's pages (its
            # release hands them to the tree, where admission reclaim can
            # evict them) is the only lever besides waiting
            reason = "capacity"
        else:
            return
        victim = min(victims,
                     key=lambda r: (int(r.priority),
                                    -(r.admitted_at or 0.0)))
        self._preempt(victim, reason)

    def _admit_starts(self, boundary: bool = True) -> None:
        """Pop pending requests into in-flight admissions while slots allow.

        ``boundary=False`` is the overlapped-loop fast path (hybrid only):
        admission STARTS are safe off an in-flight non-spec chunk —
        add_begin's device work is surgical per-row/page updates composed
        on the carry, and the admitting slot is inactive in the chunk — so
        a new request's first hybrid slice dispatches as the very next
        successor instead of draining the pipeline first. Preemption is
        skipped there (releasing a RUNNING slot needs settled mirrors).

        Paged layout: admission capacity is FREE PAGES, not free slots — a
        request whose prompt (+ one decode page) the pool cannot cover first
        reclaims idle slots' cached pages, and if still short is parked in
        `_deferred` (FIFO head; later requests wait behind it) until
        releases free capacity. Shedding still applies while it waits: the
        deferred request counts toward --max-queue depth."""
        self._drain_pending()
        self._shed_expired_queued()
        if boundary:
            self._maybe_preempt()
        if (self._deferred is not None and self._backlog
                and max(int(r.priority) for r in self._backlog)
                > int(self._deferred.priority)):
            # priority-inversion guard: a capacity-parked lower-priority
            # head must not gate a higher-priority arrival — it rejoins the
            # policy backlog and competes from there (its pages were never
            # held; deferral is a wait, not a reservation)
            self._backlog.append(self._deferred)
            self._deferred = None
        reserved = len(self._inflight)
        while (self._recover or self._deferred is not None
               or self._backlog):
            if int((~self.engine.active).sum()) - reserved <= 0:
                return
            from_recover = False
            if self._recover:
                # restart-recovered requests re-admit FIRST (they are the
                # oldest work in the system); mid-stream resumes re-prefill
                # prompt + emitted tokens below
                req = self._recover.pop(0)
                from_recover = True
            elif self._deferred is not None:
                req, self._deferred = self._deferred, None
            else:
                req = self._select_next()
                if req is None:
                    return
                self._charge_tenant(req)
            if req.cancelled.is_set():
                req.finish_reason = req.cancel_reason
                req.finished_at = time.monotonic()
                self._observe_finish(req)
                req.out.put(_END)
                continue
            if (req.deadline_at is not None
                    and time.monotonic() >= req.deadline_at):
                # expired between the sweep and the pop: same shed path
                self._shed_timeout(req)
                continue
            # the rows this admission must write: the prompt — plus, for a
            # restart resume, every already-emitted token except the last
            # (a sampled token's KV row only exists once it is fed back;
            # the last one becomes the decode carry via resume_commit)
            toks = (req.prompt if req.resume_tokens is None
                    else req.prompt + req.resume_tokens[:-1])
            if len(toks) >= self.engine.seq_len:
                # reject BEFORE slot search or any donor copy: a hopeless
                # admission must not evict a slot's cached prefix (nor pay
                # the per-slot LCP scan)
                req.finish_reason = "error"
                req.finished_at = time.monotonic()
                self._observe_finish(req)
                req.out.put(ValueError(
                    f"prompt ({len(toks)}) exceeds seq_len {self.engine.seq_len}"
                ))
                continue
            pool = getattr(self.engine, "pool", None)
            if (pool is not None
                    and self.engine.min_pages_for(len(toks)) > pool.n_pages):
                # never-fits reject: the prompt's pages (+ the decode
                # reserve) must ALL be resident at once, and reused/shared
                # prefix pages still occupy pool pages — so the bound is
                # absolute, independent of any cached prefix. Deferring such
                # a request would deadlock the FIFO head forever; reject it
                # like the seq_len check.
                req.finish_reason = "error"
                req.finished_at = time.monotonic()
                self._observe_finish(req)
                req.out.put(ValueError(
                    f"prompt ({len(toks)}) needs "
                    f"{self.engine.min_pages_for(len(toks))} KV pages; "
                    f"the pool holds {pool.n_pages}"))
                continue
            rhit = None
            if self._radix is not None:
                # radix reuse: the GLOBAL tree, not resident slots, is the
                # prefix cache — any idle slot serves (they are all empty),
                # the walk finds the longest mappable prefix, and capacity
                # shortfalls reclaim LRU tree leaves (the matched path is
                # protected) before the request parks
                taken = {adm.slot for _, adm, _ in self._inflight}
                slot = next(s for s in range(self.engine.n_slots)
                            if not self.engine.active[s] and s not in taken)
                reuse, rhit = self.engine.radix_lookup(toks)
                deficit = self.engine.radix_admission_deficit(len(toks), reuse)
                if deficit > 0 and self.engine.radix_evict(deficit, rhit) > 0:
                    deficit = self.engine.radix_admission_deficit(len(toks),
                                                                  reuse)
                cross = False
            else:
                slot, reuse, donor = self._pick_slot(toks)
                cross = donor is not None and donor != slot and reuse > 0
                deficit = self.engine.admission_deficit(slot, reuse,
                                                        len(toks), cross)
                if deficit > 0:
                    # pool short: reclaim just enough idle cache (keeping the
                    # destination and donor — their rows are this admission's
                    # reuse), then re-pick (eviction may change the best donor)
                    if self._evict_idle_pages(deficit, {slot, donor}):
                        slot, reuse, donor = self._pick_slot(toks)
                        cross = donor is not None and donor != slot and reuse > 0
                    deficit = self.engine.admission_deficit(slot, reuse,
                                                            len(toks), cross)
            if deficit > 0:
                # still short: every missing page is held by RUNNING
                # requests — park at the head until releases free them.
                # A recovered request parks back at the recover head
                # (the _deferred box may already hold the pre-crash
                # queue head — never overwrite it).
                if from_recover:
                    self._recover.insert(0, req)
                else:
                    self._deferred = req
                return
            try:
                if rhit is not None and reuse:
                    # map the tree prefix into the slot by refcount: block
                    # table written, zero copies; a partial boundary page is
                    # copy-on-written inside add_begin's prepare_admission
                    self.engine.radix_map(slot, rhit)
                elif cross:
                    # cross-slot share: materialize the donor's prefix rows
                    # in the destination before the delta prefill
                    self.engine.copy_prefix_rows(donor, slot, reuse)
                    self.slot_tokens[slot] = list(
                        self.slot_tokens.get(donor, [])[:reuse]
                    )
                adm = self.engine.add_begin(slot, toks[reuse:],
                                            start_pos=reuse, req_id=req.req_id)
            except Exception as e:  # bad request (too long, …) — fail just this one
                log.exception("admission rejected",
                              extra=trace.log_extra(req.req_id))
                # the slot's cache state is unknown: a paged add_begin may
                # have freed + partially reallocated its pages before
                # failing (e.g. a pool.alloc fault mid-grow), so the old
                # token-history claim could map reused prompts onto
                # uninitialized rows. Drop the claim and the pages — safe,
                # merely losing this slot's prefix reuse.
                self.slot_tokens[slot] = []
                if hasattr(self.engine, "drop_slot_pages"):
                    self.engine.drop_slot_pages(slot)
                req.finish_reason = "error"
                req.finished_at = time.monotonic()
                self._observe_finish(req)
                req.out.put(e)
                continue
            req.slot = slot
            req.admitted_at = time.monotonic()
            trace.TRACER.req_admitted(req.req_id, slot=slot,
                                      reused_tokens=reuse, t=req.admitted_at)
            self._inflight.append((req, adm, reuse))
            reserved += 1

    def _abort_admission(self, req, adm, reason) -> None:
        # rows past start_pos may be partially overwritten: the old history
        # no longer describes the slot's KV contents — and _finish must not
        # preserve them (keep_rows=None) nor miss the metrics ring
        self.slot_tokens[adm.slot] = []
        if isinstance(reason, Exception):
            # reason BEFORE the put: a client reads finish_reason the moment
            # the exception lands on its queue — it must never see None
            req.finish_reason = "error"
            req.out.put(reason)
            reason = "error"
        self._finish(req, reason)

    def _commit_admission(self, req: Request, adm, reuse: int) -> None:
        """Commit the HEAD in-flight admission (fully pumped): activate the
        slot, emit the first token (fresh admissions) or install the resume
        carry (restart/preemption resumes), insert radix prefixes, and do
        the recovery/resume accounting. Callable from the boundary pump AND
        opportunistically from the overlapped loop while the admission's
        last (non-spec) chunk is still in flight — the admitting slot is
        inactive in that chunk and every commit-side device write is a
        surgical per-row update off the carry, so committing early is
        value-safe and saves a full pipeline drain (the joiner's first
        token goes out as soon as its logits materialize, and running
        streams never eat the boundary's idle window)."""
        self.ledger.transition("commit")
        # popped ONCE, up front: a failure anywhere below leaves the tuple
        # in the CALLER's hands (its except aborts this request), never a
        # second pop eating the NEXT admission's entry
        assert self._inflight and self._inflight[0][1] is adm
        self._inflight.pop(0)
        with self.phases("commit.activate", self._next_seq()):
            self._activate_admission(req, adm, reuse)

    def _activate_admission(self, req: Request, adm, reuse: int) -> None:
        """_commit_admission's body under its `commit.activate` phase (the
        engine times a first token it still has to sample as
        `commit.sample`, which pauses this one)."""
        if req.resume_tokens is not None:
            # restart/preemption resume: install the last emitted token and
            # the recorded PRNG key as the decode carry — no new token is
            # sampled, so the client's stream continues exactly where it
            # was cut
            self.engine.resume_commit(
                adm, req.resume_tokens[-1], req.resume_key,
                req.temperature, req.topp,
                presence=req.presence, frequency=req.frequency,
                counted=(req.resume_tokens[:-1]
                         if (req.presence or req.frequency)
                         else None),
                spec_k=req.spec_k)
            self.slot_tokens[adm.slot] = (list(req.prompt)
                                          + list(req.resume_tokens))
            self.slots[adm.slot] = req
            if self._radix is not None:
                # resumed streams re-enter the tree too: rows written =
                # prompt + all but the unfed last resume token (so a SECOND
                # resume of a shared prefix maps instead of re-prefilling)
                if reuse:
                    self._radix.note_served(reuse)
                self.engine.radix_insert(
                    adm.slot,
                    list(req.prompt) + list(req.resume_tokens[:-1]))
            trace.TRACER.req_prefill_done(
                req.req_id, tokens=len(adm.toks) + reuse,
                reused=reuse)
        else:
            first = self.engine.add_commit(adm, req.temperature,
                                           req.topp,
                                           seed=req.seed,
                                           presence=req.presence,
                                           frequency=req.frequency,
                                           spec_k=req.spec_k)
            self.reused_prefix_tokens += reuse  # rows really served
            ins.REUSED_PREFIX_TOKENS.inc(reuse)
            self.slot_tokens[adm.slot] = list(req.prompt)
            self.slots[adm.slot] = req
            if self._radix is not None:
                # saved-prefill accounting at commit (rows REALLY served),
                # and the prompt's full pages enter the tree NOW —
                # concurrent requests sharing a system prompt hit it while
                # this one is still decoding
                if reuse:
                    self._radix.note_served(reuse)
                self.engine.radix_insert(adm.slot, req.prompt)
            trace.TRACER.req_prefill_done(
                req.req_id, tokens=len(req.prompt), reused=reuse)
            self._emit(req, first, int(self.engine.pos[adm.slot]))
        if req.recovered:
            # counted at the moment the request really made it back into a
            # slot (not at restart time — it could still fail or cancel
            # during re-admission)
            req.recovered = False
            ins.REQUESTS_RECOVERED.inc()
            trace.TRACER.event("request.recovered",
                               cat="supervision", track="requests",
                               req_id=req.req_id,
                               tokens=req.produced)
        elif req.preempted:
            # a preempted request is back in a slot and its stream
            # continues (byte-identical to uninterrupted)
            req.preempted = False
            self.resume_count += 1
            ins.RESUMED.inc()
            trace.TRACER.event("request.resumed",
                               cat="scheduling", track="requests",
                               req_id=req.req_id,
                               tokens=req.produced)

    def _commit_ready_inflight(self, sampled_only: bool = False) -> None:
        """Opportunistic early commit (overlapped loop): while the chunk in
        flight is a plain/hybrid (non-spec) chunk, a fully-pumped head
        admission can commit NOW — blocking only on its own logits (which
        materialize with that chunk) instead of draining the pipeline for a
        whole boundary. Spec chunks are excluded: their data-dependent
        position advance must settle before any host-side slot activation
        touches shared state. `sampled_only` (the pipelined commit) leaves
        a fresh admission whose first token is not being sampled yet: its
        logits come with the chunk just dispatched, and reading them now
        would hold the host for that whole chunk."""
        while self._inflight:
            req, adm, reuse = self._inflight[0]
            now = time.monotonic()
            if (adm.off < len(adm.toks) or req.cancelled.is_set()
                    or (req.deadline_at is not None
                        and now >= req.deadline_at)):
                return  # mid-pump or needs abort handling at a boundary
            if (sampled_only and adm.sampled is None
                    and req.resume_tokens is None):
                return
            try:
                self._commit_admission(req, adm, reuse)
            except Exception as e:
                log.exception("commit failed",
                              extra=trace.log_extra(req.req_id))
                # _commit_admission pops up front, so the head here is the
                # NEXT admission — pop only if the failure preceded the pop
                if self._inflight and self._inflight[0][1] is adm:
                    self._inflight.pop(0)
                self._abort_admission(req, adm, e)

    def _sample_ready_inflight(self) -> None:
        """Pipelined commit, first half: dispatch the first-token sampling
        of every fully-pumped fresh admission, reading nothing: one small
        program an admission (`add_sample`), enqueued in well under a
        millisecond, so the successor still leaves while the chunk in
        flight runs. It queues behind that chunk (which carried the
        admission's last prompt rows) and ahead of the successor about to
        be dispatched, so the commit that follows the chunk's consumption
        finds its token ready: the device never waits for a commit."""
        for req, adm, _ in self._inflight:
            if adm.off < len(adm.toks):
                return  # admissions pump head first
            if (adm.sampled is None and req.resume_tokens is None
                    and not req.cancelled.is_set()):
                self.engine.add_sample(adm, req.temperature, req.topp,
                                       seed=req.seed)

    def _hybrid_now(self) -> bool:
        """Whether in-flight admissions ride fused hybrid chunks right now:
        the hybrid step is enabled AND there are decoders to fuse with
        (with no decoders the legacy pump IS the fast path — nothing to
        protect, prefill at full speed)."""
        return self._hybrid_on and bool(self.slots)

    def _pump_admissions(self) -> bool:
        """Advance in-flight admissions. Under the hybrid step (ISSUE 12)
        an admission's prefill rides the fused decode chunks instead —
        this pump then only COMMITS fully-pumped admissions (and applies
        the hard TTFT-deadline override). On the legacy phase-split path
        (--prefill-budget 0, or no decoders): when interleaving, pump
        prefill chunks of the head admission until the stall budget is
        spent (decode chunks run between calls); when not, the whole
        queue. An admission past the TTFT deadline ignores the budget and
        pumps to completion. Returns True if any admission work ran."""
        worked = False
        t0 = time.monotonic()
        while self._inflight:
            req, adm, reuse = self._inflight[0]
            if req.cancelled.is_set():
                self._inflight.pop(0)
                self._abort_admission(req, adm, "cancelled")
                continue
            if (req.deadline_at is not None
                    and time.monotonic() >= req.deadline_at):
                # deadline crossed mid-prefill: stop spending chunks on it —
                # the slot's partial rows are abandoned like a cancel's
                self._inflight.pop(0)
                trace.TRACER.event("request.timeout", cat="deadline",
                                   track="requests", req_id=req.req_id,
                                   where="prefill")
                self._abort_admission(req, adm, "timeout")
                continue
            pumped = adm.off >= len(adm.toks)
            if not pumped and self._hybrid_now():
                # the fused hybrid chunks carry this prefill (budget tokens
                # per chunk, _dispatch_chunk) — nothing to pump here unless
                # the hard TTFT deadline says finish it NOW despite the
                # decoders (the one pacing override that survives hybrid)
                overdue = (
                    self.admit_ttft_deadline_ms is not None
                    and (time.monotonic() - req.submitted_at) * 1000.0
                    >= self.admit_ttft_deadline_ms)
                if not overdue:
                    return worked
            try:
                tr = trace.TRACER
                done = pumped
                if not pumped:
                    self.ledger.transition("prefill")
                    sp = tr.span("prefill.chunk", cat="prefill",
                                 req_id=req.req_id)
                    with self.phases("admit.pump", self._next_seq()):
                        done = self._pump_chunk(adm)
                    if tr.enabled:
                        sp.end(slot=adm.slot, off=int(adm.off),
                               total=len(adm.toks))
                    worked = True
                if done:
                    self._commit_admission(req, adm, reuse)
            except Exception as e:
                log.exception("prefill failed",
                              extra=trace.log_extra(req.req_id))
                # add_step failures leave the head in place; a commit
                # failure reaches here with it already popped by
                # _commit_admission — pop only our own tuple, never the
                # next admission's
                if self._inflight and self._inflight[0][1] is adm:
                    self._inflight.pop(0)
                self._abort_admission(req, adm, e)
                continue
            if not (self.admit_interleave and self.slots):
                continue  # no decoders to protect: drain the queue
            # evaluated AFTER the chunk ran (and its device sync), so an
            # admission that crosses the deadline during the chunk is
            # honored this visit, not one decode chunk late
            overdue = (
                self.admit_ttft_deadline_ms is not None
                and (time.monotonic() - req.submitted_at) * 1000.0
                >= self.admit_ttft_deadline_ms
            )
            if done and overdue:
                # an overdue admission just committed under the deadline
                # override: yield a decode chunk before touching the next
                # head, so a burst of overdue joiners costs one prefill per
                # visit — never the sum of all of them — regardless of how
                # much budget the override left unspent
                return worked
            if (time.monotonic() - t0) * 1000.0 < self.admit_stall_budget_ms:
                continue  # cheap so far: keep pumping
            if not done and overdue:
                # TTFT deadline: finish THIS admission despite the budget
                continue
            # stall budget spent: let a decode chunk run now
            return worked
        return worked

    def _pump_chunk(self, adm) -> bool:
        """One prefill chunk of a pumped admission and its device sync (the
        `admit.pump` phase); True when the prompt is fully written."""
        done = self.engine.add_step(adm)
        if self.slots and adm.logits is not None:
            # sync whenever decoders could stall: JAX dispatch is
            # async, so without this the pacing clock AND the
            # admission-gap metric would see host dispatch time
            # only (near zero on TPU) while the chunk's device
            # time silently serialized into the next decode
            # chunk — under-pacing the budget and mis-attributing
            # the stall. Applied in every admission mode so the
            # sync/strict/paced A/B compares like with like; the
            # chunk must finish before the next decode chunk
            # anyway (same device stream). With no decoders there
            # is no stall to attribute and dispatch stays
            # pipelined.
            jax.block_until_ready(adm.logits)
        return done

    def _fail_req(self, req: Request, exc: BaseException) -> None:
        """Crash-path finish: mark the request failed and unblock its
        consumer WITHOUT touching the engine (whose state is unknown after a
        worker crash — release()/donated buffers may be invalid)."""
        req.finish_reason = "error"
        req.finished_at = time.monotonic()
        with self._metrics_lock:
            self._completed.append(req)
            del self._completed[:-256]
        self._observe_finish(req)
        req.out.put(exc)
        req.out.put(_END)

    def _fail_all(self, exc: BaseException) -> None:
        """Fail every queue a client could be blocked on: in-flight
        admissions, decoding slots, the capacity-deferred head, and the
        pending queue. The whole point of supervision — nobody hangs
        forever on a dead worker."""
        for req, _adm, _ in self._inflight:
            self._fail_req(req, exc)
        self._inflight.clear()
        if self._deferred is not None:
            self._fail_req(self._deferred, exc)
            self._deferred = None
        for req in self._recover:
            self._fail_req(req, exc)
        self._recover = []
        for req in self._backlog:
            self._fail_req(req, exc)
        self._backlog = []
        for req in list(self.slots.values()):
            self._fail_req(req, exc)
        self.slots.clear()
        while True:
            try:
                req = self.pending.get_nowait()
            except queue.Empty:
                break
            self._fail_req(req, exc)

    def _watch(self) -> None:
        """Stall watchdog body: flag `stalled` when the worker has owed
        progress for longer than the deadline without a heartbeat. Recovers
        (clears the flag) if heartbeats resume — stall_count keeps the
        incident record either way."""
        poll = max(0.01, min(0.25, self.stall_deadline_s / 4.0))
        while not self._stop.is_set():
            time.sleep(poll)
            if self.crashed is not None:
                return  # crash supervision already owns the health verdict
            age = time.monotonic() - self._heartbeat
            if self._busy() and age > self.stall_deadline_s:
                if not self.stalled:
                    self.stalled = True
                    self.stall_count += 1
                    ins.WATCHDOG_STALLS.inc()
                    trace.TRACER.event("watchdog.stall", cat="supervision",
                                       track="scheduler", age_s=round(age, 3))
                    log.error(
                        "watchdog: scheduler worker silent for %.2fs with "
                        "work in flight (deadline %.2fs) — device chunk "
                        "presumed hung; /health reports live=false",
                        age, self.stall_deadline_s)
            elif self.stalled and age <= self.stall_deadline_s:
                self.stalled = False
                ins.WATCHDOG_RECOVERIES.inc()
                trace.TRACER.event("watchdog.recover", cat="supervision",
                                   track="scheduler")
                log.warning("watchdog: worker heartbeat resumed; clearing "
                            "stall flag (%d total stalls)", self.stall_count)

    def _run(self) -> None:
        """Supervised worker entry: any escape from the serving loop first
        attempts a warm restart under the --restart-max budget (decode state
        + page pool rebuilt against resident weights, surviving requests
        recovered, the loop re-entered); with no budget — or a restart that
        itself dies — it falls back to PR 1 semantics: every in-flight
        request fails fast (finish_reason='error', queues unblocked) and
        /health flips permanently unhealthy."""
        try:
            while True:
                try:
                    self._loop()
                    return
                except BaseException as e:  # noqa: BLE001 — supervision must be total
                    try:
                        if self._try_restart(e):
                            continue
                    except BaseException as e2:  # noqa: BLE001 — restart died too
                        log.exception("warm restart failed; giving up")
                        e = e2
                    self.crashed = e
                    log.exception("scheduler worker crashed; failing all "
                                  "in-flight requests and marking /health "
                                  "unhealthy")
                    self._fail_all(e)
                    return
        finally:
            # stop the ledger clock with the worker: the tail of the last
            # state is billed and wall_s() freezes, keeping the partition
            # invariant (sum of states == wall) true for a dead worker too
            self.ledger.close()

    #: one jitted fori_loop shared by every restart: replaying a 4000-token
    #: stream must cost ONE dispatch, not 4000 serial split() round-trips
    #: on the worker thread while every recovered request waits
    _advance_key_fn = staticmethod(jax.jit(lambda key, n: jax.lax.fori_loop(
        0, n, lambda _, k: jax.random.split(k)[0], key)))

    @classmethod
    def _advance_key(cls, key0, n: int) -> np.ndarray:
        """Replay the decode scan's per-token threefry advance: the
        device-side key after emitting n decode tokens is split(key)[0]
        applied n times to the last (re)commit-time key (BatchEngine.keys
        row). The live carry is lost with the crashed chunk, but its value
        is a pure function of the start key and the emitted-token count —
        which is what makes resumed sampled streams bit-exact."""
        key = jax.numpy.asarray(np.asarray(key0), jax.numpy.uint32)
        return np.asarray(cls._advance_key_fn(key, jax.numpy.int32(n)))

    def _try_restart(self, exc: BaseException) -> bool:
        """Warm restart after a worker crash. Returns False when the budget
        (--restart-max within --restart-window-s) is spent or restarts are
        disabled — the caller then applies the permanent-unhealthy path.

        Recovery semantics: queued + capacity-deferred requests survive
        untouched; mid-prefill admissions restart their prefill from
        scratch; mid-stream requests resume by re-prefilling prompt +
        already-emitted tokens with their recorded PRNG key and position
        (bit-exact continuation — clients see no duplicate or dropped
        tokens); requests whose state cannot be trusted fail individually
        with finish_reason='error'."""
        if self.restart_max <= 0 or self._stop.is_set():
            return False
        now = time.monotonic()
        self._restarts = [t for t in self._restarts
                          if now - t < self.restart_window_s]
        if len(self._restarts) >= self.restart_max:
            log.error("restart budget exhausted (%d within --restart-window-s"
                      " %.1fs); staying down", self.restart_max,
                      self.restart_window_s)
            return False
        self._restarts.append(now)
        # from here until _loop() re-anchors the ledger, every instant —
        # backoff sleep, recovery bookkeeping, engine rebuild — is restart
        # time, not whatever state the crash interrupted
        self.ledger.transition("restart_backoff")
        self.restart_count += 1
        attempt = len(self._restarts)
        ins.ENGINE_RESTARTS.inc()
        trace.TRACER.event("engine.restart", cat="supervision",
                           track="scheduler", attempt=attempt,
                           error=repr(exc))
        log.warning("scheduler worker crashed (%r); warm restart %d/%d "
                    "(window %.1fs)", exc, attempt, self.restart_max,
                    self.restart_window_s)
        faults.fire("engine.restart")  # drill: a restart that itself dies
        # exponential backoff, capped: repeated crashes inside one window
        # space their restarts out without ever sleeping unboundedly (the
        # budget, not the backoff, is what gives up)
        delay = min(self.restart_backoff_s * (2 ** min(attempt - 1, 10)),
                    self.restart_backoff_max_s)
        deadline = now + delay
        while time.monotonic() < deadline and not self._stop.is_set():
            # heartbeat-stamped backoff sleep: the watchdog must read
            # "restarting" as progress, not as a hung device chunk
            self._heartbeat = time.monotonic()
            time.sleep(min(0.05, max(0.0, deadline - time.monotonic())))
        # ---- collect the recovery set BEFORE touching the engine (the
        # host-side records are intact; only device state is suspect)
        recover: list[Request] = []
        for slot, req in sorted(self.slots.items(),
                                key=lambda kv: kv[1].submitted_at):
            ok = self._record_resume(req, slot)
            req.slot = -1
            if not ok:
                # bookkeeping drift between the emit records — resuming
                # could duplicate or drop tokens; fail this one request
                self._fail_req(req, RuntimeError(
                    "request not recoverable across engine restart "
                    "(emitted-token record disagrees with produced "
                    f"{req.produced})"))
                continue
            req.recovered = True
            recover.append(req)
        self.slots.clear()
        for req, _adm, _ in self._inflight:
            # mid-prefill: no tokens reached the client yet — re-prefill the
            # whole prompt (their partially-written rows died with the cache)
            req.slot = -1
            req.recovered = True
            recover.append(req)
        self._inflight.clear()
        self.slot_tokens.clear()
        # ---- rebuild decode state + page pool against resident weights
        self.engine.warm_restart()
        pool = getattr(self.engine, "pool", None)
        if pool is not None:
            pool.audit()  # a fresh pool failing audit means the rebuild is
            # broken — crash the restart (budget-accounted) rather than
            # serve from a corrupt allocator
        self._recover = recover + self._recover
        self._t_dec_end = None
        self._t_consumed = None
        self._heartbeat = time.monotonic()
        self._wake.set()
        log.warning("warm restart complete: %d request(s) recovered for "
                    "re-admission, %d queued untouched",
                    len(recover), self.pending.qsize())
        return True

    def _boundary_reason(self, inflight_chunk=None) -> str | None:
        """Why the next chunk must wait for a fully-consumed pipeline, as
        the FIRST clause that asks for it (an obs/perf.DRAIN_REASONS word),
        or None when a successor may be dispatched off the chunk in flight.
        Boundary work: admission (a prefill must not race the in-flight
        chunk's donated cache, and commit/release need settled host
        mirrors), a pending cancel, a slot with no row left to decode into
        (the context edge, or a page edge on a dry pool: NOT a slot that
        merely needs its next page, which it gets here under the chunk in
        flight), or an emptied batch. Speculative cycles pipeline like
        plain chunks (their data-dependent counts materialize at
        consumption; _dispatch_chunk drains the pipeline itself on a
        spec<->plain mode switch, the reason `mode_switch`). The
        overlapped loop then consumes its
        in-flight chunk WITHOUT dispatching a successor, counts the drain
        under the reason (dllama_pipeline_drains_total), and the next
        iteration runs the boundary work on settled state — admission
        pumps are serialized at chunk consumption points."""
        if self._stop.is_set():
            return "stop"
        if not self.slots:
            return "empty"
        if self._deferred is not None:
            return "backlog"  # the head, parked until pages free up
        if self._recover:
            return "recover"
        if self._backlog:
            return "backlog"
        if not self.pending.empty():
            return "arrival"
        if self._inflight:
            # hybrid admissions ride the pipelined chunks — no boundary
            # needed while the head is mid-prefill and healthy. Commit,
            # abort (cancel/deadline), and the TTFT-deadline override all
            # need settled state, so those drain the pipeline.
            if not self._hybrid_now():
                return "backlog"  # an admission the boundary's pump feeds
            req, adm, _ = self._inflight[0]
            now0 = time.monotonic()
            # the pipelined commit takes a pumped head after the chunk in
            # flight is consumed: no boundary for it
            if adm.off >= len(adm.toks) and not (
                    self._pipelined_commit and inflight_chunk is not None):
                return "commit"
            if req.cancelled.is_set():
                return "cancel"
            if ((req.deadline_at is not None and now0 >= req.deadline_at)
                    or (self.admit_ttft_deadline_ms is not None
                        and (now0 - req.submitted_at) * 1000.0
                        >= self.admit_ttft_deadline_ms)):
                return "deadline"
        now = time.monotonic()
        for r in self.slots.values():
            # a pending cancel OR an expired per-request deadline needs
            # boundary work: "running requests finish with
            # finish_reason='timeout' at the next chunk boundary"
            if r.cancelled.is_set():
                return "cancel"
            if r.deadline_at is not None and now >= r.deadline_at:
                return "deadline"
        # a slot with no row to decode into has boundary work: at the
        # context edge `_boundary_scans` finishes it with `length`, on a dry
        # page pool the starvation rescue may have to. A slot that merely
        # stands on the edge of its allocated pages has none: the engine
        # takes the page here, with the chunk in flight, exactly as the
        # successor's dispatch would have (`row_limited`'s top-up), and the
        # successor leaves on time
        limited = (self.engine.row_limited()
                   if hasattr(self.engine, "row_limited")
                   else np.asarray(self.engine.pos) >= self.engine.seq_len)
        if any(limited[s] for s in self.slots):
            return "row_limit"
        if inflight_chunk is not None:
            # budget finishes are host-predictable (unlike EOS): when EVERY
            # live request exhausts max_tokens within the chunk already in
            # flight, a successor would be pure discarded overrun — don't
            # burn a device chunk on it (a fixed-budget batch would pay one
            # wasted chunk per drain otherwise). For a spec chunk the real
            # counts are still on device, so use the OPTIMISTIC per-slot
            # bound (n cycles x K+1): skipping a successor that turns out
            # needed costs one boundary trip; dispatching a pure-overrun
            # chunk costs a whole wasted device launch.
            if inflight_chunk.spec:
                bound = inflight_chunk.n * (int(self.engine.spec_k) + 1)
                emptied = all(req.produced + bound >= req.max_tokens
                              for req in self.slots.values())
            else:
                emptied = all(
                    req.produced + int(inflight_chunk.advance[slot])
                    >= req.max_tokens
                    for slot, req in self.slots.items())
            if emptied:
                return "empty"  # the chunk in flight ends every request
        return None

    def _observe_host_gap(self, pipeline_empty: bool,
                          exclude_s: float = 0.0) -> float | None:
        """Inter-chunk host gap, stamped at every chunk dispatch: how long
        the device sat idle on SCHEDULING overhead between chunks. A
        dispatch into an EMPTY pipeline pays the wall time since the
        previous chunk's tokens materialized minus `exclude_s` (admission/
        boundary work — that stall is ADMISSION_STALL_SECONDS's story, and
        polluting this series with it would drown the per-chunk signal); a
        dispatch while a chunk is still in flight pays nothing — the device
        never went idle, which is the overlap win the A/B measures. The
        histogram is the one record; the seconds are handed back for the
        `decode.dispatch` span's argument (None before the first chunk)."""
        if self._t_consumed is None:
            return None
        gap_s = (max(0.0, time.monotonic() - self._t_consumed - exclude_s)
                 if pipeline_empty else 0.0)
        ins.DECODE_HOST_GAP_SECONDS.observe(gap_s)
        return gap_s

    def _dispatch_chunk(self, pipeline_empty: bool = True,
                        exclude_gap_s: float = 0.0, inflight=None):
        """Start the next device chunk — a plain fused decode chunk, or
        ONE speculative verify cycle when some live slot can accept drafts
        (per-request spec_k > 0, greedy, a K+1-row verify window). Spec
        cycles flow through the same decode_dispatch/decode_consume split
        as plain chunks (ISSUE 11), so the overlapped pipeline composes
        with speculation: cycle N+1's propose/verify launches off cycle
        N's device carry while the host emits N's tokens. Returns (chunk,
        slots snapshot); or None when `inflight` (the unconsumed
        predecessor) is of the OTHER mode — the host position mirror only
        settles when a spec cycle is consumed, so a spec<->plain switch
        drains the pipeline for one iteration instead of dispatching off
        unsettled state.

        A decode/spec failure here is NOT a per-request problem: the jitted
        step donates the KV cache, so an exception mid-chunk leaves the
        engine's buffers in an indeterminate state. It escalates to the
        supervision wrapper — every in-flight request (including ones whose
        tokens ride the unconsumed chunk) fails fast with
        finish_reason='error' and /health goes unhealthy (the process
        supervisor owns the restart)."""
        # hybrid step (ISSUE 12): while the head admission is mid-prefill
        # and decoders exist, every chunk is a FUSED hybrid dispatch that
        # carries up to `_budget_now` of its prompt tokens — no separate
        # prefill launch ever stalls the decode cadence. Hybrid chunks are
        # plain (non-spec) chunks; an in-flight spec chunk drains through
        # the same mode-switch bail as spec<->plain.
        hyb_adm = hyb_req = None
        if self._hybrid_now() and self._inflight:
            # the head, or under the pipelined commit the first admission
            # behind heads that are pumped and wait for their commit
            _req, _adm, _ = next(
                (e for e in self._inflight if e[1].off < len(e[1].toks)
                 or not self._pipelined_commit), self._inflight[0])
            if (_adm.off < len(_adm.toks) and not _req.cancelled.is_set()
                    and (_req.deadline_at is None
                         or time.monotonic() < _req.deadline_at)):
                hyb_adm, hyb_req = _adm, _req
        self.ledger.transition("hybrid" if hyb_adm is not None
                               else "decode_dispatch")
        with self.phases("dispatch.plan", self._next_seq()):
            plan = self._plan_chunk(hyb_adm, inflight)
            if plan is None:
                return None  # mode switch: consume the chunk in flight first
            use_spec, n_disp = plan
            gap_s = self._observe_host_gap(pipeline_empty, exclude_gap_s)
            if hyb_adm is not None and self._budget_ctl is not None:
                # SLO-driven budget: re-evaluated against the live ITL
                # window (rate-limited inside the controller)
                self._budget_now = self._budget_ctl.update(self.perf.itl)
        # the dispatch span: pure host work, the engine's dispatch.build /
        # .call / .after phases inside it. Under overlap it lands INSIDE the
        # previous chunk's decode.device span — the interleaving
        # scripts/trace_smoke.sh asserts on.
        tr = trace.TRACER
        sp = tr.span("decode.dispatch", cat="decode")
        # the flight recorder's prefill story stays complete under hybrid:
        # each fused slice is a prefill.chunk span for the ADMITTING request,
        # bracketing the dispatch
        sp_slice = (tr.span("prefill.chunk", cat="prefill",
                            req_id=hyb_req.req_id)
                    if hyb_adm is not None else trace.NULL_SPAN)
        if hyb_adm is None:
            chunk = self.engine.decode_dispatch(n_disp, spec=use_spec)
        else:
            try:
                chunk = self.engine.hybrid_dispatch(n_disp, hyb_adm,
                                                    self._budget_now)
            except faults.InjectedFault as e:
                if e.point != "engine.prefill":
                    raise  # decode-point drills keep the fatal contract
                # the per-request admission-failure contract survives
                # hybrid: the engine.prefill drill fires BEFORE
                # hybrid_dispatch mutates any state, so the engine is
                # clean — fail just the joiner and dispatch a plain chunk
                # for the batch. (A GENUINE failure inside the fused
                # launch is indistinguishable from a decode failure — the
                # jit donates the cache — and stays engine-fatal, handled
                # by warm restart.)
                self._inflight[:] = [e_ for e_ in self._inflight
                                     if e_[1] is not hyb_adm]
                self._abort_admission(hyb_req, hyb_adm, e)
                chunk = self.engine.decode_dispatch(n_disp, spec=False)
        self.phases.drain = None  # the pipeline holds a launch again
        if tr.enabled:
            sp.end(chunk=chunk.seq, n=chunk.n, occupancy=len(self.slots),
                   spec=use_spec, pipelined=not pipeline_empty,
                   hybrid_tokens=(chunk.hybrid_tokens or None),
                   host_gap_ms=(None if gap_s is None
                                else round(gap_s * 1000.0, 3)))
            if chunk.hybrid_tokens:
                sp_slice.end(slot=chunk.hybrid_slot, off=int(hyb_adm.off),
                             total=len(hyb_adm.toks), hybrid=True)
        return chunk, dict(self.slots)

    def _plan_chunk(self, hyb_adm, inflight):
        """The `dispatch.plan` phase's choice: (use_spec, steps) of the
        chunk to dispatch, or None when `inflight` is of the other mode."""
        use_spec = False
        alternating = False
        if getattr(self.engine, "spec_k", 0) and hyb_adm is None:
            # speculate while some live slot can actually accept drafts;
            # sampled, penalized, and spec_k=0 traffic rides the cycles one
            # token at a time (per-slot eligibility, resolved on device)
            draft = self.engine.spec_draft_k()
            elig = self.engine.spec_eligible()
            use_spec = any(draft[s] > 0 for s in self.slots)
            if use_spec and not all(elig[s] for s in self.slots):
                # gated alternation — the one case per-slot eligibility
                # cannot absorb: a live slot WITHOUT a K+1-row verify
                # window (context edge, exhausted page pool) freezes in
                # spec cycles, so plain decode chunks alternate in until
                # it finishes. Everything else rides the cycles.
                alternating = True
                use_spec = not self._spec_tick
        if inflight is not None and bool(inflight.spec) != use_spec:
            # mode switch: consume the in-flight chunk first. Crucially the
            # alternation toggle is NOT consumed here — an aborted
            # dispatch must not eat the plain-decode turn, or under
            # overlap every launched chunk would be spec and the frozen
            # slot would starve (the exact livelock alternation prevents)
            return None
        if alternating:
            self._spec_tick = use_spec  # turn consumed by a real dispatch
        n_disp = self.chunk
        if use_spec:
            # tail clamp: a chunk-sized spec launch can overshoot a
            # finishing request by up to chunk x (K+1) tokens of discarded
            # device work — when every live request fits inside ONE cycle's
            # ceiling, dispatch a single cycle instead (quantized to
            # {1, chunk} so the fused scan compiles exactly twice)
            k1 = int(self.engine.spec_k) + 1
            if all(req.max_tokens - req.produced <= k1
                   for req in self.slots.values()):
                n_disp = 1
        return use_spec, n_disp

    def _consume_chunk(self, chunk, snapshot) -> None:
        """Block on a dispatched chunk's tokens and emit them to the
        requests captured at dispatch time. A slot whose request finished
        while the chunk was in flight (EOS/budget found consuming the
        previous chunk, or a cancel) is skipped: those tokens are the
        one-chunk stop overrun — discarded, with release(keep_rows=) having
        rewound the slot to the truly-emitted prefix, so the prefix cache
        never serves overrun rows."""
        tr = trace.TRACER
        sp = tr.span("decode.consume", cat="decode")
        self.ledger.transition("decode_wait")
        # the engine's consume.wait / consume.fold phases; records
        # decode.device
        toks = self.engine.decode_consume(chunk)
        self._t_dec_end = self._t_consumed = time.monotonic()
        self.ledger.transition("emit")
        if tr.enabled:
            sp.end(chunk=chunk.seq, n=chunk.n)
        with self.phases("emit.scan", chunk.seq):
            bad = chunk.nonfinite()  # NaN guard: rows whose logits went
            # non-finite (or an armed decode.nan injection) — fail THOSE
            # requests, not the engine; their chunk tokens are garbage and are
            # never emitted, their rows are released unreusable
            for slot, req in snapshot.items():
                if self.slots.get(slot) is not req:
                    continue  # finished mid-flight: overrun tokens discarded
                if bad is not None and bad[slot]:
                    log.error("non-finite logits in decode chunk %d (slot %d); "
                              "failing the request, engine stays up",
                              chunk.seq, slot, extra=trace.log_extra(req.req_id))
                    self.slot_tokens[slot] = []  # rows are poisoned: never reuse
                    req.finish_reason = "error"  # before the put (client-visible)
                    req.out.put(RuntimeError(
                        f"non-finite logits in decode chunk {chunk.seq}; "
                        "request failed (engine healthy)"))
                    self._finish(req, "error")
                    continue
                if chunk.spec and chunk.advance[slot]:
                    # per-request acceptance record (timings()'s spec object):
                    # cycles this request participated in, and tokens they gave
                    req.spec_cycles += int((chunk.adv_cycles[:, slot] > 0).sum())
                    req.spec_tokens += int(chunk.advance[slot])
                if tr.enabled and chunk.advance[slot]:
                    # flight-recorder chunk entry BEFORE the tokens reach the
                    # client queue: a response never races its own record
                    tr.req_chunk(req.req_id, chunk.seq, int(chunk.advance[slot]))
                for i in range(int(chunk.advance[slot])):
                    # row written when sampling token i: start + i (+1 = prefix len)
                    if self._emit(req, toks[i, slot], int(chunk.start_pos[slot]) + i + 1):
                        break

    def _boundary_scans(self) -> None:
        """The boundary's scans over the decoding slots (the
        `boundary.scan` phase): cancels, deadlines, the context edge, and
        the rescue of a batch whose every slot is page-starved."""
        for slot, req in list(self.slots.items()):
            if req.cancelled.is_set():
                self._finish(req, req.cancel_reason,
                             keep_rows=int(self.engine.pos[slot]))
            elif (req.deadline_at is not None
                  and time.monotonic() >= req.deadline_at):
                # per-request deadline: the stream ends cleanly at this
                # chunk boundary with finish_reason="timeout"; the rows
                # already emitted keep their prefix-cache value
                trace.TRACER.event("request.timeout", cat="deadline",
                                   track="requests", req_id=req.req_id,
                                   where="decoding")
                self._finish(req, "timeout",
                             keep_rows=int(self.engine.pos[slot]))
            elif int(self.engine.pos[slot]) >= self.engine.seq_len:
                self._finish(req, "length")
        if self.slots and hasattr(self.engine, "page_starved"):
            # paged pool exhaustion mid-decode: a starved slot (no page
            # for its next row, pool dry) waits frozen while batch-mates
            # run — their releases re-feed it. But when EVERY live slot
            # is starved nothing will ever free a page: finish the most-
            # advanced one with 'length' (least budget wasted) so its
            # pages unfreeze the rest. Admission reserves (+1 decode
            # page) make this a last resort, not the steady state.
            # the rescue must run even while an admission is mid-prefill
            # (_inflight): admissions only ADD page consumers, so waiting
            # on one can never un-starve the batch — and dispatching a
            # chunk with every slot at its limit would raise and crash
            # the worker instead
            starved = self.engine.page_starved()
            if starved.any() and all(
                starved[s] for s in self.slots
                if self.engine.active[s]
            ):
                if self._reclaim_pages(len(self.slots)):
                    pass  # reclaimed idle caches; next dispatch tops up
                else:
                    victim = max(
                        (s for s in self.slots if starved[s]),
                        key=lambda s: int(self.engine.pos[s]))
                    log.warning(
                        "kv page pool exhausted with every active slot "
                        "starved; finishing slot %d "
                        "(finish_reason=length) to free its pages",
                        victim)
                    self._finish(self.slots[victim], "length")

    def _loop(self) -> None:
        # end of the previous decode chunk (stall metric); instance attribute
        # so reset_latency_stats can rewind it from the caller's thread
        self._t_dec_end = None
        # anchor the time ledger (re-entrant across warm restarts): from
        # here until close(), every instant is billed to exactly one state
        self.ledger.start("idle")
        pending = None  # overlap mode: the dispatched-but-unconsumed chunk
        while not self._stop.is_set():
            self._heartbeat = time.monotonic()
            # scrape-visible view of the loop's state (set, not callbacks:
            # a dead scheduler's last values are a tombstone, never a
            # dangling closure keeping the engine alive)
            ins.QUEUE_DEPTH.set(self._queue_depth())
            ins.BUSY_SLOTS.set(len(self.slots))
            faults.fire("scheduler.loop")
            if pending is not None:
                # a chunk is in flight: keep the device busy by dispatching
                # its successor off the device-side carry BEFORE consuming —
                # the emit/EOS Python work below then runs concurrently with
                # device compute — unless boundary work needs the settled,
                # fully-consumed state first.
                if self._hybrid_on and not pending[0].spec:
                    # early commit + early admission start (ISSUE 12): a
                    # fully-pumped admission activates its slot NOW
                    # (blocking only on its own logits), and a queued
                    # arrival enters _inflight so its FIRST hybrid slice
                    # rides the very next successor dispatch — neither
                    # pays a full pipeline drain. Preemption and the other
                    # release-side boundary work still wait for settled
                    # state.
                    if self._inflight:
                        if self._pipelined_commit:
                            self._sample_ready_inflight()
                        else:
                            self._commit_ready_inflight()
                    if self._backlog or not self.pending.empty():
                        self.ledger.transition("admission")
                        with self.phases("admit.start", self._next_seq()):
                            self._admit_starts(boundary=False)
                with self.phases("boundary.scan", self._next_seq()):
                    reason = self._boundary_reason(pending[0])
                nxt = None
                if reason is None:
                    nxt = self._dispatch_chunk(pipeline_empty=False,
                                               inflight=pending[0])
                    if nxt is None:
                        reason = "mode_switch"
                if nxt is None:
                    # the pipeline drains: this launch is consumed with no
                    # successor queued, and the device idles through emit,
                    # the boundary work and the next dispatch. Counted by
                    # the reason that asked, which the phases' annotations
                    # carry until a launch is dispatched again
                    ins.PIPELINE_DRAINS.labels(reason=reason).inc()
                    self.phases.drain = reason
                self._consume_chunk(*pending)
                if self._pipelined_commit and self._inflight:
                    # second half: the consumed chunk's logits are there and
                    # the sampling queued behind it has run; the successor
                    # (dispatched with the joiner inactive) is on the device
                    self._commit_ready_inflight(sampled_only=True)
                pending = nxt
                continue
            t_boundary = time.monotonic()
            self.ledger.transition("admission")
            with self.phases("admit.start", self._next_seq()):
                self._admit_starts()
            admitted = self._pump_admissions()
            # boundary scans below (cancels, deadlines, page starvation) are
            # admission-side work; this also bills the pump's open tail
            self.ledger.transition("admission")
            with self.phases("boundary.scan", self._next_seq()):
                self._boundary_scans()
            if not self.slots:
                self._t_dec_end = None
                if not self._inflight:
                    self.ledger.transition("idle")
                    self._wake.wait(timeout=self.admit_timeout)
                    self._wake.clear()
                continue
            if admitted and self._t_dec_end is not None:
                # decode-to-decode gap attributable to admission work
                gap_ms = (time.monotonic() - self._t_dec_end) * 1000.0
                with self._metrics_lock:
                    self._admit_gaps_ms.append(gap_ms)
                    del self._admit_gaps_ms[:-256]
                ins.ADMISSION_STALL_SECONDS.observe(gap_ms / 1000.0)
            chunk = self._dispatch_chunk(
                exclude_gap_s=time.monotonic() - t_boundary)
            if self.overlap:
                pending = chunk
            else:
                self._consume_chunk(*chunk)
        # shutdown with work still in flight (drain timeout, hard stop): the
        # cut-off requests must surface as FAILURES to their clients — a bare
        # _END would read as a clean, complete generation (HTTP 200 with
        # silently truncated content). One path for all three places a client
        # can be parked: mid-admission, decoding, still queued.
        def cut(req: Request) -> None:
            # reason BEFORE the put: the client reads finish_reason as soon
            # as the exception lands — it must never observe None
            req.finish_reason = "shutdown"
            req.out.put(SchedulerDraining(
                "server shut down before this request completed"))
            self._finish(req, "shutdown")  # metrics ring + _END + slot release

        for req, adm, _ in self._inflight:
            self.slot_tokens[adm.slot] = []  # rows are mid-overwrite
            cut(req)
        self._inflight.clear()
        for req in list(self.slots.values()):
            cut(req)
        if self._deferred is not None:
            cut(self._deferred)
            self._deferred = None
        for req in self._recover:
            cut(req)
        self._recover = []
        for req in self._backlog:
            cut(req)
        self._backlog = []
        while True:
            try:
                cut(self.pending.get_nowait())
            except queue.Empty:
                break
