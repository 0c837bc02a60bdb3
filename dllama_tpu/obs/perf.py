"""SLO & saturation observability core (ISSUE 7): sliding-window latency
quantiles, the scheduler time ledger, and goodput-vs-throughput.

Everything here is host-side aggregation over marks the serving stack
already produces (PR 2's metrics registry, PR 4's spans) — the layer the
ROADMAP's SLO-aware scheduling consumes. It holds what host clocks are
right for; a kernel's share of its roofline is read from a trace by
``benchmark/`` (its ``costs/`` and ``peaks.json``), never priced here:

* :class:`WindowQuantiles` — a dependency-free sliding-window quantile
  estimator in the streaming-sketch family the ISSUE cites (P²/t-digest,
  Dunning & Ertl): time is cut into ring slices, each slice holds a bounded
  uniform reservoir of raw samples, and a quantile query merges the live
  slices. Under the per-slice cap the answer is EXACT (the common case — a
  60 s window sees hundreds of requests, not millions); past the cap the
  reservoir keeps an unbiased sample, so tails degrade gracefully instead
  of the estimator growing without bound. Bounded memory, O(1) observe,
  O(window samples · log) query — queries run at scrape/debug time, not on
  the hot path.
* :class:`TimeLedger` — every second of the scheduler worker loop
  attributed to exactly ONE exclusive state (:data:`LEDGER_STATES`). The
  attribution is transition-based: ``transition(s)`` bills the wall time
  since the previous transition to the PREVIOUS state, so the per-state
  totals partition wall time by construction — their sum equals loop wall
  time to the clock's precision, which is the invariant
  tests/test_perf.py drives a real scheduler run through.
* :class:`PhaseClock` — the phases UNDER the states (ISSUE 40): the one
  seam that opens a span of the worker's host work (:data:`PHASES`), into
  three sinks: the always-on counters
  ``dllama_scheduler_phase_seconds_total{phase}`` / ``_total{phase}``, the
  ``dllama.phase.<name>`` annotation while a profiler capture runs, the
  ring span when the ring is on. Phases lie inside one state and never
  overlap, so a state's seconds minus its phases' is its self time.
* :class:`SloPolicy` / :class:`PerfAggregator` — configurable TTFT/ITL SLO
  targets (``--slo-ttft-ms`` / ``--slo-itl-ms``), burn counters
  (``dllama_slo_violations_total{kind}``), a windowed attainment gauge,
  and goodput-vs-throughput: goodput counts only tokens of requests that
  finished ``stop``/``length`` *within* their SLOs.

Stdlib-only like the rest of ``dllama_tpu/obs`` — scripts/checks.sh
imports this module without jax or a model.
"""

from __future__ import annotations

import math
import random
import time
from collections import deque
from dataclasses import dataclass

from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import trace
from dllama_tpu.utils import locks

#: the exclusive states of the scheduler worker loop — the label set of
#: dllama_scheduler_time_seconds_total{state} and the README ledger table
#: (scripts/checks.sh asserts the two stay identical). `hybrid` is the
#: dispatch of a fused chunked-prefill+decode step (ISSUE 12): host work
#: that launches BOTH a prefill slice and a decode chunk in one device
#: call — neither pure `prefill` nor pure `decode_dispatch`, so it gets
#: its own bucket instead of polluting either attribution.
LEDGER_STATES = ("idle", "admission", "prefill", "hybrid", "decode_dispatch",
                 "decode_wait", "emit", "commit", "restart_backoff")

#: the phases under the states (ISSUE 40): named stretches of the worker's
#: host work, each inside ONE state, never overlapping each other: the
#: label set of dllama_scheduler_phase_seconds_total{phase}, the
#: `dllama.phase.<name>` annotations and the ring spans of the same names
#: (obs/trace.SPAN_CATALOG holds one row each; README's phase table too)
PHASES = ("dispatch.plan", "dispatch.build", "dispatch.call",
          "dispatch.after", "consume.wait", "consume.fold", "emit.scan",
          "emit.finish", "commit.sample", "commit.activate", "admit.start",
          "admit.pump", "boundary.scan")

#: why the overlapped loop consumed a launch with no successor queued: the
#: label set of dllama_pipeline_drains_total{reason}, in the order
#: Scheduler._boundary_reason asks (mode_switch is _dispatch_chunk's bail)
DRAIN_REASONS = ("stop", "empty", "backlog", "recover", "arrival", "commit",
                 "cancel", "deadline", "row_limit", "mode_switch")

#: a launch's tokens were there when the host asked (the read took less
#: than this): the device had finished first
READY_WAIT_S = 0.0005

for _p in PHASES:  # the series exist from the first scrape on
    ins.SCHEDULER_PHASE_SECONDS.labels(phase=_p)
    ins.SCHEDULER_PHASES.labels(phase=_p)
for _r in DRAIN_REASONS:
    ins.PIPELINE_DRAINS.labels(reason=_r)
for _o in ("ready", "blocked"):
    ins.LAUNCH_WAITS.labels(outcome=_o)
for _s in LEDGER_STATES:
    ins.SCHEDULER_TIME.labels(state=_s)
ins.DECODE_HOST_GAP_SECONDS.labels()


# ------------------------------------------------------------------ windows


class WindowQuantiles:
    """Sliding-window streaming quantile estimator (see module docstring).

    ``window_s`` of history in ``slices`` ring buckets; each bucket keeps at
    most ``cap`` samples (uniform reservoir past that, unbiased). Quantiles
    use the linear-interpolation definition (``numpy.percentile`` default),
    so under the cap they match an exact sorted-list computation bit for
    bit — the contract tests/test_perf.py checks across adversarial
    streams. ``now_fn`` is injectable for deterministic window-expiry
    tests."""

    def __init__(self, window_s: float = 60.0, slices: int = 6,
                 cap: int = 512, now_fn=time.monotonic):
        if window_s <= 0 or slices <= 0 or cap <= 0:
            raise ValueError("window_s, slices and cap must be positive")
        self.window_s = float(window_s)
        self.slices = int(slices)
        self.cap = int(cap)
        self._slice_s = self.window_s / self.slices
        self._now = now_fn
        self._lock = locks.make_lock("obs.perf")
        # ring of (bucket_index, samples, seen); bucket = floor(now/slice_s)
        self._ring: list[tuple[int, list[float], int]] = []

    def _bucket(self) -> int:
        return int(self._now() / self._slice_s)

    def _live(self, bucket: int):
        """Slices still inside the window (caller holds the lock)."""
        oldest = bucket - self.slices + 1
        return [entry for entry in self._ring if entry[0] >= oldest]

    def observe(self, v: float) -> None:
        v = float(v)
        if v != v:  # NaN never enters the window
            return
        b = self._bucket()
        with self._lock:
            if not self._ring or self._ring[-1][0] != b:
                self._ring = self._live(b)
                self._ring.append((b, [], 0))
            bucket, samples, seen = self._ring[-1]
            if seen < self.cap:
                samples.append(v)
            else:
                # uniform reservoir: every sample of the slice keeps an
                # equal cap/seen chance of being retained
                j = random.randrange(seen + 1)
                if j < self.cap:
                    samples[j] = v
            self._ring[-1] = (bucket, samples, seen + 1)

    def count(self) -> int:
        """Observations currently inside the window (pre-reservoir count)."""
        with self._lock:
            return sum(seen for _, _, seen in self._live(self._bucket()))

    def _merged(self) -> list[float]:
        with self._lock:
            live = self._live(self._bucket())
            return sorted(x for _, samples, _ in live for x in samples)

    def quantile(self, q: float) -> float | None:
        """Windowed quantile, ``q`` in [0, 1]; None on an empty window."""
        xs = self._merged()
        if not xs:
            return None
        if len(xs) == 1:
            return xs[0]
        rank = min(max(q, 0.0), 1.0) * (len(xs) - 1)
        lo = int(math.floor(rank))
        hi = min(lo + 1, len(xs) - 1)
        frac = rank - lo
        return xs[lo] * (1.0 - frac) + xs[hi] * frac

    def snapshot(self) -> dict:
        """{'count', 'p50', 'p95', 'p99'} over one merged window read (a
        p-by-p loop over quantile() would re-sort the window each time)."""
        xs = self._merged()
        out: dict = {"count": self.count()}
        for name, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
            if not xs:
                out[name] = None
                continue
            rank = q * (len(xs) - 1)
            lo = int(math.floor(rank))
            hi = min(lo + 1, len(xs) - 1)
            frac = rank - lo
            out[name] = xs[lo] * (1.0 - frac) + xs[hi] * frac
        return out


class WindowSums:
    """Time-sliced sliding-window sums (the rate companion of
    :class:`WindowQuantiles`): ``add(tokens=3, finished=1)`` accumulates into
    the current slice, ``totals()`` merges live slices, ``span_s()`` is the
    window the totals cover (for rate = total / span)."""

    def __init__(self, window_s: float = 60.0, slices: int = 6,
                 now_fn=time.monotonic):
        self.window_s = float(window_s)
        self.slices = int(slices)
        self._slice_s = self.window_s / self.slices
        self._now = now_fn
        self._lock = locks.make_lock("obs.perf")
        self._ring: list[tuple[int, dict]] = []
        self._t0 = now_fn()  # windows younger than window_s rate over age

    def add(self, **fields: float) -> None:
        b = int(self._now() / self._slice_s)
        with self._lock:
            oldest = b - self.slices + 1
            self._ring = [e for e in self._ring if e[0] >= oldest]
            if not self._ring or self._ring[-1][0] != b:
                self._ring.append((b, {}))
            acc = self._ring[-1][1]
            for k, v in fields.items():
                acc[k] = acc.get(k, 0.0) + float(v)

    def totals(self) -> dict:
        b = int(self._now() / self._slice_s)
        with self._lock:
            oldest = b - self.slices + 1
            out: dict = {}
            for bucket, acc in self._ring:
                if bucket < oldest:
                    continue
                for k, v in acc.items():
                    out[k] = out.get(k, 0.0) + v
            return out

    def span_s(self) -> float:
        """Seconds the current totals cover: the full window once the
        process has lived that long, the process age before (rates must not
        read 6x too low during the first minute)."""
        return max(min(self.window_s, self._now() - self._t0), 1e-9)


# ------------------------------------------------------- clock alignment


class ClockOffset:
    """NTP-lite remote-clock offset estimator over request/response
    round-trips (ISSUE 17) — the router runs one per replica, fed by its
    health poller, to place each replica's monotonic clock on the router's
    timeline for the merged mesh trace.

    One :meth:`sample` per poll: ``t_send``/``t_recv`` are the local
    monotonic marks around the round-trip, ``t_remote`` the remote clock
    read the response carried. The classic single-exchange estimate assumes
    the remote read happened at the round-trip midpoint, so

        offset = t_remote - (t_send + t_recv) / 2

    with the true offset inside ``offset ± rtt/2`` (the read can be
    anywhere between send and receive). :meth:`estimate` returns the
    MIN-RTT sample of the sliding window — the exchange least polluted by
    queueing delay, whose error bound ``rtt/2`` is also the smallest.
    Single-writer (the replica's poller thread) / multi-reader; the deque
    append and snapshot are GIL-atomic, so no lock is needed."""

    __slots__ = ("_samples",)

    def __init__(self, window: int = 16):
        self._samples: deque = deque(maxlen=int(window))

    def sample(self, t_send: float, t_recv: float, t_remote: float) -> None:
        rtt = max(float(t_recv) - float(t_send), 0.0)
        offset = float(t_remote) - (float(t_send) + float(t_recv)) / 2.0
        self._samples.append((rtt, offset))

    def estimate(self) -> dict | None:
        """-> {offset_s, uncertainty_s, rtt_s, samples} from the min-RTT
        sample of the window, or None before the first sample."""
        samples = list(self._samples)
        if not samples:
            return None
        rtt, offset = min(samples)
        return {"offset_s": offset, "uncertainty_s": rtt / 2.0,
                "rtt_s": rtt, "samples": len(samples)}


# ------------------------------------------------------------- time ledger


class TimeLedger:
    """Exclusive-state time attribution for one worker loop.

    ``transition(state)`` bills the elapsed time since the last transition
    to the PREVIOUS state and makes ``state`` current — every instant
    between ``start()`` and ``close()`` lands in exactly one state, so the
    per-state totals sum to the loop's wall time by construction. Each
    billed span also increments the
    ``dllama_scheduler_time_seconds_total{state}`` counter (when a counter
    family is supplied), making the invariant scrape-visible.

    Thread-safety: the worker owns the state machine, but ``snapshot()``
    (and the scrape-path ``poke()``, which bills the open span without
    changing state) may run from API threads — all entry points take the
    lock, and billing stays correct because every moment is attributed to
    whatever state was current when it passed.

    While a jax.profiler capture runs (``obs/trace.PROFILER_HOOK`` set),
    every state is also one profiler annotation ``dllama.sched.<state>``,
    closed and opened at each transition on the worker's own thread: the
    states tile that thread's line of the capture's host plane, on the
    clock that stamps the device plane too."""

    def __init__(self, counter=None, now_fn=time.monotonic,
                 states=LEDGER_STATES):
        self.states = tuple(states)
        self._ann = None  # the open state's profiler annotation, if any
        self._counter = counter
        self._now = now_fn
        # _bill() increments the scheduler-time counter while holding this
        # (obs.perf ranks below the obs.metrics leaf, so that nesting is
        # rank-legal by construction)
        self._lock = locks.make_lock("obs.perf")
        self.totals = {s: 0.0 for s in self.states}
        self._state: str | None = None
        self._t: float | None = None
        self._t_start: float | None = None
        self._t_close: float | None = None

    def start(self, state: str = "idle") -> None:
        """Anchor the ledger at loop entry (re-entrant: a warm restart
        re-enters the loop without resetting the accumulated record)."""
        with self._lock:
            now = self._now()
            if self._t_start is None:
                self._t_start = now
            self._t_close = None
            self._bill(now)
            self._set(state, now)

    def _bill(self, now: float) -> None:
        if self._state is not None and self._t is not None:
            dt = max(now - self._t, 0.0)
            self.totals[self._state] += dt
            if self._counter is not None:
                self._counter.labels(state=self._state).inc(dt)
            self._t = now

    def _set(self, state: str | None, now: float) -> None:
        if state is not None and state not in self.totals:
            raise ValueError(f"unknown ledger state {state!r} "
                             f"(catalog: {self.states})")
        self._state = state
        self._t = now if state is not None else None
        self._stamp(state)

    def _stamp(self, state: str | None) -> None:
        """Close the open profiler annotation and, while a capture runs,
        open `state`'s (caller holds the lock)."""
        trace.end_annotation(self._ann)
        self._ann = (None if state is None else
                     trace.profiler_annotation("dllama.sched.", state))

    def restamp(self) -> None:
        """Close and reopen the current state's annotation, from any thread
        (the profiler accepts an annotation closed on another thread than
        it was opened on). The profiler drops an annotation that is open
        when it stops and never sees one opened before it started, so a
        capture calls this as it begins and just before it stops: the state
        it began in and the state still open at its end are then stamped
        (a commit can hold the worker in one state for a whole launch).
        The open span is billed too, so the counter deltas a capture is
        bracketed with hold the seconds inside it, not a state's whole
        open stretch."""
        with self._lock:
            self._bill(self._now())  # the counter is current at both ends
            self._stamp(self._state)

    def transition(self, state: str) -> None:
        with self._lock:
            now = self._now()
            self._bill(now)
            self._set(state, now)

    def state(self) -> str | None:
        """The current exclusive state (None before start()/after close())
        — cross-thread readers (the scheduler's drain/watchdog idleness
        check) join this with container occupancy, closing the false-idle
        window while the worker holds a request BETWEEN containers (popped
        from in-flight, slot not yet assigned)."""
        with self._lock:
            return self._state

    def poke(self) -> None:
        """Bill the open span without changing state (scrape freshness: a
        long idle park should not read as zero until the next transition)."""
        with self._lock:
            self._bill(self._now())

    def close(self) -> None:
        """Bill the tail and stop the clock (loop exit / worker death)."""
        with self._lock:
            now = self._now()
            self._bill(now)
            self._set(None, now)
            if self._t_close is None:
                self._t_close = now

    def wall_s(self) -> float:
        """start() -> now (or close()): the quantity the state totals must
        sum to."""
        with self._lock:
            if self._t_start is None:
                return 0.0
            end = self._t_close if self._t_close is not None else self._now()
            return end - self._t_start

    def snapshot(self) -> dict:
        """Per-state seconds (open span included), fractions of wall time,
        and the current state — the `/debug/perf` ledger view."""
        with self._lock:
            now = self._now()
            totals = dict(self.totals)
            if self._state is not None and self._t is not None:
                totals[self._state] += max(now - self._t, 0.0)
            if self._t_start is None:
                wall = 0.0
            else:
                end = self._t_close if self._t_close is not None else now
                wall = end - self._t_start
        covered = sum(totals.values())
        return {
            "state": self._state,
            "wall_s": round(wall, 6),
            "covered_s": round(covered, 6),
            "seconds": {s: round(v, 6) for s, v in totals.items()},
            "fractions": {s: round(v / wall, 6) if wall > 0 else 0.0
                          for s, v in totals.items()},
        }


# ------------------------------------------------------------------ phases


class PhaseClock:
    """The ONE seam that opens a span of the worker's host work (ISSUE 40).

    ``with phases("emit.scan", seq):`` names a stretch of the scheduler
    worker's (or a direct engine caller's) host work, a :data:`PHASES`
    word, done for the launch `seq`. One way to open it, three sinks:

    * always: its seconds go to
      ``dllama_scheduler_phase_seconds_total{phase}`` and one to
      ``dllama_scheduler_phase_total{phase}`` (two clock reads, a lock, two
      counter adds; nothing is built);
    * while a jax.profiler capture runs it is one ``dllama.phase.<name>``
      annotation on the profiler's clock, under the open
      ``dllama.sched.<state>``, carrying `seq` and, while the pipeline is
      drained, the reason (:attr:`drain`). A phase given a launch record
      (`launch`: the jit call, ``dispatch.call``) is that record's
      ``dllama.launch.<kind>`` annotation instead, over the same stretch;
    * when the tracer ring is on it is the ring span of the phase's name
      on the ``scheduler`` track (arg ``chunk`` = seq).

    Phases never overlap: one that opens inside another SUSPENDS it (its
    time so far is billed, its annotation and ring span closed) and the
    outer one resumes when the inner closes, so ``emit.scan`` is the emit
    loop without the finishes that ``emit.finish`` times inside it, and a
    state's seconds minus its phases' is the state's self time.
    :attr:`by_state` keeps the seconds by (ledger state at billing, phase).

    One object serves every call (``phases(...)`` returns itself), owned by
    the thread that drives the engine; :meth:`restamp` and
    :meth:`snapshot` may come from other threads, hence the lock."""

    def __init__(self, ledger: "TimeLedger | None" = None,
                 now_fn=time.monotonic):
        self.ledger = ledger  # the scheduler's, once one drives the engine
        self.drain: str | None = None  # the reason the pipeline is drained
        self.last_s = 0.0  # seconds of the phase closed last (its last run)
        self.by_state: dict = {}
        self._now = now_fn
        self._lock = locks.make_lock("obs.perf")
        self._series = {p: (ins.SCHEDULER_PHASE_SECONDS.labels(phase=p),
                            ins.SCHEDULER_PHASES.labels(phase=p))
                        for p in PHASES}
        # the open phases, innermost last (parallel lists: nothing is
        # allocated to open one)
        self._names: list = []
        self._seqs: list = []
        self._launches: list = []
        self._t = 0.0  # when the innermost phase started or resumed
        self._ann = None  # its profiler annotation, if a capture runs
        self._args = self._annotation_args  # bound once: no per-call object

    def current(self) -> str | None:
        """The phase the owner is in now (None: a state's self time)."""
        with self._lock:
            return self._names[-1] if self._names else None

    def __call__(self, name: str, seq: int = 0, launch=None) -> "PhaseClock":
        series = self._series.get(name)
        if series is None:
            raise ValueError(f"unknown phase {name!r} (catalog: {PHASES})")
        state = self.ledger.state() if self.ledger is not None else None
        with self._lock:
            now = self._now()
            if self._names:
                self._bill(now, state)  # the outer phase pauses
            self._names.append(name)
            self._seqs.append(seq)
            self._launches.append(launch)
            series[1].inc()
            self._start(now)
        return self

    def __enter__(self) -> "PhaseClock":
        return self

    def __exit__(self, *exc) -> bool:
        state = self.ledger.state() if self.ledger is not None else None
        with self._lock:
            now = self._now()
            self.last_s = self._bill(now, state)
            self._names.pop()
            self._seqs.pop()
            self._launches.pop()
            if self._names:
                self._start(now)  # the outer phase resumes
        return False

    def _annotation_args(self) -> dict:
        a = {"seq": self._seqs[-1]}
        if self.drain is not None:
            a["drain"] = self.drain
        return a

    def _start(self, now: float) -> None:
        """(Re)open the innermost phase's clock and annotation (caller
        holds the lock)."""
        self._t = now
        launch = self._launches[-1]
        self._ann = (launch.annotation() if launch is not None else
                     trace.profiler_annotation("dllama.phase.",
                                               self._names[-1], self._args))

    def _bill(self, now: float, state) -> float:
        """Close the innermost phase's clock, annotation and ring span
        (caller holds the lock); the seconds billed."""
        trace.end_annotation(self._ann)
        self._ann = None
        name = self._names[-1]
        dt = max(now - self._t, 0.0)
        self._series[name][0].inc(dt)
        key = (state, name)
        self.by_state[key] = self.by_state.get(key, 0.0) + dt
        tr = trace.TRACER
        if tr.enabled:
            tr.span_at(name, self._t, now, cat="phase", track="scheduler",
                       chunk=self._seqs[-1])
        return dt

    def restamp(self) -> None:
        """Pause and resume the open phase, from any thread: its seconds so
        far are billed and its annotation is closed and reopened. A capture
        calls this as it begins and just before it stops, as it does
        :meth:`TimeLedger.restamp`, and for the same reasons."""
        state = self.ledger.state() if self.ledger is not None else None
        with self._lock:
            if self._names:
                now = self._now()
                self._bill(now, state)
                self._start(now)

    def snapshot(self) -> dict:
        """{state: {phase: seconds}} as billed so far (`/debug/perf`)."""
        with self._lock:
            items = list(self.by_state.items())
        out: dict = {}
        for (state, name), v in items:
            out.setdefault(state or "none", {})[name] = round(v, 6)
        return out


# -------------------------------------------------------------- SLO policy


@dataclass(frozen=True)
class SloPolicy:
    """Per-request latency targets (``--slo-ttft-ms`` / ``--slo-itl-ms``);
    None disables that kind. Verdicts are tri-state per kind: True (met),
    False (violated), None (no target, or the mark never happened — an
    errored request with no first token is unknowable, not a TTFT burn)."""

    ttft_ms: float | None = None
    itl_ms: float | None = None

    def enabled(self) -> bool:
        return self.ttft_ms is not None or self.itl_ms is not None

    @staticmethod
    def _judge(measured, target):
        if target is None or measured is None:
            return None, None
        over = float(measured) - float(target)
        return over <= 0.0, (round(over, 3) if over > 0 else None)

    def verdict(self, ttft_ms: float | None, itl_ms: float | None) -> dict:
        """{'ttft_ok', 'itl_ok', 'violated_by_ms': {...}, 'ok'} — `ok` is
        False iff some kind is measurably violated."""
        ttft_ok, ttft_over = self._judge(ttft_ms, self.ttft_ms)
        itl_ok, itl_over = self._judge(itl_ms, self.itl_ms)
        return {
            "ttft_ok": ttft_ok,
            "itl_ok": itl_ok,
            "violated_by_ms": {"ttft": ttft_over, "itl": itl_over},
            "ok": ttft_ok is not False and itl_ok is not False,
        }

    def verdict_from_marks(self, ttft_ms, e2e_ms, decode_tokens) -> dict:
        """Verdict from a flight-recorder record's marks (the `/debug/
        requests/{req_id}` postmortem): ITL is derived the same way
        Request.itl_ms derives it — (e2e - ttft) / (tokens - 1)."""
        itl = None
        if (ttft_ms is not None and e2e_ms is not None
                and decode_tokens is not None and decode_tokens >= 2):
            itl = (float(e2e_ms) - float(ttft_ms)) / (decode_tokens - 1)
        out = self.verdict(ttft_ms, itl)
        out["targets"] = {"ttft_ms": self.ttft_ms, "itl_ms": self.itl_ms}
        if itl is not None:
            out["itl_ms"] = round(itl, 3)
        return out


class PrefillBudgetController:
    """SLO-driven per-chunk prefill token budget (ISSUE 12): the online
    controller behind ``--prefill-budget auto``. Each hybrid step fuses up
    to ``current`` prompt tokens of an admitting request into the decode
    chunk's device launch; this controller shrinks/grows that budget from
    the windowed ITL headroom against ``SloPolicy.itl_ms``:

    * p95 ITL over the target (headroom < 0) → HALVE the budget (down to
      ``lo``): running streams are already missing their SLO, so admissions
      must slow down, not the decoders.
    * p95 ITL under ``grow_frac`` of the target (ample headroom) → DOUBLE
      the budget (up to ``hi``): decoders are comfortably inside SLO, so
      spend the slack on joiner TTFT.
    * in between → hold.

    With no ITL target (or an empty window) the controller holds ``start``
    — auto then behaves as a fixed budget, which is what a server with no
    SLO configured should do. Budgets move in powers of two so the fused
    hybrid step's prefill-slice shapes stay in the same small compile set
    as chunked admission always had (engine.pow2_chunk). Updates are
    rate-limited to ``interval_s`` so the quantile merge never rides the
    per-chunk hot path. The current budget is published as the
    ``dllama_prefill_budget_tokens`` gauge."""

    def __init__(self, slo: SloPolicy | None, *, lo: int = 16,
                 hi: int = 256, start: int = 64, grow_frac: float = 0.6,
                 interval_s: float = 0.25, now_fn=time.monotonic):
        self.slo = slo or SloPolicy()
        self.lo = max(1, int(lo))
        self.hi = max(self.lo, int(hi))
        self.current = min(max(int(start), self.lo), self.hi)
        self.grow_frac = float(grow_frac)
        self.interval_s = float(interval_s)
        self._now = now_fn
        self._t_last = None
        ins.PREFILL_BUDGET.set(self.current)

    def update(self, itl_window: "WindowQuantiles") -> int:
        """Re-evaluate against the window's p95 ITL (seconds); returns the
        (possibly unchanged) budget. Cheap no-op inside the rate limit."""
        now = self._now()
        if self._t_last is not None and now - self._t_last < self.interval_s:
            return self.current
        self._t_last = now
        target = self.slo.itl_ms
        if target is None:
            return self.current
        p95 = itl_window.quantile(0.95)
        if p95 is None:
            return self.current
        p95_ms = p95 * 1000.0
        if p95_ms > target:
            nxt = max(self.lo, self.current // 2)
        elif p95_ms < target * self.grow_frac:
            nxt = min(self.hi, self.current * 2)
        else:
            nxt = self.current
        if nxt != self.current:
            self.current = nxt
            ins.PREFILL_BUDGET.set(nxt)
        return self.current


# ------------------------------------------------------------- aggregator


class PerfAggregator:
    """The per-scheduler join of the latency windows, the SLO accounting
    and goodput-vs-throughput, all fed by request finishes.
    Gauges live in the process registry (last scheduler wins, like every
    other serving gauge); ``refresh_gauges()`` runs at scrape time so the
    windowed values are current without putting quantile merges on the
    serving hot path."""

    def __init__(self, slo: SloPolicy | None = None,
                 window_s: float = 60.0, slices: int = 6,
                 now_fn=time.monotonic):
        self.slo = slo or SloPolicy()
        mk = lambda: WindowQuantiles(window_s, slices, now_fn=now_fn)
        self.ttft = mk()   # seconds
        self.itl = mk()    # seconds
        self.e2e = mk()    # seconds
        # request-flow window: finished counts + token sums (goodput and
        # throughput share this basis — both rate over FINISHED requests,
        # so goodput/throughput is a like-for-like fraction)
        self.flow = WindowSums(window_s, slices, now_fn=now_fn)

    # ------------------------------------------------------------ feeding

    def observe_finish(self, *, finish_reason: str, ttft_ms, itl_ms, e2e_ms,
                       tokens: int) -> None:
        """One terminal request: feed the latency windows, judge the SLOs
        (burn counters per violated kind), and account goodput — tokens
        count toward goodput only when the request finished successfully
        (stop/length) AND met every configured SLO."""
        if ttft_ms is not None:
            self.ttft.observe(ttft_ms / 1000.0)
        if itl_ms is not None:
            self.itl.observe(itl_ms / 1000.0)
        if e2e_ms is not None:
            self.e2e.observe(e2e_ms / 1000.0)
        v = self.slo.verdict(ttft_ms, itl_ms)
        if v["ttft_ok"] is False:
            ins.SLO_VIOLATIONS.labels(kind="ttft").inc()
        if v["itl_ok"] is False:
            ins.SLO_VIOLATIONS.labels(kind="itl").inc()
        good = finish_reason in ("stop", "length") and v["ok"]
        self.flow.add(finished=1, ok=1 if v["ok"] else 0,
                      tokens=tokens, good_tokens=tokens if good else 0)

    # ------------------------------------------------------------- reading

    def window_snapshot(self) -> dict:
        """p50/p95/p99 (ms) + counts for ttft/itl/e2e over the window."""
        out = {}
        for name, w in (("ttft", self.ttft), ("itl", self.itl),
                        ("e2e", self.e2e)):
            s = w.snapshot()
            out[name] = {
                "count": s["count"],
                **{p: (None if s[p] is None else round(s[p] * 1000.0, 3))
                   for p in ("p50", "p95", "p99")},
            }
        return out

    def slo_snapshot(self) -> dict:
        f = self.flow.totals()
        finished = f.get("finished", 0.0)
        att = (f.get("ok", 0.0) / finished) if finished else None
        return {
            "targets": {"ttft_ms": self.slo.ttft_ms,
                        "itl_ms": self.slo.itl_ms},
            "enabled": self.slo.enabled(),
            "window_finished": int(finished),
            "attainment": None if att is None else round(att, 4),
            "violations_total": {
                "ttft": ins.SLO_VIOLATIONS.labels(kind="ttft").value(),
                "itl": ins.SLO_VIOLATIONS.labels(kind="itl").value(),
            },
        }

    def roofline_snapshot(self) -> dict:
        """Windowed token rates over finished requests. The name is the
        `/debug/perf` key the router's fleet view federates."""
        f = self.flow.totals()
        span = self.flow.span_s()
        return {
            "throughput_tok_s": round(f.get("tokens", 0.0) / span, 3),
            "goodput_tok_s": round(f.get("good_tokens", 0.0) / span, 3),
        }

    def refresh_gauges(self) -> None:
        """Push the windowed views into the registry gauges — called at
        scrape time (`/metrics`, `/debug/perf`) rather than per request.
        A drained window sets NaN (the Prometheus "no data" value, rendered
        as the grammar's NaN token) — never the last stale value: an idle
        server must not scrape as still carrying its old p95."""
        nan = float("nan")
        for name, w in (("ttft", self.ttft), ("itl", self.itl),
                        ("e2e", self.e2e)):
            s = w.snapshot()
            for p in ("p50", "p95", "p99"):
                ins.LATENCY_WINDOW.labels(metric=name, quantile=p).set(
                    nan if s[p] is None else s[p])
        slo = self.slo_snapshot()
        att = slo["attainment"]
        ins.SLO_ATTAINMENT.set(nan if att is None else att)
        roof = self.roofline_snapshot()
        ins.THROUGHPUT.set(roof["throughput_tok_s"])
        ins.GOODPUT.set(roof["goodput_tok_s"])

    def snapshot(self, ledger: TimeLedger | None = None,
                 phases: PhaseClock | None = None) -> dict:
        """The `/debug/perf` join: windowed quantiles, SLO accounting,
        ledger attribution (with the phases' seconds under each state),
        goodput/throughput — one JSON document."""
        out = {
            "window": self.window_snapshot(),
            "slo": self.slo_snapshot(),
            "roofline": self.roofline_snapshot(),
        }
        if ledger is not None:
            out["ledger"] = ledger.snapshot()
            if phases is not None:
                out["ledger"]["phases"] = phases.snapshot()
        return out
