"""Profiling / tracing / observability.

The reference's instrumentation (SURVEY.md §5.1/§5.5): DEBUG_BENCHMARK
per-step μs prints (nn-executor.cpp:100-124), per-token console lines with
elapsed ms + net bytes (dllama.cpp:54-87), network byte counters
(nn-network.cpp:483-492) and the memory report (nn-core.cpp:152-166). TPU
equivalents here:

* :func:`trace` — jax.profiler device traces (view in XProf/TensorBoard); the
  idiomatic replacement for hand-timed executor steps.
* :func:`start_profile` — the on-demand, duration-capped capture behind
  ``POST /debug/profile``: same jax.profiler session as :func:`trace`
  (one lock guards both, so a CLI ``--trace`` run and an HTTP capture can
  never double-start the profiler), stopped by a timer thread.
* :class:`TokenTimer` — host-side per-token latency recorder with the
  reference's report shape (avg/p50/p90 ms/token, tok/s).
* :func:`collective_bytes_per_token` — analytic per-token inter-chip payload
  for a given mesh, the ICI analog of the reference's sentBytes/recvBytes
  (its Fig. 6 "sync payload per token" table is the contract this reproduces).
* :func:`memory_report` — params/cache HBM accounting.
"""

from __future__ import annotations

import contextlib
import logging
import tempfile
import threading
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import trace as reqtrace
from dllama_tpu.utils import locks

log = logging.getLogger(__name__)


class ProfileBusy(RuntimeError):
    """A jax.profiler capture is already running (there is exactly one
    profiler session per process); the API tier maps this to HTTP 409."""


#: the one-session profiler lock + state shared by trace() (CLI --trace)
#: and start_profile() (POST /debug/profile)
_prof_lock = locks.make_lock("utils.profiling")
_prof_state = {"active": False, "dir": None, "started_at": 0.0,
               "duration_s": None}

#: the counters a capture is bracketed with, by the key /debug/perf's
#: `capture` block uses: the launch counters (obs/instruments, fed by
#: engine/launch_record) and, since ISSUE 40, the host's seconds by ledger
#: state and by phase, the drains by reason, the waits by outcome and the
#: host-gap histogram's sum and count: beside the whole window's (a scrape
#: before and after) they say what the profiler costs the host, per launch
_CAPTURE_COUNTERS = {"launches": ins.LAUNCHES,
                     "sampler_launches": ins.SAMPLER_LAUNCHES,
                     "slot_steps": ins.SLOT_STEPS,
                     "kv_rows": ins.LAUNCH_KV_ROWS,
                     "kv_rows_moved": ins.LAUNCH_KV_ROWS_MOVED,
                     "prefill_rows": ins.LAUNCH_PREFILL_ROWS,
                     "kv_rows_read": ins.LAUNCH_KV_ROWS_READ,
                     "moe_assignments": ins.MOE_ASSIGNMENTS,
                     "moe_experts_touched": ins.MOE_EXPERTS_TOUCHED,
                     "moe_layer_steps": ins.MOE_LAYER_STEPS,
                     "moe_group_rows_max": ins.MOE_GROUP_ROWS_MAX,
                     "window_pages_released": ins.KV_WINDOW_PAGES_RELEASED,
                     "page_topups": ins.KV_PAGE_TOPUPS,
                     "sched_seconds": ins.SCHEDULER_TIME,
                     "phase_seconds": ins.SCHEDULER_PHASE_SECONDS,
                     "phases": ins.SCHEDULER_PHASES,
                     "drains": ins.PIPELINE_DRAINS,
                     "launch_waits": ins.LAUNCH_WAITS,
                     "host_gap": ins.DECODE_HOST_GAP_SECONDS}
_capture = {"begin": None, "t_begin": 0.0, "last": None}


def _launch_counters() -> dict:
    return {k: fam.series() for k, fam in _CAPTURE_COUNTERS.items()}


def last_capture() -> dict | None:
    """What the engine launched and what the host spent during the last
    FINISHED capture, as counter deltas: {"launches": {kind: n},
    "slot_steps": {state: n}, "kv_rows": {kind: n}, "kv_rows_moved":
    {kind: n}, "prefill_rows":
    {kind: n}, ..., "sched_seconds": {state: s}, "phase_seconds":
    {phase: s}, "phases": {phase: n}, "drains": {reason: n},
    "launch_waits": {outcome: n}, "host_gap": {"sum", "count"},
    "seconds": s}: the exact rows of the launches a reader of that trace
    is looking at, and the host's seconds inside it (`/debug/perf`'s
    `capture` block). None before the first capture."""
    with _prof_lock:
        return _capture["last"]

#: hard cap on an on-demand capture: profiles are heavy (host callbacks +
#: trace buffers); a forgotten long capture must not degrade serving forever
MAX_PROFILE_SECONDS = 60.0


def _profiler_begin(log_dir: str, duration_s: float | None = None,
                    restamp=None) -> None:
    with _prof_lock:
        if _prof_state["active"]:
            raise ProfileBusy(
                f"a profiler capture is already running "
                f"(dir={_prof_state['dir']!r}, started "
                f"{time.time() - _prof_state['started_at']:.1f}s ago)")
        # the profiler's clock is shared from here to _profiler_end, and
        # only then: launches and scheduler states become annotations on
        # the capture's host plane (obs/trace.PROFILER_HOOK). Installed
        # BEFORE the session starts, so the first state the session can see
        # is stamped; an annotation opened while it spins up is a no-op.
        reqtrace.PROFILER_HOOK = jax.profiler.TraceAnnotation
        # no Python frames: the program's captures read the device plane
        # and the dllama.* annotations (TraceMe events, the host tracer's),
        # and jax's default hooks EVERY Python call of every thread for the
        # length of the capture, the scheduler's worker among them
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        try:
            jax.profiler.start_trace(log_dir, profiler_options=options)
        except BaseException:
            reqtrace.PROFILER_HOOK = None
            raise
        _prof_state.update(active=True, dir=log_dir, started_at=time.time(),
                           duration_s=duration_s)
    if restamp is not None:
        # outside the lock (the ledger's ranks below it): the open state
        # and phase are billed and stamped BEFORE the counters are read, so
        # the block holds the host's seconds inside the capture
        restamp()
    with _prof_lock:
        _capture.update(begin=_launch_counters(), t_begin=time.monotonic())
    reqtrace.TRACER.event("profile.start", cat="profile", track="profiler",
                          dir=log_dir)


def _profiler_end() -> None:
    with _prof_lock:
        if not _prof_state["active"] or _prof_state.get("stopping"):
            return
        _prof_state["stopping"] = True
        end, before = _launch_counters(), _capture["begin"]
        _capture["last"] = {
            **{k: {label: v - before[k].get(label, 0.0)
                   for label, v in end[k].items()} for k in end},
            "seconds": time.monotonic() - _capture["t_begin"]}
    # writing the capture out takes as long as the capture is large (a
    # 40-layer step of 6,600 device ops: over a minute for 2 s): OUTSIDE the
    # lock, so that `last_capture` (GET /debug/perf) reads the block above at
    # once; the session stays `active` until the file is written, so a second
    # capture is still refused meanwhile
    t0 = time.monotonic()
    try:
        jax.profiler.stop_trace()
    finally:
        with _prof_lock:
            reqtrace.PROFILER_HOOK = None
            _prof_state.update(active=False, duration_s=None, stopping=False)
    log.info("device profile capture written in %.1f s", time.monotonic() - t0)
    reqtrace.TRACER.event("profile.stop", cat="profile", track="profiler")


def profile_status() -> dict:
    """Snapshot of the profiler session (no secrets: dir + timing only)."""
    with _prof_lock:
        return {"active": _prof_state["active"], "dir": _prof_state["dir"],
                "duration_s": _prof_state["duration_s"]}


def start_profile(log_dir: str | None = None, duration_s: float = 2.0,
                  restamp=None) -> dict:
    """Start an on-demand jax.profiler capture and schedule its stop after
    `duration_s` (clamped to [0.05, MAX_PROFILE_SECONDS]) on a timer thread.
    Returns {dir, duration_s}; raises :class:`ProfileBusy` when a capture
    (this one or a CLI ``--trace`` run) is already in flight — the caller
    never blocks behind someone else's capture. `restamp` (the scheduler's
    ``Scheduler.restamp``: its ledger's and its phase clock's) is called
    once the capture has begun and again just before it stops, so the state
    and the phase open at its two ends are stamped, and billed."""
    duration_s = min(max(float(duration_s), 0.05), MAX_PROFILE_SECONDS)
    if not log_dir:
        log_dir = tempfile.mkdtemp(prefix="dllama_profile_")
    _profiler_begin(str(log_dir), duration_s, restamp)

    def stop():
        try:
            if restamp is not None:
                restamp()
        finally:
            _profiler_end()

    t = threading.Timer(duration_s, stop)
    t.daemon = True  # a dying process must not hang on the stop timer
    t.start()
    return {"dir": str(log_dir), "duration_s": duration_s}


@contextlib.contextmanager
def trace(log_dir: str | None):
    """jax.profiler trace over a with-block; no-op when log_dir is falsy.
    Shares the process profiler session with :func:`start_profile`, so it
    raises :class:`ProfileBusy` instead of corrupting a running capture.
    Takes no `restamp`: its one caller (``inference --trace``) runs the
    batch-1 engine, which has no scheduler ledger to tile the host plane;
    only ``POST /debug/profile`` captures carry the states."""
    if not log_dir:
        yield
        return
    _profiler_begin(str(log_dir))
    try:
        yield
    finally:
        _profiler_end()


@dataclass
class TokenTimer:
    """Per-token wall-clock recorder (dllama.cpp:82-104 report shape).

    Every stop() also observes the sample into the metrics registry
    (dllama_token_latency_seconds), so the console report and a /metrics
    scrape read the same record — one source of truth."""

    ms: list[float] = field(default_factory=list)
    _t0: float = 0.0

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self) -> float:
        dt = (time.perf_counter() - self._t0) * 1000.0
        self.ms.append(dt)
        ins.TOKEN_LATENCY_SECONDS.observe(dt / 1000.0)
        return dt

    @contextlib.contextmanager
    def token(self):
        self.start()
        yield
        self.stop()

    def summary(self) -> str:
        if not self.ms:
            return "no tokens timed"
        a = np.asarray(self.ms)
        # throughput over TOTAL time, not 1000/mean: the reciprocal-of-mean
        # form overweights fast tokens (harmonic vs arithmetic) and lies
        # whenever latency varies; guard the degenerate all-zero-clock case
        total_s = float(a.sum()) / 1000.0
        tok_s = len(a) / total_s if total_s > 0 else 0.0
        return (
            f"{len(a)} tokens: avg {a.mean():.2f} ms/token "
            f"(p50 {np.percentile(a, 50):.2f}, p90 {np.percentile(a, 90):.2f}, "
            f"max {a.max():.2f}), {tok_s:.1f} tok/s"
        )


def collective_bytes_per_token(cfg, tp: int = 1, sp: int = 1, exchange_bytes: float = 2.0) -> dict:
    """Analytic inter-chip payload per decoded token, per chip.

    Mirrors the reference's measured sync payload (report.pdf Fig. 6; its Q80
    wire format is exchange_bytes≈1.06 per element — 34 bytes per 32 values;
    bf16 collectives are 2.0). Tensor-parallel Llama moves, per layer:

      attention out: all-gather of the wo partial sums — dim elements, each
      chip sends its 1/tp slice to tp-1 peers and receives the tp-1 others;
      ffn out: same for w2 partials.

    The logits gather moves vocab/tp elements once per token. sp>1 adds the
    decode-path query broadcast + LSE merge of the sequence-parallel
    attention (head_size+2 floats per kv head) — negligible, counted anyway.
    Reported bytes are sent+received per chip, matching the reference's
    sentBytes/recvBytes counters (nn-network.cpp:483-492).
    """
    per_chip = 0.0
    if tp > 1:
        # each sync: send (tp-1) copies of the 1/tp slice, receive tp-1 slices
        per_layer = 2 * 2 * (cfg.dim / tp) * (tp - 1) * exchange_bytes
        per_chip += cfg.n_layers * per_layer
        per_chip += 2 * (cfg.vocab_size / tp) * (tp - 1) * 4.0 / tp  # f32 logits gather
    if sp > 1:
        per_chip += 2 * cfg.n_layers * (cfg.n_kv_heads * (cfg.head_size + 2)) * 4.0 * (sp - 1) / sp
    return {
        "bytes_per_token_per_chip": per_chip,
        "kb_per_token_per_chip": per_chip / 1024.0,
        "tp": tp,
        "sp": sp,
        "exchange_bytes_per_elem": exchange_bytes,
    }


_COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute", "all-to-all"
)
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def measured_collective_bytes(compiled_text: str) -> dict:
    """MEASURED inter-chip bytes: sum the result shapes of every collective op
    in a compiled (post-SPMD-partitioning) HLO module — the real ops XLA
    emitted, not the analytic model. The reference counts actual socket bytes
    (nn-network.cpp:483-492); this is the compiled-program equivalent on ICI.

    Pass ``jitted.lower(*args).compile().as_text()``. Collectives inside a
    ``while`` loop (e.g. the layer scan) appear once in the text but run once
    per iteration — lower the step with ``layer_unroll=True`` for exact
    per-token totals, or treat the result as bytes *per loop trip*.
    """
    import re

    per_op: dict[str, int] = {}
    # e.g.:  %all-reduce.7 = bf16[1,2048]{1,0:T(8,128)} all-reduce(...
    # (the shape group is lazy-greedy so TPU tiled layouts like
    # {1,0:T(8,128)S(1)} are spanned). Async collectives appear as
    # -start/-done pairs: count the -start (it carries the shapes), skip the
    # -done (it aliases the same transfer).
    pat = re.compile(
        r"=\s*(.+?)\s+(" + "|".join(_COLLECTIVE_OPS) + r")(-start|-done)?[\.\(]"
    )
    shape_pat = re.compile(r"([a-z]+[0-9a-z]*)\[([0-9,]*)\]")
    for line in compiled_text.splitlines():
        m = pat.search(line)
        if not m or m.group(3) == "-done":
            continue
        shapes, op = m.group(1), m.group(2)
        found = shape_pat.findall(shapes)
        if m.group(3) == "-start" and len(found) > 1:
            # -start results are (aliased input, output, ...) tuples — only
            # the output element is a transfer
            found = found[-1:]
        nbytes = 0
        for dt, dims in found:
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        per_op[op] = per_op.get(op, 0) + nbytes
    return {"total_bytes": sum(per_op.values()), "per_op": per_op}


def params_nbytes(params) -> int:
    return sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(params) if hasattr(x, "size")
    )


def cache_nbytes(cache) -> int:
    """KV bytes: the pool (both pools where windowed layers have their own)."""
    pools = (cache.k, cache.v, getattr(cache, "kw", None),
             getattr(cache, "vw", None))
    return sum(p.size * p.dtype.itemsize for p in pools if p is not None)


def state_nbytes(cache) -> int:
    """Bytes of the recurrent state beside the KV cache (0: none)."""
    state = getattr(cache, "state", None)
    return 0 if state is None else state.nbytes


def set_memory_gauges(params, cache) -> tuple[int, int]:
    """Publish the HBM accounting as startup gauges (model_params_bytes /
    kv_cache_bytes) so it is queryable at /metrics and in the /health ready
    payload, not just a one-shot --report print. Returns (params_bytes,
    cache_bytes) for callers that also embed the numbers in a payload."""
    pb, cb = params_nbytes(params), cache_nbytes(cache)
    ins.MODEL_PARAMS_BYTES.set(pb)
    ins.KV_CACHE_BYTES.set(cb)
    return pb, cb


def memory_report(cfg, params, cache) -> str:
    """HBM accounting (nn-core.cpp:152-166 role)."""
    pb = params_nbytes(params)
    cb = cache_nbytes(cache)
    return (
        f"💿 params {pb / 1e9:.2f} GB, kv-cache {cb / 1e9:.2f} GB "
        f"(seq {cache.seq_len}, batch {cache.k.shape[1]}), total {(pb + cb) / 1e9:.2f} GB"
    )
