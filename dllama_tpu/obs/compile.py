"""Compile & device-traffic observability (ISSUE 13): the XLA layer as a
first-class observable.

The stack's perf wins all rest on two invariants nothing observed until
now: a BOUNDED universe of compiled shapes (pow2 prefill chunks, the
{1,chunk} decode scans, pow2 hybrid budgets — one stray shape recompiles
mid-traffic and steals seconds of device time) and NEAR-ZERO steady-state
host↔device traffic (PR 3's device-resident decode state — one stray
per-chunk upload serializes the overlapped pipeline). This module turns
both into gated, scrapeable contracts:

* :class:`CompileLedger` — every jit trace/compile is recorded: the
  process-global :data:`LEDGER` registers a ``jax.monitoring`` listener
  (the ``/jax/core/compile/*`` duration events fire exactly when a call
  really traces/lowers/compiles — a cached call fires nothing, so the
  record is ground truth, not a host-side shape model) and engine dispatch
  sites bracket their jitted calls in :meth:`CompileLedger.scope` so each
  compile is attributed to a function label and shape-bucket key. Feeds
  ``dllama_jit_compiles_total{fn}`` / ``dllama_jit_compile_seconds_total
  {fn}`` and a ``compile`` span per event (Perfetto shows compiles
  stealing device time mid-traffic). Compiles outside any scope land under
  ``fn="untracked"``.
* :class:`ShapeContract` — the declarative registry of the EXPECTED
  compiled-shape universe (BatchEngine.declare_serving_buckets enumerates
  it: decode scan at n∈{1..chunk}, spec verify ditto, pow2 hybrid budgets
  × the decode chunk, pow2 prefill buckets, the B=1 commit sample — each
  × {plain, penalized} sampling variants, with the {dense,paged} route in
  the bucket notes). Each recorded compile classifies expected /
  unexpected (``dllama_jit_unexpected_compiles_total{fn}`` + a structured
  warning naming the offending shape); functions with no declarations at
  all (direct library use, no serving contract) classify ``undeclared``
  and never warn. The contract also drives the ``--warmup auto``
  precompile pass (BatchEngine.warmup) so the first real request stops
  paying compile.
* **transfer accounting** — :func:`note_transfer` counts host↔device
  traffic at the engine-boundary sites (``dllama_transfers_total
  {direction,site}`` / ``dllama_transfer_bytes_total``), and
  :func:`h2d_guard` builds the ``--transfer-guard`` strict mode on
  ``jax.transfer_guard_host_to_device``: wrapped around the steady-state
  decode/spec jit calls (whose operands are all device-resident carries by
  construction), an unexpected implicit upload raises instead of silently
  serializing the pipeline — PR 3's "no per-chunk uploads" claim, enforced
  forever.
* **device-memory gauges** — :func:`refresh_device_gauges` publishes live
  buffer count/bytes (``jax.live_arrays``) alongside the existing
  param/KV gauges at scrape time.
* **the persistent compile cache's place** — :func:`place_compile_cache`,
  called by every entry point before anything jits.

Module import is stdlib-only (scripts/checks.sh imports this without jax
or a model); jax is imported lazily inside the functions that need it.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque

from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import trace
from dllama_tpu.utils import locks

log = logging.getLogger("dllama_tpu.obs")

#: Where JAX's persistent compilation cache lives when the caller's
#: environment does not place it: ONE fixed, git-ignored path inside the
#: checkout. The path is part of the cache key, so it is never derived from
#: a temp name, pid or time — a directory that moves never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "experiments", "jax_cache")


def place_compile_cache() -> str:
    """Turn the persistent compile cache on for this process and return
    its directory. Every entry point calls this before anything jits
    (cli/main.py, chip_smoke.py's children, experiments/kbench.py),
    so a server's boot, a kernel sweep and a smoke share one cache: the compiled
    shape universe (ROADMAP Speed #5) is paid for once per machine, not
    once per process.

    Placed from OUTSIDE when ``JAX_COMPILATION_CACHE_DIR`` is set: jax reads
    that variable into its own config, and this function then sets no
    directory (and never the variable). Otherwise :data:`COMPILE_CACHE_DIR`.
    Either way the minimum compile time for an entry drops from jax's 1 s
    to 0 — the small decode-bucket and boundary programs are most of the
    universe by count."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    log.info("compile cache: %s", cache_dir)
    return cache_dir


#: ledger `fn` labels the engine dispatch sites bill compiles to — the
#: single definition site for the README "Shape-bucket contract" table
#: (scripts/checks.sh asserts the two stay identical, both directions).
#: Bucket-key grammar per fn is in each description.
COMPILE_FNS = {
    "prefill_chunk": "add_step's admission prefill, one pow2 chunk "
                     "(keys m{c}, c = pow2 <= --max-prefill-chunk; B=1 "
                     "slot prefill on unsharded/paged engines, masked "
                     "full-width on dp meshes)",
    "decode": "the fused n-step decode scan, plain sampling (keys n{n}; "
              "the scheduler dispatches n=chunk, row-limit clamps may "
              "shrink n toward 1 near the context edge)",
    "decode_pen": "the decode scan with repetition-penalty counts in the "
                  "carry (keys n{n})",
    "spec": "the fused speculative propose/verify chunk, plain sampling "
            "(keys n{n} = verify cycles per launch; {1, chunk})",
    "spec_pen": "the spec chunk with penalty counts in the cycle carry "
                "(keys n{n})",
    "hybrid": "the fused prefill-slice + decode-chunk launch (keys "
              "p{P}.n{n}: P = pow2 prefill-budget slice, n = decode "
              "steps)",
    "hybrid_pen": "the hybrid launch with penalty counts (keys p{P}.n{n})",
    "commit": "add_sample's first-token program off the admission logits: "
              "key derivation, split and the B=1 sampler in one (key b1 — "
              "one [1, V] shape per engine)",
    "single_sample": "the single-engine Sampler's jitted sample off "
                     "prefill logits (keys b{B}; never contract-declared, "
                     "so it cannot classify unexpected)",
    "single_step": "the single-engine tier's jitted step "
                   "(InferenceEngine.step: pow2 prefill chunks and "
                   "decode_step; keys m{T} = token width)",
    "single_decode": "the single-engine fused n-step decode scans "
                     "(greedy, sampled and penalized variants; keys n{n})",
    "single_spec": "the single-engine prompt-lookup speculative decode "
                   "(keys n{n} = tokens requested from the chunk)",
    "boundary": "small boundary carry ops (history writes, cross-slot row "
                "copies, COW page clones, surgical .at row writes) — one-"
                "time per-process compiles; attributed so steady-state "
                "decode shows ZERO untracked compiles",
    "untracked": "compiles observed outside any instrumented dispatch "
                 "site (boundary eager ops, library use, other jits); "
                 "never classified unexpected, but counted — steady-state "
                 "decode must not produce ANY",
}

#: transfer-accounting site labels (bounded cardinality; the README
#: transfer table documents each)
TRANSFER_SITES = ("vectors", "prefill", "history", "commit",
                  "decode_tokens", "spec_counts", "nan_guard",
                  "kv_spill", "kv_restore")


def sig_of(*args, max_leaves: int = 12) -> str:
    """Abstract signature of call operands: dtype[shape] per array leaf,
    scalars by value — the ledger's record of WHAT shape compiled. Never
    raises (a ledger entry must not take down a dispatch)."""
    parts: list[str] = []
    try:
        for a in args:
            if len(parts) >= max_leaves:
                parts.append("...")
                break
            shape = getattr(a, "shape", None)
            dtype = getattr(a, "dtype", None)
            if shape is not None and dtype is not None:
                parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
            elif isinstance(a, (int, float, bool)):
                parts.append(repr(a))
            else:
                parts.append(type(a).__name__)
    except Exception:  # pragma: no cover - defensive
        parts.append("?")
    return ",".join(parts)


class ShapeContract:
    """Declarative registry of the expected compiled-shape universe.

    ``declare(fn, key)`` enumerates a bucket (optionally a warm target for
    the boot precompile pass); ``allow(fn, predicate)`` admits extra keys
    as expected without making them warm targets (e.g. the decode scan's
    row-limit clamp can produce any n in [1, chunk], but only {1, chunk}
    are worth precompiling). ``classify`` answers expected / unexpected /
    undeclared — a fn with no declarations at all has no contract to
    violate (direct library use), so it never classifies unexpected."""

    def __init__(self):
        self._lock = locks.make_lock("obs.contract")
        # fn -> {key: {"note": str, "warm": bool}}
        self._buckets: dict[str, dict[str, dict]] = {}
        # fn -> {range_key: predicate} — keyed so re-declaring the same
        # range (every Scheduler construction on a shared engine) replaces
        # instead of appending duplicate closures
        self._allow: dict[str, dict[str, object]] = {}

    def declare(self, fn: str, key: str, note: str = "",
                warm: bool = True) -> None:
        if fn not in COMPILE_FNS:
            raise ValueError(f"unknown compile fn {fn!r} "
                             f"(catalog: {sorted(COMPILE_FNS)})")
        with self._lock:
            self._buckets.setdefault(fn, {})[str(key)] = {
                "note": note, "warm": bool(warm)}

    def allow(self, fn: str, predicate, key: str = "default") -> None:
        """Admit keys matching ``predicate(key) -> bool`` as expected for
        ``fn`` without enumerating them as warm targets. ``key`` names the
        range: re-allowing under the same name REPLACES the predicate
        (declarations are re-run per scheduler on a shared engine and must
        stay idempotent), while distinct names union."""
        with self._lock:
            self._allow.setdefault(fn, {})[str(key)] = predicate

    def declared(self, fn: str) -> bool:
        with self._lock:
            return fn in self._buckets

    def classify(self, fn: str, key: str) -> str:
        """'expected' | 'unexpected' | 'undeclared'."""
        key = str(key)
        with self._lock:
            buckets = self._buckets.get(fn)
            if buckets is None:
                return "undeclared"
            if key in buckets:
                return "expected"
            preds = list(self._allow.get(fn, {}).values())
        for p in preds:
            try:
                if p(key):
                    return "expected"
            except Exception:  # pragma: no cover - a broken predicate
                continue      # must not crash a dispatch
        return "unexpected"

    def warm_targets(self) -> list[tuple[str, str, str]]:
        """(fn, key, note) of every declared warm-target bucket, in
        declaration order — the --warmup auto precompile worklist."""
        with self._lock:
            return [(fn, key, b["note"])
                    for fn, ks in self._buckets.items()
                    for key, b in ks.items() if b["warm"]]

    def coverage(self, seen: dict[str, set]) -> dict:
        """Per-fn bucket coverage against ``seen`` (fn -> compiled keys):
        declared/warm-target counts, which warm targets are still missing,
        and which seen keys fell outside the declaration — the
        `/debug/compile` contract view. ``full`` is True when every warm
        target has compiled (what `--warmup auto` must reach)."""
        with self._lock:
            buckets = {fn: dict(ks) for fn, ks in self._buckets.items()}
            preds = {fn: list(ps.values()) for fn, ps in self._allow.items()}
        out: dict = {"fns": {}, "full": True}
        for fn, ks in sorted(buckets.items()):
            got = {str(k) for k in seen.get(fn, set())}
            warm = [k for k, b in ks.items() if b["warm"]]
            missing = sorted(k for k in warm if k not in got)
            unexpected = sorted(
                k for k in got
                if k not in ks and not any(
                    self._safe(p, k) for p in preds.get(fn, ())))
            out["fns"][fn] = {
                "declared": len(ks),
                "warm_targets": len(warm),
                "compiled": len(got & set(ks)),
                "missing_warm": missing,
                "unexpected_seen": unexpected,
            }
            if missing:
                out["full"] = False
        return out

    @staticmethod
    def _safe(pred, key) -> bool:
        try:
            return bool(pred(key))
        except Exception:  # pragma: no cover
            return False


class _Scope:
    """One instrumented dispatch window (CompileLedger.scope): compile
    events firing on THIS thread inside the window are attributed to the
    scope's (fn, key). Cheap when nothing compiles: one threadlocal push/
    pop and a zero-check."""

    __slots__ = ("ledger", "fn", "key", "sig", "t0", "record",
                 "trace_s", "lower_s", "compile_s", "n_backend")

    def __init__(self, ledger, fn, key, sig, record=True):
        self.ledger = ledger
        self.fn = fn
        self.key = str(key)
        self.sig = sig
        self.record = record  # False: a meter (CompileLedger.meter)
        self.trace_s = self.lower_s = self.compile_s = 0.0
        self.n_backend = 0

    def absorb(self, meter: "_Scope") -> None:
        """Count what `meter` measured ahead of this dispatch (the warm-up's
        lowering and pooled compile of the same program) as this scope's."""
        self.trace_s += meter.trace_s
        self.lower_s += meter.lower_s
        self.compile_s += meter.compile_s
        self.n_backend += meter.n_backend

    def __enter__(self):
        self.t0 = time.monotonic()
        self.ledger._push(self)
        return self

    def __exit__(self, *exc):
        self.ledger._pop(self)
        if self.record and (self.trace_s or self.lower_s or self.compile_s):
            self.ledger._record(self, time.monotonic())
        return False


class CompileLedger:
    """Thread-safe record of every observed jit compile: bounded entry
    ring, per-fn totals, per-fn seen bucket keys, and the installed
    :class:`ShapeContract`. One process-global instance (:data:`LEDGER`),
    same lifecycle as the metrics registry."""

    def __init__(self, max_entries: int = 256):
        # _on_event bumps the untracked compile counter while holding this
        # (obs.ledger ranks below the obs.metrics leaf — rank-legal)
        self._lock = locks.make_lock("obs.ledger")
        self._tls = threading.local()
        self.max_entries = int(max_entries)
        self.entries: deque = deque(maxlen=self.max_entries)
        # fn -> {"compiles", "seconds", "unexpected"}
        self.totals: dict[str, dict] = {}
        # fn -> set of bucket keys that actually compiled
        self.seen: dict[str, set] = {}
        self.contract = ShapeContract()
        self.warmup_report: dict | None = None
        self._warmup_depth = 0  # >0: entries flag warmup=True
        self._seq = 0
        self._listener_installed = False
        self._t0 = time.monotonic()

    # ------------------------------------------------------------ listener

    def ensure_listener(self) -> None:
        """Register the jax.monitoring duration listener once per process
        (idempotent; lazily so this module imports without jax)."""
        if self._listener_installed:
            return
        with self._lock:
            if self._listener_installed:
                return
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                self._on_event)
            self._listener_installed = True

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if not event.startswith("/jax/core/compile"):
            return
        sc = self._top()
        if sc is not None:
            if event.endswith("jaxpr_trace_duration"):
                sc.trace_s += duration
            elif event.endswith("backend_compile_duration"):
                sc.compile_s += duration
                sc.n_backend += 1
            else:
                sc.lower_s += duration
            return
        # no scope on this thread: boundary eager ops, other jits. Totals
        # only — one "compile" per backend event, seconds for everything.
        with self._lock:
            tot = self.totals.setdefault(
                "untracked", {"compiles": 0, "seconds": 0.0, "unexpected": 0})
            tot["seconds"] += duration
            if event.endswith("backend_compile_duration"):
                tot["compiles"] += 1
                ins.JIT_COMPILES.labels(fn="untracked").inc()
        ins.JIT_COMPILE_SECONDS.labels(fn="untracked").inc(duration)

    # -------------------------------------------------------------- scopes

    def scope(self, fn: str, key: str = "", sig=None) -> _Scope:
        """Bracket one jitted dispatch: ``with LEDGER.scope("decode",
        f"n{n}", sig=lambda: sig_of(*args)): ...``. ``sig`` is a lazy
        thunk — evaluated only when a compile actually happened."""
        self.ensure_listener()
        return _Scope(self, fn, key, sig)

    def meter(self) -> _Scope:
        """A scope that only measures: compile events inside it add to its
        seconds and no entry is recorded. The warm-up lowers a program on
        one thread and compiles it on another under one meter, and the
        program's dispatch scope then `absorb`s it: one entry a program."""
        self.ensure_listener()
        return _Scope(self, "", "", None, record=False)

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _push(self, sc) -> None:
        self._stack().append(sc)

    def _pop(self, sc) -> None:
        st = self._stack()
        if st and st[-1] is sc:
            st.pop()

    def _top(self):
        st = getattr(self._tls, "stack", None)
        return st[-1] if st else None

    def warmup_phase(self):
        """Context manager flagging entries recorded inside it as warmup
        (boot precompile) rather than traffic-stealing compiles."""
        ledger = self

        class _Warm:
            def __enter__(self):
                with ledger._lock:
                    ledger._warmup_depth += 1

            def __exit__(self, *exc):
                with ledger._lock:
                    ledger._warmup_depth -= 1
                return False

        return _Warm()

    # ------------------------------------------------------------- record

    def _record(self, sc: _Scope, t1: float) -> None:
        total = sc.trace_s + sc.lower_s + sc.compile_s
        sig = ""
        if sc.sig is not None:
            try:
                sig = sc.sig() if callable(sc.sig) else str(sc.sig)
            except Exception:  # pragma: no cover - lazy sig must not raise
                sig = "?"
        verdict = self.contract.classify(sc.fn, sc.key)
        with self._lock:
            self._seq += 1
            warm = self._warmup_depth > 0
            entry = {
                "seq": self._seq,
                "at_ms": round((sc.t0 - self._t0) * 1000.0, 3),
                "fn": sc.fn,
                "key": sc.key,
                "sig": sig,
                "classification": verdict,
                "warmup": warm,
                "lowering_s": round(sc.trace_s + sc.lower_s, 6),
                "compile_s": round(sc.compile_s, 6),
                "total_s": round(total, 6),
                "wall_s": round(t1 - sc.t0, 6),
            }
            self.entries.append(entry)
            tot = self.totals.setdefault(
                sc.fn, {"compiles": 0, "seconds": 0.0, "unexpected": 0})
            tot["compiles"] += 1
            tot["seconds"] += total
            if verdict == "unexpected":
                tot["unexpected"] += 1
            self.seen.setdefault(sc.fn, set()).add(sc.key)
        ins.JIT_COMPILES.labels(fn=sc.fn).inc()
        ins.JIT_COMPILE_SECONDS.labels(fn=sc.fn).inc(total)
        if verdict == "unexpected":
            ins.JIT_UNEXPECTED_COMPILES.labels(fn=sc.fn).inc()
            # the structured contract-miss warning: names the offending
            # shape so "why did the fleet hiccup at 14:02" is one grep
            log.warning(
                "unexpected jit compile outside the shape-bucket contract: "
                "fn=%s key=%s sig=%s (%.3fs lowering + %.3fs compile) — "
                "declare the bucket or fix the caller's shape",
                sc.fn, sc.key, sig, sc.trace_s + sc.lower_s, sc.compile_s,
                extra={"compile_fn": sc.fn, "compile_key": sc.key})
        tr = trace.TRACER
        if tr.enabled:
            tr.span_at("compile", sc.t0, t1, cat="compile", track="compile",
                       fn=sc.fn, key=sc.key, warmup=warm,
                       classification=verdict, compile_s=round(total, 4))

    # ------------------------------------------------------------- reading

    def total_compiles(self) -> int:
        """Every observed compile, scoped AND untracked — the number a
        steady-state decode window must not move at all."""
        with self._lock:
            return sum(t["compiles"] for t in self.totals.values())

    def total_unexpected(self) -> int:
        with self._lock:
            return sum(t["unexpected"] for t in self.totals.values())

    def total_seconds(self) -> float:
        with self._lock:
            return sum(t["seconds"] for t in self.totals.values())

    def snapshot(self, entries: int = 64) -> dict:
        """The `/debug/compile` ledger body: per-fn totals, the most
        recent entries, per-fn seen bucket keys, and contract coverage."""
        n = max(0, int(entries))
        with self._lock:
            totals = {fn: dict(t) for fn, t in sorted(self.totals.items())}
            recent = list(self.entries)[-n:] if n else []
            seen = {fn: sorted(ks) for fn, ks in sorted(self.seen.items())}
            seen_sets = {fn: set(ks) for fn, ks in self.seen.items()}
        return {
            "totals": totals,
            "compiles": sum(t["compiles"] for t in totals.values()),
            "unexpected": sum(t["unexpected"] for t in totals.values()),
            "seconds": round(sum(t["seconds"] for t in totals.values()), 6),
            "entries": recent,
            "seen": seen,
            "contract": self.contract.coverage(seen_sets),
        }

    def summary(self) -> dict:
        """Compact join for latency_summary() / /health / /debug/perf."""
        with self._lock:
            totals = self.totals
            out = {
                "compiles": sum(t["compiles"] for t in totals.values()),
                "unexpected": sum(t["unexpected"] for t in totals.values()),
                "seconds": round(
                    sum(t["seconds"] for t in totals.values()), 3),
            }
        out["warmup"] = (None if self.warmup_report is None
                         else {k: self.warmup_report.get(k)
                               for k in ("mode", "buckets", "compiled",
                                         "seconds", "full_coverage")})
        return out

    def install_contract(self, contract: ShapeContract) -> None:
        """Adopt an engine's contract (last engine wins, like the serving
        gauges — one serving engine per process in production). Installing
        starts a fresh COVERAGE epoch: the per-fn seen-bucket record resets
        so `/debug/compile`'s coverage describes shapes observed under THIS
        contract, not whatever a previous engine in the process compiled
        (lifetime totals/entries stay — compiles really happened)."""
        with self._lock:
            self.contract = contract
            self.seen = {}

    def reset(self) -> None:
        """Drop entries/totals/seen and the warmup report, keeping the
        listener and contract (test isolation)."""
        with self._lock:
            self.entries.clear()
            self.totals = {}
            self.seen = {}
            self.warmup_report = None


#: the process-global compile ledger (what /debug/compile serves)
LEDGER = CompileLedger()


# ------------------------------------------------------------- transfers

_transfer_lock = locks.make_lock("obs.transfers")
# (direction, site) -> [count, bytes] — mirror of the counters so the
# /debug payload can enumerate label combos without registry introspection
_transfers: dict[tuple[str, str], list] = {}


def note_transfer(direction: str, site: str, nbytes: int) -> None:
    """Count one host↔device transfer at an engine-boundary site.
    ``direction`` is 'h2d' or 'd2h'; ``site`` one of TRANSFER_SITES."""
    ins.TRANSFERS.labels(direction=direction, site=site).inc()
    ins.TRANSFER_BYTES.labels(direction=direction, site=site).inc(
        max(0, int(nbytes)))
    with _transfer_lock:
        acc = _transfers.setdefault((direction, site), [0, 0])
        acc[0] += 1
        acc[1] += max(0, int(nbytes))


def transfer_snapshot() -> dict:
    """Per-site transfer tallies + h2d/d2h totals (the `/debug/compile`
    transfer view and the steady-state-gate's measurement surface)."""
    with _transfer_lock:
        items = {f"{d}.{s}": {"count": c, "bytes": b}
                 for (d, s), (c, b) in sorted(_transfers.items())}
        h2d = sum(b for (d, _), (_, b) in _transfers.items() if d == "h2d")
        d2h = sum(b for (d, _), (_, b) in _transfers.items() if d == "d2h")
        h2d_n = sum(c for (d, _), (c, _) in _transfers.items() if d == "h2d")
        d2h_n = sum(c for (d, _), (c, _) in _transfers.items() if d == "d2h")
    return {"sites": items,
            "h2d": {"count": h2d_n, "bytes": h2d},
            "d2h": {"count": d2h_n, "bytes": d2h}}


def reset_transfers() -> None:
    """Zero the host-side mirror (tests/benches; the registry counters
    keep their monotone lifetime totals)."""
    with _transfer_lock:
        _transfers.clear()


TRANSFER_GUARD_MODES = ("off", "log", "strict")


def h2d_guard(mode: str):
    """Context manager for the steady-state dispatch window: 'strict'
    turns any implicit host→device transfer inside it into an error
    (``jax.transfer_guard_host_to_device("disallow")``), 'log' logs them,
    'off' is a no-op. The engine wraps ONLY the steady-state decode/spec
    jit calls — whose operands are device-resident carries by construction
    — so boundary-legitimate uploads (vector refresh, prefill chunks)
    never trip it."""
    if mode == "off" or not mode:
        import contextlib

        return contextlib.nullcontext()
    if mode not in TRANSFER_GUARD_MODES:
        raise ValueError(
            f"transfer_guard must be one of {TRANSFER_GUARD_MODES}, "
            f"got {mode!r}")
    import jax

    return jax.transfer_guard_host_to_device(
        "disallow" if mode == "strict" else "log")


# --------------------------------------------------------- device memory

def refresh_device_gauges() -> dict:
    """Publish live device-buffer count/bytes (jax.live_arrays) — called
    at scrape time like the process self-metrics, never on the hot path —
    plus the first device's high-water mark where the backend keeps one
    (``memory_stats()`` is None on CPU). Answers {'buffers': None,
    'bytes': None} where jax is unavailable."""
    try:
        import jax

        arrs = jax.live_arrays()
        n = len(arrs)
        total = 0
        for a in arrs:
            try:
                total += int(a.nbytes)
            except Exception:  # pragma: no cover - deleted mid-iteration
                continue
    except Exception:  # pragma: no cover - no backend
        return {"buffers": None, "bytes": None}
    ins.DEVICE_LIVE_BUFFERS.set(n)
    ins.DEVICE_LIVE_BYTES.set(total)
    stats = jax.devices()[0].memory_stats() or {}
    return {"buffers": n, "bytes": total,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def device_memory_marks() -> dict:
    """The first device's allocator marks where the backend keeps them
    (`memory_stats()` is None on CPU: {}): bytes in use now, the
    high-water mark so far, and what live arrays account for. In use minus
    live arrays is what the runtime holds beside them: loaded programs and
    the temporaries of launches in flight. The warmup report carries one
    reading from before its first program and one from after its last, so
    a boot's high-water mark can be laid at loading and building the
    engine or at the warm programs."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if not stats:
        return {}
    return {"bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "live_array_bytes": sum(int(a.nbytes) for a in jax.live_arrays())}


_UNSET = object()


def debug_payload(warmup_report=_UNSET, entries: int = 64) -> dict:
    """The GET /debug/compile document: ledger dump + bucket coverage +
    warmup report + transfer tallies + live device memory. Callers who
    KNOW their warmup state (the API tier) pass it explicitly — including
    an explicit None for a warmup-off boot, which must not fall back to a
    stale report some earlier engine left on the global ledger; omitting
    the argument keeps the ledger's own report (library use)."""
    out = LEDGER.snapshot(entries=entries)
    out["warmup"] = (LEDGER.warmup_report if warmup_report is _UNSET
                     else warmup_report)
    out["transfers"] = transfer_snapshot()
    out["device_memory"] = refresh_device_gauges()
    return out
