"""One launch record per engine launch (ISSUE 26).

A *launch* is one call of a step program of `engine/batch.BatchEngine`: a
fused n-step decode chunk, a hybrid step (decode chunk + prefill slice), a
speculative chunk, or an admission's prefill chunk. The record is built
where the launch is built, from the host arrays the dispatch already holds
(no device read, no sync), and goes to three places through seams that
exist:

* the counters `dllama_launches_total{kind}`,
  `dllama_sampler_launches_total{path}`, `dllama_slot_steps_total{state}`,
  `dllama_launch_kv_rows_total{kind}`,
  `dllama_launch_kv_rows_moved_total{kind}`,
  `dllama_launch_prefill_rows_total{kind}`, `dllama_state_slice_bytes_total`
  (always on, O(1) a launch);
* the args of the launch's span in the tracer ring (`decode.device` /
  `decode.spec`, track `launches`), behind `tr.enabled`;
* while a jax.profiler capture runs, a `dllama.launch.<kind>` annotation
  around the jit call, on the profiler's clock: the `dispatch.call` phase
  of the phase seam (`obs/perf.PhaseClock`) opens it over its own stretch.

:data:`PROGRAMS` is the ONE table the program names come from: the word a
program's `compile_obs.LEDGER.scope(fn, key)` uses (for the small boundary
programs, which share the scope word ``boundary``: the scope's key) -> the
function name its `jax.jit` is built under, so the device plane of a
profiler trace reads ``jit_dllama_<fn>`` and a record's `kind` is a key of
the same table.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import numpy as np

from dllama_tpu.obs import instruments as ins
from dllama_tpu.obs import trace

#: the step programs: each call is a launch, and a record's `kind`
LAUNCH_KINDS = ("decode", "decode_pen", "hybrid", "hybrid_pen",
                "prefill_chunk", "spec", "spec_pen")
#: the boundary programs, by the key of their ("boundary", key) scope
BOUNDARY_PROGRAMS = ("copy_rows", "page_copy", "page_spill", "page_restore",
                     "hist", "hist_batch", "hist_copy", "commit_rows")
#: an admission's first-token sampling, by the word of its ("commit", "b1")
#: scope: one program a commit, no launch of the step (no record)
COMMIT_PROGRAMS = ("commit",)
#: fn -> the name the program is jitted under
PROGRAMS = {fn: f"dllama_{fn}"
            for fn in LAUNCH_KINDS + BOUNDARY_PROGRAMS + COMMIT_PROGRAMS}

SLOT_STATES = ("advanced", "starved", "empty")
for _s in SLOT_STATES:  # the series exist from the first scrape on
    ins.SLOT_STEPS.labels(state=_s)
#: the sampler's bodies, shortest first (engine/sampling.sample_logits)
SAMPLER_PATHS = ("greedy", "temperature", "nucleus")
for _s in SAMPLER_PATHS:
    ins.SAMPLER_LAUNCHES.labels(path=_s)


def sampler_path(active: np.ndarray, temperature: np.ndarray,
                 topp: np.ndarray) -> str:
    """The longest sampler body a launch over these slots can run: the
    predicate `sample_logits` evaluates on the device, on the host's own
    vectors (a released slot keeps its stale temperature: `active` masks
    it there as here)."""
    samples = active & (temperature != 0.0)
    nucleus = samples & (topp > 0.0) & (topp < 1.0)
    return SAMPLER_PATHS[int(samples.any()) + int(nucleus.any())]


def named_jit(fn: str, impl, **jit_kw):
    """`jax.jit(impl)` under the program's name from :data:`PROGRAMS` (a
    `functools.partial` has no `__name__`: jax would call it `_unknown`).
    An `fn` the table lacks is a KeyError: no program goes unnamed."""
    prog = functools.partial(impl)
    prog.__name__ = prog.__qualname__ = PROGRAMS[fn]
    return jax.jit(prog, **jit_kw)


@dataclass(slots=True)
class LaunchRecord:
    """What one launch was asked to do, in rows and slot-steps."""

    kind: str  # a LAUNCH_KINDS word
    seq: int = 0  # DecodeChunk.seq of the launch's chunk (0: a prefill chunk)
    n: int = 0  # decode steps (spec: verify cycles) of the launch
    active: int = 0  # slots that held a request at dispatch
    advanced: int = 0  # slot-steps that wrote a row
    starved: int = 0  # slot-steps of active slots frozen by a dry page pool
    empty: int = 0  # slot-steps of slots without a request
    kv_rows: int = 0  # KV rows the decode steps attended, over slots and steps
    prefill_rows: int = 0  # prompt rows the launch wrote
    pool_dry: bool = False  # no free page in the pool when it was dispatched
    kv_rows_window: int | None = None  # a model with windowed layers: the
    # rows a windowed layer's decode steps READ, min(position + 1, window) a
    # slot-step (kv_rows is what a layer that sees everything reads)
    kv_pool: str = ""  # a model whose cache rows are of a kind of their own
    # ("latent": one shared row a token): kv_rows is also counted as rows
    # READ in that pool
    kind_layers: tuple = (0, 0)  # a model with windowed layers: how many
    # layers see the whole context and how many are windowed (the rows a
    # KIND walks are a layer's rows times its layers)
    state_slice_bytes: int = 0  # a recurrent model: the bytes of state a
    # launch's B = 1 prefill slice cuts out of the stack and puts back
    sampler: str = ""  # a SAMPLER_PATHS word: the sampler body the launch's
    # slots ask for ("" for a prefill chunk, which samples nothing)
    kv_rows_moved: int = 0  # the paged kernel's route: KV rows the copies of
    # a layer that sees everything MOVE for those steps, in and back
    # (paged_attention.rows_moved; kv_rows is what they need)
    kv_rows_moved_window: int = 0  # and those of a windowed layer's copies

    def args(self) -> dict:
        """The span / annotation arguments (`kind` is in the name too)."""
        a = {"kind": self.kind, "seq": self.seq, "n": self.n,
             "active": self.active, "starved": self.starved,
             "kv_rows": self.kv_rows, "prefill_rows": self.prefill_rows}
        if self.kv_rows_moved:
            a["kv_rows_moved"] = self.kv_rows_moved
        if self.kv_rows_window is not None:
            a["kv_rows_window"] = self.kv_rows_window
            if self.kv_rows_moved_window:
                a["kv_rows_moved_window"] = self.kv_rows_moved_window
        return a

    def count(self) -> "LaunchRecord":
        """Into the counters, once per launch, after its call returned (a
        launch that raised was not made)."""
        ins.LAUNCHES.labels(kind=self.kind).inc()
        if self.sampler:
            ins.SAMPLER_LAUNCHES.labels(path=self.sampler).inc()
        ins.SLOT_STEPS.labels(state="advanced").inc(self.advanced)
        ins.SLOT_STEPS.labels(state="empty").inc(self.empty)
        if self.starved:
            ins.SLOT_STEPS.labels(state="starved").inc(self.starved)
        if self.kv_rows:
            ins.LAUNCH_KV_ROWS.labels(kind=self.kind).inc(self.kv_rows)
        if self.kv_rows_moved:
            ins.LAUNCH_KV_ROWS_MOVED.labels(kind=self.kind).inc(
                self.kv_rows_moved)
        if self.prefill_rows:
            ins.LAUNCH_PREFILL_ROWS.labels(kind=self.kind).inc(
                self.prefill_rows)
            if self.state_slice_bytes:
                ins.STATE_SLICE_BYTES.inc(self.state_slice_bytes)
        if self.kv_rows_window is not None and self.kv_rows:
            read = ins.LAUNCH_KV_ROWS_READ
            read.labels(kind=self.kind, pool="global").inc(self.kv_rows)
            read.labels(kind=self.kind, pool="window").inc(self.kv_rows_window)
            n_global, n_window = self.kind_layers
            ins.ATTN_ROWS_WALKED.labels(kind="global").inc(
                self.kv_rows * n_global)
            ins.ATTN_ROWS_WALKED.labels(kind="window").inc(
                self.kv_rows_window * n_window)
        elif self.kv_pool and self.kv_rows:
            ins.LAUNCH_KV_ROWS_READ.labels(
                kind=self.kind, pool=self.kv_pool).inc(self.kv_rows)
        return self

    def _annotation_args(self) -> dict:
        a = self.args()
        del a["kind"]  # it is in the annotation's name
        return a

    def annotation(self):
        """The launch's profiler annotation `dllama.launch.<kind>`, entered
        (obs/trace.profiler_annotation), or None when no capture runs. The
        phase seam opens it around the jit call: the `dispatch.call` phase
        given this record (obs/perf.PhaseClock)."""
        return trace.profiler_annotation("dllama.launch.", self.kind,
                                         self._annotation_args)


def build(kind: str, seq: int, n: int, start_pos: np.ndarray,
          active: np.ndarray, advance: np.ndarray, *, seq_len: int,
          pool_dry: bool, prefill_rows: int = 0,
          frozen: np.ndarray | None = None, window: int = 0,
          kv_pool: str = "", kind_layers: tuple = (0, 0),
          state_slice_bytes: int = 0, sampler: str = "",
          paged: tuple | None = None) -> LaunchRecord:
    """The record of a launch of `n` steps over slots at `start_pos`, of
    which the `active` ones advance `advance` rows each.

    A step at position p attends p + 1 rows, so a slot that advances a rows
    from p attends a*p + a*(a+1)/2. A slot freezes for one of two reasons:
    it reached `seq_len`, or its next row has no page; the second, with no
    free page in the pool (`pool_dry`), is `BatchEngine.page_starved`'s
    condition and counts its frozen steps as starved. `frozen` gives the
    frozen steps per slot where they are not n - advance (a spec chunk).
    `window` > 0 (a model with windowed layers): also the rows such a layer
    reads, min(p + 1, window) a step; `kind_layers` = (layers that see the
    whole context, windowed layers). `paged` = (page rows, table width, tile
    rows, end-copy rows) on the paged kernel's route: also the rows a
    layer's copies MOVE for those steps, by the kernel's own definition
    (`paged_attention.rows_moved`; a spec chunk's as its single-row steps,
    as `kv_rows` counts them)."""
    if kind not in LAUNCH_KINDS:
        raise ValueError(f"unknown launch kind {kind!r} "
                         f"(catalog: {LAUNCH_KINDS})")
    pos = start_pos[active].astype(np.int64)
    adv = advance[active].astype(np.int64)
    n_active = int(active.sum())
    starved = 0
    if pool_dry:
        idle = (n - adv) if frozen is None else frozen[active]
        starved = int(idle[pos + adv < seq_len].sum())
    kv_rows_window, moved, moved_window = None, 0, 0
    if window or paged:
        # a step a column: the s-th step of a slot writes row pos + s - 1
        step = np.arange(1, int(adv.max(initial=0)) + 1, dtype=np.int64)[None]
        took = step <= adv[:, None]
    if window:
        seen = np.minimum(pos[:, None] + step, window)
        kv_rows_window = int(seen[took].sum())
    if paged:
        from dllama_tpu.ops.pallas.paged_attention import rows_moved

        at = (pos[:, None] + step - 1)[took]
        moved = int(rows_moved(at, *paged).sum())
        if window:
            moved_window = int(rows_moved(at, *paged, window).sum())
    return LaunchRecord(
        kind=kind, seq=int(seq), n=int(n), active=n_active,
        advanced=int(adv.sum()), starved=starved,
        empty=(active.size - n_active) * int(n),
        kv_rows=int((adv * pos + adv * (adv + 1) // 2).sum()),
        kv_rows_moved=moved, kv_rows_moved_window=moved_window,
        prefill_rows=int(prefill_rows), pool_dry=bool(pool_dry),
        kv_rows_window=kv_rows_window, kv_pool=kv_pool,
        kind_layers=kind_layers,
        state_slice_bytes=state_slice_bytes, sampler=sampler)
