"""Device idle time by the PHASE of host work it fell under, per launch, in
milliseconds. While a capture runs the program writes, beside its scheduler
states (`dllama.sched.<state>`, `trace_sched_gap.py`'s join), one
`dllama.phase.<name>` annotation for every named stretch of the scheduler
worker's work (`obs/perf.PhaseClock`: plan, build, call, after, wait, fold,
emit, finish, sample, activate, admit, pump, scan) with the `seq` of the
launch it works for and, while the pipeline is drained, the `drain` reason;
the jit call itself is the `dllama.launch.<kind>` annotation and counts
here as the phase `dispatch.call` (it inherits the drain reason of the
phase before it). Every stretch in which no op ran on the device, cut to
the extent of the host's TraceMe events as in `trace_sched_gap.py`, is
laid over those phases; the value is the idle under any phase over the
launches in the device's window.

It also prints one free-form JSON line, {"phase": "idle_by_phase", ...}:
idle seconds by phase, by drain reason ("none": the pipeline held a launch
when the phase ran), the idle no phase covers and its split by scheduler
state (a state's self time, or `idle`), and the host's own clock beside
it: ms a launch by state and by phase, drains by reason and waits by
outcome, once INSIDE the capture (`/debug/perf`'s `capture` block, counter
deltas between the profiler's begin and end) and once over the whole
window (the scrapes at its open and close). The ratio of the two is what
the profiler costs the host, measured by the program itself.

Phases never overlap (one that opens inside another suspends it), so a
sorted list is laid over directly. A phase open when the profiler starts
is restamped by the program (`Scheduler.restamp`), as the states are.

None when there is no capture file, no device plane, or no
`dllama.phase.*` event on the host (a program without phases). No params.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re

from benchmark import trace_reduce
from benchmark.reducers import trace_sched_gap

PHASE = "dllama.phase."
CALL = "dispatch.call"  # the phase a dllama.launch.<kind> annotation is
NONE = "none"
_LABELLED = re.compile(r'^(\w+)\{(\w+)="([^"]*)"\}$')


def read(path: str) -> list:
    """The planes of an `.xplane.pb` as `trace_sched_gap.read` gives them,
    with each `dllama.*` host event's arguments kept (`drain`, `seq`)."""
    from jax.profiler import ProfileData

    planes = trace_sched_gap.read(path)
    by_name = {pl["name"]: pl for pl in planes}
    for pl in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(pl.name):
            continue
        lines = []
        for ln in pl.lines:
            evs = [(e.name, int(e.start_ns), int(e.duration_ns),
                    {k: v for k, v in e.stats})
                   for e in ln.events if e.name.startswith("dllama.")]
            if evs:
                lines.append({"name": ln.name, "events": evs})
        by_name[pl.name]["lines"] = lines
    return planes


def _free(gs: int, ge: int, covered: list):
    """The parts of [gs, ge) that the sorted (s, e) pieces leave open."""
    at = gs
    for s, e in covered:
        if s > at:
            yield at, s
        at = max(at, e)
    if at < ge:
        yield at, ge


def join(planes: list):
    """The device's idle stretches laid over the host's phases. None
    without a device plane or without a `dllama.phase.*` span."""
    phases, sched, launch, recorded = [], [], [], []
    for pl in planes:
        if trace_reduce.DEVICE_PLANE.match(pl["name"]):
            continue
        if pl.get("recorded"):
            recorded.append(pl["recorded"])
        for ln in pl["lines"]:
            for name, s, d, args in ln["events"]:
                if name.startswith(PHASE):
                    phases.append((s, s + d, name[len(PHASE):],
                                   args.get("drain") or NONE))
                elif name.startswith(trace_sched_gap.LAUNCH):
                    launch.append((s, s + d))
                elif name.startswith(trace_sched_gap.SCHED):
                    sched.append((s, s + d, name[len(trace_sched_gap.SCHED):]))
    devices = [pl for pl in planes
               if trace_reduce.DEVICE_PLANE.match(pl["name"])]
    if not devices or not phases:
        return None
    # a launch's call is a phase too; it runs under the drain reason of the
    # phase before it (its annotation carries the launch record instead)
    spans = sorted(phases + [(s, e, CALL, None) for s, e in launch])
    drain = NONE
    for i, (s, e, name, d) in enumerate(spans):
        if d is None:
            spans[i] = (s, e, name, drain)
        else:
            drain = d
    sched.sort()
    starts = [s for s, _, _, _ in spans]
    sched_starts = [s for s, _, _ in sched]
    h_lo = min([lo for lo, _ in recorded] or [spans[0][0]])
    h_hi = max([hi for _, hi in recorded] or [max(e for _, e, _, _ in spans)])
    by_phase: dict = {}
    by_drain: dict = {}
    uncovered_by_state: dict = {}
    idle = window = outside = 0.0
    launches = 0
    for pl in devices:
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        events = (lines.get(trace_reduce.OP_LINE)
                  or lines.get(trace_reduce.MODULE_LINE) or ())
        busy = [(s, s + d) for _, s, d, _ in events]
        if not busy:
            continue
        t_lo, t_hi = min(s for s, _ in busy), max(e for _, e in busy)
        window += (t_hi - t_lo) / 1e9
        launches += sum(1 for s, e in launch if e > t_lo and s < t_hi)
        for gs, ge in trace_reduce._gaps(busy):
            outside += (ge - gs) / 1e9
            gs, ge = max(gs, h_lo), min(ge, h_hi)
            if ge <= gs:
                continue
            outside -= (ge - gs) / 1e9
            idle += (ge - gs) / 1e9
            covered = []
            i = max(bisect.bisect_right(starts, gs) - 1, 0)
            while i < len(spans) and spans[i][0] < ge:
                s, e, name, d = spans[i]
                lo, hi = max(s, gs), min(e, ge)
                if hi > lo:
                    covered.append((lo, hi))
                    by_phase[name] = by_phase.get(name, 0.0) + (hi - lo) / 1e9
                    by_drain[d] = by_drain.get(d, 0.0) + (hi - lo) / 1e9
                i += 1
            for fs, fe in _free(gs, ge, covered):
                left = fe - fs
                for state, ns in trace_sched_gap._laid_over(
                        sched, sched_starts, fs, fe):
                    uncovered_by_state[state] = (
                        uncovered_by_state.get(state, 0.0) + ns / 1e9)
                    left -= ns
                if left > 0:
                    uncovered_by_state[NONE] = (
                        uncovered_by_state.get(NONE, 0.0) + left / 1e9)
    n = len(devices)
    by_phase = {k: v / n for k, v in sorted(by_phase.items())}
    by_drain = {k: v / n for k, v in sorted(by_drain.items())}
    uncovered_by_state = {k: v / n
                          for k, v in sorted(uncovered_by_state.items())}
    idle, window, launches = idle / n, window / n, launches / n
    under = sum(by_phase.values())
    return {"device_window_s": window, "outside_host_s": outside / n,
            "idle_s": idle, "by_phase": by_phase, "by_drain": by_drain,
            "under_phases_s": under,
            "uncovered_s": max(idle - under, 0.0),
            "uncovered_share": (max(idle - under, 0.0) / idle
                                if idle > 0 else 0.0),
            "uncovered_by_state": uncovered_by_state,
            "launches": launches, "phase_spans": len(phases)}


def _table(seconds, launches: float, states: dict, phases: dict,
           opens: dict, gap_s: float, drains: dict, waits: dict) -> dict:
    """One side of the host's clock: ms a launch by state and by phase."""
    per = lambda series: {k: 1e3 * v / launches
                          for k, v in sorted(series.items()) if v}
    return {"seconds": seconds, "launches": launches,
            "state_ms_per_launch": per(states),
            "phase_ms_per_launch": per(phases),
            "phase_opens": {k: v for k, v in opens.items() if v},
            "host_gap_ms_per_launch": 1e3 * gap_s / launches,
            "drains": {k: v for k, v in drains.items() if v},
            "launch_waits": waits}


def _window_series(run: dict, family: str) -> dict:
    """{label value: window delta} of a one-label family of the scrapes."""
    before, after = run["before"]["metrics"], run["after"]["metrics"]
    out = {}
    for key, v in after.items():
        m = _LABELLED.match(key)
        if m and m.group(1) == family:
            out[m.group(3)] = v - before.get(key, 0.0)
    return out


def host_tables(run: dict) -> dict:
    """The host's own clock, inside the capture and over the window: ms a
    launch by state and phase, drains, waits. Whatever the program does not
    export is left out."""
    out = {}
    cap = ((run.get("after") or {}).get("perf") or {}).get("capture") or {}
    n = sum((cap.get("launches") or {}).values())
    if n > 0 and "sched_seconds" in cap:
        out["capture"] = _table(
            cap.get("seconds"), n, cap["sched_seconds"],
            cap.get("phase_seconds", {}), cap.get("phases", {}),
            (cap.get("host_gap") or {}).get("sum", 0.0),
            cap.get("drains", {}), cap.get("launch_waits", {}))
    win = lambda family: _window_series(run, family)
    n = sum(win("dllama_launches_total").values())
    if n > 0:
        b, a = run["before"]["metrics"], run["after"]["metrics"]
        gap = "dllama_decode_host_gap_seconds_sum"
        out["window"] = _table(
            run["t1"] - run["t0"], n,
            win("dllama_scheduler_time_seconds_total"),
            win("dllama_scheduler_phase_seconds_total"),
            win("dllama_scheduler_phase_total"),
            a.get(gap, 0.0) - b.get(gap, 0.0),
            win("dllama_pipeline_drains_total"),
            win("dllama_launch_waits_total"))
    return out


def reduce(params: dict, run: dict):
    if not run.get("trace"):
        return None
    found = sorted(glob.glob(trace_sched_gap.CAPTURES), key=os.path.getmtime)
    if not found:
        return None
    joined = join(read(found[-1]))
    if joined is None:
        return None
    print(json.dumps({"phase": "idle_by_phase", **joined,
                      "host": host_tables(run)}), flush=True)
    if joined["launches"] <= 0:
        return None
    return 1e3 * joined["under_phases_s"] / joined["launches"]
