"""Device idle time that the scheduler's own work explains, per launch, in
milliseconds: the profiler stamps the device plane and, while it runs, the
program writes its scheduler states (`dllama.sched.<state>`, the nine
exclusive states of `obs/perf.TimeLedger`, tiling the worker thread) and
its launches (`dllama.launch.<kind>`, around each jit call) onto the host
plane of the same file. Every stretch in which no op ran on the device is
laid over those spans; what falls under a state other than `idle` is time
the device waited for the host's work, and is divided by the launches that
fall in the device's window.

It also prints one free-form JSON line, {"phase": "idle_by_state", ...}:
idle seconds by scheduler state and by launch kind (the device idle while
the host was still inside that launch's call), and the share of the idle
time no state covers (the capture's first state, begun before the
profiler, is not stamped).

The metric's value swings with what a 2 s capture happens to hold: an
admission's `commit` drains the pipeline by design and leaves the device
idle for tens of milliseconds, and a capture holds 0, 1 or 2 of them over
5-7 launches. The line therefore splits the reading by what the join
already yields: `commit_idle_ms_per_commit` (idle under the `commit` state
over the commits in the device's window; None when it holds none) and
`steady_gap_ms_per_launch` (idle under every other working state over the
launches): the second is the cost of the host's work between launches, and
does not move with the number of commits.

The join holds only where the host was recording. The profiler starts its
device tracer some milliseconds before its host recorder (TraceMe events:
the program's annotations and the runtime's own) and stops them in turn, so
the device's idle stretches are cut to the extent of the host's TraceMe
events (every host event that is not the Python tracer's, whose names start
with `$`), and what lies outside is reported apart as `outside_host_s`,
under no state and in no share. A state that is open when the profiler
stops would be dropped by it: the program closes and reopens the open state
as a capture begins and just before it stops (`TimeLedger.restamp`), so a
worker that sits in one state for a whole launch (a commit waiting for its
logits) is still stamped.

`trace_reduce.load` keeps only the first and last event of a host line and
`run` carries no path, so this reads the raw `.xplane.pb` where `run.py`
leaves it until its `finally`: `out/trace-*/plugins/profile/*/*.xplane.pb`
beside this package, the newest. None when there is no such file, no
device plane, or no `dllama.*` event on the host (a program without them).
No params."""

from __future__ import annotations

import bisect
import glob
import json
import os

from benchmark import trace_reduce

SCHED, LAUNCH = "dllama.sched.", "dllama.launch."
#: the scheduler states that are not the host's work between launches:
#: nothing to do, and an admission's commit (waits for its launch's logits)
IDLE, COMMIT = "idle", "commit"
_HERE = os.path.dirname(os.path.abspath(__file__))
CAPTURES = os.path.join(os.path.dirname(_HERE), "out", "trace-*", "plugins",
                        "profile", "*", "*.xplane.pb")


def read(path: str) -> list:
    """The planes of an `.xplane.pb` in the shape `trace_reduce.load` gives,
    keeping the device planes' op and module lines and, of the host, only
    the `dllama.*` events (every one of them)."""
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        device = bool(trace_reduce.DEVICE_PLANE.match(pl.name))
        lines, lo, hi = [], None, None
        for ln in pl.lines:
            if device and ln.name not in (trace_reduce.MODULE_LINE,
                                          trace_reduce.OP_LINE):
                continue
            evs = []
            for e in ln.events:
                if device or e.name.startswith("dllama."):
                    evs.append((e.name, int(e.start_ns), int(e.duration_ns), {}))
                if not device and not e.name.startswith("$"):
                    s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                    lo = s if lo is None else min(lo, s)
                    hi = t if hi is None else max(hi, t)
            if evs:
                lines.append({"name": ln.name, "events": evs})
        plane = {"name": pl.name, "lines": lines}
        if lo is not None:
            plane["recorded"] = [lo, hi]  # extent of the host's TraceMe events
        planes.append(plane)
    return planes


def _laid_over(spans: list, starts: list, gs: int, ge: int):
    """(label, ns) for each span of a sorted, non-overlapping list that the
    stretch [gs, ge) touches."""
    i = max(bisect.bisect_right(starts, gs) - 1, 0)
    while i < len(spans) and spans[i][0] < ge:
        s, e, label = spans[i]
        if min(e, ge) > max(s, gs):
            yield label, min(e, ge) - max(s, gs)
        i += 1


def join(planes: list):
    """The device's idle stretches laid over the host's spans. None without
    a device plane or without a `dllama.sched.*` span."""
    sched, launch, recorded = [], [], []
    for pl in planes:
        if trace_reduce.DEVICE_PLANE.match(pl["name"]):
            continue
        if pl.get("recorded"):
            recorded.append(pl["recorded"])
        for ln in pl["lines"]:
            for name, s, d, _ in ln["events"]:
                if name.startswith(SCHED):
                    sched.append((s, s + d, name[len(SCHED):]))
                elif name.startswith(LAUNCH):
                    launch.append((s, s + d, name[len(LAUNCH):]))
    devices = [pl for pl in planes
               if trace_reduce.DEVICE_PLANE.match(pl["name"])]
    if not devices or not sched:
        return None
    sched.sort()
    launch.sort()
    # where the host was recording: its TraceMe extent, or failing that (a
    # hand-built plane list) the extent of the program's own annotations
    h_lo = min([lo for lo, _ in recorded] or [sched[0][0]])
    h_hi = max([hi for _, hi in recorded] or [max(e for _, e, _ in sched)])
    sched_starts = [s for s, _, _ in sched]
    launch_starts = [s for s, _, _ in launch]
    by_state: dict = {}
    by_launch: dict = {}
    idle = window = outside = 0.0
    launches = commits = 0
    for pl in devices:
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        events = (lines.get(trace_reduce.OP_LINE)
                  or lines.get(trace_reduce.MODULE_LINE) or ())
        busy = [(s, s + d) for _, s, d, _ in events]
        if not busy:
            continue
        t_lo, t_hi = min(s for s, _ in busy), max(e for _, e in busy)
        window += (t_hi - t_lo) / 1e9
        launches += sum(1 for s, e, _ in launch if e > t_lo and s < t_hi)
        # a commit restamped at the capture's ends is several spans in a row
        commits += sum(1 for i, (s, e, label) in enumerate(sched)
                       if label == COMMIT and e > t_lo and s < t_hi
                       and (i == 0 or sched[i - 1][2] != COMMIT))
        for gs, ge in trace_reduce._gaps(busy):
            outside += (ge - gs) / 1e9
            gs, ge = max(gs, h_lo), min(ge, h_hi)
            if ge <= gs:
                continue
            outside -= (ge - gs) / 1e9
            idle += (ge - gs) / 1e9
            for label, ns in _laid_over(sched, sched_starts, gs, ge):
                by_state[label] = by_state.get(label, 0.0) + ns / 1e9
            for label, ns in _laid_over(launch, launch_starts, gs, ge):
                by_launch[label] = by_launch.get(label, 0.0) + ns / 1e9
    n = len(devices)
    by_state = {k: v / n for k, v in sorted(by_state.items())}
    by_launch = {k: v / n for k, v in sorted(by_launch.items())}
    idle, window = idle / n, window / n
    launches, commits = launches / n, commits / n
    covered = sum(by_state.values())
    host_work = sum(v for k, v in by_state.items() if k != IDLE)
    commit_idle = by_state.get(COMMIT, 0.0)
    return {"device_window_s": window,
            "host_recorded_s": (h_hi - h_lo) / 1e9,
            "outside_host_s": outside / n, "idle_s": idle,
            "by_state": by_state, "by_launch": by_launch,
            "uncovered_s": max(idle - covered, 0.0),
            "uncovered_share": (max(idle - covered, 0.0) / idle
                                if idle > 0 else 0.0),
            "host_work_s": host_work,
            "launches": launches, "commits": commits,
            "commit_idle_ms_per_commit": (1e3 * commit_idle / commits
                                          if commits else None),
            "steady_gap_ms_per_launch": (
                1e3 * (host_work - commit_idle) / launches
                if launches else None),
            "sched_spans": len(sched)}


def reduce(params: dict, run: dict):
    if not run.get("trace"):
        return None
    found = sorted(glob.glob(CAPTURES), key=os.path.getmtime)
    if not found:
        return None
    joined = join(read(found[-1]))
    if joined is None:
        return None
    print(json.dumps({"phase": "idle_by_state", **joined}), flush=True)
    if joined["launches"] <= 0:
        return None
    return 1e3 * joined["host_work_s"] / joined["launches"]
