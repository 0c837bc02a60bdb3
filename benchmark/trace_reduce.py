"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

    reduce_file(path) -> {
      "window_s":  first to last event of the device planes. (The host
                   planes run on for about half a second after the device
                   plane's last event while the capture is being stopped;
                   counting that would read as idle time that never was.
                   Without a device plane: first to last event of any plane.)
      "busy_s":    union of the intervals in which an XLA op ran on a device
                   plane, averaged over the device planes,
      "modules":   {XLA module name: [device seconds of each execution]},
      "ops":       [{"module", "name", "group", "count", "seconds", "hlo"}]
                   device ops by call site: `module` is the XLA module whose
                   execution holds the op's start (instruction names are
                   numbered per module: `_blockdot_call.71` is a layer's
                   matmul in one program and the head in another), `name`
                   the HLO instruction's name (`_blockdot_call.75`), `group`
                   that name without its number (`_blockdot_call`: every Q40
                   matmul call), `seconds` the op's SELF time (a `while`
                   holds its body's ops on the same line; what they cover is
                   taken out of it), `hlo` the instruction's text with its
                   shapes, one per (module, name),
      "device_ops": [[group, self seconds]] the ten groups that took most,
      "idle_gaps":  [[label, seconds]] the ten labels with most idle time; a
                   gap is labelled by the module the device ran next (what
                   the host was busy launching), best effort: the host's
                   and the device's clocks are not joined yet,
      "planes":    a short listing, for reading a trace by hand }

Reads with `jax.profiler.ProfileData` and nothing else; importing it touches
no backend. `python benchmark/trace_reduce.py FILE` prints the reduction;
`... FILE --dump OUT.json.gz` keeps a small recorded copy for the tests.
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
_RUN_ID = re.compile(r"\(\d+\)$")
_NUMBER = re.compile(r"(\.\d+)?(\.(rem|clone)[.\w]*)?$")


def _self_times(events: list) -> list:
    """[name, start ns, self ns] per event of one line, where an event that
    lies inside another (a loop's body inside the loop) is taken out of it."""
    out, stack = [], []  # stack of [end, index into out]
    for n, s, d, _ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= d
        out.append([n, s, d])
        stack.append([s + d, len(out) - 1])
    return out


def _module_at(mods: list, starts: list, t: int) -> str:
    """The module (of `mods`, sorted (start, end, name)) whose execution
    holds the instant t; "" where none does."""
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][2] if i >= 0 and t < mods[i][1] else ""


def _union(intervals: list) -> float:
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _gaps(intervals: list):
    """(start, end) of the idle stretches between busy intervals."""
    end = None
    for s, e in sorted(intervals):
        if end is not None and s > end:
            yield end, s
        end = e if end is None else max(end, e)


def reduce_planes(planes: list) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns, stats dict)]}]}] — the shape `load` gives."""
    t_lo, t_hi = None, None
    listing = []
    for pl in planes:
        for ln in pl["lines"]:
            listing.append([pl["name"], ln["name"], len(ln["events"])])
            for _, s, d, _ in ln["events"]:
                t_lo = s if t_lo is None else min(t_lo, s)
                t_hi = s + d if t_hi is None else max(t_hi, s + d)
    devices = [pl for pl in planes if DEVICE_PLANE.match(pl["name"])]
    spans = [(s, s + d) for pl in devices for ln in pl["lines"]
             for _, s, d, _ in ln["events"]]
    if spans:
        t_lo, t_hi = min(s for s, _ in spans), max(e for _, e in spans)
    modules: dict = {}
    ops: dict = {}
    busy, gap_by_label = [], {}
    for pl in devices:
        lines = {ln["name"]: ln["events"] for ln in pl["lines"]}
        mods = sorted((s, s + d, _RUN_ID.sub("", n))
                      for n, s, d, _ in lines.get(MODULE_LINE, ()))
        for s, e, n in mods:
            modules.setdefault(n, []).append((e - s) / 1e9)
        starts = [s for s, _, _ in mods]
        op_iv = [(s, s + d) for _, s, d, _ in lines.get(OP_LINE, ())]
        for hlo, start, self_ns in _self_times(lines.get(OP_LINE, ())):
            name = hlo.split(" = ", 1)[0].lstrip("%")
            module = _module_at(mods, starts, start)
            o = ops.setdefault((module, name),
                               {"module": module, "name": name,
                                "group": _NUMBER.sub("", name),
                                "count": 0, "seconds": 0.0,
                                "hlo": hlo[:1200]})
            o["count"] += 1
            o["seconds"] += max(self_ns, 0) / 1e9
        iv = op_iv or [(s, e) for s, e, _ in mods]
        busy.append(_union(iv) / 1e9)
        for gs, ge in _gaps(iv):
            nxt = next((n for s, _, n in mods if s >= gs), "end of trace")
            label = f"before {nxt}"
            gap_by_label[label] = gap_by_label.get(label, 0.0) + (ge - gs) / 1e9
        if iv and t_lo is not None:
            first, last = min(s for s, _ in iv), max(e for _, e in iv)
            gap_by_label["trace start"] = (gap_by_label.get("trace start", 0.0)
                                           + (first - t_lo) / 1e9)
            gap_by_label["trace end"] = (gap_by_label.get("trace end", 0.0)
                                         + (t_hi - last) / 1e9)
    op_list = sorted(ops.values(), key=lambda o: -o["seconds"])
    groups: dict = {}
    for o in op_list:
        groups[o["group"]] = groups.get(o["group"], 0.0) + o["seconds"]
    return {
        "window_s": 0.0 if t_lo is None else (t_hi - t_lo) / 1e9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "device_planes": len(devices),
        "modules": modules,
        "ops": op_list,
        "device_ops": [[k, v] for k, v in sorted(groups.items(),
                                                 key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v] for k, v in sorted(gap_by_label.items(),
                                                key=lambda kv: -kv[1])[:10]],
        "planes": listing[:40],
    }


def load(path: str) -> list:
    """An `.xplane.pb` (or a `.json.gz` written by `dump`) as plain lists.
    Host planes keep only each line's first and last event: they are read
    for the capture's extent alone."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(pl.name))
        lines = []
        for ln in pl.lines:
            if device and ln.name not in (MODULE_LINE, OP_LINE):
                continue
            evs = [(e.name, int(e.start_ns), int(e.duration_ns),
                    {k: v for k, v in e.stats
                     if isinstance(v, (int, float, str))} if device else {})
                   for e in ln.events]
            if not device and len(evs) > 2:
                evs = [min(evs, key=lambda e: e[1]),
                       max(evs, key=lambda e: e[1] + e[2])]
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def dump(planes: list, path: str, max_events: int = 4000) -> None:
    """A small recorded trace for the tests: the first `max_events` events
    of each line, as gzip'd JSON."""
    small = [{"name": pl["name"],
              "lines": [{"name": ln["name"],
                         "events": sorted(ln["events"], key=lambda e: e[1])[:max_events]}
                        for ln in pl["lines"]]} for pl in planes]
    with gzip.open(path, "wt") as f:
        json.dump(small, f)


def reduce_file(path: str) -> dict:
    return reduce_planes(load(path))


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[2] == "--dump":  # FILE --dump OUT.json.gz
        dump(load(sys.argv[1]), sys.argv[3])
        sys.exit(0)
    out = reduce_file(sys.argv[1])
    out["modules"] = {k: [len(v), sum(v)] for k, v in out["modules"].items()}
    out["ops"] = out["ops"][:40]
    print(json.dumps(out, indent=1))
