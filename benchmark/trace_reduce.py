"""From a profiler trace (`*.xplane.pb`) to device busy time, the device
operations that took most time, kernel time per flush, and the idle gaps
by what the host was doing in them.  Reads the file with
`jax.profiler.ProfileData` and nothing else; `tests/test_trace_reduce.py`
holds it to a small recorded trace.

Times inside a trace are nanoseconds from the trace's start.  The harness
writes `bench.mark` host annotations and notes `time.time_ns()` beside
each, which gives the offset between the trace clock and the wall clock,
so the program's flush spans (wall clock) can be laid on the trace.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the line of a device plane that holds one event per executed operation
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "bench.mark"


def load(path: str) -> list[dict]:
    """The trace as plain data: planes -> lines -> (name, start_ns,
    duration_ns) events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            lines.append({"name": ln.name, "events": [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in ln.events]})
        planes.append({"name": pl.name, "lines": lines})
    return planes


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length covered by [start, end) intervals (nested and
    overlapping ones counted once)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merged(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_ops(planes: list[dict]) -> dict[int, list[tuple]]:
    """device ordinal -> [(name, start_ns, end_ns)] of executed operations."""
    out = {}
    for pl in planes:
        m = DEVICE_PLANE.match(pl["name"])
        if not m:
            continue
        for ln in pl["lines"]:
            if ln["name"] == OPS_LINE:
                out[int(m.group(1))] = [(n, s, s + d)
                                        for n, s, d in ln["events"]]
    return out


def device_modules(planes: list[dict]) -> dict[int, list[tuple]]:
    """device ordinal -> [(program name, start_ns, end_ns)], one per
    executed program."""
    out = {}
    for pl in planes:
        m = DEVICE_PLANE.match(pl["name"])
        if not m:
            continue
        for ln in pl["lines"]:
            if ln["name"] == MODULES_LINE:
                out[int(m.group(1))] = [(n, s, s + d)
                                        for n, s, d in ln["events"]]
    return out


def clock_offset_ns(planes: list[dict], marks_wall_ns: list[int]) -> float:
    """wall_ns = trace_ns + offset: from the harness's `bench.mark`
    annotations, in the order they were written (median over the marks)."""
    found = sorted(s for pl in planes for ln in pl["lines"]
                   for n, s, _d in ln["events"] if n == MARK)
    if not found or len(found) != len(marks_wall_ns):
        raise ValueError(f"{len(found)} marks in the trace, "
                         f"{len(marks_wall_ns)} written")
    diffs = sorted(w - t for w, t in zip(sorted(marks_wall_ns), found))
    return diffs[len(diffs) // 2]


def reduce(planes: list[dict], window_ns: tuple[float, float],
           host_spans: list[tuple[str, float, float]],
           flush_windows: list[tuple[float, float]]) -> dict:
    """All on the trace clock (ns).  `window_ns`: the traced window.
    `host_spans`: (category, start, end) of what the host was doing, in
    priority order of their category where they overlap.  `flush_windows`:
    one (start, end) per flush in the window.

    busy_s: union of operation intervals per device, averaged over devices.
    kernel_ms_per_flush: per flush, union of the operations that started
    inside it (first device); ops_per_flush: their names (HLO text).
    """
    w0, w1 = window_ns
    ops = device_ops(planes)
    if not ops:
        return {"devices": 0, "busy_s": 0.0, "window_s": (w1 - w0) / 1e9,
                "device_ops": [], "idle_gaps": [], "kernel_ms_per_flush": [],
                "ops_per_flush": [], "programs": []}
    busy = []
    for _dev, evs in sorted(ops.items()):
        busy.append(union_length([(max(s, w0), min(e, w1)) for _n, s, e in evs
                                  if e > w0 and s < w1]) / 1e9)
    first = ops[min(ops)]
    totals: dict = {}
    for n, s, e in first:
        if e > w0 and s < w1:
            totals[n] = totals.get(n, 0.0) + (e - s) / 1e9
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    kernel_ms, kernel_ops = [], []
    for f0, f1 in flush_windows:
        mine = [(n, s, e) for n, s, e in first if f0 <= s < f1]
        kernel_ms.append(union_length([(s, e) for _n, s, e in mine]) / 1e6)
        kernel_ops.append([n for n, _s, _e in mine])
    # idle gaps of the first device, attributed to host activity
    gaps, cur = [], w0
    for s, e in merged([(s, e) for _n, s, e in first if e > w0 and s < w1]):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    by_cat: dict = {}
    for g0, g1 in gaps:
        left = [(g0, g1)]
        for cat, s, e in host_spans:
            nxt = []
            for a, b in left:
                lo, hi = max(a, s), min(b, e)
                if hi > lo:
                    by_cat[cat] = by_cat.get(cat, 0.0) + (hi - lo) / 1e9
                    if a < lo:
                        nxt.append((a, lo))
                    if hi < b:
                        nxt.append((hi, b))
                else:
                    nxt.append((a, b))
            left = nxt
        rest = sum(b - a for a, b in left) / 1e9
        if rest > 0:
            by_cat["waiting for the tick"] = by_cat.get(
                "waiting for the tick", 0.0) + rest
    mods = device_modules(planes).get(min(ops), [])
    progs: dict = {}
    for n, s, e in mods:
        if e > w0 and s < w1:
            c, t = progs.get(n, (0, 0.0))
            progs[n] = (c + 1, t + (e - s) / 1e9)
    return {
        "devices": len(ops),
        "busy_s": sum(busy) / len(busy),
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[n, t] for n, t in top],
        "idle_gaps": [[c, t] for c, t in sorted(
            by_cat.items(), key=lambda kv: -kv[1])[:10]],
        "kernel_ms_per_flush": kernel_ms,
        "ops_per_flush": kernel_ops,
        "programs": sorted(([n, c, t] for n, (c, t) in progs.items()),
                           key=lambda r: -r[2])[:10],
    }


def describe(planes: list[dict]) -> list[str]:
    """Plane and line names with event counts (to look at a trace by hand)."""
    out = []
    for pl in planes:
        for ln in pl["lines"]:
            out.append(f"{pl['name']} | {ln['name']} | {len(ln['events'])}")
    return out
