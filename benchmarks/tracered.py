"""The benchmark's own reduction of a jax profiler trace (.xplane.pb, read
with jax.profiler.ProfileData): the seconds in which an operation ran on
each device, the operations that took most time, and the longest idle gaps
named by what the host was doing (the program's pixie_tpu/trace.py spans,
put on the trace's clock by one marker annotation).

Device planes are named "/device:TPU:<n>"; their "XLA Ops" line holds one
event per executed operation.  A `while` event spans the operations of its
body, so busy time is the union of intervals, and `device_ops` gives each
name's summed durations as measured (an enclosing op counts its body too).
"""
from __future__ import annotations

import bisect
import glob
import os

CLOCK_MARK = "bench.clock_sync"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_planes(path: str) -> dict:
    """{"devices": {plane: [(start_ns, end_ns, name)]}, "marks": [...]}
    from one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, marks, layout = {}, [], []
    names: dict = {}  # a few hundred distinct ops among millions of events
    for plane in pd.planes:
        lines = list(plane.lines)
        layout.append((plane.name, [ln.name for ln in lines]))
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for ln in lines:
                if ln.name == OPS_LINE:
                    for e in ln.events:
                        raw = e.name
                        name = names.get(raw)
                        if name is None:
                            name = names[raw] = op_name(raw)
                        start = int(e.start_ns)
                        evs.append((start, start + int(e.duration_ns), name))
            devices[plane.name] = evs
        if not plane.name.startswith("/device:"):
            for ln in lines:
                for e in ln.events:
                    if e.name.startswith(CLOCK_MARK):
                        marks.append((int(e.start_ns), e.name))
    return {"devices": devices, "marks": marks, "layout": layout}


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO line ("%while.21 = (...) while(
    ...)"); the instruction's own name is what stands before " = "."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:80]


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce_trace(planes: dict, lo_ns: int, hi_ns: int,
                 host_spans: list | None = None, n_devices: int = 1) -> dict:
    """Busy seconds (mean over devices), top ops and idle gaps inside
    [lo_ns, hi_ns) on the trace's clock.  `host_spans` are (start_ns,
    end_ns, name) on the same clock; a gap takes the name of the shortest
    span that covers its middle.

    The profiler writes a device's plane only if an operation ran on it
    while it traced.  Of the cell's `n_devices`, one without a plane was
    idle for the whole span and counts 0 in the mean; with no plane at all
    busy is 0, `device_ops` is empty and the span is one gap.  Whether that
    is a window served off the chip or a broken trace, the caller knows
    (run.TailTrace.reduce): this function only reads what is there."""
    busy_s, ops, merged_all = [], {}, []
    for evs in planes["devices"].values():
        inside = [(s, e, n) for s, e, n in evs if e > lo_ns and s < hi_ns]
        merged = union(clip([(s, e) for s, e, _ in inside], lo_ns, hi_ns))
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        merged_all.append(merged)
        for s, e, n in inside:
            ops[n] = ops.get(n, 0.0) + (min(e, hi_ns) - max(s, lo_ns)) / 1e9
    # idle gaps of the first device (one chip: the only one)
    gaps, t = [], lo_ns
    for s, e in merged_all[0] if merged_all else []:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi_ns > t:
        gaps.append((t, hi_ns))
    # the host's timeline: between two span boundaries, the shortest span
    # that covers the stretch (longer spans are painted first)
    spans = host_spans or []
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    owner = ["outside_any_span"] * (len(cuts) + 1)
    for s, e, n in sorted(spans, key=lambda x: x[0] - x[1]):
        for i in range(bisect.bisect_right(cuts, s),
                       bisect.bisect_left(cuts, e) + 1):
            owner[i] = n

    # a long gap takes the name of the stretch its middle lies in; the
    # totals give every gap's pieces to the stretches they fall in
    named, totals = [], {}
    for a, b in gaps:
        named.append((owner[bisect.bisect_right(cuts, (a + b) // 2)],
                      (b - a) / 1e9))
        t = a
        for i in range(bisect.bisect_right(cuts, a),
                       bisect.bisect_left(cuts, b) + 1):
            end = min(b, cuts[i]) if i < len(cuts) else b
            totals[owner[i]] = totals.get(owner[i], 0.0) + (end - t) / 1e9
            t = end
    longest = sorted(named, key=lambda x: -x[1])[:TOP // 2]
    by_name = sorted(totals.items(), key=lambda x: -x[1])[:TOP - len(longest)]
    return {
        "busy_s": sum(busy_s) / max(n_devices, len(busy_s)),
        "window_s": (hi_ns - lo_ns) / 1e9,
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda x: -x[1])[:TOP]],
        "idle_gaps": ([[n, s] for n, s in longest]
                      + [["total:" + n, s] for n, s in by_name]),
    }
