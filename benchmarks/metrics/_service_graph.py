"""Shared by the readers of the service map's own layers: the spans of the
window's `service_edges` queries alone, by trace id, as `_flow_graph.py`
takes the flow graph's, so that the status table's spans (one query in four
of `service_map_rotation`) set no median."""
from bisect import bisect_right

from _spans import window_spans

SCRIPT = "service_edges"


def service_graph_queries(run: dict):
    """[(the query's record, its spans)] for the window's `service_edges`
    queries: the spans of every trace whose first span of the window began
    while the client waited for that query (one closed-loop client: the
    waits do not overlap).  None where there is nothing to read: no such
    query in the window, or no span of one."""
    waits = sorted(((q["t0_unix_ns"], q["t0_unix_ns"] + int(q["wall_ms"] * 1e6),
                     q) for q in run["queries"] if q.get("script") == SCRIPT),
                   key=lambda w: w[0])
    spans = window_spans(run) if waits else None
    if not spans:
        return None
    traces: dict = {}
    for s in spans:
        traces.setdefault(s.trace_id, []).append(s)
    starts = [w[0] for w in waits]
    mine: dict = {}
    for trace in traces.values():
        t0 = min(s.start_ns for s in trace)
        i = bisect_right(starts, t0) - 1
        if i >= 0 and t0 <= waits[i][1]:
            mine.setdefault(i, []).extend(trace)
    return [(waits[i][2], mine[i]) for i in sorted(mine)] or None
