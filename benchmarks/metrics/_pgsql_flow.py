"""Shared by the readers of the Postgres view's own layers
(`pgsql_flow_1chip`): the window's queries of the view's scripts with their
spans, by trace id, as `_service_graph.py` takes the service map's; the
per-query medians read the flow graph's alone, so that the per-pod chart's
spans (one query in four of `pgsql_view_rotation`) set none."""
from bisect import bisect_right

from _spans import window_spans

FLOW_GRAPH = "pgsql_flow_graph"
VIEW = (FLOW_GRAPH, "pgsql_pod_let")


def view_queries(run: dict, scripts=(FLOW_GRAPH,)):
    """[(the query's record, its spans)] for the window's queries of
    `scripts`: the spans of every trace whose first span of the window began
    while the client waited for that query (one closed-loop client: the
    waits do not overlap).  None where there is nothing to read: no such
    query in the window, or no span of one."""
    waits = sorted(((q["t0_unix_ns"], q["t0_unix_ns"] + int(q["wall_ms"] * 1e6),
                     q) for q in run["queries"] if q.get("script") in scripts),
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
