"""Shared by the readers of the flow graph's own layers: the spans of the
window's `conn_flow_graph` queries alone, by trace id, so that the widget's
spans (three queries in four of `node_net_rotation`) set no median."""
from bisect import bisect_right

from _spans import window_spans

SCRIPT = "conn_flow_graph"


def flow_graph_queries(run: dict):
    """[(the query's record, its spans)] for the window's `conn_flow_graph`
    queries: the spans of every trace whose first span of the window began
    while the client waited for that query (one closed-loop client: the
    waits do not overlap; a span that trails its query, as a telemetry
    write does, keeps its query's trace id; a hedged duplicate is a second
    trace of the same query).  None where there is nothing to read: no such
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


def sorted_chains(spans: list, **attrs) -> list:
    """The chain spans of sorted aggregates among `spans` (they say how
    many groups came out) whose attributes have the given values."""
    return [s for s in spans if "groups_out" in s.attributes
            and all(s.attributes.get(k) == v for k, v in attrs.items())]
