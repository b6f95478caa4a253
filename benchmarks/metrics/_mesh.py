"""Shared by the readers of a mesh agent's layers: the window's spans a
query.  One trace is one query as the broker sent it to the agent (a hedged
duplicate is a second trace of the same query, and reads as a query of its
own)."""
from _spans import window_spans


def by_query(run: dict, keep) -> list:
    """[[span, ...]], a list for every trace of the window in which `keep`
    picks a span, the picked spans in it; empty where there is nothing to
    read (no ring, no query, or a program whose spans lack what `keep` looks
    for: a commit before the mesh said anything on its spans)."""
    traces: dict = {}
    for s in window_spans(run) or []:
        if keep(s):
            traces.setdefault(s.trace_id, []).append(s)
    return list(traces.values())
