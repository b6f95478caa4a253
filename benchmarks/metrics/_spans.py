"""Shared by the readers of the program's own spans: the window's spans
from the ring the program keeps in memory (pixie_tpu.trace.recent)."""
import sys


def window_spans(run: dict):
    """Spans that began between the first window query's send and the last
    one's end, oldest first; None where there is nothing to read: the
    program keeps no ring (a commit before it), the window has no query,
    or the ring let go of a span that may lie inside the window (a line on
    stderr says so)."""
    from pixie_tpu import trace

    recent = getattr(trace, "recent", None)
    queries = run["queries"]
    if recent is None or not queries:
        return None
    lo = min(q["t0_unix_ns"] for q in queries)
    hi = max(q["t0_unix_ns"] + int(q["wall_ms"] * 1e6) for q in queries)
    kept = recent()
    if trace.ring_dropped() and (not kept or kept[0].end_ns >= lo):
        print(f"metrics: the span ring let go of {trace.ring_dropped()} "
              "spans, some inside the window; its span metrics are left "
              "out", file=sys.stderr)
        return None
    return [s for s in kept if lo <= s.start_ns <= hi]


def ms(span) -> float:
    return span.duration_ns / 1e6


def chain_ms(run: dict, **attrs) -> list:
    """Durations, in ms, of the window's chain spans (the executor's spans
    that say which engine ran them) whose attributes have the given
    values; empty where there is nothing to read."""
    return [ms(s) for s in window_spans(run) or []
            if "engine" in s.attributes
            and all(s.attributes.get(k) == v for k, v in attrs.items())]
