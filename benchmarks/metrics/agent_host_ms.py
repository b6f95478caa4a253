"""The agent's self time in host Python, median over the window's
executions: the `exec` root's duration minus what its `readback_wave` and
`cpu_chain_wait` children cover (the waits for a device or for XLA-CPU)."""
from _spans import ms, window_spans
from stats import median
from tracered import union

WAITS = ("readback_wave", "cpu_chain_wait")


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    waits: dict = {}
    for s in spans:
        if s.name in WAITS:
            waits.setdefault(s.parent_span_id, []).append(
                (s.start_ns, s.end_ns))
    xs = [ms(s) - sum(e - b for b, e in union(waits.get(s.span_id, []))) / 1e6
          for s in spans if s.name == "exec"]
    return median(xs) if xs else None
