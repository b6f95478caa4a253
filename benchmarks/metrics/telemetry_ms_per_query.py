"""What the agent's self-telemetry writes cost a query: the summed wall of
`telemetry_flush` (the query's own spans, written before its ack) and
`telemetry_write` (the broker's spans and flight-recorder rows, written
beside the next query) over the window's queries."""
from _spans import ms, window_spans

WRITES = ("telemetry_flush", "telemetry_write")


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    return sum(ms(s) for s in spans if s.name in WRITES) / len(run["queries"])
