"""Share of the window that the agent spent in router probes: the summed
wall of the chain spans with source=explore over the window's length."""
from _spans import chain_ms, window_spans


def read(run):
    if window_spans(run) is None or not run["window_s"]:
        return None
    return 100.0 * sum(chain_ms(run, source="explore")) / (
        run["window_s"] * 1e3)
