"""99th percentile of all the window's queries: what the router's probes
cost the user.  Per-layer because its run-to-run spread (27% on the chip, PR
25) admits no bound: it sits on the edge between two modes of the probes."""
from stats import percentile


def read(run):
    return percentile(run["walls_ms"], 0.99) if run["walls_ms"] else None
