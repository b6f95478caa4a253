"""Rows in the time ranges of all completed queries over the window: the
whole served path's rate, probes and host time included.  Decides nothing
(PR 22: it inherits the host noise of the router's probes)."""
from _common import rows_in_range


def read(run):
    if not run["queries"] or not run["window_s"]:
        return None
    return sum(rows_in_range(run, q) for q in run["queries"]) / run["window_s"]
