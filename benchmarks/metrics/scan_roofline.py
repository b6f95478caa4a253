"""The least time the chip's memory could take to read what the traced
span's device-routed queries need, over the time the device was busy.

Bytes are the query's own: the widths of the columns its script reads (the
configuration's file) times the rows in its time range, whatever kernel
implements it.  Bound: HBM bytes/s of peaks.json (the scans do no work the
MXU peak would bound first)."""
from _common import rows_in_range, touching_trace
from data import column_bytes


def read(run):
    t = run["trace"]
    qs = [q for q in touching_trace(run) if q["digest"]["engine"] == "device"]
    if not qs or not t["busy_s"]:
        return None
    need = 0
    for q in qs:
        s = run["scripts"][q["script"]]
        need += (column_bytes(run["config"], s["table"], s["columns_read"])
                 * rows_in_range(run, q))
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / t["busy_s"]
