"""Device busy time in the traced span over the device-routed queries any
part of which lies in it."""
from _common import touching_trace


def read(run):
    n = sum(q["digest"]["engine"] == "device" for q in touching_trace(run))
    if not n or not run["trace"]["busy_s"]:
        return None
    return run["trace"]["busy_s"] * 1e3 / n
