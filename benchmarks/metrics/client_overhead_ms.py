"""Median of client wall minus the broker's own wall for the same query:
the wire, framing and decode on both sides of the client's socket."""
from stats import median


def read(run):
    xs = [q["wall_ms"] - q["digest"]["broker_wall_ns"] / 1e6
          for q in run["queries"] if q["digest"]["broker_wall_ns"]]
    return median(xs) if xs else None
