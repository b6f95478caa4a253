"""Median of phases.exec_ns: dispatch to the agent until its last chunk."""
from stats import median


def read(run):
    xs = [q["digest"]["exec_ns"] / 1e6 for q in run["queries"]
          if q["digest"]["exec_ns"]]
    return median(xs) if xs else None
