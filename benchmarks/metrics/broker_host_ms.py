"""Median of the broker's host phases: PxL compile + plan split + merge."""
from stats import median


def read(run):
    xs = [(d["compile_ns"] + d["plan_split_ns"] + d["merge_ns"]) / 1e6
          for d in (q["digest"] for q in run["queries"]) if d["exec_ns"]]
    return median(xs) if xs else None
