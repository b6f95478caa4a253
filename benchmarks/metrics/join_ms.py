"""Median wall of the plan's join inside the window: the `join` spans of
whichever service ran that half of the plan (the executor's blocking frame
around `_run_join`)."""
from _spans import ms, window_spans
from stats import median


def read(run):
    xs = [ms(s) for s in window_spans(run) or [] if s.name == "join"]
    return median(xs) if xs else None
