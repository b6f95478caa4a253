"""Share of the window's routed chains that ran under the tail guard's
hold-off: the chain spans with source=fallback over those that carry a
routing decision at all.  Anything but 0 means the guard tripped."""
from _spans import window_spans


def read(run):
    routed = [s for s in window_spans(run) or []
              if "engine" in s.attributes and "source" in s.attributes]
    if not routed:
        return None
    return 100.0 * sum(s.attributes["source"] == "fallback"
                       for s in routed) / len(routed)
