"""How evenly the mesh's shards were loaded: the median, over the window's
aggregate chain spans a mesh served, of their `shard_skew`, the most valid
rows a shard was handed over the mean, summed over the chain's feeds.  1.0 is
even; the slowest shard sets a collective's time.  Nothing to read where no
chain span carries it (one chip, or a program whose spans do not say)."""
from _spans import window_spans
from stats import median


def read(run):
    xs = [s.attributes["shard_skew"] for s in window_spans(run) or []
          if "engine" in s.attributes and "shard_skew" in s.attributes]
    return median(xs) if xs else None
