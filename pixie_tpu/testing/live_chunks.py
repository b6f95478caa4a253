"""Fixtures of the live-chunk tests (tests/test_bringup.py,
tests/test_sketch_kernels.py): the masks whose live rows sit in one run of a
chunked feed, and the loop over every chunk that `ops.groupby.scan_sum`
replaced, kept as the reference its sums must equal bit for bit.
"""
from __future__ import annotations

import jax
import numpy as np

#: where the live rows sit, as (first, past-the-last) row of a feed of `c`
#: chunks of `ch` rows: the edges of a chunk, both ends of the feed, nothing
LIVE_RANGES = {
    "empty": lambda c, ch: (0, 0),
    "one_row": lambda c, ch: (c * ch // 2 + 7, c * ch // 2 + 8),
    "prefix_to_ch-1": lambda c, ch: (0, ch - 1),
    "prefix_to_ch": lambda c, ch: (0, ch),
    "prefix_to_ch+1": lambda c, ch: (0, ch + 1),
    "suffix": lambda c, ch: (c * ch - ch - 3, c * ch),
    "middle": lambda c, ch: (2 * ch + 5, (c - 3) * ch - 9),
    "whole": lambda c, ch: (0, c * ch),
}


def live_mask(name: str, c: int, ch: int, seed: int = 0) -> np.ndarray:
    """bool[c * ch]: nine rows in ten of LIVE_RANGES[name] live (its first
    and last always, so the range is the mask's own), no other."""
    a, b = LIVE_RANGES[name](c, ch)
    at = np.arange(c * ch)
    keep = np.random.default_rng(seed).random(c * ch) < 0.9
    return (at >= a) & (at < b) & (keep | (at == a) | (at == b - 1))


def scan_every_chunk(fn, xs, lo, hi):
    """`scan_sum` as it was before it took a range: one `lax.scan` over
    every chunk, seeded with fn of the first."""
    first = jax.tree.map(lambda a: a[0], xs)
    rest = jax.tree.map(lambda a: a[1:], xs)
    out, _ = jax.lax.scan(lambda acc, x: (acc + fn(x), None), fn(first), rest)
    return out
