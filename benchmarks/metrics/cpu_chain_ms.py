"""Median wall of the chains the executor pinned to XLA-CPU: the chain
spans with engine=xla_cpu_chain."""
from _spans import chain_ms
from stats import median


def read(run):
    xs = chain_ms(run, engine="xla_cpu_chain")
    return median(xs) if xs else None
