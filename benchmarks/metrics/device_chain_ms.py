"""Median wall of the chains that ran on the accelerator route: the chain
spans with engine=device_chain, dispatch to pulled state."""
from _spans import chain_ms
from stats import median


def read(run):
    xs = chain_ms(run, engine="device_chain")
    return median(xs) if xs else None
