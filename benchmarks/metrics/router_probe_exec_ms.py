"""Mean wall of the router's probes inside the agent: the chain spans whose
routing decision was an explore, those the broker hedged away included."""
from _spans import chain_ms


def read(run):
    xs = chain_ms(run, source="explore")
    return sum(xs) / len(xs) if xs else None
