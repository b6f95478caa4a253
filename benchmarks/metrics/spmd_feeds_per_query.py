"""SPMD executions a query: the `spmd_feeds` of a query's aggregate chain
spans (one for every feed the chain dispatched over the mesh) summed, median
over the window's queries that have such a span.  Each is a program of its
own and a state to merge afterwards; one resident feed a table reads 1."""
from _mesh import by_query
from stats import median


def read(run):
    xs = [sum(s.attributes["spmd_feeds"] for s in spans)
          for spans in by_query(run, lambda s: "spmd_feeds" in s.attributes)]
    return median(xs) if xs else None
