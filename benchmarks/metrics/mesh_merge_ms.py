"""What a query pays, on the host's clock, after its SPMD executions were
dispatched: the summed wall of its `mesh_merge` spans (the merge of the feeds'
replicated states in one more execution, and the read-back that waits for all
of them), median over the window's queries that have one.  A host-layer
reading: it holds the wait for the device's work as well as the merge's."""
from _mesh import by_query
from _spans import ms
from stats import median


def read(run):
    xs = [sum(ms(s) for s in spans)
          for spans in by_query(run, lambda s: s.name == "mesh_merge")]
    return median(xs) if xs else None
