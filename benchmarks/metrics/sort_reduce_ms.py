"""What the sort-and-reduce phase of its sorted aggregates costs a
flow-graph query on the host's clock: the summed wall of the `sort_reduce`
spans (the dispatch of the two sorts and the run reduction, the kernels and
the wait for the group count, on whichever arm ran them; the agent's
aggregate and the broker's regroup both) of the window's `conn_flow_graph`
queries, over those queries.  A host-layer reading, not a kernel's: less
Python around the dispatch lowers it as a faster kernel does.  Nothing to
read in a window without a flow-graph query, or where no query of one has
such a span: a program that sorts on the host."""
from _flow_graph import flow_graph_queries
from _spans import ms


def read(run):
    queries = flow_graph_queries(run)
    if not queries:
        return None
    phases = [ms(s) for _q, t in queries for s in t if s.name == "sort_reduce"]
    return sum(phases) / len(queries) if phases else None
