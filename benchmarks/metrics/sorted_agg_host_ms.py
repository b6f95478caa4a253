"""What the host-side phase of a sorted aggregate costs a flow-graph query:
the summed wall of the `key_decode` spans (the groups' key codes turned into
the values that go on the wire) of the window's `conn_flow_graph` queries,
over those queries.  The sorts and the run reductions are device phases
(`sort_reduce`, `compact_readback`) and are not in it.  Nothing to read in a
window without a flow-graph query, or where no chain span of one says how
many groups a sorted aggregate gave (`groups_out`): a program that sorts on
the host under no span."""
from _flow_graph import flow_graph_queries, sorted_chains
from _spans import ms


def read(run):
    queries = flow_graph_queries(run)
    if not queries or not any(sorted_chains(t) for _q, t in queries):
        return None
    return (sum(ms(s) for _q, t in queries for s in t
                if s.name == "key_decode") / len(queries))
