"""Mean bytes read back from the device per device-routed flow-graph query:
the `d2h_bytes` counter on the sorted aggregates' chain spans that ran on
the device arm, summed over the window's `conn_flow_graph` queries and
divided by the queries that have such a span.  Nothing to read
where no chain span of a flow-graph query carries the counter."""
from _flow_graph import flow_graph_queries, sorted_chains


def read(run):
    per_query = [sum(c.attributes["d2h_bytes"] for c in chains)
                 for _q, t in flow_graph_queries(run) or []
                 if (chains := sorted_chains(t, arm="device"))]
    return sum(per_query) / len(per_query) if per_query else None
