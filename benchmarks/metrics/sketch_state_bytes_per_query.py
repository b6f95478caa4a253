"""Bytes of quantile-sketch state a service-map query reads back from the
device: the `sketch_state_bytes` counter of the chain spans of each of the
window's `service_edges` queries, summed a query, median over the queries
that have such a span.  Nothing to read where no chain span of a service-map
query carries the counter (a program that does not count it)."""
from _service_graph import service_graph_queries
from stats import median


def read(run):
    per_query = [sum(s.attributes["sketch_state_bytes"] for s in chains)
                 for _q, t in service_graph_queries(run) or []
                 if (chains := [s for s in t if "engine" in s.attributes
                                and "sketch_state_bytes" in s.attributes])]
    return float(median(per_query)) if per_query else None
