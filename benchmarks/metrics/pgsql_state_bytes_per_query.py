"""Bytes of quantile-sketch state a Postgres flow graph reads back from the
device: the `sketch_state_bytes` counter of the chain spans of each of the
window's `pgsql_flow_graph` queries (its first aggregate is the sorted one),
summed a query, median over the queries that have such a span.  Nothing to
read where no chain span of a flow graph carries the counter (a program
whose sorted aggregate does not count it)."""
from _pgsql_flow import view_queries
from stats import median


def read(run):
    per_query = [sum(s.attributes["sketch_state_bytes"] for s in chains)
                 for _q, t in view_queries(run) or []
                 if (chains := [s for s in t if "engine" in s.attributes
                                and "sketch_state_bytes" in s.attributes])]
    return float(median(per_query)) if per_query else None
