"""What turning a Postgres flow graph's sketch states into quantiles costs
a query: the summed `sketch_finalize` spans (the broker's merger, over the
partial states the agent shipped) of each of the window's
`pgsql_flow_graph` queries, median over the queries that have one.
Nothing to read where no span of a flow graph is named so."""
from _pgsql_flow import view_queries
from _spans import ms
from stats import median


def read(run):
    per_query = [sum(ms(s) for s in fin)
                 for _q, t in view_queries(run) or []
                 if (fin := [s for s in t if s.name == "sketch_finalize"])]
    return median(per_query) if per_query else None
