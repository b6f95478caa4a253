"""What turning the service map's sketch state into quantiles costs a
query: the summed `sketch_finalize` spans (the finalize of the sketch
columns wherever it runs: the broker's merger after the agent shipped its
partial states) of each of the window's `service_edges` queries, median
over the queries that have one.  Nothing to read where no span of a
service-map query is named so (a program without the span)."""
from _service_graph import service_graph_queries
from _spans import ms
from stats import median


def read(run):
    per_query = [sum(ms(s) for s in fin)
                 for _q, t in service_graph_queries(run) or []
                 if (fin := [s for s in t if s.name == "sketch_finalize"])]
    return median(per_query) if per_query else None
