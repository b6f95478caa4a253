"""Median wall of the agent's `result_send` spans of the window's
`conn_flow_graph` queries: the encoding of a query's payloads (`bytes`, in
`chunks` frames: the flow graph's ~64k partial rows) and their sending to
the broker.  The widget's sends (110 rows) are not in it."""
from _flow_graph import flow_graph_queries
from _spans import ms
from stats import median


def read(run):
    xs = [ms(s) for _q, t in flow_graph_queries(run) or [] for s in t
          if s.name == "result_send"]
    return median(xs) if xs else None
