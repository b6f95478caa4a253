"""How often the router changed its mind inside the window: over the chain
spans that are not explores, in the order they began and within one service
and model key, (`plan_class`, `size_bucket`): a query may route a large and a
small input, and two scripts' chains of one size, each under its own key.  The
count of consecutive pairs whose `arm` differs.  A span without a
`plan_class` (a commit before PR 30) groups by service and bucket alone."""
from _spans import window_spans


def read(run):
    spans = window_spans(run)
    if spans is None:
        return None
    last: dict = {}
    flips = seen = 0
    for s in sorted(spans, key=lambda s: s.start_ns):
        a = s.attributes
        if "engine" not in a or "arm" not in a or a.get("source") == "explore":
            continue
        key = (s.service, a.get("plan_class"), a.get("size_bucket"))
        seen += 1
        if key in last and last[key] != a["arm"]:
            flips += 1
        last[key] = a["arm"]
    return float(flips) if seen else None
