"""The service map's quantile-sketch update past the dense kernel's group
cap, as a share of the least time the chip's memory could take for it.

Time: the seconds, in the traced span's `device_ops` (entries summed by
name), of every device op of the update (ops/sketch.py `_update_tiles`,
scope `px.sketch_update_tiles`): the values-only sort of its packed key,
which XLA names `sort.<n>` (no other sort runs in this cell's programs),
and the `px_sketch_tiles` kernel.  Need: the bytes the update must move at
the HBM rate (peaks.json), the columns it reads (`latency`, `upid`,
`remote_addr`, the widths the configuration's file gives) a row in range
and its [G, 544] f32 state once (G the chain span's `groups`), for each of
the window's `service_edges` queries whose device-arm chain names `tiles`
in its `sketch_form`, counted by the share of that chain span which lies
inside the traced span, as the device seconds are.  Nothing to read where
the kernel is not among `device_ops` (a program that updates the sketch
otherwise) or no such chain overlaps the span."""
from _common import rows_in_range
from _service_graph import service_graph_queries
from data import column_bytes

KERNEL = "px_sketch_tiles"
SORT = "sort"
#: the chain span's `sketch_form` where the update is this kernel's
KIND = "tiles"
COLUMNS = ["latency", "upid", "remote_addr"]
#: the rows of the [544, G] f32 state the kernel writes
STATE_ROWS = 544


def need_bytes(config: dict, rows: int, groups: int) -> int:
    """Bytes one update of `rows` rows into `groups` histograms must move:
    its columns read once, its state written once."""
    return (rows * column_bytes(config, "http_events", COLUMNS)
            + groups * STATE_ROWS * 4)


def _inside(span, lo: int, hi: int) -> float:
    """The share of `span` that lies in [lo, hi)."""
    dur = span.end_ns - span.start_ns
    if dur <= 0:
        return float(lo <= span.start_ns < hi)
    return max(0, min(span.end_ns, hi) - max(span.start_ns, lo)) / dur


def read(run):
    t = run["trace"]
    if not t or not t["busy_s"]:
        return None
    ops = dict(t["device_ops"])
    if not any(n.startswith(KERNEL) for n in ops):
        return None
    secs = sum(s for n, s in ops.items()
               if n.startswith(KERNEL) or n.startswith(SORT))
    nbytes = 0.0
    for q, spans in service_graph_queries(run) or []:
        chains = [s for s in spans if s.attributes.get("arm") == "device"
                  and KIND in s.attributes.get("sketch_form", "").split("+")]
        if chains:
            share = max(_inside(s, t["lo_unix_ns"], t["hi_unix_ns"])
                        for s in chains)
            groups = max(s.attributes["groups"] for s in chains)
            nbytes += share * need_bytes(run["config"], rows_in_range(run, q),
                                         groups)
    if not nbytes:
        return None
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / secs
