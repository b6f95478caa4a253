"""The Postgres view's quantile-sketch updates in the tiles kernel, as a
share of the least time the chip's memory could take for them.

Time: the seconds, in the traced span's `device_ops` (entries summed by
name), of the `px_sketch_tiles` kernel (ops/sketch.py `_update_tiles`).
Need: the bytes the update must move at the HBM rate (peaks.json): for
each row in range its `latency` (the width the configuration's file gives)
and its 4-byte group or run id, and the [G, 544] f32 state once, for every
device-arm chain of the window's `pgsql_flow_graph` and `pgsql_pod_let`
queries whose `sketch_form` names `tiles`, counted by the share of that
chain span which lies inside the traced span, as the device seconds are.
G is the dense chain's `groups`, or the runs' slots of a sorted aggregate
(the next power of two over its `groups_out`).  Nothing to read where the
kernel is not among `device_ops` or no such chain overlaps the span."""
from _common import rows_in_range
from _pgsql_flow import VIEW, view_queries
from data import column_bytes
from sketch_kernel_roofline import KERNEL, STATE_ROWS, _inside

KIND = "tiles"
#: the int32 group id (dense) or run id (sorted) a row carries into the
#: update
KEY_BYTES = 4


def state_groups(span) -> int:
    """The histograms the chain's update writes."""
    if "groups" in span.attributes:
        return int(span.attributes["groups"])
    return 1 << max(int(span.attributes["groups_out"]) - 1, 0).bit_length()


def need_bytes(config: dict, rows: int, groups: int) -> int:
    """Bytes one update of `rows` rows into `groups` histograms must move:
    each row's value and key read once, the state written once."""
    return (rows * (column_bytes(config, "pgsql_events", ["latency"])
                    + KEY_BYTES)
            + groups * STATE_ROWS * 4)


def read(run):
    t = run["trace"]
    if not t or not t["busy_s"]:
        return None
    secs = sum(s for n, s in dict(t["device_ops"]).items()
               if n.startswith(KERNEL))
    if not secs:
        return None
    nbytes = 0.0
    for q, spans in view_queries(run, VIEW) or []:
        for s in spans:
            if (s.attributes.get("arm") == "device"
                    and KIND in s.attributes.get("sketch_form", "").split("+")):
                nbytes += (_inside(s, t["lo_unix_ns"], t["hi_unix_ns"])
                           * need_bytes(run["config"], rows_in_range(run, q),
                                        state_groups(s)))
    if not nbytes:
        return None
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / secs
