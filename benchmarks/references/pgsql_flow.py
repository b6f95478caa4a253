"""Plain reference for Pixie's Postgres view over pgsql_events: the flow
graph (`pgsql_flow_graph`, px/pgsql_flow_graph) and the per-pod chart
(`pgsql_pod_let`, px/pgsql_stats `pod_pgsql_let`).

Of the rows at or after the query's own start_time: the pod is the row's
process's (pod i's one process, `upid` code i), the namespace the
configuration's; an address resolves to its pod where it is one of the
cluster's pod IPs (pod i is at 10.0.<i/250>.<i%250 + 1>, as the node's
metadata lays them out) and stays the address otherwise; a server-side row
(`trace_role == 2`) runs from the address to the pod, a client-side one from
the pod to the address.  Each 10 s window (the row's time floored to a
multiple of 10 s) of a group gets its count, its request_throughput (the
count over the window's 10^10 ns, float64) and its p50, p90 and p99 of
`latency` as exact order statistics at the sketch's rank (the ceil(q*n)-th
smallest), floored.  The flow graph groups by (window, source, destination,
is_source_pod_type, is_dest_pod_type, namespace), then averages the three
quantiles and request_throughput over an edge's windows and sums its
counts; the chart groups by (window, destination) over the rows that have a
pod.  Imports nothing of the program.

Four stand-ins for the program, each the reference with one thing lowered
(`stand_in`): "coarse_sketch" answers each per-window quantile as a log
sketch of a quarter of the bins would (bins four times as wide);
"unwindowed" drops the 10 s bin (one window an edge or a destination, over
the query's whole range); "source_unresolved" leaves a server-side row's
source the address, as a lookup that resolves nothing would; "f32_rates"
works request_throughput out in float32, the step below the float64 the
configuration states.
"""
from __future__ import annotations

import math

import numpy as np
import pandas as pd

#: what stands in the program's place as the control
CONTROLS = ("coarse_sketch", "unwindowed", "source_unresolved", "f32_rates")

#: the sketch the configuration states (ops/sketch.py: gamma 1.0404, a value
#: answered as the geometric middle of its bin), and the coarse stand-in's
GAMMA = 1.0404
COARSE_GAMMA = GAMMA ** 4
QUANTILES = {"latency_p50": 0.50, "latency_p90": 0.90, "latency_p99": 0.99}
WINDOW_NS = 10 * 1_000_000_000
EDGE = ["source", "destination", "is_source_pod_type", "is_dest_pod_type",
        "namespace"]
CHART = ["time_", "destination"]


def _addresses(config: dict) -> list:
    spec = next(t for t in config["tables"] if t["name"] == "pgsql_events")
    return next(c for c in spec["columns"]
                if c["name"] == "remote_addr")["values"]


def _pod_of_address(addr: str, pods: int) -> int:
    """The pod at `addr` in the metadata's layout, or -1."""
    parts = addr.split(".")
    if len(parts) != 4 or parts[:2] != ["10", "0"]:
        return -1
    i = int(parts[2]) * 250 + int(parts[3]) - 1
    return i if 0 <= i < pods and int(parts[3]) <= 250 else -1


def _windows(frame: pd.DataFrame, keys: list, lat: np.ndarray,
             stand_in: str) -> pd.DataFrame:
    """One row a group of `keys`: its count, request_throughput and floored
    exact quantiles (or the coarse sketch's)."""
    codes = frame.groupby(keys, sort=True).ngroup().to_numpy()
    order = np.lexsort((lat, codes))
    lat_s, codes_s = lat[order], codes[order]
    starts = np.flatnonzero(np.r_[True, codes_s[1:] != codes_s[:-1]])
    counts = np.diff(np.r_[starts, len(codes_s)])
    out = frame.iloc[order[starts]][keys].reset_index(drop=True)
    out["throughput_total"] = counts
    if stand_in == "f32_rates":
        out["request_throughput"] = (counts.astype(np.float32)
                                     / np.float32(WINDOW_NS))
    else:
        out["request_throughput"] = counts / WINDOW_NS
    for k, q in QUANTILES.items():
        rank = np.maximum(np.ceil(q * counts), 1).astype(np.int64) - 1
        v = lat_s[starts + rank].astype(np.float64)
        if stand_in == "coarse_sketch":
            b = np.ceil(np.log(np.maximum(v, 1.0)) / math.log(COARSE_GAMMA))
            v = np.where(v > 0, COARSE_GAMMA ** (b - 0.5), 0.0)
        out[k] = np.floor(v)
    return out


def reference(data: dict, config: dict, script: dict, start_ns: int,
              stand_in: str = ""):
    ev = data["pgsql_events"]
    md = config["metadata"]
    pods = int(md["pods"])
    a = int(np.searchsorted(ev["time_"], start_ns, side="left"))
    names = np.array([f"{md['namespace']}/pod-{i}" for i in range(pods)],
                     dtype=object)
    addrs = np.array(_addresses(config), dtype=object)
    ra_pod = np.array([_pod_of_address(x, pods) for x in addrs])
    ra_name = np.where(ra_pod >= 0, names[np.maximum(ra_pod, 0)], addrs)
    if stand_in == "source_unresolved":
        ra_name = addrs
    code = ev["remote_addr"][a:]
    pod = names[ev["upid"][a:]]
    server = ev["trace_role"][a:] == 2
    is_ra_pod = ra_pod[code] >= 0
    frame = pd.DataFrame({
        "source": np.where(server, ra_name[code], pod),
        "destination": np.where(server, pod, ra_name[code]),
        "is_source_pod_type": np.where(server, is_ra_pod, True),
        "is_dest_pod_type": np.where(server, True, is_ra_pod),
        "namespace": md["namespace"],
        "time_": (ev["time_"][a:] // WINDOW_NS) * WINDOW_NS})
    if stand_in == "unwindowed":
        frame["time_"] = int(start_ns)
    lat = ev["latency"][a:]
    keep = ((frame["source"] != "") & (frame["destination"] != "")).to_numpy()
    frame, lat = frame[keep].reset_index(drop=True), lat[keep]
    if script["name"] == "pgsql_pod_let":
        w = _windows(frame, CHART, lat, stand_in)
        if stand_in == "unwindowed":  # one row a destination, no window
            w["time_"] = 0
        return w[CHART + list(QUANTILES) + ["request_throughput"]], CHART
    w = _windows(frame, ["time_"] + EDGE, lat, stand_in)
    agg = {k: (k, "mean") for k in QUANTILES}
    agg["request_throughput"] = ("request_throughput", "mean")
    agg["throughput_total"] = ("throughput_total", "sum")
    if stand_in == "f32_rates":
        w["request_throughput"] = w["request_throughput"].astype(np.float32)
    out = w.groupby(EDGE, as_index=False, sort=True).agg(**agg)
    return out, EDGE


def _rel(m: pd.DataFrame, col: str) -> float:
    """The largest relative difference of `col` over the matched rows; a row
    whose reference reads 0 counts the answer's own distance from 0."""
    ref = m[f"{col}_ref"].to_numpy(np.float64)
    got = m[f"{col}_got"].to_numpy(np.float64)
    diff = np.abs(got - ref)
    zero = ref == 0
    rel = np.where(zero, diff, diff / np.where(zero, 1.0, np.abs(ref)))
    return float(np.max(rel)) if len(rel) else 0.0


def compare(got: pd.DataFrame, ref, config: dict) -> dict:
    """{number: (value, limit)}; the limits are the configuration's own:
    edges, windows and counts exact, request_throughput within
    `throughput_rtol`, each quantile (the flow graph's per-edge mean of the
    per-window ones) within `sketch_quantile_rtol` of the reference's."""
    from compare import joined

    ref_df, keys = ref
    g = config["guarantees"]
    m, unmatched = joined(got, ref_df, keys)
    out = {"groups_unmatched": (unmatched, 0)}
    if unmatched:
        return out
    if "throughput_total" in ref_df:
        out["cnt_mismatch"] = (int((m["throughput_total_ref"]
                                    != m["throughput_total_got"]).sum()), 0)
    out["rate_rel"] = (_rel(m, "request_throughput"), g["throughput_rtol"])
    for k in QUANTILES:
        out[k[len("latency_"):] + "_rel"] = (_rel(m, k),
                                             g["sketch_quantile_rtol"])
    return out
