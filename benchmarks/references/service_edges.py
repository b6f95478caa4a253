"""Plain reference for service_edges (px/service_edge_stats `svc_edge_let`
without its 10 s bin: the service map's statistics of every edge over the
query's range).  Of the server-side rows (`trace_role == 2`) at or after the
query's own start_time that are no health check (`req_path` neither
`/healthz` nor `/readyz`), per (remote address, responding service): the
count, the error rate (the mean of `resp_status >= 400`), the INT64 sum of
`resp_body_size`, and the p50, p90 and p99 of `latency` as exact order
statistics; the requestor is the address resolved as the node's metadata
resolves it (pod i is at 10.0.<i/250>.<i%250 + 1> and belongs to service
i mod `services`), the responder the service of the row's process (pod i's
process belongs to service i mod `services`).  The addresses and paths are
the configuration file's own `values`.  Imports nothing of the program.

Four stand-ins for the program, each the reference with one thing lowered
(`stand_in`): "coarse_sketch" answers each quantile as a log sketch of a
quarter of the bins would (bins four times as wide), the step a cheaper
sketch would take; "requestor_unresolved" leaves the requestor the address,
as a lookup that resolves nothing would; "f32_rates" gives each error rate
as a float32 mean and "f32_sums" each `bytes_total` as a float32 running
sum, the step below the float64 mean and the INT64 sum the configuration
states.
"""
from __future__ import annotations

import math

import numpy as np
import pandas as pd

#: what stands in the program's place as the control
CONTROLS = ("coarse_sketch", "requestor_unresolved", "f32_rates", "f32_sums")

#: the sketch the configuration states (ops/sketch.py: gamma 1.0404, a value
#: answered as the geometric middle of its bin), and the coarse stand-in's
GAMMA = 1.0404
COARSE_GAMMA = GAMMA ** 4
QUANTILES = {"latency_p50": 0.50, "latency_p90": 0.90, "latency_p99": 0.99}
HEALTH = ("/healthz", "/readyz")
KEYS = ["requestor", "remote_addr", "responder"]


def _values(config: dict, column: str) -> list:
    spec = next(t for t in config["tables"] if t["name"] == "http_events")
    return next(c for c in spec["columns"] if c["name"] == column)["values"]


def reference(data: dict, config: dict, script: dict, start_ns: int,
              stand_in: str = ""):
    http = data["http_events"]
    md = config["metadata"]
    card = int(md["services"])
    addrs = _values(config, "remote_addr")
    paths = _values(config, "req_path")
    a = int(np.searchsorted(http["time_"], start_ns, side="left"))
    health = [i for i, p in enumerate(paths) if p in HEALTH]
    keep = ((http["trace_role"][a:] == 2)
            & ~np.isin(http["req_path"][a:], health))
    addr = http["remote_addr"][a:][keep].astype(np.int64)
    svc = http["upid"][a:][keep].astype(np.int64) % card
    gid = addr * card + svc
    order = np.argsort(gid, kind="stable")
    gid_sorted = gid[order]
    uniq, starts, counts = np.unique(gid_sorted, return_index=True,
                                     return_counts=True)
    failed = (http["resp_status"][a:][keep][order] >= 400).astype(np.float64)
    body = http["resp_body_size"][a:][keep][order].astype(np.int64)
    lat = http["latency"][a:][keep][order]
    out = pd.DataFrame({
        "gid": uniq,
        "error_rate": pd.Series(failed).groupby(gid_sorted, sort=True)
        .mean().to_numpy(),
        "throughput_total": counts,
        "bytes_total": np.add.reduceat(body, starts)})
    if stand_in == "f32_rates":
        out["error_rate"] = (np.add.reduceat(failed, starts).astype(np.float32)
                             / counts.astype(np.float32))
    elif stand_in == "f32_sums":
        # a float32 running sum an edge, row by row (np.cumsum adds in
        # sequence; np.add.reduce would add pairwise)
        b32 = body.astype(np.float32)
        out["bytes_total"] = np.array(
            [np.cumsum(b32[s:s + n], dtype=np.float32)[-1]
             for s, n in zip(starts, counts)], dtype=np.float32)
    # exact order statistics: the sketch returns the bin holding the
    # ceil(q*n)-th smallest value of the group
    exact = {k: np.empty(len(uniq)) for k in QUANTILES}
    for g, (s, n) in enumerate(zip(starts, counts)):
        ranks = [max(math.ceil(q * n), 1) - 1 for q in QUANTILES.values()]
        part = np.partition(lat[s:s + n], ranks)
        for k, r in zip(QUANTILES, ranks):
            exact[k][g] = part[r]
    for k in QUANTILES:
        if stand_in == "coarse_sketch":
            b = np.ceil(np.log(np.maximum(exact[k], 1.0))
                        / math.log(COARSE_GAMMA))
            exact[k] = np.where(exact[k] > 0, COARSE_GAMMA ** (b - 0.5), 0.0)
        out[k] = exact[k]
    names = np.array([f"{md['namespace']}/{md['service_prefix']}{j}"
                      for j in range(card)], dtype=object)
    addr_of = np.array(addrs, dtype=object)[(out["gid"] // card).to_numpy()]
    out["remote_addr"] = addr_of
    out["requestor"] = (addr_of if stand_in == "requestor_unresolved"
                        else names[(out["gid"] // card).to_numpy() % card])
    out["responder"] = names[(out["gid"] % card).to_numpy()]
    return out.drop(columns="gid"), KEYS


def _rel(m: pd.DataFrame, col: str) -> float:
    """The largest relative difference of `col` over the matched edges; an
    edge whose reference reads 0 (a zero latency, no error) counts the
    answer's own distance from 0."""
    ref = m[f"{col}_ref"].to_numpy(np.float64)
    got = m[f"{col}_got"].to_numpy(np.float64)
    diff = np.abs(got - ref)
    zero = ref == 0
    rel = np.where(zero, diff, diff / np.where(zero, 1.0, np.abs(ref)))
    return float(np.max(rel)) if len(rel) else 0.0


def compare(got: pd.DataFrame, ref, config: dict) -> dict:
    """{number: (value, limit)}; the limits are the configuration's own:
    edges, counts and INT64 sums exact, error rates within
    `error_rate_rtol`, each quantile within `sketch_quantile_rtol` of the
    exact order statistic."""
    from compare import joined

    ref_df, keys = ref
    g = config["guarantees"]
    m, unmatched = joined(got, ref_df, keys)
    out = {"groups_unmatched": (unmatched, 0)}
    if unmatched:
        return out
    out["cnt_mismatch"] = (int((m["throughput_total_ref"]
                                != m["throughput_total_got"]).sum()), 0)
    out["sum_mismatch"] = (int((m["bytes_total_ref"]
                                != m["bytes_total_got"]).sum()), 0)
    out["err_rel"] = (_rel(m, "error_rate"), g["error_rate_rtol"])
    for k in QUANTILES:
        out[k[len("latency_"):] + "_rel"] = (_rel(m, k),
                                             g["sketch_quantile_rtol"])
    return out
