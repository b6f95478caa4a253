"""Plain reference for the http_events group-by scripts: pandas count/mean
and exact order statistics per group (after chip_smoke.ref_http, PR 21, with
the query's own start_time applied and the service taken from the node's
metadata as the configuration's file states it: the process of pod i belongs
to service i mod `services`).  Imports nothing of the program.

Two stand-ins for the program, each the reference with one thing lowered
(`stand_in`): "f32" holds the latencies and their running sums in float32,
the step below the float64 in which the program adds them; "coarse_sketch"
answers each quantile as a log sketch of a quarter of the bins would (bins
four times as wide), the step a cheaper sketch would take.
"""
from __future__ import annotations

import math

import numpy as np
import pandas as pd

#: what stands in the program's place as the control
CONTROLS = ("f32", "coarse_sketch")

WINDOW_NS = 10 * 1_000_000_000
#: the sketch the configuration states (ops/sketch.py: gamma 1.0404, a value
#: answered as the geometric middle of its bin, "~2% relative error"), and
#: the coarse stand-in's bins
GAMMA = 1.0404
COARSE_GAMMA = GAMMA ** 4


def reference(data: dict, config: dict, script: dict, start_ns: int,
              stand_in: str = ""):
    http = data["http_events"]
    md = config["metadata"]
    a = int(np.searchsorted(http["time_"], start_ns, side="left"))
    status = http["resp_status"][a:]
    keep = status != 404
    lat = http["latency"][a:][keep]
    card = int(md["services"])
    svc = http["upid"][a:][keep].astype(np.int64) % card
    if script["windowed"]:
        other = http["time_"][a:][keep] // WINDOW_NS
        gid = other * card + svc
        qs = {"p50": 0.50, "p99": 0.99}
    else:
        gid = svc * 1000 + status[keep]
        qs = {"p50": 0.50}
    order = np.argsort(gid, kind="stable")
    gid_sorted, lat_sorted = gid[order], lat[order]
    uniq, starts, counts = np.unique(gid_sorted, return_index=True,
                                     return_counts=True)
    if stand_in == "f32":
        # float32 values and a float32 running sum per group, row by row
        # (np.cumsum adds in sequence; np.add.reduce would add pairwise)
        lat32 = lat_sorted.astype(np.float32)
        sums = np.array([np.cumsum(lat32[s:s + n], dtype=np.float32)[-1]
                         for s, n in zip(starts, counts)], dtype=np.float32)
        mean = (sums / counts.astype(np.float32)).astype(np.float64)
    else:
        mean = (pd.Series(lat_sorted.astype(np.float64))
                .groupby(gid_sorted, sort=True).mean().to_numpy())
    out = pd.DataFrame({"gid": uniq, "cnt": counts, "avg_lat": mean})
    # exact order statistics: the sketch returns the bin holding the
    # ceil(q*n)-th smallest value of the group
    exact = {k: np.empty(len(uniq)) for k in qs}
    for g, (s, n) in enumerate(zip(starts, counts)):
        ranks = [max(math.ceil(q * n), 1) - 1 for q in qs.values()]
        part = np.partition(lat_sorted[s:s + n], ranks)
        for k, r in zip(qs, ranks):
            exact[k][g] = part[r]
    if stand_in == "coarse_sketch":
        for k in qs:
            b = np.ceil(np.log(np.maximum(exact[k], 1.0))
                        / math.log(COARSE_GAMMA))
            exact[k] = COARSE_GAMMA ** (b - 0.5)
    for k in qs:
        out[k] = exact[k]
    names = np.array([f"{md['namespace']}/{md['service_prefix']}{j}"
                      for j in range(card)])
    if script["windowed"]:
        out["time_"] = (out["gid"] // card) * WINDOW_NS
        out["service"] = names[(out["gid"] % card).to_numpy()]
        keys = ["time_", "service"]
    else:
        out["service"] = names[(out["gid"] // 1000).to_numpy()]
        out["resp_status"] = out["gid"] % 1000
        keys = ["service", "resp_status"]
    return out.drop(columns="gid"), keys


def compare(got: pd.DataFrame, ref, config: dict) -> dict:
    """{number: (value, limit)}; the limits are the configuration's own."""
    from compare import joined, max_rel

    ref_df, keys = ref
    g = config["guarantees"]
    m, unmatched = joined(got, ref_df, keys)
    out = {"groups_unmatched": (unmatched, 0)}
    if unmatched:
        return out
    out["cnt_mismatch"] = (int((m["cnt_ref"] != m["cnt_got"]).sum()), 0)
    out["mean_rel"] = (max_rel(m, "avg_lat"), g["mean_rtol"])
    for q in ("p50", "p99"):
        if f"{q}_ref" in m:
            out[f"{q}_rel"] = (max_rel(m, q), g["sketch_quantile_rtol"])
    return out
