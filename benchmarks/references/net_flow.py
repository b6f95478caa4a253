"""Plain reference for net_flow_by_service: pandas sums of the eight INT64
counters and a row count per pod over the rows at or after the query's own
start_time, an inner join with the `pods` table on the pod's id, and the sums
again per service with a count of pods.  The values of the coded columns are
the configuration file's (the prefix of `network_stats.pod_id`, the two maps
of `pods`).  Imports nothing of the program.

Two stand-ins for the program, each the reference with one thing lowered
(`stand_in`): "f32_sums" holds the counters and their running sums in float32,
the step below the INT64 the configuration states; "pod_dropped" loses, in
the join, the pod that carries the fewest rows in range, as a join that
mishandles one key would.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

#: what stands in the program's place as the control
CONTROLS = ("f32_sums", "pod_dropped")

COUNTERS = ("rx_bytes", "rx_packets", "rx_errors", "rx_drops",
            "tx_bytes", "tx_packets", "tx_errors", "tx_drops")


def _values(config: dict, table: str, name: str, codes: np.ndarray):
    """The strings a coded column's codes stand for, as the generator's
    `values_of` reads them from the configuration's file."""
    from data import values_of

    spec = next(t for t in config["tables"] if t["name"] == table)
    column = next(c for c in spec["columns"] if c["name"] == name)
    return np.array(values_of(config, column))[codes]


def reference(data: dict, config: dict, script: dict, start_ns: int,
              stand_in: str = ""):
    net, pods = data["network_stats"], data["pods"]
    a = int(np.searchsorted(net["time_"], start_ns, side="left"))
    pod = net["pod_id"][a:]
    order = np.argsort(pod, kind="stable")
    uniq, starts, counts = np.unique(pod[order], return_index=True,
                                     return_counts=True)
    per_pod = pd.DataFrame({"pod_id": _values(config, "network_stats",
                                              "pod_id", uniq),
                            "cnt": counts.astype(np.int64)})
    for c in COUNTERS:
        v = net[c][a:][order]
        if stand_in == "f32_sums":
            # float32 values and a float32 running sum per pod, row by row
            # (np.cumsum adds in sequence; np.add.reduce would add pairwise)
            v32 = v.astype(np.float32)
            per_pod[c] = np.array(
                [np.cumsum(v32[s:s + n], dtype=np.float32)[-1]
                 for s, n in zip(starts, counts)], dtype=np.float32)
        else:
            per_pod[c] = np.add.reduceat(v, starts)
    if stand_in == "pod_dropped":
        per_pod = per_pod.drop(index=int(np.argmin(counts)))
    table = pd.DataFrame({
        "pod_id": _values(config, "pods", "pod_id", pods["pod_id"]),
        "service": _values(config, "pods", "service", pods["service"])})
    m = per_pod.merge(table, on="pod_id", how="inner")
    out = m.groupby("service", sort=True).agg(
        **{c: (c, "sum") for c in COUNTERS}, cnt=("cnt", "sum"),
        pods=("pod_id", "count")).reset_index()
    for c in COUNTERS:
        out[c] = out[c].astype(np.int64)
    return out, ["service"]


def compare(got: pd.DataFrame, ref, config: dict) -> dict:
    """{number: (value, limit)}: the configuration states exact groups,
    counts and INT64 sums, so every limit is 0."""
    from compare import joined

    ref_df, keys = ref
    m, unmatched = joined(got, ref_df, keys)
    out = {"groups_unmatched": (unmatched, 0)}
    if unmatched:
        return out
    out["cnt_mismatch"] = (int(((m["cnt_ref"] != m["cnt_got"])
                                | (m["pods_ref"] != m["pods_got"])).sum()), 0)
    out["sum_mismatch"] = (int(sum((m[f"{c}_ref"] != m[f"{c}_got"]).sum()
                                   for c in COUNTERS)), 0)
    return out
