"""Plain reference for conn_flow_graph (px/net_flow_graph over conn_stats):
of the client-side rows (`trace_role == 1`) at or after the query's own
start_time, the smallest and largest `bytes_sent` and `bytes_recv` of every
(process, remote address) pair, their differences and the differences' sum;
the process's pod as `from_entity`, the address resolved as the node's
metadata resolves it (a pod's IP to the pod, a service's cluster IP to the
service, any other address to itself) as `to_entity`; then INT64 sums of
the three per (from_entity, to_entity).  A pod has one traced process, so the
script's three group keys (pod, upid, remote_addr) are these two.  The pods,
services and their addresses are the configuration file's `metadata`, laid
out as the generator's `install_metadata` assigns them.  Imports nothing of
the program.

Two stand-ins for the program, each the reference with one thing lowered
(`stand_in`): "f32_minmax" holds the counters in float32, the step below the
INT64 the configuration states; "nslookup_skipped" leaves `to_entity` the
address, as a lookup that resolves nothing would.
"""
from __future__ import annotations

import numpy as np
import pandas as pd

#: what stands in the program's place as the control
CONTROLS = ("f32_minmax", "nslookup_skipped")

SUMS = ("bytes_sent", "bytes_recv", "bytes_total")


def entities(config: dict) -> tuple[list, dict]:
    """(pod i's qualified name, {address: the name it resolves to}) from
    the configuration's `metadata`: pod i is `pod-<i>` at 10.0.<i/250>.<i%250
    + 1>, service j is `<service_prefix><j>` at 10.96.<j/250>.<j%250 + 1>;
    a pod's address wins over a service's."""
    md = config["metadata"]
    ns = md["namespace"]
    pods = [f"{ns}/pod-{i}" for i in range(int(md["pods"]))]
    names = {f"10.96.{j // 250}.{j % 250 + 1}":
             f"{ns}/{md['service_prefix']}{j}"
             for j in range(int(md["services"]))}
    names.update({f"10.0.{i // 250}.{i % 250 + 1}": pods[i]
                  for i in range(len(pods))})
    return pods, names


def reference(data: dict, config: dict, script: dict, start_ns: int,
              stand_in: str = ""):
    conn = data["conn_stats"]
    spec = next(t for t in config["tables"] if t["name"] == "conn_stats")
    addrs = next(c for c in spec["columns"]
                 if c["name"] == "remote_addr")["values"]
    a = int(np.searchsorted(conn["time_"], start_ns, side="left"))
    keep = conn["trace_role"][a:] == 1
    upid = conn["upid"][a:][keep].astype(np.int64)
    addr = conn["remote_addr"][a:][keep].astype(np.int64)
    pair = upid * len(addrs) + addr
    order = np.argsort(pair, kind="stable")
    uniq, starts = np.unique(pair[order], return_index=True)
    cols = {}
    for c in ("bytes_sent", "bytes_recv"):
        v = conn[c][a:][keep][order]
        if stand_in == "f32_minmax":
            v = v.astype(np.float32)
        cols[c] = (np.maximum.reduceat(v, starts)
                   - np.minimum.reduceat(v, starts))
    cols["bytes_total"] = cols["bytes_sent"] + cols["bytes_recv"]
    pods, names = entities(config)
    if stand_in == "nslookup_skipped":
        names = {}
    to = np.array([names.get(x, x) for x in addrs], dtype=object)
    per_pair = pd.DataFrame({
        "from_entity": np.array(pods, dtype=object)[uniq // len(addrs)],
        "to_entity": to[uniq % len(addrs)], **cols})
    out = per_pair.groupby(["from_entity", "to_entity"], sort=True).agg(
        **{c: (c, "sum") for c in SUMS}).reset_index()
    return out, ["from_entity", "to_entity"]


def compare(got: pd.DataFrame, ref, config: dict) -> dict:
    """{number: (value, limit)}: the configuration states exact groups and
    exact INT64 min, max and sums, so every limit is 0.  `minmax_mismatch`
    counts the groups whose summed differences of `bytes_sent` or of
    `bytes_recv` differ, `sum_mismatch` those whose `bytes_total` does."""
    from compare import joined

    ref_df, keys = ref
    m, unmatched = joined(got, ref_df, keys)
    out = {"groups_unmatched": (unmatched, 0)}
    if unmatched:
        return out
    out["minmax_mismatch"] = (int(
        ((m["bytes_sent_ref"] != m["bytes_sent_got"])
         | (m["bytes_recv_ref"] != m["bytes_recv_got"])).sum()), 0)
    out["sum_mismatch"] = (int((m["bytes_total_ref"]
                                != m["bytes_total_got"]).sum()), 0)
    return out
