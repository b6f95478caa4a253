"""Tables made from --seed by one general generator, and their loading.

A configuration's file (configs/<name>.json) lists its tables and, for each
column, a generator with its parameters.  Nothing here names a configuration:
a new one is a new file.  The arrays stay on the host so that the plain
references (references/*.py) can be worked out from them after the measured
window.  A dictionary-coded column (STRING, UINT128) is kept as integer codes
into the list of values that `values_of` gives; the strings and UPIDs
themselves are made chunk by chunk at write time.
"""
from __future__ import annotations

import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEC = 1_000_000_000
#: rows per Table.write call
WRITE_CHUNK = 1 << 19


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module: how a per-layer metric's
    reader and a script's reference are found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def table_rows(config: dict, table: dict) -> int:
    rows = table["rows"]
    return int(config[rows] if isinstance(rows, str) else rows)


def time_step_ns(config: dict, rows: int) -> int:
    return int(config["span_s"]) * SEC // rows


def card_of(config: dict, column: dict) -> int:
    """How many distinct values a coded column draws from: a number, or the
    name of a count in the configuration's `metadata` (pods, services)."""
    card = column.get("card", len(column.get("values", [])))
    return int(config["metadata"][card] if isinstance(card, str) else card)


def zipf_p(card: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, card + 1, dtype=np.float64) ** s
    return p / p.sum()


def generate(config: dict, seed: int) -> dict:
    """{table: {column: array}}; coded columns hold int32 codes."""
    rng = np.random.default_rng(seed)
    out = {}
    for table in config["tables"]:
        rows = table_rows(config, table)
        cols: dict = {}
        for c in table["columns"]:
            g = c["gen"]
            if g == "ordered_time":
                a = (int(config["time_base_ns"])
                     + np.arange(rows, dtype=np.int64)
                     * time_step_ns(config, rows))
            elif g == "zipf":  # value r drawn with p ~ 1/(r+1)^s
                card = card_of(config, c)
                a = rng.choice(card, rows,
                               p=zipf_p(card, float(c["s"]))).astype(np.int32)
            elif g == "weighted":  # codes into `values`, with weights `p`
                a = rng.choice(len(c["values"]), rows,
                               p=c["p"]).astype(np.int32)
            elif g == "choice":
                a = rng.choice(np.array(c["values"], dtype=np.int64), rows,
                               p=c["p"])
            elif g == "integers":
                a = rng.integers(int(c.get("low", 0)), int(c["high"]), rows,
                                 dtype=np.int64)
            elif g == "exponential_ns":
                a = rng.exponential(float(c["scale"]), rows).astype(np.int64)
            elif g == "const":
                a = np.full(rows, int(c["value"]), dtype=np.int64)
            elif g == "lookup":  # a code for each value of another column
                src = cols[c["of"]]
                keys = np.array(sorted(int(k) for k in c["map"]),
                                dtype=np.int64)
                a = np.searchsorted(keys, src).astype(np.int32)
            else:
                raise ValueError(f"unknown generator {g!r}")
            cols[c["name"]] = a
        out[table["name"]] = cols
    return out


def upid_parts(config: dict, i: int) -> tuple[int, int, int]:
    """(asid, pid, start time) of pod i's one process."""
    return 1, 1000 + i, int(config["time_base_ns"]) - 3600 * SEC + i


def values_of(config: dict, column: dict) -> list:
    """The values a coded column's codes stand for."""
    if column["type"] == "UINT128":
        from pixie_tpu.types import UInt128

        return [UInt128.make_upid(*upid_parts(config, i))
                for i in range(card_of(config, column))]
    if "values" in column:
        return list(column["values"])
    if column["gen"] == "lookup":
        return [column["map"][str(k)]
                for k in sorted(int(k) for k in column["map"])]
    return [f"{column['prefix']}{i}" for i in range(card_of(config, column))]


def install_metadata(config: dict) -> None:
    """The node's k8s state, as the agent's watch would have delivered it:
    `pods` pods, one process each, pod i in service i mod `services`."""
    md = config.get("metadata")
    if not md:
        return
    from pixie_tpu.metadata import state as mdstate
    from pixie_tpu.types import UInt128

    m = mdstate.MetadataStateManager(asid=1, node_name="node-0")
    updates = []
    by_service: dict = {}
    for i in range(int(md["pods"])):
        uid = f"{md['pod_prefix']}{i}"
        j = i % int(md["services"])
        by_service.setdefault(j, []).append(uid)
        updates.append({"kind": "pod", "uid": uid, "name": f"pod-{i}",
                        "namespace": md["namespace"], "node": "node-0",
                        "ip": f"10.0.{i // 250}.{i % 250 + 1}",
                        "phase": "Running", "create_time_ns": SEC})
        updates.append({"kind": "container", "cid": f"ctr-{i}",
                        "name": f"ctr-{i}", "pod_uid": uid,
                        "state": "Running"})
        updates.append({"kind": "process",
                        "upid": UInt128.make_upid(*upid_parts(config, i)),
                        "pod_uid": uid, "container_id": f"ctr-{i}",
                        "cmdline": f"/bin/app-{j}"})
    for j, uids in sorted(by_service.items()):
        updates.append({"kind": "service", "uid": f"svc-uid-{j}",
                        "name": f"{md['service_prefix']}{j}",
                        "namespace": md["namespace"],
                        "cluster_ip": f"10.96.{j // 250}.{j % 250 + 1}",
                        "pod_uids": uids})
    m.apply_updates(updates)
    mdstate.set_global_manager(m)


def load_store(config: dict, data: dict):
    """A TableStore filled through Table.write, as an agent's ingest does.
    A table with a `max_bytes` gets that budget, and must hold all its rows
    under it: nothing may have expired."""
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    store = TableStore()
    for table in config["tables"]:
        rows = table_rows(config, table)
        rel = Relation.of(*[(c["name"], getattr(DT, c["type"]))
                            for c in table["columns"]])
        kw = {}
        if "max_bytes" in table:
            kw = {"max_bytes": int(table["max_bytes"]),
                  "batch_rows": int(table["batch_rows"])}
        t = store.create(table["name"], rel, **kw)
        cols = data[table["name"]]
        coded = {}
        for c in table["columns"]:
            if c["type"] in ("STRING", "UINT128"):
                vals = values_of(config, c)
                coded[c["name"]] = (np.array(vals) if c["type"] == "STRING"
                                    else np.array(vals + [None],
                                                  dtype=object)[:-1])
        for a in range(0, rows, WRITE_CHUNK):
            b = min(a + WRITE_CHUNK, rows)
            t.write({n: (coded[n][v[a:b]] if n in coded else v[a:b])
                     for n, v in cols.items()})
        st = t.stats()
        if st["expired_batches"] or st["rows_written"] != rows:
            raise RuntimeError(f"table {table['name']}: {st}: the rows do "
                               "not fit the table's budget")
    return store


def column_bytes(config: dict, table: str, columns: list) -> int:
    """Bytes per row that a scan of `columns` has to read, from the file."""
    for t in config["tables"]:
        if t["name"] == table:
            width = {c["name"]: int(c["bytes"]) for c in t["columns"]}
            return sum(width[c] for c in columns)
    raise KeyError(table)
