"""Fixtures of the fault, durability and elasticity tests: one small
`http_events` store per agent from a seed, the query mix replayed against
it, and the bit-exact fingerprint two answers are compared by.

The tests restart and kill agents around these stores
(tests/test_fault_tolerance.py, test_durability.py, test_elastic.py) and
require that every recovered answer equals the fault-free one bit for bit:
a kill-and-restart keeps each agent's store, and per-source folds merge in
sorted-source order, so recovery may not move a bit.
"""
from __future__ import annotations

import numpy as np

from pixie_tpu.table import TableStore
from pixie_tpu.types import DataType as DT, Relation

#: batch size of the stores that lose their pod: a row count that is a
#: multiple of it seals (and therefore replicates) every acked row before a
#: fault fires — the precondition for zero-loss recovery when the journal
#: dies WITH the pod
HARD_BATCH_ROWS = 1 << 12

#: the replayed query mix — retryable (non-mutation) shapes only: a partial
#: agg channel, a multi-key agg with float state (mean/p50 exercise float
#: fold determinism), and a rows channel with a filter
SCRIPTS = [
    """
df = px.DataFrame(table='http_events')
df = df.groupby('service').agg(cnt=('latency', px.count),
                               mx=('latency', px.max))
px.display(df, 'out')
""",
    """
df = px.DataFrame(table='http_events')
df = df.groupby(['service', 'status']).agg(
    cnt=('latency', px.count), m=('latency', px.mean),
    p50=('latency', px.p50))
px.display(df, 'out')
""",
    """
df = px.DataFrame(table='http_events')
df = df[df.status == 500]
df = df.groupby('service').agg(cnt=('latency', px.count),
                               s=('latency', px.sum))
px.display(df, 'out')
""",
]


def mkdata(seed: int, rows: int) -> dict:
    rng = np.random.default_rng(seed)
    svc = np.array([f"svc-{i}" for i in range(6)])
    return {
        "time_": np.arange(rows, dtype=np.int64) * 1000,
        "service": svc[rng.integers(0, len(svc), rows)],
        "latency": rng.exponential(20.0, rows),
        "status": rng.choice([200, 404, 500], rows, p=[0.9, 0.05, 0.05]),
    }


def mkstore(seed: int, rows: int, batch_rows: int = 1 << 13):
    ts = TableStore()
    rel = Relation.of(
        ("time_", DT.TIME64NS), ("service", DT.STRING),
        ("latency", DT.FLOAT64), ("status", DT.INT64),
    )
    t = ts.create("http_events", rel, batch_rows=batch_rows,
                  max_bytes=1 << 32)
    if rows:
        t.write(mkdata(seed, rows))
    return ts


def canonical_bytes(results: dict) -> bytes:
    """Order-independent BIT-exact fingerprint of a query answer: per table,
    rows sort lexicographically by every column's VALUE (dictionary codes
    decoded — code spaces differ across merges by construction) and the
    sorted columns' raw bytes concatenate.  Float columns contribute their
    bit patterns: a recovered query that differs in one ulp fails."""
    out = []
    for name in sorted(results):
        qr = results[name]
        cols = {}
        for cname in sorted(qr.columns):
            arr = qr.columns[cname]
            if cname in qr.dictionaries:
                vals = qr.dictionaries[cname].decode(arr)
                cols[cname] = np.asarray(
                    [v if v is not None else "" for v in vals], dtype=object)
            else:
                cols[cname] = np.asarray(arr)
        if cols:
            order = np.lexsort([cols[c] if cols[c].dtype != object
                                else np.asarray(cols[c], dtype="U64")
                                for c in sorted(cols)])
        for cname in sorted(cols):
            arr = cols[cname][order] if cols else cols[cname]
            out.append(cname.encode())
            if arr.dtype == object:
                out.append("\x00".join(str(v) for v in arr).encode())
            else:
                out.append(arr.tobytes())  # bit patterns, not repr
    return b"\x01".join(out)
