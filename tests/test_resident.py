"""Device-resident hot-table tier (engine/resident.py).

Covers the ISSUE-6 acceptance matrix: warm-vs-cold bit-equality at the
1M-row interactive shape with a ZERO measured H2D transfer counter, ingest
deltas folding in place (only delta bytes cross the link), retention trims
evicting pinned batches, budget-exceeded fallback to the streaming feed
path, and flag-off (`PL_HBM_RESIDENT=0`) producing identical results.
"""
import numpy as np
import pytest

import pixie_tpu  # noqa: F401  (x64)
from pixie_tpu import flags
from pixie_tpu.engine import resident
from pixie_tpu.engine.executor import PlanExecutor, clear_device_cache
from pixie_tpu.plan import (
    AggExpr, AggOp, MemorySinkOp, MemorySourceOp, Plan,
)
from pixie_tpu.table import TableStore
from pixie_tpu.types import DataType as DT, Relation


@pytest.fixture(autouse=True)
def _clean_tier():
    resident.clear_for_testing()
    clear_device_cache()
    yield
    resident.clear_for_testing()
    clear_device_cache()


@pytest.fixture
def _budget():
    old = flags.get("PL_HBM_RESIDENT_MB")
    yield
    flags.set_for_testing("PL_HBM_RESIDENT_MB", old)


def _mkstore(rows, batch_rows=1 << 14, max_bytes=1 << 36, seed=0):
    rng = np.random.default_rng(seed)
    ts = TableStore()
    t = ts.create(
        "events",
        Relation.of(("time_", DT.TIME64NS), ("service", DT.STRING),
                    ("latency", DT.FLOAT64), ("status", DT.INT64)),
        batch_rows=batch_rows, max_bytes=max_bytes,
    )
    _write(t, rows, rng, t0=0)
    return ts, t, rng


def _write(t, n, rng, t0=0):
    t.write({
        "time_": np.arange(t0, t0 + n, dtype=np.int64),
        "service": np.array([f"svc-{i % 8}" for i in range(n)]),
        "latency": rng.exponential(50.0, n),
        "status": rng.choice([200, 404, 500], n).astype(np.int64),
    })


def _plan():
    p = Plan()
    src = p.add(MemorySourceOp(table="events"))
    agg = p.add(
        AggOp(groups=["service"], values=[
            AggExpr("cnt", "count", None),
            AggExpr("avg", "mean", "latency"),
            AggExpr("p50", "p50", "latency"),
        ]),
        parents=[src],
    )
    p.add(MemorySinkOp(name="out"), parents=[agg])
    return p


def _run(ts, backend="device"):
    # mesh=None: the single-device interactive deployment shape (the
    # 8-virtual-device test mesh would take the SPMD feed path, where the
    # resident tier intentionally does not engage)
    ex = PlanExecutor(_plan(), ts, mesh=None, force_backend=backend)
    out = ex.run()["out"]
    return ex, out


def _frames_equal(a, b):
    ga = a.to_pandas().sort_values("service").reset_index(drop=True)
    gb = b.to_pandas().sort_values("service").reset_index(drop=True)
    for c in ga.columns:
        np.testing.assert_array_equal(ga[c].to_numpy(), gb[c].to_numpy(),
                                      err_msg=f"column {c}")


def test_warm_query_zero_h2d_bit_equal_1m():
    """The headline shape: 1M rows fully sealed; cold admits the pinned
    entry, warm serves it with a MEASURED zero-byte H2D counter and
    bit-equal results."""
    ts, _t, _rng = _mkstore(1 << 20, batch_rows=1 << 16)
    ex_cold, out_cold = _run(ts)
    assert ex_cold.stats.get("resident_feeds") == 1
    assert ex_cold.stats.get("h2d_bytes", 0) > 0  # admission uploads once
    ex_warm, out_warm = _run(ts)
    assert ex_warm.stats.get("resident_feeds") == 1
    assert ex_warm.stats.get("h2d_bytes", 0) == 0  # the acceptance stat
    assert resident.tier_stats()["hits"] >= 1
    _frames_equal(out_cold, out_warm)


def test_ingest_delta_folds_in_place():
    """New seals fold into the resident buffer: the next query uploads only
    the delta bytes, not the whole table."""
    ts, t, rng = _mkstore(1 << 16, batch_rows=1 << 14)
    _run(ts)
    _write(t, 1 << 14, rng, t0=1 << 16)  # exactly one new sealed batch
    ex, out = _run(ts)
    # the feed is PRUNED to the agg's needed columns: service (i32 code)
    # + latency (f64) = 12 B/row
    assert ex.stats["h2d_bytes"] == (1 << 14) * 12
    assert resident.tier_stats()["folds"] >= 1
    # and the fold is correct: flag-off rerun matches exactly
    flags.set_for_testing("PL_HBM_RESIDENT", False)
    try:
        _ex2, out2 = _run(ts)
    finally:
        flags.set_for_testing("PL_HBM_RESIDENT", True)
    _frames_equal(out, out2)


def test_retention_trim_evicts_pinned_batches():
    """Ring-buffer expiry must not leave expired batches pinned in the
    tier: a head trim rebases the entry (zero re-upload of retained rows),
    a full expiry frees it outright."""
    rows_per_batch = 1 << 10
    # budget ~8 sealed batches of 28 B/row storage
    ts, t, rng = _mkstore(8 * rows_per_batch, batch_rows=rows_per_batch,
                          max_bytes=8 * rows_per_batch * 28)
    _run(ts)
    assert resident.tier_stats()["entries"] == 1
    lo_before = t.first_row_id()
    _write(t, 2 * rows_per_batch, rng, t0=8 * rows_per_batch)
    assert t.first_row_id() > lo_before  # expiry actually trimmed
    ex, out = _run(ts)
    st = resident.tier_stats()
    assert st["rebases"] >= 1  # head batches dropped on device
    # retained rows did NOT re-upload: only the two delta batches did
    assert ex.stats["h2d_bytes"] == 2 * rows_per_batch * 12  # pruned feed
    flags.set_for_testing("PL_HBM_RESIDENT", False)
    try:
        _ex2, out2 = _run(ts)
    finally:
        flags.set_for_testing("PL_HBM_RESIDENT", True)
    _frames_equal(out, out2)
    # full expiry: write far past the budget -> entry freed outright
    _write(t, 32 * rows_per_batch, rng, t0=10 * rows_per_batch)
    assert resident.tier_stats()["entries"] == 0
    assert resident.tier_stats()["bytes"] == 0
    assert resident.tier_stats()["trims"] >= 1


def test_budget_exceeded_falls_back_to_streaming(_budget):
    """An entry that cannot fit PL_HBM_RESIDENT_MB streams through the
    legacy feed path — identical results, no pinning."""
    flags.set_for_testing("PL_HBM_RESIDENT_MB", 0)
    ts, _t, _rng = _mkstore(1 << 15)
    ex, out = _run(ts)
    assert "resident_feeds" not in ex.stats
    assert resident.tier_stats()["entries"] == 0
    assert resident.tier_stats()["fallbacks"] >= 1
    ex2, out2 = _run(ts)  # legacy HBM feed cache still serves warm queries
    assert ex2.stats.get("feed_cache_hits", 0) >= 1
    _frames_equal(out, out2)
    # budget recovers: admission ADOPTS the legacy cache's device arrays
    # (zero re-upload of bytes already resident) instead of pinning a
    # second copy next to them
    flags.set_for_testing("PL_HBM_RESIDENT_MB", 2048)
    ex3, out3 = _run(ts)
    assert ex3.stats.get("resident_feeds") == 1
    assert ex3.stats.get("h2d_bytes", 0) == 0  # adopted, not re-uploaded
    assert resident.tier_stats()["admissions"] == 1
    _frames_equal(out, out3)


def test_flag_off_identical_results():
    ts, _t, _rng = _mkstore(1 << 15)
    _ex_on, out_on = _run(ts)
    flags.set_for_testing("PL_HBM_RESIDENT", False)
    try:
        resident.clear_for_testing()
        clear_device_cache()
        ex_off, out_off = _run(ts)
        assert "resident_feeds" not in ex_off.stats
        assert resident.tier_stats()["entries"] == 0
    finally:
        flags.set_for_testing("PL_HBM_RESIDENT", True)
    _frames_equal(out_on, out_off)


def test_hot_remainder_stays_unpinned():
    """A table with an unsealed hot tail: the sealed prefix serves from
    the tier, the hot rows stream fresh every query (they change per
    write), and results match the cpu-routed oracle."""
    ts, t, rng = _mkstore((1 << 14) + 100, batch_rows=1 << 14)
    ex, out = _run(ts)
    assert ex.stats.get("resident_feeds") == 1
    assert ex.stats["h2d_bytes"] > 0
    ex2, out2 = _run(ts)
    # warm: sealed prefix zero-H2D, only the hot remainder re-uploads
    # (bucketed to MIN_BUCKET=1024 padded rows x 12 B pruned)
    assert ex2.stats["h2d_bytes"] <= 1024 * 12
    _exc, outc = _run(ts, backend="cpu")
    ga = out2.to_pandas().sort_values("service").reset_index(drop=True)
    gb = outc.to_pandas().sort_values("service").reset_index(drop=True)
    import pandas as pd

    pd.testing.assert_frame_equal(ga, gb, check_dtype=False)
