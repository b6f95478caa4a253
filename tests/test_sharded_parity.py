"""Sharded-by-design execution parity (ISSUE 7).

Bit-equality of the sharded path against the single-device executor — not
rtol closeness: the fixture workload's aggregates are order-independent at
the bit level (count/sum/mean over ints, min/max, integer-count p50
sketch), so `sharded_parity.assert_bitequal` is exact.  Covers uneven shard
tails (row counts not divisible by the mesh width), dictionary-encoded
keys (group keys and join keys), the sharded-resident tier's zero-H2D warm
feeds + shard-local delta folds, per-shard transfer accounting, and the
serialize_cpu_collectives auto-gate.
"""
import numpy as np
import pytest

from pixie_tpu import flags
from pixie_tpu.engine import resident
from pixie_tpu.engine.executor import PlanExecutor
from pixie_tpu.parallel.spmd import collective_gate, make_mesh
from pixie_tpu.testing import sharded_parity

N_DEV = 8


@pytest.fixture(autouse=True)
def _clean_resident():
    resident.clear_for_testing()
    yield
    resident.clear_for_testing()


# ------------------------------------------------------------ agg parity
@pytest.mark.parametrize("rows", [96_000, 99_997])
def test_sharded_agg_bitequal_vs_single_device(rows):
    """filter→map→partial-agg shard-local over the mesh == single-device,
    bit for bit — including the uneven tail (99_997 % 8 != 0 leaves a
    short final shard AND a hot unsealed remainder that merges through the
    host path)."""
    out = sharded_parity.run_local(rows, n_devices=N_DEV)
    assert out["bit_equal"] is True
    assert out["spmd_feeds"] >= 1
    assert out["shard_skew_frac"] >= 1.0


def test_sharded_agg_includes_dict_group_key():
    """The workload groups by a dictionary-encoded service column; decoded
    group values must round-trip identically through the sharded path
    (run_local compares decoded VALUES, not private codes)."""
    ts = sharded_parity.build_store(64_000)
    plan = sharded_parity.agg_plan()
    mesh = make_mesh(N_DEV)
    sharded = PlanExecutor(plan, ts, mesh=mesh,
                           force_backend="device").run()["output"]
    single = PlanExecutor(plan, ts, mesh=None,
                          force_backend="device").run()["output"]
    assert "service" in sharded.dictionaries
    sharded_parity.assert_bitequal(sharded, single)


# ----------------------------------------------------- resident sharded tier
def test_sharded_resident_warm_zero_h2d_and_delta_fold():
    """Warm SPMD queries serve the whole feed from the SHARDED resident
    entry (zero H2D bytes); a new sealed batch folds ONLY its delta bytes
    shard-local, and results stay bit-equal throughout."""
    batch = 8192
    rows = 3 * batch
    ts = sharded_parity.build_store(rows, batch_rows=batch)
    plan = sharded_parity.agg_plan()
    mesh = make_mesh(N_DEV)

    cold = PlanExecutor(plan, ts, mesh=mesh, force_backend="device")
    cold.run()
    assert cold.stats.get("resident_feeds") == 1
    assert cold.stats.get("h2d_bytes", 0) > 0  # admission uploads

    warm = PlanExecutor(plan, ts, mesh=mesh, force_backend="device")
    wout = warm.run()["output"]
    assert warm.stats.get("resident_feeds") == 1
    assert warm.stats.get("h2d_bytes", 0) == 0  # fully pinned, zero upload
    assert warm.stats.get("spmd_feeds") == 1

    # ingest delta: exactly one more sealed batch → the next feed folds
    # only the delta bytes (4+8+8+8+8 = 36 B/row), not the whole table
    t = ts.table("http_events")
    services = np.array(
        [f"svc-{i}" for i in range(sharded_parity.N_SERVICES)])
    cols = sharded_parity.shard_cols(batch, 0, 1)
    t.write({"time_": cols["time_"] + rows * 1000,
             "service": services[cols["service"]],
             "status": cols["status"], "bytes": cols["bytes"],
             "latency": cols["latency"]})
    fold = PlanExecutor(plan, ts, mesh=mesh, force_backend="device")
    fout = fold.run()["output"]
    # fed columns only: service i32 + status/bytes/latency i64/f64 (time_
    # is pruned — the agg has no time bounds)
    delta_bytes = batch * (4 + 8 + 8 + 8)
    assert fold.stats.get("h2d_bytes") == delta_bytes
    assert resident.stats["folds"] >= 1
    single = PlanExecutor(plan, ts, mesh=None,
                          force_backend="device").run()["output"]
    sharded_parity.assert_bitequal(fout, single)
    assert wout.num_rows <= fout.num_rows  # sanity: delta visible


def test_sharded_and_single_device_entries_coexist():
    """n_dev=1 and n_dev=8 resident entries never alias (the key carries
    the mesh width) — a single-device query after a sharded one must not
    consume the sharded handle."""
    batch = 4096
    ts = sharded_parity.build_store(2 * batch, batch_rows=batch)
    plan = sharded_parity.agg_plan()
    mesh = make_mesh(N_DEV)
    PlanExecutor(plan, ts, mesh=mesh, force_backend="device").run()
    PlanExecutor(plan, ts, mesh=None, force_backend="device").run()
    stats = resident.tier_stats()
    assert stats["entries"] == 2  # one sharded, one single-device
    assert stats["admissions"] == 2


# ------------------------------------------------------------ join parity
def test_shuffled_join_bitequal_int_keys():
    out = sharded_parity.run_shuffled_join(60_000, n_devices=N_DEV)
    assert out["bit_equal"] is True
    assert out["n_parts"] == N_DEV
    assert out["all_to_all_exchanges"] >= 2


def test_shuffled_join_dict_keys_matches_single_device():
    """Pod-scale shuffle with DICTIONARY-ENCODED join keys: value-stable
    hashing must route every string key to one partition and the joined
    rows must match the single-device join value-for-value."""
    from pixie_tpu.parallel.cluster import LocalCluster
    from pixie_tpu.plan import (
        JoinOp, MemorySinkOp, MemorySourceOp, Plan,
    )
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    rng = np.random.default_rng(3)
    n = 4000
    ts = TableStore()
    lt = ts.create("left_t", Relation.of(("k", DT.STRING), ("lv", DT.INT64)))
    lt.write({"k": [f"key{rng.integers(0, 300)}" for _ in range(n)],
              "lv": rng.integers(0, 1000, n)})
    rt = ts.create("right_t", Relation.of(("k", DT.STRING), ("rv", DT.INT64)))
    rt.write({"k": [f"key{rng.integers(0, 300)}" for _ in range(n)],
              "rv": rng.integers(0, 1000, n)})

    p = Plan()
    left = p.add(MemorySourceOp(table="left_t", columns=["k", "lv"]))
    right = p.add(MemorySourceOp(table="right_t", columns=["k", "rv"]))
    j = p.add(JoinOp(how="inner", left_on=["k"], right_on=["k"],
                     output=[("left", "k", "k"), ("left", "lv", "lv"),
                             ("right", "rv", "rv")]),
              parents=[left, right])
    p.add(MemorySinkOp(name="out"), parents=[j])

    cluster = LocalCluster({"pem0": ts}, n_devices_per_agent=N_DEV)
    dp = cluster.planner.plan(p)
    assert dp.join_stages and dp.join_stages[0].n_parts == N_DEV
    res = cluster.execute(p)["out"]
    agents = res.exec_stats["agents"]
    assert sum(s.get("mesh_shuffles", 0) for s in agents.values()) >= 2
    single = PlanExecutor(p, ts, mesh=None).run()["out"]
    sharded_parity.assert_bitequal(res, single, keys=("k", "lv", "rv"))


def test_planner_keeps_agent_count_without_explicit_mesh():
    """n_devices=None (auto) must NOT widen the shuffle — the planner
    cannot see a mesh it wasn't told about (existing 2-agent behavior is
    pinned by test_repartition; this pins the single-agent no-op)."""
    from pixie_tpu.parallel.cluster import LocalCluster
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    ts = TableStore()
    for name, col in (("left_t", "lv"), ("right_t", "rv")):
        t = ts.create(name, Relation.of(("k", DT.INT64), (col, DT.INT64)))
        t.write({"k": np.arange(100), col: np.arange(100)})
    cluster = LocalCluster({"pem0": ts})  # auto mesh, planner sees None
    dp = cluster.planner.plan(sharded_parity.join_plan())
    assert not dp.join_stages


# ----------------------------------------------- capacity-bounded exchange
def test_mesh_exchange_extreme_skew_conserves_rows(rng):
    """All rows hashing to ONE partition (worst-case skew) must survive the
    capacity-bounded two-pass exchange intact."""
    from pixie_tpu.engine.executor import HostBatch
    from pixie_tpu.parallel.repartition import mesh_partition_exchange
    from pixie_tpu.types import DataType as DT

    n = 777
    hb = HostBatch({"k": DT.INT64, "v": DT.INT64}, {}, {
        "k": np.full(n, 12345, dtype=np.int64),
        "v": rng.integers(0, 1 << 20, n).astype(np.int64),
    })
    mesh = make_mesh(4)
    out = mesh_partition_exchange(hb, ["k"], 4, mesh)
    sizes = [b.num_rows for b in out]
    assert sum(sizes) == n
    assert sorted(sizes)[-1] == n  # everything in one partition
    got = sorted(np.concatenate([b.cols["v"] for b in out]).tolist())
    assert got == sorted(hb.cols["v"].tolist())


# ------------------------------------------------------ accounting + gate
def test_cluster_transfer_summary_sums_across_shards(rng):
    """stats["h2d_bytes"]/spmd_feeds sum across agents (each itself an
    8-shard mesh) into exec_stats["transfer"], and the worst placement
    skew is carried along."""
    from pixie_tpu.parallel.cluster import LocalCluster
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    now = 1_700_000_000_000_000_000
    stores = {}
    for name in ("pem0", "pem1"):
        ts = TableStore()
        t = ts.create("http_events", Relation.of(
            ("time_", DT.TIME64NS), ("service", DT.STRING),
            ("latency", DT.FLOAT64)), batch_rows=1024)
        m = 16_384
        t.write({"time_": now - np.arange(m, dtype=np.int64)[::-1],
                 "service": rng.choice(["x", "y"], m).tolist(),
                 "latency": rng.exponential(3.0, m)})
        stores[name] = ts
    cl = LocalCluster(stores)
    res = cl.query(
        "import px\ndf = px.DataFrame(table='http_events')\n"
        "df = df.groupby('service').agg(cnt=('latency', px.count))\n"
        "px.display(df)\n", now=now)["output"]
    agents = res.exec_stats["agents"]
    xfer = res.exec_stats["transfer"]
    assert xfer["spmd_feeds"] == sum(
        s.get("spmd_feeds", 0) for s in agents.values()) > 0
    assert xfer["h2d_bytes"] == sum(
        s.get("h2d_bytes", 0) for s in agents.values())
    skews = [s["shard_skew_frac"] for s in agents.values()
             if "shard_skew_frac" in s]
    assert skews and xfer["shard_skew_frac"] == max(skews) >= 1.0
    # per-agent shard accounting covers every mesh shard
    for s in agents.values():
        if s.get("spmd_feeds"):
            assert len(s["shard_rows"]) == 8
            assert sum(s["shard_rows"]) > 0


def test_collective_serialize_gate_auto_and_forced():
    """The XLA-CPU rendezvous workaround is a gated, observable decision:
    auto serializes on an all-CPU mesh (shared intra-op pool), forced-off
    disables it, and the executor records the decision in
    stats["device"]."""
    mesh = make_mesh(4)
    gate = collective_gate(mesh, refresh=True)
    assert gate["serialize"] is True
    assert gate["reason"] == "xla_cpu_shared_pool"
    assert gate["mesh_devices"] == 4
    try:
        flags.set_for_testing("PX_SERIALIZE_CPU_COLLECTIVES", 0)
        off = collective_gate(mesh)
        assert off["serialize"] is False and off["reason"] == "forced_off"
        flags.set_for_testing("PX_SERIALIZE_CPU_COLLECTIVES", 1)
        on = collective_gate(mesh)
        assert on["serialize"] is True and on["reason"] == "forced_on"
    finally:
        flags.set_for_testing("PX_SERIALIZE_CPU_COLLECTIVES", -1)
        collective_gate(mesh, refresh=True)

    ts = sharded_parity.build_store(4096, batch_rows=1024)
    ex = PlanExecutor(sharded_parity.agg_plan(), ts, mesh=make_mesh(N_DEV))
    rec = ex.stats["device"]["collective_gate"]
    assert rec["reason"] == "xla_cpu_shared_pool" and "_key" not in rec


# ------------------------------------------------------- multihost (slow)
@pytest.mark.slow  # two jax.distributed worker processes
def test_sharded_agg_two_process_multihost_small():
    """The 2-process `jax.distributed` run end to end at a small size: each
    worker feeds its host-local shards, the collective merge spans both,
    and process 0 finds the state bit-equal to the single-device kernel's."""
    out = sharded_parity.run_subprocess(200_000)
    assert out["rows"] == 200_000
    assert out["n_devices"] == 8 and out["processes"] == 2
    assert out.get("bit_equal") is True
