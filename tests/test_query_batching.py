"""Concurrent-query batching: shared scans + fused multi-query dispatch.

Covers the ROADMAP item 2 acceptance edges: batched-vs-unbatched
bit-equality (including a sweep over every bundled PxL script), mixed
warm/cold batches, tenant isolation inside a batch, mid-batch agent
eviction (pinned semantic: the lost agent's WHOLE fused fragment
re-dispatches, surviving agents' folded fragments are kept), flag-off
equivalence, the executor's fused multi-query gang (plain + SPMD), the
matview interaction (view-shaped members leave the batch), and the
collector/fusion building blocks.
"""
from __future__ import annotations

import json
import pathlib
import threading
import time

import numpy as np
import pytest

from pixie_tpu import flags, metrics
from pixie_tpu.compiler import compile_pxl
from pixie_tpu.engine import execute_plan
from pixie_tpu.engine.executor import PlanExecutor
from pixie_tpu.parallel.cluster import LocalCluster
from pixie_tpu.serving import batching
from pixie_tpu.services.agent import Agent
from pixie_tpu.services.broker import Broker
from pixie_tpu.services.client import Client
from pixie_tpu.table import TableStore
from pixie_tpu.testing.fixtures import canonical_bytes
from pixie_tpu.types import DataType as DT, Relation

import pixie_tpu.matview  # noqa: F401 — defines PL_MATVIEW_ENABLED

S_SERVICE = """
df = px.DataFrame(table='http_events')
df = df[df.status != 404]
df = df.groupby(['service']).agg(cnt=('latency', px.count),
                                 avg=('latency', px.mean))
px.display(df, 'out')
"""

S_STATUS = """
df = px.DataFrame(table='http_events')
df = df[df.latency > 5.0]
df = df.groupby(['status']).agg(mx=('latency', px.max),
                                p50=('latency', px.p50))
px.display(df, 'out')
"""

S_JOINY = """
left = px.DataFrame(table='http_events')
l = left.groupby('service').agg(cnt=('latency', px.count))
right = px.DataFrame(table='http_events')
r = right.groupby('service').agg(mx=('latency', px.max))
df = l.merge(r, how='inner', left_on='service', right_on='service',
             suffixes=['', '_r'])
px.display(df, 'out')
"""

BATCH_FLAGS = ("PL_QUERY_BATCHING", "PL_BATCH_WINDOW_MS",
               "PL_BATCH_MAX_QUERIES", "PL_MATVIEW_ENABLED",
               "PX_MQ_FUSION")


@pytest.fixture(autouse=True)
def _restore_flags():
    saved = {n: flags.get(n) for n in BATCH_FLAGS}
    yield
    for n, v in saved.items():
        flags.set_for_testing(n, v)


def _mkstore(seed, n=30_000):
    rng = np.random.default_rng(seed)
    ts = TableStore()
    rel = Relation.of(
        ("time_", DT.TIME64NS), ("service", DT.STRING),
        ("latency", DT.FLOAT64), ("status", DT.INT64),
    )
    t = ts.create("http_events", rel, batch_rows=1 << 13, max_bytes=1 << 32)
    svc = np.array([f"svc-{i}" for i in range(6)])
    t.write({
        "time_": np.arange(n, dtype=np.int64) * 1000,
        "service": svc[rng.integers(0, len(svc), n)],
        "latency": rng.exponential(20.0, n),
        "status": rng.choice([200, 404, 500], n),
    })
    return ts


def _canon(results) -> bytes:
    return canonical_bytes(results)


# ------------------------------------------------------------- groupability


def test_group_key_shapes():
    ts = _mkstore(1, n=2000)
    cluster = LocalCluster({"pem0": ts})
    q = compile_pxl(S_SERVICE, cluster.schemas())
    assert batching.group_key(q.plan) == ("http_events", None, None, None)
    qj = compile_pxl(S_JOINY, cluster.schemas())
    assert batching.group_key(qj.plan) is None  # joins never batch


def test_view_shaped_detection():
    ts = _mkstore(2, n=2000)
    cluster = LocalCluster({"pem0": ts})
    q = compile_pxl(S_SERVICE, cluster.schemas())
    assert batching.view_shaped(q.plan)
    qj = compile_pxl(S_JOINY, cluster.schemas())
    assert not batching.view_shaped(qj.plan)
    flags.set_for_testing("PL_MATVIEW_ENABLED", True)
    assert batching.leaves_for_matview(q.plan)
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)
    assert not batching.leaves_for_matview(q.plan)


# ---------------------------------------------------------------- collector


def test_collector_window_and_slot_order():
    c = batching.BatchCollector()
    m1 = batching.Member(("b",), None)
    m2 = batching.Member(("a",), None)
    got = {}

    def joiner():
        res = c.collect("k", m2, window_s=5.0, max_n=4, wait=True)
        got["m2"] = res

    t = threading.Thread(target=joiner)

    def leader():
        got["m1"] = c.collect("k", m1, window_s=5.0, max_n=2, wait=True)

    tl = threading.Thread(target=leader)
    tl.start()
    time.sleep(0.1)
    t.start()
    tl.join(timeout=10)
    t.join(timeout=10)
    # max_n=2 filled the batch: leader returned both, sorted by key
    assert got["m1"] is not None and got["m2"] is None
    assert [m.key for m in got["m1"]] == [("a",), ("b",)]
    m2.deliver({"ok": 1}, {})
    assert m2.wait(1.0)[0] == {"ok": 1}


def test_collector_solo_leader_never_waits_when_idle():
    c = batching.BatchCollector()
    m = batching.Member(("a",), None)
    t0 = time.monotonic()
    got = c.collect("k", m, window_s=2.0, max_n=8)  # wait=None: not busy
    assert time.monotonic() - t0 < 1.0
    assert got == [m]


def test_dedup_slots_and_signature():
    ms = [batching.Member(("a",), "PA"), batching.Member(("a",), "PA"),
          batching.Member(("b",), "PB")]
    plans, slots = batching.dedup_slots(ms)
    assert plans == ["PA", "PB"] and slots == [0, 0, 1]
    assert batching.batch_signature(ms) == (repr(("a",)), repr(("b",)))


# ----------------------------------------------- fused plan + bit-equality


def test_fused_plan_bit_equal_and_scan_shared():
    ts = _mkstore(3)
    cluster = LocalCluster({"pem0": ts})
    q1 = compile_pxl(S_SERVICE, cluster.schemas())
    q2 = compile_pxl(S_STATUS, cluster.schemas())
    fused, sink_map = batching.fuse_members(
        [("q0", q1.plan), ("q1", q2.plan)], cluster.schemas())
    # the shared scan merged: ONE MemorySourceOp feeds both chains
    from pixie_tpu.plan.plan import MemorySourceOp

    scans = [o for o in fused.ops() if isinstance(o, MemorySourceOp)]
    assert len(scans) == 1
    res = cluster.execute(fused)
    b1 = cluster.execute(q1.plan)
    b2 = cluster.execute(q2.plan)
    d1 = batching.demux_results(res, sink_map, "q0")
    d2 = batching.demux_results(res, sink_map, "q1")
    assert _canon(d1) == _canon(b1)
    assert _canon(d2) == _canon(b2)
    # demuxed results carry the ORIGINAL sink names
    assert set(d1) == {"out"} and d1["out"].name == "out"


def test_identical_members_share_one_computed_slot():
    ts = _mkstore(4)
    cluster = LocalCluster({"pem0": ts})
    q = compile_pxl(S_SERVICE, cluster.schemas())
    fused, sink_map = batching.fuse_members(
        [("q0", q.plan), ("q1", q.plan)], cluster.schemas())
    from pixie_tpu.plan.plan import AggOp

    # identical chains hash-cons: ONE agg computes both slots' sinks
    assert len([o for o in fused.ops() if isinstance(o, AggOp)]) == 1
    res = cluster.execute(fused)
    base = cluster.execute(q.plan)
    for prefix in ("q0", "q1"):
        assert _canon(batching.demux_results(res, sink_map, prefix)) \
            == _canon(base)


# ------------------------------------------- bundled-script sweep (ratchet)

SCRIPTS = pathlib.Path("/root/reference/src/pxl_scripts/px")
SEC = 1_000_000_000
NOW = 600 * SEC


def _bundled_targets():
    """Every bundled script's compile targets, reference checkout plus the
    repo-shipped scripts — skipped per script when its tables are absent
    from the demo store."""
    from pixie_tpu.scripts import script_dirs

    import tests.test_all_scripts as harness

    out = []
    for d in script_dirs():
        vis_path = d / "vis.json"
        vis = json.loads(vis_path.read_text()) if vis_path.exists() else {}
        funcs = harness._funcs_to_compile(vis)
        try:
            source = harness._source_of(d)
        except AssertionError:
            continue
        out.append((d.name, source, funcs or [(None, None)]))
    return out


def test_batched_bit_equality_all_bundled_scripts():
    """For every bundled PxL script: a groupable plan fused with itself
    (the minimal 2-member batch) answers BIT-equal to the solo run; a
    non-groupable plan is proven to fall back (group_key None).  The sweep
    runs whatever bundle is present — the reference checkout when mounted,
    always the repo-shipped scripts."""
    from pixie_tpu.collect.schemas import all_schemas
    from pixie_tpu.metadata.state import global_manager, set_global_manager
    from pixie_tpu.testing import build_demo_store, demo_metadata

    old = global_manager()
    mgr, _upids, _ips = demo_metadata()
    set_global_manager(mgr)
    try:
        store = build_demo_store(rows=2000, now_ns=NOW)
        schemas = all_schemas()
        store_tables = set(store.schemas())
        checked = fused_n = fallback_n = 0
        for name, source, targets in _bundled_targets():
            for fname, fargs in targets:
                try:
                    q = compile_pxl(source, schemas, func=fname,
                                    func_args=fargs, now=NOW)
                except Exception:
                    continue  # compile scope is test_all_scripts' ratchet
                if q.mutations:
                    continue
                gk = batching.group_key(q.plan)
                if gk is None:
                    fallback_n += 1  # proven non-groupable: unbatched path
                    continue
                tables = {op.table for op in q.plan.ops()
                          if getattr(op, "kind", "") == "memorysource"}
                if not tables <= store_tables:
                    continue
                base = execute_plan(q.plan, store)
                fused, sink_map = batching.fuse_members(
                    [("q0", q.plan), ("q1", q.plan)], schemas)
                res = execute_plan(fused, store)
                for prefix in ("q0", "q1"):
                    got = batching.demux_results(res, sink_map, prefix)
                    assert _canon(got) == _canon(base), \
                        f"{name}:{fname}: batched != unbatched"
                fused_n += 1
                checked += 1
        # the reference bundle has many groupable dashboards; the repo-
        # shipped fallback bundle may have none on an unmounted box — the
        # synthetic-script tests above cover the fused path there
        if SCRIPTS.is_dir():
            assert fused_n >= 1, "no groupable bundled script was exercised"
            assert fused_n + fallback_n >= 1, "sweep classified nothing"
    finally:
        set_global_manager(old)


# ------------------------------------------------ cluster + broker batching


def _rows(r):
    names = r.relation.names()
    return names, sorted(map(tuple, zip(*[map(str, r.decoded(n))
                                          for n in names])))


def test_cluster_concurrent_batches_bit_equal_and_counted():
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)
    flags.set_for_testing("PL_BATCH_WINDOW_MS", 100.0)
    cluster = LocalCluster({"pem0": _mkstore(5)})
    flags.set_for_testing("PL_QUERY_BATCHING", False)
    b1 = cluster.query(S_SERVICE)["out"]
    b2 = cluster.query(S_STATUS)["out"]
    flags.set_for_testing("PL_QUERY_BATCHING", True)
    formed0 = metrics.counter_value("px_batch_formed_total")
    errs = []

    def run(script, base):
        try:
            for _ in range(6):
                r = cluster.query(script)["out"]
                assert _rows(r) == _rows(base)
        except Exception as e:  # pragma: no cover — surfaced below
            errs.append(e)

    ts_ = [threading.Thread(target=run, args=(S_SERVICE, b1)),
           threading.Thread(target=run, args=(S_STATUS, b2))]
    for t in ts_:
        t.start()
    for t in ts_:
        t.join(timeout=120)
    assert not errs, errs
    assert metrics.counter_value("px_batch_formed_total") > formed0


def test_cluster_flag_off_is_pre_batching_path():
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)
    flags.set_for_testing("PL_QUERY_BATCHING", False)
    cluster = LocalCluster({"pem0": _mkstore(6)})
    formed0 = metrics.counter_value("px_batch_formed_total")
    r1 = cluster.query(S_SERVICE)["out"]
    r2 = cluster.query(S_SERVICE)["out"]  # warm repeat
    assert _rows(r1) == _rows(r2)
    assert "batch" not in r1.exec_stats
    assert metrics.counter_value("px_batch_formed_total") == formed0


def test_matview_shaped_member_leaves_batch_and_still_serves():
    flags.set_for_testing("PL_MATVIEW_ENABLED", True)
    cluster = LocalCluster({"pem0": _mkstore(7)})
    base = cluster.query(S_SERVICE)["out"]  # first sight registers the view
    fb0 = metrics.counter_value("px_batch_fallback_total",
                                labels={"reason": "matview"})
    r = cluster.query(S_SERVICE)["out"]  # second sight: view serve
    assert _rows(r) == _rows(base)
    assert metrics.counter_value(
        "px_batch_fallback_total", labels={"reason": "matview"}) > fb0
    assert "batch" not in r.exec_stats


def _broker_pair(stores, agent_cls=Agent, **kw):
    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    agents = [agent_cls(n, "127.0.0.1", broker.port, store=st,
                        heartbeat_s=0.2).start() for n, st in stores.items()]
    deadline = time.monotonic() + 5.0
    while (len(broker.registry.live_agents()) < len(stores)
           and time.monotonic() < deadline):
        time.sleep(0.02)
    return broker, agents


def test_broker_mixed_warm_cold_batch_and_tenant_isolation():
    """A warm member (plan-cache hit) and a cold member (first sight) batch
    together; members of DIFFERENT tenants share the batch while their
    plan-cache entries stay namespaced; every member's answer is bit-equal
    to its solo baseline."""
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)
    flags.set_for_testing("PL_BATCH_WINDOW_MS", 150.0)
    broker, agents = _broker_pair({"pem1": _mkstore(8), "pem2": _mkstore(9)})
    try:
        flags.set_for_testing("PL_QUERY_BATCHING", False)
        base1, _ = broker.execute_script(S_SERVICE, tenant="tA")  # warms tA
        base2, _ = broker.execute_script(S_STATUS, tenant="tB")
        flags.set_for_testing("PL_QUERY_BATCHING", True)
        got = {}
        errs = []

        def run(tag, script, tenant):
            try:
                for _ in range(5):
                    res, st = broker.execute_script(script, tenant=tenant)
                    got.setdefault(tag, []).append((res, st))
            except Exception as e:  # pragma: no cover
                errs.append(e)

        # tA is WARM for S_SERVICE; tC has never been seen (cold member)
        ts_ = [threading.Thread(target=run, args=("warm", S_SERVICE, "tA")),
               threading.Thread(target=run, args=("cold", S_SERVICE, "tC")),
               threading.Thread(target=run, args=("other", S_STATUS, "tB"))]
        for t in ts_:
            t.start()
        for t in ts_:
            t.join(timeout=120)
        assert not errs, errs
        for tag, base in (("warm", base1), ("cold", base1),
                          ("other", base2)):
            for res, _st in got[tag]:
                assert _canon(res) == _canon(base), tag
        sizes = [st["batch"]["size"] for rs in got.values()
                 for _res, st in rs if st.get("batch")]
        assert sizes and max(sizes) >= 2, "no batch formed"
        # tenant isolation: tA and tC hold SEPARATE namespaced plan-cache
        # entries for the same script (batching must not collapse them)
        ns = {k[0] for k in broker.plan_cache._entries}
        assert {"tA", "tC"} <= ns
    finally:
        for a in agents:
            a.stop()
        broker.stop()


class _DieOnceAgent(Agent):
    """Once ARMED, the next execute sends one chunk then drops the
    connection (mid-stream producer death); un-armed executes run
    normally so baselines can be computed through the same deployment."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.armed = False
        self.died = False

    def _execute(self, meta):
        if self.died or not self.armed:
            return super()._execute(meta)
        self.died = True
        from pixie_tpu.plan.plan import Plan
        from pixie_tpu.services import wire

        plan = Plan.from_dict(meta["plan"])
        ex = PlanExecutor(plan, self.store, self.registry)
        for channel, payload in ex.run_agent_stream(agg_chunk_groups=1):
            self.conn.send(wire.encode_partial_agg(payload, {
                "msg": "chunk", "req_id": meta.get("req_id"),
                "channel": channel, "seq": 0, "agent": self.name,
                "qtoken": meta.get("qtoken"),
                "attempt": meta.get("attempt"),
            }))
            break
        self.conn.close()


def test_mid_batch_agent_eviction_redispatches_whole_fused_fragment():
    """PINNED semantic: when an agent dies mid-batch, PR 9's re-dispatch
    replays that agent's WHOLE fused fragment (every member's chains on the
    lost agent) onto its restarted incarnation; surviving agents' folded
    fragments are kept.  All members recover bit-equal with zero errors."""
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)
    flags.set_for_testing("PL_BATCH_WINDOW_MS", 300.0)
    flags.set_for_testing("PL_QUERY_RETRIES", 6)
    flags.set_for_testing("PL_RETRY_BACKOFF_MS", 100)
    stores = {"pem1": _mkstore(10), "pem2": _mkstore(11)}
    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    a1 = Agent("pem1", "127.0.0.1", broker.port, store=stores["pem1"],
               heartbeat_s=0.2).start()
    a2 = _DieOnceAgent("pem2", "127.0.0.1", broker.port,
                       store=stores["pem2"], heartbeat_s=0.2)
    restarted = {}

    def restarter():
        while not a2.died:
            time.sleep(0.01)
        time.sleep(0.15)
        restarted["agent"] = Agent("pem2", "127.0.0.1", broker.port,
                                   store=stores["pem2"],
                                   heartbeat_s=0.2).start()

    try:
        a2.start()
        deadline = time.monotonic() + 5.0
        while (len(broker.registry.live_agents()) < 2
               and time.monotonic() < deadline):
            time.sleep(0.02)
        flags.set_for_testing("PL_QUERY_BATCHING", False)
        base1, _ = broker.execute_script(S_SERVICE)
        base2, _ = broker.execute_script(S_STATUS)
        flags.set_for_testing("PL_QUERY_BATCHING", True)
        # deterministic single-round batch formation: force the leader to
        # wait its window (the test seam; production leaders wait only
        # under concurrent gate traffic)
        broker._batcher.force_wait = True
        a2.armed = True
        threading.Thread(target=restarter, daemon=True).start()
        got = {}
        errs = []

        def run(tag, script):
            try:
                got[tag] = broker.execute_script(script)
            except Exception as e:
                errs.append((tag, e))

        ts_ = [threading.Thread(target=run, args=("a", S_SERVICE)),
               threading.Thread(target=run, args=("b", S_STATUS))]
        for t in ts_:
            t.start()
        for t in ts_:
            t.join(timeout=60)
        assert not errs, errs
        res_a, st_a = got["a"]
        res_b, st_b = got["b"]
        assert _canon(res_a) == _canon(base1)
        assert _canon(res_b) == _canon(base2)
        # the batch formed AND recovered: the fused fragment re-dispatched
        # as a whole (one carrier query, so both members share the rounds)
        batched = [st for st in (st_a, st_b) if st.get("batch")]
        assert batched, "queries did not batch"
        assert batched[0]["fault"]["rounds"] >= 1
        assert batched[0]["fault"]["redispatched"] == ["pem2"]
    finally:
        for a in [a1, a2, restarted.get("agent")]:
            if a is not None:
                a.stop()
        broker.stop()


# --------------------------------------------------- executor fused gang


def test_mq_gang_spmd_bit_equal():
    """With a device mesh, ≥2 sibling partial aggs over one shared scan
    execute as ONE fused SPMD program per wave — bit-equal (wire bytes) to
    the per-sink path."""
    flags.set_for_testing("PX_MQ_FUSION", 1)
    cluster = LocalCluster({"pem0": _mkstore(12)})
    q1 = compile_pxl(S_SERVICE, cluster.schemas())
    q2 = compile_pxl(S_STATUS, cluster.schemas())
    fused, _sm = batching.fuse_members(
        [("q0", q1.plan), ("q1", q2.plan)], cluster.schemas())
    dp = cluster.planner.plan(fused)
    ap = dp.agent_plans["pem0"]
    mesh = cluster._agent_mesh("pem0")
    if mesh in (None, "auto"):
        from pixie_tpu.parallel.spmd import default_mesh

        mesh = default_mesh()
    if mesh is None:
        pytest.skip("no multi-device mesh available")
    ex = PlanExecutor(ap, cluster.stores["pem0"], None, mesh=mesh)
    out = ex.run_agent()
    assert ex.stats.get("mq_fused") == 2
    flags.set_for_testing("PX_MQ_FUSION", 0)
    ex2 = PlanExecutor(ap, cluster.stores["pem0"], None, mesh=mesh)
    base = ex2.run_agent()
    assert "mq_fused" not in ex2.stats
    for cid in out:
        assert out[cid].to_bytes() == base[cid].to_bytes(), cid


def test_mq_gang_plain_bit_equal():
    """Accelerator-routed (forced), meshless executors fuse the sibling
    chains into one jitted program per wave too."""
    flags.set_for_testing("PX_MQ_FUSION", 1)
    cluster = LocalCluster({"pem0": _mkstore(13)}, n_devices_per_agent=1)
    q1 = compile_pxl(S_SERVICE, cluster.schemas())
    q2 = compile_pxl(S_STATUS, cluster.schemas())
    fused, _sm = batching.fuse_members(
        [("q0", q1.plan), ("q1", q2.plan)], cluster.schemas())
    ap = cluster.planner.plan(fused).agent_plans["pem0"]
    ex = PlanExecutor(ap, cluster.stores["pem0"], None, mesh=None,
                      force_backend="device")
    out = ex.run_agent()
    assert ex.stats.get("mq_fused") == 2
    assert ex.stats.get("mq_waves", 0) >= 1
    flags.set_for_testing("PX_MQ_FUSION", 0)
    ex2 = PlanExecutor(ap, cluster.stores["pem0"], None, mesh=None,
                       force_backend="device")
    base = ex2.run_agent()
    for cid in out:
        assert out[cid].to_bytes() == base[cid].to_bytes(), cid


def test_mq_gang_auto_off_on_cpu_only_box():
    """PX_MQ_FUSION=-1 (auto) keeps the gang off when no real accelerator
    backs the devices — XLA-CPU per-chain-set compiles cost more than the
    fused execution saves (the per-sink np_partial/wholeplan paths win)."""
    import jax

    if any(d.platform != "cpu" for d in jax.devices()):
        pytest.skip("accelerator present: auto mode legitimately fuses")
    flags.set_for_testing("PX_MQ_FUSION", -1)
    cluster = LocalCluster({"pem0": _mkstore(14)}, n_devices_per_agent=1)
    q1 = compile_pxl(S_SERVICE, cluster.schemas())
    q2 = compile_pxl(S_STATUS, cluster.schemas())
    fused, _sm = batching.fuse_members(
        [("q0", q1.plan), ("q1", q2.plan)], cluster.schemas())
    ap = cluster.planner.plan(fused).agent_plans["pem0"]
    ex = PlanExecutor(ap, cluster.stores["pem0"], None, mesh=None,
                      force_backend="device")
    ex.run_agent()
    assert "mq_fused" not in ex.stats
