"""Plan-verifier golden tests: every bundled script verifies clean through
the real dispatch paths, and seeded miscompile mutations — a dtype flip
across a shuffle, a dropped combine path, a diverged matview prefix,
mismatched partition counts, dictionary state in a cross-agent partial —
are each rejected pre-dispatch with the RIGHT structured invariant.
"""
from __future__ import annotations

import copy
import json
import time

import numpy as np
import pytest

from pixie_tpu import flags, metrics
from pixie_tpu.check.planverify import (
    PlanVerifyError,
    verify_distributed,
    verify_plan,
)
from pixie_tpu.compiler import compile_pxl
from pixie_tpu.parallel.distributed import DistributedPlanner
from pixie_tpu.parallel.topology import AgentInfo, ClusterSpec
from pixie_tpu.plan.plan import (
    AggExpr,
    AggOp,
    Call,
    Column,
    FilterOp,
    Literal,
    MapOp,
    MemorySinkOp,
    MemorySourceOp,
    Plan,
    UnionOp,
)
from pixie_tpu.types import DataType as DT, Relation

NOW = 1_700_000_000_000_000_000

HTTP_REL = Relation.of(
    ("time_", DT.TIME64NS),
    ("service", DT.STRING),
    ("latency", DT.FLOAT64),
    ("status", DT.INT64),
)
CONN_REL = Relation.of(
    ("time_", DT.TIME64NS),
    ("service", DT.STRING),
    ("bytes", DT.INT64),
)
SCHEMAS = {"http_events": HTTP_REL, "conn_stats": CONN_REL}


def _spec(n_agents: int = 2) -> ClusterSpec:
    agents = [
        AgentInfo(name=f"pem{i}", has_data_store=True, processes_data=True,
                  accepts_remote_sources=False, schemas=SCHEMAS)
        for i in range(n_agents)
    ]
    agents.append(AgentInfo(name="merger", has_data_store=False,
                            processes_data=False,
                            accepts_remote_sources=True, schemas={}))
    return ClusterSpec(agents)


def _split(src: str):
    q = compile_pxl(src, SCHEMAS, now=NOW)
    return DistributedPlanner(_spec()).plan(q.plan)


AGG_SRC = """
import px
df = px.DataFrame(table='http_events')
df = df[df.status != 404]
df = df.groupby(['service']).agg(total=('latency', px.sum),
                                 cnt=('latency', px.count))
px.display(df, 'out')
"""

LIMIT_SRC = """
import px
df = px.DataFrame(table='http_events')
df = df.head(20)
px.display(df, 'out')
"""

JOIN_SRC = """
import px
a = px.DataFrame(table='http_events')
b = px.DataFrame(table='conn_stats')
j = a.merge(b, how='inner', left_on=['service'], right_on=['service'],
            suffixes=['', '_r'])
px.display(j, 'out')
"""


def _rebuild_chain(plan: Plan, transform) -> Plan:
    """Rebuild a LINEAR agent plan (source→...→sink) with `transform`
    applied to the copied op list — the mutation seam for golden tests."""
    ops = [copy.copy(o) for o in plan.topo_sorted()]
    new = Plan()
    node = None
    for op in transform(ops):
        op.id = -1
        node = new.add(op, parents=[] if node is None else [node])
    return new


# ------------------------------------------------------- logical plan rules


def test_unknown_table_rejected():
    p = Plan()
    src = p.add(MemorySourceOp(table="nope"))
    p.add(MemorySinkOp(name="out"), parents=[src])
    with pytest.raises(PlanVerifyError) as ei:
        verify_plan(p, SCHEMAS)
    assert ei.value.invariant == "unknown-table"


def test_filter_not_boolean_rejected():
    p = Plan()
    src = p.add(MemorySourceOp(table="http_events"))
    f = p.add(FilterOp(expr=Column("latency")), parents=[src])
    p.add(MemorySinkOp(name="out"), parents=[f])
    with pytest.raises(PlanVerifyError) as ei:
        verify_plan(p, SCHEMAS)
    assert ei.value.invariant == "filter-not-boolean"
    assert ei.value.op_kind == "filter"


def test_union_schema_mismatch_rejected():
    p = Plan()
    a = p.add(MemorySourceOp(table="http_events"))
    b = p.add(MemorySourceOp(table="conn_stats"))
    u = p.add(UnionOp(), parents=[a, b])
    p.add(MemorySinkOp(name="out"), parents=[u])
    with pytest.raises(PlanVerifyError) as ei:
        verify_plan(p, SCHEMAS)
    assert ei.value.invariant == "union-schema"


def test_windowed_agg_without_time_group_rejected():
    p = Plan()
    src = p.add(MemorySourceOp(table="http_events"))
    agg = AggOp(groups=["service"],
                values=[AggExpr("cnt", "count", None)], windowed=True)
    p.add(agg, parents=[src])
    with pytest.raises(PlanVerifyError) as ei:
        verify_plan(p, SCHEMAS)
    assert ei.value.invariant == "windowed-agg-no-time"


def test_negative_limit_rejected():
    q = compile_pxl(LIMIT_SRC, SCHEMAS, now=NOW)
    for op in q.plan.ops():
        if op.kind == "limit":
            op.n = -5
    with pytest.raises(PlanVerifyError) as ei:
        verify_plan(q.plan, SCHEMAS)
    assert ei.value.invariant == "bad-limit"


# --------------------------------------------------- seeded split mutations


def test_mutation_dtype_flip_across_shuffle_rejected():
    """One producer's fragment silently re-types a column (FLOAT64 latency
    becomes INT64): the channel's consumers would fold mismatched buffers."""
    dp = _split(LIMIT_SRC)
    victim = sorted(dp.agent_plans)[1]

    def flip(ops):
        *chain, sink = ops
        cast = MapOp(exprs=[
            ("time_", Column("time_")), ("service", Column("service")),
            ("latency", Column("status")),  # INT64 masquerading as latency
            ("status", Column("status")),
        ])
        return chain + [cast, sink]

    dp.agent_plans[victim] = _rebuild_chain(dp.agent_plans[victim], flip)
    with pytest.raises(PlanVerifyError) as ei:
        verify_distributed(dp, SCHEMAS)
    assert ei.value.invariant == "shuffle-schema-mismatch"


def test_mutation_dropped_combine_path_rejected():
    """The agg_state channel's declared agg references a UDA with no
    registered combine path — the PR 9 fold-correctness linchpin."""
    dp = _split(AGG_SRC)
    (cid, ch), = [(c, ch) for c, ch in dp.channels.items()
                  if ch.kind == "agg_state"]
    # rebind a FRESH values list: cut_agg shallow-copies the logical agg,
    # so in-place list mutation would also mutate every producer's partial
    ch.agg = copy.copy(ch.agg)
    ch.agg.values = [AggExpr(ch.agg.values[0].out_name, "no_such_uda",
                             ch.agg.values[0].arg)] + list(ch.agg.values[1:])
    with pytest.raises(PlanVerifyError) as ei:
        verify_distributed(dp, SCHEMAS)
    assert ei.value.invariant == "not-mergeable"
    assert cid in str(ei.value)


def test_mutation_dict_state_in_cross_agent_partial_rejected():
    """Dictionary-coded UDA state (any over a STRING column) must never
    cross agents: each agent's code space is private."""
    dp = _split(AGG_SRC)
    (ch,) = [ch for ch in dp.channels.values() if ch.kind == "agg_state"]
    ch.agg.values.append(AggExpr("svc", "any", "service"))
    with pytest.raises(PlanVerifyError) as ei:
        verify_distributed(dp, SCHEMAS)
    assert ei.value.invariant == "partial-dict-agg"


def test_mutation_partition_count_mismatch_rejected():
    """The join stage consumes a different partition count than its
    producers exchange — the shard-axis consistency contract."""
    dp = _split(JOIN_SRC)
    assert dp.join_stages, "fixture must produce a repartitioned join"
    dp.join_stages[0].n_parts += 1
    with pytest.raises(PlanVerifyError) as ei:
        verify_distributed(dp, SCHEMAS)
    assert ei.value.invariant == "partition-count-mismatch"


def test_mutation_matview_prefix_divergence_rejected():
    """Two producers of one standing-query channel disagree on a filter
    constant: dtypes agree, the agg agrees — only the fragment CONTENT
    (what matview state is a function of) diverges."""
    dp = _split(AGG_SRC)
    victim = sorted(dp.agent_plans)[1]

    def reconstant(ops):
        out = []
        for op in ops:
            if isinstance(op, FilterOp):
                op = FilterOp(expr=Call("not_equal", (
                    Column("status"), Literal(500, DT.INT64))))
            out.append(op)
        return out

    dp.agent_plans[victim] = _rebuild_chain(dp.agent_plans[victim],
                                            reconstant)
    with pytest.raises(PlanVerifyError) as ei:
        verify_distributed(dp, SCHEMAS)
    assert ei.value.invariant == "matview-prefix-divergence"


def test_mutation_sole_producer_on_a_shared_channel_rejected():
    """One of a channel's two producers is marked its sole producer: it
    would ship finalized sketches that the merger cannot add to the other
    producer's state."""
    dp = _split(AGG_SRC)
    victim = sorted(dp.agent_plans)[1]

    def mark(ops):
        for op in ops:
            if isinstance(op, AggOp):
                op.sole_producer = True
        return ops

    dp.agent_plans[victim] = _rebuild_chain(dp.agent_plans[victim], mark)
    with pytest.raises(PlanVerifyError) as ei:
        verify_distributed(dp, SCHEMAS)
    assert ei.value.invariant == "sole-producer"


@pytest.mark.parametrize("n_agents,n_devices", [(1, None), (1, 4), (2, None),
                                               (3, 4)])
def test_split_marks_the_partial_of_a_sole_producer(n_agents, n_devices):
    """The planner marks each producer's partial agg `sole_producer` where
    its channel has one producer (a mesh agent is one) and never where it
    has more; the mark survives the agent plan's wire form, and the split
    verifies."""
    spec = _spec(n_agents)
    for a in spec.agents:
        if a.has_data_store:
            a.n_devices = n_devices
    q = compile_pxl(AGG_SRC, SCHEMAS, now=NOW)
    dp = DistributedPlanner(spec).plan(q.plan)
    verify_distributed(dp, SCHEMAS)
    partials = [op for p in dp.agent_plans.values()
                for op in Plan.from_dict(json.loads(json.dumps(
                    p.to_dict()))).ops()
                if isinstance(op, AggOp)]
    assert len(partials) == n_agents
    assert all(op.partial and op.sole_producer == (n_agents == 1)
               for op in partials)
    (ch,) = [ch for ch in dp.channels.values() if ch.kind == "agg_state"]
    assert not ch.agg.sole_producer


def test_unmutated_splits_verify_clean():
    for src in (AGG_SRC, LIMIT_SRC, JOIN_SRC):
        verify_distributed(_split(src), SCHEMAS)


# ------------------------------------------------- all bundled scripts pass


def test_all_bundled_scripts_verify_clean():
    """Every bundled script's every vis func compiles, splits over a
    2-agent topology, and verifies clean (61/61 when the reference bundle
    checkout is present; the repo bundle otherwise)."""
    from pixie_tpu.collect.schemas import all_schemas
    from pixie_tpu.metadata.state import (
        MetadataStateManager,
        global_manager,
        set_global_manager,
    )
    from pixie_tpu.scripts import script_dirs
    from pixie_tpu.trace import SPANS_RELATION, SPANS_TABLE

    schemas = dict(all_schemas())
    schemas[SPANS_TABLE] = SPANS_RELATION
    agents = [AgentInfo(name=f"pem{i}", has_data_store=True,
                        processes_data=True, accepts_remote_sources=False,
                        schemas=schemas) for i in (1, 2)]
    agents.append(AgentInfo(name="merger", has_data_store=False,
                            processes_data=False,
                            accepts_remote_sources=True, schemas={}))
    spec = ClusterSpec(agents)
    old = global_manager()
    set_global_manager(MetadataStateManager(asid=1, node_name="node-1"))
    verified = 0
    try:
        for d in script_dirs():
            src = sorted(d.glob("*.pxl"))[0].read_text()
            visp = d / "vis.json"
            vis = json.loads(visp.read_text()) if visp.exists() else {}
            vals = {v["name"]: v.get("defaultValue", "-5m")
                    for v in vis.get("variables", [])}
            funcs = []
            for w in (vis.get("widgets", [])
                      + [{"func": g["func"]}
                         for g in vis.get("globalFuncs", [])]):
                f = w.get("func")
                if f:
                    funcs.append((f["name"], {
                        a["name"]: a.get("value", vals.get(a.get("variable")))
                        for a in f.get("args", [])}))
            for fname, fargs in (funcs or [(None, None)]):
                try:
                    q = compile_pxl(src, schemas, func=fname,
                                    func_args=fargs)
                except Exception:
                    continue  # compile coverage is test_all_scripts' job
                dp = DistributedPlanner(spec).plan(q.plan)
                verify_distributed(dp, schemas)  # must not raise
                verified += 1
    finally:
        set_global_manager(old)
    assert verified >= 1


def _drop_combine_path(ch) -> None:
    """Seed the dropped-combine miscompile on a channel spec only (fresh
    values list — the producers' shallow-shared list stays intact)."""
    ch.agg = copy.copy(ch.agg)
    ch.agg.values = [AggExpr(ch.agg.values[0].out_name, "no_such_uda",
                             ch.agg.values[0].arg)] + list(ch.agg.values[1:])


# --------------------------------------------- enforcement: LocalCluster


def _mkstore(seed: int):
    from pixie_tpu.table import TableStore

    rng = np.random.default_rng(seed)
    ts = TableStore()
    t = ts.create("http_events", HTTP_REL, batch_rows=1024)
    n = 2000
    t.write({
        "time_": NOW - np.arange(n, dtype=np.int64)[::-1] * 1_000_000,
        "service": rng.choice(["cart", "frontend"], n).tolist(),
        "latency": rng.exponential(10.0, n),
        "status": rng.choice([200, 404, 500], n),
    })
    return ts


def test_localcluster_verifies_and_caches():
    """Cold query verifies once; the warm repeat hits the split cache and
    pays ZERO re-verification (the signature-cached contract)."""
    from pixie_tpu.parallel.cluster import LocalCluster

    cluster = LocalCluster({"pem0": _mkstore(0), "pem1": _mkstore(1)})
    before = metrics.counter_value("px_plan_verify_total")
    r1 = cluster.query(AGG_SRC)["out"]
    mid = metrics.counter_value("px_plan_verify_total")
    assert mid == before + 1
    r2 = cluster.query(AGG_SRC)["out"]
    assert metrics.counter_value("px_plan_verify_total") == mid, \
        "warm split-cache hit must not re-verify"
    assert r1.num_rows == r2.num_rows > 0


def test_localcluster_rejects_mutated_split_pre_dispatch():
    from pixie_tpu.parallel.cluster import LocalCluster

    cluster = LocalCluster({"pem0": _mkstore(0), "pem1": _mkstore(1)})
    q = compile_pxl(AGG_SRC, cluster.schemas(), now=NOW)

    real = cluster.planner

    class Mutator:
        def plan(self, logical):
            dp = real.plan(logical)
            for ch in dp.channels.values():
                if ch.kind == "agg_state":
                    _drop_combine_path(ch)
            return dp

    cluster.planner = Mutator()
    fails0 = metrics.counter_value("px_plan_verify_failures_total")
    with pytest.raises(PlanVerifyError) as ei:
        cluster.execute(q.plan)
    assert ei.value.invariant == "not-mergeable"
    assert metrics.counter_value("px_plan_verify_failures_total") \
        == fails0 + 1


def test_flag_off_skips_verification():
    from pixie_tpu.parallel.cluster import LocalCluster

    flags.set_for_testing("PX_PLAN_VERIFY", False)
    try:
        cluster = LocalCluster({"pem0": _mkstore(0)})
        before = metrics.counter_value("px_plan_verify_total")
        res = cluster.query(AGG_SRC)["out"]
        assert res.num_rows > 0
        assert metrics.counter_value("px_plan_verify_total") == before
    finally:
        flags.set_for_testing("PX_PLAN_VERIFY", True)


# ------------------------------------------------- enforcement: networked


@pytest.fixture
def broker_cluster():
    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker

    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    stores = {"pem1": _mkstore(1), "pem2": _mkstore(2)}
    agents = [Agent(n, "127.0.0.1", broker.port, store=s,
                    heartbeat_s=1.0).start() for n, s in stores.items()]
    deadline = time.monotonic() + 10
    while len(broker.registry.live_agents()) < 2:
        assert time.monotonic() < deadline, "agents failed to register"
        time.sleep(0.02)
    yield broker
    for a in agents:
        a.stop()
    broker.stop()


def test_broker_verifies_clean_and_serves(broker_cluster):
    before = metrics.counter_value("px_plan_verify_total")
    res, _stats = broker_cluster.execute_script(AGG_SRC)
    assert res["out"].num_rows > 0
    assert metrics.counter_value("px_plan_verify_total") == before + 1


def test_broker_rejects_mutated_split_pre_dispatch(broker_cluster,
                                                   monkeypatch):
    """A miscompiled split never reaches an agent: the broker raises the
    structured PlanVerifyError BEFORE any execute frame is sent."""
    from pixie_tpu.services import broker as broker_mod

    real = broker_mod.DistributedPlanner

    class Mutating:
        def __init__(self, spec):
            self._inner = real(spec)

        def plan(self, logical):
            dp = self._inner.plan(logical)
            for ch in dp.channels.values():
                if ch.kind == "agg_state":
                    _drop_combine_path(ch)
            return dp

    monkeypatch.setattr(broker_mod, "DistributedPlanner", Mutating)
    # distinct script text: must miss the whole-query plan cache
    src = AGG_SRC.replace("'out'", "'out2'")
    with pytest.raises(PlanVerifyError) as ei:
        broker_cluster.execute_script(src)
    assert ei.value.invariant == "not-mergeable"
    # nothing was dispatched — no query context leaked
    assert not broker_cluster._queries


# --------------------------------------------- fused multi-query (batch) form

AGG2_SRC = """
import px
df = px.DataFrame(table='http_events')
df = df.groupby(['status']).agg(mx=('latency', px.max))
px.display(df, 'out')
"""


def _fused_batch_split():
    from pixie_tpu.serving import batching

    q1 = compile_pxl(AGG_SRC, SCHEMAS, now=NOW)
    q2 = compile_pxl(AGG2_SRC, SCHEMAS, now=NOW)
    fused, sink_map = batching.fuse_members(
        [("q0", q1.plan), ("q1", q2.plan)], SCHEMAS)
    dp = DistributedPlanner(_spec()).plan(fused)
    return dp, sink_map


def test_fused_batch_form_verifies_clean():
    """A fused multi-query split passes BOTH the typed pass (it is a plan
    like any other — per-slot schema flow and agg mergeability included)
    and the batch-slot demux invariants."""
    from pixie_tpu.check.planverify import verify_fused_batch

    dp, sink_map = _fused_batch_split()
    verify_distributed(dp, SCHEMAS)
    verify_fused_batch(dp, sink_map)


def test_fused_batch_missing_slot_sink_rejected():
    """A slot whose fused sink was lost (or never produced) must be
    rejected — demux would silently answer the wrong member."""
    from pixie_tpu.check.planverify import verify_fused_batch

    dp, sink_map = _fused_batch_split()
    bad = {p: dict(m) for p, m in sink_map.items()}
    bad["q1"]["out"] = "q1/definitely_not_there"
    with pytest.raises(PlanVerifyError) as e:
        verify_fused_batch(dp, bad)
    assert e.value.invariant == "batch-slot-missing-sink"
    assert "q1" in str(e.value)


def test_fused_batch_slot_overlap_rejected():
    """Two slots claiming one fused sink break the demux partition."""
    from pixie_tpu.check.planverify import verify_fused_batch

    dp, sink_map = _fused_batch_split()
    bad = {p: dict(m) for p, m in sink_map.items()}
    bad["q1"]["out"] = bad["q0"]["out"]
    with pytest.raises(PlanVerifyError) as e:
        verify_fused_batch(dp, bad)
    assert e.value.invariant == "batch-slot-overlap"


def test_fused_batch_verification_rides_split_cache():
    """The batch leader verifies ONCE per batch signature: a warm repeat of
    the same member multiset re-verifies nothing (the fused split-cache
    slot is filled)."""
    import threading

    import pixie_tpu.matview  # noqa: F401
    from pixie_tpu.parallel.cluster import LocalCluster
    from pixie_tpu.table import TableStore

    saved = {n: flags.get(n) for n in ("PL_MATVIEW_ENABLED",
                                       "PL_BATCH_WINDOW_MS",
                                       "PL_QUERY_BATCHING")}
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)
    flags.set_for_testing("PL_BATCH_WINDOW_MS", 150.0)
    flags.set_for_testing("PL_QUERY_BATCHING", True)
    try:
        ts = TableStore()
        t = ts.create("http_events", HTTP_REL, batch_rows=4096)
        rng = np.random.default_rng(3)
        n = 8192
        t.write({"time_": np.arange(n, dtype=np.int64),
                 "service": rng.choice(["a", "b"], n).tolist(),
                 "latency": rng.exponential(5.0, n),
                 "status": rng.choice([200, 404], n)})
        cluster = LocalCluster({"pem0": ts})

        def round_trip():
            got = {}

            def run(tag, s):
                got[tag] = cluster.query(s)["out"]

            th = [threading.Thread(target=run, args=("a", AGG_SRC)),
                  threading.Thread(target=run, args=("b", AGG2_SRC))]
            for x in th:
                x.start()
            for x in th:
                x.join(timeout=60)
            return got

        v0 = metrics.counter_value("px_plan_verify_total")
        round_trip()
        v1 = metrics.counter_value("px_plan_verify_total")
        round_trip()  # warm batch signature: split cache hit, zero verify
        v2 = metrics.counter_value("px_plan_verify_total")
        b = metrics.counter_value("px_batch_formed_total")
        if b >= 2:  # both rounds actually batched (scheduling-dependent)
            assert v2 == v1
        assert v1 >= v0
    finally:
        for nm, v in saved.items():
            flags.set_for_testing(nm, v)
