"""Query flight recorder (ISSUE 14): per-query profiles with ≥80% wall-time
attribution for every bundled script run distributed, EXPLAIN ANALYZE,
provenance on the tricky paths (batched member, stale matview serve,
failover-served fragment) matching the per-query stats, metrics-as-data
sampling, SLO burn-rate monitoring, and the fully-off bit-identity
guarantee."""
from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from pixie_tpu import flags, metrics, observe, trace
from pixie_tpu.parallel.cluster import LocalCluster
from pixie_tpu.scripts import REPO_BUNDLE
from pixie_tpu.services.agent import Agent
from pixie_tpu.services.broker import Broker
from pixie_tpu.services.client import Client
from pixie_tpu.serving import slo
from pixie_tpu.table import TableStore
from pixie_tpu.testing.fixtures import canonical_bytes
from pixie_tpu.types import DataType as DT, Relation

import pixie_tpu.engine.plancache  # noqa: F401 — defines PL_QUERY_FASTPATH
import pixie_tpu.matview  # noqa: F401 — defines PL_MATVIEW_ENABLED
import pixie_tpu.serving.batching  # noqa: F401 — defines PL_QUERY_BATCHING

OBSERVE_FLAGS = (
    "PL_TRACING_ENABLED", "PL_SLO", "PL_SLO_FAST_S", "PL_SLO_SLOW_S",
    "PL_SLO_BURN_FAST", "PL_SLO_BURN_SLOW", "PL_SELF_METRICS_S",
    "PL_MATVIEW_ENABLED", "PL_QUERY_BATCHING", "PL_BATCH_WINDOW_MS",
    "PL_SERVING_ENABLED", "PL_SERVING_MAX_INFLIGHT",
    "PL_SERVING_QUEUE_DEPTH", "PL_SERVING_SHED_WATERMARK",
    "PL_TENANT_CONCURRENCY", "PL_QUERY_FASTPATH", "PL_QUERY_RETRIES",
    "PL_CLIENT_RETRIES", "PL_REJOIN_GRACE_S", "PL_DATA_DIR",
    "PL_REPLICATION", "PL_RETRY_BACKOFF_MS", "PL_JOURNAL_FSYNC",
)


@pytest.fixture(autouse=True)
def _restore_flags():
    saved = {n: flags.get(n) for n in OBSERVE_FLAGS}
    yield
    for n, v in saved.items():
        flags.set_for_testing(n, v)
    slo.reset_for_testing()


REL = Relation.of(
    ("time_", DT.TIME64NS), ("service", DT.STRING),
    ("latency", DT.FLOAT64), ("status", DT.INT64),
)

SCRIPT = """
df = px.DataFrame(table='http_events')
df = df[df.status != 404]
df = df.groupby('service').agg(cnt=('latency', px.count),
                               avg=('latency', px.mean))
px.display(df, 'out')
"""


def _mkstore(seed, n=20_000):
    rng = np.random.default_rng(seed)
    ts = TableStore()
    t = ts.create("http_events", REL, batch_rows=1 << 12, max_bytes=1 << 32)
    t.write({
        "time_": np.arange(n, dtype=np.int64) * 1000,
        "service": rng.choice(["cart", "auth", "web"], n).tolist(),
        "latency": rng.exponential(20.0, n),
        "status": rng.choice([200, 404, 500], n),
    })
    return ts


def _self_telemetry_rows(ts):
    """Synthetic rows for every self-telemetry table, so the bundled
    self_* dashboards have data to scan."""
    trace.write_spans(ts, [{
        "time_": 10 ** 15 + i, "trace_id": f"{i:032x}",
        "span_id": f"{i:016x}", "parent_span_id": "", "name": "query",
        "service": "broker", "duration_ns": 1000 * (i + 1),
        "attributes": "",
    } for i in range(20)])
    observe.write_rows(ts, observe.PROFILES_TABLE, [{
        "time_": 10 ** 15 + i, "query_id": f"q{i}", "tenant": f"t{i % 2}",
        "service": "broker", "status": "ok" if i % 4 else "error",
        "wall_ns": 10_000 * (i + 1), "plan_cache_hit": i % 2,
        "matview_hits": 1, "batch_size": i % 3,
    } for i in range(20)])
    observe.write_rows(ts, observe.METRICS_TABLE, [{
        "time_": 10 ** 15 + i, "service": "broker",
        "name": "px_broker_queries_total" if i % 2 else "px_slo_burn_rate",
        "labels": "", "kind": "counter" if i % 2 else "gauge",
        "value": float(i),
    } for i in range(20)])
    observe.write_rows(ts, observe.ALERTS_TABLE, [{
        "time_": 10 ** 15 + i, "slo": "lat", "tenant": "t0",
        "window": "fast", "burn_rate": 20.0, "threshold": 14.4,
        "objective": 0.99, "state": "firing",
    } for i in range(3)])
    observe.write_rows(ts, observe.SHARD_HEAT_TABLE, [{
        "time_": 10 ** 15 + i, "table_name": "http_events",
        "shard": f"pem{i % 2}", "tier": "stream", "age_bucket": "hot",
        "rows_scanned": 100 * (i + 1), "bytes": 800 * (i + 1),
        "heat": 50.0 * (i + 1), "skew": 1.2, "last_access": 10 ** 15 + i,
    } for i in range(6)])
    observe.write_rows(ts, observe.STORAGE_STATE_TABLE, [{
        "time_": 10 ** 15 + i, "agent": f"pem{i % 2}",
        "table_name": "http_events", "hot_rows": 10 * i,
        "sealed_batches": i, "sealed_bytes": 1000 * i,
        "age_histogram": "", "resident_bytes": 0, "matview_bytes": 0,
        "journal_bytes": 100 * i, "journal_segments": 1,
        "repl_lag_batches": 0, "peer_lag": "",
    } for i in range(6)])
    observe.write_rows(ts, observe.SCALE_EVENTS_TABLE, [{
        "time_": 10 ** 15 + i,
        "action": ("up", "rehome", "rebalance")[i % 3],
        "agent": f"pem{i % 2}", "reason": "pressure",
        "pressure": 0.5 + i, "agents": 2 + i % 2,
    } for i in range(6)])


# ---------------------------------------------------------------- unit layer


def test_write_rows_roundtrip_and_scan():
    ts = TableStore()
    observe.write_rows(ts, observe.PROFILES_TABLE, [
        {"time_": 5, "query_id": "q1", "tenant": "t", "wall_ns": 123,
         "status": "ok"}])
    from pixie_tpu.collect.schemas import all_schemas
    from pixie_tpu.compiler import compile_pxl
    from pixie_tpu.engine import execute_plan

    src = ("df = px.DataFrame(table='self_telemetry.query_profiles')\n"
           "px.display(df, 'out')")
    out = execute_plan(compile_pxl(src, all_schemas()).plan, ts)["out"]
    df = out.to_pandas()
    assert df["query_id"].tolist() == ["q1"]
    assert int(df["wall_ns"].iloc[0]) == 123
    assert df["failover"].tolist() == [""]  # unset columns default cleanly


def test_row_buffer_flush_threshold_and_bound():
    buf = observe.RowBuffer(flush_rows=4, max_rows=6)
    ts = TableStore()
    buf.add(observe.PROFILES_TABLE, [{"time_": i} for i in range(3)])
    assert buf.flush_into(ts) == 0  # below threshold: no write yet
    buf.add(observe.PROFILES_TABLE, [{"time_": 9}])
    assert buf.flush_into(ts) == 4
    buf.add(observe.PROFILES_TABLE, [{"time_": i} for i in range(10)])
    assert len(buf) == 6  # bounded
    assert buf.dropped == 4
    assert buf.flush_into(ts, force=True) == 6


def test_build_profile_maps_stats_to_provenance():
    stats = {
        "phases": {"compile_ns": 10, "plan_split_ns": 20, "exec_ns": 30,
                   "merge_ns": 40},
        "serving": {"tenant": "t", "queued_ms": 0.001, "degraded": True},
        "fastpath": {"plan_cache_hit": True, "split_cache_hit": False},
        "matview": {"eligible_agents": 2, "agents_hit": 2,
                    "rows_folded": 7},
        "batch": {"size": 3, "slot": 1},
        "fault": {"rounds": 2, "evictions": 1, "hedged": 1,
                  "chunks_discarded": 5, "failover": {"pem1": "pem2"}},
        "merger": {"rows_output": 11, "operators": [
            {"label": "remote(ch0)", "wall_ns": 9, "self_ns": 9,
             "rows_out": 11, "bytes_out": 64, "t0_unix_ns": 123}]},
        "agents": {
            "pem0": {"wall_ns": 900, "rows_scanned": 100, "h2d_bytes": 10,
                     "resident_feeds": 1, "operators": [
                         {"label": "scan", "wall_ns": 800, "self_ns": 700,
                          "rows_out": 3, "bytes_out": 24,
                          "t0_unix_ns": 456}]},
            "pem1": {"exec_s": 0.001, "rows_scanned": 50,
                     "matview": {"hit": True, "stale": True}},
        },
    }
    p, ops = observe.build_profile("qid", "t", "broker", 1000, 5000, stats)
    assert p["compile_ns"] == 10 and p["plan_split_ns"] == 20
    assert p["exec_ns"] == 30 and p["merge_ns"] == 40
    assert p["admission_wait_ns"] == 1000  # 0.001 ms
    assert p["accounted_ns"] == 10 + 20 + 30 + 40 + 1000
    assert p["agents"] == 2 and p["rows_scanned"] == 150
    assert p["rows_output"] == 11 and p["h2d_bytes"] == 10
    assert p["d2h_bytes"] == 64 + 24
    assert p["plan_cache_hit"] == 1 and p["split_cache_hit"] == 0
    assert p["matview_eligible"] == 2 and p["matview_hits"] == 2
    assert p["matview_stale"] == 1 and p["matview_rows_folded"] == 7
    assert p["resident_feeds"] == 1
    assert p["batch_size"] == 3 and p["batch_slot"] == 1
    assert json.loads(p["failover"]) == {"pem1": "pem2"}
    assert p["hedged"] == 1 and p["evictions"] == 1 and p["retries"] == 2
    assert p["chunks_discarded"] == 5 and p["degraded"] == 1
    assert {o["agent"] for o in ops} == {"pem0", "pem1", "merger"} - {"pem1"}
    text = observe.render_explain(p, ops, plan_text="[0] MemorySource")
    for marker in ("EXPLAIN ANALYZE", "MemorySource", "compile",
                   "standing view state", "fused batch of 3",
                   "pem1", "hedges", "degraded dispatch"):
        assert marker in text, marker


def test_sample_metrics_rows_covers_registry_kinds():
    metrics.counter_inc("px_obs_test_counter_total", 3.0, help_="t")
    metrics.gauge_set("px_obs_test_gauge", 1.5, help_="t")
    metrics.histogram_observe("px_obs_test_hist", 0.2, (0.1, 0.5, 1.0),
                              help_="t")
    rows = observe.sample_metrics_rows("svc", now_ns=77)
    by = {(r["name"], r["kind"]): r for r in rows}
    assert by[("px_obs_test_counter_total", "counter")]["value"] == 3.0
    assert by[("px_obs_test_gauge", "gauge")]["value"] == 1.5
    assert by[("px_obs_test_hist", "hist_count")]["value"] == 1.0
    assert ("px_obs_test_hist", "hist_p50") in by
    assert all(r["time_"] == 77 and r["service"] == "svc" for r in rows)


# ----------------------------------------------------------------- SLO layer


def test_parse_slo_spec_grammar_and_malformed():
    got = slo.parse_slo_spec(
        "lat:latency<250ms@99;avail:errors@99.9")
    assert [(s.name, s.kind, s.threshold_s) for s in got] == [
        ("lat", "latency", 0.25), ("avail", "errors", None)]
    assert [s.objective for s in got] == [
        pytest.approx(0.99), pytest.approx(0.999)]
    # malformed entries skip (counted), never raise
    kept = slo.parse_slo_spec("junk;lat:latency<10ms@99;b:bogus@200")
    assert [s.name for s in kept] == ["lat"]
    assert slo.parse_slo_spec("") == []


def test_burn_rate_math_and_alert_edges():
    m = slo.SLOMonitor("lat:latency<100ms@99", fast_s=10.0, slow_s=60.0)
    # 98 good + 2 bad in-window: bad_frac 2% over a 1% budget = burn 2.0
    for i in range(98):
        m.record("t0", 0.05, True, now=1000.0 + i * 0.01)
    for i in range(2):
        m.record("t0", 0.5, True, now=1001.0 + i * 0.01)
    rates = m.burn_rates(now=1002.0)
    assert rates[("lat", "t0", "fast")] == pytest.approx(2.0)
    assert rates[("lat", "t0", "slow")] == pytest.approx(2.0)
    assert m.evaluate(now=1002.0) == []  # 2.0 < both thresholds
    # total outage: burn 100 trips fast AND slow → two firing edges, once
    for i in range(50):
        m.record("t0", 0.5, True, now=1003.0 + i * 0.01)
    rows = m.evaluate(now=1004.0)
    assert {(r["window"], r["state"]) for r in rows} == {
        ("fast", "firing"), ("slow", "firing")}
    assert m.evaluate(now=1004.5) == []  # still firing: no re-edge
    # recovery: the fast window clears first → resolved edge
    for i in range(200):
        m.record("t0", 0.01, True, now=1020.0 + i * 0.01)
    rows = m.evaluate(now=1032.0)
    assert ("fast", "resolved") in {(r["window"], r["state"])
                                    for r in rows}
    assert m.drain_alerts()  # rows accumulated for the alerts table


def test_slo_errors_kind_and_record_query_gate():
    flags.set_for_testing("PL_SLO", "")
    slo.reset_for_testing()
    slo.record_query("t", 0.01, True)  # no-op without a spec
    flags.set_for_testing("PL_SLO", "avail:errors@90")
    slo.reset_for_testing()
    now = time.time()
    for ok in (True, False, False):
        slo.monitor().record("t", 0.01, ok, now=now)
    rates = slo.monitor().burn_rates(now=now + 1)
    assert rates[("avail", "t", "fast")] == pytest.approx((2 / 3) / 0.1)
    # the lazy gauge reads the live monitor
    text = metrics.render()
    assert 'px_slo_burn_rate{slo="avail",tenant="t",window="fast"}' in text


# ----------------------------------------------- attribution (LocalCluster)


def _bundled_runs():
    """Every repo-bundled script × vis func, with its default args (the
    reference checkout, when present, is out of scope: this bound is about
    the flight recorder's own shipped dashboards)."""
    from pixie_tpu.vis import parse_vis

    out = []
    for d in sorted(REPO_BUNDLE.iterdir()):
        if not d.is_dir() or not list(d.glob("*.pxl")):
            continue
        src = sorted(d.glob("*.pxl"))[0].read_text()
        vis = parse_vis(json.loads((d / "vis.json").read_text()))
        for _out, fn, fargs in vis.executions({}):
            out.append((d.name, src, fn, fargs))
    return out


def test_attribution_bundled_scripts_distributed_80pct():
    """EXPLAIN ANALYZE attribution completeness (the acceptance bound):
    for every bundled script run distributed (2-agent LocalCluster, cold),
    the profile's attributed phase ns sum to >= 80% of the measured e2e
    wall time."""
    runs = _bundled_runs()
    assert len(runs) >= 9  # self_query_latency + self_metrics + self_slo
    seen = set()
    for name, src, fn, fargs in runs:
        stores = {"pem0": _mkstore(1), "pem1": _mkstore(2)}
        for ts in stores.values():
            _self_telemetry_rows(ts)
        cl = LocalCluster(stores)  # fresh plan cache: a COLD distributed run
        t0 = time.perf_counter_ns()
        res = cl.query(src, func=fn, func_args=fargs)
        e2e = time.perf_counter_ns() - t0
        prof = next(iter(res.values())).exec_stats["profile"]
        frac = prof["accounted_ns"] / e2e
        assert frac >= 0.8, (name, fn, frac)
        assert prof["agents"] == 2 and prof["status"] == "ok"
        seen.add(name)
    assert seen >= {"self_query_latency", "self_metrics", "self_slo"}


def test_explain_analyze_cluster_cold_and_warm():
    cl = LocalCluster({"pem0": _mkstore(3), "pem1": _mkstore(4)})
    cold = cl.query(SCRIPT, explain=True)["out"].exec_stats["explain"]
    for marker in ("EXPLAIN ANALYZE", "MemorySource table=http_events",
                   "Filter", "Agg", "compile", "dispatch+exec",
                   "plan cache: miss", "scanned 40000 rows on 2 agents"):
        assert marker in cold, marker
    warm = cl.query(SCRIPT, explain=True)["out"].exec_stats["explain"]
    assert "plan cache: HIT" in warm
    if flags.get("PL_MATVIEW_ENABLED"):
        warm2 = cl.query(SCRIPT, explain=True)["out"].exec_stats
        assert "standing view state" in warm2["explain"]
        assert warm2["profile"]["matview_hits"] == 2


def test_tracing_off_bit_identical_no_profile_explain_still_works():
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)
    cl = LocalCluster({"pem0": _mkstore(5)})
    on = cl.query(SCRIPT)
    assert "profile" in on["out"].exec_stats
    flags.set_for_testing("PL_TRACING_ENABLED", False)
    off = cl.query(SCRIPT)
    assert canonical_bytes(off) == canonical_bytes(on)
    assert "profile" not in off["out"].exec_stats
    pend0 = len(cl._telemetry)  # nothing recorded while off
    # explain is a per-query opt-in that works with tracing fully off —
    # and records nothing
    ex = cl.query(SCRIPT, explain=True)
    assert "EXPLAIN ANALYZE" in ex["out"].exec_stats["explain"]
    assert canonical_bytes(ex) == canonical_bytes(on)
    assert len(cl._telemetry) == pend0


def test_cluster_profiles_land_in_store_and_dogfood_query():
    cl = LocalCluster({"pem0": _mkstore(6), "pem1": _mkstore(7)})
    for _ in range(4):
        cl.query(SCRIPT)
    assert cl.flush_telemetry() > 0
    out = cl.query("""
df = px.DataFrame(table='self_telemetry.query_profiles')
df = df.groupby('tenant').agg(queries=('wall_ns', px.count))
px.display(df, 'out')
""")["out"].to_pandas()
    assert int(out["queries"].iloc[0]) >= 4


def test_self_dashboards_serve_warm_as_matviews():
    """px/self_metrics + px/self_slo acceptance: every widget func is a
    standing-matview shape — the third sight serves from view state on
    every agent."""
    flags.set_for_testing("PL_MATVIEW_ENABLED", True)
    stores = {"pem0": _mkstore(8), "pem1": _mkstore(9)}
    for ts in stores.values():
        _self_telemetry_rows(ts)
    cl = LocalCluster(stores)
    for name in ("self_metrics", "self_slo"):
        src = (REPO_BUNDLE / name / f"{name}.pxl").read_text()
        import ast as _ast

        funcs = [n.name for n in _ast.parse(src).body
                 if isinstance(n, _ast.FunctionDef)]
        for fn in funcs:
            cl.query(src, func=fn, func_args={})
            cl.query(src, func=fn, func_args={})
            r = cl.query(src, func=fn, func_args={})
            es = r[next(iter(r))].exec_stats
            mv = {a: (s.get("matview") or {}).get("hit")
                  for a, s in es["agents"].items()}
            assert all(mv.values()), (name, fn, mv)
            assert es["profile"]["matview_hits"] == 2, (name, fn)


# -------------------------------------------- provenance: the tricky paths


def test_batched_member_profile_matches_stats():
    """A batched member's profile carries the batch membership + computed
    (dedup) slot exactly as its per-query stats report them."""
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)
    flags.set_for_testing("PL_QUERY_BATCHING", True)
    flags.set_for_testing("PL_BATCH_WINDOW_MS", 100.0)
    cl = LocalCluster({"pem0": _mkstore(10)})
    cl.query(SCRIPT)  # warm the plan cache so members are batch-eligible
    got: list = []

    def run():
        for _ in range(6):
            r = cl.query(SCRIPT)["out"]
            if "batch" in r.exec_stats:
                got.append(r.exec_stats)

    ts_ = [threading.Thread(target=run) for _ in range(2)]
    for t in ts_:
        t.start()
    for t in ts_:
        t.join(timeout=120)
    assert got, "no query was served through a fused batch"
    for es in got:
        b, p = es["batch"], es["profile"]
        assert p["batch_size"] == b["size"] >= 2
        assert p["batch_slot"] == b["slot"]
        # identical members dedup to ONE computed slot
        assert b["slots"] == 1 and b["slot"] == 0


def test_stale_matview_serve_profile_matches_stats():
    """Degraded dispatch serves matview hits STALE; the profile counts the
    stale serves exactly as the per-agent stats report them."""
    from pixie_tpu.serving import COST_WARM

    flags.set_for_testing("PL_MATVIEW_ENABLED", True)
    flags.set_for_testing("PL_SERVING_ENABLED", True)
    flags.set_for_testing("PL_SERVING_MAX_INFLIGHT", 8)
    flags.set_for_testing("PL_SERVING_QUEUE_DEPTH", 8)
    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    agents = [Agent("pem1", "127.0.0.1", broker.port, store=_mkstore(11),
                    heartbeat_s=1.0).start()]
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        for _ in range(3):  # register, build, hit
            client.execute_script(SCRIPT, tenant="dash")
        agents[0].store.table("http_events").write({
            "time_": np.arange(64, dtype=np.int64),
            "service": ["cart"] * 64, "latency": np.ones(64),
            "status": np.full(64, 500, dtype=np.int64)})
        # force degradation: one tenant-cap-blocked queue entry past a
        # watermark of 1 (the test_serving idiom)
        flags.set_for_testing("PL_SERVING_SHED_WATERMARK", 1)
        flags.set_for_testing("PL_TENANT_CONCURRENCY", "0,z=1")
        broker.serving.reset_for_testing()
        blocker = broker.serving.admit("z", COST_WARM)
        hold = {}

        def bg():
            hold["t"] = broker.serving.admit("z", COST_WARM,
                                             timeout_s=30.0)

        th = threading.Thread(target=bg, daemon=True)
        th.start()
        deadline = time.monotonic() + 5.0
        while broker.serving.ready() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not broker.serving.ready()
        res = client.execute_script(SCRIPT, tenant="dash")["out"]
        es = res.exec_stats
        mv = es["agents"]["pem1"]["matview"]
        assert mv["hit"] and mv["stale"]
        p = es["profile"]
        assert p["matview_hits"] == 1 and p["matview_stale"] == 1
        assert p["degraded"] == 1 == int(es["serving"]["degraded"])
        assert p["tenant"] == "dash"
        broker.serving.release(blocker)
        th.join(timeout=5.0)
        if "t" in hold:
            broker.serving.release(hold["t"])
    finally:
        client.close()
        for a in agents:
            a.stop()
        broker.stop()


def test_failover_served_profile_matches_stats(tmp_path):
    """A failover-served fragment (dead primary answered by its replica)
    lands in the profile's failover map exactly as stats["fault"] records
    it — and the profile row reaches the data plane."""
    flags.set_for_testing("PL_DATA_DIR", str(tmp_path))
    flags.set_for_testing("PL_REPLICATION", 2)
    flags.set_for_testing("PL_QUERY_RETRIES", 4)
    flags.set_for_testing("PL_RETRY_BACKOFF_MS", 60)
    flags.set_for_testing("PL_CLIENT_RETRIES", 4)
    flags.set_for_testing("PL_REJOIN_GRACE_S", 0.4)
    flags.set_for_testing("PL_JOURNAL_FSYNC", "batch")
    broker = Broker(hb_expiry_s=2.0, query_timeout_s=30.0).start()
    agents = {}
    for i in range(3):
        agents[f"pem{i}"] = Agent(f"pem{i}", "127.0.0.1", broker.port,
                                  store=_mkstore(20 + i, n=4096),
                                  heartbeat_s=0.3).start()
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        deadline = time.monotonic() + 10.0
        for a in agents.values():
            assert a.replication.wait_synced(
                max(deadline - time.monotonic(), 0.1))
        base = canonical_bytes(client.execute_script(SCRIPT))
        agents["pem1"]._pod_kill()
        agents["pem1"].conn.abort()
        time.sleep(0.6)  # past the rejoin grace
        res = client.execute_script(SCRIPT)
        assert canonical_bytes(res) == base
        es = next(iter(res.values())).exec_stats
        fo = es["fault"]["failover"]
        assert fo.get("pem1") in ("pem0", "pem2")
        p = es["profile"]
        assert json.loads(p["failover"]) == fo
        assert p["agents"] == 3 and p["status"] == "ok"
        # the ship path: this query's profile row is scannable in the
        # data plane (the broker shipped it to a live agent)
        deadline = time.monotonic() + 5.0
        fo_rows = []
        while time.monotonic() < deadline and not fo_rows:
            out = client.execute_script(
                "df = px.DataFrame("
                "table='self_telemetry.query_profiles')\n"
                "px.display(df, 'out')")["out"].to_pandas()
            fo_rows = [f for f in out["failover"].tolist() if f]
            time.sleep(0.2)
        assert fo_rows and json.loads(fo_rows[-1]) == fo
    finally:
        client.close()
        for a in agents.values():
            try:
                a.stop()
            except Exception:
                pass
        broker.stop()


# ------------------------------------------- metrics-as-data + SLO alerting


def test_broker_self_metrics_ticker_and_slo_alert_rows():
    """PL_SELF_METRICS_S folds the registry into self_telemetry.metrics on
    the data plane; an impossible latency SLO fires burn-rate alerts into
    self_telemetry.alerts through the same ship path."""
    flags.set_for_testing("PL_SELF_METRICS_S", 0.2)
    flags.set_for_testing("PL_SLO", "impossible:latency<0ms@99")
    slo.reset_for_testing()
    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    agents = [Agent("pem1", "127.0.0.1", broker.port, store=_mkstore(30),
                    heartbeat_s=1.0).start()]
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        client.execute_script(SCRIPT)  # one bad (by SLO) observation
        deadline = time.monotonic() + 8.0
        got_m = got_a = 0
        while time.monotonic() < deadline and not (got_m and got_a):
            client.execute_script(SCRIPT)
            out = client.execute_script("""
df = px.DataFrame(table='self_telemetry.metrics')
df = df.groupby('kind').agg(n=('value', px.count))
px.display(df, 'out')
""")["out"]
            got_m = out.num_rows
            out = client.execute_script("""
df = px.DataFrame(table='self_telemetry.alerts')
df = df[df.state == 'firing']
df = df.groupby('slo').agg(n=('burn_rate', px.count),
                           mx=('burn_rate', px.max))
px.display(df, 'out')
""")["out"]
            got_a = out.num_rows
            time.sleep(0.2)
        assert got_m >= 1, "no sampled metrics landed"
        assert got_a >= 1, "no SLO alert rows landed"
        df = out.to_pandas()
        assert df["slo"].tolist() == ["impossible"]
        assert metrics.counter_value(
            "px_slo_alerts_total",
            labels={"slo": "impossible", "window": "fast"}) >= 1
    finally:
        client.close()
        for a in agents:
            a.stop()
        broker.stop()
