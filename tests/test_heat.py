"""Data-plane observatory (ISSUE 16): shard heat accounting, replication
lag, and storage-tier telemetry.

Covers the heat model's deterministic decay math, age-bucket rollover,
exact row attribution (snapshot rows == executor rows_scanned), the
flag-off bit-identity guarantee, capped label space, the storage-state
fold (journal disk usage, sealed-age histogram, replication lag), the
px_journal_fsync_seconds histogram, the /healthz journal detail payload,
and the broker heat_map / retire peer_sync RPC surface end to end —
including the acceptance bound: folded shard_heat skew agrees with raw
per-shard row counts within 1%."""
from __future__ import annotations

import json
import urllib.request

import numpy as np
import pytest

from pixie_tpu import flags, metrics, observe
from pixie_tpu.parallel.cluster import LocalCluster
from pixie_tpu.services.agent import Agent
from pixie_tpu.services.broker import Broker
from pixie_tpu.services.client import Client
from pixie_tpu.table import TableStore, heat, journal
from pixie_tpu.testing.fixtures import canonical_bytes
from pixie_tpu.types import DataType as DT, Relation

HEAT_FLAGS = ("PL_TRACING_ENABLED", "PL_HEAT_HALF_LIFE_S",
              "PL_JOURNAL_FSYNC", "PL_REPLICATION", "PL_SELF_METRICS_S")


@pytest.fixture(autouse=True)
def _clean():
    saved = {n: flags.get(n) for n in HEAT_FLAGS}
    heat.reset_for_testing()
    yield
    for n, v in saved.items():
        flags.set_for_testing(n, v)
    heat.reset_for_testing()


REL = Relation.of(
    ("time_", DT.TIME64NS), ("service", DT.STRING), ("latency", DT.FLOAT64),
)

SCRIPT = """
df = px.DataFrame(table='http_events')
df = df.groupby('service').agg(cnt=('latency', px.count),
                               p50=('latency', px.p50))
px.display(df, 'out')
"""


def _mkstore(seed, n, batch_rows=4096):
    rng = np.random.default_rng(seed)
    ts = TableStore()
    t = ts.create("http_events", REL, batch_rows=batch_rows)
    t.write({
        "time_": np.arange(n, dtype=np.int64) * 1000,
        "service": rng.choice(["cart", "auth", "web"], n).tolist(),
        "latency": rng.exponential(20.0, n),
    })
    return ts


# ------------------------------------------------------------- decay math


def test_decay_is_deterministic_and_exact():
    flags.set_for_testing("PL_HEAT_HALF_LIFE_S", 600.0)
    m = heat.HeatModel()
    t0 = 1_000_000_000_000_000
    m.record_feed("t", "a", 1000, 8000, now_ns=t0)
    # exactly one half-life later the heat is exactly half
    hl = int(600.0 * 1e9)
    assert m.shard_heat(now_ns=t0)[("t", "a")] == 1000.0
    assert m.shard_heat(now_ns=t0 + hl)[("t", "a")] == 500.0
    assert m.shard_heat(now_ns=t0 + 2 * hl)[("t", "a")] == 250.0
    # a second bump decays the standing heat first, then adds
    m.record_feed("t", "a", 100, 800, now_ns=t0 + hl)
    assert m.shard_heat(now_ns=t0 + hl)[("t", "a")] == 600.0
    # raw row/byte counters never decay
    rows = m.snapshot_rows(now_ns=t0 + hl)
    assert rows[0]["rows_scanned"] == 1100 and rows[0]["bytes"] == 8800


def test_decay_disabled_makes_heat_a_plain_counter():
    flags.set_for_testing("PL_HEAT_HALF_LIFE_S", 0.0)
    m = heat.HeatModel()
    t0 = 10**18
    m.record_feed("t", "a", 10, 0, now_ns=t0)
    m.record_feed("t", "a", 10, 0, now_ns=t0 + 10**15)
    assert m.shard_heat(now_ns=t0 + 10**16)[("t", "a")] == 20.0


def test_skew_and_top_shards():
    m = heat.HeatModel()
    t0 = 10**18
    m.record_feed("t", "a", 300, 0, now_ns=t0)
    m.record_feed("t", "b", 100, 0, now_ns=t0)
    m.record_feed("t", "c", 200, 0, now_ns=t0)
    m.record_feed("u", "a", 5, 0, now_ns=t0)
    # max/mean: 300 / 200 = 1.5
    assert m.skew(now_ns=t0)["t"] == pytest.approx(1.5)
    assert m.skew(now_ns=t0)["u"] == pytest.approx(1.0)
    assert m.top_shards(2, now_ns=t0) == [("t", "a", 300.0), ("t", "c", 200.0)]
    # the module-level API (the rebalancer's entry point) hits the singleton
    heat.record_feed("t", "z", 7, 0, now_ns=t0)
    assert heat.top_shards(1, now_ns=t0) == [("t", "z", 7.0)]
    # skew rides the px_shard_heat_skew gauge family
    got = heat._skew_gauges()
    assert got[(("table_name", "t"),)] == pytest.approx(1.0)


# ------------------------------------------------------------- age buckets


def test_age_bucket_bounds():
    assert heat.age_bucket(None) == "sealed"
    assert heat.age_bucket(0.0) == "<1m"
    assert heat.age_bucket(59.9) == "<1m"
    assert heat.age_bucket(60.0) == "<10m"
    assert heat.age_bucket(599.9) == "<10m"
    assert heat.age_bucket(3600.0) == "<1d"
    assert heat.age_bucket(86400.0) == "old"
    for b in ("hot", "<1m", "<10m", "<1h", "<1d", "old", "sealed"):
        assert b in heat.AGE_BUCKETS


def test_age_bucket_rollover_as_batches_age():
    """The same sealed batch rolls to older buckets as `now` advances —
    age is computed at feed time from the batch's max data time."""
    now = 1_700_000_000 * 10**9
    ts = TableStore()
    t = ts.create("ev", REL, batch_rows=64)
    t.write({"time_": np.full(64, now - 30 * 10**9, dtype=np.int64),
             "service": ["a"] * 64, "latency": np.zeros(64)})
    assert len(t._sealed) == 1
    gen = t._sealed[0].gen
    m = heat.HeatModel()
    rec = heat.FeedRecorder(t, "pem0", model=m, now_ns=now)
    assert rec.age_by_gen[gen] == "<1m"
    rec2 = heat.FeedRecorder(t, "pem0", model=m, now_ns=now + 120 * 10**9)
    assert rec2.age_by_gen[gen] == "<10m"
    rec3 = heat.FeedRecorder(t, "pem0", model=m,
                             now_ns=now + 2 * 86400 * 10**9)
    assert rec3.age_by_gen[gen] == "old"
    # a recorded part lands in the recorder's bucket; the hot remainder
    # (gen None) lands in "hot"
    part = {"latency": np.zeros(16)}
    rec2.record([part, part], [gen, None], "stream")
    keys = set(m._cells)
    assert ("ev", "pem0", "stream", "<10m") in keys
    assert ("ev", "pem0", "stream", "hot") in keys


# ------------------------------------------- executor feed attribution


def test_snapshot_rows_match_executor_scans():
    """Every feed lands in exactly one heat cell: summed rows_scanned in
    the model equals the table sizes per shard exactly."""
    stores = {"pem0": _mkstore(1, 3000), "pem1": _mkstore(2, 9000)}
    cl = LocalCluster(stores)
    cl.query(SCRIPT)
    by_shard = {}
    for r in heat.snapshot_rows():
        assert r["table_name"] == "http_events"
        by_shard[r["shard"]] = by_shard.get(r["shard"], 0) + r["rows_scanned"]
    assert by_shard == {"pem0": 3000, "pem1": 9000}
    # a second identical query doubles the raw counters
    cl.query(SCRIPT)
    total = sum(r["rows_scanned"] for r in heat.snapshot_rows())
    assert total == 2 * 12000


def test_flag_off_is_bit_identical_and_records_nothing():
    stores = {"pem0": _mkstore(3, 2000)}
    cl = LocalCluster(stores)
    on = cl.query(SCRIPT)
    assert heat.MODEL._cells  # tracing on: the model saw the feeds
    heat.reset_for_testing()
    flags.set_for_testing("PL_TRACING_ENABLED", False)
    off = cl.query(SCRIPT)
    assert canonical_bytes(off) == canonical_bytes(on)
    assert heat.MODEL._cells == {}  # fully off: never touched
    assert heat.fold_into(cl.stores["pem0"], "pem0") == 0
    for table in (observe.SHARD_HEAT_TABLE, observe.STORAGE_STATE_TABLE):
        assert cl.stores["pem0"].table(table).stats()["rows_written"] == 0


def test_capped_label_space_bounds_shard_cardinality():
    saved = metrics._label_ids.pop("heat_shard", None)
    try:
        m = heat.HeatModel()
        for i in range(300):
            m.record_feed("t", f"shard{i}", 1, 0, now_ns=10**18)
        shards = {k[1] for k in m._cells}
        assert len(shards) <= metrics.MAX_LABEL_IDS + 1
        assert metrics.OTHER_LABEL in shards
    finally:
        metrics._label_ids.pop("heat_shard", None)
        if saved is not None:
            metrics._label_ids["heat_shard"] = saved


# ------------------------------------------------------ storage-state fold


def test_storage_state_rows_and_fold(tmp_path):
    flags.set_for_testing("PL_JOURNAL_FSYNC", "always")
    metrics._hists.pop(("px_journal_fsync_seconds", ()), None)
    now = 1_700_000_000 * 10**9
    ts = _mkstore(4, 10_000, batch_rows=2048)
    journal.attach_store(ts, str(tmp_path))
    ts.table("http_events").write({
        "time_": np.full(100, now, dtype=np.int64),
        "service": ["a"] * 100, "latency": np.zeros(100)})

    rows = heat.storage_state_rows(ts, "pem7", now_ns=now)
    by_table = {r["table_name"]: r for r in rows}
    ev = by_table["http_events"]
    assert ev["agent"] == "pem7"
    assert ev["hot_rows"] + ev["sealed_batches"] * 2048 >= 10_000
    assert ev["sealed_bytes"] > 0
    assert ev["journal_bytes"] > 0 and ev["journal_segments"] >= 1
    hist = json.loads(ev["age_histogram"])
    assert sum(hist.values()) == ev["sealed_batches"]
    # the fsync tax was measured into the histogram family
    assert any(k[0] == "px_journal_fsync_seconds" for k in metrics._hists)

    # fold writes both self tables and stamps the per-agent journal gauge
    heat.record_feed("http_events", "pem7", 50, 400, now_ns=now)
    n = heat.fold_into(ts, "pem7", now_ns=now)
    assert n >= 1 + len(rows)
    assert ts.table(observe.SHARD_HEAT_TABLE).stats()["rows_written"] == 1
    got = metrics._gauges.get(("px_journal_bytes", (("agent", "pem7"),)))
    assert got is not None and got > 0
    journal.detach_store(ts)


def test_journal_disk_usage_tracks_segments(tmp_path):
    flags.set_for_testing("PL_JOURNAL_FSYNC", "off")
    j = journal.TableJournal(str(tmp_path / "j"))
    assert j.disk_usage() == (0, 0)
    j.append(b"x" * 1000)
    j.append(b"y" * 1000)
    nbytes, nsegs = j.disk_usage()
    assert nsegs == 1 and nbytes > 2000  # payload + record headers
    j.close()


def test_matview_and_replication_fields_are_duck_typed():
    class _View:
        def __init__(self, table, nbytes):
            self.table = table
            self.state_bytes = nbytes

    class _T:
        name = "http_events"

    class _MV:
        _views = {"q1": _View(_T(), 100), "q2": _View(_T(), 50)}

    class _Repl:
        def lag(self):
            return {"pem1": 3, "pem2": 0}

    ts = _mkstore(5, 100)
    rows = heat.storage_state_rows(ts, "pem0", now_ns=10**18,
                                   matviews=_MV(), replication=_Repl())
    ev = {r["table_name"]: r for r in rows}["http_events"]
    assert ev["matview_bytes"] == 150
    assert ev["repl_lag_batches"] == 3
    assert json.loads(ev["peer_lag"]) == {"pem1": 3, "pem2": 0}


# ------------------------------------------------- replication sync state


def test_replication_sync_state_and_lag_gauge():
    from pixie_tpu.services import replication as repl

    mgr = repl.ReplicationManager("pem0", TableStore())
    with mgr._lock:
        mgr._sent = {"pem1": 10, "pem2": 4}
        mgr._acked = {"pem1": 7, "pem2": 4}
    st = mgr.sync_state()
    assert st["pem1"] == {"sent": 10, "acked": 7, "lag": 3}
    assert st["pem2"]["lag"] == 0
    assert mgr.lag() == {"pem1": 3, "pem2": 0}
    with repl._MANAGERS_LOCK:
        repl._MANAGERS.append(mgr)
    try:
        gauges = repl._lag_gauges()
        assert gauges[(("peer", "pem1"),)] == 3.0
    finally:
        with repl._MANAGERS_LOCK:
            repl._MANAGERS.remove(mgr)


# --------------------------------------------------- acceptance: 1% skew


def test_folded_skew_agrees_with_raw_shard_rows_within_1pct():
    """Acceptance: the shard_heat skew factor must agree with the skew
    computed from raw per-shard scanned rows within 1% on a multi-agent
    run (uniform decay preserves shard ratios)."""
    sizes = {"pem0": 4000, "pem1": 12_000, "pem2": 8000}
    stores = {n: _mkstore(i, sz)
              for i, (n, sz) in enumerate(sizes.items())}
    cl = LocalCluster(stores)
    for _ in range(3):
        cl.query(SCRIPT)
    assert cl.fold_storage_observatory() > 0
    first = sorted(cl.stores)[0]
    assert cl.stores[first].table(
        observe.SHARD_HEAT_TABLE).stats()["rows_written"] > 0
    rows = heat.snapshot_rows()  # same model the fold serialized
    folded_skew = {r["shard"]: r["skew"] for r in rows
                   if r["table_name"] == "http_events"}
    skew = next(iter(folded_skew.values()))
    assert all(s == skew for s in folded_skew.values())
    # oracle: skew from the raw row counts each agent actually scanned
    per_shard = {}
    for r in rows:
        if r["table_name"] == "http_events":
            per_shard[r["shard"]] = (per_shard.get(r["shard"], 0)
                                     + r["rows_scanned"])
    # repeated identical queries may be served from the standing matview
    # (no scan), so only the per-shard RATIOS are guaranteed
    k = per_shard["pem0"] / sizes["pem0"]
    assert k >= 1
    assert per_shard == {n: k * sz for n, sz in sizes.items()}
    oracle = max(sizes.values()) / (sum(sizes.values()) / len(sizes))
    assert abs(skew - oracle) / oracle < 0.01


# ------------------------------------------------------- broker e2e + CLI


@pytest.fixture
def cluster():
    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    stores = {"pem0": _mkstore(10, 4000), "pem1": _mkstore(11, 8000)}
    agents = [Agent(n, "127.0.0.1", broker.port, store=st, heartbeat_s=0.2,
                    healthz_port=0).start()
              for n, st in stores.items()]
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    yield broker, stores, agents, client
    client.close()
    for a in agents:
        a.stop()
    broker.stop()


def test_heat_map_rpc_and_cli_storage(cluster, capsys):
    broker, stores, agents, client = cluster
    client.execute_script(SCRIPT)
    hm = client.heat_map()
    assert set(hm["agents"]) == {"pem0", "pem1"}
    for rep in hm["agents"].values():
        assert not rep.get("error")
        names = {r["table_name"] for r in rep["storage_state"]}
        assert "http_events" in names
    t = hm["tables"]["http_events"]
    assert set(t["shards"]) == {"pem0", "pem1"}
    # every scan of the 4000+8000 split is fully attributed (the matview
    # build pass scans too, so the total is a multiple of the data size)
    assert t["rows_scanned"] >= 12_000 and t["rows_scanned"] % 12_000 == 0
    # shard heat ratio tracks the 8000:4000 row split
    assert t["shards"]["pem1"] > t["shards"]["pem0"]
    assert 1.0 <= t["skew"] <= 1.5
    # the broker stamped per-agent journal gauges (zero without journals,
    # but the series exist)
    keys = {k for k in metrics._gauges if k[0] == "px_journal_bytes"}
    assert {(("agent", "pem0"),), (("agent", "pem1"),)} <= {
        k[1] for k in keys}

    # the CLI renders the same map ("df for the data plane")
    from pixie_tpu import cli

    from types import SimpleNamespace

    args = SimpleNamespace(broker=f"127.0.0.1:{broker.port}",
                           auth_token=None)
    assert cli.cmd_storage(args) == 0
    out = capsys.readouterr().out
    assert "shard heat" in out and "http_events" in out
    assert "agent pem0 storage state" in out


def test_retire_info_includes_peer_sync(cluster):
    broker, stores, agents, client = cluster
    # replication off: refused retire still reports (empty) peer sync state
    res = broker.retire_agent("pem0")
    assert "peer_sync" in res
    assert res["peer_sync"] == {}


def test_healthz_detail_reports_journal_usage(tmp_path):
    flags.set_for_testing("PL_JOURNAL_FSYNC", "off")
    broker = Broker(hb_expiry_s=5.0).start()
    ts = _mkstore(12, 500)
    journal.attach_store(ts, str(tmp_path))
    ts.table("http_events").write({
        "time_": np.zeros(10, dtype=np.int64), "service": ["a"] * 10,
        "latency": np.zeros(10)})
    agent = Agent("pem0", "127.0.0.1", broker.port, store=ts,
                  heartbeat_s=0.5, healthz_port=0).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{agent.healthz.port}/healthz",
                timeout=5) as r:
            doc = json.loads(r.read())
        assert doc["ok"]
        j = doc["detail"]["journal"]
        assert j["total_bytes"] > 0
        assert j["tables"]["http_events"]["segments"] >= 1
        assert j["budget_mb"] == int(flags.get("PL_JOURNAL_MAX_MB"))
    finally:
        agent.stop()
        broker.stop()
        journal.detach_store(ts)
