"""Durable data plane: ingest journal, sealed-batch replication, failover,
rehydration (ISSUE 12).

The failure matrix: torn journal tails truncate cleanly and replay stays
idempotent; a true pod loss (store dropped via the faultinject `kill:` rule,
optionally the data dir wiped too) recovers every acknowledged row by
journal replay and/or peer fetch; queries during the outage serve bit-equal
from promoted replicas; matview standing state resumes at O(delta) from
durable snapshots; the KV store survives reopen-after-kill; and the
per-agent metric/state id spaces stay bounded.
"""
from __future__ import annotations

import os
import shutil
import threading
import time

import numpy as np
import pytest

from pixie_tpu import flags, metrics
from pixie_tpu.services import faultinject, replication
from pixie_tpu.services.agent import Agent
from pixie_tpu.services.broker import Broker
from pixie_tpu.services.client import Client
from pixie_tpu.services.kvstore import KVStore
from pixie_tpu.table import TableStore, journal
from pixie_tpu.testing.fixtures import canonical_bytes
from pixie_tpu.types import DataType as DT, Relation

REL = Relation.of(
    ("time_", DT.TIME64NS), ("service", DT.STRING),
    ("latency", DT.FLOAT64), ("status", DT.INT64),
)

AGG_SCRIPT = """
df = px.DataFrame(table='http_events')
df = df.groupby('service').agg(cnt=('latency', px.count),
                               mx=('latency', px.max))
px.display(df, 'out')
"""

DUR_FLAGS = ("PL_DATA_DIR", "PL_REPLICATION", "PL_QUERY_RETRIES",
             "PL_RETRY_BACKOFF_MS", "PL_CLIENT_RETRIES", "PL_REJOIN_GRACE_S",
             "PL_JOURNAL_FSYNC", "PL_JOURNAL_SEG_MB", "PL_JOURNAL_MAX_MB")


@pytest.fixture(autouse=True)
def _restore_flags():
    saved = {n: flags.get(n) for n in DUR_FLAGS}
    yield
    for n, v in saved.items():
        flags.set_for_testing(n, v)
    faultinject.uninstall()


def _mkdata(seed, n):
    rng = np.random.default_rng(seed)
    return {
        "time_": np.arange(n, dtype=np.int64) * 1000,
        "service": rng.choice(["cart", "auth", "web"], n).tolist(),
        "latency": rng.integers(0, 1000, n).astype(np.float64),
        "status": rng.choice([200, 500], n),
    }


def _mkstore(batch_rows=2048):
    ts = TableStore()
    ts.create("http_events", REL, batch_rows=batch_rows, max_bytes=1 << 32)
    return ts


def _table_bytes(ts):
    """Canonical content fingerprint: every batch's columns, dictionary
    codes decoded (code spaces must be deterministic across replays)."""
    t = ts.table("http_events")
    out = []
    for rb, rid, _gen in t.cursor():
        for c in sorted(rb.columns):
            arr = rb.columns[c][:rb.num_valid]
            if c in t.dictionaries:
                out.append("\x00".join(
                    str(v) for v in t.dictionaries[c].decode(arr)).encode())
            else:
                out.append(arr.tobytes())
    return b"\x01".join(out)


# ------------------------------------------------------------------ journal


def test_journal_replay_restores_bit_identical_store(tmp_path):
    ts = _mkstore()
    journal.attach_store(ts, str(tmp_path))
    t = ts.table("http_events")
    for i in range(3):
        t.write(_mkdata(i, 3000))
    want = _table_bytes(ts)
    journal.detach_store(ts)

    ts2 = TableStore()
    stats = journal.attach_store(ts2, str(tmp_path))
    assert stats["rows"] == 9000 and stats["tables"] == 1
    assert ts2.table("http_events").batch_rows == 2048  # schema.json
    assert _table_bytes(ts2) == want


def test_journal_torn_tail_truncates_and_reingest_is_idempotent(tmp_path):
    flags.set_for_testing("PL_JOURNAL_FSYNC", "off")
    ts = _mkstore()
    journal.attach_store(ts, str(tmp_path))
    t = ts.table("http_events")
    t.write(_mkdata(1, 3000))
    t.write(_mkdata(2, 3000))
    journal.detach_store(ts)
    jdir = os.path.join(str(tmp_path), "journal", "http_events")
    seg = journal.TableJournal(jdir).segments()[-1]
    good = os.path.getsize(seg)

    # torn write: a partial record (valid magic, length past EOF)
    with open(seg, "ab") as f:
        f.write(journal.REC_MAGIC + (500).to_bytes(4, "little")
                + (0).to_bytes(4, "little") + b"short")
    ts2 = TableStore()
    stats = journal.attach_store(ts2, str(tmp_path))
    assert stats["rows"] == 6000
    assert stats["truncated"] > 0
    assert os.path.getsize(seg) == good  # recover() truncated the tail
    # re-ingest after the watermark extends the SAME journal cleanly
    ts2.table("http_events").write(_mkdata(3, 3000))
    want = _table_bytes(ts2)
    journal.detach_store(ts2)
    ts3 = TableStore()
    stats = journal.attach_store(ts3, str(tmp_path))
    assert stats["rows"] == 9000
    assert _table_bytes(ts3) == want
    journal.detach_store(ts3)

    # bad CRC on the tail record: replay truncates at the last valid one
    payloads, valid, clean = journal.scan_segment(seg)
    assert clean
    with open(seg, "r+b") as f:
        f.seek(valid - 1)
        b = f.read(1)
        f.seek(valid - 1)
        f.write(bytes([b[0] ^ 0xFF]))
    _, valid2, clean2 = journal.scan_segment(seg)
    assert not clean2 and valid2 < valid
    ts4 = TableStore()
    stats = journal.attach_store(ts4, str(tmp_path))
    assert stats["rows"] < 9000  # the corrupted tail record dropped
    # and the re-ingest of the lost rows after the watermark is idempotent
    # for everything already replayed: only the missing delta applies
    have = ts4.table("http_events").stats()["rows_written"]
    assert have == stats["rows"]
    journal.detach_store(ts4)


def test_journal_replay_skips_already_present_rows(tmp_path):
    ts = _mkstore()
    journal.attach_store(ts, str(tmp_path))
    ts.table("http_events").write(_mkdata(1, 3000))
    journal.detach_store(ts)
    # re-attach to the SAME live store: every record's watermark is below
    # the row count, so replay applies nothing
    stats = journal.attach_store(ts, str(tmp_path))
    assert stats["applied"] == 0 and stats["rows"] == 0
    assert ts.table("http_events").stats()["rows_written"] == 3000
    journal.detach_store(ts)


def test_journal_segment_rotation_and_new_table_observer(tmp_path):
    flags.set_for_testing("PL_JOURNAL_SEG_MB", 1)
    ts = _mkstore()
    journal.attach_store(ts, str(tmp_path))
    t = ts.table("http_events")
    for i in range(12):
        t.write(_mkdata(i, 4096))  # ~130KB/record → rotates past 1MB
    jdir = os.path.join(str(tmp_path), "journal", "http_events")
    assert len(journal.TableJournal(jdir).segments()) >= 2
    # a table created AFTER attach journals too (store observer)
    t2 = ts.create("later", REL, batch_rows=1024)
    t2.write(_mkdata(99, 500))
    journal.detach_store(ts)
    ts2 = TableStore()
    stats = journal.attach_store(ts2, str(tmp_path))
    assert stats["rows"] == 12 * 4096 + 500
    assert ts2.table("later").stats()["rows_written"] == 500
    assert _table_bytes(ts2) == _table_bytes(ts)


def test_journal_replay_slices_partial_overlap(tmp_path):
    """A record straddling the store's existing watermark applies only its
    missing tail — never duplicates the head rows."""
    ts = _mkstore()
    journal.attach_store(ts, str(tmp_path))
    ts.table("http_events").write(_mkdata(1, 1000))
    ts.table("http_events").write(_mkdata(2, 1000))
    want = _table_bytes(ts)
    journal.detach_store(ts)

    ts2 = _mkstore()
    d1, d2 = _mkdata(1, 1000), _mkdata(2, 1000)
    ts2.table("http_events").write(d1)
    ts2.table("http_events").write({c: v[:500] for c, v in d2.items()})
    stats = journal.attach_store(ts2, str(tmp_path))
    assert stats["rows"] == 500  # only the missing tail applied
    assert ts2.table("http_events").stats()["rows_written"] == 2000
    assert _table_bytes(ts2) == want
    journal.detach_store(ts2)


def test_journal_prunes_to_byte_budget_and_replays_tail(tmp_path):
    flags.set_for_testing("PL_JOURNAL_SEG_MB", 1)
    flags.set_for_testing("PL_JOURNAL_MAX_MB", 2)
    ts = _mkstore()
    journal.attach_store(ts, str(tmp_path))
    for i in range(40):
        ts.table("http_events").write(_mkdata(i, 4096))  # ~5MB of records
    journal.detach_store(ts)
    jdir = os.path.join(str(tmp_path), "journal", "http_events")
    segs = journal.TableJournal(jdir).segments()
    assert sum(os.path.getsize(p) for p in segs) <= (3 << 20)
    assert metrics.counter_value("px_journal_pruned_segments_total") >= 1
    # replay past the pruned head ADVANCES the fresh store's row frontier:
    # the tail keeps its ABSOLUTE ids (peer-fetch coverage arithmetic and
    # watermark accounting stay consistent); head rows count as expired
    ts2 = TableStore()
    stats = journal.attach_store(ts2, str(tmp_path))
    t2 = ts2.table("http_events")
    assert t2.last_row_id() == 40 * 4096
    assert t2.first_row_id() > 0
    assert stats["rows"] == t2.last_row_id() - t2.first_row_id()
    assert metrics.counter_value(
        "px_journal_pruned_head_replays_total") >= 1
    journal.detach_store(ts2)


def test_takeover_store_stops_at_replication_hole():
    """A missing replicated batch must truncate the takeover serve at the
    hole — later batches at wrong row ids would silently corrupt answers."""
    rs = replication.ReplicaStore()
    ts = _mkstore(batch_rows=512)
    ts.table("http_events").write(_mkdata(1, 1536))
    t = ts.table("http_events")
    batches = [(rb, rid) for rb, rid, gen in t.cursor(include_hot=False)
               if gen is not None]
    assert len(batches) == 3
    for rb, rid in batches:
        if rid == 512:
            continue  # the lost send
        frame = replication.encode_sealed(t, rb, rid, "p1", 1)
        from pixie_tpu.services import wire

        kind, payload = wire.decode_frame(frame)
        rs.put(payload.wire_meta, journal.decode_columns(payload))
    tstore = rs.takeover_store("p1")
    # only the contiguous prefix (rows [0, 512)) serves; the hole counted
    assert tstore.table("http_events").stats()["rows_written"] == 512
    assert metrics.counter_value("px_repl_takeover_holes_total") >= 1


@pytest.mark.slow
def test_journal_fsync_always_durable(tmp_path):
    """fsync-per-record policy: every acked write is on disk before the
    ack (heavy: one fsync per append)."""
    flags.set_for_testing("PL_JOURNAL_FSYNC", "always")
    ts = _mkstore(batch_rows=256)
    journal.attach_store(ts, str(tmp_path))
    t = ts.table("http_events")
    for i in range(64):
        t.write(_mkdata(i, 256))
    # crash WITHOUT detach/close: the file contents must already be complete
    ts2 = TableStore()
    stats = journal.attach_store(ts2, str(tmp_path))
    assert stats["rows"] == 64 * 256
    journal.detach_store(ts2)
    journal.detach_store(ts)


# ------------------------------------------------------------------ kvstore


def test_kvstore_wal_reopen_after_kill(tmp_path):
    path = str(tmp_path / "kv.db")
    kv = KVStore(path)
    assert kv._conn.execute("PRAGMA journal_mode").fetchone()[0] == "wal"
    for i in range(50):
        kv.set(f"k/{i}", str(i).encode())
    assert kv.cas("lease", None, b"owner-a")
    # KILL: no close() — a second handle must still see every committed
    # write (WAL recovery), and writes through it must work
    kv2 = KVStore(path)
    assert kv2.get("k/49") == b"49"
    assert sum(1 for _ in kv2.scan("k/")) == 50
    assert not kv2.cas("lease", None, b"owner-b")  # lease still held
    assert kv2.cas("lease", b"owner-a", b"owner-b")
    kv2.close()
    kv.close()


@pytest.mark.parametrize("path", [":memory:", "FILE"])
def test_kvstore_concurrent_cas_stress(tmp_path, path):
    kv = KVStore(str(tmp_path / "kv.db") if path == "FILE" else path)
    kv.set("ctr", b"0")
    wins = []

    def worker():
        w = 0
        for _ in range(200):
            while True:
                cur = kv.get("ctr")
                if kv.cas("ctr", cur, str(int(cur) + 1).encode()):
                    w += 1
                    break
        wins.append(w)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # every cas win is exactly one increment: no lost updates, no doubles
    assert int(kv.get("ctr")) == sum(wins) == 8 * 200
    kv.close()


# -------------------------------------------------------------- fault rules


def test_faultinject_kill_rule_fires_handler_once():
    seed, rules = faultinject.parse_plan("kill:agent:pem1@send=2")
    assert rules[0].action == "kill" and rules[0].frame == 2
    with pytest.raises(Exception):
        faultinject.parse_plan("kill:agent:pem1")  # needs a frame index
    fired = []
    faultinject.register_kill_handler("agent:pem1", lambda: fired.append(1))
    try:
        inj = faultinject.FaultInjector("kill:agent:pem1@send=2")
        assert inj.on_frame(1, "agent:pem1", "send") is None
        d = inj.on_frame(1, "agent:pem1", "send")
        assert d is not None and d.action == "kill"
        assert faultinject.fire_kill("agent:pem1") and fired == [1]
        # one-shot: a restarted agent's fresh connection never re-kills
        assert inj.on_frame(2, "agent:pem1", "send") is None
        assert inj.on_frame(2, "agent:pem1", "send") is None
        # decision-log determinism: same plan + same frame sequence → same log
        inj2 = faultinject.FaultInjector("kill:agent:pem1@send=2")
        inj2.on_frame(1, "agent:pem1", "send")
        inj2.on_frame(1, "agent:pem1", "send")
        assert inj2.log == inj.log[:len(inj2.log)]
        assert ("agent:pem1", "send", 2, "kill") in inj2.log
    finally:
        faultinject.unregister_kill_handler("agent:pem1")
    assert not faultinject.fire_kill("agent:pem1")  # unregistered: no-op


# ------------------------------------------------------- label/state bounds


def test_capped_label_bounds_id_space():
    metrics.reset_for_testing()
    try:
        for i in range(metrics.MAX_LABEL_IDS):
            assert metrics.capped_label("agent", f"a{i}") == f"a{i}"
        assert metrics.capped_label("agent", "overflow") == "__other__"
        assert metrics.capped_label("agent", "a0") == "a0"  # known ids keep
        # families are independent
        assert metrics.capped_label("tenant", "overflow") == "overflow"
    finally:
        metrics.reset_for_testing()


def test_service_time_model_bounded():
    broker = Broker(hb_expiry_s=30.0)
    try:
        for i in range(Broker.MAX_SVC_AGENTS + 50):
            broker._record_service_time(f"agent-{i:04d}", 0.01)
        assert len(broker._svc) <= Broker.MAX_SVC_AGENTS
        # a re-appearing agent re-warms without unbounded growth
        broker._record_service_time("agent-0000", 0.02)
        assert len(broker._svc) <= Broker.MAX_SVC_AGENTS
    finally:
        broker.stop()


def test_resident_drop_table_frees_entries():
    import types

    from pixie_tpu.engine import resident

    resident.clear_for_testing()
    with resident._LOCK:
        resident._TIER[(7, ("c",), 1)] = types.SimpleNamespace(nbytes=64)
        resident._TIER[(8, ("c",), 1)] = types.SimpleNamespace(nbytes=64)
        resident._TIER_BYTES = 128
    resident.drop_table(7)
    st = resident.tier_stats()
    assert st["entries"] == 1 and st["bytes"] == 64
    resident.clear_for_testing()


# -------------------------------------------------- replication + failover


def _start_cluster(tmp_path, n_agents=3, rows=4096, batch_rows=1024,
                   grace=0.4):
    flags.set_for_testing("PL_DATA_DIR", str(tmp_path))
    flags.set_for_testing("PL_REPLICATION", 2)
    flags.set_for_testing("PL_QUERY_RETRIES", 4)
    flags.set_for_testing("PL_RETRY_BACKOFF_MS", 60)
    flags.set_for_testing("PL_CLIENT_RETRIES", 4)
    flags.set_for_testing("PL_REJOIN_GRACE_S", grace)
    flags.set_for_testing("PL_JOURNAL_FSYNC", "batch")
    broker = Broker(hb_expiry_s=2.0, query_timeout_s=30.0).start()
    agents = {}
    for i in range(n_agents):
        name = f"pem{i}"
        agents[name] = Agent(name, "127.0.0.1", broker.port,
                             store=_mkstore(batch_rows),
                             heartbeat_s=0.3).start()
    for i, name in enumerate(sorted(agents)):
        agents[name].store.table("http_events").write(_mkdata(i + 1, rows))
    deadline = time.monotonic() + 10.0
    for a in agents.values():
        assert a.replication.wait_synced(
            max(deadline - time.monotonic(), 0.1))
    return broker, agents


def _stop_cluster(broker, agents, client=None):
    if client is not None:
        client.close()
    for a in agents.values():
        try:
            a.stop()
        except Exception:
            pass
    broker.stop()


def test_shard_map_maintained_on_join_and_evict(tmp_path):
    broker, agents = _start_cluster(tmp_path, n_agents=3, rows=1024)
    try:
        m = broker.registry.shard_map()
        assert set(m) == {"pem0", "pem1", "pem2"}
        assert all(len(v) == 1 and v[0] != k for k, v in m.items())
        # evict pem1: the survivors' replica rings re-close around it, and
        # the dead primary KEEPS an entry (failover needs its replicas)
        agents["pem1"]._pod_kill()
        agents["pem1"].conn.abort()
        time.sleep(0.3)
        m2 = broker.registry.shard_map()
        assert set(m2) == {"pem0", "pem1", "pem2"}
        assert m2["pem0"] == ["pem2"] and m2["pem2"] == ["pem0"]
        assert m2["pem1"] and m2["pem1"][0] in ("pem0", "pem2")
        assert broker._failover_map()  # the dead primary fails over
        # operator DECOMMISSION: the retired node leaves the shard map,
        # failover, and catch-up — it must not degrade dispatch forever
        assert broker.registry.deregister("pem1")
        broker._push_shard_map()
        assert "pem1" not in broker.registry.shard_map()
        assert broker._failover_map() == {}
        assert broker.serving.catchup_shards == 0
        assert not broker.registry.deregister("pem1")  # idempotent
    finally:
        _stop_cluster(broker, agents)


def test_failover_serves_dead_primarys_shard_bit_equal(tmp_path):
    broker, agents = _start_cluster(tmp_path)
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        base = canonical_bytes(client.execute_script(AGG_SCRIPT))
        agents["pem1"]._pod_kill()  # store GONE — replicas must serve
        agents["pem1"].conn.abort()
        time.sleep(0.6)  # past the rejoin grace
        res, stats = None, None
        res = client.execute_script(AGG_SCRIPT)
        assert canonical_bytes(res) == base
        stats = next(iter(res.values())).exec_stats
        assert "pem1" in stats["agents"]
        assert stats["agents"]["pem1"].get("takeover", {}).get(
            "replica") in ("pem0", "pem2")
        assert metrics.counter_value("px_failover_serves_total") >= 1
        assert metrics.counter_value(
            "px_broker_failover_dispatches_total") >= 1
        # catch-up degradation armed while the shard is failover-served
        assert broker.serving.catchup_shards == 1
    finally:
        _stop_cluster(broker, agents, client)


def test_rehydration_journal_replay_and_peer_fetch(tmp_path):
    broker, agents = _start_cluster(tmp_path)
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        base = canonical_bytes(client.execute_script(AGG_SCRIPT))
        want = _table_bytes(agents["pem1"].store)

        # -- journal path: pod dies, data dir survives
        agents["pem1"]._pod_kill()
        agents["pem1"].conn.abort()
        agents["pem1"] = Agent("pem1", "127.0.0.1", broker.port,
                               store=TableStore(), heartbeat_s=0.3).start()
        assert agents["pem1"].rehydrate_stats["journal"]["rows"] >= 4096
        assert _table_bytes(agents["pem1"].store) == want
        assert canonical_bytes(client.execute_script(AGG_SCRIPT)) == base

        # -- peer-fetch path: the data dir dies WITH the pod
        agents["pem1"]._pod_kill()
        agents["pem1"].conn.abort()
        shutil.rmtree(os.path.join(str(tmp_path), "pem1"),
                      ignore_errors=True)
        agents["pem1"] = Agent("pem1", "127.0.0.1", broker.port,
                               store=TableStore(), heartbeat_s=0.3).start()
        fetch = agents["pem1"].rehydrate_stats.get("fetch") or {}
        assert fetch.get("rows", 0) == 4096  # all sealed rows recovered
        assert _table_bytes(agents["pem1"].store) == want
        assert canonical_bytes(client.execute_script(AGG_SCRIPT)) == base
        # rejoin clears catch-up degradation
        time.sleep(0.2)
        assert broker.serving.catchup_shards == 0
    finally:
        _stop_cluster(broker, agents, client)


def test_replication_disabled_keeps_legacy_surface(tmp_path):
    flags.set_for_testing("PL_REPLICATION", 1)
    flags.set_for_testing("PL_DATA_DIR", "")
    broker = Broker(hb_expiry_s=2.0).start()
    try:
        a = Agent("pem0", "127.0.0.1", broker.port, store=_mkstore(),
                  heartbeat_s=0.5).start()
        assert a.replication is None
        assert a._owns_journal is False
        rec = broker.registry.record("pem0")
        assert rec is not None and rec.repl_addr is None
        assert broker.registry.shard_map() == {}  # no KV writes
        assert broker._failover_map() == {}
        a.stop()
    finally:
        broker.stop()


def test_replica_backfill_covers_batches_sealed_before_join():
    """A target added to the shard map AFTER batches sealed still receives
    them (the late-joining replica backfill)."""
    flags.set_for_testing("PL_REPLICATION", 2)
    ts = _mkstore(batch_rows=512)
    prim = replication.ReplicationManager("p1", ts).start()
    ts.table("http_events").write(_mkdata(1, 2048))  # seals BEFORE any peer
    rep = replication.ReplicationManager("r1", TableStore()).start()
    try:
        prim.on_shard_map({"p1": ["r1"]},
                          {"r1": ["127.0.0.1", rep.port]})
        assert prim.wait_synced(10.0)
        man = rep.replicas.manifest("p1")
        assert [r for r, _ in (man["http_events"]["ranges"] or [])] == [
            0, 512, 1024, 1536]
        # takeover store materializes the primary's content bit-identically
        tstore = rep.replicas.takeover_store("p1")
        assert _table_bytes(tstore) == _table_bytes(ts)
        # content-version caching: same store until new batches arrive
        assert rep.replicas.takeover_store("p1") is tstore
    finally:
        prim.stop()
        rep.stop()


# ------------------------------------------------------- matview snapshots


def test_matview_snapshot_restores_standing_state(tmp_path):
    from pixie_tpu.matview import MatViewManager
    from pixie_tpu.plan.plan import AggExpr, AggOp, MemorySourceOp, Plan, \
        ResultSinkOp

    def _plan():
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        agg = p.add(AggOp(groups=["service"],
                          values=[AggExpr("cnt", "count", None)],
                          partial=True), parents=[src])
        p.add(ResultSinkOp(channel="mv", payload="agg_state"), parents=[agg])
        return p

    flags.set_for_testing("PL_MATVIEW_ENABLED", True)
    ts = _mkstore()
    t = ts.table("http_events")
    t.write(_mkdata(1, 4096))
    mgr = MatViewManager(ts)
    mgr.set_snapshot_dir(str(tmp_path / "mv"))
    assert mgr.serve(_plan()) is None  # first sight registers
    cid, pb, info = mgr.serve(_plan())  # refresh folds + snapshots
    assert info["rows_folded"] == 4096

    # a restarted agent: same (restored) table content, fresh manager —
    # first sight ADOPTS the snapshot and serves, folding only the delta
    t.write(_mkdata(2, 1000))
    mgr2 = MatViewManager(ts)
    mgr2.set_snapshot_dir(str(tmp_path / "mv"))
    served = mgr2.serve(_plan())
    assert served is not None, "snapshot adoption must serve on first sight"
    cid2, pb2, info2 = served
    assert info2["rows_folded"] == 1000  # O(delta), not a 5096-row rescan
    assert metrics.counter_value("px_matview_snapshot_restores_total") >= 1
    # the adopted answer equals the continuously-maintained one
    _c, pb_cont, _i = mgr.serve(_plan())
    a = dict(zip(pb_cont.key_cols["service"].tolist(),
                 np.asarray(pb_cont.states["cnt"]).tolist()))
    b = dict(zip(pb2.key_cols["service"].tolist(),
                 np.asarray(pb2.states["cnt"]).tolist()))
    assert a == b


def test_matview_snapshot_rejects_stale_or_torn(tmp_path):
    from pixie_tpu.matview import MatViewManager
    from pixie_tpu.plan.plan import AggExpr, AggOp, MemorySourceOp, Plan, \
        ResultSinkOp

    def _plan():
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        agg = p.add(AggOp(groups=["service"],
                          values=[AggExpr("cnt", "count", None)],
                          partial=True), parents=[src])
        p.add(ResultSinkOp(channel="mv", payload="agg_state"), parents=[agg])
        return p

    flags.set_for_testing("PL_MATVIEW_ENABLED", True)
    ts = _mkstore()
    ts.table("http_events").write(_mkdata(1, 4096))
    mgr = MatViewManager(ts)
    mgr.set_snapshot_dir(str(tmp_path / "mv"))
    mgr.serve(_plan())
    mgr.serve(_plan())
    snaps = os.listdir(str(tmp_path / "mv"))
    assert len(snaps) == 1
    path = os.path.join(str(tmp_path / "mv"), snaps[0])
    # torn snapshot (flipped byte → CRC fail) must NOT adopt
    with open(path, "r+b") as f:
        f.seek(40)
        b = f.read(1)
        f.seek(40)
        f.write(bytes([b[0] ^ 0xFF]))
    mgr2 = MatViewManager(ts)
    mgr2.set_snapshot_dir(str(tmp_path / "mv"))
    assert mgr2.serve(_plan()) is None  # falls back to register-only


# --------------------------------------------------------- chaos during move


def test_rehome_incarnation_fence_aborts_and_donor_keeps_owning(tmp_path):
    """ISSUE 18 chaos: the donor 'restarts' mid-move (incarnation bump
    between prepare and verify).  The fence must abort the move before
    commit: staged replica unstaged, durable move/ record gone, ownership
    with the donor, every acknowledged row still served bit-equal."""
    broker, agents = _start_cluster(tmp_path)
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        base = canonical_bytes(client.execute_script(AGG_SCRIPT))
        own_map = broker.registry.shard_map()
        real_rpc = broker._agent_rpc
        real_inc = broker.registry.incarnation
        restarted = {"flag": False}

        def chaos_rpc(name, payload, timeout=5.0):
            res = real_rpc(name, payload, timeout=timeout)
            if payload.get("msg") == "rehome_prepare":
                restarted["flag"] = True  # donor "restarts" after prepare
            return res

        def chaos_inc(name):
            inc = real_inc(name)
            if restarted["flag"] and name == "pem0":
                return inc + 1000
            return inc

        broker._agent_rpc = chaos_rpc
        broker.registry.incarnation = chaos_inc
        try:
            res = broker.rehome_agent("pem0", target="pem2", reason="chaos")
        finally:
            broker._agent_rpc = real_rpc
            broker.registry.incarnation = real_inc
        assert not res["ok"]
        assert res["reason"] == "incarnation changed mid-move"
        assert metrics.counter_value("px_rehome_aborts_total") >= 1
        # abort left no trace: no move record, no staged replica, and the
        # shard map owns exactly what it owned before the move started
        assert list(broker.kv.scan("move/")) == []
        assert broker.registry.extra_replicas("pem0") == []
        assert broker.registry.shard_map() == own_map
        # zero loss: the donor still owns and serves its shard bit-equal
        assert canonical_bytes(client.execute_script(AGG_SCRIPT)) == base
        # and the aborted move left the donor fully retryable
        res2 = broker.rehome_agent("pem0", target="pem2", reason="retry")
        assert res2["ok"], res2
        assert canonical_bytes(client.execute_script(AGG_SCRIPT)) == base
    finally:
        _stop_cluster(broker, agents, client)


def test_rehome_then_donor_death_serves_from_target(tmp_path):
    """After a committed move the staged copy leads the donor's replica
    list — a donor that dies WITHOUT retiring must fail over onto the
    re-homed target, bit-equal (the extras-first map ordering under real
    failover, not just in the registry)."""
    broker, agents = _start_cluster(tmp_path)
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        base = canonical_bytes(client.execute_script(AGG_SCRIPT))
        res = broker.rehome_agent("pem0", target="pem2", reason="drain")
        assert res["ok"], res
        assert broker.registry.shard_map()["pem0"][0] == "pem2"
        agents["pem0"]._pod_kill()  # store GONE — no retire, raw death
        agents["pem0"].conn.abort()
        time.sleep(0.6)  # past the rejoin grace
        out = client.execute_script(AGG_SCRIPT)
        assert canonical_bytes(out) == base
        stats = next(iter(out.values())).exec_stats
        assert stats["agents"]["pem0"].get("takeover", {}).get(
            "replica") == "pem2"
    finally:
        _stop_cluster(broker, agents, client)


def test_rehome_survives_broker_restart_mid_prepare(tmp_path):
    """Broker dies between staging and commit: the restarted broker's
    _abort_stale_moves unstages the extra replica, deletes the move
    record, and the donor serves on, owning its shard."""
    broker, agents = _start_cluster(tmp_path)
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        base = canonical_bytes(client.execute_script(AGG_SCRIPT))
        # freeze the move mid-prepare: durable record + staged replica,
        # then the broker "crashes" before verify/commit
        broker.kv.set_json("move/pem0", {
            "target": "pem2", "reason": "chaos", "phase": "prepare"})
        broker.registry.add_replica("pem0", "pem2")
        assert broker.registry.extra_replicas("pem0") == ["pem2"]
        stale0 = metrics.counter_value("px_rehome_stale_aborts_total")
        broker._abort_stale_moves()  # what Broker.start() replays
        assert metrics.counter_value(
            "px_rehome_stale_aborts_total") == stale0 + 1
        assert list(broker.kv.scan("move/")) == []
        assert broker.registry.extra_replicas("pem0") == []
        assert canonical_bytes(client.execute_script(AGG_SCRIPT)) == base
    finally:
        _stop_cluster(broker, agents, client)
