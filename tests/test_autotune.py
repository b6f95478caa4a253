"""Profile-fed adaptive gates (ISSUE 17): online per-gate cost models,
deterministic guarded exploration, the tail guard with its
`autotune_fallback` telemetry row, KV persistence across broker restarts
(warm first decision, corrupt record degrades), the bit-identity of
`PX_AUTOTUNE=0`, and the probe staleness horizon on the memoized
environment probes (engine/transfer.py)."""
from __future__ import annotations

import numpy as np
import pytest

from pixie_tpu import flags, metrics, observe
from pixie_tpu.engine import autotune, transfer
from pixie_tpu.engine.autotune import (
    GATE_BATCH_WINDOW, GATE_CPU_CROSSOVER, GATE_HEDGE, KV_KEY,
    AutotuneModel, size_bucket,
)
from pixie_tpu.parallel.cluster import LocalCluster
from pixie_tpu.services.broker import Broker
from pixie_tpu.services.kvstore import KVStore
from pixie_tpu.testing.fixtures import SCRIPTS, canonical_bytes, mkstore

import pixie_tpu.matview  # noqa: F401 — defines PL_MATVIEW_ENABLED

AT_FLAGS = (
    "PX_AUTOTUNE", "PX_AUTOTUNE_EPSILON", "PX_AUTOTUNE_MIN_SAMPLES",
    "PX_AUTOTUNE_GUARD_WINDOW", "PX_AUTOTUNE_GUARD_FACTOR",
    "PX_AUTOTUNE_GUARD_HOLDOFF", "PX_CPU_CROSSOVER_ROWS",
    "PL_MATVIEW_ENABLED",
)


@pytest.fixture(autouse=True)
def _isolated_model():
    saved = {n: flags.get(n) for n in AT_FLAGS}
    autotune.MODEL.reset_for_testing()
    yield
    for n, v in saved.items():
        flags.set_for_testing(n, v)
    autotune.MODEL.reset_for_testing()


def _warm(model, gate, arms_ms, plan_class="agg", bucket="4^8",
          n=None):
    """Feed `n` observations per arm (ms costs from arms_ms)."""
    n = n if n is not None else int(flags.get("PX_AUTOTUNE_MIN_SAMPLES"))
    for arm, ms in arms_ms.items():
        for _ in range(n):
            model.observe(gate, plan_class, bucket, arm, ms / 1e3)


# ------------------------------------------------------------------ model


def test_size_bucket_is_log_scale():
    assert size_bucket(0) == "4^0"
    assert size_bucket(5) == size_bucket(15)      # one 4x band
    assert size_bucket(100) != size_bucket(100_000)
    assert size_bucket((1 << 20) - 1) == "4^10"
    assert size_bucket(1 << 20) == "4^11"  # next band starts AT 4^10


def test_cold_model_stays_static_with_paced_probes():
    """A cold gate key serves the static arm except the bounded
    deterministic probe every COLD_PROBE_PERIODth decision — and the
    sequence replays identically on a fresh model (no randomness)."""
    def run():
        m = AutotuneModel()
        return [m.decide(GATE_CPU_CROSSOVER, "agg", "4^8", "device",
                         ("device", "cpu"))["source"] for _ in range(8)]

    seq = run()
    assert seq == run()  # deterministic
    probes = [i for i, s in enumerate(seq) if s == "explore"]
    assert probes == [autotune.COLD_PROBE_PERIOD - 1,
                      2 * autotune.COLD_PROBE_PERIOD - 1]
    assert all(s == "cold" for i, s in enumerate(seq) if i not in probes)


def test_warm_model_routes_to_measured_favorite():
    m = AutotuneModel()
    _warm(m, GATE_CPU_CROSSOVER, {"device": 90.0, "cpu": 2.0})
    dec = m.decide(GATE_CPU_CROSSOVER, "agg", "4^8", "device",
                   ("device", "cpu"))
    assert dec["arm"] == "cpu" and dec["source"] == "model"
    assert dec["model_ms"] < dec["static_ms"]


def test_warm_model_epsilon_probes_deterministically():
    flags.set_for_testing("PX_AUTOTUNE_EPSILON", 0.0625)  # every 16th
    m = AutotuneModel()
    _warm(m, GATE_CPU_CROSSOVER, {"device": 90.0, "cpu": 2.0})
    srcs = [m.decide(GATE_CPU_CROSSOVER, "agg", "4^8", "device",
                     ("device", "cpu"))["source"] for _ in range(32)]
    assert srcs.count("explore") == 2
    assert srcs[15] == "explore" and srcs[31] == "explore"


def _decide(m):
    return m.decide(GATE_CPU_CROSSOVER, "agg", "4^8", "device",
                    ("device", "cpu"))


def _warm_for_guard(m, device_ms=50.0, cpu_ms=2.0):
    """Both arms warm and past the guard's window; cpu (not the static
    arm) is the favorite."""
    window = int(flags.get("PX_AUTOTUNE_GUARD_WINDOW"))
    _warm(m, GATE_CPU_CROSSOVER, {"device": device_ms, "cpu": cpu_ms},
          n=max(window, int(flags.get("PX_AUTOTUNE_MIN_SAMPLES"))))
    return window


def test_tail_guard_trips_resets_arm_and_emits_fallback_row():
    """A model-favored arm of whose guard window half lies past the guard
    factor times the static arm's median snaps the gate back to static,
    keeps the arm's history less the samples that falsified it, and lands
    an autotune_fallback event row."""
    m = AutotuneModel()
    window = _warm_for_guard(m)
    # the favored cpu arm drifts: half a window of 500 ms samples, five
    # times the guard level (2 x the static arm's 50 ms)
    for _ in range(window // 2):
        m.observe(GATE_CPU_CROSSOVER, "agg", "4^8", "cpu", 500.0 / 1e3)
    dec = _decide(m)
    assert dec["arm"] == "device" and dec["source"] == "fallback"
    assert dec["guard_trips"] == 1
    # held off: the next decisions stay pinned static
    dec2 = _decide(m)
    assert dec2["source"] == "fallback" and dec2["arm"] == "device"
    assert m.snapshot()[GATE_CPU_CROSSOVER]["fallbacks"] == 1
    # the drifted arm keeps what was not falsified, and stays warm
    arm = m._gates[GATE_CPU_CROSSOVER].arms["agg|4^8"]["cpu"]
    assert list(arm.ring) == [2.0 / 1e3] * window
    assert arm.n >= int(flags.get("PX_AUTOTUNE_MIN_SAMPLES"))
    assert arm.cost() == pytest.approx(2.0 / 1e3)
    rows = m.drain_rows()
    assert len(rows) == 1 and rows[0]["source"] == "fallback"
    assert "autotune_fallback" in rows[0]["reason"]
    assert m.drain_rows() == []  # drained once


def test_one_outlier_neither_trips_guard_nor_changes_favorite():
    """One slow sample on the favored arm, however slow, is one of a guard
    window and one of a ring: the guard holds, the favorite stays, and the
    arm's predicted cost does not move."""
    m = AutotuneModel()
    window = _warm_for_guard(m, device_ms=42.0, cpu_ms=23.0)
    m.observe(GATE_CPU_CROSSOVER, "agg", "4^8", "cpu", 3.4)  # 3.4 s
    for i in range(2 * window):
        dec = _decide(m)
        assert dec["source"] in ("model", "explore"), (i, dec)
        assert dec["guard_trips"] == 0
        if dec["source"] == "model":
            assert dec["arm"] == "cpu"
            assert dec["model_ms"] == pytest.approx(23.0)
        m.observe_decision(dec, {"cpu": 23.0, "device": 42.0}[dec["arm"]]
                           / 1e3)
    assert m.snapshot()[GATE_CPU_CROSSOVER]["fallbacks"] == 0
    assert m.drain_rows() == []


def test_one_outlier_on_static_favorite_keeps_it():
    """Over the crossover the static arm is the favorite and no guard
    runs: a slow sample there must not hand the window to the other arm
    (the status cell's one-slow-sample flip)."""
    m = AutotuneModel()
    _warm(m, GATE_CPU_CROSSOVER, {"device": 118.0, "cpu": 200.0})
    m.observe(GATE_CPU_CROSSOVER, "agg", "4^8", "device", 0.9)
    dec = _decide(m)
    assert dec["arm"] == "device" and dec["source"] == "static"


def test_lasting_regression_trips_within_one_guard_window():
    """A regression of the favored arm that lasts trips the guard before
    a whole guard window of slow samples has been served."""
    m = AutotuneModel()
    window = _warm_for_guard(m)
    served = 0
    for _ in range(window):
        dec = _decide(m)
        if dec["source"] == "fallback":
            break
        if dec["arm"] == "cpu":
            served += 1
        m.observe_decision(dec, {"cpu": 500.0, "device": 50.0}[dec["arm"]]
                           / 1e3)
    assert dec["source"] == "fallback" and dec["arm"] == "device"
    assert served <= window // 2


def test_fallback_row_lands_in_self_telemetry_table():
    """The drained fallback row writes through the normal telemetry path
    and queries back from self_telemetry.autotune."""
    from pixie_tpu.table import TableStore

    row = {
        "time_": 10 ** 15, "query_id": "", "gate": "cpu_crossover",
        "plan_class": "agg", "size_bucket": "4^8", "arm": "device",
        "static_arm": "device", "source": "fallback", "model_ms": 500.0,
        "static_ms": 50.0, "observed_ms": 0.0,
        "reason": "autotune_fallback p99 500.0ms > 2x 50.0ms",
    }
    ts = TableStore()
    assert observe.write_rows(ts, observe.AUTOTUNE_TABLE, [row]) == 1
    c = LocalCluster({"pem0": ts})
    res = c.query(
        "df = px.DataFrame(table='self_telemetry.autotune')\n"
        "df = df.groupby('source').agg(cnt=('gate', px.count))\n"
        "px.display(df, 'out')\n")
    qr = next(iter(res.values()))
    srcs = [v for v in qr.dictionaries["source"].decode(
        qr.columns["source"])]
    assert srcs == ["fallback"]


def test_guard_holdoff_expires_and_arm_returns_without_cold_warmup():
    flags.set_for_testing("PX_AUTOTUNE_GUARD_HOLDOFF", 3)
    m = AutotuneModel()
    window = _warm_for_guard(m)
    for _ in range(window // 2):
        m.observe(GATE_CPU_CROSSOVER, "agg", "4^8", "cpu", 500.0 / 1e3)

    def srcs(k, cpu_ms):
        out = []
        for _ in range(k):
            dec = _decide(m)
            out.append((dec["arm"], dec["source"]))
            if dec["source"] != "fallback":
                m.observe_decision(
                    dec, {"cpu": cpu_ms, "device": 50.0}[dec["arm"]] / 1e3)
        return out

    # trip + 3 held-off decisions
    assert srcs(4, 2.0) == [("device", "fallback")] * 4
    # past the hold-off the arm is compared as it stood before it drifted:
    # the very next decision favors it again, and none is a cold warm-up
    back = srcs(8, 2.0)
    assert back[0] == ("cpu", "model")
    assert {s for _a, s in back} <= {"model", "explore"}
    assert m.snapshot()[GATE_CPU_CROSSOVER]["fallbacks"] == 1
    # a drift that has outlasted the hold-off trips again within a window
    again = srcs(window, 500.0)
    assert ("device", "fallback") in again
    assert _decide(m)["guard_trips"] == 2


def test_pacing_of_a_fresh_key_is_unchanged():
    """The sources of a fresh key's first 64 decisions under constant
    samples (static arm cpu 42 ms, device 23 ms: a table under the
    crossover): a cold probe every COLD_PROBE_PERIODth until the device arm
    has PX_AUTOTUNE_MIN_SAMPLES, then the model with an explore every
    16th.  The benchmark's warm-ups (32 queries) are sized to this."""
    m = AutotuneModel()
    got = []
    for _ in range(64):
        dec = m.decide(GATE_CPU_CROSSOVER, "agg", "4^10", "cpu",
                       ("cpu", "device"))
        got.append((dec["arm"], dec["source"]))
        m.observe_decision(dec, {"cpu": 42.0, "device": 23.0}[dec["arm"]]
                           / 1e3)
    want = [("device", "explore") if i % 4 == 3 else ("cpu", "cold")
            for i in range(32)]
    want += [("cpu", "explore") if i % 16 == 15 else ("device", "model")
             for i in range(32, 64)]
    assert got == want


def test_sample_excludes_compile_seconds():
    """Through the executor: the sample folded into the routing model is
    the chain's wall less what jax compiled in it, and the compile time is
    recorded beside it."""
    from pixie_tpu.compiler import compile_pxl
    from pixie_tpu.engine.executor import PlanExecutor
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    flags.set_for_testing("PX_AUTOTUNE", True)
    flags.set_for_testing("PX_CPU_CROSSOVER_ROWS", 64)  # jitted chain
    store = TableStore()
    t = store.create("t", Relation.of(("time_", DT.TIME64NS),
                                      ("k", DT.INT64), ("v", DT.INT64)))
    n = 5000
    t.write({"time_": np.arange(n, dtype=np.int64),
             "k": np.arange(n, dtype=np.int64) % 7,
             "v": np.arange(n, dtype=np.int64)})
    # a constant no other test uses: this chain's kernel is traced here
    src = ("df = px.DataFrame(table='t')\n"
           "df.w = df.v + 29029\n"
           "df = df.groupby('k').agg(s=('w', px.sum), c=('v', px.count))\n"
           "px.display(df, 'out')\n")
    plan = compile_pxl(src, store.schemas()).plan

    def run():
        # no mesh: a chain a mesh serves takes no routing decision (PR 38)
        ex = PlanExecutor(plan, store, mesh=None)
        ex.run()
        dec = next(d for d in ex.stats["autotune"]
                   if d["gate"] == GATE_CPU_CROSSOVER)
        chain = next(r for r in ex.op_stats
                     if r["label"].endswith("partial_agg"))
        return ex, dec, chain["wall_ns"] / 1e6

    ex, dec, wall_ms = run()
    assert ex.stats["compile_s"] > 0 and dec["compile_ms"] > 0
    assert dec["observed_ms"] == pytest.approx(
        max(wall_ms - dec["compile_ms"], 0.0), abs=0.01)
    arm = autotune.MODEL._gates[GATE_CPU_CROSSOVER].arms[
        f"{dec['plan_class']}|{dec['size_bucket']}"][dec["arm"]]
    assert arm.ring[-1] == pytest.approx(dec["observed_ms"] / 1e3, abs=1e-5)
    # a warm run compiles nothing and its sample is its whole wall
    ex2, dec2, wall2_ms = run()
    assert dec2["compile_ms"] == 0.0
    assert dec2["observed_ms"] == pytest.approx(wall2_ms, abs=0.01)


def test_hedge_floor_only_lowers_the_static_floor():
    m = AutotuneModel()
    floors = []
    for _ in range(64):
        floor, dec = m.hedge_floor_s(0.5)
        floors.append(floor)
        assert floor <= 0.5  # NEVER raises the operator's floor
        m.observe_service(0.01)
    assert floors[-1] < 0.5  # warm model lowered it to ~1.5 * p99
    assert floors[-1] == pytest.approx(0.015, rel=0.5)


def test_batch_window_outputs_clamped_to_4x_band():
    m = AutotuneModel()
    for _ in range(64):
        window, max_n, dec = m.batch_window(0.004, 16)
        assert 0.001 <= window <= 0.016  # 4x band around 4 ms
        assert 2 <= max_n <= 64
        m.observe_batch_wave(10.0, 4)  # absurd wave: clamp must hold
        m.observe_arrival()
    assert window == 0.016  # clamped at the top of the band


def test_record_row_dedupes_against_stats_path():
    m = AutotuneModel()
    dec = m.decide(GATE_CPU_CROSSOVER, "agg", "4^8", "device",
                   ("device", "cpu"))
    dec["gate"] = GATE_CPU_CROSSOVER
    m.record_row(dec, query_id="q1")
    # the direct-recorded decision drains as an event row ...
    rows = m.drain_rows()
    assert [r["query_id"] for r in rows] == ["q1"]
    # ... and the stats path skips it (no duplicate telemetry)
    assert autotune.rows_from_stats({"autotune": [dec]}, "q1") == []


# ------------------------------------------------------------ persistence


def test_kv_round_trip_warm_first_decision():
    """A KV-warmed model must decide from the fitted model IMMEDIATELY —
    no cold exploration burst after a restart."""
    m = AutotuneModel()
    _warm(m, GATE_CPU_CROSSOVER, {"device": 90.0, "cpu": 2.0})
    kv = KVStore(":memory:")
    m.save_kv(kv)

    m2 = AutotuneModel()  # "restarted process"
    assert m2.load_kv(kv)
    assert m2.loaded_from_kv
    srcs = [m2.decide(GATE_CPU_CROSSOVER, "agg", "4^8", "device",
                      ("device", "cpu"))["source"] for _ in range(8)]
    assert srcs[0] == "model" and "cold" not in srcs
    kv.close()


def test_corrupt_kv_record_degrades_to_static():
    kv = KVStore(":memory:")
    kv.set(KV_KEY, b"{not json")
    before = metrics.counter_value("px_autotune_recall_errors_total")
    m = AutotuneModel()
    assert m.load_kv(kv) is False
    assert not m.loaded_from_kv
    assert metrics.counter_value(
        "px_autotune_recall_errors_total") == before + 1
    # unknown version counts too
    kv.set_json(KV_KEY, {"v": 99, "gates": {}})
    assert m.load_kv(kv) is False
    # the model still serves static defaults
    dec = m.decide(GATE_CPU_CROSSOVER, "agg", "4^8", "device",
                   ("device", "cpu"))
    assert dec["arm"] == "device" and dec["source"] == "cold"
    kv.close()


def test_model_persists_across_broker_restart(tmp_path):
    """The broker saves the model on stop and recalls it on start from the
    same KV file — the PR 15 quota persistence pattern."""
    flags.set_for_testing("PX_AUTOTUNE", True)
    db = str(tmp_path / "control.db")
    broker = Broker(datastore_path=db).start()
    try:
        _warm(autotune.MODEL, GATE_CPU_CROSSOVER,
              {"device": 90.0, "cpu": 2.0})
    finally:
        broker.stop()  # persists the model
    autotune.MODEL.reset_for_testing()  # "new process"
    broker2 = Broker(datastore_path=db).start()
    try:
        assert autotune.MODEL.loaded_from_kv
        dec = autotune.MODEL.decide(
            GATE_CPU_CROSSOVER, "agg", "4^8", "device", ("device", "cpu"))
        assert dec["source"] == "model" and dec["arm"] == "cpu"
    finally:
        broker2.stop()


# ---------------------------------------------------------- off-identity


def test_autotune_off_is_bit_identical_and_silent():
    """PX_AUTOTUNE=0 removes every model read AND write; with the flag on,
    decisions appear in stats and the answers stay BIT-equal."""
    stores = {f"pem{i}": mkstore(i, 8_000) for i in range(2)}
    # one device an agent: a mesh would serve the chains and no router
    # would decide them (PR 38)
    cluster = LocalCluster(stores, n_devices_per_agent=1)
    # standing matviews would serve every repeat from cached fragments
    # and the routing gate would never run — the gate is what's under test
    flags.set_for_testing("PL_MATVIEW_ENABLED", False)

    flags.set_for_testing("PX_AUTOTUNE", False)
    base = canonical_bytes(cluster.query(SCRIPTS[0]))
    assert canonical_bytes(cluster.query(SCRIPTS[0])) == base
    assert autotune.MODEL.snapshot() == {}  # no writes anywhere

    flags.set_for_testing("PX_AUTOTUNE", True)
    flags.set_for_testing("PX_CPU_CROSSOVER_ROWS", 64)  # mis-tuned
    seen = []
    for _ in range(12):
        res = cluster.query(SCRIPTS[0])
        assert canonical_bytes(res) == base
        qr = next(iter(res.values()))
        seen += autotune.decisions_from_stats(qr.exec_stats)
    assert any(d["gate"] == GATE_CPU_CROSSOVER for d in seen)
    assert autotune.MODEL.snapshot()[GATE_CPU_CROSSOVER]["samples"] > 0


# -------------------------------------------------------- probe staleness


def test_probe_staleness_horizon_remeasures(monkeypatch):
    transfer.reset_probe_cache_for_testing()
    clock = [1000.0]
    monkeypatch.setattr(transfer, "_now", lambda: clock[0])
    flags.set_for_testing("PX_PROBE_MAX_AGE_S", 900.0)
    calls = []

    def measure():
        calls.append(1)
        return 42.0

    key = ("test_probe", 1)
    assert transfer._probe_cached(key, measure, False) == 42.0
    assert transfer._probe_cached(key, measure, False) == 42.0
    assert len(calls) == 1  # memoized
    epoch0 = transfer.probe_epoch()
    clock[0] += 901.0  # past the horizon
    assert transfer._probe_cached(key, measure, False) == 42.0
    assert len(calls) == 2  # re-measured
    assert transfer.probe_epoch() == epoch0 + 1  # derived gates re-open
    # the age gauge exports seconds-since-measurement per probe
    assert metrics.has_gauge_fn("px_probe_age_seconds")
    clock[0] += 5.0
    assert "px_probe_age_seconds" in metrics.render()
    transfer.reset_probe_cache_for_testing()


def test_invalidate_probes_drops_cache_and_bumps_epoch(monkeypatch):
    transfer.reset_probe_cache_for_testing()
    monkeypatch.setattr(transfer, "_now", lambda: 0.0)
    calls = []
    key = ("test_probe", 2)
    transfer._probe_cached(key, lambda: calls.append(1) or 7.0, False)
    epoch0 = transfer.probe_epoch()
    transfer.invalidate_probes()
    assert transfer.probe_epoch() > epoch0
    transfer._probe_cached(key, lambda: calls.append(1) or 7.0, False)
    assert len(calls) == 2  # the drop forced a fresh measurement
    transfer.reset_probe_cache_for_testing()
