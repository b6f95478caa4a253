"""Closed-loop elasticity: measured service-rate model, live tenant
quotas, broker-driven agent autoscaling (serving/ratemodel.py,
serving/elastic.py, the broker control plane).

Unit tests drive the model and the supervisor deterministically (tick()
with explicit clocks); integration tests run the real broker + agent +
client path so quota writes, retire audits and topology-churn
bit-equality are proven ON THE WIRE.
"""
from __future__ import annotations

import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from pixie_tpu import flags, metrics
import pixie_tpu.engine.plancache  # noqa: F401 — defines PL_QUERY_FASTPATH
from pixie_tpu.serving import COST_COLD, COST_WARM, ServingFront, ShedError
from pixie_tpu.serving import ratemodel
from pixie_tpu.serving.admission import normalize_quota
from pixie_tpu.serving.elastic import AgentSupervisor, ProcLauncher, ThreadLauncher
from pixie_tpu.serving.ratemodel import ServiceRateModel
from pixie_tpu.services.agent import Agent
from pixie_tpu.services.broker import Broker
from pixie_tpu.services.client import Client, QueryError
from pixie_tpu.status import InvalidArgument
from pixie_tpu.testing.fixtures import (
    HARD_BATCH_ROWS, SCRIPTS, canonical_bytes, mkdata, mkstore,
)

ELASTIC_FLAGS = (
    "PL_SERVING_ENABLED", "PL_SERVING_MAX_INFLIGHT",
    "PL_SERVING_QUEUE_DEPTH", "PL_SERVING_QUEUE_TIMEOUT_S",
    "PL_SERVING_SHED_WATERMARK", "PL_TENANT_QPS", "PL_TENANT_CONCURRENCY",
    "PL_TENANT_WEIGHTS", "PL_RATE_MODEL", "PL_AUTOSCALE",
    "PL_AUTOSCALE_MIN", "PL_AUTOSCALE_MAX", "PL_AUTOSCALE_UP_WATERMARK",
    "PL_AUTOSCALE_DOWN_WATERMARK", "PL_AUTOSCALE_UP_COOLDOWN_S",
    "PL_AUTOSCALE_DOWN_COOLDOWN_S", "PL_AUTOSCALE_PERIOD_S",
    "PL_AUTOSCALE_EWMA", "PL_QUERY_RETRIES", "PL_CLIENT_RETRIES",
    "PL_REPLICATION", "PL_REJOIN_GRACE_S", "PL_QUERY_FASTPATH",
)


@pytest.fixture(autouse=True)
def _restore_flags():
    saved = {n: flags.get(n) for n in ELASTIC_FLAGS}
    yield
    for n, v in saved.items():
        flags.set_for_testing(n, v)


def _set(**kw):
    for n, v in kw.items():
        flags.set_for_testing(n.upper(), v)


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.01)
    return False


# ------------------------------------------------------------ rate model


def _feed(model, tenant, cls, service_s, n):
    for _ in range(n):
        model.observe_arrival(tenant, cls)
        model.observe(tenant, cls, service_s)


def test_plan_class():
    assert ratemodel.plan_class(True) == "warm"
    assert ratemodel.plan_class(False) == "cold"
    assert ratemodel.plan_class(False, mutation=True) == "mutation"
    assert ratemodel.plan_class(True, mutation=True) == "mutation"


def test_cost_converges_to_measured_ratio():
    m = ServiceRateModel()
    # cold-start: the static PR 8 constants hold until MIN_SAMPLES land
    assert m.cost_of(True) == COST_WARM
    assert m.cost_of(False) == COST_COLD
    _feed(m, "t", "warm", 0.010, 12)
    assert m.cost_of(False) == COST_COLD  # cold class still unsampled
    _feed(m, "t", "cold", 0.080, 12)
    assert m.cost_of(True) == COST_WARM  # warm is the unit by definition
    assert m.cost_of(False) == pytest.approx(8.0, rel=0.15)
    # clamp: a pathological compile cannot mint an unpayable cost
    m2 = ServiceRateModel()
    _feed(m2, "t", "warm", 0.001, 12)
    _feed(m2, "t", "cold", 10.0, 12)
    assert m2.cost_of(False) == ratemodel.COST_MAX


def test_retry_after_tracks_injected_service_rate():
    """Satellite: the shed retry-after must TRACK measured service-rate
    changes — a slowdown stretches the hint, a speedup shrinks it."""
    m = ServiceRateModel()
    assert m.retry_after_s(10, 4) is None  # cold model: callers fall back
    _feed(m, "t", "warm", 0.050, 16)
    fast = m.retry_after_s(10, 4)
    # drain rate = cap/mean = 4/0.05 = 80 qps; 11 queued ≈ 0.1375s
    assert fast == pytest.approx(11 / 80.0, rel=0.2)
    # inject a 10x service-time slowdown: the EWMA follows, the hint grows
    _feed(m, "t", "warm", 0.500, 30)
    slow = m.retry_after_s(10, 4)
    assert slow > 4 * fast
    # and recovers when the service rate comes back
    _feed(m, "t", "warm", 0.050, 60)
    again = m.retry_after_s(10, 4)
    assert again < slow / 2


def test_front_shed_hint_uses_measured_rate():
    """The ServingFront's queue-full/timeout/overload hints come from the
    model once it is warm (the PR 8 heuristic only while cold)."""
    _set(pl_serving_enabled=True, pl_serving_max_inflight=1,
         pl_serving_queue_depth=1)
    front = ServingFront("test")
    front.reset_for_testing()
    m = ServiceRateModel()
    _feed(m, "t", "warm", 2.0, 16)  # slow service: 1 slot / 2s = 0.5 qps
    front.rate_model = m
    t_run = front.admit("a", 1.0)  # occupies the single slot
    holder = {}

    def bg():
        try:
            holder["t"] = front.admit("a", 1.0, timeout_s=30.0)
        except ShedError as e:
            holder["shed"] = e

    th = threading.Thread(target=bg, daemon=True)
    th.start()
    assert _wait(lambda: front.total_queued == 1)
    with pytest.raises(ShedError) as ei:
        front.admit("a", 1.0)  # queue full → shed with the measured hint
    # 1 queued + 1 = 2 queries over 0.5 qps ≈ 4s — far from the
    # heuristic's 0.5 + 1/1 = 1.5s
    assert ei.value.retry_after_s == pytest.approx(4.0, rel=0.3)
    front.release(t_run)
    assert _wait(lambda: "t" in holder)
    front.release(holder["t"])


def test_rate_model_flag_off_restores_constants():
    _set(pl_rate_model=False)
    m = ServiceRateModel()
    _feed(m, "t", "warm", 0.010, 16)
    _feed(m, "t", "cold", 0.100, 16)
    assert m.cost_of(False) == COST_COLD
    assert m.retry_after_s(10, 4) is None
    assert m.offered_load(4) is None


def test_arrival_window_and_capped_tenants():
    m = ServiceRateModel()
    now = time.time()
    for i in range(20):
        m.observe_arrival("t", "warm", now=now - i)
    # 20 arrivals over the last 20s ≈ 1 qps at a 30s window
    assert m.arrival_qps(window_s=30) == pytest.approx(20 / 30, rel=0.2)
    # bins past the retention window prune
    m.observe_arrival("t", "warm", now=now + ratemodel.ARRIVAL_WINDOW_S + 5)
    with m._lock:
        st = m._key_locked(m._label("t"), "warm")
        assert all(s >= now for s, _ in st.bins)
    # wire-supplied tenant ids ride a capped label family
    for i in range(ratemodel.ARRIVAL_WINDOW_S):
        pass
    big = ServiceRateModel()
    for i in range(metrics.MAX_LABEL_IDS + 50):
        big.observe("flood-%d" % i, "warm", 0.01)
    with big._lock:
        assert len(big._keys) <= metrics.MAX_LABEL_IDS + 1


# ------------------------------------------------------------ live quotas


def test_normalize_quota_validation():
    assert normalize_quota("t", qps=10, concurrency=0, weight=2) == {
        "qps": 10.0, "concurrency": 0, "weight": 2.0}
    assert normalize_quota("t") == {
        "qps": None, "concurrency": None, "weight": None}
    for bad in (dict(tenant=""), dict(tenant="  "), dict(tenant=None)):
        with pytest.raises(InvalidArgument):
            normalize_quota(bad["tenant"], qps=1)
    with pytest.raises(InvalidArgument):
        normalize_quota("t", qps="abc")
    with pytest.raises(InvalidArgument):
        normalize_quota("t", qps=-1)
    with pytest.raises(InvalidArgument):
        normalize_quota("t", weight=0)
    with pytest.raises(InvalidArgument):
        normalize_quota("t", concurrency="x")
    # weights clamp to the DRR-safe band
    assert normalize_quota("t", weight=1e9)["weight"] == 100.0
    assert normalize_quota("t", weight=1e-9)["weight"] == 0.01


def test_quota_weight_changes_drr_share_within_one_round():
    """`quota set` mid-load: the new weight applies to the very next DRR
    dispatch rounds — queued work drains at the new share immediately."""
    _set(pl_serving_enabled=True, pl_serving_max_inflight=1,
         pl_serving_queue_depth=64)
    front = ServingFront("test")
    front.reset_for_testing()
    occupant = front.admit("warmup", 1.0)
    holders = []
    for i in range(16):
        for tenant in ("a", "b"):
            h = {"tenant": tenant}

            def bg(h=h, tenant=tenant):
                try:
                    h["ticket"] = front.admit(tenant, 1.0, timeout_s=30.0)
                except ShedError as e:  # pragma: no cover — not expected
                    h["shed"] = e

            th = threading.Thread(target=bg, daemon=True)
            th.start()
            h["thread"] = th
            holders.append(h)
    assert _wait(lambda: front.total_queued == 32)
    # LIVE quota write while the queues are loaded
    front.set_quota("a", normalize_quota("a", weight=4))
    order = []
    current = occupant
    for _ in range(10):
        front.release(current)
        got = _wait(lambda: any("ticket" in h and not h.get("seen")
                                for h in holders))
        assert got
        h = next(h for h in holders if "ticket" in h and not h.get("seen"))
        h["seen"] = True
        order.append(h["tenant"])
        current = h["ticket"]
    front.release(current)
    # weight 4 vs 1: tenant a drains ~4x as fast from the first rounds
    assert order.count("a") >= 3 * order.count("b"), order
    front.reset_for_testing()


def test_quota_qps_applies_live():
    _set(pl_serving_enabled=True, pl_serving_max_inflight=8,
         pl_serving_queue_depth=8)
    front = ServingFront("test")
    front.reset_for_testing()
    t = front.admit("t", 1.0)  # unlimited before the write
    front.release(t)
    front.set_quota("t", normalize_quota("t", qps=1))
    got = front.admit("t", 1.0)  # burst capacity: one token
    front.release(got)
    with pytest.raises(ShedError) as ei:
        front.admit("t", 1.0)
    assert ei.value.reason == "qps"
    # clearing the record restores the env default (unlimited here)
    front.set_quota("t", None)
    got = front.admit("t", 1.0)
    front.release(got)


def test_quota_set_over_wire_persists_across_restart(tmp_path):
    """quota set mid-load changes the share, survives broker restart via
    the KV, and malformed specs are rejected with a clean error."""
    db = str(tmp_path / "control.db")
    broker = Broker(datastore_path=db, hb_expiry_s=5.0).start()
    st = mkstore(1, 20_000)
    agent = Agent("pem0", "127.0.0.1", broker.port, store=st,
                  heartbeat_s=0.5).start()
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        eff = client.set_quota("vip", qps=25, weight=8)
        assert eff == {"qps": 25.0, "concurrency": 0, "weight": 8.0,
                       "live": True}
        got = client.get_quotas()
        assert got["tenants"]["vip"]["weight"] == 8.0
        # malformed writes are rejected with a clean error, nothing applied
        with pytest.raises(QueryError):
            client.set_quota("", qps=10)
        with pytest.raises(QueryError):
            client.set_quota("vip", qps="abc")
        with pytest.raises(QueryError):
            client.set_quota("vip", weight=-2)
        assert client.get_quotas()["tenants"]["vip"]["qps"] == 25.0
        # the record reaches the scheduler state itself
        assert broker.serving.quotas()["vip"]["live"]
    finally:
        client.close()
        agent.stop()
        broker.stop()
    # restart on the same KV: the live record survives
    broker2 = Broker(datastore_path=db, hb_expiry_s=5.0).start()
    try:
        q = broker2.serving.quotas()["vip"]
        assert q == {"qps": 25.0, "concurrency": 0, "weight": 8.0,
                     "live": True}
    finally:
        broker2.stop()


# ------------------------------------------------------------- supervisor


class _Pressure:
    """Deterministic pressure source for supervisor tests."""

    def __init__(self, sup):
        self.value = 0.0
        sup.pressure = lambda: self.value
        # kill the EWMA lag: the tests assert on decisions, not smoothing
        flags.set_for_testing("PL_AUTOSCALE_EWMA", 1.0)


def _broker_with_seed(rows=20_000, **broker_kw):
    broker = Broker(hb_expiry_s=5.0, **broker_kw)
    broker.supervisor = AgentSupervisor(
        broker, ThreadLauncher("127.0.0.1", broker.port,
                               store_factory=lambda _n: mkstore(0, 0),
                               heartbeat_s=0.5))
    # NOT started: tests drive tick() deterministically
    broker._server.start()
    broker._expiry_thread.start()
    seed = Agent("pem0", "127.0.0.1", broker.port,
                 store=mkstore(1, rows), heartbeat_s=0.5).start()
    return broker, seed


def _teardown(broker, *agents):
    if broker.supervisor is not None:
        broker.supervisor.stop()
    for a in agents:
        try:
            a.stop()
        except Exception:
            pass
    broker._stopped.set()
    broker._server.stop()
    broker.kv.close()


def test_supervisor_watermarks_hysteresis_bounds():
    _set(pl_serving_enabled=True, pl_autoscale_min=1, pl_autoscale_max=3,
         pl_autoscale_up_watermark=0.8, pl_autoscale_down_watermark=0.25,
         pl_autoscale_up_cooldown_s=1.0, pl_autoscale_down_cooldown_s=2.0)
    broker, seed = _broker_with_seed()
    sup = broker.supervisor
    p = _Pressure(sup)
    try:
        now = 100.0
        # dead band: mid-pressure moves nothing
        p.value = 0.5
        sup.tick(now=now)
        assert sup.scale_ups == 0 and sup.scale_downs == 0
        # high pressure: one spawn per up-cooldown, never past MAX
        p.value = 2.0
        sup.tick(now=now + 2)
        assert sup.scale_ups == 1
        assert _wait(lambda: len(broker.registry.live_agents()) == 2)
        sup.tick(now + 2.5)  # inside the cooldown: no second spawn
        assert sup.scale_ups == 1
        sup.tick(now + 4)
        assert sup.scale_ups == 2
        assert _wait(lambda: len(broker.registry.live_agents()) == 3)
        sup.tick(now + 6)  # at PL_AUTOSCALE_MAX: bounded
        assert sup.scale_ups == 2
        # low pressure: retire (newest spawned first) per down-cooldown,
        # never below MIN; spawned agents are empty → clean deregisters
        p.value = 0.1
        sup.tick(now + 10)
        assert sup.scale_downs == 1
        assert _wait(lambda: len(broker.registry.live_agents()) == 2)
        sup.tick(now + 11)  # inside the down cooldown
        assert sup.scale_downs == 1
        sup.tick(now + 13)
        assert sup.scale_downs == 2
        assert _wait(lambda: len(broker.registry.live_agents()) == 1)
        sup.tick(now + 16)  # only the seed is left; MIN floors the fleet
        assert sup.scale_downs == 2
        # the seed agent is never a retire candidate even above MIN
        assert sup._retire_candidate({"pem0"}) is None
    finally:
        _teardown(broker, seed)


def test_supervisor_preemption_reaped_and_replaced():
    _set(pl_serving_enabled=True, pl_autoscale_min=1, pl_autoscale_max=3,
         pl_autoscale_up_cooldown_s=1.0, pl_rejoin_grace_s=0.1)
    broker, seed = _broker_with_seed()
    sup = broker.supervisor
    p = _Pressure(sup)
    c0 = metrics.counter_value("px_autoscale_preempted_total")
    try:
        p.value = 2.0
        base = time.monotonic()
        sup.tick(now=base)
        assert sup.scale_ups == 1
        (victim,) = sup.spawned_agents()
        handle = sup._spawned[victim]
        # preemption: the pod dies underneath the supervisor
        handle.conn.abort()
        handle.stop()
        assert _wait(lambda: not broker.registry.record(victim).alive)
        # past the grace the dead pod reaps (registry record cleaned up)…
        # (the reap clock compares against the registry's REAL died_at, so
        # the fake tick clock is a real-time offset, not an arbitrary one)
        sup.tick(now=time.monotonic() + 5.0)
        assert victim not in sup.spawned_agents()
        assert broker.registry.record(victim) is None
        assert metrics.counter_value("px_autoscale_preempted_total") > c0
        # …and sustained pressure replaced it through the normal scale-up
        # path (same tick or the next), under a FRESH name
        assert sup.scale_ups >= 2
        replacement = sup.spawned_agents()[-1]
        assert replacement != victim
        assert broker.registry.record(replacement).alive
    finally:
        _teardown(broker, seed)


def test_retire_refuses_last_live_holder_without_replication():
    """Satellite: a forced retire with PL_REPLICATION=1 (off) must never
    lose rows — the audit refuses the data-holding agent and its rows stay
    queryable."""
    broker = Broker(hb_expiry_s=5.0).start()
    agents = {n: Agent(n, "127.0.0.1", broker.port,
                       store=mkstore(i + 1, 30_000),
                       heartbeat_s=0.5).start()
              for i, n in enumerate(["pem0", "pem1"])}
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        base = canonical_bytes(client.execute_script(SCRIPTS[0]))
        res = broker.retire_agent("pem0")
        assert not res["ok"]
        assert res["rows"] == 30_000
        assert "replica" in res["reason"]
        # nothing was deregistered, nothing lost
        assert broker.registry.record("pem0") is not None
        assert canonical_bytes(client.execute_script(SCRIPTS[0])) == base
        # unknown agents refuse cleanly too
        assert not broker.retire_agent("nope")["ok"]
    finally:
        client.close()
        for a in agents.values():
            a.stop()
        broker.stop()


def test_retire_hands_off_to_synced_replica_without_row_loss():
    """With PL_REPLICATION=2 a data-holding agent retires through the
    PR 12 hand-off: its record stays, its shard serves from the replicated
    sealed batches via failover, and answers stay bit-equal."""
    _set(pl_replication=2, pl_rejoin_grace_s=0.2, pl_query_retries=4,
         pl_client_retries=4)
    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    agents = {}
    for i in range(3):
        n = f"pem{i}"
        ts = mkstore(i + 1, 0, batch_rows=HARD_BATCH_ROWS)
        agents[n] = Agent(n, "127.0.0.1", broker.port, store=ts,
                          heartbeat_s=0.4).start()
    for i, n in enumerate(sorted(agents)):
        agents[n].store.table("http_events").write(
            mkdata(i + 1, HARD_BATCH_ROWS))
    for a in agents.values():
        assert a.replication is not None
        assert a.replication.wait_synced(30.0)
    client = Client("127.0.0.1", broker.port, timeout_s=60.0)
    try:
        base = [canonical_bytes(client.execute_script(s)) for s in SCRIPTS]
        res = broker.retire_agent("pem0")
        assert res["ok"] and res["mode"] == "handoff"
        assert res["rows"] == HARD_BATCH_ROWS
        # the record STAYS (failover needs it) and the agent stops
        agents["pem0"].stop()
        assert broker.registry.record("pem0") is not None
        assert _wait(lambda: not broker.registry.record("pem0").alive)
        time.sleep(0.3)  # past the rejoin grace: failover owns the shard
        got = [canonical_bytes(client.execute_script(s)) for s in SCRIPTS]
        assert got == base  # zero rows lost: replicas answer AS pem0
    finally:
        client.close()
        for a in agents.values():
            try:
                a.stop()
            except Exception:
                pass
        broker.stop()


def test_scale_events_recorded_as_telemetry():
    _set(pl_serving_enabled=True, pl_autoscale_min=1, pl_autoscale_max=2,
         pl_autoscale_up_cooldown_s=0.0, pl_autoscale_down_cooldown_s=0.0,
         pl_autoscale_up_watermark=0.8, pl_autoscale_down_watermark=0.25)
    broker, seed = _broker_with_seed()
    sup = broker.supervisor
    p = _Pressure(sup)
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        p.value = 2.0
        sup.tick(now=100.0)
        p.value = 0.0
        sup.tick(now=200.0)
        assert sup.scale_ups == 1 and sup.scale_downs == 1

        def rows():
            got = client.execute_script("""
df = px.DataFrame(table='self_telemetry.scale_events')
df = df[['action', 'agent', 'agents']]
px.display(df, 'out')
""")
            out = got["out"]
            col = out.columns.get("action")
            d = out.dictionaries.get("action")
            return set(d.decode(col)) if d is not None else set()

        assert _wait(lambda: {"spawn", "retire_deregister"} <= rows(), 10.0)
    finally:
        client.close()
        _teardown(broker, seed)


def test_supervisor_never_reaps_unregistered_spawn_in_grace():
    """A subprocess agent pays interpreter+jax import before it can
    register: a missing registry record within the startup grace is a
    STARTING agent, not a dead one — reaping it would kill every
    ProcLauncher scale-up at birth.  A spawn whose process exited reaps
    immediately."""

    class _SlowLauncher:
        def __init__(self):
            self.live = {}

        def spawn(self, name):
            h = type("H", (), {"dead": False})()
            self.live[name] = h
            return h

        def stop(self, name, handle):
            handle.dead = True

        @staticmethod
        def alive(handle):
            return not handle.dead

    _set(pl_serving_enabled=True, pl_autoscale_min=1, pl_autoscale_max=3,
         pl_autoscale_up_cooldown_s=1.0)
    broker, seed = _broker_with_seed()
    launcher = _SlowLauncher()
    broker.supervisor.stop()
    broker.supervisor = sup = AgentSupervisor(broker, launcher)
    p = _Pressure(sup)
    try:
        p.value = 2.0
        base = time.monotonic()
        sup.tick(now=base)
        (name,) = sup.spawned_agents()
        assert broker.registry.record(name) is None  # never registered
        # inside the startup grace: repeated ticks must NOT reap it
        sup.tick(now=base + 2)
        sup.tick(now=base + AgentSupervisor.SPAWN_GRACE_S - 1)
        assert name in sup.spawned_agents()
        # once its PROCESS dies, it reaps immediately (no grace needed)
        launcher.live[name].dead = True
        sup.tick(now=base + 4)
        assert name not in sup.spawned_agents()
    finally:
        _teardown(broker, seed)


# --------------------------------------------------- launcher orphan-proof

_HARNESS = r"""
import sys, time
from pixie_tpu.serving.elastic import ProcLauncher
launcher = ProcLauncher("127.0.0.1", 1, argv_for=lambda name: [
    sys.executable, "-c", "import time; time.sleep(120)"])
p = launcher.spawn("sleeper")
print(p.pid, flush=True)
time.sleep(120)
"""


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:  # pragma: no cover
        return True


def test_proc_launcher_no_orphans_when_harness_killed(tmp_path):
    """Satellite: SIGKILL the harness mid-run — its launcher children must
    die with it (PR_SET_PDEATHSIG), not squat on ports forever."""
    script = tmp_path / "harness.py"
    script.write_text(_HARNESS)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    harness = subprocess.Popen([sys.executable, str(script)],
                               stdout=subprocess.PIPE, env=env)
    try:
        line = harness.stdout.readline().strip()
        child_pid = int(line)
        assert _pid_alive(child_pid)
        # the hard death atexit can never see
        os.kill(harness.pid, signal.SIGKILL)
        harness.wait(timeout=10.0)
        assert _wait(lambda: not _pid_alive(child_pid), timeout=10.0), \
            "launcher child survived its harness being SIGKILLed"
    finally:
        if harness.poll() is None:
            harness.kill()
        try:
            os.kill(child_pid, signal.SIGKILL)
        except Exception:
            pass


def test_proc_launcher_stop_and_atexit_registry():
    from pixie_tpu.serving import elastic

    launcher = ProcLauncher("127.0.0.1", 1, argv_for=lambda name: [
        sys.executable, "-c", "import time; time.sleep(60)"])
    p = launcher.spawn("x")
    assert p.pid in elastic._CHILDREN
    assert ProcLauncher.alive(p)
    launcher.stop("x", p)
    assert p.pid not in elastic._CHILDREN
    assert not ProcLauncher.alive(p)


# ---------------------------------------------------- flag-off equivalence


def test_autoscale_off_no_quota_writes_bit_identical():
    """PL_AUTOSCALE=0 with no live quota writes is the PR 14 serving path:
    no supervisor exists, and results are bit-identical whether the rate
    model reads are enabled or not (it only reprices scheduling)."""
    broker = Broker(hb_expiry_s=5.0).start()
    assert broker.supervisor is None
    st = mkstore(1, 30_000)
    agent = Agent("pem0", "127.0.0.1", broker.port, store=st,
                  heartbeat_s=0.5).start()
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        base = [canonical_bytes(client.execute_script(s)) for s in SCRIPTS]
        _set(pl_rate_model=False)
        off = [canonical_bytes(client.execute_script(s)) for s in SCRIPTS]
        assert off == base
        assert broker.serving.quota_overrides() == {}
    finally:
        client.close()
        agent.stop()
        broker.stop()
