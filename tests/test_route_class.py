"""The router's model key follows the chain it routes.

A `cpu_crossover` key is (gate, route class, size bucket); the class names
the chain (`executor._route_class_of`: table name, chain ops, blocking op)
and nothing that changes while the work does not.  Two scripts over the same
rows price their own arms; one script keeps one key across starts, growing
dictionaries and metadata epochs; a query of two chains takes, records and
observes two decisions; each key paces its own probes; a chain whose
completions nothing folds back asks the model nothing; class keys survive
the KV."""
from __future__ import annotations

import os

import numpy as np
import pytest

from pixie_tpu import flags
from pixie_tpu.compiler import compile_pxl
from pixie_tpu.engine import autotune
from pixie_tpu.engine import executor as ex
from pixie_tpu.engine.autotune import GATE_CPU_CROSSOVER, AutotuneModel
from pixie_tpu.services.kvstore import KVStore
from pixie_tpu.types import UInt128
from tests.test_lut_lookup import _by_status_store, metadata  # noqa: F401
from tests.test_trace_layers import (  # noqa: F401
    JOIN_QUERY, _agent_spans, _chains, fresh_ring, served, serving,
)

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "scripts")

ROUTE_ATTRS = ("source", "plan_class", "size_bucket", "decision_n",
               "guard_trips")


@pytest.fixture
def routed():
    """The model on and fresh, the crossover so low that the static arm of
    every table here is the device."""
    saved = {n: flags.get(n) for n in ("PX_AUTOTUNE", "PX_CPU_CROSSOVER_ROWS")}
    flags.set_for_testing("PX_AUTOTUNE", True)
    flags.set_for_testing("PX_CPU_CROSSOVER_ROWS", 64)
    autotune.MODEL.reset_for_testing()
    yield
    autotune.MODEL.reset_for_testing()
    for n, v in saved.items():
        flags.set_for_testing(n, v)


def _plan(ts, script: str, start: int = 0):
    with open(os.path.join(SCRIPTS, script + ".pxl")) as f:
        src = "import px\n" + f.read().replace("__START_TIME__", str(start))
    return compile_pxl(src, ts.schemas()).plan


def _route(ts, script: str, start: int = 0) -> dict:
    """Run the benchmark's `script` from `start`; its routing decision."""
    e = ex.PlanExecutor(_plan(ts, script, start), ts, mesh=None)
    e.run()
    (dec,) = [d for d in e.stats["autotune"]
              if d["gate"] == GATE_CPU_CROSSOVER]
    return dec


def _arms(dec: dict) -> dict:
    return autotune.MODEL._gates[GATE_CPU_CROSSOVER].arms[
        f"{dec['plan_class']}|{dec['size_bucket']}"]


def test_two_scripts_over_one_table_price_their_own_arms(routed, metadata):
    """The scan cell's two scripts over one table and one size bucket hold
    two keys: the windowed chain's 1.3 s on the device, which under one
    shared key sent by-status to the CPU arm with it, moves windowed
    alone."""
    ts, m, _who, _status = _by_status_store(110)
    metadata(m)
    status, windowed = _route(ts, "http_by_status"), _route(ts, "http_windowed")
    assert status["size_bucket"] == windowed["size_bucket"]
    assert status["plan_class"] != windowed["plan_class"]
    for dec in (status, windowed):
        cls = dec["plan_class"]
        assert cls.startswith("agg:http_events:") and "|" not in cls
        assert len(cls.rsplit(":", 1)[1]) == 10
        assert dec["n"] == 1 and _arms(dec)[dec["arm"]].n == 1
    assert autotune.MODEL.snapshot()[GATE_CPU_CROSSOVER]["keys"] == 2
    for dec, costs in ((windowed, {"device": 1.3, "cpu": 0.61}),
                       (status, {"device": 0.04, "cpu": 0.25})):
        for arm, secs in costs.items():
            for _ in range(12):
                autotune.MODEL.observe(
                    GATE_CPU_CROSSOVER, dec["plan_class"],
                    dec["size_bucket"], arm, secs)
    for _ in range(3):
        dec = _route(ts, "http_by_status")
        assert (dec["arm"], dec["source"]) == ("device", "static")
        dec = _route(ts, "http_windowed")
        assert (dec["arm"], dec["source"]) == ("cpu", "model")
    assert autotune.MODEL.snapshot()[GATE_CPU_CROSSOVER]["keys"] == 2


def test_one_script_keeps_one_key(routed, metadata):
    """Four starts of one script, then a grown dictionary and a later
    metadata epoch: one key, whose count is all of its decisions."""
    ts, m, _who, _status = _by_status_store(110)
    metadata(m)
    t0 = 10**9
    decs = [_route(ts, "http_by_status", t0 + 100 * k) for k in range(4)]
    assert len({d["size_bucket"] for d in decs}) == 1
    newcomer = UInt128.make_upid(1, 9000, 77)
    ts.table("http_events").write({
        "time_": np.arange(64, dtype=np.int64) + t0 + 10**6,
        "upid": [newcomer] * 64, "resp_status": np.full(64, 200),
        "latency": np.arange(64, dtype=np.int64) + 1})
    epoch = m.epoch
    m.apply_updates([
        {"kind": "pod", "uid": "p-new", "name": "pod-new", "namespace": "d",
         "node": "n", "ip": "10.0.0.2", "phase": "Running",
         "create_time_ns": 1},
        {"kind": "process", "upid": newcomer, "pod_uid": "p-new",
         "container_id": ""}])
    assert m.epoch > epoch
    decs.append(_route(ts, "http_by_status", t0))
    assert len({d["plan_class"] for d in decs}) == 1
    assert [d["n"] for d in decs] == [1, 2, 3, 4, 5]
    assert autotune.MODEL.snapshot()[GATE_CPU_CROSSOVER]["keys"] == 1


def test_each_key_paces_its_own_probes():
    """Three decisions of one key to one of another, the scan cell's
    pattern: either key's first 16 decisions explore at its own 3, 7, 11
    and 15, as a lone key's do."""
    m = AutotuneModel()
    seen: dict = {"a": [], "b": []}
    for i in range(64):
        cls = "b" if i % 4 == 3 else "a"
        dec = m.decide(GATE_CPU_CROSSOVER, f"agg:t:{cls}", "4^11", "device",
                       ("cpu", "device"))
        m.observe_decision(dec, 0.05)
        assert dec["n"] == len(seen[cls]) + 1
        seen[cls].append(dec["source"])
    for cls in ("a", "b"):
        assert [i for i, s in enumerate(seen[cls][:16]) if s == "explore"] \
            == [3, 7, 11, 15]
        assert set(seen[cls][:16]) == {"cold", "explore"}


def test_class_keys_round_trip_through_the_kv():
    """save_kv/load_kv keep a class key whole, and an `agg|4^k` record of
    an older process beside it: it loads and is never asked again."""
    cls = "agg:http_events:0123456789"
    m = AutotuneModel()
    for plan_class, costs in ((cls, {"device": 0.04, "cpu": 0.25}),
                              ("agg", {"device": 1.3, "cpu": 0.3})):
        for arm, secs in costs.items():
            for _ in range(8):
                m.observe(GATE_CPU_CROSSOVER, plan_class, "4^11", arm, secs)
    kv = KVStore(":memory:")
    m.save_kv(kv)
    m2 = AutotuneModel()
    assert m2.load_kv(kv)
    kv.close()
    g = m2._gates[GATE_CPU_CROSSOVER]
    assert set(g.arms) == {f"{cls}|4^11", "agg|4^11"}
    assert {a: (s.n, s.ewma) for a, s in g.arms[f"{cls}|4^11"].items()} == {
        "device": (8, 0.04), "cpu": (8, 0.25)}
    dec = m2.decide(GATE_CPU_CROSSOVER, cls, "4^11", "device",
                    ("cpu", "device"))
    assert (dec["arm"], dec["source"], dec["plan_class"]) == (
        "device", "static", cls)
    assert dec["model_ms"] == 40.0 and dec["n"] == 1
    assert "agg|4^11" not in g.count and g.arms["agg|4^11"]["cpu"].n == 8
    assert m2.snapshot()[GATE_CPU_CROSSOVER]["keys"] == 2


def test_two_chains_of_one_query_take_two_decisions(served):
    """A query whose two aggregates scan one table, in one size bucket:
    two decisions in stats["autotune"], each observed into its own key's
    arm and shown on its own chain span."""
    client, _store = served
    stats = client.execute_script(JOIN_QUERY)["out"].exec_stats
    decs = [d for d in stats["agents"]["pem0"]["autotune"]
            if d["gate"] == GATE_CPU_CROSSOVER]
    assert len(decs) == 2
    assert len({d["plan_class"] for d in decs}) == 2
    assert {d["size_bucket"] for d in decs} == {autotune.size_bucket(3000)}
    chains = {c.attributes["plan_class"]: c.attributes
              for c in _chains(_agent_spans())}
    assert set(chains) == {d["plan_class"] for d in decs}
    for d in decs:
        assert d["n"] == 1 and d["observed_ms"] > 0
        arm = _arms(d)[d["arm"]]
        assert arm.n == 1
        assert arm.ring[-1] == pytest.approx(d["observed_ms"] / 1e3, abs=1e-5)
        a = chains[d["plan_class"]]
        assert (a["arm"], a["source"], a["decision_n"], a["size_bucket"]) == (
            d["arm"], d["source"], 1, d["size_bucket"])
    assert autotune.MODEL.snapshot()[GATE_CPU_CROSSOVER] == {
        "keys": 2, "decisions": 2, "fallbacks": 0, "samples": 2}


SELECT_QUERY = """
import px
df = px.DataFrame(table='http_events')
df = df[df.latency > 5]
px.display(df[['service', 'latency']], 'out')
"""


@pytest.mark.parametrize("crossover,arm,engine", [
    (1 << 22, "cpu", "xla_cpu_chain"), (64, "device", "device_chain")])
def test_an_unobserved_chain_asks_the_model_nothing(
        served, crossover, arm, engine):
    """A scan->select chain feeds no completion back, so it has no key: no
    `cpu_crossover` decision in its stats, the static crossover's arm
    every time (a cold key would explore on the fourth), and a span with
    `arm` and `rows` alone."""
    client, _store = served
    before = flags.get("PX_CPU_CROSSOVER_ROWS")
    flags.set_for_testing("PX_CPU_CROSSOVER_ROWS", crossover)
    try:
        for _ in range(autotune.COLD_PROBE_PERIOD):
            stats = client.execute_script(SELECT_QUERY)["out"].exec_stats
            assert not [d for d in stats["agents"]["pem0"].get("autotune", [])
                        if d["gate"] == GATE_CPU_CROSSOVER]
    finally:
        flags.set_for_testing("PX_CPU_CROSSOVER_ROWS", before)
    chains = _chains(_agent_spans())
    assert len(chains) == autotune.COLD_PROBE_PERIOD
    for c in chains:
        a = c.attributes
        assert c.name.endswith("->select")
        assert (a["arm"], a["rows"], a["engine"]) == (arm, 3000, engine)
        assert not [k for k in ROUTE_ATTRS if k in a]
    assert GATE_CPU_CROSSOVER not in autotune.MODEL.snapshot()
