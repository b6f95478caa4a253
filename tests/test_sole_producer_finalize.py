"""A partial aggregate whose channel has one producer finalizes its quantile
sketches where its chain runs and ships their bins, not [G, 514]
histograms (parallel/distributed.py marks it `sole_producer`;
engine/executor.py `_partial_agg_batch`, and `_sorted_partial_batch` for
the sorted form; parallel/partial.py passes the finalized column through).
Served through a Broker and its Agents on loopback, on small seeded stores
of the service map's, the http widgets' and the Postgres flow graph's
shapes: the answers are the raw path's string for string, in the dense and
the sorted form, two producers of one table still ship raw state, a
standing view's state stays raw, and the merge refuses to add a finalized
column.  A local sorted aggregate finalizes on the device too, to the host
finalize's strings.
"""
import contextlib
import time

import numpy as np
import pandas as pd
import pytest

from tests import test_pgsql_flow_config as pgsql
from tests.test_service_map_config import datagen, route, small_config, traffic

from pixie_tpu import flags, trace
from pixie_tpu.engine.executor import PlanExecutor
from pixie_tpu.metadata import state as mdstate
from pixie_tpu.parallel import spmd
from pixie_tpu.parallel.partial import (
    PartialAggBatch,
    combine_partials,
    finalize_partial,
    slice_partial,
)
from pixie_tpu.plan import (
    AggExpr,
    AggOp,
    Call,
    Column,
    MapOp,
    MemorySinkOp,
    MemorySourceOp,
    Plan,
    lit,
)
from pixie_tpu.status import Internal
from pixie_tpu.table import TableStore
from pixie_tpu.types import DataType as DT, Relation
from pixie_tpu.udf import registry as udf_registry

SEED = 2147483659
#: the service map (px.quantiles), the by-status widget (p50) and the
#: windowed chart (p50 and p99)
SCRIPTS = ["service_edges", "http_by_status", "http_windowed"]


@contextlib.contextmanager
def served(stores: list, **wanted):
    """A Broker and one Agent per store (`pem0`, `pem1`, ...), tracing on
    and standing views off unless `wanted` says otherwise.  Yields the
    client and the agents."""
    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker
    from pixie_tpu.services.client import Client

    wanted = {"PL_TRACING_ENABLED": True, "PL_MATVIEW_ENABLED": False,
              **wanted}
    before = {k: flags.get(k) for k in wanted}
    for k, v in wanted.items():
        flags.set_for_testing(k, v)
    broker = Broker(hb_expiry_s=120.0, query_timeout_s=120.0).start()
    agents = [Agent(f"pem{i}", "127.0.0.1", broker.port, store=store,
                    heartbeat_s=2.0).start()
              for i, store in enumerate(stores)]
    client = Client("127.0.0.1", broker.port, timeout_s=120.0)
    try:
        yield client, agents
    finally:
        client.close()
        for agent in agents:
            agent.stop()
        broker.stop()
        for k, v in before.items():
            flags.set_for_testing(k, v)


@pytest.fixture
def config(monkeypatch):
    monkeypatch.setattr(spmd, "default_mesh", lambda: None)
    old = mdstate.global_manager()
    config = small_config()
    datagen.install_metadata(config)
    yield config
    mdstate.set_global_manager(old)


def text(config: dict, name: str) -> str:
    start = int(config["time_base_ns"]) + 2 * datagen.SEC
    return traffic.load_script(name)["text"].replace("__START_TIME__",
                                                      str(start))


def frame(out) -> pd.DataFrame:
    df = out.to_pandas()
    return df.sort_values(list(df.columns)).reset_index(drop=True)


@contextlib.contextmanager
def raw_partials(monkeypatch):
    """The path before the mark: every partial ships raw state."""
    run = PlanExecutor._partial_agg_batch

    def raw(self, op):
        was, op.sole_producer = op.sole_producer, False
        try:
            return run(self, op)
        finally:
            op.sole_producer = was

    with monkeypatch.context() as m:
        m.setattr(PlanExecutor, "_partial_agg_batch", raw)
        yield


def sketch_chains(since: int) -> list:
    return [s for s in trace.recent(since)
            if s.service.startswith("pem") and "sketch_form" in s.attributes]


@pytest.mark.parametrize("backend", ["cpu", "device", "tpu"])
@pytest.mark.parametrize("name", SCRIPTS)
def test_sole_producer_ships_bins_with_the_raw_paths_answers(
        name, backend, config, monkeypatch):
    """One agent: the chain reads back no sketch state and finalizes each
    sketch column where it runs, the broker formats bins, and the answer
    is the raw path's, quantile strings and values to the bit, on either
    arm and with the kernels traced for the TPU."""
    route(monkeypatch, backend)
    store = datagen.load_store(config, datagen.generate(config, SEED))
    with served([store]) as (client, _agents):
        since = time.time_ns()
        got = frame(client.execute_script(text(config, name))["out"])
        chains = sketch_chains(since)
        finals = [s for s in trace.recent(since)
                  if s.name == "sketch_finalize"]
        with raw_partials(monkeypatch):
            since = time.time_ns()
            want = frame(client.execute_script(text(config, name))["out"])
            raw_chains = sketch_chains(since)
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert chains and raw_chains
    for c in chains:
        assert c.attributes["sketch_state_bytes"] == 0, c.attributes
        assert c.attributes["sketch_device_finalized"] >= 1, c.attributes
    for c in raw_chains:
        assert c.attributes["sketch_state_bytes"] > 0, c.attributes
        assert c.attributes["sketch_device_finalized"] == 0, c.attributes
    assert finals and all(s.service == "broker"
                          and s.attributes["sketch_state_bytes"] == 0
                          for s in finals)


@pytest.mark.parametrize("name", ["service_edges", "http_windowed"])
def test_two_producers_of_one_table_ship_raw_state(name, config,
                                                   monkeypatch):
    """The table's rows split over two agents: the partial is not marked,
    each agent reads back its sketches' state, and the broker's merge of
    the two gives the one agent's answer (the sketches to the bit)."""
    route(monkeypatch, "device")
    tables = datagen.generate(config, SEED)
    halves = [{t: {c: v[i::2] for c, v in cols.items()}
               for t, cols in tables.items()} for i in range(2)]
    with served([datagen.load_store(config, tables)]) as (client, _a):
        one = frame(client.execute_script(text(config, name))["out"])
    half = dict(config, rows=config["rows"] // 2)
    with served([datagen.load_store(half, h) for h in halves]) as (
            client, _agents):
        since = time.time_ns()
        two = frame(client.execute_script(text(config, name))["out"])
        chains = sketch_chains(since)
    assert {c.service for c in chains} == {"pem0", "pem1"}
    for c in chains:
        assert c.attributes["sketch_state_bytes"] > 0, c.attributes
        assert c.attributes["sketch_device_finalized"] == 0, c.attributes
    floats = [c for c in one.columns if one[c].dtype == np.float64
              and not c.startswith(("latency", "p50", "p99"))]
    pd.testing.assert_frame_equal(one.drop(columns=floats),
                                  two.drop(columns=floats), check_exact=True)
    np.testing.assert_allclose(one[floats], two[floats], rtol=1e-12)


@pytest.fixture
def pgsql_config(monkeypatch):
    monkeypatch.setattr(spmd, "default_mesh", lambda: None)
    old = mdstate.global_manager()
    config = pgsql.small_config()
    datagen.install_metadata(config)
    yield config
    mdstate.set_global_manager(old)


def flow_graph(config: dict) -> str:
    """The Postgres flow graph, whose first aggregate (per 10 s window and
    edge) is the sorted one, over the table's newest half."""
    return traffic.load_script("pgsql_flow_graph")["text"].replace(
        "__START_TIME__", str(pgsql.start_ns(config, 301)))


def sorted_chains(since: int) -> list:
    return [s for s in trace.recent(since) if s.service.startswith("pem")
            and s.attributes.get("agg_form") == "sorted"]


@pytest.mark.parametrize("backend", ["cpu", "device", "tpu"])
def test_sorted_sole_producer_ships_bins_with_the_raw_paths_answers(
        backend, pgsql_config, monkeypatch):
    """One agent: the flow graph's sorted aggregate finalizes its sketch in
    the execution that updates it and reads back none of its state, and
    the answer is the raw path's to the bit, on either arm and with the
    kernels traced for the TPU."""
    route(monkeypatch, backend)
    tables = datagen.generate(pgsql_config, SEED)
    store = datagen.load_store(pgsql_config, tables)
    with served([store]) as (client, _agents):
        since = time.time_ns()
        got = frame(client.execute_script(flow_graph(pgsql_config))["out"])
        (chain,) = sorted_chains(since)
        with raw_partials(monkeypatch):
            since = time.time_ns()
            want = frame(client.execute_script(
                flow_graph(pgsql_config))["out"])
            (raw,) = sorted_chains(since)
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert len(got) > 100
    slots = 1 << (chain.attributes["groups_out"] - 1).bit_length()
    assert chain.attributes["groups_out"] > 4096
    assert (chain.attributes["sketch_state_bytes"],
            chain.attributes["sketch_device_finalized"]) == (0, 1)
    assert (raw.attributes["sketch_state_bytes"],
            raw.attributes["sketch_device_finalized"]) == (
        slots * pgsql.HIST_BYTES, 0)
    # the bins ([slots, 5] int32) in the place of the [slots, 514] f32 state
    assert (raw.attributes["d2h_bytes"] - chain.attributes["d2h_bytes"]
            == slots * (pgsql.HIST_BYTES - 5 * 4))


def test_two_producers_of_one_table_ship_raw_sorted_state(pgsql_config,
                                                          monkeypatch):
    """The table's rows split over two agents: neither sorted aggregate
    finalizes, each reads back its runs' raw state, and the broker's merge
    gives the one agent's answer (the quantiles to the bit)."""
    route(monkeypatch, "device")
    tables = datagen.generate(pgsql_config, SEED)
    halves = [{t: {c: v[i::2] for c, v in cols.items()}
               for t, cols in tables.items()} for i in range(2)]
    with served([datagen.load_store(pgsql_config, tables)]) as (client, _a):
        one = frame(client.execute_script(flow_graph(pgsql_config))["out"])
    half = dict(pgsql_config, rows=pgsql_config["rows"] // 2)
    with served([datagen.load_store(half, h) for h in halves]) as (
            client, _agents):
        since = time.time_ns()
        two = frame(client.execute_script(flow_graph(pgsql_config))["out"])
        chains = sorted_chains(since)
    assert {c.service for c in chains} == {"pem0", "pem1"}
    for c in chains:
        assert c.attributes["sketch_state_bytes"] > 0, c.attributes
        assert c.attributes["sketch_device_finalized"] == 0, c.attributes
    floats = [c for c in one.columns if one[c].dtype == np.float64]
    pd.testing.assert_frame_equal(one.drop(columns=floats),
                                  two.drop(columns=floats), check_exact=True)
    np.testing.assert_allclose(one[floats], two[floats], rtol=1e-12)


def _local_sorted_plan() -> Plan:
    """Quantiles over a computed numeric key, which the dense form does
    not serve (GroupKeyFallback): the sorted aggregate, on one store."""
    p = Plan()
    src = p.add(MemorySourceOp(table="events"))
    m = p.add(MapOp(exprs=[("k", Call("modulo", (Column("id"), lit(5000)))),
                          ("v", Column("v"))]), parents=[src])
    agg = p.add(AggOp(groups=["k"], values=[
        AggExpr("n", "count", None), AggExpr("q", "quantiles", "v"),
        AggExpr("p99", "p99", "v")]), parents=[m])
    p.add(MemorySinkOp(name="out"), parents=[agg])
    return p


@pytest.mark.parametrize("backend", ["cpu", "device", "tpu"])
def test_local_sorted_aggregate_finalizes_on_the_device(backend,
                                                        monkeypatch):
    """A local (non-partial) sorted aggregate finalizes its two sketch
    columns where its chain runs and reads back none of their state; its
    `px.quantiles` strings and p99 values are the host finalize's."""
    route(monkeypatch, backend)
    rng = np.random.default_rng(11)
    n = 40_000
    ts = TableStore()
    ts.create("events", Relation.of(("time_", DT.TIME64NS), ("id", DT.INT64),
                                    ("v", DT.FLOAT64)),
              batch_rows=1 << 15).write({
        "time_": np.arange(n, dtype=np.int64),
        "id": rng.permutation(n),
        "v": rng.lognormal(0.0, 2.0, n)})
    def run() -> tuple:
        ex = PlanExecutor(_local_sorted_plan(), ts)
        out = frame(ex.run()["out"])
        assert ex.stats["sorted_agg_fallbacks"] == 1
        (span,) = [r["span"] for r in ex.op_stats
                   if r.get("span", {}).get("agg_form") == "sorted"]
        return out, (span["sketch_state_bytes"],
                     span["sketch_device_finalized"])

    got, chain = run()
    reduce = PlanExecutor._sorted_group_reduce
    monkeypatch.setattr(
        PlanExecutor, "_sorted_group_reduce",
        lambda self, op, finalize: reduce(self, op, finalize=False))
    want, host = run()
    pd.testing.assert_frame_equal(got, want, check_exact=True)
    assert len(got) == 5000 and got["q"].str.startswith('{"p01": ').all()
    assert chain == (0, 2)
    assert host == (2 * 8192 * pgsql.HIST_BYTES, 0)


VIEW_QUERY = """
import px
df = px.DataFrame(table='http_events')
df = df.groupby(['req_path']).agg(cnt=('latency', px.count),
                                 p50=('latency', px.p50),
                                 q=('latency', px.quantiles))
px.display(df, 'out')
"""


def test_a_standing_views_state_stays_raw(config, monkeypatch):
    """A view-eligible query on one agent: its first answer ships bins, the
    view it registers folds raw histograms, and the answers served from
    the view are the first one's."""
    route(monkeypatch, "device")
    store = datagen.load_store(config, datagen.generate(config, SEED))
    with served([store], PL_MATVIEW_ENABLED=True) as (client, agents):
        since = time.time_ns()
        answers = [frame(client.execute_script(VIEW_QUERY)["out"])]
        (first,) = sketch_chains(since)
        answers += [frame(client.execute_script(VIEW_QUERY)["out"])
                    for _ in range(2)]
        views = list(agents[0].matviews._views.values())
    assert first.attributes["sketch_device_finalized"] == 2
    assert first.attributes["sketch_state_bytes"] == 0
    for later in answers[1:]:
        pd.testing.assert_frame_equal(answers[0], later, check_exact=True)
    (view,) = views
    assert view.state is not None and view.state.finalized == frozenset()
    for name in ("p50", "q"):
        assert view.state.states[name].shape[1] == 514


def _part(services, bins, finalized=True) -> PartialAggBatch:
    n = len(services)
    return PartialAggBatch(
        key_cols={"service": np.asarray(services, dtype=object)},
        key_dtypes={"service": DT.STRING},
        states={"n": np.ones(n, np.int64),
                "p": np.asarray(bins, np.int32)},
        in_types={"n": DT.FLOAT64, "p": DT.FLOAT64},
        finalized=frozenset({"p"}) if finalized else frozenset())


AGG = AggOp(groups=["service"], values=[AggExpr("n", "count", "latency"),
                                        AggExpr("p", "p50", "latency")],
            partial=True)


def test_the_merge_passes_finalized_columns_through_and_never_adds_them():
    """Chunks of one producer (disjoint groups) concatenate, through the
    wire and a slice; parts that share a group, or disagree on the mark,
    raise Internal."""
    whole = _part(["a", "b", "c", "d"], [3, -1, 7, 9])
    chunks = [PartialAggBatch.from_bytes(slice_partial(whole, idx).to_bytes())
              for idx in (np.array([2, 3]), np.array([0, 1]))]
    assert all(c.finalized == frozenset({"p"}) for c in chunks)
    merged = combine_partials(AGG, chunks, udf_registry)
    assert merged.finalized == frozenset({"p"})
    assert list(merged.key_cols["service"]) == ["a", "b", "c", "d"]
    np.testing.assert_array_equal(merged.states["p"], [3, -1, 7, 9])
    hb = finalize_partial(AGG, merged, udf_registry)
    from pixie_tpu.ops.sketch import LogHistogram

    np.testing.assert_array_equal(
        hb.cols["p"], LogHistogram().bin_values(np.array([3, -1, 7, 9])))
    with pytest.raises(Internal, match="share a group"):
        combine_partials(AGG, [_part(["a", "b"], [1, 2]),
                               _part(["b", "c"], [3, 4])], udf_registry)
    with pytest.raises(Internal, match="raw in others"):
        combine_partials(AGG, [_part(["a"], [1]),
                               _part(["b"], [3], finalized=False)],
                         udf_registry)
