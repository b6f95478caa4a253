"""A partial aggregate whose channel has one producer finalizes its quantile
sketches where its chain runs and ships their bins, not [G, 514]
histograms (parallel/distributed.py marks it `sole_producer`;
engine/executor.py `_partial_agg_batch`; parallel/partial.py passes the
finalized column through).  Served through a Broker and its Agents on
loopback, on small seeded stores of the service map's and the http
widgets' shapes: the answers are the raw path's string for string, two
producers of one table still ship raw state, a standing view's state stays
raw, and the merge refuses to add a finalized column.
"""
import contextlib
import time

import numpy as np
import pandas as pd
import pytest

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
from pixie_tpu.plan.plan import AggExpr, AggOp
from pixie_tpu.status import Internal
from pixie_tpu.types import DataType as DT
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
