"""Cross-process trace propagation: broker + 2 agents execute a distributed
query; the result is ONE trace (single trace_id) whose spans cover compile,
dispatch, per-agent exec, readback, and merge, with correct parent/child
links across the wire, no unclosed spans, and an OTLP/JSON payload accepted
by an in-process collector (the injected-exporter seam of tests/test_otel.py).
The trace is queryable via the bundled px/self_query_latency script through
the normal PxL path."""
from __future__ import annotations

import time

import numpy as np
import pytest

from pixie_tpu import flags, metrics, trace
from pixie_tpu.services.agent import Agent
from pixie_tpu.services.broker import Broker
from pixie_tpu.services.client import Client
from pixie_tpu.table import TableStore
from pixie_tpu.types import DataType as DT, Relation

SEC = 1_000_000_000


class OtlpCollector:
    """In-process OTLP collector: validates every resourceSpans payload the
    way tests/test_otel.py's injected exporter seam does, then stores it."""

    def __init__(self):
        self.payloads = []

    def __call__(self, payload: dict) -> None:
        assert "resourceSpans" in payload, sorted(payload)
        for rs in payload["resourceSpans"]:
            res_attrs = {a["key"] for a in rs["resource"]["attributes"]}
            assert "service.name" in res_attrs
            for ss in rs["scopeSpans"]:
                for s in ss["spans"]:
                    assert len(s["traceId"]) == 32
                    assert len(s["spanId"]) == 16
                    assert int(s["endTimeUnixNano"]) >= int(
                        s["startTimeUnixNano"])
        self.payloads.append(payload)

    @property
    def spans(self) -> list[dict]:
        return [s
                for p in self.payloads
                for rs in p["resourceSpans"]
                for ss in rs["scopeSpans"]
                for s in ss["spans"]]


def _mkstore(seed: int, now_ns: int, n: int = 3000) -> TableStore:
    ts = TableStore()
    rel = Relation.of(("time_", DT.TIME64NS), ("service", DT.STRING),
                      ("latency", DT.INT64))
    t = ts.create("http_events", rel, batch_rows=512)
    rng = np.random.default_rng(seed)
    t.write({
        "time_": now_ns - np.arange(n, dtype=np.int64)[::-1] * 1_000_000,
        "service": rng.choice(["a", "b"], n).tolist(),
        "latency": rng.integers(1, 1000, n),
    })
    return ts


@pytest.fixture
def cluster():
    flags.set_for_testing("PL_TRACING_ENABLED", True)
    collector = OtlpCollector()
    now_ns = time.time_ns()
    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    broker.tracer.exporter = collector
    stores = {"pem1": _mkstore(1, now_ns), "pem2": _mkstore(2, now_ns)}
    agents = []
    for name, st in stores.items():
        a = Agent(name, "127.0.0.1", broker.port, store=st,
                  heartbeat_s=1.0).start()
        a.tracer.exporter = collector
        agents.append(a)
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    yield broker, stores, agents, client, collector
    client.close()
    for a in agents:
        a.stop()
    broker.stop()


QUERY = """
import px
df = px.DataFrame(table='http_events')
df = df.groupby('service').agg(cnt=('latency', px.count),
                               p50=('latency', px.p50))
px.display(df, 'out')
"""


def _all_span_rows(stores: dict) -> list[dict]:
    rows = []
    for st in stores.values():
        if not st.has(trace.SPANS_TABLE):
            continue
        t = st.table(trace.SPANS_TABLE)
        for rb, _rid, _gen in t.cursor():
            n = rb.num_valid
            cols = {}
            for c in t.relation:
                arr = rb.columns[c.name][:n]
                cols[c.name] = (t.dictionaries[c.name].decode(arr)
                                if c.name in t.dictionaries else arr.tolist())
            rows.extend(
                {k: cols[k][i] for k in cols} for i in range(n))
    return rows


def _wait_for_root(stores, min_spans: int, timeout: float = 5.0) -> list[dict]:
    """Broker spans ship to an agent asynchronously after `done`; poll until
    the query root has landed."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        rows = _all_span_rows(stores)
        if len(rows) >= min_spans and any(
                r["name"] == "query" for r in rows):
            return rows
        time.sleep(0.05)
    raise AssertionError(f"trace never landed: {len(_all_span_rows(stores))}")


def test_single_trace_with_correct_links(cluster):
    broker, stores, agents, client, collector = cluster
    res = client.execute_script(QUERY)
    assert res["out"].num_rows == 2
    rows = _wait_for_root(stores, min_spans=8)

    # one trace_id across broker AND both agents
    trace_ids = {r["trace_id"] for r in rows}
    assert len(trace_ids) == 1, trace_ids
    services = {r["service"] for r in rows}
    assert services == {"broker", "pem1", "pem2"}

    # >= 8 spans covering compile, dispatch, per-agent exec, readback, merge
    assert len(rows) >= 8
    names = {r["name"] for r in rows}
    assert {"query", "compile", "plan_split", "dispatch", "merge",
            "exec"} <= names
    assert any(r["name"] == "readback_wave" for r in rows)
    assert sum(1 for r in rows if r["name"] == "dispatch") == 2
    assert sum(1 for r in rows if r["name"] == "exec") == 2

    # parent/child links: exactly one root; every parent id resolves; each
    # agent's exec span parents under a broker dispatch span (cross-process)
    by_id = {r["span_id"]: r for r in rows}
    roots = [r for r in rows if r["parent_span_id"] == ""]
    assert [r["name"] for r in roots] == ["query"]
    for r in rows:
        if r["parent_span_id"]:
            assert r["parent_span_id"] in by_id, r
    for r in rows:
        if r["name"] == "exec":
            parent = by_id[r["parent_span_id"]]
            assert parent["name"] == "dispatch"
            assert parent["service"] == "broker"

    # no unclosed spans anywhere
    assert broker.tracer.open_spans == 0
    for a in agents:
        assert a.tracer.open_spans == 0

    # the in-process collector accepted OTLP/JSON for every flush, and the
    # exported spans carry the same single trace id
    assert collector.payloads
    exported_tids = {s["traceId"] for s in collector.spans}
    assert trace_ids <= exported_tids


def test_trace_queryable_via_bundled_pxl_script(cluster):
    broker, stores, agents, client, collector = cluster
    client.execute_script(QUERY)
    _wait_for_root(stores, min_spans=8)

    from pixie_tpu.scripts import REPO_BUNDLE

    src = (REPO_BUNDLE / "self_query_latency"
           / "self_query_latency.pxl").read_text()
    res = client.execute_script(src, func="span_latency",
                                func_args={"start_time": "-5m"})
    df = res["output"].to_pandas()
    assert {"service", "name", "count", "latency_p50", "latency_p99",
            "total_ns"} == set(df.columns)
    assert set(df["service"]) >= {"broker", "pem1", "pem2"}
    got = df.set_index(["service", "name"])["count"]
    assert got[("broker", "query")] >= 1
    assert got[("pem1", "exec")] >= 1 and got[("pem2", "exec")] >= 1

    res2 = client.execute_script(src, func="query_latency",
                                 func_args={"start_time": "-5m"})
    df2 = res2["output"].to_pandas()
    assert set(df2["service"]) == {"broker"}
    assert int(df2["queries"].iloc[0]) >= 1


def test_latency_histograms_on_metrics_endpoint(cluster):
    broker, stores, agents, client, collector = cluster
    metrics.reset_for_testing()
    client.execute_script(QUERY)
    text = metrics.render()
    assert "# TYPE px_broker_query_latency_seconds histogram" in text
    assert "px_broker_query_latency_seconds_count 1" in text
    assert "# TYPE px_readback_wave_seconds histogram" in text
    assert 'px_readback_wave_seconds_bucket{le="+Inf"}' in text


def test_disabled_tracing_adds_no_spans_or_wire_context(cluster):
    broker, stores, agents, client, collector = cluster
    flags.set_for_testing("PL_TRACING_ENABLED", False)
    try:
        b0 = broker.tracer.started
        a0 = [a.tracer.started for a in agents]
        res = client.execute_script(QUERY)
        assert res["out"].num_rows == 2
        assert broker.tracer.started == b0
        assert [a.tracer.started for a in agents] == a0
    finally:
        flags.set_for_testing("PL_TRACING_ENABLED", True)
