"""Live web view (webui.py): server pages, run API, widget renderers,
deep links, and session auth.

Reference: the Live View user loop — script list → per-script page with
editable source + variable inputs → widget grid rendered from vis.json
(src/ui/src/containers/live/, vispb/vis.proto widget kinds).
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from tests.conftest import requires_reference as _requires_reference

from pixie_tpu.engine.result import QueryResult
from pixie_tpu.table.dictionary import Dictionary
from pixie_tpu.types import (
    ColumnSchema,
    DataType as DT,
    Relation,
    SemanticType as ST,
)
from pixie_tpu.webui import (
    LiveServer,
    bars_svg,
    flamegraph_html,
    local_runner,
    render_widget_html,
    table_html,
    timeseries_svg,
)


def _qr(cols: dict, strings=(), semantics=None):
    semantics = semantics or {}
    dicts = {}
    out = {}
    schema = []
    for name, vals in cols.items():
        st = semantics.get(name, ST.ST_NONE)
        if name in strings:
            d = Dictionary(sorted(set(vals)))
            dicts[name] = d
            out[name] = d.encode(list(vals))
            schema.append(ColumnSchema(name, DT.STRING, semantic_type=st))
        else:
            arr = np.asarray(vals)
            out[name] = arr
            schema.append(ColumnSchema(
                name, DT.FLOAT64 if arr.dtype.kind == "f" else DT.INT64,
                semantic_type=st))
    return QueryResult(name="t", relation=Relation(schema), columns=out,
                       dictionaries=dicts)


# ------------------------------------------------------------ widget golden
def test_table_html_renders_rows_and_header():
    qr = _qr({"svc": ["a", "b"], "n": [1, 2]}, strings=("svc",))
    h = table_html(qr)
    assert "<th>svc</th>" in h and "<th>n</th>" in h
    assert "<td>a</td>" in h and "<td>2</td>" in h


def test_table_html_entity_deep_link_roundtrip():
    qr = _qr({"pod": ["ns/pod-1"], "n": [3]}, strings=("pod",),
             semantics={"pod": ST.ST_POD_NAME})
    h = table_html(qr, link_args={"start_time": "-5m"})
    # entity cells become drill-down links carrying the page's args
    assert 'href="/script/pod?' in h
    assert "pod=ns%2Fpod-1" in h and "start_time=-5m" in h


def test_timeseries_svg_series_split():
    n = 20
    qr = _qr({
        "time_": np.arange(n, dtype=np.int64) * 1_000_000_000,
        "v": np.arange(n, dtype=np.float64),
        "svc": ["a" if i % 2 else "b" for i in range(n)],
    }, strings=("svc",))
    svg = timeseries_svg(qr, {"timeseries": [{"value": "v", "series": "svc"}]})
    assert svg.startswith("<svg")
    assert svg.count("<polyline") == 2  # one line per series
    assert "● a" in svg and "● b" in svg


def test_bars_svg_sorted_and_formatted():
    qr = _qr({"svc": ["a", "b", "c"], "lat": [3.0, 9.0, 6.0]},
             strings=("svc",),
             semantics={"lat": ST.ST_DURATION_NS})
    svg = bars_svg(qr, {"bar": {"label": "svc", "value": "lat"}})
    assert svg.startswith("<svg")
    # widest bar first (b=9), semantic duration formatting applied
    assert svg.index(">b</text>") < svg.index(">c</text>") < svg.index(
        ">a</text>")
    assert "9ns" in svg


def test_flamegraph_nesting():
    qr = _qr({"stack_trace": ["main;f;g", "main;f", "main;h"],
              "count": [5, 3, 2]}, strings=("stack_trace",))
    h = flamegraph_html(qr, {"stacktraceFlameGraph": {
        "stacktraceColumn": "stack_trace", "countColumn": "count"}})
    assert 'class="flame"' in h
    assert "main" in h and ">f<" in h.replace("</div>", "<")
    # f subtree (8/10) wider than h (2/10): width percentages present
    assert "width:80.0%" in h and "width:20.0%" in h


def test_render_widget_html_dispatch_and_empty():
    qr = _qr({"svc": ["a"], "n": [1]}, strings=("svc",))
    assert "<table>" in render_widget_html("Table", {}, qr)
    empty = _qr({"n": np.asarray([], dtype=np.int64)})
    assert "no rows" in render_widget_html("Table", {}, empty)


# ----------------------------------------------------------------- server
@pytest.fixture(scope="module")
def server():
    import time

    from pixie_tpu.metadata.state import set_global_manager
    from pixie_tpu.testing import build_demo_store, demo_metadata

    mgr, _, _ = demo_metadata()
    set_global_manager(mgr)
    now = time.time_ns()
    store = build_demo_store(rows=2_000, now_ns=now, span_s=300)
    srv = LiveServer(local_runner(store, now=now)).start()
    yield srv
    srv.stop()


def _get(server, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}{path}") as r:
        return r.status, r.read().decode()


def _post(server, path, body: dict, token=None, origin=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}{path}",
        data=json.dumps(body).encode(), method="POST")
    if token is not None:
        req.add_header("X-Pixie-Session", token)
    if origin is not None:
        req.add_header("Origin", origin)
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_index_lists_bundled_scripts(server):
    code, body = _get(server, "/")
    assert code == 200
    from pixie_tpu.scripts import REFERENCE_BUNDLE

    if REFERENCE_BUNDLE.is_dir():
        assert '/script/http_data' in body
        assert '/script/cluster' in body
    else:
        assert '/script/self_query_latency' in body


@_requires_reference
def test_script_page_embeds_source_vars_and_token(server):
    code, body = _get(server, "/script/http_data")
    assert code == 200
    assert "start_time" in body           # vis variable input
    assert "px.DataFrame" in body         # script source in the editor
    assert server.session_token in body   # session token embedded for fetch


def test_script_page_404(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server, "/script/nope_not_a_script")
    assert ei.value.code == 404


def test_profiles_page_renders_every_panel():
    """GET /profiles renders one pane per _PROFILE_PANELS entry — the
    flight recorder's plus the storage observatory's — with the expected
    titles derived from the panel list, never a hard-coded count."""
    from pixie_tpu import observe, webui
    from pixie_tpu.table import TableStore

    ts = TableStore()
    observe.write_rows(ts, observe.PROFILES_TABLE, [{
        "time_": 10 ** 15, "query_id": "q0", "tenant": "t0",
        "service": "broker", "status": "ok", "wall_ns": 1000}])
    observe.write_rows(ts, observe.ALERTS_TABLE, [{
        "time_": 10 ** 15, "slo": "lat", "tenant": "t0", "window": "fast",
        "burn_rate": 20.0, "threshold": 14.4, "objective": 0.99,
        "state": "firing"}])
    observe.write_rows(ts, observe.SCALE_EVENTS_TABLE, [{
        "time_": 10 ** 15, "action": "scale_up", "agent": "pem1",
        "reason": "pressure", "pressure": 2.0, "agents": 2}])
    observe.write_rows(ts, observe.SHARD_HEAT_TABLE, [{
        "time_": 10 ** 15, "table_name": "http_events", "shard": "pem0",
        "tier": "stream", "age_bucket": "hot", "rows_scanned": 100,
        "bytes": 800, "heat": 50.0, "skew": 1.0, "last_access": 10 ** 15}])
    observe.write_rows(ts, observe.STORAGE_STATE_TABLE, [{
        "time_": 10 ** 15, "agent": "pem0", "table_name": "http_events",
        "hot_rows": 100, "sealed_batches": 1, "sealed_bytes": 4096,
        "age_histogram": "", "resident_bytes": 0, "matview_bytes": 0,
        "journal_bytes": 123, "journal_segments": 1,
        "repl_lag_batches": 0, "peer_lag": ""}])
    observe.write_rows(ts, observe.AUTOTUNE_TABLE, [{
        "time_": 10 ** 15, "query_id": "q0", "gate": "cpu_crossover",
        "plan_class": "agg", "size_bucket": "4^9", "arm": "cpu",
        "static_arm": "device", "source": "model", "model_ms": 2.0,
        "static_ms": 9.0, "observed_ms": 2.1, "reason": ""}])
    srv = LiveServer(local_runner(ts)).start()
    try:
        code, body = _get(srv, "/profiles")
    finally:
        srv.stop()
    assert code == 200
    assert len(webui._PROFILE_PANELS) >= 6
    for title, _pxl in webui._PROFILE_PANELS:
        assert title in body, title
    assert "shard" in body and "journal_bytes" in body


@_requires_reference
def test_run_api_executes_and_renders_widgets(server):
    code, out = _post(server, "/api/run",
                      {"script": "http_data", "vars": {}},
                      token=server.session_token)
    assert code == 200
    assert "error" not in out
    assert out["widgets"], "http_data should render at least one widget"
    assert any("<table>" in w["html"] or "<svg" in w["html"]
               for w in out["widgets"])


@_requires_reference
def test_run_api_edited_source_reruns(server):
    # the edited source redefines the vis func (http_data) in place — the
    # Live View's edit-and-rerun loop keeps the vis spec, swaps the script
    src = ("import px\n"
           "def http_data(start_time: str, source_filter: str,\n"
           "              destination_filter: str, num_head: int):\n"
           "    df = px.DataFrame(table='http_events', start_time=start_time)\n"
           "    return df.groupby('req_path').agg(n=('latency', px.count))\n")
    code, out = _post(server, "/api/run",
                      {"script": "http_data", "vars": {}, "source": src},
                      token=server.session_token)
    assert code == 200, out
    assert "error" not in out, out
    widgets = out.get("widgets", [])
    assert widgets and all(w["name"] == "http_data" for w in widgets)
    # our 2-column aggregate, not the bundled script's wide table
    assert any("req_path" in w["html"] and "<table>" in w["html"]
               for w in widgets)


def test_run_api_rejects_missing_token(server):
    code, out = _post(server, "/api/run", {"script": "http_data"})
    assert code == 403
    assert "token" in out["error"]


def test_rejects_rebound_host_header(server):
    """DNS-rebinding defense: Host: evil.com must be rejected even on GET
    (else the rebound page could read the session token out of the HTML)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/script/http_data",
        headers={"Host": "evil.example:8083"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 403


def test_script_name_traversal_rejected(server):
    """'../' in a script name must not escape the bundle directory (404),
    for both the page and the run API."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{server.port}/script/%2e%2e%2ftmp")
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req)
    assert ei.value.code == 404
    code, out = _post(server, "/api/run", {"script": "../../../tmp"},
                      token=server.session_token)
    assert code == 200 and "FileNotFoundError" in out.get("error", "")


def test_run_api_rejects_cross_origin(server):
    code, out = _post(server, "/api/run", {"script": "http_data"},
                      token=server.session_token,
                      origin="http://evil.example")
    assert code == 403
    assert "cross-origin" in out["error"]


@_requires_reference
def test_broker_runner_end_to_end():
    """The OTHER runner path: Live View backed by a real broker+agent
    cluster (fused multi-widget execution over the wire)."""
    import numpy as np

    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker
    from pixie_tpu.services.client import Client
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation
    from pixie_tpu.webui import LiveServer, broker_runner

    rng = np.random.default_rng(5)
    ts = TableStore()
    rel = Relation.of(("time_", DT.TIME64NS),
                      ("service", DT.STRING), ("latency", DT.FLOAT64),
                      ("status", DT.INT64))
    t = ts.create("http_events", rel, batch_rows=512)
    n = 1500
    t.write({
        "time_": np.arange(n, dtype=np.int64) * 1000,
        "service": rng.choice(["cart", "web"], n).tolist(),
        "latency": rng.exponential(20.0, n),
        "status": rng.choice([200, 500], n),
    })
    broker = Broker(hb_expiry_s=2.0, query_timeout_s=30.0).start()
    agent = Agent("pem1", "127.0.0.1", broker.port, store=ts,
                  heartbeat_s=0.2).start()
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    srv = LiveServer(broker_runner(client)).start()
    try:
        code, out = _post(
            srv, "/api/run",
            {"script": "http_data",
             "source": ("import px\n"
                        "df = px.DataFrame(table='http_events')\n"
                        "def http_data(start_time: str, source_filter: str,"
                        " destination_filter: str, num_head: int):\n"
                        "    d = px.DataFrame(table='http_events')\n"
                        "    return d.groupby('service').agg("
                        "n=('latency', px.count))\n")},
            token=srv.session_token)
        assert code == 200, out
        assert "error" not in out, out
        assert out["widgets"], "broker-backed run must render widgets"
        html = out["widgets"][0]["html"]
        assert "cart" in html and "web" in html
    finally:
        srv.stop()
        client.close()
        agent.stop()
        broker.stop()


def test_run_api_surfaces_script_error_as_json(server):
    code, out = _post(server, "/api/run",
                      {"script": "http_data", "source": "import px\nboom("},
                      token=server.session_token)
    assert code == 200
    assert "error" in out
