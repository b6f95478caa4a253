"""The spans that say where the agent's time goes, and their readers.

The ring (`trace.recent`) keeps finished spans after a flush; one served
query yields a chain span with its engine and routing decision, a `feed`,
a `plan_decode`, a `result_send`, `jax_compile` spans when it compiled and
a `telemetry_flush`; a pull from XLA-CPU in an accelerator process is a
`cpu_chain_wait`; every `px.*` scope is in the lowered text of the kernel
it wraps; the benchmark's span readers (benchmarks/metrics/) compute their
values from a hand-made list of spans."""
from __future__ import annotations

import collections
import contextlib
import importlib.util
import itertools
import os
import sys
import time

import jax
import numpy as np
import pytest

from pixie_tpu import flags, trace
from pixie_tpu.engine import autotune, transfer
from pixie_tpu.parallel import spmd
from pixie_tpu.services.agent import Agent
from pixie_tpu.services.broker import Broker
from pixie_tpu.services.client import Client
from pixie_tpu.types import DataType as DT
from tests.test_trace_distributed import _all_span_rows, _mkstore

METRICS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "metrics")

#: filter + sketch: neither the numpy partial loop nor a matview takes it
QUERY = """
import px
df = px.DataFrame(table='http_events')
df = df[df.latency > {floor}]
df = df.groupby('service').agg(cnt=('latency', px.count),
                               p50=('latency', px.p50))
px.display(df, 'out')
"""


@pytest.fixture
def fresh_ring(monkeypatch):
    """An empty ring of the usual size, for this test alone."""
    monkeypatch.setattr(trace, "_RING",
                        collections.deque(maxlen=trace.RING_SPANS))
    monkeypatch.setattr(trace, "_RING_SEQ", itertools.count())


@contextlib.contextmanager
def serving(store, monkeypatch):
    """One Broker and one single-device Agent `pem0` over `store`: tracing
    and the router's model on (the model fresh), the native whole-plan loop
    and standing views off, so that every query runs its jitted chain under
    a routing decision.  Yields the client."""
    wanted = {"PL_TRACING_ENABLED": True, "PX_AUTOTUNE": True,
              "PX_WHOLEPLAN_NATIVE": False, "PL_MATVIEW_ENABLED": False}
    before = {k: flags.get(k) for k in wanted}
    for k, v in wanted.items():
        flags.set_for_testing(k, v)
    monkeypatch.setattr(spmd, "default_mesh", lambda: None)
    autotune.MODEL.reset_for_testing()
    broker = Broker(hb_expiry_s=5.0, query_timeout_s=30.0).start()
    agent = Agent("pem0", "127.0.0.1", broker.port, store=store,
                  heartbeat_s=1.0).start()
    client = Client("127.0.0.1", broker.port, timeout_s=30.0)
    try:
        yield client
    finally:
        client.close()
        agent.stop()
        broker.stop()
        autotune.MODEL.reset_for_testing()
        for k, v in before.items():
            flags.set_for_testing(k, v)


@pytest.fixture
def served(monkeypatch, fresh_ring):
    """`serving` over a small http_events."""
    store = _mkstore(1, time.time_ns())
    with serving(store, monkeypatch) as client:
        yield client, store


def _agent_spans(since_ns: int = 0) -> list:
    return [s for s in trace.recent(since_ns) if s.service == "pem0"]


def _chains(spans: list) -> list:
    return [s for s in spans if "engine" in s.attributes]


# ------------------------------------------------------------------ the ring


def test_ring_keeps_spans_after_flush_and_counts_what_it_let_go(monkeypatch):
    monkeypatch.setattr(trace, "_RING", collections.deque(maxlen=4))
    monkeypatch.setattr(trace, "_RING_SEQ", itertools.count())
    tr = trace.Tracer("svc")
    for i in range(3):
        tr.finish(tr.start_span(f"s{i}", start_ns=100 + i), end_ns=200 + i)
    assert len(tr.flush()) == 3 and tr.buffered == 0
    assert [s.name for s in trace.recent()] == ["s0", "s1", "s2"]
    assert [s.name for s in trace.recent(since_unix_ns=201)] == ["s1", "s2"]
    assert trace.ring_dropped() == 0
    for i in range(3, 7):
        tr.finish(tr.start_span(f"s{i}", start_ns=100 + i), end_ns=200 + i)
    assert [s.name for s in trace.recent()] == ["s3", "s4", "s5", "s6"]
    assert trace.ring_dropped() == 3


def test_ring_takes_nothing_with_tracing_off(fresh_ring):
    flags.set_for_testing("PL_TRACING_ENABLED", False)
    try:
        tr = trace.Tracer("svc")
        tr.finish(tr.start_span("s"))
        with trace.root(tr, "r"):
            pass
        assert trace.recent() == []
    finally:
        flags.set_for_testing("PL_TRACING_ENABLED", True)


# ----------------------------------------------------------- one served query


@pytest.mark.parametrize("accelerator_process", [False, True])
def test_served_query_spans(served, monkeypatch, accelerator_process):
    """The chain span carries its engine and routing decision; `feed`,
    `plan_decode` and `result_send` are there; the pull of a chain pinned
    to XLA-CPU is a `readback_wave` in a CPU-only process and a
    `cpu_chain_wait` where the default backend is an accelerator."""
    client, _store = served
    monkeypatch.setattr(transfer, "_accelerator_process",
                        lambda: accelerator_process)
    client.execute_script(QUERY.format(floor=5))
    spans = _agent_spans()
    names = [s.name for s in spans]
    (chain,) = _chains(spans)
    assert chain.name == "scan(http_events)->filter->partial_agg"
    a = chain.attributes
    assert a["engine"] == "xla_cpu_chain" and a["arm"] == "cpu"
    assert a["source"] == "cold" and a["decision_n"] == 1
    assert a["guard_trips"] == 0
    assert a["size_bucket"] == autotune.size_bucket(3000)
    assert a["rows"] == 3000
    (feed,) = [s for s in spans if s.name == "feed"]
    assert feed.attributes["feeds"] >= 1
    assert feed.attributes["h2d_bytes"] == 0  # the CPU arm uploads nothing
    assert chain.start_ns <= feed.start_ns and feed.end_ns <= chain.end_ns
    (exec_,) = [s for s in spans if s.name == "exec"]
    (decode,) = [s for s in spans if s.name == "plan_decode"]
    (send,) = [s for s in spans if s.name == "result_send"]
    assert send.attributes["chunks"] == 1 and send.attributes["bytes"] > 0
    for child in (chain, feed, decode, send):
        assert child.parent_span_id == exec_.span_id
        assert child.trace_id == exec_.trace_id
    assert decode.end_ns <= chain.start_ns <= chain.end_ns <= send.end_ns
    if accelerator_process:
        assert "cpu_chain_wait" in names and "readback_wave" not in names
    else:
        assert "readback_wave" in names and "cpu_chain_wait" not in names


def test_forced_explore_is_a_chain_span(served):
    """The model's fourth cold decision probes the other arm: that query's
    chain span says source=explore (the router-probe span)."""
    client, _store = served
    for i in range(autotune.COLD_PROBE_PERIOD):
        client.execute_script(QUERY.format(floor=5))
    chains = _chains(_agent_spans())
    assert [c.attributes["source"] for c in chains] == [
        "cold", "cold", "cold", "explore"]
    probe = chains[-1].attributes
    assert probe["arm"] == "device" and probe["engine"] == "device_chain"
    assert probe["decision_n"] == autotune.COLD_PROBE_PERIOD


def test_chain_span_says_the_bucket_its_kernels_were_handed(served):
    """Beside `rows` a chain span carries `feed_rows`, the pow2-bucketed
    row count of the feeds its kernels were handed, on either arm: `rows /
    feed_rows` is the share of chunks the device kernels' loops visit.  The
    store's 3,000 rows are five sealed batches of 512, one feed in a
    4,096-row bucket, and a hot remainder of 440 in the smallest bucket."""
    client, _store = served
    for _ in range(autotune.COLD_PROBE_PERIOD):
        client.execute_script(QUERY.format(floor=5))
    chains = _chains(_agent_spans())
    assert [c.attributes["engine"] for c in chains] == [
        "xla_cpu_chain"] * 3 + ["device_chain"]
    for c in chains:
        assert c.attributes["rows"] == 3000
        assert c.attributes["feed_rows"] == 4096 + 1024


SORTED_QUERY = """
import px
df = px.DataFrame(table='http_events')
df.k = df.latency % 7
df = df.groupby(['service', 'k']).agg(mn=('latency', px.min),
                                      mx=('latency', px.max))
px.display(df, 'out')
"""


def test_sorted_aggregate_spans_say_its_phases_and_what_came_back(served):
    """A computed key has no dense code: the aggregate sorts.  Its chain
    span (`..->sorted_agg`) is routed like any other and says its form, how
    many groups came out and the bytes read back; `sort_reduce` and
    `compact_readback` lie inside it, the host's `key_decode` follows it.

    A frame's span starts at a wall-clock reading and lasts a monotonic
    duration, and `compact_readback` closes some 20-40 us before its chain
    does: under the full suite's load, on a machine whose wall clock is
    stepped, a cold chain of seconds read `compact_readback` as ending
    after it (the one failure of the driver's tier-1 runs of PR 36).  So
    the phases are a query's by its trace id, in order by their starts, and
    inside the chain by their durations, which are the nested frames' own
    monotonic ones."""
    client, _store = served
    for _ in range(autotune.COLD_PROBE_PERIOD):
        out = client.execute_script(SORTED_QUERY)["out"].to_pandas()
    assert len(out) == 14
    spans = _agent_spans()
    chains = _chains(spans)
    assert [c.name for c in chains] == ["scan(http_events)->map->sorted_agg"] * 4
    assert [c.attributes["source"] for c in chains] == [
        "cold", "cold", "cold", "explore"]
    assert [c.attributes["engine"] for c in chains] == [
        "xla_cpu_chain"] * 3 + ["device_chain"]
    assert len({c.trace_id for c in chains}) == 4
    for c in chains:
        a = c.attributes
        assert a["agg_form"] == "sorted" and "groups" not in a
        assert a["groups_out"] == 14
        assert a["plan_class"].startswith("agg:http_events:")
        # 16 slots of an int32 code, an int64 key and two int64 states
        assert a["d2h_bytes"] == 4 + 16 * (4 + 8 + 8 + 8)
        assert a["rows"] == 3000 and a["feed_rows"] == 4096 + 1024
        sort, compact, decode = (
            [s for s in spans if s.name == name and s.trace_id == c.trace_id]
            for name in ("sort_reduce", "compact_readback", "key_decode"))
        assert len(sort) == len(compact) == len(decode) == 1
        assert (c.start_ns <= sort[0].start_ns <= compact[0].start_ns
                <= decode[0].start_ns)
        assert sort[0].duration_ns + compact[0].duration_ns <= c.duration_ns
        assert decode[0].attributes["rows_out"] == 14


#: the windowed chart's shape: the 751-769 windows of 4 ms that 3,000-3,072
#: rows a millisecond apart span (a 1,024-bin bucket) x 2 services = 2,048
#: dense slots, over MATMUL_MAX_GROUPS
WINDOWED_QUERY = """
import px
df = px.DataFrame(table='http_events')
df = df[df.latency > 5]
df.time_ = px.bin(df.time_, px.millis(4))
df = df.groupby(['time_', 'service']).agg(cnt=('latency', px.count),
                                          avg=('latency', px.mean),
                                          p50=('latency', px.p50))
px.display(df, 'out')
"""


@pytest.mark.parametrize("backend,rows,windowed,by_status", [
    (None, 3072, "scatter", "scatter"),
    ("tpu", 3072, "onehot2", "onehot"),
    # 3,072 rows are whole 512-row batches: one feed in a 4,096-row bucket;
    # 3,000 leave a hot remainder in a 1,024-row bucket of its own, under
    # the GEMMs' floor of rows
    ("tpu", 3000, "onehot2+scatter", "onehot+scatter"),
])
def test_chain_span_says_the_aggregates_form_and_slots(
        monkeypatch, fresh_ring, backend, rows, windowed, by_status):
    """A dense aggregate's chain span says `groups`, the slots of its dense
    state, and `agg_form`, how its sums and counts reduce into them, on
    either arm: what `ops/groupby.agg_form` says for each feed where the
    chain is traced, which is what the kernels dispatch on.  A
    windowed-shaped query (2,048 slots) takes the factored one-hot where
    the kernels are traced for the TPU, a by-status-shaped one (2 slots)
    the flat one, both the scatter anywhere else; feeds that differ in form
    are named largest first."""
    from pixie_tpu.ops import groupby

    if backend:
        monkeypatch.setattr(groupby, "dispatch_backend", lambda: backend)
    with serving(_mkstore(1, time.time_ns(), rows), monkeypatch) as client:
        for query, form, slots in ((WINDOWED_QUERY, windowed, 2048),
                                   (QUERY.format(floor=5), by_status, 2)):
            t0 = time.time_ns()
            for _ in range(autotune.COLD_PROBE_PERIOD):
                client.execute_script(query)
            chains = _chains(_agent_spans(t0))
            assert [c.attributes["arm"] for c in chains] == [
                "cpu"] * 3 + ["device"]
            for c in chains:
                assert c.attributes["agg_form"] == form, c.attributes
                assert c.attributes["groups"] == slots
                assert c.attributes["rows"] == rows


JOIN_QUERY = """
import px
a = px.DataFrame(table='http_events')
a = a.groupby('service').agg(cnt=('latency', px.count))
b = px.DataFrame(table='http_events')
b = b.groupby('service').agg(mx=('latency', px.max))
df = a.merge(b, how='inner', left_on='service', right_on='service',
             suffixes=['', '_r'])
px.display(df, 'out')
"""


def test_join_span_says_rows_and_kernel(served):
    """The `join` frame's span carries both sides' rows, the rows out and
    the match kernel; with one agent the join is in the broker's half."""
    client, _store = served
    out = client.execute_script(JOIN_QUERY)["out"].to_pandas()
    (join,) = [s for s in trace.recent() if s.name == "join"]
    assert join.service == "broker"
    a = join.attributes
    assert a["rows_left"] == a["rows_right"] == a["rows_out"] == len(out) > 0
    assert a["kernel"] == "host_sort"


def test_compile_time_is_in_exec_stats_and_spans(served):
    """A cold query's jax trace + lower + backend-compile seconds are in
    its exec_stats and as `jax_compile` spans that sum to them, and out of
    the sample its routing decision observed; a warm repeat has neither."""
    client, _store = served
    q = QUERY.format(floor=7)  # a chain no other test compiles
    cold = client.execute_script(q)["out"].exec_stats["agents"]["pem0"]
    spans = [s for s in _agent_spans() if s.name == "jax_compile"]
    assert cold["compile_s"] > 0 and cold["compiles"] >= 1
    assert {s.attributes["kind"] for s in spans} == {
        "trace", "lower", "backend_compile"}
    assert all("cache_hit" in s.attributes for s in spans
               if s.attributes["kind"] == "backend_compile")
    assert sum(s.duration_ns for s in spans) / 1e9 == pytest.approx(
        cold["compile_s"], rel=0.05)
    # the router's sample is the chain's wall less the compile beside it
    (dec,) = cold["autotune"]
    (chain,) = _chains(_agent_spans())
    assert dec["compile_ms"] > 0
    assert dec["observed_ms"] == pytest.approx(
        max(chain.duration_ns / 1e6 - dec["compile_ms"], 0.0), abs=0.01)
    assert dec["observed_ms"] < 0.2 * chain.duration_ns / 1e6
    t_warm = time.time_ns()
    warm = client.execute_script(q)["out"].exec_stats["agents"]["pem0"]
    assert warm["compile_s"] == 0 and warm["compiles"] == 0
    assert warm["autotune"][0]["compile_ms"] == 0
    assert not [s for s in _agent_spans(t_warm) if s.name == "jax_compile"]


def test_telemetry_flush_is_persisted_with_the_next_query(served):
    """`telemetry_flush` is recorded after the write it measures: it is in
    the ring at once, under the query's `exec`, and in the store with the
    second query's flush; the broker's shipped rows are `telemetry_write`
    spans of the query that shipped them."""
    client, store = served
    client.execute_script(QUERY.format(floor=5))
    (flush,) = [s for s in _agent_spans() if s.name == "telemetry_flush"]
    (exec_,) = [s for s in _agent_spans() if s.name == "exec"]
    assert flush.parent_span_id == exec_.span_id
    assert flush.attributes["table"] == trace.SPANS_TABLE
    assert flush.attributes["rows"] >= 4
    stored = _all_span_rows({"pem0": store})
    assert not [r for r in stored if r["name"] == "telemetry_flush"]
    client.execute_script(QUERY.format(floor=5))
    stored = _all_span_rows({"pem0": store})
    assert [r["span_id"] for r in stored
            if r["name"] == "telemetry_flush"] == [flush.span_id]
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        writes = [s for s in _agent_spans() if s.name == "telemetry_write"]
        if {w.trace_id for w in writes} >= {exec_.trace_id}:
            break
        time.sleep(0.05)
    mine = [w for w in writes if w.trace_id == exec_.trace_id]
    assert trace.SPANS_TABLE in {w.attributes["table"] for w in mine}
    (root,) = [s for s in trace.recent() if s.name == "query"
               and s.trace_id == exec_.trace_id]
    assert {w.parent_span_id for w in mine} == {root.span_id}


# ------------------------------------------------------------ kernels by name


def _lower_groupby(fn_name, dtype=np.float64, backend=None, g=8):
    def lower(monkeypatch):
        from pixie_tpu.ops import groupby

        if backend:
            monkeypatch.setattr(groupby, "dispatch_backend", lambda: backend)
            assert groupby.agg_form(8192, g) == (
                "onehot" if g <= groupby.MATMUL_MAX_GROUPS else "onehot2")
        fn = getattr(groupby, fn_name)
        n = 8192
        gid, mask = np.zeros(n, np.int32), np.ones(n, bool)
        if fn_name == "masked_segment_count":
            return jax.jit(lambda i, m: fn(i, g, m)).lower(gid, mask)
        return jax.jit(lambda v, i, m: fn(v, i, g, m)).lower(
            np.ones(n, dtype), gid, mask)
    return lower


def _lower_sketch(method):
    def lower(monkeypatch):
        from pixie_tpu.ops.sketch import LogHistogram

        lh = LogHistogram()
        n, g = 8192, 8
        if method == "bin_index":
            return jax.jit(lh.bin_index).lower(np.ones(n, np.float64))
        return jax.jit(
            lambda h, i, b, m: getattr(lh, method)(h, i, b, m, g)).lower(
                np.zeros((g, lh.width), np.float32), np.zeros(n, np.int32),
                np.zeros(n, np.int32), np.ones(n, bool))
    return lower


def _lower_sorted_runs(monkeypatch):
    """The sorted aggregate's kernel: the sort of the keys, the gather of
    the values, a run reduction and the gather of the runs' results."""
    from pixie_tpu.ops import groupby

    def reduce(k, v, m):
        (_dead, ks), order = groupby.sort_order(
            groupby.run_sort_keys([k], m))
        runs, live = groupby.runs_of([ks], m.sum())
        vs = groupby.take_rows(v, order)
        mn = groupby.masked_segment_min(vs, runs, k.shape[0], live)
        _ends, front = groupby.sort_order((groupby.run_end_key(runs),))
        return groupby.take_rows({"k": ks, "mn": mn}, front)

    n = 2048
    return jax.jit(reduce).lower(np.zeros(n, np.int32), np.ones(n, np.int64),
                                 np.ones(n, bool))


def _lower_md_lookup(monkeypatch):
    from pixie_tpu.engine.eval import apply_lut

    return jax.jit(lambda lut, c: apply_lut(lut, c, -1)).lower(
        np.arange(16, dtype=np.int32), np.zeros(64, np.int32))


def _lower_window_agg(monkeypatch):
    """A chain kernel's agg step over a window key: the time-range mask
    and the window binning are in one program."""
    from pixie_tpu.engine.executor import ChainKernel, GroupKey
    from pixie_tpu.udf import registry
    from pixie_tpu.udf.udf import CountUDA

    kern = ChainKernel({"time_": DT.TIME64NS}, {}, [], registry, "time_")
    key = GroupKey(name="time_", kind="window", card=64,
                   out_dtype=DT.TIME64NS, width=1000,
                   key_sval=kern.ctx.sym["time_"], lut_name="t0")
    uda = CountUDA()
    step = kern.make_agg_step([key], [("n", uda, None)], 64, jit=False)
    n = 2048
    return jax.jit(step).lower(
        {"time_": np.arange(n, dtype=np.int64)}, np.int64(n), np.int64(0),
        np.int64(n), np.full((1,), n, np.int64),
        {"t0": np.zeros(1, np.int64)}, {"n": uda.init(64, None)})


SCOPES = {
    "px.groupby_sum": _lower_groupby("masked_segment_sum"),
    "px.groupby_count": _lower_groupby("masked_segment_count"),
    "px.groupby_min": _lower_groupby("masked_segment_min"),
    "px.groupby_max": _lower_groupby("masked_segment_max"),
    # the limb split exists in the MXU formulation only
    "px.int_limbs": _lower_groupby("masked_segment_sum", np.int64, "tpu"),
    # the factored one-hot of a dense space past MATMUL_MAX_GROUPS
    # (benchmarks/tracered.py reads device time by these scopes)
    "px.groupby_sum@onehot2": _lower_groupby(
        "masked_segment_sum", np.float64, "tpu", 2048),
    "px.groupby_count@onehot2": _lower_groupby(
        "masked_segment_count", None, "tpu", 2048),
    "px.int_limbs@onehot2": _lower_groupby(
        "masked_segment_sum", np.int64, "tpu", 2048),
    "px.sketch_bin": _lower_sketch("bin_index"),
    "px.sketch_update_gemm": _lower_sketch("_update_gemm"),
    "px.sketch_update_sorted": _lower_sketch("_update_sorted"),
    "px.sketch_update_segment": _lower_sketch("_update_segment"),
    # a program of its own (the executor calls it between two others)
    "px.sort_runs": lambda _mp: __import__(
        "pixie_tpu.ops.groupby", fromlist=["sort_order"]).sort_order.lower(
            (np.zeros(2048, np.int32),)),
    "px.compact_runs": _lower_sorted_runs,
    "px.md_lookup": _lower_md_lookup,
    "px.time_mask": _lower_window_agg,
    "px.window_bin": _lower_window_agg,
}


@pytest.mark.parametrize("scope", sorted(SCOPES))
def test_scope_is_in_the_lowered_kernel(scope, monkeypatch):
    text = SCOPES[scope](monkeypatch).as_text(debug_info=True)
    assert f"/{scope.partition('@')[0]}/" in text, scope


# ------------------------------------------------------------- span readers


def _reader(name: str):
    if METRICS_DIR not in sys.path:
        sys.path.insert(0, METRICS_DIR)
        sys.path.insert(0, os.path.dirname(METRICS_DIR))
    spec = importlib.util.spec_from_file_location(
        f"metrics_{name}", os.path.join(METRICS_DIR, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MS = 1_000_000


def _window(tr: trace.Tracer) -> dict:
    """Three queries of a hand-made window, 1000 ms long from t=1000 ms,
    after one warm-up query whose spans lie before it: two on the CPU arm
    (120 and 100 ms chains), one device explore (300 ms) that the broker
    hedged away, and the hedge's duplicate on the CPU arm (110 ms)."""
    def span(name, t0_ms, dur_ms, trace_id="", parent="", **attrs):
        sp = tr.start_span(name, trace_id=trace_id or None,
                           parent_span_id=parent, attributes=attrs or None,
                           start_ns=int(t0_ms * MS))
        tr.finish(sp, end_ns=int((t0_ms + dur_ms) * MS))
        return sp

    def query(t0_ms, exec_ms, chain_ms, engine, source, wait, wait_ms):
        root = span("exec", t0_ms, exec_ms)
        span("scan(t)->partial_agg", t0_ms + 2, chain_ms, root.trace_id,
             root.span_id, engine=engine, source=source, size_bucket="4^10",
             arm="cpu" if engine == "xla_cpu_chain" else "device")
        span(wait, t0_ms + 3, wait_ms, root.trace_id, root.span_id)
        span("telemetry_flush", t0_ms + exec_ms, 1.5, root.trace_id,
             root.span_id)
        span("telemetry_write", t0_ms + exec_ms + 2, 0.5, root.trace_id)
        return root

    query(500, 400, 390, "device_chain", "explore", "readback_wave", 380)
    span("jax_compile", 600, 50)  # the warm-up's: not the window's
    first = query(1000, 130, 120, "xla_cpu_chain", "static",
                  "cpu_chain_wait", 115)
    second = query(1200, 108, 100, "xla_cpu_chain", "static",
                   "cpu_chain_wait", 96)
    probe = query(1400, 310, 300, "device_chain", "explore",
                  "readback_wave", 290)
    # two waves under one exec overlap: the union counts, not the sum
    span("readback_wave", 1500, 100, probe.trace_id, probe.span_id)
    dup = query(1700, 118, 110, "xla_cpu_chain", "static", "cpu_chain_wait",
                105)
    span("jax_compile", 1705, 4, kind="trace")
    span("jax_compile", 1709, 6, kind="backend_compile")
    # the router changes its mind twice in the large bucket (the second
    # time under the guard's hold-off); the small bucket's arm is its own
    for t0, arm, source, bucket in ((1900, "device", "model", "4^10"),
                                    (1940, "device", "static", "4^4"),
                                    (1950, "cpu", "fallback", "4^10")):
        span("scan(u)->partial_agg", t0, 5, engine="np_partial", arm=arm,
             source=source, size_bucket=bucket)
    for t0, dur in ((1100, 0.4), (1300, 0.6), (1500, 0.5)):
        span("join", t0, dur, rows_left=110, rows_right=110,
             kernel="host_sort")
    # the third query is the flow graph (the first two the widget): its
    # probe's sorted aggregates on the device arm (the table's, and the
    # broker's regroup, which is no scan of the table) and the hedge's
    # duplicate on the CPU arm (no `engine`: the chain readers above do not
    # count them); each one's phases lie inside it, the host's key decode
    # follows it, and every query sends its result
    span("scan(t)->sorted_agg", 1410, 20, probe.trace_id, probe.span_id,
         arm="device", groups_out=3, d2h_bytes=4096)
    span("sort_reduce", 1412, 10, probe.trace_id, probe.span_id)
    span("compact_readback", 1423, 5, probe.trace_id, probe.span_id)
    span("remote(ch0)->map->sorted_agg", 1640, 10, probe.trace_id,
         arm="device", groups_out=2, d2h_bytes=1024)
    span("sort_reduce", 1641, 4, probe.trace_id)
    span("scan(t)->sorted_agg", 1710, 30, dup.trace_id, dup.span_id,
         arm="cpu", groups_out=3, d2h_bytes=4096)
    span("sort_reduce", 1712, 25, dup.trace_id, dup.span_id)
    for t0, dur, tid in ((1431, 6.0, probe.trace_id), (1741, 3.0, dup.trace_id)):
        span("key_decode", t0, dur, tid)
    for t0, dur, tid in ((1120, 2.0, first.trace_id),
                         (1300, 9.0, second.trace_id),
                         (1690, 4.0, probe.trace_id),
                         (1830, 7.0, dup.trace_id)):
        span("result_send", t0, dur, tid, chunks=1, bytes=2800000)
    widget = {"script": "net_flow_by_service", "start_time": 0}
    queries = [dict(widget, t0_unix_ns=1000 * MS, wall_ms=140.0),
               dict(widget, t0_unix_ns=1200 * MS, wall_ms=120.0),
               {"script": "conn_flow_graph", "start_time": 100 * MS,
                "t0_unix_ns": 1400 * MS, "wall_ms": 600.0}]
    return {"queries": queries, "window_s": 1.0}


READERS = {
    "cpu_chain_ms": 110.0,             # median of 120, 100, 110
    "device_chain_ms": 300.0,          # the window's one device chain
    # exec minus the union of its waits: 15, 12, 20, 13 -> the lower median
    "agent_host_ms": 13.0,
    "router_probe_exec_ms": 300.0,     # the warm-up's explore is outside
    "router_probe_time_share": 30.0,   # 300 ms of 1000
    "compile_ms_in_window": 10.0,
    "telemetry_ms_per_query": 8.0 / 3,  # four flushes + writes, three queries
    "router_fallback_share": 100.0 / 7,  # one of the window's seven chains
    # cpu, cpu, (explore), cpu, device, cpu in 4^10; 4^4 stays on its arm
    "router_arm_flips": 2.0,
    "join_ms": 0.5,
    # of the flow-graph query's two traces (the probe and the hedge's
    # duplicate) alone: the widget's spans are not read
    "sorted_agg_host_ms": 9.0,         # two key decodes, one query
    "d2h_bytes_per_query": 5120.0,     # its two device-arm chains
    "result_send_ms": 4.0,             # of 4 and 7; the widget sent 2 and 9
    # the table's and the regroup's on the device arm and the hedge's
    # duplicate on the CPU arm: 10 + 4 + 25, one query
    "sort_reduce_ms": 39.0,
}
#: the readers of the flow graph's own spans
FLOW_GRAPH_READERS = ("sorted_agg_host_ms", "d2h_bytes_per_query",
                      "result_send_ms", "sort_reduce_ms")


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_computes_from_hand_made_spans(name, fresh_ring):
    run = _window(trace.Tracer("pem0"))
    assert _reader(name).read(run) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_none_when_the_ring_wrapped(name, monkeypatch, capsys):
    monkeypatch.setattr(trace, "_RING", collections.deque(maxlen=16))
    monkeypatch.setattr(trace, "_RING_SEQ", itertools.count())
    run = _window(trace.Tracer("pem0"))
    assert trace.ring_dropped() > 0
    assert _reader(name).read(run) is None
    assert "let go" in capsys.readouterr().err


@pytest.mark.parametrize("name", FLOW_GRAPH_READERS)
def test_flow_graph_reader_reads_nothing_in_a_window_of_widgets(name,
                                                                fresh_ring):
    """The same spans under a window none of whose queries is the flow
    graph (`net_flow_1chip`'s, or the parent's under another traffic): the
    reader has nothing to read, whatever sorted aggregates, key decodes and
    sends the window's spans hold."""
    run = _window(trace.Tracer("pem0"))
    assert _reader(name).read(run) is not None
    for q in run["queries"]:
        q["script"] = "net_flow_by_service"
    assert _reader(name).read(run) is None


def test_flow_graph_readers_need_the_sorted_aggregates_own_spans(fresh_ring):
    """A program that serves the flow graph with no `groups_out`,
    `d2h_bytes` or `sort_reduce` (the parent sorts on the host under one
    unobserved span): three readers are silent, `result_send_ms` reads the
    flow graph's sends."""
    tr = trace.Tracer("pem0")
    run = _window(tr)
    for s in trace.recent():
        for k in ("groups_out", "d2h_bytes"):
            s.attributes.pop(k, None)
        if s.name == "sort_reduce":
            s.name = "host_sort"
    assert [_reader(n).read(run) for n in FLOW_GRAPH_READERS] == [
        None, None, 4.0, None]


def test_readers_read_nothing_where_there_is_nothing(monkeypatch, fresh_ring):
    """An empty window, and a program without the ring: the benchmark lays
    these readers over the parent commit too."""
    run = _window(trace.Tracer("pem0"))
    for name in READERS:
        assert _reader(name).read({"queries": [], "window_s": 1.0}) is None
    monkeypatch.delattr(trace, "recent")
    for name in READERS:
        assert _reader(name).read(run) is None
