"""The deployment `pem_pgsql_48m` (benchmarks/configs/pem_pgsql_48m.json:
one PEM's pgsql_events read by Pixie's Postgres view) and its two scripts,
`pgsql_flow_graph` (px/pgsql_flow_graph) and `pgsql_pod_let`
(px/pgsql_stats `pod_pgsql_let`), on the CPU: the file states upstream's
relation and its 48 MiB budget; the generator at full size fills it with
no expiry, from the cluster's 440 pod addresses to the node's four Postgres
pods, ~41k live (window, edge) groups in the `-5m` range; the served path
(Broker + one Agent on loopback, the benchmark's own generator and loader)
equals the plain reference (benchmarks/references/pgsql_flow.py) at a small
size on either arm and with the kernels traced for the TPU (the sorted
aggregate's sketch in the tiles kernel, interpreted), and the sorted
chain's span names its sketch's form and finalizes it on the device; each
control fails its own number; a FLOAT64 column's group sums on the TPU are
fixed point, each group held to its own size; the packed key and the window
sort as the keys apart; the view's readers compute from hand-made spans and read
nothing where there is nothing."""
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from pixie_tpu import trace
from tests.test_service_map_config import route
from tests.test_trace_layers import MS, _reader, fresh_ring  # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (os.path.join(BENCH, "metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402  benchmarks/compare.py
import data as datagen  # noqa: E402  benchmarks/data.py
import traffic  # noqa: E402  benchmarks/traffic.py
from tests.test_net_flow_config import serve  # noqa: E402

ROWS = 20_000
SEEDS = [3, 2147483659]
PODS, SERVERS = 440, 4
SCRIPTS = ("pgsql_flow_graph", "pgsql_pod_let")
#: what the sketch state of one histogram takes: 514 f32 bins
HIST_BYTES = 514 * 4
WINDOW_NS = 10 * datagen.SEC


def full_config() -> dict:
    with open(os.path.join(BENCH, "configs", "pem_pgsql_48m.json")) as f:
        return json.load(f)


def small_config() -> dict:
    config = full_config()
    config["rows"] = ROWS
    for t in config["tables"]:
        t.pop("max_bytes", None)
    return config


def columns(config: dict) -> dict:
    return {c["name"]: c for c in config["tables"][0]["columns"]}


def start_ns(config: dict, offset_s: int) -> int:
    return int(config["time_base_ns"]) + offset_s * datagen.SEC


def test_file_states_the_relation_and_its_budget():
    """The 9 columns of upstream's pgsql_events (collect/schemas.py) at 52 B
    a row, the 48 MiB share of each table but http_events, and `rows` the
    most whole batches under it; nothing reduced; the client addresses are
    the service map's 440 pod IPs letter for letter."""
    from pixie_tpu.collect.schemas import SCHEMAS

    config = full_config()
    (table,) = config["tables"]
    rel = SCHEMAS["pgsql_events"]
    assert [(c["name"], c["type"]) for c in table["columns"]] == [
        (c.name, c.data_type.name) for c in rel]
    width = sum(c["bytes"] for c in table["columns"])
    assert width == 52
    assert table["max_bytes"] == 48 << 20 == 1342177280 * 3 // 5 // 16
    batch = table["batch_rows"] * width
    assert config["rows"] == table["max_bytes"] // batch * table["batch_rows"]
    assert config["rows"] == 917_504 and config["reduced"] == {}
    with open(os.path.join(BENCH, "configs",
                           "pem_http_edges_512m.json")) as f:
        edges = json.load(f)
    addr = columns(config)["remote_addr"]
    assert addr == {c["name"]: c for c in edges["tables"][0]["columns"]
                    }["remote_addr"]
    assert config["metadata"] == edges["metadata"]
    assert columns(config)["upid"]["card"] == SERVERS


def test_generator_at_full_size_fills_the_budget():
    """917,504 rows load under the 48 MiB budget with nothing expired;
    requests come from all 440 pod addresses to the four Postgres pods, and
    a `-5m` query's range holds ~41k live (10 s window, edge) groups in 30
    windows, over half the table."""
    config = full_config()
    tables = datagen.generate(config, 2147483659)
    ev = tables["pgsql_events"]
    assert len(ev["time_"]) == 917_504
    assert len(np.unique(ev["remote_addr"])) == PODS
    assert sorted(np.unique(ev["upid"])) == list(range(SERVERS))
    a = int(np.searchsorted(ev["time_"], start_ns(config, 300)))
    assert a == 458_753
    window = ev["time_"][a:] // WINDOW_NS
    groups = np.unique((window - window[0]) * (PODS * SERVERS)
                       + ev["remote_addr"][a:] * SERVERS + ev["upid"][a:])
    assert 38_000 < len(groups) < 46_000
    assert len(np.unique(window)) == 30
    store = datagen.load_store(config, tables)
    st = store.table("pgsql_events").stats()
    assert (st["rows_written"], st["expired_batches"], st["batches"],
            st["bytes"]) == (917_504, 0, 14, 47_710_208)


def _chains(since: int) -> list:
    return [s for s in trace.recent(since) if s.service == "pem0"
            and "engine" in s.attributes]


@pytest.mark.parametrize("backend", ["cpu", "device", "tpu"])
@pytest.mark.parametrize("seed", SEEDS)
def test_served_path_equals_reference(seed, backend, monkeypatch):
    """Both scripts through the Broker and one Agent equal the reference on
    the router's two arms and with the kernels traced for the TPU.  The
    flow graph's first aggregate is the sorted one: its chain span names
    the form its sketch took over the runs' slots (the tiles kernel past
    4,096 groups on the TPU, the scatter on XLA-CPU); the agent is the
    channel's sole producer, so that chain finalizes its sketch where it
    runs and reads back none of the [slots, 514] f32 state, as the chart's
    dense chain does."""
    route(monkeypatch, backend)
    config = small_config()
    tables = datagen.generate(config, seed)
    start = start_ns(config, 301)
    for name in SCRIPTS:
        script = traffic.load_script(name)
        since = time.time_ns()
        out = serve(config, tables,
                    script["text"].replace("__START_TIME__", str(start)))
        engines = out.exec_stats["agents"]["pem0"]["device"]["engines"]
        assert list(engines) == [
            "xla_cpu_chain" if backend == "cpu" else "device_chain"]
        mod = compare.load_reference(script["reference"])
        ref = mod.reference(tables, config, script, start)
        numbers = mod.compare(out.to_pandas(), ref, config)
        assert {"groups_unmatched", "rate_rel", "p50_rel", "p90_rel",
                "p99_rel"} <= set(numbers)
        assert all(v <= lim for v, lim in numbers.values()), (name, numbers)
        (chain,) = [c for c in _chains(since) if "agg_form" in c.attributes]
        a = chain.attributes
        form = "tiles" if backend == "tpu" else "segment"
        if name == "pgsql_flow_graph":
            assert len(ref[0]) > 1000 and a["agg_form"] == "sorted"
            slots = 1 << (a["groups_out"] - 1).bit_length()
            assert a["groups_out"] > 4096 and slots == 8192
            assert (a["sketch_form"], a["sketch_state_bytes"],
                    a["sketch_device_finalized"]) == (form, 0, 1)
        else:
            assert len(ref[0]) == 120 and a["groups"] > 4096
            assert (a["sketch_form"], a["sketch_state_bytes"]) == (form, 0)


CONTROLS = [
    ("coarse_sketch", "pgsql_flow_graph", "p50_rel"),
    ("coarse_sketch", "pgsql_pod_let", "p99_rel"),
    ("unwindowed", "pgsql_flow_graph", "rate_rel"),
    ("unwindowed", "pgsql_pod_let", "groups_unmatched"),
    ("source_unresolved", "pgsql_flow_graph", "groups_unmatched"),
    ("f32_rates", "pgsql_flow_graph", "rate_rel"),
    ("f32_rates", "pgsql_pod_let", "rate_rel")]


@pytest.mark.parametrize("stand_in,name,number", CONTROLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(seed, stand_in, name, number):
    """Each control, the reference with one thing lowered, passes none of
    its own number's limit, where the reference itself passes all."""
    config = small_config()
    tables = datagen.generate(config, seed)
    start = start_ns(config, 302)
    script = traffic.load_script(name)
    mod = compare.load_reference(script["reference"])
    assert stand_in in mod.CONTROLS and len(mod.CONTROLS) == 4
    ref = mod.reference(tables, config, script, start)
    same = mod.compare(ref[0], ref, config)
    assert all(v <= lim for v, lim in same.values()), same
    ctl, _ = mod.reference(tables, config, script, start, stand_in)
    value, limit = mod.compare(ctl, ref, config)[number]
    assert value > limit


# ------------------------------------------- a FLOAT64 column's group sums

def _values(case: str, rng, n: int) -> np.ndarray:
    if case == "rates":  # per-window counts over the 10 s window
        return rng.integers(1, 1000, n) / WINDOW_NS
    if case == "cancelling":
        return rng.normal(0.0, 1e5, n)
    return rng.lognormal(0.0, 3.0, n)  # six decades


@pytest.mark.parametrize("case,n,groups,rtol", [
    ("rates", 1 << 16, 2048, 1e-12), ("cancelling", 1 << 13, 100, 1e-12),
    ("wide", 1 << 17, 300, 1e-9)])
def test_float64_group_sums_are_fixed_point_on_the_tpu(monkeypatch, case, n,
                                                       groups, rtol):
    """On the TPU's GEMM forms a FLOAT64 column sums in INT64 fixed point,
    its truncation's residuals through the hi/lo split: 1e-12 of the sum
    (1e-9 where the values span six decades), where the hi/lo split of the
    values alone is off by over 1e-8 (a float32 computation's error);
    px.sum and px.mean over such a column take it, a mean of integers keeps
    the hi/lo split."""
    from pixie_tpu.ops import groupby as gb
    from pixie_tpu.udf.udf import MeanUDA, SumUDA

    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    assert gb.agg_form(n, groups) in ("onehot", "onehot2")
    rng = np.random.default_rng(5)
    v = _values(case, rng, n)
    gid = rng.integers(0, groups, n).astype(np.int32)
    mask = rng.random(n) < 0.9
    exact = np.zeros(groups)
    np.add.at(exact, gid[mask], v[mask])
    fixed, hilo = jax.jit(lambda v, g, m: (
        gb.masked_segment_fsum(v, g, groups, m),
        gb.masked_segment_sum(v, g, groups, m)))(v, gid, mask)
    err = np.abs(np.asarray(fixed) - exact) / np.abs(exact)
    assert err.max() < rtol
    assert (np.abs(np.asarray(hilo) - exact) / np.abs(exact)).max() > 1e-8
    mean = MeanUDA().update(MeanUDA().init(groups, None), gid, v, mask,
                            groups)
    assert np.asarray(mean["sum"]).tobytes() == np.asarray(fixed).tobytes()
    total = SumUDA().update(SumUDA().init(groups, np.float64), gid, v, mask,
                            groups)
    assert np.asarray(total).tobytes() == np.asarray(fixed).tobytes()
    ints = (v * 1e3).astype(np.int64)
    mean = MeanUDA().update(MeanUDA().init(groups, None), gid, ints, mask,
                            groups)
    assert np.asarray(mean["sum"]).tobytes() == np.asarray(
        gb.masked_segment_sum(ints.astype(np.float64), gid, groups,
                              mask)).tobytes()


@pytest.mark.parametrize("n,scales", [
    (1 << 20, (0.1, 1e7, 1e-2, 1e-8)), (1 << 12, (0.1, 1e12, 1e3, 1e-3))])
def test_float64_group_sums_hold_each_group_to_its_own_size(monkeypatch, n,
                                                           scales):
    """A group's sum is held to its own size, not to the column's largest
    magnitude: groups of 0.1s, of the column's maximum, of 1e-9 of it and
    of 1e-15 of it (under the fixed point's step, so the residuals carry
    them whole) each sum within F64_SUM_RTOL of an exact sum, and those at
    or above the step within 1e-9, whatever the groups beside them hold."""
    from pixie_tpu.ops import groupby as gb

    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    groups = len(scales)
    assert gb.agg_form(n, groups) == "onehot"
    rng = np.random.default_rng(13)
    gid = rng.integers(0, groups, n).astype(np.int32)
    v = np.asarray(scales)[gid] * rng.uniform(0.5, 1.0, n)
    mask = rng.random(n) < 0.9
    exact = np.zeros(groups)
    np.add.at(exact, gid[mask], v[mask])
    got = np.asarray(jax.jit(lambda v, g, m: gb.masked_segment_fsum(
        v, g, groups, m))(v, gid, mask))
    err = np.abs(got - exact) / exact
    assert err.max() < gb.F64_SUM_RTOL
    assert err[:3].max() < 1e-9


def test_float64_group_sums_of_zeros_and_of_nan(monkeypatch):
    """A column of zeros sums to zeros; one holding NaN sums to NaN in
    every group, as the GEMM's zeros times it make the hi/lo split's."""
    from pixie_tpu.ops import groupby as gb

    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    n, groups = 1 << 13, 4
    gid = np.arange(n, dtype=np.int32) % groups
    mask = np.ones(n, bool)
    fsum = jax.jit(lambda v: gb.masked_segment_fsum(v, gid, groups, mask))
    assert np.asarray(fsum(np.zeros(n))).tolist() == [0.0] * groups
    v = np.ones(n)
    v[5] = np.nan
    assert np.isnan(np.asarray(fsum(v))).all()


# ------------------------------------------------------ the view's readers

#: the three readers of the Postgres view's own spans and kernel
VIEW_READERS = ("pgsql_state_bytes_per_query", "pgsql_finalize_ms",
                "pgsql_tiles_roofline")
#: the runs' slots of the hand-made flow graphs' sorted aggregates
SLOTS = 65536


def _view_window(tr: trace.Tracer) -> dict:
    """Two flow graphs and a per-pod chart, from t=1000 ms, each a trace of
    an `exec` root and its chain spans (the flow graph's sorted aggregate
    with 41,000 groups out, its sketch in the tiles kernel, its state read
    back; the chart's dense chain at 65,536 slots, finalized where it ran)
    and the broker's `sketch_finalize` spans; the trace of the traced
    span's device: the tiles kernel in two entries, 20 ms in all."""
    def span(name, t0_ms, dur_ms, trace_id="", parent="", on=tr, **attrs):
        sp = on.start_span(name, trace_id=trace_id or None,
                           parent_span_id=parent, attributes=attrs or None,
                           start_ns=int(t0_ms * MS))
        on.finish(sp, end_ns=int((t0_ms + dur_ms) * MS))
        return sp

    broker = trace.Tracer("broker")
    for t0, sorted_chains, finals in ((1000, (SLOTS * HIST_BYTES,), (40, 10)),
                                      (1400, (3_000_000, 1_000_000), (30,))):
        root = span("exec", t0, 200)
        for i, nbytes in enumerate(sorted_chains):
            span("scan(pgsql_events)->sorted_agg", t0 + 1 + i, 150,
                 root.trace_id, root.span_id, engine="device_chain",
                 arm="device", agg_form="sorted", groups_out=41000,
                 sketch_form="tiles", sketch_state_bytes=nbytes)
        for i, ms in enumerate(finals):
            span("sketch_finalize", t0 + 210 + i * 50, ms, root.trace_id,
                 on=broker, groups=41000,
                 sketch_state_bytes=41000 * HIST_BYTES)
    root = span("exec", 1800, 200)
    span("scan(pgsql_events)->partial_agg", 1801, 150, root.trace_id,
         root.span_id, engine="device_chain", arm="device", groups=SLOTS,
         agg_form="onehot2", sketch_form="tiles", sketch_state_bytes=0,
         sketch_device_finalized=1)
    span("sketch_finalize", 2010, 2, root.trace_id, on=broker, groups=120,
         sketch_state_bytes=0)
    config = full_config()
    digest = {"engine": "device"}
    start = start_ns(config, 300)
    queries = [{"script": s, "start_time": start, "digest": digest,
                "t0_unix_ns": t0 * MS, "wall_ms": 300.0}
               for s, t0 in (("pgsql_flow_graph", 1000),
                             ("pgsql_flow_graph", 1400),
                             ("pgsql_pod_let", 1800))]
    scripts = {s: {"table": "pgsql_events"} for s in SCRIPTS}
    device = {"busy_s": 0.5, "lo_unix_ns": 900 * MS, "hi_unix_ns": 2200 * MS,
              "device_ops": [["fusion.3", 0.1], ["sort.5", 0.03],
                             ["px_sketch_tiles.1", 0.015],
                             ["px_sketch_tiles", 0.005]]}
    return {"queries": queries, "window_s": 1.2, "config": config,
            "scripts": scripts, "trace": device,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


@pytest.mark.parametrize("hi_ms,shares", [(2200, 4.0), (1876, 3.5)])
def test_view_readers_compute_from_hand_made_spans(fresh_ring, hi_ms,
                                                   shares):
    """The chart's spans set no median: the state bytes a flow graph read
    back (the [65,536, 514] state, and 3 + 1 MB: the lower median), its
    summed finalizes (50 and 30 ms); the tiles kernel's 20 ms against the
    bytes of four updates (three sorted chains and the chart's, each of the
    458,751 rows from 300 s on: `latency` and a 4-byte key, and the [65,536,
    544] f32 state once) at 819 GB/s, the sort's 30 ms outside.  A traced
    span that ends half way through the chart's chain counts half of its
    bytes."""
    run = _view_window(trace.Tracer("pem0"))
    run["trace"]["hi_unix_ns"] = hi_ms * MS
    one = 458_751 * (8 + 4) + SLOTS * 544 * 4
    got = [_reader(n).read(run) for n in VIEW_READERS]
    assert got == pytest.approx([4_000_000.0, 30.0,
                                 100.0 * shares * one / 819e9 / 0.020])


@pytest.mark.parametrize("name", VIEW_READERS)
def test_view_readers_read_nothing_where_there_is_nothing(name, monkeypatch,
                                                          fresh_ring):
    """An empty window, a window of charts alone (for the two per-query
    medians), a program without the ring or without the kernel (the
    parent, laid under these readers by the benchmark): nothing to read,
    and no error."""
    run = _view_window(trace.Tracer("pem0"))
    reader = _reader(name)
    assert reader.read(run) is not None
    assert reader.read(dict(run, queries=[], trace=None)) is None
    if name != "pgsql_tiles_roofline":
        charts = [dict(q, script="pgsql_pod_let") for q in run["queries"]]
        assert reader.read(dict(run, queries=charts)) is None
    if name == "pgsql_tiles_roofline":
        run["trace"]["device_ops"] = [["fusion.3", 0.1], ["sort.4", 0.3]]
        assert reader.read(run) is None
        run = _view_window(trace.Tracer("pem0"))
    monkeypatch.delattr(trace, "recent")
    assert reader.read(run) is None


@pytest.mark.parametrize("seed", SEEDS)
def test_packed_key_and_window_sort_as_the_keys_apart(seed):
    """The flow graph's sorted aggregate sorts two keys: its keys of known
    cardinality (two 440-address codes and a flag) packed into one id whose
    masked rows take the sentinel, and the 64-bit window.  Its live rows
    come first, in the order of one sort of the keys apart behind a
    live-first flag, and the packed ids split back into the codes."""
    from pixie_tpu.ops import groupby as gb

    rng = np.random.default_rng(seed)
    n, cards = 1 << 14, [512, 512, 2]
    codes = [rng.integers(0, 440, n).astype(np.int32),
             rng.integers(0, 440, n).astype(np.int32),
             rng.integers(0, 2, n).astype(np.int32)]
    window = (rng.integers(0, 30, n) * WINDOW_NS).astype(np.int64)
    mask = rng.random(n) < 0.7
    packed, total = gb.combine_codes(codes, cards, np.int32)
    got, _ = gb.sort_order(gb.run_sort_keys([packed, window], mask,
                                            sentinel=total))
    want, _ = gb.sort_order(gb.run_sort_keys([*codes, window], mask))
    live = int(mask.sum())
    split = gb.split_codes(np.asarray(got[0])[:live], cards)
    for g, w in zip([*split, np.asarray(got[1])[:live]], want[1:]):
        assert g.tolist() == np.asarray(w)[:live].tolist()
    assert (np.asarray(got[0])[live:] == total).all()
