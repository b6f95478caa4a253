"""The deployment `pem_http_4x512m` (benchmarks/configs/pem_http_4x512m.json)
behind one mesh agent, on the CPU's virtual devices at a few whole batches:
the file's arithmetic; the served path (Broker + `Agent(n_devices=4)` on
loopback, the benchmark's own generator and loader) against the plain
reference and against the same store behind an agent with no mesh; what the
mesh says of itself on its spans, and that no router decides a chain the
mesh serves; `n_devices` as the mesh's width; more than one feed a query;
the four readers the cell adds; and the planted fault on the mesh, through
`benchmarks/tests/test_correct.py`'s own `drive`."""
import contextlib
import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (os.path.join(BENCH, "metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import compare  # noqa: E402  benchmarks/compare.py
import data as datagen  # noqa: E402  benchmarks/data.py
import stats as st  # noqa: E402  benchmarks/stats.py
import traffic  # noqa: E402  benchmarks/traffic.py

from pixie_tpu import flags, trace  # noqa: E402
from pixie_tpu.engine import autotune  # noqa: E402
from pixie_tpu.engine import executor as executor_mod  # noqa: E402
from pixie_tpu.metadata import state as mdstate  # noqa: E402
from pixie_tpu.ops import groupby  # noqa: E402

BATCH = 65_536
#: the cut table: seven whole batches.  One feed of them is a 524,288-row
#: bucket whose fourth shard is half full; at a feed target of four batches
#: they are a full 262,144-row feed and a remainder of three shards full and
#: the fourth empty, which is the shape of the cell's own two feeds
BATCHES = 7
SEED = 2147483659
SCRIPTS = ["http_by_status", "http_windowed"]
STARTS_S = [0, 2]
#: bytes a row of http_events takes in the store: TIME64NS and nine INT64 at
#: 8, the coded upid and eight coded strings at 4
ROW_BYTES = 10 * 8 + 9 * 4


def config_file(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def small_config(batches: int = BATCHES) -> dict:
    config = config_file("pem_http_4x512m")
    config["rows"] = batches * BATCH
    return config


def text_of(script: dict, config: dict, start_s: int) -> tuple:
    start = int(config["time_base_ns"]) + start_s * datagen.SEC
    return start, script["text"].replace("__START_TIME__", str(start))


@contextlib.contextmanager
def serving(store, n_devices):
    """One Broker and one Agent `pem0` of `n_devices` over `store`, with
    tracing and the router's model on (the model fresh) and standing views
    off, so that every query runs its chain.  Yields the client."""
    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker
    from pixie_tpu.services.client import Client

    wanted = {"PL_TRACING_ENABLED": True, "PX_AUTOTUNE": True,
              "PL_MATVIEW_ENABLED": False}
    before = {k: flags.get(k) for k in wanted}
    for k, v in wanted.items():
        flags.set_for_testing(k, v)
    autotune.MODEL.reset_for_testing()
    broker = Broker(hb_expiry_s=120.0, query_timeout_s=120.0).start()
    agent = Agent("pem0", "127.0.0.1", broker.port, store=store,
                  heartbeat_s=2.0, n_devices=n_devices).start()
    client = Client("127.0.0.1", broker.port, timeout_s=120.0)
    try:
        yield client
    finally:
        client.close()
        agent.stop()
        broker.stop()
        autotune.MODEL.reset_for_testing()
        for k, v in before.items():
            flags.set_for_testing(k, v)


def ask(client, script: dict, config: dict, start_s: int) -> dict:
    """One query: its answer, its agent's stats and the agent's spans."""
    start, text = text_of(script, config, start_s)
    t0 = time.time_ns()
    out = client.execute_script(text)["out"]
    spans = [s for s in trace.recent(t0) if s.service == "pem0"
             and s.start_ns >= t0]
    return {"start": start, "out": out, "df": out.to_pandas(),
            "agent": out.exec_stats["agents"]["pem0"], "spans": spans}


def chains(spans: list) -> list:
    return [s for s in spans if "engine" in s.attributes]


@pytest.fixture(scope="module")
def loaded():
    """The cut table's arrays and one store of them, under the file's 440
    pods; the process's metadata state is put back afterwards."""
    old = mdstate.global_manager()
    config = small_config()
    tables = datagen.generate(config, SEED)
    datagen.install_metadata(config)
    store = datagen.load_store(config, tables)
    yield config, tables, store
    mdstate.set_global_manager(old)


@pytest.fixture(scope="module")
def served(loaded):
    """Every answer of both scripts at two starts, from the mesh agent and
    from an agent with no mesh over the same store, and what the router's
    model held after the mesh agent's queries."""
    config, _tables, store = loaded
    scripts = {n: traffic.load_script(n) for n in SCRIPTS}
    out = {"scripts": scripts}
    for side, n_devices in (("mesh", 4), ("plain", 1)):
        with serving(store, n_devices) as client:
            out[side] = {(n, s): ask(client, scripts[n], config, s)
                         for n in SCRIPTS for s in STARTS_S}
            out[side + "_model"] = autotune.MODEL.snapshot()
    return out


# ------------------------------------------------------- (a) the file


def test_four_pems_fill_is_the_files_rows():
    one, four = config_file("pem_http_512m"), config_file("pem_http_4x512m")
    (t1,), (t4,) = one["tables"], four["tables"]
    assert sum(c["bytes"] for c in t4["columns"]) == ROW_BYTES == 116
    budget = one["published"]["http_events_bytes"]
    assert budget == t1["max_bytes"] == 512 << 20
    assert four["published"]["http_events_bytes"] == budget
    # a PEM keeps 70 whole batches under its own 512 MiB, and not a 71st
    assert 70 * BATCH * ROW_BYTES <= budget < 71 * BATCH * ROW_BYTES
    assert four["rows"] == 4 * 70 * BATCH == 18_350_080 == 4 * one["rows"]
    assert t4["max_bytes"] == 4 * t1["max_bytes"] == 2 << 30
    assert four["rows"] * ROW_BYTES <= t4["max_bytes"]
    assert t4["batch_rows"] == t1["batch_rows"] == BATCH
    assert four["pems"] == four["mesh_devices"] == 4
    assert list(four["reduced"]) == ["pems"]
    assert four["metadata"]["pods"] == 4 * one["metadata"]["pods"] == 440


def test_the_two_files_differ_by_the_deployment_alone():
    one, four = config_file("pem_http_512m"), config_file("pem_http_4x512m")
    changed = {"name", "source", "deployment", "published", "rows",
               "rows_note", "reduced", "assumed", "metadata", "tables"}
    added = {"pems", "pems_note", "mesh_devices"}
    assert set(four) - set(one) == added and set(one) <= set(four)
    assert {k for k in one if one[k] != four[k]} == changed
    assert four["guarantees"] == one["guarantees"]
    (t1,), (t4,) = one["tables"], four["tables"]
    assert t4["columns"] == t1["columns"]  # every width, generator, skew
    assert {k for k in t1 if t1[k] != t4[k]} == {"max_bytes"}
    assert {k for k in one["metadata"]
            if one["metadata"][k] != four["metadata"][k]} == {"pods"}
    assert set(one["assumed"][1:]) <= set(four["assumed"])


# ------------------------------------- (b) answers, (c) what the spans say


@pytest.mark.parametrize("start_s", STARTS_S)
@pytest.mark.parametrize("name", SCRIPTS)
def test_mesh_answers_equal_the_reference_and_one_device(name, start_s,
                                                         loaded, served):
    config, tables, _store = loaded
    script = served["scripts"][name]
    got, plain = served["mesh"][name, start_s], served["plain"][name, start_s]
    mod = compare.load_reference(script["reference"])
    ref = mod.reference(tables, config, script, got["start"])
    in_range = int((tables["http_events"]["time_"] >= got["start"]).sum())
    assert (in_range < config["rows"]) == (start_s > 0)
    for side in (got, plain):
        numbers = mod.compare(side["df"], ref, config)
        assert all(v <= lim for v, lim in numbers.values()), numbers
        assert numbers["groups_unmatched"] == numbers["cnt_mismatch"] == (0, 0)
    keys = ref[1]
    a = got["df"].sort_values(keys).reset_index(drop=True)
    b = plain["df"].sort_values(keys).reset_index(drop=True)
    assert len(a) == len(b) == len(ref[0])
    for col in keys + ["cnt"]:
        np.testing.assert_array_equal(a[col].to_numpy(), b[col].to_numpy())
    np.testing.assert_allclose(a["avg_lat"].to_numpy(),
                               b["avg_lat"].to_numpy(),
                               rtol=config["guarantees"]["mean_rtol"])
    for q in ("p50", "p99"):
        if q in a:  # the same bins of the same sketch
            np.testing.assert_array_equal(a[q].to_numpy(), b[q].to_numpy())
    assert "device_chain" in got["agent"]["device"]["engines"]


def test_a_chain_the_mesh_serves_takes_no_routing_decision(served):
    for q in served["mesh"].values():
        (chain,) = [c for c in chains(q["spans"])
                    if c.name.endswith("partial_agg")]
        attrs = chain.attributes
        assert attrs["engine"] == "device_chain" and attrs["arm"] == "device"
        assert attrs["mesh_devices"] == 4 and attrs["spmd_feeds"] == 1
        # 524,288-row bucket: three shards of 131,072 and one of 65,536
        assert attrs["shard_skew"] == pytest.approx(131072 * 4 / (7 * BATCH),
                                                    abs=1e-4)
        assert attrs["rows"] > 0 and attrs["feed_rows"] == 8 * BATCH
        for key in ("source", "plan_class", "size_bucket", "decision_n",
                    "guard_trips"):
            assert key not in attrs, key
        assert not any(c.attributes.get("arm") == "cpu"
                       or "source" in c.attributes
                       for c in chains(q["spans"]))
        assert [s.name for s in q["spans"]].count("mesh_merge") == 1
        assert not q["agent"].get("autotune")
        assert q["agent"]["spmd_feeds"] == 1
        assert len(q["agent"]["shard_rows"]) == 4
        digest = st.digest(q["out"].exec_stats)
        assert digest["engine"] == "device" and digest["arm"] is None
        assert digest["source"] is None and digest["decisions"] == []
    recs = [{"digest": st.digest(q["out"].exec_stats)}
            for q in served["mesh"].values()]
    st.mark_probes(recs)
    assert not any(r["digest"]["probe"] for r in recs)
    # nothing was folded into the router's model; the agent with no mesh
    # routed the same chains and priced them
    assert served["mesh_model"].get(autotune.GATE_CPU_CROSSOVER, {}).get(
        "samples", 0) == 0
    assert served["plain_model"][autotune.GATE_CPU_CROSSOVER]["samples"] > 0
    for q in served["plain"].values():
        assert all("mesh_devices" not in c.attributes
                   and "spmd_feeds" not in c.attributes
                   for c in chains(q["spans"]))
        assert "mesh_merge" not in [s.name for s in q["spans"]]


@pytest.fixture(scope="module")
def lookup_440():
    """One by-status query over a store of its own under the file's 440
    pods, served by the mesh agent with its kernels traced for XLA-CPU and
    then for the TPU (the kernel cache cleared in between, so that neither
    is handed the other's program)."""
    old = mdstate.global_manager()
    config = small_config(batches=1)
    tables = datagen.generate(config, SEED + 1)
    script = traffic.load_script("http_by_status")
    out = {"config": config, "tables": tables, "script": script}
    with pytest.MonkeyPatch.context() as mp:
        try:
            datagen.install_metadata(config)
            store = datagen.load_store(config, tables)
            for backend in ("cpu", "tpu"):
                mp.setattr(groupby, "dispatch_backend", lambda b=backend: b)
                executor_mod._KERNEL_CACHE.clear()
                with serving(store, 4) as client:
                    out[backend] = ask(client, script, config, 1)
        finally:
            executor_mod._KERNEL_CACHE.clear()
            mdstate.set_global_manager(old)
    return out


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_lookup_over_440_pods_on_the_mesh(backend, lookup_440):
    """A cluster's K is past `LUT_SELECT_MAX` and under `LUT_BLOCKED_MAX`:
    a program traced for the TPU applies it as blocks of compare-selects
    under one loop, where XLA-CPU gathers; the answers are the same, and
    the reference's."""
    from pixie_tpu.engine import eval as ev

    config, script = lookup_440["config"], lookup_440["script"]
    assert (ev.LUT_SELECT_MAX < config["metadata"]["pods"]
            <= ev.LUT_BLOCKED_MAX)
    q = lookup_440[backend]
    (chain,) = [c for c in chains(q["spans"])
                if c.name.endswith("partial_agg")]
    attrs = chain.attributes
    if backend == "tpu":
        assert (attrs["lut_blocked"], attrs["lut_gather"]) == (1, 0)
    else:
        assert attrs["lut_gather"] >= 1 and attrs["lut_blocked"] == 0
    assert attrs["lut_select"] == 0
    assert attrs["agg_form"] == ("onehot" if backend == "tpu" else "scatter")
    assert attrs["sketch_form"] == ("pallas" if backend == "tpu"
                                    else "segment")
    assert attrs["groups"] == 128
    mod = compare.load_reference(script["reference"])
    numbers = mod.compare(q["df"], mod.reference(
        lookup_440["tables"], config, script, q["start"]), config)
    assert all(v <= lim for v, lim in numbers.values()), numbers
    # the lookup is bit-equal; the TPU's one-hot sums round as they do
    keys = ["service", "resp_status"]
    got = q["df"].sort_values(keys).reset_index(drop=True)
    cpu = lookup_440["cpu"]["df"].sort_values(keys).reset_index(drop=True)
    for col in keys + ["cnt", "p50"]:
        np.testing.assert_array_equal(got[col].to_numpy(), cpu[col].to_numpy())
    np.testing.assert_allclose(got["avg_lat"].to_numpy(),
                               cpu["avg_lat"].to_numpy(),
                               rtol=config["guarantees"]["mean_rtol"])


# --------------------------------------------- (d) the width of the mesh


def test_n_devices_is_the_width_of_the_agents_mesh(loaded):
    from pixie_tpu.services.agent import Agent

    config, _tables, store = loaded
    script = traffic.load_script("http_by_status")
    with serving(store, 2) as client:
        q = ask(client, script, config, 0)
    assert len(q["agent"]["shard_rows"]) == 2
    (chain,) = [c for c in chains(q["spans"])
                if c.name.endswith("partial_agg")]
    assert chain.attributes["mesh_devices"] == 2
    # 524,288-row bucket over two: 262,144 and 196,608 valid rows
    assert chain.attributes["shard_skew"] == pytest.approx(8 / 7, abs=1e-4)
    for n_devices, mesh_size in ((None, "auto"), (1, None)):
        agent = Agent("pem9", "127.0.0.1", 1, store=store,
                      n_devices=n_devices)
        assert agent.mesh == mesh_size
    with pytest.raises(RuntimeError, match="need 16 devices, have 8"):
        Agent("pem9", "127.0.0.1", 1, store=store, n_devices=16)


# ------------------------------------------- (e) more than one feed a query


@pytest.mark.parametrize("name", SCRIPTS)
def test_two_feeds_the_last_shard_of_the_second_empty(name, loaded,
                                                      monkeypatch):
    config, tables, store = loaded
    monkeypatch.setattr(executor_mod, "FEED_ROWS", 4 * BATCH)
    script = traffic.load_script(name)
    with serving(store, 4) as client:
        q = ask(client, script, config, 0)
    (chain,) = [c for c in chains(q["spans"])
                if c.name.endswith("partial_agg")]
    assert chain.attributes["spmd_feeds"] == 2
    assert chain.attributes["feed_rows"] == 8 * BATCH  # two 262,144 buckets
    # shards: 65,536 x4 and 65,536 x3 + 0
    assert q["agent"]["shard_rows"] == [2 * BATCH] * 3 + [BATCH]
    assert chain.attributes["shard_skew"] == pytest.approx(8 / 7, abs=1e-4)
    merges = [s for s in q["spans"] if s.name == "mesh_merge"]
    assert len(merges) == 1
    assert chain.start_ns <= merges[0].start_ns
    assert merges[0].end_ns <= chain.end_ns
    mod = compare.load_reference(script["reference"])
    numbers = mod.compare(
        q["df"], mod.reference(tables, config, script, q["start"]), config)
    assert all(v <= lim for v, lim in numbers.values()), numbers
    assert numbers["groups_unmatched"] == numbers["cnt_mismatch"] == (0, 0)


# ------------------------------------------------- (f) the cell's readers

T0 = 1_700_000_000_000_000_000
MESH_READERS = ["mesh_scan_roofline", "shard_skew", "spmd_feeds_per_query",
                "mesh_merge_ms"]


def reader(name: str):
    return datagen.load_module("metrics", name).read


def synthetic_run(config_name: str, spans, monkeypatch) -> dict:
    """`run` as `run_cell` hands it to the readers: six device-routed
    by-status queries 200 ms apart, 100 ms long, a traced span over the last
    four with the devices busy 0.4 s of it on average, and `spans` as what
    the program's ring holds."""
    import tracered

    monkeypatch.setattr(trace, "recent", lambda since=0: spans)
    monkeypatch.setattr(trace, "ring_dropped", lambda: 0)
    config = config_file(config_name)
    queries = []
    for i in range(6):
        stats = {"agents": {"pem0": {"device": {"engines": {
            "device_chain": 1}}, "rows_scanned": 1}}}
        queries.append({"script": "http_by_status", "bound": 0,
                        "start_time": int(config["time_base_ns"]),
                        "t0_unix_ns": T0 + i * 200_000_000, "wall_ms": 100.0,
                        "digest": st.digest(stats)})
    lo, hi = T0 + 350_000_000, T0 + 1_200_000_000
    planes = {"devices": {f"/device:TPU:{d}": [(lo, lo + 400_000_000, "f")]
                          for d in range(4)}, "marks": []}
    t = tracered.reduce_trace(planes, lo, hi, [], 4)
    t["lo_unix_ns"], t["hi_unix_ns"] = lo, hi
    return {"config": config, "scripts": {
        "http_by_status": traffic.load_script("http_by_status")},
        "queries": queries, "walls_ms": [100.0] * 6, "window_s": 1.2,
        "compiles_in_window": 0, "peaks": {"hbm_bytes_per_s": 8.19e11},
        "trace": t}


def span(i, trace_id, name, ms, **attrs):
    sp = trace.Span(trace_id, f"s{i}", "", name, "pem0",
                    T0 + i * 1_000_000 + 1000, attrs)
    sp.end_ns = sp.start_ns + int(ms * 1e6)
    return sp


def mesh_spans() -> list:
    """Three queries: one feed, two feeds, and two chains of a feed each."""
    chain = "scan(http_events)->map->filter->partial_agg"
    return [
        span(0, "q0", chain, 20, engine="device_chain", arm="device",
             mesh_devices=4, spmd_feeds=1, shard_skew=1.0),
        span(1, "q0", "mesh_merge", 3.0),
        span(2, "q1", chain, 30, engine="device_chain", arm="device",
             mesh_devices=4, spmd_feeds=2, shard_skew=1.0286),
        span(3, "q1", "mesh_merge", 5.0),
        span(4, "q2", chain, 20, engine="device_chain", arm="device",
             mesh_devices=4, spmd_feeds=1, shard_skew=1.5),
        span(5, "q2", "mesh_merge", 3.5),
        span(6, "q2", chain, 20, engine="device_chain", arm="device",
             mesh_devices=4, spmd_feeds=1, shard_skew=1.2),
        span(7, "q2", "mesh_merge", 4.0),
        span(8, "q2", "scan(pods)->select", 1, engine="xla_cpu_chain",
             arm="cpu"),
    ]


@pytest.mark.parametrize("name", MESH_READERS)
def test_mesh_reader_by_hand(name, monkeypatch):
    run = synthetic_run("pem_http_4x512m", mesh_spans(), monkeypatch)
    got = reader(name)(run)
    if name == "mesh_scan_roofline":
        # queries 2..5 touch the span; 20 bytes a row of three columns read
        need = 4 * (4 + 8 + 8) * 18_350_080
        assert run["trace"]["busy_s"] == pytest.approx(0.4)
        assert got == pytest.approx(100 * need / (4 * 8.19e11) / 0.4)
        assert reader("scan_roofline")(run) == pytest.approx(4 * got)
        assert got < 100
    elif name == "shard_skew":
        assert got == 1.0286  # nearest-rank median of 1.0, 1.0286, 1.2, 1.5
    elif name == "spmd_feeds_per_query":
        assert got == 2  # 1, 2 and 1 + 1
    else:
        assert got == pytest.approx(5.0)  # 3.0, 5.0 and 3.5 + 4.0


@pytest.mark.parametrize("name", MESH_READERS)
def test_mesh_reader_finds_nothing_on_one_chip_or_a_silent_program(
        name, monkeypatch):
    """The one-chip configuration states no mesh, and a program from before
    the mesh said anything on its spans (the parent of the PR that added
    the cell) has chain spans without the attributes: nothing is read and
    nothing is raised."""
    silent = [span(0, "q0", "scan(http_events)->partial_agg", 20,
                   engine="device_chain", arm="cpu", source="static")]
    run = synthetic_run("pem_http_512m", silent, monkeypatch)
    assert reader(name)(run) is None
    if name != "mesh_scan_roofline":  # the roofline reads the trace alone
        run = synthetic_run("pem_http_4x512m", silent, monkeypatch)
        assert reader(name)(run) is None
    monkeypatch.delattr(trace, "recent")
    run["queries"], run["trace"] = [], None
    assert reader(name)(run) is None


# ---------------------------------------------- the planted fault on a mesh

_spec = importlib.util.spec_from_file_location(
    "benchmarks_test_correct_mesh",
    os.path.join(BENCH, "tests", "test_correct.py"))
_correct = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_correct)


def test_altered_answer_on_the_mesh_is_not_correct(monkeypatch):
    """A whole run of `http_scan_4chip` at its small size on four virtual
    devices is `correct`; the same run with one count altered where the
    broker produces it is not.  (`CELLS` of that module is the benchmark's
    and names the one-chip cells.)"""
    import jax

    old = mdstate.global_manager()
    assert len(jax.devices()) >= 4
    try:
        sound = _correct.drive("http_scan_4chip", monkeypatch, alter=False,
                               chips=4)
        assert sound["correct"] and sound["failed"] == 0, sound["checks"]
        assert sound["device"]["count"] == 4
        broken = _correct.drive("http_scan_4chip", monkeypatch, alter=True,
                                chips=4)
    finally:
        mdstate.set_global_manager(old)
    assert not broken["correct"], broken["checks"]
    assert broken["checks"]["cnt_mismatch"]["value"] > 0
