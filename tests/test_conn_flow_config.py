"""The deployment `pem_netconn_96m` (benchmarks/configs/pem_netconn_96m.json:
one PEM's network_stats and conn_stats side by side) and its script
`conn_flow_graph` (px/net_flow_graph over conn_stats), on the CPU at a small
size: the served path (Broker + one Agent on loopback, the benchmark's own
generator and loader) against the plain reference
(benchmarks/references/conn_flow.py), exact on groups, INT64 min/max
differences and sums on every route; upstream's three-key group-by and its
two-key form give one frame by one path; each control comes out not
correct; the file's byte arithmetic; which addresses the node's metadata
resolves; the kernel under it (ops/groupby.sort_order, runs_of) bit-equal
to jax.ops.segment_min/max/sum past MAX_GROUPS, at a full bucket with a
masked prefix, with one group and with no row; a warm query compiles
nothing in the agent or in the broker's half of the plan; `network_stats`
and `pods` drawn as `pem_net_48m` draws them; and one store holding both
tables answers the cell's rotation, each query against its own script's
reference, uploading nothing from the second cycle on."""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
#: bytes a row of conn_stats takes in the store: TIME64NS 8, the coded upid
#: and remote_addr 4 each, nine INT64, one BOOLEAN
ROW_BYTES = 8 + 4 + 4 + 9 * 8 + 1
EXACT = {"groups_unmatched": (0, 0), "minmax_mismatch": (0, 0),
         "sum_mismatch": (0, 0)}


def full_config() -> dict:
    with open(os.path.join(BENCH, "configs", "pem_netconn_96m.json")) as f:
        return json.load(f)


def small_config() -> dict:
    config = full_config()
    config["rows"] = config["conn_rows"] = ROWS
    for t in config["tables"]:
        t.pop("max_bytes", None)
    return config


@pytest.fixture
def script():
    return traffic.load_script("conn_flow_graph")


def two_key_form(text: str) -> str:
    """The script with `pod` taken out of the first group-by and looked up
    from the `upid` key after it: the same answer, since a pod is a
    function of its process."""
    assert "df.pod = df.ctx['pod']\n" in text
    assert "['pod', 'upid', 'remote_addr']" in text
    text = text.replace("df.pod = df.ctx['pod']\n", "")
    text = text.replace("['pod', 'upid', 'remote_addr']",
                        "['upid', 'remote_addr']")
    return text.replace("df.from_entity = df.pod",
                        "df.from_entity = df.ctx['pod']")


def route(monkeypatch, backend: str) -> None:
    """`cpu` and `device` are the router's two arms on one device, as the
    cell runs (one chip = one PEM); `mesh8` leaves the executor its default
    mesh over the tests' eight virtual devices."""
    from pixie_tpu.engine.executor import PlanExecutor

    init = PlanExecutor.__init__

    @functools.wraps(init)
    def forced(self, *args, **kwargs):
        if backend != "mesh8":
            kwargs.update(force_backend=backend, mesh=None)
        init(self, *args, **kwargs)

    monkeypatch.setattr(PlanExecutor, "__init__", forced)


@pytest.mark.parametrize("backend", ["cpu", "device", "mesh8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_served_path_equals_reference(seed, backend, script, monkeypatch):
    route(monkeypatch, backend)
    config = small_config()
    tables = datagen.generate(config, seed)
    start = int(config["time_base_ns"]) + 2 * datagen.SEC
    out = serve(config, tables,
                script["text"].replace("__START_TIME__", str(start)))
    agent = out.exec_stats["agents"]["pem0"]
    # 2^28 dense slots for 20,000 rows: the aggregate sorts, on the arm asked
    assert agent["sorted_agg_fallbacks"] == 1
    if backend != "mesh8":
        assert list(agent["device"]["engines"]) == [
            "xla_cpu_chain" if backend == "cpu" else "device_chain"]
    mod = compare.load_reference(script["reference"])
    ref = mod.reference(tables, config, script, start)
    assert mod.compare(out.to_pandas(), ref, config) == EXACT
    # the query's own start_time took rows off the front of the table, and
    # some thousands of client-server pairs are left
    in_range = int((tables["conn_stats"]["time_"] >= start).sum())
    assert 0 < in_range < ROWS and 1000 < len(ref[0]) < in_range


@pytest.mark.parametrize("backend", ["cpu", "device"])
def test_three_key_and_two_key_forms_one_frame_one_path(backend, script,
                                                        monkeypatch):
    route(monkeypatch, backend)
    config = small_config()
    tables = datagen.generate(config, SEEDS[0])
    start = int(config["time_base_ns"]) + datagen.SEC
    text = script["text"].replace("__START_TIME__", str(start))
    three = serve(config, tables, text)
    two = serve(config, tables, two_key_form(text))
    keys = ["from_entity", "to_entity"]
    a = three.to_pandas().sort_values(keys).reset_index(drop=True)
    b = two.to_pandas().sort_values(keys).reset_index(drop=True)
    assert len(a) > 1000 and a.equals(b[list(a.columns)])
    sa, sb = (o.exec_stats["agents"]["pem0"] for o in (three, two))
    assert sa["sorted_agg_fallbacks"] == sb["sorted_agg_fallbacks"] == 1
    assert sa["device"]["engines"] == sb["device"]["engines"]
    for label in ("sort_reduce", "compact_readback", "key_decode"):
        for s in (sa, sb):
            assert [o["label"] for o in s["operators"]].count(label) == 1


def test_a_warm_query_compiles_nothing_in_agent_or_broker(script,
                                                          monkeypatch):
    """The cell's rotation (four starts, each its own row count) sent as
    the warm-up sends it, then once more: jax compiles nothing in the last
    cycle, in the agent's chains or in the broker's regroup.  At the
    cell's size the agent's ~64k partial rows are past
    SMALL_HOST_INPUT_ROWS, where a dense aggregate over computed keys has
    no cache signature and is jitted anew every query; the bound is
    lowered under this test's ~5,000 partial rows to meet that."""
    from pixie_tpu.engine import executor
    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker
    from pixie_tpu.services.client import Client

    monkeypatch.setattr(executor, "SMALL_HOST_INPUT_ROWS", 1 << 10)
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiled.append(event)
        if event.endswith("backend_compile_duration") else None)
    config = small_config()
    tables = datagen.generate(config, SEEDS[0])
    datagen.install_metadata(config)
    store = datagen.load_store(config, tables)
    broker = Broker(hb_expiry_s=120.0, query_timeout_s=120.0).start()
    agent = Agent("pem0", "127.0.0.1", broker.port, store=store,
                  heartbeat_s=2.0).start()
    client = Client("127.0.0.1", broker.port, timeout_s=120.0)

    def query(k):
        start = int(config["time_base_ns"]) + k * datagen.SEC
        before = len(compiled)
        out = client.execute_script(
            script["text"].replace("__START_TIME__", str(start)))["out"]
        stats = out.exec_stats
        return (len(compiled) - before, stats["agents"]["pem0"]["compiles"],
                stats["merger"]["compiles"])

    try:
        cold = query(0)
        for k in range(4):
            for _ in range(5):  # past the router's cold probe of either arm
                query(k)
        warm = [query(k) for k in range(4)]
    finally:
        client.close()
        agent.stop()
        broker.stop()
    # the listener sees what the executors' own counters see
    assert cold[0] > 0 and cold[0] == cold[1] + cold[2]
    assert warm == [(0, 0, 0)] * 4


@pytest.mark.parametrize("stand_in", ["f32_minmax", "nslookup_skipped"])
@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(seed, stand_in, script):
    config = small_config()
    tables = datagen.generate(config, seed)
    start = int(config["time_base_ns"]) + 3 * datagen.SEC
    mod = compare.load_reference(script["reference"])
    assert stand_in in mod.CONTROLS and len(mod.CONTROLS) == 2
    ref = mod.reference(tables, config, script, start)
    assert mod.compare(ref[0], ref, config) == EXACT
    ctl, _ = mod.reference(tables, config, script, start, stand_in)
    numbers = mod.compare(ctl, ref, config)
    assert any(v > lim for v, lim in numbers.values()), numbers
    # each control fails by the number it lowers
    if stand_in == "f32_minmax":
        assert numbers["groups_unmatched"] == (0, 0)
        assert numbers["minmax_mismatch"][0] > 0
        assert numbers["sum_mismatch"][0] > 0
    else:
        assert list(numbers) == ["groups_unmatched"]
        assert numbers["groups_unmatched"][0] > 0


def test_eight_whole_batches_fit_the_share_and_a_ninth_does_not():
    """The byte arithmetic of the file; the full-size load is the chip
    run's (the loader fails there if a batch expires)."""
    config = full_config()
    conn = next(t for t in config["tables"] if t["name"] == "conn_stats")
    assert len(conn["columns"]) == 13
    assert sum(c["bytes"] for c in conn["columns"]) == ROW_BYTES == 89
    batch, budget = conn["batch_rows"], conn["max_bytes"]
    assert budget == config["published"]["conn_stats_bytes"] == 48 << 20
    # the 60% of the 1.25 GiB store that is not http_events, over 16 tables
    store = config["published"]["table_store_bytes"]
    assert store == 1280 << 20 and budget == store * 6 // 10 // 16
    rows = datagen.table_rows(config, conn)
    # a key of its own: `rows` is network_stats' (selfcheck.py --rehearse
    # scales that table by it)
    assert rows == config["conn_rows"] == 8 * batch == 524_288 == 1 << 19
    assert config["rows"] == 655_360 and conn["rows"] == "conn_rows"
    assert rows * ROW_BYTES == 46_661_632 <= budget < 9 * batch * ROW_BYTES
    assert 9 * batch * ROW_BYTES == 52_494_336
    assert config["reduced"] == {}
    read = traffic.load_script("conn_flow_graph")["columns_read"]
    assert datagen.column_bytes(config, "conn_stats", read) == 40


def test_the_relation_is_upstreams():
    from pixie_tpu.collect.schemas import all_schemas

    conn = next(t for t in full_config()["tables"]
                if t["name"] == "conn_stats")
    rel = all_schemas()["conn_stats"]
    assert [c["name"] for c in conn["columns"]] == list(rel.names())
    assert [c["type"] for c in conn["columns"]] == [
        rel.dtype(n).name for n in rel.names()]


def test_known_addresses_resolve_to_names_and_the_rest_to_themselves():
    from pixie_tpu.metadata import state as mdstate

    config = full_config()
    md = config["metadata"]
    conn = next(t for t in config["tables"] if t["name"] == "conn_stats")
    addrs = next(c for c in conn["columns"]
                 if c["name"] == "remote_addr")["values"]
    assert len(addrs) == len(set(addrs)) == 10_000
    datagen.install_metadata(config)
    snap = mdstate.global_manager().current()
    mod = compare.load_reference("conn_flow")
    pods, names = mod.entities(config)
    assert len(pods) == md["pods"] == 110
    assert len(names) == md["pods"] + md["services"] == 142
    assert set(addrs[:142]) == set(names)
    for a in addrs[:142]:
        assert snap.nslookup(a) == names[a] != a
    for a in addrs[142:]:
        assert snap.nslookup(a) == a and a not in names
    for i, pod in enumerate(pods):
        upid = datagen.values_of(config, {"type": "UINT128", "card": "pods"})[i]
        assert snap.pod_of_upid(upid).qualified_name == pod


def test_decode_array_is_decode_as_one_take():
    """What turns 64k x 3 key codes into the values on the wire."""
    from pixie_tpu.table.dictionary import Dictionary
    from pixie_tpu.types import UInt128

    config = full_config()
    upids = datagen.values_of(config, {"type": "UINT128", "card": "pods"})
    for values in (upids, ["a", "b", "c"], []):
        d = Dictionary()
        d.encode(values)
        # many codes take the table of values, few codes of a larger
        # dictionary are decoded one by one
        for times in (50, 1):
            codes = np.array([0, 2, -1, len(values) - 1, len(values), 1]
                             * times, dtype=np.int32)
            got = d.decode_array(codes)
            assert got.dtype == object and got.shape == codes.shape
            assert list(got) == d.decode(codes)
    assert isinstance(got, np.ndarray) and isinstance(upids[0], UInt128)
    assert Dictionary().decode_array(np.empty(0, np.int32)).shape == (0,)


@pytest.mark.parametrize("seed", SEEDS)
def test_network_stats_and_pods_are_drawn_as_pem_net_48m_draws_them(seed):
    """The tables come in pem_net_48m's order and conn_stats after them,
    so that one seed gives `network_stats` the same arrays under either
    configuration, at the cells' own size: every `net_flow_by_service`
    answer of `conn_flow_1chip` is `net_flow_1chip`'s on that seed."""
    with open(os.path.join(BENCH, "configs", "pem_net_48m.json")) as f:
        net = json.load(f)
    config = full_config()
    assert [t["name"] for t in config["tables"]] == [
        "network_stats", "pods", "conn_stats"]
    assert config["tables"][:2] == net["tables"]
    for key in ("rows", "time_base_ns", "span_s", "metadata"):
        assert config[key] == net[key]
    mine, theirs = datagen.generate(config, seed), datagen.generate(net, seed)
    for table in ("network_stats", "pods"):
        assert list(mine[table]) == list(theirs[table])
        for name, column in theirs[table].items():
            assert column.dtype == mine[table][name].dtype
            assert (column == mine[table][name]).all(), (table, name)
    assert len(mine["network_stats"]["time_"]) == 655_360
    assert len(mine["conn_stats"]["time_"]) == 524_288


def test_one_store_answers_the_rotation_and_uploads_nothing_when_warm(
        monkeypatch):
    """`node_net_rotation` as the cell sends it (the widget three times to
    the flow graph once, the cycle of starts turned by the seed), three
    cycles on the device arm against one store that holds both tables:
    every answer equals its own script's reference, the first cycle uploads
    either table's feed, and from the second cycle on both are resident
    side by side: neither crosses the link again."""
    import stats as st
    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker
    from pixie_tpu.services.client import Client

    route(monkeypatch, "device")
    # whole sealed batches, as at the cell's size (a table's unsealed tail
    # is uploaded by every query): five of 4,096 rows under either budget
    config = full_config()
    config["rows"] = config["conn_rows"] = 5 * 4096
    for t in config["tables"]:
        if "max_bytes" in t:
            t["batch_rows"] = 4096
    seed = SEEDS[1]
    tables = datagen.generate(config, seed)
    mix = datagen.load_json("traffic", "node_net_rotation")
    assert [p["script"] for p in mix["pattern"]] == (
        ["net_flow_by_service"] * 3 + ["conn_flow_graph"])
    assert (mix["clients"], mix["start_offset_s"], mix["warmup_each_pair"]
            ) == (1, [0, 1, 2, 3], 8)
    schedule = traffic.Schedule(mix, config, seed)
    assert len(schedule.warmup()) == 64
    datagen.install_metadata(config)
    store = datagen.load_store(config, tables)
    assert sorted(store.schemas()) == ["conn_stats", "network_stats", "pods"]
    broker = Broker(hb_expiry_s=120.0, query_timeout_s=120.0).start()
    agent = Agent("pem0", "127.0.0.1", broker.port, store=store,
                  heartbeat_s=2.0).start()
    client = Client("127.0.0.1", broker.port, timeout_s=120.0)
    try:
        recs = [traffic.send(client, schedule.query(i)) for i in range(12)]
    finally:
        client.close()
        agent.stop()
        broker.stop()
    assert [r["script"] for r in recs[:4]] == [p["script"]
                                               for p in mix["pattern"]]
    # a seed gives the flow graph one start and the widget the other three
    assert len({(r["script"], r["bound"]) for r in recs}) == 4
    uploaded = []
    for r in recs:
        assert "error" not in r, r
        script = schedule.scripts[r["script"]]
        mod = compare.load_reference(script["reference"])
        ref = mod.reference(tables, config, script, r["start_time"])
        numbers = mod.compare(r["answer"], ref, config)
        assert numbers and all(v == 0 == lim for v, lim in numbers.values())
        d = st.digest(r["stats"])
        assert d["engine"] == "device"
        uploaded.append(d["h2d_bytes"])
    # forced onto the device arm, the widget's scan of the 110 unsealed
    # `pods` rows uploads their 1,024-row bucket of two codes every time
    # (in the cell the static crossover keeps that scan on the host)
    pods = 1024 * (4 + 4)
    assert uploaded[0] > pods and uploaded[3] > pods, uploaded
    assert uploaded[4:] == [pods, pods, pods, 0] * 2, uploaded


# ------------------------------------------------------------- the kernel

def _sorted_reduce(keys, sentinel, values, mask):
    """(groups, {min, max, sum, count, the keys} of every run, in run
    order at the front), as the executor's sorted aggregate composes it."""
    from pixie_tpu.ops import groupby as gb

    n = mask.shape[0]
    lead, order = gb.sort_order(gb.run_sort_keys(keys, mask, sentinel))
    ks = list(lead[-len(keys):])
    runs, live = gb.runs_of(ks, jnp.sum(mask.astype(jnp.int32)))
    vs = gb.take_rows(values, order)
    out = {"keys": ks,
           "min": gb.masked_segment_min(vs, runs, n, live),
           "max": gb.masked_segment_max(vs, runs, n, live),
           "sum": gb.masked_segment_sum(vs, runs, n, live),
           "count": gb.masked_segment_count(runs, n, live)}
    _ends, front = gb.sort_order((gb.run_end_key(runs),))
    return jnp.sum(runs.end), gb.take_rows(out, front)


KERNEL_CASES = {
    # three dictionary codes whose dense space, 2^28 slots, passes
    # MAX_GROUPS: one packed id, the masked rows under the sentinel
    "space_past_max_groups": dict(n=8192, cards=(128, 128, 16384),
                                  live=slice(0, 7000), packed=True),
    # a full pow2 bucket whose first rows a start_time masks out (PR 32's
    # loop bounds have no padding to skip here)
    "full_bucket_masked_prefix": dict(n=16384, cards=(128, 16384),
                                      live=slice(3000, 16384), packed=True),
    # separate keys, one of them INT64: no packed id, no sentinel
    "separate_keys": dict(n=4096, cards=(110, 5000),
                          live=slice(100, 4000), packed=False),
    # every live row in one group: one run, closed by the last live row
    "one_group": dict(n=2048, cards=(1, 1), live=slice(5, 1900),
                      packed=True),
    # no row passes the mask: no run, nothing at the front is read
    "no_row": dict(n=1024, cards=(128, 16384), live=slice(0, 0),
                   packed=True),
}


@pytest.mark.parametrize("form", ["scan", "passes"])
@pytest.mark.parametrize("dtype", [np.int64, np.float64])
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_sorted_runs_equal_segment_reductions(case, dtype, form, monkeypatch):
    """`form`: the run reduction as this platform traces it (the
    work-efficient scan) and as the TPU does (log2(n) whole-array
    passes)."""
    from pixie_tpu.engine.executor import MAX_GROUPS
    from pixie_tpu.ops import groupby

    if form == "passes":
        monkeypatch.setattr(groupby, "dispatch_backend", lambda: "tpu")
    c = KERNEL_CASES[case]
    n, cards = c["n"], c["cards"]
    rng = np.random.default_rng(11)
    codes = [rng.integers(0, card, n).astype(np.int32) for card in cards]
    values = rng.integers(0, 1 << 40, n).astype(dtype)
    mask = np.zeros(n, bool)
    mask[c["live"]] = rng.random(n)[c["live"]] < 0.5
    space = int(np.prod([int(x) for x in cards]))
    packed = np.zeros(n, np.int64)
    for code, card in zip(codes, cards):
        packed = packed * card + code
    if case == "space_past_max_groups":
        assert space == 1 << 28 > MAX_GROUPS
    if c["packed"]:
        keys, sentinel = [packed.astype(np.int32)], space
    else:
        keys, sentinel = [codes[0], codes[1].astype(np.int64)], None
    groups, out = jax.jit(_sorted_reduce, static_argnums=1)(
        keys, sentinel, values, mask)
    groups = int(groups)
    # exact dense ids of the live groups, for the reference reductions
    uniq, gid = np.unique(packed[mask], return_inverse=True)
    assert groups == len(uniq)
    assert {"one_group": groups == 1, "no_row": groups == 0}.get(
        case, groups > 1000)
    if c["packed"]:
        assert (np.asarray(out["keys"][0])[:groups] == uniq).all()
    else:
        assert (np.asarray(out["keys"][0])[:groups] * cards[1]
                + np.asarray(out["keys"][1])[:groups] == uniq).all()
    if not groups:
        return
    v = jnp.asarray(values[mask])
    want = {"min": jax.ops.segment_min(v, gid, num_segments=groups),
            "max": jax.ops.segment_max(v, gid, num_segments=groups),
            "sum": jax.ops.segment_sum(v, gid, num_segments=groups),
            "count": np.bincount(gid, minlength=groups)}
    for name, w in want.items():
        got = np.asarray(out[name])[:groups]
        assert got.dtype == np.asarray(w).dtype or name == "count"
        if name == "sum" and dtype is np.float64:
            # integers under 2^53: every order of addition is exact
            assert float(np.max(np.asarray(w))) < 2.0 ** 53
        assert (got == np.asarray(w)).all(), name
