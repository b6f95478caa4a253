"""The deployment `pem_net_48m` (benchmarks/configs/pem_net_48m.json) and its
script `net_flow_by_service`, on the CPU at a small size: the served path
(Broker + one Agent on loopback, the benchmark's own generator and loader)
against the plain reference (benchmarks/references/net_flow.py), exact on
groups, counts and INT64 sums on either route; each control comes out not
correct; the file's byte arithmetic; the `pods` table against the node's
metadata."""
import functools
import json
import os
import sys

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

ROWS = 20_000
SEEDS = [3, 2147483659]
#: bytes a row of network_stats takes in the store: TIME64NS 8, the coded
#: pod_id 4, eight INT64 counters
ROW_BYTES = 8 + 4 + 8 * 8


def full_config() -> dict:
    with open(os.path.join(BENCH, "configs", "pem_net_48m.json")) as f:
        return json.load(f)


def small_config() -> dict:
    config = full_config()
    config["rows"] = ROWS
    for t in config["tables"]:
        t.pop("max_bytes", None)
    return config


@pytest.fixture
def script():
    return traffic.load_script("net_flow_by_service")


def serve(config, tables, text):
    """One execute_script through a Broker and one Agent on loopback."""
    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker
    from pixie_tpu.services.client import Client

    datagen.install_metadata(config)
    store = datagen.load_store(config, tables)
    broker = Broker(hb_expiry_s=120.0, query_timeout_s=120.0).start()
    agent = Agent("pem0", "127.0.0.1", broker.port, store=store,
                  heartbeat_s=2.0).start()
    client = Client("127.0.0.1", broker.port, timeout_s=120.0)
    try:
        return client.execute_script(text)["out"]
    finally:
        client.close()
        agent.stop()
        broker.stop()


@pytest.mark.parametrize("backend", ["cpu", "device", "mesh8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_served_path_equals_reference(seed, backend, script, monkeypatch):
    """`cpu` and `device` are the router's two arms on one device, as the
    cell runs (one chip = one PEM); `mesh8` leaves the executor its default
    mesh over the tests' eight virtual devices (the SPMD chain)."""
    from pixie_tpu.engine.executor import PlanExecutor

    init = PlanExecutor.__init__

    @functools.wraps(init)
    def forced(self, *args, **kwargs):
        if backend != "mesh8":
            kwargs.update(force_backend=backend, mesh=None)
        init(self, *args, **kwargs)

    monkeypatch.setattr(PlanExecutor, "__init__", forced)
    config = small_config()
    tables = datagen.generate(config, seed)
    start = int(config["time_base_ns"]) + 2 * datagen.SEC
    out = serve(config, tables,
                script["text"].replace("__START_TIME__", str(start)))
    engines = out.exec_stats["agents"]["pem0"]["device"]["engines"]
    assert ("device_chain" in engines) == (backend != "cpu"), engines
    mod = compare.load_reference(script["reference"])
    ref = mod.reference(tables, config, script, start)
    assert len(ref[0]) == config["metadata"]["services"]
    numbers = mod.compare(out.to_pandas(), ref, config)
    assert numbers == {"groups_unmatched": (0, 0), "cnt_mismatch": (0, 0),
                       "sum_mismatch": (0, 0)}
    # the query's own start_time took rows off the front of the table
    in_range = int((tables["network_stats"]["time_"] >= start).sum())
    assert 0 < in_range < ROWS and int(ref[0]["cnt"].sum()) == in_range


@pytest.mark.parametrize("stand_in", ["f32_sums", "pod_dropped"])
@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(seed, stand_in, script):
    config = small_config()
    tables = datagen.generate(config, seed)
    start = int(config["time_base_ns"]) + 3 * datagen.SEC
    mod = compare.load_reference(script["reference"])
    assert stand_in in mod.CONTROLS and len(mod.CONTROLS) == 2
    ref = mod.reference(tables, config, script, start)
    same = mod.compare(ref[0], ref, config)
    assert all(v <= lim for v, lim in same.values()), same
    ctl, _ = mod.reference(tables, config, script, start, stand_in)
    numbers = mod.compare(ctl, ref, config)
    assert any(v > lim for v, lim in numbers.values()), numbers
    # each control fails by the numbers it lowers, not by losing groups
    assert numbers["groups_unmatched"] == (0, 0)
    if stand_in == "f32_sums":
        assert numbers["cnt_mismatch"] == (0, 0)
        assert numbers["sum_mismatch"][0] > 0
    else:
        assert numbers["cnt_mismatch"][0] == 1


def test_ten_whole_batches_fit_the_share_and_an_eleventh_does_not():
    config = full_config()
    net = next(t for t in config["tables"] if t["name"] == "network_stats")
    assert sum(c["bytes"] for c in net["columns"]) == ROW_BYTES == 76
    batch, budget = net["batch_rows"], net["max_bytes"]
    assert budget == config["published"]["network_stats_bytes"] == 48 << 20
    # the 60% of the 1.25 GiB store that is not http_events, over 16 tables
    store = config["published"]["table_store_bytes"]
    assert store == 1280 << 20 and budget == store * 6 // 10 // 16
    rows = datagen.table_rows(config, net)
    assert rows == config["rows"] == 10 * batch == 655_360
    assert rows * ROW_BYTES == 49_807_360 <= budget < 11 * batch * ROW_BYTES
    assert config["reduced"] == {}


def test_store_keeps_every_row_at_full_size_and_expires_one_batch_more():
    """The loader's own check at the configuration's size: nothing expires
    under max_bytes; with an 11th batch the oldest does, and loading fails."""
    config = full_config()
    tables = datagen.generate(config, SEEDS[0])
    store = datagen.load_store(config, tables)
    st = store.table("network_stats").stats()
    assert st["rows_written"] == 655_360 and st["expired_batches"] == 0
    config["rows"] += 65_536
    with pytest.raises(RuntimeError, match="do not fit"):
        datagen.load_store(config, datagen.generate(config, SEEDS[0]))


def test_pods_table_holds_every_pod_once_in_its_service():
    from pixie_tpu.metadata import state as mdstate

    config = full_config()
    md = config["metadata"]
    tables = datagen.generate(config, SEEDS[1])
    spec = next(t for t in config["tables"] if t["name"] == "pods")
    cols = {c["name"]: c for c in spec["columns"]}
    assert datagen.table_rows(config, spec) == md["pods"] == 110
    assert len(np.unique(tables["pods"]["time_"])) == md["pods"]
    pod_ids = np.array(datagen.values_of(config, cols["pod_id"]))[
        tables["pods"]["pod_id"]]
    services = np.array(datagen.values_of(config, cols["service"]))[
        tables["pods"]["service"]]
    datagen.install_metadata(config)
    state = mdstate.global_manager().current()
    assert sorted(pod_ids) == sorted(state.pods_by_uid)
    assert len(set(pod_ids)) == 110
    for i, (uid, svc) in enumerate(zip(pod_ids, services)):
        assert uid == f"{md['pod_prefix']}{i}"
        assert svc == f"{md['service_prefix']}{i % md['services']}"
        assert [state.services_by_uid[u].name
                for u in state.pod_uid_to_service_uids[uid]] == [svc]
    # network_stats draws its pod_id from the same 110 values, evenly
    net = next(t for t in config["tables"] if t["name"] == "network_stats")
    net_pod = next(c for c in net["columns"] if c["name"] == "pod_id")
    assert datagen.values_of(config, net_pod) == list(pod_ids)
    counts = np.bincount(tables["network_stats"]["pod_id"], minlength=110)
    assert counts.min() > 0.9 * 655_360 / 110
