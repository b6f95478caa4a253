"""The deployment `pem_http_edges_512m` (benchmarks/configs/
pem_http_edges_512m.json: one PEM's http_events read by the service map)
and its script `service_edges` (px/service_edge_stats `svc_edge_let`
without its 10 s bin), on the CPU: the generator at full size gives the
cluster's 440 requestor addresses, every one of the 14,080 (address,
service) edges and a tenth of health checks; the served path (Broker + one
Agent on loopback, the benchmark's own generator and loader) equals the
plain reference (benchmarks/references/service_edges.py) at a small size on
either arm and with the kernels traced for the TPU (the tiles kernel past
the dense kernel's cap, interpreted); each control fails its own number;
and the file is `pem_http_512m`'s in every key it does not restate."""
import functools
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
import traffic  # noqa: E402  benchmarks/traffic.py
from tests.test_net_flow_config import serve  # noqa: E402

ROWS = 20_000
SEEDS = [3, 2147483659]
PODS, SERVICES, NODE_PODS = 440, 32, 110
HEALTH = ("/healthz", "/readyz")


def full_config(name: str = "pem_http_edges_512m") -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def small_config() -> dict:
    config = full_config()
    config["rows"] = ROWS
    for t in config["tables"]:
        t.pop("max_bytes", None)
    return config


def columns(config: dict) -> dict:
    return {c["name"]: c for c in config["tables"][0]["columns"]}


@pytest.fixture
def script():
    return traffic.load_script("service_edges")


def test_generator_at_full_size_gives_the_service_maps_edges():
    """4,587,520 rows: requests from all 440 pod addresses of the cluster to
    the node's 110 pods, whose 32 services answer every address: 14,080
    live edges after the health checks, a tenth of the rows, are dropped."""
    config = full_config()
    http = datagen.generate(config, 2147483659)["http_events"]
    assert len(http["time_"]) == config["rows"] == 4_587_520
    addrs = columns(config)["remote_addr"]["values"]
    assert addrs == [f"10.0.{i // 250}.{i % 250 + 1}" for i in range(PODS)]
    assert len(np.unique(http["remote_addr"])) == PODS
    assert http["upid"].max() == NODE_PODS - 1
    paths = columns(config)["req_path"]["values"]
    health = np.isin(http["req_path"], [paths.index(p) for p in HEALTH])
    assert abs(health.mean() - 0.10) < 0.002
    keep = ~health & (http["trace_role"] == 2)
    edges = np.unique(http["remote_addr"][keep].astype(np.int64) * SERVICES
                      + http["upid"][keep] % SERVICES)
    assert len(edges) == PODS * SERVICES == 14_080


def test_file_is_pem_http_512m_but_for_what_it_restates():
    """Every key, column, width, generator and skew is `pem_http_512m`'s but
    the ones the service map changes: the cluster's pods in the metadata,
    upids over the node's own 110, the addresses and paths, and what the
    file says of itself (source, deployment, what is published, assumed and
    guaranteed)."""
    new, old = full_config(), full_config("pem_http_512m")
    restated = {"name", "source", "deployment", "published", "assumed",
                "guarantees", "metadata", "tables"}
    assert set(new) == set(old)
    for key in set(old) - restated:
        assert new[key] == old[key], key
    assert new["reduced"] == {} and new["rows"] == 4_587_520
    assert new["metadata"] == dict(old["metadata"], pods=PODS)
    assert new["guarantees"] == dict(old["guarantees"], sum="exact",
                                     error_rate_rtol=1e-08,
                                     note=new["guarantees"]["note"])
    assert {k: v for k, v in new["published"].items()
            if k not in ("pods_per_node", "script")} == {
        k: v for k, v in old["published"].items() if k != "pods_per_node"}
    (t_new,), (t_old,) = new["tables"], old["tables"]
    assert ({k: v for k, v in t_new.items() if k != "columns"}
            == {k: v for k, v in t_old.items() if k != "columns"})
    cn, co = columns(new), columns(old)
    assert list(cn) == list(co)
    for name in set(co) - {"upid", "remote_addr", "req_path"}:
        assert cn[name] == co[name], name
    assert cn["upid"] == dict(co["upid"], card=NODE_PODS)
    for name in ("remote_addr", "req_path"):
        assert ({k: cn[name][k] for k in ("type", "bytes")}
                == {k: co[name][k] for k in ("type", "bytes")})
    weights = cn["req_path"]["p"]
    assert weights[-2:] == [0.05, 0.05] and abs(sum(weights) - 1) < 1e-12
    assert cn["req_path"]["values"][:64] == [
        f"{co['req_path']['prefix']}{i}" for i in range(64)]
    np.testing.assert_allclose(np.array(weights[:64]) / 0.9,
                               datagen.zipf_p(64, 1.1), rtol=1e-12)


def route(monkeypatch, backend: str) -> None:
    """`cpu` and `device` are the router's two arms on one device, as the
    cell runs; `tpu` is the device arm with the kernels traced for the TPU,
    whose sketch past the dense kernel's cap is the tiles kernel
    (interpreted here)."""
    from pixie_tpu.engine.executor import PlanExecutor
    from pixie_tpu.ops import groupby

    init = PlanExecutor.__init__

    @functools.wraps(init)
    def forced(self, *args, **kwargs):
        kwargs.update(force_backend="cpu" if backend == "cpu" else "device",
                      mesh=None)
        init(self, *args, **kwargs)

    monkeypatch.setattr(PlanExecutor, "__init__", forced)
    if backend == "tpu":
        monkeypatch.setattr(groupby, "dispatch_backend", lambda: "tpu")


@pytest.mark.parametrize("backend", ["cpu", "device", "tpu"])
@pytest.mark.parametrize("seed", SEEDS)
def test_served_path_equals_reference(seed, backend, script, monkeypatch):
    from pixie_tpu import trace

    route(monkeypatch, backend)
    config = small_config()
    tables = datagen.generate(config, seed)
    start = int(config["time_base_ns"]) + 2 * datagen.SEC
    since = time.time_ns()
    out = serve(config, tables,
                script["text"].replace("__START_TIME__", str(start)))
    engines = out.exec_stats["agents"]["pem0"]["device"]["engines"]
    assert list(engines) == [
        "xla_cpu_chain" if backend == "cpu" else "device_chain"]
    mod = compare.load_reference(script["reference"])
    ref = mod.reference(tables, config, script, start)
    numbers = mod.compare(out.to_pandas(), ref, config)
    assert set(numbers) == {"groups_unmatched", "cnt_mismatch",
                            "sum_mismatch", "err_rel", "p50_rel", "p90_rel",
                            "p99_rel"}
    assert all(v <= lim for v, lim in numbers.values()), numbers
    assert len(ref[0]) > 3000
    chains = [s for s in trace.recent(since) if s.service == "pem0"
              and "sketch_form" in s.attributes]
    assert chains and {c.attributes["sketch_form"] for c in chains} == {
        "tiles" if backend == "tpu" else "segment"}
    # 440 addresses (512 codes) x the services' codes: past the cap
    assert {c.attributes["groups"] for c in chains} <= {16384, 32768}


@pytest.mark.parametrize("stand_in,number", [
    ("coarse_sketch", "p50_rel"), ("coarse_sketch", "p99_rel"),
    ("requestor_unresolved", "groups_unmatched"),
    ("f32_rates", "err_rel"), ("f32_sums", "sum_mismatch")])
@pytest.mark.parametrize("seed", SEEDS)
def test_control_is_not_correct(seed, stand_in, number, script):
    """Each control, the reference with one thing lowered, passes none of
    its own number's limit, where the reference itself passes all."""
    config = small_config()
    tables = datagen.generate(config, seed)
    start = int(config["time_base_ns"]) + 3 * datagen.SEC
    mod = compare.load_reference(script["reference"])
    assert stand_in in mod.CONTROLS and len(mod.CONTROLS) == 4
    ref = mod.reference(tables, config, script, start)
    same = mod.compare(ref[0], ref, config)
    assert all(v <= lim for v, lim in same.values()), same
    ctl, _ = mod.reference(tables, config, script, start, stand_in)
    value, limit = mod.compare(ctl, ref, config)[number]
    assert value > limit
