"""What decides `correct`, shown to fail: run by hand on the CPU,

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

- each control (the plain reference with one thing lowered below what the
  configuration states, put in the program's place: float32 sums, a sketch
  of a quarter of the bins) comes out as not correct on three seeds, at a
  size a test run can hold;
- a run that skips the look for a chip and has an answer altered where the
  broker produces it comes out with `correct` false, and the same run left
  alone with `correct` true;
- the benchmark's files and the recorded trace pass selfcheck.
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(HERE, "metrics"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

ROWS = 400_000
#: the control's float32 running sums need some thousands of rows a group
#: to leave the stated 1e-6: fewer 10 s windows, so larger groups
SPAN_S = 60
CELLS = ["http_scan_1chip", "http_status_1chip"]


def small_config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        config = json.load(f)
    config["rows"] = ROWS
    config["span_s"] = SPAN_S
    for t in config["tables"]:
        t.pop("max_bytes", None)
    return config


@pytest.mark.parametrize("seed", [3, 2147483659, 77])
@pytest.mark.parametrize("config_name,script_name,stand_in", [
    ("pem_http_512m", "http_by_status", "f32"),
    ("pem_http_512m", "http_windowed", "f32"),
    ("pem_http_512m", "http_by_status", "coarse_sketch"),
    ("pem_http_512m", "http_windowed", "coarse_sketch")])
def test_control_is_not_correct(config_name, script_name, stand_in, seed):
    import compare
    import data
    import traffic

    config = small_config(config_name)
    script = traffic.load_script(script_name)
    mod = compare.load_reference(script["reference"])
    tables = data.generate(config, seed)
    start = int(config["time_base_ns"]) + 3 * data.SEC
    ref = mod.reference(tables, config, script, start)
    same = mod.compare(ref[0], ref, config)
    assert all(v <= lim for v, lim in same.values()), same
    assert stand_in in mod.CONTROLS
    ctl, _ = mod.reference(tables, config, script, start, stand_in)
    numbers = mod.compare(ctl, ref, config)
    assert any(v > lim for v, lim in numbers.values()), numbers


def drive(workload, monkeypatch, alter):
    import jax

    import pixie_tpu  # noqa: F401
    import run
    from pixie_tpu.services import broker as broker_mod

    if alter:
        inner = broker_mod.Broker._execute_script_inner
        calls = {"n": 0}

        def altered(self, *a, **kw):
            results, stats = inner(self, *a, **kw)
            calls["n"] += 1
            if calls["n"] > 32:  # past the warm-up: answers of the window
                for r in results.values():
                    for col in ("cnt",):
                        if col in r.columns:
                            r.columns[col] = r.columns[col].copy()
                            r.columns[col][0] += 1
            return results, stats

        monkeypatch.setattr(broker_mod.Broker, "_execute_script_inner",
                            altered)
    bench = run.load_benchmark()
    cell, cfg = run.find_cell(bench, workload)
    dev = jax.devices()[0]
    return run.run_cell(
        bench, cell, cfg, 2147483659, 2.0, False,
        os.path.join(HERE, "out", "tests"), run.Phases(), jax, [dev],
        config=small_config(cfg["name"]),
        peaks={dev.device_kind: {"hbm_bytes_per_s": 1e11}})


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(workload, monkeypatch):
    sound = drive(workload, monkeypatch, alter=False)
    assert sound["correct"] and sound["failed"] == 0, sound["checks"]
    broken = drive(workload, monkeypatch, alter=True)
    assert not broken["correct"], broken["checks"]
    assert any(v["value"] > v["limit"] for v in broken["checks"].values())


def test_selfcheck():
    import selfcheck

    assert selfcheck.check_files() == []
    assert selfcheck.check_trace() == []
