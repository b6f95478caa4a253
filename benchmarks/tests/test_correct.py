"""What decides `correct`, shown to fail: run by hand on the CPU,

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

- each control (the plain reference with one thing lowered below what the
  configuration states, put in the program's place: float32 sums, a sketch
  of a quarter of the bins) comes out as not correct on three seeds, at a
  size a test run can hold;
- a run that skips the look for a chip and has an answer altered where the
  broker produces it comes out with `correct` false, and the same run left
  alone with `correct` true;
- the benchmark's files and the recorded trace pass selfcheck, and the
  harness keeps what README.md says of it (`HARNESS`, below): a traced
  window in which the device ran nothing is a reading, a trace that shows no
  device operation under a device-routed query is an error, the router's two
  readers group by the model key, and a cell's `chips` reaches its Agent.

Tier-1 collects this module's three tests by name
(tests/test_benchmark_correct.py; a PR that changes the benchmark may touch
no file outside it), so what tier-1 holds of the harness are cases of
`test_selfcheck`, one per function of `HARNESS`.  The four-chip rehearsal
needs four devices: this module asks the CPU for eight before any backend
starts, as tests/conftest.py does for tier-1, and the case fails without
them.  (No conftest.py here: with benchmarks/ on the path it would be found
as `tests.conftest` before tier-1's own.)
"""
import inspect
import json
import numbers
import os
import sys

import pytest

if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

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


def drive(workload, monkeypatch, alter, chips=1):
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
    devices = jax.devices()[:chips]
    return run.run_cell(
        bench, cell, cfg, 2147483659, 2.0, False,
        os.path.join(HERE, "out", "tests"), run.Phases(), jax, devices,
        config=small_config(cfg["name"]),
        peaks={devices[0].device_kind: {"hbm_bytes_per_s": 1e11}})


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(workload, monkeypatch):
    sound = drive(workload, monkeypatch, alter=False)
    assert sound["correct"] and sound["failed"] == 0, sound["checks"]
    broken = drive(workload, monkeypatch, alter=True)
    assert not broken["correct"], broken["checks"]
    assert any(v["value"] > v["limit"] for v in broken["checks"].values())


# ---- the harness itself: cases of test_selfcheck ---------------------------

T0 = 1_700_000_000_000_000_000  # a unix time in ns no span of this process has
KEY_A, KEY_B = "agg:http_events:aaaaaaaaaa", "agg:http_events:bbbbbbbbbb"


def query(i, engine="cpu", decisions=()):
    """One record of a window as `run_cell` keeps it: the digest of an
    answer's exec_stats with the router's `decisions` (arm, source, n,
    plan_class; all in bucket 4^11), sent at T0 + i x 200 ms, 100 ms long."""
    import stats as st

    stats = {"agents": {"pem0": {
        "device": {"platform": "tpu", "device_kind": "TPU v5 lite",
                   "engines": {"device_chain" if engine == "device"
                               else "xla_cpu_chain": 1}},
        "autotune": [{"gate": "cpu_crossover", "arm": arm, "source": source,
                      "size_bucket": "4^11", "n": n, "plan_class": cls}
                     for arm, source, n, cls in decisions],
        "rows_scanned": 1000}},
        "phases": {"compile_ns": 10**6, "exec_ns": 5 * 10**7},
        "profile": {"wall_ns": 9 * 10**7}}
    return {"script": "http_by_status", "bound": 0, "start_time": 0,
            "t0_unix_ns": T0 + i * 200_000_000, "wall_ms": 100.0,
            "digest": st.digest(stats)}


def window(queries, trace):
    """`run` as `run_cell` hands it to the readers."""
    import stats as st
    import traffic

    with open(os.path.join(HERE, "configs", "pem_http_512m.json")) as f:
        config = json.load(f)
    st.mark_probes(queries)
    return {"config": config,
            "scripts": {"http_by_status": traffic.load_script(
                "http_by_status")},
            "queries": queries, "walls_ms": [q["wall_ms"] for q in queries],
            "window_s": 50.0, "compiles_in_window": 0,
            "peaks": {"hbm_bytes_per_s": 8.19e11}, "trace": trace}


def traced_span(devices, lo, hi):
    """What `TailTrace.reduce` returns for a span [lo, hi) of unix time."""
    import tracered

    out = tracered.reduce_trace({"devices": devices, "marks": []}, lo, hi, [])
    out["lo_unix_ns"], out["hi_unix_ns"] = lo, hi
    return out


def files_and_recorded_trace():
    """The benchmark's files keep the contract's rules; the recorded and the
    hand-made traces reduce to their numbers, the ones without a device
    plane and the one of four devices with two planes among them."""
    import selfcheck

    assert selfcheck.check_files() == []
    assert selfcheck.check_trace() == []


def readers_of_a_window_served_off_the_chip():
    """Every reader gives `None` or a number for a traced window whose
    queries all ran on the host and whose trace has no device plane; the two
    that divide by device time give `None` there, never 0: a roofline of 0
    reads as "nothing bounded the claim"."""
    import data as datagen

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    lo, hi = T0 + 10**9, T0 + 9 * 10**9
    idle = traced_span({}, lo, hi)
    assert idle["busy_s"] == 0.0 and idle["device_ops"] == []
    assert idle["idle_gaps"] == [["outside_any_span", 8.0],
                                 ["total:outside_any_span", 8.0]]
    off_chip = window([query(i) for i in range(50)], idle)
    got = {n: datagen.load_module("metrics", n).read(off_chip) for n in names}
    for name, value in got.items():
        assert value is None or (isinstance(value, numbers.Real)
                                 and not isinstance(value, bool)), name
    assert got["device_ms_per_query"] is None and got["scan_roofline"] is None
    assert got["device_route_share"] == 0.0
    assert got["client_overhead_ms"] == pytest.approx(10.0)
    # device-routed queries over a device busy for 2 s read a number
    busy = window([query(i, engine="device") for i in range(50)],
                  traced_span({"/device:TPU:0": [(lo, lo + 2 * 10**9, "f")]},
                              lo, hi))
    assert datagen.load_module("metrics", "device_ms_per_query").read(busy) \
        == pytest.approx(2000.0 / 40)  # queries 5..44 touch the span
    assert datagen.load_module("metrics", "scan_roofline").read(busy) > 0


def trace_without_a_device_plane(monkeypatch):
    """`TailTrace.reduce` over a trace that shows no operation on a device,
    for want of the plane or of an event on it: busy 0 when no query inside
    the traced span ran a chain on the device, an error when one did (the
    trace is broken), whatever ran before the span or reaches out of it."""
    import run
    import tracered

    planes = {"devices": {}, "marks": [(T0 + 500, "bench.clock_sync")],
              "layout": [("/host:CPU", ["python"])]}
    monkeypatch.setattr(tracered, "find_xplane", lambda d: d)
    monkeypatch.setattr(tracered, "read_planes", lambda path: planes)
    tt = run.TailTrace(None, os.path.join(HERE, "out", "tests", "no_trace"),
                       50.0, 8.0)
    tt.started = True
    tt.mark_unix_ns = T0
    tt.lo_unix_ns, tt.hi_unix_ns = T0 + 10**9, T0 + 9 * 10**9
    spans = [(T0 + 2 * 10**9, T0 + 4 * 10**9, "pem0.exec")]

    def routed(*on_device):
        return [query(i, engine="device" if i in on_device else "cpu")
                for i in range(50)]

    for devices in ({}, {"/device:TPU:0": []}):
        planes["devices"] = devices
        out = tt.reduce(spans, routed(), 1)
        assert out["busy_s"] == 0.0 and out["window_s"] == 8.0
        assert out["device_ops"] == [] and out["clock_shift_ns"] == 500
        gaps = dict(map(tuple, out["idle_gaps"]))
        assert gaps["client.execute_script"] == 8.0  # one gap, by its middle
        assert gaps["total:pem0.exec"] == pytest.approx(1.0)  # less clients'
        assert gaps["total:client.execute_script"] == pytest.approx(4.0)
        # queries 0..3 end before the span begins; with the span closed at
        # 8.85 s, 44 begins inside it and ends 50 ms after it: its device
        # work may lie outside
        tt.hi_unix_ns = T0 + 8_850_000_000
        assert tt.reduce(spans, routed(0, 1, 2, 3, 44), 1)["busy_s"] == 0.0
        tt.hi_unix_ns = T0 + 9 * 10**9
        with pytest.raises(RuntimeError, match="no operation.*1 queries"):
            tt.reduce(spans, routed(20), 1)
    # the same query under a plane with an operation in the span is a reading
    planes["devices"] = {"/device:TPU:0": [
        (T0 + 500 + 5 * 10**9, T0 + 500 + 6 * 10**9, "fusion.1")]}
    assert tt.reduce(spans, routed(20), 1)["busy_s"] == pytest.approx(1.0)


def probes_by_model_key():
    """The router counts each (`plan_class`, `size_bucket`) apart: two keys
    that alternate in one bucket (the scan cell's by-status x3 then
    windowed) mark no probe without an explore; a step of two within one
    key marks the hedged-away explore; an explore marks itself."""
    import stats as st

    def sent(key, n, source="model"):
        return query(0, decisions=[("device", source, n, key)])

    steady, na, nb = [], 0, 0
    for i in range(16):
        if i % 4 == 3:
            nb += 1
            steady.append(sent(KEY_B, nb))
        else:
            na += 1
            steady.append(sent(KEY_A, na))
    st.mark_probes(steady)
    assert [r["digest"]["probe"] for r in steady] == [False] * 16
    assert steady[3]["digest"]["plan_class"] == KEY_B

    stepped = [sent(KEY_A, 5), sent(KEY_B, 2), sent(KEY_A, 7),
               sent(KEY_B, 3), sent(KEY_A, 8, "explore"), sent(KEY_B, 5)]
    st.mark_probes(stepped)
    assert [r["digest"]["probe"] for r in stepped] == \
        [False, False, True, False, True, True]


def arm_flips_by_model_key(monkeypatch):
    """Two route classes of one bucket on different arms are two decisions,
    not a flip a query; a class that changes arm is one flip; an explore is
    none; spans without a `plan_class` group by service and bucket."""
    import data as datagen
    from pixie_tpu import trace

    def chain(i, arm, cls=None, source="model"):
        attrs = {"engine": "device_chain" if arm == "device"
                 else "xla_cpu_chain", "arm": arm, "source": source,
                 "size_bucket": "4^11"}
        if cls:
            attrs["plan_class"] = cls
        sp = trace.Span("t", f"s{i}", "", "scan(http_events)->partial_agg",
                        "pem0", T0 + i * 200_000_000 + 1000, attrs)
        sp.end_ns = sp.start_ns + 50_000_000
        return sp

    def flips(spans):
        monkeypatch.setattr(trace, "recent", lambda since=0: spans)
        monkeypatch.setattr(trace, "ring_dropped", lambda: 0)
        run = window([query(i) for i in range(len(spans))], None)
        return datagen.load_module("metrics", "router_arm_flips").read(run)

    two_keys = [chain(i, "cpu", KEY_B) if i % 4 == 3
                else chain(i, "device", KEY_A) for i in range(16)]
    assert flips(two_keys) == 0.0
    one_key = [chain(i, "device" if i < 9 else "cpu", KEY_A)
               for i in range(16)]
    assert flips(one_key) == 1.0
    explored = [chain(i, "cpu" if i == 5 else "device", KEY_A,
                      "explore" if i == 5 else "model") for i in range(16)]
    assert flips(explored) == 0.0
    unkeyed = [chain(i, "cpu" if i % 4 == 3 else "device")
               for i in range(8)]
    assert flips(unkeyed) == 3.0
    assert flips([]) is None


def chips_reach_the_agent(monkeypatch):
    """A whole run of a cell on four devices: `correct`, the result names
    four, and the Agent was built with `n_devices=4`; the same on one device
    names none."""
    import jax
    from pixie_tpu.services import agent as agent_mod

    assert len(jax.devices()) >= 4, (
        "needs four devices: on the CPU, XLA_FLAGS="
        "--xla_force_host_platform_device_count=8, set before jax starts")
    init = agent_mod.Agent.__init__
    given = []

    def recording(self, *a, n_devices=None, **kw):
        given.append(n_devices)
        init(self, *a, n_devices=n_devices, **kw)

    monkeypatch.setattr(agent_mod.Agent, "__init__", recording)
    for chips, n_devices in ((4, 4), (1, None)):
        result = drive("http_status_1chip", monkeypatch, alter=False,
                       chips=chips)
        assert result["correct"] and result["failed"] == 0, result["checks"]
        assert result["device"]["count"] == chips
        assert given.pop() == n_devices and not given


HARNESS = [files_and_recorded_trace, readers_of_a_window_served_off_the_chip,
           trace_without_a_device_plane, probes_by_model_key,
           arm_flips_by_model_key, chips_reach_the_agent]


@pytest.mark.parametrize("case", HARNESS, ids=lambda f: f.__name__)
def test_selfcheck(case, request):
    """`case` with the fixtures its signature names."""
    case(*(request.getfixturevalue(name)
           for name in inspect.signature(case).parameters))
