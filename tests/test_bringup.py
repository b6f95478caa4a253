"""Chip bring-up contracts that hold on the CPU too (ISSUE 21): where the
compile cache lives, who owns a chip, that every query says where it ran,
that the smoke refuses to run without a chip, and that a stale native
library is never loaded."""
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _py(code: str, **env) -> subprocess.CompletedProcess:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO), **env)
    return subprocess.run([sys.executable, "-c", code], env=full, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


# ------------------------------------------------------------ compile cache
CACHE_DIR_CODE = ("import pixie_tpu, jax; "
                  "print(jax.config.jax_compilation_cache_dir)")


def test_cache_dir_unset_env_is_the_fixed_checkout_path():
    p = _py(CACHE_DIR_CODE)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == str(REPO / ".jax_cache")


def test_cache_dir_from_env_is_left_alone(tmp_path):
    p = _py(CACHE_DIR_CODE, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == str(tmp_path)


def test_one_guarded_cache_dir_assignment_in_the_tree():
    """`git grep jax_compilation_cache_dir` shows no other assignment: the
    one in pixie_tpu/__init__.py, under the env-unset guard."""
    setters = []
    for path in [*REPO.glob("*.py"), *(REPO / "pixie_tpu").rglob("*.py")]:
        text = path.read_text()
        assert "PX_JIT_" "CACHE_DIR" not in text, path  # the old flag
        if 'update(\n        "jax_compilation_cache_dir"' in text \
                or 'update("jax_compilation_cache_dir"' in text:
            setters.append(path.relative_to(REPO).as_posix())
    assert setters == ["pixie_tpu/__init__.py"]
    init = (REPO / "pixie_tpu" / "__init__.py").read_text()
    guard = init.index('if not _os.environ.get("JAX_COMPILATION_CACHE_DIR")')
    assert guard < init.index('"jax_compilation_cache_dir"')


# ----------------------------------------------------------- chip ownership
def test_cli_broker_pins_cpu_role_before_backend_start():
    """JAX_PLATFORMS=tpu on a box without one: a broker whose pin came after
    any backend start would die opening the TPU; pinned first, it starts
    and its start-up line says platform=cpu by role."""
    env = dict(os.environ, JAX_PLATFORMS="tpu", PYTHONPATH=str(REPO))
    p = subprocess.Popen(
        [sys.executable, "-m", "pixie_tpu.cli", "broker", "--port", "0"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)
    try:
        line = p.stdout.readline()
    finally:
        p.terminate()
        _out, err = p.communicate(timeout=30)
    assert "broker listening on" in line, err[-2000:]
    assert "platform=cpu (role: broker" in line


def test_pin_cpu_role_raises_when_a_chip_backend_already_started(monkeypatch):
    import jax

    import pixie_tpu

    class _Tpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Tpu()])
    with pytest.raises(RuntimeError, match="started before the CPU role pin"):
        pixie_tpu.pin_cpu_role("broker")


def test_proc_launcher_names_the_childs_devices(monkeypatch):
    """No chips on the host ⇒ the child is told the CPU platform by name;
    chips on the host and a parent not pinned to the CPU ⇒ refusal; a
    CPU-pinned parent hands out one chip per live child, then refuses."""
    import jax

    from pixie_tpu.serving import elastic

    sleeper = lambda name: [sys.executable, "-c",  # noqa: E731
                            "import time; time.sleep(30)"]
    launcher = elastic.ProcLauncher("127.0.0.1", 1, argv_for=sleeper)
    monkeypatch.setattr(elastic, "_local_chips", lambda: 0)
    assert launcher._device_env() == ({"JAX_PLATFORMS": "cpu"}, None)

    monkeypatch.setattr(elastic, "_local_chips", lambda: 2)
    assert jax.config.jax_platforms == "cpu"  # conftest pins the tests
    procs = []
    try:
        for chip in (0, 1):
            p = launcher.spawn(f"a{chip}")
            procs.append(p)
            assert launcher._chip_owner[chip] is p
        with pytest.raises(elastic.ChipHeldError, match="all 2 chip"):
            launcher.spawn("a2")
        launcher.stop("a0", procs[0])
        env, chip = launcher._device_env()  # the freed chip is handed out
        assert chip == 0 and env["TPU_VISIBLE_CHIPS"] == "0"
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        jax.config.update("jax_platforms", "")
        try:
            with pytest.raises(elastic.ChipHeldError, match="not pinned"):
                launcher._device_env()
        finally:
            jax.config.update("jax_platforms", "cpu")
    finally:
        for p in procs:
            launcher.stop("", p)


# ------------------------------------------------- where did the query run
def _store(rows=4096):
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    rng = np.random.default_rng(0)
    ts = TableStore()
    t = ts.create("http_events", Relation.of(
        ("time_", DT.TIME64NS), ("service", DT.STRING),
        ("latency", DT.FLOAT64), ("status", DT.INT64)), batch_rows=1024)
    t.write({"time_": np.arange(rows, dtype=np.int64),
             "service": np.array([f"svc-{i}" for i in range(4)])[
                 rng.integers(0, 4, rows)],
             "latency": rng.exponential(50.0, rows),
             "status": rng.choice([200, 404], rows)})
    return ts


SCRIPT = """
df = px.DataFrame(table='http_events')
df = df[df.status != 404]
df = df.groupby('service').agg(cnt=('latency', px.count),
                               p50=('latency', px.p50))
px.display(df, 'out')
"""


def _plan(ts):
    from pixie_tpu.compiler import compile_pxl

    return compile_pxl(SCRIPT, ts.schemas()).plan


def test_stats_device_names_platform_and_engine():
    import jax

    from pixie_tpu.engine.executor import PlanExecutor
    from pixie_tpu.status import InvalidArgument

    ts = _store()
    host = PlanExecutor(_plan(ts), ts, mesh=None, force_backend="cpu")
    host.run()
    dev = host.stats["device"]
    assert dev["platform"] == "cpu"
    assert dev["device_kind"] == jax.devices("cpu")[0].device_kind
    assert set(dev["engines"]) <= {"np_partial", "wholeplan",
                                   "xla_cpu_chain"} and dev["engines"]

    chain = PlanExecutor(_plan(ts), ts, mesh=None, force_backend="device")
    chain.run()
    dev = chain.stats["device"]
    assert dev["engines"].get("device_chain", 0) >= 1
    # the label "device" is a route; the platform is what JAX dispatched to
    assert dev["platform"] == jax.devices()[0].platform == "cpu"

    with pytest.raises(InvalidArgument):
        PlanExecutor(_plan(ts), ts, force_backend="tpu")


def test_ran_on_reaches_profile_and_explain():
    from pixie_tpu import observe

    stats = {
        "agents": {
            "pem0": {"rows_scanned": 10, "device": {
                "platform": "tpu", "device_kind": "TPU v5 lite",
                "engines": {"device_chain": 2}}},
            # a folding view refresh reports its scan under matview.exec
            "pem1": {"matview": {"hit": True, "rows_folded": 10, "exec": {
                "device": {"platform": "cpu", "device_kind": "cpu",
                           "engines": {"np_partial": 1}}}}},
            "pem2": {"matview": {"hit": True, "rows_folded": 0}},
        },
        "merger": {"rows_output": 3},
    }
    profile, ops = observe.build_profile("q", "", "broker", 0, 1000, stats)
    assert profile["ran_on"] == ("pem0=tpu/TPU v5 lite[device_chain*2] "
                                 "pem1=cpu/cpu[np_partial*1]")
    assert "ran_on" in observe.PROFILES_RELATION.names()
    assert "ran on: pem0=tpu/TPU v5 lite" in observe.render_explain(
        profile, ops)


def test_view_build_says_where_its_fold_ran():
    from pixie_tpu.parallel.cluster import LocalCluster

    cluster = LocalCluster({"pem0": _store()})
    cluster.query(SCRIPT)  # first sight registers the view
    mv = cluster.query(SCRIPT)["out"].exec_stats["agents"]["pem0"]["matview"]
    assert mv["hit"] and mv["rows_folded"] == 4096
    assert mv["exec"]["device"]["platform"] == "cpu"
    assert mv["exec"]["device"]["engines"]
    mv = cluster.query(SCRIPT)["out"].exec_stats["agents"]["pem0"]["matview"]
    assert mv["rows_folded"] == 0 and "exec" not in mv  # empty delta: no scan


# ------------------------------------------------------------ chip_smoke.py
def test_chip_smoke_without_a_chip_runs_nothing_and_fails():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""  # no result line
    assert "nothing was run" in p.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout == ""


# ------------------------------------------------------------------- native
def test_stale_or_foreign_native_library_is_rebuilt_not_loaded(
        tmp_path, monkeypatch):
    from pixie_tpu.native import build

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    for src in (REPO / "native").glob("*.cc"):
        shutil.copy(src, tmp_path)
    stale = tmp_path / "libpixie_native.so"  # the pre-digest name
    foreign = tmp_path / "libpixie_native.0123456789abcdef.so"
    stale.write_bytes(b"not an ELF")
    foreign.write_bytes(b"not an ELF")
    monkeypatch.setattr(build, "_SRC_DIR", tmp_path)
    monkeypatch.setattr(build, "_lib", None)
    monkeypatch.setattr(build, "_tried", False)
    monkeypatch.setattr(build, "_built_here", False)
    lib = build.load_native()
    assert lib is not None and lib.px_dict_new and lib.px_join_run
    assert build.built_this_process()
    assert build.so_path().name == \
        f"libpixie_native.{build.source_digest()}.so"
    assert not stale.exists() and not foreign.exists()
    # the digest follows the sources' CONTENT
    before = build.source_digest()
    (tmp_path / "dictionary.cc").write_text(
        (tmp_path / "dictionary.cc").read_text() + "\n// edited\n")
    assert build.source_digest() != before


def test_native_build_failure_with_gxx_present_is_an_error(
        tmp_path, monkeypatch):
    from pixie_tpu.native import build

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    (tmp_path / "broken.cc").write_text("this is not C++\n")
    monkeypatch.setattr(build, "_SRC_DIR", tmp_path)
    monkeypatch.setattr(build, "_lib", None)
    monkeypatch.setattr(build, "_tried", False)
    with pytest.raises(build.NativeBuildError, match="g\\+\\+ failed"):
        build.load_native()


# --------------------------------------- TPU formulations, traced on XLA-CPU
def test_tpu_groupby_formulations_trace_and_agree(monkeypatch):
    """The one-hot limb-GEMM group-by only engages when the dispatch
    platform is "tpu"; trace it on XLA-CPU against the scatter form so a
    jax upgrade cannot break it unseen (its TPU arithmetic — bf16 operand
    rounding unless Precision.HIGHEST — is checked by chip_smoke.py)."""
    import jax

    from pixie_tpu.ops import groupby as gb

    rng = np.random.default_rng(3)
    n, groups = 1 << 17, 48
    gid = rng.integers(0, groups, n).astype(np.int32)
    mask = rng.random(n) < 0.9
    cases = {"i64": rng.integers(-(1 << 40), 1 << 40, n),
             "f64": rng.exponential(50.0, n)}
    want = {k: np.asarray(gb.masked_segment_sum(v, gid, groups, mask))
            for k, v in cases.items()}
    want_cnt = np.asarray(gb.masked_segment_count(gid, groups, mask))
    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    assert gb._use_matmul(n, groups)
    got_cnt = jax.jit(lambda g, m: gb.masked_segment_count(g, groups, m))(
        gid, mask)
    np.testing.assert_array_equal(np.asarray(got_cnt), want_cnt)
    for k, v in cases.items():
        got = jax.jit(lambda v, g, m: gb.masked_segment_sum(
            v, g, groups, m))(v, gid, mask)
        if k == "i64":
            np.testing.assert_array_equal(np.asarray(got), want[k])
        else:
            np.testing.assert_allclose(np.asarray(got), want[k], rtol=1e-6)


def test_tpu_formulations_trace_under_shard_map(monkeypatch):
    """On a chip the SPMD partial step traces the one-hot GEMM group-by and
    the limb-factored sketch GEMM INSIDE `jax.shard_map`, whose scans must
    keep their carry's varying-axes type (the first 4-chip run died on
    exactly that).  Trace them over the virtual CPU mesh, two chunks per
    shard, against the single-device scatter formulations."""
    from pixie_tpu.engine.executor import PlanExecutor
    from pixie_tpu.ops import groupby as gb
    from pixie_tpu.parallel.spmd import make_mesh

    ts = _store(rows=1 << 18)
    plain = PlanExecutor(_plan(ts), ts, mesh=None, force_backend="device")
    want = plain.run()["out"].to_pandas().sort_values("service")
    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    # fresh store: the kernel cache must not hand back the scatter kernels
    ts2 = _store(rows=1 << 18)
    ex = PlanExecutor(_plan(ts2), ts2, mesh=make_mesh(2),
                      force_backend="device")
    got = ex.run()["out"].to_pandas().sort_values("service")
    assert ex.stats["spmd_feeds"] >= 1
    np.testing.assert_array_equal(got["cnt"].to_numpy(),
                                  want["cnt"].to_numpy())
    np.testing.assert_array_equal(got["p50"].to_numpy(),
                                  want["p50"].to_numpy())
