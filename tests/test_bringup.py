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

from pixie_tpu.testing.live_chunks import LIVE_RANGES

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
@pytest.mark.parametrize("groups", [48, 2048, 1500, 5000])
def test_tpu_groupby_formulations_trace_and_agree(monkeypatch, groups):
    """The one-hot limb-GEMM group-by only engages when the dispatch
    platform is "tpu"; trace it on XLA-CPU against the scatter form so a
    jax upgrade cannot break it unseen (its TPU arithmetic — bf16 operand
    rounding unless Precision.HIGHEST — is checked by chip_smoke.py).  Up
    to MATMUL_MAX_GROUPS slots it is the flat one-hot, past them the
    factored one, whose last high digit is partly empty where the slot
    count is no multiple of its low digit's width (`onehot2_lo`: 64 at
    1,500 and 2,048 slots, 128 at 5,000)."""
    import jax

    from pixie_tpu.ops import groupby as gb

    rng = np.random.default_rng(3)
    n = 1 << 17
    gid = rng.integers(0, groups, n).astype(np.int32)
    mask = rng.random(n) < 0.9
    cases = {"i64": rng.integers(-(1 << 40), 1 << 40, n),
             "bool": rng.random(n) < 0.3,
             "f64": rng.exponential(50.0, n)}
    # (the scatter takes no bool: the planner hands it the widened column)
    want = {k: np.asarray(gb.masked_segment_sum(
        v.astype(np.int64) if k == "bool" else v, gid, groups, mask))
        for k, v in cases.items()}
    want_cnt = np.asarray(gb.masked_segment_count(gid, groups, mask))
    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    assert gb.agg_form(n, groups) == (
        "onehot" if groups <= gb.MATMUL_MAX_GROUPS else "onehot2")
    got_cnt = jax.jit(lambda g, m: gb.masked_segment_count(g, groups, m))(
        gid, mask)
    np.testing.assert_array_equal(np.asarray(got_cnt), want_cnt)
    for k, v in cases.items():
        got = np.asarray(jax.jit(lambda v, g, m: gb.masked_segment_sum(
            v, g, groups, m))(v, gid, mask))
        assert got.dtype == want[k].dtype and got.shape == (groups,)
        if k == "f64":
            np.testing.assert_allclose(got, want[k], rtol=1e-6)
        else:
            np.testing.assert_array_equal(got, want[k])


def test_agg_form_reads_slots_rows_and_platform(monkeypatch):
    """The form function alone (no traced run: at the cap the operands
    would be heavy on XLA-CPU): the flat one-hot up to MATMUL_MAX_GROUPS,
    the factored one from the next slot to ONEHOT2_MAX_GROUPS, the scatter
    one slot past it, under `_use_matmul`'s floor of rows and on every
    platform but the TPU."""
    from pixie_tpu.ops import groupby as gb

    n = 1 << 20
    assert gb.agg_form(n, 2048) == "scatter"  # this process traces for XLA-CPU
    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    assert gb.agg_form(n, 1) == gb.agg_form(n, gb.MATMUL_MAX_GROUPS) == "onehot"
    assert gb.agg_form(n, gb.MATMUL_MAX_GROUPS + 1) == "onehot2"
    assert gb.agg_form(n, gb.ONEHOT2_MAX_GROUPS) == "onehot2"
    assert gb.agg_form(n, gb.ONEHOT2_MAX_GROUPS + 1) == "scatter"
    assert gb.agg_form(4096, 2048) == "onehot2"
    assert gb.agg_form(1024, 2048) == gb.agg_form(1024, 48) == "scatter"
    assert gb.agg_form(gb.CHUNK_ROWS + 4096, 2048) == "scatter"  # no whole chunks
    for rows, slots in ((n, 48), (n, 2048), (1024, 2048),
                        (n, gb.ONEHOT2_MAX_GROUPS + 1)):
        assert gb._use_matmul(rows, slots) == (
            gb.agg_form(rows, slots) != "scatter")


def _count(v, g, m, groups):
    from pixie_tpu.ops import groupby as gb

    return gb.masked_segment_count(g, groups, m)


def _sum(v, g, m, groups):
    from pixie_tpu.ops import groupby as gb

    return gb.masked_segment_sum(v, g, groups, m)


#: the chunk-loop kernels of ops/groupby.py: (values from a seed, kernel)
LOOP_KERNELS = {
    "count": (lambda rng, n: np.zeros(n), _count),
    "i64": (lambda rng, n: rng.integers(-(1 << 40), 1 << 40, n), _sum),
    "f64": (lambda rng, n: rng.exponential(50.0, n), _sum),
    "f32": (lambda rng, n: rng.exponential(50.0, n).astype(np.float32), _sum),
    "bool": (lambda rng, n: rng.random(n) < 0.3, _sum),
}


def test_live_chunks_is_the_range_of_chunks_with_a_live_row():
    """`live_chunks` gives the chunks from the first with a live row to the
    last, one chunk for an all-masked feed; `scan_sum` sums that range and
    nothing outside it."""
    import jax
    import jax.numpy as jnp

    from pixie_tpu.ops import groupby as gb
    from pixie_tpu.testing.live_chunks import live_mask

    c, ch = 8, 64
    for name, rows in LIVE_RANGES.items():
        a, b = rows(c, ch)
        lo, hi = map(int, jax.jit(lambda m: gb.live_chunks(m, ch))(
            live_mask(name, c, ch)))
        if a == b:
            assert hi - lo == 1, name
        else:
            assert (lo, hi) == (a // ch, -(-b // ch)), name
    xs = jnp.arange(c * ch, dtype=jnp.int64).reshape(c, ch)
    got = jax.jit(lambda x, lo, hi: gb.scan_sum(jnp.sum, x, lo, hi))(xs, 2, 5)
    assert int(got) == int(xs[2:5].sum())


#: (where the live rows sit, slots): every range under the flat one-hot; a
#: masked head and tail under the factored one, whole high digits and a
#: partly empty last one
LOOP_CASES = [(live, 48) for live in LIVE_RANGES] + [
    ("middle", 2048), ("middle", 1500)]


@pytest.mark.parametrize("kernel", list(LOOP_KERNELS))
@pytest.mark.parametrize("live,groups", LOOP_CASES)
def test_chunk_loop_over_live_chunks_is_the_loop_over_all(
        monkeypatch, kernel, live, groups):
    """The one-hot GEMM group-by, flat or factored, visits the chunks that
    hold a live row and no other.  Wherever the live rows sit in the pow2
    bucket, its answer is the answer of the loop over every chunk bit for
    bit, and the scatter formulation's (exactly for counts and integers, to
    F64_SUM_RTOL for float sums)."""
    import jax

    from pixie_tpu.ops import groupby as gb
    from pixie_tpu.testing.live_chunks import live_mask, scan_every_chunk

    c = 8
    n = c * gb.CHUNK_ROWS
    rng = np.random.default_rng(7)
    gid = rng.integers(0, groups, n).astype(np.int32)
    values, kern = LOOP_KERNELS[kernel]
    v = values(rng, n)

    def fn(v, g, m):
        return kern(v, g, m, groups)

    mask = live_mask(live, c, gb.CHUNK_ROWS)
    # (the scatter takes no bool: the planner hands it the widened column)
    want = np.asarray(fn(v.astype(np.int64) if kernel == "bool" else v,
                         gid, mask))
    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    assert gb.agg_form(n, groups) == (
        "onehot" if groups <= gb.MATMUL_MAX_GROUPS else "onehot2")
    got = np.asarray(jax.jit(fn)(v, gid, mask))
    monkeypatch.setattr(gb, "scan_sum", scan_every_chunk)
    # (a new callable: jit would hand back fn's program, traced before)
    every = np.asarray(jax.jit(lambda *a: fn(*a))(v, gid, mask))
    assert got.dtype == every.dtype == want.dtype
    assert got.tobytes() == every.tobytes()
    if kernel in ("f64", "f32"):
        exact = np.zeros(groups)
        np.add.at(exact, gid[mask], v[mask].astype(np.float64))
        np.testing.assert_allclose(got, exact, rtol=gb.F64_SUM_RTOL)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)
    else:
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("groups", [16, 2048, 1500])
def test_chunk_loops_walk_each_shards_own_range_under_shard_map(
        monkeypatch, groups):
    """Under `jax.shard_map` every shard derives its own live range: the
    loop's bounds vary over the mesh axis as its carry does.  Eight shards,
    one live range each, every chunk-loop kernel: each shard's answer is
    the single-device scatter formulation's of its rows.  Past
    MATMUL_MAX_GROUPS slots the group-by kernels are the factored one-hot
    (what `Agent(n_devices=4)` traces for a windowed chart on a chip); the
    sketch has its own form and cap and stays at 16."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from pixie_tpu.ops import groupby as gb
    from pixie_tpu.ops.sketch import LogHistogram
    from pixie_tpu.parallel.spmd import make_mesh, shard_map
    from pixie_tpu.testing.live_chunks import live_mask

    lh = LogHistogram()
    c = 8
    ch = lh.CHUNK
    per = c * ch
    names = list(LIVE_RANGES)
    mesh = make_mesh(len(names))
    rng = np.random.default_rng(11)
    gid = rng.integers(0, groups, (len(names), per)).astype(np.int32)
    mask = np.stack([live_mask(name, c, ch, seed=i)
                     for i, name in enumerate(names)])
    kernels, scatter = dict(LOOP_KERNELS), dict(LOOP_KERNELS)
    if groups <= gb.MATMUL_MAX_GROUPS:
        kernels["sketch"] = (
            lambda rng, n: rng.exponential(50.0, n),
            lambda v, g, m, groups: lh._update_gemm(
                lh.init(groups), g, lh.bin_index(v), m, groups))
        scatter["sketch"] = (
            None, lambda v, g, m, groups: lh._update_segment(
                lh.init(groups), g, lh.bin_index(v), m, groups))
    vals = {k: values(rng, len(names) * per).reshape(len(names), per)
            for k, (values, _fn) in kernels.items()}
    want = {k: [np.asarray(fn(
        vals[k][i].astype(np.int64) if k == "bool" else vals[k][i],
        gid[i], mask[i], groups)) for i in range(len(names))]
        for k, (_values, fn) in scatter.items()}
    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    monkeypatch.setattr(gb, "CHUNK_ROWS", ch)
    assert gb.agg_form(per, groups) == (
        "onehot" if groups <= gb.MATMUL_MAX_GROUPS else "onehot2")
    for k, (_values, fn) in kernels.items():
        f = jax.jit(shard_map(
            lambda v, g, m: fn(v[0], g[0], m[0], groups)[None], mesh=mesh,
            in_specs=(P("agents"),) * 3, out_specs=P("agents")))
        got = np.asarray(f(jnp.asarray(vals[k]), gid, mask))
        for i, name in enumerate(names):
            if k in ("f64", "f32"):
                np.testing.assert_allclose(
                    got[i], want[k][i], rtol=1e-4, atol=1e-2,
                    err_msg=f"{k} {name}")
            else:
                np.testing.assert_array_equal(
                    got[i], want[k][i], err_msg=f"{k} {name}")


#: 512 windows of 512 ns over `_store`'s 2^18 rows x 4 services: 2,048 slots
WINDOWED_SCRIPT = """
df = px.DataFrame(table='http_events')
df = df[df.status != 404]
df.time_ = px.bin(df.time_, 512)
df = df.groupby(['time_', 'service']).agg(cnt=('latency', px.count),
                                          avg=('latency', px.mean))
px.display(df, 'out')
"""


@pytest.mark.parametrize("script,form,exact,close", [
    (SCRIPT, "onehot", ["cnt", "p50"], []),
    (WINDOWED_SCRIPT, "onehot2", ["cnt"], ["avg"]),
], ids=["by_service", "windowed"])
def test_tpu_formulations_trace_under_shard_map(monkeypatch, script, form,
                                                exact, close):
    """On a chip the SPMD partial step traces the one-hot GEMM group-by and
    the limb-factored sketch GEMM INSIDE `jax.shard_map`, whose scans must
    keep their carry's varying-axes type (the first 4-chip run died on
    exactly that).  Trace them over the virtual CPU mesh, two chunks per
    shard, against the single-device scatter formulations: four slots
    under the flat one-hot, a windowed chart's 2,048 under the factored
    one; the chain's frame says the form its shards traced."""
    from pixie_tpu.compiler import compile_pxl
    from pixie_tpu.engine.executor import PlanExecutor
    from pixie_tpu.ops import groupby as gb
    from pixie_tpu.parallel.spmd import make_mesh

    def run(mesh):
        # fresh store: the kernel cache must not hand back another form
        ts = _store(rows=1 << 18)
        ex = PlanExecutor(compile_pxl(script, ts.schemas()).plan, ts,
                          mesh=mesh, force_backend="device")
        out = ex.run()["out"].to_pandas()
        keys = [c for c in ("time_", "service") if c in out]
        return ex, out.sort_values(keys).reset_index(drop=True)

    plain, want = run(None)
    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    ex, got = run(make_mesh(2))
    assert ex.stats["spmd_feeds"] >= 1
    (span,) = [r["span"] for r in ex.op_stats if "agg_form" in r.get("span", {})]
    (scatter,) = [r["span"] for r in plain.op_stats
                  if "agg_form" in r.get("span", {})]
    assert span["agg_form"] == form and scatter["agg_form"] == "scatter"
    assert span["groups"] == scatter["groups"] == (
        4 if form == "onehot" else 2048)
    assert len(got) == len(want)
    for col in exact:
        np.testing.assert_array_equal(got[col].to_numpy(),
                                      want[col].to_numpy())
    for col in close:
        np.testing.assert_allclose(got[col].to_numpy(), want[col].to_numpy(),
                                   rtol=gb.F64_SUM_RTOL)
