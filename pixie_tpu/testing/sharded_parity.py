"""Fixture of tests/test_sharded_parity.py: one workload, and three ways to
run it sharded that each compare the result bit for bit with the
single-device one.

filter→map→partial-agg runs shard-local over a device mesh with ONE
in-program collective merge (psum/pmin/pmax) at the blocking boundary.
Three runners share the workload (`build_store` / chain shape):

  * `run_local(...)` — the ENGINE path: a real TableStore + PlanExecutor
    over an n-device mesh, so the run exercises the sharded GSPMD feed
    layout (NamedSharding placement + the sharded-resident tier), per-shard
    transfer accounting, and the SPMD partial step — compared bit-for-bit
    against `PlanExecutor(mesh=None)`.
  * `run_shuffled_join(...)` — the pod-scale shuffle join: one agent's
    8-device mesh, the planner widening the repartition to mesh size, both
    sides exchanged with ONE `lax.all_to_all` each, per-partition joins
    riding the radix device join — compared against the single-device join.
  * `run_multihost(...)` (via `main --worker`; `run_subprocess` drives it
    over virtual CPU devices) — the 2-process `jax.distributed` job: each
    process feeds ONLY its host-local shards
    (`jax.make_array_from_process_local_data`) and the jitted collective
    merge spans processes — the scaling recipe of SNIPPETS [1]-[3]'s
    pjit/mesh API surface.

Every aggregate in the workload is ORDER-INDEPENDENT at the bit level
(count/sum/mean over ints, min/max, log-histogram p50 whose counts are
integer-valued), so "bit-equal to the single-device result" is a checked
invariant, not an rtol claim — see `assert_bitequal`.  Nothing here times
anything: a speed is measured on the chip, by `benchmarks/`.
"""
from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import numpy as np

N_SERVICES = 16
STATUSES = (200, 404, 500)


# ------------------------------------------------------------------ workload
def shard_cols(rows: int, shard: int, n_shards: int) -> dict:
    """Generate ONE row-block shard of the workload, seeded by shard index —
    any process can build exactly its shards (multihost host-local feeds)
    while the oracle rebuilds the full table from the same seeds."""
    n = rows // n_shards
    rng = np.random.default_rng(1234 + shard)
    return {
        "time_": (shard * n + np.arange(n, dtype=np.int64)) * 1000,
        "service": rng.integers(0, N_SERVICES, n).astype(np.int32),
        "status": rng.choice(np.asarray(STATUSES, dtype=np.int64), n),
        "bytes": rng.integers(0, 1 << 20, n).astype(np.int64),
        "latency": rng.exponential(50.0, n),
    }


def build_store(rows: int, batch_rows: int | None = None):
    """TableStore holding the workload with EVERY row sealed (batch_rows
    divides rows), so the sharded-resident tier covers the whole feed."""
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    ts = TableStore()
    rel = Relation.of(
        ("time_", DT.TIME64NS), ("service", DT.STRING),
        ("status", DT.INT64), ("bytes", DT.INT64), ("latency", DT.FLOAT64),
    )
    if batch_rows is None:
        batch_rows = rows // 16 if rows % 16 == 0 else 1 << 16
    t = ts.create("http_events", rel, batch_rows=batch_rows,
                  max_bytes=1 << 38)
    services = np.array([f"svc-{i}" for i in range(N_SERVICES)])
    n_chunks = max(1, rows // (1 << 21))
    # chunk boundaries aligned to the shard generator so data is identical
    # however it is produced
    n_shards = n_chunks
    while rows % n_shards:
        n_shards -= 1
    for i in range(n_shards):
        cols = shard_cols(rows, i, n_shards)
        t.write({
            "time_": cols["time_"],
            "service": services[cols["service"]],
            "status": cols["status"],
            "bytes": cols["bytes"],
            "latency": cols["latency"],
        })
    return ts


def agg_plan():
    """filter(status != 404) → map(lat_us = latency*1000) →
    groupby(service, status) agg — every value exactly mergeable."""
    from pixie_tpu.plan import (
        AggExpr, AggOp, Call, Column, FilterOp, MapOp, MemorySinkOp,
        MemorySourceOp, Plan, lit,
    )

    p = Plan()
    src = p.add(MemorySourceOp(table="http_events"))
    f = p.add(FilterOp(expr=Call("not_equal", (Column("status"), lit(404)))),
              parents=[src])
    m = p.add(MapOp(exprs=[
        ("service", Column("service")),
        ("status", Column("status")),
        ("bytes", Column("bytes")),
        ("lat_us", Call("multiply", (Column("latency"), lit(1000.0)))),
    ]), parents=[f])
    agg = p.add(AggOp(groups=["service", "status"], values=[
        AggExpr("cnt", "count", None),
        AggExpr("b", "sum", "bytes"),
        AggExpr("avg_b", "mean", "bytes"),
        AggExpr("lo", "min", "lat_us"),
        AggExpr("hi", "max", "lat_us"),
        AggExpr("p50", "p50", "lat_us"),
    ]), parents=[m])
    p.add(MemorySinkOp(name="output"), parents=[agg])
    return p


def assert_bitequal(got, want, keys=("service", "status")) -> None:
    """Bit-level equality of two QueryResults/HostBatches, row order
    normalized by the key columns.  Raises AssertionError with the first
    differing column."""
    gc = _result_cols(got)
    wc = _result_cols(want)
    assert set(gc) == set(wc), (sorted(gc), sorted(wc))

    def sortable(x):
        return x.astype(str) if x.dtype == object else x

    go = np.lexsort(tuple(sortable(gc[k]) for k in reversed(keys)))
    wo = np.lexsort(tuple(sortable(wc[k]) for k in reversed(keys)))
    for name in sorted(gc):
        a, b = gc[name][go], wc[name][wo]
        assert a.dtype == b.dtype and a.shape == b.shape, (
            name, a.dtype, b.dtype, a.shape, b.shape)
        assert np.array_equal(a, b), (
            f"column {name!r} not bit-equal: "
            f"{a[:5]!r} vs {b[:5]!r}")


def _result_cols(res) -> dict:
    if hasattr(res, "dictionaries"):  # QueryResult: dict cols by VALUE
        out = {}
        for n, col in res.columns.items():
            d = res.dictionaries.get(n)
            out[n] = (np.asarray(d.decode(col), dtype=object)
                      if d is not None else np.asarray(col))
        return out
    return {k: np.asarray(v) for k, v in res.cols.items()}


# ------------------------------------------------------- engine-path runner
def run_local(rows: int, n_devices: int = 8) -> dict:
    """The engine-path sharded run: PlanExecutor over an n-device mesh, one
    cold run (compiles, admits the sharded tier) and one warm, the warm one
    compared bit for bit with the single-device executor.  Returns the warm
    run's feed and skew accounting."""
    import jax

    from pixie_tpu.engine.executor import PlanExecutor
    from pixie_tpu.parallel.spmd import make_mesh

    if len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, have {len(jax.devices())}")
    ts = build_store(rows)
    plan = agg_plan()
    mesh = make_mesh(n_devices)
    for _ in range(2):
        ex = PlanExecutor(plan, ts, mesh=mesh, force_backend="device")
        out = ex.run()["output"]
    single = PlanExecutor(plan, ts, mesh=None, force_backend="device")
    assert_bitequal(out, single.run()["output"])
    stats = ex.stats
    return {
        "rows": rows,
        "n_devices": n_devices,
        "bit_equal": True,
        "spmd_feeds": int(stats.get("spmd_feeds", 0)),
        "shard_skew_frac": stats.get("shard_skew_frac"),
    }


def join_plan():
    from pixie_tpu.plan import (
        AggExpr, AggOp, JoinOp, MemorySinkOp, MemorySourceOp, Plan,
    )

    p = Plan()
    left = p.add(MemorySourceOp(table="left_t", columns=["k", "lv"]))
    right = p.add(MemorySourceOp(table="right_t", columns=["k", "rv"]))
    j = p.add(JoinOp(how="inner", left_on=["k"], right_on=["k"],
                     output=[("left", "k", "k"), ("left", "lv", "lv"),
                             ("right", "rv", "rv")]),
              parents=[left, right])
    agg = p.add(AggOp(groups=[], values=[
        AggExpr("n", "count", None), AggExpr("s", "sum", "rv"),
    ]), parents=[j])
    p.add(MemorySinkOp(name="out"), parents=[agg])
    return p


def build_join_store(rows_per_side: int):
    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    ts = TableStore()
    rng = np.random.default_rng(77)
    lt = ts.create("left_t", Relation.of(("k", DT.INT64), ("lv", DT.INT64)),
                   batch_rows=1 << 16, max_bytes=1 << 38)
    rt = ts.create("right_t", Relation.of(("k", DT.INT64), ("rv", DT.INT64)),
                   batch_rows=1 << 16, max_bytes=1 << 38)
    chunk = 1 << 21
    for t, col in ((lt, "lv"), (rt, "rv")):
        written = 0
        while written < rows_per_side:
            n = min(chunk, rows_per_side - written)
            t.write({"k": rng.integers(0, rows_per_side, n),
                     col: rng.integers(0, 1 << 20, n)})
            written += n
    return ts


def run_shuffled_join(rows_per_side: int, n_devices: int = 8) -> dict:
    """Pod-scale shuffled equijoin: ONE agent whose n-device mesh widens the
    planner's repartition to n partitions, both sides exchanged via ONE
    lax.all_to_all each, per-partition radix joins — vs the single-device
    executor join, bit-equal (the post-join aggregate is over ints)."""
    from pixie_tpu.engine.executor import PlanExecutor
    from pixie_tpu.parallel.cluster import LocalCluster

    ts = build_join_store(rows_per_side)
    cluster = LocalCluster({"pem0": ts}, n_devices_per_agent=n_devices)
    plan = join_plan()
    dp = cluster.planner.plan(plan)
    if not dp.join_stages or dp.join_stages[0].n_parts != n_devices:
        raise RuntimeError(
            f"planner did not widen the shuffle to the mesh: "
            f"{[s.n_parts for s in dp.join_stages]}")
    res = cluster.execute(plan)["out"]
    agents = res.exec_stats["agents"]
    shuffles = sum(s.get("mesh_shuffles", 0) for s in agents.values())
    if shuffles < 2:
        raise RuntimeError(f"join sides did not mesh-exchange: {shuffles}")
    single = PlanExecutor(plan, ts, mesh=None).run()["out"]
    assert_bitequal(res, single, keys=("n",))
    return {
        "rows": 2 * rows_per_side,
        "n_parts": dp.join_stages[0].n_parts,
        "all_to_all_exchanges": int(shuffles),
        "bit_equal": True,
        "join_rows": int(np.asarray(res.decoded("n"))[0]),
    }


# ------------------------------------------------------- multihost runner
def _chain_kernel():
    """The multihost run's fragment kernel: the same
    filter→map→partial-agg chain, at the ChainKernel level (the multihost
    data plane feeds the kernel directly — each process owns only its
    host-local shards, so the TableStore/executor layer stays per-process)."""
    from pixie_tpu.engine.executor import ChainKernel, GroupKey
    from pixie_tpu.plan import Call, Column, FilterOp, MapOp, lit
    from pixie_tpu.table.dictionary import Dictionary
    from pixie_tpu.types import DataType as DT
    from pixie_tpu.udf import registry

    svc_dict = Dictionary([f"svc-{i}" for i in range(N_SERVICES)])
    dtypes = {"time_": DT.TIME64NS, "service": DT.STRING,
              "status": DT.INT64, "bytes": DT.INT64, "latency": DT.FLOAT64}
    chain = [
        FilterOp(expr=Call("not_equal", (Column("status"), lit(404)))),
        MapOp(exprs=[
            ("service", Column("service")),
            ("status", Column("status")),
            ("bytes", Column("bytes")),
            ("lat_us", Call("multiply", (Column("latency"), lit(1000.0)))),
        ]),
    ]
    kern = ChainKernel(dtypes, {"service": svc_dict}, chain, registry,
                       time_col="time_")
    status_lut = kern.ctx.ec._add_lut(
        np.asarray(STATUSES, dtype=np.int64))
    keys = [
        GroupKey("service", "dict", N_SERVICES, DT.STRING, svc_dict,
                 key_sval=kern.ctx.sym["service"]),
        GroupKey("status", "intdevice", 4, DT.INT64,
                 Dictionary(list(STATUSES)), src_name="status",
                 lut_name=status_lut),
    ]
    num_groups = N_SERVICES * 4
    from pixie_tpu.plan import AggExpr

    udas, init_specs = [], []
    for ae in [AggExpr("cnt", "count", None), AggExpr("b", "sum", "bytes"),
               AggExpr("lo", "min", "lat_us"),
               AggExpr("hi", "max", "lat_us"),
               AggExpr("p50", "p50", "lat_us")]:
        uda = registry.uda(ae.fn)
        vb = kern.ctx.sym[ae.arg].build if ae.arg else None
        in_dt = np.int64 if ae.arg == "bytes" else (
            np.float64 if ae.arg else None)
        udas.append((ae.out_name, uda, vb))
        init_specs.append((ae.out_name, uda, in_dt))
    kern.make_agg_step(keys, udas, num_groups)
    return kern, udas, init_specs, num_groups


def run_multihost(rows: int, mesh) -> dict:
    """One process's share of the multihost sharded agg: feed ONLY
    host-local shards, run the lifted partial step (shard-local chain + one
    in-program collective merge) over the GLOBAL mesh, verify bit-equality
    vs the single-device kernel on process 0."""
    import jax

    from pixie_tpu.engine.executor import INT64_MAX, INT64_MIN
    from pixie_tpu.parallel.spmd import (
        AGENT_AXIS, per_shard_valid, reduce_tree_for, spmd_partial_step,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    kern, udas, init_specs, num_groups = _chain_kernel()
    n_dev = int(mesh.size)
    per = -(-rows // n_dev)
    padded = per * n_dev
    names = ("time_", "service", "status", "bytes", "latency")
    sharding = NamedSharding(mesh, P(AGENT_AXIS))
    flat = list(mesh.devices.flat)
    me = jax.process_index()
    mine = [i for i, d in enumerate(flat) if d.process_index == me]
    local = {k: [] for k in names}
    for i in mine:
        cols = shard_cols(padded, i, n_dev)
        for k in names:
            local[k].append(cols[k])
    local = {k: np.concatenate(v) for k, v in local.items()}
    gcols = {
        k: jax.make_array_from_process_local_data(
            sharding, local[k], (padded,))
        for k in names
    }
    nv = per_shard_valid(rows, padded, n_dev)
    gnv = jax.make_array_from_process_local_data(
        sharding, nv[mine[0]: mine[-1] + 1], (n_dev,))

    def init_fn():
        return {name: uda.init(num_groups, in_dt)
                for name, uda, in_dt in init_specs}

    step = spmd_partial_step(kern.raw_agg_step, init_fn,
                             reduce_tree_for(udas), len(kern.limit_ns),
                             mesh)
    t_lo, t_hi = np.int64(INT64_MIN), np.int64(INT64_MAX)

    out = step(gcols, gnv, t_lo, t_hi, kern.luts)
    state = jax.tree.map(np.asarray, out)
    result = {
        "rows": rows,
        "n_devices": n_dev,
        "processes": int(jax.process_count()),
    }
    if jax.process_index() == 0:
        # single-device oracle over the FULL regenerated data — bit-equal
        full = {k: np.concatenate([shard_cols(padded, i, n_dev)[k]
                                   for i in range(n_dev)]) for k in names}
        state0 = init_fn()
        limits = np.full((max(1, len(kern.limit_ns)),), INT64_MAX,
                         dtype=np.int64)
        with jax.default_device(jax.local_devices()[0]):
            ref, _cnt, _cons = jax.jit(kern.raw_agg_step)(
                full, np.int64(rows), t_lo, t_hi, limits, kern.luts,
                state0)
        ref = jax.tree.map(np.asarray, ref)
        flat_s, _ = jax.tree.flatten(state)
        flat_r, _ = jax.tree.flatten(ref)
        result["bit_equal"] = all(
            np.array_equal(a, b) for a, b in zip(flat_s, flat_r))
        assert result["bit_equal"], "sharded state != single-device state"
    return result


# ---------------------------------------------------- subprocess harness
def _worker_env(devices_per_proc: int) -> dict:
    from pixie_tpu import flags as _flags

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return {
        # PL_*/PX_* engine config crosses the fork through the flag
        # registry, not ad-hoc os.environ reads: whatever this process
        # overrode (env or set_for_testing) re-parses in the worker
        **_flags.env_exports(),
        "PATH": os.environ.get("PATH", "/usr/bin:/bin:/usr/local/bin"),
        "HOME": os.environ.get("HOME", "/tmp"),
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS":
            f"--xla_force_host_platform_device_count={devices_per_proc}",
        "PYTHONPATH": repo,
    }


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_subprocess(rows: int, processes: int = 2, devices_per_proc: int = 4,
                   timeout: float = 1200.0) -> dict:
    """The CPU multi-process form: `processes` ×
    `devices_per_proc` VIRTUAL CPU devices joined through a
    jax.distributed coordinator (`_worker_env` names the CPU platform
    explicitly, and the result says `"platform": "cpu"`).  On a host with
    chips the sharded run is `run_local`: ONE process over the local
    chips — a chip belongs to one process, so workers are never spawned
    onto them."""
    coord = f"127.0.0.1:{_free_port()}"
    env = _worker_env(devices_per_proc)
    base = [sys.executable, "-m", "pixie_tpu.testing.sharded_parity",
            "--worker", "--rows", str(rows)]
    procs = [
        subprocess.Popen(
            base + ["--coordinator", coord, "--processes", str(processes),
                    "--process-id", str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True)
        for pid in range(processes)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            if p.returncode != 0:
                raise RuntimeError(
                    f"sharded worker failed: {err[-2000:]!r}")
            outs.append(out)
    finally:
        for q in procs:  # peers block on a dead coordinator otherwise
            if q.poll() is None:
                q.kill()
    return json.loads(outs[0].strip().splitlines()[-1])


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rows", type=int, required=True)
    ap.add_argument("--coordinator", type=str, default="")
    ap.add_argument("--processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    args = ap.parse_args(argv)

    import pixie_tpu  # noqa: F401  (x64 flip before any jax use)
    import jax

    from pixie_tpu.parallel import multihost

    if args.coordinator:
        ok = multihost.init_multihost(args.coordinator, args.processes,
                                      args.process_id)
        assert ok, "jax.distributed init failed"
        mesh = multihost.global_mesh()
    else:
        from pixie_tpu.parallel.spmd import make_mesh

        mesh = make_mesh(len(jax.devices()))
    assert mesh is not None, "no multi-device mesh available"
    out = run_multihost(args.rows, mesh)
    if jax.process_index() == 0:
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
