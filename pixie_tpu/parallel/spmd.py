"""SPMD distributed aggregation over a device mesh.

The TPU-native replacement for the reference's distributed plan fan-out
(SURVEY.md §2.5): where Pixie replicates a plan fragment per PEM and merges
serialized UDA state over gRPC (planpb partial_agg/finalize_results,
plan.proto:250-257; splitter/partial_op_mgr), we run the SAME fragment kernel as
an SPMD program over a `jax.sharding.Mesh` axis ("agents" — the PEM analog) and
merge aggregate state *inside* the jitted program with XLA collectives riding
ICI: psum for additive state, pmin/pmax for extremal state.  Because every UDA
declares per-leaf reduce ops (see udf.udf.UDA), the collective merge is derived
mechanically — no per-UDA serialization code.

Correctness requirement: UDA init states must be reduction identities (zeros for
add, ±inf for min/max) — they are — since each device starts from the same
replicated init and contributes only its shard's rows.
"""
from __future__ import annotations

import os as _os
import threading as _threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pixie_tpu import flags as _flags

AGENT_AXIS = "agents"

shard_map = jax.shard_map

#: XLA-CPU collectives rendezvous across ALL local participants; two
#: concurrent multi-device programs in one process (concurrent agent
#: executors in tests / LocalCluster) can split the intra-op thread pool
#: between their rendezvous and deadlock (stuck AllReduceParticipantData
#: waits).  Collective-bearing executions on a CPU
#: mesh therefore serialize through one lock and block before releasing; on
#: real accelerator meshes executions stay async and unlocked.
_COLLECTIVE_EXEC_LOCK = _threading.Lock()

_SERIALIZE_FLAG = _flags.define_int(
    "PX_SERIALIZE_CPU_COLLECTIVES", -1,
    "serialize collective-bearing mesh executions through one process lock: "
    "-1 = auto (on iff every mesh device is an XLA-CPU virtual device sharing "
    "the host intra-op pool), 0 = never (trust the runtime's rendezvous), "
    "1 = always (debugging aid)")

_flags.define_str(
    "PIXIE_TPU_SPMD", "auto",
    "default-mesh gate: 0 disables SPMD over local devices (single-device "
    "execution); anything else auto-builds the pow2-clamped mesh.  Live: "
    "read at first default_mesh() use, not import", live=True)

_gate_lock = _threading.Lock()
_gate_cache: dict | None = None


def collective_gate(mesh: Mesh | None = None, refresh: bool = False) -> dict:
    """The process-wide collective-serialization decision, decided once and
    recorded like `ops.join_device.device_join_gate` — the XLA-CPU rendezvous
    workaround is a GATED behavior with an observable reason, not an
    unconditional code path.

    → {"serialize", "reason", "flag", "platform", "mesh_devices",
       "host_cores"}.  PX_SERIALIZE_CPU_COLLECTIVES forces it (0/1); -1 =
    auto: serialize iff every mesh device is an XLA-CPU virtual device —
    those share ONE host intra-op thread pool, so two concurrent
    collective programs can split the pool between their rendezvous and
    deadlock (`host_cores` vs `mesh_devices` records how oversubscribed the
    pool is).  Real accelerator meshes have per-device hardware queues:
    the gate stays OFF and executions remain async.  The executor also
    records the decision in stats["device"]["collective_gate"].
    """
    global _gate_cache
    devices = (list(mesh.devices.flat) if mesh is not None
               else list(jax.devices()))
    platform = devices[0].platform
    n_mesh = mesh.size if mesh is not None else len(devices)
    with _gate_lock:
        flag = _flags.get("PX_SERIALIZE_CPU_COLLECTIVES")
        key = (flag, platform, n_mesh)
        if _gate_cache is not None and not refresh \
                and _gate_cache.get("_key") == key:
            return _gate_cache
        all_cpu = all(d.platform == "cpu" for d in devices)
        out = {"_key": key, "flag": flag, "platform": platform,
               "mesh_devices": int(n_mesh),
               "host_cores": _os.cpu_count() or 1}
        if flag == 0:
            out.update(serialize=False, reason="forced_off")
        elif flag == 1:
            out.update(serialize=True, reason="forced_on")
        elif all_cpu:
            out.update(serialize=True, reason="xla_cpu_shared_pool")
        else:
            out.update(serialize=False, reason="accelerator_hw_queues")
        from pixie_tpu import metrics as _metrics

        _metrics.gauge_set(
            "px_collective_serialize_enabled", float(out["serialize"]),
            help_="1 when collective-bearing mesh executions serialize "
                  "through the XLA-CPU rendezvous workaround lock "
                  "(PX_SERIALIZE_CPU_COLLECTIVES; off on accelerators)")
        _gate_cache = out
        return out


def serialize_cpu_collectives(jit_fn, mesh: Mesh):
    if not collective_gate(mesh)["serialize"]:
        return jit_fn

    def run(*args, **kwargs):
        with _COLLECTIVE_EXEC_LOCK:
            out = jit_fn(*args, **kwargs)
            jax.block_until_ready(out)
            return out

    return run


def make_mesh(n_devices: int | None = None, axis: str = AGENT_AXIS) -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, have {len(devs)} ({devs[0].platform})"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


_DEFAULT_MESH: Mesh | None = None
_DEFAULT_MESH_READY = False
_DEFAULT_MESH_LOCK = __import__("threading").Lock()


def default_mesh() -> Mesh | None:
    """Process-wide mesh over ALL local devices, or None when single-device /
    disabled via PIXIE_TPU_SPMD=0.  This is what the engine's real query path
    shards over (the reference's per-PEM fan-out becomes mesh axes).
    Thread-safe: concurrent agent executors race this on first use."""
    global _DEFAULT_MESH, _DEFAULT_MESH_READY
    if not _DEFAULT_MESH_READY:
        with _DEFAULT_MESH_LOCK:
            if not _DEFAULT_MESH_READY:
                n = len(jax.devices())
                # Clamp to a power of two: feed buckets are pow2-sized, so a
                # 6-device mesh would fail every `bucket % n_dev == 0` gate
                # and silently disable SPMD; a 4-device mesh actually runs.
                n = 1 << (n.bit_length() - 1)
                if _flags.get("PIXIE_TPU_SPMD") != "0" and n > 1:
                    _DEFAULT_MESH = make_mesh(n)
                # publish the mesh BEFORE the ready flag (lock-free readers)
                _DEFAULT_MESH_READY = True
    return _DEFAULT_MESH


def reduce_tree_for(udas: list) -> dict:
    """State-structure-matching tree of reduce ops for a list of
    (out_name, UDA, value_builder) triples (the executor's agg spec)."""
    return {name: uda.reduce_ops() for name, uda, _vb in udas}


_COLLECTIVE = {"add": lax.psum, "min": lax.pmin, "max": lax.pmax}


def collective_merge(state, reduce_tree, axis_name: str):
    """Merge per-device partial agg states across a mesh axis."""
    return jax.tree.map(
        lambda op, x: _COLLECTIVE[op](x, axis_name), reduce_tree, state,
        is_leaf=lambda x: isinstance(x, str),
    )


def collective_merge_carry(carry, new_state, reduce_tree, axis_name: str):
    """Merge states across a mesh axis when `new_state` was seeded from a
    REPLICATED carry (multi-batch streaming).

    psum of the full state would multiply the carried prefix by the axis size,
    so additive leaves sum only the per-device delta; min/max collectives are
    idempotent over the replicated carry and merge the full state directly.
    """

    def leaf(op, c, x):
        if op == "add":
            return c + lax.psum(x - c, axis_name)
        return _COLLECTIVE[op](x, axis_name)

    return jax.tree.map(leaf, reduce_tree, carry, new_state,
                        is_leaf=lambda x: isinstance(x, str))


def spmd_agg_step(raw_step, reduce_tree, mesh: Mesh, axis: str = AGENT_AXIS):
    """Lift a single-device agg step into an SPMD step over `mesh`.

    raw_step(cols, n_valid, t_lo, t_hi, limits, luts, state) -> (state, count)
    is the UNJITTED kernel from ChainKernel.make_agg_step (each device sees its
    local shard).  `limits` is the kernel's per-LimitOp budget vector
    (ChainKernel.init_limits()); a scalar broadcasts one shared budget and is
    only correct for chains with ≤1 limit.  The lifted step takes:
      cols        — leading dim sharded over `axis` ([n_dev, rows_per_dev, ...])
      n_valid     — int64[n_dev], per-shard valid counts
      state       — replicated identity-initialized state
    and returns the MERGED (replicated) state plus the global passed-row count.
    """

    def local(cols, n_valid, t_lo, t_hi, limit, luts, state):
        # shard_map hands us local blocks with the sharded leading axis of size 1.
        cols = jax.tree.map(lambda x: x[0], cols)
        nv = n_valid[0]
        new_state, cnt, _consumed = raw_step(cols, nv, t_lo, t_hi, limit, luts, state)
        # `state` may be a replicated carry from a previous batch, so additive
        # leaves must psum only this batch's delta (see collective_merge_carry).
        merged = collective_merge_carry(state, new_state, reduce_tree, axis)
        total = lax.psum(cnt, axis)
        return merged, total

    shard = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P(), P(), P()),
        out_specs=(P(), P()),
    )
    return serialize_cpu_collectives(jax.jit(shard), mesh)


def spmd_partial_step(raw_step, init_state_fn, reduce_tree, n_limits: int,
                      mesh: Mesh, axis: str = AGENT_AXIS):
    """Lift an agg kernel into the engine's SPMD per-feed partial step.

    Unlike spmd_agg_step (which threads an explicit replicated state for the
    streaming/carry case), this is the shape the real query path uses: each
    feed is an INDEPENDENT execution — identity state created inside the
    trace, per-device partial update over the feed's local 1-D shard, then an
    in-program collective merge (psum/pmin/pmax over ICI).  The host merges
    feeds afterwards with ChainKernel.make_merge_states.

      lifted(cols, n_valid, t_lo, t_hi, luts) -> replicated merged state
        cols:    1-D padded columns sharded over `axis` (length % n_dev == 0)
        n_valid: int64[n_dev] per-shard valid counts, sharded over `axis`
    """

    def local(cols, n_valid, t_lo, t_hi, luts):
        state = init_state_fn()
        limits = jnp.full((max(1, n_limits),), np.iinfo(np.int64).max,
                          dtype=jnp.int64)
        new_state, cnt, _consumed = raw_step(
            cols, n_valid[0], t_lo, t_hi, limits, luts, state
        )
        return collective_merge(new_state, reduce_tree, axis)

    shard = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P()),
        out_specs=P(),
    )
    return serialize_cpu_collectives(jax.jit(shard), mesh)


def spmd_multi_partial_step(members: list, mesh: Mesh, axis: str = AGENT_AXIS):
    """Fuse N sibling agg kernels over ONE shared sharded feed into a single
    SPMD program (the multi-query gang's mesh variant — see
    engine.executor._multi_partial_agg).

    members: [(raw_step, init_state_fn, reduce_tree, n_limits)] — the same
    pieces `spmd_partial_step` lifts one at a time.  The fused program runs
    every member's per-device partial update over the same local shard and
    merges each member's state in-program (one execution per feed wave for
    the whole gang instead of N), returning a tuple of replicated states:

      lifted(cols, n_valid, t_lo, t_hi, luts_tuple) -> tuple(states)

    The collective-serialization gate wraps the WHOLE fused program once —
    fusing N collective merges into one execution is exactly what the
    CPU-mesh rendezvous lock wants (one execution, one rendezvous set).
    """

    def local(cols, n_valid, t_lo, t_hi, luts_tuple):
        outs = []
        for (raw_step, init_state_fn, reduce_tree, n_limits), luts in zip(
                members, luts_tuple):
            state = init_state_fn()
            limits = jnp.full((max(1, n_limits),), np.iinfo(np.int64).max,
                              dtype=jnp.int64)
            new_state, _cnt, _consumed = raw_step(
                cols, n_valid[0], t_lo, t_hi, limits, luts, state
            )
            outs.append(collective_merge(new_state, reduce_tree, axis))
        return tuple(outs)

    shard = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(), P(), P()),
        out_specs=P(),
    )
    return serialize_cpu_collectives(jax.jit(shard), mesh)


def shard_batches(cols: dict, n_devices: int) -> dict:
    """Host helper: split padded columns into [n_dev, rows/n_dev] blocks.

    Rows must already be padded to a multiple of n_devices. Pair with
    `per_shard_valid` for the matching per-shard valid counts.
    """
    out = {}
    for k, v in cols.items():
        n = len(v)
        assert n % n_devices == 0, f"{k}: {n} rows not divisible by {n_devices}"
        out[k] = v.reshape(n_devices, n // n_devices)
    return out


def per_shard_valid(n_valid: int, total_rows: int, n_devices: int) -> np.ndarray:
    """Valid counts per shard for a prefix-valid padded batch split row-major."""
    per = total_rows // n_devices
    starts = np.arange(n_devices) * per
    return np.clip(n_valid - starts, 0, per).astype(np.int64)
