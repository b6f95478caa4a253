"""pixie_tpu: a TPU-native telemetry-analytics framework with the capabilities of Pixie.

Architecture (see ARCHITECTURE.md): telemetry enters an in-memory columnar table store
where variable-width values (strings, 128-bit UPIDs) are dictionary-encoded to dense
int32 codes at ingest.  PxL queries compile through an IR into plan fragments; each
fragment is lowered to a single fused `jax.jit` function over fixed-shape padded
columnar tensors and executed on TPU.  Distribution is SPMD: the same fragment runs
over a `jax.sharding.Mesh` with partial aggregates merged by XLA collectives (psum)
instead of the reference's per-node C++ exec + gRPC result streams.

Reference parity map: /root/reference (easyops-cn/pixie), see SURVEY.md.
"""
import os as _os
import pathlib as _pathlib

import jax as _jax

# Timestamps are int64 nanoseconds (TIME64NS, reference src/shared/types/typespb/
# types.proto:26-33); the engine therefore requires 64-bit mode globally.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compile cache, placed from OUTSIDE: where
# JAX_COMPILATION_CACHE_DIR is set jax reads it itself and this program sets
# no directory.  Unset, every process of a checkout (broker, agent,
# chip_smoke.py, spawned children) shares one fixed, git-ignored directory —
# the path is part of the cache key, so it is never derived from a pid, a
# temporary name or the time.  Which compiles are kept is jax's own default
# (those over a second: on the chip they were 3 of a cold start's 12
# programs and most of its compile time, PERF.md); keeping every small one
# costs each XLA-CPU compile more than it saves.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        str(_pathlib.Path(__file__).resolve().parent.parent / ".jax_cache"))

from pixie_tpu.types import DataType, SemanticType, Relation  # noqa: E402,F401
from pixie_tpu.table import Table, TableStore, RowBatch  # noqa: E402,F401
import pixie_tpu.metadata  # noqa: E402,F401  (registers metadata UDFs)

__version__ = "0.1.0"


def pin_cpu_role(role: str) -> str:
    """Pin THIS process's JAX to the CPU platform, on purpose: a chip
    belongs to one process, the agent, so a broker or a client never opens
    one (a second process that touched the chip would fail or hang, or take
    it from the agent).  Must run before any JAX backend starts; starts the
    CPU backend and returns the start-up line's "platform=cpu (role: ...)"
    clause.  A role, not a fallback: it raises where the pin came too late."""
    _jax.config.update("jax_platforms", "cpu")
    platform = _jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"{role}: JAX backend {platform!r} started before the CPU role "
            "pin — this process would hold a chip the agent owns")
    return f"platform={platform} (role: {role} — the agent owns the chip)"
