"""UDF/UDA framework.

Parity with the reference's type-safe registry (src/carnot/udf/registry.h:101,
udf/udf.h): ScalarUDFs implement Exec, UDAs implement Update/Merge/Finalize with
optional partial-aggregate support (udf.h:326-368 SupportsPartial).  The TPU
re-design:

  * A *device* ScalarUDF is a pure jax function over column tensors — vectorized
    by construction (no per-row Exec loop, no udf_wrapper.h eval loops).
  * A *host* ScalarUDF runs over dictionary values (unique strings) producing a
    LUT that the evaluator applies with a gather, or a compare-select for small
    tables on the TPU (engine/eval._lookup) — O(unique) instead of O(rows).
  * A UDA's state is a pytree whose every leaf declares a reduction op
    ("add"|"min"|"max"); Merge — local or across a mesh axis — is that reduction,
    which makes every UDA partial-aggregation-capable by construction
    (the reference has to hand-write Serialize/Deserialize per UDA).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pixie_tpu.status import NotFound
from pixie_tpu.types import DataType, SemanticType

# ---------------------------------------------------------------------- scalar


@dataclasses.dataclass(frozen=True)
class ScalarUDF:
    """One overload of a scalar function.

    fn signature:
      device: fn(*arrays: jax.Array) -> jax.Array      (elementwise, traced)
      host:   fn(*values: python) -> python            (applied over dict values)
    """

    name: str
    arg_types: tuple[DataType, ...]
    out_type: DataType
    fn: Callable
    device: bool = True
    #: host fns over a BOUNDED int domain (enum decoders like
    #: http_resp_message): (lo, hi) inclusive — evaluated once over the domain
    #: into a device LUT instead of needing a dictionary-encoded input.
    int_domain: tuple[int, int] | None = None
    #: True for host fns reading ambient mutable state (the k8s metadata
    #: snapshot): their baked LUTs go stale when the state epoch advances, so
    #: kernel caches must key on the epoch (see executor._chain_cache_sig).
    volatile: bool = False
    #: declared SEMANTIC type of the output (reference typespb ST_*), or None
    #: — consumed by engine.semantics to type query results for formatting
    out_st: "object" = None
    #: True if the output keeps the semantic type of its first ST-typed
    #: argument (bin over a time column stays a time, round over bytes stays
    #: bytes)
    st_preserve: bool = False

    def key(self) -> tuple:
        return (self.name, self.arg_types)


# ------------------------------------------------------------------------- UDA


class UDA:
    """Aggregate function over groups.

    Contract (shapes: N rows, G groups):
      init(G, in_dtype)                      -> state pytree, leaves [G, ...]
      update(state, gid[N], value[N], mask[N], G) -> state
      reduce_ops()                           -> same pytree of "add"|"min"|"max"
      finalize_host(state_np)                -> np column [G]
    Merge of two states is elementwise leaf-wise reduce_ops — locally, or over a
    mesh axis via psum/pmin/pmax (see pixie_tpu.parallel).
    """

    name: str = "?"
    #: True if the UDA takes no value column (count).
    nullary: bool = False
    #: True if the UDA may consume a dictionary-encoded (STRING/UINT128)
    #: column: its update sees the CODES; the executor decodes at finalize.
    #: Only order-insensitive pickers qualify (any) — min/max over codes
    #: would not be lexical order.
    dict_ok: bool = False
    #: True if the aggregate's output keeps the input column's semantic type
    #: (min/mean/p50 of durations are durations; count of anything is not)
    st_preserve: bool = False
    #: True if finalize needs the input column's Dictionary (model-fit UDAs);
    #: the executor calls finalize_dict(state, dictionary) instead of
    #: finalize_host (see DictHistUDA)
    needs_dict: bool = False
    #: fixed output semantic type (e.g. quantiles → ST_QUANTILES), or None
    out_st = None
    #: True if update() reaches the rows through ops.groupby.masked_segment_*
    #: alone and combines their results elementwise: such a UDA reduces the
    #: runs of pre-sorted rows (groupby.SortedRuns in the place of `gid`,
    #: one state slot a row) exactly as it reduces dense group ids
    segment_only: bool = False

    def out_type(self, in_type: DataType | None) -> DataType:
        raise NotImplementedError

    def init(self, num_groups: int, in_dtype) -> object:
        raise NotImplementedError

    def update(self, state, gid, value, mask, num_groups: int):
        raise NotImplementedError

    def reduce_ops(self):
        raise NotImplementedError

    def merge(self, a, b):
        ops = self.reduce_ops()
        return jax.tree.map(
            lambda op, x, y: {"add": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[op](x, y),
            ops,
            a,
            b,
        )

    def finalize_host(self, state_np) -> np.ndarray:
        raise NotImplementedError

    # ---- optional DEVICE finalize (large-state UDAs, e.g. sketches) ----
    #: When True the executor may run `finalize_device` on the merged device
    #: state and pull only the (small) result instead of the state (a
    #: [G,514] histogram is ~2 MB; the [G] answer is one small wave).
    device_finalize = False

    def finalize_device(self, state):
        """Device state → small device array the host can format cheaply."""
        raise NotImplementedError

    def finalize_from_device(self, pulled_np) -> np.ndarray:
        """Pulled `finalize_device` result → the output column."""
        return np.asarray(pulled_np)


def _acc_dtype(in_dtype) -> jnp.dtype:
    d = jnp.dtype(in_dtype)
    if d.kind == "b":
        return jnp.dtype(jnp.int64)
    return d


class CountUDA(UDA):
    name = "count"
    nullary = True
    segment_only = True

    def out_type(self, in_type):
        return DataType.INT64

    def init(self, num_groups, in_dtype=None):
        return jnp.zeros((num_groups,), dtype=jnp.int64)

    def update(self, state, gid, value, mask, num_groups):
        from pixie_tpu.ops.groupby import masked_segment_count

        return state + masked_segment_count(gid, num_groups, mask)

    def reduce_ops(self):
        return "add"

    def finalize_host(self, state_np):
        return np.asarray(state_np, dtype=np.int64)


class SumUDA(UDA):
    name = "sum"
    st_preserve = True
    segment_only = True

    def out_type(self, in_type):
        return DataType.FLOAT64 if in_type == DataType.FLOAT64 else DataType.INT64

    def init(self, num_groups, in_dtype):
        return jnp.zeros((num_groups,), dtype=_acc_dtype(in_dtype))

    def update(self, state, gid, value, mask, num_groups):
        from pixie_tpu.ops.groupby import masked_segment_fsum, masked_segment_sum

        add = (masked_segment_fsum if value.dtype == jnp.float64
               else masked_segment_sum)
        return state + add(value.astype(state.dtype), gid, num_groups, mask)

    def reduce_ops(self):
        return "add"

    def finalize_host(self, state_np):
        return np.asarray(state_np)


class MeanUDA(UDA):
    name = "mean"
    st_preserve = True
    segment_only = True

    def out_type(self, in_type):
        return DataType.FLOAT64

    def init(self, num_groups, in_dtype):
        return {
            "sum": jnp.zeros((num_groups,), dtype=jnp.float64),
            "count": jnp.zeros((num_groups,), dtype=jnp.int64),
        }

    def update(self, state, gid, value, mask, num_groups):
        from pixie_tpu.ops.groupby import (masked_segment_count,
                                           masked_segment_fsum,
                                           masked_segment_sum)

        # a FLOAT64 column sums in fixed point on the TPU; a mean of
        # integers keeps the hi/lo split (masked_segment_sum)
        add = (masked_segment_fsum if value.dtype == jnp.float64
               else masked_segment_sum)
        return {
            "sum": state["sum"] + add(value.astype(jnp.float64), gid, num_groups, mask),
            "count": state["count"] + masked_segment_count(gid, num_groups, mask),
        }

    def reduce_ops(self):
        return {"sum": "add", "count": "add"}

    def finalize_host(self, state_np):
        cnt = np.asarray(state_np["count"], dtype=np.float64)
        with np.errstate(invalid="ignore", divide="ignore"):
            return np.where(cnt > 0, np.asarray(state_np["sum"]) / cnt, np.nan)


class MinUDA(UDA):
    name = "min"
    st_preserve = True
    segment_only = True

    def out_type(self, in_type):
        return in_type

    def init(self, num_groups, in_dtype):
        from pixie_tpu.ops.groupby import _identity_for

        return jnp.full((num_groups,), _identity_for(_acc_dtype(in_dtype), "min"))

    def update(self, state, gid, value, mask, num_groups):
        from pixie_tpu.ops.groupby import masked_segment_min

        return jnp.minimum(state, masked_segment_min(value.astype(state.dtype), gid, num_groups, mask))

    def reduce_ops(self):
        return "min"

    def finalize_host(self, state_np):
        return np.asarray(state_np)


class MaxUDA(UDA):
    name = "max"
    st_preserve = True
    segment_only = True

    def out_type(self, in_type):
        return in_type

    def init(self, num_groups, in_dtype):
        from pixie_tpu.ops.groupby import _identity_for

        return jnp.full((num_groups,), _identity_for(_acc_dtype(in_dtype), "max"))

    def update(self, state, gid, value, mask, num_groups):
        from pixie_tpu.ops.groupby import masked_segment_max

        return jnp.maximum(state, masked_segment_max(value.astype(state.dtype), gid, num_groups, mask))

    def reduce_ops(self):
        return "max"

    def finalize_host(self, state_np):
        return np.asarray(state_np)


class VarianceUDA(UDA):
    """Sample variance via (sum, sumsq, count) — trivially psum-mergeable,
    unlike Welford (reference math_ops.cc uses pairwise-merge Welford because
    its states merge two at a time; collectives prefer linear state)."""

    name = "variance"
    segment_only = True

    def out_type(self, in_type):
        return DataType.FLOAT64

    def init(self, num_groups, in_dtype):
        # Distinct arrays per leaf: the agg step donates its state buffers, and
        # aliased leaves would be donated twice.
        return {
            "sum": jnp.zeros((num_groups,), dtype=jnp.float64),
            "sumsq": jnp.zeros((num_groups,), dtype=jnp.float64),
            "count": jnp.zeros((num_groups,), dtype=jnp.int64),
        }

    def update(self, state, gid, value, mask, num_groups):
        from pixie_tpu.ops.groupby import masked_segment_count, masked_segment_sum

        v = value.astype(jnp.float64)
        return {
            "sum": state["sum"] + masked_segment_sum(v, gid, num_groups, mask),
            "sumsq": state["sumsq"] + masked_segment_sum(v * v, gid, num_groups, mask),
            "count": state["count"] + masked_segment_count(gid, num_groups, mask),
        }

    def reduce_ops(self):
        return {"sum": "add", "sumsq": "add", "count": "add"}

    def finalize_host(self, state_np):
        n = np.asarray(state_np["count"], dtype=np.float64)
        s = np.asarray(state_np["sum"])
        ss = np.asarray(state_np["sumsq"])
        with np.errstate(invalid="ignore", divide="ignore"):
            var = (ss - s * s / np.where(n > 0, n, 1)) / np.where(n > 1, n - 1, 1)
        return np.where(n > 1, np.maximum(var, 0.0), np.nan)


class StddevUDA(VarianceUDA):
    name = "stddev"

    def finalize_host(self, state_np):
        return np.sqrt(super().finalize_host(state_np))


class AnyUDA(UDA):
    """Pick a representative value per group (reference math_ops.cc AnyUDA).
    Implemented as segment-min, which is a correct 'any' and, unlike
    'first-seen', is order-independent across shards/batches."""

    name = "any"
    st_preserve = True
    dict_ok = True
    segment_only = True

    def out_type(self, in_type):
        return in_type

    def init(self, num_groups, in_dtype):
        from pixie_tpu.ops.groupby import _identity_for

        return jnp.full((num_groups,), _identity_for(_acc_dtype(in_dtype), "min"))

    def update(self, state, gid, value, mask, num_groups):
        from pixie_tpu.ops.groupby import masked_segment_min

        return jnp.minimum(state, masked_segment_min(value.astype(state.dtype), gid, num_groups, mask))

    def reduce_ops(self):
        return "min"

    def finalize_host(self, state_np):
        return np.asarray(state_np)


class DictHistUDA(UDA):
    """Base for aggregates over a dictionary-encoded column whose FINALIZE
    needs the string values (model-fitting UDAs: kmeans, request-path
    clustering — reference funcs/builtins/ml_ops.cc, request_path_ops.cc).

    TPU redesign: instead of per-row C++ Update calls into pointer-chasing
    model state, the device state is a bounded per-group histogram of
    dictionary codes ([G, CAP] int32 counts) — "add"-mergeable, so partial
    aggregation and psum merges work by construction — and the model fit
    runs once at finalize over the observed UNIQUE values (dict values with
    multiplicities), not over rows.  Codes beyond CAP are dropped: the same
    bounded-budget approximation as the reference's 64-point coreset
    (exec/ml/coreset.h).  Distributed plans ship rows for dict-input
    aggregates (parallel/distributed.py), so cross-agent code spaces never
    mix.
    """

    dict_ok = True
    needs_dict = True  # executor must call finalize_dict, not finalize_host
    CAP = 256

    def out_type(self, in_type):
        return DataType.STRING

    def init(self, num_groups, in_dtype=None):
        return jnp.zeros((num_groups, self.CAP), dtype=jnp.int32)

    def update(self, state, gid, value, mask, num_groups):
        code = value.astype(jnp.int32)
        # null codes arrive as a huge sentinel (executor PICKER_NULL_SENTINEL)
        # and overflow codes are dropped, so `code < CAP` handles both
        ok = mask & (code >= 0) & (code < self.CAP)
        c = jnp.clip(code, 0, self.CAP - 1)
        return state.at[gid, c].add(ok.astype(jnp.int32))

    def reduce_ops(self):
        return "add"

    def finalize_host(self, state_np):
        raise NotFound(
            f"UDA {self.name} needs the input dictionary to finalize "
            "(needs_dict); the executor must call finalize_dict"
        )

    def finalize_dict(self, state_np, dictionary) -> np.ndarray:
        counts = np.asarray(state_np)
        out = np.empty(counts.shape[0], dtype=object)
        for g in range(counts.shape[0]):
            nz = np.nonzero(counts[g] > 0)[0]
            vals = dictionary.decode(nz.astype(np.int32)) if len(nz) else []
            out[g] = self.fit_group(list(vals), counts[g][nz])
        return out

    def fit_group(self, values: list, weights) -> str:
        """Fit one group's model over unique `values` with multiplicities
        `weights`; returns the serialized model (a JSON string)."""
        raise NotImplementedError


class QuantileUDA(UDA):
    """Single quantile via mergeable log-histogram sketch (replaces t-digest,
    reference src/carnot/funcs/builtins/math_sketches.h:34-49)."""

    st_preserve = True

    def __init__(self, q: float, name: str | None = None):
        self.q = float(q)
        self.name = name or f"p{int(round(q * 100)):02d}"

    def out_type(self, in_type):
        return DataType.FLOAT64

    def init(self, num_groups, in_dtype):
        from pixie_tpu.ops.sketch import LogHistogram

        self._sketch = LogHistogram()
        return self._sketch.init(num_groups)

    def update(self, state, gid, value, mask, num_groups):
        return self._sketch.update(state, gid, value, mask, num_groups)

    def reduce_ops(self):
        return "add"

    def finalize_host(self, state_np):
        from pixie_tpu.ops.sketch import LogHistogram

        return LogHistogram().quantile(np.asarray(state_np), [self.q])[:, 0]

    device_finalize = True

    def finalize_device(self, state):
        from pixie_tpu.ops.sketch import LogHistogram

        return LogHistogram().quantile_bins_device(state, [self.q])[:, 0]

    def finalize_from_device(self, pulled_np) -> np.ndarray:
        from pixie_tpu.ops.sketch import LogHistogram

        return LogHistogram().bin_values(pulled_np)


class QuantilesUDA(UDA):
    """px.quantiles equivalent: ST_QUANTILES JSON column {p01,p10,p50,p90,p99}."""

    name = "quantiles"
    out_st = SemanticType.ST_QUANTILES
    QS = (0.01, 0.10, 0.50, 0.90, 0.99)

    def out_type(self, in_type):
        return DataType.STRING

    def init(self, num_groups, in_dtype):
        from pixie_tpu.ops.sketch import LogHistogram

        self._sketch = LogHistogram()
        return self._sketch.init(num_groups)

    def update(self, state, gid, value, mask, num_groups):
        return self._sketch.update(state, gid, value, mask, num_groups)

    def reduce_ops(self):
        return "add"

    def finalize_host(self, state_np):
        from pixie_tpu.ops.sketch import LogHistogram

        qv = LogHistogram().quantile(np.asarray(state_np), list(self.QS))
        return self._format(qv)

    def _format(self, qv: np.ndarray) -> np.ndarray:
        out = np.empty(qv.shape[0], dtype=object)
        for i in range(qv.shape[0]):
            out[i] = (
                "{" + ", ".join(f'"p{int(q*100):02d}": {v:.6g}' for q, v in zip(self.QS, qv[i])) + "}"
            )
        return out

    device_finalize = True

    def finalize_device(self, state):
        from pixie_tpu.ops.sketch import LogHistogram

        return LogHistogram().quantile_bins_device(state, list(self.QS))

    def finalize_from_device(self, pulled_np) -> np.ndarray:
        from pixie_tpu.ops.sketch import LogHistogram

        return self._format(LogHistogram().bin_values(pulled_np))


# -------------------------------------------------------------------- registry


_registry_uid = itertools.count(1)


class Registry:
    """Name → overloads (reference src/carnot/udf/registry.h:101)."""

    def __init__(self):
        # Process-unique uid for kernel-cache keys: id() can be reused after
        # GC, aliasing a stale cached kernel to a new registry.
        self.uid = next(_registry_uid)
        self._scalar: dict[str, list[ScalarUDF]] = {}
        self._uda: dict[str, Callable[[], UDA]] = {}
        self._udtf: dict = {}

    # scalar
    def register(self, udf: ScalarUDF):
        self._scalar.setdefault(udf.name, []).append(udf)

    def scalar(self, name: str, arg_types: Sequence[DataType]) -> ScalarUDF:
        overloads = self._scalar.get(name)
        if not overloads:
            raise NotFound(f"no scalar UDF named {name!r}")
        args = tuple(arg_types)
        for o in overloads:
            if o.arg_types == args:
                return o
        # Numeric widening: allow INT64/TIME64NS/BOOLEAN args where FLOAT64 declared.
        for o in overloads:
            if len(o.arg_types) == len(args) and all(
                a == b or (b == DataType.FLOAT64 and a in (DataType.INT64, DataType.BOOLEAN, DataType.TIME64NS))
                or (b == DataType.INT64 and a in (DataType.BOOLEAN, DataType.TIME64NS))
                for a, b in zip(args, o.arg_types)
            ):
                return o
        raise NotFound(
            f"no overload of {name!r} for {tuple(t.name for t in args)}; "
            f"have {[tuple(t.name for t in o.arg_types) for o in overloads]}"
        )

    def has_scalar(self, name: str) -> bool:
        return name in self._scalar

    def is_volatile(self, name: str) -> bool:
        """Any overload of `name` reads ambient mutable state (metadata)."""
        return any(o.volatile for o in self._scalar.get(name, ()))

    # uda
    def register_uda(self, name: str, factory: Callable[[], UDA]):
        self._uda[name] = factory

    def uda(self, name: str) -> UDA:
        f = self._uda.get(name)
        if f is None:
            raise NotFound(f"no UDA named {name!r} (have {sorted(self._uda)})")
        return f()

    def has_uda(self, name: str) -> bool:
        return name in self._uda

    # udtf (reference src/carnot/udf/udtf.h; see pixie_tpu.udf.udtf)
    def register_udtf(self, udtf):
        self._udtf[udtf.name] = udtf

    def udtf(self, name: str):
        u = self._udtf.get(name)
        if u is None:
            raise NotFound(f"no UDTF named {name!r} (have {sorted(self._udtf)})")
        return u

    def has_udtf(self, name: str) -> bool:
        return name in self._udtf

    # iteration accessors (introspection UDTFs; keeps internals private)
    def scalar_overloads(self):
        """Yield (name, ScalarUDF) in name order."""
        for name in sorted(self._scalar):
            for o in self._scalar[name]:
                yield name, o

    def uda_names(self) -> list[str]:
        return sorted(self._uda)

    def udtfs(self):
        """Yield UDTF specs in name order."""
        for name in sorted(self._udtf):
            yield self._udtf[name]

    def names(self) -> dict:
        return {
            "scalar": sorted(self._scalar),
            "uda": sorted(self._uda),
            "udtf": sorted(self._udtf),
        }
