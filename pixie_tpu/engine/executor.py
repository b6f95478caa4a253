"""Fragment executor: lowers a plan DAG to jitted batch kernels and runs it.

This replaces the reference's push-based ExecutionGraph interpreter
(src/carnot/exec/exec_graph.cc:177-295, exec_node.h Prepare/Open/Consume/Generate)
with compilation: every maximal Source→(Map|Filter|Limit)*→(Agg|Sink) chain
becomes ONE jitted function over fixed-shape padded batches.  Filters never
compact on device — they refine a validity mask (XLA static shapes); compaction
happens host-side at sinks.  Blocking aggregates carry a device-resident state
pytree across batches (the streaming loop is host-driven), exactly the structure
that later distributes: the same state merged over a mesh axis with collectives.

Blocking operators (Agg finalize, Join, Union) materialize host batches; chains
re-stream from those.  Joins/unions run host-side in numpy in v1 (they see small
aggregated inputs in the target workloads); the device hash-join is a perf-phase
upgrade tracked in SURVEY.md §7.

Group-by strategy (see ops/groupby.py): a key reducible to a dense code —
dictionary columns natively, raw int columns via a query-time dictionary
built in a host pre-scan of the cursor snapshot, and `px.bin(time)`-derived
window keys via range arithmetic — indexes a dense state.  Any other key, and
a dense space that is past MAX_GROUPS or sparse against its input, takes the
sorted form on the same routed arm (GroupKeyFallback, _sorted_group_reduce).
"""
from __future__ import annotations

import contextlib as _contextlib
import dataclasses
import math
import time as _time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pixie_tpu.engine.eval import ExprCompiler, SVal, apply_lut, apply_lut_np
from pixie_tpu.engine import autotune as _autotune
from pixie_tpu.engine import resident, transfer
from pixie_tpu.native import codegen as _codegen
from pixie_tpu.engine.result import QueryResult
from pixie_tpu.plan.plan import (
    AggOp,
    Call,
    Column,
    FilterOp,
    JoinOp,
    LimitOp,
    Literal,
    MapOp,
    MemorySinkOp,
    MemorySourceOp,
    OTelExportSinkOp,
    Plan,
    RemoteSourceOp,
    ResultSinkOp,
    UDTFSourceOp,
    UnionOp,
)
from pixie_tpu.status import CompilerError, Internal, InvalidArgument, Unimplemented
from pixie_tpu.table.dictionary import Dictionary
from pixie_tpu.types import STORAGE_DTYPE, ColumnSchema, DataType as DT, Relation

from pixie_tpu.ops import groupby as _gb
from pixie_tpu.ops import sketch as _sketch
from pixie_tpu.ops.groupby import next_pow2

INT64_MIN = np.iinfo(np.int64).min
INT64_MAX = np.iinfo(np.int64).max
MAX_GROUPS = 1 << 22
#: A dense state of up to this many slots a leaf is cheap to initialise, read
#: back and search whatever its occupancy (half a megabyte an INT64 leaf):
#: under it the dense form is kept however few rows the input has.
SPARSE_MIN_GROUPS = 1 << 16
#: Minimum window-bin bucket: keeps the compiled group space stable across
#: streaming polls whose deltas span few windows.
MIN_WINDOW_BINS = 1 << 6


#: All-null sentinel for dict-valued pickers: equals _identity_for(int32,
#: "min") so an all-null group's state stays at the identity and decodes null.
PICKER_NULL_SENTINEL = np.iinfo(np.int32).max


def _decode_picker_codes(vals, d: Dictionary) -> np.ndarray:
    """Picker state codes → int32 dictionary codes; out-of-range (all-null
    sentinel) becomes -1 (null)."""
    codes = np.asarray(vals, dtype=np.int64)
    return np.where((codes < 0) | (codes >= d.size), -1, codes).astype(np.int32)


class GroupKeyFallback(Unimplemented):
    """Raised where the dense form of an aggregate (one state slot for every
    combination of the keys' dense codes) does not serve it: a key has no
    bounded dense code (a computed numeric key, a float key), the product of
    the codes' cardinalities passes MAX_GROUPS, or that product is sparse
    against the input (`_group_space_is_sparse`: more slots than the input
    has rows, so the state would be mostly identities to initialise, read
    back and search).  The executor catches it and runs the aggregate
    through the sorted form on the same routed arm (`_sorted_group_reduce`:
    the rows sorted by their keys on the device, one run a group, a
    result of one slot a live group; reference capability: exec/agg_node.h's
    hash map has no cardinality bound)."""
MIN_BUCKET = 1 << 10
from pixie_tpu import flags as _flags

#: Feed coalescing target: sealed storage batches (64K-ish, the reference's
#: compaction granularity) are merged into large device feeds so a typical
#: query is ONE device execution.  Sized at 16M rows (~0.5 GB at 32 B/row):
#: every execution pays a fixed dispatch + readback cost, so fewer, bigger
#: launches beat streaming many small batches.  The size is unverified on
#: the current chip (ROADMAP S2).
FEED_ROWS = _flags.define_int(
    "PX_FEED_ROWS", 1 << 24, "feed coalescing target (rows per device feed)"
)


# -------------------------------------------------------------- kernel cache
# Compiled chain kernels are reused across queries (the reference re-walks its
# exec-node tree per query; we must NOT re-jit per query or XLA compile time
# dominates).  Sound because cache keys capture everything baked into a kernel:
# the chain structure, input dtypes, and (id, size) of every input dictionary —
# dictionaries are append-only, so same (id, size) ⇒ identical content ⇒
# identical LUTs.  Data-dependent aggregation state (intdevice key sets, window
# origins) is covered by including the table's rows_written in agg signatures.
import collections as _collections
import functools as _functools
import hashlib as _hashlib
import inspect as _inspect
import json as _json
import threading as _threading

# ------------------------------------------------------------ compile telemetry
#: jax's compile phases as it reports them (jax.monitoring duration events)
_COMPILE_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: per thread: the stats dict of the executor running there (`sink`), and
#: whether jax read the program being compiled from its persistent cache
_COMPILE_TLS = _threading.local()


def _on_jax_duration(event: str, secs: float, **_kw) -> None:
    """jax reports a compile phase on the thread that compiled: add it to
    the exec_stats of the query running there (`compile_s`, `compiles`)
    and, under an active trace, record it as a `jax_compile` span.  jax
    calls this only when it compiles, so a warm query pays nothing."""
    if event == _CACHE_RETRIEVAL_EVENT:
        _COMPILE_TLS.cache_hit = True
        return
    kind = _COMPILE_KINDS.get(event)
    stats = getattr(_COMPILE_TLS, "sink", None)
    if kind is None or stats is None:
        return
    end_ns = _time.time_ns()
    stats["compile_s"] += secs
    attrs = {"kind": kind}
    if kind == "backend_compile":
        stats["compiles"] += 1
        # the retrieval event comes from inside the backend-compile phase
        attrs["cache_hit"] = getattr(_COMPILE_TLS, "cache_hit", False)
        _COMPILE_TLS.cache_hit = False
    from pixie_tpu import trace

    dur_ns = int(secs * 1e9)
    trace.event_span("jax_compile", end_ns - dur_ns, dur_ns, **attrs)


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


@_contextlib.contextmanager
def _compile_sink(stats: dict):
    """`stats` takes what jax compiles on this thread, for the block."""
    prev = getattr(_COMPILE_TLS, "sink", None)
    _COMPILE_TLS.sink = stats
    try:
        yield
    finally:
        _COMPILE_TLS.sink = prev


def _compile_scoped(fn):
    """Run a PlanExecutor entry point (a method or a generator method)
    with the executor's stats as this thread's compile sink."""
    if _inspect.isgeneratorfunction(fn):
        @_functools.wraps(fn)
        def scoped(self, *args, **kwargs):
            with _compile_sink(self.stats):
                yield from fn(self, *args, **kwargs)
    else:
        @_functools.wraps(fn)
        def scoped(self, *args, **kwargs):
            with _compile_sink(self.stats):
                return fn(self, *args, **kwargs)
    return scoped


_KERNEL_CACHE: "_collections.OrderedDict[str, tuple]" = _collections.OrderedDict()
_KERNEL_CACHE_MAX = 128
#: concurrent agent executors (cluster thread pool) share these caches
_CACHE_LOCK = _threading.Lock()


def _cache_get(sig):
    if sig is None:
        return None
    with _CACHE_LOCK:
        got = _KERNEL_CACHE.get(sig)
        if got is not None:
            _KERNEL_CACHE.move_to_end(sig)
        return got


def _cache_put(sig, value):
    if sig is None:
        return
    with _CACHE_LOCK:
        _KERNEL_CACHE[sig] = value
        while len(_KERNEL_CACHE) > _KERNEL_CACHE_MAX:
            _KERNEL_CACHE.popitem(last=False)


#: op fields the streaming poller / matview maintainer PATCH between runs
#: (stream.py: source since/stop row ids + carried limit budgets;
#: maintainer.py: delta scan bounds) — everything else on a plan op is
#: immutable after compile
_VOLATILE_OP_FIELDS = ("n", "since_row_id", "stop_row_id")


def _op_sig(op) -> dict:
    # Memoized on the op instance: plan ops are structurally immutable after
    # compile, and warm interactive queries re-sign the same plan objects
    # every few ms — re-walking the op/expression tree per query was
    # measurable fast-path latency.  (copy.copy in the distributed planner
    # carries the memo; only `id` changes there, and `id` is excluded.)
    # The VOLATILE fields above are re-read live on every call: they are
    # runtime-patched per poll, and a stale signature would let the chain
    # cache serve a kernel with last poll's baked-in budget/scan bounds.
    got = op.__dict__.get("_op_sig_cache")
    if got is None:
        d = op.to_dict()
        d.pop("id", None)
        op.__dict__["_op_sig_cache"] = got = d
        return got
    for f in _VOLATILE_OP_FIELDS:
        if f in got:
            got[f] = getattr(op, f)
    return got


#: blocking-op intermediates cache kernels by dictionary CONTENT; above this
#: size fingerprinting costs more than the compile it saves
CONTENT_SIG_MAX_DICT = 1 << 16

#: (table uid, column) → (sorted unique values, scanned-to row id).  Tables
#: are append-only (expiry only drops rows), so the set is maintained
#: incrementally: each refresh scans only rows past the watermark.  This
#: keys intdevice agg kernels by VALUE-SET CONTENT instead of rows_written —
#: a streaming poll with no new key values then reuses the compiled kernel
#: instead of rebuilding it every poll.
_KEY_UNIQUES: "_collections.OrderedDict[tuple, tuple]" = _collections.OrderedDict()
_KEY_UNIQUES_MAX = 64
#: beyond this cardinality the set stops being tracked (the agg would take
#: the sorted fallback anyway); monotonic, so the overflow mark is permanent
_KEY_UNIQUES_CAP = MAX_GROUPS
_KEY_OVERFLOW = "overflow"


def _int_key_uniques(table, col: str, src) -> Optional[np.ndarray]:
    """Cumulative sorted unique values of `col` over a contiguous covered
    row-id range [lo, hi), extended/rebased from THIS query's snapshot cursor.

    Scanning the live table instead of the snapshot would race ring-buffer
    expiry: a value pinned in the query's feed could be missing from the
    fresh scan and searchsorted would silently fold its rows into a
    neighboring group.  Rows are immutable and row ids monotone, so values
    inside [lo, hi) were observed live by the scan that covered them — any
    snapshot whose rows all sit in [lo, hi) gets a valid (possibly strict
    superset) value set.  Returns None when the set overflows
    _KEY_UNIQUES_CAP (caller falls back to per-query prescan / sorted agg).

    Coverage rules (advisor r3 high + r4 review finding):
      * time-bounded cursors skip whole live batches — they neither consult
        nor update the cache (caller prescans this query's own snapshot);
      * a cursor reaching BELOW lo (an old pinned snapshot after a rebase)
        gets None — its rows may hold values the cache never saw;
      * a cursor starting past hi (expiry gap [hi, start) was never scanned)
        REBASES the entry to its own contiguous coverage instead of killing
        the cache for the table's remaining lifetime — expired rows can only
        be yielded by older pinned cursors, which the lo bound now rejects.
    """
    if (getattr(src, "start_time", None) is not None
            or getattr(src, "stop_time", None) is not None):
        return None
    if getattr(src, "since_row_id", None) is None:
        return None  # not a table Cursor — no coverage guarantee
    items = [(rb, rid) for rb, rid, _gen in src]
    key = (table.uid, col)
    with _CACHE_LOCK:
        entry = _KEY_UNIQUES.get(key)
    vals, lo, hi = entry if entry is not None else (None, 0, 0)
    if vals is _KEY_OVERFLOW:
        return None
    cfirst = min((rid for _rb, rid in items), default=None)
    if cfirst is None:  # empty snapshot: nothing to encode, superset is fine
        return vals if vals is not None else np.empty(0, dtype=np.int64)
    if vals is not None and cfirst < lo:
        return None  # pinned rows below cached coverage: prescan, keep entry
    rebase = vals is None or cfirst > hi
    parts = [] if rebase else [vals]
    cover = cfirst if rebase else hi
    base_lo = cfirst if rebase else lo
    changed = rebase
    for rb, rid in items:  # a cursor's batches are row-contiguous
        end = rid + rb.num_valid
        if end <= cover:
            continue
        if rid > cover:
            return None  # non-contiguous cursor (unexpected): refuse
        off = max(0, cover - rid)
        arr = rb.columns[col][off: rb.num_valid]
        if len(arr):
            parts.append(np.unique(arr))
            changed = True
        cover = end
    if changed:
        vals = (np.unique(np.concatenate(parts)) if parts
                else np.empty(0, dtype=np.int64))
        with _CACHE_LOCK:
            if len(vals) > _KEY_UNIQUES_CAP:
                _KEY_UNIQUES[key] = (_KEY_OVERFLOW, base_lo, cover)
                return None
            _KEY_UNIQUES[key] = (vals, base_lo, cover)
            while len(_KEY_UNIQUES) > _KEY_UNIQUES_MAX:
                _KEY_UNIQUES.popitem(last=False)
    return vals


def _group_source_column(chain, name: str):
    """Resolve a group name back through chain Maps to a direct source
    column name, or None if it is computed (any non-rename expression)."""
    from pixie_tpu.plan.plan import Column

    for op in reversed(chain):
        if isinstance(op, MapOp):
            e = next((ex for n, ex in op.exprs if n == name), None)
            if not isinstance(e, Column):
                return None
            name = e.name
    return name


def _dict_fingerprint(d) -> int:
    """Content hash of a Dictionary (process-local; cache is in-process)."""
    return hash(tuple(str(v) for v in d.values()))


# ------------------------------------------------- small-input device policy
#: content-signature key hashing is O(rows); only hash small intermediates
SMALL_HOST_INPUT_ROWS = 1 << 15

#: Inputs at or under this row count dispatch on the CPU backend.  Mechanism:
#: an accelerator query pays a fixed cost per execution and per readback
#: wave whatever its size, while the host engines start at once; below some
#: row count the host wins, above it the device's bandwidth does.  This is
#: ALSO why kernels must minimize executions per query.  The constant is
#: unverified on the current chip (ROADMAP S2).
CPU_CROSSOVER_ROWS = _flags.define_int(
    "PX_CPU_CROSSOVER_ROWS", 1 << 22,
    "inputs at/below this row count run on the CPU backend",
)

_CPU_DEVICE: "object" = None  # resolved lazily


def _cpu_device():
    """The XLA-CPU device host-routed chains pin to.  A process whose JAX
    has no CPU backend (JAX_PLATFORMS without "cpu") raises here: routing
    such a query somewhere else would hide where it ran."""
    global _CPU_DEVICE
    if _CPU_DEVICE is None:
        _CPU_DEVICE = jax.devices("cpu")[0]
    return _CPU_DEVICE


def _src_rows(src) -> Optional[int]:
    if isinstance(src, HostBatch):
        return src.num_rows
    try:
        return src.num_rows()
    except Exception:
        return None


def _shard_skew(rows) -> float:
    """Max over mean of the valid rows the mesh's shards were handed; 1.0
    is even, and what no rows at all read."""
    mean = sum(rows) / max(len(rows), 1)
    return float(max(rows) / mean) if mean > 0 else 1.0


def _route_backend(src, scale: int = 1) -> str:
    """Route for this input: "cpu" (the host engines and XLA-CPU) or
    "device" (the process's JAX default device / mesh — WHICH platform that
    is lands in stats["device"], see PlanExecutor._note_engine).  `scale` is
    the distributed fan-out (number of data agents executing the same
    fragment): routing must consider the QUERY's size, not the local
    shard's — 8 agents each holding 2M rows are a
    16M-row query, and pushing each shard to XLA-CPU throws away the TPU win
    that partial aggregation exists to deliver (round-3 config-4 regression).
    """
    n = _src_rows(src)
    # read through the flag registry (not the import-time constant) so the
    # crossover is live-tunable — the static arm the autotune tests and
    # the tail-guard fallback both pin against
    if n is not None and \
            n * max(1, scale) <= int(_flags.get("PX_CPU_CROSSOVER_ROWS")):
        return "cpu"
    return "device"


def _group_space_is_sparse(num_groups: int, src) -> bool:
    """True where a dense state of `num_groups` slots a leaf has more slots
    than the input over `src` has rows (its pow2 bucket) and more than
    SPARSE_MIN_GROUPS: most of it would be identities, and what it costs to
    initialise, read back and search grows with the space while the sorted
    form's cost and result grow with the rows alone.  Both arms take the
    answer, so that a script's spellings share one path and one model key;
    XLA-CPU alone would scatter into such a space faster than it sorts
    (PERF.md section 7, row 9)."""
    n = _src_rows(src)
    return (n is not None and num_groups > SPARSE_MIN_GROUPS
            and num_groups > next_pow2(max(n, 1)))


@_functools.partial(jax.jit, static_argnums=2)
def _take_front(tree, order, n: int):
    """Every leaf of `tree` at the first `n` rows of `order`, still on the
    device."""
    return _gb.take_rows(tree, order[:n])


def _route_class_of(head, chain, op) -> str:
    """The class of the router's model key for the chain `head -> chain ->
    op`: what _chain_cache_sig signs less everything that changes while the
    work does not.  In: the head's table name (a blocking head's kind), the
    chain's ops and the blocking op (group keys, UDAs).  Out: the scan's
    time and row-id bounds, its column list, the table's uid, dictionary
    identities and sizes, the metadata epoch, the mesh, key-set hashes and
    rows_written: one script at any start shares one key with itself and
    with no other script.  No `|` in it (autotune joins its keys on one)."""
    name = head.table if isinstance(head, MemorySourceOp) else head.kind
    body = _json.dumps([[_op_sig(o) for o in chain], _op_sig(op)],
                       sort_keys=True, default=str)
    digest = _hashlib.blake2s(body.encode(), digest_size=5).hexdigest()
    return f"agg:{name.replace('|', '_')}:{digest}"


def _iter_call_fns(expr):
    """Yield every Call fn name in an expression tree."""
    if isinstance(expr, Call):
        yield expr.fn
        for a in expr.args:
            yield from _iter_call_fns(a)


def _chain_uses_volatile(chain, registry) -> bool:
    """True if any chain expression calls a volatile (metadata-reading) UDF —
    such kernels bake snapshot-derived LUTs and must cache per state epoch."""
    for op in chain:
        exprs = []
        if isinstance(op, MapOp):
            exprs = [e for _n, e in op.exprs]
        elif isinstance(op, FilterOp):
            exprs = [op.expr]
        for e in exprs:
            for fn in _iter_call_fns(e):
                if registry.is_volatile(fn):
                    return True
    return False


# ------------------------------------------------------------ device feed cache
# The TPU-native analog of the reference's cold store (table/table.h hot/cold
# partitions): sealed batches are immutable, so their assembled, padded device
# feeds are cached in HBM keyed by the seal gens.  Repeat queries then stream
# ZERO bytes host→device: the upload would otherwise be paid on every query.
_DEVICE_CACHE: "_collections.OrderedDict[tuple, dict]" = _collections.OrderedDict()
_DEVICE_CACHE_BYTES = 0
_DEVICE_CACHE_MAX = _flags.define_int(
    "PIXIE_TPU_DEVICE_CACHE_MB", 4096,
    "HBM feed cache budget (MB); the PEM table-memory-budget analog",
) << 20


def _device_cache_get(key):
    with _CACHE_LOCK:
        got = _DEVICE_CACHE.get(key)
        if got is not None:
            _DEVICE_CACHE.move_to_end(key)
        return got


def _device_cache_put(key, cols: dict):
    global _DEVICE_CACHE_BYTES
    nbytes = sum(v.nbytes for v in cols.values())
    if nbytes > _DEVICE_CACHE_MAX:
        return
    with _CACHE_LOCK:
        _DEVICE_CACHE[key] = cols
        _DEVICE_CACHE_BYTES += nbytes
        while _DEVICE_CACHE_BYTES > _DEVICE_CACHE_MAX and _DEVICE_CACHE:
            _k, v = _DEVICE_CACHE.popitem(last=False)
            _DEVICE_CACHE_BYTES -= sum(x.nbytes for x in v.values())


def _device_cache_pop(key):
    """Drop one entry (the resident tier adopted its arrays — keeping both
    would pin the same bytes twice)."""
    global _DEVICE_CACHE_BYTES
    with _CACHE_LOCK:
        got = _DEVICE_CACHE.pop(key, None)
        if got is not None:
            _DEVICE_CACHE_BYTES -= sum(x.nbytes for x in got.values())


def clear_device_cache():
    global _DEVICE_CACHE_BYTES
    with _CACHE_LOCK:
        _DEVICE_CACHE.clear()
        _DEVICE_CACHE_BYTES = 0


def _bucket(n: int, cap: int) -> int:
    return min(max(next_pow2(n), MIN_BUCKET), max(cap, MIN_BUCKET))


# --------------------------------------------------------------------- batches


@dataclasses.dataclass
class HostBatch:
    """Materialized intermediate (compacted, host numpy)."""

    dtypes: dict[str, DT]
    dicts: dict[str, Dictionary]
    cols: dict[str, np.ndarray]

    @property
    def num_rows(self) -> int:
        for v in self.cols.values():
            return len(v)
        return 0


# ----------------------------------------------------------------- group keys


@dataclasses.dataclass
class GroupKey:
    name: str
    kind: str  # "dict" | "intdevice" | "window"
    card: int  # pow2-bucketed static cardinality
    out_dtype: DT
    dictionary: Optional[Dictionary] = None  # dict/intdevice
    #: source column the intdevice key reads (differs from `name` when a Map
    #: renamed the column).
    src_name: str = ""
    # window params
    width: int = 0
    t0_bin: int = 0
    key_sval: Optional[SVal] = None  # device codes builder (dict/window)
    #: luts entry holding the sorted unique values (intdevice: in-kernel
    #: searchsorted replaces host-side per-batch encoding).
    lut_name: str = ""


class _ChainCtx:
    """Symbolic column environment threaded through a chain of transforms."""

    def __init__(
        self,
        dtypes: dict[str, DT],
        dicts: dict[str, Dictionary],
        registry,
        visible: Optional[list[str]] = None,
    ):
        self.sym: dict[str, SVal] = {}
        self.provenance: dict[str, object] = {}
        #: default output columns — the fed columns minus internals (e.g. a
        #: time_ column fetched only to evaluate row-level time bounds).
        self.visible: list[str] = list(visible) if visible is not None else list(dtypes)
        self.registry = registry
        self.ec = ExprCompiler(dtypes, dicts, registry)
        # Seed with input columns.
        for name, dt in dtypes.items():
            self.sym[name] = self.ec.compile(Column(name))
            self.provenance[name] = Column(name)
        # Redirect column resolution to the evolving symbolic env.
        self.ec._compile_column = self._resolve_column  # type: ignore[method-assign]

    def _resolve_column(self, expr: Column) -> SVal:
        v = self.sym.get(expr.name)
        if v is None:
            raise CompilerError(f"column {expr.name!r} not found; have {sorted(self.sym)}")
        return v

    def apply_map(self, op: MapOp):
        new_sym = {}
        new_prov = {}
        for name, expr in op.exprs:
            new_sym[name] = self.ec.compile(expr)
            # Track one level of provenance for window-key detection, resolving
            # pass-through renames to their origin.
            if isinstance(expr, Column):
                new_prov[name] = self.provenance.get(expr.name, expr)
            else:
                new_prov[name] = expr
        self.ec._memo.clear()  # column meanings changed; don't reuse SVals
        self.sym = new_sym
        self.provenance = new_prov
        self.visible = [n for n, _ in op.exprs]

    def compile_predicate(self, op: FilterOp) -> SVal:
        v = self.ec.compile(op.expr)
        if v.dtype != DT.BOOLEAN:
            raise CompilerError(f"filter expression has type {v.dtype.name}, want BOOLEAN")
        return v


# ---------------------------------------------------------------- chain kernel


class ChainKernel:
    """Compiles Source/HostBatch → transforms → (agg | output) into one jit fn."""

    def __init__(
        self,
        in_dtypes: dict[str, DT],
        in_dicts: dict[str, Dictionary],
        transforms: list,
        registry,
        time_col: Optional[str],
        visible: Optional[list[str]] = None,
    ):
        self.ctx = _ChainCtx(in_dtypes, in_dicts, registry, visible)
        self.registry = registry
        self.time_col = time_col
        self.steps = []  # ("map", op) applied symbolically; ("filter", sval); ("limit", i)
        #: per-LimitOp budgets, in chain order — each limit step tracks its OWN
        #: remaining budget (a single min-collapsed budget under-returns when a
        #: filter between two limits drops admitted rows).
        self.limit_ns: list[int] = []
        for op in transforms:
            if isinstance(op, MapOp):
                self.ctx.apply_map(op)
            elif isinstance(op, FilterOp):
                self.steps.append(("filter", self.ctx.compile_predicate(op)))
            elif isinstance(op, LimitOp):
                self.steps.append(("limit", len(self.limit_ns)))
                self.limit_ns.append(int(op.n))
            else:
                raise Internal(f"non-streamable op {op.kind} in chain")

    @property
    def has_limit(self) -> bool:
        return bool(self.limit_ns)

    def init_limits(self) -> np.ndarray:
        """Initial per-limit remaining budgets (shape [max(1, n_limits)]).
        Numpy on purpose: an eager jnp.asarray would be a fixed-cost device
        op per query; as a jit argument it rides the execution's upload."""
        ns = self.limit_ns or [INT64_MAX]
        return np.asarray(ns, dtype=np.int64)

    @property
    def luts(self) -> dict[str, np.ndarray]:
        return self.ctx.ec.luts

    def lut_forms(self) -> dict:
        return self.ctx.ec.lut_forms()

    @jax.named_scope("px.time_mask")
    def _base_mask(self, env, n, n_valid, t_lo, t_hi):
        mask = jnp.arange(n) < n_valid
        if self.time_col is not None and self.time_col in env["cols"]:
            t = env["cols"][self.time_col]
            mask = mask & (t >= t_lo) & (t < t_hi)
        return mask

    def _apply_steps(self, env, mask, limits):
        """Apply filter/limit steps. Returns (mask, consumed[n_limits]).

        `limits` is the per-limit remaining-budget vector (shape
        [max(1, n_limits)]).  consumed[i] counts limit i's slots used by THIS
        batch — rows reaching that limit step, capped at its remaining budget.
        The host subtracts the whole vector from `remaining`: decrementing by
        the final output count instead would let later batches emit rows past
        a limit whenever a downstream filter drops admitted rows.
        """
        consumed = [jnp.int64(0)] * max(1, len(self.limit_ns))
        for kind, sv in self.steps:
            if kind == "filter":
                mask = mask & sv.build(env)
            else:  # limit; sv = budget index
                # Scalar `limits` broadcasts one shared budget (SPMD callers
                # pass INT64_MAX); the executor always passes the per-limit
                # vector from init_limits().  Two limits sharing one scalar
                # budget would silently mis-account, so reject at trace time.
                if jnp.ndim(limits) == 0 and len(self.limit_ns) > 1:
                    raise Internal(
                        "chains with multiple LimitOps need the per-limit "
                        "budget vector (ChainKernel.init_limits()), not a scalar"
                    )
                rem = limits[sv] if jnp.ndim(limits) else limits
                reaching = jnp.sum(mask.astype(jnp.int64))
                mask = mask & (jnp.cumsum(mask.astype(jnp.int64)) <= rem)
                consumed[sv] = jnp.minimum(reaching, rem)
        return mask, jnp.stack(consumed)

    def make_output_step(self, out_names: list[str]):
        """→ jit fn(cols, n_valid, t_lo, t_hi, limit_remaining, luts)
        → (out_cols, count, consumed) with selected rows COMPACTED to the front
        on device (stable partition by mask), so the host can read back exactly
        `count` rows. Also returns (dtypes, dicts) of outputs."""
        sym = self.ctx.sym
        missing = [n for n in out_names if n not in sym]
        if missing:
            raise CompilerError(f"output columns {missing} not found; have {sorted(sym)}")
        out_dtypes = {n: sym[n].dtype for n in out_names}
        out_dicts = {n: sym[n].dictionary for n in out_names if sym[n].dictionary is not None}
        builders = [(n, sym[n].build) for n in out_names]

        def step(cols, n_valid, t_lo, t_hi, limit_remaining, luts):
            env = {"cols": cols, "luts": luts}
            n = _first_len(cols)
            mask = self._base_mask(env, n, n_valid, t_lo, t_hi)
            mask, consumed = self._apply_steps(env, mask, limit_remaining)
            # Stable front-compaction: selected rows keep order at the front.
            order = jnp.argsort(jnp.logical_not(mask), stable=True)
            outs = {}
            for name, b in builders:
                v = b(env)
                v = jnp.broadcast_to(v, (n,)) if v.ndim == 0 else v
                outs[name] = jnp.take(v, order)
            return outs, jnp.sum(mask.astype(jnp.int64)), consumed

        return jax.jit(step), out_dtypes, out_dicts

    def make_keyed_rows_step(self, key_builders: list, cards: list,
                             value_builders: list):
        """→ jit fn(cols, n_valid, t_lo, t_hi, limit_remaining, luts)
        → (mask, sort keys, values, consumed): the rows the chain lets
        through (`mask`, over the whole bucket: nothing is compacted), what
        to sort them by (ops/groupby.run_sort_keys of their group keys) and
        the aggregates' value columns, for the sorted aggregate.
        `key_builders`: (build, kind) per group key, kind "dict" (codes; a
        null code, -1, takes its row out of `mask`), "float" (a NaN does)
        or "int".  `cards` has a key's pow2 cardinality where it is known
        (a dictionary code, a flag) and None where not: the keys of known
        cardinality are one mixed-radix id, int32 where the product fits
        and int64 otherwise, whose masked rows take the product itself,
        and the others follow it as they are; with none known, one array a
        key behind a live-first key."""
        packed = [i for i, c in enumerate(cards) if c]
        loose = [i for i, c in enumerate(cards) if not c]
        packed_dtype = total = None
        if packed:
            total = math.prod(cards[i] for i in packed)
            packed_dtype = jnp.int32 if total < (1 << 31) else jnp.int64

        def step(cols, n_valid, t_lo, t_hi, limit_remaining, luts):
            env = {"cols": cols, "luts": luts}
            n = _first_len(cols)
            mask = self._base_mask(env, n, n_valid, t_lo, t_hi)
            mask, consumed = self._apply_steps(env, mask, limit_remaining)

            def rows(v):
                return jnp.broadcast_to(v, (n,)) if v.ndim == 0 else v

            keys = []
            for build, kind in key_builders:
                k = rows(build(env))
                if kind == "dict":
                    mask = mask & (k >= 0)
                elif kind == "float":
                    mask = mask & ~jnp.isnan(k)
                elif k.dtype == jnp.bool_:
                    k = k.astype(jnp.int32)
                keys.append(k)
            if packed:
                keys = [_gb.combine_codes([keys[i] for i in packed],
                                          [cards[i] for i in packed],
                                          packed_dtype)[0],
                        *(keys[i] for i in loose)]
            values = []
            for build in value_builders:
                v = rows(build(env))
                values.append(v.astype(jnp.int32) if v.dtype == jnp.bool_
                              else v)
            return (mask, _gb.run_sort_keys(keys, mask, total), values,
                    consumed)

        return jax.jit(step)

    def make_partial_agg_step(self, keys, udas, num_groups: int, init_specs):
        """→ jit fn(cols, n_valid, t_lo, t_hi, luts) → partial state.

        Identity state is created INSIDE the trace, so per-feed calls are
        mutually independent — crucial on runtimes where dependent executions
        serialize (each feed's partial dispatches without waiting).  Pair with
        `make_merge_states` to combine the partials in one stacked reduction.
        """
        raw = self.make_agg_step(keys, udas, num_groups, jit=False)
        spec = list(init_specs)

        n_lim = max(1, len(self.limit_ns))

        def step(cols, n_valid, t_lo, t_hi, luts):
            state = {name: uda.init(num_groups, in_dt) for name, uda, in_dt in spec}
            new_state, _cnt, _consumed = raw(
                cols, n_valid, t_lo, t_hi,
                jnp.full((n_lim,), INT64_MAX, dtype=jnp.int64), luts, state
            )
            return new_state

        return jax.jit(step)

    @staticmethod
    def merge_states_fn(reduce_tree):
        """Traceable fn(*states) → merged state: ONE stacked reduction per
        leaf, op per leaf from `reduce_tree` ("add"|"min"|"max").  The single
        source of truth for device-side state merging (per-feed AND the
        cross-agent gang merge)."""
        fns = {"add": (lambda s: jnp.sum(s, axis=0)),
               "min": (lambda s: jnp.min(s, axis=0)),
               "max": (lambda s: jnp.max(s, axis=0))}

        def merge(*states):
            return jax.tree.map(
                lambda op, *leaves: fns[op](jnp.stack(leaves)),
                reduce_tree,
                *states,
                is_leaf=lambda x: isinstance(x, str),
            )

        return merge

    @staticmethod
    def make_merge_states(udas):
        """→ jit fn(*states) → merged state (flat dependency graph: N
        partials merge in a single execution)."""
        reduce_tree = {name: uda.reduce_ops() for name, uda, _vb in udas}
        return jax.jit(ChainKernel.merge_states_fn(reduce_tree))

    @staticmethod
    def make_merge_states_np(udas):
        """→ fn(*numpy_states) → merged numpy state, on HOST.  Per-feed
        partials are pulled in one overlapped readback wave and merged here:
        a device-side merge would cost one more execution, and every
        execution pays a fixed dispatch + readback cost."""
        reduce_tree = {name: uda.reduce_ops() for name, uda, _vb in udas}
        fns = {"add": (lambda *ls: np.sum(np.stack(ls), axis=0)),
               "min": (lambda *ls: np.min(np.stack(ls), axis=0)),
               "max": (lambda *ls: np.max(np.stack(ls), axis=0))}

        def merge(*states):
            if len(states) == 1:
                return states[0]
            return jax.tree.map(
                lambda op, *leaves: fns[op](*leaves),
                reduce_tree,
                *states,
                is_leaf=lambda x: isinstance(x, str),
            )

        return merge

    def make_agg_step(self, keys: list[GroupKey], udas: list, num_groups: int, jit: bool = True):
        """→ jit fn(cols, n_valid, t_lo, t_hi, limit_remaining, luts, state)
        → (state, count). udas: list of (out_name, UDA, value_builder|None)."""
        from pixie_tpu.ops.groupby import combine_codes, encode_against

        key_builders = []
        for k in keys:
            if k.kind == "intdevice":
                src_name, lut_name = k.src_name, k.lut_name
                key_builders.append(
                    lambda env, s=src_name, l=lut_name: encode_against(
                        env["luts"][l], env["cols"][s]
                    )
                )
            elif k.kind == "dict":
                key_builders.append(k.key_sval.build)
            else:  # window: origin is a runtime scalar in luts (streaming)
                sv, w, t0name = k.key_sval, k.width, k.lut_name
                key_builders.append(jax.named_scope("px.window_bin")(
                    lambda env, sv=sv, w=w, t0name=t0name: (
                        sv.build(env) // w - env["luts"][t0name][0]
                    ).astype(jnp.int32)
                ))
        cards = [k.card for k in keys]

        def step(cols, n_valid, t_lo, t_hi, limit_remaining, luts, state):
            env = {"cols": cols, "luts": luts}
            n = _first_len(cols)
            mask = self._base_mask(env, n, n_valid, t_lo, t_hi)
            mask, consumed = self._apply_steps(env, mask, limit_remaining)
            if keys:
                # literal group keys (df.node = 'x') build scalar codes —
                # broadcast to row length so the segment scatter sees [n]
                code_arrays = [
                    jnp.broadcast_to(c, (n,)) if c.ndim == 0 else c
                    for c in (kb(env) for kb in key_builders)
                ]
                # Null keys (code -1, e.g. unmatched left-join fills) drop out
                # of the aggregate (pandas dropna semantics); without this,
                # combine_codes would clamp them into group 0.
                for k, c in zip(keys, code_arrays):
                    if k.kind == "dict":
                        mask = mask & (c >= 0)
                gid, _ = combine_codes(code_arrays, cards)
            else:
                gid = jnp.zeros(n, dtype=jnp.int32)
            new_state = {}
            for out_name, uda, vb in udas:
                v = None
                if vb is not None:
                    v = vb(env)
                    v = jnp.broadcast_to(v, (n,)) if v.ndim == 0 else v
                new_state[out_name] = uda.update(state[out_name], gid, v, mask, num_groups)
            return new_state, jnp.sum(mask.astype(jnp.int64)), consumed

        # Kept unjitted for the SPMD lifter (parallel.spmd.spmd_agg_step wraps it
        # in shard_map over a mesh axis).
        self.raw_agg_step = step
        if not jit:
            return step
        return jax.jit(step, donate_argnums=(6,))


def _first_len(cols: dict) -> int:
    for v in cols.values():
        return v.shape[0]
    return 0


# ------------------------------------------------------------ column pruning
def _expr_columns(e) -> set:
    from pixie_tpu.plan.plan import Call, Column

    if isinstance(e, Column):
        return {e.name}
    if isinstance(e, Call):
        out = set()
        for a in e.args:
            out |= _expr_columns(a)
        return out
    return set()


def _prune_to_needed(head, chain, dtypes, dicts, names, visible, time_col,
                     needed_end: set):
    """Narrow the feed (and the chain's Map projections) to the columns the
    consumer actually reads.  Feeding unused columns wastes host→device
    bandwidth and, on the CPU route, memcpy + mask work per query (the
    compiler prunes PxL plans, but hand-built / remote plans arrive
    unpruned).  The hidden time column stays whenever the source has time
    bounds (names carries it beyond `visible` in that case).

    Returns (dtypes, dicts, names, visible, chain') — chain' has Map exprs
    for dropped outputs removed, since the kernel evaluates every listed
    expr (an unneeded expr over a pruned input would fail to resolve).
    """
    chain, req = _chain_required_columns(chain, set(needed_end))
    keep_visible = [n for n in visible if n in req]
    if not keep_visible and visible:
        keep_visible = [visible[0]]  # row count still needs one column
    keep = list(keep_visible)
    has_bounds = (getattr(head, "start_time", None) is not None
                  or getattr(head, "stop_time", None) is not None)
    if has_bounds and time_col is not None and time_col not in keep \
            and time_col in names:
        keep.append(time_col)
    dtypes = {n: dtypes[n] for n in keep}
    dicts = {n: dicts[n] for n in keep if n in dicts}
    return dtypes, dicts, keep, keep_visible, chain


def _chain_required_columns(chain, needed: set):
    """Backward dataflow through Map (full-list projection semantics) and
    Filter: -> (pruned_chain, required_source_columns)."""
    new_rev = []
    for op in reversed(chain):
        if isinstance(op, MapOp):
            defined = {name for name, _ in op.exprs}
            kept = [(name, ex) for name, ex in op.exprs if name in needed]
            out = set()
            for _name, ex in kept:
                out |= _expr_columns(ex)
            needed = out | (needed - defined)
            op = (dataclasses.replace(op, exprs=kept)
                  if len(kept) != len(op.exprs) else op)
        elif isinstance(op, FilterOp):
            needed = needed | _expr_columns(op.expr)
        new_rev.append(op)
    return list(reversed(new_rev)), needed


# -------------------------------------------------------------------- executor


def _state_on_cpu(state) -> bool:
    """True if every leaf of a partial state lives on host/CPU."""
    for leaf in jax.tree.leaves(state):
        if isinstance(leaf, np.ndarray):
            continue
        if isinstance(leaf, jax.Array):
            try:
                if any(d.platform != "cpu" for d in leaf.devices()):
                    return False
            except Exception:
                return False
        else:
            return False
    return True


@dataclasses.dataclass
class _DeferredState:
    """Un-pulled partial-agg state: per-feed device partials + the host merge
    to run after the (batched) readback."""

    partials: list
    merge_fn: Callable
    #: pre-merged state of any CPU-resident feeds (hot remainder), merged on
    #: host at defer time; folded in at finish
    host_state: object = None


@dataclasses.dataclass
class _DeferredPartial:
    """An agg_state channel payload whose readback is deferred: the cluster
    pulls `partials` (for ALL agents in one transfer wave) and then calls
    finish(pulled) -> PartialAggBatch.

    When every agent's `layout_fp` matches (same group-key value sets /
    dictionaries / UDA layout), the cluster instead merges ALL agents' states
    ON DEVICE (gang_merge_states) and finishes once on the merged state —
    the TPU-native tree reduction of SURVEY §2.5 P2, and 8x fewer readback
    bytes over the device→host link."""

    partials: list
    finish: Callable
    #: state-layout fingerprint; None = never gang-merge (e.g. sorted path)
    layout_fp: object = None
    #: finish on an ALREADY-MERGED state_np (gang path)
    finish_state: Optional[Callable] = None
    #: {out_name: reduce-op pytree} for the device merge
    reduce_tree: object = None
    #: CPU-feed state merged at defer time (not part of `partials`)
    host_state: object = None
    #: host merge fn for folding host_state into a pulled/merged state
    host_merge: Optional[Callable] = None


#: jitted state packers keyed by (treedef, leaf specs): every pulled LEAF
#: is its own device→host transfer, so the agg state (several
#: arrays: per-UDA accumulators + seen counts) is concatenated into ONE
#: buffer per distinct dtype in the same device program and unpacked from
#: the pulled buffers on host — the readback batched into the kernel's final
#: step.  Grouping is by dtype (not a single bitcast byte buffer) because
#: this runtime's X64 rewrite cannot compile bitcast-converts of 64-bit
#: element types.
_PACK_CACHE: dict = {}


@dataclasses.dataclass
class _PackedState:
    """A partial state living on device as per-dtype packed buffers."""

    buf: object  # tuple of concatenated per-dtype arrays
    unpack: Callable


def _state_packer(sample_state):
    """(pack_jit, unpack_np) for states shaped like `sample_state`, or None
    when packing cannot reduce the pulled leaf count (already one leaf per
    dtype) — the pack is a separate jitted dispatch, so a no-gain pack is
    pure overhead."""
    leaves, treedef = jax.tree.flatten(sample_state)
    spec = tuple((tuple(l.shape), np.dtype(l.dtype).str) for l in leaves)
    key = (treedef, spec)
    got = _PACK_CACHE.get(key)
    if got is not None:
        return got
    dtypes = sorted({d for _s, d in spec})
    if len(spec) <= len(dtypes):
        _PACK_CACHE[key] = None
        return None

    def pack(state):
        ls, _ = jax.tree.flatten(state)
        groups = {d: [] for d in dtypes}
        for x, (_shape, d) in zip(ls, spec):
            groups[d].append(x.reshape(-1))
        return tuple(jnp.concatenate(groups[d]) for d in dtypes)

    def unpack(bufs):
        offs = {d: 0 for d in dtypes}
        bufs_np = {d: np.asarray(b) for d, b in zip(dtypes, bufs)}
        out = []
        for shape, d in spec:
            n = int(np.prod(shape, dtype=np.int64))
            out.append(bufs_np[d][offs[d]: offs[d] + n].reshape(shape))
            offs[d] += n
        return jax.tree.unflatten(treedef, out)

    got = (jax.jit(pack), unpack)
    if len(_PACK_CACHE) > 128:
        _PACK_CACHE.clear()
    _PACK_CACHE[key] = got
    return got


@dataclasses.dataclass
class _FinalizedCol:
    """An output column finalized ON DEVICE and already pulled: the agg
    finalize step must run finalize_from_device on it instead of
    finalize_host on state bytes."""

    col: np.ndarray


@_contextlib.contextmanager
def sketch_finalize_span(uda, state, finalized: bool = False):
    """A `sketch_finalize` span around the finalize of a quantile sketch's
    host state (its histograms as `groups`, their bytes as
    `sketch_state_bytes`) into its column, wherever that runs: the
    merger's `finalize_partial`, a local aggregate's host finalize.  Where
    the producer `finalized` the sketch on its device, `state` is its bins
    and the span is around their formatting, with `sketch_state_bytes` 0.
    No span for any other aggregate."""
    from pixie_tpu import trace
    from pixie_tpu.udf.udf import QuantilesUDA, QuantileUDA

    if not isinstance(uda, (QuantileUDA, QuantilesUDA)):
        yield
        return
    state = np.asarray(state)
    with trace.span("sketch_finalize", groups=int(state.shape[0]),
                    sketch_state_bytes=0 if finalized else int(state.nbytes)):
        yield


#: jitted merge(+device finalize) of per-feed partials, keyed by the agg's
#: UDA spec — the single execution that replaces N per-feed state pulls +
#: a host merge with one small readback wave
_MERGE_FINALIZE_CACHE: dict = {}


def _device_finalize_split(udas_by_name, finalize_ok: bool = True):
    """state → (finals, rest) closure shared by the merge and fused paths:
    device-finalizable outputs run finalize_device, the rest pass through
    for the host finalize step."""
    fin = {name: uda for name, uda in udas_by_name.items()
           if finalize_ok and uda.device_finalize}

    def split(state):
        finals = {k: fin[k].finalize_device(state[k]) for k in fin}
        rest = {k: v for k, v in state.items() if k not in fin}
        return finals, rest

    return split


def _merge_finalize_fn(spec_key, reduce_tree, udas_by_name,
                       finalize_ok: bool = True):
    fn = _MERGE_FINALIZE_CACHE.get(spec_key)
    if fn is None:
        merge = ChainKernel.merge_states_fn(reduce_tree)
        finalize = _device_finalize_split(udas_by_name, finalize_ok)

        def run(*states):
            return finalize(merge(*states) if len(states) > 1 else states[0])

        fn = jax.jit(run)
        if len(_MERGE_FINALIZE_CACHE) > 64:
            _MERGE_FINALIZE_CACHE.clear()
        _MERGE_FINALIZE_CACHE[spec_key] = fn
    return fn


#: fused single-feed partial+finalize executions, keyed by the chain's cache
#: sig (which pins the kernel's structure, dictionaries, and key sets)
_FUSED_FINALIZE_CACHE: dict = {}


def _fused_partial_finalize(fuse_key, udas_by_name, partial_step):
    """ONE device execution for the single-feed warm query: the per-feed
    partial update and the device finalize trace TOGETHER, so a forced-TPU
    interactive query (1M rows = one coalesced feed) pays one execution +
    one small readback wave instead of two chained executions — every
    execution pays the fixed exec+readback cost (transfer.wave_rtt_floor),
    so this is the difference between sitting on that floor and 2x it."""
    fn = _FUSED_FINALIZE_CACHE.get(fuse_key)
    if fn is None:
        finalize = _device_finalize_split(udas_by_name)

        def run(cols, n_valid, t_lo, t_hi, luts):
            return finalize(partial_step(cols, n_valid, t_lo, t_hi, luts))

        fn = jax.jit(run)
        if len(_FUSED_FINALIZE_CACHE) > 64:
            _FUSED_FINALIZE_CACHE.clear()
        _FUSED_FINALIZE_CACHE[fuse_key] = fn
    return fn


#: jitted cross-agent state merges, keyed by (layout_fp, arity) — a fresh
#: jit per query would recompile the merge every time
_GANG_MERGE_CACHE: dict = {}


def gang_merge_states(deferred: list) -> object:
    """Merge every agent's per-feed device partials into ONE device state.
    Caller guarantees equal layout_fp across `deferred`."""
    flat: list = []
    for d in deferred:
        flat.extend(d.partials)
    if len(flat) == 1:
        return flat[0]
    key = (deferred[0].layout_fp, len(flat))
    fn = _GANG_MERGE_CACHE.get(key)
    if fn is None:
        # same stacked reduction as ChainKernel.make_merge_states, built from
        # the payload's reduce_tree (the kernel's udas aren't in scope here)
        fn = jax.jit(ChainKernel.merge_states_fn(deferred[0].reduce_tree))
        if len(_GANG_MERGE_CACHE) > 64:
            _GANG_MERGE_CACHE.clear()
        _GANG_MERGE_CACHE[key] = fn
    return fn(*flat)


#: multi-query gang fusion: fuse the distinct partial-agg chains of one
#: shared scan (the fused-batch agent-plan shape, serving/batching.py) into
#: ONE jitted program per wave — N queries pay one device dispatch per feed
#: instead of N, and the whole gang reads back in one transfer wave
_MQ_FUSION = _flags.define_int(
    "PX_MQ_FUSION", -1,
    "fuse sibling partial-agg chains sharing one scan into a single jitted "
    "multi-query program per feed wave (the batched-query device path): "
    "-1 = auto (on iff a real accelerator backs the dispatch devices — "
    "the gang amortizes per-execution RTT, while on XLA-CPU the extra "
    "per-chain-set compiles cost more than they save), 0 = never, "
    "1 = always (tests / forced proof)")

_HAS_ACCEL: "Optional[bool]" = None


def _mq_fusion_enabled() -> bool:
    v = int(_flags.get("PX_MQ_FUSION"))
    if v == 0:
        return False
    if v >= 1:
        return True
    global _HAS_ACCEL
    if _HAS_ACCEL is None:
        # a backend that fails to start raises here: answering "no
        # accelerator" would quietly run a chip deployment unfused
        _HAS_ACCEL = any(d.platform != "cpu" for d in jax.devices())
    return _HAS_ACCEL


@dataclasses.dataclass
class _AggSetup:
    """One aggregate's prepared execution state (see _agg_setup)."""

    op: AggOp
    head: object
    chain: list
    sig: Optional[str]
    dtypes: dict
    dicts: dict
    src: object
    names: list
    visible: list
    time_col: Optional[str]
    cap: int
    kern: "ChainKernel"
    keys: list
    udas: list
    in_types: dict
    init_specs: list
    num_groups: int
    seen_name: str
    step: Callable
    partial_step: Callable
    merge_fn: Callable
    spmd_step: Optional[Callable]
    val_dicts: dict
    lut_over: dict


class PlanExecutor:
    def __init__(self, plan: Plan, table_store, registry=None, inputs=None,
                 mesh="auto", analyze: bool = False, udtf_ctx=None,
                 otel_exporter=None, route_scale: int = 1,
                 force_backend: Optional[str] = None):
        from pixie_tpu.udf import registry as default_registry

        self.plan = plan
        self.store = table_store
        self.registry = registry or default_registry
        #: channel id → HostBatch injected by the cluster layer (remote edges;
        #: reference: GRPCRouter demuxing inbound streams, grpc_router.h:52)
        self.inputs: dict[str, HostBatch] = inputs or {}
        self._materialized: dict[int, HostBatch] = {}
        #: compile_s / compiles: jax trace + lower + backend-compile seconds
        #: and backend compiles of this query (_on_jax_duration fills them)
        self.stats = {"rows_scanned": 0, "rows_output": 0, "batches": 0,
                      "compile_s": 0.0, "compiles": 0}
        #: per-kernel / per-blocking-op timing records (the reference's
        #: ExecNodeStats analog, exec_node.h:41; grain = compiled unit).
        self.op_stats: list[dict] = []
        self._stat_stack: list[dict] = []
        #: analyze mode (reference ExecutePlan(analyze=true), carnot.cc:318):
        #: synchronizes the device after every feed so per-kernel wall times
        #: measure real execution, not async dispatch.
        self.analyze = analyze
        #: ambient state for UDTF sources (udf.udtf.UDTFContext); None builds
        #: a local-view context on demand.
        self.udtf_ctx = udtf_ctx
        #: override transport for OTel export sinks (tests inject a collector;
        #: None resolves from each sink's endpoint config).
        self.otel_exporter = otel_exporter
        #: distributed fan-out: how many data agents run this same fragment.
        #: CPU/TPU routing multiplies local input sizes by this so a sharded
        #: query routes by its TOTAL size (see _route_backend).
        self.route_scale = max(1, int(route_scale))
        #: adaptive-routing decisions taken for this query, one per
        #: (route class, size bucket) (engine/autotune.py; empty with
        #: PX_AUTOTUNE=0)
        self._at_route: dict[tuple, dict] = {}
        #: id(src) -> (src, model key) of the chains the model routes: those
        #: whose wall is folded back into the decision that routed them
        #: (_name_route); the src is held so that its id is not reused
        self._route_keys: dict[int, tuple] = {}
        #: pin the dispatch backend regardless of input size.  The streaming
        #: executor pins "cpu": every poll delta would re-UPLOAD its rows to
        #: the device (hot data is host-resident), so size-based routing is
        #: wrong for polls however large the delta.  Valid: None, "cpu",
        #: "device" (the two _route_backend labels).
        if force_backend not in (None, "cpu", "device"):
            raise InvalidArgument(
                f"force_backend={force_backend!r}: expected None, 'cpu' "
                "or 'device'")
        self.force_backend = force_backend
        #: colocated-agent mode (LocalCluster): partial-agg channels return
        #: device-resident state (_DeferredPartial) instead of pulling — the
        #: cluster coalesces ALL agents' readbacks into ONE transfer wave.
        #: Each sync readback pays a fixed round trip, so 8 agents pulling
        #: separately cost ~8 waves; what a wave costs on the current chip
        #: is unverified (ROADMAP S2).
        self.defer_agg_pull = False
        # Device mesh for SPMD aggregation: every unlimited agg shards its
        # feeds over all local devices and merges state with in-program
        # collectives (the reference's per-PEM fan-out + Kelvin merge becomes
        # mesh axes + psum — SURVEY §2.5).  "auto" = all local devices when >1.
        if mesh == "auto":
            from pixie_tpu.parallel.spmd import default_mesh

            mesh = default_mesh()
        self.mesh = mesh
        if mesh is not None:
            # the XLA-CPU collective-serialization workaround is a GATED
            # decision (parallel.spmd.collective_gate), recorded per query
            # like the device-join gate so rounds can audit it
            from pixie_tpu.parallel.spmd import collective_gate

            gate = {k: v for k, v in collective_gate(mesh).items()
                    if k != "_key"}
            self.stats.setdefault("device", {})["collective_gate"] = gate

    # ------------------------------------------------------------- routing
    def _name_route(self, src, head, chain, op) -> None:
        """Give the chain `head -> chain -> op` over `src` its model key,
        (route class, size bucket): its caller folds the chain's wall into
        the decision that routes it.  No key with the model off, the
        backend forced or an input of unknown size; and none for a chain
        nobody names here, which the static crossover routes alone (a key
        whose completions nothing folds back would stay cold and probe
        for ever)."""
        n = _src_rows(src)
        if (self.force_backend is None and n is not None
                and _autotune.enabled()):
            self._route_keys[id(src)] = (src, (
                _route_class_of(head, chain, op),
                _autotune.size_bucket(n * self.route_scale)))

    def _route_key(self, src) -> Optional[tuple]:
        return self._route_keys.get(id(src), (None, None))[1]

    def _route_decision(self, src) -> Optional[dict]:
        """The model's decision this query took for the chain over `src`,
        if it took one."""
        return self._at_route.get(self._route_key(src))

    def _backend_for(self, src) -> str:
        if self.force_backend is not None:
            return self.force_backend
        static = _route_backend(src, self.route_scale)
        key = self._route_key(src)
        if key is None:
            return static
        # the model's key is (gate, route class, size bucket): the class
        # names the chain being routed (_route_class_of), so two scripts
        # over the same rows price their own arms.  One decision per key
        # per executor: every _backend_for call for this chain routes
        # consistently (fast paths ask repeatedly), and stats["autotune"]
        # carries exactly the decisions this query ran under
        dec = self._at_route.get(key)
        if dec is None:
            dec = _autotune.MODEL.decide(
                _autotune.GATE_CPU_CROSSOVER, key[0], key[1],
                "cpu" if static == "cpu" else "device", ("cpu", "device"))
            self._at_route[key] = dec
            self.stats.setdefault("autotune", []).append(dec)
        return "cpu" if dec["arm"] == "cpu" else "device"

    def _observe_route(self, src, rec: dict, compile_s0: float) -> None:
        """Fold the measured wall of the chain over `src` (its _timed frame
        `rec`) into the routing decision that picked its backend (per-arm
        cost model, engine/autotune.py).  The sample is the chain's wall
        less what jax compiled in it since `compile_s0` (recorded beside
        it): a program's first run must not price its arm.  jax reports
        nested traces inside their outer one too, so compile_s can pass the
        wall by a few percent and that one sample reads 0."""
        dec = self._route_decision(src)
        if dec is not None and rec.get("wall_ns"):
            compile_s = self.stats["compile_s"] - compile_s0
            _autotune.MODEL.observe_decision(
                dec, max(rec["wall_ns"] / 1e9 - compile_s, 0.0))
            dec["compile_ms"] = round(compile_s * 1e3, 3)

    def _device_ctx(self, src):
        if self._backend_for(src) == "cpu":
            return jax.default_device(_cpu_device())
        return _contextlib.nullcontext()

    def _note_engine(self, engine: str, rec: Optional[dict] = None,
                     src=None, kern: Optional["ChainKernel"] = None,
                     num_groups: Optional[int] = None) -> None:
        """Record which engine ran one of this query's chains, and the
        platform and device_kind its kernels were dispatched to, in
        stats["device"] — so exec_stats, the flight recorder and EXPLAIN
        ANALYZE say where a query ran instead of inferring it from a
        routing label.  Engines: "device_chain" (jitted chain on the
        process's default device or mesh), "xla_cpu_chain" (the same
        chain pinned to XLA-CPU), "np_partial", "wholeplan" (host numpy /
        native loops; no kernel is dispatched).  "platform" names the
        accelerator-route device when any chain ran there, else "cpu".
        `rec`, the chain's _timed frame, takes the engine and the routing
        decision `src` ran under as the attributes of its trace span, and,
        for a jitted chain over `kern`, how its program applies its LUTs
        (`lut_select`/`lut_blocked`/`lut_gather`: call this inside the
        chain's device context, where the program is traced).  A dense
        aggregate passes `num_groups`, the span's `groups`; `agg_form`
        beside it says how its sums and counts reduce them: the host
        engines scatter, a jitted chain's feeds say theirs as they are
        dispatched (_agg_feed_loop)."""
        if rec is not None:
            span = rec.setdefault("span", {})  # _feed may have come first
            span.update(engine=engine, **self._route_attrs(src))
            if kern is not None:
                span.update(kern.lut_forms())
            if num_groups is not None:
                span["groups"] = num_groups
                if engine in ("np_partial", "wholeplan"):
                    span["agg_form"] = "scatter"
        dev = self.stats.setdefault("device", {})
        engines = dev.setdefault("engines", {})
        engines[engine] = engines.get(engine, 0) + 1
        if engine == "device_chain":
            if self.mesh is not None:
                d = self.mesh.devices.flat[0]
            else:
                d = jax.config.jax_default_device or jax.devices()[0]
            if isinstance(d, str):  # jax_default_device may name a platform
                d = jax.devices(d)[0]
            dev["platform"], dev["device_kind"] = d.platform, d.device_kind
        elif "platform" not in dev:
            d = _cpu_device()
            dev["platform"], dev["device_kind"] = d.platform, d.device_kind

    def _note_chain(self, src, rec: Optional[dict] = None,
                    kern: Optional["ChainKernel"] = None,
                    num_groups: Optional[int] = None) -> None:
        self._note_engine("xla_cpu_chain" if self._backend_for(src) == "cpu"
                          else "device_chain", rec, src, kern, num_groups)

    def _route_attrs(self, src) -> dict:
        """What the router decided for a chain over `src`: the arm it ran
        on and, where the adaptive model took the decision, its source
        (`model`/`static`/`cold`/`explore`/`fallback`), the key's route
        class and size bucket, the decision's number and how often the
        key's tail guard has tripped so far.  A chain span with
        source=explore is a router probe."""
        n = _src_rows(src) if src is not None else None
        if n is None:
            return {}
        attrs = {"arm": self._backend_for(src), "rows": n}
        dec = self._route_decision(src)
        if dec is not None:
            attrs.update(source=dec["source"], plan_class=dec["plan_class"],
                         size_bucket=dec["size_bucket"], decision_n=dec["n"],
                         guard_trips=dec["guard_trips"])
        return attrs

    # -------------------------------------------------------------- exec stats
    @_contextlib.contextmanager
    def _timed(self, label: str, ops: list[int], spanned: bool = True):
        """Record a wall-time frame; nesting attributes child time so
        self_ns = wall_ns - nested frames (exec_node.h self/total split).
        `spanned=False`: the frame is in the op stats and is no trace span
        (a frame no reader names: the blocking aggregate and union, the
        gang, the OTel export).

        The parent is captured at ENTER and the frame is removed by identity:
        frames opened inside generators close at exhaustion/GC, not in LIFO
        order, so a plain stack pop could discharge someone else's frame.
        """
        rec = {"ops": ops, "label": label, "wall_ns": 0, "rows_out": 0,
               "bytes_out": 0, "_child_ns": 0,
               # wall-clock anchor so the frame adapts into a trace span
               # (self-telemetry) without extra timing calls
               "t0_unix_ns": _time.time_ns()}
        if not spanned:
            rec["unspanned"] = True
        parent = self._stat_stack[-1] if self._stat_stack else None
        self._stat_stack.append(rec)
        t0 = _time.perf_counter_ns()
        try:
            yield rec
        finally:
            rec["wall_ns"] = _time.perf_counter_ns() - t0
            try:
                self._stat_stack.remove(rec)
            except ValueError:
                pass
            if parent is not None and "_child_ns" in parent:
                # A parent that already closed (abandoned generator finalized
                # late) has popped its _child_ns; skip attribution then.
                parent["_child_ns"] += rec["wall_ns"]
            rec["self_ns"] = rec["wall_ns"] - rec.pop("_child_ns")
            self.op_stats.append(rec)

    def _emit_op_spans(self) -> None:
        """Adapt the per-op exec stats into trace spans (near-zero cost: the
        frames already carry wall-clock anchors; under no active trace this
        is one ContextVar read)."""
        from pixie_tpu import trace

        if not self.op_stats or trace.current() is None:
            return
        for rec in self.op_stats:
            t0 = rec.get("t0_unix_ns")
            if t0 is None or rec.get("unspanned"):
                continue
            trace.event_span(rec["label"], t0, rec["wall_ns"],
                             rows_out=rec.get("rows_out", 0),
                             **rec.get("span", {}))

    def _chain_label(self, head, chain, terminal: str = "") -> str:
        parts = []
        if isinstance(head, MemorySourceOp):
            parts.append(f"scan({head.table})")
        elif isinstance(head, RemoteSourceOp):
            parts.append(f"remote({head.channel})")
        else:
            parts.append(head.kind)
        parts.extend(op.kind for op in chain)
        if terminal:
            parts.append(terminal)
        return "->".join(parts)

    # ------------------------------------------------------------ plan walking
    def _upstream_chain(self, op):
        """Walk up through streamable transforms. Returns (head, [transforms...])."""
        chain = []
        cur = op
        while isinstance(cur, (MapOp, FilterOp, LimitOp)):
            chain.append(cur)
            parents = self.plan.parents(cur)
            if len(parents) != 1:
                raise Internal(f"transform {cur.kind} must have exactly one parent")
            cur = parents[0]
        return cur, list(reversed(chain))

    def _input_of(self, head):
        """head is a Source or blocking op.

        Returns (dtypes, dicts, src, feed_names, visible_names, time_col, cap).
        feed_names may include a hidden time_ column fetched only so row-level
        time bounds can be applied; visible_names excludes it.
        """
        if isinstance(head, MemorySourceOp):
            table = self.store.table(head.table)
            if head.tablet is not None:
                from pixie_tpu.table.tablets import TabletsGroup

                if not isinstance(table, TabletsGroup):
                    raise InvalidArgument(
                        f"table {head.table!r} is not tabletized (tablet="
                        f"{head.tablet!r} requested)"
                    )
                table = table.tablet(head.tablet)
            if head.since_row_id is not None or head.stop_row_id is not None:
                cursor = table.cursor_since(
                    head.since_row_id or 0, head.stop_row_id,
                    head.start_time, head.stop_time,
                )
            else:
                cursor = table.cursor(head.start_time, head.stop_time)
            visible = list(head.columns or table.relation.names())
            names = list(visible)
            has_bounds = head.start_time is not None or head.stop_time is not None
            if has_bounds and table.time_col is not None and table.time_col not in names:
                names.append(table.time_col)
            dtypes = {n: table.relation.dtype(n) for n in names}
            dicts = {n: table.dictionaries[n] for n in names if n in table.dictionaries}
            return dtypes, dicts, cursor, names, visible, table.time_col, table.batch_rows
        hb = self._eval_blocking(head)
        return hb.dtypes, hb.dicts, hb, list(hb.cols), list(hb.cols), None, MIN_BUCKET

    def _heat_recorder(self, src):
        """Shard-heat accounting hook shared by every scan path (the
        coalescing `_feed`, np_partial's fused window loop, the wholeplan
        native loop): a per-stream FeedRecorder, or None when tracing is
        off or `src` is not a storage cursor — flag-off never touches the
        model."""
        from pixie_tpu import observe as _observe

        table = getattr(src, "table", None)
        if table is None or not _observe.enabled():
            return None
        from pixie_tpu.table import heat as _heat

        return _heat.FeedRecorder(
            table, getattr(self.store, "node_name", "") or "local")

    def _note_shard_rows(self, per_shard) -> None:
        """Per-shard placement accounting for SPMD feeds: accumulates each
        feed's per-shard valid rows and keeps the skew ratio (max/mean shard
        rows) visible — stats["shard_rows"]/["shard_skew_frac"] plus the
        px_shard_skew_frac gauge.  1.0 = perfectly even placement; row-major
        block sharding should stay near 1 except at uneven tails."""
        rows = [int(x) for x in np.asarray(per_shard).reshape(-1)]
        acc = self.stats.get("shard_rows")
        if not isinstance(acc, list) or len(acc) != len(rows):
            acc = [0] * len(rows)
        acc = [a + r for a, r in zip(acc, rows)]
        self.stats["shard_rows"] = acc
        skew = _shard_skew(acc)
        self.stats["shard_skew_frac"] = round(skew, 4)
        from pixie_tpu import metrics as _metrics

        _metrics.gauge_set(
            "px_shard_skew_frac", skew,
            help_="max/mean rows per mesh shard over this process's latest "
                  "SPMD query feeds (placement-skew visibility; 1.0 = even)")

    # ------------------------------------------------------------- stream feed
    def _predicted_single_feed(self, src, cap) -> bool:
        """Exact feed count from snapshot metadata (mirrors _feed's flush
        logic: hot remainder flushes pending sealed rows; sealed rows
        coalesce to the feed target).  Cursors are immutable snapshots, so
        the prediction cannot be invalidated by concurrent writes."""
        if isinstance(src, HostBatch):
            return True
        target = max(cap, FEED_ROWS)
        cold_gens = getattr(src, "cold_gens", None) or frozenset()
        # metadata iteration: sizing feeds must never materialize data —
        # iter_meta answers from counts, so a mostly-cold retention window
        # costs zero decodes here
        meta = (src.iter_meta() if hasattr(src, "iter_meta")
                else ((rb.num_valid, rid, gen) for rb, rid, gen in src))
        feeds = pend_rows = 0
        pend_cold = False
        for n, _row_id, gen in meta:
            if n == 0:
                continue
            is_cold = gen in cold_gens
            if pend_rows and (gen is None or is_cold != pend_cold):
                feeds += 1
                pend_rows = 0
            pend_cold = is_cold
            pend_rows += n
            if pend_rows >= target:
                feeds += 1
                pend_rows = 0
        if pend_rows:
            feeds += 1
        return feeds <= 1

    def _feed(self, src, names, cap, spmd: bool = False,
              backend: str = "device"):
        """`_feed_batches`, with each feed's bucketed row count added to
        `feed_rows` on the span of the `_timed` frame that consumes it (the
        chain's: beside `rows` it says what share of the bucket is live,
        which is the share of chunks the kernels' loops visit), and under
        an active trace one `feed` span a chain: from the first batch asked
        for to the last feed yielded, with how many feeds there were, how
        many of them the resident tier served and the bytes that crossed
        host->device."""
        from pixie_tpu import trace

        span = (self._stat_stack[-1].setdefault("span", {})
                if self._stat_stack else {})
        traced = trace.current() is not None
        t0 = t_last = _time.time_ns()
        feeds = 0
        resident0 = self.stats.get("resident_feeds", 0)
        h2d0 = self.stats.get("h2d_bytes", 0)
        try:
            for item in self._feed_batches(src, names, cap, spmd, backend):
                feeds += 1
                span["feed_rows"] = span.get("feed_rows", 0) + _first_len(
                    item[0])
                t_last = _time.time_ns()
                yield item
        finally:
            if traced:
                trace.event_span(
                    "feed", t0, t_last - t0, feeds=feeds,
                    resident=self.stats.get("resident_feeds", 0) - resident0,
                    h2d_bytes=self.stats.get("h2d_bytes", 0) - h2d0)

    def _feed_batches(self, src, names, cap, spmd: bool = False,
                      backend: str = "device"):
        """Yield (cols np dict padded, n_valid) host batches.

        Cursor batches (storage granularity) are coalesced into ~FEED_ROWS
        device feeds: fewer kernel dispatches and transfers, and the bucketed
        shapes repeat so XLA's shape cache stays warm.

        spmd=True (the unlimited-agg path): cacheable feeds are placed SHARDED
        over the mesh, so repeat SPMD queries stream zero bytes and reshard
        nothing.  Single-device consumers (select/limit/join kernels) must NOT
        receive sharded inputs — their jits would get implicitly
        GSPMD-partitioned — so the placement (and the cache key) is gated on
        the consumer.
        """
        if isinstance(src, HostBatch):
            n = src.num_rows
            # Materialized intermediates can exceed the stream cap (e.g. many
            # groups out of an agg): bucket to their own pow2 size.
            bucket = max(MIN_BUCKET, next_pow2(max(n, 1)))
            cols = {k: _pad(src.cols[k], bucket) for k in names}
            yield cols, n
            return

        target = max(cap, FEED_ROWS)
        table_id = src.table.uid
        n_dev = self.mesh.size if (spmd and self.mesh is not None) else 1
        # Shard-heat accounting (table/heat.py): one recorder per feed
        # stream, bumped per coalesced emit with the serving tier.  Gated on
        # the tracing master switch — flag-off never touches the model.
        heat_rec = self._heat_recorder(src)

        def emit(parts, gens, n, cold=False):
            # Sealed-only feeds are immutable → serve/place them from the HBM
            # feed cache; anything touching the hot remainder streams fresh.
            # CPU-routed queries keep feeds as (cached) numpy — device_put to
            # TPU would commit the inputs there and defeat the routing.
            # Cold-tier feeds are decode-on-read by design: caching them
            # (resident or HBM) would promote through the back door and pin
            # the demoted window in memory — promotion is the cold tier's
            # explicit, read-heat-driven call.
            cacheable = (not cold
                         and all(g is not None for g in gens)
                         and not getattr(src, "is_delta", False))
            if cacheable and backend == "device":
                # Pinned-resident tier first: unlike the gen-tuple-keyed HBM
                # cache below, a new seal FOLDS into the resident buffer
                # (only the delta rows cross the link) instead of
                # invalidating the whole feed — the warm interactive query
                # then uploads zero bytes (engine/resident.py).  A legacy
                # cache entry for this exact feed (e.g. from a transient
                # budget fallback) is handed over for ADOPTION and then
                # dropped, so the bytes are never uploaded or pinned twice.
                # SPMD consumers (n_dev > 1) get the SHARDED-resident tier:
                # the same entry pinned column-wise across the mesh with a
                # NamedSharding, so warm sharded queries reshard nothing
                # and ingest deltas fold shard-local.
                sharding = None
                if n_dev > 1:
                    from jax.sharding import NamedSharding, PartitionSpec as P
                    from pixie_tpu.parallel.spmd import AGENT_AXIS

                    sharding = NamedSharding(self.mesh, P(AGENT_AXIS))
                lkey = (table_id, tuple(gens), tuple(names), n_dev, backend)
                got = resident.feed(table_id, tuple(names), gens, cap,
                                    parts, n,
                                    prewarmed=_device_cache_get(lkey),
                                    sharding=sharding, n_dev=n_dev)
                if got is not None:
                    _device_cache_pop(lkey)
                    rcols, h2d = got
                    self.stats["resident_feeds"] = (
                        self.stats.get("resident_feeds", 0) + 1)
                    self.stats["h2d_bytes"] = (
                        self.stats.get("h2d_bytes", 0) + h2d)
                    if heat_rec is not None:
                        heat_rec.record(parts, gens, "resident")
                    return rcols, n
            dkey = ((table_id, tuple(gens), tuple(names), n_dev, backend)
                    if cacheable else None)
            if dkey is not None:
                cached = _device_cache_get(dkey)
                if cached is not None:
                    self.stats["feed_cache_hits"] = self.stats.get("feed_cache_hits", 0) + 1
                    if heat_rec is not None:
                        heat_rec.record(parts, gens, "hbm_cache")
                    return dict(cached), n
            # Single-copy assembly: write every storage batch straight into the
            # padded bucket buffer (concatenate-then-pad would copy twice).
            # The bucket must hold n even when accumulation overshot `target`
            # (storage batch sizes don't necessarily divide the feed target).
            bucket = max(_bucket(n, target), next_pow2(max(n, 1)))
            cols = resident.assemble_padded(parts, names, bucket)
            if dkey is not None:
                if backend == "cpu":
                    dev = cols  # host arrays ARE the cpu-backend feed
                elif n_dev > 1 and bucket % n_dev == 0:
                    from jax.sharding import NamedSharding, PartitionSpec as P
                    from pixie_tpu.parallel.spmd import AGENT_AXIS

                    sh = NamedSharding(self.mesh, P(AGENT_AXIS))
                    dev = {k: jax.device_put(v, sh) for k, v in cols.items()}
                else:
                    dev = jax.device_put(cols)
                _device_cache_put(dkey, dev)
                cols = dict(dev)
            if backend != "cpu":
                # transfer accounting: a fresh device_put above, or a
                # numpy hot/delta feed that uploads at dispatch — either
                # way these bucketed bytes cross host->device (the stat
                # the zero-H2D warm-query assertion reads; LUT/limit
                # scalars are kilobytes and excluded)
                self.stats["h2d_bytes"] = (
                    self.stats.get("h2d_bytes", 0)
                    + sum(v.nbytes for v in cols.values()))
            if heat_rec is not None:
                heat_rec.record(parts, gens, "cold" if cold else "stream")
            if cold:
                # read-heat promotion hook (data-plane, not gated on the
                # observe switch): enough decodes of the same cold batch
                # move it back to RAM (PL_COLD_PROMOTE_READS)
                tier = getattr(src.table, "cold", None)
                if tier is not None:
                    tier.note_reads(gens)
            return cols, n

        cold_gens = getattr(src, "cold_gens", None) or frozenset()
        pend, gens, nrows = [], [], 0
        pend_cold = False
        for rb, _row_id, gen in src:  # cursor
            n = rb.num_valid
            if n == 0:
                continue
            is_cold = gen in cold_gens
            # The hot remainder (gen None) must not join a sealed feed: sealed
            # feeds are immutable and HBM-cached, the hot tail changes every
            # write — mixing them would force a full re-upload per query.
            # Cold↔RAM boundaries flush for the dual reason: a cold batch in
            # a RAM feed would poison the cacheable-feed key (and vice versa
            # hide RAM rows inside a never-cached cold feed).
            if pend and (gen is None or is_cold != pend_cold):
                yield emit(pend, gens, nrows, pend_cold)
                pend, gens, nrows = [], [], 0
            pend_cold = is_cold
            pend.append({k: rb.columns[k][:n] for k in names})
            gens.append(gen)
            nrows += n
            self.stats["rows_scanned"] += n
            self.stats["batches"] += 1
            if nrows >= target:
                yield emit(pend, gens, nrows, pend_cold)
                pend, gens, nrows = [], [], 0
        if pend:
            yield emit(pend, gens, nrows, pend_cold)

    # ---------------------------------------------------------------- blocking
    def _eval_blocking(self, op) -> HostBatch:
        got = self._materialized.get(op.id)
        if got is not None:
            return got
        if isinstance(op, AggOp):
            label = f"agg(by={op.groups})"
        elif isinstance(op, RemoteSourceOp):
            label = f"remote({op.channel})"
        else:
            label = op.kind
        with self._timed(label, [op.id], spanned=not isinstance(
                op, (AggOp, UnionOp))) as rec:
            if isinstance(op, AggOp):
                out = self._run_agg(op)
            elif isinstance(op, JoinOp):
                out = self._run_join(op, rec)
            elif isinstance(op, UnionOp):
                out = self._run_union(op)
            elif isinstance(op, MemorySourceOp):
                out = self._consume_to_batch(op, [])
            elif isinstance(op, UDTFSourceOp):
                out = self._run_udtf(op)
            elif isinstance(op, RemoteSourceOp):
                got = self.inputs.get(op.channel)
                if got is None:
                    raise Internal(f"no input injected for channel {op.channel!r}")
                out = got
            else:
                raise Internal(f"unexpected blocking op {op.kind}")
            rec["rows_out"] = out.num_rows
            rec["bytes_out"] = sum(v.nbytes for v in out.cols.values())
        self._materialized[op.id] = out
        return out

    def _chain_cache_sig(
        self, head, chain, dtypes, dicts, extra, include_times: bool = False
    ) -> Optional[str]:
        """Cache signature for a kernel over this chain; None = not cacheable.

        Table-headed chains: dictionaries are append-only, so (id, size) pins
        exact content (the table uid keeps id() stable).  Blocking-op heads
        (join/agg intermediates) get FRESH dictionary objects per query, so
        identity can't pin them — they cache by dictionary CONTENT fingerprint
        instead (small dicts only; hashing a huge dict would cost more than
        the compile it saves).  Without this, every query re-jits its
        post-join/post-agg kernels — the dominant cost of multi-stage plans.
        Source time bounds are RUNTIME args (t_lo/t_hi), so they are excluded
        from the signature unless the kernel bakes them (window aggs) —
        otherwise every '-5m'-style relative query would re-jit.
        """
        if not isinstance(head, MemorySourceOp):
            if any(d.size > CONTENT_SIG_MAX_DICT for d in dicts.values()):
                return None
            key = {
                "reg": self.registry.uid,
                "head": "blocking",
                "chain": [_op_sig(op) for op in chain],
                "dtypes": {n: int(t) for n, t in dtypes.items()},
                "dicts": {n: (d.size, _dict_fingerprint(d))
                          for n, d in dicts.items()},
                "extra": extra,
            }
            if _chain_uses_volatile(chain, self.registry):
                from pixie_tpu.metadata import state as _mdstate

                key["md_epoch"] = _mdstate.global_manager().epoch
            return _json.dumps(key, sort_keys=True, default=str)
        table = self.store.table(head.table)
        # _op_sig memoizes its dict on the op; copy before popping so the
        # shared cache keeps its time bounds for include_times=True callers.
        src_sig = dict(_op_sig(head))
        # Row-id bounds are pure runtime cursor state (streaming resume
        # tokens); kernels never bake them.
        src_sig.pop("since_row_id", None)
        src_sig.pop("stop_row_id", None)
        # The scan's column LIST is not kernel state either: chains prune
        # to the columns they read, and the pruned dtypes/dicts are in the
        # signature below.  A fused batch plan widens the shared scan to
        # the member-column union (plan.fusion._merge_pruned_scans) —
        # without this pop, every batch composition would re-jit kernels
        # identical to the solo-warmed ones.
        src_sig.pop("columns", None)
        if not include_times:
            src_sig.pop("start_time", None)
            src_sig.pop("stop_time", None)
        key = {
            "reg": self.registry.uid,
            "table": (head.table, table.uid),
            "src": src_sig,
            "chain": [_op_sig(op) for op in chain],
            "dtypes": {n: int(t) for n, t in dtypes.items()},
            "dicts": {n: (id(d), d.size) for n, d in dicts.items()},
            "extra": extra,
        }
        if _chain_uses_volatile(chain, self.registry):
            # Metadata UDFs bake the K8sSnapshot into LUTs at kernel-build
            # time; a new epoch must miss the cache even when no dictionary
            # grew (e.g. a pod rename reuses every existing string).
            from pixie_tpu.metadata import state as _mdstate

            key["md_epoch"] = _mdstate.global_manager().epoch
        return _json.dumps(key, sort_keys=True, default=str)

    def _consume_chain(self, terminal_parent, out_names=None):
        """Run the chain feeding `terminal_parent` through an output step.

        Returns (out_dtypes, out_dicts, iterator of (np_cols, np_mask)).
        """
        head, chain = self._upstream_chain(terminal_parent)

        # Fast path: a bare blocking op feeding a sink (the common shape for
        # aggregated results) is already a host batch — plain column selection,
        # no kernel (and no per-query XLA compile of a trivial projection).
        if not chain and not isinstance(head, MemorySourceOp):
            hb = self._eval_blocking(head)
            sel = out_names if out_names is not None else list(hb.cols)
            missing = [n for n in sel if n not in hb.cols]
            if missing:
                raise CompilerError(f"output columns {missing} not found")
            out_dtypes = {n: hb.dtypes[n] for n in sel}
            out_dicts = {n: hb.dicts[n] for n in sel if n in hb.dicts}

            def gen_direct():
                yield {n: hb.cols[n] for n in sel}, hb.num_rows

            return out_dtypes, out_dicts, sel, gen_direct()

        dtypes, dicts, src, names, visible, time_col, cap = self._input_of(head)
        if out_names is not None:
            dtypes, dicts, names, visible, chain = _prune_to_needed(
                head, chain, dtypes, dicts, names, visible, time_col,
                set(out_names),
            )
        sig = self._chain_cache_sig(
            head, chain, dtypes, dicts,
            ("out", tuple(out_names) if out_names is not None else None),
        )
        cached = _cache_get(sig)
        if cached is not None:
            kern, step, out_dtypes, out_dicts, out_names = cached
        else:
            kern = ChainKernel(dtypes, dicts, chain, self.registry, time_col, visible)
            if out_names is None:
                out_names = list(kern.ctx.visible)
            step, out_dtypes, out_dicts = kern.make_output_step(out_names)
            _cache_put(sig, (kern, step, out_dtypes, out_dicts, out_names))
        t_lo, t_hi = _time_bounds(head)
        luts = kern.luts

        label = self._chain_label(head, chain, "select")
        op_ids = [head.id] + [op.id for op in chain]

        def gen():
            # Double-buffered readback pipeline: every feed's step dispatches
            # async (limit budgets carried as a DEVICE vector, no host sync in
            # the dispatch path); one feed behind, the previous wave's count
            # lands (its async copy started at dispatch) and its count-sliced
            # outputs start their D2H copy — so that transfer is in flight
            # WHILE the current wave computes; two feeds behind, the sliced
            # outputs materialize and yield.  Each readback pays a fixed
            # round trip; here every wave's copy is issued under a later
            # wave's compute, so the RTTs hide instead of serializing at the
            # end (transfer.AsyncPull records the overlap split per wave).
            from collections import deque

            with self._timed(label, op_ids) as rec, self._device_ctx(src):
                self._note_chain(src, rec, kern)
                has_limit = kern.has_limit
                remaining = kern.init_limits()
                computing: deque = deque()  # (outs, cnt): compute dispatched
                pulling: deque = deque()    # (AsyncPull, rows): D2H in flight
                feed_ns = []

                def start_readback(overlapped: bool):
                    outs, cnt = computing.popleft()
                    c = int(np.asarray(cnt))
                    pulling.append(
                        (transfer.pull_async({k: v[:c] for k, v in outs.items()}),
                         c))
                    if overlapped:
                        rec["pipelined_waves"] = rec.get("pipelined_waves", 0) + 1
                        self.stats["pipelined_waves"] = (
                            self.stats.get("pipelined_waves", 0) + 1)

                def emit_ready():
                    h, c = pulling.popleft()
                    cols_np = h.wait()
                    rec["rows_out"] += c
                    rec["bytes_out"] += sum(v.nbytes for v in cols_np.values())
                    return cols_np, c

                for cols, n_valid in self._feed(
                        src, names, cap, backend=self._backend_for(src)):
                    tf0 = _time.perf_counter_ns()
                    outs, cnt, consumed = step(
                        cols, np.int64(n_valid), t_lo, t_hi, remaining, luts
                    )
                    if has_limit:
                        # Only limit queries need the budget threaded (chains
                        # the per-feed executions); unlimited scans stay
                        # independent.
                        remaining = remaining - consumed
                    if self.analyze:
                        jax.block_until_ready(outs)
                        feed_ns.append(_time.perf_counter_ns() - tf0)
                    if isinstance(cnt, jax.Array):
                        # the count rides home under this wave's own compute
                        cnt.copy_to_host_async()
                    computing.append((outs, cnt))
                    if len(computing) >= 2:
                        start_readback(overlapped=True)
                    while len(pulling) >= 2:
                        yield emit_ready()
                if self.analyze and feed_ns:
                    rec["feed_ns"] = feed_ns
                if has_limit:
                    # Surface each LimitOp's remaining budget (chain order) —
                    # the streaming executor carries these across polls;
                    # decrementing by emitted rows instead would over-deliver
                    # when a filter follows a limit.
                    rec["limit_remaining"] = [
                        int(x) for x in np.asarray(jax.device_get(remaining))
                    ]
                while computing:
                    start_readback(overlapped=False)
                while pulling:
                    yield emit_ready()

        return out_dtypes, out_dicts, out_names, gen()

    def _consume_to_batch(self, terminal_parent, out_names=None) -> HostBatch:
        out_dtypes, out_dicts, out_names, gen = self._consume_chain(terminal_parent, out_names)
        parts = [c for c, _ in gen]
        cols = {
            n: (
                np.concatenate([p[n] for p in parts])
                if parts
                else np.empty(0, STORAGE_DTYPE[out_dtypes[n]])
            )
            for n in out_names
        }
        return HostBatch(out_dtypes, out_dicts, cols)

    # --------------------------------------------------------------------- agg
    def _plan_group_keys(self, op: AggOp, kern: ChainKernel, src, head) -> list[GroupKey]:
        keys = []
        for name in op.groups:
            sv = kern.ctx.sym.get(name)
            if sv is None:
                raise CompilerError(f"group key {name!r} not found")
            if sv.dictionary is not None:
                keys.append(
                    GroupKey(
                        name,
                        "dict",
                        next_pow2(max(sv.dictionary.size, 1)),
                        sv.dtype,
                        sv.dictionary,
                        key_sval=sv,
                    )
                )
                continue
            # A bin key gets window-range semantics ONLY over the source time
            # column — px.bin over a value column must go through the generic
            # paths or it would collapse into bogus time-range bins.
            wk = _window_key(kern.ctx.provenance.get(name), kern.time_col)
            if wk is not None and sv.dtype in (DT.TIME64NS, DT.INT64):
                width = wk
                t_min, t_max = _source_time_range(src, head)
                t0_bin = t_min // width
                nbins = int(t_max // width - t0_bin) + 1
                # The window ORIGIN is a runtime parameter (fed through the
                # luts dict, see _refresh_window_keys), NOT baked into the
                # kernel: streaming polls and shifting '-5m' ranges then reuse
                # one compiled kernel.  Only the bin-count bucket is static;
                # it grows (cache bust) if a later range spans more bins.
                t0name = kern.ctx.ec._add_lut(np.asarray([t0_bin], dtype=np.int64))
                keys.append(
                    GroupKey(
                        name,
                        "window",
                        next_pow2(max(nbins, MIN_WINDOW_BINS)),
                        sv.dtype,
                        width=width,
                        t0_bin=int(t0_bin),
                        key_sval=sv,
                        lut_name=t0name,
                    )
                )
                continue
            if sv.dtype in (DT.INT64, DT.TIME64NS, DT.BOOLEAN):
                prov = kern.ctx.provenance.get(name)
                if not isinstance(prov, Column):
                    raise GroupKeyFallback(
                        f"group key {name!r} is a computed numeric column"
                    )
                # Device-side encoding: the uniques come from the per-table
                # incremental union when available (matches the kernel-cache
                # signature and costs O(new rows)); otherwise one prescan
                # over this query's cursor.  Sorted, so dictionary code ==
                # sorted position; the kernel maps value→code against a
                # small runtime array — no per-batch host encode.
                from pixie_tpu.table.table import Table as _Table

                qd = Dictionary()
                u = None
                if isinstance(head, MemorySourceOp) and head.tablet is None:
                    t = self.store.table(head.table)
                    if type(t) is _Table and prov.name in t.relation:
                        u = _int_key_uniques(t, prov.name, src)
                if u is not None:
                    qd.encode(u.tolist())
                else:
                    _prescan_unique(src, prov.name, qd, sort=True)
                vals = np.asarray(qd.values(), dtype=np.int64)
                lut_name = kern.ctx.ec._add_lut(vals)
                keys.append(
                    GroupKey(
                        name,
                        "intdevice",
                        next_pow2(max(qd.size, 1)),
                        sv.dtype,
                        qd,
                        src_name=prov.name,
                        lut_name=lut_name,
                    )
                )
                continue
            raise GroupKeyFallback(f"group key {name!r} has type {sv.dtype.name}")
        total = 1
        for k in keys:
            total *= k.card
        if total > MAX_GROUPS:
            raise GroupKeyFallback(
                f"group cardinality bound {total} exceeds {MAX_GROUPS}"
            )
        return keys

    def _run_agg(self, op: AggOp) -> HostBatch:
        try:
            keys, udas, state_np, seen_name, in_types, val_dicts = self._agg_state(op)
        except GroupKeyFallback:
            return self._run_agg_sorted(op)
        return self._finalize_agg(op, keys, udas, state_np, seen_name, in_types,
                                  val_dicts)

    # ------------------------------------------------------- sorted aggregate
    def _sorted_agg_kernel(self, op: AggOp, sig, dtypes, dicts, chain,
                           time_col, visible):
        """Fetch-or-build the sorted aggregate's kernel bundle for `op`:
        (kern, rows_step, reduce_step, rest_step, key_specs, cards, udas,
        in_types, val_dicts).  `udas` holds (out_name, uda, index of its
        value column | None, input dtype); `key_specs` (name, DataType,
        dictionary | None) per group key."""
        cached = _cache_get(sig)
        if cached is not None:
            return cached
        kern = ChainKernel(dtypes, dicts, chain, self.registry, time_col,
                           visible)
        key_specs, key_builders = [], []
        for g in op.groups:
            sv = kern.ctx.sym.get(g)
            if sv is None:
                raise CompilerError(f"group key {g!r} not found")
            if sv.dictionary is not None:
                kind = "dict"
            elif sv.dtype == DT.FLOAT64:
                kind = "float"
            elif sv.dtype in (DT.INT64, DT.TIME64NS, DT.BOOLEAN):
                kind = "int"
            else:
                raise Unimplemented(
                    f"group key {g!r} has type {sv.dtype.name} and no "
                    "dictionary")
            key_specs.append((g, sv.dtype, sv.dictionary))
            key_builders.append((sv.build, kind))
        # the keys of known cardinality (dictionary codes, flags) ride the
        # sorts as one mixed-radix id where the product of their pow2
        # cardinalities fits 62 bits, the others after it as they are
        cards = [next_pow2(max(d.size, 1)) if d is not None
                 else 2 if dt == DT.BOOLEAN else None
                 for _g, dt, d in key_specs]
        if math.prod(c for c in cards if c) >= (1 << 62):
            cards = [None] * len(cards)
        udas, in_types = [], {}
        val_dicts: dict[str, Dictionary] = {}
        value_names: list[str] = []
        value_builders = []
        for ae in op.values:
            uda = self.registry.uda(ae.fn)
            vi = in_dt = None
            in_types[ae.out_name] = None
            if ae.arg is not None:
                sv = kern.ctx.sym.get(ae.arg)
                if sv is None:
                    raise CompilerError(
                        f"agg input column {ae.arg!r} not found")
                picker = sv.dictionary is not None
                if picker and not uda.dict_ok:
                    raise Unimplemented(
                        f"aggregate {ae.fn} over string column {ae.arg!r}")
                if not picker and getattr(uda, "needs_dict", False):
                    raise Unimplemented(
                        f"aggregate {ae.fn} requires a string "
                        f"(dictionary-encoded) input column, got {ae.arg!r}")
                in_types[ae.out_name] = sv.dtype
                in_dt = np.int32 if picker else STORAGE_DTYPE[sv.dtype]
                if picker:
                    val_dicts[ae.out_name] = sv.dictionary
                if ae.arg not in value_names:  # one payload a column
                    value_names.append(ae.arg)
                    b = sv.build
                    # null codes must never win the picker's min-reduction
                    value_builders.append(
                        (lambda env, b=b: jnp.where(
                            (v := b(env)) >= 0, v,
                            jnp.int32(PICKER_NULL_SENTINEL)))
                        if picker else b)
                vi = value_names.index(ae.arg)
            elif not uda.nullary:
                raise CompilerError(
                    f"aggregate {ae.fn} requires an input column")
            udas.append((ae.out_name, uda, vi, in_dt))
        rows_step = kern.make_keyed_rows_step(key_builders, cards,
                                              value_builders)
        n_keys = any(cards) + sum(not c for c in cards)
        scans = [u for u in udas if u[1].segment_only]
        rest = [u for u in udas if not u[1].segment_only]

        def reduce_step(mask, keys_sorted, order, values):
            """Over the rows as `sort_order` ordered them: every run of
            equal keys reduced at its last row.  -> (groups, the key whose
            `sort_order` brings those rows to the front, {"keys", "state"}
            with [n] leaves, and what `rest_step` needs)."""
            n = mask.shape[0]
            keys_s = list(keys_sorted[-n_keys:])
            runs, live = _gb.runs_of(keys_s, jnp.sum(mask.astype(jnp.int32)))
            values_s = _gb.take_rows(values, order)
            state = {
                name: uda.update(
                    uda.init(n, in_dt), runs,
                    None if vi is None else values_s[vi], live, n)
                for name, uda, vi, in_dt in scans}
            groups = jnp.sum(runs.end.astype(jnp.int32))
            after = ((_gb.run_ids(runs), live, values_s) if rest else None)
            return (groups, _gb.run_end_key(runs),
                    {"keys": keys_s, "state": state}, after)

        def rest_step(seg, live, values_s, gb, fin):
            """The aggregates that do not reduce runs (sketches, code
            histograms), over the runs' exact ids into `gb` slots; those
            named in `fin` finalized in the same execution (their bins)."""
            out = {}
            for name, uda, vi, in_dt in rest:
                state = uda.update(uda.init(gb, in_dt), seg,
                                   None if vi is None else values_s[vi],
                                   live, gb)
                out[name] = uda.finalize_device(state) if name in fin else state
            return out

        bundle = (kern, rows_step, jax.jit(reduce_step),
                  jax.jit(rest_step, static_argnums=(3, 4)) if rest else None,
                  key_specs, cards, udas, in_types, val_dicts)
        _cache_put(sig, bundle)
        return bundle

    def _sorted_group_reduce(self, op: AggOp, finalize: bool):
        """The aggregate for a group space the dense form does not serve
        (GroupKeyFallback), on whichever arm the router picks for its
        chain.  The chain's rows, keys and value columns stay where the
        feed is; there the rows are sorted by their keys, every run of
        equal keys is reduced by a segmented scan and a second sort finds
        the runs' results (ops/groupby.sort_order, runs_of, run_end_key:
        the analog of the reference's unbounded hash map,
        exec/agg_node.h:55-140, with no scatter and no dense state).  Where
        `finalize`, the device-finalizable aggregates (the quantile
        sketches) are finalized in the execution that updates them, as the
        dense form's are.  The host reads the number of groups, then their
        keys, the finalized columns' bins and the other states, and nothing
        else.

        Returns (group_cols, dtypes, dicts, udas, in_types, state_np, G,
        val_dicts): the leaves of state_np hold at least G slots, in the
        order of group_cols' rows, a finalized column as a `_FinalizedCol`;
        val_dicts maps dict-valued picker outputs to the dictionary their
        code-state decodes through.
        """
        self.stats["sorted_agg_fallbacks"] = self.stats.get("sorted_agg_fallbacks", 0) + 1
        head, chain = self._upstream_chain(self.plan.parents(op)[0])
        dtypes, dicts, src, names, visible, time_col, cap = self._input_of(head)
        needed = set(op.groups) | {ae.arg for ae in op.values
                                   if ae.arg is not None}
        dtypes, dicts, names, visible, chain = _prune_to_needed(
            head, chain, dtypes, dicts, names, visible, time_col, needed)
        self._name_route(src, head, chain, op)
        sig = self._chain_cache_sig(head, chain, dtypes, dicts,
                                    ["sorted_agg", _op_sig(op)])
        (kern, rows_step, reduce_step, rest_step, key_specs, cards, udas,
         in_types, val_dicts) = self._sorted_agg_kernel(
            op, sig, dtypes, dicts, chain, time_col, visible)
        t_lo, t_hi = _time_bounds(head)
        compile_s0 = self.stats["compile_s"]
        from pixie_tpu.udf.udf import QuantilesUDA, QuantileUDA

        sketches = [name for name, uda, _vi, _dt in udas
                    if isinstance(uda, (QuantileUDA, QuantilesUDA))]
        fin = tuple(name for name, uda, _vi, _dt in udas
                    if finalize and uda.device_finalize)
        with self._timed(
            self._chain_label(head, chain, "sorted_agg"),
            ([head.id] if head.id >= 0 else []) + [o.id for o in chain]
            + [op.id],
        ) as rec, self._device_ctx(src):
            self._note_chain(src, rec, kern)
            remaining = kern.init_limits()
            parts = []
            for cols, n_valid in self._feed(
                    src, names, cap, backend=self._backend_for(src)):
                mask, keys, values, consumed = rows_step(
                    cols, np.int64(n_valid), t_lo, t_hi, remaining, kern.luts)
                if kern.has_limit:
                    remaining = remaining - consumed
                parts.append((mask, keys, values))
            groups = 0
            state, key_cols = {}, []
            if parts:
                with self._timed("sort_reduce", [op.id]):
                    # a table of several feeds sorts them as one input
                    mask, sort_keys, values = (
                        parts[0] if len(parts) == 1 else jax.tree.map(
                            lambda *xs: jnp.concatenate(xs), *parts))
                    n_groups, end_key, found, after = reduce_step(
                        mask, *_gb.sort_order(sort_keys), values)
                    _ends, front = _gb.sort_order((end_key,))
                    groups = int(n_groups)  # the one sync before the pull
                with self._timed("compact_readback", [op.id]):
                    gb = next_pow2(max(groups, 1))
                    found = _take_front(found, front, gb)
                    if rest_step is not None:
                        found["state"].update(rest_step(*after, gb, fin))
                    if sketches:
                        # the form the sketch's update takes over the runs,
                        # which is what `rest_step` dispatched on, as the
                        # dense chains' spans name theirs
                        rec["span"]["sketch_form"] = _sketch.sketch_form(
                            mask.shape[0], gb)
                    pulled = transfer.pull(found)
                    state, key_cols = pulled["state"], pulled["keys"]
            else:  # no rows at all: identity states, no group
                fin = ()
                state = transfer.pull({name: uda.init(1, in_dt)
                                       for name, uda, _vi, in_dt in udas})
            rec["rows_out"] = groups
            rec["span"].update(
                agg_form="sorted", groups_out=groups,
                d2h_bytes=4 + sum(
                    x.nbytes for x in jax.tree.leaves((state, key_cols))))
            if sketches:  # the sketch state read back raw (0 if finalized)
                rec["span"].update(
                    sketch_state_bytes=sum(
                        x.nbytes for name in sketches if name not in fin
                        for x in jax.tree.leaves(state[name])),
                    sketch_device_finalized=len(fin))
            for name in fin:
                state[name] = _FinalizedCol(state[name])
        self._observe_route(src, rec, compile_s0)
        if not parts:
            codes = [np.empty(0, np.int64)] * len(key_specs)
        else:
            cols = iter(k[:groups] for k in key_cols)
            split = iter(_gb.split_codes(next(cols), [c for c in cards if c])
                         if any(cards) else ())
            codes = [next(split) if c else next(cols) for c in cards]
        group_cols, out_dtypes, out_dicts = {}, {}, {}
        for code, (g, dt, d) in zip(codes, key_specs):
            out_dtypes[g] = dt
            if d is not None:
                out_dicts[g] = d
            group_cols[g] = code.astype(
                np.int32 if d is not None else STORAGE_DTYPE[dt], copy=False)
        return (group_cols, out_dtypes, out_dicts,
                [(name, uda, vi) for name, uda, vi, _dt in udas], in_types,
                state, groups, val_dicts)

    def _run_agg_sorted(self, op: AggOp) -> HostBatch:
        (group_cols, in_dtypes, in_dicts, udas, in_types, state_np, G,
         val_dicts) = self._sorted_group_reduce(op, finalize=True)
        dtypes: dict[str, DT] = {}
        dicts: dict[str, Dictionary] = {}
        cols: dict[str, np.ndarray] = {}
        for g in op.groups:
            dtypes[g] = in_dtypes[g]
            cols[g] = group_cols[g]
            if g in in_dicts:
                dicts[g] = in_dicts[g]
        for out_name, uda, _vn in udas:
            st = state_np[out_name]
            if isinstance(st, _FinalizedCol):
                full = uda.finalize_from_device(st.col)
            elif getattr(uda, "needs_dict", False):
                # model-fit UDA: finalize over the input DICTIONARY (unique
                # values + multiplicities), emitting fresh strings
                full = uda.finalize_dict(st, val_dicts[out_name])
            else:
                full = uda.finalize_host(st)
            vals = np.asarray(full)[:G]
            out_dt = uda.out_type(in_types[out_name]) if not uda.nullary else uda.out_type(None)
            if out_name in val_dicts and not getattr(uda, "needs_dict", False):
                cols[out_name] = _decode_picker_codes(vals, val_dicts[out_name])
                dicts[out_name] = val_dicts[out_name]
                dtypes[out_name] = out_dt
                continue
            if out_dt == DT.STRING:
                d = Dictionary()
                cols[out_name] = d.encode(vals)
                dicts[out_name] = d
            else:
                cols[out_name] = vals.astype(STORAGE_DTYPE[out_dt], copy=False)
            dtypes[out_name] = out_dt
        return HostBatch(dtypes, dicts, cols)

    def _sorted_partial_batch(self, op: AggOp):
        """Distributed partial for the sorted path: group key VALUES + dense
        state sliced to the seen groups (same wire shape as
        _partial_agg_batch, whose sole producer's rule it follows: the
        sketches' bins in the place of their state)."""
        from pixie_tpu.parallel.partial import PartialAggBatch

        (group_cols, in_dtypes, in_dicts, udas, in_types, state_np, G,
         val_dicts) = self._sorted_group_reduce(
            op, finalize=self._finalizes_on_producer(op))
        if val_dicts:
            raise Internal(
                "dict-valued aggregates must ship rows, not partial state "
                "(the distributed planner cuts them as rows channels)"
            )
        key_cols, key_dtypes = {}, {}
        with self._timed("key_decode", [op.id]) as rec:
            # ship VALUES: each agent has a private code space
            rec["rows_out"] = G
            for g in op.groups:
                key_dtypes[g] = in_dtypes[g]
                if g in in_dicts:
                    key_cols[g] = in_dicts[g].decode_array(group_cols[g])
                else:
                    key_cols[g] = group_cols[g]
        states, finalized = {}, set()
        for out_name, _uda, _vn in udas:
            st = state_np[out_name]
            if isinstance(st, _FinalizedCol):
                finalized.add(out_name)
                st = st.col
            states[out_name] = jax.tree.map(lambda x: np.asarray(x)[:G], st)
        return PartialAggBatch(
            key_cols=key_cols, key_dtypes=key_dtypes, states=states,
            in_types=dict(in_types), finalized=frozenset(finalized),
        )

    def _agg_setup(self, op: AggOp):
        """Everything `_agg_state` needs BEFORE the feed loop runs: chain
        walk, pruning, cache signatures, the fetched-or-built kernel bundle,
        and per-run window-origin refresh.  Factored out so the multi-query
        gang (`_multi_partial_agg`) can prepare N member aggregates against
        one shared scan and fuse their per-wave steps into a single jitted
        program.  Raises GroupKeyFallback exactly like `_agg_state`."""
        head, chain = self._upstream_chain(self.plan.parents(op)[0])
        dtypes, dicts, src, names, visible, time_col, cap = self._input_of(head)
        needed = set(op.groups) | {ae.arg for ae in op.values
                                   if ae.arg is not None}
        dtypes, dicts, names, visible, chain = _prune_to_needed(
            head, chain, dtypes, dicts, names, visible, time_col, needed,
        )
        if self.mesh is None:  # the mesh serves the chain: nothing to route
            self._name_route(src, head, chain, op)  # _agg_state observes it

        # Agg kernels bake data-dependent key sets (intdevice uniques, window
        # origins) unless every group key is dictionary-backed; cover that with
        # the table's rows_written in the signature.
        sig = None
        fb_sig = None
        if isinstance(head, MemorySourceOp):
            # The fallback DECISION memo is data-independent (no rows_written/
            # times): once keys prove non-dense, falling back stays correct as
            # the table grows — and streaming polls must hit this memo FIRST,
            # before any keyset work, so doomed aggs skip the union scan.
            fb_sig = self._chain_cache_sig(
                head, chain, dtypes, dicts, ["agg_fallback", _op_sig(op)]
            )
            if _cache_get(fb_sig) == "group_key_fallback":
                raise GroupKeyFallback(f"agg {op.id}: cached fallback decision")
            extra = ["agg", _op_sig(op), ("mesh", self.mesh.size if self.mesh else 0)]
            table = self.store.table(head.table)
            windowish = _windowish_groups(chain, table.time_col)
            # Only intdevice keys bake data (their unique-value sets); window
            # origins are runtime parameters (_refresh_window_keys), so
            # windowed/dict-keyed aggs reuse one kernel across polls/ranges.
            # Direct-source int keys sign by VALUE-SET CONTENT (incremental
            # union, O(new rows)): a streaming poll without new key values
            # reuses the kernel instead of rebuilding per rows_written.
            # Tabletized tables (TabletsGroup) have no uid/row-id surface
            # for the union cache — they take the rows_written signature.
            from pixie_tpu.table.table import Table as _Table

            data_dependent = False
            plain_table = type(table) is _Table and head.tablet is None
            for g in op.groups:
                if g in dicts or g in windowish:
                    continue
                src_col = _group_source_column(chain, g)
                u = None
                if plain_table and src_col is not None \
                        and src_col in table.relation and src_col not in dicts:
                    u = _int_key_uniques(table, src_col, src)
                if u is not None:
                    extra.append(("keyset", g, len(u), hash(u.tobytes())))
                else:
                    data_dependent = True
            if data_dependent:
                extra.append(table.stats()["rows_written"])
            sig = self._chain_cache_sig(
                head, chain, dtypes, dicts, extra, include_times=data_dependent
            )
        else:
            # Blocking-op-headed agg (e.g. the post-join re-aggregation):
            # content-keyed caching.  Non-dict group keys bake their unique
            # value sets into the kernel, so their column content joins the
            # signature (small host batches only — hashing is O(rows)).
            extra = ["agg", _op_sig(op),
                     ("mesh", self.mesh.size if self.mesh else 0)]
            cacheable = True
            non_dict = [g for g in op.groups if g not in dicts]
            if non_dict:
                # Computed keys derive from source columns through the chain;
                # hashing the REQUIRED source columns pins the baked value
                # sets regardless of where in the chain the key is built.
                if (isinstance(src, HostBatch)
                        and src.num_rows <= SMALL_HOST_INPUT_ROWS):
                    _unused, req = _chain_required_columns(chain, set(non_dict))
                    for c in sorted(req):
                        if c in src.cols:
                            extra.append(
                                ("keyhash", c, hash(src.cols[c].tobytes())))
                        else:
                            cacheable = False
                            break
                else:
                    cacheable = False
            if cacheable:
                sig = self._chain_cache_sig(head, chain, dtypes, dicts, extra)
                fb_sig = self._chain_cache_sig(
                    head, chain, dtypes, dicts,
                    ["agg_fallback", _op_sig(op)])
        if _cache_get(fb_sig) == "group_key_fallback":
            raise GroupKeyFallback(f"agg {op.id}: cached fallback decision")
        for _attempt in range(2):
            built = self._agg_kernel(op, sig, fb_sig, dtypes, dicts, chain,
                                     time_col, visible, src, head)
            (kern, keys, udas, in_types, init_specs, num_groups, seen_name,
             step, partial_step, merge_fn, spmd_step, val_dicts) = built
            if _group_space_is_sparse(num_groups, src):
                # decided for this input, so not memoized under fb_sig
                raise GroupKeyFallback(
                    f"agg {op.id}: {num_groups} dense slots for "
                    f"{_src_rows(src)} rows")
            ok, keys, lut_over = self._refresh_window_keys(keys, src, head)
            if ok:
                break
            # A cached kernel's window-bin bucket is too small for this run's
            # time span: drop it and rebuild with the larger card.
            with _CACHE_LOCK:
                _KERNEL_CACHE.pop(sig, None)
        else:
            # Both attempts failed: concurrent ingest grew the time span
            # between the rebuild's range read and the refresh.  Running with
            # a stale bucket would silently alias windows — fail loudly.
            raise Internal(
                "window-bin bucket overflowed twice (concurrent ingest "
                "outpacing kernel rebuild); retry the query"
            )
        return _AggSetup(
            op=op, head=head, chain=chain, sig=sig, dtypes=dtypes,
            dicts=dicts, src=src, names=names, visible=visible,
            time_col=time_col, cap=cap, kern=kern, keys=keys, udas=udas,
            in_types=in_types, init_specs=init_specs, num_groups=num_groups,
            seen_name=seen_name, step=step, partial_step=partial_step,
            merge_fn=merge_fn, spmd_step=spmd_step, val_dicts=val_dicts,
            lut_over=lut_over)

    def _agg_state(self, op: AggOp):
        """Run the aggregation and pull the raw state (shared by the local
        finalize path and the distributed partial path)."""
        s = self._agg_setup(op)
        # one named binding per line: the body below was written against
        # these locals, and per-line assignment can't transpose fields the
        # way a parallel-tuple unpack could
        head = s.head
        chain = s.chain
        sig = s.sig
        src = s.src
        names = s.names
        cap = s.cap
        kern = s.kern
        keys = s.keys
        udas = s.udas
        in_types = s.in_types
        init_specs = s.init_specs
        num_groups = s.num_groups
        seen_name = s.seen_name
        step = s.step
        partial_step = s.partial_step
        merge_fn = s.merge_fn
        spmd_step = s.spmd_step
        val_dicts = s.val_dicts
        lut_over = s.lut_over
        dtypes = s.dtypes
        dicts = s.dicts
        time_col = s.time_col
        # Small host-batch inputs dispatch on the CPU backend (compile is the
        # dominant cost at this scale); the SPMD path stays on the mesh.
        dev_ctx = (self._device_ctx(src)
                   if spmd_step is None else _contextlib.nullcontext())
        compile_s0 = self.stats["compile_s"]
        with dev_ctx:
            t_lo, t_hi = _time_bounds(head)
            luts = {**kern.luts, **lut_over} if lut_over else kern.luts
            with self._timed(
                self._chain_label(head, chain, "partial_agg"),
                ([head.id] if head.id >= 0 else []) + [o.id for o in chain],
            ) as rec:
                self._feed_rec = rec if self.analyze else None
                from pixie_tpu.engine import np_partial

                if (self._backend_for(src) == "cpu" and spmd_step is None
                        and np_partial.eligible(kern, keys, udas, val_dicts,
                                                t_lo, t_hi, src)
                        and np_partial.value_args_ok(kern, op, names)):
                    # CPU streaming/poll fast path: bincount-shaped numpy +
                    # native histogram scatter at memory speed, identical
                    # state layout (see np_partial module docstring)
                    state_np = np_partial.run(
                        self, src, names, cap, kern, keys, init_specs,
                        num_groups, t_lo, t_hi, luts,
                        np_partial.value_args(kern, op))
                    self.stats["np_fast_polls"] = self.stats.get(
                        "np_fast_polls", 0) + 1
                    self._note_engine("np_partial", rec, src,
                                      num_groups=num_groups)
                elif (prog := self._wholeplan_program(
                        sig, kern, chain, op, keys, init_specs, dtypes,
                        dicts, names, time_col, src, val_dicts,
                        spmd_step)) is not None \
                        and _codegen.applicable(prog, t_lo, t_hi):
                    # Whole-plan native loop (Flare): the ENTIRE fused
                    # scan->filter->map->partial-agg chain runs as one
                    # compiled pass straight off the storage batches —
                    # no feed coalescing, no masks, no per-op kernels
                    # (native/wholeplan.cc via native/codegen.py)
                    state_np = _codegen.run(self, prog, src, num_groups,
                                            init_specs, t_lo, t_hi, luts)
                    self.stats["wholeplan_native"] = self.stats.get(
                        "wholeplan_native", 0) + 1
                    self._note_engine("wholeplan", rec, src,
                                      num_groups=num_groups)
                else:
                    if spmd_step is not None:
                        # no router took this decision (_agg_setup gave the
                        # chain no key): the span says what ran
                        self._note_engine("device_chain", rec, src, kern,
                                          num_groups)
                        rec["span"].update(arm="device",
                                           mesh_devices=self.mesh.size)
                    else:
                        self._note_chain(src, rec, kern, num_groups)
                    state_np = self._agg_feed_loop(
                        kern, step, partial_step, merge_fn, spmd_step,
                        init_specs, num_groups,
                        src, names, cap, t_lo, t_hi, luts, fuse_key=sig,
                    )
                self._feed_rec = None
        self._observe_route(src, rec, compile_s0)
        return keys, udas, state_np, seen_name, in_types, val_dicts

    def _wholeplan_program(self, sig, kern, chain, op, keys, init_specs,
                           dtypes, dicts, names, time_col, src, val_dicts,
                           spmd_step):
        """Fetch-or-lower the native whole-plan micro-program for this agg
        chain (engine.plancache.native_programs, keyed by the same chain
        signature that pins the kernel bundle).  None = out of scope —
        the interpreted kernel path runs instead."""
        if (self._backend_for(src) != "cpu" or spmd_step is not None
                or val_dicts or not hasattr(src, "__iter__")):
            return None
        # the flag is re-read HERE, outside the program cache: a cached
        # program must not outlive an operator flipping the kill switch,
        # and flag-off-at-first-query must not poison the sig with None
        if not _flags.get("PX_WHOLEPLAN_NATIVE"):
            return None
        from pixie_tpu.engine.plancache import native_programs

        # window-bin buckets can GROW under an unchanged chain sig (the
        # rebuild loop above); the baked cards join the key so a stale
        # program can never alias windows
        psig = None if sig is None else (sig, tuple(k.card for k in keys))
        return native_programs.get_or_lower(
            psig,
            lambda: _codegen.lower(kern, chain, op, keys, init_specs,
                                   dtypes, dicts, names, time_col))

    def _refresh_window_keys(self, keys, src, head):
        """Per-run window-origin resolution.

        Returns (ok, keys', lut_overrides).  keys' holds fresh GroupKey copies
        with this run's t0_bin, and lut_overrides carries the runtime origin
        scalars — per-run values never mutate the cached kernel, so concurrent
        queries over different time ranges can share it.  ok=False means the
        kernel's static bin bucket can't hold this run's span (rebuild)."""
        if not any(k.kind == "window" for k in keys):
            return True, keys, {}
        t_min, t_max = _source_time_range(src, head)
        out, over = [], {}
        for k in keys:
            if k.kind != "window":
                out.append(k)
                continue
            t0 = int(t_min // k.width)
            nbins = int(t_max // k.width) - t0 + 1
            if nbins > k.card:
                return False, keys, {}
            out.append(dataclasses.replace(k, t0_bin=t0))
            over[k.lut_name] = np.asarray([t0], dtype=np.int64)
        return True, out, over

    def _agg_kernel(self, op, sig, fb_sig, dtypes, dicts, chain, time_col,
                    visible, src, head):
        """Fetch-or-build the compiled agg kernel bundle for `op`."""
        cached = _cache_get(sig)
        if cached is not None:
            return cached
        kern = ChainKernel(dtypes, dicts, chain, self.registry, time_col, visible)
        try:
            keys = self._plan_group_keys(op, kern, src, head)
        except GroupKeyFallback:
            _cache_put(fb_sig, "group_key_fallback")
            raise
        num_groups = 1
        for k in keys:
            num_groups *= k.card

        # UDA instances + value builders (+ implicit row counter for
        # seen-groups).
        udas = []
        init_specs = []
        seen_name = "__seen"
        val_dicts: dict[str, Dictionary] = {}
        from pixie_tpu.udf.udf import CountUDA

        in_types: dict[str, DT | None] = {}
        for ae in [*op.values]:
            uda = self.registry.uda(ae.fn)
            vb = None
            in_dtype = None
            in_types[ae.out_name] = None
            if ae.arg is not None:
                sv = kern.ctx.sym.get(ae.arg)
                if sv is None:
                    raise CompilerError(f"agg input column {ae.arg!r} not found")
                if sv.dictionary is not None:
                    if not uda.dict_ok:
                        raise Unimplemented(
                            f"aggregate {ae.fn} over string column {ae.arg!r}"
                        )
                    # Dict-valued picker: aggregate over CODES (null code -1
                    # masked to the min-identity so it never wins); the
                    # finalize step decodes back through the dictionary.
                    b = sv.build

                    def vb(env, b=b):
                        v = b(env)
                        return jnp.where(v >= 0, v, jnp.int32(PICKER_NULL_SENTINEL))

                    in_dtype = np.int32
                    in_types[ae.out_name] = sv.dtype
                    val_dicts[ae.out_name] = sv.dictionary
                else:
                    if getattr(uda, "needs_dict", False):
                        raise Unimplemented(
                            f"aggregate {ae.fn} requires a string "
                            f"(dictionary-encoded) input column, got "
                            f"{ae.arg!r}"
                        )
                    vb = sv.build
                    in_dtype = STORAGE_DTYPE[sv.dtype]
                    in_types[ae.out_name] = sv.dtype
            elif not uda.nullary:
                raise CompilerError(f"aggregate {ae.fn} requires an input column")
            udas.append((ae.out_name, uda, vb))
            init_specs.append((ae.out_name, uda, in_dtype))
        seen_uda = CountUDA()
        udas.append((seen_name, seen_uda, None))
        init_specs.append((seen_name, seen_uda, None))

        step = kern.make_agg_step(keys, udas, num_groups)
        partial_step = kern.make_partial_agg_step(keys, udas, num_groups, init_specs)
        merge_fn = kern.make_merge_states_np(udas)
        spmd_step = None
        if self.mesh is not None:
            from pixie_tpu.parallel.spmd import reduce_tree_for, spmd_partial_step

            reduce_tree = reduce_tree_for(udas)
            specs = list(init_specs)

            def init_fn(specs=specs, g=num_groups):
                return {name: uda.init(g, in_dt) for name, uda, in_dt in specs}

            spmd_step = spmd_partial_step(
                kern.raw_agg_step, init_fn, reduce_tree,
                len(kern.limit_ns), self.mesh,
            )
        bundle = (kern, keys, udas, in_types, init_specs, num_groups,
                  seen_name, step, partial_step, merge_fn, spmd_step, val_dicts)
        _cache_put(sig, bundle)
        return bundle

    def _agg_feed_loop(self, kern, step, partial_step, merge_fn, spmd_step,
                       init_specs, num_groups, src, names, cap, t_lo, t_hi,
                       luts, fuse_key=None):
        """Drive the feeds through the agg step and pull the final state.

        State init is LAZY: creating identity state eagerly would dispatch
        one device op per UDA leaf before any feed runs, each at the fixed
        per-execution cost.  The partial path inits
        inside its trace; only the budget-threaded limit path (and the
        no-feed fallback) materializes identities here.
        """
        state = None
        span = (self._stat_stack[-1].setdefault("span", {})
                if self._stat_stack else {})  # the chain's, as in _feed
        from pixie_tpu.udf.udf import QuantilesUDA, QuantileUDA

        forms: dict = {}  # form -> its largest feed
        sketch_forms: dict = {}  # the same for the quantile sketches
        sketches = [name for name, uda, _dt in init_specs
                    if isinstance(uda, (QuantileUDA, QuantilesUDA))]
        sketched = bool(sketches)

        def read_back(out: dict) -> dict:
            # `out` was pulled from the device: the chain's span counts the
            # bytes of quantile-sketch state in it as `sketch_state_bytes`
            # (0 where the sketches were finalized on the device), and the
            # sketch columns finalized there as `sketch_device_finalized`
            if sketched:
                done = [name for name in sketches
                        if isinstance(out.get(name), _FinalizedCol)]
                span["sketch_state_bytes"] = span.get(
                    "sketch_state_bytes", 0) + sum(
                    np.asarray(leaf).nbytes for name in sketches
                    if name not in done
                    for leaf in jax.tree.leaves(out.get(name)))
                span["sketch_device_finalized"] = span.get(
                    "sketch_device_finalized", 0) + len(done)
            return out

        def join(seen: dict, form: str, rows: int) -> str:
            seen[form] = max(seen.get(form, 0), rows)
            return "+".join(sorted(seen, key=lambda f: -seen[f]))

        def note_form(rows: int) -> None:
            # A feed of `rows` rows (one shard's, under a mesh) is about to
            # be dispatched: its sums and counts take the form
            # ops/groupby.agg_form says, and its quantile sketches the one
            # ops/sketch.sketch_form says, which is what the kernels
            # dispatch on (call this inside the context the step is traced
            # in).  The chain's span names the forms of its feeds as
            # `agg_form` (and `sketch_form`, where the chain has a sketch),
            # largest feed first, joined by `+`: a 1,024-row hot remainder
            # scatters beside a bucket that takes a GEMM.
            span["agg_form"] = join(forms, _gb.agg_form(rows, num_groups), rows)
            if sketched:
                span["sketch_form"] = join(
                    sketch_forms, _sketch.sketch_form(rows, num_groups), rows)

        if kern.has_limit:
            # Limit queries must thread the budgets, so the feed steps chain;
            # the budgets stay a device vector (no per-feed host sync).
            state = {name: uda.init(num_groups, in_dt)
                     for name, uda, in_dt in init_specs}
            remaining = kern.init_limits()
            for cols, n_valid in self._feed(
                    src, names, cap, backend=self._backend_for(src)):
                note_form(_first_len(cols))
                state, cnt, consumed = step(
                    cols, np.int64(n_valid), t_lo, t_hi, remaining, luts, state
                )
                remaining = remaining - consumed
                if self.analyze:
                    jax.block_until_ready(state)
        else:
            # No limit → per-feed partials are INDEPENDENT executions (init
            # inside the trace).  Dependent executions serialize; this
            # keeps the device pipeline flat: N parallel
            # steps + ONE overlapped readback wave + a HOST merge (a device
            # merge would be one more fixed-cost execution).  With a mesh,
            # each feed shards row-wise over ALL devices and merges
            # per-device state in-program via psum/pmin/pmax (the reference's
            # PEM-partial → Kelvin-finalize, but over ICI).
            partials = []
            n_dev = self.mesh.size if self.mesh is not None else 1
            shard_rows = np.zeros(n_dev, np.int64)  # this chain's, a shard
            backend = ("device" if spmd_step is not None
                       else self._backend_for(src))
            # Accelerator-backend feeds normally end in a DEVICE merge (+
            # device finalize) with one small readback — raw states stay
            # unpacked for it.  Packing only pays on the paths that still
            # pull per-feed states (defer / mixed CPU partials).  The SPMD
            # path qualifies too: its per-feed states are already in-mesh
            # merged (replicated), and the merge+finalize jit runs under
            # GSPMD like any other consumer.
            # A partial that finalizes its sketches (_partial_agg_batch)
            # takes the same merge + finalize execution on the CPU arm: the
            # router's two arms then both ship finalized sketches.
            device_merge_ok = ((backend == "device"
                                or getattr(self, "_sole_finalize", False))
                               and not getattr(self, "_defer_active", False))
            # Single-feed fusion: when the snapshot metadata predicts exactly
            # one feed (the interactive warm-query shape — 1M rows coalesce
            # into one feed), the first feed is held back undispatched and
            # partial+finalize run as ONE fused execution below instead of
            # two chained ones.  Multi-feed queries never hold: the device
            # would idle through the next feed's host-side assembly, undoing
            # the compute/transfer overlap.  (The dispatch-on-second-arrival
            # fallback in the loop stays as a safety net.)
            fuse_ok = (fuse_key is not None and not self.analyze
                       and spmd_step is None and device_merge_ok
                       and not getattr(self, "_partial_wire", False)
                       and self._predicted_single_feed(src, cap))
            held = None

            def dispatch_plain(cols, n_valid):
                # A small NUMPY feed (typically the hot remainder of a
                # big table) dispatches on CPU even in a TPU-routed
                # query: it would otherwise cost one more fixed-price
                # TPU execution; the host merge unifies the partials.
                bucket = _first_len(cols)
                first = next(iter(cols.values()))
                small_np = (isinstance(first, np.ndarray)
                            and bucket <= int(
                                _flags.get("PX_CPU_CROSSOVER_ROWS")))
                if small_np and device_merge_ok:
                    # A device-merged query keeps its small feeds (the
                    # hot remainder) ON the accelerator: executions are
                    # cheap async dispatches, while a CPU partial here
                    # would force the mixed pull path — megabytes of
                    # sketch state read back instead of one device
                    # merge + a kilobyte readback.
                    small_np = False
                if small_np:
                    self._note_engine("xla_cpu_chain")
                ctx = (jax.default_device(_cpu_device()) if small_np
                       else _contextlib.nullcontext())
                with ctx:
                    note_form(bucket)
                    p = partial_step(cols, np.int64(n_valid), t_lo,
                                     t_hi, luts)
                    if not small_np and backend == "device" \
                            and not device_merge_ok \
                            and not getattr(self, "_defer_active",
                                            False):
                        # pack the multi-leaf state into one buffer per
                        # dtype (an extra async dispatch): each pulled
                        # leaf is its own device→host transfer
                        # (deferred partials stay raw — the gang merge
                        # reduces leaf-wise)
                        pk = _state_packer(p)
                        if pk is not None:
                            packer, unpack = pk
                            p = _PackedState(packer(p), unpack)
                partials.append(p)

            for cols, n_valid in self._feed(src, names, cap,
                                            spmd=spmd_step is not None,
                                            backend=backend):
                if fuse_ok and held is None and not partials:
                    held = (cols, n_valid)
                    continue
                if held is not None:
                    dispatch_plain(*held)
                    held = None
                bucket = _first_len(cols)
                if spmd_step is not None and bucket % n_dev == 0:
                    from pixie_tpu.parallel.spmd import per_shard_valid

                    nv = per_shard_valid(n_valid, bucket, n_dev)
                    note_form(bucket // n_dev)
                    partials.append(spmd_step(cols, nv, t_lo, t_hi, luts))
                    self.stats["spmd_feeds"] = self.stats.get("spmd_feeds", 0) + 1
                    self._note_shard_rows(nv)
                    # the same two on the chain's span: the SPMD executions
                    # it dispatched, and max over mean of the valid rows a
                    # shard was handed over all of them (1.0 is even)
                    shard_rows += nv
                    span["spmd_feeds"] = span.get("spmd_feeds", 0) + 1
                    span["shard_skew"] = round(_shard_skew(shard_rows), 4)
                else:
                    dispatch_plain(cols, n_valid)
                if self.analyze:
                    tf0 = _time.perf_counter_ns()
                    jax.block_until_ready(
                        partials[-1].buf
                        if isinstance(partials[-1], _PackedState)
                        else partials[-1])
                    rec = getattr(self, "_feed_rec", None)
                    if rec is not None:
                        rec.setdefault("feed_ns", []).append(
                            _time.perf_counter_ns() - tf0)
            if held is not None:
                # exactly ONE feed: the fused execution computes partial
                # state AND finalizes on device in a single dispatch — one
                # execution + one small readback wave is the whole query
                fn = _fused_partial_finalize(
                    fuse_key,
                    {name: uda for name, uda, _dt in init_specs},
                    partial_step)
                note_form(_first_len(held[0]))
                finals, rest = fn(held[0], np.int64(held[1]), t_lo, t_hi,
                                  luts)
                finals_np, rest_np = transfer.pull((finals, rest))
                self.stats["fused_single_feed"] = self.stats.get(
                    "fused_single_feed", 0) + 1
                out = dict(rest_np)
                for k, v in finals_np.items():
                    out[k] = _FinalizedCol(v)
                return read_back(out)
            if partials:
                # deferral is scoped to the distributed partial path
                # (_partial_agg_batch) — the local finalize path reads the
                # pulled state dict directly and must never see a
                # _DeferredState
                if getattr(self, "_defer_active", False):
                    # Split CPU-resident partials (small numpy feeds, e.g.
                    # the hot remainder) from accelerator ones: CPU states
                    # merge on host for free, and must NOT ride into the
                    # device gang merge — that would UPLOAD each one back to
                    # the accelerator.
                    dev, host = [], []
                    for p in partials:
                        (host if _state_on_cpu(p) else dev).append(p)
                    host_state = (merge_fn(*transfer.pull(host))
                                  if host else None)
                    if not dev:
                        return host_state
                    return _DeferredState(dev, merge_fn, host_state)
                # under a mesh each feed's state is already merged across
                # the shards in its program (replicated): what is left, the
                # merge of the feeds' states and its read-back, has a span
                # of its own, so "more than one feed" has a cost by name
                with (self._timed("mesh_merge", []) if spmd_step is not None
                      else _contextlib.nullcontext()):
                    if device_merge_ok:
                        # ONE device execution merges every per-feed partial
                        # and finalizes large-state UDAs (sketch → quantiles)
                        # in place, so the readback wave carries kilobytes of
                        # answers instead of megabytes of state (reference
                        # bar: zero-copy batch handoff,
                        # exec_graph.cc:177-260).
                        udas_by_name = {name: uda
                                        for name, uda, _dt in init_specs}
                        rt = {name: uda.reduce_ops()
                              for name, uda, _dt in init_specs}
                        # the distributed partial path ships RAW state where
                        # it must stay mergeable across agents: device-merge
                        # the feeds but never finalize
                        fin_ok = not getattr(self, "_partial_wire", False)
                        spec_key = ("mfz", fin_ok, tuple(
                            (name, type(uda).__qualname__,
                             getattr(uda, "q", None))
                            for name, uda, _dt in init_specs))
                        finals, rest = _merge_finalize_fn(
                            spec_key, rt, udas_by_name,
                            finalize_ok=fin_ok)(*partials)
                        finals_np, rest_np = transfer.pull((finals, rest))
                        out = dict(rest_np)
                        for k, v in finals_np.items():
                            out[k] = _FinalizedCol(v)
                        return read_back(out)
                    pulled = transfer.pull(
                        [p.buf if isinstance(p, _PackedState) else p
                         for p in partials])
                    states = [
                        read_back(p.unpack(buf) if isinstance(p, _PackedState)
                                  else buf)
                        for p, buf in zip(partials, pulled)
                    ]
                    return merge_fn(*states)

        if state is None:  # no feeds at all: identity state
            state = {name: uda.init(num_groups, in_dt)
                     for name, uda, in_dt in init_specs}
        return read_back(transfer.pull(state))

    def _decode_key_column(self, k: GroupKey, codes: np.ndarray):
        """Seen-group codes → (np column, dictionary|None) for key k."""
        if k.kind == "dict":
            return codes.astype(np.int32), k.dictionary
        if k.kind == "intdevice":
            vals = k.dictionary.decode(codes)
            return np.asarray(vals, dtype=STORAGE_DTYPE[k.out_dtype]), None
        return ((codes.astype(np.int64) + k.t0_bin) * k.width).astype(np.int64), None

    def _partial_agg_batch(self, op: AggOp):
        """Distributed partial path: seen groups as VALUES + raw UDA state
        (see pixie_tpu.parallel.partial.PartialAggBatch).  The sole
        producer of its channel (`op.sole_producer`) finalizes its
        device-finalizable aggregates (the quantile sketches) where its
        chain runs and ships their bins instead: no other producer's state
        is added to them.  An aggregate with none ships raw state as any
        other partial does."""
        self._defer_active = self.defer_agg_pull
        self._sole_finalize = self._finalizes_on_producer(op)
        self._partial_wire = not self._sole_finalize
        try:
            keys, udas, state_np, seen_name, in_types, val_dicts = self._agg_state(op)
        except GroupKeyFallback:
            return self._sorted_partial_batch(op)
        finally:
            self._defer_active = False
            self._partial_wire = False
            self._sole_finalize = False
        if val_dicts:
            raise Internal(
                "dict-valued aggregates must ship rows, not partial state "
                "(the distributed planner cuts them as rows channels)"
            )
        if isinstance(state_np, _DeferredState):
            deferred = state_np

            def finish_state(merged, self=self, keys=keys, udas=udas,
                             seen_name=seen_name, in_types=in_types):
                return self._finish_partial_batch(
                    keys, udas, merged, seen_name, in_types)

            def finish(pulled, finish_state=finish_state, deferred=deferred):
                states = list(pulled)
                if deferred.host_state is not None:
                    states.append(deferred.host_state)
                return finish_state(deferred.merge_fn(*states))

            return _DeferredPartial(
                deferred.partials, finish,
                layout_fp=self._partial_layout_fp(keys, udas, in_types,
                                                  seen_name),
                finish_state=finish_state,
                reduce_tree={name: uda.reduce_ops()
                             for name, uda, _vb in udas},
                host_state=deferred.host_state,
                host_merge=deferred.merge_fn,
            )
        return self._finish_partial_batch(keys, udas, state_np, seen_name,
                                          in_types)

    def _finalizes_on_producer(self, op: AggOp) -> bool:
        """A partial of its channel's sole producer with a device-
        finalizable aggregate (a quantile sketch) finalizes it where its
        chain runs: no other producer's state is added to it."""
        return op.sole_producer and any(
            self.registry.uda(ae.fn).device_finalize for ae in op.values)

    @staticmethod
    def _partial_layout_fp(keys, udas, in_types, seen_name):
        """Fingerprint of the partial state's LAYOUT + key code spaces.  Two
        agents with equal fingerprints produce states indexed identically
        (same composite group-code meaning), so their states may merge on
        device BEFORE decode.  Dictionaries fingerprint by CONTENT — two
        stores ingesting different values hash apart and take the host
        value-keyed merge instead."""
        key_fp = []
        for k in keys:
            d_fp = (_dict_fingerprint(k.dictionary)
                    if k.dictionary is not None else None)
            key_fp.append((k.name, k.kind, k.card, int(k.out_dtype), d_fp,
                           k.width, k.t0_bin))
        uda_fp = tuple((name, type(uda).__name__) for name, uda, _vb in udas)
        return (tuple(key_fp), uda_fp, seen_name,
                tuple(sorted((k, -1 if v is None else int(v))
                             for k, v in in_types.items())))

    def _finish_partial_batch(self, keys, udas, state_np, seen_name, in_types):
        from pixie_tpu.parallel.partial import PartialAggBatch

        seen_counts = np.asarray(state_np[seen_name])
        if keys:
            gids = np.nonzero(seen_counts > 0)[0]
        else:
            gids = np.array([0])
        key_cols: dict = {}
        key_dtypes: dict = {}
        if keys:
            from pixie_tpu.ops.groupby import split_codes

            codes = split_codes(gids, [k.card for k in keys])
            for k, kc in zip(keys, codes):
                key_dtypes[k.name] = k.out_dtype
                col, d = self._decode_key_column(k, kc)
                if d is not None:
                    # ship VALUES — each agent has a private code space
                    key_cols[k.name] = d.decode_array(col)
                else:
                    key_cols[k.name] = col
        states = {}
        finalized = set()
        for out_name, _uda, _vb in udas:
            if out_name == seen_name:
                continue
            st = state_np[out_name]
            if isinstance(st, _FinalizedCol):
                finalized.add(out_name)
                st = st.col
            states[out_name] = jax.tree.map(lambda x: np.asarray(x)[gids], st)
        return PartialAggBatch(
            key_cols=key_cols, key_dtypes=key_dtypes, states=states,
            in_types={k: v for k, v in in_types.items()},
            finalized=frozenset(finalized),
        )

    # ------------------------------------------------- multi-query gang
    def _gang_agg_payloads(self) -> dict:
        """{channel: PartialAggBatch} for agg_state sinks executed as ONE
        fused multi-query gang — ≥2 distinct partial aggs sharing a single
        MemorySourceOp (the fused-batch agent-plan shape).  Empty when
        fusion is off or inapplicable; such sinks run per-sink as before."""
        if not _mq_fusion_enabled() or self.analyze:
            return {}
        groups: dict[int, list] = {}
        for sink in self.plan.sinks():
            if not isinstance(sink, ResultSinkOp) \
                    or sink.payload != "agg_state":
                continue
            parent = self.plan.parents(sink)[0]
            if not (isinstance(parent, AggOp) and parent.partial):
                continue
            try:
                head, _chain = self._upstream_chain(
                    self.plan.parents(parent)[0])
            except Internal:
                continue
            if isinstance(head, MemorySourceOp):
                groups.setdefault(head.id, []).append((sink.channel, parent))
        out: dict = {}
        for g in groups.values():
            # one agg feeding several channels computes ONCE — dedup by op
            # identity before fusing, then fan the payload out per channel
            uniq, seen = [], set()
            for _c, p in g:
                if id(p) not in seen:
                    seen.add(id(p))
                    uniq.append(p)
            if len(uniq) < 2:
                continue
            got = self._multi_partial_agg(uniq)
            if got is None:
                continue
            for cid, parent in g:
                out[cid] = got[parent.id]
        if out and _autotune.enabled():
            # record-only gate: the fusion choice is baked into compiled
            # kernels at trace time, so the model attributes it but never
            # flips it per query (flipping would churn the program cache —
            # tuning it from measured wave RTT on accelerator hardware is
            # the documented ROADMAP remainder)
            self.stats.setdefault("autotune", []).append({
                "gate": _autotune.GATE_MQ_FUSION, "plan_class": "agg",
                "size_bucket": _autotune.size_bucket(len(out)),
                "arm": "fused", "static_arm": "fused", "source": "static",
                "model_ms": None, "static_ms": None, "n": len(out)})
        return out

    def _multi_partial_agg(self, ops: list) -> Optional[dict]:
        """Execute N partial aggregates over ONE shared scan as a fused
        multi-query device program: each feed wave runs a single jitted
        execution computing EVERY member's partial state (the members'
        own partial_steps traced together, states stacked in the output
        tuple), and the whole gang's states read back in one transfer
        wave — wave RTT and H2D amortize across the batch.  Returns
        {op.id: PartialAggBatch}, or None when any member is out of scope
        (callers run the per-sink path; results are bit-identical either
        way because the fused program calls each member's own unchanged
        kernel over the same feed contents)."""
        spmd = self.mesh is not None
        setups = []
        for op in ops:
            try:
                s = self._agg_setup(op)
            except GroupKeyFallback:
                return None  # per-sink path reruns via the sorted fallback
            if (s.sig is None or s.kern.has_limit or s.val_dicts
                    or (spmd and s.spmd_step is None)):
                return None
            if not setups and not spmd \
                    and self._backend_for(s.src) != "device":
                # CPU-routed queries keep the per-member np_partial /
                # wholeplan-native loops (memory-speed paths the fused jit
                # does not beat); the gang amortizes ACCELERATOR wave RTT —
                # decided on the FIRST setup so a bail wastes only one
                return None
            setups.append(s)
        # the EARLIEST member's snapshot feeds the gang: later setups'
        # prescanned key sets cover at least its rows (tables are
        # append-only), so every member's kernel can encode every fed row
        src = setups[0].src
        union_names: list[str] = []
        for s in setups:
            for n in s.names:
                if n not in union_names:
                    union_names.append(n)
        # member sigs already carry the mesh size, so plain and spmd gangs
        # can never collide under one fused-program cache key
        fkey = ("mq",) + tuple(s.sig for s in setups)
        fused = _cache_get(fkey)
        if fused is None:
            steps = tuple(s.partial_step for s in setups)

            def fused_fn(cols, n_valid, t_lo, t_hi, luts, steps=steps):
                return tuple(st(cols, n_valid, t_lo, t_hi, l)
                             for st, l in zip(steps, luts))

            fused_spmd = None
            if spmd:
                from pixie_tpu.parallel.spmd import (
                    reduce_tree_for,
                    spmd_multi_partial_step,
                )

                specs = []
                for s in setups:
                    init = list(s.init_specs)
                    g = s.num_groups
                    specs.append((
                        s.kern.raw_agg_step,
                        lambda init=init, g=g: {
                            name: uda.init(g, in_dt)
                            for name, uda, in_dt in init},
                        reduce_tree_for(s.udas),
                        len(s.kern.limit_ns),
                    ))
                fused_spmd = spmd_multi_partial_step(specs, self.mesh)
            fused = (jax.jit(fused_fn), fused_spmd)
            _cache_put(fkey, fused)
        fused_plain, fused_spmd = fused
        t_lo, t_hi = _time_bounds(setups[0].head)
        luts = tuple(
            ({**s.kern.luts, **s.lut_over} if s.lut_over else s.kern.luts)
            for s in setups)
        n_dev = self.mesh.size if spmd else 1
        per_member: list[list] = [[] for _ in setups]
        with self._timed(f"mq_gang[{len(setups)}]", [op.id for op in ops],
                         spanned=False):
            self._note_engine("device_chain")
            for cols, n_valid in self._feed(src, union_names, setups[0].cap,
                                            spmd=spmd, backend="device"):
                bucket = _first_len(cols)
                if spmd and bucket % n_dev == 0:
                    from pixie_tpu.parallel.spmd import per_shard_valid

                    nv = per_shard_valid(n_valid, bucket, n_dev)
                    states = fused_spmd(cols, nv, t_lo, t_hi, luts)
                    self.stats["spmd_feeds"] = (
                        self.stats.get("spmd_feeds", 0) + 1)
                    self._note_shard_rows(nv)
                else:
                    states = fused_plain(cols, np.int64(n_valid), t_lo,
                                         t_hi, luts)
                for parts, st in zip(per_member, states):
                    parts.append(st)
            self.stats["mq_waves"] = (self.stats.get("mq_waves", 0)
                                      + len(per_member[0]))
            pull = []
            for s, parts in zip(setups, per_member):
                fin = self._finalizes_on_producer(s.op)
                if not parts:  # empty scan: identity state per member
                    pull.append(({}, {name: uda.init(s.num_groups, in_dt)
                                      for name, uda, in_dt in s.init_specs}))
                elif len(parts) == 1 and not fin:
                    pull.append(({}, parts[0]))
                else:
                    # same per-member device merge (+ finalize) the
                    # unbatched partial path runs — shared cache key
                    # included
                    rt = {name: uda.reduce_ops()
                          for name, uda, _dt in s.init_specs}
                    by_name = {name: uda for name, uda, _dt in s.init_specs}
                    spec_key = ("mfz", fin, tuple(
                        (name, type(uda).__qualname__,
                         getattr(uda, "q", None))
                        for name, uda, _dt in s.init_specs))
                    pull.append(_merge_finalize_fn(
                        spec_key, rt, by_name, finalize_ok=fin)(*parts))
            pulled = transfer.pull(pull)
        out = {}
        for s, (finals, state_np) in zip(setups, pulled):
            state_np = {**state_np,
                        **{k: _FinalizedCol(v) for k, v in finals.items()}}
            out[s.op.id] = self._finish_partial_batch(
                s.keys, s.udas, state_np, s.seen_name, s.in_types)
        self.stats["mq_fused"] = self.stats.get("mq_fused", 0) + len(setups)
        return out

    @_compile_scoped
    def run_agent(self) -> dict:
        """Execute an AGENT plan: returns {channel: payload} where payload is a
        HostBatch (rows channels) or PartialAggBatch (agg_state channels)."""
        from pixie_tpu.plan.plan import PartitionSinkOp

        out = {}
        t0 = _time.perf_counter_ns()
        gang = self._gang_agg_payloads()
        for sink in self.plan.sinks():
            if isinstance(sink, PartitionSinkOp):
                # hash-partitioned shuffle edge: one rows channel per bucket.
                # With a multi-device mesh whose size matches n_parts, the
                # exchange is ONE lax.all_to_all over the mesh (the ICI
                # shuffle of SURVEY §2.5; reference splitter.h:114-155);
                # otherwise the host hash/sort/split exchange.  Both assign
                # partitions by identical value hashes, so mixed producers
                # interoperate.
                from pixie_tpu.parallel.repartition import (
                    mesh_partition_exchange,
                    partition_ids,
                    split_host_batch,
                )

                parent = self.plan.parents(sink)[0]
                hb = self._materialize_parent(parent)
                if (self.mesh is not None
                        and self.mesh.size == sink.n_parts
                        and hb.num_rows > 0):
                    buckets = mesh_partition_exchange(
                        hb, sink.keys, sink.n_parts, self.mesh)
                    self.stats["mesh_shuffles"] = (
                        self.stats.get("mesh_shuffles", 0) + 1)
                else:
                    part = partition_ids(hb, sink.keys, sink.n_parts)
                    buckets = split_host_batch(hb, part, sink.n_parts)
                for p, bucket in enumerate(buckets):
                    out[f"{sink.prefix}{p}"] = bucket
                continue
            if not isinstance(sink, ResultSinkOp):
                raise Internal(f"agent plan sink {sink.kind} is not a ResultSink")
            parent = self.plan.parents(sink)[0]
            if sink.payload == "agg_state":
                if not (isinstance(parent, AggOp) and parent.partial):
                    raise Internal("agg_state channel must be fed by a partial AggOp")
                if sink.channel in gang:
                    out[sink.channel] = gang[sink.channel]
                else:
                    out[sink.channel] = self._partial_agg_batch(parent)
            else:
                out[sink.channel] = self._materialize_parent(parent)
        self.stats["wall_ns"] = _time.perf_counter_ns() - t0
        self.stats["operators"] = self.op_stats
        self._emit_op_spans()
        return out

    @_compile_scoped
    def run_agent_stream(self, agg_chunk_groups: int = 0):
        """Execute an AGENT plan as a chunk stream: yields (channel, payload)
        in wave order — one HostBatch per readback wave for rows channels
        (each wave's D2H rode under a later wave's compute, engine.transfer),
        per group-slice for agg_state channels (`agg_chunk_groups` > 0 caps
        the slice), per bucket for partition sinks.  The networked agent
        ships each yield as its own wire frame, so the broker's incremental
        fold starts while this executor is still computing; run_agent is the
        barrier shape of the same walk.

        Chunks of one channel are yielded in order, but consumers must not
        rely on it: the broker-side folds (PartialAggFold / HostBatchUnion)
        are order-insensitive by construction.
        """
        from pixie_tpu.plan.plan import PartitionSinkOp

        t0 = _time.perf_counter_ns()
        gang = self._gang_agg_payloads()
        for sink in self.plan.sinks():
            if isinstance(sink, PartitionSinkOp):
                from pixie_tpu.parallel.repartition import (
                    mesh_partition_exchange,
                    partition_ids,
                    split_host_batch,
                )

                parent = self.plan.parents(sink)[0]
                hb = self._materialize_parent(parent)
                if (self.mesh is not None
                        and self.mesh.size == sink.n_parts
                        and hb.num_rows > 0):
                    buckets = mesh_partition_exchange(
                        hb, sink.keys, sink.n_parts, self.mesh)
                    self.stats["mesh_shuffles"] = (
                        self.stats.get("mesh_shuffles", 0) + 1)
                else:
                    part = partition_ids(hb, sink.keys, sink.n_parts)
                    buckets = split_host_batch(hb, part, sink.n_parts)
                for p, bucket in enumerate(buckets):
                    yield f"{sink.prefix}{p}", bucket
                continue
            if not isinstance(sink, ResultSinkOp):
                raise Internal(f"agent plan sink {sink.kind} is not a ResultSink")
            parent = self.plan.parents(sink)[0]
            if sink.payload == "agg_state":
                if not (isinstance(parent, AggOp) and parent.partial):
                    raise Internal("agg_state channel must be fed by a partial AggOp")
                pb = (gang[sink.channel] if sink.channel in gang
                      else self._partial_agg_batch(parent))
                n = pb.num_groups
                if agg_chunk_groups > 0 and n > agg_chunk_groups:
                    from pixie_tpu.parallel.partial import slice_partial

                    for a in range(0, n, agg_chunk_groups):
                        idx = np.arange(a, min(a + agg_chunk_groups, n))
                        yield sink.channel, slice_partial(pb, idx)
                else:
                    yield sink.channel, pb
            else:
                out_dtypes, out_dicts, out_names, gen = self._consume_chain(parent)
                sent = False
                for cols, _c in gen:
                    sent = True
                    yield sink.channel, HostBatch(
                        dict(out_dtypes), dict(out_dicts),
                        {name: cols[name] for name in out_names})
                if not sent:
                    # the channel contract is ≥1 payload: an empty scan still
                    # ships one zero-row chunk carrying the dtypes/dicts
                    yield sink.channel, HostBatch(
                        dict(out_dtypes), dict(out_dicts),
                        {name: np.empty(0, STORAGE_DTYPE[out_dtypes[name]])
                         for name in out_names})
        self.stats["wall_ns"] = _time.perf_counter_ns() - t0
        self.stats["operators"] = self.op_stats
        self._emit_op_spans()

    def _finalize_agg(self, op, keys, udas, state_np, seen_name, in_types=None,
                      val_dicts=None) -> HostBatch:
        from pixie_tpu.ops.groupby import split_codes

        seen_counts = np.asarray(state_np[seen_name])
        if keys:
            gids = np.nonzero(seen_counts > 0)[0]
        else:
            gids = np.array([0])  # group-by-none always emits one row
        dtypes: dict[str, DT] = {}
        dicts: dict[str, Dictionary] = {}
        cols: dict[str, np.ndarray] = {}
        if keys:
            codes = split_codes(gids, [k.card for k in keys])
            for k, kc in zip(keys, codes):
                dtypes[k.name] = k.out_dtype
                if k.kind == "dict":
                    cols[k.name] = kc.astype(np.int32)
                    dicts[k.name] = k.dictionary
                elif k.kind == "intdevice":
                    vals = k.dictionary.decode(kc)
                    cols[k.name] = np.asarray(vals, dtype=STORAGE_DTYPE[k.out_dtype])
                else:  # window
                    cols[k.name] = ((kc.astype(np.int64) + k.t0_bin) * k.width).astype(
                        np.int64
                    )
        for out_name, uda, _vb in udas:
            if out_name == seen_name:
                continue
            st = state_np[out_name]
            if isinstance(st, _FinalizedCol):
                full = uda.finalize_from_device(st.col)
            elif getattr(uda, "needs_dict", False):
                full = uda.finalize_dict(
                    jax.tree.map(lambda x: x, st), val_dicts[out_name])
            else:
                with sketch_finalize_span(uda, st):
                    full = uda.finalize_host(jax.tree.map(lambda x: x, st))
            vals = np.asarray(full)[gids]
            # Use the DECLARED input DataType so e.g. min(time_) stays TIME64NS
            # (matching the compile-time schema); fall back to array inference
            # for callers that bypass _run_agg.
            if uda.nullary:
                out_dt = uda.out_type(None)
            elif in_types is not None and out_name in in_types:
                out_dt = uda.out_type(in_types[out_name])
            else:
                out_dt = uda.out_type(_dtype_of(full))
            if (val_dicts and out_name in val_dicts
                    and not getattr(uda, "needs_dict", False)):
                # dict-valued picker: the state holds CODES; out-of-range
                # (all-null group sentinel) decodes to null
                cols[out_name] = _decode_picker_codes(vals, val_dicts[out_name])
                dicts[out_name] = val_dicts[out_name]
                dtypes[out_name] = out_dt
                continue
            if out_dt == DT.STRING:
                d = Dictionary()
                cols[out_name] = d.encode(vals)
                dicts[out_name] = d
            else:
                cols[out_name] = vals.astype(STORAGE_DTYPE[out_dt], copy=False)
            dtypes[out_name] = out_dt
        return HostBatch(dtypes, dicts, cols)

    # -------------------------------------------------------------------- udtf
    def _run_udtf(self, op: UDTFSourceOp) -> HostBatch:
        """Materialize a table-generating function (reference
        exec/udtf_source_node.*): one columnar batch from a host fn."""
        from pixie_tpu.types import is_dict_encoded
        from pixie_tpu.udf.udtf import UDTFContext

        u = self.registry.udtf(op.name)
        # The serialized schema (when present) is authoritative for the output
        # relation — a remote plan's view of the UDTF wins over whatever
        # version is registered locally.
        relation = (
            Relation.from_dict(op.schema) if op.schema is not None else u.relation
        )
        ctx = self.udtf_ctx
        if ctx is None:
            from pixie_tpu.metadata import state as _mdstate

            m = _mdstate.global_manager()
            ctx = UDTFContext(
                table_store=self.store, registry=self.registry,
                asid=m.current().asid, node_name=m.current().node_name,
            )
        cols_raw = u.fn(ctx, **(op.args or {}))
        dtypes, dicts, cols = {}, {}, {}
        for c in relation:
            if c.name not in cols_raw:
                raise Internal(
                    f"UDTF {op.name} did not produce declared column {c.name!r}"
                )
            vals = list(cols_raw[c.name])
            dtypes[c.name] = c.data_type
            if is_dict_encoded(c.data_type):
                if c.data_type == DT.UINT128:
                    # tuples would np-broadcast into 2-D object arrays inside
                    # Dictionary.encode; normalize to UInt128 scalars.
                    from pixie_tpu.types import UInt128

                    vals = [
                        UInt128(*v) if isinstance(v, (tuple, list)) else v
                        for v in vals
                    ]
                d = Dictionary()
                cols[c.name] = d.encode(vals)
                dicts[c.name] = d
            else:
                cols[c.name] = np.asarray(vals, dtype=STORAGE_DTYPE[c.data_type])
        return HostBatch(dtypes, dicts, cols)

    # -------------------------------------------------------------------- join
    def _run_join(self, op: JoinOp, rec: dict) -> HostBatch:
        """Equijoin with full many-to-many expansion, inner/left/right/outer.
        `rec`, the join's _timed frame, takes both sides' rows, the rows
        out and the match kernel (`cross`, `host_sort`, `native`,
        `device_radix`) as the attributes of its trace span.

        Reference: exec/equijoin_node.h + planpb JoinOperator
        (plan.proto:301-316).  Redesigned as a sort/searchsorted join over
        factorized composite key codes (no hash table): the left side is
        sorted once, each right row binary-searches its match range, and
        m:n pairs expand with a repeat/offset vector — all O((n+m) log n)
        columnar numpy, the same structure the device path reuses for the
        unique-build fast case.  Null keys (dict code -1 or untranslatable
        values) never match but their rows still surface as unmatched in
        left/right/outer joins (pandas semantics).
        """
        parents = self.plan.parents(op)
        if len(parents) != 2:
            raise Internal("join needs two parents")
        left = self._materialize_parent(parents[0])
        right = self._materialize_parent(parents[1])
        if len(op.left_on) != len(op.right_on):
            raise CompilerError("join requires equal-length key lists")
        if op.how not in ("inner", "left", "right", "outer"):
            raise Unimplemented(f"join how={op.how!r}")
        nl, nr = left.num_rows, right.num_rows

        if not op.left_on:
            # Empty key lists = cross join (the bundled cluster script uses
            # merge(left_on=[], right_on=[]) to attach a 1-row time window).
            # When either side is empty, left/right/outer keep the other
            # side's rows with null fills (every row is unmatched).
            lidx = np.repeat(np.arange(nl, dtype=np.int64), nr)
            ridx = np.tile(np.arange(nr, dtype=np.int64), nl)
            if nr == 0 and op.how in ("left", "outer"):
                lidx = np.arange(nl, dtype=np.int64)
                ridx = np.full(nl, -1, dtype=np.int64)
            elif nl == 0 and op.how in ("right", "outer"):
                ridx = np.arange(nr, dtype=np.int64)
                lidx = np.full(nr, -1, dtype=np.int64)
            rec["span"] = {"rows_left": nl, "rows_right": nr,
                           "kernel": "cross"}
            return self._join_output(op, left, right, lidx, ridx)

        # Factorize each key pair into a shared integer code space; nulls
        # (dict code -1) are tracked separately and excluded from matching.
        lcodes, rcodes = [], []
        lnull = np.zeros(nl, dtype=bool)
        rnull = np.zeros(nr, dtype=bool)
        for lk, rk in zip(op.left_on, op.right_on):
            lv, rv = left.cols[lk], right.cols[rk]
            ld, rd = left.dicts.get(lk), right.dicts.get(rk)
            if (ld is None) != (rd is None):
                raise CompilerError(f"join key {lk}/{rk}: dictionary/plain mismatch")
            if ld is not None:
                lnull |= lv < 0
                if rd is not ld:
                    rv = apply_lut_np(rd.translate_to(ld, insert=False), rv)
                rnull |= rv < 0
            lcodes.append(np.asarray(lv))
            rcodes.append(np.asarray(rv))
        lc, rc = _composite_codes(lcodes, rcodes)

        from pixie_tpu.ops import join_device as _jd  # defines the flag

        at_dec = None
        if min(nl, nr) >= (1 << 16):
            # the gate is AUTO by default: measured H2D bandwidth on
            # accelerators, native-kernel availability on CPU — and the
            # decision is recorded so it is observable, not silent
            gate = _jd.device_join_gate()
            self.stats.setdefault("device", {})["join_gate"] = {
                k: v for k, v in gate.items() if k != "flag"}
            if _autotune.enabled() and gate.get("flag") == -1:
                # under autotune the threshold heuristic becomes the
                # STATIC arm of a measured device-vs-host cost model;
                # epsilon probes keep the unfavored arm's cost current.
                # Both arms return the same matched-pair SET (pair ORDER
                # is unspecified by the join contract either way).
                # Forced flag settings (0/1) are never overridden.
                at_dec = _autotune.MODEL.decide(
                    _autotune.GATE_DEVICE_JOIN, "join",
                    _autotune.size_bucket(min(nl, nr)),
                    "device" if gate["enabled"] else "host",
                    ("device", "host"))
                self.stats.setdefault("autotune", []).append(at_dec)
        else:
            gate = {"enabled": False}
        use_device = (at_dec["arm"] == "device" if at_dec is not None
                      else gate["enabled"])
        rec["span"] = {
            "rows_left": nl, "rows_right": nr,
            "kernel": "host_sort" if not use_device else
            "native" if _jd.join_path() == "native_cpu" else "device_radix"}
        t_match0 = _time.perf_counter_ns()
        if use_device:
            # device radix-bucketed match phase (ops/join_device.py):
            # sentinel out the nulls so they can't match (-1 vs -2), then
            # the device kernel returns the same pair/mask contract
            lcx = np.where(lnull, np.int64(-1), lc)
            rcx = np.where(rnull, np.int64(-2), rc)
            lidx, ridx, l_matched, r_matched = _jd.device_join_codes(
                lcx, rcx)
            self.stats["device_joins"] = self.stats.get(
                "device_joins", 0) + 1
        else:
            lidx, ridx, l_matched, r_matched = _match_pairs(
                lc, rc, lnull, rnull)
        if at_dec is not None:
            _autotune.MODEL.observe_decision(
                at_dec, (_time.perf_counter_ns() - t_match0) / 1e9)
            # joins often run inside repartition-stage executors whose
            # stats dict is consumed, not forwarded — the event buffer is
            # the durable telemetry path for this gate
            _autotune.MODEL.record_row(at_dec)
        lsel, rsel = [lidx], [ridx]
        if op.how in ("left", "outer"):
            lum = np.nonzero(~l_matched)[0]
            lsel.append(lum)
            rsel.append(np.full(len(lum), -1, dtype=np.int64))
        if op.how in ("right", "outer"):
            rum = np.nonzero(~r_matched)[0]
            lsel.append(np.full(len(rum), -1, dtype=np.int64))
            rsel.append(rum)
        lsel = np.concatenate(lsel)
        rsel = np.concatenate(rsel)
        return self._join_output(op, left, right, lsel, rsel)

    def _join_output(self, op, left, right, lsel, rsel) -> HostBatch:
        dtypes, dicts, cols = {}, {}, {}
        outputs = op.output or _default_join_output(left, right)
        for side, col, out_name in outputs:
            src_b = left if side == "left" else right
            sel = lsel if side == "left" else rsel
            cols[out_name] = _take_with_nulls(
                src_b.cols[col], sel, src_b.dtypes[col]
            )
            dtypes[out_name] = src_b.dtypes[col]
            if col in src_b.dicts:
                dicts[out_name] = src_b.dicts[col]
        return HostBatch(dtypes, dicts, cols)

    def _run_union(self, op: UnionOp) -> HostBatch:
        parents = self.plan.parents(op)
        batches = [self._materialize_parent(p) for p in parents]
        first = batches[0]
        cols: dict[str, np.ndarray] = {}
        dicts: dict[str, Dictionary] = {}
        for name, dt in first.dtypes.items():
            parts = []
            if name in first.dicts:
                target = Dictionary(first.dicts[name].values())
                dicts[name] = target
                for b in batches:
                    lut = b.dicts[name].translate_to(target, insert=True)
                    parts.append(apply_lut_np(lut, b.cols[name]))
            else:
                parts = [b.cols[name] for b in batches]
            cols[name] = np.concatenate(parts) if parts else np.empty(0)
        return HostBatch(dict(first.dtypes), dicts, cols)

    def _materialize_parent(self, parent) -> HostBatch:
        head, chain = self._upstream_chain(parent)
        if not chain and not isinstance(head, MemorySourceOp):
            return self._eval_blocking(head)
        return self._consume_to_batch(parent)

    # ------------------------------------------------------------------- otel
    def _run_otel_sink(self, sink: OTelExportSinkOp) -> None:
        """Export parent rows as OTLP (reference exec/otel_export_sink_node.*)."""
        from pixie_tpu.engine.otel import batch_to_otlp, make_exporter

        parent = self.plan.parents(sink)[0]
        hb = self._materialize_parent(parent)
        with self._timed("otel_export", [sink.id], spanned=False) as rec:
            payload = batch_to_otlp(hb, sink.config)
            export = make_exporter(sink.config, self.otel_exporter)
            export(payload)
            n_metrics = sum(
                len(m["gauge"]["dataPoints"] if "gauge" in m else m["summary"]["dataPoints"])
                for rm in payload.get("resourceMetrics", [])
                for sm in rm["scopeMetrics"]
                for m in sm["metrics"]
            )
            n_spans = sum(
                len(ss["spans"])
                for rs in payload.get("resourceSpans", [])
                for ss in rs["scopeSpans"]
            )
            rec["rows_out"] = hb.num_rows
            self.stats["otel_datapoints"] = self.stats.get("otel_datapoints", 0) + n_metrics
            self.stats["otel_spans"] = self.stats.get("otel_spans", 0) + n_spans

    # -------------------------------------------------------------------- run
    @_compile_scoped
    def run(self) -> dict[str, QueryResult]:
        results = {}
        t0 = _time.perf_counter_ns()
        for sink in self.plan.sinks():
            if isinstance(sink, OTelExportSinkOp):
                self._run_otel_sink(sink)
                continue
            if not isinstance(sink, MemorySinkOp):
                raise Internal(f"plan sink {sink.kind} is not a MemorySink")
            parent = self.plan.parents(sink)[0]
            out_dtypes, out_dicts, out_names, gen = self._consume_chain(
                parent, sink.columns
            )
            parts = [c for c, _ in gen]
            cols = {
                n: (
                    np.concatenate([p[n] for p in parts])
                    if parts
                    else np.empty(0, STORAGE_DTYPE[out_dtypes[n]])
                )
                for n in out_names
            }
            from pixie_tpu.engine.semantics import sink_relation

            rel = sink_relation(self.plan, sink, out_names, out_dtypes,
                                self.store, self.registry)
            nrows = len(next(iter(cols.values()))) if cols else 0
            self.stats["rows_output"] += nrows
            results[sink.name] = QueryResult(
                name=sink.name,
                relation=rel,
                columns=cols,
                dictionaries={n: d for n, d in out_dicts.items()},
                exec_stats=dict(self.stats),
            )
        self.stats["wall_ns"] = _time.perf_counter_ns() - t0
        self.stats["operators"] = self.op_stats
        self._emit_op_spans()
        for r in results.values():
            r.exec_stats["wall_ns"] = self.stats["wall_ns"]
            r.exec_stats["operators"] = self.op_stats
        return results


# --------------------------------------------------------------------- helpers


def _pad(arr: np.ndarray, n: int) -> np.ndarray:
    if len(arr) == n:
        return arr
    out = np.zeros(n, dtype=arr.dtype)
    out[: len(arr)] = arr
    return out


def _time_bounds(head) -> tuple[np.int64, np.int64]:
    if isinstance(head, MemorySourceOp):
        lo = INT64_MIN if head.start_time is None else int(head.start_time)
        hi = INT64_MAX if head.stop_time is None else int(head.stop_time)
        return np.int64(lo), np.int64(hi)
    return np.int64(INT64_MIN), np.int64(INT64_MAX)


def _windowish_groups(chain, time_col: Optional[str]) -> dict[str, int]:
    """Group-key names whose FINAL definition in the chain is px.bin over the
    time column (candidates for runtime-origin window keys; used for cache-sig
    planning BEFORE the kernel is built).

    Tracks each Map's full output list in order — a later redefinition of the
    column to anything else drops its window-ness (matching the provenance
    resolution in _plan_group_keys), while a plain rename passes it through.
    """
    out: dict[str, int] = {}
    for op in chain:
        if not isinstance(op, MapOp):
            continue
        new: dict[str, int] = {}
        for name, e in op.exprs:
            w = _window_key(e, time_col)
            if w is not None:
                new[name] = w
            elif isinstance(e, Column) and e.name in out:
                new[name] = out[e.name]  # passthrough keeps window-ness
        out = new
    return out


def _window_key(expr, time_col: Optional[str]) -> Optional[int]:
    """Detect Call(bin, (Column(time_col), Literal w)) → window width, else
    None.  The binned argument must be the source's time column — only then do
    the baked t0_bin/nbins range semantics hold."""
    if (
        isinstance(expr, Call)
        and expr.fn == "bin"
        and len(expr.args) == 2
        and time_col is not None
        and isinstance(expr.args[0], Column)
        and expr.args[0].name == time_col
    ):
        w = expr.args[1]
        if isinstance(w, Literal) and isinstance(w.value, int) and w.value > 0:
            return int(w.value)
    return None


def _source_time_range(src, head) -> tuple[int, int]:
    if isinstance(src, HostBatch):
        raise Unimplemented("window group keys require a table source")
    if src.table.time_col is None:
        raise Unimplemented("window group keys require a time_ column")
    rng = src.time_range()  # O(batches): sealed bounds cached at seal time
    t_min, t_max = rng if rng is not None else (0, 0)
    if isinstance(head, MemorySourceOp):
        if head.start_time is not None:
            t_min = max(t_min, int(head.start_time))
        if head.stop_time is not None:
            t_max = min(t_max, int(head.stop_time) - 1)
    return t_min, max(t_min, t_max)


def _prescan_unique(src, col: str, qd: Dictionary, sort: bool = False):
    """Populate qd with the column's unique values; sort=True assigns codes in
    sorted order (required by the intdevice searchsorted encoding)."""
    if isinstance(src, HostBatch):
        vals = np.unique(src.cols[col]) if sort else src.cols[col]
        qd.encode(vals)
        return
    if sort:
        parts = [rb.columns[col][: rb.num_valid] for rb, _rid, _gen in src]
        parts = [p for p in parts if len(p)]
        if parts:
            qd.encode(np.unique(np.concatenate([np.unique(p) for p in parts])))
        return
    for rb, _rid, _gen in src:
        arr = rb.columns[col][: rb.num_valid]
        if len(arr):
            qd.encode(np.unique(arr))


def _composite_codes(
    lkeys: list[np.ndarray], rkeys: list[np.ndarray]
) -> tuple[np.ndarray, np.ndarray]:
    """Factorize both sides' (multi-)key rows into one shared int64 code space
    so matching reduces to integer comparison.

    Each key pair factorizes separately FIRST (np.unique collapses NaN on 1-D
    float arrays, giving pandas' NaN==NaN merge semantics), then the per-key
    code columns combine — structured-array comparison over floats would treat
    NaNs as distinct and make join behavior depend on key count.
    """
    nl = len(lkeys[0]) if lkeys else 0
    per = []
    for l, r in zip(lkeys, rkeys):
        _u, inv = np.unique(np.concatenate([l, r]), return_inverse=True)
        per.append(inv.astype(np.int64))
    if len(per) == 1:
        comb = per[0]
    else:
        _u, comb = np.unique(np.rec.fromarrays(per), return_inverse=True)
        comb = comb.astype(np.int64)
    return comb[:nl], comb[nl:]


def _match_pairs(
    lc: np.ndarray, rc: np.ndarray, lnull: np.ndarray, rnull: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """All matching (left_row, right_row) pairs with m:n expansion.

    Returns (lidx, ridx, l_matched[nl], r_matched[nr]).  Sort the valid left
    rows by code; each valid right row finds its [lo, hi) match range by
    binary search and contributes hi-lo pairs.
    """
    nl, nr = len(lc), len(rc)
    lvalid = np.nonzero(~lnull)[0]
    order = lvalid[np.argsort(lc[lvalid], kind="stable")]
    sorted_keys = lc[order]
    if nr >= (1 << 20):
        # Large probe sides: binary-searching RANDOM keys over a big sorted
        # array is memory-latency-bound (measured 29 s for 16M x 16M);
        # sorting the probes first makes consecutive searches cache-local
        # (1.3 s) and the extra sort+scatter-back pays for itself 5x over.
        rorder = np.argsort(rc, kind="stable")
        rs = rc[rorder]
        lo = np.empty(nr, np.int64)
        hi = np.empty(nr, np.int64)
        lo[rorder] = np.searchsorted(sorted_keys, rs, side="left")
        hi[rorder] = np.searchsorted(sorted_keys, rs, side="right")
    else:
        lo = np.searchsorted(sorted_keys, rc, side="left")
        hi = np.searchsorted(sorted_keys, rc, side="right")
    counts = np.where(rnull, 0, hi - lo)
    total = int(counts.sum())
    ridx = np.repeat(np.arange(nr, dtype=np.int64), counts)
    offsets = np.cumsum(counts) - counts
    within = np.arange(total, dtype=np.int64) - np.repeat(offsets, counts)
    lidx = order[np.repeat(lo, counts) + within]
    l_matched = np.zeros(nl, dtype=bool)
    l_matched[lidx] = True
    r_matched = counts > 0
    return lidx, ridx, l_matched, r_matched


def _take_with_nulls(arr: np.ndarray, sel: np.ndarray, dt: DT) -> np.ndarray:
    """arr[sel] with sel == -1 producing the type's null fill."""
    if len(arr) == 0:
        out = np.zeros(len(sel), dtype=arr.dtype)
        miss = np.ones(len(sel), dtype=bool)
    else:
        out = arr[np.clip(sel, 0, len(arr) - 1)]
        miss = sel < 0
    if miss.any():
        out = out.copy()
        out[miss] = _null_value(dt)
    return out


def _default_join_output(left: HostBatch, right: HostBatch):
    out = []
    for c in right.cols:
        out.append(("right", c, c))
    for c in left.cols:
        if c not in right.cols:
            out.append(("left", c, c))
    return out


def _null_value(dt: DT):
    if dt == DT.FLOAT64:
        return np.nan
    if dt in (DT.STRING, DT.UINT128):
        return -1  # code -1 decodes to None
    return 0


def _dtype_of(arr) -> DT:
    d = np.asarray(arr).dtype
    if d.kind == "f":
        return DT.FLOAT64
    if d.kind in "iu":
        return DT.INT64
    if d.kind == "b":
        return DT.BOOLEAN
    return DT.STRING


def execute_plan(plan: Plan, table_store, registry=None,
                 analyze: bool = False) -> dict[str, QueryResult]:
    """Compile + run a plan against a table store; returns {sink_name: QueryResult}."""
    return PlanExecutor(plan, table_store, registry, analyze=analyze).run()
