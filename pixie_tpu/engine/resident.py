"""Device-resident hot tables: the pinned tier above the HBM feed cache.

The sealed-feed HBM cache (executor._DEVICE_CACHE) keys whole feeds by their
seal-gen tuple — sound, but every new seal changes the tuple, so ingest
invalidates the entry and the NEXT query re-uploads every byte of the hot
columns.  Mechanism: a warm interactive query should upload nothing, and
after every seal it uploaded the whole table.  What that upload costs on
the current chip is unverified (ROADMAP S2).  This tier fixes the
invalidation granularity:

  * One pinned entry per (table uid, column set): the newest run of sealed
    batches as ONE stacked device array per column (pow2 bucket, zero pad).
  * Ingest deltas FOLD IN PLACE: a new seal uploads only its own rows and a
    jitted ``dynamic_update_slice`` appends them to the resident buffer —
    the epoch-keyed append kernel (entry.epoch counts folds; jit reuse is
    by shape, so steady-state folds hit one compiled kernel).
  * Retention trims EVICT: `Table._expire_locked` calls `on_retention_trim`;
    a fully-expired entry frees immediately, a head-trimmed entry marks
    `trim_to` and the next feed rebases (one jitted roll — retained rows
    never re-cross the link).
  * A warm query whose cursor matches the resident range consumes the
    handle directly: ZERO host→device bytes, and with one feed the executor
    fuses partial+finalize into one execution + a kilobyte readback.

Budget: `PL_HBM_RESIDENT_MB` bounds the tier (LRU across entries; an entry
that cannot fit falls back to the streaming feed path — the executor's
legacy cache/upload path, bit-identical results).  `PL_HBM_RESIDENT=0`
turns the tier off entirely (A/B proof of bit-equality).
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from functools import partial
from typing import Optional

import numpy as np

from pixie_tpu import flags as _flags
from pixie_tpu import metrics as _metrics

_ENABLED = _flags.define_bool(
    "PL_HBM_RESIDENT", True,
    "pinned device-resident tier for sealed hot-table columns (warm "
    "queries upload zero bytes; deltas fold in place)")
_BUDGET_MB = _flags.define_int(
    "PL_HBM_RESIDENT_MB", 2048,
    "resident-tier HBM budget (MB); entries beyond it fall back to the "
    "streaming feed path")

MIN_BUCKET = 1 << 10

_LOCK = threading.Lock()
#: per-(table_uid, names) feed locks: fold/rebase range math must serialize
#: PER ENTRY (two warm queries racing the same delta would double-fold it),
#: but a global lock would head-of-line block every table's sub-10ms warm
#: hit behind one table's seconds-long cold admission upload
_ENTRY_LOCKS: dict = {}


def _entry_lock(key):
    with _LOCK:
        lk = _ENTRY_LOCKS.get(key)
        if lk is None:
            lk = _ENTRY_LOCKS[key] = threading.RLock()
        return lk
#: (table_uid, names tuple) -> _Entry, LRU order
_TIER: "OrderedDict[tuple, _Entry]" = OrderedDict()
_TIER_BYTES = 0

#: process-wide tier stats (also exported as px_resident_* metrics)
stats = {"hits": 0, "folds": 0, "rebases": 0, "admissions": 0,
         "fallbacks": 0, "trims": 0}


class _Entry:
    __slots__ = ("gen_lo", "gen_hi", "rows", "batch_rows", "bucket", "cols",
                 "nbytes", "epoch", "trim_to", "sharding")

    def __init__(self, gen_lo, gen_hi, rows, batch_rows, bucket, cols,
                 sharding=None):
        self.gen_lo = gen_lo
        self.gen_hi = gen_hi
        self.rows = rows
        self.batch_rows = batch_rows
        self.bucket = bucket
        self.cols = cols
        self.nbytes = sum(v.nbytes for v in cols.values())
        self.epoch = 0
        self.trim_to: Optional[int] = None
        #: None = single-device entry; a jax NamedSharding = SHARDED-resident
        #: entry, each column pinned row-block-wise across a device mesh (the
        #: GSPMD column layout — SPMD queries consume it with zero reshard)
        self.sharding = sharding


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1)).bit_length()


# ------------------------------------------------------------- jit kernels
# Defined lazily (jax import stays off the table-writer path until a query
# actually uses the tier).

_KERNELS = None
#: sharded-entry kernel variants, one set per (mesh, spec): identical math,
#: but jitted with out_shardings so fold/grow/shift products KEEP the
#: NamedSharding instead of decaying to single-device (a decayed buffer
#: would silently reshard every later SPMD consumer)
_SHARD_KERNELS: dict = {}


def _kernels(sharding=None):
    global _KERNELS
    import jax
    import jax.numpy as jnp

    def build(**jit_kw):
        @partial(jax.jit, **jit_kw)
        def fold(buf, delta, off):
            # epoch-keyed append: off is a TRACED scalar, so every fold of
            # the same (buffer, delta) shape reuses one compiled kernel
            return jax.lax.dynamic_update_slice(buf, delta, (off,))

        @partial(jax.jit, static_argnames=("extra",), **jit_kw)
        def grow(buf, extra):
            return jnp.pad(buf, (0, extra))

        @partial(jax.jit, **jit_kw)
        def shift(buf, drop):
            # head rebase after a retention trim: retained rows move to the
            # front; the wrapped tail is garbage but sits past n_valid and
            # every consumer masks by n_valid
            return jnp.roll(buf, -drop)

        return fold, grow, shift

    if sharding is None:
        if _KERNELS is None:
            _KERNELS = build()
        return _KERNELS
    key = (id(sharding.mesh), tuple(sharding.spec))
    got = _SHARD_KERNELS.get(key)
    if got is None:
        # sharded fold/grow/shift are MULTI-DEVICE programs: on an XLA-CPU
        # mesh they must take the same collective-serialization lock as
        # every other mesh execution — an unserialized fold racing a locked
        # SPMD agg splits the shared intra-op pool between their
        # rendezvous and deadlocks (parallel.spmd.collective_gate)
        from pixie_tpu.parallel.spmd import serialize_cpu_collectives

        got = _SHARD_KERNELS[key] = tuple(
            serialize_cpu_collectives(k, sharding.mesh)
            for k in build(out_shardings=sharding))
    return got


def _budget_bytes() -> int:
    return int(_flags.get("PL_HBM_RESIDENT_MB")) << 20


def _evict_lru_locked(need: int, keep_key) -> bool:
    """Evict LRU entries (never `keep_key`) until `need` bytes fit the
    budget.  Returns False when impossible (the entry alone exceeds it)."""
    global _TIER_BYTES
    budget = _budget_bytes()
    if need > budget:
        return False
    while _TIER_BYTES + need > budget:
        victim = next((k for k in _TIER if k != keep_key), None)
        if victim is None:
            return False
        e = _TIER.pop(victim)
        _TIER_BYTES -= e.nbytes
    return True


def _device_put(host_cols: dict, sharding=None) -> dict:
    import jax

    if sharding is not None:
        return {k: jax.device_put(v, sharding) for k, v in host_cols.items()}
    return {k: jax.device_put(v) for k, v in host_cols.items()}


def assemble_padded(parts: list, names, bucket: int) -> dict:
    """Single-copy host assembly into zero-padded bucket buffers — the ONE
    implementation of feed assembly (PlanExecutor._feed and the tier's
    admission both use it, so their buffers can never diverge)."""
    cols = {}
    for k in names:
        first = parts[0][k]
        buf = np.zeros(bucket, dtype=first.dtype)
        off = 0
        for p in parts:
            a = p[k]
            buf[off: off + len(a)] = a
            off += len(a)
        cols[k] = buf
    return cols


def feed(table_uid: int, names: tuple, gens: list, batch_rows: int,
         parts: list, n_rows: int, prewarmed=None, sharding=None,
         n_dev: int = 1):
    """Serve one sealed-only feed from the resident tier.

    → (device cols dict padded to the entry bucket, h2d_bytes) or None
    (tier off / shape not coverable / budget exceeded — caller streams
    through the legacy feed path).  `gens` must be the consecutive seal
    gens of `parts`, each part exactly `batch_rows` rows (whole sealed
    batches; sliced delta batches carry gen None and never reach here).
    `prewarmed` optionally carries the legacy gen-tuple HBM-cache entry
    for exactly this feed: admission then ADOPTS those device arrays
    instead of re-uploading the same bytes alongside them.

    `sharding`/`n_dev` select the SHARDED-resident tier: entries keyed per
    mesh width, columns pinned with the NamedSharding (GSPMD row-block
    layout over the mesh axis), ingest deltas folding shard-local via the
    out_shardings fold kernels — so warm SPMD queries consume the handle
    with zero H2D bytes AND zero resharding.  Single-device (n_dev=1) and
    sharded entries coexist; they never alias (the key carries n_dev).
    """
    if not _flags.get("PL_HBM_RESIDENT") or not gens:
        return None
    if not all(isinstance(g, (int, np.integer)) for g in gens):
        # tabletized tables namespace gens as (tablet id, gen) tuples —
        # no linear fold frontier exists across a chained cursor; stream
        return None
    if any(gens[i + 1] != gens[i] + 1 for i in range(len(gens) - 1)):
        return None  # time-pruned cursor skipped interior batches
    if any(len(p[names[0]]) != batch_rows for p in parts):
        return None
    if n_dev > 1:
        if sharding is None:
            return None
        bucket = max(_next_pow2(n_rows), MIN_BUCKET)
        if bucket % n_dev:
            return None  # not row-block shardable; caller streams
    # one feed mutates a given entry at a time: concurrent warm queries
    # over the same table would otherwise both compute the same delta and
    # double-fold it (other tables' feeds proceed in parallel)
    with _entry_lock((table_uid, names, n_dev)):
        return _feed_locked(table_uid, names, gens, parts, batch_rows,
                            n_rows, prewarmed, sharding, n_dev)


def _feed_locked(table_uid, names, gens, parts, batch_rows, n_rows,
                 prewarmed=None, sharding=None, n_dev: int = 1):
    global _TIER_BYTES
    g0, g1 = int(gens[0]), int(gens[-1])
    key = (table_uid, names, n_dev)
    with _LOCK:
        entry = _TIER.get(key)
        if entry is not None:
            _TIER.move_to_end(key)
    if entry is None:
        return _admit(key, g0, g1, batch_rows, parts, n_rows, prewarmed,
                      sharding)
    # lazily apply a pending retention trim before range math
    if entry.trim_to is not None and entry.trim_to > entry.gen_lo:
        _rebase(entry, entry.trim_to)
    if g0 < entry.gen_lo:
        # an old pinned cursor reaching below the resident window: its head
        # rows are gone from the tier — stream it, keep the entry
        stats["fallbacks"] += 1
        return None
    if g1 <= entry.gen_hi:
        if g0 == entry.gen_lo and g1 == entry.gen_hi:
            stats["hits"] += 1
            _metrics.counter_inc(
                "px_resident_hits_total",
                help_="warm feeds served fully from the resident tier "
                      "(zero H2D bytes)")
            return dict(entry.cols), 0
        stats["fallbacks"] += 1
        return None  # strict subrange (bounded cursor): stream it
    if g0 > entry.gen_hi + 1:
        # disjoint newer run (a >FEED_ROWS table's later feed): the newest
        # batches win the pinned slot
        with _LOCK:
            _TIER.pop(key, None)
            _TIER_BYTES -= entry.nbytes
        return _admit(key, g0, g1, batch_rows, parts, n_rows, prewarmed,
                      sharding)
    # overlap/extension: fold only the genuinely new batches.  A cursor
    # starting PAST the entry head without a pending trim is a
    # time-pruned head (the head batches are still retained and other
    # queries still want them) — stream it rather than destructively
    # rebasing the pinned entry; real retention trims arrive via
    # on_retention_trim and were applied above.
    if g0 > entry.gen_lo:
        stats["fallbacks"] += 1
        return None
    delta = [p for g, p in zip(gens, parts) if g > entry.gen_hi]
    h2d = _fold(key, entry, delta, g1)
    if h2d is None:
        return None
    if entry.rows != n_rows:  # pragma: no cover — defensive: never serve
        with _LOCK:           # a mis-sized buffer as a feed
            _TIER.pop(key, None)
            _TIER_BYTES -= entry.nbytes
        return None
    return dict(entry.cols), h2d


def _admit(key, g0, g1, batch_rows, parts, n_rows, prewarmed=None,
           sharding=None):
    global _TIER_BYTES
    names = key[1]
    bucket = max(_next_pow2(n_rows), MIN_BUCKET)

    def adoptable(arr):
        if arr.shape != (bucket,):
            return False
        # a sharded entry may only adopt arrays already placed with the SAME
        # sharding — adopting a single-device array would silently reshard
        # (and mis-account) every later consumer
        if sharding is not None:
            return getattr(arr, "sharding", None) == sharding
        return True

    if (prewarmed is not None
            and all(n in prewarmed and adoptable(prewarmed[n])
                    for n in names)):
        # adopt the legacy gen-tuple cache's device arrays for this exact
        # feed: zero re-upload, and the caller evicts the legacy entry so
        # the bytes are pinned ONCE
        cols = {n: prewarmed[n] for n in names}
        h2d = 0
    else:
        host = assemble_padded(parts, names, bucket)
        cols = None
        h2d = sum(v.nbytes for v in host.values())
    # h2d accounting is REAL uploaded bytes everywhere: admission ships the
    # padded bucket buffers (same convention as the streaming feed path);
    # folds ship exact-length deltas; adoption ships nothing
    nbytes = sum((cols or host)[n].nbytes for n in names)
    with _LOCK:
        if not _evict_lru_locked(nbytes, key):
            stats["fallbacks"] += 1
            _metrics.counter_inc(
                "px_resident_fallbacks_total",
                help_="feeds that exceeded PL_HBM_RESIDENT_MB and streamed "
                      "through the legacy path")
            return None
    if cols is None:
        cols = _device_put(host, sharding)
    entry = _Entry(g0, g1, n_rows, batch_rows, bucket, cols, sharding)
    with _LOCK:
        old = _TIER.pop(key, None)
        if old is not None:
            _TIER_BYTES -= old.nbytes
        _TIER[key] = entry
        _TIER_BYTES += entry.nbytes
    stats["admissions"] += 1
    _metrics.counter_inc("px_resident_admissions_total",
                         help_="fresh resident-tier entry uploads")
    return dict(entry.cols), h2d


def _rebase(entry: _Entry, new_lo: int) -> None:
    """Drop expired head batches on device (one jitted roll per column)."""
    _fold_k, _grow_k, shift_k = _kernels(entry.sharding)
    drop = (new_lo - entry.gen_lo) * entry.batch_rows
    entry.cols = {k: shift_k(v, np.int64(drop)) for k, v in entry.cols.items()}
    entry.rows -= drop
    entry.gen_lo = new_lo
    with _LOCK:
        # clear the trim mark only if no NEWER trim landed mid-rebase (the
        # writer sets trim_to under _LOCK; blindly clearing would discard
        # it and pin the newly-expired batches until full expiry)
        if entry.trim_to is not None and entry.trim_to <= new_lo:
            entry.trim_to = None
    entry.epoch += 1
    stats["rebases"] += 1


def _fold(key, entry: _Entry, delta_parts: list, new_hi: int):
    """Append new sealed batches in place; → uploaded delta bytes or None
    (growth blew the budget — entry dropped, caller streams)."""
    global _TIER_BYTES
    fold_k, grow_k, _shift_k = _kernels(entry.sharding)
    names = key[1]
    add_rows = sum(len(p[names[0]]) for p in delta_parts)
    new_rows = entry.rows + add_rows
    if new_rows > entry.bucket:
        new_bucket = max(_next_pow2(new_rows), MIN_BUCKET)
        extra = new_bucket - entry.bucket
        grown_bytes = sum((v.nbytes // entry.bucket) * new_bucket
                          for v in entry.cols.values())
        with _LOCK:
            # a concurrent retention trim may have popped this entry
            # (on_retention_trim never waits on _FEED_LOCK): then the
            # tier's byte ledger no longer covers it — grow the orphan for
            # this one serve without touching the accounting
            present = _TIER.get(key) is entry
            if present:
                _TIER_BYTES -= entry.nbytes
                if not _evict_lru_locked(grown_bytes, key):
                    _TIER.pop(key, None)
                    stats["fallbacks"] += 1
                    _metrics.counter_inc("px_resident_fallbacks_total")
                    return None
                _TIER_BYTES += grown_bytes
            # nbytes must flip INSIDE the ledger's lock: a trim popping the
            # entry between the +grown_bytes above and this assignment
            # would subtract the stale figure and inflate the ledger
            entry.nbytes = grown_bytes
        entry.cols = {k: grow_k(v, extra=extra) for k, v in entry.cols.items()}
        entry.bucket = new_bucket
    h2d = 0
    off = np.int64(entry.rows)
    for k in names:
        d = np.concatenate([p[k] for p in delta_parts]) \
            if len(delta_parts) > 1 else delta_parts[0][k]
        d = np.ascontiguousarray(d)
        h2d += d.nbytes
        entry.cols[k] = fold_k(entry.cols[k], d, off)
    entry.rows = new_rows
    entry.gen_hi = new_hi
    entry.epoch += 1
    stats["folds"] += 1
    _metrics.counter_inc(
        "px_resident_folds_total",
        help_="in-place ingest-delta folds into resident buffers")
    return h2d


def on_retention_trim(table_uid: int, oldest_retained_gen) -> None:
    """Table expiry hook: free fully-expired entries now; mark head-trimmed
    entries for a lazy rebase at their next feed.  Cheap (no device ops) —
    runs on the writer thread under the table lock, so it must NEVER wait
    on an entry feed lock (feed() holds those across device uploads,
    which can be long); _fold re-checks membership under _LOCK
    before touching the byte accounting, so racing a pop here is safe."""
    global _TIER_BYTES
    with _LOCK:
        for key in [k for k in _TIER if k[0] == table_uid]:
            e = _TIER[key]
            if oldest_retained_gen is None or oldest_retained_gen > e.gen_hi:
                _TIER.pop(key)
                _TIER_BYTES -= e.nbytes
                stats["trims"] += 1
                _metrics.counter_inc(
                    "px_resident_trim_evictions_total",
                    help_="resident entries freed by retention trimming")
            elif oldest_retained_gen > e.gen_lo:
                e.trim_to = max(e.trim_to or 0, oldest_retained_gen)


def drop_table(table_uid: int) -> None:
    """Free every resident entry for one table NOW — the pinned-tier
    invalidation hook for shard-map changes: a replica dropping a dead
    primary's takeover store (services/replication.py) must not leave that
    store's columns pinned in HBM.  Cheap bookkeeping only, same contract
    as on_retention_trim."""
    global _TIER_BYTES
    with _LOCK:
        for key in [k for k in _TIER if k[0] == table_uid]:
            e = _TIER.pop(key)
            _TIER_BYTES -= e.nbytes
            stats["trims"] += 1
            _metrics.counter_inc(
                "px_resident_shard_map_evictions_total",
                help_="resident entries freed by shard-map / takeover-store "
                      "invalidation")


def tier_stats() -> dict:
    with _LOCK:
        return {"entries": len(_TIER), "bytes": _TIER_BYTES, **stats}


def per_table_bytes() -> dict[int, int]:
    """{table_uid: pinned HBM bytes} — the storage-state fold's view of who
    holds the resident budget (entries are keyed (table_uid, names,
    n_dev))."""
    out: dict[int, int] = {}
    with _LOCK:
        for key, e in _TIER.items():
            uid = int(key[0])
            out[uid] = out.get(uid, 0) + int(e.nbytes)
    return out


def clear_for_testing() -> None:
    global _TIER_BYTES
    with _LOCK:
        _TIER.clear()
        _ENTRY_LOCKS.clear()
        _TIER_BYTES = 0
    for k in stats:
        stats[k] = 0


def _gauges() -> dict:
    with _LOCK:
        return {(("tier", "resident"),): float(_TIER_BYTES)}


_metrics.register_gauge_fn(
    "px_resident_tier_bytes", _gauges,
    help_="bytes pinned in the device-resident hot-table tier")
