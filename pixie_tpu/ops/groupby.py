"""Group-by primitives: group codes and masked segment reductions.

Replaces the reference's hash group-by (AbslRowTupleHashMap over RowTuples,
src/carnot/exec/agg_node.h:55-140).  Every group key column is a dense int32
code (dictionary code for strings/UPIDs; query-time dictionary for raw
ints) and multi-key groups are mixed-radix combined into one group id.  What
reduces the rows of a group depends on the group count and on the platform
the kernel is traced for (`dispatch_backend`):

  * on the TPU, sums and counts over a dense space are one-hot GEMMs on
    the MXU, chunk by chunk over the live rows (`scan_sum`, `live_chunks`):
    exact for counts and INT64 (8-bit limbs), F64_SUM_RTOL for f64 values
    summed as a hi/lo float32 pair; a FLOAT64 column's sums are fixed
    point in those limbs, the truncation's residuals in the hi/lo pair
    (`masked_segment_fsum`).  Up to
    MATMUL_MAX_GROUPS slots the one-hot is flat (a chunk's rows x slots);
    from there to ONEHOT2_MAX_GROUPS it is factored by the id's own two
    digits (`_onehot2_gemm`: both operands narrow, the same sums cell for
    cell); `agg_form` says which, from the slot count, the rows and the
    platform alone;
  * past ONEHOT2_MAX_GROUPS, and on every other platform at any group
    count, sums and counts are `jax.ops.segment_sum`; min/max are
    `jax.ops.segment_min/max` everywhere: scatters into the dense
    [num_groups] state.  XLA-CPU runs them at memory speed (0.007 us a row
    a scatter); the TPU serializes them (0.104 us a row on a v5e: PERF.md
    section 6, PR 36's kernels alone; a dense min/max past 1,024 slots
    still pays that there), and the dense state is initialised, read back
    and searched for its seen groups whatever its occupancy;
  * where the group space is sparse (more slots than rows, or no dense
    code at all: float keys, computed keys, a space past the executor's
    MAX_GROUPS) the rows are sorted by their keys instead (`sort_order`),
    every group becomes one run of adjacent rows (`runs_of`), the same
    `masked_segment_*` calls reduce each run with a segmented scan
    (`SortedRuns` in the place of the group id) and a second `sort_order`
    (`run_end_key`) says where the runs' results lie: O(rows log rows)
    whatever the size of the space, and a result of one slot a live
    group.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


def next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1)).bit_length()


def combine_codes(codes: list[jax.Array], cards: list[int],
                  dtype=jnp.int32) -> tuple[jax.Array, int]:
    """Mixed-radix combine k dense code columns into one group id of `dtype`
    (int64 where the product of the cards passes 31 bits).

    cards[i] is a static upper bound on codes[i] (dictionary-size snapshot,
    bucketed by the caller to stabilize compiled shapes). Returns (gid, num_groups)
    with num_groups = prod(cards); gid of a row with any out-of-range/negative code
    is clamped into range — callers must mask such rows out beforehand.
    """
    assert len(codes) == len(cards) and codes
    num_groups = 1
    for c in cards:
        num_groups *= int(c)
    gid = jnp.zeros_like(codes[0], dtype=dtype)
    for code, card in zip(codes, cards):
        c = jnp.clip(code.astype(jnp.int32), 0, card - 1)
        gid = gid * card + c.astype(dtype)
    return gid, num_groups


def split_codes(gids: np.ndarray, cards: list[int]) -> list[np.ndarray]:
    """Host-side inverse of combine_codes: group id → per-key codes."""
    out = []
    rem = np.asarray(gids)
    for card in reversed(cards):
        out.append((rem % card).astype(np.int32))
        rem = rem // card
    return list(reversed(out))


#: Three forms reduce a dense space's sums and counts on the TPU
#: (`agg_form`).  Up to this group count the one-hot GEMM is flat; its
#: one-hot chunk buffer is CHUNK_ROWS × groups × 4B (≤ 256 MB at the cap).
MATMUL_MAX_GROUPS = 1 << 10
#: From there up to this group count the one-hot GEMM is factored by the
#: group id's two digits (`_onehot2_gemm`); past it sums and counts scatter.
#: The executor's SPARSE_MIN_GROUPS: a space past it with fewer rows than
#: slots sorts, and no denser one has been measured.  Read on a v5e at the
#: scan cell's shapes (8,388,608-row bucket, 4,587,520 live; count / f64 sum
#: / INT64 sum, ms a call; PERF.md section 6, PR 37's kernels alone):
#: `segment_sum` 594 / 631 / 595 at 2,048 slots and 893 / 933 / 894 at
#: 65,536; the flat one-hot at 2,048 slots (512 MB a chunk) 7.5 / 15.6 / 9.2;
#: the factored one 1.3 / 4.8 / 4.0 at 2,048, 9.8 / 10.7 / 11.8 at 8,192,
#: 10.4 / 11.7 / 13.3 at 16,384 and 9.7 / 37.5 / 43.3 at 65,536, each at
#: `onehot2_lo`'s width; a first call compiles in 0.4-4.4 s.
ONEHOT2_MAX_GROUPS = 1 << 16
#: Documented bound: an f64 group sum (and so a mean) from the one-hot GEMM
#: agrees with an exact f64 sum to this relative tolerance.  chip_smoke.py
#: holds every f64 mean it reads on the chip to it.
F64_SUM_RTOL = 1e-6
#: Rows per scan chunk.  Chosen so an 8-bit limb chunk sum (≤ CHUNK_ROWS × 255)
#: stays below 2^24 and is therefore EXACT in float32 MXU accumulation.
CHUNK_ROWS = 1 << 16


def dispatch_backend() -> str:
    """The platform kernels traced right now will run on.

    `jax.default_backend()` ignores an active `jax.default_device(...)`
    override (the executor routes small queries to CPU that way), so consult
    the config var first.  Formulation choices (MXU one-hot vs scatter) must
    follow the DISPATCH platform or CPU-routed aggs would trace the matmul
    path — measured 3.6 s vs 8 ms for 1M rows on CPU.
    """
    d = jax.config.jax_default_device
    if d is not None:
        return d.platform
    return jax.default_backend()


def encode_against(lut: jax.Array, values: jax.Array) -> jax.Array:
    """value → sorted-LUT position (== jnp.searchsorted(lut, values, 'left')).

    Small LUTs use a broadcast compare-count: XLA CPU lowers searchsorted to
    a sequential scan (~17 ms for 1M rows × 5 entries, measured) while the
    [N, K] compare is vectorized (~1 ms); TPU fuses either form.
    """
    if lut.shape[0] <= 64 and dispatch_backend() != "tpu":
        return jnp.sum(lut[None, :] < values[:, None], axis=1).astype(jnp.int32)
    return jnp.searchsorted(lut, values).astype(jnp.int32)


def agg_form(n: int, num_groups: int) -> str:
    """The form in which `masked_segment_sum` and `masked_segment_count`,
    traced now, reduce `n` rows into a dense space of `num_groups` slots:
    `onehot` (the flat one-hot GEMM), `onehot2` (the factored one) or
    `scatter` (`jax.ops.segment_sum`).  It reads the slot count, the rows
    and the platform the kernel is traced for and nothing else; the
    kernels dispatch on it and the executor writes it on the chain's span
    (`agg_form`), so the span says what the program does."""
    if (dispatch_backend() != "tpu" or n < 4096
            or n % min(n, CHUNK_ROWS) or num_groups > ONEHOT2_MAX_GROUPS):
        return "scatter"
    return "onehot" if num_groups <= MATMUL_MAX_GROUPS else "onehot2"


def _use_matmul(n: int, num_groups: int) -> bool:
    return agg_form(n, num_groups) != "scatter"


def onehot2_lo(num_groups: int) -> int:
    """The width of the factored one-hot's low digit: the power of two at
    or above sqrt(2 * num_groups), so that the one-hot operand is about
    twice as wide as a lane's rows of the other are many.  The chip's
    readings, not a model (ms a call, f64 sum / INT64 sum, as
    ONEHOT2_MAX_GROUPS' comment): at 2,048 slots 64 wide 4.8 / 4.0 against
    7.6 / 6.4 at 32 and 6.7 / 8.3 at 128; at 8,192 128 wide 10.7 / 11.8
    against 21.3 / 28.3 at 64; at 16,384 256 wide 11.7 / 13.3 against 24.8
    / 28.2 at 128 and 9.8 / 16.1 at 512; at 65,536 512 wide 37.5 / 43.3
    against 66.0 / 56.2 at 256 and 224 / 208 at 64.  (A count alone reads
    1.3-2.7 ms at 64 wide and 9.5-10.4 at any wider width tried, whatever
    the slot count: unexplained, and under the sums' difference past 4,096
    slots.)"""
    return next_pow2(math.isqrt(2 * num_groups))


def _chunked_onehot_sum(v32: jax.Array, gid: jax.Array, num_groups: int,
                        mask: jax.Array, precision=None) -> jax.Array:
    """sum per group of float32 contributions via MXU: for each chunk,
    v[1,CH] @ one_hot[CH,G], accumulated across chunks in float64.

    Scatter-adds on TPU run orders of magnitude slower than this (measured:
    segment_sum over 16M rows ≈ 1.4 s f64 / 180 ms f32; one-hot matmul ≈ 30 ms),
    and chunking keeps the materialized one-hot bounded while making per-chunk
    f32 accumulation exact for bounded-magnitude contributions.
    """
    return _chunked_onehot_multi_sum(
        lambda vv: vv[None, :], v32, gid, num_groups, mask, precision)[0]


def live_chunks(mask: jax.Array, ch: int) -> tuple[jax.Array, jax.Array]:
    """(lo, hi): the chunks [lo, hi) of `ch` rows that hold every live row
    of `mask`, from the first chunk with one to the last, on the device.  An
    all-masked input gives one chunk (the last), so a loop over the range
    still runs once and adds zeros."""
    live = mask.reshape(-1, ch).any(axis=1)
    c = live.shape[0]
    at = jnp.arange(c, dtype=jnp.int32)
    lo = jnp.min(jnp.where(live, at, c - 1))
    hi = jnp.max(jnp.where(live, at + 1, lo + 1))
    return lo, hi


def scan_sum(fn, xs, lo, hi):
    """sum over i in [lo, hi) of fn(xs[i]) along the leading axis, in
    order, as one `lax.fori_loop` whose bounds are traced: the callers pad
    a feed to a pow2 bucket and mask the padding, so they pass the chunk
    range that holds a live row (`live_chunks`) and the loop visits no
    other.  A chunk outside it holds masked rows only: fn of it is exact
    zeros (+0.0 to a float carry, 0 to a count), so leaving it out is
    bit-identical to the loop over every chunk.  The carry starts at
    fn(xs[lo]) instead of a fresh zeros array: under `jax.shard_map` a
    loop's carry must have the same varying-axes type going in as coming
    out, and zeros made inside the body are replicated while fn's output
    varies over the mesh axis (as lo and hi do: each shard walks its own
    range).  (0 + x is x, so the sum is bit-identical to the zero-seeded
    one.)"""
    def at(i):
        return fn(jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), xs))

    return jax.lax.fori_loop(lo + 1, hi, lambda i, acc: acc + at(i), at(lo))


#: Float lanes accumulate on the MXU in runs of this many rows (see
#: _lanes_gemm); the runs are added in f64.
FLOAT_RUN_ROWS = 1 << 12


def _lanes_gemm(lanes: jax.Array, oh: jax.Array, precision) -> jax.Array:
    """[L, CH] f32 lanes @ [CH, G] one-hot → [L, G] f64.

    `precision` None: lanes whose values are bf16-exact (0/1 masks, 8-bit
    limbs) — the MXU's default rounding of f32 operands to bf16 loses
    nothing and f32 accumulation of a chunk is exact.  FLOAT lanes pass
    Precision.HIGHEST, or every value is rounded to 8 significant bits
    before it is summed; and because the MXU's f32 accumulator truncates —
    a one-sided error that grows with the length of the run it accumulates
    — they contract in runs of FLOAT_RUN_ROWS rows whose partial sums are
    added in f64.  Same GEMM, same one-hot, only the accumulation is cut.
    """
    if precision is None:
        return (lanes @ oh).astype(jnp.float64)
    n_lanes, ch = lanes.shape
    k = ch // FLOAT_RUN_ROWS if ch % FLOAT_RUN_ROWS == 0 else 1
    runs = jnp.einsum("lkr,krg->klg", lanes.reshape(n_lanes, k, ch // k),
                      oh.reshape(k, ch // k, oh.shape[1]),
                      precision=precision)
    return runs.astype(jnp.float64).sum(axis=0)


def _chunked_onehot_multi_sum(lanes_fn, v, gid: jax.Array, num_groups: int,
                              mask: jax.Array, precision=None) -> jax.Array:
    """[L, G] f64 per-group sums where lanes_fn(chunk) -> [L, CH] f32 lanes
    and `v` is zero wherever `mask` is False.

    The one-hot is the expensive part (CH x G f32 written/read from HBM per
    chunk); stacking all L lanes into ONE [L,CH] @ [CH,G] GEMM builds it
    once instead of L times — the 8-limb exact-int64 sum was measured
    HBM-bound on exactly this (8 one-hot rebuilds per column per chunk).
    Past MATMUL_MAX_GROUPS slots (`agg_form`) the chunk's GEMM is the
    factored one (`_onehot2_gemm`), the same sums from two narrow operands.
    `precision`: None for bf16-exact lanes, Precision.HIGHEST for float
    lanes (_lanes_gemm).

    The loop visits the chunks from the first that holds a live row of
    `mask` to the last (scan_sum, live_chunks), not the whole bucket: a
    chunk of masked rows has all-zero lanes, its GEMM is exact zeros in
    every lane, and the limb and run exactness arguments are per chunk, so
    the sums are the all-chunks loop's bit for bit.
    """
    n = v.shape[0]
    ch = min(n, CHUNK_ROWS)
    c = n // ch
    factored = agg_form(n, num_groups) == "onehot2"

    def chunk(xs):
        vv, gg = xs
        if factored:
            return _onehot2_gemm(lanes_fn(vv), gg, num_groups, precision)
        oh = jax.nn.one_hot(gg, num_groups, dtype=jnp.float32)
        return _lanes_gemm(lanes_fn(vv), oh, precision)

    return scan_sum(chunk, (v.reshape(c, ch), gid.reshape(c, ch)),
                    *live_chunks(mask, ch))


def _onehot2_gemm(lanes: jax.Array, gg: jax.Array, num_groups: int,
                  precision) -> jax.Array:
    """[L, CH] f32 lanes and the chunk's [CH] group ids → [L, G] f64, the
    flat one-hot GEMM's sums with both operands narrow.  The id's own two
    digits, gid = hi * R + lo (R = `onehot2_lo(G)`, hi < C1 = ceil(G / R)):

        A[l * C1 + hi(r), r] = lanes[l, r]    a lane's value at the row's
                                              high digit, 0 in the other
                                              C1 - 1 rows
        B[r, lo(r)]          = 1              [CH, R]
        (A @ B)[l * C1 + h, j] = sum of lanes[l, r] over the rows r of
                                 group h * R + j

    CH * (L * C1 + R) operand elements a chunk where the flat one-hot has
    CH * G.  Every output cell sums exactly the values the flat one-hot's
    cell of that group would, through the same `_lanes_gemm`: an entry of
    A is a lane's value or a zero, so limb and mask lanes stay bf16-exact
    and a chunk's sum exact in f32, float lanes keep Precision.HIGHEST and
    their FLOAT_RUN_ROWS runs; the exactness arguments of `_lanes_gemm`,
    `_chunked_onehot_multi_sum` and `masked_segment_sum` hold word for
    word.  An id outside [0, G) meets no row of A or a column past G, as it
    meets no column of the flat one-hot."""
    n_lanes, ch = lanes.shape
    r = onehot2_lo(num_groups)
    c1 = -(-num_groups // r)
    at_hi = (gg // r)[None, :] == jnp.arange(c1, dtype=jnp.int32)[:, None]
    a = jnp.where(at_hi[None], lanes[:, None, :], jnp.float32(0))
    b = jax.nn.one_hot(gg % r, r, dtype=jnp.float32)
    s = _lanes_gemm(a.reshape(n_lanes * c1, ch), b, precision)
    return s.reshape(n_lanes, c1 * r)[:, :num_groups]


class SortedRuns(NamedTuple):
    """Rows sorted so that every group is one run of adjacent rows, handed
    to `masked_segment_*` in the place of the group id: `start[i]` says that
    row i opens its run, `end[i]` that it closes it; a row past the live
    ones does neither.  A reduction over runs is aligned with the rows
    ([n], not [num_groups]) and holds a run's result at the run's last row;
    `run_end_key` finds those."""

    start: jax.Array
    end: jax.Array


def run_sort_keys(keys: list, mask: jax.Array,
                  sentinel: Optional[int] = None) -> tuple:
    """What `sort_order` sorts the rows of a sorted aggregate by: the rows
    of `mask` first, then `keys` lexicographically.  `sentinel`: a value
    above every live row's first key, an integer; the masked rows take it
    there and no key of their own orders them last."""
    if sentinel is not None:
        k, *rest = keys
        return (jnp.where(mask, k, jnp.asarray(sentinel, k.dtype)), *rest)
    return (jnp.logical_not(mask).astype(jnp.int32), *keys)


@jax.jit
def sort_order(keys: tuple) -> tuple:
    """(the keys sorted lexicographically, the row each sorted position
    came from): one `lax.sort` of the keys and an iota.  Nothing else
    rides it and it is a program of its own, because the TPU compiler's
    time for a sort grows with its operands (11 s for a 32-bit key and the
    iota at 524,288 rows, 38 s with two INT64 columns riding, 89 s with
    nine 32-bit ones: PERF.md section 6, PR 36's kernels alone): the value
    columns are gathered by the order instead, and the sort that moves the
    runs' results to the front (`run_end_key`) is this same program
    again.  It grows faster still with the keys it compares: at 1,048,576
    rows, compiled for a described v5e, 91.0 s for a 32-bit and a 64-bit
    key against 602.7 s for seven keys, so the sorted aggregate packs its
    keys of known cardinality into one (`make_keyed_rows_step`)."""
    n = keys[0].shape[0]
    with jax.named_scope("px.sort_runs"):
        out = jax.lax.sort((*keys, jnp.arange(n, dtype=jnp.int32)),
                           num_keys=len(keys))
    return tuple(out[:-1]), out[-1]


def runs_of(keys_sorted: list, n_live: jax.Array) -> tuple:
    """(runs, live) of rows sorted by `keys_sorted` of which the first
    `n_live` are live: a run is a stretch of live rows whose keys are all
    equal; `live[i]` says that sorted row i is a live row."""
    n = keys_sorted[0].shape[0]
    live = jnp.arange(n, dtype=jnp.int32) < n_live
    differs = jnp.zeros((n - 1,), dtype=jnp.bool_)
    for k in keys_sorted:
        differs = differs | (k[1:] != k[:-1])
    first = jnp.ones((1,), dtype=jnp.bool_)
    start = live & jnp.concatenate([first, differs])
    end = live & jnp.concatenate([start[1:] | ~live[1:], first])
    return SortedRuns(start, end), live


def run_ids(runs: SortedRuns) -> jax.Array:
    """The number of each row's run, in run order (int32; 0 before the
    first): exact dense group ids for a reduction that needs them."""
    return jnp.maximum(jnp.cumsum(runs.start.astype(jnp.int32)) - 1, 0)


def run_end_key(runs: SortedRuns) -> jax.Array:
    """An int32 key under which `sort_order` puts the rows that close a
    run first, in run order: its order's first sum(runs.end) entries are
    where the groups' results lie."""
    n = runs.end.shape[0]
    at = jnp.arange(n, dtype=jnp.int32)
    return jnp.where(runs.end, at, at + n)


@jax.named_scope("px.compact_runs")
def take_rows(tree, rows: jax.Array):
    """Every leaf of `tree` at `rows`: the gather that brings the value
    columns into sorted order, and the runs' results to the front."""
    return jax.tree.map(lambda a: a[rows], tree)


def _run_reduce(v: jax.Array, runs: SortedRuns, op) -> jax.Array:
    """Inclusive scan of `v` under `op` that starts anew at every run
    start; at a run's last row it is the run's reduction.  No scatter on
    either platform.  On the TPU: log2(n) whole-array passes, each row
    taking in the row 2^k before it unless a run starts in between
    (Hillis-Steele; O(n log n) elementwise work, a few MB a pass).
    Elsewhere `lax.associative_scan`, O(n) work.  Each platform has the
    form the other cannot afford (one INT64 leaf of 524,288 rows alone,
    PERF.md section 6, PR 36): the TPU runs the passes in 0.7 ms and
    builds them in 2.5 s, where the work-efficient scan's strided slices
    run in 2.4 ms and take its compiler 24 s a leaf; XLA-CPU runs the
    passes in 97 ms and the work-efficient scan in 2.5."""
    if dispatch_backend() != "tpu":
        def combine(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, jnp.where(fb, vb, op(va, vb))

        return jax.lax.associative_scan(combine, (runs.start, v))[1]
    n = v.shape[0]
    started = runs.start  # a run starts in (i - d, i]: nothing to take in
    d = 1
    while d < n:
        before = jnp.concatenate([v[:d], v[:-d]])
        v = jnp.where(started, v, op(before, v))
        started = started | jnp.concatenate(
            [jnp.ones((d,), dtype=jnp.bool_), started[:-d]])
        d *= 2
    return v


@jax.named_scope("px.groupby_sum")
def masked_segment_sum(values: jax.Array, gid: jax.Array, num_groups: int, mask: jax.Array):
    v = jnp.where(mask, values, jnp.zeros((), dtype=values.dtype))
    if isinstance(gid, SortedRuns):
        return _run_reduce(v, gid, jnp.add)
    if not _use_matmul(v.shape[0], num_groups):
        return jax.ops.segment_sum(v, gid, num_segments=num_groups)
    gid = gid.astype(jnp.int32)
    d = jnp.dtype(v.dtype)
    if d == jnp.bool_:
        return _chunked_onehot_sum(
            v.astype(jnp.float32), gid, num_groups, mask).astype(jnp.int64)
    if d in (jnp.dtype(jnp.int64), jnp.dtype(jnp.uint64), jnp.dtype(jnp.int32)):
        # EXACT 64-bit sums on the MXU: split the two's-complement bit pattern
        # into 8-bit limbs; each limb's chunk sum ≤ 2^24 is exact in f32, the
        # f64 cross-chunk accumulation is exact below 2^53, and the final
        # shifted int64 adds wrap mod 2^64 — i.e. true two's-complement sum.
        # All 8 limbs ride ONE GEMM per chunk (the one-hot dominates HBM).
        u = v.astype(jnp.uint64)
        shifts = jnp.arange(8, dtype=jnp.uint64) * jnp.uint64(8)

        @jax.named_scope("px.int_limbs")
        def limbs(uu):
            return ((uu[None, :] >> shifts[:, None])
                    & jnp.uint64(0xFF)).astype(jnp.float32)

        s = _chunked_onehot_multi_sum(limbs, u, gid, num_groups, mask)  # [8, G]
        with jax.named_scope("px.int_limbs"):
            total = jnp.zeros((num_groups,), dtype=jnp.uint64)
            for k in range(8):
                total = total + (s[k].astype(jnp.uint64) << (8 * k))
        return total.astype(v.dtype if d != jnp.dtype(jnp.int32) else jnp.int64)
    if d == jnp.dtype(jnp.float64):
        # hi/lo float32 split: v == hi + lo to ~2^-48 relative; residual error
        # is the f32 accumulation of hi within a run (F64_SUM_RTOL).
        def hilo(vv):
            hi = vv.astype(jnp.float32)
            lo = (vv - hi.astype(jnp.float64)).astype(jnp.float32)
            return jnp.stack([hi, lo])

        s = _chunked_onehot_multi_sum(hilo, v, gid, num_groups, mask,
                                      precision=jax.lax.Precision.HIGHEST)
        return s[0] + s[1]
    if d in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return _chunked_onehot_sum(
            v.astype(jnp.float32), gid, num_groups, mask,
            precision=jax.lax.Precision.HIGHEST).astype(d)
    return jax.ops.segment_sum(v, gid, num_segments=num_groups)


def _pow2(k: jax.Array) -> jax.Array:
    """2.0**k as a float64 for an int32 k in [-252, 252]: the product of two
    float32 powers of two, each exact, so no float64 bit arithmetic is
    needed where the TPU emulates float64."""
    half = k // 2
    one = jnp.float32(1.0)
    return (jnp.ldexp(one, half).astype(jnp.float64)
            * jnp.ldexp(one, k - half).astype(jnp.float64))


@jax.named_scope("px.groupby_fsum")
def masked_segment_fsum(values: jax.Array, gid: jax.Array, num_groups: int,
                        mask: jax.Array) -> jax.Array:
    """Per-group sums of a FLOAT64 column (px.sum and px.mean over one).

    On the TPU's GEMM forms (`agg_form`) every value is split at the one
    power of two 2^-k that brings the column's largest magnitude under
    2^(62 - ceil(log2 n)): its multiples of 2^-k, truncated to an INT64,
    sum exactly in `masked_segment_sum`'s 8-bit limbs, and what truncation
    left (under 2^-k a row) sums through the hi/lo split.  A group's error
    is then the hi/lo split's float32 accumulation of its residuals alone:
    ~6e-8 of the residuals' sum, never more than the hi/lo split of the
    values would leave (F64_SUM_RTOL), and far less where the group's
    values stand above 2^-k, whatever the other groups hold.  The scale
    comes from the largest magnitude as a float32, so the column must lie
    within float32's range, as the hi/lo split's must; a column holding
    NaN or an infinity sums to NaN in every group (the GEMM's zeros times
    it would too).  Elsewhere, and over sorted runs, `masked_segment_sum`'s
    own forms, which add in float64."""
    if isinstance(gid, SortedRuns) or not _use_matmul(values.shape[0],
                                                      num_groups):
        return masked_segment_sum(values, gid, num_groups, mask)
    v = jnp.where(mask, values.astype(jnp.float64), 0.0)
    top = jnp.max(jnp.abs(v))
    _, e = jnp.frexp(top.astype(jnp.float32))  # top < 2^e
    k = (62 - (v.shape[0] - 1).bit_length()) - e
    q = (v * _pow2(k)).astype(jnp.int64)
    rest = v - q.astype(jnp.float64) * _pow2(-k)
    s = (masked_segment_sum(q, gid, num_groups, mask).astype(jnp.float64)
         * _pow2(-k) + masked_segment_sum(rest, gid, num_groups, mask))
    return jnp.where(jnp.isfinite(top), s, jnp.nan)


@jax.named_scope("px.groupby_count")
def masked_segment_count(gid: jax.Array, num_groups: int, mask: jax.Array) -> jax.Array:
    """Rows per group (int64, exact): f32 one-hot matmul of the mask on TPU,
    flat or factored (per-chunk counts ≤ CHUNK_ROWS are exact in f32),
    scatter elsewhere and past ONEHOT2_MAX_GROUPS (`agg_form`)."""
    if isinstance(gid, SortedRuns):
        return _run_reduce(mask.astype(jnp.int64), gid, jnp.add)
    n = gid.shape[0]
    if _use_matmul(n, num_groups):
        c = _chunked_onehot_sum(mask.astype(jnp.float32),
                                gid.astype(jnp.int32), num_groups, mask)
        return c.astype(jnp.int64)
    ones = jnp.where(mask, 1, 0).astype(jnp.int64)
    return jax.ops.segment_sum(ones, gid, num_segments=num_groups)


@jax.named_scope("px.groupby_min")
def masked_segment_min(values: jax.Array, gid: jax.Array, num_groups: int, mask: jax.Array):
    big = _identity_for(values.dtype, "min")
    v = jnp.where(mask, values, big)
    if isinstance(gid, SortedRuns):
        return _run_reduce(v, gid, jnp.minimum)
    return jax.ops.segment_min(v, gid, num_segments=num_groups)


@jax.named_scope("px.groupby_max")
def masked_segment_max(values: jax.Array, gid: jax.Array, num_groups: int, mask: jax.Array):
    small = _identity_for(values.dtype, "max")
    v = jnp.where(mask, values, small)
    if isinstance(gid, SortedRuns):
        return _run_reduce(v, gid, jnp.maximum)
    return jax.ops.segment_max(v, gid, num_segments=num_groups)


def _identity_for(dtype, op: str):
    d = jnp.dtype(dtype)
    if d.kind == "f":
        return jnp.array(jnp.inf if op == "min" else -jnp.inf, dtype=d)
    if d.kind in "iu":
        info = jnp.iinfo(d)
        return jnp.array(info.max if op == "min" else info.min, dtype=d)
    if d.kind == "b":
        return jnp.array(op == "min", dtype=d)
    raise TypeError(f"no identity for dtype {d}")
