"""Group-by primitives: dense group codes + masked segment reductions.

Replaces the reference's hash group-by (AbslRowTupleHashMap over RowTuples,
src/carnot/exec/agg_node.h:55-140) with a TPU-native formulation: every group key
column is a dense int32 code (dictionary code for strings/UPIDs; query-time
dictionary for raw ints), multi-key groups are mixed-radix combined into a single
segment id, and aggregation is an XLA segment reduction — which lowers to sorted
scatter-adds that tile well, instead of pointer-chasing hash probes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1)).bit_length()


def combine_codes(codes: list[jax.Array], cards: list[int]) -> tuple[jax.Array, int]:
    """Mixed-radix combine k dense code columns into one group id.

    cards[i] is a static upper bound on codes[i] (dictionary-size snapshot,
    bucketed by the caller to stabilize compiled shapes). Returns (gid, num_groups)
    with num_groups = prod(cards); gid of a row with any out-of-range/negative code
    is clamped into range — callers must mask such rows out beforehand.
    """
    assert len(codes) == len(cards) and codes
    num_groups = 1
    for c in cards:
        num_groups *= int(c)
    gid = jnp.zeros_like(codes[0], dtype=jnp.int32)
    for code, card in zip(codes, cards):
        c = jnp.clip(code.astype(jnp.int32), 0, card - 1)
        gid = gid * card + c
    return gid, num_groups


def split_codes(gids: np.ndarray, cards: list[int]) -> list[np.ndarray]:
    """Host-side inverse of combine_codes: group id → per-key codes."""
    out = []
    rem = np.asarray(gids)
    for card in reversed(cards):
        out.append((rem % card).astype(np.int32))
        rem = rem // card
    return list(reversed(out))


#: Matmul-lowered segment sums are used on TPU up to this group count; the
#: one-hot chunk buffer is CHUNK_ROWS × groups × 4B (≤ 256 MB at the cap).
MATMUL_MAX_GROUPS = 1 << 10
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


def _use_matmul(n: int, num_groups: int) -> bool:
    return (
        dispatch_backend() == "tpu"
        and num_groups <= MATMUL_MAX_GROUPS
        and n >= 4096
        and (n % min(n, CHUNK_ROWS)) == 0
    )


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

    def chunk(xs):
        vv, gg = xs
        oh = jax.nn.one_hot(gg, num_groups, dtype=jnp.float32)
        return _lanes_gemm(lanes_fn(vv), oh, precision)

    return scan_sum(chunk, (v.reshape(c, ch), gid.reshape(c, ch)),
                    *live_chunks(mask, ch))


@jax.named_scope("px.groupby_sum")
def masked_segment_sum(values: jax.Array, gid: jax.Array, num_groups: int, mask: jax.Array):
    v = jnp.where(mask, values, jnp.zeros((), dtype=values.dtype))
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


@jax.named_scope("px.groupby_count")
def masked_segment_count(gid: jax.Array, num_groups: int, mask: jax.Array) -> jax.Array:
    """Rows per group (int64, exact): f32 one-hot matmul of the mask on TPU
    (per-chunk counts ≤ CHUNK_ROWS are exact in f32), scatter elsewhere."""
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
    return jax.ops.segment_min(v, gid, num_segments=num_groups)


@jax.named_scope("px.groupby_max")
def masked_segment_max(values: jax.Array, gid: jax.Array, num_groups: int, mask: jax.Array):
    small = _identity_for(values.dtype, "max")
    v = jnp.where(mask, values, small)
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
