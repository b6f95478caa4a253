"""Mergeable quantile sketch as a dense tensor.

Replaces the reference's t-digest UDA (src/carnot/funcs/builtins/math_sketches.h:34-49)
whose pointer-based centroid structure cannot live on a TPU. We use a DDSketch-style
log-bucketed histogram: fixed relative accuracy, fixed memory, and — crucially —
merge is elementwise addition, so distributed merge of per-device partial sketches
is a single `psum` over the mesh axis.

Layout per group: float32[NBINS + 2] — bin 0 counts values <= 0 ("zero bin"),
bins 1..NBINS count positive values by ceil(log_gamma(v)); the last bin absorbs
overflow. With gamma = 1.0404 and 512 bins the dynamic range is ~6.6e8 at ~2%
relative error, which covers latency-in-ns style telemetry after scaling.

Update formulations (`sketch_form` says which one a feed takes, from its
rows, its group count and the platform it is traced for; `update`
dispatches on it and the executor writes it on the chain's span):

  * `pallas` (TPU, up to SKETCH_KERNEL_MAX_GROUPS groups): the
    LIMB-FACTORED GEMM as one Pallas kernel.  The bin index factors into
    two limbs, ``bin = digit * 257 + lane``: the lane stays one-hot and
    the digit rides the GEMM *value* as a base-4096 digit (the same trick
    ops/groupby.py uses to sum int64 via 8-bit f32 limbs).  A narrow
    [257, CH] x [G, CH] GEMM a sub-block of CH rows unpacks into the two
    histogram halves with an exact divmod: HALF the MXU FLOPs of the
    514-wide one-hot at bit-equal counts.  Exactness: per-sub-block
    per-cell counts <= CHUNK (2048) occupy the low digit, 4096·count the
    high one; their sum stays < 2^23, exact in f32 MXU accumulation; 1.0
    and 4096.0 are exact in bf16, so bf16 operands with f32 accumulation
    stay exact at 2x the f32 MXU rate.  The histogram stays in VMEM across
    the kernel's grid and is written to HBM once; the grid visits the
    blocks that hold a live row (`groupby.live_chunks`) and no other.
  * `tiles` (TPU, past SKETCH_KERNEL_MAX_GROUPS): the packed (group, bin)
    key sorted once, values only, so that a tile of KERNEL_GROUP_TILE
    groups is one contiguous range of rows; then one Pallas kernel counts
    each tile's rows into that tile's state in VMEM with the same packed
    GEMM and exact unpack, over a scalar-prefetched schedule of (tile, row
    block) steps, and writes each tile once.  Each live row meets one
    tile: the MXU's work grows with the rows, not with rows x G.
  * `sorted` (XLA-CPU, high group count, mirrors the agg's sorted
    fallback): sort the flat (gid, bin) key — values only, no payload —
    and diff a searchsorted over the static G·W cell edges.  Model cost is
    O(n log n) comparisons with NO group factor.  The crossover is picked by
    measurement (`measure_update_crossover`), default from the measured
    CPU crossover (sorted ties segment_sum at G=128, wins 2.3x at G=1024),
    override via PX_SKETCH_SORT_MIN_GROUPS.  Never on the TPU: the Pallas
    kernel and the tiles kernel beat it 6-137x at every group count
    measured (PERF.md section 6).
  * `segment` (CPU, low group count, and what the others leave):
    XLA-CPU native scatter, unchanged.

All formulations produce identical histograms (tests/test_sketch_kernels.py
asserts bit-equality), and every one is an elementwise ADD into the state,
so the distributed merge stays a single psum by construction.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from pixie_tpu import flags

#: Sorted segment-count takes over from the dense formulations at this many
#: groups.  Measured on XLA-CPU (8M rows): sorted 1.27s vs segment_sum 1.31s
#: at G=128 (tie), 1.03s vs 2.40s at G=1024 (2.3x) — the sort has no group
#: factor, so the gap only widens.  Re-measure on new hardware with
#: `measure_update_crossover()`; override with this env flag.
SORT_MIN_GROUPS = flags.define_int(
    "PX_SKETCH_SORT_MIN_GROUPS", 0,
    "group count at which the sketch update switches from the dense "
    "(segment_sum) formulation to the sorted segment-count kernel on "
    "XLA-CPU; 0 = the fitted or measured default (512)")


def _sort_min_groups(backend: str) -> int:
    """Effective sorted-kernel crossover for `backend`, which is never the
    TPU (`sketch_form` takes the kernels there) — the flag when set, else a
    crossover fitted for `backend`, else the measured 512 (sorted ties
    segment_sum at G=128 and wins 2.3x at G=1024 on XLA-CPU)."""
    v = flags.get("PX_SKETCH_SORT_MIN_GROUPS")
    if v > 0:
        return v
    from pixie_tpu.engine import autotune as _autotune

    if _autotune.enabled():
        # kernel-choice model: measure_update_crossover feeds both kernels'
        # measured costs per group count into the model; once it has a
        # fitted crossover for this backend the hand-measured default
        # retires.  Model-only (no per-query probe): the dispatch is baked
        # into compiled programs at trace time.
        fitted = _autotune.MODEL.sketch_threshold(backend)
        if fitted is not None:
            return fitted
    return 512


#: The Pallas kernel serves every quantile sketch on the TPU up to this many
#: groups; the tiles kernel above (or the scatter, for a feed under 4,096
#: rows).  From the kernels alone (PERF.md section 6; one v5e chip,
#: an 8,388,608-row bucket, 4,587,520 live rows, ms): the kernel against
#: an XLA loop of one GEMM a 2,048-row chunk 2.85 / 13.40 at 128 groups,
#: 7.74 / 15.60 at 512, 14.84 / 19.04 at 1,024, 28.44 / 34.92 at 2,048 and
#: 55.83 / 72.19 at 4,096.
SKETCH_KERNEL_MAX_GROUPS = 4096
#: Past that cap the tiles kernel (`_update_tiles`) serves the TPU up to
#: this many groups: its packed key (`gid * KEY_BINS + bin`, and one past
#: the last tile for a masked row) must stay an int32.  From the kernels
#: alone (PERF.md section 6, as above, gids uniform), the tiles kernel
#: against the sorted form and the scatter: 20.8 / 771 / 84.8 ms at 8,192
#: groups, 21.5 / 1,537 / 85.0 at 16,384, 22.5 / 3,068 / 85.2 at 32,768 and
#: 24.4 / - / 86.2 at 65,536, of which its sort is 16.4; no crossover in
#: sight, so the cap is the key's.
SKETCH_TILES_MAX_GROUPS = (1 << 21) - 1024
#: The kernel's key packs the group id over the bin: bins < 1024.
KEY_SHIFT = 10
KEY_BINS = 1 << KEY_SHIFT
#: Sub-blocks of CHUNK rows a grid step of the kernel takes: 8, 16 and 32
#: read the same (PERF.md section 6).
KERNEL_BLOCK_SUBS = 8
#: Groups a one-hot tile of the kernel spans (the MXU's weights; the lane
#: side streams): 512 was the fastest of 128, 256 and 512 at every group
#: count from 512 up.
KERNEL_GROUP_TILE = 512
#: The kernel's VMEM: its [544, G] f32 state (8.9 MB at 4,096 groups),
#: double-buffered, and a sub-block's one-hots and iotas (~8 MB).
KERNEL_VMEM_BYTES = 64 << 20


def _call_per_shard(call, *operands):
    """`call(*operands, interpret=...)`, a kernel's pallas_call: compiled on
    the chip, interpreted off it.  The last operand (the rows) says over
    which mesh axes the operands vary under `jax.shard_map`.  Off the chip
    under a mesh (tests that trace for the TPU) the interpreter's loop is
    not typed for operands that vary over the mesh, and the TPU
    interpreter holds the mesh's devices at barriers that can deadlock
    against the program's own collectives.  So there each shard interprets
    every shard's kernel, over operands gathered by a psum (which makes
    them invariant), and keeps its own."""
    axes = tuple(sorted(jax.typeof(operands[-1]).vma))
    if jax.default_backend() == "tpu":
        return call(*operands, interpret=False)
    if not axes:
        return call(*operands, interpret=True)
    me = jax.lax.axis_index(axes)
    every = jax.lax.psum(jax.tree.map(lambda x: jnp.zeros(
        (jax.lax.psum(1, axes),) + x.shape, x.dtype).at[me].set(x),
        operands), axes)
    return jax.lax.pcast(jax.lax.map(
        lambda a: call(*a, interpret=True), every), axes, to="varying")[me]


def _lane_onehot(k, li, lanes: int, digit: float):
    """The lane side of a sub-block's packed GEMM, [LANES_P, CH] bf16: the
    one-hot of each row's lane (`li` its iota), scaled by the digit base
    where the row's bin sits in the upper half (1 and 4096 are exact in
    bf16).  `k` holds the rows' packed keys, `gid * KEY_BINS + bin`.  (No
    Python scalar in a select: with x64 on it would be a 64-bit operand,
    which Mosaic has not.)"""
    b = jnp.bitwise_and(k, KEY_BINS - 1)
    up = b >= lanes
    lane = jnp.where(up, b - lanes, b)
    val = jnp.where(up, jnp.full_like(lane, int(digit)),
                    jnp.ones_like(lane)).astype(jnp.float32)
    return jnp.where(li == lane, val, jnp.zeros_like(val)).astype(
        jnp.bfloat16)


def _tile_counts(ohb, hit, digit: float):
    """The (low digit, high digit) counts, [LANES_P, tile] f32 each, of one
    sub-block against one tile of groups (`hit`: [tile, CH], a row's group
    is the tile's i-th): the lane side streams through the MXU and the
    group tile is its weights, so the 257 lanes pad to 272, not 384.  A
    packed cell stays below 2^23, so the unpack is exact."""
    packed = jax.lax.dot_general(
        ohb, hit.astype(jnp.float32).astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    c_hi = jnp.floor(packed * (1.0 / digit))
    return packed - c_hi * digit, c_hi


def _loop32(n: int, body) -> None:
    """body(i) for i in [0, n), on an int32 counter.  (`lax.fori_loop`
    over static bounds counts in Python ints, which are int64 in a process
    with x64 on, and a Pallas TPU kernel has no 64-bit integers.)"""
    def step(i):
        body(i)
        return i + 1

    jax.lax.while_loop(lambda i: i < n, step, np.int32(0))


@dataclasses.dataclass(frozen=True)
class LogHistogram:
    nbins: int = 512
    gamma: float = 1.0404
    #: values below this are counted in the zero bin.
    min_value: float = 1e-9

    @property
    def width(self) -> int:
        return self.nbins + 2

    def _log_gamma(self):
        return math.log(self.gamma)

    @jax.named_scope("px.sketch_bin")
    def bin_index(self, v: jax.Array) -> jax.Array:
        """Bin index per value (device)."""
        lg = jnp.log(jnp.maximum(v.astype(jnp.float32), self.min_value)) / self._log_gamma()
        idx = jnp.ceil(lg).astype(jnp.int32) + 1  # +1: bin 0 is the zero bin
        idx = jnp.where(v <= self.min_value, 0, idx)
        return jnp.clip(idx, 0, self.width - 1)

    #: GEMM lanes: the 514 bins fold into width/2 lanes × 2 digits.
    LANES = 257
    #: base of the packed digit — per-chunk counts must stay below it so the
    #: two digits never carry into each other (CHUNK < DIGIT ⇒ exact).
    DIGIT = 4096
    #: rows per chunk for the limb-factored GEMM path.  Must be < DIGIT for
    #: exact digit separation; 2048 keeps the one-hot buffer small
    #: (chunk × 257 bf16) while the MXU contraction stays deep enough.
    CHUNK = 2048

    def update(
        self,
        hist: jax.Array,  # [num_groups, width]
        gid: jax.Array,
        values: jax.Array,
        mask: jax.Array,
        num_groups: int,
    ) -> jax.Array:
        """Add values into per-group histograms, in the form `sketch_form`
        says (module docstring).  All forms bit-equal."""
        form = sketch_form(gid.shape[0], num_groups, self.width)
        return getattr(self, "_update_" + form)(
            hist, gid, self.bin_index(values), mask, num_groups)

    @jax.named_scope("px.sketch_update_segment")
    def _update_segment(self, hist, gid, bins, mask, num_groups):
        """Flat scatter-add (XLA-CPU native path)."""
        flat_idx = gid.astype(jnp.int32) * self.width + bins
        ones = jnp.where(mask, 1.0, 0.0).astype(hist.dtype)
        add = jax.ops.segment_sum(ones, flat_idx, num_segments=num_groups * self.width)
        return hist + add.reshape(num_groups, self.width)

    @jax.named_scope("px.sketch_update_sorted")
    def _update_sorted(self, hist, gid, bins, mask, num_groups):
        """Sorted segment-count: values-only sort of the flat cell key, then
        per-cell counts from a searchsorted diff over the STATIC cell edges.

        No payload rides the sort and no G-wide one-hot is built, so the
        model cost is O(n log n) with no group factor — the high-group-count
        regime where the GEMM's rows × G × LANES term explodes.  Counts are
        computed as exact integers before the single f32 add into the state
        (the scatter formulations round progressively; this path can only be
        more exact, and is bit-equal at any count below 2^24).
        """
        ncell = num_groups * self.width
        flat = gid.astype(jnp.int32) * self.width + bins
        # masked rows get the one-past-the-end cell: they sort after every
        # real cell edge and fall out of the diff
        flat = jnp.where(mask, flat, ncell)
        s = jnp.sort(flat)
        edges = jnp.arange(ncell + 1, dtype=jnp.int32)
        bounds = jnp.searchsorted(s, edges, side="left")
        cnt = (bounds[1:] - bounds[:-1]).astype(hist.dtype)
        return hist + cnt.reshape(num_groups, self.width)

    @jax.named_scope("px.sketch_update_pallas")
    def _update_pallas(self, hist, gid, bins, mask, num_groups, *,
                       sub_rows=None, block_subs=None):
        """The limb-factored GEMM as one Pallas kernel (TPU; module
        docstring): a sub-block's packed GEMM, its exact unpack and the sum
        over sub-blocks all run in VMEM, and the histogram is written to HBM
        once.  A grid step takes a block of `block_subs` sub-blocks of
        `sub_rows` rows (CHUNK and KERNEL_BLOCK_SUBS unless a test asks for
        smaller); the steps past the live blocks (`live_chunks`,
        scalar-prefetched) repeat the last live block's index, so they
        fetch nothing and compute nothing.  The rows ride the lane axis:
        both one-hots are built from an iota compare, [LANES_P, CH] and
        [tile of G, CH], and contracted over their last axes.  The group id
        and bin travel packed in one int32 key (`gid * KEY_BINS + bin`, -1
        where masked): the bins are XLA's (`bin_index`), the kernel only
        takes them apart.  The state is [2 * LANES_P, G] (the two digits'
        halves, groups on the lanes) and is laid out as [G, width] after
        the kernel.  Counts, and so the histogram, are the other forms' bit
        for bit: each sub-block's packed cell is below 2^23 (sub_rows <=
        CHUNK < DIGIT) and is unpacked before it is added; a masked or
        padding row matches no group and adds zeros."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        from pixie_tpu.ops.groupby import live_chunks

        ch = sub_rows or self.CHUNK
        r = block_subs or KERNEL_BLOCK_SUBS
        tile = KERNEL_GROUP_TILE
        # groups on the state's lanes: a whole number of 128-lane tiles,
        # and of one-hot tiles past one
        step = tile if num_groups > tile else 128
        gp = -(-num_groups // step) * step
        tg = min(gp, tile)
        lanes, digit = self.LANES, float(self.DIGIT)
        lp = -(-lanes // 16) * 16  # bf16 rows come in sixteens
        n = gid.shape[0]
        subs = -(-n // ch)
        if subs <= r:
            r = subs  # one block: the whole (padded) feed
        subs = -(-subs // r) * r
        key = jnp.where(mask, gid.astype(jnp.int32) * KEY_BINS
                        + bins.astype(jnp.int32), -1)
        live = mask
        if subs * ch != n:
            # masked rows to a whole number of blocks: keys of -1 match no
            # group, so they add zeros
            key = jnp.concatenate([key, jnp.full(subs * ch - n, -1, jnp.int32)])
            live = jnp.concatenate([mask, jnp.zeros(subs * ch - n, bool)])
        lo, hi = live_chunks(live, r * ch)

        def kernel(lo_ref, hi_ref, key_ref, acc_ref):
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            @pl.when(lo_ref[0] + i < hi_ref[0])
            def _():
                gi = jax.lax.broadcasted_iota(jnp.int32, (tg, ch), 0)
                li = jax.lax.broadcasted_iota(jnp.int32, (lp, ch), 0)

                def sub(j):
                    k = key_ref[pl.ds(j, 1), :]  # [1, CH]
                    g = jnp.right_shift(k, KEY_SHIFT)  # -1 where masked
                    ohb = _lane_onehot(k, li, lanes, digit)

                    def tile_step(t):
                        at = pl.multiple_of(t * tg, tg)
                        c_lo, c_hi = _tile_counts(ohb, gi == g - at, digit)
                        acc_ref[:lp, pl.ds(at, tg)] += c_lo
                        acc_ref[lp:, pl.ds(at, tg)] += c_hi

                    _loop32(gp // tg, tile_step)

                _loop32(r, sub)

        def call(lo, hi, key, interpret):
            return pl.pallas_call(
                kernel,
                # (under `jax.shard_map` the state varies over the mesh axes
                # the rows do: each shard counts its own)
                out_shape=jax.ShapeDtypeStruct((2 * lp, gp), jnp.float32,
                                               vma=jax.typeof(key).vma),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=2,
                    grid=(subs // r,),
                    in_specs=[pl.BlockSpec(
                        (r, ch), lambda i, lo, hi: (
                            jnp.minimum(lo[0] + i, hi[0] - 1), np.int32(0)))],
                    out_specs=pl.BlockSpec((2 * lp, gp), lambda i, lo, hi: (
                        np.int32(0), np.int32(0))),
                ),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",),
                    vmem_limit_bytes=KERNEL_VMEM_BYTES),
                interpret=interpret,
                name="px_sketch_hist",
            )(lo.reshape(1), hi.reshape(1), key.reshape(subs, ch))

        acc = _call_per_shard(call, lo, hi, key)
        add = jnp.concatenate([acc[:lanes], acc[lp:lp + lanes]], axis=0)
        return hist + add[:self.width, :num_groups].T.astype(hist.dtype)

    @jax.named_scope("px.sketch_update_tiles")
    def _update_tiles(self, hist, gid, bins, mask, num_groups, *,
                      sub_rows=None, block_subs=None):
        """Past the kernel's group cap (TPU; module docstring): the packed
        (group, bin) key sorted once, values only, so that the rows of a
        tile of KERNEL_GROUP_TILE groups are one contiguous range; then one
        Pallas kernel counts each tile's rows into that tile's [544, tile]
        state in VMEM with `_update_pallas`'s packed-digit GEMM and exact
        unpack, and writes each tile once.  Its grid is a schedule of
        (tile, row block) steps, scalar-prefetched: each tile's blocks from
        the one that holds its first row to the one that holds its last,
        one step for a tile with no row (it writes zeros), and the steps
        past the schedule's end repeat its last one and compute nothing.
        A block's rows of a neighbouring tile, and the masked rows (sorted
        past every tile), match no group of the step's tile and add zeros.
        So the MXU's work grows with the rows (each live row meets one
        tile), not with rows x G, and the search is over G / tile + 1 tile
        edges, not over the G x 514 cells the `sorted` form diffs.  Bit
        for bit the other forms' histogram, as `_update_pallas` is."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        ch = sub_rows or self.CHUNK
        r = block_subs or KERNEL_BLOCK_SUBS
        tg = KERNEL_GROUP_TILE
        tiles = -(-num_groups // tg)
        lanes, digit = self.LANES, float(self.DIGIT)
        lp = -(-lanes // 16) * 16  # bf16 rows come in sixteens
        n = gid.shape[0]
        blocks = -(-n // (r * ch))
        pad = blocks * r * ch - n
        past = tiles * tg * KEY_BINS  # sorts after every tile's keys
        key = jnp.where(mask, gid.astype(jnp.int32) * KEY_BINS
                        + bins.astype(jnp.int32), past)
        if pad:
            key = jnp.concatenate([key, jnp.full(pad, past, jnp.int32)])
        key = jnp.sort(key)
        edges = jnp.arange(tiles + 1, dtype=jnp.int32) * (tg * KEY_BINS)
        bounds = jnp.searchsorted(key, edges, side="left").astype(jnp.int32)
        first = jnp.minimum(bounds[:-1] // (r * ch), blocks - 1)
        empty = bounds[1:] == bounds[:-1]
        last = jnp.where(empty, first, (bounds[1:] - 1) // (r * ch))
        count = last - first + 1  # a tile's steps
        ends = jnp.cumsum(count)
        steps = blocks + tiles  # at least the schedule's length
        i = jnp.arange(steps, dtype=jnp.int32)
        tile = jnp.minimum(jnp.searchsorted(ends, i, side="right"), tiles - 1)
        off = i - (ends[tile] - count[tile])
        live = i < ends[-1]
        block = jnp.where(live, first[tile] + off, last[-1]).astype(jnp.int32)
        # bit 0: the step opens its tile (zero the state); bit 1: it counts
        flag = (jnp.where(live & (off == 0), 1, 0)
                + jnp.where(live & ~empty[tile], 2, 0)).astype(jnp.int32)
        tile = tile.astype(jnp.int32)

        def kernel(tile_ref, block_ref, flag_ref, key_ref, acc_ref):
            f = flag_ref[pl.program_id(0)]
            at = tile_ref[pl.program_id(0)] * tg

            @pl.when(jnp.bitwise_and(f, 1) == 1)
            def _():
                acc_ref[...] = jnp.zeros_like(acc_ref)

            @pl.when(jnp.bitwise_and(f, 2) == 2)
            def _():
                gi = jax.lax.broadcasted_iota(jnp.int32, (tg, ch), 0)
                li = jax.lax.broadcasted_iota(jnp.int32, (lp, ch), 0)

                def sub(j):
                    k = key_ref[pl.ds(j, 1), :]  # [1, CH]
                    c_lo, c_hi = _tile_counts(
                        _lane_onehot(k, li, lanes, digit),
                        gi == jnp.right_shift(k, KEY_SHIFT) - at, digit)
                    acc_ref[:lp, :] += c_lo
                    acc_ref[lp:, :] += c_hi

                _loop32(r, sub)

        def call(tile, block, flag, key, interpret):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct((2 * lp, tiles * tg),
                                               jnp.float32,
                                               vma=jax.typeof(key).vma),
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=3,
                    grid=(steps,),
                    in_specs=[pl.BlockSpec(
                        (r, ch), lambda i, t, b, f: (b[i], np.int32(0)))],
                    out_specs=pl.BlockSpec(
                        (2 * lp, tg), lambda i, t, b, f: (np.int32(0), t[i])),
                ),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("arbitrary",),
                    vmem_limit_bytes=KERNEL_VMEM_BYTES),
                interpret=interpret,
                name="px_sketch_tiles",
            )(tile, block, flag, key.reshape(blocks * r, ch))

        acc = _call_per_shard(call, tile, block, flag, key)
        add = jnp.concatenate([acc[:lanes], acc[lp:lp + lanes]], axis=0)
        return hist + add[:self.width, :num_groups].T.astype(hist.dtype)

    def init(self, num_groups: int, dtype=jnp.float32) -> jax.Array:
        return jnp.zeros((num_groups, self.width), dtype=dtype)

    # merge == elementwise add (psum-compatible); no method needed.

    def bin_value(self, idx: np.ndarray) -> np.ndarray:
        """Representative value of a bin (host): geometric mean of bin bounds."""
        i = np.asarray(idx, dtype=np.float64) - 1.0
        val = np.power(self.gamma, i - 0.5)
        return np.where(np.asarray(idx) <= 0, 0.0, val)

    def bin_values(self, idx: np.ndarray) -> np.ndarray:
        """Bin indices as `quantile_bins` or `quantile_bins_device` give
        them → quantile values (host): NaN for an empty group's -1."""
        idx = np.asarray(idx)
        return np.where(idx < 0, np.nan, self.bin_value(idx))

    def quantile_bins(self, hist: np.ndarray, qs: list[float]) -> np.ndarray:
        """Host rank rule: [G, width] → [G, len(qs)] int32, per group and q
        the first bin whose cumulative count reaches q * total (in f64),
        -1 for an empty group."""
        h = np.asarray(hist, dtype=np.float64)
        totals = h.sum(axis=-1)
        cum = np.cumsum(h, axis=-1)
        out = np.empty((h.shape[0], len(qs)), dtype=np.int32)
        for j, q in enumerate(qs):
            target = np.clip(q, 0.0, 1.0) * totals
            # Per-row searchsorted: first bin where cum >= target.
            idx = (cum < target[:, None]).sum(axis=-1)
            out[:, j] = np.minimum(idx, h.shape[1] - 1)
        out[totals == 0] = -1
        return out

    def quantile(self, hist: np.ndarray, qs: list[float]) -> np.ndarray:
        """Host-side finalize: quantiles per group. hist: [G, width] → [G, len(qs)]."""
        return self.bin_values(self.quantile_bins(hist, qs))

    def quantile_bins_device(self, hist: jax.Array,
                             qs: list[float]) -> jax.Array:
        """DEVICE finalize: `quantile_bins` on the device, the same bins
        for every group of fewer than 2^31 rows: [G, width] → [G, len(qs)]
        int32.  The histogram is the big part of an agg's state ([G, 514]
        f32, per sketch) while the [G, nq] bins are kilobytes: reading
        back these instead of the state is one small wave.  The host turns
        them into values (`bin_values`), so a value is the host's own to
        the bit (XLA's f64 power is not numpy's in the last place).

        The cumulative counts are int32, exact to 2^31 rows a group (f32
        is exact only to 2^24), and the host's `cum < q * total` over
        integer counts is `cum < _ceil_rank(q, total)`, exactly."""
        cum = jnp.cumsum(hist.astype(jnp.int32), axis=-1)
        totals = cum[:, -1]
        rank = jnp.stack([_ceil_rank(q, totals) for q in qs], axis=-1)
        idx = (cum[:, None, :] < rank[:, :, None]).sum(axis=-1,
                                                       dtype=jnp.int32)
        idx = jnp.minimum(idx, hist.shape[-1] - 1)
        return jnp.where(totals[:, None] > 0, idx, -1)


def _ceil_rank(q: float, totals: jax.Array) -> jax.Array:
    """ceil(clip(q, 0, 1) * total) where the product is the f64 one, rounded
    to nearest as the host's is, in integer arithmetic alone: [G] int32 →
    [G] int32.  q is n / 2**k exactly (a float is a dyadic rational), so
    q * total is n * total / 2**k, whose whole part and fraction are
    integers; the f64 product is the whole part where the fraction is at
    most half an f64 ulp of it (ties go to the even whole part), and above
    it otherwise."""
    n, d = min(max(float(q), 0.0), 1.0).as_integer_ratio()
    k = d.bit_length() - 1
    if k >= 96:  # q * total < 2**-12: every nonempty group's rank is 1
        return (totals > 0).astype(jnp.int32)
    t = totals.astype(jnp.uint64)
    if k < 32:  # n <= 2**k: the product fits in 63 bits
        prod = t * np.uint64(n)
        whole = prod >> np.uint64(k)
        frac_hi = jnp.zeros_like(prod)
        frac_lo = prod & np.uint64((1 << k) - 1)
    else:  # the product is hi * 2**32 + lo mod 2**32, hi < 2**53
        lo = t * np.uint64(n & 0xFFFFFFFF)
        hi = t * np.uint64(n >> 32) + (lo >> np.uint64(32))
        whole = hi >> np.uint64(k - 32)
        frac_hi = hi & np.uint64((1 << (k - 32)) - 1)
        frac_lo = lo & np.uint64(0xFFFFFFFF)
    # half an ulp of whole (< 2**31) is 2**(e - 53), e = floor(log2(whole)):
    # 2**s in units of 2**-k; any fraction lifts a whole part of 0
    e = 31 - jax.lax.clz(whole.astype(jnp.uint32)).astype(jnp.int32)
    s = jnp.where(whole > 0, k + e - 53, -1)
    one = jnp.uint64(1)
    bit_lo = jnp.left_shift(one, jnp.clip(s, 0, 31).astype(jnp.uint64))
    bit_hi = jnp.left_shift(one, jnp.clip(s - 32, 0, 63).astype(jnp.uint64))
    above = jnp.where(
        s < 0, (frac_hi > 0) | (frac_lo > 0),
        jnp.where(s < 32, (frac_hi > 0) | (frac_lo > bit_lo),
                  (frac_hi > bit_hi) | ((frac_hi == bit_hi) & (frac_lo > 0))))
    return (whole + above.astype(jnp.uint64)).astype(jnp.int32)


def sketch_form(n: int, num_groups: int,
                width: int = LogHistogram().width) -> str:
    """The form in which `LogHistogram.update`, traced now, adds `n` rows
    (one shard's, under a mesh) into `num_groups` histograms of `width`
    bins: `pallas`, `tiles`, `sorted` or `segment` (module docstring).  On
    the TPU, from 4,096 rows, it is `pallas` up to SKETCH_KERNEL_MAX_GROUPS
    groups and `tiles` past it up to SKETCH_TILES_MAX_GROUPS; the sorted
    form's crossover governs XLA-CPU alone.  It reads the rows, the group
    count and the platform the kernels are traced for and nothing else;
    `update` dispatches on it and the executor writes it on the chain's
    span (`sketch_form`), so the span says what the program does."""
    from pixie_tpu.ops.groupby import dispatch_backend

    backend = dispatch_backend()
    if backend == "tpu":
        if n >= 4096 and num_groups <= SKETCH_KERNEL_MAX_GROUPS:
            return "pallas"
        if n >= 4096 and num_groups <= SKETCH_TILES_MAX_GROUPS:
            return "tiles"
        return "segment"
    if (num_groups >= _sort_min_groups(backend) and n >= (1 << 14)
            and num_groups * width <= 4 * n):
        # the cell-edge diff costs O(G·W): only worth it while the cell
        # space stays comparable to the row count
        return "sorted"
    return "segment"


def measure_update_crossover(n: int = 1 << 21, groups=(128, 256, 512, 1024),
                             repeats: int = 3) -> dict:
    """Measure the dense-vs-sorted sketch-update crossover ON THIS BACKEND.

    Times the dense formulation (the Pallas kernel on TPU dispatch,
    segment_sum on CPU)
    against the sorted segment-count kernel at each group count and returns
    {"backend", "points": {G: {"dense_ms", "sorted_ms"}}, "crossover":
    smallest measured G where sorted wins}.  The default
    PX_SKETCH_SORT_MIN_GROUPS was picked from exactly this measurement;
    re-run on new hardware and override the flag if the crossover moved.
    """
    import time

    from pixie_tpu.ops.groupby import dispatch_backend

    lh = LogHistogram()
    rng = np.random.default_rng(7)
    gidh = rng.integers(0, max(groups), n)
    vals = jax.device_put(rng.exponential(50.0, n))
    mask = jax.device_put(np.ones(n, dtype=bool))
    backend = dispatch_backend()
    bins = lh.bin_index(vals)
    points = {}
    crossover = None
    for g in sorted(groups):
        gid = jax.device_put((gidh % g).astype(np.int32))
        hist = lh.init(g)
        dense_form = "pallas" if backend == "tpu" else "segment"
        dense = jax.jit(lambda h, i, b, m: getattr(
            lh, "_update_" + dense_form)(h, i, b, m, g))
        srt = jax.jit(lambda h, i, b, m: lh._update_sorted(h, i, b, m, g))
        out = {}
        for name, fn in (("dense_ms", dense), ("sorted_ms", srt)):
            jax.block_until_ready(fn(hist, gid, bins, mask))  # compile
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(hist, gid, bins, mask))
                ts.append(time.perf_counter() - t0)
            out[name] = round(sorted(ts)[len(ts) // 2] * 1000, 1)
        points[g] = out
        if crossover is None and out["sorted_ms"] < out["dense_ms"]:
            crossover = g
        from pixie_tpu.engine import autotune as _autotune

        if _autotune.enabled():
            # each measured point feeds the kernel-choice model: once every
            # probed group count is warm, _sort_min_groups serves the
            # fitted crossover instead of the hand-measured default
            for _ in range(int(flags.get("PX_AUTOTUNE_MIN_SAMPLES"))):
                _autotune.MODEL.observe_sketch(
                    backend, g, out["dense_ms"], out["sorted_ms"])
    return {"backend": backend, "rows": n, "points": points,
            "crossover": crossover}
