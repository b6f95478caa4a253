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

Update formulations (the FLOP bulk of a quantile query is this histogram
scatter — rows × groups × width on the old full-width one-hot GEMM):

  * LIMB-FACTORED GEMM (TPU, low group count): the bin index factors into
    two limbs, ``bin = digit * 257 + lane`` — the lane stays one-hot and the
    digit rides the GEMM *value* as a base-4096 digit (the same trick
    ops/groupby.py uses to sum int64 via 8-bit f32 limbs).  One narrow
    [G,CH]@[CH,257] GEMM then unpacks into the two histogram halves with an
    exact divmod — HALF the MXU FLOPs of the 514-wide one-hot at bit-equal
    counts.  Exactness: per-chunk per-cell counts ≤ CHUNK (2048) occupy the
    low digit, 4096·count the high one; their sum stays < 2^23, exact in
    f32 MXU accumulation; 1.0 and 4096.0 are exact in bf16, so bf16
    operands with f32 accumulation stay exact at 2x the f32 MXU rate.
  * SORTED SEGMENT-COUNT (high group count, mirrors the agg's sorted
    fallback): sort the flat (gid, bin) key — values only, no payload — and
    diff a searchsorted over the static G·W cell edges.  Model cost is
    O(n log n) comparisons with NO group factor, vs rows × G × 257 GEMM
    MACs: the win grows linearly in G.  The crossover is picked by
    measurement (`measure_update_crossover`), default from the measured
    CPU crossover (sorted ties segment_sum at G=128, wins 2.3x at
    G=1024), override via PX_SKETCH_SORT_MIN_GROUPS.
  * segment_sum (CPU, low group count): XLA-CPU native scatter, unchanged.

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
    "(GEMM/segment_sum) formulation to the sorted segment-count kernel; "
    "0 = measured per-backend default (512 on CPU, 4097 on TPU)")


def _sort_min_groups(backend: str) -> int:
    """Effective sorted-kernel crossover for `backend` — the flag when set,
    else the measured default: 512 on CPU (sorted ties segment_sum at G=128
    and wins 2.3x at G=1024), 4097 on TPU (the narrow GEMM is MXU-bound and
    beats the bitonic sort up to its 4096-group cap; beyond the cap the old
    code fell back to the serialized scatter, which the sort replaces)."""
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
    return 4097 if backend == "tpu" else 512


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
        """Add values into per-group histograms.

        Formulation dispatch (see module docstring): limb-factored GEMM on
        TPU at low group counts, sorted segment-count above the measured
        crossover, segment_sum otherwise.  All paths bit-equal.
        """
        n = gid.shape[0]
        bins = self.bin_index(values)
        from pixie_tpu.ops.groupby import dispatch_backend

        backend = dispatch_backend()
        if (num_groups >= _sort_min_groups(backend) and n >= (1 << 14)
                and num_groups * self.width <= 4 * n):
            # the cell-edge diff costs O(G·W): only worth it while the cell
            # space stays comparable to the row count
            return self._update_sorted(hist, gid, bins, mask, num_groups)
        if backend == "tpu" and num_groups <= 4096 and n >= 4096:
            return self._update_gemm(hist, gid, bins, mask, num_groups)
        return self._update_segment(hist, gid, bins, mask, num_groups)

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

    @jax.named_scope("px.sketch_update_gemm")
    def _update_gemm(self, hist, gid, bins, mask, num_groups):
        """Limb-factored one-hot GEMM (TPU): bin = digit·LANES + lane; the
        lane is one-hot, the digit is the VALUE (1 or DIGIT) — one narrow
        [G,CH]@[CH,LANES] MXU GEMM per chunk, then an exact divmod unpack
        into the histogram halves.  Half the MXU FLOPs of the full-width
        one-hot (LANES = width/2) at bit-equal counts.

        The chunk loop visits the chunks from the first that holds a live
        row of `mask` to the last (groupby.scan_sum, live_chunks), not the
        whole pow2 bucket: a chunk of masked rows has an all-zero group
        one-hot, so its packed GEMM and both unpacked digits are exact
        zeros and the histogram is the all-chunks loop's bit for bit."""
        n = gid.shape[0]
        ch = min(n, self.CHUNK)
        if n % ch:
            # pad to a whole number of chunks with masked-out rows — zero
            # contributions, so exactness and bit-equality are unaffected
            pad = ch - n % ch
            gid = jnp.concatenate([gid, jnp.zeros(pad, gid.dtype)])
            bins = jnp.concatenate([bins, jnp.zeros(pad, bins.dtype)])
            mask = jnp.concatenate([mask, jnp.zeros(pad, mask.dtype)])
            n += pad
        g32 = gid.astype(jnp.int32)
        c = n // ch
        digit = jnp.float32(self.DIGIT)

        def gemm(gg, bb, mm):
            # group side: exact {0,1} bf16 one-hot, masked
            ohg = jax.nn.one_hot(gg, num_groups,
                                 dtype=jnp.bfloat16) * mm[:, None]
            lane = bb % self.LANES
            hi = (bb // self.LANES).astype(jnp.bfloat16)
            # lane side: one-hot scaled by the digit base when the bin sits
            # in the upper half — 1.0 and 4096.0 are both exact in bf16
            val = jnp.float32(1.0) + hi.astype(jnp.float32) * (digit - 1.0)
            ohb = jax.nn.one_hot(lane, self.LANES,
                                 dtype=jnp.bfloat16) * val.astype(
                                     jnp.bfloat16)[:, None]
            packed = jax.lax.dot_general(
                ohg, ohb, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [G, LANES]
            # exact unpack: packed = c_lo + DIGIT * c_hi with
            # c_lo, c_hi <= CHUNK < DIGIT and packed < 2^23
            c_hi = jnp.floor(packed / digit)
            c_lo = packed - c_hi * digit
            return jnp.concatenate([c_lo, c_hi], axis=1)[:, :self.width]

        from pixie_tpu.ops.groupby import live_chunks, scan_sum

        mb = jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)
        return hist + scan_sum(
            lambda xs: gemm(*xs).astype(hist.dtype),
            (g32.reshape(c, ch), bins.reshape(c, ch), mb.reshape(c, ch)),
            *live_chunks(mask, ch))

    def init(self, num_groups: int, dtype=jnp.float32) -> jax.Array:
        return jnp.zeros((num_groups, self.width), dtype=dtype)

    # merge == elementwise add (psum-compatible); no method needed.

    def bin_value(self, idx: np.ndarray) -> np.ndarray:
        """Representative value of a bin (host): geometric mean of bin bounds."""
        i = np.asarray(idx, dtype=np.float64) - 1.0
        val = np.power(self.gamma, i - 0.5)
        return np.where(np.asarray(idx) <= 0, 0.0, val)

    def quantile(self, hist: np.ndarray, qs: list[float]) -> np.ndarray:
        """Host-side finalize: quantiles per group. hist: [G, width] → [G, len(qs)]."""
        h = np.asarray(hist, dtype=np.float64)
        totals = h.sum(axis=-1, keepdims=True)
        cum = np.cumsum(h, axis=-1)
        out = np.empty((h.shape[0], len(qs)), dtype=np.float64)
        for j, q in enumerate(qs):
            target = np.clip(q, 0.0, 1.0) * totals[:, 0]
            # Per-row searchsorted: first bin where cum >= target.
            idx = (cum < target[:, None]).sum(axis=-1)
            idx = np.minimum(idx, h.shape[1] - 1)
            out[:, j] = self.bin_value(idx)
        out[totals[:, 0] == 0] = np.nan
        return out

    def quantile_device(self, hist: jax.Array, qs: list[float]) -> jax.Array:
        """DEVICE finalize (same rank rule as `quantile`): [G, width] →
        [G, len(qs)] f64.  Rationale: the histogram is the big part of an
        agg's state ([G, 514] f32 — ~2 MB at G≈1024, per sketch, per feed)
        while the [G, nq] RESULT is kilobytes: reading back the result
        instead of the state is one small wave, so finalize belongs
        device-side.
        """
        # f32 for the [G, width] cumsum/compare (TPU f64 is software-emulated
        # and a f64 cumsum becomes a serialized scan — measured ~4x
        # whole-query regression).  The final power runs in f64 over the
        # tiny [G, nq] result, matching the host finalize (`quantile`)
        # exactly while group counts stay below 2^24 (above that, f32
        # cum/target rounding near a rank boundary can pick the adjacent
        # bin — a sub-bucket-width deviation).
        h = hist.astype(jnp.float32)
        totals = h.sum(axis=-1, keepdims=True)
        cum = jnp.cumsum(h, axis=-1)
        qv = jnp.asarray(qs, dtype=jnp.float32)
        target = jnp.clip(qv, 0.0, 1.0)[None, :] * totals  # [G, nq]
        idx = (cum[:, None, :] < target[:, :, None]).sum(axis=-1)
        idx = jnp.minimum(idx, h.shape[-1] - 1)
        val = jnp.power(jnp.float64(self.gamma),
                        idx.astype(jnp.float64) - 1.5)
        out = jnp.where(idx <= 0, 0.0, val)
        return jnp.where(totals > 0, out, jnp.nan)


def measure_update_crossover(n: int = 1 << 21, groups=(128, 256, 512, 1024),
                             repeats: int = 3) -> dict:
    """Measure the dense-vs-sorted sketch-update crossover ON THIS BACKEND.

    Times the dense formulation (GEMM on TPU dispatch, segment_sum on CPU)
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
        if backend == "tpu":
            dense = jax.jit(lambda h, i, b, m: lh._update_gemm(h, i, b, m, g))
        else:
            dense = jax.jit(lambda h, i, b, m: lh._update_segment(h, i, b, m, g))
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
