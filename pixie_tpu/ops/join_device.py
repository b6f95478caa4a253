"""Device equijoin kernels: radix-bucketed match/expand on the dispatch device.

Reference: exec/equijoin_node.h builds a hash table and probes it row by
row.  A row-at-a-time hash build/probe is hostile to accelerators (pointer
chasing, dynamic growth); r5 measured that the first TPU-native shape — one
full-width stable argsort with an iota payload + global searchsorteds — is
hostile too (868K rows/s at 16M x 16M: the variadic comparator sort and the
DRAM-random binary searches dominate).  This round reshapes the kernel for
the hardware (Flare/Tailwind's lesson in PAPERS.md):

  * RADIX-PACKED PARTITION SORT: each side packs ``code << idx_bits | row``
    into ONE int64 and a values-only sort both radix-partitions the rows
    (the key's high bits are the bucket) and orders every bucket — no
    payload tensor rides the sort (measured 10x cheaper than stable argsort
    on XLA-CPU, half the shuffled bytes on a TPU bitonic sort), and the
    original row index is a mask away.
  * PER-BUCKET MATCH + EXPAND: B = pow2 buckets sliced out of the sorted
    arrays; each bucket builds a bucket-local first-position LUT
    (scatter-min over its dense local code span + a reverse min-scan), so
    probe lookups are cache-shaped gathers, and expands its pairs with a
    boundary-scatter cumsum — all shapes pow2-padded so compiled kernels
    are reused across buckets; buckets dispatch over a small thread pool.
  * NATIVE CPU KERNEL: when the dispatch device IS XLA-CPU the buffer is
    host memory, so the honest device kernel is the pthread radix hash join
    in native/join.cc running zero-copy on the same bytes (measured ~10x
    the XLA formulation at 16M x 16M).  Accelerator backends always use the
    XLA path.

Gate: PX_DEVICE_JOIN is AUTO by default (-1).  Mechanism: the join's
partitions are host-resident, so the device kernel pays their upload; over
a slow host->device link that upload costs more than the host match.  The
executor asks `device_join_gate()`, which measures H2D bandwidth once per
process (`engine/transfer.h2d_bandwidth_probe`, the upload sibling of
`wave_rtt_floor`) and enables the device path when the link reaches
PX_DEVICE_JOIN_MIN_H2D_MBPS (or when the CPU-native kernel applies, where
there is no upload at all).  The threshold is unverified on the current
chip (ROADMAP S2/S4).  The probe result and decision are recorded in
`stats["device"]` and as px_* gauges, so the gate is observable, not silent.
"""
from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from pixie_tpu import flags

DEVICE_JOIN = flags.define_int(
    "PX_DEVICE_JOIN", -1,
    "-1 = auto (measured H2D probe on accelerators; native kernel on CPU), "
    "0 = force host match, 1 = force device kernel")

MIN_H2D_MBPS = flags.define_int(
    "PX_DEVICE_JOIN_MIN_H2D_MBPS", 1000,
    "auto-gate threshold: enable the accelerator join when the measured "
    "host->device bandwidth (MB/s) reaches this")

#: rows per radix bucket for the XLA kernel (B = pow2 covering n/this)
_BUCKET_TARGET_ROWS = 1 << 17
_MAX_BUCKETS = 1 << 12
#: below this many rows per side the flat (1-bucket) shape is used
_MIN_BUCKETED_ROWS = 1 << 18


from pixie_tpu.ops.groupby import next_pow2 as _next_pow2


def _bucket_count(nb: int, npr: int) -> int:
    """Radix bucket count for one join shape — shared by the kernel and the
    caller's LUT-size guard so the two can never drift."""
    if max(nb, npr) < _MIN_BUCKETED_ROWS:
        return 1
    return min(_next_pow2((nb + npr) // _BUCKET_TARGET_ROWS), _MAX_BUCKETS)


# ----------------------------------------------------------- legacy kernel
# Full-width argsort + searchsorted formulation (r4).  Kept as the fallback
# for code spaces too wide to radix-pack (arbitrary raw int64 keys — the
# executor's unique-inverse codes always pack) and for its unit tests.


@jax.jit
def match_ranges(build_codes: jax.Array, probe_codes: jax.Array):
    """Sorted-join phase 1 (legacy full-width form).

    Returns (order, lo, hi, total):
      order: argsort of build_codes (maps sorted position → original row)
      lo/hi: per-probe-row match range [lo, hi) into the SORTED build side
      total: Σ (hi - lo) — the number of matched pairs
    """
    order = jnp.argsort(build_codes, stable=True)
    skey = build_codes[order]
    lo = jnp.searchsorted(skey, probe_codes, side="left")
    hi = jnp.searchsorted(skey, probe_codes, side="right")
    return order, lo, hi, jnp.sum((hi - lo).astype(jnp.int64))


@partial(jax.jit, static_argnames=("total",))
def _expand(order, lo, counts, total):
    starts = jnp.cumsum(counts) - counts  # exclusive prefix
    # pair p belongs to the probe row r with starts[r] <= p; its slot
    # within the run is p - starts[r]
    p = jnp.arange(total, dtype=jnp.int64)
    r = jnp.searchsorted(starts, p, side="right") - 1
    slot = p - starts[r]
    bpos = lo[r] + slot
    return order[bpos], r


def expand_pairs(order, lo, hi, total: int):
    """Sorted-join phase 2 (static `total` from phase 1's pulled scalar):
    → (build_idx[total], probe_idx[total]) original-row gather indices."""
    if total == 0:
        return (jnp.zeros((0,), jnp.int64), jnp.zeros((0,), jnp.int64))
    return _expand(order, lo, hi - lo, total)


def _legacy_join_codes(b, p):
    from pixie_tpu.engine import transfer

    order, lo, hi, total = match_ranges(b, p)
    total = int(total)
    bidx_d, pidx_d = expand_pairs(order, lo, hi, total)
    bidx, pidx = transfer.pull([bidx_d, pidx_d])
    return np.asarray(bidx), np.asarray(pidx)


# ----------------------------------------------------- bucketed XLA kernel


@partial(jax.jit, static_argnames=("ib", "pad"))
def _pack_sort(codes, ib, pad):
    """Radix-packed values-only partition sort of one side.

    key = code << ib | row: the sort groups equal codes (high bits) and the
    original row rides the low bits — no payload operand.  `pad` sentinel
    rows (MAX key) let per-bucket pow2 slices read past the end safely.
    """
    n = codes.shape[0]
    k = (codes.astype(jnp.int64) << ib) | jnp.arange(n, dtype=jnp.int64)
    s = jnp.sort(k)
    if pad:
        s = jnp.concatenate([s, jnp.full((pad,), jnp.int64(2) ** 62,
                                         jnp.int64)])
    return s


@partial(jax.jit, static_argnames=("extra",))
def _append_pad(s, extra):
    """Grow the sentinel tail (rare: a heavily skewed bucket whose pow2 cap
    overruns the standard pad)."""
    return jnp.concatenate([s, jnp.full((extra,), jnp.int64(2) ** 62,
                                        jnp.int64)])


@partial(jax.jit, static_argnames=("cap_b", "cap_p", "kloc", "ib"))
def _bucket_match(sb, sp, bs, nb, ps, npr, c0, cap_b, cap_p, kloc, ib):
    """Match one bucket: per-probe-slot (count, lo) into the bucket's sorted
    build slice, via a bucket-local dense first-position LUT.

    The LUT (`offf[c]` = first sorted position with local code ≥ c) comes
    from a scatter-min of positions + a reverse min-scan — both over the
    bucket's own code span, so the working set is cache-sized.  Pads carry
    local code `kloc`, which lands in the LUT's boundary slot and cannot
    produce counts (their probe slots are masked).
    """
    bsl = jax.lax.dynamic_slice(sb, (bs,), (cap_b,))
    psl = jax.lax.dynamic_slice(sp, (ps,), (cap_p,))
    vp = jnp.arange(cap_p) < npr
    bc = jnp.minimum((bsl >> ib) - c0, kloc).astype(jnp.int32)
    pc = jnp.where(vp, jnp.minimum((psl >> ib) - c0, kloc),
                   kloc).astype(jnp.int32)
    off = jnp.full((kloc + 2,), cap_b, jnp.int32).at[bc].min(
        jnp.arange(cap_b, dtype=jnp.int32), mode="drop")
    off = off.at[kloc + 1].min(jnp.int32(nb))
    offf = jax.lax.associative_scan(jnp.minimum, off, reverse=True)
    cnt_by_code = offf[1:] - offf[:-1]
    cntP = jnp.where(vp, cnt_by_code[pc], 0)
    return cntP.astype(jnp.int32), offf[pc].astype(jnp.int32), jnp.sum(
        cntP.astype(jnp.int64))


@partial(jax.jit, static_argnames=("cap_t", "ib"))
def _bucket_expand(cntP, loP, sb, sp, bs, ps, total, cap_t, ib):
    """Expand one bucket's (count, lo) ranges into original-row pairs.

    Probe-run boundaries scatter 1s at each run start (indices are sorted —
    the starts cumsum is monotone) and a cumsum recovers the probe slot per
    pair; the build row then sits `j` past the run's first sorted position.
    Both original indices are the packed keys' low bits — no order arrays.
    """
    starts = jnp.cumsum(cntP) - cntP
    z = jnp.zeros((cap_t,), jnp.int32).at[starts].add(
        1, mode="drop", indices_are_sorted=True)
    r = jnp.cumsum(z) - 1
    pos = jnp.arange(cap_t, dtype=jnp.int32)
    valid = pos < total
    rr = jnp.where(valid, r, 0)
    j = pos - starts[rr]
    spos = loP[rr] + j
    mask = (jnp.int64(1) << ib) - 1
    bidx = sb[bs + spos] & mask
    pidx = sp[ps + rr] & mask
    return jnp.where(valid, bidx, -1), jnp.where(valid, pidx, -1)


def _xla_bucketed_join(b, p, max_code: int, nthreads: int | None = None):
    """Radix-bucketed sorted join on the XLA device → (bidx, pidx) numpy.

    `b`/`p` are device (or host) int64 code arrays with codes in
    [0, max_code]; the caller guarantees packability
    (bits(max_code) + bits(rows) ≤ 62).
    """
    from pixie_tpu.engine import transfer

    nb, npr = int(b.shape[0]), int(p.shape[0])
    K = int(max_code) + 1
    ib = max(max(nb, npr) - 1, 1).bit_length()
    B = _bucket_count(nb, npr)
    # equal spans by construction: every bucket covers exactly `kloc` codes,
    # so out-of-bucket rows in an over-read slice always clamp into the
    # LUT's boundary slot instead of polluting a narrower bucket's cells
    kloc = -(-K // B)
    edges = np.arange(B + 1, dtype=np.int64) * kloc
    pad = _next_pow2(max(nb, npr) * 4 // B) if B > 1 else _next_pow2(
        max(nb, npr))
    if nthreads is None:
        import os

        nthreads = min(4, os.cpu_count() or 1)
    with ThreadPoolExecutor(2) as ex:
        fb = ex.submit(_pack_sort, jnp.asarray(b), ib, pad)
        fp = ex.submit(_pack_sort, jnp.asarray(p), ib, pad)
        sb, sp = fb.result(), fp.result()
    dedges = jnp.asarray(edges << ib)
    bb = np.asarray(jnp.searchsorted(sb[:nb], dedges))
    pb = np.asarray(jnp.searchsorted(sp[:npr], dedges))
    bsz, psz = bb[1:] - bb[:-1], pb[1:] - pb[:-1]
    cap_bs = [_next_pow2(int(s)) for s in bsz]
    cap_ps = [_next_pow2(int(s)) for s in psz]
    # a pow2 cap may overrun the sentinel tail under heavy skew — grow it
    over_b = max(int(bb[i]) + cap_bs[i] for i in range(B)) - (nb + pad)
    over_p = max(int(pb[i]) + cap_ps[i] for i in range(B)) - (npr + pad)
    if over_b > 0:
        sb = _append_pad(sb, _next_pow2(over_b))
    if over_p > 0:
        sp = _append_pad(sp, _next_pow2(over_p))
    res = [None] * B

    def match(i):
        res[i] = _bucket_match(sb, sp, int(bb[i]), int(bsz[i]), int(pb[i]),
                               int(psz[i]), int(edges[i]), cap_bs[i],
                               cap_ps[i], kloc, ib)

    with ThreadPoolExecutor(nthreads) as ex:
        list(ex.map(match, range(B)))
    totals = np.asarray(jax.device_get([r[2] for r in res]))
    outs = [None] * B

    def expand(i):
        t = int(totals[i])
        if t == 0:
            return
        outs[i] = _bucket_expand(res[i][0], res[i][1], sb, sp, int(bb[i]),
                                 int(pb[i]), t, _next_pow2(t), ib) + (t,)

    with ThreadPoolExecutor(nthreads) as ex:
        list(ex.map(expand, range(B)))
    parts = transfer.pull([(o[0], o[1]) for o in outs if o])
    total = int(totals.sum())
    bidx = np.empty(total, np.int64)
    pidx = np.empty(total, np.int64)
    at = 0
    for (bo, po), o in zip(parts, (o for o in outs if o)):
        t = o[2]
        bidx[at:at + t] = np.asarray(bo)[:t]
        pidx[at:at + t] = np.asarray(po)[:t]
        at += t
    return bidx, pidx


# ------------------------------------------------------ native CPU kernel


def native_join_available() -> bool:
    from pixie_tpu.native import load_native

    return load_native() is not None


def _native_join(bh: np.ndarray, ph: np.ndarray):
    import ctypes

    from pixie_tpu.native import load_native

    lib = load_native()
    bh = np.ascontiguousarray(bh, dtype=np.int64)
    ph = np.ascontiguousarray(ph, dtype=np.int64)
    total = ctypes.c_int64(0)
    h = lib.px_join_run(
        bh.ctypes.data_as(ctypes.c_void_p), len(bh),
        ph.ctypes.data_as(ctypes.c_void_p), len(ph), ctypes.byref(total))
    try:
        n = total.value
        bidx = np.empty(n, np.int64)
        pidx = np.empty(n, np.int64)
        if n:
            lib.px_join_fetch(h, bidx.ctypes.data_as(ctypes.c_void_p),
                              pidx.ctypes.data_as(ctypes.c_void_p))
    finally:
        lib.px_join_free(h)
    return bidx, pidx


# ------------------------------------------------------------- entry points


def _dispatch_backend() -> str:
    from pixie_tpu.ops.groupby import dispatch_backend

    return dispatch_backend()


def device_join_codes(build_codes, probe_codes):
    """Full device join over composite int64 key codes → (build_idx,
    probe_idx, build_matched[nb] bool, probe_matched[np] bool) — the same
    contract the host `_match_pairs` provides, so the executor's
    output/unmatched logic is shared.  Pair ORDER is unspecified.

    Inputs may be host numpy or device-resident jax arrays.  Dispatch:
    native radix hash join when the dispatch device is XLA-CPU (zero-copy
    on the same bytes), radix-bucketed XLA kernel otherwise; raw code
    spaces too wide to radix-pack fall back to the legacy full-width
    sort/searchsorted kernel.
    """
    nb, npr = int(build_codes.shape[0]), int(probe_codes.shape[0])
    if nb == 0 or npr == 0:
        z = np.zeros(0, np.int64)
        return z, z.copy(), np.zeros(nb, bool), np.zeros(npr, bool)
    path = join_path()
    if path == "native_cpu":
        bidx, pidx = _native_join(np.asarray(build_codes),
                                  np.asarray(probe_codes))
    else:
        b = jnp.asarray(build_codes)
        p = jnp.asarray(probe_codes)
        # packability check: codes must be >= some floor and narrow enough
        # for code << idx_bits | idx to stay positive in int64
        cmin, cmax = jax.device_get(
            [jnp.minimum(jnp.min(b), jnp.min(p)),
             jnp.maximum(jnp.max(b), jnp.max(p))])
        ib = max(max(nb, npr) - 1, 1).bit_length()
        shift = -int(cmin) if cmin < 0 else 0
        width = int(cmax) + shift
        # packable + dense enough that the per-bucket LUT stays bounded;
        # sparse/wide raw code spaces keep the legacy full-width kernel
        # (the executor's unique-inverse codes are always dense)
        if (width >= 0 and width.bit_length() + ib <= 62
                and (width + 1) // _bucket_count(nb, npr) <= (1 << 24)):
            if shift:
                b = b + shift
                p = p + shift
            bidx, pidx = _xla_bucketed_join(b, p, width)
        else:
            bidx, pidx = _legacy_join_codes(b, p)
    bm = np.zeros(nb, bool)
    pm = np.zeros(npr, bool)
    bm[bidx] = True
    pm[pidx] = True
    return bidx, pidx, bm, pm


def join_path() -> str:
    """Which kernel `device_join_codes` will take right now:
    "native_cpu" or "xla_bucketed"."""
    if _dispatch_backend() == "cpu" and native_join_available():
        return "native_cpu"
    return "xla_bucketed"


# ---------------------------------------------------------------- auto-gate

_gate_lock = threading.Lock()
_gate_cache: dict | None = None
#: transfer.probe_epoch() at the cached decision: a probe expiry or
#: explicit invalidate_probes() bumps the epoch, which re-opens the gate
#: decision too (it was derived from the now-dead H2D figure)
_gate_epoch: int = -1


def device_join_gate(refresh: bool = False) -> dict:
    """The process-wide device-join gating decision, measured once.

    → {"enabled", "reason", "path", "h2d_mbps" (accelerators only),
       "flag"}.  PX_DEVICE_JOIN forces it (0/1); -1 = auto:
      * CPU dispatch: on iff the native kernel loaded — there is no
        transfer at all, and the native radix join beats the numpy host
        match (~3x at 16M x 16M).
      * accelerator: on iff the MEASURED H2D bandwidth
        (transfer.h2d_bandwidth_probe) reaches PX_DEVICE_JOIN_MIN_H2D_MBPS
        — direct-attached deployments get the kernel without config, a
        link slower than the threshold keeps the host match.  A probe
        that fails raises: a chip whose link cannot be measured must not
        quietly answer from the host.
    The decision is cached; metrics gauges px_device_join_enabled /
    px_h2d_bandwidth_mbps are set as a side effect so the gate is
    observable (the executor also records it in stats["device"]).
    """
    global _gate_cache, _gate_epoch
    from pixie_tpu.engine import transfer as _transfer

    with _gate_lock:
        flag = flags.get("PX_DEVICE_JOIN")
        # forced settings are never cached (tests flip the flag; no probe
        # needed anyway) — only the measured auto decision is, and only
        # while the probe epoch it was derived from is still current
        if _gate_cache is not None and not refresh \
                and _gate_cache.get("flag") == flag \
                and _gate_epoch == _transfer.probe_epoch():
            return _gate_cache
        out = {"flag": flag, "path": join_path()}
        if flag == 0:
            out.update(enabled=False, reason="forced_off")
        elif flag == 1:
            out.update(enabled=True, reason="forced_on")
        elif _dispatch_backend() == "cpu":
            ok = native_join_available()
            out.update(enabled=ok,
                       reason="native_cpu" if ok else "no_native_kernel")
        else:
            mbps = _transfer.h2d_bandwidth_probe()["mbps"]
            out["h2d_mbps"] = mbps
            thresh = flags.get("PX_DEVICE_JOIN_MIN_H2D_MBPS")
            out.update(enabled=mbps >= thresh,
                       reason=("h2d_direct_attached" if mbps >= thresh
                               else "h2d_below_threshold"))
        from pixie_tpu import metrics

        metrics.gauge_set("px_device_join_enabled", float(out["enabled"]),
                          help_="device-join auto-gate decision (1=device "
                                "kernel, 0=host match)")
        # px_h2d_bandwidth_mbps is set by the probe itself now
        # (transfer.h2d_bandwidth_probe memoizes per process and owns the
        # gauge), so the gate no longer re-measures or re-exports it
        if flag == -1:
            _gate_cache = out
            _gate_epoch = _transfer.probe_epoch()
        return out


def reset_gate_for_testing() -> None:
    global _gate_cache
    with _gate_lock:
        _gate_cache = None
