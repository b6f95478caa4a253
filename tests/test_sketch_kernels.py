"""Bit-equality of the sketch-update formulations (ops/sketch.py).

The limb-factored GEMM (bin digit packed into the one-hot value) and the
sorted segment-count kernel must produce IDENTICAL histograms to the
segment_sum scatter — the sketch's accuracy contract is formulation-
independent, and the distributed merge (psum) assumes every agent's state
came from the same arithmetic.  Edge shapes from the satellite list: zero
bin, overflow bin, empty mask, 1 and 4096 groups, post-psum merge parity
across a mesh.
"""
import numpy as np
import pytest

import pixie_tpu  # noqa: F401 — enables x64
import jax
import jax.numpy as jnp

from pixie_tpu import flags
from pixie_tpu.ops.sketch import LogHistogram, _sort_min_groups
from pixie_tpu.testing.live_chunks import LIVE_RANGES, live_mask, scan_every_chunk


@pytest.fixture(scope="module")
def lh():
    return LogHistogram()


def _paths(lh, gid, vals, mask, G):
    bins = lh.bin_index(vals)
    h0 = lh.init(G)
    return {
        "segment": np.asarray(lh._update_segment(h0, gid, bins, mask, G)),
        "sorted": np.asarray(lh._update_sorted(h0, gid, bins, mask, G)),
        "gemm": np.asarray(lh._update_gemm(h0, gid, bins, mask, G)),
    }


def _assert_all_equal(outs):
    ref = outs["segment"]
    for name, arr in outs.items():
        np.testing.assert_array_equal(ref, arr, err_msg=name)


class TestBitEquality:
    def test_mixed_values(self, lh):
        rng = np.random.default_rng(0)
        n, G = 1 << 13, 16
        gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
        vals = jnp.asarray(rng.exponential(50.0, n))
        mask = jnp.asarray(rng.random(n) < 0.9)
        outs = _paths(lh, gid, vals, mask, G)
        _assert_all_equal(outs)
        assert float(outs["segment"].sum()) == float(np.asarray(mask).sum())

    def test_zero_bin(self, lh):
        # values <= min_value (incl. negatives and exact 0) land in bin 0
        n, G = 4096, 4
        vals = jnp.asarray(np.tile([0.0, -3.5, 1e-12, 5.0], n // 4))
        gid = jnp.asarray(np.arange(n, dtype=np.int32) % G)
        mask = jnp.ones(n, bool)
        outs = _paths(lh, gid, vals, mask, G)
        _assert_all_equal(outs)
        assert outs["segment"][:, 0].sum() == 3 * (n // 4)

    def test_overflow_bin(self, lh):
        # values past the dynamic range clip into the last bin
        n, G = 4096, 4
        vals = jnp.asarray(np.tile([1e30, 7.0], n // 2))
        gid = jnp.asarray(np.arange(n, dtype=np.int32) % G)
        mask = jnp.ones(n, bool)
        outs = _paths(lh, gid, vals, mask, G)
        _assert_all_equal(outs)
        assert outs["segment"][:, -1].sum() == n // 2

    def test_empty_mask(self, lh):
        n, G = 4096, 8
        rng = np.random.default_rng(1)
        gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
        vals = jnp.asarray(rng.exponential(9.0, n))
        mask = jnp.zeros(n, bool)
        outs = _paths(lh, gid, vals, mask, G)
        _assert_all_equal(outs)
        assert outs["segment"].sum() == 0

    def test_one_group(self, lh):
        n = 1 << 12
        rng = np.random.default_rng(2)
        gid = jnp.zeros(n, jnp.int32)
        vals = jnp.asarray(rng.exponential(100.0, n))
        mask = jnp.asarray(rng.random(n) < 0.5)
        _assert_all_equal(_paths(lh, gid, vals, mask, 1))

    def test_4096_groups(self, lh):
        n, G = 1 << 14, 4096
        rng = np.random.default_rng(3)
        gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
        vals = jnp.asarray(rng.exponential(50.0, n))
        mask = jnp.asarray(rng.random(n) < 0.95)
        _assert_all_equal(_paths(lh, gid, vals, mask, G))

    def test_update_dispatch_matches_segment(self, lh):
        """update() (whatever path it picks on this backend) == scatter."""
        n, G = 1 << 15, 1024
        rng = np.random.default_rng(4)
        gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
        vals = jnp.asarray(rng.exponential(50.0, n))
        mask = jnp.asarray(rng.random(n) < 0.9)
        got = np.asarray(lh.update(lh.init(G), gid, vals, mask, G))
        want = np.asarray(
            lh._update_segment(lh.init(G), gid, lh.bin_index(vals), mask, G))
        np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("live", list(LIVE_RANGES))
def test_gemm_over_live_chunks_is_the_gemm_over_all(lh, monkeypatch, live):
    """The GEMM's chunk loop visits the chunks that hold a live row and no
    other: wherever the live rows sit in the pow2 bucket its histogram is
    the scatter's, and the loop over every chunk's, bit for bit."""
    from pixie_tpu.ops import groupby as gb

    c, G = 8, 16
    n = c * lh.CHUNK
    rng = np.random.default_rng(6)
    gid = rng.integers(0, G, n).astype(np.int32)
    bins = lh.bin_index(jnp.asarray(rng.exponential(50.0, n)))
    mask = live_mask(live, c, lh.CHUNK)

    def gemm(g, b, m):
        return lh._update_gemm(lh.init(G), g, b, m, G)

    want = np.asarray(lh._update_segment(lh.init(G), gid, bins, mask, G))
    got = np.asarray(jax.jit(gemm)(gid, bins, mask))
    monkeypatch.setattr(gb, "scan_sum", scan_every_chunk)
    # (a new callable: jit would hand back gemm's program, traced before)
    every = np.asarray(jax.jit(lambda *a: gemm(*a))(gid, bins, mask))
    assert got.tobytes() == every.tobytes() == want.tobytes()
    assert got.sum() == mask.sum()


class TestDigitPacking:
    def test_chunk_below_digit_base(self, lh):
        # the GEMM's exactness proof needs per-chunk counts < DIGIT
        assert lh.CHUNK < lh.DIGIT
        assert 2 * lh.LANES >= lh.width

    def test_gemm_saturated_cell(self, lh):
        # every row in ONE (group, bin) cell: the worst case for the packed
        # digit — a full chunk's count must come through exactly
        n, G = 1 << 13, 2
        vals = jnp.full(n, 7.0)
        gid = jnp.zeros(n, jnp.int32)
        mask = jnp.ones(n, bool)
        _assert_all_equal(_paths(lh, gid, vals, mask, G))

    def test_gemm_upper_half_bins(self, lh):
        # values whose bins sit in the packed (digit=1) half
        hi_bin = lh.LANES + 5
        v = float(lh.gamma ** (hi_bin - 2))  # lands past LANES
        n, G = 4096, 2
        vals = jnp.full(n, v)
        gid = jnp.asarray(np.arange(n, dtype=np.int32) % G)
        mask = jnp.ones(n, bool)
        outs = _paths(lh, gid, vals, mask, G)
        _assert_all_equal(outs)
        assert int(np.nonzero(outs["segment"][0])[0][0]) >= lh.LANES


class TestSortMinGroups:
    def test_backend_defaults(self):
        assert _sort_min_groups("cpu") == 512
        assert _sort_min_groups("tpu") == 4097

    def test_flag_override(self):
        flags.set_for_testing("PX_SKETCH_SORT_MIN_GROUPS", 7)
        try:
            assert _sort_min_groups("cpu") == 7
            assert _sort_min_groups("tpu") == 7
        finally:
            flags.set_for_testing("PX_SKETCH_SORT_MIN_GROUPS", 0)


class TestPsumMergeParity:
    def test_mesh_psum_merge(self, lh):
        """Per-shard updates psum-merged across an 8-device CPU mesh equal
        the single-device update over all rows — for BOTH per-shard
        formulations (sorted and segment), since a mixed-formulation mesh
        (e.g. heterogeneous backends) must still merge exactly."""
        from jax.sharding import PartitionSpec as P

        from pixie_tpu.parallel.spmd import (
            make_mesh, serialize_cpu_collectives, shard_map,
        )

        n_dev, per = 8, 2048
        n, G = n_dev * per, 32
        rng = np.random.default_rng(5)
        gid = rng.integers(0, G, n).astype(np.int32)
        vals = rng.exponential(50.0, n)
        mask = rng.random(n) < 0.9
        mesh = make_mesh(n_dev)
        bins = np.asarray(lh.bin_index(jnp.asarray(vals)))

        for form in ("_update_sorted", "_update_segment"):
            upd = getattr(lh, form)

            def shard_fn(g, b, m):
                h = upd(lh.init(G), g[0], b[0], m[0], G)
                return jax.lax.psum(h, "agents")[None]

            f = jax.jit(shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P("agents"), P("agents"), P("agents")),
                out_specs=P("agents"),
            ))
            f = serialize_cpu_collectives(f, mesh)
            merged = np.asarray(f(
                gid.reshape(n_dev, per),
                bins.reshape(n_dev, per),
                mask.reshape(n_dev, per),
            ))[0]
            want = np.asarray(lh._update_segment(
                lh.init(G), jnp.asarray(gid), jnp.asarray(bins),
                jnp.asarray(mask), G))
            np.testing.assert_array_equal(want, merged, err_msg=form)
