"""Bit-equality of the sketch-update formulations (ops/sketch.py).

The Pallas kernel (the limb-factored GEMM: bin digit packed into the
one-hot value, the histogram in VMEM across the grid) and the sorted
segment-count kernel must produce IDENTICAL histograms to the segment_sum
scatter — the sketch's accuracy contract is formulation-independent, and
the distributed merge (psum) assumes every agent's state came from the same
arithmetic.  Edge shapes: zero bin, overflow bin, the digits' edge, empty
mask, 1 to 4096 groups, a feed that is not a whole number of the kernel's
blocks, live rows anywhere in a bucket, post-psum merge parity across a
mesh.  Off the chip the kernel runs interpreted.
"""
import numpy as np
import pytest

import pixie_tpu  # noqa: F401 — enables x64
import jax
import jax.numpy as jnp

from pixie_tpu import flags
from pixie_tpu.ops import sketch as sk
from pixie_tpu.ops.sketch import LogHistogram, _sort_min_groups
from pixie_tpu.testing.live_chunks import LIVE_RANGES, live_mask


@pytest.fixture(scope="module")
def lh():
    return LogHistogram()


def _paths(lh, gid, vals, mask, G):
    bins = lh.bin_index(vals)
    h0 = lh.init(G)
    return {
        "segment": np.asarray(lh._update_segment(h0, gid, bins, mask, G)),
        "sorted": np.asarray(lh._update_sorted(h0, gid, bins, mask, G)),
        "pallas": np.asarray(lh._update_pallas(h0, gid, bins, mask, G)),
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


#: the kernel's blocks in the tests: 8 sub-blocks of 512 rows, two CHUNKs,
#: so that a live range starts and ends inside a block
SMALL_BLOCK = {"sub_rows": 512, "block_subs": 8}


@pytest.mark.parametrize("live", list(LIVE_RANGES))
def test_gemm_over_live_chunks_is_the_gemm_over_all(lh, monkeypatch, live):
    """The kernel's grid visits the blocks that hold a live row and no
    other: wherever the live rows sit in the pow2 bucket, its histogram is
    the scatter's, and that of the kernel over every block, bit for bit."""
    from pixie_tpu.ops import groupby as gb

    c, G = 8, 16
    n = c * lh.CHUNK
    rng = np.random.default_rng(6)
    gid = rng.integers(0, G, n).astype(np.int32)
    bins = lh.bin_index(jnp.asarray(rng.exponential(50.0, n)))
    mask = live_mask(live, c, lh.CHUNK)

    def kernel(g, b, m):
        return lh._update_pallas(lh.init(G), g, b, m, G, **SMALL_BLOCK)

    want = np.asarray(lh._update_segment(lh.init(G), gid, bins, mask, G))
    got = np.asarray(jax.jit(kernel)(gid, bins, mask))
    blocks = n // (SMALL_BLOCK["sub_rows"] * SMALL_BLOCK["block_subs"])
    monkeypatch.setattr(gb, "live_chunks", lambda m, ch: (
        jnp.int32(0), jnp.int32(blocks)))
    # (a new callable: jit would hand back kernel's program, traced before)
    every = np.asarray(jax.jit(lambda *a: kernel(*a))(gid, bins, mask))
    assert got.tobytes() == every.tobytes() == want.tobytes()
    assert got.sum() == mask.sum()


@pytest.mark.parametrize("live", list(LIVE_RANGES))
def test_kernel_at_its_own_block_over_live_chunks(lh, live):
    """The block the chip runs (KERNEL_BLOCK_SUBS sub-blocks of CHUNK rows)
    over four of them: wherever the live rows start and end inside a block,
    the scatter's histogram."""
    G = 16
    c = 4 * sk.KERNEL_BLOCK_SUBS
    n = c * lh.CHUNK
    rng = np.random.default_rng(8)
    gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
    bins = lh.bin_index(jnp.asarray(rng.exponential(50.0, n)))
    mask = jnp.asarray(live_mask(live, c, lh.CHUNK))
    want = np.asarray(lh._update_segment(lh.init(G), gid, bins, mask, G))
    got = np.asarray(jax.jit(lambda g, b, m: lh._update_pallas(
        lh.init(G), g, b, m, G))(gid, bins, mask))
    assert got.tobytes() == want.tobytes()
    assert got.sum() == int(np.asarray(mask).sum())


@pytest.mark.parametrize("G", [4, 128, 512, 2048])
def test_kernel_is_the_scatter_at_any_group_count(lh, G):
    """One group tile (4 and 128 padded to a lane tile), one whole tile
    (512) and several (2,048): the same histogram as the scatter's."""
    rng = np.random.default_rng(G)
    n = 1 << 14
    gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
    bins = lh.bin_index(jnp.asarray(rng.exponential(50.0, n)))
    mask = jnp.asarray(rng.random(n) < 0.9)
    want = np.asarray(lh._update_segment(lh.init(G), gid, bins, mask, G))
    got = np.asarray(jax.jit(lambda g, b, m: lh._update_pallas(
        lh.init(G), g, b, m, G, **SMALL_BLOCK))(gid, bins, mask))
    assert got.tobytes() == want.tobytes()
    assert got.sum() == int(np.asarray(mask).sum())


def test_kernel_over_rows_that_are_not_whole_blocks(lh):
    """5 blocks and 1,234 rows: the kernel pads to a whole block with rows
    that match no group, the last of them live."""
    G = 24
    n = 5 * 4096 + 1234
    rng = np.random.default_rng(9)
    gid = jnp.asarray(rng.integers(0, G, n).astype(np.int32))
    bins = lh.bin_index(jnp.asarray(rng.exponential(50.0, n)))
    mask = np.zeros(n, bool)
    mask[3000:] = True
    want = np.asarray(lh._update_segment(lh.init(G), gid, bins, mask, G))
    got = np.asarray(lh._update_pallas(lh.init(G), gid, bins, mask, G,
                                       **SMALL_BLOCK))
    assert got.tobytes() == want.tobytes()
    assert got.sum() == n - 3000


@pytest.mark.parametrize("edge", [0, 256, 257, 513])
def test_kernel_at_the_edge_bins_and_the_digits_edge(lh, edge):
    """Bin 0 (the zero bin) and 513 (overflow) are the histogram's edges;
    256 is the low digit's last lane and 257 the high digit's first: every
    row of a saturated sub-block in one such bin, beside a spread."""
    G, n = 8, 8192
    rng = np.random.default_rng(edge)
    bins = rng.integers(0, lh.width, n).astype(np.int32)
    bins[: 2 * lh.CHUNK] = edge  # two whole chunks of one bin
    gid = rng.integers(0, G, n).astype(np.int32)
    gid[: 2 * lh.CHUNK] = 3
    mask = np.ones(n, bool)
    want = np.asarray(lh._update_segment(lh.init(G), gid, bins, mask, G))
    got = np.asarray(lh._update_pallas(lh.init(G), gid, bins, mask, G))
    assert got.tobytes() == want.tobytes()
    assert got[3, edge] >= 2 * lh.CHUNK


MAX = sk.SKETCH_KERNEL_MAX_GROUPS


@pytest.mark.parametrize("backend,n,groups,form", [
    # this process's own dispatch: XLA-CPU
    ("cpu", 1 << 20, 128, "segment"),
    ("cpu", 1 << 20, 1024, "sorted"),
    ("cpu", 1 << 20, MAX + 1, "sorted"),
    # the TPU: the kernel up to its cap from 4,096 rows
    ("tpu", 1 << 20, 1, "pallas"),
    ("tpu", 4096, 128, "pallas"),
    ("tpu", 1 << 20, MAX, "pallas"),
    ("tpu", 4095, 128, "segment"),
    # past the cap the tiles kernel, from 4,096 rows, up to its own cap
    ("tpu", 1 << 20, MAX + 1, "tiles"),
    ("tpu", 4096, MAX + 1, "tiles"),
    ("tpu", 1 << 23, 32768, "tiles"),
    ("tpu", 1 << 23, sk.SKETCH_TILES_MAX_GROUPS, "tiles"),
    ("tpu", 4095, MAX + 1, "segment"),
    ("tpu", 1 << 23, sk.SKETCH_TILES_MAX_GROUPS + 1, "segment"),
])
def test_sketch_form_reads_rows_groups_and_platform(monkeypatch, backend, n,
                                                    groups, form):
    """The kernel on the TPU up to SKETCH_KERNEL_MAX_GROUPS groups and from
    4,096 rows, the tiles kernel past that up to SKETCH_TILES_MAX_GROUPS;
    on XLA-CPU the sorted form past the crossover where the cell space
    stays under four times the rows; the scatter everywhere else."""
    from pixie_tpu.ops import groupby as gb

    monkeypatch.setattr(gb, "dispatch_backend", lambda: backend)
    assert sk.sketch_form(n, groups) == form


@pytest.mark.parametrize("G,kernel", [(128, "px_sketch_hist"),
                                      (16384, "px_sketch_tiles")])
def test_kernel_compiles_for_a_v5e_mesh_beside_its_psum(lh, monkeypatch, G,
                                                       kernel):
    """What each chip of `http_scan_4chip` runs (128 groups), and a service
    map's edges past the cap (16,384): the sketch's update traced for the
    TPU under `jax.shard_map`, each shard over its own rows, and the states
    summed across the mesh by a psum.  Lowered and compiled for a described
    v5e 2x2 (no chip needed), the program holds the Mosaic kernel and the
    all-reduce."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pixie_tpu.ops import groupby as gb

    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("agents",))
    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    per = 1 << 16
    assert sk.sketch_form(per, G) == kernel.replace("px_sketch_hist", "pallas"
                                                    ).replace("px_sketch_", "")
    step = jax.jit(jax.shard_map(
        lambda v, g, m: jax.lax.psum(
            lh.update(lh.init(G), g[0], v[0], m[0], G), "agents"),
        mesh=mesh, in_specs=(P("agents"),) * 3, out_specs=P()))
    sharded = NamedSharding(mesh, P("agents"))
    program = step.lower(*(jax.ShapeDtypeStruct((4, per), dt, sharding=sharded)
                           for dt in (jnp.float32, jnp.int32, jnp.bool_))
                         ).compile().as_text()
    assert kernel in program and "all-reduce" in program


#: the tiles kernel's blocks in the tests: 4 sub-blocks of 512 rows
TILES_BLOCK = {"sub_rows": 512, "block_subs": 4}


def _tiles_case(case: str, G: int):
    """(gid, values, mask) of a test of the tiles kernel at G groups: rows
    that are not whole blocks with a hot group, a masked stretch, a full
    bucket of whole blocks, or every row in the first and last tiles (the
    ones between have none)."""
    rng = np.random.default_rng(G + len(case))
    n = {"full_bucket": 4 * 4 * 512}.get(case, 3 * 2048 + 777)
    gid = rng.integers(0, G, n).astype(np.int32)
    mask = np.ones(n, bool)
    if case == "hot_group":
        gid[rng.random(n) < 0.6] = G // 3
    elif case == "masked_rows":
        mask[500:4500] = False
        mask[rng.random(n) < 0.2] = False
    elif case == "empty_tiles":
        gid = np.where(rng.random(n) < 0.5, gid % 7, G - 1 - gid % 5)
    vals = rng.exponential(50.0, n)
    vals[::97] = 0.0  # the zero bin
    vals[::89] = 1e30  # the overflow bin
    return gid.astype(np.int32), vals, mask


@pytest.mark.parametrize("case", ["hot_group", "masked_rows", "full_bucket",
                                  "empty_tiles"])
@pytest.mark.parametrize("G", [MAX + 1, 16384, 32768])
def test_tiles_kernel_is_add_at(lh, G, case):
    """Past the kernel's cap: the tiles kernel's histogram is np.add.at's
    bit for bit, whatever the group count (one row past a whole tile, 32
    and 64 tiles), a hot group, masked rows, rows that are not whole
    blocks, a full bucket, and tiles with no rows."""
    gid, vals, mask = _tiles_case(case, G)
    bins = lh.bin_index(jnp.asarray(vals))
    want = np.zeros((G, lh.width), np.float32)
    np.add.at(want, (gid[mask], np.asarray(bins)[mask]), 1.0)
    got = np.asarray(jax.jit(lambda g, b, m: lh._update_tiles(
        lh.init(G), g, b, m, G, **TILES_BLOCK))(gid, bins, mask))
    assert got.tobytes() == want.tobytes()
    assert got.sum() == mask.sum()


def test_tiles_kernel_at_its_own_block(lh):
    """The block the chip runs (KERNEL_BLOCK_SUBS sub-blocks of CHUNK
    rows), over two blocks and a part of a third, past the cap."""
    G = 16384
    n = 2 * sk.KERNEL_BLOCK_SUBS * lh.CHUNK + 3000
    rng = np.random.default_rng(11)
    gid = rng.integers(0, G, n).astype(np.int32)
    gid[: n // 3] = 4242
    bins = lh.bin_index(jnp.asarray(rng.exponential(50.0, n)))
    mask = rng.random(n) < 0.9
    want = np.asarray(lh._update_segment(lh.init(G), gid, bins, mask, G))
    got = np.asarray(jax.jit(lambda g, b, m: lh._update_tiles(
        lh.init(G), g, b, m, G))(gid, bins, mask))
    assert got.tobytes() == want.tobytes()


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


def _tpu_forms(monkeypatch) -> set:
    """The forms the TPU's dispatch takes over group counts from 1 to past
    the tiles kernel's cap, at a full bucket."""
    from pixie_tpu.ops import groupby as gb

    monkeypatch.setattr(gb, "dispatch_backend", lambda: "tpu")
    return {sk.sketch_form(1 << 23, g) for g in
            (1, 7, 128, 1024, MAX, MAX + 1, 32768,
             sk.SKETCH_TILES_MAX_GROUPS + 1)}


class TestSortMinGroups:
    def test_backend_defaults(self, monkeypatch):
        """512 on XLA-CPU; the TPU never takes the sorted form."""
        assert _sort_min_groups("cpu") == 512
        assert _tpu_forms(monkeypatch) == {"pallas", "tiles", "segment"}

    def test_flag_override(self, monkeypatch):
        """The flag moves XLA-CPU's crossover and not the TPU's forms."""
        flags.set_for_testing("PX_SKETCH_SORT_MIN_GROUPS", 7)
        try:
            assert _sort_min_groups("cpu") == 7
            assert _tpu_forms(monkeypatch) == {"pallas", "tiles", "segment"}
        finally:
            flags.set_for_testing("PX_SKETCH_SORT_MIN_GROUPS", 0)


class TestPsumMergeParity:
    @pytest.mark.parametrize("form,G", [
        ("_update_sorted", 32), ("_update_segment", 32),
        ("_update_tiles", 32), ("_update_tiles", MAX + 1)])
    def test_mesh_psum_merge(self, lh, form, G):
        """Per-shard updates psum-merged across an 8-device CPU mesh equal
        the single-device update over all rows — for every per-shard
        formulation (sorted and segment, and the tiles kernel, which each
        shard interprets over its own rows off the chip, within its cap and
        past the dense kernel's), since a mixed-formulation mesh (e.g.
        heterogeneous backends) must still merge exactly."""
        from jax.sharding import PartitionSpec as P

        from pixie_tpu.parallel.spmd import (
            make_mesh, serialize_cpu_collectives, shard_map,
        )

        n_dev, per = 8, 2048
        n = n_dev * per
        rng = np.random.default_rng(5)
        gid = rng.integers(0, G, n).astype(np.int32)
        vals = rng.exponential(50.0, n)
        mask = rng.random(n) < 0.9
        mesh = make_mesh(n_dev)
        bins = np.asarray(lh.bin_index(jnp.asarray(vals)))
        upd = getattr(lh, form)
        kw = TILES_BLOCK if form == "_update_tiles" else {}

        def shard_fn(g, b, m):
            h = upd(lh.init(G), g[0], b[0], m[0], G, **kw)
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


# ------------------------------------------------------------ the finalize

#: every quantile the bundled scripts ask: px.quantiles' five, p50, p99
FINAL_QS = [0.01, 0.10, 0.50, 0.90, 0.99]


def _at_rank(lh, totals, q):
    """Two histograms per total: the host's rank for q, less one and
    itself, in bin 3 and the rest in bin 400, so that a rule one rank off
    moves the quantile from one of these bins to the other."""
    rank = np.ceil(np.float64(q) * np.asarray(totals, np.float64))
    rows = []
    for total, r in zip(totals, rank):
        for low in (max(r - 1, 0), r):
            h = np.zeros(lh.width, np.float32)
            h[3], h[400] = low, total - low
            rows.append(h)
    return np.stack(rows)


def _device_is_host(lh, hist, qs):
    bins = np.asarray(jax.jit(lambda h: lh.quantile_bins_device(h, qs))(
        jnp.asarray(hist)))
    np.testing.assert_array_equal(bins, lh.quantile_bins(hist, qs))
    np.testing.assert_array_equal(lh.bin_values(bins), lh.quantile(hist, qs))
    return bins


@pytest.mark.parametrize("q,totals", [
    # the first totals at which an f32 target's ceil is not the host's
    (0.99, [264_799, 264_899]), (0.90, [1_572_869]), (0.01, [13_107_201])])
def test_device_finalize_is_the_hosts_where_f32_ranks_were_not(lh, q,
                                                               totals):
    """The device's bins and values equal the host finalize's to the bit
    at totals where q * total rounded in f32 gives another rank."""
    for t in totals:
        assert (np.ceil(np.float32(q) * np.float32(t))
                != np.ceil(np.float64(q) * t))
    bins = _device_is_host(lh, _at_rank(lh, totals, q), [q])
    assert set(bins[:, 0]) == {3, 400}


def test_device_finalize_is_the_hosts_over_random_totals(lh):
    """Totals from 1 to 2^24 for each of the scripts' quantiles, at the
    host's rank and one below it, and spread over random bins."""
    rng = np.random.default_rng(11)
    totals = rng.integers(1, 1 << 24, 400)
    hist = [_at_rank(lh, totals, q) for q in FINAL_QS]
    spread = np.zeros((400, lh.width), np.float32)
    for row, total in zip(spread, totals):
        cuts = np.sort(rng.integers(0, total + 1, 6))
        row[np.sort(rng.choice(lh.width, 7, replace=False))] = np.diff(
            np.concatenate([[0], cuts, [total]]))
    _device_is_host(lh, np.concatenate(hist + [spread]), FINAL_QS)


def test_device_finalize_of_an_empty_group_is_nan(lh):
    hist = np.zeros((3, lh.width), np.float32)
    hist[1, 7] = 5
    bins = _device_is_host(lh, hist, FINAL_QS)
    assert (bins[[0, 2]] == -1).all() and (bins[1] == 7).all()
    values = lh.bin_values(bins)
    assert np.isnan(values[[0, 2]]).all() and not np.isnan(values[1]).any()


@pytest.mark.parametrize("chips", [1, 4])
def test_device_finalize_compiles_for_a_v5e(lh, monkeypatch, chips):
    """The service map's finalize (px.quantiles and a p99 over 16,384
    histograms), as its one chip runs it and as the 2x2 mesh's merge runs
    it over a replicated state: lowered and compiled for a described v5e
    (no chip needed), its 64-bit integer rank arithmetic included."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pixie_tpu.udf.udf import QuantilesUDA, QuantileUDA

    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:chips]), ("agents",))
    fin = jax.jit(lambda h: (QuantilesUDA().finalize_device(h),
                             QuantileUDA(0.99).finalize_device(h)))
    state = jax.ShapeDtypeStruct((16384, lh.width), jnp.float32,
                                 sharding=NamedSharding(mesh, P()))
    shapes = jax.eval_shape(fin, state)
    assert [(s.shape, s.dtype) for s in shapes] == [
        ((16384, 5), jnp.int32), ((16384,), jnp.int32)]
    assert fin.lower(state).compile().as_text()
