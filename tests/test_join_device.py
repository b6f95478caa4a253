"""Device equijoin kernel (ops/join_device.py): parity with the host match
phase + the PX_DEVICE_JOIN executor gate + a unit microbench.

Reference: exec/equijoin_node.h (hash build/probe) — redesigned as device
sort/searchsorted (SURVEY §7 'Pallas hash join or sort-merge join on TPU').
"""
import numpy as np
import pandas as pd
import pytest

import pixie_tpu  # noqa: F401
from pixie_tpu import flags
from pixie_tpu.engine.executor import PlanExecutor, _match_pairs
from pixie_tpu.ops.join_device import device_join_codes, expand_pairs, match_ranges
from pixie_tpu.plan import JoinOp, MemorySinkOp, MemorySourceOp, Plan
from pixie_tpu.table import TableStore
from pixie_tpu.types import DataType as DT, Relation


def _pairs_equal(host, dev):
    hl, hr, hlm, hrm = host
    dl, dr, dlm, drm = dev
    # pair SETS must match (ordering may differ between implementations)
    hs = set(zip(hl.tolist(), hr.tolist()))
    ds = set(zip(dl.tolist(), dr.tolist()))
    assert hs == ds
    np.testing.assert_array_equal(hlm, dlm)
    np.testing.assert_array_equal(hrm, drm)


class TestKernelParity:
    def test_many_to_many_with_nulls(self):
        rng = np.random.default_rng(3)
        nl, nr = 5000, 7000
        lc = rng.integers(0, 800, nl)
        rc = rng.integers(0, 800, nr)
        lnull = rng.random(nl) < 0.05
        rnull = rng.random(nr) < 0.05
        host = _match_pairs(lc, rc, lnull, rnull)
        dev = device_join_codes(np.where(lnull, np.int64(-1), lc),
                                np.where(rnull, np.int64(-2), rc))
        _pairs_equal(host, dev)

    def test_no_matches_and_empty(self):
        dev = device_join_codes(np.array([1, 2, 3], dtype=np.int64),
                                np.array([9, 9], dtype=np.int64))
        assert len(dev[0]) == 0 and not dev[2].any() and not dev[3].any()

    def test_match_ranges_total(self):
        import jax.numpy as jnp

        b = jnp.asarray(np.array([5, 1, 5, 2], dtype=np.int64))
        p = jnp.asarray(np.array([5, 3, 1], dtype=np.int64))
        order, lo, hi, total = match_ranges(b, p)
        assert int(total) == 3  # 5 matches twice, 1 once
        bidx, pidx = expand_pairs(order, lo, hi, int(total))
        got = sorted(zip(np.asarray(bidx).tolist(),
                         np.asarray(pidx).tolist()))
        assert got == [(0, 0), (1, 2), (2, 0)]


class TestBucketedKernelParity:
    """Radix-bucketed kernel (both dispatch paths) vs the host match phase:
    m:n duplicates, empty buckets, and the degenerate all-one-bucket hash."""

    @staticmethod
    def _pairs_sorted(bi, pi):
        a = np.stack([np.asarray(bi), np.asarray(pi)])
        return a[:, np.lexsort(a)]

    def _check(self, lc, rc, lnull=None, rnull=None):
        from pixie_tpu.ops import join_device as jd

        nl, nr = len(lc), len(rc)
        lnull = np.zeros(nl, bool) if lnull is None else lnull
        rnull = np.zeros(nr, bool) if rnull is None else rnull
        host = _match_pairs(lc, rc, lnull, rnull)
        lcx = np.where(lnull, np.int64(-1), lc)
        rcx = np.where(rnull, np.int64(-2), rc)
        hp = self._pairs_sorted(host[0], host[1])
        orig = jd.join_path
        try:
            for path in ("native_cpu", "xla_bucketed"):
                if path == "native_cpu" and not jd.native_join_available():
                    continue
                jd.join_path = lambda p=path: p
                dev = device_join_codes(lcx, rcx)
                np.testing.assert_array_equal(
                    hp, self._pairs_sorted(dev[0], dev[1]), err_msg=path)
                np.testing.assert_array_equal(host[2], dev[2], err_msg=path)
                np.testing.assert_array_equal(host[3], dev[3], err_msg=path)
        finally:
            jd.join_path = orig

    def test_mn_duplicates(self):
        rng = np.random.default_rng(7)
        lc = rng.integers(0, 50, 4000).astype(np.int64)  # heavy m:n
        rc = rng.integers(0, 50, 3000).astype(np.int64)
        self._check(lc, rc)

    def test_empty_buckets(self):
        # codes clustered in a sliver of the space: most radix buckets empty
        rng = np.random.default_rng(8)
        n = 1 << 19  # crosses _MIN_BUCKETED_ROWS so B > 1
        lc = (rng.integers(0, 1 << 15, n) + (n // 2)).astype(np.int64)
        rc = (rng.integers(0, 1 << 15, n // 2) + (n // 2)).astype(np.int64)
        from pixie_tpu.ops import join_device as jd

        host = _match_pairs(lc, rc, np.zeros(n, bool),
                            np.zeros(n // 2, bool))
        bidx, pidx = jd._xla_bucketed_join(lc, rc, int(lc.max()))
        np.testing.assert_array_equal(self._pairs_sorted(host[0], host[1]),
                                      self._pairs_sorted(bidx, pidx))

    def test_all_one_bucket_degenerate(self):
        # every row shares ONE code: the hash/radix partition degenerates to
        # a single bucket and the m:n expansion is the full cross product
        nl, nr = 1500, 900
        lc = np.full(nl, 42, np.int64)
        rc = np.full(nr, 42, np.int64)
        self._check(lc, rc)

    def test_nulls_with_duplicates(self):
        rng = np.random.default_rng(9)
        nl, nr = 5000, 4000
        lc = rng.integers(0, 300, nl).astype(np.int64)
        rc = rng.integers(0, 300, nr).astype(np.int64)
        self._check(lc, rc, rng.random(nl) < 0.1, rng.random(nr) < 0.1)

    def test_wide_sparse_codes_fall_back(self):
        # raw code spaces too wide/sparse to radix-pack use the legacy
        # full-width kernel and still match
        rng = np.random.default_rng(10)
        lc = rng.integers(0, 1 << 60, 3000).astype(np.int64)
        rc = np.concatenate([lc[:1000], rng.integers(0, 1 << 60, 1000)])
        self._check(lc, rc)


class TestExecutorJoinParity:
    """Device joins (gate forced on) vs the host `_run_join` through the
    FULL executor for every join type, with m:n duplicate keys."""

    def _plan(self, how):
        p = Plan()
        l = p.add(MemorySourceOp(table="left"))
        r = p.add(MemorySourceOp(table="right"))
        j = p.add(JoinOp(how=how, left_on=["k"], right_on=["k"],
                         output=[("left", "k", "k"), ("left", "a", "a"),
                                 ("right", "b", "b")]), parents=[l, r])
        p.add(MemorySinkOp(name="out"), parents=[j])
        return p

    @pytest.fixture(scope="class")
    def stores(self):
        rng = np.random.default_rng(11)
        n = 1 << 17
        ts = TableStore()
        lt = ts.create("left", Relation.of(("k", DT.INT64), ("a", DT.INT64)),
                       batch_rows=1 << 16)
        rt = ts.create("right", Relation.of(("k", DT.INT64), ("b", DT.INT64)),
                       batch_rows=1 << 16)
        # m:n duplicates + keys unique to each side (exercise unmatched)
        lt.write({"k": rng.integers(0, n // 8, n),
                  "a": np.arange(n, dtype=np.int64)})
        rt.write({"k": rng.integers(n // 16, n // 8 + n // 16, n),
                  "b": np.arange(n, dtype=np.int64)})
        return ts

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_how_parity(self, stores, how):
        plan = self._plan(how)
        flags.set_for_testing("PX_DEVICE_JOIN", 0)
        try:
            host = PlanExecutor(plan, stores).run()["out"].to_pandas()
        finally:
            flags.set_for_testing("PX_DEVICE_JOIN", 1)
        try:
            ex = PlanExecutor(plan, stores)
            dev = ex.run()["out"].to_pandas()
            assert ex.stats.get("device_joins", 0) == 1
            assert ex.stats["device"]["join_gate"]["enabled"]
        finally:
            flags.set_for_testing("PX_DEVICE_JOIN", -1)
        cols = ["k", "a", "b"]
        h = host.sort_values(cols).reset_index(drop=True)
        d = dev.sort_values(cols).reset_index(drop=True)
        pd.testing.assert_frame_equal(h, d, check_dtype=False)


class TestAutoGate:
    def test_gate_shape_and_gauges(self):
        from pixie_tpu import metrics
        from pixie_tpu.ops import join_device as jd

        jd.reset_gate_for_testing()
        gate = jd.device_join_gate()
        assert gate["reason"] in ("native_cpu", "no_native_kernel",
                                  "h2d_direct_attached", "h2d_below_threshold",
                                  "forced_on", "forced_off")
        assert "px_device_join_enabled" in metrics.render()

    def test_forced_off(self):
        from pixie_tpu.ops import join_device as jd

        flags.set_for_testing("PX_DEVICE_JOIN", 0)
        jd.reset_gate_for_testing()
        try:
            gate = jd.device_join_gate()
            assert not gate["enabled"] and gate["reason"] == "forced_off"
        finally:
            flags.set_for_testing("PX_DEVICE_JOIN", -1)
            jd.reset_gate_for_testing()


class TestExecutorGate:
    def _join_plan(self):
        p = Plan()
        l = p.add(MemorySourceOp(table="left"))
        r = p.add(MemorySourceOp(table="right"))
        j = p.add(JoinOp(how="inner", left_on=["k"], right_on=["k"],
                         output=[("left", "k", "k"), ("left", "a", "a"),
                                 ("right", "b", "b")]), parents=[l, r])
        p.add(MemorySinkOp(name="out"), parents=[j])
        return p

    def _stores(self, n=1 << 17):
        rng = np.random.default_rng(9)
        ts = TableStore()
        lt = ts.create("left", Relation.of(("k", DT.INT64), ("a", DT.INT64)),
                       batch_rows=1 << 16)
        rt = ts.create("right", Relation.of(("k", DT.INT64), ("b", DT.INT64)),
                       batch_rows=1 << 16)
        lt.write({"k": rng.integers(0, n // 4, n),
                  "a": np.arange(n, dtype=np.int64)})
        rt.write({"k": rng.integers(0, n // 4, n),
                  "b": np.arange(n, dtype=np.int64)})
        return ts

    def test_gated_device_join_matches_host(self):
        ts = self._stores()
        plan = self._join_plan()
        host = PlanExecutor(plan, ts).run()["out"].to_pandas()
        flags.set_for_testing("PX_DEVICE_JOIN", 1)
        try:
            ex = PlanExecutor(plan, ts)
            dev = ex.run()["out"].to_pandas()
            assert ex.stats.get("device_joins", 0) == 1
        finally:
            flags.set_for_testing("PX_DEVICE_JOIN", -1)
        cols = ["k", "a", "b"]
        h = host.sort_values(cols).reset_index(drop=True)
        d = dev.sort_values(cols).reset_index(drop=True)
        pd.testing.assert_frame_equal(h, d, check_dtype=False)

    def test_small_joins_stay_on_host(self):
        ts = self._stores(n=1000)
        flags.set_for_testing("PX_DEVICE_JOIN", 1)
        try:
            ex = PlanExecutor(self._join_plan(), ts)
            ex.run()
            assert ex.stats.get("device_joins", 0) == 0
        finally:
            flags.set_for_testing("PX_DEVICE_JOIN", -1)
