"""Parity tests for the round-5 performance paths.

1. Device merge+finalize (executor._merge_finalize_fn): per-feed partials
   merge ON device and sketch UDAs finalize there — results must be
   bit-compatible with the host finalize path.
2. np_partial (CPU streaming fast path): bincount/native accumulation must
   produce the same state/results as the jitted kernel path.
3. native px_window_agg fused pass vs the numpy fallback.
"""
import numpy as np
import pandas as pd
import pytest

import pixie_tpu  # noqa: F401  (x64)
from pixie_tpu.engine import np_partial
from pixie_tpu.engine.executor import PlanExecutor
from pixie_tpu.engine.stream import stream_pxl
from pixie_tpu.plan import AggExpr, AggOp, MemorySinkOp, MemorySourceOp, Plan
from pixie_tpu.table import TableStore
from pixie_tpu.types import DataType as DT, Relation

SEC = 1_000_000_000


def _store(n=200_000, seed=0, strings=True):
    rng = np.random.default_rng(seed)
    ts = TableStore()
    cols = [("time_", DT.TIME64NS), ("latency", DT.FLOAT64),
            ("status", DT.INT64)]
    if strings:
        cols.insert(1, ("service", DT.STRING))
    t = ts.create("http_events", Relation.of(*cols), batch_rows=1 << 14)
    data = {
        "time_": np.sort(rng.integers(0, 600 * SEC, n)).astype(np.int64),
        "latency": rng.exponential(50.0, n),
        "status": rng.choice([200, 404, 500], n).astype(np.int64),
    }
    if strings:
        data["service"] = rng.choice(
            [f"svc-{i}" for i in range(12)], n).tolist()
    t.write(data)
    return ts


def _agg_plan(groups, values, windowed=False):
    p = Plan()
    src = p.add(MemorySourceOp(table="http_events"))
    agg = p.add(AggOp(groups=groups, values=values, windowed=windowed),
                parents=[src])
    p.add(MemorySinkOp(name="out"), parents=[agg])
    return p


VALUES = [AggExpr("cnt", "count", None), AggExpr("avg", "mean", "latency"),
          AggExpr("p50", "p50", "latency"), AggExpr("p99", "p99", "latency"),
          AggExpr("mx", "max", "latency"), AggExpr("qs", "quantiles",
                                                   "latency")]


def _run(plan, ts, backend):
    return PlanExecutor(plan, ts, force_backend=backend).run()["out"]


def _cmp(a, b, sort_cols):
    ga = a.to_pandas().sort_values(sort_cols).reset_index(drop=True)
    gb = b.to_pandas().sort_values(sort_cols).reset_index(drop=True)
    pd.testing.assert_frame_equal(ga, gb, check_dtype=False)


class TestDeviceMergeFinalizeParity:
    def test_grouped_all_udas(self):
        ts = _store()
        plan = _agg_plan(["service", "status"], VALUES)
        _cmp(_run(plan, ts, "cpu"), _run(plan, ts, "device"),
             ["service", "status"])

    def test_multi_feed_merge(self, monkeypatch):
        # tiny feed target → many per-feed partials → device merge arity > 1
        from pixie_tpu.engine import executor as X

        monkeypatch.setattr(X, "FEED_ROWS", 1 << 14)
        ts = _store(n=100_000)
        plan = _agg_plan(["service"], VALUES)
        _cmp(_run(plan, ts, "cpu"), _run(plan, ts, "device"), ["service"])

    def test_distributed_partial_state_not_finalized(self):
        """The partial wire path must ship raw mergeable state even on the
        accelerator backend (device finalize would break cross-agent
        merges)."""
        from pixie_tpu.parallel.cluster import LocalCluster

        stores = {"a": _store(seed=1), "b": _store(seed=2)}
        script = """
df = px.DataFrame(table='http_events')
df = df.groupby('service').agg(cnt=('latency', px.count), p50=('latency', px.p50))
px.display(df, 'out')
"""
        got = LocalCluster(stores).query(script)["out"].to_pandas()
        # oracle: run over a merged single store
        ts = TableStore()
        rel = stores["a"].table("http_events").relation
        t = ts.create("http_events", rel, batch_rows=1 << 14)
        for s in stores.values():
            for rb, _, _ in s.table("http_events").cursor():
                cols = {}
                for c in rel:
                    arr = rb.columns[c.name][: rb.num_valid]
                    if c.name in s.table("http_events").dictionaries:
                        cols[c.name] = s.table(
                            "http_events").dictionaries[c.name].decode(arr)
                    else:
                        cols[c.name] = arr
                t.write(cols)
        from pixie_tpu.collect.schemas import all_schemas
        from pixie_tpu.compiler import compile_pxl
        from pixie_tpu.engine import execute_plan

        q = compile_pxl(script, {**all_schemas(), **ts.schemas()})
        want = execute_plan(q.plan, ts)["out"].to_pandas()
        g = got.sort_values("service").reset_index(drop=True)
        w = want.sort_values("service").reset_index(drop=True)
        pd.testing.assert_frame_equal(g, w, check_dtype=False)


class TestNpPartialParity:
    def _poll_results(self, fast: bool, monkeypatch):
        if not fast:
            monkeypatch.setattr(np_partial, "eligible",
                                lambda *a, **k: False)
        ts = TableStore()
        rel = Relation.of(("time_", DT.TIME64NS), ("service", DT.STRING),
                          ("svc_id", DT.INT64), ("latency", DT.FLOAT64))
        t = ts.create("http_events", rel, batch_rows=1 << 12)
        sq = stream_pxl(
            "df = px.DataFrame(table='http_events').stream()\n"
            "df = df.rolling('10s').groupby('service').agg("
            "cnt=('latency', px.count), avg=('latency', px.mean), "
            "p50=('latency', px.p50))\n"
            "px.display(df, 'win')\n", ts)
        rng = np.random.default_rng(7)
        out = []
        for i in range(3):
            n = 60_000
            t.write({
                "time_": (np.arange(n) * (60 * SEC // n)
                          + i * 60 * SEC).astype(np.int64),
                "service": rng.choice(["a", "b", "c"], n).tolist(),
                "svc_id": rng.integers(0, 5, n).astype(np.int64),
                "latency": rng.exponential(20.0, n),
            })
            got = sq.poll()
            if got:
                out.append(got["win"].to_pandas())
        fin = sq.close()
        if fin:
            out.append(fin["win"].to_pandas())
        df = pd.concat(out, ignore_index=True)
        return df.sort_values(["time_", "service"]).reset_index(drop=True)

    def test_stream_poll_matches_kernel_path(self, monkeypatch):
        fast = self._poll_results(True, monkeypatch)
        with pytest.MonkeyPatch.context() as mp:
            slow = self._poll_results(False, mp)
        pd.testing.assert_frame_equal(fast, slow, check_dtype=False)

    def test_fast_path_engages(self):
        ts = TableStore()
        rel = Relation.of(("time_", DT.TIME64NS), ("svc_id", DT.INT64),
                          ("latency", DT.FLOAT64))
        t = ts.create("http_events", rel, batch_rows=1 << 12)
        n = 50_000
        t.write({"time_": np.arange(n, dtype=np.int64) * 1000,
                 "svc_id": np.arange(n, dtype=np.int64) % 7,
                 "latency": np.ones(n)})
        plan = _agg_plan(["svc_id"], [AggExpr("cnt", "count", None),
                                      AggExpr("p50", "p50", "latency")])
        # mesh=None + cpu backend == exactly how streaming polls execute
        ex = PlanExecutor(plan, ts, mesh=None, force_backend="cpu")
        out = ex.run()["out"]
        assert ex.stats.get("np_fast_polls", 0) >= 1
        assert out.to_pandas()["cnt"].sum() == n


class TestNpPartialEdgeCases:
    def test_int64_sum_exact_beyond_2_53(self):
        """The numpy fast path must keep int64 sums EXACT (the kernel path's
        limb-GEMM guarantee), not round through f64."""
        ts = TableStore()
        rel = Relation.of(("time_", DT.TIME64NS), ("k", DT.INT64),
                          ("big", DT.INT64))
        t = ts.create("http_events", rel, batch_rows=1 << 12)
        vals = np.array([2**60 + 1, 2**60 + 3, 5], dtype=np.int64)
        t.write({"time_": np.array([1, 2, 3], dtype=np.int64),
                 "k": np.array([0, 0, 1], dtype=np.int64), "big": vals})
        plan = _agg_plan(["k"], [AggExpr("s", "sum", "big")])
        ex = PlanExecutor(plan, ts, mesh=None, force_backend="cpu")
        out = ex.run()["out"].to_pandas().sort_values("k")
        assert ex.stats.get("np_fast_polls", 0) >= 1
        assert out["s"].tolist() == [2**61 + 4, 5]

    def test_empty_feed_contribution(self):
        """A feed whose mask selects zero rows must contribute identity
        state, not crash (min/max reduceat on empty)."""
        ts = TableStore()
        rel = Relation.of(("time_", DT.TIME64NS), ("k", DT.INT64),
                          ("v", DT.FLOAT64))
        t = ts.create("http_events", rel, batch_rows=1 << 12)
        t.write({"time_": np.array([100 * SEC], dtype=np.int64),
                 "k": np.array([0], dtype=np.int64),
                 "v": np.array([7.0])})
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events",
                                   start_time=200 * SEC))
        agg = p.add(AggOp(groups=["k"], values=[
            AggExpr("mn", "min", "v"), AggExpr("mx", "max", "v"),
            AggExpr("cnt", "count", None)]), parents=[src])
        p.add(MemorySinkOp(name="out"), parents=[agg])
        out = PlanExecutor(p, ts, mesh=None,
                           force_backend="cpu").run()["out"]
        assert out.num_rows == 0  # nothing in range — and no crash


class TestNativeWindowAgg:
    def test_fused_matches_numpy_fallback(self, monkeypatch):
        lib = np_partial._native()
        if lib is None:
            pytest.skip("native library unavailable")
        from pixie_tpu.ops.sketch import LogHistogram

        lh = LogHistogram()
        rng = np.random.default_rng(3)
        n, G = 100_000, 64
        tcol = np.sort(rng.integers(0, G * 10 * SEC, n)).astype(np.int64)
        vals = rng.exponential(50.0, n)
        import ctypes

        counts = np.zeros(G, dtype=np.int64)
        sums = np.zeros(G, dtype=np.float64)
        hist = np.zeros((G, lh.width), dtype=np.float32)
        P = ctypes.POINTER
        import math

        lib.px_window_agg(
            ctypes.c_int64(n),
            tcol.ctypes.data_as(P(ctypes.c_int64)),
            ctypes.c_int64(10 * SEC), ctypes.c_int64(0), ctypes.c_int64(G),
            vals.ctypes.data_as(P(ctypes.c_double)),
            ctypes.c_int64(lh.width),
            ctypes.c_float(1.0 / math.log(lh.gamma)),
            ctypes.c_float(lh.min_value),
            counts.ctypes.data_as(P(ctypes.c_int64)),
            sums.ctypes.data_as(P(ctypes.c_double)),
            hist.ctypes.data_as(P(ctypes.c_float)),
        )
        g = np.clip(tcol // (10 * SEC), 0, G - 1)
        np.testing.assert_array_equal(counts, np.bincount(g, minlength=G))
        np.testing.assert_allclose(
            sums, np.bincount(g, weights=vals, minlength=G), rtol=1e-12)
        bins = np_partial._bin_index_np(lh, vals)
        ref = np.bincount(g * lh.width + bins.astype(np.int64),
                          minlength=G * lh.width).reshape(G, lh.width)
        # logf vs numpy SIMD log can disagree by one bin at exact bucket
        # boundaries — allow a tiny count of boundary flips, none elsewhere
        diff = np.abs(hist - ref.astype(np.float32))
        assert diff.sum() <= 2 * n * 1e-4
