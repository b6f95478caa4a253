"""Bench regression guard (`bench.py --check-regressions`): the tier-1 gate
that fails a PR on >15% rows_per_sec drops OR >15% p50_ms latency rises
instead of letting them surface in the next round's verdict (the r05 ingest
regression path; the r5 interactive-latency blind spot)."""
import json

import bench


def _doc(ingest=22_000_000, join=125_000_000, rows=64_000_000,
         p50=80.0, warm_p50=12.0):
    return {
        "rows": rows,
        "sweep": {"1000000": {"rows_per_sec": 50_000_000, "p50_ms": 20.0,
                              "tpu_path_p50_ms": 95.0}},
        "configs": {
            "ingest_microbench": {"rows_per_sec": ingest},
            "3_flow_join": {"rows_per_sec": join, "rows": 16_000_000},
            "interactive_1m": {
                "rows": 1_000_000, "rows_per_sec": 12_500_000,
                "p50_ms": p50, "tpu_path_p50_ms": 110.0,
                "warm_matview": {"p50_ms": warm_p50, "vs_pandas": 9.0},
            },
        },
    }


def test_compare_flags_drops_over_threshold():
    prior, now = _doc(), _doc(ingest=16_700_000)  # the r05 regression shape
    regs = bench.compare_bench(prior, now, threshold=0.15)
    assert [r["key"] for r in regs] == ["configs.ingest_microbench"]
    assert regs[0]["prior"] == 22_000_000
    assert regs[0]["drop_pct"] > 15


def test_compare_tolerates_small_drops_and_gains():
    prior = _doc()
    now = _doc(ingest=int(22_000_000 * 0.9), join=200_000_000)  # -10% / +60%
    assert bench.compare_bench(prior, now, threshold=0.15) == []


def test_compare_only_shape_matched_points():
    """A --smoke/--quick run (different shapes) must not 'regress' vs a full
    run: mismatched rows are skipped entirely."""
    prior = _doc()
    now = _doc(join=1_000, rows=64_000_000)
    now["configs"]["3_flow_join"]["rows"] = 200_000  # smoke-sized join
    now["sweep"] = {"200000": {"rows_per_sec": 1_000}}  # different sweep point
    regs = bench.compare_bench(prior, now, threshold=0.15)
    assert regs == []


def test_latency_rise_flags_regression():
    """A >15% p50 increase fails even when every rows_per_sec key held — the
    interactive path is latency-bound (ISSUE-3 satellite)."""
    prior, now = _doc(), _doc(p50=100.0)  # +25% routed p50
    regs = bench.compare_bench(prior, now, threshold=0.15)
    assert [r["key"] for r in regs] == ["configs.interactive_1m.p50_ms"]
    assert regs[0]["rise_pct"] > 15
    assert "REGRESSION" not in bench._format_regression(regs[0])
    assert "ms p50" in bench._format_regression(regs[0])


def test_latency_covers_nested_and_sweep_points():
    pts = bench.bench_latency_points(_doc())
    assert pts["sweep.1000000.p50_ms"] == (20.0, 1_000_000)
    assert pts["sweep.1000000.tpu_path_p50_ms"] == (95.0, 1_000_000)
    assert pts["configs.interactive_1m.p50_ms"] == (80.0, 1_000_000)
    assert pts["configs.interactive_1m.warm_matview.p50_ms"] == (
        12.0, 1_000_000)
    # warm-matview regression is caught through the nested point
    regs = bench.compare_bench(_doc(), _doc(warm_p50=30.0), threshold=0.15)
    assert [r["key"] for r in regs] == [
        "configs.interactive_1m.warm_matview.p50_ms"]


def test_latency_tolerates_improvement_and_shape_mismatch():
    assert bench.compare_bench(_doc(), _doc(p50=40.0), threshold=0.15) == []
    now = _doc(p50=500.0)
    now["configs"]["interactive_1m"]["rows"] = 200_000  # smoke shape
    assert bench.compare_bench(_doc(), now, threshold=0.15) == []


def test_ingest_shape_matching_old_and_new_docs():
    """r06 records the ingest shape (`rows`); pre-r06 docs didn't — the
    guard assumes the full-run 32M shape for those, so the ingest point
    stays guarded ACROSS the key addition instead of silently unmatched."""
    prior = _doc()  # pre-r06 shape: no rows key on ingest_microbench
    assert bench.bench_points(prior)["configs.ingest_microbench"] == (
        22_000_000, 32_000_000)
    now = _doc(ingest=15_000_000)
    now["configs"]["ingest_microbench"]["rows"] = 32_000_000
    regs = bench.compare_bench(prior, now, threshold=0.15)
    assert "configs.ingest_microbench" in [r["key"] for r in regs]
    # a --quick run ingests fewer rows: different shape, no comparison
    now["configs"]["ingest_microbench"]["rows"] = 4_000_000
    assert bench.compare_bench(prior, now, threshold=0.15) == []


def test_mfu_and_device_join_points_guarded():
    """r06's new rate points: mxu_est.mfu_vs_peak and the device-join unit
    bench (rows-keyed) fail the guard on >15% drops — the device-kernel
    efficiency work must not silently regress (ISSUE-5 satellite)."""
    prior = _doc()
    prior["mxu_est"] = {"achieved_flops_per_sec": 2.2e13,
                        "mfu_vs_peak": 0.11}
    prior["configs"]["device_join_unit"] = {
        "rows_per_sec": 11_000_000, "rows": 16_000_000, "path": "native_cpu"}
    pts = bench.bench_points(prior)
    assert pts["mxu_est.mfu_vs_peak"] == (0.11, 64_000_000)
    assert pts["configs.device_join_unit"] == (11_000_000, 16_000_000)

    now = json.loads(json.dumps(prior))
    now["mxu_est"]["mfu_vs_peak"] = 0.08  # -27%
    now["configs"]["device_join_unit"]["rows_per_sec"] = 8_000_000  # -27%
    regs = bench.compare_bench(prior, now, threshold=0.15)
    assert {r["key"] for r in regs} == {"mxu_est.mfu_vs_peak",
                                        "configs.device_join_unit"}
    # pre-r06 prior (agg-only model, no mfu point / no join rows key):
    # the new-model numbers must NOT compare against the old model's
    old = _doc()
    old["configs"]["device_join_unit"] = {"rows_per_sec": 868_456}
    assert bench.compare_bench(old, now, threshold=0.15) == []


def test_sharded_agg_config_guarded():
    """ISSUE-7: the promoted `sharded_agg_64m` config is a guarded
    throughput AND latency point — MULTICHIP rounds carry real numbers and
    a >15% rows/s drop or p50 rise fails the PR; smoke shapes never
    compare against full runs."""
    prior = _doc()
    prior["configs"]["sharded_agg_64m"] = {
        "rows": 64_000_000, "rows_per_sec": 40_000_000, "p50_ms": 1600.0,
        "n_devices": 8, "mode": "local", "bit_equal": True}
    pts = bench.bench_points(prior)
    assert pts["configs.sharded_agg_64m"] == (40_000_000, 64_000_000)
    lpts = bench.bench_latency_points(prior)
    assert lpts["configs.sharded_agg_64m.p50_ms"] == (1600.0, 64_000_000)

    now = json.loads(json.dumps(prior))
    now["configs"]["sharded_agg_64m"]["rows_per_sec"] = 30_000_000  # -25%
    regs = bench.compare_bench(prior, now, threshold=0.15)
    assert "configs.sharded_agg_64m" in [r["key"] for r in regs]
    now2 = json.loads(json.dumps(prior))
    now2["configs"]["sharded_agg_64m"]["p50_ms"] = 2200.0  # +37%
    regs2 = bench.compare_bench(prior, now2, threshold=0.15)
    assert "configs.sharded_agg_64m.p50_ms" in [r["key"] for r in regs2]
    # smoke shape: no comparison
    now["configs"]["sharded_agg_64m"]["rows"] = 200_000
    assert bench.compare_bench(prior, now, threshold=0.15) == []


def test_rtt_floor_is_environmental_not_a_latency_point():
    """wave_rtt_floor_ms measures the ENVIRONMENT (the link's RTT), not the
    code: a noisier box must not read as a latency regression, and the
    forced-TPU p50 keeps its own guard besides the floor ratio."""
    prior = _doc()
    prior["configs"]["interactive_1m"]["wave_rtt_floor_ms"] = 95.0
    prior["configs"]["interactive_1m"]["tpu_path_vs_rtt_floor"] = 1.2
    pts = bench.bench_latency_points(prior)
    assert not any("floor" in k for k in pts)
    assert "configs.interactive_1m.tpu_path_p50_ms" in pts
    now = _doc()
    now["configs"]["interactive_1m"]["wave_rtt_floor_ms"] = 300.0
    assert bench.compare_bench(prior, now, threshold=0.15) == []


def test_interactive_vs_pandas_floor():
    """ISSUE-6 acceptance: routed interactive_1m must stay ≥5x pandas at
    the full 1M shape — an ABSOLUTE floor, so a slow ratchet down across
    rounds cannot hide below the relative threshold."""
    prior, now = _doc(), _doc()
    now["configs"]["interactive_1m"]["vs_pandas"] = 3.4
    regs = bench.compare_bench(prior, now, threshold=0.15)
    assert [r["key"] for r in regs] == ["configs.interactive_1m.vs_pandas"]
    assert regs[0]["floor"] == 5.0 and regs[0]["now"] == 3.4
    assert "below floor" in bench._format_regression(regs[0])
    # at/above the floor: clean
    now["configs"]["interactive_1m"]["vs_pandas"] = 5.0
    assert bench.compare_bench(prior, now, threshold=0.15) == []
    # --smoke/--quick shapes never trip the full-run floor
    now["configs"]["interactive_1m"]["vs_pandas"] = 1.0
    now["configs"]["interactive_1m"]["rows"] = 200_000
    assert bench.absolute_floors(now) == []


def test_wholeplan_unit_p50_guarded():
    """The wholeplan_native_unit config is a guarded latency AND
    throughput point (ISSUE-6 satellite)."""
    prior = _doc()
    prior["configs"]["wholeplan_native_unit"] = {
        "rows": 1_000_000, "rows_per_sec": 60_000_000, "p50_ms": 16.0,
        "path": "native"}
    pts = bench.bench_latency_points(prior)
    assert pts["configs.wholeplan_native_unit.p50_ms"] == (16.0, 1_000_000)
    assert bench.bench_points(prior)["configs.wholeplan_native_unit"] == (
        60_000_000, 1_000_000)
    now = json.loads(json.dumps(prior))
    now["configs"]["wholeplan_native_unit"]["p50_ms"] = 25.0  # +56%
    regs = bench.compare_bench(prior, now, threshold=0.15)
    assert "configs.wholeplan_native_unit.p50_ms" in [r["key"] for r in regs]
    # a silent native->interpreted dispatch fallback fails even when the
    # p50 holds
    now2 = json.loads(json.dumps(prior))
    now2["configs"]["wholeplan_native_unit"]["path"] = "interpreted"
    regs2 = bench.compare_bench(prior, now2, threshold=0.15)
    assert [r["key"] for r in regs2] == [
        "configs.wholeplan_native_unit.path"]
    assert "native -> interpreted" in bench._format_regression(regs2[0])
    # shape-mismatched (smoke) runs don't compare the path either
    now2["configs"]["wholeplan_native_unit"]["rows"] = 200_000
    assert bench.compare_bench(prior, now2, threshold=0.15) == []


def _serving_doc(rows=560, goodput=60.0, p99=9000.0, fairness=1.2,
                 shed_inter=0.0, err=0.0, rss=400.0, shed_total=40):
    doc = _doc()
    doc["configs"]["serving_load"] = {
        "rows": rows, "clients": rows, "goodput_qps": goodput,
        "p50_ms": 2500.0, "p99_ms": p99, "fairness_ratio": fairness,
        "shed_rate": 0.05, "shed_rate_interactive": shed_inter,
        "error_rate": err, "shed_total": shed_total,
        "rss_growth_mb": rss, "queue_bounded": True,
    }
    return doc


def test_serving_load_points_guarded():
    """ISSUE-9: serving_load is a guarded goodput AND latency (p50 + p99)
    point — the multi-tenant closed-loop path may not silently lose
    throughput or grow its interactive tail."""
    prior = _serving_doc()
    pts = bench.bench_points(prior)
    assert pts["configs.serving_load.goodput_qps"] == (60.0, 560)
    lpts = bench.bench_latency_points(prior)
    assert lpts["configs.serving_load.p99_ms"] == (9000.0, 560)
    assert lpts["configs.serving_load.p50_ms"] == (2500.0, 560)
    regs = bench.compare_bench(prior, _serving_doc(goodput=40.0),
                               threshold=0.15)  # -33% goodput
    assert "configs.serving_load.goodput_qps" in [r["key"] for r in regs]
    regs = bench.compare_bench(prior, _serving_doc(p99=12_000.0),
                               threshold=0.15)  # +33% p99
    assert "configs.serving_load.p99_ms" in [r["key"] for r in regs]
    # smoke shape (60 clients) never compares against the full 560 run
    assert bench.compare_bench(prior, _serving_doc(rows=60, goodput=5.0,
                                                   p99=20_000.0),
                               threshold=0.15) == []


def test_serving_load_absolute_ceilings_and_shed_floor():
    """The serving acceptance criteria hold ABSOLUTELY at the full shape:
    fairness ≤ 2.0, interactive shed rate / error budget / RSS growth
    ceilings, and ≥1 shed (the bounded-queue proof — an oversized batch
    flood that never overflowed means the bound wasn't enforced)."""
    ok = _serving_doc()
    assert bench.absolute_floors(ok) == []
    bad = _serving_doc(fairness=2.4)
    regs = bench.absolute_floors(bad)
    assert [r["key"] for r in regs] == [
        "configs.serving_load.fairness_ratio"]
    assert regs[0]["ceiling"] == 2.0 and regs[0]["now"] == 2.4
    assert "above ceiling" in bench._format_regression(regs[0])
    assert bench.absolute_floors(_serving_doc(shed_inter=0.5))
    assert bench.absolute_floors(_serving_doc(err=0.1))
    assert bench.absolute_floors(_serving_doc(rss=4096.0))
    regs = bench.absolute_floors(_serving_doc(shed_total=0))
    assert [r["key"] for r in regs] == ["configs.serving_load.shed_total"]
    # ceilings are violations through compare_bench too (the CI entry)
    assert bench.compare_bench(_serving_doc(), _serving_doc(fairness=2.4),
                               threshold=0.15)
    # smoke shapes trip neither floors nor ceilings
    assert bench.absolute_floors(
        _serving_doc(rows=60, fairness=3.0, shed_total=0)) == []


def test_serving_load_harness_crash_fails_guards():
    """A crashed harness returns {rows, error} — at the guarded shape that
    must TRIP every absolute bound (missing keys), not silently disable
    the serving CI coverage."""
    doc = _doc()
    doc["configs"]["serving_load"] = {"rows": 560,
                                      "error": "RuntimeError: boom"}
    regs = bench.absolute_floors(doc)
    n_serving = len([k for k, *_ in bench.ABS_CEILINGS + bench.ABS_FLOORS
                     if k.startswith("configs.serving_load")])
    assert len(regs) == n_serving
    assert all(r.get("missing") for r in regs)
    assert all(r["key"].startswith("configs.serving_load") for r in regs)
    assert "missing at guarded shape" in bench._format_regression(regs[0])
    assert "boom" in bench._format_regression(regs[0])
    # a smoke-shape crash doesn't (smoke isn't guarded)
    doc["configs"]["serving_load"] = {"rows": 60, "error": "boom"}
    assert bench.absolute_floors(doc) == []


def _chaos_doc(rows=80, recovery=1.0, bit_equal=1.0, errors=0,
               added_p99=900.0, kills=11):
    doc = _doc()
    doc["configs"]["chaos_recovery"] = {
        "rows": rows, "queries": rows, "kills": kills,
        "recovery_rate": recovery, "bit_equal_frac": bit_equal,
        "client_errors": errors, "added_p99_ms": added_p99,
    }
    return doc


def test_chaos_recovery_absolute_guards():
    """ISSUE-10 acceptance held by CI: under the injected kill-and-restart
    schedule every retryable query recovers (recovery_rate == 1.0) with
    BIT-equal results (bit_equal_frac == 1.0), zero client-visible errors,
    bounded added p99 — and the schedule must actually have killed agents."""
    assert bench.absolute_floors(_chaos_doc()) == []
    regs = bench.absolute_floors(_chaos_doc(recovery=0.975))
    assert [r["key"] for r in regs] == [
        "configs.chaos_recovery.recovery_rate"]
    assert "below floor" in bench._format_regression(regs[0])
    regs = bench.absolute_floors(_chaos_doc(bit_equal=0.99))
    assert [r["key"] for r in regs] == [
        "configs.chaos_recovery.bit_equal_frac"]
    assert bench.absolute_floors(_chaos_doc(errors=1))
    assert bench.absolute_floors(_chaos_doc(added_p99=9_000.0))
    assert bench.absolute_floors(_chaos_doc(kills=0))
    # the guards ride compare_bench (the CI entry point) too
    assert bench.compare_bench(_chaos_doc(), _chaos_doc(bit_equal=0.5),
                               threshold=0.15)
    # smoke shape (16 queries) trips nothing — shape-matched guards only
    assert bench.absolute_floors(
        _chaos_doc(rows=16, recovery=0.5, bit_equal=0.0, errors=5,
                   kills=0)) == []


def test_chaos_recovery_harness_crash_fails_guards():
    """A crashed chaos harness at the guarded shape must TRIP the absolute
    bounds (missing keys), not silently disable the fault-tolerance CI."""
    doc = _doc()
    doc["configs"]["chaos_recovery"] = {"rows": 80, "error": "boom"}
    regs = bench.absolute_floors(doc)
    assert regs and all(r.get("missing") for r in regs)
    assert all(r["key"].startswith("configs.chaos_recovery") for r in regs)


def _chaos_hard_doc(rows=40, row_loss=0, recovery=1.0, bit_equal=1.0,
                    errors=0, kills=5, wipes=2, recovery_s=2.1,
                    journal_rows=17_000.0, repl_rows=16_000.0):
    doc = _doc()
    doc["configs"]["chaos_recovery_hard"] = {
        "rows": rows, "queries": rows, "kills": kills, "wipe_kills": wipes,
        "row_loss": row_loss, "recovery_rate": recovery,
        "bit_equal_frac": bit_equal, "client_errors": errors,
        "recovery_s_max": recovery_s, "journal_replayed_rows": journal_rows,
        "repl_rehydrated_rows": repl_rows,
    }
    return doc


def test_chaos_recovery_hard_absolute_guards():
    """ISSUE-12 acceptance held by CI: true pod losses (store dropped, data
    dir alternately wiped) lose ZERO acknowledged rows, stay bit-equal with
    zero client errors, recover within the budget — and both recovery paths
    (journal replay AND peer-fetch rehydration) must actually have run."""
    assert bench.absolute_floors(_chaos_hard_doc()) == []
    assert [r["key"] for r in bench.absolute_floors(
        _chaos_hard_doc(row_loss=1))] == [
        "configs.chaos_recovery_hard.row_loss"]
    assert bench.absolute_floors(_chaos_hard_doc(bit_equal=0.99))
    assert bench.absolute_floors(_chaos_hard_doc(recovery=0.9))
    assert bench.absolute_floors(_chaos_hard_doc(errors=1))
    assert bench.absolute_floors(_chaos_hard_doc(recovery_s=30.0))
    assert bench.absolute_floors(_chaos_hard_doc(kills=1))
    assert bench.absolute_floors(_chaos_hard_doc(wipes=0))
    # a run that never replayed a journal or never rehydrated from peers
    # proved only half the recovery machinery
    assert bench.absolute_floors(_chaos_hard_doc(journal_rows=0.0))
    assert bench.absolute_floors(_chaos_hard_doc(repl_rows=0.0))
    # rides the CI entry point, and smoke shapes trip nothing
    assert bench.compare_bench(_chaos_hard_doc(), _chaos_hard_doc(row_loss=9),
                               threshold=0.15)
    assert bench.absolute_floors(
        _chaos_hard_doc(rows=12, row_loss=5, bit_equal=0.0, kills=0,
                        journal_rows=0.0, repl_rows=0.0)) == []


def test_chaos_recovery_hard_harness_crash_fails_guards():
    doc = _doc()
    doc["configs"]["chaos_recovery_hard"] = {"rows": 40, "error": "boom"}
    regs = bench.absolute_floors(doc)
    assert regs and all(r.get("missing") for r in regs)
    assert all(r["key"].startswith("configs.chaos_recovery_hard")
               for r in regs)


def test_budget_json_line_sheds_diagnostics_keeps_headline():
    """The stdout line must fit the driver's ~2000-char tail cap
    (a round's line once outgrew it and parsed as null): the
    budgeter sheds diagnostic keys in priority order, never headline
    ones."""
    doc = _doc()
    doc["metric"] = "x"
    doc["value"] = 1
    doc["exec_split"] = {f"c{i}": {"e2e_ms": 1.0,
                                   "_debug": {"pad": "y" * 120}}
                        for i in range(8)}
    doc["roofline"] = {"note": "z" * 400}
    doc["sketch_update"] = {"note": "w" * 400}
    line = bench.budget_json_line(doc, cap=1200)
    assert len(line) <= 1200
    out = json.loads(line)
    assert out["metric"] == "x" and "configs" in out and "sweep" in out
    assert "_debug" not in json.dumps(out.get("exec_split", {}))
    # under budget: nothing shed
    small = {"metric": "x", "configs": {}, "roofline": {"n": 1}}
    assert json.loads(bench.budget_json_line(small, cap=1200)) == small


def test_check_regressions_cli_paths(tmp_path, capsys, monkeypatch):
    """File mode: a doc with a dropped config fails (exit 1) against the
    prior BENCH round beside bench.py; the prior round's own numbers pass
    (exit 0).  The prior round is built here under tmp_path (bench.py
    looks beside its own file), so the repo need carry no records."""
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    (tmp_path / "BENCH_r01.json").write_text(
        json.dumps({"parsed": None, "tail": "truncated..."}))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({"parsed": _doc()}))
    prior, prior_path = bench.latest_bench_doc()
    assert prior == _doc() and prior_path.endswith("BENCH_r02.json")

    same = tmp_path / "same.json"
    same.write_text(json.dumps(prior))
    assert bench.check_regressions(str(same), threshold=0.15) == 0

    import copy

    bad = copy.deepcopy(prior)
    key = next(k for k, v in bad["configs"].items()
               if isinstance(v, dict) and "rows_per_sec" in v)
    bad["configs"][key]["rows_per_sec"] = int(
        bad["configs"][key]["rows_per_sec"] * 0.5)
    badf = tmp_path / "bad.json"
    badf.write_text(json.dumps({"parsed": bad}))  # wrapper shape accepted too
    assert bench.check_regressions(str(badf), threshold=0.15) == 1
    err = capsys.readouterr().err
    assert "REGRESSION" in err and key in err


def test_check_regressions_rejects_unparsed(tmp_path):
    f = tmp_path / "empty.json"
    f.write_text(json.dumps({"parsed": None, "tail": "truncated..."}))
    assert bench.check_regressions(str(f), threshold=0.15) == 2


def _batched_doc(rows=560, speedup=1.5, size_p50=4.0, bit_equal=1,
                 **kw):
    doc = _serving_doc(rows=rows, **kw)
    doc["configs"]["serving_load"].update({
        "unbatched_goodput_qps": 30.0,
        "batched_goodput_qps": 30.0 * speedup,
        "batched_speedup": speedup,
        "batch_size_p50": size_p50,
        "batched_bit_equal": bit_equal,
        "batch_clients": 120,
    })
    return doc


def test_serving_load_batched_floors():
    """ISSUE-13: the batched-mode shape holds ABSOLUTELY at the full
    serving_load shape — aggregate goodput at 100+ concurrent warm queries
    must scale superlinearly vs the unbatched path (speedup floor), batches
    must actually form (batch_size_p50 floor), and every batched answer
    must be bit-equal to its solo baseline."""
    assert bench.absolute_floors(_batched_doc()) == []
    regs = bench.absolute_floors(_batched_doc(speedup=0.9))
    assert [r["key"] for r in regs] == [
        "configs.serving_load.batched_speedup"]
    assert regs[0]["floor"] == 1.1
    assert "below floor" in bench._format_regression(regs[0])
    assert bench.absolute_floors(_batched_doc(size_p50=1.0))
    assert bench.absolute_floors(_batched_doc(bit_equal=0))
    # smoke shape (60 clients) never trips the full-shape floors
    assert bench.absolute_floors(
        _batched_doc(rows=60, speedup=0.5, size_p50=0.0)) == []


def test_serving_load_batched_harness_crash_trips_floors():
    """A crashed batched-compare harness (error marker + missing batched
    keys at the guarded shape) FAILS the floors instead of silently
    disabling them."""
    doc = _serving_doc()
    doc["configs"]["serving_load"]["error"] = "batched_compare: Boom: x"
    regs = bench.absolute_floors(doc)
    keys = {r["key"] for r in regs}
    assert "configs.serving_load.batched_speedup" in keys
    assert all(r.get("missing") for r in regs
               if r["key"].startswith("configs.serving_load.batched"))


def _observe_doc(rows=200_000, frac=0.021, **extra):
    return {
        "rows": 64_000_000,
        "configs": {
            "observe_overhead": {
                "rows": rows, "on_p50_ms": 2.0, "off_p50_ms": 1.96,
                "overhead_frac": frac, "samples_per_arm": 48, **extra,
            },
        },
    }


def test_observe_overhead_absolute_ceiling():
    """The flight recorder's instrumentation tax is guarded ABSOLUTELY:
    overhead_frac (warm p50 with tracing+profiles+SLO on vs
    PL_TRACING_ENABLED=0) above 5% fails the round."""
    assert bench.absolute_floors(_observe_doc()) == []
    regs = bench.absolute_floors(_observe_doc(frac=0.08))
    assert [r["key"] for r in regs] == [
        "configs.observe_overhead.overhead_frac"]
    assert regs[0]["ceiling"] == 0.05 and regs[0]["now"] == 0.08
    assert "above ceiling" in bench._format_regression(regs[0])
    # a ceiling violation fails compare_bench too (the CI entry point)
    assert bench.compare_bench(_observe_doc(), _observe_doc(frac=0.2),
                               threshold=0.15)
    # a different shape never trips the 200k-row bound
    assert bench.absolute_floors(_observe_doc(rows=50_000, frac=0.5)) == []


def test_observe_overhead_doc_with_heat_cells_passes_guard():
    """The data-plane observatory rides the observe_overhead on-arm: the
    result doc grew a heat_cells field and the ABS ceiling still guards
    overhead_frac exactly as before."""
    assert bench.absolute_floors(_observe_doc(heat_cells=12)) == []
    regs = bench.absolute_floors(_observe_doc(frac=0.07, heat_cells=12))
    assert [r["key"] for r in regs] == [
        "configs.observe_overhead.overhead_frac"]


def test_observe_overhead_live_run_accounts_heat():
    """A small live observe_overhead run measures with shard-heat
    accounting active: the ON arm populates the heat model (heat_cells >
    0) while the result keeps the guarded shape."""
    import pixie_tpu.trace  # noqa: F401 — defines PL_TRACING_ENABLED
    from pixie_tpu import flags
    from pixie_tpu.table import heat

    saved_tracing = flags.get("PL_TRACING_ENABLED")
    out = bench.bench_observe_overhead(rows=4000, repeats=4)
    assert "error" not in out, out
    assert {"overhead_frac", "on_p50_ms", "off_p50_ms",
            "samples_per_arm", "heat_cells"} <= set(out)
    assert out["heat_cells"] > 0
    assert flags.get("PL_TRACING_ENABLED") == saved_tracing
    heat.reset_for_testing()


def test_observe_overhead_harness_crash_fails_guard():
    """A crashed observe_overhead harness (error marker, overhead_frac
    missing at the guarded shape) FAILS the ceiling instead of silently
    disabling the gate."""
    doc = _observe_doc()
    node = doc["configs"]["observe_overhead"]
    del node["overhead_frac"], node["on_p50_ms"], node["off_p50_ms"]
    node["error"] = "RuntimeError: boom"
    regs = bench.absolute_floors(doc)
    assert [r["key"] for r in regs] == [
        "configs.observe_overhead.overhead_frac"]
    assert regs[0].get("missing")
    assert "missing at guarded shape" in bench._format_regression(regs[0])


# ----------------------------------------------------------- elastic_ramp


def _elastic_doc(rows=16, fairness=1.1, errors=0, bit_equal=1.0,
                 scale_ups=3, scale_downs=2, preemptions=1, p99=900.0,
                 goodput=80.0):
    doc = _doc()
    doc["configs"]["elastic_ramp"] = {
        "rows": rows, "duration_s": 16.0, "queries": 1200,
        "goodput_qps": goodput, "p50_ms": 20.0, "p99_ms": p99,
        "fairness_ratio": fairness, "shed_rate": 0.0,
        "client_errors": errors, "bit_equal_frac": bit_equal,
        "scale_ups": scale_ups, "scale_downs": scale_downs,
        "preemptions": preemptions, "agents_start": 2, "agents_peak": 5,
        "agents_final": 2,
    }
    return doc


def test_elastic_ramp_points_guarded():
    """elastic_ramp is a guarded goodput AND latency config (shape-matched
    on the high-phase client count)."""
    pts = bench.bench_points(_elastic_doc())
    assert pts["configs.elastic_ramp.goodput_qps"] == (80.0, 16)
    lpts = bench.bench_latency_points(_elastic_doc())
    assert lpts["configs.elastic_ramp.p99_ms"] == (900.0, 16)
    assert lpts["configs.elastic_ramp.p50_ms"] == (20.0, 16)
    regs = bench.compare_bench(_elastic_doc(),
                               _elastic_doc(goodput=40.0, p99=2000.0),
                               threshold=0.15)
    keys = [r["key"] for r in regs]
    assert "configs.elastic_ramp.goodput_qps" in keys
    assert "configs.elastic_ramp.p99_ms" in keys


def test_elastic_ramp_absolute_guards():
    """The ROADMAP-4 acceptance holds ABSOLUTELY: scale both ways with a
    real preemption, fairness <= 2.0, zero client errors, bit-equal
    results, bounded interactive p99."""
    assert bench.absolute_floors(_elastic_doc()) == []
    assert [r["key"] for r in bench.absolute_floors(
        _elastic_doc(scale_ups=0))] == ["configs.elastic_ramp.scale_ups"]
    assert [r["key"] for r in bench.absolute_floors(
        _elastic_doc(scale_downs=0))] == [
            "configs.elastic_ramp.scale_downs"]
    assert [r["key"] for r in bench.absolute_floors(
        _elastic_doc(preemptions=0))] == [
            "configs.elastic_ramp.preemptions"]
    assert [r["key"] for r in bench.absolute_floors(
        _elastic_doc(bit_equal=0.999))] == [
            "configs.elastic_ramp.bit_equal_frac"]
    assert [r["key"] for r in bench.absolute_floors(
        _elastic_doc(fairness=2.4))] == [
            "configs.elastic_ramp.fairness_ratio"]
    assert [r["key"] for r in bench.absolute_floors(
        _elastic_doc(errors=1))] == ["configs.elastic_ramp.client_errors"]
    assert [r["key"] for r in bench.absolute_floors(
        _elastic_doc(p99=25_000.0))] == ["configs.elastic_ramp.p99_ms"]
    # smoke/quick shapes never trip the full-shape bounds
    assert bench.absolute_floors(
        _elastic_doc(rows=10, scale_ups=0, fairness=9.0, errors=3)) == []


def test_elastic_ramp_harness_crash_fails_guards():
    """A crashed elastic harness at the guarded shape must TRIP the
    absolute guards (missing-key rule), never silently disable them."""
    doc = _doc()
    doc["configs"]["elastic_ramp"] = {"rows": 16, "error": "boom"}
    regs = bench.absolute_floors(doc)
    assert len(regs) >= 7
    assert all(r["key"].startswith("configs.elastic_ramp") for r in regs)
    assert all(r.get("missing") for r in regs)


# ----------------------------------------------------- elastic_rebalance


def _rebalance_doc(rows=12, moves=1, demotions=38, bit_equal=1.0,
                   skew=1.0, row_loss=0, errors=0, ram_peak=1.0,
                   goodput=50.0, p99=700.0):
    doc = _doc()
    doc["configs"]["elastic_rebalance"] = {
        "rows": rows, "duration_s": 16.6, "queries": 900,
        "goodput_qps": goodput, "p99_ms": p99, "client_errors": errors,
        "bit_equal_frac": bit_equal, "moves": moves, "move_refusals": 0,
        "skew_final": skew, "skew_mean_final": 1.5, "row_loss": row_loss,
        "rows_total": 228_000, "demotions": demotions,
        "hot_ram_peak_mb": ram_peak,
        "agents_final": ["pem1", "pem2", "spare0"],
    }
    return doc


def test_elastic_rebalance_points_guarded():
    """elastic_rebalance is a guarded goodput AND latency config
    (shape-matched on the high-phase client count)."""
    pts = bench.bench_points(_rebalance_doc())
    assert pts["configs.elastic_rebalance.goodput_qps"] == (50.0, 12)
    lpts = bench.bench_latency_points(_rebalance_doc())
    assert lpts["configs.elastic_rebalance.p99_ms"] == (700.0, 12)


def test_elastic_rebalance_absolute_guards():
    """The ROADMAP-2 data-lifecycle acceptance holds ABSOLUTELY: the hot
    shard moved, the cold tier demoted, zero loss, bit-equal answers,
    settled skew, zero client errors, bounded sealed RAM."""
    assert bench.absolute_floors(_rebalance_doc()) == []
    assert [r["key"] for r in bench.absolute_floors(
        _rebalance_doc(moves=0))] == ["configs.elastic_rebalance.moves"]
    assert [r["key"] for r in bench.absolute_floors(
        _rebalance_doc(demotions=0))] == [
            "configs.elastic_rebalance.demotions"]
    assert [r["key"] for r in bench.absolute_floors(
        _rebalance_doc(bit_equal=0.999))] == [
            "configs.elastic_rebalance.bit_equal_frac"]
    assert [r["key"] for r in bench.absolute_floors(
        _rebalance_doc(skew=1.4))] == [
            "configs.elastic_rebalance.skew_final"]
    assert [r["key"] for r in bench.absolute_floors(
        _rebalance_doc(row_loss=24_000))] == [
            "configs.elastic_rebalance.row_loss"]
    assert [r["key"] for r in bench.absolute_floors(
        _rebalance_doc(errors=3))] == [
            "configs.elastic_rebalance.client_errors"]
    assert [r["key"] for r in bench.absolute_floors(
        _rebalance_doc(ram_peak=4.2))] == [
            "configs.elastic_rebalance.hot_ram_peak_mb"]
    # smoke/quick shapes never trip the full-shape bounds
    assert bench.absolute_floors(
        _rebalance_doc(rows=8, moves=0, demotions=0, row_loss=9)) == []


def test_elastic_rebalance_harness_crash_fails_guards():
    """A crashed rebalance harness at the guarded shape must TRIP the
    absolute guards (missing-key rule), never silently disable them."""
    doc = _doc()
    doc["configs"]["elastic_rebalance"] = {"rows": 12, "error": "boom"}
    regs = bench.absolute_floors(doc)
    assert len(regs) >= 7
    assert all(r["key"].startswith("configs.elastic_rebalance")
               for r in regs)
    assert all(r.get("missing") for r in regs)


# --------------------------------------------------------- adaptive_gates


def _adaptive_doc(rows=400_000, ratio=1.3, bit_equal=1.0, gates=4,
                  p99_ratio=1.0, fallbacks=0):
    doc = _doc()
    doc["configs"]["adaptive_gates"] = {
        "rows": rows, "queries": 96, "static_goodput_qps": 5.3,
        "adaptive_goodput_qps": 5.3 * ratio, "adaptive_vs_static": ratio,
        "static_p50_ms": 100.0, "adaptive_p50_ms": 32.0,
        "static_p99_ms": 550.0, "adaptive_p99_ms": 550.0 * p99_ratio,
        "p99_ratio": p99_ratio, "bit_equal_frac": bit_equal,
        "gates_decided": gates, "decisions": 330, "fallbacks": fallbacks,
    }
    return doc


def test_adaptive_gates_absolute_guards():
    """ISSUE-17 acceptance held by CI: against deliberately mis-tuned
    static constants the fitted models must at least match (ratio >= 1.0),
    every answer BIT-equal between arms, >= 4 distinct gates actually
    decided, zero tail-guard fallbacks, and a bounded adaptive p99."""
    assert bench.absolute_floors(_adaptive_doc()) == []
    assert [r["key"] for r in bench.absolute_floors(
        _adaptive_doc(ratio=0.95))] == [
        "configs.adaptive_gates.adaptive_vs_static"]
    assert [r["key"] for r in bench.absolute_floors(
        _adaptive_doc(bit_equal=0.99))] == [
        "configs.adaptive_gates.bit_equal_frac"]
    assert [r["key"] for r in bench.absolute_floors(
        _adaptive_doc(gates=3))] == [
        "configs.adaptive_gates.gates_decided"]
    assert [r["key"] for r in bench.absolute_floors(
        _adaptive_doc(p99_ratio=1.4))] == [
        "configs.adaptive_gates.p99_ratio"]
    assert [r["key"] for r in bench.absolute_floors(
        _adaptive_doc(fallbacks=2))] == [
        "configs.adaptive_gates.fallbacks"]
    # the guards ride compare_bench (the CI entry point) too
    assert bench.compare_bench(_adaptive_doc(), _adaptive_doc(ratio=0.5),
                               threshold=0.15)
    # smoke/quick shapes never trip the full-shape bounds
    assert bench.absolute_floors(
        _adaptive_doc(rows=24_000, ratio=0.5, bit_equal=0.0, gates=0,
                      fallbacks=9)) == []


def test_adaptive_gates_harness_crash_fails_guards():
    """A crashed adaptive harness at the guarded shape must TRIP the
    absolute bounds (missing-key rule), never silently disable the
    self-driving hot path's CI proof."""
    doc = _doc()
    doc["configs"]["adaptive_gates"] = {"rows": 400_000, "error": "boom"}
    regs = bench.absolute_floors(doc)
    assert len(regs) == 5
    assert all(r["key"].startswith("configs.adaptive_gates") for r in regs)
    assert all(r.get("missing") for r in regs)
    assert "boom" in bench._format_regression(regs[0])
