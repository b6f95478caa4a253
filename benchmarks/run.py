#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json on the machine it is started on.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process (a chip belongs to one process): the cell's tables are made from
--seed, loaded through Table.write, served by a real Broker and one Agent on
loopback, warmed up with a fixed list of queries, and then driven through
Client.execute_script by one closed-loop client for --seconds.  The answers
of the window are compared with the plain reference once the window has
closed.  The last line of standard output is the result.  No TPU, or fewer
chips than the cell asks for: exit 2 and no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(HERE, "metrics"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

AGENT = "pem0"
#: a hang must still end in a non-zero exit, inside the first run's 1200 s
WATCHDOG_S = 1100
#: the profiler runs over the last so many seconds of a traced window
TRACE_SPAN_S = 8.0


class Phases:
    """Where the set-up goes: one earlier line per phase."""

    def __init__(self):
        self.t = T_PROCESS
        self.rows: list = []

    def mark(self, name: str, **extra) -> None:
        now = time.perf_counter()
        self.rows.append(dict(phase=name, s=round(now - self.t, 3), **extra))
        print(f"[setup +{now - T_PROCESS:7.2f}s] {name}: {now - self.t:.3f}s "
              + " ".join(f"{k}={v}" for k, v in extra.items()), flush=True)
        self.t = now


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cell = next((w for w in bench["workloads"] if w["name"] == workload),
                None)
    if cell is None:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, cfg


def import_jax():
    """jax with the program's x64 and compile-cache policy, and every
    program of a run, not only those that took a second to compile, kept in
    the checkout's cache: a later run of the cell compiles nothing."""
    import jax

    import pixie_tpu  # noqa: F401  x64 and the compile cache's place

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(HERE, "out"),
                    help="directory of the per-run query logs")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    bench = load_benchmark()
    cell, cfg_entry = find_cell(bench, args.workload)
    phases = Phases()
    jax = import_jax()
    phases.mark("imports")
    devices = jax.devices()
    phases.mark("backend_start", platform=devices[0].platform,
                n=len(devices))
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"run.py: JAX found platform={devices[0].platform!r} with "
              f"{len(devices)} device(s); the cell needs {cell['chips']} TPU "
              "chip(s).  Nothing was measured.", file=sys.stderr)
        return 2
    result = run_cell(bench, cell, cfg_entry, args.seed, args.seconds,
                      bool(args.trace), args.out, phases, jax,
                      devices[:int(cell["chips"])])
    print(json.dumps(result), flush=True)
    return 0


def run_cell(bench, cell, cfg_entry, seed, seconds, traced, out_dir, phases,
             jax, devices, config=None, peaks=None,
             with_control=False) -> dict:
    """Everything of a run after the look for a chip.  `config` overrides
    the cell's configuration file and `peaks` the table of peaks
    (rehearsals and tests only); `with_control` adds the readings of each
    control on the same sample (tests/seeds_on_chip.py)."""
    import compare
    import data as datagen
    import stats as st
    import traffic

    from pixie_tpu.native import load_native
    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker
    from pixie_tpu.services.client import Client

    if config is None:
        with open(os.path.join(ROOT, cfg_entry["file"])) as f:
            config = json.load(f)
    mix = datagen.load_json("traffic", cell["traffic"])
    if peaks is None:
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         "peaks.json")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices)}
    compile_log = st.CompileLog(jax)
    native = load_native()
    phases.mark("native_library", loaded=native is not None)
    schedule = traffic.Schedule(mix, config, seed)
    data = datagen.generate(config, seed)
    phases.mark("data_generation")
    datagen.install_metadata(config)
    store = datagen.load_store(config, data)
    phases.mark("table_write")

    broker = Broker(hb_expiry_s=120.0, query_timeout_s=300.0).start()
    agent = Agent(AGENT, "127.0.0.1", broker.port, store=store,
                  heartbeat_s=2.0,
                  n_devices=len(devices) if len(devices) > 1 else None
                  ).start()
    client = Client("127.0.0.1", broker.port, timeout_s=300.0)
    host_spans: list = []
    tracer = None
    try:
        phases.mark("broker_agent_start")
        if traced:
            tap_spans(broker.tracer, host_spans)
            tap_spans(agent.tracer, host_spans)
        log_rows = warm_up(client, schedule, compile_log)
        phases.mark("warmup", queries=len(log_rows))
        if traced:
            tracer = TailTrace(jax, os.path.join(out_dir, "trace_tmp",
                                                 f"{cell['name']}_{seed}"),
                               seconds, TRACE_SPAN_S)
        setup_s = time.perf_counter() - T_PROCESS
        print(f"[setup] setup_s={setup_s:.3f}", flush=True)

        c0 = compile_log.snapshot()
        recs, window_s = traffic.closed_loop(
            client, schedule, seconds,
            between=tracer.between if tracer else None,
            compiles=lambda: compile_log.backend)
        c1 = compile_log.snapshot()
        if tracer:
            window_s -= tracer.spent_s
            tracer.stop()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
    finally:
        client.close()
        agent.stop()
        broker.stop()
    del store, agent, broker

    for r in recs:
        r["phase"] = "window"
        r["digest"] = st.digest(r.pop("stats")) if "stats" in r else None
    st.mark_probes(log_rows + recs)
    scripts = schedule.scripts
    t0 = time.perf_counter()
    verdict = compare.check_window(recs, data, config, scripts, seed)
    check_s = time.perf_counter() - t0
    failed = sum(1 for r in recs if "error" in r)
    correct = bool(verdict["ok"] and failed == 0 and recs)

    walls = [r["wall_ms"] for r in recs]
    run = {"config": config, "scripts": scripts,
           "queries": [r for r in recs if r["digest"] is not None],
           "walls_ms": walls, "window_s": window_s,
           "compiles_in_window": c1["backend_compiles"]
           - c0["backend_compiles"],
           "peaks": peaks[kind], "trace": None}
    metrics = {}
    breakdown = None
    if traced:
        run["trace"] = tracer.reduce(host_spans, recs, len(devices))
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        breakdown = {"device_ops": run["trace"]["device_ops"],
                     "idle_gaps": run["trace"]["idle_gaps"]}
        for m in bench["per_layer"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            value = datagen.load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                metrics[m["name"]] = {"value": end_to_end(m["name"], walls,
                                                          setup_s),
                                      "unit": m["unit"]}
    device["memory_peak_bytes"] = peak

    header = {"setup_phases": phases.rows, "setup_s": setup_s,
              "window_s": window_s}
    if traced:
        header["trace"] = {k: run["trace"][k] for k in
                           ("busy_s", "window_s", "clock_shift_ns", "layout")}
    write_log(out_dir, cell["name"], seed, traced, header, log_rows, recs)
    checks = {k: {"value": v["value"], "limit": v["limit"]}
              for k, v in sorted(verdict["numbers"].items())}
    print(f"[check] compared={verdict['compared']} answers in "
          f"{check_s:.2f}s; queries={len(recs)} failed={failed} "
          f"window_s={window_s:.3f}", flush=True)
    for k, v in checks.items():
        print(f"check {k}: value={v['value']!r} limit={v['limit']!r} "
              f"{'ok' if v['value'] <= v['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": len(recs), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if with_control:
        result["control"] = {
            name: compare.check_window(recs, data, config, scripts, seed,
                                       stand_in=name)
            for name in compare.controls(scripts)}
    result["compared"] = verdict["compared"]
    result["checks"] = checks
    return result


def end_to_end(name: str, walls_ms: list, setup_s: float) -> float:
    """`setup_s`, or `query_p<NN>_ms`: that percentile of every query of
    the window, from the client's clock."""
    import re

    import stats as st

    if name == "setup_s":
        return setup_s
    m = re.fullmatch(r"query_p(\d+)_ms", name)
    if not m:
        raise SystemExit(f"run.py: no end-to-end metric {name!r}")
    return st.percentile(walls_ms, int(m.group(1)) / 100.0)


def warm_up(client, schedule, compile_log) -> list:
    """The mix's fixed warm-up, one line a query: what it compiled, where it
    ran.  A warm-up query that fails ends the run."""
    import stats as st
    import traffic

    rows = []
    for i, q in enumerate(schedule.warmup()):
        c0 = compile_log.snapshot()
        rec = traffic.send(client, q)
        c1 = compile_log.snapshot()
        if "error" in rec:
            raise RuntimeError(f"warm-up query {i} failed: {rec['error']}")
        rec["digest"] = d = st.digest(rec.pop("stats"))
        rec.pop("answer")
        rec.update(i=i, phase="warmup",
                   compile_s=round(c1["compile_s"] - c0["compile_s"], 3),
                   backend_compiles=(c1["backend_compiles"]
                                     - c0["backend_compiles"]),
                   cache_hits=c1["cache_hits"] - c0["cache_hits"])
        rows.append(rec)
        print(f"[warmup {i:3d}] {rec['script']} bound={rec['bound']} "
              f"wall={rec['wall_ms']:.1f}ms compile={rec['compile_s']}s "
              f"compiles={rec['backend_compiles']} "
              f"cache_hits={rec['cache_hits']} engine={d['engine']} "
              f"arm={d['arm']}({d['source']}) bucket={d['size_bucket']}",
              flush=True)
    return rows


def tap_spans(tracer, sink: list) -> None:
    """Copy every span the program's tracer finishes (name, service, wall
    clock bounds) for the naming of idle gaps; traced runs only."""
    finish = tracer.finish

    def tapped(span, end_ns=None):
        finish(span, end_ns)
        sink.append((span.start_ns, span.end_ns,
                     f"{span.service}.{span.name}"))

    tracer.finish = tapped


class TailTrace:
    """The profiler over the last `span_s` seconds of the window.  Starting
    it happens between two queries; the time that takes is given back to the
    window and not counted in its length."""

    def __init__(self, jax, trace_dir: str, seconds: float, span_s: float):
        self.jax, self.dir = jax, trace_dir
        self.start_after = max(0.0, seconds - span_s)
        self.started = False
        self.spent_s = 0.0
        self.lo_unix_ns = self.mark_unix_ns = 0
        self.hi_unix_ns = 0
        shutil.rmtree(trace_dir, ignore_errors=True)

    def between(self, elapsed_s: float) -> float:
        if self.started or elapsed_s < self.start_after:
            return 0.0
        t0 = time.perf_counter()
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        import tracered

        self.mark_unix_ns = time.time_ns()
        with self.jax.profiler.TraceAnnotation(tracered.CLOCK_MARK):
            time.sleep(0.001)
        self.started = True
        self.lo_unix_ns = time.time_ns()
        self.spent_s = time.perf_counter() - t0
        return self.spent_s

    def stop(self) -> None:
        self.hi_unix_ns = time.time_ns()
        if self.started:
            self.jax.profiler.stop_trace()

    def reduce(self, host_spans: list, recs: list, n_devices: int) -> dict:
        """The trace's reduction over the traced span, for a cell of
        `n_devices`.  A device that ran nothing while the profiler was on
        has no plane in the trace: a window the program served off the chip
        reads busy 0 and is a reading like any other.  Only where a query
        that began and ended inside the span says that it ran a chain on
        the device (`recs` carry their digests) is a trace that shows no
        device operation there a broken one, whether it lacks the plane or
        holds one without an operation.  (A query that only reaches into
        the span may have had its device work outside it.)"""
        import tracered

        if not self.started:
            raise RuntimeError("the window closed before the trace began")
        planes = tracered.read_planes(tracered.find_xplane(self.dir))
        shutil.rmtree(self.dir, ignore_errors=True)
        if not planes["marks"]:
            raise RuntimeError("the trace lacks the clock mark: its times "
                               "cannot be laid against the window's")
        shift = planes["marks"][0][0] - self.mark_unix_ns
        lo, hi = self.lo_unix_ns + shift, self.hi_unix_ns + shift
        spans = [(s + shift, e + shift, n) for s, e, n in host_spans
                 if e + shift > lo and s + shift < hi]
        spans += [(r["t0_unix_ns"] + shift,
                   r["t0_unix_ns"] + shift + int(r["wall_ms"] * 1e6),
                   "client.execute_script") for r in recs]
        out = tracered.reduce_trace(planes, lo, hi, spans, n_devices)
        routed = sum(
            1 for r in recs
            if r.get("digest") and r["digest"]["engine"] == "device"
            and r["t0_unix_ns"] >= self.lo_unix_ns
            and r["t0_unix_ns"] + r["wall_ms"] * 1e6 <= self.hi_unix_ns)
        if routed and out["busy_s"] == 0:
            raise RuntimeError(
                f"the trace shows no operation on a device in the traced "
                f"span (device planes: {sorted(planes['devices'])}), yet "
                f"{routed} queries inside it ran a chain on the device: the "
                "trace is broken, not a window without device work")
        out["clock_shift_ns"] = shift
        out["lo_unix_ns"], out["hi_unix_ns"] = self.lo_unix_ns, self.hi_unix_ns
        out["layout"] = planes["layout"]
        return out


def write_log(out_dir, workload, seed, traced, header, warm, recs) -> None:
    """Every query's script, bounds, wall, engine and router arm, one JSON
    line each, so that two runs can be laid side by side."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{workload}.seed{seed}.trace{int(traced)}"
                                 ".jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(header) + "\n")
        for r in warm + recs:
            row = {k: v for k, v in r.items() if k != "answer"}
            f.write(json.dumps(row, default=str) + "\n")
    print(f"[log] {path}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
