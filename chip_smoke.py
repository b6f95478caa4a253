#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served query path starts on the chip.

One process (a chip belongs to one process) starts a real Broker and one Agent
over loopback framed TCP and a Client, fills the agent's TableStore through
`Table.write` from `--seed` with the repo's `http_events` and `network_stats`
shapes at `--rows` rows each (default 64M, BASELINE config #1's headline size),
and sends three distinct PxL scripts — the single-node BASELINE shapes — each
several times through `Client.execute_script`:

  s1  filter + groupby(service, status): count / mean / p50
  s2  10 s-windowed count / mean / p50 / p99 by service
  s3  per-pod int64 byte sums, joined to pod metadata, re-grouped by service

Every answer is compared with a plain pandas/numpy computation of the same
query on the same generated arrays (counts and integer sums exactly; f64 means
within the bound ops/groupby.py documents; sketch quantiles within one
LogHistogram bin), and every answer's exec_stats is read for WHERE it ran.  A
standing-view hit counts as served, not as device work.  The run fails unless
every script had at least two full-table scans on the TPU and one warm answer,
and fails if a scan the router sent to the device ran anywhere else.

No TPU ⇒ nothing runs and the exit code is 2.  `--cpu-dry-run` is a debugging
aid chosen on the command line only: it pins the CPU platform, says
platform=cpu on every line, and always exits 3.

Exit 0 and a last stdout line {"ok": true, "device": {...}} mean every phase
passed on a TPU.  Details go to <out>/result.json.
"""
from __future__ import annotations

import argparse
import faulthandler
import importlib.metadata
import json
import math
import os
import sys
import time

SEC = 1_000_000_000
WINDOW_NS = 10 * SEC
SPAN_S = 600
N_SERVICES = 16
N_PODS = 256
N_POD_SERVICES = 24
MIN_ROWS = 1 << 24
AGENT = "pem0"
#: the contract gives 1200 s; a hang must still end in a non-zero exit
WATCHDOG_S = 1150

SCRIPTS = {
    "s1": ("http_events", """
df = px.DataFrame(table='http_events')
df = df[df.status != 404]
df = df.groupby(['service', 'status']).agg(
    cnt=('latency', px.count), avg_lat=('latency', px.mean),
    p50=('latency', px.p50))
px.display(df, 'out')
"""),
    "s2": ("http_events", """
df = px.DataFrame(table='http_events')
df = df[df.status != 404]
df.time_ = px.bin(df.time_, px.seconds(10))
df = df.groupby(['time_', 'service']).agg(
    cnt=('latency', px.count), avg_lat=('latency', px.mean),
    p50=('latency', px.p50), p99=('latency', px.p99))
px.display(df, 'out')
"""),
    "s3": ("network_stats", """
df = px.DataFrame(table='network_stats')
agg = df.groupby('pod_id').agg(rx=('rx_bytes', px.sum), tx=('tx_bytes', px.sum))
pods = px.DataFrame(table='pods')
j = agg.merge(pods, how='inner', left_on='pod_id', right_on='pod_id',
              suffixes=['', '_m'])
out = j.groupby('service').agg(rx=('rx', px.sum), tx=('tx', px.sum))
px.display(out, 'out')
"""),
}
#: (script, sends), in sending order.  The adaptive router
#: (engine/autotune.py) probes its cold CPU arm on every 4th decision of a
#: (gate, size bucket) key, by counter, not at random; s1 and s2 each take
#: two decisions (the scan, then the standing view's build) before the view
#: answers for them, so s3 — never view-served — is sent three times in
#: between to take the probe.  If that cadence changes, the two-TPU-scans
#: check below says so.  (Over a mesh the SPMD step runs whatever the
#: router records — its arm is then a record, not where the scan ran.)
PLAN = (("s1", 4), ("s3", 3), ("s2", 4))


class SmokeFailure(Exception):
    pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 26,
                    help="rows per table (default 64M; at least 16M on a TPU)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=os.path.join("chiprun_out", "chip_smoke"))
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="debugging only: pin the CPU platform, say "
                         "platform=cpu on every line, never report success")
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    t_start = time.perf_counter()

    import jax

    import pixie_tpu  # noqa: F401  x64 + compile-cache policy, before any backend

    if args.cpu_dry_run:
        jax.config.update("jax_platforms", "cpu")
    want = "cpu" if args.cpu_dry_run else "tpu"
    devices = jax.devices()
    if devices[0].platform != want:
        print(f"chip_smoke: JAX found platform={devices[0].platform!r}, no "
              "TPU; nothing was run", file=sys.stderr)
        return 2
    if not args.cpu_dry_run and args.rows < MIN_ROWS:
        print(f"chip_smoke: --rows {args.rows} is under the {MIN_ROWS} floor",
              file=sys.stderr)
        return 2
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    tag = f"platform={device['platform']}"

    def say(msg: str) -> None:
        print(f"[{tag} +{time.perf_counter() - t_start:6.1f}s] {msg}",
              flush=True)

    os.makedirs(args.out, exist_ok=True)
    result = {"ok": False, "dry_run": bool(args.cpu_dry_run),
              "platform": device["platform"], "device_kind": device["kind"],
              "device_count": device["count"],
              "rows": args.rows, "seed": args.seed}
    if args.rows < (1 << 26):
        result["rows_cut"] = (f"--rows {args.rows}: cut from the 64M "
                              "default on the command line")
        say(f"NOTE rows cut to {args.rows} from the 64M default")
    try:
        run(args, jax, devices, want, say, result)
        result["ok"] = not args.cpu_dry_run
    except SmokeFailure as e:
        result["failure"] = str(e)
        say(f"FAIL {e}")
    finally:
        result["total_s"] = round(time.perf_counter() - t_start, 1)
        result["claim"] = None
        from pixie_tpu.services.broker import _jsonable

        with open(os.path.join(args.out, "result.json"), "w") as f:
            json.dump(_jsonable(result), f, indent=1)
    if args.cpu_dry_run:
        say("dry run on the CPU: no chip was driven; this is never a pass")
        return 3
    if not result["ok"]:
        return 1
    say(f"PASS in {result['total_s']}s; details in {args.out}/result.json")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ------------------------------------------------------------------ the run


def run(args, jax, devices, want, say, result) -> None:
    import jaxlib
    import numpy as np

    from pixie_tpu.engine import resident, transfer
    from pixie_tpu.native import build as native_build
    from pixie_tpu.native import load_native
    from pixie_tpu.services.agent import Agent
    from pixie_tpu.services.broker import Broker
    from pixie_tpu.services.client import Client

    n_dev = len(devices)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    cache_dir = jax.config.jax_compilation_cache_dir
    result["versions"] = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                          "libtpu": libtpu, "python": sys.version.split()[0]}
    result["compile_cache"] = {
        "dir": cache_dir,
        "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_before": _n_entries(cache_dir)}
    say(f"device_kind={devices[0].device_kind!r} devices={n_dev} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} libtpu={libtpu}")
    say(f"compile cache dir={cache_dir} "
        f"entries_before={result['compile_cache']['entries_before']}")

    compile_log = _CompileLog(jax)

    if load_native() is None:
        raise SmokeFailure("the native library did not build (no g++, or "
                           "PIXIE_TPU_NO_NATIVE is set)")
    result["native"] = {"built_in_this_run": native_build.built_this_process(),
                        "path": str(native_build.so_path())}
    say(f"native library {result['native']['path']} "
        f"built_in_this_run={result['native']['built_in_this_run']}")

    # ---- data: made in bulk from the seed, loaded through Table.write
    t0 = time.perf_counter()
    data = make_data(args.rows, args.seed)
    store = load_store(data)
    result["load_s"] = round(time.perf_counter() - t0, 1)
    say(f"loaded 2 x {args.rows} rows (+{N_PODS} pods) in {result['load_s']}s")
    t0 = time.perf_counter()
    refs = {"s1": ref_http(data, windowed=False),
            "s2": ref_http(data, windowed=True), "s3": ref_flow(data)}
    result["reference_s"] = round(time.perf_counter() - t0, 1)
    say(f"plain pandas/numpy references in {result['reference_s']}s")

    # ---- the served path: Client -> Broker -> Agent -> device -> Broker
    broker = Broker(hb_expiry_s=120.0, query_timeout_s=900.0).start()
    agent = Agent(AGENT, "127.0.0.1", broker.port, store=store,
                  heartbeat_s=2.0,
                  n_devices=n_dev if n_dev > 1 else None).start()
    client = Client("127.0.0.1", broker.port, timeout_s=900.0)
    answers: dict[str, list] = {}
    result["answers"] = answers
    try:
        for name, sends in PLAN:
            table, script = SCRIPTS[name]
            for i in range(sends):
                c0 = compile_log.seconds()
                t0 = time.perf_counter()
                out = client.execute_script(script)["out"]
                wall = time.perf_counter() - t0
                rec = read_answer(out.exec_stats, args.rows)
                rec.update(send=i, wall_s=round(wall, 4),
                           compile_s=round(compile_log.seconds() - c0, 3),
                           rows_out=int(out.num_rows))
                answers.setdefault(name, []).append(rec)
                try:
                    rec["check"] = CHECKS[name](out.to_pandas(), refs[name])
                finally:  # the answer's line is printed even when it fails
                    say(f"{name}#{i} wall={wall:.3f}s "
                        f"compile={rec['compile_s']}s {rec['kind']} "
                        f"ran_on={rec['ran_on'] or '-'} "
                        f"router={rec['router_arm']} rows={rec['rows']} "
                        f"resident_feeds={rec['resident_feeds']} "
                        f"h2d_bytes={rec['h2d_bytes']} "
                        f"spmd_feeds={rec['spmd_feeds']} "
                        f"check={rec.get('check', 'FAILED')}")
                judge_answer(name, i, rec, want, n_dev)
    finally:
        client.close()
        agent.stop()
        broker.stop()

    # ---- verdicts over the whole run
    for name, _sends in PLAN:
        recs = answers[name]
        scans = [r for r in recs if r["kind"] in ("scan", "view_build")
                 and r["platform"] == want and r["device_chains"] > 0]
        warm = [r for r in recs if r["plan_cache_hit"]]
        if len(scans) < 2:
            raise SmokeFailure(
                f"{name}: {len(scans)} full-table scan(s) on platform={want}, "
                "need 2 (has the router's probe cadence moved? see PLAN)")
        if not warm:
            raise SmokeFailure(f"{name}: no warm (plan-cache hit) answer")
    result["per_query"] = {
        name: {"first_wall_s": recs[0]["wall_s"],
               "first_compile_s": recs[0]["compile_s"],
               "steady_wall_s": [r["wall_s"] for r in recs[1:]],
               "where": [f"{r['kind']}:{r['ran_on'] or 'view'}"
                         for r in recs]}
        for name, recs in answers.items()}
    result["compile"] = compile_log.summary()
    result["compile_cache"]["entries_after"] = _n_entries(cache_dir)
    say(f"compile: {result['compile']} cache entries_after="
        f"{result['compile_cache']['entries_after']}")

    # ---- what the chip holds, and the link as read on this chip
    result["resident_stats"] = dict(resident.stats)
    mem = [d.memory_stats() for d in devices]
    result["memory_stats"] = mem
    say(f"resident.stats={result['resident_stats']}")
    judge_memory(mem, answers, n_dev, want, say)
    result["wave_rtt_floor"] = transfer.wave_rtt_floor()
    result["h2d_bandwidth_probe"] = transfer.h2d_bandwidth_probe()
    say(f"wave_rtt_floor={result['wave_rtt_floor']}")
    say(f"h2d_bandwidth_probe={result['h2d_bandwidth_probe']} "
        "(device 0; information, not a claim)")


def _n_entries(path) -> int:
    return len(os.listdir(path)) if path and os.path.isdir(path) else 0


class _CompileLog:
    """Compile seconds and persistent-cache hits, from jax's own monitoring
    events (tracing + lowering + backend compile or cache retrieval)."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.secs = dict.fromkeys(self.DURATIONS, 0.0)
        self.backend: list[float] = []
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event in self.secs:
            self.secs[event] += secs
        if event == self.DURATIONS[2]:
            self.backend.append(secs)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def seconds(self) -> float:
        return sum(self.secs.values())

    def summary(self) -> dict:
        long = [d for d in self.backend if d >= 1.0]
        return {"total_s": round(self.seconds(), 2),
                "backend_compile_s": round(self.secs[self.DURATIONS[2]], 2),
                "programs": len(self.backend),
                "programs_over_1s": len(long),
                "over_1s_total_s": round(sum(long), 2),
                "persistent_cache_hits": self.hits,
                "persistent_cache_misses": self.misses}


# --------------------------------------------------------------------- data


def make_data(rows: int, seed: int) -> dict:
    import numpy as np

    rng = np.random.default_rng(seed)
    return {
        "rows": rows,
        "http": {
            "time_": np.arange(rows, dtype=np.int64) * (SPAN_S * SEC // rows),
            "svc": rng.integers(0, N_SERVICES, rows, dtype=np.int32),
            "latency": rng.exponential(50.0, rows),
            "status": rng.choice(np.array([200, 404, 500], dtype=np.int64),
                                 rows, p=[0.85, 0.05, 0.10]),
        },
        "net": {
            "pod": rng.integers(0, N_PODS, rows, dtype=np.int32),
            "rx": rng.integers(0, 1 << 20, rows, dtype=np.int64),
            "tx": rng.integers(0, 1 << 20, rows, dtype=np.int64),
        },
    }


def load_store(data: dict):
    import numpy as np

    from pixie_tpu.table import TableStore
    from pixie_tpu.types import DataType as DT, Relation

    rows, http, net = data["rows"], data["http"], data["net"]
    store = TableStore()
    services = np.array([f"svc-{i}" for i in range(N_SERVICES)])
    pods = np.array([f"pod-{i}" for i in range(N_PODS)])
    t_http = store.create("http_events", Relation.of(
        ("time_", DT.TIME64NS), ("service", DT.STRING),
        ("latency", DT.FLOAT64), ("status", DT.INT64),
    ), batch_rows=1 << 16, max_bytes=1 << 36)
    t_net = store.create("network_stats", Relation.of(
        ("time_", DT.TIME64NS), ("pod_id", DT.STRING),
        ("rx_bytes", DT.INT64), ("tx_bytes", DT.INT64),
    ), batch_rows=1 << 16, max_bytes=1 << 36)
    chunk = 1 << 21
    for a in range(0, rows, chunk):
        b = min(a + chunk, rows)
        t_http.write({"time_": http["time_"][a:b],
                      "service": services[http["svc"][a:b]],
                      "latency": http["latency"][a:b],
                      "status": http["status"][a:b]})
        t_net.write({"time_": np.arange(a, b, dtype=np.int64),
                     "pod_id": pods[net["pod"][a:b]],
                     "rx_bytes": net["rx"][a:b], "tx_bytes": net["tx"][a:b]})
    store.create("pods", Relation.of(
        ("pod_id", DT.STRING), ("service", DT.STRING))).write({
            "pod_id": pods,
            "service": np.array([f"svc-{i % N_POD_SERVICES}"
                                 for i in range(N_PODS)])})
    return store


# --------------------------------------------------- the plain references


def ref_http(data: dict, windowed: bool):
    """pandas count/mean + exact-rank quantiles per group, as a DataFrame
    keyed like the script's output."""
    import numpy as np
    import pandas as pd

    http = data["http"]
    keep = http["status"] != 404
    lat = http["latency"][keep]
    svc = http["svc"][keep].astype(np.int64)
    if windowed:
        other = http["time_"][keep] // WINDOW_NS
        gid = other * N_SERVICES + svc
        qs = {"p50": 0.50, "p99": 0.99}
    else:
        other = http["status"][keep]
        gid = svc * 1000 + other
        qs = {"p50": 0.50}
    df = pd.DataFrame({"gid": gid, "lat": lat})
    agg = df.groupby("gid", sort=True)["lat"].agg(cnt="size", avg_lat="mean")
    # exact order statistics: the sketch returns the bin holding the
    # ceil(q*n)-th smallest value of the group
    order = np.argsort(gid, kind="stable")
    lat_sorted = lat[order]
    ends = np.cumsum(agg["cnt"].to_numpy())
    exact = {k: np.empty(len(agg)) for k in qs}
    for g, (b, n) in enumerate(zip(ends, agg["cnt"].to_numpy())):
        ranks = [max(math.ceil(q * n), 1) - 1 for q in qs.values()]
        part = np.partition(lat_sorted[b - n:b], ranks)
        for k, r in zip(qs, ranks):
            exact[k][g] = part[r]
    out = agg.reset_index()
    for k in qs:
        out[k] = exact[k]
    if windowed:
        out["time_"] = (out["gid"] // N_SERVICES) * WINDOW_NS
        out["service"] = "svc-" + (out["gid"] % N_SERVICES).astype(str)
        keys = ["time_", "service"]
    else:
        out["service"] = "svc-" + (out["gid"] // 1000).astype(str)
        out["status"] = out["gid"] % 1000
        keys = ["service", "status"]
    return out.drop(columns="gid"), keys


def ref_flow(data: dict):
    import numpy as np
    import pandas as pd

    net = data["net"]
    svc_of_pod = np.arange(N_PODS) % N_POD_SERVICES
    df = pd.DataFrame({"svc": svc_of_pod[net["pod"]], "rx": net["rx"],
                       "tx": net["tx"]})
    out = df.groupby("svc", sort=True)[["rx", "tx"]].sum().reset_index()
    out["service"] = "svc-" + out["svc"].astype(str)
    return out.drop(columns="svc"), ["service"]


def _joined(got, ref):
    import pandas as pd

    ref_df, keys = ref
    got = got.copy()
    if "time_" in keys:
        got["time_"] = pd.to_numeric(got["time_"]).astype("int64")
    m = ref_df.merge(got, on=keys, how="outer", suffixes=("_ref", "_got"),
                     indicator=True)
    if len(got) != len(ref_df) or not (m["_merge"] == "both").all():
        raise SmokeFailure(
            f"group sets differ: got {len(got)} rows, reference "
            f"{len(ref_df)}, matched {(m['_merge'] == 'both').sum()}")
    return m


def _rel(m, col):
    import numpy as np

    ref, got = m[f"{col}_ref"].to_numpy(), m[f"{col}_got"].to_numpy()
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


def check_http(got, ref) -> dict:
    from pixie_tpu.ops.groupby import F64_SUM_RTOL
    from pixie_tpu.ops.sketch import LogHistogram

    m = _joined(got, ref)
    if not (m["cnt_ref"] == m["cnt_got"]).all():
        raise SmokeFailure("counts differ from the reference")
    out = {"groups": len(m), "cnt": "exact",
           "mean_max_rel": _rel(m, "avg_lat")}
    if out["mean_max_rel"] > F64_SUM_RTOL:
        raise SmokeFailure(f"f64 mean off by {out['mean_max_rel']:.3g} "
                           f"relative (bound {F64_SUM_RTOL})")
    # one bin: the estimate is its bin's geometric mid-point, and an f32
    # log at a bin edge may land a value in the neighbouring bin
    qtol = LogHistogram().gamma - 1.0
    for q in ("p50", "p99"):
        if f"{q}_ref" in m:
            out[f"{q}_max_rel"] = _rel(m, q)
            if out[f"{q}_max_rel"] > qtol:
                raise SmokeFailure(f"{q} off by {out[f'{q}_max_rel']:.3g} "
                                   f"relative (bound gamma-1={qtol:.4f})")
    return out


def check_flow(got, ref) -> dict:
    m = _joined(got, ref)
    for c in ("rx", "tx"):
        if not (m[f"{c}_ref"].astype("int64")
                == m[f"{c}_got"].astype("int64")).all():
            raise SmokeFailure(f"int64 sum {c} differs from the reference")
    return {"groups": len(m), "rx": "exact", "tx": "exact"}


CHECKS = {"s1": check_http, "s2": check_http, "s3": check_flow}


# ------------------------------------------------ reading where it ran


def read_answer(stats: dict, table_rows: int) -> dict:
    """What one answer's exec_stats says: how many rows were scanned, from
    which cache, under which routing decision, on which platform."""
    agent = (stats.get("agents") or {}).get(AGENT) or {}
    mv = agent.get("matview") or {}
    fold = mv.get("exec") or {}
    src = fold if mv.get("hit") else agent
    dev = src.get("device") or {}
    rows = int(mv.get("rows_folded", 0) if mv.get("hit")
               else agent.get("rows_scanned", 0))
    if mv.get("hit") and rows == 0:
        kind = "view_hit"
    elif rows >= table_rows:
        kind = "view_build" if mv.get("hit") else "scan"
    else:
        kind = "partial"
    big = max((d for d in src.get("autotune") or []
               if d.get("gate") == "cpu_crossover"),
              key=lambda d: int(str(d.get("size_bucket", "4^0"))[2:] or 0),
              default=None)
    gates = {**((stats.get("merger") or {}).get("device") or {}), **dev}
    return {
        "kind": kind,
        "rows": rows,
        "plan_cache_hit": bool((stats.get("fastpath") or {})
                               .get("plan_cache_hit")),
        "matview_hit": bool(mv.get("hit")),
        "router_arm": (f"{big['arm']}({big['source']})" if big else None),
        "router_device": bool(big and big["arm"] == "device"),
        "platform": dev.get("platform"),
        "device_kind": dev.get("device_kind"),
        "engines": dev.get("engines") or {},
        "device_chains": int((dev.get("engines") or {})
                             .get("device_chain", 0)),
        "resident_feeds": int(src.get("resident_feeds", 0)),
        "feed_cache_hits": int(src.get("feed_cache_hits", 0)),
        "h2d_bytes": int(src.get("h2d_bytes", 0)),
        "spmd_feeds": int(src.get("spmd_feeds", 0)),
        "join_gate": gates.get("join_gate"),
        "collective_gate": gates.get("collective_gate"),
        "ran_on": (stats.get("profile") or {}).get("ran_on", ""),
    }


def judge_answer(name, i, rec, want, n_dev) -> None:
    where = f"{name}#{i}"
    if rec["kind"] == "partial":
        raise SmokeFailure(f"{where}: scanned {rec['rows']} rows, neither "
                           "the whole table nor a view hit")
    if rec["kind"] == "view_hit":
        return  # served from standing state: no device work to place
    if not rec["platform"] or not rec["engines"]:
        raise SmokeFailure(f"{where}: scanned {rec['rows']} rows and its "
                           "exec_stats does not say where")
    if rec["router_device"] and (rec["platform"] != want
                                 or not rec["device_chains"]):
        raise SmokeFailure(
            f"{where}: the router sent it to the device, exec_stats says it "
            f"ran on platform={rec['platform']} engines={rec['engines']}")
    if n_dev > 1 and rec["device_chains"]:
        gate = rec["collective_gate"] or {}
        if rec["spmd_feeds"] <= 0:
            raise SmokeFailure(f"{where}: {n_dev} devices but spmd_feeds=0")
        if gate.get("mesh_devices") != n_dev or (
                want == "tpu"
                and gate.get("reason") != "accelerator_hw_queues"):
            raise SmokeFailure(f"{where}: collective_gate={gate}")


def judge_memory(mem, answers, n_dev, want, say) -> None:
    """Evidence that does not come from exec_stats: the chip holds the
    table bytes the answers say they uploaded, spread over the mesh."""
    if any(m is None for m in mem):
        if want == "tpu":
            raise SmokeFailure("a TPU device reported no memory_stats()")
        say("memory_stats() is None on this platform: placement not checked")
        return
    in_use = [int(m["bytes_in_use"]) for m in mem]
    # the most any single scan uploaded: one column set of one table
    uploaded = max(r["h2d_bytes"] for recs in answers.values() for r in recs)
    say(f"bytes_in_use per device={in_use} largest upload={uploaded}")
    if sum(in_use) < uploaded:
        raise SmokeFailure(f"devices hold {sum(in_use)} bytes, less than "
                           f"the {uploaded} one scan says it uploaded")
    if n_dev > 1:
        shares = [b / sum(in_use) for b in in_use]
        if min(shares) < 0.6 / n_dev or max(shares) > 1.6 / n_dev:
            raise SmokeFailure("resident bytes are not spread over the "
                               f"mesh: shares={[round(s, 3) for s in shares]}")


if __name__ == "__main__":
    sys.exit(main())
