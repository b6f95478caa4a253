"""`px`-style CLI (reference src/pixie_cli: run scripts, render tables, start
services).

    python -m pixie_tpu.cli run <script.pxl | bundle-dir>  [--broker H:P | --demo]
    python -m pixie_tpu.cli explain <script.pxl>
    python -m pixie_tpu.cli scripts --bundle DIR
    python -m pixie_tpu.cli broker [--port P] [--datastore PATH]
    python -m pixie_tpu.cli agent --name N --broker H:P [--connector seq_gen]
    python -m pixie_tpu.cli storage --broker H:P   # df for the data plane

Results render as aligned text tables with semantic-aware formatting
(durations, bytes, percentages) — the CLI analog of the Live UI's table view.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


# ------------------------------------------------------------------ rendering


def _fmt_duration(ns: float) -> str:
    ns = float(ns)
    for unit, div in (("s", 1e9), ("ms", 1e6), ("us", 1e3)):
        if abs(ns) >= div:
            return f"{ns / div:.2f}{unit}"
    return f"{ns:.0f}ns"


def _fmt_bytes(b: float) -> str:
    b = float(b)
    for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if abs(b) >= div:
            return f"{b / div:.2f}{unit}"
    return f"{b:.0f}B"


def _formatter(cs):
    """ColumnSchema → value formatter, driven by the SEMANTIC type the
    engine propagates through query results (reference: vis formatting by
    ST, vispb/vis.proto) — no column-name guessing."""
    from pixie_tpu.types import SemanticType as ST

    st = cs.semantic_type
    if st == ST.ST_DURATION_NS:
        return _fmt_duration
    if st == ST.ST_BYTES:
        return _fmt_bytes
    if st == ST.ST_PERCENT:
        return lambda v: f"{float(v) * 100:.2f}%"
    if st == ST.ST_THROUGHPUT_BYTES_PER_NS:
        return lambda v: _fmt_bytes(float(v) * 1e9) + "/s"
    if st == ST.ST_THROUGHPUT_PER_NS:
        return lambda v: f"{float(v) * 1e9:.2f}/s"
    return None


def render_table(result, max_rows: int = 40) -> str:
    """QueryResult → aligned text table (only the shown rows are decoded)."""
    names = result.relation.names()
    shown_n = min(result.num_rows, max_rows)
    cols = {}
    for n in names:
        arr = result.columns[n][:shown_n]
        d = result.dictionaries.get(n)
        vals = d.decode(arr) if d is not None else arr.tolist()
        fmt = _formatter(result.relation.col(n))
        if fmt is not None:
            try:
                vals = [fmt(v) if v is not None else "" for v in vals]
            except (TypeError, ValueError):
                pass
        cols[n] = ["" if v is None else str(v) for v in vals]
    n_rows = result.num_rows
    shown = shown_n
    widths = {
        n: max(len(n), *(len(cols[n][i]) for i in range(shown))) if shown else len(n)
        for n in names
    }
    lines = ["  ".join(n.ljust(widths[n]) for n in names)]
    lines.append("  ".join("-" * widths[n] for n in names))
    for i in range(shown):
        lines.append("  ".join(cols[n][i].ljust(widths[n]) for n in names))
    if n_rows > shown:
        lines.append(f"... ({n_rows - shown} more rows)")
    return "\n".join(lines)


# ----------------------------------------------------------------- script run


def _load_script(target: str):
    """Accept a .pxl file OR a bundled-script directory (pxl + vis.json).
    Returns (source, VisSpec|None, name)."""
    from pixie_tpu.vis import parse_vis

    p = pathlib.Path(target)
    if p.is_dir():
        pxls = sorted(p.glob("*.pxl"))
        if not pxls:
            raise SystemExit(f"{target}: no .pxl file in bundle dir")
        vis_path = p / "vis.json"
        vis = parse_vis(vis_path.read_text()) if vis_path.exists() else None
        return pxls[0].read_text(), vis, p.name
    return p.read_text(), None, p.stem


def _demo_cluster():
    """In-process demo data (no broker needed): canonical tables + metadata."""
    from pixie_tpu.metadata.state import set_global_manager
    from pixie_tpu.testing import build_demo_store, demo_metadata

    mgr, _, _ = demo_metadata()
    set_global_manager(mgr)
    SEC = 1_000_000_000
    now = time.time_ns()
    store = build_demo_store(rows=20_000, now_ns=now, span_s=300)
    # the self-telemetry tables exist (empty) so the bundled self_*
    # dashboards run against demo data like any other script
    from pixie_tpu import observe, trace

    trace.ensure_table(store)
    observe.ensure_self_tables(store)
    return store, now


def _render_results(out_name, results, args, displays=None) -> None:
    from pixie_tpu.cli_widgets import render_widget

    for sink, res in results.items():
        w = (displays or {}).get(out_name)
        kind = w.kind if w else "Table"
        hdr = f"== {out_name}/{sink} [{kind}] ({res.num_rows} rows)"
        print(hdr)
        chart = render_widget(kind, w.display if w else {}, res)
        if chart:
            print(chart)
        else:
            print(render_table(res, max_rows=args.max_rows))
        if args.analyze and res.exec_stats.get("operators"):
            from pixie_tpu.plan.debug import render_stats

            print("-- exec stats:")
            print(render_stats(res.exec_stats))
        print()
    if getattr(args, "explain", False):
        # EXPLAIN ANALYZE: the annotated plan tree + phase attribution +
        # provenance the flight recorder assembled for THIS query — one
        # query, ONE block, however many sinks it displayed (the broker
        # stamps the same stats dict on every result)
        for res in results.values():
            if res.exec_stats.get("explain"):
                print(res.exec_stats["explain"])
                print()
                break


def cmd_run(args) -> int:
    source, vis, name = _load_script(args.script)
    overrides = {}
    for kv in args.arg or []:
        if "=" not in kv:
            raise SystemExit(f"--arg expects name=value, got {kv!r}")
        k, v = kv.split("=", 1)
        overrides[k] = v

    runs: list[tuple[str, str | None, dict | None]] = [(name, None, None)]
    if vis is not None and (vis.global_funcs or any(w.func for w in vis.widgets)):
        runs = [(out, fn, fargs) for out, fn, fargs in vis.executions(overrides)]

    if args.broker:
        import sys as _sys

        from pixie_tpu.services.client import Client, QueryError
        from pixie_tpu.status import Unavailable

        host, port = args.broker.rsplit(":", 1)
        client = Client(host, int(port), auth_token=args.auth_token,
                        tenant=getattr(args, "tenant", None))

        def execute(fn, fargs):
            # the client auto-retries idempotent scripts through agent
            # evictions and broker restarts — surface the recovery as a
            # one-line note (or a clean error), never a stack trace
            try:
                out = client.execute_script(
                    source, func=fn, func_args=fargs, analyze=args.analyze,
                    explain=getattr(args, "explain", False))
            except (QueryError, Unavailable) as e:
                # Unavailable covers the reconnect path exhausting its
                # budget (broker down past PL_CLIENT_RETRIES) and timeouts
                n = client.last_retries
                retried = f" (retried {n}x)" if n else ""
                raise SystemExit(f"query failed{retried}: {e}") from None
            if client.last_retries:
                print(f"note: retried {client.last_retries}x after a "
                      "transient broker/agent failure", file=_sys.stderr)
            return out
    else:
        from pixie_tpu.collect.schemas import all_schemas
        from pixie_tpu.compiler import compile_pxl
        from pixie_tpu.engine import execute_plan
        from pixie_tpu.services.tracepoints import TracepointManager

        store, now = _demo_cluster()
        schemas = {**all_schemas(), **store.schemas()}
        tp_mgr = TracepointManager(store)

        def execute(fn, fargs):
            q = compile_pxl(source, schemas, func=fn, func_args=fargs, now=now)
            if q.mutations:
                tp_mgr.apply(q.mutations)
            t0 = time.perf_counter_ns()
            results = execute_plan(q.plan, store, analyze=args.analyze)
            if getattr(args, "explain", False) and results:
                from pixie_tpu import observe

                first = next(iter(results.values()))
                first.exec_stats["explain"] = observe.explain_local(
                    q.plan, first.exec_stats,
                    time.perf_counter_ns() - t0)
            return results

        if len(runs) > 1:
            # Multi-widget vis: fuse all funcs' plans so shared subplans
            # (scans, filters, first aggregates) execute ONCE — via the same
            # compile path the broker uses (reference MergeNodesRule,
            # optimizer.h:39 fuses in the compiler so every entry point
            # benefits).
            from pixie_tpu.compiler import compile_pxl_funcs

            q, sink_map = compile_pxl_funcs(source, schemas, runs, now=now)
            if q.mutations:
                tp_mgr.apply(q.mutations)
            t0 = time.perf_counter_ns()
            all_results = execute_plan(q.plan, store, analyze=args.analyze)
            fused_wall_ns = time.perf_counter_ns() - t0

            def execute_fused(out_name):
                return {
                    orig: all_results[fused_name]
                    for orig, fused_name in sink_map.get(out_name, {}).items()
                }

            displays = vis.widget_displays()
            render_args = args
            if args.analyze:
                # every fused result shares ONE executor's stats — print
                # them once at the end, not per widget
                import copy as _copy

                render_args = _copy.copy(args)
                render_args.analyze = False
            for out_name, _fn, _fargs in runs:
                _render_results(out_name, execute_fused(out_name),
                                render_args, displays)
            if args.analyze and all_results:
                from pixie_tpu.plan.debug import render_stats

                first = next(iter(all_results.values()))
                if first.exec_stats.get("operators"):
                    print("-- exec stats (fused plan):")
                    print(render_stats(first.exec_stats))
            if getattr(args, "explain", False) and all_results:
                # the fused plan ran ONCE for every widget: one EXPLAIN
                from pixie_tpu import observe

                first = next(iter(all_results.values()))
                print(observe.explain_local(q.plan, first.exec_stats,
                                            fused_wall_ns))
            return 0

    displays = vis.widget_displays() if vis is not None else {}
    for out_name, fn, fargs in runs:
        _render_results(out_name, execute(fn, fargs), args, displays)
    return 0


def cmd_explain(args) -> int:
    from pixie_tpu.collect.schemas import all_schemas
    from pixie_tpu.compiler import compile_pxl
    from pixie_tpu.vis import parse_vis  # noqa: F401  (bundle support)

    source, vis, _name = _load_script(args.script)
    fn = fargs = None
    if vis is not None:
        runs = vis.executions({})
        if runs:
            _out, fn, fargs = runs[0]
    q = compile_pxl(source, all_schemas(), func=fn, func_args=fargs)
    print(q.plan.explain())
    return 0


def cmd_scripts(args) -> int:
    # reference ∪ repo-shipped scripts, overlaid by an explicit --bundle —
    # the same resolution surface the Web UI and live REPL use
    from pixie_tpu.scripts import bundle_map

    m = bundle_map(args.bundle)
    for d in (m[k] for k in sorted(m)):
        desc = ""
        manifest = d / "manifest.yaml"
        if manifest.exists():
            for line in manifest.read_text().splitlines():
                if line.strip().startswith("short:"):
                    desc = line.split(":", 1)[1].strip()
                    break
        print(f"{d.name:<36} {desc}")
    return 0


def cmd_broker(args) -> int:
    from pixie_tpu.services.broker import Broker

    broker = Broker(host=args.host, port=args.port,
                    datastore_path=args.datastore,
                    auth_token=args.auth_token,
                    healthz_port=args.healthz_port,
                    election_id=args.election_id).start()
    print(f"broker listening on {args.host}:{broker.port} "
          f"(datastore={args.datastore}) {args.role_line}", flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        broker.stop()
    return 0


def _make_runner(args):
    """Shared execution backend for the live surfaces (`ui`, `live`):
    a broker client when --broker is given, else in-process demo data."""
    from pixie_tpu.webui import broker_runner, local_runner

    if args.broker:
        from pixie_tpu.services.client import Client

        host, port = args.broker.rsplit(":", 1)
        return broker_runner(Client(host, int(port),
                                    auth_token=args.auth_token,
                                    tenant=getattr(args, "tenant", None)))
    store, now = _demo_cluster()
    return local_runner(store, now=now)


def cmd_ui(args) -> int:
    """Serve the Live View (reference src/ui Live View, server-rendered)."""
    from pixie_tpu.webui import LiveServer

    runner = _make_runner(args)
    server = LiveServer(runner, scripts_dir=args.bundle,
                        host=args.host, port=args.port).start()
    print(f"live view on http://{args.host}:{server.port}/", flush=True)
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        server.stop()
    return 0


def cmd_live(args) -> int:
    """Interactive live REPL (reference src/pixie_cli/pkg/live/)."""
    from pixie_tpu.cli_live import main_live

    return main_live(_make_runner(args), args.bundle)


def _fmt_quota(tenant: str, q: dict) -> str:
    def lim(v):
        return "unlimited" if not v else f"{v:g}"

    src = "live" if q.get("live") else "env-default"
    return (f"{tenant:<24} qps={lim(q.get('qps')):<10} "
            f"concurrency={lim(q.get('concurrency')):<10} "
            f"weight={q.get('weight', 1.0):<6g} [{src}]")


def cmd_quota(args) -> int:
    """Live tenant quota control plane: `quota set` writes a per-tenant
    record the broker applies to its scheduler immediately and persists in
    its KV (survives restart; the PL_TENANT_* env specs stay the
    defaults); `quota show` dumps effective quotas + the measured
    service-rate model."""
    from pixie_tpu.services.client import Client, QueryError

    host, port = args.broker.rsplit(":", 1)
    client = Client(host, int(port), auth_token=args.auth_token)
    try:
        if args.quota_cmd == "set":
            if args.clear:
                eff = client.clear_quota(args.tenant)
            else:
                if (args.qps is None and args.concurrency is None
                        and args.weight is None):
                    raise SystemExit(
                        "quota set: give at least one of --qps/"
                        "--concurrency/--weight (or --clear)")
                eff = client.set_quota(args.tenant, qps=args.qps,
                                       concurrency=args.concurrency,
                                       weight=args.weight)
            print(_fmt_quota(args.tenant, eff))
        else:
            got = client.get_quotas()
            tenants = got.get("tenants") or {}
            if not tenants:
                print("no active tenants or live quota records")
            for tenant in sorted(tenants):
                print(_fmt_quota(tenant, tenants[tenant]))
            rm = got.get("rate_model") or {}
            if rm:
                print(f"-- measured rates: cold_cost={rm.get('cost_cold')} "
                      f"arrival_qps={rm.get('arrival_qps')} "
                      f"warm_p50_ms={(rm.get('warm') or {}).get('p50_ms')} "
                      f"cold_p50_ms={(rm.get('cold') or {}).get('p50_ms')}")
    except QueryError as e:
        raise SystemExit(f"quota: {e}") from None
    finally:
        client.close()
    return 0


def cmd_storage(args) -> int:
    """`df` for the data plane: the broker's cluster heat map (heat_map
    RPC) rendered as per-table shard heat + skew and per-agent storage
    state (hot rows, sealed batches, journal/resident/matview bytes,
    replication lag)."""
    from pixie_tpu.services.client import Client, QueryError

    host, port = args.broker.rsplit(":", 1)
    client = Client(host, int(port), auth_token=args.auth_token)
    try:
        hm = client.heat_map()
    except QueryError as e:
        raise SystemExit(f"storage: {e}") from None
    finally:
        client.close()
    tables = hm.get("tables") or {}
    if tables:
        print("-- shard heat (decayed rows scanned):")
        print(f"   {'table':<34} {'shard':<12} {'heat':>12} "
              f"{'scanned':>10} {'bytes':>10}  skew")
        for tname in sorted(tables):
            t = tables[tname]
            shards = t.get("shards") or {}
            for i, sh in enumerate(sorted(shards)):
                skew = f"{t.get('skew', 1.0):.3f}" if i == 0 else ""
                print(f"   {tname[:34]:<34} {sh[:12]:<12} "
                      f"{shards[sh]:>12.1f} {t.get('rows_scanned', 0):>10} "
                      f"{_fmt_bytes(t.get('bytes', 0)):>10}  {skew}")
    else:
        print("no shard heat recorded (is PL_TRACING_ENABLED on and has "
              "anything queried?)")
    agents = hm.get("agents") or {}
    for name in sorted(agents):
        rep = agents[name]
        if rep.get("error"):
            print(f"-- agent {name}: error: {rep['error']}")
            continue
        print(f"-- agent {name} storage state:")
        print(f"   {'table':<34} {'hot':>8} {'sealed':>7} {'bytes':>10} "
              f"{'cold':>10} {'cseg':>5} {'journal':>10} {'resident':>10} "
              f"{'matview':>10} {'lag':>4}  ages")
        for r in rep.get("storage_state") or []:
            print(f"   {str(r.get('table_name', ''))[:34]:<34} "
                  f"{r.get('hot_rows', 0):>8} "
                  f"{r.get('sealed_batches', 0):>7} "
                  f"{_fmt_bytes(r.get('sealed_bytes', 0)):>10} "
                  f"{_fmt_bytes(r.get('cold_bytes', 0)):>10} "
                  f"{r.get('cold_segments', 0):>5} "
                  f"{_fmt_bytes(r.get('journal_bytes', 0)):>10} "
                  f"{_fmt_bytes(r.get('resident_bytes', 0)):>10} "
                  f"{_fmt_bytes(r.get('matview_bytes', 0)):>10} "
                  f"{r.get('repl_lag_batches', 0):>4}  "
                  f"{r.get('age_histogram', '') or '-'}")
    return 0


def cmd_rehome(args) -> int:
    """Operator shard re-homing: move a hot or draining agent's sealed
    shard data onto a peer over the replication channel, verify coverage,
    flip the shard map.  A refused move (printed reason) means ownership
    never left the donor."""
    from pixie_tpu.services.client import Client, QueryError

    host, port = args.broker.rsplit(":", 1)
    client = Client(host, int(port), auth_token=args.auth_token)
    try:
        res = client.rehome(args.agent, target=args.target,
                            reason=args.reason)
    except QueryError as e:
        raise SystemExit(f"rehome: {e}") from None
    finally:
        client.close()
    if not res.get("ok"):
        print(f"rehome refused: {res.get('reason')} "
              f"(ownership stays with {args.agent})")
        return 1
    tables = res.get("tables") or {}
    print(f"re-homed {res.get('donor')} -> {res.get('target')}: "
          f"{len(tables)} table(s)")
    for name in sorted(tables):
        f = tables[name]
        print(f"   {name}: rows [{f.get('first', 0)}, {f.get('last', 0)})")
    return 0


def cmd_agent(args) -> int:
    from pixie_tpu.services.agent import main as agent_main

    argv = ["--name", args.name, "--broker", args.broker]
    if args.auth_token:
        argv += ["--auth-token", args.auth_token]
    for c in args.connector or []:
        argv += ["--connector", c]
    agent_main(argv)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="px-tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="run a PxL script and render results")
    run.add_argument("script", help=".pxl file or bundled-script directory")
    run.add_argument("--broker", help="host:port (default: in-process demo data)")
    run.add_argument("--auth-token", default=None,
                     help="shared secret when the broker enables auth")
    run.add_argument("--tenant", default=None,
                     help="tenant id for broker admission control / quotas "
                          "and per-tenant cache namespaces")
    run.add_argument("--arg", action="append", help="vis variable override k=v")
    run.add_argument("--analyze", action="store_true")
    run.add_argument("--explain", action="store_true",
                     help="EXPLAIN ANALYZE: print the annotated plan tree "
                          "(per-op ns, phase attribution, cache/matview/"
                          "batch/failover provenance) for each query")
    run.add_argument("--max-rows", type=int, default=40)
    run.set_defaults(fn=cmd_run)

    exp = sub.add_parser("explain", help="compile and pretty-print the plan")
    exp.add_argument("script")
    exp.set_defaults(fn=cmd_explain)

    sc = sub.add_parser("scripts", help="list bundled scripts")
    sc.add_argument("--bundle", default=None,
                    help="script bundle dir (default: reference checkout "
                         "∪ repo-shipped scripts)")
    sc.set_defaults(fn=cmd_scripts)

    br = sub.add_parser("broker", help="start a query broker")
    br.add_argument("--host", default="127.0.0.1")
    br.add_argument("--port", type=int, default=59300)
    br.add_argument("--datastore", default=":memory:")
    br.add_argument("--auth-token", default=None,
                    help="require this shared secret from every connection")
    br.add_argument("--healthz-port", type=int, default=None,
                    help="serve HTTP /healthz + /metrics on this port")
    br.add_argument("--election-id", default=None,
                    help="participate in broker leader election under this "
                         "instance id (shared --datastore required)")
    br.set_defaults(fn=cmd_broker)

    from pixie_tpu.webui import DEFAULT_SCRIPTS

    ui = sub.add_parser("ui", help="serve the live web view")
    ui.add_argument("--host", default="127.0.0.1")
    ui.add_argument("--port", type=int, default=8083)
    ui.add_argument("--bundle", default=str(DEFAULT_SCRIPTS))
    ui.add_argument("--broker", help="host:port (default: in-process demo data)")
    ui.add_argument("--auth-token", default=None)
    ui.add_argument("--tenant", default=None)
    ui.set_defaults(fn=cmd_ui)

    lv = sub.add_parser("live", help="interactive live REPL with completion")
    lv.add_argument("--bundle", default=str(DEFAULT_SCRIPTS))
    lv.add_argument("--broker", help="host:port (default: in-process demo data)")
    lv.add_argument("--auth-token", default=None)
    lv.add_argument("--tenant", default=None)
    lv.set_defaults(fn=cmd_live)

    qt = sub.add_parser("quota", help="live tenant quotas (set | show)")
    qsub = qt.add_subparsers(dest="quota_cmd", required=True)
    qs = qsub.add_parser("set", help="write one tenant's live quota record")
    qs.add_argument("tenant")
    qs.add_argument("--broker", required=True, help="host:port")
    qs.add_argument("--qps", type=float, default=None,
                    help="token-bucket rate (0 = unlimited; omit = keep "
                         "the env-spec default)")
    qs.add_argument("--concurrency", type=int, default=None,
                    help="in-flight cap (0 = unlimited; omit = env default)")
    qs.add_argument("--weight", type=float, default=None,
                    help="DRR share (> 0; omit = env default)")
    qs.add_argument("--clear", action="store_true",
                    help="drop the live record (back to env-spec defaults)")
    qs.add_argument("--auth-token", default=None)
    qs.set_defaults(fn=cmd_quota)
    qw = qsub.add_parser("show",
                         help="effective quotas + measured service rates")
    qw.add_argument("--broker", required=True, help="host:port")
    qw.add_argument("--auth-token", default=None)
    qw.set_defaults(fn=cmd_quota)

    st = sub.add_parser("storage",
                        help="cluster heat map: df for the data plane")
    st.add_argument("--broker", required=True, help="host:port")
    st.add_argument("--auth-token", default=None)
    st.set_defaults(fn=cmd_storage)

    rh = sub.add_parser("rehome",
                        help="move an agent's shard onto a peer (verified "
                             "two-phase; refused moves change nothing)")
    rh.add_argument("agent", help="donor agent name")
    rh.add_argument("--target", default=None,
                    help="receiving agent (default: broker picks a live "
                         "replica, else the least-loaded live peer)")
    rh.add_argument("--reason", default="manual")
    rh.add_argument("--broker", required=True, help="host:port")
    rh.add_argument("--auth-token", default=None)
    rh.set_defaults(fn=cmd_rehome)

    ag = sub.add_parser("agent", help="start an agent")
    ag.add_argument("--name", required=True)
    ag.add_argument("--broker", required=True)
    ag.add_argument("--connector", action="append")
    ag.add_argument("--auth-token", default=None)
    ag.set_defaults(fn=cmd_agent)

    args = ap.parse_args(argv)
    if args.cmd == "broker" or (args.cmd != "agent"
                                and getattr(args, "broker", None)):
        # the broker and every client of one run their own JAX (merge
        # fragments, result decode) on the CPU by ROLE, pinned before any
        # backend starts: the agent process owns the chip(s)
        import pixie_tpu

        args.role_line = pixie_tpu.pin_cpu_role(
            "broker" if args.cmd == "broker" else "client")
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
