"""Agent runtime: a PEM analog — local TableStore (+ collectors) that dials
the broker, registers its schemas, heartbeats, and executes plan fragments.

Reference: src/vizier/services/agent/ Manager (registration handshake +
heartbeats every 5s, manager/manager.h:100-266, heartbeat.h:79) and
ExecuteQueryMessageHandler running plans on a threadpool (manager/exec.cc:38-98).
PEM wiring of collector→store mirrors pem/pem_manager.cc:47.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Optional

import numpy as np

from pixie_tpu import flags, observe, trace
from pixie_tpu.engine.executor import HostBatch, PlanExecutor
from pixie_tpu.matview import MatViewManager
from pixie_tpu.parallel.partial import PartialAggBatch
from pixie_tpu.plan.plan import Plan
from pixie_tpu.services import replication as _replication
from pixie_tpu.services import wire
from pixie_tpu.services.transport import Connection, dial
from pixie_tpu.table import heat as _heat
from pixie_tpu.table import journal as _journal
from pixie_tpu.table.table import TableStore

DEFAULT_HEARTBEAT_S = 5.0  # reference manager/heartbeat.h:79

flags.define_int(
    "PL_STREAM_WINDOW", 4,
    "max unacked in-flight result chunk frames per query (the agent blocks "
    "further chunk sends until the broker acks; 0 = unbounded)")
flags.define_int(
    "PL_STREAM_AGG_CHUNK_GROUPS", 65536,
    "split an agg_state channel payload into chunks of at most this many "
    "groups so the broker's incremental fold starts early; 0 = one chunk")
#: give up waiting for chunk acks after this long and degrade to unbounded
#: streaming — a slow broker must throttle us, a broken one must not hang us
ACK_STALL_S = 10.0


def _chunk_view_state(channel: str, pb: PartialAggBatch, agg_chunk_groups: int):
    """Yield a standing view's state as the same chunk stream shape the
    executor produces, honoring the agg-chunk split so the broker's
    incremental fold and ack window behave identically on view answers."""
    from pixie_tpu.parallel.partial import slice_partial

    n = pb.num_groups
    if agg_chunk_groups > 0 and n > agg_chunk_groups:
        for a in range(0, n, agg_chunk_groups):
            idx = np.arange(a, min(a + agg_chunk_groups, n))
            yield channel, slice_partial(pb, idx)
    else:
        yield channel, pb


class Agent:
    def __init__(
        self,
        name: str,
        broker_host: str,
        broker_port: int,
        store: Optional[TableStore] = None,
        collector=None,
        registry=None,
        heartbeat_s: float = DEFAULT_HEARTBEAT_S,
        n_devices: Optional[int] = None,
        auth_token: Optional[str] = None,
        healthz_port: Optional[int] = None,
        healthz_host: str = "127.0.0.1",
    ):
        self.auth_token = auth_token
        self.healthz = None
        if healthz_port is not None:
            from pixie_tpu.services.health import HealthzServer

            self.healthz = HealthzServer(checks={
                "broker_conn": lambda: (self.conn is not None
                                        and not self.conn.closed),
                "registered": lambda: self._registered.is_set(),
            }, host=healthz_host, port=healthz_port,
                detail={"journal": self._journal_detail})
        self.name = name
        self.broker = (broker_host, broker_port)
        self.store = store or (collector.store if collector else TableStore())
        #: shard identity for the heat model (table/heat.py): executor feeds
        #: over this store account as this agent's shard
        self.store.node_name = name
        self.collector = collector
        self.registry = registry
        self.heartbeat_s = heartbeat_s
        self.n_devices = n_devices
        #: the mesh this agent's executors shard their feeds over: the first
        #: `n_devices` local devices (fewer fails here, at start-up, with
        #: make_mesh's message; 1 = none), or with None the executor's own
        #: "auto", every local device
        self.mesh = "auto"
        if n_devices is not None:
            from pixie_tpu.parallel.spmd import make_mesh

            self.mesh = make_mesh(n_devices) if n_devices > 1 else None
        self.conn: Optional[Connection] = None
        self.asid: Optional[int] = None
        self._registered = threading.Event()
        self._stop = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        from pixie_tpu.services.tracepoints import TracepointManager

        #: dynamic tracepoints deployed to this agent (pem TracepointManager
        #: analog, pem/tracepoint_manager.h:48)
        self.tracepoints = TracepointManager(self.store)
        #: self-telemetry: this agent's exec spans + broker-shipped spans
        #: land in the local spans table, created BEFORE registration so the
        #: broker's registry knows the schema from the first handshake
        self.tracer = trace.Tracer(name)
        trace.ensure_table(self.store)
        #: flight-recorder tables (query profiles, op stats, metrics,
        #: alerts) exist before registration too: the broker ships its
        #: per-query rows here and PxL dashboards scan them like any table
        observe.ensure_self_tables(self.store)
        self._self_metrics = None
        #: standing materialized views over this agent's store: repeated
        #: scan→filter→map→partial-agg plans answer from incrementally
        #: refreshed state instead of rescanning (pixie_tpu.matview)
        self.matviews = MatViewManager(self.store, registry, mesh=self.mesh)
        #: req_id → in-flight window semaphore; chunk_ack frames release it
        self._windows: dict[str, threading.Semaphore] = {}
        self._windows_lock = threading.Lock()
        #: durable data plane (PL_DATA_DIR / PL_REPLICATION): set in start()
        self.replication = None
        self.rehydrate_stats: dict = {}
        self._owns_journal = False
        self.pod_killed = threading.Event()
        #: broker RPC slots (get_peers): req_id -> [Event, reply]
        self._replies: dict[str, list] = {}
        self._replies_lock = threading.Lock()

    # ---------------------------------------------------------------- lifecycle
    def start(self, timeout: float = 10.0) -> "Agent":
        trace.register_gauges()
        if self.collector is not None:
            self.collector.start()
        self.conn = dial(*self.broker, on_frame=self._on_frame)
        # fault-injection target (services/faultinject.py): chaos plans
        # address this agent's broker link as "agent:<name>"; kill rules
        # (true pod loss) route back into _pod_kill through the handler
        # registry so the store drops with the connection
        self.conn.label = f"agent:{self.name}"
        from pixie_tpu.services import faultinject as _faultinject

        _faultinject.register_kill_handler(self.conn.label, self._pod_kill)
        if self.auth_token is not None:
            self.conn.send(wire.encode_json(
                {"msg": "auth", "token": self.auth_token}))
        self._rehydrate(timeout)
        self._register()
        if not self._registered.wait(timeout=timeout):
            raise TimeoutError(f"agent {self.name}: broker did not ack registration")
        self._hb_thread = threading.Thread(
            target=self._hb_loop, daemon=True, name=f"pixie-agent-hb-{self.name}"
        )
        self._hb_thread.start()
        if self.healthz is not None:
            self.healthz.start()
        self.matviews.start_refresher()  # no-op unless PL_MATVIEW_REFRESH_S>0
        period = float(flags.get("PL_SELF_METRICS_S"))
        if period > 0:
            from pixie_tpu.services.cron import Ticker

            # metrics-as-data on the agent side: this process's registry
            # folds into the LOCAL store (no hop — the agent IS the data
            # plane), stamped with the agent's own service name
            self._self_metrics = Ticker(
                f"self_metrics_{self.name}", period,
                self._fold_self_metrics).start()
        return self

    def _journal_detail(self) -> dict:
        """Per-table journal disk usage for the /healthz detail payload:
        PL_JOURNAL_MAX_MB pruning pressure, visible before it bites."""
        from pixie_tpu.table.table import Table

        tables = {}
        total = 0
        for name in self.store.names():
            t = self.store._tables.get(name)
            j = getattr(t, "journal", None) if isinstance(t, Table) else None
            if j is None:
                continue
            nbytes, nsegs = j.disk_usage()
            tables[name] = {"bytes": nbytes, "segments": nsegs}
            total += nbytes
        return {"tables": tables, "total_bytes": total,
                "budget_mb": int(flags.get("PL_JOURNAL_MAX_MB"))}

    def _fold_self_metrics(self) -> None:
        """PL_SELF_METRICS_S cron body: the metrics registry plus the
        storage observatory (decayed shard heat + per-table storage state,
        table/heat.py) fold into the local store."""
        observe.write_rows(self.store, observe.METRICS_TABLE,
                           observe.sample_metrics_rows(self.name))
        _heat.fold_into(self.store, self.name, matviews=self.matviews,
                        replication=self.replication)
        from pixie_tpu.engine import autotune as _autotune

        if _autotune.enabled():
            # adaptive-gate events raised in THIS process (fallback trips,
            # fitted-threshold changes) land in the local store's slice of
            # the autotune table on the same cadence as the metrics fold
            rows = _autotune.MODEL.drain_rows()
            if rows:
                observe.write_rows(self.store, observe.AUTOTUNE_TABLE, rows)

    def stop(self):
        self._stop.set()
        if self._self_metrics is not None:
            self._self_metrics.stop()
            self._self_metrics = None
        self.matviews.stop_refresher()
        if self.healthz is not None:
            self.healthz.stop()
        if self.collector is not None:
            self.collector.stop()
        from pixie_tpu.services import faultinject as _faultinject

        # only OUR handler: a restarted successor owns the label now
        _faultinject.unregister_kill_handler(f"agent:{self.name}",
                                             fn=self._pod_kill)
        if self.replication is not None:
            self.replication.stop()
            self.replication = None
        if self._owns_journal:
            _journal.detach_store(self.store)
            self._owns_journal = False
        if self.conn is not None:
            self.conn.close()

    # ------------------------------------------------------------- durability
    def _rehydrate(self, timeout: float) -> None:
        """Restore durable state BEFORE registration, so the broker never
        dispatches to a store that is still catching up: (1) journal replay
        into the local store (acked rows survive restart), (2) peer fetch
        of sealed batches the journal no longer covers (pod loss), (3) the
        matview snapshot dir arms so standing state resumes at O(delta).
        A no-op with PL_DATA_DIR unset and PL_REPLICATION=1."""
        ndir = _journal.node_dir(self.name)
        if ndir is not None:
            self.rehydrate_stats["journal"] = _journal.attach_store(
                self.store, ndir)
            self._owns_journal = True
            import os as _os

            self.matviews.set_snapshot_dir(_os.path.join(ndir, "matview"))
        if not _replication.enabled():
            return
        self.replication = _replication.ReplicationManager(
            self.name, self.store).start()
        try:
            reply = self._rpc({"msg": "get_peers", "agent": self.name},
                              timeout=timeout)
        except TimeoutError:
            return  # an old broker: replicate-only mode, no topology yet
        shard_map = reply.get("shard_map") or {}
        peers = reply.get("peers") or {}
        self.replication.on_shard_map(shard_map, peers)
        holders = [h for h in (shard_map.get(self.name) or []) if h in peers]
        if holders:
            self.rehydrate_stats["fetch"] = self.replication.fetch_missing(
                self.store, holders)

    def _pod_kill(self) -> None:
        """True pod loss (faultinject `kill:` rule): drop every in-memory
        table — recovery must come from the journal and the replica peers,
        never from preserved process state."""
        self.pod_killed.set()
        self._stop.set()
        if self._owns_journal:
            _journal.detach_store(self.store)
            self._owns_journal = False
        if self.replication is not None:
            self.replication.stop()
            self.replication = None
        for n in list(self.store.names()):
            self.store.drop(n)

    def _rpc(self, meta: dict, timeout: float = 10.0) -> dict:
        import uuid as _uuid

        rid = meta.setdefault("req_id", _uuid.uuid4().hex)
        slot = [threading.Event(), None]
        with self._replies_lock:
            self._replies[rid] = slot
        try:
            self.conn.send(wire.encode_json(meta))
            if not slot[0].wait(timeout):
                raise TimeoutError(f"broker did not answer {meta.get('msg')}")
            return slot[1]
        finally:
            with self._replies_lock:
                self._replies.pop(rid, None)

    def _register(self):
        self.conn.send(wire.encode_json({
            "msg": "register",
            "agent": self.name,
            "schemas": {t: r.to_dict() for t, r in self.store.schemas().items()},
            "n_devices": self.n_devices,
            "repl_addr": (list(self.replication.addr())
                          if self.replication is not None else None),
        }))

    def _hb_loop(self):
        while not self._stop.wait(timeout=self.heartbeat_s):
            if self.conn is None or self.conn.closed:
                return
            self.conn.send(wire.encode_json({"msg": "heartbeat", "agent": self.name}))

    # ------------------------------------------------------------------- frames
    def _on_frame(self, conn: Connection, frame: bytes):
        kind, payload = wire.decode_frame(frame)
        if kind != "json":
            return
        msg = payload.get("msg")
        if msg == "registered":
            self.asid = payload.get("asid")
            self._registered.set()
        elif msg == "chunk_ack":
            # broker consumed (folded) one of our chunk frames: open the
            # in-flight window by one.  MUST stay on the read loop — it's a
            # lone semaphore release, and a thread per ack would cost more
            # than the fold it acknowledges.  Keyed per (req_id, attempt,
            # source agent): a hedged duplicate dispatch runs concurrently
            # with its twin, and a failover replica may stream its OWN
            # fragment beside a takeover fragment of the same query —
            # neither must drain the other's window.
            key = (f"{payload.get('req_id', '')}"
                   f"#{int(payload.get('attempt') or 0)}"
                   f"#{payload.get('agent') or self.name}")
            with self._windows_lock:
                sem = self._windows.get(key)
            if sem is not None:
                sem.release()
        elif msg == "reregister":
            self._register()
        elif msg == "retire_query":
            # scale-down drain audit (broker.retire_agent) — OFF the read
            # loop: wait_synced may block up to its budget, and stalling
            # this loop would freeze chunk_ack/execute/shard_map handling
            # for every in-flight query on a still-serving retire candidate
            threading.Thread(
                target=self._answer_retire_query,
                args=(payload.get("req_id"),), daemon=True,
                name=f"pixie-agent-retire-{self.name}",
            ).start()
        elif msg == "peers":
            # reply to a get_peers RPC (rehydration topology fetch)
            with self._replies_lock:
                slot = self._replies.get(payload.get("req_id"))
            if slot is not None:
                slot[1] = payload
                slot[0].set()
        elif msg == "shard_map":
            # broker push on topology change: retarget replication and drop
            # takeover materializations for primaries this node left
            if self.replication is not None:
                self.replication.on_shard_map(payload.get("map") or {},
                                              payload.get("peers") or {})
        elif msg == "execute":
            threading.Thread(
                target=self._execute, args=(payload,), daemon=True,
                name=f"pixie-agent-exec-{self.name}",
            ).start()
        elif msg == "spans":
            # broker-shipped spans (the merger holds no scanned store):
            # append into the local spans table so one distributed scan
            # returns the full trace.  Off the read loop — a table write
            # must not queue execute/heartbeat frames behind telemetry.
            threading.Thread(
                target=self._write_shipped_spans,
                args=(payload.get("spans") or [], payload.get("trace")),
                daemon=True,
                name=f"pixie-agent-spans-{self.name}",
            ).start()
        elif msg == "telemetry_rows":
            # broker-shipped flight-recorder rows (query profiles, op
            # stats, sampled metrics, SLO alerts): same contract as spans
            threading.Thread(
                target=self._write_telemetry_rows,
                args=(payload.get("table"), payload.get("rows") or [],
                      payload.get("trace")),
                daemon=True,
                name=f"pixie-agent-telemetry-{self.name}",
            ).start()
        elif msg == "rehome_prepare":
            # donor-side shard re-homing prep (broker.rehome_agent) — OFF
            # the read loop: force-sealing takes table locks and the
            # replication drain blocks up to its budget
            threading.Thread(
                target=self._answer_rehome_prepare,
                args=(payload.get("req_id"),), daemon=True,
                name=f"pixie-agent-rehome-{self.name}",
            ).start()
        elif msg == "rehome_audit":
            # target-side coverage audit: report the replica manifest this
            # node holds FOR the donor so the broker can verify the move
            threading.Thread(
                target=self._answer_rehome_audit,
                args=(payload.get("req_id"), payload.get("donor")),
                daemon=True,
                name=f"pixie-agent-rehome-audit-{self.name}",
            ).start()
        elif msg == "storage_report":
            # on-demand storage observatory read (broker heat_map RPC):
            # current decayed heat + storage state, NOT a fold — nothing is
            # written.  Off the read loop: the state walk takes table locks.
            threading.Thread(
                target=self._answer_storage_report,
                args=(payload.get("req_id"),), daemon=True,
                name=f"pixie-agent-storage-{self.name}",
            ).start()
        elif msg == "deploy_tracepoint":
            try:
                self.tracepoints.apply([payload["spec"]])
                # schemas changed: re-register BEFORE acking so the broker's
                # registry sees the new table when the ack lands
                self._register()
                self.conn.send(wire.encode_json({
                    "msg": "tracepoint_ready", "req_id": payload.get("req_id"),
                    "qtoken": payload.get("qtoken"),
                    "agent": self.name,
                }))
            except Exception as e:
                self.conn.send(wire.encode_json({
                    "msg": "tracepoint_error", "req_id": payload.get("req_id"),
                    "qtoken": payload.get("qtoken"),
                    "agent": self.name, "error": str(e),
                }))

    def _answer_retire_query(self, req_id) -> None:
        """Report the rows this agent holds outside the self-telemetry
        tables (the data a retire would lose) and whether the replication
        stream has synced them onto the peers — the broker's loss-safety
        input (broker.retire_agent)."""
        rows = 0
        for n in self.store.names():
            if n.startswith("self_telemetry."):
                continue
            try:
                rows += int(self.store.table(n).stats()
                            .get("rows_written", 0))
            except Exception:
                rows = -1  # unauditable: the broker refuses the retire
                break
        synced = (self.replication is not None
                  and self.replication.wait_synced(0.5))
        # per-peer watermark detail: the drain audit used to infer "synced"
        # as a bare bool — now the sent/acked/lag numbers behind the verdict
        # travel with it
        peer_sync = (self.replication.sync_state()
                     if self.replication is not None else {})
        self.conn.send(wire.encode_json({
            "msg": "retire_info", "req_id": req_id,
            "agent": self.name, "rows": rows, "repl_synced": synced,
            "peer_sync": peer_sync}))

    def _answer_rehome_prepare(self, req_id) -> None:
        """Donor half of a shard move (broker.rehome_agent): force-seal
        every hot remainder into replicable sealed form, drain the
        replication stream (the staged target is already in our shard map,
        so the seals ship to it), and report per-table row frontiers — the
        coverage the broker audits against the target's replica manifest."""
        from pixie_tpu.table.table import Table

        tables: dict = {}
        err = ""
        synced = False
        try:
            skip = _journal.non_durable_tables()
            for n in self.store.names():
                if n.startswith("self_telemetry.") or n in skip:
                    continue
                t = self.store._tables.get(n)
                if not isinstance(t, Table):
                    continue
                t.seal_hot()
                tables[n] = {"first": int(t.first_row_id()),
                             "last": int(t.last_row_id())}
            synced = (self.replication is not None
                      and self.replication.wait_synced(10.0))
        except Exception as e:
            err = str(e)
        self.conn.send(wire.encode_json({
            "msg": "rehome_info", "req_id": req_id, "agent": self.name,
            "phase": "prepare", "tables": tables,
            "repl_synced": bool(synced),
            "peer_sync": (self.replication.sync_state()
                          if self.replication is not None else {}),
            "error": err}))

    def _answer_rehome_audit(self, req_id, donor) -> None:
        """Target half of a shard move: the replica manifest this node
        holds FOR the donor ({table: {ranges: [[start, n]...]}}), which the
        broker diffs against the donor's reported frontiers to decide
        whether the flip is safe to commit."""
        man: dict = {}
        err = ""
        try:
            if self.replication is not None:
                man = self.replication.replicas.manifest(str(donor or ""))
        except Exception as e:
            err = str(e)
        self.conn.send(wire.encode_json({
            "msg": "rehome_info", "req_id": req_id, "agent": self.name,
            "phase": "audit", "donor": donor,
            "tables": {n: {"ranges": m.get("ranges") or []}
                       for n, m in man.items()},
            "error": err}))

    def _answer_storage_report(self, req_id) -> None:
        """One storage_report RPC answer: this agent's decayed shard-heat
        snapshot + storage-state rows (table/heat.py), as JSON."""
        try:
            report = {
                "shard_heat": _heat.snapshot_rows(),
                "storage_state": _heat.storage_state_rows(
                    self.store, self.name, matviews=self.matviews,
                    replication=self.replication),
            }
        except Exception as e:
            report = {"error": str(e)}
        self.conn.send(wire.encode_json({
            "msg": "storage_report", "req_id": req_id,
            "agent": self.name, **report}))

    def _execute(self, meta: dict):
        import contextlib

        req_id = meta.get("req_id", "")
        # echoed on every result frame; the broker drops frames whose token
        # doesn't match the live dispatch (per-dispatch result-stream auth,
        # reference carnotpb/carnot.proto:30-96).  `attempt` distinguishes
        # re-dispatches and hedged duplicates of the same query.
        qtoken = meta.get("qtoken")
        attempt = int(meta.get("attempt") or 0)
        # failover takeover: the broker dispatched a DEAD primary's fragment
        # here — execute it over the store materialized from that primary's
        # replicated sealed batches, and answer AS the primary (src/token
        # bookkeeping at the broker is keyed by the planned agent name)
        serve_for = meta.get("serve_for")
        src_name = str(serve_for) if serve_for else self.name
        # the window key carries the SOURCE name: a replica can run its own
        # fragment AND a takeover fragment of the same (req, attempt) — two
        # streams, two windows; a shared key would starve one of its acks
        wkey = f"{req_id}#{attempt}#{src_name}"
        # cross-process trace context: parent this agent's exec spans under
        # the broker's dispatch span for the same query
        tctx = meta.get("trace")
        cm = (trace.root(self.tracer, "exec", ctx=tctx, agent=self.name,
                         req_id=req_id)
              if tctx else contextlib.nullcontext())
        # a degraded broker narrows the in-flight chunk window per query
        # (serving-front backpressure: admitted queries throttle harder
        # instead of queueing frames at a merge that can't keep up)
        window = int(meta.get("stream_window")
                     or flags.get("PL_STREAM_WINDOW"))
        sem = threading.Semaphore(window) if window > 0 else None
        if sem is not None:
            with self._windows_lock:
                self._windows[wkey] = sem
        #: the exec root's own wire context: telemetry_flush is recorded
        #: under it once the root has closed
        exec_ctx = None
        try:
            with cm:
                exec_ctx = trace.wire_context()
                t_decode = time.time_ns()
                plan = Plan.from_dict(meta["plan"])
                exec_store = self.store
                if serve_for:
                    if self.replication is None:
                        raise RuntimeError(
                            f"takeover dispatch for {serve_for} without "
                            "replication enabled")
                    exec_store = self.replication.takeover_store(
                        str(serve_for))
                # Standing-view fast path: an eligible repeated plan answers
                # from incrementally refreshed partial-agg state (first sight
                # only registers and runs the normal path below).  analyze
                # runs bypass views — they exist to measure the real scan.
                # Takeover serves bypass them too: standing state is bound to
                # THIS node's store, not the materialized primary shard.
                served = None
                if not meta.get("analyze") and not serve_for:
                    served = self.matviews.serve(
                        plan, route_scale=int(meta.get("route_scale", 1)),
                        mesh=self.mesh, tenant=str(meta.get("tenant") or ""),
                        stale_ok=bool(meta.get("stale_ok")))
                if served is not None:
                    cid, pb, mv_info = served
                    ex = None
                    stream = _chunk_view_state(cid, pb, int(
                        flags.get("PL_STREAM_AGG_CHUNK_GROUPS")))
                else:
                    mv_info = None
                    ex = PlanExecutor(
                        plan, exec_store, self.registry,
                        analyze=bool(meta.get("analyze", False)),
                        route_scale=int(meta.get("route_scale", 1)),
                        mesh=self.mesh,
                    )
                    stream = ex.run_agent_stream(
                        agg_chunk_groups=int(
                            flags.get("PL_STREAM_AGG_CHUNK_GROUPS")))
                trace.event_span("plan_decode", t_decode,
                                 time.time_ns() - t_decode,
                                 matview=served is not None)
                t0 = time.perf_counter()
                # Chunk stream: each wave/slice ships as its own frame the
                # moment the executor yields it, so the broker's incremental
                # fold (and the NEXT wave's D2H) overlap this agent's compute
                # instead of queueing behind a terminal result frame.
                counts: dict[str, int] = {}
                stalled = False
                # result_send: first chunk's encode -> last chunk's send
                t_send = t_sent = sent_bytes = 0
                for channel, payload in stream:
                    t_send = t_send or time.time_ns()
                    if not stalled:
                        stalled = not self._await_window(sem)
                    seq = counts.get(channel, 0)
                    counts[channel] = seq + 1
                    extra = {"msg": "chunk", "req_id": req_id,
                             "channel": channel, "seq": seq,
                             "agent": src_name, "qtoken": qtoken,
                             "attempt": attempt}
                    if isinstance(payload, PartialAggBatch):
                        frame = wire.encode_partial_agg(payload, extra)
                    elif isinstance(payload, HostBatch):
                        frame = wire.encode_host_batch(payload, extra)
                    else:
                        raise TypeError(f"unexpected payload {type(payload)}")
                    self.conn.send(frame)
                    sent_bytes += len(frame)
                    t_sent = time.time_ns()
                if t_send:
                    trace.event_span("result_send", t_send, t_sent - t_send,
                                     chunks=sum(counts.values()),
                                     bytes=sent_bytes)
                stats = dict(ex.stats) if ex is not None else {}
                if mv_info is not None:
                    stats["matview"] = mv_info
                if serve_for:
                    # completeness accounting: the broker folds this into
                    # stats["fault"]["failover"] so a degraded (replica-
                    # served) answer is auditable per query
                    stats["takeover"] = {"primary": src_name,
                                         "replica": self.name}
                stats["exec_s"] = time.perf_counter() - t0
            # spans persist BEFORE the ack: when exec_done lands at the
            # broker, this query's spans are already scannable
            self._flush_trace(exec_ctx)
            from pixie_tpu.services.broker import _jsonable

            self.conn.send(wire.encode_json({
                "msg": "exec_done", "req_id": req_id, "agent": src_name,
                "qtoken": qtoken, "attempt": attempt,
                "stats": _jsonable(stats),
                # per-channel chunk counts: the broker verifies its folds saw
                # every frame (a dropped chunk must fail loudly, not merge a
                # silently-partial answer)
                "chunks": counts,
            }))
        except Exception as e:
            self._flush_trace(exec_ctx)
            self.conn.send(wire.encode_json({
                "msg": "exec_error", "req_id": req_id, "agent": src_name,
                "qtoken": qtoken, "attempt": attempt, "error": str(e),
            }))
        finally:
            if sem is not None:
                with self._windows_lock:
                    self._windows.pop(wkey, None)

    def _await_window(self, sem: Optional[threading.Semaphore]) -> bool:
        """Block until the in-flight chunk window opens; False on stall.
        After one stall the caller stops waiting for the rest of the query
        (degraded to unbounded, counted): TCP still backpressures a
        slow-but-alive broker, and a broker that stopped acking — typically
        because this query already died there — must not wedge this
        executor thread for stall-budget × remaining-chunks."""
        if sem is None:
            return True
        deadline = time.monotonic() + ACK_STALL_S
        while not self._stop.is_set():
            if sem.acquire(timeout=0.2):
                return True
            if self.conn is None or self.conn.closed:
                return False
            if time.monotonic() >= deadline:
                from pixie_tpu import metrics as _metrics

                _metrics.counter_inc(
                    "px_agent_chunk_ack_stalls_total",
                    help_="chunk sends that proceeded without an ack "
                          "(broker stopped acking within the stall budget)")
                return False
        return False

    def _write_shipped_spans(self, rows: list, tctx=None) -> None:
        t0 = time.time_ns()
        try:
            trace.write_spans(self.store, rows)
        except Exception:
            from pixie_tpu import metrics as _metrics

            _metrics.counter_inc(
                "px_agent_span_write_errors_total",
                help_="spans that failed to persist to the local store")
        self._telemetry_span("telemetry_write", tctx, t0, len(rows),
                             trace.SPANS_TABLE)

    def _write_telemetry_rows(self, table, rows: list, tctx=None) -> None:
        t0 = time.time_ns()
        try:
            if table in observe.SELF_TABLES:
                observe.write_rows(self.store, str(table), rows)
        except Exception:
            from pixie_tpu import metrics as _metrics

            _metrics.counter_inc(
                "px_agent_telemetry_write_errors_total",
                help_="flight-recorder rows that failed to persist to the "
                      "local store")
        self._telemetry_span("telemetry_write", tctx, t0, len(rows),
                             str(table))

    def _flush_trace(self, tctx=None) -> None:
        """Persist buffered spans; never let telemetry failure block the
        exec_done/exec_error ack (an unacked query stalls the broker for
        the full query timeout)."""
        if not trace.enabled():
            # no query records a span now; what the last flush's own span
            # left in the buffer waits for tracing to come back
            return
        t0 = time.time_ns()
        rows = []
        try:
            rows = self.tracer.flush(store=self.store)
        except Exception:
            from pixie_tpu import metrics as _metrics

            _metrics.counter_inc(
                "px_agent_span_write_errors_total",
                help_="spans that failed to persist to the local store")
        self._telemetry_span("telemetry_flush", tctx, t0, len(rows),
                             trace.SPANS_TABLE)

    def _telemetry_span(self, name: str, tctx, t0_unix_ns: int, rows: int,
                        table: str) -> None:
        """What a self-telemetry write cost, as a span of the query that
        caused it (`tctx`, its wire context).  Recorded after the write it
        measures, so it is persisted with the next flush: one span a
        write, and no write for the span."""
        trace.remote_event_span(self.tracer, tctx, name, t0_unix_ns,
                                time.time_ns() - t0_unix_ns, rows=rows,
                                table=table)


def main(argv=None):
    """`python -m pixie_tpu.services.agent --name pem1 --broker host:port
    [--connector seq_gen]` — standalone agent process (the pem_main analog)."""
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--name", required=True)
    ap.add_argument("--broker", required=True, help="host:port")
    ap.add_argument("--connector", action="append", default=[],
                    help="seq_gen | proc_stats | perf_profiler | "
                         "access_log:/path/to/log (repeatable)")
    ap.add_argument("--heartbeat-s", type=float, default=DEFAULT_HEARTBEAT_S)
    ap.add_argument("--auth-token", default=None,
                    help="shared secret; required if the broker enables auth")
    ap.add_argument("--healthz-port", type=int, default=None,
                    help="serve HTTP /healthz + /metrics on this port")
    ap.add_argument("--healthz-host", default="127.0.0.1",
                    help="bind address for the healthz listener (use the "
                         "pod IP / 0.0.0.0 for remote k8s probes)")
    ap.add_argument("--proc-scan-s", type=float, default=0.0,
                    help="scan /proc every N seconds, binding live PIDs to "
                         "UPIDs (+pods via cgroup) in the metadata state "
                         "(reference pids.cc); 0 disables")
    ap.add_argument("--watch-feed", default=None,
                    help="JSONL file of ResourceUpdates to tail into the "
                         "metadata state (the k8s watch fanout analog)")
    args = ap.parse_args(argv)
    host, port = args.broker.rsplit(":", 1)

    from pixie_tpu.collect.core import Collector

    collector = Collector()
    for cname in args.connector:
        if cname == "seq_gen":
            from pixie_tpu.collect.seq_gen import SeqGenConnector

            collector.register(SeqGenConnector())
        elif cname == "proc_stats":
            from pixie_tpu.collect.proc_stats import ProcStatsConnector

            collector.register(ProcStatsConnector())
        elif cname == "perf_profiler":
            from pixie_tpu.collect.perf_profiler import PerfProfilerConnector

            collector.register(PerfProfilerConnector())
        elif cname.startswith("access_log:"):
            from pixie_tpu.collect.access_log import AccessLogConnector

            collector.register(AccessLogConnector(cname.split(":", 1)[1]))
        elif cname.startswith("capture:"):
            # Replay a socket-event capture through the protocol parsers
            # (socket_tracer): capture:/path/to/capture.jsonl
            from pixie_tpu.collect.tracer import (
                CaptureFileSource,
                SocketTraceConnector,
            )

            path = cname.split(":", 1)[1]
            collector.register(SocketTraceConnector(
                CaptureFileSource(path), name=f"socket_tracer:{path}"))
        elif cname.startswith("tap:"):
            # Live tap proxy: tap:<listen_port>:<upstream_host>:<upstream_port>
            # — proxies traffic and traces every connection through it.
            from pixie_tpu.collect.tap import TapProxy
            from pixie_tpu.collect.tracer import SocketTraceConnector

            lport, uhost, uport = cname.split(":", 1)[1].split(":")
            tap = TapProxy(uhost, int(uport), listen_port=int(lport),
                           pid=os.getpid()).start()
            collector.register(SocketTraceConnector(
                tap.source, name=f"socket_tracer:tap:{tap.port}"))
        else:
            raise SystemExit(f"unknown connector {cname!r}")
    md_jobs = []
    if args.proc_scan_s > 0 or args.watch_feed:
        from pixie_tpu.metadata.state import global_manager

        mgr = global_manager()
        if args.proc_scan_s > 0:
            from pixie_tpu.metadata.proc_scanner import ProcScanner

            md_jobs.append((args.proc_scan_s,
                            ProcScanner(asid=mgr.current().asid).scan_into,
                            mgr))
        if args.watch_feed:
            from pixie_tpu.metadata.watch import ResourceUpdateFeed

            feed = ResourceUpdateFeed(mgr, args.watch_feed)
            md_jobs.append((1.0, lambda _m, feed=feed: feed.poll(), mgr))

    def _md_loop(period, fn, mgr):
        while True:
            try:
                fn(mgr)
            except Exception:
                pass  # metadata refresh must never kill the agent
            time.sleep(period)

    for period, fn, mgr in md_jobs:
        threading.Thread(target=_md_loop, args=(period, fn, mgr),
                         daemon=True).start()

    # The agent OWNS this process's chip(s): start the backend now, say
    # which, and register the width of the mesh the executor will actually
    # shard over, so the planner widens shuffle joins to it.
    import jax

    from pixie_tpu.parallel.spmd import default_mesh

    mesh = default_mesh()
    n_devices = mesh.size if mesh is not None else 1
    d0 = jax.devices()[0]
    agent = Agent(args.name, host, int(port), collector=collector,
                  heartbeat_s=args.heartbeat_s, auth_token=args.auth_token,
                  healthz_port=args.healthz_port,
                  healthz_host=args.healthz_host, n_devices=n_devices)
    agent.start()
    print(f"agent {args.name} registered with {args.broker} "
          f"platform={d0.platform} device_kind={d0.device_kind!r} "
          f"devices={len(jax.devices())} mesh_devices={n_devices}",
          flush=True)
    try:
        while True:
            time.sleep(1.0)
            if agent.conn is None or agent.conn.closed:
                raise SystemExit("broker connection lost")
    except KeyboardInterrupt:
        pass
    finally:
        agent.stop()


if __name__ == "__main__":
    main()
