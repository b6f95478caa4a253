"""Query broker: the networked ExecuteScript front door.

Reference: src/vizier/services/query_broker — Server.ExecuteScript
(controllers/server.go:307) compiles the script, LaunchQuery ships per-agent
plans (launch_query.go:36-66), and QueryResultForwarder merges agent result
streams into the client stream with producer/consumer watchdogs
(query_result_forwarder.go:358-560).

This broker listens on one framed-TCP port for BOTH agents and clients
(the envelope's `msg` field routes).  Per query: compile against the live
registry's schemas, split with DistributedPlanner, push `execute` frames to
each agent's connection, collect channel payload frames, merge (partials via
combine/finalize, rows via dictionary-reconciled union), run the merger plan
locally, and stream result chunks back to the client.
"""
from __future__ import annotations

import json as _json
import threading
import time
import traceback
from typing import Optional

from pixie_tpu import flags as _flags
from pixie_tpu import trace
from pixie_tpu.engine import autotune as _autotune
from pixie_tpu.engine.executor import HostBatch, PlanExecutor
from pixie_tpu.engine.result import QueryResult
from pixie_tpu.parallel.distributed import DistributedPlanner
from pixie_tpu.serving import COST_COLD, COST_WARM, ServingFront, ShedError
from pixie_tpu.services import replication as _replication
from pixie_tpu.services import wire
from pixie_tpu.services.kvstore import KVStore
from pixie_tpu.services.registry import AgentRegistry
from pixie_tpu.services.transport import Connection, Server
from pixie_tpu.status import PxError
from pixie_tpu.table.table import TableStore
from pixie_tpu.types import Relation

DEFAULT_QUERY_TIMEOUT_S = 60.0

#: tenant id stamped on queries that arrive without one (older clients,
#: in-process callers like cron): they share one namespace and one quota
#: bucket rather than bypassing admission entirely
DEFAULT_TENANT = "default"

#: broker end-to-end query latency buckets (seconds)
QUERY_LATENCY_BOUNDS = (0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                        10.0, 30.0, 60.0)

#: incremental_fold spans recorded per query (folds beyond the cap still
#: merge and count; only their span detail is dropped)
MAX_FOLD_EVENT_SPANS = 256

_flags.define_int(
    "PL_QUERY_RETRIES", 2,
    "broker-side re-dispatch rounds after an agent eviction (heartbeat "
    "expiry / mid-stream disconnect): surviving agents' folded results are "
    "kept, the lost fragments re-plan onto the live agent set and re-"
    "dispatch under fresh per-dispatch tokens; 0 restores fail-fast")
_flags.define_int(
    "PL_RETRY_BACKOFF_MS", 100,
    "base for the jittered exponential backoff between re-dispatch rounds "
    "(round i sleeps ~base*2^i, capped at 5s) — the window a killed-and-"
    "restarted agent gets to re-register before its fragments re-plan "
    "around it")
_flags.define_bool(
    "PL_HEDGE_ENABLED", True,
    "straggler hedging: a dispatch outliving its per-agent service-time "
    "deadline (EWMA/p99-derived) gets a duplicate dispatch; first answer "
    "wins, the loser's chunks are discarded idempotently")
_flags.define_int(
    "PL_HEDGE_MIN_MS", 500,
    "floor for the hedge deadline — never hedge a dispatch younger than "
    "this, however fast the agent's history says it should be")
_flags.define_float(
    "PL_HEDGE_FACTOR", 3.0,
    "hedge deadline = max(PL_HEDGE_MIN_MS, factor * p99_estimate) where "
    "p99_estimate = service-time EWMA + 4 * EWMA(|deviation|)")
_flags.define_float(
    "PL_REJOIN_GRACE_S", 2.0,
    "how long after an agent's death its shard counts as REJOINING: "
    "dispatch (and re-dispatch) holds for it instead of silently planning "
    "a reduced topology — a restarting pod re-registers within the grace; "
    "past it the cluster serves the surviving agents' data (the reference "
    "data-plane semantic).  Only active when PL_QUERY_RETRIES > 0")

#: service-time samples required before hedging arms for an agent — a cold
#: EWMA over one or two samples would hedge every slow compile
HEDGE_MIN_SAMPLES = 8

#: cap on the re-dispatch backoff and on the retry-after hint shipped with
#: a retry-budget-exhausted error
MAX_BACKOFF_MS = 5000.0

#: pxlint lock-discipline: _QueryCtx's *_locked members are owned by the
#: per-query ctx lock (checked by pixie_tpu.check.pxlint at CI time)
_pxlint_locks_ = {"_check_done_locked": ".lock"}


class _QueryCtx:
    """In-flight bookkeeping for one distributed query (or tracepoint
    deploy round).

    Fault-tolerant dispatch model: every `execute` frame is one DISPATCH,
    identified by ``src = f"{agent}#{attempt}"`` and authenticated by its
    OWN token (the per-query token of PR 1, narrowed per dispatch).  Chunk
    frames fold into per-src accumulators (`parallel.cluster.
    SourceKeyedFold`), so an evicted agent's partial stream — or the losing
    side of a hedged duplicate dispatch — is discarded at merge simply by
    never ACCEPTING its src; nothing is un-folded and late/duplicate chunks
    land in sub-folds nobody reads (idempotent discard).  The first
    exec_done per agent wins (`accepted[agent] = src`)."""

    def __init__(self, channels: set[str], retryable: bool = True):
        import secrets

        self.lock = threading.RLock()
        #: dead primary → live replica serving its shard this query
        #: (sealed-batch replication failover); {} without replication
        self.failover: dict[str, str] = {}
        #: failover routes actually dispatched (→ stats["fault"])
        self.failover_used: dict[str, str] = {}
        #: False for tracepoint-deploy rounds: agent loss fails the round
        #: immediately (mutations are never transparently re-dispatched)
        self.retryable = retryable
        self.error: Optional[str] = None
        self.done = threading.Event()
        #: nudges the query thread: completion, eviction, or error
        self.wake = threading.Event()
        #: base token — tracepoint deploy rounds dispatch under it directly
        self.token = secrets.token_urlsafe(12)
        #: agents whose answer the current plan requires
        self.needed_agents: set[str] = set()
        #: src → {agent, attempt, frag, deadline, hedged, t0}
        self.pending: dict[str, dict] = {}
        #: src → per-dispatch auth token (never pruned within a query: late
        #: frames from a losing/evicted src must validate so their discard
        #: is COUNTED as a discard, not mistaken for a stale-query frame)
        self.tokens: dict[str, str] = {}
        #: agent → winning src (first exec_done)
        self.accepted: dict[str, str] = {}
        #: src → the fragment JSON it was dispatched with (re-dispatch
        #: keeps an accepted result only when its fragment is unchanged
        #: under the re-planned split)
        self.frags: dict[str, Optional[str]] = {}
        self.next_attempt: dict[str, int] = {}
        #: (agent, reason) eviction events awaiting the query thread
        self.evictions: list[tuple] = []
        #: one hedge per agent per dispatch round
        self.hedged_agents: set[str] = set()
        #: per-src dispatch spans, opened at frame send and closed by the
        #: exec_done/exec_error handler threads (or eviction cleanup)
        self.dispatch_spans: dict[str, object] = {}
        self.agent_stats: dict[str, dict] = {}
        # ---- streaming incremental merge (set up by configure_folds) ----
        #: channel id → SourceKeyedFold: chunk frames fold into per-src
        #: sub-accumulators AS THEY ARRIVE (reader threads), so merge work
        #: hides under the slowest agent's compute AND a src is droppable
        self.folds: dict[str, object] = {}
        #: per-channel locks: fold.add serializes across agent reader
        #: threads, but folds on DISTINCT channels share no state
        self.fold_locks: dict[str, threading.Lock] = {}
        #: join-stage bucket channels accumulate whole payload lists per
        #: src; the stage runner consumes the accepted srcs' lists at merge
        self.bucket_payloads: dict[str, dict[str, list]] = {}
        #: (channel, src) → chunks the producer reported on exec_done
        self.expected_chunks: dict[tuple, int] = {}
        #: (start_unix_ns, duration_ns, channel, agent) per fold, emitted as
        #: incremental_fold spans at merge time (the reader threads hold no
        #: trace context); capped — first_fold_ns/last_terminal_ns carry the
        #: overlap evidence, span detail beyond the cap adds nothing
        self.fold_events: list[tuple] = []
        self.first_fold_ns: Optional[int] = None
        self.last_terminal_ns: Optional[int] = None

    def configure_folds(self, dp, registry) -> None:
        """Arm one source-keyed accumulator per merge-input channel.  Must
        run before the first `execute` frame is sent (chunks race the
        dispatch loop); join-stage bucket channels keep list accumulation —
        the stage runner consumes whole per-partition lists at merge time."""
        from pixie_tpu.parallel.cluster import SourceKeyedFold
        from pixie_tpu.parallel.repartition import bucket_channels

        consumed = bucket_channels(dp)
        for cid, ch in dp.channels.items():
            if cid in consumed:
                continue
            self.folds[cid] = SourceKeyedFold(ch.kind, agg=ch.agg,
                                              registry=registry)
            self.fold_locks[cid] = threading.Lock()

    # ------------------------------------------------------- dispatch state
    @staticmethod
    def src_of(meta: dict) -> str:
        return f"{meta.get('agent')}#{int(meta.get('attempt') or 0)}"

    def register_dispatch(self, agent: str, frag=None, deadline=None,
                          hedged: bool = False, token: Optional[str] = None,
                          via: Optional[str] = None):
        import secrets
        import time as _time

        with self.lock:
            attempt = self.next_attempt.get(agent, 0)
            self.next_attempt[agent] = attempt + 1
            src = f"{agent}#{attempt}"
            self.tokens[src] = token or secrets.token_urlsafe(12)
            self.frags[src] = frag
            self.pending[src] = {
                "agent": agent, "attempt": attempt, "frag": frag,
                "deadline": deadline, "hedged": hedged,
                # the agent whose CONNECTION carries this dispatch: the
                # planned agent itself, or its failover replica — eviction
                # of the carrier must drop the dispatch either way
                "via": via or agent,
                "t0": _time.monotonic(),
            }
            if hedged:
                self.hedged_agents.add(agent)
            return src, self.tokens[src], attempt

    def drop_dispatch(self, src: str) -> None:
        with self.lock:
            self.pending.pop(src, None)
            self.tokens.pop(src, None)

    def token_for(self, src: str) -> Optional[str]:
        with self.lock:
            return self.tokens.get(src)

    def frag_of(self, src: str) -> Optional[str]:
        return self.frags.get(src)

    def outstanding_agents(self) -> list[str]:
        with self.lock:
            return sorted(self.needed_agents - set(self.accepted))

    def uncovered_agents(self) -> list[str]:
        """Needed agents with neither an accepted result nor an in-flight
        dispatch — the set a re-dispatch round must cover."""
        with self.lock:
            covered = set(self.accepted)
            covered.update(i["agent"] for i in self.pending.values())
            return sorted(self.needed_agents - covered)

    def _check_done_locked(self) -> None:
        if self.error is not None or self.needed_agents <= set(self.accepted):
            self.done.set()
        self.wake.set()

    def fail(self, error: str) -> None:
        with self.lock:
            if self.error is None:
                self.error = error
            self._check_done_locked()

    # --------------------------------------- producer frames (reader threads)
    def on_exec_done(self, meta: dict):
        """Returns (agent, service_seconds) when this frame ACCEPTED the
        agent's result; None for stale or hedge-losing frames."""
        import time as _time

        src = self.src_of(meta)
        with self.lock:
            self.last_terminal_ns = _time.time_ns()
            info = self.pending.pop(src, None)
            if info is None:
                return None
            agent = info["agent"]
            if agent in self.accepted:
                # a hedge raced: first answer already won — this src's
                # chunks are discarded at merge (never accepted)
                self._check_done_locked()
                return None
            self.accepted[agent] = src
            self.agent_stats[agent] = meta.get("stats", {})
            for cid, n in (meta.get("chunks") or {}).items():
                self.expected_chunks[(cid, src)] = int(n)
            self._check_done_locked()
            return agent, _time.monotonic() - info["t0"]

    def on_exec_error(self, meta: dict) -> Optional[str]:
        """Returns the fatal error when no other live attempt can still
        answer for this agent; None when a hedge twin is outstanding or
        the frame is stale."""
        src = self.src_of(meta)
        with self.lock:
            info = self.pending.pop(src, None)
            if info is None:
                return None
            agent = info["agent"]
            if agent in self.accepted:
                return None
            if any(i["agent"] == agent for i in self.pending.values()):
                return None  # the hedged twin may still answer
            err = f"agent {meta.get('agent')}: {meta.get('error')}"
            if self.error is None:
                self.error = err
            self._check_done_locked()
            return err

    def on_agent_lost(self, agent: str, reason: str) -> list[str]:
        """Connection/liveness loss: drop the agent's in-flight dispatches
        and queue an eviction for the query thread (or fail outright for
        non-retryable rounds).  Returns dropped srcs for span cleanup.  An
        agent whose result was already accepted is a no-op — its data is
        folded and verified; its later death cannot poison this query."""
        with self.lock:
            srcs = [s for s, i in self.pending.items()
                    if i["agent"] == agent or i.get("via") == agent]
            for s in srcs:
                self.pending.pop(s, None)
            affected = bool(srcs) or (agent in self.needed_agents
                                      and agent not in self.accepted)
            if not affected:
                self.wake.set()
                return srcs
            if not self.retryable:
                if self.error is None:
                    self.error = f"agent {agent} disconnected mid-query"
                self._check_done_locked()
                return srcs
            self.evictions.append((agent, reason))
            self.wake.set()
            return srcs

    def take_evictions(self) -> list[tuple]:
        with self.lock:
            ev, self.evictions = self.evictions, []
            return ev

    def reset_for_restart(self, dp, registry) -> None:
        """Full re-dispatch: the re-planned channel topology changed (e.g.
        a repartition join lost its widest mesh), so every fold so far is
        unusable.  Fresh tokens mean frames from superseded dispatches are
        rejected (and counted) rather than folded."""
        with self.lock:
            self.pending.clear()
            self.tokens.clear()
            self.accepted.clear()
            self.frags = {}
            self.expected_chunks = {}
            self.agent_stats = {}
            self.folds = {}
            self.fold_locks = {}
            self.bucket_payloads = {}
            self.configure_folds(dp, registry)
            self.needed_agents = set(dp.agent_plans)
            self.hedged_agents = set()
            self.done.clear()

    # ------------------------------------------- chunk folds (reader threads)
    def fold_chunk(self, meta: dict, payload) -> None:
        """Fold one producer chunk frame; called from connection reader
        threads.  A malformed chunk fails the QUERY (error + done), never
        the reader thread."""
        import time as _time

        cid = meta["channel"]
        src = self.src_of(meta)
        fold = self.folds.get(cid)
        t0 = _time.time_ns()
        try:
            if fold is None:
                with self.lock:
                    self.bucket_payloads.setdefault(cid, {}).setdefault(
                        src, []).append(payload)
                return
            with self.fold_locks[cid]:
                fold.add(src, payload)
        except Exception as e:
            self.fail(f"chunk fold failed on channel {cid}: {e}")
            return
        if self.first_fold_ns is None:
            self.first_fold_ns = t0
        if len(self.fold_events) < MAX_FOLD_EVENT_SPANS:
            self.fold_events.append(
                (t0, _time.time_ns() - t0, cid, meta.get("agent")))


def _channels_compatible(dp, dp2) -> bool:
    """Whether a re-planned split can reuse the folds of the original: the
    channel set/kinds, join stages (incl. partition counts), and the merger
    plan must be identical — producer lists may differ (that is the point
    of re-planning around a dead agent)."""
    a, b = dp.to_dict(), dp2.to_dict()
    ak = {cid: (c["kind"], _json.dumps(c["agg"], sort_keys=True))
          for cid, c in a["channels"].items()}
    bk = {cid: (c["kind"], _json.dumps(c["agg"], sort_keys=True))
          for cid, c in b["channels"].items()}
    return (ak == bk and a["merger_plan"] == b["merger_plan"]
            and a["join_stages"] == b["join_stages"])


class Broker:
    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        datastore_path: str = ":memory:",
        hb_expiry_s: float = 15.0,
        registry=None,
        query_timeout_s: float = DEFAULT_QUERY_TIMEOUT_S,
        auth_token: Optional[str] = None,
        healthz_port: Optional[int] = None,
        elector=None,
        election_id: Optional[str] = None,
    ):
        # resource-free validation FIRST: a raise here must not leak a
        # bound socket or an open KV handle
        if (election_id is not None and elector is None
                and datastore_path == ":memory:"):
            from pixie_tpu.status import InvalidArgument

            raise InvalidArgument(
                "leader election requires a shared --datastore file "
                "(an in-memory lease is private to this process)")
        #: shared-secret auth (reference fronts this port with JWT,
        #: src/shared/services/).  When set, every connection must present the
        #: token in an `auth` frame before any other message is honored.  The
        #: port must never be exposed beyond a trusted network regardless.
        self.auth_token = auth_token
        self.udf_registry = registry
        self.query_timeout_s = query_timeout_s
        self.merger_store = TableStore()
        #: whole-query plan cache (PL_QUERY_FASTPATH): warm dashboard
        #: queries skip re-trace/re-optimize/re-split/re-serialize — see
        #: engine/plancache.py for the soundness argument
        from pixie_tpu.engine.plancache import QueryPlanCache

        self.plan_cache = QueryPlanCache()
        #: multi-tenant serving front (pixie_tpu.serving): every
        #: ExecuteScript passes its admission gate (per-tenant token
        #: buckets, global in-flight cap, DRR fair-share dispatch) and
        #: returns its slot on completion.  PL_SERVING_ENABLED=0 makes it
        #: a pass-through.
        self.serving = ServingFront("broker")
        #: measured per-(tenant, plan-class) service-rate model
        #: (serving/ratemodel.py): fed from every completion, it replaces
        #: the static warm/cold DRR costs and the heuristic retry-after
        #: with measured rates, and drives the autoscaler's demand signal
        from pixie_tpu.serving.ratemodel import ServiceRateModel

        self.ratemodel = ServiceRateModel()
        self.serving.rate_model = self.ratemodel
        #: broker-driven agent autoscaler (serving/elastic.py), armed in
        #: start() when PL_AUTOSCALE=1 (benches/tests may pre-assign one
        #: with their own launcher before start())
        self.supervisor = None
        #: self-telemetry spans for the query path; shipped to an agent's
        #: spans table at query end (the broker holds no scanned store)
        self.tracer = trace.Tracer("broker")
        #: query flight recorder: per-query profile/op-stat rows (and SLO
        #: alert + sampled-metric rows) buffered here, shipped to an agent
        #: store alongside the spans (pixie_tpu.observe)
        from pixie_tpu import observe as _observe

        self._telemetry = _observe.RowBuffer()
        self._self_metrics: Optional[object] = None
        #: concurrent-query batching rendezvous (PL_QUERY_BATCHING):
        #: groupable concurrent queries fuse into ONE distributed dispatch
        #: with a shared scan; results demux per member (serving/batching)
        from pixie_tpu.serving import batching as _batching

        self._batcher = _batching.BatchCollector()
        #: batch signature → BatchSlot (fused plan + sink map + split slot)
        from collections import OrderedDict as _OrderedDict

        self._batch_splits: "_OrderedDict" = _OrderedDict()
        self._agent_conns: dict[str, Connection] = {}
        self._queries: dict[str, _QueryCtx] = {}
        self._qlock = threading.Lock()
        #: broker→agent control RPC slots (retire drain audits):
        #: req_id -> [Event, reply payload]
        self._control_replies: dict[str, list] = {}
        #: per-agent service-time model for straggler hedging: EWMA of
        #: dispatch→exec_done seconds + EWMA of |deviation| (a cheap p99
        #: estimate: ewma + 4*dev); warmed by HEDGE_MIN_SAMPLES before a
        #: hedge deadline arms
        self._svc: dict[str, dict] = {}
        self._svc_lock = threading.Lock()
        self._req_counter = 0
        self._stopped = threading.Event()
        self._expiry_thread = threading.Thread(
            target=self._expiry_loop, daemon=True, name="pixie-broker-expiry"
        )
        self.kv = KVStore(datastore_path)
        self.healthz: Optional[object] = None
        self._server = None
        try:
            self.registry = AgentRegistry(self.kv, expiry_s=hb_expiry_s)
            from pixie_tpu.services.tracepoints import TracepointManager

            #: cluster-level tracepoint registry (metadata-service analog:
            #: persisted in the control KV, surfaced by GetTracepointStatus)
            self.tracepoints = TracepointManager(self.merger_store, kv=self.kv)
            from pixie_tpu.services.cron import CronScriptRunner

            #: cron scripts (reference script_runner.go:47-54), persisted in kv
            self.cron = CronScriptRunner(
                lambda script, func, func_args: self.execute_script(
                    script, func=func, func_args=func_args
                )[0],
                kv=self.kv,
            )
            # live tenant quotas persisted by the control plane: recall
            # them into the serving front so quota writes survive broker
            # restart (the PL_TENANT_* env specs stay the defaults)
            self._load_quotas()
            # recall the persisted adaptive-gate model (engine/autotune.py,
            # same KV pattern as quotas) so a restarted broker's gates
            # start warm — its first queries pay no cold exploration burst
            if _autotune.enabled():
                _autotune.MODEL.load_kv(self.kv)
            #: optional LeaderElector (services/election.py): when set, this
            #: broker only serves queries while holding the lease — a standby
            #: broker sharing the KV takes over when the leader dies
            #: (reference src/shared/services/election/).  `election_id`
            #: builds one over THIS broker's kv (one handle, one close path).
            if election_id is not None and elector is None:
                from pixie_tpu.services.election import LeaderElector

                elector = LeaderElector(self.kv, "broker", election_id)
            self.elector = elector
            #: optional HTTP healthz/metrics listener (reference
            #: src/shared/services/ healthz for k8s probes).  Leadership is
            #: a READINESS concern only: a healthy standby must pass
            #: /healthz (liveness) or a k8s liveness probe would restart it
            #: in a loop, defeating failover.
            if healthz_port is not None:
                from pixie_tpu.services.health import HealthzServer

                def _kv_alive() -> bool:
                    self.kv.get("__healthz")  # raises when the kv is unusable
                    return True

                self.healthz = HealthzServer(checks={
                    "kv": _kv_alive,
                    "server": lambda: not self._stopped.is_set(),
                }, ready_checks={
                    "leader": lambda: (self.elector is None
                                       or self.elector.is_leader()),
                }, host=host, port=healthz_port)
                # READINESS only: an overloaded broker (admission queue
                # past the shed watermark) must drop out of the serving
                # endpoints without a liveness restart wiping its queues
                self.healthz.add_ready_check("serving", self.serving.ready)
            self._server = Server(host, port, self._on_frame, self._on_close)
        except Exception:
            if self.healthz is not None:
                self.healthz.stop()
            self.kv.close()
            raise

    # ------------------------------------------------------------------ server
    @property
    def port(self) -> int:
        return self._server.port

    def start(self) -> "Broker":
        from pixie_tpu import metrics as _metrics

        _metrics.register_gauge_fn(
            "px_broker_live_agents",
            lambda: {(): float(len(self.registry.live_agents()))},
            "agents currently live in the registry",
        )
        trace.register_gauges()
        self.serving.attach_gauges()
        self.ratemodel.attach_gauges()
        # interrupted shard moves from a prior broker life abort BEFORE the
        # first shard-map push: ownership stays with the donor
        self._abort_stale_moves()
        self._server.start()
        self._expiry_thread.start()
        self.cron.start()
        from pixie_tpu.serving import elastic as _elastic  # PL_AUTOSCALE_*

        if _flags.get("PL_AUTOSCALE") and self.supervisor is None:
            # standalone broker (cli): the default launcher spawns real
            # agent subprocesses against this broker's port; harnesses
            # pre-assign a supervisor with their own launcher instead
            self.supervisor = _elastic.AgentSupervisor(
                self, _elastic.ProcLauncher("127.0.0.1", self.port))
        if self.supervisor is not None:
            self.supervisor.start()
        period = float(_flags.get("PL_SELF_METRICS_S"))
        if period > 0:
            from pixie_tpu.services.cron import Ticker

            #: metrics-as-data: fold the registry into
            #: self_telemetry.metrics (and evaluate SLO burn rates) on the
            #: same cadence dashboards poll at
            self._self_metrics = Ticker("self_metrics", period,
                                        self._sample_self_metrics).start()
        if self.elector is not None:
            self.elector.start()
        if self.healthz is not None:
            self.healthz.start()
        return self

    def stop(self):
        from pixie_tpu import metrics as _metrics

        self._stopped.set()
        if self.supervisor is not None:
            self.supervisor.stop()
        self.cron.stop()
        if self._self_metrics is not None:
            self._self_metrics.stop()
            self._self_metrics = None
        if self.healthz is not None:
            self.healthz.stop()
        if self.elector is not None:
            self.elector.stop()
        self._server.stop()
        self.serving.detach_gauges()
        self.ratemodel.detach_gauges()
        _metrics.unregister_gauge_fn("px_broker_live_agents")
        if _autotune.enabled():
            # final checkpoint: the next broker on this KV starts warm
            _autotune.MODEL.save_kv(self.kv)
        self.kv.close()

    def _expiry_loop(self):
        while not self._stopped.wait(timeout=max(self.registry.expiry_s / 3, 0.2)):
            self.registry.expire()
            # Reconcile connections against registry liveness — no matter
            # WHICH thread's expire() marked an agent dead (query paths call
            # live_agents() too), its connection gets closed here.  Dead
            # agents can't be revived by heartbeats (registry.heartbeat), so
            # this doesn't race a revival.
            live = {r.name for r in self.registry.live_agents()}
            for name, conn in list(self._agent_conns.items()):
                if name not in live:
                    self._agent_conns.pop(name, None)
                    conn.close()

    # ------------------------------------------------------------------ frames
    def _on_frame(self, conn: Connection, frame: bytes):
        if self.auth_token is not None and not conn.state.get("authed"):
            import hmac

            # Unauthenticated peers get NO decode work: the only acceptable
            # first frame is a small auth json.  Oversized or malformed
            # frames close the connection without allocating for them
            # (decode_frame would happily materialize a 1GB host_batch).
            if len(frame) > 4096:
                conn.close()
                return
            try:
                kind, payload = wire.decode_frame(frame)
            except Exception:
                conn.close()
                return
            # compare_digest over utf-8 bytes: str operands raise TypeError
            # on non-ASCII, which would skip the reject-and-close path.
            if (kind == "json" and payload.get("msg") == "auth"
                    and hmac.compare_digest(
                        str(payload.get("token", "")).encode(),
                        self.auth_token.encode())):
                conn.state["authed"] = True
                conn.send(wire.encode_json({"msg": "auth_ok"}))
            else:
                rid = payload.get("req_id") if kind == "json" else None
                conn.send(wire.encode_json(
                    {"msg": "error", "req_id": rid,
                     "error": "authentication required"}))
                conn.close()
            return
        kind, payload = wire.decode_frame(frame)
        if kind == "json":
            msg = payload.get("msg")
            if msg == "auth":
                conn.state["authed"] = True
                conn.send(wire.encode_json({"msg": "auth_ok"}))
            elif msg == "register":
                self._handle_register(conn, payload)
            elif msg == "heartbeat":
                if self._stale_incarnation(conn):
                    return  # a superseded socket's heartbeat must not keep
                    # the NEW incarnation's record warm
                if not self.registry.heartbeat(payload["agent"]):
                    conn.send(wire.encode_json({"msg": "reregister"}))
            elif msg == "tracepoint_ready":
                if self._stale_incarnation(conn):
                    return
                self._handle_exec_done({
                    "req_id": payload.get("req_id"),
                    "qtoken": payload.get("qtoken"),
                    "agent": payload.get("agent"), "stats": {},
                })
            elif msg == "tracepoint_error":
                if self._stale_incarnation(conn):
                    return
                self._handle_exec_error(payload)
            elif msg == "exec_done":
                if self._stale_incarnation(conn):
                    return
                self._handle_exec_done(payload)
            elif msg == "exec_error":
                if self._stale_incarnation(conn):
                    return
                self._handle_exec_error(payload)
            elif msg == "execute_script":
                threading.Thread(
                    target=self._run_query, args=(conn, payload), daemon=True
                ).start()
            elif msg == "metrics":
                from pixie_tpu import metrics as _metrics

                conn.send(wire.encode_json({
                    "msg": "metrics_text",
                    "req_id": payload.get("req_id"),
                    "text": _metrics.render(),
                }))
            elif msg == "flags":
                from pixie_tpu import flags as _flags

                conn.send(wire.encode_json({
                    "msg": "flags_dump",
                    "req_id": payload.get("req_id"),
                    "flags": _flags.dump(),
                }))
            elif msg == "cron_upsert":
                self._reply_ack(conn, payload, lambda: self.cron.upsert(
                    payload["name"], payload["script"],
                    payload.get("interval_s", 60.0),
                    func=payload.get("func"),
                    func_args=payload.get("func_args"),
                ))
            elif msg == "cron_delete":
                self._reply_ack(
                    conn, payload, lambda: self.cron.delete(payload["name"])
                )
            elif msg == "cron_list":
                conn.send(wire.encode_json({
                    "msg": "cron_scripts", "req_id": payload.get("req_id"),
                    "scripts": [
                        {"name": c.name, "interval_s": c.interval_s,
                         "enabled": c.enabled, "run_count": c.run_count,
                         "error_count": c.error_count,
                         "last_error": c.last_error}
                        for c in self.cron.list()
                    ],
                }))
            elif msg == "set_quota":
                self._handle_set_quota(conn, payload)
            elif msg == "get_quotas":
                conn.send(wire.encode_json({
                    "msg": "quotas", "req_id": payload.get("req_id"),
                    "quotas": self.serving.quotas(),
                    "rate_model": self.ratemodel.snapshot(),
                }))
            elif msg in ("retire_info", "storage_report", "rehome_info"):
                # reply to a broker→agent control RPC (retire drain audit /
                # heat_map storage fan-out / re-homing prepare+audit)
                with self._qlock:
                    slot = self._control_replies.get(payload.get("req_id"))
                if slot is not None:
                    slot[1] = payload
                    slot[0].set()
            elif msg == "heat_map":
                # cluster storage observatory read ("df for the data
                # plane") — off the read loop: it blocks on per-agent RPCs
                threading.Thread(
                    target=self._answer_heat_map, args=(conn, payload),
                    daemon=True, name="pixie-broker-heatmap",
                ).start()
            elif msg == "rehome_agent":
                # operator/controller shard move — off the read loop: the
                # prepare RPC + coverage audit block for seconds
                threading.Thread(
                    target=self._answer_rehome, args=(conn, payload),
                    daemon=True, name="pixie-broker-rehome",
                ).start()
            elif msg == "deregister_agent":
                # operator decommission: drop the durable record so the
                # shard map stops treating the retired node as a failover
                # primary (and catch-up degradation clears).  Refused when
                # the shard map says this agent is the LAST live holder of
                # any shard (its own, or a dead primary's it alone serves
                # failover for) — deregistering it would lose that shard
                # from every future plan; force=true overrides.
                name = str(payload.get("agent"))
                sole = ([] if payload.get("force")
                        else self._sole_holder_of(name))
                if sole:
                    conn.send(wire.encode_json({
                        "msg": "error", "req_id": payload.get("req_id"),
                        "error": f"agent {name} is the last live holder of "
                                 f"shard(s) {sole}; deregistering it would "
                                 "lose them (force=true overrides)"}))
                else:
                    ok = self.registry.deregister(name)
                    conn.send(wire.encode_json({
                        "msg": "ok" if ok else "error",
                        "req_id": payload.get("req_id"),
                        **({} if ok else {"error": "unknown agent"})}))
                    if ok:
                        self._push_shard_map()
            elif msg == "get_peers":
                # pre-registration topology fetch: a rehydrating agent asks
                # who backs its shard (and where their replication ports
                # live) BEFORE it registers, so peer fetch completes before
                # the broker ever dispatches to it
                conn.send(wire.encode_json({
                    "msg": "peers", "req_id": payload.get("req_id"),
                    "shard_map": self.registry.shard_map(),
                    "peers": self.registry.peer_addrs(),
                }))
            elif msg == "list_schemas":
                conn.send(wire.encode_json({
                    "msg": "schemas",
                    "req_id": payload.get("req_id"),
                    "schemas": {
                        t: r.to_dict()
                        for t, r in self.registry.combined_schemas().items()
                    },
                }))
            else:
                conn.send(wire.encode_json({"msg": "error", "error": f"unknown msg {msg!r}"}))
        else:
            # data chunk from an agent (host_batch | partial_agg)
            if self._stale_incarnation(conn):
                return
            meta = payload.wire_meta
            self._handle_chunk(conn, meta, payload)

    @staticmethod
    def _reply_ack(conn: Connection, payload: dict, fn) -> None:
        """Run a control action; reply {msg: ok} or the error envelope."""
        try:
            fn()
            conn.send(wire.encode_json({"msg": "ok", "req_id": payload.get("req_id")}))
        except Exception as e:
            conn.send(wire.encode_json({
                "msg": "error", "req_id": payload.get("req_id"), "error": str(e),
            }))

    def _on_close(self, conn: Connection):
        if conn.state.get("superseded"):
            # a newer incarnation already owns the name: marking it dead
            # here would kill the NEW agent's liveness (dead stays dead
            # until register), and its eviction already ran at supersede
            return
        name = conn.state.get("agent")
        if name is not None:
            self.registry.mark_dead(name)
            if self._agent_conns.get(name) is conn:
                self._agent_conns.pop(name, None)
            # producer watchdog analog: evict the agent from every pending
            # query — retryable queries re-plan + re-dispatch, tracepoint
            # deploy rounds (and PL_QUERY_RETRIES=0) fail fast
            self._evict_agent(name, "disconnected")

    def _stale_incarnation(self, conn: Connection) -> bool:
        """Incarnation fence: frames arriving on a connection registered
        under an OLDER incarnation of the agent name are dropped (counted).
        A restarted agent re-registering under the same name supersedes the
        old socket; whatever that socket still delivers — chunks, acks,
        heartbeats — must be rejected, not folded."""
        name = conn.state.get("agent")
        inc = conn.state.get("incarnation")
        if name is None or inc is None:
            return False
        if inc == self.registry.incarnation(name):
            return False
        from pixie_tpu import metrics as _metrics

        _metrics.counter_inc(
            "px_broker_stale_incarnation_frames_total",
            help_="frames dropped from superseded agent sockets (an agent "
                  "re-registered under the same name; the old incarnation "
                  "is fenced)")
        return True

    def _evict_agent(self, name: str, reason: str) -> None:
        from pixie_tpu import metrics as _metrics

        _metrics.counter_inc(
            "px_agent_evictions_total",
            help_="agent connections lost (disconnect, heartbeat expiry, "
                  "or supersede by a re-registration)")
        # per-agent series ride a CAPPED label: agent names arrive on the
        # wire, so an id flood must not mint immortal counter series past
        # the cap (same policy as the PR 8 tenant-label cap)
        _metrics.counter_inc(
            "px_agent_evictions_by_agent_total",
            labels={"agent": _metrics.capped_label("agent", name)},
            help_="agent evictions by (capped) agent name")
        with self._qlock:
            ctxs = list(self._queries.values())
        for ctx in ctxs:
            for src in ctx.on_agent_lost(name, reason):
                self._finish_dispatch_span(ctx, src,
                                           error=f"agent {name} {reason}")
        self._push_shard_map()

    # ------------------------------------------------------------ durability
    def _push_shard_map(self) -> None:
        """Broadcast the registry's primary→replicas map + peer addresses
        to every live agent connection, and flag catch-up on the serving
        front (dead primaries being served by failover replicas degrade
        dispatch until they rehydrate).  No-op with replication off."""
        if not _replication.enabled():
            return
        m = self.registry.shard_map()
        peers = self.registry.peer_addrs()
        self.serving.set_catchup(len(self._failover_map(m)))
        frame = wire.encode_json({"msg": "shard_map", "map": m,
                                  "peers": peers})
        for _name, conn in sorted(self._agent_conns.items()):
            if not conn.closed:
                conn.send(frame)

    def _failover_map(self, shard_map: Optional[dict] = None) -> dict:
        """{dead primary → live replica} for every known-dead agent whose
        shard map lists a replica with a live connection.  Empty unless
        replication is enabled."""
        if not _replication.enabled():
            return {}
        if shard_map is None:
            shard_map = self.registry.shard_map()
        live = {r.name for r in self.registry.live_agents()}
        out: dict[str, str] = {}
        for primary, reps in sorted(shard_map.items()):
            if primary in live:
                continue
            rec = self.registry.record(primary)
            if rec is None or not rec.schemas:
                continue
            for r in reps or []:
                conn = self._agent_conns.get(r)
                if r in live and conn is not None and not conn.closed:
                    out[primary] = r
                    break
        return out

    def _spec_with_failover(self, spec, failover: dict):
        """Planner topology with the failover map's dead primaries added
        back as virtual data agents (their durable schemas come from the
        registry records).  The merger stays last."""
        from pixie_tpu.parallel.topology import AgentInfo, ClusterSpec

        have = {a.name for a in spec.agents}
        extra = []
        for primary in sorted(failover):
            if primary in have:
                continue
            rec = self.registry.record(primary)
            if rec is None:
                continue
            extra.append(AgentInfo(
                name=primary, has_data_store=True, processes_data=True,
                accepts_remote_sources=False, schemas=rec.schemas,
                n_devices=rec.n_devices))
        if not extra:
            return spec
        return ClusterSpec(spec.agents[:-1] + extra + spec.agents[-1:])

    # ---------------------------------------------------------- quota control
    def _handle_set_quota(self, conn: Connection, payload: dict) -> None:
        """Live tenant quota write: validate (malformed specs are REJECTED
        with a clean error — this is an interactive API, not an env var),
        apply to the serving front in place, persist in the KV so the
        record survives broker restart."""
        from pixie_tpu.serving.admission import normalize_quota
        from pixie_tpu.status import InvalidArgument

        rid = payload.get("req_id")
        tenant = payload.get("tenant")
        try:
            rec = normalize_quota(tenant, payload.get("qps"),
                                  payload.get("concurrency"),
                                  payload.get("weight"))
        except InvalidArgument as e:
            conn.send(wire.encode_json(
                {"msg": "error", "req_id": rid, "error": str(e)}))
            return
        try:
            eff = self.serving.set_quota(tenant, rec)
        except PxError as e:  # e.g. the live-record cap: a clean reject
            conn.send(wire.encode_json(
                {"msg": "error", "req_id": rid, "error": str(e)}))
            return
        if all(v is None for v in rec.values()):
            self.kv.delete(f"quota/{tenant}")
        else:
            self.kv.set_json(f"quota/{tenant}", rec)
        conn.send(wire.encode_json({
            "msg": "quota_ok", "req_id": rid, "tenant": tenant,
            "effective": eff}))

    def _load_quotas(self) -> None:
        """Recall persisted quota records into the serving front (broker
        restart).  A corrupt record is skipped (counted), never fatal."""
        from pixie_tpu import metrics as _metrics
        from pixie_tpu.serving.admission import normalize_quota

        for key, raw in self.kv.scan("quota/"):
            tenant = key[len("quota/"):]
            try:
                d = _json.loads(raw.decode())
                rec = normalize_quota(tenant, d.get("qps"),
                                      d.get("concurrency"), d.get("weight"))
            except Exception:
                _metrics.counter_inc(
                    "px_broker_quota_recall_errors_total",
                    help_="persisted quota records skipped at broker "
                          "startup (corrupt or no longer valid)")
                continue
            self.serving.set_quota(tenant, rec)

    # ------------------------------------------------------------ agent retire
    def _sole_holder_of(self, name: str) -> list[str]:
        """Primaries whose ONLY live holder is `name` per the PR 12 shard
        map: the shard coverage retiring `name` would lose.  Empty with
        replication off (no map) — the retire path then relies on the
        drain audit (rows held) instead."""
        m = self.registry.shard_map()
        live = {r.name for r in self.registry.live_agents()}
        out = []
        for p, reps in m.items():
            holders = (({p} if p in live else set())
                       | {r for r in (reps or []) if r in live})
            if holders == {name}:
                out.append(p)
        return sorted(out)

    def _agent_rpc(self, name: str, meta: dict, timeout: float = 5.0) -> dict:
        """One broker→agent control round-trip on the agent's connection."""
        conn = self._agent_conns.get(name)
        if conn is None or conn.closed:
            raise TimeoutError(f"agent {name} not connected")
        with self._qlock:
            self._req_counter += 1
            rid = f"ctl{self._req_counter}"
            slot = [threading.Event(), None]
            self._control_replies[rid] = slot
        try:
            meta = dict(meta, req_id=rid)
            if not conn.send(wire.encode_json(meta)):
                raise TimeoutError(f"agent {name} not connected")
            if not slot[0].wait(timeout):
                raise TimeoutError(
                    f"agent {name} did not answer {meta.get('msg')}")
            return slot[1]
        finally:
            with self._qlock:
                self._control_replies.pop(rid, None)

    def _answer_heat_map(self, conn: Connection, payload: dict) -> None:
        """Aggregate every live agent's storage_report into the cluster
        heat map: per-agent raw reports plus a per-table rollup (shard →
        summed decayed heat, cluster skew = max/mean shard heat).  Consumed
        by `pixie_tpu.cli storage`; also refreshes the px_journal_bytes
        gauge family from the reports (the broker may be the only scraped
        process in a multi-process deployment)."""
        from pixie_tpu import metrics as _metrics

        agents: dict = {}
        for rec in self.registry.live_agents():
            try:
                rep = self._agent_rpc(rec.name, {"msg": "storage_report"},
                                      timeout=5.0)
            except TimeoutError as e:
                agents[rec.name] = {"error": str(e)}
                continue
            agents[rec.name] = {
                "shard_heat": rep.get("shard_heat") or [],
                "storage_state": rep.get("storage_state") or [],
                **({"error": rep["error"]} if rep.get("error") else {}),
            }
        tables: dict = {}
        for rep in agents.values():
            for r in rep.get("shard_heat") or []:
                t = tables.setdefault(str(r.get("table_name")), {
                    "shards": {}, "rows_scanned": 0, "bytes": 0})
                sh = str(r.get("shard"))
                t["shards"][sh] = (t["shards"].get(sh, 0.0)
                                   + float(r.get("heat") or 0.0))
                t["rows_scanned"] += int(r.get("rows_scanned") or 0)
                t["bytes"] += int(r.get("bytes") or 0)
        for t in tables.values():
            heats = list(t["shards"].values())
            mean = sum(heats) / max(len(heats), 1)
            t["skew"] = round(max(heats) / mean, 4) if mean > 0 else 1.0
        jbytes: dict = {}
        for name, rep in agents.items():
            for r in rep.get("storage_state") or []:
                jbytes[name] = (jbytes.get(name, 0)
                                + int(r.get("journal_bytes") or 0))
        for name, b in jbytes.items():
            _metrics.gauge_set(
                "px_journal_bytes", float(b),
                labels={"agent": _metrics.capped_label("heat_shard", name)},
                help_="journal bytes on disk per agent (PL_JOURNAL_MAX_MB "
                      "pruning pressure)")
        conn.send(wire.encode_json({
            "msg": "heat_map", "req_id": payload.get("req_id"),
            "agents": agents, "tables": tables}))

    # ---------------------------------------------------------- shard re-homing
    def _answer_rehome(self, conn: Connection, payload: dict) -> None:
        """Control-frame wrapper for rehome_agent (cli / tests)."""
        res = self.rehome_agent(str(payload.get("agent")),
                                target=(str(payload["target"])
                                        if payload.get("target") else None),
                                reason=str(payload.get("reason") or "manual"))
        conn.send(wire.encode_json({
            "msg": "rehome_result", "req_id": payload.get("req_id"), **res}))

    def _pick_rehome_target(self, donor: str) -> Optional[str]:
        """A live peer to re-home `donor`'s shard onto: prefer one that
        already replicates the donor (its copy is a backfill head start);
        otherwise the live agent backing the fewest shards (spread, not
        pile-up).  None when the donor is the only live agent."""
        live = sorted(r.name for r in self.registry.live_agents()
                      if r.name != donor)
        if not live:
            return None
        m = self.registry.shard_map()
        for r in m.get(donor) or []:
            if r in live:
                return r
        load = {a: 0 for a in live}
        for _p, reps in m.items():
            for r in reps or []:
                if r in load:
                    load[r] += 1
        return min(live, key=lambda a: (load[a], a))

    @staticmethod
    def _manifest_covers(ranges: list, first: int, last: int) -> bool:
        """True when the sorted [start, n] ranges contiguously cover
        [first, last) — the donor's sealed frontier.  An empty frontier
        (first == last) needs no batches."""
        if first >= last:
            return True
        if not ranges or int(ranges[0][0]) > first:
            return False
        end = int(ranges[0][0])
        for start, n in ranges:
            if int(start) > end:
                break  # hole
            end = max(end, int(start) + int(n))
        return end >= last

    def rehome_agent(self, donor: str, target: Optional[str] = None,
                     reason: str = "manual") -> dict:
        """Move the `donor` shard's sealed data onto `target` over the
        PR 12 replication channel — the heavy half of elastic rebalancing
        (hot shards migrate instead of refusing to retire).  Two-phase:

          prepare — durable `move/<donor>` KV record, then the target is
             staged as an extra shard-map replica (registry.add_replica):
             the donor's ReplicationManager backfills every sealed batch
             to it over the normal channel — no new transfer code.  A
             `rehome_prepare` RPC force-seals the donor's hot remainders
             (table.seal_hot) and drains the stream, so the frontier the
             donor reports is fully shipped.
          verify — a `rehome_audit` RPC asks the TARGET what it actually
             holds for the donor; the broker diffs the replica manifest
             against the donor's reported per-table frontiers.  Bounded
             retries (backfill is async); incarnation fences on BOTH ends
             abort the move if either process restarted mid-flight.
          commit — the move record is deleted; the staged replica STAYS
             in the map (durable under rehome/<donor>), so failover and
             the retire audit find the copy.  The registry epoch bump
             from staging already invalidated every plan cache.

        Crash-safety: ownership stays with the donor until commit — an
        interrupted move leaves only an EXTRA copy staged, and a
        restarted broker aborts the stale `move/` record (start()).
        Returns {ok, donor, target, tables, synced, reason}."""
        from pixie_tpu import metrics as _metrics

        def _abort(why: str, staged: bool = False) -> dict:
            if staged:
                self.registry.remove_replica(donor, target)
                self._push_shard_map()
            self.kv.delete(f"move/{donor}")
            _metrics.counter_inc(
                "px_rehome_aborts_total",
                help_="shard re-homing moves aborted before commit "
                      "(ownership stayed with the donor)")
            return {"ok": False, "donor": donor, "target": target,
                    "tables": {}, "synced": False, "reason": why}

        if not _replication.enabled():
            return {"ok": False, "donor": donor, "target": target,
                    "tables": {}, "synced": False,
                    "reason": "replication disabled (PL_REPLICATION<=1)"}
        rec = self.registry.record(donor)
        if rec is None or not rec.alive:
            return {"ok": False, "donor": donor, "target": target,
                    "tables": {}, "synced": False,
                    "reason": "donor not live"}
        if target is None:
            target = self._pick_rehome_target(donor)
        if target is None or target == donor:
            return {"ok": False, "donor": donor, "target": target,
                    "tables": {}, "synced": False,
                    "reason": "no live re-home target"}
        trec = self.registry.record(target)
        if trec is None or not trec.alive:
            return {"ok": False, "donor": donor, "target": target,
                    "tables": {}, "synced": False,
                    "reason": "target not live"}
        # incarnation fences: a donor or target that restarts mid-move
        # invalidates the coverage evidence gathered so far
        d_inc = self.registry.incarnation(donor)
        t_inc = self.registry.incarnation(target)
        self.kv.set_json(f"move/{donor}", {
            "target": target, "reason": reason, "phase": "prepare"})
        self.registry.add_replica(donor, target)
        self._push_shard_map()
        try:
            prep = self._agent_rpc(donor, {"msg": "rehome_prepare"},
                                   timeout=15.0)
        except TimeoutError as e:
            return _abort(f"prepare failed: {e}", staged=True)
        if prep.get("error"):
            return _abort(f"prepare failed: {prep['error']}", staged=True)
        frontiers = {n: (int(f.get("first") or 0), int(f.get("last") or 0))
                     for n, f in (prep.get("tables") or {}).items()}
        covered = False
        for _try in range(20):
            if (self.registry.incarnation(donor) != d_inc
                    or self.registry.incarnation(target) != t_inc):
                return _abort("incarnation changed mid-move", staged=True)
            try:
                audit = self._agent_rpc(
                    target, {"msg": "rehome_audit", "donor": donor},
                    timeout=5.0)
            except TimeoutError as e:
                return _abort(f"audit failed: {e}", staged=True)
            man = audit.get("tables") or {}
            covered = all(
                self._manifest_covers(
                    (man.get(n) or {}).get("ranges") or [], first, last)
                for n, (first, last) in frontiers.items())
            if covered:
                break
            time.sleep(0.25)
        if not covered:
            return _abort("target manifest never covered the donor "
                          "frontier", staged=True)
        # commit: the one-key delete IS the flip — a crash before it
        # replays as an abort (extra copy unstaged, donor keeps owning)
        self.kv.delete(f"move/{donor}")
        _metrics.counter_inc(
            "px_rehome_moves_total",
            help_="shard re-homing moves committed (donor sealed data "
                  "verified resident on the target)")
        _metrics.counter_inc(
            "px_rehome_moved_tables_total", float(len(frontiers)),
            help_="tables whose sealed frontier was re-homed")
        self.record_scale_event(
            "rehome", donor, f"{reason} -> {target}", 0.0,
            len(self.registry.live_agents()))
        return {"ok": True, "donor": donor, "target": target,
                "tables": {n: {"first": f, "last": l}
                           for n, (f, l) in frontiers.items()},
                "synced": bool(prep.get("repl_synced")), "reason": ""}

    def _abort_stale_moves(self) -> None:
        """Broker restart mid-move: every surviving `move/` record is a
        prepare that never committed — unstage its extra replica and
        delete it.  Ownership stays with the donor (the two-phase flip's
        crash guarantee); the staged copy was only ever additive."""
        from pixie_tpu import metrics as _metrics

        for key, raw in list(self.kv.scan("move/")):
            donor = key.split("/", 1)[1]
            try:
                d = _json.loads(raw.decode())
            except Exception:
                d = {}
            if d.get("target"):
                self.registry.remove_replica(donor, str(d["target"]))
            self.kv.delete(key)
            _metrics.counter_inc(
                "px_rehome_stale_aborts_total",
                help_="interrupted re-homing moves aborted at broker "
                      "startup (ownership left with the donor)")

    def retire_agent(self, name: str, force: bool = False) -> dict:
        """Scale-down decommission with loss safety (the autoscaler's
        retire path; serving/elastic.py).  Protocol:

          1. Shard-map check FIRST: an agent that is the last live holder
             of any shard (its own primary data, or a dead primary it
             alone serves failover for) is refused — deregistering it
             would lose rows from every future answer.
          2. Drain audit: the agent reports the rows it holds outside the
             self-telemetry tables (`retire_query` RPC) and whether its
             replication stream is synced.
          3. rows == 0 → deregister + shard-map push (a clean retire: the
             agent held nothing irreplaceable).
             rows > 0 with replication synced onto a live replica → the
             PR 12 hand-off: the agent stops but its durable record STAYS,
             so its shard keeps answering through broker failover from the
             replicated sealed batches.
             rows > 0 otherwise → REFUSED (retiring it would lose rows).

        Returns {ok, mode: deregister|handoff|None, rows, reason,
        peer_sync} — peer_sync is the agent's per-peer replication
        watermark detail ({peer: {sent, acked, lag}}), so the audit's
        "synced" verdict ships with the numbers behind it."""
        from pixie_tpu import metrics as _metrics

        rec = self.registry.record(name)
        if rec is None:
            return {"ok": False, "mode": None, "rows": None,
                    "reason": "unknown agent", "peer_sync": {}}
        sole = self._sole_holder_of(name)
        if sole and not force:
            # rehome-first: instead of refusing outright, try moving the
            # sole-held shard onto a live peer over the replication
            # channel, then re-check.  A failed move (no peers, audit
            # never covered, replication off) falls back to the old
            # refusal — force keeps the old semantics entirely.
            moved = self.rehome_agent(name, reason="retire")
            if moved.get("ok"):
                sole = self._sole_holder_of(name)
            if sole:
                _metrics.counter_inc(
                    "px_autoscale_retire_refused_total",
                    help_="scale-down retires refused by the loss-safety "
                          "audit (last live shard holder, unauditable "
                          "rows, or unsynced replication)")
                return {"ok": False, "mode": None, "rows": None,
                        "reason": f"last live holder of shard(s) {sole}"
                                  + (f"; rehome failed: {moved['reason']}"
                                     if moved.get("reason") else ""),
                        "peer_sync": {}}
        rows = None
        repl_synced = False
        peer_sync: dict = {}
        try:
            reply = self._agent_rpc(name, {"msg": "retire_query"},
                                    timeout=5.0)
            rows = int(reply.get("rows", -1))
            repl_synced = bool(reply.get("repl_synced"))
            peer_sync = dict(reply.get("peer_sync") or {})
        except TimeoutError:
            pass
        if rows is None or rows < 0:
            if not force:
                _metrics.counter_inc(
                    "px_autoscale_retire_refused_total",
                    help_="scale-down retires refused by the loss-safety "
                          "audit (last live shard holder, unauditable "
                          "rows, or unsynced replication)")
                return {"ok": False, "mode": None, "rows": rows,
                        "reason": "drain audit unanswered",
                        "peer_sync": peer_sync}
            rows = -1
        if rows > 0 and not force:
            reps = self.registry.shard_map().get(name) or []
            live = {r.name for r in self.registry.live_agents()}
            if not (_replication.enabled() and repl_synced
                    and any(r in live for r in reps)):
                # rehome-first here too: a failed drain audit (unsynced
                # stream, no live replica yet) is exactly what the move
                # protocol repairs — it force-seals, drains, and VERIFIES
                # the target's coverage before the hand-off proceeds
                moved = self.rehome_agent(name, reason="retire")
                if moved.get("ok"):
                    repl_synced = True
                    reps = self.registry.shard_map().get(name) or []
                    live = {r.name for r in self.registry.live_agents()}
            if not (_replication.enabled() and repl_synced
                    and any(r in live for r in reps)):
                _metrics.counter_inc(
                    "px_autoscale_retire_refused_total",
                    help_="scale-down retires refused by the loss-safety "
                          "audit (last live shard holder, unauditable "
                          "rows, or unsynced replication)")
                return {"ok": False, "mode": None, "rows": rows,
                        "reason": "holds rows with no synced live replica",
                        "peer_sync": peer_sync}
            # PR 12 hand-off: keep the durable record — the shard keeps
            # serving through failover from the replicated sealed batches
            # once the agent stops (the supervisor owns the stop)
            return {"ok": True, "mode": "handoff", "rows": rows,
                    "reason": "", "peer_sync": peer_sync}
        self.registry.deregister(name)
        self._push_shard_map()
        return {"ok": True, "mode": "deregister", "rows": rows,
                "reason": "", "peer_sync": peer_sync}

    def reap_dead_agent(self, name: str) -> bool:
        """Deregister a DEAD supervisor-owned agent (preemption cleanup) —
        refused when the shard map still needs it (it may hold the only
        replicated copy of a shard some peer will rehydrate from)."""
        rec = self.registry.record(name)
        if rec is None or rec.alive or self._sole_holder_of(name):
            return False
        self.registry.deregister(name)
        self._push_shard_map()
        return True

    def record_scale_event(self, action: str, agent: str, reason: str,
                           pressure: float, agents: int) -> None:
        """One autoscaler decision into self_telemetry.scale_events (the
        supervisor's journal, shipped with the normal telemetry path)."""
        import time as _time

        from pixie_tpu import observe as _observe

        self._telemetry.add(_observe.SCALE_EVENTS_TABLE, [{
            "time_": _time.time_ns(),
            "action": str(action),
            "agent": str(agent),
            "reason": str(reason or ""),
            "pressure": round(float(pressure), 4),
            "agents": int(agents),
        }])
        self._ship_spans()

    # ---------------------------------------------------------------- handlers
    def _handle_register(self, conn: Connection, meta: dict):
        name = meta["agent"]
        schemas = {t: Relation.from_dict(r) for t, r in meta["schemas"].items()}
        asid = self.registry.register(name, schemas, meta.get("n_devices"),
                                      repl_addr=meta.get("repl_addr"))
        conn.state["agent"] = name
        # the incarnation this socket speaks for — older sockets for the
        # same name are fenced from here on (_stale_incarnation)
        conn.state["incarnation"] = self.registry.incarnation(name)
        old = self._agent_conns.get(name)
        self._agent_conns[name] = conn
        if old is not None and old is not conn:
            # fence the old socket BEFORE acking the new registration:
            # once the agent sees "registered" the rejoin is observable,
            # so the supersede marker must already be set.  Keep
            # "agent"+"incarnation" on the old conn so frames its reader
            # already queued are FENCED (stale incarnation) rather than
            # processed; the superseded marker keeps its close from
            # killing the new registration
            old.state["superseded"] = True
            old.close()
        conn.send(wire.encode_json({"msg": "registered", "asid": asid}))
        if old is not None and old is not conn:
            # in-flight dispatches on the old socket are orphaned (the new
            # process never saw them): evict so they re-dispatch to the
            # fresh incarnation (after the ack, so any re-dispatch frame
            # follows "registered" on the new socket)
            self._evict_agent(name, "superseded")
        # topology changed: replicas retarget, rehydrated shards leave
        # catch-up, takeover materializations for this name invalidate
        self._push_shard_map()

    def _ctx(self, meta: dict) -> Optional[_QueryCtx]:
        """Resolve the query ctx for a producer frame, enforcing the
        per-dispatch token.  Mismatched/missing tokens are dropped (and
        counted): a stale producer must not corrupt a newer query — or a
        newer dispatch round — that reused context state."""
        import hmac

        with self._qlock:
            ctx = self._queries.get(meta.get("req_id", ""))
        if ctx is None:
            return None
        expect = ctx.token_for(_QueryCtx.src_of(meta))
        # utf-8 bytes operands: compare_digest raises TypeError on non-ASCII
        # str, which would skip the counted-drop path (same pitfall the auth
        # handler avoids)
        if expect is None or not hmac.compare_digest(
                str(meta.get("qtoken", "")).encode(), expect.encode()):
            from pixie_tpu import metrics as _metrics

            _metrics.counter_inc(
                "px_broker_stale_token_frames_total",
                help_="producer frames rejected for a bad per-dispatch token")
            # surfaced loudly: an agent that never echoes the token (e.g. a
            # version mismatch) would otherwise present as an opaque query
            # timeout with only a metric to explain it
            _metrics.warn(
                "dropping producer frame with bad per-dispatch token",
                req_id=meta.get("req_id"), agent=meta.get("agent"),
                has_token=bool(meta.get("qtoken")))
            return None
        return ctx

    def _handle_chunk(self, conn: Connection, meta: dict, payload):
        ctx = self._ctx(meta)
        if ctx is not None:
            ctx.fold_chunk(meta, payload)
        # Open the producer's in-flight window (its backpressure gate): the
        # ack means this chunk's fold work is DONE, so a slow merge throttles
        # the agents instead of queueing unbounded frames.  Acked even when
        # the query is already dead (ctx None / stale token): acks are pure
        # flow control, and a producer still draining a doomed stream must
        # not stall on a window nobody will ever open.  Replied on the SAME
        # connection the chunk arrived on — routing by agent name would ack
        # a restarted incarnation for its predecessor's frames.
        if not conn.closed:
            conn.send(wire.encode_json({
                "msg": "chunk_ack", "req_id": meta.get("req_id"),
                "channel": meta["channel"], "seq": meta.get("seq"),
                "attempt": meta.get("attempt"),
                # the SOURCE the chunk answered for (≠ the executing agent
                # on a failover takeover): the producer's ack-window key
                # includes it, so two streams on one socket stay distinct
                "agent": meta.get("agent"),
            }))

    def _finish_dispatch_span(self, ctx: _QueryCtx, src,
                              error: Optional[str] = None) -> None:
        sp = ctx.dispatch_spans.pop(src, None)
        if sp is not None:
            if error:
                sp.attributes["error"] = error[:200]
            self.tracer.finish(sp)

    #: distinct agents the service-time model tracks; like metric label
    #: series, the dict is keyed by wire-supplied names and would otherwise
    #: grow without bound — past the cap the least-recently-updated entry
    #: is evicted (a re-appearing agent just re-warms)
    MAX_SVC_AGENTS = 256

    def _record_service_time(self, agent: str, secs: float) -> None:
        """Fold one dispatch→exec_done sample into the agent's EWMA model
        (hedge deadlines derive from it)."""
        import time as _time

        if _autotune.enabled():
            # the same completion stream feeds the fleet-wide hedge-floor
            # model (engine/autotune.py): measured service p99 replaces
            # the fixed PL_HEDGE_MIN_MS once warm
            _autotune.MODEL.observe_service(secs)
        a = 0.2
        with self._svc_lock:
            s = self._svc.get(agent)
            if s is None:
                if len(self._svc) >= self.MAX_SVC_AGENTS:
                    lru = min(self._svc, key=lambda k: self._svc[k]["at"])
                    self._svc.pop(lru, None)
                self._svc[agent] = {"ewma": secs, "dev": secs / 2, "n": 1,
                                    "at": _time.monotonic()}
                return
            s["ewma"] += a * (secs - s["ewma"])
            s["dev"] += a * (abs(secs - s["ewma"]) - s["dev"])
            s["n"] += 1
            s["at"] = _time.monotonic()

    def _hedge_deadline_s(self, agent: str) -> Optional[float]:
        """Seconds a dispatch to `agent` may run before a hedged duplicate
        fires; None while the service-time model is cold (or hedging off)."""
        if not _flags.get("PL_HEDGE_ENABLED"):
            return None
        with self._svc_lock:
            s = self._svc.get(agent)
            if s is None or s["n"] < HEDGE_MIN_SAMPLES:
                return None
            p99 = s["ewma"] + 4.0 * s["dev"]
        floor = float(_flags.get("PL_HEDGE_MIN_MS")) / 1e3
        if _autotune.enabled():
            # adaptive floor: the measured fleet service p99 (with
            # headroom) replaces the fixed half-second constant once the
            # model is warm.  It only ever LOWERS the operator's floor —
            # a fast fleet hedges stragglers in tens of ms; the tail guard
            # snaps back to the static floor if the model drifts.
            floor, _dec = _autotune.MODEL.hedge_floor_s(floor)
        return max(floor, float(_flags.get("PL_HEDGE_FACTOR")) * p99)

    def _handle_exec_done(self, meta: dict):
        ctx = self._ctx(meta)
        if ctx is None:
            return
        src = _QueryCtx.src_of(meta)
        res = ctx.on_exec_done(meta)
        self._finish_dispatch_span(ctx, src)
        # non-retryable rounds are tracepoint deploys: their round-trip
        # measures apply+re-register, not query execution — folding them
        # into the hedge model would skew the straggler deadlines
        if res is not None and ctx.retryable:
            self._record_service_time(*res)

    def _handle_exec_error(self, meta: dict):
        ctx = self._ctx(meta)
        if ctx is None:
            return
        src = _QueryCtx.src_of(meta)
        ctx.on_exec_error(meta)
        self._finish_dispatch_span(ctx, src, error=str(meta.get("error")))

    # ------------------------------------------------------------------- query
    def _run_query(self, client: Connection, meta: dict):
        req_id = meta.get("req_id", "")
        tenant = str(meta.get("tenant") or DEFAULT_TENANT)
        tctx = None
        try:
            with trace.root(self.tracer, "query", req_id=req_id,
                            tenant=tenant):
                tctx = trace.wire_context()
                results, stats = self.execute_script(
                    meta["script"],
                    func=meta.get("func"),
                    func_args=meta.get("func_args"),
                    now=meta.get("now"),
                    default_limit=meta.get("default_limit"),
                    analyze=bool(meta.get("analyze", False)),
                    funcs=[tuple(f) for f in meta.get("funcs") or []] or None,
                    tenant=tenant,
                    explain=bool(meta.get("explain", False)),
                )
                with trace.span("render"):
                    for name, qr in results.items():
                        hb = HostBatch(
                            dtypes={n: qr.relation.dtype(n)
                                    for n in qr.relation.names()},
                            dicts=qr.dictionaries,
                            cols=qr.columns,
                        )
                        client.send(wire.encode_host_batch(
                            hb, {"msg": "result_chunk", "req_id": req_id,
                                 "table": name,
                                 # semantic types ride the wire with the
                                 # relation
                                 "relation": qr.relation.to_dict()}
                        ))
                    client.send(wire.encode_json(
                        {"msg": "done", "req_id": req_id,
                         "stats": _jsonable(stats)}
                    ))
        except ShedError as e:
            # admission rejection: NOT a failure of the query itself — the
            # envelope carries the retry-after hint so clients back off
            client.send(wire.encode_error(req_id, e,
                                          retry_after_s=e.retry_after_s))
        except Exception as e:  # compile/plan/exec errors all surface to client
            if not isinstance(e, PxError):
                traceback.print_exc()
            # infrastructure failures on idempotent queries carry the
            # retryable marker (+ a retry-after hint) so clients auto-retry
            # instead of surfacing a one-off agent death to the user
            client.send(wire.encode_error(
                req_id, e,
                retry_after_s=getattr(e, "retry_after_s", None),
                retryable=getattr(e, "retryable", None)))
        finally:
            self._ship_spans(tctx)

    def _ship_spans(self, tctx: Optional[dict] = None) -> None:
        """Persist this broker's finished spans AND flight-recorder rows
        (query profiles, op stats, sampled metrics, SLO alerts) into the
        data plane: everything goes to one live agent's self_telemetry
        tables through the normal write path, so PxL scripts and standing
        matviews see it without the broker holding a scanned store.
        `tctx` is the wire context of the query whose end ships them: the
        agent records what each write cost as a span of that query.

        Runs in query finally-blocks: telemetry failure (agent churn racing
        the conn map, dead sockets) must never replace a query's outcome, so
        everything is counted instead of raised."""
        from pixie_tpu import metrics as _metrics

        try:
            if self.tracer.buffered == 0 and len(self._telemetry) == 0:
                return
            # snapshot: the expiry thread pops entries concurrently
            conns = dict(self._agent_conns)

            def send_to_agent(frame) -> bool:
                for name in sorted(conns):
                    c = conns[name]
                    if not c.closed and c.send(frame):
                        return True
                return False

            def send(rows):
                if not send_to_agent(wire.encode_json(
                        {"msg": "spans", "spans": rows, "trace": tctx})):
                    _metrics.counter_inc(
                        "px_broker_trace_spans_unshipped_total",
                        float(len(rows)),
                        help_="broker spans dropped: no agent accepted them")

            self.tracer.flush(send=send)
            for table, rows in self._telemetry.drain().items():
                if not send_to_agent(wire.encode_json(
                        {"msg": "telemetry_rows", "table": table,
                         "rows": rows, "trace": tctx})):
                    _metrics.counter_inc(
                        "px_broker_telemetry_rows_unshipped_total",
                        float(len(rows)),
                        help_="flight-recorder rows dropped: no agent "
                              "accepted them")
        except Exception:
            _metrics.counter_inc(
                "px_broker_trace_ship_errors_total",
                help_="unexpected failures shipping broker spans")

    def _sample_self_metrics(self) -> None:
        """PL_SELF_METRICS_S cron body: metrics registry → telemetry rows,
        SLO burn-rate evaluation → alert rows, one ship."""
        from pixie_tpu import observe as _observe
        from pixie_tpu.serving import slo as _slo

        self._telemetry.add(_observe.METRICS_TABLE,
                            _observe.sample_metrics_rows("broker"))
        if _slo.configured():
            mon = _slo.monitor()
            mon.evaluate()
            self._telemetry.add(_observe.ALERTS_TABLE, mon.drain_alerts())
        if _autotune.enabled():
            # fallback trips and fitted-threshold changes → the autotune
            # telemetry table; checkpoint the model so a crash between
            # crons loses at most one period of learning
            rows = _autotune.MODEL.drain_rows()
            if rows:
                self._telemetry.add(_observe.AUTOTUNE_TABLE, rows)
            _autotune.MODEL.save_kv(self.kv)
        self._ship_spans()

    def _deploy_mutations(self, mutations: list) -> None:
        from pixie_tpu.status import Unavailable

        specs = [
            m for m in mutations
            if m.get("kind") in ("tracepoint", "delete_tracepoint")
        ]
        targets = {
            name: conn for name, conn in self._agent_conns.items()
            if not conn.closed
        }
        if not specs or not targets:
            return
        # A fresh req_id + ctx per spec round: a straggler ack from round N
        # that lands after its timeout cannot corrupt round N+1's accounting.
        for spec in specs:
            with self._qlock:
                self._req_counter += 1
                rid = f"tp{self._req_counter}"
                # retryable=False: mutations are never transparently
                # re-dispatched — agent loss mid-deploy fails the round
                ctx = _QueryCtx(set(), retryable=False)
                ctx.needed_agents = set(targets)
                for name in targets:
                    # deploy acks ride the base token at attempt 0
                    ctx.register_dispatch(name, token=ctx.token)
                self._queries[rid] = ctx
            try:
                for conn in targets.values():
                    conn.send(wire.encode_json({
                        "msg": "deploy_tracepoint", "req_id": rid, "spec": spec,
                        "qtoken": ctx.token,
                    }))
                if not ctx.done.wait(timeout=self.query_timeout_s):
                    raise Unavailable(
                        f"tracepoint deploy timed out on "
                        f"{ctx.outstanding_agents()}"
                    )
                if ctx.error:
                    raise Unavailable(ctx.error)
            finally:
                with self._qlock:
                    self._queries.pop(rid, None)

    # ------------------------------------------------- fault-tolerant dispatch
    def _await_rejoin_grace(self) -> None:
        """Hold dispatch while a just-dead agent may still re-register: a
        query planned in the kill→restart window would otherwise silently
        answer from the surviving shards only.  Bounded by the grace window
        measured from each death (never the full query timeout); a no-op
        with retries disabled — PL_QUERY_RETRIES=0 keeps the legacy
        plan-with-whatever-is-live behavior bit-identically."""
        import time as _time

        if int(_flags.get("PL_QUERY_RETRIES")) <= 0:
            return
        grace = float(_flags.get("PL_REJOIN_GRACE_S"))
        if grace <= 0:
            return
        deadline = _time.monotonic() + min(grace, self.query_timeout_s)
        waited_for = None
        t0 = _time.time_ns()
        while _time.monotonic() < deadline:
            recent = self.registry.recently_dead(grace)
            if not recent:
                break
            waited_for = recent
            _time.sleep(0.05)
        if waited_for is not None:
            trace.event_span("rejoin_wait", t0, _time.time_ns() - t0,
                             agents=",".join(waited_for))

    def _send_execute(self, ctx: _QueryCtx, req_id: str, agent: str,
                      plan_json: str, base_meta: dict,
                      hedged: bool = False) -> str:
        """Send one execute dispatch (fragment `plan_json`) to `agent` under
        a fresh per-dispatch token.  Returns the src id; raises Unavailable
        when the agent has no live connection."""
        from pixie_tpu.status import Unavailable

        conn = self._agent_conns.get(agent)
        serve_for = None
        if conn is None or conn.closed:
            # failover: a dead primary's fragment dispatches to its live
            # replica, which serves it from the replicated sealed batches
            # (takeover store) and answers AS the primary
            replica = ctx.failover.get(agent)
            rconn = (self._agent_conns.get(replica)
                     if replica is not None else None)
            if rconn is None or rconn.closed:
                raise Unavailable(f"agent {agent} not connected")
            conn, serve_for = rconn, agent
            ctx.failover_used[agent] = replica
            from pixie_tpu import metrics as _metrics

            _metrics.counter_inc(
                "px_broker_failover_dispatches_total",
                help_="fragments dispatched to failover replicas for dead "
                      "primaries")
        deadline = None
        if not hedged:
            h = self._hedge_deadline_s(agent)
            if h is not None:
                import time as _time

                deadline = _time.monotonic() + h
        src, token, attempt = ctx.register_dispatch(
            agent, frag=plan_json, deadline=deadline, hedged=hedged,
            via=(ctx.failover.get(agent) if serve_for else None))
        # one dispatch span per src: opened at send, closed by the
        # exec_done/exec_error handler (or eviction cleanup); its id rides
        # the wire so the agent's exec spans parent under it cross-process
        dsp = trace.start_child("dispatch", agent=agent, attempt=attempt,
                                hedged=hedged)
        tctx = None
        if dsp is not None:
            ctx.dispatch_spans[src] = dsp
            tctx = {"trace_id": dsp.trace_id, "span_id": dsp.span_id}
        meta = dict(base_meta)
        meta.update({"req_id": req_id, "qtoken": token, "attempt": attempt,
                     "trace": tctx})
        if serve_for is not None:
            meta["serve_for"] = serve_for
        # splice the cached plan JSON (encoded once per plan/split, not per
        # query) instead of re-serializing the plan dict
        if not conn.send(wire.encode_json_raw(meta, {"plan": plan_json})):
            ctx.drop_dispatch(src)
            self._finish_dispatch_span(ctx, src, error="send failed")
            raise Unavailable(f"agent {agent} not connected")
        return src

    def _await_agents(self, ctx: _QueryCtx, req_id: str, entry, q, dp,
                      split_extras, base_meta: dict, reg, fault: dict,
                      retries: int, extra_verify=None):
        """Wait for every needed agent's answer, surviving evictions and
        stragglers: evicted fragments re-plan onto the live agent set and
        re-dispatch with jittered exponential backoff (bounded by
        PL_QUERY_RETRIES); dispatches outliving their service-time deadline
        get a hedged duplicate.  Returns the final (dp, split_extras) —
        re-dispatch may have re-planned them."""
        import random as _random
        import time as _time

        from pixie_tpu import metrics as _metrics
        from pixie_tpu.status import CompilerError, Unavailable

        backoff_ms = float(_flags.get("PL_RETRY_BACKOFF_MS"))
        rng = _random.Random()
        deadline = _time.monotonic() + self.query_timeout_s
        rounds = 0
        while True:
            if ctx.error:
                raise Unavailable(ctx.error)
            if ctx.done.is_set():
                return dp, split_extras
            evicted = ctx.take_evictions()
            fault["evictions"] += len(evicted)
            if evicted or ctx.uncovered_agents():
                names = (sorted({a for a, _ in evicted})
                         or ctx.uncovered_agents())
                if rounds >= retries:
                    err = Unavailable(
                        f"agent {names[0]} disconnected mid-query")
                    if not q.mutations:
                        # infrastructure loss, not a query bug: the client
                        # may retry once the agent re-registers.  The hint
                        # composes BOTH waits the retry faces: the backoff
                        # schedule covering the agent's rejoin window (the
                        # drain rate says nothing about when lost DATA
                        # comes back — a bare drain hint of 0.05s on an
                        # idle queue would burn every client retry inside
                        # the rejoin grace) and, when the rate model is
                        # warm, the measured time for the queued work
                        # ahead of the retry to drain.
                        err.retryable = True
                        hint = self.ratemodel.retry_after_s(
                            self.serving.total_queued,
                            int(_flags.get("PL_SERVING_MAX_INFLIGHT")))
                        err.retry_after_s = max(
                            min(backoff_ms * (2 ** rounds),
                                MAX_BACKOFF_MS) / 1e3,
                            hint or 0.0)
                    raise err
                rounds += 1
                fault["rounds"] = rounds
                _metrics.counter_inc(
                    "px_query_retries_total",
                    help_="query re-dispatch rounds after agent eviction")
                # jittered exponential backoff: the window a killed-and-
                # restarted agent gets to re-register before this round
                # re-plans around it
                delay = (backoff_ms * (2 ** (rounds - 1)) / 1e3
                         * (0.5 + rng.random()))
                delay = min(delay, MAX_BACKOFF_MS / 1e3,
                            max(deadline - _time.monotonic(), 0.0))
                if delay > 0:
                    _time.sleep(delay)
                t0 = _time.time_ns()
                try:
                    dp, split_extras = self._redispatch(
                        ctx, req_id, entry, q, dp, split_extras, base_meta,
                        reg, fault, extra_verify=extra_verify)
                except (Unavailable, CompilerError):
                    # the cluster cannot serve the query right now (e.g.
                    # the killed agent has not re-registered): burn the
                    # round and look again after the next backoff — the
                    # uncovered set keeps this loop re-entering here
                    continue
                trace.event_span("redispatch", t0, _time.time_ns() - t0,
                                 agents=",".join(names), round=rounds)
                continue
            nxt = self._maybe_hedge(ctx, req_id, base_meta, fault)
            remaining = deadline - _time.monotonic()
            if remaining <= 0:
                raise Unavailable(
                    f"query timed out after {self.query_timeout_s}s waiting "
                    f"for agents {ctx.outstanding_agents()}")
            wait_s = min(0.25, remaining)
            if nxt is not None:
                wait_s = min(wait_s, max(nxt, 0.01))
            ctx.wake.wait(timeout=wait_s)
            ctx.wake.clear()

    def _redispatch(self, ctx: _QueryCtx, req_id: str, entry, q, dp,
                    split_extras, base_meta: dict, reg, fault: dict,
                    extra_verify=None):
        """One re-plan + re-dispatch round: re-split over the LIVE agent
        set and dispatch every uncovered fragment under fresh tokens.
        Accepted results (and in-flight dispatches) whose fragments are
        unchanged are KEPT — only the lost work repeats.  Falls back to a
        full restart when the channel topology changed (e.g. a repartition
        join lost its widest mesh)."""
        from pixie_tpu.engine.plancache import QueryPlanCache as _QPC
        from pixie_tpu.parallel.distributed import DistributedPlanner
        from pixie_tpu.status import Unavailable

        topo_epoch = self.registry.epoch
        spec = self.registry.cluster_spec()
        if not any(a.has_data_store for a in spec.agents):
            raise Unavailable("no live data agents registered")
        # a needed agent that died within the rejoin grace is REJOINING,
        # not gone: re-planning around it now would silently answer from
        # the surviving shards — burn the round and wait for it instead
        grace = float(_flags.get("PL_REJOIN_GRACE_S"))
        live = {a.name for a in spec.agents}
        rejoining = [a for a in sorted(ctx.needed_agents)
                     if a not in live
                     and a in set(self.registry.recently_dead(grace))]
        if rejoining:
            raise Unavailable(
                f"agent {rejoining[0]} re-registration pending")
        # past the grace: dead primaries with live replicas re-plan as
        # failover (virtual) agents instead of dropping out of the answer
        failover = self._failover_map()
        ctx.failover = failover
        if failover:
            spec = self._spec_with_failover(spec, failover)

        def _split():
            with trace.span("plan_split", redispatch=True):
                dp2 = DistributedPlanner(spec).plan(q.plan)
                # the re-planned split dispatches too: same pre-dispatch
                # verification contract as the first round — INCLUDING the
                # fused-batch demux invariants for batched carriers (the
                # re-split is cached into the batch slot for warm repeats)
                from pixie_tpu.check import planverify

                planverify.maybe_verify(dp2, spec.combined_schemas(), reg)
                if extra_verify is not None:
                    extra_verify(dp2)
                extras = {"plan_json": {
                    a: _json.dumps(p.to_dict())
                    for a, p in dp2.agent_plans.items()
                }}
                return dp2, extras

        (dp2, extras2), _hit = _QPC.get_split(
            entry, ("split", topo_epoch), _split)
        base_meta["route_scale"] = len(dp2.agent_plans)
        if not _channels_compatible(dp, dp2):
            # topology-shaped plan state (join partition counts, channel
            # sets, the merger plan) changed: nothing folded so far is
            # usable — restart the whole dispatch under fresh tokens
            for src in list(ctx.dispatch_spans):
                self._finish_dispatch_span(ctx, src, error="redispatched")
            ctx.reset_for_restart(dp2, reg)
        else:
            with ctx.lock:
                ctx.needed_agents = set(dp2.agent_plans)
                # an accepted fragment that CHANGED under the new plan (or
                # an in-flight dispatch of one) cannot be kept — its chunks
                # answer a different question now
                for agent in list(ctx.accepted):
                    if (agent in dp2.agent_plans
                            and ctx.frag_of(ctx.accepted[agent])
                            != extras2["plan_json"][agent]):
                        ctx.accepted.pop(agent)
                for src, info in list(ctx.pending.items()):
                    agent = info["agent"]
                    if (agent not in dp2.agent_plans
                            or info.get("frag")
                            != extras2["plan_json"][agent]):
                        ctx.pending.pop(src, None)
                ctx.hedged_agents.clear()  # fresh round, fresh hedge budget
                ctx._check_done_locked()
        for agent in ctx.uncovered_agents():
            try:
                self._send_execute(ctx, req_id, agent,
                                   extras2["plan_json"][agent], base_meta)
            except Unavailable:
                # its conn raced away again — the uncovered set re-enters
                # the retry loop for it
                continue
            if agent not in fault["redispatched"]:
                fault["redispatched"].append(agent)
        return dp2, extras2

    def _maybe_hedge(self, ctx: _QueryCtx, req_id: str, base_meta: dict,
                     fault: dict):
        """Dispatch hedged duplicates for in-flight dispatches past their
        straggler deadline (first answer wins; the loser's chunks are
        discarded idempotently at merge).  Returns seconds until the next
        armed deadline, or None when nothing is armed."""
        if not _flags.get("PL_HEDGE_ENABLED"):
            return None
        import time as _time

        from pixie_tpu import metrics as _metrics
        from pixie_tpu.status import Unavailable

        now = _time.monotonic()
        soonest = None
        with ctx.lock:
            pend = [(s, dict(i)) for s, i in ctx.pending.items()]
        for src, info in pend:
            dl = info.get("deadline")
            if dl is None or info.get("hedged"):
                continue
            agent = info["agent"]
            with ctx.lock:
                if agent in ctx.hedged_agents or agent in ctx.accepted:
                    continue
            if now < dl:
                gap = dl - now
                soonest = gap if soonest is None else min(soonest, gap)
                continue
            try:
                self._send_execute(ctx, req_id, agent, info["frag"],
                                   base_meta, hedged=True)
            except Unavailable:
                continue  # conn gone: the eviction path owns this agent now
            fault["hedged"] += 1
            _metrics.counter_inc(
                "px_hedged_dispatches_total",
                help_="duplicate dispatches sent for straggling agents "
                      "(first answer wins)")
            _metrics.counter_inc(
                "px_hedged_dispatches_by_agent_total",
                labels={"agent": _metrics.capped_label("agent", agent)},
                help_="hedged dispatches by (capped) agent name")
        return soonest

    def _admit(self, script, func, func_args, default_limit, tenant):
        """Pass one query through the serving front's admission gate.

        Cost estimate: a plan-cache peek decides warm (dispatch+merge only)
        vs cold (full compile/split) — the same signal the DRR scheduler
        charges, so a tenant flooding cold compiles drains proportionally
        slower.  The cold price is the MEASURED cold/warm service-time
        ratio once the rate model has samples (PL_RATE_MODEL), the static
        COST_COLD until then.  Raises ShedError (quota/queue-full/timeout/
        overload); returns (ticket, plan_class) — ticket None when serving
        is disabled (the class still feeds the model)."""
        from pixie_tpu.serving import ratemodel as _rm

        trace.set_attr(tenant=tenant)
        from pixie_tpu.engine import plancache as _plancache

        # mutations classify apart (deploy round-trips must skew neither
        # service class) — the same lexical marker the client's no-retry
        # rule uses; everything else prices off the plan-cache peek
        mutation = ("UpsertTracepoint" in script
                    or "DeleteTracepoint" in script)
        if not _plancache.enabled():
            # PL_QUERY_FASTPATH=0: no warm/cold signal exists and every
            # query pays the same full compile — price uniformly WARM so
            # DRR stays fair by count and the overload shed (which drops
            # cost >= COST_COLD work) cannot turn degradation into a full
            # outage
            warm = True
        else:
            key = self.plan_cache.key(script, func, func_args, default_limit,
                                      ("reg", self.registry.epoch),
                                      tenant=tenant)
            warm = self.plan_cache.contains(key)
        cls = _rm.plan_class(warm, mutation=mutation)
        self.ratemodel.observe_arrival(tenant, cls)
        if not self.serving.enabled():
            return None, cls  # pass-through: no accounting, no queueing
        cost = (COST_WARM if warm
                else self.ratemodel.cost_of(False) if _rm.enabled()
                else COST_COLD)
        with trace.span("admission_wait", tenant=tenant, cost=cost):
            ticket = self.serving.admit(tenant, cost)
        if ticket.queued:
            # the scheduler's dispatch decision as its own span: start =
            # enqueue, duration = queue wait (ends at dispatch)
            trace.event_span("sched_dispatch", ticket.enqueue_ns,
                             ticket.wait_ns, tenant=tenant, cost=cost,
                             degraded=ticket.degraded)
        return ticket, cls

    def execute_script(
        self, script: str, func=None, func_args=None, now=None,
        default_limit=None, analyze: bool = False, funcs=None,
        tenant: str = None, explain: bool = False,
    ) -> tuple[dict[str, QueryResult], dict]:
        """Compile + distribute + merge (the in-process core of ExecuteScript).

        `funcs=[(prefix, func_name, func_args)]` executes a MULTI-widget
        request as ONE fused distributed query (shared scans/filters/aggs
        run once — reference optimizer.h:39 MergeNodesRule); the returned
        stats carry `sink_map` so the caller splits results per widget.

        `explain=True` (EXPLAIN ANALYZE) annotates whatever path ACTUALLY
        served the query — plan tree, measured phase breakdown, per-op ns,
        cache/matview/batch/failover provenance — into stats["explain"] +
        stats["profile"], without changing execution (a matview hit is
        explained AS a matview hit, not bypassed).
        """
        import time as _time

        from pixie_tpu import observe as _observe
        from pixie_tpu import metrics as _metrics
        from pixie_tpu.serving import slo as _slo

        tenant = str(tenant or DEFAULT_TENANT)
        _metrics.counter_inc("px_broker_queries_total",
                             help_="ExecuteScript requests received")
        # In-process callers (cron, tests) get their own trace root; under
        # the networked path _run_query's root is already active and this is
        # a no-op.  Shipping happens only when this frame owns the root.
        owns_root = trace.enabled() and trace.current() is None
        #: flight recorder: assemble a per-query profile when tracing is on
        #: (recorded into self_telemetry.*) or explain was requested (the
        #: per-query opt-in works with tracing off, without recording)
        prof_on = trace.enabled() or explain
        t0 = _time.perf_counter()
        t0_unix_ns = _time.time_ns()
        shed = False
        ok_query = False
        qid = None
        tctx = None  # the root's wire context, shipped with its telemetry
        cls = None  # rate-model plan class, set once admission classifies
        wait_ns = 0
        try:
            with trace.maybe_root(self.tracer, "query"):
                # captured while the trace root is live: the except block
                # below runs AFTER the cm unwinds, and an error profile
                # must still join this query's spans on query_id==trace_id
                qid = self._query_trace_id() if prof_on else None
                tctx = trace.wire_context() if owns_root else None
                ticket, cls = self._admit(script, func, func_args,
                                          default_limit, tenant)
                wait_ns = ticket.wait_ns if ticket is not None else 0
                ok = False
                try:
                    results, stats = self._execute_script_inner(
                        script, func, func_args, now, default_limit, analyze,
                        funcs, tenant=tenant, ticket=ticket, explain=explain,
                    )
                    ok = True
                    ok_query = True
                    if prof_on:
                        self._record_profile(
                            qid, stats, tenant, t0_unix_ns,
                            int((_time.perf_counter() - t0) * 1e9),
                            explain=explain)
                    return results, stats
                finally:
                    self.serving.release(ticket, ok=ok)
        except ShedError:
            # admission rejections are flow control, not query failures —
            # they are counted under px_serving_shed_total instead
            shed = True
            raise
        except Exception as e:
            _metrics.counter_inc("px_broker_query_errors_total",
                                 help_="ExecuteScript requests that failed")
            if trace.enabled():
                # failed queries are profile rows too (status=error): an
                # error budget burning down must be visible in the same
                # table the latency dashboards read
                prow, _ops = _observe.build_profile(
                    qid or self._query_trace_id(), tenant, "broker",
                    t0_unix_ns, int((_time.perf_counter() - t0) * 1e9), {},
                    status="error", error=str(e))
                self._telemetry.add(_observe.PROFILES_TABLE, [prow])
            raise
        finally:
            latency_s = _time.perf_counter() - t0
            if not shed:
                # sheds stay out of the latency SLO histogram: a flood of
                # sub-ms rejections (or 30s queue-timeout sheds) during
                # overload would swamp the distribution of queries that
                # actually EXECUTED — exactly when the SLO signal matters
                _metrics.histogram_observe(
                    "px_broker_query_latency_seconds",
                    latency_s, QUERY_LATENCY_BOUNDS,
                    help_="broker end-to-end ExecuteScript latency "
                          "(executed queries; sheds excluded)")
            # the serving front's SLO loop eats every outcome — completed,
            # failed, AND shed (a shed is a client-visible availability
            # failure; hiding it from the burn rate would defeat the alert)
            _slo.record_query(tenant, latency_s, ok_query)
            # the rate model eats SERVICE time only (queue wait excluded —
            # it measures how fast the engine serves, not the line length);
            # sheds never executed, so they feed arrival counts only
            if cls is not None and not shed:
                self.ratemodel.observe(tenant, cls,
                                       latency_s - wait_ns / 1e9, ok_query)
            if _slo.configured():
                mon = _slo.monitor()
                mon.maybe_evaluate()
                self._telemetry.add(_observe.ALERTS_TABLE,
                                    mon.drain_alerts())
            if owns_root:
                self._ship_spans(tctx)

    def _query_trace_id(self) -> str:
        """Query id for profile rows: the active trace root's trace_id (so
        profiles JOIN against self_telemetry.spans), else a fresh token."""
        c = trace.current()
        if c is not None:
            return c[1].trace_id
        import secrets as _secrets

        return _secrets.token_hex(16)

    def _record_profile(self, qid, stats: dict, tenant: str,
                        t0_unix_ns: int, wall_ns: int,
                        explain: bool) -> None:
        """Assemble this query's flight-recorder profile from its stats and
        attach it (stats["profile"], stats["explain"]); recording into the
        data plane only happens with tracing enabled."""
        from pixie_tpu import observe as _observe

        profile, op_rows = _observe.build_profile(
            qid or self._query_trace_id(), tenant, "broker", t0_unix_ns,
            wall_ns, stats)
        stats["profile"] = profile
        if explain:
            stats["explain"] = _observe.render_explain(
                profile, op_rows, plan_text=stats.pop("plan_explain", None))
        if trace.enabled():
            self._telemetry.add(_observe.PROFILES_TABLE, [profile])
            self._telemetry.add(_observe.OP_STATS_TABLE, op_rows)
            if _autotune.enabled():
                at_rows = _autotune.rows_from_stats(
                    stats, profile.get("query_id", ""))
                if at_rows:
                    self._telemetry.add(_observe.AUTOTUNE_TABLE, at_rows)

    def _execute_script_inner(
        self, script, func, func_args, now, default_limit, analyze,
        funcs=None, tenant: str = DEFAULT_TENANT, ticket=None,
        explain: bool = False,
    ) -> tuple[dict[str, QueryResult], dict]:
        import time as _time

        from pixie_tpu import metrics as _metrics
        from pixie_tpu.compiler import compile_pxl, compile_pxl_funcs
        from pixie_tpu.status import Internal, Unavailable

        if self.elector is not None and not self.elector.is_leader():
            leader = self.elector.leader()
            raise Unavailable(
                f"this broker is not the leader (current leader: {leader})")
        if _autotune.enabled():
            # arrival-rate signal for the batch-window controller
            _autotune.MODEL.observe_arrival()
        # Hold for shards whose agent died moments ago and may re-register
        # (kill-and-restart): planning through the gap would silently serve
        # a reduced topology
        self._await_rejoin_grace()
        # Epoch BEFORE cluster_spec: a registration landing between the two
        # reads must not let a split computed from the agent-less spec be
        # cached under the post-registration epoch (sticky wrong results).
        # The inverse race — cluster_spec's live_agents() expiring an agent
        # and bumping the epoch after our read — only caches the fresh split
        # under the stale epoch: one redundant miss, never a poisoned hit.
        topo_epoch = self.registry.epoch
        spec = self.registry.cluster_spec()
        # Failover: dead primaries with live replicas stay IN the plan as
        # virtual agents — their fragments dispatch to the replica's
        # connection (serve_for), so the answer keeps covering their shard
        # instead of silently shrinking to the survivors.
        failover = self._failover_map()
        if failover:
            spec = self._spec_with_failover(spec, failover)
        if not any(a.has_data_store for a in spec.agents):
            e = Unavailable("no live data agents registered")
            # nothing compiled, nothing executed: always safe to retry
            # once an agent (re-)registers
            e.retryable = True
            e.retry_after_s = 1.0
            raise e
        sink_map = None
        entry = None
        plan_cache_hit = False
        t_compile0 = _time.perf_counter_ns()
        if funcs:
            # multi-widget fusion stays on the slow path: its sink_map and
            # per-widget arg sets make the cache key explode for no warm win
            with trace.span("compile"):
                q, sink_map = compile_pxl_funcs(
                    script, self.registry.combined_schemas(),
                    [(p, f, a) for p, f, a in funcs],
                    registry=self.udf_registry, now=now,
                    default_limit=default_limit,
                )
        else:
            def _compile():
                with trace.span("compile"):
                    return compile_pxl(
                        script, self.registry.combined_schemas(), func=func,
                        func_args=func_args, registry=self.udf_registry,
                        now=now, default_limit=default_limit,
                    )

            key = self.plan_cache.key(script, func, func_args, default_limit,
                                      ("reg", topo_epoch), tenant=tenant)
            q, entry, plan_cache_hit = self.plan_cache.get_query(key, _compile)
        compile_ns = _time.perf_counter_ns() - t_compile0
        plan_text = None
        if explain:
            from pixie_tpu.plan.debug import explain as _plan_explain

            plan_text = _plan_explain(q.plan)
        if q.mutations:
            # Deploy tracepoints to every live agent and wait for readiness
            # (reference MutationExecutor: register → agents deploy → poll
            # isSchemaReady, mutation_executor.go:84,272).
            with trace.span("deploy_mutations"):
                self.tracepoints.apply(q.mutations)
                self._deploy_mutations(q.mutations)
            topo_epoch = self.registry.epoch  # BEFORE cluster_spec (see above)
            spec = self.registry.cluster_spec()  # schemas refreshed by re-register
        elif not analyze and funcs is None \
                and not getattr(q, "now_sensitive", True):
            # Concurrent-query batching (PL_QUERY_BATCHING): groupable
            # concurrent queries over the same (table, scan window,
            # topology epoch) rendezvous at the serving front's dispatch
            # seam and execute as ONE fused distributed query; results
            # demux back per member.  None = run the normal path.
            got = self._maybe_batched(q, key, spec, topo_epoch, failover,
                                      tenant, ticket)
            if got is not None:
                results, stats = got
                if plan_text is not None or trace.enabled():
                    # a batched member's profile carries ITS OWN compile
                    # time and logical plan (the fused plan is the
                    # leader's implementation detail) beside the fused
                    # run's measured phases + batch slot
                    stats = dict(stats)
                    stats["phases"] = dict(stats.get("phases") or {},
                                           compile_ns=compile_ns)
                    if plan_text is not None:
                        stats["plan_explain"] = plan_text
                return results, stats
        return self._run_distributed(
            q, entry, spec, topo_epoch, failover, analyze, tenant, ticket,
            plan_cache_hit, sink_map=sink_map, compile_ns=compile_ns,
            plan_text=plan_text)

    # ------------------------------------------------------ query batching
    def _maybe_batched(self, q, key, spec, topo_epoch, failover, tenant,
                       ticket):
        """Pass one compiled, cache-eligible query through the shared
        batching gate (serving/batching.gate).  Returns (results, stats)
        when the query was served through a fused batch, or None to run
        the normal path (batching off, non-groupable plan, matview-shaped
        member, solo leader)."""
        from pixie_tpu.serving import batching

        reg = self.udf_registry
        if reg is None:
            from pixie_tpu.udf import registry as reg
        window_s = float(_flags.get("PL_BATCH_WINDOW_MS")) / 1e3
        max_n = int(_flags.get("PL_BATCH_MAX_QUERIES"))
        at_dec = None
        if _autotune.enabled():
            # rendezvous window from measured wave RTT, member cap from the
            # measured arrival rate (engine/autotune.py batch controller);
            # both clamped to a 4x band around the operator's constants
            window_s, max_n, at_dec = _autotune.MODEL.batch_window(
                window_s, max_n)
        got = batching.gate(
            self._batcher, q.plan, key, topo_epoch, window_s, max_n,
            lambda members: self._execute_batch(members, spec, topo_epoch,
                                                failover, reg),
            wait_timeout_s=self.query_timeout_s + 30.0,
            tenant=tenant, ticket=ticket, registry=reg,
            # concurrent-traffic signal: other queries executing past
            # admission right now (members waiting in a batch hold their
            # slots, so sustained concurrency keeps this ≥ 2; a lone
            # sequential client sees only itself and never waits)
            concurrency=lambda: (self.serving.enabled()
                                 and self.serving.inflight >= 2))
        if got is None:
            return None
        results, stats = got
        if at_dec is not None and isinstance(stats, dict):
            # fresh list, not setdefault: fused-member stats share inner
            # structures across the batch — appending in place would leak
            # this member's decision into every sibling's stats
            stats = dict(stats)
            stats["autotune"] = list(stats.get("autotune") or []) + [at_dec]
        b = (stats or {}).get("batch") or {}
        if b.get("t0_unix_ns"):
            # ONE batch_exec span under every member's query root (leaders
            # and waiters alike): the cross-query group marker
            trace.event_span("batch_exec", b["t0_unix_ns"],
                             b.get("wall_ns", 0),
                             size=b.get("size"), slot=b.get("slot"))
        return results, stats

    def _execute_batch(self, members, spec, topo_epoch, failover, reg):
        """Batch-leader path: merge the member plans (shared scans, deduped
        chains, per-slot renamed sinks; identical members share ONE
        computed slot), split+verify once per batch signature riding the
        split cache, run ONE fault-tolerant distributed dispatch (an
        evicted agent's WHOLE fused fragment re-dispatches — the pinned
        mid-batch recovery semantic), and demux per-member
        (results, stats)."""
        import time as _time
        import types

        from pixie_tpu.check import planverify
        from pixie_tpu.serving import batching

        k = len(members)
        slot, plans, slot_of = batching.fused_slot(
            self._batch_splits, self._qlock, members,
            spec.combined_schemas())
        # DRR cost-accounting: each member was admitted at the full plan
        # cost estimate; the batch executes ~one dispatch, so charge the
        # amortized share (refunds queued members' deficit — batching must
        # not distort tenant fairness)
        for m in members:
            if m.ticket is not None:
                self.serving.rebate(m.ticket, m.ticket.cost / k)
        fused_q = types.SimpleNamespace(plan=slot.fused, mutations=[],
                                        now_sensitive=False)
        # the batch_exec span lands on EVERY member root (leader included)
        # via the event emission in _maybe_batched — no cm span here, or
        # the leader's root would carry it twice
        t0_ns = _time.time_ns()
        results, stats = self._run_distributed(
            fused_q, slot, spec, topo_epoch, failover, False,
            "__batch__", None, plan_cache_hit=False,
            extra_verify=lambda dp: planverify.maybe_verify_fused_batch(
                dp, slot.sink_map))
        wall_ns = _time.time_ns() - t0_ns
        if _autotune.enabled():
            # measured fused-wave wall → the batch-window controller
            _autotune.MODEL.observe_batch_wave(wall_ns / 1e9, k)
        batching.note_formed(k)
        out = []
        for i, m in enumerate(members):
            res = batching.demux_results(results, slot.sink_map,
                                         f"q{slot_of[i]}")
            st = dict(stats)
            st["batch"] = {"size": k, "slots": len(plans),
                           "slot": slot_of[i], "t0_unix_ns": t0_ns,
                           "wall_ns": wall_ns}
            st["serving"] = {
                "tenant": m.tenant,
                "queued_ms": (round(m.ticket.wait_ns / 1e6, 3)
                              if m.ticket is not None and m.ticket.queued
                              else 0.0),
                "cost": m.ticket.cost if m.ticket is not None else None,
                "degraded": stats.get("serving", {}).get("degraded", False),
            }
            for qr in res.values():
                qr.exec_stats["batch"] = st["batch"]
            out.append((res, st))
        return out

    def _run_distributed(
        self, q, entry, spec, topo_epoch, failover, analyze, tenant,
        ticket, plan_cache_hit, sink_map=None, extra_verify=None,
        compile_ns: int = 0, plan_text=None,
    ) -> tuple[dict[str, QueryResult], dict]:
        """Split (cached per topology epoch), dispatch to agents with the
        fault-tolerant machinery, fold/merge, run the merger plan, and
        assemble per-query stats — the shared back half of
        `_execute_script_inner` and the fused-batch leader path
        (`_execute_batch`, which passes the merged plan as `q` and the
        batch-signature slot as `entry` so warm batches ride the same
        split cache)."""
        import time as _time

        from pixie_tpu import metrics as _metrics
        from pixie_tpu.status import Internal, Unavailable

        def _split():
            with trace.span("plan_split"):
                dp = DistributedPlanner(spec).plan(q.plan)
                # pre-dispatch verification rides the split computation, so
                # a cached split IS a verified split: warm queries skip it
                # entirely (check/planverify.py, PX_PLAN_VERIFY)
                from pixie_tpu.check import planverify

                planverify.maybe_verify(dp, spec.combined_schemas(),
                                        self.udf_registry)
                if extra_verify is not None:
                    extra_verify(dp)
                # pre-serialize the per-agent plan dicts: the dispatch loop
                # splices these cached JSON fragments into each execute
                # frame instead of re-walking + re-dumping the plan per query
                extras = {"plan_json": {
                    a: _json.dumps(p.to_dict())
                    for a, p in dp.agent_plans.items()
                }}
                return dp, extras

        from pixie_tpu.engine.plancache import QueryPlanCache as _QPC

        #: flight-recorder phase anchors (observe.build_profile): one
        #: perf_counter read per phase boundary — cheap enough to measure
        #: unconditionally; the dict only ships when profiles are on
        t_split0 = _time.perf_counter_ns()
        (dp, split_extras), split_hit = _QPC.get_split(
            entry, ("split", topo_epoch), _split)
        split_ns = _time.perf_counter_ns() - t_split0

        reg = self.udf_registry
        if reg is None:
            from pixie_tpu.udf import registry as reg
        # Broker-side view matcher: which agent fragments have a standing-
        # query shape?  The agents decide (and do) the actual serving — this
        # is the control-plane ledger that makes hit/miss observable per
        # query (stats["matview"], px_broker_matview_* counters, and a
        # matview_hit span when the whole query answered from views).
        # Disabled subsystem = no ledger: otherwise every query would pay
        # the canonicalize+hash and count as a "miss" for a feature that
        # is off.
        import pixie_tpu.matview  # noqa: F401 — defines the PL_MATVIEW_* flags

        with self._qlock:
            self._req_counter += 1
            req_id = f"q{self._req_counter}"
            ctx = _QueryCtx(set(dp.channels))
            ctx.failover = failover
            ctx.needed_agents = set(dp.agent_plans)
            ctx.configure_folds(dp, reg)
            self._queries[req_id] = ctx
        # Degradation hints ride each execute frame: past the shed
        # watermark, matview hits serve standing state WITHOUT folding
        # their delta (stale-while-revalidate) and the agents' chunk ack
        # window narrows so producers throttle at the source.  Read at
        # dispatch time (not admit time) so a queue that drained while
        # this query waited dispatches at full quality.  Catch-up counts
        # as degradation too: while a dead shard is served by failover
        # replicas, views serve stale-while-revalidate and ack windows
        # narrow — quality sheds, not correctness, while the restarted
        # shard rehydrates.
        degraded = self.serving.enabled() and (self.serving.degraded()
                                               or self.serving.catching_up())
        base_meta = {
            "msg": "execute",
            "analyze": analyze,
            # tenant rides to the agents: matview state namespaces
            # per tenant under PL_TENANT_ISOLATION
            "tenant": tenant,
            # distributed fan-out: agents route CPU/TPU by the
            # query's total size, not their local shard's
            "route_scale": len(dp.agent_plans),
        }
        if degraded:
            base_meta["stale_ok"] = True
            dw = int(_flags.get("PL_SERVING_DEGRADED_WINDOW"))
            if dw > 0:
                base_meta["stream_window"] = dw
        #: per-query fault/recovery ledger → stats["fault"]
        fault = {"rounds": 0, "evictions": 0, "hedged": 0,
                 "chunks_discarded": 0, "redispatched": []}
        retries = int(_flags.get("PL_QUERY_RETRIES"))
        t_exec0 = _time.perf_counter_ns()
        try:
            for agent_name in dp.agent_plans:
                pj = (split_extras["plan_json"].get(agent_name)
                      or _json.dumps(dp.agent_plans[agent_name].to_dict()))
                try:
                    self._send_execute(ctx, req_id, agent_name, pj, base_meta)
                except Unavailable:
                    if retries <= 0 or q.mutations:
                        raise
                    # the retry loop below re-plans around (or waits out)
                    # the missing agent
                    with ctx.lock:
                        ctx.evictions.append((agent_name, "not connected"))
                        ctx.wake.set()
            if dp.agent_plans:
                dp, split_extras = self._await_agents(
                    ctx, req_id, entry, q, dp, split_extras, base_meta,
                    reg, fault, retries, extra_verify=extra_verify)
            if ctx.error:
                raise Unavailable(ctx.error)
            mv_keys = {}
            if _flags.get("PL_MATVIEW_ENABLED"):
                from pixie_tpu.matview.registry import plan_view_key

                mv_keys = {
                    name: k for name, plan in dp.agent_plans.items()
                    if (k := plan_view_key(plan, reg)) is not None
                }

            t_merge0 = _time.perf_counter_ns()
            with trace.span("merge"):
                from pixie_tpu.parallel.repartition import (
                    bucket_channels,
                    run_join_stages,
                    stage_output_inputs,
                )

                # chunk folds ran on the reader threads (no trace context
                # there): emit them as spans now, under this query's root —
                # their start times preceding last_terminal_ns is the direct
                # evidence that merge work overlapped agent compute
                for t0_ns, dur_ns, cid, agent in ctx.fold_events:
                    trace.event_span("incremental_fold", t0_ns, dur_ns,
                                     channel=cid, agent=agent)
                # only the ACCEPTED sources (first answer per agent) merge;
                # everything else — evicted agents' partial streams, losing
                # hedge attempts, late duplicates — is discarded here and
                # counted, never folded into the answer.  Losing/superseded
                # producers may STILL be streaming into ctx on their reader
                # threads, so every shared structure is read under its lock
                # (an unguarded dict iteration here would raise mid-merge
                # and fail a query that succeeded).
                with ctx.lock:
                    accepted_srcs = set(ctx.accepted.values())
                    buckets = {cid: {s: list(chunks)
                                     for s, chunks in by_src.items()}
                               for cid, by_src in ctx.bucket_payloads.items()}
                discarded = 0
                payloads: dict[str, list] = {cid: [] for cid in dp.channels}
                for cid, by_src in buckets.items():
                    for s, chunks in sorted(by_src.items()):
                        if s in accepted_srcs and cid in payloads:
                            payloads[cid].extend(chunks)
                        else:
                            discarded += len(chunks)
                if dp.join_stages:
                    # repartitioned joins run partition-parallel on the merger
                    # (the Kelvin role); bucket channels are consumed here, with
                    # the same payload-shape contract as rows channels
                    run_join_stages(dp, payloads, reg,
                                    store=self.merger_store, analyze=analyze)
                consumed = bucket_channels(dp)
                inputs: dict[str, HostBatch] = {}
                folded_total = 0
                for cid, ch in dp.channels.items():
                    if cid in consumed:
                        continue
                    fold = ctx.folds.get(cid)
                    flock = ctx.fold_locks.get(cid)
                    if fold is None or flock is None:
                        raise Internal(f"channel {cid} received no payloads")
                    # the channel's fold lock serializes against loser/
                    # superseded producers still folding on reader threads
                    with flock:
                        total = sum(fold.count_for(s)
                                    for s in accepted_srcs)
                        if total == 0:
                            raise Internal(
                                f"channel {cid} received no payloads")
                        # every chunk an accepted producer SENT must have
                        # folded: a dropped frame means a silently-partial
                        # answer, so fail instead
                        for s in sorted(accepted_srcs):
                            exp = ctx.expected_chunks.get((cid, s))
                            if exp is not None and fold.count_for(s) != exp:
                                raise Internal(
                                    f"channel {cid}: folded "
                                    f"{fold.count_for(s)} of "
                                    f"{exp} chunk frames")
                        folded_total += total
                        discarded += fold.discarded_chunks(accepted_srcs)
                        # the running per-src folds already combined every
                        # chunk on arrival; finish() pays one cross-source
                        # combine (deterministic sorted-source order) + the
                        # finalize
                        with trace.span("merge_finish", channel=cid,
                                        kind=ch.kind, chunks=total,
                                        incremental=True):
                            inputs[cid] = fold.finish(accepted_srcs)
                if discarded:
                    _metrics.counter_inc(
                        "px_chunks_discarded_total", float(discarded),
                        help_="producer chunks discarded at merge (evicted "
                              "agents' partial streams, losing hedge "
                              "attempts, late duplicates)")
                fault["chunks_discarded"] = discarded
                inputs.update(stage_output_inputs(dp, payloads))

                from pixie_tpu.udf.udtf import UDTFContext

                ex = PlanExecutor(
                    dp.merger_plan, self.merger_store, self.udf_registry,
                    inputs=inputs, analyze=analyze,
                    udtf_ctx=UDTFContext(
                        table_store=self.merger_store, registry=reg,
                        agent_registry=self.registry,
                        tracepoint_manager=self.tracepoints,
                    ),
                )
                results = ex.run()
                # The merger plan's sources are channels (no STs); the LOGICAL
                # plan + agent schemas determine them.
                from pixie_tpu.engine.semantics import SchemaStore, restamp_result

                sstore = SchemaStore(self.registry.combined_schemas())
                for r in results.values():
                    restamp_result(r, q.plan, sstore, reg)
                stats = {"agents": ctx.agent_stats, "merger": dict(ex.stats)}
                #: fast-path observability: did this query skip compile /
                #: split work?  (PL_QUERY_FASTPATH off ⇒ both always False)
                stats["fastpath"] = {"plan_cache_hit": plan_cache_hit,
                                     "split_cache_hit": split_hit}
                #: serving-front observability per query: its tenant, the
                #: queue wait it paid, and whether it dispatched degraded
                #: (stale matview serving + narrowed ack window)
                stats["serving"] = {
                    "tenant": tenant,
                    "queued_ms": (round(ticket.wait_ns / 1e6, 3)
                                  if ticket is not None and ticket.queued
                                  else 0.0),
                    "cost": ticket.cost if ticket is not None else None,
                    "degraded": degraded,
                }
                if mv_keys:
                    served = {
                        a: s["matview"] for a, s in ctx.agent_stats.items()
                        if isinstance(s, dict) and s.get("matview")
                    }
                    hits = sum(1 for i in served.values() if i.get("hit"))
                    stats["matview"] = {
                        "eligible_agents": len(mv_keys),
                        "agents_hit": hits,
                        "rows_folded": sum(
                            int(i.get("rows_folded", 0))
                            for i in served.values()),
                        "keys": sorted(set(mv_keys.values())),
                    }
                    if hits and hits == len(dp.agent_plans):
                        # the ENTIRE scan side answered from standing state:
                        # this query's cost was delta folds + one finalize
                        _metrics.counter_inc(
                            "px_broker_matview_hit_queries_total",
                            help_="queries fully answered from standing "
                                  "view state on every agent")
                        trace.event_span(
                            "matview_hit", _time.time_ns(), 0,
                            agents=hits,
                            rows_folded=stats["matview"]["rows_folded"])
                    else:
                        _metrics.counter_inc(
                            "px_broker_matview_miss_queries_total",
                            help_="view-eligible queries that rescanned on "
                                  "at least one agent")
                #: streaming-merge observability: merge_overlapped=True means
                #: the first chunk folded BEFORE the last agent's terminal
                #: frame — merge cost hid under the slowest agent's compute
                stats["stream"] = {
                    "chunks_folded": folded_total,
                    "first_fold_unix_ns": ctx.first_fold_ns,
                    "last_terminal_unix_ns": ctx.last_terminal_ns,
                    "merge_overlapped": bool(
                        ctx.first_fold_ns is not None
                        and ctx.last_terminal_ns is not None
                        and ctx.first_fold_ns < ctx.last_terminal_ns),
                }
                #: fault-recovery observability per query: re-dispatch
                #: rounds paid, agents evicted mid-query, hedged duplicate
                #: dispatches, and chunks discarded at merge — all zero on
                #: the fault-free path.  Row-completeness accounting:
                #: which primaries answered via a failover replica, and the
                #: rows each accepted source actually scanned (0 for
                #: standing-view serves) — the audit trail for "did this
                #: answer cover every shard".
                with ctx.lock:
                    fault["failover"] = dict(ctx.failover_used)
                fault["rows_scanned"] = {
                    a: int(s.get("rows_scanned", 0))
                    for a, s in ctx.agent_stats.items()
                    if isinstance(s, dict)
                }
                stats["fault"] = fault
                if sink_map is not None:
                    stats["sink_map"] = sink_map
                    stats["merger"]["operators"] = ex.op_stats
                for r in results.values():
                    r.exec_stats["agents"] = ctx.agent_stats
            if trace.enabled() or plan_text is not None:
                # where the time went, measured at the phase seams the
                # spans already mark — observe.build_profile sums these
                # into the per-query attribution row
                stats["phases"] = {
                    "compile_ns": int(compile_ns),
                    "plan_split_ns": int(split_ns),
                    "exec_ns": int(t_merge0 - t_exec0),
                    "merge_ns": int(_time.perf_counter_ns() - t_merge0),
                }
                if plan_text is not None:
                    stats["plan_explain"] = plan_text
            return results, stats
        finally:
            # span hygiene: a timeout / disconnect / error leaves dispatch
            # spans without an exec_done to close them
            for src in list(ctx.dispatch_spans):
                self._finish_dispatch_span(ctx, src,
                                           error=ctx.error or "unresolved")
            with self._qlock:
                self._queries.pop(req_id, None)


def _jsonable(obj):
    import numpy as np

    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.generic):
        return obj.item()
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    return obj
