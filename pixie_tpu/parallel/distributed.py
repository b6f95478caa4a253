"""Distributed planner: logical plan → per-agent plans + channels.

Reference architecture (src/carnot/planner/distributed/): Coordinator
partitions by CarnotInfo, Splitter cuts the plan at EVERY blocking boundary
inserting GRPCSink/GRPCSourceGroup pairs (splitter/splitter.h:114-155), and
PartialOperatorMgr splits aggregates into partial (data agents) + finalize
(merger) (splitter/partial_op_mgr/).  This implementation mirrors those
boundaries with a TPU-shaped data plane:

  * The AGENT-SIDE region is the maximal subgraph of scans + streamable ops
    (map/filter/limit); every edge leaving it is a cut.
  * An AggOp directly fed by an unlimited agent-side chain cuts as an
    "agg_state" channel: the agents run the chain + a partial agg SPMD over
    their mesh and ship value-keyed per-group UDA state (each agent has its
    own dictionary code space, so keys cross agents as VALUES — the analog of
    the reference's serialized-UDA partial rows, planpb plan.proto:250-257).
  * Every other cut (join/union inputs, sinks, second-level aggs, limited
    chains) is a "rows" channel; the merger re-applies any upstream limit
    (reference LimitPushdownRule keeps the original on the Kelvin side).
  * Agent plans are DAGs: a scan shared by several cut branches (e.g.
    net_flow_graph's one source feeding two aggs) is cloned ONCE per agent
    and fanned out.  Each branch still drives its own cursor, but device
    feeds dedupe through the HBM feed cache, so repeated traversals stream
    bytes once.
  * Fragments go only to agents holding the fragment's table (reference
    coordinator/prune_unavailable_sources_rule.cc).
"""
from __future__ import annotations

import copy
import dataclasses
import itertools
from typing import Optional

from pixie_tpu.plan.plan import (
    AggOp,
    FilterOp,
    LimitOp,
    MapOp,
    MemorySinkOp,
    MemorySourceOp,
    Plan,
    RemoteSourceOp,
    ResultSinkOp,
    UDTFSourceOp,
)
from pixie_tpu.parallel.topology import AgentInfo, ClusterSpec
from pixie_tpu.status import CompilerError

_STREAMABLE = (MapOp, FilterOp, LimitOp)
_INF = float("inf")


def _mesh_parts(agents) -> int:
    """Pod-scale shuffle width from the producers' EXPLICIT device meshes:
    the largest pow2-clamped AgentInfo.n_devices (≥2) among them, else 1.
    None ("auto") stays 1 — the planner must not guess a mesh it cannot
    see, and agent-count partitioning is always correct; an agent whose
    mesh is narrower than the chosen width simply host-exchanges its side
    (partition_ids() assignment is identical either way)."""
    best = 1
    for a in agents:
        n = getattr(a, "n_devices", None)
        if isinstance(n, int) and n >= 2:
            best = max(best, 1 << (n.bit_length() - 1))
    return best


@dataclasses.dataclass
class Channel:
    """One remote edge (reference: a GRPCSink/GRPCSourceGroup pair keyed by
    (query_id, source_id); here a named channel)."""

    id: str
    kind: str  # "rows" | "agg_state"
    #: producing agents
    producers: list = dataclasses.field(default_factory=list)
    #: for agg_state channels: the full AggOp spec merged at the consumer
    agg: Optional[AggOp] = None


@dataclasses.dataclass
class JoinStage:
    """One repartitioned join: producers hash both sides into per-partition
    bucket channels; each partition's buckets union and join independently
    (key-disjoint), and the outputs concatenate into `out_channel`."""

    fragment: Plan
    left_prefix: str
    right_prefix: str
    left_channel: str
    right_channel: str
    out_channel: str
    n_parts: int


@dataclasses.dataclass
class DistributedPlan:
    """Per-agent plans + the merger plan + channel specs."""

    agent_plans: dict  # agent name -> Plan
    merger_plan: Plan
    channels: dict  # channel id -> Channel
    merger: str
    #: repartitioned large-large joins executed between the agent stage and
    #: the merger plan (parallel.repartition.run_join_stages)
    join_stages: list = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "agents": {n: p.to_dict() for n, p in self.agent_plans.items()},
            "merger": self.merger,
            "merger_plan": self.merger_plan.to_dict(),
            "channels": {
                c.id: {
                    "kind": c.kind,
                    "producers": list(c.producers),
                    "agg": c.agg.to_dict() if c.agg else None,
                }
                for c in self.channels.values()
            },
            "join_stages": [
                {"fragment": s.fragment.to_dict(),
                 "left_prefix": s.left_prefix,
                 "right_prefix": s.right_prefix,
                 "left_channel": s.left_channel,
                 "right_channel": s.right_channel,
                 "out": s.out_channel,
                 "n_parts": s.n_parts}
                for s in self.join_stages
            ],
        }


class DistributedPlanner:
    """Splits one logical plan across a ClusterSpec (reference
    DistributedPlanner::Plan, distributed_planner.cc)."""

    def __init__(self, cluster: ClusterSpec, registry=None):
        self.cluster = cluster
        if registry is None:
            from pixie_tpu.udf import registry as registry_mod

            registry = registry_mod
        self.registry = registry

    def _partial_safe(self, op: AggOp) -> bool:
        """Whether the agg's state merges across agents' private dictionary
        code spaces.  dict_ok UDAs (any over a string column) carry CODES in
        their state — conservative: ship rows even for numeric any()."""
        for ae in op.values:
            try:
                uda = self.registry.uda(ae.fn)
            except Exception:
                return False
            if uda.dict_ok:
                return False
        return True

    def plan(self, logical: Plan) -> DistributedPlan:
        merger = self.cluster.merger()
        chan_ids = itertools.count(0)
        channels: dict[str, Channel] = {}
        merger_plan = Plan()

        # ---- 1. classify the agent-side region + per-op upstream limit/table.
        agent_side: set[int] = set()
        min_limit: dict[int, float] = {}  # op id -> min LimitOp.n upstream
        src_table: dict[int, str] = {}  # op id -> root table of its chain
        for op in logical.topo_sorted():
            if isinstance(op, MemorySourceOp):
                agent_side.add(op.id)
                min_limit[op.id] = _INF
                src_table[op.id] = op.table
            elif isinstance(op, _STREAMABLE):
                ps = logical.parents(op)
                if len(ps) == 1 and ps[0].id in agent_side:
                    agent_side.add(op.id)
                    lim = min_limit[ps[0].id]
                    if isinstance(op, LimitOp):
                        lim = min(lim, op.n)
                    min_limit[op.id] = lim
                    src_table[op.id] = src_table[ps[0].id]

        # ---- 2. per-agent DAG cloning (shared scans clone once).
        agent_plans: dict[str, Plan] = {}
        agent_ops: dict[str, dict[int, object]] = {}

        def clone_into(agent: str, op):
            m = agent_ops.setdefault(agent, {})
            got = m.get(op.id)
            if got is not None:
                return got
            parents = [clone_into(agent, p) for p in logical.parents(op)]
            c = copy.copy(op)
            c.id = -1
            agent_plans.setdefault(agent, Plan()).add(c, parents=parents)
            m[op.id] = c
            return c

        def producers_for(op) -> list[AgentInfo]:
            table = src_table[op.id]
            prods = self.cluster.data_agents(table)
            if not prods:
                raise CompilerError(f"no agent has table {table!r}")
            return prods

        # ---- 3. cut every agent-side → non-agent-side edge.
        lowered: dict[int, object] = {}  # logical id -> merger plan op
        rows_channel_of: dict[int, str] = {}  # agent-side op id -> channel id

        def cut_rows(p) -> None:
            """Rows channel at agent-side op p (idempotent per p)."""
            if p.id in rows_channel_of:
                return
            cid = f"ch{next(chan_ids)}"
            rows_channel_of[p.id] = cid
            prods = producers_for(p)
            channels[cid] = Channel(cid, "rows", [a.name for a in prods])
            for a in prods:
                cp = clone_into(a.name, p)
                agent_plans[a.name].add(
                    ResultSinkOp(channel=cid, payload="rows"), parents=[cp]
                )
            rs = RemoteSourceOp(channel=cid)
            merger_plan.add(rs)
            lowered[p.id] = rs
            # Re-apply any upstream limit on the merger side: each agent
            # enforces head(n) over ITS rows, so k producers ship up to k*n.
            lim = min_limit[p.id]
            if lim != _INF:
                lop = LimitOp(n=int(lim))
                merger_plan.add(lop, parents=[rs])
                lowered[p.id] = lop

        def cut_agg(agg: AggOp, parent) -> None:
            """Partial-agg channel: agents run chain + partial agg."""
            cid = f"ch{next(chan_ids)}"
            prods = producers_for(parent)
            channels[cid] = Channel(
                cid, "agg_state", [a.name for a in prods], agg=copy.copy(agg)
            )
            for a in prods:
                cp = clone_into(a.name, parent)
                partial = copy.copy(agg)
                partial.id = -1
                partial.partial = True
                # one producer (a mesh agent is one): nothing is added to its
                # state downstream, so it may ship its sketches finalized
                partial.sole_producer = len(prods) == 1
                ap = agent_plans[a.name]
                ap.add(partial, parents=[cp])
                ap.add(
                    ResultSinkOp(channel=cid, payload="agg_state"),
                    parents=[partial],
                )
            rs = RemoteSourceOp(channel=cid)
            merger_plan.add(rs)
            lowered[agg.id] = rs  # merged+finalized agg arrives as rows

        join_stages: list[JoinStage] = []

        def cut_repartition_join(op, parents) -> bool:
            """Large-large equijoin: hash-exchange both UNAGGREGATED sides
            into key-disjoint partitions instead of funneling full rows to
            one merger join (reference splitter shuffle, splitter.h:114-155).
            Returns False when the shape doesn't qualify (keyless/cross
            join, limited side ⇒ small side, or a single producer with no
            multi-device mesh).

            Pod-scale width: the partition count is decoupled from the
            agent count — when producers declare EXPLICIT device meshes
            (AgentInfo.n_devices), the shuffle widens to the largest mesh so
            each mesh device owns one partition and the PartitionSink
            exchange lowers to ONE lax.all_to_all over the mesh (the
            executor's in-mesh path).  A single agent with an 8-device mesh
            therefore still gets an 8-way shuffled join — partitions are
            device shards, not host processes."""
            from pixie_tpu.plan.plan import JoinOp, PartitionSinkOp

            if not (isinstance(op, JoinOp) and len(parents) == 2
                    and op.left_on and op.right_on
                    and all(p.id in agent_side for p in parents)
                    and all(min_limit[p.id] == _INF for p in parents)):
                return False
            prods_l = producers_for(parents[0])
            prods_r = producers_for(parents[1])
            n_parts = max(
                len({a.name for a in prods_l} | {a.name for a in prods_r}),
                _mesh_parts(prods_l + prods_r),
            )
            if n_parts < 2:
                return False
            j = next(chan_ids)
            lp, rp = f"rp{j}l_", f"rp{j}r_"
            out_cid = f"rp{j}out"
            for parent, prefix, keys, prods in (
                    (parents[0], lp, op.left_on, prods_l),
                    (parents[1], rp, op.right_on, prods_r)):
                for a in prods:
                    cp = clone_into(a.name, parent)
                    agent_plans[a.name].add(
                        PartitionSinkOp(prefix=prefix, keys=list(keys),
                                        n_parts=n_parts),
                        parents=[cp],
                    )
                for p_i in range(n_parts):
                    channels[f"{prefix}{p_i}"] = Channel(
                        f"{prefix}{p_i}", "rows", [a.name for a in prods]
                    )
            frag = Plan()
            left = frag.add(RemoteSourceOp(channel="left"))
            right = frag.add(RemoteSourceOp(channel="right"))
            jop = copy.copy(op)
            jop.id = -1
            frag.add(jop, parents=[left, right])
            frag.add(ResultSinkOp(channel=out_cid, payload="rows"),
                     parents=[jop])
            join_stages.append(JoinStage(
                fragment=frag, left_prefix=lp, right_prefix=rp,
                left_channel="left", right_channel="right",
                out_channel=out_cid, n_parts=n_parts,
            ))
            rs = RemoteSourceOp(channel=out_cid)
            merger_plan.add(rs)
            lowered[op.id] = rs
            return True

        for op in logical.topo_sorted():
            if op.id in agent_side:
                continue
            parents = logical.parents(op)
            if (
                isinstance(op, AggOp)
                and len(parents) == 1
                and parents[0].id in agent_side
                # A limited chain must NOT cut at the agg: each agent would
                # admit its own n rows, feeding up to k*n rows into the
                # distributed aggregate.  Ship rows; the merger re-applies
                # the limit, then aggregates exactly n rows.
                and min_limit[parents[0].id] == _INF
                and self._partial_safe(op)
            ):
                cut_agg(op, parents[0])
                continue
            if cut_repartition_join(op, parents):
                continue
            for p in parents:
                if p.id in agent_side:
                    cut_rows(p)

        # ---- 4. lower the remaining (merger-side) ops.
        for op in logical.topo_sorted():
            if op.id in agent_side or op.id in lowered:
                continue
            parents = logical.parents(op)
            if not parents:
                if isinstance(op, UDTFSourceOp):
                    # UDTF sources run merger-side (the reference's ONE_KELVIN
                    # executor scope, udtf.h UDTFSourceExecutor).
                    c = copy.copy(op)
                    c.id = -1
                    merger_plan.add(c)
                    lowered[op.id] = c
                    continue
                raise CompilerError(
                    f"distributed plan source must be a table scan, got {op.kind}"
                )
            c = copy.copy(op)
            c.id = -1
            merger_plan.add(c, parents=[lowered[p.id] for p in parents])
            lowered[op.id] = c

        return DistributedPlan(
            agent_plans=agent_plans,
            merger_plan=merger_plan,
            channels=channels,
            merger=merger.name,
            join_stages=join_stages,
        )
