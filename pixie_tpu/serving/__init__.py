"""Multi-tenant serving front: admission control + fair-share scheduling.

The broker is the one chokepoint every ExecuteScript passes through
(Pixie's L3 query_broker orchestrating the agent fleet, PAPER.md layer
map); this package is what it absorbs in-cluster so a burst of queries —
or one heavy tenant — cannot take the fleet down or starve interactive
users:

  admission.py  — per-tenant token-bucket quotas (PL_TENANT_QPS,
                  PL_TENANT_CONCURRENCY), quota-spec parsing, ShedError
                  (the retry-after envelope)
  scheduler.py  — ServingFront: global in-flight cap, bounded per-tenant
                  queues, deficit-round-robin dispatch weighted by tenant
                  share and estimated query cost (plan-cache warm vs cold
                  compile), degradation state (readyz flip, cold-query
                  shedding, stale matview serving, narrowed chunk ack
                  windows)
  ratemodel.py  — measured per-(tenant, plan-class) service-rate model:
                  replaces the static warm/cold DRR costs and heuristic
                  retry-after with measured rates, and supplies the
                  autoscaler's Little's-law demand signal (PL_RATE_MODEL)
  elastic.py    — AgentSupervisor: broker-driven agent autoscaling with
                  hysteresis/cooldowns/bounds, loss-safe retires, and
                  orphan-proof launchers (PL_AUTOSCALE)

Live quotas: `ServingFront.set_quota` applies control-plane records
(`admission.normalize_quota`) ahead of the PL_TENANT_* env specs; the
broker persists them in its KV and exposes `set_quota`/`get_quotas`
frames (CLI `quota set|show`).

Flag-off (`PL_SERVING_ENABLED=0`) the front is a pass-through: no
accounting, no queueing, bit-identical results.
"""
from pixie_tpu.serving.admission import (
    COST_COLD,
    COST_WARM,
    ShedError,
    TokenBucket,
    normalize_quota,
    parse_tenant_spec,
)
from pixie_tpu.serving.scheduler import ServingFront, Ticket

__all__ = [
    "COST_COLD",
    "COST_WARM",
    "ServingFront",
    "ShedError",
    "Ticket",
    "TokenBucket",
    "normalize_quota",
    "parse_tenant_spec",
]
