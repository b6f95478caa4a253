"""Profile-fed adaptive gates: the hot path's hand-tuned constants become
online cost models.

The engine's dispatch seams are gated by magic numbers tuned once on one
box — `PX_CPU_CROSSOVER_ROWS`, the device-join H2D gate,
`PX_SKETCH_SORT_MIN_GROUPS`, the hedge floor (`PL_HEDGE_MIN_MS`), the batch
window (`PL_BATCH_WINDOW_MS`/`PL_BATCH_MAX_QUERIES`) — while the flight
recorder (observe.py) already measures the ground truth those constants
are guessing at.  This module closes that loop (ROADMAP item 4; Tailwind's
framing in PAPERS.md: route each fragment to the backend the MEASUREMENTS
favor, not the one a build-time constant picked):

  * **Per-gate cost models.**  Each gate keeps, per (plan class, size
    bucket) key, one `_Arm` per choice (service-time EWMA + mean-absolute
    deviation + a bounded sample ring — the PR 15 ratemodel estimator).
    The caller names the class.  For the CPU/device crossover it is the
    chain being routed (`executor._route_class_of`: `agg:<table>:<digest
    of the chain's ops and its aggregate>`, less everything that changes
    while the work does not — time bounds, dictionary sizes, the metadata
    epoch), so two scripts over the same rows price their own arms and one
    script keeps one key; a chain whose completions nobody observes gets
    no key and routes by the static crossover alone.
    An arm's predicted cost is the MEDIAN of its ring, so one slow sample
    among its recent ones moves no decision; the EWMA is what persists
    and what a KV-warmed arm answers with until its ring has samples.
    `decide()` returns the arm with the lowest predicted cost once every
    arm is warm (`PX_AUTOTUNE_MIN_SAMPLES`), else the gate's static
    default — a cold model must never steer dispatch off one noisy sample.
  * **Guarded exploration.**  A small deterministic epsilon of decisions
    (`PX_AUTOTUNE_EPSILON`; counter-paced, never random — replays and
    restarts stay reproducible) probes the least-sampled non-favored arm so
    the model keeps a live baseline for the road not taken.  Cold
    non-static arms probe at a faster fixed cadence so a fresh model warms
    in bounded decisions; a KV-warmed model skips that burst entirely.
  * **Tail guard.**  Whenever the model favors a non-static arm, the
    favored arm's last `PX_AUTOTUNE_GUARD_WINDOW` samples are held
    against `PX_AUTOTUNE_GUARD_FACTOR`× the static arm's median: once
    `GUARD_SHARE` of them lie over it (a regression that lasts, not one
    outlier) the gate snaps back to its static default for
    `PX_AUTOTUNE_GUARD_HOLDOFF` decisions, the drifted arm keeps its
    history less the samples that falsified it (so its return after the
    hold-off is one comparison, not a cold warm-up), and an
    `autotune_fallback` event lands in `self_telemetry.autotune` — a
    drifted model can never hold a tail hostage.
  * **Persistence.**  `save_kv`/`load_kv` round-trip the per-arm (n, ewma,
    dev) triples through the broker KV (`autotune/model`, the PR 15 quota
    pattern) so a restarted broker starts warm; a corrupt record degrades
    to static defaults (counted, never fatal).
  * **Attribution.**  Every decision dict lands in `stats["autotune"]`,
    the EXPLAIN ANALYZE provenance block, and the
    `self_telemetry.autotune` table, so "why did this query take this
    path" is always answerable.

`PX_AUTOTUNE=0` removes every model read AND write: gates run their
original static logic bit-identically, no decision is recorded anywhere.
"""
from __future__ import annotations

import statistics
import threading
import time
from collections import deque
from typing import Optional

from pixie_tpu import flags, metrics

flags.define_bool(
    "PX_AUTOTUNE", True,
    "profile-fed adaptive gates (engine/autotune.py): the CPU/device "
    "crossover, device-join gate, sketch sort crossover, hedge floor and "
    "batch window route through online cost models fit from measured "
    "completions instead of their static constants; 0 restores every "
    "hand-tuned default bit-identically")
flags.define_float(
    "PX_AUTOTUNE_EPSILON", 0.0625,
    "fraction of warm-model decisions that probe the non-favored arm "
    "(deterministic counter pacing, not random) so the model keeps a live "
    "baseline for the road not taken")
flags.define_int(
    "PX_AUTOTUNE_MIN_SAMPLES", 8,
    "observations every arm of a gate key needs before the fitted model "
    "may override the static default")
flags.define_int(
    "PX_AUTOTUNE_GUARD_WINDOW", 8,
    "the tail guard's window: the model-favored arm's most recent samples "
    "it holds against the static arm (and the fewest samples either arm "
    "needs before it compares at all)")
flags.define_float(
    "PX_AUTOTUNE_GUARD_FACTOR", 2.0,
    "tail-guard trip ratio: a model-favored arm with half its guard window "
    "over factor * the static arm's median sample reverts the gate to its "
    "static default")
flags.define_int(
    "PX_AUTOTUNE_GUARD_HOLDOFF", 256,
    "decisions a tripped gate key stays pinned to its static default "
    "before the model may favor the tripped arm again")

#: the gates this module models (mq_fusion is record-only: its decision is
#: baked into compiled kernels at trace time, so flipping it per query
#: would churn the program cache — tuning it from measured wave RTT on
#: accelerator hardware is the documented ROADMAP remainder)
GATE_CPU_CROSSOVER = "cpu_crossover"
GATE_DEVICE_JOIN = "device_join"
GATE_SKETCH_SORT = "sketch_sort"
GATE_HEDGE = "hedge"
GATE_BATCH_WINDOW = "batch_window"
GATE_MQ_FUSION = "mq_fusion"

#: recent service samples kept per arm (the arm's median and the tail
#: guard's window are read from them)
RING = 64

#: ring samples an arm recalled from the KV (n restored, ring empty) needs
#: before its median replaces the persisted EWMA as its predicted cost
MEDIAN_MIN = 8

#: share of the favored arm's guard window that must lie over the trip
#: level: one outlier in a window of 8 is an eighth and trips nothing, a
#: regression that lasts reaches half within one window
GUARD_SHARE = 0.5

#: cold non-static arms probe every Nth decision until warm — bounded
#: warmup without randomness (a KV-warmed model never enters this phase)
COLD_PROBE_PERIOD = 4

#: arrival-rate window (seconds of 1-second bins) for the batch controller
ARRIVAL_WINDOW_S = 30

#: bounded fallback/decision event buffer (drained into
#: self_telemetry.autotune on the self-metrics cron)
MAX_EVENTS = 512

#: keys tracked per gate — size buckets are intrinsically bounded (log
#: scale), but the cap keeps a pathological key stream from growing the
#: model without bound (same discipline as metric label families)
MAX_KEYS_PER_GATE = 64

#: EWMA smoothing factor (matches the PR 9/15 service-time estimators)
ALPHA = 0.2

#: the KV record the model persists under (PR 15 quota pattern)
KV_KEY = "autotune/model"

#: pxlint lock-discipline: every *_locked member of AutotuneModel is owned
#: by the model's one mutex
_pxlint_locks_ = {
    "_gate_locked": "self._lock",
    "_arm_locked": "self._lock",
    "_decide_locked": "self._lock",
    "_guard_locked": "self._lock",
    "_event_locked": "self._lock",
    "_quantile_locked": "self._lock",
}


def enabled() -> bool:
    return bool(flags.get("PX_AUTOTUNE"))


def size_bucket(n: int) -> str:
    """Log-scale size bucket (powers of 4): inputs within a 4x band share
    one model key — fine enough to separate the crossover regions, coarse
    enough that every bucket warms from real traffic."""
    n = int(n)
    if n <= 0:
        return "4^0"
    return f"4^{(n.bit_length() + 1) // 2}"


class _Arm:
    """One (gate, key, arm) completion stream: cost EWMA + tail ring."""

    __slots__ = ("n", "ewma", "dev", "ring")

    def __init__(self, n: int = 0, ewma: float = 0.0, dev: float = 0.0):
        self.n = int(n)
        self.ewma = float(ewma)
        self.dev = float(dev)
        self.ring: deque = deque(maxlen=RING)

    def observe(self, secs: float) -> None:
        if self.n == 0:
            self.ewma = secs
            self.dev = secs / 2
        else:
            self.ewma += ALPHA * (secs - self.ewma)
            self.dev += ALPHA * (abs(secs - self.ewma) - self.dev)
        self.n += 1
        self.ring.append(secs)

    def cost(self) -> float:
        """The arm's predicted cost: the median of its recent samples,
        which one outlier among them cannot move; the EWMA while the ring
        holds fewer than the arm has seen and fewer than MEDIAN_MIN (an
        arm recalled from the KV starts with an empty ring)."""
        if len(self.ring) < max(1, min(self.n, MEDIAN_MIN)):
            return self.ewma
        return statistics.median(self.ring)


class _GateState:
    """One gate's model: per-key arms + decision pacing + guard holdoff."""

    __slots__ = ("arms", "count", "holdoff", "last_arm", "trips",
                 "fallbacks")

    def __init__(self):
        #: key -> {arm_name: _Arm}
        self.arms: dict[str, dict[str, _Arm]] = {}
        #: key -> decisions taken (paces the deterministic epsilon probe)
        self.count: dict[str, int] = {}
        #: key -> decisions left pinned to static after a guard trip
        self.holdoff: dict[str, int] = {}
        #: key -> arm of the most recent decision (observation routing for
        #: call sites whose completion callback has no decision handle)
        self.last_arm: dict[str, str] = {}
        #: key -> guard trips so far (every decision carries its key's)
        self.trips: dict[str, int] = {}
        self.fallbacks = 0


class AutotuneModel:
    """Thread-safe per-process model over every adaptive gate."""

    def __init__(self):
        self._lock = threading.Lock()
        self._gates: dict[str, _GateState] = {}
        #: pending self_telemetry.autotune event rows (fallbacks, fitted-
        #: threshold changes) — drained on the self-metrics cron
        self._events: list[dict] = []
        self._events_dropped = 0
        #: fleet-wide dispatch service times (hedge-floor fit)
        self._service: deque = deque(maxlen=256)
        #: recent fused-batch wave walls (batch-window fit)
        self._waves: deque = deque(maxlen=128)
        #: (sec, arrivals) 1-second bins, ascending (batch-window fit)
        self._bins: deque = deque()
        #: fitted sketch thresholds last reported per backend (event dedup)
        self._sketch_fit: dict[str, int] = {}
        self.loaded_from_kv = False

    # ------------------------------------------------------------- internals
    def _gate_locked(self, gate: str) -> _GateState:
        g = self._gates.get(gate)
        if g is None:
            g = self._gates[gate] = _GateState()
        return g

    def _arm_locked(self, g: _GateState, key: str, arm: str) -> _Arm:
        arms = g.arms.get(key)
        if arms is None:
            if len(g.arms) >= MAX_KEYS_PER_GATE:
                # bounded like a metric label family: evict the least-
                # decided key (a re-appearing workload just re-warms)
                lru = min(g.count, key=g.count.get, default=None)
                if lru is not None:
                    g.arms.pop(lru, None)
                    g.count.pop(lru, None)
                    g.holdoff.pop(lru, None)
                    g.last_arm.pop(lru, None)
                    g.trips.pop(lru, None)
            arms = g.arms[key] = {}
        a = arms.get(arm)
        if a is None:
            a = arms[arm] = _Arm()
        return a

    def _event_locked(self, row: dict) -> None:
        if len(self._events) >= MAX_EVENTS:
            self._events_dropped += 1
            return
        self._events.append(row)

    def _guard_locked(self, gate: str, g: _GateState, key: str,
                      favored: str, static_arm: str) -> bool:
        """Tail guard: True = trip (revert to static, take the falsifying
        samples out of the drifted arm, record the fallback event)."""
        window = int(flags.get("PX_AUTOTUNE_GUARD_WINDOW"))
        factor = float(flags.get("PX_AUTOTUNE_GUARD_FACTOR"))
        arms = g.arms.get(key) or {}
        fav, sta = arms.get(favored), arms.get(static_arm)
        if fav is None or sta is None:
            return False
        if len(fav.ring) < window or len(sta.ring) < window:
            return False
        static_med = statistics.median(sta.ring)
        level = factor * max(static_med, 1e-9)
        recent = list(fav.ring)[-window:]
        over = sum(s > level for s in recent)
        if over < GUARD_SHARE * window:
            return False
        g.holdoff[key] = int(flags.get("PX_AUTOTUNE_GUARD_HOLDOFF"))
        g.trips[key] = g.trips.get(key, 0) + 1
        g.fallbacks += 1
        # the drifted arm keeps what the guard did not falsify: after the
        # hold-off it is compared as it stood before it drifted, and trips
        # again within one window if the drift has lasted
        kept = [s for s in fav.ring if s <= level]
        if kept:
            fav.ring = deque(kept, maxlen=RING)
            fav.ewma = statistics.median(kept)
        else:
            arms[favored] = _Arm()
        cls, _, bucket = key.partition("|")
        recent_med = statistics.median(recent)
        self._event_locked({
            "time_": time.time_ns(), "query_id": "", "gate": gate,
            "plan_class": cls, "size_bucket": bucket, "arm": static_arm,
            "static_arm": static_arm, "source": "fallback",
            "model_ms": round(recent_med * 1e3, 3),
            "static_ms": round(static_med * 1e3, 3), "observed_ms": 0.0,
            "reason": f"autotune_fallback {over}/{window} recent samples > "
                      f"{factor:g}x median {static_med * 1e3:.1f}ms"})
        return True

    # ------------------------------------------------------------- decisions
    def decide(self, gate: str, plan_class: str, bucket: str,
               static_arm: str, arms: tuple) -> dict:
        """One gate decision for (plan_class, bucket): the fitted favorite
        when every arm is warm, the static default while cold or held off,
        a deterministic epsilon probe of the least-sampled other arm at the
        pacing counter's beat.  Callers gate on enabled() — this method
        assumes autotune is on."""
        key = f"{plan_class}|{bucket}"
        min_n = int(flags.get("PX_AUTOTUNE_MIN_SAMPLES"))
        eps = float(flags.get("PX_AUTOTUNE_EPSILON"))
        with self._lock:
            dec = self._decide_locked(gate, key, static_arm, tuple(arms),
                                      min_n, eps)
        dec["gate"] = gate
        dec["plan_class"] = plan_class
        dec["size_bucket"] = bucket
        dec["static_arm"] = static_arm
        if dec["source"] in ("fallback", "explore"):
            metrics.counter_inc(
                "px_autotune_decisions_total", labels={
                    "gate": gate, "source": dec["source"]},
                help_="adaptive-gate decisions by source "
                      "(model/static/cold/explore/fallback)")
        return dec

    def _decide_locked(self, gate: str, key: str, static_arm: str,
                       arms: tuple, min_n: int, eps: float) -> dict:
        g = self._gate_locked(gate)
        states = {a: self._arm_locked(g, key, a) for a in arms}
        count = g.count.get(key, 0)
        g.count[key] = count + 1
        hold = g.holdoff.get(key, 0)
        static_ms = (round(states[static_arm].cost() * 1e3, 3)
                     if static_arm in states and states[static_arm].n
                     else None)

        def _dec(arm, source, model_ms=None):
            g.last_arm[key] = arm
            return {"arm": arm, "source": source, "model_ms": model_ms,
                    "static_ms": static_ms, "n": count + 1,
                    "guard_trips": g.trips.get(key, 0)}

        if hold > 0:
            g.holdoff[key] = hold - 1
            return _dec(static_arm, "fallback")
        warm = all(s.n >= min_n for s in states.values())
        if not warm:
            # bounded cold warmup: every COLD_PROBE_PERIODth decision runs
            # the least-sampled cold arm; everything else stays static.
            # A KV-warmed model (n restored) never enters this branch —
            # the "no cold exploration burst" restart contract.
            if count % COLD_PROBE_PERIOD == COLD_PROBE_PERIOD - 1:
                cold = [a for a in arms if states[a].n < min_n]
                probe = min(cold, key=lambda a: states[a].n)
                return _dec(probe, "explore")
            return _dec(static_arm, "cold")
        costs = {a: states[a].cost() for a in arms}
        favored = min(arms, key=costs.get)
        model_ms = round(costs[favored] * 1e3, 3)
        if favored != static_arm and self._guard_locked(
                gate, g, key, favored, static_arm):
            return _dec(static_arm, "fallback", model_ms)
        period = max(2, int(round(1.0 / max(eps, 1e-6))))
        if count % period == period - 1 and len(arms) > 1:
            others = [a for a in arms if a != favored]
            probe = min(others, key=lambda a: states[a].n)
            return _dec(probe, "explore", model_ms)
        return _dec(favored, "model" if favored != static_arm else "static",
                    model_ms)

    def observe(self, gate: str, plan_class: str, bucket: str, arm: str,
                secs: float) -> None:
        """Fold one measured completion into (gate, key, arm)."""
        if secs < 0:
            return
        key = f"{plan_class}|{bucket}"
        with self._lock:
            g = self._gate_locked(gate)
            self._arm_locked(g, key, arm).observe(float(secs))

    def observe_decision(self, dec: dict, secs: float) -> None:
        """Fold the completion that a decide() dict routed (also stamps
        the measured cost onto the decision for telemetry rows)."""
        dec["observed_ms"] = round(float(secs) * 1e3, 3)
        self.observe(dec["gate"], dec["plan_class"], dec["size_bucket"],
                     dec["arm"], secs)

    def observe_last(self, gate: str, plan_class: str, bucket: str,
                     secs: float) -> None:
        """Fold a completion into whatever arm the gate key last decided —
        for call sites whose completion callback has no decision handle
        (hedge exec_done, batch wave close)."""
        key = f"{plan_class}|{bucket}"
        with self._lock:
            g = self._gate_locked(gate)
            arm = g.last_arm.get(key)
            if arm is None:
                return
            self._arm_locked(g, key, arm).observe(float(secs))

    # ----------------------------------------------------------- hedge model
    def observe_service(self, secs: float) -> None:
        """One dispatch→exec_done service time (broker completion stream):
        feeds the fleet-wide hedge floor and the hedge gate's active arm."""
        if secs < 0:
            return
        with self._lock:
            self._service.append(float(secs))
        self.observe_last(GATE_HEDGE, "dispatch", "fleet", secs)

    def _quantile_locked(self, ring, q: float) -> Optional[float]:
        if not ring:
            return None
        xs = sorted(ring)
        return xs[min(len(xs) - 1, int(q * len(xs)))]

    def hedge_floor_s(self, static_floor_s: float
                      ) -> tuple[float, Optional[dict]]:
        """The hedge deadline floor: the measured fleet service p99 (with
        headroom) instead of the fixed PL_HEDGE_MIN_MS — a fast fleet hedges
        its stragglers in tens of ms instead of waiting out a half-second
        constant tuned for another box.  The measured floor only LOWERS the
        static one (hedging later than the operator's floor would widen the
        tail the flag exists to cap)."""
        dec = self.decide(GATE_HEDGE, "dispatch", "fleet", "static",
                          ("static", "model"))
        min_n = int(flags.get("PX_AUTOTUNE_MIN_SAMPLES"))
        with self._lock:
            p99 = (self._quantile_locked(self._service, 0.99)
                   if len(self._service) >= min_n else None)
        if dec["arm"] != "model" or p99 is None:
            dec["model_ms"] = None if p99 is None else round(p99 * 1e3, 3)
            dec["static_ms"] = round(static_floor_s * 1e3, 3)
            return float(static_floor_s), dec
        floor = min(float(static_floor_s), max(1.5 * p99, 0.01))
        dec["model_ms"] = round(floor * 1e3, 3)
        dec["static_ms"] = round(static_floor_s * 1e3, 3)
        return floor, dec

    # ---------------------------------------------------- batch-window model
    def observe_arrival(self, now: Optional[float] = None) -> None:
        """One query arrived at the dispatch seam (batch-window demand)."""
        sec = int(time.time() if now is None else now)
        with self._lock:
            if self._bins and self._bins[-1][0] == sec:
                self._bins[-1][1] += 1
            else:
                self._bins.append([sec, 1])
            while self._bins and self._bins[0][0] < sec - ARRIVAL_WINDOW_S:
                self._bins.popleft()

    def arrival_qps(self, window_s: int = 10,
                    now: Optional[float] = None) -> float:
        sec = int(time.time() if now is None else now)
        with self._lock:
            n = sum(c for s, c in self._bins if s >= sec - window_s)
        return n / max(window_s, 1)

    def observe_batch_wave(self, wall_s: float, size: int) -> None:
        """One fused batch executed: its wave wall feeds the window
        controller and the batch gate's active arm."""
        if wall_s < 0:
            return
        with self._lock:
            self._waves.append(float(wall_s))
        self.observe_last(GATE_BATCH_WINDOW, "batch", "global", wall_s)

    def batch_window(self, static_window_s: float, static_max_n: int
                     ) -> tuple[float, int, Optional[dict]]:
        """The batching rendezvous parameters: window from measured wave
        RTT (half a wave — waiting longer than the work takes trades
        latency for no extra fusion), max members from the measured arrival
        rate over that window.  Static values until the model is warm; both
        outputs clamped to a 4x band around the static constants so a
        drifted fit can only mistune, never wedge, the collector."""
        dec = self.decide(GATE_BATCH_WINDOW, "batch", "global", "static",
                          ("static", "model"))
        min_n = int(flags.get("PX_AUTOTUNE_MIN_SAMPLES"))
        with self._lock:
            wave_p50 = (self._quantile_locked(self._waves, 0.5)
                        if len(self._waves) >= min_n else None)
        dec["static_ms"] = round(static_window_s * 1e3, 3)
        if dec["arm"] != "model" or wave_p50 is None:
            dec["model_ms"] = (None if wave_p50 is None
                               else round(wave_p50 * 1e3, 3))
            return float(static_window_s), int(static_max_n), dec
        window = min(max(0.5 * wave_p50, 0.25 * static_window_s),
                     4.0 * static_window_s)
        qps = self.arrival_qps()
        max_n = int(min(max(static_max_n, qps * window * 2.0),
                        4.0 * static_max_n))
        dec["model_ms"] = round(window * 1e3, 3)
        return window, max(2, max_n), dec

    # --------------------------------------------------------- sketch model
    def observe_sketch(self, backend: str, groups: int, dense_ms: float,
                       sorted_ms: float) -> None:
        """One measured dense-vs-sorted point (ops/sketch.py
        measure_update_crossover): both kernels' costs at `groups` fold
        into the kernel-choice model for `backend`."""
        self.observe(GATE_SKETCH_SORT, backend, str(int(groups)), "dense",
                     dense_ms / 1e3)
        self.observe(GATE_SKETCH_SORT, backend, str(int(groups)), "sorted",
                     sorted_ms / 1e3)

    def sketch_threshold(self, backend: str) -> Optional[int]:
        """The fitted sorted-kernel crossover for `backend`: the smallest
        measured group count where the sorted kernel beats the dense one,
        or None while unmeasured (callers keep the static default).  The
        sketch dispatch happens at kernel-trace time and is baked into the
        compiled program, so this gate is model-only — no per-query
        exploration (probing would churn the jit cache), the fit comes from
        an explicit run of `ops/sketch.measure_update_crossover`, which no
        caller makes today (ROADMAP D7)."""
        min_n = int(flags.get("PX_AUTOTUNE_MIN_SAMPLES"))
        fitted = None
        with self._lock:
            g = self._gates.get(GATE_SKETCH_SORT)
            if g is not None:
                for key, arms in g.arms.items():
                    cls, _, bucket = key.partition("|")
                    if cls != backend or not bucket.isdigit():
                        continue
                    d, s = arms.get("dense"), arms.get("sorted")
                    if (d is None or s is None or d.n < min_n
                            or s.n < min_n or s.ewma >= d.ewma):
                        continue
                    gval = int(bucket)
                    if fitted is None or gval < fitted:
                        fitted = gval
            if fitted is not None and \
                    self._sketch_fit.get(backend) != fitted:
                self._sketch_fit[backend] = fitted
                self._event_locked({
                    "time_": time.time_ns(), "query_id": "",
                    "gate": GATE_SKETCH_SORT, "plan_class": backend,
                    "size_bucket": str(fitted), "arm": "sorted",
                    "static_arm": "dense", "source": "model",
                    "model_ms": 0.0, "static_ms": 0.0, "observed_ms": 0.0,
                    "reason": f"fitted sort crossover {fitted} groups"})
        return fitted

    # ------------------------------------------------------------ telemetry
    def record_row(self, dec: dict, query_id: str = "") -> None:
        """Push a completed decision straight into the event buffer — for
        call sites whose stats dict never reaches a telemetry sink (the
        join gate runs inside repartition-stage executors whose stats are
        consumed, not forwarded).  Marks the decision so rows_from_stats
        won't emit it twice when the stats DO flow."""
        dec["_recorded"] = True
        row = {
            "time_": time.time_ns(), "query_id": str(query_id),
            "gate": str(dec.get("gate", "")),
            "plan_class": str(dec.get("plan_class", "")),
            "size_bucket": str(dec.get("size_bucket", "")),
            "arm": str(dec.get("arm", "")),
            "static_arm": str(dec.get("static_arm", "")),
            "source": str(dec.get("source", "")),
            "model_ms": float(dec.get("model_ms") or 0.0),
            "static_ms": float(dec.get("static_ms") or 0.0),
            "observed_ms": float(dec.get("observed_ms") or 0.0),
            "reason": str(dec.get("reason", "")),
        }
        with self._lock:
            self._event_locked(row)

    def drain_rows(self) -> list[dict]:
        """Pending event rows (fallback trips, fitted-threshold changes)
        for self_telemetry.autotune — drained on the self-metrics cron."""
        with self._lock:
            out, self._events = self._events, []
            dropped, self._events_dropped = self._events_dropped, 0
        if dropped:
            metrics.counter_inc(
                "px_autotune_events_dropped_total", float(dropped),
                help_="autotune event rows dropped by a full bounded "
                      "buffer")
        return out

    def snapshot(self) -> dict:
        """Per-gate model state for tests and ops surfaces."""
        out = {}
        with self._lock:
            for gate, g in self._gates.items():
                out[gate] = {
                    "keys": len(g.arms),
                    "decisions": sum(g.count.values()),
                    "fallbacks": g.fallbacks,
                    "samples": sum(a.n for arms in g.arms.values()
                                   for a in arms.values()),
                }
        return out

    # ---------------------------------------------------------- persistence
    def save_kv(self, kv) -> None:
        """Persist every arm's (n, ewma, dev) under autotune/model (rings
        stay volatile: the tail guard must re-earn its window from live
        traffic after a restart, not from another epoch's tail)."""
        with self._lock:
            gates = {
                gate: {
                    key: {arm: {"n": a.n, "ewma": a.ewma, "dev": a.dev}
                          for arm, a in arms.items()}
                    for key, arms in g.arms.items()
                }
                for gate, g in self._gates.items()
            }
        try:
            kv.set_json(KV_KEY, {"v": 1, "gates": gates})
        except Exception:
            metrics.counter_inc(
                "px_autotune_persist_errors_total",
                help_="failed attempts to persist the autotune model to "
                      "the broker KV")

    def load_kv(self, kv) -> bool:
        """Recall a persisted model (broker restart).  A corrupt record is
        counted and ignored — the model starts cold on static defaults,
        never fails the broker."""
        try:
            doc = kv.get_json(KV_KEY)
            if doc is None:
                return False
            if int(doc["v"]) != 1:
                raise ValueError(f"unknown model version {doc['v']}")
            gates = doc["gates"]
            loaded: dict[str, _GateState] = {}
            for gate, keys in gates.items():
                g = _GateState()
                for key, arms in keys.items():
                    g.arms[str(key)] = {
                        str(arm): _Arm(int(st["n"]), float(st["ewma"]),
                                       float(st["dev"]))
                        for arm, st in arms.items()}
                loaded[str(gate)] = g
        except Exception:
            metrics.counter_inc(
                "px_autotune_recall_errors_total",
                help_="persisted autotune model records skipped at broker "
                      "startup (corrupt or unknown version)")
            return False
        with self._lock:
            for gate, g in loaded.items():
                self._gates[gate] = g
            self.loaded_from_kv = True
        return True

    def reset_for_testing(self) -> None:
        with self._lock:
            self._gates.clear()
            self._events.clear()
            self._events_dropped = 0
            self._service.clear()
            self._waves.clear()
            self._bins.clear()
            self._sketch_fit.clear()
            self.loaded_from_kv = False


#: the process-wide model (gates live in executor/broker/serving seams all
#: over the process; one model sees the whole completion stream — the same
#: singleton shape as table/heat.MODEL)
MODEL = AutotuneModel()


# -------------------------------------------------------- stats/row plumbing


def decisions_from_stats(stats: dict) -> list[dict]:
    """Every decision dict a query's stats carry: the broker/cluster-level
    list plus each agent executor's list."""
    out = [d for d in (stats.get("autotune") or []) if isinstance(d, dict)]
    for s in (stats.get("agents") or {}).values():
        if isinstance(s, dict):
            out.extend(d for d in (s.get("autotune") or [])
                       if isinstance(d, dict))
    return out


def rows_from_stats(stats: dict, query_id: str,
                    now_ns: Optional[int] = None) -> list[dict]:
    """stats["autotune"] decisions → self_telemetry.autotune rows."""
    now_ns = int(now_ns if now_ns is not None else time.time_ns())
    rows = []
    for d in decisions_from_stats(stats):
        if d.get("_recorded"):
            continue
        rows.append({
            "time_": now_ns,
            "query_id": str(query_id),
            "gate": str(d.get("gate", "")),
            "plan_class": str(d.get("plan_class", "")),
            "size_bucket": str(d.get("size_bucket", "")),
            "arm": str(d.get("arm", "")),
            "static_arm": str(d.get("static_arm", "")),
            "source": str(d.get("source", "")),
            "model_ms": float(d.get("model_ms") or 0.0),
            "static_ms": float(d.get("static_ms") or 0.0),
            "observed_ms": float(d.get("observed_ms") or 0.0),
            "reason": str(d.get("reason", "")),
        })
    return rows


def summary_from_stats(stats: dict) -> str:
    """Compact per-query provenance: one "gate:arm(source)" token per
    decision, for profile rows and EXPLAIN ANALYZE."""
    toks = []
    for d in decisions_from_stats(stats):
        tok = (f"{d.get('gate', '?')}:{d.get('arm', '?')}"
               f"({d.get('source', '?')})")
        if tok not in toks:
            toks.append(tok)
    return " ".join(toks[:16])
