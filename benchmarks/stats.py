"""What the benchmark reads from the program: one digest per answer's
exec_stats (where it ran, under which routing decision, what it uploaded),
jax's own compile events, and the percentile the end-to-end metrics use.
Copied from chip_smoke.read_answer / _CompileLog (PR 21)."""
from __future__ import annotations

import math


def digest(stats: dict) -> dict:
    """One answer's exec_stats, flat.  `engine` is "device" where a chain
    ran on the accelerator route, else "cpu"; `arm`/`source` are the
    router's decision for the largest input, `plan_class` and
    `size_bucket` the model key it was taken under (engine/autotune.py)."""
    agents = stats.get("agents") or {}
    agent = next(iter(agents.values()), {}) if agents else {}
    mv = agent.get("matview") or {}
    dev = agent.get("device") or {}
    engines = dev.get("engines") or {}
    decisions = [d for d in agent.get("autotune") or []
                 if d.get("gate") == "cpu_crossover"]
    # the decision for the largest input: the scan of the cell's table
    big = max(decisions, key=lambda d: int(
        str(d.get("size_bucket", "4^0"))[2:] or 0), default=None)
    phases = stats.get("phases") or {}
    profile = stats.get("profile") or {}
    return {
        "engine": "device" if engines.get("device_chain") else "cpu",
        "engines": engines,
        "platform": dev.get("platform"),
        "device_kind": dev.get("device_kind"),
        "arm": big["arm"] if big else None,
        "source": big["source"] if big else None,
        "plan_class": big.get("plan_class") if big else None,
        "size_bucket": big["size_bucket"] if big else None,
        "decision_n": big.get("n") if big else None,
        "decisions": [[d.get("arm"), d.get("source"), d.get("size_bucket"),
                       d.get("n"), d.get("plan_class")] for d in decisions],
        "rows_scanned": int(agent.get("rows_scanned", 0)),
        "matview_hit": bool(mv.get("hit")),
        "plan_cache_hit": bool((stats.get("fastpath") or {})
                               .get("plan_cache_hit")),
        "resident_feeds": int(agent.get("resident_feeds", 0)),
        "h2d_bytes": int(agent.get("h2d_bytes", 0)),
        "compile_ns": int(phases.get("compile_ns", 0)),
        "plan_split_ns": int(phases.get("plan_split_ns", 0)),
        "exec_ns": int(phases.get("exec_ns", 0)),
        "merge_ns": int(phases.get("merge_ns", 0)),
        "broker_wall_ns": int(profile.get("wall_ns", 0)),
        "hedged": int((stats.get("fault") or {}).get("hedged", 0)),
        "ran_on": profile.get("ran_on", ""),
    }


def mark_probes(recs: list) -> None:
    """Set digest["probe"] on every record, in sending order (warm-up, then
    window).  A probe is a query in which the router explored its other
    arm.  Where the explored arm outlasts the broker's straggler deadline
    (services/broker.py, PL_HEDGE_*), the broker sends the agent a hedged
    duplicate, which takes the router's next decision and answers first;
    the explore's decision is discarded with the loser's stats, and the
    router's counter shows it only as a step of two from the query before
    under the same model key, (`plan_class`, `size_bucket`): the router
    counts each key's decisions apart (`hedged` in the digest counts the
    duplicate)."""
    last: dict = {}
    for r in recs:
        d = r.get("digest")
        if not d:
            continue
        probe = d["source"] == "explore"
        key = (d.get("plan_class"), d["size_bucket"])
        mine = [x for x in d["decisions"] if (x[4], x[2]) == key]
        if mine and mine[0][3] is not None:
            prev = last.get(key)
            if prev is not None and mine[0][3] - prev >= 2:
                probe = True
            last[key] = mine[-1][3]
        d["probe"] = probe


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q of the
    sample at or under it."""
    xs = sorted(values)
    return xs[max(0, min(len(xs) - 1, math.ceil(q * len(xs)) - 1))]


def median(values: list) -> float:
    return percentile(values, 0.5)


class CompileLog:
    """Compile seconds, backend compiles and persistent-cache hits, from
    jax's monitoring events."""

    DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration",
                 "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.secs = dict.fromkeys(self.DURATIONS, 0.0)
        self.backend = 0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_kw):
        if event in self.secs:
            self.secs[event] += secs
        if event == self.DURATIONS[2]:
            self.backend += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def seconds(self) -> float:
        return sum(self.secs.values())

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds(), "backend_compiles": self.backend,
                "cache_hits": self.hits, "cache_misses": self.misses}
