"""One general generator of query schedules, and the closed loop that sends
them.

A traffic mix (traffic/<name>.json) is data: a pattern of scripts, a cycle of
time bounds (`start_offset_s`: seconds after the table's first row) and how
often the warm-up sends each (script, bound) pair.  The schedule is a fixed
sequence that repeats until the time is up: query i is script i of the
pattern with bound i of the cycle.  --seed turns the cycle of bounds to
another starting point: the same sizes in another order.  The pattern of
scripts never moves against the query counter, because the router paces its
probes by that counter (engine/autotune.py) and a shifted pattern would hand
the probes to another script: other work, not other order.
"""
from __future__ import annotations

import os
import time

import numpy as np

from data import HERE, SEC, load_json

SCRIPT_DIR = os.path.join(HERE, "scripts")


def load_script(name: str) -> dict:
    """{"name", "text", and the script's meta (table, columns_read)}."""
    with open(os.path.join(SCRIPT_DIR, name + ".pxl")) as f:
        text = f.read()
    return dict(load_json("scripts", name), name=name, text=text)


def start_time_ns(config: dict, bound) -> int:
    return int(config["time_base_ns"]) + int(bound) * SEC


class Schedule:
    """Query i of the window, and the fixed warm-up before it."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix, self.config = mix, config
        self.pattern = [p["script"] for p in mix["pattern"]]
        self.bounds = mix["start_offset_s"]
        self.offset = int(np.random.default_rng(seed).integers(
            0, len(self.bounds)))
        self.scripts = {n: load_script(n) for n in sorted(set(self.pattern))}

    def _query(self, script: str, bound) -> dict:
        start = start_time_ns(self.config, bound)
        return {"script": script, "bound": bound, "start_time": start,
                "text": self.scripts[script]["text"].replace(
                    "__START_TIME__", str(start))}

    def query(self, i: int) -> dict:
        return self._query(self.pattern[i % len(self.pattern)],
                           self.bounds[(i + self.offset) % len(self.bounds)])

    def warmup(self) -> list:
        """The same queries in every run, whatever the seed: every (script,
        bound) pair the window can send, `warmup_each_pair` times in a row,
        so that each is compiled on whichever arm the router takes."""
        scripts = list(dict.fromkeys(self.pattern))
        return [self._query(s, b) for b in self.bounds for s in scripts
                for _ in range(int(self.mix["warmup_each_pair"]))]


def send(client, q: dict, compiles=None) -> dict:
    """One execute_script through the served path, timed from the client.
    The record keeps the decoded answer for the comparison after the window."""
    rec = {"script": q["script"], "bound": q["bound"],
           "start_time": q["start_time"], "t0_unix_ns": time.time_ns()}
    c0 = compiles() if compiles else 0
    t0 = time.perf_counter()
    try:
        out = client.execute_script(q["text"])["out"]
        rec["wall_ms"] = (time.perf_counter() - t0) * 1e3
        rec["answer"] = out.to_pandas()
        rec["stats"] = out.exec_stats
    except Exception as e:  # a failed or shed query is a result, not a crash
        rec["wall_ms"] = (time.perf_counter() - t0) * 1e3
        rec["error"] = f"{type(e).__name__}: {e}"
    if compiles:
        rec["backend_compiles"] = compiles() - c0
    return rec


def closed_loop(client, schedule: Schedule, seconds: float,
                between=None, compiles=None) -> tuple[list, float]:
    """Send query after query for `seconds`; a query that has started is
    finished and counted.  `between(elapsed_s)` runs between two queries and
    returns the seconds it took, which the window is given back;
    `compiles()` is jax's count of backend compiles so far.  Returns
    the records and the window's length."""
    recs = []
    t_start = time.perf_counter()
    deadline = t_start + seconds
    i = 0
    while (now := time.perf_counter()) < deadline:
        if between is not None:
            deadline += between(now - t_start)
        rec = send(client, schedule.query(i), compiles)
        rec["i"] = i
        recs.append(rec)
        i += 1
    return recs, time.perf_counter() - t_start
