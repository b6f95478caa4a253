"""Two things `benchmarks/README.md` states of the harness the driver runs
and nothing held: what a per-layer reader does with a window that has
nothing in it, and what `--seed` may and may not move in a traffic mix.
The files under `benchmarks/` are read where they stand, through the same
`load_module` / `Schedule` that `run.py` uses.
"""
import json
import numbers
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (os.path.join(BENCH, "metrics"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import data as datagen  # noqa: E402  benchmarks/data.py
import traffic  # noqa: E402  benchmarks/traffic.py

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CONFIG_FILES = {c["name"]: c["file"] for c in BENCHMARK["configs"]}
#: traffic file -> the configuration of the cell that sends it
MIXES = {w["traffic"]: w["config"] for w in BENCHMARK["workloads"]}
SEEDS = [3, 77, 2147483659, 2900000011, 5, 11, 13, 17]


def config_of(name: str) -> dict:
    with open(os.path.join(ROOT, CONFIG_FILES[name])) as f:
        return json.load(f)


@pytest.mark.parametrize("name", [m["name"] for m in BENCHMARK["per_layer"]])
def test_reader_of_an_empty_window_returns_none_or_a_number(name):
    """"A reader that finds nothing returns `None` and the metric is left
    out of the line": `run` as `run_cell` builds it, for a window in which
    no query finished and no trace was taken."""
    config = config_of(BENCHMARK["configs"][0]["name"])
    run = {"config": config, "scripts": {}, "queries": [], "walls_ms": [],
           "window_s": 50.0, "compiles_in_window": 0,
           "peaks": {"hbm_bytes_per_s": 1e11}, "trace": None}
    value = datagen.load_module("metrics", name).read(run)
    assert value is None or (isinstance(value, numbers.Real)
                             and not isinstance(value, bool)), value


@pytest.mark.parametrize("mix_name", sorted(MIXES))
def test_seed_turns_the_cycle_of_starts_and_nothing_else(mix_name):
    """The same `--seed` gives the same schedule; another seed gives the
    same scripts at the same positions (the router paces its probes by the
    query counter) with the cycle of starts turned; the warm-up is the
    same whatever the seed, and sends every pair the window can send."""
    mix = datagen.load_json("traffic", mix_name)
    config = config_of(MIXES[mix_name])
    by_offset: dict = {}
    for seed in SEEDS:
        by_offset.setdefault(
            traffic.Schedule(mix, config, seed).offset, seed)
    assert len(by_offset) >= 2, by_offset
    (off_a, seed_a), (off_b, seed_b) = sorted(by_offset.items())[:2]
    a = traffic.Schedule(mix, config, seed_a)
    b = traffic.Schedule(mix, config, seed_b)
    n = 3 * len(a.pattern) * len(a.bounds)

    again = traffic.Schedule(mix, config, seed_a)
    assert [again.query(i) for i in range(n)] == \
        [a.query(i) for i in range(n)]

    qa = [a.query(i) for i in range(n + len(a.bounds))]
    qb = [b.query(i) for i in range(n)]
    assert [q["script"] for q in qb] == [q["script"] for q in qa[:n]]
    turn = off_b - off_a
    assert turn % len(a.bounds)
    assert [q["bound"] for q in qb] == \
        [q["bound"] for q in qa[turn:turn + n]]
    for q in qb:
        assert q["start_time"] == traffic.start_time_ns(config, q["bound"])
        assert str(q["start_time"]) in q["text"]
        assert "__START_TIME__" not in q["text"]

    warm = a.warmup()
    assert warm == b.warmup()
    scripts = list(dict.fromkeys(a.pattern))
    each = int(mix["warmup_each_pair"])
    assert len(warm) == each * len(scripts) * len(a.bounds)
    sent = {(q["script"], q["bound"]) for q in qa}
    assert sent <= {(q["script"], q["bound"]) for q in warm}
