"""The comparison that decides `correct`: each sampled answer of the window
against the plain reference of its own script and time bound, every number
beside its limit."""
from __future__ import annotations

import numpy as np
import pandas as pd

from data import load_module

#: distinct (script, bound) pairs compared in one run; every answer of the
#: window that carries a sampled pair is compared
SAMPLE_KEYS = 6


def load_reference(name: str):
    return load_module("references", name)


def controls(scripts: dict) -> list:
    """The stand-ins that the references of these scripts know."""
    return sorted({c for s in scripts.values()
                   for c in load_reference(s["reference"]).CONTROLS})


def joined(got: pd.DataFrame, ref_df: pd.DataFrame, keys: list):
    """(reference merged with the answer, rows on either side with no
    partner)."""
    got = got.copy()
    if "time_" in keys:
        got["time_"] = pd.to_numeric(got["time_"]).astype("int64")
    m = ref_df.merge(got, on=keys, how="outer", suffixes=("_ref", "_got"),
                     indicator=True)
    unmatched = int((m["_merge"] != "both").sum()) + abs(len(got)
                                                         - len(ref_df))
    return m, unmatched


def max_rel(m: pd.DataFrame, col: str) -> float:
    ref, got = m[f"{col}_ref"].to_numpy(), m[f"{col}_got"].to_numpy()
    return float(np.max(np.abs(got - ref) / np.abs(ref)))


def sample_keys(recs: list, seed: int) -> list:
    """A sample of the window's (script, bound) pairs, drawn from the seed:
    every script, and both sides of the router where the window has both."""
    rng = np.random.default_rng(seed + 1)
    answered = [r for r in recs if "answer" in r]
    strata: dict = {}
    for r in answered:
        strata.setdefault((r["script"], r["digest"]["engine"]), set()).add(
            (r["script"], r["bound"]))
    picked: list = []
    for _, keys in sorted(strata.items()):
        ks = sorted(keys)
        k = ks[int(rng.integers(0, len(ks)))]
        if k not in picked:
            picked.append(k)
    rest = sorted({(r["script"], r["bound"]) for r in answered}
                  - set(picked))
    rng.shuffle(rest)
    return (picked + [tuple(k) for k in rest])[:max(SAMPLE_KEYS, len(picked))]


def check_window(recs: list, data: dict, config: dict, scripts: dict,
                 seed: int, stand_in: str = "") -> dict:
    """Worst value of every number compared, beside its limit, over the
    sampled answers.  With `stand_in`, that lowered reference of each
    script (its module's CONTROLS) answers in the program's place: the
    control."""
    worst: dict = {}
    compared = 0
    for script_name, bound in sample_keys(recs, seed):
        script = scripts[script_name]
        mod = load_reference(script["reference"])
        picked = [r for r in recs if "answer" in r
                  and (r["script"], r["bound"]) == (script_name, bound)]
        ref = mod.reference(data, config, script, picked[0]["start_time"])
        if stand_in:
            ctl, _ = mod.reference(data, config, script,
                                   picked[0]["start_time"], stand_in)
            picked = [dict(picked[0], answer=ctl)]
        for r in picked:
            compared += 1
            for name, (value, limit) in mod.compare(r["answer"], ref,
                                                    config).items():
                if value != value:  # NaN: no number, so no pass
                    value = float("inf")
                if name not in worst or value > worst[name]["value"]:
                    worst[name] = {"value": value, "limit": limit}
            r["compared"] = True
    ok = compared > 0 and all(w["value"] <= w["limit"]
                              for w in worst.values())
    return {"ok": ok, "compared": compared, "numbers": worst}
