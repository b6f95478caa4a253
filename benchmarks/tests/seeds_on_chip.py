#!/usr/bin/env python3
"""Many seeds of one cell in one process, on the chip: the program's
readings of every number compared, and each control's on the same sample.

    python benchmarks/tests/seeds_on_chip.py --workload <name> --seeds 12 \
        --first-seed <n> --seconds 6

Set-up is most of a run, so the dozen seeds that a limit is read from share
one backend start and one compile; each seed still makes its own tables,
store, broker and agent and runs the fixed warm-up.  The router's model is
the process's (engine/autotune.py), so it stays warm from seed to seed:
these runs read `correct`, never a latency.
"""
import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def main() -> int:
    import run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147484000)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", default=os.path.join(HERE, "out", "seeds"))
    args = ap.parse_args()

    jax = run.import_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("seeds_on_chip: no TPU", file=sys.stderr)
        return 2
    bench = run.load_benchmark()
    cell, cfg = run.find_cell(bench, args.workload)
    bad = 0
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        res = run.run_cell(bench, cell, cfg, seed, args.seconds, False,
                           args.out, run.Phases(), jax, devices[:1],
                           with_control=True)
        gc.collect()
        row = {"seed": seed, "correct": res["correct"],
               "attempted": res["attempted"], "compared": res["compared"],
               "program": {n: v["value"] for n, v in res["checks"].items()},
               "controls_ok": {c: v["ok"] for c, v
                               in res["control"].items()},
               "controls": {c: {n: x["value"] for n, x
                                in v["numbers"].items()}
                            for c, v in res["control"].items()}}
        print("SEED " + json.dumps(row), flush=True)
        bad += (not res["correct"]) or any(row["controls_ok"].values())
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
