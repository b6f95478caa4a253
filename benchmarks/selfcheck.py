#!/usr/bin/env python3
"""Checks of the benchmark's own files, run by hand and in the rehearsal:

    python benchmarks/selfcheck.py            # files and the recorded trace
    JAX_PLATFORMS=cpu python benchmarks/selfcheck.py --rehearse <workload>
        [--trace 1] [--chips N]

The first loads every JSON under configs/, traffic/, scripts/ and
BENCHMARK.json, holds names and units to the allowed characters and lengths,
checks that every per-layer metric has a reader and moves an end-to-end
metric that each of its workloads reports, and reduces the small recorded
trace in testdata/ to the busy and idle numbers written beside it.  The second
drives a whole run at a tiny size on whatever JAX finds (the CPU here); its
last line names that platform, and it is never a measurement.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
#: the plane a host trace has in the device's place (rehearsals only); the
#: lines read there are this process's Python threads, which the profiler
#: names as the kernel does (/proc/self/comm): their JAX calls stand in for
#: the device's operations
HOST_PLANE = "/host:CPU"
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def check_files() -> list:
    errs = []
    for path in glob.glob(os.path.join(HERE, "*", "*.json")):
        try:
            with open(path) as f:
                json.load(f)
        except ValueError as e:
            errs.append(f"{path}: {e}")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    cells = {w["name"]: w for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for x in b[kind]:
            if not NAME.match(x["name"]):
                errs.append(f"{kind}: bad name {x['name']!r}")
    for m in b["end_to_end"] + b["per_layer"]:
        if not UNIT.match(m["unit"]):
            errs.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["source"] not in SOURCES:
            errs.append(f"{m['name']}: bad source {m['source']!r}")
        if m["better"] not in ("lower", "higher"):
            errs.append(f"{m['name']}: bad better {m['better']!r}")
    if "setup_s" not in e2e:
        errs.append("end_to_end lacks setup_s")
    for m in b["end_to_end"]:
        if not 0.01 <= m["bound"] <= 0.25:
            errs.append(f"{m['name']}: bound {m['bound']} outside 1%..25%")
    for c in b["configs"]:
        path = os.path.join(ROOT, c["file"])
        if not os.path.isfile(path):
            errs.append(f"config {c['name']}: no file {c['file']}")
            continue
        with open(path) as f:
            cfg = json.load(f)
        for key in c["reduced"]:
            if not NAME.match(key) or key not in cfg:
                errs.append(f"config {c['name']}: reduced key {key!r} is not "
                            "a key of its file")
        if len(c["source"]) > 200 or len(c["why"]) > 200:
            errs.append(f"config {c['name']}: source or why over 200 chars")
    for w in b["workloads"]:
        if w["config"] not in configs:
            errs.append(f"cell {w['name']}: unknown config {w['config']}")
        mix_path = os.path.join(HERE, "traffic", w["traffic"] + ".json")
        if not os.path.isfile(mix_path):
            errs.append(f"cell {w['name']}: no traffic file {mix_path}")
            continue
        with open(mix_path) as f:
            mix = json.load(f)
        for p in mix["pattern"]:
            for ext in (".pxl", ".json"):
                if not os.path.isfile(os.path.join(HERE, "scripts",
                                                   p["script"] + ext)):
                    errs.append(f"traffic {w['traffic']}: script "
                                f"{p['script']}{ext} is missing")
        if len(w["why"]) > 200 or w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']}: why over 200 chars or bad chips")
    for name in {json.load(open(p))["reference"] for p in
                 glob.glob(os.path.join(HERE, "scripts", "*.json"))}:
        if not os.path.isfile(os.path.join(HERE, "references", name + ".py")):
            errs.append(f"no reference file for {name!r}")
    for m in b["per_layer"]:
        if not os.path.isfile(os.path.join(HERE, "metrics",
                                           m["name"] + ".py")):
            errs.append(f"per-layer metric {m['name']}: no reader")
        if m["moves"] not in e2e:
            errs.append(f"{m['name']}: moves {m['moves']!r}, which is no "
                        "end-to-end metric")
            continue
        moved_in = e2e[m["moves"]].get("workloads", list(cells))
        for w in m.get("workloads", list(cells)):
            if w not in cells:
                errs.append(f"{m['name']}: unknown workload {w!r}")
            elif w not in moved_in:
                errs.append(f"{m['name']}: {w} does not report {m['moves']}")
    with open(os.path.join(HERE, "peaks.json")) as f:
        for kind, row in json.load(f).items():
            if "source" not in row or "hbm_bytes_per_s" not in row:
                errs.append(f"peaks.json: {kind!r} lacks a source or a peak")
    return errs


def check_trace() -> list:
    """The recorded planes reduce to the numbers kept beside them."""
    sys.path.insert(0, HERE)
    import tracered

    with open(os.path.join(HERE, "testdata", "planes_small.json")) as f:
        rec = json.load(f)
    planes = {"devices": {k: [tuple(e) for e in v]
                          for k, v in rec["devices"].items()}, "marks": []}
    got = tracered.reduce_trace(planes, rec["lo_ns"], rec["hi_ns"],
                                [tuple(s) for s in rec["host_spans"]])
    errs = []
    for key in ("busy_s", "window_s"):
        if abs(got[key] - rec["expect"][key]) > 1e-9:
            errs.append(f"trace {key}: {got[key]!r}, expected "
                        f"{rec['expect'][key]!r}")
    if got["device_ops"][0][0] != rec["expect"]["top_op"]:
        errs.append(f"trace top op: {got['device_ops'][0]}")
    if got["idle_gaps"][0][0] != rec["expect"]["longest_gap"]:
        errs.append(f"trace longest gap: {got['idle_gaps'][0]}")
    # a second, hand-made case with known numbers: two ops that overlap,
    # one nested, in a window of 100 ns
    hand = {"devices": {"/device:TPU:0": [(10, 40, "while.1"),
                                          (15, 25, "fusion.2"),
                                          (60, 70, "fusion.2")]},
            "marks": []}
    r = tracered.reduce_trace(hand, 0, 100, [(0, 100, "a.query"),
                                             (38, 62, "a.exec")])
    if (abs(r["busy_s"] - 40e-9) > 1e-15
            or r["idle_gaps"][:2] != [["a.query", 30e-9], ["a.exec", 20e-9]]
            or r["idle_gaps"][3:] != [["total:a.query", 40e-9],
                                      ["total:a.exec", 20e-9]]
            or r["device_ops"][0] != ["while.1", 30e-9]):
        errs.append(f"hand-made trace reduced to {r}")
    # a span in which the device ran nothing is a reading, not an error:
    # no plane at all, or a plane without an operation.  Busy 0, no ops,
    # and the span as one gap: named by the stretch its middle lies in,
    # its totals split over the host's spans
    hosts = [(0, 100, "a.query"), (38, 62, "a.exec")]
    idle = {"busy_s": 0.0, "window_s": 100e-9, "device_ops": [],
            "idle_gaps": [["a.exec", 100e-9], ["total:a.query", 76e-9],
                          ["total:a.exec", 24e-9]]}
    for devices in ({}, {"/device:TPU:0": []}):
        r = tracered.reduce_trace({"devices": devices, "marks": []}, 0, 100,
                                  hosts)
        if r != idle:
            errs.append(f"device-less trace {devices} reduced to {r}")
    # a cell of four chips with planes for two: the others were idle and
    # count 0 in the mean
    hand["devices"]["/device:TPU:2"] = [(0, 20, "fusion.2")]
    r = tracered.reduce_trace(hand, 0, 100, hosts, n_devices=4)
    if abs(r["busy_s"] - (40e-9 + 20e-9) / 4) > 1e-15:
        errs.append(f"four devices, two planes: busy_s {r['busy_s']!r}")
    return errs


def rehearse(workload: str, rows: int, seconds: float, traced: bool,
             chips: int = 1) -> int:
    sys.path.insert(0, HERE)
    import run
    import tracered

    jax = run.import_jax()
    bench = run.load_benchmark()
    cell, cfg = run.find_cell(bench, workload)
    with open(os.path.join(ROOT, cfg["file"])) as f:
        config = json.load(f)
    config["rows"] = rows
    for t in config["tables"]:
        t.pop("max_bytes", None)
    devices = jax.devices()[:chips]
    if len(devices) < chips:
        raise SystemExit(f"selfcheck: --chips {chips}, and JAX has "
                         f"{len(devices)} device(s) (on the CPU: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    dev = devices[0]
    if dev.platform != "tpu":  # a host trace has no device plane to reduce
        tracered.DEVICE_PREFIX = HOST_PLANE
        with open("/proc/self/comm") as f:
            tracered.OPS_LINE = f.read().strip()
    result = run.run_cell(
        bench, cell, cfg, 2147483659, seconds, traced,
        os.path.join(HERE, "out", "rehearsal"), run.Phases(), jax, devices,
        config=config,
        peaks={dev.device_kind: {"hbm_bytes_per_s": 1e11}})
    print(f"REHEARSAL on platform={dev.platform} at rows={rows}: not a "
          "measurement, never reported")
    print(json.dumps(result))
    return 0 if result["correct"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", metavar="WORKLOAD")
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--chips", type=int, default=1,
                    help="rehearse on the first N devices, whatever the "
                         "cell's `chips` (the Agent gets n_devices=N)")
    args = ap.parse_args()
    if args.rehearse:
        return rehearse(args.rehearse, args.rows, args.seconds,
                        bool(args.trace), args.chips)
    errs = check_files() + check_trace()
    for e in errs:
        print("selfcheck:", e, file=sys.stderr)
    print(f"selfcheck: {'FAILED' if errs else 'ok'} ({len(errs)} finding(s))")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
