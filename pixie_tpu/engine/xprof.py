"""Measured device occupancy from a real profiler trace.

``measure_device_busy(fn)`` runs ``fn`` under ``jax.profiler.trace`` and
parses the resulting ``*.xplane.pb`` files DIRECTLY (a minimal protobuf
wire-format walk — no tensorflow/tensorboard dependency) to compute
``device_busy_frac``: the union of device-event intervals divided by the
traced wall time.

Why this exists (VERDICT r5, Tailwind's lesson in PAPERS.md): the previous
occupancy metric divided a *serialized analyze-mode* device-time sum by the
*pipelined production* wall time and clamped at 1.0 — structurally incapable
of being falsified.  This module measures the production run itself: every
interval comes from the profiler's own device timeline, overlapping events
union (they cannot double-count), and the raw numerator/denominator ship
with the ratio.

Plane selection:
  * accelerator planes (``/device:TPU:N`` …) when present — the honest
    measure on real hardware;
  * otherwise the XLA-CPU executor's ``TfrtCpuExecutable::Execute`` events
    on the host plane (the "device" of the routed interactive path is
    XLA-CPU), so CPU-only runs still report a real measured number.

The xplane schema walked here (XSpace→XPlane→XLine→XEvent) is stable across
TF/JAX releases — it is the on-disk format TensorBoard's profiler plugin
reads; field numbers from tsl/profiler/protobuf/xplane.proto.
"""
from __future__ import annotations

import glob
import os
import tempfile
import time


# ------------------------------------------------------- protobuf wire walk


def _varint(b, i):
    r = 0
    s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if not x & 0x80:
            return r, i
        s += 7


def _fields(b):
    """Yield (field_number, wire_type, value) over a length-delimited buffer."""
    i = 0
    n = len(b)
    while i < n:
        tag, i = _varint(b, i)
        fn, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v = b[i:i + ln]
            i += ln
        elif wt == 5:
            v = b[i:i + 4]
            i += 4
        elif wt == 1:
            v = b[i:i + 8]
            i += 8
        else:  # groups (3/4) never appear in xplane
            raise ValueError(f"unsupported wire type {wt}")
        yield fn, wt, v


# XSpace: planes=1 | XPlane: name=2 lines=3 event_metadata=4
# XLine: name=2 timestamp_ns=3 events=4 | XEvent: metadata_id=1 offset_ps=2
# duration_ps=3 | XEventMetadata map entry: key=1 value=2; value.name=2

#: XLA-CPU executes its HLO thunks on named thread pools — these line-name
#: prefixes carry the actual kernel compute (the `python` line only shows
#: the ~0.3 ms async dispatch, which is NOT occupancy)
_XLA_CPU_LINE_PREFIX = "tf_XLA"
#: non-compute events that appear on the compute-pool lines: blocking waits
#: for other threads' thunks and the profiler's own listener bookkeeping
_CPU_SKIP_SUBSTR = ("wait for completion", "ThreadpoolListener")


def _plane_intervals(plane: bytes, want_cpu_exec: bool):
    """→ list of (start_ps, end_ps) event intervals for one XPlane.

    want_cpu_exec selects HLO-thunk execution events on the XLA-CPU compute
    thread-pool lines (host-plane fallback — the "device" of a routed
    interactive query is XLA-CPU); otherwise every event on the plane counts
    (device planes carry only device activity)."""
    skip_ids = set()
    if want_cpu_exec:
        for fn, _wt, v in _fields(plane):
            if fn != 4:
                continue
            k = name = None
            for f2, _w2, v2 in _fields(v):
                if f2 == 1:
                    k = v2
                elif f2 == 2:
                    for f3, _w3, v3 in _fields(v2):
                        if f3 == 2:
                            name = v3.decode(errors="replace")
            if k is not None and name is not None \
                    and any(s in name for s in _CPU_SKIP_SUBSTR):
                skip_ids.add(k)
    out = []
    for fn, _wt, v in _fields(plane):
        if fn != 3:  # XLine
            continue
        line_ts_ns = 0
        line_name = ""
        events = []
        for f2, w2, v2 in _fields(v):
            if f2 == 2 and w2 == 2:
                line_name = v2.decode(errors="replace")
            elif f2 == 3 and w2 == 0:
                line_ts_ns = v2
            elif f2 == 4 and w2 == 2:
                events.append(v2)
        if want_cpu_exec and not line_name.startswith(_XLA_CPU_LINE_PREFIX):
            continue
        base_ps = line_ts_ns * 1000
        for ev in events:
            mid = off = dur = 0
            for f3, _w3, v3 in _fields(ev):
                if f3 == 1:
                    mid = v3
                elif f3 == 2:
                    off = v3
                elif f3 == 3:
                    dur = v3
            if mid in skip_ids:
                continue
            if dur > 0:
                out.append((base_ps + off, base_ps + off + dur))
    return out


def parse_busy_ns(paths) -> dict:
    """Union of device-event intervals across xplane.pb files → busy ns.

    → {"busy_ns", "source": "device"|"xla_cpu"|"none", "planes": [names]}.
    """
    dev_iv, cpu_iv = [], []
    dev_names, cpu_names = [], []
    for path in paths:
        with open(path, "rb") as f:
            space = f.read()
        for fn, _wt, plane in _fields(space):
            if fn != 1:
                continue
            name = ""
            for f2, _w2, v2 in _fields(plane):
                if f2 == 2:
                    name = v2.decode(errors="replace")
                    break
            if name.startswith("/device:"):
                iv = _plane_intervals(plane, want_cpu_exec=False)
                if iv:
                    dev_iv.extend(iv)
                    dev_names.append(name)
            elif name == "/host:CPU":
                iv = _plane_intervals(plane, want_cpu_exec=True)
                if iv:
                    cpu_iv.extend(iv)
                    cpu_names.append(name)
    if dev_iv:
        ivs, source, names = dev_iv, "device", dev_names
    elif cpu_iv:
        ivs, source, names = cpu_iv, "xla_cpu", cpu_names
    else:
        return {"busy_ns": 0, "source": "none", "planes": []}
    # union of possibly-overlapping intervals (multiple lines/queues)
    ivs.sort()
    busy_ps = 0
    cur_s, cur_e = ivs[0]
    for s, e in ivs[1:]:
        if s > cur_e:
            busy_ps += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    busy_ps += cur_e - cur_s
    return {"busy_ns": busy_ps // 1000, "source": source,
            "planes": sorted(set(names))}


# --------------------------------------------- XLA-CPU thread-state sampler
#
# The xplane path above is the honest measure on accelerator devices (their
# planes carry only bounded per-kernel events).  On XLA-CPU it is unusable
# for production-size runs: scatter/while-loop HLOs execute one thunk per
# iteration, each emitting a TraceMe (a 1M-row config #1 run records
# ~2.4M host events — ~100x wall inflation and GBs of buffer), so the trace
# deforms and OOMs the thing it measures.  The CPU fallback instead samples
# DEVICE EVENT TIMESTAMPS the cheap way: the XLA compute pool's thread run
# states from /proc, during the unmodified production run.
#
#   * calibration: a short jitted loop attributes per-thread CPU time; the
#     threads that burn it (excluding every python `threading` thread and
#     the caller) ARE the XLA pool — pools are created at backend init and
#     stable for the process lifetime.
#   * measurement: a sampler thread polls those TIDs' run state every few
#     ms while fn() runs; device_busy_frac = fraction of samples with at
#     least one pool thread running.  Statistical, production-true, and
#     falsifiable: raw busy/total sample counts ship with the ratio.


def _tid_cpu_ticks() -> dict:
    """{tid: utime+stime clock ticks} for every thread of this process."""
    out = {}
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/stat") as fh:
                parts = fh.read().rsplit(") ", 1)[1].split()
            out[int(tid)] = int(parts[11]) + int(parts[12])
        except (OSError, IndexError, ValueError):
            continue
    return out


def _xla_pool_tids() -> list:
    """TIDs of the XLA-CPU compute pool, found by CPU-time attribution over
    a short calibration loop (see module comment).  Fresh per call — cheap,
    and robust to pools that grow after backend init."""
    import threading

    import jax
    import jax.numpy as jnp

    py_tids = {t.native_id for t in threading.enumerate()
               if t.native_id is not None}
    # Pin the calibration to the CPU backend explicitly: on an accelerator-
    # attached box the default device would run it on the accelerator and
    # attribute nothing — but the pool being calibrated here is XLA-CPU's
    # (the backend whose occupancy the sampler measures).
    try:
        cpu = jax.devices("cpu")[0]
    except Exception:
        cpu = jax.devices()[0]
    with jax.default_device(cpu):
        f = jax.jit(lambda a: (a * 2 + 1).sum())
        x = jnp.arange(1 << 20)
        jax.block_until_ready(f(x))  # compile outside the attribution window
        before = _tid_cpu_ticks()
        out = None
        for _ in range(30):
            out = f(x)
        jax.block_until_ready(out)
    after = _tid_cpu_ticks()
    return [tid for tid, t in after.items()
            if t - before.get(tid, t) > 0 and tid not in py_tids]


class _StateSampler:
    """Polls XLA-pool thread run states every `period_s` from a daemon
    thread; busy ticks are samples where >=1 pool thread is R(unning)."""

    def __init__(self, tids, period_s: float = 0.003):
        self.tids = tids
        self.period_s = period_s
        self.busy = 0
        self.total = 0
        self._stop = None

    def __enter__(self):
        import threading

        self._stop = threading.Event()
        all_threads = self.tids == ["*"]

        def loop():
            me = threading.get_native_id()
            if all_threads:
                paths = None
            else:
                paths = [f"/proc/self/task/{t}/stat" for t in self.tids]
            while not self._stop.is_set():
                if all_threads:
                    # refresh per sample: native kernels spawn short-lived
                    # workers; exclude the sampler thread itself (it is R
                    # while reading /proc and would count as always-busy)
                    paths = [f"/proc/self/task/{t}/stat"
                             for t in os.listdir("/proc/self/task")
                             if t != str(me)]
                running = False
                for p in paths:
                    try:
                        with open(p) as fh:
                            if fh.read().rsplit(") ", 1)[1][0] == "R":
                                running = True
                                break
                    except (OSError, IndexError):
                        continue
                self.total += 1
                self.busy += running
                self._stop.wait(self.period_s)

        self._th = threading.Thread(target=loop, daemon=True)
        self._th.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._th.join(timeout=1.0)


def cpu_pool_sampler() -> "_StateSampler":
    """Calibrate now and return a context-manager sampler over the XLA-CPU
    pool — for callers that time their own region (bench config #5 wraps
    its whole replay loop; `fn`-shaped callers use the measure functions).
    Read `.busy`/`.total` after exit."""
    return _StateSampler(_xla_pool_tids())


def process_busy_sampler() -> "_StateSampler":
    """Context-manager sampler over EVERY thread of this process (tids
    refreshed per sample via the '*' sentinel).  For kernels whose compute
    does not run on the XLA pool — the native CPU join's pthread workers —
    where the XLA-pool sampler would report idle while the cores burn."""
    return _StateSampler(["*"])


def measure_process_busy(fn) -> dict:
    """Occupancy of fn() counting ANY process thread in run state — the
    honest busy measure for native (non-XLA) kernels on the CPU device.

    Semantic (same contract as the XLA-pool sampler): the fraction of wall
    time with AT LEAST ONE thread running — occupancy, not core
    utilization; it cannot distinguish 1 busy worker from 8.  The caller
    thread counts too: during a native kernel it is either blocked in the
    extension call (S state, not sampled busy) or doing the kernel's own
    host-side glue (buffer alloc, mask scatters), which IS part of the
    kernel's wall and would be idle time if unsampled."""
    import jax

    with process_busy_sampler() as s:
        t0 = time.perf_counter()
        out = fn()
        try:
            jax.block_until_ready(out)
        except Exception:
            pass
        wall_s = time.perf_counter() - t0
    frac = s.busy / s.total if s.total else 0.0
    return {
        "device_busy_frac": round(frac, 3),
        "busy_ms": round(frac * wall_s * 1000, 1),
        "wall_ms": round(wall_s * 1000, 1),
        "source": "proc_sampled",
        "_debug": {"busy_samples": s.busy, "total_samples": s.total},
    }


def measure_device_busy_sampled(fn) -> dict:
    """XLA-CPU occupancy of the production run via thread-state sampling."""
    import jax

    with _StateSampler(_xla_pool_tids()) as s:
        t0 = time.perf_counter()
        out = fn()
        try:
            jax.block_until_ready(out)
        except Exception:
            pass
        wall_s = time.perf_counter() - t0
    frac = s.busy / s.total if s.total else 0.0
    return {
        "device_busy_frac": round(frac, 3),
        "busy_ms": round(frac * wall_s * 1000, 1),
        "wall_ms": round(wall_s * 1000, 1),
        "source": "xla_cpu_sampled",
        "_debug": {"busy_samples": s.busy, "total_samples": s.total,
                   "pool_threads": len(s.tids)},
    }


def measure_device_busy(fn, trace_dir: str | None = None,
                        force_trace: bool = False) -> dict:
    """Measured occupancy of the production run ``fn()``:
    {"device_busy_frac", "busy_ms", "wall_ms", "source"}.

    Accelerator backends use a real ``jax.profiler`` trace (device planes).
    XLA-CPU uses the thread-state sampler above — the profiler trace floods
    on production-size CPU runs (see the sampler's comment); pass
    ``force_trace=True`` to trace anyway (tests, tiny runs).

    The fraction is busy/wall of the PRODUCTION run itself — no analyze-mode
    serialization, no clamping; >1.0 is impossible by construction (the
    interval union cannot exceed wall time on one timeline; tiny profiler
    skew can push it a percent past, which is reported as measured).
    """
    import jax

    if not force_trace and jax.devices()[0].platform == "cpu":
        return measure_device_busy_sampled(fn)
    tmp = trace_dir or tempfile.mkdtemp(prefix="px_xprof_")
    # Trace with the PYTHON tracer OFF: the default options record every
    # Python call, which inflates a ~10 ms production query to seconds — the
    # measurement must not deform the thing it measures.  Device/host
    # TraceMe events (the ones occupancy is computed from) come from the
    # C++ host tracer.
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    t0 = time.perf_counter()
    try:
        with jax.profiler.trace(tmp, profiler_options=opts):
            # drain async dispatches so their device time lands in the trace
            jax.block_until_ready(fn())
    finally:
        wall_s = time.perf_counter() - t0
    paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    parsed = parse_busy_ns(paths)
    if trace_dir is None:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)
    busy_s = parsed["busy_ns"] / 1e9
    return {
        "device_busy_frac": round(busy_s / wall_s, 3) if wall_s > 0 else 0.0,
        "busy_ms": round(busy_s * 1000, 1),
        "wall_ms": round(wall_s * 1000, 1),
        "source": parsed["source"],
    }
