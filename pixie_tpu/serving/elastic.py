"""AgentSupervisor: broker-driven agent autoscaling (closed-loop elasticity).

The last leg of the ROADMAP-4 control loop: the measured service-rate
model (serving/ratemodel.py) supplies the demand signal, the live quota
plane shapes per-tenant shares, and this module sizes the FLEET — the
broker spawns agents when measured pressure exceeds the high watermark and
retires them through the loss-safe decommission protocol
(`Broker.retire_agent`: shard-map last-holder check, drain audit, PR 12
replication hand-off) when it falls below the low watermark.

Control loop (one tick per ``PL_AUTOSCALE_PERIOD_S``):

  * **Pressure** — ``max(offered_load, (inflight + queued) / cap)``:
    Little's-law offered concurrency from the rate model (arrival rate ×
    measured mean service time over ``PL_SERVING_MAX_INFLIGHT``) guarded
    by the instantaneous occupancy so a thundering herd registers before
    the arrival window catches up.  EWMA-smoothed (``PL_AUTOSCALE_EWMA``)
    so one bursty tick cannot flap the fleet.
  * **Hysteresis** — scale up at ``smoothed ≥ PL_AUTOSCALE_UP_WATERMARK``,
    down at ``smoothed ≤ PL_AUTOSCALE_DOWN_WATERMARK``; the dead band
    between them plus per-direction cooldowns
    (``PL_AUTOSCALE_{UP,DOWN}_COOLDOWN_S``) absorb diurnal noise and
    preemption churn.
  * **Bounds** — the fleet never leaves
    [``PL_AUTOSCALE_MIN``, ``PL_AUTOSCALE_MAX``] live agents; only agents
    this supervisor spawned are retire candidates (newest first — the
    most likely to hold nothing), seed agents are never touched.
  * **Preemption repair** — a spawned agent that dies (spot kill,
    ``faultinject kill:`` rule) is reaped once past the rejoin grace and,
    under sustained pressure, replaced by the normal scale-up path.

Launchers: ``ThreadLauncher`` runs agents in-process over the real
framed-TCP transport (what the tests use);
``ProcLauncher`` spawns real ``python -m pixie_tpu.services.agent``
subprocesses with orphan-proof cleanup (``PR_SET_PDEATHSIG`` so a
SIGKILLed parent takes its children with it, plus an atexit sweep for
clean exits) — a crashed parent can never leave agents squatting on ports.

Every decision lands in ``self_telemetry.scale_events`` with the smoothed
pressure that drove it.  ``PL_AUTOSCALE=0`` (the default) never starts the
loop: the serving path is bit-identical to the fixed-fleet engine.
"""
from __future__ import annotations

import atexit
import subprocess
import sys
import threading
import time
from collections import OrderedDict
from typing import Callable, Optional

from pixie_tpu import flags, metrics

flags.define_bool(
    "PL_AUTOSCALE", False,
    "broker-driven agent autoscaling (serving/elastic.py): spawn agents "
    "when smoothed pressure exceeds the high watermark, retire "
    "supervisor-spawned agents through the loss-safe decommission "
    "protocol below the low watermark; 0 keeps the fleet fixed")
flags.define_int(
    "PL_AUTOSCALE_MIN", 1,
    "lower bound on live agents — the supervisor never retires below it")
flags.define_int(
    "PL_AUTOSCALE_MAX", 8,
    "upper bound on live agents — the supervisor never spawns above it")
flags.define_float(
    "PL_AUTOSCALE_UP_WATERMARK", 0.8,
    "smoothed pressure (offered load / capacity) at or above which one "
    "agent spawns per up-cooldown")
flags.define_float(
    "PL_AUTOSCALE_DOWN_WATERMARK", 0.25,
    "smoothed pressure at or below which one spawned agent retires per "
    "down-cooldown; the dead band up to the high watermark is the "
    "anti-flap hysteresis")
flags.define_float(
    "PL_AUTOSCALE_UP_COOLDOWN_S", 3.0,
    "minimum seconds between scale-ups (a burst adds agents one measured "
    "step at a time, not a thundering spawn)")
flags.define_float(
    "PL_AUTOSCALE_DOWN_COOLDOWN_S", 10.0,
    "minimum seconds between scale-downs — deliberately longer than the "
    "up cooldown so a preemption-riddled or flapping load curve errs "
    "toward capacity")
flags.define_float(
    "PL_AUTOSCALE_PERIOD_S", 0.5,
    "supervisor tick period (pressure sample + decision)")
flags.define_float(
    "PL_AUTOSCALE_EWMA", 0.3,
    "EWMA smoothing factor for the pressure signal (1.0 = raw samples)")

#: pxlint lock-discipline: supervisor state is owned by its one mutex
_pxlint_locks_ = {
    "_reap_locked": "self._lock",
    "_retire_candidate_locked": "self._lock",
}


# --------------------------------------------------------------- launchers


#: live subprocess children spawned by every ProcLauncher in this process,
#: swept at interpreter exit — a bench/test that crashes out of its finally
#: block must not leave agents holding ports (the stale `pkill -f
#: pixie_tpu` hazard)
_CHILDREN: dict[int, subprocess.Popen] = {}
_CHILDREN_LOCK = threading.Lock()
_ATEXIT_ARMED = False


def _reap_children() -> None:
    with _CHILDREN_LOCK:
        procs = list(_CHILDREN.values())
        _CHILDREN.clear()
    for p in procs:
        try:
            if p.poll() is None:
                p.terminate()
        except Exception:
            pass
    deadline = time.monotonic() + 3.0
    for p in procs:
        try:
            p.wait(timeout=max(deadline - time.monotonic(), 0.1))
        except Exception:
            try:
                p.kill()
            except Exception:
                pass


def _arm_atexit() -> None:
    global _ATEXIT_ARMED
    with _CHILDREN_LOCK:
        if _ATEXIT_ARMED:
            return
        _ATEXIT_ARMED = True
    atexit.register(_reap_children)


def _pdeathsig_preexec() -> None:  # pragma: no cover — runs in the child
    """Linux parent-death signal: the kernel SIGKILLs this child the
    moment its parent dies, however the parent died (SIGKILL included —
    the case atexit can never cover)."""
    try:
        import ctypes
        import signal as _signal

        libc = ctypes.CDLL("libc.so.6", use_errno=True)
        libc.prctl(1, _signal.SIGKILL)  # PR_SET_PDEATHSIG = 1
    except Exception:
        pass  # non-Linux: atexit + terminate remain the cleanup path


class ChipHeldError(RuntimeError):
    """A child agent cannot be given a chip of its own: this process holds
    (or would take) the host's chips, or every chip already has a child."""


def _local_chips() -> int:
    """Accelerator chips on this host, counted from their device files —
    WITHOUT touching JAX: the spawning process is the CPU-pinned broker,
    and enumerating through JAX would open the very chips the children
    need."""
    import glob

    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


class ProcLauncher:
    """Spawn agents as real subprocesses (`python -m
    pixie_tpu.services.agent`), orphan-proof: PR_SET_PDEATHSIG ties each
    child's life to this process, the module atexit sweep covers clean
    exits, and stop() terminates individually.

    Each child is given its devices EXPLICITLY (a chip belongs to one
    process): on a host with chips, one free chip per child through
    libtpu's TPU_VISIBLE_CHIPS + TPU_*_PROCESS_BOUNDS; on a host without,
    the CPU platform by name.  spawn() raises ChipHeldError — which the supervisor
    counts and logs as a spawn error — when this process's own JAX is not
    pinned to the CPU (it holds, or on first use takes, every local chip)
    or when no chip is free."""

    def __init__(self, broker_host: str, broker_port: int,
                 argv_for: Optional[Callable[[str], list]] = None,
                 extra_env: Optional[dict] = None):
        self.broker = (broker_host, int(broker_port))
        self._argv_for = argv_for
        self._extra_env = dict(extra_env or {})
        #: chip index -> the live child that owns it; spawn() holds the
        #: lock from picking a chip to recording its owner
        self._chip_owner: dict[int, subprocess.Popen] = {}
        self._spawn_lock = threading.Lock()
        _arm_atexit()

    def _device_env(self) -> tuple[dict, Optional[int]]:
        """→ (env that names the child's devices, the chip index taken)."""
        n_chips = _local_chips()
        if n_chips == 0:
            return {"JAX_PLATFORMS": "cpu"}, None
        import jax

        if jax.config.jax_platforms != "cpu":
            raise ChipHeldError(
                f"this process's JAX is not pinned to the CPU "
                f"(jax_platforms={jax.config.jax_platforms!r}): it holds, "
                f"or on first use takes, this host's {n_chips} chip(s), so "
                "a child agent would fail or hang on them — start the "
                "broker through `cli broker` (CPU by role)")
        for chip, owner in list(self._chip_owner.items()):
            if owner.poll() is not None:
                del self._chip_owner[chip]
        free = [c for c in range(n_chips) if c not in self._chip_owner]
        if not free:
            raise ChipHeldError(
                f"all {n_chips} chip(s) of this host belong to live "
                "child agents")
        chip = free[0]
        # what libtpu 0.0.34 honours (read on a 4-chip v5e host, PR 21):
        # the chip by index PLUS one-chip process bounds — with the index
        # alone the first child locks every chip and the second dies on
        # libtpu's multi-process lockfile
        return {
            "TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
        }, chip

    def _argv(self, name: str) -> list:
        if self._argv_for is not None:
            return list(self._argv_for(name))
        return [sys.executable, "-m", "pixie_tpu.services.agent",
                "--name", name,
                "--broker", f"{self.broker[0]}:{self.broker[1]}"]

    def spawn(self, name: str):
        import os

        env = dict(os.environ)
        # the flag registry is the single config surface on both sides of
        # the fork
        env.update(flags.env_exports())
        with self._spawn_lock:
            device_env, chip = self._device_env()
            env.update(device_env)
            env.update(self._extra_env)
            # stderr is inherited: a child that dies at start-up (a chip
            # it could not open, an import error) must be visible in this
            # process's log, not discarded
            p = subprocess.Popen(
                self._argv(name), env=env,
                stdout=subprocess.DEVNULL, stderr=None,
                preexec_fn=_pdeathsig_preexec)
            if chip is not None:
                self._chip_owner[chip] = p
        with _CHILDREN_LOCK:
            _CHILDREN[p.pid] = p
        return p

    def stop(self, name: str, handle) -> None:
        with _CHILDREN_LOCK:
            _CHILDREN.pop(getattr(handle, "pid", None), None)
        try:
            if handle.poll() is None:
                handle.terminate()
                try:
                    handle.wait(timeout=5.0)
                except subprocess.TimeoutExpired:
                    handle.kill()
        except Exception:
            pass

    @staticmethod
    def alive(handle) -> bool:
        return handle.poll() is None


class ThreadLauncher:
    """In-process agents over the real framed-TCP transport.
    `store_factory(name)` supplies each spawned agent's TableStore
    (default: empty) — tests pass a factory that pre-creates the serving
    tables' SCHEMAS (empty) so the new shard joins every plan without
    perturbing results."""

    def __init__(self, broker_host: str, broker_port: int,
                 store_factory: Optional[Callable] = None,
                 heartbeat_s: float = 1.0):
        self.broker = (broker_host, int(broker_port))
        self.store_factory = store_factory
        self.heartbeat_s = heartbeat_s

    def spawn(self, name: str):
        from pixie_tpu.services.agent import Agent
        from pixie_tpu.table.table import TableStore

        store = (self.store_factory(name) if self.store_factory is not None
                 else TableStore())
        return Agent(name, self.broker[0], self.broker[1], store=store,
                     heartbeat_s=self.heartbeat_s).start()

    def stop(self, name: str, handle) -> None:
        try:
            handle.stop()
        except Exception:
            pass

    @staticmethod
    def alive(handle) -> bool:
        return handle.conn is not None and not handle.conn.closed


# -------------------------------------------------------------- supervisor


class AgentSupervisor:
    """The broker's fleet-sizing control loop (see module docstring)."""

    def __init__(self, broker, launcher, name_prefix: str = "px-auto"):
        self.broker = broker
        self.launcher = launcher
        self.name_prefix = name_prefix
        self._lock = threading.Lock()
        #: name -> launcher handle, insertion-ordered (retires pop newest)
        self._spawned: "OrderedDict[str, object]" = OrderedDict()
        #: name -> monotonic spawn time (the _reap startup-grace anchor)
        self._spawn_at: dict[str, float] = {}
        self._seq = 0
        self.smoothed = 0.0
        self._last_up = 0.0
        self._last_down = 0.0
        self.scale_ups = 0
        self.scale_downs = 0
        self.retire_refusals = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._gauges = False

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "AgentSupervisor":
        if self._thread is not None:
            return self
        self._stop.clear()
        if not self._gauges:
            self._gauges = True
            metrics.register_gauge_fn(
                "px_autoscale_pressure",
                lambda: {(): float(self.smoothed)},
                "smoothed autoscaler pressure (offered load / capacity)")
            metrics.register_gauge_fn(
                "px_autoscale_agents",
                lambda: {(): float(len(
                    self.broker.registry.live_agents()))},
                "live agents under autoscaler management")
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="pixie-autoscale")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        th, self._thread = self._thread, None
        if th is not None:
            th.join(timeout=5.0)
        if self._gauges:
            self._gauges = False
            metrics.unregister_gauge_fn("px_autoscale_pressure")
            metrics.unregister_gauge_fn("px_autoscale_agents")
        with self._lock:
            spawned = list(self._spawned.items())
            self._spawned.clear()
            self._spawn_at.clear()
        for name, handle in spawned:
            self.launcher.stop(name, handle)

    def spawned_agents(self) -> list[str]:
        with self._lock:
            return list(self._spawned)

    # ------------------------------------------------------------- pressure
    def pressure(self) -> float:
        """Instantaneous demand over capacity: the rate model's Little's-
        law offered load, guarded by live occupancy (inflight + queued
        over the in-flight cap) so a burst registers before the arrival
        window catches up."""
        front = self.broker.serving
        cap = max(1, int(flags.get("PL_SERVING_MAX_INFLIGHT")))
        inst = (front.inflight + front.total_queued) / cap
        # short arrival window: the loop must SEE a diurnal trough within
        # a few ticks — a long window would hold yesterday's peak against
        # scale-down for its whole span
        offered = self.broker.ratemodel.offered_load(cap, window_s=5)
        return max(inst, offered or 0.0)

    # ----------------------------------------------------------------- loop
    def _loop(self) -> None:
        while not self._stop.wait(
                timeout=max(float(flags.get("PL_AUTOSCALE_PERIOD_S")), 0.05)):
            try:
                self.tick()
            except Exception:
                metrics.counter_inc(
                    "px_autoscale_tick_errors_total",
                    help_="supervisor ticks that raised (the loop "
                          "survives; the decision is skipped)")

    def tick(self, now: Optional[float] = None) -> None:
        """One control decision (public so tests drive it deterministically
        without the timer thread)."""
        now = time.monotonic() if now is None else now
        alpha = min(max(float(flags.get("PL_AUTOSCALE_EWMA")), 0.01), 1.0)
        raw = self.pressure()
        self.smoothed += alpha * (raw - self.smoothed)
        self._reap(now)
        live = {r.name for r in self.broker.registry.live_agents()}
        n = len(live)
        lo = max(1, int(flags.get("PL_AUTOSCALE_MIN")))
        hi = max(lo, int(flags.get("PL_AUTOSCALE_MAX")))
        up_wm = float(flags.get("PL_AUTOSCALE_UP_WATERMARK"))
        down_wm = float(flags.get("PL_AUTOSCALE_DOWN_WATERMARK"))
        if (self.smoothed >= up_wm and n < hi
                and now - self._last_up
                >= float(flags.get("PL_AUTOSCALE_UP_COOLDOWN_S"))):
            self._last_up = now
            self._spawn()
        elif (self.smoothed <= down_wm and n > lo
                and now - self._last_down
                >= float(flags.get("PL_AUTOSCALE_DOWN_COOLDOWN_S"))):
            name = self._retire_candidate(live)
            if name is not None:
                self._last_down = now
                self._retire(name)

    def _reap_locked(self, dead: list) -> list:
        out = []
        for name in dead:
            h = self._spawned.pop(name, None)
            self._spawn_at.pop(name, None)
            if h is not None:
                out.append((name, h))
        return out

    #: seconds a freshly-spawned agent gets to REGISTER before a missing
    #: registry record counts as death — a ProcLauncher subprocess pays
    #: interpreter + jax import before it can register, and reaping it in
    #: that window would kill every scale-up at birth.  A child whose
    #: PROCESS exited reaps immediately regardless.
    SPAWN_GRACE_S = 120.0

    def _reap(self, now: float) -> None:
        """Drop spawned agents that died underneath us (preemption, spot
        kill) once past the rejoin grace: their registry records deregister
        (they cannot self-restart — the supervisor owns their lifecycle)
        and the normal scale-up path replaces them under pressure."""
        grace = float(flags.get("PL_REJOIN_GRACE_S"))
        dead = []
        with self._lock:
            names = {n: self._spawned[n] for n in self._spawned}
        for name, handle in names.items():
            rec = self.broker.registry.record(name)
            if rec is None:
                # not registered (yet): dead only once its process/thread
                # is gone or the startup grace has lapsed — never while a
                # subprocess is still importing its way to registration
                spawned_at = self._spawn_at.get(name, now)
                if (not self.launcher.alive(handle)
                        or now - spawned_at > self.SPAWN_GRACE_S):
                    dead.append(name)
                continue
            if (not rec.alive and rec.died_at > 0
                    and now - rec.died_at > max(grace, 1.0)):
                dead.append(name)
        if not dead:
            return
        with self._lock:
            reaped = self._reap_locked(dead)
        for name, handle in reaped:
            self.launcher.stop(name, handle)
            self.broker.reap_dead_agent(name)
            metrics.counter_inc(
                "px_autoscale_preempted_total",
                help_="supervisor-spawned agents that died underneath the "
                      "supervisor (preemption) and were reaped")
            self._event("preempt_reap", name, "agent died (preemption)")

    def _spawn(self) -> None:
        with self._lock:
            self._seq += 1
            name = f"{self.name_prefix}-{self._seq}"
        try:
            handle = self.launcher.spawn(name)
        except Exception as e:
            metrics.counter_inc(
                "px_autoscale_spawn_errors_total",
                help_="agent spawns that failed to launch")
            self._event("spawn_error", name, str(e)[:120])
            return
        with self._lock:
            self._spawned[name] = handle
            self._spawn_at[name] = time.monotonic()
        self.scale_ups += 1
        metrics.counter_inc(
            "px_autoscale_up_total",
            help_="agents spawned by the autoscaler")
        self._event("spawn", name,
                    f"pressure over {flags.get('PL_AUTOSCALE_UP_WATERMARK')}")

    def _retire_candidate_locked(self, live: set) -> Optional[str]:
        for name in reversed(self._spawned):  # newest first
            if name in live:
                return name
        return None

    def _retire_candidate(self, live: set) -> Optional[str]:
        """Only agents this supervisor spawned retire — seed agents (the
        operator's fleet, whose stores hold the primary data) never do."""
        with self._lock:
            return self._retire_candidate_locked(live)

    def _retire(self, name: str) -> None:
        res = self.broker.retire_agent(name)
        if not res.get("ok"):
            self.retire_refusals += 1
            self._event("retire_refused", name,
                        str(res.get("reason", ""))[:120])
            return
        with self._lock:
            handle = self._spawned.pop(name, None)
            self._spawn_at.pop(name, None)
        if handle is not None:
            self.launcher.stop(name, handle)
        self.scale_downs += 1
        metrics.counter_inc(
            "px_autoscale_down_total",
            help_="agents retired by the autoscaler (deregister or "
                  "replication hand-off)")
        self._event(f"retire_{res.get('mode')}", name,
                    f"pressure under "
                    f"{flags.get('PL_AUTOSCALE_DOWN_WATERMARK')}")

    def _event(self, action: str, agent: str, reason: str) -> None:
        try:
            self.broker.record_scale_event(
                action, agent, reason, self.smoothed,
                len(self.broker.registry.live_agents()))
        except Exception:
            metrics.counter_inc(
                "px_autoscale_event_errors_total",
                help_="scale events that failed to record (telemetry must "
                      "never fail the control loop)")
