"""On-device space-sharing executor — the TPU-native xCUDA analogue.

One device loop interleaves an *online* serving function (priority; batched
decode requests with an SLO) and an *offline* training function (best-effort
microsteps).  The offline duty fraction plays the SM-percentage role:

  * the PID-driven KernelThrottle (protection.py, Eq. 1–2) gates offline
    microsteps from device telemetry (duty cycle ↔ U_SM, clock factor),
  * the MemoryQuota ledger enforces the offline HBM quota before the offline
    state is ever allocated,
  * GracefulExit freezes offline launches and checkpoints on SIGINT/SIGTERM,
  * an SLO guard (latency-based eviction) mirrors SysMonitor's Overlimit.

The loop runs on the multiplexer's clock: time advances by the durations
the step functions return and by idle quanta, never by the loop's own host
work.  After `run()` the multiplexer holds its own record of that clock:

  steps      one `Step` per loop iteration (online, offline or idle) with
             its start and end on the clock, the online batch, and the
             offline duty after the PID update; the steps tile the clock
  requests   every `Request` in arrival order, with the index into `steps`
             of the online step that served it and when it was done
  phases     the loop's own host work per iteration as `mux.control` spans
             (arrival intake, batch pop, PID update, violation count, gate)
"""
from __future__ import annotations

import dataclasses
import heapq
from typing import Callable

import numpy as np

from repro.core.errors import GracefulExit
from repro.core.protection import (KernelThrottle, MemoryQuota, PIDConfig,
                                   PIDController, QuotaExceeded)
from repro.obs.phases import PhaseProfiler


@dataclasses.dataclass
class Request:
    arrival: float
    request_id: int
    done: float | None = None
    step: int | None = None          # index into Multiplexer.steps

    @property
    def latency(self) -> float:
        return (self.done - self.arrival) if self.done is not None else float("inf")


@dataclasses.dataclass
class Step:
    kind: str                        # "online", "offline" or "idle"
    start: float
    end: float
    batch: int = 0                   # requests served (online steps)
    duty: float = 0.0                # offline duty after the PID update


@dataclasses.dataclass
class MuxConfig:
    slo_slowdown: float = 1.2        # protect online latency to <= 1.2x base
    max_batch: int = 8               # online serving batch cap
    quantum_s: float = 0.010         # scheduling quantum (one decode step)
    evict_after_violations: int = 50  # SysMonitor-style overlimit -> evict
    latency_budget_s: float | None = None   # absolute end-to-end budget
    quota_frac: float = 0.4
    # stand-in for backends that report no memory; callers on an
    # accelerator pass the device's own bytes_limit (memory_stats())
    device_bytes: int = 16 << 30


@dataclasses.dataclass
class MuxStats:
    served: int = 0
    p50_ms: float = 0.0
    p99_ms: float = 0.0
    base_ms: float = 0.0
    offline_steps: int = 0
    offline_duty: float = 0.0        # offline step time / the clock
    oversold: float = 0.0            # offline steps / steps it would do alone
    evicted: bool = False
    slo_violations: int = 0


class Multiplexer:
    """Interleaves online serving with offline training on one device.

    online_fn(batch_size) -> latency_s of one serving step (measured or
    modeled); offline_fn() -> duration_s of one training microstep.  With
    real JAX step functions, pass wrappers that execute and time them.
    """

    def __init__(self, online_fn: Callable[[int], float],
                 offline_fn: Callable[[], float],
                 base_step_s: float,
                 offline_step_s: float,
                 cfg: MuxConfig = MuxConfig(),
                 offline_state_bytes: int = 0):
        self.online_fn = online_fn
        self.offline_fn = offline_fn
        self.base_step_s = base_step_s
        self.offline_step_s = offline_step_s
        self.cfg = cfg
        self.quota = MemoryQuota(cfg.device_bytes, cfg.quota_frac)
        if offline_state_bytes:
            self.quota.alloc(offline_state_bytes)  # raises QuotaExceeded
        # PID setpoint: keep measured online latency at slo
        self.throttle = KernelThrottle(PIDController(
            PIDConfig(setpoint=cfg.slo_slowdown, kp=0.6, ki=0.1, kd=0.0,
                      out_min=0.0, out_max=0.95), initial=0.5))
        self.stats = MuxStats(base_ms=base_step_s * 1e3)
        self.phases = PhaseProfiler()
        self.steps: list[Step] = []
        self.requests: list[Request] = []
        self._latencies: list[float] = []
        self._violations = 0
        # callers may install a GracefulExit wired with their own
        # checkpoint/release callbacks (examples/serve_multiplex.py); the
        # run loop falls back to a bare freeze-only harness otherwise
        self.graceful: GracefulExit | None = None

    def run(self, arrivals: list[float], horizon_s: float,
            max_offline_steps: int | None = None) -> MuxStats:
        """Simulated-clock loop: serve `arrivals` (sorted times), fill idle
        quanta with offline microsteps while the PID allows."""
        cfg = self.cfg
        control = self.phases.phase
        budget = (cfg.latency_budget_s
                  or cfg.slo_slowdown * self.base_step_s * 4)
        # keyed by (arrival, id): equal arrival times are served in id order
        queue: list[tuple[float, int, Request]] = []
        pending = [Request(a, i) for i, a in enumerate(sorted(arrivals))]
        self.requests = pending
        self.steps = steps = []
        t = 0.0
        i = 0
        offline_steps = 0
        gex = self.graceful or GracefulExit(throttle=self.throttle)
        if gex.throttle is None:
            gex.throttle = self.throttle
        with gex:
            while t < horizon_s:
                with control("mux.control"):
                    while i < len(pending) and pending[i].arrival <= t:
                        r = pending[i]
                        heapq.heappush(queue, (r.arrival, r.request_id, r))
                        i += 1
                    batch = [heapq.heappop(queue)[2]
                             for _ in range(min(cfg.max_batch, len(queue)))]
                    launch = (not batch
                              and not self.throttle.frozen
                              and self.throttle.should_launch(cfg.quantum_s)
                              and (max_offline_steps is None
                                   or offline_steps < max_offline_steps))
                start = t
                if batch:
                    dt = self.online_fn(len(batch))
                    t += dt
                    with control("mux.control"):
                        for r in batch:
                            r.done, r.step = t, len(steps)
                            self._latencies.append(r.latency)
                            if r.latency > budget:
                                self._violations += 1
                        # telemetry -> PID: measured slowdown of this step
                        slowdown = dt / max(self.base_step_s, 1e-9)
                        # PID drives duty so that slowdown tracks the SLO
                        self.throttle.pid.cfg.setpoint = cfg.slo_slowdown
                        self.throttle.duty = self.throttle.pid.update(
                            slowdown, dt)
                        steps.append(Step("online", start, t, len(batch),
                                          self.throttle.duty))
                        if self._violations >= cfg.evict_after_violations:
                            self.stats.evicted = True   # SysMonitor Overlimit
                            break
                elif launch:
                    dt = self.offline_fn()
                    t += dt
                    offline_steps += 1
                    steps.append(Step("offline", start, t, 0,
                                      self.throttle.duty))
                else:
                    # idle quantum (throttled): time still passes in quanta so
                    # the throttle keeps accruing offline credit
                    t += cfg.quantum_s
                    steps.append(Step("idle", start, t, 0, self.throttle.duty))
        s = self.stats
        s.served = len(self._latencies)
        if self._latencies:
            lat = np.array(self._latencies) * 1e3
            s.p50_ms = float(np.percentile(lat, 50))
            s.p99_ms = float(np.percentile(lat, 99))
        s.offline_steps = offline_steps
        offline_s = sum(st.end - st.start for st in steps
                        if st.kind == "offline")
        s.offline_duty = offline_s / t if t > 0 else 0.0
        alone = horizon_s / max(self.offline_step_s, 1e-9)
        s.oversold = offline_steps / max(alone, 1e-9)
        s.slo_violations = self._violations
        return s
