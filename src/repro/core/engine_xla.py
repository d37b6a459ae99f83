"""Compiled (XLA) tick engine — the fused counterpart of
:meth:`repro.core.simulator.ClusterSim._dense_core_numpy`.

The dense per-tick math — failure/error/completion state transitions,
progress/wall/checkpoint accrual, outage windows, DCGM-style telemetry, and
the full vectorized SysMonitor state machine — is traced once as a
``FleetState``-in/``FleetState``-out kernel and run through ``jax.lax.scan``
over tick *blocks* with donated buffers.  Python is re-entered only at
sparse event boundaries: job arrivals, scheduling rounds, control-plane
hooks, and fault injections (the accounting pass in ``simulator.py`` replays
each tick's sparse events from the kernel's stacked mask outputs).  The
same replay is what lets the request-level serving plane
(:mod:`repro.serving_plane`) ride block mode unchanged: ``_account`` runs
per tick, in order, on bitwise-identical arrays under both engines, so the
plane's per-tick queue/admission updates — and the report's ``"serving"``
section — are engine-invariant by construction.

Bitwise parity contract
-----------------------
``SimConfig.engine = "xla"`` must produce *byte-identical* ``SimResults``
and scenario reports to the numpy engine at the same seed.  Three things
make that possible:

* every accumulation/reduction and every transcendental stays on the host
  (shared numpy code in ``_tick_inputs`` / ``_account``): the kernel sees
  only IEEE-correctly-rounded elementwise ops (+, −, ×, min, max, select,
  compares, gathers/scatters, integer math), which agree bitwise between
  numpy and XLA CPU;
* no multiply in the kernel ever feeds an add/sub directly — the one
  rewrite LLVM may legally apply to such chains (contracting them into
  FMAs, which changes the rounding) therefore has nothing to bite on.
  Products that the telemetry math needs are formed host-side in
  ``_tick_inputs`` or routed through an intervening min/max (the numpy
  core is written in the same shapes, so the restriction costs nothing);
  a fixed-seed test pins kernel outputs to the numpy core bitwise;
* both engines draw per-tick randomness from one numpy ``Generator``
  stream and read trace/profile/policy inputs from the same host-computed
  arrays.

All state is host-authoritative: the fleet arrays, monitor state codes,
and re-admission timers round-trip through the (donated) kernel arguments
each call, while the Overlimit ring buffer never enters the kernel at all
— its rare, sparse updates run host-side through the same
:class:`VectorSysMonitor` primitives the numpy engine uses (see
``_tick_body``).  That keeps the control plane's between-tick surface
(``force_error``, ``evict_device``, ``set_schedulable_mask`` …)
engine-agnostic: everything it mutates is plain numpy.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.sysmonitor import (S_DISABLED, S_HEALTHY, S_INIT,
                                   S_OVERLIMIT, S_UNHEALTHY)

# one compiled executable per (T, n, n_kinds) — every true scalar (rates,
# thresholds, tick length) and every per-kind outcome table is an argument,
# so one kernel serves every scenario of a given shape without recompiling.
# Blocks are power-of-two sized, so T ∈ {1, 2, 4, …, _MAX_BLOCK}.
_COMPILE_CACHE: dict[tuple, object] = {}

_MAX_BLOCK = 32

# scalar-vector layout (argument `sc`); see _scalars()
_SC = ("dt", "p_fail", "p_err", "repair_s", "outage_s", "ck_interval",
       "err_total", "th_util_h", "th_util_o", "th_sm_h", "th_sm_o",
       "th_mem_h", "th_mem_o", "th_clk_h", "th_clk_o", "th_tmp_h",
       "th_tmp_o", "readmit_base", "readmit_cap", "ol_window", "init_dur",
       "temp_c")
_SCI = {k: i for i, k in enumerate(_SC)}


def _host_device():
    """Where the kernel runs: the host CPU, on every backend.  The parity
    contract needs IEEE binary64 elementwise math, which XLA:CPU gives; a
    TPU has no native f64 and emulates it, so on a TPU host the kernel is
    placed on the CPU explicitly rather than following the default device
    (ROADMAP S4/D3)."""
    return jax.devices("cpu")[0]


def _compile(jitted, *args):
    """AOT-compile the kernel (full optimization — the kernel's op graph is
    contraction-free by construction, see the module docstring)."""
    return jitted.lower(*args).compile()


def _tick_body(carry, x, stat, sc, n_kinds: int):
    """One tick of dense state evolution — mirrors
    ``ClusterSim._dense_core_numpy`` + ``VectorSysMonitor.update``
    operation-for-operation (see the bitwise parity contract above).

    The monitor's Overlimit *ring buffer* stays host-side: entries are rare
    (a scatter here would lower to a sequential per-row loop and drag 10 MB
    of buffer copies through every tick), so the kernel only emits the
    ``mon_evict``/``start_wait`` masks and the host applies the sparse ring
    push / re-admission-period math through the same
    :class:`VectorSysMonitor` primitives the numpy engine uses.  A
    ``start_wait`` before the last tick of a block truncates the block (the
    kernel cannot see the period the host assigns), which the driver
    handles by accepting the prefix and re-stepping the rest.
    """
    (has_job, progress, checkpoint, wall, failed_until, outage_until,
     mstate, readmit_at) = carry
    t, u, tput_dt, on_util, on_act, on_mem = x
    (used_min, used62, used45, duration, off_mem, init_at, err_thresh,
     err_propagates, err_graceful_ck) = stat
    fail_u, err_u, kind_u = u[0], u[1], u[2]
    dt = sc[_SCI["dt"]]

    alive = failed_until <= t
    new_fail = alive & (fail_u < sc[_SCI["p_fail"]])
    failed_until = jnp.where(new_fail, t + sc[_SCI["repair_s"]],
                             failed_until)
    act = alive & ~new_fail
    busy = act & has_job
    has_job = has_job & ~new_fail
    # offline progress + periodic checkpoint (tput·dt is a host-side
    # product, so the kernel adds — no mul→add chain to contract)
    progress = jnp.where(busy, progress + tput_dt, progress)
    wall = jnp.where(busy, wall + dt, wall)
    ck = busy & (progress - checkpoint >= sc[_SCI["ck_interval"]])
    checkpoint = jnp.where(ck, progress, checkpoint)
    # offline container errors — kind and §4.2 handling outcome are pure
    # functions of the tick's uniforms; the outcome comes from the
    # per-kind tables the simulator probes out of MixedErrorHandler, so
    # the handler stays the single home of the propagation semantics
    err = busy & (err_u < sc[_SCI["p_err"]])
    r = kind_u * sc[_SCI["err_total"]]
    kind_idx = jnp.minimum(
        (r[:, None] > err_thresh[None, :]).sum(axis=1).astype(jnp.int64),
        n_kinds - 1)
    propagated = err & err_propagates[kind_idx]
    checkpoint = jnp.where(err & err_graceful_ck[kind_idx], progress,
                           checkpoint)
    outage_until = jnp.where(propagated, t + sc[_SCI["outage_s"]],
                             outage_until)
    has_job = has_job & ~err
    # job completion
    fin = busy & has_job & (progress >= duration)
    has_job = has_job & ~fin
    # telemetry (products precomputed host-side / routed through max — the
    # kernel's no-mul-into-add discipline, see module docstring)
    used_off = jnp.where(has_job, used_min, 0.0)
    tele_util = jnp.minimum(1.0, on_util + jnp.where(has_job, used62, 0.0))
    tele_sm = jnp.minimum(1.0, on_act + jnp.where(has_job, used45, 0.0))
    tele_clock = 1590.0 - jnp.maximum(0.0,
                                      420.0 * (on_act + used_off - 0.8))
    tele_mem = jnp.minimum(1.0, on_mem + jnp.where(has_job, off_mem, 0.0))
    # SysMonitor classification (0 healthy / 1 unhealthy / 2 overlimit)
    over = ((tele_util > sc[_SCI["th_util_o"]])
            | (tele_sm > sc[_SCI["th_sm_o"]])
            | (tele_mem > sc[_SCI["th_mem_o"]])
            | (sc[_SCI["temp_c"]] > sc[_SCI["th_tmp_o"]])
            | (tele_clock < sc[_SCI["th_clk_o"]]))
    unhealthy = ((tele_util > sc[_SCI["th_util_h"]])
                 | (tele_sm > sc[_SCI["th_sm_h"]])
                 | (tele_mem > sc[_SCI["th_mem_h"]])
                 | (sc[_SCI["temp_c"]] > sc[_SCI["th_tmp_h"]])
                 | (tele_clock < sc[_SCI["th_clk_h"]]))
    level = jnp.where(over, 2, jnp.where(unhealthy, 1, 0)).astype(jnp.int8)
    # SysMonitor transitions (VectorSysMonitor.update, vector form)
    init_m = act & (mstate == S_INIT)
    promote = init_m & (t - init_at >= sc[_SCI["init_dur"]])
    mstate = jnp.where(promote, S_HEALTHY, mstate).astype(jnp.int8)
    rest = act & ~init_m & (mstate != S_DISABLED)
    healthy_m = rest & (mstate == S_HEALTHY)
    unhealthy_m = rest & (mstate == S_UNHEALTHY)
    over_m = rest & (mstate == S_OVERLIMIT)
    evict = (healthy_m | unhealthy_m) & (level == 2)
    mstate = jnp.where(healthy_m & (level == 1), S_UNHEALTHY, mstate)
    mstate = jnp.where(unhealthy_m & (level == 0), S_HEALTHY, mstate)
    mstate = jnp.where(evict, S_OVERLIMIT, mstate).astype(jnp.int8)
    readmit_at = jnp.where(evict, jnp.nan, readmit_at)
    # Overlimit: wait out the exponential re-admission period (the period
    # itself is assigned host-side from the ring — see module docstring)
    exit_lvl = over_m & (level != 2)
    had_wait = ~jnp.isnan(readmit_at)
    start_wait = exit_lvl & ~had_wait
    readmit = exit_lvl & had_wait & (t >= readmit_at)
    readmit_at = jnp.where(over_m & (level == 2), jnp.nan, readmit_at)
    mstate = jnp.where(readmit, S_UNHEALTHY, mstate).astype(jnp.int8)
    readmit_at = jnp.where(readmit, jnp.nan, readmit_at)
    evict_cand = evict & has_job
    has_job = has_job & ~evict_cand

    carry = (has_job, progress, checkpoint, wall, failed_until,
             outage_until, mstate, readmit_at)
    ys = (new_fail, err, kind_idx, fin, evict_cand, busy, act, tele_util,
          tele_sm, tele_clock, tele_mem, level, progress, wall, checkpoint,
          outage_until, evict, start_wait)
    # per-tick copies of the carry state, needed only by multi-tick blocks
    # (truncation restore); T=1 reads the final carry instead
    ys_state = (has_job, failed_until, mstate, readmit_at)
    return carry, ys, ys_state


_YS = ("new_fail", "err", "kind_idx", "fin", "evict_cand", "busy", "act",
       "tele_util", "tele_sm", "tele_clock", "tele_mem", "level",
       "progress", "wall", "checkpoint", "outage_until", "mon_evict",
       "start_wait")
_YS_STATE = ("has_job", "failed_until", "mstate", "readmit_at")


def _get_kernel(T: int, n: int, n_kinds: int, example_args):
    key = (T, n, n_kinds)
    comp = _COMPILE_CACHE.get(key)
    if comp is None:
        if T == 1:
            # per-tick (control-plane interleaved) mode: no scan (the
            # while-loop's carry plumbing is pure overhead at T=1), and the
            # per-tick state copies are skipped — the caller reads the
            # final carry
            def kernel(carry, stat, sc, xs):
                x1 = jax.tree_util.tree_map(lambda a: a[0], xs)
                carry, ys, _ = _tick_body(carry, x1, stat, sc, n_kinds)
                return carry, jax.tree_util.tree_map(lambda a: a[None], ys)
        else:
            def kernel(carry, stat, sc, xs):
                def body(c, x):
                    c2, ys, ys_state = _tick_body(c, x, stat, sc, n_kinds)
                    return c2, ys + ys_state
                return lax.scan(body, carry, xs)

        jitted = jax.jit(kernel, donate_argnums=(0,))
        comp = _COMPILE_CACHE[key] = _compile(jitted, *example_args)
    return comp


class XlaTickEngine:
    """Drives the compiled tick kernel for one :class:`ClusterSim`.

    Fleet and monitor state stay numpy-authoritative (pushed in / pulled
    out around each kernel call, so the control plane's between-tick
    mutations keep working); the SysMonitor's Overlimit ring never enters
    the kernel — its sparse updates replay host-side per tick.
    """

    def __init__(self, sim):
        self.sim = sim
        cfg = sim.cfg
        mon = sim.monitor
        th = mon.cfg.thresholds
        sc = np.zeros(len(_SC), np.float64)
        sc[_SCI["dt"]] = cfg.tick_s
        sc[_SCI["p_fail"]] = cfg.tick_s / (cfg.device_mtbf_h * 3600.0)
        sc[_SCI["p_err"]] = cfg.error_rate_per_job_hour * cfg.tick_s / 3600.0
        sc[_SCI["repair_s"]] = cfg.device_repair_s
        sc[_SCI["outage_s"]] = cfg.online_outage_s
        sc[_SCI["ck_interval"]] = cfg.checkpoint_interval_s
        sc[_SCI["err_total"]] = sim._err_total
        sc[_SCI["th_util_h"]], sc[_SCI["th_util_o"]] = th.gpu_util
        sc[_SCI["th_sm_h"]], sc[_SCI["th_sm_o"]] = th.sm_activity
        sc[_SCI["th_mem_h"]], sc[_SCI["th_mem_o"]] = th.mem_used_frac
        sc[_SCI["th_clk_h"]], sc[_SCI["th_clk_o"]] = th.sm_clock_min
        sc[_SCI["th_tmp_h"]], sc[_SCI["th_tmp_o"]] = th.temp_c
        sc[_SCI["readmit_base"]] = mon.cfg.readmit_base_s
        sc[_SCI["readmit_cap"]] = mon.cfg.readmit_cap_s
        sc[_SCI["ol_window"]] = mon.cfg.overlimit_window_s
        sc[_SCI["init_dur"]] = mon.cfg.init_duration_s
        sc[_SCI["temp_c"]] = 60.0      # the engines' constant device temp
        self._sc = sc
        self._n_kinds = len(sim._err_kinds)
        self._init_at = mon._init_at            # static after construction
        self._block_hint = _MAX_BLOCK

    # ------------------------------------------------------------- driving
    def tick(self, inp: dict) -> dict:
        """Per-tick mode (control-plane interleaving): a T=1 block."""
        return self.tick_block([inp])[0]

    def tick_block(self, inps: list[dict]) -> list[dict]:
        """Run a scheduling-free run of ticks through kernel calls and
        return per-tick core dicts for the shared accounting pass.

        A ``start_wait`` event before a block's last tick truncates the
        accepted prefix (the host assigns the re-admission period the
        kernel cannot know); the remainder re-steps from the restored state
        — with the *same* already-drawn inputs, so nothing diverges.
        """
        cores: list[dict] = []
        while inps:
            # power-of-two block sizes only: truncation tails re-enter here
            # and must not mint fresh compile shapes per remainder length
            T = min(len(inps), self._block_hint)
            T = 1 << (T.bit_length() - 1)
            accepted = self._run_block(inps[:T], cores)
            # adapt: monitor-event-dense phases shrink blocks (a truncated
            # block discards work past the event), quiet phases regrow them
            self._block_hint = (min(_MAX_BLOCK, max(2 * accepted, 1))
                                if accepted == T
                                else max(1, 1 << max(accepted.bit_length()
                                                     - 1, 0)))
            inps = inps[accepted:]
        return cores

    def _run_block(self, inps: list[dict], cores: list[dict]) -> int:
        # x64 and the host placement are scoped to the engine's own
        # traces/calls (the fleet math is float64 end to end) so the rest
        # of the process — the float32 predictor, models, serving engine —
        # keeps jax's default dtypes and device
        with jax.enable_x64(True), jax.default_device(_host_device()):
            return self._run_block_x64(inps, cores)

    def _run_block_x64(self, inps: list[dict], cores: list[dict]) -> int:
        sim = self.sim
        s = sim.state
        mon = sim.monitor
        n = sim.cfg.n_devices
        T = len(inps)
        if T == 1:
            inp = inps[0]
            xs = (np.array([inp["t"]]),
                  np.stack((inp["fail_u"], inp["err_u"],
                            inp["kind_u"]))[None],
                  inp["tput_dt"][None], inp["on"]["gpu_util"][None],
                  inp["on"]["sm_activity"][None],
                  inp["on"]["mem_bytes_frac"][None])
        else:
            xs = (np.array([inp["t"] for inp in inps], np.float64),
                  np.stack([np.stack((inp["fail_u"], inp["err_u"],
                                      inp["kind_u"])) for inp in inps]),
                  np.stack([inp["tput_dt"] for inp in inps]),
                  np.stack([inp["on"]["gpu_util"] for inp in inps]),
                  np.stack([inp["on"]["sm_activity"] for inp in inps]),
                  np.stack([inp["on"]["mem_bytes_frac"] for inp in inps]))
        carry = (s.has_job, s.progress, s.checkpoint, s.wall,
                 s.failed_until, s.outage_until, mon.state,
                 mon._readmit_at)
        inp0 = inps[0]
        stat = (inp0["used_min"], inp0["used62"], inp0["used45"],
                s.duration, inp0["off_mem"], self._init_at,
                sim._err_thresh, sim._err_propagates,
                sim._err_graceful_ck)
        comp = _get_kernel(T, n, self._n_kinds,
                           (carry, stat, self._sc, xs))
        carry, ys = comp(carry, stat, self._sc, xs)
        names = _YS if T == 1 else _YS + _YS_STATE
        ys = {k: np.asarray(v) for k, v in zip(names, ys)}
        # accept ticks up to (and including) the first mid-block start_wait
        # (the host assigns re-admission periods the kernel can't see)
        accepted = T
        if T > 1:
            sw_any = ys["start_wait"].any(axis=1)
            for j in range(T - 1):
                if sw_any[j]:
                    accepted = j + 1
                    break
        last = accepted - 1
        # fleet/monitor state back to (writable) numpy — the authoritative
        # copies — from the last accepted tick
        if T == 1:
            (s.has_job, s.progress, s.checkpoint, s.wall, s.failed_until,
             s.outage_until, mon.state, mon._readmit_at) = (
                np.array(a) for a in carry)
        else:
            s.has_job = ys["has_job"][last].copy()
            s.progress = ys["progress"][last].copy()
            s.checkpoint = ys["checkpoint"][last].copy()
            s.wall = ys["wall"][last].copy()
            s.failed_until = ys["failed_until"][last].copy()
            s.outage_until = ys["outage_until"][last].copy()
            mon.state = ys["mstate"][last].copy()
            mon._readmit_at = ys["readmit_at"][last].copy()
        for j in range(accepted):
            inp = inps[j]
            t = inp["t"]
            busy = ys["busy"][j]
            core = {k: ys[k][j] for k in _YS}
            # the host-side masking the numpy core applies (shared formula)
            core["slowdown"] = np.where(busy, inp["slow_raw"], 1.0)
            core["tput"] = np.where(busy, inp["tput_speed"], 0.0)
            # post-tick state snapshots for the obs rollups (core contract
            # shared with the numpy engine): per-tick scan copies in block
            # mode — the synced live arrays hold only the *last* accepted
            # tick's state — and the synced carry at T=1 (where they are
            # one and the same)
            if T == 1:
                core["has_job"] = s.has_job
                core["mstate"] = mon.state
            else:
                core["has_job"] = ys["has_job"][j]
                core["mstate"] = ys["mstate"][j]
            cores.append(core)
            # sparse host-side monitor ring work, per tick and in order —
            # through the same VectorSysMonitor primitives the numpy
            # engine's update() uses
            ei = np.flatnonzero(ys["mon_evict"][j])
            if ei.size:
                mon.push_overlimit(ei, t)
            si = np.flatnonzero(ys["start_wait"][j])
            if si.size:
                mon._readmit_at[si] = t + mon.wait_periods(si, t)
        return accepted
