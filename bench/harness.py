"""One run of a cell: set-up, the measured window, the check, the result.

The window drives the program's own co-location loop, `Multiplexer.run`,
over two callables wired as in `chip_smoke.py`:

  online   keeps every engine slot busy with refill requests and times one
           `ServingEngine.step()` to `block_until_ready`;
  offline  times one jitted `make_train_step` (AdamW) through its loss.

An online request is the multiplexer's unit: one arrival, served by one
batched engine step.  The multiplexer's clock is the sum of the steps it
measured and of its idle quanta; the window is `seconds` of that clock, and
the loop runs one latency budget past it so that the last arrivals can be
served.  The harness rebuilds that clock from the loop's iterations (each
online call, each offline call, each consultation of the throttle's gate)
and checks it against the latencies the multiplexer reports.
"""
from __future__ import annotations

import gc
import importlib.util
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import arrivals, inputs, spec, stats, trace_reduce

# the traced part of a --trace 1 run: it starts this many wall seconds into
# the loop and lasts at most TRACE_S (a whole window's trace is too large to
# reduce within a run's time limit)
TRACE_LEAD_S = 1.0
TRACE_S = 8.0


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else at a fixed path inside the checkout.  Every program is
    cached, however fast it compiled, so that set-up repeats."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int):
    """The TPU devices, or exit without a result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX sees {devs[0].platform}; this "
                         "benchmark measures the chip and has no fallback")
    if len(devs) < n:
        raise SystemExit(f"{len(devs)} TPU chips, the cell needs {n}")
    return devs


def peaks_for(kind: str) -> dict:
    table = spec.load_json(spec.BENCH / "peaks.json")["kinds"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ----------------------------------------------------------------- online
class Online:
    """The engine at the configuration's widths, and the online callable."""

    def __init__(self, conf: dict, traffic: dict, seed: int):
        import jax
        from repro.serving.engine import EngineConfig, ServingEngine
        self.arch = conf["arch"]
        self.cfg = spec.model_config(self.arch)
        self.params = jax.block_until_ready(
            inputs.make_params(self.cfg, seed, inputs.ONLINE_WEIGHTS))
        self.engine = ServingEngine(self.cfg, self.params,
                                    EngineConfig(**conf["engine"]))
        self.traffic = traffic
        self.rng = inputs.rng(seed, inputs.REQUESTS)
        self.next_id = 0
        self.steps: list[dict] = []
        self.tracer = None
        self.loop_log: list | None = None

    def refill(self) -> None:
        from repro.serving.engine import ServeRequest
        e = self.engine
        for _ in range(e.ecfg.num_slots - e.active_slots - len(e.waiting)):
            prompt, n = inputs.refill_request(self.rng, self.traffic,
                                              self.cfg.vocab_size)
            e.submit(ServeRequest(self.next_id, prompt, n))
            self.next_id += 1

    def live(self) -> list[int]:
        """Positions held by each slot that the next step runs."""
        e = self.engine
        held = [int(e.slot_pos[i]) for i, r in enumerate(e.slot_req)
                if r is not None]
        new = min(e.ecfg.num_slots - len(held), len(e.waiting))
        return held + [0] * new

    def __call__(self, batch: int) -> float:
        import jax
        if self.tracer:
            self.tracer.tick()
        with jax.profiler.TraceAnnotation("bench.refill"):
            self.refill()
        live = self.live()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            self.engine.step()
            jax.block_until_ready(self.engine.cache)
        t1 = time.perf_counter()
        self.steps.append({"dt": t1 - t0, "live": live,
                           "traced": bool(self.tracer and self.tracer.on)})
        if self.loop_log is not None:
            self.loop_log.append(("online", t1 - t0, batch))
        return t1 - t0

    def free(self) -> None:
        self.engine.cache = None
        self.engine = None


# ---------------------------------------------------------------- offline
class Offline:
    """The training job at the configuration's widths, and the offline
    callable.  Set-up drives it through its first steps (the first
    compiles) and keeps what the check needs of them."""

    CHECKED_STEPS = 3

    def __init__(self, conf: dict, seed: int, fault: str | None = None):
        import jax
        import jax.numpy as jnp
        from repro.models import make_train_step
        from repro.optim.optimizer import AdamW, AdamWConfig
        self.conf = conf
        self.arch = conf["arch"]
        self.cfg = spec.model_config(self.arch)
        self.seed = seed
        self.opt = AdamW(AdamWConfig(**conf["adamw"]))
        if not self.opt.cfg.master_weights:
            raise SystemExit("the offline job must keep float32 master "
                             "weights: its check reads the change from them")
        params = inputs.make_params(self.cfg, seed, inputs.OFFLINE_WEIGHTS)
        # fresh buffers: the master copy of a float32 leaf would otherwise be
        # the leaf itself, and the step donates both
        self.state = [params, jax.jit(lambda p: jax.tree.map(
            jnp.copy, self.opt.init(p)))(params)]
        self.state_bytes = sum(x.nbytes for x in jax.tree.leaves(self.state))
        step = make_train_step(self.cfg, self.opt)
        if fault == "frozen_step":
            def step_fn(p, o, b):
                _, _, m = step(p, o, b)
                return p, o, m
        elif fault == "half_batch":
            def step_fn(p, o, b):
                half = b["tokens"].shape[0] // 2
                return step(p, o, {"tokens": b["tokens"][:half]})
        else:
            step_fn = step
        self.step_fn = jax.jit(step_fn, donate_argnums=(0, 1))
        self.batches = inputs.TokenBatches(seed, self.cfg.vocab_size,
                                           conf["batch"], conf["seq"])
        self.i = 0
        self.losses: list[float] = []
        self.steps: list[dict] = []
        self.tracer = None
        self.loop_log: list | None = None
        self.snap: dict = {}

    def __call__(self) -> float:
        import jax
        if self.tracer:
            self.tracer.tick()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.train_step"):
            batch = {"tokens": self.batches(self.i)}
            p, o, m = self.step_fn(self.state[0], self.state[1], batch)
            loss = float(m["loss"])
        t1 = time.perf_counter()
        self.state = [p, o]
        self.metrics = m
        self.i += 1
        self.losses.append(loss)
        self.steps.append({"dt": t1 - t0,
                           "traced": bool(self.tracer and self.tracer.on)})
        if self.loop_log is not None:
            self.loop_log.append(("offline", t1 - t0))
        return t1 - t0

    def first_steps(self) -> None:
        """Steps 1..3 through the window's own callable, keeping the first
        gradient as the optimizer holds it (m / (1 - b1) after one step)
        and the master weights' change after three."""
        import jax
        from bench.reference import leaf_norms
        norms = jax.jit(leaf_norms)
        b1 = self.opt.cfg.b1
        self()
        self.snap["grad1"] = np.asarray(norms(self.state[1]["m"])) / (1 - b1)
        self.snap["grad_norm1"] = float(self.metrics["grad_norm"])
        for _ in range(self.CHECKED_STEPS - 1):
            self()
        p0 = inputs.make_params(self.cfg, self.seed, inputs.OFFLINE_WEIGHTS)
        self.snap["change3"] = np.asarray(jax.jit(
            lambda m, p: leaf_norms(jax.tree.map(
                lambda a, b: a.astype(np.float32) - b.astype(np.float32), m, p)))(
            self.state[1]["master"], p0))
        self.snap["losses"] = list(self.losses[:self.CHECKED_STEPS])
        del p0
        self.steps.clear()

    def free(self) -> None:
        self.state = None


# ----------------------------------------------------------------- tracer
class Tracer:
    """Starts the profiler TRACE_LEAD_S into the loop and stops it TRACE_S
    later, between steps (never inside a timed step)."""

    def __init__(self, out_dir: str):
        self.dir = out_dir
        self.t_loop = None
        self.on = False
        self.done = False
        self._win = None

    def tick(self) -> None:
        import jax
        now = time.perf_counter()
        if self.t_loop is None:
            self.t_loop = now
        if not self.on and not self.done and now - self.t_loop >= TRACE_LEAD_S:
            jax.profiler.start_trace(self.dir)
            self._win = jax.profiler.TraceAnnotation("bench.window")
            self._win.__enter__()
            self.on, self.t_on = True, now
        elif self.on and now - self.t_on >= TRACE_S:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on:
            self._win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on, self.done = False, True


def _metric_readers(names: list[str]) -> dict:
    out = {}
    for name in names:
        path = spec.BENCH / "metrics" / f"{name}.py"
        sp = importlib.util.spec_from_file_location(f"bench_metric_{len(out)}",
                                                    path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        out[name] = mod.read
    return out


# -------------------------------------------------------------------- run
def run(cell: dict, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, fault: str | None = None,
        readings: bool = False) -> dict:
    """One run.  Returns {"result": the result line, "checks": [(name,
    value, limit)], "readings": the numbers read, with the control's and
    the half-batch fault's if `readings`}.  `fault` plants a fault for the
    harness's own tests: "frozen_step", "half_batch" and "altered_token" in
    the program, "fp8_control" in place of it (see check.run_checks)."""
    import jax
    from repro.core.multiplexer import Multiplexer, MuxConfig

    from bench import check

    if require_tpu:
        devs = require_chips(cell["chips"])
    else:
        devs = jax.devices()
    dev = devs[0]
    conf, traffic = cell["config_data"], cell["traffic_data"]
    with_offline = bool(traffic["offline"])

    # ------------------------------------------------------------ set-up
    online = Online(conf["online"], traffic, seed)
    if fault == "altered_token":
        plant_altered_token(online.engine)
    for _ in range(2):                       # compiles the decode program
        online(online.engine.ecfg.num_slots)
    online.steps.clear()
    offline = None
    if with_offline:
        offline = Offline(conf["offline"], seed, fault)
        offline.first_steps()
    mc = conf["mux"]
    device_bytes = int((dev.memory_stats() or {}).get("bytes_limit", 16 << 30))
    mux_cfg = MuxConfig(slo_slowdown=mc["slo_slowdown"],
                        max_batch=mc["max_batch"], quantum_s=mc["quantum_s"],
                        evict_after_violations=mc["evict_after_violations"],
                        latency_budget_s=mc["latency_budget_s"],
                        quota_frac=mc["quota_frac"], device_bytes=device_bytes)

    def offline_fn() -> float:
        if offline is None:
            raise RuntimeError("this cell runs no offline job")
        return offline()

    mux = Multiplexer(online, offline_fn, mc["base_step_s"],
                      mc["offline_step_s"], mux_cfg,
                      offline_state_bytes=offline.state_bytes if offline else 0)
    loop_log: list[tuple] = []
    gate = mux.throttle.should_launch

    def observed_gate(quantum: float = 1.0) -> bool:
        ok = gate(quantum)
        loop_log.append(("gate", quantum))
        return ok

    mux.throttle.should_launch = observed_gate
    rate = traffic["rate_share_of_capacity"] * conf["online"]["capacity_rps"]
    arr = arrivals.schedule(traffic["arrivals"], rate, seconds,
                            inputs.rng(seed, inputs.ARRIVALS))
    tmp = tracer = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = Tracer(tmp)
        online.tracer = tracer
        if offline:
            offline.tracer = tracer
    # set-up's objects go to the collector's permanent generation, so that
    # no collection inside the window scans them: a full collection of them
    # stalls the host for some 0.1 s, inside a timed step, and the
    # multiplexer's PID reads such a step as a slowdown
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s; {len(arr)} arrivals at {rate:.3f}/s over "
        f"{seconds}s ({traffic['arrivals']['kind']}); offline "
        f"{'on' if offline else 'off'}")

    # ------------------------------------------------------------ window
    online.loop_log = loop_log
    if offline:
        offline.loop_log = loop_log
    compiles = _count_compiles()
    pauses = _gc_pauses()
    t_wall = time.perf_counter()
    mst = mux.run(arr, horizon_s=seconds + mc["latency_budget_s"],
                  max_offline_steps=None if offline else 0)
    wall = time.perf_counter() - t_wall
    compiles = compiles()
    pauses = pauses()
    gc.unfreeze()
    if tracer:
        tracer.stop()
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    timeline = stats.replay(loop_iterations(loop_log))
    recs, acct_faults = stats.serve_records(arr, mux._latencies, timeline)
    lat_ms = [r["latency"] * 1e3 for r in recs]
    served = len(recs)
    if lat_ms:
        for name, mine, theirs in (("p50", stats.percentile(lat_ms, 50), mst.p50_ms),
                                   ("p99", stats.percentile(lat_ms, 99), mst.p99_ms)):
            if not math.isclose(mine, theirs, rel_tol=1e-9, abs_tol=1e-9):
                acct_faults.append(f"{name}: yardstick {mine} ms, program {theirs} ms")
    else:
        acct_faults.append("no request served")
    off_done = [ev for ev in timeline if ev[0] == "offline" and ev[2] <= seconds]
    log(f"loop: {wall:.3f}s wall for {timeline[-1][2] if timeline else 0:.3f}s "
        f"of the multiplexer's clock; served {served}/{len(arr)}; "
        f"{len(online.steps)} online and "
        f"{len(offline.steps) if offline else 0} offline steps; "
        f"evicted={mst.evicted} violations={mst.slo_violations}; "
        f"{compiles} compiles inside the window")
    longest = {kind: sorted((ev[2] - ev[1] for ev in timeline if ev[0] == kind),
                            reverse=True)[:3] for kind in ("online", "offline")}
    log("longest steps (ms): " + "; ".join(
        f"{k} {', '.join(f'{d * 1e3:.1f}' for d in v)}" for k, v in longest.items())
        + f"; {len(pauses)} garbage collections in the window, "
        f"{sum(pauses) * 1e3:.1f} ms in all, the longest "
        f"{max(pauses, default=0.0) * 1e3:.1f} ms")

    rec = {"window_s": seconds, "timeline": timeline, "requests": recs,
           "online_steps": online.steps,
           "offline_steps": offline.steps if offline else [],
           "online_arch": conf["online"]["arch"],
           "offline_arch": conf["offline"]["arch"] if offline else None,
           "offline_batch": conf["offline"]["batch"] if offline else 0,
           "offline_seq": conf["offline"]["seq"] if offline else 0,
           "peak": peaks_for(dev.device_kind) if require_tpu else None,
           "trace": None}

    result = {"correct": False, "attempted": len(arr),
              "failed": len(arr) - served, "metrics": {},
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": peak}}
    if trace:
        rec["trace"] = _reduce_trace(tmp, result)
    if not trace:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        vals = {"setup_s": setup_s}
        if lat_ms:
            vals["online_p99_ms"] = stats.percentile(lat_ms, 99)
        if offline:
            vals["offline_tokens_per_s"] = (
                len(off_done) * conf["offline"]["batch"] * conf["offline"]["seq"]
                / seconds)
        for name, unit in units.items():
            if name in vals:
                result["metrics"][name] = {"value": vals[name], "unit": unit}
    else:
        readers = _metric_readers([m["name"] for m in cell["per_layer"]])
        units = {m["name"]: m["unit"] for m in cell["per_layer"]}
        for name, read in readers.items():
            v = read(rec)
            if v is not None:
                result["metrics"][name] = {"value": float(v), "unit": units[name]}

    # ------------------------------------------------------------- check
    finished = list(online.engine.finished)
    mux = None
    online.free()
    train_snap = None
    if offline:
        train_snap = dict(offline.snap)
        offline.free()
    holder = {"params": online.params}
    online.params = None
    gc.collect()
    t_check = time.perf_counter()
    out = check.run_checks(conf, traffic, seed, holder, finished,
                           train_snap, acct_faults,
                           stand_in="fp8" if fault == "fp8_control" else None,
                           readings=readings)
    log(f"check: {time.perf_counter() - t_check:.3f}s")
    result["correct"] = all(v <= lim for _, v, lim in out["checks"])
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in out["checks"]}
    if tmp:
        shutil.rmtree(tmp, ignore_errors=True)
    return {"result": result, "checks": out["checks"],
            "readings": out.get("readings"), "wall_s": wall}


def plant_altered_token(engine) -> None:
    """A fault for the harness's own tests: every tenth decode step, each
    slot's greedy token is replaced by its neighbour id where the logits
    are produced."""
    import jax.numpy as jnp
    decode, n = engine._decode, [0]

    def altered(*args):
        logits, cache = decode(*args)
        n[0] += 1
        if n[0] % 10 == 0:
            top = jnp.argmax(logits[:, :engine.cfg.vocab_size], -1)
            logits = logits.at[jnp.arange(logits.shape[0]),
                               (top + 1) % engine.cfg.vocab_size].set(1e4)
        return logits, cache

    engine._decode = altered


def loop_iterations(loop_log: list) -> list:
    """The loop's iterations in order, ("online", dt, batch), ("offline",
    dt) or ("idle", quantum), from the calls logged in order: a gate the
    throttle consulted is an idle quantum unless an offline step follows
    it."""
    out = []
    for i, ev in enumerate(loop_log):
        if ev[0] != "gate":
            out.append(ev)
        elif i + 1 == len(loop_log) or loop_log[i + 1][0] != "offline":
            out.append(("idle", ev[1]))
    return out


def _count_compiles():
    """Counts XLA compilations from now until the returned function is
    called (which stops counting and returns the count)."""
    import jax
    n = [0]
    live = [True]

    def listener(event: str, _secs: float, **_kw) -> None:
        if live[0] and "backend_compile" in event:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)

    def stop() -> int:
        live[0] = False
        return n[0]

    return stop


def _gc_pauses():
    """Times the garbage collector's passes from now until the returned
    function is called (which stops timing and returns their durations)."""
    out: list[float] = []
    t0 = [0.0]

    def cb(phase: str, _info: dict) -> None:
        if phase == "start":
            t0[0] = time.perf_counter()
        else:
            out.append(time.perf_counter() - t0[0])

    gc.callbacks.append(cb)

    def stop() -> list[float]:
        gc.callbacks.remove(cb)
        return out

    return stop


def _reduce_trace(tmp: str, result: dict):
    paths = list(Path(tmp).rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    red = trace_reduce.Reduced(trace_reduce.load(str(paths[0])))
    win = red.window()
    if win is None:
        raise RuntimeError("the trace has no bench.window span")
    a, b = win
    busy = red.busy_ns(a, b) * 1e-9
    result["device"]["busy_s"] = busy
    result["device"]["window_s"] = (b - a) * 1e-9
    log(f"trace: {red.alignment(a, b)}")
    result["breakdown"] = {"device_ops": red.top_ops(a, b),
                           "idle_gaps": red.idle_gaps(a, b)}
    return red
