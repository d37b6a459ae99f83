"""End-to-end driver: serve a small model with batched requests while an
offline training job space-shares the same device under MuxFlow protection.

Real JAX compute on this host: the online workload is `decode_step` of a
reduced h2o-danube (batched requests, Poisson arrivals); the offline workload
is `train_step` of a reduced granite-MoE.  The multiplexer's PID holds the
online latency inside the SLO while harvesting idle quanta for training —
the xCUDA/dynamic-SM mechanism at step granularity.

The §4.2 signal path is demonstrated end-to-end: a GracefulExit harness with
real checkpoint/release callbacks is installed on the multiplexer, and a
timer sends this process an actual SIGINT mid-run — the handler freezes
kernel launches (no more offline microsteps), checkpoints the training
state, and releases resources while the online workload keeps serving.
Ctrl-C exercises the same path by hand.

  PYTHONPATH=src python examples/serve_multiplex.py
"""
import os
import signal
import threading
import time

import jax

from repro.api import ArrivalProcess
from repro.configs import get_config
from repro.core.errors import GracefulExit
from repro.core.multiplexer import Multiplexer, MuxConfig
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models import init_cache, init_params, make_decode_step, make_train_step
from repro.optim.optimizer import AdamW, AdamWConfig


def main() -> None:
    key = jax.random.PRNGKey(0)
    # ---- online: danube decode over a standing KV cache
    on_cfg = get_config("h2o-danube-1.8b", smoke=True)
    on_params = init_params(key, on_cfg)
    decode = jax.jit(make_decode_step(on_cfg))
    BATCH, CAP = 8, 128
    cache = init_cache(on_cfg, BATCH, CAP)
    toks = jax.numpy.zeros((BATCH, 1), jax.numpy.int32)
    logits, cache = decode(on_params, cache, toks, 0)
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for i in range(1, 9):
        logits, cache = decode(on_params, cache, toks, i)
    jax.block_until_ready(logits)
    base_step = (time.perf_counter() - t0) / 8
    print(f"online decode step (batch {BATCH}): {base_step*1e3:.2f} ms")

    # ---- offline: granite-MoE training
    off_cfg = get_config("granite-moe-1b-a400m", smoke=True)
    opt = AdamW(AdamWConfig(lr=3e-3, total_steps=100_000))
    state = {"p": init_params(jax.random.PRNGKey(1), off_cfg)}
    state["o"] = opt.init(state["p"])
    train = jax.jit(make_train_step(off_cfg, opt), donate_argnums=(0, 1))
    pipe = TokenPipeline(DataConfig(off_cfg.vocab_size, 64, 8))
    state["p"], state["o"], m = train(state["p"], state["o"], pipe.batch_at(0))
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    state["p"], state["o"], m = train(state["p"], state["o"], pipe.batch_at(1))
    jax.block_until_ready(m["loss"])
    off_step = time.perf_counter() - t0
    losses = [float(m["loss"])]
    step_i = [2]
    print(f"offline train microstep: {off_step*1e3:.2f} ms")

    pos = [9]

    def online_fn(bs: int) -> float:
        t = time.perf_counter()
        out, _ = decode(on_params, cache, toks, pos[0] % (CAP - 1))
        jax.block_until_ready(out)
        pos[0] += 1
        return time.perf_counter() - t

    def offline_fn() -> float:
        t = time.perf_counter()
        state["p"], state["o"], m = train(state["p"], state["o"],
                                          pipe.batch_at(step_i[0]))
        jax.block_until_ready(m["loss"])
        losses.append(float(m["loss"]))
        step_i[0] += 1
        return time.perf_counter() - t

    n_req = 150
    # arrival rate sized so the device is ~half-loaded by online traffic;
    # the latency budget absorbs at most one offline microstep of queueing
    # (the paper: latency demands >100ms, a ~10ms share-slowdown is fine).
    # Same seeded ArrivalProcess the sim and profiler consume — one
    # definition of "requests arrive" across the repo.
    process = ArrivalProcess.poisson(
        mean_gap=max(base_step * 2.0, off_step * 1.2), seed=0)
    arrivals = process.first_n(n_req).tolist()
    horizon = arrivals[-1] + 0.5
    budget = base_step * 2 + off_step * 2.5
    print(f"\nserving {n_req} request batches over ~{horizon:.1f}s; "
          f"latency budget {budget*1e3:.0f}ms; offline fills the slack...")
    mux = Multiplexer(online_fn, offline_fn, base_step, off_step,
                      MuxConfig(slo_slowdown=1.25, latency_budget_s=budget))

    # ---- §4.2 graceful exit, wired end-to-end: freeze -> checkpoint ->
    # release, driven by a *real* signal delivered mid-run
    ckpt: dict = {}
    released: list[float] = []

    def on_checkpoint() -> None:
        ckpt["step"] = step_i[0]
        ckpt["loss"] = losses[-1]
        ckpt["params"] = state["p"]          # persisted snapshot stand-in

    def on_release() -> None:
        released.append(time.perf_counter())  # CUDA-context release analogue

    mux.graceful = GracefulExit(throttle=mux.throttle,
                                on_checkpoint=on_checkpoint,
                                on_release=on_release)
    # deliver SIGINT partway through serving (Ctrl-C does the same by hand)
    killer = threading.Timer(horizon * 0.5,
                             lambda: os.kill(os.getpid(), signal.SIGINT))
    killer.daemon = True
    killer.start()
    s = mux.run(arrivals, horizon)
    killer.cancel()
    print(f"\nonline : served={s.served} p50={s.p50_ms:.2f}ms "
          f"p99={s.p99_ms:.2f}ms (base {s.base_ms:.2f}ms)")
    print(f"offline: {s.offline_steps} train steps "
          f"(loss {losses[0]:.3f} -> {losses[-1]:.3f}), "
          f"offline time share={s.offline_duty:.2f}, oversold={s.oversold:.2f}")
    print(f"safety : evicted={s.evicted}, slo_violations={s.slo_violations}")
    gex = mux.graceful
    if gex.triggered is not None:
        print(f"graceful exit: caught {gex.triggered.value} -> froze kernel "
              f"launches (frozen={mux.throttle.frozen}), checkpointed at "
              f"step {ckpt.get('step')} (loss {ckpt.get('loss', 0.0):.3f}), "
              f"released context ({len(released)} release callback)")
        print("online kept serving after the signal: errors propagated = 0")
    else:
        print("graceful exit: signal did not arrive before the horizon "
              "(run was too short); Ctrl-C exercises the same path")


if __name__ == "__main__":
    main()
