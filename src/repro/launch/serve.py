"""Online-serving driver: the `decode_step` workload MuxFlow protects —
optionally space-shared with an offline train step through the multiplexer.

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-350m --smoke \
      --requests 200 --qps 40 --share
  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b --full
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.core.multiplexer import Multiplexer, MuxConfig
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.cache import enable_compile_cache
from repro.models import (init_cache, init_params, make_decode_step,
                          make_train_step)
from repro.optim.optimizer import AdamW, AdamWConfig


def run(arch: str, *, smoke: bool = True, requests: int = 200,
        qps: float = 40.0, share: bool = False, slo: float = 1.25,
        seed: int = 0, batch: int = 4, kv_cap: int = 128) -> dict:
    cfg = get_config(arch, smoke=smoke)
    key = jax.random.PRNGKey(seed)
    params = init_params(key, cfg)
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))
    cache = init_cache(cfg, batch, kv_cap,
                       src_len=kv_cap if cfg.enc_layers else 0)
    toks = jnp.zeros((batch, 1), jnp.int32)
    # warm up + measure base step
    logits, cache = decode(params, cache, toks, 0)
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for i in range(1, 6):
        logits, cache = decode(params, cache, toks, i)
    jax.block_until_ready(logits)
    base_step = (time.perf_counter() - t0) / 5
    pos = [6]

    def online_fn(bs: int) -> float:
        nonlocal cache
        t = time.perf_counter()
        out, cache = decode(params, cache, toks, pos[0] % (kv_cap - 1))
        jax.block_until_ready(out)
        pos[0] += 1
        return time.perf_counter() - t

    state = {}
    offline_bytes = 0
    if share:
        opt = AdamW(AdamWConfig(lr=1e-3, total_steps=10_000))
        tparams = init_params(jax.random.PRNGKey(1), cfg)
        topt = opt.init(tparams)
        train = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
        pipe = TokenPipeline(DataConfig(cfg.vocab_size, 32, 4))
        state = {"p": tparams, "o": topt, "step": 0}
        offline_bytes = sum(x.nbytes for x in jax.tree.leaves((tparams, topt)))
        # measure offline microstep
        p, o, _ = train(state["p"], state["o"], pipe.batch_at(0))
        jax.block_until_ready(jax.tree.leaves(p)[0])
        t0 = time.perf_counter()
        p, o, _ = train(p, o, pipe.batch_at(1))
        jax.block_until_ready(jax.tree.leaves(p)[0])
        off_step = time.perf_counter() - t0
        state.update(p=p, o=o, step=2)

        def offline_fn() -> float:
            t = time.perf_counter()
            state["p"], state["o"], _ = train(state["p"], state["o"],
                                              pipe.batch_at(state["step"]))
            jax.block_until_ready(jax.tree.leaves(state["p"])[0])
            state["step"] += 1
            return time.perf_counter() - t
    else:
        off_step = 1.0

        def offline_fn() -> float:
            return off_step

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=requests)).tolist()
    horizon = arrivals[-1] + 1.0
    mem = jax.devices()[0].memory_stats()
    mcfg = MuxConfig(slo_slowdown=slo)
    if mem and "bytes_limit" in mem:
        mcfg.device_bytes = int(mem["bytes_limit"])
    mux = Multiplexer(online_fn, offline_fn, base_step, off_step, mcfg,
                      offline_state_bytes=offline_bytes)
    stats = mux.run(arrivals, horizon,
                    max_offline_steps=None if share else 0)
    return {"base_ms": base_step * 1e3, "p50_ms": stats.p50_ms,
            "p99_ms": stats.p99_ms, "served": stats.served,
            "offline_steps": stats.offline_steps,
            "offline_duty": stats.offline_duty, "oversold": stats.oversold,
            "train_steps_done": state.get("step", 0)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="xlstm-350m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--qps", type=float, default=40.0)
    ap.add_argument("--share", action="store_true")
    ap.add_argument("--slo", type=float, default=1.25)
    args = ap.parse_args()
    enable_compile_cache()
    out = run(args.arch, smoke=args.smoke, requests=args.requests,
              qps=args.qps, share=args.share, slo=args.slo)
    print(f"[serve] base={out['base_ms']:.2f}ms p50={out['p50_ms']:.2f}ms "
          f"p99={out['p99_ms']:.2f}ms served={out['served']} "
          f"offline_steps={out['offline_steps']} oversold={out['oversold']:.2f}")


if __name__ == "__main__":
    main()
