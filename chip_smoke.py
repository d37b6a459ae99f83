#!/usr/bin/env python3
"""Chip smoke test: MuxFlow's co-location path on one TPU at full width.

    python3 chip_smoke.py [--seed 0]

One process, one chip.  The phases run in order and the first failure ends
the run with a non-zero exit; nothing is caught and continued past.

  a. device    a TPU must be present: there is no CPU fallback.
  b. kernels   decode_attention, flash_attention and ssm_scan compiled by
               Mosaic at real widths, against kernels/ref.py.
  c. online    h2o-danube-1.8b FULL through ServingEngine (8 slots, KV
               capacity 2048), against a full-sequence forward of the same
               parameters.
  d. offline   xlstm-350m FULL AdamW train steps: the loss is finite.
  e. colocate  the Multiplexer interleaves engine steps (online) with train
               steps (offline) over seeded Poisson arrivals, with the
               device's own memory limit and the offline state's real bytes
               behind the MemoryQuota.

Weights and data are random, made from --seed.  The last line of stdout is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro  # noqa: E402

if [Path(p).resolve() for p in repro.__path__] != [HERE / "src" / "repro"]:
    sys.exit(f"repro imported from {list(repro.__path__)}, not this checkout")

from repro.configs import get_config  # noqa: E402
from repro.core.multiplexer import Multiplexer, MuxConfig  # noqa: E402
from repro.data.pipeline import DataConfig, TokenPipeline  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import init_params, make_train_step  # noqa: E402
from repro.models.model import forward  # noqa: E402
from repro.optim.optimizer import AdamW, AdamWConfig  # noqa: E402
from repro.serving.engine import (EngineConfig, ServeRequest,  # noqa: E402
                                  ServingEngine)

# kernel tolerances: bf16 attention as in tests/test_kernels.py; the f32
# scan is looser than the interpret tests' 1e-4 because Mosaic's and XLA's
# exp may differ by an ulp, compounded over 2048 recurrent steps
ATTN_TOL = 2e-2
SCAN_TOL = 1e-3
# online: engine logits (bf16, decode path with a KV cache) against the
# full-sequence forward (bf16, prefill-shaped attention), in logit units;
# random-init logits have unit scale
LOGIT_TOL = 0.25

# kernel widths: h2o-danube-1.8b decode (B, Skv, H, Hk, d) and prefill
# (B, S, H, Hk, d, window); jamba-1.5-large's Mamba (B, S, d_inner, N)
DECODE_SHAPE = (8, 2048, 32, 8, 80)
FLASH_SHAPE = (1, 2048, 32, 8, 80, 4096)
SCAN_SHAPE = (1, 2048, 16384, 16)

ONLINE_ARCH, OFFLINE_ARCH = "h2o-danube-1.8b", "xlstm-350m"
SLOTS, KV_CAPACITY = 8, 2048
PROMPT_LEN, NEW_TOKENS = (64, 256), (16, 32)
TRAIN_BATCH, TRAIN_SEQ = 8, 512


def init_weights(seed: int, cfg):
    """Random weights from a seed, as one compiled program (eager init
    dispatches and compiles every op of a 24-layer model on its own)."""
    return jax.jit(init_params, static_argnums=1)(jax.random.PRNGKey(seed),
                                                  cfg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(cond: bool, phase: str, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[{phase}] FAILED: {msg}")


def max_err(got, want) -> tuple[float, float]:
    """(max |got - want|, max |want|) in f32."""
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    return float(np.max(np.abs(g - w))), float(np.max(np.abs(w)))


# --------------------------------------------------------------- a. device
def device_phase():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"[device] FAILED: no TPU; JAX sees {dev.platform}")
    limit = int(dev.memory_stats()["bytes_limit"])
    log("device", f"platform={dev.platform} kind={dev.device_kind} "
                  f"count={len(jax.devices())} bytes_limit={limit}")
    return dev, limit


# -------------------------------------------------------------- b. kernels
def compiled_kernel(name: str, fn, *args, **static):
    """Lower and compile one jitted kernel wrapper; it must be a Mosaic
    custom call, not an interpreted or XLA fallback."""
    t = time.perf_counter()
    exe = fn.lower(*args, interpret=False, **static).compile()
    check("tpu_custom_call" in exe.as_text(), "kernels",
          f"{name}: no tpu_custom_call in the compiled program")
    log("kernels", f"{name}: compiled in {time.perf_counter() - t:.2f}s "
                   "(tpu_custom_call present)")
    return exe


def kernel_phase(seed: int) -> None:
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 12)
    bf = jnp.bfloat16
    rnd = lambda k, shape, dt=jnp.float32: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dt)

    B, Skv, H, Hk, d = DECODE_SHAPE
    q = rnd(keys[0], (B, 1, H, d), bf)
    kc = rnd(keys[1], (B, Skv, Hk, d), bf)
    vc = rnd(keys[2], (B, Skv, Hk, d), bf)
    kv_len = jax.random.randint(keys[3], (B,), 1, Skv + 1, jnp.int32)
    exe = compiled_kernel(f"decode_attention B{B} Skv{Skv} H{H}/{Hk} d{d} bf16",
                          ops.decode_attention, q, kc, vc, kv_len)
    err, scale = max_err(exe(q, kc, vc, kv_len),
                         jax.jit(ref.decode_attention_reference)(
                             q, kc, vc, kv_len))
    log("kernels", f"decode_attention max|err|={err:.3e} "
                   f"(max|ref|={scale:.3f}, tol {ATTN_TOL})")
    check(err <= ATTN_TOL * max(1.0, scale), "kernels", "decode_attention")

    B, S, H, Hk, d, W = FLASH_SHAPE
    q = rnd(keys[4], (B, S, H, d), bf)
    k = rnd(keys[5], (B, S, Hk, d), bf)
    v = rnd(keys[6], (B, S, Hk, d), bf)
    exe = compiled_kernel(
        f"flash_attention B{B} S{S} H{H}/{Hk} d{d} causal w{W} bf16",
        ops.flash_attention, q, k, v, causal=True, window=W)
    want = jax.jit(lambda q, k, v: ref.attention_reference(
        q, k, v, causal=True, window=W))(q, k, v)
    err, scale = max_err(exe(q, k, v), want)
    log("kernels", f"flash_attention max|err|={err:.3e} "
                   f"(max|ref|={scale:.3f}, tol {ATTN_TOL})")
    check(err <= ATTN_TOL * max(1.0, scale), "kernels", "flash_attention")

    B, S, di, N = SCAN_SHAPE
    dt = jax.nn.softplus(rnd(keys[7], (B, S, di)))
    x = rnd(keys[8], (B, S, di))
    Bc = rnd(keys[9], (B, S, N))
    Cc = rnd(keys[10], (B, S, N))
    A_log = jnp.log(jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32),
                                     (di, N)))
    exe = compiled_kernel(f"ssm_scan S{S} d_inner{di} N{N} f32",
                          ops.ssm_scan, dt, x, Bc, Cc, A_log)
    err, scale = max_err(exe(dt, x, Bc, Cc, A_log),
                         jax.jit(ref.ssm_scan_reference)(dt, x, Bc, Cc, A_log))
    log("kernels", f"ssm_scan max|err|={err:.3e} "
                   f"(max|ref|={scale:.3f}, tol {SCAN_TOL})")
    check(err <= SCAN_TOL * max(1.0, scale), "kernels", "ssm_scan")


# --------------------------------------------------------------- c. online
def make_requests(rng, n: int, vocab: int, first_id: int = 0):
    return [ServeRequest(first_id + i,
                         rng.integers(0, vocab, int(rng.integers(
                             PROMPT_LEN[0], PROMPT_LEN[1] + 1))
                                      ).astype(np.int32),
                         max_new_tokens=int(rng.integers(
                             NEW_TOKENS[0], NEW_TOKENS[1] + 1)))
            for i in range(n)]


def online_phase(seed: int, cfg=None, slots: int = SLOTS,
                 kv_capacity: int = KV_CAPACITY):
    cfg = cfg or get_config(ONLINE_ARCH)
    t = time.perf_counter()
    params = jax.block_until_ready(init_weights(seed, cfg))
    log("online", f"{cfg.name}: {cfg.num_layers}L d{cfg.d_model} "
                  f"{cfg.num_heads}H/{cfg.num_kv_heads}KV hd{cfg.head_dim} "
                  f"v{cfg.vocab_size} {jnp.dtype(cfg.dtype).name}, "
                  f"{sum(x.nbytes for x in jax.tree.leaves(params))} param "
                  f"bytes, init {time.perf_counter() - t:.1f}s")
    engine = ServingEngine(cfg, params, EngineConfig(
        num_slots=slots, kv_capacity=kv_capacity))
    # observe (never alter) the engine's decode outputs, to read each
    # request's logits at its last prompt position
    decode, last = engine._decode, {}

    def observed(*args):
        logits, cache = decode(*args)
        last["logits"] = logits
        return logits, cache

    engine._decode = observed
    reqs = make_requests(np.random.default_rng(seed), slots, cfg.vocab_size)
    for r in reqs:
        engine.submit(r)
    first_logits: dict[int, np.ndarray] = {}
    t = time.perf_counter()
    while engine.waiting or engine.active_slots:
        engine.step()
        for slot, r in enumerate(engine.slot_req):
            if r is not None and len(r.output) == 1 \
                    and r.request_id not in first_logits:
                first_logits[r.request_id] = np.asarray(
                    last["logits"][slot, :cfg.vocab_size], np.float32)
    jax.block_until_ready(engine.cache)
    wall = time.perf_counter() - t
    engine._decode = decode
    check(len(engine.finished) == len(reqs), "online",
          f"{len(engine.finished)}/{len(reqs)} requests answered")
    n_tok = sum(len(r.output) for r in reqs)
    log("online", f"answered {len(reqs)} requests (prompts "
                  f"{min(len(r.prompt) for r in reqs)}-"
                  f"{max(len(r.prompt) for r in reqs)} tokens, {n_tok} new "
                  f"tokens) in {engine.steps} engine steps, {wall:.2f}s wall")

    # reference: one full-sequence forward over prompt + generated tokens
    L = PROMPT_LEN[1] + NEW_TOKENS[1]
    toks = np.zeros((len(reqs), L), np.int32)
    for i, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)])
        toks[i, :len(seq)] = seq
    ref_logits = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t},
                                              mode="train")[0])(
        params, jnp.asarray(toks))
    ref_logits = np.asarray(ref_logits[..., :cfg.vocab_size], np.float32)
    worst, checked, agreed = 0.0, 0, 0
    for i, r in enumerate(reqs):
        p0 = len(r.prompt) - 1
        check(r.request_id in first_logits, "online",
              f"request {r.request_id}: no first-token logits observed")
        worst = max(worst, float(np.max(np.abs(
            first_logits[r.request_id] - ref_logits[i, p0]))))
        for j, tok in enumerate(r.output):
            row = ref_logits[i, p0 + j]
            top2 = np.partition(row, -2)[-2:]
            if top2[1] - top2[0] > LOGIT_TOL:
                checked += 1
                agreed += int(tok == int(np.argmax(row)))
    log("online", f"last-prompt-position logits max|err|={worst:.4f} "
                  f"(tol {LOGIT_TOL}); greedy tokens agree at "
                  f"{agreed}/{checked} positions whose reference top-2 "
                  f"margin exceeds the tolerance (of {n_tok})")
    check(worst <= LOGIT_TOL, "online", "logits disagree with the reference")
    check(checked > 0 and agreed == checked, "online",
          "greedy tokens disagree, or no position was decisive")
    return engine


# -------------------------------------------------------------- d. offline
def offline_phase(seed: int, cfg=None, batch: int = TRAIN_BATCH,
                  seq: int = TRAIN_SEQ, steps: int = 3):
    cfg = cfg or get_config(OFFLINE_ARCH)
    opt = AdamW(AdamWConfig(lr=3e-4, warmup_steps=10, total_steps=1000))
    params = init_weights(seed + 1, cfg)
    state = {"p": params, "o": opt.init(params), "step": 0}
    state_bytes = sum(x.nbytes for x in jax.tree.leaves((state["p"],
                                                          state["o"])))
    train = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    pipe = TokenPipeline(DataConfig(cfg.vocab_size, seq, batch, seed=seed))

    def step() -> float:
        state["p"], state["o"], m = train(state["p"], state["o"],
                                          pipe.batch_at(state["step"]))
        state["step"] += 1
        loss = float(m["loss"])      # waits for the step to finish
        check(math.isfinite(loss), "offline",
              f"loss {loss} at step {state['step']}")
        return loss

    t = time.perf_counter()
    losses = [step() for _ in range(steps)]
    log("offline", f"{cfg.name}: {cfg.num_layers}L d{cfg.d_model} "
                   f"v{cfg.vocab_size}, AdamW, batch {batch} x seq {seq}; "
                   f"{steps} steps (first compiles) in "
                   f"{time.perf_counter() - t:.1f}s, losses "
                   + " ".join(f"{x:.4f}" for x in losses)
                   + f"; params+optimizer {state_bytes} bytes")
    return step, state_bytes


# ------------------------------------------------------------- e. colocate
def colocate_phase(seed: int, engine: ServingEngine, train_step,
                   offline_bytes: int, device_bytes: int,
                   requests: int = 400):
    rng = np.random.default_rng(seed + 2)
    next_id = [10_000]

    def online_fn(_batch: int) -> float:
        # keep every slot busy: the step is one fixed-shape program anyway
        free = engine.ecfg.num_slots - engine.active_slots - len(engine.waiting)
        for r in make_requests(rng, free, engine.cfg.vocab_size, next_id[0]):
            engine.submit(r)
        next_id[0] += free
        t = time.perf_counter()
        engine.step()
        jax.block_until_ready(engine.cache)
        return time.perf_counter() - t

    def offline_fn() -> float:
        t = time.perf_counter()
        train_step()
        return time.perf_counter() - t

    base = float(np.median([online_fn(SLOTS) for _ in range(8)]))
    off = float(np.median([offline_fn() for _ in range(2)]))
    # step-granularity interleaving: a request may wait one offline step
    # plus its own online step, and no more
    cfg = MuxConfig(max_batch=engine.ecfg.num_slots, quantum_s=base,
                    latency_budget_s=base + off, device_bytes=device_bytes)
    qps = 0.25 * cfg.max_batch / base
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=requests)).tolist()
    mux = Multiplexer(online_fn, offline_fn, base, off, cfg,
                      offline_state_bytes=offline_bytes)
    t = time.perf_counter()
    st = mux.run(arrivals, horizon_s=arrivals[-1] + 4 * off)
    log("colocate", f"online step {base * 1e3:.2f}ms, offline step "
                    f"{off * 1e3:.2f}ms alone; {requests} Poisson arrivals "
                    f"at {qps:.1f}/s; quota {offline_bytes} of "
                    f"{int(cfg.quota_frac * device_bytes)} bytes")
    log("colocate", f"served={st.served} p50={st.p50_ms:.2f}ms "
                    f"p99={st.p99_ms:.2f}ms offline_steps={st.offline_steps} "
                    f"offline_time_share={st.offline_duty:.3f} "
                    f"oversold={st.oversold:.3f} "
                    f"slo_violations={st.slo_violations} evicted={st.evicted} "
                    f"({time.perf_counter() - t:.1f}s wall)")
    check(st.served > 0 and math.isfinite(st.p99_ms), "colocate",
          "no online request served")
    check(st.offline_steps > 0, "colocate", "no offline step ran")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    t0 = time.perf_counter()
    dev, limit = device_phase()
    kernel_phase(args.seed)
    engine = online_phase(args.seed)
    train_step, offline_bytes = offline_phase(args.seed)
    colocate_phase(args.seed, engine, train_step, offline_bytes, limit)
    peak = dev.memory_stats().get("peak_bytes_in_use")
    log("done", f"all phases passed in {time.perf_counter() - t0:.1f}s; "
                f"peak_bytes_in_use={peak} of bytes_limit={limit}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
