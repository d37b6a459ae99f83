"""Offline-training driver: the `train_step` workload MuxFlow schedules.

Runs a real training loop on the current backend (CPU smoke configs through
full pod configs), with: sharded params/optimizer via the rules engine,
deterministic data pipeline, async atomic checkpointing, graceful-exit signal
handling (checkpoint on SIGTERM — the §4.2 mechanism), heartbeats, and
optional gradient compression.

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-350m --smoke \
      --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.checkpoint.checkpointing import AsyncCheckpointer, latest_step, restore
from repro.configs import ARCH_IDS, get_config
from repro.core.errors import GracefulExit
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import init_params, make_train_step
from repro.optim.optimizer import AdamW, AdamWConfig
from repro.runtime.fault_tolerance import HeartbeatMonitor
from repro.sharding.context import activation_mesh
from repro.sharding.rules import batch_sharding, opt_state_sharding, param_sharding


def run(arch: str, *, smoke: bool = True, steps: int = 50, batch: int = 8,
        seq: int = 64, lr: float = 3e-3, ckpt_dir: str | None = None,
        ckpt_every: int = 20, microbatches: int = 1, mesh_shape=None,
        log_every: int = 10, resume: bool = True) -> dict:
    cfg = get_config(arch, smoke=smoke)
    devs = len(jax.devices())
    if mesh_shape is None:
        mesh_shape, axes = (devs, 1), ("data", "model")
    else:
        axes = ("data", "model")
    mesh = make_mesh(mesh_shape, axes)

    key = jax.random.PRNGKey(0)
    opt = AdamW(AdamWConfig(lr=lr, warmup_steps=max(steps // 10, 1),
                            total_steps=steps))
    with mesh, activation_mesh(mesh):
        params = init_params(key, cfg)
        p_sh = param_sharding(mesh, params, mode="train")
        params = jax.tree.map(jax.device_put, params, p_sh)
        opt_state = opt.init(params)
        o_sh = opt_state_sharding(mesh, p_sh, opt_state)
        opt_state = jax.tree.map(jax.device_put, opt_state, o_sh)

        pipe = TokenPipeline(DataConfig(cfg.vocab_size, seq, batch))
        step_fn = jax.jit(make_train_step(cfg, opt, microbatches=microbatches),
                          donate_argnums=(0, 1), out_shardings=(p_sh, o_sh, None))

        start = 0
        ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        if ckpt_dir and resume and latest_step(ckpt_dir) is not None:
            (params, opt_state), start = restore(
                ckpt_dir, (params, opt_state), shardings=(p_sh, o_sh))
            print(f"[train] resumed from step {start}")

        hb = HeartbeatMonitor(1)
        losses = []
        interrupted = False

        def on_checkpoint():
            nonlocal interrupted
            interrupted = True

        gex = GracefulExit(on_checkpoint=on_checkpoint)
        t0 = time.time()
        with gex:
            for step in range(start, steps):
                b_sh = batch_sharding(mesh, pipe.batch_at(step))
                data = {k: jax.device_put(v, b_sh[k])
                        for k, v in pipe.batch_at(step).items()}
                params, opt_state, metrics = step_fn(params, opt_state, data)
                loss = float(metrics["loss"])
                losses.append(loss)
                hb.heartbeat(0, step_time=time.time() - t0)
                if step % log_every == 0:
                    print(f"[train] step {step} loss {loss:.4f} "
                          f"({(time.time()-t0)/(step-start+1)*1e3:.0f} ms/step)",
                          flush=True)
                if ckpt and (step + 1) % ckpt_every == 0:
                    ckpt.save(step + 1, (params, opt_state))
                if interrupted:
                    print("[train] SIGTERM/SIGINT: graceful exit, checkpointing")
                    break
        if ckpt:
            # graceful exit persists progress before releasing the device
            ckpt.wait()
            if interrupted or steps % ckpt_every:
                ckpt.save(steps if not interrupted else step + 1,
                          (params, opt_state))
                ckpt.wait()
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "steps_done": len(losses), "interrupted": interrupted}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="xlstm-350m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()
    out = run(args.arch, smoke=args.smoke, steps=args.steps, batch=args.batch,
              seq=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
              ckpt_every=args.ckpt_every, microbatches=args.microbatches)
    print(f"[train] done: {out['steps_done']} steps, "
          f"final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
