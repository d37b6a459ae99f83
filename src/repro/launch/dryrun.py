"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes, record memory / cost / roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all            # single-pod, all cells
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
Results are cached per-cell in experiments/dryrun/*.json (--force to redo).
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices.  These two
# lines MUST precede every other import — jax locks the device count on init.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config, batch_specs, decode_specs, supports_shape
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import init_cache, init_params, make_decode_step, make_prefill, make_train_step
from repro.optim.optimizer import AdamW, AdamWConfig
from repro.sharding.context import activation_mesh
from repro.sharding.rules import (batch_sharding, cache_sharding,
                                  opt_state_sharding, param_sharding)

# TPU v5e-like hardware model (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

# Gradient-accumulation factors for cells whose activations exceed HBM at
# full global batch (production practice for very large models).
TRAIN_MICROBATCHES = {
    "jamba-1.5-large-398b": 16,
}

# Beyond-paper optimized variant (§Perf): per-arch config overrides applied
# with --variant opt.  The baseline records stay untouched.
OPT_OVERRIDES = {
    "deepseek-v2-lite-16b": {"moe_impl": "a2a"},
    "granite-moe-1b-a400m": {"moe_impl": "a2a"},
    "jamba-1.5-large-398b": {"moe_impl": "a2a"},
}

# §Perf: the opt variant amortizes FSDP gathers / grad reduce-scatters over
# fewer, larger microbatches (jamba iteration 3: 16 -> 8).
OPT_MICROBATCHES = {
    "jamba-1.5-large-398b": 8,
}


def model_flops_per_device(cfg, shape, n_devices: int) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / n_devices
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / n_devices
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch / n_devices


def build_cell(cfg, shape, mesh, *, serve_mode: str | None = None,
               microbatches: dict | None = None):
    """Returns (jitted_fn, example_args as ShapeDtypeStructs)."""
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    if serve_mode is None:
        # big models cannot replicate across the data axis in serving:
        # TP-only leaves param_bytes/TP per device; above ~6 GiB switch to
        # 2D (FSDP x TP) weight sharding (weight-gathered serving).
        pbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
        serve_mode = "serve_big" if pbytes / mesh.shape["model"] > 6 * 2**30 else "serve"
    p_mode = "train" if shape.kind == "train" else serve_mode
    p_sh = param_sharding(mesh, params, mode=p_mode)
    params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                          params, p_sh)

    if shape.kind == "train":
        opt = AdamW(AdamWConfig(master_weights=False))
        mb = (microbatches or TRAIN_MICROBATCHES).get(cfg.name, 1)
        step_fn = make_train_step(cfg, opt, microbatches=mb)
        opt_state = jax.eval_shape(opt.init, params)
        o_sh = opt_state_sharding(mesh, p_sh, opt_state)
        opt_state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                                 opt_state, o_sh)
        batch = batch_specs(cfg, shape)
        b_sh = batch_sharding(mesh, batch)
        batch = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                             batch, b_sh)
        fn = jax.jit(step_fn, donate_argnums=(0, 1),
                     out_shardings=(p_sh, o_sh, None))
        return fn, (params, opt_state, batch)

    if shape.kind == "prefill":
        prefill = make_prefill(cfg)
        batch = batch_specs(cfg, shape)
        b_sh = batch_sharding(mesh, batch)
        batch = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                             batch, b_sh)
        fn = jax.jit(prefill)
        return fn, (params, batch)

    # decode
    decode = make_decode_step(cfg)
    specs = decode_specs(cfg, shape)
    c_sh = cache_sharding(mesh, specs["cache"])
    cache = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                         specs["cache"], c_sh)
    tokens = jax.ShapeDtypeStruct(specs["tokens"].shape, specs["tokens"].dtype,
                                  sharding=NamedSharding(mesh, P()))
    dp = dp_axes(mesh)
    dp_spec = dp[0] if len(dp) == 1 else dp
    B = shape.global_batch
    import numpy as np
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    logit_spec = P(dp_spec if B % dp_size == 0 else None, "model")
    fn = jax.jit(decode, donate_argnums=(1,),
                 out_shardings=(NamedSharding(mesh, logit_spec), c_sh))
    pos = jnp.asarray(specs["pos"], jnp.int32)
    return fn, (params, cache, tokens, pos)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, dump_hlo=None,
             variant: str = "base", overrides=None) -> dict:
    cfg = get_config(arch)
    if variant == "opt":
        cfg = get_config(arch, **OPT_OVERRIDES.get(arch, {}))
    if overrides:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    ok, reason = supports_shape(cfg, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size
    t0 = time.time()
    try:
        mbs = dict(TRAIN_MICROBATCHES)
        if variant == "opt":
            mbs.update(OPT_MICROBATCHES)
        with mesh, activation_mesh(mesh):
            fn, args = build_cell(cfg, shape, mesh, microbatches=mbs)
            lowered = fn.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            ma = compiled.memory_analysis()
            print(ma)
            ca = compiled.cost_analysis()
            print({k: v for k, v in ca.items() if k in ("flops", "bytes accessed")})
            text = compiled.as_text()
            if dump_hlo:
                with open(dump_hlo, "w") as f:
                    f.write(text)
            st = analyze(text, total_devices=n_dev)
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug to record
        rec.update(status="failed", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        return rec

    mem = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
    }
    mem["peak_device_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                                + mem["temp_bytes"] - mem["alias_bytes"])
    mf = model_flops_per_device(cfg, shape, n_dev)
    compute_s = st.flops / PEAK_FLOPS
    memory_s = st.bytes_accessed / HBM_BW
    collective_s = st.collective_bytes / LINK_BW
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", collective_s), key=lambda kv: kv[1])[0]
    bound = max(compute_s, memory_s, collective_s)
    # decode is bandwidth-bound by nature: its roofline fraction is measured
    # against the *minimal* per-step HBM traffic (params + cache read once)
    model_bytes = None
    if shape.kind == "decode":
        cache_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(args[1]))
        pb = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(args[0]))
        model_bytes = (cache_bytes + pb * (cfg.active_param_count()
                                           / max(cfg.param_count(), 1))) / n_dev
    rec.update(
        status="ok",
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=mem,
        cost_analysis={"flops": ca.get("flops"), "bytes": ca.get("bytes accessed")},
        hlo={"dot_flops": st.flops, "elementwise_flops": st.elementwise_flops,
             "bytes": st.bytes_accessed, "collective_bytes": st.collective_bytes,
             "collective_count": st.collective_count,
             "collective_breakdown": st.collective_breakdown},
        terms={"compute_s": compute_s, "memory_s": memory_s,
               "collective_s": collective_s},
        dominant=dominant,
        model_flops=mf,
        useful_ratio=(mf / st.flops if st.flops else 0.0),
        roofline_fraction=(((model_bytes / HBM_BW) / bound)
                           if (model_bytes and bound) else
                           ((mf / PEAK_FLOPS) / bound if bound else 0.0)),
        model_bytes=model_bytes,
    )
    return rec


def cell_path(arch, shape_name, multi_pod, variant="base"):
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = "" if variant == "base" else f"__{variant}"
    return os.path.join(OUT_DIR, f"{arch}__{shape_name}__{mesh}{suffix}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--dump-hlo")
    ap.add_argument("--variant", default="base", choices=["base", "opt"])
    args = ap.parse_args()
    os.makedirs(OUT_DIR, exist_ok=True)
    cells = []
    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    for arch, shape_name in cells:
        path = cell_path(arch, shape_name, args.multi_pod, args.variant)
        if os.path.exists(path) and not args.force:
            print(f"[skip cached] {arch} × {shape_name}")
            continue
        print(f"=== {arch} × {shape_name} ({'multi' if args.multi_pod else 'single'}-pod, "
              f"{args.variant}) ===", flush=True)
        rec = run_cell(arch, shape_name, multi_pod=args.multi_pod,
                       dump_hlo=args.dump_hlo, variant=args.variant)
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        if rec["status"] == "ok":
            t = rec["terms"]
            print(f"  ok: compile={rec['compile_s']}s peak_mem="
                  f"{rec['memory']['peak_device_bytes']/2**30:.2f}GiB "
                  f"terms(c/m/coll)={t['compute_s']:.4f}/{t['memory_s']:.4f}/"
                  f"{t['collective_s']:.4f}s dominant={rec['dominant']} "
                  f"roofline={rec['roofline_fraction']:.3f}", flush=True)
        else:
            print(f"  {rec['status']}: {rec.get('reason') or rec.get('error')}",
                  flush=True)


if __name__ == "__main__":
    main()
