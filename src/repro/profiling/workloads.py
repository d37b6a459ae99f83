"""The workload catalog: the repo's real jax_pallas executables as named,
seeded, role-tagged :class:`Workload` records the pair-profiling harness can
run.

This module is the single metrics-sampling path (it absorbed the seed's
53-line ``core/profiler.py``, whose deprecation shim has since been
removed).  A profile has two sources of truth, kept deliberately separate:

  * **Execution** — :func:`execute` really runs the step function (Pallas
    kernels interpreted or compiled, as the caller says) and records an
    output checksum plus wall-time stats.  Wall time is *measurement-only*: it
    proves the workload runs and how fast, but it never enters a speed-matrix
    artifact, because artifacts must be byte-identical across runs.
  * **Cost model** — deterministic per-step cost from the declared analytic
    FLOP/byte counts against T4-class peaks (``roofline-v1``).  The harness's
    virtual clock runs on these costs, so co-location measurements are exact
    functions of (catalog, suite, seed).

The four catalog entries cover the repo's serving and training hot paths:
flash-attention prefill and decode-attention (online role — the workloads
MuxFlow protects) and the SSM scan plus a real LM train step (offline role —
the best-effort work MuxFlow packs in).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

from repro.core.interference import OFFLINE_MODEL_PROFILES, WorkloadProfile

# roofline-v1 device model (T4-class, matching the paper's testbed GPU)
PEAK_FLOPS = 8.1e12        # fp32 FLOP/s
PEAK_BW = 300e9            # HBM bytes/s
DEVICE_BYTES = 16 << 30    # 16 GiB HBM
COST_MODEL = "roofline-v1"

ROLE_ONLINE = "online"
ROLE_OFFLINE = "offline"


@dataclasses.dataclass(frozen=True)
class Workload:
    """One named, seeded, role-tagged executable.

    ``build(interpret)`` returns a zero-argument step function whose float
    return value feeds the execution checksum.  ``flops_per_step`` /
    ``bytes_per_step`` are analytic counts for the roofline cost model;
    ``mem_bytes`` is the resident footprint (inputs + params) for
    memory-quota feasibility.  ``target_util`` is the online role's duty
    cycle in the harness (offline workloads run dense).
    """
    name: str
    role: str                          # ROLE_ONLINE | ROLE_OFFLINE
    seed: int
    warmup: int
    steps: int
    flops_per_step: float
    bytes_per_step: float
    mem_bytes: int
    build: Callable[[bool], Callable[[], float]]
    target_util: float = 0.5

    def cost_s(self) -> float:
        """Deterministic roofline step cost (compute + memory phases)."""
        return self.flops_per_step / PEAK_FLOPS + self.bytes_per_step / PEAK_BW

    def profile(self) -> WorkloadProfile:
        """Separate-execution profile derived from the cost model.

        The 'SM activity' analogue is the compute fraction of the roofline
        cost, 'memory bandwidth' the byte fraction (they sum to 1 by
        construction, floored at 0.05 like the seed profiler did)."""
        cost = max(self.cost_s(), 1e-12)
        compute_frac = (self.flops_per_step / PEAK_FLOPS) / cost
        bw_frac = (self.bytes_per_step / PEAK_BW) / cost
        util = self.target_util if self.role == ROLE_ONLINE else 0.95
        return WorkloadProfile(
            name=self.name, gpu_util=util,
            sm_activity=max(compute_frac, 0.05),
            sm_occupancy=0.35 + 0.3 * max(compute_frac, 0.05),
            mem_bw=max(bw_frac, 0.05),
            exec_time_ms=cost * 1e3,
            mem_bytes_frac=self.mem_bytes / DEVICE_BYTES)


@dataclasses.dataclass
class ExecutionRecord:
    """What one :func:`execute` run measured."""
    workload: Workload
    steps_executed: int
    checksum: float              # deterministic (seeded inputs, CPU/TPU math)
    wall_ms_per_step: float      # measured; NEVER serialized into artifacts
    profile: WorkloadProfile = dataclasses.field(init=False)

    def __post_init__(self):
        self.profile = self.workload.profile()


def execute(workload: Workload, *, interpret: bool,
            clock=time.perf_counter) -> ExecutionRecord:
    """Run ``workload`` for real: warmup, then ``steps`` timed iterations.

    Returns the execution record with an output checksum (rounded so the
    float is stable) and wall stats.  ``interpret`` says whether the Pallas
    kernels run in the interpreter or compiled; it is never guessed from
    the backend."""
    step_fn = workload.build(interpret)
    for _ in range(workload.warmup):
        step_fn()
    acc = 0.0
    t0 = clock()
    for _ in range(workload.steps):
        acc += step_fn()
    wall = (clock() - t0) / max(workload.steps, 1)
    return ExecutionRecord(
        workload=workload, steps_executed=workload.steps,
        checksum=float(round(acc, 6)), wall_ms_per_step=wall * 1e3)


# ---------------------------------------------------------------------------
# Catalog builders (imports deferred so the module stays cheap to import)
# ---------------------------------------------------------------------------

def _build_flash_prefill(interpret: bool) -> Callable[[], float]:
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention
    B, Sq, H, Hk, d = 1, 128, 4, 2, 64
    key = jax.random.PRNGKey(11)
    q = jax.random.normal(key, (B, Sq, H, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sq, Hk, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sq, Hk, d), jnp.float32)

    def step() -> float:
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=interpret)
        return float(jnp.sum(out.astype(jnp.float32)))
    return step


def _build_decode_serve(interpret: bool) -> Callable[[], float]:
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import decode_attention
    B, Skv, H, Hk, d, kv_len = 4, 256, 4, 2, 64, 224
    key = jax.random.PRNGKey(23)
    q = jax.random.normal(key, (B, 1, H, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Skv, Hk, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Skv, Hk, d), jnp.float32)

    def step() -> float:
        out = decode_attention(q, k, v, kv_len, block_k=128,
                               interpret=interpret)
        return float(jnp.sum(out.astype(jnp.float32)))
    return step


def _build_ssm_scan(interpret: bool) -> Callable[[], float]:
    import jax
    import jax.numpy as jnp
    from repro.kernels.ssm_scan import ssm_scan
    B, S, di, N, chunk = 2, 64, 128, 8, 16
    key = jax.random.PRNGKey(37)
    dt = jax.nn.softplus(jax.random.normal(key, (B, S, di), jnp.float32))
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, di), jnp.float32)
    Bc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, N), jnp.float32)
    Cc = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N), jnp.float32)
    A_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, N + 1, dtype=jnp.float32), (di, N)))

    def step() -> float:
        out = ssm_scan(dt, x, Bc, Cc, A_log, chunk=chunk, interpret=interpret)
        return float(jnp.sum(out))
    return step


_TRAIN_ARCH = "xlstm-350m"
_TRAIN_BATCH, _TRAIN_SEQ = 2, 32


def _build_lm_train(interpret: bool) -> Callable[[], float]:
    # interpret is irrelevant here: the smoke model's CPU path is pure jnp
    import jax
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.models import init_params
    from repro.models.steps import make_train_step
    from repro.optim.optimizer import MomentumSGD, MomentumSGDConfig
    cfg = get_config(_TRAIN_ARCH, smoke=True)
    params = init_params(jax.random.PRNGKey(41), cfg)
    opt = MomentumSGD(MomentumSGDConfig(lr=1e-3, momentum=0.9))
    opt_state = opt.init(params)
    pipe = TokenPipeline(DataConfig(cfg.vocab_size, _TRAIN_SEQ, _TRAIN_BATCH,
                                    seed=41))
    step_fn = jax.jit(make_train_step(cfg, opt))
    state = {"params": params, "opt": opt_state, "i": 0}

    def step() -> float:
        batch = pipe.batch_at(state["i"])
        state["params"], state["opt"], metrics = step_fn(
            state["params"], state["opt"], batch)
        state["i"] += 1
        return float(metrics["loss"])
    return step


def _train_work() -> tuple[float, float, int]:
    """Analytic train-step work: ~6 FLOP per param per token, parameter +
    gradient + optimizer traffic for bytes (fp32)."""
    from repro.configs import get_config
    cfg = get_config(_TRAIN_ARCH, smoke=True)
    n_params = cfg.param_count()
    tokens = _TRAIN_BATCH * _TRAIN_SEQ
    flops = 6.0 * n_params * tokens
    bytes_ = 3.0 * n_params * 4
    mem = int(4 * n_params * 4)          # params + grads + momentum + slack
    return flops, bytes_, mem


def _attn_flops(B, Sq, Skv, H, d) -> float:
    return 4.0 * B * H * Sq * Skv * d


def build_catalog() -> dict[str, Workload]:
    """The canonical catalog, rebuilt fresh each call (entries are frozen)."""
    train_flops, train_bytes, train_mem = _train_work()
    entries = [
        Workload(
            name="flash-prefill", role=ROLE_ONLINE, seed=11, warmup=1, steps=3,
            flops_per_step=_attn_flops(1, 128, 128, 4, 64),
            bytes_per_step=float((128 * 4 * 64 + 2 * 128 * 2 * 64
                                  + 128 * 4 * 64) * 4),
            mem_bytes=(128 * 4 * 64 + 2 * 128 * 2 * 64) * 4,
            build=_build_flash_prefill, target_util=0.6),
        Workload(
            name="decode-serve", role=ROLE_ONLINE, seed=23, warmup=1, steps=3,
            flops_per_step=_attn_flops(4, 1, 256, 4, 64),
            bytes_per_step=float(4 * (2 * 256 * 2 * 64 + 2 * 4 * 64) * 4),
            mem_bytes=4 * 2 * 256 * 2 * 64 * 4,
            build=_build_decode_serve, target_util=0.45),
        Workload(
            name="ssm-scan", role=ROLE_OFFLINE, seed=37, warmup=1, steps=3,
            flops_per_step=float(2 * 64 * 128 * 8 * 6),
            bytes_per_step=float(2 * 64 * (2 * 128 + 2 * 8) * 4),
            mem_bytes=2 * 64 * (2 * 128 + 2 * 8) * 4,
            build=_build_ssm_scan),
        Workload(
            name="lm-train-step", role=ROLE_OFFLINE, seed=41, warmup=1, steps=2,
            flops_per_step=train_flops, bytes_per_step=train_bytes,
            mem_bytes=train_mem, build=_build_lm_train),
    ]
    return {w.name: w for w in entries}


def catalog_by_role(catalog: dict[str, Workload] | None = None,
                    ) -> tuple[list[Workload], list[Workload]]:
    """(online workloads, offline workloads) in catalog order."""
    catalog = catalog or build_catalog()
    ws = list(catalog.values())
    return ([w for w in ws if w.role == ROLE_ONLINE],
            [w for w in ws if w.role == ROLE_OFFLINE])


# ---------------------------------------------------------------------------
# Seed-era profiler API (the profiler's home since it left core/profiler.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProfileStore:
    """The paper stores measured profiles in a database keyed by workload."""
    profiles: dict = dataclasses.field(default_factory=dict)

    def get(self, key: str) -> WorkloadProfile | None:
        return self.profiles.get(key)

    def put(self, key: str, profile: WorkloadProfile) -> None:
        self.profiles[key] = profile


def profile_step_fn(step_fn: Callable[[], None], *, name: str,
                    warmup: int = 2, iters: int = 5,
                    flops_per_step: float = 0.0,
                    bytes_per_step: float = 0.0,
                    peak_flops: float = 197e12,
                    peak_bw: float = 819e9,
                    mem_bytes: int = 0,
                    device_bytes: int = DEVICE_BYTES) -> WorkloadProfile:
    """Wall-clock profiling of an arbitrary step callable (the seed's dry-run
    path).  Prefer the catalog's deterministic :meth:`Workload.profile` for
    anything that feeds an artifact."""
    for _ in range(warmup):
        step_fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        step_fn()
    dt = (time.perf_counter() - t0) / iters
    compute_frac = min(1.0, (flops_per_step / peak_flops) / max(dt, 1e-9))
    bw_frac = min(1.0, (bytes_per_step / peak_bw) / max(dt, 1e-9))
    return WorkloadProfile(
        name=name, gpu_util=0.95, sm_activity=max(compute_frac, 0.05),
        sm_occupancy=0.5, mem_bw=max(bw_frac, 0.05), exec_time_ms=dt * 1e3,
        mem_bytes_frac=mem_bytes / device_bytes)


def profile_from_trace(model: str) -> WorkloadProfile:
    return OFFLINE_MODEL_PROFILES[model]
