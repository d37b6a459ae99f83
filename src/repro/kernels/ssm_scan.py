"""Pallas TPU chunked selective-scan kernel (Mamba hot spot in jamba).

Grid (batch, d_inner/d_blk, n_chunks) with the chunk axis *sequential*: the
SSM state h lives in VMEM scratch as (N, d_blk) — d_inner on the 128 lanes,
the N state entries on sublanes — and is carried across chunk iterations.
Blocking d_inner keeps the working set fixed whatever the model width
(jamba's d_inner is 16384).  Within a chunk the first-order recurrence
h_t = dA_t·h_{t-1} + dBx_t runs as a loop over groups of 8 timesteps: each
group loads 8 aligned rows of dt and dt·x, unrolls the 8 steps over static
slices and stores 8 aligned output rows, so no load or store needs a dynamic
sub-tile offset.  The per-step work is an (N, d_blk) FMA and exp — VPU-bound,
which is the true character of the Mamba scan; the matmuls around it stay in
XLA.

B_t and C_t enter as (chunk, N, 1) tiles, so that B_t is an (N, 1) column
that broadcasts along the lanes.  VMEM per program at chunk=64, d_blk=512,
N=16: dt, dt·x and y tiles 3·128 KB, B and C tiles 2·512 KB (the unit lane
dim pads to 128), state 32 KB; about 3 MB double-buffered.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_GROUP = 8      # timesteps per aligned load/store (f32 sublane tile)
_D_BLK = 512    # d_inner lanes per program


def _ssm_kernel(dt_ref, bx_ref, b_ref, c_ref, a_ref, o_ref, h_ref, *, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = -jnp.exp(a_ref[...])                             # (N, d_blk)

    def group(g, h):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        dt = dt_ref[0, pl.ds(t0, _GROUP), :]             # (8, d_blk)
        bx = bx_ref[0, pl.ds(t0, _GROUP), :]
        Bg = b_ref[0, pl.ds(t0, _GROUP)]                 # (8, N, 1)
        Cg = c_ref[0, pl.ds(t0, _GROUP)]
        ys = []
        for i in range(_GROUP):
            dA = jnp.exp(dt[i:i + 1, :] * A)             # (N, d_blk)
            h = dA * h + Bg[i] * bx[i:i + 1, :]
            ys.append((h * Cg[i]).sum(axis=0, keepdims=True))
        o_ref[0, pl.ds(t0, _GROUP), :] = jnp.concatenate(ys, axis=0)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk // _GROUP, group, h_ref[...])


def ssm_scan(dt: jax.Array, x: jax.Array, B_ssm: jax.Array, C_ssm: jax.Array,
             A_log: jax.Array, *, chunk: int = 64,
             interpret: bool = False) -> jax.Array:
    """Selective scan: y[b,t,d] = Σ C[b,t]·h[b,t,d,:], h recurrent.

    dt, x: (B, S, di); B_ssm, C_ssm: (B, S, N); A_log: (di, N).
    Returns y (B, S, di) fp32 (without the D·x skip, applied by the caller).
    """
    Bsz, S, di = x.shape
    N = B_ssm.shape[-1]
    d_blk = min(_D_BLK, di)
    assert S % chunk == 0 and chunk % _GROUP == 0 and di % d_blk == 0, \
        (S, chunk, di, d_blk)
    dt = dt.astype(jnp.float32)
    bx = dt * x.astype(jnp.float32)
    bc = B_ssm.astype(jnp.float32)[..., None]            # (B, S, N, 1)
    cc = C_ssm.astype(jnp.float32)[..., None]
    a_t = A_log.astype(jnp.float32).T                    # (N, di)

    seq_spec = pl.BlockSpec((1, chunk, d_blk), lambda b, j, c: (b, c, j))
    col_spec = pl.BlockSpec((1, chunk, N, 1), lambda b, j, c: (b, c, 0, 0))
    return pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=(Bsz, di // d_blk, S // chunk),
        in_specs=[seq_spec, seq_spec, col_spec, col_spec,
                  pl.BlockSpec((N, d_blk), lambda b, j, c: (0, j))],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((Bsz, S, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((N, d_blk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dt, bx, bc, cc, a_t)
