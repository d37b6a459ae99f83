"""Pallas TPU flash-attention kernel (train/prefill hot spot).

Tiling: grid (batch*q_heads, Sq/block_q, Skv/block_k) with the KV axis
*sequential*: each program step brings one (block_k, d) K/V tile into VMEM
and folds it into (block_q, d) accumulators in VMEM scratch with the
online-softmax recurrence; the last KV step normalizes and writes the output.
Only one tile of K and V is resident at a time, so VMEM use does not grow
with the sequence.  Causal and sliding-window masks are applied from
absolute positions; tiles wholly outside the mask are neither fetched (the
index map repeats an in-mask tile) nor computed.  GQA is handled by mapping
each query head onto its KV head in the index maps (no KV repeat in HBM).

Block shapes default to (block_q, block_k) = (128, 128): MXU-aligned and a
VMEM working set of block_q*d + 2*block_k*d + block_q*block_k fp32 ≈ 0.3 MB
at d=128, leaving room for double buffering.

Validated against ref.attention_reference in interpret mode (tests sweep
shapes/dtypes); on CPU the model's distribution path uses the jnp chunked
form (models/layers.py) with identical math.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kv_range(qi, *, block_q, block_k, num_k, causal, window):
    """First and last KV tile that any query of q-tile `qi` attends to."""
    lo, hi = 0, num_k - 1
    if causal:
        hi = jnp.minimum(hi, ((qi + 1) * block_q - 1) // block_k)
    if window is not None:
        lo = jnp.maximum(lo, (qi * block_q - window + 1) // block_k)
    return lo, hi


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q, block_k, num_k, causal, window, sm_scale):
    qi, ki = pl.program_id(1), pl.program_id(2)
    lo, hi = _kv_range(qi, block_q=block_q, block_k=block_k, num_k=num_k,
                       causal=causal, window=window)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when((ki >= lo) & (ki <= hi))
    def _block():
        q = q_ref[0]                                     # (block_q, d)
        k_blk = k_ref[0]                                 # (block_k, d)
        v_blk = v_ref[0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones(s.shape, jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]          # (block_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == num_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, d); k, v: (B, Skv, Hk, d), H = G*Hk.  Returns (B,Sq,H,d).

    Each grid program owns one (batch, q-head, q-block) and walks the KV
    tiles; the index maps send query head h to KV head h // G.
    """
    B, Sq, H, d = q.shape
    Skv, Hk = k.shape[1], k.shape[2]
    G = H // Hk
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)
    num_k = Skv // block_k
    # layout: heads-major so one program sees a contiguous (seq, d) tile
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Hk, Skv, d)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Hk, Skv, d)
    kv_range = functools.partial(_kv_range, block_q=block_q, block_k=block_k,
                                 num_k=num_k, causal=causal, window=window)

    def kv_map(bh, qi, ki):
        lo, hi = kv_range(qi)
        return (bh // G, jnp.clip(ki, lo, hi), 0)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, num_k=num_k,
        causal=causal, window=window, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // block_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running sum
            pltpu.VMEM((block_q, d), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, Sq, d).transpose(0, 2, 1, 3)
