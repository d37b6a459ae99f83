"""Pallas TPU flash-decode kernel (the online-serving hot spot MuxFlow
protects).

One new query token per sequence against a long KV cache: grid
(batch*kv_heads, Skv/block_k) with the KV-length axis *sequential* ("split-K"
over the cache).  Each program reduces its KV block into VMEM scratch
(running max / sum / accumulator, flash-decoding style) and the final block
normalizes — giving O(block) VMEM for arbitrarily long caches.

The G query heads of a KV group are carried together: the q tile is (G, d),
MXU work per block is (G, d) × (d, block_k).  block_k defaults to 512 rows:
the kernel is bandwidth-bound, so wide blocks amortize control overhead while
(G·block_k + block_k·d) stays ≪ VMEM.

The per-sequence valid lengths arrive by scalar prefetch (SMEM), so a block
wholly past a sequence's length is neither fetched (its index map repeats the
last valid block) nor computed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, block_k, grid_k, hk, sm_scale):
    bh, ki = pl.program_id(0), pl.program_id(1)
    kv_len = len_ref[bh // hk]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ki * block_k < kv_len)
    def _block():
        q = q_ref[0]                                     # (G, d)
        k_blk = k_ref[0]                                 # (block_k, d)
        v_blk = v_ref[0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)        # (G, block_k)
        m_prev, l_prev = m_ref[...], l_ref[...]          # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == grid_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len, *, block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B, 1, H, d); caches: (B, Skv, Hk, d); kv_len: valid entries
    (scalar or (B,)).  Returns (B, 1, H, d)."""
    B, _, H, d = q.shape
    Skv, Hk = k_cache.shape[1], k_cache.shape[2]
    G = H // Hk
    assert Skv % block_k == 0, (Skv, block_k)
    sm_scale = 1.0 / math.sqrt(d)
    qt = q.reshape(B, Hk, G, d).reshape(B * Hk, G, d)
    kt = k_cache.transpose(0, 2, 1, 3).reshape(B * Hk, Skv, d)
    vt = v_cache.transpose(0, 2, 1, 3).reshape(B * Hk, Skv, d)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (B,))
    grid_k = Skv // block_k

    def kv_map(bh, ki, lens):
        # past the sequence's length, repeat the last valid block: the
        # pipeline skips a copy whose block index did not change
        last = jnp.maximum(lens[bh // Hk] - 1, 0) // block_k
        return (bh, jnp.minimum(ki, last), 0)

    kernel = functools.partial(_decode_kernel, block_k=block_k,
                               grid_k=grid_k, hk=Hk, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hk, grid_k),
            in_specs=[
                pl.BlockSpec((1, G, d), lambda bh, ki, lens: (bh, 0, 0)),
                pl.BlockSpec((1, block_k, d), kv_map),
                pl.BlockSpec((1, block_k, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, G, d), lambda bh, ki, lens: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),    # running max
                pltpu.VMEM((G, 1), jnp.float32),    # running sum
                pltpu.VMEM((G, d), jnp.float32),    # output accumulator
            ]),
        out_shape=jax.ShapeDtypeStruct((B * Hk, G, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qt, kt, vt)
    return out.reshape(B, 1, H, d)
