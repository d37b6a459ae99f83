"""Jit'd public wrappers for the Pallas kernels.

`interpret` is required: callers say whether a kernel runs compiled (Mosaic,
on a TPU) or in the Pallas interpreter (tests on CPU).  It is never derived
from the backend, so a kernel on the chip path cannot quietly fall back to
the interpreter.
"""
from __future__ import annotations

import functools

import jax

from .decode_attention import decode_attention as _decode
from .flash_attention import flash_attention as _flash
from .ssm_scan import ssm_scan as _ssm


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, block_q=128,
                    block_k=128, interpret):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, kv_len, *, block_k=512, interpret):
    return _decode(q, k_cache, v_cache, kv_len, block_k=block_k,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(dt, x, B_ssm, C_ssm, A_log, *, chunk=64, interpret):
    return _ssm(dt, x, B_ssm, C_ssm, A_log, chunk=chunk, interpret=interpret)
