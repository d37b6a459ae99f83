"""Compile-for-chip guard: the Pallas kernels of the main path compile for a
described TPU v5e at real widths, with no chip attached.

Nothing runs: Mosaic lowers and the TPU compiler accepts each kernel, and
the program holds a ``tpu_custom_call``.  This catches what interpret mode
cannot (tile alignment, fast-memory limits, unsupported in-kernel ops).
The topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one that runs them loads the
TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _mosaic_text(one_chip, fn, shapes, **static):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return fn.lower(*args, interpret=False, **static).compile().as_text()


def test_decode_attention_compiles_at_danube_decode_width(one_chip):
    # h2o-danube-1.8b decode: B 8, KV cache 2048, 32 heads over 8 KV heads
    bf = jnp.bfloat16
    text = _mosaic_text(one_chip, ops.decode_attention, [
        ((8, 1, 32, 80), bf), ((8, 2048, 8, 80), bf), ((8, 2048, 8, 80), bf),
        ((8,), jnp.int32)])
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_danube_prefill_width(one_chip):
    # h2o-danube-1.8b prefill: S 2048, causal, sliding window 4096
    bf = jnp.bfloat16
    text = _mosaic_text(one_chip, ops.flash_attention, [
        ((1, 2048, 32, 80), bf), ((1, 2048, 8, 80), bf),
        ((1, 2048, 8, 80), bf)], causal=True, window=4096)
    assert "tpu_custom_call" in text


def test_ssm_scan_compiles_at_jamba_mamba_width(one_chip):
    # jamba-1.5-large Mamba: d_inner 16384, state dim 16
    f32 = jnp.float32
    text = _mosaic_text(one_chip, ops.ssm_scan, [
        ((1, 2048, 16384), f32), ((1, 2048, 16384), f32),
        ((1, 2048, 16), f32), ((1, 2048, 16), f32), ((16384, 16), f32)])
    assert "tpu_custom_call" in text
