"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod: 2×16×16 = 512 chips, axes (pod, data, model) — the pod axis is an
outer data-parallel axis in training and a replica axis in serving.
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Generic mesh for tests/examples (e.g. (1,1) on CPU)."""
    return _mesh(shape, axes)


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a production mesh (pod folds into data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"
