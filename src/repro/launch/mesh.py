"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single-pod: 16x16 = 256 chips (v5e pod), axes (data, model).
Multi-pod: 2x16x16 = 512 chips, axes (pod, data, model); the pod axis is a
pure data-parallel / gradient-reduction axis (DCN-friendly traffic only).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape: tuple[int, ...],
              axes: tuple[str, ...]) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(n_devices: int | None = None) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = 1
    for m in (4, 2, 1):
        if n % m == 0:
            model = m
            break
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
