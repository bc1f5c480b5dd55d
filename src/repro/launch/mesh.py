"""Production meshes.  Functions only -- importing this module never touches
jax device state (required: the dry-run sets XLA_FLAGS before first init).
Use `make_mesh` below instead of calling `jax.make_mesh` directly.
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_test_mesh"]


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis of type Auto."""
    kwargs = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(shape, axes, **kwargs,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2x16x16 = 512
    chips (pod, data, model).  The fabric maps each pod onto PF(17) racks
    (fabric/placement.py); the pod axis models the inter-pod optical fabric."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh for CPU integration tests (requires >= data*model[*pod]
    visible devices, e.g. via --xla_force_host_platform_device_count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))
