"""Production mesh construction (TPU v5e pods; CPU placeholder devices for
the dry-run).

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests (all axes size 1)."""
    return _make_mesh((1, 1), ("data", "model"))
