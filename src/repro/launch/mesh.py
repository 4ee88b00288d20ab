"""Production mesh construction (DESIGN.md §6).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Single pod: v5e-256, mesh (data=16, model=16).
Multi-pod:  2 pods = 512 chips, mesh (pod=2, data=16, model=16) — the 'pod'
axis carries only data parallelism + cross-pod gradient reduction (DCN-ish
traffic), 'model' stays intra-pod (ICI).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_single_device_mesh", "make_host_mesh"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2, 2), axes=("pod", "data", "model")):
    """Small mesh over host devices (tests; requires enough host devices)."""
    return _make_mesh(shape, axes)


def make_single_device_mesh():
    """1-device mesh with the production axis names (smoke tests)."""
    return _make_mesh((1, 1), ("data", "model"))
