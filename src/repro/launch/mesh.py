"""Production mesh builders.

Functions, not module-level constants: importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before any jax
init; tests and benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: the shardings in repro.parallel are constraints for the
    # partitioner to propagate, not explicit per-op sharding types
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod mesh: 16x16 = 256 chips per pod; the multi-pod variant
    adds a leading 2-pod data-parallel axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for local sharding tests (subprocess with
    xla_force_host_platform_device_count set accordingly)."""
    return _auto_mesh(shape, axes)
