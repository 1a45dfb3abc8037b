"""Production, host and 2x2 meshes.

Defined as FUNCTIONS, not module-level constants, so importing this module
never touches jax device state (the dry-run must set
--xla_force_host_platform_device_count *before* first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods =
    512 chips as (pod=2, data=16, model=16) — the `pod` axis is pure data
    parallelism over DCN (HeMT-DP skews grain counts along it)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1-device mesh with the production axis names — lets smoke tests run
    the exact same sharded code paths on CPU."""
    return _mesh((1, 1), ("data", "model"))


def make_2x2_mesh():
    """The four chips of one v5e host as (data=2, model=2): the smallest
    mesh on which both the batch rules and the model rules split."""
    return _mesh((2, 2), ("data", "model"))
