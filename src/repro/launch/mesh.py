"""Production meshes (defined as functions: importing this module never
touches jax device state).

Single pod : (16, 16)      axes (data, model)        = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16)   axes (pod, data, model)   = 512 chips

The ``pod`` axis rides DCN (slow), ``data``/``model`` ride ICI — the
gradient-compression and ZeRO machinery in repro.train keys off these
names.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with explicit Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 1) -> jax.sharding.Mesh:
    """Small mesh over host devices (tests / smoke runs)."""
    if pod > 1:
        return _make_mesh((pod, data, model), ("pod", "data", "model"))
    return _make_mesh((data, model), ("data", "model"))
