"""Production meshes (spec-mandated shapes).

single-pod: (16, 16) over ("data", "model")   — 256 chips
multi-pod : (2, 16, 16) over ("pod", "data", "model") — 512 chips

Functions, not module constants, so importing never touches jax device
state; TPU launches rely on the default device discovery.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # the sharding rules here are written for automatic (GSPMD) axes;
    # jax.make_mesh defaults to explicit axes since JAX 0.7
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2):
    """Small mesh for tests (requires host-device override)."""
    return _mesh((n_data, n_model), ("data", "model"))
