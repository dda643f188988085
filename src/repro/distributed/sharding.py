"""Logical-axis sharding: policies, rules context, and PartitionSpec
resolution.

Model code annotates activations with *logical* axis names via
:func:`shard_act` and parameters carry logical axes from init
(``repro.layers.param``). A :class:`ShardingPolicy` (per architecture ×
shape kind) maps logical names → mesh axes; :func:`resolve_param_pspecs`
turns an axes-tree into a PartitionSpec tree, silently dropping mesh axes
that don't divide the dimension (e.g. 8 q-heads on a 16-wide 'model' axis →
replicated) — the divisibility-driven fallback documented in DESIGN.md §6.

Outside a ``use_rules`` context (CPU smoke tests), ``shard_act`` is the
identity, so the model runs unmodified on one device.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Array = jax.Array

_TLS = threading.local()


MeshAxes = tuple[str, ...] | str | None


def default_param_rules() -> dict[str, MeshAxes]:
    return {
        "embed": "data",  # ZeRO-3-style storage sharding
        "vocab": "model",
        "heads_flat": "model",
        "kv_flat": "model",
        "mlp": "model",
        "experts": "model",
        "inner_flat": "model",
        "heads": None,
        "blocks": "model",
        "block_k": None,
        "layers": None,
    }


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    """Logical-axis → mesh-axis mapping for one (arch, shape-kind)."""

    # activations
    batch: MeshAxes = ("pod", "data")
    seq: MeshAxes = None  # 'model' for context-parallel archs / SP decode
    heads_act: MeshAxes = "model"
    kv_seq: MeshAxes = None  # decode cache sequence axis
    mlp_act: MeshAxes = "model"
    vocab_act: MeshAxes = "model"
    experts_act: MeshAxes = "model"
    # gather the sequence dim at the MoE boundary (helps ff-TP experts whose
    # routing conflicts with context-parallel seq sharding; hurts EP
    # experts, whose all-to-all already moves exactly the routed tokens)
    moe_gather_seq: bool = False
    # parameters (logical param axes from repro.layers.param)
    params: dict[str, MeshAxes] = dataclasses.field(
        default_factory=default_param_rules
    )

    def act_axes(self, name: str) -> MeshAxes:
        return getattr(self, name)


@dataclasses.dataclass
class _Rules:
    mesh: Mesh
    policy: ShardingPolicy


@contextlib.contextmanager
def use_rules(mesh: Mesh | None, policy: ShardingPolicy | None):
    prev = getattr(_TLS, "rules", None)
    _TLS.rules = _Rules(mesh, policy) if mesh is not None else None
    try:
        yield
    finally:
        _TLS.rules = prev


def _current() -> _Rules | None:
    return getattr(_TLS, "rules", None)


def _fit_axes(ax: MeshAxes, dim_size: int, mesh: Mesh) -> MeshAxes:
    """Drop axes absent from the mesh; replicate if the size doesn't divide."""
    if ax is None:
        return None
    ax_t = (ax,) if isinstance(ax, str) else tuple(ax)
    ax_t = tuple(a for a in ax_t if a in mesh.shape)
    if not ax_t:
        return None
    n = int(np.prod([mesh.shape[a] for a in ax_t]))
    if dim_size % n != 0:
        return None
    return ax_t if len(ax_t) > 1 else ax_t[0]


def shard_act(x: Array, *logical: str | None) -> Array:
    """Constrain activation sharding: one logical name (or None) per dim."""
    rules = _current()
    if rules is None:
        return x
    assert len(logical) == x.ndim, (logical, x.shape)
    mesh_axes = []
    for dim, name in enumerate(logical):
        ax = rules.policy.act_axes(name) if name else None
        mesh_axes.append(_fit_axes(ax, x.shape[dim], rules.mesh))
    spec = PartitionSpec(*mesh_axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec)
    )


def resolve_param_pspecs(axes_tree, shape_tree, mesh: Mesh, policy: ShardingPolicy):
    """axes-tree (tuples of logical names) + shapes → PartitionSpec tree."""

    def one(axes, shape):
        if axes is None:
            return PartitionSpec()
        mesh_axes = []
        used: set[str] = set()
        for dim_size, name in zip(shape, axes):
            ax = policy.params.get(name) if name else None
            ax = _fit_axes(ax, dim_size, mesh)
            # a mesh axis may appear at most once per spec: first wins
            ax_t = (ax,) if isinstance(ax, str) else (ax or ())
            if any(a in used for a in ax_t):
                ax = None
            else:
                used.update(ax_t)
            mesh_axes.append(ax)
        return PartitionSpec(*mesh_axes)

    return jax.tree_util.tree_map(
        one,
        axes_tree,
        jax.tree_util.tree_map(lambda x: tuple(x.shape), shape_tree),
        # None is a leaf meaning "fully replicated" (one() returns P());
        # without marking it, tree_map would treat it as an empty subtree
        # and fail to match the shape tree's tuple leaf
        is_leaf=lambda x: x is None or (
            isinstance(x, tuple)
            and all(isinstance(a, (str, type(None))) for a in x)
        ),
    )


def tree_named_sharding(pspec_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
