"""Mixture-of-Experts FFN: top-k routing with per-group capacity dispatch.

Dispatch is the *no-token-crossing* formulation: tokens are grouped by the
leading batch dim (which is data-sharded), each group routes its own tokens
into a per-group expert buffer of static capacity, and expert compute is a
single einsum over (groups, experts, capacity, d). Under GSPMD this keeps
token gathers within their data shard and shards expert weights/compute on
the 'model' axis (EP) with no explicit all-to-all. Overflowing tokens
are dropped (capacity factor controls the drop rate), underfull slots are
zero-padded — the standard GShard/Switch capacity semantics.

Supports: top-1 (Switch / llama4-maverick), top-k (granite top-8), optional
shared expert (llama4), load-balancing auxiliary loss (Switch eq. 4).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.layers.mlp import GLU_KINDS, _act
from repro.layers.param import annotate, dense_init

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoESpec:
    n_experts: int
    top_k: int
    d_ff: int
    act: str = "swiglu"
    capacity_factor: float = 1.25
    shared_expert_ff: int = 0  # 0 → no shared expert
    router_aux_coef: float = 0.01


def moe_init(key: jax.Array, d_model: int, spec: MoESpec, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 6)
    e, ff = spec.n_experts, spec.d_ff
    std_in = float(1.0 / np.sqrt(d_model))  # python floats: keep dtype weak
    std_out = float(1.0 / np.sqrt(ff))
    p = {
        "router": dense_init(ks[0], d_model, e, ("embed", "experts"), dtype=jnp.float32),
        "w_up": annotate(
            (jax.random.normal(ks[1], (e, d_model, ff), dtype=dtype) * std_in).astype(dtype),
            "experts", "embed", "mlp",
        ),
        "w_down": annotate(
            (jax.random.normal(ks[2], (e, ff, d_model), dtype=dtype) * std_out).astype(dtype),
            "experts", "mlp", "embed",
        ),
    }
    if spec.act in GLU_KINDS:
        p["w_gate"] = annotate(
            (jax.random.normal(ks[3], (e, d_model, ff), dtype=dtype) * std_in).astype(dtype),
            "experts", "embed", "mlp",
        )
    if spec.shared_expert_ff:
        from repro.layers.mlp import mlp_init

        p["shared"] = mlp_init(ks[4], d_model, spec.shared_expert_ff, spec.act, dtype)
    return p


def capacity_per_group(tokens_per_group: int, spec: MoESpec) -> int:
    c = int(np.ceil(tokens_per_group * spec.top_k / spec.n_experts * spec.capacity_factor))
    return max(c, 1)


class _Routing(NamedTuple):
    slot_src: Array  # (G, E*C) source token index per expert slot (T_g ⇒ pad)
    dest: Array  # (G, T_g*k) destination slot per (token, k) (E*C ⇒ dropped)
    weights: Array  # (G, T_g, k) routing weights (post-softmax, renormalized)
    aux_loss: Array  # scalar load-balance loss


def route(logits: Array, spec: MoESpec) -> _Routing:
    """Routing for grouped tokens. ``logits``: (G, T_g, E)."""
    g, t, e = logits.shape
    k = spec.top_k
    c = capacity_per_group(t, spec)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)  # (G, T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)

    flat_e = top_e.reshape(g, t * k)
    order = jnp.argsort(flat_e, axis=-1, stable=True)  # (G, T*k)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    ones = jnp.ones_like(flat_e, dtype=jnp.int32)
    counts = jax.vmap(lambda fe, on: jax.ops.segment_sum(on, fe, e))(flat_e, ones)
    offsets = jnp.cumsum(counts, axis=-1) - counts  # (G, E)
    pos_in_e = jnp.arange(t * k)[None, :] - jnp.take_along_axis(offsets, sorted_e, axis=-1)
    keep = pos_in_e < c
    dest_sorted = jnp.where(keep, sorted_e * c + pos_in_e, e * c)  # (G, T*k)
    # scatter dest back to (token, k) order
    dest = jnp.zeros((g, t * k), jnp.int32)
    dest = jax.vmap(lambda d, o, ds: d.at[o].set(ds))(dest, order, dest_sorted)
    # slot → source token (argsort position // k)
    src_token_sorted = order // k
    slot_src = jnp.full((g, e * c + 1), t, jnp.int32)
    slot_src = jax.vmap(lambda ss, ds, st: ss.at[ds].set(st))(
        slot_src, dest_sorted, src_token_sorted
    )[:, : e * c]

    # Switch load-balancing loss: E · Σ_e f_e · P_e
    dispatch_frac = counts.astype(jnp.float32) / (t * k)
    prob_frac = jnp.mean(probs, axis=1)
    aux = spec.n_experts * jnp.mean(jnp.sum(dispatch_frac * prob_frac, axis=-1))
    return _Routing(slot_src, dest, top_w, aux)


def moe_apply(p: dict, x: Array, spec: MoESpec) -> tuple[Array, Array]:
    """x: (B, S, d) — B is the (data-sharded) group dim. Returns (y, aux)."""
    b, s, d = x.shape
    e, k = spec.n_experts, spec.top_k
    c = capacity_per_group(s, spec)
    logits = x.astype(jnp.float32) @ p["router"]  # (B, S, E)
    r = route(logits, spec)

    x_pad = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)  # pad row
    xe = jnp.take_along_axis(
        x_pad, r.slot_src[..., None], axis=1
    ).reshape(b, e, c, d)

    up = jnp.einsum("becd,edf->becf", xe, p["w_up"])
    if spec.act in GLU_KINDS:
        h = _act(spec.act, jnp.einsum("becd,edf->becf", xe, p["w_gate"])) * up
    else:
        h = _act(spec.act, up)
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"])  # (B, E, C, d)

    # combine: gather each (token, k) contribution from its slot
    ye_flat = ye.reshape(b, e * c, d)
    ye_pad = jnp.concatenate([ye_flat, jnp.zeros((b, 1, d), ye.dtype)], axis=1)
    contrib = jnp.take_along_axis(ye_pad, r.dest[..., None], axis=1)  # (B, S*k, d)
    contrib = contrib.reshape(b, s, k, d) * r.weights[..., None].astype(x.dtype)
    y = jnp.sum(contrib, axis=2)

    if spec.shared_expert_ff:
        from repro.layers.mlp import mlp_apply

        y = y + mlp_apply(p["shared"], x, spec.act)
    return y, r.aux_loss * spec.router_aux_coef
