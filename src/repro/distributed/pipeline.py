"""Pipeline parallelism over the ``pod`` mesh axis (DESIGN.md §6).

GPipe-style microbatch pipelining implemented with ``shard_map`` +
``lax.ppermute``: each pod holds a contiguous block of stages (here: one
stage per pod), activations stream pod→pod over the slow inter-pod links —
only microbatch-sized boundary activations ever cross pods, which is the
point of using PP on the pod axis (DP would all-reduce full gradients
across pods every step).

Schedule: classic GPipe fill/drain — ``n_micro + n_stages − 1`` ticks, each
tick runs every stage on its current buffer and shifts results forward.
Bubble fraction = (S−1)/(M+S−1); callers pick ``n_micro ≫ n_stages``.

The stage function is arbitrary (a stack of model layers under its own
lax.scan); parameters arrive stacked over a leading ``n_stages`` dim which
shard_map splits across the axis.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: Array,
    *,
    mesh: Mesh,
    axis: str = "pod",
    n_microbatches: int,
):
    """Run ``y = stage_{S-1}(...stage_0(x))`` pipelined over ``axis``.

    ``stage_params``: pytree with leading dim = n_stages (sharded over
    ``axis``); ``stage_fn(params_slice, h) -> h`` applies one stage.
    ``x``: (batch, ...) — batch must divide n_microbatches. Returns y with
    x's shape (replicated over ``axis``).
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_microbatches == 0, (b, n_microbatches)
    mb = b // n_microbatches
    xm = x.reshape(n_microbatches, mb, *x.shape[1:])

    def pp(params_local, xm_local):
        # under shard_map: params_local has leading dim 1 (this pod's stage)
        params_here = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_microbatches + n_stages - 1

        buf0 = jnp.zeros_like(xm_local[0])
        out0 = jnp.zeros_like(xm_local)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (clamped during drain)
            inject = jax.lax.dynamic_index_in_dim(
                xm_local, jnp.clip(t, 0, n_microbatches - 1), 0, keepdims=False
            )
            h_in = jnp.where(stage == 0, inject, buf)
            h_out = stage_fn(params_here, h_in)
            # shift forward: stage i → i+1 (ring; wraparound is ignored)
            buf_next = jax.lax.ppermute(
                h_out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            # last stage emits microbatch t − (S−1) during the drain window
            emit_t = t - (n_stages - 1)
            valid = (stage == n_stages - 1) & (emit_t >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.clip(emit_t, 0, n_microbatches - 1), 0
                ),
                lambda o: o,
                outs,
            )
            return (buf_next, outs), None

        (buf, outs), _ = jax.lax.scan(
            tick, (buf0, out0), jnp.arange(n_ticks)
        )
        # broadcast the result from the last stage to every pod
        mask = (stage == n_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * mask, axis)
        return outs

    other_axes = [a for a in mesh.axis_names if a != axis]
    in_specs = (
        jax.tree_util.tree_map(lambda _: P(axis), stage_params),
        P(None),  # microbatched input replicated along the pipeline axis
    )
    out_specs = P(None)
    y = jax.shard_map(
        pp, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(stage_params, xm)
    return y.reshape(b, *x.shape[1:])


def pipeline_bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
