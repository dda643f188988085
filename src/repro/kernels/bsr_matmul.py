"""Pallas TPU kernel: block-sparse matmul — the FAµST apply hot-spot.

The paper's speed-of-multiplication benefit (§II-B2) on TPU requires the
sparse factors to be *block* sparse (DESIGN.md §3). This kernel computes

    y = x @ F,   F packed as values (O, K, bk, bn) + in_idx (O, K)

with a 3-D grid ``(batch tiles, output blocks, k)``:

  * the block-column indices ``in_idx`` are **scalar-prefetched** (flattened
    to ``(O·K,)``: SMEM pads the minor dim of a 2-D table to 128 lanes) so
    the ``x`` BlockSpec index_map can steer the HBM→VMEM stream to fetch
    only the K referenced input blocks per output block — the TPU analog of
    the paper's "only touch the nonzeros";
  * a VMEM scratch accumulator carries the partial product across the k
    dimension (f32 accumulation regardless of input dtype);
  * block shapes are chosen by the caller; production sizes are MXU-aligned
    (bk, bn multiples of 128, batch tile ≥ 8·sublane) — tests sweep small
    shapes in interpret mode against the jnp oracle in ``ref.py``.

Arithmetic intensity: each program does a (bt × bk) @ (bk × bn) MXU matmul
per k step; bytes moved per step ≈ bt·bk + bk·bn (+ bt·bn once), so with
bt = bk = bn = 128 the kernel runs at dense-matmul intensity while touching
only s_tot values — i.e. RCG transfers to both the compute and memory
roofline terms.

*Chain* applies, however, pay an extra 2·batch·d_j HBM round-trip of the
intermediate activations at every factor boundary when driven one launch per
factor.  ``kernels/chain.py`` generalizes this kernel to the whole
``x @ F_1 @ ... @ F_J`` product in a single ``pallas_call`` (this kernel is
its J = 1 special case); prefer ``repro.api.FaustOp.apply(x,
backend="fused")`` (or ``packed_chain_apply``) for multi-factor chains.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chain import dot_precision

Array = jax.Array


def _bsr_matmul_kernel(idx_ref, x_ref, v_ref, o_ref, acc_ref, *, n_k: int, precision):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...],
        v_ref[0, 0],
        precision=precision,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def bsr_matmul(
    x: Array,
    values: Array,
    in_idx: Array,
    *,
    bt: int = 128,
    interpret: bool = False,
) -> Array:
    """``y = x @ F`` on TPU via Pallas. ``x``: (B, IB·bk) with B % bt == 0
    (callers pad via :func:`repro.kernels.ops.bsr_apply`)."""
    b, in_pad = x.shape
    o, k, bk, bn = values.shape
    assert b % bt == 0, (b, bt)
    assert in_pad % bk == 0, (in_pad, bk)
    grid = (b // bt, o, k)

    return pl.pallas_call(
        functools.partial(
            _bsr_matmul_kernel, n_k=k, precision=dot_precision(x.dtype)
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # x: batch tile  ×  the k-th referenced input block
                pl.BlockSpec((bt, bk), lambda bi, oi, ki, idx: (bi, idx[oi * k + ki])),
                # values: one (bk × bn) block per (o, k)
                pl.BlockSpec((1, 1, bk, bn), lambda bi, oi, ki, idx: (oi, ki, 0, 0)),
            ],
            out_specs=pl.BlockSpec((bt, bn), lambda bi, oi, ki, idx: (bi, oi)),
            scratch_shapes=[pltpu.VMEM((bt, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, o * bn), x.dtype),
        interpret=interpret,
        name="faust_bsr_matmul",
    )(in_idx.reshape(-1), x, values)
