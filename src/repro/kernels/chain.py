"""Pallas TPU kernel: fused multi-factor FAµST chain apply.

The paper's O(s_tot) multiplication (§II-B2) is a *chain* — ``y = lam ·
x @ F_1 @ ... @ F_J`` — but launching one kernel per factor (``bsr_matmul``)
round-trips every intermediate activation through HBM, adding a
``2·Σ_j batch·d_j`` memory term that the RCG flop model never pays.  For
inference-shaped batches the per-factor path is therefore *memory*-bound at
the factor boundaries exactly where Le Magoarou & Gribonval promise a
compute win.  This kernel applies the whole chain in **one** ``pallas_call``:

  * the packed flat layout (``repro.core.compress.PackedChain``) concatenates
    all factors' ``(block × block)`` value blocks into ``values (S, blk, blk)``
    in ``(factor j, out block o, slot k)`` order — see the ASCII layout
    diagram on ``repro.core.compress.ChainPlan`` for the step ordering and
    the ``offsets`` factor-boundary metadata — so the grid's minor
    dimension simply streams block ``s`` per step with automatic double
    buffering — HBM traffic for weights is exactly ``s_tot`` values, once;
  * a per-step metadata table (``(S, 7)`` int32, scalar-prefetched
    *flattened* to ``(S·7,)`` — SMEM pads the minor dim of a 2-D table to
    128 lanes, which would cap a chain at ~2,000 steps; flat, a vocabulary
    -wide chain of 16k steps takes 450 KiB) tells each step which input
    block of the resident activation to read, which output block it
    accumulates into, which of the two ping-pong activation buffers is
    current, and whether it opens/closes an accumulation group or finishes
    the chain;
  * intermediate activations live in a ``(2, ChainPlan.act_blocks, bt,
    blk)`` VMEM scratch (block-major so all addressing is a dynamic
    *leading* index) and never touch HBM: factor ``j`` reads buffer
    ``j % 2`` and writes ``1 - j % 2``; the last factor writes its output
    one ``(bt, blk)`` block at a time straight to HBM (the output
    BlockSpec follows the step table), so neither the scratch nor the
    output buffer grows with the output width — a 92k-wide vocabulary
    projection needs the same VMEM as a 2k-wide one;
  * accumulation is f32 in a ``(bt, blk)`` scratch regardless of input
    dtype, downcast once per output block — bit-compatible with the
    per-factor kernel's behaviour;
  * ragged (non-block-multiple) feature dims are handled by masking the tail
    columns of boundary blocks at flush time (``ncols`` metadata column),
    reproducing the per-factor path's slice-then-zero-pad semantics.

Arithmetic intensity: each step is one (bt × blk) @ (blk × blk) MXU matmul
against blk·blk weight bytes moved; activations are VMEM-resident, so with
bt = blk = 128 the chain runs at dense-matmul intensity end to end while
moving each of the s_tot weights exactly once — the memory-roofline term of
``benchmarks/apply_speed.py`` scales by 1/RCG with **no** J-proportional
activation traffic.

Grid: ``(batch tiles, S)`` with the step dimension minor, so for each batch
tile the S steps run sequentially on-core while the next tile's ``x`` block
prefetches.  Dispatch fits the batch tile to a VMEM budget once
(:func:`fit_bt`, the footprint of :func:`fwd_vmem_bytes`); the kernel
checks the tile it is given and hands Mosaic a scoped-VMEM limit above
the budget.  A chain whose *input* is so wide that no tile fits is
refused from its shapes (:func:`fwd_infeasible`) — dispatch then never
picks the fused backend for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compress import ChainPlan

Array = jax.Array

# meta columns (per step s):
#   0 in_blk   input block id within the current activation buffer (runtime)
#   1 out_blk  output block id this step accumulates into
#   2 parity   which ping-pong buffer holds this factor's input (j % 2)
#   3 is_k0    1 ⇔ first slot of an output block: zero the accumulator
#   4 is_kend  1 ⇔ last slot of an output block: flush the accumulator
#   5 is_last  1 ⇔ step belongs to the final factor: flush to the output ref
#   6 ncols    valid columns in the flushed block (< blk only at a ragged
#              feature boundary; the tail is zeroed to match the per-factor
#              path's slice-then-pad)
META_COLS = 7

# Default batch-tile rows per kernel invocation.  Single-sourced here so
# the apply wrappers and the dispatch pricing agree on what "default"
# means; ``FaustOp.apply`` runs the chain kernels at it (fitted to VMEM by
# ``repro.api.dispatch.fit_chain_bt``) unless the caller forces ``bt=``.
DEFAULT_BT = 128
MIN_BT = 8  # sublane tile: the smallest batch tile Mosaic lays out

# VMEM the chain kernels (forward here, backward in ``chain_bwd``) may
# use.  A TPU v5e core has 128 MiB of VMEM; Mosaic's default scoped limit
# is 16 MiB.  Tiles are fitted to BUDGET and Mosaic is given LIMIT, the
# headroom covering its own internal scratch.
VMEM_BUDGET_BYTES = 24 * 2**20
VMEM_LIMIT_BYTES = 32 * 2**20
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def dot_precision(dtype):
    """MXU precision for a chain computed in ``dtype``.  Mosaic contracts
    f32 operands as one bf16 pass unless told otherwise — measured on a
    v5e at 2.4e-3 relative error for the 2048→92553 f32 unembedding — so
    f32 chains ask for full f32 precision; bf16 chains keep the default."""
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None


def fit_bt(footprint, bt: int, budget: int | None = None) -> int | None:
    """Largest power-of-two divisor of ``bt`` (≥ :data:`MIN_BT`) whose
    ``footprint(tile)`` bytes fit ``budget`` (default
    :data:`VMEM_BUDGET_BYTES`), or None when none does.  The apply wrappers
    pad the batch to a multiple of ``bt``, so any divisor still tiles it
    exactly."""
    if budget is None:
        budget = VMEM_BUDGET_BYTES
    while bt >= MIN_BT:
        if footprint(bt) <= budget:
            return bt
        if bt % 2:
            break
        bt //= 2
    return None


def fwd_vmem_bytes(plan: ChainPlan, bt: int, x_elt: int, v_elt: int, quant: bool) -> int:
    """VMEM footprint of :func:`chain_matmul` at batch tile ``bt``: the
    double-buffered x tile, value-block (+ scale-row, sublane-padded to
    8) stream and output block, the ping-pong activation scratch and the
    f32 accumulator."""
    blk = plan.block
    return (
        2 * bt * plan.in_blocks[0] * blk * x_elt
        + 2 * blk * blk * v_elt
        + (2 * 8 * blk * 4 if quant else 0)
        + 2 * bt * blk * x_elt
        + 2 * plan.act_blocks * bt * blk * x_elt
        + bt * blk * 4
    )


def fwd_infeasible(plan: ChainPlan, x_elt: int, v_elt: int, quant: bool) -> str | None:
    """Why no batch tile fits the forward's VMEM budget (None when one
    does) — a pure function of the shapes, so dispatch can rule the fused
    backend out before anything is traced or compiled."""
    need = fwd_vmem_bytes(plan, MIN_BT, x_elt, v_elt, quant)
    if need <= VMEM_BUDGET_BYTES:
        return None
    return (
        f"fused forward needs {need} B of VMEM at bt={MIN_BT} "
        f"(input {plan.in_blocks[0]} blocks, widest activation "
        f"{plan.act_blocks} blocks of {plan.block}) > budget {VMEM_BUDGET_BYTES} B"
    )


def check_fwd_bt(plan: ChainPlan, bt: int, x_elt: int, v_elt: int, quant: bool) -> None:
    """Raise unless the forward's footprint at ``bt`` fits the budget.  The
    tile is chosen once, by dispatch (``repro.api.dispatch``), and the
    kernel runs at exactly the tile it is given."""
    need = fwd_vmem_bytes(plan, bt, x_elt, v_elt, quant)
    if need > VMEM_BUDGET_BYTES:
        why = fwd_infeasible(plan, x_elt, v_elt, quant)
        raise ValueError(
            why or f"fused forward needs {need} B of VMEM at bt={bt} "
            f"> budget {VMEM_BUDGET_BYTES} B; fit the tile with fit_bt"
        )


def _chain_kernel(meta_ref, x_ref, v_ref, *refs, n_in0, blk, quant, precision):
    # Quantized chains stream one extra input: the step's (1, blk) f32 scale
    # row.  It scales the value block's rows, which is the same as scaling
    # the activation's columns, (a·diag(s)) @ Q — so the row multiplies the
    # activation and the codes enter the dot as stored.  HBM moves only
    # 1-byte codes + blk scale floats per step.
    if quant:
        s_ref, o_ref, act_ref, acc_ref = refs
    else:
        o_ref, act_ref, acc_ref = refs
    s = pl.program_id(1)
    row = s * META_COLS
    i_blk = meta_ref[row]
    o_blk = meta_ref[row + 1]
    par = meta_ref[row + 2]

    @pl.when(s == 0)
    def _load_x():
        # Stage the batch tile into ping-pong buffer 0, block-major.
        for b in range(n_in0):
            act_ref[0, b] = x_ref[:, b * blk : (b + 1) * blk]

    @pl.when(meta_ref[row + 3] == 1)
    def _open():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = act_ref[par, i_blk]
    v = v_ref[0]
    if quant:
        a = a.astype(jnp.float32) * s_ref[0]
        v = v.astype(jnp.float32)
    acc_ref[...] += jnp.dot(
        a,
        v,
        precision=precision,
        preferred_element_type=jnp.float32,
    )

    @pl.when(meta_ref[row + 4] == 1)
    def _flush():
        cols = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
        tile = jnp.where(cols < meta_ref[row + 6], acc_ref[...], 0.0)

        @pl.when(meta_ref[row + 5] == 0)
        def _to_scratch():
            act_ref[1 - par, o_blk] = tile.astype(act_ref.dtype)

        @pl.when(meta_ref[row + 5] == 1)
        def _to_out():
            # the output BlockSpec already points at block o_blk
            o_ref[...] = tile.astype(o_ref.dtype)


def _out_index(bi, s, meta):
    # Final-factor steps write output block out_blk; every earlier step
    # parks on block 0, which the first final-factor group writes before
    # the index first moves — so no unwritten block is ever flushed.
    row = s * META_COLS
    return (bi, jnp.where(meta[row + 5] == 1, meta[row + 1], 0))


def chain_matmul(
    x: Array,
    values: Array,
    meta: Array,
    *,
    plan: ChainPlan,
    bt: int = DEFAULT_BT,
    interpret: bool = False,
    scales: Array | None = None,
) -> Array:
    """Fused ``y = x @ F_1 @ ... @ F_J`` in a single ``pallas_call``.

    ``x``: (B, IB_1·blk) with B % bt == 0; ``values``: (S, blk, blk) flat
    blocks; ``meta``: (S, META_COLS) int32 step table (see module header;
    build with :func:`repro.kernels.ops.chain_meta`). Returns
    (B, O_J·blk) — ragged tails already zeroed, caller slices/scales.
    ``bt`` must fit the VMEM budget (:func:`check_fwd_bt`); dispatch fits
    it with :func:`fit_bt`.

    ``scales``: optional (S, blk) f32 per-block-row scales for a quantized
    ``values`` payload (int8/fp8) — streamed alongside each value block as
    ``(1, 1, blk)`` rows (a free reshape: no padded HBM copy) and applied to
    the activation's columns in VMEM before the dot.
    """
    b, in_pad = x.shape
    blk = plan.block
    n_steps = plan.n_steps
    assert b % bt == 0, (b, bt)
    assert in_pad == plan.in_blocks[0] * blk, (in_pad, plan.in_blocks[0], blk)
    assert values.shape == (n_steps, blk, blk), values.shape
    assert meta.shape == (n_steps, META_COLS), meta.shape
    quant = scales is not None
    if quant:
        assert scales.shape == (n_steps, blk), scales.shape
    check_fwd_bt(
        plan, bt, jnp.dtype(x.dtype).itemsize, jnp.dtype(values.dtype).itemsize, quant
    )
    out_w = plan.out_blocks[-1] * blk
    grid = (b // bt, n_steps)

    in_specs = [
        # x: whole batch tile, refetched only when the tile changes
        pl.BlockSpec((bt, in_pad), lambda bi, s, meta: (bi, 0)),
        # values: the s-th flat block — streams with double buffering
        pl.BlockSpec((1, blk, blk), lambda bi, s, meta: (s, 0, 0)),
    ]
    operands = [meta.reshape(-1), x, values]
    if quant:
        # scale rows ride the same per-step stream as the value blocks
        in_specs.append(pl.BlockSpec((1, 1, blk), lambda bi, s, meta: (s, 0, 0)))
        operands.append(scales.reshape(n_steps, 1, blk))

    return pl.pallas_call(
        functools.partial(
            _chain_kernel,
            n_in0=plan.in_blocks[0],
            blk=blk,
            quant=quant,
            precision=dot_precision(x.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            # output: one (bt, blk) block, written by its final-factor group
            out_specs=pl.BlockSpec((bt, blk), _out_index),
            scratch_shapes=[
                # ping-pong activation buffers, block-major
                pltpu.VMEM((2, plan.act_blocks, bt, blk), x.dtype),
                # f32 accumulator for the open output block
                pltpu.VMEM((bt, blk), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, out_w), x.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="faust_chain_fwd",
    )(*operands)
