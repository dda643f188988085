"""Pallas TPU kernels: fused backward of the packed FAµST chain.

The fused forward (``kernels/chain.py``) applies ``y = x @ F_1 @ ... @ F_J``
in one launch with the intermediate activations resident in VMEM — they
never reach HBM, so there is nothing saved for autodiff.  The original
backward rematerialized every per-factor activation with the reference
einsums and walked the chain factor-by-factor: ~3·J launches and the full
``2·batch·Σ_j d_j`` HBM activation round-trip the forward was built to
avoid.  This module gives the backward the same fusion treatment
(FlashAttention-style: recompute inside VMEM, not through HBM):

**dgrad** — ``dx = dy @ F_Jᵀ @ ... @ F_1ᵀ`` as one ``pallas_call``.  The
step table is the forward's, reversed (``ChainPlan.reverse()`` describes
the transposed chain); each step reads its ``(blk × blk)`` value block
*transposed* straight from the packed ``(S, blk, blk)`` layout and
scatter-accumulates ``g_o @ F[s]ᵀ`` into the ping-pong cotangent buffer —
the gather-on-input forward is a scatter-on-input backward, so steps
accumulate directly into VMEM slabs instead of framing an accumulator.
Cotangents are masked at ragged factor boundaries exactly where the
forward masked activations (the forward zeroed those columns, so their
cotangent is dropped).

**wgrad** — per-slot ``dvalues[s] = a_jᵀ @ g_j`` for every stored block,
in one ``pallas_call`` of ``S_pre + S`` steps: a forward *recompute* phase
re-runs factors ``1..J-1`` (checkpoint-free — the per-factor inputs
``a_j`` land in one flat VMEM scratch, zero HBM activation traffic),
then a reversed cotangent walk emits one packed ``(blk, blk)`` cotangent
block per step while propagating ``g`` through the same transposed reads
as dgrad.  Batch tiles each emit a partial ``(S, blk, blk)`` slab
(accumulated outside the kernel — one ``s_tot`` store per tile, f32);
single-tile batches store ``s_tot`` exactly once.

Together: the whole chain backward is **≤ 2 launches** for any J (vs
~3·J), with weight traffic ``3·s_tot`` (dgrad stream + wgrad's two
phases) and *no* per-boundary activation round-trips.  VMEM budget: the
wgrad scratch holds every per-factor input activation
(``Σ_j IB_j · bt · blk`` f32) plus the cotangent ping-pong, sized by the
input and inner widths only — ``dy`` streams in one ``(bt, blk)`` block
per last-factor step, so a vocabulary-wide output costs no VMEM.  Chains
with wide inner widths run a smaller batch tile: dispatch halves ``bt``
until the footprint fits (:func:`fit_bt` — interpret mode never checks
VMEM, real TPU does at compile time) and the kernels check the tile they
are given (:func:`check_bwd_bt`); a chain that fits no tile is refused
from its shapes (:func:`bwd_infeasible`).  Step tables are
scalar-prefetched flat, as in the forward.

``chain_bwd_ref`` is the step-exact jnp oracle (the old rematerializing
walk) — the parity target for tests and the ``REPRO_CHAIN_BWD=ref``
escape hatch in ``kernels/ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compress import ChainPlan
from repro.core.eager import cached
from repro.kernels import ref as _ref
from repro.kernels.chain import (
    COMPILER_PARAMS,
    DEFAULT_BT,
    MIN_BT,
    VMEM_BUDGET_BYTES,
    dot_precision,
)
from repro.kernels.chain import fit_bt as _fit_bt

Array = jax.Array

# dgrad meta columns (one row per *reversed* step t; flat step s = S-1-t):
#   0 dst_blk  input block the step scatter-accumulates into (runtime in_idx)
#   1 src_blk  output block of the cotangent this step reads (static o)
#   2 parity   ping-pong buffer holding this factor's cotangent input
#   3 is_j0    1 ⇔ first reversed step of a factor: zero the dst buffer
#   4 ncols    valid columns of the src cotangent block (ragged mask — the
#              forward zeroed these columns, so the cotangent drops them)
DGRAD_META_COLS = 5

# wgrad meta columns (S_pre forward-recompute rows, then S reversed rows):
#   fwd rows:  0 in_blk (runtime)  1 out_blk  2 is_k0  3 is_kend
#              4 ncols  5 act_off_in  6 act_off_out
#   bwd rows:  0 dst_blk (runtime) 1 src_blk  2 parity 3 is_j0
#              4 ncols  5 act_off_j 6 propagate (0 on factor 0 — dx is
#                                    dgrad's job, the walk stops there)
WGRAD_META_COLS = 7


# ---------------------------------------------------------------------------
# Step-table assembly (host-side; cached per operator identity)
# ---------------------------------------------------------------------------

# Assembled (static ++ runtime in_idx) tables, keyed by the in_idx array
# identity — repeated eager applies of the same operator do zero per-call
# host work.  Rebuilt under tracing (``repro.core.eager``); the per-plan
# static halves below stay lru-cached either way.
_TABLE_CACHE: dict[tuple, tuple] = {}
_TABLE_CACHE_MAX = 256


def cached_table(plan: ChainPlan, in_idx: Array, tag: str, build) -> Array:
    """Cache ``build()`` per ``(in_idx identity, plan, tag)``; assemble
    inline under tracing."""
    return cached(_TABLE_CACHE, _TABLE_CACHE_MAX, in_idx, (plan, tag), build)


def _ncols(plan: ChainPlan, j: int, o: np.ndarray) -> np.ndarray:
    return np.minimum(plan.block, plan.out_feats[j] - o * plan.block)


# Footprint the batch-tile search fits into (module-level so tests can
# shrink it); Mosaic gets ``chain.VMEM_LIMIT_BYTES`` as its scoped limit.
_VMEM_BUDGET_BYTES = VMEM_BUDGET_BYTES


def bwd_vmem_bytes(
    plan: ChainPlan, bt: int, elt: int, *, wgrad: bool, quant: bool = False
) -> int:
    """VMEM footprint of :func:`chain_dgrad` (``wgrad=False``) or
    :func:`chain_wgrad` at batch tile ``bt``; ``elt`` is the activation /
    cotangent itemsize.  Value blocks are counted at 4 bytes (an upper
    bound for every stored dtype), a quantized chain's scale row
    sublane-padded to 8.  The chain-end cotangent streams one ``(bt, blk)``
    block per step, so the output width never enters."""
    blk = plan.block
    in_w = plan.in_blocks[0] * blk
    streams = 2 * bt * blk * elt + 2 * blk * blk * 4  # dy block, value block
    if quant:
        streams += 2 * 8 * blk * 4  # scale row
    cot = 2 * plan.act_blocks * bt * blk * 4  # f32 cotangent ping-pong
    if not wgrad:
        return streams + 2 * bt * in_w * elt + cot  # + dx tile
    acts = sum(plan.in_blocks) * bt * blk * 4  # every factor's input
    return (
        streams + 2 * bt * in_w * elt  # x tile
        + 2 * blk * blk * 4  # dvalues block
        + acts + cot + bt * blk * 4  # + recompute accumulator
    )


def fit_bt(
    plan: ChainPlan, bt: int, elt: int, *, wgrad: bool, quant: bool = False
) -> int | None:
    """Largest power-of-two divisor of ``bt`` (≥ 8) whose backward-kernel
    footprint fits the VMEM budget, or None when none does.  Unlike the
    forward kernel (one ping-pong pair in x dtype), the backward holds f32
    cotangent slabs — and wgrad additionally every factor's input
    activation — so chains with wide inner widths shrink the batch tile
    instead of overflowing VMEM at kernel compile time."""
    return _fit_bt(
        lambda t: bwd_vmem_bytes(plan, t, elt, wgrad=wgrad, quant=quant),
        bt,
        _VMEM_BUDGET_BYTES,
    )


def bwd_infeasible(plan: ChainPlan, elt: int, quant: bool = False) -> str | None:
    """Why no batch tile fits the backward kernels' VMEM budget (None when
    one does) — shape-computed, like :func:`repro.kernels.chain.fwd_infeasible`."""
    need = bwd_vmem_bytes(plan, MIN_BT, elt, wgrad=True, quant=quant)
    if need <= _VMEM_BUDGET_BYTES:
        return None
    return (
        f"fused backward needs {need} B of VMEM at bt={MIN_BT} "
        f"(input {plan.in_blocks[0]} blocks, activations {sum(plan.in_blocks)} "
        f"blocks of {plan.block}) > budget {_VMEM_BUDGET_BYTES} B"
    )


def check_bwd_bt(
    plan: ChainPlan, bt: int, elt: int, *, wgrad: bool, quant: bool = False
) -> None:
    """Raise unless the backward kernel's footprint at ``bt`` fits the
    budget.  Dispatch fits the tile for forward and backward together when
    it sees the apply is differentiated; a ``grad(jit(f))`` trace hides
    that, and such callers pass ``apply(..., grad=True)``."""
    need = bwd_vmem_bytes(plan, bt, elt, wgrad=wgrad, quant=quant)
    if need > _VMEM_BUDGET_BYTES:
        raise ValueError(
            bwd_infeasible(plan, elt, quant)
            or f"fused {'wgrad' if wgrad else 'dgrad'} needs {need} B of VMEM "
            f"at bt={bt} > budget {_VMEM_BUDGET_BYTES} B; dispatch with "
            f"grad=True fits the tile for the backward"
        )


@functools.lru_cache(maxsize=64)
def _dgrad_meta_static(plan: ChainPlan) -> np.ndarray:
    """Static dgrad columns (1..4), rows already in reversed step order."""
    rows = []
    for j in range(plan.n_factors):
        o_count, k_count = plan.out_blocks[j], plan.k_blocks[j]
        o = np.repeat(np.arange(o_count), k_count)
        cols = np.empty((o_count * k_count, DGRAD_META_COLS - 1), dtype=np.int32)
        cols[:, 0] = o  # src_blk
        cols[:, 1] = (plan.n_factors - 1 - j) % 2  # parity (source buffer)
        start = np.zeros(o_count * k_count, dtype=np.int32)
        start[-1] = 1  # last flat step of factor j == first reversed step
        cols[:, 2] = start
        cols[:, 3] = _ncols(plan, j, o)
        rows.append(cols)
    return np.concatenate(rows, axis=0)[::-1].copy()


def dgrad_meta(plan: ChainPlan, in_idx: Array) -> Array:
    """(S, DGRAD_META_COLS) reversed step table: runtime ``in_idx`` (reversed)
    in column 0, static columns after it."""

    def build():
        static = jnp.asarray(_dgrad_meta_static(plan))
        dst = in_idx[::-1].astype(jnp.int32)[:, None]
        return jnp.concatenate([dst, static], axis=1)

    return cached_table(plan, in_idx, "dgrad", build)


def _act_offsets(plan: ChainPlan) -> tuple[int, ...]:
    """Flat-scratch offset of each factor's *input* activation blocks."""
    offs = [0]
    for ib in plan.in_blocks:
        offs.append(offs[-1] + ib)
    return tuple(offs)


@functools.lru_cache(maxsize=64)
def _wgrad_meta_static(plan: ChainPlan) -> np.ndarray:
    """Static wgrad columns (1..6): ``S_pre`` forward-recompute rows for
    factors ``0..J-2`` followed by ``S`` reversed cotangent-walk rows."""
    actoff = _act_offsets(plan)
    fwd = []
    for j in range(plan.n_factors - 1):  # last factor's output is unused
        o_count, k_count = plan.out_blocks[j], plan.k_blocks[j]
        o = np.repeat(np.arange(o_count), k_count)
        k = np.tile(np.arange(k_count), o_count)
        cols = np.empty((o_count * k_count, WGRAD_META_COLS - 1), dtype=np.int32)
        cols[:, 0] = o  # out_blk
        cols[:, 1] = k == 0  # is_k0
        cols[:, 2] = k == k_count - 1  # is_kend
        cols[:, 3] = _ncols(plan, j, o)
        cols[:, 4] = actoff[j]  # act_off_in
        cols[:, 5] = actoff[j + 1]  # act_off_out
        fwd.append(cols)
    bwd = []
    for j in range(plan.n_factors):
        o_count, k_count = plan.out_blocks[j], plan.k_blocks[j]
        o = np.repeat(np.arange(o_count), k_count)
        cols = np.empty((o_count * k_count, WGRAD_META_COLS - 1), dtype=np.int32)
        cols[:, 0] = o  # src_blk
        cols[:, 1] = (plan.n_factors - 1 - j) % 2  # parity
        start = np.zeros(o_count * k_count, dtype=np.int32)
        start[-1] = 1
        cols[:, 2] = start  # is_j0
        cols[:, 3] = _ncols(plan, j, o)
        cols[:, 4] = actoff[j]  # act_off_j
        cols[:, 5] = int(j > 0)  # propagate
        bwd.append(cols)
    bwd_rows = np.concatenate(bwd, axis=0)[::-1]
    parts = fwd + [bwd_rows]
    return np.concatenate(parts, axis=0).copy()


def wgrad_meta(plan: ChainPlan, in_idx: Array) -> Array:
    """(S_pre + S, WGRAD_META_COLS) two-phase step table: forward-recompute
    rows carry the forward ``in_idx``, walk rows the reversed one."""

    def build():
        static = jnp.asarray(_wgrad_meta_static(plan))
        s_pre = plan.offsets[plan.n_factors - 1]
        idx = jnp.concatenate(
            [in_idx[:s_pre], in_idx[::-1]]
        ).astype(jnp.int32)[:, None]
        return jnp.concatenate([idx, static], axis=1)

    return cached_table(plan, in_idx, "wgrad", build)


# ---------------------------------------------------------------------------
# dgrad kernel
# ---------------------------------------------------------------------------


def _dgrad_kernel(
    meta_ref, dy_ref, v_ref, *refs, n_last, n_in0, blk, n_steps, out_par,
    quant, precision,
):
    # Quantized chains stream the per-step (1, blk) f32 scale row next to
    # the value block.  Scaling the block's *rows* commutes with the
    # transposed read, g @ (diag(s)·Q)ᵀ = (g @ Qᵀ)·diag(s), so the scale
    # multiplies the product's columns and Q enters the dot as stored.
    if quant:
        s_ref, o_ref, cot_ref = refs
    else:
        o_ref, cot_ref = refs
    t = pl.program_id(1)
    row = t * DGRAD_META_COLS
    dst = meta_ref[row]
    src = meta_ref[row + 1]
    par = meta_ref[row + 2]
    v = v_ref[0]
    if quant:
        v = v.astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (dy_ref.shape[0], blk), 1)

    @pl.when(meta_ref[row + 3] == 1)
    def _open_factor():
        # Scatter target of a fresh factor: blocks never written must read 0.
        cot_ref[1 - par] = jnp.zeros(cot_ref.shape[1:], cot_ref.dtype)

    def scatter(g):
        # g @ F[s]ᵀ — the transposed block read straight off the packed layout
        g = jnp.where(cols < meta_ref[row + 4], g, 0.0)
        gv = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32,
        )
        cot_ref[1 - par, dst] += gv * s_ref[0] if quant else gv

    @pl.when(t < n_last)
    def _from_dy():
        # the last factor's cotangent is dy itself: its block `src` is the
        # one the dy BlockSpec fetched for this step
        scatter(dy_ref[...].astype(jnp.float32))

    @pl.when(t >= n_last)
    def _from_scratch():
        scatter(cot_ref[par, src])

    @pl.when(t == n_steps - 1)
    def _to_out():
        for b in range(n_in0):
            o_ref[:, b * blk : (b + 1) * blk] = cot_ref[out_par, b].astype(
                o_ref.dtype
            )


def _dy_index(first: int, n_last: int, cols: int):
    """dy BlockSpec index map: during the ``n_last`` walk steps of the last
    factor (from step ``first``) fetch the cotangent block the step reads;
    park on block 0 (the last one that phase fetched) otherwise."""

    def index(bi, t, meta):
        live = (t >= first) & (t < first + n_last)
        return (bi, jnp.where(live, meta[t * cols + 1], 0))

    return index


def chain_dgrad(
    dy: Array,
    values: Array,
    in_idx: Array,
    *,
    plan: ChainPlan,
    bt: int = DEFAULT_BT,
    interpret: bool = False,
    scales: Array | None = None,
) -> Array:
    """Fused ``dx = dy @ F_Jᵀ @ ... @ F_1ᵀ`` in a single ``pallas_call``.

    ``dy``: (B, O_J·blk) with B % bt == 0 (the cotangent of the *padded*
    forward output — ragged tails are re-masked in-kernel either way).
    Returns (B, IB_1·blk), the cotangent of the padded forward input.
    ``scales``: optional (S, blk) f32 per-block-row scales for quantized
    ``values`` — dequantized in VMEM alongside the reversed value stream.
    """
    b, out_w = dy.shape
    blk = plan.block
    n_steps = plan.n_steps
    n_last = n_steps - plan.offsets[-2]  # walk steps of the last factor
    assert b % bt == 0, (b, bt)
    assert out_w == plan.out_blocks[-1] * blk, (out_w, plan.out_blocks[-1], blk)
    assert values.shape == (n_steps, blk, blk), values.shape
    quant = scales is not None
    check_bwd_bt(plan, bt, jnp.dtype(dy.dtype).itemsize, wgrad=False, quant=quant)
    meta = dgrad_meta(plan, in_idx)
    in_pad = plan.in_blocks[0] * blk
    grid = (b // bt, n_steps)

    in_specs = [
        pl.BlockSpec((bt, blk), _dy_index(0, n_last, DGRAD_META_COLS)),
        # the t-th reversed flat block — streams with double buffering
        pl.BlockSpec((1, blk, blk), lambda bi, t, meta: (n_steps - 1 - t, 0, 0)),
    ]
    operands = [meta.reshape(-1), dy, values]
    if quant:
        assert scales.shape == (n_steps, blk), scales.shape
        in_specs.append(pl.BlockSpec((1, 1, blk), lambda bi, t, meta: (n_steps - 1 - t, 0, 0)))
        operands.append(scales.reshape(n_steps, 1, blk))

    return pl.pallas_call(
        functools.partial(
            _dgrad_kernel,
            n_last=n_last,
            n_in0=plan.in_blocks[0],
            blk=blk,
            n_steps=n_steps,
            out_par=plan.n_factors % 2,
            quant=quant,
            precision=dot_precision(dy.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bt, in_pad), lambda bi, t, meta: (bi, 0)),
            scratch_shapes=[
                # cotangent ping-pong, f32 (scatter-accumulated in place)
                pltpu.VMEM((2, plan.act_blocks, bt, blk), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, in_pad), dy.dtype),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="faust_chain_dgrad",
    )(*operands)


# ---------------------------------------------------------------------------
# wgrad kernel
# ---------------------------------------------------------------------------


def _wgrad_kernel(
    meta_ref, x_ref, dy_ref, v_ref, *refs, s_pre, n_last, n_in0, blk, quant,
    precision,
):
    # Quantized chains stream the step's (1, blk) f32 scale row next to the
    # code block and apply it on the activation side — (a·diag(s)) @ Q in
    # the recompute, (g @ Qᵀ)·diag(s) in the propagation — so one value
    # stream feeds both phases and the backward stays ≤ 2 launches.
    if quant:
        s_ref, o_ref, acts_ref, cot_ref, acc_ref = refs
    else:
        o_ref, acts_ref, cot_ref, acc_ref = refs
    t = pl.program_id(1)
    row = t * WGRAD_META_COLS
    v = v_ref[0]
    if quant:
        v = v.astype(jnp.float32)

    @pl.when(t == 0)
    def _load_x():
        for b in range(n_in0):
            acts_ref[b] = x_ref[:, b * blk : (b + 1) * blk].astype(jnp.float32)

    @pl.when(t < s_pre)
    def _recompute():
        # Forward step (factors 0..J-2), identical framing to the forward
        # kernel; flushes land in the flat per-factor activation scratch.
        @pl.when(meta_ref[row + 2] == 1)
        def _open():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        a = acts_ref[meta_ref[row + 5] + meta_ref[row]]
        acc_ref[...] += jnp.dot(
            a * s_ref[0] if quant else a,
            v,
            precision=precision,
            preferred_element_type=jnp.float32,
        )

        @pl.when(meta_ref[row + 3] == 1)
        def _flush():
            cols = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 1)
            acts_ref[meta_ref[row + 6] + meta_ref[row + 1]] = jnp.where(
                cols < meta_ref[row + 4], acc_ref[...], 0.0
            )

    dst = meta_ref[row]
    src = meta_ref[row + 1]
    par = meta_ref[row + 2]
    cols = jax.lax.broadcasted_iota(jnp.int32, (x_ref.shape[0], blk), 1)

    def walk(g):
        g = jnp.where(cols < meta_ref[row + 4], g, 0.0)
        # per-slot cotangent block: a_jᵀ @ g  (blk × blk), written once
        o_ref[0, 0] = jax.lax.dot_general(
            acts_ref[meta_ref[row + 5] + dst],
            g,
            (((0,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32,
        )

        @pl.when(meta_ref[row + 6] == 1)
        def _propagate():
            gv = jax.lax.dot_general(
                g,
                v,
                (((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32,
            )
            cot_ref[1 - par, dst] += gv * s_ref[0] if quant else gv

    @pl.when((t >= s_pre) & (meta_ref[row + 3] == 1))
    def _open_factor():
        cot_ref[1 - par] = jnp.zeros(cot_ref.shape[1:], cot_ref.dtype)

    @pl.when((t >= s_pre) & (t < s_pre + n_last))
    def _walk_dy():
        # the last factor's cotangent is dy itself (block `src`, fetched
        # by the dy BlockSpec for this step)
        walk(dy_ref[...].astype(jnp.float32))

    @pl.when(t >= s_pre + n_last)
    def _walk_scratch():
        walk(cot_ref[par, src])


def chain_wgrad(
    x: Array,
    dy: Array,
    values: Array,
    in_idx: Array,
    *,
    plan: ChainPlan,
    bt: int = DEFAULT_BT,
    interpret: bool = False,
    scales: Array | None = None,
) -> Array:
    """Fused per-slot weight cotangent ``dvalues (S, blk, blk)`` in a single
    ``pallas_call`` (forward recompute + reversed cotangent walk — see the
    module docstring).  ``x``/``dy`` are the padded forward input/output
    cotangent, B % bt == 0.  Returns f32 (cast by the caller) — partial
    per-tile slabs are summed here when B > bt.

    ``scales``: optional (S, blk) f32 per-block-row scales for quantized
    ``values`` — the emitted cotangent is then wrt the *dequantized* f32
    values (the caller chain-rules it onto the scales).
    """
    b, in_w = x.shape
    blk = plan.block
    n_steps = plan.n_steps
    s_pre = plan.offsets[plan.n_factors - 1]
    n_last = n_steps - s_pre  # walk steps of the last factor
    assert b % bt == 0, (b, bt)
    assert dy.shape == (b, plan.out_blocks[-1] * blk), dy.shape
    assert values.shape == (n_steps, blk, blk), values.shape
    quant = scales is not None
    check_bwd_bt(plan, bt, jnp.dtype(x.dtype).itemsize, wgrad=True, quant=quant)
    meta = wgrad_meta(plan, in_idx)
    n_tiles = b // bt
    grid = (n_tiles, s_pre + n_steps)

    def _v_index(bi, t, meta):
        return (jnp.where(t < s_pre, t, s_pre + n_steps - 1 - t), 0, 0)

    def _o_index(bi, t, meta):
        # forward-phase steps park on the first walk block (S-1) so no
        # unwritten buffer is ever flushed; walk step t emits flat block
        # S-1-(t-s_pre)
        return (bi, jnp.where(t < s_pre, n_steps - 1, s_pre + n_steps - 1 - t), 0, 0)

    in_specs = [
        pl.BlockSpec((bt, in_w), lambda bi, t, meta: (bi, 0)),
        pl.BlockSpec((bt, blk), _dy_index(s_pre, n_last, WGRAD_META_COLS)),
        pl.BlockSpec((1, blk, blk), _v_index),
    ]
    operands = [meta.reshape(-1), x, dy, values]
    if quant:
        assert scales.shape == (n_steps, blk), scales.shape
        in_specs.append(pl.BlockSpec((1, 1, blk), _v_index))
        operands.append(scales.reshape(n_steps, 1, blk))

    partials = pl.pallas_call(
        functools.partial(
            _wgrad_kernel,
            s_pre=s_pre,
            n_last=n_last,
            n_in0=plan.in_blocks[0],
            blk=blk,
            quant=quant,
            precision=dot_precision(x.dtype),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, blk, blk), _o_index),
            scratch_shapes=[
                # every factor's input activation, flat (recompute target)
                pltpu.VMEM((sum(plan.in_blocks), bt, blk), jnp.float32),
                # cotangent ping-pong for the walk
                pltpu.VMEM((2, plan.act_blocks, bt, blk), jnp.float32),
                # forward-phase f32 accumulator
                pltpu.VMEM((bt, blk), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles, n_steps, blk, blk), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="faust_chain_wgrad",
    )(*operands)
    return partials[0] if n_tiles == 1 else partials.sum(axis=0)


# ---------------------------------------------------------------------------
# Reference oracle (the pre-fusion rematerializing walk)
# ---------------------------------------------------------------------------


def chain_bwd_ref(
    x: Array, values: Array, in_idx: Array, dy: Array, *, plan: ChainPlan
) -> tuple[Array, Array]:
    """Step-exact jnp oracle for (dgrad, wgrad): rematerialize the
    per-factor activations with the reference einsums and walk the chain
    backwards (identical to XLA autodiff of ``ref.packed_chain_ref``).
    Pays the per-boundary HBM round-trips the kernels avoid — kept as the
    parity target and the ``REPRO_CHAIN_BWD=ref`` fallback."""
    blk = plan.block
    acts = [x]
    y = x
    for j in range(plan.n_factors - 1):
        vj, ij = _ref.factor_slices(values, in_idx, plan, j)
        y = _ref._mask_tail(_ref.bsr_matmul_ref(y, vj, ij), plan.out_feats[j])
        acts.append(y)
    g = dy
    dvals = []
    for j in reversed(range(plan.n_factors)):
        vj, ij = _ref.factor_slices(values, in_idx, plan, j)
        # forward zeroed the ragged tail, so its cotangent is dropped too
        g = _ref._mask_tail(g, plan.out_feats[j])
        dvals.append(
            _ref.bsr_matmul_dvalues(acts[j], g, ij, (blk, blk)).reshape(-1, blk, blk)
        )
        g = _ref.bsr_matmul_dx(g, vj, ij, plan.in_blocks[j] * blk)
    dvalues = jnp.concatenate(dvals[::-1], axis=0)
    return g, dvalues
