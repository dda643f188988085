"""Packed block-sparse FAµST representation + dense→FAµST compression.

Deployment format (consumed by the Pallas kernel and FaustLinear):

:class:`BlockSparseFactor` packs a right-multiplication factor
``F ∈ R^{in × out}`` whose support is a union of aligned ``(bk × bn)``
blocks, **exactly k blocks per output block-column**:

    values : (n_out_blocks, k, bk, bn)
    in_idx : (n_out_blocks, k) int32      — input block ids gathered per
                                            output block

so that ``y[:, o·bn:(o+1)·bn] = Σ_j  x[:, in_idx[o,j]·bk : +bk] @ values[o,j]``.

The gather-on-input/no-scatter layout means one kernel program owns one
output block — the TPU-friendly shape (DESIGN.md §3).

Dense→FAµST factorization moved behind the unified front door
:func:`repro.api.factorize` (see EXPERIMENTS.md §Operator API).  This
module keeps the *formats* (pack/unpack, random prescribed-support init)
plus the shared orientation/constraint helpers the block route uses and
the workload drivers (``compress_layers`` / ``compress_model`` — thin
wrappers bucketing named weights into ``factorize`` calls, optionally
mesh-sharded).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projections as P
from repro.core.faust import Faust
from repro.core.hierarchical import HierarchicalInfo, HierarchicalSpec

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockSparseFactor:
    """Packed block-sparse factor for ``y = x @ F`` (see module docstring)."""

    values: Array  # (O, K, bk, bn)
    in_idx: Array  # (O, K) int32
    in_features: int
    out_features: int

    def tree_flatten(self):
        return (self.values, self.in_idx), (self.in_features, self.out_features)

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, in_idx = children
        return cls(values, in_idx, aux[0], aux[1])

    @property
    def bk(self) -> int:
        return self.values.shape[2]

    @property
    def bn(self) -> int:
        return self.values.shape[3]

    @property
    def k(self) -> int:
        return self.values.shape[1]

    @property
    def n_out_blocks(self) -> int:
        return self.values.shape[0]

    @property
    def n_in_blocks(self) -> int:
        return -(-self.in_features // self.bk)  # ceil: padded block count

    @property
    def nnz(self) -> int:
        return int(np.prod(self.values.shape))

    def todense(self) -> Array:
        """Materialize F (in_features × out_features)."""
        o, k, bk, bn = self.values.shape
        ib = self.n_in_blocks
        dense = jnp.zeros((ib, o, bk, bn), dtype=self.values.dtype)
        ob = jnp.broadcast_to(jnp.arange(o)[:, None], (o, k))
        dense = dense.at[self.in_idx, ob].add(self.values)
        dense = dense.transpose(0, 2, 1, 3).reshape(ib * bk, o * bn)
        return dense[: self.in_features, : self.out_features]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BlockFaust:
    """Deployment FAµST: ``W ≈ lam · F_1 F_2 ··· F_J`` (right-multiply chain:
    ``y = lam · (((x @ F_1) @ F_2) ...)``)."""

    factors: tuple[BlockSparseFactor, ...]
    lam: Array

    def tree_flatten(self):
        return (self.factors, self.lam), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        factors, lam = children
        return cls(tuple(factors), lam)

    @property
    def in_features(self) -> int:
        return self.factors[0].in_features

    @property
    def out_features(self) -> int:
        return self.factors[-1].out_features

    @property
    def s_tot(self) -> int:
        return sum(f.nnz for f in self.factors)

    def rc(self) -> float:
        return self.s_tot / (self.in_features * self.out_features)

    def rcg(self) -> float:
        return 1.0 / self.rc()

    def todense(self) -> Array:
        w = self.factors[0].todense()
        for f in self.factors[1:]:
            w = w @ f.todense()
        return self.lam * w


# ---------------------------------------------------------------------------
# Fused-chain packing (single-pallas_call apply — see repro.kernels.chain)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChainPlan:
    """Static (hashable) metadata for a flat-packed FAµST chain.

    The fused kernel (``repro.kernels.chain``) enumerates one *step* per
    stored block, in ``(factor j, output block o, gathered slot k)``
    lexicographic order, so step ``s`` of the flat arrays is block
    ``(j, o, k)`` with ``s = offsets[j] + o·k_blocks[j] + k``::

        step s:   0        1        2        3       off[1]    …      S-1
                ┌────────┬────────┬────────┬────────╥────────┬─────┬────────┐
        values  │  j=0   │  j=0   │  j=0   │  j=0   ║  j=1   │  …  │ j=J-1  │
        (S,b,b) │ o=0 k=0│ o=0 k=1│ o=1 k=0│ o=1 k=1║ o=0 k=0│     │o=O-1   │
                └────────┴────────┴────────┴────────╨────────┴─────┴────────┘
                ╰── factor 0: O_0·K_0 blocks, offsets[0] = 0 ──╯
                                                    ╰── factor 1 starts at
                                                        offsets[1] = O_0·K_0

    (here factor 0 has O_0 = 2 output blocks gathering K_0 = 2 slots each).
    ``in_idx[s]`` names the input block of the *current* activation that
    step ``s`` multiplies; offsets make the factor boundaries recoverable
    without per-step factor ids.  Everything here is a Python int/tuple:
    the plan travels as a pytree aux / ``nondiff_argnums`` value and never
    enters the traced graph — two chains with equal plans share one kernel
    specialization.
    """

    block: int  # uniform square block side (bk == bn for every factor)
    in_blocks: tuple[int, ...]  # IB_j  = ceil(in_features_j / block)
    out_blocks: tuple[int, ...]  # O_j  = n_out_blocks of factor j
    k_blocks: tuple[int, ...]  # K_j  = gathered blocks per output block
    offsets: tuple[int, ...]  # len J+1: step offset of factor j (offsets[J] == n_steps)
    in_feats: tuple[int, ...]  # unpadded in_features per factor
    out_feats: tuple[int, ...]  # unpadded out_features per factor

    @property
    def n_factors(self) -> int:
        return len(self.out_blocks)

    @property
    def n_steps(self) -> int:
        return self.offsets[-1]

    @property
    def act_blocks(self) -> int:
        """Widest activation (in blocks) a chain kernel keeps resident in
        VMEM: the chain input and every inner boundary.  The last factor's
        output streams to HBM one block at a time, so the output width
        never sizes scratch."""
        return max(self.in_blocks)

    @property
    def in_features(self) -> int:
        return self.in_feats[0]

    @property
    def out_features(self) -> int:
        return self.out_feats[-1]

    def reverse(self) -> "ChainPlan":
        """Plan of the *transposed* chain ``Wᵀ = F_Jᵀ ··· F_1ᵀ``.

        Factor order flips and every factor swaps its input/output block
        domains; ``k_blocks``/step counts are unchanged (a transposed block
        is still one stored block).  The transposed chain is a *scatter*
        on the input side, so this plan never feeds the forward gather
        kernel — it drives the fused **dgrad** kernel's reversed step
        table (``repro.kernels.chain_bwd``) and the dispatch cost model's
        transposed-roofline pricing.  An involution: ``p.reverse().reverse()
        == p``.
        """
        sizes = tuple(
            self.offsets[j + 1] - self.offsets[j] for j in range(self.n_factors)
        )
        offs = [0]
        for s in reversed(sizes):
            offs.append(offs[-1] + s)
        return ChainPlan(
            block=self.block,
            in_blocks=tuple(reversed(self.out_blocks)),
            out_blocks=tuple(reversed(self.in_blocks)),
            k_blocks=tuple(reversed(self.k_blocks)),
            offsets=tuple(offs),
            in_feats=tuple(reversed(self.out_feats)),
            out_feats=tuple(reversed(self.in_feats)),
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedChain:
    """Flat-packed FAµST chain: every factor's blocks concatenated so a single
    Pallas launch can stream them (``repro.kernels.chain.chain_matmul``).

        values : (S, block, block)  — S = Σ_j O_j·K_j blocks, (j,o,k) order
        in_idx : (S,) int32         — input block id within the *current*
                                      activation for each step

    See the :class:`ChainPlan` docstring for the ASCII diagram of the
    ``(factor, out-block, slot)`` step ordering and the ``offsets``
    metadata that delimits factors.  The static layout lives in the plan
    (pytree aux), so a ``PackedChain`` jits/vmaps like any array pytree.
    """

    values: Array  # (S, block, block) — f32/bf16, or int8/fp8 when quantized
    in_idx: Array  # (S,) int32
    lam: Array  # scalar
    plan: ChainPlan
    # Low-precision payload (ISSUE 9): when ``qscheme`` is set, ``values``
    # holds the quantized codes and ``scales`` the per-block f32 scales —
    # shape (S,) for scheme "per_block", (S, block) for "per_row" (one scale
    # per block *row*, i.e. per input feature of the block).  The kernels
    # dequantize in VMEM; nothing outside this pair changes layout, so a
    # quantized chain shares the f32 chain's step tables and shard plans.
    scales: Array | None = None
    qscheme: str | None = None  # e.g. "int8:per_block", "fp8_e4m3:per_row"

    def tree_flatten(self):
        return (self.values, self.in_idx, self.lam, self.scales), (
            self.plan,
            self.qscheme,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, in_idx, lam, scales = children
        plan, qscheme = aux
        return cls(values, in_idx, lam, plan, scales, qscheme)

    @property
    def quantized(self) -> bool:
        return self.qscheme is not None

    @property
    def values_dtype(self) -> str:
        return str(jnp.dtype(self.values.dtype).name)

    @property
    def weight_bytes(self) -> int:
        """HBM bytes of one full weight stream (values + scales) — the
        post-quantization byte term the dispatch roofline prices."""
        b = int(np.prod(self.values.shape)) * jnp.dtype(self.values.dtype).itemsize
        if self.scales is not None:
            b += int(np.prod(self.scales.shape)) * jnp.dtype(self.scales.dtype).itemsize
        return b


# Quantization schemes for PackedChain values: name -> (jnp dtype, qmax).
# qmax is the largest representable magnitude the scale maps each block's
# absmax onto (int8 symmetric: 127; fp8: the format's finite max).
QUANT_DTYPES = {
    "int8": (jnp.int8, 127.0),
    "fp8_e4m3": (jnp.float8_e4m3fn, 448.0),
    "fp8_e5m2": (jnp.float8_e5m2, 57344.0),
}
QUANT_SCHEMES = ("per_block", "per_row")


def _scale_broadcast(scales: Array) -> Array:
    """Broadcastable view of scales against (S, blk, blk) values."""
    if scales.ndim == 1:  # per_block (S,)
        return scales[:, None, None]
    return scales[:, :, None]  # per_row (S, blk)


def expand_scales(scales: Array, blk: int) -> Array:
    """Normalize scales to the (S, blk) per-row layout the kernels stream
    (per_block (S,) scales broadcast exactly — no information change)."""
    sc = scales.astype(jnp.float32)
    if sc.ndim == 1:
        sc = jnp.broadcast_to(sc[:, None], (sc.shape[0], blk))
    return sc


def quantize_chain(
    chain: PackedChain, dtype: str = "int8", scheme: str = "per_block"
) -> PackedChain:
    """Quantize a packed chain's block values to ``dtype`` with per-block
    (or per-block-row) f32 scales.

    Symmetric absmax quantization: ``scale = absmax / qmax`` over each
    block (scheme "per_block") or block row (scheme "per_row"), then
    ``q = round(v / scale)`` clipped to the format (int8) or cast with
    round-to-nearest (fp8).  All-zero groups get scale 1.0 so the round
    trip stays exact.  ``lam``/``in_idx``/``plan`` are untouched — the
    quantized chain runs through the same step tables and shard plans.

    The round trip *from the quantized payload* is lossless:
    ``quantize_chain(dequantize_chain(q)) == q`` bit-for-bit.
    """
    if chain.qscheme is not None:
        raise ValueError(f"chain is already quantized ({chain.qscheme})")
    if dtype not in QUANT_DTYPES:
        raise ValueError(f"unknown quant dtype {dtype!r}; want one of {list(QUANT_DTYPES)}")
    if scheme not in QUANT_SCHEMES:
        raise ValueError(f"unknown quant scheme {scheme!r}; want one of {QUANT_SCHEMES}")
    qdt, qmax = QUANT_DTYPES[dtype]
    v = chain.values.astype(jnp.float32)
    axes = (1, 2) if scheme == "per_block" else (2,)
    amax = jnp.max(jnp.abs(v), axis=axes)
    scales = jnp.where(amax > 0, amax / qmax, 1.0).astype(jnp.float32)
    scaled = v / _scale_broadcast(scales)
    if dtype == "int8":
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(qdt)
    else:
        q = scaled.astype(qdt)  # round-to-nearest-even cast into the fp8 grid
    return PackedChain(q, chain.in_idx, chain.lam, chain.plan, scales, f"{dtype}:{scheme}")


def dequantize_chain(chain: PackedChain) -> PackedChain:
    """Exact f32 reconstruction of a quantized chain (``q * scale`` per
    block/row) — the reference the kernels' in-VMEM dequant must match
    step-exactly.  No-op on an unquantized chain."""
    if chain.qscheme is None:
        return chain
    v = chain.values.astype(jnp.float32) * _scale_broadcast(chain.scales)
    return PackedChain(v, chain.in_idx, chain.lam, chain.plan)


def chain_plan(bfaust: BlockFaust) -> ChainPlan:
    """The :class:`ChainPlan` :func:`pack_chain` would build — shapes
    only, no array is touched (callers must check packability first)."""
    factors = bfaust.factors
    offsets = [0]
    for f in factors:
        offsets.append(offsets[-1] + f.n_out_blocks * f.k)
    return ChainPlan(
        block=factors[0].bk,
        in_blocks=tuple(f.n_in_blocks for f in factors),
        out_blocks=tuple(f.n_out_blocks for f in factors),
        k_blocks=tuple(f.k for f in factors),
        offsets=tuple(offsets),
        in_feats=tuple(f.in_features for f in factors),
        out_feats=tuple(f.out_features for f in factors),
    )


def pack_chain(bfaust: BlockFaust) -> PackedChain:
    """Flatten a :class:`BlockFaust` into the fused-kernel layout.

    Requires uniform square blocks and a contiguous chain (each factor's
    padded output domain is exactly the next factor's padded input domain)
    — both hold for every factor produced by :func:`random_block_factor`
    with one block size or by the ``repro.api.factorize`` block route.  Raises
    ``ValueError`` otherwise; callers fall back to the per-factor path.
    """
    factors = bfaust.factors
    blk = factors[0].bk
    for f in factors:
        if f.bk != blk or f.bn != blk:
            raise ValueError(
                f"pack_chain needs uniform square blocks; got ({f.bk},{f.bn}) vs {blk}"
            )
    for a, b in zip(factors[:-1], factors[1:]):
        if a.out_features != b.in_features or a.n_out_blocks != b.n_in_blocks:
            raise ValueError(
                "pack_chain needs a contiguous chain: factor boundary "
                f"{a.out_features}/{a.n_out_blocks} blocks → "
                f"{b.in_features}/{b.n_in_blocks} blocks"
            )
    plan = chain_plan(bfaust)
    values = jnp.concatenate([f.values.reshape(-1, blk, blk) for f in factors])
    in_idx = jnp.concatenate(
        [f.in_idx.reshape(-1).astype(jnp.int32) for f in factors]
    )
    return PackedChain(values, in_idx, bfaust.lam, plan)


def unpack_chain(chain: PackedChain, dequantize: bool = True) -> BlockFaust:
    """Inverse of :func:`pack_chain`: recover the per-factor
    :class:`BlockFaust` from the flat-packed layout (pure reshapes/slices
    driven by the plan's offset metadata — no repacking heuristics).

    Quantized chains dequantize to f32 factors by default so every
    non-fused consumer (dense/bsr backends, ``todense``) sees exact
    reconstructed values; ``dequantize=False`` keeps the low-precision
    codes in the factor arrays (the sharded path slices scales
    separately and dequantizes in VMEM)."""
    if dequantize:
        chain = dequantize_chain(chain)
    plan = chain.plan
    blk = plan.block
    factors = []
    for j in range(plan.n_factors):
        o, k = plan.out_blocks[j], plan.k_blocks[j]
        sl = slice(plan.offsets[j], plan.offsets[j + 1])
        factors.append(
            BlockSparseFactor(
                chain.values[sl].reshape(o, k, blk, blk),
                chain.in_idx[sl].reshape(o, k),
                plan.in_feats[j],
                plan.out_feats[j],
            )
        )
    return BlockFaust(tuple(factors), chain.lam)


# ---------------------------------------------------------------------------
# Packing
# ---------------------------------------------------------------------------


def _pad_to_multiple(w: Array, bk: int, bn: int) -> Array:
    i, o = w.shape
    pi = (-i) % bk
    po = (-o) % bn
    if pi or po:
        w = jnp.pad(w, ((0, pi), (0, po)))
    return w


def pack_dense(w: Array, bk: int, bn: int, k: int) -> BlockSparseFactor:
    """Pack dense ``F (in, out)`` keeping the top-``k`` energy blocks per
    output block-column (pads dims up to block multiples; padded blocks have
    zero energy and are never selected unless k exceeds the live blocks)."""
    in_f, out_f = w.shape
    wp = _pad_to_multiple(w, bk, bn)
    ib, ob = wp.shape[0] // bk, wp.shape[1] // bn
    blocks = wp.reshape(ib, bk, ob, bn).transpose(2, 0, 1, 3)  # (O, I, bk, bn)
    energy = jnp.sum(blocks**2, axis=(-1, -2))  # (O, I)
    k = min(k, ib)
    _, idx = jax.lax.top_k(energy, k)  # (O, k)
    idx = jnp.sort(idx, axis=1).astype(jnp.int32)  # sorted for locality
    values = jnp.take_along_axis(blocks, idx[:, :, None, None], axis=1)
    return BlockSparseFactor(values, idx, in_f, out_f)


def random_block_factor(
    key: jax.Array,
    in_features: int,
    out_features: int,
    bk: int,
    bn: int,
    k: int,
    scale: float | None = None,
    dtype=jnp.float32,
) -> BlockSparseFactor:
    """Prescribed-support init for training FAµSTs from scratch: k distinct
    random input blocks per output block, variance-scaled values.

    The effective fan-in of each output unit is ``k·bk``, so values use
    std = scale/sqrt(k·bk) (LeCun-style on the *sparse* fan-in — the paper's
    statistical-significance argument: only s_tot parameters).
    """
    ib = -(-in_features // bk)
    ob = -(-out_features // bn)
    k = min(k, ib)
    kv, ki = jax.random.split(key)
    # distinct block ids per row via per-row permutation
    perm = jax.vmap(lambda kk: jax.random.permutation(kk, ib)[:k])(
        jax.random.split(ki, ob)
    )
    idx = jnp.sort(perm, axis=1).astype(jnp.int32)
    if scale is None:
        scale = 1.0
    std = float(scale / np.sqrt(k * bk))  # python float: keeps param dtype
    values = (jax.random.normal(kv, (ob, k, bk, bn), dtype=dtype) * std).astype(dtype)
    return BlockSparseFactor(values, idx, in_features, out_features)


# ---------------------------------------------------------------------------
# Dense weight → BlockFaust via the paper's hierarchical algorithm
# ---------------------------------------------------------------------------


def _block_factorize_spec(
    n_factors: int,
    bk: int,
    bn: int,
    k_first: int,
    k_mid: int,
    k_resid: Sequence[int] | None,
    n_iter_two: int,
    n_iter_global: int,
    mesh=None,
    data_axis: str = "data",
    model_axis: str = "model",
):
    """The :class:`repro.api.factorize.FactorizeSpec` for one block-route
    compression request (shared by the workload drivers below).  ``mesh``
    makes the factorized chains come out pre-sharded (factor arrays
    placed by out-block over ``model_axis``, ops carrying a ShardSpec
    whose apply batch shards over ``data_axis``)."""
    from repro.api.factorize import FactorizeSpec

    assert bk == bn, "the block route requires square blocks (see DESIGN.md)"
    return FactorizeSpec(
        strategy="hierarchical",
        n_factors=n_factors,
        block=bk,
        k_first=k_first,
        k_mid=k_mid,
        k_resid=tuple(k_resid) if k_resid is not None else None,
        n_iter_two=n_iter_two,
        n_iter_global=n_iter_global,
        mesh=mesh,
        data_axis=data_axis,
        model_axis=model_axis,
    )


def _compress_spec(
    a_shape: tuple[int, int],
    transpose: bool,
    n_factors: int,
    bk: int,
    bn: int,
    k_first: int,
    k_mid: int,
    k_resid: Sequence[int] | None,
    n_iter_two: int,
    n_iter_global: int,
) -> HierarchicalSpec:
    """The §V-A-style block-granular constraint schedule for one (padded,
    oriented) matrix shape — shared by the single and batched pipelines, so
    same-shaped compressions land in the same palm4msa trace bucket."""
    m, n = a_shape
    mb = m // bk  # residuals are (m, m): mb × mb blocks
    if k_resid is None:
        rho = 0.7
        k_resid = [
            max(int(round(mb * 0.5 * rho ** (ell - 1))), min(2, mb))
            for ell in range(1, n_factors)
        ]
    # per-line budget orientation on the A side that maps to per-block-col
    # of the chain side:
    kind = "blockrow" if transpose else "blockcol"
    key = "k_per_row" if transpose else "k_per_col"
    factor_projs = []
    resid_projs = []
    for ell in range(1, n_factors):
        kf = k_first if ell == 1 else k_mid
        factor_projs.append(P.make_proj(kind, bm=bk, bn=bn, **{key: kf}))
        resid_projs.append(
            P.make_proj(kind, bm=bk, bn=bn, **{key: int(k_resid[ell - 1])})
        )
    return HierarchicalSpec(
        tuple(factor_projs),
        tuple(resid_projs),
        (m,) * (n_factors - 1),
        n_iter_two=n_iter_two,
        n_iter_global=n_iter_global,
    )


def _faust_to_blockfaust(
    faust: Faust, transpose: bool, bk: int, bn: int, in_f: int, out_f: int
) -> BlockFaust:
    """Map A = S_J ... S_1 to the right-multiply packed chain on the padded W:

      transpose=True : Wp = Aᵀ = S_1ᵀ S_2ᵀ ... S_Jᵀ → F_i = S_iᵀ
      transpose=False: Wp = A = S_J ... S_1 and x@Wp = ((x@S_J)···)@S_1
                       → F_i = S_{J+1-i}
    """
    if transpose:
        dense_chain = [s.T for s in faust.factors]
    else:
        dense_chain = list(reversed(list(faust.factors)))

    packed: list[BlockSparseFactor] = []
    for f in dense_chain:
        # pack losslessly: k = max live blocks in any output block-column
        # (≤ the budget by construction of the projections above)
        k_actual = _max_blocks_per_outcol(f, bk, bn)
        packed.append(pack_dense(f, bk, bn, k_actual))
    # restore unpadded feature sizes at the chain ends
    packed[0] = dataclasses.replace(packed[0], in_features=in_f)
    packed[-1] = dataclasses.replace(packed[-1], out_features=out_f)
    return BlockFaust(tuple(packed), faust.lam)


def _max_blocks_per_outcol(f: Array, bk: int, bn: int) -> int:
    fp = _pad_to_multiple(f, bk, bn)
    ib, ob = fp.shape[0] // bk, fp.shape[1] // bn
    blocks = fp.reshape(ib, bk, ob, bn).transpose(2, 0, 1, 3)
    energy = np.asarray(jnp.sum(blocks**2, axis=(-1, -2)))  # (O, I)
    return int(max((energy > 0).sum(axis=1).max(), 1))


# ---------------------------------------------------------------------------
# Batched compression — amortize one compile across a stack of weights
# ---------------------------------------------------------------------------


def _maybe_shard_batch(stack: Array, mesh, batch_axis: str) -> Array:
    """Shard a stack's leading (batch) dim over ``batch_axis`` when the mesh
    has that axis and it divides the batch evenly; otherwise leave default
    placement (an uneven bucket — e.g. 6 layers over 8 devices — or a mesh
    without the axis must not turn into a device_put error)."""
    if (
        mesh is not None
        and batch_axis in mesh.shape
        and stack.shape[0] % mesh.shape[batch_axis] == 0
    ):
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(batch_axis)
        )
        stack = jax.device_put(stack, sharding)
    return stack


_DEFAULT_BLOCK = 128  # TPU-native block side (DESIGN.md §3)


def compress_layers(
    weights: dict[str, Array],
    n_factors: int = 2,
    bk: int = _DEFAULT_BLOCK,
    bn: int = _DEFAULT_BLOCK,
    k_first: int = 4,
    k_mid: int = 4,
    k_resid: Sequence[int] | None = None,
    n_iter_two: int = 40,
    n_iter_global: int = 40,
    mesh=None,
    batch_axis: str = "data",
    model_axis: str = "model",
) -> dict[str, BlockFaust]:
    """Compress a named collection of dense weights into per-layer
    :class:`BlockFaust` chains, batching same-shaped weights.

    A value may be a single 2-D weight or a 3-D ``(L, in, out)`` scan stack
    (the ``models.lm`` per-layer kernel layout): stacks go to the batched
    solver *as-is* — no unstack/restack copy — and expand to ``name[i]``
    entries in the result.  2-D weights are bucketed by ``(shape, dtype)``;
    each bucket of size > 1 is stacked and solved by one batched
    :func:`repro.api.factorize` call (one compile + one batched solve per
    bucket), singletons fall back to a sequential ``factorize`` — which
    still reuses traces across buckets of equal shape thanks to the
    value-hashable projection specs.

    ``mesh``: optional ``jax.sharding.Mesh``; when given, each stack is
    placed with its batch dimension sharded over ``batch_axis`` (when that
    axis exists and divides the batch), so the batched solver's matmuls run
    under the mesh — each device owns a slice of the stack, the
    layer-parallel compression mode — and the resulting chains come out
    *pre-sharded*: factor arrays placed by out-block over ``model_axis``
    (``_fit_axes`` replication fallback on non-dividing counts), ready for
    the ``fused_sharded`` serving path (EXPERIMENTS.md §Sharded apply).

    The returned dict maps each input name to a :class:`BlockFaust` ready
    for :func:`pack_chain` /
    ``repro.layers.faust_linear.blockfaust_to_params``.
    """
    from repro.api.factorize import factorize

    # batch_axis doubles as the serving ShardSpec's data axis, so a mesh
    # whose batch axis has a non-default name shards the apply batch too
    fspec = _block_factorize_spec(
        n_factors, bk, bn, k_first, k_mid, k_resid, n_iter_two, n_iter_global,
        mesh=mesh, data_axis=batch_axis, model_axis=model_axis,
    )
    out: dict[str, BlockFaust] = {}
    buckets: dict[tuple, list[str]] = {}
    for name, w in sorted(weights.items()):
        if w.ndim == 3:  # pre-stacked (L, in, out): already the batch layout
            stack = _maybe_shard_batch(w, mesh, batch_axis)
            _, info = factorize(stack, fspec)
            out.update(
                (f"{name}[{i}]", bf) for i, bf in enumerate(info.blockfausts)
            )
            continue
        assert w.ndim == 2, f"{name}: expected a 2-D or (L, in, out) weight, got {w.shape}"
        buckets.setdefault((tuple(w.shape), str(w.dtype)), []).append(name)

    for _, names in sorted(buckets.items(), key=lambda kv: kv[1][0]):
        if len(names) == 1:
            _, info = factorize(weights[names[0]], fspec)
            out[names[0]] = info.blockfausts[0]
            continue
        stack = _maybe_shard_batch(
            jnp.stack([weights[n] for n in names]), mesh, batch_axis
        )
        _, info = factorize(stack, fspec)
        out.update(zip(names, info.blockfausts))
    return out


def compress_model(
    params,
    min_dim: int | None = None,
    select: "Callable[[str], bool] | None" = None,
    **kw,
) -> dict[str, BlockFaust]:
    """Gather every eligible 2-D weight from a ``configs/``-built model's
    parameter pytree and compress them with :func:`compress_layers`.

    ``params`` is any pytree (plain dicts or the ``Annotated`` trees built
    by ``repro.models.lm.init_model``); leaves are addressed by their
    ``jax.tree_util`` key path string.  Eligible leaves are 2-D weights
    with both dims ≥ ``min_dim`` (default: the block size, so at least one
    block fits per side), plus 3-D ``(L, in, out)`` *scan-stacked* layer
    weights — the layout ``models.lm`` uses for its per-layer kernels —
    which pass straight through as ready-made batches (the result carries
    per-layer entries ``path[i]``); every transformer block's stacked
    QKV/MLP kernels land in a single batched solve, which is where the
    amortization pays off at model scale.  ``select`` further filters by
    path name (e.g. ``lambda n: "mlp" in n``).

    Returns ``{path: BlockFaust}`` ready for ``pack_chain`` + the
    ``faust_linear`` serving path.
    """
    bk = kw.get("bk", _DEFAULT_BLOCK)
    if min_dim is None:
        min_dim = bk
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    weights: dict[str, Array] = {}
    for path, leaf in leaves:
        if not hasattr(leaf, "ndim") or leaf.ndim not in (2, 3):
            continue
        if min(leaf.shape[-2:]) < min_dim:
            continue
        name = jax.tree_util.keystr(path)
        if select is not None and not select(name):
            continue
        weights[name] = leaf  # 3-D stacks stay stacked; compress_layers
        # handles both ranks
    return compress_layers(weights, **kw)
