"""Mesh-sharded fused FAµST chain apply (`shard_map` over the Pallas kernel).

The block-diagonal-plus-permutation structure of FAµST factors partitions
naturally by *output block* across a ``'model'`` mesh axis — exactly like
the butterfly stages the format generalizes — while the batch dimension
shards over ``'data'``.  This module plans and executes that layout:

* every factor's ``(O_j, K_j, blk, blk)`` value blocks are split
  contiguously by out-block over the ``n_model`` model shards, so each
  shard streams only ``s_tot / n_model`` weight bytes per apply;
* the activation between factors is sharded by the same out-block ranges.
  A factor whose gathered input blocks (``in_idx``) all fall inside its
  own shard's range needs **no** communication — the chain keeps running
  shard-locally inside one fused ``pallas_call``
  (:func:`repro.kernels.chain.chain_matmul`).  Where the support pattern
  *crosses* block shards the chain is split into segments and an
  ``all_gather`` over ``'model'`` rebuilds the full activation at exactly
  that boundary — the minimal collective for the gather-on-input layout;
* batch shards over ``'data'`` with no collectives (pure DP on that axis).

Feasibility is decided host-side by :func:`plan_shard` from static
metadata only (block counts, concrete ``in_idx`` when available).  When
the out-block counts don't divide ``n_model`` — or a ragged (non-block-
multiple) *inner* feature dim would make the per-shard step tables
diverge — the plan falls back to **replicated** weights with the batch sharded over
every fitting mesh axis, reusing the divisibility-driven replication
semantics of ``repro.distributed.sharding._fit_axes``: sharding degrades,
it never errors.

The resulting :class:`ShardPlan` also prices itself for the dispatch cost
model (``repro.api.dispatch``): per-shard flops/HBM bytes plus the ICI
bytes of each boundary all-gather — see EXPERIMENTS.md §Sharded apply.

**Backward.** The sharded apply is differentiable end to end with the
same collective structure transposed: each fused segment runs under the
``_chain_pallas`` ``custom_vjp``, so its backward is the fused dgrad +
wgrad kernel pair of ``kernels/chain_bwd.py`` *per shard* (≤ 2 launches
per segment, activations recomputed in VMEM), and JAX transposes every
boundary ``all_gather`` into a ``reduce_scatter`` of the boundary
cotangent — collectives appear at exactly the crossing boundaries in the
backward too, and only there.  Parity vs the single-device backward is
gated in ``tests/test_sharded_apply.py``.
"""
from __future__ import annotations

import dataclasses
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.compress import BlockFaust, ChainPlan, pack_chain
from repro.distributed.sharding import _fit_axes
from repro.kernels import ref as _ref

Array = jax.Array


def ici_bytes(
    batch: int,
    itemsize: int,
    n_batch_shards: int,
    n_model: int,
    crossing_feats: tuple[int, ...],
) -> int:
    """Per-shard ICI bytes of the boundary all-gathers: each delivers the
    other shards' ``(n_model-1)/n_model`` share of a ``(b_loc, w)``
    activation.  Single source of truth — consumed by both
    :meth:`ShardPlan.collective_bytes` and the dispatch cost model."""
    if n_model <= 1 or not crossing_feats:
        return 0
    b_loc = -(-batch // max(n_batch_shards, 1))
    frac = (n_model - 1) / n_model
    return int(itemsize * b_loc * sum(w * frac for w in crossing_feats))


@dataclasses.dataclass(frozen=True)
class SegmentPlan:
    """One fused launch between collectives: a contiguous run of factors
    whose intermediate supports stay shard-local."""

    factors: tuple[int, ...]  # global factor indices in this segment
    gather_in: bool  # all-gather the activation before this segment
    plan: ChainPlan  # the per-shard local chain plan (identical on every shard)


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Static execution plan for one (chain, mesh, axes) combination.

    ``mode`` is ``"model"`` (factors partitioned by out-block over the
    model axis, batch over data) or ``"replicated"`` (weights replicated,
    batch sharded over every fitting axis — the divisibility fallback).
    ``crossing_feats`` lists the padded activation widths all-gathered at
    segment boundaries (empty when the support never crosses shards).
    """

    mode: str  # "model" | "replicated"
    n_data: int
    n_model: int
    data_spec: tuple[str, ...] | str | None  # batch mesh axes actually used
    model_axis: str | None
    block: int
    segments: tuple[SegmentPlan, ...]
    crossing_feats: tuple[int, ...]
    reason: str  # why this mode was chosen (surfaces in DispatchReport)
    mesh_shape: tuple[tuple[str, int], ...]
    # replicated mode: whether the chain packs into one fused launch per
    # shard (False ⇒ the per-factor reference fallback runs, J launches)
    fusable: bool = True
    n_factors: int = 1

    @property
    def n_batch_shards(self) -> int:
        return self.n_data * (self.n_model if self.mode == "replicated" else 1)

    @property
    def n_launches(self) -> int:
        if self.mode == "model":
            return len(self.segments)
        return 1 if self.fusable else self.n_factors

    def collective_bytes(self, batch: int, itemsize: int) -> int:
        if self.mode != "model":
            return 0
        return ici_bytes(
            batch, itemsize, self.n_batch_shards, self.n_model,
            self.crossing_feats,
        )

    def summary(self) -> dict:
        """The shard facts the dispatch cost model consumes."""
        return {
            "mode": self.mode,
            "n_data": self.n_data,
            "n_model": self.n_model,
            "n_segments": self.n_launches,
            "crossing_feats": self.crossing_feats,
            "mesh_shape": self.mesh_shape,
            "fusable": self.fusable,
            "reason": self.reason,
        }


def _mesh_shape(mesh: Mesh) -> tuple[tuple[str, int], ...]:
    return tuple((str(a), int(s)) for a, s in mesh.shape.items())


def _concrete_idx(bf: BlockFaust) -> list[np.ndarray] | None:
    """Per-factor ``in_idx`` as numpy, or None under tracing (crossing
    detection then falls back to all-crossing — correct, never wrong)."""
    if any(isinstance(f.in_idx, jax.core.Tracer) for f in bf.factors):
        return None
    return [np.asarray(f.in_idx) for f in bf.factors]


def _model_blockers(bf: BlockFaust, n_model: int) -> str | None:
    """Why out-block partitioning over ``n_model`` shards is infeasible
    (None when it is).  Mirrors ``_fit_axes``: non-dividing sizes degrade
    to replication instead of erroring."""
    if n_model <= 1:
        return "model axis absent or size 1"
    blk = bf.factors[0].bk
    for j, f in enumerate(bf.factors):
        if f.bk != blk or f.bn != blk:
            return f"factor {j}: non-uniform blocks ({f.bk},{f.bn}) vs {blk}"
        if f.n_out_blocks % n_model:
            return (
                f"factor {j}: {f.n_out_blocks} out-blocks do not divide "
                f"{n_model} model shards"
            )
        if f.out_features != f.n_out_blocks * f.bn and j < len(bf.factors) - 1:
            # a ragged *inner* width must be masked on one shard only, so
            # per-shard step tables would diverge; the last factor's tail
            # is sliced off after the apply and needs no mask
            return (
                f"factor {j}: ragged inner width {f.out_features} "
                f"(per-shard step tables would diverge)"
            )
    for j, (a, b) in enumerate(zip(bf.factors[:-1], bf.factors[1:])):
        if a.out_features != b.in_features or a.n_out_blocks != b.n_in_blocks:
            return f"factor boundary {j}->{j + 1} not contiguous"
    return None


def _crossing_boundaries(bf: BlockFaust, n_model: int) -> list[bool]:
    """``crossing[j]`` ⇔ factor ``j`` (j ≥ 1) gathers an input block owned
    by a different model shard than its output block — i.e. the boundary
    before factor j needs an all-gather."""
    idx = _concrete_idx(bf)
    crossing = [False] * len(bf.factors)
    for j in range(1, len(bf.factors)):
        if idx is None:
            crossing[j] = True  # conservative under tracing
            continue
        o_loc_prev = bf.factors[j - 1].n_out_blocks // n_model
        o_loc = bf.factors[j].n_out_blocks // n_model
        out_shard = np.repeat(np.arange(n_model), o_loc)[:, None]
        in_shard = idx[j] // o_loc_prev
        crossing[j] = bool(np.any(in_shard != out_shard))
    return crossing


def _segment_plans(
    bf: BlockFaust, n_model: int, crossing: list[bool]
) -> tuple[SegmentPlan, ...]:
    """Split the chain at crossing boundaries; build each segment's local
    (per-shard) ChainPlan.  A segment's first factor reads the full
    (replicated input / freshly gathered) activation; later factors read
    the shard-local out-blocks of their predecessor."""
    blk = bf.factors[0].bk
    bounds = [0] + [j for j in range(1, len(bf.factors)) if crossing[j]]
    bounds.append(len(bf.factors))
    segments = []
    for s, js in enumerate(bounds[:-1]):
        je = bounds[s + 1]
        in_blocks, out_blocks, k_blocks, in_feats, out_feats = [], [], [], [], []
        offsets = [0]
        for pos, j in enumerate(range(js, je)):
            f = bf.factors[j]
            o_loc = f.n_out_blocks // n_model
            ib = f.n_in_blocks if pos == 0 else out_blocks[-1]
            in_blocks.append(ib)
            out_blocks.append(o_loc)
            k_blocks.append(f.k)
            in_feats.append(ib * blk)
            out_feats.append(o_loc * blk)
            offsets.append(offsets[-1] + o_loc * f.k)
        segments.append(
            SegmentPlan(
                factors=tuple(range(js, je)),
                gather_in=s > 0,
                plan=ChainPlan(
                    block=blk,
                    in_blocks=tuple(in_blocks),
                    out_blocks=tuple(out_blocks),
                    k_blocks=tuple(k_blocks),
                    offsets=tuple(offsets),
                    in_feats=tuple(in_feats),
                    out_feats=tuple(out_feats),
                ),
            )
        )
    return tuple(segments)


# plan_shard is called per apply (and per dispatch decision); planning is
# host-side numpy over the index tables, so cache per chain identity.
_PLAN_CACHE: dict[tuple, tuple] = {}
_PLAN_CACHE_MAX = 64


def plan_shard(
    bf: BlockFaust,
    mesh: Mesh,
    data_axis: str = "data",
    model_axis: str = "model",
) -> ShardPlan:
    """Plan the mesh execution of one chain (see module docstring)."""
    key = (id(bf), data_axis, model_axis)
    ent = _PLAN_CACHE.get(key)
    # guard both identities: the chain by weakref (id() reuse), the mesh by
    # value (a different mesh shape must re-plan)
    if ent is not None and ent[0]() is bf and ent[1] == mesh:
        return ent[2]
    plan = _plan_shard(bf, mesh, data_axis, model_axis)
    if _concrete_idx(bf) is not None:  # don't cache trace-conservative plans
        if len(_PLAN_CACHE) >= _PLAN_CACHE_MAX:
            _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
        _PLAN_CACHE[key] = (weakref.ref(bf), mesh, plan)
    return plan


def _pack_ok(bf: BlockFaust) -> bool:
    """Whether ``pack_chain`` accepts this chain (uniform square blocks,
    contiguous boundaries) — ragged feature dims are fine here, unlike in
    the model-sharded mode, because the replicated plan is shard-invariant."""
    blk = bf.factors[0].bk
    if any(f.bk != blk or f.bn != blk for f in bf.factors):
        return False
    return all(
        a.out_features == b.in_features and a.n_out_blocks == b.n_in_blocks
        for a, b in zip(bf.factors[:-1], bf.factors[1:])
    )


def _plan_shard(bf, mesh, data_axis, model_axis) -> ShardPlan:
    n_model = int(mesh.shape.get(model_axis, 1))
    n_data = int(mesh.shape.get(data_axis, 1))
    blocker = _model_blockers(bf, n_model)
    if blocker is None:
        crossing = _crossing_boundaries(bf, n_model)
        segments = _segment_plans(bf, n_model, crossing)
        blk = bf.factors[0].bk
        crossing_feats = tuple(
            bf.factors[j - 1].n_out_blocks * blk
            for j in range(1, len(bf.factors))
            if crossing[j]
        )
        return ShardPlan(
            mode="model",
            n_data=n_data,
            n_model=n_model,
            data_spec=data_axis if data_axis in mesh.shape else None,
            model_axis=model_axis,
            block=blk,
            segments=segments,
            crossing_feats=crossing_feats,
            reason=(
                f"out-blocks partition over {n_model} '{model_axis}' shards; "
                f"{len(crossing_feats)}/{max(len(bf.factors) - 1, 0)} "
                "boundaries cross shards"
            ),
            mesh_shape=_mesh_shape(mesh),
            fusable=True,
            n_factors=len(bf.factors),
        )
    # replicated fallback: weights whole on every shard, batch over every
    # fitting axis (the batch is padded to divisibility by the applier, so
    # _fit_axes here only filters axes absent from the mesh)
    n_shards = n_data * n_model
    data_spec = _fit_axes((data_axis, model_axis), n_shards, mesh)
    return ShardPlan(
        mode="replicated",
        n_data=n_data,
        n_model=n_model,
        data_spec=data_spec,
        model_axis=None,
        block=bf.factors[0].bk,
        segments=(),
        crossing_feats=(),
        reason=f"replicated fallback: {blocker}"
        + ("" if _pack_ok(bf) else "; non-fusable chain: per-factor fallback"),
        mesh_shape=_mesh_shape(mesh),
        fusable=_pack_ok(bf),
        n_factors=len(bf.factors),
    )


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------


def _seg_apply(y, seg_vals, seg_idx, plan, use_kernel, bt, interpret, seg_scales=None):
    """One fused segment on the local shard — Pallas kernel (whose
    ``custom_vjp`` is the fused dgrad/wgrad pair of ``chain_bwd.py``) or
    the step-exact jnp oracle off-TPU (XLA autodiff).  ``seg_scales``
    (segment-local (S_seg, blk) f32) routes to the dequantizing variants
    when the value blocks are a quantized int8/fp8 payload."""
    if use_kernel:
        from repro.kernels.ops import _chain_pallas, _chain_pallas_q

        if seg_scales is not None:
            return _chain_pallas_q(y, seg_vals, seg_scales, seg_idx, plan, bt, interpret)
        return _chain_pallas(y, seg_vals, seg_idx, plan, bt, interpret)
    if seg_scales is not None:
        return _ref.packed_chain_q_ref(y, seg_vals, seg_idx, plan, seg_scales)
    return _ref.packed_chain_ref(y, seg_vals, seg_idx, plan)


def sharded_chain_apply(
    x: Array,
    bf: BlockFaust,
    mesh: Mesh,
    data_axis: str = "data",
    model_axis: str = "model",
    *,
    plan: ShardPlan | None = None,
    use_kernel: bool = False,
    bt: int = 128,
    interpret: bool = True,
    scales: Array | None = None,
) -> Array:
    """Distributed ``y = lam · x @ F_1 @ ... @ F_J`` under ``shard_map``.

    Semantics match :func:`repro.kernels.ops.packed_chain_apply` exactly
    (arbitrary leading batch dims, feature pad/slice, lam scaling); only
    the placement differs.  ``plan`` may be precomputed via
    :func:`plan_shard` (the apply reuses it for the jit cache and so the
    dispatch report prices the same plan that runs).

    Quantized chains: pass ``bf`` with its factor values holding the
    int8/fp8 codes (``unpack_chain(chain, dequantize=False)``) and
    ``scales`` the full-chain (S, blk) f32 per-block-row scales
    (``expand_scales``).  Scales shard by out-block over the model axis
    exactly like the value blocks they scale, and each shard's segments
    dequantize in VMEM — per-shard weight traffic stays
    ``s_tot/n_model`` *bytes* + its scale rows.
    """
    if plan is None:
        plan = plan_shard(bf, mesh, data_axis, model_axis)
    blk = bf.factors[0].bk
    in_pad = bf.factors[0].n_in_blocks * blk
    batch_shape = x.shape[:-1]
    fpad = in_pad - x.shape[-1]
    if fpad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, fpad)])
    b = int(np.prod(batch_shape)) if batch_shape else 1
    x2 = x.reshape(b, in_pad)
    # pad the batch so every shard gets equal, kernel-tileable work
    b_mult = plan.n_batch_shards * (bt if use_kernel else 1)
    bpad = (-b) % b_mult
    if bpad:
        x2 = jnp.pad(x2, ((0, bpad), (0, 0)))

    fac_scales = None
    if scales is not None:
        # slice the flat (S, blk) scale rows back per factor, mirroring the
        # (factor, out-block, slot) order of the packed value stream
        fac_scales, off = [], 0
        for f in bf.factors:
            n = f.n_out_blocks * f.k
            fac_scales.append(
                scales[off : off + n].reshape(f.n_out_blocks, f.k, blk)
            )
            off += n

    if plan.mode == "model":
        y2 = _apply_model_sharded(
            x2, bf, mesh, plan, use_kernel, bt, interpret, fac_scales
        )
    else:
        y2 = _apply_replicated(
            x2, bf, mesh, plan, use_kernel, bt, interpret, scales
        )

    y = y2[:b].reshape(*batch_shape, -1)
    if y.shape[-1] != bf.out_features:
        y = y[..., : bf.out_features]
    return bf.lam.astype(y.dtype) * y


def _apply_model_sharded(x2, bf, mesh, plan, use_kernel, bt, interpret, fac_scales=None):
    segments = plan.segments
    model_axis = plan.model_axis
    n_model = plan.n_model
    n_fac = len(bf.factors)
    quant = fac_scales is not None

    def local(x_loc, *flat):
        vals, idxs = flat[:n_fac], flat[n_fac : 2 * n_fac]
        scls = flat[2 * n_fac :] if quant else None
        p = jax.lax.axis_index(model_axis)
        y = x_loc
        for seg in segments:
            if seg.gather_in:
                y = jax.lax.all_gather(y, model_axis, axis=1, tiled=True)
            seg_vals = jnp.concatenate(
                [vals[j].reshape(-1, plan.block, plan.block) for j in seg.factors]
            )
            seg_scl = (
                jnp.concatenate([scls[j].reshape(-1, plan.block) for j in seg.factors])
                if quant
                else None
            )
            parts = []
            for pos, j in enumerate(seg.factors):
                ij = idxs[j].reshape(-1).astype(jnp.int32)
                if pos > 0:
                    # shard-local input: previous factor's out-blocks live
                    # at local ids 0..O_loc, offset by this shard's range
                    ij = ij - p * seg.plan.in_blocks[pos]
                parts.append(ij)
            seg_idx = jnp.concatenate(parts)
            y = _seg_apply(
                y, seg_vals, seg_idx, seg.plan, use_kernel, bt, interpret, seg_scl
            )
        return y

    in_specs = [P(plan.data_spec, None)]
    in_specs += [P(model_axis, None, None, None)] * n_fac
    in_specs += [P(model_axis, None)] * n_fac
    operands = [f.values for f in bf.factors] + [f.in_idx for f in bf.factors]
    if quant:
        # scale rows shard by out-block exactly like the blocks they scale
        in_specs += [P(model_axis, None, None)] * n_fac
        operands += list(fac_scales)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(plan.data_spec, model_axis),
        check_vma=False,
    )
    return fn(x2, *operands)


def _apply_replicated(x2, bf, mesh, plan, use_kernel, bt, interpret, scales=None):
    chain = pack_chain(bf) if _pack_ok(bf) else None

    if chain is not None:  # fusable: one local fused launch per shard

        def local(x_loc, values, in_idx, *rest):
            return _seg_apply(
                x_loc, values, in_idx, chain.plan, use_kernel, bt, interpret,
                rest[0] if rest else None,
            )

        in_specs = [P(plan.data_spec, None), P(None, None, None), P(None)]
        operands = [chain.values, chain.in_idx]
        if scales is not None:  # replicated scale rows next to replicated codes
            in_specs.append(P(None, None))
            operands.append(scales)
        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=tuple(in_specs),
            out_specs=P(plan.data_spec, None),
            check_vma=False,
        )
        return fn(x2, *operands)

    if scales is not None:
        # non-fusable fallback with a quantized payload: dequantize the
        # factor values up front (quantized chains always originate from a
        # packable PackedChain, so this branch is defensive only)
        blk = bf.factors[0].bk
        factors, off = [], 0
        for f in bf.factors:
            n = f.n_out_blocks * f.k
            sc = scales[off : off + n].reshape(f.n_out_blocks, f.k, blk)
            factors.append(
                dataclasses.replace(
                    f, values=f.values.astype(jnp.float32) * sc[..., None]
                )
            )
            off += n
        bf = BlockFaust(tuple(factors), bf.lam)

    # non-fusable chain (ragged/non-uniform): per-factor reference chain,
    # still batch-sharded — the always-works floor
    def local_ref(x_loc, *factors_flat):
        y = x_loc
        for j in range(len(bf.factors)):
            y = _ref.bsr_matmul_ref(
                y, factors_flat[2 * j], factors_flat[2 * j + 1]
            )
            y = _ref._mask_tail(y, bf.factors[j].out_features)
            nxt = (
                bf.factors[j + 1].n_in_blocks * bf.factors[j + 1].bk
                if j + 1 < len(bf.factors)
                else y.shape[-1]
            )
            if nxt > y.shape[-1]:
                y = jnp.pad(y, ((0, 0), (0, nxt - y.shape[-1])))
            elif nxt < y.shape[-1]:
                y = y[:, :nxt]
        return y

    flat = []
    specs = [P(plan.data_spec, None)]
    for f in bf.factors:
        flat += [f.values, f.in_idx]
        specs += [P(None, None, None, None), P(None, None)]
    fn = jax.shard_map(
        local_ref,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=P(plan.data_spec, None),
        check_vma=False,
    )
    return fn(x2, *flat)


# ---------------------------------------------------------------------------
# Parameter placement (factorize --mesh--> pre-sharded operators)
# ---------------------------------------------------------------------------


def place_blockfaust(
    bf: BlockFaust,
    mesh: Mesh,
    model_axis: str = "model",
) -> BlockFaust:
    """``device_put`` a chain's arrays in the layout the sharded apply
    reads: each factor's values/in_idx sharded by out-block over
    ``model_axis`` when the block count divides (``_fit_axes`` semantics:
    replicate otherwise), lam replicated."""
    factors = []
    for f in bf.factors:
        ax = _fit_axes(model_axis, f.n_out_blocks, mesh)
        factors.append(
            dataclasses.replace(
                f,
                values=jax.device_put(
                    f.values, NamedSharding(mesh, P(ax, None, None, None))
                ),
                in_idx=jax.device_put(
                    f.in_idx, NamedSharding(mesh, P(ax, None))
                ),
            )
        )
    lam = jax.device_put(bf.lam, NamedSharding(mesh, P()))
    return BlockFaust(tuple(factors), lam)
