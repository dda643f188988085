"""FaustOp — one operator object over every FAµST representation.

The paper's point (§II–III) is that a FAµST *is* a linear operator you
apply cheaply: ``A ≈ λ·S_J···S_1``.  The repo grew three concrete
representations of that one object —

* :class:`repro.core.faust.Faust` — dense-with-zeros factors, the
  optimization-side form every solver operates on;
* :class:`repro.core.compress.BlockFaust` — packed block-sparse, the
  per-factor deployment form;
* :class:`repro.core.compress.PackedChain` — flat-packed, the fused
  single-``pallas_call`` form —

and :class:`FaustOp` wraps any of them behind one interface, plus lazy
operator algebra on top (nothing is materialized or transposed until you
``apply``/``todense``):

* ``op.apply(x)`` — the row-batch hot path: ``x (..., m) → (..., n)``
  computing ``x @ op.todense()`` (exactly what ``blockfaust_apply`` and
  the fused chain kernel compute), with ``backend="auto"`` cost-model
  dispatch (:mod:`repro.api.dispatch`); ``x @ op`` is sugar for it.
* ``op @ x`` — column/matrix semantics ``op.todense() @ x`` (the paper's
  ``A x``); ``op2 @ op1`` is lazy composition.
* ``op.T`` / ``op.H`` — lazy (conjugate-)adjoint: structural only, no
  factor is transposed until apply/materialize.
* ``block_diag([...])`` / ``vstack([...])`` / ``hstack([...])`` —
  multi-head and stacked-layer operators.
* ``op.to("faust" | "block" | "packed")`` — conversions between the three
  representations (subsuming ``pack_chain`` / ``unpack_chain`` /
  ``_faust_to_blockfaust`` at the call-site level).
* ``op.s_tot`` / ``op.rcg`` — the paper's complexity accounting
  (Definition II.1), summed over leaves.
* ``op.with_sharding(ShardSpec(mesh))`` — mesh placement metadata: batch
  shards over ``'data'``, factor out-blocks partition over ``'model'``,
  and ``apply`` gains the ``"fused_sharded"`` backend
  (``repro.kernels.chain_sharded``; ``backend="auto"`` prices it with
  collective terms — see EXPERIMENTS.md §Sharded apply).

``FaustOp`` is a frozen pytree: it jits/vmaps/grads like any parameter
structure (the static node kind/adjoint flags travel as aux data).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compress import (
    BlockFaust,
    ChainPlan,
    PackedChain,
    _faust_to_blockfaust,
    chain_plan,
    expand_scales,
    pack_chain,
    unpack_chain,
)
from repro.core.eager import cached
from repro.core.faust import Faust

Array = jax.Array

_LEAF_REPS = (Faust, BlockFaust, PackedChain)
_FORMATS = ("faust", "block", "packed")
BACKENDS = ("auto", "dense", "bsr", "fused", "fused_sharded")


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How a FaustOp lives on a device mesh.

    ``data_axis`` shards the apply batch (pure DP, no collectives);
    ``model_axis`` partitions every factor's *out-blocks* (each shard
    streams ``s_tot / n_model`` weight bytes; boundary all-gathers appear
    only where the support pattern crosses block shards — see
    ``repro.kernels.chain_sharded``).  Hashable (the mesh is), so the spec
    travels as pytree aux data / static jit state like the rest of the
    operator's structure.  Attach with :meth:`FaustOp.with_sharding`.
    """

    mesh: "jax.sharding.Mesh"
    data_axis: str = "data"
    model_axis: str = "model"


def _conj_rep(rep):
    """Conjugate every array leaf of a representation (no-op on reals and
    on the integer index arrays)."""
    return jax.tree_util.tree_map(
        lambda v: jnp.conj(v) if jnp.issubdtype(v.dtype, jnp.inexact) else v,
        rep,
    )


# Eager applies would otherwise re-pack (or re-slice) the whole chain per
# call; one cache per conversion, keyed by the source rep's identity.
_PACK_CACHE: dict[tuple, tuple] = {}
_UNPACK_CACHE: dict[tuple, tuple] = {}
_CACHE_MAX = 64


def _cached_pack(bf: BlockFaust) -> "PackedChain":
    return cached(
        _PACK_CACHE, _CACHE_MAX, bf, (), lambda: pack_chain(bf),
        bf.lam, *(f.values for f in bf.factors),
    )


def _cached_unpack(pc: PackedChain, dequantize: bool = True) -> BlockFaust:
    """Eager unpack cache (mirrors :func:`_cached_pack`): a sharded packed
    leaf would otherwise re-slice its factors — and re-key the shard-plan
    cache — on every apply.  ``dequantize=False`` keeps a quantized
    chain's int8/fp8 codes in the factor values: the sharded path
    dequantizes in-kernel against the separately-threaded scales."""
    return cached(
        _UNPACK_CACHE, _CACHE_MAX, pc, (dequantize,),
        lambda: unpack_chain(pc, dequantize=dequantize), pc.values,
    )


def _shard_view(rep) -> tuple[BlockFaust, "Array | None"]:
    """BlockFaust view of a leaf rep for the sharded path, plus the flat
    ``(S, blk)`` f32 scales to thread through ``sharded_chain_apply`` when
    the rep is a quantized :class:`PackedChain` (``None`` otherwise)."""
    if isinstance(rep, BlockFaust):
        return rep, None
    if rep.qscheme is not None:
        return (
            _cached_unpack(rep, dequantize=False),
            expand_scales(rep.scales, rep.plan.block),
        )
    return _cached_unpack(rep), None


def _under_ad(*trees) -> bool:
    """Whether any array leaf is an autodiff tracer — i.e. this apply is
    being staged under ``jax.grad``/``jax.vjp``/``jax.linearize`` and will
    be followed by a backward pass.  Drives the dispatch cost model's
    joint fwd+bwd pricing (``repro.api.dispatch`` ``grad=True``).

    Limitations: ``jax.grad(jax.jit(f))`` is *not* detected — pjit's JVP
    rule retraces the inner function with plain jaxpr tracers, so no
    JVPTracer reaches this apply.  The repo convention (trainer,
    benchmarks) is ``jit(grad(f))``, which is detected; callers on the
    other pattern should pass ``apply(..., grad=True)`` explicitly.
    Conversely a pure forward-mode ``jax.jvp`` also carries JVPTracers
    and is priced as training (whether a transpose follows is unknowable
    at trace time) — pass ``grad=False`` for jvp-only workloads."""
    # jax.grad/vjp trace with LinearizeTracer (JAX ≥ 0.7), jvp with
    # JVPTracer; only the former has no public alias
    from jax._src.interpreters import ad

    return any(
        isinstance(leaf, (ad.JVPTracer, ad.LinearizeTracer))
        for tree in trees
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def _degraded_on() -> bool:
    """Whether degraded-mode dispatch (auto-backend failure → one priced
    demotion to a reference path) is enabled.  Opt-in with
    ``REPRO_DEGRADED=1``: unset, a failing auto-chosen backend raises, so
    a broken kernel can never be replaced by a slower path unnoticed."""
    v = os.environ.get("REPRO_DEGRADED", "").strip().lower()
    return v in ("1", "on", "true", "yes")


def _fusable(bf: BlockFaust) -> bool:
    """Whether ``pack_chain`` would accept this chain (uniform square
    blocks + contiguous factor boundaries) — checked without packing."""
    blk = bf.factors[0].bk
    if any(f.bk != blk or f.bn != blk for f in bf.factors):
        return False
    return all(
        a.out_features == b.in_features and a.n_out_blocks == b.n_in_blocks
        for a, b in zip(bf.factors[:-1], bf.factors[1:])
    )


def _rep_shape(rep) -> tuple[int, int]:
    """Dense shape of a representation under FaustOp semantics: the shape
    of its ``todense()``."""
    if isinstance(rep, Faust):
        return rep.shape
    if isinstance(rep, BlockFaust):
        return (rep.in_features, rep.out_features)
    if isinstance(rep, PackedChain):
        return (rep.plan.in_features, rep.plan.out_features)
    raise TypeError(type(rep))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class FaustOp:
    """A lazy linear operator over FAµST representations.

    ``kind`` is one of ``"leaf"`` (wraps ``rep``), ``"compose"``,
    ``"block_diag"``, ``"vstack"``, ``"hstack"`` (wrap ``children``).
    ``adjoint``/``conj`` live on leaves only — ``.T``/``.H`` push the
    flags down structurally, so no factor array is touched until apply
    or materialization.  ``compose`` children are stored in *application*
    order: ``apply(x)`` folds ``x @ M_c1 @ M_c2 @ …``.

    Do not call the constructor directly — use :meth:`wrap`,
    :func:`block_diag`, :func:`vstack`, :func:`hstack`, or composition
    via ``@`` (the factories validate shapes; the raw constructor is the
    pytree-unflatten fast path).
    """

    kind: str
    rep: Faust | BlockFaust | PackedChain | None
    children: tuple["FaustOp", ...]
    adjoint: bool = False
    conj: bool = False
    shard: ShardSpec | None = None

    # NumPy must defer `ndarray @ op` to our __rmatmul__ instead of letting
    # its matmul gufunc claim (and fail on) the operator operand
    __array_ufunc__ = None

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.rep, self.children), (
            self.kind, self.adjoint, self.conj, self.shard,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        rep, ch = children
        return cls(aux[0], rep, tuple(ch), aux[1], aux[2], aux[3])

    # -- constructors ------------------------------------------------------
    @classmethod
    def wrap(cls, obj) -> "FaustOp":
        """Lift any representation (or an existing op) into a FaustOp."""
        if isinstance(obj, cls):
            return obj
        if isinstance(obj, _LEAF_REPS):
            return cls("leaf", obj, ())
        raise TypeError(
            f"FaustOp.wrap expects Faust | BlockFaust | PackedChain | FaustOp, "
            f"got {type(obj).__name__}"
        )

    @classmethod
    def from_faust(cls, f: Faust) -> "FaustOp":
        return cls.wrap(f)

    @classmethod
    def from_blockfaust(cls, bf: BlockFaust) -> "FaustOp":
        return cls.wrap(bf)

    @classmethod
    def from_packed(cls, pc: PackedChain) -> "FaustOp":
        return cls.wrap(pc)

    # -- shapes ------------------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        """``todense().shape``: ``apply`` maps ``(..., shape[0]) →
        (..., shape[1])``; ``op @ x`` maps ``(shape[1], b) → (shape[0], b)``."""
        if self.kind == "leaf":
            m, n = _rep_shape(self.rep)
            return (n, m) if self.adjoint else (m, n)
        shapes = [c.shape for c in self.children]
        if self.kind == "compose":
            return (shapes[0][0], shapes[-1][1])
        if self.kind == "block_diag":
            return (sum(s[0] for s in shapes), sum(s[1] for s in shapes))
        if self.kind == "vstack":
            return (sum(s[0] for s in shapes), shapes[0][1])
        if self.kind == "hstack":
            return (shapes[0][0], sum(s[1] for s in shapes))
        raise ValueError(self.kind)

    @property
    def in_dim(self) -> int:
        """Feature dim ``apply`` consumes (= ``shape[0]``)."""
        return self.shape[0]

    @property
    def out_dim(self) -> int:
        """Feature dim ``apply`` produces (= ``shape[1]``)."""
        return self.shape[1]

    # -- complexity accounting (paper §II-B) --------------------------------
    @property
    def s_tot(self) -> int:
        """Total stored nonzeros over every leaf.

        Packed representations count stored blocks (shape-only, safe under
        jit tracing).  A ``Faust`` leaf counts actual nonzeros when the
        factors are concrete; under a trace it falls back to the dense
        element count (an upper bound — the dispatch cost model then
        simply never *over*-estimates the dense path's advantage)."""
        if self.kind == "leaf":
            if isinstance(self.rep, PackedChain):
                return int(np.prod(self.rep.values.shape))
            if isinstance(self.rep, Faust) and any(
                isinstance(s, jax.core.Tracer) for s in self.rep.factors
            ):
                return sum(int(np.prod(s.shape)) for s in self.rep.factors)
            return self.rep.s_tot
        return sum(c.s_tot for c in self.children)

    @property
    def rcg(self) -> float:
        """Relative Complexity Gain (Definition II.1): dense nnz / s_tot."""
        m, n = self.shape
        return m * n / self.s_tot

    # -- lazy algebra ------------------------------------------------------
    def _adj(self, conj: bool) -> "FaustOp":
        if self.kind == "leaf":
            return FaustOp(
                "leaf", self.rep, (), not self.adjoint, self.conj ^ conj,
                self.shard,
            )
        kids = tuple(c._adj(conj) for c in self.children)
        if self.kind == "compose":
            return FaustOp("compose", None, tuple(reversed(kids)))
        if self.kind == "vstack":
            return FaustOp("hstack", None, kids)
        if self.kind == "hstack":
            return FaustOp("vstack", None, kids)
        return FaustOp("block_diag", None, kids)

    def with_sharding(self, shard: ShardSpec | None) -> "FaustOp":
        """Attach (or clear, with ``None``) a :class:`ShardSpec`.

        Structural only — no array moves; pair with
        :func:`repro.kernels.chain_sharded.place_blockfaust` (or
        ``FactorizeSpec.mesh``) to also place the factor arrays.  Pushed
        down to every leaf so composite operators dispatch each leaf on
        the mesh."""
        if self.kind == "leaf":
            return dataclasses.replace(self, shard=shard)
        return dataclasses.replace(
            self, children=tuple(c.with_sharding(shard) for c in self.children)
        )

    @property
    def T(self) -> "FaustOp":
        """Lazy transpose (structural; no factor transposition happens)."""
        return self._adj(conj=False)

    @property
    def H(self) -> "FaustOp":
        """Lazy conjugate transpose (Hermitian adjoint)."""
        return self._adj(conj=True)

    def __matmul__(self, other):
        """``op2 @ op1`` — lazy composition; ``op @ x`` — matrix semantics
        ``todense() @ x`` for ``x`` of shape ``(n,)`` or ``(n, b)``."""
        if isinstance(other, FaustOp):
            if self.shape[1] != other.shape[0]:
                raise ValueError(
                    f"compose shape mismatch: {self.shape} @ {other.shape}"
                )
            kids = self.children if self.kind == "compose" else (self,)
            kids += other.children if other.kind == "compose" else (other,)
            return FaustOp("compose", None, kids)
        x = jnp.asarray(other)
        if x.ndim == 1:
            return self.T.apply(x)
        if x.ndim == 2:
            return self.T.apply(x.T).T
        raise ValueError(
            f"op @ x expects x of shape (n,) or (n, b); got {x.shape} "
            "(use op.apply(x) for leading-batch row semantics)"
        )

    def __rmatmul__(self, x):
        """``x @ op`` — row-batch semantics, alias of :meth:`apply`."""
        return self.apply(jnp.asarray(x))

    # -- materialization ---------------------------------------------------
    def todense(self) -> Array:
        """Materialize the dense matrix this operator represents."""
        if self.kind == "leaf":
            rep = _conj_rep(self.rep) if self.conj else self.rep
            if isinstance(rep, PackedChain):
                rep = unpack_chain(rep)
            d = rep.todense()
            return d.T if self.adjoint else d
        denses = [c.todense() for c in self.children]
        if self.kind == "compose":
            out = denses[0]
            for d in denses[1:]:
                out = out @ d
            return out
        if self.kind == "vstack":
            return jnp.concatenate(denses, axis=0)
        if self.kind == "hstack":
            return jnp.concatenate(denses, axis=1)
        return jax.scipy.linalg.block_diag(*denses)

    # -- application -------------------------------------------------------
    def apply(
        self,
        x: Array,
        backend: str = "auto",
        *,
        use_kernel: bool | None = None,
        bt: int | None = None,
        interpret: bool | None = None,
        grad: bool | None = None,
    ) -> Array:
        """``y = x @ todense()`` for ``x (..., shape[0])`` — the paper's
        O(s_tot) multiplication, on the backend of your choice:

        * ``"auto"``  — roofline cost model picks per leaf
          (:func:`repro.api.dispatch.choose_backend`; the decision is
          recorded and retrievable via
          :func:`repro.api.dispatch.last_report`);
        * ``"dense"`` — materialize and matmul, re-built every call (the
          op never caches ``todense()``; wins when RCG < 1 or the
          per-factor activation traffic dominates);
        * ``"bsr"``   — per-factor chain (one launch per factor);
        * ``"fused"`` — single-``pallas_call`` packed chain
          (``kernels/chain.py``; forward of packable chains only);
        * ``"fused_sharded"`` — the fused chain per mesh shard under
          ``shard_map`` (``kernels/chain_sharded.py``; needs a
          :class:`ShardSpec` — see :meth:`with_sharding`): factors
          partitioned by out-block over ``model_axis``, batch over
          ``data_axis``, all-gathers only at support-crossing factor
          boundaries, replicated fallback when block counts don't divide.

        ``use_kernel=None`` auto-selects Pallas on TPU and the jnp
        reference paths elsewhere (CPU-safe); ``interpret`` likewise.
        ``grad=None`` auto-detects an active autodiff trace (``jax.grad``
        through this apply) and switches the cost model to joint
        forward+backward pricing — ``jit(grad(f))`` training loops
        dispatch training-aware with no call-site change; pass
        ``True``/``False`` to override (``grad(jit(f))`` hides the AD
        trace from detection — see :func:`_under_ad` — so pass
        ``grad=True`` there).

        ``bt=None`` runs the chain kernels at their default batch tile;
        an explicit ``bt`` wins.  Either is fitted to the kernels' VMEM
        budget by dispatch (:func:`repro.api.dispatch.fit_chain_bt`).
        """
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}; got {backend!r}")
        if use_kernel is None:
            use_kernel = jax.default_backend() == "tpu"
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        if grad is None:
            grad = _under_ad(x, self)  # FaustOp is a pytree: covers all leaves
        if x.shape[-1] != self.shape[0]:
            raise ValueError(
                f"apply expects x (..., {self.shape[0]}); got {x.shape}"
            )
        return self._apply(x, backend, use_kernel, bt, interpret, grad)

    def _apply(self, x, backend, use_kernel, bt, interpret, grad=False) -> Array:
        if self.kind == "leaf":
            return self._leaf_apply(x, backend, use_kernel, bt, interpret, grad)
        if self.kind == "compose":
            y = x
            for c in self.children:
                y = c._apply(y, backend, use_kernel, bt, interpret, grad)
            return y
        ms = [c.shape[0] for c in self.children]
        if self.kind == "hstack":
            return jnp.concatenate(
                [c._apply(x, backend, use_kernel, bt, interpret, grad)
                 for c in self.children],
                axis=-1,
            )
        splits = np.cumsum(ms[:-1]).tolist()
        parts = jnp.split(x, splits, axis=-1)
        ys = [
            c._apply(p, backend, use_kernel, bt, interpret, grad)
            for c, p in zip(self.children, parts)
        ]
        if self.kind == "vstack":
            return sum(ys[1:], ys[0])
        return jnp.concatenate(ys, axis=-1)  # block_diag

    def _leaf_apply(
        self, x, backend, use_kernel, bt, interpret, grad=False
    ) -> Array:
        from repro.api import dispatch as _dispatch

        rep = _conj_rep(self.rep) if self.conj else self.rep
        if backend != "auto" and backend not in self.feasible_backends():
            raise ValueError(
                f"backend {backend!r} is not feasible for this leaf "
                f"(feasible: {self.feasible_backends()})"
            )
        # mesh plan first: the dispatch decision prices the exact plan that
        # would run (collective bytes, segment count) and records the mesh.
        # Only when the sharded path can actually be chosen — a forced
        # non-sharded backend must not pay unpack/planning per call.
        shard_plan, bf_sharded, shard_scales = None, None, None
        if (
            self.shard is not None
            and backend in ("auto", "fused_sharded")
            and "fused_sharded" in self.feasible_backends()
        ):
            from repro.kernels import chain_sharded as _cs

            bf_sharded, shard_scales = _shard_view(rep)
            shard_plan = _cs.plan_shard(
                bf_sharded, self.shard.mesh,
                self.shard.data_axis, self.shard.model_axis,
            )
        shard_summary = shard_plan.summary() if shard_plan is not None else None
        # auto and forced decisions both land on dispatch.last_report()
        requested = backend
        report = _dispatch.dispatch(
            self, batch_of(x), x.dtype, requested=backend,
            shard=shard_summary, grad=grad, bt=bt,
        )
        try:
            return self._run_backend(
                x, rep, report.backend, use_kernel, report.bt, interpret,
                shard_plan, bf_sharded, shard_scales,
            )
        except Exception as exc:  # noqa: BLE001 — degraded-mode boundary
            # Degraded-mode dispatch (opt-in, ``REPRO_DEGRADED=1``): an
            # auto-chosen backend that raises demotes ONCE down the priced
            # ladder to a reference path (bsr/dense), quarantining the
            # failing (signature, backend) for the session so later auto
            # dispatches skip it up front.  Off by default: a kernel that
            # fails must fail the apply, not hand it silently to a slower
            # path.  Forced backends always re-raise.  Only trace/eager-visible
            # failures are catchable — a runtime abort inside a compiled
            # step is jax's to surface.
            ladder = tuple(
                b for b in report.feasible
                if _dispatch._ORDER.get(b, 9) > _dispatch._ORDER.get(report.backend, -1)
                and not b.startswith("fused")
            )
            if requested != "auto" or not _degraded_on() or not ladder:
                raise
            _dispatch.quarantine_backend(self, report.backend)
            demoted = _dispatch.dispatch(
                self, batch_of(x), x.dtype, requested="auto",
                shard=shard_summary, grad=grad, bt=None, record=False,
                feasible=ladder,
            )
            demoted = dataclasses.replace(
                demoted,
                source="demoted",
                demoted_from=report.backend,
                reason=(
                    f"{report.backend} raised {type(exc).__name__}: {exc}; "
                    f"demoted to {demoted.backend} ({demoted.reason})"
                ),
            )
            _dispatch._record(demoted)
            return self._run_backend(
                x, rep, demoted.backend, use_kernel, demoted.bt, interpret,
                shard_plan, bf_sharded, shard_scales,
            )

    def _run_backend(
        self, x, rep, backend, use_kernel, bt, interpret,
        shard_plan=None, bf_sharded=None, shard_scales=None,
    ) -> Array:
        """Execute one already-decided backend (the tail of
        :meth:`_leaf_apply`, shared by the primary and demoted attempts)."""
        from repro.kernels.ops import (
            blockfaust_apply,
            blockfaust_apply_t,
            packed_chain_apply,
        )

        if backend == "fused_sharded":
            from repro.kernels import chain_sharded as _cs

            return _cs.sharded_chain_apply(
                x, bf_sharded, self.shard.mesh,
                self.shard.data_axis, self.shard.model_axis,
                plan=shard_plan, use_kernel=use_kernel, bt=bt,
                interpret=interpret, scales=shard_scales,
            )
        if backend == "dense":
            return x @ self.todense()
        if isinstance(rep, Faust):  # "bsr" = the per-factor chain
            y = x
            if self.adjoint:  # x @ Aᵀ = x @ S_1ᵀ @ … @ S_Jᵀ
                for s in rep.factors:
                    y = y @ s.T
            else:  # x @ A = x @ S_J @ … @ S_1
                for s in reversed(rep.factors):
                    y = y @ s
            return rep.lam.astype(y.dtype) * y
        if isinstance(rep, PackedChain):
            if backend == "fused":
                return packed_chain_apply(
                    x, rep, use_kernel=use_kernel, bt=bt, interpret=interpret
                )
            rep = unpack_chain(rep)
        if self.adjoint:
            return blockfaust_apply_t(
                x, rep, use_kernel=use_kernel, bt=bt, interpret=interpret
            )
        if backend == "fused":
            return packed_chain_apply(
                x, _cached_pack(rep), use_kernel=use_kernel, bt=bt,
                interpret=interpret,
            )
        return blockfaust_apply(
            x, rep, use_kernel=use_kernel, bt=bt, interpret=interpret
        )

    # -- dispatch metadata (leaf-level; see repro.api.dispatch) -------------
    def feasible_backends(self) -> tuple[str, ...]:
        """Concrete backends this *leaf* can execute (adjoints have no
        fused kernel; Faust leaves have no packed layout;
        ``fused_sharded`` needs a :class:`ShardSpec` — attach one with
        :meth:`with_sharding`)."""
        assert self.kind == "leaf", "feasible_backends is leaf-level"
        if isinstance(self.rep, Faust):
            return ("dense", "bsr")
        if self.adjoint:
            return ("dense", "bsr")
        sharded = ("fused_sharded",) if self.shard is not None else ()
        if isinstance(self.rep, PackedChain) or _fusable(self.rep):
            return ("dense", "bsr", "fused") + sharded
        return ("dense", "bsr") + sharded

    def chain_plan(self) -> "ChainPlan | None":
        """The fused kernels' :class:`ChainPlan` for this leaf (shapes
        only — nothing is packed), or None when ``fused`` is not among
        :meth:`feasible_backends`."""
        if "fused" not in self.feasible_backends():
            return None
        if isinstance(self.rep, PackedChain):
            return self.rep.plan
        return chain_plan(self.rep)

    def quant_info(self) -> tuple[str | None, int]:
        """``(values_dtype, scales_bytes)`` for the dispatch byte model: the
        stored-value dtype name of a quantized packed leaf plus the byte
        count of its f32 scale sidecar, or ``(None, 0)`` for everything
        else (f32 leaves, composites — their leaves dispatch individually).
        Shape-only, so safe under jit tracing."""
        if (
            self.kind == "leaf"
            and isinstance(self.rep, PackedChain)
            and self.rep.qscheme is not None
        ):
            return (
                jnp.dtype(self.rep.values.dtype).name,
                int(self.rep.scales.size) * 4,
            )
        return None, 0

    def inner_dims(self) -> tuple[int, ...]:
        """Intermediate activation widths along the chain (the per-factor
        path round-trips ``2·batch·Σ inner_dims`` elements through HBM)."""
        assert self.kind == "leaf"
        rep = self.rep
        if isinstance(rep, Faust):
            dims = [s.shape[1] for s in rep.factors[1:]]
        elif isinstance(rep, BlockFaust):
            dims = [f.out_features for f in rep.factors[:-1]]
        else:
            dims = list(rep.plan.out_feats[:-1])
        return tuple(reversed(dims)) if self.adjoint else tuple(dims)

    def dispatch_for(
        self, batch: int, dtype=jnp.float32, *, grad: bool = False,
        bt: int | None = None,
    ):
        """Advisory dispatch query: the decision ``apply(backend="auto")``
        *would* make at a hypothetical ``batch``, without applying
        anything and without touching :func:`repro.api.dispatch.last_report`
        (``record=False``).  The serving engine calls this every decode
        step with the *live* batch size so the chosen backend (and ``bt``
        tile) follows the batch as it breathes; the same roofline model
        prices the answer.  Composites
        return the last leaf's report (leaves dispatch independently
        during a real ``apply``)."""
        if self.kind != "leaf":
            rep = None
            for c in self.children:
                rep = c.dispatch_for(batch, dtype, grad=grad, bt=bt)
            return rep
        from repro.api import dispatch as _dispatch

        shard_summary = None
        if self.shard is not None and "fused_sharded" in self.feasible_backends():
            from repro.kernels import chain_sharded as _cs

            rep = _conj_rep(self.rep) if self.conj else self.rep
            bf, _ = _shard_view(rep)
            shard_summary = _cs.plan_shard(
                bf, self.shard.mesh, self.shard.data_axis,
                self.shard.model_axis,
            ).summary()
        return _dispatch.dispatch(
            self, batch, dtype, requested="auto", shard=shard_summary,
            grad=grad, bt=bt, record=False,
        )

    @property
    def n_factors(self) -> int:
        if self.kind == "leaf":
            if isinstance(self.rep, PackedChain):
                return self.rep.plan.n_factors
            return len(self.rep.factors)
        return sum(c.n_factors for c in self.children)

    # -- conversions -------------------------------------------------------
    def _as_faust(self) -> Faust:
        """Collapse to a single optimization-side :class:`Faust` chain
        (leaves and compositions only — stacked operators have no single
        chain and raise)."""
        if self.kind == "leaf":
            rep = _conj_rep(self.rep) if self.conj else self.rep
            if isinstance(rep, PackedChain):
                rep = unpack_chain(rep)
            if isinstance(rep, BlockFaust):
                # todense = lam·F_1···F_J = lam·S_J···S_1 with S_i = F_{J+1-i}
                rep = Faust(
                    tuple(f.todense() for f in reversed(rep.factors)), rep.lam
                )
            return rep.T if self.adjoint else rep
        if self.kind == "compose":
            fausts = [c._as_faust() for c in self.children]
            # x @ M_1 @ … @ M_k: the rightmost (first-applied, paper order)
            # factor of the combined chain is M_k's first factor
            factors: list[Array] = []
            for f in reversed(fausts):
                factors.extend(f.factors)
            lam = fausts[0].lam
            for f in fausts[1:]:
                lam = lam * f.lam
            return Faust(tuple(factors), lam)
        raise ValueError(
            f"cannot collapse a {self.kind!r} operator into a single chain; "
            "convert its children individually"
        )

    def _infer_block(self) -> int | None:
        if self.kind == "leaf":
            if isinstance(self.rep, BlockFaust):
                return self.rep.factors[0].bk
            if isinstance(self.rep, PackedChain):
                return self.rep.plan.block
            return None
        for c in self.children:
            b = c._infer_block()
            if b is not None:
                return b
        return None

    def to(self, fmt: str, block: int | None = None) -> "FaustOp":
        """Convert to a chosen representation, preserving ``todense()``.

        ``fmt`` ∈ ``{"faust", "block", "packed"}``.  ``block`` — square
        block side for the packed formats (defaults to the block size of
        any block-structured leaf; required when converting a pure
        ``Faust`` chain).  Conversions re-pack losslessly (the packed
        ``k`` is the max live blocks per output block-column).
        """
        if fmt not in _FORMATS:
            raise ValueError(f"fmt must be one of {_FORMATS}; got {fmt!r}")
        if fmt == "faust":
            return FaustOp.wrap(self._as_faust())
        # fast paths: already in the target format, untouched by flags
        if self.kind == "leaf" and not self.adjoint and not self.conj:
            if fmt == "block":
                if isinstance(self.rep, BlockFaust) and (
                    block is None or block == self.rep.factors[0].bk
                ):
                    return self
                if isinstance(self.rep, PackedChain) and (
                    block is None or block == self.rep.plan.block
                ):
                    return FaustOp.wrap(unpack_chain(self.rep))
            if fmt == "packed":
                if isinstance(self.rep, PackedChain) and (
                    block is None or block == self.rep.plan.block
                ):
                    return self
                if isinstance(self.rep, BlockFaust) and _fusable(self.rep) and (
                    block is None or block == self.rep.factors[0].bk
                ):
                    return FaustOp.wrap(pack_chain(self.rep))
        blk = block if block is not None else self._infer_block()
        if blk is None:
            raise ValueError(
                "to('block'/'packed') from a dense-factor chain needs an "
                "explicit block= size"
            )
        faust = self._as_faust()
        m, n = faust.shape
        # W := todense (m, n): right-multiply chain F_i = S_{J+1-i}
        bf = _faust_to_blockfaust(faust, False, blk, blk, m, n)
        if fmt == "block":
            return FaustOp.wrap(bf)
        return FaustOp.wrap(pack_chain(bf))

    # -- diagnostics ---------------------------------------------------------
    def rel_error_fro(self, a: Array) -> Array:
        """Jit-safe relative Frobenius error vs a dense target."""
        return jnp.linalg.norm(a - self.todense()) / jnp.linalg.norm(a)

    def rel_error_spec(self, a: Array) -> Array:
        """Jit-safe relative operator-norm error (paper eq. (6))."""
        from repro.core.lipschitz import spectral_norm

        return spectral_norm(a - self.todense()) / (spectral_norm(a) + 1e-30)

    def __repr__(self) -> str:
        if self.kind == "leaf":
            tags = ("ᵀ" if self.adjoint else "") + ("*" if self.conj else "")
            return f"FaustOp<{type(self.rep).__name__}{tags} {self.shape}>"
        return (
            f"FaustOp<{self.kind}({len(self.children)}) {self.shape}>"
        )


def batch_of(x: Array) -> int:
    """Row count of a leading-batch input (static under jit)."""
    return int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1


# ---------------------------------------------------------------------------
# Structural combinators (multi-head / stacked-layer operators)
# ---------------------------------------------------------------------------


def _wrap_all(ops: Sequence) -> tuple[FaustOp, ...]:
    if not ops:
        raise ValueError("need at least one operator")
    return tuple(FaustOp.wrap(o) for o in ops)


def block_diag(ops: Sequence) -> FaustOp:
    """``diag(M_1, …, M_k)`` — independent heads side by side: ``apply``
    splits the feature axis per head and concatenates the outputs."""
    return FaustOp("block_diag", None, _wrap_all(ops))


def vstack(ops: Sequence) -> FaustOp:
    """``[M_1; …; M_k]`` (rows stacked) — all children share ``out_dim``;
    ``apply`` splits the input and sums the per-part outputs."""
    kids = _wrap_all(ops)
    outs = {c.shape[1] for c in kids}
    if len(outs) > 1:
        raise ValueError(f"vstack needs equal output dims; got {outs}")
    return FaustOp("vstack", None, kids)


def hstack(ops: Sequence) -> FaustOp:
    """``[M_1 … M_k]`` (columns stacked) — all children share ``in_dim``;
    ``apply`` feeds every child the same input and concatenates outputs."""
    kids = _wrap_all(ops)
    ins = {c.shape[0] for c in kids}
    if len(ins) > 1:
        raise ValueError(f"hstack needs equal input dims; got {ins}")
    return FaustOp("hstack", None, kids)
