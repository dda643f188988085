"""Atomic operator hot-swap for the serving runtime.

A serving process holds a FAµST unembedding chain inside jitted
prefill/decode closures (:class:`repro.runtime.engine.LMExecutor`).  The
streaming tracker (:mod:`repro.streaming.online`) periodically produces a
refreshed chain for the same projection; this module publishes it into a
live :class:`~repro.runtime.engine.Engine` / ``Server`` / executor
*between* decode steps, without breaking in-flight requests:

* **values-only swap** — the refreshed chain keeps the old support
  (identical ``in_idx``, identical shapes ⇒ identical ``ChainPlan``).
  Params are per-call arguments of the jitted closures, so the swap is a
  pure host-side pointer flip: compiled caches and the dispatch decision
  (a function of shapes alone) both stay valid.  In-flight requests
  simply see the new values from their next step on — greedy decode of
  a request admitted *after* the swap is token-exact vs a process that
  had the refreshed chain from the start (pinned by
  ``tests/test_swap.py``).
* **staged re-pack** — the support moved (``in_idx`` values or shapes
  changed).  The next prefill/decode call with the new shapes retraces
  (that *is* the staged re-pack: ``pack_chain`` runs against the new
  support at trace time) and the executor's advisory op is rebuilt, so
  dispatch prices the new shapes.

The swap itself is atomic at the scheduler's granularity: the engine is
host-driven (``Engine.step()``), so calling :func:`hot_swap` between
steps is the "between decode steps" point — no step ever sees a
half-published chain.

**Guarded swaps** (ISSUE 10).  Streaming makes swaps a routine runtime
event, and the PALM4MSA iterates behind them are non-convex — a diverged
or corrupted refresh must not reach the serving params.  ``hot_swap`` /
``quantized_swap`` therefore accept a sketched relative-error *guard*
(:func:`sketched_swap_err` — the same Gaussian-probe sketch as
``StreamingFaust.estimate_drift``, O(s_tot·probes), never dense): when
the candidate's RE vs the incumbent exceeds the threshold (or is
non-finite — NaN poisoning), the swap is **rejected before publication**
— the incumbent keeps serving, which makes rollback atomic by
construction (there is no half-swapped state to restore), the report
says why (``accepted=False``, ``rel_err``, ``reject_reason``), and
``EngineStats.swap_rejects`` counts it.  The guard is off by default
(``guard=None`` + unset ``REPRO_SWAP_GUARD``): legitimate refreshes may
be arbitrarily far from a *stale* incumbent, so the threshold is policy,
not physics — ``REPRO_SWAP_GUARD=1`` enables the default 0.5 (the same
magnitude ``StreamingConfig.full_above`` uses to call a chain rotten),
any float sets its own, and an explicit ``guard=`` always wins.
``tests/test_engine_faults.py`` pins rejected-swap byte-exactness:
engine output with a rejected regressed swap is identical to never
attempting it.
"""
from __future__ import annotations

import dataclasses
import os

import jax.numpy as jnp
import numpy as np

from repro.core.compress import (
    BlockFaust,
    PackedChain,
    pack_chain,
    quantize_chain,
)

VALUES_ONLY, REPACK = "values_only", "repack"


@dataclasses.dataclass(frozen=True)
class SwapReport:
    """What one :func:`hot_swap` / :func:`quantized_swap` publication did."""

    kind: str  # "values_only" | "repack"
    s_tot_before: int
    s_tot_after: int
    retrace: bool  # will the next engine step retrace its closures?
    # Quantized swaps only (defaults preserve the f32 report contract):
    requantized: bool = False  # new values re-quantized to the old layout
    # A values-only f32 swap is token-exact for post-swap requests by
    # construction.  A *quantized* values-only swap is classified
    # token-exact only when requantization reproduced the serving chain's
    # scales bit-for-bit — changed scales mean changed rounding points, so
    # equality with a from-scratch process is no longer structural.
    token_exact: bool = True
    # Guard outcome: accepted=False means the candidate failed the
    # sketched acceptance check and was NEVER published — the incumbent
    # keeps serving (atomic rollback by construction).  rel_err is the
    # sketched RE vs the incumbent whenever the guard ran (accepted or
    # not); None when the guard was off.
    accepted: bool = True
    rel_err: float | None = None
    reject_reason: str | None = None


def classify_swap(old: BlockFaust, new: BlockFaust) -> str:
    """``"values_only"`` when the refreshed chain keeps the old support
    (same shapes, same ``in_idx`` contents — same ``ChainPlan``), else
    ``"repack"``.  Raises when the chains are not interchangeable behind
    one serving config (feature dims / chain length fixed by the model's
    static ``FaustSpec``)."""
    if len(old.factors) != len(new.factors):
        raise ValueError(
            f"hot-swap cannot change chain length ({len(old.factors)} → "
            f"{len(new.factors)}): the serving FaustSpec is static config"
        )
    if (old.in_features, old.out_features) != (
        new.in_features, new.out_features
    ):
        raise ValueError(
            "hot-swap cannot change operator shape: "
            f"{(old.in_features, old.out_features)} → "
            f"{(new.in_features, new.out_features)}"
        )
    for fo, fn in zip(old.factors, new.factors):
        if (fo.in_features, fo.out_features) != (fn.in_features, fn.out_features):
            raise ValueError(
                "hot-swap cannot change per-factor feature dims "
                f"({(fo.in_features, fo.out_features)} → "
                f"{(fn.in_features, fn.out_features)})"
            )
        if fo.in_idx.shape != fn.in_idx.shape:
            return REPACK  # different k: support (and s_tot) changed
        if fo.values.shape != fn.values.shape:
            return REPACK
        if not np.array_equal(np.asarray(fo.in_idx), np.asarray(fn.in_idx)):
            return REPACK  # same budget, moved support
    return VALUES_ONLY


def _guard_threshold(guard) -> float | None:
    """Resolve the acceptance threshold: an explicit ``guard`` number
    wins; ``None`` defers to ``REPRO_SWAP_GUARD`` (unset/``0``/``off`` →
    guard disabled, ``1``/``on`` → the default 0.5, a float → itself);
    ``False`` disables outright."""
    if guard is False:
        return None
    if guard is not None:
        return float(guard)
    v = os.environ.get("REPRO_SWAP_GUARD", "").strip().lower()
    if v in ("", "0", "off", "false", "no"):
        return None
    if v in ("1", "on", "true", "yes"):
        return 0.5
    return float(v)


def _probe_op(chain):
    """A FaustOp over either deployment representation, for probe applies
    on the robust reference path (quantized chains dequantize)."""
    from repro.api.operator import FaustOp

    if isinstance(chain, BlockFaust):
        return FaustOp.from_blockfaust(chain)
    return FaustOp.from_packed(chain)


def sketched_swap_err(
    old, new, *, n_probes: int = 8, seed: int = 0
) -> float:
    """Sketched relative error of a candidate chain vs the incumbent:
    ``‖X·new − X·old‖_F / ‖X·old‖_F`` over ``n_probes`` Gaussian probe
    rows — O(s_tot · probes) per chain, never materializing either dense
    matrix (the :meth:`~repro.streaming.online.StreamingFaust
    .estimate_drift` sketch, pointed at two chains instead of a chain and
    a target).  Deterministic in ``seed``.  NaN/Inf anywhere in the
    candidate's probe image yields a non-finite RE — the guard treats
    that as an automatic reject."""
    import jax

    op_old, op_new = _probe_op(old), _probe_op(new)
    x = jax.random.normal(
        jax.random.PRNGKey(seed), (n_probes, op_old.shape[0]), jnp.float32
    )
    y_old = op_old.apply(x, backend="bsr")
    y_new = op_new.apply(x, backend="bsr")
    denom = jnp.maximum(jnp.linalg.norm(y_old), 1e-12)
    return float(jnp.linalg.norm(y_new - y_old) / denom)


def _guard_check(old, candidate, guard, n_probes, seed):
    """(rel_err, reject_reason) — reason is None when the swap may
    publish.  ``guard`` is the resolved threshold (None ⇒ guard off)."""
    if guard is None:
        return None, None
    rel_err = sketched_swap_err(old, candidate, n_probes=n_probes, seed=seed)
    if not np.isfinite(rel_err):
        return rel_err, "non-finite candidate (NaN/Inf in probe image)"
    if rel_err > guard:
        return rel_err, (
            f"sketched RE {rel_err:.4g} vs incumbent exceeds guard "
            f"threshold {guard:.4g}"
        )
    return rel_err, None


def _count_reject(target) -> None:
    stats = getattr(target, "stats", None)
    if stats is not None and hasattr(stats, "swap_rejects"):
        stats.swap_rejects += 1


def _executor_of(target):
    """Accept an Engine, a Server, or a bare executor."""
    ex = getattr(target, "executor", None)  # Engine
    if ex is not None:
        return ex
    if hasattr(target, "swap_unembed"):  # LMExecutor / Server
        return target
    raise TypeError(f"cannot hot-swap into {type(target).__name__}")


def hot_swap(
    target,
    new: BlockFaust,
    *,
    guard: float | bool | None = None,
    n_probes: int = 8,
    seed: int = 0,
) -> SwapReport:
    """Publish ``new`` as the serving unembedding chain of ``target``
    (an :class:`~repro.runtime.engine.Engine`,
    :class:`~repro.runtime.server.Server`, or
    :class:`~repro.runtime.engine.LMExecutor`).

    Call between engine steps / ``generate()`` calls.  Returns a
    :class:`SwapReport`; bumps ``EngineStats.swaps`` when the target is an
    engine.

    ``guard`` arms the sketched acceptance check (module docstring): a
    candidate whose probe RE vs the incumbent exceeds the threshold — or
    is non-finite — is rejected *before* publication: the incumbent keeps
    serving untouched, ``EngineStats.swap_rejects`` is bumped, and the
    report carries ``accepted=False`` + the reason.  ``None`` defers to
    ``REPRO_SWAP_GUARD`` (off by default), ``False`` disables."""
    ex = _executor_of(target)
    old = ex.unembed_blockfaust()
    if old is None:
        raise ValueError("target serves no FAµST unembedding chain")
    kind = classify_swap(old, new)
    rel_err, reject = _guard_check(
        old, new, _guard_threshold(guard), n_probes, seed
    )
    if reject is not None:
        _count_reject(target)
        return SwapReport(
            kind=kind,
            s_tot_before=int(old.s_tot),
            s_tot_after=int(new.s_tot),
            retrace=False,
            accepted=False,
            rel_err=rel_err,
            reject_reason=reject,
        )
    ex.swap_unembed(new)
    stats = getattr(target, "stats", None)  # Engine-level accounting
    if stats is not None and hasattr(stats, "swaps"):
        stats.swaps += 1
    return SwapReport(
        kind=kind,
        s_tot_before=int(old.s_tot),
        s_tot_after=int(new.s_tot),
        retrace=kind == REPACK
        and any(
            fo.values.shape != fn.values.shape
            for fo, fn in zip(old.factors, new.factors)
        ),
        rel_err=rel_err,
    )


def requantize_like(old: PackedChain, new) -> PackedChain:
    """Quantize a refreshed f32 chain against the serving chain's existing
    quantization layout (same values dtype, same scale scheme — the
    ``qscheme`` string).  ``new`` may be a :class:`PackedChain` or a
    :class:`BlockFaust` (packed first).  Raises when ``old`` is not
    quantized or ``new`` already is (double quantization is lossy in a way
    no swap should silently perform)."""
    if old.qscheme is None:
        raise ValueError("requantize_like: serving chain is not quantized")
    pc = pack_chain(new) if isinstance(new, BlockFaust) else new
    if pc.qscheme is not None:
        raise ValueError(
            "requantize_like: refreshed chain is already quantized; "
            "hand the f32 chain and let the swap pick the layout"
        )
    dtype, scheme = old.qscheme.split(":")
    return quantize_chain(pc, dtype, scheme)


def quantized_swap(
    old: PackedChain,
    new,
    *,
    guard: float | bool | None = None,
    n_probes: int = 8,
    seed: int = 0,
) -> tuple[PackedChain, SwapReport]:
    """Values-only-style swap for a *quantized* serving chain.

    Re-quantizes the refreshed chain ``new`` (f32 ``PackedChain`` or
    ``BlockFaust``) against ``old``'s existing layout and classifies the
    result: ``values_only`` when the support survived (same plan, same
    ``in_idx``), ``repack`` otherwise.  ``token_exact`` is True
    only when requantization reproduced the old scales bit-for-bit; a
    scale that moved means the new chain rounds to different grid points
    than the one it replaces, so post-swap decodes are equivalent to a
    fresh process but not to the pre-swap stream.  Returns the quantized
    replacement chain and the report — publishing it (engine param flip)
    is the caller's step, same as any values-only swap.

    ``guard`` arms the sketched acceptance check on the *requantized*
    candidate (post-rounding — the guard sees exactly what would serve)
    vs the quantized incumbent; a rejected candidate returns ``(old,
    report)`` with ``accepted=False`` — the incumbent chain is handed
    back, so publishing the returned chain is always safe."""
    new_q = requantize_like(old, new)
    rel_err, reject = _guard_check(
        old, new_q, _guard_threshold(guard), n_probes, seed
    )
    if reject is not None:
        return old, SwapReport(
            kind=VALUES_ONLY,
            s_tot_before=int(np.prod(old.values.shape)),
            s_tot_after=int(np.prod(old.values.shape)),
            retrace=False,
            requantized=True,
            token_exact=True,  # nothing published: the stream is untouched
            accepted=False,
            rel_err=rel_err,
            reject_reason=reject,
        )
    if old.plan == new_q.plan and np.array_equal(
        np.asarray(old.in_idx), np.asarray(new_q.in_idx)
    ):
        kind = VALUES_ONLY
    else:
        kind = REPACK
    token_exact = kind == VALUES_ONLY and np.array_equal(
        np.asarray(old.scales), np.asarray(new_q.scales)
    )
    return new_q, SwapReport(
        kind=kind,
        s_tot_before=int(np.prod(old.values.shape)),
        s_tot_after=int(np.prod(new_q.values.shape)),
        retrace=kind == REPACK,
        requantized=True,
        token_exact=token_exact,
        rel_err=rel_err,
    )


def refreshed_chain(streaming, like: BlockFaust) -> BlockFaust:
    """Adapt a :class:`~repro.streaming.online.StreamingFaust`'s published
    chain to a serving chain's λ dtype/shape (the tracker optimizes in
    f32; serving params may run bf16 values with f32 λ).  Raises when the
    tracker's op is not a deployment ``BlockFaust`` (use a block-route
    ``FactorizeSpec`` for serving-bound trackers)."""
    bf = streaming.blockfaust
    if bf is None:
        raise ValueError(
            "StreamingFaust op is not a deployment BlockFaust; track with "
            "a block-route FactorizeSpec to feed a serving swap"
        )
    factors = tuple(
        dataclasses.replace(
            f, values=f.values.astype(lf.values.dtype)
        )
        for f, lf in zip(bf.factors, like.factors)
    )
    return BlockFaust(factors, jnp.asarray(bf.lam, like.lam.dtype))
