"""Cost-model backend dispatch for :class:`repro.api.operator.FaustOp`.

``apply(x, backend="auto")`` has three concrete execution paths (dense
matmul, per-factor BSR chain, fused packed chain) whose crossover depends
on (batch, shape, dtype, device).  This module picks among them with a
roofline model priced at the builtin constants of the chip's
``device_kind`` (:func:`builtin_constants`):

    t(backend) ≈ max(flops / PEAK_FLOPS, bytes / HBM_BW) + launches·t_launch

* ``dense``:  materialize-then-multiply — ``FaustOp`` never caches
  ``todense()``, so every apply pays the chain product that builds the
  dense matrix (≈ ``2·s_tot·min(m,n)`` flops over J−1 launches, and an
  ``m·n`` store + reload) before the ``2·b·m·n`` matmul.  Callers who
  hold a pre-materialized matrix shouldn't route it through a FaustOp.
* ``bsr``:    flops ``2·b·s_tot``;     bytes ``s_tot + b·(m+n) +
  2·b·Σ d_inner`` (every factor boundary round-trips the intermediate
  activation through HBM); J launches.
* ``fused``:  flops ``2·b·s_tot``;     bytes ``s_tot + b·(m+n)``
  (intermediates stay in VMEM scratch); 1 launch.
* ``fused_sharded``: the fused chain per mesh shard
  (``kernels/chain_sharded.py``) — per-shard flops/HBM terms divide by the
  shard counts, plus a **collective** term ``ici_bytes / LINK_BW`` for the
  boundary all-gathers where the support pattern crosses block shards,
  and one launch per chain segment.  Only feasible when the operator
  carries a :class:`~repro.api.operator.ShardSpec` (see
  EXPERIMENTS.md §Sharded apply).

**Training-aware pricing** (``grad=True``): gradient applies cost three
passes, not one, and the passes have *different* rooflines per backend —
the per-factor path re-pays the boundary activation round-trips in both
backward passes while the fused path runs the ``kernels/chain_bwd.py``
dgrad (transposed chain, 1 launch) + wgrad (VMEM recompute + cotangent
walk, 1 launch, one ``s_tot`` f32 cotangent store per batch tile).  A
``grad=True`` cost query prices forward+backward jointly so
``backend="auto"`` under ``jax.grad`` (detected automatically by
``FaustOp.apply``) makes training-aware choices; the report records
``grad`` and per-backend joint estimates.

Every decision is materialized as a :class:`DispatchReport` — benchmarks
record it next to their numbers (``benchmarks/run.py --json``) and tests
assert which path ran (the report is also retrievable after the fact via
:func:`last_report`).  The model is the *TPU* roofline even off-TPU, and
no file or environment variable steers it, so the backend and the batch
tile are a pure function of (operator, batch, dtype, grad, shard).  The
one exception is degraded mode (``REPRO_DEGRADED``, opt-in; see
``FaustOp.apply``): a backend that raised is quarantined for the session
(:func:`quarantine_backend`) and later ``auto`` decisions skip it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.chain import DEFAULT_BT

# Builtin per-chip constants, keyed by ``jax.Device.device_kind``.  Peaks
# from the Google Cloud TPU documentation ("TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM); ``link_bw`` and ``t_launch_us`` are model constants that
# no chip run has measured yet.
_BUILTIN_BY_KIND = {
    "TPU v5 lite": {
        "peak_flops": 197e12,  # bf16 / chip
        "hbm_bw": 819e9,  # bytes/s / chip
        "link_bw": 50e9,  # bytes/s / link (ICI)
        "t_launch_us": 2.0,  # fixed per-launch overhead (µs)
    },
}
# Off-TPU (CPU tests, compile rehearsals) dispatch prices the chip the
# repo deploys on, so its decisions stay a pure function of the shapes.
_REFERENCE_KIND = "TPU v5 lite"
_BUILTIN = _BUILTIN_BY_KIND[_REFERENCE_KIND]


def builtin_constants() -> dict:
    """Builtin constants for the default device: its ``device_kind`` entry
    on a TPU — a TPU kind missing from the table raises rather than being
    priced as another chip — and the reference chip's off-TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return dict(_BUILTIN)
    if dev.device_kind not in _BUILTIN_BY_KIND:
        raise KeyError(
            f"no builtin roofline constants for TPU kind {dev.device_kind!r}; "
            f"known kinds: {sorted(_BUILTIN_BY_KIND)}"
        )
    return dict(_BUILTIN_BY_KIND[dev.device_kind])


# Stable preference on est_us ties: fewest-launch structured path first
# (single-device fused before sharded — a tie means the mesh buys nothing).
_ORDER = {"fused": 0, "fused_sharded": 1, "bsr": 2, "dense": 3}


def _wgrad_spill_bytes(b: int, s_tot: float, bt: int = DEFAULT_BT) -> float:
    """HBM bytes of the wgrad kernel's f32 partial-dvalues slabs: batches
    wider than one tile store (and re-read for the sum) one ``s_tot`` f32
    slab per *extra* tile — single-tile batches write dvalues exactly
    once, already counted in the weight-stream term.  ``bt`` is the batch
    tile the wgrad kernel will actually run at (caller-forced, else
    ``kernels/chain_bwd.py``'s default, fitted to VMEM) — smaller
    tiles mean more spill slabs, so the grad pricing must see the real
    one.  Shared by the single-device and per-shard grad pricings."""
    return 8.0 * s_tot * (max(-(-b // max(bt, 1)), 1) - 1)


@dataclasses.dataclass(frozen=True)
class DispatchReport:
    """One backend decision, with its evidence."""

    requested: str  # what the caller asked for ("auto" or forced)
    backend: str  # what will run
    batch: int
    shape: tuple[int, int]
    dtype: str
    device: str  # jax.default_backend() at decision time
    s_tot: int
    feasible: tuple[str, ...]
    est_us: dict  # backend -> modeled µs (feasible backends only)
    reason: str
    # mesh facts (None / 0 when the operator carries no ShardSpec)
    mesh_shape: tuple | None = None  # ((axis, size), ...) of the target mesh
    collective_bytes: int = 0  # per-shard ICI bytes of the sharded plan
    # training-aware pricing: True ⇔ est_us are joint forward+backward costs
    grad: bool = False
    # how the backend was decided: "model" (the roofline above) or
    # "demoted" (degraded mode re-priced after the model's pick raised)
    source: str = "model"
    # the chain kernels' batch tile this decision priced and the apply
    # runs: the requested tile (caller-forced, else DEFAULT_BT) halved
    # until the kernels' VMEM footprint fits
    bt: int = DEFAULT_BT
    # the weight-stream bytes the structured backends were priced at:
    # values bytes (post-quantization — 1 byte/value for int8/fp8 payloads)
    # plus scale bytes.  f32 operators: elt·s_tot.
    weight_bytes: int = 0
    # dtype of the stored block values ("int8"/"float8_e4m3fn"/... when the
    # chain is quantized; the activation dtype otherwise)
    values_dtype: str = ""
    # degraded-mode dispatch: the backend that raised at apply time and
    # was replaced by ``backend`` (source is then "demoted"; the failing
    # (signature, backend) pair is session-quarantined so later auto
    # dispatches skip it up front)
    demoted_from: str | None = None

    def as_row(self) -> dict:
        """Flat JSON-ready form for benchmark rows."""
        row = {
            "backend": self.backend,
            "requested": self.requested,
            "batch": self.batch,
            "shape": list(self.shape),
            "dtype": self.dtype,
            "device": self.device,
            "s_tot": self.s_tot,
            "est_us": {k: round(v, 3) for k, v in self.est_us.items()},
            "reason": self.reason,
            "grad": self.grad,
            "source": self.source,
            "bt": self.bt,
            "weight_bytes": self.weight_bytes,
            "values_dtype": self.values_dtype,
        }
        if self.demoted_from is not None:
            row["demoted_from"] = self.demoted_from
        if self.mesh_shape is not None:
            row["mesh_shape"] = {a: s for a, s in self.mesh_shape}
            row["collective_bytes"] = self.collective_bytes
        return row


_LAST_REPORT: DispatchReport | None = None


def last_report() -> DispatchReport | None:
    """The most recent decision (auto or forced) made in this process —
    set at trace time, so it reflects what was staged into the jaxpr."""
    return _LAST_REPORT


def _record(report: DispatchReport) -> DispatchReport:
    global _LAST_REPORT
    _LAST_REPORT = report
    return report


def choose_backend(
    *,
    batch: int,
    shape: tuple[int, int],
    dtype,
    s_tot: int,
    inner_dims: tuple[int, ...] = (),
    n_factors: int = 1,
    feasible: tuple[str, ...] = ("dense", "bsr", "fused"),
    requested: str = "auto",
    shard: dict | None = None,
    grad: bool = False,
    bt: int = DEFAULT_BT,
    values_dtype: str | None = None,
    scales_bytes: int = 0,
) -> DispatchReport:
    """Pick the cheapest feasible backend under the roofline model.

    ``values_dtype``/``scales_bytes`` describe a quantized chain payload
    (int8/fp8 stored values + per-block f32 scales): the structured
    backends' weight-stream byte term then prices at the stored itemsize
    plus the scale bytes — honestly, scales included — while the edge /
    activation terms stay in the compute dtype.  Unquantized operators
    (``values_dtype=None``) price bit-for-bit as before.

    Pure function of its arguments (device is recorded, not consulted):
    the same operator/batch always dispatches the same way, so benchmark
    rows are comparable across hosts.  ``shard`` is the
    :meth:`repro.kernels.chain_sharded.ShardPlan.summary` of the operator's
    mesh plan — when given, ``fused_sharded`` joins the priced backends
    with per-shard roofline terms plus the ICI collective term.
    ``grad=True`` prices forward+backward jointly (see module docstring);
    ``bt`` is the chain kernels' batch tile the apply will run at — it
    prices the wgrad partial-dvalues spill, so a caller-forced tile
    changes the grad estimates.  The constants are
    :func:`builtin_constants`.
    """
    consts = builtin_constants()
    peak_flops, hbm_bw = consts["peak_flops"], consts["hbm_bw"]
    link_bw, launch_us = consts["link_bw"], consts["t_launch_us"]
    m, n = shape
    b = batch
    elt = jnp.dtype(dtype).itemsize

    def roofline_us(
        flops: float, byts: float, launches: int, coll_bytes: float = 0.0
    ) -> float:
        return (
            (max(flops / peak_flops, byts / hbm_bw) + coll_bytes / link_bw)
            * 1e6
            + launches * launch_us
        )

    edge = b * (m + n)
    inner = 2 * b * sum(inner_dims)
    # weight-stream bytes of one pass over the stored values: quantized
    # payloads stream 1-byte codes + their f32 scale rows, f32 chains
    # stream elt·s_tot — the term the fused kernel is bound by at small
    # batch, and the one quantization shrinks.
    quant = values_dtype is not None
    w_elt = jnp.dtype(values_dtype).itemsize if quant else elt
    w_stream = w_elt * s_tot + scales_bytes
    # the wgrad dvalues slab is written f32 for quantized payloads (the
    # cotangent is wrt the dequantized values); elt-sized otherwise —
    # keeping the unquantized formulas bit-identical
    dv_bytes = 4.0 * s_tot if quant else elt * s_tot
    # dense = build the matrix (chain product: ~2·s_tot·min(m,n) flops over
    # J−1 launches, m·n written then re-read) + one dense matmul
    build_flops = 2.0 * s_tot * min(m, n)
    if not grad:
        est = {
            "dense": roofline_us(
                2.0 * b * m * n + build_flops,
                elt * (2 * m * n + edge),
                n_factors,
            ),
            "bsr": roofline_us(
                2.0 * b * s_tot, w_stream + elt * (edge + inner), n_factors
            ),
            "fused": roofline_us(2.0 * b * s_tot, w_stream + elt * edge, 1),
        }
    else:
        # joint fwd+bwd pricing — three passes per apply, both structured
        # paths stream weights ~4× (fwd + dgrad + wgrad recompute/walk) and
        # write the s_tot weight cotangent once; they differ in what rides
        # along:
        #   dense: fwd matmul + dgrad (dy@Wᵀ) + wgrad (xᵀ@dy) = 3·2bmn, the
        #     build chain re-paid through its own grads (~2×build), W
        #     re-read twice + dW written, every edge activation touched 3×;
        #   bsr:  XLA autodiff of the per-factor walk — every pass pays the
        #     per-boundary activation round-trips (`inner`, the term the
        #     forward fusion removed: stored acts in fwd, re-read in wgrad,
        #     cotangent round-trips in dgrad) and J launches each;
        #   fused: the chain_bwd kernels — dgrad is the transposed fwd
        #     roofline (1 launch); wgrad recomputes the chain in VMEM and
        #     walks cotangents while emitting dvalues (1 launch, ~2 extra
        #     flop passes), with *zero* activation traffic; batches wider
        #     than one tile pay the partial-dvalues spill
        #     (:func:`_wgrad_spill_bytes`).
        wgrad_spill = _wgrad_spill_bytes(b, s_tot, bt)
        est = {
            "dense": roofline_us(
                3 * 2.0 * b * m * n + 3.0 * build_flops,
                elt * (4 * m * n + 3 * edge),
                3 * n_factors,
            ),
            "bsr": roofline_us(
                3 * 2.0 * b * s_tot,
                3 * w_stream + dv_bytes + elt * (3 * edge + 3 * inner),
                3 * n_factors,
            ),
            "fused": roofline_us(
                5 * 2.0 * b * s_tot,
                3 * w_stream + dv_bytes + elt * 3 * edge + wgrad_spill,
                3,
            ),
        }
    coll_bytes = 0
    if shard is not None and "fused_sharded" in feasible:
        est["fused_sharded"], coll_bytes = _sharded_est(
            roofline_us, b, m, n, s_tot, elt, shard, inner_dims, grad, bt,
            w_elt=w_elt, scales_bytes=scales_bytes, quant=quant,
        )
    est = {k: v for k, v in est.items() if k in feasible}
    backend = min(est, key=lambda k: (est[k], _ORDER[k]))
    runner_up = min(
        (k for k in est if k != backend),
        key=lambda k: (est[k], _ORDER[k]),
        default=None,
    )
    weight_bytes = int(w_stream)
    if runner_up is None:
        reason = f"only feasible backend ({backend}); weight_bytes={weight_bytes}"
    else:
        reason = (
            f"{backend} modeled {est[backend]:.2f}us"
            f"{' fwd+bwd' if grad else ''} vs "
            f"{runner_up} {est[runner_up]:.2f}us "
            f"(batch={b}, s_tot={s_tot}, dense_nnz={m * n}, "
            f"weight_bytes={weight_bytes})"
        )
    if shard is not None and "fused_sharded" in est:
        reason += (
            f"; sharded plan: {shard['mode']}, "
            f"{shard['n_segments']} segment(s), "
            f"{coll_bytes} ICI bytes/shard"
        )
    return DispatchReport(
        requested=requested,
        backend=backend,
        batch=b,
        shape=(m, n),
        dtype=jnp.dtype(dtype).name,
        device=jax.default_backend(),
        s_tot=s_tot,
        feasible=tuple(est),
        est_us=est,
        reason=reason,
        mesh_shape=shard.get("mesh_shape") if shard is not None else None,
        collective_bytes=coll_bytes,
        grad=grad,
        bt=bt,
        weight_bytes=weight_bytes,
        values_dtype=(
            jnp.dtype(values_dtype).name if quant else jnp.dtype(dtype).name
        ),
    )


def _sharded_est(
    roofline_us, b: int, m: int, n: int, s_tot: int, elt: int, shard: dict,
    inner_dims: tuple[int, ...] = (),
    grad: bool = False,
    bt: int = DEFAULT_BT,
    w_elt: int | None = None,
    scales_bytes: int = 0,
    quant: bool = False,
) -> tuple[float, int]:
    """Model the sharded fused apply: per-shard roofline + ICI collectives.

    ``model`` mode: each of the ``n_model`` shards streams ``s_tot/n_model``
    weights and ``b_loc·(m + n/n_model)`` edge activations per apply, pays
    the per-shard all-gather receive bytes of every crossing boundary over
    ICI (:func:`repro.kernels.chain_sharded.ici_bytes` — the same
    accounting the executed plan reports), re-writes/re-reads the gathered
    activation around each boundary, and launches once per chain segment.
    ``replicated`` mode is pure DP: full weight traffic per shard, batch
    divided over every fitting axis, no collectives — and when the chain
    is *not* fusable (``shard["fusable"]`` False) the fallback really runs
    one launch per factor with the per-factor activation round-trips, so
    it is priced like ``bsr``, not like the fused kernel.

    ``grad=True`` scales to the joint fwd+bwd cost with the same
    three-pass structure as the single-device ``fused`` pricing (dgrad
    transposed + wgrad recompute/walk per shard, 3× the segment
    launches); the boundary collectives run in both directions — the
    transpose of the forward ``all_gather`` is a ``reduce_scatter`` of
    the boundary cotangent in dgrad *and* in wgrad's walk, so the ICI
    term triples.
    """
    from repro.kernels.chain_sharded import ici_bytes

    n_model = max(int(shard.get("n_model", 1)), 1)
    n_data = max(int(shard.get("n_data", 1)), 1)
    launches = int(shard.get("n_segments", 1))
    if w_elt is None:
        w_elt = elt
    if shard.get("mode") == "model":
        b_loc = -(-b // n_data)
        s_loc = s_tot / n_model
        cross = tuple(shard.get("crossing_feats", ()))
        coll_bytes = ici_bytes(b, elt, n_data, n_model, cross)
        boundary_hbm = elt * b_loc * sum(w * (1 + 1 / n_model) for w in cross)
        flops = 2.0 * b_loc * s_loc
        # per-shard weight stream: quantized shards move 1-byte codes + their
        # slice of the scale rows — the same n_model-fold split either way
        w_loc = w_elt * s_loc + scales_bytes / n_model
        byts = w_loc + elt * b_loc * (m + n / n_model) + boundary_hbm
    else:
        b_loc = -(-b // (n_data * n_model))
        s_loc = float(s_tot)
        coll_bytes = 0
        flops = 2.0 * b_loc * s_tot
        w_loc = w_elt * s_loc + scales_bytes
        byts = w_loc + elt * b_loc * (m + n)
        if not shard.get("fusable", True):
            # per-factor reference fallback: every boundary activation
            # round-trips through HBM, one launch per factor
            byts += elt * 2 * b_loc * sum(inner_dims)
    if grad:
        dv_loc = 4.0 * s_loc if quant else w_loc
        if shard.get("mode") != "model" and not shard.get("fusable", True):
            # the non-fusable fallback differentiates through the
            # per-factor XLA walk, not the chain_bwd kernels — price its
            # backward like bsr (3 passes re-paying the fwd traffic, a
            # dvalues write, no fused recompute or spill)
            flops = 3.0 * flops
            byts = 3.0 * byts + (4.0 * s_tot if quant else elt * s_tot)
        else:
            flops = 5.0 * flops  # fwd + dgrad + wgrad's recompute/walk/emit
            # 3 weight streams (fwd+dgrad+wgrad) + the f32 dvalues slab +
            # 4 passes over the activation/boundary traffic + tile spill —
            # collapses to the historical 4·byts for unquantized chains
            byts = (
                3.0 * w_loc
                + dv_loc
                + 4.0 * (byts - w_loc)
                + _wgrad_spill_bytes(b_loc, s_loc, bt)
            )
        launches = 3 * launches
        coll_est = 3 * coll_bytes
    else:
        coll_est = coll_bytes
    return roofline_us(flops, byts, launches, coll_est), coll_bytes


# (operator signature, backend) pairs that raised at apply time this
# session (degraded mode).  Process-local and never persisted: a launch
# failure is a property of this host and session (driver state, VMEM
# pressure, a broken lowering), not of the operator — the next process
# tries the full ladder again.
_QUARANTINE: set[tuple[str, str]] = set()


def _signature(op) -> str:
    """What a quarantine is keyed by: shape, chain length and stored
    nonzeros, shared by every batch, dtype and mesh of one operator."""
    return f"{op.shape[0]}x{op.shape[1]}|J{op.n_factors}|s{op.s_tot}|"


def quarantine_backend(op, backend: str) -> None:
    """Bar ``backend`` from auto dispatch for every operator sharing
    ``op``'s signature for this process."""
    _QUARANTINE.add((_signature(op), backend))


def quarantined_backends(op) -> frozenset[str]:
    """Backends quarantined for ``op``'s signature this session."""
    sig = _signature(op)
    return frozenset(b for s, b in _QUARANTINE if s == sig)


def clear_quarantine() -> None:
    """Reset the session quarantine (tests)."""
    _QUARANTINE.clear()


def dispatch(
    op, batch: int, dtype, requested: str = "auto", shard: dict | None = None,
    grad: bool = False, bt: int | None = None, record: bool = True,
    feasible: tuple[str, ...] | None = None,
) -> DispatchReport:
    """Decide (or record) the backend for one *leaf* operator.

    ``requested="auto"`` runs the cost model; a concrete backend name is
    a caller override — the report still carries the model's estimates
    (and what it *would* have picked, in ``reason``) but ``backend`` is
    the forced one.  ``shard`` is the operator's
    :meth:`~repro.kernels.chain_sharded.ShardPlan.summary` when it carries
    a ShardSpec; ``grad=True`` prices forward+backward jointly (set by
    ``FaustOp.apply`` when it detects an AD trace).  ``bt`` is the
    caller-forced chain batch tile, or None for ``DEFAULT_BT``; either is
    fitted to the kernels' VMEM budget (:func:`fit_chain_bt`), and the
    resolved tile comes back on ``DispatchReport.bt`` —
    ``FaustOp.apply`` runs the chain kernels at it.  Composite operators
    dispatch per leaf during ``apply``; :func:`last_report` returns the
    latest decision either way.

    ``record=False`` makes the call a pure *query*: the report is
    computed identically but :func:`last_report` is left untouched, so an
    advisory consult (e.g. the serving engine pricing the live decode
    batch each step) can't be mistaken for a decision an ``apply``
    actually staged.

    ``feasible`` overrides the candidate set (a subset of the operator's
    feasible backends) — the degraded-mode re-dispatch in
    ``FaustOp.apply`` uses it to re-price after a backend raised.  Auto
    requests additionally skip backends session-quarantined for this
    operator's signature (:func:`quarantine_backend`), unless that would
    leave nothing.
    """
    cand = op.feasible_backends() if feasible is None else tuple(feasible)
    if requested == "auto" and _QUARANTINE:
        barred = quarantined_backends(op)
        kept = tuple(b for b in cand if b not in barred)
        if kept:
            cand = kept
    values_dtype, scales_bytes = op.quant_info()
    eff_bt, unfit = fit_chain_bt(
        op, DEFAULT_BT if bt is None else bt, dtype, values_dtype, grad
    )
    if "fused" not in cand:
        unfit = None
    if unfit is not None and requested != "fused":
        cand = tuple(b for b in cand if b != "fused")
    report = choose_backend(
        batch=batch,
        shape=op.shape,
        dtype=dtype,
        s_tot=op.s_tot,
        inner_dims=op.inner_dims(),
        n_factors=op.n_factors,
        feasible=cand,
        requested=requested,
        shard=shard,
        grad=grad,
        bt=eff_bt,
        values_dtype=values_dtype,
        scales_bytes=scales_bytes,
    )
    if unfit is not None:
        report = dataclasses.replace(
            report, reason=f"{report.reason}; fused ruled out: {unfit}"
        )
    if requested != "auto":
        report = dataclasses.replace(
            report,
            backend=requested,
            reason=f"forced by caller (cost model would pick "
                   f"{report.backend}: {report.reason})",
        )
    return _record(report) if record else report


def fit_chain_bt(
    op, bt: int, dtype, values_dtype=None, grad: bool = False
) -> tuple[int, str | None]:
    """The chain kernels' batch tile for ``op`` and, when none fits, why.

    Returns the largest power-of-two divisor of ``bt`` whose forward VMEM
    footprint (and, with ``grad``, the backward's) fits the kernels'
    budgets — the one tile every fused launch of the apply runs at — or
    ``(bt, reason)`` when no tile does, computed from shapes so an unfit
    chain is priced without ``fused`` instead of failing at compile time.
    Operators with no chain plan keep ``bt``."""
    from repro.kernels import chain as kchain
    from repro.kernels import chain_bwd as kbwd

    plan = op.chain_plan()
    if plan is None:
        return bt, None
    elt = jnp.dtype(dtype).itemsize
    v_elt = jnp.dtype(values_dtype).itemsize if values_dtype else elt
    quant = values_dtype is not None
    fitted = kchain.fit_bt(
        lambda t: kchain.fwd_vmem_bytes(plan, t, elt, v_elt, quant), bt
    )
    if fitted is not None and grad:
        # wgrad's footprint contains dgrad's: one fit covers both kernels
        fitted = kbwd.fit_bt(plan, fitted, elt, wgrad=True, quant=quant)
    if fitted is not None:
        return fitted, None
    why = kchain.fwd_infeasible(plan, elt, v_elt, quant)
    if why is None and grad:
        why = kbwd.bwd_infeasible(plan, elt, quant)
    return bt, why or f"no power-of-two tile dividing bt={bt} fits VMEM"
