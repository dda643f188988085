"""Measured-timings autotuner on top of the analytic roofline dispatch.

The cost model in :mod:`repro.api.dispatch` is a closed-form roofline —
host-independent and cheap, but it *mispriced* real shapes (ROADMAP:
``apply_2048x8192_J3`` picked fused at 0.8× measured speedup; interpret-
mode ``est_us`` was off by 20–30× from measured ``us_per_call``).  This
module stops trusting the model where real timings exist (or can cheaply
be gathered): on first encounter of a dispatch key —

    (shape, n_factors, s_tot, batch bucket, dtype, grad, mesh shape, device)

— with measurement enabled (``REPRO_AUTOTUNE=1`` or
``FaustOp.apply(..., autotune=True)``), it times every feasible backend
of the operator (and sweeps the fused chain kernels' batch-tile size —
``kernels/chain.py`` / ``kernels/chain_bwd.py`` both take ``bt=``),
persists the winners to a versioned JSON table next to the roofline
cache, and the dispatch layer thereafter prefers table hits over the
model: ``DispatchReport.source`` flips to ``"measured"`` and the measured
µs land in ``est_us`` so ``benchmarks/run.py --json`` rows show which
decisions were tuned.

Modes (``REPRO_AUTOTUNE``):

* ``off`` / ``0``      — the table is never consulted; dispatch is the
  pure analytic model, bit-for-bit what it was before this module
  existed.  CI pins this on the tier-1 and bench legs so decisions stay
  host-independent.
* unset (default)      — *read-only*: existing table hits are preferred
  over the model, but nothing is ever measured.  With no table file this
  is identical to ``off``.
* ``1`` / ``on``       — read-write: missing keys are measured on first
  (concrete, eager) encounter and persisted.

Table location: ``~/.cache/repro/autotune.json`` (the directory of the
roofline calibration cache), ``REPRO_AUTOTUNE_TABLE`` overrides the
path.  The file is versioned (:data:`TABLE_VERSION`); a corrupt file or
a stale version falls back to the model — it never raises into a
dispatch.  ``scripts/calibrate_roofline.py --autotune`` pre-populates
the table over the benchmark shapes.

Batch bucketing: timings are keyed by the next power of two ≥ batch, so
a serving batch that breathes 97→128→64 hits one entry per octave
instead of re-measuring every distinct row count.  The measured µs are
therefore representative, not exact, for non-bucket batches — still far
better than a 20–30× model error.

See EXPERIMENTS.md §Autotuned dispatch for the workflow and the
measured-vs-model decisions on the benchmark shapes.
"""
from __future__ import annotations

import json
import os
import tempfile
import time

import numpy as np

TABLE_VERSION = 1

# Re-entrance guard: measurement drives FaustOp.apply with forced
# backends, and those applies must not recurse into measurement.
_MEASURING = False

# In-memory table cache, invalidated on (path, mtime) change like the
# roofline constants cache — a table written by another process (or by
# scripts/calibrate_roofline.py --autotune in this one) is picked up on
# the next dispatch without an explicit reload().
_STATE: dict = {"stamp": None, "table": None}


def autotune_mode() -> str:
    """``"off"`` | ``"readonly"`` | ``"measure"`` from ``REPRO_AUTOTUNE``."""
    v = os.environ.get("REPRO_AUTOTUNE", "").strip().lower()
    if v in ("0", "off", "false", "no"):
        return "off"
    if v in ("1", "on", "true", "yes", "measure"):
        return "measure"
    return "readonly"


def table_path() -> str:
    """Where the measured-timings table lives (sibling of roofline.json)."""
    override = os.environ.get("REPRO_AUTOTUNE_TABLE")
    if override:
        return override
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "autotune.json"
    )


def _stamp() -> tuple:
    path = table_path()
    try:
        return (path, os.stat(path).st_mtime_ns)
    except OSError:
        return (path, None)


def load_table() -> dict | None:
    """The validated table (``{"version": .., "entries": {..}}``), or None
    when the file is absent, unreadable, corrupt, or a stale version —
    every failure mode degrades to the analytic model, never raises."""
    stamp = _stamp()
    if _STATE["stamp"] == stamp:
        return _STATE["table"]
    table = None
    path = stamp[0]
    if stamp[1] is not None:
        try:
            with open(path, encoding="utf-8") as f:
                data = json.load(f)
            if (
                isinstance(data, dict)
                and data.get("version") == TABLE_VERSION
                and isinstance(data.get("entries"), dict)
            ):
                table = data
        except (OSError, ValueError):
            table = None
    _STATE["stamp"] = stamp
    _STATE["table"] = table
    return table


def reload() -> dict | None:
    """Drop the in-memory cache and re-read the table file now."""
    _STATE["stamp"] = None
    return load_table()


def bucket_batch(b: int) -> int:
    """Next power of two ≥ b (min 1) — the batch axis of the table key."""
    return 1 << max(0, int(b) - 1).bit_length() if b > 1 else 1


def key_of(
    *,
    shape: tuple[int, int],
    n_factors: int,
    s_tot: int,
    batch: int,
    dtype: str,
    grad: bool,
    mesh_shape: tuple | None,
    device: str,
    vq: str | None = None,
) -> str:
    """The dispatch-key string a timing is filed under.  Everything the
    cost model's decision depends on, batch bucketed (see module
    docstring), plus the device — measured µs are host timings.

    ``vq`` is the quantization scheme of a quantized packed leaf (e.g.
    ``"int8:per_block"``); it appends a ``|vq:...`` component so quantized
    and f32 variants of the same signature never share measured timings.
    Unquantized keys stay byte-identical to what they were before
    quantization existed — old tables keep hitting."""
    mesh = (
        "x".join(f"{a}{s}" for a, s in mesh_shape) if mesh_shape else "-"
    )
    kind = "grad" if grad else "fwd"
    base = (
        f"{shape[0]}x{shape[1]}|J{n_factors}|s{s_tot}"
        f"|b{bucket_batch(batch)}|{dtype}|{kind}|mesh:{mesh}|{device}"
    )
    return f"{base}|vq:{vq}" if vq else base


def lookup(key: str) -> dict | None:
    """The measured entry for ``key`` (``{"best", "us", "bt", ...}``), or
    None on any miss.  Respects the mode: ``off`` never hits."""
    if autotune_mode() == "off":
        return None
    table = load_table()
    if table is None:
        return None
    ent = table["entries"].get(key)
    if not isinstance(ent, dict) or not isinstance(ent.get("us"), dict):
        return None
    return ent


def record(key: str, entry: dict, path: str | None = None) -> None:
    """Merge one measured entry into the persisted table (atomic rename;
    read-modify-write so concurrent tuners lose at most their own key)."""
    table = load_table() or {"version": TABLE_VERSION, "entries": {}}
    table["entries"][key] = entry
    _write_table(table, path or table_path())


def _write_table(table: dict, path: str) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(os.path.abspath(path)), suffix=".tmp"
    )
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(table, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    finally:
        _STATE["stamp"] = None  # next load_table() re-reads the file


def key_for_op(op, *, batch: int, dtype, grad: bool, mesh_shape) -> str:
    """:func:`key_of` from an operator — the one spelling used by dispatch,
    measurement, and the hot-swap layer, so the three can never disagree
    about what identifies a timing."""
    import jax
    import jax.numpy as jnp

    return key_of(
        shape=op.shape,
        n_factors=op.n_factors,
        s_tot=op.s_tot,
        batch=batch,
        dtype=jnp.dtype(dtype).name,
        grad=grad,
        mesh_shape=mesh_shape,
        device=jax.default_backend(),
        vq=getattr(getattr(op, "rep", None), "qscheme", None),
    )


def op_key_prefix(op) -> str:
    """Key prefix shared by every (batch, dtype, grad, mesh, device) entry
    of one operator *signature* — shape, chain length, stored nonzeros.

    This is the hot-swap invariant in one string: a values-only swap keeps
    the signature, so existing measured entries stay valid and keep
    hitting; a support change that alters ``s_tot`` (different k) moves to
    a fresh prefix and re-prices from the model naturally.  The one case
    needing explicit action — support moved but ``s_tot`` happens to
    survive (sharding collective crossings may differ) — is handled by
    :func:`invalidate` from :func:`repro.streaming.swap.hot_swap`."""
    return f"{op.shape[0]}x{op.shape[1]}|J{op.n_factors}|s{op.s_tot}|"


# ---------------------------------------------------------------------------
# Session backend quarantine (degraded-mode dispatch)
# ---------------------------------------------------------------------------

# (op key prefix, backend) pairs that raised at apply time this session.
# Process-local and deliberately NOT persisted: a launch failure is a
# property of this host/session (driver state, VMEM pressure, a broken
# lowering), not of the operator signature — the next process re-tries
# the full ladder.  Checked by repro.api.dispatch.dispatch() so a
# quarantined backend stops being priced/picked for the session.
_QUARANTINE: set[tuple[str, str]] = set()


def quarantine_backend(prefix: str, backend: str) -> None:
    """Bar ``backend`` from auto dispatch for every operator sharing the
    signature ``prefix`` (:func:`op_key_prefix`) for this process."""
    _QUARANTINE.add((prefix, backend))


def quarantined_backends(prefix: str) -> frozenset[str]:
    """Backends quarantined for the signature ``prefix`` this session."""
    return frozenset(b for p, b in _QUARANTINE if p == prefix)


def clear_quarantine() -> None:
    """Reset the session quarantine (tests)."""
    _QUARANTINE.clear()


def invalidate(prefix: str, path: str | None = None) -> int:
    """Drop every measured entry whose key starts with ``prefix`` from the
    persisted table (atomic rewrite, :func:`record`'s contract).  Returns
    the number of entries removed; missing/unreadable tables drop 0."""
    table = load_table()
    if table is None:
        return 0
    victims = [k for k in table["entries"] if k.startswith(prefix)]
    if victims:
        for k in victims:
            del table["entries"][k]
        _write_table(table, path or table_path())
    return len(victims)


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------


def _timing_iters() -> tuple[int, int]:
    """(n_warmup, n_iter) — small by default (interpret-mode fused applies
    are CPU emulation and slow); ``REPRO_AUTOTUNE_ITERS=w,n`` overrides."""
    v = os.environ.get("REPRO_AUTOTUNE_ITERS", "")
    if v:
        try:
            w, n = (int(t) for t in v.split(","))
            return max(w, 0), max(n, 1)
        except ValueError:
            pass
    return 1, 3


def bt_candidates() -> tuple[int, ...]:
    """Batch-tile sweep for the fused chain kernels
    (``REPRO_AUTOTUNE_BT=64,128,256`` overrides)."""
    v = os.environ.get("REPRO_AUTOTUNE_BT", "")
    if v:
        try:
            return tuple(int(t) for t in v.split(",") if t)
        except ValueError:
            pass
    return (64, 128, 256)


def _timeit_us(fn, *args) -> float:
    """Median wall µs per call of a jitted callable."""
    import jax

    n_warmup, n_iter = _timing_iters()
    for _ in range(n_warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(n_iter):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


def measure(
    op,
    x,
    *,
    grad: bool,
    use_kernel: bool,
    interpret: bool,
) -> dict:
    """Time every feasible backend of one leaf operator on the concrete
    input ``x`` and return the table entry (not yet persisted).

    ``grad=True`` times ``jit(grad(...))`` of a scalar loss wrt both the
    input *and* the operator arrays — the fused path's wgrad kernel is
    dead code under an x-only grad, which would make its timing a lie.
    The fused backend additionally sweeps the chain kernels' batch tile
    (:func:`bt_candidates`); the winning tile is persisted and
    ``FaustOp.apply`` runs at it on table hits unless the caller forces
    ``bt=``.
    """
    import jax
    import jax.numpy as jnp

    from repro.api.dispatch import fit_chain_bt
    from repro.kernels.chain import DEFAULT_BT

    global _MEASURING
    us: dict[str, float] = {}
    bt_us: dict[str, float] = {}
    best_bt = None
    _MEASURING = True
    try:
        for backend in op.feasible_backends():
            # each candidate as dispatch would fit it, so every row times
            # a distinct tile under the label it really ran at
            tiles = (
                sorted(
                    {
                        fit_chain_bt(op, t, x.dtype, op.quant_info()[0], grad)[0]
                        for t in set(bt_candidates()) | {DEFAULT_BT}
                    }
                )
                if backend in ("fused", "fused_sharded") and use_kernel
                else (DEFAULT_BT,)
            )
            per_tile: dict[int, float] = {}
            for bt in tiles:
                if not grad:
                    fn = jax.jit(
                        lambda v, _b=backend, _t=bt: op.apply(
                            v, backend=_b, use_kernel=use_kernel, bt=_t,
                            interpret=interpret, grad=False, autotune=False,
                        )
                    )
                    args = (x,)
                else:
                    def loss(o, v, _b=backend, _t=bt):
                        return jnp.sum(
                            o.apply(
                                v, backend=_b, use_kernel=use_kernel, bt=_t,
                                interpret=interpret, grad=True,
                                autotune=False,
                            )
                        )

                    fn = jax.jit(
                        jax.grad(loss, argnums=(0, 1), allow_int=True)
                    )
                    args = (op, x)
                try:
                    per_tile[bt] = _timeit_us(fn, *args)
                except Exception:  # noqa: BLE001 — one broken path must
                    continue  # not poison the whole sweep
            if not per_tile:
                continue
            if len(tiles) > 1:
                for bt, t in per_tile.items():
                    bt_us[str(bt)] = round(t, 3)
            win_bt = min(per_tile, key=per_tile.get)
            us[backend] = per_tile[win_bt]
            if backend in ("fused", "fused_sharded") and len(tiles) > 1:
                best_bt = win_bt
    finally:
        _MEASURING = False
    if not us:
        raise RuntimeError("autotune: no backend could be measured")
    best = min(us, key=us.get)
    entry = {
        "best": best,
        "us": {k: round(v, 3) for k, v in us.items()},
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "ctx": {
            "use_kernel": bool(use_kernel),
            "interpret": bool(interpret),
            "device": jax.default_backend(),
        },
    }
    if best_bt is not None:
        entry["bt"] = int(best_bt)
        entry["bt_us"] = bt_us
    return entry


def ensure_measured(
    op,
    x,
    *,
    batch: int,
    dtype,
    grad: bool,
    mesh_shape: tuple | None,
    use_kernel: bool,
    interpret: bool,
) -> dict | None:
    """Measure-and-persist the key for this apply if it is missing.

    Returns the entry (fresh or existing), or None when measurement is
    not possible here: inside a trace (timing a tracer is meaningless),
    re-entrantly from a measurement apply, or for a non-leaf operator.
    Callers gate on the *mode* — this function only guards feasibility.
    """
    from repro.core.eager import is_eager

    if _MEASURING or op.kind != "leaf":
        return None
    if not is_eager(x):
        return None
    key = key_for_op(
        op, batch=batch, dtype=dtype, grad=grad, mesh_shape=mesh_shape
    )
    table = load_table()
    if table is not None and isinstance(table["entries"].get(key), dict):
        return table["entries"][key]
    entry = measure(
        op, x, grad=grad, use_kernel=use_kernel, interpret=interpret
    )
    record(key, entry)
    return entry
