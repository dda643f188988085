"""Paper §II-B2 RCG flop model: measured apply time + roofline transfer.

Measures dense vs FAµST matmuls through the unified operator API
(``repro.api.FaustOp``) and reports the flop model (2·s_tot vs 2·m·n)
plus the TPU roofline estimate.  Reports **both** chain paths:

* ``bsr``   — one launch per factor (``FaustOp.apply(backend="bsr")``),
  which on hardware pays a 2·batch·d_j HBM round-trip of the
  intermediate activations at every factor boundary;
* ``fused`` — the single-``pallas_call`` chain kernel
  (``backend="fused"``, ``kernels/chain.py``) whose intermediates stay
  in VMEM scratch, so the memory-roofline term drops from
  ``s_tot + 2·batch·Σ_j d_j`` to ``s_tot + batch·(d_in + d_out)``.

``backend="auto"`` runs the cost-model dispatch
(``repro.api.dispatch``); the resulting :class:`DispatchReport` is
recorded on the benchmark row (``run.py --json``) and this benchmark
asserts the auto path reproduces the forced paths to ≤ 1e-6 relative
error — the acceptance gate for the dispatch layer.

Also verifies the launch-count claim structurally: the fused path stages
exactly **one** pallas_call into the jaxpr vs J on the per-factor path.
On CPU the Pallas paths run in interpret mode (emulation — the measured
times are for smoke value only; the roofline columns carry the TPU story).

``run_grad`` (``--grad`` / the runner's ``apply_grad``) benchmarks the
**training path**: ``jax.grad`` of a scalar loss through the dense /
per-factor / fused (old rematerializing backward vs the fused
``kernels/chain_bwd.py`` dgrad+wgrad pair) / mesh-sharded backends, with
fwd-only vs fwd+bwd ratios, backward launch counts, a dx/dvalues parity
gate vs the reference walk, and the grad-priced DispatchReport on the
JSON row (EXPERIMENTS.md §Training-path perf).
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit_us
from repro.api import FaustOp, last_report
from repro.core.compress import (
    BlockFaust,
    pack_chain,
    quantize_chain,
    random_block_factor,
)

PEAK_FLOPS = 197e12
HBM_BW = 819e9

# --dtype axis: f32 is the full benchmark; the low-precision dtypes run a
# focused fused-path comparison against the f32 fused baseline (bf16 casts
# the packed values; int8/fp8 quantize them — in-VMEM dequant, see
# EXPERIMENTS.md §Quantized chains).
DTYPES = ("f32", "bf16", "int8", "fp8_e4m3")


def _bench_dtypes() -> tuple[str, ...]:
    """Low-precision rows appended to the default f32 run —
    ``REPRO_BENCH_DTYPES`` (comma list, "" to disable) overrides."""
    v = os.environ.get("REPRO_BENCH_DTYPES")
    if v is None:
        return ("int8", "fp8_e4m3")
    return tuple(t for t in (s.strip() for s in v.split(",")) if t)


def count_pallas_calls(fn, *args) -> int:
    """Number of pallas_call primitives staged into ``fn``'s jaxpr."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return str(jaxpr).count("pallas_call")


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def run(cases=((1024, 4096, 2, 4, 128), (2048, 8192, 2, 4, 128), (2048, 8192, 3, 4, 128)),
        batch: int = 128, dtype: str = "f32") -> None:
    if dtype not in DTYPES:
        raise ValueError(f"--dtype must be one of {DTYPES}; got {dtype!r}")
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = True  # interpret-mode emulation off-TPU
    interpret = not on_tpu
    if dtype != "f32":  # focused low-precision run: fused path vs f32 fused
        for case in cases:
            bf, _ = _chain_case(*case)
            _lowprec_row(bf, case, batch, dtype, use_kernel, interpret)
        return
    for in_dim, out_dim, n_factors, blocks_k, block in cases:
        bf, dims = _chain_case(in_dim, out_dim, n_factors, blocks_k, block)
        op = FaustOp.from_blockfaust(bf)
        w = op.todense()
        x = jax.random.normal(jax.random.PRNGKey(1), (batch, in_dim))

        dense_fn = jax.jit(lambda v: v @ w)
        faust_fn = jax.jit(lambda v: op.apply(v, backend="bsr", use_kernel=False))
        perfac_fn = jax.jit(
            lambda v: op.apply(v, backend="bsr", use_kernel=use_kernel,
                               interpret=interpret)
        )
        fused_fn = jax.jit(
            lambda v: op.apply(v, backend="fused", use_kernel=use_kernel,
                               interpret=interpret)
        )
        auto_fn = jax.jit(lambda v: op.apply(v, backend="auto", use_kernel=False))
        y_auto = auto_fn(x)
        report = last_report()  # decision staged by the auto trace
        y_perfac, y_fused = perfac_fn(x), fused_fn(x)
        # acceptance gate: one operator, one answer, whatever the backend
        parity = max(_rel(y_fused, y_perfac), _rel(y_auto, y_perfac))
        if parity > 1e-6:
            raise RuntimeError(
                f"backend parity broken ({in_dim}x{out_dim} J{n_factors}): "
                f"{parity:.3e} > 1e-6"
            )
        t_dense = timeit_us(dense_fn, x)
        t_faust = timeit_us(faust_fn, x)
        t_perfac = timeit_us(perfac_fn, x)
        t_fused = timeit_us(fused_fn, x)
        n_calls_perfac = count_pallas_calls(perfac_fn, x)
        n_calls_fused = count_pallas_calls(fused_fn, x)
        assert n_calls_fused == 1, n_calls_fused
        assert n_calls_perfac == n_factors, (n_calls_perfac, n_factors)

        rcg = op.rcg
        dense_flops = 2 * in_dim * out_dim * batch
        faust_flops = 2 * op.s_tot * batch
        # TPU roofline (bf16 bytes): weights + boundary activations only for
        # the fused path, + intermediate activation round-trips per-factor
        act_inner = 2 * batch * sum(dims[1:-1])  # stored + reloaded
        act_edge = batch * (in_dim + out_dim)
        bytes_fused = 2 * (op.s_tot + act_edge)  # leading 2 = bf16 bytes/elt
        bytes_perfac = 2 * (op.s_tot + act_edge + act_inner)
        t_tpu_dense = max(dense_flops / PEAK_FLOPS, 2 * (in_dim * out_dim + act_edge) / HBM_BW)
        t_tpu_fused = max(faust_flops / PEAK_FLOPS, bytes_fused / HBM_BW)
        t_tpu_perfac = max(faust_flops / PEAK_FLOPS, bytes_perfac / HBM_BW)
        emit(
            f"apply_{in_dim}x{out_dim}_J{n_factors}",
            t_faust,
            f"dense_us={t_dense:.1f};perfactor_us={t_perfac:.1f};"
            f"fused_us={t_fused:.1f};pallas_calls={n_calls_perfac}->{n_calls_fused};"
            f"speedup={t_dense / max(t_faust, 1e-9):.2f};"
            f"RCG={rcg:.2f};flop_gain={dense_flops / faust_flops:.2f};"
            f"auto_backend={report.backend};"
            f"dispatch_source={report.source};parity={parity:.1e};"
            f"tpu_roofline_gain={t_tpu_dense / t_tpu_fused:.2f};"
            f"tpu_fuse_gain={t_tpu_perfac / t_tpu_fused:.2f};"
            f"values_dtype=float32;weight_bytes={4 * op.s_tot};"
            f"interpret={int(interpret)}",
            dispatch=report,
        )
        for qd in _bench_dtypes():
            _lowprec_row(
                bf, (in_dim, out_dim, n_factors, blocks_k, block), batch,
                qd, use_kernel, interpret, t_f32=t_fused, y_f32=y_fused,
            )


def _lowprec_row(
    bf, case, batch, dtype, use_kernel, interpret, t_f32=None, y_f32=None
):
    """One ``apply_{m}x{n}_J{J}_{dtype}`` row: the fused path at a
    low-precision values dtype vs the f32 fused baseline — measured µs
    (interpret-mode emulation off-TPU; the dispatch estimate carries the
    TPU story), post-quantization weight bytes, and the RE paid for them."""
    in_dim, out_dim, n_factors, _, _ = case
    chain = pack_chain(bf)
    if dtype == "bf16":
        lp = dataclasses.replace(chain, values=chain.values.astype(jnp.bfloat16))
    else:
        lp = quantize_chain(chain, dtype)
    op = FaustOp.from_packed(lp)
    op_f = FaustOp.from_packed(chain)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, in_dim))
    fn = jax.jit(
        lambda v: op.apply(v, backend="fused", use_kernel=use_kernel,
                           interpret=interpret)
    )
    y = fn(x)
    if y_f32 is None:
        f32_fn = jax.jit(
            lambda v: op_f.apply(v, backend="fused", use_kernel=use_kernel,
                                 interpret=interpret)
        )
        y_f32, t_f32 = f32_fn(x), timeit_us(f32_fn, x)
    re = _rel(y, y_f32)
    t = timeit_us(fn, x)
    report = op.dispatch_for(batch)  # auto decision at the quantized bytes
    wb = lp.weight_bytes  # itemsize-aware: 2·s_tot bf16, s_tot+scales int8
    emit(
        f"apply_{in_dim}x{out_dim}_J{n_factors}_{dtype}",
        t,
        f"fused_f32_us={t_f32:.1f};speedup_vs_f32={t_f32 / max(t, 1e-9):.2f};"
        f"re_vs_f32={re:.2e};values_dtype={dtype};weight_bytes={wb};"
        f"f32_weight_bytes={4 * op.s_tot};"
        f"bytes_ratio={wb / (4 * op.s_tot):.3f};"
        f"auto_backend={report.backend};est_speedup_vs_f32="
        f"{_est_gain(op_f, op, batch):.2f};"
        f"interpret={int(interpret)}",
        dispatch=report,
    )


def _est_gain(op_f32, op_lp, batch) -> float:
    """Dispatch-estimated fwd µs ratio f32/low-precision at the auto pick
    — the deterministic roofline headline the measured interpret-mode µs
    can't carry off-TPU."""
    rf = op_f32.dispatch_for(batch)
    rl = op_lp.dispatch_for(batch)
    lo = rl.est_us.get(rl.backend, 0.0)
    return rf.est_us.get(rf.backend, 0.0) / lo if lo else 0.0


def _chain_case(in_dim, out_dim, n_factors, blocks_k, block):
    keys = jax.random.split(jax.random.PRNGKey(0), n_factors)
    dims = [in_dim] + [min(in_dim, out_dim)] * (n_factors - 1) + [out_dim]
    factors = tuple(
        random_block_factor(keys[i], dims[i], dims[i + 1], block, block, blocks_k)
        for i in range(n_factors)
    )
    return BlockFaust(factors, jnp.asarray(1.0)), dims


def run_grad(
    cases=((1024, 4096, 2, 4, 128), (2048, 8192, 3, 4, 128)),
    batch: int = 128,
) -> None:
    """Time ``jax.grad`` of a scalar loss through every backend (see module
    docstring).  The old rematerializing chain backward is reachable via
    ``REPRO_CHAIN_BWD=ref`` (set only around its trace), so the fused vs
    rematerializing comparison is same-forward, backward-only."""
    from repro.kernels.ops import packed_chain_apply

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    devices = jax.devices()
    for in_dim, out_dim, n_factors, blocks_k, block in cases:
        bf, dims = _chain_case(in_dim, out_dim, n_factors, blocks_k, block)
        chain = pack_chain(bf)
        op = FaustOp.from_blockfaust(bf)
        w = op.todense()
        x = jax.random.normal(jax.random.PRNGKey(1), (batch, in_dim))
        dy_seed = jax.random.normal(jax.random.PRNGKey(2), (batch, out_dim))

        def chain_loss(values, v, use_kernel):
            pc = dataclasses.replace(chain, values=values)
            y = packed_chain_apply(
                v, pc, use_kernel=use_kernel, interpret=interpret
            )
            return jnp.sum(y * dy_seed)

        # the uncompressed layer: grad wrt the dense weight
        dense_fn = jax.jit(
            jax.grad(lambda w_, v: jnp.sum((v @ w_) * dy_seed), (0, 1))
        )
        # per-factor reference walk under XLA autodiff (backend="bsr" shape)
        bsr_fn = jax.jit(
            jax.grad(lambda a, b: chain_loss(a, b, False), (0, 1))
        )
        # fused forward + the OLD rematerializing einsum backward
        remat_fn = jax.jit(
            jax.grad(lambda a, b: chain_loss(a, b, True), (0, 1))
        )
        prev_bwd = os.environ.get("REPRO_CHAIN_BWD")
        os.environ["REPRO_CHAIN_BWD"] = "ref"
        try:
            remat_fn(chain.values, x)  # compile while the escape hatch is on
            # fwd kernel only — the rematerializing backward is all einsums
            n_calls_remat = count_pallas_calls(remat_fn, chain.values, x)
        finally:
            if prev_bwd is None:
                os.environ.pop("REPRO_CHAIN_BWD", None)
            else:
                os.environ["REPRO_CHAIN_BWD"] = prev_bwd
        # fused forward + fused dgrad/wgrad backward (kernels/chain_bwd.py)
        # — compiled with the escape hatch pinned OFF, so an ambient
        # REPRO_CHAIN_BWD=ref can't turn this leg into a second remat one
        fused_fn = jax.jit(
            jax.grad(lambda a, b: chain_loss(a, b, True), (0, 1))
        )
        fwd_fn = jax.jit(lambda a, b: chain_loss(a, b, True))
        os.environ.pop("REPRO_CHAIN_BWD", None)
        try:
            fused_fn(chain.values, x)  # compile
            # structural: the whole fused backward is ≤ 2 extra launches
            n_calls = count_pallas_calls(fused_fn, chain.values, x)
        finally:
            if prev_bwd is not None:
                os.environ["REPRO_CHAIN_BWD"] = prev_bwd

        gv_f, gx_f = fused_fn(chain.values, x)
        gv_r, gx_r = bsr_fn(chain.values, x)
        parity = max(_rel(gv_f, gv_r), _rel(gx_f, gx_r))
        if parity > 1e-5:
            raise RuntimeError(
                f"grad parity broken ({in_dim}x{out_dim} J{n_factors}): "
                f"{parity:.3e} > 1e-5"
            )

        # interpret-mode calls are CPU emulation (smoke value only, and
        # slow) — keep their iteration count down; the XLA paths get the
        # usual medians
        kw = dict(n_warmup=1, n_iter=3) if interpret else {}
        t_dense = timeit_us(dense_fn, w, x)
        t_bsr = timeit_us(bsr_fn, chain.values, x)
        t_remat = timeit_us(remat_fn, chain.values, x, **kw)
        t_fused = timeit_us(fused_fn, chain.values, x, **kw)
        t_fwd = timeit_us(fwd_fn, chain.values, x, **kw)

        assert n_calls == 3, n_calls  # 1 fwd + dgrad + wgrad
        assert n_calls_remat == 1, n_calls_remat  # fwd only, einsum bwd

        # optional: the mesh-sharded training path (2×2 debug mesh; ref
        # segments on CPU so the collective structure is timed, not the
        # interpret emulator).  Skipped (key omitted — NaN would break
        # strict-JSON consumers of run.py --json) below 4 devices.
        t_sharded = None
        if len(devices) >= 4:
            from repro.api.operator import ShardSpec

            mesh = jax.sharding.Mesh(
                np.array(devices[:4]).reshape(2, 2), ("data", "model")
            )

            def sh_loss(vals, v):
                bf_sh = BlockFaust(
                    tuple(
                        dataclasses.replace(f, values=val)
                        for f, val in zip(bf.factors, vals)
                    ),
                    bf.lam,
                )
                o = FaustOp.from_blockfaust(bf_sh).with_sharding(
                    ShardSpec(mesh)
                )
                return jnp.sum(
                    o.apply(v, backend="fused_sharded", use_kernel=on_tpu)
                    * dy_seed
                )

            sharded_fn = jax.jit(
                jax.grad(sh_loss, (0, 1), allow_int=True)
            )
            vals = [f.values for f in bf.factors]
            t_sharded = timeit_us(sharded_fn, vals, x, **kw)

        # the grad-priced dispatch decision (staged under the AD trace)
        jax.make_jaxpr(
            jax.grad(lambda v: jnp.sum(op.apply(v, use_kernel=False)))
        )(x)
        report = last_report()
        assert report.grad, "dispatch did not detect the AD trace"
        est = report.est_us
        grad_fuse_gain = (
            est["bsr"] / est["fused"] if "fused" in est and "bsr" in est else 0.0
        )
        sharded_col = (
            f"sharded_us={t_sharded:.1f};" if t_sharded is not None else ""
        )
        emit(
            f"grad_{in_dim}x{out_dim}_J{n_factors}",
            t_fused,
            f"dense_us={t_dense:.1f};bsr_us={t_bsr:.1f};"
            f"remat_us={t_remat:.1f};fused_us={t_fused:.1f};"
            f"{sharded_col}fwd_us={t_fwd:.1f};"
            f"bwd_over_fwd={t_fused / max(t_fwd, 1e-9):.2f};"
            f"remat_over_fused={t_remat / max(t_fused, 1e-9):.2f};"
            f"bwd_pallas_calls={n_calls - 1};"
            f"grad_parity={parity:.1e};auto_grad_backend={report.backend};"
            f"dispatch_source={report.source};"
            f"tpu_grad_fuse_gain={grad_fuse_gain:.2f};"
            f"interpret={int(interpret)}",
            dispatch=report,
        )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--grad", action="store_true",
        help="run the training-path (fwd+bwd) benchmark instead",
    )
    ap.add_argument(
        "--dtype", choices=DTYPES, default="f32",
        help="values dtype axis: f32 = full benchmark (+low-precision "
        "rows per REPRO_BENCH_DTYPES); others = focused fused-path run",
    )
    args = ap.parse_args()
    run_grad() if args.grad else run(dtype=args.dtype)
