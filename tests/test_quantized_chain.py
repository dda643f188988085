"""Quantized packed chains: int8/fp8 block values with in-VMEM dequant.

Coverage per the quantization contract (``core/compress.quantize_chain``
and the dequantizing kernels):

  * round-trip error bounds per (dtype, scheme), requantization
    idempotence (quantize∘dequantize∘quantize is the identity on the
    codes/scales), and layout invariants;
  * kernel-vs-oracle parity for J ∈ {1, 2, 4} including ragged feature
    boundaries and odd batch — the in-VMEM dequant must be step-exact
    against :func:`repro.kernels.ref.packed_chain_q_ref`;
  * gradient parity (dx and dscales) through the dequantizing fused
    backward vs autodiff of the dequantizing reference walk;
  * sharded parity on a 2×2 debug mesh (skips below 4 devices);
  * the quantized hot-swap: re-quantize against the serving layout,
    values-only vs repack classification, token-exactness only when the
    scales survived bit-for-bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FaustOp
from repro.core.compress import (
    BlockFaust,
    QUANT_DTYPES,
    QUANT_SCHEMES,
    dequantize_chain,
    expand_scales,
    pack_chain,
    pack_dense,
    quantize_chain,
    random_block_factor,
    unpack_chain,
)
from repro.kernels import ref as R
from repro.kernels.ops import packed_chain_apply

jax.config.update("jax_platform_name", "cpu")

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

# Round-trip relative error budget per dtype (values drawn N(0, 0.3)):
# int8 symmetric-absmax lands ~4e-3; e4m3 (3 mantissa bits) ~3e-2; e5m2
# (2 bits) ~7e-2.  Bounds carry ~2× headroom.
ROUNDTRIP_TOL = {"int8": 8e-3, "fp8_e4m3": 6e-2, "fp8_e5m2": 1.3e-1}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _chain(seed=0, counts=(4, 6, 3), blk=8, k=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(counts) - 1)
    factors = tuple(
        random_block_factor(
            keys[i], counts[i] * blk, counts[i + 1] * blk, blk, blk,
            min(k, counts[i]),
        )
        for i in range(len(counts) - 1)
    )
    return pack_chain(BlockFaust(factors, jnp.asarray(1.2, jnp.float32)))


# ---------------------------------------------------------------------------
# Round trip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", QUANT_SCHEMES)
@pytest.mark.parametrize("dtype", sorted(QUANT_DTYPES))
def test_roundtrip_error_bounds(dtype, scheme):
    pc = _chain(1)
    qc = quantize_chain(pc, dtype, scheme)
    assert qc.quantized and qc.qscheme == f"{dtype}:{scheme}"
    assert qc.values.dtype == QUANT_DTYPES[dtype][0]
    s = pc.values.shape[0]
    assert qc.scales.shape == ((s,) if scheme == "per_block" else (s, 8))
    assert qc.scales.dtype == jnp.float32
    back = dequantize_chain(qc)
    assert back.qscheme is None and back.values.dtype == jnp.float32
    assert _rel(back.values, pc.values) <= ROUNDTRIP_TOL[dtype]
    # per-row scales can only tighten the per-block bound
    if scheme == "per_row":
        qb = quantize_chain(pc, dtype, "per_block")
        assert _rel(back.values, pc.values) <= _rel(
            np.asarray(dequantize_chain(qb).values), pc.values
        ) + 1e-7


@pytest.mark.parametrize("dtype", sorted(QUANT_DTYPES))
def test_requantize_is_idempotent(dtype):
    """quantize(dequantize(q)) reproduces codes and scales exactly — the
    dequantized grid points are representable, so the round trip through
    f32 is lossless."""
    qc = quantize_chain(_chain(2), dtype)
    q2 = quantize_chain(dequantize_chain(qc), dtype)
    np.testing.assert_array_equal(np.asarray(qc.scales), np.asarray(q2.scales))
    np.testing.assert_array_equal(
        np.asarray(qc.values).view(np.uint8), np.asarray(q2.values).view(np.uint8)
    )


def test_quantize_rejects_bad_args():
    pc = _chain(3)
    with pytest.raises(ValueError):
        quantize_chain(pc, "int4")
    with pytest.raises(ValueError):
        quantize_chain(pc, "int8", "per_tensor")
    with pytest.raises(ValueError):
        quantize_chain(quantize_chain(pc, "int8"), "int8")  # already quantized


def test_zero_block_quantizes_to_zero():
    pc = _chain(4)
    vals = np.asarray(pc.values).copy()
    vals[0] = 0.0
    pc0 = dataclasses.replace(pc, values=jnp.asarray(vals))
    qc = quantize_chain(pc0, "int8")
    assert float(np.abs(np.asarray(qc.scales)[0]).min()) == 1.0  # guard scale
    np.testing.assert_array_equal(
        np.asarray(dequantize_chain(qc).values)[0], 0.0
    )


# ---------------------------------------------------------------------------
# Kernel vs oracle (fwd)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_factors", [1, 2, 4])
@pytest.mark.parametrize("dtype", ["int8", "fp8_e4m3"])
def test_kernel_matches_dequant_oracle(n_factors, dtype):
    counts = [4, 6, 3, 5, 4][: n_factors + 1]
    qc = quantize_chain(_chain(n_factors, counts), dtype)
    x = jax.random.normal(jax.random.PRNGKey(9), (9, counts[0] * 8))  # odd batch
    sc = expand_scales(qc.scales, qc.plan.block)
    want = qc.lam * R.packed_chain_q_ref(x, qc.values, qc.in_idx, qc.plan, sc)
    got_ref = packed_chain_apply(x, qc, use_kernel=False)
    got_kern = packed_chain_apply(x, qc, use_kernel=True, bt=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got_ref), np.asarray(want), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(got_kern), np.asarray(want), rtol=1e-5, atol=1e-5
    )


@pytest.mark.parametrize("scheme", QUANT_SCHEMES)
def test_kernel_equals_dequantized_f32_apply(scheme):
    """The quantized apply must equal the f32 apply of the *dequantized*
    chain — quantization error lives in the values, never in the walk."""
    qc = quantize_chain(_chain(7), "int8", scheme)
    fc = dequantize_chain(qc)
    x = jax.random.normal(jax.random.PRNGKey(3), (6, fc.plan.in_features))
    got = packed_chain_apply(x, qc, use_kernel=True, bt=8, interpret=True)
    want = packed_chain_apply(x, fc, use_kernel=True, bt=8, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
    )


def test_kernel_ragged_boundaries():
    """Ragged (non-block-multiple) dims at the ends and interior, odd
    batch: quantized kernel vs quantized oracle vs dense product."""
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.normal(size=(20, 30)).astype(np.float32) * 0.3)
    w2 = jnp.asarray(rng.normal(size=(30, 13)).astype(np.float32) * 0.3)
    bf = BlockFaust(
        (pack_dense(w1, 8, 8, 4), pack_dense(w2, 8, 8, 4)),
        jnp.asarray(0.9, jnp.float32),
    )
    qc = quantize_chain(pack_chain(bf), "int8")
    x = jnp.asarray(rng.normal(size=(5, 20)).astype(np.float32))
    got = packed_chain_apply(x, qc, use_kernel=True, bt=8, interpret=True)
    want = packed_chain_apply(x, qc, use_kernel=False)
    assert got.shape == (5, 13)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )
    dense = np.asarray(x) @ np.asarray(unpack_chain(qc).todense())
    np.testing.assert_allclose(np.asarray(got), dense, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Gradients
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", ["int8", "fp8_e4m3"])
def test_grad_parity_through_dequantizing_backward(dtype):
    """dx and dscales from the fused dequantizing dgrad/wgrad pair vs
    autodiff of the dequantizing reference walk."""
    qc = quantize_chain(_chain(11, (4, 6, 3, 5)), dtype)
    x = jax.random.normal(jax.random.PRNGKey(4), (9, qc.plan.in_features))
    dy = jax.random.normal(jax.random.PRNGKey(5), (9, qc.plan.out_features))

    def loss(xx, scl, use_kernel):
        pc = dataclasses.replace(qc, scales=scl)
        y = packed_chain_apply(
            xx, pc, use_kernel=use_kernel, bt=8, interpret=True
        )
        return jnp.sum(y * dy)

    gx_k, gs_k = jax.grad(lambda a, b: loss(a, b, True), (0, 1))(x, qc.scales)
    gx_r, gs_r = jax.grad(lambda a, b: loss(a, b, False), (0, 1))(x, qc.scales)
    assert _rel(gx_k, gx_r) <= 1e-5
    assert _rel(gs_k, gs_r) <= 1e-5


def test_grad_wrt_codes_is_inert():
    """The integer codes are frozen parameters — grad wrt the quantized
    values must be a zero/float0 cotangent, not a dequantized float one."""
    qc = quantize_chain(_chain(12), "int8")
    x = jax.random.normal(jax.random.PRNGKey(6), (4, qc.plan.in_features))

    def loss(vals):
        pc = dataclasses.replace(qc, values=vals)
        return jnp.sum(
            packed_chain_apply(x, pc, use_kernel=True, bt=8, interpret=True)
        )

    g = jax.grad(loss, allow_int=True)(qc.values)
    assert not np.any(np.asarray(jax.tree_util.tree_leaves(g)[0]))


# ---------------------------------------------------------------------------
# Sharded
# ---------------------------------------------------------------------------


@needs_mesh
@pytest.mark.parametrize("use_kernel", [False, True])
def test_sharded_quantized_parity(use_kernel):
    from repro.api import ShardSpec
    from repro.launch.mesh import make_debug_mesh

    qc = quantize_chain(_chain(13, (4, 4, 6)), "int8")
    op = FaustOp.from_packed(qc)
    x = jax.random.normal(jax.random.PRNGKey(7), (10, qc.plan.in_features))
    want = op.apply(x, backend="fused", use_kernel=use_kernel, bt=8,
                    interpret=True)
    sop = op.with_sharding(ShardSpec(make_debug_mesh(2, 2)))
    got = sop.apply(x, backend="fused_sharded", use_kernel=use_kernel, bt=8,
                    interpret=True)
    assert _rel(got, want) <= 1e-6


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def test_dispatch_prices_quantized_bytes():
    pc = _chain(14)
    qc = quantize_chain(pc, "int8")
    rf = FaustOp.from_packed(pc).dispatch_for(64)
    rq = FaustOp.from_packed(qc).dispatch_for(64)
    assert rq.values_dtype == "int8" and rf.values_dtype == "float32"
    assert rq.weight_bytes == qc.weight_bytes
    assert rq.weight_bytes < rf.weight_bytes
    assert f"weight_bytes={rq.weight_bytes}" in rq.reason
    row = rq.as_row()
    assert row["weight_bytes"] == qc.weight_bytes
    assert row["values_dtype"] == "int8"


# ---------------------------------------------------------------------------
# Hot swap
# ---------------------------------------------------------------------------


def test_quantized_swap_values_only_and_token_exactness():
    from repro.streaming.swap import quantized_swap, requantize_like

    pc = _chain(16)
    qc = quantize_chain(pc, "fp8_e4m3", "per_row")
    # identical values → identical scales → token-exact values-only swap
    new_q, rep = quantized_swap(qc, pc)
    assert rep.kind == "values_only" and rep.requantized
    assert rep.token_exact and not rep.retrace
    assert new_q.qscheme == qc.qscheme  # layout preserved
    # perturbed values (same support): values-only but scales moved
    bumped = dataclasses.replace(pc, values=pc.values * 1.7)
    new_q2, rep2 = quantized_swap(qc, bumped)
    assert rep2.kind == "values_only"
    assert not rep2.token_exact
    # requantize_like guards
    with pytest.raises(ValueError):
        requantize_like(pc, pc)  # serving chain not quantized
    with pytest.raises(ValueError):
        requantize_like(qc, new_q)  # refreshed chain already quantized


def test_quantized_swap_moved_support_repacks():
    from repro.streaming.swap import quantized_swap

    pc = _chain(17, (4, 4))
    qc = quantize_chain(pc, "int8")
    # moved support, same s_tot: shuffle each factor's in_idx
    idx = np.asarray(pc.in_idx).copy()
    o0, o1 = pc.plan.offsets[0], pc.plan.offsets[1]
    k = pc.plan.k_blocks[0]
    per_row = idx[o0:o1].reshape(-1, k)
    per_row = (per_row + 1) % pc.plan.in_blocks[0]
    per_row.sort(axis=1)
    idx[o0:o1] = per_row.reshape(-1)
    moved = dataclasses.replace(pc, in_idx=jnp.asarray(idx))
    new_q, rep = quantized_swap(qc, moved)
    assert rep.kind == "repack" and rep.retrace
    assert not rep.token_exact


def test_faustop_roundtrip_preserves_quantization():
    qc = quantize_chain(_chain(18), "int8")
    op = FaustOp.from_packed(qc)
    assert op.to("packed") is op  # fast path keeps the quantized rep
    assert op.quant_info() == ("int8", int(np.asarray(qc.scales).size) * 4)
    # adjoint + todense run off the dequantized view, shape-correct
    m, n = op.shape
    assert op.T.shape == (n, m)
    y = op.T.apply(jax.random.normal(jax.random.PRNGKey(8), (3, n)))
    assert y.shape == (3, m)
