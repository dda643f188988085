"""Fused chain backward (``kernels/chain_bwd.py``) vs its oracles.

Coverage per the kernel contract:
  * dgrad/wgrad parity vs the rematerializing reference walk
    (``chain_bwd_ref``) and vs XLA autodiff of the dense product, gated
    ≤ 1e-5 (f32) across J ∈ {1, 2, 4}, ragged feature dims, odd batches,
    and bf16 inputs;
  * the ``custom_vjp`` rewiring: ``jax.grad`` through
    ``packed_chain_apply(use_kernel=True)`` equals the reference path,
    including the ``REPRO_CHAIN_BWD=ref`` escape hatch;
  * the launch-count claim: the whole backward is ≤ 2 ``pallas_call``s
    regardless of J (3 in the grad jaxpr: 1 forward + dgrad + wgrad);
  * ``ChainPlan.reverse()`` invariants (involution, swapped domains) and
    the assembled step-table cache (zero per-call host assembly on
    repeated eager applies of the same operator).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compress import (
    BlockFaust,
    pack_chain,
    pack_dense,
    random_block_factor,
)
from repro.kernels import chain_bwd as CB
from repro.kernels.ops import chain_meta, packed_chain_apply

jax.config.update("jax_platform_name", "cpu")


def _rand_chain(seed, block_counts, blk=8, k=2, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(block_counts) - 1)
    factors = tuple(
        random_block_factor(
            keys[i],
            block_counts[i] * blk,
            block_counts[i + 1] * blk,
            blk,
            blk,
            min(k, block_counts[i]),
            dtype=dtype,
        )
        for i in range(len(block_counts) - 1)
    )
    return BlockFaust(factors, jnp.asarray(1.3, dtype))


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


# ---------------------------------------------------------------------------
# kernel-level parity vs the reference walk
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_factors", [1, 2, 4])
@pytest.mark.parametrize("batch", [8, 9])  # tile-exact and odd (padded)
def test_dgrad_wgrad_match_ref_walk(n_factors, batch):
    counts = [4, 6, 3, 5, 4][: n_factors + 1]
    bf = _rand_chain(n_factors, counts, k=3)
    chain = pack_chain(bf)
    plan = chain.plan
    bpad = -(-batch // 8) * 8
    x = jax.random.normal(jax.random.PRNGKey(1), (bpad, counts[0] * 8))
    dy = jax.random.normal(jax.random.PRNGKey(2), (bpad, counts[-1] * 8))
    dx_ref, dv_ref = CB.chain_bwd_ref(x, chain.values, chain.in_idx, dy, plan=plan)
    dx = CB.chain_dgrad(dy, chain.values, chain.in_idx, plan=plan, bt=8, interpret=True)
    dv = CB.chain_wgrad(
        x, dy, chain.values, chain.in_idx, plan=plan, bt=8, interpret=True
    )
    assert _rel(dx, dx_ref) <= 1e-5
    assert _rel(dv, dv_ref) <= 1e-5


def test_wgrad_multi_tile_partials_sum():
    """B > bt exercises the per-tile partial slabs + their accumulation."""
    bf = _rand_chain(7, [4, 6, 4], k=3)
    chain = pack_chain(bf)
    x = jax.random.normal(jax.random.PRNGKey(3), (32, 32))  # 4 tiles of bt=8
    dy = jax.random.normal(jax.random.PRNGKey(4), (32, 32))
    _, dv_ref = CB.chain_bwd_ref(x, chain.values, chain.in_idx, dy, plan=chain.plan)
    dv = CB.chain_wgrad(
        x, dy, chain.values, chain.in_idx, plan=chain.plan, bt=8, interpret=True
    )
    assert _rel(dv, dv_ref) <= 1e-5


# ---------------------------------------------------------------------------
# custom_vjp rewiring: jax.grad parity vs reference and vs the dense product
# ---------------------------------------------------------------------------


def _grad_through(chain, x, dy_seed, use_kernel):
    def loss(x, values):
        pc = dataclasses.replace(chain, values=values)
        y = packed_chain_apply(x, pc, use_kernel=use_kernel, bt=8, interpret=True)
        return jnp.sum(y * dy_seed)

    return jax.grad(loss, (0, 1))(x, chain.values)


@pytest.mark.parametrize("n_factors", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_matches_ref_walk(n_factors, dtype):
    counts = [4, 6, 3, 5, 4][: n_factors + 1]
    bf = _rand_chain(20 + n_factors, counts, k=3, dtype=dtype)
    chain = pack_chain(bf)
    x = jax.random.normal(jax.random.PRNGKey(5), (9, counts[0] * 8), dtype=dtype)
    dy_seed = jax.random.normal(
        jax.random.PRNGKey(6), (9, counts[-1] * 8), dtype=dtype
    )
    gx_k, gv_k = _grad_through(chain, x, dy_seed, use_kernel=True)
    gx_r, gv_r = _grad_through(chain, x, dy_seed, use_kernel=False)
    assert gx_k.dtype == x.dtype and gv_k.dtype == chain.values.dtype
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert _rel(gx_k, gx_r) <= tol
    assert _rel(gv_k, gv_r) <= tol


@pytest.mark.parametrize("n_factors", [1, 2, 4])
def test_grad_x_matches_dense_autodiff(n_factors):
    """dx through the fused backward == XLA autodiff of x @ todense()."""
    counts = [4, 6, 3, 5, 4][: n_factors + 1]
    bf = _rand_chain(30 + n_factors, counts, k=3)
    chain = pack_chain(bf)
    w = bf.todense()
    x = jax.random.normal(jax.random.PRNGKey(7), (8, counts[0] * 8))
    dy_seed = jax.random.normal(jax.random.PRNGKey(8), (8, counts[-1] * 8))

    def loss_k(x):
        y = packed_chain_apply(x, chain, use_kernel=True, bt=8, interpret=True)
        return jnp.sum(y * dy_seed)

    gx_k = jax.grad(loss_k)(x)
    gx_d = jax.grad(lambda x: jnp.sum((x @ w) * dy_seed))(x)
    assert _rel(gx_k, gx_d) <= 1e-5


def test_grad_ragged_and_odd_batch():
    """Ragged dims at the ends and an interior boundary, odd batch rows —
    backward masking must mirror the forward's slice-then-pad exactly."""
    rng = np.random.default_rng(2)
    w1 = jnp.asarray(rng.normal(size=(20, 30)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(30, 13)).astype(np.float32))
    bf = BlockFaust(
        (pack_dense(w1, 8, 8, 4), pack_dense(w2, 8, 8, 4)),
        jnp.asarray(0.9, jnp.float32),
    )
    chain = pack_chain(bf)
    x = jnp.asarray(rng.normal(size=(5, 20)).astype(np.float32))
    dy_seed = jnp.asarray(rng.normal(size=(5, 13)).astype(np.float32))

    def loss(x, values, use_kernel):
        pc = dataclasses.replace(chain, values=values)
        y = packed_chain_apply(x, pc, use_kernel=use_kernel, bt=8, interpret=True)
        return jnp.sum(y * dy_seed)

    gx_k, gv_k = jax.grad(lambda a, b: loss(a, b, True), (0, 1))(x, chain.values)
    gx_r, gv_r = jax.grad(lambda a, b: loss(a, b, False), (0, 1))(x, chain.values)
    assert _rel(gx_k, gx_r) <= 1e-5
    assert _rel(gv_k, gv_r) <= 1e-5
    # and vs autodiff of the dense product (grad wrt x only — the dense
    # matrix has no per-block parameterization)
    gx_d = jax.grad(
        lambda a: jnp.sum((a @ bf.todense()) * dy_seed)
    )(x)
    assert _rel(gx_k, gx_d) <= 1e-5


def test_ref_escape_hatch(monkeypatch):
    """REPRO_CHAIN_BWD=ref routes the custom_vjp through the einsum walk."""
    bf = _rand_chain(40, [4, 5, 4], k=2)
    chain = pack_chain(bf)
    x = jax.random.normal(jax.random.PRNGKey(9), (8, 32))

    def loss(x):
        return jnp.sum(
            packed_chain_apply(x, chain, use_kernel=True, bt=8, interpret=True) ** 2
        )

    monkeypatch.setenv("REPRO_CHAIN_BWD", "ref")
    jaxpr_ref = str(jax.make_jaxpr(jax.grad(loss))(x))
    monkeypatch.delenv("REPRO_CHAIN_BWD")
    jaxpr_fused = str(jax.make_jaxpr(jax.grad(loss))(x))
    assert jaxpr_ref.count("pallas_call") == 1  # fwd only; bwd is einsums
    assert jaxpr_fused.count("pallas_call") == 3
    gx_ref = jax.grad(loss)(x)
    gx_fused = jax.grad(loss)(x)
    assert _rel(gx_fused, gx_ref) <= 1e-5


# ---------------------------------------------------------------------------
# launch-count claim
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_factors", [1, 2, 4])
def test_backward_at_most_two_pallas_calls(n_factors):
    """The fused backward is ≤ 2 launches (dgrad + wgrad) for any J — the
    grad jaxpr stages exactly 3 pallas_calls incl. the forward."""
    counts = [4, 6, 3, 5, 4][: n_factors + 1]
    bf = _rand_chain(50 + n_factors, counts)
    chain = pack_chain(bf)
    x = jax.random.normal(jax.random.PRNGKey(10), (8, counts[0] * 8))

    def loss(x, values):
        pc = dataclasses.replace(chain, values=values)
        return jnp.sum(
            packed_chain_apply(x, pc, use_kernel=True, bt=8, interpret=True)
        )

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, chain.values))
    assert jaxpr.count("pallas_call") == 3


# ---------------------------------------------------------------------------
# ChainPlan.reverse() + step-table assembly
# ---------------------------------------------------------------------------


def test_fit_bt_clamps_wide_chains():
    """Wide chains must shrink the backward batch tile to fit VMEM; the
    clamped tile always divides the caller's (so the padded batch still
    tiles exactly), and small test chains are untouched."""
    small = pack_chain(_rand_chain(70, [4, 4], k=2)).plan
    assert CB.fit_bt(small, 8, 4, wgrad=True) == 8
    # a production-wide chain: 128 blocks of 128 ⇒ the f32 cotangent
    # ping-pong alone (2·128·bt·128·4) blows 12 MiB at bt=128
    import dataclasses as dc

    wide = dc.replace(
        small,
        in_blocks=(128, 128),
        out_blocks=(128, 128),
        in_feats=(128 * 128, 128 * 128),
        out_feats=(128 * 128, 128 * 128),
        block=128,
    )
    for wgrad in (False, True):
        fitted = CB.fit_bt(wide, 128, 4, wgrad=wgrad)
        assert fitted < 128 and 128 % fitted == 0 and fitted >= 8
    # wgrad (extra acts scratch) never gets a larger tile than dgrad
    assert CB.fit_bt(wide, 128, 4, wgrad=True) <= CB.fit_bt(
        wide, 128, 4, wgrad=False
    )
    # the kernels run at exactly the tile they are given: an unfit tile
    # raises, and the clamped tile produces correct gradients end to end
    bf = _rand_chain(71, [3, 4, 3], k=2)
    chain = pack_chain(bf)
    x = jax.random.normal(jax.random.PRNGKey(72), (16, 24))
    dy = jax.random.normal(jax.random.PRNGKey(73), (16, 24))
    dx_ref, dv_ref = CB.chain_bwd_ref(x, chain.values, chain.in_idx, dy, plan=chain.plan)
    import unittest.mock as mock

    with mock.patch.object(CB, "_VMEM_BUDGET_BYTES", 8 * 1024):
        bt = CB.fit_bt(chain.plan, 16, 4, wgrad=True)
        assert bt == 8
        with pytest.raises(ValueError, match="VMEM"):
            CB.chain_wgrad(x, dy, chain.values, chain.in_idx, plan=chain.plan, bt=16, interpret=True)
        dx = CB.chain_dgrad(dy, chain.values, chain.in_idx, plan=chain.plan, bt=bt, interpret=True)
        dv = CB.chain_wgrad(x, dy, chain.values, chain.in_idx, plan=chain.plan, bt=bt, interpret=True)
    assert _rel(dx, dx_ref) <= 1e-5
    assert _rel(dv, dv_ref) <= 1e-5


def test_chain_plan_reverse_involution():
    bf = _rand_chain(60, [4, 6, 3, 5], k=2)
    plan = pack_chain(bf).plan
    rev = plan.reverse()
    assert rev.reverse() == plan
    assert rev.n_steps == plan.n_steps
    assert rev.in_blocks == tuple(reversed(plan.out_blocks))
    assert rev.out_blocks == tuple(reversed(plan.in_blocks))
    assert rev.in_features == plan.out_features
    assert rev.out_features == plan.in_features
    assert max(rev.in_blocks + rev.out_blocks) == max(plan.in_blocks + plan.out_blocks)


def test_dgrad_meta_layout():
    bf = _rand_chain(61, [3, 4, 2], k=2)
    chain = pack_chain(bf)
    plan = chain.plan
    meta = np.asarray(CB.dgrad_meta(plan, chain.in_idx))
    assert meta.shape == (plan.n_steps, CB.DGRAD_META_COLS)
    # column 0 is the reversed flat in_idx
    np.testing.assert_array_equal(meta[:, 0], np.asarray(chain.in_idx)[::-1])
    # each factor's reversed block: parity (J-1-j)%2, factor-start flag on
    # its first reversed row, src blocks counting down
    J = plan.n_factors
    for j in range(J):
        lo = plan.n_steps - plan.offsets[j + 1]
        hi = plan.n_steps - plan.offsets[j]
        rows = meta[lo:hi]
        np.testing.assert_array_equal(rows[:, 2], (J - 1 - j) % 2)
        assert rows[0, 3] == 1 and not rows[1:, 3].any()
        np.testing.assert_array_equal(
            rows[:, 1],
            np.repeat(np.arange(plan.out_blocks[j]), plan.k_blocks[j])[::-1],
        )


def test_step_table_cache_hits_on_repeat_eager_apply():
    bf = _rand_chain(62, [3, 4], k=2)
    chain = pack_chain(bf)
    plan = chain.plan
    CB._TABLE_CACHE.clear()
    m1 = chain_meta(plan, chain.in_idx)
    m2 = chain_meta(plan, chain.in_idx)
    assert m1 is m2  # identical object: zero per-call assembly
    d1 = CB.dgrad_meta(plan, chain.in_idx)
    assert CB.dgrad_meta(plan, chain.in_idx) is d1
    w1 = CB.wgrad_meta(plan, chain.in_idx)
    assert CB.wgrad_meta(plan, chain.in_idx) is w1
    # a different in_idx array must not hit the same entry
    other = chain.in_idx + 0
    assert chain_meta(plan, other) is not m1
    # under tracing the cache is bypassed (no tracer leaks)
    def traced(idx):
        t = chain_meta(plan, idx)
        assert isinstance(t, jax.core.Tracer)
        return t.sum()

    jax.jit(traced)(chain.in_idx)
    assert not any(
        isinstance(ent[1], jax.core.Tracer) for ent in CB._TABLE_CACHE.values()
    )
