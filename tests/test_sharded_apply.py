"""Multi-device FaustOp parity: the sharded fused apply vs single-device
backends on a debug mesh.

Needs ≥ 4 devices — run under the CPU host-device override, which is what
the dedicated ``scripts/ci.sh`` leg does on every push::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_sharded_apply.py

(the flag must be set before the *first* jax import, so it cannot be
applied from inside a collected test module; on a bare single-device run
everything here skips).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FactorizeSpec, FaustOp, ShardSpec, factorize, last_report
from repro.core.compress import BlockFaust, BlockSparseFactor, random_block_factor
from repro.kernels import chain_sharded as cs
from repro.launch.mesh import make_debug_mesh

jax.config.update("jax_platform_name", "cpu")

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

PARITY = 1e-6  # acceptance gate: sharded == single-device fused


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _chain(seed=0, nblocks=(4, 4, 6), blk=8, k=2, lam=1.1):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(nblocks) - 1)
    factors = tuple(
        random_block_factor(
            keys[i], nblocks[i] * blk, nblocks[i + 1] * blk, blk, blk, k
        )
        for i in range(len(nblocks) - 1)
    )
    return BlockFaust(factors, jnp.asarray(lam, jnp.float32))


def _local_support_chain(nb=4, blk=8, k=2, n_model=2, seed=3, n_factors=3):
    per = nb // n_model
    rng = np.random.default_rng(seed)
    factors = []
    for _ in range(n_factors):
        idx = np.stack([
            np.sort(rng.choice(per, size=min(k, per), replace=False))
            + (o // per) * per
            for o in range(nb)
        ]).astype(np.int32)
        vals = 0.3 * rng.normal(size=(nb, min(k, per), blk, blk)).astype(
            np.float32
        )
        factors.append(
            BlockSparseFactor(jnp.asarray(vals), jnp.asarray(idx),
                              nb * blk, nb * blk)
        )
    return BlockFaust(tuple(factors), jnp.asarray(1.0, jnp.float32))


@needs_mesh
@pytest.mark.parametrize("use_kernel", [False, True])
def test_sharded_matches_fused_crossing_chain(use_kernel):
    """Random supports (every boundary crosses shards): segmented fused
    launches + all-gathers reproduce the single-device fused apply."""
    bf = _chain()
    mesh = make_debug_mesh(2, 2)
    op = FaustOp.wrap(bf)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, bf.in_features))
    want = op.apply(x, backend="fused", use_kernel=False)
    sop = op.with_sharding(ShardSpec(mesh))
    got = sop.apply(
        x, backend="fused_sharded", use_kernel=use_kernel, bt=8, interpret=True
    )
    assert _rel(got, want) <= PARITY
    plan = cs.plan_shard(bf, mesh)
    # 2 factors, 1 crossing boundary → 2 fused segments with 1 all-gather
    assert plan.mode == "model" and len(plan.segments) == 2


@needs_mesh
@pytest.mark.parametrize("use_kernel", [False, True])
def test_sharded_local_support_single_launch(use_kernel):
    """Shard-local supports: the whole chain is one fused launch per shard
    with zero collectives, still bit-parity with single-device fused."""
    bf = _local_support_chain()
    mesh = make_debug_mesh(2, 2)
    plan = cs.plan_shard(bf, mesh)
    assert plan.mode == "model"
    assert len(plan.segments) == 1 and plan.crossing_feats == ()
    op = FaustOp.wrap(bf).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(2), (6, bf.in_features))
    want = FaustOp.wrap(bf).apply(x, backend="fused", use_kernel=False)
    got = op.apply(
        x, backend="fused_sharded", use_kernel=use_kernel, bt=8, interpret=True
    )
    assert _rel(got, want) <= PARITY


@needs_mesh
def test_sharded_report_carries_mesh_and_collectives():
    bf = _chain()
    mesh = make_debug_mesh(2, 2)
    op = FaustOp.wrap(bf).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(3), (8, bf.in_features))
    op.apply(x, backend="fused_sharded", use_kernel=False)
    rep = last_report()
    assert rep.backend == "fused_sharded"
    assert dict(rep.mesh_shape) == {"data": 2, "model": 2}
    assert rep.collective_bytes > 0  # crossing boundaries were priced
    assert "fused_sharded" in rep.est_us


@needs_mesh
def test_auto_selects_fused_sharded_at_scale():
    """The acceptance gate: backend='auto' picks (and reports) the sharded
    path when the per-shard weight-traffic win beats the ICI cost."""
    bf = _local_support_chain(nb=8, blk=16, k=4, n_model=2)
    mesh = make_debug_mesh(2, 2)
    op = FaustOp.wrap(bf).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(4), (16, bf.in_features))
    got = op.apply(x, backend="auto", use_kernel=False)
    rep = last_report()
    assert rep.backend == "fused_sharded", rep.reason
    assert rep.requested == "auto"
    want = FaustOp.wrap(bf).apply(x, backend="fused", use_kernel=False)
    assert _rel(got, want) <= PARITY


@needs_mesh
def test_sharded_fallback_non_divisible_blocks():
    """3 out-blocks over 2 model shards → replicated fallback, batch over
    the full mesh, same numbers."""
    bf = _chain(nblocks=(3, 3, 5))
    mesh = make_debug_mesh(2, 2)
    plan = cs.plan_shard(bf, mesh)
    assert plan.mode == "replicated"
    op = FaustOp.wrap(bf).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(5), (7, bf.in_features))
    want = FaustOp.wrap(bf).apply(x, backend="fused", use_kernel=False)
    got = op.apply(x, backend="fused_sharded", use_kernel=False)
    assert _rel(got, want) <= PARITY


@needs_mesh
def test_sharded_fallback_ragged_chain():
    """Non-block-multiple dims: replicated per-factor reference fallback."""
    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    bf = BlockFaust(
        (random_block_factor(keys[0], 30, 28, 8, 8, 2),
         random_block_factor(keys[1], 28, 44, 8, 8, 2)),
        jnp.asarray(1.2, jnp.float32),
    )
    mesh = make_debug_mesh(2, 2)
    assert cs.plan_shard(bf, mesh).mode == "replicated"
    op = FaustOp.wrap(bf).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(7), (5, 30))
    want = FaustOp.wrap(bf).apply(x, backend="bsr", use_kernel=False)
    got = op.apply(x, backend="fused_sharded", use_kernel=False)
    assert _rel(got, want) <= PARITY


@needs_mesh
def test_sharded_ragged_last_factor_model_mode():
    """A ragged output width (vocabulary tail) keeps the model-sharded
    plan; forward and grad match the single-device fused apply."""
    keys = jax.random.split(jax.random.PRNGKey(16), 2)
    bf = BlockFaust(
        (random_block_factor(keys[0], 32, 32, 8, 8, 2),
         random_block_factor(keys[1], 32, 29, 8, 8, 2)),
        jnp.asarray(0.9, jnp.float32),
    )
    mesh = make_debug_mesh(1, 4)
    assert cs.plan_shard(bf, mesh).mode == "model"
    placed = cs.place_blockfaust(bf, mesh)
    op = FaustOp.wrap(placed).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(17), (5, 32))
    want = FaustOp.wrap(bf).apply(x, backend="fused", use_kernel=False)
    got = op.apply(x, backend="fused_sharded", use_kernel=False)
    assert got.shape == (5, 29)
    assert _rel(got, want) <= PARITY
    g_sh = jax.grad(lambda v: op.apply(v, backend="fused_sharded", use_kernel=False).sum())(x)
    g_one = jax.grad(lambda v: FaustOp.wrap(bf).apply(v, backend="fused", use_kernel=False).sum())(x)
    assert _rel(g_sh, g_one) <= PARITY


@needs_mesh
def test_sharded_apply_jit_and_grad():
    bf = _chain()
    mesh = make_debug_mesh(2, 2)
    op = FaustOp.wrap(bf).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(8), (6, bf.in_features))

    def loss_sharded(v):
        return op.apply(v, backend="fused_sharded", use_kernel=False).sum()

    def loss_ref(v):
        return FaustOp.wrap(bf).apply(v, backend="bsr", use_kernel=False).sum()

    assert _rel(jax.jit(loss_sharded)(x), loss_ref(x)) <= PARITY
    g, g_ref = jax.grad(loss_sharded)(x), jax.grad(loss_ref)(x)
    assert _rel(g, g_ref) <= PARITY


def _sharded_grads(bf, mesh, x, dy_seed, *, use_kernel):
    """(dvalues list, dx) of a scalar loss through the sharded apply."""
    import dataclasses

    def loss(vals, v):
        bfx = BlockFaust(
            tuple(
                dataclasses.replace(f, values=val)
                for f, val in zip(bf.factors, vals)
            ),
            bf.lam,
        )
        y = cs.sharded_chain_apply(
            v, bfx, mesh, use_kernel=use_kernel, bt=8, interpret=True
        )
        return jnp.sum(y * dy_seed)

    return jax.grad(loss, (0, 1))([f.values for f in bf.factors], x)


def _ref_grads(bf, x, dy_seed):
    import dataclasses

    from repro.kernels.ops import blockfaust_apply

    def loss(vals, v):
        bfx = BlockFaust(
            tuple(
                dataclasses.replace(f, values=val)
                for f, val in zip(bf.factors, vals)
            ),
            bf.lam,
        )
        return jnp.sum(blockfaust_apply(v, bfx, use_kernel=False) * dy_seed)

    return jax.grad(loss, (0, 1))([f.values for f in bf.factors], x)


@needs_mesh
@pytest.mark.parametrize("use_kernel", [False, True])
def test_sharded_vjp_crossing_chain(use_kernel):
    """Gradients through the sharded apply — the fused dgrad/wgrad kernels
    run *per shard* inside shard_map (use_kernel=True) and JAX transposes
    the boundary all-gathers into reduce-scatters of the cotangent; parity
    vs single-device reference autodiff on dvalues and dx."""
    bf = _chain()  # random supports: the boundary crosses shards
    mesh = make_debug_mesh(2, 2)
    x = jax.random.normal(jax.random.PRNGKey(30), (10, bf.in_features))
    dy = jax.random.normal(jax.random.PRNGKey(31), (10, bf.out_features))
    gv, gx = _sharded_grads(bf, mesh, x, dy, use_kernel=use_kernel)
    gv_r, gx_r = _ref_grads(bf, x, dy)
    for a, b in zip(gv, gv_r):
        assert _rel(a, b) <= 1e-5
    assert _rel(gx, gx_r) <= 1e-5


@needs_mesh
@pytest.mark.parametrize("use_kernel", [False, True])
def test_sharded_vjp_local_support_odd_batch(use_kernel):
    """Shard-local supports (zero collectives either direction) + an odd
    batch that pads per shard — grads still match the reference."""
    bf = _local_support_chain()
    mesh = make_debug_mesh(2, 2)
    x = jax.random.normal(jax.random.PRNGKey(32), (7, bf.in_features))
    dy = jax.random.normal(jax.random.PRNGKey(33), (7, bf.out_features))
    gv, gx = _sharded_grads(bf, mesh, x, dy, use_kernel=use_kernel)
    gv_r, gx_r = _ref_grads(bf, x, dy)
    for a, b in zip(gv, gv_r):
        assert _rel(a, b) <= 1e-5
    assert _rel(gx, gx_r) <= 1e-5


@needs_mesh
def test_grad_dispatch_prices_sharded_fwd_bwd():
    """Under jax.grad the dispatch query is grad=True and fused_sharded is
    priced jointly (3× collectives/launches) — the report says so."""
    bf = _chain()
    mesh = make_debug_mesh(2, 2)
    op = FaustOp.wrap(bf).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(34), (8, bf.in_features))

    def loss(v):
        return op.apply(v, backend="fused_sharded", use_kernel=False).sum()

    jax.make_jaxpr(jax.grad(loss))(x)
    rep = last_report()
    assert rep.grad and rep.backend == "fused_sharded"
    assert "fused_sharded" in rep.est_us


@needs_mesh
def test_sharded_batch_padding_and_leading_dims():
    """Odd batches and extra leading dims survive the per-shard padding."""
    bf = _local_support_chain()
    mesh = make_debug_mesh(2, 2)
    op = FaustOp.wrap(bf).with_sharding(ShardSpec(mesh))
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 5, bf.in_features))
    want = FaustOp.wrap(bf).apply(x, backend="fused", use_kernel=False)
    got = op.apply(x, backend="fused_sharded", use_kernel=False)
    assert got.shape == want.shape
    assert _rel(got, want) <= PARITY


@needs_mesh
def test_factorize_mesh_returns_presharded_op():
    """FactorizeSpec.mesh: compressed layers come out carrying a ShardSpec
    with factor arrays already placed; apply parity holds end to end."""
    mesh = make_debug_mesh(2, 2)
    w = jax.random.normal(jax.random.PRNGKey(10), (32, 64)) * 0.05
    spec = FactorizeSpec(n_factors=2, block=8, k_first=3, k_mid=2,
                         n_iter_two=8, n_iter_global=8, mesh=mesh)
    op, info = factorize(w, spec)
    assert op.shard is not None and op.shard.mesh is mesh
    assert "fused_sharded" in op.feasible_backends()
    # same solve without the mesh: identical numbers
    op0, _ = factorize(w, FactorizeSpec(n_factors=2, block=8, k_first=3,
                                        k_mid=2, n_iter_two=8,
                                        n_iter_global=8))
    x = jax.random.normal(jax.random.PRNGKey(11), (6, 32))
    want = op0.apply(x, backend="bsr", use_kernel=False)
    got = op.apply(x, backend="fused_sharded", use_kernel=False)
    assert _rel(got, want) <= PARITY
    # factor arrays were device_put with a sharding on the mesh
    vals = info.blockfausts[0].factors[0].values
    assert vals.sharding.mesh is mesh or len(vals.sharding.device_set) >= 1


@needs_mesh
def test_composite_op_leaves_dispatch_on_mesh():
    """with_sharding pushes the spec to every leaf of a composite."""
    from repro.api import block_diag

    bf1, bf2 = _chain(seed=20), _chain(seed=21)
    mesh = make_debug_mesh(2, 2)
    op = block_diag([bf1, bf2]).with_sharding(ShardSpec(mesh))
    assert all(c.shard is not None for c in op.children)
    x = jax.random.normal(
        jax.random.PRNGKey(12), (4, bf1.in_features + bf2.in_features)
    )
    want = block_diag([bf1, bf2]).apply(x, backend="bsr", use_kernel=False)
    got = op.apply(x, backend="fused_sharded", use_kernel=False)
    assert _rel(got, want) <= PARITY


@needs_mesh
def test_compress_layers_mesh_presharded():
    """compress_layers(mesh=...) places every returned chain's factor
    arrays by out-block over the model axis (replication fallback where
    counts don't divide) — compressed layers come out serving-ready."""
    from repro.core.compress import compress_layers

    mesh = make_debug_mesh(2, 2)
    w = jax.random.normal(jax.random.PRNGKey(13), (16, 16)) * 0.1
    out = compress_layers(
        {"w": w}, n_factors=2, bk=8, bn=8, k_first=2, k_mid=2,
        n_iter_two=4, n_iter_global=4, mesh=mesh,
    )
    bf = out["w"]
    np.testing.assert_allclose(
        np.asarray(bf.todense()).shape, (16, 16)
    )
    for f in bf.factors:
        assert f.values.sharding.mesh is mesh
