"""The unified operator layer (``repro.api``) vs dense oracles.

Coverage per the API contract:
  * ``apply`` equals ``x @ todense()`` on every backend, for every
    wrapped representation (Faust / BlockFaust / PackedChain);
  * lazy algebra: adjoint (``op.H @ y ≈ op.todense().conj().T @ y``),
    composition (``(op2 @ op1).todense() ≈ op2.todense() @ op1.todense()``),
    block_diag / vstack / hstack vs their dense assemblies;
  * round-trip ``.to()`` conversions across all three formats;
  * cost-model dispatch: ``backend="auto"`` picks the fused path on a
    small-batch chain shape, and the :class:`DispatchReport` records the
    decision;
  * ``factorize()`` routing: presets, block route, auto-batching;
  * jit-safety of the ``rel_error_*`` diagnostics (both return traced
    Arrays).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (
    FactorizeSpec,
    FaustOp,
    block_diag,
    choose_backend,
    factorize,
    hstack,
    last_report,
    vstack,
)
from repro.core.compress import (
    BlockFaust,
    PackedChain,
    pack_chain,
    random_block_factor,
    unpack_chain,
)
from repro.core.faust import Faust
from repro.core.hierarchical import hadamard_matrix

jax.config.update("jax_platform_name", "cpu")


def _chain(seed, dims_blocks, blk=8, k=2, lam=1.3):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(dims_blocks) - 1)
    factors = tuple(
        random_block_factor(
            keys[i], dims_blocks[i] * blk, dims_blocks[i + 1] * blk, blk, blk,
            min(k, dims_blocks[i]),
        )
        for i in range(len(dims_blocks) - 1)
    )
    return BlockFaust(factors, jnp.asarray(lam, jnp.float32))


def _dense_faust(seed, dims, lam=0.9):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(dims) - 1)
    factors = tuple(
        jax.random.normal(keys[i], (dims[i + 1], dims[i])) * 0.3
        for i in range(len(dims) - 1)
    )
    return Faust(factors, jnp.asarray(lam, jnp.float32))


@pytest.fixture(scope="module")
def op_block():
    return FaustOp.from_blockfaust(_chain(0, [4, 4, 8]))


# ---------------------------------------------------------------------------
# apply vs dense, per representation and backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["dense", "bsr", "fused"])
def test_apply_matches_dense_blockfaust(op_block, backend):
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 32))
    want = x @ op_block.todense()
    got = op_block.apply(x, backend=backend)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["dense", "bsr"])
def test_apply_matches_dense_faust(backend):
    op = FaustOp.from_faust(_dense_faust(2, [24, 16, 40]))
    assert op.shape == (40, 24)  # = Faust.shape = (a_{J+1}, a_1)
    x = jax.random.normal(jax.random.PRNGKey(3), (6, op.shape[0]))
    np.testing.assert_allclose(
        np.asarray(op.apply(x, backend=backend)),
        np.asarray(x @ op.todense()),
        rtol=1e-5, atol=1e-5,
    )


def test_apply_matches_dense_packed(op_block):
    pc = op_block.to("packed")
    assert isinstance(pc.rep, PackedChain)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 32))
    for backend in ("dense", "bsr", "fused"):
        np.testing.assert_allclose(
            np.asarray(pc.apply(x, backend=backend)),
            np.asarray(x @ op_block.todense()),
            rtol=1e-5, atol=1e-5,
        )


# ---------------------------------------------------------------------------
# operator algebra vs dense oracles
# ---------------------------------------------------------------------------


def test_adjoint_vs_dense(op_block):
    m = op_block.todense()
    y = jax.random.normal(jax.random.PRNGKey(5), (4, 64))
    np.testing.assert_allclose(
        np.asarray(op_block.T.apply(y)), np.asarray(y @ m.T), rtol=1e-5, atol=1e-5
    )
    v = jax.random.normal(jax.random.PRNGKey(6), (32,))
    np.testing.assert_allclose(
        np.asarray(op_block.H @ v),
        np.asarray(m.conj().T @ v),
        rtol=1e-5, atol=1e-5,
    )
    # double transpose is the identity operator
    np.testing.assert_allclose(
        np.asarray(op_block.T.T.todense()), np.asarray(m), rtol=1e-6, atol=1e-6
    )


def test_adjoint_is_lazy(op_block):
    """No factor array changes under .T — only structural flags."""
    t = op_block.T
    assert t.adjoint and t.rep is op_block.rep
    assert t.shape == op_block.shape[::-1]


def test_compose_vs_dense(op_block):
    op2 = FaustOp.from_blockfaust(_chain(7, [8, 4], lam=0.7))  # (64, 32)
    comp = op_block @ op2  # (32, 64) @ (64, 32) → (32, 32)
    assert comp.kind == "compose" and comp.shape == (32, 32)
    np.testing.assert_allclose(
        np.asarray(comp.todense()),
        np.asarray(op_block.todense() @ op2.todense()),
        rtol=1e-5, atol=1e-5,
    )
    x = jax.random.normal(jax.random.PRNGKey(8), (5, 32))
    np.testing.assert_allclose(
        np.asarray(comp.apply(x)),
        np.asarray(x @ comp.todense()),
        rtol=1e-4, atol=1e-5,
    )
    with pytest.raises(ValueError, match="compose shape mismatch"):
        op_block @ op_block


def test_matmul_column_semantics(op_block):
    m = op_block.todense()
    xc = jax.random.normal(jax.random.PRNGKey(9), (64, 3))
    np.testing.assert_allclose(
        np.asarray(op_block @ xc), np.asarray(m @ xc), rtol=1e-5, atol=1e-5
    )
    x = jax.random.normal(jax.random.PRNGKey(10), (3, 32))
    np.testing.assert_allclose(  # __rmatmul__ = row semantics
        np.asarray(x @ op_block), np.asarray(x @ m), rtol=1e-5, atol=1e-5
    )
    # a raw NumPy lhs must defer to __rmatmul__ too (__array_ufunc__ = None)
    np.testing.assert_allclose(
        np.asarray(np.asarray(x) @ op_block), np.asarray(x @ m),
        rtol=1e-5, atol=1e-5,
    )


def test_stacks_vs_dense(op_block):
    other = FaustOp.from_blockfaust(_chain(11, [2, 3], lam=1.1))  # (16, 24)
    bd = block_diag([op_block, other])
    want = jax.scipy.linalg.block_diag(op_block.todense(), other.todense())
    np.testing.assert_allclose(np.asarray(bd.todense()), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(12), (4, 48))
    np.testing.assert_allclose(np.asarray(bd.apply(x)), np.asarray(x @ want),
                               rtol=1e-5, atol=1e-5)

    vs = vstack([op_block, op_block])  # (64, 64)
    want = jnp.concatenate([op_block.todense()] * 2, axis=0)
    xv = jax.random.normal(jax.random.PRNGKey(13), (4, 64))
    np.testing.assert_allclose(np.asarray(vs.apply(xv)), np.asarray(xv @ want),
                               rtol=1e-5, atol=1e-5)

    hs = hstack([op_block, op_block])  # (32, 128)
    want = jnp.concatenate([op_block.todense()] * 2, axis=1)
    xh = jax.random.normal(jax.random.PRNGKey(14), (4, 32))
    np.testing.assert_allclose(np.asarray(hs.apply(xh)), np.asarray(xh @ want),
                               rtol=1e-5, atol=1e-5)

    # structural adjoints swap the stack kind
    assert vs.T.kind == "hstack" and hs.T.kind == "vstack"
    assert bd.T.kind == "block_diag"
    np.testing.assert_allclose(
        np.asarray(vs.T.todense()), np.asarray(vs.todense().T),
        rtol=1e-6, atol=1e-6,
    )
    with pytest.raises(ValueError, match="equal output dims"):
        vstack([op_block, other])
    with pytest.raises(ValueError, match="cannot collapse"):
        bd.to("faust")


# ---------------------------------------------------------------------------
# conversions
# ---------------------------------------------------------------------------


def test_roundtrip_conversions(op_block):
    m = np.asarray(op_block.todense())
    seen = {"block": op_block}
    for fmt, typ in (("faust", Faust), ("packed", PackedChain),
                     ("block", BlockFaust)):
        for src in list(seen.values()):
            cv = src.to(fmt, block=8)
            assert isinstance(cv.rep, typ), (fmt, type(cv.rep))
            np.testing.assert_allclose(
                np.asarray(cv.todense()), m, rtol=1e-5, atol=1e-5
            )
            seen[fmt] = cv
    # faust → block/packed needs the block size (inferred here from none)
    fa = FaustOp.from_faust(_dense_faust(20, [24, 16]))
    with pytest.raises(ValueError, match="explicit block"):
        fa.to("block")
    cv = fa.to("block", block=8)
    np.testing.assert_allclose(
        np.asarray(cv.todense()), np.asarray(fa.todense()), rtol=1e-5, atol=1e-5
    )


def test_adjoint_and_compose_conversions(op_block):
    m = np.asarray(op_block.todense())
    np.testing.assert_allclose(
        np.asarray(op_block.T.to("faust").todense()), m.T, rtol=1e-5, atol=1e-5
    )
    comp = op_block @ op_block.T  # (32, 32) chain of 4 factors
    cv = comp.to("packed")
    np.testing.assert_allclose(
        np.asarray(cv.todense()), m @ m.T, rtol=1e-4, atol=1e-4
    )
    assert cv.n_factors == comp.n_factors


def test_unpack_chain_roundtrip(op_block):
    bf = op_block.rep
    back = unpack_chain(pack_chain(bf))
    assert [f.values.shape for f in back.factors] == [
        f.values.shape for f in bf.factors
    ]
    np.testing.assert_allclose(
        np.asarray(back.todense()), np.asarray(bf.todense()), rtol=0, atol=0
    )


def test_s_tot_and_rcg(op_block):
    bf = op_block.rep
    assert op_block.s_tot == bf.s_tot
    assert op_block.rcg == pytest.approx(bf.rcg())
    assert (op_block @ op_block.T).s_tot == 2 * bf.s_tot


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def test_auto_dispatch_picks_fused_on_small_batch_chain():
    # 64→256, J=2, k=2, block=8: s_tot=5120 vs dense 16384 (RCG 3.2);
    # at batch 4 the per-factor path pays the inner-activation round-trip
    # and dense pays 3.2× the weight bytes — fused must win.
    op = FaustOp.from_blockfaust(_chain(30, [8, 8, 32], k=2))
    x = jax.random.normal(jax.random.PRNGKey(31), (4, 64))
    y = op.apply(x, backend="auto")
    report = last_report()
    assert report.backend == "fused", report
    assert report.requested == "auto"
    assert report.est_us["fused"] <= min(report.est_us.values())
    assert set(report.feasible) == {"dense", "bsr", "fused"}
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ op.todense()), rtol=1e-5, atol=1e-5
    )
    row = report.as_row()
    assert row["backend"] == "fused" and row["batch"] == 4
    # forced backends record too — last_report() never goes stale
    op.apply(x, backend="bsr")
    forced = last_report()
    assert forced.backend == "bsr" and "forced by caller" in forced.reason


def test_dispatch_dense_when_rcg_below_one():
    # fully-dense factors ⇒ s_tot = 2·m·n ⇒ the per-factor path moves
    # more weight bytes than materialize-and-matmul (same launch count,
    # no fused path on a Faust leaf) — dense must win
    op = FaustOp.from_faust(_dense_faust(32, [32, 32, 32]))
    assert op.rcg <= 1.0
    report = choose_backend(
        batch=256, shape=op.shape, dtype=jnp.float32, s_tot=op.s_tot,
        inner_dims=op.inner_dims(), n_factors=op.n_factors,
        feasible=op.feasible_backends(),
    )
    assert report.backend == "dense", report
    # ...and a high-RCG operator never auto-dispatches dense
    hi = FaustOp.from_blockfaust(_chain(33, [8, 8, 8], k=1))
    assert hi.rcg > 2.0
    hi.apply(jax.random.normal(jax.random.PRNGKey(34), (16, 64)),
             backend="auto")
    assert last_report().backend != "dense", last_report()


def test_dispatch_grad_pricing_joint_fwd_bwd():
    """grad=True prices forward+backward jointly: a chain with heavy
    boundary activation traffic keeps fused ahead of bsr at fine-tuning
    batch (no wgrad spill) while huge batches tip to bsr (the f32
    partial-dvalues slabs outweigh the saved round-trips)."""
    op = FaustOp.from_blockfaust(_chain(40, [8, 8, 32], k=4, blk=128))
    kw = dict(
        shape=op.shape, dtype=jnp.float32, s_tot=op.s_tot,
        inner_dims=op.inner_dims(), n_factors=op.n_factors,
        feasible=op.feasible_backends(),
    )
    small = choose_backend(batch=128, grad=True, **kw)
    assert small.grad and small.backend == "fused", small.reason
    assert "fwd+bwd" in small.reason
    big = choose_backend(batch=4096, grad=True, **kw)
    assert big.backend == "bsr", big.reason
    # joint estimates strictly dominate the fwd-only ones
    fwd_only = choose_backend(batch=128, grad=False, **kw)
    assert not fwd_only.grad
    assert all(
        small.est_us[k] > fwd_only.est_us[k] for k in fwd_only.est_us
    )
    assert small.as_row()["grad"] is True


def test_apply_autodetects_ad_trace():
    """FaustOp.apply flips to grad pricing under jax.grad with no call-site
    change, and stays on fwd pricing for plain jit/inference."""
    op = FaustOp.from_blockfaust(_chain(41, [4, 4, 4], k=2))
    x = jax.random.normal(jax.random.PRNGKey(42), (8, op.shape[0]))
    jax.jit(lambda v: op.apply(v, use_kernel=False))(x)
    assert last_report().grad is False
    jax.grad(lambda v: op.apply(v, use_kernel=False).sum())(x)
    assert last_report().grad is True
    # explicit override wins over detection
    op.apply(x, use_kernel=False, grad=True)
    assert last_report().grad is True


def test_dispatch_adjoint_has_no_fused_path(op_block):
    assert "fused" not in op_block.T.feasible_backends()
    op_block.T.apply(
        jax.random.normal(jax.random.PRNGKey(33), (2, 64)), backend="auto"
    )
    assert last_report().backend in ("dense", "bsr")
    with pytest.raises(ValueError, match="not feasible"):
        op_block.T.apply(
            jax.random.normal(jax.random.PRNGKey(34), (2, 64)), backend="fused"
        )


# ---------------------------------------------------------------------------
# factorize routing
# ---------------------------------------------------------------------------


def test_factorize_hadamard_exact():
    a = hadamard_matrix(16)
    op, info = factorize(a, FactorizeSpec(strategy="hadamard"))
    assert isinstance(op.rep, Faust)
    assert float(op.rel_error_fro(a)) < 1e-5
    assert info.hierarchical is not None and info.strategy == "hadamard"


def test_factorize_block_route_is_canonical():
    """The block route is the single entry point (the PR-3 deprecation
    shims are gone): the returned operator, the info chains, and the layer
    bridge all agree."""
    w = jax.random.normal(jax.random.PRNGKey(40), (32, 64)) * 0.05
    spec = FactorizeSpec(n_factors=2, block=8, k_first=3, k_mid=2,
                         n_iter_two=10, n_iter_global=10)
    op, info = factorize(w, spec)
    assert isinstance(op.rep, BlockFaust)
    np.testing.assert_allclose(
        np.asarray(op.todense()),
        np.asarray(info.blockfausts[0].todense()),
        rtol=0, atol=0,
    )
    # the old entry points no longer exist anywhere
    import repro.core as core
    import repro.core.compress as compress
    import repro.layers.faust_linear as fl

    for mod, name in [
        (core, "compress_matrix"), (compress, "compress_matrix"),
        (compress, "compress_matrix_batched"),
        (fl, "from_dense"), (fl, "from_dense_batched"),
    ]:
        assert not hasattr(mod, name), f"{name} should have been removed"


def test_faust_linear_apply_backend_parity():
    """faust_linear_apply reproduces the same projection on every backend
    (the coverage the removed fuse=-kwarg tests provided, on the new
    surface)."""
    from repro.layers.faust_linear import (
        FaustSpec, faust_linear_apply, faust_linear_init,
    )
    from repro.layers.param import split_annotations

    spec = FaustSpec(n_factors=2, block=8, k=2)
    ann = faust_linear_init(jax.random.PRNGKey(7), 32, 48, spec)
    p, _ = split_annotations(ann)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 32))
    want = faust_linear_apply(p, x, spec, 32, 48, backend="bsr")
    for backend in ("fused", "dense", "auto"):
        got = faust_linear_apply(p, x, spec, 32, 48, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


def test_factorize_auto_batches_stacks():
    ws = jax.random.normal(jax.random.PRNGKey(41), (3, 32, 64)) * 0.05
    spec = FactorizeSpec(n_factors=2, block=8, k_first=3, k_mid=2,
                         n_iter_two=10, n_iter_global=10)
    op, info = factorize(ws, spec)
    assert op.kind == "block_diag" and len(info.ops) == 3
    assert info.batched
    # per-matrix parity with the sequential route
    for i in range(3):
        seq_op, _ = factorize(ws[i], spec)
        np.testing.assert_allclose(
            np.asarray(info.ops[i].todense()),
            np.asarray(seq_op.todense()),
            rtol=1e-5, atol=1e-6,
        )


def test_factorize_validation():
    a = jnp.eye(8)
    with pytest.raises(ValueError, match="strategy"):
        factorize(a, FactorizeSpec(strategy="nope"))
    with pytest.raises(ValueError, match="spec.hier .*or spec.block"):
        factorize(a, FactorizeSpec(strategy="hierarchical"))
    with pytest.raises(ValueError, match="projs and spec.dims"):
        factorize(a, FactorizeSpec(strategy="palm4msa"))
    # batched=False cannot take a stack — rejected up front, not deep in
    # the solver with a shape assertion
    with pytest.raises(ValueError, match="batched=False"):
        factorize(
            jnp.zeros((3, 8, 8)),
            FactorizeSpec(strategy="hadamard", batched=False),
        )


# ---------------------------------------------------------------------------
# jit-safety
# ---------------------------------------------------------------------------


def test_rel_errors_are_jit_safe(op_block):
    """Both diagnostics return traced Arrays (the old rel_error_spec
    eagerly called float() and broke under jit)."""
    a = op_block.todense() + 0.01
    faust = op_block.to("faust").rep
    fro, spec = jax.jit(
        lambda t: (faust.rel_error_fro(t), faust.rel_error_spec(t))
    )(a)
    assert isinstance(fro, jax.Array) and isinstance(spec, jax.Array)
    assert 0.0 <= float(spec) <= float(fro) * 10 + 1.0


def test_auto_dispatch_traces_over_faust_leaves():
    """backend='auto' on a Faust leaf must survive jit (s_tot falls back
    to the shape-based bound when the factors are tracers)."""
    faust = _dense_faust(51, [16, 16, 16])
    op = FaustOp.from_faust(faust)
    x = jax.random.normal(jax.random.PRNGKey(52), (3, 16))
    y = jax.jit(lambda o, v: o.apply(v, backend="auto"))(op, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ faust.todense()), rtol=1e-5, atol=1e-5
    )


def test_faustop_is_a_pytree(op_block):
    x = jax.random.normal(jax.random.PRNGKey(50), (4, 32))
    y = jax.jit(lambda o, v: o.apply(v, backend="fused"))(op_block, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ op_block.todense()), rtol=1e-5, atol=1e-5
    )
    leaves, treedef = jax.tree_util.tree_flatten(op_block.T)
    rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rebuilt.adjoint and rebuilt.shape == op_block.shape[::-1]


def test_pack_cache_not_poisoned_across_jits():
    """Regression: packing inside one jit trace must not cache tracers for
    the next jit (UnexpectedTracerError on main's apply_speed: the first
    auto/fused trace cached a tracer-holding PackedChain because the
    pack's concatenates bind into any active trace even with constant
    inputs)."""
    from repro.core.compress import random_block_factor

    keys = jax.random.split(jax.random.PRNGKey(50), 2)
    bf = BlockFaust(
        (random_block_factor(keys[0], 32, 32, 8, 8, 2),
         random_block_factor(keys[1], 32, 32, 8, 8, 2)),
        jnp.asarray(1.0),
    )
    op = FaustOp.wrap(bf)
    x = jax.random.normal(jax.random.PRNGKey(51), (4, 32))
    f1 = jax.jit(lambda v: op.apply(v, backend="fused", use_kernel=False))
    f2 = jax.jit(lambda v: 2.0 * op.apply(v, backend="fused", use_kernel=False))
    y1 = f1(x)  # first trace: packs under the trace — must not cache
    y2 = f2(x)  # second trace: would explode on a poisoned cache
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1), rtol=1e-6)
    # eager apply afterwards still works (and may now cache concretely)
    y3 = op.apply(x, backend="fused", use_kernel=False)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(y1), rtol=1e-6)


def _small_block_chain(seed=60):
    from repro.core.compress import random_block_factor

    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return BlockFaust(
        (random_block_factor(keys[0], 32, 32, 8, 8, 2),
         random_block_factor(keys[1], 32, 40, 8, 8, 2)),
        jnp.asarray(1.1),
    )


@pytest.mark.parametrize("staged", [True, False], ids=["jit", "eager"])
def test_auto_fused_choice_stays_fused(monkeypatch, staged):
    """Regression: an exception inside the fused path (a JAX API that no
    longer exists) was swallowed by degraded-mode dispatch, which served
    the per-factor path with source="demoted".  With degraded mode on, a
    packable chain that auto-dispatches to fused must run fused."""
    from repro.api import last_report

    monkeypatch.setenv("REPRO_DEGRADED", "1")
    op = FaustOp.wrap(_small_block_chain())
    x = jax.random.normal(jax.random.PRNGKey(61), (4, 32))
    assert op.dispatch_for(4, x.dtype).backend == "fused"

    def apply(v):
        return op.apply(v, backend="auto", use_kernel=True)

    y = jax.jit(apply)(x) if staged else apply(x)
    rep = last_report()
    assert rep.backend == "fused" and rep.source != "demoted", rep.reason
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ op.todense()), rtol=1e-5, atol=1e-5
    )


def test_dispatch_fits_one_tile_that_the_kernels_run(monkeypatch):
    """Dispatch halves the requested tile until the kernels fit, records
    it on the report, and the apply runs at exactly that tile; with
    ``grad`` the backward's footprint narrows it further."""
    from repro.kernels import chain as kchain
    from repro.kernels import chain_bwd as kbwd

    op = FaustOp.wrap(_small_block_chain())
    x = jax.random.normal(jax.random.PRNGKey(63), (16, 32))
    plan = op.chain_plan()
    monkeypatch.setattr(
        kchain, "VMEM_BUDGET_BYTES", kchain.fwd_vmem_bytes(plan, 16, 4, 4, False)
    )
    assert op.dispatch_for(16, x.dtype, bt=32).bt == 16
    y = op.apply(x, backend="fused", use_kernel=True, interpret=True, bt=32)
    assert last_report().bt == 16
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x @ op.todense()), rtol=1e-5, atol=1e-5
    )
    # a tile that does not fit is refused by the kernel, never refitted
    with pytest.raises(ValueError, match="VMEM"):
        kchain.check_fwd_bt(plan, 32, 4, 4, False)
    monkeypatch.setattr(
        kbwd, "_VMEM_BUDGET_BYTES", kbwd.bwd_vmem_bytes(plan, 8, 4, wgrad=True)
    )
    assert op.dispatch_for(16, x.dtype, bt=32, grad=True).bt == 8
    assert op.dispatch_for(16, x.dtype, bt=32).bt == 16


def test_dispatch_rules_out_fused_chain_that_cannot_fit(monkeypatch):
    """A chain whose fused kernels cannot fit VMEM at any batch tile is
    priced without ``fused`` (the reason says why) and a forced fused
    apply fails from its shapes, before anything compiles."""
    from repro.kernels import chain as kchain
    from repro.kernels import chain_bwd as kbwd

    op = FaustOp.wrap(_small_block_chain())
    x = jax.random.normal(jax.random.PRNGKey(62), (4, 32))
    plan = op.chain_plan()
    fwd_need = kchain.fwd_vmem_bytes(plan, kchain.MIN_BT, 4, 4, False)
    monkeypatch.setattr(kchain, "VMEM_BUDGET_BYTES", fwd_need - 1)
    rep = op.dispatch_for(4, x.dtype)
    assert "fused" not in rep.feasible
    assert "fused ruled out" in rep.reason
    with pytest.raises(ValueError, match="VMEM"):
        op.apply(x, backend="fused", use_kernel=True)
    # the backward has its own budget: a forward that fits but a backward
    # that does not rules fused out for training applies only
    monkeypatch.setattr(kchain, "VMEM_BUDGET_BYTES", fwd_need)
    assert op.dispatch_for(4, x.dtype).backend == "fused"
    monkeypatch.setattr(kbwd, "_VMEM_BUDGET_BYTES", 1024)
    assert "fused" not in op.dispatch_for(4, x.dtype, grad=True).feasible
    assert op.dispatch_for(4, x.dtype).backend == "fused"
