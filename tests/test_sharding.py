"""Sharding metadata layer: ``_fit_axes`` / ``resolve_param_pspecs`` edge
cases, the sharded-chain planner, and the collective-aware dispatch model.

Everything here is *planning* — pure functions of shapes and mesh
metadata — so it runs on a single bare-CPU device via ``AbstractMesh``
(no host-device-count override needed).  The execution-side parity tests
live in ``tests/test_sharded_apply.py`` behind the multi-device CI leg.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.api import choose_backend
from repro.core.compress import BlockFaust, BlockSparseFactor, random_block_factor
from repro.distributed.sharding import (
    ShardingPolicy,
    _fit_axes,
    resolve_param_pspecs,
)
from repro.kernels import chain_sharded as cs

jax.config.update("jax_platform_name", "cpu")

MESH = AbstractMesh((2, 4), ("data", "model"))


# ---------------------------------------------------------------------------
# _fit_axes
# ---------------------------------------------------------------------------


def test_fit_axes_none_passthrough():
    assert _fit_axes(None, 16, MESH) is None


def test_fit_axes_divides():
    assert _fit_axes("model", 16, MESH) == "model"
    assert _fit_axes(("data", "model"), 16, MESH) == ("data", "model")


def test_fit_axes_non_dividing_replicates():
    # 6 % 4 != 0 → replicate rather than error (DESIGN.md §6 fallback)
    assert _fit_axes("model", 6, MESH) is None
    # the *product* must divide, even if each axis alone would
    assert _fit_axes(("data", "model"), 4, MESH) is None


def test_fit_axes_absent_axis_dropped():
    assert _fit_axes("pod", 16, MESH) is None
    # absent axes are dropped, surviving ones keep working
    assert _fit_axes(("pod", "model"), 16, MESH) == "model"


def test_fit_axes_single_axis_unwrapped():
    # a 1-tuple comes back as the bare axis name (PartitionSpec idiom)
    assert _fit_axes(("model",), 16, MESH) == "model"


# ---------------------------------------------------------------------------
# resolve_param_pspecs
# ---------------------------------------------------------------------------


def _specs(axes_tree, shape_tree, policy=None):
    policy = policy or ShardingPolicy()
    shapes = jax.tree_util.tree_map(
        lambda s: np.zeros(s, dtype=np.float32), shape_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )
    return resolve_param_pspecs(axes_tree, shapes, MESH, policy)


def test_resolve_pspecs_basic():
    got = _specs({"w": ("embed", "mlp")}, {"w": (8, 16)})
    assert got["w"] == P("data", "model")


def test_resolve_pspecs_non_dividing_dim_replicates():
    # mlp → 'model' (4-way) but dim 6 doesn't divide → that dim replicated
    got = _specs({"w": ("embed", "mlp")}, {"w": (8, 6)})
    assert got["w"] == P("data", None)


def test_resolve_pspecs_duplicate_mesh_axis_first_wins():
    # both logical axes map to 'model'; a mesh axis may appear at most once
    # per spec, so the second occurrence is dropped
    got = _specs({"w": ("mlp", "vocab")}, {"w": (16, 16)})
    assert got["w"] == P("model", None)


def test_resolve_pspecs_absent_logical_and_none_axes():
    got = _specs({"w": ("heads", None)}, {"w": (8, 16)})
    # 'heads' maps to None in the default policy; None name is None
    assert got["w"] == P(None, None)


def test_resolve_pspecs_none_axes_tree_fully_replicated():
    got = _specs({"w": None}, {"w": (8, 16)})
    assert got["w"] == P()


# ---------------------------------------------------------------------------
# chain_sharded planning
# ---------------------------------------------------------------------------


def _chain(seed=0, nblocks=(4, 4, 4), blk=8, k=2, feats=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(nblocks) - 1)
    factors = []
    for i in range(len(nblocks) - 1):
        f = random_block_factor(
            keys[i],
            (feats[i] if feats else nblocks[i] * blk),
            (feats[i + 1] if feats else nblocks[i + 1] * blk),
            blk, blk, k,
        )
        factors.append(f)
    return BlockFaust(tuple(factors), jnp.asarray(1.0, jnp.float32))


def _local_support_chain(nb=8, blk=8, k=2, n_model=4, seed=3):
    """Every out-block gathers only in-blocks of its own model shard —
    the butterfly-stage layout that needs zero collectives."""
    per = nb // n_model
    rng = np.random.default_rng(seed)
    factors = []
    for _ in range(2):
        idx = np.stack([
            np.sort(rng.choice(per, size=min(k, per), replace=False))
            + (o // per) * per
            for o in range(nb)
        ]).astype(np.int32)
        vals = rng.normal(size=(nb, min(k, per), blk, blk)).astype(np.float32)
        factors.append(
            BlockSparseFactor(jnp.asarray(vals), jnp.asarray(idx),
                              nb * blk, nb * blk)
        )
    return BlockFaust(tuple(factors), jnp.asarray(1.0, jnp.float32))


def test_plan_model_mode_crossing():
    bf = _chain()  # random supports: boundaries cross shards
    plan = cs.plan_shard(bf, MESH)
    assert plan.mode == "model"
    assert plan.n_model == 4 and plan.n_data == 2
    assert len(plan.segments) == 2  # one all-gather at the crossing boundary
    assert plan.segments[0].gather_in is False
    assert plan.segments[1].gather_in is True
    assert plan.crossing_feats == (32,)
    # local plans: 4 out-blocks over 4 shards → 1 out-block per shard
    assert plan.segments[0].plan.out_blocks == (1,)
    assert plan.segments[0].plan.in_blocks == (4,)  # replicated x input
    assert plan.segments[1].plan.in_blocks == (4,)  # gathered activation


def test_plan_local_support_no_collectives():
    bf = _local_support_chain()
    plan = cs.plan_shard(bf, MESH)
    assert plan.mode == "model"
    assert len(plan.segments) == 1  # whole chain fused, zero collectives
    assert plan.crossing_feats == ()
    assert plan.collective_bytes(batch=64, itemsize=4) == 0


def test_plan_non_dividing_blocks_fall_back_replicated():
    bf = _chain(nblocks=(3, 3, 3))  # 3 out-blocks over 4 model shards
    plan = cs.plan_shard(bf, MESH)
    assert plan.mode == "replicated"
    assert "do not divide" in plan.reason
    assert plan.n_batch_shards == 8  # batch spreads over both axes


def test_plan_ragged_falls_back_replicated():
    bf = _chain(nblocks=(4, 4, 4), feats=(32, 28, 32))  # ragged inner dim
    plan = cs.plan_shard(bf, MESH)
    assert plan.mode == "replicated"
    assert "ragged" in plan.reason


def test_plan_ragged_last_factor_stays_model_sharded():
    # a vocabulary-style ragged *output* width is sliced off after the
    # apply, so the per-shard step tables stay identical
    bf = _chain(nblocks=(4, 4, 4), feats=(32, 32, 30))
    plan = cs.plan_shard(bf, MESH)
    assert plan.mode == "model", plan.reason
    assert plan.segments[-1].plan.out_feats[-1] == 8  # one full local block


def test_plan_no_model_axis_falls_back():
    mesh = AbstractMesh((2,), ("data",))
    plan = cs.plan_shard(_chain(), mesh)
    assert plan.mode == "replicated"
    assert plan.n_model == 1 and plan.n_batch_shards == 2


def test_plan_collective_bytes_accounting():
    bf = _chain()
    plan = cs.plan_shard(bf, MESH)
    # one gathered boundary, width 32, f32: each shard receives 3/4 of
    # b_loc×32 elements — b=64 over 2 data shards → b_loc=32
    want = int(4 * 32 * 32 * 3 / 4)
    assert plan.collective_bytes(batch=64, itemsize=4) == want


# ---------------------------------------------------------------------------
# dispatch: collective-aware cost model
# ---------------------------------------------------------------------------


def _shard_summary(mode="model", crossing=(4096,), n_segments=2):
    return {
        "mode": mode,
        "n_data": 2,
        "n_model": 4,
        "n_segments": n_segments,
        "crossing_feats": crossing,
        "mesh_shape": (("data", 2), ("model", 4)),
        "reason": "test",
    }


def test_dispatch_selects_fused_sharded_at_scale():
    # big weight traffic, one narrow crossing boundary: the per-shard
    # weight-streaming win dwarfs the ICI term
    rep = choose_backend(
        batch=256, shape=(8192, 8192), dtype=jnp.float32,
        s_tot=2 * 64 * 16 * 128 * 128, inner_dims=(8192,), n_factors=2,
        feasible=("dense", "bsr", "fused", "fused_sharded"),
        shard=_shard_summary(crossing=(8192,)),
    )
    assert rep.backend == "fused_sharded"
    assert rep.collective_bytes > 0
    assert rep.mesh_shape == (("data", 2), ("model", 4))
    row = rep.as_row()
    assert row["mesh_shape"] == {"data": 2, "model": 4}
    assert row["collective_bytes"] == rep.collective_bytes


def test_dispatch_prefers_single_device_when_collectives_dominate():
    # tiny batch, every boundary crossing: launches + ICI outweigh the
    # per-shard roofline savings → stay on the single-device fused path
    rep = choose_backend(
        batch=4, shape=(256, 256), dtype=jnp.float32,
        s_tot=4 * 256 * 8, inner_dims=(256, 256), n_factors=3,
        feasible=("dense", "bsr", "fused", "fused_sharded"),
        shard=_shard_summary(crossing=(256, 256), n_segments=3),
    )
    assert rep.backend == "fused"
    assert "fused_sharded" in rep.est_us
    assert rep.est_us["fused"] <= rep.est_us["fused_sharded"]


def test_dispatch_no_shard_no_mesh_fields():
    rep = choose_backend(
        batch=8, shape=(64, 64), dtype=jnp.float32, s_tot=1024,
        feasible=("dense", "bsr", "fused"),
    )
    assert rep.mesh_shape is None and rep.collective_bytes == 0
    assert "mesh_shape" not in rep.as_row()


def test_dispatch_replicated_mode_has_no_collectives():
    rep = choose_backend(
        batch=512, shape=(1024, 1024), dtype=jnp.float32,
        s_tot=1024 * 64, inner_dims=(1024,), n_factors=2,
        feasible=("dense", "bsr", "fused", "fused_sharded"),
        shard=_shard_summary(mode="replicated", crossing=(), n_segments=1),
    )
    assert rep.collective_bytes == 0
    assert "fused_sharded" in rep.est_us


def test_dispatch_non_fusable_fallback_priced_per_factor():
    """A non-fusable chain's replicated fallback really runs one launch per
    factor with boundary round-trips — the model must not price it as one
    fused launch (it would displace bsr on false pretenses)."""
    kw = dict(batch=64, shape=(512, 512), dtype=jnp.float32,
              s_tot=512 * 64, inner_dims=(512, 512), n_factors=3,
              feasible=("dense", "bsr", "fused_sharded"))
    base = _shard_summary(mode="replicated", crossing=(), n_segments=3)
    rep = choose_backend(**kw, shard={**base, "fusable": False})
    rep_fused = choose_backend(**kw, shard={**base, "fusable": True,
                                            "n_segments": 1})
    assert rep.est_us["fused_sharded"] > rep_fused.est_us["fused_sharded"]


def test_plan_non_fusable_replicated_launch_count():
    # non-uniform block sizes: not packable → per-factor fallback, J launches
    f1 = random_block_factor(jax.random.PRNGKey(0), 32, 32, 8, 8, 2)
    f2 = random_block_factor(jax.random.PRNGKey(1), 32, 32, 16, 16, 2)
    bf = BlockFaust((f1, f2), jnp.asarray(1.0, jnp.float32))
    plan = cs.plan_shard(bf, MESH)
    assert plan.mode == "replicated" and not plan.fusable
    assert plan.n_launches == 2
    assert "non-fusable" in plan.reason
    assert plan.summary()["n_segments"] == 2
