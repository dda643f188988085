"""Compile the chain kernels for a described TPU v5e at the widths the
serving path runs — nothing executes.

Interpret-mode tests run at ``blk=8`` and never meet Mosaic's tiling, SMEM
or VMEM limits.  Here each kernel is lowered with ``interpret=False`` for a
``v5e:2x2`` topology that is described, not attached, and compiled by the
installed TPU compiler; a step table that overflows SMEM or a scratch that
overflows VMEM fails here.  The reference shape is InternVL2-2B's
unembedding as a FAµST: 2048→2048→92553, ``FaustSpec(n_factors=2,
block=128, k=8)`` (5,920 steps, a ragged vocab tail of 9 columns).

The topology is described inside a module fixture, never at import, so a
test worker that does not run this file never loads the TPU library.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.compress import BlockFaust, BlockSparseFactor, ChainPlan, chain_plan
from repro.kernels.bsr_matmul import bsr_matmul
from repro.kernels.chain import META_COLS, chain_matmul
from repro.kernels.chain_bwd import chain_dgrad, chain_wgrad
from repro.kernels.chain_sharded import plan_shard, sharded_chain_apply
from repro.layers.faust_linear import FaustSpec

BLK = 128
UNEMBED = (2048, 92553, FaustSpec(n_factors=2, block=BLK, k=8))
MLP = (2048, 16384, FaustSpec(n_factors=2, block=BLK, k=4))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without the chip
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _abstract_chain(in_dim, out_dim, spec, dtype, sharding) -> BlockFaust:
    """A :class:`BlockFaust` of shape-only leaves with ``spec``'s layout."""
    dims = spec.chain_dims(in_dim, out_dim)
    factors = []
    for j in range(spec.n_factors):
        o = -(-dims[j + 1] // BLK)
        k = min(spec.k, -(-dims[j] // BLK))
        factors.append(
            BlockSparseFactor(
                _sds((o, k, BLK, BLK), dtype, sharding),
                _sds((o, k), jnp.int32, sharding),
                dims[j],
                dims[j + 1],
            )
        )
    return BlockFaust(tuple(factors), jnp.ones((), dtype))


def _plan(in_dim, out_dim, spec) -> ChainPlan:
    return chain_plan(_abstract_chain(in_dim, out_dim, spec, jnp.float32, None))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("bt", [8, 128])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_fused_forward_unembed(one_chip, dtype, bt):
    plan = _plan(*UNEMBED)
    assert plan.n_steps == 5920
    s = plan.n_steps
    _compile(
        functools.partial(chain_matmul, plan=plan, bt=bt, interpret=False),
        _sds((bt, plan.in_blocks[0] * BLK), dtype, one_chip),
        _sds((s, BLK, BLK), dtype, one_chip),
        _sds((s, META_COLS), jnp.int32, one_chip),
    )


def test_fused_forward_unembed_int8(one_chip):
    plan = _plan(*UNEMBED)
    s, bt = plan.n_steps, 128

    def fwd(x, values, meta, scales):
        return chain_matmul(
            x, values, meta, plan=plan, bt=bt, interpret=False, scales=scales
        )

    compiled = _compile(
        fwd,
        _sds((bt, plan.in_blocks[0] * BLK), jnp.bfloat16, one_chip),
        _sds((s, BLK, BLK), jnp.int8, one_chip),
        _sds((s, META_COLS), jnp.int32, one_chip),
        _sds((s, BLK), jnp.float32, one_chip),
    )
    _assert_scales_stream_in_place(compiled, s)


def _assert_scales_stream_in_place(compiled, n_steps):
    # The 1-byte codes are the point of quantizing: the f32 scale rows must
    # reach the kernel as a free reshape, never as a lane-padded HBM copy
    # (an (S, blk, 1) column would be padded 128-fold, ~4x the codes).
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < n_steps * BLK * 4, mem


@pytest.mark.parametrize("shape", [MLP, UNEMBED], ids=["mlp", "unembed"])
@pytest.mark.parametrize("kernel", ["dgrad", "wgrad"])
def test_fused_backward_f32(one_chip, kernel, shape):
    plan = _plan(*shape)
    s, bt = plan.n_steps, 128
    x = _sds((bt, plan.in_blocks[0] * BLK), jnp.float32, one_chip)
    dy = _sds((bt, plan.out_blocks[-1] * BLK), jnp.float32, one_chip)
    values = _sds((s, BLK, BLK), jnp.float32, one_chip)
    in_idx = _sds((s,), jnp.int32, one_chip)
    if kernel == "dgrad":
        fn = functools.partial(chain_dgrad, plan=plan, bt=bt, interpret=False)
        _compile(fn, dy, values, in_idx)
    else:
        fn = functools.partial(chain_wgrad, plan=plan, bt=bt, interpret=False)
        _compile(fn, x, dy, values, in_idx)


@pytest.mark.parametrize("kernel", ["dgrad", "wgrad"])
def test_fused_backward_int8(one_chip, kernel):
    plan = _plan(*MLP)
    s, bt = plan.n_steps, 128
    x = _sds((bt, plan.in_blocks[0] * BLK), jnp.float32, one_chip)
    dy = _sds((bt, plan.out_blocks[-1] * BLK), jnp.float32, one_chip)
    values = _sds((s, BLK, BLK), jnp.int8, one_chip)
    in_idx = _sds((s,), jnp.int32, one_chip)
    scales = _sds((s, BLK), jnp.float32, one_chip)
    if kernel == "dgrad":
        fn = functools.partial(chain_dgrad, plan=plan, bt=bt, interpret=False)
        compiled = _compile(lambda dy, v, i, sc: fn(dy, v, i, scales=sc), dy, values, in_idx, scales)
    else:
        fn = functools.partial(chain_wgrad, plan=plan, bt=bt, interpret=False)
        compiled = _compile(
            lambda x, dy, v, i, sc: fn(x, dy, v, i, scales=sc), x, dy, values, in_idx, scales
        )
    _assert_scales_stream_in_place(compiled, s)


def test_bsr_matmul_unembed_last_factor(one_chip):
    plan = _plan(*UNEMBED)
    o, k, bt = plan.out_blocks[-1], plan.k_blocks[-1], 128
    _compile(
        functools.partial(bsr_matmul, bt=bt, interpret=False),
        _sds((bt, plan.in_blocks[-1] * BLK), jnp.bfloat16, one_chip),
        _sds((o, k, BLK, BLK), jnp.bfloat16, one_chip),
        _sds((o, k), jnp.int32, one_chip),
    )


def test_sharded_chain_unembed_4_devices(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    bf = _abstract_chain(*UNEMBED, jnp.bfloat16, NamedSharding(mesh, P("model")))
    flat, treedef = jax.tree_util.tree_flatten(bf)

    def apply(x, *flat):
        chain = jax.tree_util.tree_unflatten(treedef, flat)
        shard_plan = plan_shard(chain, mesh)
        assert shard_plan.mode == "model", shard_plan.reason
        return sharded_chain_apply(
            x, chain, mesh, plan=shard_plan, use_kernel=True, bt=128,
            interpret=False,
        )

    x = _sds((128, UNEMBED[0]), jnp.bfloat16, NamedSharding(mesh, P()))
    compiled = _compile(apply, x, *flat)
    assert "all-gather" in compiled.as_text()


def test_fused_apply_names_the_chain_kernel(one_chip):
    """``FaustOp.apply(..., backend="fused")`` reaches the chain kernel
    through the ``custom_vjp`` of ``kernels/ops.py``; the kernel keeps its
    own name there, so a device trace names its events ``%faust_chain_fwd``
    whatever jitted program encloses it."""
    from repro.api import FaustOp

    bf = _abstract_chain(*MLP, jnp.bfloat16, one_chip)
    flat, treedef = jax.tree_util.tree_flatten(bf)

    def apply(x, *flat):
        op = FaustOp.from_blockfaust(jax.tree_util.tree_unflatten(treedef, flat))
        return op.apply(x, backend="fused", use_kernel=True, bt=128, interpret=False)

    x = _sds((128, MLP[0]), jnp.bfloat16, one_chip)
    text = _compile(apply, x, *flat).as_text()
    calls = re.findall(r"^\s*(%faust_chain_fwd[\w.]* = .*custom-call\(.*)$", text, re.M)
    assert calls, "no %faust_chain_fwd instruction in the compiled program"
    assert all('custom_call_target="tpu_custom_call"' in c for c in calls)
