"""Sharded step builders shared by the dry-run, train and serve launchers.

Everything is built from abstract shapes — nothing allocates until a real
launcher feeds device arrays.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ArchConfig, SHAPES, ShapeCell
from repro.distributed import sharding as shd
from repro.distributed.sharding import ShardingPolicy, _fit_axes
from repro.layers.attention import KVCache
from repro.layers.mamba2 import MambaCache
from repro.models import lm
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig, AdamWState


def param_shardings(cfg: ArchConfig, mesh: Mesh):
    axes = lm.param_axes(cfg)
    ap = lm.abstract_params(cfg)
    pspecs = shd.resolve_param_pspecs(axes, ap, mesh, cfg.policy)
    return shd.tree_named_sharding(pspecs, mesh)


def batch_shardings(cfg: ArchConfig, mesh: Mesh, policy: ShardingPolicy, batch: dict):
    def one(spec_leaf):
        bax = _fit_axes(policy.batch, spec_leaf.shape[0], mesh)
        return NamedSharding(
            mesh, PartitionSpec(bax, *([None] * (len(spec_leaf.shape) - 1)))
        )

    return jax.tree_util.tree_map(one, batch)


def cache_shardings(cfg: ArchConfig, mesh: Mesh, policy: ShardingPolicy, cell: ShapeCell):
    """PartitionSpecs matching make_caches' structure (stage/unit nesting)."""
    b = cell.global_batch

    def kv_sharding(cap: int):
        # stacked cache layout: (layers, B, KH, capacity, D)
        bax = _fit_axes(policy.batch, b, mesh)
        sax = _fit_axes(policy.kv_seq, cap, mesh)
        kv = NamedSharding(mesh, PartitionSpec(None, bax, None, sax, None))
        pos = NamedSharding(mesh, PartitionSpec(None, bax))  # (layers, B)
        return KVCache(kv, kv, pos)

    def mamba_sharding():
        bax = _fit_axes(policy.batch, b, mesh)
        hax = _fit_axes("model", cfg.ssm.n_heads, mesh) if cfg.ssm else None
        conv = NamedSharding(mesh, PartitionSpec(None, bax, None, None))
        ssm = NamedSharding(mesh, PartitionSpec(None, bax, hax, None, None))
        pos = NamedSharding(mesh, PartitionSpec(None, bax))  # (layers, B)
        return MambaCache(conv, ssm, pos)

    stages = []
    for repeat, unit in cfg.stages:
        stage = []
        for kind in unit:
            if kind in ("ssm", "ssm_mlp"):
                stage.append(mamba_sharding())
            else:
                cap = cell.seq_len
                if kind == "local" and cfg.window is not None:
                    cap = min(cfg.window, cell.seq_len)
                stage.append(kv_sharding(cap))
        stages.append(stage)
    return stages


def abstract_train_state(cfg: ArchConfig, opt_cfg: AdamWConfig):
    def build(key):
        params = lm.init_model(key, cfg)
        return {"params": params, "opt": adamw.init_state(params)}

    return jax.eval_shape(build, jax.random.PRNGKey(0))


def make_sharded_train_step(cfg: ArchConfig, opt_cfg: AdamWConfig, mesh: Mesh):
    """Production train step (loss+grads+AdamW+NaN-guard), jit w/ shardings."""
    from repro.runtime.trainer import TrainConfig, make_train_step

    return make_train_step(cfg, opt_cfg, TrainConfig(), mesh)


def make_sharded_prefill(cfg: ArchConfig, mesh: Mesh, cell: ShapeCell):
    policy = cfg.policy  # prefill compute = train-like sharding
    dec_policy = cfg.decode_policy()

    def fn(params, batch, caches):
        with shd.use_rules(mesh, policy):
            return lm.prefill(params, cfg, batch, caches)

    param_sh = param_shardings(cfg, mesh)
    cache_sh = cache_shardings(cfg, mesh, dec_policy, cell)
    return jax.jit(
        fn,
        in_shardings=(param_sh, None, cache_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=2,
    )


def make_sharded_decode(cfg: ArchConfig, mesh: Mesh, cell: ShapeCell):
    policy = cfg.decode_policy()

    def fn(params, tokens, caches):
        with shd.use_rules(mesh, policy):
            return lm.decode_step(params, cfg, tokens, caches)

    param_sh = param_shardings(cfg, mesh)
    cache_sh = cache_shardings(cfg, mesh, policy, cell)
    bax = _fit_axes(policy.batch, cell.global_batch, mesh)
    tok_sh = NamedSharding(
        mesh,
        PartitionSpec(bax, *( [None] * (1 if cfg.n_codebooks == 1 else 2) )),
    )
    return jax.jit(
        fn,
        in_shardings=(param_sh, tok_sh, cache_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=2,
    )
