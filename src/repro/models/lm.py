"""Unified decoder LM covering all 11 assigned architectures.

The per-layer structure is described by ``cfg.stages`` — (repeat, unit)
pairs where a *unit* is a tuple of layer kinds executed inside one
``lax.scan`` step (so gemma3's 5:1 local:global pattern and zamba2's
mamba+shared-block pattern scan over their periodic repeat units, keeping
the HLO small at 62–81 layers).

Layer kinds: "attn" (global attention + FFN), "local" (sliding window +
FFN), "moe" (attention + MoE), "ssm" (mamba2), "ssm_mlp" (a mamba2 block
then an FFN block, each with its own norm and residual: nemotron-h's
``M-`` pair), "shared" (zamba2's shared transformer block — parameters
live outside the scan and are reused; each occurrence still owns its KV
cache).

Entry points:
  init_model / param_axes      — parameters (+ logical sharding axes)
  train_loss                   — next-token CE (+ MoE aux), fp32 logits
  prefill / decode_step        — serving path with per-layer caches
  decode_delta / scatter_cache_slots — decode that reads the caches and
                                 the write of its update (the slot pool)
  make_caches                  — cache pytree (abstract-init friendly)

Modality frontends (per spec, stubs): "vlm" consumes precomputed patch
embeddings replacing the first ``n_vision_tokens`` positions; "audio"
consumes ``n_codebooks`` parallel token streams (summed embeddings,
parallel unembed heads).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard_act
from repro.layers import attention as A
from repro.layers import mamba2 as M
from repro.layers import moe as MOE
from repro.layers.embedding import embedding_init, unembed_apply, unembed_init
from repro.layers.mlp import mlp_apply, mlp_init
from repro.layers.norms import apply_norm, norm_init
from repro.layers.param import Annotated, annotate, split_annotations, stack_annotated

Array = jax.Array


# ---------------------------------------------------------------------------
# Specs derived from config
# ---------------------------------------------------------------------------


def attn_spec(cfg: ArchConfig, kind: str) -> A.AttnSpec:
    local = kind == "local"
    rotary_dim = int(cfg.head_dim * cfg.rotary_pct)
    if rotary_dim % 2:
        rotary_dim -= 1
    return A.AttnSpec(
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        rope_base=(cfg.rope_base_local or cfg.rope_base) if local else cfg.rope_base,
        rotary_dim=rotary_dim if cfg.rotary_pct < 1.0 else None,
        window=cfg.window if local else None,
        qk_norm=cfg.qk_norm,
        scale=cfg.attn_scale,
        use_rope=cfg.rotary_pct > 0.0,
    )


def _dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_init(key: jax.Array, cfg: ArchConfig, kind: str) -> dict:
    dt = _dtype(cfg)
    d = cfg.d_model
    if kind in ("ssm", "ssm_mlp"):
        k1, k2 = jax.random.split(key)
        p = {
            "norm": norm_init(cfg.norm, d, dt),
            "mamba": M.mamba2_init(k1, cfg.ssm, dt),
        }
        if kind == "ssm_mlp":
            p["norm2"] = norm_init(cfg.norm, d, dt)
            p["mlp"] = mlp_init(k2, d, cfg.d_ff, cfg.act, dt, faust=cfg.faust_mlp)
        return p
    if kind == "shared":
        return {}  # params live outside the scan
    ks = jax.random.split(key, 4)
    p = {
        "norm1": norm_init(cfg.norm, d, dt),
        "attn": A.attn_init(
            ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.qk_norm, dt
        ),
        "norm2": norm_init(cfg.norm, d, dt),
    }
    if kind == "moe":
        p["moe"] = MOE.moe_init(ks[1], d, cfg.moe, dt)
    else:
        p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.act, dt, faust=cfg.faust_mlp)
    return p


def _init_annotated(key: jax.Array, cfg: ArchConfig):
    dt = _dtype(cfg)
    keys = jax.random.split(key, 8)
    p: dict[str, Any] = {}
    if cfg.n_codebooks > 1:
        tabs = [
            embedding_init(k, cfg.vocab, cfg.d_model, dt)
            for k in jax.random.split(keys[0], cfg.n_codebooks)
        ]
        p["embed"] = stack_annotated(tabs)
    else:
        p["embed"] = embedding_init(keys[0], cfg.vocab, cfg.d_model, dt)

    stages = []
    lkeys = jax.random.split(keys[1], len(cfg.stages))
    for (repeat, unit), skey in zip(cfg.stages, lkeys):
        ukeys = jax.random.split(skey, len(unit))
        stage = []
        for pos, kind in enumerate(unit):
            per_layer = [
                _layer_init(k, cfg, kind)
                for k in jax.random.split(ukeys[pos], repeat)
            ]
            stage.append(stack_annotated(per_layer))
        stages.append(stage)
    p["stages"] = stages

    if any(k == "shared" for k in cfg.layer_kinds()):
        p["shared"] = _layer_init(keys[2], cfg, "attn")

    p["final_norm"] = norm_init(cfg.norm, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks > 1:
            heads = [
                unembed_init(k, cfg.d_model, cfg.vocab, cfg.faust_unembed, dt)
                for k in jax.random.split(keys[3], cfg.n_codebooks)
            ]
            p["unembed"] = stack_annotated(heads)
        else:
            p["unembed"] = unembed_init(
                keys[3], cfg.d_model, cfg.vocab, cfg.faust_unembed, dt
            )
    return p


def init_model(key: jax.Array, cfg: ArchConfig):
    params, _ = split_annotations(_init_annotated(key, cfg))
    return params


def param_axes(cfg: ArchConfig):
    ann = jax.eval_shape(functools.partial(_init_annotated, cfg=cfg), jax.random.PRNGKey(0))
    _, axes = split_annotations(ann)
    return axes


def abstract_params(cfg: ArchConfig):
    ann = jax.eval_shape(functools.partial(_init_annotated, cfg=cfg), jax.random.PRNGKey(0))
    params, _ = split_annotations(ann)
    return params


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def _layer_cache(cfg: ArchConfig, kind: str, batch: int, cache_len: int, dtype):
    if kind in ("ssm", "ssm_mlp"):
        return M.mamba_cache_init(batch, cfg.ssm, dtype)
    cap = cache_len
    if kind == "local" and cfg.window is not None:
        cap = min(cfg.window, cache_len)
    return A.kv_cache_init(batch, cap, cfg.n_kv_heads, cfg.head_dim, dtype)


def make_caches(cfg: ArchConfig, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """Every layer's cache, stacked over its stage's repeat.  Each cache
    starts as zeros, so each leaf is allocated stacked at once: stacking
    per-layer arrays eagerly held the whole pool three times over."""

    def stacked(repeat, kind):
        one = jax.eval_shape(lambda: _layer_cache(cfg, kind, batch, cache_len, dtype))
        return jax.tree_util.tree_map(lambda a: jnp.zeros((repeat,) + a.shape, a.dtype), one)

    return [[stacked(repeat, kind) for kind in unit] for repeat, unit in cfg.stages]


# Every cache leaf is stacked over the scan repeat (axis 0), so the batch
# dim — the serving engine's *slot* dim — is axis 1 uniformly: KVCache.k
# (repeat, B, KH, cap, D), KVCache.pos (repeat, B), MambaCache.ssm
# (repeat, B, H, P, N), …  The slot-paged pool (runtime/engine.py) keeps
# one make_caches(cfg, n_slots, max_len) pytree alive and decodes all of
# its rows where they lie: decode_delta reads the pool, and
# scatter_cache_slots writes the step's update into the live rows.
_CACHE_BATCH_AXIS = 1


def scatter_cache_slots(pool, update, slot_idx: Array):
    """Write one decode step's ``update`` (:func:`decode_delta` over
    ``pool``) into pool rows ``slot_idx (M,)``, in place when ``pool`` is
    donated: each listed row's new K/V entry at its ``pos % capacity``
    and its ``pos`` advanced; Mamba rows take their whole new state.
    Indices past the pool's rows are dropped, so a fixed-width
    ``slot_idx`` lists the live rows and pads; rows not listed keep their
    cache and ``pos`` bit for bit."""
    n = _first_cache_pos(pool).shape[0]
    live = jnp.zeros((n,), bool).at[slot_idx].set(True, mode="drop")

    def write(cache, upd):
        if isinstance(cache, A.KVCache):  # upd: KVEntry, stacked over repeat
            return A.kv_cache_write(cache, upd, live)
        return jax.tree_util.tree_map(  # whole states, stacked over repeat
            lambda p, u: jnp.where(live.reshape((1, n) + (1,) * (p.ndim - 2)), u, p),
            cache,
            upd,
        )

    return jax.tree_util.tree_map(
        write, pool, update, is_leaf=lambda c: isinstance(c, (A.KVCache, M.MambaCache))
    )


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


class _Mode:
    TRAIN = "train"
    PREFILL = "prefill"
    DECODE = "decode"


def _apply_layer(
    cfg: ArchConfig,
    kind: str,
    lp: dict,
    shared_params: dict | None,
    x: Array,
    aux: Array,
    mode: str,
    cache,
):
    chunk = cfg.attn_chunk
    if kind in ("ssm", "ssm_mlp"):
        h = apply_norm(cfg.norm, lp["norm"], x)
        if mode == _Mode.TRAIN:
            y, new_cache = M.mamba2_apply(lp["mamba"], h, cfg.ssm, None, False)
        elif mode == _Mode.PREFILL:
            y, new_cache = M.mamba2_apply(lp["mamba"], h, cfg.ssm, cache, False)
        else:
            y, new_cache = M.mamba2_apply(lp["mamba"], h, cfg.ssm, cache, True)
        x = x + y.astype(x.dtype)
        if kind == "ssm_mlp":
            x = x + _mlp(cfg, lp, x)
        return x, aux, new_cache

    if kind == "shared":
        lp = shared_params
    spec = attn_spec(cfg, kind)
    h = apply_norm(cfg.norm, lp["norm1"], x)
    h = shard_act(h, "batch", "seq", None)
    if mode == _Mode.TRAIN:
        y = A.attn_train(lp["attn"], h, spec, chunk)
        new_cache = cache
    elif mode == _Mode.PREFILL:
        y, new_cache = A.attn_prefill(lp["attn"], h, spec, cache, chunk)
    else:
        y, new_cache = A.attn_decode_entry(lp["attn"], h, spec, cache)
    x = x + shard_act(y.astype(x.dtype), "batch", "seq", None)

    if kind == "moe":
        h = apply_norm(cfg.norm, lp["norm2"], x)
        # Optionally gather the sequence dim at the MoE
        # boundary — routing sorts and the (B,E,C,·) expert einsums otherwise
        # conflict with context-parallel seq sharding and XLA partial-sum
        # all-reduces expert-activation-sized tensors per layer. Helps ff-TP
        # experts (granite); hurts EP experts (llama4) — policy-selected.
        if cfg.policy.moe_gather_seq:
            h = shard_act(h, "batch", None, None)
        y, layer_aux = MOE.moe_apply(lp["moe"], h, cfg.moe)
        aux = aux + layer_aux
        x = x + shard_act(y.astype(x.dtype), "batch", "seq", None)
        return x, aux, new_cache
    return x + _mlp(cfg, lp, x), aux, new_cache


def _mlp(cfg: ArchConfig, lp: dict, x: Array) -> Array:
    """The FFN block's residual branch: ``mlp(norm2(x))``."""
    h = apply_norm(cfg.norm, lp["norm2"], x)
    y = mlp_apply(
        lp["mlp"], h, cfg.act,
        faust=cfg.faust_mlp, d_model=cfg.d_model, d_ff=cfg.d_ff,
    )
    return shard_act(y.astype(x.dtype), "batch", "seq", None)


def _run_stages(params, cfg: ArchConfig, x: Array, mode: str, caches):
    """Scan every stage; returns (x, aux, new_caches).  In decode mode the
    caches are read only and each layer emits what the step changes (the
    ``update`` of :func:`decode_delta`) in place of its cache."""
    aux = jnp.zeros((), jnp.float32)
    shared = params.get("shared")
    new_caches = []
    for si, (repeat, unit) in enumerate(cfg.stages):
        stage_params = params["stages"][si]
        stage_caches = caches[si] if caches is not None else [None] * len(unit)

        def unit_body(carry, xs):
            x, aux = carry
            lps, lcs = xs
            ncs = []
            for pos, kind in enumerate(unit):
                x, aux, nc = _apply_layer(
                    cfg, kind, lps[pos], shared, x, aux, mode, lcs[pos]
                )
                ncs.append(nc)
            return (x, aux), ncs

        body = unit_body
        if cfg.remat and mode == _Mode.TRAIN:
            body = jax.checkpoint(unit_body, prevent_cse=False)

        xs = (stage_params, stage_caches)
        (x, aux), ncs = jax.lax.scan(body, (x, aux), xs)
        new_caches.append(ncs)
    return x, aux, new_caches


def _embed_tokens(params, cfg: ArchConfig, tokens: Array, pos0) -> Array:
    dt = _dtype(cfg)
    if cfg.n_codebooks > 1:
        # tokens (B, K, S): sum codebook embeddings x[b,s] = Σ_k T[k, tok[b,k,s]]
        tabs = params["embed"]["table"]  # (K, V, d)
        kidx = jnp.arange(cfg.n_codebooks)[None, :, None]
        x = jnp.sum(tabs[kidx, tokens], axis=1).astype(dt)  # (B,S,d)
        # sinusoidal positions (musicgen has no rope); pos0 may be a
        # per-row (B,) vector — slot-paged decode steps rows at
        # independent positions — or a scalar (train/prefill from 0)
        s = tokens.shape[-1]
        pos = jnp.asarray(pos0)[..., None] + jnp.arange(s)  # (S,) or (B,S)
        half = cfg.d_model // 2
        freq = jnp.exp(-np.log(10000.0) * jnp.arange(half) / half)
        ang = pos[..., :, None] * freq  # (..., S, half)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = x + (pe if pe.ndim == 3 else pe[None]).astype(dt)
        return x
    x = params["embed"]["table"][tokens].astype(dt)
    if cfg.scale_embed:
        x = x * float(np.sqrt(cfg.d_model))  # weak-typed: stays in dt
    return x


def _logits(params, cfg: ArchConfig, x: Array) -> Array:
    tied = params["embed"]["table"] if cfg.tie_embeddings else None
    if cfg.n_codebooks > 1:
        outs = []
        for k in range(cfg.n_codebooks):
            head = jax.tree_util.tree_map(lambda t: t[k], params["unembed"])
            outs.append(
                unembed_apply(head, x, cfg.d_model, cfg.vocab, cfg.faust_unembed)
            )
        return jnp.stack(outs, axis=-2).astype(jnp.float32)  # (B,S,K,V)
    logits = unembed_apply(
        params["unembed"] if not cfg.tie_embeddings else None,
        x,
        cfg.d_model,
        cfg.vocab,
        cfg.faust_unembed,
        tied_table=tied,
    )
    return logits.astype(jnp.float32)


def forward_train(params, cfg: ArchConfig, batch: dict) -> tuple[Array, Array]:
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, tokens, 0)
    if cfg.n_vision_tokens:
        nv = cfg.n_vision_tokens
        ve = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([ve, x[:, nv:]], axis=1)
    x = shard_act(x, "batch", "seq", None)
    x, aux, _ = _run_stages(params, cfg, x, _Mode.TRAIN, None)
    x = apply_norm(cfg.norm, params["final_norm"], x)
    return _logits(params, cfg, x), aux


def train_loss(params, cfg: ArchConfig, batch: dict) -> tuple[Array, dict]:
    logits, aux = forward_train(params, cfg, batch)
    tokens = batch["tokens"]
    if cfg.n_codebooks > 1:
        labels = tokens[:, :, 1:]  # (B,K,S-1)
        lg = logits[:, :-1].transpose(0, 2, 1, 3)  # (B,K,S-1,V)
    else:
        labels = tokens[:, 1:]
        lg = logits[:, :-1]
    lg = shard_act(lg, *(("batch",) + (None,) * (lg.ndim - 2) + ("vocab_act",)))
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    ce = jnp.mean(lse - gold)
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux": aux}


def prefill(params, cfg: ArchConfig, batch: dict, caches):
    tokens = batch["tokens"]
    x = _embed_tokens(params, cfg, tokens, 0)
    if cfg.n_vision_tokens:
        nv = cfg.n_vision_tokens
        ve = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([ve, x[:, nv:]], axis=1)
    x = shard_act(x, "batch", "seq", None)
    x, _, new_caches = _run_stages(params, cfg, x, _Mode.PREFILL, caches)
    x = apply_norm(cfg.norm, params["final_norm"], x)
    logits = _logits(params, cfg, x[:, -1:])
    return logits, new_caches


def decode_delta(params, cfg: ArchConfig, tokens: Array, caches):
    """One decode step that reads ``caches`` and leaves them as they are.

    tokens: (B,1) (or (B,K,1) audio).  Returns ``(logits, update)``:
    ``update`` mirrors ``caches`` layer by layer, holding for attention
    layers the new token's :class:`~repro.layers.attention.KVEntry`
    (repeat, B, KH, 1, D) and for Mamba layers the whole new state, which
    is rewritten every step — what :func:`scatter_cache_slots` writes."""
    pos0 = _first_cache_pos(caches)
    x = _embed_tokens(params, cfg, tokens, pos0)
    x = shard_act(x, "batch", None, None)
    x, _, update = _run_stages(params, cfg, x, _Mode.DECODE, caches)
    x = apply_norm(cfg.norm, params["final_norm"], x)
    return _logits(params, cfg, x), update


def decode_step(params, cfg: ArchConfig, tokens: Array, caches):
    """tokens: (B,1) (or (B,K,1) audio). Returns (logits, new_caches)."""
    logits, update = decode_delta(params, cfg, tokens, caches)
    rows = jnp.arange(tokens.shape[0])
    return logits, scatter_cache_slots(caches, update, rows)


def _first_cache_pos(caches) -> Array:
    first = caches[0][0]
    return first.pos[0]  # stacked over repeat → per-row (B,)


def greedy_token(logits: Array) -> Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
