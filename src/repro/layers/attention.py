"""Attention: GQA/MQA projections, chunked-flash reference attention,
banded sliding-window attention, decode with (ring) KV caches.

All softmax math runs in fp32 with running-max/sum chunking (the memory
shape that makes prefill_32k representable and that a TPU flash kernel
would stream); local layers use a *banded* kv gather so sliding-window
attention is O(S·window), not O(S²) — both choices feed honest FLOP/byte
counts into the roofline.

Sequence sharding (context parallelism / SP decode) is applied by the model
via ``with_sharding_constraint``; the math here is sharding-agnostic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.layers.norms import rmsnorm, rmsnorm_init
from repro.layers.param import Annotated, annotate, dense_init
from repro.layers.rope import apply_rope

Array = jax.Array

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def attn_init(
    key: jax.Array,
    d_model: int,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    qk_norm: bool = False,
    dtype=jnp.float32,
) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, ("embed", "heads_flat"), dtype=dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, ("embed", "kv_flat"), dtype=dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, ("embed", "kv_flat"), dtype=dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, ("heads_flat", "embed"), dtype=dtype),
    }
    if qk_norm:
        p["q_norm"] = annotate(jnp.zeros((head_dim,), dtype=dtype), None)
        p["k_norm"] = annotate(jnp.zeros((head_dim,), dtype=dtype), None)
    return p


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _mask_bias(q_pos: Array, kv_pos: Array, causal: bool, window: int | None) -> Array:
    """(..., Sq, Skv) additive bias from position comparisons."""
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), dtype=bool)
    if causal:
        ok &= k <= q
    if window is not None:
        ok &= k > q - window
    return jnp.where(ok, 0.0, NEG_INF)


def _gqa_scores(q: Array, k: Array, scale: float) -> Array:
    """q (B,Sq,KH,G,D) · k (B,C,KH,D) → (B,KH,G,Sq,C) fp32."""
    return jnp.einsum(
        "bqhgd,bchd->bhgqc", q, k, preferred_element_type=jnp.float32
    ) * scale


class _FlashCarry(NamedTuple):
    m: Array  # (B,KH,G,Sq)
    l: Array  # (B,KH,G,Sq)
    acc: Array  # (B,KH,G,Sq,D) fp32


def flash_attention_ref(
    q: Array,
    k: Array,
    v: Array,
    *,
    q_positions: Array,
    kv_positions: Array,
    causal: bool = True,
    window: int | None = None,
    kv_valid_len: Array | None = None,
    scale: float | None = None,
    chunk: int = 512,
) -> Array:
    """Chunked stable-softmax attention (flash reference, pure jnp).

    q: (B,Sq,H,D); k/v: (B,Skv,KH,D) with H = KH·G. Positions are global
    token indices used for causal/window masks. Returns (B,Sq,H,D).
    """
    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    g = h // kh
    scale = d**-0.5 if scale is None else scale
    chunk = min(chunk, skv)
    assert skv % chunk == 0, (skv, chunk)
    n_chunks = skv // chunk

    qg = q.reshape(b, sq, kh, g, d)
    kc = k.reshape(b, n_chunks, chunk, kh, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, kh, d).transpose(1, 0, 2, 3, 4)
    kvp = kv_positions.reshape(n_chunks, chunk)

    def step(carry: _FlashCarry, xs):
        kch, vch, kvpos = xs
        s = _gqa_scores(qg, kch, scale)  # (B,KH,G,Sq,C)
        bias = _mask_bias(q_positions, kvpos, causal, window)  # (Sq,C)
        if kv_valid_len is not None:
            bias = bias + jnp.where(kvpos < kv_valid_len, 0.0, NEG_INF)[None, :]
        s = s + bias
        m_new = jnp.maximum(carry.m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(carry.m - m_new)
        l_new = carry.l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqc,bchd->bhgqd", p.astype(v.dtype), vch,
                        preferred_element_type=jnp.float32)
        acc_new = carry.acc * corr[..., None] + pv
        return _FlashCarry(m_new, l_new, acc_new), None

    init = _FlashCarry(
        jnp.full((b, kh, g, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, kh, g, sq), jnp.float32),
        jnp.zeros((b, kh, g, sq, d), jnp.float32),
    )
    carry, _ = jax.lax.scan(step, init, (kc, vc, kvp))
    out = carry.acc / jnp.maximum(carry.l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def banded_attention_ref(
    q: Array,
    k: Array,
    v: Array,
    *,
    window: int,
    scale: float | None = None,
    chunk: int = 512,
) -> Array:
    """Causal sliding-window attention in O(S·(window+chunk)).

    Self-attention layout (q and kv aligned, positions 0..S-1). Each q chunk
    attends to a gathered kv band [chunk_start − window + 1, chunk_end).
    """
    b, s, h, d = q.shape
    _, _, kh, _ = k.shape
    g = h // kh
    scale = d**-0.5 if scale is None else scale
    chunk = min(chunk, s)
    assert s % chunk == 0
    n_chunks = s // chunk
    band = window + chunk  # static band width

    qg = q.reshape(b, n_chunks, chunk, kh, g, d).transpose(1, 0, 2, 3, 4, 5)

    def per_chunk(qch, i):
        # kv band start (clamped): positions [start, start+band)
        start = jnp.maximum(i * chunk + chunk - band, 0)
        start = jnp.minimum(start, max(s - band, 0))
        kb = jax.lax.dynamic_slice_in_dim(k, start, min(band, s), axis=1)
        vb = jax.lax.dynamic_slice_in_dim(v, start, min(band, s), axis=1)
        s_ = jnp.einsum("bqhgd,bchd->bhgqc", qch, kb,
                        preferred_element_type=jnp.float32) * scale
        qpos = i * chunk + jnp.arange(chunk)
        kpos = start + jnp.arange(min(band, s))
        s_ = s_ + _mask_bias(qpos, kpos, True, window)
        m = jnp.max(s_, axis=-1, keepdims=True)
        p = jnp.exp(s_ - m)
        o = jnp.einsum("bhgqc,bchd->bhgqd", p.astype(v.dtype), vb,
                       preferred_element_type=jnp.float32)
        o = o / jnp.sum(p, axis=-1)[..., None]
        return o  # (B,KH,G,chunk,D)

    def step(_, xs):
        qch, i = xs
        return None, per_chunk(qch, i)

    _, outs = jax.lax.scan(step, None, (qg, jnp.arange(n_chunks)))
    # outs: (n_chunks, B, KH, G, chunk, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, s, h, d)
    return out.astype(q.dtype)


def decode_attention(
    q: Array,
    k_cache: Array,
    v_cache: Array,
    k_new: Array,
    v_new: Array,
    *,
    q_position: Array,
    kv_positions: Array,
    window: int | None = None,
    scale: float | None = None,
) -> Array:
    """Single-step decode: q (B,1,H,D) over the cached tokens (B,KH,L,D)
    and its own key and value ``k_new``/``v_new`` (B,KH,1,D), which are
    kept apart from the cache: the cache is read, never written here.

    Direct stable softmax (no chunk scan) — with a seq-sharded cache the
    max/sum reductions lower to partial reductions + all-reduce (SP decode).
    ``kv_positions`` carries the *global* position of every cache row
    (ring-buffer caches pass their unrolled positions); cached rows at or
    after the query's position, or outside ``window``, are masked out.  The
    new token's own score joins the cached scores in one softmax and its
    value is added to theirs — the arithmetic of writing the entry into
    the cache first and attending over it.  Both position arguments may
    carry a leading batch dim (``q_position (B,Sq)``, ``kv_positions
    (B,L)``) — the slot-paged serving pool decodes rows at independent
    positions — or be batch-free (legacy shared-position decode).

    Perf notes: the cache layout is
    (B, KH, L, D) — the dot's native batch-major layout, so no per-step
    transpose copy of the cache; the scores dot runs in the cache dtype
    (contraction is over head_dim only — ≤256 terms — so bf16 accumulation
    is safe) and only the (B,KH,G,Sq,L) scores tensor is cast to f32 for
    the softmax. Before these two changes the lowered decode step
    materialized two full-cache-sized copies per layer per token.
    """
    b, sq, h, d = q.shape
    _, kh, l, _ = k_cache.shape
    assert sq == 1, "decode attends one new token per row"
    g = h // kh
    scale = d**-0.5 if scale is None else scale
    qg = q.reshape(b, sq, kh, g, d).transpose(0, 2, 3, 1, 4)  # (B,KH,G,Sq,D)
    qg = qg.reshape(b, kh, g * sq, d).astype(k_cache.dtype)

    def scores(keys):  # bf16 dot, no transpose → (B,KH,G,Sq,C) f32
        s = jnp.einsum("bhqd,bhcd->bhqc", qg, keys)
        return s.astype(jnp.float32).reshape(b, kh, g, sq, keys.shape[2]) * scale

    s = scores(k_cache)
    bias = _mask_bias(q_position, kv_positions, True, window)  # ([B,]Sq,L)
    s = s + (bias[:, None, None] if bias.ndim == 3 else bias)
    s_new = scores(k_new)  # (B,KH,G,Sq,1): the new token, always visible
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    p = jnp.exp(s - m)
    p_new = jnp.exp(s_new - m)
    denom = jnp.sum(p, axis=-1, keepdims=True) + p_new

    def mix(weights, values):  # (B,KH,G,Sq,C)·(B,KH,C,D) → (B,KH,G·Sq,D) f32
        w = (weights / denom).astype(values.dtype)
        return jnp.einsum(
            "bhqc,bhcd->bhqd", w.reshape(b, kh, g * sq, -1), values,
            preferred_element_type=jnp.float32,
        )

    o = mix(p, v_cache) + mix(p_new, v_new)
    o = o.reshape(b, kh, g, sq, d).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV caches (functional)
# ---------------------------------------------------------------------------


class KVCache(NamedTuple):
    """Fixed-capacity cache in dot-native layout (B, KH, capacity, D).
    ``capacity == window`` for sliding layers (ring buffer) or the max
    sequence length for global layers.

    ``pos`` is per-row: shape ``(B,)``, the number of tokens each batch
    row has seen.  The serving engine's slot-paged pool relies on this —
    every batch row is an independently-positioned cache *slot*, so
    requests of uneven length share one static-shape cache; a decode step
    reads the whole pool and writes each live row's new entry in place
    (``models/lm.py`` ``scatter_cache_slots``, through
    :func:`kv_cache_write`).  A scalar ``pos`` (legacy all-rows-share
    semantics) still broadcasts correctly through every function here."""

    k: Array  # (B, KH, capacity, D)
    v: Array
    pos: Array  # (B,) int32 — tokens seen per row (scalar = shared)

    @property
    def capacity(self) -> int:  # also under leading (stacked-layer) axes
        return self.k.shape[-2]


class KVEntry(NamedTuple):
    """One decode step's new key and value per row, ``(B, KH, 1, D)`` in
    the cache dtype — what a decode step adds to a :class:`KVCache`."""

    k: Array
    v: Array


def kv_cache_init(b: int, capacity: int, kh: int, d: int, dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        jnp.zeros((b, kh, capacity, d), dtype=dtype),
        jnp.zeros((b, kh, capacity, d), dtype=dtype),
        jnp.zeros((b,), jnp.int32),
    )


def kv_cache_write(cache: KVCache, new: KVEntry, live: Array) -> KVCache:
    """Insert each live row's new entry at its pos (mod capacity for ring
    buffers) and advance its pos; rows where ``live (B,)`` is False keep
    their entries and pos bit for bit.  Leaves may carry leading axes
    ahead of the batch (a model's stacked layers, ``new`` likewise), over
    which each row's pos is the same.  One in-place
    ``dynamic_update_slice`` per row: the cache is never copied."""
    b, cap = cache.pos.shape[-1], cache.capacity
    lead = cache.pos.ndim - 1
    row_pos = cache.pos.reshape(-1, b)[0]

    def put(row, kv):
        start = (0,) * lead + (row, 0, row_pos[row] % cap, 0)
        out = []
        for buf, entry in zip(kv, new):
            val = jax.lax.dynamic_slice_in_dim(entry, row, 1, axis=lead)
            cur = jax.lax.dynamic_slice(buf, start, val.shape)
            out.append(jax.lax.dynamic_update_slice(buf, jnp.where(live[row], val, cur), start))
        return tuple(out)

    k, v = jax.lax.fori_loop(0, b, put, (cache.k, cache.v))
    return KVCache(k, v, cache.pos + live.astype(cache.pos.dtype))


def kv_cache_update_decode(cache: KVCache, new: KVEntry) -> KVCache:
    """Insert one token's entry (B,KH,1,D) into every row of ``cache``."""
    if cache.pos.ndim == 0:  # legacy scalar pos: one dynamic slice for all rows
        idx = cache.pos % cache.capacity
        k = jax.lax.dynamic_update_slice_in_dim(cache.k, new.k, idx, axis=2)
        v = jax.lax.dynamic_update_slice_in_dim(cache.v, new.v, idx, axis=2)
        return KVCache(k, v, cache.pos + 1)
    return kv_cache_write(cache, new, jnp.ones(cache.pos.shape, bool))


def kv_cache_positions(cache: KVCache) -> Array:
    """Global position of each cache row's entries — ``(B, capacity)``
    for per-row pos, ``(capacity,)`` for legacy scalar pos.  Entries not
    yet written get a position beyond the current pos so causal masking
    removes them (this is also what keeps a reused pool slot's *stale*
    rows — left over from a freed request — unread: they all sit at
    indices ≥ the new occupant's pos until overwritten)."""
    cap = cache.capacity
    slots = jnp.arange(cap)
    pos = cache.pos[..., None]  # (B,1); scalar pos → (1,) broadcasts flat
    n_wraps = pos // cap
    base = slots + (n_wraps - 1) * cap
    latest = slots + n_wraps * cap
    positions = jnp.where(latest < pos, latest, base)
    # rows never written (pos < capacity): base is negative → mark invalid
    return jnp.where(positions >= 0, positions, pos + 1 + slots)


def kv_cache_prefill(cache: KVCache, k_seq: Array, v_seq: Array) -> KVCache:
    """Fill from a full prefill sequence (B,S,KH,D); for ring buffers keeps
    the last ``capacity`` tokens, laid out so that slot = pos % capacity."""
    s = k_seq.shape[1]
    cap = cache.capacity
    pos = jnp.full(cache.pos.shape, s, jnp.int32)
    k_t = k_seq.transpose(0, 2, 1, 3)  # (B,KH,S,D)
    v_t = v_seq.transpose(0, 2, 1, 3)
    if s <= cap:
        k = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k_t.astype(cache.k.dtype), 0, axis=2)
        v = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v_t.astype(cache.v.dtype), 0, axis=2)
        return KVCache(k, v, pos)
    tail_k = k_t[:, :, s - cap :]
    tail_v = v_t[:, :, s - cap :]
    # token at global position p lives in slot p % cap
    roll = (s - cap) % cap
    k = jnp.roll(tail_k, shift=roll, axis=2).astype(cache.k.dtype)
    v = jnp.roll(tail_v, shift=roll, axis=2).astype(cache.v.dtype)
    return KVCache(k, v, pos)


# ---------------------------------------------------------------------------
# Full attention block (projections + rope + attention)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_base: float = 10000.0
    rotary_dim: int | None = None  # None → full head_dim
    window: int | None = None  # sliding window (local layers)
    qk_norm: bool = False
    scale: float | None = None
    use_rope: bool = True


def attn_qkv(p: dict, x: Array, spec: AttnSpec, positions: Array):
    b, s, _ = x.shape
    h, kh, d = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = (x @ p["wq"]).reshape(b, s, h, d)
    k = (x @ p["wk"]).reshape(b, s, kh, d)
    v = (x @ p["wv"]).reshape(b, s, kh, d)
    if spec.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)
    if spec.use_rope:
        q = apply_rope(q, positions, rotary_dim=spec.rotary_dim, base=spec.rope_base)
        k = apply_rope(k, positions, rotary_dim=spec.rotary_dim, base=spec.rope_base)
    return q, k, v


def attn_train(p: dict, x: Array, spec: AttnSpec, chunk: int = 512) -> Array:
    """Self-attention over a full sequence (training / prefill compute)."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = attn_qkv(p, x, spec, positions)
    if spec.window is not None and spec.window < s:
        o = banded_attention_ref(q, k, v, window=spec.window, scale=spec.scale,
                                 chunk=min(chunk, s))
    else:
        o = flash_attention_ref(
            q, k, v,
            q_positions=positions, kv_positions=positions,
            causal=True, window=spec.window, scale=spec.scale,
            chunk=min(chunk, s),
        )
    return o.reshape(b, s, -1) @ p["wo"]


def attn_prefill(p: dict, x: Array, spec: AttnSpec, cache: KVCache, chunk: int = 512):
    """Prefill: same math as train, but also fills the KV cache."""
    b, s, _ = x.shape
    positions = jnp.arange(s)
    q, k, v = attn_qkv(p, x, spec, positions)
    if spec.window is not None and spec.window < s:
        o = banded_attention_ref(q, k, v, window=spec.window, scale=spec.scale,
                                 chunk=min(chunk, s))
    else:
        o = flash_attention_ref(
            q, k, v,
            q_positions=positions, kv_positions=positions,
            causal=True, window=spec.window, scale=spec.scale,
            chunk=min(chunk, s),
        )
    new_cache = kv_cache_prefill(cache, k, v)
    return o.reshape(b, s, -1) @ p["wo"], new_cache


def attn_decode_entry(p: dict, x: Array, spec: AttnSpec, cache: KVCache):
    """One-token decode step that reads ``cache`` and leaves it as it is:
    x (B,1,d) attends over the cached tokens with its own K/V kept apart
    (:func:`decode_attention`).  Returns ``(y, entry)``, the
    :class:`KVEntry` for the caller to write.  Per-row cache positions
    give per-row rope/mask positions — (B,S); legacy scalar pos gives (S,)."""
    b, s, _ = x.shape
    positions = cache.pos[..., None] + jnp.arange(s)
    q, k, v = attn_qkv(p, x, spec, positions)
    new = KVEntry(
        k.astype(cache.k.dtype).transpose(0, 2, 1, 3),  # (B,KH,1,D)
        v.astype(cache.v.dtype).transpose(0, 2, 1, 3),
    )
    # the written cache would hold the last `capacity` tokens: mask as it would
    window = cache.capacity if spec.window is None else min(spec.window, cache.capacity)
    o = decode_attention(
        q, cache.k, cache.v, new.k, new.v,
        q_position=positions,
        kv_positions=kv_cache_positions(cache),
        window=window, scale=spec.scale,
    )
    return o.reshape(b, s, -1) @ p["wo"], new


def attn_decode(p: dict, x: Array, spec: AttnSpec, cache: KVCache):
    """One-token decode step that returns the cache with its entry written."""
    y, new = attn_decode_entry(p, x, spec, cache)
    return y, kv_cache_update_decode(cache, new)
