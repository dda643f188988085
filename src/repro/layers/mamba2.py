"""Mamba2 / SSD (state-space duality) block [arXiv:2405.21060].

Training/prefill uses the *chunked SSD algorithm*: within-chunk terms are
quadratic attention-like matmuls (MXU-friendly), across-chunk terms pass a
(H, P, N) state through a sequential scan over chunks — exactly the
"matmul-rich" TPU adaptation of the selective scan. Decode keeps the O(1)
recurrent state (the reason mamba archs run the long_500k cell).

Layout: d_inner = expand·d_model, H heads of size P = headdim, G state
groups (B/C shared per group), N = ssm state size.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.layers.norms import rmsnorm
from repro.layers.param import annotate, dense_init

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Mamba2Spec:
    d_model: int
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    headdim: int = 64
    n_groups: int = 1
    chunk: int = 256

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def n_heads(self) -> int:
        assert self.d_inner % self.headdim == 0
        return self.d_inner // self.headdim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def mamba2_init(key: jax.Array, spec: Mamba2Spec, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 5)
    d = spec.d_model
    d_in_proj = 2 * spec.d_inner + 2 * spec.n_groups * spec.d_state + spec.n_heads
    h = spec.n_heads
    dt = jnp.exp(
        jax.random.uniform(ks[2], (h,), minval=np.log(1e-3), maxval=np.log(1e-1))
    )
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    return {
        "in_proj": dense_init(ks[0], d, d_in_proj, ("embed", "inner_flat"), dtype=dtype),
        "conv_w": annotate(
            (
                jax.random.normal(ks[1], (spec.d_conv, spec.conv_dim), dtype=dtype)
                * float(1.0 / np.sqrt(spec.d_conv))
            ).astype(dtype),
            None, "inner_flat",
        ),
        "conv_b": annotate(jnp.zeros((spec.conv_dim,), dtype=dtype), "inner_flat"),
        "a_log": annotate(jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32), "heads"),
        "d_skip": annotate(jnp.ones((h,), jnp.float32), "heads"),
        "dt_bias": annotate(dt_bias.astype(jnp.float32), "heads"),
        "norm_w": annotate(jnp.zeros((spec.d_inner,), dtype=dtype), "inner_flat"),
        "out_proj": dense_init(ks[3], spec.d_inner, d, ("inner_flat", "embed"), dtype=dtype),
    }


class MambaCache(NamedTuple):
    conv: Array  # (B, d_conv-1, conv_dim) — last inputs for causal conv
    ssm: Array  # (B, H, P, N) fp32 recurrent state
    pos: Array  # (B,) int32 — tokens seen per row (slot-paged serving)


def mamba_cache_init(b: int, spec: Mamba2Spec, dtype=jnp.bfloat16) -> MambaCache:
    return MambaCache(
        jnp.zeros((b, spec.d_conv - 1, spec.conv_dim), dtype=dtype),
        jnp.zeros((b, spec.n_heads, spec.headdim, spec.d_state), jnp.float32),
        jnp.zeros((b,), jnp.int32),
    )


def _causal_conv(x: Array, w: Array, b: Array, prev: Array | None) -> Array:
    """Depthwise causal conv over seq: x (B,S,C), w (K,C). ``prev`` prepends
    (B,K-1,C) history (decode) or zeros (train)."""
    k = w.shape[0]
    if prev is None:
        prev = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([prev.astype(x.dtype), x], axis=1)
    out = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(k)
    )
    return out + b[None, None, :]


def _segsum(log_a: Array) -> Array:
    """Cumulative log-decay matrix: L[i,j] = Σ_{j<t≤i} log_a[t], -inf above
    the diagonal. log_a: (..., T). Returns (..., T, T)."""
    t = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # Σ_{j<t≤i}
    mask = jnp.tril(jnp.ones((t, t), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: Array,  # (B, S, H, P)
    dt: Array,  # (B, S, H) fp32 (post-softplus)
    a: Array,  # (H,) fp32 negative decay rates (−exp(a_log))
    b_: Array,  # (B, S, G, N)
    c: Array,  # (B, S, G, N)
    chunk: int,
    init_state: Array | None = None,  # (B, H, P, N)
) -> tuple[Array, Array]:
    """Chunked SSD: returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    g, n = b_.shape[2], b_.shape[3]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    rep = h // g

    # reshape into chunks
    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = b_.reshape(bsz, nc, chunk, g, n)
    cc = c.reshape(bsz, nc, chunk, g, n)

    log_a = dtc * a[None, None, None, :]  # (B,nc,T,H) — ≤ 0
    # intra-chunk (attention-like) term
    lmat = jnp.exp(_segsum(log_a.transpose(0, 1, 3, 2)))  # (B,nc,H,T,T)
    cb = jnp.einsum("bctgn,bcsgn->bcgts", cc, bc)  # (B,nc,G,T,S)
    cb = jnp.repeat(cb, rep, axis=2)  # (B,nc,H,T,S)
    xdt = xc * dtc[..., None]  # (B,nc,T,H,P)
    y_intra = jnp.einsum("bchts,bcshp->bcthp", cb * lmat, xdt)

    # inter-chunk state passing
    cum = jnp.cumsum(log_a, axis=2)  # (B,nc,T,H)
    total = cum[:, :, -1:, :]  # (B,nc,1,H)
    return _ssd_interchunk(
        y_intra, xdt, bc, cc, log_a, cum, total, init_state, bsz, nc, chunk, h, p, g, n, rep
    )


def _ssd_interchunk(y_intra, xdt, bc, cc, log_a, cum, total, init_state,
                    bsz, nc, chunk, h, p, g, n, rep):
    decay_to_end = jnp.exp(total - cum)  # (B,nc,T,H)
    # chunk state: Σ_t B_t ⊗ (x_t·dt_t) · decay(t→end); B broadcast to heads
    bc_h = jnp.repeat(bc, rep, axis=3)  # (B,nc,T,H,N)
    chunk_states = jnp.einsum(
        "bcthn,bcthp->bchpn", bc_h, xdt * decay_to_end[..., None]
    )  # (B,nc,H,P,N)
    chunk_decay = jnp.exp(total[:, :, 0, :])  # (B,nc,H) decay across whole chunk

    s0 = (
        init_state
        if init_state is not None
        else jnp.zeros((bsz, h, p, n), jnp.float32)
    )

    def scan_step(state, xs):
        cs, dec = xs  # (B,H,P,N), (B,H)
        new = state * dec[..., None, None] + cs
        return new, state  # emit the state *entering* this chunk

    (final_state, prev_states) = jax.lax.scan(
        scan_step,
        s0.astype(jnp.float32),
        (chunk_states.transpose(1, 0, 2, 3, 4).astype(jnp.float32),
         chunk_decay.transpose(1, 0, 2)),
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)  # (B,nc,H,P,N)

    # inter-chunk output: C_t · decay(start→t) · state_in
    decay_from_start = jnp.exp(cum)  # (B,nc,T,H)
    cc_h = jnp.repeat(cc, rep, axis=3)  # (B,nc,T,H,N)
    y_inter = jnp.einsum(
        "bcthn,bchpn->bcthp", cc_h * decay_from_start[..., None], prev_states
    )
    y = (y_intra + y_inter).reshape(bsz, nc * chunk, h, p)
    return y, final_state


def mamba2_apply(
    p: dict,
    x: Array,
    spec: Mamba2Spec,
    cache: MambaCache | None = None,
    decode: bool = False,
):
    """Full block. Train: cache=None. Prefill: cache returned filled.
    Decode: x (B,1,d), recurrent update."""
    bsz, s, _ = x.shape
    h, pd, g, n = spec.n_heads, spec.headdim, spec.n_groups, spec.d_state
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt_raw = jnp.split(
        zxbcdt, [spec.d_inner, spec.d_inner + spec.conv_dim], axis=-1
    )
    prev = cache.conv if (cache is not None and decode) else None
    xbc_conv = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"], prev))
    xs, b_, c = jnp.split(
        xbc_conv, [spec.d_inner, spec.d_inner + g * n], axis=-1
    )
    xs = xs.reshape(bsz, s, h, pd)
    b_ = b_.reshape(bsz, s, g, n)
    c = c.reshape(bsz, s, g, n)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a = -jnp.exp(p["a_log"])  # (H,)

    new_cache = None
    if decode:
        assert cache is not None and s == 1
        # recurrent update: h' = h·exp(dt·a) + dt·B⊗x ; y = C·h' + D·x
        dt1 = dt[:, 0]  # (B,H)
        da = jnp.exp(dt1 * a[None, :])  # (B,H)
        b_h = jnp.repeat(b_[:, 0], h // g, axis=1)  # (B,H,N) groups→heads
        c_h = jnp.repeat(c[:, 0], h // g, axis=1)
        bx = jnp.einsum(
            "bhn,bhp->bhpn",
            b_h.astype(jnp.float32),
            (xs[:, 0] * dt1[..., None]).astype(jnp.float32),
        )
        ssm = cache.ssm * da[..., None, None] + bx
        y = jnp.einsum("bhpn,bhn->bhp", ssm, c_h.astype(jnp.float32))
        y = y + p["d_skip"][None, :, None] * xs[:, 0].astype(jnp.float32)
        y = y.reshape(bsz, 1, spec.d_inner).astype(x.dtype)
        conv_hist = jnp.concatenate([cache.conv[:, 1:], xbc.astype(cache.conv.dtype)], axis=1)
        new_cache = MambaCache(conv_hist, ssm, cache.pos + 1)
    else:
        init_state = None
        y, final_state = ssd_chunked(xs, dt, a, b_, c, spec.chunk, init_state)
        y = y + p["d_skip"][None, None, :, None] * xs
        y = y.reshape(bsz, s, spec.d_inner).astype(x.dtype)
        if cache is not None:  # prefill: stash conv history + final state
            k = spec.d_conv - 1
            conv_hist = xbc[:, -k:] if s >= k else jnp.concatenate(
                [jnp.zeros((bsz, k - s, spec.conv_dim), xbc.dtype), xbc], axis=1
            )
            new_cache = MambaCache(
                conv_hist.astype(cache.conv.dtype),
                final_state,
                jnp.full(cache.pos.shape, s, jnp.int32),
            )

    y = gated_norm(p["norm_w"], y, z, g)
    out = y @ p["out_proj"]
    return out, new_cache


def gated_norm(w: Array, y: Array, z: Array, groups: int) -> Array:
    """RMSNorm of ``y·silu(z)`` over each of ``groups`` equal slices of the
    ``d_inner`` channels (one per B/C group), with the ``1 + w`` gain.
    One group keeps the whole-width expression: under jit a reshaped
    bf16 graph fuses, and so rounds, differently."""
    gated = y * jax.nn.silu(z)
    if groups == 1:
        return rmsnorm(w, gated)
    shape = gated.shape
    split = shape[:-1] + (groups, shape[-1] // groups)
    return rmsnorm(w.reshape(groups, -1), gated.reshape(split)).reshape(shape)
