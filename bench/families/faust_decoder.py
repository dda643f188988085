"""Pre-norm GQA decoder with FAµST projections: the benchmark's side of it.

One configuration file (``bench/configs/<name>.json``) of this family holds
the sizes; this module turns them into

* ``arch(c)`` — the program's ``ArchConfig`` (the system under test);
* ``make_params(c, seed)`` — weights made on the device from the seed in one
  jitted call, in the program's parameter layout and served dtype;
* ``reference_pass(...)`` — the plain float32 reference: each FAµST factor
  expanded to a dense matrix, attention written out, no cache, no kernel,
  nothing imported from the program;
* ``chain_work`` / ``decode_work`` / ``prefill_work`` — the FLOPs and bytes
  the configuration requires, from shapes and live rows only.

The layer equations follow the repository's decoder (``models/lm.py``):
RMSNorm with a ``1 + w`` gain (eps 1e-6), rotary over the first
``rotary_dim`` channels of each head in half-split order, causal softmax
attention with grouped KV heads, SwiGLU MLP, final RMSNorm, unembedding.
A vision prefix replaces the first ``n_vision_tokens`` embeddings.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
ROPE_BASE = 10000.0


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Chain:
    """One FAµST chain ``W ≈ lam · F_1 ··· F_J`` of ``(block × block)`` blocks,
    ``k`` kept input blocks per output block-column (the program's
    ``FaustSpec`` layout)."""

    in_dim: int
    out_dim: int
    n_factors: int
    block: int
    k: int

    def dims(self) -> list[int]:
        inner = -(-min(self.in_dim, self.out_dim) // self.block) * self.block
        return [self.in_dim] + [inner] * (self.n_factors - 1) + [self.out_dim]

    def factor_shapes(self) -> list[tuple[int, int, int, int]]:
        """Per factor ``(in_blocks, out_blocks, kept blocks, factor index)``."""
        d = self.dims()
        out = []
        for i in range(self.n_factors):
            ib = -(-d[i] // self.block)
            ob = -(-d[i + 1] // self.block)
            out.append((ib, ob, min(self.k, ib), i))
        return out

    @property
    def s_tot(self) -> int:
        return sum(ob * k * self.block**2 for _, ob, k, _ in self.factor_shapes())


def sizes(c: dict) -> dict:
    """The numbers the rest of this module reads, from a configuration."""
    return dict(
        L=c["n_layers"], d=c["d_model"], H=c["n_heads"], KH=c["n_kv_heads"],
        D=c["head_dim"], F=c["d_ff"], V=c["vocab"],
        nv=c.get("n_vision_tokens", 0),
        rot=int(c["head_dim"] * c.get("rotary_pct", 1.0)) // 2 * 2,
    )


def _chain_of(spec: dict | None, in_dim: int, out_dim: int) -> Chain | None:
    if spec is None:
        return None
    return Chain(in_dim, out_dim, spec["n_factors"], spec["block"], spec["k"])


def chains(c: dict) -> dict[str, Chain]:
    """Every FAµST chain of the configuration by role."""
    s = sizes(c)
    out = {}
    mlp = c.get("faust_mlp")
    if mlp is not None:
        out["gate"] = _chain_of(mlp, s["d"], s["F"])
        out["up"] = _chain_of(mlp, s["d"], s["F"])
        out["down"] = _chain_of(mlp, s["F"], s["d"])
    if c.get("faust_unembed") is not None:
        out["unembed"] = _chain_of(c["faust_unembed"], s["d"], s["V"])
    return out


def arch(c: dict):
    """The program's ``ArchConfig`` for this configuration."""
    from repro.configs.base import ArchConfig
    from repro.layers.faust_linear import FaustSpec

    def spec(x):
        return None if x is None else FaustSpec(x["n_factors"], x["block"], x["k"])

    return ArchConfig(
        name=c["name"], family=c.get("arch_family", "dense"),
        n_layers=c["n_layers"], d_model=c["d_model"], n_heads=c["n_heads"],
        n_kv_heads=c["n_kv_heads"], head_dim=c["head_dim"], d_ff=c["d_ff"],
        vocab=c["vocab"], act="swiglu", norm="rms",
        stages=((c["n_layers"], ("attn",)),),
        rotary_pct=c.get("rotary_pct", 1.0),
        n_vision_tokens=c.get("n_vision_tokens", 0),
        faust_mlp=spec(c.get("faust_mlp")),
        faust_unembed=spec(c.get("faust_unembed")),
        tie_embeddings=False, dtype=c["dtype"], remat=False,
        attn_chunk=c.get("attn_chunk", 512),
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 64 bits (``PRNGKey`` alone
    would truncate seeds above 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _dtype(c: dict):
    return jnp.bfloat16 if c["dtype"] == "bfloat16" else jnp.float32


def _chain_params(key, ch: Chain, lead: tuple, dt) -> dict:
    factors = []
    for ib, ob, k, i in ch.factor_shapes():
        kv, ki, key = jax.random.split(jax.random.fold_in(key, i), 3)
        values = jax.random.normal(kv, lead + (ob, k, ch.block, ch.block), dt)
        values = (values * (1.0 / math.sqrt(k * ch.block))).astype(dt)
        order = jnp.argsort(jax.random.uniform(ki, lead + (ob, ib)), axis=-1)
        in_idx = jnp.sort(order[..., :k], axis=-1).astype(jnp.int32)
        factors.append({"values": values, "in_idx": in_idx})
    lam = jax.random.uniform(jax.random.fold_in(key, 99), lead, jnp.float32, 0.75, 1.25)
    return {"factors": factors, "lam": lam.astype(dt)}


def _dense(key, shape, dt):
    return (jax.random.normal(key, shape, dt) * (1.0 / math.sqrt(shape[-2]))).astype(dt)


def _gain(key, shape, dt):
    return (jax.random.normal(key, shape, jnp.float32) * 0.1).astype(dt)


def _make(key, c: dict) -> dict:
    s, dt, ch = sizes(c), _dtype(c), chains(c)
    L, d = s["L"], s["d"]
    ks = iter(jax.random.split(key, 16))
    attn = {
        "wq": _dense(next(ks), (L, d, s["H"] * s["D"]), dt),
        "wk": _dense(next(ks), (L, d, s["KH"] * s["D"]), dt),
        "wv": _dense(next(ks), (L, d, s["KH"] * s["D"]), dt),
        "wo": _dense(next(ks), (L, s["H"] * s["D"], d), dt),
    }
    if "up" in ch:
        mlp = {
            "w_gate": _chain_params(next(ks), ch["gate"], (L,), dt),
            "w_up": _chain_params(next(ks), ch["up"], (L,), dt),
            "w_down": _chain_params(next(ks), ch["down"], (L,), dt),
        }
    else:
        mlp = {
            "w_gate": _dense(next(ks), (L, d, s["F"]), dt),
            "w_up": _dense(next(ks), (L, d, s["F"]), dt),
            "w_down": _dense(next(ks), (L, s["F"], d), dt),
        }
    layer = {
        "norm1": _gain(next(ks), (L, d), dt),
        "attn": attn,
        "norm2": _gain(next(ks), (L, d), dt),
        "mlp": mlp,
    }
    if "unembed" in ch:
        unembed = {"faust": _chain_params(next(ks), ch["unembed"], (), dt)}
    else:
        unembed = {"w": _dense(next(ks), (d, s["V"]), dt)}
    return {
        "embed": {"table": jax.random.normal(next(ks), (s["V"], d), dt)},
        "stages": [[layer]],
        "final_norm": _gain(next(ks), (d,), dt),
        "unembed": unembed,
    }


@functools.lru_cache(maxsize=None)
def _maker(c_key: tuple):
    return jax.jit(functools.partial(_make, c=_thaw(c_key)))


def make_params(c: dict, seed: int) -> dict:
    """All weights from ``seed``, made on the default device in one jitted
    call, in the dtype the configuration serves in."""
    return jax.block_until_ready(_maker(_freeze(c))(seed_key(seed)))


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------


def expand_factor(values, in_idx, in_f: int, out_f: int):
    """A packed ``(O, K, b, b)`` block factor as the dense ``(in_f, out_f)``
    float32 matrix it stands for."""
    o, k, b, _ = values.shape
    ib = -(-in_f // b)
    dense = jnp.zeros((ib, o, b, b), jnp.float32)
    cols = jnp.broadcast_to(jnp.arange(o)[:, None], (o, k))
    dense = dense.at[in_idx, cols].add(values.astype(jnp.float32))
    dense = dense.transpose(0, 2, 1, 3).reshape(ib * b, o * b)
    return dense[:in_f, :out_f]


def _fake_int8(x, axis):
    """Symmetric int8 quantize-dequantize along ``axis`` (the control)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _matmul(x, w, quant: bool):
    if quant:
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return x @ w


def _linear(x, p, ch: Chain | None, quant: bool):
    """``x @ W`` with ``W`` dense, or a FAµST chain applied factor by factor
    through dense expansions."""
    if ch is None:
        return _matmul(x, p.astype(jnp.float32), quant)
    dims = ch.dims()
    for i, f in enumerate(p["factors"]):
        w = expand_factor(f["values"], f["in_idx"], dims[i], dims[i + 1])
        x = _matmul(x, w, quant)
    return p["lam"].astype(jnp.float32) * x


def _rms(x, w):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return x * (1.0 + w.astype(jnp.float32))


def _rope(x, rot: int):
    """Half-split rotary over the first ``rot`` channels; x (S, heads, D)."""
    if rot == 0:
        return x
    s = x.shape[0]
    inv = 1.0 / ROPE_BASE ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2 : rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], -1)


def _attention(q, k, v, q_chunk: int, quant: bool):
    """Causal softmax attention, f32; q (S,H,D), k/v (S,H,D).  ``quant``
    rounds both products' inputs to int8 along the contracted axis."""
    s, h, dh = q.shape
    n = s // q_chunk
    kpos = jnp.arange(s)
    if quant:
        k, v = _fake_int8(k, -1), _fake_int8(v, 0)

    def one(i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, 0)
        if quant:
            qc = _fake_int8(qc, -1)
        sc = jnp.einsum("qhd,khd->hqk", qc, k) / math.sqrt(dh)
        qpos = i * q_chunk + jnp.arange(q_chunk)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if quant:
            p = _fake_int8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    return jax.lax.map(one, jnp.arange(n)).reshape(s, h, dh)


def _forward_logits(params, tokens, vision, c: dict, quant: bool):
    s, ch = sizes(c), chains(c)
    n = tokens.shape[0]
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    if s["nv"]:
        x = x.at[: s["nv"]].set(vision.astype(jnp.float32))
    q_chunk = math.gcd(n, 512)
    g = s["H"] // s["KH"]

    def layer(x, lp):
        h = _rms(x, lp["norm1"])
        a = lp["attn"]
        q = _matmul(h, a["wq"].astype(jnp.float32), quant).reshape(n, s["H"], s["D"])
        k = _matmul(h, a["wk"].astype(jnp.float32), quant).reshape(n, s["KH"], s["D"])
        v = _matmul(h, a["wv"].astype(jnp.float32), quant).reshape(n, s["KH"], s["D"])
        q, k = _rope(q, s["rot"]), _rope(k, s["rot"])
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        o = _attention(q, k, v, q_chunk, quant).reshape(n, s["H"] * s["D"])
        x = x + _matmul(o, a["wo"].astype(jnp.float32), quant)
        h = _rms(x, lp["norm2"])
        m = lp["mlp"]
        gate = _linear(h, m["w_gate"], ch.get("gate"), quant)
        up = _linear(h, m["w_up"], ch.get("up"), quant)
        return x + _linear(jax.nn.silu(gate) * up, m["w_down"], ch.get("down"), quant), None

    x, _ = jax.lax.scan(layer, x, params["stages"][0][0])
    x = _rms(x, params["final_norm"])
    u = params["unembed"]
    return _linear(x, u["faust"] if "faust" in u else u["w"], ch.get("unembed"), quant)


@functools.partial(jax.jit, static_argnames=("c_key", "quant"))
def _reference_pass(params, tokens, vision, targets, c_key, quant):
    c = _thaw(c_key)
    with jax.default_matmul_precision("highest"):
        logits = _forward_logits(params, tokens, vision, c, quant)
    best = jnp.max(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return best - picked, jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _freeze(c: dict) -> tuple:
    """A hashable copy of the numeric part of a configuration."""
    keep = {}
    for k, v in c.items():
        if isinstance(v, dict):
            keep[k] = tuple(sorted(v.items()))
        elif isinstance(v, (int, float, str)) or v is None:
            keep[k] = v
    return tuple(sorted(keep.items()))


def _thaw(c_key: tuple) -> dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in c_key}


def reference_pass(params, c: dict, tokens, vision, targets, *, quant: bool = False):
    """Per position ``p`` of ``tokens`` (length a multiple of 512 or at most
    512): the gap ``max(logits[p]) − logits[p, targets[p]]`` of the float32
    reference and its own argmax.  ``quant`` runs the control: every matrix
    product in symmetric int8 (weights per output column, activations per
    row, both attention products along their contracted axis)."""
    gaps, top = _reference_pass(
        params, jnp.asarray(tokens, jnp.int32),
        None if vision is None else jnp.asarray(vision),
        jnp.asarray(targets, jnp.int32), _freeze(c), quant,
    )
    return np.asarray(gaps), np.asarray(top)


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------


def chain_work(ch: Chain, rows: int, elt: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one chain call on ``rows`` live rows requires: each
    stored value multiplied once per row and read once, plus the rows in and
    out.  Padded rows and index tables do not count."""
    return 2.0 * rows * ch.s_tot, float(elt * (ch.s_tot + rows * (ch.in_dim + ch.out_dim)))


def _layer_weights(c: dict) -> tuple[float, float, float]:
    """(dense attention weights, MLP weights as stored, MLP multiply-adds per
    row) of one layer."""
    s, ch = sizes(c), chains(c)
    attn = s["d"] * (s["H"] + 2 * s["KH"]) * s["D"] + s["H"] * s["D"] * s["d"]
    if "up" in ch:
        mlp = ch["gate"].s_tot + ch["up"].s_tot + ch["down"].s_tot
    else:
        mlp = 3 * s["d"] * s["F"]
    return float(attn), float(mlp), float(mlp)


def _unembed(c: dict) -> float:
    s, ch = sizes(c), chains(c)
    return float(ch["unembed"].s_tot if "unembed" in ch else s["d"] * s["V"])


def decode_work(c: dict, context: list[int], elt: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over live rows whose caches hold
    ``context[i]`` tokens before the step: every weight once, each row's own
    keys and values read, one new key and value written per row and layer,
    attention over ``context[i] + 1`` positions."""
    s = sizes(c)
    b = len(context)
    attn_w, mlp_w, mlp_mac = _layer_weights(c)
    kv_tok = 2 * s["KH"] * s["D"]  # key + value elements per token and layer
    ctx = float(sum(n + 1 for n in context))
    flops = s["L"] * (2.0 * b * (attn_w + mlp_mac) + 4.0 * s["H"] * s["D"] * ctx)
    flops += 2.0 * b * _unembed(c)
    byts = elt * (s["L"] * (attn_w + mlp_w) + _unembed(c) + b * s["d"])
    byts += elt * s["L"] * kv_tok * (ctx + b)
    byts += 4.0 * b * s["V"]  # f32 logits out
    return flops, byts


def prefill_work(c: dict, n: int, elt: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of prefilling one ``n``-token prompt: every weight once,
    causal attention over ``n(n+1)/2`` query-key pairs, the cache written,
    and the last position unembedded."""
    s = sizes(c)
    attn_w, mlp_w, mlp_mac = _layer_weights(c)
    kv_tok = 2 * s["KH"] * s["D"]
    pairs = n * (n + 1) / 2.0
    flops = s["L"] * (2.0 * n * (attn_w + mlp_mac) + 4.0 * s["H"] * s["D"] * pairs)
    flops += 2.0 * _unembed(c)
    byts = elt * (s["L"] * (attn_w + mlp_w) + _unembed(c) + n * s["d"])
    byts += elt * s["L"] * kv_tok * n + 4.0 * s["V"]
    return flops, byts


def chain_calls_decode(c: dict, rows: int) -> list[tuple[str, int, int]]:
    """``(role, rows, calls)`` of the chain applies one decode step makes."""
    ch = chains(c)
    out = [(r, rows, sizes(c)["L"]) for r in ("gate", "up", "down") if r in ch]
    if "unembed" in ch:
        out.append(("unembed", rows, 1))
    return out


def chain_calls_prefill(c: dict, n: int) -> list[tuple[str, int, int]]:
    """``(role, rows, calls)`` of the chain applies one ``n``-token prefill
    makes: the MLP chains at prompt width, the unembedding on the last row."""
    ch = chains(c)
    out = [(r, n, sizes(c)["L"]) for r in ("gate", "up", "down") if r in ch]
    if "unembed" in ch:
        out.append(("unembed", 1, 1))
    return out
