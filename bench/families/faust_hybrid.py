"""Hybrid Mamba-2 / attention decoder with FAµST FFN and unembedding chains
(Nemotron-H's block pattern): the benchmark's side of it.

One configuration file of this family holds the published config.json's
keys (``hidden_size``, ``mamba_num_heads``, ``hybrid_override_pattern``,
…) for the layers it keeps; this module turns them into

* ``arch(c)`` — the program's ``ArchConfig`` (the system under test);
* ``make_params(c, seed)`` — weights made on the device from the seed in one
  jitted call, in the program's parameter layout and served dtype;
* ``reference_pass(...)`` — the plain float32 reference: Mamba-2 as its
  literal per-token recurrence (not the chunked SSD), each FAµST factor
  expanded to a dense matrix, attention written out, nothing imported
  from the program;
* ``chain_work`` / ``decode_work`` / ``prefill_work`` / ``ssm_state_bytes``
  — the FLOPs and bytes the configuration requires, from shapes and live
  rows only.

Each character of ``hybrid_override_pattern`` is one pre-norm residual
block ``x + mixer(RMSNorm(x))``: ``M`` Mamba-2, ``*`` attention, ``-`` the
FFN ``down(relu(up(x))²)``.  Mamba-2: ``in_proj`` → ``[z, xBC, dt]``;
depthwise causal conv with bias, then SiLU, over ``xBC``; ``dt =
softplus(dt + dt_bias)``, ``A = −exp(A_log)``; ``h ← exp(dt·A)·h +
dt·B⊗x``, ``y = C·h + D·x`` with B and C shared by the heads of a group;
RMSNorm of ``y·silu(z)`` per group; ``out_proj``.  Attention: grouped KV
heads, no position encoding, causal softmax.  RMSNorm has a ``1 + w``
gain and eps 1e-6, as the repository's layer has (the configuration
file lists these departures).
"""
from __future__ import annotations

import functools
import itertools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from bench.families.faust_decoder import (
    Chain,
    _chain_params,
    _dense,
    _fake_int8,
    _freeze,
    _gain,
    _matmul,
    _thaw,
    chain_work,  # noqa: F401 — the metrics read it from the family
    expand_factor,
    seed_key,
)

EPS = 1e-6
BLOCK = 512  # tokens the reference carries its state across at a time
Q_CHUNK = 128  # query rows of one attention score block in the reference
COL_BLOCKS = 64  # output blocks of the unembedding expanded at a time

# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def sizes(c: dict) -> dict:
    """The numbers the rest of this module reads, from a configuration."""
    s = dict(
        d=c["hidden_size"], H=c["num_attention_heads"], KH=c["num_key_value_heads"],
        D=c["attention_head_dim"], F=c["intermediate_size"], V=c["vocab_size"],
        MH=c["mamba_num_heads"], P=c["mamba_head_dim"], N=c["ssm_state_size"],
        G=c["n_groups"], K=c["conv_kernel"], chunk=c["chunk_size"],
        ctx=c["max_position_embeddings"],
    )
    s["din"] = s["MH"] * s["P"]
    assert s["din"] == c["expand"] * s["d"], "mamba heads × head dim != expand × hidden"
    s["conv"] = s["din"] + 2 * s["G"] * s["N"]
    pattern = c["hybrid_override_pattern"]
    assert len(pattern) == c["num_hidden_layers"]
    s["n_mamba"], s["n_attn"], s["n_mlp"] = (pattern.count(k) for k in "M*-")
    return s


def layer_kinds(c: dict) -> list[str]:
    """The program's layer kinds for ``hybrid_override_pattern``: ``M-`` is
    one ``ssm_mlp``, ``*-`` one ``attn``, a lone ``M`` one ``ssm``."""
    kinds = {"M-": "ssm_mlp", "*-": "attn", "M": "ssm"}
    toks = re.findall(r"M-|\*-|M|\*|-", c["hybrid_override_pattern"])
    bad = [t for t in toks if t not in kinds]
    if bad:
        raise ValueError(f"no layer kind of the program serves {bad} alone")
    return [kinds[t] for t in toks]


def stages(c: dict) -> tuple:
    """``(repeat, unit)`` stages of the layer kinds: a run of one kind is a
    stage of its own, consecutive lone kinds one unit of repeat 1."""
    out: list = []
    for kind, same in itertools.groupby(layer_kinds(c)):
        run = len(list(same))
        if run > 1 or not out or out[-1][0] > 1:
            out.append((run, (kind,)))
        else:
            out[-1] = (1, out[-1][1] + (kind,))
    return tuple(out)


def _chain_of(spec: dict, in_dim: int, out_dim: int) -> Chain:
    return Chain(in_dim, out_dim, spec["n_factors"], spec["block"], spec["k"])


def chains(c: dict) -> dict[str, Chain]:
    """Every FAµST chain of the configuration by role."""
    s = sizes(c)
    return {
        "up": _chain_of(c["faust_mlp"], s["d"], s["F"]),
        "down": _chain_of(c["faust_mlp"], s["F"], s["d"]),
        "unembed": _chain_of(c["faust_unembed"], s["d"], s["V"]),
    }


def state_dims(c: dict) -> tuple[int, int, int]:
    """``(heads, head dim, state)``: the trailing dims of a Mamba-2 state."""
    s = sizes(c)
    return s["MH"], s["P"], s["N"]


def arch(c: dict):
    """The program's ``ArchConfig`` for this configuration."""
    from repro.configs.base import ArchConfig
    from repro.layers.faust_linear import FaustSpec
    from repro.layers.mamba2 import Mamba2Spec

    s = sizes(c)

    def spec(x):
        return FaustSpec(x["n_factors"], x["block"], x["k"])

    st = stages(c)
    return ArchConfig(
        name=c["name"], family="hybrid",
        n_layers=sum(r * len(u) for r, u in st), d_model=s["d"],
        n_heads=s["H"], n_kv_heads=s["KH"], head_dim=s["D"], d_ff=s["F"],
        vocab=s["V"], act="sq_relu", norm="rms", stages=st, rotary_pct=0.0,
        ssm=Mamba2Spec(d_model=s["d"], d_state=s["N"], d_conv=s["K"],
                       expand=c["expand"], headdim=s["P"], n_groups=s["G"],
                       chunk=s["chunk"]),
        faust_mlp=spec(c["faust_mlp"]), faust_unembed=spec(c["faust_unembed"]),
        tie_embeddings=False, dtype=c["dtype"], remat=False,
        attn_chunk=c.get("attn_chunk", 512),
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _dtype(c: dict):
    return jnp.bfloat16 if c["dtype"] == "bfloat16" else jnp.float32


def _mamba(key, c: dict, lead: tuple, dt) -> dict:
    s = sizes(c)
    ks = iter(jax.random.split(key, 10))
    f32 = jnp.float32
    lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])
    step = jnp.exp(jax.random.uniform(next(ks), lead + (s["MH"],), f32, lo, hi))
    return {
        "in_proj": _dense(next(ks), lead + (s["d"], 2 * s["din"] + 2 * s["G"] * s["N"] + s["MH"]), dt),
        "conv_w": (jax.random.normal(next(ks), lead + (s["K"], s["conv"]), f32)
                   / math.sqrt(s["K"])).astype(dt),
        "conv_b": _gain(next(ks), lead + (s["conv"],), dt),
        "a_log": jnp.log(jax.random.uniform(next(ks), lead + (s["MH"],), f32, 1.0, 16.0)),
        "d_skip": jax.random.uniform(next(ks), lead + (s["MH"],), f32, 0.5, 1.5),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # inverse softplus
        "norm_w": _gain(next(ks), lead + (s["din"],), dt),
        "out_proj": _dense(next(ks), lead + (s["din"], s["d"]), dt),
    }


def _attn(key, c: dict, lead: tuple, dt) -> dict:
    s = sizes(c)
    ks = jax.random.split(key, 4)
    return {
        "wq": _dense(ks[0], lead + (s["d"], s["H"] * s["D"]), dt),
        "wk": _dense(ks[1], lead + (s["d"], s["KH"] * s["D"]), dt),
        "wv": _dense(ks[2], lead + (s["d"], s["KH"] * s["D"]), dt),
        "wo": _dense(ks[3], lead + (s["H"] * s["D"], s["d"]), dt),
    }


def _layer(key, c: dict, kind: str, repeat: int, dt) -> dict:
    s, ch, lead = sizes(c), chains(c), (repeat,)
    ks = jax.random.split(key, 6)
    p = {}
    if kind in ("ssm", "ssm_mlp"):
        p["norm"] = _gain(ks[0], lead + (s["d"],), dt)
        p["mamba"] = _mamba(ks[1], c, lead, dt)
    else:
        p["norm1"] = _gain(ks[0], lead + (s["d"],), dt)
        p["attn"] = _attn(ks[1], c, lead, dt)
    if kind != "ssm":
        p["norm2"] = _gain(ks[2], lead + (s["d"],), dt)
        p["mlp"] = {"w_up": _chain_params(ks[3], ch["up"], lead, dt),
                    "w_down": _chain_params(ks[4], ch["down"], lead, dt)}
    return p


def _make(key, c: dict) -> dict:
    s, dt = sizes(c), _dtype(c)
    ks = iter(jax.random.split(key, 16))
    st = []
    for repeat, unit in stages(c):
        st.append([_layer(next(ks), c, kind, repeat, dt) for kind in unit])
    return {
        "embed": {"table": jax.random.normal(next(ks), (s["V"], s["d"]), dt)},
        "stages": st,
        "final_norm": _gain(next(ks), (s["d"],), dt),
        "unembed": {"faust": _chain_params(next(ks), chains(c)["unembed"], (), dt)},
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
#
# The reference walks the sequence in blocks of BLOCK tokens through every
# layer, carrying what each layer has seen so far: a Mamba layer's state
# and its last K-1 conv inputs, an attention layer's keys and values for
# every position before the block.  Within a block each Mamba layer steps
# its recurrence token by token.  So one compiled program serves every
# sequence length, and nothing larger than one block's activations, one
# layer's expanded weights and one column block of the unembedding is
# held at a time.


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, w):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return x * (1.0 + _f32(w))


def _chain(x, p, ch: Chain, quant: bool, upto: int | None = None):
    """``x`` through the chain's factors ``[:upto]``, each expanded dense."""
    dims = ch.dims()
    for i, f in enumerate(p["factors"][:upto]):
        x = _matmul(x, expand_factor(f["values"], f["in_idx"], dims[i], dims[i + 1]), quant)
    return x


def _mamba_block(p, x, state, s: dict, quant: bool):
    """One Mamba-2 mixer over a block; ``state`` = (conv tail (K-1, conv),
    h (MH, P, N)) before the block's first token."""
    tail, h0 = state
    t = x.shape[0]
    din, gn = s["din"], s["G"] * s["N"]
    zxbcdt = _matmul(x, _f32(p["in_proj"]), quant)
    z, xbc, dt = jnp.split(zxbcdt, [din, 2 * din + 2 * gn], axis=-1)
    xp = jnp.concatenate([tail, xbc], axis=0)
    w = _f32(p["conv_w"])
    conv = sum(xp[i : i + t] * w[i] for i in range(s["K"])) + _f32(p["conv_b"])
    u = jax.nn.silu(conv)
    xs = u[:, :din].reshape(t, s["MH"], s["P"])
    b = u[:, din : din + gn].reshape(t, s["G"], s["N"])
    cc = u[:, din + gn :].reshape(t, s["G"], s["N"])
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (t, MH)
    a = -jnp.exp(p["a_log"])
    rep = s["MH"] // s["G"]

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        bh = jnp.repeat(b_t, rep, axis=0)  # (MH, N): each head its group's B
        ch = jnp.repeat(c_t, rep, axis=0)
        h = jnp.exp(dt_t * a)[:, None, None] * h + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :]
        return h, jnp.sum(h * ch[:, None, :], axis=-1)

    h, y = jax.lax.scan(step, h0, (xs, b, cc, dt))
    y = (y + p["d_skip"][:, None] * xs).reshape(t, din)
    g = (y * jax.nn.silu(z)).reshape(t, s["G"], din // s["G"])
    g = _rms(g, p["norm_w"].reshape(s["G"], -1)).reshape(t, din)
    out = _matmul(g, _f32(p["out_proj"]), quant)
    return out, (xp[t:], h)


def _attn_block(p, x, state, p0, s: dict, quant: bool):
    """Causal attention of a block's queries over every position up to each
    one; ``state`` = (keys, values) of the whole context, (ctx, KH, D),
    filled before ``p0``."""
    kbuf, vbuf = state
    t, g = x.shape[0], s["H"] // s["KH"]
    q = _matmul(x, _f32(p["wq"]), quant).reshape(t, s["KH"], g, s["D"])
    k = _matmul(x, _f32(p["wk"]), quant).reshape(t, s["KH"], s["D"])
    v = _matmul(x, _f32(p["wv"]), quant).reshape(t, s["KH"], s["D"])
    kbuf = jax.lax.dynamic_update_slice_in_dim(kbuf, k, p0, axis=0)
    vbuf = jax.lax.dynamic_update_slice_in_dim(vbuf, v, p0, axis=0)
    kk, vv = kbuf, vbuf
    if quant:
        kk, vv = _fake_int8(kk, -1), _fake_int8(vv, 0)
    kpos = jnp.arange(kbuf.shape[0])
    qc = min(Q_CHUNK, t)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, 0)
        if quant:
            qi = _fake_int8(qi, -1)
        sc = jnp.einsum("qkgd,skd->kgqs", qi, kk) / math.sqrt(s["D"])
        qpos = p0 + i * qc + jnp.arange(qc)
        sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        if quant:
            w = _fake_int8(w, -1)
        return jnp.einsum("kgqs,skd->qkgd", w, vv)

    o = jax.lax.map(one, jnp.arange(t // qc)).reshape(t, s["H"] * s["D"])
    return _matmul(o, _f32(p["wo"]), quant), (kbuf, vbuf)


def _mlp_block(p, x, ch: dict, quant: bool):
    u = _chain(x, p["w_up"], ch["up"], quant) * _f32(p["w_up"]["lam"])
    r = jax.nn.relu(u)
    return _chain(r * r, p["w_down"], ch["down"], quant) * _f32(p["w_down"]["lam"])


def _layer_block(kind, lp, x, state, p0, s, ch, quant):
    if kind in ("ssm", "ssm_mlp"):
        y, state = _mamba_block(lp["mamba"], _rms(x, lp["norm"]), state, s, quant)
    else:
        y, state = _attn_block(lp["attn"], _rms(x, lp["norm1"]), state, p0, s, quant)
    x = x + y
    if kind != "ssm":
        x = x + _mlp_block(lp["mlp"], _rms(x, lp["norm2"]), ch, quant)
    return x, state


def _empty_state(c: dict) -> list:
    s = sizes(c)
    out = []
    for repeat, unit in stages(c):
        st = []
        for kind in unit:
            if kind == "attn":
                kv = jnp.zeros((repeat, s["ctx"], s["KH"], s["D"]), jnp.float32)
                st.append((kv, kv))
            else:
                st.append((jnp.zeros((repeat, s["K"] - 1, s["conv"]), jnp.float32),
                           jnp.zeros((repeat, s["MH"], s["P"], s["N"]), jnp.float32)))
        out.append(st)
    return out


def _hidden_block(params, state, tokens, p0, c: dict, quant: bool):
    """The final-normed hidden states of one block, and the carried state."""
    s, ch = sizes(c), chains(c)
    x = _f32(params["embed"]["table"][tokens])
    new_state = []
    for (repeat, unit), sp, ss in zip(stages(c), params["stages"], state):

        def body(x, xs, unit=unit):
            lps, lss = xs
            out = []
            for kind, lp, ls in zip(unit, lps, lss):
                x, ls = _layer_block(kind, lp, x, ls, p0, s, ch, quant)
                out.append(ls)
            return x, out

        x, ss = jax.lax.scan(body, x, (sp, ss))
        new_state.append(ss)
    return _rms(x, params["final_norm"]), new_state


def _col_blocks(c: dict) -> tuple[int, int]:
    """(output blocks per column block, column blocks) of the unembedding."""
    ch = chains(c)["unembed"]
    o = -(-ch.out_dim // ch.block)
    cb = min(COL_BLOCKS, o)
    return cb, -(-o // cb)


def _unembed_blocks(params, x, c: dict, quant: bool, fold, init):
    """Fold ``fold(acc, first column, logits)`` over the column blocks of
    the unembedding (its last factor expanded ``COL_BLOCKS`` output blocks
    at a time); columns past the vocabulary read ``-inf``."""
    ch = chains(c)["unembed"]
    u = params["unembed"]["faust"]
    lam = _f32(u["lam"])
    x = _chain(x, u, ch, quant, upto=-1)
    last = u["factors"][-1]
    o, b = last["values"].shape[0], ch.block
    cb, nb = _col_blocks(c)
    vals = jnp.pad(last["values"], ((0, nb * cb - o), (0, 0), (0, 0), (0, 0)))
    idx = jnp.pad(last["in_idx"], ((0, nb * cb - o), (0, 0)))
    in_f = ch.dims()[-2]

    def one(acc, j):
        vj = jax.lax.dynamic_slice_in_dim(vals, j * cb, cb, 0)
        ij = jax.lax.dynamic_slice_in_dim(idx, j * cb, cb, 0)
        lg = lam * _matmul(x, expand_factor(vj, ij, in_f, cb * b), quant)
        col = j * cb * b + jnp.arange(cb * b)
        lg = jnp.where(col[None, :] < ch.out_dim, lg, -jnp.inf)
        return fold(acc, j * cb * b, lg), None

    acc, _ = jax.lax.scan(one, init, jnp.arange(nb))
    return acc


@functools.partial(jax.jit, static_argnames=("c_key", "quant"))
def _gap_block(params, state, tokens, targets, p0, c_key, quant):
    c = _thaw(c_key)
    with jax.default_matmul_precision("highest"):
        x, state = _hidden_block(params, state, tokens, p0, c, quant)
        t = tokens.shape[0]

        def fold(acc, col0, lg):
            best, top, picked = acc
            m = jnp.max(lg, axis=-1)
            arg = col0 + jnp.argmax(lg, axis=-1).astype(jnp.int32)
            rel = targets - col0
            inside = (rel >= 0) & (rel < lg.shape[1])
            got = jnp.take_along_axis(lg, jnp.clip(rel, 0, lg.shape[1] - 1)[:, None], -1)[:, 0]
            return (jnp.maximum(best, m), jnp.where(m > best, arg, top),
                    jnp.where(inside, got, picked))

        init = (jnp.full((t,), -jnp.inf), jnp.zeros((t,), jnp.int32), jnp.zeros((t,)))
        best, top, picked = _unembed_blocks(params, x, c, quant, fold, init)
    return state, best - picked, top


@functools.partial(jax.jit, static_argnames=("c_key",))
def _logit_block(params, state, tokens, p0, c_key):
    c = _thaw(c_key)
    with jax.default_matmul_precision("highest"):
        x, state = _hidden_block(params, state, tokens, p0, c, False)
        ch = chains(c)["unembed"]
        cb, nb = _col_blocks(c)
        out = jnp.zeros((tokens.shape[0], nb * cb * ch.block), jnp.float32)

        def fold(acc, col0, lg):
            return jax.lax.dynamic_update_slice_in_dim(acc, lg, col0, 1)

        out = _unembed_blocks(params, x, c, False, fold, out)
    return state, out[:, : ch.out_dim]


def _blocks(n: int, c: dict):
    if n % BLOCK or n > sizes(c)["ctx"]:
        raise ValueError(f"{n} tokens: the reference takes a multiple of {BLOCK} "
                         f"up to the context, {sizes(c)['ctx']}")
    return range(0, n, BLOCK)


def reference_pass(params, c: dict, tokens, vision, targets, *, quant: bool = False):
    """Per position ``p`` of ``tokens`` (a multiple of 512 long): the gap
    ``max(logits[p]) − logits[p, targets[p]]`` of the float32 reference and
    its own argmax.  ``quant`` runs the control: every matrix product in
    symmetric int8 (weights per output column, activations per row, the
    Mamba projections and both attention products included; the
    recurrence itself stays float32)."""
    assert vision is None
    tokens = np.asarray(tokens, np.int32)
    targets = np.asarray(targets, np.int32)
    state, gaps, tops = _empty_state(c), [], []
    for p0 in _blocks(len(tokens), c):
        sl = slice(p0, p0 + BLOCK)
        state, g, t = _gap_block(params, state, jnp.asarray(tokens[sl]),
                                 jnp.asarray(targets[sl]), jnp.int32(p0), _freeze(c), quant)
        gaps.append(np.asarray(g))
        tops.append(np.asarray(t))
    return np.concatenate(gaps), np.concatenate(tops)


def reference_logits(params, c: dict, tokens) -> np.ndarray:
    """The float32 reference's whole logits ``(n, vocab)`` for ``tokens`` (a
    multiple of 512 long): for agreement tests at small sizes (the check
    itself, ``reference_pass``, never holds them)."""
    tokens = np.asarray(tokens, np.int32)
    state, out = _empty_state(c), []
    for p0 in _blocks(len(tokens), c):
        state, lg = _logit_block(params, state, jnp.asarray(tokens[p0 : p0 + BLOCK]),
                                 jnp.int32(p0), _freeze(c))
        out.append(np.asarray(lg))
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# required work
# ---------------------------------------------------------------------------


def _weights(c: dict) -> dict:
    """Stored weight elements by part, each counted once (FAµST chains as
    their stored values)."""
    s, ch = sizes(c), chains(c)
    mamba = (s["d"] * (2 * s["din"] + 2 * s["G"] * s["N"] + s["MH"])
             + s["K"] * s["conv"] + s["conv"] + s["din"] + s["din"] * s["d"])
    return {
        "mamba": float(s["n_mamba"] * mamba),
        "mamba_f32": float(s["n_mamba"] * 3 * s["MH"]),  # A_log, D, dt_bias
        "attn": float(s["n_attn"] * (s["d"] * (s["H"] + 2 * s["KH"]) * s["D"]
                                     + s["H"] * s["D"] * s["d"])),
        "mlp": float(s["n_mlp"] * (ch["up"].s_tot + ch["down"].s_tot)),
        "unembed": float(ch["unembed"].s_tot),
        "norms": float(s["d"] * (s["n_mamba"] + s["n_attn"] + s["n_mlp"] + 1)),
    }


def _state_elems(c: dict) -> int:
    s = sizes(c)
    return s["MH"] * s["P"] * s["N"]


def ssm_state_bytes(c: dict, kind: str, arg) -> float:
    """Bytes of Mamba-2 state (f32) one model call requires: a decode step
    reads and writes each live row's state once per Mamba layer
    (``arg``: tokens cached per live row); a prefill (``arg``: its
    length) writes each layer's final state once."""
    s = sizes(c)
    rows = 2 * len(arg) if kind == "decode" else 1
    return 4.0 * rows * s["n_mamba"] * _state_elems(c)


def decode_work(c: dict, context: list[int], elt: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over live rows whose caches hold
    ``context[i]`` tokens before the step: every weight once; per live row
    and Mamba layer its state read and written and its conv history read and
    rewritten; per live row the attention layers' own keys and values read
    over ``context[i] + 1`` positions and one new key and value written."""
    s, w = sizes(c), _weights(c)
    b = len(context)
    macs = w["mamba"] + w["attn"] + w["mlp"] + w["unembed"]
    ctx = float(sum(n + 1 for n in context))
    kv_tok = 2 * s["KH"] * s["D"]
    flops = 2.0 * b * macs
    flops += b * s["n_mamba"] * 5.0 * _state_elems(c)  # h update and C·h
    flops += s["n_attn"] * 4.0 * s["H"] * s["D"] * ctx
    byts = elt * (macs + w["norms"] + b * s["d"]) + 4.0 * w["mamba_f32"]
    byts += ssm_state_bytes(c, "decode", context)
    byts += elt * b * s["n_mamba"] * 2 * (s["K"] - 1) * s["conv"]
    byts += elt * s["n_attn"] * kv_tok * (ctx + b)
    byts += 4.0 * b * s["V"]  # f32 logits out
    return flops, byts


def prefill_work(c: dict, n: int, elt: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of prefilling one ``n``-token prompt: every weight
    once, the chunked SSD's products (within each chunk of ``chunk_size``
    tokens C·Bᵀ per group and its product with x per head; each chunk's
    state and its read-out), causal attention over ``n(n+1)/2`` query-key
    pairs, the final states, conv tails and keys and values written, and
    the last position unembedded."""
    s, w = sizes(c), _weights(c)
    t = min(s["chunk"], n)
    macs = w["mamba"] + w["attn"] + w["mlp"]
    ssd = n * (2.0 * s["G"] * t * s["N"] + s["MH"] * (2.0 * t * s["P"] + 4.0 * s["P"] * s["N"]))
    flops = 2.0 * n * macs + 2.0 * w["unembed"] + s["n_mamba"] * ssd
    flops += s["n_attn"] * 4.0 * s["H"] * s["D"] * n * (n + 1) / 2.0
    byts = elt * (macs + w["unembed"] + w["norms"] + n * s["d"]) + 4.0 * w["mamba_f32"]
    byts += ssm_state_bytes(c, "prefill", n)
    byts += elt * s["n_mamba"] * (s["K"] - 1) * s["conv"]
    byts += elt * s["n_attn"] * 2 * s["KH"] * s["D"] * n + 4.0 * s["V"]
    return flops, byts


def chain_calls_decode(c: dict, rows: int) -> list[tuple[str, int, int]]:
    """``(role, rows, calls)`` of the chain applies one decode step makes."""
    n = sizes(c)["n_mlp"]
    return [("up", rows, n), ("down", rows, n), ("unembed", rows, 1)]


def chain_calls_prefill(c: dict, n: int) -> list[tuple[str, int, int]]:
    """``(role, rows, calls)`` of the chain applies one ``n``-token prefill
    makes: the FFN chains at prompt width, the unembedding on the last row."""
    m = sizes(c)["n_mlp"]
    return [("up", n, m), ("down", n, m), ("unembed", 1, 1)]
