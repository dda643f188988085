"""Decode in place over the slot pool.

``LMExecutor`` decodes every row of its slot pool where it lies and
writes back only what one token changes (``lm.decode_delta`` +
``lm.scatter_cache_slots``).  Pinned here against the path it replaced —
gather the live rows, ``lm.decode_step`` them, scatter the whole rows
back — for a global-attention model, a sliding-window model whose ring
buffer wraps, and two Mamba hybrids (Mamba-2 beside a shared attention
block, and Mamba-2 over 8 groups with FFN pairs beside GQA attention):

* token-exact over 24 steps, with the live caches equal at the end;
* free slots' K, V, Mamba state and ``pos`` bit-identical across steps;
* logits come back in ``slots`` order for unsorted, non-contiguous slots;
* the compiled pool decode holds no pool-sized temporary.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.layers.attention import KVCache
from repro.models import lm
from repro.runtime.engine import Engine, LMExecutor

jax.config.update("jax_platform_name", "cpu")

N_SLOTS = 4
LIVE = [3, 0, 2]  # unsorted, non-contiguous; slot 1 holds a stale request
STEPS = 24


def _deep(cfg, unit):
    """``cfg`` with four repeats of ``unit``: one layer's slice of a stacked
    pool leaf is then a quarter of the leaf."""
    return dataclasses.replace(cfg, stages=((4, unit),), n_layers=4 * len(unit))


def _global_attn():
    return _deep(get_smoke("gemma_2b"), ("attn",))


def _sliding_window():
    cfg = get_smoke("gemma3_27b")  # local layers with a 16-token ring buffer
    assert cfg.window == 16
    return _deep(cfg, ("local", "attn"))


def _mamba_hybrid():
    cfg = get_smoke("zamba2_7b")  # Mamba layers and a shared attention block
    return _deep(cfg, ("ssm", "shared"))


def _nemotron_h():
    cfg = get_smoke("nemotron_h_47b")  # Mamba-2 over 8 groups with FFN pairs, and GQA
    return _deep(cfg, ("ssm_mlp", "attn"))


CASES = {
    "global_attn": _global_attn,
    "sliding_window": _sliding_window,
    "mamba_hybrid": _mamba_hybrid,
    "nemotron_h": _nemotron_h,
}


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _prefilled(case, max_len=40):
    """An executor whose four slots hold prompts of uneven length, and
    each slot's first greedy token."""
    cfg = CASES[case]()
    params = lm.init_model(jax.random.PRNGKey(0), cfg)
    ex = LMExecutor(cfg, params, max_len, n_slots=N_SLOTS)
    rng = np.random.default_rng(1)
    first, prompts = {}, {}
    for slot, n in enumerate([5, 7, 3, 6]):
        prompts[slot] = rng.integers(0, cfg.vocab, n).astype(np.int32)
        first[slot] = ex.sample(ex.prefill_forward(slot, prompts[slot], {}))[0]
    return cfg, ex, first, prompts


def _old_step(cfg):
    """The replaced decode: gather the live rows, step them, scatter the
    whole rows back."""

    def step(params, tokens, pool, slot_idx):
        caches = jax.tree_util.tree_map(lambda a: jnp.take(a, slot_idx, axis=1), pool)
        logits, caches = lm.decode_step(params, cfg, tokens, caches)
        pool = jax.tree_util.tree_map(lambda p, a: p.at[:, slot_idx].set(a), pool, caches)
        return logits, pool

    return jax.jit(step)


def _layer_caches(pool):
    return [c for c in jax.tree_util.tree_leaves(pool, is_leaf=lambda c: hasattr(c, "pos"))
            if hasattr(c, "pos")]


def _rows(pool, rows):
    return [np.asarray(leaf)[:, rows] for leaf in jax.tree_util.tree_leaves(pool)]


@pytest.mark.parametrize("case", list(CASES))
def test_pool_decode_token_exact_vs_gather_scatter(case):
    cfg, ex, first, prompts = _prefilled(case)
    old = _old_step(cfg)
    ref_pool = _copy(ex.pool)
    slot_idx = jnp.asarray(LIVE, jnp.int32)
    tok = np.stack([first[s] for s in LIVE])  # (B, 1)
    ref_tok, served, step_logits = tok, [tok], []
    for t in range(STEPS):
        lg = ex.decode_forward(LIVE, tok)
        tok = ex.sample(lg)
        step_logits.append(np.asarray(lg[:, 0], np.float32))
        ref_logits, ref_pool = old(ex.params, jnp.asarray(ref_tok), ref_pool, slot_idx)
        ref_tok = ex.sample(ref_logits)
        np.testing.assert_array_equal(tok, ref_tok, err_msg=f"{case} step {t}")
        served.append(tok)
    # and both are the greedy stream of the model itself, with no cache
    served = np.concatenate(served, axis=1)  # (B, STEPS + 1)
    step_logits = np.stack(step_logits, axis=1)  # (B, STEPS, V)
    for row, s in enumerate(LIVE):
        seq = np.concatenate([prompts[s], served[row]])
        n = len(prompts[s])
        padded = np.zeros(-(-len(seq) // cfg.attn_chunk) * cfg.attn_chunk, np.int32)
        padded[: len(seq)] = seq  # causal: the tail padding is never attended
        logits, _ = lm.forward_train(ex.params, cfg, {"tokens": jnp.asarray(padded)[None]})
        greedy = np.asarray(jnp.argmax(logits[0], axis=-1))[n - 1 : len(seq) - 1]
        np.testing.assert_array_equal(served[row], greedy, err_msg=f"{case} slot {s}")
        np.testing.assert_allclose(  # the decode steps' own logits, position by position
            step_logits[row], np.asarray(logits[0, n : len(seq) - 1]), rtol=5e-3, atol=5e-3,
            err_msg=f"{case} slot {s}",
        )
    for got, want in zip(_rows(ex.pool, LIVE), _rows(ref_pool, LIVE)):
        np.testing.assert_allclose(
            got.astype(np.float32), want.astype(np.float32), rtol=1e-2, atol=1e-2
        )
    if case == "sliding_window":  # the ring buffer wrapped for every live row
        kv = [c for c in _layer_caches(ex.pool) if isinstance(c, KVCache)]
        ring = min(c.k.shape[3] for c in kv)  # (repeat, B, KH, capacity, D)
        assert ring == cfg.window
        assert all(int(p) > ring for c in kv for p in np.asarray(c.pos)[:, LIVE].ravel())


@pytest.mark.parametrize("case", list(CASES))
def test_pool_decode_leaves_free_slots_untouched(case):
    cfg, ex, first, prompts = _prefilled(case)
    free = [s for s in range(N_SLOTS) if s not in LIVE]
    before = _rows(ex.pool, free)
    tok = np.stack([first[s] for s in LIVE])
    for _ in range(3):
        tok = ex.sample(ex.decode_forward(LIVE, tok))
    for b, a in zip(before, _rows(ex.pool, free)):
        np.testing.assert_array_equal(a, b)  # K, V, states and pos, bit for bit
    # the live rows did advance, from prompts of 3 to 6 tokens
    assert all((np.asarray(c.pos)[:, LIVE] >= 6).all() for c in _layer_caches(ex.pool))


@pytest.mark.parametrize("case", list(CASES))
def test_decode_forward_returns_rows_in_slots_order(case):
    cfg, ex, first, prompts = _prefilled(case)
    start = _copy(ex.pool)
    toks = {s: first[s] for s in LIVE}
    got = np.asarray(ex.decode_forward(LIVE, np.stack([toks[s] for s in LIVE])))
    assert got.shape[0] == len(LIVE)
    for s, row in zip(LIVE, got):  # each slot alone, from the same pool
        ex.pool = _copy(start)
        alone = np.asarray(ex.decode_forward([s], toks[s][None]))
        np.testing.assert_allclose(row, alone[0], rtol=1e-5, atol=1e-5)
    ex.pool = _copy(start)
    got_sorted = np.asarray(ex.decode_forward(sorted(LIVE), np.stack([toks[s] for s in sorted(LIVE)])))
    np.testing.assert_array_equal(got, got_sorted[np.argsort(np.argsort(LIVE))])


@pytest.mark.parametrize("case", list(CASES))
def test_pool_decode_holds_no_pool_sized_temporary(case):
    """A copy of an attention cache leaf — the gather or whole-row scatter
    the pool decode replaced — would show in the compiled program's
    temporaries (the CPU backend still copies one layer's K and V slices,
    half a leaf at four repeats)."""
    cfg, ex, _, _ = _prefilled(case, max_len=512)
    kv = [c for c in _layer_caches(ex.pool) if isinstance(c, KVCache)]
    leaf = max(c.k.nbytes for c in kv)
    tokens = jnp.zeros((N_SLOTS, 1), jnp.int32)
    slot_idx = jnp.asarray(LIVE + [N_SLOTS], jnp.int32)
    mem = ex._decode_fn.lower(ex.params, tokens, ex.pool, slot_idx).compile().memory_analysis()
    assert mem.temp_size_in_bytes < leaf, (mem.temp_size_in_bytes, leaf)
    old = _old_step(cfg).lower(ex.params, tokens[:3], ex.pool, slot_idx[:3]).compile()
    assert old.memory_analysis().temp_size_in_bytes >= leaf  # the check can fail


def test_make_caches_allocates_each_leaf_once():
    """The pool is made as stacked zeros: no per-layer arrays stacked
    afterwards, which briefly held it three times over on the device."""
    cfg = _mamba_hybrid()
    jaxpr = jax.make_jaxpr(lambda: lm.make_caches(cfg, N_SLOTS, 64))()
    assert {e.primitive.name for e in jaxpr.eqns} <= {"broadcast_in_dim"}
    assert len(jaxpr.eqns) == len(jax.tree_util.tree_leaves(jaxpr.out_avals))


def test_engine_counts_masked_rows():
    """Free slots decoded at the pool's width are counted per step."""
    cfg = _global_attn()
    params = lm.init_model(jax.random.PRNGKey(0), cfg)
    eng = Engine(LMExecutor(cfg, params, 24, n_slots=N_SLOTS))
    rng = np.random.default_rng(2)
    for n, budget in [(4, 5), (6, 2)]:
        eng.submit(rng.integers(0, cfg.vocab, n).astype(np.int32), budget)
    eng.run()
    want = sum((N_SLOTS - b) * steps for b, steps in eng.stats.occupancy.items())
    assert eng.stats.decode_masked_rows == want > 0
