"""Operator hot-swap into the serving runtime (repro.streaming.swap).

The load-bearing claims, each pinned here:
  * ``classify_swap`` separates values-only refreshes (same ChainPlan)
    from support changes (re-pack), and rejects chains a static serving
    ``FaustSpec`` cannot host;
  * a mid-stream values-only swap is *token-exact* for requests decoded
    after it — differential test against an engine that had the refreshed
    chain from the start;
  * a re-pack swap keeps serving (staged retrace) and ``dispatch_for``
    reports the new chain truthfully.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FaustOp
from repro.api import dispatch as dispatch_mod
from repro.configs import get_smoke
from repro.core.compress import BlockFaust, random_block_factor
from repro.layers.faust_linear import FaustSpec
from repro.models import lm
from repro.runtime.engine import Engine, LMExecutor
from repro.streaming.swap import classify_swap, hot_swap

jax.config.update("jax_platform_name", "cpu")


def _chain(k=2, dim=32, n_factors=2, seed=0, blk=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), n_factors)
    factors = tuple(
        random_block_factor(ks[i], dim, dim, blk, blk, k)
        for i in range(n_factors)
    )
    return BlockFaust(factors, jnp.float32(1.0))


def _perturb_values(bf, eps=0.01):
    return dataclasses.replace(
        bf,
        factors=tuple(
            dataclasses.replace(f, values=f.values * (1.0 + eps))
            for f in bf.factors
        ),
    )


def _move_support(bf):
    """Same shapes / same s_tot, different in_idx contents."""
    f0 = bf.factors[0]
    moved = dataclasses.replace(
        f0, in_idx=(f0.in_idx + 1) % (f0.in_features // f0.bk)
    )
    return dataclasses.replace(bf, factors=(moved,) + bf.factors[1:])


# --- classification ---------------------------------------------------------


def test_classify_values_only():
    bf = _chain()
    assert classify_swap(bf, _perturb_values(bf)) == "values_only"
    # bit-identical chain is trivially values-only
    assert classify_swap(bf, bf) == "values_only"


def test_classify_repack_on_support_change():
    bf = _chain()
    assert classify_swap(bf, _chain(k=3)) == "repack"  # shapes moved
    moved = _move_support(bf)
    assert moved.s_tot == bf.s_tot
    assert classify_swap(bf, moved) == "repack"  # same budget, moved support


def test_classify_rejects_incompatible_chains():
    bf = _chain()
    with pytest.raises(ValueError, match="chain length"):
        classify_swap(bf, _chain(n_factors=3))
    with pytest.raises(ValueError, match="shape|feature dims"):
        classify_swap(bf, _chain(dim=64))


# --- serving differential ---------------------------------------------------


@functools.lru_cache(maxsize=None)
def _model(k=2):
    cfg = dataclasses.replace(
        get_smoke("gemma_2b"),
        faust_unembed=FaustSpec(n_factors=2, block=16, k=k),
        tie_embeddings=False,
    )
    return cfg, lm.init_model(jax.random.PRNGKey(0), cfg)


_PROMPTS = [
    np.random.default_rng(1).integers(1, 100, size=8) for _ in range(4)
]


def _engine(k=2):
    cfg, params = _model(k)
    eng = Engine(LMExecutor(cfg, params, max_len=24, n_slots=2))
    for i, p in enumerate(_PROMPTS):
        eng.submit(p, max_new_tokens=8, rid=f"r{i}")
    return eng


def test_values_only_swap_token_exact_mid_stream():
    """Greedy decode of requests admitted after a mid-stream values-only
    swap equals an engine that served the refreshed chain from step 0."""
    eng = _engine()
    old = eng.executor.unembed_blockfaust()
    new = _perturb_values(old)

    # serve the first wave under the old chain; r2/r3 still queued
    while eng.stats.completed < 2:
        eng.step()
    assert eng.n_pending == 2
    report = hot_swap(eng, new)
    assert report.kind == "values_only"
    assert not report.retrace
    assert report.s_tot_before == report.s_tot_after
    assert eng.stats.swaps == 1
    eng.run()

    # oracle: refreshed chain from the start, identical submissions —
    # completion is length-driven, so the slot schedule is identical too
    oracle = _engine()
    hot_swap(oracle, new)
    oracle.run()
    for rid in ("r2", "r3"):
        np.testing.assert_array_equal(eng.result(rid), oracle.result(rid))


def test_repack_swap_keeps_serving_and_reprices():
    eng = _engine()
    cfg3, params3 = _model(k=3)
    new = LMExecutor(cfg3, params3, max_len=24, n_slots=2).unembed_blockfaust()
    while eng.stats.completed < 2:
        eng.step()
    report = hot_swap(eng, new)
    assert report.kind == "repack"
    assert report.retrace  # values shapes changed → next step retraces
    assert report.s_tot_after > report.s_tot_before
    eng.run()
    assert eng.stats.completed == 4  # in-flight requests all finished
    # the advisory op (what the scheduler logs per step) tracks the swap
    assert eng.executor.dispatch_for(2).s_tot == new.s_tot


def test_hot_swap_requires_faust_unembed():
    cfg = dataclasses.replace(get_smoke("gemma_2b"), n_layers=1,
                              stages=((1, ("attn",)),))
    params = lm.init_model(jax.random.PRNGKey(0), cfg)
    ex = LMExecutor(cfg, params, max_len=16, n_slots=1)
    assert ex.unembed_blockfaust() is None
    with pytest.raises(ValueError, match="no FAµST unembedding"):
        hot_swap(ex, _chain())
    with pytest.raises(TypeError, match="cannot hot-swap"):
        hot_swap(object(), _chain())


def test_server_swap_unembed():
    from repro.runtime.server import Server

    cfg, params = _model()
    srv = Server(cfg, params, max_len=24)
    batch = {"tokens": np.stack([_PROMPTS[0]])}
    out1, _ = srv.generate(batch, 4)
    old = srv.unembed_blockfaust()
    report = hot_swap(srv, _perturb_values(old, eps=0.5))
    assert report.kind == "values_only"
    out2, _ = srv.generate(batch, 4)
    assert out1.shape == out2.shape
    # and the published chain actually changed
    np.testing.assert_allclose(
        np.asarray(srv.unembed_blockfaust().factors[0].values),
        np.asarray(old.factors[0].values) * 1.5, rtol=1e-6,
    )
