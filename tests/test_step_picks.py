"""Greedy picks and the NaN guard taken inside the step's own program.

``LMExecutor``'s decode and prefill programs return, beside the logits,
each row's greedy token per codebook and its all-finite flag, and
``sample`` / ``row_finite`` answer for the logits the last step returned
from one host copy of them.  Pinned here:

* the program's picks equal the eager ``sample`` and ``row_finite`` on the
  same logits, exactly, with every slot live, with fewer live rows than
  slots, through the ragged-tail prefill replay, with two codebooks, and
  where every logit ties;
* a NaN-poisoned unembedding published through ``swap_unembed`` clears the
  program's own finite flag, and the engine quarantines the streams;
* the ``FaultInjector``'s NaN-poisoned copy still quarantines, through the
  eager path;
* a clean engine run serves every pick from the program and the same
  tokens as an engine whose picks are all eager.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.layers.faust_linear import FaustSpec
from repro.models import lm
from repro.runtime.engine import DONE, FAILED, Engine, LMExecutor
from repro.runtime.faults import FaultInjector, FaultSpec, regressed_chain

jax.config.update("jax_platform_name", "cpu")

MAX_LEN = 40


def _executor(arch, n_slots, zero_unembed=False):
    cfg = get_smoke(arch)
    params = lm.init_model(jax.random.PRNGKey(0), cfg)
    if zero_unembed:  # every logit 0: each row's argmax is a tie over the vocabulary
        params = {**params, "unembed": jax.tree_util.tree_map(jnp.zeros_like, params["unembed"])}
    return LMExecutor(cfg, params, MAX_LEN, n_slots=n_slots)


def _prompt(cfg, n, seed):
    rng = np.random.default_rng(seed)
    shape = (cfg.n_codebooks, n) if cfg.n_codebooks > 1 else (n,)
    return rng.integers(1, cfg.vocab, shape).astype(np.int32)


def _check_picks(ex, logits):
    """The program's picks for ``logits`` against the eager path on a copy
    of the same array; returns the tokens."""
    fused, eager = ex.picks_fused, ex.picks_eager
    tok, fin = ex.sample(logits), ex.row_finite(logits)
    assert (ex.picks_fused, ex.picks_eager) == (fused + 2, eager)
    copy = jnp.array(logits, copy=True)
    want_tok, want_fin = ex.sample(copy), ex.row_finite(copy)
    assert (ex.picks_fused, ex.picks_eager) == (fused + 2, eager + 2)
    assert tok.dtype == want_tok.dtype == np.int32 and fin.dtype == want_fin.dtype == bool
    np.testing.assert_array_equal(tok, want_tok)
    np.testing.assert_array_equal(fin, want_fin)
    b = logits.shape[0]
    k = ex.cfg.n_codebooks
    assert tok.shape == ((b, k, 1) if k > 1 else (b, 1)) and fin.shape == (b,)
    return tok


# (arch, n_slots, live slots, prompt lengths by slot, zero unembedding)
PICK_CASES = {
    "all_live": ("gemma_2b", 3, [0, 1, 2], [5, 7, 3], False),
    "fewer_live": ("gemma_2b", 4, [3, 0], [5, 0, 0, 6], False),
    # 19 tokens at attn_chunk 8: prefill 16, replay 3 through the decode step
    "ragged_tail": ("zamba2_7b", 2, [1, 0], [19, 11], False),
    "codebooks": ("musicgen_medium", 3, [2, 0], [5, 0, 6], False),
    "ties": ("zamba2_7b", 3, [0, 2], [4, 0, 6], True),
}


@pytest.mark.parametrize("case", list(PICK_CASES))
def test_program_picks_equal_eager_sample_and_guard(case):
    arch, n_slots, live, lengths, zero = PICK_CASES[case]
    ex = _executor(arch, n_slots, zero_unembed=zero)
    first = {}
    for slot in live:
        first[slot] = _check_picks(ex, ex.prefill_forward(slot, _prompt(ex.cfg, lengths[slot], slot), {}))[0]
    tok = np.stack([first[s] for s in live])
    for _ in range(3):
        tok = _check_picks(ex, ex.decode_forward(live, tok))
    if zero:
        assert not tok.any()  # a tie goes to the first index on both paths
    assert ex.picks_eager == ex.picks_fused  # each fused call was checked once


def _faust_executor(n_slots=2):
    cfg = dataclasses.replace(
        get_smoke("gemma_2b"), tie_embeddings=False,
        faust_unembed=FaustSpec(n_factors=2, block=16, k=2),
    )
    return LMExecutor(cfg, lm.init_model(jax.random.PRNGKey(0), cfg), 24, n_slots=n_slots)


def test_nan_unembedding_swap_quarantines_through_the_program_flag():
    ex = _faust_executor()
    eng = Engine(ex)
    for i in range(2):
        eng.submit(_prompt(ex.cfg, 8, i), max_new_tokens=8, rid=f"r{i}")
    eng.step()  # both admitted and decoded once on the clean chain
    assert eng.stats.quarantined == 0 and len(eng.running) == 2
    ex.swap_unembed(regressed_chain(ex.unembed_blockfaust(), nan=True))
    eng.run()
    assert [eng.status(f"r{i}") for i in range(2)] == [FAILED, FAILED]
    assert eng.stats.quarantined == 2
    assert ex.picks_eager == 0  # the decode program's own flag caught it
    assert not ex.row_finite(ex._step_picks[0]).any()


class _EagerPicks:
    """An executor whose logits are copies, so that every pick is eager."""

    def __init__(self, ex):
        self.inner, self.n_slots = ex, ex.n_slots

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def prefill_forward(self, slot, prompt, extras):
        return jnp.array(self.inner.prefill_forward(slot, prompt, extras), copy=True)

    def decode_forward(self, slots, tokens):
        return jnp.array(self.inner.decode_forward(slots, tokens), copy=True)


def _serve(wrap, faults=()):
    """Three streams on two slots, the second evicted after its third token
    (re-admitted at 11 tokens: prefill 8, replay 3)."""
    ex = _faust_executor()
    served = FaultInjector(ex, faults) if wrap == "injector" else ex
    eng = Engine(_EagerPicks(served) if wrap == "eager" else served)
    for i in range(3):
        eng.submit(_prompt(ex.cfg, 8, 10 + i), max_new_tokens=6, rid=f"r{i}")
    evicted = False
    while eng.n_pending:
        eng.step()
        r1 = eng.running.get("r1")
        if not evicted and r1 is not None and len(r1.generated) == 3:
            evicted = eng.evict("r1")
    assert evicted
    return ex, eng


def test_injected_nan_logits_quarantine_through_the_eager_path():
    ex, eng = _serve("injector", [FaultSpec("nan_logits", step=1, op="decode", rid="r0")])
    assert eng.status("r0") == FAILED and eng.stats.quarantined == 1
    assert eng.status("r1") == eng.status("r2") == DONE
    assert ex.picks_eager == 2  # the poisoned copy's sample and guard
    _, clean = _serve("eager")
    for rid in ("r1", "r2"):
        np.testing.assert_array_equal(eng.result(rid), clean.result(rid))


@pytest.mark.parametrize("wrap", ["bare", "injector"])
def test_clean_run_serves_every_pick_from_the_program(wrap):
    ex, eng = _serve(wrap)
    assert ex.picks_eager == 0
    assert ex.picks_fused == 2 * (eng.stats.admitted + eng.stats.steps)
    ref_ex, ref = _serve("eager")
    assert ref_ex.picks_fused == 0 and ref_ex.picks_eager == ex.picks_fused
    for rid in ("r0", "r1", "r2"):  # the same tokens as eager picking
        np.testing.assert_array_equal(eng.result(rid), ref.result(rid))
