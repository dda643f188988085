"""Nemotron-H's hybrid block against the benchmark's plain reference.

* The Mamba-2 gated RMSNorm normalises each B/C group of channels on its
  own; one group keeps the whole-width expression bit for bit.
* ``nemotron_h_47b.smoke_config()`` (float32, 8 groups, ``ssm_mlp`` and
  ``attn`` stages, FAµST FFN and unembedding chains) served through
  ``LMExecutor`` — a prefill, then decode steps through the slot pool —
  agrees on logits with ``bench/families/faust_hybrid.py``'s reference
  full forward pass, which steps Mamba-2 token by token and expands every
  FAµST factor to a dense matrix.
* The family's ``arch()`` at the benchmark's sizes is ``CONFIG`` with the
  FAµST chains the configuration file assumes.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.layers import mamba2 as M
from repro.layers.faust_linear import FaustSpec
from repro.layers.norms import rmsnorm
from repro.layers.param import split_annotations
from repro.runtime.engine import LMExecutor

jax.config.update("jax_platform_name", "cpu")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.families import faust_hybrid as fam  # noqa: E402

FAUST = {"n_factors": 2, "block": 16, "k": 2}
# smoke_config() in the published config.json's keys, with FAµST chains
# (vocab 109 leaves the unembedding's last 16-wide block ragged)
SMOKE = {
    "name": "nemotron-h-47b", "family": "faust_hybrid", "hidden_size": 64,
    "num_attention_heads": 8, "num_key_value_heads": 4, "attention_head_dim": 16,
    "intermediate_size": 128, "vocab_size": 109, "mamba_num_heads": 16,
    "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 8, "conv_kernel": 4,
    "chunk_size": 8, "expand": 2, "max_position_embeddings": 512,
    "num_hidden_layers": 11, "hybrid_override_pattern": "M-M-M-M-M*-",
    "time_step_min": 0.001, "time_step_max": 0.1, "attn_chunk": 8,
    "dtype": "float32", "faust_mlp": FAUST, "faust_unembed": FAUST,
}


def _same_model(arch, cfg) -> bool:
    """``arch`` is ``cfg`` with FAµST chains (name and remat aside)."""
    plain = dataclasses.replace(arch, name=cfg.name, faust_mlp=None, faust_unembed=None,
                                remat=cfg.remat, policy_decode=cfg.policy_decode)
    return plain == cfg


def test_family_arch_is_the_config_at_benchmark_sizes():
    path = os.path.join(ROOT, "bench", "configs", "nemotron_h_47b.faust_mlp_unembed.json")
    c = json.load(open(path))
    arch = fam.arch(c)
    assert _same_model(arch, get_config("nemotron_h_47b"))
    assert arch.faust_mlp == arch.faust_unembed == FaustSpec(2, 128, 8)
    assert arch.stages == ((4, ("ssm_mlp",)), (1, ("ssm", "attn")))
    assert _same_model(fam.arch(SMOKE), get_smoke("nemotron_h_47b"))


def test_grouped_norm_is_per_group():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.normal(ks[0], (2, 3, 64))
    z = jax.random.normal(ks[1], (2, 3, 64))
    w = jax.random.normal(ks[2], (64,)) * 0.1
    got = np.asarray(M.gated_norm(w, y, z, 8))
    gated = np.asarray(y * jax.nn.silu(z), np.float64)
    for g in range(8):
        sl = slice(8 * g, 8 * g + 8)
        part = gated[..., sl]
        want = part / np.sqrt(np.mean(part * part, axis=-1, keepdims=True) + 1e-6)
        want = want * (1.0 + np.asarray(w[sl], np.float64))
        np.testing.assert_allclose(got[..., sl], want, rtol=1e-5, atol=1e-6)
    whole = np.asarray(M.gated_norm(w, y, z, 1))
    assert not np.allclose(got, whole, atol=1e-3)  # the groups do change the result


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_group_norm_is_bit_identical_to_the_whole_width_norm(dtype):
    """What Mamba-2 2.7B and Zamba2 (one group) computed before, under jit."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    y = jax.random.normal(ks[0], (2, 5, 128), dtype)
    z = jax.random.normal(ks[1], (2, 5, 128), dtype)
    w = (jax.random.normal(ks[2], (128,)) * 0.1).astype(dtype)
    before = jax.jit(lambda w, y, z: rmsnorm(w, y * jax.nn.silu(z)))(w, y, z)
    now = jax.jit(lambda w, y, z: M.gated_norm(w, y, z, 1))(w, y, z)
    np.testing.assert_array_equal(np.asarray(now), np.asarray(before))


def test_mamba_block_with_one_group_is_unchanged(monkeypatch):
    """A whole one-group Mamba-2 block, jitted, against the same block with
    the norm written as before."""
    spec = get_smoke("mamba2_2p7b").ssm
    p, _ = split_annotations(M.mamba2_init(jax.random.PRNGKey(2), spec, jnp.bfloat16))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, spec.d_model), jnp.bfloat16)
    now, _ = jax.jit(lambda p, x: M.mamba2_apply(p, x, spec))(p, x)
    monkeypatch.setattr(M, "gated_norm", lambda w, y, z, groups: rmsnorm(w, y * jax.nn.silu(z)))
    before, _ = jax.jit(lambda p, x: M.mamba2_apply(p, x, spec))(p, x)
    np.testing.assert_array_equal(np.asarray(now), np.asarray(before))


# float32 program against the float32 reference: they differ by the chunked
# SSD against the token-by-token recurrence, the fused chain kernel against
# dense expansions and the order of f32 sums — 2.4e-6 at most on logits of
# size ~1; 2e-5 leaves ten times that and no room for a wrong group, gate
# or state.
TOL = 2e-5


def test_served_logits_agree_with_the_reference():
    c = SMOKE
    params = fam.make_params(c, 5)
    ex = LMExecutor(fam.arch(c), params, max_len=64, n_slots=3)
    rng = np.random.default_rng(0)
    prompts = {0: rng.integers(0, c["vocab_size"], 24).astype(np.int32),
               2: rng.integers(0, c["vocab_size"], 16).astype(np.int32)}
    served = {s: [] for s in prompts}
    logits = {s: [] for s in prompts}
    for s, prompt in prompts.items():
        lg = ex.prefill_forward(s, prompt, {})
        logits[s].append(np.asarray(lg[0, -1]))
        served[s].append(int(ex.sample(lg)[0, 0]))
    slots = [2, 0]
    for _ in range(10):
        tok = np.asarray([[served[s][-1]] for s in slots], np.int32)
        lg = ex.decode_forward(slots, tok)
        for row, s in enumerate(slots):
            logits[s].append(np.asarray(lg[row, -1]))
            served[s].append(int(ex.sample(lg[row : row + 1])[0, 0]))
    for s, prompt in prompts.items():
        seq = np.concatenate([prompt, served[s][:-1]]).astype(np.int32)
        padded = np.zeros(512, np.int32)
        padded[: len(seq)] = seq
        want = fam.reference_logits(params, c, padded)[len(prompt) - 1 : len(seq)]
        got = np.stack(logits[s])
        assert got.shape == want.shape == (11, c["vocab_size"])
        np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL, err_msg=f"slot {s}")
        assert np.abs(want).max() > 0.5  # logits are not degenerate
