"""``correct`` comes out false when the timed path is broken underneath.

The whole run is driven on the CPU at toy size (the look for a chip is the
entry's, not the harness's): a token altered where it is produced, and a
decode step that returns the cache pool unchanged, each fail the logit-gap
check against the plain reference.  A cell on one chip has no exchange
between chips, and serving takes no mean over a batch, so those faults do
not apply here.
"""
import time

import numpy as np
import pytest

import bench_tiny
from bench import harness


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


FLOORS = ("requests_compared", "tokens_compared")
# a vision prefix in place of the first embeddings, as a VQA mix sends
VISION_CONFIG = dict(bench_tiny.CONFIG, n_vision_tokens=6)
VISION_TRAFFIC = dict(bench_tiny.CLOSED, vision=True,
                      prompt=dict(bench_tiny.CLOSED["prompt"], max=26))


def _run(seed=7, **cell):
    cell = bench_tiny.cell(**cell)
    return harness.run(cell.name, seed, 1.0, False, started=time.time(), cell=cell)


def _over_limit(res) -> list:
    """The compared numbers a run failed (the counts compared are floors)."""
    out = []
    for name, chk in res["checks"].items():
        bad = chk["value"] < chk["limit"] if name in FLOORS else chk["value"] > chk["limit"]
        if bad:
            out.append(name)
    return out


@pytest.mark.parametrize("prefix", ["text", "vision"])
def test_sound_run_is_correct(no_cache, prefix):
    res = _run() if prefix == "text" else _run(config=VISION_CONFIG, traffic=VISION_TRAFFIC)
    assert res["correct"] is True
    assert _over_limit(res) == []
    assert res["checks"]["requests_compared"]["value"] >= bench_tiny.CHECK["min_requests"]


def test_sample_spans_several_requests():
    """The longest request first, then others drawn from the seed until both
    the request and the token floors are met."""
    from bench.generator import Spec

    recs = []
    for i, n in enumerate([900, 5, 40, 7, 300, 12, 60, 3]):
        r = harness.Rec(Spec(i, 4, n), f"q{i}", due=0.0, sent=0.0)
        r.tokens = [1] * n
        r.state = "done" if i % 2 else "running"
        recs.append(r)
    failed = harness.Rec(Spec(8, 4, 50), "q8", due=0.0, sent=0.0)
    failed.tokens, failed.state = [1] * 50, "failed"
    recs.append(failed)
    check = {"min_requests": 4, "min_tokens": 100, "max_requests": 6}
    picked = harness.sample_for_check(recs, check, seed=2**33 + 5)
    assert picked[0].rid == "q0"
    assert len(picked) >= 4 and "q8" not in {r.rid for r in picked}
    assert picked == harness.sample_for_check(recs, check, seed=2**33 + 5)
    # the token floor is counted beyond the longest: more requests until it is met
    check = {"min_requests": 2, "min_tokens": 1300, "max_requests": 6}
    assert len(harness.sample_for_check(recs, check, seed=1)) == 6


def test_altered_token_is_caught(no_cache, monkeypatch):
    from repro.runtime.engine import LMExecutor

    sample = LMExecutor.sample

    def shifted(self, logits):
        tok = sample(self, logits)
        return (tok + 1) % bench_tiny.CONFIG["vocab"]

    monkeypatch.setattr(LMExecutor, "sample", shifted)
    res = _run()
    assert res["correct"] is False
    assert set(_over_limit(res)) & {"logit_gap", "logit_gap_mean"}


def test_state_left_unchanged_is_caught(no_cache, monkeypatch):
    from repro.models import lm

    monkeypatch.setattr(lm, "scatter_cache_slots", lambda pool, caches, idx: pool)
    res = _run()
    assert res["correct"] is False
    assert set(_over_limit(res)) & {"logit_gap", "logit_gap_mean"}


def test_reference_matches_program_prefill_logits(no_cache):
    """The plain reference and the program agree on a prompt's last-position
    logits (bf16 program against the f32 reference)."""
    import jax.numpy as jnp

    from repro.runtime.engine import LMExecutor

    cell = bench_tiny.cell()
    c = cell.config
    params = cell.family.make_params(c, 3)
    ex = LMExecutor(cell.family.arch(c), params, max_len=64, n_slots=1)
    tokens = np.random.default_rng(0).integers(0, c["vocab"], 32).astype(np.int32)
    got = np.asarray(ex.prefill_forward(0, tokens, {}), np.float32)[0, -1]
    padded = np.zeros(512, np.int32)
    padded[:32] = tokens
    _, top = cell.family.reference_pass(params, c, padded, None, padded)
    gaps, _ = cell.family.reference_pass(params, c, padded, None, np.full(512, int(got.argmax())))
    assert gaps[31] < 0.05
    assert float(jnp.max(jnp.abs(got))) > 0.5  # logits are not degenerate
