"""Scheduler-simulation suite for the continuous-batching engine.

Two layers:

1. **Pure-sim scripted traces** (FakeClock + SimExecutor, zero jax, zero
   wall-clock): differential token parity against the closed-form
   single-stream oracle across staggered arrivals, early finishes, slot
   reuse and eviction/re-admission; full-run determinism including
   stats; slot-hygiene guards.

2. **Real-model differential traces**: the engine serving N interleaved
   requests must be *token-exact* against N independent single-request
   ``Server.generate`` oracle runs (greedy decode is bit-identical
   regardless of batching schedule) — the ISSUE-7 acceptance criterion,
   over ≥3 scripted traces (staggered arrival, early finish, slot
   reuse), plus a multi-codebook trace and a multi-device parity case
   (run by the ci.sh multi-device leg under
   ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from engine_sim import FakeClock, SimExecutor, reference_stream
from repro.configs import get_smoke
from repro.models import lm
from repro.runtime.engine import Engine, LMExecutor
from repro.runtime.server import Server

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Pure-sim scripted traces
# ---------------------------------------------------------------------------


def _sim_engine(n_slots=3, max_len=64, tick=0.001, seed=0):
    clock = FakeClock(tick=tick)
    ex = SimExecutor(n_slots=n_slots, max_len=max_len, seed=seed)
    return Engine(ex, clock=clock), ex, clock


def _prompt(rng, n, vocab=97):
    return rng.integers(0, vocab, size=n).astype(np.int32)


def _check_parity(engine, ex, rids, prompts, budgets):
    for rid, p, n in zip(rids, prompts, budgets):
        want = reference_stream(p, n, ex.mix, ex.vocab)
        np.testing.assert_array_equal(engine.result(rid), want)


def test_sim_trace_staggered_arrivals():
    """Trace 1: requests arrive mid-stream of earlier ones; every stream
    still matches its single-stream oracle."""
    engine, ex, clock = _sim_engine(n_slots=3)
    rng = np.random.default_rng(1)
    prompts = [_prompt(rng, n) for n in (5, 3, 7)]
    budgets = [6, 4, 3]
    rids = [engine.submit(prompts[0], budgets[0])]
    engine.step()
    engine.step()  # r0 two tokens in…
    clock.advance(0.5)
    rids.append(engine.submit(prompts[1], budgets[1]))  # …r1 arrives
    engine.step()
    clock.advance(0.5)
    rids.append(engine.submit(prompts[2], budgets[2]))  # …then r2
    engine.run()
    _check_parity(engine, ex, rids, prompts, budgets)
    # batching actually happened: some steps ran 2- and 3-wide
    assert set(engine.stats.occupancy) >= {2, 3}
    # staggered admission is visible on the (fake) clock: first tokens
    # land strictly later for later arrivals
    first_ts = [engine.done[r].first_token_t for r in rids]
    assert first_ts[0] < first_ts[1] < first_ts[2]
    assert all(t >= 0 for t in engine.stats.ttft_s.values())


def test_sim_trace_early_finish():
    """Trace 2: a short-budget request completes mid-stream; the survivor
    decodes on at smaller batch, token-exact, and the slot frees."""
    engine, ex, _ = _sim_engine(n_slots=2)
    rng = np.random.default_rng(2)
    prompts = [_prompt(rng, 4), _prompt(rng, 6)]
    budgets = [2, 9]
    rids = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
    finished_order = []
    while engine.n_pending:
        finished_order.extend(engine.step())
    _check_parity(engine, ex, rids, prompts, budgets)
    assert finished_order == [rids[0], rids[1]]
    # the batch breathed: 2-wide while both live, 1-wide after
    assert engine.stats.occupancy.get(2, 0) >= 1
    assert engine.stats.occupancy.get(1, 0) >= 1
    assert engine.allocator.n_free == 2


def test_sim_trace_slot_reuse():
    """Trace 3: more requests than slots — the queue drains through
    reused slots; all streams exact; the allocator stayed within pool."""
    engine, ex, _ = _sim_engine(n_slots=2)
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, n) for n in (4, 5, 3, 6, 2)]
    budgets = [3, 5, 2, 4, 6]
    rids = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
    engine.run()
    _check_parity(engine, ex, rids, prompts, budgets)
    prefill_slots = [slots[0] for op, slots in ex.calls if op == "prefill"]
    assert len(prefill_slots) == 5 and set(prefill_slots) <= {0, 1}
    # at least one slot served multiple requests (freed then re-assigned)
    assert max(np.bincount(prefill_slots)) >= 2
    assert engine.stats.admitted == 5 and engine.stats.completed == 5


def test_sim_eviction_readmission_token_exact():
    """Preemption is invisible in the output: evict a mid-stream request,
    let another take its slot, re-admit, and the stream is still exact."""
    engine, ex, _ = _sim_engine(n_slots=2)
    rng = np.random.default_rng(4)
    prompts = [_prompt(rng, 5), _prompt(rng, 4), _prompt(rng, 3)]
    budgets = [8, 6, 2]
    r0 = engine.submit(prompts[0], budgets[0])
    r1 = engine.submit(prompts[1], budgets[1])
    engine.step()
    engine.step()  # both streams mid-flight
    engine.evict(r0)  # preempt r0; its slot is free
    r2 = engine.submit(prompts[2], budgets[2])
    # r0 is at the *front* of the queue: it re-admits before r2
    engine.step()
    assert engine.running[r0].slot is not None
    engine.run()
    _check_parity(engine, ex, [r0, r1, r2], prompts, budgets)
    assert engine.stats.evicted == 1
    assert engine.done[r0].n_evictions == 1
    # re-admission re-prefilled: 3 requests, 4 prefills
    assert sum(1 for op, _ in ex.calls if op == "prefill") == 4


def test_sim_determinism_bitwise():
    """Same scripted trace twice from scratch ⇒ identical tokens, stats,
    slot schedule and timings (FakeClock ⇒ zero wall-clock dependence)."""

    def run_once():
        engine, ex, clock = _sim_engine(n_slots=2, tick=0.01)
        rng = np.random.default_rng(5)
        prompts = [_prompt(rng, n) for n in (4, 6, 3)]
        rids = [engine.submit(prompts[0], 5)]
        engine.step()
        clock.advance(1.0)
        rids.append(engine.submit(prompts[1], 3))
        engine.step()
        rids.append(engine.submit(prompts[2], 4))
        engine.evict(rids[0])
        engine.run()
        outs = [engine.result(r) for r in rids]
        s = engine.stats
        return outs, (
            s.tokens_decoded, s.steps, s.admitted, s.completed, s.evicted,
            s.queue_depth_max, s.queue_depth_sum, tuple(sorted(s.occupancy.items())),
            tuple(sorted(s.ttft_s.items())), tuple(sorted(s.tpot_s.items())),
        ), ex.calls

    out_a, stats_a, calls_a = run_once()
    out_b, stats_b, calls_b = run_once()
    for a, b in zip(out_a, out_b):
        np.testing.assert_array_equal(a, b)
    assert stats_a == stats_b
    assert calls_a == calls_b


def test_sim_stats_accounting():
    """tokens_decoded counts the prefill-sampled token (the ServeStats
    bug this PR fixes); occupancy sums to decode steps; decode_s covers
    every sample under the fake clock."""
    engine, ex, _ = _sim_engine(n_slots=2, tick=0.5)
    rng = np.random.default_rng(6)
    rids = [engine.submit(_prompt(rng, 4), 3), engine.submit(_prompt(rng, 5), 1)]
    engine.run()
    # 3 + 1 tokens, *including* each stream's prefill-sampled token
    assert engine.stats.tokens_decoded == 4
    assert sum(engine.stats.occupancy.values()) == engine.stats.steps
    assert engine.stats.decode_s > 0 and engine.stats.prefill_s > 0
    assert engine.stats.tokens_per_s > 0
    # budget-1 request: done at prefill, zero decode steps of its own
    assert engine.result(rids[1]).shape == (1,)
    assert engine.stats.tpot_s[rids[1]] == 0.0


def test_sim_executor_guards_freed_slots():
    """The harness itself: freed rows are poisoned and any read asserts."""
    ex = SimExecutor(n_slots=2, max_len=16)
    ex.prefill_forward(0, np.asarray([1, 2, 3], np.int32), {})
    ex.free(0)
    with pytest.raises(AssertionError):
        ex.decode_forward([0], np.asarray([[1]], np.int32))
    with pytest.raises(AssertionError):
        ex.free(0)  # double free
    # a live slot next to a freed one still decodes fine
    ex.prefill_forward(1, np.asarray([4, 5], np.int32), {})
    ex.decode_forward([1], np.asarray([[7]], np.int32))


# ---------------------------------------------------------------------------
# Real-model differential traces (engine vs single-request Server oracle)
# ---------------------------------------------------------------------------


def _model(arch="gemma_2b", key=0):
    cfg = get_smoke(arch)
    params = lm.init_model(jax.random.PRNGKey(key), cfg)
    return cfg, params


def _prompts_for(cfg, lengths, key=1):
    ks = jax.random.split(jax.random.PRNGKey(key), len(lengths))
    shape = (lambda s: (cfg.n_codebooks, s)) if cfg.n_codebooks > 1 else (
        lambda s: (s,)
    )
    return [
        np.asarray(jax.random.randint(k, shape(s), 0, cfg.vocab), np.int32)
        for k, s in zip(ks, lengths)
    ]


def _oracle(cfg, params, prompts, budgets, max_len, mesh=None):
    """N independent single-request Server.generate runs."""
    srv = Server(cfg, params, max_len=max_len, mesh=mesh)
    return [
        srv.generate({"tokens": jnp.asarray(p)[None]}, n)[0][0]
        for p, n in zip(prompts, budgets)
    ]


def test_engine_vs_server_staggered_arrivals():
    """Real-model trace 1: arrivals interleave mid-stream; engine output
    is token-exact vs independent single-request oracle runs."""
    cfg, params = _model()
    max_len = 16
    prompts = _prompts_for(cfg, [6, 6, 4])
    budgets = [5, 3, 4]
    ex = LMExecutor(cfg, params, max_len, n_slots=3)
    engine = Engine(ex)
    rids = [engine.submit(prompts[0], budgets[0])]
    engine.step()  # r0 decoding alone
    rids.append(engine.submit(prompts[1], budgets[1]))
    engine.step()  # r1 joins: batch of 2
    rids.append(engine.submit(prompts[2], budgets[2]))
    engine.run()  # r2 joins: batch of 3, then drains
    assert set(engine.stats.occupancy) >= {2, 3}
    want = _oracle(cfg, params, prompts, budgets, max_len)
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(engine.result(rid), w)


def test_engine_vs_server_early_finish_and_slot_reuse():
    """Real-model traces 2+3: uneven budgets finish mid-stream (batch
    breathes down) and a 4th request reuses a freed slot — all exact."""
    cfg, params = _model(key=7)
    max_len = 16
    prompts = _prompts_for(cfg, [5, 5, 5, 6], key=8)
    budgets = [2, 6, 4, 3]
    ex = LMExecutor(cfg, params, max_len, n_slots=3)
    engine = Engine(ex)
    rids = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
    engine.run()
    want = _oracle(cfg, params, prompts, budgets, max_len)
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(engine.result(rid), w)
    # r3 was queued (3 slots, 4 requests) and admitted into a freed slot
    assert engine.stats.admitted == 4
    assert engine.stats.occupancy.get(3, 0) >= 1


def test_engine_vs_server_eviction_readmission():
    """Real-model eviction: preempt a stream mid-decode, re-admit, and
    the recomputed prefix continues the greedy stream token-exactly."""
    cfg, params = _model(key=11)
    max_len = 20
    prompts = _prompts_for(cfg, [5, 4], key=12)
    budgets = [6, 4]
    ex = LMExecutor(cfg, params, max_len, n_slots=2)
    engine = Engine(ex)
    rids = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
    engine.step()
    engine.step()
    engine.evict(rids[0])
    engine.run()
    assert engine.stats.evicted == 1
    want = _oracle(cfg, params, prompts, budgets, max_len)
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(engine.result(rid), w)


def test_engine_vs_server_multi_codebook():
    """Multi-codebook (musicgen) rows are (K, S); engine parity holds
    through the stacked-head logits layout."""
    cfg, params = _model("musicgen_medium", key=3)
    max_len = 12
    prompts = _prompts_for(cfg, [6, 4], key=4)
    budgets = [3, 4]
    ex = LMExecutor(cfg, params, max_len, n_slots=2)
    engine = Engine(ex)
    rids = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
    engine.run()
    want = _oracle(cfg, params, prompts, budgets, max_len)
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(engine.result(rid), w)


def test_engine_live_batch_dispatch_reports():
    """A FAµST-parameterized model gets a DispatchReport at each *live*
    batch size it decoded at (advisory query: doesn't clobber
    last_report), priced by the model; the executor prices
    each batch size once."""
    from repro.api import dispatch as _dispatch
    from repro.layers.faust_linear import FaustSpec

    cfg, _ = _model(key=5)
    cfg = dataclasses.replace(
        cfg,
        faust_unembed=FaustSpec(n_factors=2, block=16, k=2),
        tie_embeddings=False,
    )
    params = lm.init_model(jax.random.PRNGKey(5), cfg)
    max_len = 16
    prompts = _prompts_for(cfg, [5, 5, 4], key=6)
    budgets = [4, 2, 3]
    ex = LMExecutor(cfg, params, max_len, n_slots=2)
    engine = Engine(ex)
    for p, b in zip(prompts, budgets):
        engine.submit(p, b)
    engine.run()
    reps = engine.stats.dispatch_by_batch
    assert all(r is not None for r in reps.values())
    # the decision followed the live batch as it breathed
    seen_batches = set(reps)
    assert seen_batches == set(engine.stats.occupancy)
    assert sum(engine.stats.backend_counts().values()) == engine.stats.steps
    for b, r in reps.items():
        assert r.batch == b and ex.dispatch_for(b) is r  # priced once
        assert r.backend in r.feasible
        assert r.source == "model"
        assert r.bt >= 1
    # the engine's advisory queries are record=False: the process-level
    # last_report still holds a decision staged by a real apply
    staged = _dispatch.last_report()
    assert staged is not None and staged.batch in seen_batches | {1}
    # EngineStats keeps the staged (traced) decision too, ServeStats-style
    assert engine.stats.faust_dispatch is not None


@pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)
def test_engine_vs_server_multi_device_parity():
    """Multi-device parity case (ci.sh multi-device leg): engine and
    single-request oracle on the *same* mesh are token-exact."""
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(2, 2)
    cfg, params = _model(key=9)
    max_len = 16
    prompts = _prompts_for(cfg, [6, 6], key=10)
    budgets = [4, 3]
    ex = LMExecutor(cfg, params, max_len, n_slots=2, mesh=mesh)
    engine = Engine(ex)
    rids = [engine.submit(p, b) for p, b in zip(prompts, budgets)]
    engine.run()
    want = _oracle(cfg, params, prompts, budgets, max_len, mesh=mesh)
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(engine.result(rid), w)
