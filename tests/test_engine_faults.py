"""Supervision proofs for the fault-tolerant serving stack (ISSUE 10).

Scripted fault traces drive :class:`repro.runtime.faults.FaultInjector`
wrapped around the deterministic sim harness (``tests/engine_sim.py``) —
zero jax, zero wall-clock — and pin the acceptance criteria:

* a zero-fault injector run is **byte-identical** to no injector at all
  (wrapper transparency: outputs, stats, and the executor's call log);
* transient step failures retry through the eviction path and every
  stream stays **token-exact** vs the fault-free closed-form oracle;
* persistent failures exhaust the retry budget and turn terminal FAILED
  — for the targeted stream only;
* NaN logits quarantine exactly the poisoned stream, never the batch;
* deadline/TTL expiry frees the slot and the queue drains behind it;
* admission control sheds (REJECTED) at ``max_queue``;
* the eviction cap stops re-admission starvation (a short request under
  constant eviction pressure completes);
* degraded-mode dispatch (jax): a raising auto-chosen backend demotes to
  a reference path with the demotion on the report + session quarantine;
* guarded swaps (jax): a regressed refresh is rejected *before*
  publication — incumbent applies stay byte-identical.
"""
import dataclasses

import numpy as np
import pytest

from engine_sim import FakeClock, SimExecutor, reference_stream
from repro.runtime.engine import DONE, FAILED, REJECTED, TIMED_OUT, Engine
from repro.runtime.faults import FaultInjector, FaultSpec, InjectedFault


@pytest.fixture(autouse=True)
def _clean_quarantine():
    """Degraded-mode dispatch quarantines (signature, backend) pairs
    process-globally; never leak them into other tests."""
    yield
    from repro.api import dispatch

    dispatch.clear_quarantine()


def _prompt(rng, n, vocab=97):
    return rng.integers(0, vocab, size=n).astype(np.int32)


def _engine(faults=(), n_slots=3, max_len=64, tick=0.001, seed=0, **kw):
    clock = FakeClock(tick=tick)
    sim = SimExecutor(n_slots=n_slots, max_len=max_len, seed=seed)
    ex = FaultInjector(sim, faults=faults, clock=clock)
    kw.setdefault("backoff_s", 0.01)
    return Engine(ex, clock=clock, **kw), sim, ex, clock


def _submit_all(engine, prompts, budgets, **kw):
    return [engine.submit(p, n, **kw) for p, n in zip(prompts, budgets)]


def _assert_oracle(engine, sim, rids, prompts, budgets, skip=()):
    for rid, p, n in zip(rids, prompts, budgets):
        if rid in skip:
            continue
        want = reference_stream(p, n, sim.mix, sim.vocab)
        np.testing.assert_array_equal(engine.result(rid), want)


# ---------------------------------------------------------------------------
# Wrapper transparency
# ---------------------------------------------------------------------------


def _stats_key(stats):
    d = dataclasses.asdict(stats)
    d.pop("faust_dispatch", None)
    d.pop("dispatch_by_batch", None)  # None reports either way
    return d


def test_zero_fault_injector_is_byte_identical():
    """Acceptance: an empty FaultInjector is transparent — outputs, full
    stats, and the sim's call log match a run with no injector at all."""
    rng = np.random.default_rng(0)
    prompts = [_prompt(rng, n) for n in (5, 3, 7, 4)]
    budgets = [6, 4, 3, 5]

    def run(wrap):
        clock = FakeClock(tick=0.001)
        sim = SimExecutor(n_slots=2, max_len=64, seed=0)
        ex = FaultInjector(sim, faults=(), clock=clock) if wrap else sim
        engine = Engine(ex, clock=clock)
        rids = _submit_all(engine, prompts, budgets)
        engine.run()
        outs = [engine.result(r) for r in rids]
        return outs, _stats_key(engine.stats), sim.calls

    outs_a, stats_a, calls_a = run(wrap=False)
    outs_b, stats_b, calls_b = run(wrap=True)
    for a, b in zip(outs_a, outs_b):
        np.testing.assert_array_equal(a, b)
    assert stats_a == stats_b
    assert calls_a == calls_b


# ---------------------------------------------------------------------------
# Transient / persistent step failures
# ---------------------------------------------------------------------------


def test_transient_decode_error_retries_token_exact():
    """A decode step that fails once: every affected stream is preempted,
    backed off, re-prefilled, and finishes token-exact vs the fault-free
    oracle — the ISSUE differential proof for the transient class."""
    faults = [FaultSpec("step_error", step=3, op="decode", count=1)]
    engine, sim, ex, clock = _engine(faults, n_slots=3)
    rng = np.random.default_rng(1)
    prompts = [_prompt(rng, n) for n in (5, 3, 7)]
    budgets = [8, 6, 5]
    rids = _submit_all(engine, prompts, budgets)
    engine.run()
    assert ex.fired_log and ex.fired_log[0][0] == "step_error"
    assert engine.stats.retries == 3  # the whole live batch was preempted
    assert engine.stats.failed == 0
    assert all(engine.done[r].state == DONE for r in rids)
    _assert_oracle(engine, sim, rids, prompts, budgets)


def test_transient_prefill_error_retries_token_exact():
    faults = [FaultSpec("step_error", step=1, op="prefill", count=1)]
    engine, sim, ex, clock = _engine(faults, n_slots=2)
    rng = np.random.default_rng(2)
    prompts = [_prompt(rng, n) for n in (4, 6, 3)]
    budgets = [5, 4, 6]
    rids = _submit_all(engine, prompts, budgets)
    engine.run()
    assert engine.stats.retries == 1
    assert all(engine.done[r].state == DONE for r in rids)
    _assert_oracle(engine, sim, rids, prompts, budgets)


def test_persistent_failure_exhausts_budget_and_fails_one_stream():
    """A persistently failing stream turns terminal FAILED after the
    retry budget; the other streams are untouched and token-exact."""
    faults = [
        FaultSpec("step_error", op="prefill", rid="bad", count=None)
    ]
    engine, sim, ex, clock = _engine(faults, n_slots=2, retry_budget=2)
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, n) for n in (5, 4, 6)]
    budgets = [6, 5, 4]
    rids = _submit_all(
        engine, prompts[:1], budgets[:1], rid="bad"
    ) + _submit_all(engine, prompts[1:], budgets[1:])
    engine.run()
    assert engine.status("bad") == FAILED
    assert engine.stats.failed == 1
    assert engine.stats.retries == 2  # budget spent before the verdict
    with pytest.raises(RuntimeError, match="retry budget"):
        engine.result("bad")
    assert all(engine.done[r].state == DONE for r in rids[1:])
    _assert_oracle(engine, sim, rids, prompts, budgets, skip=("bad",))


def test_retry_backoff_delays_readmission():
    """After a transient failure the request is not re-admitted before
    ``not_before``; with nothing else live the engine sleeps the fake
    clock forward instead of spinning."""
    faults = [FaultSpec("step_error", step=0, op="prefill", count=1)]
    engine, sim, ex, clock = _engine(
        faults, n_slots=1, tick=0.0, backoff_s=5.0
    )
    rng = np.random.default_rng(4)
    p, n = _prompt(rng, 4), 3
    (rid,) = _submit_all(engine, [p], [n])
    t_fail = clock.now
    engine.run(max_steps=10)
    assert engine.done[rid].state == DONE
    # the re-prefill that succeeded happened after the backoff elapsed
    assert engine.done[rid].not_before >= t_fail + 5.0
    assert clock.now >= 5.0
    np.testing.assert_array_equal(
        engine.result(rid), reference_stream(p, n, sim.mix, sim.vocab)
    )


# ---------------------------------------------------------------------------
# NaN quarantine
# ---------------------------------------------------------------------------


def test_nan_quarantine_kills_exactly_one_stream():
    faults = [FaultSpec("nan_logits", step=2, op="decode", rid="sick")]
    engine, sim, ex, clock = _engine(faults, n_slots=3)
    rng = np.random.default_rng(5)
    prompts = [_prompt(rng, n) for n in (5, 3, 7)]
    budgets = [8, 8, 8]
    rids = _submit_all(engine, prompts[:1], budgets[:1], rid="sick")
    rids += _submit_all(engine, prompts[1:], budgets[1:])
    engine.run()
    assert engine.status("sick") == FAILED
    assert engine.stats.quarantined == 1
    assert engine.stats.failed == 1
    with pytest.raises(RuntimeError, match="non-finite"):
        engine.result("sick")
    # exactly one stream died; the co-batched survivors are token-exact
    assert all(engine.done[r].state == DONE for r in rids[1:])
    _assert_oracle(engine, sim, rids, prompts, budgets, skip=("sick",))


def test_nan_guard_off_lets_divergence_through():
    """nan_guard=False restores the old behaviour: the poisoned logits
    row argmaxes to *something* and the stream keeps decoding garbage —
    proving the guard (not the injector) is what kills the stream."""
    faults = [FaultSpec("nan_logits", step=1, op="decode", rid="sick")]
    engine, sim, ex, clock = _engine(faults, n_slots=2, nan_guard=False)
    rng = np.random.default_rng(6)
    (rid,) = _submit_all(engine, [_prompt(rng, 5)], [4], rid="sick")
    engine.run()
    assert engine.done["sick"].state == DONE
    assert engine.stats.quarantined == 0


# ---------------------------------------------------------------------------
# Deadlines / admission control
# ---------------------------------------------------------------------------


def test_deadline_expiry_frees_slot_and_queue_drains():
    """A slow-stepped request blows its TTL: it turns TIMED_OUT, its slot
    frees, and the queued request behind it admits and completes."""
    faults = [FaultSpec("slow_step", step=1, op="decode", delay_s=10.0)]
    engine, sim, ex, clock = _engine(faults, n_slots=1)
    rng = np.random.default_rng(7)
    p_slow, p_next = _prompt(rng, 5), _prompt(rng, 4)
    (slow,) = _submit_all(engine, [p_slow], [20], ttl=1.0)
    (nxt,) = _submit_all(engine, [p_next], [3])
    engine.run(max_steps=40)
    assert engine.status(slow) == TIMED_OUT
    assert engine.stats.timed_out == 1
    with pytest.raises(RuntimeError, match="deadline"):
        engine.result(slow)
    assert engine.done[nxt].state == DONE
    np.testing.assert_array_equal(
        engine.result(nxt), reference_stream(p_next, 3, sim.mix, sim.vocab)
    )
    assert engine.n_pending == 0  # the queue drained; nothing is stuck


def test_queued_past_deadline_is_shed():
    """TTL applies in the queue too: a request that never got a slot
    before its deadline is shed, not served stale."""
    engine, sim, ex, clock = _engine((), n_slots=1)
    rng = np.random.default_rng(8)
    (long_r,) = _submit_all(engine, [_prompt(rng, 4)], [30])
    (stale,) = _submit_all(engine, [_prompt(rng, 3)], [3], ttl=0.005)
    engine.step()  # long_r admitted; stale waits
    clock.advance(1.0)  # deadline blown while queued
    engine.run(max_steps=60)
    assert engine.status(stale) == TIMED_OUT
    assert "shed" in engine.done[stale].error
    assert engine.done[long_r].state == DONE


def test_max_queue_rejects_at_submit():
    engine, sim, ex, clock = _engine((), n_slots=1, max_queue=2)
    rng = np.random.default_rng(9)
    r0 = engine.submit(_prompt(rng, 4), 5)  # queued at depth 0
    r1 = engine.submit(_prompt(rng, 4), 5)  # queued at depth 1
    r2 = engine.submit(_prompt(rng, 4), 5)  # queue full: shed
    assert engine.status(r2) == REJECTED
    assert engine.stats.rejected == 1
    with pytest.raises(RuntimeError, match="max_queue"):
        engine.result(r2)
    engine.run()
    assert engine.done[r0].state == DONE
    assert engine.done[r1].state == DONE


# ---------------------------------------------------------------------------
# Starvation-proof re-admission
# ---------------------------------------------------------------------------


def test_eviction_cap_lets_short_request_complete():
    """ISSUE satellite: under constant eviction pressure a short request
    used to bounce queue↔slot forever; the cap pins it after
    ``max_evictions`` and it finishes, token-exact."""
    engine, sim, ex, clock = _engine((), n_slots=1, max_evictions=3)
    rng = np.random.default_rng(10)
    p, n = _prompt(rng, 4), 12  # 2 tokens per admit/evict cycle: the cap
    # must kick in (at 3) well before the budget is decoded
    (rid,) = _submit_all(engine, [p], [n])
    evictions_refused = 0
    for _ in range(60):
        engine.step()
        if rid in engine.running:
            if not engine.evict(rid):  # pinned: the cap kicked in
                evictions_refused += 1
        if engine.n_pending == 0:
            break
    assert engine.done[rid].state == DONE
    assert engine.done[rid].n_evictions == 3
    assert evictions_refused > 0
    assert engine.stats.evicted == 3
    np.testing.assert_array_equal(
        engine.result(rid), reference_stream(p, n, sim.mix, sim.vocab)
    )


def test_requeue_is_age_ordered():
    """Two preemptees re-queue oldest-arrival first, ahead of fresh
    arrivals they were admitted before."""
    engine, sim, ex, clock = _engine((), n_slots=2)
    rng = np.random.default_rng(11)
    prompts = [_prompt(rng, 4) for _ in range(3)]
    r0 = engine.submit(prompts[0], 8)
    clock.advance(0.1)
    r1 = engine.submit(prompts[1], 8)
    engine.step()  # both admitted
    clock.advance(0.1)
    r2 = engine.submit(prompts[2], 8)  # fresh, waiting
    assert engine.evict(r1)  # younger preemptee first...
    assert engine.evict(r0)  # ...then the older one
    order = [r.rid for r in engine.queue]
    assert order == [r0, r1, r2]  # age-ordered preemptees ahead of fresh
    engine.run()
    for rid, p in zip((r0, r1, r2), prompts):
        np.testing.assert_array_equal(
            engine.result(rid), reference_stream(p, 8, sim.mix, sim.vocab)
        )


def test_engine_counts_demotions_from_dispatch_reports():
    """EngineStats.demotions: a newly staged computation whose dispatch
    report carries ``demoted_from`` is counted once, not once per step."""
    from types import SimpleNamespace

    class _Demoting(SimExecutor):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.faust_dispatch = None

        def decode_forward(self, slots, tokens):
            out = super().decode_forward(slots, tokens)
            if self.faust_dispatch is None:  # one staged (demoted) trace
                self.faust_dispatch = SimpleNamespace(
                    backend="bsr", demoted_from="fused"
                )
            return out

    clock = FakeClock(tick=0.001)
    sim = _Demoting(n_slots=2, max_len=32, seed=0)
    engine = Engine(sim, clock=clock)
    rng = np.random.default_rng(12)
    prompts, budgets = [_prompt(rng, 4), _prompt(rng, 5)], [6, 6]
    rids = _submit_all(engine, prompts, budgets)
    engine.run()
    assert engine.stats.demotions == 1
    assert engine.stats.faust_dispatch.demoted_from == "fused"
    _assert_oracle(engine, sim, rids, prompts, budgets)


# ---------------------------------------------------------------------------
# FaultSpec hygiene
# ---------------------------------------------------------------------------


def test_faultspec_validation_and_exhaustion():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec("explode")
    with pytest.raises(ValueError, match="op"):
        FaultSpec("step_error", op="sample")
    f = FaultSpec("step_error", count=2)
    assert not f.exhausted()
    f.fired = 2
    assert f.exhausted()
    persistent = FaultSpec("step_error", count=None, fired=99)
    assert not persistent.exhausted()


def test_injector_owns_fault_copies():
    """Two injectors built from one spec list don't share fire counters."""
    spec = [FaultSpec("step_error", step=0, op="prefill", count=1)]
    sim = SimExecutor(2, 16)
    inj_a = FaultInjector(sim, faults=spec)
    inj_b = FaultInjector(SimExecutor(2, 16), faults=spec)
    inj_a.on_admit("r0", 0)
    with pytest.raises(InjectedFault):
        inj_a.prefill_forward(0, np.asarray([1, 2], np.int32), {})
    assert inj_b.faults[0].fired == 0 and spec[0].fired == 0


# ---------------------------------------------------------------------------
# Degraded-mode dispatch (jax)
# ---------------------------------------------------------------------------


def _packed_op(seed=0, blocks=4, blk=8, k=2):
    import jax
    import jax.numpy as jnp

    from repro.api.operator import FaustOp
    from repro.core.compress import (
        BlockFaust,
        pack_chain,
        random_block_factor,
    )

    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    n = blocks * blk
    factors = tuple(
        random_block_factor(keys[i], n, n, blk, blk, k) for i in range(2)
    )
    bf = BlockFaust(factors, jnp.asarray(1.3, jnp.float32))
    return FaustOp.from_packed(pack_chain(bf)), bf


def test_degraded_dispatch_demotes_once_and_quarantines(monkeypatch):
    """Acceptance: a forced fused failure completes the apply on the
    fallback backend with the demotion on the report, and the failing
    (signature, backend) stays quarantined for the session."""
    import jax

    import repro.kernels.ops as kops
    from repro.api import dispatch

    jax.config.update("jax_platform_name", "cpu")
    monkeypatch.setenv("REPRO_DEGRADED", "1")  # degraded mode is opt-in
    op, _ = _packed_op(seed=20)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, op.shape[0]))
    ref = np.asarray(op.apply(x, backend="bsr"))
    assert op.dispatch_for(4, x.dtype).backend == "fused"

    calls = {"n": 0}
    real = kops.packed_chain_apply

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("pallas launch failed")

    monkeypatch.setattr(kops, "packed_chain_apply", boom)
    y = op.apply(x)  # auto: fused raises -> demoted reference path
    assert calls["n"] == 1
    rep = dispatch.last_report()
    assert rep.source == "demoted" and rep.demoted_from == "fused"
    assert rep.backend in ("bsr", "dense")
    assert "demoted_from" in rep.as_row()
    np.testing.assert_array_equal(np.asarray(y), ref)
    # session quarantine: auto dispatch now skips fused up front (the
    # broken kernel is not even tried again)
    monkeypatch.setattr(kops, "packed_chain_apply", real)
    rep2 = op.dispatch_for(4, x.dtype)
    assert rep2.backend != "fused" and "fused" not in rep2.feasible
    dispatch.clear_quarantine()
    assert op.dispatch_for(4, x.dtype).backend == "fused"


def test_degraded_dispatch_respects_forced_and_env(monkeypatch):
    """Forced backends stay loud; auto stays loud unless REPRO_DEGRADED
    opts in."""
    import jax

    import repro.kernels.ops as kops

    op, _ = _packed_op(seed=21)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, op.shape[0]))

    def boom(*a, **k):
        raise RuntimeError("pallas launch failed")

    monkeypatch.setattr(kops, "packed_chain_apply", boom)
    monkeypatch.setenv("REPRO_DEGRADED", "1")
    with pytest.raises(RuntimeError, match="pallas launch failed"):
        op.apply(x, backend="fused")
    monkeypatch.delenv("REPRO_DEGRADED")
    with pytest.raises(RuntimeError, match="pallas launch failed"):
        op.apply(x)
    monkeypatch.setenv("REPRO_DEGRADED", "off")
    with pytest.raises(RuntimeError, match="pallas launch failed"):
        op.apply(x)


# ---------------------------------------------------------------------------
# Guarded swaps (jax)
# ---------------------------------------------------------------------------


class _FakeServing:
    """Minimal hot_swap target: holds a chain, counts swap publications,
    carries an EngineStats so swap_rejects accounting is observable."""

    def __init__(self, bf):
        from repro.runtime.engine import EngineStats

        self.bf = bf
        self.published = 0
        self.stats = EngineStats()

    def unembed_blockfaust(self):
        return self.bf

    def swap_unembed(self, bf):
        self.bf = bf
        self.published += 1


def test_swap_guard_rejects_regressed_chain_byte_identical():
    """Acceptance: a regressed refresh is rejected before publication —
    the incumbent chain keeps serving and its applies are byte-identical
    to never having attempted the swap."""
    import jax

    from repro.api.operator import FaustOp
    from repro.runtime.faults import regressed_chain
    from repro.streaming.swap import hot_swap

    _, bf = _packed_op(seed=22)
    serving = _FakeServing(bf)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, bf.in_features))
    before = np.asarray(FaustOp.from_blockfaust(serving.bf).apply(x, backend="bsr"))

    report = hot_swap(serving, regressed_chain(bf, scale=25.0), guard=0.5)
    assert not report.accepted
    assert report.rel_err is not None and report.rel_err > 0.5
    assert "exceeds guard" in report.reject_reason
    assert serving.published == 0 and serving.bf is bf
    assert serving.stats.swap_rejects == 1 and serving.stats.swaps == 0
    after = np.asarray(FaustOp.from_blockfaust(serving.bf).apply(x, backend="bsr"))
    np.testing.assert_array_equal(before, after)


def test_swap_guard_rejects_nan_chain():
    from repro.runtime.faults import regressed_chain
    from repro.streaming.swap import hot_swap

    _, bf = _packed_op(seed=23)
    serving = _FakeServing(bf)
    report = hot_swap(serving, regressed_chain(bf, nan=True), guard=0.5)
    assert not report.accepted and "non-finite" in report.reject_reason
    assert serving.published == 0


def test_swap_guard_accepts_small_refresh_and_reports_rel_err(monkeypatch):
    import dataclasses as dc

    import jax.numpy as jnp

    from repro.streaming.swap import hot_swap

    monkeypatch.delenv("REPRO_SWAP_GUARD", raising=False)
    _, bf = _packed_op(seed=24)
    serving = _FakeServing(bf)
    factors = tuple(
        dc.replace(f, values=f.values + jnp.asarray(1e-4, f.values.dtype))
        for f in bf.factors
    )
    near = type(bf)(factors, bf.lam)
    report = hot_swap(serving, near, guard=0.5)
    assert report.accepted and report.kind == "values_only"
    assert report.rel_err is not None and report.rel_err < 0.5
    assert serving.published == 1 and serving.stats.swaps == 1
    # guard off (default env): no sketch runs, rel_err stays None
    report2 = hot_swap(serving, near)
    assert report2.accepted and report2.rel_err is None


def test_quantized_swap_guard_returns_incumbent():
    from repro.core.compress import pack_chain, quantize_chain
    from repro.runtime.faults import regressed_chain
    from repro.streaming.swap import quantized_swap

    _, bf = _packed_op(seed=25)
    old_q = quantize_chain(pack_chain(bf), "int8", "per_block")
    new_q, report = quantized_swap(
        old_q, regressed_chain(bf, scale=25.0), guard=0.5
    )
    assert not report.accepted and report.rel_err > 0.5
    assert new_q is old_q  # the incumbent is handed back: safe to publish
