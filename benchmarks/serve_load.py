"""Serving-engine load benchmark: continuous batching under Poisson load.

Drives the PR-7 engine (``repro.runtime.engine``) with a FAµST-unembedded
smoke LM and measures the serving numbers the scheduler design is for:

* ``serve_load`` (the BENCH-gated row): per-decode-step time at
  *saturated* load — every request submitted up front, the batch
  breathing from ``n_slots`` wide down to 1 as budgets drain.  This is
  the steady-state cost the continuous-batching claim rests on, and the
  per-step FAµST :class:`DispatchReport` rides on the JSON row so the
  perf trajectory records which backend served the live batch.
* ``serve_load_poisson_*`` rows: an open-loop **seeded** Poisson arrival
  sweep at offered-load factors below and above saturation, reporting
  p50/p99 request latency, p50 TTFT, tokens/s and the mean live-batch
  occupancy.  Arrival draws are deterministic in the seed; the wall
  clock only decides *when* each scripted arrival is released, so the
  load factors (not host speed) shape the queueing story.
* ``serve_load_faults`` (``--faults``): the same saturated run through a
  scripted :class:`~repro.runtime.faults.FaultInjector` — a transient
  ``step_error`` every 10th decode call (≈10% decode fault rate), one
  NaN-poisoned stream, and admission control sized to shed the last two
  submissions.  Reports **goodput** (completed streams' tokens per
  engine-second), shed/failed/retry counts, and asserts goodput stays
  nonzero under faults (EXPERIMENTS.md §Fault tolerance).

All rows derive their timing from ``EngineStats`` (the engine's own
accounting, incl. the prefill-sampled token — the PR-7 fix), not from an
outer stopwatch, so the benchmark measures what operators would see.
Smoke-scale model on CPU: absolute µs are for smoke value (sub-100ms rows
sit below the ``check_bench.py`` gate floor and are informational); the
occupancy-vs-tokens/s table in EXPERIMENTS.md §Serving engine comes from
these rows.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs import get_smoke
from repro.layers.faust_linear import FaustSpec
from repro.models import lm
from repro.runtime.engine import Engine, LMExecutor

N_SLOTS = 4
N_REQ = 12
PROMPT_LEN = 8
MAX_LEN = 24
SEED = 0


def _model():
    cfg = dataclasses.replace(
        get_smoke("gemma_2b"),
        faust_unembed=FaustSpec(n_factors=2, block=16, k=2),
        tie_embeddings=False,
    )
    params = lm.init_model(jax.random.PRNGKey(SEED), cfg)
    return cfg, params


def _requests(cfg, rng, n):
    prompts = [
        np.asarray(
            rng.integers(0, cfg.vocab, size=PROMPT_LEN), np.int32
        )
        for _ in range(n)
    ]
    budgets = [int(b) for b in rng.integers(3, 9, size=n)]
    return prompts, budgets


def _occ_mean(stats) -> float:
    steps = sum(stats.occupancy.values())
    if not steps:
        return 0.0
    return sum(b * c for b, c in stats.occupancy.items()) / steps


def _occ_str(stats) -> str:
    return "/".join(
        f"occ{b}={c}" for b, c in sorted(stats.occupancy.items())
    ).replace("/", ";")


def _last_dispatch(stats):
    """The dispatch report at the widest live batch served."""
    reps = [rep for _, rep in sorted(stats.dispatch_by_batch.items()) if rep is not None]
    return reps[-1] if reps else None


def _saturated(cfg, params) -> tuple:
    """All N_REQ submitted at t=0 over N_SLOTS slots: warm + measure."""
    rng = np.random.default_rng(SEED)
    prompts, budgets = _requests(cfg, rng, N_REQ)

    def run_once():
        ex = LMExecutor(cfg, params, MAX_LEN, n_slots=N_SLOTS)
        engine = Engine(ex)
        for p, b in zip(prompts, budgets):
            engine.submit(p, b)
        engine.run()
        return engine

    run_once()  # warmup: compiles prefill + decode at every live width
    engine = run_once()
    return engine.stats, sum(budgets)


def _poisson(cfg, params, qps: float, seed: int):
    """Open-loop Poisson arrivals at ``qps`` — seeded draws, wall-clock
    release.  Returns (stats, per-request latencies in seconds)."""
    rng = np.random.default_rng(seed)
    prompts, budgets = _requests(cfg, rng, N_REQ)
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=N_REQ))
    ex = LMExecutor(cfg, params, MAX_LEN, n_slots=N_SLOTS)
    engine = Engine(ex)
    t0 = time.monotonic()
    i, rids = 0, []
    while i < len(arrivals) or engine.n_pending:
        now = time.monotonic() - t0
        while i < len(arrivals) and arrivals[i] <= now:
            rids.append(engine.submit(prompts[i], budgets[i]))
            i += 1
        if engine.n_pending:
            engine.step()
        elif i < len(arrivals):
            time.sleep(min(arrivals[i] - now, 0.005))
    lat = [engine.done[r].done_t - engine.done[r].arrival for r in rids]
    return engine.stats, np.asarray(lat)


def run() -> None:
    cfg, params = _model()
    stats, n_tokens = _saturated(cfg, params)
    step_us = stats.decode_s / max(stats.steps, 1) * 1e6
    ttft = np.asarray(sorted(stats.ttft_s.values()))
    emit(
        "serve_load",
        step_us,
        f"tokens_per_s={stats.tokens_per_s:.1f};"
        f"tokens={stats.tokens_decoded};steps={stats.steps};"
        f"occ_mean={_occ_mean(stats):.2f};{_occ_str(stats)};"
        f"ttft_p50_ms={np.percentile(ttft, 50) * 1e3:.1f};"
        f"n_slots={N_SLOTS};n_req={N_REQ}",
        dispatch=_last_dispatch(stats),
    )
    assert stats.tokens_decoded == n_tokens, "engine lost tokens"

    # service rate per stream ≈ one token per decode step → offered-load
    # factors are host-relative, so the sweep tells the same queueing
    # story on any machine
    svc_s = (
        np.mean([3, 9]) / 2 * stats.decode_s / max(stats.steps, 1)
        + stats.prefill_s / max(stats.admitted, 1)
    )
    for load in (0.5, 4.0):
        qps = load * N_SLOTS / max(svc_s, 1e-6)
        pstats, lat = _poisson(cfg, params, qps, seed=SEED + 1)
        emit(
            f"serve_load_poisson_x{load:g}",
            float(np.percentile(lat, 50) * 1e6),
            f"qps={qps:.1f};p99_ms={np.percentile(lat, 99) * 1e3:.1f};"
            f"ttft_p50_ms={np.percentile(sorted(pstats.ttft_s.values()), 50) * 1e3:.1f};"
            f"tokens_per_s={pstats.tokens_per_s:.1f};"
            f"occ_mean={_occ_mean(pstats):.2f}",
            dispatch=_last_dispatch(pstats),
        )


def run_faults() -> None:
    """Saturated load at a ~10% scripted fault rate: the supervision
    layer must keep goodput nonzero while shedding/retrying around the
    faults (the ISSUE 10 acceptance criterion, as a tracked BENCH row)."""
    from repro.runtime.engine import DONE
    from repro.runtime.faults import FaultInjector, FaultSpec

    cfg, params = _model()
    rng = np.random.default_rng(SEED)
    prompts, budgets = _requests(cfg, rng, N_REQ)
    # ~10% of decode calls raise (transient, each fires once); one stream
    # is NaN-poisoned on its second decode step.  All indices are per-op
    # call counters, so the schedule is deterministic on any host.
    faults = [
        FaultSpec("step_error", step=s, op="decode", count=1)
        for s in range(2, 80, 10)
    ]
    faults.append(FaultSpec("nan_logits", step=1, op="decode", rid="req3"))
    ex = FaultInjector(
        LMExecutor(cfg, params, MAX_LEN, n_slots=N_SLOTS), faults=faults
    )
    engine = Engine(
        ex, retry_budget=5, backoff_s=0.01, max_queue=N_REQ - 2
    )
    rids = [
        engine.submit(p, b, rid=f"req{i}")
        for i, (p, b) in enumerate(zip(prompts, budgets))
    ]
    engine.run()

    st = engine.stats
    done_tokens = sum(
        len(engine.done[r].generated)
        for r in rids
        if engine.done[r].state == DONE
    )
    wall = st.prefill_s + st.decode_s
    goodput = done_tokens / max(wall, 1e-9)
    shed = st.rejected + st.timed_out
    n_faults = len(ex.fired_log)
    assert goodput > 0, "no goodput under 10% fault rate"
    assert st.retries > 0, "fault schedule never exercised a retry"
    assert st.quarantined == 1 and engine.done["req3"].state != DONE
    # transient errors must resolve via retry: the only terminal failure
    # is the NaN-quarantined stream (also proves ragged-length re-prefill
    # — prompt+generated is rarely attn_chunk-aligned — works end to end)
    assert st.failed == 1, f"transient faults failed streams: {st.failed}"
    assert shed == 2, f"admission control shed {shed} != 2"
    assert st.completed == N_REQ - shed - st.failed
    emit(
        "serve_load_faults",
        st.decode_s / max(st.steps, 1) * 1e6,
        f"goodput_tok_s={goodput:.1f};good_tokens={done_tokens};"
        f"completed={st.completed};faults_fired={n_faults};"
        f"retries={st.retries};failed={st.failed};"
        f"quarantined={st.quarantined};shed={shed};"
        f"demotions={st.demotions};n_req={N_REQ};n_slots={N_SLOTS}",
        dispatch=_last_dispatch(st),
    )


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--faults",
        action="store_true",
        help="run only the fault-injection axis (serve_load_faults row)",
    )
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run_faults() if args.faults else run()
