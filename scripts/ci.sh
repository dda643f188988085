#!/usr/bin/env bash
# Tier-1 CI: run the test suite under a wall-clock timeout and report the
# pass/fail delta vs the recorded seed baseline.
#
#   ./scripts/ci.sh            # default 900 s budget
#   CI_TIMEOUT=300 ./scripts/ci.sh
#
# Seed baseline (commit dfcff03): collection itself failed — 2 collection
# errors (hard `hypothesis` imports), 0 tests ran.  Any green run beats it;
# the delta line makes regressions vs the current numbers obvious too.
set -u
cd "$(dirname "$0")/.."

CI_TIMEOUT="${CI_TIMEOUT:-900}"
# Seed-baseline numbers (what `python -m pytest -q` did at the seed commit):
SEED_PASSED=0
SEED_FAILED=0
SEED_ERRORS=2

# Hygiene: no compiled bytecode may be tracked (a PR once committed a full
# __pycache__ tree; .gitignore prevents new ones, this catches regressions).
if git ls-files | grep -qE '(^|/)__pycache__/|\.pyc$'; then
    echo "ci: TRACKED .pyc/__pycache__ FILES:"
    git ls-files | grep -E '(^|/)__pycache__/|\.pyc$'
    exit 1
fi

# Docs check (cheap): every EXPERIMENTS.md §…/README reference in the
# tree must resolve to an existing file/heading.
if ! python scripts/check_docs.py; then
    echo "ci: DOCS CHECK FAILED"
    exit 1
fi

out=$(PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "$CI_TIMEOUT" \
      python -m pytest -q tests 2>&1)
status=$?
echo "$out" | tail -20

if [ "$status" -eq 124 ]; then
    echo "ci: TIMEOUT after ${CI_TIMEOUT}s"
    exit 124
fi

summary=$(echo "$out" | tail -5)
count() { echo "$summary" | grep -oE "[0-9]+ $1" | tail -1 | grep -oE "^[0-9]+" || echo 0; }
passed=$(count passed)
failed=$(count failed)
errors=$(count "errors?")

echo "ci: passed=${passed} failed=${failed} errors=${errors}" \
     "(seed: passed=${SEED_PASSED} failed=${SEED_FAILED} errors=${SEED_ERRORS})"
echo "ci: delta vs seed: passed $((passed - SEED_PASSED))," \
     "failed $((failed - SEED_FAILED)), errors $((errors - SEED_ERRORS))"

if [ "$failed" -gt "$SEED_FAILED" ] || [ "$errors" -gt "$SEED_ERRORS" ]; then
    echo "ci: WORSE THAN SEED"
    exit 1
fi

# Multi-device leg: the shard_map/collective paths (tests/test_sharded_apply.py
# skips itself on a single-device host), run under the CPU host-device-count
# override so they execute on every push, not just when a TPU is attached.
# The engine-sim suite rides along: the scheduler traces re-run here with
# 8 host devices, which unlocks the engine-vs-Server multi-device parity
# case.
mdout=$(XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
        PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "$CI_TIMEOUT" \
        python -m pytest -q tests/test_sharded_apply.py tests/test_sharding.py \
        tests/test_engine_sim.py tests/test_engine_sched.py 2>&1)
mdstatus=$?
echo "$mdout" | tail -3
if [ "$mdstatus" -eq 124 ]; then
    echo "ci: MULTI-DEVICE LEG TIMEOUT after ${CI_TIMEOUT}s"
    exit 124
elif [ "$mdstatus" -ne 0 ]; then
    echo "ci: MULTI-DEVICE LEG FAILED"
    exit "$mdstatus"
fi
echo "ci: multi-device leg OK"

# Perf-regression leg: re-run the cheap apply benchmarks and gate >25%
# relative regressions against the committed BENCH_baseline.json
# (scripts/check_bench.py).  REPRO_SKIP_BENCH=1 skips it on slow/noisy
# hosts.
if [ "${REPRO_SKIP_BENCH:-0}" != "1" ]; then
    if ! PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} timeout "$CI_TIMEOUT" \
        python benchmarks/run.py --only apply_speed,apply_grad,serve_load \
        --json /tmp/repro_bench_ci.json > /dev/null; then
        echo "ci: BENCH LEG FAILED TO RUN"
        exit 1
    fi
    if ! python scripts/check_bench.py /tmp/repro_bench_ci.json; then
        echo "ci: PERF REGRESSION vs BENCH_baseline.json"
        exit 1
    fi
    # Streaming-track smoke (2 drift steps, tiny shapes): proves the
    # warm-vs-cold tracking pipeline end to end; the sweep-budget claim
    # itself is asserted in tests/test_streaming.py, the wall-µs rows are
    # gated (informationally, sub-100µs rows excepted) like the rest.
    if ! PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} REPRO_STREAM_SMOKE=1 \
        timeout "$CI_TIMEOUT" \
        python benchmarks/run.py --only streaming_track \
        --json /tmp/repro_bench_stream.json > /dev/null; then
        echo "ci: STREAMING BENCH SMOKE FAILED TO RUN"
        exit 1
    fi
    if ! python scripts/check_bench.py /tmp/repro_bench_stream.json; then
        echo "ci: STREAMING BENCH SMOKE REGRESSION"
        exit 1
    fi
    echo "ci: bench leg OK"
else
    echo "ci: bench leg skipped (REPRO_SKIP_BENCH=1)"
fi

# Quantization smoke leg: quantize a small chain, assert fwd/bwd parity
# vs the dequantized-f32 apply and that dispatch prices the reduced byte
# term — the full matrix is tests/test_quantized_chain.py; this leg
# proves the quantize → apply → grad → dispatch workflow end to end.
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "$CI_TIMEOUT" \
    python - <<'EOF'
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.api import FaustOp
from repro.core.compress import (
    BlockFaust, dequantize_chain, pack_chain, quantize_chain,
    random_block_factor,
)
from repro.kernels.ops import packed_chain_apply

ks = jax.random.split(jax.random.PRNGKey(0), 2)
bf = BlockFaust(tuple(
    random_block_factor(ks[i], 64, 64, 8, 8, 2) for i in range(2)),
    jnp.asarray(1.0))
pc = pack_chain(bf)
x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
for dt in ("int8", "fp8_e4m3"):
    qc = quantize_chain(pc, dt)
    fc = dequantize_chain(qc)
    y_q = packed_chain_apply(x, qc, use_kernel=True, bt=8, interpret=True)
    y_f = packed_chain_apply(x, fc, use_kernel=True, bt=8, interpret=True)
    err = float(jnp.abs(y_q - y_f).max())
    assert err <= 1e-5, (dt, "fwd", err)
    def loss(xx, scl, q=qc):
        y = packed_chain_apply(xx, dataclasses.replace(q, scales=scl),
                               use_kernel=True, bt=8, interpret=True)
        return jnp.sum(y ** 2)
    gx, gs = jax.grad(loss, (0, 1))(x, qc.scales)
    gx_r, gs_r = jax.grad(
        lambda xx, scl: jnp.sum(packed_chain_apply(
            xx, dataclasses.replace(qc, scales=scl), use_kernel=False) ** 2),
        (0, 1))(x, qc.scales)
    for g, gr, tag in ((gx, gx_r, "dx"), (gs, gs_r, "dscales")):
        rel = float(jnp.linalg.norm(g - gr) /
                    jnp.maximum(jnp.linalg.norm(gr), 1e-30))
        assert rel <= 1e-5, (dt, tag, rel)
    rq = FaustOp.from_packed(qc).dispatch_for(16)
    rf = FaustOp.from_packed(pc).dispatch_for(16)
    assert rq.values_dtype == {"int8": "int8", "fp8_e4m3": "float8_e4m3fn"}[dt]
    assert rq.weight_bytes == qc.weight_bytes < rf.weight_bytes
    assert f"weight_bytes={rq.weight_bytes}" in rq.reason
print("quantization smoke: fwd/bwd parity + reduced byte pricing OK")
EOF
then
    echo "ci: QUANTIZATION SMOKE FAILED"
    exit 1
fi
echo "ci: quantization smoke leg OK"

# Fault-injection smoke leg: the scripted fault suite (also in the tier-1
# leg — re-run here standalone so a fault-path regression is named), then
# the serving workflow end to end: saturated load through a FaultInjector
# at ~10% decode fault rate must keep nonzero goodput while shedding,
# retrying, and quarantining (benchmarks/serve_load.py --faults asserts
# goodput > 0, retries > 0, failed == quarantined == 1, shed == 2).
if ! PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} timeout "$CI_TIMEOUT" \
    python -m pytest -q tests/test_engine_faults.py > /dev/null; then
    echo "ci: FAULT SUITE FAILED"
    exit 1
fi
if ! PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} timeout "$CI_TIMEOUT" \
    python benchmarks/serve_load.py --faults > /dev/null; then
    echo "ci: FAULT-INJECTION SMOKE FAILED"
    exit 1
fi
echo "ci: fault-injection smoke leg OK"
exit "$status"
