"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks that are
accuracy-only report us_per_call=0.0).  With ``--json PATH`` the same rows
are also written as machine-readable JSON (derived ``k=v`` pairs parsed
into a dict; apply-path benchmarks additionally carry a ``dispatch``
object — the ``repro.api`` cost-model :class:`DispatchReport` naming
which backend served the measured numbers) so the perf trajectory can be
tracked across PRs, e.g.::

    PYTHONPATH=src:. python benchmarks/run.py --only apply_speed \
        --json BENCH_apply.json

  hadamard            — §IV-C, Figs. 1/6 (exact reverse-engineering + ablation)
  meg_tradeoff        — §V-A, Figs. 7/8 (RE vs RCG sweep)
  svd_comparison      — §II-C1, Fig. 2 (FAµST vs truncated SVD)
  source_localization — §V-B, Fig. 9 (OMP with FAµST operators)
  denoising           — §VI-C, Fig. 12 (FAµST dictionaries vs DDL)
  apply_speed         — §II-B2 (RCG flop model, measured + TPU roofline)
  apply_grad          — training path: jax.grad through dense / per-factor /
                        fused (old rematerializing vs fused dgrad+wgrad
                        backward) / mesh-sharded backends
                        (EXPERIMENTS.md §Training-path perf)
  batch_compress      — §II-B amortization at workload scale (batched vs
                        sequential factorization; EXPERIMENTS.md §Batched
                        compression)
  shard_scaling       — mesh-sharded vs single-device fused apply
                        (debug mesh via CPU host-device override;
                        EXPERIMENTS.md §Sharded apply)
  serve_load          — continuous-batching engine under saturated +
                        Poisson load: per-decode-step time, p50/p99
                        latency, TTFT, tokens/s, batch occupancy
                        (EXPERIMENTS.md §Serving engine)
  serve_load_faults   — the same engine through a scripted FaultInjector
                        at ~10% decode fault rate: goodput, shed/retry/
                        quarantine counts (EXPERIMENTS.md §Fault
                        tolerance)
  streaming_track     — time-varying operator under scripted drift:
                        warm StreamingFaust tracking vs cold per-snapshot
                        refactorization — RE-vs-updates and sweeps/us per
                        update (EXPERIMENTS.md §Streaming factorization)
  quantized_re        — int8/fp8 chain quantization quality gate: ΔRE vs
                        f32 on the Hadamard / MEG / denoising workloads
                        against committed thresholds
                        (EXPERIMENTS.md §Quantized chains)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback


def _force_host_devices(n: int = 8) -> None:
    """Give the CPU host ``n`` devices so the shard_map benchmarks run on
    every machine.  Must happen before the first jax import (hence here,
    not in the benchmark modules); a no-op when the flag is already set,
    and it only affects the *host* platform — TPU runs are untouched.
    Applied only when shard_scaling or apply_grad (whose sharded-training
    leg wants a 2×2 debug mesh) is among the selected benchmarks, so
    `--only apply_speed`-style timing runs keep their historical
    single-device environment."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated benchmark names")
    ap.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="also write the emitted rows as machine-readable JSON",
    )
    args = ap.parse_args()

    requested = args.only.split(",") if args.only else None
    # apply_grad's sharded-training leg needs a (2, 2) debug mesh too
    if requested is None or {"shard_scaling", "apply_grad"} & set(requested):
        _force_host_devices()
    from benchmarks import (
        apply_speed,
        batch_compress,
        common,
        denoising,
        hadamard,
        meg_tradeoff,
        quantized_re,
        serve_load,
        shard_scaling,
        source_localization,
        streaming_track,
        svd_comparison,
    )
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    table = {
        "hadamard": hadamard.run,
        "meg_tradeoff": meg_tradeoff.run,
        "svd_comparison": svd_comparison.run,
        "source_localization": source_localization.run,
        "denoising": denoising.run,
        "apply_speed": apply_speed.run,
        "apply_grad": apply_speed.run_grad,
        "batch_compress": batch_compress.run,
        "shard_scaling": shard_scaling.run,
        "serve_load": serve_load.run,
        "serve_load_faults": serve_load.run_faults,
        "streaming_track": streaming_track.run,
        "quantized_re": quantized_re.run,
    }
    names = args.only.split(",") if args.only else list(table)
    print("name,us_per_call,derived")
    common.reset_rows()
    failed = []
    for name in names:
        t0 = time.monotonic()
        try:
            table[name]()
            print(f"# {name} done in {time.monotonic() - t0:.1f}s", file=sys.stderr)
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(common.rows(), f, indent=2)
            f.write("\n")
        print(f"# wrote {len(common.rows())} rows to {args.json}", file=sys.stderr)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
