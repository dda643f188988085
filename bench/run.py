"""Chip benchmark of the FAµST serving path.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process is one run of one cell of ``BENCHMARK.json``: it makes the
weights and the traffic from the seed, compiles (or reads from the cache)
every program the cell's traffic uses, measures for ``--seconds``, checks the
served tokens against the plain reference, and prints one JSON object as
the last line of standard output.  ``--trace 0`` reports the end-to-end
metrics, ``--trace 1`` the per-layer metrics from a profiler trace.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

STARTED = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.find_cell(args.workload, ROOT)
    why = harness.require_tpu(cell.entry["chips"])
    if why:
        print(f"bench: {why}", file=sys.stderr)
        return 2
    result = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                         started=STARTED, cell=cell)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
