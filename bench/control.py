"""Readings that set a cell's correctness limits: the program's numbers and
the int8 control's, on several seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> \
        [--control-seeds 1,2]

Set-up runs once; for each seed the weights and the traffic are made anew,
the cell's traffic runs for ``--seconds`` through the program, and the
harness's own check (``harness.check_correct``) judges the requests a run
would compare twice:

* ``program`` — the served tokens, as every run judges them;
* ``control`` — in their place, at the same positions, the tokens that the
  reference computed in int8 (weights per output column, activations per
  row, both attention products) puts first: the precision a later change
  might be tempted to use.  Only on ``--control-seeds`` (default: all).

Each line gives both verdicts with every number beside its limit; the
command exits 1 where the control comes out correct or the program does not.
The benchmark's own runs never run the control.  Without a TPU it exits 2.
"""
from __future__ import annotations

import time

STARTED = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, system, seed: int, seconds: float, *, control: bool = True,
             clock=time.perf_counter, sleep=time.sleep) -> dict:
    """One seed: fresh weights and traffic through the built program, then
    the harness's verdict on the program's tokens and on the control's.
    ``clock`` and ``sleep`` pace the window (a stepped clock makes the work
    a window holds the same on any host)."""
    from bench import harness
    from bench.generator import Traffic
    from repro.runtime.engine import Engine

    system.executor.params = system.params = None  # one set of weights at a time
    params = cell.family.make_params(cell.config, seed)
    system.executor.params = system.params = params
    traffic = Traffic(cell.traffic, cell.config, seed, seconds)
    engine = Engine(system.recorder, clock=clock)
    driver = harness.Driver(engine, traffic, clock=clock, sleep=sleep)
    if cell.traffic["loop"] == "closed":
        driver.ramp(cell.traffic["clients"])
    t0 = clock()
    driver.run(t0, seconds)
    recs = list(driver.recs.values())
    failed = sum(r.state not in ("done", "queued", "running") for r in recs)
    out = {"seed": seed}
    for side in ("program", "control") if control else ("program",):
        ok, checks = harness.check_correct(cell, params, traffic, recs, seed, failed,
                                           control=side == "control")
        out[side] = {"correct": ok, "checks": checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.find_cell(args.workload, ROOT)
    why = harness.require_tpu(cell.entry["chips"])
    if why:
        print(f"control: {why}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctl = set(seeds if args.control_seeds is None
              else (int(s) for s in args.control_seeds.split(",")))
    system = harness.build(cell, seeds[0])
    sound = True
    for seed in seeds:
        r = readings(cell, system, seed, args.seconds, control=seed in ctl)
        print(json.dumps(r), flush=True)
        sound &= r["program"]["correct"] and not r.get("control", {}).get("correct", False)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
