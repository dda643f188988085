"""The int8 control comes out as not correct through the harness's own
check, where the program passes, at a size the CPU can hold (the chip
readings at the cells' own sizes are in PERF.md).

The window runs on a stepped clock, so it holds the same work on any host.
The toy cell's limits were set as the cells' are, from readings on seeds
6-10: the program's largest (widest gap 0.0275, mean gap 2.34e-4) and the
control's smallest (0.0546 and 6.44e-4).
"""
import pytest

import bench_tiny
from bench import control, harness

CONFIG = dict(bench_tiny.CONFIG, vocab=2000)
CHECK = dict(bench_tiny.CHECK, min_requests=4, max_requests=40, min_tokens=1500,
             min_compared=200, limit_logit_gap=0.04, limit_logit_gap_mean=4e-4)
LOGIT_NUMBERS = ("logit_gap", "logit_gap_mean")


@pytest.fixture(scope="module")
def readings():
    mp = pytest.MonkeyPatch()
    mp.setattr(harness, "use_compile_cache", lambda: "off")
    cell = bench_tiny.cell(config=CONFIG, check=CHECK)
    system = harness.build(cell, 6)
    try:
        out = []
        for seed in (6, 7, 8):
            clock = bench_tiny.TickClock()
            out.append(control.readings(cell, system, seed, 3.0, clock=clock, sleep=clock.sleep))
        yield out
    finally:
        mp.undo()


@pytest.mark.parametrize("i", [0, 1, 2])
def test_program_passes_and_control_fails(readings, i):
    prog, ctl = readings[i]["program"], readings[i]["control"]
    assert prog["correct"] is True
    assert prog["checks"]["tokens_compared"]["value"] >= 200
    assert ctl["correct"] is False
    # the control is judged on the same requests and positions as the program
    for k in ("requests_compared", "tokens_compared", "failed_requests"):
        assert ctl["checks"][k]["value"] == prog["checks"][k]["value"]
    over = [k for k in LOGIT_NUMBERS if ctl["checks"][k]["value"] > ctl["checks"][k]["limit"]]
    assert over, ctl["checks"]
