"""The program's own spans in a profiler trace, and the metrics that read
them: the device's idle time split by what the host was doing, and the
device programs run per engine step."""
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny
from bench import harness, program_spans, trace_reduce
from bench.trace_reduce import Trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "vqa_open.v5e.xplane.pb")
IDLE = ["device.idle_between_steps", "device.idle_engine", "device.idle_launch",
        "device.idle_wait", "device.idle_readback"]
U = 100_000  # ns: a tenth of a millisecond


def _spans(*rows):
    return [(name, a * U, b * U, args) for name, a, b, args in rows]


# Two engine steps in a 20 ms window: an admission and a decode, then a decode.
SPANS = _spans(
    ("engine.step", 10, 90, {"step": 1}),
    ("engine.admit", 10, 50, {"rid": "q0", "tokens": 16}),
    ("executor.prefill", 15, 40, {}),
    ("executor.launch", 15, 25, {}),
    ("executor.wait", 25, 40, {}),
    ("executor.sample", 40, 45, {}),
    ("executor.row_finite", 45, 50, {}),
    ("engine.decode", 50, 90, {"rows": 1}),
    ("engine.dispatch_query", 50, 55, {}),
    ("executor.decode", 55, 80, {}),
    ("executor.launch", 55, 65, {}),
    ("executor.wait", 65, 80, {}),
    ("executor.sample", 80, 85, {}),
    ("engine.step", 100, 190, {"step": 2}),
    ("engine.decode", 100, 190, {"rows": 1}),
    ("executor.decode", 100, 180, {}),
    ("executor.launch", 100, 120, {}),
    ("executor.wait", 120, 180, {}),
    ("executor.sample", 180, 185, {}),
)
# device ops leave gaps at 12-20, 35-60, 70-75, 82-102 (across the step
# boundary), 105-115, 170-182 and 195-200
OPS = [[(f"op{i}", a * U, b * U) for i, (a, b) in enumerate(
    [(0, 12), (20, 35), (60, 70), (75, 82), (102, 105), (115, 170), (182, 195)])]]
WANT_MS = {"between_steps": 1.0 + 0.5, "engine": 0.3 + 0.5 + 0.5,
           "launch": 0.5 + 0.5 + 0.2 + 1.0, "wait": 0.5 + 0.5 + 1.0,
           "readback": 0.5 + 0.5 + 0.3 + 0.2}


def _trace(ops=OPS, window=(0, 200 * U)):
    return Trace(ops, [("bench.window", *window)], window, {})


@pytest.fixture
def traced(monkeypatch, tmp_path):
    """The readers find a trace file under the harness's trace directory and
    read ``pt`` from it."""
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    monkeypatch.setattr(program_spans, "_LOADED", {})
    (tmp_path / "host.xplane.pb").write_bytes(b"")

    def use(pt):
        monkeypatch.setattr(program_spans, "load", lambda path: pt)

    return use


def _ctx(trace):
    return SimpleNamespace(trace=trace)


def test_idle_parts_come_out_as_set_and_sum_to_device_idle(traced):
    traced(program_spans.ProgramTrace(SPANS, []))
    ctx = _ctx(_trace())
    idle = harness.metric_reader("device.idle").read(ctx)
    assert idle == pytest.approx(42.5)
    got = {name: harness.metric_reader(name).read(ctx) for name in IDLE}
    for name, part in zip(IDLE, program_spans.PARTS):
        assert got[name] == pytest.approx(100.0 * WANT_MS[part] / 20.0), name
    assert sum(got.values()) == pytest.approx(idle, abs=1e-9)


def test_a_gap_across_a_step_boundary_is_split():
    spans = _spans(("engine.step", 0, 100, {}), ("engine.step", 115, 200, {}),
                   ("executor.decode", 120, 200, {}), ("executor.wait", 125, 200, {}))
    split = program_spans.idle_split([(80 * U, 140 * U)], spans)
    assert split == {"between_steps": 15 * U, "engine": 20 * U + 5 * U, "launch": 5 * U,
                     "wait": 15 * U, "readback": 0}
    # a gap no span covers belongs to the caller; one inside a span's
    # own time (no child open) to that span's part
    assert program_spans.idle_split([(300 * U, 310 * U)], spans)["between_steps"] == 10 * U
    assert program_spans.idle_split([(125 * U, 130 * U)], spans)["wait"] == 5 * U


def test_programs_per_step_counts_only_the_window(traced):
    steps = [("engine.step", a, a + 5, {}) for a in (5, 100, 200)]
    modules = [(f"jit_m{i}", a, a + 1) for i, a in enumerate((2, 8, 100, 110, 120, 210, 220, 299,
                                                                 300, 310))]
    traced(program_spans.ProgramTrace(_spans(*steps), [(n, a * U, b * U) for n, a, b in modules]))
    ctx = _ctx(_trace(ops=[[("op", 100 * U, 300 * U)]], window=(100 * U, 300 * U)))
    assert harness.metric_reader("model.programs_per_step").read(ctx) == pytest.approx(6 / 2)


def test_readers_read_nothing_without_a_device_or_program_spans(traced):
    traced(program_spans.ProgramTrace(SPANS, [("jit__decode", 0, U)]))
    names = IDLE + ["model.programs_per_step"]
    for trace in (None, _trace(ops=[])):  # no trace; a trace with no device plane (the CPU)
        assert all(harness.metric_reader(n).read(_ctx(trace)) is None for n in names)
    traced(program_spans.ProgramTrace([], [("jit__decode", 0, U)]))  # a program without spans
    assert all(harness.metric_reader(n).read(_ctx(_trace())) is None for n in names)


def test_chip_trace_without_program_spans(monkeypatch, tmp_path):
    """The v5e trace recorded before the program had spans: its programs are
    counted, and every reader of the spans reads nothing."""
    pt = program_spans.load(FIXTURE)
    tr = trace_reduce.reduce_file(FIXTURE)
    assert pt.spans == []
    w0, w1 = tr.window
    names = [n.split("(")[0] for n, a, _ in pt.modules if w0 <= a < w1]
    assert len(names) == 257
    assert names.count("jit__decode") == 10 and names.count("jit__prefill") == 1
    shutil.copy(FIXTURE, tmp_path / "chip.xplane.pb")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    for name in IDLE + ["model.programs_per_step"]:
        assert harness.metric_reader(name).read(_ctx(tr)) is None


NESTING = {
    "engine.admit": {"engine.step"},
    "engine.decode": {"engine.step"},
    "engine.dispatch_query": {"engine.decode"},
    "executor.prefill": {"engine.admit"},
    "executor.decode": {"engine.decode"},
    "executor.launch": {"executor.prefill", "executor.decode"},
    "executor.wait": {"executor.prefill", "executor.decode"},
    "executor.sample": {"engine.admit", "engine.decode"},
    "executor.row_finite": {"engine.admit", "engine.decode"},
}


def _parent(span, spans):
    """The innermost other span that encloses ``span``."""
    around = [s for s in spans if s is not span and s[1] <= span[1] and span[2] <= s[2]]
    return max(around, key=lambda s: (s[1], -s[2]))[0] if around else None


def test_toy_run_traces_the_engine_and_executor_spans(monkeypatch, tmp_path):
    import jax

    from repro.runtime.engine import Engine

    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    system = harness.build(bench_tiny.cell(), 5)
    engine = Engine(system.executor)
    for i in range(3):  # a prompt rung the warm-up compiled
        engine.submit(np.arange(1, 17, dtype=np.int32) + i, 4)
    calls = 0
    with jax.profiler.trace(str(tmp_path)):
        while engine.n_pending:
            engine.step()
            calls += 1
    pt = program_spans.load(trace_reduce.find_trace(str(tmp_path)))
    assert pt.modules == []  # the CPU trace has no device plane
    steps = pt.named("engine.step")
    assert len(steps) == calls
    assert [s[3]["step"] for s in steps] == list(range(1, calls + 1))
    assert {s[0] for s in pt.spans} == {"engine.step", *NESTING}
    for span in pt.spans:
        want = NESTING.get(span[0])
        assert _parent(span, pt.spans) in (want or {None}), span
    admits = pt.named("engine.admit")
    assert len(admits) == 3 and all(s[3]["tokens"] == 16 for s in admits)
    assert {s[3]["rows"] for s in pt.named("engine.decode")} >= {1, 2}
