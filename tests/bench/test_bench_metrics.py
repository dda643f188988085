"""Metric arithmetic on synthetic engine records and traces."""
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny
from bench import harness
from bench.generator import Spec
from bench.peaks import peaks_for
from bench.trace_reduce import Trace


def _rec(due, times, rid="r"):
    r = harness.Rec(Spec(0, 4, len(times)), rid, due=due, sent=due)
    r.times = list(times)
    r.tokens = [1] * len(times)
    r.first_t = times[0] if times else None
    return r


def test_end_to_end_rate_tail_and_edges():
    t0 = 100.0
    recs = [
        # in flight at the window's start: only its tokens inside count
        _rec(99.0, [99.5, 99.9, 100.1, 100.3]),
        # in flight at the end
        _rec(100.5, [100.8, 101.0, 101.2, 102.5]),
    ]
    # twenty requests with first tokens 10 ms .. 200 ms after their due time
    for i in range(20):
        due = 100.0 + 0.05 * i
        recs.append(_rec(due, [due + 0.01 * (i + 1), due + 0.01 * (i + 1) + 0.02], rid=f"q{i}"))
    out = harness.end_to_end(recs, t0, 2.0)
    inside = 2 + 3 + 40
    assert out["tokens_per_s"] == pytest.approx(inside / 2.0)
    ttft = [800.0] + [10.0 * (i + 1) for i in range(20)]  # ms; the first one before t0 is out
    assert out["ttft_p95_ms"] == pytest.approx(np.percentile(ttft, 95))
    assert out["n_ttft"] == 21
    gaps = [200.0] + [200.0, 200.0] + [20.0] * 20
    assert out["n_itl"] == len(gaps)
    assert out["itl_p95_ms"] == pytest.approx(np.percentile(gaps, 95))


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeEngine:
    """Admits everything queued, then one token per live request per step."""

    def __init__(self, clock, step_s):
        self.clock, self.step_s = clock, step_s
        self.queue, self.running, self.done = [], {}, {}

    @property
    def n_pending(self):
        return len(self.queue) + len(self.running)

    def submit(self, tokens, max_new, extras=None, rid=None):
        self.queue.append(SimpleNamespace(rid=rid, max_new=max_new, generated=[],
                                          first_token_t=None, state="queued"))
        return rid

    def step(self):
        self.clock.advance(self.step_s)
        for req in self.queue:
            req.generated.append(np.array([1]))
            req.first_token_t = self.clock()
            req.state = "running"
            self.running[req.rid] = req
        self.queue = []
        for rid, req in list(self.running.items()):
            if len(req.generated) >= req.max_new:
                req.state = "done"
                self.done[rid] = self.running.pop(rid)
            else:
                req.generated.append(np.array([1]))


class FakeTraffic:
    def __init__(self, due):
        self.due = np.asarray(due)

    def spec(self, i):
        return Spec(i, 4, 3)

    def content(self, spec):
        return np.zeros(4, np.int32), {}


def test_open_loop_ttft_is_timed_from_the_due_time():
    clock = FakeClock()
    engine = FakeEngine(clock, step_s=0.25)
    # the second request falls due while the first one's step runs
    driver = harness.Driver(engine, FakeTraffic([0.0, 0.1, 2.0]), clock=clock, sleep=clock.advance)
    driver.run(0.0, 3.0)
    recs = sorted(driver.recs.values(), key=lambda r: r.spec.index)
    assert [r.due for r in recs] == [0.0, 0.1, 2.0]
    assert recs[1].sent == pytest.approx(0.25)  # released late, after the step
    assert driver.lateness[1] == pytest.approx(0.15)
    out = harness.end_to_end(recs, 0.0, 3.0)
    # first tokens at 0.25, 0.5 and 2.25: 250 ms, 400 ms (150 of them late) and 250 ms
    assert out["n_ttft"] == 3
    assert out["ttft_p95_ms"] == pytest.approx(np.percentile([250.0, 400.0, 250.0], 95))


def test_closed_loop_clients_send_again_when_a_request_ends():
    clock = FakeClock()
    engine = FakeEngine(clock, step_s=0.1)
    tr = FakeTraffic([])
    tr.due = None
    driver = harness.Driver(engine, tr, clock=clock, sleep=clock.advance)
    driver.ramp(2)
    driver.run(clock(), 1.0)
    assert len(driver.recs) > 2
    assert engine.n_pending == 2  # two clients, always one request each
    for r in driver.recs.values():
        assert r.due == r.sent


def _ctx(**kw):
    fam = bench_tiny.cell().family
    base = dict(trace=None, stats={"steps": 0, "decode_s": 0.0, "prefill_s": 0.0,
                                   "admitted": 0, "occupancy": {}},
                calls=[], step_wall_s=0.0, config=bench_tiny.CONFIG, family=fam,
                dispatch={}, peaks=peaks_for("TPU v5 lite"))
    base.update(kw)
    return SimpleNamespace(**base)


def test_engine_and_model_step_readers():
    ctx = _ctx(stats={"steps": 4, "decode_s": 0.2, "prefill_s": 0.9, "admitted": 3,
                      "occupancy": {16: 3, 12: 1}})
    assert harness.metric_reader("engine.occupancy").read(ctx) == pytest.approx(15.0)
    assert harness.metric_reader("model.decode_step_ms").read(ctx) == pytest.approx(50.0)
    assert harness.metric_reader("model.prefill_ms").read(ctx) == pytest.approx(300.0)
    empty = _ctx()
    for name in ("engine.occupancy", "model.decode_step_ms", "model.prefill_ms", "step_mfu"):
        assert harness.metric_reader(name).read(empty) is None


def _trace():
    ms = 1_000_000
    chain = ('%closed_call.3 = bf16[128,64]{1,0} custom-call(%a, %b), '
             'custom_call_target="tpu_custom_call"')
    ops = [[("fusion.1", 0, 2 * ms), (chain, 2 * ms, 6 * ms),
            ("fusion.2", 5 * ms, 7 * ms), ("fusion.3", 9 * ms, 10 * ms)]]
    spans = [("bench.window", 0, 10 * ms), ("bench.step", 0, 7 * ms),
             ("bench.sample", 7 * ms, 9 * ms)]
    return Trace(ops, spans, (0, 10 * ms), {})


def test_trace_busy_kernel_and_gaps():
    tr = _trace()
    assert tr.busy_s == pytest.approx(0.008)
    assert tr.kernel_s(harness.metric_reader("chain_fwd.busy_share").KERNELS) == pytest.approx(0.004)
    assert tr.idle_gaps() == [(7_000_000, 9_000_000)]
    bd = tr.breakdown()
    assert bd["idle_gaps"] == [["bench.sample", pytest.approx(0.002)]]
    assert bd["device_ops"][0] == ["%closed_call.3 custom-call bf16[128,64]", pytest.approx(0.004)]
    ctx = _ctx(trace=tr)
    assert harness.metric_reader("device.idle").read(ctx) == pytest.approx(20.0)
    assert harness.metric_reader("chain_fwd.busy_share").read(ctx) == pytest.approx(50.0)


def test_roofline_readers_count_required_work():
    fam = bench_tiny.cell().family
    c = bench_tiny.CONFIG
    peaks = peaks_for("TPU v5 lite")
    fused = SimpleNamespace(backend="fused")
    dispatch = {(r, n): fused for r in ("gate", "up", "down", "unembed") for n in (1, 2, 16)}
    calls = [("decode", [5, 9]), ("prefill", 16)]
    ctx = _ctx(trace=_trace(), calls=calls, dispatch=dispatch, step_wall_s=0.5)
    chains = fam.chains(c)
    least = 0.0
    for role, rows, n in [("gate", 2, 2), ("up", 2, 2), ("down", 2, 2), ("unembed", 2, 1),
                          ("gate", 16, 2), ("up", 16, 2), ("down", 16, 2), ("unembed", 1, 1)]:
        f, b = fam.chain_work(chains[role], rows)
        least += n * max(f / 197e12, b / 819e9)
    reader = harness.metric_reader("chain_fwd_roofline")
    assert reader.read(ctx) == pytest.approx(100.0 * least / 0.004)
    f1, b1 = fam.decode_work(c, [5, 9])
    f2, b2 = fam.prefill_work(c, 16)
    want = max(f1 / 197e12, b1 / 819e9) + max(f2 / 197e12, b2 / 819e9)
    assert harness.metric_reader("step_mfu").read(ctx) == pytest.approx(100.0 * want / 0.5)
    # calls that dispatch sent elsewhere than the fused kernel do not count
    ctx.dispatch = {}
    assert reader.read(ctx) is None
