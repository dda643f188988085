"""Trace reduction on a short trace recorded on a TPU v5e: a 0.2 s traced
window of the harness serving InternVL2-2B at its published widths with a
FAµST unembedding (one 320-token prefill with a 256-token vision prefix,
then ten decode steps of one live row)."""
import os

import pytest

import bench_tiny  # noqa: F401 — puts the checkout on sys.path
from bench import harness, trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "vqa_open.v5e.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.reduce_file(FIXTURE)


def test_window_and_busy(trace):
    assert "/device:TPU:0" in trace.planes
    assert len(trace.ops) == 1
    assert trace.window_s == pytest.approx(0.211527017, abs=1e-9)
    assert 0 < trace.busy_s < trace.window_s
    assert trace.busy_s == pytest.approx(0.088152262, rel=1e-6)


def test_chain_kernel_events_are_found_by_the_metric_files_names(trace):
    for name in ("chain_fwd_roofline", "chain_fwd.busy_share"):
        t = trace.kernel_s(harness.metric_reader(name).KERNELS)
        assert t == pytest.approx(0.018228209, rel=1e-6)
    chains = [text for text, _, _ in trace.ops[0] if "tpu_custom_call" in text]
    # the unembedding chain: once in the prefill, once in each decode step
    assert len(chains) == 11


def test_idle_gaps_are_named_by_host_spans(trace):
    gaps = trace.idle_gaps()
    idle = sum(b - a for a, b in gaps) * 1e-9
    assert idle == pytest.approx(trace.window_s - trace.busy_s, rel=1e-6)
    bd = trace.breakdown()
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) == 10
    assert all(name.startswith("bench.") for name, _ in bd["idle_gaps"])
    assert bd["idle_gaps"][0] == ["bench.generator", pytest.approx(0.00495689, rel=1e-6)]
    assert all(len(name) < 120 and " while " not in name for name, _ in bd["device_ops"])
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_short_names():
    name, kind = trace_reduce.short_name(
        '%closed_call.26 = bf16[128,13696]{1,0:T(8,128)(2,1)S(1)} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call"')
    assert (name, kind) == ("%closed_call.26 custom-call bf16[128,13696]", "custom-call")
    name, kind = trace_reduce.short_name("%while.108 = (s32[], bf16[16,1,4096]) while(%t)")
    assert kind == "while"
