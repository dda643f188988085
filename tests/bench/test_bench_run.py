"""The benchmark's entry and its result line, driven on the CPU at toy size."""
import json
import os
import re
import subprocess
import sys
import time

import pytest

import bench_tiny
from bench import harness
from bench.peaks import V5E

ROOT = bench_tiny.ROOT
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "glm3_faust.decode_closed16", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "metrics" not in p.stdout and "{" not in p.stdout


def test_control_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "control.py"), "--workload",
         "glm3_faust.decode_closed16", "--seeds", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and "{" not in p.stdout


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("traffic", ["closed", "open"])
def test_result_line_holds_the_contract_keys(no_cache, monkeypatch, trace, traffic):
    monkeypatch.setattr(harness, "_peaks", lambda kind: V5E)
    tr = bench_tiny.CLOSED if traffic == "closed" else bench_tiny.OPEN
    cell = bench_tiny.cell(traffic=tr)
    res = harness.run(cell.name, 2**33 + 1, 1.0, trace, started=time.time(), cell=cell)
    json.dumps(res)
    keys = list(res)
    assert set(keys) - {"breakdown", "checks"} == CONTRACT_KEYS
    assert keys[-1] == "checks"
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    for name, chk in res["checks"].items():
        assert set(chk) == {"value", "limit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(res["device"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        want = {m["name"] for m in cell.per_layer}
        assert set(res["metrics"]) <= want
        assert {"engine.occupancy", "model.decode_step_ms", "step_mfu"} <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_traced_run_traces_the_end_of_the_window(no_cache, monkeypatch):
    """The whole window runs; the trace and the per-layer counters cover its
    last ``TRACE_CAP_S`` seconds only."""
    monkeypatch.setattr(harness, "_peaks", lambda kind: V5E)
    monkeypatch.setattr(harness, "TRACE_CAP_S", 0.6)
    cell = bench_tiny.cell()
    t = time.perf_counter()
    res = harness.run(cell.name, 11, 3.0, True, started=time.time(), cell=cell)
    assert res["correct"] is True, res["checks"]
    assert time.perf_counter() - t > 3.0
    assert 0.3 <= res["device"]["window_s"] < 2.4, res["device"]


def test_benchmark_file_names_every_part():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfg["name"] == c["name"]
        assert os.path.exists(os.path.join(ROOT, "bench", "families", cfg["family"] + ".py"))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(ROOT, "bench", "cells", w["name"] + ".json"))
        assert w["chips"] == 1
    for m in bench["per_layer"]:
        assert hasattr(harness.metric_reader(m["name"]), "read")
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    entries = bench["configs"] + bench["workloads"] + bench["end_to_end"] + bench["per_layer"]
    for e in entries:
        assert name.match(e["name"]), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] and "\t" not in e[k]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    cells = [w["name"] for w in bench["workloads"]]
    reported = {m["name"]: m.get("workloads", cells) for m in bench["end_to_end"]}
    for w in cells:
        assert "setup_s" in reported and w in reported["setup_s"]
        assert sum(w in v for v in reported.values()) >= 2
    for m in bench["per_layer"]:
        for w in m.get("workloads", cells):
            assert w in reported[m["moves"]], (m["name"], w)
