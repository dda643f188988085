"""The traffic generator: every seed the same work in another order."""
import json
import os

import numpy as np
import pytest

import bench_tiny
from bench.generator import Traffic

ROOT = bench_tiny.ROOT


# an open-loop mix as a data file would give it: bursty gamma arrivals at a
# fixed rate over the decode mix's sizes
OPEN_MIX = {
    "loop": "open", "rate": 1.6, "arrival": {"dist": "gamma", "cv": 2.0, "gap_seed": 2401},
    "n_slots": 16, "max_len": 3072, "pool": 82,
    "prompt": {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 64, "max": 1024},
    "ladder": [128, 256, 384, 512, 1024],
    "output": {"dist": "lognormal", "median": 96, "sigma": 0.8, "min": 8, "max": 512},
}


def _traffic(name):
    if name == "open_mix":
        return OPEN_MIX
    return json.load(open(os.path.join(ROOT, "bench", "traffic", name + ".json")))


def _config(name):
    return json.load(open(os.path.join(ROOT, "bench", "configs", name + ".json")))


CELLS = [
    ("decode_closed16", "chatglm3_6b.faust_mlp_unembed"),
    ("open_mix", "chatglm3_6b.faust_mlp_unembed"),
    ("prefill_closed8", "chatglm3_6b.faust_mlp_unembed"),
]


@pytest.mark.parametrize("traffic, config", CELLS)
def test_deterministic_in_seed(traffic, config):
    t, c = _traffic(traffic), _config(config)
    a = Traffic(t, c, 2**33 + 7, 30.0)
    b = Traffic(t, c, 2**33 + 7, 30.0)
    other = Traffic(t, c, 5, 30.0)
    assert [a.spec(i) for i in range(50)] == [b.spec(i) for i in range(50)]
    ta, ea = a.content(a.spec(3))
    tb, eb = b.content(b.spec(3))
    np.testing.assert_array_equal(ta, tb)
    for k in ea:
        np.testing.assert_array_equal(ea[k], eb[k])
    # the same set of sizes in another order
    assert sorted(a.prompts) == sorted(other.prompts)
    assert sorted(a.outputs) == sorted(other.outputs)
    assert list(a.prompts) != list(other.prompts)


@pytest.mark.parametrize("traffic, config", CELLS)
def test_lengths_on_the_ladder_and_within_max_len(traffic, config):
    t, c = _traffic(traffic), _config(config)
    tr = Traffic(t, c, 1, 30.0)
    assert set(tr.prompts) == set(t["ladder"])  # every warmed rung is used
    assert (tr.prompts + tr.outputs).max() <= t["max_len"]
    chunk = c.get("attn_chunk", 512)
    assert all(p <= chunk or p % chunk == 0 for p in t["ladder"])
    tokens, extras = tr.content(tr.spec(0))
    assert tokens.shape == (tr.spec(0).prompt_len,)
    assert tokens.max() < c["vocab"]
    if t.get("vision"):
        assert extras["vision_embeds"].shape == (c["n_vision_tokens"], c["d_model"])


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    t, c = _traffic("open_mix"), _config("chatglm3_6b.faust_mlp_unembed")
    a = Traffic(t, c, 3, 30.0)
    b = Traffic(t, c, 4, 30.0)
    assert len(a.due) == round(t["rate"] * 30.0)
    assert a.due[0] >= 0 and a.due[-1] < 30.0
    assert np.all(np.diff(a.due) >= 0)
    assert sorted(np.diff(np.append(a.due, 30.0))) == pytest.approx(
        sorted(np.diff(np.append(b.due, 30.0))))
    gaps = np.diff(a.due)
    cv = gaps.std() / gaps.mean()
    assert 1.3 < cv < 2.7  # bursty, as the file's cv 2 asks
