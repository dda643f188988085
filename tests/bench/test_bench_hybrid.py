"""The hybrid Mamba-2 / attention family (``bench/families/faust_hybrid.py``)
and the ``ssm_state.*`` metrics.

* A toy hybrid cell run through the harness on the CPU comes out
  ``correct``; a token altered where it is produced, and a decode step
  that leaves every Mamba-2 state as it was, come out ``correct`` false.
* The work counts charge each live row's own state, conv history and keys
  and values, and every weight once, at the benchmark's sizes.
* The state ops are found in a trace by their output's trailing dims, in
  a tuple too, and never as the loop that carries them.
"""
import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

import bench_tiny
from bench import harness
from bench.peaks import peaks_for
from bench.trace_reduce import Trace

ROOT = bench_tiny.ROOT
FAMILY = os.path.join(ROOT, "bench", "families", "faust_hybrid.py")
NEMO = "nemotron_h_47b.faust_mlp_unembed"

TINY = {
    "name": "tiny_hybrid", "family": "faust_hybrid", "hidden_size": 64, "d_model": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "attention_head_dim": 16,
    "intermediate_size": 128, "vocab_size": 300, "vocab": 300, "mamba_num_heads": 16,
    "mamba_head_dim": 8, "ssm_state_size": 16, "n_groups": 8, "conv_kernel": 4,
    "chunk_size": 8, "expand": 2, "max_position_embeddings": 512,
    "num_hidden_layers": 11, "hybrid_override_pattern": "M-M-M-M-M*-",
    "time_step_min": 0.001, "time_step_max": 0.1, "attn_chunk": 16,
    "dtype": "bfloat16", "faust_mlp": {"n_factors": 2, "block": 16, "k": 2},
    "faust_unembed": {"n_factors": 2, "block": 16, "k": 2},
}


def _family():
    return harness.load_module(FAMILY, "bench_family_faust_hybrid")


def _config(name):
    return json.load(open(os.path.join(ROOT, "bench", "configs", name + ".json")))


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: "off")
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path / "trace"))


def _run(seed=7):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = harness.Cell("tiny.hybrid", {"chips": 1}, TINY, bench_tiny.CLOSED,
                        bench_tiny.CHECK, _family(), bench["end_to_end"], bench["per_layer"])
    return harness.run(cell.name, seed, 1.0, False, started=time.time(), cell=cell)


def _over_limit(res) -> set:
    return {k for k in ("logit_gap", "logit_gap_mean")
            if res["checks"][k]["value"] > res["checks"][k]["limit"]}


def test_sound_hybrid_run_is_correct(no_cache):
    res = _run()
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["requests_compared"]["value"] >= bench_tiny.CHECK["min_requests"]


def test_altered_token_is_caught(no_cache, monkeypatch):
    from repro.runtime.engine import LMExecutor

    sample = LMExecutor.sample
    monkeypatch.setattr(LMExecutor, "sample",
                        lambda self, logits: (sample(self, logits) + 1) % TINY["vocab"])
    res = _run()
    assert res["correct"] is False
    assert _over_limit(res)


def test_unchanged_mamba_state_is_caught(no_cache, monkeypatch):
    """Decode steps that write the attention layer's keys and values and
    advance every position, but leave each Mamba-2 state and conv history
    as it was."""
    from repro.layers.mamba2 import MambaCache
    from repro.models import lm

    scatter = lm.scatter_cache_slots

    def keep_states(pool, update, slot_idx):
        new = scatter(pool, update, slot_idx)
        return [[MambaCache(old.conv, old.ssm, got.pos) if isinstance(old, MambaCache) else got
                 for old, got in zip(po, ne)] for po, ne in zip(pool, new)]

    monkeypatch.setattr(lm, "scatter_cache_slots", keep_states)
    res = _run()
    assert res["correct"] is False
    assert _over_limit(res)


# ---------------------------------------------------------------------------
# work counts at the benchmark's sizes
# ---------------------------------------------------------------------------

STATE = 256 * 64 * 256 * 4  # one row's f32 state in one Mamba layer, bytes


def test_sizes_and_chains_of_the_benchmark_configuration():
    fam, c = _family(), _config(NEMO)
    s = fam.sizes(c)
    assert (s["n_mamba"], s["n_mlp"], s["n_attn"]) == (5, 5, 1)
    assert fam.state_dims(c) == (256, 64, 256)
    ch = fam.chains(c)
    assert (ch["up"].s_tot, ch["down"].s_tot, ch["unembed"].s_tot) == (
        39_845_888, 16_777_216, 142_606_336)
    from repro.layers.faust_linear import FaustSpec

    for chain in ch.values():
        spec = FaustSpec(chain.n_factors, chain.block, chain.k)
        assert chain.s_tot == spec.s_tot(chain.in_dim, chain.out_dim)


def test_state_bytes_count_live_rows_only():
    fam, c = _family(), _config(NEMO)
    assert fam.ssm_state_bytes(c, "decode", [10] * 16) == 16 * 5 * 2 * STATE
    assert fam.ssm_state_bytes(c, "decode", [10, 4000]) == 2 * 5 * 2 * STATE
    assert fam.ssm_state_bytes(c, "prefill", 2048) == 5 * STATE


def test_decode_work_charges_each_live_row_its_own_state():
    fam, c = _family(), _config(NEMO)
    f1, b1 = fam.decode_work(c, [100])
    f2, b2 = fam.decode_work(c, [100, 300])
    conv = 2 * 3 * (16384 + 2 * 8 * 256) * 5 * 2  # conv history read and rewritten, bf16
    kv = 2 * 8 * 128 * 2  # one token's key and value in the attention layer, bf16
    row = 2 * 5 * STATE + conv + kv * (301 + 1) + 2 * 8192 + 4 * 131072
    assert b2 - b1 == pytest.approx(row)
    # weights once per step whatever the batch: 5.54 GB of bf16 (Mamba 4.38)
    weights = b1 - (2 * 5 * STATE + conv + kv * 102 + 2 * 8192 + 4 * 131072)
    assert 5.53e9 < weights < 5.56e9
    # sixteen rows: ~8.2 GB a step, 86 % of it Mamba projections and states
    _, b16 = fam.decode_work(c, [2048] * 16)
    assert 8.1e9 < b16 < 8.4e9
    assert f2 > f1


def test_prefill_work_counts_the_final_states_and_the_ssd():
    fam, c = _family(), _config(NEMO)
    f1, b1 = fam.prefill_work(c, 1024)
    f2, b2 = fam.prefill_work(c, 2048)
    assert b2 - b1 == pytest.approx(1024 * (2 * 8192 + 2 * 2 * 8 * 128))
    assert f2 > 2 * f1  # the projections double, attention's pairs more than that
    _, bd = fam.decode_work(c, [2048])
    assert b1 < bd  # a prefill writes its final states, a decode step reads and writes them


# ---------------------------------------------------------------------------
# the ssm_state.* metrics on a small trace
# ---------------------------------------------------------------------------


def _trace():
    ms = 1_000_000
    state = "%fusion.7 = f32[4,16,256,64,256]{4,3,2,1,0} fusion(%p0, %p1), kind=kLoop"
    tup = ("%fusion.9 = (f32[16,256,64]{2,1,0}, f32[16,256,64,256]{3,2,1,0}) "
           "fusion(%a, %b), kind=kLoop")
    other = "%fusion.3 = f32[16,256,64,128]{3,2,1,0} fusion(%a), kind=kLoop"
    loop = "%while.2 = (s32[], f32[4,16,256,64,256]{4,3,2,1,0}) while(%t), body=%b"
    ops = [[(loop, 0, 10 * ms), (state, 0, 2 * ms), (tup, 3 * ms, 4 * ms),
            (other, 4 * ms, 6 * ms), ("%fusion.1 = bf16[16,8192]{1,0} fusion(%x)", 6 * ms, 8 * ms)]]
    return Trace(ops, [("bench.window", 0, 10 * ms)], (0, 10 * ms), {})


def _ctx(**kw):
    base = dict(trace=_trace(), calls=[("decode", [5] * 16), ("prefill", 2048)],
                config=_config(NEMO), family=_family(), peaks=peaks_for("TPU v5 lite"))
    base.update(kw)
    return SimpleNamespace(**base)


def test_state_ops_by_their_output_dims():
    from bench import ssm_state

    tr = _trace()
    dims = (256, 64, 256)
    found = [t for t, _, _ in tr.ops[0] if ssm_state.is_state_op(t, dims)]
    assert [t.split(" =")[0] for t in found] == ["%fusion.7", "%fusion.9"]
    assert ssm_state.state_seconds(_ctx()) == pytest.approx(0.003)
    assert not ssm_state.is_state_op(tr.ops[0][0][0], dims)  # the loop carries, never counts


def test_ssm_state_readers():
    share = harness.metric_reader("ssm_state.busy_share")
    roof = harness.metric_reader("ssm_state_roofline")
    ctx = _ctx()
    assert share.read(ctx) == pytest.approx(30.0)  # 3 ms of 10 ms busy (the loop spans it)
    want = (16 * 5 * 2 * STATE + 5 * STATE) / 819e9
    assert roof.read(ctx) == pytest.approx(100.0 * want / 0.003)
    # nothing to read: no trace, a family with no Mamba-2 state, no state op
    assert share.read(_ctx(trace=None)) is None
    glm = harness.load_module(os.path.join(ROOT, "bench", "families", "faust_decoder.py"),
                              "bench_family_faust_decoder")
    assert share.read(_ctx(family=glm)) is None and roof.read(_ctx(family=glm)) is None
    bare = Trace([[(op, a, b) for op, a, b in _trace().ops[0][3:]]],
                 [], (0, 10_000_000), {})
    assert share.read(_ctx(trace=bare)) is None and roof.read(_ctx(trace=bare)) is None


def test_long_decode_traffic_fits_the_context():
    from bench.generator import Traffic

    t = json.load(open(os.path.join(ROOT, "bench", "traffic", "decode_long16.json")))
    c = _config(NEMO)
    a, b = Traffic(t, c, 2**33 + 11, 51.0), Traffic(t, c, 3, 51.0)
    assert sorted(a.prompts) == sorted(b.prompts) and list(a.prompts) != list(b.prompts)
    assert set(a.prompts) <= set(t["ladder"])
    assert int(np.max(a.prompts) + np.max(a.outputs)) <= t["max_len"] == c["max_position_embeddings"]
