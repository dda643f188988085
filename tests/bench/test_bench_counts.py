"""Work counts of the configurations and the peaks table."""
import json
import os

import pytest

import bench_tiny
from bench import harness
from bench.peaks import least_time_s, peaks_for

ROOT = bench_tiny.ROOT


def _family():
    return harness.load_module(
        os.path.join(ROOT, "bench", "families", "faust_decoder.py"), "bench_family_faust_decoder"
    )


GLM = "chatglm3_6b.faust_mlp_unembed"
# InternVL2-2B's language model (arXiv:2404.16821) with a FAµST unembedding:
# the ragged vocabulary (92553 is not a multiple of 128) the counts must not pad
INTERNVL2 = {
    "n_layers": 24, "d_model": 2048, "n_heads": 16, "n_kv_heads": 8, "head_dim": 128,
    "d_ff": 8192, "vocab": 92553, "faust_mlp": None,
    "faust_unembed": {"n_factors": 2, "block": 128, "k": 8},
}


def _config(name):
    if name == "internvl2_2b":
        return INTERNVL2
    return json.load(open(os.path.join(ROOT, "bench", "configs", name + ".json")))


@pytest.mark.parametrize(
    "config, role, s_tot",
    [
        ("internvl2_2b", "unembed", 96_993_280),
        ("chatglm3_6b.faust_mlp_unembed", "gate", 18_219_008),
        ("chatglm3_6b.faust_mlp_unembed", "up", 18_219_008),
        ("chatglm3_6b.faust_mlp_unembed", "down", 8_388_608),
        ("chatglm3_6b.faust_mlp_unembed", "unembed", 70_778_880),
    ],
)
def test_chain_s_tot(config, role, s_tot):
    ch = _family().chains(_config(config))[role]
    assert ch.s_tot == s_tot


def test_s_tot_matches_the_program_spec():
    from repro.layers.faust_linear import FaustSpec

    fam = _family()
    c = _config("chatglm3_6b.faust_mlp_unembed")
    for ch in fam.chains(c).values():
        spec = FaustSpec(ch.n_factors, ch.block, ch.k)
        assert ch.s_tot == spec.s_tot(ch.in_dim, ch.out_dim)


def test_chain_work_counts_live_rows_only():
    """A call's work depends on its live rows alone: padding to the kernel's
    tile or the ragged vocabulary's last block adds nothing."""
    fam = _family()
    ch = fam.chains(_config("internvl2_2b"))["unembed"]
    f5, b5 = fam.chain_work(ch, 5)
    f128, b128 = fam.chain_work(ch, 128)
    assert f5 == 2 * 5 * ch.s_tot
    assert b5 == 2 * (ch.s_tot + 5 * (2048 + 92553))  # 92553, not the padded 92672
    assert f128 / f5 == pytest.approx(128 / 5)
    assert b128 - b5 == 2 * 123 * (2048 + 92553)


def test_decode_and_prefill_work():
    fam = _family()
    c = _config("chatglm3_6b.faust_mlp_unembed")
    f1, b1 = fam.decode_work(c, [100])
    f2, b2 = fam.decode_work(c, [100, 100])
    # weights are read once per step whatever the batch; each row adds its own cache
    kv = 2 * 2 * 128 * 28 * 2  # bytes of one token's keys and values over all layers
    assert b2 - b1 == pytest.approx(kv * 102 + 2 * 4096 + 4 * 65024)
    assert f2 > f1
    fp, bp = fam.prefill_work(c, 512)
    assert fp > 512 * f1 * 0.9  # prefill multiplies every weight by every row
    assert bp < b1 + kv * 512 * 1.01 + 2 * 512 * 4096


def test_peaks_unknown_kind_raises():
    with pytest.raises(KeyError):
        peaks_for("cpu")
    v5e = peaks_for("TPU v5 lite")
    assert v5e["flops"]["bfloat16"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9


def test_least_time_names_its_bound():
    p = peaks_for("TPU v5 lite")
    t, bound = least_time_s(197e12, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = least_time_s(1.0, 819e9, p)
    assert bound == "memory" and t == pytest.approx(1.0)
