"""Tests for the backend and tile decision (repro.api.dispatch).

The decision is the roofline model at the builtin constants of the chip's
``device_kind``: a pure function of (operator, rows, dtype, grad, shard).
The served-chain cases pin what it picks for every FAµST chain the
benchmark serves, at their published widths, from shapes alone (no weight
array is built); the home-cache cases pin that no file outside the
checkout can change it.
"""
import functools
import json

import jax
import jax.numpy as jnp
import pytest

from repro.api import FaustOp, last_report
from repro.api import dispatch as dispatch_mod
from repro.api.dispatch import _wgrad_spill_bytes, choose_backend
from repro.core.compress import BlockFaust, random_block_factor
from repro.kernels.chain import DEFAULT_BT
from repro.layers.faust_linear import (
    FaustSpec,
    faust_linear_init,
    params_to_blockfaust,
)
from repro.layers.param import split_annotations

jax.config.update("jax_platform_name", "cpu")


def _tiny_op(blk=8, n_factors=2, dim=32, k=2):
    ks = jax.random.split(jax.random.PRNGKey(0), n_factors)
    factors = tuple(
        random_block_factor(ks[i], dim, dim, blk, blk, k)
        for i in range(n_factors)
    )
    return FaustOp.wrap(BlockFaust(factors, jnp.float32(1.0)))


# ---------------------------------------------------------------------------
# the chains the benchmark serves
# ---------------------------------------------------------------------------

# (in, out) of every served chain: ChatGLM3-6B (d 4096, d_ff 13696, vocab
# 65024) and Nemotron-H-47B (d 8192, d_ff 30720, vocab 131072), each a
# FaustSpec(2, 128, 8) chain in bf16 (bench/configs/).
SERVED = {
    "glm3.gate": (4096, 13696),
    "glm3.up": (4096, 13696),
    "glm3.down": (13696, 4096),
    "glm3.unembed": (4096, 65024),
    "nemoh.up": (8192, 30720),
    "nemoh.down": (30720, 8192),
    "nemoh.unembed": (8192, 131072),
}
# The batch tile each chain runs at: DEFAULT_BT, halved where the forward
# kernel's VMEM footprint does not fit (Nemotron-H's down chain reads a
# 30720-wide input).
SERVED_BT = {name: 128 for name in SERVED} | {"nemoh.down": 64}
SERVED_SPEC = FaustSpec(2, 128, 8)


@functools.cache
def _served_op(name: str) -> FaustOp:
    """The chain as shapes only: ``jax.eval_shape`` of the layer's init."""
    i, o = SERVED[name]

    def build():
        p, _ = split_annotations(
            faust_linear_init(jax.random.PRNGKey(0), i, o, SERVED_SPEC, jnp.bfloat16)
        )
        return FaustOp.from_blockfaust(params_to_blockfaust(p, SERVED_SPEC, i, o))

    return jax.eval_shape(build)


@pytest.mark.parametrize("rows", [1, 16, 128, 1024, 4096])
@pytest.mark.parametrize("name", sorted(SERVED))
def test_served_chain_decision(name, rows):
    """Every served chain at decode and prompt widths runs the fused
    kernel, priced by the model, at its fitted tile."""
    op = _served_op(name)
    assert op.s_tot == SERVED_SPEC.s_tot(*SERVED[name])
    rep = dispatch_mod.dispatch(op, rows, jnp.bfloat16, record=False)
    assert (rep.backend, rep.bt, rep.source) == ("fused", SERVED_BT[name], "model")
    assert rep.weight_bytes == 2 * op.s_tot


@pytest.mark.parametrize("cache", ["autotune", "roofline"])
def test_dispatch_ignores_home_cache_files(cache, tmp_path, monkeypatch):
    """Files under ``~/.cache/repro`` and the variables that once named
    them (an autotune table, a host roofline calibration) leave the
    decision as it is without them."""
    op = _served_op("glm3.gate")
    rows = 16
    monkeypatch.setenv("HOME", str(tmp_path))
    clean = dispatch_mod.dispatch(op, rows, jnp.bfloat16, record=False)
    home = tmp_path / ".cache" / "repro"
    home.mkdir(parents=True)
    if cache == "autotune":
        m, n = op.shape
        key = (
            f"{m}x{n}|J{op.n_factors}|s{op.s_tot}|b{rows}|bfloat16|fwd"
            f"|mesh:-|{jax.default_backend()}"
        )
        path = home / "autotune.json"
        path.write_text(json.dumps({"version": 1, "entries": {
            key: {"best": "dense", "us": {"dense": 0.001, "fused": 9e9}, "bt": 8},
        }}))
        monkeypatch.setenv("REPRO_AUTOTUNE", "1")
        monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", str(path))
    else:
        path = home / "roofline.json"
        path.write_text(json.dumps({
            "peak_flops": 1.0, "hbm_bw": 1e30, "link_bw": 1.0, "t_launch_us": 1e9,
        }))
        monkeypatch.setenv("REPRO_ROOFLINE", str(path))
    assert dispatch_mod.dispatch(op, rows, jnp.bfloat16, record=False) == clean


# ---------------------------------------------------------------------------
# the constants
# ---------------------------------------------------------------------------


def test_builtin_roofline_keyed_by_device_kind(monkeypatch):
    """Builtin constants come from the device's own ``device_kind``: a TPU
    kind missing from the table raises instead of being priced as a v5e;
    off-TPU the reference chip prices the decision."""
    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Dev()])
    assert dispatch_mod.builtin_constants()["peak_flops"] == 197e12
    Dev.device_kind = "TPU v99"
    with pytest.raises(KeyError, match="TPU v99"):
        dispatch_mod.builtin_constants()
    Dev.platform, Dev.device_kind = "cpu", "cpu"
    assert dispatch_mod.builtin_constants() == dispatch_mod._BUILTIN


# ---------------------------------------------------------------------------
# wgrad spill priced at the real batch tile
# ---------------------------------------------------------------------------


def test_wgrad_spill_scales_with_tile():
    s_tot = 4096
    assert _wgrad_spill_bytes(128, s_tot) == 0.0            # one default tile
    assert _wgrad_spill_bytes(128, s_tot, 128) == 0.0
    # bt=32: 4 tiles → 3 extra f32 slabs
    assert _wgrad_spill_bytes(128, s_tot, 32) == 8.0 * s_tot * 3
    assert _wgrad_spill_bytes(64, s_tot, 64) == 0.0


def test_grad_pricing_sees_caller_bt():
    """choose_backend(bt=...) changes the fused joint estimate via the
    spill term — the old hardcoded _WGRAD_BT=128 priced every tile the
    same."""
    kw = dict(
        batch=1024, shape=(1024, 1024), dtype=jnp.float32, s_tot=65536,
        inner_dims=(1024,), n_factors=2, grad=True,
    )
    default = choose_backend(**kw)
    small_tile = choose_backend(**kw, bt=8)
    assert small_tile.bt == 8 and default.bt == DEFAULT_BT
    spill_delta = (
        _wgrad_spill_bytes(1024, 65536, 8)
        - _wgrad_spill_bytes(1024, 65536, DEFAULT_BT)
    )
    assert spill_delta > 0
    assert small_tile.est_us["fused"] > default.est_us["fused"]
    # fwd-only pricing has no wgrad spill: bt must not move it
    kw_fwd = {**kw, "grad": False}
    assert (
        choose_backend(**kw_fwd, bt=8).est_us
        == choose_backend(**kw_fwd).est_us
    )


def test_apply_passes_forced_bt_into_grad_pricing():
    """FaustOp.apply(bt=...) reaches the dispatch grad cost query."""
    op = _tiny_op()
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))

    def loss(v):
        return jnp.sum(op.apply(v, use_kernel=True, interpret=True, bt=8))

    jax.make_jaxpr(jax.grad(loss))(x)
    rep = last_report()
    assert rep.grad and rep.bt == 8
