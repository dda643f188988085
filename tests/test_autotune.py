"""Tests for the measured-timings autotune layer (repro.api.autotune)
and its dispatch integration, plus the reloadable-roofline and
wgrad-tile pricing fixes that ride with it.

Fast paths (table mechanics, key/bucketing, pricing) run with no
measurement at all — entries are hand-written JSON.  One end-to-end test
actually measures a tiny chain in interpret mode with the iteration
knobs floored.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import FaustOp, autotune, last_report
from repro.api import dispatch as dispatch_mod
from repro.api.dispatch import _wgrad_spill_bytes, choose_backend
from repro.core.compress import BlockFaust, random_block_factor
from repro.kernels.chain import DEFAULT_BT
from repro.launch import roofline

jax.config.update("jax_platform_name", "cpu")


def _tiny_op(blk=8, n_factors=2, dim=32, k=2):
    ks = jax.random.split(jax.random.PRNGKey(0), n_factors)
    factors = tuple(
        random_block_factor(ks[i], dim, dim, blk, blk, k)
        for i in range(n_factors)
    )
    return FaustOp.wrap(BlockFaust(factors, jnp.float32(1.0)))


def _key_for(op, batch, grad=False):
    return autotune.key_of(
        shape=op.shape, n_factors=op.n_factors, s_tot=op.s_tot,
        batch=batch, dtype="float32", grad=grad, mesh_shape=None,
        device=jax.default_backend(),
    )


def _write_table(path, entries, version=autotune.TABLE_VERSION):
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": version, "entries": entries}, f)


@pytest.fixture
def table(tmp_path, monkeypatch):
    """A fresh table path with readonly autotune mode active."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_TABLE", path)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)  # readonly mode
    autotune.reload()
    yield path
    autotune.reload()


# ---------------------------------------------------------------------------
# table mechanics
# ---------------------------------------------------------------------------


def test_bucket_batch_next_pow2():
    assert [autotune.bucket_batch(b) for b in (1, 2, 3, 16, 17, 128, 129)] \
        == [1, 2, 4, 16, 32, 128, 256]


def test_mode_resolution(monkeypatch):
    for v, want in (
        ("off", "off"), ("0", "off"), ("false", "off"),
        ("1", "measure"), ("on", "measure"), ("yes", "measure"),
    ):
        monkeypatch.setenv("REPRO_AUTOTUNE", v)
        assert autotune.autotune_mode() == want
    monkeypatch.delenv("REPRO_AUTOTUNE")
    assert autotune.autotune_mode() == "readonly"


def test_key_includes_everything_decisions_depend_on():
    op = _tiny_op()
    k = _key_for(op, batch=100)
    assert k == f"32x32|J2|s{op.s_tot}|b128|float32|fwd|mesh:-|cpu"
    assert _key_for(op, batch=100, grad=True) != k
    assert "mesh:d2xm4" in autotune.key_of(
        shape=(4, 4), n_factors=1, s_tot=4, batch=1, dtype="float32",
        grad=False, mesh_shape=(("d", 2), ("m", 4)), device="cpu",
    )


def test_record_lookup_roundtrip(table):
    entry = {"best": "fused", "us": {"fused": 10.0, "dense": 20.0}, "bt": 64}
    autotune.record("some|key", entry)
    assert autotune.lookup("some|key")["us"]["fused"] == 10.0
    # second record merges, not clobbers
    autotune.record("other|key", {"best": "dense", "us": {"dense": 5.0}})
    assert autotune.lookup("some|key") is not None
    assert autotune.lookup("other|key")["best"] == "dense"


def test_lookup_misses_never_raise(table):
    assert autotune.lookup("no|such|key") is None          # no file
    _write_table(table, {"k": {"best": "fused"}})          # entry missing "us"
    autotune.reload()
    assert autotune.lookup("k") is None


def test_corrupt_table_falls_back_to_none(table):
    with open(table, "w", encoding="utf-8") as f:
        f.write("{not json")
    autotune.reload()
    assert autotune.load_table() is None
    assert autotune.lookup("anything") is None


def test_stale_version_falls_back_to_none(table):
    _write_table(
        table, {"k": {"best": "fused", "us": {"fused": 1.0}}},
        version=autotune.TABLE_VERSION + 1,
    )
    autotune.reload()
    assert autotune.load_table() is None


def test_off_mode_never_consults_table(table, monkeypatch):
    _write_table(table, {"k": {"best": "fused", "us": {"fused": 1.0}}})
    autotune.reload()
    assert autotune.lookup("k") is not None
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    assert autotune.lookup("k") is None


def test_table_rewrite_picked_up_without_reload(table):
    _write_table(table, {"k": {"best": "fused", "us": {"fused": 1.0}}})
    autotune.reload()
    assert autotune.lookup("k")["us"]["fused"] == 1.0
    os.remove(table)
    _write_table(table, {"k": {"best": "dense", "us": {"dense": 2.0}}})
    # no reload(): the (path, mtime) stamp invalidates on its own
    assert autotune.lookup("k")["best"] == "dense"


# ---------------------------------------------------------------------------
# dispatch integration (hand-written entries, no measurement)
# ---------------------------------------------------------------------------


def test_dispatch_prefers_table_hit(table):
    op = _tiny_op()
    batch = 16
    # the model picks fused for this shape; the "measured" entry says bsr
    _write_table(table, {
        _key_for(op, batch): {
            "best": "bsr",
            "us": {"bsr": 3.0, "fused": 7.0, "dense": 50.0},
            "bt": 16,
        }
    })
    autotune.reload()
    rep = dispatch_mod.dispatch(op, batch, jnp.float32)
    assert rep.source == "measured"
    assert rep.backend == "bsr"
    assert rep.est_us == {"bsr": 3.0, "fused": 7.0, "dense": 50.0}
    assert rep.bt == 16  # the tuned tile rides the report
    assert "measured table hit" in rep.reason
    assert rep.as_row()["source"] == "measured"


def test_dispatch_hit_restricted_to_feasible(table):
    """A table entry naming an infeasible backend must not force it —
    measured µs are filtered to the leaf's feasible set."""
    op = _tiny_op().T  # adjoints have no fused path
    assert "fused" not in op.feasible_backends()
    _write_table(table, {
        _key_for(op, 16): {
            "best": "fused",
            "us": {"fused": 1.0, "bsr": 4.0, "dense": 9.0},
        }
    })
    autotune.reload()
    rep = dispatch_mod.dispatch(op, 16, jnp.float32)
    assert rep.source == "measured"
    assert rep.backend == "bsr"  # fastest *feasible* measured backend
    assert "fused" not in rep.est_us


def test_dispatch_miss_and_forced_stay_model(table):
    op = _tiny_op()
    rep = dispatch_mod.dispatch(op, 16, jnp.float32)  # empty table: miss
    assert rep.source == "model"
    _write_table(table, {
        _key_for(op, 16): {"best": "bsr", "us": {"bsr": 3.0}},
    })
    autotune.reload()
    forced = dispatch_mod.dispatch(op, 16, jnp.float32, requested="fused")
    assert forced.backend == "fused"  # forced request ignores the table
    assert forced.source == "model"


def test_off_mode_reproduces_model_decision_bit_for_bit(table, monkeypatch):
    """REPRO_AUTOTUNE=off with a populated (contradicting) table must
    equal the no-table model decision field-for-field."""
    op = _tiny_op()
    baseline = dispatch_mod.dispatch(op, 16, jnp.float32)  # empty table
    _write_table(table, {
        _key_for(op, 16): {"best": "dense", "us": {"dense": 0.001}},
    })
    autotune.reload()
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    off = dispatch_mod.dispatch(op, 16, jnp.float32)
    assert off == baseline  # frozen dataclass: full field equality
    monkeypatch.delenv("REPRO_AUTOTUNE")
    steered = dispatch_mod.dispatch(op, 16, jnp.float32)
    assert steered.backend == "dense" and steered.source == "measured"


def test_apply_runs_at_tuned_bt_unless_forced(table):
    """A table hit's bt steers the kernel tile; an explicit bt= wins."""
    op = _tiny_op()
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    _write_table(table, {
        _key_for(op, 16): {
            "best": "fused",
            "us": {"fused": 1.0, "bsr": 2.0, "dense": 3.0},
            "bt": 16,
        }
    })
    autotune.reload()
    y = op.apply(x, use_kernel=True, interpret=True)
    assert last_report().bt == 16
    y_forced = op.apply(x, use_kernel=True, interpret=True, bt=8)
    assert last_report().bt == 8
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y_forced), rtol=1e-5
    )


# ---------------------------------------------------------------------------
# end-to-end measurement (one real timing pass, tiny + interpret mode)
# ---------------------------------------------------------------------------


def test_measure_populates_table_and_dispatch_hits(table, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_ITERS", "0,1")
    monkeypatch.setenv("REPRO_AUTOTUNE_BT", "8,16")
    op = _tiny_op()
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    y = op.apply(x, use_kernel=True, interpret=True)
    rep = last_report()
    assert rep.source == "measured"
    table_data = json.load(open(table))
    assert table_data["version"] == autotune.TABLE_VERSION
    (key, entry), = table_data["entries"].items()
    assert key == _key_for(op, 16)
    assert set(entry["us"]) == {"dense", "bsr", "fused"}
    assert entry["best"] == min(entry["us"], key=entry["us"].get)
    assert entry["bt"] in (8, 16, DEFAULT_BT)  # sweep winner persisted
    assert rep.backend == entry["best"]
    # numeric parity with the measured-backend answer on a re-apply
    y2 = op.apply(x, use_kernel=True, interpret=True, autotune=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-5)
    # second apply is a pure table hit: the file is not rewritten
    mtime = os.stat(table).st_mtime_ns
    op.apply(x, use_kernel=True, interpret=True)
    assert os.stat(table).st_mtime_ns == mtime


def test_measure_skipped_under_jit(table, monkeypatch):
    """Tracing an auto apply under jit must not try to time tracers."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    op = _tiny_op()
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    y = jax.jit(
        lambda v: op.apply(v, use_kernel=True, interpret=True)
    )(x)
    assert y.shape == (16, 32)
    assert not os.path.exists(table)  # nothing was measured


# ---------------------------------------------------------------------------
# satellite: reloadable roofline constants in dispatch
# ---------------------------------------------------------------------------


def test_dispatch_reprices_after_calibration(tmp_path, monkeypatch):
    """A calibration written after import must reprice the next decision
    and be named in DispatchReport.roofline (the old import-by-value
    constants silently ignored it)."""
    kw = dict(
        batch=64, shape=(1024, 1024), dtype=jnp.float32, s_tot=65536,
        inner_dims=(1024,), n_factors=2,
    )
    before = choose_backend(**kw)
    assert before.roofline == "builtin"
    path = str(tmp_path / "roofline.json")
    # absurd launch overhead: the J-launch bsr path becomes untouchable
    # and every estimate inflates — the decision must re-price
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"peak_flops": 197e12, "hbm_bw": 819e9,
                   "link_bw": 50e9, "t_launch_us": 5e5}, f)
    monkeypatch.setenv("REPRO_ROOFLINE", path)
    after = choose_backend(**kw)
    assert after.roofline == f"measured:{path}"
    assert after.est_us["bsr"] > before.est_us["bsr"] + 9e5
    monkeypatch.setenv("REPRO_ROOFLINE", "builtin")
    again = choose_backend(**kw)
    assert again.roofline == "builtin"
    assert again.est_us == before.est_us


def test_roofline_reload_hook(tmp_path, monkeypatch):
    path = str(tmp_path / "roofline.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"hbm_bw": 1e9}, f)
    monkeypatch.setenv("REPRO_ROOFLINE", path)
    consts, src = roofline.reload()
    assert consts["hbm_bw"] == 1e9
    assert src == f"measured:{path}"
    # partial cache: unmeasured keys fall back to builtin individually
    assert consts["peak_flops"] == roofline._BUILTIN["peak_flops"]


def test_builtin_roofline_keyed_by_device_kind(monkeypatch):
    """Builtin constants come from the device's own ``device_kind``: a TPU
    kind missing from the table raises instead of being priced as a v5e;
    off-TPU the reference chip prices the decision."""
    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Dev()])
    assert roofline.builtin_constants()["peak_flops"] == 197e12
    Dev.device_kind = "TPU v99"
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.builtin_constants()
    Dev.platform, Dev.device_kind = "cpu", "cpu"
    assert roofline.builtin_constants() == roofline._BUILTIN


# ---------------------------------------------------------------------------
# satellite: wgrad spill priced at the real batch tile
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


def test_apply_passes_forced_bt_into_grad_pricing(table):
    """FaustOp.apply(bt=...) reaches the dispatch grad cost query."""
    op = _tiny_op()
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))

    def loss(v):
        return jnp.sum(op.apply(v, use_kernel=True, interpret=True, bt=8))

    jax.make_jaxpr(jax.grad(loss))(x)
    rep = last_report()
    assert rep.grad and rep.bt == 8
