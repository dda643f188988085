"""``chip_smoke.py`` rehearsed on the CPU at the reduced config: the same
phase code the chip runs, with the Pallas kernels in interpret mode."""
import importlib.util
import os

import jax
import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(smoke):
    cfg = smoke.model_config(smoke=True)
    return cfg, smoke.init_params(cfg, seed=0)


def test_operator_phase_small(smoke, model):
    cfg, params = model
    out = smoke.operator_phase(cfg, params, seed=0, rows=(4, 16))
    assert set(out) >= {"float32_b4", "bfloat16_b16", "grad_dx", "grad_dvalues1"}
    assert out["float32_b16"] <= smoke.F32_BOUND


def test_serve_phase_small(smoke, model):
    cfg, params = model
    lens = (cfg.n_vision_tokens + 8, cfg.attn_chunk) * 2
    out = smoke.serve_phase(cfg, params, seed=0, prompt_lens=lens, new_tokens=4)
    assert out["states"] == ["done"] * 4
    assert out["retries"] == out["failed"] == out["demotions"] == 0
    assert out["source"] != "demoted"


def test_sharded_phase_small(smoke):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    cfg = smoke.model_config(smoke=True)
    out = smoke.sharded_phase(cfg, seed=0, devices=jax.devices()[:4], rows=8)
    assert out["fwd_vs_fused"] <= smoke.F32_BOUND


def test_no_tpu_exits_without_result(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) == 2
    assert capsys.readouterr().out == ""


def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev  # nothing set in code
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("fail", [False, True], ids=["done", "failed"])
def test_serve_launcher_exit_status(monkeypatch, capsys, fail):
    """``launch/serve.py`` exits non-zero when a request does not finish."""
    import sys

    from repro.launch import serve
    from repro.runtime.engine import LMExecutor

    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)
    monkeypatch.setenv("REPRO_RETRY_BACKOFF", "0")
    if fail:
        def boom(self, slots, tokens):
            raise RuntimeError("decode step failed")

        monkeypatch.setattr(LMExecutor, "decode_forward", boom)
    argv = ["serve", "--arch", "internvl2_2b", "--smoke", "--batch", "2",
            "--prompt-len", "8", "--new-tokens", "6"]
    monkeypatch.setattr(sys, "argv", argv)
    if fail:
        with pytest.raises(SystemExit) as e:
            serve.main()
        assert e.value.code not in (0, None)
        assert "'failed': 2" in capsys.readouterr().out
    else:
        serve.main()
        assert "generated shape: (2, 6)" in capsys.readouterr().out
