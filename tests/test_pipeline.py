"""Pipeline parallelism over the 'pod' axis — subprocess tests (forced
multi-device host platform)."""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_apply, pipeline_bubble_fraction

    n_stages, d, b, n_micro = 4, 16, 24, 6
    mesh = jax.make_mesh((n_stages, 2), ("pod", "data"))

    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (n_stages, d, d)) * (1.0 / jnp.sqrt(d))
    bvec = jax.random.normal(jax.random.PRNGKey(1), (n_stages, d)) * 0.1
    params = {"w": w, "b": bvec}

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    x = jax.random.normal(jax.random.PRNGKey(2), (b, d))

    # sequential oracle
    ref = x
    for s in range(n_stages):
        ref = stage_fn({"w": w[s], "b": bvec[s]}, ref)

    with mesh:
        fn = jax.jit(
            lambda p, xx: pipeline_apply(
                stage_fn, p, xx, mesh=mesh, axis="pod", n_microbatches=n_micro
            )
        )
        lowered = fn.lower(params, x)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        assert "collective-permute" in hlo, "expected inter-stage ppermute"
        y = compiled(params, x)

    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-4, atol=2e-5)
    assert abs(pipeline_bubble_fraction(4, 6) - 3 / 9) < 1e-9
    print("PIPELINE_OK")
    """
)


def test_pipeline_matches_sequential_and_compiles():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=420, cwd="/root/repo",
    )
    assert "PIPELINE_OK" in res.stdout, res.stdout + res.stderr
