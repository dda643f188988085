"""Smoke run of the FAµST serving path on a TPU.

    python chip_smoke.py               # one chip: operator, serve, report
    python chip_smoke.py --chips 4     # four chips: the sharded phase only

The model is InternVL2-2B at its published widths (24 layers, d_model 2048,
16/8 heads, d_ff 8192, vocab 92553, 256 vision tokens) with a FAµST
unembedding, ``FaustSpec(n_factors=2, block=128, k=8)``.  Weights are random
from ``--seed``; vision embeddings are the data pipeline's seeded stand-ins.

* operator — the unembedding ``FaustOp`` applied with ``backend="fused"``
  at decode (4 rows) and prefill (256 rows) width in f32 and bf16, and
  ``jax.grad`` through it in f32 (the fused dgrad and wgrad kernels),
  against ``x @ op.todense()`` computed in f32 at highest precision;
* serve — four requests with vision prefixes, two prompt lengths, 32 new
  tokens each through ``Engine``/``LMExecutor``; one prompt's prefill
  logits are checked against the same model with a dense unembedding;
* report — compile against run seconds per phase, cache hits, peak bytes;
* sharded (``--chips 4``) — the unembedding placed over a 4-chip mesh,
  forward and ``jax.grad`` with ``backend="fused_sharded"`` against the
  single-chip fused result and the dense reference.

Every failed check exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Without a TPU
the script exits 2 and prints no result.

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else to ``.jax_cache/`` in the checkout; a second run reads it back.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# bounds (relative Frobenius error against the f32 dense reference)
F32_BOUND = 1e-4
BF16_BOUND = 2e-2
LOGIT_BOUND = 2e-2  # serve: max |Δlogit| ≤ LOGIT_BOUND · max |logit|

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class Meter:
    """Per-phase wall, compile and cache-hit accounting from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        self.phases: dict[str, dict] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0, m0, t0 = self.compile_s, self.hits, self.misses, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        self.phases[name] = {
            "wall_s": wall,
            "compile_s": comp,
            "run_s": wall - comp,
            "cache_hits": self.hits - h0,
            "cache_misses": self.misses - m0,
        }


def model_config(smoke: bool = False):
    """InternVL2-2B with the FAµST unembedding (``smoke``: the repo's
    reduced same-family config, for CPU rehearsals)."""
    from repro.configs import get_config, get_smoke
    from repro.layers.faust_linear import FaustSpec

    if smoke:
        return dataclasses.replace(
            get_smoke("internvl2_2b"),
            faust_unembed=FaustSpec(n_factors=2, block=16, k=2),
            tie_embeddings=False,
        )
    return dataclasses.replace(
        get_config("internvl2_2b"),
        faust_unembed=FaustSpec(n_factors=2, block=128, k=8),
        tie_embeddings=False,
    )


def init_params(cfg, seed: int):
    import jax

    from repro.models import lm

    return jax.block_until_ready(
        jax.jit(lambda key: lm.init_model(key, cfg))(jax.random.PRNGKey(seed))
    )


def unembed_blockfaust(cfg, params):
    from repro.layers.faust_linear import params_to_blockfaust

    return params_to_blockfaust(
        params["unembed"]["faust"], cfg.faust_unembed, cfg.d_model, cfg.vocab
    )


def _as_f32(bf):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating) else a,
        bf,
    )


def _rel(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _dense(bf32):
    """``todense()`` of a chain, f32 at highest precision."""
    import jax

    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda b: b.todense())(bf32)


def _fused_apply(backend: str, shard=None):
    import jax

    from repro.api import FaustOp

    def apply(x, bf):
        op = FaustOp.from_blockfaust(bf)
        if shard is not None:
            op = op.with_sharding(shard)
        return op.apply(x, backend=backend, use_kernel=True)

    return apply


def _grads(apply, x, bf, w):
    """(dx, dvalues per factor) of ``sum(apply(x, bf) * w)``."""
    import jax
    import jax.numpy as jnp

    def loss(x, bf):
        return jnp.sum(apply(x, bf).astype(jnp.float32) * w)

    gx, gbf = jax.jit(jax.grad(loss, argnums=(0, 1), allow_int=True))(x, bf)
    return gx, [f.values for f in gbf.factors]


def _ref_grads(x, bf32, w):
    import jax

    def apply(x, bf):
        return x @ bf.todense()

    with jax.default_matmul_precision("highest"):
        return _grads(apply, x, bf32, w)


def operator_phase(cfg, params, seed: int, rows=(4, 256)) -> dict:
    """Fused forward (f32, bf16) and f32 backward of the unembedding op
    against the dense f32 reference; returns the errors."""
    import jax
    import jax.numpy as jnp

    from repro.api import last_report

    bf = unembed_blockfaust(cfg, params)
    bf32 = _as_f32(bf)
    dense = _dense(bf32)
    fused = jax.jit(_fused_apply("fused"))
    key = jax.random.PRNGKey(seed + 1)
    out = {}
    for dt, chain, bound in (
        (jnp.float32, bf32, F32_BOUND),
        (jnp.bfloat16, bf, BF16_BOUND),
    ):
        for n in rows:
            x = jax.random.normal(jax.random.fold_in(key, n), (n, cfg.d_model))
            x = x.astype(dt)
            y = fused(x, chain)
            rep = last_report()
            check(rep.backend == "fused", f"operator dispatch ran {rep.backend}")
            check(y.shape == (n, cfg.vocab), f"fused output shape {y.shape}")
            check(bool(jnp.isfinite(y).all()), f"non-finite fused output {dt} b={n}")
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(jnp.dot)(x.astype(jnp.float32), dense)
            err = _rel(y, ref)
            name = f"{jnp.dtype(dt).name}_b{n}"
            out[name] = err
            check(err <= bound, f"fused {name}: rel err {err} > {bound}")

    n = rows[-1]
    kx, kw = jax.random.split(jax.random.fold_in(key, 7))
    x = jax.random.normal(kx, (n, cfg.d_model))
    w = jax.random.normal(kw, (n, cfg.vocab))
    gx, gv = _grads(_fused_apply("fused"), x, bf32, w)
    rx, rv = _ref_grads(x, bf32, w)
    out["grad_dx"] = _rel(gx, rx)
    for j, (g, r) in enumerate(zip(gv, rv)):
        out[f"grad_dvalues{j}"] = _rel(g, r)
    for name in [k for k in out if k.startswith("grad_")]:
        check(out[name] <= F32_BOUND, f"fused {name}: rel err {out[name]} > {F32_BOUND}")
    return out


def _requests(cfg, seed: int, prompt_lens):
    import numpy as np

    from repro.data.pipeline import DataConfig, global_batch

    reqs = []
    for i, plen in enumerate(prompt_lens):
        batch = global_batch(
            DataConfig(
                vocab=cfg.vocab,
                seq_len=plen,
                global_batch=1,
                seed=seed,
                n_vision_tokens=cfg.n_vision_tokens,
                d_model=cfg.d_model,
            ),
            i,
        )
        extras = {k: np.asarray(v[0]) for k, v in batch.items() if k != "tokens"}
        reqs.append((np.asarray(batch["tokens"][0]), extras))
    return reqs


def serve_phase(cfg, params, seed: int, prompt_lens, new_tokens: int = 32) -> dict:
    """Four requests through the engine, then one prompt's prefill logits
    against the dense-unembedding model."""
    import numpy as np

    from repro.runtime.engine import DONE, Engine, LMExecutor

    max_len = max(prompt_lens) + new_tokens
    ex = LMExecutor(cfg, params, max_len=max_len, n_slots=len(prompt_lens))
    engine = Engine(ex)
    reqs = _requests(cfg, seed, prompt_lens)
    rids = [engine.submit(p, new_tokens, extras=e) for p, e in reqs]
    engine.run()
    st = engine.stats
    states = [engine.status(r) for r in rids]
    check(all(s == DONE for s in states), f"request states {states}")
    check(
        st.retries == st.failed == st.demotions == st.quarantined == 0,
        f"retries={st.retries} failed={st.failed} demotions={st.demotions} "
        f"quarantined={st.quarantined}",
    )
    toks = np.stack([engine.result(r) for r in rids])
    check(toks.shape == (len(rids), new_tokens), f"tokens shape {toks.shape}")
    check(bool(((toks >= 0) & (toks < cfg.vocab)).all()), "token out of range")
    rep = ex.faust_dispatch
    check(rep is not None, "no FAµST dispatch was staged")
    check(rep.source != "demoted", f"unembedding demoted: {rep.reason}")
    check(
        rep.backend == "fused" or "fused ruled out" in rep.reason,
        f"unembedding ran {rep.backend} without a recorded reason: {rep.reason}",
    )

    # one prompt's last-position prefill logits vs the dense unembedding
    prompt, extras = reqs[0]
    got = np.asarray(ex.prefill_forward(0, prompt, extras), np.float32)[0, -1]
    dense = _dense(_as_f32(unembed_blockfaust(cfg, params)))
    cfg_dense = dataclasses.replace(cfg, faust_unembed=None)
    params_dense = {**params, "unembed": {"w": dense}}
    ref_ex = LMExecutor(cfg_dense, params_dense, max_len=max_len, n_slots=1)
    want = np.asarray(ref_ex.prefill_forward(0, prompt, extras), np.float32)[0, -1]
    check(bool(np.isfinite(got).all()), "non-finite prefill logits")
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    check(err <= LOGIT_BOUND * scale, f"prefill logits: max err {err} > {LOGIT_BOUND}·{scale}")
    check(int(got.argmax()) == int(want.argmax()), "prefill argmax differs from dense")
    return {
        "states": states,
        "tokens": int(toks.size),
        "retries": st.retries,
        "failed": st.failed,
        "demotions": st.demotions,
        "backend": rep.backend,
        "bt": rep.bt,
        "source": rep.source,
        "logit_max_err": err,
        "logit_max_abs": scale,
        "argmax": int(got.argmax()),
        "decode_tok_s": st.tokens_per_s,
    }


def sharded_phase(cfg, seed: int, devices, rows: int = 128) -> dict:
    """The unembedding over a (1, n) data×model mesh: fused_sharded forward
    and grad against single-chip fused and the dense reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.api import ShardSpec, last_report
    from repro.kernels.chain_sharded import place_blockfaust
    from repro.layers.faust_linear import faust_linear_init, params_to_blockfaust
    from repro.layers.param import split_annotations

    n = len(devices)
    spec = cfg.faust_unembed
    fp = jax.jit(
        lambda k: split_annotations(
            faust_linear_init(k, cfg.d_model, cfg.vocab, spec, jnp.bfloat16)
        )[0]
    )(jax.random.PRNGKey(seed))
    bf32 = _as_f32(params_to_blockfaust(fp, spec, cfg.d_model, cfg.vocab))
    mesh = Mesh(np.asarray(devices).reshape(1, n), ("data", "model"))
    placed = place_blockfaust(bf32, mesh)
    weight_bytes = sum(f.values.nbytes for f in bf32.factors)
    for j, f in enumerate(placed.factors):
        check(
            len(f.values.sharding.device_set) == n,
            f"factor {j} spans {len(f.values.sharding.device_set)} devices",
        )
    per_dev = {}
    for f in placed.factors:
        for s in f.values.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    for d, b in per_dev.items():
        check(abs(b - weight_bytes / n) <= 0.01 * weight_bytes / n,
              f"device {d} holds {b} weight bytes, want {weight_bytes / n}")

    one = jax.device_put(bf32, devices[0])
    key = jax.random.PRNGKey(seed + 2)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (rows, cfg.d_model))
    w = jax.random.normal(kw, (rows, cfg.vocab))
    sharded = _fused_apply("fused_sharded", ShardSpec(mesh))
    y_sh = jax.jit(sharded)(x, placed)
    rep = last_report()
    check(rep.backend == "fused_sharded", f"sharded dispatch ran {rep.backend}")
    y_one = jax.jit(_fused_apply("fused"))(jax.device_put(x, devices[0]), one)
    dense = _dense(one)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(jnp.dot)(jax.device_put(x, devices[0]), dense)
    out = {
        "mesh": f"1x{n}",
        "per_device_weight_bytes": sorted(per_dev.values()),
        "fwd_vs_fused": _rel(jax.device_put(y_sh, devices[0]), y_one),
        "fwd_vs_dense": _rel(jax.device_put(y_sh, devices[0]), ref),
    }
    gx, gv = _grads(sharded, x, placed, w)
    fx, fv = _grads(_fused_apply("fused"), jax.device_put(x, devices[0]), one,
                    jax.device_put(w, devices[0]))
    rx, rv = _ref_grads(jax.device_put(x, devices[0]), one, jax.device_put(w, devices[0]))
    to0 = lambda a: jax.device_put(a, devices[0])  # noqa: E731
    out["grad_vs_fused"] = max(
        [_rel(to0(gx), fx)] + [_rel(to0(g), f) for g, f in zip(gv, fv)]
    )
    out["grad_vs_dense"] = max(
        [_rel(to0(gx), rx)] + [_rel(to0(g), r) for g, r in zip(gv, rv)]
    )
    for name in ("fwd_vs_fused", "fwd_vs_dense", "grad_vs_fused", "grad_vs_dense"):
        check(out[name] <= F32_BOUND, f"sharded {name}: rel err {out[name]} > {F32_BOUND}")
    return out


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devices[0].platform})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    meter = Meter()
    cfg = model_config()
    dev = devices[0]
    results = {}
    try:
        if args.chips == 4:
            with meter.phase("sharded"):
                results["sharded"] = sharded_phase(cfg, args.seed, devices[:4])
            print("sharded", json.dumps(results["sharded"]), flush=True)
        else:
            with meter.phase("init"):
                params = init_params(cfg, args.seed)
            print(f"init peak_bytes_in_use={peak_bytes(dev)}", flush=True)
            with meter.phase("operator"):
                results["operator"] = operator_phase(cfg, params, args.seed)
            print("operator", json.dumps(results["operator"]), flush=True)
            with meter.phase("serve"):
                results["serve"] = serve_phase(
                    cfg, params, args.seed,
                    prompt_lens=(cfg.n_vision_tokens + 64, cfg.attn_chunk) * 2,
                )
            print("serve", json.dumps(results["serve"]), flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    report = {
        "phases": meter.phases,
        "peak_bytes_in_use": peak_bytes(dev),
        "compile_cache": cache_dir,
    }
    print("report", json.dumps(report), flush=True)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
