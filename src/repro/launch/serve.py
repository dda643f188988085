"""Serving launcher: continuous-batching engine, greedy decode.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma_2b --smoke \
      --batch 4 --prompt-len 64 --new-tokens 32

Exits non-zero when any request ends in a state other than ``done``
(failed, timed out or rejected).
"""
from __future__ import annotations

import argparse
from collections import Counter

import jax
import numpy as np

from repro.configs import get_config, get_smoke
from repro.data.pipeline import DataConfig, global_batch
from repro.launch.compile_cache import use_compile_cache
from repro.models import lm
from repro.runtime.engine import DONE, Engine, LMExecutor


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    params = jax.jit(lambda key: lm.init_model(key, cfg))(jax.random.PRNGKey(0))

    data_cfg = DataConfig(
        vocab=cfg.vocab,
        seq_len=args.prompt_len,
        global_batch=args.batch,
        n_codebooks=cfg.n_codebooks,
        n_vision_tokens=cfg.n_vision_tokens,
        d_model=cfg.d_model,
    )
    batch = global_batch(data_cfg, 0)

    ex = LMExecutor(
        cfg, params, max_len=args.prompt_len + args.new_tokens, n_slots=args.batch
    )
    engine = Engine(ex)
    rids = [
        engine.submit(
            batch["tokens"][i],
            args.new_tokens,
            extras={k: v[i] for k, v in batch.items() if k != "tokens"},
        )
        for i in range(args.batch)
    ]
    engine.run()
    states = Counter(engine.status(r) for r in rids)
    st = engine.stats
    print(f"requests: {dict(states)}")
    print(
        f"prefill {st.prefill_s*1e3:.1f} ms; decode {st.decode_s*1e3:.1f} ms "
        f"({st.tokens_per_s:.1f} tok/s); retries {st.retries}"
    )
    if st.faust_dispatch is not None:
        print(f"faust dispatch: {st.faust_dispatch.backend} bt={st.faust_dispatch.bt}")
    if states[DONE] != len(rids):
        raise SystemExit(f"{len(rids) - states[DONE]} request(s) did not finish: {dict(states)}")
    gen = np.stack([engine.result(r) for r in rids])
    print(f"generated shape: {gen.shape}")


if __name__ == "__main__":
    main()
