"""Training launcher.

Runs the fault-tolerant Trainer on a (possibly reduced) arch config —
the end-to-end driver. On real hardware this is the per-host entry point
(jax.distributed.initialize + the production mesh); on this container it
runs the reduced configs on CPU.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch gemma_2b --smoke \
      --steps 200 --batch 8 --seq 128 [--resume] [--faust]
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.configs import get_config, get_smoke
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.layers.faust_linear import FaustSpec
from repro.optim.adamw import AdamWConfig
from repro.optim.compression import TopKConfig
from repro.runtime.trainer import TrainConfig, Trainer


def main() -> None:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress-grads", type=float, default=0.0,
                    help="EF top-k ratio (0 = off)")
    ap.add_argument("--faust", action="store_true",
                    help="FAµST-parameterize the unembedding")
    ap.add_argument("--faust-block", type=int, default=16)
    ap.add_argument("--faust-k", type=int, default=4)
    ap.add_argument("--faust-factors", type=int, default=2)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", action="store_true", help="use production mesh")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if args.faust:
        cfg = dataclasses.replace(
            cfg,
            faust_unembed=FaustSpec(
                n_factors=args.faust_factors, block=args.faust_block, k=args.faust_k
            ),
            tie_embeddings=False,
        )
    data_cfg = DataConfig(
        vocab=cfg.vocab,
        seq_len=args.seq,
        global_batch=args.batch,
        n_codebooks=cfg.n_codebooks,
        n_vision_tokens=cfg.n_vision_tokens,
        d_model=cfg.d_model,
    )
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                          decay_steps=args.steps)
    tcfg = TrainConfig(
        steps=args.steps,
        microbatches=args.microbatches,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
        compression=TopKConfig(args.compress_grads) if args.compress_grads else None,
    )
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_production_mesh

        mesh = make_production_mesh(multi_pod=args.multi_pod)

    trainer = Trainer(cfg, data_cfg, opt_cfg, tcfg, mesh=mesh)
    out = trainer.run(resume=args.resume)
    hist = out["history"]
    if hist:
        print(f"first loss {hist[0]['loss']:.4f} → last loss {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
