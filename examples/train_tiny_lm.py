"""End-to-end training driver (deliverable b): train a small LM for a few
hundred steps on the synthetic pipeline, with checkpoint/resume and an
optional FAµST-parameterized unembedding + FFN — the paper's technique as a
*training-time* parameterization (prescribed-support constraint sets).

On a CPU the model is a reduced same-family config; on a real pod this
script is the same entry point with --mesh.

Run: PYTHONPATH=src:. python examples/train_tiny_lm.py [--faust] [--steps 200]
"""
import argparse
import dataclasses
import logging

import jax

from repro.configs import get_smoke
from repro.data.pipeline import DataConfig
from repro.layers.faust_linear import FaustSpec
from repro.optim.adamw import AdamWConfig
from repro.runtime.trainer import TrainConfig, Trainer


def main() -> None:
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--arch", default="gemma_2b")
    ap.add_argument("--faust", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt", default="/tmp/repro_tiny_lm")
    args = ap.parse_args()

    cfg = get_smoke(args.arch)
    cfg = dataclasses.replace(
        cfg,
        n_layers=4,
        stages=((4, ("attn",)),) if cfg.family == "dense" else cfg.stages,
        d_model=128,
        n_heads=4,
        n_kv_heads=2,
        head_dim=32,
        d_ff=512,
        vocab=2048,
        tie_embeddings=False,
    )
    if args.faust:
        cfg = dataclasses.replace(
            cfg,
            faust_unembed=FaustSpec(n_factors=2, block=32, k=2),
            faust_mlp=FaustSpec(n_factors=2, block=32, k=2),
        )
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=8)
    trainer = Trainer(
        cfg,
        data_cfg,
        AdamWConfig(lr=1e-3, warmup_steps=20, decay_steps=args.steps),
        TrainConfig(
            steps=args.steps, checkpoint_every=50, checkpoint_dir=args.ckpt,
            log_every=20,
        ),
    )
    out = trainer.run(resume=args.resume)
    hist = out["history"]
    first = sum(h["loss"] for h in hist[:10]) / max(len(hist[:10]), 1)
    last = sum(h["loss"] for h in hist[-10:]) / max(len(hist[-10:]), 1)
    print(f"mean loss: first 10 steps {first:.4f} → last 10 steps {last:.4f}")
    assert last < first, "training did not reduce the loss"


if __name__ == "__main__":
    main()
