"""nemotron-h-47b [hybrid] — Mamba-2 and GQA attention blocks, squared-ReLU
FFN [arXiv:2504.03624; huggingface.co/nvidia/Nemotron-H-47B-Base-8K].

Published: 98 layers, each its own pre-norm residual block, per
``hybrid_override_pattern``: 45 Mamba-2 (``M``), 48 FFN (``-``), 5
attention (``*``).  d_model=8192; Mamba-2 256 heads × 64, state 256, 8
B/C groups (gated RMSNorm per group of 2048 channels), conv 4, chunk 128,
expand 2; attention 64 heads over 8 KV heads × 128 with no position
encoding; FFN non-gated squared ReLU of width 30720; vocab=131072 untied;
context 8192.

``CONFIG`` holds one period of the pattern at these widths: published
layers 40–50, ``M-M-M-M-M*-``, which recurs at layers 8–18, 29–39 and
51–61.  In this repository's layer kinds that is four ``ssm_mlp`` (an
``M-`` pair), one ``ssm`` and one ``attn`` (the ``*-`` pair): six
stage entries for eleven published layers.  The rest of the model would
lie on further chips as pipeline stages; its four FFN-only layers (the
``---`` runs) have no layer kind here.
"""
import dataclasses

from repro.configs.base import ArchConfig, DECODE_POLICY, TP_POLICY
from repro.layers.mamba2 import Mamba2Spec

# published layers 40-50: four "M-" pairs, then "M", then "*-"
STAGES = ((4, ("ssm_mlp",)), (1, ("ssm", "attn")))

CONFIG = ArchConfig(
    name="nemotron-h-47b",
    family="hybrid",
    n_layers=6,
    d_model=8192,
    n_heads=64,
    n_kv_heads=8,
    head_dim=128,
    d_ff=30720,
    vocab=131072,
    act="sq_relu",
    norm="rms",
    stages=STAGES,
    rotary_pct=0.0,  # config.json has no rope keys: NoPE attention
    ssm=Mamba2Spec(d_model=8192, d_state=256, d_conv=4, expand=2, headdim=64,
                   n_groups=8, chunk=128),
    policy=TP_POLICY,
    policy_decode=DECODE_POLICY,
)


def smoke_config() -> ArchConfig:
    return dataclasses.replace(
        CONFIG,
        d_model=64,
        n_heads=8,
        n_kv_heads=4,
        head_dim=16,
        d_ff=128,
        vocab=109,
        ssm=Mamba2Spec(d_model=64, d_state=16, d_conv=4, expand=2, headdim=8,
                       n_groups=8, chunk=8),
        dtype="float32",
        remat=False,
        attn_chunk=8,
    )
