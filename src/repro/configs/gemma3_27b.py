"""gemma3-27b [dense] — 5:1 local:global attention, 128k context
[hf:google/gemma-3 family].

62L d_model=5376 32H (GQA kv=16, head_dim=128) d_ff=21504 GeGLU
vocab=262144, qk-norm, sliding window 1024 on local layers, distinct rope
bases (10k local / 1M global). Majority-sliding-window → runs long_500k.

The 262144×5376 unembedding (1.4 B params, 26% of the weight bytes, read
every decode step) is the framework's flagship FAµST target.
"""
import dataclasses

from repro.configs.base import ArchConfig, DECODE_POLICY, TP_POLICY

# 62 layers: repeating [local×5, global] ×10, then 2 local tail layers.
STAGES = ((10, ("local",) * 5 + ("attn",)), (1, ("local", "local")))

CONFIG = ArchConfig(
    name="gemma3-27b",
    family="dense",
    n_layers=62,
    d_model=5376,
    n_heads=32,
    n_kv_heads=16,
    head_dim=128,
    d_ff=21504,
    vocab=262144,
    act="geglu",
    norm="rms",
    stages=STAGES,
    rope_base=1_000_000.0,
    rope_base_local=10_000.0,
    qk_norm=True,
    window=1024,
    scale_embed=True,
    attn_scale=(5376 // 32) ** -0.5,  # query_pre_attn_scalar = d/H
    policy=TP_POLICY,
    policy_decode=DECODE_POLICY,
    sub_quadratic=True,  # 52/62 layers window-bounded; globals SP-sharded
)


def smoke_config() -> ArchConfig:
    return dataclasses.replace(
        CONFIG,
        n_layers=8,
        d_model=64,
        n_heads=4,
        n_kv_heads=2,
        head_dim=16,
        d_ff=128,
        vocab=151,
        stages=((1, ("local",) * 5 + ("attn",)), (1, ("local", "local"))),
        window=16,
        attn_scale=16**-0.5,
        dtype="float32",
        remat=False,
        attn_chunk=8,
    )
