"""granite-moe-3b-a800m [moe] — 40 experts top-8 [hf:ibm-granite family].

32L d_model=1536 24H (GQA kv=8, head_dim=64) d_ff=512 per expert
vocab=49155, SwiGLU. 40 experts and 24 heads don't divide 16 → experts
replicated with per-expert d_ff TP'd... d_ff=512/16=32 (divisible); heads
context-parallel. FAµST note (DESIGN.md §5): 512-wide expert FFNs are below
the 128-block granularity for useful block sparsity → FAµST applies to the
unembedding only.
"""
import dataclasses

from repro.configs.base import ArchConfig, CP_POLICY, DECODE_POLICY
from repro.distributed.sharding import ShardingPolicy
from repro.layers.moe import MoESpec

# CP activations + gather-at-MoE-boundary (ff-TP experts)
GRANITE_POLICY = ShardingPolicy(seq="model", heads_act=None, moe_gather_seq=True)

CONFIG = ArchConfig(
    name="granite-moe-3b-a800m",
    family="moe",
    n_layers=32,
    d_model=1536,
    n_heads=24,
    n_kv_heads=8,
    head_dim=64,
    d_ff=512,
    vocab=49155,
    act="swiglu",
    norm="rms",
    stages=((32, ("moe",)),),
    tie_embeddings=True,
    moe=MoESpec(
        n_experts=40, top_k=8, d_ff=512, act="swiglu", capacity_factor=1.25
    ),
    policy=GRANITE_POLICY,
    policy_decode=DECODE_POLICY,
)


def smoke_config() -> ArchConfig:
    return dataclasses.replace(
        CONFIG,
        n_layers=2,
        d_model=48,
        n_heads=4,
        n_kv_heads=2,
        head_dim=12,
        d_ff=32,
        vocab=101,
        stages=((2, ("moe",)),),
        # capacity_factor = E/k → drop-free for consistency tests
        moe=MoESpec(n_experts=5, top_k=2, d_ff=32, act="swiglu", capacity_factor=2.5),
        dtype="float32",
        remat=False,
        attn_chunk=8,
    )
