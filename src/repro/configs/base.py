"""Architecture config schema + the shape cells assigned to every arch.

Each assigned architecture gets one ``src/repro/configs/<id>.py`` exporting
``CONFIG`` (the exact published dims) and ``smoke_config()`` (a reduced
same-family variant for CPU smoke tests). ``layer_stages`` describes the
block pattern as (repeat, unit) pairs so models scan over the periodic
structure (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

from repro.distributed.sharding import ShardingPolicy
from repro.layers.faust_linear import FaustSpec
from repro.layers.mamba2 import Mamba2Spec
from repro.layers.moe import MoESpec

# Layer kinds appearing in stage units:
#   "attn"   — global attention + dense FFN
#   "local"  — sliding-window attention + dense FFN
#   "moe"    — global attention + MoE FFN
#   "ssm"    — mamba2 block (no FFN)
#   "ssm_mlp" — mamba2 block, then a dense FFN block (nemotron-h's "M-")
#   "shared" — zamba2's shared transformer block (params reused)
Stage = tuple[int, tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act: str = "swiglu"  # geglu | swiglu | gelu | sq_relu
    norm: str = "rms"  # rms | ln1p
    stages: tuple[Stage, ...] = ()
    # attention details
    rope_base: float = 10000.0
    rope_base_local: float | None = None  # gemma3 local layers
    rotary_pct: float = 1.0
    qk_norm: bool = False
    window: int | None = None  # sliding window for "local" kind
    attn_scale: float | None = None
    # embeddings
    tie_embeddings: bool = False
    scale_embed: bool = False
    n_codebooks: int = 1  # audio: parallel codebooks
    n_vision_tokens: int = 0  # vlm: prepended patch embeddings
    # moe / ssm
    moe: MoESpec | None = None
    ssm: Mamba2Spec | None = None
    # the paper's technique
    faust_unembed: FaustSpec | None = None
    faust_mlp: FaustSpec | None = None
    # numerics / distribution
    dtype: str = "bfloat16"
    remat: bool = True
    attn_chunk: int = 512
    policy: ShardingPolicy = dataclasses.field(default_factory=ShardingPolicy)
    policy_decode: ShardingPolicy | None = None
    # capability flags
    sub_quadratic: bool = False  # eligible for long_500k

    def layer_kinds(self) -> tuple[str, ...]:
        kinds: list[str] = []
        for repeat, unit in self.stages:
            kinds.extend(unit * repeat)
        assert len(kinds) == self.n_layers, (self.name, len(kinds), self.n_layers)
        return tuple(kinds)

    def decode_policy(self) -> ShardingPolicy:
        return self.policy_decode if self.policy_decode is not None else self.policy


# --- shape cells (assigned to every LM arch) -------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    kind: str  # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeCell("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeCell("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeCell("long_500k", "decode", 524288, 1),
}


def cells_for(cfg: ArchConfig) -> list[str]:
    cells = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.sub_quadratic:
        cells.append("long_500k")
    return cells


# --- common sharding policies ----------------------------------------------

TP_POLICY = ShardingPolicy()  # heads/mlp/vocab on 'model', batch on pod+data

# context-parallel: seq on 'model' (archs whose head counts don't divide 16)
CP_POLICY = ShardingPolicy(seq="model", heads_act=None)

# decode: KV-cache sequence on 'model' (SP decode), batch on data
DECODE_POLICY = ShardingPolicy(
    batch=("pod", "data"), seq=None, heads_act=None, kv_seq="model"
)
# long-context decode (batch=1): cache sequence over everything available
DECODE_LONG_POLICY = ShardingPolicy(
    batch=None, seq=None, heads_act=None, kv_seq=("pod", "data", "model")
)


def param_count(cfg: ArchConfig) -> int:
    """Analytic parameter count (embedding + layers + unembed)."""
    d = cfg.d_model
    kinds = cfg.layer_kinds()
    total = cfg.vocab * d * cfg.n_codebooks  # embed
    if not cfg.tie_embeddings:
        total += d * cfg.vocab * cfg.n_codebooks
    glu = 3 if cfg.act in ("geglu", "swiglu") else 2
    attn_p = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim + cfg.n_heads * cfg.head_dim * d
    mlp_p = glu * d * cfg.d_ff
    shared_seen = False
    for kind in kinds:
        if kind in ("attn", "local"):
            total += attn_p + mlp_p
        elif kind == "moe":
            e = cfg.moe.n_experts
            moe_p = d * e + e * glu * d * cfg.moe.d_ff
            if cfg.moe.shared_expert_ff:
                moe_p += glu * d * cfg.moe.shared_expert_ff
            total += attn_p + moe_p
        elif kind in ("ssm", "ssm_mlp"):
            s = cfg.ssm
            din = s.d_inner
            total += d * (2 * din + 2 * s.n_groups * s.d_state + s.n_heads)
            total += s.d_conv * (din + 2 * s.n_groups * s.d_state)
            total += din * d + 3 * s.n_heads + din
            if kind == "ssm_mlp":
                total += mlp_p
        elif kind == "shared":
            if not shared_seen:
                total += attn_p + mlp_p
                shared_seen = True
        else:
            raise ValueError(kind)
    return total


def active_param_count(cfg: ArchConfig) -> int:
    """Per-token active params (MoE: top-k + shared expert only)."""
    if cfg.moe is None:
        return param_count(cfg)
    d = cfg.d_model
    glu = 3 if cfg.act in ("geglu", "swiglu") else 2
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    routed = e * glu * d * cfg.moe.d_ff
    active_routed = k * glu * d * cfg.moe.d_ff
    n_moe = sum(1 for x in cfg.layer_kinds() if x == "moe")
    return param_count(cfg) - n_moe * (routed - active_routed)
