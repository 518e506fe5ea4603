"""Model / shape / engine configuration system.

Every assigned architecture is a :class:`ModelConfig` instance in its own
``configs/<id>.py``; the CARMEN execution point (precision x depth policy) is
orthogonal and supplied per run. ``reduced()`` produces the small-config
variant used by CPU smoke tests; the full configs are exercised only through
the dry-run (ShapeDtypeStruct, no allocation).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    d_ff_expert: int
    num_shared_experts: int = 0
    d_ff_shared: int = 0
    first_dense_layers: int = 0  # leading dense layers (deepseek: 3)
    moe_every: int = 1  # a layer is MoE iff (i % moe_every == moe_every-1) past prefix
    d_ff_dense: int = 0  # d_ff of the interleaved/prefix dense layers
    capacity_factor: float = 1.25
    router_dtype: str = "float32"  # criticality-pinned (DESIGN.md §4)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V3 multi-head latent attention."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD mixer."""

    state_dim: int = 128
    head_dim: int = 64  # P
    num_heads: int = 0  # derived: d_inner // head_dim if 0
    expand: int = 2  # d_inner = expand * d_model
    conv_width: int = 4
    chunk_size: int = 256
    n_groups: int = 1  # B/C projection groups


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """Zamba2 hybrid (arXiv:2411.15242): every layer holds a Mamba2 mixer;
    before its mixer, each layer in ``hybrid_layer_ids`` calls one of
    ``num_mem_blocks`` shared transformer blocks (the ``k``-th such call
    block ``k % num_mem_blocks``) on the concatenation of the residual
    stream and the embedding output, through its own rank-``adapter_rank``
    adapter on the MLP's gate/up projections and its own ``linear``. A
    model of ``num_layers`` layers keeps the ids below it."""

    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 2
    adapter_rank: int = 128


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    encoder_layers: int = 24
    # decoder layer count = ModelConfig.num_layers
    encoder_seq_factor: float = 1.0  # encoder frames per decoder token (stub frontend)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | vlm | hybrid | ssm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    # attention flavour
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e6
    # softmax scale of attention scores; None: head_dim ** -0.5
    attn_scale: Optional[float] = None
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm | nonparametric
    norm_eps: float = 1e-6  # rmsnorm epsilon (and the mixer's gated norm)
    act: str = "swish"  # MLP activation (multi-AF block mode)
    glu: bool = True  # gated MLP (SwiGLU-style)
    tie_embeddings: bool = False
    # sub-configs
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid: Optional[HybridConfig] = None
    encdec: Optional[EncDecConfig] = None
    frontend: Optional[str] = None  # None | "vision" | "audio"
    frontend_tokens: int = 256  # stub patch/frame positions prepended
    dtype: str = "bfloat16"
    # which attention flavor long-context decoding is allowed with
    subquadratic: bool = False

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kv_groups(self) -> int:
        return max(1, self.num_heads // max(self.num_kv_heads, 1))

    def validate(self) -> None:
        if self.num_heads:
            assert self.num_heads % max(self.num_kv_heads, 1) == 0, self.name
        if self.moe:
            assert self.family in ("moe",), self.name
        if self.family == "ssm":
            assert self.ssm is not None
        if self.family == "hybrid":
            assert self.ssm is not None and self.hybrid is not None
        if self.family == "audio":
            assert self.encdec is not None


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One benchmark cell shape. ``kind`` selects which step gets lowered."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")
ALL_SHAPES: Tuple[ShapeConfig, ...] = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
SHAPES = {s.name: s for s in ALL_SHAPES}


def shape_applicable(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Spec'd skip rules: long_500k only for sub-quadratic mixers."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "quadratic full attention at 524k ctx — architecturally inapplicable"
    return True, ""


def reduced(cfg: ModelConfig, *, layers: int = 2, d_model: int = 128) -> ModelConfig:
    """Family-preserving small config for CPU smoke tests. A hybrid keeps
    the published layer pattern's shortest prefix in which every shared
    block is called (``layers`` is ignored) and its attention head as wide
    as the concatenated input over the heads."""
    scale = d_model / cfg.d_model
    heads = max(2, min(cfg.num_heads, 4))
    kv = max(1, min(cfg.num_kv_heads, heads))
    head_dim = max(16, d_model // heads)
    if cfg.hybrid:
        h = cfg.hybrid
        layers = h.hybrid_layer_ids[h.num_mem_blocks - 1] + 1
        head_dim = 2 * d_model // heads
    updates = dict(
        num_layers=layers,
        d_model=d_model,
        num_heads=heads if cfg.num_heads else 0,
        num_kv_heads=kv if cfg.num_heads else 0,
        head_dim=head_dim,
        d_ff=max(32, int(cfg.d_ff * scale)) if cfg.d_ff else 0,
        vocab_size=256,
        frontend_tokens=8,
        dtype="float32",
    )
    if cfg.moe:
        updates["moe"] = dataclasses.replace(
            cfg.moe,
            num_experts=4,
            top_k=min(cfg.moe.top_k, 2),
            d_ff_expert=64,
            d_ff_shared=64 if cfg.moe.num_shared_experts else 0,
            d_ff_dense=64 if cfg.moe.d_ff_dense else 0,
            first_dense_layers=min(cfg.moe.first_dense_layers, 1),
        )
    if cfg.mla:
        updates["mla"] = MLAConfig(
            q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16
        )
        updates["head_dim"] = 16
    if cfg.attn_scale is not None:  # keep its ratio to head_dim ** -0.5
        updates["attn_scale"] = cfg.attn_scale * math.sqrt(cfg.head_dim / head_dim)
    if cfg.ssm:
        updates["ssm"] = dataclasses.replace(
            cfg.ssm, state_dim=16, head_dim=16, chunk_size=32, num_heads=0
        )
    if cfg.hybrid:
        updates["hybrid"] = dataclasses.replace(cfg.hybrid, adapter_rank=8)
    if cfg.encdec:
        updates["encdec"] = dataclasses.replace(cfg.encdec, encoder_layers=layers)
    return dataclasses.replace(cfg, **updates)
