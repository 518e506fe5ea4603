"""zamba2-7b [arXiv:2411.15242; config.json of Zyphra/Zamba2-7B-Instruct].

81 layers, each with a Mamba2 (SSD) mixer of 112 heads x 64 (d_inner 7168),
2 B/C groups, state 64, conv 4. The 13 ``hybrid_layer_ids`` first call one
of two shared transformer blocks, alternating A, B, A, ...: attention of 32
heads x 224 over the 7168-wide concatenation of the residual stream and the
embedding output (RoPE on all 224 dims, scores scaled by (224 / 2) ** -0.5),
then a GELU GLU MLP of 14336 with the call's own rank-128 adapter on
gate/up, and the layer's own 3584 x 3584 ``linear`` into the mixer's input.
``tie_word_embeddings`` is absent from the published config: the default,
tied, applies.
"""
from .base import HybridConfig, ModelConfig, SSMConfig

HEAD_DIM = 224  # attention_head_dim = 2 * hidden_size / num_attention_heads

CONFIG = ModelConfig(
    name="zamba2-7b",
    family="hybrid",
    num_layers=81,
    d_model=3584,
    num_heads=32,
    num_kv_heads=32,
    head_dim=HEAD_DIM,
    attn_scale=(HEAD_DIM / 2) ** -0.5,
    d_ff=14336,
    vocab_size=32000,
    norm_type="rmsnorm",
    norm_eps=1e-5,
    act="gelu",
    glu=True,
    rope_theta=1e4,
    tie_embeddings=True,
    ssm=SSMConfig(state_dim=64, head_dim=64, num_heads=112, expand=2,
                  conv_width=4, chunk_size=256, n_groups=2),
    hybrid=HybridConfig(
        hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
        num_mem_blocks=2, adapter_rank=128),
    subquadratic=True,
)
