"""Spec conformance: every assigned architecture config matches the assignment
table exactly (guards against silent drift in the dry-run subjects)."""
import pytest

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable

# (arch, layers, d_model, heads, kv, d_ff, vocab)
ASSIGNED = [
    ("olmo-1b", 16, 2048, 16, 16, 8192, 50304),
    ("qwen3-8b", 36, 4096, 32, 8, 12288, 151936),
    ("qwen2.5-14b", 48, 5120, 40, 8, 13824, 152064),
    ("yi-9b", 48, 4096, 32, 4, 11008, 64000),
    ("deepseek-v3-671b", 61, 7168, 128, 128, 2048, 129280),
    ("llama4-maverick-400b-a17b", 48, 5120, 40, 8, 8192, 202048),
    ("internvl2-2b", 24, 2048, 16, 8, 8192, 92553),
    ("zamba2-7b", 81, 3584, 32, 32, 14336, 32000),
    ("mamba2-780m", 48, 1536, 0, 0, 0, 50280),
    ("seamless-m4t-large-v2", 24, 1024, 16, 16, 8192, 256206),
]


@pytest.mark.parametrize("arch,L,d,h,kv,ff,v", ASSIGNED)
def test_assigned_numbers(arch, L, d, h, kv, ff, v):
    cfg = get_config(arch)
    assert cfg.num_layers == L
    assert cfg.d_model == d
    assert cfg.num_heads == h
    assert cfg.num_kv_heads == kv
    assert cfg.d_ff == ff
    assert cfg.vocab_size == v


def test_all_ten_present():
    assert len(ARCHS) == 10


def test_family_features():
    ds = get_config("deepseek-v3-671b")
    assert ds.moe.num_experts == 256 and ds.moe.top_k == 8
    assert ds.moe.num_shared_experts == 1 and ds.moe.first_dense_layers == 3
    assert ds.mla.kv_lora_rank == 512 and ds.mla.qk_rope_head_dim == 64
    l4 = get_config("llama4-maverick-400b-a17b")
    assert l4.moe.num_experts == 128 and l4.moe.top_k == 1
    z = get_config("zamba2-7b")
    assert z.ssm.state_dim == 64 and z.subquadratic
    m = get_config("mamba2-780m")
    assert m.ssm.state_dim == 128 and m.subquadratic
    assert get_config("qwen3-8b").qk_norm
    assert get_config("qwen2.5-14b").qkv_bias
    assert get_config("olmo-1b").norm_type == "nonparametric"
    assert get_config("seamless-m4t-large-v2").encdec.encoder_layers == 24
    assert get_config("internvl2-2b").frontend == "vision"


def test_zamba2_is_the_published_model():
    """configs/zamba2_7b.py against config.json of Zyphra/Zamba2-7B-Instruct
    (the catalog row): the hybrid layer list, two shared blocks, adapters of
    rank 128, attention of 32 x 224 on the 7168-wide concat scaled by
    (224 / 2) ** -0.5, a GELU GLU of 14336, and the Mamba2 mixer."""
    z = get_config("zamba2-7b")
    assert z.hybrid.hybrid_layer_ids == (6, 11, 17, 23, 29, 35, 41, 47, 53,
                                         59, 65, 71, 77)
    assert z.hybrid.num_mem_blocks == 2 and z.hybrid.adapter_rank == 128
    assert z.head_dim == 224 == 2 * z.d_model // z.num_heads
    assert z.attn_scale == pytest.approx(112 ** -0.5)
    assert (z.act, z.glu, z.d_ff, z.tie_embeddings) == ("gelu", True, 14336, True)
    assert (z.norm_type, z.norm_eps, z.rope_theta) == ("rmsnorm", 1e-5, 1e4)
    s = z.ssm
    assert (s.num_heads, s.head_dim, s.n_groups, s.state_dim, s.conv_width,
            s.chunk_size, s.expand) == (112, 64, 2, 64, 4, 256, 2)
    assert s.num_heads * s.head_dim == 2 * z.d_model


def test_long_500k_skip_rules():
    """long_500k runs iff sub-quadratic (per the assignment)."""
    runs = {a for a in ARCHS if shape_applicable(get_config(a), SHAPES["long_500k"])[0]}
    assert runs == {"zamba2-7b", "mamba2-780m"}
    # every arch runs the other three shapes
    for a in ARCHS:
        for sname in ("train_4k", "prefill_32k", "decode_32k"):
            assert shape_applicable(get_config(a), SHAPES[sname])[0]


def test_param_budgets():
    """Total parameter counts land on the models' nominal sizes."""
    from repro.models import get_model

    expect = {
        "olmo-1b": (1.0e9, 1.4e9),
        "qwen3-8b": (7.5e9, 9.0e9),
        "qwen2.5-14b": (13.5e9, 15.5e9),
        "yi-9b": (8.0e9, 9.5e9),
        "deepseek-v3-671b": (650e9, 690e9),
        "llama4-maverick-400b-a17b": (380e9, 420e9),
        "internvl2-2b": (1.6e9, 2.2e9),
        "zamba2-7b": (6.0e9, 7.6e9),
        "mamba2-780m": (0.7e9, 1.0e9),
        "seamless-m4t-large-v2": (1.3e9, 2.1e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_model(get_config(arch)).count_params()
        assert lo <= n <= hi, (arch, n)
