"""The main path's Pallas kernels compile for a TPU v5e at olmo-1b widths.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
operand types the MXU does not take, blocks that break the (8, 128) tiling,
more VMEM than a kernel may use. These tests compile each kernel for one chip
of a described ``v5e:2x2`` topology — no chip attached, nothing runs — and
check that the kernel is in the compiled program (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only one
process at a time may load the TPU compiler library, and every test worker
imports this file. ``interpret=False`` is passed explicitly because the ops'
own default sees the CPU backend here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.fxp import FXP8
from repro.kernels.cordic_af.ops import multi_af_pallas
from repro.kernels.cordic_fused import fused_dot_af
from repro.kernels.cordic_mac.ops import cordic_mac
from repro.kernels.decode_attention import (
    gqa_decode_attention,
    mla_decode_attention,
)
from repro.kernels.flash_attention.ops import flash_attention

# olmo-1b (configs/olmo_1b.py)
D_MODEL, D_FF, VOCAB, HEADS, HEAD_DIM = 2048, 8192, 50304, 16, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import importlib.util

    from jax.experimental import topologies

    # skip only where the TPU compiler is not installed at all; any other
    # failure to describe the chip fails — these are the only tests that
    # see what Mosaic refuses
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed: no TPU compiler")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize(
    "af, m, k, n",
    [
        ("swish", 4, D_MODEL, D_FF),       # gate projection, decode rows
        ("identity", 4, D_MODEL, D_MODEL),  # attention projection
        ("identity", 4, D_MODEL, VOCAB),    # tied lm_head
        ("swish", 256, D_MODEL, D_FF),      # gate projection, a prefill bucket
        ("identity", 4, 4096, D_MODEL),     # the largest fused contraction
    ],
    ids=["gate", "attn", "lm_head", "gate_prefill", "max_k"],
)
def test_fused_dot_af_compiles(one_chip, af, m, k, n):
    _compile(
        lambda x, w, p: fused_dot_af(x, w, p, af_mode=af, compute_round=True,
                                     interpret=False),
        one_chip,
        ((m, k), jnp.bfloat16), ((k, n), jnp.float32), ((5,), jnp.int32),
    )


def test_cordic_mac_compiles(one_chip):
    _compile(
        lambda x, w: cordic_mac(x, w, depth=7, interpret=False),
        one_chip,
        ((128, D_MODEL), jnp.float32), ((D_MODEL, D_MODEL), jnp.float32),
    )


@pytest.mark.parametrize("mode", ["swish", "softmax"])
def test_cordic_af_compiles(one_chip, mode):
    _compile(
        lambda x: multi_af_pallas(x, mode, depth=8, fmt=FXP8, interpret=False),
        one_chip,
        ((128, D_FF), jnp.float32),
    )


def test_flash_attention_compiles(one_chip):
    qkv = ((1, 1024, HEADS, HEAD_DIM), jnp.bfloat16)
    _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False),
        one_chip, qkv, qkv, qkv,
    )


def test_gqa_decode_attention_compiles(one_chip):
    cache = ((4, 1024, HEADS, HEAD_DIM), jnp.float32)
    _compile(
        lambda q, k, v, pos: gqa_decode_attention(q, k, v, pos, scale=0.088,
                                                  interpret=False),
        one_chip,
        ((4, 8, HEADS, HEAD_DIM), jnp.float32), cache, cache,
        ((4, 8), jnp.int32),
    )


def test_mla_decode_attention_compiles(one_chip):
    # deepseek-v3 latent widths: kv_lora_rank 512, rope head dim 64
    heads, r, rd = 16, 512, 64
    _compile(
        lambda ql, qr, c, kr, pos: mla_decode_attention(
            ql, qr, c, kr, pos, scale=0.07, interpret=False),
        one_chip,
        ((4, 8, heads, r), jnp.float32), ((4, 8, heads, rd), jnp.float32),
        ((4, 1024, r), jnp.float32), ((4, 1024, rd), jnp.float32),
        ((4, 8), jnp.int32),
    )
