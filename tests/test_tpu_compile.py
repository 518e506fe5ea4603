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


def test_decode_burst_carries_cache(one_chip):
    """The greedy decode burst at olmo-1b widths (2 layers, 16 slots,
    max_len 1026) keeps the stacked KV cache in the layer scan's carry: the
    program materialises no ``copy`` or ``dynamic-slice`` of the shape of
    one layer's or of the whole stack's K/V cache, and no fusion makes one
    layer's slab; the attention fusions read the stack in place. Its
    temporaries stay below one K+V cache (moving the cache as scan xs/ys
    needs a whole stack of them)."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.core import EngineContext
    from repro.models import get_model
    from repro.serve.engine import _init_slot_state, make_decode_burst

    layers, slots, max_len = 2, 16, 1026
    cfg = dataclasses.replace(get_config("olmo-1b"), num_layers=layers)
    model = get_model(cfg)
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)
    params = place(model.abstract_params(jnp.bfloat16))
    cache = place(model.make_cache(slots, max_len, dtype=jnp.float32,
                                   abstract=True))
    state = place(jax.eval_shape(lambda: _init_slot_state(slots)))
    burst = jax.jit(make_decode_burst(model, EngineContext(mode="exact"), 8,
                                      sampled=False), donate_argnums=(1, 2))
    compiled = burst.lower(params, cache, state).compile()

    # A fused computation reads its operands where they lie; what the
    # program materialises is an instruction of any other computation.
    text = compiled.as_text()
    fused = set(re.findall(r"calls=(%[\w.-]+)", text))
    layer = f"{slots},{max_len},{HEADS},{HEAD_DIM}"
    slabs = {f"f32[{layer}]", f"f32[1,{layer}]"}
    stack = f"f32[{layers},{layer}]"
    movers, inside = [], None
    for line in text.splitlines():
        if line.endswith("{") and (head := re.match(r"(?:ENTRY )?(%\S+) ", line)):
            inside = head.group(1)
            continue
        m = re.search(r"= (f32\[[0-9,]+\])\S* ([\w-]+)\(", line)
        if inside in fused or not m:
            continue
        shape, op = m.groups()
        if (op in ("copy", "dynamic-slice") and (shape in slabs or shape == stack)
                or op == "fusion" and shape in slabs):
            movers.append(line.strip()[:160])
    assert not movers, movers
    kv_bytes = 2 * layers * slots * max_len * HEADS * HEAD_DIM * 4
    assert compiled.memory_analysis().temp_size_in_bytes < kv_bytes
