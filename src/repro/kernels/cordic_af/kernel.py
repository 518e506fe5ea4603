"""Pallas TPU kernel: CARMEN's time-multiplexed multi-AF block.

One kernel body serves six elementwise activation functions, selected by a
**runtime mode scalar** (SMEM) — the software image of the paper's
time-multiplexed shared CORDIC datapath: the hyperbolic-rotation exp core,
the linear-vectoring divider and the linear-rotation multiplier are emitted
once and every AF branch of the ``lax.switch`` composes them. ReLU is the
bypass branch. Softmax (the seventh AF) needs a row reduction, so it gets a
row-blocked sibling kernel sharing the same sub-units.

The fixed-point arithmetic inside the kernel is *literally* the core library
(`repro.core.activations` / `repro.core.cordic`) traced into the Pallas body —
kernel and bit-faithful simulation cannot drift apart.

Tiling: elementwise AFs use (bm, bn) = (256, 256) f32 blocks (in + out + ~3
int32 temporaries ~= 1.25 MiB VMEM). Softmax blocks whole rows (bm, N).

CORDIC depth is a compile-time parameter of the kernel (one specialization per
depth — the runtime-adaptive *traced-depth* path lives in the production int8
engine, see core/engine.py). Mode is runtime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import activations as afs
from repro.core.fxp import FxPFormat, dequantize, quantize, requantize

DEFAULT_BM = 256
DEFAULT_BN = 256

# Elementwise AFs become switch branches in this fixed order (softmax separate).
ELEMENTWISE_AFS = ("relu", "gelu", "tanh", "sigmoid", "swish", "selu")


def _af_elementwise_kernel(mode_ref, x_ref, out_ref, *, depth: int, fmt: FxPFormat):
    x = x_ref[...]
    ifmt = afs.internal_fmt(fmt)
    d = max(depth + (ifmt.frac - fmt.frac), 2)
    xq = requantize(quantize(x, fmt), fmt, ifmt)  # I/O grid -> guard-bit datapath

    branches = [
        functools.partial(afs.multi_af, mode=name, depth=d, fmt=ifmt)
        for name in ELEMENTWISE_AFS
    ]
    out_raw = jax.lax.switch(mode_ref[0], branches, xq)
    out_ref[...] = dequantize(requantize(out_raw, ifmt, fmt), fmt)


def _af_softmax_kernel(x_ref, out_ref, *, depth: int, fmt: FxPFormat):
    x = x_ref[...]
    ifmt = afs.internal_fmt(fmt)
    d = max(depth + (ifmt.frac - fmt.frac), 2)
    xq = requantize(quantize(x, fmt), fmt, ifmt)
    out_raw = afs.cordic_softmax(xq, d, ifmt, axis=-1)
    out_ref[...] = dequantize(requantize(out_raw, ifmt, fmt), fmt)


@functools.partial(jax.jit, static_argnames=("depth", "fmt", "bm", "bn", "interpret"))
def af_elementwise(
    x,
    mode,
    *,
    depth: int,
    fmt: FxPFormat,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    interpret: bool = False,
):
    """(M, N) f32 -> (M, N) f32, AF selected by runtime ``mode`` (int32 index)."""
    m, n = x.shape
    assert m % bm == 0 and n % bn == 0, (x.shape, bm, bn)
    mode = jnp.asarray(mode, jnp.int32).reshape((1,))
    return pl.pallas_call(
        functools.partial(_af_elementwise_kernel, depth=depth, fmt=fmt),
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(mode, x)


@functools.partial(jax.jit, static_argnames=("depth", "fmt", "bm", "interpret"))
def af_softmax(
    x,
    *,
    depth: int,
    fmt: FxPFormat,
    bm: int = 8,
    interpret: bool = False,
):
    """Row-blocked fixed-point softmax over the last axis."""
    m, n = x.shape
    assert m % bm == 0, (x.shape, bm)
    return pl.pallas_call(
        functools.partial(_af_softmax_kernel, depth=depth, fmt=fmt),
        grid=(m // bm,),
        in_specs=[pl.BlockSpec((bm, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x)
