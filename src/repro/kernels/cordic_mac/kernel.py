"""Pallas TPU kernel: CARMEN CORDIC-MAC as a blocked fixed-point matmul.

TPU-native adaptation of the paper's iterative CORDIC MAC (DESIGN.md §2):
the depth-d signed-digit rounding of the weights — the *entire* arithmetic
content of a depth-d linear-CORDIC multiplier — is applied to the weight
memory bank once (ops.py), and the MAC array itself is the MXU: an
int8/int16 x int8/int16 -> int32 blocked matmul. The epilogue fuses the
requantization stage and (optionally) the ReLU bypass of the multi-AF block,
mirroring the silicon pipeline MAC -> requant -> AF.

Tiling: grid (M/bm, N/bn, K/bk) with K innermost; partial products accumulate
in an int32 VMEM scratch tile that lives across the K steps (the PE's wide
accumulator register). Block shapes are MXU-aligned (128 multiples; int8
native tile is (32, 128)).

VMEM budget at defaults bm=bn=bk=256:
    x tile   256*256*1B  =  64 KiB
    w tile   256*256*1B  =  64 KiB
    acc      256*256*4B  = 256 KiB
    out      256*256*4B  = 256 KiB   (dequantized f32)
    total ~= 640 KiB << 16 MiB VMEM (leaves room for double buffering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256


def _mac_kernel(x_ref, w_ref, xscale_ref, wscale_ref, out_ref, acc_ref, *, n_k: int, fuse_relu: bool):
    """One (bm, bn) output tile; K-step ``pl.program_id(2)`` accumulates."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU path: integer dot with int32 accumulation.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        # requant stage: int32 accumulator -> float via the per-tile scales
        # (xscale: per-row of this tile; wscale: per-column of this tile).
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * xscale_ref[...] * wscale_ref[...]
        if fuse_relu:
            out = jnp.maximum(out, 0.0)
        out_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "fuse_relu", "interpret"),
)
def mac_matmul(
    x_q,
    w_q,
    x_scale,
    w_scale,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    fuse_relu: bool = False,
    interpret: bool = False,
):
    """Blocked integer matmul with fused requant (+ReLU) epilogue.

    x_q: (M, K) int8/int16 quantized activations.
    w_q: (K, N) int8/int16 signed-digit weights.
    x_scale: (M, 1) f32 per-row scales;  w_scale: (1, N) f32 per-col scales.
    Returns (M, N) f32.
    """
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shapes must be tile-aligned: {(m, k, n)} vs {(bm, bk, bn)}"
    )
    n_k = k // bk
    grid = (m // bm, n // bn, n_k)

    return pl.pallas_call(
        functools.partial(_mac_kernel, n_k=n_k, fuse_relu=fuse_relu),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, x_scale, w_scale)

