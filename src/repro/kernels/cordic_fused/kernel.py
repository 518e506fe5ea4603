"""Fused CORDIC dot + activation epilogue — one VMEM-resident Pallas pass.

The unfused kernel path materializes the prepared-dot output to HBM, then
re-reads it through ``multi_af_pallas``.  This kernel performs the whole
per-layer chain in one pass over the output tile:

    quantize(x) -> exact integer dot against the signed-digit weight grid
                -> descale -> (optional compute-dtype round)
                -> time-multiplexed CORDIC activation -> f32 out

Everything that varies across :class:`~repro.runtime.bank.ExecutionPoint`\\ s —
CORDIC dot depth, activation-format parameters, and the AF mode selector —
rides in a small int32 *params* vector delivered as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``).  The compiled program is therefore
identical for every point: a ModeController switch swaps the vector, not the
kernel.

Bit-parity strategy: the matmul is an exact integer dot.  Activations are
quantized in-kernel (round-half-even, saturate) and the signed-digit grid
values are multiples of ``2**-w_frac``, so ``round(w * 2**w_frac)`` recovers
the weight integers exactly.  The TPU MXU multiplies int8 (and bf16), not
int32, so the kernel feeds it int8 operands accumulating to int32
(:func:`int_dot`): FXP8 points (8-bit activations, ``w_frac <= 6`` grids)
fit int8 directly; wider points are split into three exact base-``2**7``
int8 digits each and recombined, which equals the int32 dot modulo ``2**32``.
The branch is a runtime ``lax.cond`` on the params vector, so one compiled
kernel still serves every point.  Integer accumulation is order-independent,
so the pure-XLA reference (:func:`repro.kernels.cordic_fused.ref`), which
runs a plain int32 ``dot_general``, is bitwise equal — for FXP8 *and* FXP16 —
regardless of tile order.  Power-of-two scales are built from exponent bits
(:func:`pow2`), never ``exp2``, so both paths scale exactly.  The activation
epilogue reuses the same fixed-point `multi_af` library as the standalone
``cordic_af`` kernel.

The params vector layout (``make_point`` builds the first five entries; the
op appends the AF mode index):

    [0] dot CORDIC depth (informational — baked into the prepared grid)
    [1] activation fraction bits  (x_frac)
    [2] activation qmin
    [3] activation qmax
    [4] weight fraction bits      (w_frac)
    [5] AF mode index into FUSED_AFS
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import activations as afs
from repro.core import fxp
from repro.core.fxp import FXP8_UNIT

from ..cordic_af.kernel import ELEMENTWISE_AFS

# Mode 0 is a plain (no-activation) prepared dot so attention/output
# projections share the same compiled kernel as MLP gate/up projections.
FUSED_AFS = ("identity",) + ELEMENTWISE_AFS

# params-vector indices
P_DEPTH = 0
P_XFRAC = 1
P_XQMIN = 2
P_XQMAX = 3
P_WFRAC = 4
P_MODE = 5
POINT_LEN = 5  # entries owned by make_point; P_MODE is appended per call
PARAM_LEN = 6


# Operand widths the int8 digit split covers: activations of at most 16 bits,
# weight grids (|w| < 2, see ``cordic.signed_digit_round``) of at most 14
# fraction bits, i.e. integers in [-2**15, 2**15).
MAX_X_BITS = 16
MAX_W_FRAC = 14


def make_point(depth: int, x_fmt: fxp.FxPFormat, w_fmt: fxp.FxPFormat):
    """Pack an execution point's dot parameters into the int32 params vector.

    The result is a *traced-compatible* array: swapping it between calls does
    not retrace, which is the whole trick behind zero-cost mode switches.
    """
    if x_fmt.bits > MAX_X_BITS or w_fmt.frac > MAX_W_FRAC:
        raise ValueError(
            f"the fused dot takes activations of <= {MAX_X_BITS} bits and "
            f"weight grids of <= {MAX_W_FRAC} fraction bits; got {x_fmt}, "
            f"{w_fmt}"
        )
    return jnp.asarray(
        [int(depth), x_fmt.frac, x_fmt.qmin, x_fmt.qmax, w_fmt.frac],
        jnp.int32,
    )


def pow2(n):
    """Exact ``2.0**n`` in f32 for int32 ``n`` in [-126, 127], assembled from
    the exponent bits: ``exp2`` may be approximated on a TPU, and the kernel
    and the XLA reference must scale identically. Mosaic bitcasts vectors
    only, so the kernel passes ``n`` broadcast to its tile shape."""
    return jax.lax.bitcast_convert_type((n + 127) << 23, jnp.float32)


def round_to_bf16(v):
    """f32 ``v`` rounded to the nearest bfloat16 (ties to even), kept in f32.

    Built from integer ops on the bits: XLA may drop an
    ``astype(bfloat16).astype(float32)`` pair as excess precision while
    Mosaic keeps it, and the two paths must round alike. Finite inputs only.
    """
    b = jax.lax.bitcast_convert_type(v, jnp.int32)
    b = (b + (0x7FFF + ((b >> 16) & 1))) & -0x10000
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def is_narrow(params):
    """Whether the point's integer operands fit int8: activations clipped to
    [-128, 127] and weight grids (|w| < 2) with at most 6 fraction bits."""
    return ((params[P_XQMIN] >= -128) & (params[P_XQMAX] <= 127)
            & (params[P_WFRAC] <= FXP8_UNIT.frac))


def _dot8(a, b):
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _int8_digits(v):
    """Base-2**7 digits ``(hi, mid, lo)`` of int32 ``v`` in [-2**15, 2**15):
    ``v == hi * 2**14 + mid * 2**7 + lo``, each digit an exact int8."""
    return ((v >> 14).astype(jnp.int8), ((v >> 7) & 127).astype(jnp.int8),
            (v & 127).astype(jnp.int8))


def int_dot(xq, wq, narrow):
    """``xq @ wq`` of int32 operands (modulo 2**32, like an int32 dot) with
    int8 MXU operands: one int8 dot when ``narrow`` (see :func:`is_narrow`),
    else nine dots of the int8 digits, each exact in int32, recombined."""

    def one():
        return _dot8(xq.astype(jnp.int8), wq.astype(jnp.int8))

    def digits():
        xd, wd = _int8_digits(xq), _int8_digits(wq)
        acc = None
        for i in range(3):
            for j in range(3):
                part = _dot8(xd[i], wd[j]) << (7 * (4 - i - j))
                acc = part if acc is None else acc + part
        return acc

    return jax.lax.cond(narrow, one, digits)


def af_epilogue(h, mode, af_depth, af_fmt, compute_round):
    """The shared activation chain applied to the f32 dot output ``h``.

    ``mode`` may be a static string (XLA reference path) or a traced int32
    scalar indexing :data:`FUSED_AFS` (kernel path, via ``lax.switch``).  Both
    run the exact same ops so the two paths stay bitwise identical.
    """
    ifmt = afs.internal_fmt(af_fmt)
    d = max(int(af_depth) + (ifmt.frac - af_fmt.frac), 2)

    def _apply(v, name):
        if name == "identity":
            return v
        if compute_round:
            # the unfused path hands the dot output to apply_af in the
            # compute dtype; reproduce that single rounding here
            v = round_to_bf16(v)
        xq = fxp.requantize(fxp.quantize(v, af_fmt), af_fmt, ifmt)
        raw = afs.multi_af(xq, name, d, ifmt)
        return fxp.dequantize(fxp.requantize(raw, ifmt, af_fmt), af_fmt)

    if isinstance(mode, str):
        return _apply(h, mode)
    branches = [functools.partial(_apply, name=name) for name in FUSED_AFS]
    return jax.lax.switch(mode, branches, h)


def fused_kernel(params_ref, x_ref, w_ref, out_ref, *, af_depth, af_fmt,
                 compute_round):
    """grid = (M // bm, N // bn); x tile (bm, K), w tile (K, bn)."""
    x_frac = params_ref[P_XFRAC]
    qmin = params_ref[P_XQMIN].astype(jnp.float32)
    qmax = params_ref[P_XQMAX].astype(jnp.float32)
    w_frac = params_ref[P_WFRAC]

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    tile_pow2 = lambda n, like: pow2(jnp.full(like.shape, n, jnp.int32))

    xq = jnp.clip(jnp.round(x * tile_pow2(x_frac, x)),
                  qmin, qmax).astype(jnp.int32)
    # signed-digit grid values are exact multiples of 2**-w_frac, so this
    # recovers the weight integers exactly
    wq = jnp.round(w * tile_pow2(w_frac, w)).astype(jnp.int32)

    acc = int_dot(xq, wq, is_narrow(params_ref))
    h = acc.astype(jnp.float32) * tile_pow2(-(x_frac + w_frac), acc)

    out_ref[...] = af_epilogue(h, params_ref[P_MODE], af_depth, af_fmt,
                               compute_round)
