"""int8 backend: real int8 x int8 -> int32 dot (production MXU path).

Per-call path: per-output-channel weight scales recomputed every call (the
seed behaviour — kept for calibration sweeps and as the parity oracle).

Prepared path: ``prepare`` quantizes the weight bank once — int8 qvalues with
per-channel scales, CORDIC depth pre-applied as trailing-bit zeroing — so the
serving forward only computes the dynamic per-token activation scale. This
absorbs what ``quant/qat.py`` used to do standalone (``quantize_params_int8``
and ``QuantizedLinear`` now delegate here).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import cordic
from .base import Backend, PreparedWeight

__all__ = ["Int8Backend", "effective_bits", "int8_dot", "quantize_weight"]


def effective_bits(lp) -> int:
    """CORDIC depth -> effective weight bits (the int8 incarnation of depth)."""
    return max(2, min(8, int(np.ceil(lp.depth * 8 / cordic.full_depth(lp.fmt)))))


def quantize_weight(w, *, per_channel: bool = True, stacked_axes: int = 0,
                    eff_bits: int = 8,
                    in_axes: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """One-time weight-bank quantization: int8 qvalues + float scales.

    ``per_channel`` reduces over the contraction axes (keepdims): the
    ``in_axes`` axes that fold into the matmul's input dim (default: all but
    the last axis). Leading ``stacked_axes`` axes (stacked layer banks
    consumed by ``lax.scan``) keep their extent so the scale slices alongside
    the qvalues. ``eff_bits < 8`` zeroes trailing bits of the grid — reduced
    CORDIC depth, baked in.
    """
    wf = jnp.asarray(w, jnp.float32)
    if in_axes is None:
        in_axes = wf.ndim - stacked_axes - 1
    axes = tuple(range(stacked_axes, stacked_axes + in_axes)) if per_channel else None
    amax = jnp.max(jnp.abs(wf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    wq = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    if eff_bits < 8:
        drop = 8 - eff_bits
        wq = ((wq.astype(jnp.int32) >> drop) << drop).astype(jnp.int8)
    return wq, scale.astype(jnp.float32)


def int8_dot(x, w, *, effective_bits: int = 8, w_scale=None):
    """int8 x int8 -> int32 dot with per-output-channel weight scales.

    ``effective_bits < 8`` zeroes trailing bits of the weight grid — the int8
    incarnation of reduced CORDIC depth. ``w_scale`` may be precomputed
    (serving: weights stored quantized once).
    """
    xf = x.astype(jnp.float32)
    # per-token (per-row) dynamic activation scale — broadcasts over the N axis
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    x_scale = jnp.maximum(amax, 1e-8) / 127.0
    xq = jnp.clip(jnp.round(xf / x_scale), -127, 127).astype(jnp.int8)

    if w_scale is None:
        wf = w.astype(jnp.float32)
        w_scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=0, keepdims=True), 1e-8) / 127.0
        wq = jnp.clip(jnp.round(wf / w_scale), -127, 127).astype(jnp.int8)
    else:
        wq = w  # already int8
    if effective_bits < 8:
        drop = 8 - effective_bits
        wq = ((wq.astype(jnp.int32) >> drop) << drop).astype(jnp.int8)

    acc = jax.lax.dot_general(
        xq, wq, (((xq.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    return acc.astype(jnp.float32) * x_scale * w_scale


class Int8Backend(Backend):
    name = "int8"

    def prepare(self, w, lp, *, stacked_axes: int = 0, in_axes: Optional[int] = None):
        eff = effective_bits(lp)
        wq, scale = quantize_weight(
            w, stacked_axes=stacked_axes, eff_bits=eff, in_axes=in_axes
        )
        # depth recorded for the runtime cycle model (repro.runtime.telemetry);
        # the arithmetic consumes only the pre-baked effective_bits grid
        return PreparedWeight(
            wq, scale, self.name,
            (("effective_bits", eff), ("depth", int(lp.depth))),
        )

    @jax.named_scope("dot.int8")
    def dot(self, ctx, x, w, *, name: str = ""):
        if isinstance(w, PreparedWeight):
            # depth already baked into the stored grid — activation side only
            out = int8_dot(x, w.data, effective_bits=8, w_scale=w.scale)
        else:
            lp = ctx.layer_precision(name)
            out = int8_dot(x, w, effective_bits=effective_bits(lp))
        return out.astype(ctx.compute_dtype)
