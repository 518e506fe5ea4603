"""carmen backend: paper-faithful CORDIC simulation over the FxP substrate.

Per-call path (QAT / training): activations fake-quantized to the FxP format,
weights rounded to the depth-d signed-digit grid by a traced masked loop
(= linear-CORDIC multiplier), single real matmul, straight-through gradients.

Prepared path (serving): the signed-digit grid is materialized once by
``prepare`` at the policy depth — the forward then only fake-quantizes
activations and runs the matmul, exactly like the silicon engine whose weight
bank is written once. Bit-identical to the per-call forward (the traced and
static rounders agree digit-for-digit; see tests/test_backends.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import cordic
from ..fxp import FXP8, FxPFormat, dequantize, quantize
from .base import Backend, PreparedWeight, unit_fmt

__all__ = ["CarmenBackend", "carmen_dot", "sd_round_traced"]


def sd_round_traced(w, depth, w_fmt: FxPFormat):
    """signed_digit_round with a (possibly traced) depth: full-trip masked loop.

    Runtime-adaptive mode switching: the loop bound is static (full depth) but
    iterations beyond ``depth`` are masked out, so one compiled program serves
    every depth — the software analogue of the paper's "no hardware
    modification" claim.
    """
    z = jnp.round(jnp.asarray(w, jnp.float32) * (1 << w_fmt.frac)).astype(jnp.int32)
    z = jnp.clip(z, w_fmt.qmin, w_fmt.qmax)
    depth = jnp.asarray(depth, jnp.int32)
    full = cordic.full_depth(w_fmt)

    def body(k, carry):
        z, acc = carry
        active = k < depth
        d = jnp.where(z >= 0, jnp.int32(1), jnp.int32(-1))
        step = jnp.where(active, (jnp.int32(w_fmt.one) >> k) * d, 0)
        return (z - step, acc + step)

    _, acc = jax.lax.fori_loop(0, full, body, (z, jnp.zeros_like(z)))
    return acc.astype(jnp.float32) * np.float32(w_fmt.scale)


def quantize_activations(x, x_fmt: FxPFormat):
    """Fake-quantize activations into the FxP grid (float32 values out).

    Identity on non-finite inputs: the float->int32 grid cast would otherwise
    launder a NaN/Inf (e.g. from a poisoned KV row) into a plausible finite
    value — silent data corruption that the serving fault flag
    (``serve.engine.make_decode_burst``) could never see at the logits. Real
    FxP silicon cannot hold a NaN either, but there the symptom is a
    saturated accumulator (the ``logit_limit`` probe); the float simulation
    keeps the poison explicit instead. Finite values are untouched, so clean
    streams stay bit-identical.
    """
    xf = jnp.asarray(x, jnp.float32)
    q = dequantize(quantize(xf, x_fmt), x_fmt).astype(jnp.float32)
    return jnp.where(jnp.isfinite(xf), q, xf)


# --- fake-quant forward, straight-through backward ---------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _carmen_matmul_ste(x, w, depth, x_fmt: FxPFormat, w_fmt: FxPFormat):
    xq = quantize_activations(x, x_fmt)
    wq = sd_round_traced(w, depth, w_fmt)
    return jnp.dot(xq, wq, preferred_element_type=jnp.float32)


def _carmen_fwd(x, w, depth, x_fmt, w_fmt):
    return _carmen_matmul_ste(x, w, depth, x_fmt, w_fmt), (x, w)


def _carmen_bwd(x_fmt, w_fmt, res, g):
    x, w = res
    gf = g.astype(jnp.float32)
    dx = jnp.dot(gf, w.astype(jnp.float32).T).astype(x.dtype)
    dw = jnp.dot(x.astype(jnp.float32).reshape(-1, x.shape[-1]).T,
                 gf.reshape(-1, g.shape[-1])).astype(w.dtype)
    return dx, dw, None


_carmen_matmul_ste.defvjp(_carmen_fwd, _carmen_bwd)


def carmen_dot(x, w, depth, x_fmt: FxPFormat = FXP8, w_fmt: Optional[FxPFormat] = None):
    """Functional form of the carmen-mode matmul (used by benchmarks/tests)."""
    return _carmen_matmul_ste(x, w, depth, x_fmt, w_fmt or unit_fmt(x_fmt))


class CarmenBackend(Backend):
    name = "carmen"

    def prepare(self, w, lp, *, stacked_axes: int = 0, in_axes=None):
        fmt = unit_fmt(lp.fmt)
        data = cordic.signed_digit_round(w, int(lp.depth), fmt)
        # x_fmt makes the bank self-describing: the prepared dot quantizes
        # activations at the preparation point's format, so runtime mode
        # switching (multi-point banks, repro.runtime) never consults ctx.policy
        return PreparedWeight(
            data, None, self.name,
            (("depth", int(lp.depth)), ("fmt", (fmt.bits, fmt.frac)),
             ("x_fmt", (lp.fmt.bits, lp.fmt.frac))),
        )

    @jax.named_scope("dot.carmen")
    def dot(self, ctx, x, w, *, name: str = ""):
        shape = x.shape[:-1] + (w.shape[-1],)
        x2 = x.reshape(-1, x.shape[-1])
        if isinstance(w, PreparedWeight):
            x_fmt = w.get("x_fmt")
            x_fmt = (
                FxPFormat(*x_fmt) if x_fmt else ctx.layer_precision(name).fmt
            )
            xq = quantize_activations(x2, x_fmt)
            out = jnp.dot(xq, w.data, preferred_element_type=jnp.float32)
        else:
            lp = ctx.layer_precision(name)
            out = _carmen_matmul_ste(x2, w, lp.depth, lp.fmt, unit_fmt(lp.fmt))
        return out.reshape(shape).astype(ctx.compute_dtype)
