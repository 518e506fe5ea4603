"""Pure-XLA reference for the fused dot+AF chain.

Runs the same integer-dot computation as the Pallas kernel — same
quantization, the same integer dot (here one plain int32 ``dot_general``; the
kernel feeds the MXU exact int8 digits of it), same descale, same activation
epilogue — so it is bitwise equal to the kernel in interpret mode and on
TPU.  It doubles as the dispatch fallback whenever the fused kernel is
unavailable (mesh-sharded params, oversized K) and as the oracle in the
parity tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import P_WFRAC, P_XFRAC, P_XQMAX, P_XQMIN, af_epilogue, pow2


def fused_dot_af_ref(x, w, point, *, af_mode, af_depth, af_fmt, compute_round):
    """``x: (..., K) float``, ``w: (K, N) float`` signed-digit grid values,
    ``point: int32[5]`` from :func:`make_point`.  Returns f32."""
    x_frac = point[P_XFRAC]
    qmin = point[P_XQMIN].astype(jnp.float32)
    qmax = point[P_XQMAX].astype(jnp.float32)
    w_frac = point[P_WFRAC]

    xq = jnp.clip(
        jnp.round(x.astype(jnp.float32) * pow2(x_frac)), qmin, qmax
    ).astype(jnp.int32)
    wq = jnp.round(w.astype(jnp.float32) * pow2(w_frac)).astype(jnp.int32)

    acc = jax.lax.dot_general(
        xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    h = acc.astype(jnp.float32) * pow2(-(x_frac + w_frac))
    return af_epilogue(h, af_mode, af_depth, af_fmt, compute_round)
