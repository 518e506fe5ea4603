"""Exact backend: FP32/bf16 matmul — the paper's FP32 baseline."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import Backend, PreparedWeight

__all__ = ["ExactBackend"]


class ExactBackend(Backend):
    name = "exact"

    @jax.named_scope("dot.exact")
    def dot(self, ctx, x, w, *, name: str = ""):
        if isinstance(w, PreparedWeight):
            w = w.data
        out_dt = ctx.compute_dtype if ctx.tp_reduce_bf16 else jnp.float32
        return jnp.dot(
            x.astype(ctx.compute_dtype),
            w.astype(ctx.compute_dtype),
            preferred_element_type=out_dt,
        ).astype(ctx.compute_dtype)
