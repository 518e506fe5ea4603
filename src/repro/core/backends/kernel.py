"""kernel backend: the Pallas CORDIC kernels (same math as carmen).

Prepared path: weights are signed-digit-rounded once (the PE weight memory
bank) and the execution point's dot parameters — CORDIC depth, activation and
weight quantization formats — ride in a small *traced* int32 ``point`` vector
on the :class:`PreparedWeight` (``make_point``).  The fused dot+AF kernel
(``kernels/cordic_fused``) consumes that vector as a scalar-prefetch operand,
so one compiled program serves every :class:`~repro.runtime.bank.ExecutionPoint`
and a ModeController switch swaps arrays, never programs.  When the Pallas
kernel is unavailable (mesh-sharded params, CPU under ``fused="auto"``,
oversized contraction dim) the bitwise-identical pure-XLA chain
(``cordic_fused.ref``) runs instead — the parity tests gate on exact equality.

The per-call path (raw float weights, static formats from the policy) still
runs the standalone ``cordic_mac`` kernel, as does the legacy prepared layout
that carried static formats in ``meta``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import cordic
from ..fxp import FxPFormat
from .base import Backend, PreparedWeight, unit_fmt

__all__ = ["KernelBackend"]


def _use_fused(ctx, k: int) -> bool:
    """Pallas kernel vs XLA fallback for the fused chain (values identical)."""
    from repro.kernels.cordic_fused.ops import _interpret_default, fuse_supported
    from repro.sharding.partition import current_mesh_axes

    fused = getattr(ctx, "fused", "auto")
    if fused == "off" or not fuse_supported(k) or current_mesh_axes():
        return False
    if fused == "on":
        return True
    return not _interpret_default()  # auto: native TPU only


class KernelBackend(Backend):
    name = "kernel"

    def prepare(self, w, lp, *, stacked_axes: int = 0, in_axes=None):
        from repro.kernels.cordic_fused import POINT_LEN, make_point

        fmt = unit_fmt(lp.fmt)
        data = cordic.signed_digit_round(w, int(lp.depth), fmt)
        point = make_point(int(lp.depth), lp.fmt, fmt)
        if stacked_axes:
            # stacked layer banks are consumed as lax.scan xs: give each
            # layer slice its own copy of the params vector
            point = jnp.broadcast_to(
                point, w.shape[:stacked_axes] + (POINT_LEN,)
            )
        # meta stays empty so every execution point shares one treedef
        return PreparedWeight(data, None, self.name, (), point)

    def _fused(self, ctx, x, w, af_mode: str, name: str):
        from repro.kernels.cordic_fused import fused_dot_af, fused_dot_af_ref

        lp_af = ctx.layer_precision("af")
        fused = _use_fused(ctx, x.shape[-1])
        fn = fused_dot_af if fused else fused_dot_af_ref
        # Barriers at both ends: the kernel is a fusion boundary, so the XLA
        # chain must be one too. Fused into its neighbours it would change
        # their code — a skipped bf16 round (XLA's excess precision), another
        # accumulation order in the producing einsum — and the two paths
        # would no longer give the same bits.
        with jax.named_scope("dot.kernel" if fused else "dot.kernel.xla_chain"):
            out = fn(
                jax.lax.optimization_barrier(x), w.data, w.point,
                af_mode=af_mode,
                af_depth=int(lp_af.depth),
                af_fmt=lp_af.fmt,
                compute_round=ctx.compute_dtype != jnp.float32,
            )
            return jax.lax.optimization_barrier(out.astype(ctx.compute_dtype))

    def dot(self, ctx, x, w, *, name: str = ""):
        if isinstance(w, PreparedWeight) and w.point is not None:
            return self._fused(ctx, x, w, "identity", name)
        with jax.named_scope("dot.kernel"):
            return self._mac(ctx, x, w, name)

    def _mac(self, ctx, x, w, name: str):
        """The standalone ``cordic_mac`` kernel: raw float weights, or the
        legacy prepared layout."""
        from repro.kernels.cordic_mac import ops as mac_ops

        x2 = x.reshape(-1, x.shape[-1])
        if isinstance(w, PreparedWeight):
            # legacy prepared leaf: static formats in meta
            bits, frac = w.get("fmt")
            x_fmt = w.get("x_fmt")
            x_fmt = (
                FxPFormat(*x_fmt) if x_fmt else ctx.layer_precision(name).fmt
            )
            out = mac_ops.cordic_mac(
                x2, w.data, depth=w.get("depth"), x_fmt=x_fmt,
                w_fmt=FxPFormat(bits, frac), w_prequantized=True,
            )
        else:
            lp = ctx.layer_precision(name)
            out = mac_ops.cordic_mac(
                x2, w, depth=int(lp.depth), x_fmt=lp.fmt, w_fmt=unit_fmt(lp.fmt)
            )
        return out.reshape(x.shape[:-1] + (w.shape[-1],)).astype(ctx.compute_dtype)

    def dot_af(self, ctx, x, w, *, af: str, name: str = ""):
        """Fused dot + activation epilogue; NotImplemented -> caller unfuses."""
        from repro.kernels.cordic_fused import FUSED_AFS

        if not (
            isinstance(w, PreparedWeight)
            and w.point is not None
            and af in FUSED_AFS
        ):
            return NotImplemented
        return self._fused(ctx, x, w, af, name)
