"""jit'd wrappers around the fused dot+AF kernel.

``fused_dot_af`` is the Pallas path (interpret on CPU, native on TPU);
``fused_dot_af_ref`` is the bitwise-identical pure-XLA chain used as the
mesh/oversize fallback and as the parity oracle in tests.

The per-point parameters arrive as a traced int32 vector (scalar-prefetch
operand on TPU), so swapping execution points never retraces or recompiles —
the zero-cost half of the ModeController switch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fxp import FXP8, FxPFormat

from . import kernel as _k
from . import ref as _ref

DEFAULT_BM = 128
DEFAULT_BN = 128
# full-K tiles: keep x(bm,K) + w(K,bn) + out under a few MiB of VMEM
FUSE_MAX_K = 4096


@functools.lru_cache(maxsize=1)
def _interpret_default() -> bool:
    # cached: jax.default_backend() walks the backend registry on every call,
    # and this probe sits on the per-layer hot path
    return jax.default_backend() == "cpu"


def fuse_supported(k: int) -> bool:
    """Whether the contraction dim fits the kernel's full-K VMEM tiles."""
    return k <= FUSE_MAX_K


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def _pad_to(x, rows: int, cols: int):
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if pr or pc:
        x = jnp.pad(x, ((0, pr), (0, pc)))
    return x


def _grid_call(kernel_fn, grid, bm, kp, bn, out_shape, interpret):
    """The pallas_call, with the params vector as a scalar-prefetch operand."""
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=grid,
        in_specs=[
            pl.BlockSpec((bm, kp), lambda i, j, *_: (i, 0)),
            pl.BlockSpec((kp, bn), lambda i, j, *_: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
    )
    return pl.pallas_call(
        kernel_fn, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "af_mode", "af_depth", "af_fmt", "compute_round", "interpret",
        "bm", "bn",
    ),
)
def fused_dot_af(
    x,
    w,
    point,
    *,
    af_mode: str = "identity",
    af_depth: int = 8,
    af_fmt: FxPFormat = FXP8,
    compute_round: bool = False,
    interpret: bool | None = None,
    bm: int | None = None,
    bn: int | None = None,
):
    """Fused prepared dot + activation: float (..., K) x (K, N) -> f32 (..., N).

    ``w`` carries signed-digit grid values (a prepared weight bank); ``point``
    is the int32[5] vector from :func:`make_point` carrying the execution
    point's dot depth and quantization formats.  ``af_mode`` selects the
    epilogue branch; its index is appended to the params vector so the
    compiled kernel itself is mode-agnostic.
    """
    interpret = _interpret_default() if interpret is None else interpret
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)

    params = jnp.concatenate(
        [jnp.asarray(point, jnp.int32).reshape(_k.POINT_LEN),
         jnp.asarray([_k.FUSED_AFS.index(af_mode)], jnp.int32)]
    )

    bm = bm or min(DEFAULT_BM, _round_up(m, 8))
    bn = bn or min(DEFAULT_BN, _round_up(n, 128))
    kp = _round_up(k, 128)
    mp, np_ = _round_up(m, bm), _round_up(n, bn)

    x2 = _pad_to(x2.astype(jnp.float32), mp, kp)
    wp = _pad_to(jnp.asarray(w, jnp.float32), kp, np_)

    call = _grid_call(
        functools.partial(
            _k.fused_kernel, af_depth=af_depth, af_fmt=af_fmt,
            compute_round=compute_round,
        ),
        grid=(mp // bm, np_ // bn),
        bm=bm, kp=kp, bn=bn,
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )
    out = call(params, x2, wp)
    return out[:m, :n].reshape(lead + (n,))


fused_dot_af_ref = jax.jit(
    _ref.fused_dot_af_ref,
    static_argnames=("af_mode", "af_depth", "af_fmt", "compute_round"),
)
