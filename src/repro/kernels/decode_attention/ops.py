"""jit'd wrappers for the decode-attention kernels (model layout in/out)."""
from __future__ import annotations

import functools

import jax

from . import kernel as _k


@functools.lru_cache(maxsize=1)
def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def gqa_decode_attention(q, ck, cv, positions, *, scale: float,
                         interpret: bool | None = None):
    """Cache-decode GQA attention: q (B, S, H, hd) against slot caches
    ck/cv (B, T, KV, hd) with per-query positions (B, S). Compiled for a TPU
    it needs lane-aligned heads, ``hd % 128 == 0``."""
    interpret = _interpret_default() if interpret is None else interpret
    if not interpret and q.shape[-1] % 128:
        raise ValueError(
            f"the compiled GQA decode kernel needs head_dim % 128 == 0, got "
            f"{q.shape[-1]}; serve this model with attn_impl='xla'"
        )
    groups = q.shape[2] // ck.shape[2]
    return _k.gqa_decode(q, ck, cv, positions, groups=groups, scale=scale,
                         interpret=interpret)


def mla_decode_attention(q_lat, q_rope, c_kv, k_rope, positions, *,
                         scale: float, interpret: bool | None = None):
    """Cache-decode absorbed-MLA attention; returns latent output f32."""
    interpret = _interpret_default() if interpret is None else interpret
    return _k.mla_decode(q_lat, q_rope, c_kv, k_rope, positions, scale=scale,
                         interpret=interpret)
