"""Pallas TPU kernel: per-query-causal decode attention over the slot KV cache.

The serving decode path (burst S in {1..burst}, speculative verify) attends a
short query block against the whole cache with a *per-query* validity mask
(``k_pos <= q_pos``) instead of the training-time triangular mask.  The XLA
chain materializes GQA-repeated keys/values ((B, T, KV, hd) -> (B, T, H, hd))
and an (B, H, S, T) score tensor in HBM; this kernel keeps both inside VMEM:

  grid = (B, H); each program reads its query head's slice, the *shared* kv
  head's cache slice (GQA resolved by the index map — no ``jnp.repeat``
  materialization), computes the (S, T) score tile, masks, softmaxes and
  contracts against V without leaving VMEM.  Every block spans whole
  trailing dims or whole 128-lane head slices, as the TPU's (8, 128) tiling
  requires; positions ride as a (B, S, 1) column so each query row's
  position broadcasts across its score row.

Numerics deliberately mirror ``models/blocks.attention`` (GQA) and
``models/mla.mla_attention._block`` (MLA) op-for-op — same mask application
order, same dtypes at each step — so the kernel is exchangeable with the XLA
cache path: greedy token streams are identical, and raw outputs agree to
reduction-order tolerance (XLA does not pin f32 reduction order across
differently shaped programs, so the per-(b,h) tiles here vs the whole-batch
einsum can differ by a couple of ulps depending on how the backend threads
the contraction).  Softmax is the plain (not online) form: decode tiles are
small (S <= burst, T = cache length), and the online-softmax rescaling would
drift further from the reference chain.

Sibling kernels: ``flash_attention`` / ``mla_flash`` cover the long-sequence
prefill/training shapes with online softmax; this one covers the cache-decode
shape they cannot express (per-row positions, per-query masks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _gqa_decode_kernel(q_ref, k_ref, v_ref, pos_ref, o_ref, *, scale: float):
    q = q_ref[0].astype(jnp.float32)  # (S, hd)
    k = k_ref[0].astype(jnp.float32)  # (T, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (S, T)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = t_idx <= pos_ref[0]
    s = jnp.where(valid, s * scale, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    v = v_ref[0]  # (T, hd) cache dtype
    o = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
    o_ref[0] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "scale", "interpret"))
def gqa_decode(q, k, v, positions, *, groups: int, scale: float,
               interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, T, KV, hd) slot caches with H = KV*groups;
    positions: (B, S) int32 absolute query positions.  Returns (B, S, H, hd)
    in the cache dtype (matching the XLA chain's einsum output).

    Heads are folded into the lane axis (free reshapes of the trailing
    dims), so each program's block is one head's ``hd`` lanes over every
    query row or cache row. On a TPU that block must be lane-aligned:
    ``hd % 128 == 0``."""
    b, s, h, hd = q.shape
    _, t, kv, _ = k.shape
    assert h == kv * groups, (q.shape, k.shape, groups)
    out = pl.pallas_call(
        functools.partial(_gqa_decode_kernel, scale=scale),
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, s, hd), lambda bi, hi: (bi, 0, hi)),
            pl.BlockSpec((1, t, hd), lambda bi, hi: (bi, 0, hi // groups)),
            pl.BlockSpec((1, t, hd), lambda bi, hi: (bi, 0, hi // groups)),
            pl.BlockSpec((1, s, 1), lambda bi, hi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, s, hd), lambda bi, hi: (bi, 0, hi)),
        out_shape=jax.ShapeDtypeStruct((b, s, h * hd), v.dtype),
        interpret=interpret,
    )(q.reshape(b, s, h * hd), k.reshape(b, t, kv * hd),
      v.reshape(b, t, kv * hd), positions[..., None])
    return out.reshape(b, s, h, hd)


def _mla_decode_kernel(ql_ref, qr_ref, ckv_ref, kr_ref, pos_ref, o_ref, *,
                       scale: float):
    ql = ql_ref[0, 0].astype(jnp.float32)         # (S, R)
    qr = qr_ref[0, 0].astype(jnp.float32)         # (S, r)
    ckv = ckv_ref[0].astype(jnp.float32)          # (T, R)
    kr = kr_ref[0].astype(jnp.float32)            # (T, r)
    s = jax.lax.dot_general(
        ql, ckv, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s + jax.lax.dot_general(
        qr, kr, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s * scale
    t_idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(t_idx <= pos_ref[0], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_ref[0, 0] = jax.lax.dot_general(
        p, ckv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_decode(q_lat, q_rope, c_kv, k_rope, positions, *, scale: float,
               interpret: bool = False):
    """Absorbed-form MLA decode: q_lat (B, S, H, R), q_rope (B, S, H, r),
    c_kv (B, T, R), k_rope (B, T, r), positions (B, S).  Returns the latent
    output (B, S, H, R) f32 — MLA is MQA-shaped in latent space, so every
    head reads the same cache slice.

    The small query block is moved head-major, (B, H, S, ·), so every block
    spans its full trailing two dims (the TPU tiling rule holds at any R, r);
    the caches are read whole."""
    b, s, h, r = q_lat.shape
    _, t, _ = c_kv.shape
    rd = q_rope.shape[-1]
    out = pl.pallas_call(
        functools.partial(_mla_decode_kernel, scale=scale),
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, s, r), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, rd), lambda bi, hi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, t, r), lambda bi, hi: (bi, 0, 0)),
            pl.BlockSpec((1, t, rd), lambda bi, hi: (bi, 0, 0)),
            pl.BlockSpec((1, s, 1), lambda bi, hi: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, s, r), lambda bi, hi: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, r), jnp.float32),
        interpret=interpret,
    )(jnp.swapaxes(q_lat, 1, 2), jnp.swapaxes(q_rope, 1, 2), c_kv, k_rope,
      positions[..., None])
    return jnp.swapaxes(out, 1, 2)
