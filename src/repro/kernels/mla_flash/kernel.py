"""Pallas TPU kernel: flash attention for Multi-head Latent Attention.

Closes EXPERIMENTS.md §Perf B8: deepseek's remaining memory term is ~6 TB/dev
of materialized f32 MLA score tiles. MLA's structure — every head attends over
the SAME compressed latent (c_kv, k_rope) — means a flash kernel can broadcast
one K/V tile across a block of heads inside VMEM. The pure-JAX twin cannot
express this without materializing the H-repeated K (refuted iteration B6);
this kernel can, because the broadcast is just a BlockSpec index_map that
ignores the head-block grid axis.

Score identity (models/mla.py): s[h, q, t] = q_cat[q, h, :] . k_cat[t, :]
with q_cat = [q_lat, q_rope] (Dk = kv_lora_rank + rope_dim) and
k_cat = [c_kv, k_rope]; the "value" is c_kv alone (Dv = kv_lora_rank).

Grid (B, H/bh, nq, nk), k innermost. VMEM at bh=8, bq=128, bk=512,
Dk=576, Dv=512 (deepseek-v3):
  q tile 128*8*576*4 = 2.4 MB | k tile 512*576*4 = 1.2 MB (shared by 8 heads)
  v tile 512*512*4 = 1 MB | scores 8*128*512*4 = 2 MB | acc 8*128*512*4 = 2 MB
  ~= 8.6 MB << 16 MiB. One k fetch serves bh heads — the H-broadcast the
  XLA twin cannot express.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _mla_flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      n_k: int, bq: int, bk: int, causal: bool, scale: float):
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, bh, Dk)
    k = k_ref[0].astype(jnp.float32)  # (bk, Dk)  — shared across the bh heads
    v = v_ref[0].astype(jnp.float32)  # (bk, Dv)

    # scores (bh, bq, bk): one shared-latent K tile serves every head
    s = jax.lax.dot_general(
        q, k, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (bq, bh, bk)
    s = s * scale
    if causal:
        qi = pl.program_id(2)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1, bk), 0)
        k_pos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, 1, bk), 2)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, bh, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    # acc (bq, bh, Dv) += p (bq, bh, bk) @ v (bk, Dv)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((2,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(kk == n_k - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "bh", "interpret"))
def mla_flash(q_cat, k_cat, v, *, causal: bool = True, bq: int = 128, bk: int = 512,
              bh: int = 8, interpret: bool = False):
    """q_cat: (B, Sq, H, Dk); k_cat: (B, Sk, Dk); v: (B, Sk, Dv).

    Returns (B, Sq, H, Dv) in q_cat.dtype. Scaling uses 1/sqrt(Dk) — pre-scale
    q_cat if the model uses a different score scale.
    """
    b, sq, h, dk = q_cat.shape
    _, sk, dv = v.shape
    bq = min(bq, sq)
    bk = min(bk, sk)
    bh = min(bh, h)
    assert sq % bq == 0 and sk % bk == 0 and h % bh == 0, (sq, sk, h, bq, bk, bh)
    n_k = sk // bk
    grid = (b, h // bh, sq // bq, n_k)
    scale = 1.0 / math.sqrt(dk)

    return pl.pallas_call(
        functools.partial(
            _mla_flash_kernel, n_k=n_k, bq=bq, bk=bk, causal=causal, scale=scale
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, bh, dk), lambda bb, hh, qq, kk: (bb, qq, hh, 0)),
            # the K/V index maps ignore hh: one latent tile broadcast to bh heads
            pl.BlockSpec((1, bk, dk), lambda bb, hh, qq, kk: (bb, kk, 0)),
            pl.BlockSpec((1, bk, dv), lambda bb, hh, qq, kk: (bb, kk, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, bh, dv), lambda bb, hh, qq, kk: (bb, qq, hh, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, dv), q_cat.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, bh, dv), jnp.float32),
            pltpu.VMEM((bq, bh, 1), jnp.float32),
            pltpu.VMEM((bq, bh, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q_cat, k_cat, v)
