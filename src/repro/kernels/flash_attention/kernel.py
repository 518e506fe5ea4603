"""Pallas TPU kernel: blocked flash attention (online softmax, VMEM-resident).

Beyond-paper optimization (EXPERIMENTS.md §Perf): the dry-run baselines show
materialized f32 attention scores dominating the memory roofline term for
every *_32k cell. This kernel never writes scores to HBM — the classic
flash-attention restructuring, tiled for the TPU memory hierarchy:

  grid = (BH, nq, nk), k innermost; the (bq, bk) score tile, the online
  softmax statistics m/l and the (bq, D) output accumulator live in VMEM
  scratch across the k sweep; HBM traffic is exactly q + k + v + out.

VMEM at defaults (bq = bk = 512, D = 128, f32 compute):
  q/k/v tiles ~3 x 256 KiB, scores 1 MiB, acc 256 KiB, stats 4 KiB
  ~= 2.1 MiB << 16 MiB (room for double buffering).

GQA is handled by the index maps (kv head = q head // group); causal masking
by position arithmetic inside the tile (blocks entirely above the diagonal
contribute zero and are masked, not skipped — grid shapes stay static).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  n_k: int, bq: int, bk: int, causal: bool, scale: float):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)  # (bq, D)
    k = k_ref[0].astype(jnp.float32)  # (bk, D)
    v = v_ref[0].astype(jnp.float32)  # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (bq, bk)
    if causal:
        qi = pl.program_id(1)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kk == n_k - 1)
    def _finalize():
        # rows with no valid keys (fully masked) have l == 0 -> emit 0
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention_bhsd(
    q, k, v, *, causal: bool = True, bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
    interpret: bool = False,
):
    """q: (BH, Sq, D); k, v: (BH, Sk, D) — pre-broadcast over GQA groups.

    Returns (BH, Sq, D) in q.dtype.
    """
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    n_k = sk // bk
    grid = (bh, sq // bq, n_k)
    scale = 1.0 / math.sqrt(d)

    return pl.pallas_call(
        functools.partial(
            _flash_kernel, n_k=n_k, bq=bq, bk=bk, causal=causal, scale=scale
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
