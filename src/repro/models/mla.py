"""Multi-head Latent Attention (DeepSeek-V3, arXiv:2412.19437 §2.1).

Queries go through a low-rank down/up projection (q_lora_rank), keys/values
through a compressed latent c_kv (kv_lora_rank) plus a decoupled RoPE key of
qk_rope_head_dim shared across heads. The decode cache stores only
(c_kv, k_rope) — (512 + 64) per token instead of 2*128*128 — which is the
technique's entire point and what our cache specs reflect.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import EngineContext
from repro.core.normalization import rmsnorm

from .blocks import Q_CHUNK, cache_row_write, rope
from .params import ParamSpec


def mla_specs(cfg: ModelConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": ParamSpec((d, m.q_lora_rank), ("embed", "q_lora")),
        "q_a_norm": ParamSpec((m.q_lora_rank,), ("q_lora",), "ones"),
        "wq_b": ParamSpec((m.q_lora_rank, h, qk_head), ("q_lora", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "kv_lora")),
        "kv_a_norm": ParamSpec((m.kv_lora_rank,), ("kv_lora",), "ones"),
        "wk_b": ParamSpec((m.kv_lora_rank, h, m.qk_nope_head_dim), ("kv_lora", "heads", "head_dim")),
        "wv_b": ParamSpec((m.kv_lora_rank, h, m.v_head_dim), ("kv_lora", "heads", "head_dim")),
        "wo": ParamSpec((h, m.v_head_dim, d), ("heads", "head_dim", "embed")),
    }


def _q_proj(p, x, cfg, ctx, name):
    m = cfg.mla
    h = cfg.num_heads
    q_lat = ctx.linear(x, p["wq_a"], name=f"{name}.q_a")
    q_lat = rmsnorm(q_lat, p["q_a_norm"])
    wq_b = p["wq_b"].reshape(m.q_lora_rank, -1)
    q = ctx.linear(q_lat, wq_b, name=f"{name}.q_b")
    return q.reshape(x.shape[:-1] + (h, m.qk_nope_head_dim + m.qk_rope_head_dim))


def _kv_latent(p, x, cfg, ctx, name):
    m = cfg.mla
    kv_a = ctx.linear(x, p["wkv_a"], name=f"{name}.kv_a")
    c_kv, k_rope = kv_a[..., : m.kv_lora_rank], kv_a[..., m.kv_lora_rank :]
    c_kv = rmsnorm(c_kv, p["kv_a_norm"])
    return c_kv, k_rope


def mla_attention(p, x, cfg: ModelConfig, ctx: EngineContext, *, positions, name, cache=None):
    """Returns (out, new_cache); cache = {c_kv, k_rope, index}."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    nope, rdim, vdim = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q = _q_proj(p, x, cfg, ctx, name)  # (B,S,H,nope+rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)

    c_kv, k_rope = _kv_latent(p, x, cfg, ctx, name)  # (B,S,R), (B,S,rdim)
    k_rope = rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]

    if cache is not None:
        idx = cache["index"]  # (B,)
        c_kv = cache_row_write(cache["c_kv"], c_kv, idx)
        k_rope = cache_row_write(cache["k_rope"], k_rope, idx)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope, "index": idx + s}
        t = c_kv.shape[1]
        k_positions = jnp.arange(t)
        # per-query causal validity (s > 1 = batched prefill; see blocks.py)
        valid = k_positions[None, None, :] <= positions[:, :, None]  # (B, S, T)
    else:
        new_cache = None
        t = s
        k_positions = positions
        valid = None

    # absorbed-matmul form: score = q_nope^T (W_kb c_kv) + q_rope^T k_rope.
    # q_nope is mapped into latent space once (q_lat = q_nope @ W_kb^T), so the
    # per-token cache stays compressed — scores contract over kv_lora_rank.
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32), p["wk_b"].astype(jnp.float32))
    scale = 1.0 / math.sqrt(nope + rdim)
    c_kv_f = c_kv.astype(jnp.float32)
    k_rope_f = k_rope.astype(jnp.float32)

    def _block(q_lat_i, q_rope_i, qpos_i):
        """One query chunk: (B, Qc, H, R/rdim) -> latent-space output (B,Qc,H,R)."""
        scores = jnp.einsum("bqhr,btr->bhqt", q_lat_i, c_kv_f)
        scores = scores + jnp.einsum("bqhr,btr->bhqt", q_rope_i.astype(jnp.float32), k_rope_f)
        scores = scores * scale
        if valid is not None:
            scores = jnp.where(valid[:, None], scores, -1e30)
        else:
            mask = qpos_i[:, None] >= k_positions[None, :]
            scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqt,btr->bqhr", probs, c_kv_f)

    if cache is None and ctx.attn_impl == "flash":
        # flash for MLA via the concat trick: [q_lat, q_rope] . [c_kv, k_rope]
        # equals the two-term score exactly, and the "value" is c_kv — MLA is
        # MQA-shaped in latent space, so the shared online-softmax path
        # (KV-chunked, tile-resident scores) applies unchanged.
        from .blocks import _sdpa_flash_xla

        scale_full = 1.0  # _sdpa_flash_xla scales by 1/sqrt(hd of q) below
        q_cat = jnp.concatenate([q_lat, q_rope.astype(jnp.float32)], axis=-1)
        # undo the helper's 1/sqrt(dim(q_cat)) and apply MLA's own scale
        q_cat = q_cat * (math.sqrt(q_cat.shape[-1]) * scale)
        k_cat = jnp.concatenate([c_kv_f, k_rope_f], axis=-1)[:, :, None, :]  # (B,T,1,R+r)
        kr = jnp.repeat(k_cat, h, axis=2)
        vr = jnp.repeat(c_kv_f[:, :, None, :], h, axis=2)
        o_lat = _sdpa_flash_xla(q_cat, kr, vr, positions, k_positions, causal=True)
    elif cache is not None and ctx.attn_impl == "decode_kernel":
        from repro.sharding.partition import current_mesh_axes

        if current_mesh_axes():
            o_lat = _block(q_lat, q_rope, positions)  # mesh: XLA chain
        else:
            # Pallas cache-decode kernel (absorbed/MQA-shaped in latent
            # space): both score terms, mask, softmax and the latent
            # contraction happen in one VMEM-resident pass per (batch, head)
            from repro.kernels.decode_attention import mla_decode_attention

            o_lat = mla_decode_attention(
                q_lat, q_rope.astype(jnp.float32), c_kv, k_rope, positions,
                scale=scale,
            )
    elif cache is None and s > Q_CHUNK and s % Q_CHUNK == 0:
        nc = s // Q_CHUNK
        ql = jnp.moveaxis(q_lat.reshape(b, nc, Q_CHUNK, h, -1), 1, 0)
        qr = jnp.moveaxis(q_rope.reshape(b, nc, Q_CHUNK, h, rdim), 1, 0)
        qp = positions.reshape(nc, Q_CHUNK)
        _, o_lat = jax.lax.scan(lambda _, args: (None, _block(*args)), None, (ql, qr, qp))
        o_lat = jnp.moveaxis(o_lat, 0, 1).reshape(b, s, h, -1)
    else:
        o_lat = _block(q_lat, q_rope, positions)

    # up-project latent output with W_vb (absorbed form)
    out = jnp.einsum("bshr,rhv->bshv", o_lat, p["wv_b"].astype(jnp.float32)).astype(x.dtype)

    wo = p["wo"].reshape(h * vdim, cfg.d_model)
    return ctx.linear(out.reshape(b, s, h * vdim), wo, name=f"{name}.o"), new_cache


def mla_cache_specs(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_head_dim), dtype),
        "index": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }
