"""Shared transformer building blocks: RoPE, norms, GQA attention, MLP, MoE.

All matmuls route through ``EngineContext`` (the CARMEN vector engine) and all
activation functions through the multi-AF block mapping, so the paper's
technique is a first-class execution mode for every architecture.

Attention is computed in query chunks (flash-style, pure JAX ``lax.scan``) so
that 32k-sequence cells never materialize an (S, S) score tensor — scores per
step stay (B, H, Qc, S).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import EngineContext
from repro.core.normalization import layernorm, nonparametric_ln, rmsnorm
from repro.configs.base import ModelConfig
from repro.sharding.partition import constrain

from .params import ParamSpec

Q_CHUNK = 1024  # flash-style query block


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------


def norm_spec(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    if cfg.norm_type == "nonparametric":
        return {}
    if cfg.norm_type == "layernorm":
        return {
            "scale": ParamSpec((d,), ("embed",), "ones"),
            "bias": ParamSpec((d,), ("embed",), "zeros"),
        }
    return {"scale": ParamSpec((d,), ("embed",), "ones")}


def apply_norm(p, x, cfg: ModelConfig):
    if cfg.norm_type == "nonparametric":
        return nonparametric_ln(x)
    if cfg.norm_type == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"], cfg.norm_eps)


def apply_af(x, mode: str, ctx: EngineContext):
    """Activation through the CARMEN multi-AF block (or the exact ref)."""
    return ctx.activate(x, mode)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: (..., S, H, D) with positions (..., S). Rotates pairs (D/2)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (..., S, 1, half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, chunked-causal; decode path with KV cache)
# ---------------------------------------------------------------------------


def attention_specs(cfg: ModelConfig, d_in: Optional[int] = None):
    """Projections of one attention block; ``d_in`` is the width of its
    input where that is not ``d_model`` (its output is ``d_model`` wide)."""
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    di = d_in or d
    specs = {
        "wq": ParamSpec((di, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((di, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((di, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        specs["bq"] = ParamSpec((h, hd), ("heads", "head_dim"), "zeros")
        specs["bk"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), "zeros")
        specs["bv"] = ParamSpec((kv, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        specs["q_norm"] = ParamSpec((hd,), ("head_dim",), "ones")
        specs["k_norm"] = ParamSpec((hd,), ("head_dim",), "ones")
    return specs


def _proj(ctx, x, w, b, name):
    """(B,S,D) x (D,H,hd) -> (B,S,H,hd) through the engine (2D matmul form)."""
    d = w.shape[0]
    out = ctx.linear(x, w.reshape(d, -1), b.reshape(-1) if b is not None else None, name=name)
    return out.reshape(x.shape[:-1] + w.shape[1:])


def attn_scale(cfg: ModelConfig) -> float:
    """Softmax scale of the scores: the config's, else ``head_dim ** -0.5``."""
    if cfg.attn_scale is not None:
        return cfg.attn_scale
    return 1.0 / math.sqrt(cfg.head_dim)


def _sdpa_chunked(q, k, v, q_positions, k_positions, causal: bool,
                  scale: Optional[float] = None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,H,hd) (KV pre-repeated to H so the head dim
    shards over the model axis for EVERY kv_heads count — the 5-D (KV,G)
    layout forced head replication whenever kv_heads %% TP != 0, §Perf A)."""
    b, sq, h, hd = q.shape
    scale = scale or 1.0 / math.sqrt(hd)
    n_chunks = max(1, sq // Q_CHUNK) if sq % Q_CHUNK == 0 else 1
    qc = q.reshape(b, n_chunks, sq // n_chunks, h, hd)
    qp = q_positions.reshape(n_chunks, sq // n_chunks)

    def chunk_fn(_, qq):
        q_i, qp_i = qq  # (B, Qc, H, hd), (Qc,)
        scores = jnp.einsum("bqhd,bshd->bhqs", q_i.astype(jnp.float32), k.astype(jnp.float32))
        scores = scores * scale
        if causal:
            mask = qp_i[:, None] >= k_positions[None, :]  # (Qc, Sk)
            scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v)
        return None, out

    _, outs = jax.lax.scan(chunk_fn, None, (jnp.moveaxis(qc, 1, 0), qp))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd)


def _sdpa_flash_xla(q, k, v, q_positions, k_positions, causal: bool,
                    q_chunk: int = 512, k_chunk: int = 512,
                    scale: Optional[float] = None):
    """KV-chunked online-softmax attention (pure-JAX flash twin).

    q, k, v: (B,S,H,hd) (KV pre-repeated to H — see _sdpa_chunked). Never
    materializes more than a (Qc, Kc) score tile per (q-chunk, k-chunk) pair —
    the HBM-traffic shape the Pallas kernel (kernels/flash_attention) realizes
    on TPU. Tested equal to both the naive reference and the kernel.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    hd_v = v.shape[-1]  # may differ from hd (MLA: scores over R+r, values R)
    scale = scale or 1.0 / math.sqrt(hd)
    qc = q_chunk if sq % q_chunk == 0 else sq
    kc = k_chunk if sk % k_chunk == 0 else sk
    nq, nk = sq // qc, sk // kc
    q_r = jnp.moveaxis(q.reshape(b, nq, qc, h, hd), 1, 0)
    qp_r = q_positions.reshape(nq, qc)
    k_r = jnp.moveaxis(k.reshape(b, nk, kc, h, hd), 1, 0)
    v_r = jnp.moveaxis(v.reshape(b, nk, kc, h, hd_v), 1, 0)
    kp_r = k_positions.reshape(nk, kc)

    def q_step(_, qq):
        q_i, qp_i = qq  # (B,Qc,H,hd), (Qc,)
        q_f = q_i.astype(jnp.float32)

        def k_step(carry, kk):
            m, l, acc = carry
            k_j, v_j, kp_j = kk
            s = jnp.einsum("bqhd,bshd->bhqs", q_f, k_j.astype(jnp.float32)) * scale
            if causal:
                mask = qp_i[:, None] >= kp_j[None, :]
                s = jnp.where(mask[None, None], s, -1e30)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m, m_cur)
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhqs,bshd->bhqd", p, v_j.astype(jnp.float32)
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((b, h, qc), jnp.float32)
        a0 = jnp.zeros((b, h, qc, hd_v), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0), (k_r, v_r, kp_r))
        out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        return None, jnp.moveaxis(out, 2, 1).astype(v.dtype)  # (B,Qc,H,hd)

    _, outs = jax.lax.scan(q_step, None, (q_r, qp_r))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, hd_v)


def cache_row_write(c, x, i):
    """Write block ``x`` (B, S, ...) into rows [i, i+S) of cache ``c``
    (B, Smax, ...), ``i`` (B,) int32 — the decode/prefill KV write.

    Two lowerings with identical values:

    * single device: a vmapped ``dynamic_update_slice`` — O(S) rows touched,
      in-place on the donated cache buffer;
    * under a mesh: a gather + select over the row axis. The vmapped DUS
      lowers to a scatter that XLA's SPMD partitioner cannot lower inside
      the nested burst/layer scans whenever an MoE dispatch shares the
      program (hlo_verifier RET_CHECK on the scatter index broadcast,
      jax 0.4.37) — the gather form is partitioner-friendly on every family.
      Start indices are clamped exactly like DUS clamps them.

    Named scope ``attention.kv_write``.
    """
    from repro.sharding.partition import current_mesh_axes

    s = x.shape[1]
    with jax.named_scope("attention.kv_write"):
        if not current_mesh_axes():
            start = (lambda b_i: (b_i,) + (0,) * (x.ndim - 2))
            upd = jax.vmap(lambda cb, xb, ib: jax.lax.dynamic_update_slice(cb, xb, start(ib)))
            return upd(c, x.astype(c.dtype), i)
        i = jnp.clip(i, 0, c.shape[1] - s)  # DUS start-clamping semantics
        j = jnp.arange(c.shape[1], dtype=jnp.int32)[None, :] - i[:, None]  # (B, Smax)
        valid = (j >= 0) & (j < s)
        idx = jnp.clip(j, 0, s - 1).reshape(j.shape + (1,) * (x.ndim - 2))
        gathered = jnp.take_along_axis(x.astype(c.dtype), idx, axis=1)
        return jnp.where(valid.reshape(idx.shape), gathered, c)


def cache_layer_write(c, x, i, layer):
    """The carried form of :func:`cache_row_write`: write block ``x``
    (B, S, ...) into rows [i, i+S) of layer ``layer`` of the stacked cache
    ``c`` (L, B, Smax, ...), ``i`` (B,) int32, in place.

    One ``dynamic_update_slice`` per slot, each clamping its start index as
    the single-layer write does. A ``vmap`` over the slot axis would lower
    to a scatter for which XLA lays the stack out slot-minor and copies the
    whole cache at the program's entry and exit. Single device only.

    Named scope ``attention.kv_write``.
    """
    x = x.astype(c.dtype)
    tail = (0,) * (x.ndim - 2)
    with jax.named_scope("attention.kv_write"):
        for b in range(x.shape[0]):
            c = jax.lax.dynamic_update_slice(c, x[b][None, None],
                                             (layer, b, i[b]) + tail)
    return c


def attention(p, x, cfg: ModelConfig, ctx: EngineContext, *, positions, name, cache=None,
              causal: bool = True):
    """Returns (out, new_cache). cache = dict(k, v, index) for decode: one
    layer's cache, or, with a ``layer`` entry, the segment's whole stacked
    cache carried through the layer scan in a decode step
    (``transformer._scan_segment``): this layer's rows are written in place
    and its K/V read from the stack.

    Named scopes: ``attention.kv_write`` (:func:`cache_row_write`,
    :func:`cache_layer_write`) and
    ``attention.core``, from the scores to the weighted sum; the projections
    are dots (``dot.<backend>``)."""
    b, s, _ = x.shape
    kvh, g, hd = cfg.num_kv_heads, cfg.kv_groups, cfg.head_dim

    q = _proj(ctx, x, p["wq"], p.get("bq"), f"{name}.q")  # (B,S,H,hd)
    k = _proj(ctx, x, p["wk"], p.get("bk"), f"{name}.k")
    v = _proj(ctx, x, p["wv"], p.get("bv"), f"{name}.v")

    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])

    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    # H-layout with KV repeated over groups: the head dim then shards over the
    # model axis for every kv_heads count (the (KV, G) split replicated
    # attention whenever kv_heads %% TP != 0 — §Perf A). The repeat is a
    # broadcast on TPU, not a copy.
    q = constrain(q, "batch", None, "model", None)

    if cache is None:
        kr = jnp.repeat(k, g, axis=2) if g > 1 else k
        vr = jnp.repeat(v, g, axis=2) if g > 1 else v
        kr = constrain(kr, "batch", None, "model", None)
        vr = constrain(vr, "batch", None, "model", None)
        k_pos = positions
        with jax.named_scope("attention.core"):
            if ctx.attn_impl == "flash":
                out = _sdpa_flash_xla(q, kr, vr, positions, k_pos,
                                      causal=causal, scale=attn_scale(cfg))
            else:
                out = _sdpa_chunked(q, kr, vr, positions, k_pos,
                                    causal=causal, scale=attn_scale(cfg))
        new_cache = None
    else:
        if "layer" in cache:
            layer = cache["layer"]
            idx = jax.lax.dynamic_index_in_dim(cache["index"], layer, keepdims=False)
            ck_all = cache_layer_write(cache["k"], k, idx, layer)
            cv_all = cache_layer_write(cache["v"], v, idx, layer)
            ck = jax.lax.dynamic_index_in_dim(ck_all, layer, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(cv_all, layer, keepdims=False)
            new_cache = dict(cache, k=ck_all, v=cv_all,
                             index=jax.lax.dynamic_update_index_in_dim(
                                 cache["index"], idx + s, layer, 0))
        else:
            idx = cache["index"]  # (B,) int32: per-row next write slot
            ck = cache_row_write(cache["k"], k, idx)
            cv = cache_row_write(cache["v"], v, idx)
            new_cache = {"k": ck, "v": cv, "index": idx + s}
        s_max = ck.shape[1]
        scale = attn_scale(cfg)
        from repro.sharding.partition import current_mesh_axes

        with jax.named_scope("attention.core"):
            if ctx.attn_impl == "decode_kernel" and not current_mesh_axes():
                # Pallas cache-decode kernel: GQA resolved by index maps (no
                # repeated-KV materialization), (S, Smax) score tile stays in
                # VMEM. Mesh-sharded caches keep the XLA chain below.
                from repro.kernels.decode_attention import gqa_decode_attention

                out = gqa_decode_attention(q, ck, cv, positions, scale=scale)
            else:
                k_pos = jnp.arange(s_max)
                # per-query causal validity: query at position p sees keys <= p.
                # With s == 1 this is the classic decode mask; with s > 1 (batched
                # prefill writing a whole prompt at once) it is causal within the
                # new block.
                valid = k_pos[None, None, :] <= positions[:, :, None]  # (B, Sq, Smax)
                ckr = jnp.repeat(ck, g, axis=2) if g > 1 else ck
                cvr = jnp.repeat(cv, g, axis=2) if g > 1 else cv
                scores = jnp.einsum("bqhd,bshd->bhqs", q.astype(jnp.float32), ckr.astype(jnp.float32))
                scores = jnp.where(valid[:, None], scores * scale, -1e30)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(cvr.dtype), cvr)

    out = out.reshape(b, s, cfg.num_heads * hd)
    wo = p["wo"].reshape(cfg.num_heads * hd, cfg.d_model)
    return ctx.linear(out, wo, name=f"{name}.o"), new_cache


def attn_cache_specs(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, max_len, kvh, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, max_len, kvh, hd), dtype),
        "index": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLP (gated / plain) through the multi-AF block
# ---------------------------------------------------------------------------


def mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    specs = {
        "up": ParamSpec((d, f), ("embed", "mlp")),
        "down": ParamSpec((f, d), ("mlp", "embed")),
    }
    if cfg.glu:
        specs["gate"] = ParamSpec((d, f), ("embed", "mlp"))
    return specs


def mlp(p, x, cfg: ModelConfig, ctx: EngineContext, *, name, delta=None):
    """``delta``, a pair ``(gate, up)`` (a per-call adapter's output, gated
    MLP only), adds to the gate and up projections before the activation."""
    # linear_af fuses the dot and the activation epilogue into one Pallas
    # pass on the kernel backend; every other backend unfuses to the same
    # linear -> multi-AF chain as before
    if cfg.glu:
        up = ctx.linear(x, p["up"], name=f"{name}.up")
        if delta is None:
            h = ctx.linear_af(x, p["gate"], af=cfg.act,
                              name=f"{name}.gate") * up
        else:
            gate = ctx.linear(x, p["gate"], name=f"{name}.gate") + delta[0]
            h = ctx.activate(gate, cfg.act) * (up + delta[1])
    else:
        h = ctx.linear_af(x, p["up"], af=cfg.act, name=f"{name}.up")
    return ctx.linear(h, p["down"], name=f"{name}.down")


# ---------------------------------------------------------------------------
# MoE (token-choice top-k, capacity-based, sort/gather dispatch)
# ---------------------------------------------------------------------------


def moe_specs(cfg: ModelConfig):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    specs = {
        "router": ParamSpec((d, e), ("embed", "experts"), scale=0.02),
        "up": ParamSpec((e, d, f), ("experts", "embed", "mlp")),
        "gate": ParamSpec((e, d, f), ("experts", "embed", "mlp")),
        "down": ParamSpec((e, f, d), ("experts", "mlp", "embed")),
    }
    if m.num_shared_experts:
        fs = m.d_ff_shared * m.num_shared_experts
        specs["shared"] = {
            "up": ParamSpec((d, fs), ("embed", "mlp")),
            "gate": ParamSpec((d, fs), ("embed", "mlp")),
            "down": ParamSpec((fs, d), ("mlp", "embed")),
        }
    return specs


def _dispatch_indices(expert_idx, num_experts: int, capacity: int):
    """Per-row sort/gather dispatch plan.

    expert_idx: (S, K) int32 chosen experts for each of S tokens.
    Returns (gather_idx (E, C) into S*K flat choices, valid (E, C) mask,
             rank (S, K) position of each choice in its expert queue).
    """
    s, k = expert_idx.shape
    flat = expert_idx.reshape(-1)  # (S*K,)
    order = jnp.argsort(flat, stable=True)
    sorted_e = flat[order]
    pos = jnp.arange(s * k, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, pos, -1))
    rank_sorted = pos - seg_start  # position within the expert's queue
    rank = jnp.zeros((s * k,), jnp.int32).at[order].set(rank_sorted)
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
    slot = jnp.arange(capacity, dtype=jnp.int32)
    gather_pos = starts[:, None] + slot[None, :]  # (E, C) index into sorted order
    valid = slot[None, :] < jnp.minimum(counts[:, None], capacity)
    gather_idx = order[jnp.clip(gather_pos, 0, s * k - 1)]  # (E, C) -> flat choice id
    return gather_idx, valid, rank.reshape(s, k)


def _combine_scatter(yw, token_of_choice, s: int, d: int):
    """Combine expert-slot outputs into per-token sums.

    Under a mesh, each model shard scatter-adds its LOCAL experts' slots into
    a (B, S, D) partial and psums over the model axis (shard_map) — the
    minimum-communication combine (~1 GB/dev/layer). A plain GSPMD scatter
    here replicated the batch and moved 1.7 TB/dev (§Perf B); shard_map makes
    the partial-sum structure explicit. Backward of psum+local-scatter is a
    broadcast+gather — no K-replicated cotangents.
    """
    b, e, capacity, _ = yw.shape

    def local(yw_l, tok_l):
        bb = yw_l.shape[0]
        out = (
            jnp.zeros((bb, s, d), yw_l.dtype)
            .at[jnp.arange(bb)[:, None], tok_l.reshape(bb, -1)]
            .add(yw_l.reshape(bb, -1, d))
        )
        return jax.lax.psum(out, "model")

    from repro.sharding.partition import BATCH_AXES, current_mesh_axes, mesh_axis_sizes

    axes = current_mesh_axes()
    sizes = mesh_axis_sizes()
    if "model" in axes and e % max(sizes.get("model", 1), 1) == 0:
        from jax.sharding import PartitionSpec as _P

        batch_axes = tuple(a for a in BATCH_AXES if a in axes)
        import numpy as _np

        bext = int(_np.prod([sizes.get(a, 1) for a in batch_axes])) if batch_axes else 1
        bspec = batch_axes if (batch_axes and b % bext == 0) else None
        return jax.shard_map(
            local,
            in_specs=(
                _P(bspec, "model", None, None),
                _P(bspec, "model", None),
            ),
            out_specs=_P(bspec, None, None),
            check_vma=False,
        )(yw, token_of_choice)
    bb = yw.shape[0]
    return (
        jnp.zeros((bb, s, d), yw.dtype)
        .at[jnp.arange(bb)[:, None], token_of_choice.reshape(bb, -1)]
        .add(yw.reshape(bb, -1, d))
    )


def moe_ffn(p, x, cfg: ModelConfig, ctx: EngineContext, *, name,
            dropless: bool = False):
    """Batched-per-row MoE: dispatch stays local to each batch row; the E-axis
    reshard of the (B, E, C, D) buffer is the all-to-all (DESIGN.md §6).

    ``dropless`` (the cached-decode path) widens short blocks' capacity so no
    routed token is ever dropped. Returns (out, aux) where aux carries the
    load-balancing loss terms.
    """
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    capacity = max(k, int(math.ceil(s * k / e * m.capacity_factor)))
    if dropless and s <= 64:
        # short cached-decode blocks (speculative verify, short batched
        # prefills): a token's top-k experts are distinct, so per-expert load
        # is at most s — this capacity is dropless, making S>1 decode match
        # token-by-token decode (whose s=1 capacity never drops either). The
        # multi-token verifier leans on that parity. Training/eval forwards
        # (dropless=False) and long prefills keep capacity-factor economics.
        capacity = max(capacity, s)

    router_logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    router_logits = constrain(router_logits, "batch", None, None)
    probs = constrain(jax.nn.softmax(router_logits, axis=-1), "batch", None, None)
    top_p, top_i = jax.lax.top_k(probs, k)  # (B, S, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    plan_fn = jax.vmap(lambda ti: _dispatch_indices(ti, e, capacity))
    from repro.sharding.partition import current_mesh_axes

    if current_mesh_axes():
        # manual-mode island: the plan is a sort/scan/gather chain over a few
        # hundred int32s, and XLA's SPMD partitioner SILENTLY miscomputes it
        # when the downstream dispatch constraint propagates a sharding onto
        # it (observed: gather_idx off by whole tokens on a 2x2 mesh, jax
        # 0.4.37). Replicated in/out shard_map makes every device compute
        # the full plan with the unpartitioned lowering — bit-identical to
        # single-device by construction, and O(S*K) int work is free.
        from jax.sharding import PartitionSpec as _P

        plan = jax.shard_map(plan_fn, in_specs=_P(), out_specs=_P(),
                             check_vma=False)(top_i)
    else:
        plan = plan_fn(top_i)
    gather_idx, valid, rank = plan  # (B,E,C), (B,E,C), (B,S,K)

    token_of_choice = gather_idx // k  # (B, E, C) -> source token position
    x_disp = jnp.take_along_axis(
        x, token_of_choice.reshape(b, e * capacity, 1), axis=1
    ).reshape(b, e, capacity, d) * valid[..., None].astype(x.dtype)
    # dispatch reshard: this boundary is where the EP all-to-all belongs;
    # without the constraint GSPMD replicated the batch and all-reduced
    # expert outputs (§Perf B). 2D EP (experts over data x model, weights
    # fully local) when expert count allows; else batch x model.
    x_disp = constrain(x_disp, "batch", "model", None, None)

    # expert FFN (einsum over stacked expert weights; E is the EP axis)
    def expert_mm(h, w):
        return jnp.einsum("becd,edf->becf", h.astype(cfg.compute_dtype), w.astype(cfg.compute_dtype))

    up = expert_mm(x_disp, p["up"])
    gate = expert_mm(x_disp, p["gate"])
    h = apply_af(gate, cfg.act, ctx) * up
    y = jnp.einsum("becf,efd->becd", h.astype(cfg.compute_dtype), p["down"].astype(cfg.compute_dtype))
    y = constrain(y, "batch", "model", None, None)

    # combine: scatter-add each expert slot's weighted output back to its
    # token. Combine-as-scatter (not gather+einsum) is deliberate: the
    # einsum-combine's BACKWARD materializes a K-replicated (B, S*K, D)
    # full-D f32 cotangent (872 GB/dev all-gather + 872 GB all-reduce
    # measured); scatter-add's backward is a plain gather (§Perf B).
    kept = (rank < capacity).astype(jnp.float32) * top_p  # (B,S,K); drops -> 0
    w_slot = jnp.take_along_axis(
        kept.reshape(b, s * k), gather_idx.reshape(b, e * capacity), axis=1
    ) * valid.reshape(b, e * capacity)  # (B, E*C) weight of the choice per slot
    yw = y.astype(cfg.compute_dtype) * w_slot.reshape(b, e, capacity, 1).astype(
        cfg.compute_dtype
    )
    out = _combine_scatter(yw, token_of_choice, s, d).astype(x.dtype)
    out = constrain(out, "batch", None, None)

    if m.num_shared_experts:
        out = out + mlp(p["shared"], x, cfg, ctx, name=f"{name}.shared")

    # aux: load-balance loss. Scatter-counts instead of a one_hot (B,S,E)
    # materialization — the one_hot form all-gathered 62 GB/dev of f32 router
    # probs per pass (§Perf B iteration 4). Cached decode (dropless=True)
    # skips it entirely: the loss is a training quantity the serving loop
    # discards, and its flat scatter-add is the same scatter class the SPMD
    # partitioner mis-lowers inside nested decode scans (see
    # ``cache_row_write``) — no reason to carry it through the burst.
    if dropless:
        aux = {"lb_loss": jnp.zeros((), jnp.float32)}
    else:
        me = jnp.mean(probs, axis=(0, 1))  # (E,)
        counts = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
        ce = counts / (b * s * k)
        aux = {"lb_loss": e * jnp.sum(me * ce)}
    return out, aux
