"""Decoder-only LM covering the dense / moe / vlm / hybrid / ssm families.

Design constraints that shaped this file:

* **HLO is O(1) in depth**: every repeated layer stack is a ``lax.scan`` over
  stacked parameters (stacked leading 'layers' axis). MoE models with a dense
  prefix (deepseek) or interleaving (llama4) scan each homogeneous segment.
* **one code path for train / prefill / decode**: segments take an optional
  cache pytree, stacked along layers. In a one-token decode step a stacked
  attention KV cache on one device is the scan's carry, written one row in
  place per layer; blocks of several tokens, recurrent state, MLA caches and
  every cache under a mesh are consumed as scan xs and emitted as ys
  (``_scan_segment``).
* **CARMEN everywhere**: all projections go through ``EngineContext``; MLP
  activations go through the multi-AF block mapping.
* **hybrid (Zamba2)**: the published layer list, as runs of plain mamba
  layers (each run a stacked scan) between hybrid layers (each a stack of
  one, called in order). The ``k``-th hybrid layer calls shared block
  ``k % num_mem_blocks`` on ``[h ; e]`` (``e`` the embedding output), with
  its own adapter and ``linear``; the shared blocks' K/V live in one stacked
  cache, ``shared_kv``, row ``k`` for the ``k``-th hybrid layer.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import EngineContext

from repro.sharding.partition import constrain, current_mesh_axes

from . import blocks, mamba2, mla
from .params import ParamSpec, stack_layers


# ---------------------------------------------------------------------------
# Layer specs per family
# ---------------------------------------------------------------------------


def _attn_specs(cfg: ModelConfig):
    return mla.mla_specs(cfg) if cfg.mla else blocks.attention_specs(cfg)


def _dense_layer_specs(cfg: ModelConfig, d_ff: Optional[int] = None):
    return {
        "attn_norm": blocks.norm_spec(cfg),
        "attn": _attn_specs(cfg),
        "mlp_norm": blocks.norm_spec(cfg),
        "mlp": blocks.mlp_specs(cfg, d_ff),
    }


def _moe_layer_specs(cfg: ModelConfig):
    return {
        "attn_norm": blocks.norm_spec(cfg),
        "attn": _attn_specs(cfg),
        "mlp_norm": blocks.norm_spec(cfg),
        "moe": blocks.moe_specs(cfg),
    }


def _mamba_layer_specs(cfg: ModelConfig):
    return {"norm": blocks.norm_spec(cfg), "mixer": mamba2.mamba2_specs(cfg)}


def _hybrid_layer_specs(cfg: ModelConfig):
    """A hybrid layer's own weights: its mixer, the ``linear`` that takes the
    shared block's output into the mixer's input, and its call's adapter on
    the shared MLP's gate/up (``down`` to the rank, then ``gate``/``up``)."""
    d, f, r = cfg.d_model, cfg.d_ff, cfg.hybrid.adapter_rank
    return dict(
        _mamba_layer_specs(cfg),
        linear=ParamSpec((d, d), ("embed", "embed")),
        adapter={
            "down": ParamSpec((d, r), ("embed", None)),
            "gate": ParamSpec((r, f), (None, "mlp")),
            "up": ParamSpec((r, f), (None, "mlp")),
        },
    )


def _shared_block_specs(cfg: ModelConfig):
    """One shared transformer block: attention on the ``2 * d_model`` wide
    ``[h ; e]``, then the MLP on the norm of its output."""
    return {
        "attn_norm": blocks.norm_spec(cfg, 2 * cfg.d_model),
        "attn": blocks.attention_specs(cfg, d_in=2 * cfg.d_model),
        "mlp_norm": blocks.norm_spec(cfg),
        "mlp": blocks.mlp_specs(cfg),
    }


def _segments(cfg: ModelConfig):
    """(kind, layer_count) segments; layer params stack within a segment."""
    if cfg.family in ("dense", "vlm"):
        return [("dense", cfg.num_layers)]
    if cfg.family == "moe":
        m = cfg.moe
        segs = []
        if m.first_dense_layers:
            segs.append(("dense_prefix", m.first_dense_layers))
        rest = cfg.num_layers - m.first_dense_layers
        if m.moe_every == 1:
            segs.append(("moe", rest))
        else:
            assert rest % m.moe_every == 0
            segs.append(("pair", rest // m.moe_every))
        return segs
    if cfg.family == "ssm":
        return [("mamba", cfg.num_layers)]
    if cfg.family == "hybrid":
        segs, run = [], 0
        for i in range(cfg.num_layers):
            if i not in cfg.hybrid.hybrid_layer_ids:
                run += 1
                continue
            if run:
                segs.append(("mamba", run))
            segs.append(("hybrid", 1))
            run = 0
        if run:
            segs.append(("mamba", run))
        return segs
    raise ValueError(cfg.family)



def decoder_specs(cfg: ModelConfig):
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
        "final_norm": blocks.norm_spec(cfg),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    for i, (kind, n) in enumerate(_segments(cfg)):
        key = f"seg{i}_{kind}"
        if kind == "dense":
            specs[key] = stack_layers(lambda: _dense_layer_specs(cfg), n)
        elif kind == "dense_prefix":
            specs[key] = stack_layers(lambda: _dense_layer_specs(cfg, cfg.moe.d_ff_dense), n)
        elif kind == "moe":
            specs[key] = stack_layers(lambda: _moe_layer_specs(cfg), n)
        elif kind == "pair":
            specs[key] = stack_layers(
                lambda: {
                    "dense": _dense_layer_specs(cfg, cfg.moe.d_ff_dense),
                    "moe": _moe_layer_specs(cfg),
                },
                n,
            )
        elif kind == "mamba":
            specs[key] = stack_layers(lambda: _mamba_layer_specs(cfg), n)
        elif kind == "hybrid":
            specs[key] = stack_layers(lambda: _hybrid_layer_specs(cfg), n)
    if cfg.family == "hybrid":
        specs["shared"] = stack_layers(lambda: _shared_block_specs(cfg),
                                       cfg.hybrid.num_mem_blocks)
    return specs


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------


def _attn_block(p, h, cfg, ctx, positions, cache, name):
    h = constrain(h, "batch", None, None)
    x = blocks.apply_norm(p["attn_norm"], h, cfg)
    if cfg.mla:
        out, new_cache = mla.mla_attention(
            p["attn"], x, cfg, ctx, positions=positions, name=name, cache=cache
        )
    else:
        out, new_cache = blocks.attention(
            p["attn"], x, cfg, ctx, positions=positions, name=name, cache=cache
        )
    return h + out, new_cache


def _dense_layer(p, h, cfg, ctx, positions, cache, name="layer"):
    h, new_cache = _attn_block(p, h, cfg, ctx, positions, cache, f"{name}.attn")
    x = blocks.apply_norm(p["mlp_norm"], h, cfg)
    h = h + blocks.mlp(p["mlp"], x, cfg, ctx, name=f"{name}.mlp")
    return h, new_cache, {}


def _moe_layer(p, h, cfg, ctx, positions, cache, name="layer"):
    h, new_cache = _attn_block(p, h, cfg, ctx, positions, cache, f"{name}.attn")
    x = blocks.apply_norm(p["mlp_norm"], h, cfg)
    # cached decode gets the dropless short-block capacity (S>1 verify parity)
    out, aux = blocks.moe_ffn(p["moe"], x, cfg, ctx, name=f"{name}.moe",
                              dropless=cache is not None)
    return h + out, new_cache, aux


def _mamba_layer(p, h, cfg, ctx, state, name="layer", *, clen=None, extra=None):
    """``h + Mamba2(norm(h + extra))``: ``extra``, a hybrid layer's shared
    block output, enters the mixer's input and not the residual."""
    h = constrain(h, "batch", None, None)
    x = blocks.apply_norm(p["norm"], h if extra is None else h + extra, cfg)
    out, new_state = mamba2.mamba2_forward(p["mixer"], x, cfg, ctx, name=f"{name}.mixer",
                                           state=state, clen=clen)
    return h + out, new_state


def _kv_row(kv, k: int, block: int):
    """Hybrid layer ``k``'s view of the stacked ``shared_kv`` cache: in a
    decode step the whole stack, written in place (``_carried``); else its
    own slab."""
    if _carried(kv, block):
        return dict(kv, layer=jnp.int32(k))
    return jax.tree.map(lambda a: a[k], kv)


def _kv_put(kv, k: int, new):
    """The stack after hybrid layer ``k``'s attention wrote ``new``."""
    if "layer" in new:
        return _drop_layer(new)
    return jax.tree.map(lambda a, n: a.at[k].set(n), kv, new)


def _hybrid_layer(p, shared, h, e, cfg, ctx, positions, state, kv, k: int,
                  clen=None):
    """The ``k``-th hybrid layer (Zamba2): shared block ``k % num_mem_blocks``
    on ``[h ; e]`` through this call's adapter, this layer's ``linear``, and
    its mixer. Returns ``(h, new_state, new_kv)``; ``kv`` is the stacked
    ``shared_kv`` cache (None without a cache).

    Named scopes: ``shared`` holds the block, ``shared.adapter`` the
    adapter; attention keeps its own (``attention.*``)."""
    sp = jax.tree.map(lambda a: a[k % cfg.hybrid.num_mem_blocks], shared)
    with jax.named_scope("shared"):
        u = blocks.apply_norm(sp["attn_norm"], jnp.concatenate([h, e], -1), cfg)
        cache = None if kv is None else _kv_row(kv, k, h.shape[1])
        a, new_kv = blocks.attention(sp["attn"], u, cfg, ctx, positions=positions,
                                     name="shared.attn", cache=cache)
        x = blocks.apply_norm(sp["mlp_norm"], a, cfg)
        ad = p["adapter"]
        with jax.named_scope("shared.adapter"):
            r = ctx.linear(x, ad["down"], name="layer.adapter.down")
            delta = (ctx.linear(r, ad["gate"], name="layer.adapter.gate"),
                     ctx.linear(r, ad["up"], name="layer.adapter.up"))
        t = blocks.mlp(sp["mlp"], x, cfg, ctx, name="shared.mlp", delta=delta)
        t = ctx.linear(t, p["linear"], name="layer.linear")
    h, new_state = _mamba_layer(p, h, cfg, ctx, state, clen=clen, extra=t)
    return h, new_state, None if kv is None else _kv_put(kv, k, new_kv)


# ---------------------------------------------------------------------------
# Segment runners (scan over stacked layer params [+ caches])
# ---------------------------------------------------------------------------


_ATTN_CACHE = frozenset(("k", "v", "index"))


def _carried(caches, block: int) -> bool:
    """Whether a segment's cache travels in the layer scan's carry: a stacked
    attention KV cache (``blocks.attn_cache_specs``), or a dict of them
    (``pair``), on one device, written one token a row (``block`` 1, the
    decode step). Recurrent state and MLA caches do not, nor does any cache
    under a mesh, where ``blocks.cache_row_write``'s gather-and-select form
    rewrites the whole layer anyway.

    Nor does a block of several tokens (a prefill chunk, a speculative
    verify): its scores and values are MXU dots, and on a TPU v5e a block
    reading its K/V out of the carried stack computed other numbers than
    one reading the layer's own slab (the compiled dots then take the cache
    converted to bf16 in a fusion of its own), while single-token steps
    matched bit for bit."""
    if block != 1 or not isinstance(caches, dict) or current_mesh_axes():
        return False
    if set(caches) == _ATTN_CACHE:
        return True
    return all(isinstance(c, dict) and set(c) == _ATTN_CACHE
               for c in caches.values())


def _layer_view(caches, layer):
    """The stacked cache as one layer sees it: each attention cache gains a
    ``layer`` entry, its index in the stack (``blocks.attention``)."""
    if set(caches) == _ATTN_CACHE:
        return dict(caches, layer=layer)
    return {key: dict(c, layer=layer) for key, c in caches.items()}


def _drop_layer(caches):
    if "layer" in caches:
        return {key: c for key, c in caches.items() if key != "layer"}
    return {key: _drop_layer(c) for key, c in caches.items()}


def _scan_segment(layer_fn, stacked_params, h, caches, *, remat: bool):
    """One segment's layers as a ``lax.scan``.

    In a decode step, a stacked attention KV cache on one device
    (:func:`_carried`) travels in the scan's carry: each layer writes its
    new row into the stack in place and reads its own K/V from it, so no
    layer's slab is copied out and back and the caller's carry aliases the
    scan's. Every other cache (and none, in training) is scanned as ``xs``
    and comes back as ``ys``.

    Named scopes: ``layers`` holds the scan, ``layer`` its body; operations
    in ``layers`` but in no ``layer`` move each layer's slice of the stacked
    weights (and of an ``xs`` cache) in and out."""
    body = layer_fn
    if remat:
        body = jax.checkpoint(layer_fn, prevent_cse=False)

    if _carried(caches, h.shape[1]):
        def carried_fn(carry, xs):
            h, cache = carry
            p, layer = xs
            with jax.named_scope("layer"):
                h, new_cache, aux = body(p, h, _layer_view(cache, layer))
            return (h, _drop_layer(new_cache)), aux

        n = jax.tree.leaves(stacked_params)[0].shape[0]
        with jax.named_scope("layers"):
            (h, new_caches), auxs = jax.lax.scan(
                carried_fn, (h, caches),
                (stacked_params, jnp.arange(n, dtype=jnp.int32)))
        return h, new_caches, auxs

    def scan_fn(h, xs):
        p, cache = xs
        with jax.named_scope("layer"):
            h, new_cache, aux = body(p, h, cache)
        return h, (new_cache, aux)

    with jax.named_scope("layers"):
        h, (new_caches, auxs) = jax.lax.scan(scan_fn, h,
                                             (stacked_params, caches))
    return h, new_caches, auxs


def _run_segments(params, h, cfg, ctx, positions, caches, *, remat: bool,
                  clen=None):
    """caches: dict seg_key -> stacked cache (or None). Returns h, caches, aux.

    ``clen``: in a block of cached tokens, the rows that are real (scalar or
    (B,)); recurrent mixers leave their state as it was past it."""
    new_caches = {}
    lb_loss = jnp.zeros((), jnp.float32)
    e, kv, k = h, None, 0  # hybrid: the embedding output, shared K/V, calls
    if cfg.family == "hybrid" and caches:
        kv = caches["shared_kv"]
    for i, (kind, n) in enumerate(_segments(cfg)):
        key = f"seg{i}_{kind}"
        seg_cache = caches.get(key) if caches else None
        if kind in ("dense", "dense_prefix"):
            fn = lambda p, h, c: _dense_layer(p, h, cfg, ctx, positions, c)
            h, nc, _ = _scan_segment(fn, params[key], h, seg_cache, remat=remat)
            new_caches[key] = nc
        elif kind == "moe":
            fn = lambda p, h, c: _moe_layer(p, h, cfg, ctx, positions, c)
            h, nc, aux = _scan_segment(fn, params[key], h, seg_cache, remat=remat)
            lb_loss = lb_loss + jnp.sum(aux.get("lb_loss", jnp.zeros((n,))))
            new_caches[key] = nc
        elif kind == "pair":

            def pair_fn(p, h, c):
                c_d, c_m = (c or {}).get("dense"), (c or {}).get("moe")
                h, nc_d, _ = _dense_layer(p["dense"], h, cfg, ctx, positions, c_d)
                h, nc_m, aux = _moe_layer(p["moe"], h, cfg, ctx, positions, c_m)
                return h, {"dense": nc_d, "moe": nc_m}, aux

            h, nc, aux = _scan_segment(pair_fn, params[key], h, seg_cache, remat=remat)
            lb_loss = lb_loss + jnp.sum(aux.get("lb_loss", jnp.zeros((n,))))
            new_caches[key] = nc
        elif kind == "mamba":

            def mamba_fn(p, h, c):
                h, ns = _mamba_layer(p, h, cfg, ctx, c, clen=clen)
                return h, ns, {}

            h, nc, _ = _scan_segment(mamba_fn, params[key], h, seg_cache, remat=remat)
            new_caches[key] = nc
        elif kind == "hybrid":
            one = lambda t: None if t is None else jax.tree.map(lambda a: a[0], t)

            def hybrid_fn(p, h, state, kv, k=k):
                return _hybrid_layer(p, params["shared"], h, e, cfg, ctx,
                                     positions, state, kv, k, clen)

            if remat:
                hybrid_fn = jax.checkpoint(hybrid_fn, prevent_cse=False)
            with jax.named_scope("layer"):
                h, ns, kv = hybrid_fn(one(params[key]), h, one(seg_cache), kv)
            new_caches[key] = jax.tree.map(lambda a: a[None], ns)
            k += 1
    if kv is not None:
        new_caches["shared_kv"] = kv
    return h, new_caches, {"lb_loss": lb_loss}


# ---------------------------------------------------------------------------
# Cache construction
# ---------------------------------------------------------------------------


def _seg_cache(cfg, kind, n, batch, max_len, dtype, abstract: bool):
    """One segment's stacked cache: its shapes, or (``abstract`` False)
    zeros of them, made at the stacked shape at once (no per-layer copy to
    stack, which would hold the cache twice while it is made)."""
    def attn_c():
        f = mla.mla_cache_specs if cfg.mla else blocks.attn_cache_specs
        return f(cfg, batch, max_len, dtype)

    def mamba_c():
        return mamba2.mamba_state_specs(cfg, batch, dtype)

    def stack(tree, m):
        shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((m,) + s.shape, s.dtype), tree)
        if abstract:
            return shapes
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)

    if kind in ("dense", "dense_prefix", "moe", "shared_kv"):
        return stack(attn_c(), n)
    if kind == "pair":
        return stack({"dense": attn_c(), "moe": attn_c()}, n)
    if kind in ("mamba", "hybrid"):
        return stack(mamba_c(), n)
    raise ValueError(kind)


def make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16, abstract=False):
    caches = {
        f"seg{i}_{kind}": _seg_cache(cfg, kind, n, batch, max_len, dtype, abstract)
        for i, (kind, n) in enumerate(_segments(cfg))
    }
    if cfg.family == "hybrid":
        n = sum(kind == "hybrid" for kind, _ in _segments(cfg))
        caches["shared_kv"] = _seg_cache(cfg, "shared_kv", n, batch, max_len,
                                         dtype, abstract)
    return caches


# ---------------------------------------------------------------------------
# Public model API
# ---------------------------------------------------------------------------


def forward(params, batch, cfg: ModelConfig, ctx: EngineContext, *, remat: bool = False):
    """Train/prefill forward: batch['tokens'] (B, S) -> logits (B, S(+P), V).

    VLM/audio-lm families prepend batch['frontend_embeds'] (B, P, D) stub
    embeddings; logits cover the full concatenated sequence.
    """
    tokens = batch["tokens"]
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    h = constrain(h, "batch", None, None)
    if cfg.frontend == "vision":
        fe = batch["frontend_embeds"].astype(cfg.compute_dtype)
        h = jnp.concatenate([fe, h], axis=1)
    s = h.shape[1]
    positions = jnp.arange(s)
    h, _, aux = _run_segments(params, h, cfg, ctx, positions, None, remat=remat)
    h = constrain(h, "batch", None, None)
    logits = constrain(_lm_head(params, h, cfg, ctx), "batch", None, "model")
    return logits, aux


@jax.named_scope("lm_head")
def _lm_head(params, h, cfg, ctx):
    """The final norm and the output head (named scope ``lm_head``)."""
    h = blocks.apply_norm(params["final_norm"], h, cfg)
    # prepared trees carry an explicit lm_head even when embeddings are tied
    # (prepare_params materializes the transposed bank once), so decoding
    # never re-quantizes the output head
    if cfg.tie_embeddings and "lm_head" not in params:
        w = params["embed"].T
    else:
        w = params["lm_head"]
    return ctx.linear(h, w, name="lm_head").astype(jnp.float32)


def decode_step(params, tokens, cache, cfg: ModelConfig, ctx: EngineContext,
                clen=None):
    """Cached decode: tokens (B, S) + cache -> (logits (B, S, V), cache).

    S = 1 is the classic one-token decode step; S > 1 writes a whole block
    (batched prefill: the serving engine feeds the full prompt in one call
    and scatters the resulting KV into its slot cache). ``clen`` (scalar or
    (B,)) counts a block's real rows: recurrent state ignores the rest
    (attention caches hide them behind the rewound write index).
    """
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    h = constrain(h, "batch", None, None)
    index = _cache_index(cache)  # (B,) per-row decode positions
    positions = index[:, None] + jnp.arange(tokens.shape[1])[None, :]  # (B, S)
    h, new_caches, _ = _run_segments(params, h, cfg, ctx, positions, cache,
                                     remat=False, clen=clen)
    logits = _lm_head(params, h, cfg, ctx)
    return logits, new_caches


def _cache_index(cache):
    """Per-row decode positions: attn caches carry a stacked (L, B) index; all
    layers advance in lockstep so layer 0's row is authoritative. SSM-only
    models have no index (positions are unused by the mixer) -> zeros."""
    for v in jax.tree.leaves(cache):
        if hasattr(v, "dtype") and v.dtype == jnp.int32 and v.ndim >= 2:
            return v[0]  # (B,)
    # ssm-only: derive batch from any state leaf
    some = jax.tree.leaves(cache)[0]
    return jnp.zeros((some.shape[1],), jnp.int32)
