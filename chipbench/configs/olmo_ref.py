"""Plain reference forward of OLMo (arXiv:2402.00838), in float32.

Straight ``jax.numpy`` at ``highest`` matmul precision, with no kernels,
cache or batching: one sequence, every position at once, layer by layer so
that only one layer's temporaries live at a time. It follows the published
description: non-parametric LayerNorm (no affine terms) before attention and
before the MLP, rotary embeddings on queries and keys (the two halves of each
head rotated as a pair), causal softmax attention, a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``), no biases, and an output head tied to the
embedding. The weights arrive as a tree with the layout of the served model's
parameters; nothing else of the program is used.

It computes in the precision the configuration states: the input of every
projection is rounded to the configuration's ``activations`` format, and the
MLP's activation function takes and gives values in its ``af_format``
(``fixed``: a saturating binary point, round half to even; ``per_row``:
symmetric, one scale per row), and each projection weight to its
``weight_format`` where one is stated (``per_channel``: symmetric, one scale
per output channel). Everything else is float32.

``control=True`` gives the control: the same forward with every projection's
input at the configuration's ``control`` format, 4 bits in its own scheme,
the step below the 8-bit activations the configurations serve.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS)


def _quant(v, fmt):
    """Fake quantization of activations ``v (S, K)`` to ``fmt`` (None: as is)."""
    if fmt is None:
        return v
    scheme, bits = fmt[0], fmt[1]
    if scheme == "fixed":  # binary point at ``frac`` bits, saturating
        step = 2.0 ** -fmt[2]
        lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
        return jnp.clip(jnp.round(v / step), lo, hi) * step
    if scheme == "per_row":
        q = (1 << (bits - 1)) - 1
        scale = jnp.maximum(jnp.max(jnp.abs(v), -1, keepdims=True), 1e-12) / q
        return jnp.clip(jnp.round(v / scale), -q, q) * scale
    raise ValueError(f"unknown scheme {scheme!r}")


def _weight(w, fmt):
    """Round ``w (K, N)`` to ``fmt`` (None: as is)."""
    if fmt is None:
        return w
    if fmt[0] != "per_channel":
        raise ValueError(f"unknown weight scheme {fmt[0]!r}")
    q = (1 << (fmt[1] - 1)) - 1
    scale = jnp.maximum(jnp.max(jnp.abs(w), 0, keepdims=True), 1e-12) / q
    return jnp.clip(jnp.round(w / scale), -q, q) * scale


def _proj(x, w, fmt, wfmt):
    """``x (S, K) @ w (K, N)`` with ``x`` rounded to ``fmt``, ``w`` to ``wfmt``."""
    return _quant(x, fmt) @ _weight(w, wfmt)


def _rope(x, pos, theta):
    """x (S, H, D): rotate the pair (x[:D/2], x[D/2:]) by position."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit,
                   static_argnames=("heads", "theta", "fmt", "af", "wfmt"))
def _layer(h, lw, *, heads, theta, fmt, af, wfmt):
    s, d = h.shape
    pos = jnp.arange(s)
    x = _ln(h)
    a = lw["attn"]
    q = _proj(x, a["wq"].reshape(d, -1), fmt, wfmt).reshape(s, heads, -1)
    k = _proj(x, a["wk"].reshape(d, -1), fmt, wfmt).reshape(s, heads, -1)
    v = _proj(x, a["wv"].reshape(d, -1), fmt, wfmt).reshape(s, heads, -1)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
    h = h + _proj(att, a["wo"].reshape(-1, d), fmt, wfmt)
    x = _ln(h)
    m = lw["mlp"]
    g = _quant(jax.nn.silu(_quant(_proj(x, m["gate"], fmt, wfmt), af)), af)
    u = _proj(x, m["up"], fmt, wfmt)
    return h + _proj(g * u, m["down"], fmt, wfmt)


@functools.partial(jax.jit, static_argnames=("fmt", "wfmt"))
def _head(h, embed, *, fmt, wfmt):
    return _proj(_ln(h), embed.T, fmt, wfmt)


def _fmt(entry):
    return None if entry is None else (entry["scheme"], entry["bits"],
                                       entry.get("frac"))


def logits(weights, tokens, cfg, *, control=False):
    """Logits ``(S, V)`` of one token sequence ``(S,)`` (int32)."""
    fmt = _fmt(cfg["control"] if control else cfg["activations"])
    af = _fmt(cfg["af_format"])
    wfmt = _fmt(cfg.get("weight_format"))
    with jax.default_matmul_precision("highest"):
        h = jnp.take(weights["embed"], tokens, axis=0)
        stack = weights["seg0_dense"]
        for i in range(cfg["num_hidden_layers"]):
            lw = jax.tree.map(lambda a: a[i], stack)
            h = _layer(h, lw, heads=cfg["num_attention_heads"],
                       theta=float(cfg["rope_theta"]), fmt=fmt, af=af, wfmt=wfmt)
        return _head(h, weights["embed"], fmt=fmt, wfmt=wfmt)
