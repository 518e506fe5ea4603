"""Plain reference forward of Zamba2 (arXiv:2411.15242), in float32.

Straight ``jax.numpy`` at ``highest`` matmul precision, with no kernels,
cache or batching: one sequence, every position at once, layer by layer so
that only one layer's temporaries live at a time. It follows the published
model (``transformers``' ``modeling_zamba2``) and the configuration file's
keys (``layers_block_type``, ``n_mamba_heads``, ``mamba_headdim``, ...):

* a ``mamba`` layer is ``h + Mamba2(RMSNorm(h))``. The mixer projects to
  ``z, x, B, C, dt``; ``x, B, C`` pass a depthwise causal conv of width
  ``mamba_d_conv`` (with bias) and SiLU; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the state runs as the per-token recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t``, ``y_t = C_t h_t + D x_t``
  (B and C shared by the heads of each of ``mamba_ngroups`` groups); then
  ``y * silu(z)`` is RMS-normalised in ``mamba_ngroups`` groups of channels
  and projected out;
* a ``hybrid`` layer, the ``k``-th, first calls shared block
  ``k % num_mem_blocks``: ``u = RMSNorm([h ; e])`` (``e`` the embedding
  output), attention of ``num_attention_heads`` heads of
  ``attention_head_dim`` with RoPE on every dim (the two halves of a head
  rotated as a pair) and scores scaled by ``(attention_head_dim / 2) **
  -0.5``, ``a = o_proj(...)``; then ``t = down(gelu(g) * up)`` with
  ``[g ; up] = gate_up(RMSNorm(a)) + adapter_k(RMSNorm(a))`` and no residual
  inside the block; ``T = linear(t)``; then ``h + Mamba2(RMSNorm(h + T))``;
* the final RMSNorm and an output head tied to the embedding.

The weights arrive as a tree with the layout of the served model's
parameters (``seg<i>_mamba`` stacks, ``seg<i>_hybrid`` stacks of one,
``shared`` the stacked blocks); nothing else of the program is used.

It computes in the precision the configuration states, as ``olmo_ref``
does: the input of every projection (the adapters' and ``linear`` too) is
rounded to the ``activations`` format, the MLP's activation function takes
and gives values in its ``af_format``, and each projection weight is rounded
to its ``weight_format``. Everything else is float32. ``control=True`` puts
every projection's input at the ``control`` format instead.

Departures from the published model, each below what the comparison reads:

* the activation is the tanh form of GELU, the program's; the published
  model uses the erf form. The two differ by under 1e-3, below the AF
  format's step of 2^-6;
* ``dt`` is not clamped: ``transformers``' CPU path clamps it below at
  ``time_step_min``, its kernel path (``time_step_limit`` null) does not;
  this follows the kernel path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_GELU_C = math.sqrt(2.0 / math.pi)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


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


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(_GELU_C * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("dims", "fmt", "wfmt"))
def _mamba(h, extra, lw, *, dims, fmt, wfmt):
    """``h + Mamba2(RMSNorm(h + extra))`` over one sequence ``h (S, d)``."""
    heads, p, groups, n, width, eps = dims
    s, _ = h.shape
    mw = lw["mixer"]
    x = _rms(h + extra, lw["norm"]["scale"], eps)
    zxbcdt = _proj(x, mw["in_proj"], fmt, wfmt)
    d_in = heads * p
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:2 * d_in + 2 * groups * n],
                  zxbcdt[:, 2 * d_in + 2 * groups * n:])
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = sum(padded[i:i + s] * mw["conv_w"][i] for i in range(width))
    xbc = jax.nn.silu(conv + mw["conv_b"])
    xs = xbc[:, :d_in].reshape(s, heads, p)
    bm = xbc[:, d_in:d_in + groups * n].reshape(s, groups, n)
    cm = xbc[:, d_in + groups * n:].reshape(s, groups, n)
    rep = heads // groups
    bm, cm = jnp.repeat(bm, rep, axis=1), jnp.repeat(cm, rep, axis=1)  # (S,H,N)
    dt = jax.nn.softplus(dt + mw["dt_bias"])  # (S, H)
    a = -jnp.exp(mw["A_log"])

    def step(state, inp):  # state (H, N, P)
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + dt_t[:, None, None] * b_t[:, :, None] * x_t[:, None, :])
        return state, jnp.einsum("hn,hnp->hp", c_t, state)

    _, y = jax.lax.scan(step, jnp.zeros((heads, n, p), jnp.float32),
                        (xs, dt, bm, cm))
    y = (y + mw["D"][:, None] * xs).reshape(s, d_in)
    g = (y * jax.nn.silu(z)).reshape(s, groups, d_in // groups)
    y = _rms(g, mw["norm"].reshape(groups, -1), eps).reshape(s, d_in)
    return h + _proj(y, mw["out_proj"], fmt, wfmt)


@functools.partial(jax.jit,
                   static_argnames=("heads", "theta", "scale", "eps", "fmt",
                                    "af", "wfmt"))
def _shared(h, e, sw, lw, *, heads, theta, scale, eps, fmt, af, wfmt):
    """``T = linear(block(u))``: the shared block's output into the mixer."""
    s, d = h.shape
    pos = jnp.arange(s)
    u = _rms(jnp.concatenate([h, e], -1), sw["attn_norm"]["scale"], eps)
    at = sw["attn"]
    q = _proj(u, at["wq"].reshape(2 * d, -1), fmt, wfmt).reshape(s, heads, -1)
    k = _proj(u, at["wk"].reshape(2 * d, -1), fmt, wfmt).reshape(s, heads, -1)
    v = _proj(u, at["wv"].reshape(2 * d, -1), fmt, wfmt).reshape(s, heads, -1)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = pos[None, :, None] >= pos[None, None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
    a = _proj(att, at["wo"].reshape(-1, d), fmt, wfmt)
    x = _rms(a, sw["mlp_norm"]["scale"], eps)
    m, ad = sw["mlp"], lw["adapter"]
    r = _proj(x, ad["down"], fmt, wfmt)
    g = _proj(x, m["gate"], fmt, wfmt) + _proj(r, ad["gate"], fmt, wfmt)
    up = _proj(x, m["up"], fmt, wfmt) + _proj(r, ad["up"], fmt, wfmt)
    t = _proj(_quant(_gelu(_quant(g, af)), af) * up, m["down"], fmt, wfmt)
    return _proj(t, lw["linear"], fmt, wfmt)


@functools.partial(jax.jit, static_argnames=("eps", "fmt", "wfmt"))
def _head(h, norm, embed, *, eps, fmt, wfmt):
    return _proj(_rms(h, norm, eps), embed.T, fmt, wfmt)


def _fmt(entry):
    return None if entry is None else (entry["scheme"], entry["bits"],
                                       entry.get("frac"))


def _layers(weights, kinds):
    """``(kind, layer weights)`` in the order of ``kinds``, read from the
    tree's ``seg<i>_<kind>`` stacks."""
    segs = sorted((int(key[3:key.index("_")]), key) for key in weights
                  if key.startswith("seg"))
    out = []
    for _, key in segs:
        stack = weights[key]
        kind = key[key.index("_") + 1:]
        for j in range(jax.tree.leaves(stack)[0].shape[0]):
            out.append((kind, jax.tree.map(lambda a, j=j: a[j], stack)))
    if [k for k, _ in out] != list(kinds):
        raise ValueError(f"weights hold layers {[k for k, _ in out]}, the "
                         f"configuration {list(kinds)}")
    return out


def logits(weights, tokens, cfg, *, control=False):
    """Logits ``(S, V)`` of one token sequence ``(S,)`` (int32)."""
    fmt = _fmt(cfg["control"] if control else cfg["activations"])
    af = _fmt(cfg["af_format"])
    wfmt = _fmt(cfg.get("weight_format"))
    eps = float(cfg["rms_norm_eps"])
    hd = cfg["attention_head_dim"]
    dims = (cfg["n_mamba_heads"], cfg["mamba_headdim"], cfg["mamba_ngroups"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"], eps)
    kinds = cfg["layers_block_type"][:cfg["num_hidden_layers"]]
    with jax.default_matmul_precision("highest"):
        e = jnp.take(weights["embed"], tokens, axis=0)
        h, calls = e, 0
        for kind, lw in _layers(weights, kinds):
            extra = jnp.zeros_like(h)
            if kind == "hybrid":
                b = calls % cfg["num_mem_blocks"]
                sw = jax.tree.map(lambda a: a[b], weights["shared"])
                extra = _shared(h, e, sw, lw, heads=cfg["num_attention_heads"],
                                theta=float(cfg["rope_theta"]),
                                scale=(hd / 2) ** -0.5, eps=eps, fmt=fmt,
                                af=af, wfmt=wfmt)
                calls += 1
            h = _mamba(h, extra, lw, dims=dims, fmt=fmt, wfmt=wfmt)
        return _head(h, weights["final_norm"]["scale"], weights["embed"],
                     eps=eps, fmt=fmt, wfmt=wfmt)
