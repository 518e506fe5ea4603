"""Mamba2 / SSD mixer (arXiv:2405.21060, state-space duality).

Chunked SSD forward for train/prefill: within-chunk quadratic ("attention-like")
term + across-chunk linear recurrence carried by ``lax.scan`` — O(L) in sequence
length, which is what qualifies the ssm/hybrid archs for the long_500k cell.
Single-step recurrent form for decode.

Arch-applicability note (DESIGN.md §4): the SSD *recurrence* is elementwise
state decay, not a MAC-array workload, so the CORDIC-MAC technique applies to
the in/out projections (routed through EngineContext) while the recurrence
itself stays in bf16/f32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import EngineContext
from repro.core.normalization import rmsnorm

from .params import ParamSpec


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    n_heads = s.num_heads or d_inner // s.head_dim
    assert n_heads * s.head_dim == d_inner and n_heads % s.n_groups == 0, cfg.name
    return d_inner, n_heads


def mamba2_specs(cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, n_heads = _dims(cfg)
    conv_dim = d_inner + 2 * s.n_groups * s.state_dim
    proj_out = 2 * d_inner + 2 * s.n_groups * s.state_dim + n_heads  # z, x, B, C, dt
    return {
        "in_proj": ParamSpec((d, proj_out), ("embed", "ssm_inner")),
        "conv_w": ParamSpec((s.conv_width, conv_dim), ("conv", "ssm_inner")),
        "conv_b": ParamSpec((conv_dim,), ("ssm_inner",), "zeros"),
        "A_log": ParamSpec((n_heads,), ("ssm_heads",), "zeros"),
        "D": ParamSpec((n_heads,), ("ssm_heads",), "ones"),
        "dt_bias": ParamSpec((n_heads,), ("ssm_heads",), "zeros"),
        "norm": ParamSpec((d_inner,), ("ssm_inner",), "ones"),
        "out_proj": ParamSpec((d_inner, d), ("ssm_inner", "embed")),
    }


def _split_proj(cfg: ModelConfig, zxbcdt):
    s = cfg.ssm
    d_inner, n_heads = _dims(cfg)
    gn = s.n_groups * s.state_dim
    z = zxbcdt[..., :d_inner]
    x = zxbcdt[..., d_inner : 2 * d_inner]
    b_mat = zxbcdt[..., 2 * d_inner : 2 * d_inner + gn]
    c_mat = zxbcdt[..., 2 * d_inner + gn : 2 * d_inner + 2 * gn]
    dt = zxbcdt[..., 2 * d_inner + 2 * gn :]
    return z, x, b_mat, c_mat, dt


def _segsum(dA):
    """Lower-triangular pairwise decay sums: out[..., i, j] = sum dA[j+1..i]."""
    q = dA.shape[-1]
    cs = jnp.cumsum(dA, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]  # sum (j, i]
    mask = jnp.tril(jnp.ones((q, q), bool), 0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, a, b_mat, c_mat, chunk: int, init):
    """SSD scan. x: (B,L,H,P), dt: (B,L,H), a: (H,) (negative),
    b_mat/c_mat: (B,L,G,N) with H a multiple of G, ``init`` the state
    (B,H,N,P) entering the block. Returns (y, final_state)."""
    bsz, l, h, p = x.shape
    g, n = b_mat.shape[-2:]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    rep = h // g

    # chunked views
    xc = x.reshape(bsz, nc, chunk, h, p)
    dtc = dt.reshape(bsz, nc, chunk, h)
    bc = jnp.repeat(b_mat.reshape(bsz, nc, chunk, g, n), rep, axis=3)  # (B,NC,Q,H,N)
    cc = jnp.repeat(c_mat.reshape(bsz, nc, chunk, g, n), rep, axis=3)

    dA = dtc * a[None, None, None, :]  # (B,NC,Q,H) negative decay increments
    dA_cs = jnp.cumsum(dA, axis=2)
    dA_total = dA_cs[:, :, -1:, :]  # (B,NC,1,H)
    xdt = xc * dtc[..., None]

    # 1) intra-chunk (quadratic within the chunk)
    L = jnp.exp(_segsum(jnp.moveaxis(dA, 3, 2)))  # (B,NC,H,Q,Q) causal decay mask
    scores = jnp.einsum("bcqhn,bckhn->bchqk", cc, bc) * L
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", scores, xdt)

    # 2) per-chunk terminal states
    decay_states = jnp.exp(dA_total - dA_cs)  # (B,NC,Q,H)
    states = jnp.einsum("bcqhn,bcqhp->bchnp", bc * decay_states[..., None], xdt)

    # 3) inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(dA_total[:, :, 0, :])  # (B,NC,H)

    def scan_fn(carry, inp):
        st, dec = inp  # (B,H,N,P), (B,H)
        new = carry * dec[..., None, None] + st
        return new, carry  # emit state *entering* the chunk

    final_state, prev_states = jax.lax.scan(
        scan_fn, init, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)  # (B,NC,H,N,P)

    # 4) inter-chunk contribution
    state_decay = jnp.exp(dA_cs)  # (B,NC,Q,H)
    y_off = jnp.einsum("bcqhn,bchnp->bcqhp", cc * state_decay[..., None], prev_states)

    y = (y_diag + y_off).reshape(bsz, l, h, p)
    return y, final_state


def gated_norm(y, z, weight, groups: int, eps: float):
    """RMS norm of ``y * silu(z)`` over each of ``groups`` equal runs of
    channels, times ``weight`` (``n_groups`` 1: one norm over all)."""
    g = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    return rmsnorm(g, weight.reshape(groups, -1), eps).reshape(shape)


def _conv_window(window, clen, width: int):
    """The conv window after a block: the last ``width - 1`` valid rows of
    ``window``, the carried window and the block's inputs (rows at or past
    ``clen`` of the block are not inputs)."""
    if clen is None:
        return window[:, -(width - 1):]
    rows = jnp.reshape(clen, (-1, 1)) + jnp.arange(width - 1)[None, :]
    rows = jnp.broadcast_to(rows, (window.shape[0], width - 1))
    return jnp.take_along_axis(window, rows[..., None], axis=1)


def mamba2_forward(p, x, cfg: ModelConfig, ctx: EngineContext, *, name,
                   state=None, clen=None):
    """One mixer over ``x (B, L, d_model)``. Returns (out, new_state).

    * one token: the recurrent step (decode);
    * a block of L > 1 tokens: the chunked SSD form from the carried state
      (a prefill chunk; ``state`` None: the whole sequence from a zero
      state, train and forward). Rows at or past ``clen`` (scalar or
      (B,); None: every row is valid) get ``dt = 0`` and ``x = 0``, so they
      leave the state as it was, and the new conv window holds the last
      ``conv_width - 1`` valid inputs.

    state = {"conv": (B, W-1, conv_dim), "ssm": (B, H, N, P)}.

    Named scopes: ``mamba`` holds the mixer, ``mamba.conv`` the causal conv,
    ``mamba.scan`` the block form's SSD and ``mamba.step`` the recurrent
    step; the projections are dots (``dot.<backend>``).
    """
    s = cfg.ssm
    d_inner, n_heads = _dims(cfg)
    bsz, l, _ = x.shape
    gn = s.n_groups * s.state_dim
    if state is None:  # the whole sequence, from a zero state
        state = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype),
                             mamba_state_specs(cfg, bsz, x.dtype))
    step = l == 1

    with jax.named_scope("mamba"):
        zxbcdt = ctx.linear(x, p["in_proj"], name=f"{name}.in_proj")
        z, xs, b_mat, c_mat, dt = _split_proj(cfg, zxbcdt)
        conv_in = jnp.concatenate([xs, b_mat, c_mat], axis=-1)

        with jax.named_scope("mamba.conv"):
            if step:
                window = jnp.concatenate([state["conv"], conv_in], axis=1)
                conv_out = (jnp.einsum("bwc,wc->bc", window, p["conv_w"])[:, None, :]
                            + p["conv_b"][None, None, :])
                new_conv = window[:, 1:, :]
            else:
                window = jnp.concatenate(
                    [state["conv"], conv_in.astype(state["conv"].dtype)], axis=1)
                conv_out = sum(window[:, i:i + l, :] * p["conv_w"][i][None, None, :]
                               for i in range(s.conv_width)) + p["conv_b"][None, None, :]
                new_conv = _conv_window(window, clen, s.conv_width)
            conv_out = jax.nn.silu(conv_out)

        xs = conv_out[..., :d_inner]
        b_mat = conv_out[..., d_inner:d_inner + gn]
        c_mat = conv_out[..., d_inner + gn:]

        a = -jnp.exp(p["A_log"].astype(jnp.float32))  # (H,)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])  # (B,L,H)
        xh = xs.reshape(bsz, l, n_heads, s.head_dim)
        bm = b_mat.reshape(bsz, l, s.n_groups, s.state_dim).astype(jnp.float32)
        cm = c_mat.reshape(bsz, l, s.n_groups, s.state_dim).astype(jnp.float32)

        if not step:
            with jax.named_scope("mamba.scan"):
                xin = xh.astype(jnp.float32)
                if clen is not None:
                    valid = jnp.arange(l)[None, :] < jnp.reshape(clen, (-1, 1))
                    dt = jnp.where(valid[..., None], dt, 0.0)
                    xin = jnp.where(valid[..., None, None], xin, 0.0)
                y, ssm = ssd_chunked(xin, dt, a, bm, cm, min(s.chunk_size, l),
                                     state["ssm"].astype(jnp.float32))
        else:
            with jax.named_scope("mamba.step"):
                # h' = h * exp(dt A) + dt * B x ; y = C h' + D x
                rep = n_heads // s.n_groups
                bmh = jnp.repeat(bm[:, 0], rep, axis=1)  # (B,H,N)
                cmh = jnp.repeat(cm[:, 0], rep, axis=1)
                dt0 = dt[:, 0]  # (B,H)
                decay = jnp.exp(dt0 * a[None, :])  # (B,H)
                xdt = xh[:, 0].astype(jnp.float32) * dt0[..., None]  # (B,H,P)
                upd = jnp.einsum("bhn,bhp->bhnp", bmh, xdt)
                ssm = state["ssm"].astype(jnp.float32) * decay[..., None, None] + upd
                y = jnp.einsum("bhn,bhnp->bhp", cmh, ssm)[:, None]  # (B,1,H,P)
        new_state = {"conv": new_conv.astype(state["conv"].dtype),
                     "ssm": ssm.astype(state["ssm"].dtype)}

        y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
        y = y.reshape(bsz, l, d_inner).astype(x.dtype)
        y = gated_norm(y, z, p["norm"], s.n_groups, cfg.norm_eps)
        return ctx.linear(y, p["out_proj"], name=f"{name}.out_proj"), new_state


def mamba_state_specs(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    s = cfg.ssm
    d_inner, n_heads = _dims(cfg)
    conv_dim = d_inner + 2 * s.n_groups * s.state_dim
    return {
        "conv": jax.ShapeDtypeStruct((batch, s.conv_width - 1, conv_dim), dtype),
        "ssm": jax.ShapeDtypeStruct((batch, n_heads, s.state_dim, s.head_dim), dtype),
    }
