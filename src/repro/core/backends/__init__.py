"""Backend registry + the one-time ``prepare_params`` pass (quantize once, serve fast).

The engine's four execution modes (``exact`` / ``carmen`` / ``int8`` /
``kernel``) are registered :class:`~repro.core.backends.base.Backend` objects.
``EngineContext.dot`` resolves the backend per call — from the weight leaf
itself when it is a :class:`PreparedWeight` (the prepared bank pins its own
execution path), from the context mode otherwise.

``prepare_params`` is the weight-bank lifecycle step: walk a model's param
tree once, materialize each ctx-routed matmul weight in its backend's serving
format, and return a tree the unchanged model code consumes through
``ctx.linear``. Training (QAT) keeps raw float trees — the traced per-call
path; inference prepares once and then performs zero weight-side rounding or
scale computation per forward.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

import jax

from ..precision_policy import PrecisionPolicy
from .base import Backend, PreparedWeight, unit_fmt
from .carmen import CarmenBackend, carmen_dot, sd_round_traced
from .exact import ExactBackend
from .int8 import Int8Backend, effective_bits, int8_dot, quantize_weight
from .kernel import KernelBackend

__all__ = [
    "Backend", "PreparedWeight", "get_backend", "register", "resolve",
    "prepare_params", "iter_dot_weights", "carmen_dot", "int8_dot",
    "sd_round_traced", "effective_bits", "quantize_weight", "unit_fmt",
]

_REGISTRY: Dict[str, Backend] = {}


def register(backend: Backend) -> Backend:
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown engine mode {name!r}") from None


def resolve(w, mode: str) -> Backend:
    """Backend for one dot: the prepared leaf's own backend wins, else the mode."""
    if isinstance(w, PreparedWeight) and w.backend != "exact":
        return get_backend(w.backend)
    return get_backend(mode)


for _b in (ExactBackend(), CarmenBackend(), Int8Backend(), KernelBackend()):
    register(_b)


# ---------------------------------------------------------------------------
# prepare_params: walk a model param tree, materialize per-layer weight banks
# ---------------------------------------------------------------------------

# basenames of weight leaves that reach EngineContext.dot (everything else —
# norms, biases, conv filters, routers, MoE expert stacks, embeddings — stays
# float: criticality-pinned or consumed outside the engine)
_DOT_WEIGHT_NAMES = frozenset({
    "wq", "wk", "wv", "wo", "up", "gate", "down",
    "in_proj", "out_proj", "wq_a", "wq_b", "wkv_a", "lm_head", "linear",
})

# param-tree key -> dot-time layer-name component (policy lookup only)
_KEY_RENAMES = {
    "wq": "q", "wk": "k", "wv": "v", "wo": "o",
    "wq_a": "q_a", "wq_b": "q_b", "wkv_a": "kv_a",
    "self_attn": "self", "cross_attn": "cross",
    "enc_layers": "enc", "dec_layers": "dec",
}

_SEG_RE = re.compile(r"^seg\d+_(\w+)$")


def _path_keys(path):
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def _eligible(keys) -> bool:
    if not keys or keys[-1] not in _DOT_WEIGHT_NAMES:
        return False
    if len(keys) >= 2 and keys[-2] == "moe":
        return False  # expert stacks + router run as einsums, not engine dots
    return True


def _policy_name(keys) -> str:
    out = []
    for k in keys:
        if _SEG_RE.match(k):
            out.append("layer")
        else:
            out.append(_KEY_RENAMES.get(k, k))
    return ".".join(out)


def _stacked_axes(keys, spec) -> int:
    if spec is not None:
        n = 0
        for ax in spec.axes:
            if ax == "layers":
                n += 1
            else:
                break
        return n
    if keys and (_SEG_RE.match(keys[0]) or keys[0] in ("enc_layers", "dec_layers",
                                                       "shared")):
        return 1
    return 0


def _spec_index(specs):
    """path-keys tuple -> ParamSpec for stacked-axis identification."""
    if specs is None:
        return {}
    from repro.models.params import is_spec

    flat_specs, _ = jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_spec)
    return {tuple(_path_keys(p)): s for p, s in flat_specs}


def _classify(keys, leaf, spec):
    """(policy_name, stacked_axes, in_axes) of an engine-routed matmul weight,
    or None when the leaf never reaches ``EngineContext.dot``."""
    if not _eligible(keys) or not hasattr(leaf, "ndim"):
        return None
    stacked = _stacked_axes(keys, spec)
    if leaf.ndim - stacked < 2:
        return None
    # contraction axes of the dot-time 2D view: weights are (in..., out...)
    # with a single input axis everywhere except wo, whose leading
    # (heads, head_dim) axes fold into the contraction
    in_axes = leaf.ndim - stacked - 1 if keys[-1] == "wo" else 1
    return _policy_name(keys), stacked, in_axes


def iter_dot_weights(params, *, specs=None):
    """Yield ``(keys, policy_name, leaf, stacked_axes, in_axes)`` for every
    weight leaf in ``params`` that reaches ``EngineContext.dot``.

    The single source of truth for "which leaves does the engine multiply":
    ``prepare_params`` formats exactly these leaves, the runtime cycle model
    (``repro.runtime.telemetry``) costs exactly these leaves, and the serving
    calibration scan (``repro.runtime.calibrate``) perturbs exactly these
    layer names. The tied-embedding lm_head is synthesized by callers (it has
    no leaf of its own in a tied tree — except in prepared trees, where the
    materialized head leaf IS yielded).

    Works on raw and prepared trees alike: :class:`PreparedWeight` nodes are
    treated as leaves (not descended into data/scale children).
    """
    spec_of = _spec_index(specs)
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, PreparedWeight)
    )
    for path, leaf in flat:
        keys = _path_keys(path)
        info = _classify(keys, leaf, spec_of.get(tuple(keys)))
        if info is not None:
            name, stacked, in_axes = info
            yield keys, name, leaf, stacked, in_axes


def prepare_params(params, policy: Optional[PrecisionPolicy], mode: str, *,
                   specs=None, memo: Optional[Dict] = None):
    """Materialize per-layer prepared weight banks for serving.

    Walks ``params`` and replaces every engine-routed matmul weight with the
    ``mode`` backend's prepared form at the policy's per-layer (fmt, depth):
    signed-digit grids for ``carmen``/``kernel``, int8 qvalues + per-channel
    scales for ``int8``, pass-through for ``exact``. Leaves shared across
    calls are prepared once per (tensor, execution point).

    ``specs`` (the model's ``ParamSpec`` tree, ``model.specs()``) identifies
    stacked layer banks so int8 scales keep their per-layer leading axis and
    slice alongside the qvalues inside ``lax.scan``; without it a naming
    heuristic over the segment keys is used.

    Tied-embedding models get an explicit prepared ``lm_head`` entry (the
    transposed embedding), so decoding never re-quantizes the output head;
    the embedding itself stays float for the table lookup.

    ``memo`` is an optional cross-call cache keyed by (tensor identity, mode,
    execution point, stacked axes). Passing the same dict across several calls
    makes the prepared trees SHARE leaves wherever the execution point agrees
    — how the multi-point weight bank (``repro.runtime.bank``) keeps pinned
    layers single-copy across its modes.
    """
    backend = get_backend(mode)
    if mode == "exact":
        return params
    policy = policy or PrecisionPolicy.accurate()

    spec_of = _spec_index(specs)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    if memo is None:
        memo = {}
    out = []
    for path, leaf in flat:
        keys = _path_keys(path)
        info = _classify(keys, leaf, spec_of.get(tuple(keys)))
        if isinstance(leaf, PreparedWeight) or info is None:
            out.append(leaf)
            continue
        name, stacked, in_axes = info
        lp = policy.for_layer(name)
        key = (id(leaf), mode, lp, stacked)
        if key not in memo:
            memo[key] = backend.prepare(leaf, lp, stacked_axes=stacked, in_axes=in_axes)
        out.append(memo[key])
    prepared = jax.tree_util.tree_unflatten(treedef, out)

    if isinstance(prepared, dict) and "lm_head" not in prepared and "embed" in prepared:
        embed = params["embed"]
        if hasattr(embed, "ndim") and embed.ndim == 2 and not isinstance(embed, PreparedWeight):
            lp = policy.for_layer("lm_head")
            key = (id(embed), "lm_head.T", mode, lp)
            if key not in memo:
                memo[key] = backend.prepare(embed.T, lp, stacked_axes=0)
            prepared = dict(prepared)
            prepared["lm_head"] = memo[key]
    return prepared
