"""Serving-side §III sensitivity scan: calibrate a policy at server startup.

The JVP-based :func:`repro.core.precision_policy.sensitivity_scan` needs a
per-layer noise-injection hook that the big transformer families do not
expose. For serving we measure the same quantity the direct way: demote one
engine dot *group* (all stacked layers of e.g. ``layer.mlp.up`` share a
policy name) to approximate depth, run the calibration batch, and record the
normalized logit perturbation. One forward per group — a handful of forwards
on a calibration batch — and the resulting sensitivities feed
``assign_depths`` exactly like the JVP scan does.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.core import EngineContext
from repro.core.backends import iter_dot_weights
from repro.core.cordic import approx_depth, full_depth
from repro.core.fxp import FXP8, FxPFormat
from repro.core.precision_policy import LayerPrecision, PrecisionPolicy

__all__ = ["calibration_scan"]


def calibration_scan(
    model,
    params,
    tokens,
    *,
    fmt: FxPFormat = FXP8,
    mode: str = "carmen",
) -> Dict[str, float]:
    """name -> normalized logit perturbation when that group runs approximate.

    ``tokens``: (B, S) int32 calibration batch. Uses the per-call engine path
    (no prepare needed — this runs once at startup, before the bank is built).
    """
    names = sorted({name for _, name, _, _, _ in iter_dot_weights(params, specs=model.specs())})
    if isinstance(params, dict) and "lm_head" not in params and "embed" in params:
        names.append("lm_head")
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}

    def logits_at(policy: PrecisionPolicy) -> np.ndarray:
        ctx = EngineContext(mode=mode, policy=policy,
                            compute_dtype=model.cfg.compute_dtype)
        out, _ = model.forward(params, batch, ctx)
        return np.asarray(out, np.float32)

    accurate = LayerPrecision(fmt, full_depth(fmt))
    base = logits_at(PrecisionPolicy(accurate))
    base_norm = float(np.linalg.norm(base)) + 1e-9

    sens: Dict[str, float] = {}
    demoted = LayerPrecision(fmt, approx_depth(fmt))
    for name in names:
        perturbed = logits_at(PrecisionPolicy(accurate, {name: demoted}))
        sens[name] = float(np.linalg.norm(perturbed - base)) / base_norm
    return sens
