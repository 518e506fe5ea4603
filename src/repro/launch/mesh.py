"""Production mesh construction (spec'd shapes: 16x16 single-pod, 2x16x16 multi-pod).

A FUNCTION, not a module-level constant, so importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).

Every mesh here has ``Auto`` axes: the repo places arrays with
``NamedSharding`` and lets GSPMD propagate the rest (``partition.constrain``
pins activation layouts), which ``Explicit`` axes — ``jax.make_mesh``'s
default — do not allow.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A device mesh with ``Auto`` axes (see the module docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, model: Optional[int] = None):
    """(data, model) mesh over whatever devices exist locally.

    ``model=`` fixes the tensor-parallel extent (it must divide the local
    device count). By default the device count is factored into the most
    square (data, model) split with ``model <= data`` — 1 device -> 1x1,
    4 -> 2x2, 8 -> 4x2 — so local multi-device runs (e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``) exercise tensor
    parallelism, not just data parallelism. ``model=1`` recovers the old
    pure-DP (n, 1) shape.
    """
    n = len(jax.devices())
    if model is None:
        model = max(d for d in range(1, n + 1) if n % d == 0 and d * d <= n)
    if model < 1 or n % model:
        raise ValueError(
            f"model={model} does not divide the {n} local devices"
        )
    return make_mesh((n // model, model), ("data", "model"))
