"""Seeded model weights, made on the device in one jitted call.

The tree has the structure and shapes of the served model's own parameter
tree (read with ``jax.eval_shape``, which allocates nothing); the values are
the benchmark's. Every leaf is drawn from a normal distribution and rounded
to the weight grid that the configuration states, so the program and the
plain reference serve the same model: the rounding of a float checkpoint to
the format is done here, once, and is not a property of the serving path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def snap_to_grid(w, frac: int, qmax: int):
    """Nearest odd multiple of ``2**-frac`` within ``[-qmax, qmax] * 2**-frac``:
    the values a signed-digit weight of ``frac + 1`` digits can take."""
    z = w * (1 << frac)
    odd = 2.0 * jnp.floor(z / 2.0) + 1.0
    return jnp.clip(odd, -qmax, qmax) / (1 << frac)


def make_weights(model, seed: int, *, std: float, grid_frac: int,
                 grid_qmax: int, std_by_leaf=None):
    """The model's float32 parameter tree, drawn from ``seed`` on the device.

    ``std_by_leaf`` gives some leaves, by the last key of their path, a
    standard deviation of their own."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    stds = [(std_by_leaf or {}).get(str(getattr(p[-1], "key", p[-1])), std)
            for p, _ in paths]

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(paths))
        out = [snap_to_grid(sd * jax.random.normal(k, s.shape, jnp.float32),
                            grid_frac, grid_qmax)
               for k, (_, s), sd in zip(keys, paths, stds)]
        return jax.tree.unflatten(treedef, out)

    return draw(seed_key(seed))
