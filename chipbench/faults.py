"""Faults a serving cell can have, planted in the program's decode burst.

Each wraps ``BatchedServer.decode_burst`` (a method that returns the jitted
burst ``(tree, cache, state) -> (cache, state, tokens, margins, faults)``).
The check of ``correct`` has to read a run with either of them as not
correct: ``chipbench/test_chipbench_check.py`` plants them at a size a test
holds, ``chipbench/calibrate.py --fault <name>`` at the cell's own size.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def token_altered(real):
    """The burst's tokens altered where they are produced."""

    def burst(self, sampled=True):
        fn = real(self, sampled)

        def wrapped(tree, cache, state):
            cache, state, toks, margins, faults = fn(tree, cache, state)
            vocab = self.model.cfg.vocab_size
            return cache, state, (toks + 1) % vocab, margins, faults
        return wrapped
    return burst


def state_unchanged(real):
    """The burst returns the slot state it was given (each slot's position,
    last token and counts; the cache as the burst wrote it): every burst
    decodes again from where the first one started. Only the small state
    is copied, so the fault fits beside a full-size cache."""

    def burst(self, sampled=True):
        fn = real(self, sampled)

        def wrapped(tree, cache, state):
            kept = jax.tree.map(jnp.copy, state)
            cache, _, toks, margins, faults = fn(tree, cache, state)
            return cache, kept, toks, margins, faults
        return wrapped
    return burst


FAULTS = {"token_altered": token_altered, "state_unchanged": state_unchanged}


def plant(name: str) -> None:
    """Wrap the program's decode burst with the fault ``name``."""
    from repro.serve.engine import BatchedServer

    BatchedServer.decode_burst = FAULTS[name](BatchedServer.decode_burst)
