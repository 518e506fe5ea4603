"""The comparison that decides ``correct``: served tokens against the plain
reference.

After the window has closed and the server is freed, a sample of the
requests that finished (drawn from the seed, the longest always in it) is run
through the configuration's plain reference, once per request, over its
prompt and its served tokens. At each served position the reference's logits
give the gap by which the served token lies below the reference's best token
(0 when the reference would have chosen it too). The widest gap over the
sample is the number compared. Only greedy tokens are checked; every mix
serves greedy requests.

The control puts the reference itself in the program's place, with its
activations at 4 bits (``control=True``, see the reference), and reads, at
the same positions, the gap of the token that it ranks first.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

PAD = 512  # sequences are padded to a multiple of this: few reference programs


def sample(finished: Sequence[Tuple[np.ndarray, List[int]]], seed: int,
           k: int) -> List[int]:
    """Indices into ``finished``: the longest request, then ``k - 1`` more
    drawn from the seed."""
    if not finished:
        return []
    sizes = [len(p) + len(t) for p, t in finished]
    longest = int(np.argmax(sizes))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = np.random.default_rng([seed, 7])
    pick = rng.permutation(rest)[: max(0, k - 1)]
    return [longest] + [int(i) for i in pick]


def served_gaps(ref_logits, prompt: np.ndarray, served: Sequence[int], cfg,
                weights, *, control=False) -> Dict[str, np.ndarray]:
    """Per served position: the gap of the served token under the reference
    and, with ``control``, the gap of the control's first choice."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    n = len(seq)
    padded = np.zeros((-(-n // PAD) * PAD,), np.int32)
    padded[:n] = seq
    tokens = jnp.asarray(padded)
    # read back at the padded shape and index on the host: no program is
    # compiled for each request's own length
    rows = slice(len(prompt) - 1, n)
    at = np.arange(len(served))
    ref = np.asarray(ref_logits(weights, tokens, cfg))[rows]
    best = ref.max(-1)
    out = {"served": best - ref[at, served]}
    if control:
        ctrl = np.asarray(ref_logits(weights, tokens, cfg, control=True))[rows]
        out["control"] = best - ref[at, ctrl.argmax(-1)]
    return out
