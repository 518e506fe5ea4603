"""Seeded traffic: request sizes and token ids.

A traffic mix is a JSON file of parameters (``chipbench/traffic/<name>.json``)
that this one generator reads. Sizes are stratified: a mix of ``n``
requests takes the ``n`` quantiles ``(i + 0.5) / n`` of each declared
distribution. They are dealt into blocks of ``block`` consecutive
requests, each block one length from each of ``block`` equal strata, the
same for every seed; the seed only orders the lengths within each block and
draws the token ids. So every seed offers the same lengths in the same
stretch of the pool, in another order, and a window of some tens of
requests holds the same work whatever the seed: seeds differ in what the
work is arranged like, not in how much of it there is.

The loop is closed: ``concurrency`` clients; each sends its next request
when its previous one has finished. The requests are taken in order from a
pool of ``pool`` requests (wrapping round).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request as the generator makes it."""

    prompt: np.ndarray  # int32 token ids
    max_new: int


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, rounded to
    whole tokens and clipped to ``[min, max]``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        v = np.floor(v)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def blocks(values: np.ndarray, block: int, stream: int) -> np.ndarray:
    """``values`` dealt into ``(len(values) // block, block)``: row ``k`` takes
    one value of each stratum (the sorted values cut into ``block`` equal
    runs), picked by a generator that no seed changes."""
    strata = np.sort(values).reshape(block, -1)
    fixed = _rng(0, stream)
    return np.stack([fixed.permutation(row) for row in strata], axis=1)


def _requests(mix: Dict, n: int, seed: int, stream: int,
              vocab: int) -> List[Spec]:
    rng = _rng(seed, stream)
    b = mix["block"]
    plen = rng.permuted(blocks(quantiles(mix["prompt"], n), b, stream),
                        axis=1).ravel()
    olen = rng.permuted(blocks(quantiles(mix["output"], n), b, stream + 100),
                        axis=1).ravel()
    return [Spec(rng.integers(0, vocab, int(p), dtype=np.int64).astype(np.int32),
                 int(o))
            for p, o in zip(plen, olen)]


def closed_pool(mix: Dict, seed: int, vocab: int) -> List[Spec]:
    """The closed loop's requests, in the order the clients take them."""
    return _requests(mix, mix["pool"], seed, 1, vocab)


def max_len(mix: Dict) -> int:
    """Cache rows a slot needs: the longest prompt and output, plus two."""
    return int(mix["prompt"]["max"] + mix["output"]["max"] + 2)


def validate(mix: Dict) -> None:
    """Reject a mix the generator cannot make."""
    for key in ("prompt", "output"):
        d = mix[key]
        if not 1 <= d["min"] <= d["max"]:
            raise ValueError(f"{key}: want 1 <= min <= max, got {d}")
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}; known: closed")
    if not 1 <= mix["slots"] <= mix["concurrency"]:
        raise ValueError(f"want 1 <= slots <= concurrency, got {mix}")
    if mix["block"] < 1 or mix["pool"] % mix["block"]:
        raise ValueError(f"want a pool of whole blocks, got {mix}")
