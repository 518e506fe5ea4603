"""Direct unit tests for the KV-cache index helpers (serve/kvcache.py).

These contracts were only covered transitively through the burst / spec e2e
suites; here each helper is exercised on its own:

* ``cache_positions`` / ``with_cache_positions`` — the write-index rewind
  that bucketed prefill and speculative rollback share;
* ``scatter_rows`` — slot insertion of a single-row cache, eager and traced;
* scratch-region invisibility — rows at positions >= the write index are
  dead: poisoning them cannot change the next decode's logits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import EngineContext
from repro.models import get_model
from repro.serve.kvcache import (
    bucket_length,
    cache_positions,
    scatter_rows,
    with_cache_positions,
)

EXACT = EngineContext(mode="exact", compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def olmo():
    cfg = reduced(get_config("olmo-1b"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _decode_n(model, params, cache, tokens):
    """Feed ``tokens`` one at a time; returns (last_logits, cache)."""
    logits = None
    for t in tokens:
        logits, cache = model.decode_step(
            params, jnp.array([[t]], jnp.int32), cache, EXACT
        )
    return logits, cache


# ---------------------------------------------------------------------------
# write-index read / rewind
# ---------------------------------------------------------------------------


def test_cache_positions_roundtrip(olmo):
    cfg, model, params = olmo
    cache = model.make_cache(2, 16, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(cache_positions(cache)), [0, 0])
    cache = with_cache_positions(cache, jnp.array([3, 7], jnp.int32))
    np.testing.assert_array_equal(np.asarray(cache_positions(cache)), [3, 7])
    # every layer's index row rewrote, not just layer 0
    for leaf in jax.tree.leaves(cache):
        if jnp.issubdtype(leaf.dtype, jnp.integer) and leaf.ndim >= 2:
            np.testing.assert_array_equal(
                np.asarray(leaf), np.broadcast_to([3, 7], leaf.shape)
            )


def test_cache_positions_advance_with_decode(olmo):
    cfg, model, params = olmo
    cache = model.make_cache(1, 16, dtype=jnp.float32)
    _, cache = _decode_n(model, params, cache, [5, 17, 3])
    np.testing.assert_array_equal(np.asarray(cache_positions(cache)), [3])


def test_cache_positions_raises_on_recurrent():
    cfg = reduced(get_config("mamba2-780m"))
    model = get_model(cfg)
    cache = model.make_cache(1, 16, dtype=jnp.float32)
    with pytest.raises(ValueError, match="no write index"):
        cache_positions(cache)


def test_rewind_replays_identically(olmo):
    """Rewinding the index to k and re-decoding the same suffix reproduces
    the original logits — the rewound rows are overwritten before they can
    become visible."""
    cfg, model, params = olmo
    cache = model.make_cache(1, 16, dtype=jnp.float32)
    _, cache = _decode_n(model, params, cache, [5, 17])
    want, full = _decode_n(model, params, cache, [3, 9])
    rewound = with_cache_positions(full, jnp.array([2], jnp.int32))
    got, _ = _decode_n(model, params, rewound, [3, 9])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# scratch-region invisibility
# ---------------------------------------------------------------------------


def test_scratch_rows_invisible(olmo):
    """Poisoning every row at positions >= the write index does not change
    the next decode step — the per-query-causal mask plus the
    write-at-index discipline make that region pure scratch."""
    cfg, model, params = olmo
    cache = model.make_cache(1, 16, dtype=jnp.float32)
    _, cache = _decode_n(model, params, cache, [5, 17, 3])
    idx = int(np.asarray(cache_positions(cache))[0])

    def poison(leaf):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            return leaf
        # row leaves are (L, B, S, ...): blast positions >= idx along S
        mask = (jnp.arange(leaf.shape[2]) >= idx).reshape(
            (1, 1, -1) + (1,) * (leaf.ndim - 3)
        )
        return jnp.where(mask, jnp.float32(1e9), leaf)

    poisoned = jax.tree.map(poison, cache)
    want, _ = _decode_n(model, params, cache, [9])
    got, _ = _decode_n(model, params, poisoned, [9])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# slot scatter
# ---------------------------------------------------------------------------


def test_scatter_rows_writes_one_slot(olmo):
    cfg, model, params = olmo
    full = model.make_cache(3, 8, dtype=jnp.float32)
    row = model.make_cache(1, 8, dtype=jnp.float32)
    row = jax.tree.map(lambda l: l + 1, row)
    out = scatter_rows(full, row, jnp.int32(1))
    for dst, src, new in zip(
        jax.tree.leaves(full), jax.tree.leaves(row), jax.tree.leaves(out)
    ):
        new = np.asarray(new)
        np.testing.assert_array_equal(new[:, 1], np.asarray(src)[:, 0])
        np.testing.assert_array_equal(new[:, 0], np.asarray(dst)[:, 0])
        np.testing.assert_array_equal(new[:, 2], np.asarray(dst)[:, 2])


def test_scatter_rows_whole_cache_when_single_slot(olmo):
    cfg, model, params = olmo
    full = model.make_cache(1, 8, dtype=jnp.float32)
    row = jax.tree.map(lambda l: l + 2, model.make_cache(1, 8, dtype=jnp.float32))
    out = scatter_rows(full, row, jnp.int32(0))
    for src, new in zip(jax.tree.leaves(row), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(src))


def test_scatter_rows_under_jit_with_traced_slot(olmo):
    cfg, model, params = olmo
    full = model.make_cache(4, 8, dtype=jnp.float32)
    row = jax.tree.map(lambda l: l + 3, model.make_cache(1, 8, dtype=jnp.float32))
    eager = scatter_rows(full, row, jnp.int32(2))
    jitted = jax.jit(scatter_rows)(full, row, jnp.int32(2))
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bucket_length_properties():
    for plen in range(1, 70):
        b = bucket_length(plen, 64)
        assert b >= min(plen, 64) and b <= 64
        assert b & (b - 1) == 0 or b == 64  # pow2 unless clamped


# ---------------------------------------------------------------------------
# carried stacked cache against a per-layer loop
# ---------------------------------------------------------------------------


def _gqa_config():
    """qwen3-8b reduced keeps 4 KV heads for 4 query heads: give it 2."""
    import dataclasses

    return dataclasses.replace(reduced(get_config("qwen3-8b")), num_kv_heads=2)


@pytest.fixture(scope="module", params=["olmo-1b", "qwen3-gqa", "llama4-pair"])
def family(request):
    cfg = {
        "olmo-1b": lambda: reduced(get_config("olmo-1b")),
        "qwen3-gqa": _gqa_config,
        "llama4-pair": lambda: reduced(get_config("llama4-maverick-400b-a17b")),
    }[request.param]()
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(1))


def _layer_loop_decode(params, tokens, cache, cfg):
    """Reference decode: each segment's layers as a Python loop, each layer
    handed its own slice ``cache[l]`` and writing it with
    ``blocks.cache_row_write``; the slices are stacked back afterwards."""
    from repro.models import transformer as T

    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    index = T._cache_index(cache)
    positions = index[:, None] + jnp.arange(tokens.shape[1])[None, :]
    out = {}
    for i, (kind, n) in enumerate(T._segments(cfg)):
        key = f"seg{i}_{kind}"
        new = []
        for layer in range(n):
            p = jax.tree.map(lambda a: a[layer], params[key])
            c = jax.tree.map(lambda a: a[layer], cache[key])
            if kind == "pair":
                h, c_d, _ = T._dense_layer(p["dense"], h, cfg, EXACT, positions,
                                           c["dense"])
                h, c_m, _ = T._moe_layer(p["moe"], h, cfg, EXACT, positions,
                                         c["moe"])
                c = {"dense": c_d, "moe": c_m}
            else:
                layer_fn = T._moe_layer if kind == "moe" else T._dense_layer
                h, c, _ = layer_fn(p, h, cfg, EXACT, positions, c)
            new.append(c)
        out[key] = jax.tree.map(lambda *a: jnp.stack(a), *new)
    return T._lm_head(params, h, cfg, EXACT), out


def _filled_cache(model, cfg, slots, max_len, index):
    """A cache of random K/V rows with per-slot write indices ``index``."""
    cache = model.make_cache(slots, max_len, dtype=jnp.float32)
    leaves, treedef = jax.tree.flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(7), len(leaves))
    leaves = [leaf if jnp.issubdtype(leaf.dtype, jnp.integer)
              else jax.random.normal(k, leaf.shape, leaf.dtype)
              for k, leaf in zip(keys, leaves)]
    cache = jax.tree.unflatten(treedef, leaves)
    return with_cache_positions(cache, jnp.asarray(index, jnp.int32))


@pytest.mark.parametrize(
    "s, index",
    [(1, [0, 5, 11]), (1, [15, 16, 20]), (4, [0, 3, 9]), (3, [13, 15, 16])],
    ids=["one_token_mixed", "one_token_clamped_end", "block", "clamped_end"],
)
def test_carried_cache_matches_layer_loop(family, s, index):
    """Decode gives the logits and the whole returned cache (k, v, index) of
    a per-layer loop over ``cache_row_write`` on ``cache[l]``, bit for bit:
    single-token steps, which carry the stacked cache through the layer
    scan, at mixed per-slot indices and at the cache's end (index
    ``max_len - 1`` and beyond, where the write clamps), and S > 1 blocks
    (speculative verify, a prefill chunk), which scan it as xs, in the
    middle and clamped at the end (index ``max_len - S`` and beyond)."""
    cfg, model, params = family
    max_len = 16
    cache = _filled_cache(model, cfg, len(index), max_len, index)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (len(index), s), 0,
                                cfg.vocab_size, jnp.int32)
    got_logits, got = jax.jit(
        lambda p, t, c: model.decode_step(p, t, c, EXACT))(params, tokens, cache)
    want_logits, want = jax.jit(
        lambda p, t, c: _layer_loop_decode(p, t, c, cfg))(params, tokens, cache)
    np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the index advances by S even where the rows were clamped
    np.testing.assert_array_equal(np.asarray(cache_positions(got)),
                                  np.asarray(index) + s)
