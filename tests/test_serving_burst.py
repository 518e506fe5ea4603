"""Decode-burst serving semantics.

Bursts are a pure scheduling change — one jitted scan over up to ``burst``
single-token steps with device-resident slot state — so every observable
contract of per-token serving must survive them bit-for-bit:

* greedy output is bit-identical to ``burst=1`` across every scatterable
  family (dense / vlm / moe / mla) AND the recurrent scan-prefill families;
* sampled streams depend only on (seed, token index) — never on burst size
  or batch composition (the PRNG folds by generated-token count);
* ``max_new`` is exact even when a request finishes mid-burst (emitted
  tokens past the budget are clipped on the host);
* bucketed prefill compiles O(log max_len) programs, not one per distinct
  prompt length;
* the whole point: host round-trips shrink by the burst factor.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import EngineContext, FXP16, PrecisionPolicy
from repro.models import get_model
from repro.serve.engine import BatchedServer, Request
from repro.serve.kvcache import bucket_length

EXACT = EngineContext(mode="exact", compute_dtype=jnp.float32)


def _setup(arch):
    cfg = reduced(get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, *, max_new=6, temperature=0.0, seed_base=None):
    rng = np.random.default_rng(0)
    return [
        Request(i, rng.integers(0, cfg.vocab_size, 3 + i).astype(np.int32),
                max_new, temperature=temperature,
                seed=None if seed_base is None else seed_base + i)
        for i in range(n)
    ]


@pytest.fixture(scope="module")
def olmo():
    return _setup("olmo-1b")


# ---------------------------------------------------------------------------
# greedy bit-identity across burst sizes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["olmo-1b", "internvl2-2b",
                                  "llama4-maverick-400b-a17b",
                                  "deepseek-v3-671b"])
def test_burst_greedy_bit_identical_to_per_token(arch):
    """dense / vlm / moe / mla: burst=4 output == burst=1 output, token for
    token, including margins (same compiled step math, fewer round-trips)."""
    cfg, model, params = _setup(arch)
    reqs1 = _requests(cfg, 3)
    ref = BatchedServer(model, EXACT, params, slots=2, max_len=32,
                        burst=1).run(reqs1)
    reqs4 = _requests(cfg, 3)
    out = BatchedServer(model, EXACT, params, slots=2, max_len=32,
                        burst=4).run(reqs4)
    assert out == ref
    for a, b in zip(reqs1, reqs4):
        np.testing.assert_allclose(a.margins, b.margins, atol=1e-5)


@pytest.mark.parametrize("arch", ["mamba2-780m", "zamba2-7b"])
def test_recurrent_scan_prefill_burst_identical(arch):
    """ssm / hybrid: the block prefill (mixers from the carried state,
    masked past the prompt) + burst decode match burst=1."""
    cfg, model, params = _setup(arch)
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=1)
    # these prefill a block, but their state cannot be rewound
    assert server.block_prefill and not server.batched_prefill
    ref = server.run(_requests(cfg, 3))
    out = BatchedServer(model, EXACT, params, slots=2, max_len=32,
                        burst=4).run(_requests(cfg, 3))
    assert out == ref


def test_burst_matches_dedicated_sequential_decode(olmo):
    """Burst serving with padded bucketed prefill reproduces a hand-rolled
    single-sequence decode loop exactly (the seed's ground truth)."""
    cfg, model, params = olmo
    prompt = np.array([5, 17, 3], np.int32)
    out = BatchedServer(model, EXACT, params, slots=2, max_len=32,
                        burst=8).run([Request(0, prompt, 5)])
    cache = model.make_cache(1, 32, dtype=jnp.float32)
    tok = None
    for t in prompt:
        lg, cache = model.decode_step(params, jnp.array([[t]]), cache, EXACT)
        tok = int(np.asarray(lg[0, 0]).argmax())
    gen = [tok]
    for _ in range(4):
        lg, cache = model.decode_step(params, jnp.array([[gen[-1]]]), cache, EXACT)
        gen.append(int(np.asarray(lg[0, 0]).argmax()))
    assert out[0] == gen


def test_pinned_adaptive_burst_identical_to_static(olmo):
    """The adaptive machinery at a fixed execution point composes with
    bursts: burst=8 through the bank == static burst=1 serving."""
    from repro.runtime import ControllerConfig, ModeController, build_bank, default_points

    cfg, model, params = olmo
    ctx = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP16),
                        compute_dtype=jnp.float32)
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    want = BatchedServer(model, ctx, bank.tree("accurate"), slots=2, max_len=32,
                         burst=1, prepare_weights=False).run(_requests(cfg, 4))
    ctrl = ModeController(bank, ControllerConfig(pin="accurate"))
    srv = BatchedServer(model, ctx, params, slots=2, max_len=32, burst=8,
                        controller=ctrl)
    assert srv.run(_requests(cfg, 4)) == want
    tele = srv.telemetry.summary()
    assert tele["decode_steps"] == tele["steps"] * 8  # one observation/burst


# ---------------------------------------------------------------------------
# sampled streams: burst- and schedule-independent
# ---------------------------------------------------------------------------


def test_sampled_streams_independent_of_burst_size(olmo):
    cfg, model, params = olmo
    serve = lambda burst: BatchedServer(
        model, EXACT, params, slots=2, max_len=32, burst=burst,
    ).run(_requests(cfg, 3, max_new=8, temperature=1.3, seed_base=40))
    a, b = serve(1), serve(8)
    assert a == b
    # sanity: high temperature actually diverges from greedy
    greedy = BatchedServer(model, EXACT, params, slots=2, max_len=32,
                           burst=8).run(_requests(cfg, 3, max_new=8))
    assert a != greedy


def test_sampled_streams_independent_of_batch_composition(olmo):
    """Request 0's stream is the same served alone or alongside others, at
    any burst size — keys fold by token index, not by schedule."""
    cfg, model, params = olmo
    reqs = _requests(cfg, 3, max_new=8, temperature=1.3, seed_base=7)
    together = BatchedServer(model, EXACT, params, slots=2, max_len=32,
                             burst=8).run(reqs)
    alone = BatchedServer(model, EXACT, params, slots=1, max_len=32,
                          burst=4).run(_requests(cfg, 1, max_new=8,
                                                 temperature=1.3, seed_base=7))
    assert together[0] == alone[0]


# ---------------------------------------------------------------------------
# budget clipping + transfer accounting
# ---------------------------------------------------------------------------


def test_mid_burst_max_new_clipping(olmo):
    """max_new that is not a multiple of burst is exact: tokens computed past
    the budget inside the final burst are discarded on the host."""
    cfg, model, params = olmo
    for max_new in (1, 3, 9, 12):
        out = BatchedServer(model, EXACT, params, slots=2, max_len=40,
                            burst=8).run(_requests(cfg, 2, max_new=max_new))
        assert all(len(v) == max_new for v in out.values())
        ref = BatchedServer(model, EXACT, params, slots=2, max_len=40,
                            burst=1).run(_requests(cfg, 2, max_new=max_new))
        assert out == ref


def test_rejects_requests_exceeding_cache_rows(olmo):
    """prompt + max_new beyond max_len is rejected up front — the KV write
    index would clamp onto the last row mid-decode and corrupt output.

    This is the legacy (resilience=None) fail-stop contract; with a
    ResilienceConfig the same request is shed with reason ``too_long``
    instead (tests/test_resilience.py)."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=16, burst=8)
    with pytest.raises(ValueError, match="exceeds max_len"):
        server.run([Request(0, np.arange(12, dtype=np.int32) % cfg.vocab_size, 8)])
    with pytest.raises(ValueError, match="exceeds max_len"):  # prompt alone too long
        server.run([Request(0, np.arange(20, dtype=np.int32) % cfg.vocab_size, 1)])


def test_oversized_request_shed_when_resilient(olmo):
    """Same oversized request, resilient server: shed with a structured
    reason, batch unharmed, nothing raises."""
    from repro.resilience import ResilienceConfig

    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=16, burst=8,
                           resilience=ResilienceConfig())
    ok = Request(1, np.arange(3, dtype=np.int32) % cfg.vocab_size, 4)
    out = server.run(
        [Request(0, np.arange(12, dtype=np.int32) % cfg.vocab_size, 8), ok])
    assert server.outcomes[0].status == "shed"
    assert server.outcomes[0].reason == "too_long"
    assert 0 not in out and len(out[1]) == 4


def test_host_transfers_shrink_with_burst(olmo):
    cfg, model, params = olmo
    counts = {}
    for burst in (1, 8):
        srv = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=burst)
        srv.run(_requests(cfg, 2, max_new=8))
        counts[burst] = srv.host_transfers
    # 2 prefills either way; decode rounds collapse by the burst factor
    assert counts[8] < counts[1]
    assert counts[1] - 2 >= 4 * (counts[8] - 2)


# ---------------------------------------------------------------------------
# bucketed prefill: compile count
# ---------------------------------------------------------------------------


def test_bucket_length_is_pow2_clamped():
    assert [bucket_length(p, 64) for p in (1, 2, 3, 5, 9, 33, 64)] == \
        [1, 2, 4, 8, 16, 64, 64]
    assert bucket_length(50, 40) == 40  # clamped to the cache row budget


def test_bucketed_prefill_compile_count(olmo):
    """20 distinct prompt lengths must compile <= log2(max_len)+1 prefill
    programs (one per power-of-two bucket), not one per length."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=64, burst=8)
    rng = np.random.default_rng(3)
    reqs = [
        Request(i, rng.integers(0, cfg.vocab_size, i + 1).astype(np.int32), 1)
        for i in range(20)  # prompt lengths 1..20, max_new=1: prefill only
    ]
    out = server.run(reqs)
    assert all(len(v) == 1 for v in out.values())
    assert server.prefill._cache_size() <= int(np.log2(64)) + 1


def test_scan_prefill_compile_count():
    """The recurrent-family block prefill buckets too."""
    cfg, model, params = _setup("mamba2-780m")
    server = BatchedServer(model, EXACT, params, slots=1, max_len=32, burst=4)
    rng = np.random.default_rng(3)
    reqs = [
        Request(i, rng.integers(0, cfg.vocab_size, i + 1).astype(np.int32), 1)
        for i in range(10)  # lengths 1..10 -> buckets {1, 2, 4, 8, 16}
    ]
    server.run(reqs)
    assert server.prefill._cache_size() <= int(np.log2(32)) + 1
