"""Continuous-batching frontend: identity, interleaving, cancellation.

The frontend is a scheduling layer over the unchanged device-resident
engine, so its core contract is the one every scheduling change in this
repo carries: **greedy token streams are bit-identical to batch
``run()``** — per model family (attention chunking and the recurrent
mixers' block from a carried state are different programs), under sampling, under adaptive and
speculative serving, and regardless of when requests arrive relative to
each other. On top of that ride the open-world behaviours ``run()`` cannot
express: chunked prefill's interleaving bound (a long prompt admitted
mid-run stalls decoding slots by at most one chunk budget), client
cancellation mid-prefill / mid-decode (slot freed at the next tick, outcome
``aborted`` with partial tokens, no telemetry leak onto the slot's next
tenant), submit-relative deadlines, and per-tick shed sweeps.
"""
import asyncio
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import EngineContext, FXP16, PrecisionPolicy
from repro.models import get_model
from repro.obs import ServingObserver
from repro.resilience import ResilienceConfig
from repro.runtime import (
    ControllerConfig,
    ModeController,
    build_bank,
    default_points,
)
from repro.serve.engine import BatchedServer, Request
from repro.serve.frontend import (
    AsyncFrontend,
    ContinuousScheduler,
    FrontendConfig,
)
from repro.spec import SpecConfig

EXACT = EngineContext(mode="exact", compute_dtype=jnp.float32)
CARMEN = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP16),
                       compute_dtype=jnp.float32)


def _setup(arch):
    cfg = reduced(get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, *, max_new=6, temperature=0.0, seed_base=None,
              prompt_len=None):
    rng = np.random.default_rng(0)
    return [
        Request(i, rng.integers(
                    0, cfg.vocab_size,
                    prompt_len if prompt_len else 3 + i).astype(np.int32),
                max_new, temperature=temperature,
                seed=None if seed_base is None else seed_base + i)
        for i in range(n)
    ]


def _frontend_serve(server, reqs, *, chunk_tokens=2, monolithic=False):
    sched = ContinuousScheduler(
        server, FrontendConfig(chunk_tokens=chunk_tokens,
                               monolithic_prefill=monolithic))
    with sched:
        for r in reqs:
            sched.submit(r)
        out = sched.drain()
    return out, sched


@pytest.fixture(scope="module")
def olmo():
    return _setup("olmo-1b")


# ---------------------------------------------------------------------------
# identity: chunked frontend == run(), every family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["olmo-1b", "internvl2-2b",
                                  "llama4-maverick-400b-a17b",
                                  "deepseek-v3-671b",
                                  "mamba2-780m", "zamba2-7b"])
def test_frontend_greedy_bit_identical_to_run(arch):
    """dense / vlm / moe / mla / ssm / hybrid: chunk_tokens=2 forces every
    prompt through multiple chunks; the streams must still match run()
    token for token — chunked prefill is scheduling, never numerics."""
    cfg, model, params = _setup(arch)
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=4)
    ref = server.run(_requests(cfg, 3))
    out, sched = _frontend_serve(server, _requests(cfg, 3))
    assert out == ref
    assert sched.stats["prefill_rows"] == sum(3 + i for i in range(3))


def test_frontend_monolithic_prefill_matches_run(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=4)
    ref = server.run(_requests(cfg, 3))
    out, _ = _frontend_serve(server, _requests(cfg, 3), monolithic=True)
    assert out == ref


def test_frontend_sampled_streams_match_run(olmo):
    """Sampling depends only on (seed, token index): the frontend's chunked
    admission must reproduce run()'s sampled streams exactly."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=4)
    ref = server.run(_requests(cfg, 3, temperature=0.8, seed_base=11))
    out, _ = _frontend_serve(
        server, _requests(cfg, 3, temperature=0.8, seed_base=11))
    assert out == ref


def test_frontend_adaptive_matches_run(olmo):
    cfg, model, params = olmo
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    def build():
        return BatchedServer(
            model, CARMEN, params, slots=2, max_len=32, burst=4, bank=bank,
            controller=ModeController(bank,
                                      ControllerConfig(pin=bank.reference)))
    ref = build().run(_requests(cfg, 3))
    out, _ = _frontend_serve(build(), _requests(cfg, 3))
    assert out == ref


def test_frontend_speculative_matches_run(olmo):
    cfg, model, params = olmo
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    def build():
        return BatchedServer(model, CARMEN, params, slots=2, max_len=40,
                             bank=bank, speculate=SpecConfig(draft_len=3))
    ref = build().run(_requests(cfg, 3))
    out, _ = _frontend_serve(build(), _requests(cfg, 3))
    assert out == ref


def test_frontend_late_arrival_stream_identical(olmo):
    """A request admitted mid-run (other slots already decoding) gets the
    same stream as when it was in the opening batch: per-slot state is
    independent of batch composition."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=2)
    reqs = _requests(cfg, 3, max_new=8)
    ref = server.run(_requests(cfg, 3, max_new=8))
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=2))
    with sched:
        sched.submit(reqs[0])
        sched.submit(reqs[1])
        for _ in range(4):
            sched.step()
        sched.submit(reqs[2])  # mid-run arrival
        out = sched.drain()
    assert out == ref


# ---------------------------------------------------------------------------
# interleaving: the chunk budget bounds prefill stall
# ---------------------------------------------------------------------------


def _interleave_workload(cfg):
    """Two shorts with different budgets (one outlives the other, so the
    long prompt's prefill really interleaves with live decoding) plus one
    24-token prompt submitted mid-run."""
    rng = np.random.default_rng(5)
    short = [
        Request(0, rng.integers(0, cfg.vocab_size, 3).astype(np.int32), 20),
        Request(1, rng.integers(0, cfg.vocab_size, 4).astype(np.int32), 6),
    ]
    long_req = Request(
        9, rng.integers(0, cfg.vocab_size, 24).astype(np.int32), 4)
    return short, long_req


def test_interleaving_bound_holds_for_long_prompt(olmo):
    """A 24-token prompt admitted while a slot is still decoding advances
    at most chunk_tokens rows between bursts — decoding keeps emitting."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=48, burst=2)
    short, long_req = _interleave_workload(cfg)
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=4))
    with sched:
        for r in short:
            sched.submit(r)
        sched.step()
        sched.submit(long_req)
        out = sched.drain()
    # non-vacuous: prefill rows really ran while a slot was decoding...
    assert sched.stats["max_prefill_rows_between_bursts"] > 0
    # ...and never more than one chunk budget of them between two bursts
    assert sched.stats["max_prefill_rows_between_bursts"] <= 4
    assert len(out[9]) == 4
    # and the long prompt's stream is still exactly what run() gives it
    ref = server.run([Request(9, long_req.prompt.copy(), 4)])
    assert out[9] == ref[9]


def test_monolithic_contrast_takes_the_stall(olmo):
    """With monolithic_prefill the same workload charges the whole long
    prompt between two bursts — the stall chunking exists to amortize."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=48, burst=2)
    short, long_req = _interleave_workload(cfg)
    sched = ContinuousScheduler(
        server, FrontendConfig(chunk_tokens=4, monolithic_prefill=True))
    with sched:
        for r in short:
            sched.submit(r)
        sched.step()
        sched.submit(long_req)
        sched.drain()
    assert sched.stats["max_prefill_rows_between_bursts"] >= 24


# ---------------------------------------------------------------------------
# cancellation: mid-prefill, mid-decode, queued
# ---------------------------------------------------------------------------


def test_cancel_mid_prefill_frees_slot_no_leak(olmo):
    """Cancelling during a chunked prefill drops the private row cache,
    frees the slot at the next tick, settles the handle as aborted with 0
    tokens — and the slot's next tenant streams exactly as if the
    cancelled request never existed."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=32, burst=4,
                           resilience=ResilienceConfig())
    server.observer = ServingObserver()
    ref = server.run(_requests(cfg, 1, max_new=6))

    rng = np.random.default_rng(5)
    victim = Request(
        50, rng.integers(0, cfg.vocab_size, 12).astype(np.int32), 6)
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=2))
    with sched:
        handle = sched.submit(victim)
        sched.step()  # 2 of 12 prompt rows done: mid-prefill
        assert sched.job is not None and sched.job.done == 2
        handle.cancel()
        sched.step()
        assert sched.job is None and sched.free == [0]
        assert handle.done and handle.status == "aborted"
        assert handle.outcome.reason == "cancelled"
        assert handle.tokens == []
        # slot reuse: the next request on slot 0 is untouched by the corpse
        out = {}
        for r in _requests(cfg, 1, max_new=6):
            sched.submit(r)
        out = sched.drain()
    assert out[0] == ref[0]
    assert 50 not in out
    # telemetry: cancelled counted, but no first-token/ttft ever recorded
    snap = server.observer.snapshot()
    assert snap["metrics"]["counters"]["cancelled"] == 1
    assert snap["requests"][50]["tokens"] == 0
    assert snap["requests"][50]["ttft_s"] is None  # no first token ever
    prefilled = [e for e in server.observer.trace.events
                 if e["name"] == "request_prefilled"
                 and e["args"]["rid"] == 50]
    assert prefilled == []


def test_cancel_mid_decode_keeps_partial_tokens(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=64, burst=2,
                           resilience=ResilienceConfig())
    ref = server.run(_requests(cfg, 1, max_new=40))
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=4))
    with sched:
        handle = sched.submit(_requests(cfg, 1, max_new=40)[0])
        while len(handle.tokens) < 5:
            sched.step()
        handle.cancel()
        out = sched.drain()
    assert handle.status == "aborted"
    assert handle.outcome.reason == "cancelled"
    assert 0 < len(handle.tokens) < 40
    # the partial stream is a clean prefix of the uncancelled one
    assert out[0] == ref[0][:len(out[0])]


def test_cancel_queued_request_never_prefills(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=32, burst=4,
                           resilience=ResilienceConfig())
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=8))
    with sched:
        first = sched.submit(_requests(cfg, 1, max_new=12)[0])
        queued = sched.submit(Request(
            7, np.arange(1, 5, dtype=np.int32), 6))
        sched.step()  # first occupies the only slot; 7 waits
        queued.cancel()
        out = sched.drain()
    assert queued.status == "aborted" and queued.tokens == []
    assert first.status == "ok" and len(out[0]) == 12
    assert 7 not in out


# ---------------------------------------------------------------------------
# submit-relative deadlines + per-tick shed sweeps
# ---------------------------------------------------------------------------


def test_deadline_counts_from_submit(olmo):
    """Frontend deadlines anchor at submit(): a request whose deadline
    passes while it sits in the inbox/queue is shed at the next tick."""
    cfg, model, params = olmo
    server = BatchedServer(
        model, EXACT, params, slots=1, max_len=32, burst=4,
        resilience=ResilienceConfig(default_deadline_s=30.0))
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=8))
    with sched:
        doomed = sched.submit(Request(0, np.arange(1, 4, dtype=np.int32), 4,
                                      deadline_s=0.03))
        time.sleep(0.15)  # expires before the first tick ever sees it
        fine = sched.submit(Request(1, np.arange(1, 4, dtype=np.int32), 4))
        out = sched.drain()
    assert doomed.status == "shed"
    assert doomed.outcome.reason == "deadline_expired"
    assert fine.status == "ok" and len(out[1]) == 4
    # the caller's Request objects were never mutated by resolution
    assert doomed.request.deadline_s == 0.03
    assert fine.request.deadline_s is None


def test_queue_overflow_sheds_per_tick(olmo):
    """shed_overflow runs on every tick, not once per run: requests
    submitted while the queue is full are shed with queue_full even though
    they never coexisted in one run() call."""
    cfg, model, params = olmo
    server = BatchedServer(
        model, EXACT, params, slots=1, max_len=32, burst=2,
        resilience=ResilienceConfig(queue_limit=1))
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=8))
    with sched:
        running = sched.submit(_requests(cfg, 1, max_new=12)[0])
        sched.step()  # occupies the slot
        waiters = [sched.submit(Request(10 + i,
                                        np.arange(1, 4, dtype=np.int32), 4))
                   for i in range(3)]
        sched.drain()
    assert running.status == "ok"
    statuses = sorted(h.status for h in waiters)
    assert statuses == ["ok", "shed", "shed"]
    shed = [h for h in waiters if h.status == "shed"]
    assert all(h.outcome.reason == "queue_full" for h in shed)


def test_legacy_contract_raises_at_submit(olmo):
    """resilience=None keeps fail-stop: invalid requests raise
    synchronously at submit(), byte-identical to run()'s message."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=8, burst=2)
    sched = ContinuousScheduler(server, FrontendConfig())
    with sched:
        with pytest.raises(ValueError, match="exceeds max_len"):
            sched.submit(Request(0, np.arange(1, 30, dtype=np.int32), 4))
        with pytest.raises(ValueError, match="empty prompt"):
            sched.submit(Request(1, np.zeros(0, dtype=np.int32), 4))


# ---------------------------------------------------------------------------
# API guards
# ---------------------------------------------------------------------------


def test_duplicate_rid_rejected(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=32, burst=2)
    sched = ContinuousScheduler(server, FrontendConfig())
    with sched:
        sched.submit(Request(3, np.arange(1, 4, dtype=np.int32), 2))
        with pytest.raises(ValueError, match="duplicate rid"):
            sched.submit(Request(3, np.arange(1, 4, dtype=np.int32), 2))
        sched.drain()


def test_submit_requires_open_and_close_is_final(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=32, burst=2)
    sched = ContinuousScheduler(server, FrontendConfig())
    with pytest.raises(RuntimeError, match="not open"):
        sched.submit(Request(0, np.arange(1, 4, dtype=np.int32), 2))
    with sched:
        pass
    with pytest.raises(RuntimeError, match="closed"):
        sched.submit(Request(0, np.arange(1, 4, dtype=np.int32), 2))


def test_mesh_server_rejected(olmo):
    cfg, model, params = olmo
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    server = BatchedServer(model, EXACT, params, slots=1, max_len=32,
                           burst=2, mesh=mesh)
    with pytest.raises(ValueError, match="single-device"):
        ContinuousScheduler(server)


def test_frontend_config_validation():
    with pytest.raises(ValueError):
        FrontendConfig(chunk_tokens=0)


def test_close_settles_in_flight_as_shutdown(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=64, burst=2,
                           resilience=ResilienceConfig())
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=8))
    with sched:
        h = sched.submit(_requests(cfg, 1, max_new=30)[0])
        sched.step()
        sched.step()
    assert h.done and h.status == "aborted"
    assert h.outcome.reason == "shutdown"
    assert 0 < len(h.tokens) < 30  # partial stream kept


# ---------------------------------------------------------------------------
# threads + asyncio facade
# ---------------------------------------------------------------------------


def test_threaded_submitters_one_scheduler(olmo):
    """submit() is thread-safe: N client threads feeding one scheduler get
    exactly the streams run() computes for the same requests."""
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=4)
    ref = server.run(_requests(cfg, 4))
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=2))
    reqs = _requests(cfg, 4)
    with sched:
        threads = [threading.Thread(target=sched.submit, args=(r,))
                   for r in reqs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        out = sched.drain()
    assert out == ref


def test_async_frontend_generate_and_stream(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=4)
    ref = server.run(_requests(cfg, 2))

    async def go():
        async with AsyncFrontend(server,
                                 FrontendConfig(chunk_tokens=2)) as fe:
            reqs = _requests(cfg, 2)
            task = asyncio.ensure_future(fe.generate(reqs[0]))
            streamed = []
            async for tok in fe.stream(reqs[1]):
                streamed.append(tok)
            return await task, streamed

    generated, streamed = asyncio.run(go())
    assert generated == ref[0]
    assert streamed == ref[1]


def test_async_frontend_cancellation(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=1, max_len=64, burst=2,
                           resilience=ResilienceConfig())
    fe = AsyncFrontend(server, FrontendConfig(chunk_tokens=4)).start()
    try:
        handle = fe.submit(_requests(cfg, 1, max_new=40)[0])
        while len(handle.tokens) < 4:
            time.sleep(0.005)
        handle.cancel()
        handle.result(timeout=30.0)
    finally:
        fe.stop()
    assert handle.status == "aborted"
    assert handle.outcome.reason == "cancelled"
    assert 0 < len(handle.tokens) < 40
