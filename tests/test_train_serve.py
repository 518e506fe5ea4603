"""End-to-end behaviour: training reduces loss; serving is consistent;
checkpoint-restart resumes identically; CARMEN modes train too (STE)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import EngineContext, FXP16, PrecisionPolicy
from repro.data.pipeline import TokenPipeline
from repro.models import get_model
from repro.serve.engine import BatchedServer, Request
from repro.train import optimizer as opt
from repro.train.train_loop import TrainConfig, make_train_step

CTX = EngineContext(mode="exact", compute_dtype=jnp.float32)


def _setup(arch="olmo-1b", steps_cfg=None):
    cfg = reduced(get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tcfg = steps_cfg or TrainConfig(
        optimizer=opt.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=30),
        remat=False,
    )
    return cfg, model, params, tcfg


def _run(model, params, tcfg, ctx, steps=25, seq=32, batch=8):
    pipe = TokenPipeline(model.cfg, seq, batch)
    state = opt.init_state(params)
    step_fn = jax.jit(make_train_step(model, ctx, tcfg))
    losses = []
    for s in range(steps):
        params, state, m = step_fn(params, state, pipe.batch(s))
        losses.append(float(m["loss"]))
    return params, state, losses


def test_training_reduces_loss():
    cfg, model, params, tcfg = _setup()
    _, _, losses = _run(model, params, tcfg, CTX)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])


def test_training_carmen_mode_reduces_loss():
    """QAT via STE: the paper-faithful quantized engine is trainable."""
    cfg, model, params, tcfg = _setup()
    ctx = EngineContext(
        mode="carmen", policy=PrecisionPolicy.accurate(FXP16), compute_dtype=jnp.float32
    )
    _, _, losses = _run(model, params, tcfg, ctx, steps=20)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.2


def test_microbatching_matches_full_batch():
    """Gradient accumulation must be loss-equivalent to the monolithic step."""
    cfg, model, params, _ = _setup()
    pipe = TokenPipeline(cfg, 32, 8)
    batch = pipe.batch(0)
    t1 = TrainConfig(optimizer=opt.AdamWConfig(lr=1e-3), microbatches=1, remat=False)
    t2 = TrainConfig(optimizer=opt.AdamWConfig(lr=1e-3), microbatches=4, remat=False)
    s1 = opt.init_state(params)
    p1, _, m1 = jax.jit(make_train_step(model, CTX, t1))(params, s1, batch)
    s2 = opt.init_state(params)
    p2, _, m2 = jax.jit(make_train_step(model, CTX, t2))(params, s2, batch)
    # same data, same update (microbatch mean == full mean for mean losses)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_checkpoint_restart_resumes_identically(tmp_path):
    from repro.train import checkpoint

    cfg, model, params, tcfg = _setup()
    pipe = TokenPipeline(cfg, 32, 8)
    step_fn = jax.jit(make_train_step(model, CTX, tcfg))
    state = opt.init_state(params)
    # run 6 steps, checkpoint at 3
    p, s = params, state
    for i in range(3):
        p, s, _ = step_fn(p, s, pipe.batch(i))
    checkpoint.save(str(tmp_path), 3, p)
    checkpoint.save(str(tmp_path / "opt"), 3, s)
    p_cont, s_cont = p, s
    for i in range(3, 6):
        p_cont, s_cont, m_direct = step_fn(p_cont, s_cont, pipe.batch(i))
    # restart from disk
    p_r = checkpoint.restore(str(tmp_path), 3, p)
    s_r = checkpoint.restore(str(tmp_path / "opt"), 3, s)
    for i in range(3, 6):
        p_r, s_r, m_restart = step_fn(p_r, s_r, pipe.batch(i))
    np.testing.assert_allclose(float(m_direct["loss"]), float(m_restart["loss"]), rtol=1e-6)


def test_batched_server_matches_sequential_decode():
    """Continuous batching must produce the same tokens as dedicated decoding."""
    cfg, model, params, _ = _setup()
    prompt = np.array([5, 17, 3], np.int32)
    server = BatchedServer(model, CTX, params, slots=2, max_len=32)
    results = server.run([Request(0, prompt, 5), Request(1, prompt, 5)])
    # identical prompts -> identical generations, regardless of slot
    assert results[0] == results[1]
    # reference: single-sequence decode
    cache = model.make_cache(1, 32, dtype=jnp.float32)
    tok = None
    for t in prompt:
        lg, cache = model.decode_step(params, jnp.array([[t]]), cache, CTX)
        tok = int(np.asarray(lg[0, 0]).argmax())
    gen = [tok]
    for _ in range(4):
        lg, cache = model.decode_step(params, jnp.array([[gen[-1]]]), cache, CTX)
        gen.append(int(np.asarray(lg[0, 0]).argmax()))
    assert results[0] == gen


def test_max_new_one_returns_single_token():
    """max_new=1 is satisfied by the prefill token alone — no extra decode."""
    cfg, model, params, _ = _setup()
    server = BatchedServer(model, CTX, params, slots=1, max_len=32)
    out = server.run([Request(0, np.array([5, 17, 3], np.int32), 1)])
    assert len(out[0]) == 1


def test_sequential_prefill_isolated_from_active_slots():
    """Recurrent-state families prefill into a fresh row cache: admitting a
    request must never advance other active slots' state (two identical
    prompts across slots generate identically, matching a dedicated server)."""
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("mamba2-780m"))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    p = np.array([5, 17, 3], np.int32)
    server = BatchedServer(model, CTX, params, slots=2, max_len=32)
    # ssm prefills as one block from the fresh row's state, not rewindably
    assert server.block_prefill and not server.batched_prefill
    out = server.run([Request(0, p, 4), Request(1, p, 4)])
    assert out[0] == out[1]
    ref = BatchedServer(model, CTX, params, slots=1, max_len=32).run(
        [Request(0, p, 4)]
    )
    assert out[0] == ref[0]


def test_slot_reuse_after_eviction():
    """A new request admitted into a used slot must not see stale cache."""
    cfg, model, params, _ = _setup()
    p1 = np.array([5, 17, 3], np.int32)
    p2 = np.array([9, 2, 44], np.int32)
    # serve p2 alone on a fresh server
    fresh = BatchedServer(model, CTX, params, slots=1, max_len=32)
    ref = fresh.run([Request(0, p2, 4)])[0]
    # serve p1 then p2 through the SAME slot
    server = BatchedServer(model, CTX, params, slots=1, max_len=32)
    out = server.run([Request(0, p1, 4), Request(1, p2, 4)])
    assert out[1] == ref
