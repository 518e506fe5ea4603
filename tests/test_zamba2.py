"""Zamba2 (``configs/zamba2_7b.py``) against its plain reference
(``chipbench/configs/zamba2_ref.py``), on the CPU at a tiny size that keeps
the published 12-layer prefix (6 mamba layers, hybrid A, 4 mamba, hybrid B),
both shared blocks and two B/C groups, on weights drawn as the benchmark
draws them (``chipbench/weights.py``, the configuration file's spreads).

The served path is the one the benchmark times: ``BatchedServer`` behind a
``ContinuousScheduler``, each prompt prefilled in chunks (the block form of
the mixers, from the row's carried state) and then decoded in bursts through
the slot cache. The reference runs one sequence at a time, its state as the
per-token recurrence.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import FXP8, EngineContext, PrecisionPolicy
from repro.models import blocks, get_model, mamba2
from repro.serve.engine import BatchedServer, Request
from repro.serve.frontend import ContinuousScheduler, FrontendConfig

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import manifest  # noqa: E402
from chipbench.weights import make_weights  # noqa: E402

EXACT = EngineContext(mode="exact", compute_dtype=jnp.float32)
INT8 = EngineContext(mode="int8", policy=PrecisionPolicy.accurate(FXP8),
                     compute_dtype=jnp.float32)
FILE = manifest.config("zamba2-7b.int8")
REF = manifest.reference(FILE)
SEED = 20261019


def tiny_file(cfg, *, quantized: bool):
    """The benchmark's configuration file at ``cfg``'s tiny widths; without
    ``quantized`` every format is None (the exact reference)."""
    s = cfg.ssm
    out = dict(FILE, hidden_size=cfg.d_model, attention_hidden_size=2 * cfg.d_model,
               attention_head_dim=cfg.head_dim, num_attention_heads=cfg.num_heads,
               num_key_value_heads=cfg.num_kv_heads, ffn_hidden_size=cfg.d_ff,
               intermediate_size=cfg.d_ff, n_mamba_heads=2 * cfg.d_model // s.head_dim,
               mamba_headdim=s.head_dim, mamba_d_state=s.state_dim,
               chunk_size=s.chunk_size, adapter_rank=cfg.hybrid.adapter_rank,
               vocab_size=cfg.vocab_size, num_hidden_layers=cfg.num_layers)
    if not quantized:
        out.update(activations=None, control=None, af_format=None,
                   weight_format=None)
    return out


def _setup(layers=None):
    cfg = reduced(get_config("zamba2-7b"))
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    model = get_model(cfg)
    return cfg, model, make_weights(model, SEED, **FILE["weights"])


@pytest.fixture(scope="module")
def zamba():
    return _setup()


def _requests(cfg, lengths, max_new):
    rng = np.random.default_rng(7)
    return [Request(i, rng.integers(0, cfg.vocab_size, n).astype(np.int32), max_new)
            for i, n in enumerate(lengths)]


def _serve(server, requests, chunk_tokens):
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=chunk_tokens))
    with sched:
        handles = [sched.submit(r) for r in requests]
        while not all(h.done for h in handles):
            sched.step()
    return [list(h.tokens) for h in handles]


def _ref_rows(params, cfg, prompt, served, *, quantized, control=False):
    """Reference logits at the positions that chose each served token."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    out = REF.logits(params, jnp.asarray(seq), tiny_file(cfg, quantized=quantized),
                     control=control)
    return np.asarray(out)[len(prompt) - 1:]


def _gaps(rows, tokens):
    """How far each chosen token's reference logit lies below the best."""
    return rows.max(-1) - rows[np.arange(len(tokens)), tokens]


def _margins(rows):
    top = np.sort(rows, -1)
    return top[:, -1] - top[:, -2]


@pytest.mark.parametrize("chunk_tokens", [2, 5, 64])
def test_served_streams_match_reference_exact(zamba, chunk_tokens):
    """Prefill in chunks of 2, 5 and whole buckets (64 holds every prompt),
    then bursts: every served token is the reference's first choice, and its
    top-2 margin (the logits' top two, as served) is the reference's, within
    1e-4: float32 on both sides, summed in another order (the chunked SSD
    form against the recurrence), some 1e-6 apart."""
    cfg, model, params = zamba
    server = BatchedServer(model, EXACT, params, slots=3, max_len=64, burst=4)
    reqs = _requests(cfg, (7, 12, 19), 9)
    served = _serve(server, reqs, chunk_tokens)
    for req, toks in zip(reqs, served):
        assert len(toks) == 9
        rows = _ref_rows(params, cfg, req.prompt, toks, quantized=False)
        assert _gaps(rows, toks).max() <= 1e-4
        np.testing.assert_allclose(req.margins, _margins(rows), atol=1e-4)


def test_chunk_programs_logits_match_reference_exact(zamba):
    """The serving programs' own logits, whole vectors: the chunk program's
    last row after chunks of 2, 5 and 6 rows (buckets 2, 8, 8: padded tails
    past ``clen``), then single-token decode steps through the row cache."""
    cfg, model, params = zamba
    server = BatchedServer(model, EXACT, params, slots=1, max_len=64, burst=4)
    chunk, _ = server.chunk_fns()
    prompt = _requests(cfg, (13,), 1)[0].prompt
    cont = np.random.default_rng(3).integers(0, cfg.vocab_size, 4).astype(np.int32)
    ref = np.asarray(REF.logits(params, jnp.asarray(np.concatenate([prompt, cont])),
                                tiny_file(cfg, quantized=False)))
    row, last = server.fresh_row()
    done = 0
    for n, bucket in ((2, 2), (5, 8), (6, 8)):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = prompt[done:done + n]
        row, last = chunk(server.params, row, last, jnp.asarray(padded),
                          jnp.int32(done), jnp.int32(n))
        done += n
        np.testing.assert_allclose(np.asarray(last[0]), ref[done - 1], atol=1e-4)
    for i, tok in enumerate(cont):
        logits, row = model.decode_step(server.params, jnp.asarray([[tok]]), row, EXACT)
        np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[done + i], atol=1e-4)


def test_served_streams_match_reference_int8(zamba):
    """int8 serving (per-row 8-bit activations, per-channel 8-bit weights,
    the AF in FxP8) against the reference at the same quantization. The two
    round the same values, but a value that the program and the reference
    compute a few float32 ulps apart can round to neighbouring int8 levels
    (a step of 1/127 of its row's largest value) or AF levels (2^-6), and
    the change carries on. So a served token may trail the reference's best
    by that much: 0.05 (the readings here stay below 0.01), where the 4-bit
    control lies above it."""
    cfg, model, params = zamba
    server = BatchedServer(model, INT8, params, slots=3, max_len=64, burst=4)
    reqs = _requests(cfg, (7, 12, 19), 9)
    served = _serve(server, reqs, 5)
    worst, control = 0.0, 0.0
    for req, toks in zip(reqs, served):
        rows = _ref_rows(params, cfg, req.prompt, toks, quantized=True)
        worst = max(worst, _gaps(rows, toks).max())
        ctrl = _ref_rows(params, cfg, req.prompt, toks, quantized=True,
                         control=True)
        control = max(control, _gaps(rows, ctrl.argmax(-1)).max())
    assert worst <= 0.05 < control


@pytest.mark.parametrize("clen", [1, 5, 45, 64])
def test_block_from_state_equals_recurrence(zamba, clen):
    """The mixer's block form from a carried state (64 rows, two SSD chunks
    of 32) equals the per-token recurrence over its first ``clen`` rows, and
    the rows past ``clen`` leave the state and the conv window as they were
    after row ``clen``."""
    cfg, _, params = zamba
    p = jax.tree.map(lambda a: a[0], params["seg0_mamba"])["mixer"]
    rng = np.random.default_rng(clen)
    state = jax.tree.map(
        lambda s: jnp.asarray(rng.normal(0, 1, s.shape), jnp.float32),
        mamba2.mamba_state_specs(cfg, 1, jnp.float32))
    x = jnp.asarray(rng.normal(0, 1, (1, 64, cfg.d_model)), jnp.float32)
    out, block_state = mamba2.mamba2_forward(p, x, cfg, EXACT, name="m",
                                             state=state, clen=jnp.int32(clen))
    steps, st = [], state
    for t in range(clen):
        y, st = mamba2.mamba2_forward(p, x[:, t:t + 1], cfg, EXACT, name="m",
                                      state=st)
        steps.append(np.asarray(y[0, 0]))
    np.testing.assert_allclose(np.asarray(out[0, :clen]), np.stack(steps),
                               atol=1e-4, rtol=1e-4)
    for key in ("conv", "ssm"):
        np.testing.assert_allclose(np.asarray(block_state[key]),
                                   np.asarray(st[key]), atol=1e-4, rtol=1e-4)


def test_hybrid_call_k_uses_block_k_mod_2_and_adapter_k(monkeypatch):
    """18 layers hold three hybrid calls (layers 6, 11, 17): blocks A, B, A,
    each with its own adapter; the served forward agrees with the reference,
    which reads the calls from the published layer list on its own."""
    cfg, model, params = _setup(layers=18)
    seen = {"wq": [], "down": []}
    attention = blocks.attention

    def spy_attention(p, *a, **kw):
        seen["wq"].append(np.asarray(p["wq"]))
        return attention(p, *a, **kw)

    class Spy(EngineContext):
        def linear(self, x, w, b=None, *, name=""):
            if name.endswith("adapter.down"):
                seen["down"].append(np.asarray(w))
            return super().linear(x, w, b, name=name)

    monkeypatch.setattr(blocks, "attention", spy_attention)
    tokens = jnp.asarray(_requests(cfg, (24,), 1)[0].prompt)
    logits, _ = model.forward(params, {"tokens": tokens[None]},
                              Spy(mode="exact", compute_dtype=jnp.float32))
    wq = np.asarray(params["shared"]["attn"]["wq"])
    hybrid = [key for key in sorted(params, key=lambda k: (len(k), k))
              if key.endswith("_hybrid")]
    assert len(hybrid) == len(seen["wq"]) == len(seen["down"]) == 3
    assert not np.array_equal(wq[0], wq[1])
    for k in range(3):
        np.testing.assert_array_equal(seen["wq"][k], wq[k % 2])
        np.testing.assert_array_equal(seen["down"][k],
                                      np.asarray(params[hybrid[k]]["adapter"]["down"][0]))
    ref = REF.logits(params, tokens, tiny_file(cfg, quantized=False))
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(ref), atol=1e-4)
