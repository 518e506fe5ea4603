"""The comparison that decides ``correct``, driven on the CPU at a small
size: the plain reference agrees with the program's exact forward; a sound
run is correct; the control and each fault a serving cell can have read
above the limit."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import faults, manifest, run
from chipbench.weights import make_weights

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**33 + 77
# at this size (4 layers, width 256, 2048 tokens, float32 compute) sound
# runs read gaps of 0-0.01, the 4-bit controls 0.3-0.9, a token altered
# 1.0-1.7 and a burst that keeps its state 0.14-0.56
LIMITS = {"kernel": 0.1, "int8": 0.1}


def _small(mode="kernel"):
    with open(os.path.join(HERE, "configs", f"olmo-1b.{'fxp8-kernel' if mode == 'kernel' else 'int8'}.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=256, intermediate_size=1024, num_hidden_layers=4,
               num_attention_heads=4, num_key_value_heads=4, vocab_size=2048,
               head_dim=64)
    cfg["serving"]["compute_dtype"] = "float32"
    # queries and keys at 1/sqrt(width), as 0.02 is at the published width
    cfg["weights"]["std_by_leaf"].update(wq=1 / 16, wk=1 / 16)
    cfg["check"] = {"max_logit_gap": LIMITS[mode]}
    return cfg


def _mix():
    with open(os.path.join(HERE, "traffic", "batch-decode.json")) as f:
        mix = json.load(f)
    mix.update(slots=4, concurrency=8, chunk_tokens=32, warmup_done=4,
               pool=64, check_requests=8, check_min_tokens=64)
    mix["prompt"].update(median=24, min=8, max=64)
    mix["output"].update(min=24, max=48)
    return mix


def _run(monkeypatch, cfg, **kw):
    monkeypatch.setattr(manifest, "config", lambda name: cfg)
    monkeypatch.setattr(manifest, "traffic", lambda name: _mix())
    bench = {"workloads": [{"name": "small", "config": "c", "traffic": "t",
                            "chips": 1}],
             "end_to_end": [{"name": "tokens_per_s", "unit": "tokens/s"}],
             "per_layer": []}
    return run.run_cell(bench, "small", SEED, 1.0, False, **kw)


def test_reference_equals_program_exact_forward():
    from repro.core import EngineContext
    from repro.models import get_model

    cfg = _small()
    model = get_model(run.program_config(cfg))
    weights = make_weights(model, 3, **cfg["weights"])
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 2048, 96),
                         jnp.int32)
    ctx = EngineContext(mode="exact", compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        program, _ = model.forward(weights, {"tokens": tokens[None]}, ctx)
    # the reference's rounding to the stated formats off: plain float32
    plain = dict(cfg, activations=None, af_format=None, weight_format=None)
    ref = manifest.reference(plain).logits(weights, tokens, plain)
    np.testing.assert_allclose(np.asarray(program[0]), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["kernel", "int8"])
def test_sound_run_is_correct_and_control_is_not(monkeypatch, mode):
    out = _run(monkeypatch, _small(mode), control=True)
    checks = out["checks"]
    assert out["correct"], checks
    assert checks["served_tokens_checked"]["value"] >= 64
    assert out["window"]["compiles"] == 0
    # the control, in the program's place, is judged as a run is: not correct
    control = checks["control_max_logit_gap"]
    assert control["value"] > LIMITS[mode], checks
    assert control["correct"] is False
    assert run.decide(control["value"], checks["served_tokens_checked"]["value"],
                      _small(mode), _mix()) == (False, {
                          "max_logit_gap": {"value": control["value"],
                                            "limit": LIMITS[mode]},
                          "served_tokens_checked": {
                              "value": checks["served_tokens_checked"]["value"],
                              "limit": 64}})


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("mode", ["kernel", "int8"])
def test_faulted_run_is_not_correct(monkeypatch, fault, mode):
    from repro.serve.engine import BatchedServer

    monkeypatch.setattr(BatchedServer, "decode_burst",
                        faults.FAULTS[fault](BatchedServer.decode_burst))
    out = _run(monkeypatch, _small(mode))
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] > LIMITS[mode]
