"""One compute dtype: the config's.

Published configs compute in bfloat16. Models cast embeddings to the
config's dtype and the engine emits dots in ``EngineContext.compute_dtype``;
the serving CLI takes the latter from the config, so both agree and every
layer scan carries one dtype. A float32 context over a bfloat16 config gave
the scan a bf16 carry in and an f32 carry out, a trace-time failure at
published widths. Here each family serves a few tokens with a bfloat16
config (reduced widths) in kernel mode.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import FXP8, EngineContext, PrecisionPolicy
from repro.models import get_model
from repro.serve.engine import BatchedServer, Request


@pytest.mark.parametrize("arch", [
    "olmo-1b", "deepseek-v3-671b", "mamba2-780m", "zamba2-7b",
    "seamless-m4t-large-v2", "internvl2-2b",
])
def test_bf16_config_serves(arch):
    cfg = dataclasses.replace(reduced(get_config(arch)), dtype="bfloat16")
    model = get_model(cfg)
    ctx = EngineContext(mode="kernel", policy=PrecisionPolicy.accurate(FXP8),
                        compute_dtype=cfg.compute_dtype)
    assert ctx.compute_dtype == jnp.bfloat16
    server = BatchedServer(model, ctx, model.init(jax.random.PRNGKey(0)),
                           slots=2, max_len=24, burst=2)
    out = server.run([Request(i, np.arange(1, 5 + i, dtype=np.int32), 3)
                      for i in range(2)])
    assert {rid: len(t) for rid, t in out.items()} == {0: 3, 1: 3}
    assert all(0 <= t < cfg.vocab_size for t in out[0] + out[1])


def test_serve_cli_uses_the_config_dtype(monkeypatch):
    """launch/serve.py builds its context from the config, not float32."""
    from repro.launch import serve

    seen = {}
    real = serve.BatchedServer

    def spy(model, ctx, *a, **kw):
        seen["ctx"] = ctx.compute_dtype
        seen["cfg"] = model.cfg.compute_dtype
        return real(model, ctx, *a, **kw)

    monkeypatch.setattr(serve, "BatchedServer", spy)
    prev = jax.config.jax_compilation_cache_dir
    try:
        serve.main(["--reduced", "--requests", "1", "--max-new", "2",
                    "--mode", "int8"])
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    assert seen["ctx"] == seen["cfg"]
