"""Unified model API over all assigned architecture families."""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import EngineContext

from . import blocks, encdec, mamba2, mla, params as P, transformer


@dataclasses.dataclass(frozen=True)
class ModelApi:
    cfg: ModelConfig

    # -- parameters ---------------------------------------------------------
    def specs(self):
        if self.cfg.family == "audio":
            return encdec.encdec_specs(self.cfg)
        return transformer.decoder_specs(self.cfg)

    def init(self, key, dtype=jnp.float32):
        return P.init(self.specs(), key, dtype)

    def abstract_params(self, dtype=jnp.bfloat16):
        return P.abstract(self.specs(), dtype)

    def param_axes(self):
        return P.axes_tree(self.specs())

    def count_params(self) -> int:
        return P.count_params(self.specs())

    # -- compute ------------------------------------------------------------
    def forward(self, prms, batch, ctx: EngineContext, *, remat: bool = False):
        if self.cfg.family == "audio":
            return encdec.forward(prms, batch, self.cfg, ctx, remat=remat)
        return transformer.forward(prms, batch, self.cfg, ctx, remat=remat)

    def decode_step(self, prms, tokens, cache, ctx: EngineContext, clen=None):
        """``clen``: a block's real rows, for recurrent state (see
        ``transformer.decode_step``); attention-only caches need none."""
        if self.cfg.family == "audio":
            return encdec.decode_step(prms, tokens, cache, self.cfg, ctx)
        return transformer.decode_step(prms, tokens, cache, self.cfg, ctx,
                                       clen=clen)

    def make_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16, abstract: bool = False):
        if self.cfg.family == "audio":
            return encdec.make_cache(self.cfg, batch, max_len, dtype, abstract=abstract)
        return transformer.make_cache(self.cfg, batch, max_len, dtype, abstract=abstract)


def get_model(cfg: ModelConfig) -> ModelApi:
    cfg.validate()
    return ModelApi(cfg)


__all__ = ["ModelApi", "get_model", "blocks", "encdec", "mamba2", "mla", "transformer"]
