"""SpeculativeDecoder: the jitted draft/verify pair bound to a weight bank.

One decoder owns one compiled draft loop and one compiled verify step (both
keyed on the static draft length); the draft *tree* is an argument, so an
attached mode controller can hand a different resident bank tree each round
with zero recompilation beyond the first visit to each point.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import EngineContext
from repro.models import ModelApi
from repro.obs.trace import span
from repro.runtime.bank import MultiPointBank
from repro.serve.engine import exact_rounding

from .config import SpecConfig
from .decoding import make_draft_loop, make_verify_step
from .telemetry import SpecTelemetry


class SpeculativeDecoder:
    """Draft-k-then-verify serving rounds over a multi-point weight bank."""

    def __init__(self, model: ModelApi, ctx: EngineContext,
                 bank: MultiPointBank, cfg: Optional[SpecConfig] = None, *,
                 shardings=None):
        self.cfg = cfg or SpecConfig()
        self.bank = bank
        self.verify_point = self.cfg.verify_point or bank.reference
        for name in (self.cfg.draft_point, self.verify_point):
            if name is not None and name not in bank.names:
                raise ValueError(
                    f"unknown execution point {name!r}; bank has {bank.names}"
                )
        # default draft point: the cheapest rung of the ladder
        self.default_draft_point = self.cfg.draft_point or bank.names[0]
        if self.default_draft_point == self.verify_point:
            # catches the post-resolution collisions SpecConfig cannot see
            # (draft_point == bank reference, or verify_point == cheapest)
            raise ValueError(
                f"draft point {self.default_draft_point!r} is the verify "
                "point: every round would pay k full-cost draft passes on "
                "top of the verify pass — pick a cheaper draft point"
            )
        # the cache is donated through both halves of the round (draft writes
        # scratch rows in place, verify overwrites them and rolls back), so a
        # round never copies the KV buffers; emit/accept/margin buffers stay
        # on device until the caller's single host transfer. With a sharded
        # server (``shardings`` = the partition.ServingShardings bundle), the
        # cache is pinned to its serving placement through both jits so the
        # donated carry never reshards mid-round; everything else is inferred
        # from the committed bank trees / slot state.
        draft_kwargs, verify_kwargs = {}, {}
        if shardings is not None:
            c = shardings.cache
            draft_kwargs = dict(
                in_shardings=(None, None, c, None, None, None, None),
                out_shardings=(None, None, c),
            )
            verify_kwargs = dict(
                in_shardings=(None, None, None, None, c, None, None, None,
                              None, None),
                out_shardings=(None, None, None, None, None, c),
            )
        self.draft_loop = jax.jit(
            make_draft_loop(model, ctx, self.cfg.draft_len), donate_argnums=(2,),
            compiler_options=exact_rounding(ctx), **draft_kwargs,
        )
        self.verify = jax.jit(
            make_verify_step(model, ctx, self.cfg.draft_len), donate_argnums=(4,),
            compiler_options=exact_rounding(ctx), **verify_kwargs,
        )
        self.telemetry = SpecTelemetry.for_bank(bank, self.cfg.draft_len)
        # optional repro.obs.ServingObserver: draft/verify dispatch spans and
        # the rollback commit land on the serving trace (the server wires
        # this per run)
        self.observer = None
        self._round = 0

    @property
    def draft_len(self) -> int:
        return self.cfg.draft_len

    def reset(self) -> None:
        """Fresh telemetry and round counter (PRNG folds restart), so
        consecutive ``BatchedServer.run`` calls are reproducible."""
        self.telemetry.reset()
        self._round = 0

    def round(self, tokens, cache, base_keys, counts, temps, start, *,
              draft_point: Optional[str] = None):
        """One draft+verify round over the whole slot batch.

        ``tokens`` (B,1) pending token per slot, ``start`` (B,) committed row
        counts, ``counts`` (B,) generated-token indices (PRNG folds). Returns
        ``(emitted (B,k+1) np, accepted (B,) np, margins (B,k+1) np,
        draft_fault (B,) np, verify_fault (B,) np, cache, point)`` with the
        cache rolled back to ``start + accepted + 1`` rows per slot. The
        emit and fault buffers come back in ONE host transfer; the cache
        stays resident (and is donated through draft + verify — no copies).
        The caller records telemetry (it knows which slots are active) and
        acts on the fault flags (draft fault: the lane already degraded to
        plain accurate decode this round; verify fault: quarantine).
        """
        point = draft_point or self.default_draft_point
        obs = self.observer
        round_idx = jnp.int32(self._round)
        self._round += 1
        counts = jnp.asarray(counts, jnp.int32)
        temps = jnp.asarray(temps, jnp.float32)
        start = jnp.asarray(start, jnp.int32)
        if obs is not None:
            obs.spec_stage_begin("draft", point)
        with span("engine.spec.draft"):
            draft_toks, draft_probs, cache = self.draft_loop(
                self.bank.tree(point), tokens, cache, base_keys, counts, temps,
                round_idx,
            )
        if obs is not None:
            obs.spec_stage_end("draft", point)
            obs.spec_stage_begin("verify", self.verify_point)
        with span("engine.spec.verify"):
            (emitted, accepted, margins, draft_fault, verify_fault,
             cache) = self.verify(
                self.bank.tree(self.verify_point), tokens, draft_toks,
                draft_probs, cache, start, base_keys, counts, temps, round_idx,
            )
        if obs is not None:
            obs.spec_stage_end("verify", self.verify_point)
        with span("engine.spec.wait"):
            (emitted, accepted, margins, draft_fault,
             verify_fault) = jax.device_get(
                (emitted, accepted, margins, draft_fault, verify_fault))
        if obs is not None:
            obs.spec_commit(accepted)
        return emitted, accepted, margins, draft_fault, verify_fault, cache, point
