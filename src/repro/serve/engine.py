"""Serving engine: decode bursts, bucketed prefill, sampling, batched scheduler.

The scheduler implements continuous batching over a fixed slot count —
admit/evict at burst boundaries, per-slot positions — with four serving fast
paths on top:

* **prepared weight banks**: on construction the server runs
  ``prepare_params`` (quantize once), so carmen/int8/kernel decode performs
  zero weight-side rounding or scale computation per step;
* **device-resident decode bursts**: the decode hot loop is ONE jitted
  ``lax.scan`` over up to ``burst`` single-token steps. All per-slot state
  (pending token, generated count, remaining budget, PRNG key, temperature)
  lives on device in the burst carry; token ids and top-2 logit margins
  accumulate into ``(slots, burst)`` on-device buffers, so exactly one host
  round-trip happens per burst instead of per token. The KV cache and slot
  state are donated (``donate_argnums``), so XLA updates them in place
  rather than copying per call. ``burst=1`` is the classic per-token loop;
  larger bursts are bit-identical for greedy requests and stream-identical
  for sampled ones (per-request PRNG keys are folded by generated-token
  index, never by schedule);
* **bucketed prefill**: an admitted prompt is padded to a power-of-two
  length bucket and run through the model in one jitted call that also
  scatters the resulting KV rows into the slot cache and rewinds the write
  index to the true prompt length (the padded tail's rows are invisible
  behind the per-query-causal mask and reclaimed by decode) — prefill
  compiles O(log max_len) programs instead of one per distinct prompt
  length, and cache insertion is not an eager ``jax.tree.map`` anymore.
  Recurrent-state families (ssm/hybrid) run the same block program: their
  mixers start from the carried state and leave it as it was past the
  prompt's length (the chunked SSD form). The audio family prefills through
  a jitted ``lax.scan`` over the padded prompt with masked state updates —
  same bucketing, no per-token host round-trip;
* **runtime-adaptive precision** (``repro.runtime``): pass a
  :class:`~repro.runtime.controller.ModeController` and each decode burst
  executes at the controller's current execution point — a different
  prepared tree from the multi-point weight bank, selected from per-burst
  aggregated telemetry (min top-2 margin over the burst, queue pressure,
  cycle budget) with zero weight-side work per switch and zero extra device
  syncs (the margins ride the burst's one transfer). ``self.telemetry``
  accumulates burst-aware mode occupancy, estimated MAC cycles, and switch
  counts;
* **self-speculative decoding** (``repro.spec``): pass
  ``speculate=SpecConfig(...)`` (plus a bank, or a controller that carries
  one) and the decode loop becomes draft-k-then-verify rounds: a jitted scan
  rolls the approximate execution point ``k`` tokens forward into the cache
  region past each slot's committed index, then ONE accurate multi-token
  forward verifies all ``k+1`` positions, accepts a draft prefix
  (greedy exact-match / rejection sampling), and rolls the cache back to the
  accepted length per slot. The round keeps the burst discipline: the cache
  is donated through draft and verify, and the emit buffers come back in a
  single host transfer. Greedy output is bit-identical to accurate-only
  serving; ``self.spec_telemetry`` records acceptance and weight-pass cycle
  savings.

* **observability** (``BatchedServer(observer=ServingObserver())``): per-
  request SLO latency metrics (time-to-first-token, inter-token latency,
  queue wait, prefill/decode wall time — streaming p50/p90/p99 histograms)
  and a structured event trace (admission, bursts with their execution
  point, controller switches, speculative draft/verify/rollback, compile
  events) with Chrome-trace and replayable JSONL exports. Every hook runs
  host-side at a sync point the loop already pays for, so the jitted
  programs are untouched and token streams are bit-identical with the
  observer on or off; ``snapshot()`` is the symmetric export of everything
  ``run()`` resets on entry.

* **sharded serving** (``BatchedServer(mesh=...)``): the same hot paths run
  tensor-parallel on a device mesh with no code fork. Every prepared weight
  leaf (including whole multi-point banks, alias-preserving) is placed with
  the logical-axis rules from ``sharding/partition.py``, the KV cache shards
  slots across the ``data`` axis and heads/latent across ``model`` (the S
  row axis is never split — decode's write index stays shard-local), the
  per-slot decode state shards slots across ``data``, and the burst/prefill
  jits carry explicit in/out shardings so the donated carry round-trips at a
  fixed placement. ``mesh=None`` (the default) skips every placement call —
  that path is byte-identical to single-device serving, and greedy token
  streams are bit-identical across mesh shapes
  (``tests/test_sharded_serving.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EngineContext, prepare_params
from repro.models import ModelApi
from repro.obs.trace import span
from repro.sharding import partition

from .kvcache import bucket_length, scatter_rows, with_cache_positions

# families whose decode caches are pure attention/MLA KV rows (scatterable,
# index-rewindable: speculative rollback and cache faults need that)
_BATCHED_PREFILL_FAMILIES = ("dense", "vlm", "moe")
# families that prefill a prompt (or chunk) as one block; the recurrent ones
# mask their state past the block's length. The rest (audio) prefill through
# the per-token masked scan
_BLOCK_PREFILL_FAMILIES = _BATCHED_PREFILL_FAMILIES + ("ssm", "hybrid")


def exact_rounding(ctx: EngineContext) -> Optional[Dict]:
    """Compile options of a serving program. Kernel mode compiles without
    excess precision: XLA may otherwise keep f32 intermediates inside a
    fusion, and which ones depends on what the fusion holds, so the fused
    kernel's program and the XLA chain's (``fused="off"``, or any mesh)
    could round apart and greedy streams would differ. Only kernel mode has
    two implementations of one dot that must agree bit for bit; the other
    modes keep XLA's default."""
    return {"xla_allow_excess_precision": False} if ctx.mode == "kernel" else None


def make_decode_sample_step(model: ModelApi, ctx: EngineContext, *,
                            temperature: float = 0.0):
    """Decode + on-device sampling: only (B, 1) ids leave the device."""

    def decode_sample(params, tokens, cache, key=None):
        logits, cache = model.decode_step(params, tokens, cache, ctx)
        return sample(logits, key, temperature=temperature), cache

    return decode_sample


def sample(logits, key, *, temperature: float = 0.0):
    """logits (B, 1, V) -> tokens (B, 1)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / temperature
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Serving steps: per-slot sampling + margin telemetry
# ---------------------------------------------------------------------------


def _sample_slots(last, base_keys, counts, temps):
    """Per-slot sampling: last (B, V) logits -> (B, 1) int32 tokens.

    ``base_keys`` (B, 2) per-request PRNG keys, ``counts`` (B,) per-request
    generated-token indices (folded in, so a request's stream is independent
    of batch composition, scheduling, AND burst size), ``temps`` (B,)
    temperatures — ``temp <= 0`` means greedy, bit-identical to plain argmax.
    """
    greedy = jnp.argmax(last, axis=-1).astype(jnp.int32)
    keys = jax.vmap(jax.random.fold_in)(base_keys, counts)
    scaled = last / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)[:, None]


def top2_margin(logits):
    """Top-2 logit margin along the last axis — the controller's confidence
    signal (shared with the speculative verify step)."""
    top2 = jax.lax.top_k(logits, 2)[0]
    return top2[..., 0] - top2[..., 1]


# ---------------------------------------------------------------------------
# Jitted hot paths: decode burst + bucketed prefill
# ---------------------------------------------------------------------------
#
# Per-slot serving state, device-resident between jitted calls:
#   tok   (slots, 1) int32   pending token (last generated)
#   count (slots,)   int32   generated-token index (PRNG fold position)
#   rem   (slots,)   int32   remaining token budget; 0 = slot inactive
#   key   (slots, 2) uint32  per-request PRNG base key
#   temp  (slots,)   float32 per-request temperature (<= 0: greedy)
#   fault (slots,)   bool    non-finite/saturated logits seen since admission


def _init_slot_state(slots: int):
    return {
        "tok": jnp.zeros((slots, 1), jnp.int32),
        "count": jnp.zeros((slots,), jnp.int32),
        "rem": jnp.zeros((slots,), jnp.int32),
        # distinct placeholder keys per slot; every admission overwrites the
        # slot's key inside the jitted prefill (the seed's identical
        # PRNGKey(0) stack relied on that overwrite happening eagerly)
        "key": jax.vmap(jax.random.PRNGKey)(jnp.arange(slots)),
        "temp": jnp.zeros((slots,), jnp.float32),
        "fault": jnp.zeros((slots,), jnp.bool_),
    }


def _admit_state(state, slot, tok, base_key, temp, max_new):
    """Write one admitted request's serving state into slot ``slot``."""
    return {
        "tok": state["tok"].at[slot].set(tok[0]),
        "count": state["count"].at[slot].set(1),  # prefill emitted token 0
        "rem": state["rem"].at[slot].set(max_new - 1),
        "key": state["key"].at[slot].set(base_key),
        "temp": state["temp"].at[slot].set(temp),
        "fault": state["fault"].at[slot].set(False),
    }


def make_decode_burst(model: ModelApi, ctx: EngineContext, burst: int,
                      sampled: bool = True,
                      logit_limit: Optional[float] = None):
    """The decode hot loop: ``burst`` single-token steps as one lax.scan.

    ``(tree, cache, state) -> (cache, state, tokens (B, burst), margins
    (B, burst), faults (B, burst))``. Tokens/margins accumulate on device;
    the caller performs ONE host transfer per burst and clips each slot's
    emitted run to its remaining budget (``state['rem']`` on entry — slots
    keep computing after their budget drains, their output is discarded and
    their rows are re-scattered at the next admission).

    ``faults`` is the per-slot numeric-fault flag, cumulative across the
    burst: step ``j`` is True iff some step ``<= j`` produced a non-finite
    logit (or, with ``logit_limit``, a logit beyond ``±logit_limit`` — the
    saturated-accumulator probe) in that slot's lane. The flag folds into
    the scan carry and persists in ``state['fault']``, so detection costs
    one ``isfinite``+reduce per step and ZERO extra host round-trips; the
    host finds the first faulted step as the count of leading False entries
    and commits only the clean prefix. Token math is untouched — with
    finite logits the emitted streams are bit-identical to a build without
    the flag.

    ``sampled=False`` compiles the all-greedy variant: no threefry fold /
    categorical per step (a real cost on small models), bit-identical to the
    sampled variant at ``temp <= 0``. The server picks per burst from the
    active requests' temperatures.

    Named scopes in the compiled program: ``burst`` holds the scan (alone,
    the embedding lookup and the loop's small state; the KV cache in its
    carry is the layer scan's carry too, updated in place, not copied), and
    in it the model's (``layers``, ``layer``, ``attention.*``,
    ``dot.<backend>``, ``lm_head``) and ``sample``: each step's fault
    probe, token choice and margin.
    """

    def decode_burst(tree, cache, state):
        keys, temps = state["key"], state["temp"]

        def step(carry, _):
            tok, cache, count, rem, fault = carry
            logits, cache = model.decode_step(tree, tok, cache, ctx)
            with jax.named_scope("sample"):
                last = logits[:, -1, :].astype(jnp.float32)
                bad = ~jnp.all(jnp.isfinite(last), axis=-1)
                if logit_limit is not None:
                    bad |= jnp.any(jnp.abs(last) > logit_limit, axis=-1)
                fault = fault | bad
                if sampled:
                    nxt = _sample_slots(last, keys, count, temps)
                    margin = top2_margin(last)
                else:
                    # one top_k yields the greedy token AND the margin (top_k
                    # and argmax share first-occurrence tie-breaking)
                    top2, idx = jax.lax.top_k(last, 2)
                    nxt = idx[:, :1].astype(jnp.int32)
                    margin = top2[..., 0] - top2[..., 1]
            active = (rem > 0).astype(jnp.int32)
            return (nxt, cache, count + active, rem - active, fault), (
                nxt[:, 0], margin, fault,
            )

        with jax.named_scope("burst"):
            (tok, cache, count, rem, fault), (toks, margins, faults) = (
                jax.lax.scan(step, (state["tok"], cache, state["count"],
                                    state["rem"], state["fault"]),
                             None, length=burst))
        state = dict(state, tok=tok, count=count, rem=rem, fault=fault)
        return (cache, state, jnp.moveaxis(toks, 0, 1),
                jnp.moveaxis(margins, 0, 1), jnp.moveaxis(faults, 0, 1))

    return decode_burst


def make_prefill_chunk(model: ModelApi, ctx: EngineContext):
    """One chunked-prefill step for the block-prefill families (attention,
    MLA, and recurrent state, whose mixers start from the row's carried
    state and leave it as it was past ``clen``).

    ``(tree, row, last, tokens (1, Cb), start, clen) -> (row, last (1, V))``.
    ``row`` is the request's PRIVATE single-row cache with its write index at
    ``start`` (the prompt rows committed by earlier chunks); ``tokens`` is
    the next ``clen`` prompt rows padded to a pow2 bucket ``Cb``. The chunk
    runs ONE S=Cb decode forward — each query attends the committed rows
    plus its own chunk prefix under the per-query-causal mask, exactly the
    key set the monolithic prefill's single forward gives it — then the
    write index rewinds to ``start + clen`` so the padded tail is invisible
    scratch, reclaimed by the next chunk. ``last`` returns the logits at the
    chunk's final REAL row: once the prompt is exhausted this is the
    sampling input for token 0 (:func:`make_chunk_admit`).

    A prompt that fits one chunk runs the same program shape as monolithic
    prefill; split prompts agree to reduction-order ulps (token streams are
    asserted identical, the repo-wide cross-shape contract). Compiles once
    per chunk bucket: O(log chunk_budget) programs.
    """

    def chunk(tree, row, last, tokens, start, clen):
        logits, row = model.decode_step(tree, tokens, row, ctx, clen=clen)
        new_last = jax.lax.dynamic_slice_in_dim(logits, clen - 1, 1, axis=1)
        new_last = new_last[:, 0, :].astype(jnp.float32)
        row = with_cache_positions(row, (start + clen)[None])
        return row, new_last

    return chunk


def make_scan_chunk(model: ModelApi, ctx: EngineContext):
    """Chunked prefill for the audio family: the masked-scan prefill over
    one chunk, with the (state, last-logits) carry crossing chunks.

    Same signature as :func:`make_prefill_chunk`; ``start`` is unused (mixer
    state carries no positional index) and steps past ``clen`` run but have
    their state update masked out, so chunk bucketing composes with
    recurrent state exactly as whole-prompt bucketing does.
    """

    def chunk(tree, row, last, tokens, start, clen):
        def step(carry, xs):
            row, last = carry
            tok_i, i = xs
            logits, new_row = model.decode_step(tree, tok_i[None, None], row, ctx)
            valid = i < clen
            row = jax.tree.map(lambda n, o: jnp.where(valid, n, o), new_row, row)
            last = jnp.where(valid, logits[:, -1, :].astype(jnp.float32), last)
            return (row, last), None

        (row, last), _ = jax.lax.scan(
            step, (row, last), (tokens[0], jnp.arange(tokens.shape[1]))
        )
        return row, last

    return chunk


def make_chunk_admit():
    """Finalize a chunked prefill: sample token 0 from the accumulated last
    logits, scatter the finished row cache into its slot, admit the slot
    state — the shared :func:`_finish_prefill` tail as its own jitted
    program. ``(cache, state, row, last, slot, base_key, temp, max_new) ->
    (tok (1, 1), margin (1,), cache, state)``."""

    def admit(cache, state, row, last, slot, base_key, temp, max_new):
        return _finish_prefill(cache, state, row, last, slot, base_key, temp,
                               max_new)

    return admit


def make_bucketed_prefill(model: ModelApi, ctx: EngineContext, max_len: int):
    """Whole-prompt prefill for the block-prefill families, scatter included.

    ``(tree, cache, state, tokens (1, Pb), plen, slot, base_key, temp,
    max_new) -> (tok (1, 1), margin (1,), cache, state)``. ``tokens`` is the
    prompt padded to a power-of-two bucket ``Pb`` (suffix padding, so MoE
    dispatch ranks of real tokens are untouched); the first sampled token
    comes from the logits at ``plen - 1`` and the fresh row cache is written
    into slot ``slot`` with its index rewound to ``plen`` — the padded
    tail's KV rows are invisible garbage, overwritten by decode, and
    recurrent state ignores it.

    Compiles once per bucket shape: O(log max_len) programs total.
    """

    def prefill(tree, cache, state, tokens, plen, slot, base_key, temp, max_new):
        row = model.make_cache(1, max_len, dtype=jnp.float32)
        logits, row = model.decode_step(tree, tokens, row, ctx, clen=plen)
        last = jax.lax.dynamic_slice_in_dim(logits, plen - 1, 1, axis=1)
        last = last[:, 0, :].astype(jnp.float32)
        row = with_cache_positions(row, plen[None])
        return _finish_prefill(cache, state, row, last, slot, base_key, temp,
                               max_new)

    return prefill


def make_scan_prefill(model: ModelApi, ctx: EngineContext, max_len: int):
    """Prefill for the audio family: one jitted ``lax.scan`` over the padded
    prompt instead of a per-token host loop.

    Steps past ``plen`` run but their state update is masked out
    (``jnp.where`` select on every cache leaf), so buckets compose with
    recurrent state too. Same signature and compile-count bound as
    :func:`make_bucketed_prefill`.
    """

    def prefill(tree, cache, state, tokens, plen, slot, base_key, temp, max_new):
        row0 = model.make_cache(1, max_len, dtype=jnp.float32)
        last0 = jnp.zeros((1, model.cfg.vocab_size), jnp.float32)

        def step(carry, xs):
            row, last = carry
            tok_i, i = xs
            logits, new_row = model.decode_step(tree, tok_i[None, None], row, ctx)
            valid = i < plen
            row = jax.tree.map(lambda n, o: jnp.where(valid, n, o), new_row, row)
            last = jnp.where(valid, logits[:, -1, :].astype(jnp.float32), last)
            return (row, last), None

        (row, last), _ = jax.lax.scan(
            step, (row0, last0), (tokens[0], jnp.arange(tokens.shape[1]))
        )
        return _finish_prefill(cache, state, row, last, slot, base_key, temp,
                               max_new)

    return prefill


def _finish_prefill(cache, state, row, last, slot, base_key, temp, max_new):
    """Shared prefill tail: sample token 0, scatter the row, admit the slot."""
    tok = _sample_slots(last, base_key[None, :], jnp.zeros((1,), jnp.int32),
                        temp[None])
    cache = scatter_rows(cache, row, slot)
    state = _admit_state(state, slot, tok, base_key, temp, max_new)
    return tok, top2_margin(last), cache, state


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (P,) int32, P >= 1
    max_new: int
    temperature: float = 0.0      # <= 0: greedy
    seed: Optional[int] = None    # PRNG stream seed; defaults to rid
    # deadline in seconds from run entry; checked at the loop's existing host
    # sync points (burst boundaries), so expiry granularity is one burst.
    # None: no deadline (ResilienceConfig.default_deadline_s may fill it in)
    deadline_s: Optional[float] = None
    generated: Optional[List[int]] = None
    margins: Optional[List[float]] = None  # top-2 logit margin per generated token


def _checked_prompt(req: Request) -> np.ndarray:
    prompt = np.asarray(req.prompt, np.int32)
    if prompt.size == 0:
        raise ValueError(
            f"request {req.rid}: empty prompt — prompts must carry at least "
            "one token (seed with BOS)"
        )
    return prompt


@dataclasses.dataclass
class BatchedServer:
    """Continuous batching over ``slots`` concurrent sequences.

    ``burst`` is the decode granularity: one jitted scan of up to ``burst``
    single-token steps per host round-trip, with admission/eviction at burst
    boundaries. ``burst=1`` reproduces the per-token loop exactly; larger
    bursts produce identical per-request streams (greedy is bit-identical,
    sampled streams fold the PRNG by token index) while cutting Python
    dispatch and host transfers by the burst factor. ``host_transfers``
    counts device->host round-trips for the run.

    ``prepare_weights=True`` (default) formats the weight bank once through
    the engine's backend registry; pass False to benchmark the per-call path.

    ``controller`` switches the server to runtime-adaptive precision: each
    burst executes at the controller's current execution point (a tree from
    its multi-point weight bank), the controller observes the burst's
    aggregated margins / queue pressure, and ``self.telemetry`` accumulates
    occupancy, switch counts, and estimated MAC-cycle savings. ``params``
    may stay the raw float tree in that case — the bank carries all serving
    weights.

    ``speculate`` (a :class:`repro.spec.SpecConfig`) switches the decode loop
    to self-speculative rounds served from a multi-point ``bank`` (defaulting
    to ``controller.bank``): draft ``draft_len`` tokens at the draft point,
    verify all of them plus a bonus position in one accurate multi-token
    forward, commit the accepted prefix, roll the KV cache back. Requires a
    scatterable (attention/MLA) cache family — recurrent state cannot roll
    back. With a controller attached, the controller picks the draft point
    per round; ``self.telemetry``'s cycle fields then describe draft-point
    occupancy only, and ``self.spec_telemetry`` is the cycle-accounting
    authority.

    ``resilience`` (a :class:`repro.resilience.ResilienceConfig`) switches
    the server from fail-stop to shed/quarantine/degrade: oversized or empty
    prompts and queue overflow are *shed* with structured reasons instead of
    raising, per-request deadlines are enforced at burst boundaries, and
    slots whose logits go non-finite are quarantined and evicted before
    their state can corrupt a neighbor (the detection flag rides the burst
    carry — zero extra host round-trips). Every request then ends in exactly
    one ``self.outcomes[rid]`` :class:`~repro.resilience.RequestOutcome`;
    ``run()`` still returns rid -> tokens (partial for expired/faulted, shed
    requests excluded). ``resilience=None`` (default) keeps the legacy
    contract byte-identical. ``injector`` (a
    :class:`~repro.resilience.FaultInjector`) fires deterministic faults at
    chosen decode rounds — test/benchmark instrumentation, never wired in
    production.

    ``mesh`` serves tensor-parallel on a device mesh (axes from
    ``data``/``model``/``pod``): weights, KV cache, and slot state are placed
    once at construction with the logical-axis sharding rules and the jitted
    hot paths carry explicit in/out shardings. ``mesh=None`` keeps the
    single-device path byte-identical (no placement calls at all);
    ``self.shardings`` holds the :class:`~repro.sharding.partition.\
ServingShardings` bundle (``partition.serving_sharding_report`` summarizes
    it) when a mesh is attached.
    """

    model: ModelApi
    ctx: EngineContext
    params: object
    slots: int = 4
    max_len: int = 256
    burst: int = 8
    prepare_weights: bool = True
    controller: Optional[object] = None  # repro.runtime.ModeController
    speculate: Optional[object] = None   # repro.spec.SpecConfig
    bank: Optional[object] = None        # repro.runtime.MultiPointBank
    mesh: Optional[object] = None        # jax.sharding.Mesh
    observer: Optional[object] = None    # repro.obs.ServingObserver
    resilience: Optional[object] = None  # repro.resilience.ResilienceConfig
    injector: Optional[object] = None    # repro.resilience.FaultInjector

    def __post_init__(self):
        if self.burst < 1:
            raise ValueError(f"burst must be >= 1, got {self.burst}")
        self._bank = self.bank
        if self._bank is None and self.controller is not None:
            self._bank = self.controller.bank
        if self.controller is not None:
            from repro.runtime import TelemetryRecorder

            self.telemetry = TelemetryRecorder.for_bank(self.controller.bank)
        else:
            self.telemetry = None
            if self.prepare_weights and self.speculate is None:
                self.params = prepare_params(
                    self.params, self.ctx.policy, self.ctx.mode, specs=self.model.specs()
                )
        self.batched_prefill = self.model.cfg.family in _BATCHED_PREFILL_FAMILIES
        self.block_prefill = self.model.cfg.family in _BLOCK_PREFILL_FAMILIES
        self.spec = None
        self.spec_telemetry = None
        if self.speculate is not None:
            if self._bank is None:
                raise ValueError(
                    "speculate= needs a multi-point weight bank: pass bank= "
                    "or a controller that carries one"
                )
            if not self.batched_prefill:
                raise ValueError(
                    f"speculative serving needs a scatterable KV cache; the "
                    f"{self.model.cfg.family!r} family carries recurrent "
                    "state that cannot roll back past rejected drafts"
                )
        self.cache = self.model.make_cache(self.slots, self.max_len, dtype=jnp.float32)
        self.active: Dict[int, Request] = {}
        self._state = _init_slot_state(self.slots)
        self._slot_start = np.zeros((self.slots,), np.int32)  # committed KV rows
        self.host_transfers = 0
        self._run_complete: Optional[bool] = None  # None: never ran
        # resilience accounting (per run, reset in _begin_run)
        self.outcomes: Dict[int, object] = {}  # rid -> RequestOutcome
        self._round_idx = 0
        self._t0 = 0.0
        self._fault_counts = {"shed": 0, "expired": 0, "faulted": 0,
                              "deadline_misses": 0}
        self._deadlines: Dict[int, Optional[float]] = {}
        self._chunk_fns = None       # (chunk, admit) jits, frontend-only
        self._frontend_meta = None   # set by the streaming frontend
        # mesh serving: derive every placement once from the logical-axis
        # rules and commit weights / cache / slot state to the mesh. With
        # mesh=None nothing below runs — that path stays byte-identical.
        self.shardings = None
        if self.mesh is not None:
            specs = self.model.specs()
            sample_tree = (self._bank.tree(self._bank.names[0])
                           if self._bank is not None else self.params)
            self.shardings = partition.serving_shardings(
                self.mesh, params=sample_tree, cache=self.cache,
                state=self._state, specs=specs, cfg=self.model.cfg,
                max_len=self.max_len,
            )
            if self._bank is not None:
                from repro.runtime.bank import place_bank

                place_bank(self._bank, self.mesh, specs)
            else:
                self.params = jax.device_put(self.params, self.shardings.params)
            self.cache = jax.device_put(self.cache, self.shardings.cache)
            self._state = jax.device_put(self._state, self.shardings.state)
        if self.speculate is not None:
            from repro.spec import SpeculativeDecoder

            self.spec = SpeculativeDecoder(
                self.model, self.ctx, self._bank, self.speculate,
                shardings=self.shardings,
            )
            self.spec_telemetry = self.spec.telemetry
        # the two jitted hot paths: cache + slot state are donated so XLA
        # writes them in place instead of copying the KV buffers per call.
        # Burst variants (sampled / all-greedy) compile lazily on first use.
        self._burst_fns = {}
        prefill_factory = (
            make_bucketed_prefill if self.block_prefill else make_scan_prefill
        )
        prefill_sharding_kwargs = {}
        if self.shardings is not None:
            sh, r = self.shardings, self.shardings.replicated
            prefill_sharding_kwargs = dict(
                # (tree, cache, state, tokens, plen, slot, key, temp, max_new);
                # the tree inherits its committed placement: carmen/int8 bank
                # points carry distinct pytree aux data (one shardings tree
                # cannot describe them all), and kernel-mode points — which DO
                # share a treedef via the traced params vector — are already
                # placed by place_bank. cache/state are pinned so the donated
                # carry round-trips at a fixed placement
                in_shardings=(None, sh.cache, sh.state, r, r, r, r, r, r),
                out_shardings=(r, r, sh.cache, sh.state),
            )
        self.prefill = jax.jit(
            prefill_factory(self.model, self.ctx, self.max_len),
            donate_argnums=(1, 2), compiler_options=exact_rounding(self.ctx),
            **prefill_sharding_kwargs,
        )

    def _serving_tree(self):
        """The tree prefill / non-speculative decode executes at.

        Speculative serving prefills at the VERIFY point so the committed
        prompt KV is accurate — the bit-exactness guarantee starts there.
        """
        if self.spec is not None:
            return self._bank.tree(self.spec.verify_point)
        return self.controller.tree() if self.controller is not None else self.params

    def _prefill_slot(self, slot: int, req: Request):
        """Run the prompt into this slot's cache; sets ``req.generated``.

        One jitted call: the prompt (padded to its length bucket) prefills a
        FRESH single-row cache, the row is scattered into the slot, and the
        slot's serving state is admitted — prefilling never touches other
        active slots' state, and only the first token + margin cross back to
        the host.
        """
        prompt = _checked_prompt(req)
        tree = self._serving_tree()
        seed = req.seed if req.seed is not None else req.rid
        bucket = bucket_length(len(prompt), self.max_len)
        obs, point_name = self.observer, self._serving_point()
        if obs is not None:
            obs.prefill_begin(req.rid, bucket, point_name)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(prompt)] = prompt
        with span("engine.prefill", rid=req.rid), self._scope():
            tok, margin, self.cache, self._state = self.prefill(
                tree, self.cache, self._state, jnp.asarray(padded),
                jnp.int32(len(prompt)), jnp.int32(slot),
                jax.random.PRNGKey(seed), jnp.float32(req.temperature),
                jnp.int32(req.max_new),
            )
        with span("engine.prefill.wait", rid=req.rid):
            tok, margin = jax.device_get((tok, margin))
        self.host_transfers += 1
        self._slot_start[slot] = len(prompt)
        req.generated = [int(tok[0, 0])]
        req.margins = [float(margin[0])]
        if obs is not None:
            obs.prefill_end(req.rid, len(prompt), point_name)
        if self.telemetry is not None:
            self.telemetry.record_prefill(point_name, len(prompt))

    def _serving_point(self) -> Optional[str]:
        """Name of the execution point prefill / static decode runs at
        (None when serving a plain prepared tree, no bank)."""
        if self.spec is not None:
            return self.spec.verify_point
        return self.controller.point if self.controller is not None else None

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Serve requests to completion; returns rid -> generated tokens.

        Per-token top-2 margins land on each request's ``.margins``; with a
        controller attached, ``self.telemetry`` holds the adaptive-run record.
        ``run`` is reusable: telemetry, controller state, speculative
        counters, observer state, the transfer count, AND any slots stranded
        by an aborted prior run all start fresh on every invocation
        (``_begin_run``); ``snapshot()`` exports exactly the state one run
        accumulated, whether it completed or died mid-flight.

        With ``resilience`` attached the fail-stop contract becomes
        shed/quarantine/degrade: invalid or overflowing requests are shed
        with structured reasons instead of raising, deadlines evict at burst
        boundaries, faulted slots are quarantined, and every request ends in
        exactly one ``self.outcomes[rid]``. The returned dict then carries
        partial streams for expired/faulted requests and omits shed ones.
        """
        res = self.resilience
        shed_pre: List[Tuple[Request, str]] = []
        admitted: List[Request] = []
        # deadlines resolve into RUN-LOCAL state, never onto the caller's
        # Request objects: a list reused across servers (or runs) must not
        # carry one run's resolved default_deadline_s into the next
        deadlines = {req.rid: self._resolve_deadline(req) for req in requests}
        for req in requests:  # reject/shed before any state mutates
            reason = self._admission_error(req)
            if reason is not None:
                shed_pre.append((req, reason))
                continue
            admitted.append(req)
        if res is not None and res.queue_limit is not None:
            from repro.resilience.outcome import shed_overflow

            admitted, dropped = shed_overflow(
                admitted, res.queue_limit, res.shed_policy,
                deadline_of=lambda r: deadlines[r.rid],
            )
            shed_pre.extend((r, "queue_full") for r in dropped)
        self._begin_run(requests)
        self._deadlines = deadlines
        obs = self.observer
        for req, reason in shed_pre:
            self._shed(req, reason)
        aborted = True
        try:
            queue = list(admitted)
            results: Dict[int, List[int]] = {}
            slot_of: Dict[int, int] = {}
            free = list(range(self.slots))
            shed_since = len(shed_pre)  # sheds since the last controller observe
            while queue or self.active:
                if res is not None:  # shed queued work that can no longer win
                    queue, n_shed = self._expire_queue(queue)
                    shed_since += n_shed
                while queue and free:
                    req = queue.pop(0)
                    slot = free.pop(0)
                    if obs is not None:
                        obs.request_admitted(req.rid, slot)
                    self._prefill_slot(slot, req)
                    self._after_prefill(slot, req, results, slot_of, free)
                if not self.active:
                    continue
                queue_depth, free_slots = len(queue), len(free)
                summary, misses = self._decode_round(slot_of, results, free)
                if self.controller is not None:
                    self._observe(summary["point"], summary["emitted"],
                                  summary["steps"], queue_depth, free_slots,
                                  summary["min_margin"],
                                  deadline_misses=misses, shed=shed_since)
                    shed_since = 0
            aborted = False
        finally:
            self._end_run(aborted)
        return results

    # -- per-round bookkeeping (shared by run() and the streaming frontend) ---

    def _after_prefill(self, slot: int, req: Request, results: Dict,
                       slot_of: Dict[int, int], free: List[int]) -> None:
        """Post-prefill triage: quarantine a non-finite prefill, retire a
        request whose budget the prefill token already satisfied, otherwise
        activate the slot."""
        res = self.resilience
        if (res is not None and res.fault_isolation
                and not math.isfinite(req.margins[0])):
            # non-finite prefill logits: the sampled token is garbage —
            # quarantine before anything is committed (the slot's rows are
            # reclaimed by the next scatter)
            req.generated, req.margins = [], []
            results[req.rid] = req.generated
            self._finish(req, "faulted", reason="prefill_nonfinite")
            free.append(slot)
            return
        if len(req.generated) >= req.max_new:  # prefill already done
            results[req.rid] = req.generated
            self._finish(req, "ok")
            free.append(slot)
            return
        self.active[req.rid] = req
        slot_of[req.rid] = slot

    def _decode_round(self, slot_of: Dict[int, int], results: Dict,
                      free: List[int]) -> Tuple[Dict, int]:
        """One decode burst, or one speculative round, over the active slots,
        then its settle (:meth:`_settle_round`). Returns the round summary
        and the deadline misses."""
        if self.spec is not None:
            return self._spec_round(slot_of, results, free)
        return self._burst_round(slot_of, results, free)

    def _settle_round(self, summary: Dict, results: Dict,
                      slot_of: Dict[int, int], free: List[int]) -> int:
        """After one burst/spec round: quarantine faulted lanes, evict
        deadline misses, retire finished requests. Returns the number of
        deadline misses (the controller signal)."""
        res = self.resilience
        for rid in summary["faulted"]:  # quarantine at the boundary
            req = self.active.pop(rid)
            results[rid] = req.generated
            self._finish(req, "faulted", reason=summary["fault_reason"])
            free.append(slot_of.pop(rid))
        misses = 0
        if res is not None:
            now = time.perf_counter() - self._t0
            for rid, req in list(self.active.items()):
                d = self._deadline(req)
                if d is not None and now >= d:
                    self.active.pop(rid)
                    results[rid] = req.generated
                    self._finish(req, "expired", reason="deadline")
                    free.append(slot_of.pop(rid))
                    misses += 1
        done = [r for r, q in self.active.items()
                if len(q.generated) >= q.max_new]
        for rid in done:
            req = self.active.pop(rid)
            results[rid] = req.generated
            self._finish(req, "ok")
            free.append(slot_of.pop(rid))
        return misses

    # -- admission: validation + run-local deadline resolution ----------------

    def _admission_error(self, req: Request) -> Optional[str]:
        """Validate one request at admission. Resilient servers get a
        structured shed reason (or None when admissible); the legacy
        ``resilience=None`` contract raises instead (byte-identical to the
        original fail-stop path). Shared by ``run()`` and the streaming
        frontend's ``submit``."""
        scratch = self.spec.draft_len if self.spec is not None else 0
        prompt = np.asarray(req.prompt, np.int32)
        too_long = len(prompt) + req.max_new + scratch > self.max_len
        if self.resilience is None:  # legacy fail-stop contract
            _checked_prompt(req)
            if too_long:
                extra = (f" + draft_len ({scratch})"
                         if self.spec is not None else "")
                why = (" — the verify forward needs draft_len rows of "
                       "scratch headroom" if self.spec is not None else
                       " — the KV cache would overflow mid-decode")
                raise ValueError(
                    f"request {req.rid}: prompt ({len(prompt)}) + max_new "
                    f"({req.max_new}){extra} exceeds max_len "
                    f"({self.max_len}){why}"
                )
            return None
        if prompt.size == 0:
            return "empty_prompt"
        if too_long:
            return "too_long"
        return None

    def _resolve_deadline(self, req: Request) -> Optional[float]:
        """The deadline this run enforces for ``req`` — its own, else the
        resilience default. Pure: the Request is never written."""
        if req.deadline_s is not None:
            return req.deadline_s
        res = self.resilience
        return res.default_deadline_s if res is not None else None

    def _deadline(self, req: Request) -> Optional[float]:
        """Run-local resolved deadline (run-relative seconds); falls back to
        the request's own field for rids this run never registered."""
        return self._deadlines.get(req.rid, req.deadline_s)

    # -- resilience: outcome bookkeeping --------------------------------------

    def _finish(self, req: Request, status: str,
                reason: Optional[str] = None) -> None:
        """Record the terminal outcome of an admitted request."""
        from repro.resilience.outcome import RequestOutcome

        tokens = len(req.generated or [])
        self.outcomes[req.rid] = RequestOutcome(
            rid=req.rid, status=status, reason=reason, tokens=tokens,
            deadline_s=self._deadline(req),
            wall_s=time.perf_counter() - self._t0,
        )
        obs = self.observer
        if status == "ok":
            if obs is not None:
                obs.request_completed(req.rid)
        elif status == "expired":
            self._fault_counts["expired"] += 1
            self._fault_counts["deadline_misses"] += 1
            if obs is not None:
                obs.request_expired(req.rid, tokens)
        elif status == "aborted":
            # streaming-frontend cancellation (client disconnect); the batch
            # run() path never produces this status itself
            self._fault_counts["aborted"] = (
                self._fault_counts.get("aborted", 0) + 1)
            if obs is not None:
                obs.request_cancelled(req.rid, tokens)
        else:
            self._fault_counts["faulted"] += 1
            if obs is not None:
                obs.request_faulted(req.rid, tokens, reason)

    def _shed(self, req: Request, reason: str) -> None:
        """Record a rejected-at-admission request (never held a slot)."""
        from repro.resilience.outcome import RequestOutcome

        self.outcomes[req.rid] = RequestOutcome(
            rid=req.rid, status="shed", reason=reason, tokens=0,
            deadline_s=self._deadline(req),
            wall_s=time.perf_counter() - self._t0,
        )
        self._fault_counts["shed"] += 1
        if self.observer is not None:
            self.observer.request_shed(req.rid, reason)

    def _expire_queue(self, queue: List[Request]):
        """Shed queued requests whose deadline already passed — admitting
        them would burn prefill on work that cannot finish in time."""
        now = time.perf_counter() - self._t0
        kept, n_shed = [], 0
        for req in queue:
            d = self._deadline(req)
            if d is not None and now >= d:
                self._shed(req, "deadline_expired")
                n_shed += 1
            else:
                kept.append(req)
        return kept, n_shed

    # -- run lifecycle: symmetric reset / export ------------------------------

    def _begin_run(self, requests: List[Request]) -> None:
        """Reset every per-run accumulator ``snapshot()`` exports.

        Slots stranded by an aborted prior run are dropped here (their device
        rows are reclaimed by the next admission's scatter), so a failed run
        can never leak tokens, telemetry, or transfer counts into the next
        run's results or exported snapshots.
        """
        self.active.clear()
        self.outcomes = {}
        self._round_idx = 0
        self._t0 = time.perf_counter()
        self._fault_counts = {"shed": 0, "expired": 0, "faulted": 0,
                              "deadline_misses": 0}
        self._deadlines = {}  # rid -> resolved run-relative deadline
        self._run_requests = list(requests)
        if self.telemetry is not None:
            self.telemetry.reset()
        if self.controller is not None:
            self.controller.reset()
            self.controller.on_switch = (
                self.observer.controller_switch
                if self.observer is not None else None
            )
        if self.spec is not None:
            self.spec.reset()
            self.spec.observer = self.observer
        self.host_transfers = 0
        self._run_complete = False
        if self.observer is not None:
            self.observer.run_begin(self._run_meta(), requests)

    def _end_run(self, aborted: bool) -> None:
        self._run_complete = not aborted
        if aborted:
            # every request the run touched but never resolved gets an
            # ``aborted`` outcome (with its partial token count), so a run
            # that died mid-flight is still fully attributable from
            # ``snapshot()``
            from repro.resilience.outcome import RequestOutcome

            wall = time.perf_counter() - self._t0
            for req in getattr(self, "_run_requests", []):
                if req.rid not in self.outcomes:
                    self.outcomes[req.rid] = RequestOutcome(
                        rid=req.rid, status="aborted",
                        tokens=len(req.generated or []),
                        deadline_s=self._deadline(req), wall_s=wall,
                    )
        if self.observer is not None:
            self.observer.run_end(aborted, self.host_transfers,
                                  self._telemetry_records())

    def _run_meta(self) -> Dict:
        """The trace-header metadata for one run (sharding report included
        under a mesh)."""
        meta = {
            "family": self.model.cfg.family,
            "mode": self.ctx.mode,
            "slots": self.slots,
            "burst": self.burst,
            "max_len": self.max_len,
            "adaptive": self.controller is not None,
            "speculative": self.spec is not None,
        }
        if self.spec is not None:
            meta["draft_len"] = self.spec.draft_len
            meta["verify_point"] = self.spec.verify_point
        if self.resilience is not None:
            meta["resilience"] = {
                "queue_limit": self.resilience.queue_limit,
                "shed_policy": self.resilience.shed_policy,
                "fault_isolation": self.resilience.fault_isolation,
                "default_deadline_s": self.resilience.default_deadline_s,
            }
        if self._frontend_meta is not None:
            meta["frontend"] = dict(self._frontend_meta)
        if self.shardings is not None:
            meta["sharding"] = partition.serving_sharding_report(self.shardings)
        engine = self._engine_cost_meta()
        if engine is not None:
            meta["engine"] = engine
        return meta

    def _engine_cost_meta(self) -> Optional[Dict]:
        """The trace header's ``engine`` block: per-point cycle estimates plus
        the per-weight (shape, depth, bits) table — everything the PE-array
        simulator needs to replay this trace without reconstructing the
        model. ``None`` for exact-mode serving (no precision knob, nothing to
        attribute cycles to). Computed once per server (the bank and policy
        are fixed at construction)."""
        if not hasattr(self, "_engine_meta_cache"):
            from repro.runtime.telemetry import (estimate_point_cycles,
                                                 layer_cost_table)

            specs = self.model.specs()
            if self._bank is not None:
                bank = self._bank
                policies = {p.name: p.policy for p in bank.points}
                self._engine_meta_cache = {
                    "points": {n: bank.cycles_per_token[n] for n in bank.names},
                    "reference": bank.reference,
                    "cycle_model": getattr(bank, "cycle_model", "analytic"),
                    "layers": layer_cost_table(bank.tree(bank.reference),
                                               policies, specs=specs),
                }
            elif self.ctx.mode != "exact" and self.ctx.policy is not None:
                # static prepared serving: a single-point "bank"
                self._engine_meta_cache = {
                    "points": {"static": estimate_point_cycles(
                        self.params, self.ctx.policy, specs=specs)},
                    "reference": "static",
                    "cycle_model": "analytic",
                    "layers": layer_cost_table(
                        self.params, {"static": self.ctx.policy}, specs=specs),
                }
            else:
                self._engine_meta_cache = None
        return self._engine_meta_cache

    def _telemetry_records(self) -> List[Dict]:
        """The unified telemetry records (``to_dict`` shape) this run holds."""
        recs = []
        if self.telemetry is not None:
            recs.append(self.telemetry.to_dict())
        if self.spec_telemetry is not None:
            recs.append(self.spec_telemetry.to_dict())
        return recs

    def snapshot(self) -> Dict:
        """Everything one ``run()`` accumulated, as one JSON-able record.

        Symmetric with the reset in ``_begin_run``: the export covers exactly
        the state since the last run started — ``completed`` is False for a
        run that died mid-flight (and None if the server never ran), and no
        field can carry residue from an earlier run.
        """
        return {
            "completed": self._run_complete,
            "host_transfers": self.host_transfers,
            "telemetry": self._telemetry_records(),
            "observability": (self.observer.snapshot()
                              if self.observer is not None else None),
            "resilience": {
                "outcomes": {rid: o.to_dict()
                             for rid, o in self.outcomes.items()},
                "counters": dict(self._fault_counts),
            },
        }

    def collective_snapshot(self) -> Optional[Dict]:
        """Collective-traffic summary of the compiled greedy decode burst —
        the mesh-serving cost block a trace header carries. ``None`` without
        a mesh; compiles the burst program if it has not run yet."""
        if self.mesh is None:
            return None
        from repro.launch import hlo_analysis

        costs = hlo_analysis.analyze(self.compiled_burst_text())
        return {
            "collective_bytes": costs.collective_bytes,
            "collective_by_kind": costs.collective_by_kind,
        }

    def compiled_burst_text(self) -> str:
        """Optimized HLO text of the all-greedy decode burst as compiled for
        the current serving tree, cache and slot state (compiles it if it
        has not run)."""
        with self._scope():
            return (
                self.decode_burst(False)
                .lower(self._serving_tree(), self.cache, self._state)
                .compile()
                .as_text()
            )

    def _observe(self, point, tokens, steps, queue_depth, free_slots,
                 min_margin, deadline_misses=0, shed=0):
        from repro.runtime import StepSignals

        self.telemetry.record_burst(point, tokens=tokens, steps=steps,
                                    min_margin=min_margin)
        self.controller.observe(StepSignals(
            active=len(self.active),
            queue_depth=queue_depth,
            free_slots=free_slots,
            min_margin=min_margin,
            steps=steps,
            deadline_misses=deadline_misses,
            shed=shed,
        ))

    def _scope(self):
        """Ambient context for the jitted hot-path calls. A no-op without a
        mesh; with one it (a) sets the mesh (``jax.set_mesh``) so the model's
        activation constraints (``partition.constrain``) bind to it at trace
        time and
        (b) switches to partitionable threefry — the sharding-invariant PRNG
        mode, so SAMPLED streams are identical across mesh shapes (the legacy
        PRNG generates different bits when the vocab axis is sharded; greedy
        decoding never samples and is bit-identical to ``mesh=None`` either
        way)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        stack = contextlib.ExitStack()
        stack.enter_context(jax.threefry_partitionable(True))
        stack.enter_context(jax.set_mesh(self.mesh))
        return stack

    def chunk_fns(self):
        """The jitted chunked-prefill programs ``(chunk, admit)`` — the
        streaming frontend's prefill hot path. Built lazily so batch-only
        servers never trace them; ``run()`` itself never calls these.
        ``chunk`` advances a request's private row cache by one padded chunk
        (row + last-logits donated); ``admit`` is the shared
        :func:`_finish_prefill` tail (cache/state/row donated)."""
        if self._chunk_fns is None:
            factory = (make_prefill_chunk if self.block_prefill
                       else make_scan_chunk)
            if self.mesh is not None:
                raise ValueError(
                    "chunked prefill is single-device for now: the streaming "
                    "frontend rejects mesh= (ROADMAP: sharded streaming)"
                )
            self._chunk_fns = (
                jax.jit(factory(self.model, self.ctx), donate_argnums=(1, 2),
                        compiler_options=exact_rounding(self.ctx)),
                # the row is an input-only buffer here (scattered into the
                # slot cache, never returned) — donating it would just warn
                jax.jit(make_chunk_admit(), donate_argnums=(0, 1),
                        compiler_options=exact_rounding(self.ctx)),
            )
        return self._chunk_fns

    def fresh_row(self):
        """A fresh single-request prefill carry: a private ``(1, max_len)``
        row cache (write index 0) and a zeroed last-logits buffer."""
        row = self.model.make_cache(1, self.max_len, dtype=jnp.float32)
        last = jnp.zeros((1, self.model.cfg.vocab_size), jnp.float32)
        return row, last

    def decode_burst(self, sampled: bool = True):
        """The jitted burst step (``sampled=False``: the all-greedy variant)."""
        if sampled not in self._burst_fns:
            sharding_kwargs = {}
            if self.shardings is not None:
                sh = self.shardings
                buf = sh.slots((self.slots, self.burst))  # emit buffers
                sharding_kwargs = dict(
                    in_shardings=(None, sh.cache, sh.state),
                    out_shardings=(sh.cache, sh.state, buf, buf, buf),
                )
            limit = (self.resilience.logit_limit
                     if self.resilience is not None else None)
            self._burst_fns[sampled] = jax.jit(
                make_decode_burst(self.model, self.ctx, self.burst,
                                  sampled=sampled, logit_limit=limit),
                donate_argnums=(1, 2), compiler_options=exact_rounding(self.ctx),
                **sharding_kwargs,
            )
        return self._burst_fns[sampled]

    def _burst_round(self, slot_of, results: Dict,
                     free: List[int]) -> Tuple[Dict, int]:
        """One decode burst over the active slots: ``burst`` scan steps on
        device, one host transfer, per-slot budget clipping on the host,
        then the round's settle.

        Returns the round summary the scheduler acts on (tokens emitted,
        the executed point, the min margin over *clean* committed tokens,
        and the rids whose lanes faulted: their commit is clipped to the
        steps before the first bad logit, and the settle quarantines them)
        and the settle's deadline misses.
        """
        obs = self.observer
        if self.injector is not None:
            self.injector.before_round(self, self._round_idx, slot_of)
        self._round_idx += 1
        point = self.controller.point if self.controller is not None else None
        sampled = any(r.temperature > 0.0 for r in self.active.values())
        if obs is not None:
            obs.burst_begin(point)
        with span("engine.burst"), self._scope():
            self.cache, self._state, toks, margins, faults = (
                self.decode_burst(sampled)(
                    self._serving_tree(), self.cache, self._state,
                ))
        with span("engine.burst.wait"):
            toks, margins, faults = jax.device_get((toks, margins, faults))
        self.host_transfers += 1
        with span("engine.settle"):
            summary = self._commit_burst(point, toks, margins, faults,
                                         slot_of)
            return summary, self._settle_round(summary, results, slot_of,
                                               free)

    def _commit_burst(self, point, toks, margins, faults, slot_of) -> Dict:
        """The host side of one burst: each active request's emitted run,
        clipped to its budget and to the steps before a fault."""
        obs = self.observer
        isolate = (self.resilience is not None
                   and self.resilience.fault_isolation)
        emitted = 0
        burst_margins = []
        by_rid: Dict[int, List[int]] = {}
        faulted: List[int] = []
        for rid, req in self.active.items():
            s = slot_of[rid]
            n = min(self.burst, req.max_new - len(req.generated))
            if isolate and faults[s].any():
                # the flag is cumulative: clean steps are the leading False
                # run; everything from the first bad logit on is discarded
                n = min(n, int((~faults[s]).sum()))
                faulted.append(rid)
            by_rid[rid] = [int(t) for t in toks[s, :n]]
            req.generated.extend(by_rid[rid])
            req.margins.extend(float(m) for m in margins[s, :n])
            self._slot_start[s] += n
            emitted += n
            if rid not in faulted:
                burst_margins.append(float(margins[s, :n].min()))
        if obs is not None:
            obs.burst_end(point, self.burst, by_rid)
        return {
            "point": point,
            "emitted": emitted,
            "steps": self.burst,
            "min_margin": min(burst_margins) if burst_margins else None,
            "faulted": faulted,
            "fault_reason": "decode_nonfinite",
        }

    def _spec_round(self, slot_of, results: Dict,
                    free: List[int]) -> Tuple[Dict, int]:
        """One draft-k-then-verify round over the active slots, then its
        settle; returns the summary and the deadline misses, as
        :meth:`_burst_round` does.

        Each active request gains between 1 (first draft rejected) and
        ``draft_len + 1`` (all accepted + bonus) tokens, clipped to its
        ``max_new``; the KV cache comes back rolled back to the committed
        length per slot, and the device slot state (pending token, count) is
        re-synced in one fused update.

        Fault handling (the spec abort path, flags from the verify step's
        single host transfer): a *draft*-faulted lane already degraded to
        plain accurate decode inside the verify step (zero accepts, accurate
        correction token, accurate KV rewritten over the drafted scratch) —
        it commits normally and stays admitted. A *verify*-faulted lane is
        numerically unrecoverable: it commits nothing and the scheduler
        quarantines it.
        """
        st = self._state
        obs = self.observer
        if self.injector is not None:
            self.injector.before_round(self, self._round_idx, slot_of)
        self._round_idx += 1
        draft_point = self.controller.point if self.controller is not None else None
        if obs is not None:
            obs.burst_begin(draft_point or self.spec.default_draft_point,
                            kind="spec")
        with self._scope():
            (emitted, accepted, margins, draft_fault, verify_fault,
             self.cache, point) = self.spec.round(
                st["tok"], self.cache, st["key"], st["count"], st["temp"],
                self._slot_start, draft_point=draft_point,
            )
        self.host_transfers += 1
        with span("engine.settle"):
            summary = self._commit_spec(st, point, emitted, accepted, margins,
                                        draft_fault, verify_fault, slot_of)
            return summary, self._settle_round(summary, results, slot_of,
                                               free)

    def _commit_spec(self, st, point, emitted, accepted, margins, draft_fault,
                     verify_fault, slot_of) -> Dict:
        """The host side of one speculative round: commit each lane's
        accepted run and re-sync the device slot state."""
        obs = self.observer
        isolate = (self.resilience is not None
                   and self.resilience.fault_isolation)
        accs, emits, round_margins = [], [], []
        by_rid: Dict[int, List[int]] = {}
        faulted: List[int] = []
        draft_faults: List[int] = []
        sync_slots, sync_toks, sync_counts = [], [], []
        for rid, req in self.active.items():
            s = slot_of[rid]
            if isolate and bool(verify_fault[s]):
                by_rid[rid] = []
                faulted.append(rid)
                continue
            if isolate and bool(draft_fault[s]):
                draft_faults.append(rid)
            n = min(int(accepted[s]) + 1, req.max_new - len(req.generated))
            by_rid[rid] = [int(t) for t in emitted[s, :n]]
            req.generated.extend(by_rid[rid])
            req.margins.extend(float(m) for m in margins[s, :n])
            self._slot_start[s] += int(accepted[s]) + 1
            accs.append(int(accepted[s]))
            emits.append(n)
            round_margins.append(float(margins[s, :n].min()))
            sync_slots.append(s)
            sync_toks.append(int(emitted[s, n - 1]))
            sync_counts.append(len(req.generated))
        if obs is not None:
            extra = {"draft_faults": draft_faults} if draft_faults else {}
            obs.burst_end(point, self.spec.draft_len + 1, by_rid, kind="spec",
                          accepted=accs, **extra)
        if sync_slots:
            sl = jnp.asarray(sync_slots, jnp.int32)
            self._state = dict(
                st,
                tok=st["tok"].at[sl].set(
                    jnp.asarray(sync_toks, jnp.int32)[:, None]),
                count=st["count"].at[sl].set(
                    jnp.asarray(sync_counts, jnp.int32)),
            )
        self.spec.telemetry.record_round(point, self.spec.verify_point, accs,
                                         emits)
        # a round executes draft_len single-token steps + one multi-token
        # verify forward: that is what the budget EMA / decode_steps cover
        return {
            "point": point,
            "emitted": sum(emits),
            "steps": self.spec.draft_len + 1,
            "min_margin": min(round_margins) if round_margins else None,
            "faulted": faulted,
            "fault_reason": "verify_nonfinite",
        }
