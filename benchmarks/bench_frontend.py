"""Continuous-batching frontend benchmark: identity, interleaving, arrivals.

Three configs, each with a CI gate (``--smoke`` exits nonzero on violation):

* **identity** — the same greedy workload served by ``BatchedServer.run()``
  (monolithic prefill, batch admission) and through the
  :class:`~repro.serve.frontend.ContinuousScheduler` with a deliberately
  tiny chunk budget, per model family (attention chunking and the recurrent
  scan carry are different programs). Gate: token streams bit-identical —
  chunked prefill is a scheduling change, never a numerics change.

* **interleave** — short requests are decoding on every slot when one long
  prompt is admitted mid-run. Chunked arm vs ``monolithic_prefill`` arm on
  the same scheduler. Gates: the chunked arm's
  ``max_prefill_rows_between_bursts`` stays within one chunk budget (the
  structural no-stall bound: decoding slots wait at most ``chunk_tokens``
  prefill rows between bursts), and its p99 inter-token latency does not
  exceed the monolithic arm's *max* inter-token stall — the stall the
  monolithic arm takes in one tick is exactly what chunking amortizes.

* **arrival** — a seeded Poisson arrival process at a fixed offered rate
  through the scheduler with per-request deadlines and a bounded queue.
  Records TTFT / inter-token / queue-wait percentiles (submission-anchored:
  TTFT includes queue time) next to tok/s. Gates: every offered request
  settles with an attributed outcome, every served request has a TTFT
  sample, and the structural interleaving bound holds under load.

    PYTHONPATH=src python -m benchmarks.bench_frontend --smoke
"""
from __future__ import annotations

import sys
import time

import numpy as np

from repro.core import EngineContext
from repro.resilience import ResilienceConfig
from repro.serve.engine import BatchedServer, Request
from repro.serve.frontend import ContinuousScheduler, FrontendConfig

from ._common import (
    attach_observer,
    base_record,
    bench_parser,
    emit_record,
    latency_block,
    load_model,
    make_requests,
    timed,
)

IDENTITY_ARCHS = {
    "dense": "olmo-1b",
    "ssm": "mamba2-780m",
    "mla_moe": "deepseek-v3-671b",
}


def _build(arch, args, *, max_len, resilience=None):
    cfg, model, params = load_model(arch, full_size=args.full_size,
                                    d_model=args.d_model)
    ctx = EngineContext(mode="exact", compute_dtype=cfg.compute_dtype)
    srv = BatchedServer(model, ctx, params, slots=args.slots, max_len=max_len,
                        burst=args.burst, resilience=resilience)
    return cfg, srv


def _frontend_run(server, reqs, *, chunk_tokens, monolithic=False):
    """Serve ``reqs`` through the scheduler (all submitted up front);
    returns (seconds, results, stats)."""
    sched = ContinuousScheduler(
        server, FrontendConfig(chunk_tokens=chunk_tokens,
                               monolithic_prefill=monolithic))
    t0 = time.perf_counter()
    with sched:
        for r in reqs:
            sched.submit(r)
        out = sched.drain()
    return time.perf_counter() - t0, out, dict(sched.stats)


# ---------------------------------------------------------------------------
# identity: chunked frontend streams == run() streams, per family
# ---------------------------------------------------------------------------


def _identity_config(args):
    rows = []
    for family, arch in IDENTITY_ARCHS.items():
        if args.smoke and family == "mla_moe":
            continue
        cfg, srv = _build(arch, args,
                          max_len=args.prompt_len + args.max_new + 2)
        work = lambda: make_requests(cfg, args.requests,
                                     prompt_len=args.prompt_len,
                                     max_new=args.max_new)
        dt_ref, ref = timed(lambda: srv.run(work()))
        dt_fe, out, stats = _frontend_run(srv, work(),
                                          chunk_tokens=args.chunk_tokens)
        total = sum(len(v) for v in ref.values())
        rows.append({
            "family": family,
            "arch": arch,
            "chunk_tokens": args.chunk_tokens,
            "run_tok_s": round(total / max(dt_ref, 1e-9), 1),
            "frontend_tok_s": round(total / max(dt_fe, 1e-9), 1),
            "prefill_chunks_per_prompt": round(
                stats["prefill_rows"] / max(args.prompt_len, 1)
                / max(args.requests, 1), 3),
            "bit_identical": out == ref,
        })
    return {"rows": rows}


# ---------------------------------------------------------------------------
# interleave: a long prompt admitted mid-run must not stall decode
# ---------------------------------------------------------------------------


def _interleave_config(args):
    long_len = args.long_prompt

    def serve(monolithic):
        cfg, srv = _build(
            "olmo-1b", args,
            max_len=max(args.prompt_len, long_len) + args.max_new + 2)
        obs = attach_observer(srv)
        short = make_requests(cfg, args.slots, prompt_len=args.prompt_len,
                              max_new=args.max_new)
        rng = np.random.default_rng(3)
        late = Request(
            99, rng.integers(0, cfg.vocab_size, long_len).astype(np.int32),
            args.max_new)
        sched = ContinuousScheduler(
            srv, FrontendConfig(chunk_tokens=args.chunk_tokens,
                                monolithic_prefill=monolithic))
        with sched:
            for r in short:
                sched.submit(r)
            # one tick so every slot is mid-decode, then the long prompt —
            # its prefill now interleaves (or, monolithic, stalls) decoding
            sched.step()
            sched.submit(late)
            out = sched.drain()
        block = latency_block(obs)
        return out, dict(sched.stats), block

    out_c, stats_c, lat_c = serve(False)
    out_m, stats_m, lat_m = serve(True)
    it_c, it_m = lat_c["intertoken_s"], lat_m["intertoken_s"]
    return {
        "long_prompt": long_len,
        "chunk_tokens": args.chunk_tokens,
        "streams_match_monolithic": out_c == out_m,
        "chunked": {
            "max_prefill_rows_between_bursts":
                stats_c["max_prefill_rows_between_bursts"],
            "intertoken_p99_s": it_c["p99"] if it_c else None,
            "tok_s": lat_c["tok_s"],
        },
        "monolithic": {
            "max_prefill_rows_between_bursts":
                stats_m["max_prefill_rows_between_bursts"],
            "intertoken_max_s": lat_m["intertoken_s"] and round(max(
                it_m["p99"], it_m["mean"]), 6),
            "tok_s": lat_m["tok_s"],
        },
    }


# ---------------------------------------------------------------------------
# arrival: Poisson offered load with deadlines + bounded admission
# ---------------------------------------------------------------------------


def _arrival_config(args):
    cfg, srv = _build(
        "olmo-1b", args, max_len=args.prompt_len + args.max_new + 2,
        resilience=ResilienceConfig(queue_limit=args.queue_limit,
                                    default_deadline_s=args.deadline_s))
    obs = attach_observer(srv)
    reqs = make_requests(cfg, args.arrival_requests,
                         prompt_len=args.prompt_len, max_new=args.max_new)
    rng = np.random.default_rng(11)
    gaps = rng.exponential(1.0 / args.arrival_rate, size=len(reqs))
    arrive = np.cumsum(gaps).tolist()

    sched = ContinuousScheduler(srv, FrontendConfig(
        chunk_tokens=args.chunk_tokens))
    pending = list(zip(arrive, reqs))
    t0 = time.perf_counter()
    with sched:
        while pending or not sched.idle:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                sched.submit(pending.pop(0)[1])
            if not sched.step() and pending:
                time.sleep(min(0.005, max(0.0, pending[0][0] - now)))
        out = dict(sched.results)
    dt = time.perf_counter() - t0

    statuses: dict = {}
    for o in srv.outcomes.values():
        statuses[o.status] = statuses.get(o.status, 0) + 1
    block = latency_block(obs)
    total = sum(len(v) for v in out.values())
    return {
        "offered": args.arrival_requests,
        "arrival_rate_hz": args.arrival_rate,
        "queue_limit": args.queue_limit,
        "deadline_s": args.deadline_s,
        "chunk_tokens": args.chunk_tokens,
        "tok_s": round(total / max(dt, 1e-9), 1),
        "outcomes": statuses,
        "outcomes_attributed": len(srv.outcomes) == args.arrival_requests,
        "ttft_samples": (block["ttft_s"] or {}).get("count", 0),
        "max_prefill_rows_between_bursts":
            sched.stats["max_prefill_rows_between_bursts"],
        "latency": block,
    }


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = bench_parser(__doc__, default_out="BENCH_frontend.json")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--burst", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--chunk-tokens", type=int, default=4)
    ap.add_argument("--long-prompt", type=int, default=48,
                    help="interleave config: the mid-run long prompt length")
    ap.add_argument("--arrival-requests", type=int, default=16)
    ap.add_argument("--arrival-rate", type=float, default=40.0,
                    help="arrival config: offered Poisson rate (req/s)")
    ap.add_argument("--queue-limit", type=int, default=8)
    ap.add_argument("--deadline-s", type=float, default=30.0)
    ap.add_argument("--d-model", type=int, default=64)
    args = ap.parse_args(argv)

    if args.smoke:
        args.full_size = False
        args.requests = 4
        args.max_new = 8
        args.slots = 2
        args.long_prompt = 32
        args.arrival_requests = 10

    record = base_record(args, configs={})
    record["configs"]["identity"] = _identity_config(args)
    record["configs"]["interleave"] = _interleave_config(args)
    record["configs"]["arrival"] = _arrival_config(args)
    emit_record(record, args.out)

    failures = []
    for row in record["configs"]["identity"]["rows"]:
        if not row["bit_identical"]:
            failures.append(
                f"identity violated for {row['family']}: chunked frontend "
                "stream diverged from run()")
    il = record["configs"]["interleave"]
    if not il["streams_match_monolithic"]:
        failures.append("interleave: chunked streams diverged from "
                        "monolithic prefill")
    if il["chunked"]["max_prefill_rows_between_bursts"] > args.chunk_tokens:
        failures.append(
            f"interleave: {il['chunked']['max_prefill_rows_between_bursts']} "
            f"prefill rows between bursts exceeds the chunk budget "
            f"{args.chunk_tokens}")
    if il["monolithic"]["max_prefill_rows_between_bursts"] < args.long_prompt:
        failures.append("interleave: monolithic arm did not take the "
                        "one-tick stall the gate contrasts against")
    p99_c = il["chunked"]["intertoken_p99_s"]
    max_m = il["monolithic"]["intertoken_max_s"]
    if p99_c is not None and max_m is not None and p99_c > max_m * 1.5:
        failures.append(
            f"interleave: chunked p99 inter-token {p99_c}s exceeds the "
            f"monolithic arm's worst stall {max_m}s — chunking is not "
            "amortizing the long prompt")
    ar = record["configs"]["arrival"]
    if not ar["outcomes_attributed"]:
        failures.append("arrival: not every offered request settled with an "
                        "outcome")
    if ar["ttft_samples"] != ar["outcomes"].get("ok", 0):
        failures.append(
            f"arrival: {ar['ttft_samples']} TTFT samples for "
            f"{ar['outcomes'].get('ok', 0)} served requests")
    if ar["max_prefill_rows_between_bursts"] > args.chunk_tokens:
        failures.append("arrival: interleaving bound violated under load")
    if failures:
        print("FAIL:", "; ".join(failures))
        sys.exit(1)
    print("frontend gates passed")
    return record


if __name__ == "__main__":
    main()
