"""Per-call vs prepared weight-bank serving benchmark (JSON output).

Measures the jitted decode step (the serving hot loop) with the seed's
per-call weight path (weights re-rounded / re-scaled every step) against the
prepared path (``prepare_params``: quantize once, serve fast), per engine
mode. Complements the ``benchmarks/run.py`` CSV tables with a JSON record:

    PYTHONPATH=src python -m benchmarks.bench_prepared --arch olmo-1b \
        --modes carmen,int8 --steps 20

writes ``artifacts/bench/bench_prepared.json`` (and prints it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import EngineContext, FXP8, PrecisionPolicy, prepare_params
from repro.serve.engine import BatchedServer, make_decode_sample_step

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


def bench_mode(model, params, mode: str, *, slots: int, max_len: int, steps: int):
    policy = PrecisionPolicy.accurate(FXP8)
    ctx = EngineContext(mode=mode, policy=policy,
                        compute_dtype=model.cfg.compute_dtype)
    prepared = prepare_params(params, policy, mode, specs=model.specs())
    rec = {}
    for label, p in (("per_call", params), ("prepared", prepared)):
        decode = jax.jit(make_decode_sample_step(model, ctx))

        def run_steps():
            cache = model.make_cache(slots, max_len, dtype=jnp.float32)
            tok = jnp.zeros((slots, 1), jnp.int32)
            for _ in range(steps):
                tok, cache = decode(p, tok, cache)
            return tok

        dt, _ = timed(run_steps)  # warmup run eats compile + first dispatch
        rec[label] = {
            "step_ms": round(1e3 * dt / steps, 3),
            "tok_s": round(steps * slots / dt, 1),
        }
    rec["speedup"] = round(rec["per_call"]["step_ms"] / rec["prepared"]["step_ms"], 2)
    return rec


def main(argv=None):
    ap = bench_parser(__doc__, default_out="bench_prepared.json", smoke=False)
    ap.add_argument("--modes", default="carmen,int8")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args(argv)

    cfg, model, params = load_model(args.arch, full_size=args.full_size)
    record = base_record(args, slots=args.slots, steps=args.steps, modes={})
    for mode in args.modes.split(","):
        record["modes"][mode] = bench_mode(
            model, params, mode, slots=args.slots, max_len=args.max_len,
            steps=args.steps,
        )

    # one small end-to-end served run on the first mode's prepared path, so
    # this record also carries SLO latency percentiles, not just step_ms
    mode = args.modes.split(",")[0]
    ctx = EngineContext(mode=mode, policy=PrecisionPolicy.accurate(FXP8),
                        compute_dtype=cfg.compute_dtype)
    server = BatchedServer(model, ctx, params, slots=args.slots,
                           max_len=args.max_len)
    obs = attach_observer(server)
    timed(lambda: server.run(make_requests(
        cfg, args.slots * 2, prompt_len=6,
        max_new=min(args.steps, args.max_len - 8))))
    record["served"] = {"mode": mode, "latency": latency_block(obs)}
    return emit_record(record, args.out)


if __name__ == "__main__":
    main()
