"""Self-speculative serving benchmark: acceptance, tokens/step, cycle cost.

The same greedy workload is served twice — all-accurate (the bank's
reference tree, classic one-token decode steps) and self-speculatively
(draft ``k`` tokens on the approximate execution point, verify all ``k+1`` in
one accurate multi-token forward) — per draft length. The record captures
the quantities the draft/verify split trades in:

* **acceptance_rate** / **mean_accepted_per_step** — how often the shallow
  CORDIC point agrees with the deep one;
* **tokens_per_step** — committed tokens per verify round (the latency
  leverage: one weight pass now yields several tokens);
* **est_cycle_savings_frac** — weight-pass cycles saved under the
  ``K*(depth+1)`` iterative-PE model, where a multi-token verify streams the
  resident weight bank once (see ``repro.spec.telemetry``);
* **sequence_agreement** — MUST be 1.0: greedy speculative output is
  bit-identical to accurate-only decoding by construction.

    PYTHONPATH=src python -m benchmarks.bench_speculative --arch olmo-1b \
        --draft-lens 2,4,6 --requests 6 --max-new 24

``--smoke`` shrinks the workload for CI and writes the same JSON shape to
``artifacts/bench/BENCH_speculative.json``.
"""
from __future__ import annotations

import numpy as np

from repro.core import EngineContext, FXP8, FXP16, PrecisionPolicy
from repro.runtime import build_bank, default_points
from repro.serve.engine import BatchedServer
from repro.spec import SpecConfig

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


def bench_accurate_only(model, cfg, bank, ctx, *, requests, slots,
                        prompt_len, max_new, max_len):
    """The baseline run, shared across the draft-length sweep (the cache's
    max_len does not affect generated tokens — rows past the write index are
    exactly masked)."""
    ref_server = BatchedServer(model, ctx, bank.tree(bank.reference),
                               slots=slots, max_len=max_len,
                               prepare_weights=False)
    ref_dt, ref_out = timed(lambda: ref_server.run(make_requests(
        cfg, requests, prompt_len=prompt_len, max_new=max_new)))
    return ref_out, ref_dt


def bench_draft_len(model, cfg, params, bank, ctx, k, ref_out, ref_dt, *,
                    requests, slots, prompt_len, max_new, max_len):
    spec_server = BatchedServer(model, ctx, params, slots=slots,
                                max_len=max_len, bank=bank,
                                speculate=SpecConfig(draft_len=k))
    obs = attach_observer(spec_server)
    spec_dt, spec_out = timed(lambda: spec_server.run(make_requests(
        cfg, requests, prompt_len=prompt_len, max_new=max_new)))
    tele = spec_server.spec_telemetry.summary()

    agree = float(np.mean([
        np.mean(np.array(spec_out[r]) == np.array(ref_out[r])) for r in ref_out
    ]))
    gen_toks = sum(len(v) for v in ref_out.values())
    return {
        "draft_len": k,
        "accurate_tok_s": round(gen_toks / max(ref_dt, 1e-9), 1),
        "speculative_tok_s": round(gen_toks / max(spec_dt, 1e-9), 1),
        "acceptance_rate": tele["acceptance_rate"],
        "mean_accepted_per_step": tele["mean_accepted_per_step"],
        "tokens_per_step": tele["tokens_per_step"],
        "est_cycle_savings_frac": tele["est_cycle_savings_frac"],
        "est_weight_pass_cycles": tele["est_weight_pass_cycles"],
        "accurate_only_cycles": tele["accurate_only_cycles"],
        "verify_rounds": tele["rounds"],
        "sequence_agreement": round(agree, 4),
        "latency": latency_block(obs),
    }


def main(argv=None):
    ap = bench_parser(__doc__, default_out="BENCH_speculative.json")
    ap.add_argument("--mode", choices=["carmen", "int8", "kernel"], default="carmen")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--draft-lens", default="2,4,6",
                    help="comma-separated draft lengths to sweep")
    ap.add_argument("--fxp8", action="store_true",
                    help="FxP8 operand ladder (default FxP16)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.full_size = False
        args.requests = 3
        args.slots = 2
        args.max_new = 12
        args.draft_lens = "3"

    cfg, model, params = load_model(args.arch, full_size=args.full_size)
    fmt = FXP8 if args.fxp8 else FXP16
    bank = build_bank(params, args.mode, default_points(fmt, hifi_fmt=None),
                      specs=model.specs())

    record = base_record(
        args,
        mode=args.mode,
        fmt=f"FXP{fmt.bits}",
        slots=args.slots,
        requests=args.requests,
        max_new=args.max_new,
        draft_point=bank.names[0],
        verify_point=bank.reference,
        rel_draft_cycles=round(bank.rel_cycles(bank.names[0]), 4),
        sweeps=[],
    )
    draft_lens = [int(x) for x in args.draft_lens.split(",")]
    ctx = EngineContext(mode=bank.mode, policy=PrecisionPolicy.accurate(fmt),
                        compute_dtype=cfg.compute_dtype)
    # one cache geometry for the whole sweep: the baseline is served once
    max_len = args.prompt_len + args.max_new + max(draft_lens) + 2
    ref_out, ref_dt = bench_accurate_only(
        model, cfg, bank, ctx, requests=args.requests, slots=args.slots,
        prompt_len=args.prompt_len, max_new=args.max_new, max_len=max_len,
    )
    for k in draft_lens:
        record["sweeps"].append(bench_draft_len(
            model, cfg, params, bank, ctx, k, ref_out, ref_dt,
            requests=args.requests, slots=args.slots,
            prompt_len=args.prompt_len, max_new=args.max_new, max_len=max_len,
        ))
    return emit_record(record, args.out)


if __name__ == "__main__":
    main()
