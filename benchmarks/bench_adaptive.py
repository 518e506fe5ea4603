"""Runtime-adaptive serving benchmark: cycles saved vs accuracy across load.

For each load level (request count against a fixed slot count) the same
workload is served twice — once all-accurate (static prepared bank), once
through the runtime-adaptive subsystem (multi-point bank + mode controller)
— and the record captures the trade the paper's §III makes measurable
end-to-end: estimated MAC-cycle savings, mode occupancy, switch counts,
throughput, and greedy token agreement (teacher-forced overall + on
high-confidence tokens, split at the median accurate-run top-2 margin).

    PYTHONPATH=src python -m benchmarks.bench_adaptive --arch olmo-1b \
        --loads 4,12 --max-new 16

``--smoke`` shrinks the workload for CI and writes the same JSON shape to
``artifacts/bench/BENCH_adaptive.json``.
"""
from __future__ import annotations

import numpy as np

from repro.core import EngineContext, FXP8, FXP16, PrecisionPolicy
from repro.runtime import (
    ControllerConfig,
    ModeController,
    build_bank,
    default_points,
    teacher_forced_agreement,
)
from repro.serve.engine import BatchedServer

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


def bench_load(model, cfg, params, bank, n_requests, *, slots, prompt_len,
               max_new, cycle_budget, fmt):
    ctx = EngineContext(mode=bank.mode, policy=PrecisionPolicy.accurate(fmt),
                        compute_dtype=cfg.compute_dtype)
    max_len = prompt_len + max_new + 2
    workload = lambda: make_requests(cfg, n_requests, prompt_len=prompt_len,
                                     max_new=max_new)

    ref_reqs = workload()
    # the bank already holds the all-accurate tree — no second prepare pass
    ref_server = BatchedServer(model, ctx, bank.tree(bank.reference), slots=slots,
                               max_len=max_len, prepare_weights=False)
    ref_dt, ref_out = timed(lambda: ref_server.run(ref_reqs))

    controller = ModeController(bank, ControllerConfig(cycle_budget=cycle_budget))
    adp_server = BatchedServer(model, ctx, params, slots=slots, max_len=max_len,
                               controller=controller)
    obs = attach_observer(adp_server)
    adp_dt, adp_out = timed(lambda: adp_server.run(workload()))
    tele = adp_server.telemetry.summary()

    seq_agree = float(np.mean([
        np.mean(np.array(adp_out[r]) == np.array(ref_out[r])) for r in ref_out
    ]))
    overall, high_conf, thr, _ = teacher_forced_agreement(
        model, ctx, bank.tree(bank.names[0]), ref_reqs, ref_out,
        {r.rid: r.margins for r in ref_reqs},
    )
    gen_toks = sum(len(v) for v in ref_out.values())  # decode tokens only
    return {
        "requests": n_requests,
        "queue_pressure": round(n_requests / slots, 2),
        "accurate_tok_s": round(gen_toks / max(ref_dt, 1e-9), 1),
        "adaptive_tok_s": round(gen_toks / max(adp_dt, 1e-9), 1),
        "est_cycle_savings_frac": tele["est_cycle_savings_frac"],
        "mode_occupancy": tele["mode_occupancy"],
        "switches": tele["switches"],
        "sequence_agreement": round(seq_agree, 4),
        "greedy_agreement_overall": round(overall, 4),
        "greedy_agreement_high_conf": round(high_conf, 4),
        "margin_threshold": round(thr, 4),
        "latency": latency_block(obs),
    }


def main(argv=None):
    ap = bench_parser(__doc__, default_out="BENCH_adaptive.json")
    ap.add_argument("--mode", choices=["carmen", "int8", "kernel"], default="carmen")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--loads", default="4,12",
                    help="comma-separated request counts (load levels)")
    ap.add_argument("--cycle-budget", type=float, default=0.75)
    ap.add_argument("--fxp8", action="store_true",
                    help="FxP8 operand ladder (default FxP16)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.full_size = False
        args.loads = "2,6"
        args.max_new = 8
        args.slots = 2

    cfg, model, params = load_model(args.arch, full_size=args.full_size)
    fmt = FXP8 if args.fxp8 else FXP16
    bank = build_bank(params, args.mode, default_points(fmt, hifi_fmt=None),
                      specs=model.specs())

    record = base_record(
        args,
        mode=args.mode,
        fmt=f"FXP{fmt.bits}",
        slots=args.slots,
        max_new=args.max_new,
        cycle_budget=args.cycle_budget,
        bank={
            "points": list(bank.names),
            "rel_cycles": {n: round(bank.rel_cycles(n), 4) for n in bank.names},
            "shared_leaves": bank.shared_leaves,
            "unique_leaves": bank.unique_leaves,
        },
        loads=[],
    )
    for n in (int(x) for x in args.loads.split(",")):
        rec = bench_load(model, cfg, params, bank, n, slots=args.slots,
                         prompt_len=args.prompt_len, max_new=args.max_new,
                         cycle_budget=args.cycle_budget, fmt=fmt)
        record["loads"].append(rec)
    return emit_record(record, args.out)


if __name__ == "__main__":
    main()
