"""Predicted-vs-measured gate for the PE-array simulator.

The simulator (``repro.sim``) claims its cycle model predicts serving cost.
This benchmark makes that claim falsifiable: it serves the same workload
under several configs (per-token burst=1, burst=8, free adaptive
controller, speculative), records a serve trace + wall-clock for each,
calibrates the array model against this machine (Tables 2/3/5 protocol),
replays every trace, and gates on three predictions:

* **cost ordering** — the simulator's host-attributed cycles (round-trips
  x the fitted dispatch floor) must order the burst-family configs the same
  way measured wall-clock does. The key is host cycles, not total cycles,
  deliberately: on this CPU the array back-end is emulated by vectorized
  matmuls whose wall time is insensitive to CORDIC depth and to drain
  padding, so config-level wall differences are dispatch-bound — exactly
  the term the calibration fits from this machine's dispatch floor. The
  array-compute half of the model (which dominates on the paper's actual
  hardware) is validated by the savings and scaling gates instead. Only
  pairs whose predicted costs differ by more than ``--ordering-margin``
  are comparable; near-ties are excluded rather than letting scheduler
  noise flip the gate.
* **savings agreement** — the simulator's ``est_cycle_savings_frac`` for
  the adaptive (and speculative) config must land within ``--savings-tol``
  relative of the value the serving loop itself reported. The serving bank
  is built WITH the calibration, so the ModeController and the simulator
  price cost identically — this gate isolates the *replay* accounting, not
  token counting.
* **PE scaling** — the simulated 64→256-lane time exponent over the
  Table 5 protocol (full cost model: waves + AF contention + weight
  stream + the fitted parallel penalty) must match the measured exponent
  within ``--scaling-tol``. The penalty constant comes from the same
  measurement, so this checks that the *rest* of the cost model (stalls,
  wave quantization) does not break the fitted scaling.

    PYTHONPATH=src python -m benchmarks.bench_sim --smoke \
        --trace artifacts/obs/trace.jsonl

``--smoke`` shrinks the workload for CI, writes
``artifacts/bench/BENCH_sim.json``, and exits nonzero on any gate failure.
``--trace PATH`` additionally replays an externally produced trace (CI
feeds it the obs-smoke serve trace) and applies the savings gate to it.
"""
from __future__ import annotations

import json
import os
import sys


from repro.core import EngineContext, FXP8, PrecisionPolicy
from repro.runtime import ControllerConfig, ModeController, build_bank, default_points
from repro.serve.engine import BatchedServer
from repro.sim import ArrayConfig, dot_pass_cost, replay_trace, run_calibration
from repro.sim.analyze import ordering_inversions, report_dict, savings_drift
from repro.spec import SpecConfig

from ._common import (
    ARTIFACTS,
    base_record,
    bench_parser,
    emit_record,
    load_model,
    make_requests,
    timed,
)


def _serve_traced(make_server, cfg, *, label, trace_dir, requests, prompt_len,
                  max_new, reps=3):
    """One config's measurement: warmup run (compile lands off-clock), then
    best-of-``reps`` traced timed runs — each with a fresh observer so every
    trace holds exactly one run, keeping the fastest run's trace so the
    measured wall and the replayed trace describe the same run. Returns
    (trace_path, row) where row carries the measured side of the
    comparison."""
    from repro.obs import ServingObserver

    srv = make_server()
    work = lambda: make_requests(cfg, requests, prompt_len=prompt_len,
                                 max_new=max_new)
    srv.run(work())  # warmup: jit compile + bucket tracing
    path = os.path.join(trace_dir, f"trace_{label}.jsonl")
    best = float("inf")
    for _ in range(reps):
        observer = ServingObserver(trace=True)
        srv.observer = observer
        dt, out = timed(lambda: srv.run(work()), warmup=0)
        if dt < best:
            best = dt
            observer.trace.write_jsonl(path)
            tokens = sum(len(v) for v in out.values())
    return path, {
        "config": label,
        "measured_wall_s": round(best, 4),
        "tok_s": round(tokens / max(best, 1e-9), 1),
        "tokens": tokens,
    }


def _replayed(path, row, calibration):
    """Attach the predicted side of one config's row from a replay."""
    result = replay_trace(path, calibration=calibration)
    t = result.totals
    row.update(
        predicted_cycles=round(t["total_cycles"], 1),
        predicted_wall_s=(round(t["predicted_wall_s"], 4)
                          if t.get("predicted_wall_s") is not None else None),
        pe_occupancy=round(t["pe_occupancy"], 4),
        host_sync_cycles=round(t["host_sync_cycles"], 1),
        savings=result.savings["est_cycle_savings_frac"],
        savings_rel_diff=savings_drift(result),
        spec_savings_rel_diff=(
            result.savings["speculative"]["rel_diff_vs_reported"]
            if result.savings.get("speculative") else None),
    )
    return result


def _sim_scaling_exponent(calibration, *, m=4096, k=512):
    """The Table 5 protocol run through the full cost model: an N-lane dot
    on an N-PE array at 64 and 256 lanes (work scales with N, like the
    measured sweep). Perfect scaling => time exponent 0."""
    import math

    cost = {}
    for n in (64, 256):
        cfg = ArrayConfig.from_calibration(calibration, n_pes=n)
        cost[n] = dot_pass_cost(cfg, k, n, 7, positions=m, bits=8).total
    return math.log(cost[256] / cost[64]) / math.log(256 / 64)


def main(argv=None):
    ap = bench_parser(__doc__, default_out="BENCH_sim.json")
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--cycle-budget", type=float, default=0.75)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="also replay this serve trace (CI: the obs-smoke "
                         "trace) and apply the savings gate to it")
    ap.add_argument("--trace-dir", default=os.path.join(
        os.path.dirname(ARTIFACTS), "sim"))
    ap.add_argument("--ordering-margin", type=float, default=0.10,
                    help="predicted gaps at or below this relative margin "
                         "are near-ties, excluded from the ordering gate")
    ap.add_argument("--savings-tol", type=float, default=0.15,
                    help="max |simulated - reported| / |reported| savings")
    ap.add_argument("--scaling-tol", type=float, default=0.20,
                    help="max |simulated - measured| 64->256 PE exponent")
    args = ap.parse_args(argv)

    if args.smoke:
        args.full_size = False
        args.requests = 4
        args.max_new = 12

    os.makedirs(args.trace_dir, exist_ok=True)
    calibration = run_calibration(smoke=args.smoke)
    print(f"calibration {calibration['id']}:",
          json.dumps(calibration["constants"]))

    cfg, model, params = load_model(args.arch, full_size=args.full_size,
                                    d_model=args.d_model)
    ctx = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP8),
                        compute_dtype=cfg.compute_dtype)
    # the bank carries the calibration: controller, telemetry, and simulator
    # all price points with the same constants
    bank = build_bank(params, "carmen", default_points(FXP8, hifi_fmt=None),
                      specs=model.specs(), calibration=calibration)
    max_len = args.prompt_len + args.max_new + SpecConfig().draft_len + 2

    def pinned(burst):
        return lambda: BatchedServer(
            model, ctx, params, slots=args.slots, max_len=max_len, burst=burst,
            controller=ModeController(bank, ControllerConfig(pin=bank.reference)),
        )

    configs = {
        "burst1": pinned(1),
        "burst8": pinned(8),
        "adaptive": lambda: BatchedServer(
            model, ctx, params, slots=args.slots, max_len=max_len, burst=4,
            controller=ModeController(
                bank, ControllerConfig(cycle_budget=args.cycle_budget)),
        ),
        "speculative": lambda: BatchedServer(
            model, ctx, params, slots=args.slots, max_len=max_len, bank=bank,
            speculate=SpecConfig(draft_len=3),
        ),
    }

    rows = []
    for label, make in configs.items():
        path, row = _serve_traced(
            make, cfg, label=label, trace_dir=args.trace_dir,
            requests=args.requests, prompt_len=args.prompt_len,
            max_new=args.max_new)
        _replayed(path, row, calibration)
        rows.append(row)
        print(f"{label}: predicted {row['predicted_cycles']:.3g} cycles, "
              f"measured {row['measured_wall_s']}s ({row['tok_s']} tok/s), "
              f"savings={row['savings']}")

    sim_exp = _sim_scaling_exponent(calibration)
    measured_exp = calibration["fit"]["measured_scaling_exponent"]
    scaling = {
        "sim_exponent": round(sim_exp, 4),
        "measured_exponent": round(measured_exp, 4),
        "abs_diff": round(abs(sim_exp - measured_exp), 4),
        "tolerance": args.scaling_tol,
    }
    print("scaling:", json.dumps(scaling))

    external = None
    if args.trace:
        result = replay_trace(args.trace, calibration=calibration)
        external = {
            "path": args.trace,
            "savings": result.savings["est_cycle_savings_frac"],
            "savings_rel_diff": savings_drift(result),
            "report": report_dict(result),
        }
        print(f"external trace {args.trace}: savings={external['savings']} "
              f"rel_diff={external['savings_rel_diff']}")

    # ordering over the pinned burst pair only: identical workload, identical
    # executed point — the configs differ in host round-trips alone, the one
    # axis the model and this machine agree on. Adaptive executes different
    # points (near-free on this CPU, expensive on the model's hardware) and
    # speculative restructures the rounds themselves; both are gated via
    # savings instead, where their trace carries a reported value to match.
    inversions = ordering_inversions(
        [(r["config"], r["host_sync_cycles"], r["measured_wall_s"])
         for r in rows if r["config"] in ("burst1", "burst8")],
        margin=args.ordering_margin)

    record = base_record(
        args,
        slots=args.slots, requests=args.requests, max_new=args.max_new,
        calibration={"id": calibration["id"],
                     "constants": calibration["constants"],
                     "fit": calibration["fit"]},
        configs=rows,
        scaling=scaling,
        ordering={"margin": args.ordering_margin, "inversions": inversions},
        external_trace=(
            {k: external[k] for k in ("path", "savings", "savings_rel_diff")}
            if external else None),
    )
    emit_record(record, args.out)

    failures = []
    for inv in inversions:
        failures.append(
            f"ordering: {inv['pair']} predicted {inv['predicted']} but "
            f"measured {inv['measured']}")
    for row in rows:
        for key, what in (("savings_rel_diff", "adaptive"),
                          ("spec_savings_rel_diff", "speculative")):
            drift = row.get(key)
            if drift is not None and drift > args.savings_tol:
                failures.append(
                    f"{row['config']}: simulated {what} savings drifts "
                    f"{drift:.3f} from reported (> {args.savings_tol})")
    if external and external["savings_rel_diff"] is not None \
            and external["savings_rel_diff"] > args.savings_tol:
        failures.append(
            f"external trace: savings drift {external['savings_rel_diff']:.3f} "
            f"(> {args.savings_tol})")
    if scaling["abs_diff"] > args.scaling_tol:
        failures.append(
            f"scaling: simulated exponent {sim_exp:.3f} vs measured "
            f"{measured_exp:.3f} (|diff| > {args.scaling_tol})")
    if failures:
        print("FAIL:", "; ".join(failures))
        sys.exit(1)
    print("bench_sim gates passed")
    return record


if __name__ == "__main__":
    main()
