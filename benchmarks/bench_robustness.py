"""Fault-tolerance benchmark: isolation, shedding, graceful degradation.

Three configs, each with a CI gate (``--smoke`` exits nonzero on violation):

* **fault_isolation** — dense and MoE+MLA, adaptive-burst and speculative
  serving: the same workload runs fault-free and with a NaN-poisoned KV slot
  (``resilience.inject.NaNCacheFault``, deterministic round/rid from config).
  Gate: every unaffected slot's greedy stream is bit-identical to the
  fault-free run, the faulted slot is quarantined with a structured reason,
  and its committed tokens are exactly the clean prefix of the fault-free
  stream. Healthy-run tok/s is recorded for the trend gate.

* **overload_shedding** — offered load far above capacity, bounded vs
  unbounded admission queue. Gate: with shedding on, every rejected request
  carries a shed reason and the p99 queue-wait does not exceed the
  unbounded server's (the bounded queue serves a prefix of the same arrival
  order, so waiting is structurally bounded).

* **degradation** — the same overload served by a pinned-accurate server
  and by a ``DegradationPolicy`` wrapper that demotes the batch down the
  depth ladder under queue pressure. Deadline-met fractions are measured in
  **modeled PE cycles** (the bank's per-token cycle table walked over the
  serving trace): the software emulation's masked full-depth loop makes
  every depth cost identical *wall* time by design — one compiled program
  serves every point — so the silicon currency, where approx mode really is
  cheaper, is the honest clock (it is exactly what ``sim/replay.py``
  prices). The deadline is calibrated to the pinned run's median modeled
  completion. Gate: the degrading server's deadline-met fraction strictly
  exceeds the pinned one's at the same offered load.

    PYTHONPATH=src python -m benchmarks.bench_robustness --smoke
"""
from __future__ import annotations

import sys

import numpy as np

from repro.core import EngineContext, FXP16, PrecisionPolicy
from repro.obs import ServingObserver
from repro.resilience import (
    DegradationConfig,
    DegradationPolicy,
    FaultInjector,
    NaNCacheFault,
    ResilienceConfig,
)
from repro.runtime import (
    ControllerConfig,
    ModeController,
    build_bank,
    default_points,
)
from repro.serve.engine import BatchedServer, Request
from repro.spec import SpecConfig

from ._common import (
    base_record,
    bench_parser,
    emit_record,
    latency_block,
    load_model,
    timed,
)

ISOLATION_ARCHS = {
    "dense": "olmo-1b",
    "mla_moe": "deepseek-v3-671b",
}
FAULT_RID = 1
FAULT_ROUND = 1


def _workload(cfg, n, *, max_new, seed=7):
    rng = np.random.default_rng(seed)
    return [
        Request(i, rng.integers(0, cfg.vocab_size, 5).astype(np.int32), max_new)
        for i in range(n)
    ]


def _gen_tokens(out):
    return sum(len(v) for v in out.values())


# ---------------------------------------------------------------------------
# fault isolation
# ---------------------------------------------------------------------------


def _isolation_config(arch, args, *, speculative):
    cfg, model, params = load_model(arch, full_size=args.full_size,
                                    d_model=args.d_model)
    ctx = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP16),
                        compute_dtype=cfg.compute_dtype)
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    max_len = 16 + args.max_new + (3 if speculative else 0)
    kw = dict(slots=args.slots, max_len=max_len, bank=bank,
              resilience=ResilienceConfig())
    if speculative:
        kw.update(speculate=SpecConfig(draft_len=3))
    else:
        kw.update(burst=args.burst,
                  controller=ModeController(
                      bank, ControllerConfig(pin=bank.reference)))

    ref = BatchedServer(model, ctx, params, **kw)
    work = lambda: _workload(cfg, args.requests, max_new=args.max_new)
    dt, ref_out = timed(lambda: ref.run(work()))

    srv = BatchedServer(
        model, ctx, params,
        injector=FaultInjector(NaNCacheFault(rid=FAULT_RID,
                                             at_round=FAULT_ROUND)),
        **kw)
    out = srv.run(work())

    clean = [r for r in ref_out if r != FAULT_RID]
    o = srv.outcomes.get(FAULT_RID)
    row = {
        "arch": arch,
        "mode": "speculative" if speculative else "adaptive_burst",
        "tok_s": round(_gen_tokens(ref_out) / max(dt, 1e-9), 1),
        "fault_fired": bool(srv.injector.fired),
        "unaffected_bit_identical": all(out[r] == ref_out[r] for r in clean),
        "faulted_quarantined": o is not None and o.status == "faulted",
        "fault_reason": o.reason if o is not None else None,
        "clean_prefix_ok": (
            out[FAULT_RID] == ref_out[FAULT_RID][:len(out[FAULT_RID])]
        ),
        "faulted_tokens": len(out.get(FAULT_RID, [])),
    }
    row["isolation_ok"] = (row["fault_fired"]
                           and row["unaffected_bit_identical"]
                           and row["faulted_quarantined"]
                           and row["clean_prefix_ok"])
    return row


# ---------------------------------------------------------------------------
# overload shedding
# ---------------------------------------------------------------------------


def _overload_config(args):
    cfg, model, params = load_model("olmo-1b", full_size=args.full_size,
                                    d_model=args.d_model)
    ctx = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP16),
                        compute_dtype=cfg.compute_dtype)
    max_len = 16 + args.max_new

    def serve(resilience):
        srv = BatchedServer(model, ctx, params, slots=args.slots,
                            max_len=max_len, burst=args.burst,
                            resilience=resilience)
        srv.observer = ServingObserver(trace=False)
        work = lambda: _workload(cfg, args.overload_requests,
                                 max_new=args.max_new)
        dt, out = timed(lambda: srv.run(work()))
        return srv, dt, out

    unbounded, dt_u, out_u = serve(ResilienceConfig())
    bounded, dt_b, out_b = serve(
        ResilienceConfig(queue_limit=args.queue_limit,
                         shed_policy=args.shed_policy))

    def p99(srv):
        block = latency_block(srv.observer)
        qw = block.get("queue_wait_s")
        return qw["p99"] if qw else 0.0

    shed = {r: o for r, o in bounded.outcomes.items() if o.status == "shed"}
    return {
        "offered": args.overload_requests,
        "slots": args.slots,
        "queue_limit": args.queue_limit,
        "shed_policy": args.shed_policy,
        "unbounded": {
            "tok_s": round(_gen_tokens(out_u) / max(dt_u, 1e-9), 1),
            "queue_wait_p99_s": round(p99(unbounded), 6),
            "served": sum(o.status == "ok"
                          for o in unbounded.outcomes.values()),
        },
        "bounded": {
            "tok_s": round(_gen_tokens(out_b) / max(dt_b, 1e-9), 1),
            "queue_wait_p99_s": round(p99(bounded), 6),
            "served": sum(o.status == "ok" for o in bounded.outcomes.values()),
            "shed": len(shed),
            "shed_reasons": sorted({o.reason for o in shed.values()}),
            "all_sheds_attributed": all(o.reason for o in shed.values()),
        },
    }


# ---------------------------------------------------------------------------
# graceful degradation (modeled-cycle deadlines)
# ---------------------------------------------------------------------------


def _modeled_completions(events, cycles_per_token, reference):
    """Walk a serving trace; return {rid: modeled completion time} in PE
    cycles. Each prefill charges its bucket and each decode burst its steps
    at the executed point's per-token cost — the same currency
    ``sim/replay.py`` prices, reduced to what the deadline gate needs."""
    cum = 0.0
    open_args = {}
    done = {}
    for ev in events:
        name, ph = ev["name"], ev["ph"]
        args = ev.get("args", {})
        if ph == "B" and name in ("prefill", "burst", "spec"):
            open_args[name] = args
        elif ph == "E" and name in ("prefill", "burst", "spec"):
            merged = {**open_args.pop(name, {}), **args}
            point = merged.get("point") or reference
            per_tok = cycles_per_token.get(point, cycles_per_token[reference])
            units = (int(merged.get("bucket", 1)) if name == "prefill"
                     else int(merged.get("steps", 1)))
            cum += per_tok * units
        elif ph == "I" and name == "request_completed":
            done[int(args["rid"])] = cum
    return done


def _degradation_config(args):
    cfg, model, params = load_model("olmo-1b", full_size=args.full_size,
                                    d_model=args.d_model)
    ctx = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP16),
                        compute_dtype=cfg.compute_dtype)
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    max_len = 16 + args.max_new

    def serve(controller):
        srv = BatchedServer(model, ctx, params, slots=args.slots,
                            max_len=max_len, burst=args.burst, bank=bank,
                            controller=controller,
                            resilience=ResilienceConfig())
        srv.observer = ServingObserver()
        work = lambda: _workload(cfg, args.overload_requests,
                                 max_new=args.max_new)
        dt, out = timed(lambda: srv.run(work()))
        comp = _modeled_completions(srv.observer.trace.events,
                                    bank.cycles_per_token, bank.reference)
        return srv, dt, out, comp

    pinned = ModeController(bank, ControllerConfig(pin=bank.reference))
    _, dt_p, out_p, comp_p = serve(pinned)
    degrade = DegradationPolicy(
        ModeController(bank, ControllerConfig(pin=bank.reference)),
        DegradationConfig(demote_hysteresis=1))
    srv_d, dt_d, out_d, comp_d = serve(degrade)

    # deadline = the pinned run's median modeled completion: pinned meets
    # roughly half by construction, so any cycle savings show up as met
    deadline = float(np.median(sorted(comp_p.values())))
    met_p = sum(c <= deadline for c in comp_p.values()) / max(len(comp_p), 1)
    met_d = sum(c <= deadline for c in comp_d.values()) / max(len(comp_d), 1)
    return {
        "offered": args.overload_requests,
        "deadline_cycles": round(deadline, 1),
        "clock": "modeled_pe_cycles",
        "pinned": {
            "tok_s": round(_gen_tokens(out_p) / max(dt_p, 1e-9), 1),
            "deadline_met_frac": round(met_p, 4),
        },
        "degrade": {
            "tok_s": round(_gen_tokens(out_d) / max(dt_d, 1e-9), 1),
            "deadline_met_frac": round(met_d, 4),
            "demotions": degrade.demotions,
            "promotions": degrade.promotions,
            "final_cap": degrade.cap,
        },
    }


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = bench_parser(__doc__, default_out="BENCH_robustness.json")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--burst", type=int, default=4)
    ap.add_argument("--requests", type=int, default=4,
                    help="isolation workload size (>= 3 so slots neighbor "
                         "the faulted one)")
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--overload-requests", type=int, default=16,
                    help="offered load for the shedding/degradation configs")
    ap.add_argument("--queue-limit", type=int, default=6)
    ap.add_argument("--shed-policy", default="reject_newest",
                    choices=["reject_newest", "reject_largest",
                             "deadline_aware"])
    ap.add_argument("--d-model", type=int, default=64)
    args = ap.parse_args(argv)

    if args.smoke:
        args.full_size = False
        args.max_new = 8
        args.requests = 4
        args.overload_requests = 12
        args.slots = 2

    record = base_record(args, configs={})
    record["configs"]["fault_isolation"] = {
        "fault": {"kind": "nan_kv_cache", "rid": FAULT_RID,
                  "at_round": FAULT_ROUND},
        "rows": [
            _isolation_config(arch, args, speculative=spec)
            for arch in ISOLATION_ARCHS.values()
            for spec in (False, True)
        ],
    }
    record["configs"]["overload_shedding"] = _overload_config(args)
    record["configs"]["degradation"] = _degradation_config(args)
    emit_record(record, args.out)

    failures = []
    for row in record["configs"]["fault_isolation"]["rows"]:
        if not row["isolation_ok"]:
            failures.append(
                f"fault isolation violated for {row['arch']}/{row['mode']}: "
                f"{ {k: row[k] for k in ('fault_fired', 'unaffected_bit_identical', 'faulted_quarantined', 'clean_prefix_ok')} }"
            )
    ov = record["configs"]["overload_shedding"]
    if not ov["bounded"]["all_sheds_attributed"] or ov["bounded"]["shed"] == 0:
        failures.append("overload: sheds missing or unattributed")
    if ov["bounded"]["queue_wait_p99_s"] > ov["unbounded"]["queue_wait_p99_s"] * 1.05:
        failures.append(
            f"overload: bounded p99 queue-wait "
            f"{ov['bounded']['queue_wait_p99_s']}s exceeds unbounded "
            f"{ov['unbounded']['queue_wait_p99_s']}s"
        )
    dg = record["configs"]["degradation"]
    if not dg["degrade"]["deadline_met_frac"] > dg["pinned"]["deadline_met_frac"]:
        failures.append(
            f"degradation: met fraction {dg['degrade']['deadline_met_frac']} "
            f"does not strictly improve on pinned "
            f"{dg['pinned']['deadline_met_frac']}"
        )
    if failures:
        print("FAIL:", "; ".join(failures))
        sys.exit(1)
    print("robustness gates passed")
    return record


if __name__ == "__main__":
    main()
