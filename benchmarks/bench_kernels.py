"""Fused vs unfused CORDIC decode path: tok/s, per-layer kernel time, parity.

Two gates ride along with the numbers (exit nonzero on violation):

* **bit-identity** — greedy decode through the fused dot+AF path must equal
  the unfused prepared-XLA chain token for token (and margin for margin);
* **zero recompiles across a mode switch** — an adaptive kernel-mode bank
  under forced switching must serve every execution point from ONE compiled
  burst program (the params vector carries depth/format as data).

Speed numbers are honest for the platform they ran on: on CPU the "fused"
path runs the Pallas kernel in interpret mode, so the XLA fallback usually
wins — the record is the parity/compile-count evidence plus a per-layer
kernel microbenchmark; the tok/s comparison becomes meaningful on TPU.

CI runs ``--smoke`` and uploads ``BENCH_kernels.json``.
"""
from __future__ import annotations

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._common import (
    attach_observer,
    base_record,
    bench_parser,
    emit_record,
    latency_block,
    load_model,
    make_requests,
    timed,
)
from repro.core import EngineContext, PrecisionPolicy
from repro.core.fxp import FXP8
from repro.serve.engine import BatchedServer


def _serve(model, ctx, params, reqs, *, slots, max_len, burst):
    # both contenders carry the same metrics-only observer, so the fused/
    # unfused tok/s comparison stays fair and the record gets SLO latency
    server = BatchedServer(model, ctx, params, slots=slots, max_len=max_len,
                           burst=burst)
    attach_observer(server)
    out = server.run(reqs)
    return out, [r.margins for r in reqs], server


def _layer_microbench(d_model: int, d_ff: int, interpret_fused: bool):
    """One MLP gate layer (dot + gelu): fused single pass vs unfused chain."""
    from repro.core import cordic
    from repro.kernels.cordic_af.ops import multi_af_pallas
    from repro.kernels.cordic_fused import fused_dot_af, make_point
    from repro.kernels.cordic_mac import ops as mac_ops

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, d_model)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(d_model, d_ff)).astype(np.float32) * 0.1)
    depth = 5
    sd = cordic.signed_digit_round(w, depth, FXP8)
    point = make_point(depth, FXP8, FXP8)

    t_fused, _ = timed(lambda: fused_dot_af(
        x, sd, point, af_mode="gelu", af_depth=8, af_fmt=FXP8,
        interpret=interpret_fused,
    ))
    t_unfused, _ = timed(lambda: multi_af_pallas(
        mac_ops.cordic_mac(x, sd, depth=depth, x_fmt=FXP8, w_fmt=FXP8,
                           w_prequantized=True),
        "gelu", depth=8, fmt=FXP8,
    ))
    return {"fused_us": round(t_fused * 1e6, 1),
            "unfused_us": round(t_unfused * 1e6, 1)}


def _mode_switch_record(model, cfg, params, ctx):
    """Adaptive bank under forced switching: compile-count assertion."""
    from repro.runtime import (
        ControllerConfig, ModeController, build_bank, default_points,
    )

    bank = build_bank(params, "kernel", default_points(FXP8),
                      specs=model.specs())
    ctrl = ModeController(bank, ControllerConfig(margin_demote=0.5,
                                                 hysteresis=1))
    srv = BatchedServer(model, ctx, params, slots=2, max_len=32, burst=2,
                        controller=ctrl)
    srv.run(make_requests(cfg, 2, prompt_len=4, max_new=8))
    tele = srv.telemetry.summary()
    compiles = {k: fn._cache_size() for k, fn in srv._burst_fns.items()}
    return {
        "switches": tele["switches"],
        "steps_by_point": tele["steps_by_point"],
        "burst_compiles": compiles,
    }


def main(argv=None):
    args = bench_parser(
        "fused vs unfused CORDIC decode path",
        default_out="BENCH_kernels.json",
    ).parse_args(argv)
    n, max_new, burst = (2, 4, 2) if args.smoke else (4, 16, 4)
    max_len = 32

    cfg, model, params = load_model(args.arch, full_size=args.full_size)
    base = EngineContext(mode="kernel", policy=PrecisionPolicy.accurate(FXP8),
                         compute_dtype=cfg.compute_dtype)

    results = {}
    for fused in ("off", "on"):
        ctx = dataclasses.replace(base, fused=fused)
        reqs = make_requests(cfg, n, prompt_len=4, max_new=max_new)
        secs, (out, margins, srv) = timed(lambda: _serve(
            model, ctx, params, reqs, slots=2, max_len=max_len, burst=burst,
        ))
        tokens = sum(len(v) for v in out.values())
        results[fused] = {
            "out": out,
            "margins": margins,
            "decode_tok_s": round(tokens / secs, 2),
            "latency": latency_block(srv.observer),
        }

    bit_identical = results["on"]["out"] == results["off"]["out"] and all(
        np.array_equal(a, b)
        for a, b in zip(results["on"]["margins"], results["off"]["margins"])
    )

    switch = _mode_switch_record(model, cfg, params, base)

    record = base_record(
        args,
        mode="kernel",
        fmt="fxp8",
        burst=burst,
        max_new=max_new,
        fused_decode_tok_s=results["on"]["decode_tok_s"],
        unfused_decode_tok_s=results["off"]["decode_tok_s"],
        bit_identical=bit_identical,
        latency=results["on"]["latency"],
        layer_kernel=_layer_microbench(cfg.d_model, cfg.d_ff,
                                       interpret_fused=None),
        mode_switch=switch,
    )
    emit_record(record, args.out)

    if not bit_identical:
        print("FAIL: fused decode path diverged from the prepared XLA chain",
              file=sys.stderr)
        return 1
    if any(c != 1 for c in switch["burst_compiles"].values()):
        print(f"FAIL: mode switch recompiled the burst program "
              f"({switch['burst_compiles']})", file=sys.stderr)
        return 1
    if switch["switches"] < 1:
        print("FAIL: controller never switched; compile-count assertion is "
              "vacuous", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
