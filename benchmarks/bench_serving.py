"""Decode-burst serving benchmark: tokens/sec + host round-trips per burst size.

The decode hot loop's cost on small models is dominated by what happens
BETWEEN engine steps — Python dispatch, (B, 1) token transfers, numpy
bookkeeping — not by the steps themselves. This benchmark measures exactly
that: the same workload served at burst sizes {1, 4, 8, 16} (``burst=1`` is
the per-token loop the seed shipped), for a dense model, a MoE model, an MLA
latent-cache model, and the adaptive-controller machinery, plus one
speculative run. Each record carries tokens/sec, the server's counted host
round-trips, and a bit-identity flag against the burst=1 greedy output —
bursts are a pure scheduling change, so any token drift is a bug.

    PYTHONPATH=src python -m benchmarks.bench_serving --bursts 1,4,8,16

``--smoke`` shrinks the workload for CI, writes
``artifacts/bench/BENCH_serving.json``, and exits nonzero if burst=8 is
slower than burst=1 (``--min-speedup``) or any config loses bit-identity —
the CI gate that keeps the burst path honest.

The ``observability`` config serves the same workload on two identical
servers — one with a metrics-only :class:`repro.obs.ServingObserver`
attached, one without, interleaved best-of — and records the throughput
ratio plus the observer's SLO latency block (TTFT / inter-token / queue-wait
percentiles). With ``--smoke`` the run exits nonzero if the observed server
falls below ``--min-obs-ratio`` (default 0.95) of the plain one: the
"observability costs ≤5% tok/s" gate.

``--devices 1,2,4,8`` switches to the SHARDED sweep instead: one fresh
subprocess per host device count (XLA locks the device count at first init,
so it cannot vary in-process), each forcing
``--xla_force_host_platform_device_count=N``, serving the same greedy
workload on ``mesh=None`` and on ``make_host_mesh()`` (4x2 at N=8), and
recording tok/s for both, bit-identity between them, and the collective
bytes of the compiled decode burst (``launch.hlo_analysis``). The record
lands in ``BENCH_sharded.json``; with ``--smoke`` the run exits nonzero if
any row loses bit-identity or the 1-device mesh path falls below
``--min-mesh-ratio`` of the ``mesh=None`` throughput (the "sharding must be
free when it is a no-op" gate).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

from repro.core import EngineContext, FXP16, PrecisionPolicy
from repro.serve.engine import BatchedServer, Request

from ._common import (
    attach_observer,
    base_record,
    bench_parser,
    emit_record,
    latency_block,
    load_model,
    timed,
)

CONFIG_ARCHS = {
    "dense": "olmo-1b",
    "moe": "llama4-maverick-400b-a17b",
    "mla": "deepseek-v3-671b",
}


def _workload(cfg, n, *, max_new, seed=1):
    rng = np.random.default_rng(seed)
    return [
        Request(i, rng.integers(0, cfg.vocab_size, int(rng.integers(3, 9))).astype(np.int32),
                max_new)
        for i in range(n)
    ]


def _gen_tokens(out):
    return sum(len(v) for v in out.values())


def bench_bursts(make_server, cfg, bursts, *, requests, max_new, reps=3):
    """Sweep burst sizes over one server config; burst=1 is the reference.

    Reps are interleaved across burst sizes (A/B/A/B, best-of per burst) so
    machine-load drift hits every burst size equally instead of biasing
    whichever happened to run during a quiet stretch.
    """
    servers = {burst: make_server(burst) for burst in bursts}
    run = lambda srv: srv.run(_workload(cfg, requests, max_new=max_new))
    outs, best = {}, {b: float("inf") for b in bursts}
    for burst, srv in servers.items():  # warmup: compile + first dispatch
        outs[burst] = run(srv)
    for _ in range(reps):
        for burst, srv in servers.items():
            dt, outs[burst] = timed(lambda: run(srv), warmup=0)
            best[burst] = min(best[burst], dt)
    ref = outs[bursts[0]]
    rows = [{
        "burst": burst,
        "tok_s": round(_gen_tokens(outs[burst]) / max(best[burst], 1e-9), 1),
        "host_transfers": servers[burst].host_transfers,
        "bit_identical": outs[burst] == ref,
    } for burst in bursts]
    base = rows[0]["tok_s"]
    for row in rows:
        row["speedup"] = round(row["tok_s"] / max(base, 1e-9), 2)
    return rows


def _sharded_worker(args):
    """One device-count probe (run in a fresh process with XLA_FLAGS set):
    mesh=None vs make_host_mesh() on the same greedy workload."""
    import jax

    from repro.launch import hlo_analysis
    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    mesh = make_host_mesh()
    data_extent = dict(zip(mesh.axis_names, mesh.devices.shape))["data"]
    # smallest multiple of the data extent >= requested slots, so the slot
    # state and cache batch dim actually shard (recorded per row)
    slots = -(-max(args.slots, 1) // data_extent) * data_extent
    max_len = 16 + args.max_new + args.draft_len
    cfg, model, params = load_model("olmo-1b", full_size=args.full_size,
                                    d_model=args.d_model)
    ctx = EngineContext(mode="exact", compute_dtype=cfg.compute_dtype)
    work = lambda: _workload(cfg, args.requests, max_new=args.max_new)

    none_srv = BatchedServer(model, ctx, params, slots=slots, max_len=max_len)
    mesh_srv = BatchedServer(model, ctx, params, slots=slots, max_len=max_len,
                             mesh=mesh)
    # warmup (compile) once each, then interleave best-of-3 so load drift
    # hits both paths equally — the mesh-ratio gate is a timing comparison
    t_none, out_none = timed(lambda: none_srv.run(work()))
    t_mesh, out_mesh = timed(lambda: mesh_srv.run(work()))
    for _ in range(2):
        t_none = min(t_none, timed(lambda: none_srv.run(work()), warmup=0)[0])
        t_mesh = min(t_mesh, timed(lambda: mesh_srv.run(work()), warmup=0)[0])

    # collective bytes of the compiled greedy decode burst on the mesh —
    # lowered under the server's scope so the analyzed program is the one
    # that executed (ambient mesh + the mesh-specific cache-write lowering)
    with mesh_srv._scope():
        hlo = (
            mesh_srv.decode_burst(False)
            .lower(mesh_srv._serving_tree(), mesh_srv.cache, mesh_srv._state)
            .compile()
            .as_text()
        )
    costs = hlo_analysis.analyze(hlo)
    row = {
        "devices": n,
        "mesh": dict(zip(mesh.axis_names, (int(s) for s in mesh.devices.shape))),
        "slots": slots,
        "tok_s_none": round(_gen_tokens(out_none) / max(t_none, 1e-9), 1),
        "tok_s_mesh": round(_gen_tokens(out_mesh) / max(t_mesh, 1e-9), 1),
        "bit_identical": out_mesh == out_none,
        "collective_bytes": costs.collective_bytes,
        "collective_by_kind": costs.collective_by_kind,
    }
    row["mesh_ratio"] = round(row["tok_s_mesh"] / max(row["tok_s_none"], 1e-9), 2)
    print("::SHARDED::" + json.dumps(row))


def _sharded_sweep(args):
    """Fan the device-count sweep out to fresh subprocesses (the forced host
    device count is locked at first jax init) and gate on the results.

    The workers run on forced host (CPU) devices, so on a TPU host the sweep
    refuses to start rather than quietly measure the CPU; a chip mesh is
    served in one process (``python -m repro.launch.serve --mesh auto``)."""
    import jax

    if jax.devices()[0].platform == "tpu":
        raise SystemExit(
            "the sharded sweep runs one CPU worker process per device count; "
            "on a TPU host serve the chip mesh in one process instead: "
            "python -m repro.launch.serve --mesh auto ..."
        )
    devices = [int(x) for x in args.devices.split(",")]
    passthrough = ["--_sharded-worker",
                   "--slots", str(args.slots),
                   "--requests", str(args.requests),
                   "--max-new", str(args.max_new),
                   "--d-model", str(args.d_model)]
    if args.full_size:
        passthrough.append("--full-size")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rows = []
    for n in devices:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} "
            + env.get("XLA_FLAGS", "")
        ).strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
        env["PYTHONPATH"] = (
            os.path.join(repo, "src") + os.pathsep + env.get("PYTHONPATH", "")
        )
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_serving"] + passthrough,
            env=env, capture_output=True, text=True, cwd=repo,
        )
        payload = [l for l in proc.stdout.splitlines()
                   if l.startswith("::SHARDED::")]
        if proc.returncode != 0 or not payload:
            print(proc.stdout)
            print(proc.stderr, file=sys.stderr)
            raise SystemExit(f"sharded worker for {n} devices failed")
        rows.append(json.loads(payload[0][len("::SHARDED::"):]))

    one = next((r for r in rows if r["devices"] == 1), rows[0])
    base = one["tok_s_mesh"]
    for row in rows:
        row["scaling_vs_1dev"] = round(row["tok_s_mesh"] / max(base, 1e-9), 2)
    record = base_record(args, sweep="sharded", devices=devices, rows=rows)
    out = args.out
    if out and os.path.basename(out) == "BENCH_serving.json":
        out = os.path.join(os.path.dirname(out), "BENCH_sharded.json")
    emit_record(record, out)

    failures = []
    for row in rows:
        if not row["bit_identical"]:
            failures.append(f"{row['devices']} devices: mesh output drifted "
                            "from mesh=None")
    one = next((r for r in rows if r["devices"] == 1), None)
    if one is not None and one["mesh_ratio"] < args.min_mesh_ratio:
        failures.append(
            f"1-device mesh path at {one['mesh_ratio']}x of mesh=None "
            f"(< {args.min_mesh_ratio}x): sharding must be free when it is "
            "a no-op"
        )
    if failures:
        print("FAIL:", "; ".join(failures))
        sys.exit(1)
    return record


def main(argv=None):
    ap = bench_parser(__doc__, default_out="BENCH_serving.json")
    ap.add_argument("--bursts", default="1,4,8,16",
                    help="comma-separated burst sizes (first is the reference)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--draft-len", type=int, default=3)
    ap.add_argument("--d-model", type=int, default=128,
                    help="reduced-model width (smoke shrinks it so the "
                         "per-token loop's dispatch overhead is visible)")
    ap.add_argument("--min-speedup", type=float, default=1.0,
                    help="CI gate: burst=8 must reach this speedup over "
                         "burst=1 (checked when 1 and 8 are both swept)")
    ap.add_argument("--min-obs-ratio", type=float, default=0.95,
                    help="CI gate: an attached metrics observer must keep "
                         "this fraction of the plain server's tok/s")
    ap.add_argument("--devices", default=None,
                    help="comma-separated host device counts: run the "
                         "SHARDED sweep (mesh=None vs make_host_mesh per "
                         "count, fresh subprocess each) instead of the "
                         "burst sweep; writes BENCH_sharded.json")
    ap.add_argument("--min-mesh-ratio", type=float, default=0.85,
                    help="sharded-sweep CI gate: the 1-device mesh path "
                         "must reach this fraction of mesh=None tok/s")
    ap.add_argument("--_sharded-worker", action="store_true",
                    help="(internal) run one device-count probe in-process")
    args = ap.parse_args(argv)

    if args.smoke:
        args.full_size = False
        args.slots = 2
        args.requests = 8
        args.max_new = 32
        args.d_model = 64

    if getattr(args, "_sharded_worker"):
        return _sharded_worker(args)
    if args.devices:
        return _sharded_sweep(args)

    bursts = [int(x) for x in args.bursts.split(",")]
    max_len = 16 + args.max_new + args.draft_len
    record = base_record(args, slots=args.slots, requests=args.requests,
                         max_new=args.max_new, bursts=bursts, configs={})

    for name, arch in CONFIG_ARCHS.items():
        cfg, model, params = load_model(arch, full_size=args.full_size,
                                        d_model=args.d_model)
        ctx = EngineContext(mode="exact", compute_dtype=cfg.compute_dtype)
        make = lambda burst: BatchedServer(model, ctx, params, slots=args.slots,
                                           max_len=max_len, burst=burst)
        record["configs"][name] = {
            "arch": arch,
            "sweep": bench_bursts(make, cfg, bursts, requests=args.requests,
                                  max_new=args.max_new),
        }

    # adaptive machinery under bursts: pinned controller (bank tree per burst,
    # telemetry live) so the output stays comparable across burst sizes —
    # free-controller trajectories legitimately differ with observation
    # cadence and are bench_adaptive's subject
    from repro.runtime import ControllerConfig, ModeController, build_bank, default_points

    cfg, model, params = load_model("olmo-1b", full_size=args.full_size,
                                    d_model=args.d_model)
    ctx = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP16),
                        compute_dtype=cfg.compute_dtype)
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    make = lambda burst: BatchedServer(
        model, ctx, params, slots=args.slots, max_len=max_len, burst=burst,
        controller=ModeController(bank, ControllerConfig(pin="accurate")),
    )
    record["configs"]["adaptive"] = {
        "arch": "olmo-1b", "pin": "accurate",
        "sweep": bench_bursts(make, cfg, bursts, requests=args.requests,
                              max_new=args.max_new),
    }

    # speculative serving (its round structure subsumes bursting; one run,
    # identity vs the accurate-only burst=1 output)
    from repro.spec import SpecConfig

    ref_server = BatchedServer(model, ctx, bank.tree(bank.reference),
                               slots=args.slots, max_len=max_len, burst=1,
                               prepare_weights=False)
    _, ref_out = timed(lambda: ref_server.run(
        _workload(cfg, args.requests, max_new=args.max_new)))
    spec_server = BatchedServer(model, ctx, params, slots=args.slots,
                                max_len=max_len, bank=bank,
                                speculate=SpecConfig(draft_len=args.draft_len))
    spec_obs = attach_observer(spec_server)
    dt, out = timed(lambda: spec_server.run(
        _workload(cfg, args.requests, max_new=args.max_new)))
    record["configs"]["speculative"] = {
        "arch": "olmo-1b", "draft_len": args.draft_len,
        "tok_s": round(_gen_tokens(out) / max(dt, 1e-9), 1),
        "host_transfers": spec_server.host_transfers,
        "bit_identical": out == ref_out,
        "acceptance_rate": spec_server.spec_telemetry.summary()["acceptance_rate"],
        "latency": latency_block(spec_obs),
    }

    # observability overhead: the same workload on two identical burst=8
    # servers, metrics-only observer on vs off, interleaved best-of (load
    # drift hits both equally). The observed server also supplies the
    # record's SLO latency block — percentiles, not just tok/s.
    plain = BatchedServer(model, ctx, params, slots=args.slots,
                          max_len=max_len, burst=8)
    watched = BatchedServer(model, ctx, params, slots=args.slots,
                            max_len=max_len, burst=8)
    obs = attach_observer(watched)
    work = lambda: _workload(cfg, args.requests, max_new=args.max_new)
    t_plain, out_plain = timed(lambda: plain.run(work()))
    t_obs, out_obs = timed(lambda: watched.run(work()))
    for _ in range(2):
        t_plain = min(t_plain, timed(lambda: plain.run(work()), warmup=0)[0])
        t_obs = min(t_obs, timed(lambda: watched.run(work()), warmup=0)[0])
    tok_plain = _gen_tokens(out_plain) / max(t_plain, 1e-9)
    tok_obs = _gen_tokens(out_obs) / max(t_obs, 1e-9)
    record["configs"]["observability"] = {
        "arch": "olmo-1b", "burst": 8,
        "tok_s_plain": round(tok_plain, 1),
        "tok_s_observed": round(tok_obs, 1),
        "obs_ratio": round(tok_obs / max(tok_plain, 1e-9), 3),
        "bit_identical": out_obs == out_plain,
        "latency": latency_block(obs),
    }

    emit_record(record, args.out)

    # CI gate: bursts must never lose tokens/sec or bit-identity, and
    # observability must stay (near-)free
    failures = []
    obs_rec = record["configs"]["observability"]
    if not obs_rec["bit_identical"]:
        failures.append("observability: token stream changed with an "
                        "observer attached")
    if obs_rec["obs_ratio"] < args.min_obs_ratio:
        failures.append(
            f"observability: observed server at {obs_rec['obs_ratio']}x of "
            f"plain tok/s (< {args.min_obs_ratio}x)"
        )
    for name, rec in record["configs"].items():
        if name == "observability":
            continue
        if "sweep" not in rec:
            if not rec["bit_identical"]:
                failures.append(f"{name}: speculative output drifted")
            continue
        by_burst = {row["burst"]: row for row in rec["sweep"]}
        for row in rec["sweep"]:
            if not row["bit_identical"]:
                failures.append(f"{name}: burst={row['burst']} output drifted")
        if 1 in by_burst and 8 in by_burst:
            speedup = by_burst[8]["tok_s"] / max(by_burst[1]["tok_s"], 1e-9)
            if speedup < args.min_speedup:
                failures.append(
                    f"{name}: burst=8 speedup {speedup:.2f}x < {args.min_speedup}x"
                )
    if failures:
        print("FAIL:", "; ".join(failures))
        sys.exit(1)
    return record


if __name__ == "__main__":
    main()
