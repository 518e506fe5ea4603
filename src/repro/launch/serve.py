"""Batched serving driver (continuous batching over decode steps).

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --reduced \
        --requests 6 --max-new 16 --mode carmen

Precision policy (paper §III): ``--policy-file`` loads a JSON policy
(``PrecisionPolicy.save`` / ``assign_depths`` output), ``--calibrate`` runs
the sensitivity scan on a synthetic calibration batch at startup, otherwise
the policy is uniform accurate. ``--adaptive`` serves through the
runtime-adaptive subsystem (``repro.runtime``): a multi-point weight bank +
mode controller that switches execution points per decode step from live
telemetry, optionally steered by ``--cycle-budget``. ``--speculative``
serves self-speculatively (``repro.spec``): draft ``--draft-len`` tokens on
the shallow execution point (``--draft-point``, default the bank's cheapest;
with ``--adaptive`` the controller picks it per round), verify them in one
accurate multi-token forward, roll the KV cache back past rejections —
greedy output stays bit-identical to accurate-only serving. ``--burst``
sets the decode burst length (jitted scan steps per host round-trip;
``--burst 1`` is the per-token loop, for A/B benchmarking). ``--mesh
DATA,MODEL`` (or ``--mesh auto``) serves tensor-parallel on a device mesh —
greedy token streams are bit-identical to single-device serving across mesh
shapes. ``--metrics``/``--metrics-out`` report per-request SLO latency
(TTFT, inter-token, queue-wait percentiles); ``--trace-out`` /
``--chrome-trace`` export the structured serve trace (JSONL replay format /
Perfetto); ``--profile DIR`` additionally captures a ``jax.profiler`` trace,
in which the host thread shows the program's own spans by name
(``frontend.tick``, ``engine.burst``, ``engine.burst.wait``, ...: the list is
``repro.obs.trace.PROGRAM_SPANS``) and every device operation carries its
named scope (``layers``, ``layer``, ``attention.*``, ``dot.<mode>``,
``lm_head``, ``sample``) in its ``op_name``. The serve trace's timestamps
are on the profiler's clock, so the two line up.

Fault tolerance (``repro.resilience``, see ``docs/robustness.md``) — any of
the flags below switches the server from fail-stop to shed/quarantine/
degrade, with a per-request outcome summary printed at the end::

    # per-request deadlines: requests that cannot finish inside 500 ms are
    # shed from the queue or evicted mid-decode with partial output
    ... --deadline-ms 500

    # bounded admission queue: at most 8 requests held; overload is shed
    # fast with attributable reasons instead of waiting unboundedly
    ... --queue-limit 8 --shed-policy deadline_aware

    # graceful degradation: under deadline misses / queue pressure the whole
    # batch demotes down the CORDIC depth ladder before anything is shed
    ... --adaptive --deadline-ms 500 --degrade

Streaming frontend (``repro.serve.frontend``, see ``docs/serving.md``) —
``--frontend`` serves the synthetic workload through the continuous-batching
scheduler instead of ``run()``: requests arrive over time (``--arrival-rate``
req/s, seeded Poisson; 0 = all at once), admission/eviction/shed sweeps run
every tick, and prefill is chunked to ``--chunk-tokens`` rows per tick so a
long prompt never stalls decoding slots for more than one chunk budget
(``--monolithic-prefill`` disables chunking, the A/B contrast). Deadlines
become submit-relative. Two live drivers ride the same scheduler::

    # JSONL requests on stdin -> streamed {"rid", "token"} JSONL on stdout
    echo '{"rid": 0, "prompt": [5, 17, 3], "max_new": 8}' | \
        ... --stdin-requests

    # minimal HTTP service: POST /generate {"prompt": [...], "max_new": N}
    ... --http-port 8080

Compute runs in the config's dtype (bfloat16 at published widths, float32
under ``--reduced``). Compiled programs persist in ``$JAX_COMPILATION_CACHE_DIR``
when it is set, else in ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config, reduced as reduce_cfg
from repro.core import FXP8, FXP16, EngineContext, PrecisionPolicy, assign_depths
from repro.models import get_model
from repro.serve.engine import BatchedServer, Request


CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Persist compiled programs: in ``$JAX_COMPILATION_CACHE_DIR`` when set
    (jax reads it itself; nothing else is set), else in ``.jax_cache/`` at
    the root of the checkout — a fixed path, since the path is part of the
    cache key. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def prompt_lengths(spec: str):
    """``--prompt-len`` as ``(lo, hi)``: ``"N"`` or a seeded range ``"LO-HI"``."""
    lo, _, hi = spec.partition("-")
    lo, hi = int(lo), int(hi or lo)
    if not 0 <= lo <= hi:
        raise ValueError(f"want N or LO-HI with LO <= HI, got {spec!r}")
    return lo, hi


def synthetic_requests(args, vocab_size: int):
    """The CLI's seeded workload: ``--requests`` prompts of ``--prompt-len``
    tokens (uniform in the range when one is given)."""
    lo, hi = args.prompt_len
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        n = lo if lo == hi else int(rng.integers(lo, hi + 1))
        reqs.append(Request(
            i, rng.integers(0, vocab_size, n).astype(np.int32),
            args.max_new, temperature=args.temperature,
            seed=None if args.seed is None else args.seed + i,
        ))
    return reqs


def resolve_policy(args, model, params, fmt) -> PrecisionPolicy:
    """--policy-file > --calibrate (startup sensitivity scan) > accurate."""
    if args.policy_file:
        policy = PrecisionPolicy.load(args.policy_file)
    elif args.calibrate:
        from repro.runtime import calibration_scan

        rng = np.random.default_rng(0)
        tokens = rng.integers(0, model.cfg.vocab_size,
                              (2, max(args.prompt_len[1], 8)))
        sens = calibration_scan(model, params, tokens, fmt=fmt, mode=args.mode)
        policy = assign_depths(
            sens, fmt=fmt, cycle_reduction_target=args.cycle_reduction
        )
        print("calibration scan:", {k: round(v, 4) for k, v in sorted(sens.items())})
    else:
        policy = PrecisionPolicy.accurate(fmt)
    if args.save_policy:
        policy.save(args.save_policy)
        print(f"policy saved to {args.save_policy}")
    return policy


def _frontend_config(args):
    from repro.serve.frontend import FrontendConfig

    return FrontendConfig(chunk_tokens=args.chunk_tokens,
                          monolithic_prefill=args.monolithic_prefill)


def _serve_synthetic(args, server, reqs):
    """The synthetic workload through the scheduler, ticked on this thread:
    a seeded arrival process decides *when* each request is submitted, and
    between arrivals the scheduler keeps admitting/prefilling/decoding."""
    from repro.serve.frontend import ContinuousScheduler

    rng = np.random.default_rng(args.arrival_seed)
    if args.arrival_rate > 0:
        arrive = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                           size=len(reqs)))
    else:
        arrive = np.zeros(len(reqs))
    pending = list(zip(arrive.tolist(), reqs))
    sched = ContinuousScheduler(server, _frontend_config(args))
    with sched:
        t0 = time.perf_counter()
        while pending or not sched.idle:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                sched.submit(pending.pop(0)[1])
            if not sched.step() and pending:
                # idle but arrivals remain: sleep until the next one is due
                time.sleep(min(0.01, max(0.0, pending[0][0] - now)))
        results = dict(sched.results)
    print(f"frontend: ticks={sched.stats['ticks']} "
          f"bursts={sched.stats['bursts']} "
          f"prefill_rows={sched.stats['prefill_rows']} "
          f"max_prefill_rows_between_bursts="
          f"{sched.stats['max_prefill_rows_between_bursts']} "
          f"(chunk budget {args.chunk_tokens})")
    return results


def _serve_stdin(args, server):
    """JSONL requests on stdin, streamed JSONL tokens on stdout. Each line
    in is one request; each token lands as its own line out, then a final
    ``done`` line with the outcome status."""
    import sys
    import threading

    from repro.serve.frontend import AsyncFrontend

    fe = AsyncFrontend(server, _frontend_config(args)).start()
    results = {}
    out_lock = threading.Lock()

    def pump(handle):
        for tok in handle:
            with out_lock:
                print(json.dumps({"rid": handle.rid, "token": int(tok)}),
                      flush=True)
        with out_lock:
            print(json.dumps({"rid": handle.rid, "done": True,
                              "status": handle.status or "ok",
                              "tokens": len(handle.tokens)}), flush=True)
            results[handle.rid] = list(handle.tokens)

    pumps = []
    auto_rid = 0
    try:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            rid = int(d.get("rid", auto_rid))
            auto_rid = max(auto_rid, rid) + 1
            req = Request(
                rid, np.asarray(d["prompt"], np.int32),
                int(d.get("max_new", args.max_new)),
                temperature=float(d.get("temperature", args.temperature)),
                seed=d.get("seed", args.seed),
                deadline_s=d.get("deadline_s"),
            )
            try:
                handle = fe.submit(req)
            except ValueError as e:
                with out_lock:
                    print(json.dumps({"rid": rid, "done": True,
                                      "status": "rejected",
                                      "error": str(e)}), flush=True)
                continue
            t = threading.Thread(target=pump, args=(handle,), daemon=True)
            t.start()
            pumps.append(t)
        for t in pumps:
            t.join()
    finally:
        fe.stop()
    return results


def _serve_http(args, server):
    """Minimal stdlib HTTP service over the async frontend. One endpoint:
    POST /generate with ``{"prompt": [...], "max_new": N, ...}`` blocks
    until the request settles and returns the full token stream (a broken
    connection mid-wait cancels the request — client disconnect maps to
    eviction at the next tick). GET /healthz for liveness."""
    import itertools
    import select
    import socket
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from repro.serve.frontend import AsyncFrontend

    fe = AsyncFrontend(server, _frontend_config(args)).start()
    results = {}
    counter = itertools.count()
    lock = threading.Lock()

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # keep stdout for the serving summary
            pass

        def do_GET(self):
            if self.path != "/healthz":
                self.send_error(404)
                return
            self._reply(200, {"ok": True})

        def do_POST(self):
            if self.path != "/generate":
                self.send_error(404)
                return
            n = int(self.headers.get("Content-Length", 0))
            try:
                d = json.loads(self.rfile.read(n) or b"{}")
                with lock:
                    rid = int(d.get("rid", next(counter) + 100000))
                req = Request(
                    rid, np.asarray(d["prompt"], np.int32),
                    int(d.get("max_new", args.max_new)),
                    temperature=float(d.get("temperature", args.temperature)),
                    seed=d.get("seed", args.seed),
                    deadline_s=d.get("deadline_s"),
                )
                handle = fe.submit(req)
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._reply(400, {"error": str(e)})
                return
            # block until settled, but watch the socket: a client that
            # disconnects mid-generation cancels the request (eviction at
            # the next tick, partial tokens kept, outcome ``aborted``)
            while not handle._done.wait(0.25):
                readable, _, _ = select.select([self.connection], [], [], 0)
                if readable and not self.connection.recv(1, socket.MSG_PEEK):
                    handle.cancel()
                    handle._done.wait(5.0)
                    return
            toks = list(handle.tokens)
            with lock:
                results[rid] = toks
            self._reply(200, {"rid": rid, "tokens": toks,
                              "status": handle.status or "ok"})

        def _reply(self, code, payload):
            body = json.dumps(payload).encode()
            try:
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            except (BrokenPipeError, ConnectionResetError):
                pass  # client went away; the request already settled

    srv = ThreadingHTTPServer(("127.0.0.1", args.http_port), Handler)
    print(f"serving on http://127.0.0.1:{args.http_port} "
          "(POST /generate, GET /healthz); Ctrl-C to stop", flush=True)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        print("\nshutting down")
    finally:
        srv.server_close()
        fe.stop()
    return results


def _serve_frontend(args, server, reqs):
    if args.http_port:
        return _serve_http(args, server)
    if args.stdin_requests:
        return _serve_stdin(args, server)
    return _serve_synthetic(args, server, reqs)


def main(argv=None):
    """Serve the CLI's workload; returns ``(server, results)`` (rid ->
    generated tokens) for programmatic callers."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="olmo-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=prompt_lengths, default="8",
                    metavar="N|LO-HI",
                    help="prompt tokens per request, or a range to draw "
                         "each request's length from (seeded)")
    ap.add_argument("--mode", choices=["exact", "carmen", "int8", "kernel"], default="exact")
    ap.add_argument("--per-call", action="store_true",
                    help="skip prepare_params: re-quantize weights every step "
                         "(the seed behaviour; for A/B benchmarking)")
    ap.add_argument("--fxp16", action="store_true",
                    help="FxP16 operand format (default FxP8)")
    ap.add_argument("--policy-file", default=None,
                    help="JSON precision policy (PrecisionPolicy.save / assign_depths)")
    ap.add_argument("--calibrate", action="store_true",
                    help="run the §III sensitivity scan on a calibration batch at startup")
    ap.add_argument("--save-policy", default=None,
                    help="write the resolved policy as JSON (round-trips via --policy-file)")
    ap.add_argument("--cycle-reduction", type=float, default=0.33,
                    help="assign_depths cycle-reduction budget for --calibrate")
    ap.add_argument("--adaptive", action="store_true",
                    help="runtime-adaptive precision: multi-point bank + mode controller")
    ap.add_argument("--cycle-budget", type=float, default=0.75,
                    help="--adaptive: target MAC-cycle fraction vs all-accurate")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="PE-array calibration JSON (repro.sim.calibrate "
                         "export): prices the bank's per-point cycle costs "
                         "with fitted constants instead of the analytic "
                         "model; recorded in telemetry/trace as cycle_model")
    ap.add_argument("--speculative", action="store_true",
                    help="self-speculative serving: draft on the shallow "
                         "execution point, verify on the accurate point")
    ap.add_argument("--draft-len", type=int, default=4,
                    help="--speculative: tokens drafted per verify round")
    ap.add_argument("--draft-point", default=None,
                    help="--speculative: bank point to draft at (default: the "
                         "cheapest; with --adaptive the controller picks)")
    ap.add_argument("--burst", type=int, default=8,
                    help="decode burst length: jitted scan steps per host "
                         "round-trip (1 = the per-token loop)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--seed", type=int, default=None,
                    help="base sampling seed (request i uses seed + i)")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL",
                    help="serve tensor-parallel on a (data, model) device "
                         "mesh: 'DATA,MODEL' extents (e.g. --mesh 4,2) or "
                         "'auto' to factor the local device count (see "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    res_args = ap.add_argument_group(
        "resilience",
        "fault-tolerant serving (repro.resilience): deadlines, bounded "
        "admission with load shedding, per-slot fault quarantine, graceful "
        "precision degradation — any flag here enables the resilient "
        "contract (structured RequestOutcomes instead of crashes)")
    res_args.add_argument("--deadline-ms", type=float, default=None,
                          help="per-request deadline in ms from run entry: "
                               "expired queued requests are shed, expired "
                               "running requests are evicted with partial "
                               "output at the next burst boundary")
    res_args.add_argument("--queue-limit", type=int, default=None,
                          help="bounded admission queue: overflow is shed "
                               "per --shed-policy with reason queue_full")
    res_args.add_argument("--shed-policy", default="reject_newest",
                          choices=["reject_newest", "reject_largest",
                                   "deadline_aware"],
                          help="queue-overflow victim selection")
    res_args.add_argument("--degrade", action="store_true",
                          help="graceful degradation: cap the whole batch "
                               "down the bank's depth ladder under deadline "
                               "misses / queue pressure, promote back with "
                               "hysteresis (needs a bank: --adaptive or "
                               "--speculative)")
    res_args.add_argument("--degrade-floor", default=None, metavar="POINT",
                          help="--degrade: cheapest bank point the cap may "
                               "reach (default: the cheapest rung)")
    obs_args = ap.add_argument_group(
        "observability",
        "SLO metrics + structured serve trace (repro.obs); hooks run only at "
        "host sync points, so token streams are bit-identical with or "
        "without them")
    obs_args.add_argument("--metrics", action="store_true",
                          help="print the metrics snapshot (TTFT / inter-token "
                               "/ queue-wait percentiles, counters, gauges)")
    obs_args.add_argument("--metrics-out", default=None, metavar="PATH",
                          help="write the metrics + per-request snapshot JSON")
    obs_args.add_argument("--trace-out", default=None, metavar="PATH",
                          help="write the versioned JSONL serve trace "
                               "(replayable: the PE-array simulator input)")
    obs_args.add_argument("--chrome-trace", default=None, metavar="PATH",
                          help="write a Chrome-trace JSON (load in Perfetto "
                               "or chrome://tracing)")
    obs_args.add_argument("--profile", default=None, metavar="DIR",
                          help="wrap the run in a jax.profiler trace: the "
                               "program's host spans (frontend.*, engine.*) "
                               "and device scopes (layers, layer, attention.*, "
                               "dot.<mode>, lm_head, sample) by name, on the "
                               "serve trace's clock")
    fe_args = ap.add_argument_group(
        "streaming frontend",
        "continuous-batching scheduler (repro.serve.frontend): requests "
        "arrive over time, admission/eviction sweeps run every tick, prefill "
        "is chunked so long prompts never stall decoding slots")
    fe_args.add_argument("--frontend", action="store_true",
                         help="serve the synthetic workload through the "
                              "continuous-batching scheduler instead of "
                              "run() (deadlines become submit-relative)")
    fe_args.add_argument("--chunk-tokens", type=int, default=32,
                         help="prefill budget: prompt rows advanced per "
                              "admission tick (bounds how long a newly "
                              "admitted prompt can stall decoding slots)")
    fe_args.add_argument("--monolithic-prefill", action="store_true",
                         help="disable chunking: prefill whole prompts in "
                              "one tick (the A/B contrast arm)")
    fe_args.add_argument("--arrival-rate", type=float, default=0.0,
                         help="--frontend: synthetic request arrivals per "
                              "second (seeded Poisson process; 0 = all "
                              "submitted at once)")
    fe_args.add_argument("--arrival-seed", type=int, default=0,
                         help="--frontend: seed for the arrival process")
    fe_args.add_argument("--stdin-requests", action="store_true",
                         help="read JSONL requests from stdin "
                              '({"rid", "prompt", "max_new", ...}) and '
                              'stream {"rid", "token"} JSONL to stdout')
    fe_args.add_argument("--http-port", type=int, default=None,
                         help="serve a minimal HTTP API on 127.0.0.1: "
                              "POST /generate with a JSON request body; "
                              "Ctrl-C to stop")
    args = ap.parse_args(argv)
    use_compile_cache()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_host_mesh, make_mesh

        if args.mesh == "auto":
            mesh = make_host_mesh()
        else:
            data, model_ext = (int(x) for x in args.mesh.split(","))
            mesh = make_mesh((data, model_ext), ("data", "model"))
        print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"over {mesh.devices.size} devices")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    fmt = FXP16 if args.fxp16 else FXP8

    if args.mode == "exact":
        ctx = EngineContext(mode="exact", compute_dtype=cfg.compute_dtype)
        policy = None
    else:
        policy = resolve_policy(args, model, params, fmt)
        ctx = EngineContext(mode=args.mode, policy=policy,
                            compute_dtype=cfg.compute_dtype)

    controller = None
    bank = None
    speculate = None
    if args.adaptive or args.speculative:
        what = "--adaptive/--speculative"
        if args.mode == "exact":
            raise SystemExit(f"{what} needs --mode carmen|int8|kernel")
        if args.per_call:
            raise SystemExit(f"--per-call contradicts {what}: the multi-point "
                             "bank IS the prepared path")
        from repro.runtime import ControllerConfig, ModeController, build_bank, default_points

        calibration = None
        if args.calibration:
            from repro.sim import load_calibration

            calibration = load_calibration(args.calibration)
            print(f"cycle calibration: {calibration['id']} "
                  f"(from {args.calibration})")
        # int8 caps at 8 effective bits: an FXP16 point would cost 1.75x
        # cycles for bit-identical arithmetic, so the ladder drops it
        hifi = None if args.mode == "int8" else FXP16
        bank = build_bank(
            params, args.mode,
            default_points(fmt, base_policy=policy, hifi_fmt=hifi),
            specs=model.specs(), mesh=mesh, calibration=calibration,
        )
        print(f"bank: points={bank.names} shared_leaves={bank.shared_leaves}/"
              f"{bank.unique_leaves} rel_cycles="
              f"{ {n: round(bank.rel_cycles(n), 3) for n in bank.names} }")
        if args.adaptive:
            controller = ModeController(bank, ControllerConfig(
                cycle_budget=args.cycle_budget,
                # speculative rounds draft cheap from the first step; the
                # verify point guards accuracy regardless
                start=bank.names[0] if args.speculative else None,
            ))
    if args.speculative:
        from repro.spec import SpecConfig

        speculate = SpecConfig(draft_len=args.draft_len,
                               draft_point=args.draft_point)

    resilience = None
    if args.deadline_ms is not None or args.queue_limit is not None or args.degrade:
        from repro.resilience import ResilienceConfig

        resilience = ResilienceConfig(
            queue_limit=args.queue_limit,
            shed_policy=args.shed_policy,
            default_deadline_s=(args.deadline_ms / 1000.0
                                if args.deadline_ms is not None else None),
        )
    if args.degrade:
        if bank is None:
            raise SystemExit("--degrade needs a multi-point bank: add "
                             "--adaptive or --speculative")
        from repro.resilience import DegradationConfig, DegradationPolicy
        from repro.runtime import ControllerConfig, ModeController

        # without --adaptive the inner controller pins the reference point —
        # degradation then is the only thing moving the ladder
        inner = controller or ModeController(
            bank, ControllerConfig(pin=bank.reference))
        controller = DegradationPolicy(
            inner, DegradationConfig(floor=args.degrade_floor))

    server = BatchedServer(
        model, ctx, params, slots=args.slots,
        max_len=args.prompt_len[1] + args.max_new
        + (args.draft_len if args.speculative else 0) + 2,
        burst=args.burst,
        prepare_weights=not args.per_call,
        controller=controller,
        speculate=speculate,
        bank=bank,
        mesh=mesh,
        resilience=resilience,
    )
    # the server holds the prepared tree (or the bank); the raw float tree
    # is dead weight on the device from here on
    del params
    if server.shardings is not None:
        from repro.sharding.partition import serving_sharding_report

        print("sharding:", json.dumps(serving_sharding_report(server.shardings)))
    observer = None
    want_trace = bool(args.trace_out or args.chrome_trace)
    if args.metrics or args.metrics_out or want_trace:
        from repro.obs import ServingObserver

        # trace_sink: the JSONL trace is flushed there even if the run
        # raises, so crashed-run traces stay replayable
        observer = ServingObserver(trace=want_trace, trace_sink=args.trace_out)
        server.observer = observer
    reqs = synthetic_requests(args, cfg.vocab_size)
    use_frontend = args.frontend or args.stdin_requests or args.http_port
    if use_frontend and mesh is not None:
        raise SystemExit("the streaming frontend is single-device for now: "
                         "drop --mesh or drop --frontend/--stdin-requests/"
                         "--http-port")
    if args.profile:
        jax.profiler.start_trace(args.profile)
    t0 = time.time()
    try:
        if use_frontend:
            results = _serve_frontend(args, server, reqs)
        else:
            results = server.run(reqs)
    finally:
        if args.profile:
            jax.profiler.stop_trace()
            print(f"jax profiler trace written to {args.profile}")
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in results.values())
    weights = "adaptive" if args.adaptive else ("per-call" if args.per_call else "prepared")
    serving = "speculative " if args.speculative else ""
    print(f"served {len(results)} requests, {total_tokens} tokens in {dt:.1f}s "
          f"({total_tokens/max(dt,1e-9):.1f} tok/s, mode={args.mode}, "
          f"burst={args.burst}, {server.host_transfers} host round-trips, "
          f"{serving}{weights} weights)")
    if resilience is not None:
        statuses: dict = {}
        for o in server.outcomes.values():
            statuses[o.status] = statuses.get(o.status, 0) + 1
        met = sum(1 for o in server.outcomes.values() if o.deadline_met)
        print(f"outcomes: {statuses}; deadline_met {met}/"
              f"{len(server.outcomes)}")
        shed = {rid: o.reason for rid, o in sorted(server.outcomes.items())
                if o.status in ("shed", "faulted", "expired")}
        if shed:
            print(f"shed/evicted reasons: {shed}")
        if args.degrade:
            print(f"degradation: cap={controller.cap} "
                  f"demotions={controller.demotions} "
                  f"promotions={controller.promotions}")
    if server.telemetry is not None:
        print("telemetry:", json.dumps(server.telemetry.summary()))
    if server.spec_telemetry is not None:
        print("speculative:", json.dumps(server.spec_telemetry.summary()))
    if observer is not None:
        if observer.trace is not None and mesh is not None:
            # the mesh cost block rides on the trace header: collective bytes
            # of the compiled decode burst, next to the sharding report
            observer.trace.attach("collectives", server.collective_snapshot())
        for out in (args.metrics_out, args.trace_out, args.chrome_trace):
            if out and os.path.dirname(out):
                os.makedirs(os.path.dirname(out), exist_ok=True)
        if args.metrics or args.metrics_out:
            snap = observer.snapshot()
            if args.metrics:
                print("metrics:", json.dumps(snap["metrics"]))
            if args.metrics_out:
                with open(args.metrics_out, "w") as f:
                    json.dump(snap, f, indent=1)
                print(f"metrics snapshot written to {args.metrics_out}")
        if args.trace_out:
            observer.trace.write_jsonl(args.trace_out)
            print(f"serve trace (JSONL) written to {args.trace_out}")
        if args.chrome_trace:
            observer.trace.write_chrome(args.chrome_trace)
            print(f"chrome trace written to {args.chrome_trace}")
    for rid in sorted(results):
        print(f"  req {rid}: {results[rid][:8]}...")
    return server, results


if __name__ == "__main__":
    main()
