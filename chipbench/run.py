"""Run one benchmark cell on the chip and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration and a traffic mix. The run builds the program's own streaming
server through its public API (``BatchedServer`` wrapped in a
``ContinuousScheduler``), with weights drawn from the seed on the device,
warms every program the traffic will use, drives the traffic through
``submit()`` and ``step()`` on this one thread for ``--seconds``, and then
checks a seeded sample of the served tokens against the configuration's
plain reference. The last line of standard output is one JSON object: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

Programs are never compiled in the measuring process. Where the compile
cache holds no complete set-up of this cell for this code, a child process
does the set-up first (``--prepare-only``) and fills the cache; the
measuring process then starts and loads every program from there, so the
first run of a checkout measures what every later run does (PERF.md,
section 6).

Without a TPU, or with fewer chips than the cell asks for, it exits with 2
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.metadata  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import check, manifest, traffic, work  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")  # unless the environment names one
# a traced run measures at most this long: the device trace holds some
# 80,000 operations a second, and reading it back must fit the run's time
TRACE_SECONDS = 10.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
GIB = 1 << 30


class CompileClock:
    """Backend compiles (persistent-cache loads included), counted from
    JAX's monitoring events."""

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


class Track:
    """One submitted request, as the client sees it."""

    def __init__(self, spec, handle, due: float):
        self.spec = spec
        self.handle = handle
        self.due = due            # when it was submitted
        self.events = []          # (host arrival time, tokens so far)
        self.done = None          # host time it settled

    def poll(self, now: float) -> bool:
        """Record tokens that arrived; True once the request has settled."""
        n = len(self.handle.tokens)
        if n > (self.events[-1][1] if self.events else 0):
            self.events.append((now, n))
        if self.handle.done and self.done is None:
            self.done = now
        return self.done is not None


class Load:
    """Feeds a closed loop of clients into the scheduler and ticks it on this
    thread: each client sends its next request when its last one settled."""

    def __init__(self, jax, sched, mix, seed, vocab):
        from repro.serve.engine import Request

        self.jax = jax
        self.sched = sched
        self.mix = mix
        self.Request = Request
        self.tracks = []
        self.live = []
        self.pool = traffic.closed_pool(mix, seed, vocab)
        self.next = 0

    def _submit(self, spec, now):
        req = self.Request(len(self.tracks), spec.prompt, spec.max_new)
        with self.jax.profiler.TraceAnnotation("bench.submit"):
            handle = self.sched.submit(req)
        track = Track(spec, handle, now)
        self.tracks.append(track)
        self.live.append(track)

    def tick(self):
        """Submit for every idle client, run one scheduler step, collect
        tokens."""
        clock = time.perf_counter
        while len(self.live) < self.mix["concurrency"]:
            self._submit(self.pool[self.next % len(self.pool)], clock())
            self.next += 1
        with self.jax.profiler.TraceAnnotation("bench.step"):
            self.sched.step()
        now = clock()
        self.live = [t for t in self.live if not t.poll(now)]
        return now

    def warm_until(self, done: int):
        """Run the cell's own traffic until ``done`` requests have finished."""
        while sum(t.done is not None for t in self.tracks) < done:
            self.tick()
        return time.perf_counter()

    def run_until(self, t_stop: float) -> float:
        now = time.perf_counter()
        while now < t_stop:
            now = self.tick()
        return now


def warm_buckets(sched, chunk_tokens: int, vocab: int):
    """Compile every chunk bucket the traffic can produce (powers of two up
    to ``chunk_tokens``: a tick's prefill budget splits prompts at any row),
    the admit program and the greedy burst: one two-token request per
    bucket, served alone."""
    import numpy as np
    from repro.serve.engine import Request

    b, rid = 1, 10**9
    while b <= chunk_tokens:
        handle = sched.submit(Request(rid, np.arange(b, dtype=np.int32) % vocab,
                                      2))
        while not handle.done:
            sched.step()
        b, rid = b * 2, rid + 1


def program_config(cfg_file):
    """The program's model config for ``arch``, with the fields that
    ``program_fields`` maps to keys of the file (``{field: key}``) and the
    serving compute dtype: the file holds the configuration as it is run."""
    import dataclasses

    from repro.configs import get_config

    fields = {field: cfg_file[key]
              for field, key in cfg_file["program_fields"].items()}
    fields["dtype"] = cfg_file["serving"]["compute_dtype"]
    return dataclasses.replace(get_config(cfg_file["arch"]), **fields)


def _named(table, kind, name):
    if name not in table:
        raise ValueError(f"unknown {kind} {name!r}; known: {sorted(table)}")
    return table[name]


def engine_context(serving, compute_dtype):
    """The program's ``EngineContext`` for the file's ``serving`` entry:
    ``mode``, the FxP ``format`` and the ``policy`` constructor, by name."""
    from repro.core import FXP8, FXP16, EngineContext, PrecisionPolicy

    fmt = _named({"fxp8": FXP8, "fxp16": FXP16}, "format", serving["format"])
    policy = _named({"accurate": PrecisionPolicy.accurate,
                     "approximate": PrecisionPolicy.approximate},
                    "policy", serving["policy"])
    return EngineContext(mode=serving["mode"], policy=policy(fmt),
                         compute_dtype=compute_dtype)


def build(cfg_file, mix, seed):
    """The program's streaming server, as ``launch/serve.py`` builds it."""
    from chipbench.weights import make_weights
    from repro.models import get_model
    from repro.serve.engine import BatchedServer
    from repro.serve.frontend import ContinuousScheduler, FrontendConfig

    program_cfg = program_config(cfg_file)
    serving = cfg_file["serving"]
    _named({"prepared": True}, "weights", serving["weights"])
    model = get_model(program_cfg)
    ctx = engine_context(serving, program_cfg.compute_dtype)
    # the raw tree is held by nothing once the server has prepared it
    server = BatchedServer(model, ctx,
                           make_weights(model, seed, **cfg_file["weights"]),
                           slots=mix["slots"], max_len=traffic.max_len(mix),
                           burst=serving["burst"])
    sched = ContinuousScheduler(server, FrontendConfig(
        chunk_tokens=mix["chunk_tokens"]))
    return model, server, sched


def window_stats(tracks, t0, t_end):
    """Output tokens delivered in the window ``(t0, t_end]``, and the time
    per output token of each of them after its request's first delivery:
    the time since the request's previous delivery over the tokens this one
    brought (a burst delivers several at once)."""
    tokens, per_token = 0, []
    for tr in tracks:
        prev_t, prev_n = None, 0
        for t, n in tr.events:
            if t0 < t <= t_end:
                tokens += n - prev_n
                if prev_t is not None:
                    per_token += [(t - prev_t) / (n - prev_n)] * (n - prev_n)
            prev_t, prev_n = t, n
    return {"tokens": tokens, "per_token": per_token}


def p95(values):
    """95th percentile of all values (the exclusive method, as ``statistics``
    computes it; the largest value when there are fewer than 20)."""
    if len(values) < 20:
        return max(values)
    return statistics.quantiles(values, n=20)[-1]


def decide(gap, n_checked, cfg_file, mix):
    """``(correct, checks)``: the served tokens' widest reference gap within
    the configuration's limit, over enough served tokens."""
    limit = cfg_file["check"]["max_logit_gap"]
    checks = {"max_logit_gap": {"value": gap, "limit": limit},
              "served_tokens_checked": {"value": n_checked,
                                        "limit": mix["check_min_tokens"]}}
    correct = (gap is not None and gap <= limit
               and n_checked >= mix["check_min_tokens"])
    return bool(correct), checks


def set_up(jax, cfg_file, mix, seed, log=sys.stderr):
    """Build the server and run the cell's own traffic until the slots are
    in steady state; returns ``(model, server, sched, load)``."""
    t = time.perf_counter()
    model, server, sched = build(cfg_file, mix, seed)
    sched.open()
    t_built = time.perf_counter()
    vocab = cfg_file["vocab_size"]
    with jax.profiler.TraceAnnotation("bench.warmup"):
        warm_buckets(sched, mix["chunk_tokens"], vocab)
        t_buckets = time.perf_counter()
        load = Load(jax, sched, mix, seed, vocab)
        load.warm_until(mix["warmup_done"])
    print(f"set-up: imports {t - T_PROCESS:.1f}s, server built "
          f"{t_built - t:.1f}s, chunk buckets {t_buckets - t_built:.1f}s, "
          f"warm-up traffic {time.perf_counter() - t_buckets:.1f}s",
          file=log, flush=True)
    return model, server, sched, load


def run_cell(bench, workload, seed, seconds, trace, *, log=sys.stderr,
             control=False):
    """One run of one cell; returns the result object (not yet printed).

    ``control`` also reads the control (``chipbench/calibrate.py``); the
    benchmark's own runs never do."""
    import jax

    clock = CompileClock(jax)

    cell = manifest.workload(bench, workload)
    cfg_file = manifest.config(cell["config"])
    mix = manifest.traffic(cell["traffic"])
    traffic.validate(mix)
    model, server, sched, load = set_up(jax, cfg_file, mix, seed, log)
    # what set-up left behind is never garbage: keep the collector off it
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    setup_s = t0 - T_PROCESS
    compiles0, bursts0, rows0 = (clock.count, sched.stats["bursts"],
                                 sched.stats["prefill_rows"])
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        seconds = min(seconds, TRACE_SECONDS)
    with jax.profiler.TraceAnnotation("bench.window"):
        t_end = load.run_until(t0 + seconds)
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - t0
    compiles = clock.count - compiles0
    bursts = sched.stats["bursts"] - bursts0
    prefill_rows = sched.stats["prefill_rows"] - rows0
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    print(f"window: {window_s:.3f}s, compiles inside it: {compiles}, "
          f"bursts {bursts}, prefill rows {prefill_rows}", file=log, flush=True)

    stats = window_stats(load.tracks, t0, t_end)
    attempted = [t for t in load.tracks if t0 < t.due <= t_end]
    failed = sum(t.handle.status in ("shed", "faulted", "expired")
                 for t in attempted)
    finished = [(t.spec.prompt, list(t.handle.tokens)) for t in load.tracks
                if t.handle.status == "ok"]
    # what the per-layer metrics read (chipbench/metrics/*.py)
    record = {
        "cfg": cfg_file, "window_s": window_s, "t0": t0, "t_end": t_end,
        "bursts": bursts, "burst": cfg_file["serving"]["burst"],
        "slots": mix["slots"], "prefill_rows": prefill_rows,
        "tracks": [(t.spec, t.due, t.events) for t in load.tracks],
    }
    sched.close()
    del server, sched, load, model
    gc.unfreeze()
    gc.collect()

    t_check = time.perf_counter()
    gap, n_checked, control_gap = check_outputs(cfg_file, seed, mix,
                                                finished, control)
    print(f"check: {n_checked} served tokens of {mix['check_requests']} "
          f"requests against the reference in "
          f"{time.perf_counter() - t_check:.1f}s", file=log, flush=True)
    correct, checks = decide(gap, n_checked, cfg_file, mix)
    if control:
        control_correct, _ = decide(control_gap, n_checked, cfg_file, mix)
        checks["control_max_logit_gap"] = {
            "value": control_gap, "limit": cfg_file["check"]["max_logit_gap"],
            "correct": control_correct}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(attempted),
           "failed": int(failed)}
    if trace:
        from chipbench import trace as tr

        reduction = tr.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if reduction is None:
            raise RuntimeError("the trace holds no device operations in the "
                               "window")
        record["trace"] = reduction
        record["peak"] = work.peaks(dev.device_kind)
        metrics = {}
        for m in manifest.per_layer(bench, workload):
            value = manifest.metric_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=reduction["busy_s"],
                      window_s=reduction["window_s"])
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = reduction["breakdown"]
    else:
        values = {
            "tokens_per_s": stats["tokens"] / window_s,
            "tpot_p95_ms": (1e3 * p95(stats["per_token"])
                            if stats["per_token"] else None),
            "peak_hbm_gib": peak / GIB,
            "setup_s": setup_s,
        }
        metrics = {}
        for m in manifest.end_to_end(bench, workload):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
    out["window"] = {"seconds": window_s, "compiles": compiles,
                     "requests_finished": len(finished),
                     "compile_s_total": clock.seconds}
    out["checks"] = checks
    return out


def check_outputs(cfg_file, seed, mix, finished, control=False):
    """Widest reference gap of the served tokens in a seeded sample of the
    finished requests, how many served tokens it covered, and (with
    ``control``) the control's widest gap at the same positions."""
    from chipbench.weights import make_weights
    from repro.models import get_model

    pick = check.sample(finished, seed, mix["check_requests"])
    if not pick:
        return None, 0, None
    ref = manifest.reference(cfg_file)
    model = get_model(program_config(cfg_file))
    weights = make_weights(model, seed, **cfg_file["weights"])
    worst, n, control_worst = 0.0, 0, None
    for i in pick:
        prompt, served = finished[i]
        g = check.served_gaps(ref.logits, prompt, served, cfg_file, weights,
                              control=control)
        worst = max(worst, float(g["served"].max()))
        if control:
            control_worst = max(control_worst or 0.0, float(g["control"].max()))
        n += len(served)
    del weights
    gc.collect()
    return worst, n, control_worst


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` where the environment sets it, else
    ``.jax_cache/`` at the root of the checkout: a fixed path (it is part of
    the cache's key), so only a cell's first run in a checkout compiles."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR


def use_compile_cache(jax, path: str):
    """Keep every compiled program in ``path``, however short its compile."""
    os.makedirs(path, exist_ok=True)  # jax writes into it, never makes it
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def prepared_marker(cache_dir: str, cell) -> str:
    """The file that says the cache holds this cell's set-up for this code:
    named by the cell and a hash of what decides its programs (the
    program's sources, the harness that builds and warms the server, the
    cell's configuration and traffic files), of the JAX version and of the
    checkout's path, which the programs' source locations carry."""
    h = hashlib.sha256(ROOT.encode())
    for pkg in ("jax", "jaxlib"):
        h.update(importlib.metadata.version(pkg).encode())
    h.update(json.dumps([manifest.config(cell["config"]),
                         manifest.traffic(cell["traffic"])],
                        sort_keys=True).encode())
    here = os.path.dirname(os.path.abspath(__file__))
    files = [os.path.join(here, n) for n in ("run.py", "weights.py",
                                              "traffic.py")]
    for d, _, names in os.walk(os.path.join(ROOT, "src")):
        files += [os.path.join(d, n) for n in names if n.endswith(".py")]
    for path in sorted(files):
        h.update(os.path.relpath(path, ROOT).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return os.path.join(cache_dir, "chipbench-prepared",
                        f"{cell['name']}.{h.hexdigest()[:16]}")


def prepare(args, cell, cache_dir: str) -> int:
    """Fill the compile cache with the cell's set-up in a child process,
    unless it holds it already. This process must not have touched a chip:
    the child needs it. Returns the child's exit code (0 when none ran)."""
    marker = prepared_marker(cache_dir, cell)
    if os.path.exists(marker):
        return 0
    cmd = [sys.executable, os.path.abspath(__file__), "--workload",
           args.workload, "--seed", str(args.seed), "--seconds", "0",
           "--prepare-only"]
    print(f"chipbench: no prepared set-up in {cache_dir}; preparing it in a "
          "child process", file=sys.stderr, flush=True)
    # the child's output goes to standard error: the result line is ours
    return subprocess.run(cmd, stdout=sys.stderr, check=False).returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--prepare-only", action="store_true",
                    help="do the cell's set-up, fill the compile cache, and "
                         "exit without a result")
    args = ap.parse_args(argv)

    bench = manifest.load()
    cell = manifest.workload(bench, args.workload)
    cache_dir = compile_cache_dir()
    if not args.prepare_only:
        rc = prepare(args, cell, cache_dir)
        if rc:
            return rc
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). Nothing run.",
              file=sys.stderr)
        return 2
    use_compile_cache(jax, cache_dir)
    if args.prepare_only:
        cfg_file = manifest.config(cell["config"])
        mix = manifest.traffic(cell["traffic"])
        traffic.validate(mix)
        set_up(jax, cfg_file, mix, args.seed)[2].close()
        marker = prepared_marker(cache_dir, cell)
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        with open(marker, "w") as f:
            f.write("set-up complete\n")
        return 0
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
