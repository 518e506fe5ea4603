"""Smoke run of the serving path on a TPU at olmo-1b's published widths.

    python3 chip_smoke.py               # phases 1-3, one chip
    python3 chip_smoke.py --four-chips  # the mesh phase only, one 4-chip host

olmo-1b runs unreduced (16 layers, d_model 2048, vocab 50304, bfloat16
compute) with random weights from a fixed seed, through the CLI entry point
``repro.launch.serve.main``:

1. ``--mode kernel`` (FxP8, greedy): about 8 requests with 64-256 token
   prompts and 32 new tokens each, once through ``run()`` and once through
   the streaming frontend. The compiled decode burst must contain Pallas
   kernels (``tpu_custom_call``).
2. ``--mode int8``: the same workload.
3. Parity: the phase-1 requests again, served with ``EngineContext(
   fused="off")`` (the XLA chain in place of the fused kernel); the greedy
   tokens must equal phase 1's bit for bit.

``--four-chips`` serves one kernel-mode batch (``run()``, 4 requests of
129-256 prompt tokens, 16 new tokens) on a 2x2 ``make_host_mesh()`` and with
``mesh=None``; the greedy streams must be identical.

Every phase prints one line. The script exits non-zero, and prints no result,
when JAX finds no TPU or a phase fails; its last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything runs in this one process: the chip belongs to one process at a
time.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402

from repro.obs.trace import compile_counter  # noqa: E402

ARCH = "olmo-1b"
REQUESTS, SLOTS, MAX_NEW, BURST = 8, 4, 32, 8
PROMPTS = "64-256"
# the mesh phase serves one batch (4 requests, 16 new tokens) of prompts that
# all land in the 256-row prefill bucket: one prefill program and two bursts
# per run, as each second on a four-chip host costs four
MESH_REQUESTS, MESH_MAX_NEW, MESH_PROMPTS = 4, 16, "129-256"

def serve_argv(mode: str, *, reduced: bool = False, frontend: bool = False,
               mesh: str | None = None, prompts: str = PROMPTS,
               requests: int = REQUESTS, max_new: int = MAX_NEW):
    argv = ["--arch", ARCH, "--mode", mode, "--prompt-len", prompts,
            "--requests", str(requests), "--slots", str(SLOTS),
            "--max-new", str(max_new), "--burst", str(BURST)]
    if reduced:
        argv.append("--reduced")
    if frontend:
        argv.append("--frontend")
    if mesh:
        argv += ["--mesh", mesh]
    return argv


def custom_calls(server) -> int:
    """Pallas kernels in the compiled greedy decode burst."""
    return server.compiled_burst_text().count(
        'custom_call_target="tpu_custom_call"')


def check_streams(results, *, requests: int, max_new: int, vocab: int):
    """Every request got exactly ``max_new`` tokens, each a vocab id."""
    if sorted(results) != list(range(requests)):
        raise AssertionError(f"served rids {sorted(results)}, want 0..{requests - 1}")
    for rid, toks in results.items():
        if len(toks) != max_new or not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"req {rid}: bad stream {toks}")


def serve_phase(mode: str, *, reduced: bool = False):
    """Phases 1 and 2: the workload through ``run()`` and through the
    frontend. Returns a record with both streams and the burst's kernel
    count."""
    from repro.launch import serve

    t0 = time.perf_counter()
    server, run_results = serve.main(serve_argv(mode, reduced=reduced))
    cfg = server.model.cfg
    kernels = custom_calls(server)
    del server
    gc.collect()
    _, fe_results = serve.main(serve_argv(mode, reduced=reduced, frontend=True))
    gc.collect()
    for res in (run_results, fe_results):
        check_streams(res, requests=REQUESTS, max_new=MAX_NEW,
                      vocab=cfg.vocab_size)
    return {
        "mode": mode,
        "run": run_results,
        "frontend": fe_results,
        "frontend_equals_run": fe_results == run_results,
        "custom_calls": kernels,
        "seconds": time.perf_counter() - t0,
    }


def parity_phase(reference, *, reduced: bool = False):
    """Phase 3: the kernel-mode workload with the fused kernel off (the XLA
    chain) must reproduce ``reference`` (phase 1's ``run()`` streams)."""
    from repro.configs import get_config, reduced as reduce_cfg
    from repro.core import FXP8, EngineContext, PrecisionPolicy
    from repro.launch import serve
    from repro.models import get_model
    from repro.serve.engine import BatchedServer

    t0 = time.perf_counter()
    # serve.main's workload and cache geometry, built without the CLI
    prompt_len = serve.prompt_lengths(PROMPTS)
    args = argparse.Namespace(requests=REQUESTS, prompt_len=prompt_len,
                              max_new=MAX_NEW, temperature=0.0, seed=None)
    cfg = get_config(ARCH)
    if reduced:
        cfg = reduce_cfg(cfg)
    model = get_model(cfg)
    ctx = EngineContext(mode="kernel", policy=PrecisionPolicy.accurate(FXP8),
                        compute_dtype=cfg.compute_dtype, fused="off")
    server = BatchedServer(model, ctx, model.init(jax.random.PRNGKey(0)),
                           slots=SLOTS, max_len=prompt_len[1] + MAX_NEW + 2,
                           burst=BURST)
    results = server.run(serve.synthetic_requests(args, cfg.vocab_size))
    del server
    gc.collect()
    mismatched = sorted(r for r in reference if results.get(r) != reference[r])
    if mismatched:
        raise AssertionError(f"fused='off' streams differ for rids {mismatched}")
    return {"requests": len(results), "seconds": time.perf_counter() - t0}


def mesh_phase(*, reduced: bool = False):
    """``--four-chips``: ``run()`` on the host mesh must equal ``mesh=None``."""
    from repro.launch import serve

    t0 = time.perf_counter()
    argv = functools.partial(serve_argv, "kernel", reduced=reduced,
                             prompts=MESH_PROMPTS, requests=MESH_REQUESTS,
                             max_new=MESH_MAX_NEW)
    _, single = serve.main(argv())
    gc.collect()
    server, meshed = serve.main(argv(mesh="auto"))
    mesh = dict(zip(server.mesh.axis_names, server.mesh.devices.shape))
    vocab = server.model.cfg.vocab_size
    del server
    gc.collect()
    if meshed != single:
        diff = sorted(r for r in single if meshed.get(r) != single[r])
        raise AssertionError(f"mesh {mesh} streams differ for rids {diff}")
    check_streams(meshed, requests=MESH_REQUESTS, max_new=MESH_MAX_NEW,
                  vocab=vocab)
    return {"mesh": mesh, "requests": len(meshed),
            "seconds": time.perf_counter() - t0}


def _memory(dev):
    """Device bytes held now (after the phase dropped its server: what
    outlives it) and the process's peak so far."""
    gc.collect()
    stats = dev.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh phase against mesh=None")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.launch import serve

    cache = serve.use_compile_cache()
    print(f"device_kind={dev.device_kind} platform={dev.platform} "
          f"count={len(devices)} compile_cache={cache}", flush=True)
    compiles = compile_counter()
    lapped = compiles.seconds

    def report(name, rec):
        # seconds of the phase's backend compiles (cache loads included)
        nonlocal lapped
        rec = {k: v for k, v in rec.items() if k not in ("run", "frontend")}
        rec.update(compile_s=compiles.seconds - lapped, **_memory(dev))
        lapped = compiles.seconds
        print(f"phase {name}: {json.dumps(rec)}", flush=True)

    if args.four_chips:
        report("mesh", mesh_phase())
    else:
        kernel = serve_phase("kernel")
        report("kernel", kernel)
        if kernel["custom_calls"] == 0:
            raise AssertionError("kernel-mode decode burst has no Pallas kernels")
        report("int8", serve_phase("int8"))
        report("parity", parity_phase(kernel["run"]))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
