"""Program spans, device scopes and the compile counter (``repro.obs.trace``).

A small streaming run and a small speculative ``run()`` are traced with the
JAX profiler on the CPU: the host thread must hold every span of
``PROGRAM_SPANS``, nested as the module documents, with ``rid`` on the
request spans, and the observer's serve trace must land on the profiler's
clock. The compiled decode burst must carry every named scope in its
``op_name`` metadata (a reduced Zamba2's burst and chunk programs the
mixer's and the shared block's too), and the compile counter must see a fresh chunk bucket
compile and a repeat of the same traffic compile nothing.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import FXP8, EngineContext, PrecisionPolicy
from repro.models import get_model
from repro.obs import ServingObserver
from repro.obs.trace import (PROGRAM_SPANS, compile_counter, open_spans,
                             span)
from repro.serve.engine import BatchedServer, Request
from repro.serve.frontend import ContinuousScheduler, FrontendConfig

EXACT = EngineContext(mode="exact", compute_dtype=jnp.float32)
NAMES = [name for name, _ in PROGRAM_SPANS]
# the documented parent of each span (None: opened outside any program span,
# as run() opens the engine spans)
PARENTS = {
    "frontend.tick": {None},
    "frontend.intake": {"frontend.tick"},
    "frontend.prefill": {"frontend.tick"},
    "frontend.flush": {"frontend.tick"},
    "engine.chunk": {"frontend.prefill"},
    "engine.admit": {"frontend.prefill"},
    "engine.admit.wait": {"frontend.prefill"},
    "engine.prefill": {"frontend.prefill", None},
    "engine.prefill.wait": {"frontend.prefill", None},
    "engine.burst": {"frontend.tick", None},
    "engine.burst.wait": {"frontend.tick", None},
    "engine.spec.draft": {"frontend.tick", None},
    "engine.spec.verify": {"frontend.tick", None},
    "engine.spec.wait": {"frontend.tick", None},
    "engine.settle": {"frontend.tick", None},
}
REQUEST_SPANS = {"engine.chunk", "engine.admit", "engine.admit.wait",
                 "engine.prefill", "engine.prefill.wait"}


@pytest.fixture(scope="module")
def olmo():
    cfg = reduced(get_config("olmo-1b"))
    model = get_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _requests(cfg, n, *, first=0, max_new=6):
    rng = np.random.default_rng(first)
    return [Request(first + i,
                    rng.integers(0, cfg.vocab_size, 5 + 3 * i).astype(np.int32),
                    max_new)
            for i in range(n)]


def _stream(server, requests, chunk_tokens=8):
    with ContinuousScheduler(server, FrontendConfig(
            chunk_tokens=chunk_tokens)) as sched:
        for r in requests:
            sched.submit(r)
        return sched.drain()


def _host_events(trace_dir):
    """The profile's start (unix ns) and the thread events holding program
    spans: ``(name, start_ns, end_ns, stats)`` with start from the profile's
    start, as ``jax.profiler.ProfileData`` reports it."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = ProfileData.from_file(path)
    start, events = None, []
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = stats["profile_start_time"]
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events]
            if any(n in NAMES for n, *_ in evs):
                events += evs
    return start, events


def _ctx(mode):
    return EngineContext(mode=mode, policy=PrecisionPolicy.accurate(FXP8),
                         compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def servers(olmo):
    """An int8 streaming server with an observer, and a speculative server
    over a kernel-mode bank (on the CPU its dots run the XLA chain)."""
    from repro.runtime import build_bank, default_points
    from repro.spec import SpecConfig

    cfg, model, params = olmo
    server = BatchedServer(model, _ctx("int8"), params, slots=2, max_len=40,
                           burst=4)
    server.observer = ServingObserver()
    bank = build_bank(params, "kernel", default_points(FXP8, hifi_fmt=None),
                      specs=model.specs())
    spec = BatchedServer(model, _ctx("kernel"), params, slots=2, max_len=40,
                         bank=bank, speculate=SpecConfig(draft_len=2))
    return {"int8": server, "kernel": spec}


@pytest.fixture(scope="module")
def profiled(olmo, servers, tmp_path_factory):
    """A streaming run with an observer's serve trace, then a speculative
    ``run()``, under one profiler trace."""
    cfg = olmo[0]
    server, spec = servers["int8"], servers["kernel"]
    # compile outside the trace: its host events would crowd the profile
    _stream(server, _requests(cfg, 3, first=100))
    spec.run(_requests(cfg, 1, first=100))
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host annotations only
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        _stream(server, _requests(cfg, 3))
        spec.run(_requests(cfg, 1))
    finally:
        jax.profiler.stop_trace()
    start, events = _host_events(trace_dir)
    return server, start, events


def _parent(ev, events):
    """The innermost program span that holds ``ev``."""
    name, s, e, _ = ev
    best = None
    for other in events:
        n, s2, e2, _ = other
        if other is ev or n not in NAMES or not (s2 <= s and e <= e2):
            continue
        if best is None or e2 - s2 < best[2] - best[1]:
            best = other
    return best[0] if best else None


def test_profile_holds_every_program_span(profiled):
    _, _, events = profiled
    seen = {n for n, *_ in events}
    assert set(NAMES) <= seen


def test_program_spans_nest_as_documented(profiled):
    _, _, events = profiled
    spans = [ev for ev in events if ev[0] in NAMES]
    for ev in spans:
        assert _parent(ev, spans) in PARENTS[ev[0]], ev[:3]
    # a tick runs its burst, waits for it, then settles, in that order
    ticks = [ev for ev in spans if ev[0] == "frontend.tick"]
    for tick in ticks:
        inside = [n for n, s, e, _ in spans
                  if tick[1] <= s and e <= tick[2]
                  and n in ("engine.burst", "engine.burst.wait",
                            "engine.settle")]
        assert inside in ([], ["engine.burst", "engine.burst.wait",
                               "engine.settle"])


def test_request_spans_carry_rid(profiled):
    _, _, events = profiled
    for name, _, _, stats in events:
        if name in REQUEST_SPANS:
            assert "rid" in stats, name


def test_serve_trace_on_profiler_clock(profiled):
    """Each burst the observer recorded starts within 1 ms of the profiler's
    ``engine.burst`` span of the same burst."""
    server, start, events = profiled
    trace = server.observer.trace
    t0_ns = trace.header["t0_ns"]
    ours = [ev["ts"] for ev in trace.events
            if ev["name"] == "burst" and ev["ph"] == "B"]
    theirs = sorted((start + s - t0_ns) * 1e-9 for n, s, _, _ in events
                    if n == "engine.burst")
    # the speculative run opens no engine.burst
    assert len(ours) >= 2 and len(theirs) == len(ours)
    for a, b in zip(ours, theirs):
        assert abs(a - b) < 1e-3


def test_span_stack_empty_after_a_raising_step(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=48, burst=4)

    def broken(sampled=True):
        raise RuntimeError("burst failed")

    server.decode_burst = broken
    sched = ContinuousScheduler(server, FrontendConfig(chunk_tokens=64))
    sched.open()
    sched.submit(_requests(cfg, 1)[0])
    with pytest.raises(RuntimeError, match="burst failed"):
        sched.step()
    assert open_spans() == ()
    sched.close(aborted=True)


def test_span_rejects_unlisted_names():
    with pytest.raises(ValueError, match="not a program span"):
        span("engine.unlisted")
    with span("frontend.tick"), span("engine.burst"):
        assert open_spans() == ("frontend.tick", "engine.burst")
    assert open_spans() == ()


SCOPES = ("layers", "layer", "attention.kv_write", "attention.core",
          "lm_head", "sample")


def _scopes(text):
    """Every scope named in a compiled program's ``op_name`` paths."""
    names = set()
    for line in text.splitlines():
        if 'op_name="' in line:
            names.update(line.split('op_name="', 1)[1].split('"', 1)[0]
                         .split("/"))
    return names


@pytest.mark.parametrize("mode,dot", [("int8", "dot.int8"),
                                      ("kernel", "dot.kernel.xla_chain")])
def test_compiled_burst_carries_every_scope(servers, mode, dot):
    """On the CPU kernel mode runs the fused kernel's XLA chain."""
    text = servers[mode].compiled_burst_text()
    assert set(SCOPES) | {dot} <= _scopes(text)


# the hybrid's own scopes: the mixer (its conv, and the recurrent step in a
# decode step or the SSD scan in a prefill block) and the shared block (its
# adapter); the shared block's attention keeps attention.*
ZAMBA_SCOPES = {
    "burst": {"mamba", "mamba.conv", "mamba.step", "shared", "shared.adapter",
              "attention.core", "attention.kv_write", "dot.int8", "layers",
              "layer", "lm_head", "sample"},
    "chunk": {"mamba", "mamba.conv", "mamba.scan", "shared", "shared.adapter",
              "attention.core", "attention.kv_write", "dot.int8"},
}


@pytest.mark.parametrize("program", sorted(ZAMBA_SCOPES))
def test_compiled_zamba2_programs_carry_the_hybrid_scopes(program):
    cfg = reduced(get_config("zamba2-7b"))
    model = get_model(cfg)
    server = BatchedServer(model, _ctx("int8"), model.init(jax.random.PRNGKey(0)),
                           slots=2, max_len=40, burst=4)
    if program == "burst":
        text = server.compiled_burst_text()
    else:
        row, last = server.fresh_row()
        text = server.chunk_fns()[0].lower(
            server.params, row, last, jnp.zeros((1, 8), jnp.int32),
            jnp.int32(0), jnp.int32(5)).compile().as_text()
    assert ZAMBA_SCOPES[program] <= _scopes(text)


def test_compile_counter_attributes_a_fresh_bucket(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=48, burst=4)
    server.observer = ServingObserver(trace=False)
    counter = compile_counter()
    before = counter.count
    reqs = lambda: _requests(cfg, 2, first=7)
    first = _stream(server, reqs())
    counters = server.observer.snapshot()["metrics"]["counters"]
    # the chunk program of a new bucket compiles inside engine.chunk, which
    # the documented nesting puts inside frontend.prefill
    assert counters.get("compiles.engine.chunk", 0) >= 1
    assert counters["compiles"] == sum(
        v for k, v in counters.items() if k.startswith("compiles."))
    assert counter.count - before >= counters["compiles"]
    # the same traffic again: every program is already built
    again = _stream(server, reqs())
    assert again == first
    assert "compiles" not in server.observer.snapshot()["metrics"]["counters"]
