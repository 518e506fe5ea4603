"""Serving observability: bit-identity, SLO metrics, trace well-formedness.

The observability layer's core contract is that it is a pure observer: every
hook runs host-side at a synchronization point the serving loop already pays
for, so attaching a :class:`~repro.obs.ServingObserver` must never change a
token stream — across dense / MoE / MLA, adaptive, speculative, and mesh
serving. The rest of this file pins the exported artifacts: histograms
populated with plausible (monotone, non-negative) latencies, Chrome traces
that load as valid nesting-consistent JSON, JSONL traces that round-trip
through :func:`repro.obs.read_trace`, symmetric reset/export across run
reuse and aborted runs, the unified telemetry ``to_dict`` shape, and the
``teacher_forced_agreement`` edge cases.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.configs import get_config, reduced
from repro.core import EngineContext, FXP16, PrecisionPolicy
from repro.models import get_model
from repro.obs import (
    MetricsRegistry,
    ServingObserver,
    StreamingHistogram,
    TraceRecorder,
    TRACE_SCHEMA,
    TRACE_VERSION,
    read_trace,
)
from repro.runtime import teacher_forced_agreement
from repro.serve.engine import BatchedServer, Request

EXACT = EngineContext(mode="exact", compute_dtype=jnp.float32)


def _setup(arch):
    cfg = reduced(get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, *, max_new=6):
    rng = np.random.default_rng(0)
    return [
        Request(i, rng.integers(0, cfg.vocab_size, 3 + i).astype(np.int32),
                max_new)
        for i in range(n)
    ]


@pytest.fixture(scope="module")
def olmo():
    return _setup("olmo-1b")


def _bank_and_ctx(model, params):
    from repro.runtime import build_bank, default_points

    ctx = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP16),
                        compute_dtype=jnp.float32)
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    return bank, ctx


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_streaming_histogram_summary():
    h = StreamingHistogram()
    for v in (0.001, 0.002, 0.004, 0.008, 0.1):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(0.1)
    assert s["mean"] == pytest.approx(0.023)
    # quantiles come from geometric bucket midpoints, clamped to [min, max],
    # so they are within one bucket's growth factor of the exact value
    assert 0.001 <= s["p50"] <= 0.008
    assert s["p50"] <= s["p90"] <= s["p99"] <= s["max"]


def test_streaming_histogram_weighted_observe():
    h = StreamingHistogram()
    h.observe(0.5, n=7)
    assert h.count == 7
    assert h.summary()["p99"] == pytest.approx(0.5)


@given(st.lists(st.floats(min_value=1e-6, max_value=1e3), min_size=1,
                max_size=200))
@settings(max_examples=60, deadline=None)
def test_streaming_histogram_quantile_bound(values):
    """The documented accuracy contract, as a property: every reported
    percentile is within one geometric-bucket growth factor of the exact
    order statistic, for any latency-plausible value set.

    The histogram's quantile is the midpoint of the bucket holding the
    rank-th observation; a value ``v`` in bucket ``i`` satisfies
    ``floor*growth**(i-1) < v <= floor*growth**i``, so midpoint/value lies
    in ``[growth**-0.5, growth**0.5)`` — and the [min, max] clamp can only
    move the estimate *toward* the exact value, never past it.
    """
    h = StreamingHistogram()
    for v in values:
        h.observe(v)
    ordered = sorted(values)
    for q in (0.50, 0.90, 0.99):
        exact = ordered[max(math.ceil(q * len(ordered)) - 1, 0)]
        approx = h.quantile(q)
        ratio = approx / exact
        assert 1 / h.growth <= ratio <= h.growth * (1 + 1e-9), (
            f"p{q}: approx {approx} vs exact {exact} "
            f"(ratio {ratio}, growth {h.growth})")
    # exact aggregates stay exact regardless of bucketing
    assert h.count == len(values)
    assert h.lo == pytest.approx(min(values))
    assert h.hi == pytest.approx(max(values))
    assert h.total == pytest.approx(sum(values), rel=1e-9)


@given(st.floats(min_value=1e-12, max_value=1e-7),
       st.floats(min_value=1e-12, max_value=1e-7))
@settings(max_examples=30, deadline=None)
def test_streaming_histogram_below_floor_clamps_exact(a, b):
    """Values at or below the bucket floor all share bucket 0, whose raw
    midpoint is the floor itself — the [min, max] clamp is what keeps the
    reported percentiles inside the actually-observed range."""
    h = StreamingHistogram()
    h.observe(a)
    h.observe(b)
    for q in (0.50, 0.99):
        assert min(a, b) <= h.quantile(q) <= max(a, b)


def test_streaming_histogram_single_huge_value_clamped():
    # the top tail: one bucket past every observation returns hi, and the
    # clamp keeps midpoints from overshooting the observed max
    h = StreamingHistogram()
    h.observe(5e4)
    for q in (0.5, 0.9, 0.99):
        assert h.quantile(q) == pytest.approx(5e4)


def test_registry_reset_symmetric():
    reg = MetricsRegistry()
    reg.inc("tokens", 3)
    reg.set("tok_s", 9.0)
    reg.observe("ttft_s", 0.1)
    snap = reg.snapshot()
    assert snap["counters"]["tokens"] == 3
    assert snap["gauges"]["tok_s"] == 9.0
    assert snap["histograms"]["ttft_s"]["count"] == 1
    reg.reset()
    empty = reg.snapshot()
    assert empty["counters"] == {} and empty["histograms"] == {}


# ---------------------------------------------------------------------------
# trace primitives
# ---------------------------------------------------------------------------


def test_trace_nesting_enforced_at_record_time():
    tr = TraceRecorder()
    tr.begin("outer")
    tr.begin("inner")
    with pytest.raises(ValueError, match="span mismatch"):
        tr.end("outer")  # inner is still open on the same track
    tr.end("inner")
    tr.end("outer")


def test_trace_close_open_settles_aborted_spans():
    tr = TraceRecorder()
    tr.begin("run", track="run")
    tr.begin("burst")
    tr.close_open(aborted=True)
    phases = [(e["ph"], e["name"]) for e in tr.events]
    assert phases.count(("E", "burst")) == 1
    assert phases.count(("E", "run")) == 1


def test_trace_jsonl_roundtrip_and_version_guard(tmp_path):
    tr = TraceRecorder()
    tr.attach("run", {"family": "t"})
    tr.instant("x", rid=0)
    path = str(tmp_path / "t.jsonl")
    tr.write_jsonl(path)
    header, events = read_trace(path)
    assert header["schema"] == TRACE_SCHEMA
    assert header["version"] == TRACE_VERSION
    assert header["run"] == {"family": "t"}
    assert len(events) == 1 and events[0]["name"] == "x"

    future = str(tmp_path / "future.jsonl")
    with open(future, "w") as f:
        f.write(json.dumps({"schema": TRACE_SCHEMA,
                            "version": TRACE_VERSION + 1}) + "\n")
    with pytest.raises(ValueError, match="newer than this reader"):
        read_trace(future)
    alien = str(tmp_path / "alien.jsonl")
    with open(alien, "w") as f:
        f.write(json.dumps({"schema": "other"}) + "\n")
    with pytest.raises(ValueError, match="not a"):
        read_trace(alien)


# ---------------------------------------------------------------------------
# bit-identity: observability must never change a token stream
# ---------------------------------------------------------------------------


def _run_pair(model, ctx, params, cfg, **kw):
    """The same workload with and without an observer attached."""
    plain = BatchedServer(model, ctx, params, slots=2, max_len=32, **kw)
    ref = plain.run(_requests(cfg, 3))
    watched = BatchedServer(model, ctx, params, slots=2, max_len=32, **kw)
    watched.observer = ServingObserver()
    out = watched.run(_requests(cfg, 3))
    return ref, out, watched


@pytest.mark.parametrize("arch", ["olmo-1b", "llama4-maverick-400b-a17b",
                                  "deepseek-v3-671b"])
def test_observer_bit_identical(arch):
    cfg, model, params = _setup(arch)
    ref, out, _ = _run_pair(model, EXACT, params, cfg, burst=4)
    assert out == ref


def test_observer_bit_identical_adaptive(olmo):
    from repro.runtime import ControllerConfig, ModeController

    cfg, model, params = olmo
    bank, ctx = _bank_and_ctx(model, params)
    make_ctrl = lambda: ModeController(bank, ControllerConfig(cycle_budget=0.8))
    plain = BatchedServer(model, ctx, params, slots=2, max_len=32, burst=4,
                          controller=make_ctrl())
    ref = plain.run(_requests(cfg, 3))
    watched = BatchedServer(model, ctx, params, slots=2, max_len=32, burst=4,
                            controller=make_ctrl())
    watched.observer = ServingObserver()
    assert watched.run(_requests(cfg, 3)) == ref
    # the observer saw the run without steering it
    assert watched.snapshot()["observability"]["metrics"]["counters"]["tokens"] \
        == sum(len(v) for v in ref.values())


def test_observer_bit_identical_speculative(olmo):
    from repro.spec import SpecConfig

    cfg, model, params = olmo
    bank, ctx = _bank_and_ctx(model, params)
    spec = lambda: SpecConfig(draft_len=3)
    plain = BatchedServer(model, ctx, params, slots=2, max_len=40, bank=bank,
                          speculate=spec())
    ref = plain.run(_requests(cfg, 3))
    watched = BatchedServer(model, ctx, params, slots=2, max_len=40, bank=bank,
                            speculate=spec())
    watched.observer = ServingObserver()
    assert watched.run(_requests(cfg, 3)) == ref
    counters = watched.observer.metrics.snapshot()["counters"]
    assert counters["spec_rounds"] > 0
    names = {e["name"] for e in watched.observer.trace.events}
    assert {"spec_draft", "spec_verify", "spec_rollback"} <= names


def test_observer_bit_identical_mesh(olmo):
    cfg, model, params = olmo
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    ref, out, watched = _run_pair(model, EXACT, params, cfg, burst=4, mesh=mesh)
    assert out == ref
    # the mesh cost block is available for the trace header
    coll = watched.collective_snapshot()
    assert set(coll) == {"collective_bytes", "collective_by_kind"}


# ---------------------------------------------------------------------------
# SLO metrics + trace contents of a real run
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def observed_run(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=4)
    server.observer = ServingObserver()
    out = server.run(_requests(cfg, 4))
    return server, out


def test_slo_histograms_populated(observed_run):
    server, out = observed_run
    snap = server.observer.snapshot()
    hists = snap["metrics"]["histograms"]
    gen = sum(len(v) for v in out.values())
    assert hists["ttft_s"]["count"] == 4
    assert hists["queue_wait_s"]["count"] == 4
    # every token past each request's first contributes inter-token weight
    assert hists["intertoken_s"]["count"] == gen - 4
    for name in ("ttft_s", "intertoken_s", "queue_wait_s", "prefill_s",
                 "decode_burst_s", "request_s"):
        h = hists[name]
        assert h["count"] > 0
        assert 0.0 <= h["min"] <= h["mean"] <= h["max"]
        assert h["min"] - 1e-12 <= h["p50"] <= h["p90"] <= h["p99"] <= h["max"] + 1e-12
    counters = snap["metrics"]["counters"]
    assert counters["tokens"] == gen
    assert counters["host_transfers"] == server.host_transfers
    assert counters["requests"] == 4 and "evicted" not in counters


def test_per_request_rows_monotone(observed_run):
    server, out = observed_run
    rows = server.observer.snapshot()["requests"]
    for rid, row in rows.items():
        assert row["completed"]
        assert row["tokens"] == len(out[rid])
        # submit <= admit <= first token: queue wait can never exceed TTFT
        assert 0.0 <= row["queue_wait_s"] <= row["ttft_s"]
        assert row["request_s"] >= 0.0


def test_trace_events_monotone_and_nested(observed_run):
    server, _ = observed_run
    events = server.observer.trace.events
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)  # recorded strictly in wall order
    stacks = {}
    for e in events:
        stack = stacks.setdefault(e["track"], [])
        if e["ph"] == "B":
            stack.append(e["name"])
        elif e["ph"] == "E":
            assert stack and stack[-1] == e["name"]
            stack.pop()
    assert all(not s for s in stacks.values())  # every span closed


def test_chrome_export_valid_and_balanced(observed_run, tmp_path):
    server, _ = observed_run
    path = str(tmp_path / "trace.json")
    server.observer.trace.write_chrome(path)
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert doc["metadata"]["schema"] == TRACE_SCHEMA
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"engine", "run", "sched"} <= names
    per_tid = {}
    for e in events:
        if e["ph"] in ("B", "E"):
            per_tid[e["tid"]] = per_tid.get(e["tid"], 0) + (
                1 if e["ph"] == "B" else -1)
    assert all(v == 0 for v in per_tid.values())


def test_jsonl_export_roundtrips_run(observed_run, tmp_path):
    server, _ = observed_run
    path = str(tmp_path / "trace.jsonl")
    server.observer.trace.write_jsonl(path)
    header, events = read_trace(path)
    assert header["run"]["slots"] == 2 and header["run"]["burst"] == 4
    assert header["meta"]["aborted"] is False
    assert len(events) == len(server.observer.trace.events)


def test_shed_requests_contribute_queue_wait(olmo):
    """queue_wait_s is submission -> leaving the queue, by admission OR by
    shed: a request shed for queue overflow still waited, and dropping its
    sample would optimistically bias the tail exactly when shedding is
    heaviest. Every offered request lands exactly one queue_wait sample."""
    from repro.resilience import ResilienceConfig

    cfg, model, params = olmo
    server = BatchedServer(
        model, EXACT, params, slots=1, max_len=32, burst=4,
        resilience=ResilienceConfig(queue_limit=2))
    server.observer = ServingObserver(trace=False)
    out = server.run(_requests(cfg, 5))
    shed = [o for o in server.outcomes.values() if o.status == "shed"]
    assert len(shed) == 3 and len(out) == 2
    hists = server.observer.snapshot()["metrics"]["histograms"]
    assert hists["queue_wait_s"]["count"] == 5  # 2 admitted + 3 shed
    counters = server.observer.snapshot()["metrics"]["counters"]
    assert counters["shed"] == 3 and counters["requests"] == 5


# ---------------------------------------------------------------------------
# run reuse + aborted runs: reset and export must be symmetric
# ---------------------------------------------------------------------------


def test_aborted_run_resets_cleanly_for_reuse(olmo):
    cfg, model, params = olmo
    ref = BatchedServer(model, EXACT, params, slots=2, max_len=32,
                        burst=4).run(_requests(cfg, 3))

    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=4)
    server.observer = ServingObserver()
    server._burst_round = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("induced failure"))
    with pytest.raises(RuntimeError, match="induced failure"):
        server.run(_requests(cfg, 3))

    snap = server.snapshot()
    assert snap["completed"] is False
    assert snap["observability"]["aborted"] is True
    assert snap["observability"]["metrics"]["counters"]["evicted"] > 0
    # close_open settled the spans the abort left dangling
    assert all(not s for s in server.observer.trace._open.values())

    del server._burst_round  # restore the class method
    out = server.run(_requests(cfg, 3))
    assert out == ref  # no stale slots served into the second run
    snap = server.snapshot()
    assert snap["completed"] is True
    assert snap["observability"]["aborted"] is False
    counters = snap["observability"]["metrics"]["counters"]
    assert counters["requests"] == 3  # no residue from the aborted run
    assert "evicted" not in counters


def test_second_run_snapshot_has_no_residue(olmo):
    cfg, model, params = olmo
    server = BatchedServer(model, EXACT, params, slots=2, max_len=32, burst=4)
    server.observer = ServingObserver()
    server.run(_requests(cfg, 2))
    first = server.snapshot()
    server.run(_requests(cfg, 3))
    second = server.snapshot()
    assert first["observability"]["metrics"]["counters"]["requests"] == 2
    assert second["observability"]["metrics"]["counters"]["requests"] == 3
    assert second["host_transfers"] <= first["host_transfers"] + 3  # reset, not accumulated


# ---------------------------------------------------------------------------
# unified telemetry export shape
# ---------------------------------------------------------------------------


def test_telemetry_records_share_one_shape(olmo):
    from repro.runtime import ControllerConfig, ModeController
    from repro.spec import SpecConfig

    cfg, model, params = olmo
    bank, ctx = _bank_and_ctx(model, params)
    server = BatchedServer(
        model, ctx, params, slots=2, max_len=40, bank=bank,
        controller=ModeController(bank, ControllerConfig(cycle_budget=0.8)),
        speculate=SpecConfig(draft_len=3),
    )
    server.run(_requests(cfg, 3))
    recs = server.snapshot()["telemetry"]
    assert sorted(r["kind"] for r in recs) == ["adaptive", "speculative"]
    common = {"kind", "reference", "tokens", "est_cycles", "baseline_cycles",
              "est_cycle_savings_frac", "detail"}
    for rec in recs:
        assert common <= set(rec)
        assert rec["reference"] == bank.reference
        assert isinstance(rec["detail"], dict)


# ---------------------------------------------------------------------------
# teacher_forced_agreement edge cases
# ---------------------------------------------------------------------------


def _tfa_fixture(olmo, gens):
    cfg, model, params = olmo
    reqs = [Request(i, np.array([1 + i, 2, 3], np.int32), 6)
            for i in range(len(gens))]
    results = {i: list(g) for i, g in enumerate(gens)}
    margins = {i: [2.0] * len(g) for i, g in enumerate(gens)}
    return cfg, model, params, reqs, results, margins


def test_tfa_skips_empty_generation(olmo):
    cfg, model, params, reqs, results, margins = _tfa_fixture(
        olmo, [[5, 7, 5], []])
    overall, high, thr, n_high = teacher_forced_agreement(
        model, EXACT, params, reqs, results, margins)
    assert 0.0 <= overall <= 1.0
    assert n_high == 3  # only the non-empty request's tokens are scored


def test_tfa_single_token_request(olmo):
    cfg, model, params, reqs, results, margins = _tfa_fixture(olmo, [[9]])
    overall, high, thr, n_high = teacher_forced_agreement(
        model, EXACT, params, reqs, results, margins)
    assert n_high == 1 and high == overall


def test_tfa_all_empty_raises(olmo):
    cfg, model, params, reqs, results, margins = _tfa_fixture(olmo, [[], []])
    with pytest.raises(ValueError, match="no generated tokens"):
        teacher_forced_agreement(model, EXACT, params, reqs, results, margins)


def test_tfa_misaligned_margins_raise(olmo):
    cfg, model, params, reqs, results, margins = _tfa_fixture(olmo, [[5, 7]])
    margins[0] = [2.0]  # one margin for two tokens
    with pytest.raises(ValueError, match="align"):
        teacher_forced_agreement(model, EXACT, params, reqs, results, margins)


def test_tfa_all_below_threshold_falls_back(olmo):
    """Non-finite margins are the only way NO token clears the median (a
    finite median keeps at least one at/above it): high-confidence agreement
    falls back to overall with n_high == 0 instead of a NaN mean."""
    cfg, model, params, reqs, results, margins = _tfa_fixture(olmo, [[5, 7, 5]])
    margins[0] = [float("nan")] * 3
    overall, high, thr, n_high = teacher_forced_agreement(
        model, EXACT, params, reqs, results, margins)
    assert n_high == 0
    assert high == overall
