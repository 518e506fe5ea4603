"""Fault-tolerant serving: deadlines, admission control, fault isolation.

The resilience layer must never change what a healthy server computes:

* with ``resilience=None`` the engine keeps its legacy fail-stop contract
  (oversized prompts raise, faults crash or corrupt loudly) bit-for-bit;
* with a ``ResilienceConfig`` and an injected NaN fault in ONE slot, every
  other slot's greedy stream is bit-identical to a fault-free run — the
  fault flag rides the existing burst carry and the token math is untouched
  (dense and MoE+MLA, adaptive and speculative, mesh=None and 1x1);
* the faulted slot commits exactly its clean prefix (the tokens before the
  first bad logit match the fault-free stream) and is quarantined with a
  structured ``RequestOutcome``;
* admission control sheds work it cannot serve (oversized prompt, full
  queue, expired deadline) instead of crashing, and every shed outcome
  names its reason;
* ``DegradationPolicy`` demotes the batch down the depth ladder under
  pressure before anything is shed, and promotes back with hysteresis.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import EngineContext, FXP16, PrecisionPolicy
from repro.models import get_model
from repro.obs import ServingObserver
from repro.obs.trace import TraceRecorder, read_trace
from repro.resilience import (
    DegradationConfig,
    DegradationPolicy,
    DelayFault,
    FaultInjector,
    NaNCacheFault,
    NaNWeightFault,
    RequestOutcome,
    ResilienceConfig,
    oversized_request,
    shed_overflow,
)
from repro.runtime import (
    ControllerConfig,
    ModeController,
    StepSignals,
    build_bank,
    default_points,
)
from repro.serve.engine import BatchedServer, Request
from repro.spec import SpecConfig

CARMEN = EngineContext(mode="carmen", policy=PrecisionPolicy.accurate(FXP16),
                       compute_dtype=jnp.float32)


def _setup(arch):
    cfg = reduced(get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, *, prompt_len=5, max_new=10, deadline_s=None):
    rng = np.random.default_rng(2)
    return [
        Request(i, rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32),
                max_new, deadline_s=deadline_s)
        for i in range(n)
    ]


@pytest.fixture(scope="module")
def olmo():
    return _setup("olmo-1b")


@pytest.fixture(scope="module")
def olmo_bank(olmo):
    _, model, params = olmo
    return build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())


# ---------------------------------------------------------------------------
# fault isolation: the acceptance-criterion matrix
# ---------------------------------------------------------------------------


def _isolation_case(arch, *, spec=False, mesh_shape=None, bank=None,
                    controller_factory=None):
    """Run fault-free vs one-slot-NaN and assert the isolation contract."""
    cfg, model, params = _setup(arch)
    from repro.launch.mesh import make_mesh

    mesh = (make_mesh(mesh_shape, ("data", "model"))
            if mesh_shape is not None else None)
    kw = dict(slots=4, max_len=64, burst=4, mesh=mesh,
              resilience=ResilienceConfig())
    if spec or controller_factory is not None:
        bank = bank or build_bank(params, "carmen",
                                  default_points(FXP16, hifi_fmt=None),
                                  specs=model.specs())
        kw.update(bank=bank)
    if spec:
        kw.update(speculate=SpecConfig(draft_len=3))

    def build(injector=None):
        ctl = (controller_factory(bank)
               if controller_factory is not None else None)
        return BatchedServer(model, CARMEN, params, injector=injector,
                             controller=ctl, **kw)

    ref = build()
    ref_out = ref.run(_requests(cfg, 3))
    assert all(o.status == "ok" for o in ref.outcomes.values())

    srv = build(FaultInjector(NaNCacheFault(rid=1, at_round=1)))
    out = srv.run(_requests(cfg, 3))
    # the injector really fired (otherwise the assertions below are vacuous)
    assert srv.injector.fired and srv.injector.fired[0][0] == 1
    # unaffected slots: bit-identical streams and clean outcomes
    for rid in (0, 2):
        assert out[rid] == ref_out[rid]
        assert srv.outcomes[rid].status == "ok"
    # faulted slot: quarantined, and what WAS committed is the clean prefix
    o1 = srv.outcomes[1]
    assert o1.status == "faulted"
    assert o1.reason in ("decode_nonfinite", "verify_nonfinite")
    assert len(out[1]) < len(ref_out[1])
    assert out[1] == ref_out[1][:len(out[1])]
    assert srv._fault_counts["faulted"] == 1
    return srv


@pytest.mark.parametrize("arch", ["olmo-1b", "deepseek-v3-671b"])
def test_fault_isolation_burst(arch):
    """Dense and MoE+MLA: a NaN-poisoned KV slot faults alone; the other
    slots' greedy streams never see it."""
    _isolation_case(arch)


@pytest.mark.parametrize("arch", ["olmo-1b", "deepseek-v3-671b"])
def test_fault_isolation_speculative(arch):
    """Same contract through the draft/verify round: the verify forward
    detects the poisoned lane, quarantines it with zero committed tokens
    from the round, and the other lanes' commits are untouched."""
    _isolation_case(arch, spec=True)


def test_fault_isolation_on_mesh(olmo):
    """The fault flag is one more slot-state leaf: the sharded decode path
    (mesh=1x1) carries it and isolates identically."""
    _isolation_case("olmo-1b", mesh_shape=(1, 1))


def test_fault_isolation_adaptive(olmo_bank):
    """With a ModeController swapping bank trees mid-run, isolation still
    holds (the flag is orthogonal to the executed point)."""
    _isolation_case(
        "olmo-1b",
        controller_factory=lambda bank: ModeController(
            bank, ControllerConfig(pin=bank.reference)),
        bank=olmo_bank,
    )


def test_spec_draft_fault_degrades_to_accurate(olmo, olmo_bank):
    """NaN draft weights: every lane's round aborts to the accurate
    position-0 distribution — one correct token per round, streams
    bit-identical to a healthy run, no quarantine."""
    cfg, model, params = olmo
    kw = dict(slots=4, max_len=64, speculate=SpecConfig(draft_len=3),
              resilience=ResilienceConfig())
    ref = BatchedServer(model, CARMEN, params, bank=olmo_bank, **kw)
    ref_out = ref.run(_requests(cfg, 3))
    # fresh bank: the injector poisons the draft tree in place
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    srv = BatchedServer(
        model, CARMEN, params, bank=bank,
        injector=FaultInjector(NaNWeightFault(at_round=1, point=bank.names[0])),
        **kw)
    out = srv.run(_requests(cfg, 3))
    assert out == ref_out
    assert all(o.status == "ok" for o in srv.outcomes.values())
    # after the fault every round emits exactly 1 token: acceptance collapses
    tele = srv.spec_telemetry.summary()
    assert tele["rounds"] > ref.spec_telemetry.summary()["rounds"]


def test_prefill_fault_quarantines_before_commit(olmo):
    """A non-finite prefill margin means the first sampled token is garbage:
    the request is quarantined with zero tokens and the slot is reused.

    slots=1 sequences it: request 0 prefills clean, the round-0 injector
    poisons the serving weights (decode fault), then request 1's prefill
    runs on the poisoned tree and is caught before any token commits."""
    cfg, model, params = olmo
    bank = build_bank(params, "carmen", default_points(FXP16, hifi_fmt=None),
                      specs=model.specs())
    srv = BatchedServer(
        model, CARMEN, params, slots=1, max_len=64, burst=4, bank=bank,
        controller=ModeController(bank, ControllerConfig(pin="accurate")),
        resilience=ResilienceConfig(),
        injector=FaultInjector(NaNWeightFault(at_round=0, point="accurate")))
    out = srv.run(_requests(cfg, 2))
    assert srv.outcomes[0].status == "faulted"
    assert srv.outcomes[0].reason == "decode_nonfinite"
    assert srv.outcomes[1].status == "faulted"
    assert srv.outcomes[1].reason == "prefill_nonfinite"
    assert out[1] == []


# ---------------------------------------------------------------------------
# admission control and shedding
# ---------------------------------------------------------------------------


def test_oversized_prompt_shed_not_crash(olmo):
    """Satellite: prompt + max_new > max_len is shed with reason too_long
    when resilience is on; the rest of the batch serves normally."""
    cfg, model, params = olmo
    srv = BatchedServer(model, CARMEN, params, slots=2, max_len=16, burst=4,
                        resilience=ResilienceConfig())
    good = _requests(cfg, 2, max_new=4)
    out = srv.run(good + [oversized_request(9, 16)])
    assert srv.outcomes[9].status == "shed"
    assert srv.outcomes[9].reason == "too_long"
    assert 9 not in out
    assert all(len(out[r.rid]) == 4 for r in good)


def test_legacy_contract_still_raises(olmo):
    """resilience=None keeps the fail-stop ValueError byte-for-byte."""
    cfg, model, params = olmo
    srv = BatchedServer(model, CARMEN, params, slots=1, max_len=16, burst=4)
    with pytest.raises(ValueError, match="exceeds max_len"):
        srv.run([oversized_request(0, 16)])


def test_queue_limit_sheds_with_reason(olmo):
    """queue_limit bounds admitted work; every rejected request carries a
    structured shed outcome, and survivors complete."""
    cfg, model, params = olmo
    srv = BatchedServer(model, CARMEN, params, slots=2, max_len=64, burst=4,
                        resilience=ResilienceConfig(queue_limit=3))
    out = srv.run(_requests(cfg, 6, max_new=4))
    shed = {r: o for r, o in srv.outcomes.items() if o.status == "shed"}
    served = {r: o for r, o in srv.outcomes.items() if o.status == "ok"}
    assert len(shed) == 3 and len(served) == 3
    assert all(o.reason == "queue_full" for o in shed.values())
    assert all(len(out[r]) == 4 for r in served)
    assert srv._fault_counts["shed"] == 3


def test_shed_policies():
    """The three shed policies pick different victims from one queue."""
    reqs = [
        Request(0, np.arange(2, dtype=np.int32), 4, deadline_s=None),
        Request(1, np.arange(9, dtype=np.int32), 4, deadline_s=0.5),
        Request(2, np.arange(5, dtype=np.int32), 4, deadline_s=9.0),
        Request(3, np.arange(3, dtype=np.int32), 4, deadline_s=2.0),
    ]
    kept, shed = shed_overflow(list(reqs), 2, "reject_newest")
    assert [r.rid for r in kept] == [0, 1]
    assert [r.rid for r in shed] == [2, 3]
    kept, shed = shed_overflow(list(reqs), 2, "reject_largest")
    assert [r.rid for r in kept] == [0, 3]  # arrival order preserved
    assert {r.rid for r in shed} == {1, 2}
    kept, shed = shed_overflow(list(reqs), 2, "deadline_aware")
    # least slack shed first: 0.5s then 2.0s; no-deadline ranks last (safe)
    assert {r.rid for r in shed} == {1, 3}
    assert [r.rid for r in kept] == [0, 2]


def test_shed_overflow_noop_under_limit():
    reqs = [Request(0, np.arange(3, dtype=np.int32), 2)]
    kept, shed = shed_overflow(list(reqs), 4, "reject_newest")
    assert kept == reqs and shed == []


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------


def test_deadline_expiry_keeps_partial_tokens(olmo):
    """A burst-boundary delay past every deadline expires the active slots;
    their partial streams survive in the results."""
    cfg, model, params = olmo
    srv = BatchedServer(model, CARMEN, params, slots=4, max_len=64, burst=4,
                        resilience=ResilienceConfig(default_deadline_s=0.5),
                        injector=FaultInjector(DelayFault(at_round=1,
                                                          seconds=1.0)))
    out = srv.run(_requests(cfg, 3, max_new=24))
    assert all(o.status == "expired" for o in srv.outcomes.values())
    assert all(o.reason == "deadline" for o in srv.outcomes.values())
    assert all(0 < len(v) < 24 for v in out.values())
    assert srv._fault_counts["deadline_misses"] == 3
    assert all(not o.deadline_met for o in srv.outcomes.values())


def test_queued_requests_expire_without_prefill(olmo):
    """A request whose deadline passes while queued is shed, never
    prefilled — no wasted forward pass on work that cannot win."""
    cfg, model, params = olmo
    srv = BatchedServer(model, CARMEN, params, slots=1, max_len=64, burst=4,
                        resilience=ResilienceConfig(),
                        injector=FaultInjector(DelayFault(at_round=0,
                                                          seconds=0.3)))
    reqs = _requests(cfg, 1, max_new=8)
    reqs.append(Request(7, np.arange(1, 6, dtype=np.int32), 8,
                        deadline_s=0.05))
    srv.run(reqs)
    assert srv.outcomes[7].status == "shed"
    assert srv.outcomes[7].reason == "deadline_expired"
    assert srv.outcomes[0].status == "ok"


def test_per_request_deadline_overrides_default(olmo):
    cfg, model, params = olmo
    srv = BatchedServer(model, CARMEN, params, slots=2, max_len=64, burst=4,
                        resilience=ResilienceConfig(default_deadline_s=0.001))
    reqs = _requests(cfg, 2, max_new=4)
    reqs[0].deadline_s = 60.0  # generous per-request override
    srv.run(reqs)
    assert srv.outcomes[0].status == "ok"
    # rid 1 inherits the impossible default and expires (or finishes within
    # a round if the host is absurdly fast — accept either terminal state)
    assert srv.outcomes[1].status in ("expired", "ok")
    assert srv.outcomes[1].deadline_s == 0.001


def test_run_never_mutates_caller_requests(olmo):
    """Deadline resolution is run-local state, not a write onto the caller's
    Request objects: the SAME request list served by two servers with
    different default deadlines must leave ``req.deadline_s`` untouched and
    give each run its own server's default (the old code stamped the first
    server's default onto the requests, so the second run inherited it)."""
    cfg, model, params = olmo
    reqs = _requests(cfg, 2, max_new=4)  # deadline_s=None on every request
    generous = BatchedServer(
        model, CARMEN, params, slots=2, max_len=64, burst=4,
        resilience=ResilienceConfig(default_deadline_s=120.0))
    generous.run(reqs)
    assert all(r.deadline_s is None for r in reqs)
    assert all(o.deadline_s == 120.0 for o in generous.outcomes.values())

    tight = BatchedServer(
        model, CARMEN, params, slots=2, max_len=64, burst=4,
        resilience=ResilienceConfig(default_deadline_s=0.002))
    tight.run(reqs)
    assert all(r.deadline_s is None for r in reqs)
    # the second run resolved ITS default, not the first server's 120 s
    assert all(o.deadline_s == 0.002 for o in tight.outcomes.values())


# ---------------------------------------------------------------------------
# outcomes and aborted-run attribution
# ---------------------------------------------------------------------------


def test_outcomes_recorded_without_resilience(olmo):
    """RequestOutcome bookkeeping is unconditional — a legacy run still
    reports structured per-request outcomes in the snapshot."""
    cfg, model, params = olmo
    srv = BatchedServer(model, CARMEN, params, slots=2, max_len=64, burst=4)
    srv.run(_requests(cfg, 2, max_new=4))
    snap = srv.snapshot()
    oc = snap["resilience"]["outcomes"]
    assert set(oc) == {0, 1}
    assert all(v["status"] == "ok" and v["deadline_met"] for v in oc.values())
    assert snap["resilience"]["counters"]["faulted"] == 0


def test_aborted_run_snapshot_attribution(olmo):
    """Satellite: snapshot() after an aborted run reports every in-flight
    request's outcome (status aborted, tokens so far) plus fault counters."""
    cfg, model, params = olmo

    class Boom(RuntimeError):
        pass

    class _Bomb:
        fired = ()

        def before_round(self, server, round_idx, slot_of):
            if round_idx == 1:
                raise Boom()

    srv = BatchedServer(model, CARMEN, params, slots=2, max_len=64, burst=4,
                        resilience=ResilienceConfig(), injector=_Bomb())
    with pytest.raises(Boom):
        srv.run(_requests(cfg, 3, max_new=24))
    snap = srv.snapshot()
    oc = snap["resilience"]["outcomes"]
    assert set(oc) == {0, 1, 2}
    assert all(v["status"] == "aborted" for v in oc.values())
    # the two admitted slots had committed their prefill + first burst
    assert sorted(v["tokens"] for v in oc.values()) == [0, 5, 5]


def test_outcome_to_dict_roundtrip():
    o = RequestOutcome(rid=3, status="expired", reason="deadline", tokens=4,
                       deadline_s=0.5, wall_s=0.7)
    d = o.to_dict()
    assert d["rid"] == 3 and d["deadline_met"] is False
    ok = RequestOutcome(rid=1, status="ok", tokens=8, wall_s=0.1)
    assert ok.deadline_met  # no deadline == met
    with pytest.raises(ValueError):
        RequestOutcome(rid=0, status="nope")


def test_resilience_config_validation():
    with pytest.raises(ValueError):
        ResilienceConfig(shed_policy="coin_flip")
    with pytest.raises(ValueError):
        ResilienceConfig(queue_limit=0)
    with pytest.raises(ValueError):
        ResilienceConfig(default_deadline_s=-1.0)


# ---------------------------------------------------------------------------
# graceful degradation
# ---------------------------------------------------------------------------


def _mk_controller(bank, **cfg):
    inner = ModeController(bank, ControllerConfig(pin=bank.reference))
    return DegradationPolicy(inner, DegradationConfig(**cfg))


def test_degradation_demotes_under_pressure(olmo_bank):
    pol = _mk_controller(olmo_bank, promote_hysteresis=3)
    assert pol.point == olmo_bank.reference
    pol.observe(StepSignals(active=2, steps=4, queue_depth=3,
                            free_slots=0, deadline_misses=1))
    assert pol._cap < pol._top_idx  # demoted one rung
    assert pol.demotions == 1 and pol.switches == 1
    before = pol._cap
    # calm rounds: promotion waits for the hysteresis streak
    for _ in range(3):
        assert pol._cap == before
        pol.observe(StepSignals(active=2, steps=4, queue_depth=0,
                                free_slots=2))
    assert pol._cap == before + 1 and pol.promotions == 1


def test_degradation_floor_bounds_demotion(olmo_bank):
    floor = olmo_bank.names[1]
    pol = _mk_controller(olmo_bank, floor=floor, demote_hysteresis=1)
    for _ in range(10):
        pol.observe(StepSignals(active=2, steps=4, queue_depth=5,
                                free_slots=0, shed=1))
    assert pol.point == floor  # never below the configured floor


def test_degradation_effective_point_caps_inner(olmo_bank):
    """The effective point is min(inner, cap): a pinned-accurate inner
    controller still runs cheap under pressure."""
    pol = _mk_controller(olmo_bank, demote_hysteresis=1)
    pol.observe(StepSignals(active=2, steps=4, queue_depth=9,
                            free_slots=0, deadline_misses=2))
    assert olmo_bank.index(pol.point) < olmo_bank.index(pol.inner.point)
    assert pol.cap == pol.point  # pinned inner: the cap IS the effective point


def test_degradation_reset(olmo_bank):
    pol = _mk_controller(olmo_bank, demote_hysteresis=1)
    pol.observe(StepSignals(active=2, steps=4, queue_depth=9,
                            free_slots=0, shed=2))
    assert pol._cap < pol._top_idx
    pol.reset()
    assert pol._cap == pol._top_idx and pol.point == olmo_bank.reference


def test_degradation_improves_deadline_met_fraction(olmo, olmo_bank):
    """The headline property: under deadline pressure the degrading server
    meets at least as many deadlines as the pinned-accurate one (strict
    improvement is asserted by the robustness benchmark, which calibrates
    the deadline; here we assert monotonicity with a fixed one)."""
    cfg, model, params = olmo

    def run(controller):
        srv = BatchedServer(model, CARMEN, params, slots=2, max_len=64,
                            burst=4, bank=olmo_bank, controller=controller,
                            resilience=ResilienceConfig(
                                default_deadline_s=2.0))
        srv.run(_requests(cfg, 6, max_new=12))
        return sum(o.deadline_met for o in srv.outcomes.values())

    pinned = ModeController(olmo_bank, ControllerConfig(pin=olmo_bank.reference))
    met_pinned = run(pinned)
    met_degrade = run(_mk_controller(olmo_bank, demote_hysteresis=1))
    assert met_degrade >= met_pinned


# ---------------------------------------------------------------------------
# trace recorder context manager (satellite)
# ---------------------------------------------------------------------------


def test_trace_recorder_flushes_on_exception(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with pytest.raises(RuntimeError):
        with TraceRecorder(sink=path) as tr:
            tr.begin("burst")
            raise RuntimeError("mid-span crash")
    header, events = read_trace(path)
    assert header["meta"]["aborted"] is True
    # the open span was settled: B and E both present, well-formed
    assert [e["ph"] for e in events] == ["B", "E"]


def test_trace_recorder_clean_exit_flushes(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with TraceRecorder(sink=path) as tr:
        tr.instant("tick")
    header, events = read_trace(path)
    assert "aborted" not in header["meta"]
    assert len(events) == 1


def test_server_trace_survives_aborted_run(olmo, tmp_path):
    """End to end: a crash mid-run still leaves a replayable trace on disk
    when the observer has a sink."""
    cfg, model, params = olmo
    path = str(tmp_path / "aborted.jsonl")

    class _Bomb:
        fired = ()

        def before_round(self, server, round_idx, slot_of):
            if round_idx == 1:
                raise RuntimeError("boom")

    obs = ServingObserver(trace_sink=path)
    srv = BatchedServer(model, CARMEN, params, slots=2, max_len=64, burst=4,
                        observer=obs, resilience=ResilienceConfig(),
                        injector=_Bomb())
    with pytest.raises(RuntimeError):
        srv.run(_requests(cfg, 2, max_new=24))
    header, events = read_trace(path)
    assert header["meta"]["aborted"] is True
    assert any(e["name"] == "burst" for e in events)
