"""The reduction by the program's own spans and scopes (``scopes.py``):
scope classes, the scope map of a compiled module, host span self time,
device time by scope, and idle gaps labelled by program span."""
import gzip
import json
import os

import pytest

from chipbench import scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))

MS = 1_000_000  # ns
BURST = "jit(decode_burst)/burst/while/body/closed_call"
LAYER = f"{BURST}/layers/while/body/closed_call/layer"


@pytest.mark.parametrize("op_name,cls", [
    (f"{LAYER}/dot.kernel/pallas_call", "dot"),
    (f"{LAYER}/dot.kernel.xla_chain/jit(fused_dot_af_ref)/dot_general", "dot"),
    (f"{LAYER}/attention.core/bhqs,bshd->bqhd/dot_general", "attention"),
    (f"{LAYER}/attention.kv_write/dynamic_update_slice", "attention"),
    (f"{LAYER}/add", "layer"),
    (f"{BURST}/layers/while/body/dynamic_update_slice", "layer_io"),
    (f"{BURST}/lm_head/dot.int8/convert_element_type", "dot"),
    (f"{BURST}/lm_head/reduce_sum", "lm_head"),
    (f"{BURST}/sample/top_k", "sample"),
    ("jit(decode_burst)/burst/while", "burst"),
    ("jit(decode_burst)/transpose", "other"),
    ("", "other"),
])
def test_scope_class(op_name, cls):
    assert scopes.scope_class(op_name) == cls


HLO = """HloModule jit_decode_burst, entry_computation_layout={(f32[4])->f32[4]}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="L/layer/add"}
}

%body.2 (arg.1: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %gte.3 = f32[4]{0} get-tuple-element(%arg.1), index=1
  %copy.138 = f32[4]{0} copy(%gte.3)
  %fusion.7 = f32[4]{0} fusion(%copy.138), kind=kLoop, calls=%fused_computation.1, metadata={op_name="L/layer/add"}
  ROOT %tuple.4 = (s32[], f32[4]{0}) tuple(%gte.3, %fusion.7)
}

%cond.5 (arg.2: (s32[], f32[4])) -> pred[] {
  %arg.2 = (s32[], f32[4]{0}) parameter(0)
  ROOT %constant.6 = pred[] constant(false)
}

ENTRY %main.9 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0)
  %while.8 = (s32[], f32[4]{0}) while(%Arg_0.1), condition=%cond.5, body=%body.2, metadata={op_name="jit(decode_burst)/burst/while"}
  ROOT %gte.10 = f32[4]{0} get-tuple-element(%while.8), index=1
}
"""


def test_op_scopes_inherit_the_calling_instruction():
    names = scopes.op_scopes(HLO)
    assert names["fusion.7"] == "L/layer/add"
    assert names["add.1"] == "L/layer/add"
    # a copy the compiler put in the loop body has no op_name of its own:
    # it takes the loop's
    assert names["copy.138"] == "jit(decode_burst)/burst/while"
    assert scopes.scope_class(names["copy.138"]) == "burst"
    # the entry computation has no caller
    assert names["gte.10"] == ""


def _events():
    """A window of 100 ms: one tick (0-90 ms) that prefills, dispatches a
    burst, waits for it and settles; a decode_burst run on the device from
    32 to 70 ms, and a chunk from 10 to 20 ms."""
    host = [("bench.window", 0, 100 * MS),
            ("bench.step", 0, 90 * MS),
            ("frontend.tick", 1 * MS, 89 * MS),
            ("frontend.intake", 1 * MS, 2 * MS),
            ("frontend.prefill", 2 * MS, 30 * MS),
            ("engine.chunk", 3 * MS, 5 * MS),
            ("engine.admit", 5 * MS, 6 * MS),
            ("engine.admit.wait", 6 * MS, 28 * MS),
            ("np.asarray(jax.Array)", 7 * MS, 28 * MS),
            ("engine.burst", 31 * MS, 32 * MS),
            ("engine.burst.wait", 32 * MS, 80 * MS),
            ("np.asarray(jax.Array)", 33 * MS, 80 * MS),
            ("engine.settle", 80 * MS, 85 * MS),
            ("frontend.flush", 85 * MS, 88 * MS)]
    ops = [("%fusion.1 = f32[16]{0} fusion(%a)", 10 * MS, 20 * MS),
           ("%while.3 = (f32[4]) while(%t)", 32 * MS, 70 * MS),
           ("%fusion.7 = f32[4]{0} fusion(%b)", 32 * MS, 50 * MS),
           ("%copy.138 = f32[4]{0} copy(%c)", 50 * MS, 60 * MS),
           ("%fused_dot_af.2 = f32[4]{0} custom-call(%d)", 60 * MS, 66 * MS),
           ("%fusion.9 = f32[4]{0} fusion(%e)", 66 * MS, 70 * MS)]
    modules = [("jit_chunk(1)", 10 * MS, 20 * MS),
               ("jit_decode_burst(2)", 32 * MS, 70 * MS)]
    names = {"fusion.7": f"{LAYER}/attention.core/dot_general",
             "copy.138": "jit(decode_burst)/burst/while",
             "fused_dot_af.2": f"{LAYER}/dot.kernel/pallas_call",
             "fusion.1": f"{LAYER}/dot.kernel/x"}
    return [{"ops": ops, "modules": modules}], host, names


def test_host_spans_count_total_and_self_time():
    red = scopes.reduce_events(*_events())
    spans = red["spans"]
    assert spans["frontend.tick"][:2] == [1, pytest.approx(0.088)]
    # the tick less intake, prefill, burst, wait, settle and flush
    assert spans["frontend.tick"][2] == pytest.approx(0.088 - 0.001 - 0.028
                                                      - 0.001 - 0.048
                                                      - 0.005 - 0.003)
    assert spans["frontend.prefill"][2] == pytest.approx(0.028 - 0.025)
    assert spans["engine.burst.wait"] == [1, pytest.approx(0.048),
                                          pytest.approx(0.048)]
    # the host's own time in the tick: 88 ms less the two waits
    assert red["host_s_per_tick"] == pytest.approx(0.088 - 0.022 - 0.048)


def test_burst_device_time_by_scope():
    red = scopes.reduce_events(*_events())
    sc = red["scopes"]
    # the chunk's operation is outside decode_burst, the loop is a container
    assert sc["attention"] == pytest.approx(0.018)
    assert sc["burst"] == pytest.approx(0.010)
    assert sc["dot"] == pytest.approx(0.006)
    assert sc["other"] == pytest.approx(0.004)  # fusion.9: no scope known
    assert sc["busy_s"] == pytest.approx(0.038) and sc["runs"] == 1
    assert sum(sc[k] for k in scopes.SCOPE_CLASSES) == pytest.approx(0.038)


def test_idle_gaps_by_program_span():
    red = scopes.reduce_events(*_events())
    gaps = dict(red["idle_gaps"])
    # 0-10 ms: 0-1 in the step alone, 1-10 the tick; the midpoint (5 ms)
    # falls in engine.admit
    assert gaps["bench.step/engine.admit/engine.admit"] == pytest.approx(0.010)
    # 20-32 ms, midpoint 26 ms: the host waits on the admit's transfer
    assert gaps["bench.step/engine.admit.wait/np.asarray(jax.Array)"] == \
        pytest.approx(0.012)
    # 70-100 ms, midpoint 85 ms: frontend.flush starts at 85
    assert gaps["bench.step/frontend.flush/frontend.flush"] == \
        pytest.approx(0.030)
    assert red["idle_s"] == pytest.approx(0.052)
    assert red["idle_in_step"] == {"program": pytest.approx(0.052),
                                   "client": 0.0}


def test_gap_without_a_program_span_keeps_its_label():
    planes, host, names = _events()
    # the tick's spans gone: the labels are trace.py's
    host = [h for h in host if not scopes._is_span(h[0])]
    red = scopes.reduce_events(planes, host, names)
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.step/np.asarray(jax.Array)"] == pytest.approx(0.012)
    assert red["idle_in_step"]["client"] == pytest.approx(0.052)
    assert red["spans"] == {} and red["host_s_per_tick"] is None


def test_no_window_gives_nothing():
    planes, host, names = _events()
    assert scopes.reduce_events(planes, host[1:], names) is None


def test_scopes_of_a_compiled_burst():
    """Every class of the program's decode burst (a small olmo-1b in int8
    mode, compiled for the CPU) is found in its compiled text."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.core import FXP8, EngineContext, PrecisionPolicy
    from repro.models import get_model
    from repro.serve.engine import BatchedServer

    model = get_model(reduced(get_config("olmo-1b")))
    ctx = EngineContext(mode="int8", policy=PrecisionPolicy.accurate(FXP8),
                        compute_dtype=jnp.float32)
    server = BatchedServer(model, ctx, model.init(jax.random.PRNGKey(0)),
                           slots=2, max_len=16, burst=2)
    names = scopes.op_scopes(server.compiled_burst_text())
    found = {scopes.scope_class(op) for op in names.values()}
    assert set(scopes.SCOPE_CLASSES) <= found


def _recorded_tick():
    """One tick of olmo-1b in kernel mode on a TPU v5e (16 slots): a chunk,
    its admit and one 8-step decode burst, with the program's spans and the
    compiled burst's op_names; the window is the tick."""
    with gzip.open(os.path.join(HERE, "testdata", "v5e_kernel_tick.json.gz"),
                   "rt") as f:
        data = json.load(f)
    planes = [{"ops": [tuple(o) for o in data["ops"]],
               "modules": [tuple(m) for m in data["modules"]]}]
    host = [("bench.window", *data["tick"])] + [tuple(h) for h in data["host"]]
    return planes, host, data["op_names"]


def test_recorded_tick_by_scope():
    planes, host, names = _recorded_tick()
    sc = scopes.reduce_events(planes, host, names)["scopes"]
    assert sc["runs"] == 1
    # every operation of the burst counted once, and the program busy all
    # but a few hundred microseconds of its 460 ms
    assert sum(sc[k] for k in scopes.SCOPE_CLASSES) == pytest.approx(
        sc["busy_s"])
    burst = trace.reduce_events(planes, host)["programs"]["decode_burst"][1]
    assert 0.999 * burst < sc["busy_s"] <= burst
    share = {k: 100 * sc[k] / sc["busy_s"] for k in scopes.SCOPE_CLASSES}
    # the stacked cache moved in and out of the layer scan, and the burst
    # loop's copy of it, take three quarters of the step
    assert 50 < share["layer_io"] < 56 and 20 < share["burst"] < 25
    assert 11 < share["attention"] < 14 and 7 < share["dot"] < 10
    assert share["other"] < 1


def test_recorded_tick_spans_and_idle():
    red = scopes.reduce_events(*_recorded_tick())
    spans = red["spans"]
    assert {name: c for name, (c, _, _) in spans.items()} == {
        "frontend.tick": 1, "frontend.intake": 1, "frontend.prefill": 1,
        "engine.chunk": 1, "engine.admit": 1, "engine.admit.wait": 1,
        "engine.burst": 1, "engine.burst.wait": 1, "engine.settle": 1,
        "frontend.flush": 1}
    tick, wait = spans["frontend.tick"][1], spans["engine.burst.wait"][1]
    # the host waits on the burst most of the tick; its own time is some
    # 11 ms of the 504
    assert 0.9 < wait / tick < 0.95
    assert 0.005 < red["host_s_per_tick"] < 0.015
    # every idle gap of the tick lies under a program span
    assert red["idle_in_step"]["client"] == 0
    assert red["idle_in_step"]["program"] == pytest.approx(red["idle_s"])
    assert all(label.startswith(("bench.step/frontend.",
                                 "bench.step/engine."))
               for label, _ in red["idle_gaps"])
