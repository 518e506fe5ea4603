"""The trace reduction: busy and idle time, per-program and per-kernel device
time, idle gaps by host span."""
import os

import pytest

from chipbench import manifest, trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns

# operation events on a TPU are named by their HLO instruction text
KERNEL = ('%fused_dot_af.47 = f32[16,2048]{1,0:T(8,128)S(1)} custom-call('
          's32[6]{0:T(128)S(1)} %pad_add_fusion.20, f32[16,2048]{1,0:T(8,128)S(1)} '
          '%convert_bitcast_fusion.37, f32[2048,2048]{1,0:T(8,128)} %w)')
LOOP = '%while.46 = (s32[]{:T(128)}, f32[16,2048]{1,0}) while(%tuple)'



def _synthetic():
    host = [("bench.window", 0, 100 * MS),
            ("bench.step", 0, 60 * MS),
            ("np.asarray(jax.Array)", 40 * MS, 60 * MS),
            ("bench.step", 60 * MS, 100 * MS)]
    ops = [(LOOP, 10 * MS, 40 * MS),  # holds the next two
           ("%fusion.1 = f32[16]{0} fusion(%a)", 10 * MS, 30 * MS),
           (KERNEL, 20 * MS, 40 * MS),  # overlaps fusion.1
           ("%fusion.2 = f32[16]{0} fusion(%b)", 70 * MS, 90 * MS),
           ("%fusion.9 = f32[16]{0} fusion(%c)", 120 * MS, 130 * MS)]  # after
    modules = [("jit_decode_burst(42)", 10 * MS, 40 * MS),
               ("jit_chunk(7)", 70 * MS, 90 * MS)]
    return [{"ops": ops, "modules": modules}], host


def test_busy_union_and_window():
    red = trace.reduce_events(*_synthetic())
    assert red["window_s"] == pytest.approx(0.100)
    # 10-40 ms (two overlapping ops) and 70-90 ms; the op after the window
    # does not count
    assert red["busy_s"] == pytest.approx(0.050)


def test_programs_and_kernels():
    red = trace.reduce_events(*_synthetic())
    assert red["programs"]["decode_burst"] == [1, pytest.approx(0.030)]
    assert red["programs"]["chunk"] == [1, pytest.approx(0.020)]
    assert red["kernels"] == [["fused_dot_af", pytest.approx(0.020),
                               (16, 2048, 2048)]]


def test_idle_gaps_by_host_span():
    red = trace.reduce_events(*_synthetic())
    gaps = dict(red["breakdown"]["idle_gaps"])
    # 0-10 ms inside the first step, 40-70 ms: the host waiting for a
    # transfer (midpoint 55 ms), 90-100 ms in the second step
    assert gaps["bench.step"] == pytest.approx(0.020)
    assert gaps["bench.step/np.asarray(jax.Array)"] == pytest.approx(0.030)
    top = dict(red["breakdown"]["device_ops"])
    # the loop holds other operations: it is not ranked beside them
    assert set(top) == {"%fusion.1", "%fused_dot_af.47", "%fusion.2"}
    assert top["%fused_dot_af.47"] == pytest.approx(0.020)


def test_no_window_or_device_gives_nothing():
    planes, host = _synthetic()
    assert trace.reduce_events(planes, host[1:]) is None
    assert trace.reduce_events([], host) is None


def test_program_and_instruction_names():
    assert trace.program_name("jit_decode_burst(123)") == "decode_burst"
    assert trace.program_name("jit_admit") == "admit"
    assert trace.instruction(KERNEL) == "fused_dot_af"
    assert trace.instruction(LOOP) == "while"
    assert trace.kernel_shape(LOOP) is None


def _recorded():
    """One whole decode step of olmo-1b in kernel mode on a TPU v5e (16
    slots), cut from the profiler's trace with its operations' HLO text."""
    import gzip
    import json

    with gzip.open(os.path.join(HERE, "testdata", "v5e_kernel_step.json.gz"),
                   "rt") as f:
        data = json.load(f)
    planes = [{"ops": [tuple(o) for o in data["ops"]],
               "modules": [tuple(m) for m in data["modules"]]}]
    return planes, [tuple(h) for h in data["host"]]


def test_recorded_step_kernel_calls():
    red = trace.reduce_events(*_recorded())
    assert red["window_s"] == pytest.approx(0.0574, abs=1e-4)
    assert 0.99 * red["window_s"] < red["busy_s"] <= red["window_s"]
    assert set(red["programs"]) == {"decode_burst"}
    # the fused kernel's calls in one step: q, k, v, o and gate, up in each
    # of the 16 layers, and the output head; the K = 8192 down projection
    # runs on the XLA chain and is no kernel call
    calls = {}
    for name, seconds, shape in red["kernels"]:
        assert name == "fused_dot_af"
        calls.setdefault(shape, []).append(seconds)
    assert {s: len(v) for s, v in calls.items()} == {
        (16, 2048, 2048): 4 * 16, (16, 2048, 8192): 2 * 16,
        (16, 2048, 50304): 1}
    # the output head reads its f32 weight from HBM itself: 412 MB in its
    # time, within the chip's 819 GB/s
    (head,) = calls[(16, 2048, 50304)]
    assert 2048 * 50304 * 4 / head < 819e9
    # the layers' calls read a weight staged into on-chip memory: faster
    # than HBM could deliver it, so their own time leaves the read out
    gate = sum(calls[(16, 2048, 8192)]) / 32
    assert 2048 * 8192 * 4 / gate > 819e9


def test_recorded_step_staging_and_time():
    from chipbench import work

    planes, host = _recorded()
    red = trace.reduce_events(planes, host)
    weights = {(2048, 2048), (2048, 8192), (2048, 50304)}
    staged = [(name, sec, dims) for name, sec, dims in red["staged"]
              if any(trace.splits_as(dims, k, n) for k, n in weights)]
    # per layer: q, k, v, o sliced out of the stacked weights (q, k, v then
    # copied to a (2048, 2048) layout), gate and up sliced
    by_dims = {}
    for _, _, dims in staged:
        by_dims[dims] = by_dims.get(dims, 0) + 1
    assert by_dims == {(1, 2048, 16, 128): 48, (2048, 2048): 48,
                       (1, 16, 128, 2048): 16, (2048, 8192): 32}
    # every leaf operation counted once: they add up to the step
    leaf = sum(e - s for text, s, e in planes[0]["ops"]
               if trace.instruction(text) not in trace.CONTAINERS) * 1e-9
    assert leaf == pytest.approx(red["busy_s"], rel=0.01)
    kernel = sum(sec for _, sec, _ in red["kernels"])
    staging = sum(sec for _, sec, _ in staged)
    assert 0.05 < kernel / red["busy_s"] < 0.07
    assert kernel + staging < 0.2 * red["busy_s"]
    # f32 weights read at 4 B and counted at 1 B: at most a quarter of the
    # roofline, and above it only where the time leaves work out
    share = manifest.metric_reader("cordic_fused_roofline")(
        {"trace": red, "peak": work.peaks("TPU v5 lite")})
    assert 5 < share < 25
    without_staging = dict(red, staged=[])
    assert manifest.metric_reader("cordic_fused_roofline")(
        {"trace": without_staging, "peak": work.peaks("TPU v5 lite")}) > 25


@pytest.mark.parametrize("dims,k,n,expect", [
    ((2048, 8192), 2048, 8192, True),
    ((1, 2048, 16, 128), 2048, 2048, True),
    ((1, 16, 128, 2048), 2048, 2048, True),
    ((8192, 2048), 2048, 8192, False),   # the down projection's weight
    ((16, 1, 2048), 2048, 2048, False),  # activations
    ((1, 4), 2048, 2048, False),
])
def test_splits_as(dims, k, n, expect):
    assert trace.splits_as(dims, k, n) is expect


def test_staged_dims_reads_on_chip_slices_and_copies():
    assert trace.staged_dims(
        "%dynamic-slice_bitcast_fusion.10 = f32[2048,8192]{1,0:T(8,128)S(1)}"
        " fusion(f32[16,2048,8192]{2,1,0:T(8,128)} %gte)") == (2048, 8192)
    # a slice left in HBM, and an operation that is no slice or copy
    assert trace.staged_dims(
        "%dynamic-slice_bitcast_fusion.8 = f32[16,1026,16,128]"
        "{3,2,1,0:T(8,128)} fusion(%a)") is None
    assert trace.staged_dims(
        "%fusion.136 = f32[16,16,128]{2,1,0:T(8,128)S(1)} fusion(%a)") is None
