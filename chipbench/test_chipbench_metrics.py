"""Each per-layer metric's reader on a synthetic run record: by hand, and
nothing (None) where the run gave it nothing to read."""
import json
import os

import numpy as np
import pytest

from chipbench import manifest, traffic, work

HERE = os.path.dirname(os.path.abspath(__file__))
PEAK = work.peaks("TPU v5 lite")


def _cfg():
    with open(os.path.join(HERE, "configs", "olmo-1b.fxp8-kernel.json")) as f:
        return json.load(f)


def _record(trace=None):
    spec = traffic.Spec(np.zeros(10, np.int32), 20)
    # request 0: submitted at 0.5, first token at 1.5 (its prefill), then 8
    # more tokens at 2.0; the window is (1.0, 3.0]
    events = [(1.5, 1), (2.0, 9)]
    return {"cfg": _cfg(), "t0": 1.0, "t_end": 3.0, "window_s": 2.0,
            "bursts": 2, "burst": 8, "slots": 4, "prefill_rows": 500,
            "tracks": [(spec, 0.5, events)], "peak": PEAK,
            "trace": trace}


def _read(name, record):
    return manifest.metric_reader(name)(record)


def test_host_side_readers_by_hand():
    rec = _record()
    # 8 decoded tokens over 2 bursts x 8 steps x 4 slots
    assert _read("frontend.slot_occupancy", rec) == pytest.approx(100 * 8 / 64)
    cfg = _cfg()
    flops = (work.prompt_flops(cfg, [10])
             + sum(work.token_flops(cfg, 10 + j) for j in range(1, 9)))
    assert _read("step.mfu", rec) == pytest.approx(
        100 * flops / 2.0 / PEAK["int8_ops"])


def test_trace_readers_by_hand():
    trace = {"window_s": 2.0, "busy_s": 1.5,
             "programs": {"decode_burst": [10, 0.8], "chunk": [3, 0.3],
                          "admit": [2, 0.1]},
             "kernels": [["fused_dot_af", 20e-6, (16, 2048, 2048)],
                         ["other_kernel", 1.0, (16, 2048, 2048)]]}
    rec = _record(trace)
    assert _read("engine.decode_step_ms", rec) == pytest.approx(1e3 * 0.8 / 80)
    assert _read("engine.prefill_ms_per_ktok", rec) == pytest.approx(800.0)
    assert _read("device.idle_share", rec) == pytest.approx(25.0)
    least = (2048 * 2048 + 16 * 4096 * 2) / PEAK["hbm_bytes_per_s"]
    assert _read("cordic_fused_roofline", rec) == pytest.approx(
        100 * least / 20e-6)
    # the time that staged the call's weight counts with the call; a staged
    # tensor of other dims does not
    trace["staged"] = [["%slice.1", 5e-6, (1, 2048, 16, 128)],
                       ["%copy.2", 7e-6, (16, 1, 2048)]]
    assert _read("cordic_fused_roofline", rec) == pytest.approx(
        100 * least / 25e-6)


@pytest.mark.parametrize("name", ["engine.decode_step_ms",
                                  "engine.prefill_ms_per_ktok",
                                  "device.idle_share",
                                  "cordic_fused_roofline"])
def test_nothing_to_read_gives_none(name):
    assert _read(name, _record()) is None
    empty = {"window_s": 2.0, "busy_s": 0.0, "programs": {}, "kernels": []}
    if name != "device.idle_share":
        assert _read(name, _record(empty)) is None
