"""Work counts and peaks, checked by hand at olmo-1b's shapes."""
import json
import os

import numpy as np
import pytest

from chipbench import trace, work

HERE = os.path.dirname(os.path.abspath(__file__))


def _olmo():
    with open(os.path.join(HERE, "configs", "olmo-1b.fxp8-kernel.json")) as f:
        return json.load(f)


def test_fused_call_work_by_hand():
    # one decode step of the q projection, 16 slots: (16, 2048) x (2048, 2048)
    w = work.fused_call_work(16, 2048, 2048)
    assert w["ops"] == 2 * 16 * 2048 * 2048 == 134_217_728
    # weights at one byte each, bf16 activations in and out
    assert w["bytes"] == 2048 * 2048 + 16 * 2048 * 2 + 16 * 2048 * 2 == 4_325_376
    peak = work.peaks("TPU v5 lite")
    # memory bound: 4.33 MB at 819 GB/s is 5.28 us; the int8 ops take 0.34 us
    assert work.least_seconds(w, peak) == pytest.approx(4_325_376 / 819e9)
    # a 512-row prefill chunk of the gate projection is compute bound
    w = work.fused_call_work(512, 2048, 8192)
    assert work.least_seconds(w, peak) == pytest.approx(2 * 512 * 2048 * 8192 / 393e12)


@pytest.mark.parametrize("dtype", ["float32", "int8", "bfloat16"])
def test_kernel_bytes_do_not_follow_the_stored_dtype(dtype):
    # the same call with its weight operand stored as f32 (today), int8 or
    # bf16 counts the same work: the format's least storage, 1 B a weight
    text = (f"%fused_dot_af.7 = f32[16,8192]{{1,0}} custom-call(s32[6]{{0}} %p, "
            f"f32[16,2048]{{1,0}} %x, {_short(dtype)}[2048,8192]{{1,0}} %w)")
    shape = trace.kernel_shape(text)
    assert shape == (16, 2048, 8192)
    assert work.fused_call_work(*shape)["bytes"] == 2048 * 8192 + 16 * (2048 + 8192) * 2


def _short(dtype):
    return {"float32": "f32", "int8": "s8", "bfloat16": "bf16"}[dtype]


def test_model_flops_by_hand():
    cfg = _olmo()
    # 16 layers x (4 x 2048^2 attention + 3 x 2048 x 8192 MLP) + tied head
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert work.matmul_params(cfg) == 16 * per_layer + 2048 * 50304 == 1_176_764_416
    # a decode token at context 1000 adds 4 x 1000 x 2048 x 16 attention flops
    assert work.token_flops(cfg, 1000) == 2 * 1_176_764_416 + 4 * 1000 * 2048 * 16
    # a prompt of 3 rows: rows see 1, 2 and 3 positions
    assert work.prompt_flops(cfg, [3]) == pytest.approx(
        3 * 2 * 1_176_764_416 + 4 * 2048 * 16 * (1 + 2 + 3))


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("TPU v99 imaginary")
    assert np.isclose(work.peaks("TPU v5 lite")["int8_ops"], 393e12)
