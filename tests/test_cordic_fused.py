"""The fused CORDIC dot+AF kernel: bit-parity and zero-recompile guarantees.

Three layers of contract, each gated on exact equality:

* kernel vs pure-XLA reference — the fused Pallas pass (interpret mode here,
  native on TPU) and ``fused_dot_af_ref`` run the identical int32-dot +
  activation-epilogue chain, so they must agree bitwise at every (depth,
  format, AF mode) combination;
* one compiled program serves every execution point — depth/format ride a
  traced params vector (scalar-prefetch operand on TPU), so swapping points
  must not add jit cache entries, while still changing the arithmetic;
* serving through the fused path == serving through the XLA fallback — the
  kernel backend's greedy decode streams are bit-identical with
  ``fused="on"`` and ``fused="off"`` for dense / MoE / MLA, including the
  adaptive controller and the self-speculative decoder.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import EngineContext, PrecisionPolicy
from repro.core.backends import prepare_params
from repro.core.backends.base import PreparedWeight
from repro.core.fxp import FXP8, FXP16, FXP16_UNIT
from repro.core import cordic
from repro.kernels.cordic_fused import (
    FUSED_AFS,
    fused_dot_af,
    fused_dot_af_ref,
    make_point,
)
from repro.models import get_model
from repro.serve.engine import BatchedServer, Request


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32) * 0.2)
    return x, w


# ---------------------------------------------------------------------------
# kernel vs XLA reference: bitwise across depths x formats x AF modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", [FXP8, FXP16], ids=["fxp8", "fxp16"])
@pytest.mark.parametrize("depth", [4, 6, None], ids=["d4", "d6", "full"])
def test_fused_kernel_matches_ref_bitwise(operands, fmt, depth):
    x, w = operands
    depth = depth if depth is not None else fmt.frac + 1
    sd = cordic.signed_digit_round(w, depth, fmt)
    point = make_point(depth, fmt, fmt)
    for af in FUSED_AFS:
        for compute_round in (False, True):
            got = fused_dot_af(x, sd, point, af_mode=af, af_depth=8,
                               af_fmt=FXP8, compute_round=compute_round)
            want = fused_dot_af_ref(x, sd, point, af_mode=af, af_depth=8,
                                    af_fmt=FXP8, compute_round=compute_round)
            assert jnp.array_equal(got, want), (af, compute_round)


def test_fused_identity_matches_cordic_mac(operands):
    """Mode 0 (plain dot) reproduces the standalone MAC kernel bitwise —
    the fused kernel is a strict superset of the unfused prepared dot."""
    from repro.kernels.cordic_mac import ops as mac_ops

    x, w = operands
    for fmt in (FXP8, FXP16):
        for depth in (4, fmt.frac + 1):
            sd = cordic.signed_digit_round(w, depth, fmt)
            got = fused_dot_af(x, sd, make_point(depth, fmt, fmt),
                               af_mode="identity")
            want = mac_ops.cordic_mac(x, sd, depth=depth, x_fmt=fmt, w_fmt=fmt,
                                      w_prequantized=True)
            assert jnp.array_equal(got, want), (fmt, depth)


# ---------------------------------------------------------------------------
# depth/format as data: one compiled program serves every execution point
# ---------------------------------------------------------------------------


def test_point_swap_adds_no_compile(operands):
    """Two execution points (different depth AND format) through the same
    call signature: exactly one new jit entry, two different results."""
    x, w = operands
    sd8 = cordic.signed_digit_round(w, 4, FXP8)
    base = fused_dot_af._cache_size()
    a = fused_dot_af(x, sd8, make_point(4, FXP8, FXP8), af_mode="gelu")
    after_first = fused_dot_af._cache_size()
    assert after_first == base + 1
    b = fused_dot_af(x, sd8, make_point(13, FXP16, FXP16), af_mode="gelu")
    assert fused_dot_af._cache_size() == after_first  # same program
    assert not jnp.array_equal(a, b)  # the params vector is live arithmetic


def test_prepared_kernel_trees_share_treedef():
    """prepare_params at two kernel-mode policies yields treedef-identical
    trees (empty meta + traced point), so jitted serving programs are reused
    across a ModeController switch."""
    rng = np.random.default_rng(1)
    # key must be a recognized engine-weight name or prepare_params skips it
    params = {"up": jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))}
    approx = prepare_params(params, PrecisionPolicy.approximate(FXP8), "kernel")
    hifi = prepare_params(params, PrecisionPolicy.accurate(FXP16), "kernel")
    assert isinstance(approx["up"], PreparedWeight)
    assert approx["up"].point is not None
    assert jax.tree.structure(approx) == jax.tree.structure(hifi)

    ctx = EngineContext(mode="kernel", compute_dtype=jnp.float32, fused="on")
    f = jax.jit(lambda tree, x: ctx.linear_af(x, tree["up"], af="relu"))
    x = jnp.asarray(rng.normal(size=(2, 16)).astype(np.float32))
    f(approx, x)
    f(hifi, x)
    assert f._cache_size() == 1  # one program, both points
    # The params vector is live arithmetic: the raw dot (no AF re-quantization
    # collapsing values onto the FXP8 activation grid) differs between points.
    da = ctx.dot(x, approx["up"], name="up")
    db = ctx.dot(x, hifi["up"], name="up")
    assert not jnp.array_equal(da, db)


def test_prepared_weight_point_survives_scan_slicing():
    """Stacked layer banks are scan xs: each slice must carry its own params
    vector (broadcast at prepare time), not a scalar shred of one."""
    rng = np.random.default_rng(2)
    stacked = jnp.asarray(rng.normal(size=(3, 8, 8)).astype(np.float32))
    from repro.core.backends import get_backend

    pw = get_backend("kernel").prepare(
        stacked, PrecisionPolicy.accurate(FXP8).for_layer("up"), stacked_axes=1
    )
    assert pw.point.shape == (3, 5)

    ctx = EngineContext(mode="kernel", compute_dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 8)).astype(np.float32))

    def layer(h, w):
        return ctx.dot(h.astype(jnp.float32), w, name="w"), None

    h, _ = jax.lax.scan(layer, x, pw)
    ref = x
    for i in range(3):
        sliced = PreparedWeight(pw.data[i], None, "kernel", (), pw.point[i])
        ref = ctx.dot(ref.astype(jnp.float32), sliced, name="w")
    assert jnp.array_equal(h, ref)


# ---------------------------------------------------------------------------
# backend dispatch: fused == fallback == unfused linear+AF chain
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("compute_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_linear_af_fused_matches_unfused_chain(operands, compute_dtype):
    x, w = operands
    lp = PrecisionPolicy.accurate(FXP8)
    tree = prepare_params({"up": w}, lp, "kernel")
    assert isinstance(tree["up"], PreparedWeight)
    base = EngineContext(mode="kernel", policy=lp, compute_dtype=compute_dtype)
    xc = x.astype(compute_dtype)
    outs = {}
    for fused in ("on", "off"):
        ctx = dataclasses.replace(base, fused=fused)
        outs[fused] = ctx.linear_af(xc, tree["up"], af="gelu", name="up")
    unfused = base.activate(base.linear(xc, tree["up"], name="up"), "gelu")
    assert jnp.array_equal(outs["on"], outs["off"])
    assert jnp.array_equal(outs["on"], unfused)


def test_prepared_dot_still_matches_per_call_kernel(operands):
    """The new prepared chain (int32 dot from the params vector) stays bit-
    identical to the per-call cordic_mac path at the same (depth, fmt)."""
    from repro.kernels.cordic_mac import ops as mac_ops

    x, w = operands
    lp = PrecisionPolicy.accurate(FXP8)
    tree = prepare_params({"up": w}, lp, "kernel")
    assert isinstance(tree["up"], PreparedWeight)
    ctx = EngineContext(mode="kernel", policy=lp, compute_dtype=jnp.float32)
    prepared = ctx.dot(x, tree["up"], name="up")
    layer = lp.for_layer("up")
    from repro.core.backends.base import unit_fmt

    per_call = mac_ops.cordic_mac(
        x, w, depth=int(layer.depth), x_fmt=layer.fmt,
        w_fmt=unit_fmt(layer.fmt),
    )
    assert jnp.array_equal(prepared, per_call)


# ---------------------------------------------------------------------------
# serving: fused path == XLA fallback, stream for stream
# ---------------------------------------------------------------------------


def _setup(arch):
    cfg = reduced(get_config(arch))
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _requests(cfg, n, *, max_new=4):
    rng = np.random.default_rng(0)
    return [
        Request(i, rng.integers(0, cfg.vocab_size, 3 + i).astype(np.int32),
                max_new)
        for i in range(n)
    ]


def _kernel_ctx(fused):
    return EngineContext(mode="kernel", policy=PrecisionPolicy.accurate(FXP8),
                         compute_dtype=jnp.float32, fused=fused)


@pytest.mark.parametrize("arch", ["olmo-1b", "deepseek-v3-671b"])
def test_serving_fused_bit_identical_to_fallback(arch):
    """Greedy decode through the fused Pallas path (interpret mode) ==
    the prepared XLA chain, for the dense and MoE+MLA families."""
    cfg, model, params = _setup(arch)
    out, margins = {}, {}
    for fused in ("off", "on"):
        reqs = _requests(cfg, 2)
        out[fused] = BatchedServer(model, _kernel_ctx(fused), params, slots=2,
                                   max_len=16, burst=2).run(reqs)
        margins[fused] = [r.margins for r in reqs]
    assert out["on"] == out["off"]
    for a, b in zip(margins["on"], margins["off"]):
        np.testing.assert_array_equal(a, b)


def test_serving_adaptive_fused_parity_and_zero_recompile():
    """An adaptive kernel-mode bank under forced switching: streams match
    between fused and fallback, the controller actually switches, and the
    burst program compiles ONCE across all execution points."""
    from repro.runtime import (
        ControllerConfig, ModeController, build_bank, default_points,
    )

    cfg, model, params = _setup("olmo-1b")
    outs = {}
    for fused in ("off", "on"):
        bank = build_bank(params, "kernel", default_points(FXP8),
                          specs=model.specs())
        for name in bank.names[1:]:
            assert (jax.tree.structure(bank.tree(name))
                    == jax.tree.structure(bank.tree(bank.names[0])))
        ctrl = ModeController(
            bank, ControllerConfig(margin_demote=0.5, hysteresis=1)
        )
        srv = BatchedServer(model, _kernel_ctx(fused), params, slots=2,
                            max_len=24, burst=2, controller=ctrl)
        outs[fused] = srv.run(_requests(cfg, 2, max_new=8))
        tele = srv.telemetry.summary()
        assert tele["switches"] >= 1  # the ladder was actually walked
        assert len([k for k, v in tele["steps_by_point"].items() if v]) >= 2
        for fn in srv._burst_fns.values():
            assert fn._cache_size() == 1  # one program serves every point
    assert outs["on"] == outs["off"]


def test_serving_speculative_fused_parity():
    """Self-speculative serving (draft approx / verify accurate) through the
    fused path matches the fallback stream for stream."""
    from repro.runtime import build_bank, default_points
    from repro.spec import SpecConfig

    cfg, model, params = _setup("olmo-1b")
    outs = {}
    for fused in ("off", "on"):
        bank = build_bank(params, "kernel", default_points(FXP8),
                          specs=model.specs())
        srv = BatchedServer(model, _kernel_ctx(fused), params, slots=2,
                            max_len=24, speculate=SpecConfig(draft_len=2),
                            bank=bank)
        outs[fused] = srv.run(_requests(cfg, 2, max_new=6))
        assert srv.spec_telemetry.summary()["emitted"] > 0
    assert outs["on"] == outs["off"]


# ---------------------------------------------------------------------------
# int8 MXU operands: the digit split is the int32 dot
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bound", [128, 1 << 15], ids=["int8", "int16"])
def test_int_dot_equals_int32_dot(bound):
    """int8 operands directly (narrow) or as three base-2**7 digits (wide)
    reproduce the int32 dot exactly — modulo 2**32 where it wraps —
    including the range extremes."""
    from repro.kernels.cordic_fused.kernel import int_dot

    rng = np.random.default_rng(3)
    x = rng.integers(-bound, bound, size=(8, 256)).astype(np.int32)
    w = rng.integers(-bound, bound, size=(256, 128)).astype(np.int32)
    x[0, :] = -bound
    x[1, :] = bound - 1
    w[:, 0] = bound - 1
    w[:, 1] = -bound
    want = jax.lax.dot_general(jnp.asarray(x), jnp.asarray(w),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)
    narrow = bound == 128
    got = jax.jit(int_dot, static_argnums=2)(jnp.asarray(x), jnp.asarray(w),
                                             narrow)
    assert jnp.array_equal(got, want)
    if narrow:  # the digit path covers the narrow range too
        assert jnp.array_equal(int_dot(jnp.asarray(x), jnp.asarray(w), False),
                               want)


def test_fused_kernel_saturated_fxp16_matches_ref():
    """FXP16 activations at the clip bounds against full-scale weight grids:
    the wide (digit) kernel path equals the int32 reference bitwise."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.choice([-1e3, 1e3], size=(8, 64)).astype(np.float32))
    w = jnp.asarray(rng.choice([-1.99, 1.99], size=(64, 128)).astype(np.float32))
    sd = cordic.signed_digit_round(w, 15, FXP16_UNIT)
    point = make_point(15, FXP16, FXP16_UNIT)
    got = fused_dot_af(x, sd, point, af_mode="identity", interpret=True)
    want = fused_dot_af_ref(x, sd, point, af_mode="identity", af_depth=8,
                            af_fmt=FXP8, compute_round=False)
    assert jnp.array_equal(got, want)


def test_make_point_rejects_formats_beyond_the_digit_split():
    from repro.core.fxp import FxPFormat

    with pytest.raises(ValueError, match="16 bits"):
        make_point(8, FxPFormat(24, 12), FXP16_UNIT)
    with pytest.raises(ValueError, match="fraction bits"):
        make_point(8, FXP16, FxPFormat(16, 15))


# ---------------------------------------------------------------------------
# the epilogue's bf16 round, built from integer ops
# ---------------------------------------------------------------------------


def test_bit_rounding_equals_bf16_convert():
    """``round_to_bf16`` (f32 -> nearest bf16, ties to even) equals the
    plain convert on finite values, ties and overflow to infinity included."""
    from repro.kernels.cordic_fused.kernel import round_to_bf16

    rng = np.random.default_rng(5)
    v = (rng.standard_normal(1 << 16) * 10.0 ** rng.integers(-30, 30, 1 << 16)
         ).astype(np.float32)
    ties = np.array([1 + 2.0 ** -8, 1 + 3 * 2.0 ** -8, -(1 + 2.0 ** -8)],
                    np.float32)  # halfway between two bf16 values
    v = jnp.asarray(np.concatenate([v, ties, [0.0, -0.0, 3.4e38, -3.4e38]]
                                   ).astype(np.float32))
    want = v.astype(jnp.bfloat16)
    assert jnp.array_equal(round_to_bf16(v), want.astype(jnp.float32))
