"""Distribution layer: sharding rules, constraint helper, HLO analyzer,
and small-mesh lowering of the real train/decode steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, reduced
from repro.launch.hlo_analysis import analyze
from repro.launch.mesh import make_mesh
from repro.models import get_model
from repro.models.params import ParamSpec
from repro.sharding import partition


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) != 1:
        pytest.skip("host-device test")
    return make_mesh((1, 1), ("data", "model"))


def _pspec_entries(ps):
    """Normalize PartitionSpec entries for version-robust comparison — jax
    releases disagree on whether ``P(("data",), m)`` equals ``P("data", m)``."""
    return tuple(
        None if e is None else (e,) if isinstance(e, str) else tuple(e)
        for e in ps
    )


def test_param_pspec_rules(mesh):
    spec = ParamSpec((64, 16, 128), ("embed", "heads", "head_dim"))
    ps = partition.param_pspec(spec, mesh)
    # head_dim replicated -> trailing None trimmed
    assert _pspec_entries(ps) == (("data",), ("model",))


def test_param_pspec_divisibility_fallback():
    mesh = make_mesh((1, 1), ("data", "model"))
    # dims of size 1 divide anything; force non-divisible with a fake extent via
    # a 3-wide dim against model axis of 1 -> still divides. Use axis not in rules:
    spec = ParamSpec((7,), ("conv",))
    assert partition.param_pspec(spec, mesh) == P()


def test_no_duplicate_mesh_axes(mesh):
    spec = ParamSpec((64, 64), ("mlp", "experts"))  # both want "model"
    ps = partition.param_pspec(spec, mesh)
    used = [e for e in ps if e is not None]
    assert len(used) <= 1  # second claim on "model" must be dropped


def test_constrain_noop_without_mesh():
    x = jnp.ones((4, 4))
    out = partition.constrain(x, "batch", None)
    assert out.shape == x.shape


def test_constrain_inside_mesh(mesh):
    with jax.set_mesh(mesh):
        f = jax.jit(lambda x: partition.constrain(x * 2, "batch", None))
        np.testing.assert_allclose(np.asarray(f(jnp.ones((4, 4)))), 2.0)


def test_hlo_analyzer_scan_correction():
    """The analyzer must multiply while-body costs by the trip count."""

    def body(h, w):
        return jnp.tanh(h @ w), None

    def scanned(h, ws):
        return jax.lax.scan(body, h, ws)[0]

    h = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    costs = analyze(jax.jit(scanned).lower(h, ws).compile().as_text())
    assert costs.dot_flops == 5 * 2 * 32 * 64 * 64
    assert 5 in costs.while_trips


def test_hlo_analyzer_grad_counts_backward():
    def loss(w, x):
        return jnp.sum(jnp.tanh(x @ w) ** 2)

    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    fwd = analyze(jax.jit(loss).lower(w, x).compile().as_text()).dot_flops
    bwd = analyze(jax.jit(jax.grad(loss)).lower(w, x).compile().as_text()).dot_flops
    assert bwd >= 2 * fwd  # dL/dw and dL/dx dots


@pytest.mark.parametrize("arch", ["olmo-1b", "deepseek-v3-671b", "mamba2-780m"])
def test_reduced_train_step_lowers_with_shardings(arch, mesh):
    """The full train step (sharded params/opt) lowers+compiles on a 1x1 mesh."""
    from repro.core import EngineContext
    from repro.train import optimizer as opt
    from repro.train.train_loop import TrainConfig, make_train_step

    cfg = reduced(get_config(arch))
    model = get_model(cfg)
    with jax.set_mesh(mesh):
        specs = model.specs()
        param_sh, _ = partition.param_shardings(specs, mesh)
        aparams = model.abstract_params(jnp.float32)
        aopt = opt.abstract_state(aparams)
        step = make_train_step(model, EngineContext(mode="exact", compute_dtype=jnp.float32),
                               TrainConfig(remat=True))
        batch = {
            "tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32),
            "targets": jax.ShapeDtypeStruct((2, 32), jnp.int32),
        }
        compiled = jax.jit(step).lower(aparams, aopt, batch).compile()
        assert compiled.cost_analysis() is not None


def test_cache_shardings_skip_unsplittable_batch(mesh):
    cfg = reduced(get_config("mamba2-780m"))
    model = get_model(cfg)
    cache = model.make_cache(1, 16, jnp.float32, abstract=True)
    sh = partition.cache_shardings(cache, mesh, cfg)
    for leaf in jax.tree.leaves(sh, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)):
        assert isinstance(leaf, jax.sharding.NamedSharding)
