"""chip_smoke.py's phases, run here on a reduced olmo-1b.

The script itself refuses the CPU; these tests call its phase functions with
``reduced=True`` so a refactor of the serving entry points cannot break the
script between chip runs. On the CPU the fused kernel is not selected
(``fused="auto"`` runs the XLA chain), so the burst holds no Pallas kernel
here; the parity and stream checks still run in full.
"""
import importlib.util
import pathlib

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _restore_cache_dir():
    # serve.main points the persistent compile cache at the checkout
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture(scope="module")
def kernel_phase(smoke):
    return smoke.serve_phase("kernel", reduced=True)


def test_kernel_phase_streams(kernel_phase):
    assert kernel_phase["frontend_equals_run"]
    assert kernel_phase["custom_calls"] == 0  # CPU: the XLA chain


def test_parity_phase_matches_kernel_phase(smoke, kernel_phase):
    rec = smoke.parity_phase(kernel_phase["run"], reduced=True)
    assert rec["requests"] == 8


def test_parity_phase_catches_a_changed_stream(smoke, kernel_phase):
    wrong = dict(kernel_phase["run"])
    wrong[3] = [(t + 1) % 256 for t in wrong[3]]
    with pytest.raises(AssertionError, match=r"rids \[3\]"):
        smoke.parity_phase(wrong, reduced=True)


def test_int8_phase(smoke):
    rec = smoke.serve_phase("int8", reduced=True)
    assert rec["frontend_equals_run"]


def test_mesh_phase_on_host_mesh(smoke):
    rec = smoke.mesh_phase(reduced=True)
    assert rec["requests"] == smoke.MESH_REQUESTS
    assert rec["mesh"] == {"data": len(jax.devices()) // rec["mesh"]["model"],
                           "model": rec["mesh"]["model"]}


@pytest.mark.parametrize("argv", [[], ["--four-chips"]], ids=["one", "four"])
def test_script_refuses_the_cpu(smoke, capsys, argv):
    assert smoke.main(argv) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
