"""The harness's own process handling: no result without a chip, and the
child process that fills the compile cache before the measuring one."""
import os
import subprocess
import sys

import pytest

from chipbench import run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "olmo-1b.int8.batch-decode"
BENCH = run.manifest.load()
CELLS = {w["name"]: w for w in BENCH["workloads"]}


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "Nothing run" in out.stderr
    # the child found no chip either, and marked nothing as prepared
    assert not os.path.exists(run.prepared_marker(str(tmp_path),
                                                 CELLS[CELL]))


class _Args:
    workload, seed = CELL, 2**33 + 9


def test_prepare_runs_the_child_once(tmp_path, monkeypatch):
    calls = []

    def fake_run(cmd, stdout, check):
        calls.append(cmd)
        marker = run.prepared_marker(str(tmp_path), CELLS[CELL])
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        open(marker, "w").close()
        return subprocess.CompletedProcess(cmd, 0)

    monkeypatch.setattr(subprocess, "run", fake_run)
    assert run.prepare(_Args, CELLS[CELL], str(tmp_path)) == 0
    assert run.prepare(_Args, CELLS[CELL], str(tmp_path)) == 0
    (cmd,) = calls
    assert cmd[cmd.index("--workload") + 1] == CELL
    assert "--prepare-only" in cmd
    # its output never reaches the result's standard output
    assert cmd[1] == os.path.join(HERE, "run.py")


def test_prepare_passes_the_childs_failure_on(tmp_path, monkeypatch):
    monkeypatch.setattr(subprocess, "run", lambda cmd, stdout, check:
                        subprocess.CompletedProcess(cmd, 2))
    assert run.prepare(_Args, CELLS[CELL], str(tmp_path)) == 2


def test_marker_follows_the_cell_and_the_code(tmp_path, monkeypatch):
    a = run.prepared_marker(str(tmp_path), CELLS[CELL])
    assert a == run.prepared_marker(str(tmp_path), CELLS[CELL])
    other = run.prepared_marker(str(tmp_path),
                                CELLS["olmo-1b.fxp8-kernel.batch-decode"])
    assert os.path.dirname(a) == os.path.dirname(other) and a != other
    assert os.path.dirname(os.path.dirname(a)) == str(tmp_path)
    # a traffic mix of other sizes is another set-up
    mix = run.manifest.traffic("batch-decode")
    monkeypatch.setattr(run.manifest, "traffic",
                        lambda name: dict(mix, slots=8))
    assert run.prepared_marker(str(tmp_path), CELLS[CELL]) != a


@pytest.mark.parametrize("env,want", [("", None), ("/some/dir", "/some/dir")])
def test_compile_cache_dir(monkeypatch, env, want):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    assert run.compile_cache_dir() == (want or run.CACHE_DIR)
