"""BENCHMARK.json and every file it names load, and keep the contract's
naming and coverage rules."""
import json
import os
import re

import pytest

from chipbench import manifest, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = manifest.load()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"][1] == "chipbench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_load(cfg):
    assert NAME.match(cfg["name"])
    assert cfg["file"] == f"chipbench/configs/{cfg['name']}.json"
    data = manifest.config(cfg["name"])
    assert data["reduced"] == cfg["reduced"] == []
    assert data["source"] == cfg["source"]
    assert data["check"]["max_logit_gap"] > 0
    assert callable(manifest.reference(data).logits)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells_name_their_files(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = manifest.traffic(cell["traffic"])
    traffic.validate(mix)
    ends = {m["name"] for m in manifest.end_to_end(BENCH, cell["name"])}
    assert "setup_s" in ends and len(ends) >= 2
    layers = manifest.per_layer(BENCH, cell["name"])
    assert layers
    # every per-layer metric moves an end-to-end metric the cell reports
    for m in layers:
        assert m["moves"] in ends, (m["name"], cell["name"])


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert callable(manifest.metric_reader(metric["name"]))
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert metric["layer"] and "\n" not in metric["layer"]


def test_names_are_unique_and_files_exist():
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[key]]
        assert len(names) == len(set(names)), key
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for name in os.listdir(os.path.join(HERE, "metrics")):
        if name.endswith(".py"):
            assert name[:-3] in {m["name"] for m in BENCH["per_layer"]}


def _serving(**change):
    return dict({"mode": "kernel", "format": "fxp8", "policy": "accurate"},
                **change)


@pytest.mark.parametrize("fmt,policy", [("fxp8", "accurate"),
                                        ("fxp16", "accurate"),
                                        ("fxp8", "approximate")])
def test_serving_format_and_policy_by_name(fmt, policy):
    import jax.numpy as jnp
    from repro.core import FXP8, FXP16, PrecisionPolicy

    from chipbench import run

    ctx = run.engine_context(_serving(format=fmt, policy=policy), jnp.bfloat16)
    want = getattr(PrecisionPolicy, policy)({"fxp8": FXP8, "fxp16": FXP16}[fmt])
    assert ctx.policy == want and ctx.mode == "kernel"


@pytest.mark.parametrize("change", [{"format": "fxp4"},
                                    {"policy": "greedy"}])
def test_unknown_serving_names_raise(change):
    import jax.numpy as jnp

    from chipbench import run

    with pytest.raises(ValueError, match="unknown"):
        run.engine_context(_serving(**change), jnp.bfloat16)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_program_config_follows_the_file(cfg):
    from chipbench import run

    data = manifest.config(cfg["name"])
    program = run.program_config(data)
    for field, key in data["program_fields"].items():
        assert getattr(program, field) == data[key], field
    assert program.dtype == data["serving"]["compute_dtype"]
