"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by its name:

* ``chipbench/configs/<config>.json``: the model's sizes, the serving mode,
  the weight grid, the name of its plain reference (``<reference>.py``
  beside it) and the limit of the comparison that decides ``correct``;
* ``chipbench/traffic/<traffic>.json``: the loop, length distributions,
  rate or concurrency, ``slots`` and ``chunk_tokens``;
* ``chipbench/metrics/<metric>.py``: one per-layer metric, a function
  ``read(record)`` that returns a number, or None where the run gave it
  nothing to read.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def config(name: str) -> Dict:
    return _json("configs", f"{name}.json")


def traffic(name: str) -> Dict:
    return _json("traffic", f"{name}.json")


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: Dict):
    """The configuration's plain reference module."""
    name = cfg["reference"]
    return _module(os.path.join(HERE, "configs", f"{name}.py"),
                   f"chipbench_ref_{name.replace('-', '_')}")


def metric_reader(name: str):
    """The ``read`` function of one per-layer metric."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    safe = re.sub(r"\W", "_", name)
    return _module(path, f"chipbench_metric_{safe}").read


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def _covers(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def end_to_end(bench: Dict, cell: str) -> List[Dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in bench["end_to_end"] if _covers(m, cell)]


def per_layer(bench: Dict, cell: str) -> List[Dict]:
    """The per-layer metrics this cell reports."""
    return [m for m in bench["per_layer"] if _covers(m, cell)]
