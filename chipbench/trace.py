"""Reduction of one profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Read with ``jax.profiler.ProfileData`` and nothing else. The device planes
(``/device:TPU:<n>``) hold two lines this module reads: the programs that ran
(``XLA Modules``, one event per execution, named after the jitted function)
and the operations inside them (``XLA Ops``). The host plane holds the
benchmark's own spans (``bench.*``, written with ``TraceAnnotation``), and
``bench.window`` gives the window on the trace's clock.

``reduce_file`` returns plain data:

* ``window_s``: the length of ``bench.window``;
* ``busy_s``: the union of the intervals in which an operation ran, inside
  the window, averaged over the device planes;
* ``programs``: jitted program name -> ``[executions, device seconds]``;
* ``kernels``: custom calls (Pallas kernels), each ``[name, seconds, (m, k,
  n)]``: the HLO instruction's name without its number (``fused_dot_af``)
  and the shape read from the call's own operands;
* ``staged``: operations that write a slice or a copy of a tensor into the
  chip's on-chip memory (memory space ``S(1)``), each ``[instruction,
  seconds, dims]``. A kernel whose weight operand is staged so (the layer
  loop slices each layer's weight out of the stacked weights before the
  call) reads it from there, and its own time leaves that read out: a
  kernel's metric adds the staging of its operands back;
* ``breakdown``: the ten operations that took most time, summed by
  instruction, and the idle gaps summed by what the host was doing in them.

On a TPU an operation's event is named by its HLO instruction text, operand
shapes included; loops (``while``) and calls hold other operations and are
left out of the ranking.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")

_SHAPE = re.compile(r"\b[a-z]+\d*\[(\d+(?:,\d+)*)\]")
_PROGRAM = re.compile(r"^(?:jit_)?([A-Za-z_][A-Za-z0-9_]*)")
_INSTR = re.compile(r"^%?([A-Za-z_][A-Za-z0-9_\-]*?)(?:\.\d+)?(?: =|$)")
# ``%name = f32[2048,8192]{1,0:T(8,128)S(1)} fusion(``: the output's dims,
# in memory space 1
_ON_CHIP = re.compile(r"^%?\S+ = [a-z]+\d*\[(\d+(?:,\d+)*)\]\{[^}]*\bS\(1\)")


def program_name(event_name: str) -> str:
    """``jit_decode_burst(123)`` -> ``decode_burst``."""
    m = _PROGRAM.match(event_name)
    return m.group(1) if m else event_name


def instruction(text: str) -> str:
    """``%fused_dot_af.47 = f32[16,8192]... custom-call(...)`` ->
    ``fused_dot_af``."""
    m = _INSTR.match(text)
    return m.group(1) if m else text.split(" ", 1)[0]


def kernel_shape(text: str) -> Optional[Tuple[int, int, int]]:
    """``(m, k, n)`` of a custom call from its instruction text: the first two
    2-D operands are the activations ``(m, k)`` and the weights ``(k, n)``.
    Operand dtypes are ignored: the work is counted at the format's least
    storage, whatever the program stores."""
    if "custom-call(" not in text:
        return None
    args = text.split("custom-call(", 1)[1]
    dims = [tuple(int(d) for d in s.split(",")) for s in _SHAPE.findall(args)]
    mats = [d for d in dims if len(d) == 2]
    if len(mats) >= 2 and mats[0][1] == mats[1][0]:
        return mats[0][0], mats[0][1], mats[1][1]
    return None


def staged_dims(text: str) -> Optional[Tuple[int, ...]]:
    """The output dims of an operation that slices or copies a tensor into
    on-chip memory, else None."""
    name = instruction(text)
    if "slice" not in name and "copy" not in name:
        return None
    m = _ON_CHIP.match(text)
    return tuple(int(d) for d in m.group(1).split(",")) if m else None


def splits_as(dims: Tuple[int, ...], k: int, n: int) -> bool:
    """Whether a tensor of ``dims`` is a ``(k, n)`` matrix in another layout:
    some leading dims (ones dropped) multiply to ``k``, the rest to ``n``."""
    dims = tuple(d for d in dims if d != 1)
    head = 1
    for i, d in enumerate(dims[:-1]):
        head *= d
        if head == k:
            tail = 1
            for e in dims[i + 1:]:
                tail *= e
            return tail == n
    return False


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _host_label(host_events, t: float) -> str:
    """What the host was doing at ``t``: the outermost ``bench.*`` span and
    the innermost host event under it."""
    outer, inner, inner_len = None, None, None
    for name, s, e in host_events:
        if s <= t < e:
            if name.startswith("bench.") and name != WINDOW_SPAN:
                outer = name if outer is None else outer
            if inner_len is None or e - s < inner_len:
                inner, inner_len = name, e - s
    if outer is None and inner is None:
        return "host:none"
    if inner is None or inner == outer:
        return outer or inner
    return f"{outer or 'host'}/{inner}"


def reduce_events(device_planes, host_events) -> Dict:
    """The reduction over already-extracted events (see module docstring).

    ``device_planes``: one dict per device with ``ops`` and ``modules`` lists
    of ``(name, start_ns, end_ns)``, an operation named by its HLO text;
    ``host_events``: the host thread's ``(name, start_ns, end_ns)``.
    """
    windows = [(s, e) for name, s, e in host_events if name == WINDOW_SPAN]
    if not windows or not device_planes:
        return None
    lo, hi = windows[0]
    busy_total, programs, kernels, staged, op_time = 0.0, {}, [], [], {}
    gaps = {}
    for plane in device_planes:
        spans = []
        for text, s, e in plane["ops"]:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            spans.append((s, e))
            name = instruction(text)
            if name not in CONTAINERS:
                key = text.split(" ", 1)[0]
                op_time[key] = op_time.get(key, 0.0) + (e - s) * 1e-9
            shape = kernel_shape(text)
            if shape is not None:
                kernels.append([name, (e - s) * 1e-9, shape])
            dims = staged_dims(text)
            if dims is not None:
                staged.append([text.split(" ", 1)[0], (e - s) * 1e-9, dims])
        merged = _union(spans)
        busy_total += sum(e - s for s, e in merged)
        prev = lo
        for s, e in merged + [(hi, hi)]:
            if s > prev:
                label = _host_label(host_events, (prev + s) / 2)
                gaps[label] = gaps.get(label, 0.0) + (s - prev) * 1e-9
            prev = max(prev, e)
        for name, s, e in plane["modules"]:
            s, e = _clip(s, e, lo, hi)
            if e <= s:
                continue
            entry = programs.setdefault(program_name(name), [0, 0.0])
            entry[0] += 1
            entry[1] += (e - s) * 1e-9
    n = len(device_planes)
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / n,
        "programs": programs,
        "kernels": kernels,
        "staged": staged,
        "breakdown": {"device_ops": [[k, v / n] for k, v in top],
                      "idle_gaps": [[k, v / n] for k, v in idle]},
    }


def _events(line):
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, e)
            for e in line.events]


def reduce_file(path: str) -> Optional[Dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_planes, host_events = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops = [(n, s, e) for n, s, e, _ in _events(lines[OPS_LINE])]
            modules = ([(n, s, e) for n, s, e, _ in
                        _events(lines[MODULES_LINE])]
                       if MODULES_LINE in lines else [])
            device_planes.append({"ops": ops, "modules": modules})
        elif plane.name.startswith("/host:CPU"):
            # the thread that ran the window: it holds the benchmark's spans
            for line in plane.lines:
                events = [(n, s, e) for n, s, e, _ in _events(line)]
                if any(n == WINDOW_SPAN for n, _, _ in events):
                    host_events = events
    return reduce_events(device_planes, host_events)


def reduce_dir(trace_dir: str) -> Optional[Dict]:
    """Reduce the one ``.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return reduce_file(paths[0]) if paths else None
