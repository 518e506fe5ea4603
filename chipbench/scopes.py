"""Reduction of one profiler trace by the serving program's own spans and
named scopes, beside ``trace.py``'s (which it leaves as it is).

The program opens host spans (``repro.obs.trace.PROGRAM_SPANS``:
``frontend.tick``, ``engine.burst.wait``, ...) as profiler annotations on
the thread that drives the scheduler, and names the parts of its device
programs with ``jax.named_scope`` (``layers``, ``layer``,
``attention.kv_write``, ``attention.core``, ``dot.<backend>``, ``lm_head``,
``sample``), which land in each HLO instruction's ``op_name``.
``reduce_events`` returns:

* ``spans``: program span -> ``[count, seconds, self seconds]`` inside
  ``bench.window`` on the window's thread; self time leaves out the time of
  the span's child program spans;
* ``host_s_per_tick``: per ``frontend.tick``, its time less that of the
  ``*.wait`` spans inside it: the host's own time in a tick, dispatch
  included;
* ``scopes``: device seconds of the ``decode_burst`` operations in the
  window by the class of their innermost known scope (``SCOPE_CLASSES``),
  with ``busy_s``, the union of their intervals, and ``runs``;
* ``idle_gaps``: the window's idle gaps summed by label,
  ``<bench span>/<innermost program span>/<innermost host event>`` where a
  program span covers the gap, else ``trace.py``'s label;
* ``idle_s`` and ``idle_in_step``: idle seconds in the window, and those
  inside ``bench.step`` split into ``program`` (a program span covers the
  gap) and ``client`` (none does).

An operation's scope comes from the compiled ``decode_burst`` text (the
program's ``compiled_burst_text()``): its instruction's ``op_name``, or,
for an instruction the compiler added without one (a copy), that of the
instruction whose computation holds it (the layer loop's ``while`` for a
copy in the loop body).

Run by hand, on the chip, for one traced run of a cell:

    python3 chipbench/scopes.py --workload <cell> --seed <n>

It runs ``run.py``'s traced run (``--trace 1``) and adds this reduction to
the result line's ``breakdown`` under ``program``.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace  # noqa: E402

BURST = "decode_burst"
STEP_SPAN = "bench.step"
SPAN_PREFIXES = ("frontend.", "engine.")
# innermost scope -> class; ``dot.*`` and ``attention.*`` by prefix
SCOPE_CLASSES = ("dot", "attention", "layer", "layer_io", "lm_head", "sample",
                 "burst", "other")

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\b(?:branch_computations|called_computations)=\{([^}]*)\}")


def scope_class(op_name: str) -> str:
    """The class of the innermost known scope in an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part.startswith("dot."):
            return "dot"
        if part.startswith("attention."):
            return "attention"
        if part in ("lm_head", "sample", "layer", "burst"):
            return part
        if part == "layers":
            return "layer_io"
    return "other"


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` in a compiled module's text; an
    instruction without one takes that of the instruction calling its
    computation."""
    own: Dict[str, str] = {}
    parent: Dict[str, str] = {}      # instruction -> computation holding it
    caller: Dict[str, str] = {}      # computation -> instruction calling it
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and "=" not in line.split("(", 1)[0]:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        parent[name] = comp
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
        for single, group in _CALLED.findall(line):
            for callee in ([single] if single else group.split(",")):
                callee = callee.strip().lstrip("%")
                if callee:
                    caller.setdefault(callee, name)
    resolved: Dict[str, str] = {}

    def resolve(name: str, depth: int = 0) -> str:
        if name in own:
            return own[name]
        if name in resolved:
            return resolved[name]
        up = caller.get(parent.get(name, ""))
        out = resolve(up, depth + 1) if up and depth < 64 else ""
        resolved[name] = out
        return out

    return {name: resolve(name) for name in parent}


def _within(s, e, intervals, starts):
    """Whether ``[s, e]`` lies in one of the sorted, disjoint
    ``intervals`` (``starts``: their starts)."""
    i = bisect.bisect_right(starts, s) - 1
    return i >= 0 and e <= intervals[i][1]


def _is_span(name: str) -> bool:
    return name.startswith(SPAN_PREFIXES)


def host_context(host_events, times):
    """For each of the sorted ``times``: the outermost ``bench.*`` span (the
    window aside), the innermost program span and the innermost host event
    open at it, each a name or None."""
    events = sorted(host_events, key=lambda ev: (ev[1], -ev[2]))
    out, active, i = [], [], 0
    for t in times:
        while i < len(events) and events[i][1] <= t:
            name, s, e = events[i]
            heapq.heappush(active, (e, s, name))
            i += 1
        while active and active[0][0] <= t:
            heapq.heappop(active)
        live = [(s, e, name) for e, s, name in active if e > t]
        bench = [x for x in live if x[2].startswith("bench.")
                 and x[2] != trace.WINDOW_SPAN]
        spans = [x for x in live if _is_span(x[2])]
        inner = lambda xs: min(xs, key=lambda x: x[1] - x[0])[2] if xs else None
        out.append((min(bench)[2] if bench else None, inner(spans),
                    inner(live)))
    return out


def gap_label(outer, span, inner) -> str:
    """``<bench span>/<program span>/<host event>`` where a program span
    covers the gap, else ``trace.py``'s label."""
    if span is not None:
        return f"{outer or 'host'}/{span}/{inner}"
    if outer is None and inner is None:
        return "host:none"
    if inner is None or inner == outer:
        return outer or inner
    return f"{outer or 'host'}/{inner}"


def host_spans(host_events, lo, hi) -> Tuple[Dict, Optional[float]]:
    """Program spans in ``[lo, hi]``: ``{name: [count, s, self s]}`` and the
    host seconds per ``frontend.tick`` (less its ``*.wait`` spans)."""
    spans = sorted(((n, s, e) for n, s, e in host_events
                    if _is_span(n) and lo <= s and e <= hi),
                   key=lambda ev: (ev[1], -ev[2]))
    out: Dict[str, List] = {}
    for name, s, e in spans:
        children = [(s2, e2) for n2, s2, e2 in spans
                    if s <= s2 and e2 <= e and (n2, s2, e2) != (name, s, e)]
        # direct children only: drop those inside another child
        direct = [c for c in children
                  if not any(o != c and o[0] <= c[0] and c[1] <= o[1]
                             for o in children)]
        entry = out.setdefault(name, [0, 0.0, 0.0])
        entry[0] += 1
        entry[1] += (e - s) * 1e-9
        entry[2] += (e - s - sum(b - a for a, b in direct)) * 1e-9
    ticks = [(s, e) for n, s, e in spans if n == "frontend.tick"]
    if not ticks:
        return out, None
    starts = [s for s, _ in ticks]
    waits = sum(e - s for n, s, e in spans
                if n.endswith(".wait") and _within(s, e, ticks, starts))
    return out, (sum(e - s for s, e in ticks) - waits) * 1e-9 / len(ticks)


def burst_scopes(device_planes, lo, hi, scopes: Dict[str, str]) -> Dict:
    """Device seconds of the ``decode_burst`` operations in ``[lo, hi]`` by
    scope class, averaged over the device planes."""
    seconds = dict.fromkeys(SCOPE_CLASSES, 0.0)
    busy, runs = 0.0, 0
    for plane in device_planes:
        bursts = [trace._clip(s, e, lo, hi) for n, s, e in plane["modules"]
                  if trace.program_name(n) == BURST]
        bursts = trace._union([(s, e) for s, e in bursts if e > s])
        starts = [s for s, _ in bursts]
        runs += len(bursts)
        intervals = []
        for text, s, e in plane["ops"]:
            s, e = trace._clip(s, e, lo, hi)
            if (e <= s or trace.instruction(text) in trace.CONTAINERS
                    or not _within(s, e, bursts, starts)):
                continue
            key = text.split(" ", 1)[0].lstrip("%")
            seconds[scope_class(scopes.get(key, ""))] += (e - s) * 1e-9
            intervals.append((s, e))
        busy += sum(e - s for s, e in trace._union(intervals)) * 1e-9
    n = max(len(device_planes), 1)
    out = {k: v / n for k, v in seconds.items()}
    out.update(busy_s=busy / n, runs=runs / n)
    return out


def idle_gaps(device_planes, host_events, lo, hi) -> Dict:
    """Idle gaps in the window by host label, and the idle time inside
    ``bench.step`` split by whether a program span covers it."""
    gaps: Dict[str, float] = {}
    idle, in_step = 0.0, {"program": 0.0, "client": 0.0}
    for plane in device_planes:
        spans = [trace._clip(s, e, lo, hi) for _, s, e in plane["ops"]]
        found, prev = [], lo
        for s, e in trace._union([x for x in spans if x[1] > x[0]]) + [(hi, hi)]:
            if s > prev:
                found.append(((prev + s) / 2, (s - prev) * 1e-9))
            prev = max(prev, e)
        context = host_context(host_events, [t for t, _ in found])
        for (t, dt), (outer, span, inner) in zip(found, context):
            label = gap_label(outer, span, inner)
            gaps[label] = gaps.get(label, 0.0) + dt
            idle += dt
            if outer == STEP_SPAN:
                in_step["program" if span else "client"] += dt
    n = max(len(device_planes), 1)
    return {"idle_gaps": sorted(([k, v / n] for k, v in gaps.items()),
                                key=lambda kv: -kv[1]),
            "idle_s": idle / n,
            "idle_in_step": {k: v / n for k, v in in_step.items()}}


def reduce_events(device_planes, host_events, scopes: Dict[str, str]):
    """This module's reduction (see its docstring) over the events
    ``trace.reduce_events`` takes, and the compiled burst's scopes."""
    windows = [(s, e) for n, s, e in host_events if n == trace.WINDOW_SPAN]
    if not windows or not device_planes:
        return None
    lo, hi = windows[0]
    spans, per_tick = host_spans(host_events, lo, hi)
    out = {"spans": spans, "host_s_per_tick": per_tick,
           "scopes": burst_scopes(device_planes, lo, hi, scopes)}
    out.update(idle_gaps(device_planes, host_events, lo, hi))
    return out


def read_file(path: str):
    """``(device_planes, host_events)`` of one ``.xplane.pb``, as
    ``trace.reduce_file`` extracts them."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_planes, host_events = [], []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:TPU:") and trace.OPS_LINE in lines:
            events = lambda line: [(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events]
            device_planes.append({
                "ops": events(lines[trace.OPS_LINE]),
                "modules": (events(lines[trace.MODULES_LINE])
                            if trace.MODULES_LINE in lines else [])})
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if any(n == trace.WINDOW_SPAN for n, _, _ in evs):
                    host_events = evs
    return device_planes, host_events


def main(argv=None) -> int:
    """``run.py``'s traced run of one cell, with this reduction added.

    JAX's compile cache keys a program without its debug information, and
    the scopes are debug information: a program cached by another checkout
    would run with that checkout's ``op_name``s. So the cache key takes
    them in here (in the child that prepares the cache too)."""
    os.environ["JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"] = "true"
    from chipbench import run

    hlo = {}
    set_up, reduce_dir = run.set_up, trace.reduce_dir

    def set_up_keeping_text(jax, *args, **kwargs):
        out = set_up(jax, *args, **kwargs)
        hlo["text"] = out[1].compiled_burst_text()  # from the compile cache
        return out

    def reduce_dir_with_scopes(trace_dir):
        red = reduce_dir(trace_dir)
        path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True)
        if red is not None and path:
            red["breakdown"]["program"] = reduce_events(
                *read_file(path[0]), op_scopes(hlo.get("text", "")))
        return red

    run.set_up, trace.reduce_dir = set_up_keeping_text, reduce_dir_with_scopes
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--seconds" not in argv:
        argv += ["--seconds", str(run.TRACE_SECONDS)]
    rc = run.main(argv + ["--trace", "1"])
    sys.stdout.flush()
    return rc


if __name__ == "__main__":
    sys.exit(main())
