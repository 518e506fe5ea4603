"""Structured serving traces: versioned JSONL + Chrome-trace/Perfetto export,
program spans on the profiler's clock, and the compile counter.

A :class:`TraceRecorder` accumulates timestamped events during one serving
run. Events are recorded host-side at the engine's existing synchronization
points, so tracing never changes a compiled program or adds a device
round-trip; span durations therefore measure what the *host* observed —
dispatch plus any device wait the call already contained. (The draft/verify
spans inside a speculative round are dispatch-only: jax dispatch is async and
the round synchronizes once, at its single host transfer.)

Timestamps come from :func:`profiler_clock`, the clock the JAX profiler
stamps its host events with (``CLOCK_REALTIME``). ``jax.profiler.ProfileData``
reports an event's ``start_ns`` from the profile's ``profile_start_time``
(a stat of its ``Task Environment`` plane), so such an event lands at
``(profile_start_time + start_ns - t0_ns) / 1e9`` in trace time, where
``t0_ns`` is the header's anchor.

Two exports from the same event list:

* **JSONL** (:meth:`TraceRecorder.write_jsonl` / :func:`read_trace`): the
  replayable serving-telemetry format. Line 1 is the header
  (``schema``/``version``, clock anchor, run metadata, optional sharding
  report and collective-bytes snapshot); every following line is one event
  ``{"ts": seconds-since-run-start, "ph": "B"|"E"|"I", "name": ...,
  "track": ..., "args": {...}}``. This is the trace the ROADMAP's
  cycle-accurate PE-array simulator replays — treat field removals as a
  version bump.
* **Chrome trace** (:meth:`TraceRecorder.to_chrome`): the same events as a
  Chrome ``traceEvents`` JSON (load in Perfetto / ``chrome://tracing``).
  Tracks map to tids — one lane per serving slot plus ``engine`` (bursts,
  prefills, spec rounds), ``sched`` (admission), and ``run``.

B/E spans must nest per track; :meth:`end` enforces it at record time so an
exported trace is always well-formed, and :meth:`close_open` settles any
spans left open by an aborted run.

**Program spans** (:func:`span`) mark the layer boundaries of the serving
program itself: every name is listed once in :data:`PROGRAM_SPANS`. Each is
a ``jax.profiler.TraceAnnotation``, so a profiler trace of a serving run
shows them on the host thread by name; without a running profiler a span
costs one annotation object and a push and pop of the thread's span stack.
:class:`CompileCounter` attributes every backend compile to the innermost
span open on the compiling thread.
"""
from __future__ import annotations

import json
import os
import threading
import time
import weakref
from typing import Dict, List, Optional, Tuple

import jax

__all__ = ["PROGRAM_SPANS", "TRACE_SCHEMA", "TRACE_VERSION", "CompileCounter",
           "TraceRecorder", "compile_counter", "iter_trace", "open_spans",
           "profiler_clock", "read_trace", "span"]

TRACE_SCHEMA = "carmen-serve-trace"
TRACE_VERSION = 1

# Every span the serving program opens, with its meaning. Nesting:
# frontend.tick holds frontend.intake, frontend.prefill (engine.chunk,
# engine.admit, engine.admit.wait; engine.prefill and engine.prefill.wait
# for a monolithic prefill), then engine.burst, engine.burst.wait and
# engine.settle (or engine.spec.*, then engine.settle), then frontend.flush.
# ``run()`` opens the engine.* spans alone.
PROGRAM_SPANS: Tuple[Tuple[str, str], ...] = (
    ("frontend.tick", "one ContinuousScheduler.step, intake to flush"),
    ("frontend.intake", "the inbox, cancellations and the resilience sweeps"),
    ("frontend.prefill", "the tick's prefill budget: chunks, admits, prefills"),
    ("frontend.flush", "committed tokens pushed to their stream handles"),
    ("engine.chunk", "dispatch of one chunked-prefill program"),
    ("engine.admit", "dispatch of the admit program that ends a chunked prefill"),
    ("engine.admit.wait", "the admit's first token and margin reaching the host"),
    ("engine.prefill", "dispatch of one whole-prompt prefill program"),
    ("engine.prefill.wait", "the prefill's first token reaching the host"),
    ("engine.burst", "dispatch of one decode burst"),
    ("engine.burst.wait", "the burst's tokens, margins and faults reaching the host"),
    ("engine.spec.draft", "dispatch of a speculative round's draft loop"),
    ("engine.spec.verify", "dispatch of a speculative round's verify step"),
    ("engine.spec.wait", "the round's emitted tokens and flags reaching the host"),
    ("engine.settle", "host commit of a round's tokens, then its retirements"),
)
SPAN_NAMES = frozenset(name for name, _ in PROGRAM_SPANS)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
NO_SPAN = "(no span)"

_local = threading.local()


def profiler_clock() -> float:
    """Seconds on the clock the JAX profiler stamps host events with."""
    return time.time_ns() * 1e-9


def _stack() -> List[str]:
    stack = getattr(_local, "spans", None)
    if stack is None:
        stack = _local.spans = []
    return stack


def open_spans() -> Tuple[str, ...]:
    """The program spans open on this thread, outermost first."""
    return tuple(_stack())


class span:
    """``with span("engine.burst"): ...`` — one program span (a name of
    :data:`PROGRAM_SPANS`; ``args`` ride on the profiler event, e.g.
    ``rid=``). The thread's span stack is popped however the body exits."""

    __slots__ = ("name", "_annotation")

    def __init__(self, name: str, **args) -> None:
        if name not in SPAN_NAMES:
            raise ValueError(f"{name!r} is not a program span; add it to "
                             "PROGRAM_SPANS")
        self.name = name
        self._annotation = jax.profiler.TraceAnnotation(name, **args)

    def __enter__(self) -> "span":
        _stack().append(self.name)
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        try:
            self._annotation.__exit__(*exc)
        finally:
            _stack().pop()


class CompileCounter:
    """Backend compiles in this process, read from JAX's monitoring events.

    Each compile is attributed to the innermost program span open on the
    thread that compiled it (:data:`NO_SPAN` outside any). ``by_span`` maps
    span -> ``[compiles, seconds, cache_loads]``: a load from the persistent
    compile cache still fires the compile event (short), and is counted as a
    compile and, besides, as a load. Listeners (bound methods, held weakly)
    are called as ``listener(span, seconds, cached)`` per compile.
    """

    def __init__(self) -> None:
        self.by_span: Dict[str, List] = {}
        self._listeners: List[weakref.WeakMethod] = []
        self._hit = threading.local()

    @property
    def count(self) -> int:
        return sum(c for c, _, _ in self.by_span.values())

    @property
    def seconds(self) -> float:
        return sum(s for _, s, _ in self.by_span.values())

    def listen(self, method) -> None:
        self.unlisten(method)
        self._listeners.append(weakref.WeakMethod(method))

    def unlisten(self, method) -> None:
        self._listeners = [m for m in self._listeners
                           if m() is not None and m() != method]

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit.flag = True

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event != COMPILE_EVENT:
            return
        cached = getattr(self._hit, "flag", False)
        self._hit.flag = False
        stack = _stack()
        where = stack[-1] if stack else NO_SPAN
        entry = self.by_span.setdefault(where, [0, 0.0, 0])
        entry[0] += 1
        entry[1] += duration
        entry[2] += int(cached)
        for ref in list(self._listeners):
            method = ref()
            if method is not None:
                method(where, duration, cached)


_COUNTER: Optional[CompileCounter] = None
_COUNTER_LOCK = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process's :class:`CompileCounter` (its listeners are registered
    with ``jax.monitoring`` on the first call)."""
    global _COUNTER
    with _COUNTER_LOCK:
        if _COUNTER is None:
            _COUNTER = CompileCounter()
            jax.monitoring.register_event_listener(_COUNTER._on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _COUNTER._on_duration)
        return _COUNTER


class TraceRecorder:
    """Append-only event recorder for one serving run.

    ``sink`` names a JSONL path the recorder can always flush to. Used as a
    context manager, a recorder with a sink is crash-safe: if the ``with``
    body raises, ``__exit__`` settles the open spans (:meth:`close_open`)
    and writes the JSONL tail anyway, so the trace of a crashed or aborted
    run is still complete, well-formed, and replayable by ``sim/replay.py``
    (``meta.aborted`` is set so the replay report names it). A normal exit
    flushes too — ``flush()`` is idempotent and explicit calls remain fine.
    """

    def __init__(self, clock=profiler_clock,
                 sink: Optional[str] = None) -> None:
        self._clock = clock
        # the anchor of trace time on the profiler's clock (module
        # docstring); with another clock it only dates the run
        t0_ns = time.time_ns()
        self._t0 = clock()
        self.sink = sink
        self.header: Dict = {
            "schema": TRACE_SCHEMA,
            "version": TRACE_VERSION,
            "t0_unix": t0_ns * 1e-9,
            "t0_ns": t0_ns,
            "meta": {},
        }
        self.events: List[Dict] = []
        self._open: Dict[str, List[str]] = {}  # track -> stack of open spans

    def now(self) -> float:
        """Seconds since recorder creation (the trace time base)."""
        return self._clock() - self._t0

    def attach(self, key: str, value) -> None:
        """Attach a header field (sharding report, collective bytes, ...)."""
        self.header[key] = value

    def _emit(self, ph: str, name: str, track: str, args: Dict,
              ts: Optional[float] = None) -> None:
        self.events.append({
            "ts": self.now() if ts is None else ts,
            "ph": ph,
            "name": name,
            "track": track,
            "args": args,
        })

    def instant(self, name: str, track: str = "engine", **args) -> None:
        self._emit("I", name, track, args)

    def begin(self, name: str, track: str = "engine", **args) -> None:
        self._open.setdefault(track, []).append(name)
        self._emit("B", name, track, args)

    def end(self, name: str, track: str = "engine", **args) -> None:
        stack = self._open.get(track, [])
        if not stack or stack[-1] != name:
            raise ValueError(
                f"trace span mismatch on track {track!r}: ending {name!r}, "
                f"open spans are {stack}"
            )
        stack.pop()
        self._emit("E", name, track, args)

    def close_open(self, **args) -> None:
        """End every open span (innermost first) — aborted-run cleanup, so
        exports are always nesting-consistent."""
        for track, stack in self._open.items():
            while stack:
                self._emit("E", stack.pop(), track, args)

    def flush(self, path: Optional[str] = None) -> Optional[str]:
        """Settle open spans and write the JSONL trace to ``path`` (default:
        the configured ``sink``). Returns the written path, or None when
        neither is set. Safe to call repeatedly — the exports rewrite."""
        target = path or self.sink
        if target is None:
            return None
        self.close_open()
        return self.write_jsonl(target)

    # -- context manager: flush-on-exception ----------------------------------

    def __enter__(self) -> "TraceRecorder":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.header.setdefault("meta", {})["aborted"] = True
        self.flush()

    # -- exports --------------------------------------------------------------

    def to_chrome(self) -> Dict:
        """Chrome trace-event JSON (open in Perfetto / chrome://tracing)."""
        tids: Dict[str, int] = {}
        out = []
        for ev in self.events:
            tid = tids.setdefault(ev["track"], len(tids))
            out.append({
                "name": ev["name"],
                "ph": {"B": "B", "E": "E", "I": "i"}[ev["ph"]],
                "ts": ev["ts"] * 1e6,  # chrome wants microseconds
                "pid": 1,
                "tid": tid,
                "cat": "serving",
                "args": ev["args"],
            })
        meta = [
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
             "args": {"name": track}}
            for track, tid in tids.items()
        ]
        return {
            "traceEvents": meta + out,
            "displayTimeUnit": "ms",
            "metadata": self.header,
        }

    def write_chrome(self, path: str) -> str:
        _ensure_dir(path)
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path

    def write_jsonl(self, path: str) -> str:
        """The versioned replayable trace: header line, then one event/line."""
        _ensure_dir(path)
        with open(path, "w") as f:
            f.write(json.dumps(self.header) + "\n")
            for ev in self.events:
                f.write(json.dumps(ev) + "\n")
        return path


def _ensure_dir(path: str) -> None:
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)


def _checked_header(path: str, header: Dict) -> Dict:
    if header.get("schema") != TRACE_SCHEMA:
        raise ValueError(
            f"{path}: not a {TRACE_SCHEMA} trace (schema={header.get('schema')!r})"
        )
    if header.get("version", 0) > TRACE_VERSION:
        raise ValueError(
            f"{path}: trace version {header['version']} is newer than this "
            f"reader ({TRACE_VERSION})"
        )
    return header


class TraceReader:
    """Streaming JSONL trace reader: header eagerly, events lazily.

    The header line is read and schema-checked at construction; iterating
    yields one validated event dict per JSONL line without ever holding the
    whole file — a multi-hundred-MB serving trace replays in O(1) memory.
    Single-pass: iterate once (the PE-array simulator's replay is a single
    forward sweep by design).
    """

    def __init__(self, path: str) -> None:
        self.path = path
        self._f = open(path)
        first = self._f.readline()
        if not first.strip():
            self._f.close()
            raise ValueError(f"{path}: empty trace")
        self.header: Dict = _checked_header(path, json.loads(first))

    def __iter__(self):
        return self

    def __next__(self) -> Dict:
        for line in self._f:
            if not line.strip():
                continue
            ev = json.loads(line)
            if "ts" not in ev or "ph" not in ev or "name" not in ev:
                self._f.close()
                raise ValueError(f"{self.path}: malformed event {ev!r}")
            return ev
        self._f.close()
        raise StopIteration

    def close(self) -> None:
        self._f.close()

    def __enter__(self) -> "TraceReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def iter_trace(path: str) -> TraceReader:
    """Open a JSONL trace for streaming replay.

    Returns a :class:`TraceReader`: ``reader.header`` is the schema-checked
    header (validated before the first event is touched, same checks as
    :func:`read_trace`), and iterating the reader yields events one line at a
    time. Use as an iterator or a context manager::

        with iter_trace(path) as tr:
            for ev in tr: ...
    """
    return TraceReader(path)


def read_trace(path: str) -> Tuple[Dict, List[Dict]]:
    """Load a JSONL trace fully: ``(header, events)``, schema-checked.

    Thin wrapper over :func:`iter_trace` that materializes the event list —
    convenient for tests and small traces; the simulator streams instead.
    """
    with iter_trace(path) as tr:
        return tr.header, list(tr)
