"""Serving observability: SLO latency metrics + replayable structured traces.

The measurement substrate under the serving engine's performance claims.
Three pieces, all host-side (never inside a jitted program — token streams
are bit-identical with observability on or off, asserted in
``tests/test_obs.py``):

* :mod:`repro.obs.metrics` — counters, gauges, and streaming histograms
  (p50/p90/p99) for the SLO quantities: time-to-first-token, inter-token
  latency, queue wait, prefill/decode wall time, per-request and run tok/s,
  acceptance rate, host transfers.
* :mod:`repro.obs.trace` — a structured event timeline (admission, prefill,
  bursts with their execution point, controller switches with their
  ``StepSignals``, speculative draft/verify/rollback, compile events) with
  two exports: Chrome-trace JSON (render a serving run in Perfetto) and a
  versioned JSONL format — the replay input for the ROADMAP's cycle-accurate
  PE-array simulator (``read_trace`` is the schema-checked reader).
* :mod:`repro.obs.observer` — :class:`ServingObserver`, the hook bundle
  ``BatchedServer(observer=...)`` drives at its existing host sync points.

The serving program also opens its own profiler spans
(:func:`repro.obs.trace.span`, listed in ``PROGRAM_SPANS``) and names the
parts of its device programs with ``jax.named_scope``, which is metadata
only; :func:`repro.obs.trace.compile_counter` attributes each backend
compile to the innermost open span.

Overhead is gated in CI: ``bench_serving --smoke`` fails if serving with an
observer attached falls below 95% of uninstrumented tok/s.
"""
from .metrics import Counter, Gauge, MetricsRegistry, StreamingHistogram
from .observer import ServingObserver
from .trace import (TRACE_SCHEMA, TRACE_VERSION, TraceReader, TraceRecorder,
                    iter_trace, read_trace)

__all__ = [
    "Counter",
    "Gauge",
    "MetricsRegistry",
    "ServingObserver",
    "StreamingHistogram",
    "TraceReader",
    "TraceRecorder",
    "TRACE_SCHEMA",
    "TRACE_VERSION",
    "iter_trace",
    "read_trace",
]
