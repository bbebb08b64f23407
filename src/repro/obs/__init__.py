"""Unified observability plane: span tracing + metrics registry.

Two small modules, light to import (``trace`` loads ``jax.profiler``
only when a tracer is built):

* :mod:`repro.obs.trace` — a thread-safe span tracer with **dual
  clocks** (wall clock and the scheduler's simulated clock), nested
  spans mirrored as ``jax.profiler.TraceAnnotation`` (so they land in a
  profiler trace beside the device's operations), a bounded ring-buffer
  flight recorder, and Chrome trace-event JSON export viewable in
  Perfetto / ``chrome://tracing``.
* :mod:`repro.obs.metrics` — a registry of labeled counters / gauges /
  histograms with JSON-safe snapshots; ``TrafficStats``,
  ``RuntimeStats`` and ``MemoryMeter`` publish into it instead of
  remaining islands.

The default-off path is near-zero-cost: hot layers guard every
instrumentation block on a single ``trace.ACTIVE is None`` check, so an
untraced run allocates nothing and pays one global load per guarded
site. Tracing is strictly observational — it never perturbs simulated
timelines or trained weights (a tested invariant).
"""
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer, activate, validate_chrome_trace

__all__ = ["MetricsRegistry", "Tracer", "activate", "validate_chrome_trace"]
