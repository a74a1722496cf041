"""Unified observability layer: tag-addressed spans + process metrics.

Stark's evaluation is a wall-clock argument — the paper decomposes
execution into the recursion tree's divide / multiply / combine phases
to show where the 7-multiplication scheme wins. This package is the
repro's single substrate for that decomposition:

* :mod:`repro.obs.tracer` — nestable spans with a thread-local context
  stack. Block-scheduler spans are addressed by the paper's base-7 /
  base-4 **tag** (``tags.to_string``), so an exported trace literally
  renders the recursion tree: level-order divide spans, 7^q leaf-wave
  stage / dispatch / fetch spans, and the async-pipeline overlap as
  concurrent tracks.
* :mod:`repro.obs.metrics` — process-local counters, gauges, and
  fixed-bucket histograms (TTFT / TPOT per request, autotune hit /
  miss, pool pages in use) with a ``snapshot()`` dict API.
* :mod:`repro.obs.export` — the Chrome / Perfetto ``trace_event`` JSON
  writer and its schema check.

Live spans of an enabled tracer are also ``jax.profiler`` annotations,
so under a running profiler trace they line up with the device ops on
one clock.

Tracing is **disabled by default**: ``get_tracer().span(...)`` returns
a shared no-op context manager (zero allocation) until
``obs.configure(enabled=True)`` — launchers flip it on behind their
``--trace-out`` flags.
"""
from __future__ import annotations

from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Metrics,
    get_metrics,
    reset_metrics,
)
from repro.obs.tracer import (  # noqa: F401
    Span,
    Tracer,
    configure,
    get_tracer,
    reset_tracing,
)

__all__ = [
    "Span",
    "Tracer",
    "configure",
    "get_tracer",
    "reset_tracing",
    "Counter",
    "Gauge",
    "Histogram",
    "Metrics",
    "get_metrics",
    "reset_metrics",
]
