"""Unified telemetry for the engine + service stack (DESIGN.md §17).

Three measurement planes, one export:

* **device**: per-level engine traces — ``trace=True`` on the engine runners
  records ``(frontier, direction, fallback, flush)`` per level into a
  fixed-length device array inside the single stepping loop (no host syncs;
  see :mod:`repro.obs.trace` for the decode contract), plus the push
  counters ``push_edges`` / ``push_slots`` and the ``fallbacks`` count on
  the local placement;
* **host**: spans — :func:`~repro.obs.spans.span` is the one span path: it
  always writes a ``jax.profiler.TraceAnnotation`` (on the device trace's
  clock) and, with an :class:`Observability` attached, also keeps the span
  in its :class:`~repro.obs.spans.SpanRecorder`;
* **counters**: :mod:`repro.obs.metrics` — the process-wide registry every
  fallback/degradation event lands in (the ROADMAP guardrail).

:class:`Observability` bundles the recorder and the registry for one
consumer (a ``GraphService``, a bench section, the example's ``--trace``
flag) and exports them as one Chrome ``trace_event`` JSON.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, REGISTRY,
                      get_registry)
from .spans import Span, SpanRecorder, span
from .trace import LevelTrace, decode_level_trace
from .export import (build_chrome_trace, write_chrome_trace,
                     validate_chrome_trace, summarize, format_summary)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "get_registry", "Span", "SpanRecorder", "span", "LevelTrace",
    "decode_level_trace", "build_chrome_trace", "write_chrome_trace",
    "validate_chrome_trace", "summarize", "format_summary",
    "Observability", "export_chrome_trace",
]


class Observability:
    """One consumer's telemetry bundle: a span recorder + metrics.

    Attached to a ``GraphService(obs=...)``, it keeps the service's spans
    (which reach a running profiler with or without it) for the Chrome
    export, and compiles the service's traversal runners with
    ``trace=True``: each traced batch's decoded levels become an arg of its
    ``svc.sync`` span.  The metrics registry defaults to the process-wide
    one (counters are always on), but an isolated :class:`MetricsRegistry`
    may be passed for hermetic readouts.
    """

    def __init__(self, clock=time.perf_counter,
                 metrics: Optional[MetricsRegistry] = None,
                 span_capacity: int = 65536):
        self.spans = SpanRecorder(clock=clock, capacity=span_capacity)
        self.metrics = metrics if metrics is not None else get_registry()

    def build_trace(self) -> Dict[str, Any]:
        return build_chrome_trace(self.spans.spans(), self.metrics.snapshot())

    def export_chrome_trace(self, path: str) -> Dict[str, Any]:
        """Write the Chrome ``trace_event`` JSON; returns the document."""
        doc = self.build_trace()
        import json
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return doc

    def summary(self) -> Dict[str, Any]:
        return summarize(self.build_trace())

    def clear(self) -> None:
        self.spans.clear()


def export_chrome_trace(path: str, obs: Observability) -> Dict[str, Any]:
    """Module-level convenience: ``obs.export_chrome_trace(path)``."""
    return obs.export_chrome_trace(path)
