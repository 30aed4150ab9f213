"""Host-side spans: the wall-clock half of the observability layer.

:func:`span` is the one span path.  Every call enters a
``jax.profiler.TraceAnnotation`` with the span's name and scalar args, so
the span lands in a profiler trace on the host plane, on the same clock as
the device's ops, whether or not anything else records it; with no profiler
running an annotation costs about a microsecond, so traced and untraced runs
drive the same code.  Given a :class:`SpanRecorder` (an attached
``Observability``), the call also keeps the span there, for the Chrome
``trace_event`` export.

A :class:`Span` is one named, closed time interval on a logical thread
(``tid``) with free-form ``args`` — exactly a Chrome ``trace_event``
complete ("X") event before serialization.  Spans are opened and closed
around the work they time, so spans on one tid nest or are disjoint by
construction; an interval measured after the fact (a queue wait) is a
counter, not a span, because a profiler cannot hold it.

jax is imported by the first span, not with this module, so the
``python -m repro.obs summarize`` CLI stays jax-free.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

__all__ = ["Span", "SpanRecorder", "span"]


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed interval: seconds-based ts/dur, converted to µs on export."""

    name: str
    ts: float            # start, seconds on the recorder's clock
    dur: float           # duration, seconds (>= 0)
    tid: int = 0
    pid: int = 0
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)


class SpanRecorder:
    """Bounded span sink.

    clock: injectable monotonic seconds source, so a service driven by a
      fake clock (the deadline tests) records coherent spans.
    capacity: spans retained (oldest dropped) — observability must not be
      the unbounded buffer the latency deque used to be.
    """

    def __init__(self, clock=time.perf_counter, capacity: int = 65536):
        self._clock = clock
        self._spans: "collections.deque[Span]" = \
            collections.deque(maxlen=int(capacity))

    def now(self) -> float:
        return self._clock()

    def add(self, sp: Span) -> None:
        self._spans.append(sp)

    def spans(self) -> List[Span]:
        return list(self._spans)

    def clear(self) -> None:
        self._spans.clear()

    def __len__(self) -> int:
        return len(self._spans)


def _scalars(args: Dict[str, Any]) -> Dict[str, Any]:
    """The args a profiler event can hold as stats."""
    return {k: v for k, v in args.items() if isinstance(v, (str, int, float))}


@contextmanager
def span(name: str, recorder: Optional[SpanRecorder] = None, *,
         tid: int = 0, **args):
    """Time a code block as span ``name`` (see the module docstring).

    Yields the span's args as a dict the block may add to (a count known
    only at its end); scalar args reach the profiler event, and the
    recorder, when given, keeps every arg."""
    from jax.profiler import TraceAnnotation
    live: Dict[str, Any] = dict(args)
    ann = TraceAnnotation(name, **_scalars(args))
    t0 = recorder.now() if recorder is not None else 0.0
    with ann:
        try:
            yield live
        finally:
            added = _scalars({k: v for k, v in live.items() if k not in args})
            if added:
                ann.set_metadata(**added)
            if recorder is not None:
                recorder.add(Span(name, t0, max(0.0, recorder.now() - t0),
                                  tid=tid, args=live))
