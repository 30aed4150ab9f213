"""What every driver shares: host spans, the traced window, compile counts.

A driver marks what the host is doing with :meth:`Harness.span`, which
writes a ``jax.profiler.TraceAnnotation`` (about a microsecond when no trace
runs, so traced and untraced runs drive the same code).  In a ``--trace 1``
run the whole window is traced: :meth:`Harness.start_trace` before it, and
:meth:`Harness.finish_trace` once it has closed, which reduces the trace.
"""
from __future__ import annotations

import os
import shutil
import sys
import time

import jax

from . import trace as trace_mod

__all__ = ["Harness"]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Harness:
    def __init__(self, root: str, trace: bool):
        self.work_dir = os.path.join(root, ".chipbench")
        self.trace_on = trace
        self.tracing = False
        self.traced = None              # the reduced trace, once finished
        self._window_ann = None
        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_):
        if event == _COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    @staticmethod
    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    @staticmethod
    def span(name: str):
        return jax.profiler.TraceAnnotation(name)

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    # -- the traced window ---------------------------------------------------

    def start_trace(self) -> None:
        if self.trace_on:
            self._trace_dir = os.path.join(self.work_dir, "trace")
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self._trace_dir)
            self._window_ann = jax.profiler.TraceAnnotation(
                trace_mod.WINDOW_SPAN)
            self._window_ann.__enter__()
            self.tracing = True

    def finish_trace(self) -> None:
        """Close the traced window, stop the profiler and reduce its trace."""
        if not self.tracing:
            return
        self._window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.tracing = False
        t0 = time.perf_counter()
        device, spans = trace_mod.events_of(trace_mod.load(self._trace_dir))
        self.traced = trace_mod.reduce_trace(device, spans)
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        self.log(f"trace: {sum(map(len, device.values()))} device "
                 f"operations on {len(device)} chip(s), reduced in "
                 f"{time.perf_counter() - t0:.1f} s")
