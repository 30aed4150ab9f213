"""Reduce a ``jax.profiler`` trace to the benchmark's device numbers.

The trace is read with ``jax.profiler.ProfileData``.  Device operations are
the events on the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; the
harness's own host spans are the events named ``bench.*`` on the host plane,
written by ``jax.profiler.TraceAnnotation`` on the same clock.  The traced
window is the ``bench.traced`` span.

From those, :func:`reduce_trace` gives, averaged over the chips traced:

* ``busy_s`` — the union of the intervals in which an operation ran,
  clipped to the window; ``window_s`` — the window's length;
* ``collective_s`` — time in collective operations (all-reduce, all-gather,
  all-to-all, collective-permute, reduce-scatter);
* ``top_ops`` — the operations that took most time, by the name the trace
  gives them.  Operations nest on the line (a ``while`` holds its
  ``conditional``, which holds fusions), so each counts its self time: its
  duration less that of the operations inside it;
* ``idle_gaps`` — the longest stretches with no operation on the first
  chip, each labelled by the innermost harness span the host was in.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

__all__ = ["events_of", "load", "reduce_trace", "WINDOW_SPAN"]

WINDOW_SPAN = "bench.traced"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter",
    re.IGNORECASE)

Interval = Tuple[str, float, float]      # (name, start_ns, end_ns)


def events_of(profile) -> Tuple[Dict[int, List[Interval]], List[Interval]]:
    """({chip: device operations}, harness host spans) of a ProfileData."""
    device: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == _OPS_LINE:
                device.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.end_ns) for e in line.events)
            elif not m:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith("bench."))
    return device, spans


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir``, as ProfileData."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return ProfileData.from_file(max(paths, key=os.path.getmtime))


def _merge(ivs: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(ops: List[Interval], into: Dict[str, float]) -> None:
    """Add each operation's duration less its nested operations' to
    ``into``, by name."""
    stack: list = []

    def close(frame):
        into[frame[0]] = into.get(frame[0], 0.0) + frame[2] - frame[1] \
            - frame[3]

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())


def _label(spans: List[Interval], t: float) -> str:
    """The innermost (shortest) harness span covering time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and name != WINDOW_SPAN and (
                best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside harness spans"


def reduce_trace(device: Dict[int, List[Interval]], spans: List[Interval],
                 top: int = 10) -> dict:
    """The device numbers of one traced window (see the module docstring)."""
    win = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not win or not device:
        raise ValueError("the trace holds no traced window or no device "
                         "operation")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    chips = sorted(device)
    busy_ns, coll_ns = 0.0, 0.0
    by_op: Dict[str, float] = {}
    first_busy: List[Tuple[float, float]] = []
    for chip in chips:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in device[chip]
                   if e > w0 and s < w1]
        merged = _merge([(s, e) for _, s, e in clipped])
        busy_ns += sum(e - s for s, e in merged)
        coll_ns += sum(e - s for s, e in _merge(
            [(s, e) for n, s, e in clipped if _COLLECTIVE.search(n)]))
        _self_times(clipped, by_op)
        if chip == chips[0]:
            first_busy = merged
    k = len(chips)
    edges = [w0] + [x for iv in first_busy for x in iv] + [w1]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "chips": k,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns / k * 1e-9,
        "collective_s": coll_ns / k * 1e-9,
        "top_ops": [[n, t / k * 1e-9] for n, t in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in gaps[:top]],
    }
