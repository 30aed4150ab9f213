"""Per-level engine traces: the device half of the observability layer.

The engine's ``_core_loop`` can carry a fixed-length ``(trace_len, 4)``
int32 array and write one row per level **on device** (``trace=True`` on the
public runners) — columns ``[frontier, was_push, fallback, flush]``, where
``frontier`` is the globally-agreed active count *entering* the level,
``was_push`` the direction decision (1 = sparse push / 0 = dense pull; under
``placement='async'`` the engine counts buffered flushes there), ``fallback``
the compacted-push capacity overflow flag (distributed: the push routed the
full partition; local: the frontier's out-edges passed the push step's edge
budget, so the level pulled), and ``flush`` mirrors ``was_push``
only under the async placement (an outbox flush happened this check).
Levels beyond ``trace_len`` are dropped on device (``.at[].set(mode='drop')``),
never clamp-overwritten.

Nothing in this module runs inside a trace: :func:`decode_level_trace` is
the host-side readback that turns the returned stats dict into
:class:`LevelTrace` records *after* the run — the split that keeps the
`host-sync` lint rule satisfied by construction.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

__all__ = ["LevelTrace", "decode_level_trace", "TRACE_COLS"]

#: Column order of the on-device trace rows (engine._core_loop contract).
TRACE_COLS = ("frontier", "was_push", "fallback", "flush")


@dataclasses.dataclass(frozen=True)
class LevelTrace:
    """One decoded engine level (or one global check under async pacing)."""

    level: int           # 0-based body-iteration index
    frontier: int        # global active count entering the level
    direction: str       # 'push' | 'pull' ('flush' under async placement)
    fallback: bool       # push capacity overflow this level (see above)
    flush: bool          # outbox flush fired (async placement only)

    def as_dict(self) -> Dict[str, Any]:
        return {"level": self.level, "frontier": self.frontier,
                "direction": self.direction, "fallback": self.fallback,
                "flush": self.flush}


# trace-safe: decode is the post-run host readback of the stats the jitted
# runner already returned — repro-lint: disable=host-sync
def decode_level_trace(stats: Dict[str, Any]) -> List[LevelTrace]:
    """Decode ``stats['trace']`` (a traced run's stats dict) into records.

    Accepts both layouts the runners return: local ``(L, 4)`` and
    distributed ``(S, L, 4)`` — the trace rows are built from globally
    psum'd quantities, so every shard's copy is identical and shard 0 is
    authoritative.  Rows past the recorded level count (``pushes + pulls``
    body iterations) are unwritten and skipped; rows the device dropped
    (level >= trace_len) are simply absent.
    """
    if "trace" not in stats:
        raise KeyError("stats has no 'trace' — run the engine with "
                       "trace=True (and return_stats=True)")
    arr = np.asarray(stats["trace"])
    if arr.ndim == 3:             # distributed: stacked (S, L, 4), identical
        arr = arr[0]
    levels = int(np.asarray(stats["pushes"]).reshape(-1)[0]
                 + np.asarray(stats["pulls"]).reshape(-1)[0])
    out: List[LevelTrace] = []
    for lvl in range(min(levels, arr.shape[0])):
        frontier, was_push, fb, flush = (int(v) for v in arr[lvl])
        direction = ("flush" if flush else ("push" if was_push else "pull"))
        out.append(LevelTrace(level=lvl, frontier=frontier,
                              direction=direction, fallback=bool(fb),
                              flush=bool(flush)))
    return out
