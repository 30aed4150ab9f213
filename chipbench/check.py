"""The comparison that decides ``correct``, and its controls.

Every number compared has a limit; a run is correct when each number is at
or under its limit.  The numbers:

* ``unanswered`` — queries or searches of the window that never returned
  (limit 0).
* ``reach_wrong`` / ``dist_wrong`` — Reachability and Distance answers
  that differ from the reference (limit 0: both are exact).
* ``ppr_gap`` — the widest gap, over the PPR top-k answers, between a
  returned score and the reference's score of that vertex, or between the
  returned vertices' reference scores and the reference's own top k, as a
  share of the query's top reference score (limit in the mix file, set from
  sound runs and the control).
* ``levels_wrong`` / ``dist_wrong`` for searches — vertices, summed over
  the window's searches, whose BFS level or SSSP distance differs from the
  reference (limit 0).

Every answer the window produced is compared, once the window has closed:
each answered query, and each search's levels or distances.  A traversal
is checked against the reference's whole search from its source, one
reference search per distinct source.

The control puts the reference in the program's place, computed in
bfloat16 (the precision below the configuration's float32), or, for BFS
levels, which carry no floating-point precision, stopped one level short.
"""
from __future__ import annotations

import numpy as np

from . import reference as ref

__all__ = ["served_numbers", "served_control", "search_numbers",
           "search_control", "verdict"]


def _ppr_gap(x: np.ndarray, k: int, answer) -> float:
    ids, scores = (np.asarray(a) for a in answer)
    top = np.sort(x)[::-1][:k]
    if ids.shape != (k,) or scores.shape != (k,) or ids.min() < 0 \
            or ids.max() >= x.shape[0] or np.unique(ids).size != k:
        return float("inf")
    gap = max(np.abs(scores - x[ids]).max(),
              np.abs(np.sort(x[ids])[::-1] - top).max()) / top[0]
    return float(gap) if np.isfinite(gap) else float("inf")


def _ppr_of(g: ref.Graph, answered: list, params: dict,
            precision: str = "float64") -> dict:
    """{source: reference PPR vector} of every PPR query answered."""
    srcs = sorted({a for (kind, a, _), _ in answered if kind == "ppr"})
    x = ref.ppr(g, srcs, params["damping"], params["ppr_iters"], precision)
    return {s: x[:, i] for i, s in enumerate(srcs)}


def served_numbers(g: ref.Graph, answered: list, params: dict) -> dict:
    """``{number: value}`` of the answered ``((kind, a, b), answer)`` pairs
    against the reference."""
    levels, dists = {}, {}
    pprs = _ppr_of(g, answered, params)
    out = {"reach_wrong": 0, "dist_wrong": 0, "ppr_gap": 0.0}
    for (kind, a, b), ans in answered:
        if kind == "reach":
            if a not in levels:
                levels[a] = ref.bfs_levels(g, a)
            out["reach_wrong"] += bool(ans) != bool(levels[a][b] >= 0)
        elif kind == "dist":
            if a not in dists:
                dists[a] = ref.sssp_f32(g, a)
            want = dists[a][b]
            got = np.float32(ans)
            out["dist_wrong"] += not (got == want or (
                np.isinf(got) and np.isinf(want)))
        else:
            out["ppr_gap"] = max(out["ppr_gap"], _ppr_gap(pprs[a], b, ans))
    return out


def served_control(g: ref.Graph, answered: list, params: dict) -> list:
    """The answered queries answered again by the reference in bfloat16."""
    levels, dists = {}, {}
    pprs = _ppr_of(g, answered, params, precision="bfloat16")
    out = []
    for (kind, a, b), _ in answered:
        if kind == "reach":
            if a not in levels:
                levels[a] = ref.bfs_levels(g, a)
            ans = bool(levels[a][b] >= 0)
        elif kind == "dist":
            if a not in dists:
                dists[a] = ref.sssp_f32(g, a, precision="bfloat16")
            ans = float(dists[a][b])
        else:
            ids = np.argsort(-pprs[a], kind="stable")[:b]
            ans = (ids, pprs[a][ids])
        out.append(((kind, a, b), ans))
    return out


def _search_ref(g: ref.Graph, search: str, key: int, control: bool):
    if search == "bfs":
        return ref.bfs_levels(g, key, levels_short_by=int(control))
    return ref.sssp_f32(g, key,
                        precision="bfloat16" if control else "float32")


def search_numbers(g: ref.Graph, search: str, results: list) -> dict:
    """Vertices whose level (BFS) or distance (SSSP) differs from the
    reference, over ``results`` ([(key, program output)])."""
    wrong, wants = 0, {}
    for key, got in results:
        if key not in wants:
            wants[key] = _search_ref(g, search, key, control=False)
        want = wants[key]
        got = np.asarray(got)
        if search == "bfs":
            wrong += int((got != want).sum())
        else:
            wrong += int((~((got == want) | (np.isinf(got)
                                              & np.isinf(want)))).sum())
    return {("levels_wrong" if search == "bfs" else "dist_wrong"): wrong}


def search_control(g: ref.Graph, search: str, results: list) -> list:
    """``results`` answered again by the control."""
    outs = {k: _search_ref(g, search, k, control=True)
            for k in {k for k, _ in results}}
    return [(k, outs[k]) for k, _ in results]


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {number: {"value", "limit"}}) — every number at or under
    its limit."""
    shown = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(v <= limits[k] for k, v in numbers.items()), shown
