"""Breadth-first search, as a :mod:`repro.core.engine` vertex program.

The program: active vertices emit an indicator along out-edges; a destination
combining a positive count for the first time is assigned the next level and
joins the frontier.  Direction optimization (push the sparse frontier, pull
once it saturates) is the engine's job, not BFS's — locally ``mode='auto'``
switches on the frontier population count (Beamer's heuristic); distributed,
push expands through PIUMA remote atomics at the dst owner and pull gathers
via fine-grained dgas reads over the reversed edge shards.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import engine
from ..dgas import ATT
from ..graph import CSR, BBCSR
from .distgraph import ShardedGraph

__all__ = ["bfs", "bfs_distributed", "bfs_program", "bfs_level_program",
           "msbfs", "msbfs_distributed", "msbfs_program"]

_INF = jnp.float32(jnp.inf)


def bfs_program() -> engine.VertexProgram:
    """Levels in state['level'], int32 frontier indicator as the message."""

    def msg_fn(state, frontier):
        return frontier.astype(jnp.int32)

    def update_fn(state, acc, frontier, it):
        new = (acc > 0) & (state["level"] < 0)
        level = jnp.where(new, it + 1, state["level"])
        return {"level": level}, new.astype(jnp.int32)

    return engine.VertexProgram(edge_op="copy", combine="add",
                                msg_fn=msg_fn, update_fn=update_fn)


def bfs(csr: CSR, source: int, *, max_levels: int | None = None,
        mode: str = "auto", kernel_bb: Optional[BBCSR] = None,
        return_stats: bool = False, trace: bool = False,
        trace_len: Optional[int] = None):
    """Returns level array (n,) int32; unreachable = -1.

    mode: 'auto' (direction-optimizing, default) | 'push' | 'pull'.
    kernel_bb: optional BBCSR of A^T to run both directions on the Pallas
      SpMV/SpMSpV kernels; must be unit-valued — build it with
      engine.build_pull_operand(csr, unit_values=True) (the engine rejects a
      weighted operand, since the kernel multiplies by stored values).
    return_stats: also return the engine's stats ((levels, stats)); with
      ``trace`` they hold the per-level trace and the push counters
      (``engine.run``).
    """
    n = csr.n_rows
    max_levels = max_levels or n
    state0 = {"level": jnp.full((n,), -1, jnp.int32).at[source].set(0)}
    frontier0 = jnp.zeros((n,), jnp.int32).at[source].set(1)
    out = engine.run(csr, bfs_program(), state0, frontier0,
                     max_iters=max_levels, mode=mode, kernel_bb=kernel_bb,
                     return_stats=return_stats, trace=trace,
                     trace_len=trace_len)
    if return_stats:
        state, stats = out
        return state["level"], stats
    return out["level"]


def bfs_level_program() -> engine.VertexProgram:
    """Monotone min-level BFS — the async placement's BFS program.

    :func:`bfs_program` stamps a destination's level from the iteration
    counter the first time it is touched, which is order-*dependent* under
    the async placement's deferred message delivery.  This variant is
    label-correcting instead: the state is a float distance (levels are
    small ints, exact in f32), active vertices emit ``dist + 1``, and
    destinations keep the **min** — the unit-weight (min, +) semiring, whose
    unique fixpoint is the hop distance no matter in which order (or how
    stale) messages arrive.  Convert with
    ``where(isfinite(dist), dist, -1).astype(int32)`` to match
    :func:`bfs_program` levels exactly.
    """

    def msg_fn(state, frontier):
        return jnp.where(frontier > 0, state["dist"] + 1.0, _INF)

    def update_fn(state, acc, frontier, it):
        better = acc < state["dist"]
        return ({"dist": jnp.minimum(state["dist"], acc)},
                better.astype(jnp.int32))

    return engine.VertexProgram(edge_op="copy", combine="min",
                                msg_fn=msg_fn, update_fn=update_fn)


def _levels_from_dist(dist: jnp.ndarray) -> jnp.ndarray:
    """f32 min-level fixpoint -> int32 levels, unreachable = -1."""
    return jnp.where(jnp.isfinite(dist), dist, -1.0).astype(jnp.int32)


def msbfs_program(n_lanes: int) -> engine.VertexProgram:
    """Multi-source BFS (MS-BFS, Then et al.): one bit lane per source.

    The frontier is the bit-packed (n, W) uint32 word array; ``seen`` is the
    OR-accumulated visited mask, and a destination's new lanes are
    ``acc & ~seen`` — B traversals advance per edge scan.  Levels are kept
    unpacked (B, n) so they read out exactly like B separate `bfs` runs.
    """

    def msg_fn(state, frontier):
        return frontier

    def update_fn(state, acc, frontier, it):
        new = acc & ~state["seen"]
        newb = engine.unpack_lanes(new, n_lanes)
        level = jnp.where(newb > 0, it + 1, state["level"])
        return {"seen": state["seen"] | new, "level": level}, new

    return engine.VertexProgram(edge_op="copy", combine="or",
                                msg_fn=msg_fn, update_fn=update_fn)


def msbfs(csr: CSR, sources, *, max_levels: int | None = None,
          mode: str = "auto", return_stats: bool = False,
          trace: bool = False, trace_len: Optional[int] = None):
    """Levels (B, n) int32 for B concurrent BFS traversals; unreachable = -1.

    Row b is bit-identical to ``bfs(csr, sources[b])`` — the lanes share
    every edge scan but never interact.  Duplicate sources are allowed (their
    lanes evolve identically).  ``trace`` (with ``return_stats``) records the
    per-level engine trace into ``stats['trace']`` (obs.decode_level_trace).
    """
    n = csr.n_rows
    src = jnp.asarray(sources, jnp.int32)
    B = int(src.shape[0])
    max_levels = max_levels or n
    lanes = jnp.arange(B)
    bits0 = jnp.zeros((B, n), jnp.int32).at[lanes, src].set(1)
    f0 = engine.pack_lanes(bits0)
    state0 = {"seen": f0,
              "level": jnp.full((B, n), -1, jnp.int32).at[lanes, src].set(0)}
    out = engine.run_batched(csr, msbfs_program(B), state0, f0,
                             max_iters=max_levels, mode=mode,
                             return_stats=return_stats,
                             trace=trace, trace_len=trace_len)
    if return_stats:
        state, stats = out
        return state["level"], stats
    return out["level"]


def msbfs_distributed(g: ShardedGraph, att: ATT, sources, mesh: Mesh, *,
                      axis=None, max_levels: int = 64,
                      push_edge_capacity: Optional[int] = None,
                      return_stats: bool = False, placement: str = "sync",
                      sync_interval: Optional[int] = None,
                      trace: bool = False, trace_len: Optional[int] = None):
    """Batched-lane BFS on the distributed push pipeline.

    Returns levels stacked (S, B, per_shard) under the `att` layout — slice
    ``[:, b, :]`` is bit-identical to ``bfs_distributed(g, att, sources[b],
    mesh)``.  One compacted exchange per level carries all B lanes as packed
    words (`offload.remote_scatter_or`).

    placement='async' runs the monotone :func:`bfs_level_program` on vmapped
    valued lanes instead (the first-touch level stamp of the packed program
    is order-dependent under deferred delivery; the min-level fixpoint is
    not), with `sync_interval` local micro-steps per global check — same
    levels, ≥K× fewer global reductions.
    """
    S, per = att.n_shards, att.per_shard
    src = jnp.asarray(sources, jnp.int32)
    B = int(src.shape[0])
    W = engine.lane_words(B)
    owner = att.owner(src)
    local = att.local(src)
    lanes = jnp.arange(B)
    if placement == "async":
        k = int(sync_interval) if sync_interval is not None else 8
        dist0 = jnp.full((S, B, per), _INF) \
            .at[owner, lanes, local].set(0.0)
        f0 = jnp.zeros((S, B, per), jnp.int32) \
            .at[owner, lanes, local].set(1)
        out = engine.run_batched_distributed(
            g, att, mesh, bfs_level_program(), {"dist": dist0}, f0,
            axis=axis, max_iters=max_levels * k,
            push_edge_capacity=push_edge_capacity,
            return_stats=return_stats, placement="async", sync_interval=k,
            trace=trace, trace_len=trace_len)
        if return_stats:
            state, stats = out
            return _levels_from_dist(state["dist"]), stats
        return _levels_from_dist(out["dist"])
    # traceable init (sources may be a jit argument — the service's padded
    # batches): lanes occupy disjoint bits of their word, so the scatter-add
    # is the bitwise OR even when sources collide on a (shard, vertex, word)
    bits = jnp.uint32(1) << (lanes % 32).astype(jnp.uint32)
    words0 = jnp.zeros((S, per, W), jnp.uint32) \
        .at[owner, local, lanes // 32].add(bits)
    level0 = jnp.full((S, B, per), -1, jnp.int32) \
        .at[owner, lanes, local].set(0)
    state0 = {"seen": words0, "level": level0}
    out = engine.run_batched_distributed(
        g, att, mesh, msbfs_program(B), state0, words0,
        axis=axis, max_iters=max_levels,
        push_edge_capacity=push_edge_capacity, return_stats=return_stats,
        trace=trace, trace_len=trace_len)
    if return_stats:
        state, stats = out
        return state["level"], stats
    return out["level"]


def bfs_distributed(g: ShardedGraph, att: ATT, source: int, mesh: Mesh, *,
                    axis=None, max_levels: int = 64,
                    g_rev: Optional[ShardedGraph] = None,
                    mode: str = "push", placement: str = "sync",
                    sync_interval: Optional[int] = None) -> jnp.ndarray:
    """Returns level array stacked (S, per_shard) under `att` layout.

    mode='push' reproduces the seed behavior exactly; pass `g_rev`
    (engine.reverse_graph) with mode='auto' for the direction-optimizing
    variant.  placement='async' (push-only) runs the monotone
    :func:`bfs_level_program` with bounded-staleness pacing — identical
    levels, `sync_interval`× fewer global reductions.
    """
    S, per = att.n_shards, att.per_shard
    owner = int(att.owner(jnp.asarray(source)))
    local = int(att.local(jnp.asarray(source)))
    frontier0 = jnp.zeros((S, per), jnp.int32).at[owner, local].set(1)
    if placement == "async":
        k = int(sync_interval) if sync_interval is not None else 8
        dist0 = jnp.full((S, per), _INF).at[owner, local].set(0.0)
        state = engine.run_distributed(
            g, att, mesh, bfs_level_program(), {"dist": dist0}, frontier0,
            axis=axis, max_iters=max_levels * k, mode=mode,
            placement="async", sync_interval=k)
        return _levels_from_dist(state["dist"])
    state0 = {"level": jnp.full((S, per), -1, jnp.int32).at[owner, local].set(0)}
    state = engine.run_distributed(g, att, mesh, bfs_program(), state0,
                                   frontier0, axis=axis, max_iters=max_levels,
                                   g_rev=g_rev, mode=mode)
    return state["level"]
