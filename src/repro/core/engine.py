"""Direction-optimizing vertex-program execution engine.

Every frontier algorithm in this repo (BFS, PageRank, SpMV-as-one-step, SSSP,
connected components) is the same loop: per-vertex *messages* flow along edges
and are combined at the destination, then a per-vertex *update* produces the
next state and the next frontier.  This module owns that loop **once** — as
the :class:`ExecutionCore` (DESIGN.md §14): a single stepping loop
(:func:`_core_loop`) parameterized by two orthogonal axes,

* **lane representation** — ``scalar`` (one traversal), ``valued`` (B
  concurrent traversals as vmapped (B, n) lanes), ``packed`` (B boolean
  traversals bit-packed into (n, ceil(B/32)) uint32 words, MS-BFS style);
* **placement** — ``local`` (one device), shard_map-``distributed``
  (stacked (S, ...) operands, owner-routed exchanges, globally-agreed
  branches, one global reduction per level), or ``async`` (the distributed
  placement with bounded-staleness shard pacing: each shard runs up to
  ``sync_interval`` collective-free local micro-steps against its resident
  partition, deferring remote contributions into a dense combine outbox
  that one ``offload.buffered_flush`` exchange delivers at each global
  convergence check — the PIUMA fine-grained-asynchrony model, bit-identical
  to sync for monotone combines); all share one ``_MAPPED_CACHE`` keying
  scheme with the algorithm layer (louvain's compiled sweeps).

The five public runners — :func:`run`, :func:`run_batched`,
:func:`run_distributed`, :func:`run_batched_distributed`, :func:`run_queue` —
are thin wrappers that pick a point in that grid (``run_queue`` is the
queue-program family: its per-iteration body is its own, but it shares the
compaction, routing and shard_map plumbing).  The algorithms supply only the
little per-edge/per-vertex functions — the paper's "programmable offload"
story: the hardware-ish machinery (DMA gather, remote atomics, collectives,
queues) is shared.

Semiring-lite model.  A program computes, per iteration::

    msg  = msg_fn(state, frontier)            # (n,) — identity on inactive
    acc[v] = combine_{(u,v) in E} edge_op(msg[u], w_uv)
    state, frontier = update_fn(state, acc, frontier, it)

with ``edge_op`` in {mul, add, copy} and ``combine`` in {add, min, max} — plus
two *structured* combines that extend the semiring with non-scalar reductions
(DESIGN.md §4):

* ``combine='argmax_weighted'`` — per-destination weighted label mode: the
  message is an int label, the edge value is the vote weight, and ``acc`` is
  the pair (winning label's total weight, winning label).  Weighted label
  propagation (Louvain local moves) is this combine plus a two-line update.
* ``combine='sample'`` — per-destination keyed reservoir pick: every edge
  draws a random priority from the iteration key (Efraimidis–Spirakis when
  ``edge_op='mul'`` weights the draw) and ``acc`` is the pair (best priority,
  sampled source payload).  Random walks and neighbor sampling are one-step
  programs on this combine, via :func:`sample_neighbors`.

Frontier masking is folded into ``msg_fn`` (inactive vertices emit the combine
identity — ``-1`` for structured payloads), which is what makes push and pull
produce the same ``acc``.

Direction optimization (Beamer-style, re-expressed for bulk arrays):

* **sparse / push** — extract the frontier as an index list (static capacity
  ``C``), lay those vertices' out-edges flat into a static budget of ``E``
  edge slots and scatter-combine their contributions: work ∝ ``E``, sized
  by the graph's edge count rather than by ``C`` × its max degree.
* **dense / pull** — one full edge-parallel pass (gather msg at src, segment
  combine at dst): work ∝ |E| but with no compaction overhead and perfectly
  vectorized.

The switch is a ``lax.cond`` on the frontier population count — carried
through the core loop, and globally reduced with
:func:`offload.hierarchical_psum` under the distributed placement so all
shards take the same branch.  The local placement also checks the
frontier's out-edge count against ``E``: a level within ``C`` vertices but
over ``E`` edges pulls, and is counted as a fallback.

When the program's combine is ``add``, both directions can instead run on the
BBCSR Pallas machinery (``kernels/spmv_dma.py``): the dense step is the SpMV
kernel, and the sparse step is the new SpMSpV variant that skips every tile
whose column block contains no active vertex (PIUMA's "only touch the data
the sparse frontier names").
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from ..compat import shard_map
from .. import tune as _tune
from . import offload
from .dgas import ATT
from .graph import CSR, BBCSR, dst_sorted_edges, to_bbcsr
from .algorithms.distgraph import ShardedGraph

AxisName = Union[str, Sequence[str]]

__all__ = [
    "VertexProgram", "ExecutionCore", "run", "run_distributed", "spmv_pass",
    "build_pull_operand", "tile_active", "sample_neighbors",
    "QueueProgram", "run_queue", "frontier_edge_capacity",
    "Hierarchy", "run_multilevel",
    "run_batched", "run_batched_distributed",
    "lane_words", "pack_lanes", "unpack_lanes",
    "cached_mapped",
]

_COMBINE_IDENTITY = {"add": 0.0, "min": float("inf"), "max": float("-inf"),
                     "or": 0}
_STRUCTURED_COMBINES = ("argmax_weighted", "sample")
_LANE_REPS = ("scalar", "valued", "packed")


@dataclasses.dataclass(frozen=True)
class VertexProgram:
    """One frontier algorithm, reduced to its per-edge/per-vertex pieces.

    Attributes:
      edge_op:   how a message meets the edge weight: 'mul' | 'add' | 'copy'.
      combine:   destination-side reduction: 'add' | 'min' | 'max', or a
                 structured combine 'argmax_weighted' | 'sample' (the message
                 is then an int32 payload, -1 = inactive, and `acc` is the
                 (score, payload) pair — see the module docstring), or the
                 batched-only bitwise combine 'or' (messages are bit-packed
                 uint32 lane words, :func:`run_batched`; edge_op 'copy').
      msg_fn:    (state, frontier) -> (n,) messages; MUST emit `identity` for
                 vertices outside the frontier (that makes push == pull).
      update_fn: (state, acc, frontier, it) -> (state, next_frontier).
      identity:  combine identity (defaults per combine).
    """

    edge_op: str
    combine: str
    msg_fn: Callable[[Any, jnp.ndarray], jnp.ndarray]
    update_fn: Callable[[Any, jnp.ndarray, jnp.ndarray, jnp.ndarray], tuple]
    identity: Optional[float] = None

    def __post_init__(self):
        if self.edge_op not in ("mul", "add", "copy"):
            raise ValueError(f"unknown edge_op {self.edge_op!r}")
        if (self.combine not in _COMBINE_IDENTITY
                and self.combine not in _STRUCTURED_COMBINES):
            raise ValueError(f"unknown combine {self.combine!r}")
        if self.structured and self.edge_op == "add":
            raise ValueError(f"combine {self.combine!r} takes its weight from "
                             "the edge value: edge_op must be 'mul' (weighted)"
                             " or 'copy' (unit)")
        if self.combine == "or" and self.edge_op != "copy":
            raise ValueError("combine 'or' reduces bit-packed lane words — "
                             "edge values cannot weigh in: edge_op must be "
                             "'copy'")

    @property
    def structured(self) -> bool:
        """True for the non-scalar combines whose acc is a (score, payload)
        pair rather than a single reduced value."""
        return self.combine in _STRUCTURED_COMBINES

    @property
    def ident(self):
        if self.identity is not None:
            return self.identity
        if self.structured:
            return float("-inf")  # score identity; payload identity is -1
        return _COMBINE_IDENTITY[self.combine]


def _apply_edge(em: jnp.ndarray, ev: jnp.ndarray, edge_op: str) -> jnp.ndarray:
    if edge_op == "mul":
        return em * ev
    if edge_op == "add":
        return em + ev
    return em


def _scatter_combine(dest: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray,
                     combine: str, identity) -> jnp.ndarray:
    """Scatter-{add,min,max} with out-of-range indices dropped.  ``vals`` may
    carry trailing lane dims beyond ``idx`` (the batched engine's (m, B)
    payloads); ``dest`` then carries the same trailing shape."""
    valid = (idx >= 0) & (idx < dest.shape[0])
    safe = jnp.where(valid, idx, 0)
    neutral = jnp.asarray(identity, dest.dtype)
    vmask = valid.reshape(valid.shape + (1,) * (vals.ndim - valid.ndim))
    masked = jnp.where(vmask, vals.astype(dest.dtype), neutral)
    if combine == "add":
        return dest.at[safe].add(masked)
    if combine == "min":
        return dest.at[safe].min(masked)
    return dest.at[safe].max(masked)


# ---------------------------------------------------------------------------
# Bit-packed lanes (batched boolean frontiers, MS-BFS style)
# ---------------------------------------------------------------------------

def lane_words(n_lanes: int) -> int:
    """uint32 words needed to bit-pack ``n_lanes`` boolean lanes."""
    return -(-n_lanes // 32)


def pack_lanes(bits: jnp.ndarray) -> jnp.ndarray:
    """(B, n) lane indicators -> (n, W) uint32 words; lane b lives at bit
    b % 32 of word b // 32."""
    B, n = bits.shape
    W = lane_words(B)
    b = (jnp.asarray(bits) != 0).astype(jnp.uint32)
    b = jnp.pad(b, ((0, W * 32 - B), (0, 0))).reshape(W, 32, n)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    # lanes occupy disjoint bits, so the sum is the OR
    return (b << shifts).sum(axis=1, dtype=jnp.uint32).T


def unpack_lanes(words: jnp.ndarray, n_lanes: int) -> jnp.ndarray:
    """(n, W) uint32 words -> (B, n) int32 {0, 1} lane indicators."""
    lanes = jnp.arange(n_lanes)
    bits = (words[:, lanes // 32] >> (lanes % 32).astype(jnp.uint32)) & 1
    return bits.T.astype(jnp.int32)


def _acc_init(n: int, prog: VertexProgram, dtype) -> jnp.ndarray:
    return jnp.full((n,), prog.ident, dtype)


# ---------------------------------------------------------------------------
# Kernel (BBCSR / Pallas) operands
# ---------------------------------------------------------------------------

def build_pull_operand(csr: CSR, *, unit_values: bool = False,
                       combine: str = "add", **bb_kwargs) -> BBCSR:
    """BBCSR of A^T — rows are *destinations*, columns are *sources* — so
    ``spmv_dma(bb, msg)`` computes exactly the engine's dense step for an
    'add' program (and ``spmspv_dma`` its sparse step).

    The tile geometry defaults to the tuned config for ``combine``'s kernel
    family on this backend and graph scale (``repro.tune``, DESIGN.md §18);
    explicit ``block_rows=`` / ``block_cols=`` / ``tile_nnz=`` kwargs win
    per key."""
    family = "bbcsr_min" if combine in ("min", "max") else "bbcsr_add"
    params = {k: _tune.resolve(f"kernels.{family}.{k}",
                               explicit=bb_kwargs.get(k), n=csr.n_rows)
              for k in ("block_rows", "block_cols", "tile_nnz")}
    params.update({k: v for k, v in bb_kwargs.items()
                   if k not in ("block_rows", "block_cols", "tile_nnz")})
    t = csr.transpose()
    if unit_values:
        t = CSR(t.indptr, t.indices, None, t.n_rows, t.n_cols)
    return to_bbcsr(t, **params)


def tile_active(bb: BBCSR, frontier: jnp.ndarray) -> jnp.ndarray:
    """(n_tiles,) int32 flags: 1 iff the tile's column block holds any active
    source vertex.  Scalar-prefetched by the SpMSpV kernel."""
    ncb = bb.n_col_blocks
    f = frontier.astype(jnp.int32)
    pad = ncb * bb.block_cols - f.shape[0]
    f = jnp.pad(f, (0, pad))
    blk = f.reshape(ncb, bb.block_cols).max(axis=1)
    return jnp.take(blk, bb.tile_cb)


# ---------------------------------------------------------------------------
# Per-level step primitives (local placement)
# ---------------------------------------------------------------------------

def _gather_rows(indptr, indices, vals, ids, k):
    """DMA-gather up to ``k`` adjacency entries per id (padding id = -1).

    Returns (cols (C, k), w (C, k) f32 — edge values or unit, valid (C, k),
    deg (C,)); the expansion behind the structured combines' push step and
    the weighted sampling step.
    """
    safe = jnp.maximum(ids, 0)
    start = jnp.take(indptr, safe)
    deg = jnp.take(indptr, safe + 1) - start
    offs = start[:, None] + jnp.arange(k, dtype=indptr.dtype)[None, :]
    valid = (jnp.arange(k)[None, :] < deg[:, None]) & (ids >= 0)[:, None]
    cols = offload.dma_gather(indices, jnp.where(valid, offs, -1))
    if vals is not None:
        w = offload.dma_gather(vals, jnp.where(valid, offs, -1))
    else:
        w = jnp.ones((ids.shape[0], k), jnp.float32)
    return cols, w, valid, deg


def _es_scores(u: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Efraimidis–Spirakis reservoir priorities: the max of u_i^(1/w_i) picks
    item i with probability w_i / sum(w); non-positive weights never win."""
    return jnp.where(w > 0, u ** (1.0 / jnp.maximum(w, 1e-30)), -jnp.inf)


def _structured_combine(idx, payload, w, n, prog: VertexProgram, key):
    """Edge-stream entry to the structured combines: idx = destination per
    item (-1 ignored), payload = int message, w = edge weight."""
    idx = jnp.where(payload >= 0, idx, -1)
    if prog.combine == "argmax_weighted":
        return offload.segment_weighted_mode(idx, payload, w, n)
    # 'sample': keyed reservoir pick — iid priorities, so the per-destination
    # argmax is a uniform pick; Efraimidis–Spirakis exponents weight the draw
    # by the edge value when edge_op='mul'.
    u = jax.random.uniform(key, idx.shape, minval=1e-7, maxval=1.0)
    score = _es_scores(u, w) if prog.edge_op == "mul" else u
    return offload.segment_argmax(idx, score, payload, n)


def _dense_step(rows, cols, vals, msg, n, prog: VertexProgram, key=None):
    """Pull direction: one edge-parallel pass over every edge."""
    if prog.structured:
        payload = jnp.take(msg, rows).astype(jnp.int32)
        w = vals if vals is not None else jnp.ones_like(rows, jnp.float32)
        return _structured_combine(cols, payload, w, n, prog, key)
    em = jnp.take(msg, rows)
    ev = _apply_edge(em, vals, prog.edge_op)
    if prog.combine == "add":
        return jax.ops.segment_sum(ev.astype(msg.dtype), cols, num_segments=n)
    return _scatter_combine(_acc_init(n, prog, msg.dtype), cols, ev,
                            prog.combine, prog.ident)


def _edge_budget(nnz: int, mode: str) -> int:
    """Static edge slots of the flat push step (:func:`_expand_frontier`).
    'auto' takes Beamer's α = 14: the power of two at or above nnz / 14,
    capped at nnz — a frontier with more out-edges pulls.  'push' takes nnz,
    so every frontier fits and that mode never falls back on edges."""
    if mode == "push":
        return max(nnz, 1)
    return max(1, min(nnz, 1 << (-(-nnz // 14) - 1).bit_length()))


def _expand_frontier(indptr, indices, vals, active, C, E):
    """Edge-budgeted push expansion: the out-edges of the ≤C active vertices,
    laid flat into ``E`` slots in frontier order, each row's edges in CSR
    order — the valid entries of a (C, max_degree) row gather, in the same
    order, with the padding dropped.  The caller keeps the frontier's
    out-edge count within ``E``.

    Every non-empty row's index is scattered at its first slot and a running
    max carries it over the row's remaining slots (no per-slot search).
    Returns (src (E,) source vertex per slot, dst (E,) destination, -1 past
    the frontier's edges, w (E,) f32 edge value or unit, valid (E,)).
    """
    ids, = jnp.nonzero(active, size=C, fill_value=-1)
    safe = jnp.maximum(ids, 0)
    start = jnp.take(indptr, safe)
    deg = jnp.where(ids >= 0, jnp.take(indptr, safe + 1) - start, 0)
    end = jnp.cumsum(deg)
    first = end - deg
    row = jnp.zeros((E,), jnp.int32).at[jnp.where(deg > 0, first, E)].max(
        jnp.arange(C, dtype=jnp.int32), mode="drop")
    row = lax.cummax(row)
    slot = jnp.arange(E, dtype=end.dtype)
    valid = slot < end[-1]
    edge = jnp.where(valid, jnp.take(start, row) + slot - jnp.take(first, row),
                     -1)
    dst = offload.dma_gather(indices, edge, fill=-1)
    w = offload.dma_gather(vals, edge) if vals is not None \
        else jnp.ones((E,), jnp.float32)
    return jnp.take(safe, row), dst, w, valid


def _sparse_step(indptr, indices, vals, msg, frontier, n, C, E,
                 prog: VertexProgram):
    """Push direction: combine the ≤C active vertices' out-edges, laid flat
    into ``E`` slots (:func:`_expand_frontier`)."""
    src, dst, w, valid = _expand_frontier(indptr, indices, vals, frontier,
                                          C, E)
    contrib = _apply_edge(jnp.take(msg, src), w.astype(msg.dtype),
                          prog.edge_op)
    contrib = jnp.where(valid, contrib, jnp.asarray(prog.ident, msg.dtype))
    return _scatter_combine(_acc_init(n, prog, msg.dtype), dst, contrib,
                            prog.combine, prog.ident)


def _structured_sparse_step(indptr, indices, vals, msg, frontier, n, C, k,
                            prog: VertexProgram, key=None):
    """Push direction for the structured combines: the ≤C active vertices'
    rows gathered as a (C, k) block, k the max degree — the keyed draws of
    'sample' are shaped by that slot layout, so it stays."""
    ids, = jnp.nonzero(frontier, size=C, fill_value=-1)
    cols, ev, valid, _ = _gather_rows(indptr, indices, vals, ids, k)
    payload = jnp.broadcast_to(
        jnp.take(msg, jnp.maximum(ids, 0)).astype(jnp.int32)[:, None], (C, k))
    idx = jnp.where(valid, cols, -1).reshape(-1)
    return _structured_combine(idx, payload.reshape(-1),
                               ev.astype(jnp.float32).reshape(-1), n, prog,
                               key)


# trace-safe: validation runs at dispatch time on a concrete BBCSR operand,
# before any trace begins — repro-lint: disable=host-sync
def _check_kernel_operand(prog: VertexProgram, kernel_bb: BBCSR) -> None:
    """Validate a Pallas operand against the program's semiring: 'add'
    accumulates val*msg on the MXU; 'min'/'max' relax msg + w with the
    masked-select tile combine ((min,+)/(max,+) — the distance semirings)."""
    if prog.combine == "add":
        if prog.edge_op == "add":
            raise ValueError("the 'add'-combine kernels compute val*msg; "
                             "edge_op 'add' has no kernel path")
        if prog.edge_op == "copy":
            v = np.asarray(kernel_bb.vals)
            if not bool(np.all((v == 0) | (v == 1))):
                raise ValueError(
                    "edge_op 'copy' needs a unit-valued kernel operand — "
                    "build it with build_pull_operand(csr, unit_values=True)")
    elif prog.combine in ("min", "max"):
        if prog.edge_op != "add":
            raise ValueError("the min/max tile combines relax msg + w: "
                             "edge_op must be 'add'")
        if kernel_bb.tile_cnt is None:
            raise ValueError("min/max tile combines need the BBCSR per-tile "
                             "padding counts — rebuild the operand with "
                             "to_bbcsr")
    else:
        raise ValueError(f"no kernel path for combine {prog.combine!r}")


# trace-safe: indptr is graph structure, concrete by the engine's contract —
# the pull happens once, pre-trace, to derive a *static* gather budget
def _max_degree(csr: CSR) -> int:  # repro-lint: disable=host-sync
    """Static max degree for gather budgets: the CSR's recorded
    ``max_degree`` (set by ``CSR.for_jit`` for a CSR passed to jit as an
    argument, its indptr traced), else derived with numpy from the concrete
    indptr so callers stay usable under jit."""
    if csr.max_degree is not None:
        return csr.max_degree
    indptr = np.asarray(csr.indptr)
    return max(int(np.diff(indptr).max()) if indptr.size > 1 else 1, 1)


def sample_neighbors(csr: CSR, queries: jnp.ndarray, key: jax.Array, *,
                     weighted: bool = False,
                     k: Optional[int] = None) -> jnp.ndarray:
    """One push-compacted step of a ``combine='sample'`` program.

    For every query slot (duplicates allowed — each slot draws independently,
    so colliding walkers stay uncorrelated) the engine picks one out-neighbor
    of that vertex.  The unweighted pick lowers the reservoir to the
    equivalent inverse-CDF draw — one random offset into the row, O(1) DMA
    per slot instead of a max-degree-padded row gather (same uniform
    distribution, and the pointer-chase access pattern the paper's random
    walks measure).  ``weighted=True`` keeps the full keyed reservoir: the
    row is DMA-gathered and the per-slot argmax of Efraimidis–Spirakis
    priorities draws proportionally to edge values.  Sinks return the query
    itself (walkers stay put, shapes stay static).

    Random walks and layered neighbor sampling are scans/loops over this one
    step — the offload machinery (DMA gather, keyed pick) is the engine's,
    the algorithms keep only their loop shape.
    """
    q = queries.astype(jnp.int32)
    safe = jnp.maximum(q, 0)
    if not weighted:
        start = jnp.take(csr.indptr, safe)
        deg = jnp.take(csr.indptr, safe + 1) - start
        r = jax.random.randint(key, q.shape, 0, 1 << 30)
        off = start + r % jnp.maximum(deg, 1)
        nbr = offload.dma_gather(csr.indices, jnp.where(deg > 0, off, -1))
        return jnp.where((deg > 0) & (q >= 0), nbr, q)
    if k is None:
        k = _max_degree(csr)
    cols, w, valid, deg = _gather_rows(csr.indptr, csr.indices, csr.values,
                                       q, k)
    u = jax.random.uniform(key, cols.shape, minval=1e-7, maxval=1.0)
    score = jnp.where(valid, _es_scores(u, w), -jnp.inf)
    pick = jnp.argmax(score, axis=1)
    nbr = jnp.take_along_axis(cols, pick[:, None], 1)[:, 0]
    return jnp.where((deg > 0) & (q >= 0), nbr, q)


# ---------------------------------------------------------------------------
# ExecutionCore: THE stepping loop (DESIGN.md §14)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutionCore:
    """One fully-lowered point of the (lane representation × placement) grid.

    The public runners *plan* (build these four callables), the core *steps*
    (the single :func:`_core_loop` below), the runners *finish* (unpack
    state/stats).  Fields:

      msg:    (state, frontier) -> messages — lane-axis handling baked in
              (vmapped for valued lanes, direct for scalar/packed).
      step:   (msg, frontier, alive, it_key) -> (acc, was_push, fallback) —
              the per-level direction decision plus the placement's
              dense/sparse (local) or push/pull (distributed) step.
              ``alive`` is the carried global active count, so the decision
              is one comparison, not a fresh reduction.
      update: (state, acc, frontier, it) -> (state, next_frontier).
      count:  frontier -> int32 global active count (psum'd under the
              distributed placement so every shard agrees).
      pace:   optional (state, frontier, it) -> (state, frontier, it) — the
              async placement's bounded-staleness hook, run at the top of
              every loop body: advance up to sync_interval-1 collective-free
              local micro-steps (deferring remote traffic into the outbox
              carried inside ``state``) before the body's globally-checked
              step.  None (the default) keeps the body fully synchronous.
      push_edges: optional frontier -> f32 out-edges of the frontier's
              vertices.  Set only on cores planned for a traced run: their
              ``step`` then returns a fourth value, the element slots the
              level's push step sized (0 on a pull level), and the loop sums
              both over push levels into ``stats['push_edges']`` /
              ``stats['push_slots']``.
    """

    msg: Callable
    step: Callable
    update: Callable
    count: Callable
    pace: Optional[Callable] = None
    push_edges: Optional[Callable] = None


def _lane_ops(prog: VertexProgram, lanes: str):
    """The lane-representation axis: how msg/update see the lane dim and how
    a frontier collapses to the per-vertex union indicator."""
    if lanes not in _LANE_REPS:
        raise ValueError(f"unknown lane representation {lanes!r}")
    if lanes == "valued":
        return (jax.vmap(prog.msg_fn),
                jax.vmap(prog.update_fn, in_axes=(0, 0, 0, None)),
                lambda f: (f > 0).any(axis=0))
    if lanes == "packed":
        return prog.msg_fn, prog.update_fn, lambda f: (f != 0).any(axis=-1)
    return prog.msg_fn, prog.update_fn, lambda f: f


def _core_loop(core: ExecutionCore, state0: Any, frontier0: jnp.ndarray, *,
               max_iters: int, key: Optional[jax.Array] = None,
               trace_len: int = 0, trace_flush: bool = False):
    """Run an :class:`ExecutionCore` to frontier exhaustion (or `max_iters`).

    This is the engine's only stepping loop (`scripts/check_single_core.py`
    guards that it stays that way): every public frontier runner lowers to
    it.  The carry holds the active-population count so the level's
    direction decision and the termination test share one reduction per
    iteration.  Returns ``(state, stats)`` with stats =
    {'iters', 'pushes', 'pulls', 'fallbacks'} (int32 scalars; wrappers drop
    the keys their placement cannot produce).

    trace_len > 0 additionally carries a ``(trace_len, 4)`` int32 per-level
    trace (DESIGN.md §17): one row ``[frontier, was_push, fallback, flush]``
    per body iteration, written on device with a drop-mode scatter (levels
    past the buffer are dropped, never clamp-overwritten) and returned as
    ``stats['trace']``.  ``frontier`` is the carried global active count
    entering the level — no new reduction — and ``flush`` mirrors
    ``was_push`` only when ``trace_flush`` is set (the async placement,
    where the globally-checked step's "push" IS the outbox flush).  The
    trace rides its own carry slot and never feeds state/frontier, so
    results are bit-identical with tracing on or off; with ``trace_len=0``
    the carry (and the compiled loop) is exactly the untraced one.  A traced
    core with ``push_edges`` also carries two f32 sums over push levels
    (f32, so neither wraps at scales where a level's block passes 2**31):
    ``push_edges``, the out-edges of the frontier entering each push level,
    and ``push_slots``, the element slots each push step sized.

    The body's phases carry ``jax.named_scope`` names (``engine.msg``,
    ``engine.update``; ``engine.push`` / ``engine.pull`` in the step), so a
    device trace can attribute op time to them; scopes are op metadata and
    leave the computation unchanged.
    """
    traced = int(trace_len) > 0
    counted = traced and core.push_edges is not None

    def cond(carry):
        it, alive = carry[2], carry[3]
        return jnp.logical_and(alive > 0, it < max_iters)

    def body(carry):
        state, frontier, it, alive, (n_push, n_pull, n_fb) = carry[:5]
        if core.pace is not None:  # async: local micro-steps first
            state, frontier, it = core.pace(state, frontier, it)
        with jax.named_scope("engine.msg"):
            msg = core.msg(state, frontier)
        it_key = jax.random.fold_in(key, it) if key is not None else None
        stepped = core.step(msg, frontier, alive, it_key)
        acc, was_push, fb = stepped[:3]
        if counted:   # the frontier entering the level, before the update
            edges = jnp.where(was_push > 0, core.push_edges(frontier), 0.0)
        with jax.named_scope("engine.update"):
            state, frontier = core.update(state, acc, frontier, it)
        out = (state, frontier, it + 1, core.count(frontier),
               (n_push + was_push, n_pull + (1 - was_push), n_fb + fb))
        if traced:
            tr, row, *work = carry[5]
            rec = jnp.stack([alive, was_push, fb,
                             was_push if trace_flush else jnp.int32(0)])
            if counted:
                work = [work[0] + edges, work[1] + stepped[3]]
            # row, not it: async pacing advances `it` by sync_interval per
            # body call, the trace records one row per global check
            out += ((tr.at[row].set(rec, mode="drop"), row + 1, *work),)
        return out

    zero = jnp.int32(0)
    carry0 = (state0, frontier0, zero, core.count(frontier0),
              (zero, zero, zero))
    if traced:
        work0 = (jnp.float32(0), jnp.float32(0)) if counted else ()
        carry0 += ((jnp.zeros((int(trace_len), 4), jnp.int32), zero)
                   + work0,)
    fin = lax.while_loop(cond, body, carry0)
    state, it, (n_push, n_pull, n_fb) = fin[0], fin[2], fin[4]
    stats = {"iters": it, "pushes": n_push, "pulls": n_pull,
             "fallbacks": n_fb}
    if traced:
        stats["trace"] = fin[5][0]
    if counted:
        stats["push_edges"], stats["push_slots"] = fin[5][2:]
    return state, stats


def _scan_steps(body, carry, xs):
    """The engine's ONE fixed-length scan call site.  Both fixed-length
    iteration shapes — `run_queue`'s per-iteration body and the async
    placement's collective-free micro-step pacing — lower to this helper, so
    the `single-core` rule's ≤1-scan budget keeps a second stepping loop from
    regrowing unnoticed (the exhaustion loop stays `_core_loop`'s
    while_loop)."""
    return lax.scan(body, carry, xs)


def _direction_step(dense, sparse, mode: str, threshold,
                    counted: bool = False, fits: Optional[Callable] = None):
    """The shared direction-switch plumbing: 'pull' always takes the dense
    step; 'push'/'auto' take the sparse step while the carried active count
    fits ``threshold`` (a frontier over the push capacity would be silently
    truncated by the size=C nonzero, so oversized levels fall back to dense —
    with push's default capacity C=n the fallback never fires).  ``fits``
    (frontier -> bool), where given, is a second test the sparse step needs:
    a level within ``threshold`` that fails it takes the dense step and is
    the step's fallback flag.

    ``sparse`` returns ``(acc, slots)``: the element slots its push block
    sized, as a number the step itself fixes.  With ``counted`` the step
    returns them (as f32; 0 on a pull level) after the usual triple;
    without, they are dropped before anything is traced for them.  The
    branches run under ``engine.push`` / ``engine.pull`` named scopes."""
    def pull(msg, frontier, it_key):
        with jax.named_scope("engine.pull"):
            acc = dense(msg, frontier, it_key)
        return (acc, jnp.float32(0)) if counted else acc

    def push(msg, frontier, it_key):
        with jax.named_scope("engine.push"):
            acc, slots = sparse(msg, frontier, it_key)
            return (acc, jnp.asarray(slots, jnp.float32)) if counted else acc

    def done(res, was_push, fb=jnp.int32(0)):
        if counted:
            return res[0], was_push, fb, res[1]
        return res, was_push, fb

    if mode == "pull":
        def step(msg, frontier, alive, it_key):
            return done(pull(msg, frontier, it_key), jnp.int32(0))
        return step

    def step(msg, frontier, alive, it_key):
        small = alive <= threshold
        fb = jnp.int32(0)
        if fits is not None:
            ok = fits(frontier)
            fb = (small & ~ok).astype(jnp.int32)
            small = small & ok
        res = lax.cond(small, lambda: push(msg, frontier, it_key),
                       lambda: pull(msg, frontier, it_key))
        return done(res, small.astype(jnp.int32), fb)
    return step


# ---------------------------------------------------------------------------
# Local placement
# ---------------------------------------------------------------------------

_DST_SORTED_CACHE: dict = {}


# trace-safe: deliberate pre-trace CSR host pull — indptr/indices are
# concrete graph structure and the sorted stream is memoized per graph
def _dst_sorted_stream(csr: CSR):  # repro-lint: disable=host-sync
    """(src, dst) edge stream sorted by destination — the packed dense step's
    presorted segment_or input.  A CSR prepared by ``CSR.for_jit`` carries
    it (the service passes its graph to jit as an argument, traced).
    Otherwise it is graph-only data derived from the concrete indptr/indices
    with numpy — concrete even under jit, like _max_degree's indptr — so the
    O(m log m) host sort is memoized per CSR (eager callers would otherwise
    pay it every call).  The cache holds *numpy* arrays: device arrays
    materialized inside a jit trace are constants of that trace, and caching
    those leaks tracers into later traces.  Keyed by object identity with a
    weakref guard, so entries die with their graph and a recycled id cannot
    alias."""
    if csr.dst_stream is not None:
        return csr.dst_stream
    key = id(csr)
    hit = _DST_SORTED_CACHE.get(key)
    if hit is None or hit[0]() is not csr:
        hit = (None,) + dst_sorted_edges(np.asarray(csr.indptr),
                                         np.asarray(csr.indices))
        try:
            ref = weakref.ref(csr,
                              lambda _, k=key: _DST_SORTED_CACHE.pop(k, None))
            hit = (ref,) + hit[1:]
            _DST_SORTED_CACHE[key] = hit
        except TypeError:
            pass  # un-weakrefable: skip caching rather than leak
    return jnp.asarray(hit[1]), jnp.asarray(hit[2])


def _local_core(csr: CSR, prog: VertexProgram, lanes: str, *, mode: str,
                C: int, k: int, E: int, kernel_bb: Optional[BBCSR],
                interpret, counted: bool = False) -> ExecutionCore:
    """Plan the local placement: lower (prog, lanes, mode) to an
    :class:`ExecutionCore` whose dense/sparse steps run on this device.

    Each sparse step returns ``(acc, slots)``: the XLA push step lays the
    frontier's out-edges flat into ``E`` slots (the structured combines
    gather ``C`` rows of ``k``, the max degree, slots), the kernel path the
    ``tile_nnz`` slots of every tile it ran.  The flat step pushes only while
    the frontier's out-edges fit ``E``.  ``counted`` (traced runs) plans the
    push counters of :func:`_core_loop`."""
    msg_of, update, union = _lane_ops(prog, lanes)
    n = csr.n_rows
    rows, cols = csr.row_ids(), csr.indices
    vals = csr.values
    if prog.edge_op == "copy":
        vals = None
    elif vals is None:
        vals = jnp.ones_like(csr.indices, jnp.float32)
    if lanes == "packed":
        p_src, p_dst = _dst_sorted_stream(csr)
    deg = csr.indptr[1:] - csr.indptr[:-1]
    flat = kernel_bb is None and not prog.structured

    def expand(frontier):
        return _expand_frontier(csr.indptr, csr.indices, vals,
                                union(frontier), C, E)

    def tile_slots(tiles):
        # traced runs only, so an untraced program holds no op for it
        if not counted:
            return 0
        return tiles.astype(jnp.float32).sum() * float(kernel_bb.tile_nnz)

    if lanes == "scalar":
        def dense(msg, frontier, it_key):
            if kernel_bb is not None:
                from ..kernels import ops as kops
                if prog.combine == "add":
                    return kops.spmv_dma(kernel_bb, msg,
                                         interpret=interpret)[:n]
                # min/max: the SpMSpV kernel with every tile active is the
                # dense pass (there is no separate dense-combine kernel)
                all_active = jnp.ones((kernel_bb.n_tiles,), jnp.int32)
                return kops.spmspv_dma(kernel_bb, msg, all_active,
                                       combine=prog.combine,
                                       interpret=interpret)[:n]
            return _dense_step(rows, cols, vals, msg, n, prog, it_key)

        def sparse(msg, frontier, it_key):
            if kernel_bb is not None:
                from ..kernels import ops as kops
                tiles = tile_active(kernel_bb, frontier)
                return kops.spmspv_dma(kernel_bb, msg, tiles,
                                       combine=prog.combine,
                                       interpret=interpret)[:n], \
                    tile_slots(tiles)
            if prog.structured:
                return _structured_sparse_step(
                    csr.indptr, csr.indices, vals, msg, frontier, n, C, k,
                    prog, it_key), C * k
            return _sparse_step(csr.indptr, csr.indices, vals, msg, frontier,
                                n, C, E, prog), E

    elif lanes == "packed":
        def dense(msg, frontier, it_key):
            return offload.segment_or(p_dst, jnp.take(msg, p_src, axis=0), n,
                                      presorted=True)

        def sparse(msg, frontier, it_key):
            src, dst, _, _ = expand(frontier)
            return offload.segment_or(dst, jnp.take(msg, src, axis=0),
                                      n), E

    else:  # valued
        def dense(msg, frontier, it_key):
            if kernel_bb is not None:
                return _kernel_lanes(kernel_bb, msg, prog,
                                     jnp.ones((kernel_bb.n_tiles,), jnp.int32),
                                     interpret)
            em = jnp.take(msg, rows, axis=1)                    # (B, m)
            ev = _apply_edge(em, vals[None, :], prog.edge_op) \
                if vals is not None else em
            if prog.combine == "add":
                return jax.ops.segment_sum(ev.T, cols, num_segments=n).T
            acc = jnp.full((n, ev.shape[0]), prog.ident, msg.dtype)
            return _scatter_combine(acc, cols, ev.T, prog.combine,
                                    prog.ident).T

        def sparse(msg, frontier, it_key):
            if kernel_bb is not None:
                tiles = tile_active(kernel_bb,
                                    union(frontier).astype(jnp.int32))
                return _kernel_lanes(kernel_bb, msg, prog, tiles,
                                     interpret), tile_slots(tiles)
            src, dst, w, valid = expand(frontier)
            em = jnp.take(msg, src, axis=1)                     # (B, E)
            contrib = _apply_edge(em, w[None, :], prog.edge_op) \
                if prog.edge_op != "copy" else em
            contrib = jnp.where(valid[None, :], contrib,
                                jnp.asarray(prog.ident, msg.dtype))
            acc = jnp.full((n, em.shape[0]), prog.ident, msg.dtype)
            return _scatter_combine(acc, dst, contrib.T, prog.combine,
                                    prog.ident).T, E

    def out_edges(frontier):
        return jnp.where(union(frontier), deg, 0).sum()

    return ExecutionCore(
        msg=msg_of,
        step=_direction_step(dense, sparse, mode, C, counted,
                             (lambda f: out_edges(f) <= E)
                             if flat and E < csr.nnz else None),
        update=update,
        count=lambda f: union(f).astype(jnp.int32).sum(),
        push_edges=(lambda f: out_edges(f).astype(jnp.float32))
        if counted else None)


def _trace_len_of(trace: bool, trace_len, max_iters, return_stats: bool) -> int:
    """Resolve the runners' (trace, trace_len) opt-in to a static buffer
    length (0 = tracing off).  The trace rides the stats dict, so tracing
    requires return_stats; the default buffer covers min(max_iters, 512)
    levels (per-level rows, so even async runs — whose `max_iters` counts
    micro-steps — rarely drop rows)."""
    if not trace:
        if trace_len is not None:
            raise ValueError("trace_len is only meaningful with trace=True")
        return 0
    if not return_stats:
        raise ValueError("trace=True returns stats['trace']: pass "
                         "return_stats=True as well")
    n = int(trace_len) if trace_len is not None else min(int(max_iters), 512)
    if n < 1:
        raise ValueError(f"trace_len must be >= 1, got {n}")
    return n


def _run_local(csr: CSR, prog: VertexProgram, lanes: str, state0, frontier0,
               *, max_iters, mode, push_capacity, kernel_bb, interpret, key,
               return_stats, trace_len: int = 0):
    """Shared local wrapper: validate, plan a local ExecutionCore, loop."""
    if mode not in ("auto", "push", "pull"):
        raise ValueError(f"mode must be 'auto', 'push' or 'pull', got {mode!r}")
    n = csr.n_rows
    k = _max_degree(csr) if mode != "pull" and prog.structured else 1
    if push_capacity is None:
        push_capacity = n if mode == "push" else max(1, n // 32)
    C = min(push_capacity, n)
    if kernel_bb is not None:
        _check_kernel_operand(prog, kernel_bb)
    core = _local_core(csr, prog, lanes, mode=mode, C=C, k=k,
                       E=_edge_budget(csr.nnz, mode), kernel_bb=kernel_bb,
                       interpret=interpret, counted=trace_len > 0)
    state, stats = _core_loop(core, state0, frontier0, max_iters=max_iters,
                              key=key, trace_len=trace_len)
    if return_stats:
        keys = ("iters", "pushes", "pulls") + \
            (("fallbacks", "trace", "push_edges", "push_slots")
             if trace_len else ())
        return state, {k_: stats[k_] for k_ in keys}
    return state


def run(csr: CSR, prog: VertexProgram, state0: Any, frontier0: jnp.ndarray, *,
        max_iters: int, mode: str = "auto", push_capacity: Optional[int] = None,
        kernel_bb: Optional[BBCSR] = None, interpret: Optional[bool] = None,
        key: Optional[jax.Array] = None, return_stats: bool = False,
        trace: bool = False, trace_len: Optional[int] = None):
    """Run `prog` to frontier exhaustion (or `max_iters`).

    The (scalar lanes, local placement) point of the ExecutionCore grid.

    Warm-start contract (the streaming-repair seed, DESIGN.md §16):
    `state0` need not be the program's cold initial state — any *feasible*
    labeling works, with `frontier0` marking the vertices whose outgoing
    relaxations might still fire.  For a monotone (min-combining) program
    the fixpoint is schedule-independent, so running from an old fixpoint
    plus a changed-endpoint frontier lands bit-identically on the
    from-scratch result (`algorithms.incremental` builds on exactly this).

    mode: 'auto' (direction-optimizing), 'push' (always sparse), 'pull'
      (always dense).  'auto' pushes while the frontier fits `push_capacity`
      vertices (default n/32) and its out-edges fit the push step's static
      edge budget (the power of two at or above nnz/14, Beamer's α), and
      pulls otherwise; a level that fits the first but not the second is a
      fallback.  'push' sizes the budget at nnz, so it never falls back.
    kernel_bb: BBCSR of A^T (see `build_pull_operand`) — routes both
      directions through the Pallas SpMV/SpMSpV kernels (combine='add' only).
    key: PRNG key, required for combine='sample' (folded per iteration).
    return_stats: also return {'iters', 'pushes', 'pulls'} taken.
    trace: with return_stats, also record the fixed-length per-level trace
      (``stats['trace']``, decoded by `repro.obs.decode_level_trace`; its
      ``fallback`` column marks the levels the edge budget sent to pull),
      their count ``stats['fallbacks']``, and the push counters
      ``stats['push_edges']`` (f32: out-edges of the frontier entering each
      push level, summed) and ``stats['push_slots']`` (f32: the element
      slots those push steps sized, the edge budget a level on the XLA
      path) — results are bit-identical trace on or off.  trace_len
      overrides the default min(max_iters, 512)-row buffer.  The push
      counters are the local placement's; the distributed runners return
      the trace alone.
    """
    if prog.combine == "or":
        raise ValueError("combine='or' is the batched bitwise combine: run it "
                         "through run_batched")
    if prog.combine == "sample" and key is None:
        raise ValueError("combine='sample' draws keyed priorities: pass key=")
    return _run_local(csr, prog, "scalar", state0, frontier0,
                      max_iters=max_iters, mode=mode,
                      push_capacity=push_capacity, kernel_bb=kernel_bb,
                      interpret=interpret, key=key, return_stats=return_stats,
                      trace_len=_trace_len_of(trace, trace_len, max_iters,
                                              return_stats))


def run_batched(csr: CSR, prog: VertexProgram, state0: Any,
                frontier0: jnp.ndarray, *, max_iters: int, mode: str = "auto",
                push_capacity: Optional[int] = None,
                kernel_bb: Optional[BBCSR] = None,
                interpret: Optional[bool] = None, return_stats: bool = False,
                trace: bool = False, trace_len: Optional[int] = None):
    """Run ``prog`` for a *batch* of sources in one pass over the graph.

    The (valued | packed lanes, local placement) points of the ExecutionCore
    grid.  PIUMA hides latency by keeping many traversals in flight per core;
    the bulk-array re-expression is MS-BFS-style lane batching: per iteration
    the engine scans the edges touched by the **union frontier** once and
    carries all B lanes' payloads through that single scan, so the
    irregular-access cost (gathers, compaction, routing) is amortized B ways.
    Two lane representations:

    * ``combine='or'`` — **bit-packed boolean lanes**: frontier and messages
      are (n, W) uint32 words, W = ceil(B/32); the destination combine is a
      bitwise OR (:func:`offload.segment_or`).  The program is written
      against packed words (state may keep unpacked per-lane planes — see
      ``bfs.msbfs_program``).
    * any scalar combine — **vmapped valued lanes**: frontier/state leaves
      are (B, n) and ``msg_fn``/``update_fn`` are the *single-source*
      functions, vmapped over the lane axis; the per-edge work is one fused
      (m, B) pass.  Results are bit-identical to B separate :func:`run`
      calls: each lane sees the same per-edge arithmetic, and lanes whose
      frontier has emptied emit combine identities (no-ops) until the whole
      batch drains.

    mode: as :func:`run`; 'auto' switches on the union frontier's population
      and out-edge counts.  kernel_bb routes the valued dense/sparse steps
      through the Pallas kernels (combine 'add', or 'min'/'max' via the
      masked-select tile combine), one lane per kernel launch under
      ``lax.map`` with the union-frontier tile schedule shared across lanes.
    Returns the final state (leaves (B, n)); ``return_stats`` adds
    {'iters', 'pushes', 'pulls'}; ``trace``/``trace_len`` as :func:`run`
    (the per-level rows describe the shared union-frontier scan).
    """
    if prog.structured:
        raise NotImplementedError(
            "structured combines are not lane-batched: sampling is already "
            "batch-shaped (sample_neighbors), label modes are one-shot")
    packed = prog.combine == "or"
    if kernel_bb is not None and packed:
        raise ValueError("the Pallas path carries f32 payloads: bit-packed"
                         " 'or' lanes have no kernel combine")
    return _run_local(csr, prog, "packed" if packed else "valued", state0,
                      frontier0, max_iters=max_iters, mode=mode,
                      push_capacity=push_capacity, kernel_bb=kernel_bb,
                      interpret=interpret, key=None, return_stats=return_stats,
                      trace_len=_trace_len_of(trace, trace_len, max_iters,
                                              return_stats))


def _kernel_lanes(bb: BBCSR, msg: jnp.ndarray, prog: VertexProgram,
                  tile_sched: jnp.ndarray, interpret) -> jnp.ndarray:
    """One Pallas SpMV/SpMSpV launch per lane (lax.map keeps it a single
    compilation), sharing the union-frontier tile schedule: a tile inactive
    for every lane is skipped for all of them, and lanes inactive on an
    active tile contribute combine identities."""
    from ..kernels import ops as kops
    n = bb.n_rows

    def one(msg_b):
        return kops.spmspv_dma(bb, msg_b, tile_sched, combine=prog.combine,
                               interpret=interpret)[:n]

    return lax.map(one, msg)


# ---------------------------------------------------------------------------
# Multi-level pipeline (hierarchy of coarsened graphs)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Hierarchy:
    """A chain of coarsening maps from a :func:`run_multilevel` run.

    ``maps[l]`` is the (n_l,) int32 map from level-l vertex to its level-(l+1)
    supernode, so the hierarchy is itself a graph-of-graphs: the level-(l+1)
    graph is the level-l graph contracted along ``maps[l]``.
    """

    maps: tuple

    @property
    def n_levels(self) -> int:
        return len(self.maps)

    def project(self, top: jnp.ndarray) -> jnp.ndarray:
        """Pull a per-vertex array at the *top* (coarsest) level down to level
        0 by composing the maps: out[v] = top[maps[-1][... maps[0][v]]]."""
        x = jnp.asarray(top)
        for m in reversed(self.maps):
            x = jnp.take(x, jnp.asarray(m), axis=0)
        return x


# trace-safe: deliberately host-driven — each level's shapes depend on the
# previous level's readback, so the float() syncs ARE the control flow
def run_multilevel(csr: CSR, level_fn: Callable, contract_fn: Callable,  # repro-lint: disable=host-sync
                   score_fn: Callable, *, max_levels: int = 10,
                   tol: float = 1e-4):
    """Generic cluster-then-contract level pipeline (multi-level Louvain's
    loop shape, program-agnostic — the distributed driver runs this same
    loop with sharded closures).

    Per level: ``level_fn(g, level) -> (n_l,) assignment`` (typically an
    engine :func:`run` whose VertexProgram state re-seeds from the coarse
    identity labeling — the level pipeline reuses one program across every
    level), ``score_fn(g, assign) -> float`` scores the raw assignment (for
    Louvain: modularity, invariant to both label renumbering and
    contraction, so the per-level score *is* the level-0 score of the
    projected labels), and — only if the level is accepted —
    ``contract_fn(g, assign) -> (coarse_g, renumber)`` collapses it.

    Stall criterion: a level is **accepted only if it improves the score by
    more than ``tol``** over the previous accepted level (level 0 must beat
    the singleton baseline ``score_fn(csr, arange)``); the first
    non-improving level is discarded — without paying for its contraction —
    and the loop stops, so the returned per-level score trace is strictly
    increasing by construction.  Also stops when a level no longer shrinks
    the graph.

    Host-driven loop (each level's shapes are data-dependent); the per-level
    work inside ``level_fn`` stays jitted engine machinery.

    Returns ``(labels0, hierarchy, scores)``: the level-0 projection of the
    final clustering, the :class:`Hierarchy` of accepted coarsening maps, and
    the accepted levels' scores.
    """
    g = csr
    maps, scores = [], []
    q_prev = float(score_fn(g, jnp.arange(g.n_rows, dtype=jnp.int32)))
    for level in range(max_levels):
        assign = level_fn(g, level)
        q = float(score_fn(g, assign))
        if not np.isfinite(q) or q <= q_prev + tol:
            break
        coarse, renumber = contract_fn(g, assign)
        maps.append(renumber)
        scores.append(q)
        q_prev = q
        no_shrink = coarse.n_rows >= g.n_rows
        g = coarse
        if no_shrink:
            break
    hier = Hierarchy(tuple(maps))
    n_top = g.n_rows if maps else csr.n_rows
    labels0 = hier.project(jnp.arange(n_top, dtype=jnp.int32))
    return labels0, hier, scores


# ---------------------------------------------------------------------------
# Distributed placement (owns the shard_map/ATT boilerplate, once)
# ---------------------------------------------------------------------------

def _axes_list(axis: AxisName):
    return [axis] if isinstance(axis, str) else list(axis)


def _spec(axis: AxisName) -> P:
    return P(axis) if isinstance(axis, str) else P(tuple(axis))


def _axis_key(axis: AxisName):
    return axis if isinstance(axis, str) else tuple(axis)


def _mesh_key(mesh):
    try:
        hash(mesh)
        return mesh
    except TypeError:
        return id(mesh)


# trace-safe: ATT boundaries are concrete placement metadata fixed at mesh
# setup; the pull makes them a hashable compile-cache key component
def _att_key(att: ATT):  # repro-lint: disable=host-sync
    return (att.kind, att.n_global, att.n_shards,
            tuple(np.asarray(att.boundaries).tolist()))


_MAPPED_CACHE: dict = {}
_MAPPED_CACHE_MAX = 64


def cached_mapped(key, build, *, ident=None):
    """One keying scheme for every compiled ``shard_map`` wrapper in the repo
    — the engine's distributed placements and the algorithm layer's sweeps
    (louvain) share this cache, so re-tracing never dominates wall clock on
    forced multi-device hosts.

    ``key`` must capture every *structural* input baked into the closure
    (mesh, axis, ATT rule, capacities, operand shapes/dtypes, flags);
    ``ident`` guards the parts a tuple key cannot: the entry only hits while
    ``ident`` is the *same object* (e.g. the VertexProgram whose closures the
    shard function captured — a rebuilt program rebuilds the wrapper, so a
    different delta/damping baked into an update_fn can never be served
    stale).  Entries hold strong refs, bounded FIFO at ``_MAPPED_CACHE_MAX``.
    """
    hit = _MAPPED_CACHE.get(key)
    if hit is not None and hit[0] is ident:
        return hit[1]
    while len(_MAPPED_CACHE) >= _MAPPED_CACHE_MAX:
        _MAPPED_CACHE.pop(next(iter(_MAPPED_CACHE)))
    fn = build()
    _MAPPED_CACHE[key] = (ident, fn)
    return fn


def _shard_apply(mesh: Mesh, axis: AxisName, shard_fn, operands, *,
                 check_rep: bool = False, cache_key=None, ident=None):
    """The shard_map boilerplate, owned once: spec construction, the
    stacked-operand in_specs, pytree-broadcast out_specs, and (optionally)
    the `cached_mapped` wrapper reuse."""
    spec = _spec(axis)

    def build():
        # check_rep=False (default): this jax has no replication rule for
        # while_loop with a psum in its cond; outputs are per-shard anyway.
        return shard_map(shard_fn, mesh=mesh, in_specs=(spec,) * len(operands),
                         out_specs=spec, check_rep=check_rep)

    mapped = build() if cache_key is None else cached_mapped(cache_key, build,
                                                             ident=ident)
    return mapped(*operands)


def frontier_edge_capacity(m: int, switch_frac: float, *,
                           slack: Optional[float] = None,
                           n: Optional[int] = None) -> int:
    """Per-peer routing capacity for the compacted sparse push.

    While the engine is in the push regime the frontier holds at most
    ``switch_frac * n`` vertices, so with edges spread uniformly a shard sees
    ≈ ``switch_frac * m`` active edges; ``slack`` covers degree skew.  Levels
    that overflow this capacity fall back to full-capacity routing at
    runtime, so the rule trades traffic (capacity shrinks with the frontier
    bound) against fallback frequency — see DESIGN.md §7 and
    `traffic.push_level_route_bytes` for the byte model the capacity feeds.

    slack: None takes the tuned ``engine.push_slack`` (``repro.tune``) for
    this backend and graph scale; ``n`` (global vertex count) keys that
    lookup when the caller knows it.
    """
    slack = _tune.resolve("engine.push_slack", explicit=slack, n=n)
    return max(1, min(m, int(m * switch_frac * slack)))


def _active_edge_mask(src, frontier, att: ATT):
    """Per-shard mask of edges whose (owned) source is in the frontier —
    computed once per level and shared by the overflow count and the
    compaction (they sit on opposite sides of a `lax.cond`, so CSE across
    the boundary is not guaranteed)."""
    local_src = jnp.where(src >= 0, att.local(jnp.maximum(src, 0)), 0)
    return (src >= 0) & (jnp.take(frontier, local_src) > 0)


def _compact_slots(valid, cap: int):
    """Stable compaction order shared by the frontier push (active edges into
    the routing capacity) and the queue runner (live entries to a prefix):
    positions of the True entries, in order, padded with -1 to ``cap``."""
    slots, = jnp.nonzero(valid, size=cap, fill_value=-1)
    return slots


def _compact_active_edges(src, dst, val, active, cap: int):
    """Frontier-proportional payload: keep only the edges the `active` mask
    names, compacted into ``cap`` slots (the distributed analogue of the
    local push step's frontier extraction).
    Returns (src, dst, val) of length ``cap``, padded with src = dst = -1.
    """
    slots = _compact_slots(active, cap)
    ssafe = jnp.maximum(slots, 0)
    keep = slots >= 0
    return (jnp.where(keep, jnp.take(src, ssafe), -1),
            jnp.where(keep, jnp.take(dst, ssafe), -1),
            jnp.where(keep, jnp.take(val, ssafe), 0.0))


def _push_step_shard(src, dst, val, msg, att: ATT, axis, prog: VertexProgram,
                     capacity: int):
    """Scalar-lane push: owner of src computes contributions locally,
    remote-combines at the dst owner (PIUMA remote atomic).  ``capacity`` is
    the per-peer routing budget — ``_route`` moves O(S * capacity) bytes, so
    a compacted edge list with a small capacity makes the level's traffic
    proportional to the active frontier instead of the full edge partition."""
    local_src = jnp.where(src >= 0, att.local(jnp.maximum(src, 0)), 0)
    gidx = jnp.where(src >= 0, dst, -1)
    if prog.structured:
        payload = offload.dma_gather(msg, local_src, fill=-1).astype(jnp.int32)
        payload = jnp.where(src >= 0, payload, -1)
        w = val if prog.edge_op == "mul" else jnp.ones_like(val)
        if prog.combine != "argmax_weighted":
            raise NotImplementedError(
                "distributed combine='sample' is queue-shaped work: run it "
                "through run_queue / sample_neighbors instead")
        return offload.remote_scatter_weighted_mode(
            att.per_shard, gidx, payload, w, att, axis, capacity=capacity)
    em = offload.dma_gather(msg, local_src, fill=prog.ident)
    em = jnp.where(src >= 0, em, jnp.asarray(prog.ident, msg.dtype))
    ev = _apply_edge(em, val, prog.edge_op) if prog.edge_op != "copy" else em
    ev = jnp.where(src >= 0, ev, jnp.asarray(prog.ident, msg.dtype))
    acc = _acc_init(att.per_shard, prog, msg.dtype)
    if prog.combine == "add":
        return offload.remote_scatter_add(acc, gidx, ev, att, axis,
                                          capacity=capacity)
    return offload.remote_scatter_combine(acc, gidx, ev, att, axis,
                                          combine=prog.combine,
                                          identity=prog.ident,
                                          capacity=capacity)


def _batched_push_step(att: ATT, axis, prog: VertexProgram, packed: bool):
    """Lane-batched push: one routed item per active edge carries **all** B
    lanes (packed words or a trailing valued lane dim) to the dst owner."""

    def push_step(csrc, cdst, cval, msg, cap):
        gidx = jnp.where(csrc >= 0, cdst, -1)
        lsrc = jnp.where(csrc >= 0, att.local(jnp.maximum(csrc, 0)), -1)
        if packed:
            em = offload.dma_gather(msg, lsrc, fill=0).astype(jnp.uint32)
            return offload.remote_scatter_or(att.per_shard, gidx, em,
                                             att, axis, capacity=cap)
        em = offload.dma_gather(msg.T, lsrc, fill=prog.ident)  # (m, B)
        ev = _apply_edge(em, cval[:, None], prog.edge_op) \
            if prog.edge_op != "copy" else em
        ev = jnp.where((csrc >= 0)[:, None], ev,
                       jnp.asarray(prog.ident, em.dtype))
        B = msg.shape[0]
        if prog.combine == "add":
            acc = offload.remote_scatter_add(
                jnp.zeros((att.per_shard, B), msg.dtype), gidx, ev,
                att, axis, capacity=cap)
        else:
            acc = offload.remote_scatter_combine(
                jnp.full((att.per_shard, B), prog.ident, msg.dtype),
                gidx, ev, att, axis, combine=prog.combine,
                identity=prog.ident, capacity=cap)
        return acc.T                                           # (B, per)

    return push_step


def _push_dispatch(push_step, src, dst, val, att: ATT, axes, union,
                   edge_cap: int, m_fwd: int, compact: bool):
    """The §7 compaction plumbing, shared by every distributed lane rep:
    when the globally-agreed active-edge count fits ``edge_cap`` the level
    routes a compacted edge list at that small capacity; overflowing levels
    fall back to full-capacity routing (the fallback counter's numerator)."""

    @jax.named_scope("engine.push")
    def push(msg, frontier):
        if not compact:
            return push_step(src, dst, val, msg, m_fwd), jnp.int32(0)
        active = _active_edge_mask(src, union(frontier), att)
        # every shard must take the same branch: reduce the overflow flag
        over = offload.hierarchical_psum(
            (active.astype(jnp.int32).sum() > edge_cap
             ).astype(jnp.int32), axes)

        def compacted():
            csrc, cdst, cval = _compact_active_edges(src, dst, val, active,
                                                     edge_cap)
            return push_step(csrc, cdst, cval, msg, edge_cap)

        acc = lax.cond(over == 0, compacted,
                       lambda: push_step(src, dst, val, msg, m_fwd))
        return acc, (over > 0).astype(jnp.int32)

    return push


def _pull_step_shard(own, remote, val, msg, att_in: ATT, att_out: ATT, axis,
                     prog: VertexProgram, capacity: int, gather_mode: str):
    """Pull: owner of the *output* vertex fetches messages from the input
    owners (fine-grained dgas_gather, or the all_gather baseline) and reduces
    locally."""
    gidx = jnp.where(remote >= 0, remote, -1)
    if prog.structured:
        if prog.combine != "argmax_weighted":
            raise NotImplementedError(
                "distributed combine='sample' is queue-shaped work: run it "
                "through run_queue / sample_neighbors instead")
        payload = offload.dgas_gather(msg, gidx, att_in, axis,
                                      capacity=capacity, fill=-1)
        payload = payload.astype(jnp.int32)
        w = val if prog.edge_op == "mul" else jnp.ones_like(val)
        local_own = jnp.where(own >= 0, att_out.local(jnp.maximum(own, 0)), -1)
        return offload.segment_weighted_mode(local_own, payload, w,
                                             att_out.per_shard)
    if gather_mode == "dgas":
        em = offload.dgas_gather(msg, gidx, att_in, axis, capacity=capacity,
                                 fill=prog.ident)
    else:
        em = offload.all_gather_gather(msg, gidx, att_in, axis, fill=prog.ident)
    ev = _apply_edge(em, val, prog.edge_op) if prog.edge_op != "copy" else em
    ev = jnp.where(own >= 0, ev, jnp.asarray(prog.ident, msg.dtype))
    local_own = jnp.where(own >= 0, att_out.local(jnp.maximum(own, 0)), -1)
    acc = _acc_init(att_out.per_shard, prog, msg.dtype)
    if prog.combine == "add":
        return offload.dma_scatter_add(acc, local_own, ev)
    return _scatter_combine(acc, local_own, ev, prog.combine, prog.ident)


def _async_split(src, dst, val, att: ATT, axis, prog: VertexProgram,
                 lanes: str):
    """Plan the async placement's *split* push pass (DESIGN.md §14).

    Every resident edge is either **local** (destination owned by this shard
    — its contribution is applied to the local accumulator immediately) or
    **remote** (its contribution is folded into the dense ``(S*per, ...)``
    outbox at `ATT.flat_slot`, to be delivered by the next
    `offload.buffered_flush`).  A pass is completely collective-free, which
    is what lets the pacing scan run K of them between global checks.

    Returns ``(pass_, orient, merge, outbox0)``:
      pass_(msg, outbox) -> (acc, outbox) — acc is the (per[, lanes]) local
        accumulator in scatter layout, outbox the updated deferred buffer.
      orient(acc) — scatter layout -> the update_fn's lane layout
        (transpose for valued lanes, identity otherwise).
      merge(a, b) — the program's combine, elementwise (folds flushed
        arrivals into the local accumulator; both in scatter layout).
      outbox0(msg_aval) -> identity-filled outbox for the msg shape/dtype.
    """
    per = att.per_shard
    me = offload.my_shard(axis)
    in_range = src >= 0
    lsrc = jnp.where(in_range, att.local(jnp.maximum(src, 0)), -1)
    d_safe = jnp.maximum(dst, 0)
    is_local = in_range & (att.owner(d_safe) == me)
    lidx = jnp.where(is_local, att.local(d_safe), -1)
    ridx = jnp.where(in_range & ~is_local, att.flat_slot(d_safe), -1)

    if lanes == "packed":
        def pass_(msg, outbox):
            em = offload.dma_gather(msg, lsrc, fill=0).astype(jnp.uint32)
            acc = offload.segment_or(lidx, em, per)
            outbox = outbox | offload.segment_or(ridx, em, outbox.shape[0])
            return acc, outbox

        orient = lambda a: a
        merge = jnp.bitwise_or
    else:
        def pass_(msg, outbox):
            flat = msg.T if lanes == "valued" else msg       # gather by row
            em = offload.dma_gather(flat, lsrc, fill=prog.ident)
            ev = val if lanes == "scalar" else val[:, None]
            ev = _apply_edge(em, ev, prog.edge_op) \
                if prog.edge_op != "copy" else em
            mask = in_range if lanes == "scalar" else in_range[:, None]
            ev = jnp.where(mask, ev, jnp.asarray(prog.ident, em.dtype))
            acc0 = jnp.full((per,) + ev.shape[1:], prog.ident, em.dtype)
            acc = _scatter_combine(acc0, lidx, ev, prog.combine, prog.ident)
            outbox = _scatter_combine(outbox, ridx, ev, prog.combine,
                                      prog.ident)
            return acc, outbox

        orient = (lambda a: a.T) if lanes == "valued" else (lambda a: a)
        merge = {"add": jnp.add, "min": jnp.minimum,
                 "max": jnp.maximum}[prog.combine]

    def outbox0(msg_aval):
        if lanes == "packed":
            return jnp.zeros((att.n_shards * per,) + tuple(msg_aval.shape[1:]),
                             jnp.uint32)
        trail = (msg_aval.shape[0],) if lanes == "valued" else ()
        return jnp.full((att.n_shards * per,) + trail, prog.ident,
                        msg_aval.dtype)

    return pass_, orient, merge, outbox0


def reverse_graph(csr: CSR, att: ATT) -> ShardedGraph:
    """Shard the *transposed* edge list by destination owner (= `att`, the
    vertex rule) for the distributed pull direction."""
    from .algorithms.distgraph import shard_graph
    g_rev, _ = shard_graph(csr.transpose(), att.n_shards, row_att=att)
    return g_rev


def _run_distributed(g: ShardedGraph, att: ATT, mesh: Mesh,
                     prog: VertexProgram, state0: Any, frontier0: jnp.ndarray,
                     *, lanes: str, axis, max_iters: int, mode: str,
                     switch_frac: Optional[float], push_edge_capacity,
                     g_rev, return_stats: bool, placement: str = "sync",
                     sync_interval: int = 1, trace_len: int = 0):
    """Shared distributed wrapper: plan a sharded ExecutionCore and run the
    single stepping loop inside one shard_map (cached via `cached_mapped`).

    placement 'sync' is the per-level bulk-synchronous engine; 'async' is the
    bounded-staleness variant: sync_interval-1 collective-free local
    micro-steps (`_async_split` + the pacing scan) between global checks,
    each check being one `offload.buffered_flush` + the termination psum.
    """
    if placement not in ("sync", "async"):
        raise ValueError(
            f"placement must be 'sync' or 'async', got {placement!r}")
    if placement == "async":
        if prog.structured:
            raise NotImplementedError(
                "the async placement defers messages in a dense combine "
                "outbox: structured combines (argmax_weighted/sample) have "
                "no identity-mergeable buffer entry")
        if mode != "push":
            raise ValueError("the async placement paces the split "
                             "local/remote push pass: mode must be 'push'")
        if sync_interval < 1:
            raise ValueError(f"sync_interval must be >= 1, got {sync_interval}")
    axis = axis if axis is not None else mesh.axis_names[0]
    axes = _axes_list(axis)
    # tuned-config funnel (DESIGN.md §18): a caller's explicit switch_frac /
    # push_edge_capacity wins; None consults TUNED.json for this backend and
    # graph scale, then the hand-picked default
    switch_frac = _tune.resolve("engine.switch_frac",
                                explicit=switch_frac, n=att.n_global)
    switch_count = max(1, int(att.n_global * switch_frac))
    state_leaves, state_def = jax.tree.flatten(state0)
    n_state = len(state_leaves)
    use_rev = g_rev is not None
    m_fwd = g.edges_per_shard
    m_rev = g_rev.edges_per_shard if use_rev else 0
    if push_edge_capacity is None:
        edge_cap = frontier_edge_capacity(m_fwd, switch_frac,
                                          n=att.n_global)
    else:
        edge_cap = int(push_edge_capacity)
    compact = mode != "pull" and 0 < edge_cap < m_fwd

    def shard_fn(src, dst, val, rsrc, rdst, rval, frontier, *leaves):
        src, dst, val = src[0], dst[0], val[0]
        rsrc, rdst, rval = rsrc[0], rdst[0], rval[0]
        frontier = frontier[0]
        state = jax.tree.unflatten(state_def, [l[0] for l in leaves])
        msg_of, update, union = _lane_ops(prog, lanes)

        def count(f):
            return offload.hierarchical_psum(
                union(f).astype(jnp.int32).sum(), axes)

        if placement == "async":
            # Bounded-staleness pacing: the carried state is (state, outbox).
            # Each loop body = sync_interval-1 collective-free split passes
            # (pace), then one globally-checked step: split pass + one
            # buffered_flush delivering every deferred remote contribution +
            # the termination psum.  The outbox is always fully drained
            # before `count`, so alive == 0 means global quiescence.
            pass_, orient, merge, outbox0 = _async_split(src, dst, val, att,
                                                         axis, prog, lanes)
            box_id = outbox0(jax.eval_shape(msg_of, state, frontier))

            def amsg(wrapped, f):
                st, box = wrapped
                return msg_of(st, f), box

            def astep(msg_box, f, alive, it_key):
                m, box = msg_box
                acc, box = pass_(m, box)
                arrived = offload.buffered_flush(box, axis,
                                                 combine=prog.combine)
                # stats: 'pushes' counts flushes under the async placement
                return orient(merge(acc, arrived)), jnp.int32(1), jnp.int32(0)

            def aupdate(wrapped, acc, f, it):
                st, _ = wrapped
                st, f = update(st, acc, f, it)
                return (st, box_id), f  # flushed: fresh identity outbox

            def apace(wrapped, f, it):
                st, box = wrapped

                def micro(carry, step_it):
                    st_, box_, f_ = carry
                    acc_, box_ = pass_(msg_of(st_, f_), box_)
                    st_, f_ = update(st_, orient(acc_), f_, step_it)
                    return (st_, box_, f_), None

                (st, box, f), _ = _scan_steps(
                    micro, (st, box, f), it + jnp.arange(sync_interval - 1))
                return (st, box), f, it + jnp.int32(sync_interval - 1)

            core = ExecutionCore(
                msg=amsg, step=astep, update=aupdate, count=count,
                pace=apace if sync_interval > 1 else None)
            state = (state, box_id)
        else:
            if lanes == "scalar":
                def push_step(s, d, v, msg, cap):
                    return _push_step_shard(s, d, v, msg, att, axis, prog,
                                            capacity=cap)
            else:
                push_step = _batched_push_step(att, axis, prog,
                                               packed=lanes == "packed")
            push = _push_dispatch(push_step, src, dst, val, att, axes, union,
                                  edge_cap, m_fwd, compact)

            @jax.named_scope("engine.pull")
            def pull(msg):
                # g_rev rows: src = output vertex (owned here), dst = input
                return _pull_step_shard(rsrc, rdst, rval, msg, att, att, axis,
                                        prog, capacity=m_rev,
                                        gather_mode="dgas")

            if mode == "push":
                def step(msg, frontier, alive, it_key):
                    acc, fb = push(msg, frontier)
                    return acc, jnp.int32(1), fb
            elif mode == "pull":
                def step(msg, frontier, alive, it_key):
                    return pull(msg), jnp.int32(0), jnp.int32(0)
            else:
                def step(msg, frontier, alive, it_key):
                    def do_push():
                        acc, fb = push(msg, frontier)
                        return acc, jnp.int32(1), fb
                    return lax.cond(
                        alive <= switch_count, do_push,
                        lambda: (pull(msg), jnp.int32(0), jnp.int32(0)))

            core = ExecutionCore(msg=msg_of, step=step, update=update,
                                 count=count)
        state, stats = _core_loop(core, state, frontier, max_iters=max_iters,
                                  trace_len=trace_len,
                                  trace_flush=placement == "async")
        if placement == "async":
            state = state[0]  # drop the (drained) outbox
        out = tuple(l[None] for l in jax.tree.leaves(state))
        if return_stats:
            out = out + tuple(stats[k][None] for k in
                              ("iters", "pushes", "pulls", "fallbacks"))
            if trace_len:   # rows are globally-agreed: every shard identical
                out = out + (stats["trace"][None],)
        return out

    if not use_rev:  # placeholder operands keep the shard_map arity static
        z = jnp.full((att.n_shards, 1), -1, jnp.int32)
        rsrc, rdst, rval = z, z, jnp.zeros((att.n_shards, 1), jnp.float32)
    else:
        rsrc, rdst, rval = g_rev.src, g_rev.dst, g_rev.val

    operands = (g.src, g.dst, g.val, rsrc, rdst, rval, frontier0,
                *state_leaves)
    cache_key = ("core", _mesh_key(mesh), _axis_key(axis), _att_key(att),
                 (lanes, mode, int(max_iters), float(switch_frac), edge_cap,
                  compact, use_rev, m_fwd, m_rev, return_stats, state_def,
                  placement, int(sync_interval), int(trace_len)),
                 tuple((tuple(x.shape), str(x.dtype)) for x in operands))
    out = _shard_apply(mesh, axis, shard_fn, operands, cache_key=cache_key,
                       ident=prog)
    state = jax.tree.unflatten(state_def, list(out[:n_state]))
    if return_stats:
        keys = ("iters", "pushes", "pulls", "fallbacks")
        stats = dict(zip(keys, out[n_state:n_state + len(keys)]))
        if trace_len:
            stats["trace"] = out[n_state + len(keys)]
        return state, stats
    return state


def run_distributed(g: ShardedGraph, att: ATT, mesh: Mesh,
                    prog: VertexProgram, state0: Any, frontier0: jnp.ndarray,
                    *, axis: Optional[AxisName] = None, max_iters: int,
                    g_rev: Optional[ShardedGraph] = None, mode: str = "push",
                    switch_frac: Optional[float] = None,
                    push_edge_capacity: Optional[int] = None,
                    return_stats: bool = False, placement: str = "sync",
                    sync_interval: Optional[int] = None,
                    trace: bool = False, trace_len: Optional[int] = None):
    """Distributed loop; `state0`/`frontier0` are stacked (S, per) per `att`.

    The (scalar lanes, distributed placement) point of the ExecutionCore
    grid.

    mode: 'push' (every level scatters via remote atomics — the seed
      behavior), 'pull' (requires `g_rev`; every level gathers via dgas), or
      'auto' (push while the globally-psum'd frontier is below
      `switch_frac * n`, pull once it saturates — Beamer's heuristic).
    switch_frac: the 'auto' switch threshold (and the capacity derivation's
      frontier bound).  None resolves the tuned value for this backend and
      graph scale, then the hand-picked 1/32 (``repro.tune``, DESIGN.md §18).
    placement: 'sync' (one global reduction per level) or 'async'
      (bounded-staleness pacing: each shard runs `sync_interval` local
      micro-steps per global check, deferring cross-shard messages into a
      dense combine outbox flushed once per check — requires mode='push' and
      a non-structured combine; fixpoints are bit-identical to 'sync' for
      monotone programs, see DESIGN.md §14).
    sync_interval: local micro-steps per global check under 'async'
      (default 8; 1 = flush every step, which reproduces the sync schedule).
    push_edge_capacity: per-peer routing capacity for the *compacted* push
      step.  When a level's globally-agreed active-edge count fits, the shard
      compacts active edges with nonzero-into-capacity and routes at this
      small capacity, so sparse levels move O(active edges) bytes instead of
      the full edge partition; overflowing levels fall back to full-capacity
      routing.  None derives `frontier_edge_capacity(m, switch_frac)`; 0
      disables compaction (the seed behavior).
    return_stats: also return {'iters', 'pushes', 'pulls', 'fallbacks'} —
      (S,) int32 arrays, identical on every shard (globally reduced);
      'fallbacks' counts the push levels whose active-edge count overflowed
      the compacted capacity (the §7 fallback rate's numerator).
    trace: with return_stats, record the per-level device trace
      (``stats['trace']``, stacked (S, trace_len, 4) and identical on every
      shard — the rows are built from globally-agreed quantities); under
      'async' each row is one global check and the flush column fires.
    Returns the final state pytree, stacked (S, per).
    """
    if mode not in ("auto", "push", "pull"):
        raise ValueError(f"mode must be 'auto', 'push' or 'pull', got {mode!r}")
    if prog.combine == "or":
        raise ValueError("combine='or' is the batched bitwise combine: run it "
                         "through run_batched_distributed")
    if mode in ("pull", "auto") and g_rev is None:
        raise ValueError(f"mode={mode!r} needs g_rev (see reverse_graph)")
    if sync_interval is None:
        sync_interval = 8 if placement == "async" else 1
    return _run_distributed(g, att, mesh, prog, state0, frontier0,
                            lanes="scalar", axis=axis, max_iters=max_iters,
                            mode=mode, switch_frac=switch_frac,
                            push_edge_capacity=push_edge_capacity,
                            g_rev=g_rev, return_stats=return_stats,
                            placement=placement,
                            sync_interval=int(sync_interval),
                            trace_len=_trace_len_of(trace, trace_len,
                                                    max_iters, return_stats))


def run_batched_distributed(g: ShardedGraph, att: ATT, mesh: Mesh,
                            prog: VertexProgram, state0: Any,
                            frontier0: jnp.ndarray, *,
                            axis: Optional[AxisName] = None, max_iters: int,
                            switch_frac: Optional[float] = None,
                            push_edge_capacity: Optional[int] = None,
                            return_stats: bool = False,
                            placement: str = "sync",
                            sync_interval: Optional[int] = None,
                            trace: bool = False,
                            trace_len: Optional[int] = None):
    """Distributed batched loop: B concurrent traversals, one push pipeline.

    The (valued | packed lanes, distributed placement) points of the
    ExecutionCore grid.  Lane layouts (leading dim S = shard, matching
    :func:`run_batched`):

    * packed (``combine='or'``): frontier0 is (S, per, W) uint32 words and
      the program operates on per-shard (per, W) words directly; the remote
      combine is :func:`offload.remote_scatter_or` at the dst owner.
    * valued: frontier0 is (S, B, per) int32, state leaves (S, B, ...), and
      the single-source ``msg_fn``/``update_fn`` are vmapped over the lane
      axis (collectives inside the program — e.g. SSSP's global bucket min —
      batch elementwise across lanes).

    Every level runs the push direction with the §7 active-edge compaction
    driven by the **union** frontier, so one compacted exchange carries all
    B lanes: a routed item is (idx, validity, B-lane payload) —
    `traffic.batched_payload_bytes` is the byte model, vs B single-source
    exchanges at `ROUTE_PAYLOAD_BYTES` each.  Levels whose active-edge count
    overflows the capacity fall back to full-capacity routing (counted in
    ``stats['fallbacks']``), exactly as in :func:`run_distributed`.

    placement/sync_interval: as :func:`run_distributed` — 'async' paces each
    shard through `sync_interval` local micro-steps per global check (the
    batched engine is already push-only, so every batched program with a
    monotone combine qualifies; under 'async' stats count micro-steps in
    'iters' and buffered flushes in 'pushes').

    Returns the final state pytree stacked (S, ...); ``return_stats`` adds
    {'iters', 'pushes', 'pulls', 'fallbacks'} ((S,) int32, identical on
    every shard; 'pulls' is always 0 — the batched distributed engine is
    push-only).  ``trace``/``trace_len`` as :func:`run_distributed`.
    """
    if prog.structured:
        raise NotImplementedError(
            "structured combines are not lane-batched: sampling is already "
            "batch-shaped (sample_neighbors / run_queue)")
    packed = prog.combine == "or"
    if sync_interval is None:
        sync_interval = 8 if placement == "async" else 1
    return _run_distributed(g, att, mesh, prog, state0, frontier0,
                            lanes="packed" if packed else "valued",
                            axis=axis, max_iters=max_iters, mode="push",
                            switch_frac=switch_frac,
                            push_edge_capacity=push_edge_capacity,
                            g_rev=None, return_stats=return_stats,
                            placement=placement,
                            sync_interval=int(sync_interval),
                            trace_len=_trace_len_of(trace, trace_len,
                                                    max_iters, return_stats))


def spmv_pass(g: ShardedGraph, x_sharded: jnp.ndarray, x_att: ATT,
              row_att: ATT, mesh: Mesh, *, axis: Optional[AxisName] = None,
              mode: str = "dgas") -> jnp.ndarray:
    """One distributed engine pull step == y = A @ x (rows per `row_att`,
    x per `x_att`).  `spmv_distributed` delegates here; kept in the engine so
    SpMV shares the exact same shard step as every frontier algorithm."""
    axis = axis if axis is not None else mesh.axis_names[0]
    prog = VertexProgram(edge_op="mul", combine="add",
                         msg_fn=lambda s, f: s, update_fn=None)

    def shard_fn(src, dst, val, x_local):
        return _pull_step_shard(src[0], dst[0], val[0], x_local[0],
                                x_att, row_att, axis, prog,
                                capacity=g.edges_per_shard,
                                gather_mode=mode)[None]

    return _shard_apply(mesh, axis, shard_fn, (g.src, g.dst, g.val, x_sharded),
                        check_rep=True)


# ---------------------------------------------------------------------------
# Queue-driven programs (the second program family: work entries, not bitmaps)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class QueueProgram:
    """An algorithm whose unit of work is a queue entry, not a frontier bit.

    step_fn: (operands, items, payload, state, it, key)
             -> (items, payload, state, out)
      items:   (cap,) int32 queue entries, -1 = empty slot; setting an entry
               to -1 retires it (the runner re-compacts before balancing).
      payload: pytree of (cap, ...) companion data aligned with the items —
               it migrates with them through the balancer.
      out:     anything to stack per iteration (see run_queue).
    """

    step_fn: Callable


def run_queue(mesh: Mesh, prog: QueueProgram, items0: jnp.ndarray,
              payload0: Any, operands: Any, *, n_iters: int,
              axis: Optional[AxisName] = None,
              key: Optional[jax.Array] = None, state0: Any = (),
              sync_interval: int = 1):
    """Queue-driven distributed runner — shard_map plumbing owned once.

    Frontier programs are bitmap-shaped; walker / sampler workloads are a bag
    of work entries that migrate between shards — a different program family,
    so its per-iteration body is its own (a fixed-length `lax.scan`, not the
    frontier core's exhaustion loop), but the machinery around that body is
    the shared ExecutionCore plumbing: `_compact_slots` stable compaction,
    `offload.queue_balance` owner routing (the hardware queue engine's work
    stealing), `_shard_apply` shard_map wiring, per-(shard, iteration) key
    folding.

    items0:   (S, cap) int32 stacked queues, -1 = empty slot.
    payload0: pytree of (S, cap, ...) companion data riding with the items.
    operands: pytree of (S, ...) sharded arrays handed to every step
              (graph shards, lookup tables, ...).
    sync_interval: rebalance cadence (the async placement's knob for queue
      work): the queue-engine steal/balance — the body's only collective —
      runs every sync_interval-th iteration, so shards proceed at their own
      pace in between (entries still read remote data through dgas_gather
      by global id, so results stay valid; only load placement and the
      per-(shard, it) key stream differ from cadence 1).  Default 1 keeps
      the fully-balanced schedule.
    Returns (state, outs) with each `out` leaf stacked (S, n_iters, ...).
    """
    axis = axis if axis is not None else mesh.axis_names[0]
    key = key if key is not None else jax.random.PRNGKey(0)
    pl_leaves, pl_def = jax.tree.flatten(payload0)
    op_leaves, op_def = jax.tree.flatten(operands)
    st_leaves, st_def = jax.tree.flatten(state0)
    n_pl, n_op = len(pl_leaves), len(op_leaves)

    def shard_fn(items, *rest):
        items = items[0]
        payload = jax.tree.unflatten(pl_def, [l[0] for l in rest[:n_pl]])
        ops = jax.tree.unflatten(op_def, [l[0] for l in rest[n_pl:n_pl + n_op]])
        state = jax.tree.unflatten(st_def, [l[0] for l in rest[n_pl + n_op:]])
        shard_key = jax.random.fold_in(key, offload.my_shard(axis))
        cap = items.shape[0]

        def body(carry, it):
            items, payload, state = carry
            # retired entries may sit anywhere in the buffer: compact live
            # ones to a stable prefix (same machinery as the push step's
            # active-edge compaction) before balancing
            slots = _compact_slots(items >= 0, cap)
            safe = jnp.maximum(slots, 0)
            items = jnp.where(slots >= 0, jnp.take(items, safe), -1)
            payload = jax.tree.map(lambda x: jnp.take(x, safe, axis=0),
                                   payload)
            q = offload.QueueState(items,
                                   (items >= 0).sum().astype(jnp.int32))

            def balance(args):
                q_, pl_ = args
                if pl_leaves:
                    return offload.queue_balance(q_, axis, pl_)
                return offload.queue_balance(q_, axis), pl_

            if sync_interval > 1:
                # `it` is the scan index — identical on every shard — so the
                # branch is globally uniform and the collective inside the
                # cond is trace-safe (same pattern as _push_dispatch).
                q, payload = lax.cond((it % sync_interval) == 0, balance,
                                      lambda args: args, (q, payload))
            else:
                q, payload = balance((q, payload))
            items, payload, state, out = prog.step_fn(
                ops, q.items, payload, state, it,
                jax.random.fold_in(shard_key, it))
            return (items, payload, state), out

        (items, payload, state), outs = _scan_steps(
            body, (items, payload, state), jnp.arange(n_iters))
        return jax.tree.map(lambda l: l[None], (state, outs))

    return _shard_apply(mesh, axis, shard_fn,
                        (items0, *pl_leaves, *op_leaves, *st_leaves))
