"""Offload engines: DMA gather/scatter, remote atomics, queues, collectives.

PIUMA blocks contain engines that execute memory operations *in the background,
where the data lives*:

* DMA engine      — (strided) copy / gather / scatter between memory and SPAD
* remote atomics  — atomic update at the owning memory controller
* queue engine    — shared work queues (work stealing / dynamic partitioning)
* collective eng. — system-wide barriers and reductions

On a TPU mesh the analogues are (a) local fused gathers/segment-reductions for
the in-node case and (b) `shard_map` + `all_to_all` *owner-routed* exchanges
for the remote case: requests travel to the owner shard, the owner performs the
gather or the commutative update locally, and only the requested/accepted words
cross the network.  This is the paper's fine-grained-access model; the
conventional-architecture baseline ("fetch the whole cache line") is an
`all_gather` of the full remote array, kept for comparison in the algorithms
and benchmarks.

All remote primitives consult an ATT (see `core.dgas`) so distribution rules
stay programmable.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from .dgas import ATT
from .. import compat

AxisName = Union[str, Sequence[str]]

__all__ = [
    "dma_gather", "dma_scatter_add", "dma_strided_copy",
    "axis_size", "my_shard",
    "segment_argmax", "segment_weighted_mode", "compact_labels", "run_starts",
    "segment_or",
    "dgas_gather", "remote_scatter_add", "remote_scatter_combine",
    "remote_scatter_weighted_mode", "remote_scatter_or",
    "buffered_flush",
    "all_gather_gather",
    "QueueState", "queue_make", "queue_balance",
    "hierarchical_psum", "barrier", "prefix_scan",
]

# payload sentinel that sorts after every real label / vertex id
LABEL_PAD = 2 ** 30


# ---------------------------------------------------------------------------
# Local (in-node) DMA engine ops
# ---------------------------------------------------------------------------

def dma_gather(table: jnp.ndarray, idx: jnp.ndarray, *, fill: float = 0.0) -> jnp.ndarray:
    """Gather rows/elements; out-of-range indices return `fill` (padding-safe)."""
    valid = (idx >= 0) & (idx < table.shape[0])
    safe = jnp.where(valid, idx, 0)
    out = jnp.take(table, safe, axis=0)
    mask = valid.reshape(valid.shape + (1,) * (out.ndim - valid.ndim))
    return jnp.where(mask, out, jnp.asarray(fill, out.dtype))


def dma_scatter_add(dest: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Scatter-add with padding indices (<0 or >=n) dropped."""
    valid = (idx >= 0) & (idx < dest.shape[0])
    safe = jnp.where(valid, idx, 0)
    mask = valid.reshape(valid.shape + (1,) * (vals.ndim - valid.ndim))
    return dest.at[safe].add(jnp.where(mask, vals, 0).astype(dest.dtype))


def dma_strided_copy(src: jnp.ndarray, start: int, stride: int, count: int) -> jnp.ndarray:
    return lax.dynamic_slice_in_dim(src, start, 1 + (count - 1) * stride)[::stride]


# ---------------------------------------------------------------------------
# Structured segment combines (the engine's argmax / sample reductions; also
# executed at the owner shard by the remote variants below)
# ---------------------------------------------------------------------------

def segment_argmax(idx: jnp.ndarray, score: jnp.ndarray, payload: jnp.ndarray,
                   n: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-destination (score, payload)-packed segment max.

    For each destination v: best[v] = max score over items with idx==v, and
    pick[v] = the payload of a maximizing item (ties broken toward the
    *smaller* payload, deterministically).  Items with idx outside [0, n) or
    score == -inf are ignored; destinations with no items get (-inf, -1).

    HBM has no native packed max, so the pack is expressed as a two-plane
    lexicographic scatter: scatter-max the score plane, then scatter-min the
    payload plane masked to score winners.
    """
    valid = (idx >= 0) & (idx < n)
    safe = jnp.where(valid, idx, 0)
    neg = jnp.asarray(-jnp.inf, score.dtype)
    s = jnp.where(valid, score, neg)
    best = jnp.full((n,), neg, score.dtype).at[safe].max(s)
    is_best = valid & (s == jnp.take(best, safe)) & (s > neg)
    pad = jnp.int32(LABEL_PAD)
    cand = jnp.where(is_best, payload.astype(jnp.int32), pad)
    pick = jnp.full((n,), pad, jnp.int32).at[safe].min(cand)
    return best, jnp.where(pick == pad, -1, pick)


def run_starts(*sorted_keys: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Run detection over a lex-sorted key stream (nonempty): returns
    (is_start (m,) bool, run_id (m,) — the exclusive count of starts).  The
    shared reduction behind :func:`segment_weighted_mode`,
    :func:`compact_labels` and `graph.contract`: a run is a maximal stretch
    where every key matches its predecessor."""
    neq = None
    for k in sorted_keys:
        d = k[1:] != k[:-1]
        neq = d if neq is None else (neq | d)
    is_start = jnp.concatenate([jnp.ones((1,), bool), neq])
    return is_start, jnp.cumsum(is_start) - 1


def segment_weighted_mode(idx: jnp.ndarray, labels: jnp.ndarray,
                          weights: jnp.ndarray, n: int
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-destination weighted mode: argmax_l sum(weights | idx==v, labels==l).

    Returns (best_w, best_label): the winning label's total weight and the
    label itself, ties toward the smaller label.  Items with idx outside
    [0, n) or labels < 0 are ignored; destinations with no items get
    (-inf, -1).  This is the two-stage structured combine: weights are first
    summed per (destination, label) run — the stream is sorted by that pair so
    the sums are one fused segment reduction — then the (sum, label) pack goes
    through :func:`segment_argmax`.
    """
    m = int(idx.shape[0])
    if m == 0:
        return (jnp.full((n,), -jnp.inf, weights.dtype),
                jnp.full((n,), -1, jnp.int32))
    valid = (idx >= 0) & (idx < n) & (labels >= 0)
    si = jnp.where(valid, idx, n).astype(jnp.int32)
    sl = jnp.where(valid, labels, LABEL_PAD).astype(jnp.int32)
    order = jnp.lexsort((sl, si))
    si, sl = jnp.take(si, order), jnp.take(sl, order)
    sw = jnp.where(jnp.take(valid, order),
                   jnp.take(weights, order), jnp.zeros((), weights.dtype))
    is_start, run_id = run_starts(si, sl)
    run_w = jax.ops.segment_sum(sw, run_id, num_segments=m)
    rep_idx = jnp.where(is_start & (si < n), si, -1)
    return segment_argmax(rep_idx, jnp.take(run_w, run_id), sl, n)


def segment_or(idx: jnp.ndarray, words: jnp.ndarray, n: int, *,
               presorted: bool = False) -> jnp.ndarray:
    """Per-destination bitwise OR of packed lane words (MS-BFS's combine).

    ``idx`` (m,) int32 destinations (out-of-range ignored), ``words`` (m, W)
    uint32 bit-packed lane payloads.  Returns (n, W) uint32 with out[v] = OR
    of all words whose idx == v (0 where no items land).

    HBM scatters have no native OR, and bit-packed words cannot ride the
    add/min/max scatters (carries / monotonicity), so the reduction is a
    *segmented scan*: sort by destination (skipped when the caller's stream
    is already destination-sorted, e.g. the engine's host-presorted pull
    stream), run a segmented inclusive OR-scan — the collective engine's
    prefix-scan machinery applied within runs — and keep each run's last
    element.  The scan is Hillis–Steele doubling: ceil(log2 m) shifted
    ORs, each a contiguous slice.  (``lax.associative_scan``'s strided
    slices compile on TPU to code that grows with m: ~100 MB at m = 1.2M.)
    O(m log m) work, fully vectorized over the W lane words.
    """
    m = int(idx.shape[0])
    W = int(words.shape[1])
    if m == 0:
        return jnp.zeros((n, W), jnp.uint32)
    valid = (idx >= 0) & (idx < n)
    key = jnp.where(valid, idx, n).astype(jnp.int32)
    w = jnp.where(valid[:, None], words.astype(jnp.uint32), jnp.uint32(0))
    if not presorted:
        order = jnp.argsort(key)  # OR is commutative: stability not needed
        key = jnp.take(key, order)
        w = jnp.take(w, order, axis=0)
    # after the step with shift d, w[i] is the OR over the last 2d items of
    # i's run (or the whole run): keys are sorted, so key[i] == key[i - d]
    # means every item between lies in the same run
    d = 1
    while d < m:
        same = jnp.concatenate([jnp.zeros((d,), bool), key[d:] == key[:-d]])
        prev = jnp.concatenate([jnp.zeros((d, W), jnp.uint32), w[:-d]])
        w = jnp.where(same[:, None], w | prev, w)
        d *= 2
    is_end = jnp.concatenate([key[1:] != key[:-1], jnp.ones((1,), bool)])
    # one writer per run: scatter the run totals; the n sentinel (and any
    # non-end position) is dropped by the out-of-bounds scatter rule
    end_key = jnp.where(is_end, key, n)
    return jnp.zeros((n, W), jnp.uint32).at[end_key].set(w)


def compact_labels(labels: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Renumber arbitrary int labels into dense ids [0, n_c), order-preserving.

    The graph-contraction step (collapse communities into supernodes) needs
    community ids that double as coarse vertex ids.  Same run-detection
    machinery as :func:`segment_weighted_mode`: sort, mark run starts, prefix
    sum the starts — a segment scan, not a host-side unique.  Returns
    (dense (n,) int32, n_c () int32); the smallest original label maps to 0,
    so the renumbering is deterministic and monotone in the original ids.
    """
    n = int(labels.shape[0])
    if n == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32)
    order = jnp.argsort(labels, stable=True)
    sl = jnp.take(labels, order)
    _, run_id = run_starts(sl)
    rank = run_id.astype(jnp.int32)
    dense = jnp.zeros((n,), jnp.int32).at[order].set(rank)
    return dense, rank[-1] + 1


# ---------------------------------------------------------------------------
# Axis helpers (work with a single axis name or a tuple of axis names)
# ---------------------------------------------------------------------------

def axis_size(axis_name: AxisName) -> int:
    return compat.axis_size(axis_name)


def my_shard(axis_name: AxisName) -> jnp.ndarray:
    """Flattened linear shard index across (possibly) multiple mesh axes."""
    if isinstance(axis_name, (tuple, list)):
        idx = jnp.int32(0)
        for a in axis_name:
            idx = idx * compat.axis_size(a) + lax.axis_index(a)
        return idx
    return lax.axis_index(axis_name)


def _all_to_all(x: jnp.ndarray, axis_name: AxisName) -> jnp.ndarray:
    """all_to_all over leading axis of size = axis size (possibly tuple axes)."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0, tiled=False)


# ---------------------------------------------------------------------------
# Owner routing (shared by dgas_gather / remote_scatter_add / queues)
# ---------------------------------------------------------------------------

def _owner_slots(dest: jnp.ndarray, n_shards: int, capacity: int):
    """Assign each item a slot in its destination bucket.

    Returns (flat, valid): flat = dest*capacity + slot for valid items, and
    valid = slot < capacity.  Deterministic (stable sort order).
    """
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sorted_dest = jnp.take(dest, order)
    starts = jnp.searchsorted(sorted_dest, jnp.arange(n_shards, dtype=dest.dtype))
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - jnp.take(starts, sorted_dest).astype(jnp.int32)
    slot = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    valid = (slot < capacity) & (dest >= 0) & (dest < n_shards)
    flat = jnp.where(valid, dest.astype(jnp.int32) * capacity + slot, 0)
    return flat, valid


def _route(payload, dest: jnp.ndarray, axis_name: AxisName, capacity: int):
    """Send each payload row to shard `dest[i]` (fixed per-peer capacity).

    payload: pytree of arrays with leading dim n.
    Returns (recv_payload, recv_valid, flat, valid):
      recv_payload: pytree with leading dims (S*capacity,) — grouped by source peer
      recv_valid:   (S*capacity,) bool
      flat, valid:  sender-side slot bookkeeping (for reply unscatter).
    """
    S = axis_size(axis_name)
    flat, valid = _owner_slots(dest, S, capacity)

    def scatter_one(x):
        buf = jnp.zeros((S * capacity,) + x.shape[1:], x.dtype)
        vmask = valid.reshape((-1,) + (1,) * (x.ndim - 1))
        return buf.at[flat].add(jnp.where(vmask, x, jnp.zeros((), x.dtype)))

    send = jax.tree.map(scatter_one, payload)
    sendv = jnp.zeros((S * capacity,), jnp.bool_).at[flat].max(valid)

    def a2a(x):
        return _all_to_all(x.reshape((S, capacity) + x.shape[1:]), axis_name).reshape(
            (S * capacity,) + x.shape[1:])

    recv = jax.tree.map(a2a, send)
    recvv = a2a(sendv.astype(jnp.int8)).astype(jnp.bool_)
    return recv, recvv, flat, valid


def _reply(reply_payload, flat: jnp.ndarray, valid: jnp.ndarray, axis_name: AxisName,
           capacity: int, fill=0):
    """Return per-request answers computed at the owner back to the requesters."""
    S = axis_size(axis_name)

    def a2a(x):
        return _all_to_all(x.reshape((S, capacity) + x.shape[1:]), axis_name).reshape(
            (S * capacity,) + x.shape[1:])

    back = jax.tree.map(a2a, reply_payload)

    def unscatter(x):
        out = jnp.take(x, flat, axis=0)
        vmask = valid.reshape(valid.shape + (1,) * (out.ndim - 1))
        return jnp.where(vmask, out, jnp.asarray(fill, out.dtype))

    return jax.tree.map(unscatter, back)


# ---------------------------------------------------------------------------
# DGAS remote access primitives
# ---------------------------------------------------------------------------

def dgas_gather(local: jnp.ndarray, gidx: jnp.ndarray, att: ATT, axis_name: AxisName,
                *, capacity: Optional[int] = None, fill: float = 0.0) -> jnp.ndarray:
    """PIUMA fine-grained remote gather (DMA gather across the DGAS).

    Each shard holds `local` (its rows of the global array, per `att`); `gidx`
    are *global* ids to fetch.  Only the index requests (8 B) and the fetched
    elements travel the network — never whole array replicas.

    capacity: max requests any single peer pair exchanges; defaults to
      2*ceil(n/S) (fine for interleaved/balanced rules; raise for skew —
      overflowing requests return `fill`).
    """
    n = gidx.shape[0]
    S = axis_size(axis_name)
    C = capacity if capacity is not None else min(n, 2 * (-(-n // S)))
    owner = att.owner(gidx).astype(jnp.int32)
    local_idx = att.local(gidx).astype(jnp.int32)
    local_idx = jnp.where((gidx >= 0) & (gidx < att.n_global), local_idx, -1)
    recv, recvv, flat, valid = _route(local_idx, owner, axis_name, C)
    answers = dma_gather(local, jnp.where(recvv, recv, -1), fill=fill)
    return _reply(answers, flat, valid, axis_name, C, fill=fill)


def remote_scatter_add(local: jnp.ndarray, gidx: jnp.ndarray, vals: jnp.ndarray,
                       att: ATT, axis_name: AxisName, *,
                       capacity: Optional[int] = None) -> jnp.ndarray:
    """PIUMA remote atomic add: the update executes at the owner shard.

    Routes (local index, value) pairs to the owning shard which applies a
    single fused segment update — the batched bulk-synchronous equivalent of
    per-word remote atomics (commutative ops only; see DESIGN.md §2).
    """
    n = gidx.shape[0]
    S = axis_size(axis_name)
    C = capacity if capacity is not None else min(n, 2 * (-(-n // S)))
    owner = att.owner(gidx).astype(jnp.int32)
    local_idx = att.local(gidx).astype(jnp.int32)
    local_idx = jnp.where((gidx >= 0) & (gidx < att.n_global), local_idx, -1)
    (ridx, rvals), recvv, _, _ = _route((local_idx, vals), owner, axis_name, C)
    ridx = jnp.where(recvv, ridx, -1)
    return dma_scatter_add(local, ridx, rvals)


def remote_scatter_combine(local: jnp.ndarray, gidx: jnp.ndarray,
                           vals: jnp.ndarray, att: ATT, axis_name: AxisName, *,
                           combine: str, identity,
                           capacity: Optional[int] = None) -> jnp.ndarray:
    """Remote atomic min/max (the non-additive PIUMA remote atomics).

    Same routing as `remote_scatter_add`; the owner applies a fused
    scatter-{min,max}.  Dropped/padding slots carry `identity` so they are
    no-ops at the owner.
    """
    if combine not in ("min", "max"):
        raise ValueError(f"combine must be 'min' or 'max', got {combine!r}")
    n = gidx.shape[0]
    S = axis_size(axis_name)
    C = capacity if capacity is not None else min(n, 2 * (-(-n // S)))
    owner = att.owner(gidx).astype(jnp.int32)
    local_idx = att.local(gidx).astype(jnp.int32)
    local_idx = jnp.where((gidx >= 0) & (gidx < att.n_global), local_idx, -1)
    neutral = jnp.asarray(identity, vals.dtype)
    # each routed slot holds exactly one item, so values arrive unchanged;
    # empty slots are zero-filled by _route and masked to `identity` here.
    (ridx, rvals), recvv, _, _ = _route((local_idx, vals), owner, axis_name, C)
    ridx = jnp.where(recvv, ridx, -1)
    trail = (1,) * (rvals.ndim - 1)  # vals may carry lanes: (m, B) and beyond
    rvals = jnp.where(recvv.reshape((-1,) + trail), rvals, neutral)
    valid = (ridx >= 0) & (ridx < local.shape[0])
    safe = jnp.where(valid, ridx, 0)
    masked = jnp.where(valid.reshape((-1,) + trail), rvals.astype(local.dtype),
                       jnp.asarray(identity, local.dtype))
    if combine == "min":
        return local.at[safe].min(masked)
    return local.at[safe].max(masked)


def remote_scatter_or(per_shard_n: int, gidx: jnp.ndarray, words: jnp.ndarray,
                      att: ATT, axis_name: AxisName, *,
                      capacity: Optional[int] = None) -> jnp.ndarray:
    """Remote atomic OR of bit-packed lane words, executed at the owner.

    The batched engine's push step for bitwise (MS-BFS-style) programs: each
    shard contributes (global vertex, (W,) uint32 lane words) pairs; the
    owner reduces arrivals with :func:`segment_or`.  One routed item carries
    all B lanes in ceil(B/32) words — the amortization PIUMA's concurrent
    traversals exploit, `traffic.batched_payload_bytes` charges it.
    Returns the (per_shard_n, W) uint32 OR-accumulator.
    """
    n = gidx.shape[0]
    S = axis_size(axis_name)
    C = capacity if capacity is not None else min(n, 2 * (-(-n // S)))
    in_range = (gidx >= 0) & (gidx < att.n_global)
    owner = jnp.where(in_range, att.owner(jnp.maximum(gidx, 0)), -1).astype(jnp.int32)
    local_idx = jnp.where(in_range, att.local(jnp.maximum(gidx, 0)), -1).astype(jnp.int32)
    (ridx, rwords), recvv, _, _ = _route(
        (local_idx, words.astype(jnp.uint32)), owner, axis_name, C)
    ridx = jnp.where(recvv, ridx, -1)
    return segment_or(ridx, rwords, per_shard_n)


def remote_scatter_weighted_mode(per_shard_n: int, gidx: jnp.ndarray,
                                 labels: jnp.ndarray, weights: jnp.ndarray,
                                 att: ATT, axis_name: AxisName, *,
                                 capacity: Optional[int] = None
                                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Remote structured combine: weighted label mode executed at the owner.

    Each shard contributes (global vertex, label, weight) votes; the triples
    are owner-routed *raw* (no sender-side pre-reduction) so the owner's
    :func:`segment_weighted_mode` sums each (vertex, label) pair over every
    contributing shard before taking the argmax — the reduction is correct
    even when votes for one pair arrive from many shards.  Returns the
    per-local-vertex (best_w, best_label); vertices with no votes get
    (-inf, -1).
    """
    n = gidx.shape[0]
    S = axis_size(axis_name)
    C = capacity if capacity is not None else min(n, 2 * (-(-n // S)))
    in_range = (gidx >= 0) & (gidx < att.n_global)
    owner = jnp.where(in_range, att.owner(jnp.maximum(gidx, 0)), -1).astype(jnp.int32)
    local_idx = jnp.where(in_range, att.local(jnp.maximum(gidx, 0)), -1).astype(jnp.int32)
    (ridx, rlab, rw), recvv, _, _ = _route(
        (local_idx, labels.astype(jnp.int32), weights), owner, axis_name, C)
    ridx = jnp.where(recvv, ridx, -1)
    rlab = jnp.where(recvv, rlab, -1)
    return segment_weighted_mode(ridx, rlab, rw, per_shard_n)


def buffered_flush(outbox: jnp.ndarray, axis_name: AxisName, *,
                   combine: str) -> jnp.ndarray:
    """Deliver a dense deferred-message buffer to its owner shards.

    The async placement's exchange primitive (DESIGN.md §14): between global
    checks each shard folds remote contributions into a dense ``(S*per, ...)``
    outbox addressed by flat slot ``owner * per + local`` (`ATT.flat_slot`)
    using the program's combine, so arbitrarily many local micro-steps of
    traffic collapse into one fixed-size buffer.  At the sync point this
    single collective transposes the buffers — peer p's slice lands on shard
    p — and the S inbound slices are folded with the same combine.  Because
    the combine is associative and commutative, delivery order (i.e. the
    staleness window) cannot change the merged value.

    outbox: (S * per, ...) with identity-filled empty slots
      (0 for 'add'/'or', +inf for 'min', -inf for 'max').
    combine: 'add' | 'min' | 'max' | 'or' ('or' expects uint32 lane words).
    Returns the (per, ...) merged arrivals for this shard's residents.
    """
    S = axis_size(axis_name)
    lead = outbox.shape[0]
    if lead % S != 0:
        raise ValueError(
            f"outbox leading dim {lead} is not divisible by {S} shards")
    box = outbox.reshape((S, lead // S) + outbox.shape[1:])
    arrived = _all_to_all(box, axis_name)  # [p] = peer p's messages for me
    if combine == "add":
        return arrived.sum(axis=0)
    if combine == "min":
        return arrived.min(axis=0)
    if combine == "max":
        return arrived.max(axis=0)
    if combine == "or":
        out = arrived[0]
        for i in range(1, S):  # static: S is a compile-time mesh size
            out = out | arrived[i]
        return out
    raise ValueError(f"unsupported combine {combine!r} for buffered_flush")


def all_gather_gather(local: jnp.ndarray, gidx: jnp.ndarray, att: ATT,
                      axis_name: AxisName, *, fill: float = 0.0) -> jnp.ndarray:
    """Conventional-architecture baseline: replicate the whole array, then index.

    This is the 'move the cache line (here: the entire remote array)' strategy
    GSPMD produces by default; kept to quantify PIUMA's advantage.
    Requires a contiguous or interleaved rule to reassemble the global order.
    """
    g = lax.all_gather(local, axis_name, tiled=False)  # (S, rows_per_shard, ...)
    S = g.shape[0]
    if att.kind == "interleave":
        # global id g -> (g % S, g // S): reassemble by transposing
        full = jnp.swapaxes(g, 0, 1).reshape((-1,) + g.shape[2:])[: att.n_global]
    else:
        full = g.reshape((-1,) + g.shape[2:])[: att.n_global]
    return dma_gather(full, gidx, fill=fill)


# ---------------------------------------------------------------------------
# Queue engine
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QueueState:
    """Fixed-capacity distributed work queue (one buffer per shard)."""

    items: jnp.ndarray  # (capacity,) int32, padding = -1
    count: jnp.ndarray  # () int32 — valid prefix length

    def tree_flatten(self):
        return (self.items, self.count), ()

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def queue_make(capacity: int) -> QueueState:
    return QueueState(jnp.full((capacity,), -1, jnp.int32), jnp.zeros((), jnp.int32))


def queue_balance(q: QueueState, axis_name: AxisName, payload=None):
    """Rebalance queued items evenly across shards (hardware work stealing).

    Every item gets a global rank via a device prefix scan; item with rank r
    moves to shard r % S (interleave), so post-balance counts differ by <=1.
    Since the global item count never exceeds S * capacity, the balanced
    per-shard count fits the original capacity and the returned queue keeps
    the input buffer size (a fixed point for iterated balancing).

    payload: optional pytree with leading dim == capacity, routed alongside
      the items (a queue entry's companion data — e.g. a walker's current
      vertex); rows without an item are zeroed.  Returns (QueueState, payload)
      when given, else just the QueueState.
    """
    S = axis_size(axis_name)
    cap = q.items.shape[0]
    offset = prefix_scan(q.count, axis_name)
    rank = offset + jnp.arange(cap, dtype=jnp.int32)
    is_item = jnp.arange(cap) < q.count
    dest = jnp.where(is_item, rank % S, -1)
    pl_leaves, pl_def = (jax.tree.flatten(payload) if payload is not None
                         else ((), None))
    recv, recvv, _, _ = _route((q.items,) + tuple(pl_leaves),
                               dest.astype(jnp.int32), axis_name, cap)
    # compact received items to a prefix, back into the original capacity
    order = jnp.argsort(~recvv, stable=True)[:cap]  # valid first
    kept = jnp.take(recvv, order)
    items = jnp.where(kept, jnp.take(recv[0], order), -1)
    out_q = QueueState(items, recvv.sum().astype(jnp.int32))
    if payload is None:
        return out_q
    out_pl = []
    for x in recv[1:]:
        xs = jnp.take(x, order, axis=0)
        mask = kept.reshape((-1,) + (1,) * (xs.ndim - 1))
        out_pl.append(jnp.where(mask, xs, jnp.zeros((), xs.dtype)))
    return out_q, jax.tree.unflatten(pl_def, out_pl)


# ---------------------------------------------------------------------------
# Collective engine
# ---------------------------------------------------------------------------

def hierarchical_psum(x, axes: Sequence[AxisName]):
    """Reduce one mesh level at a time (intra-block -> intra-pod -> cross-pod),
    matching the HyperX hierarchy; XLA can then schedule each stage on its own
    link class."""
    for a in axes:
        x = lax.psum(x, a)
    return x


def barrier(axis_name: AxisName) -> jnp.ndarray:
    """System-wide barrier (semantic, via a 1-word reduction)."""
    return lax.psum(jnp.ones((), jnp.int32), axis_name)


def prefix_scan(x: jnp.ndarray, axis_name: AxisName) -> jnp.ndarray:
    """Exclusive prefix sum across shards (collective-engine scan)."""
    g = lax.all_gather(x, axis_name, tiled=False)  # (S, ...)
    csum = jnp.cumsum(g, axis=0) - g
    return jnp.take(csum, my_shard(axis_name), axis=0)
