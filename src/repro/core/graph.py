"""Graph substrate: CSR storage, RMAT synthesis, partitioning, kernel formats.

PIUMA "directly operates on sparse data (e.g., CSR)"; this module is the CSR
layer plus the two derived formats the TPU kernels need:

* padded-ELL row blocks (per-row fixed budget) for vectorized per-row work, and
* BBCSR — *block-bucketed* COO, nonzeros sorted by (column block, row), so a
  Pallas kernel can DMA one dense-vector block into VMEM and service every
  nonzero that touches it (the TPU-native re-expression of PIUMA's 8-byte
  gather; see DESIGN.md §2).

Streaming mutation (DESIGN.md §16): :class:`GraphHandle` is the one graph
currency for code that serves a graph *changing under the queries* — an
immutable (CSR, epoch, delta log, per-partition mutation stamps) tuple.
``handle.apply(inserts, deletes)`` splices a batch of edge updates into the
CSR as an overlay delta (no global re-sort), bumps the epoch, stamps the
touched partitions, and appends to the :class:`DeltaLog`; once the log
outgrows ``compact_threshold`` of the edge count, the handle compacts back
into a clean ``CSR.from_coo`` rebuild.  Epoch and stamp bookkeeping lives
HERE and only here — the `mutable-handle` repro-lint rule rejects
``.epoch`` / ``.csr`` assignment anywhere else.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import get_registry

__all__ = ["CSR", "rmat", "uniform_random_graph", "to_padded_ell", "to_bbcsr", "BBCSR",
           "contract", "DeltaLog", "UpdateReport", "GraphHandle"]


def dst_sorted_edges(indptr: np.ndarray, indices: np.ndarray):
    """(src, dst) int32 numpy edge stream of a CSR, stably sorted by
    destination (ties keep source order)."""
    src = np.repeat(np.arange(indptr.shape[0] - 1, dtype=np.int32),
                    np.diff(indptr))
    order = np.argsort(indices, kind="stable")
    return src[order], indices[order].astype(np.int32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class CSR:
    """Compressed sparse row matrix / adjacency.

    indptr:  (n_rows+1,) int32
    indices: (nnz,) int32 column ids
    values:  (nnz,) float — edge weights (None -> implicit 1.0 handled by callers)
    max_degree: static largest out-degree, or None (derived from a concrete
      indptr when needed).
    dst_stream: (src, dst) int32 edge stream sorted by destination — the
      packed dense step's presorted ``segment_or`` input — or None (sorted on
      the host from a concrete CSR when needed).
    :meth:`for_jit` records both, so a jitted function that takes the CSR as
    an *argument* — its arrays then traced — can still size the engine's
    static per-row gathers and needs no sort on the device.
    """

    indptr: jnp.ndarray
    indices: jnp.ndarray
    values: Optional[jnp.ndarray]
    n_rows: int
    n_cols: int
    max_degree: Optional[int] = None
    dst_stream: Optional[tuple] = None

    def tree_flatten(self):
        return (self.indptr, self.indices, self.values, self.dst_stream), (
            self.n_rows, self.n_cols, self.max_degree)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:3], *aux, dst_stream=children[3])

    # trace-safe: host-side, on the concrete graph before any trace —
    # repro-lint: disable=host-sync
    def for_jit(self) -> "CSR":
        """This CSR with ``max_degree`` and ``dst_stream`` recorded, to be
        passed to jit as an argument (one host read and sort of the edges)."""
        indptr = np.asarray(self.indptr)
        k = int(np.diff(indptr).max()) if indptr.size > 1 else 1
        src, dst = dst_sorted_edges(indptr, np.asarray(self.indices))
        return dataclasses.replace(
            self, max_degree=max(k, 1),
            dst_stream=(jnp.asarray(src), jnp.asarray(dst)))

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def degrees(self) -> jnp.ndarray:
        return self.indptr[1:] - self.indptr[:-1]

    def row_ids(self) -> jnp.ndarray:
        """Expand indptr to a (nnz,) row id per nonzero (sorted)."""
        return jnp.searchsorted(
            self.indptr, jnp.arange(self.indices.shape[0], dtype=self.indptr.dtype), side="right"
        ) - 1

    def to_dense(self) -> jnp.ndarray:
        vals = self.values if self.values is not None else jnp.ones_like(self.indices, jnp.float32)
        out = jnp.zeros((self.n_rows, self.n_cols), vals.dtype)
        return out.at[self.row_ids(), self.indices].add(vals)

    def transpose(self) -> "CSR":
        """Host-side transpose (CSC view as a CSR). The frontier engine's pull
        direction iterates in-edges, so it needs A^T sharing A's vertex ids.

        The result stays NumPy-backed: wrapping with `jnp.asarray` inside a
        jit trace would stage the arrays into tracers, and callers that
        transpose under jit (e.g. a jitted LPA) need the result concrete so
        the engine can still derive its static gather budgets from it.
        """
        indptr = np.asarray(self.indptr)
        rows = np.repeat(np.arange(self.n_rows), np.diff(indptr))
        cols = np.asarray(self.indices)
        vals = None if self.values is None else np.asarray(self.values)
        return CSR.from_coo(cols, rows, vals, self.n_cols, self.n_rows,
                            device=False)

    def contract(self, labels) -> tuple["CSR", jnp.ndarray]:
        """Collapse label groups into supernodes; see :func:`contract`."""
        return contract(self, labels)

    @staticmethod
    def from_coo(rows, cols, vals, n_rows, n_cols, *, sum_duplicates: bool = False,
                 device: bool = True) -> "CSR":
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = None if vals is None else np.asarray(vals)
        order = np.lexsort((cols, rows))
        rows, cols = rows[order], cols[order]
        if vals is not None:
            vals = vals[order]
        if sum_duplicates:
            keep = np.ones(rows.shape[0], bool)
            keep[1:] = (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1])
            if vals is not None:
                seg = np.cumsum(keep) - 1
                vals = np.bincount(seg, weights=vals, minlength=int(keep.sum()))
            rows, cols = rows[keep], cols[keep]
        indptr = np.zeros(n_rows + 1, np.int64)
        np.add.at(indptr, rows + 1, 1)
        indptr = np.cumsum(indptr)
        wrap = jnp.asarray if device else np.asarray
        return CSR(
            wrap(np.asarray(indptr, np.int32)),
            wrap(np.asarray(cols, np.int32)),
            None if vals is None else wrap(np.asarray(vals, np.float32)),
            int(n_rows),
            int(n_cols),
        )


def contract(csr: CSR, labels) -> tuple[CSR, jnp.ndarray]:
    """Collapse communities into a coarsened graph (multi-level Louvain's
    level step; PAPERS: Gill et al. hinge community-detection throughput on
    cheap contraction between levels).

    ``labels`` is any (n_rows,) int assignment.  The labels are renumbered to
    dense coarse vertex ids with :func:`offload.compact_labels`, every edge
    (u, v, w) becomes (label[u], label[v], w), and parallel coarse edges are
    merged by a segment-sum over the lex-sorted (src_label, dst_label) pairs
    — the same fused run-reduction the engine's structured combines use.
    Intra-community edges accumulate into self-loops (they carry the
    community's internal weight, which keeps modularity invariant under
    contraction: Q(coarse, identity) == Q(fine, labels)).

    Returns (coarse CSR (n_c x n_c, weighted), renumber (n_rows,) int32
    mapping each fine vertex to its coarse vertex id).  Host-boundary op:
    the coarse shapes are data-dependent, so like `CSR.transpose` the result
    is concrete (usable for deriving the next level's static budgets), not a
    jit-traceable value.
    """
    from . import offload

    lab = jnp.asarray(labels).astype(jnp.int32)
    if lab.shape[0] != csr.n_rows:
        raise ValueError(f"labels must be ({csr.n_rows},), got {lab.shape}")
    dense, n_c_dev = offload.compact_labels(lab)
    n_c = int(n_c_dev) if csr.n_rows else 0
    m = csr.nnz
    if m == 0:
        return CSR(jnp.zeros((n_c + 1,), jnp.int32), jnp.zeros((0,), jnp.int32),
                   jnp.zeros((0,), jnp.float32), n_c, n_c), dense
    vals = (csr.values if csr.values is not None
            else jnp.ones((m,), jnp.float32))
    rows = offload.dma_gather(dense, csr.row_ids())
    cols = offload.dma_gather(dense, csr.indices)
    # segment-sum of edge weights over (src_label, dst_label) runs
    order = jnp.lexsort((cols, rows))
    sr, sc = jnp.take(rows, order), jnp.take(cols, order)
    sv = jnp.take(vals, order)
    is_start, run_id = offload.run_starts(sr, sc)
    run_w = jax.ops.segment_sum(sv, run_id, num_segments=m)
    starts = np.asarray(is_start)
    n_runs = int(starts.sum())
    sr_h, sc_h = np.asarray(sr)[starts], np.asarray(sc)[starts]
    w_h = np.asarray(run_w)[:n_runs]
    coarse = CSR.from_coo(sr_h, sc_h, w_h, n_c, n_c)
    return coarse, dense


def rmat(scale: int, edge_factor: int = 16, *, a=0.57, b=0.19, c=0.19, seed: int = 0,
         weighted: bool = True, dedup: bool = True) -> CSR:
    """RMAT generator (Graph500 parameters by default). n = 2**scale vertices.

    Matches the paper's evaluation input class ("RMAT-30 synthetic matrix",
    scaled down for CPU validation).  Pure numpy; deterministic per seed.
    """
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    rows = np.zeros(m, np.int64)
    cols = np.zeros(m, np.int64)
    # per-bit quadrant choice
    pa, pb, pc = a, b, c
    for bit in range(scale):
        r = rng.random(m)
        go_right = (r >= pa + pc) & (r < pa + pc + pb) | (r >= pa + pb + pc)
        go_down = (r >= pa) & (r < pa + pc) | (r >= pa + pb + pc)
        rows |= go_down.astype(np.int64) << bit
        cols |= go_right.astype(np.int64) << bit
    vals = rng.random(m).astype(np.float32) if weighted else None
    return CSR.from_coo(rows, cols, vals, n, n, sum_duplicates=dedup)


def uniform_random_graph(n: int, avg_degree: int, *, seed: int = 0, weighted: bool = True) -> CSR:
    rng = np.random.default_rng(seed)
    m = n * avg_degree
    rows = rng.integers(0, n, m)
    cols = rng.integers(0, n, m)
    vals = rng.random(m).astype(np.float32) if weighted else None
    return CSR.from_coo(rows, cols, vals, n, n, sum_duplicates=True)


# ---------------------------------------------------------------------------
# Streaming mutation: DeltaLog + epoch-versioned GraphHandle (DESIGN.md §16)
# ---------------------------------------------------------------------------

def _edge_keys(csr: CSR) -> np.ndarray:
    """(nnz,) int64 ``row * n_cols + col`` keys.  Canonical CSRs (everything
    a GraphHandle holds) have strictly increasing keys: row-major, columns
    sorted within each row, no duplicate (row, col) pairs."""
    indptr = np.asarray(csr.indptr, np.int64)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(indptr))
    return rows * np.int64(csr.n_cols) + np.asarray(csr.indices, np.int64)


def _canonical(csr: CSR) -> CSR:
    """Return `csr` if its keys are strictly increasing, else a
    duplicate-summed `from_coo` rebuild (the handle's splice arithmetic
    relies on sorted-unique keys)."""
    key = _edge_keys(csr)
    if key.size == 0 or bool(np.all(key[1:] > key[:-1])):
        return csr
    rows, cols = key // csr.n_cols, key % csr.n_cols
    vals = None if csr.values is None else np.asarray(csr.values)
    return CSR.from_coo(rows, cols, vals, csr.n_rows, csr.n_cols,
                        sum_duplicates=True)


def _coerce_edges(edges, *, weighted: bool):
    """Normalize an (rows, cols[, vals]) tuple / None to int64/f32 arrays."""
    if edges is None:
        e = np.zeros((0,), np.int64)
        return e, e.copy(), (np.zeros((0,), np.float32) if weighted else None)
    rows, cols = np.asarray(edges[0], np.int64), np.asarray(edges[1], np.int64)
    if rows.shape != cols.shape or rows.ndim != 1:
        raise ValueError(f"edge endpoints must be matching 1-d arrays, got "
                         f"{rows.shape} vs {cols.shape}")
    vals = None
    if weighted:
        vals = (np.asarray(edges[2], np.float32) if len(edges) > 2
                and edges[2] is not None else np.ones(rows.shape, np.float32))
        if vals.shape != rows.shape:
            raise ValueError(f"edge values shape {vals.shape} != {rows.shape}")
    return rows, cols, vals


@dataclasses.dataclass(frozen=True)
class DeltaLog:
    """Pending edge updates since the last compaction, as flat COO arrays.

    The log is *bookkeeping*, not the source of truth: every ``apply``
    already splices the batch into the handle's canonical CSR.  The log
    records what changed since the CSR was last rebuilt clean — its size
    drives the compaction trigger, and its endpoint set is what a
    distributed deployment must reship (only the touched partitions)."""

    ins_rows: np.ndarray
    ins_cols: np.ndarray
    ins_vals: Optional[np.ndarray]
    del_rows: np.ndarray
    del_cols: np.ndarray

    @classmethod
    def empty(cls, *, weighted: bool = True) -> "DeltaLog":
        e = np.zeros((0,), np.int64)
        return cls(e, e.copy(), np.zeros((0,), np.float32) if weighted
                   else None, e.copy(), e.copy())

    @property
    def size(self) -> int:
        """Pending update count (inserts + deletes since last compaction)."""
        return int(self.ins_rows.size + self.del_rows.size)

    def extend(self, ins_r, ins_c, ins_v, del_r, del_c) -> "DeltaLog":
        return DeltaLog(
            np.concatenate([self.ins_rows, ins_r]),
            np.concatenate([self.ins_cols, ins_c]),
            None if self.ins_vals is None
            else np.concatenate([self.ins_vals, ins_v]),
            np.concatenate([self.del_rows, del_r]),
            np.concatenate([self.del_cols, del_c]))


@dataclasses.dataclass(frozen=True)
class UpdateReport:
    """What one ``GraphHandle.apply`` batch did — the repair/invalidation
    contract: ``changed_sources`` seeds incremental monotone recompute
    (algorithms.incremental), ``touched_partitions`` scopes cache eviction
    and distributed resharding, ``monotone_safe`` says whether label-
    correcting repair is valid (insert-only, no weight increases) or the
    caller must fall back to full recompute."""

    epoch: int
    n_inserted: int          # new edges spliced in (upserts excluded)
    n_deleted: int           # edges actually removed
    n_upserted: int          # existing edges whose weight was replaced
    changed_sources: np.ndarray     # unique source endpoints of changed edges
    changed_vertices: np.ndarray    # unique endpoints, both sides
    touched_partitions: np.ndarray  # unique partition ids (both endpoints)
    monotone_safe: bool
    compacted: bool

    @property
    def n_changed(self) -> int:
        return self.n_inserted + self.n_deleted + self.n_upserted


@dataclasses.dataclass(frozen=True)
class GraphHandle:
    """Epoch-versioned graph: the one currency for mutable-graph serving.

    Immutable — every mutation returns a NEW handle (so readers holding the
    old one keep a consistent graph+epoch pair):

    * ``apply(inserts, deletes)``: splice one update batch into the CSR as
      an overlay delta — deletes mask matched edges, inserts upsert existing
      (row, col) pairs in place and splice genuinely new edges at their
      sorted positions (O(m + d), no global re-sort).  Bumps the epoch,
      stamps the partitions owning either endpoint of any changed edge, and
      extends the :class:`DeltaLog`.  Batch semantics: deletes apply before
      inserts; duplicate inserts in one batch keep the LAST occurrence;
      inserting an existing edge replaces its weight; deleting a missing
      edge is a no-op; self-loops are ordinary edges.
    * ``replace(csr)``: whole-graph swap (the deprecated
      ``GraphService.update_graph`` shim) — every partition is stamped.
    * ``compact()``: rebuild the CSR clean via ``CSR.from_coo`` and clear
      the log; ``apply`` auto-compacts once the log exceeds
      ``compact_threshold`` × nnz.

    Partitions are contiguous vertex blocks (``ceil(n / n_partitions)`` per
    block — the same arithmetic as ``dgas.block_rule``), so partition ids
    line up with the distributed service's shard ids.  ``stamps[p]`` is the
    epoch partition ``p`` last mutated: a cached result that only touched
    partitions whose stamp predates it is still valid (DESIGN.md §16).
    """

    csr: CSR
    epoch: int
    delta: DeltaLog
    stamps: np.ndarray          # (n_partitions,) int64 last-mutated epoch
    n_partitions: int
    compact_threshold: float = 0.25

    @classmethod
    def wrap(cls, csr: CSR, *, n_partitions: int = 8,
             compact_threshold: float = 0.25) -> "GraphHandle":
        if n_partitions < 1:
            raise ValueError("n_partitions must be >= 1")
        return cls(_canonical(csr), 0,
                   DeltaLog.empty(weighted=csr.values is not None),
                   np.zeros((n_partitions,), np.int64), int(n_partitions),
                   float(compact_threshold))

    @property
    def per_partition(self) -> int:
        return -(-self.csr.n_rows // self.n_partitions)

    def partition_of(self, vertices) -> np.ndarray:
        """Owning partition of each vertex (block rule)."""
        return np.asarray(vertices, np.int64) // self.per_partition

    def partition_edge_counts(self) -> np.ndarray:
        """(n_partitions,) edges whose SOURCE row each partition owns —
        what a block-sharded deployment stores (and must reship) per
        partition."""
        indptr = np.asarray(self.csr.indptr, np.int64)
        per = self.per_partition
        bounds = np.minimum(np.arange(self.n_partitions + 1) * per,
                            self.csr.n_rows)
        return np.diff(indptr[bounds])

    # -- mutation ----------------------------------------------------------

    def apply(self, inserts=None, deletes=None) -> tuple["GraphHandle",
                                                         UpdateReport]:
        """Apply one update batch; returns (new handle, report).

        inserts: (rows, cols) or (rows, cols, vals) arrays; vals default 1.0
          on weighted graphs and are ignored on unweighted (values=None)
          graphs.
        deletes: (rows, cols) arrays.
        """
        weighted = self.csr.values is not None
        ins_r, ins_c, ins_v = _coerce_edges(inserts, weighted=weighted)
        del_r, del_c, _ = _coerce_edges(deletes, weighted=False)
        n, ncol = self.csr.n_rows, self.csr.n_cols
        for name, (r, c) in (("insert", (ins_r, ins_c)),
                             ("delete", (del_r, del_c))):
            if r.size and not ((0 <= r).all() and (r < n).all()
                               and (0 <= c).all() and (c < ncol).all()):
                raise ValueError(f"{name} endpoints outside [0, {n}) x "
                                 f"[0, {ncol})")

        csr, stats = _splice_updates(self.csr, ins_r, ins_c, ins_v,
                                     del_r, del_c)
        n_ins, n_del, n_ups, weight_grew = stats
        epoch = self.epoch + 1

        ch_src = np.unique(np.concatenate([ins_r, del_r]))
        ch_all = np.unique(np.concatenate([ins_r, ins_c, del_r, del_c]))
        touched = np.unique(self.partition_of(ch_all)) if ch_all.size \
            else np.zeros((0,), np.int64)
        stamps = self.stamps.copy()
        stamps[touched] = epoch

        delta = self.delta.extend(ins_r, ins_c, ins_v, del_r, del_c)
        compacted = delta.size > self.compact_threshold * max(1, csr.nnz)
        if compacted:
            csr = _canonical(CSR.from_coo(
                *_coo_of(csr), csr.n_rows, csr.n_cols))
            delta = DeltaLog.empty(weighted=weighted)
            get_registry().counter("graph.compactions").inc()
        handle = GraphHandle(csr, epoch, delta, stamps, self.n_partitions,
                             self.compact_threshold)
        report = UpdateReport(
            epoch=epoch, n_inserted=n_ins, n_deleted=n_del, n_upserted=n_ups,
            changed_sources=ch_src, changed_vertices=ch_all,
            touched_partitions=touched,
            monotone_safe=(n_del == 0 and not weight_grew),
            compacted=compacted)
        return handle, report

    def replace(self, csr: CSR) -> "GraphHandle":
        """Whole-graph swap: epoch bumps, every partition is stamped."""
        epoch = self.epoch + 1
        csr = _canonical(csr)
        n_p = self.n_partitions
        return GraphHandle(csr, epoch,
                           DeltaLog.empty(weighted=csr.values is not None),
                           np.full((n_p,), epoch, np.int64), n_p,
                           self.compact_threshold)

    def compact(self) -> "GraphHandle":
        """Explicit compaction: clean ``from_coo`` rebuild + empty log.
        Bit-identical arrays (the overlay splice already keeps the CSR
        canonical — the round-trip test pins this)."""
        csr = CSR.from_coo(*_coo_of(self.csr), self.csr.n_rows,
                           self.csr.n_cols)
        return GraphHandle(csr, self.epoch,
                           DeltaLog.empty(weighted=csr.values is not None),
                           self.stamps.copy(), self.n_partitions,
                           self.compact_threshold)


def _coo_of(csr: CSR):
    indptr = np.asarray(csr.indptr)
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(indptr))
    vals = None if csr.values is None else np.asarray(csr.values)
    return rows, np.asarray(csr.indices, np.int64), vals


def _splice_updates(csr: CSR, ins_r, ins_c, ins_v, del_r, del_c):
    """Overlay-splice one update batch into a canonical CSR.

    Returns (new CSR, (n_inserted, n_deleted, n_upserted, weight_grew)).
    O(m + d log d): delete by sorted-key membership mask, upsert in place,
    splice new edges at their searchsorted positions — the result is
    bit-identical to a clean ``CSR.from_coo`` over the effective edge set.
    """
    n_cols = np.int64(csr.n_cols)
    key = _edge_keys(csr)
    cols = np.asarray(csr.indices, np.int64)
    vals = None if csr.values is None else np.asarray(csr.values, np.float32)

    n_del = 0
    if del_r.size:
        dkey = np.unique(del_r * n_cols + del_c)
        keep = ~np.isin(key, dkey)
        n_del = int(key.size - keep.sum())
        key, cols = key[keep], cols[keep]
        if vals is not None:
            vals = vals[keep]

    n_ins = n_ups = 0
    weight_grew = False
    if ins_r.size:
        ikey = ins_r * n_cols + ins_c
        order = np.argsort(ikey, kind="stable")
        ikey = ikey[order]
        iv = None if ins_v is None else ins_v[order]
        last = np.ones(ikey.size, bool)          # duplicate keys: last wins
        last[:-1] = ikey[1:] != ikey[:-1]
        ikey = ikey[last]
        if iv is not None:
            iv = iv[last]
        pos = np.searchsorted(key, ikey)
        exists = (pos < key.size)
        exists[exists] = key[pos[exists]] == ikey[exists]
        n_ups = int(exists.sum())
        n_ins = int(ikey.size - n_ups)
        if vals is not None and n_ups:
            old = vals[pos[exists]]
            new = iv[exists]
            weight_grew = bool((new > old).any())
            vals = vals.copy()
            vals[pos[exists]] = new
        newkey = ikey[~exists]
        if newkey.size:
            at = pos[~exists]
            key = np.insert(key, at, newkey)
            cols = np.insert(cols, at, newkey % n_cols)
            if vals is not None:
                vals = np.insert(vals, at, iv[~exists])

    rows = key // n_cols
    indptr = np.zeros(csr.n_rows + 1, np.int64)
    np.add.at(indptr, rows + 1, 1)
    indptr = np.cumsum(indptr)
    out = CSR(jnp.asarray(np.asarray(indptr, np.int32)),
              jnp.asarray(np.asarray(cols, np.int32)),
              None if vals is None else jnp.asarray(vals),
              csr.n_rows, csr.n_cols)
    return out, (n_ins, n_del, n_ups, weight_grew)


# ---------------------------------------------------------------------------
# Kernel-facing formats
# ---------------------------------------------------------------------------

def to_padded_ell(csr: CSR, max_nnz_per_row: Optional[int] = None):
    """Pad each row to a fixed nonzero budget.

    Returns (cols (n_rows, k) int32, vals (n_rows, k) f32, mask (n_rows, k) bool).
    Padding entries have col=0, val=0.
    """
    indptr = np.asarray(csr.indptr)
    cols = np.asarray(csr.indices)
    vals = np.asarray(csr.values) if csr.values is not None else np.ones_like(cols, np.float32)
    deg = indptr[1:] - indptr[:-1]
    k = int(max_nnz_per_row or deg.max())
    out_c = np.zeros((csr.n_rows, k), np.int32)
    out_v = np.zeros((csr.n_rows, k), np.float32)
    mask = np.zeros((csr.n_rows, k), bool)
    for r in range(csr.n_rows):  # host-side preprocessing; fine offline
        d = min(int(deg[r]), k)
        s = indptr[r]
        out_c[r, :d] = cols[s:s + d]
        out_v[r, :d] = vals[s:s + d]
        mask[r, :d] = True
    return jnp.asarray(out_c), jnp.asarray(out_v), jnp.asarray(mask)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class BBCSR:
    """Block-bucketed sparse format for the Pallas SpMV kernel.

    Nonzeros are bucketed by (row block, column block) and sorted by
    (row_block, col_block, row); each bucket is padded to a multiple of
    ``tile_nnz``.  The kernel grid walks tiles in that order: the output row
    block is revisited only *consecutively* (legal Pallas accumulation), and
    for each (rb, cb) pair the dense-vector block is DMA'd into VMEM once
    (PIUMA: "DMA gather into SPAD") and gathered/scattered with one-hot MXU
    matmuls.  Every row block gets at least one (possibly all-padding) tile so
    the output is fully initialized.

    rows_local / cols_local : (n_tiles, 1, tile_nnz) int32, local to the
                              block — the unit middle dim is the Pallas
                              kernels' layout: each tile is one (1, T) row
                              whose last two block dims Mosaic accepts
    vals                    : (n_tiles, 1, tile_nnz) f32 (0 on padding)
    tile_rb / tile_cb       : (n_tiles,) int32 — owning row/col block
    tile_init               : (n_tiles,) int32 — 1 on first tile of a row block
    tile_cnt                : (n_tiles,) int32 — real (non-padding) nonzeros
                              in the tile; padding is always the tile's tail,
                              so `slot < tile_cnt` is the validity mask the
                              min/max tile combines need (a padded (0, 0, 0.0)
                              entry is indistinguishable from a real
                              zero-weight edge at the block origin).  None on
                              operands built before the field existed;
                              `to_bbcsr` always fills it.
    """

    rows_local: jnp.ndarray
    cols_local: jnp.ndarray
    vals: jnp.ndarray
    tile_rb: jnp.ndarray
    tile_cb: jnp.ndarray
    tile_init: jnp.ndarray
    n_rows: int
    n_cols: int
    block_rows: int
    block_cols: int
    tile_nnz: int
    tile_cnt: Optional[jnp.ndarray] = None

    def tree_flatten(self):
        return (self.rows_local, self.cols_local, self.vals, self.tile_rb,
                self.tile_cb, self.tile_init, self.tile_cnt), (
            self.n_rows, self.n_cols, self.block_rows, self.block_cols, self.tile_nnz)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children[:6], *aux, tile_cnt=children[6])

    @property
    def n_tiles(self) -> int:
        return int(self.tile_rb.shape[0])

    @property
    def n_row_blocks(self) -> int:
        return -(-self.n_rows // self.block_rows)

    @property
    def n_col_blocks(self) -> int:
        return -(-self.n_cols // self.block_cols)


def to_bbcsr(csr: CSR, *, block_rows: int = 256, block_cols: int = 512,
             tile_nnz: int = 512) -> BBCSR:
    """Host-side conversion CSR -> BBCSR (see BBCSR docstring), vectorized:
    each nonzero's tile and slot follow from its rank inside its
    (row block, col block) bucket."""
    indptr = np.asarray(csr.indptr, np.int64)
    cols = np.asarray(csr.indices, np.int64)
    vals = (np.asarray(csr.values, np.float32) if csr.values is not None
            else np.ones(cols.shape, np.float32))
    rows = np.repeat(np.arange(csr.n_rows, dtype=np.int64), np.diff(indptr))
    rb = rows // block_rows
    cb = cols // block_cols
    order = np.lexsort((rows, cb, rb))
    rows, cols, vals, rb, cb = rows[order], cols[order], vals[order], rb[order], cb[order]

    m = rows.shape[0]
    new = np.ones(m, bool)
    new[1:] = (rb[1:] != rb[:-1]) | (cb[1:] != cb[:-1])
    g_start = np.flatnonzero(new)
    g_cnt = np.diff(np.append(g_start, m))
    g_tiles = -(-g_cnt // tile_nnz)
    g_tile0 = np.cumsum(g_tiles) - g_tiles
    gid = np.cumsum(new) - 1
    pos = np.arange(m) - g_start[gid]
    tile = g_tile0[gid] + pos // tile_nnz
    slot = pos % tile_nnz

    n_full = int(g_tiles.sum())
    # an all-padding tile for every empty row block, so its output block
    # gets initialized
    empty_rb = np.setdiff1d(np.arange(-(-csr.n_rows // block_rows)), rb[g_start])
    n_t = n_full + empty_rb.shape[0]
    t_r = np.zeros((n_t, tile_nnz), np.int32)
    t_c = np.zeros((n_t, tile_nnz), np.int32)
    t_v = np.zeros((n_t, tile_nnz), np.float32)
    t_r[tile, slot] = rows - rb * block_rows
    t_c[tile, slot] = cols - cb * block_cols
    t_v[tile, slot] = vals
    # padding sits at each tile's tail: full tiles, then the remainder
    first = np.arange(n_full) - np.repeat(g_tile0, g_tiles)
    t_m = np.concatenate([np.minimum(tile_nnz, np.repeat(g_cnt, g_tiles)
                                     - first * tile_nnz),
                          np.zeros(empty_rb.shape[0], np.int64)])
    t_rb = np.concatenate([np.repeat(rb[g_start], g_tiles), empty_rb])
    t_cb = np.concatenate([np.repeat(cb[g_start], g_tiles),
                           np.zeros(empty_rb.shape[0], np.int64)])
    order = np.argsort(t_rb, kind="stable")
    t_r, t_c, t_v, t_m, t_rb, t_cb = (
        a[order] for a in (t_r, t_c, t_v, t_m, t_rb, t_cb))
    init = np.ones(n_t, np.int32)
    init[1:] = t_rb[1:] != t_rb[:-1]
    return BBCSR(
        jnp.asarray(t_r[:, None, :]), jnp.asarray(t_c[:, None, :]),
        jnp.asarray(t_v[:, None, :]),
        jnp.asarray(t_rb, jnp.int32), jnp.asarray(t_cb, jnp.int32),
        jnp.asarray(init), csr.n_rows, csr.n_cols, block_rows, block_cols,
        tile_nnz, tile_cnt=jnp.asarray(t_m, jnp.int32),
    )
