"""Pallas TPU kernel: BBCSR SpMV — PIUMA's "DMA gather + selective caching".

Mapping of the paper's SpMV optimizations onto the TPU memory hierarchy
(DESIGN.md §2):

* *selective caching*  — CSR values/indices stream sequentially through VMEM
  tiles (the "cache the matrix" decision); the dense vector is never
  replicated: exactly one `block_cols` slice is resident per column block.
* *DMA gather to SPAD* — the Pallas pipeline DMAs the vector block into VMEM
  (SPAD) while the previous tile computes (double buffering = the offload
  engine running "in the background").
* *8-byte access*      — HBM cannot do 8 B, so the fine-grained gather/scatter
  happens **inside VMEM** as one-hot MXU matmuls: gather = onehot(cols) @ x,
  scatter = contribᵀ @ onehot(rows). Irregularity is densified locally while
  the global structure stays sparse.

Grid: one step per tile, ordered by (row_block, col_block); output row blocks
are revisited only consecutively, so accumulation uses the standard
init-on-first-visit pattern driven by the host-precomputed `tile_init` flags
(scalar-prefetched, like the tile→block maps).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.graph import BBCSR

__all__ = ["spmv_bbcsr_kernel_call", "spmspv_bbcsr_kernel_call",
           "collapse_inactive_blocks"]


def collapse_inactive_blocks(tile_cb: jnp.ndarray,
                             tile_active: jnp.ndarray) -> jnp.ndarray:
    """x-block DMA schedule for SpMSpV: drop the fetch for inactive tiles.

    The Pallas pipeline issues a new x-block DMA whenever consecutive grid
    steps map to *different* block indices.  `pl.when` alone only skips the
    compute — the inactive tile's x block still streams into VMEM dead.  So
    the x index_map is collapsed: an inactive tile re-uses the most recent
    active tile's column block (same index => no new DMA), and tiles before
    the first active one pin block 0.  Works for any engine operand the
    active mask derives from — BFS frontiers and the structured-combine
    programs' weight operands alike (`engine.tile_active`).

    Returns the (n_tiles,) int32 schedule handed to the kernel as its cb
    scalar-prefetch operand (the kernel body itself never reads cb).
    """
    ta = tile_active.astype(jnp.int32)
    n = ta.shape[0]
    idx = jnp.where(ta == 1, jnp.arange(n, dtype=jnp.int32), -1)
    last_active = jax.lax.cummax(idx)
    safe = jnp.maximum(last_active, 0)
    return jnp.where(last_active >= 0, jnp.take(tile_cb, safe), 0).astype(jnp.int32)


def _col(v: jnp.ndarray) -> jnp.ndarray:
    """(1, L) row vector -> (L, 1) column, as one aligned 2-D transpose
    (Mosaic transposes (8, L) tiles; a bare (1, L) reshape does not lower)."""
    return jnp.transpose(jnp.broadcast_to(v, (8, v.shape[1])))[:, :1]


def _row(v: jnp.ndarray) -> jnp.ndarray:
    """(L, 1) column -> (1, L) row vector (inverse of :func:`_col`)."""
    return jnp.transpose(jnp.broadcast_to(v, (v.shape[0], 128)))[:1, :]


# Layout: every tile array is (n_tiles, 1, tile_nnz), the vector x is
# (n_col_blocks, 1, block_cols) and y is (n_row_blocks, 1, block_rows); the
# BlockSpecs squeeze the leading dim, so each kernel ref is a (1, width)
# row — the last two block dims equal the array's, which Mosaic accepts for
# any width.  Inside a tile, lanes index the tile's nonzeros (T) and the
# one-hot planes put the block coordinate on sublanes: (C, T) and (R, T).

def _tile_yblk(rows_ref, cols_ref, vals_ref, x_ref, *, block_rows: int,
               block_cols: int, tile_nnz: int):
    """One tile's dense output block (1, R): gather + scatter on the MXU."""
    cols = cols_ref[...]                                    # (1, T) local col ids
    rows = rows_ref[...]                                    # (1, T) local row ids
    vals = vals_ref[...]                                    # (1, T) 0 on padding
    xblk = x_ref[...]                                       # (1, C) VMEM-resident vector block
    hi = jax.lax.Precision.HIGHEST

    # fine-grained gather inside VMEM, expressed on the MXU
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (block_cols, tile_nnz), 0)
    onehot_g = (col_iota == cols).astype(jnp.float32)               # (C, T)
    gathered = jax.lax.dot_general(
        xblk, onehot_g, (((1,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)                         # (1, T)

    contrib = vals * gathered                                       # (1, T)

    # fine-grained scatter-add inside VMEM, also on the MXU
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (block_rows, tile_nnz), 0)
    onehot_s = (row_iota == rows).astype(jnp.float32)               # (R, T)
    return jax.lax.dot_general(
        contrib, onehot_s, (((1,), (1,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)                        # (1, R)


def _kernel(rb_ref, cb_ref, init_ref, rows_ref, cols_ref, vals_ref, x_ref, y_ref,
            *, block_rows: int, block_cols: int, tile_nnz: int):
    i = pl.program_id(0)
    yblk = _tile_yblk(rows_ref, cols_ref, vals_ref, x_ref,
                      block_rows=block_rows, block_cols=block_cols,
                      tile_nnz=tile_nnz)

    @pl.when(init_ref[i] == 1)
    def _init():
        y_ref[...] = yblk

    @pl.when(init_ref[i] == 0)
    def _acc():
        y_ref[...] += yblk


def _spmspv_kernel(rb_ref, cb_ref, init_ref, act_ref, rows_ref, cols_ref,
                   vals_ref, x_ref, y_ref, *, block_rows: int, block_cols: int,
                   tile_nnz: int):
    """SpMSpV: the scalar-prefetched `act` flag marks tiles whose column block
    holds at least one active (nonzero) vector entry; inactive tiles skip the
    gather/compute entirely (work ∝ active columns, the direction-optimizing
    engine's sparse step) and only zero-initialize their output block."""
    i = pl.program_id(0)
    act = act_ref[i]

    @pl.when(jnp.logical_and(init_ref[i] == 1, act == 0))
    def _zero():
        y_ref[...] = jnp.zeros((1, block_rows), jnp.float32)

    @pl.when(act == 1)
    def _compute():
        yblk = _tile_yblk(rows_ref, cols_ref, vals_ref, x_ref,
                          block_rows=block_rows, block_cols=block_cols,
                          tile_nnz=tile_nnz)

        @pl.when(init_ref[i] == 1)
        def _init():
            y_ref[...] = yblk

        @pl.when(init_ref[i] == 0)
        def _acc():
            y_ref[...] += yblk


def _tile_yblk_select(rows_ref, cols_ref, vals_ref, x_ref, cnt, *,
                      block_rows: int, block_cols: int, tile_nnz: int,
                      combine: str):
    """One tile's output block (1, R) for the min/max combines, by masked
    select.

    The MXU one-hot matmuls only implement *additive* gather/scatter (0 * x
    annihilates, + accumulates) — and a one-hot gather of a vector holding
    the min-identity +inf would produce 0 * inf = NaN.  So the min/max tile
    combine stays on the VPU as two masked-select reductions:

    * gather:  sel[c, t] = x[c] where cols[t] == c else identity; the
      column reduction picks x[cols[t]] exactly (one live column per t).
    * relax:   contrib = gathered + vals — the (min,+)/(max,+) semirings'
      edge op; slots past ``cnt`` (padding is always a tile's tail) park at
      the identity (a padded (0, 0, 0.0) slot is otherwise indistinguishable
      from a real edge).
    * scatter: y[r] = reduce_t contrib[t] where rows[t] == r else identity —
      the same select pattern over (R, T).
    """
    cols = cols_ref[...]                                    # (1, T)
    rows = rows_ref[...]                                    # (1, T)
    vals = vals_ref[...]                                    # (1, T)
    xcol = _col(x_ref[...])                                 # (C, 1)
    ident = jnp.float32(jnp.inf if combine == "min" else -jnp.inf)
    red = jnp.min if combine == "min" else jnp.max

    col_iota = jax.lax.broadcasted_iota(jnp.int32, (block_cols, tile_nnz), 0)
    sel = jnp.where(col_iota == cols, xcol, ident)                  # (C, T)
    gathered = red(sel, axis=0, keepdims=True)                      # (1, T)

    slot = jax.lax.broadcasted_iota(jnp.int32, (1, tile_nnz), 1)
    contrib = jnp.where(slot < cnt, gathered + vals, ident)         # (1, T)

    row_iota = jax.lax.broadcasted_iota(jnp.int32, (block_rows, tile_nnz), 0)
    scat = jnp.where(row_iota == rows, contrib, ident)              # (R, T)
    return _row(red(scat, axis=1, keepdims=True))                   # (1, R)


def _spmspv_select_kernel(rb_ref, cb_ref, init_ref, act_ref, cnt_ref,
                          rows_ref, cols_ref, vals_ref, x_ref, y_ref, *,
                          block_rows: int, block_cols: int, tile_nnz: int,
                          combine: str):
    """SpMSpV with a min/max destination combine: same tile schedule as the
    'add' kernel (inactive tiles skip compute and their x DMA is collapsed),
    but blocks initialize to the combine identity and revisits reduce with
    min/max instead of accumulating.  ``cnt_ref`` is the scalar-prefetched
    per-tile real-nonzero count (`BBCSR.tile_cnt`)."""
    i = pl.program_id(0)
    act = act_ref[i]
    ident = jnp.float32(jnp.inf if combine == "min" else -jnp.inf)

    @pl.when(jnp.logical_and(init_ref[i] == 1, act == 0))
    def _ident():
        y_ref[...] = jnp.full((1, block_rows), ident, jnp.float32)

    @pl.when(act == 1)
    def _compute():
        yblk = _tile_yblk_select(rows_ref, cols_ref, vals_ref, x_ref,
                                 cnt_ref[i],
                                 block_rows=block_rows, block_cols=block_cols,
                                 tile_nnz=tile_nnz, combine=combine)

        @pl.when(init_ref[i] == 1)
        def _init():
            y_ref[...] = yblk

        @pl.when(init_ref[i] == 0)
        def _acc():
            if combine == "min":
                y_ref[...] = jnp.minimum(y_ref[...], yblk)
            else:
                y_ref[...] = jnp.maximum(y_ref[...], yblk)


def _row_spec(width: int, block_of) -> pl.BlockSpec:
    """BlockSpec of one (1, width) row of an (n, 1, width) array; `block_of`
    maps (grid index, *scalar-prefetch refs) to the leading index."""
    return pl.BlockSpec((None, 1, width),
                        lambda *a: (block_of(*a), 0, 0))


def spmv_bbcsr_kernel_call(bb: BBCSR, x: jnp.ndarray, *, interpret: bool = True
                           ) -> jnp.ndarray:
    """Launch the kernel. Returns y (n_rows,) float32."""
    n_rb, n_cb = bb.n_row_blocks, bb.n_col_blocks
    x_pad = jnp.pad(x.astype(jnp.float32), (0, n_cb * bb.block_cols - x.shape[0]))
    x3d = x_pad.reshape(n_cb, 1, bb.block_cols)
    kern = functools.partial(_kernel, block_rows=bb.block_rows,
                             block_cols=bb.block_cols, tile_nnz=bb.tile_nnz)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # tile_rb, tile_cb, tile_init
        grid=(bb.n_tiles,),
        in_specs=[
            _row_spec(bb.tile_nnz, lambda i, rb, cb, ini: i),      # rows
            _row_spec(bb.tile_nnz, lambda i, rb, cb, ini: i),      # cols
            _row_spec(bb.tile_nnz, lambda i, rb, cb, ini: i),      # vals
            _row_spec(bb.block_cols, lambda i, rb, cb, ini: cb[i]),  # x blk
        ],
        out_specs=_row_spec(bb.block_rows, lambda i, rb, cb, ini: rb[i]),
    )
    y3d = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rb, 1, bb.block_rows), jnp.float32),
        interpret=interpret,
    )(bb.tile_rb, bb.tile_cb, bb.tile_init,
      bb.rows_local, bb.cols_local, bb.vals, x3d)
    return y3d.reshape(-1)[: bb.n_rows]


def spmspv_bbcsr_kernel_call(bb: BBCSR, x: jnp.ndarray,
                             tile_active: jnp.ndarray, *,
                             combine: str = "add",
                             interpret: bool = True) -> jnp.ndarray:
    """y = A ⊕ x for a sparsely-populated x, ⊕ per ``combine``.

    `tile_active` is (n_tiles,) int32 — 1 iff the tile's column block holds a
    nonzero x entry (see `engine.tile_active`).  Inactive tiles skip the
    compute (`pl.when`) *and* the x-block DMA (their index_map entry is
    collapsed onto the previous active tile's block via
    `collapse_inactive_blocks`), so both tile work and VMEM traffic scale
    with the active column blocks instead of nnz(A).

    combine='add' (default) is the MXU one-hot path computing val * x[col];
    'min'/'max' run the masked-select tile combine relaxing x[col] + val
    (the (min,+)/(max,+) distance semirings) — they need ``bb.tile_cnt`` and
    the caller's "active" convention flips to "x[col] != identity" (the
    engine's frontier mask covers both).  Untouched rows return the combine
    identity.
    """
    if combine not in ("add", "min", "max"):
        raise ValueError(f"combine must be 'add', 'min' or 'max', got {combine!r}")
    n_rb, n_cb = bb.n_row_blocks, bb.n_col_blocks
    pad_val = 0.0 if combine == "add" else float("inf") if combine == "min" \
        else float("-inf")
    x_pad = jnp.pad(x.astype(jnp.float32),
                    (0, n_cb * bb.block_cols - x.shape[0]),
                    constant_values=pad_val)
    x3d = x_pad.reshape(n_cb, 1, bb.block_cols)
    cb_sched = collapse_inactive_blocks(bb.tile_cb, tile_active)
    if combine == "add":
        kern = functools.partial(_spmspv_kernel, block_rows=bb.block_rows,
                                 block_cols=bb.block_cols, tile_nnz=bb.tile_nnz)
        # tile_rb, tile_cb, tile_init, tile_active
        scalars = (bb.tile_rb, cb_sched, bb.tile_init,
                   tile_active.astype(jnp.int32))

        def tile_spec():
            return _row_spec(bb.tile_nnz, lambda i, rb, cb, ini, act: i)

        x_spec = _row_spec(bb.block_cols, lambda i, rb, cb, ini, act: cb[i])
        y_spec = _row_spec(bb.block_rows, lambda i, rb, cb, ini, act: rb[i])
    else:
        if bb.tile_cnt is None:
            raise ValueError("min/max combines need the BBCSR per-tile "
                             "padding counts (mask) — rebuild the operand "
                             "with to_bbcsr")
        kern = functools.partial(_spmspv_select_kernel,
                                 block_rows=bb.block_rows,
                                 block_cols=bb.block_cols,
                                 tile_nnz=bb.tile_nnz, combine=combine)
        # ... + tile_cnt (the padding boundary per tile)
        scalars = (bb.tile_rb, cb_sched, bb.tile_init,
                   tile_active.astype(jnp.int32), bb.tile_cnt)

        def tile_spec():
            return _row_spec(bb.tile_nnz, lambda i, rb, cb, ini, act, cnt: i)

        x_spec = _row_spec(bb.block_cols,
                           lambda i, rb, cb, ini, act, cnt: cb[i])
        y_spec = _row_spec(bb.block_rows,
                           lambda i, rb, cb, ini, act, cnt: rb[i])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(bb.n_tiles,),
        in_specs=[tile_spec() for _ in range(3)] + [x_spec],
        out_specs=y_spec,
    )
    y3d = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rb, 1, bb.block_rows), jnp.float32),
        interpret=interpret,
    )(*scalars, bb.rows_local, bb.cols_local, bb.vals, x3d)
    return y3d.reshape(-1)[: bb.n_rows]
