"""Pure-jnp oracles for every Pallas kernel (the `assert_allclose` targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.graph import BBCSR

__all__ = ["spmv_bbcsr_ref", "spmspv_bbcsr_ref", "segment_sum_ref", "embedding_bag_ref",
           "flash_attention_ref"]


def spmv_bbcsr_ref(bb: BBCSR, x: jnp.ndarray) -> jnp.ndarray:
    """y = A @ x straight off the tile arrays (padding vals are 0)."""
    rows = (bb.tile_rb[:, None, None] * bb.block_rows + bb.rows_local).reshape(-1)
    cols = (bb.tile_cb[:, None, None] * bb.block_cols + bb.cols_local).reshape(-1)
    vals = bb.vals.reshape(-1)
    x_pad = jnp.pad(x, (0, bb.n_col_blocks * bb.block_cols - x.shape[0]))
    contrib = vals * jnp.take(x_pad, cols)
    y = jax.ops.segment_sum(contrib, rows,
                            num_segments=bb.n_row_blocks * bb.block_rows)
    return y[: bb.n_rows]


def spmspv_bbcsr_ref(bb: BBCSR, x: jnp.ndarray, tile_active: jnp.ndarray,
                     combine: str = "add") -> jnp.ndarray:
    """y = A ⊕ x over the active tiles only: 'add' sums val * x[col], 'min' /
    'max' reduce x[col] + val; rows no live entry reaches hold the combine's
    identity (0, +inf, -inf)."""
    rows = (bb.tile_rb[:, None, None] * bb.block_rows + bb.rows_local).reshape(-1)
    cols = (bb.tile_cb[:, None, None] * bb.block_cols + bb.cols_local).reshape(-1)
    vals = bb.vals.reshape(-1)
    slot = jnp.arange(bb.tile_nnz)[None, None, :]
    live = ((tile_active[:, None, None] > 0)
            & (slot < bb.tile_cnt[:, None, None])).reshape(-1)
    n_out = bb.n_row_blocks * bb.block_rows
    pad = bb.n_col_blocks * bb.block_cols - x.shape[0]
    if combine == "add":
        xg = jnp.take(jnp.pad(x, (0, pad)), cols)
        y = jax.ops.segment_sum(jnp.where(live, vals * xg, 0.0), rows,
                                num_segments=n_out)
    else:
        ident = jnp.inf if combine == "min" else -jnp.inf
        xg = jnp.take(jnp.pad(x, (0, pad), constant_values=ident), cols)
        seg = jax.ops.segment_min if combine == "min" else jax.ops.segment_max
        y = seg(jnp.where(live, xg + vals, ident), rows, num_segments=n_out)
    return y[: bb.n_rows]


def segment_sum_ref(data: jnp.ndarray, seg: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    """Sorted-or-not segment sum; rows with seg<0 are dropped."""
    valid = seg >= 0
    safe = jnp.where(valid, seg, 0)
    w = valid.reshape(valid.shape + (1,) * (data.ndim - 1)).astype(data.dtype)
    return jax.ops.segment_sum(data * w, safe, num_segments=num_segments)


def embedding_bag_ref(table: jnp.ndarray, idx: jnp.ndarray, bag: jnp.ndarray,
                      n_bags: int, weights: Optional[jnp.ndarray] = None,
                      mode: str = "sum") -> jnp.ndarray:
    """EmbeddingBag: sum/mean of table rows grouped by bag id (idx<0 = padding)."""
    valid = idx >= 0
    rows = jnp.take(table, jnp.where(valid, idx, 0), axis=0)
    w = jnp.where(valid, 1.0, 0.0) if weights is None else jnp.where(valid, weights, 0.0)
    rows = rows * w[:, None].astype(rows.dtype)
    out = jax.ops.segment_sum(rows, jnp.where(valid, bag, n_bags),
                              num_segments=n_bags + 1)[:n_bags]
    if mode == "mean":
        cnt = jax.ops.segment_sum(w, jnp.where(valid, bag, n_bags),
                                  num_segments=n_bags + 1)[:n_bags]
        out = out / jnp.maximum(cnt, 1e-9)[:, None]
    return out


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: Optional[int] = None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Reference attention.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) with Hq % Hkv == 0 (GQA).
    causal alignment assumes q occupies the LAST Sq positions of the kv range.
    window: sliding window — key j visible to query position p iff p-window < j <= p.
    """
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    kq = jnp.repeat(k, g, axis=1)
    vq = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kq.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq)[:, None] + (Skv - Sq)
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, vq.astype(jnp.float32)).astype(q.dtype)
