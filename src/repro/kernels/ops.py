"""jit'd public wrappers for the Pallas kernels.

Each graph-kernel call runs under a ``kernel.<name>`` named scope, so a
device trace attributes its time by name (op metadata only).

On CPU (this container) the kernels execute with ``interpret=True`` (the
kernel body runs as traced jnp — numerically identical); on a real TPU they
compile to Mosaic.  ``interpret=None`` auto-detects.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .. import tune as _tune
from ..core.graph import BBCSR
from . import embedding_bag as _eb
from . import flash_attention as _fa
from . import ref as ref
from . import segment_sum as _ss
from . import spmv_dma as _spmv

__all__ = ["spmv_dma", "spmspv_dma", "segment_sum_sorted", "embedding_bag",
           "flash_attention"]

# segment-sum kernel VMEM budget: out (M, d) + onehot (bn, M) in f32
_SEGSUM_VMEM_LIMIT = 4 * 1024 * 1024


def _interp(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def spmv_dma(bb: BBCSR, x: jnp.ndarray, *, interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = A @ x via the DMA-gather/selective-caching kernel."""
    with jax.named_scope("kernel.spmv_dma"):
        return _spmv.spmv_bbcsr_kernel_call(bb, x,
                                            interpret=_interp(interpret))


def spmspv_dma(bb: BBCSR, x: jnp.ndarray, tile_active: jnp.ndarray, *,
               combine: str = "add",
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """y = A ⊕ x for sparse x; tiles whose column block is inactive (per
    `tile_active`, see `core.engine.tile_active`) skip compute.  combine:
    'add' (val * x[col], MXU one-hot path) or 'min' / 'max' (x[col] + val,
    masked-select tile combine — the distance semirings; needs
    bb.tile_cnt)."""
    with jax.named_scope("kernel.spmspv_dma"):
        return _spmv.spmspv_bbcsr_kernel_call(bb, x, tile_active,
                                              combine=combine,
                                              interpret=_interp(interpret))


def segment_sum_sorted(data: jnp.ndarray, seg: jnp.ndarray, num_segments: int,
                       *, block_n: Optional[int] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sorted segment sum. Falls back to jax.ops.segment_sum above the VMEM cap.

    block_n: stream tile width; None takes the tuned value (repro.tune,
    explicit kwarg wins)."""
    block_n = int(_tune.resolve("kernels.segment_sum.block_n",
                                explicit=block_n, n=num_segments))
    d = data.shape[-1]
    if 4 * num_segments * (d + block_n) > _SEGSUM_VMEM_LIMIT:
        return ref.segment_sum_ref(data, seg, num_segments)
    return _ss.segment_sum_kernel_call(data, seg, num_segments, block_n=block_n,
                                       interpret=_interp(interpret))


def embedding_bag(table: jnp.ndarray, idx: jnp.ndarray, bag: jnp.ndarray,
                  n_bags: int, weights: Optional[jnp.ndarray] = None,
                  mode: str = "sum", *, presorted: bool = False,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """EmbeddingBag(sum|mean). idx (N,) int32 (-1 pad), bag (N,) int32 in [0, n_bags).

    The kernel needs the stream sorted by bag with every bag present; unless
    `presorted`, this wrapper adds one sentinel per bag and sorts (stable).
    """
    if not presorted:
        sent_idx = jnp.full((n_bags,), -1, jnp.int32)
        sent_bag = jnp.arange(n_bags, dtype=jnp.int32)
        idx_all = jnp.concatenate([idx.astype(jnp.int32), sent_idx])
        bag_all = jnp.concatenate([bag.astype(jnp.int32), sent_bag])
        w_all = (None if weights is None else
                 jnp.concatenate([weights, jnp.zeros((n_bags,), weights.dtype)]))
        order = jnp.argsort(bag_all, stable=True)
        idx_all = jnp.take(idx_all, order)
        bag_all = jnp.take(bag_all, order)
        w_all = None if w_all is None else jnp.take(w_all, order)
    else:
        idx_all, bag_all, w_all = idx, bag, weights
    out = _eb.embedding_bag_kernel_call(table, idx_all, bag_all, n_bags, w_all,
                                        interpret=_interp(interpret))
    if mode == "mean":
        valid = (idx_all >= 0)
        w = valid.astype(jnp.float32) if w_all is None else jnp.where(valid, w_all, 0.0)
        cnt = jax.ops.segment_sum(w, bag_all, num_segments=n_bags)
        out = out / jnp.maximum(cnt, 1e-9)[:, None]
    return out


def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Flash attention with GQA/causal/sliding-window. See flash_attention.py.

    block_q / block_k: tile shape; None takes the tuned values (repro.tune,
    explicit kwargs win)."""
    seq = q.shape[-2]
    block_q = int(_tune.resolve("kernels.flash_attention.block_q",
                                explicit=block_q, n=seq))
    block_k = int(_tune.resolve("kernels.flash_attention.block_k",
                                explicit=block_k, n=seq))
    return _fa.flash_attention_kernel_call(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, interpret=_interp(interpret))
