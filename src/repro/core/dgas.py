"""DGAS: distributed global address space, and the ATT (address translation table).

PIUMA exposes one flat address space across all nodes; *programmable* ATT rules
decide where each application address physically lives (interleaved, block
partitioned, ...).  On a TPU mesh the physical location is the device shard, so
the ATT here is the programmable map

    global element id  ->  (owner shard, local offset)

used consistently by the graph partitioner, the offload engines and the
distributed algorithms.  Because every primitive consults the ATT (instead of
hard-coding ``id % n`` or ``id // per``), the *same* algorithm code runs under
any distribution rule — the paper's "application code does not need to change
for multinode execution".
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "ATT",
    "interleave_rule",
    "block_rule",
    "custom_boundary_rule",
    "degree_balanced_rule",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ATT:
    """Address translation table: global id -> (owner, local offset).

    ``boundaries`` is only used by boundary-based rules; for the closed-form
    rules it is a size-1 placeholder so the pytree structure is static.

    Attributes:
      kind: 'interleave' | 'block' | 'boundaries'.
      n_global: size of the global id space.
      n_shards: number of owners (devices along the sharded axis).
      boundaries: (n_shards+1,) int32 — shard s owns [boundaries[s], boundaries[s+1]).
    """

    kind: str
    n_global: int
    n_shards: int
    boundaries: jnp.ndarray  # (n_shards+1,) for 'boundaries', else (1,)

    # -- pytree plumbing ---------------------------------------------------
    def tree_flatten(self):
        return (self.boundaries,), (self.kind, self.n_global, self.n_shards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        kind, n_global, n_shards = aux
        return cls(kind, n_global, n_shards, children[0])

    # -- core queries -------------------------------------------------------
    @property
    def per_shard(self) -> int:
        """Padded local capacity (max elements any shard owns)."""
        if self.kind == "interleave":
            return -(-self.n_global // self.n_shards)
        if self.kind == "block":
            return -(-self.n_global // self.n_shards)
        # boundary rule: static upper bound = n_global (callers should use
        # local_capacity computed at build time instead); we store it densely.
        return int(self._max_span)

    @property
    def _max_span(self):
        b = np.asarray(self.boundaries)
        if b.shape[0] <= 1:
            return -(-self.n_global // self.n_shards)
        return int(np.max(b[1:] - b[:-1]))

    def _xp(self, gid):
        """NumPy for host ids (no device op, so nothing compiles per number
        of ids), jnp for device or traced ids or traced boundaries."""
        if isinstance(gid, jax.Array) or \
                isinstance(self.boundaries, jax.core.Tracer):
            return jnp
        return np

    def owner(self, gid: jnp.ndarray) -> jnp.ndarray:
        """Owner shard of each global id, in the array module of ``gid``."""
        if self.kind == "interleave":
            return gid % self.n_shards
        if self.kind == "block":
            per = -(-self.n_global // self.n_shards)
            return gid // per
        # boundaries: owner s satisfies boundaries[s] <= gid < boundaries[s+1]
        xp = self._xp(gid)
        return xp.clip(
            xp.searchsorted(xp.asarray(self.boundaries), gid, side="right") - 1,
            0,
            self.n_shards - 1,
        )

    def local(self, gid: jnp.ndarray) -> jnp.ndarray:
        """Local offset of each global id within its owner shard, in the
        array module of ``gid``."""
        if self.kind == "interleave":
            return gid // self.n_shards
        if self.kind == "block":
            per = -(-self.n_global // self.n_shards)
            return gid % per
        xp = self._xp(gid)
        return gid - xp.take(xp.asarray(self.boundaries), self.owner(gid))

    def to_global(self, shard: jnp.ndarray, local: jnp.ndarray) -> jnp.ndarray:
        """Inverse translation: (owner, local) -> global id."""
        if self.kind == "interleave":
            return local * self.n_shards + shard
        if self.kind == "block":
            per = -(-self.n_global // self.n_shards)
            return shard * per + local
        return jnp.take(self.boundaries, shard) + local

    def flat_slot(self, gid: jnp.ndarray) -> jnp.ndarray:
        """Dense outbox address of each global id: owner * per_shard + local.

        The async placement's deferred-message buffers (offload.buffered_flush)
        are laid out as (n_shards * per_shard, ...) so that a plain reshape
        splits them per destination peer; this is the slot a message for
        ``gid`` occupies in such a buffer.
        """
        return self.owner(gid) * self.per_shard + self.local(gid)

    def shard_slice(self, shard: int) -> tuple[int, int]:
        """Host-side: (start, count) of globally-contiguous ids owned by `shard`.

        Only meaningful for contiguous rules ('block' / 'boundaries').
        """
        if self.kind == "block":
            per = -(-self.n_global // self.n_shards)
            start = shard * per
            return start, max(0, min(per, self.n_global - start))
        if self.kind == "boundaries":
            b = np.asarray(self.boundaries)
            return int(b[shard]), int(b[shard + 1] - b[shard])
        raise ValueError("interleave rule has no contiguous shard slice")


def interleave_rule(n_global: int, n_shards: int) -> ATT:
    """PIUMA 'address interleaved' rule: id % n_shards."""
    return ATT("interleave", n_global, n_shards, jnp.zeros((1,), jnp.int32))


def block_rule(n_global: int, n_shards: int) -> ATT:
    """PIUMA 'block partitioned' rule: contiguous equal blocks."""
    return ATT("block", n_global, n_shards, jnp.zeros((1,), jnp.int32))


def custom_boundary_rule(boundaries: np.ndarray, n_global: int) -> ATT:
    """Arbitrary contiguous partition given explicit boundaries (n_shards+1,)."""
    b = jnp.asarray(np.asarray(boundaries, dtype=np.int32))
    return ATT("boundaries", n_global, int(b.shape[0]) - 1, b)


def degree_balanced_rule(indptr: np.ndarray, n_shards: int) -> ATT:
    """Contiguous row partition balancing *nonzeros* (the paper's SpMV rule:

    "rows are partitioned across the threads based on the number of
    non-zeros for a balanced execution").
    """
    indptr = np.asarray(indptr)
    n_rows = indptr.shape[0] - 1
    nnz = int(indptr[-1])
    targets = (np.arange(1, n_shards) * (nnz / n_shards)).astype(np.int64)
    cuts = np.searchsorted(indptr, targets, side="left")
    boundaries = np.concatenate([[0], cuts, [n_rows]]).astype(np.int32)
    boundaries = np.maximum.accumulate(boundaries)  # monotone under ties
    return custom_boundary_rule(boundaries, n_rows)
