"""The one traffic generator: draws a cell's work from its mix file and the seed.

A mix file (``chipbench/traffic/<name>.json``) names its driver and gives
the parameters read here:

* ``kinds`` — the share of each query kind (``reach``, ``dist``, ``ppr``);
  every run holds them in exactly these shares.
* ``rate_qps`` (open loop) — the offered rate.  A run of ``seconds`` offers
  ``round(rate_qps * seconds)`` queries, the gaps between arrivals those of
  sorted uniform times: a Poisson process conditioned on its count.
* ``ppr_k`` — the k of every PPR top-k query.
* ``keys_per_round`` (searches) — how many search keys a round cycles over.
* ``pool_seed`` — fixes the work: the open loop's queries and arrival
  times, or the set of search keys, whose order ``--seed`` draws.  Every
  seed then brings the same work.  The open loop keeps even the order: a
  Distance batch takes as long as its slowest lane, so which queries share
  a batch moves the tail, and seed-drawn orders spread ``p95_ms`` by 15%
  where runs of one order spread by under 2% (one TPU v5e).  An open-loop
  run is then the same whatever ``--seed`` it gets.

Sources, and search keys, are uniform over vertices with an out-edge;
targets are uniform over all vertices.  A query is a tuple ``(kind, source,
target_or_k)``.
"""
from __future__ import annotations

import numpy as np

__all__ = ["kind_sequence", "served_queries", "open_loop_schedule",
           "search_keys"]


def kind_sequence(kinds: dict, count: int, rng) -> list:
    """``count`` kinds in the exact shares of ``kinds`` (largest remainder),
    shuffled."""
    names = sorted(kinds)
    share = np.array([kinds[k] for k in names], float)
    share = share / share.sum() * count
    n = np.floor(share).astype(int)
    for i in np.argsort(n - share)[: count - n.sum()]:
        n[i] += 1
    seq = np.repeat(np.arange(len(names)), n)
    rng.shuffle(seq)
    return [names[i] for i in seq]


def served_queries(traffic: dict, deg: np.ndarray, count: int, rng) -> list:
    sources = np.flatnonzero(deg > 0)
    kinds = kind_sequence(traffic["kinds"], count, rng)
    src = rng.choice(sources, count)
    tgt = rng.integers(0, deg.shape[0], count)
    k = int(traffic.get("ppr_k", 0))
    return [(kind, int(s), k if kind == "ppr" else int(t))
            for kind, s, t in zip(kinds, src, tgt)]


def open_loop_schedule(traffic: dict, deg: np.ndarray, seconds: float):
    """(due times in seconds from the window's start, queries), fixed by
    the mix's ``pool_seed``."""
    count = int(round(traffic["rate_qps"] * seconds))
    pool = np.random.default_rng(traffic["pool_seed"])
    due = np.sort(pool.random(count)) * seconds
    return due, served_queries(traffic, deg, count, pool)


def search_keys(traffic: dict, deg: np.ndarray, rng) -> np.ndarray:
    """The pool's search keys, in an order drawn from ``rng``."""
    pool = np.random.default_rng(traffic["pool_seed"])
    return rng.permutation(pool.choice(np.flatnonzero(deg > 0),
                                       traffic["keys_per_round"],
                                       replace=False))
