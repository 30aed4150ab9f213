"""Graph500 kernels 2 and 3: single-source searches, one after another.

Each search is the public ``repro.core.algorithms.bfs`` or ``sssp``, jitted
once with the graph (``CSR.for_jit``) and the key as arguments, so no search
compiles in the window; SSSP's delta is resolved once at set-up by
``auto_delta``, as the service does.  The keys are put on the device at
set-up.

The window runs whole rounds, each over every key of the round once, until
the window's seconds are over at the end of a round: every run does the
same searches, only in another order.  Within a round the next ``DEPTH - 1``
searches wait on the device while the host reads back and counts the one
that finished, so the device does not idle on the host between searches.
TEPS is the edges of every search over the time from the first search's
dispatch to the last one's read-back.
"""
from __future__ import annotations

import gc
from collections import deque

import numpy as np

from .. import check, generate, reference, system, work

DEPTH = 3       # searches in flight on the device within a round


class Searches:
    def __init__(self, h, config: dict, traffic: dict, seed: int):
        import jax
        from repro.core.algorithms import bfs, sssp
        from repro.core.algorithms.sssp import auto_delta

        self.h, self.config, self.traffic = h, config, traffic
        self.search = traffic["search"]
        self.rng = np.random.default_rng(seed)
        t = h.now()
        self.n, self.rows, self.cols, self.w = system.edges(config,
                                                            h.work_dir)
        self.deg = system.degrees(self.n, self.rows)
        t1 = h.now()
        csr = system.csr_of(self.n, self.rows, self.cols, self.w)
        self.graph = csr.for_jit()
        if self.search == "bfs":
            self.fn = jax.jit(lambda g, s: bfs(g, s))
        elif self.search == "sssp":
            delta = auto_delta(csr)
            self.fn = jax.jit(lambda g, s: sssp(g, s, delta=delta))
        else:
            raise ValueError(f"unknown search {self.search!r}")
        self.keys = [int(k) for k in generate.search_keys(traffic, self.deg,
                                                          self.rng)]
        self.dev_keys = [jax.device_put(np.int32(k)) for k in self.keys]
        t2 = h.now()
        self.fn(self.graph, self.dev_keys[0]).block_until_ready()
        t3 = h.now()
        h.log(f"set-up: graph {t1 - t:.2f} s, CSR {t2 - t1:.2f} s, warm-up "
              f"{t3 - t2:.2f} s (compiles {h.compiles}, "
              f"{h.compile_s:.2f} s)")

    def _finish(self, key: int, out) -> None:
        """Read back one search's output and count its work."""
        with self.h.span("bench.readback"):
            host = np.asarray(out)
            reached = host >= 0 if self.search == "bfs" else np.isfinite(host)
            self.searches.append({
                "key": key,
                "edges": work.traversed_edges(self.deg, reached),
                "bytes": work.least_bytes(self.deg, reached,
                                          self.search == "sssp")})
            self.results.append((key, host))

    def window(self, seconds: float) -> dict:
        h, fn, graph = self.h, self.fn, self.graph
        self.results, self.searches = [], []
        inflight = deque()
        rounds = 0
        t0 = h.now()
        while True:
            for key, dev_key in zip(self.keys, self.dev_keys):
                with h.span("bench.search"):
                    inflight.append((key, fn(graph, dev_key)))
                if len(inflight) == DEPTH:
                    self._finish(*inflight.popleft())
            while inflight:
                self._finish(*inflight.popleft())
            rounds += 1
            if h.now() - t0 >= seconds:
                break
        t_end = h.now() - t0
        edges = sum(s["edges"] for s in self.searches)
        h.log(f"searches: {len(self.searches)} {self.search} in {rounds} "
              f"rounds in {t_end:.3f} s, {edges} edges, "
              f"{len({k for k, _ in self.results})} distinct keys")
        return {"attempted": len(self.searches), "failed": 0,
                "e2e": {"teps": edges / t_end},
                "record": {"searches": self.searches}}

    def release(self) -> None:
        self.fn = self.graph = self.dev_keys = None
        gc.collect()

    def check(self, result: dict, control: bool = False):
        """(numbers, limits) of the comparison of every search's output
        with the reference."""
        g = reference.Graph(self.n, self.rows, self.cols, self.w)
        got = check.search_control(g, self.search, self.results) if control \
            else self.results
        numbers = {"unanswered": result["failed"],
                   **check.search_numbers(g, self.search, got)}
        self.h.log(f"check: {len(got)} searches")
        return numbers, {k: 0 for k in numbers}


def setup(h, config, traffic, seed):
    return Searches(h, config, traffic, seed)
