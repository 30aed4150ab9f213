"""Set-up, the window's bookkeeping and the check, shared by the drivers
that serve queries through ``GraphService``."""
from __future__ import annotations

import gc
import math

import numpy as np

from . import check, reference, system

__all__ = ["Served"]


class Served:
    """The service of one configuration, warmed up for one mix."""

    def __init__(self, h, config: dict, traffic: dict, seed: int):
        self.h, self.config, self.traffic = h, config, traffic
        t = h.now()
        self.n, self.rows, self.cols, self.w = system.edges(config,
                                                            h.work_dir)
        self.deg = system.degrees(self.n, self.rows)
        t1 = h.now()
        csr = system.csr_of(self.n, self.rows, self.cols, self.w)
        self.svc = system.service_of(config, csr)
        t2 = h.now()
        # one batch of each kind of the mix compiles (or loads) and runs its
        # program
        for q in self._warm_queries():
            self.svc.query(system.to_query(q))
        t3 = h.now()
        h.log(f"set-up: graph {t1 - t:.2f} s, CSR + service "
              f"{t2 - t1:.2f} s, warm-up {t3 - t2:.2f} s (compiles "
              f"{h.compiles}, {h.compile_s:.2f} s)")
        if h.trace_on:
            self._memory_report()
        self.stats0 = dict(self.svc.stats.as_dict())

    def _memory_report(self) -> None:
        """What the compiler plans for each served program of the mix, to
        set beside the run's ``peak_bytes_in_use``."""
        for kind in sorted(self.traffic["kinds"]):
            fn, shapes = self.svc.program(kind)
            m = fn.lower(*shapes).compile().memory_analysis()
            self.h.log(
                f"memory_analysis {kind}: args {m.argument_size_in_bytes} B, "
                f"out {m.output_size_in_bytes} B, temp "
                f"{m.temp_size_in_bytes} B, code "
                f"{m.generated_code_size_in_bytes} B")

    def _warm_queries(self) -> list:
        rng = np.random.default_rng(0)
        src = np.flatnonzero(self.deg > 0)
        k = int(self.traffic.get("ppr_k", 0))
        out = []
        for kind in sorted(self.traffic["kinds"]):
            s, t = int(rng.choice(src)), int(rng.integers(self.n))
            out.append((kind, s, k if kind == "ppr" else t))
        return out

    def summary(self, queries, answers, due, done, flush_start,
                t_end: float) -> dict:
        """The window's result: end-to-end metrics and the readers' record.
        ``done`` is NaN for a query that never returned."""
        answered = ~np.isnan(done)
        lat = np.where(answered, done - due, np.inf)
        n = len(queries)
        p95 = np.sort(lat)[max(0, math.ceil(0.95 * n) - 1)] if n else np.inf
        stats1 = self.svc.stats.as_dict()
        self.answered = [(queries[i], answers[i])
                         for i in np.flatnonzero(answered)]
        return {
            "attempted": n,
            "failed": int(n - answered.sum()),
            "e2e": {"qps": float(answered.sum() / t_end) if t_end > 0 else 0.0,
                    "p95_ms": float(p95 * 1e3)},
            "record": {
                "budget": self.svc.budget,
                "stats": {k: stats1[k] - self.stats0[k]
                          for k in ("queries", "batches", "lanes_used",
                                    "cache_hits")},
                "queue_wait_s": (flush_start - due)[answered],
            },
        }

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.svc = None
        gc.collect()

    def check(self, result: dict, control: bool = False):
        """(numbers, limits) of the comparison of every answered query with
        the reference."""
        g = reference.Graph(self.n, self.rows, self.cols, self.w)
        params = self.config["service"]
        answered = self.answered
        if control:
            answered = check.served_control(g, answered, params)
        kinds = self.traffic["kinds"]
        numbers = {"unanswered": result["failed"],
                   **{k: v for k, v in check.served_numbers(
                       g, answered, params).items()
                      if k.split("_")[0] in kinds}}
        limits = {"unanswered": 0, "reach_wrong": 0, "dist_wrong": 0,
                  "ppr_gap": self.traffic.get("ppr_gap_limit")}
        self.h.log(f"check: {len(answered)} answered queries")
        return numbers, limits
