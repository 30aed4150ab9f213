"""Open loop: independent users, queries sent when they are due.

One thread submits every query that is due, then flushes while the queue is
non-empty, so batches fill from the arrivals during a flush.  A query is
timed from when it was due until the ``flush`` that served it returned.
"""
from __future__ import annotations

import time

import numpy as np

from .. import generate, system
from ..served import Served


class OpenLoop(Served):
    def window(self, seconds: float) -> dict:
        h, svc = self.h, self.svc
        due, queries = generate.open_loop_schedule(self.traffic, self.deg,
                                                   seconds)
        n = len(queries)
        done = np.full(n, np.nan)
        flush_start = np.full(n, np.nan)
        answers = [None] * n
        tickets = [0] * n
        pending = []
        late = []
        i = 0
        t0 = h.now()
        while i < n or pending:
            with h.span("bench.submit"):
                now = h.now() - t0
                while i < n and due[i] <= now:
                    tickets[i] = svc.submit(system.to_query(queries[i]))
                    pending.append(i)
                    i += 1
            if pending:
                fs = h.now() - t0
                with h.span("bench.flush"):
                    svc.flush()
                fe = h.now() - t0
                with h.span("bench.result"):
                    for j in pending:
                        answers[j] = svc.result(tickets[j])
                    done[pending] = fe
                    flush_start[pending] = fs
                pending = []
            else:
                with h.span("bench.wait"):
                    time.sleep(max(0.0, due[i] - (h.now() - t0)))
                late.append(h.now() - t0 - due[i])
        t_end = float(np.nanmax(done)) if n else seconds
        h.log(f"open loop: {n} queries offered at "
              f"{self.traffic['rate_qps']} queries/s, last answered at "
              f"{t_end:.3f} s; the generator woke late by at most "
              f"{1e3 * max(late, default=0.0):.3f} ms (mean "
              f"{1e3 * float(np.mean(late)) if late else 0.0:.3f} ms)")
        return self.summary(queries, answers, due, done, flush_start, t_end)


def setup(h, config, traffic, seed):
    return OpenLoop(h, config, traffic, seed)
