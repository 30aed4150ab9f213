#!/usr/bin/env python3
"""Sweep an open-loop cell's offered rate on the chip, to find its knee.

    python3 chipbench/knee.py --workload svc-mix-open --seed 7 \
        --seconds 40 --rates 1.5 2 2.5 3

One process sets the cell up once and then runs one window per rate, each
after the last has drained.  For each rate it prints the queries offered,
when the last was answered (a window whose last answer comes more than a
flush after its close has a backlog that grew through it), ``qps`` and
``p95_ms``.  The knee is the highest rate without a growing backlog; the
cell's mix file then offers four fifths of it.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    sys.path.insert(0, run.ROOT)
    import jax
    from chipbench.harness import Harness

    spec = run.cell_spec(args.workload)
    run._use_compile_cache(jax)
    run.devices_for(jax, spec["cell"]["chips"])
    h = Harness(run.ROOT, trace=False)
    driver = importlib.import_module(
        f"chipbench.drivers.{spec['traffic']['driver']}")
    state = driver.setup(h, spec["config"], spec["traffic"], args.seed)
    for rate in args.rates:
        state.traffic = dict(spec["traffic"], rate_qps=rate)
        state.stats0 = dict(state.svc.stats.as_dict())
        r = state.window(args.seconds)
        print(json.dumps({"rate_qps": rate, "offered": r["attempted"],
                          "failed": r["failed"], **r["e2e"],
                          "stats": r["record"]["stats"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
