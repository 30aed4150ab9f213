#!/usr/bin/env python3
"""Read the control of a cell on the chip: the window's queries or keys
answered by the reference in the precision below the configuration's (or,
for BFS, stopped a level short), compared as a run compares the program's
answers.

    python3 chipbench/control.py --workload graph500-sssp --seconds 15 \
        --seeds 11 12 13

Each seed runs the cell's window at its own load and prints the compared
numbers with their limits; the control has to come out not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        out = run.run(args.workload, seed, args.seconds, False, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checked": out["checked"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
