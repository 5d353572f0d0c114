"""The one search for a serve cell's knee, made when the cell is defined
and never by the benchmark's own runs: one engine, one window at each
offered rate, and how the backlog stood at each close.

    python benchmarks/sweep.py --workload <name> --rates 0.8,1.2,1.6 --seconds 30
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmarks import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2_400_000_011)
    args = ap.parse_args(argv)
    cell = harness.Cell(harness.load_benchmark(), args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    runner = harness.load_module("runners", cell.spec["runner"])
    runner.sweep(cell, args.seed, [float(r) for r in args.rates.split(",")],
                 args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
