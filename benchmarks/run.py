"""One cell, once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in `BENCHMARK.json`, its files by name under `benchmarks/`,
and its runner; refuses to run without the chips the cell asks for; prints
the result as the last line of standard output.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)

    cell = harness.Cell(harness.load_benchmark(), args.workload)
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        raise SystemExit("the program (paddle_tpu/) is not in this checkout")
    device = harness.require_chips(cell.chips)
    harness.place_compile_cache()
    runner = harness.load_module("runners", cell.spec["runner"])
    # a run that is not correct has still run: its line says so
    runner.run(cell, seed=args.seed, seconds=args.seconds,
               trace=bool(args.trace), device=device,
               keep_trace=args.keep_trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
