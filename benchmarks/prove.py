"""The readings that a cell's limits are set from, on the chip, at the
cell's own size, many seeds in one process.

    python benchmarks/prove.py --workload <name> --seeds 12 --control-seeds 3 \
        [--first-seed N] [--out chiprun_out/prove_<name>.jsonl]

For every seed the cell's runner reads the program's numbers against the
plain reference; for the first ``--control-seeds`` of them also the
control's and the planted faults'.  One JSON line a seed, then a summary:
for each number the largest the program gave (the lower reading) and the
smallest of the control and of each fault (the upper readings).  The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def summarize(rows: list) -> dict:
    out = {}
    for who in ("program", "control", "half_batch", "altered_token"):
        have = [r[who] for r in rows if who in r]
        if not have:
            continue
        out[who] = {
            k: {"min": min(r[k][0] for r in have),
                "max": max(r[k][0] for r in have), "n": len(have)}
            for k in have[0]}
    return out


def main(argv=None) -> int:
    from benchmarks import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cell = harness.Cell(harness.load_benchmark(), args.workload)
    harness.require_chips(cell.chips)
    harness.place_compile_cache()
    runner = harness.load_module("runners", cell.spec["runner"])
    rows = []
    out = open(args.out, "w") if args.out else None
    try:
        for i in range(args.seeds):
            # seeds far apart, and past 2**31 as the driver's are
            seed = args.first_seed + 7919 * i
            row = runner.prove(cell, seed, control=i < args.control_seeds)
            rows.append(row)
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(row) + "\n")
                out.flush()
        summary = {"workload": cell.name, "summary": summarize(rows)}
        print(json.dumps(summary), flush=True)
        if out:
            out.write(json.dumps(summary) + "\n")
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
