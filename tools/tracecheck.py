#!/usr/bin/env python
"""tracecheck: static trace-safety, donation, lock-discipline, and
engine-mutation analysis over the serving stack's own source.

Usage:

    python tools/tracecheck.py                      # default targets
    python tools/tracecheck.py paddle_tpu/inference # explicit paths
    python tools/tracecheck.py --baseline tools/tracecheck_baseline.json
    python tools/tracecheck.py --write-baseline     # grandfather now
    python tools/tracecheck.py --json               # machine-readable

Exit codes: 0 = clean (or fully baselined), 1 = unbaselined findings,
2 = usage / scan error.

Passes (see docs/STATIC_ANALYSIS.md for the catalog):

* trace-hazard    — python control flow / bool()/int()/float()/.item()
                    on traced values inside jitted functions
* flags-in-trace  — FLAGS_* reads inside jitted functions (baked at
                    trace time; set_flags silently ignored after)
* lock-discipline — writes to the shared telemetry registries outside
                    their designated lock
* engine-mutation — DecodeEngine mutating calls outside the sanctioned
                    between-steps sites
* donation        — jax.jit sites whose function takes the KV pool
                    (`kv`) and does not donate it
* fleet-trace     — HTTP sites under paddle_tpu/fleet/ (urlopen client
                    legs, do_* handlers) that neither propagate the
                    x-paddle-trace header nor sit on the control-plane
                    allowlist (docs/FLEET_TRACING.md)

The baseline file grandfathers findings by CONTENT fingerprint (pass +
file + source-line text): pre-existing debt never blocks CI, but any
touched line resurfaces.  The shipped baseline is empty — everything
the passes surfaced was fixed in code.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from paddle_tpu.analysis import (  # noqa: E402
    DEFAULT_TARGETS, load_baseline, run_tracecheck, split_baselined,
    write_baseline,
)

DEFAULT_BASELINE = os.path.join(REPO, "tools", "tracecheck_baseline.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tracecheck",
        description="static trace-safety / donation / lock-discipline "
                    "analysis for the serving stack")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to scan (default: "
                         + ", ".join(DEFAULT_TARGETS) + ")")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="grandfather file (default: "
                         "tools/tracecheck_baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: report everything")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write ALL current findings into the baseline "
                         "and exit 0")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit findings as JSON")
    args = ap.parse_args(argv)

    try:
        findings = run_tracecheck(args.paths or None, root=REPO)
    except (FileNotFoundError, SyntaxError) as e:
        print(f"tracecheck: {e}", file=sys.stderr)
        return 2

    if args.write_baseline:
        write_baseline(args.baseline, findings)
        print(f"tracecheck: wrote {len(findings)} finding(s) to "
              f"{os.path.relpath(args.baseline, REPO)}")
        return 0

    baseline = {} if args.no_baseline else load_baseline(args.baseline)
    new, grandfathered = split_baselined(findings, baseline)

    if args.as_json:
        print(json.dumps({
            "new": [vars(f) | {"fingerprint": f.fingerprint}
                    for f in new],
            "baselined": [vars(f) | {"fingerprint": f.fingerprint}
                          for f in grandfathered],
        }, indent=2))
    else:
        for f in new:
            print(f.render())
        summary = (f"tracecheck: {len(new)} finding(s)"
                   + (f", {len(grandfathered)} baselined"
                      if grandfathered else ""))
        print(summary if new or grandfathered
              else "tracecheck: clean")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(main())
