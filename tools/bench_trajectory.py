"""Cross-PR bench trajectory: aggregate every ``BENCH_*.json`` into one
machine-stamped ``BENCH_trajectory.json``.

The repo has accumulated one bench artifact per major PR (decode,
prefill, prefix cache, SLO scheduling, chaos, recovery, flight
recorder, quantized KV, cost observatory, ops plane, profiling...) but
no cross-PR view: answering "did sustained tokens/s regress since the
quantization PR" meant opening nine files by hand.  This tool walks
the repo root, pulls each artifact's HEADLINE numbers — the ``summary``
dict when the bench emits one (the standard shape since the serving
benches), else the top-level scalars — and writes one aggregate:

    {
      "trajectory": 1,
      "generated_unix": ...,          # machine stamp: when/where
      "machine": {"platform": ..., "python": ..., "jax": ...,
                  "cpu_count": ...},
      "count": N,
      "benches": {
        "cost":    {"file": "BENCH_cost.json", "bench": "...",
                    "device": "cpu", "smoke": false,
                    "headline": {"median_error": 0.04, ...}},
        ...
      }
    }

Headlines keep scalars only (numbers / bools / short strings) so the
aggregate stays a dashboard, not a second copy of every artifact.  The
tool is deliberately **jax-free** — it reads JSON and stamps the
machine, so CI and operators can run it anywhere in milliseconds.

Usage:
    python tools/bench_trajectory.py [--root DIR]
                                     [--out BENCH_trajectory.json]
"""
import argparse
import glob
import json
import os
import platform
import sys
import time

# headline scalars kept per bench (beyond this the aggregate stops
# being a dashboard); strings longer than this are dropped too
MAX_HEADLINE_KEYS = 16
MAX_STR = 48


def _scalars(obj: dict) -> dict:
    """The JSON-scalar subset of one dict, insertion-ordered, capped."""
    out = {}
    for k, v in obj.items():
        if isinstance(v, bool) or isinstance(v, (int, float)):
            out[k] = v
        elif isinstance(v, str) and len(v) <= MAX_STR:
            out[k] = v
        if len(out) >= MAX_HEADLINE_KEYS:
            break
    return out


def headline(data) -> dict:
    """One artifact's headline numbers: the ``summary`` dict when the
    bench emits one (every serving bench since PR 6), else the
    top-level scalars (the kernel/int8 shapes)."""
    if not isinstance(data, dict):
        return {}
    summary = data.get("summary")
    if isinstance(summary, dict) and summary:
        return _scalars(summary)
    return _scalars(data)


def build_trajectory(root: str) -> dict:
    benches = {}
    skipped = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_*.json"))):
        name = os.path.basename(path)
        if name == "BENCH_trajectory.json":
            continue  # never aggregate the aggregate
        key = name[len("BENCH_"):-len(".json")]
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            skipped.append({"file": name, "error": str(e)[:MAX_STR]})
            continue
        entry = {"file": name, "headline": headline(data)}
        if isinstance(data, dict):
            for meta in ("bench", "device", "smoke"):
                if meta in data:
                    entry[meta] = data[meta]
        benches[key] = entry
    try:
        jax_version = __import__("importlib.metadata", fromlist=[
            "version"]).version("jax")
    except Exception:
        jax_version = None
    return {
        "trajectory": 1,
        "generated_unix": time.time(),
        "machine": {
            "platform": platform.platform(),
            "python": platform.python_version(),
            "jax": jax_version,
            "cpu_count": os.cpu_count(),
        },
        "count": len(benches),
        "benches": benches,
        "skipped": skipped,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        help="directory scanned for BENCH_*.json (default: repo root)")
    ap.add_argument("--out", default=None,
                    help="output path (default: "
                         "<root>/BENCH_trajectory.json)")
    args = ap.parse_args()
    out_path = args.out or os.path.join(args.root,
                                        "BENCH_trajectory.json")
    traj = build_trajectory(args.root)
    with open(out_path, "w") as f:
        json.dump(traj, f, indent=2)
    print(f"wrote {out_path} ({traj['count']} benches"
          + (f", {len(traj['skipped'])} skipped" if traj["skipped"]
             else "") + ")")
    for key, entry in traj["benches"].items():
        hl = entry["headline"]
        peek = ", ".join(f"{k}={v}" for k, v in list(hl.items())[:4])
        print(f"  {key:<12} {peek}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
