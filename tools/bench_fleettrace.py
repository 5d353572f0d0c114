"""Fleet tracing bench: propagation overhead + kill -9 trace stitch
(``FLAGS_fleet_trace``; docs/FLEET_TRACING.md).

Three asserted gates:

* **overhead** — the same waved workload runs against two fleets, one
  with the flag off and one with it on (shared compile cache, same
  shapes, same prompts).  Minting the trace id, carrying the
  ``x-paddle-trace`` header, and tagging every span must cost less
  than ``--overhead-bound`` percent of the mean request wall (default
  1%; smoke mode loosens it — tiny CPU shapes are noise-dominated).

* **completeness** — with streams inflight on the traced fleet, the
  busiest replica is kill -9'd.  Every replica's ``/tracez/spans``
  was scraped just before the kill (the victim's buffer dies with
  it — continuous scraping is the operator contract), survivors are
  scraped after; for EVERY migrated stream the merged trace must
  carry its trace id on requests-track spans from **both** the victim
  and a survivor, plus the router's own ``route`` span.

* **stitch** — the merged fleet chrome trace
  (`observability.fleettrace.merge_fleet_trace`) has exactly **one**
  requests-track lane per trace id: a request killed on one chip and
  finished on another renders as one contiguous row, never two.

Also exercises the ``/fleetz`` rollup round-trip (replica cards +
merged trace with a dead replica in the set) and asserts zero request
loss through the kill.  Emits BENCH_fleettrace.json.

Usage:
    python tools/bench_fleettrace.py [--out BENCH_fleettrace.json]
                                     [--smoke]

``--smoke`` (or env BENCH_SMOKE=1) shrinks to 2 replicas and tiny
shapes so CI can assert the script end-to-end (tests/test_tooling.py).
The ``--child`` mode is internal (replicas re-exec this script).

Processes and the chip: as tools/bench_fleet.py says of itself — a CPU
program on both sides.  Its module top, imported below before anything
imports JAX, pins this parent and (through the inherited environment)
every child.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_fleet as bf  # noqa: E402  (FIRST: its top pins JAX to the
#                         # CPU.  Shared harness: model/engine/router
#                         # builders, fleet teardown, percentile)
import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402


# ---------------------------------------------------------------------------
# child: one replica process (edge + ops plane + journal + trace flag)
# ---------------------------------------------------------------------------
def _child_replica(args):
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.fleet import EdgeServer
    from paddle_tpu.observability import opsserver

    paddle.set_flags({"journal_fsync": "always",
                      "fleet_trace": bool(args.fleet_trace)})
    enable_compile_cache()
    model = bf._build_model(args)
    jdir = os.path.join(args.dir, args.name)
    eng = bf._engine(model, args, journal_dir=jdir)
    ops_port = opsserver.start_ops_server(port=0)
    edge = EdgeServer(eng)
    edge_port = edge.start()
    print(f"FLEET_CHILD name={args.name} edge={edge_port} "
          f"ops={ops_port}", flush=True)
    while True:
        time.sleep(3600)


def _spawn_fleet(args, tmp, n, fleet_trace):
    """bench_fleet's spawner, re-execing THIS script so the children
    carry the fleet_trace flag."""
    env = dict(os.environ)  # carries JAX_PLATFORMS=cpu (bench_fleet's top)
    flags = env.get("XLA_FLAGS", "")
    if "xla_backend_optimization_level" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_backend_optimization_level=0").strip()
    base = [sys.executable, os.path.abspath(__file__),
            "--child", "replica", "--dir", tmp,
            "--fleet-trace", str(int(fleet_trace))]
    for k in ("slots", "prompt", "new", "chunk", "page_size",
              "layers", "hidden", "heads", "vocab"):
        base += [f"--{k.replace('_', '-')}", str(getattr(args, k))]
    tag = "on" if fleet_trace else "off"
    reps = []
    for i in range(n):
        name = f"r{i}"
        os.makedirs(os.path.join(tmp, f"{tag}_{name}"), exist_ok=True)
        proc = subprocess.Popen(
            base + ["--name", f"{tag}_{name}"],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=env)
        reps.append(bf._Replica(f"{tag}_{name}", proc, None, None))
    deadline = time.time() + 300
    for rep in reps:
        while True:
            if time.time() > deadline:
                raise RuntimeError(
                    f"replica {rep.name} never announced its ports")
            line = rep.proc.stdout.readline()
            if not line:
                raise RuntimeError(
                    f"replica {rep.name} exited during boot "
                    f"(rc={rep.proc.poll()})")
            if line.startswith("FLEET_CHILD "):
                kv = dict(f.split("=", 1) for f in line.split()[1:])
                rep.edge_port = int(kv["edge"])
                rep.ops_port = int(kv["ops"])
                break
        threading.Thread(target=lambda p=rep.proc: p.stdout.read(),
                         daemon=True).start()
    return reps


def _scrape_spans(rep):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{rep.edge_port}/tracez/spans",
            timeout=10) as r:
        return json.load(r)["spans"]


# ---------------------------------------------------------------------------
# leg 1: propagation overhead — flag off vs on, same workload
# ---------------------------------------------------------------------------
def _workload(args, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(4, args.vocab, (args.prompt,))
            .astype(np.int32).tolist()
            for _ in range(args.waves * args.wave_size)]


def _overhead_arm(args, reps, prompts):
    """Waved submit/complete over one fleet; returns mean request
    wall in seconds (first wave excluded: it pays compile/cache-load,
    not propagation)."""
    router = bf._router(args, reps, "affinity")
    try:
        warm = prompts[:args.wave_size]
        for s in [router.submit(p, max_new_tokens=args.overhead_new)
                  for p in warm]:
            s.result(timeout=600)
        done = 0
        t0 = time.perf_counter()
        for w in range(args.waves):
            wave = prompts[w * args.wave_size:(w + 1) * args.wave_size]
            streams = [router.submit(p,
                                     max_new_tokens=args.overhead_new)
                       for p in wave]
            for s in streams:
                s.result(timeout=600)
            done += len(streams)
        wall = time.perf_counter() - t0
    finally:
        router.close()
    return wall / max(done, 1)


# ---------------------------------------------------------------------------
# leg 2: chaos kill — completeness + single-lane stitch
# ---------------------------------------------------------------------------
def _lane_report(merged):
    """(trace -> requests-lane tids, trace -> replicas on that lane)
    from a merged fleet chrome trace."""
    events = merged.get("traceEvents", [])
    req_pids = {ev["pid"] for ev in events
                if ev.get("ph") == "M"
                and ev.get("name") == "process_name"
                and (ev.get("args") or {}).get("name") == "requests"}
    lanes, lane_reps = {}, {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in req_pids:
            continue
        t = (ev.get("args") or {}).get("trace")
        if not t:
            continue
        lanes.setdefault(t, set()).add(ev.get("tid"))
        rep = (ev.get("args") or {}).get("replica")
        if rep:
            lane_reps.setdefault(t, set()).add(rep)
    return lanes, lane_reps


def _chaos_leg(args, reps):
    from paddle_tpu.observability import fleettrace, tracing

    router = bf._router(args, reps, "affinity")
    try:
        prompts = _workload(args, seed=11)[:args.before_kill]
        streams = [router.submit(p, max_new_tokens=args.new)
                   for p in prompts]
        assert all(s.trace_id for s in streams), \
            "FLAGS_fleet_trace on: every submit must mint a trace id"
        deadline = time.time() + 300
        while any(len(s.tokens) < 3 for s in streams) \
                and time.time() < deadline:
            time.sleep(0.02)
        # the victim's span buffer dies with it: scrape BEFORE the kill
        pre_kill = {rep.name: _scrape_spans(rep) for rep in reps}
        by_rep = {}
        for s in streams:
            if not s.done and s.replica:
                by_rep.setdefault(s.replica, []).append(s)
        victim_name = max(by_rep, key=lambda n: len(by_rep[n]))
        victim = next(r for r in reps if r.name == victim_name)
        os.kill(victim.proc.pid, signal.SIGKILL)
        victim.proc.wait(timeout=30)

        for s in streams:
            s.result(timeout=600)
        migrated = [s for s in streams if s.failovers > 0]

        # merge: survivors scraped fresh (their buffers retain the
        # whole story), the victim contributes its pre-kill scrape,
        # the router folds in its own route/failover spans
        replica_spans = {}
        for rep in reps:
            replica_spans[rep.name] = (
                pre_kill[rep.name] if rep.proc.poll() is not None
                else _scrape_spans(rep))
        replica_spans["router"] = fleettrace.span_slice(tracing.spans())
        offsets = {name: h.clock_offset_ns()
                   for name, h in router._replicas.items()}
        offsets["router"] = 0
        merged = fleettrace.merge_fleet_trace(replica_spans, offsets)
        lanes, lane_reps = _lane_report(merged)

        route_traces = {
            (s.get("args") or {}).get("trace")
            for s in replica_spans["router"]
            if s.get("track") == "router" and s.get("name") == "route"}
        complete = [
            s.trace_id in lane_reps
            and victim_name in lane_reps[s.trace_id]
            and len(lane_reps[s.trace_id]) >= 2
            and s.trace_id in route_traces
            for s in migrated]

        fleetz = router.fleetz()
        return {
            "replicas": len(reps),
            "requests": len(streams),
            "victim": victim_name,
            "killed_by_sigkill":
                victim.proc.returncode == -signal.SIGKILL,
            "streams_migrated": len(migrated),
            "zero_request_loss": all(
                s.finish_reason in ("eos", "length") for s in streams),
            "traced_lanes": len(lanes),
            "single_lane_per_trace": bool(
                lanes and all(len(t) == 1 for t in lanes.values())),
            "migrated_traces_complete":
                round(sum(complete) / len(complete), 4)
                if complete else 0.0,
            "failovers": router.stats["failovers"],
            "fleetz_has_merged_trace":
                bool(fleetz.get("trace", {}).get("traceEvents")),
            "fleetz_replica_cards": len(fleetz.get("replicas", {})),
        }
    finally:
        router.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_fleettrace.json"))
    ap.add_argument("--child", choices=("replica",))
    ap.add_argument("--name", default="r0")
    ap.add_argument("--dir", default=None)
    ap.add_argument("--fleet-trace", type=int, default=0,
                    help="(child) serve with FLAGS_fleet_trace on")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--new", type=int, default=48,
                    help="chaos-leg generation length (long enough "
                         "that the kill lands mid-stream)")
    ap.add_argument("--overhead-new", type=int, default=16)
    ap.add_argument("--waves", type=int, default=4)
    ap.add_argument("--wave-size", type=int, default=4)
    ap.add_argument("--before-kill", type=int, default=6)
    ap.add_argument("--overhead-bound", type=float, default=1.0,
                    help="max propagation overhead, % of mean "
                         "request wall")
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="2 replicas + tiny shapes: CI end-to-end "
                         "check")
    args = ap.parse_args()
    if os.environ.get("BENCH_SMOKE") == "1":
        args.smoke = True
    if args.smoke and args.child is None:
        args.replicas, args.slots = 2, 3
        args.waves, args.wave_size = 2, 3
        args.before_kill, args.new = 4, 32
        args.overhead_new = 8
        # tiny CPU shapes are noise-dominated: the smoke run asserts
        # the SCRIPT end-to-end, the full run asserts the 1% bar
        args.overhead_bound = 50.0

    if args.child:
        if not args.dir:
            ap.error("--child requires --dir")
        _child_replica(args)
        return 0

    import tempfile

    import jax

    tmp = tempfile.mkdtemp(prefix="bench_fleettrace_")
    prompts = _workload(args, seed=5)

    # arm 1: flag off everywhere (children AND the router process)
    paddle.set_flags({"fleet_trace": False})
    reps = _spawn_fleet(args, tmp, args.replicas, fleet_trace=False)
    try:
        wall_off = _overhead_arm(args, reps, prompts)
    finally:
        bf._kill_fleet(reps)
    print(f"overhead arm [off]: {wall_off * 1e3:.2f}ms mean "
          f"request wall")

    # arm 2 + chaos: flag on everywhere (same compile cache, same
    # prompts — the only delta is the trace plumbing)
    paddle.set_flags({"fleet_trace": True})
    reps = _spawn_fleet(args, tmp, args.replicas, fleet_trace=True)
    try:
        wall_on = _overhead_arm(args, reps, prompts)
        print(f"overhead arm [ on]: {wall_on * 1e3:.2f}ms mean "
              f"request wall")
        chaos = _chaos_leg(args, reps)
    finally:
        bf._kill_fleet(reps)
        paddle.set_flags({"fleet_trace": False})

    overhead_pct = (wall_on - wall_off) / wall_off * 100.0
    print(f"chaos: killed {chaos['victim']} | migrated "
          f"{chaos['streams_migrated']} | lanes {chaos['traced_lanes']}"
          f" | single-lane {chaos['single_lane_per_trace']} | "
          f"complete {chaos['migrated_traces_complete']:.0%} | "
          f"overhead {overhead_pct:+.2f}%")

    summary = {
        "mean_request_wall_off_s": round(wall_off, 6),
        "mean_request_wall_on_s": round(wall_on, 6),
        "propagation_overhead_pct": round(overhead_pct, 3),
        "overhead_bounded": overhead_pct <= args.overhead_bound,
        "killed_by_sigkill": chaos["killed_by_sigkill"],
        "zero_request_loss": chaos["zero_request_loss"],
        "streams_migrated": chaos["streams_migrated"],
        "single_lane_per_trace": chaos["single_lane_per_trace"],
        "migrated_traces_complete": chaos["migrated_traces_complete"],
        "fleetz_has_merged_trace": chaos["fleetz_has_merged_trace"],
    }
    out = {
        "bench": "fleet tracing: x-paddle-trace propagation overhead "
                 "+ kill -9 cross-replica trace stitch",
        "device": str(jax.devices()[0].device_kind)
        if jax.devices() else "unknown",
        "smoke": bool(args.smoke),
        "config": {k: getattr(args, k) for k in
                   ("replicas", "slots", "prompt", "new",
                    "overhead_new", "waves", "wave_size",
                    "before_kill", "overhead_bound", "chunk",
                    "page_size", "layers", "hidden", "heads",
                    "vocab")},
        "legs": {"chaos": chaos},
        "summary": summary,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out} (overhead "
          f"{summary['propagation_overhead_pct']:+.2f}% bounded="
          f"{summary['overhead_bounded']}, single-lane="
          f"{summary['single_lane_per_trace']}, complete="
          f"{summary['migrated_traces_complete']:.0%})")
    ok = all(summary[k] for k in
             ("overhead_bounded", "killed_by_sigkill",
              "zero_request_loss", "single_lane_per_trace",
              "fleetz_has_merged_trace")) and \
        summary["streams_migrated"] >= 1 and \
        summary["migrated_traces_complete"] == 1.0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
