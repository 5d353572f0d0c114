"""Fleet bench: prefix-affinity routing win + kill -9 chaos failover
(paddle_tpu.fleet — the HTTP/SSE edge and the fleet router).

Two legs, both asserted (the fleet acceptance bar):

* **affinity** — the same shared-prefix workload shape is routed over
  the replica set twice, once ``policy="round_robin"`` and once
  ``policy="affinity"`` (prefix chain hashes as the routing key).
  Each replica's prefix-cache page hit/miss counters are scraped off
  its ops plane ``/metrics`` before and after; affinity routing must
  land a **strictly higher fleet-wide prefix-cache hit rate** than
  round-robin — the whole point of making the PR 6 chain hashes the
  routing key.

* **chaos** — N replica child processes serve behind one affinity
  router with journals armed (``fsync=always``); mid-generation, with
  streams inflight, the busiest replica is **kill -9'd** (no cleanup,
  real process death).  The router detects the death (broken SSE
  streams + ``/readyz`` refusing), replays the dead replica's journal
  into a survivor (``/v1/adopt``) reporting exactly how many tokens
  each stream delivered, and every interrupted stream resumes via
  ``/v1/resume``.  Asserted: the victim really died by SIGKILL,
  **zero request loss** (every stream — pre-kill, migrated, and
  post-kill — finishes eos/length), **token-for-token continuity**
  (every stream's full token list is bit-identical to the
  uninterrupted greedy oracle: nothing re-emitted, nothing dropped),
  at least one recorded failover, the fleet ``/alertz`` rollup
  narrating it, and a **bounded fleet-wide TTFT spike** for requests
  admitted after the kill.

Emits BENCH_fleet.json.

Usage:
    python tools/bench_fleet.py [--out BENCH_fleet.json] [--smoke]

``--smoke`` (or env BENCH_SMOKE=1) shrinks to 2 replicas and tiny
shapes so CI can assert the script end-to-end (tests/test_tooling.py).
The ``--child`` mode is internal (replicas re-exec this script).

Processes and the chip: this harness kills and restarts whole processes,
so it is a CPU program on both sides.  The parent (which computes the
greedy oracle with JAX) and every child pin ``JAX_PLATFORMS=cpu`` before
JAX is imported; neither side ever holds a chip, and on a machine that
has one the chip stays free.  Replicas ON chips are one process driving
one device each (a chip belongs to one process at a time), which is not
what this tool measures.
"""
import argparse
import json
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # parent and children alike: see above

import signal
import subprocess
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models.gpt import GPT, GPTConfig  # noqa: E402


def _build_model(args):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers, num_heads=args.heads,
                    max_seq_len=2 * (args.prompt + args.new) + 64,
                    use_parallel_layers=False, dropout=0.0)
    model = GPT(cfg)
    model.eval()
    return model


def _engine(model, args, **kw):
    from paddle_tpu.inference.serving import DecodeEngine

    return DecodeEngine(model, max_batch_size=args.slots,
                        max_seq_len=args.prompt + args.new + 8,
                        page_size=args.page_size,
                        prefill_chunk_tokens=args.chunk,
                        prefix_cache=True, **kw)


# ---------------------------------------------------------------------------
# child: one replica process (edge + ops plane + journal)
# ---------------------------------------------------------------------------
def _child_replica(args):
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.fleet import EdgeServer
    from paddle_tpu.observability import opsserver

    paddle.set_flags({"journal_fsync": "always"})
    # tiny models, identical configs: the replicas share the one
    # persistent compile cache, so replicas 2..n skip the XLA compile
    enable_compile_cache()
    model = _build_model(args)
    jdir = os.path.join(args.dir, args.name)
    eng = _engine(model, args, journal_dir=jdir)
    ops_port = opsserver.start_ops_server(port=0)
    edge = EdgeServer(eng)
    edge_port = edge.start()
    # the parent parses this line for the ports; everything after it
    # on stdout is noise
    print(f"FLEET_CHILD name={args.name} edge={edge_port} "
          f"ops={ops_port}", flush=True)
    while True:  # serve until the parent kills us (SIGKILL or SIGTERM)
        time.sleep(3600)


# ---------------------------------------------------------------------------
# parent: fleet orchestration
# ---------------------------------------------------------------------------
class _Replica:
    def __init__(self, name, proc, edge_port, ops_port):
        self.name = name
        self.proc = proc
        self.edge_port = edge_port
        self.ops_port = ops_port


def _spawn_fleet(args, tmp, n):
    """Start ``n`` replica children; returns them once every edge has
    printed its ports."""
    env = dict(os.environ)  # carries JAX_PLATFORMS=cpu (module top)
    flags = env.get("XLA_FLAGS", "")
    if "xla_backend_optimization_level" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_backend_optimization_level=0").strip()
    base = [sys.executable, os.path.abspath(__file__),
            "--child", "replica", "--dir", tmp]
    for k in ("slots", "prompt", "new", "chunk", "page_size",
              "layers", "hidden", "heads", "vocab"):
        base += [f"--{k.replace('_', '-')}", str(getattr(args, k))]
    reps = []
    for i in range(n):
        name = f"r{i}"
        os.makedirs(os.path.join(tmp, name), exist_ok=True)
        proc = subprocess.Popen(base + ["--name", name],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.DEVNULL, text=True,
                                env=env)
        reps.append(_Replica(name, proc, None, None))
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
                kv = dict(f.split("=", 1)
                          for f in line.split()[1:])
                rep.edge_port = int(kv["edge"])
                rep.ops_port = int(kv["ops"])
                break
        # keep the pipe drained so the child never blocks on stdout
        threading.Thread(target=lambda p=rep.proc: p.stdout.read(),
                         daemon=True).start()
    return reps


def _kill_fleet(reps):
    for rep in reps:
        if rep.proc.poll() is None:
            rep.proc.kill()
    for rep in reps:
        try:
            rep.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            pass


def _router(args, reps, policy):
    from paddle_tpu.fleet import FleetRouter

    router = FleetRouter(policy=policy, poll_interval_s=0.05,
                         dead_after=4, admit_timeout_s=300.0,
                         rollup_every=10)
    for rep in reps:
        router.add_replica(rep.name,
                           f"http://127.0.0.1:{rep.edge_port}")
    router.start()
    return router


def _scrape_prefix(reps):
    """Fleet-wide prefix-cache page (hits, misses) off each live
    replica's /metrics."""
    hits = misses = 0.0
    for rep in reps:
        if rep.proc.poll() is not None:
            continue
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{rep.ops_port}/metrics",
            timeout=10).read().decode()
        for line in text.splitlines():
            if line.startswith("paddle_prefix_cache_page_hits_total"):
                hits += float(line.rsplit(None, 1)[1])
            elif line.startswith(
                    "paddle_prefix_cache_page_misses_total"):
                misses += float(line.rsplit(None, 1)[1])
    return hits, misses


def _shared_prefix_workload(args, seed):
    """``groups`` families of ``per_group`` prompts, each family
    sharing a page-aligned prefix — the workload prefix-affinity
    routing exists for."""
    rng = np.random.RandomState(seed)
    shared_len = (args.prompt // 2 // args.page_size) * args.page_size
    prompts = []
    for _ in range(args.groups):
        shared = rng.randint(4, args.vocab, (shared_len,))
        for _ in range(args.per_group):
            tail = rng.randint(
                4, args.vocab, (args.prompt - shared_len,))
            prompts.append(np.concatenate([shared, tail])
                           .astype(np.int32).tolist())
    return prompts


# ---------------------------------------------------------------------------
# leg 1: affinity routing vs round-robin — prefix-cache hit rate
# ---------------------------------------------------------------------------
def _affinity_leg(args, reps):
    out = {}
    for policy, seed in (("round_robin", 1), ("affinity", 2)):
        prompts = _shared_prefix_workload(args, seed)
        router = _router(args, reps, policy)
        try:
            h0, m0 = _scrape_prefix(reps)
            # submit in waves — one request per family per wave, the
            # wave's streams concurrent across families.  Submitting a
            # whole family at once would defeat ANY router: siblings
            # admit before the first one's pages are registered, so no
            # policy could hit.  Affinity pays off on the arrival
            # pattern prefix caches exist for: the follow-up request.
            for wave in range(args.per_group):
                streams = [router.submit(p,
                                         max_new_tokens=args.leg1_new)
                           for p in prompts[wave::args.per_group]]
                for s in streams:
                    s.result(timeout=600)
            h1, m1 = _scrape_prefix(reps)
        finally:
            router.close()
        hits, misses = h1 - h0, m1 - m0
        total = hits + misses
        out[policy] = {
            "requests": len(prompts),
            "prefix_page_hits": hits,
            "prefix_page_misses": misses,
            "prefix_hit_rate": round(hits / total, 4) if total else 0.0,
            "router_affinity_hits": router.stats["affinity_hits"],
            "router_affinity_misses": router.stats["affinity_misses"],
        }
        print(f"affinity leg [{policy:>11}]: "
              f"page hit rate {out[policy]['prefix_hit_rate']:.2%} "
              f"({hits:.0f}/{total:.0f})")
    out["affinity_wins"] = (out["affinity"]["prefix_hit_rate"] >
                            out["round_robin"]["prefix_hit_rate"])
    return out


# ---------------------------------------------------------------------------
# leg 2: kill -9 chaos — zero-loss failover with stream continuity
# ---------------------------------------------------------------------------
def _pct(vals, q):
    if not vals:
        return None
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(q * len(vals)))]


def _chaos_leg(args, reps, oracle, prompts1, prompts2):
    router = _router(args, reps, "affinity")
    try:
        streams = [router.submit(p, max_new_tokens=args.new)
                   for p in prompts1]
        # let every stream establish itself (meta + a few tokens
        # delivered) so the kill lands MID-generation
        deadline = time.time() + 300
        while any(len(s.tokens) < 3 for s in streams) \
                and time.time() < deadline:
            time.sleep(0.02)
        by_rep = {}
        for s in streams:
            if not s.done and s.replica:
                by_rep.setdefault(s.replica, []).append(s)
        victim_name = max(by_rep, key=lambda n: len(by_rep[n]))
        victim = next(r for r in reps if r.name == victim_name)
        inflight_on_victim = len(by_rep[victim_name])
        pre_kill_tokens = {id(s): len(s.tokens)
                           for s in by_rep[victim_name]}
        t_kill = time.perf_counter()
        os.kill(victim.proc.pid, signal.SIGKILL)
        victim.proc.wait(timeout=30)

        phase1 = [s.result(timeout=600) for s in streams]
        t_recovered = time.perf_counter()

        # post-failover admissions: the fleet must still take traffic,
        # with bounded TTFT (no cold recompile — survivors are warm)
        streams2 = [router.submit(p, max_new_tokens=args.new)
                    for p in prompts2]
        phase2 = [s.result(timeout=600) for s in streams2]

        continuity = all(toks == oracle[tuple(s.prompt_ids)]
                         for s, toks in zip(streams, phase1))
        phase2_ok = all(toks == oracle[tuple(s.prompt_ids)]
                        for s, toks in zip(streams2, phase2))
        migrated = [s for s in streams if s.failovers > 0]
        # a migrated stream never loses a delivered token: its token
        # list strictly extends what it held when the replica died
        monotone = all(
            len(s.tokens) >= pre_kill_tokens.get(id(s), 0)
            for s in by_rep[victim_name])
        ttft1 = [s.ttft_s for s in streams if s.ttft_s is not None]
        ttft2 = [s.ttft_s for s in streams2 if s.ttft_s is not None]
        rollup = router.alertz_rollup()
        events = rollup.get("events", [])
        return {
            "replicas": len(reps),
            "requests_before_kill": len(streams),
            "requests_after_kill": len(streams2),
            "victim": victim_name,
            "victim_exit": victim.proc.returncode,
            "killed_by_sigkill":
                victim.proc.returncode == -signal.SIGKILL,
            "inflight_on_victim": inflight_on_victim,
            "streams_migrated": len(migrated),
            "zero_request_loss": all(
                s.finish_reason in ("eos", "length")
                for s in streams + streams2),
            "token_continuity": bool(continuity and phase2_ok
                                     and monotone),
            "failovers": router.stats["failovers"],
            "failover_seconds": router.stats["failover_seconds"],
            "kill_to_all_complete_s": round(t_recovered - t_kill, 3),
            "ttft_p50_before_kill_s": round(_pct(ttft1, 0.50), 3),
            "ttft_p99_before_kill_s": round(_pct(ttft1, 0.99), 3),
            "ttft_p99_after_kill_s": round(_pct(ttft2, 0.99), 3),
            "ttft_after_kill_bounded":
                _pct(ttft2, 0.99) <= args.ttft_bound,
            "rollup_narrates_failover": any(
                e.get("event") == "failover" for e in events),
            "rollup_events": events[-6:],
        }
    finally:
        router.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_fleet.json"))
    ap.add_argument("--child", choices=("replica",))
    ap.add_argument("--name", default="r0")
    ap.add_argument("--dir", default=None)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--new", type=int, default=48,
                    help="chaos-leg generation length (long enough "
                         "that the kill lands mid-stream)")
    ap.add_argument("--leg1-new", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--groups", type=int, default=4,
                    help="affinity leg: shared-prefix families")
    ap.add_argument("--per-group", type=int, default=4)
    ap.add_argument("--before-kill", type=int, default=6,
                    help="chaos leg: streams inflight at the kill")
    ap.add_argument("--after-kill", type=int, default=4)
    ap.add_argument("--ttft-bound", type=float, default=30.0,
                    help="post-failover admission TTFT p99 ceiling (s)")
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
        # 3 groups over 2 replicas: wave size coprime to the replica
        # count, so round-robin cannot accidentally pin every family
        # to one replica (which would tie the affinity comparison)
        args.groups, args.per_group = 3, 3
        args.before_kill, args.after_kill = 4, 2
        args.new, args.ttft_bound = 32, 60.0

    if args.child:
        if not args.dir:
            ap.error("--child requires --dir")
        _child_replica(args)
        return 0

    import tempfile

    import jax

    tmp = tempfile.mkdtemp(prefix="bench_fleet_")

    # the uninterrupted greedy oracle for every chaos-leg prompt —
    # same seed-0 weights the replicas build, so a migrated stream's
    # full token list must match bit for bit
    rng = np.random.RandomState(7)
    mk = lambda: [rng.randint(4, args.vocab, (args.prompt,))
                  .astype(np.int32).tolist()
                  for _ in range(args.before_kill)]
    prompts1 = mk()
    prompts2 = [p for p in _shared_prefix_workload(args, 9)
                [:args.after_kill]]
    model = _build_model(args)
    ref = _engine(model, args).generate(prompts1 + prompts2,
                                        max_new_tokens=args.new)
    oracle = {tuple(p): list(o)
              for p, o in zip(prompts1 + prompts2, ref)}

    t0 = time.perf_counter()
    reps = _spawn_fleet(args, tmp, args.replicas)
    boot_s = time.perf_counter() - t0
    print(f"fleet up: {args.replicas} replicas in {boot_s:.1f}s")
    try:
        affinity = _affinity_leg(args, reps)
        chaos = _chaos_leg(args, reps, oracle, prompts1, prompts2)
    finally:
        _kill_fleet(reps)
    print(f"chaos: killed {chaos['victim']} with "
          f"{chaos['inflight_on_victim']} streams inflight | "
          f"migrated {chaos['streams_migrated']} | loss-free "
          f"{chaos['zero_request_loss']} | continuity "
          f"{chaos['token_continuity']} | failover "
          f"{chaos['failover_seconds']}s | post-kill TTFT p99 "
          f"{chaos['ttft_p99_after_kill_s']}s")

    summary = {
        "affinity_hit_rate": affinity["affinity"]["prefix_hit_rate"],
        "round_robin_hit_rate":
            affinity["round_robin"]["prefix_hit_rate"],
        "affinity_wins": affinity["affinity_wins"],
        "zero_request_loss": chaos["zero_request_loss"],
        "token_continuity": chaos["token_continuity"],
        "killed_by_sigkill": chaos["killed_by_sigkill"],
        "streams_migrated": chaos["streams_migrated"],
        "failover_seconds": chaos["failover_seconds"],
        "ttft_p99_after_kill_s": chaos["ttft_p99_after_kill_s"],
        "ttft_after_kill_bounded": chaos["ttft_after_kill_bounded"],
        "rollup_narrates_failover": chaos["rollup_narrates_failover"],
    }
    out = {
        "bench": "fleet front door: prefix-affinity routing win + "
                 "kill -9 zero-loss failover across replicas",
        "device": str(jax.devices()[0].device_kind)
        if jax.devices() else "unknown",
        "smoke": bool(args.smoke),
        "config": {k: getattr(args, k) for k in
                   ("replicas", "slots", "prompt", "new", "chunk",
                    "page_size", "groups", "per_group", "before_kill",
                    "after_kill", "ttft_bound", "layers", "hidden",
                    "heads", "vocab")},
        "legs": {"affinity": affinity, "chaos": chaos},
        "summary": summary,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out} (affinity {summary['affinity_hit_rate']:.2%}"
          f" vs rr {summary['round_robin_hit_rate']:.2%}, loss-free="
          f"{summary['zero_request_loss']}, continuity="
          f"{summary['token_continuity']})")
    ok = all(summary[k] for k in
             ("affinity_wins", "zero_request_loss", "token_continuity",
              "killed_by_sigkill", "ttft_after_kill_bounded",
              "rollup_narrates_failover")) and \
        summary["streams_migrated"] >= 1
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
