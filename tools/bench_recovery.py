"""Recovery benchmark: executable handoff vs cold recompile, and a
kill -9'd serve resumed in a fresh process (inference.durability).

Two legs, both asserted (the durable-serving acceptance bar):

* **in_process** — the same overload workload is driven into a fatal
  step fault twice; the engine is rebuilt once COLD
  (``recover(handoff=False)``: every executable recompiles) and once
  with **executable handoff** (the default: the dead engine's live
  compiled executables move to the rebuilt engine under a config-
  fingerprint gate).  Measured: ``recover()`` + the first successful
  step — the latency a fatal fault adds before the engine serves
  again.  Handoff must be **>= 5x** faster than cold on CPU (measured
  ~100x+: the cold path pays full mixed+decode recompiles), with
  greedy parity in both legs.

* **cross_process** — a child process serves with the write-ahead
  journal armed (``fsync=always``) and **SIGKILLs itself** mid-serve
  (no cleanup, no atexit — real process death); a second child rebuilds
  via ``restore_from_dir`` in a fresh process and serves to completion.
  Asserted: the serve child really died by SIGKILL, **zero request
  loss** (every offered request reaches eos/length), **no re-emitted
  tokens** (the two lives' streamed tokens concatenate to EXACTLY the
  uninterrupted reference — the journal watermark gates ``_emit``),
  and **bit-identical greedy outputs** vs the uninterrupted run.
  JAX's persistent compilation cache (`core.compile_cache`) warms the
  restore's executables when available; its effect is reported, not
  asserted.

Emits BENCH_recovery.json.

Usage:
    python tools/bench_recovery.py [--out BENCH_recovery.json] [--smoke]

``--smoke`` (or env BENCH_SMOKE=1) shrinks shapes so CI can assert the
script end-to-end (tests/test_tooling.py).  The ``--child`` modes are
internal (the cross-process leg re-execs this script).

Processes and the chip: a CPU program on both sides, pinned like
tools/bench_fleet.py (see the statement there).  On a chip the serve child
and the restore child would have to own it one after the other, with the
parent off JAX.
"""
import argparse
import json
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # before JAX; children inherit it

import signal
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.core.compile_cache import enable_compile_cache  # noqa: E402
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
                        prefill_chunk_tokens=args.chunk, **kw)


def _workload(args):
    """Deterministic prompts shared by every process: the reference
    run, the serve child and the restore child must agree byte for
    byte."""
    rng = np.random.RandomState(0)
    return [rng.randint(4, args.vocab, (args.prompt,)).astype(np.int32)
            for _ in range(args.requests)]


def _reference(model, args):
    eng = _engine(model, args)
    reqs = [eng.add_request(p, max_new_tokens=args.new)
            for p in _workload(args)]
    eng.run()
    return {r.request_id: list(r.generated_ids) for r in reqs}


# ---------------------------------------------------------------------------
# leg 1: in-process recovery latency, handoff vs cold recompile
# ---------------------------------------------------------------------------
def _recovery_latency(model, args, handoff):
    from paddle_tpu.inference import resilience
    from paddle_tpu.inference.errors import StepFault

    eng = _engine(model, args,
                  fault_plan=f"step@{args.fault_at}-"
                             f"{args.fault_at + 8}")
    reqs = [eng.add_request(p, max_new_tokens=args.new)
            for p in _workload(args)]
    fault = None
    while fault is None:
        try:
            eng.step()
        except StepFault as e:
            fault = e
    t0 = time.perf_counter()
    new = resilience.recover(eng, fault=fault, handoff=handoff)
    new.step()  # cold pays the recompile right here
    latency = time.perf_counter() - t0
    new.run()
    outs = {r.request_id: list(r.generated_ids) for r in reqs}
    return latency, outs


def _in_process_leg(model, args, reference):
    from paddle_tpu.inference.serving import (decode_stats,
                                              reset_decode_stats)

    reset_decode_stats()
    cold_s, cold_outs = _recovery_latency(model, args, handoff=False)
    cold_compiles = decode_stats()["mixed_compiles"]
    reset_decode_stats()
    warm_s, warm_outs = _recovery_latency(model, args, handoff=True)
    st = decode_stats()
    # request ids differ per run; compare by admission order
    ref_seq = [v for _, v in sorted(reference.items())]
    parity = [v for _, v in sorted(cold_outs.items())] == ref_seq and \
        [v for _, v in sorted(warm_outs.items())] == ref_seq
    return {
        "cold_recovery_s": round(cold_s, 4),
        "handoff_recovery_s": round(warm_s, 4),
        "speedup": round(cold_s / warm_s, 1) if warm_s else None,
        "parity": bool(parity),
        "exec_handoffs": st["exec_handoffs"],
        # each leg's FIRST engine compiles the mixed step once; any
        # compile beyond that is the rebuilt engine recompiling
        "handoff_leg_recompiles": st["mixed_compiles"] - 1,
        "cold_leg_recompiles": cold_compiles - 1,
        "retraces_after_warmup": st["retraces_after_warmup"],
    }


# ---------------------------------------------------------------------------
# leg 2: kill -9 + fresh-process restore (child modes)
# ---------------------------------------------------------------------------
def _stream_hook(stream_path, rid):
    fh = open(stream_path, "a")

    def on_token(tok):
        fh.write(f"{rid} {tok}\n")
        fh.flush()
    return on_token


def _child_serve(args):
    """Serve with the journal armed, then SIGKILL ourselves at a step
    boundary — no cleanup runs, the journal and snapshot on disk are
    all that survives."""
    paddle.set_flags({"journal_fsync": "always",
                      "snapshot_interval_steps": args.snap_every})
    enable_compile_cache()
    model = _build_model(args)
    eng = _engine(model, args, journal_dir=args.dir)
    stream = os.path.join(args.dir, "stream.log")
    for p in _workload(args):
        req = eng.add_request(p, max_new_tokens=args.new)
        req.on_token = _stream_hook(stream, req.request_id)
    for _ in range(args.kill_after):
        eng.step()
    os.kill(os.getpid(), signal.SIGKILL)


def _child_restore(args):
    """Fresh process: rebuild from the journal, finish the serve, and
    report what happened."""
    from paddle_tpu.inference import durability

    paddle.set_flags({"journal_fsync": "always"})
    enable_compile_cache()
    model = _build_model(args)
    t0 = time.perf_counter()
    eng, rmap = durability.restore_from_dir(args.dir, model)
    restore_s = time.perf_counter() - t0
    stream = os.path.join(args.dir, "stream.log")
    for rid, req in rmap.items():
        req.on_token = _stream_hook(stream, rid)
    t1 = time.perf_counter()
    eng.step()
    first_step_s = time.perf_counter() - t1
    eng.run()
    out = {
        "restore_s": round(restore_s, 4),
        "first_step_s": round(first_step_s, 4),
        "snapshot_present":
            durability.load_snapshot(args.dir) is not None,
        "results": {rid: {"generated": list(r.generated_ids),
                          "finish_reason": r.finish_reason}
                    for rid, r in rmap.items()},
    }
    with open(os.path.join(args.dir, "restore.json"), "w") as f:
        json.dump(out, f)


def _cross_process_leg(args, reference, tmp):
    child_env = dict(os.environ)  # carries JAX_PLATFORMS=cpu (module top)
    base = [sys.executable, os.path.abspath(__file__), "--dir", tmp]
    for k in ("slots", "requests", "prompt", "new", "chunk",
              "page_size", "layers", "hidden", "heads", "vocab",
              "kill_after", "snap_every"):
        base += [f"--{k.replace('_', '-')}", str(getattr(args, k))]
    serve = subprocess.run(base + ["--child", "serve"],
                           capture_output=True, text=True,
                           env=child_env, timeout=600)
    if serve.returncode != -signal.SIGKILL:
        raise RuntimeError(
            f"serve child was supposed to die by SIGKILL, exited "
            f"{serve.returncode}: {serve.stderr[-2000:]}")
    stream = os.path.join(tmp, "stream.log")
    pre = sum(1 for _ in open(stream)) if os.path.exists(stream) else 0

    t0 = time.perf_counter()
    restore = subprocess.run(base + ["--child", "restore"],
                             capture_output=True, text=True,
                             env=child_env, timeout=600)
    restore_wall_s = time.perf_counter() - t0
    if restore.returncode != 0:
        raise RuntimeError(
            f"restore child failed: {restore.stderr[-2000:]}")
    with open(os.path.join(tmp, "restore.json")) as f:
        rj = json.load(f)

    # streamed tokens across BOTH lives, in order, per request
    streamed = {}
    for line in open(stream):
        rid, tok = line.split()
        streamed.setdefault(int(rid), []).append(int(tok))

    ref = {int(k): v for k, v in reference.items()}
    results = {int(k): v for k, v in rj["results"].items()}
    bit_identical = all(
        results.get(rid, {}).get("generated") == gen
        for rid, gen in ref.items())
    no_loss = sorted(results) == sorted(ref) and all(
        r["finish_reason"] in ("eos", "length")
        for r in results.values())
    # the two lives' streams concatenate to EXACTLY the reference:
    # no token re-emitted, no token lost
    no_reemit = all(streamed.get(rid, []) == gen
                    for rid, gen in ref.items())
    from paddle_tpu.inference.durability import read_journal

    events, _ = read_journal(os.path.join(tmp, "journal.wal"))
    return {
        "kill_after_steps": args.kill_after,
        "serve_exit": serve.returncode,
        "killed_by_sigkill": True,
        "tokens_streamed_before_kill": pre,
        "tokens_streamed_total": sum(len(v) for v in streamed.values()),
        "journal_events": len(events),
        "snapshot_present": rj["snapshot_present"],
        "restore_s": rj["restore_s"],
        "restore_first_step_s": rj["first_step_s"],
        "restore_wall_s": round(restore_wall_s, 3),
        "zero_request_loss": bool(no_loss),
        "no_reemitted_tokens": bool(no_reemit),
        "bit_identical": bool(bit_identical),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_recovery.json"))
    ap.add_argument("--child", choices=("serve", "restore"))
    ap.add_argument("--dir", default=None)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=24)
    ap.add_argument("--new", type=int, default=24)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--kill-after", type=int, default=18,
                    help="serve-child steps before the self-SIGKILL "
                         "(mid-serve: running AND queued requests die)")
    ap.add_argument("--snap-every", type=int, default=8)
    ap.add_argument("--fault-at", type=int, default=14,
                    help="in-process leg: first occurrence of the "
                         "fatal step burst")
    ap.add_argument("--min-speedup", type=float, default=5.0)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes: CI end-to-end check")
    args = ap.parse_args()
    if os.environ.get("BENCH_SMOKE") == "1":
        args.smoke = True
    if args.smoke and args.child is None:
        args.requests, args.prompt, args.new = 3, 12, 12
        args.chunk, args.page_size = 8, 8
        args.hidden, args.vocab = 64, 128
        args.kill_after, args.snap_every, args.fault_at = 10, 4, 10

    if args.child:
        if not args.dir:
            ap.error("--child requires --dir")
        (_child_serve if args.child == "serve"
         else _child_restore)(args)
        return 0

    import tempfile

    import jax

    model = _build_model(args)
    reference = _reference(model, args)

    in_proc = _in_process_leg(model, args, reference)
    print(f"in-process : cold {in_proc['cold_recovery_s'] * 1e3:.1f}ms"
          f" | handoff {in_proc['handoff_recovery_s'] * 1e3:.1f}ms"
          f" | speedup {in_proc['speedup']}x"
          f" | parity {in_proc['parity']}")

    tmp = tempfile.mkdtemp(prefix="bench_recovery_")
    cross = _cross_process_leg(args, reference, tmp)
    print(f"cross-proc : SIGKILL after {cross['kill_after_steps']} "
          f"steps ({cross['tokens_streamed_before_kill']} tokens "
          f"streamed) | restore {cross['restore_s'] * 1e3:.1f}ms + "
          f"first step {cross['restore_first_step_s'] * 1e3:.1f}ms | "
          f"loss-free {cross['zero_request_loss']} | no-reemit "
          f"{cross['no_reemitted_tokens']} | bit-identical "
          f"{cross['bit_identical']}")

    summary = {
        "handoff_speedup": in_proc["speedup"],
        "handoff_speedup_ok":
            in_proc["speedup"] is not None and
            in_proc["speedup"] >= args.min_speedup,
        "in_process_parity": in_proc["parity"],
        "zero_request_loss": cross["zero_request_loss"],
        "no_reemitted_tokens": cross["no_reemitted_tokens"],
        "bit_identical": cross["bit_identical"],
        "killed_by_sigkill": cross["serve_exit"] == -signal.SIGKILL,
    }
    out = {
        "bench": "durable serving: executable-handoff recovery latency "
                 "+ kill -9 restore from journal/snapshot",
        "device": str(jax.devices()[0].device_kind)
        if jax.devices() else "unknown",
        "smoke": bool(args.smoke),
        "config": {k: getattr(args, k) for k in
                   ("slots", "requests", "prompt", "new", "chunk",
                    "page_size", "kill_after", "snap_every", "fault_at",
                    "min_speedup", "layers", "hidden", "heads",
                    "vocab")},
        "legs": {"in_process": in_proc, "cross_process": cross},
        "summary": summary,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out} (speedup={summary['handoff_speedup']}x, "
          f"loss-free={summary['zero_request_loss']}, "
          f"no-reemit={summary['no_reemitted_tokens']}, "
          f"bit-identical={summary['bit_identical']})")
    ok = all(summary[k] for k in
             ("handoff_speedup_ok", "in_process_parity",
              "zero_request_loss", "no_reemitted_tokens",
              "bit_identical", "killed_by_sigkill"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
