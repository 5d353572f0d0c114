"""Cost-observatory benchmark: calibration accuracy, HBM-ledger
reconciliation, and cost-accounting overhead.

Three legs (the ISSUE-13 acceptance bar):

* **calibration** — a mixed prefill/decode/spec workload (staggered
  arrivals so steps interleave prompt chunks with decodes, then a
  speculative engine over a repetitive workload) served with the cost
  observatory armed.  Every flight record carries its
  ``predicted_s`` / ``actual_s`` pair; after warmup (predictions made
  from an already-learned calibration factor) the MEDIAN
  |predicted - actual| / actual must be <= ``--error-bound`` (25% by
  default; asserted at full scale only — smoke steps are sub-
  millisecond and timer-noise dominated).

* **ledger** — after the serve, `CostModel.hbm_ledger` attributes
  every live device byte by category and reconciles against
  ``jax.live_arrays()``: the unattributed residue must stay <=
  ``--ledger-bound`` (5%) of total live bytes, and the weights /
  kv_pages categories must be nonzero (the ledger actually found the
  engine's arrays, it did not just report an empty process).

* **overhead** — an identical decode workload served with the cost
  observatory ON vs OFF (``cost_model=False``): outputs must be
  bit-exact with zero new executables and 0 warm retraces, and the
  per-step wall overhead <= ``--overhead-bound`` (2% by default; full
  scale only), on the smaller of the interleaved differential and the
  direct per-entry-point accounting.

Emits BENCH_cost.json.

Usage:
    python tools/bench_cost.py [--out BENCH_cost.json] [--smoke]
                               [--error-bound 0.25]
                               [--ledger-bound 0.05]
                               [--overhead-bound 0.02]
"""
import argparse
import json
import os
import statistics
import sys
import time

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

    kw.setdefault("flight_window", 4096)  # keep every record
    return DecodeEngine(model, max_batch_size=args.slots,
                        max_seq_len=args.prompt + args.new + 8,
                        page_size=args.page_size,
                        prefill_chunk_tokens=args.chunk, **kw)


def _cost_records(eng):
    return [r["cost"] for r in eng._flight.records()
            if r.get("kind") == "step" and r.get("cost")
            and r["cost"].get("actual_s")]


def _errors(recs, calibrated_only=True):
    return [abs(c["predicted_s"] - c["actual_s"]) / c["actual_s"]
            for c in recs if c.get("calibrated") or not calibrated_only]


# ---------------------------------------------------------------------------
# leg 1: calibration accuracy under a mixed workload
# ---------------------------------------------------------------------------
def _calibration_leg(model, args):
    from paddle_tpu.inference.serving import decode_stats, \
        reset_decode_stats

    reset_decode_stats()
    rng = np.random.RandomState(0)

    # phase A: staggered arrivals — steps interleave prompt chunks
    # (mixed) with running decodes, and pure decode runs the tail
    eng = _engine(model, args)
    pending = [rng.randint(4, args.vocab,
                           (args.prompt,)).astype(np.int32)
               for _ in range(args.requests)]
    reqs = []
    while pending or eng._queue or eng._active.any():
        if pending:
            reqs.append(eng.add_request(pending.pop(0),
                                        max_new_tokens=args.new))
        eng.step()
    recs_mixed = _cost_records(eng)
    # the ledger audits NOW, while this engine's arrays are the only
    # engine arrays alive — the unattributed residue then measures
    # real attribution gaps, not the other legs' engines
    ledger = _ledger_leg(eng, args)

    # phase B: a speculative engine over a repetitive workload (the
    # prompt-lookup drafter's home turf) — spec rounds calibrate their
    # own "spec" executable kind
    eng_spec = _engine(model, args, spec_decode_k=2)
    base = rng.randint(4, args.vocab, (8,)).astype(np.int32)
    rep = [np.tile(base, args.prompt // 8 + 1)[:args.prompt]
           for _ in range(args.requests)]
    eng_spec.generate(rep, max_new_tokens=args.new)
    recs_spec = _cost_records(eng_spec)

    st = decode_stats()
    errs = _errors(recs_mixed) + _errors(recs_spec)
    by_fn = {}
    for c in recs_mixed + recs_spec:
        if c.get("calibrated"):
            by_fn.setdefault(c["fn"], []).append(
                abs(c["predicted_s"] - c["actual_s"]) / c["actual_s"])
    z = eng.statusz()["cost"]
    # the spec engine's calibration lives on its own cost model —
    # merge both views so the leg reports every executable kind
    z_spec = eng_spec.statusz()["cost"]
    z["calibration"].update(z_spec["calibration"])
    z["error_ratio"].update(z_spec["error_ratio"])
    return {
        "records": len(recs_mixed) + len(recs_spec),
        "calibrated_records": len(errs),
        "median_error": round(statistics.median(errs), 4) if errs
        else None,
        "p90_error": round(sorted(errs)[int(0.9 * len(errs))], 4)
        if errs else None,
        "median_error_by_fn": {
            fn: round(statistics.median(v), 4)
            for fn, v in sorted(by_fn.items())},
        "fn_kinds": sorted(by_fn),
        "calibration": {k: round(v, 3)
                        for k, v in z["calibration"].items()},
        "error_gauges": {k: round(v, 4)
                         for k, v in z["error_ratio"].items()},
        "profiles": sorted(z["profiles"]),
        "profile_sources": sorted({p["source"]
                                   for p in z["profiles"].values()}),
        "cost_profiles": st["cost_profiles"],
        "cost_updates": st["cost_updates"],
        "retraces_after_warmup": st["retraces_after_warmup"],
        "headroom": z["headroom"],
    }, ledger


# ---------------------------------------------------------------------------
# leg 2: HBM-ledger reconciliation
# ---------------------------------------------------------------------------
def _ledger_leg(eng, args):
    led = eng._cost.hbm_ledger(set_gauges=True)
    from paddle_tpu import observability as obs

    snap = obs.snapshot()
    gauge_rows = snap.get("paddle_hbm_ledger_bytes", {}).get(
        "series", [])
    total = max(led["total_live_bytes"], 1)
    return {
        "categories": led["categories"],
        "total_live_bytes": led["total_live_bytes"],
        "attributed_bytes": led["attributed_bytes"],
        "unattributed_bytes": led["unattributed_bytes"],
        "unattributed_frac": round(
            led["unattributed_bytes"] / total, 6),
        "gauge_series": len(gauge_rows),
        "weights_nonzero": led["categories"]["weights"] > 0,
        "kv_pages_nonzero": led["categories"]["kv_pages"] > 0,
    }


# ---------------------------------------------------------------------------
# leg 3: overhead — cost accounting on vs off, bit-exact + bounded
# ---------------------------------------------------------------------------
def _overhead_leg(model, args):
    from paddle_tpu.inference.serving import DecodeEngine, \
        decode_stats, reset_decode_stats
    from paddle_tpu.observability.costmodel import CostModel

    rng = np.random.RandomState(1)
    prompts = [rng.randint(4, args.vocab,
                           (args.oh_prompt,)).astype(np.int32)
               for _ in range(args.oh_requests)]

    def mk(cost_model):
        eng = DecodeEngine(model, max_batch_size=args.slots,
                           max_seq_len=args.oh_prompt + args.oh_new + 8,
                           page_size=args.oh_page,
                           prefill_chunk_tokens=args.oh_chunk,
                           cost_model=cost_model)
        eng.generate([prompts[0]], max_new_tokens=2)  # warm
        return eng

    # direct accounting: time every cost-model entry point in place
    acc = {"s": 0.0}
    hooks = ("note_step_begin", "observe")
    saved = {}
    for name in hooks:
        orig = saved[name] = getattr(CostModel, name)

        def timed(self, *a, _orig=orig, **kw):
            t0 = time.perf_counter()
            out = _orig(self, *a, **kw)
            acc["s"] += time.perf_counter() - t0
            return out
        setattr(CostModel, name, timed)

    def serve(eng):
        reqs = [eng.add_request(p, max_new_tokens=args.oh_new)
                for p in prompts]
        reset_decode_stats()
        t0 = time.perf_counter()
        eng.run()
        wall = time.perf_counter() - t0
        st = decode_stats(reset=True)
        assert st["retraces_after_warmup"] == 0
        return [list(r.generated_ids) for r in reqs], \
            wall / max(st["steps"], 1), st["steps"], st

    try:
        eng_off = mk(False)
        eng_on = mk(True)
        t_off = t_on = None
        outs_off = outs_on = None
        steps_on = 0
        st_off = st_on = None
        for _ in range(args.reps):
            outs_off, dt, _, st_off = serve(eng_off)
            t_off = dt if t_off is None else min(t_off, dt)
            outs_on, dt, n, st_on = serve(eng_on)
            t_on = dt if t_on is None else min(t_on, dt)
            steps_on += n
    finally:
        for name, orig in saved.items():
            setattr(CostModel, name, orig)
    # identical compile counters: the observatory lowers but never
    # compiles — cost-on builds the exact executable set cost-off does
    same_execs = all(
        st_on[k] == st_off[k]
        for k in ("decode_compiles", "mixed_compiles",
                  "prefill_compiles"))
    cost_us = acc["s"] / max(steps_on, 1) * 1e6
    diff_frac = t_on / t_off - 1.0
    acct_frac = cost_us * 1e-6 / t_on
    return {
        "parity": outs_on == outs_off,
        "zero_new_executables": same_execs,
        "step_ms_cost_off": round(t_off * 1e3, 4),
        "step_ms_cost_on": round(t_on * 1e3, 4),
        "overhead_frac": round(diff_frac, 4),
        "cost_us_per_step": round(cost_us, 2),
        "accounted_frac": round(acct_frac, 4),
        "gated_frac": round(min(diff_frac, acct_frac), 4),
        "reps": args.reps,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_cost.json"))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=96)
    ap.add_argument("--new", type=int, default=48)
    ap.add_argument("--chunk", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--reps", type=int, default=4)
    # overhead-leg shapes: decode-dominated, production-like steps
    # (ctx-512, the bench_decode scale the fixed
    # host-microsecond accounting cost is judged against)
    ap.add_argument("--oh-prompt", type=int, default=512)
    ap.add_argument("--oh-new", type=int, default=32)
    ap.add_argument("--oh-requests", type=int, default=4)
    ap.add_argument("--oh-chunk", type=int, default=64)
    ap.add_argument("--oh-page", type=int, default=32)
    ap.add_argument("--error-bound", type=float, default=0.25)
    ap.add_argument("--ledger-bound", type=float, default=0.05)
    ap.add_argument("--overhead-bound", type=float, default=0.02)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    if os.environ.get("BENCH_SMOKE") == "1":
        args.smoke = True
    if args.smoke:
        args.requests, args.prompt, args.new = 4, 48, 16
        args.hidden, args.vocab, args.slots = 128, 128, 2
        args.reps = 2
        args.oh_prompt, args.oh_new = 64, 12
        args.oh_requests = 2

    import jax

    from paddle_tpu import observability

    observability.reset()
    model = _build_model(args)

    legs = {}
    legs["calibration"], legs["ledger"] = _calibration_leg(model, args)
    print(f"calibration: {legs['calibration']['calibrated_records']} "
          f"records, median err "
          f"{legs['calibration']['median_error']}, by fn "
          f"{legs['calibration']['median_error_by_fn']}")
    print(f"ledger: {legs['ledger']['total_live_bytes']}B live, "
          f"unattributed {legs['ledger']['unattributed_frac'] * 100:.3f}%")
    # the overhead leg's ctx-512 shapes need their own position table
    if args.smoke:
        oh_model = model
    else:
        import copy as _copy

        oh_args = _copy.copy(args)
        oh_args.prompt, oh_args.new = args.oh_prompt, args.oh_new
        oh_model = _build_model(oh_args)
    legs["overhead"] = _overhead_leg(oh_model, args)
    print(f"overhead: off {legs['overhead']['step_ms_cost_off']}ms "
          f"on {legs['overhead']['step_ms_cost_on']}ms "
          f"(diff {legs['overhead']['overhead_frac'] * 100:+.2f}%, "
          f"accounted {legs['overhead']['cost_us_per_step']}us = "
          f"+{legs['overhead']['accounted_frac'] * 100:.2f}%) parity "
          f"{legs['overhead']['parity']}")

    cal = legs["calibration"]
    summary = {
        "median_error": cal["median_error"],
        "error_bound": args.error_bound,
        "mixed_and_spec_calibrated": {"mixed", "decode"} <=
        set(cal["calibration"]) and "spec" in cal["calibration"],
        "profiles_extracted": cal["cost_profiles"] > 0,
        "unattributed_frac": legs["ledger"]["unattributed_frac"],
        "ledger_bound": args.ledger_bound,
        "ledger_within_bound": legs["ledger"]["unattributed_frac"]
        <= args.ledger_bound,
        "ledger_categories_found": legs["ledger"]["weights_nonzero"]
        and legs["ledger"]["kv_pages_nonzero"],
        "parity_cost_off": legs["overhead"]["parity"],
        "zero_new_executables": legs["overhead"]["zero_new_executables"],
        "overhead_frac": legs["overhead"]["overhead_frac"],
        "accounted_frac": legs["overhead"]["accounted_frac"],
        "gated_frac": legs["overhead"]["gated_frac"],
        "overhead_bound": args.overhead_bound,
        "zero_warm_retraces": cal["retraces_after_warmup"] == 0,
    }
    out = {
        "bench": "serving cost observatory: calibration accuracy, HBM "
                 "ledger reconciliation, accounting overhead",
        "device": str(jax.devices()[0].device_kind)
        if jax.devices() else "unknown",
        "smoke": bool(args.smoke),
        "config": {k: getattr(args, k) for k in
                   ("slots", "requests", "prompt", "new", "chunk",
                    "layers", "hidden", "heads", "vocab", "page_size",
                    "reps", "oh_prompt", "oh_new", "oh_requests",
                    "oh_chunk", "oh_page", "error_bound",
                    "ledger_bound", "overhead_bound")},
        "legs": legs,
        "summary": summary,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out} (median_err={summary['median_error']}, "
          f"unattributed={summary['unattributed_frac'] * 100:.3f}%, "
          f"overhead={summary['gated_frac'] * 100:+.2f}%)")
    ok = all(summary[k] for k in
             ("mixed_and_spec_calibrated", "profiles_extracted",
              "ledger_within_bound", "ledger_categories_found",
              "parity_cost_off", "zero_new_executables",
              "zero_warm_retraces"))
    if not args.smoke:
        # the accuracy and overhead RATIOS are gated at full scale
        # only: smoke steps are sub-millisecond, where CPU timer noise
        # dwarfs both the prediction error and the accounting cost
        ok = ok and summary["median_error"] is not None and \
            summary["median_error"] <= args.error_bound and \
            summary["gated_frac"] <= args.overhead_bound
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
