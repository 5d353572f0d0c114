"""Quantized-KV serving benchmark: int8 pages vs fp32 at fixed pool
bytes (FLAGS_kv_quant, ISSUE 12 acceptance).

Four legs, greedy, on the CPU-sized GPT the other decode benches use:

* **density** — both engines get the SAME pool **byte** budget; the
  int8 engine's pages cost ~a quarter of the fp32 engine's (int8
  payload + f32 per-page/head scales), so it fits proportionally more
  pages and therefore more concurrent slots.  A bench_slo-style
  overload workload (more requests than either engine's slots) is
  served to completion through each; sustained tokens/s = total
  generated tokens / serve wall.  Gates: slots_int8/slots_fp32 >= 1.8
  and tokens_per_s ratio >= 1.4.
* **quality** — token-level agreement with the fp32 engine over an
  eval workload, measured TEACHER-FORCED: the fp32 engine's reference
  generations are replayed context by context and the int8 engine
  predicts each next token conditioned on the REFERENCE prefix (one
  single-token request per position, riding the prefix cache), so one
  early flip cannot cascade into a misleading rate.  Gate: match
  >= 99%.  Max final-position logit drift |logits_int8 - logits_fp32|
  is measured through a probe that replays the serving write/read
  path (`pa.paged_quant_write` + `pa.paged_attention`) and
  self-checks against the engines' own sampled tokens.  Gate: drift
  <= --drift-bound.
* **parity_off** — `kv_quant="off"` must be bit-exact with the
  default engine, compile ZERO new executables (compile counters
  identical, `kv_quant_compiles == 0`), and leave every quant counter
  at zero.
* all legs: **0 warm retraces**.

Emits BENCH_kvquant.json.

Usage:
    python tools/bench_kv_quant.py [--out BENCH_kvquant.json]
                                   [--pool-kib 48] [--smoke]

``--smoke`` (or env BENCH_SMOKE=1) shrinks shapes so CI can assert the
script end-to-end (tests/test_tooling.py).
"""
import argparse
import json
import os
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
                    max_seq_len=args.seq + 64,
                    use_parallel_layers=False, dropout=0.0)
    model = GPT(cfg)
    model.eval()
    return model


def _page_bytes(model, args, quant):
    cfg = model.cfg
    head_dim = cfg.hidden_size // cfg.num_heads
    payload = 2 * cfg.num_layers * cfg.num_heads * args.page_size * \
        head_dim * (1 if quant else 4)
    scales = 2 * cfg.num_layers * cfg.num_heads * 4 if quant else 0
    return payload + scales


def _engine(model, args, mode, num_pages, slots):
    from paddle_tpu.inference.serving import DecodeEngine

    # the per-STEP prompt budget scales with the slot count (same
    # per-slot prefill bandwidth for both engines — a 4x-denser engine
    # on an 8-slot budget would starve its own admissions), while
    # prefill_q_max pins the mixed executable's row width so the two
    # engines run the same step shape per slot
    return DecodeEngine(model, max_batch_size=slots,
                        max_seq_len=args.seq, page_size=args.page_size,
                        num_pages=num_pages, kv_quant=mode,
                        prefill_chunk_tokens=max(
                            args.chunk, args.chunk_per_slot * slots),
                        prefill_q_max=args.chunk)


def _prompts(args, n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, args.vocab, (args.prompt,)).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# density: fixed pool bytes -> slots -> overload throughput
# ---------------------------------------------------------------------------
def _density_leg(model, args):
    from paddle_tpu.inference.serving import (decode_stats,
                                              reset_decode_stats)

    budget = args.pool_kib * 1024
    pages_per_seq = -(-args.seq // args.page_size)
    legs = {}
    outs = {}
    for mode in ("off", "int8"):
        quant = mode == "int8"
        num_pages = budget // _page_bytes(model, args, quant)
        slots = max(int(num_pages // pages_per_seq), 1)
        num_pages = slots * pages_per_seq
        eng = _engine(model, args, mode, num_pages, slots)
        # overload: the same request count for both engines, sized past
        # the BIGGER engine's slots so both serve under queue pressure
        prompts = _prompts(args, args.requests)
        warm = _prompts(args, 1, seed=777)
        eng.generate(warm, max_new_tokens=2)  # compile outside the wall
        reset_decode_stats()
        t0 = time.perf_counter()
        toks = eng.generate(prompts, max_new_tokens=args.new_tokens)
        wall = time.perf_counter() - t0
        st = decode_stats()
        n_tokens = sum(len(t) for t in toks)
        occ = eng._kv_byte_occupancy()
        legs[mode] = {
            "slots": slots,
            "num_pages": num_pages,
            "pool_bytes": num_pages * _page_bytes(model, args, quant),
            "bytes_per_token": occ["bytes_per_token"],
            "requests": len(prompts),
            "tokens": n_tokens,
            "wall_s": round(wall, 4),
            "tokens_per_s": round(n_tokens / wall, 2),
            "batch_occupancy": round(st["batch_occupancy"], 4),
            "kv_quant_pages": st["kv_quant_pages"],
            "kv_quant_refolds": st["kv_quant_refolds"],
            "retraces_after_warmup": st["retraces_after_warmup"],
        }
        outs[mode] = toks
    return legs, outs


# ---------------------------------------------------------------------------
# quality: teacher-forced token match + logit-drift probe
# ---------------------------------------------------------------------------
def _reference_generations(model, args):
    eng = _engine(model, args, "off", None, 2)
    prompts = _prompts(args, args.eval_requests, seed=42)
    outs = eng.generate(prompts, max_new_tokens=args.eval_tokens)
    return prompts, outs


def _teacher_forced_match(model, args, prompts, refs):
    """For every reference position, ask the int8 engine for ONE
    next token conditioned on the reference prefix.  Successive
    extensions of one request prefix-hit each other, so this is much
    cheaper than it looks."""
    eng = _engine(model, args, "int8", None, 2)
    match = total = 0
    mismatches = []
    for p, ref in zip(prompts, refs):
        ctx = list(p)
        for i, want in enumerate(ref):
            got = eng.generate([np.asarray(ctx, np.int32)],
                               max_new_tokens=1)[0][0]
            total += 1
            if int(got) == int(want):
                match += 1
            else:
                mismatches.append({"pos": i, "want": int(want),
                                   "got": int(got)})
            ctx.append(int(want))  # teacher forcing: follow the ref
    return match, total, mismatches[:8]


def _logit_probe(model, args, prompts, refs):
    """Final-position logits for each reference context, through a
    probe that mirrors the serving path: rows written into, and
    attention read out of, the pool type the step bodies use
    (`pa.KVPool`).
    Self-check: the fp32 probe's argmax must equal the fp32 engine's
    sampled token (proves the probe measures the real path)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.serving import (_extract_gpt_params, _ln,
                                              _logits_of)
    from paddle_tpu.ops.pallas import paged_attention as pa

    params = _extract_gpt_params(model)
    cfg = model.cfg
    hd = cfg.hidden_size // cfg.num_heads
    page = args.page_size

    def forward(ids, quant):
        s = len(ids)
        n_pages = -(-s // page)
        bt = jnp.arange(n_pages, dtype=jnp.int32)[None]
        pos = jnp.arange(s, dtype=jnp.int32)
        kv = pa.KVPool.zeros(cfg.num_layers, cfg.num_heads, n_pages, page,
                             hd, jnp.int8 if quant else jnp.float32)
        x = params["wte"][jnp.asarray(ids)] + params["wpe"][pos]
        start = jnp.zeros(1, jnp.int32)
        lens = jnp.asarray([s], jnp.int32)
        for li, blk in enumerate(params["blocks"]):
            y = _ln(x, blk["ln1_w"], blk["ln1_b"],
                    float(getattr(model.ln_f, "_epsilon", 1e-5)))
            qkv = jnp.matmul(y, blk["qkv_w"]) + blk["qkv_b"]
            qkv = qkv.reshape(s, 3, cfg.num_heads, hd)
            q = qkv[:, 0][None]  # [1, S, H, D]
            kv, _ = kv.write(li, qkv[None, :, 1], qkv[None, :, 2], bt, start,
                             lens)
            attn = kv.attend(q, li, bt, lens, q_offsets=start)
            x = x + jnp.matmul(attn[0].reshape(s, cfg.hidden_size),
                               blk["out_w"]) + blk["out_b"]
            y = _ln(x, blk["ln2_w"], blk["ln2_b"],
                    float(getattr(model.ln_f, "_epsilon", 1e-5)))
            y = jax.nn.gelu(jnp.matmul(y, blk["fc1_w"]) + blk["fc1_b"],
                            approximate=True)
            x = x + jnp.matmul(y, blk["fc2_w"]) + blk["fc2_b"]
        h_last = _ln(x[-1:], params["lnf_w"], params["lnf_b"],
                     float(getattr(model.ln_f, "_epsilon", 1e-5)))
        return np.asarray(_logits_of(params, h_last)[0], np.float32)

    max_drift = 0.0
    probe_ok = True
    for p, ref in zip(prompts, refs):
        ctx = list(p)
        lf = forward(ctx, False)
        lq = forward(ctx, True)
        probe_ok = probe_ok and int(np.argmax(lf)) == int(ref[0])
        max_drift = max(max_drift, float(np.abs(lq - lf).max()))
    return max_drift, probe_ok


# ---------------------------------------------------------------------------
# off-mode parity
# ---------------------------------------------------------------------------
def _parity_off_leg(model, args):
    from paddle_tpu.inference.serving import (decode_stats,
                                              reset_decode_stats)

    prompts = _prompts(args, 4, seed=5)
    reset_decode_stats()
    default = _engine(model, args, "off", None, 2)
    out_default = default.generate(prompts,
                                   max_new_tokens=args.new_tokens)
    st_default = decode_stats(reset=True)
    off = _engine(model, args, "off", None, 2)
    out_off = off.generate(prompts, max_new_tokens=args.new_tokens)
    st_off = decode_stats(reset=True)
    compile_keys = ("decode_compiles", "mixed_compiles",
                    "prefill_compiles", "verify_compiles",
                    "draft_compiles", "kv_quant_compiles")
    return {
        "bit_exact": out_default == out_off,
        "compiles": {k: st_off[k] for k in compile_keys},
        "zero_new_executables": all(
            st_off[k] == st_default[k] for k in compile_keys)
        and st_off["kv_quant_compiles"] == 0,
        "quant_counters_zero": st_off["kv_quant_pages"] == 0
        and st_off["kv_quant_refolds"] == 0,
        "retraces_after_warmup": st_off["retraces_after_warmup"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_kvquant.json"))
    ap.add_argument("--pool-kib", type=int, default=512,
                    help="shared pool BYTE budget per engine (KiB)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--prompt", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=24,
                    help="decode-heavy by default: KV density pays "
                         "during GENERATION, so the overload workload "
                         "spends its steps decoding, not prefilling")
    ap.add_argument("--requests", type=int, default=48,
                    help="overload workload size (density leg)")
    ap.add_argument("--eval-requests", type=int, default=8)
    ap.add_argument("--eval-tokens", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--chunk-per-slot", type=int, default=4,
                    help="per-slot prompt-token budget per step (the "
                         "engine budget is chunk_per_slot * slots, "
                         "floored at --chunk)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--drift-bound", type=float, default=1.0,
                    help="max |logit drift| allowed at the final "
                         "position of any eval context")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes: CI end-to-end check")
    args = ap.parse_args()
    if os.environ.get("BENCH_SMOKE") == "1":
        args.smoke = True
    if args.smoke:
        args.pool_kib, args.seq, args.prompt = 160, 40, 10
        args.new_tokens, args.requests = 6, 8
        args.eval_requests, args.eval_tokens = 3, 3
        args.hidden, args.vocab, args.page_size = 64, 128, 8
        args.chunk = 8

    import jax

    model = _build_model(args)

    density, density_outs = _density_leg(model, args)
    prompts, refs = _reference_generations(model, args)
    match, total, mismatches = _teacher_forced_match(
        model, args, prompts, refs)
    drift, probe_ok = _logit_probe(model, args, prompts, refs)
    parity_off = _parity_off_leg(model, args)

    slot_ratio = density["int8"]["slots"] / density["off"]["slots"]
    tps_ratio = density["int8"]["tokens_per_s"] / \
        density["off"]["tokens_per_s"]
    match_rate = match / max(total, 1)
    summary = {
        "slot_density_ratio": round(slot_ratio, 3),
        "tokens_per_s_ratio": round(tps_ratio, 3),
        "bytes_per_token_ratio": round(
            density["int8"]["bytes_per_token"]
            / density["off"]["bytes_per_token"], 4),
        "token_match_rate": round(match_rate, 6),
        "token_match": [match, total],
        "max_logit_drift": round(drift, 6),
        "drift_bound": args.drift_bound,
        "probe_self_check": bool(probe_ok),
        "parity_off_bit_exact": bool(parity_off["bit_exact"]),
        "zero_new_executables_off": bool(
            parity_off["zero_new_executables"]),
        "zero_warm_retraces": all(
            leg["retraces_after_warmup"] == 0
            for leg in density.values())
        and parity_off["retraces_after_warmup"] == 0,
        # the acceptance gates (ISSUE 12): asserted at FULL scale,
        # recorded (and smoke-asserted where shape-independent) in CI
        "gate_slot_density": slot_ratio >= 1.8,
        "gate_throughput": tps_ratio >= 1.4,
        "gate_token_match": match_rate >= 0.99,
        "gate_logit_drift": drift <= args.drift_bound,
    }
    out = {
        "bench": "quantized KV serving: int8 pages + fused dequant vs "
                 "fp32 at fixed pool bytes; teacher-forced quality "
                 "gate; off-mode parity",
        "device": str(jax.devices()[0].device_kind)
        if jax.devices() else "unknown",
        "smoke": bool(args.smoke),
        "config": vars(args).copy(),
        "legs": {
            "density": density,
            "quality": {
                "match": match, "total": total,
                "match_rate": round(match_rate, 6),
                "mismatches_sample": mismatches,
                "max_logit_drift": round(drift, 6),
                "probe_self_check": bool(probe_ok),
            },
            "parity_off": parity_off,
        },
        "summary": summary,
        "parity": bool(parity_off["bit_exact"]),
    }
    out["config"].pop("out", None)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}: slots x{summary['slot_density_ratio']} "
          f"tokens/s x{summary['tokens_per_s_ratio']} "
          f"match {summary['token_match_rate']:.4f} "
          f"drift {summary['max_logit_drift']:.4f} "
          f"off-parity {summary['parity_off_bit_exact']}")
    gates = ["gate_token_match", "gate_logit_drift"] + \
        ([] if args.smoke else ["gate_slot_density", "gate_throughput"])
    failed = [g for g in gates if not summary[g]]
    if failed or not summary["parity_off_bit_exact"] or \
            not summary["zero_warm_retraces"] or not probe_ok:
        print(f"FAIL: {failed or 'parity/retrace/probe'}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
