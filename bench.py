"""Flagship benchmark: GPT decoder LM pretrain throughput (tokens/sec/chip).

Runs the framework's own fused train step (paddle_tpu.jit.TrainStep — one
donated XLA executable for forward+backward+optimizer, the TPU-native
replacement for the reference's per-op dygraph dispatch; see SURVEY.md §3.1)
on a GPT-base-class model in bf16 AMP.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
BASELINE.md: the reference publishes no numbers (vs_baseline fixed at 1.0);
the north-star metric is tokens/sec/chip (BASELINE.json config 2).

Needs a TPU: any failed phase fails the run (non-zero exit, no JSON line).
Env knobs: BENCH_SMOKE=1 shrinks the model for a control-flow run on the
CPU, whose numbers are not device numbers.
"""
from __future__ import annotations

import json
import os
import statistics
import time


def gpt_train_step(cfg):
    """The flagship train step, shared with chip_smoke.py: a GPT under bf16
    autocast with AdamW, forward + backward + update as one donated
    executable.  Returns ``(model, step)``; ``step(tokens, labels)``."""
    from paddle_tpu import amp, jit, nn, optimizer
    from paddle_tpu.models.gpt import GPT

    model = GPT(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                          weight_decay=0.01)

    def loss_fn(m, tokens, labels):
        with amp.auto_cast(enable=True, dtype="bfloat16"):
            logits = m(tokens)
        # bf16 logits straight into CE: the loss upcasts with f32
        # accumulation internally (Megatron-style vocab CE) instead of
        # materializing a [B,S,V] f32 logits tensor
        return nn.functional.cross_entropy(logits, labels,
                                           reduction="mean")

    return model, jit.train_step(model, loss_fn, opt)


def main():
    smoke = os.environ.get("BENCH_SMOKE") == "1"

    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import amp, jit, nn, optimizer
    from paddle_tpu.core.compile_cache import enable_compile_cache
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.observability.costmodel import device_peaks

    dev = jax.devices()[0]
    if not smoke and dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU and JAX found {dev.platform!r}; "
            f"BENCH_SMOKE=1 is the CPU control-flow run")
    enable_compile_cache()
    peak = device_peaks(dev)["flops_bf16"]

    paddle.seed(0)
    if smoke:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128,
                        use_parallel_layers=False)
        batch, seq, steps, warmup = 2, 128, 4, 2
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=1024,
                        use_parallel_layers=False)
        # batch 16 fills a 16 GB v5e chip: batch 20+ runs out of memory
        batch, seq, steps, warmup = 16, 1024, 20, 3

    model, step = gpt_train_step(cfg)

    rng = np.random.default_rng(0)
    tokens = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    for _ in range(warmup):
        loss = step(tokens, labels)
    # The final loss depends on every prior step through the donated param
    # chain, so one readback per window fences the whole window.
    float(np.asarray(loss._array))

    windows = []
    for _ in range(1 if smoke else 3):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(tokens, labels)
        float(np.asarray(loss._array))
        windows.append(time.perf_counter() - t0)

    tok_per_s = batch * seq * steps / statistics.median(windows)

    # Achieved model FLOP/s + MFU so rounds are comparable across chips.
    # Train step ≈ 6*N FLOPs/token (fwd+bwd weight matmuls) plus causal
    # attention 6*L*h*S (12*L*h*S halved for causality) — the PaLM-appendix
    # accounting.
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = 6 * n_params + 6 * cfg.num_layers * cfg.hidden_size * seq
    model_flops_per_s = tok_per_s * flops_per_token

    vision = {}
    gate = {}
    if not smoke:
        vision = _vision_benches(paddle, amp, jit, nn, optimizer, np, peak)
        # what a plain bf16 matmul chain reaches on this chip, in the same
        # run: the ceiling every MFU row should be read against
        vision["chip_effective_peak_tflops"] = round(
            _calibrate_effective_peak(np) / 1e12, 1)
        gate = _tpu_op_gate()
    print(json.dumps({
        "metric": "gpt_base_pretrain_tokens_per_sec_per_chip",
        "value": round(tok_per_s, 1),
        "unit": "tokens/s",
        "vs_baseline": 1.0,
        "model_flops_per_s": round(model_flops_per_s / 1e12, 3),
        "model_flops_unit": "Tflop/s",
        "mfu_vs_peak": round(model_flops_per_s / peak, 4),
        "peak_assumed": f"{dev.device_kind} bf16 {peak / 1e12:g} Tflop/s",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        **vision,
        **gate,
    }))


def _tpu_op_gate():
    """Run the TPU op suite and gate it against the committed
    matmul-normalized baseline (tools/op_bench_tpu_baseline.json) at
    2.0x: wide enough for run-to-run spread, tight enough to catch a
    kernel collapse (flash falling back to the composed path at S=2048
    is ~2.8-3.7x).  The result rides the JSON line."""
    import io
    import sys
    from contextlib import redirect_stdout

    repo = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(repo, "tools"))
    import op_bench
    from check_op_benchmark_result import compare_units

    results = []
    with redirect_stdout(io.StringIO()):
        for name, (fn, fargs) in op_bench.tpu_suite().items():
            results.append(op_bench.bench_one(name, fn, fargs, 8))
    matmul_us = next(r["mean_us"] for r in results if r["op"] == "matmul")
    for r in results:
        r["matmul_units"] = r["mean_us"] / matmul_us
    with open(os.path.join(repo, "tools",
                           "op_bench_tpu_baseline.json")) as f:
        base = json.load(f)
    failed, _lines = compare_units(base["results"], results, 2.0)
    flash = next(r["matmul_units"] for r in results
                 if r["op"] == "flash_attention")
    return {
        "op_gate_ok": not failed,
        "op_gate_failed": sorted(failed),
        "op_gate_flash_matmul_units": round(flash, 3),
    }


def _calibrate_effective_peak(np):
    """Median-of-3 8192^3 bf16 matmul chain, in FLOP/s: what the chip
    delivers to a plain XLA matmul in this run (docs/VISION_PERF.md)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = 8192
    a = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def mm(a, b):
        def body(i, c):
            return (c @ b) * 0.5 + a * 0.001
        return lax.fori_loop(0, 20, body, a)

    r = mm(a, a)
    float(np.asarray(r[0, 0]))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        r = mm(a, r)
        float(np.asarray(r[0, 0]))
        times.append(time.perf_counter() - t0)
    return 20 * 2 * n ** 3 / statistics.median(times)


def _vision_benches(paddle, amp, jit, nn, optimizer, np, peak):
    """BASELINE configs 1 and 5: ResNet50 and ViT-B/16 train-step imgs/s on
    one chip, ImageNet shapes, bf16 AMP.  Train-step model FLOPs ~= 3x
    forward (fwd + 2x bwd weight/input passes).  Per-image forward counts
    use TRUE FLOPs (2 per multiply-add) to match the GPT row's 6N/token
    convention: the papers' "4.1 / 17.6 GFLOPs" are multiply-add counts,
    so ResNet50 fwd = 8.2e9, ViT-B/16 fwd = 35.2e9 (docs/VISION_PERF.md)."""
    from paddle_tpu.vision.models import resnet50, vit_b_16

    out = {}
    for key, build, batch, flops_per_img in (
            ("resnet50_imgs_per_sec_per_chip",
             lambda: resnet50(num_classes=1000), 256, 3 * 8.2e9),
            ("vit_b16_imgs_per_sec_per_chip",
             lambda: vit_b_16(num_classes=1000), 128, 3 * 35.2e9)):
        paddle.seed(0)
        model = build()
        opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                 parameters=model.parameters())

        def loss_fn(m, x, y):
            with amp.auto_cast(enable=True, dtype="bfloat16"):
                logits = m(x)
            return nn.functional.cross_entropy(
                logits.astype("float32"), y, reduction="mean")

        step = jit.train_step(model, loss_fn, opt)
        rng = np.random.default_rng(0)
        x = paddle.to_tensor(
            rng.standard_normal((batch, 3, 224, 224)).astype(np.float32))
        y = paddle.to_tensor(
            rng.integers(0, 1000, (batch,)).astype(np.int64))
        steps = 10
        for _ in range(2):
            loss = step(x, y)
        float(np.asarray(loss._array))  # fence (see above)
        windows = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(x, y)
            float(np.asarray(loss._array))
            windows.append(time.perf_counter() - t0)
        imgs = batch * steps / statistics.median(windows)
        out[key] = round(imgs, 1)
        out[key.replace("imgs_per_sec_per_chip", "mfu_vs_peak")] = round(
            imgs * flops_per_img / peak, 4)
    return out


if __name__ == "__main__":
    main()
