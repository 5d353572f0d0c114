"""Per-op micro-benchmark harness.

Reference counterpart: `operators/benchmark/op_tester.cc` (config-driven
per-op latency) and `tests/unittests/benchmark.py`.  Emits one JSON
object per op to stdout (and optionally a file) so
`tools/check_op_benchmark_result.py` can gate regressions in CI.

Usage:
    python tools/op_bench.py [--out ops.json] [--iters 50] [--ops a,b,c]

Each benchmarked op runs as its own jitted executable on the default
device, fenced by a host readback of a value that depends on the result.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _fence(x):
    return float(np.asarray(jax.device_get(jnp.sum(x.astype(jnp.float32)))))


CHAIN = 32  # op executions per dispatch (amortizes dispatch latency)


def bench_one(name, fn, args, iters):
    """Time CHAIN chained executions inside ONE executable: each scan
    step feeds a sum-derived epsilon back into the first float operand,
    so XLA cannot hoist the op out of the loop, and the host's
    per-dispatch cost is amortized over CHAIN runs."""
    float_idx = next((i for i, a in enumerate(args)
                      if jnp.issubdtype(a.dtype, jnp.floating)), None)
    if float_idx is None:
        # without a float operand to perturb, fn(*carry) is
        # loop-invariant — XLA would hoist it and the chain would time
        # nothing.  Refuse rather than silently under-report.
        raise ValueError(
            f"bench_one({name}): needs at least one floating operand "
            "for the anti-hoist feedback")

    def chained(*a):
        def body(carry, _):
            out = fn(*carry)
            seed = jnp.sum(out.astype(jnp.float32)) * 1e-30
            new = list(carry)
            new[float_idx] = new[float_idx] + seed.astype(
                new[float_idx].dtype)
            return tuple(new), seed

        _, outs = jax.lax.scan(body, tuple(a), None, length=CHAIN)
        return outs

    jfn = jax.jit(chained)
    _fence(jfn(*args))  # compile
    t0 = time.perf_counter()
    acc = None
    for _ in range(iters):
        acc = jfn(*args)
    _fence(acc)
    dt = (time.perf_counter() - t0) / (iters * CHAIN)
    return {"op": name, "mean_us": round(dt * 1e6, 2), "iters": iters}


def default_suite():
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(1024, 1024).astype(np.float32))
    b = jnp.asarray(rng.randn(1024, 1024).astype(np.float32))
    img = jnp.asarray(rng.randn(8, 64, 56, 56).astype(np.float32))
    ker = jnp.asarray(rng.randn(64, 64, 3, 3).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, 1000, (64, 128)))
    emb = jnp.asarray(rng.randn(1000, 256).astype(np.float32))
    logits = jnp.asarray(rng.randn(256, 1000).astype(np.float32))

    from jax import lax

    dn = lax.conv_dimension_numbers(img.shape, ker.shape,
                                    ("NCHW", "OIHW", "NCHW"))
    return {
        "matmul": (lambda x, y: x @ y, (a, b)),
        "elementwise_add": (lambda x, y: x + y, (a, b)),
        "softmax": (lambda x: jax.nn.softmax(x, -1), (logits,)),
        "layer_norm": (
            lambda x: (x - x.mean(-1, keepdims=True))
            * jax.lax.rsqrt(x.var(-1, keepdims=True) + 1e-5), (a,)),
        "conv2d": (
            lambda x, k: lax.conv_general_dilated(
                x, k, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn),
            (img, ker)),
        "embedding": (lambda t, w: w[t], (ids, emb)),
        "reduce_sum": (lambda x: x.sum(), (a,)),
        "transpose": (lambda x: x.T.copy(), (a,)),
    }


def tpu_suite():
    """Ops worth gating ON TPU (round-4 VERDICT #8): the Pallas flash
    kernel plus the MXU/HBM staples.  Timings are stored normalized to
    the same-run big-matmul time ("matmul_units"), so the committed
    baseline compares as ratios and not as raw microseconds."""
    rng = np.random.RandomState(0)
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_fwd

    # sizes chosen so REAL kernel time (>= a few hundred us) dominates
    # the per-dispatch host cost; smaller shapes time the harness, not
    # the op
    a4 = jnp.asarray(rng.randn(4096, 4096).astype(np.float32),
                     jnp.bfloat16)
    img4 = jnp.asarray(rng.randn(16, 128, 56, 56).astype(np.float32),
                       jnp.bfloat16)
    ker4 = jnp.asarray(rng.randn(128, 128, 3, 3).astype(np.float32),
                       jnp.bfloat16)
    from jax import lax as _lax

    dn4 = _lax.conv_dimension_numbers(img4.shape, ker4.shape,
                                      ("NCHW", "OIHW", "NCHW"))
    q = jnp.asarray(rng.randn(4, 8, 2048, 64).astype(np.float32),
                    jnp.bfloat16)
    suite = {
        "matmul": (lambda x: x @ x, (a4,)),
        "elementwise_chain": (
            lambda x: jnp.tanh(x) * jax.nn.sigmoid(x) + x, (a4,)),
        "softmax": (lambda x: jax.nn.softmax(x, -1), (a4,)),
        "layer_norm": (
            lambda x: (x - x.mean(-1, keepdims=True))
            * jax.lax.rsqrt(x.var(-1, keepdims=True) + 1e-5), (a4,)),
        "conv2d": (
            lambda x, k: _lax.conv_general_dilated(
                x, k, (1, 1), [(1, 1), (1, 1)], dimension_numbers=dn4),
            (img4, ker4)),
        "reduce_sum": (lambda x: x.sum(), (a4,)),
        "flash_attention": (
            lambda qq: flash_attention_fwd(qq, qq, qq, None, True,
                                           None), (q,)),
    }
    return suite


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--ops", default=None,
                    help="comma-separated subset of the suite")
    ap.add_argument("--platform", default=None, choices=("cpu", "tpu"),
                    help="force a jax platform (the CI gate pins cpu so "
                         "numbers are comparable to the committed "
                         "baseline)")
    ap.add_argument("--tpu-suite", action="store_true",
                    help="bench the TPU gate suite (adds the Pallas "
                         "flash kernel) and record matmul-normalized "
                         "units alongside raw times")
    args = ap.parse_args()

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    suite = tpu_suite() if args.tpu_suite else default_suite()
    if args.ops:
        pick = set(args.ops.split(","))
        suite = {k: v for k, v in suite.items() if k in pick}
    results = []
    for name, (fn, fargs) in suite.items():
        r = bench_one(name, fn, fargs, args.iters)
        results.append(r)
        print(json.dumps(r))
    if args.tpu_suite:
        matmul_us = next((r["mean_us"] for r in results
                          if r["op"] == "matmul"), None)
        if matmul_us is None:
            ap.error("--tpu-suite normalization needs 'matmul' in the "
                     "run; do not filter it out with --ops")
        for r in results:
            r["matmul_units"] = round(r["mean_us"] / matmul_us, 3)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"device": str(jax.devices()[0]),
                       "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
