#!/usr/bin/env python
"""int8 vs bf16 at MXU-SATURATING shapes (round-5 VERDICT #5).

The round-4 bench timed per call, so the measured 11.4 bf16 Tflop/s was
dispatch-bound (~12% of delivered peak) and said nothing about the MXU's
int8 story.  This version runs the
whole iteration chain INSIDE one jit (`lax.fori_loop`, the
bench_kernels.py pattern), so device time dominates:

* bf16 leg: chained 4096x4096 GEMMs at M=4096 — the delivered bf16
  peak of this part, measured in-run;
* int8 serving leg: s8xs8->s32 GEMM + scale + requantize per step
  (exactly what Int8Linear does between layers);
* int8 raw leg: s8xs8->s32 GEMM with a shift-truncate requant — the
  quant/dequant arithmetic removed, isolating where the serving leg
  loses.

Writes BENCH_int8.json with all three plus the probe deltas; analysis
in docs/INT8_PERF.md.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

M = 4096
D = 4096
CHAIN = 32


def timeit(fn, arg, reps=5):
    float(fn(arg))  # compile + warm (host fence via float())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        float(fn(arg))
        times.append((time.perf_counter() - t0) / CHAIN)
    return sorted(times)[len(times) // 2]


def main():
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(D, D).astype(np.float32) * 0.02,
                    jnp.bfloat16)
    qw = jnp.clip(jnp.round(w.astype(jnp.float32) / 0.02 * 127),
                  -127, 127).astype(jnp.int8)
    x = jnp.asarray(rng.randn(M, D).astype(np.float32), jnp.bfloat16)
    qx = jnp.clip(jnp.round(x.astype(jnp.float32) * 50), -127,
                  127).astype(jnp.int8)

    @jax.jit
    def bf16_chain(h):
        def body(_, hh):
            out = hh @ w
            # cheap renorm keeps values bounded without a reduction
            return (out * jnp.bfloat16(0.05)).astype(jnp.bfloat16)

        return jnp.sum(jax.lax.fori_loop(0, CHAIN, body, h)
                       .astype(jnp.float32))

    @jax.jit
    def int8_serving_chain(qh):
        scale = jnp.float32(0.02 * 0.05 / 127.0)

        def body(_, hh):
            acc = jax.lax.dot_general(
                hh, qw, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            f = acc.astype(jnp.float32) * scale
            return jnp.clip(jnp.round(f * 127.0), -127.0,
                            127.0).astype(jnp.int8)

        return jnp.sum(jax.lax.fori_loop(0, CHAIN, body, qh)
                       .astype(jnp.int32))

    @jax.jit
    def int8_raw_chain(qh):
        def body(_, hh):
            acc = jax.lax.dot_general(
                hh, qw, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            # shift-truncate stand-in for requant: keeps the data
            # dependency, removes the float round/clip arithmetic
            return jax.lax.shift_right_arithmetic(
                acc, 8).astype(jnp.int8)

        return jnp.sum(jax.lax.fori_loop(0, CHAIN, body, qh)
                       .astype(jnp.int32))

    # issue-rate probe with the VALIDATED anti-hoist pattern
    # (tools/op_bench.py bench_one: a sum-derived epsilon perturbs the
    # carried input, so the operand layout stays put and XLA pipelines
    # the MXU — this is the pattern that reaches ~80% of nominal peak
    # on this part, where a result-carried serial chain plateaus ~4x
    # lower for BOTH dtypes)
    @jax.jit
    def bf16_issue(xx):
        def body(carry, _):
            (h,) = carry
            out = h @ w
            seed = jnp.sum(out.astype(jnp.float32)) * 1e-30
            return (h + seed.astype(h.dtype),), seed

        _, outs = jax.lax.scan(body, (xx,), None, length=CHAIN)
        return jnp.sum(outs)

    @jax.jit
    def int8_issue(xx):
        def body(carry, _):
            (h,) = carry
            out = jax.lax.dot_general(
                h, qw, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            seed = (jnp.sum(out) & 1).astype(jnp.int8)
            return (h + seed,), seed.astype(jnp.float32)

        _, outs = jax.lax.scan(body, (xx,), None, length=CHAIN)
        return jnp.sum(outs)

    t_bf16 = timeit(bf16_chain, x)
    t_int8 = timeit(int8_serving_chain, qx)
    t_raw = timeit(int8_raw_chain, qx)
    t_bf16_issue = timeit(bf16_issue, x)
    t_int8_issue = timeit(int8_issue, qx)

    flops = 2 * M * D * D  # per chain step
    out = {
        "platform": jax.devices()[0].platform,
        "shape": f"M{M}xK{D}xN{D} chained x{CHAIN} in one jit",
        "bf16_ms": round(t_bf16 * 1e3, 4),
        "int8_serving_ms": round(t_int8 * 1e3, 4),
        "int8_raw_ms": round(t_raw * 1e3, 4),
        "bf16_tflops": round(flops / t_bf16 / 1e12, 2),
        "int8_serving_tops": round(flops / t_int8 / 1e12, 2),
        "int8_raw_tops": round(flops / t_raw / 1e12, 2),
        "int8_speedup_vs_bf16": round(t_bf16 / t_int8, 3),
        "int8_raw_speedup_vs_bf16": round(t_bf16 / t_raw, 3),
        "requant_overhead_ms": round((t_int8 - t_raw) * 1e3, 4),
        "bf16_issue_tflops": round(flops / t_bf16_issue / 1e12, 2),
        "int8_issue_tops": round(flops / t_int8_issue / 1e12, 2),
        "int8_issue_rate_vs_bf16": round(t_bf16_issue / t_int8_issue,
                                         3),
    }
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_int8.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
