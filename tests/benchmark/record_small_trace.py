"""How `small_trace.xplane.pb` (beside this file) was recorded, on one v5e
chip: three calls of a small jitted program with a host stall before the
third, under the benchmark's own spans.  `test_trace_reduce.py` reduces the
recording; run this again only to replace it.

    python tests/benchmark/record_small_trace.py <out.xplane.pb>
"""
import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out):
    import jax
    import jax.numpy as jnp

    from benchmarks import harness

    harness.require_chips(1)

    @jax.jit
    def work(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.5
        return x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    work(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        for i in range(3):
            if i == 2:
                with jax.profiler.TraceAnnotation("stall"):
                    time.sleep(0.02)
            with jax.profiler.TraceAnnotation("dispatch"):
                y = work(x)
            with jax.profiler.TraceAnnotation("fence"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
