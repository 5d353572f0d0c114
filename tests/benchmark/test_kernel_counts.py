"""The operations and bytes the benchmark counts for a kernel call, against
counts worked out by hand, and the step's model-FLOP count against
`bench.py`'s 6N + 6LhS on GPT-base."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness, weights  # noqa: E402


@pytest.fixture(scope="module")
def flash():
    return harness.load_module("kernels", "flash_attention")


@pytest.fixture(scope="module")
def paged():
    return harness.load_module("kernels", "paged_attention")


@pytest.mark.parametrize("shape,fwd_flops,fwd_bytes,bwd_flops,bwd_bytes", [
    # b=1 h=1 sq=sk=4 d=8 causal: 4*5/2 = 10 live pairs.
    # forward 2 products x 2 flops x 10 pairs x 8 = 320; bytes bf16:
    # q,k,v,o = 4 x (4 x 8 x 2) = 256, log-sum-exp 4 x 4 = 16
    (dict(b=1, h=1, sq=4, sk=4, d=8, causal=True), 320.0, 272.0,
     # backward 5 products: 800; q,k,v,o,do,dq,dk,dv = 8 x 64 = 512, + 16
     800.0, 528.0),
    # the train shape of gpt2s-train: 16 x 12 heads, 1024 x 1024, d 64,
    # causal: 1024*1025/2 = 524800 pairs
    (dict(b=16, h=12, sq=1024, sk=1024, d=64, causal=True),
     4.0 * 192 * 524800 * 64, 192 * (2 * 64 * 4096 + 4096.0),
     10.0 * 192 * 524800 * 64, 192 * (2 * 64 * 8192 + 4096.0)),
])
def test_flash_attention_counts(flash, shape, fwd_flops, fwd_bytes,
                                bwd_flops, bwd_bytes):
    f, b = flash.forward(**shape), flash.backward(**shape)
    assert f == {"flops": fwd_flops, "bytes": fwd_bytes}
    assert b == {"flops": bwd_flops, "bytes": bwd_bytes}


def test_flash_attention_without_mask_counts_every_pair(flash):
    assert flash.forward(2, 3, 8, 16, 4, causal=False)["flops"] == \
        4.0 * 2 * 3 * 8 * 16 * 4


@pytest.mark.parametrize("ctx,h,d,flops,nbytes", [
    # one sequence of 3 cached tokens, 2 heads of 4, f32: QK^T and PV are
    # 2 x 2 x 3 x 4 flops a head = 96; K and V 2 x 2 x 4 x 4 B x 3 = 192,
    # q and o 2 x 2 x 4 x 4 = 64
    ([3], 2, 4, 96.0, 256.0),
    # 16 sequences of 300 tokens, 12 heads of 64, f32
    ([300] * 16, 12, 64, 4.0 * 12 * 64 * 4800,
     2.0 * 12 * 64 * 4 * 4800 + 2.0 * 16 * 12 * 64 * 4),
])
def test_paged_attention_counts(paged, ctx, h, d, flops, nbytes):
    assert paged.call(ctx, h, d) == {"flops": flops, "bytes": nbytes}


def test_paged_decode_is_bandwidth_bound_on_the_v5e(paged):
    peaks = harness.peaks_of("TPU v5 lite")
    need = paged.call([512] * 16, 12, 64)
    assert need["bytes"] / peaks["hbm_bytes_per_s"] > \
        need["flops"] / peaks["flops_bf16"]


def test_step_mfu_count_is_bench_py_on_gpt_base():
    """bench.py: flops_per_token = 6 * n_params + 6 * L * h * S, n_params
    every parameter of GPT(vocab 50304, 768, 12 layers, 1024 positions)."""
    with open(os.path.join(ROOT, "benchmarks/configs/gpt2-small.json")) as f:
        cfg = json.load(f)
    h, layers, vocab, pos = 768, 12, 50304, 1024
    per_block = (h * 3 * h + 3 * h) + (h * h + h) + (h * 4 * h + 4 * h) \
        + (4 * h * h + h) + 4 * h
    n_params = vocab * h + pos * h + layers * per_block + 2 * h
    assert weights.param_count(cfg) == n_params == 124_475_904
    count = harness.load_module("kernels", "gpt2_step")
    assert count.train_flops_per_token(n_params, layers, h, 1024) == \
        6 * n_params + 6 * layers * h * 1024
    # a served token is a third of a trained one at the same context
    assert count.serve_flops_per_token(n_params, layers, h, 512) == \
        2 * n_params + 4 * layers * h * 512


def test_an_unlisted_device_has_no_peaks():
    with pytest.raises(SystemExit):
        harness.peaks_of("cpu")
