"""Flash-blocks-inside-ring-attention, CI-covered via Pallas interpret
mode on the virtual CPU mesh (the real-kernel path runs on TPU; numerics
are identical by construction)."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh

RA = importlib.import_module("paddle_tpu.parallel.ring_attention")
FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture
def flash_ring_interpret(monkeypatch):
    orig = pl.pallas_call

    def patched(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setattr(pl, "pallas_call", patched)
    # force the flash path despite the CPU backend (tiling checks kept)
    monkeypatch.setattr(
        RA, "_use_flash_blocks",
        lambda q, s: q.shape[-2] % 512 == 0 and q.shape[-1] % 64 == 0
        and isinstance(s, (int, float)))
    yield


@pytest.mark.parametrize("causal", [False, True])
def test_flash_ring_matches_composed(flash_ring_interpret, causal):
    mesh = Mesh(np.array(jax.devices()[:2]), ("sp",))
    B, H, S, D = 1, 2, 1024, 64
    q, k, v, g = (jax.random.normal(jax.random.PRNGKey(i), (B, H, S, D),
                                    jnp.float32) for i in range(4))
    out, vjp = jax.vjp(
        lambda a, b, c: RA.ring_attention(a, b, c, mesh, axis_name="sp",
                                          causal=causal), q, k, v)
    ref, vjp_ref = jax.vjp(
        lambda a, b, c: FA._xla_reference(a, b, c, None, causal, None),
        q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)
    for got, want in zip(vjp(g), vjp_ref(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-2)


def test_flash_blocks_trace_inside_the_hybrid_step(monkeypatch):
    """`gpt_spmd` is a shard_map with ``check_vma=True``; with the flash
    blocks on (as on a TPU) the kernel's outputs must say over which mesh
    axes they vary and every branch of the ring's `cond` must agree — both
    failed at trace time the first time the step met the kernel (PR 21).
    Traced only (`eval_shape`): Pallas' interpret mode does not track
    varying axes, and the TPU compile of this step is in
    tests/test_tpu_compile.py.  dp=2 x sp=2 x mp=2, so the ring has a
    masked block too."""
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import gpt_spmd
    from paddle_tpu.models.gpt import GPTConfig

    monkeypatch.setattr(RA, "_use_flash_blocks", lambda q, s: True)
    cfg = GPTConfig(vocab_size=64, hidden_size=128, num_layers=1,
                    num_heads=2, max_seq_len=1024)
    step = gpt_spmd.build_spmd_train_step(
        cfg, build_mesh(dp=2, pp=1, sp=2, mp=2))
    params = jax.eval_shape(
        lambda: gpt_spmd.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 1024), jnp.int32)
    loss, new_params = jax.eval_shape(step, params, tokens, tokens)
    assert loss.shape == () and loss.dtype == jnp.float32
    assert {k: v.shape for k, v in new_params.items()} == \
        {k: v.shape for k, v in params.items()}
