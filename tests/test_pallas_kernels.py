"""Pallas kernel numerics tests (interpret mode on CPU).

The kernels are gated to real TPU backends at runtime; here they run under
`pallas_call(interpret=True)` against the XLA composed references —
the OpTest numeric-parity pattern applied to custom kernels.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import layer_norm as LN


@pytest.fixture
def interpret_pallas(monkeypatch):
    orig = pl.pallas_call

    def patched(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setattr(pl, "pallas_call", patched)
    yield


class TestFlashAttention:
    def _inputs(self, seed, B=1, H=2, S=256, D=64, dtype=jnp.float32):
        key = jax.random.PRNGKey(seed)
        return [jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D),
                                  dtype) for i in range(4)]

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_xla(self, interpret_pallas, causal):
        q, k, v, _ = self._inputs(0)
        out, lse = FA._pallas_forward(q, k, v, causal, None, 128, 128)
        ref = FA._xla_reference(q, k, v, None, causal, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3)
        assert lse.shape == (2, 256) and bool(jnp.all(jnp.isfinite(lse)))

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_matches_xla(self, interpret_pallas, causal):
        q, k, v, g = self._inputs(1)
        out_p, vjp_p = jax.vjp(
            lambda a, b, c: FA._flash_diff(a, b, c, causal, None, 128, 128),
            q, k, v)
        out_x, vjp_x = jax.vjp(
            lambda a, b, c: FA._xla_reference(a, b, c, None, causal, None),
            q, k, v)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   atol=2e-3)
        for got, want in zip(vjp_p(g), vjp_x(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-2)

    @pytest.mark.parametrize("causal", [False, True])
    def test_streaming_forward_matches_xla(self, interpret_pallas,
                                           monkeypatch, causal):
        # force the constant-VMEM streaming kernel (used when K/V exceed
        # the resident budget at very long sequences)
        monkeypatch.setattr(FA, "_RESIDENT_KV_BYTES", 0)
        q, k, v, _ = self._inputs(3)
        out, lse = FA._pallas_forward(q, k, v, causal, None, 128, 64)
        ref = FA._xla_reference(q, k, v, None, causal, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3)
        assert lse.shape == (2, 256) and bool(jnp.all(jnp.isfinite(lse)))

    def test_causal_cross_length_routes_to_xla(self, monkeypatch):
        # kernels mask top-left (q_pos >= k_pos); the reference masks
        # bottom-right (tril offset kl-ql) — they only agree at sq == sk,
        # so cross-length causal must never reach the Pallas path
        def boom(*a, **k):
            raise AssertionError("Pallas path taken for cross-length causal")

        monkeypatch.setattr(FA, "_flash_diff", boom)
        monkeypatch.setattr(FA.jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(FA, "pallas_attention_wanted",
                            lambda s, c=True: True)
        q = jnp.zeros((1, 2, 128, 64))
        k = jnp.zeros((1, 2, 256, 64))
        out = FA.flash_attention_fwd(q, k, k, is_causal=True)
        assert out.shape == (1, 2, 128, 64)

    def test_noncausal_threshold_stays_1024(self):
        assert FA._auto_threshold(is_causal=True) == 512
        assert FA._auto_threshold(is_causal=False) == 1024

    def test_uneven_blocks_backward(self, interpret_pallas):
        # block_q != block_k exercises the causal loop-bound arithmetic
        q, k, v, g = self._inputs(2, S=256)
        out_p, vjp_p = jax.vjp(
            lambda a, b, c: FA._flash_diff(a, b, c, True, None, 128, 64),
            q, k, v)
        out_x, vjp_x = jax.vjp(
            lambda a, b, c: FA._xla_reference(a, b, c, None, True, None),
            q, k, v)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   atol=2e-3)
        for got, want in zip(vjp_p(g), vjp_x(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-2)


class TestFusedLayerNorm:
    def test_forward_matches_xla(self, interpret_pallas):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(8, 256).astype(np.float32))
        w = jnp.asarray(rng.rand(256).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(256).astype(np.float32))
        out_pl = LN._fwd_pallas(x, w, b, 1e-5)
        out_ref = LN._fwd_xla(x, w, b, 1e-5)
        np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                                   atol=1e-5)

    def test_odd_row_count_blocks(self, interpret_pallas):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(3, 128).astype(np.float32))  # rows !% 256
        w = jnp.ones((128,), jnp.float32)
        b = jnp.zeros((128,), jnp.float32)
        out_pl = LN._fwd_pallas(x, w, b, 1e-5)
        out_ref = LN._fwd_xla(x, w, b, 1e-5)
        np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_ref),
                                   atol=1e-5)

    def test_custom_vjp_matches_autodiff(self):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(6, 64).astype(np.float32))
        w = jnp.asarray(rng.rand(64).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(64).astype(np.float32))

        def f_fused(x, w, b):
            return (LN.fused_layer_norm(x, w, b, 1e-5) ** 2).sum()

        def f_ref(x, w, b):
            xh = (x - x.mean(-1, keepdims=True)) * jax.lax.rsqrt(
                x.var(-1, keepdims=True) + 1e-5)
            return ((xh * w + b) ** 2).sum()

        g1 = jax.grad(f_fused, argnums=(0, 1, 2))(x, w, b)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(x, w, b)
        for a, bb in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                       atol=1e-4)
