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

    # what a grid step holds of S=256 in the one backward kernel: the whole
    # head (dQ complete inside the step, as in the train cells); several
    # K blocks and several q blocks (dQ summed across grid steps that are
    # not consecutive, dK/dV across consecutive ones); uneven pairs
    BWD_BLOCKS = {"one_grid_step": (256, 256), "k_and_q_blocks": (128, 128),
                  "uneven": (128, 64), "k_blocks": (256, 64),
                  "q_blocks": (64, 256)}

    @pytest.mark.parametrize("blocks", BWD_BLOCKS.values(),
                             ids=BWD_BLOCKS.keys())
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_matches_xla(self, interpret_pallas, causal, dtype,
                                  blocks):
        q, k, v, g = self._inputs(1, dtype=dtype)
        f32 = dtype == jnp.float32
        out_p, vjp_p = jax.vjp(
            lambda a, b, c: FA._flash_diff(a, b, c, causal, None, *blocks),
            q, k, v)
        out_x, vjp_x = jax.vjp(
            lambda a, b, c: FA._composed_attention(a, b, c, None, causal,
                                                   None), q, k, v)
        np.testing.assert_allclose(
            self._f32(out_p), self._f32(out_x),
            atol=2e-3 if f32 else self.BF16_OUT_ATOL)
        for got, want in zip(vjp_p(g), vjp_x(g)):
            assert got.dtype == dtype
            np.testing.assert_allclose(
                self._f32(got), self._f32(want),
                atol=2e-2 if f32 else self.BF16_GRAD_ATOL)

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

    # ---- operands in their own dtype: bf16 products, f32 accumulation ----
    # Tolerances for bf16 operands against `_composed_attention` on the
    # SAME bf16 inputs (which rounds its probabilities to bf16 too): both
    # sides round their results to bf16, whose ulp is 2**-8 relative —
    # 0.016 at the outputs' magnitude (up to ~4 under the causal mask's
    # first rows), 0.03 at the gradients' (up to ~5).  Measured over three
    # seeds, every block pair below: out <= 0.016, gradients <= 0.031.
    BF16_OUT_ATOL = 2e-2
    BF16_GRAD_ATOL = 5e-2

    @staticmethod
    def _f32(x):
        return np.asarray(x.astype(jnp.float32))

    @pytest.mark.parametrize("forward", ["resident", "streaming"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_bf16_forward_matches_composed(self, interpret_pallas,
                                           monkeypatch, causal, forward):
        if forward == "streaming":
            monkeypatch.setattr(FA, "_RESIDENT_KV_BYTES", 0)
        q, k, v, _ = self._inputs(4, dtype=jnp.bfloat16)
        out, lse = FA._pallas_forward(q, k, v, causal, None, 128, 64)
        ref, ref_lse = FA._composed_attention(q, k, v, None, causal, None,
                                              want_lse=True)
        assert out.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
        np.testing.assert_allclose(self._f32(out), self._f32(ref),
                                   atol=self.BF16_OUT_ATOL)
        # the kernel's log-sum-exp comes from float32 logits; the
        # composed form rounds its logits to bf16 first (measured: 3e-3)
        np.testing.assert_allclose(np.asarray(lse).reshape(ref_lse.shape),
                                   np.asarray(ref_lse), atol=2e-2)

    @pytest.mark.parametrize("forward", ["resident", "streaming"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_bf16_backward_matches_composed(self, interpret_pallas,
                                            monkeypatch, causal, forward):
        if forward == "streaming":
            monkeypatch.setattr(FA, "_RESIDENT_KV_BYTES", 0)
        q, k, v, g = self._inputs(5, dtype=jnp.bfloat16)
        out_p, vjp_p = jax.vjp(
            lambda a, b, c: FA._flash_diff(a, b, c, causal, None, 128, 128),
            q, k, v)
        out_x, vjp_x = jax.vjp(
            lambda a, b, c: FA._composed_attention(a, b, c, None, causal,
                                                   None), q, k, v)
        np.testing.assert_allclose(self._f32(out_p), self._f32(out_x),
                                   atol=self.BF16_OUT_ATOL)
        for got, want in zip(vjp_p(g), vjp_x(g)):
            assert got.dtype == jnp.bfloat16
            np.testing.assert_allclose(self._f32(got), self._f32(want),
                                       atol=self.BF16_GRAD_ATOL)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("sub", [(512, 512), (96, 192), (64, 128),
                                     (128, 64), (64, 64)])
    @pytest.mark.parametrize("blocks", [(64, 128), (256, 128), (128, 256),
                                        (256, 256)])
    def test_causal_tiles_below_on_and_above_the_diagonal(
            self, interpret_pallas, monkeypatch, blocks, sub, dtype):
        # a grid step's tile is worked through in sub-tiles by loops in
        # the kernel, and one wholly above the diagonal is skipped.  Every
        # ratio of block to sub-tile and of rows to columns has to find
        # the live ones (S=256: up to 4 x 4 sub-tiles, inside one grid
        # step or across several), in both kernels; (512, 512) is the
        # shipped sub-tile, larger than these blocks, and (96, 192) divides
        # none of them: one sub-tile a grid step
        monkeypatch.setattr(FA, "_SUB_Q", sub[0])
        monkeypatch.setattr(FA, "_SUB_K", sub[1])
        q, k, v, g = self._inputs(6, dtype=dtype)
        f32 = dtype == jnp.float32
        want_o, vjp_x = jax.vjp(
            lambda a, b, c: FA._composed_attention(a, b, c, None, True,
                                                   None), q, k, v)
        for resident_bytes in (FA._RESIDENT_KV_BYTES, 0):
            monkeypatch.setattr(FA, "_RESIDENT_KV_BYTES", resident_bytes)
            got_o, vjp_p = jax.vjp(
                lambda a, b, c: FA._flash_diff(a, b, c, True, None,
                                               *blocks), q, k, v)
            np.testing.assert_allclose(
                self._f32(got_o), self._f32(want_o),
                atol=2e-3 if f32 else self.BF16_OUT_ATOL)
        for got, want in zip(vjp_p(g), vjp_x(g)):
            np.testing.assert_allclose(
                self._f32(got), self._f32(want),
                atol=2e-2 if f32 else self.BF16_GRAD_ATOL)

    @pytest.mark.parametrize("lengths", [(128, 256), (256, 128)])
    @pytest.mark.parametrize("sub", [(64, 128), (128, 64)])
    def test_full_attention_sub_tiles(self, interpret_pallas, monkeypatch,
                                      sub, lengths):
        # no mask: every sub-tile of every tile runs, cross-length too
        # (more keys than queries: one q block a head; more queries than
        # keys: dQ's rows span two q blocks)
        monkeypatch.setattr(FA, "_SUB_Q", sub[0])
        monkeypatch.setattr(FA, "_SUB_K", sub[1])
        sq, sk = lengths
        q, _, _, g = self._inputs(8, S=sq)
        _, k, v, _ = self._inputs(8, S=sk)
        out_p, vjp_p = jax.vjp(
            lambda a, b, c: FA._flash_diff(a, b, c, False, None, 128, sk),
            q, k, v)
        out_x, vjp_x = jax.vjp(
            lambda a, b, c: FA._composed_attention(a, b, c, None, False,
                                                   None), q, k, v)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                                   atol=2e-3)
        for got, want in zip(vjp_p(g), vjp_x(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=2e-2)

    @pytest.mark.parametrize("dtype, d, seq, blocks, stated", [
        (jnp.bfloat16, 64, 1024, (1024, 1024), False),   # the train cells
        (jnp.float32, 64, 1024, (1024, 1024), False),
        (jnp.bfloat16, 64, 4096, (1024, 1024), False),
        (jnp.bfloat16, 128, 2048, (1024, 1024), False),
        (jnp.bfloat16, 64, 8192, (1024, 1024), True),
        (jnp.bfloat16, 64, 2048, (2048, 2048), True),
        (jnp.float32, 64, 32768, (1024, 1024), True),
    ])
    def test_backward_states_a_vmem_limit_only_where_the_shapes_need_it(
            self, dtype, d, seq, blocks, stated):
        # dQ's block and accumulator span a head's rows: the backward
        # kernel's VMEM grows with the sequence, and only past what every
        # kernel gets does it state a limit of its own (that the chip's
        # compiler accepts what is stated, and needs it: test_tpu_compile)
        limit = FA._bwd_vmem_limit(seq, *blocks, d,
                                   jnp.dtype(dtype).itemsize)
        if not stated:
            assert limit is None
        else:
            # at least the head's dQ accumulator and output block, and
            # inside the chip's 128 MiB
            held = seq * d * (4 + jnp.dtype(dtype).itemsize)
            assert FA._SCOPED_VMEM_BYTES < limit < 128 * 2 ** 20
            assert limit > held

    @pytest.mark.parametrize("forward", ["resident", "streaming"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matrix_products_take_the_operands_dtype(self, monkeypatch,
                                                     dtype, forward):
        """Every `dot_general` of the traced kernel bodies (forward and
        backward) takes both operands in the caller's dtype — bf16 in, bf16
        products; float32 in, float32 products — and gives float32."""
        if forward == "streaming":
            monkeypatch.setattr(FA, "_RESIDENT_KV_BYTES", 0)
        q, k, v, g = self._inputs(7, dtype=dtype)

        def fwd_bwd(q, k, v, g):
            out, vjp = jax.vjp(
                lambda a, b, c: FA._flash_diff(a, b, c, True, None, 128,
                                               128), q, k, v)
            return out, vjp(g)

        kernels = {}
        for eqn in _walk(jax.make_jaxpr(fwd_bwd)(q, k, v, g).jaxpr):
            if eqn.primitive.name == "pallas_call":
                dots = [e for e in _walk(eqn.params["jaxpr"])
                        if e.primitive.name == "dot_general"]
                kernels[eqn.params["name"]] = dots
        # products a sub-tile: 2 forward; 5 backward (S and dP once, then
        # dV, dK and dQ from the same P and dS)
        assert {n: len(d) for n, d in kernels.items()} == {
            "flash_attention_fwd": 2, "flash_attention_bwd": 5}
        for name, dots in kernels.items():
            for e in dots:
                assert [x.aval.dtype for x in e.invars] == [dtype, dtype], \
                    (name, e)
                assert e.outvars[0].aval.dtype == jnp.float32, (name, e)


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else (val,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


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
