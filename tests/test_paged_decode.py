"""Paged KV-cache decode: kernel parity, preallocated cache, serving
engine, and the memory-optim donation path.

The Pallas ragged paged-attention kernel runs under
`pallas_call(interpret=True)` against the XLA paged reference (the
OpTest numeric-parity pattern); the serving engine is pinned to
bit-parity with the legacy concat-growth eager decode path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import paged_attention as PA


@pytest.fixture
def interpret_pallas(monkeypatch):
    orig = pl.pallas_call

    def patched(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setattr(pl, "pallas_call", patched)
    yield


def _paged_inputs(seed, b=3, hq=4, hkv=2, d=32, page=16, pages_max=8,
                  lens=(37, 0, 128), dtype=np.float32):
    """Random page pools + a shuffled block table (the indirection must
    actually be exercised, so page ids are a permutation, not arange)."""
    rng = np.random.RandomState(seed)
    npages = b * pages_max + 3
    k_pages = jnp.asarray(rng.randn(hkv, npages, page, d).astype(dtype))
    v_pages = jnp.asarray(rng.randn(hkv, npages, page, d).astype(dtype))
    bt = jnp.asarray(
        rng.permutation(npages)[:b * pages_max].reshape(b, pages_max)
        .astype(np.int32))
    q = jnp.asarray(rng.randn(b, hq, d).astype(dtype))
    return q, k_pages, v_pages, bt, jnp.asarray(np.asarray(lens, np.int32))


class TestPagedAttentionKernel:
    def test_ragged_matches_reference_f32(self, interpret_pallas):
        q, kp, vp, bt, lens = _paged_inputs(0)
        out = PA._pallas_paged_attention(q, kp, vp, bt, lens)
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_ragged_matches_reference_bf16(self, interpret_pallas):
        q, kp, vp, bt, lens = _paged_inputs(1, dtype=np.float32)
        q, kp, vp = (a.astype(jnp.bfloat16) for a in (q, kp, vp))
        out = PA._pallas_paged_attention(q, kp, vp, bt, lens)
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=3e-2)

    def test_gqa_grouping(self, interpret_pallas):
        # 8 query heads over 2 kv heads: each group of 4 must read its
        # own kv head
        q, kp, vp, bt, lens = _paged_inputs(2, hq=8, hkv=2,
                                            lens=(40, 17, 96))
        out = PA._pallas_paged_attention(q, kp, vp, bt, lens)
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_zero_length_slot_outputs_zeros(self, interpret_pallas):
        q, kp, vp, bt, lens = _paged_inputs(3, lens=(16, 0, 48))
        out = PA._pallas_paged_attention(q, kp, vp, bt, lens)
        assert float(jnp.abs(out[1]).max()) == 0.0

    def test_reference_matches_dense_sdpa(self):
        """The XLA paged reference must equal dense attention over each
        sequence's first `len` tokens — the numerics contract the paged
        engine's bit-parity with the eager path rests on."""
        from paddle_tpu.nn.functional.attention import _sdpa_reference

        q, kp, vp, bt, lens = _paged_inputs(4, hq=2, hkv=2,
                                            lens=(37, 1, 128))
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens)
        b, hq, d = q.shape
        page = kp.shape[2]
        for i in range(b):
            ln = int(lens[i])
            if ln == 0:
                continue
            # densify sequence i from its pages
            k = kp[:, bt[i]].reshape(hq, -1, d)[:, :ln]
            v = vp[:, bt[i]].reshape(hq, -1, d)[:, :ln]
            dense = _sdpa_reference(q[i][None, :, None, :], k[None],
                                    v[None], None, 0.0, None, False)
            np.testing.assert_allclose(
                np.asarray(dense[0, :, 0]), np.asarray(ref[i]),
                atol=1e-5, err_msg=f"seq {i} len {ln}")

    def test_entry_point_validates_shapes(self):
        q, kp, vp, bt, lens = _paged_inputs(5)
        with pytest.raises(ValueError, match="multiple of kv heads"):
            PA.paged_attention(q[:, :3], kp, vp, bt, lens)
        with pytest.raises(ValueError, match="head_dim"):
            PA.paged_attention(q[..., :16], kp, vp, bt, lens)

    def test_cpu_routes_to_reference(self):
        # no TPU in CI: the public entry must take the XLA path and agree
        q, kp, vp, bt, lens = _paged_inputs(6)
        out = PA.paged_attention(q, kp, vp, bt, lens)
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("qn", [1, 64])
    @pytest.mark.parametrize("hq, hkv, hb", [
        (12, 12, 12), (12, 12, 6), (12, 12, 4), (12, 12, 3), (12, 12, 1),
        (8, 2, 2)])
    def test_heads_per_block_parity(self, interpret_pallas, monkeypatch,
                                    hq, hkv, hb, qn):
        """A grid step takes ``hb`` K/V heads of a page; the budget picks
        ``hb``.  Every blocking gives the reference's answer, and the
        same bits as one head a step: a zero-length slot, one ending
        mid-page, one on a page boundary; one query row a slot (decode)
        or a 64-row chunk with causal offsets."""
        d, page = 32, 16
        q, kp, vp, bt, lens = _paged_inputs(
            10 + hb, b=4, hq=hq, hkv=hkv, d=d, page=page,
            lens=(0, 37, 128, 100))
        rng = np.random.RandomState(hb)
        q = jnp.asarray(rng.randn(4, qn, hq, d).astype(np.float32))
        offs = jnp.maximum(lens - qn, 0)
        rows = -(-qn * (hq // hkv) // 8) * 8

        def run(heads):
            monkeypatch.setattr(PA, "_PAGED_VMEM_BUDGET", heads *
                                PA._head_vmem_bytes(rows, page, d,
                                                    jnp.float32, jnp.float32))
            assert PA.heads_per_block(hkv, rows, page, d, jnp.float32,
                                      jnp.float32) == heads
            return np.asarray(PA._pallas_paged_attention(
                q, kp, vp, bt, lens, q_offsets=offs))

        out = run(hb)
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens, q_offsets=offs)
        np.testing.assert_allclose(out, np.asarray(ref), atol=2e-5)
        assert float(np.abs(out[0]).max()) == 0.0
        np.testing.assert_array_equal(out, run(1))

    def test_heads_per_block_rule(self, monkeypatch):
        f32 = jnp.float32
        # the serve cell: 12 heads of 64, pages of 64, decode (8 rows
        # after padding) and mixed (64 rows) calls: one block of 12
        assert PA.heads_per_block(12, 8, 64, 64, f32, f32) == 12
        assert PA.heads_per_block(12, 64, 64, 64, f32, f32) == 12
        # large-group GQA: 8 query heads a K/V head, 64 query tokens,
        # heads of 128 — 512 rows a head, so fewer heads fit a step
        hb = PA.heads_per_block(8, 512, 64, 128, f32, f32)
        assert hb < 8 and 8 % hb == 0
        # a budget no head fits still takes one head a step
        monkeypatch.setattr(PA, "_PAGED_VMEM_BUDGET", 1)
        assert PA.heads_per_block(12, 64, 64, 64, f32, f32) == 1


class TestPageSizeMachinery:
    def test_pick_page_size_shrinks_to_tile(self):
        assert PA.pick_page_size(1024, 64) == 64
        assert PA.pick_page_size(1056, 64) == 32   # 1056 = 32 * 33
        assert PA.pick_page_size(48, 64) == 16
        assert PA.pick_page_size(17, 64) is None   # nothing tiles 17

    def test_cached_page_size_validates_entries(self, monkeypatch):
        # stale/malformed entries degrade to None, never crash — the
        # cached_blocks validation discipline applied to the page axis
        monkeypatch.setattr(FA, "_AUTOTUNE_LOADED", True)
        key = PA._paged_key(1024, 64, jnp.float32)
        monkeypatch.setitem(FA._AUTOTUNE, key, 64)
        assert PA.cached_page_size(1024, 64, jnp.float32) == 64
        monkeypatch.setitem(FA._AUTOTUNE, key, 48)   # doesn't tile 1024
        assert PA.cached_page_size(1024, 64, jnp.float32) is None
        monkeypatch.setitem(FA._AUTOTUNE, key, 4)    # below page floor
        assert PA.cached_page_size(1024, 64, jnp.float32) is None
        monkeypatch.setitem(FA._AUTOTUNE, key, "garbage")
        assert PA.cached_page_size(1024, 64, jnp.float32) is None
        assert PA.default_page_size(1024, 64) == PA.pick_page_size(1024)


class TestPreallocCache:
    def test_mha_prealloc_matches_concat_decode(self):
        paddle.seed(1)
        mha = nn.MultiHeadAttention(32, 4)
        mha.eval()
        rng = np.random.RandomState(0)
        x0 = paddle.to_tensor(rng.randn(2, 1, 32).astype(np.float32))
        cc = mha.gen_cache(x0)
        pc = mha.gen_cache(x0, max_length=8)
        for _ in range(5):
            xs = paddle.to_tensor(rng.randn(2, 1, 32).astype(np.float32))
            o1, cc = mha(xs, xs, xs, None, cc)
            o2, pc = mha(xs, xs, xs, None, pc)
            np.testing.assert_allclose(np.asarray(o1.numpy()),
                                       np.asarray(o2.numpy()), atol=1e-5)
        assert int(pc.length.numpy()) == 5
        assert pc.k.shape == [2, 4, 8, 8]  # buffer never reallocated

    def test_prealloc_chunk_is_dropin_for_concat(self):
        """Multi-token appends follow the legacy Cache contract: the
        buffer-validity mask hides only unwritten rows; within-chunk
        causality stays the caller's attn_mask's business."""
        paddle.seed(2)
        mha = nn.MultiHeadAttention(32, 4)
        mha.eval()
        rng = np.random.RandomState(1)
        chunk = paddle.to_tensor(rng.randn(2, 4, 32).astype(np.float32))
        # no mask: bidirectional within the chunk, like the concat path
        pc = mha.gen_cache(chunk, max_length=16)
        o_pre, pc = mha(chunk, chunk, chunk, None, pc)
        cc = mha.gen_cache(chunk)
        o_cat, cc = mha(chunk, chunk, chunk, None, cc)
        np.testing.assert_allclose(np.asarray(o_pre.numpy()),
                                   np.asarray(o_cat.numpy()), atol=1e-5)
        # caller-supplied causal mask: both paths honor it identically
        mask16 = np.zeros((2, 1, 4, 16), dtype=bool)
        mask16[:, :, :, :4] = np.tril(np.ones((4, 4), dtype=bool))
        pc2 = mha.gen_cache(chunk, max_length=16)
        o_pre2, pc2 = mha(chunk, chunk, chunk,
                          paddle.to_tensor(mask16), pc2)
        mask4 = np.tril(np.ones((4, 4), dtype=bool))[None, None]
        o_ref2 = mha(chunk, chunk, chunk, paddle.to_tensor(mask4))
        np.testing.assert_allclose(np.asarray(o_pre2.numpy()),
                                   np.asarray(o_ref2.numpy()), atol=1e-5)

    def test_prealloc_overflow_raises(self):
        """Writing past max_length must fail loudly: the clamped
        dynamic_update_slice + all-valid mask would otherwise silently
        corrupt attention output."""
        paddle.seed(5)
        mha = nn.MultiHeadAttention(32, 4)
        mha.eval()
        rng = np.random.RandomState(4)
        x = paddle.to_tensor(rng.randn(1, 1, 32).astype(np.float32))
        pc = mha.gen_cache(x, max_length=3)
        for _ in range(3):
            _, pc = mha(x, x, x, None, pc)
        with pytest.raises(ValueError, match="overflow"):
            mha(x, x, x, None, pc)

    def test_prealloc_steps_hit_dispatch_cache(self):
        """The point of preallocation: steps 2..N reuse the executables
        step 1 compiled (stable shapes), where the concat cache misses
        every step."""
        from paddle_tpu.core import dispatch as D

        paddle.seed(3)
        mha = nn.MultiHeadAttention(32, 4)
        mha.eval()
        rng = np.random.RandomState(2)
        x0 = paddle.to_tensor(rng.randn(1, 1, 32).astype(np.float32))
        pc = mha.gen_cache(x0, max_length=8)
        # two warm steps: the first writes at the freshly-allocated
        # zeros length, the second at an add-produced length — the two
        # signatures differ once, then everything is steady state
        o, pc = mha(x0, x0, x0, None, pc)
        o, pc = mha(x0, x0, x0, None, pc)
        D.reset_dispatch_stats()
        for _ in range(4):
            xs = paddle.to_tensor(rng.randn(1, 1, 32).astype(np.float32))
            o, pc = mha(xs, xs, xs, None, pc)
        stats = D.dispatch_stats()
        assert sum(s["misses"] for s in stats.values()) == 0, stats
        # and nothing BYPASSES either: the cache-write/mask op fns must
        # be fingerprintable (a function-local `import jax` would put a
        # module in a closure cell and silently bypass every call)
        assert sum(s["bypasses"] for s in stats.values()) == 0, stats


class _AttnCell(nn.Layer):
    """Beam-search cell over a cached MultiHeadAttention step."""

    def __init__(self, vocab, d):
        super().__init__()
        self.emb = nn.Embedding(vocab, d)
        self.attn = nn.MultiHeadAttention(d, 2)
        self.proj = nn.Linear(d, vocab)

    def forward(self, tokens, states):
        x = self.emb(tokens)
        x = Tensor(x._array[:, None, :])
        out, new_cache = self.attn(x, x, x, None, states)
        return self.proj(Tensor(out._array[:, 0])), new_cache


class TestBeamSearchPrealloc:
    def test_dynamic_decode_prealloc_matches_concat(self):
        vocab, d, w, b = 8, 16, 2, 2
        paddle.seed(3)
        cell = _AttnCell(vocab, d)
        cell.eval()
        dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=7,
                                   beam_size=w)
        pre = cell.attn.gen_cache(paddle.zeros([b, 1, d]), max_length=8)
        seqs_p, scores_p = nn.dynamic_decode(dec, pre, max_step_num=6,
                                             batch_size=b)
        legacy = cell.attn.gen_cache(paddle.zeros([b, 1, d]))
        seqs_c, scores_c = nn.dynamic_decode(dec, legacy, max_step_num=6,
                                             batch_size=b)
        np.testing.assert_array_equal(np.asarray(seqs_p.numpy()),
                                      np.asarray(seqs_c.numpy()))
        np.testing.assert_allclose(np.asarray(scores_p.numpy()),
                                   np.asarray(scores_c.numpy()),
                                   atol=1e-5)

    def test_prealloc_buffers_stay_fixed_size(self):
        vocab, d, w, b = 8, 16, 2, 1
        paddle.seed(4)
        cell = _AttnCell(vocab, d)
        cell.eval()
        dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=7,
                                   beam_size=w)
        pre = cell.attn.gen_cache(paddle.zeros([b, 1, d]), max_length=8)
        tokens, log_probs, finished, states = dec.initialize(pre, b)
        assert states.k.shape[0] == b * w  # tiled across beams
        for _ in range(3):
            tokens, log_probs, finished, states, _ = dec.step(
                tokens, log_probs, finished, states, b)
            assert states.k.shape == [b * w, 2, 8, 8]  # never grows


TINY = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 max_seq_len=128, use_parallel_layers=False, dropout=0.0)


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    m = GPT(TINY)
    m.eval()
    return m


class TestGPTDecodeParity:
    def test_generate_prealloc_matches_concat(self):
        m = _tiny_gpt()
        rng = np.random.RandomState(0)
        ids = paddle.to_tensor(rng.randint(0, 64, (2, 7)).astype(np.int32))
        t_c = np.asarray(m.generate(ids, max_new_tokens=8,
                                    use_cache="concat").numpy())
        t_p = np.asarray(m.generate(ids, max_new_tokens=8,
                                    use_cache="prealloc").numpy())
        np.testing.assert_array_equal(t_c, t_p)

    def test_engine_matches_eager_generate(self):
        """End-to-end greedy bit-parity: legacy concat-cache GPT.generate
        vs the paged continuous-batching engine."""
        from paddle_tpu.inference.serving import DecodeEngine

        m = _tiny_gpt()
        rng = np.random.RandomState(1)
        prompt = rng.randint(0, 64, (1, 8)).astype(np.int32)
        ref = np.asarray(m.generate(paddle.to_tensor(prompt),
                                    max_new_tokens=8,
                                    use_cache="concat").numpy())[0]
        eng = DecodeEngine(m, max_batch_size=2, max_seq_len=64,
                           page_size=16)
        out = eng.generate([prompt[0]], max_new_tokens=8)[0]
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_generate_eos_stops(self):
        m = _tiny_gpt()
        rng = np.random.RandomState(2)
        ids = paddle.to_tensor(rng.randint(0, 64, (1, 5)).astype(np.int32))
        # force eos = the first greedy token: generation must stop at 1
        first = np.asarray(m.generate(ids, max_new_tokens=1).numpy())[0, 0]
        toks = m.generate(ids, max_new_tokens=8, eos_token_id=int(first))
        assert np.asarray(toks.numpy()).shape[1] == 1


class TestServingEngine:
    def test_continuous_batching_staggered(self):
        """More requests than slots, ragged prompt lengths: every request
        must reproduce its single-request greedy decode, pages must all
        return to the pool, and the decode step must not retrace after
        warmup."""
        from paddle_tpu.inference.serving import (DecodeEngine,
                                                  decode_stats,
                                                  reset_decode_stats)

        m = _tiny_gpt(seed=5)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 9, 13)]
        refs = [np.asarray(m.generate(paddle.to_tensor(p[None]),
                                      max_new_tokens=6,
                                      use_cache="concat").numpy())[0]
                for p in prompts]
        reset_decode_stats()
        eng = DecodeEngine(m, max_batch_size=2, max_seq_len=64,
                           page_size=16)
        outs = eng.generate(prompts, max_new_tokens=6)
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(o), r)
        st = decode_stats()
        assert st["retraces_after_warmup"] == 0
        assert st["decode_compiles"] == 1
        assert st["steps"] > 0 and st["tokens"] >= 18
        assert 0 < st["batch_occupancy"] <= 1
        assert 0 < st["kv_block_utilization"] <= 1
        assert st["avg_step_ms"] > 0
        # eviction returned or cache-parked every page; slots all free
        assert eng.pool.available_count == eng.pool.num_pages
        assert not eng._active.any()

    def test_non_tiling_horizon_rounds_page_table_up(self):
        """A max_seq_len that no page size tiles must still serve: the
        block table rounds up and ragged lengths mask the partial last
        page (auto page-size path included)."""
        from paddle_tpu.inference.serving import DecodeEngine

        m = _tiny_gpt(seed=4)
        rng = np.random.RandomState(5)
        p = rng.randint(0, 64, (7,)).astype(np.int32)
        ref = np.asarray(m.generate(paddle.to_tensor(p[None]),
                                    max_new_tokens=6,
                                    use_cache="concat").numpy())[0]
        eng = DecodeEngine(m, max_batch_size=1, max_seq_len=50,
                           page_size=16)
        assert eng._pages_per_seq == 4  # ceil(50/16)
        np.testing.assert_array_equal(
            np.asarray(eng.generate([p], max_new_tokens=6)[0]), ref)
        auto = DecodeEngine(m, max_batch_size=1, max_seq_len=50)
        np.testing.assert_array_equal(
            np.asarray(auto.generate([p], max_new_tokens=6)[0]), ref)

    def test_slot_and_page_reuse_across_waves(self):
        from paddle_tpu.inference.serving import DecodeEngine

        m = _tiny_gpt(seed=6)
        rng = np.random.RandomState(4)
        eng = DecodeEngine(m, max_batch_size=1, max_seq_len=32,
                           page_size=16)
        for wave in range(3):
            p = rng.randint(0, 64, (4,)).astype(np.int32)
            ref = np.asarray(m.generate(paddle.to_tensor(p[None]),
                                        max_new_tokens=4,
                                        use_cache="concat").numpy())[0]
            out = eng.generate([p], max_new_tokens=4)[0]
            np.testing.assert_array_equal(np.asarray(out), ref)
            assert eng.pool.available_count == eng.pool.num_pages

    def test_admission_guards(self):
        from paddle_tpu.inference.serving import DecodeEngine

        m = _tiny_gpt(seed=7)
        eng = DecodeEngine(m, max_batch_size=1, max_seq_len=32,
                           page_size=16)
        with pytest.raises(ValueError, match="exceeds max_seq_len"):
            eng.add_request(np.arange(30), max_new_tokens=8)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.add_request(np.arange(4), max_new_tokens=0)
        with pytest.raises(ValueError, match="empty prompt"):
            eng.add_request([], max_new_tokens=4)
        # a horizon past the wpe table would silently clamp positions in
        # the embedding gather — the constructor must refuse
        with pytest.raises(ValueError, match="position table"):
            DecodeEngine(m, max_batch_size=1,
                         max_seq_len=TINY.max_seq_len + 64, page_size=16)

    def test_generate_rejects_horizon_past_position_table(self):
        m = _tiny_gpt(seed=9)
        ids = paddle.to_tensor(np.zeros((1, 8), np.int32))
        with pytest.raises(ValueError, match="max_seq_len"):
            m.generate(ids, max_new_tokens=TINY.max_seq_len)

    def test_stochastic_sampling_seed_reproducible(self):
        """DecodeEngine(seed=) must pin the sampling stream regardless
        of how many requests earlier engines created (keys derive from
        per-engine counters, prefill/decode domains disjoint)."""
        from paddle_tpu.inference.serving import DecodeEngine

        m = _tiny_gpt(seed=8)
        rng = np.random.RandomState(6)
        p = rng.randint(0, 64, (6,)).astype(np.int32)
        outs = []
        for _ in range(2):
            eng = DecodeEngine(m, max_batch_size=1, max_seq_len=32,
                               page_size=16, sampler="top_k", top_k=8,
                               temperature=0.9, seed=11)
            # churn the global Request counter between the two runs
            eng.add_request(p, max_new_tokens=1)
            eng.run()
            outs.append(eng.generate([p], max_new_tokens=6)[0])
        assert outs[0] == outs[1]
        assert len(outs[0]) == 6

    def test_sampling_top_k_top_p(self):
        from paddle_tpu.inference.serving import sample_logits

        logits = jnp.asarray(
            np.array([[0.0, 5.0, 1.0, -2.0]], np.float32))
        assert int(sample_logits(logits)[0]) == 1
        key = jax.random.PRNGKey(0)
        t1 = sample_logits(logits, sampler="top_k", top_k=1, key=key)
        assert int(t1[0]) == 1  # k=1 degenerates to greedy
        tp = sample_logits(logits, sampler="top_p", top_p=1e-6, key=key)
        assert int(tp[0]) == 1  # nucleus of one keeps the argmax
        # deterministic under a fixed key
        a = sample_logits(logits, sampler="top_k", top_k=3, key=key)
        b = sample_logits(logits, sampler="top_p", top_p=0.9, key=key)
        assert a.shape == (1,) and b.shape == (1,)
        with pytest.raises(ValueError, match="needs a PRNG key"):
            sample_logits(logits, sampler="top_k", top_k=2)


class TestMemoryOptimStableHLO:
    def test_predictor_donates_stablehlo_feeds(self, tmp_path):
        """enable_memory_optim on a StableHLO (jit.save) artifact: the
        jitted runner donates feed buffers; outputs identical and
        repeated runs work (fresh device buffers per run)."""
        from paddle_tpu import inference, jit

        paddle.seed(8)
        layer = nn.Linear(8, 4)
        layer.eval()
        x = np.random.RandomState(5).randn(3, 8).astype(np.float32)
        prefix = str(tmp_path / "m_hlo")
        jit.save(layer, prefix, input_spec=[paddle.to_tensor(x)])

        base = inference.create_predictor(
            inference.Config(prefix)).run([x])[0]
        cfg = inference.Config(prefix)
        cfg.enable_memory_optim(True)
        pred = inference.create_predictor(cfg)
        np.testing.assert_allclose(pred.run([x])[0], base, rtol=1e-6)
        np.testing.assert_allclose(pred.run([x])[0], base, rtol=1e-6)
        # clone shares the donated runner without re-wrapping
        np.testing.assert_allclose(pred.clone().run([x])[0], base,
                                   rtol=1e-6)


class TestSanitizedServe:
    """tier-1 sanitizer coverage (tests/conftest.py `sanitize` marker):
    the engine's steady-state serve holds every FLAGS_sanitize
    invariant — pool audit every step, one host sync per step, zero
    warm retraces, donated buffers tombstoned — while the tokens stay
    bit-identical to the concat-cache reference."""

    @pytest.mark.sanitize
    def test_staggered_serve_clean_under_sanitizer(self):
        from paddle_tpu.analysis import sanitizer
        from paddle_tpu.inference.serving import DecodeEngine

        m = _tiny_gpt(seed=5)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 9, 13)]
        refs = [np.asarray(m.generate(paddle.to_tensor(p[None]),
                                      max_new_tokens=6,
                                      use_cache="concat").numpy())[0]
                for p in prompts]
        sanitizer.reset()  # eager reference ran outside the engine
        eng = DecodeEngine(m, max_batch_size=2, max_seq_len=64,
                           page_size=16)
        outs = eng.generate(prompts, max_new_tokens=6)
        for o, r in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(o), r)
        rep = sanitizer.get().report()
        assert rep["steps"] > 0
        assert rep["warm_retraces"] == 0
        assert rep["host_syncs"] == rep["steps"]  # ONE sync per step
        assert rep["tombstoned_buffers"] > 0      # donation tracked
