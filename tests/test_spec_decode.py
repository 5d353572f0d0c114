"""Speculative decoding: ragged multi-query paged attention, the
accept/resample rule, K/V rollback invariants, and sampling edge cases.

Contracts pinned here (ISSUE 3 acceptance):

* greedy speculative decode is BIT-IDENTICAL to the non-speculative
  engine (and therefore to eager ``GPT.generate``) on the tiny GPT
  fixture, for both drafters, including staggered continuous batching;
* stochastic emission follows the target model's distribution (the
  verify targets ARE `sample_logits` draws — checked at the rule level
  and end-to-end against the non-speculative engine's marginals);
* rejection is a pure ``seq_lens`` rollback: the page pool is clean
  after mixed accept/reject traffic, even under an adversarial
  always-wrong drafter;
* ``retraces_after_warmup == 0`` covers the draft and verify
  executables, not just the decode step.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.nn.decode import sample_logits
from paddle_tpu.nn.functional.attention import (_sdpa_reference,
                                                multi_query_causal_mask)
from paddle_tpu.ops.pallas import paged_attention as PA


@pytest.fixture
def interpret_pallas(monkeypatch):
    orig = pl.pallas_call

    def patched(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setattr(pl, "pallas_call", patched)
    yield


def _mq_inputs(seed, b=3, qn=4, hq=4, hkv=2, d=32, page=16, pages_max=8,
               lens=(37, 0, 100), offs=(33, 0, 98), dtype=np.float32):
    """Sequence 0: plain suffix queries; 1: inactive slot; 2: write-capped
    (seq_len < offset + Q: trailing K/V writes were suppressed)."""
    rng = np.random.RandomState(seed)
    npages = b * pages_max + 3
    kp = jnp.asarray(rng.randn(hkv, npages, page, d).astype(dtype))
    vp = jnp.asarray(rng.randn(hkv, npages, page, d).astype(dtype))
    bt = jnp.asarray(rng.permutation(npages)[:b * pages_max]
                     .reshape(b, pages_max).astype(np.int32))
    q = jnp.asarray(rng.randn(b, qn, hq, d).astype(dtype))
    return (q, kp, vp, bt, jnp.asarray(np.asarray(lens, np.int32)),
            jnp.asarray(np.asarray(offs, np.int32)))


class TestMultiQueryPagedAttention:
    def test_kernel_matches_reference(self, interpret_pallas):
        q, kp, vp, bt, lens, offs = _mq_inputs(0)
        out = PA._pallas_paged_attention(q, kp, vp, bt, lens,
                                         q_offsets=offs)
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens, q_offsets=offs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        assert float(jnp.abs(out[1]).max()) == 0.0  # inactive slot

    def test_kernel_matches_reference_gqa(self, interpret_pallas):
        # 8 query heads over 2 kv heads AND 3 query tokens: rows are
        # (token, group) pairs, each group must read its own kv head
        q, kp, vp, bt, lens, offs = _mq_inputs(1, qn=3, hq=8, hkv=2,
                                               lens=(40, 17, 96),
                                               offs=(37, 14, 93))
        out = PA._pallas_paged_attention(q, kp, vp, bt, lens,
                                         q_offsets=offs)
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens, q_offsets=offs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_reference_matches_dense_causal_sdpa(self):
        """The multi-query reference must equal dense bottom-right
        causal attention over each sequence prefix — the numerics
        contract spec-decode's greedy parity rests on."""
        q, kp, vp, bt, lens, offs = _mq_inputs(2, hq=2, hkv=2)
        ref = PA._xla_paged_attention(q, kp, vp, bt, lens, q_offsets=offs)
        b, qn, hq, d = q.shape
        for i in range(b):
            ln, off = int(lens[i]), int(offs[i])
            if ln == 0:
                continue
            k = kp[:, bt[i]].reshape(hq, -1, d)[:, :ln]
            v = vp[:, bt[i]].reshape(hq, -1, d)[:, :ln]
            mask = (np.arange(ln)[None, :]
                    < (off + np.arange(qn) + 1)[:, None])
            dense = _sdpa_reference(
                q[i].transpose(1, 0, 2)[None], k[None], v[None],
                jnp.asarray(mask[None, None]), 0.0, None, False)
            np.testing.assert_allclose(
                np.asarray(dense[0].transpose(1, 0, 2)),
                np.asarray(ref[i]), atol=1e-5, err_msg=f"seq {i}")

    def test_single_query_compat(self):
        """A rank-3 q must behave exactly like rank-4 with Q == 1 and
        the default offsets (seq_lens - 1) — the engine's decode step
        depends on this reduction."""
        q, kp, vp, bt, lens, _ = _mq_inputs(3, qn=1)
        flat = PA._xla_paged_attention(q[:, 0], kp, vp, bt, lens)
        mq = PA._xla_paged_attention(q, kp, vp, bt, lens,
                                     q_offsets=lens - 1)
        np.testing.assert_array_equal(np.asarray(flat),
                                      np.asarray(mq[:, 0]))

    def test_mask_helper_semantics(self):
        m = multi_query_causal_mask(
            jnp.asarray([2, 0], jnp.int32), 3,
            jnp.asarray([4, 0], jnp.int32), 6)
        # seq 0: limits min(4, 3/4/5) = 3,4,4 ; seq 1 inactive -> none
        expect0 = np.array([[1, 1, 1, 0, 0, 0],
                            [1, 1, 1, 1, 0, 0],
                            [1, 1, 1, 1, 0, 0]], bool)
        np.testing.assert_array_equal(np.asarray(m[0]), expect0)
        assert not np.asarray(m[1]).any()

    def test_entry_point_validates_rank(self):
        q, kp, vp, bt, lens, _ = _mq_inputs(4)
        with pytest.raises(ValueError, match="rank"):
            PA.paged_attention(q[:, :, :, None], kp, vp, bt, lens)


class TestSampleLogitsEdges:
    LOGITS = jnp.asarray(np.array([[0.5, 3.0, 1.0, -2.0],
                                   [2.0, -1.0, 0.0, 4.0]], np.float32))

    def test_top_p_too_small_keeps_argmax(self):
        key = jax.random.PRNGKey(0)
        for p in (0.0, 1e-30, -1.0):
            toks = sample_logits(self.LOGITS, sampler="top_p", top_p=p,
                                 key=key)
            np.testing.assert_array_equal(np.asarray(toks), [1, 3])

    def test_top_k_ge_vocab_is_noop(self):
        key = jax.random.PRNGKey(1)
        full = jax.random.categorical(
            key, self.LOGITS).astype(jnp.int32)
        for k in (4, 5, 1000):
            toks = sample_logits(self.LOGITS, sampler="top_k", top_k=k,
                                 key=key)
            np.testing.assert_array_equal(np.asarray(toks),
                                          np.asarray(full))

    def test_temperature_zero_is_greedy(self):
        # no key needed: T <= 0 must short-circuit to argmax, not
        # divide by epsilon and overflow
        for sampler, kw in (("top_k", {"top_k": 3}),
                            ("top_p", {"top_p": 0.9})):
            toks = sample_logits(self.LOGITS, sampler=sampler,
                                 temperature=0.0, **kw)
            np.testing.assert_array_equal(np.asarray(toks), [1, 3])

    def test_sampler_distribution_matches_softmax(self):
        """The verify step emits `sample_logits` draws verbatim — its
        distribution IS the spec-decode output distribution, so pin it:
        empirical marginals over many rows match softmax(logits/T)."""
        rng = np.random.RandomState(0)
        logits_row = rng.randn(8).astype(np.float32) * 1.5
        n = 4000
        tiled = jnp.asarray(np.tile(logits_row, (n, 1)))
        toks = np.asarray(sample_logits(
            tiled, sampler="top_k", top_k=8, temperature=0.7,
            key=jax.random.PRNGKey(2)))
        emp = np.bincount(toks, minlength=8) / n
        want = np.asarray(jax.nn.softmax(
            jnp.asarray(logits_row / 0.7)))
        assert 0.5 * np.abs(emp - want).sum() < 0.05, (emp, want)


TINY = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 max_seq_len=128, use_parallel_layers=False, dropout=0.0)


def _tiny_gpt(seed=0, cfg=TINY):
    paddle.seed(seed)
    m = GPT(cfg)
    m.eval()
    return m


def _engine(m, **kw):
    from paddle_tpu.inference.serving import DecodeEngine

    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 16)
    return DecodeEngine(m, **kw)


from paddle_tpu.inference.speculative import Drafter  # noqa: E402


class _AlwaysWrongDrafter(Drafter):
    """Adversarial drafter (exercises the Drafter extension API):
    proposes rotating off-by-one tokens — in practice acceptance ~0,
    forcing a full K-token rollback every round."""

    name = "always_wrong"

    def propose(self, write_caps):
        eng = self.engine
        out = np.zeros((eng._slots, self.k), np.int32)
        for s in range(eng._slots):
            out[s] = (int(eng._last[s]) + 1 + np.arange(self.k)) % 64
        return out


class TestGreedyParity:
    def test_prompt_lookup_matches_engine(self):
        """Greedy spec decode ≡ the PR 2 engine, bit for bit, under
        staggered continuous batching (more requests than slots), for
        several K."""
        m = _tiny_gpt(seed=5)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 9, 13)]
        refs = _engine(m).generate(prompts, max_new_tokens=10)
        for k in (2, 4):
            outs = _engine(m, spec_decode_k=k).generate(
                prompts, max_new_tokens=10)
            for o, r in zip(outs, refs):
                assert o == r, (k, o, r)

    def test_prompt_lookup_matches_eager_concat(self):
        """...and therefore ≡ eager GPT.generate(use_cache='concat'),
        closing the whole parity chain from PR 2."""
        m = _tiny_gpt(seed=0)
        rng = np.random.RandomState(1)
        p = rng.randint(0, 64, (1, 8)).astype(np.int32)
        ref = np.asarray(m.generate(paddle.to_tensor(p), max_new_tokens=8,
                                    use_cache="concat").numpy())[0]
        out = _engine(m, spec_decode_k=4).generate(
            [p[0]], max_new_tokens=8)[0]
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_draft_model_matches_engine(self):
        from paddle_tpu.inference.speculative import DraftModelDrafter

        m = _tiny_gpt(seed=5)
        paddle.seed(17)
        dm = GPT(TINY.draft_config())
        dm.eval()
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (6, 11)]
        refs = _engine(m).generate(prompts, max_new_tokens=9)
        outs = _engine(m, spec_decode_k=3,
                       drafter=DraftModelDrafter(dm)).generate(
            prompts, max_new_tokens=9)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)

    def test_always_wrong_drafter_still_exact(self):
        """Acceptance ~0 must degrade throughput, never tokens: every
        round rolls K tokens back and still emits the target's pick."""
        m = _tiny_gpt(seed=6)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 7, 10)]
        refs = _engine(m).generate(prompts, max_new_tokens=7)
        from paddle_tpu.inference.serving import (decode_stats,
                                                  reset_decode_stats)

        reset_decode_stats()
        eng = _engine(m, spec_decode_k=3, drafter=_AlwaysWrongDrafter())
        outs = eng.generate(prompts, max_new_tokens=7)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["acceptance_rate"] < 0.2, st["acceptance_rate"]
        assert st["mean_accepted_per_step"] < 1.5
        # rollback left the pool clean (prefix-cached pages stay parked)
        assert eng.pool.available_count == eng.pool.num_pages
        assert eng.pool.reserved == 0

    def test_zero_warm_retraces_for_draft_and_verify(self):
        from paddle_tpu.inference.serving import (decode_stats,
                                                  reset_decode_stats)
        from paddle_tpu.inference.speculative import DraftModelDrafter

        m = _tiny_gpt(seed=7)
        paddle.seed(23)
        dm = GPT(TINY.draft_config())
        dm.eval()
        rng = np.random.RandomState(6)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 9, 13, 6)]
        reset_decode_stats()
        eng = _engine(m, spec_decode_k=3, drafter=DraftModelDrafter(dm))
        eng.generate(prompts, max_new_tokens=8)
        st = decode_stats()
        assert st["retraces_after_warmup"] == 0, st
        assert st["verify_compiles"] == 1
        # draft catch-up + draft step + one prefill bucket per prompt
        # length bucket (16 here) — compiles happen, retraces never
        assert st["draft_compiles"] >= 3
        assert st["spec_steps"] > 0
        assert st["verify_time_s"] > 0 and st["draft_time_s"] > 0

    def test_eos_inside_verify_window_truncates(self):
        # the fixture is searched for, not assumed: a prompt whose
        # greedy chain emits a NEW token mid-stream (index >= 2, e.g.
        # [42, 42, 42, 59, ...]), so that eos first lands inside a
        # verify window, after accepted repeats, and the accepted tail
        # after it must be dropped.  Tiny random models mostly repeat
        # one token, and which prompts do not changes with the JAX
        # version — hence the search and the assertion on its result.
        m = _tiny_gpt(seed=8)
        plain = _engine(m)
        found = None
        for prompt_seed in range(16):
            p = np.random.RandomState(prompt_seed).randint(
                0, 64, (5,)).astype(np.int32)
            ref = plain.generate([p], max_new_tokens=8)[0]
            j = next((i for i in range(2, 8) if ref[i] not in ref[:i]),
                     None)
            if j is not None:
                found = (p, ref, j)
                break
        assert found is not None, (
            "no prompt seed in range(16) gives a greedy chain with a new "
            "token at index >= 2 under this JAX: widen the search")
        p, ref, j = found
        eos, want = ref[j], ref[:j + 1]
        eng = _engine(m, spec_decode_k=4, eos_token_id=int(eos))
        toks, reasons = eng.generate([p], max_new_tokens=8,
                                     return_meta=True)
        assert toks[0] == list(want), (toks, want)
        assert reasons == ["eos"]
        assert eng.pool.available_count == eng.pool.num_pages


class TestStochasticAcceptance:
    def test_spec_marginals_match_engine(self):
        """Distribution preservation end-to-end: under temperature
        sampling the speculative engine's second-token marginal matches
        the non-speculative engine's (every emitted token is a target-
        model draw; drafts only decide how many land per step)."""
        from paddle_tpu.inference.serving import DecodeEngine

        cfg = GPTConfig(vocab_size=16, hidden_size=16, num_layers=1,
                        num_heads=2, max_seq_len=64,
                        use_parallel_layers=False, dropout=0.0)
        m = _tiny_gpt(seed=3, cfg=cfg)
        p = np.asarray([3, 7, 3, 7], np.int32)
        kw = dict(max_batch_size=1, max_seq_len=32, page_size=16,
                  sampler="top_k", top_k=16, temperature=1.0, seed=0)
        plain = DecodeEngine(m, **kw)
        spec = DecodeEngine(m, spec_decode_k=2, **kw)
        n = 200
        hists = []
        for eng in (plain, spec):
            toks = [eng.generate([p], max_new_tokens=2)[0][1]
                    for _ in range(n)]
            hists.append(np.bincount(toks, minlength=16) / n)
        tv = 0.5 * np.abs(hists[0] - hists[1]).sum()
        assert tv < 0.35, (tv, hists)

    def test_seeded_reproducibility(self):
        m = _tiny_gpt(seed=8)
        rng = np.random.RandomState(6)
        p = rng.randint(0, 64, (6,)).astype(np.int32)
        outs = []
        for _ in range(2):
            eng = _engine(m, max_batch_size=1, sampler="top_p",
                          top_p=0.9, temperature=0.8, seed=11,
                          spec_decode_k=3)
            outs.append(eng.generate([p], max_new_tokens=6)[0])
        assert outs[0] == outs[1]
        assert len(outs[0]) == 6


class TestRollbackInvariants:
    def test_pool_clean_after_mixed_traffic(self):
        """Waves of requests through a spec engine with an adversarial
        drafter (constant rollback) then a prompt-lookup one (mostly
        accept): every page returns, reservations zero out, and slots
        free — rejection really is just seq_lens arithmetic."""
        from paddle_tpu.inference.speculative import PromptLookupDrafter

        m = _tiny_gpt(seed=9)
        rng = np.random.RandomState(7)
        for drafter in (_AlwaysWrongDrafter(), PromptLookupDrafter()):
            eng = _engine(m, max_batch_size=2, spec_decode_k=3,
                          drafter=drafter)
            for wave in range(3):
                prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                           for n in (4, 9, 6)]
                eng.generate(prompts, max_new_tokens=6)
                assert eng.pool.available_count == eng.pool.num_pages, \
                    (drafter.name, wave)
                assert eng.pool.reserved == 0
                assert not eng._active.any()

    def test_rollback_never_outruns_reservation(self):
        """Near a request's token budget the verify window shrinks
        (write caps), so speculative writes can never touch pages past
        the conservative-admission reservation — even with K larger
        than the remaining budget."""
        m = _tiny_gpt(seed=10)
        rng = np.random.RandomState(8)
        p = rng.randint(0, 64, (4,)).astype(np.int32)
        ref = _engine(m, max_batch_size=1, max_seq_len=32).generate(
            [p], max_new_tokens=3)[0]
        # K = 6 >> max_new_tokens = 3: caps clamp to the need
        eng = _engine(m, max_batch_size=1, max_seq_len=32,
                      spec_decode_k=6)
        out = eng.generate([p], max_new_tokens=3)[0]
        assert out == ref
        assert eng.pool.available_count == eng.pool.num_pages
        assert eng.pool.reserved == 0

    def test_lens_rollback_exact(self):
        """A fully-rejected round advances seq_lens by exactly 1 (the
        correction token) even though K+1 K/V rows were written."""
        m = _tiny_gpt(seed=11)
        rng = np.random.RandomState(9)
        p = rng.randint(0, 64, (5,)).astype(np.int32)
        eng = _engine(m, max_batch_size=1, spec_decode_k=4,
                      drafter=_AlwaysWrongDrafter())
        req = eng.add_request(p, max_new_tokens=10)
        eng.step()  # admit + prefill + first speculative round
        lens0, out0 = int(eng._lens[0]), len(req.output_ids)
        eng.step()  # one fully-rejected speculative round
        # K+1 = 5 K/V rows were written, but only the correction token
        # survives: seq_lens advanced by exactly the emission count
        assert int(eng._lens[0]) == lens0 + 1
        assert len(req.output_ids) == out0 + 1
        eng.evict(req)


class TestFinishReasons:
    def test_reasons_and_counters(self):
        from paddle_tpu.inference.serving import (decode_stats,
                                                  reset_decode_stats)

        m = _tiny_gpt(seed=12)
        rng = np.random.RandomState(10)
        p = rng.randint(0, 64, (5,)).astype(np.int32)
        first = _engine(m).generate([p], max_new_tokens=1)[0][0]
        reset_decode_stats()
        eng = _engine(m, max_batch_size=2, eos_token_id=int(first))
        toks, reasons = eng.generate([p, p], max_new_tokens=6,
                                     return_meta=True)
        assert reasons == ["eos", "eos"]
        other = rng.randint(0, 64, (7,)).astype(np.int32)
        toks, reasons = eng.generate([other], max_new_tokens=2,
                                     return_meta=True)
        assert reasons == ["length"]
        req = eng.add_request(other, max_new_tokens=30)
        eng.step()
        eng.evict(req)
        assert req.finish_reason == "evicted"
        st = decode_stats()
        assert st["finished_eos"] == 2
        assert st["finished_length"] == 1
        assert st["evicted"] == 1

    def test_evict_queued_request(self):
        m = _tiny_gpt(seed=13)
        eng = _engine(m, max_batch_size=1)
        p = np.arange(4).astype(np.int32)
        r1 = eng.add_request(p, max_new_tokens=4)
        r2 = eng.add_request(p, max_new_tokens=4)
        eng.evict(r2)
        assert r2.state == "done" and r2.finish_reason == "evicted"
        assert r2.output_ids == []
        eng.run()
        assert r1.finish_reason == "length"

    def test_evict_foreign_request_refused(self):
        from paddle_tpu.inference.serving import Request

        m = _tiny_gpt(seed=14)
        eng = _engine(m)
        with pytest.raises(ValueError, match="not queued|not owned"):
            eng.evict(Request(np.arange(3), 4))


class TestDraftConfig:
    def test_draft_config_pins_token_space(self):
        cfg = TINY.draft_config()
        assert cfg.vocab_size == TINY.vocab_size
        assert cfg.max_seq_len == TINY.max_seq_len
        assert cfg.num_layers == 1
        assert cfg.hidden_size < TINY.hidden_size
        assert cfg.hidden_size % cfg.num_heads == 0

    def test_draft_config_validates_divisibility(self):
        with pytest.raises(ValueError, match="divisible"):
            TINY.draft_config(hidden_size=30, num_heads=4)

    def test_vocab_mismatch_refused(self):
        from paddle_tpu.inference.speculative import DraftModelDrafter

        m = _tiny_gpt(seed=15)
        bad = GPTConfig(vocab_size=32, hidden_size=16, num_layers=1,
                        num_heads=2, max_seq_len=128,
                        use_parallel_layers=False)
        paddle.seed(1)
        dm = GPT(bad)
        dm.eval()
        with pytest.raises(ValueError, match="vocab"):
            _engine(m, spec_decode_k=2, drafter=DraftModelDrafter(dm))

    def test_unknown_drafter_name_refused(self):
        m = _tiny_gpt(seed=16)
        with pytest.raises(ValueError, match="unknown drafter"):
            _engine(m, spec_decode_k=2, drafter="no_such_drafter")

    def test_drafter_without_k_refused(self):
        # a drafter with spec decoding off would be silently unused
        m = _tiny_gpt(seed=17)
        with pytest.raises(ValueError, match="spec_decode_k"):
            _engine(m, drafter="prompt_lookup")

    def test_drafter_rebind_refused(self):
        from paddle_tpu.inference.speculative import PromptLookupDrafter

        m = _tiny_gpt(seed=19)
        d = PromptLookupDrafter()
        _engine(m, spec_decode_k=2, drafter=d)
        with pytest.raises(ValueError, match="already bound"):
            _engine(m, spec_decode_k=2, drafter=d)


class TestFlagWiring:
    def test_flag_enables_spec_decode(self):
        m = _tiny_gpt(seed=18)
        rng = np.random.RandomState(11)
        p = rng.randint(0, 64, (6,)).astype(np.int32)
        ref = _engine(m).generate([p], max_new_tokens=6)[0]
        paddle.set_flags({"FLAGS_spec_decode_k": 3})
        try:
            eng = _engine(m)
            assert eng._spec is not None and eng._spec.k == 3
            assert eng.generate([p], max_new_tokens=6)[0] == ref
        finally:
            paddle.set_flags({"FLAGS_spec_decode_k": 0})
        # explicit arg beats the flag
        eng = _engine(m, spec_decode_k=2)
        assert eng._spec is not None and eng._spec.k == 2


class TestPromptLookup:
    def test_lookup_proposes_repetition(self):
        from paddle_tpu.inference.speculative import PromptLookupDrafter

        d = PromptLookupDrafter(ngram_max=2)
        d.k = 3
        hist = np.asarray([5, 1, 2, 9, 1, 2], np.int32)
        # suffix [1, 2] recurs at index 1 -> continuation [9, 1, 2]
        np.testing.assert_array_equal(d._lookup(hist), [9, 1, 2])
        # no recurrence: flat repeat of the last token
        np.testing.assert_array_equal(
            d._lookup(np.asarray([1, 2, 3], np.int32)), [3, 3, 3])

    def test_lookup_pads_short_continuation(self):
        from paddle_tpu.inference.speculative import PromptLookupDrafter

        d = PromptLookupDrafter(ngram_max=1)
        d.k = 4
        hist = np.asarray([7, 8, 7], np.int32)
        # continuation after the earlier 7 is just [8]; padded with last
        np.testing.assert_array_equal(d._lookup(hist), [8, 7, 7, 7])

    def test_validates_ngram_range(self):
        from paddle_tpu.inference.speculative import PromptLookupDrafter

        with pytest.raises(ValueError, match="ngram"):
            PromptLookupDrafter(ngram_max=0)
