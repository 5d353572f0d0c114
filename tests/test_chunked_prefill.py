"""Chunked prefill fused into the decode step (FLAGS_chunked_prefill).

Contracts pinned here (ISSUE 5 acceptance):

* greedy output through the mixed prefill+decode executable is
  BIT-IDENTICAL to the legacy one-shot prefill path (the parity
  oracle behind ``chunked_prefill=0``) and therefore to eager
  ``GPT.generate`` — across chunk sizes including page-size-unaligned
  ones, under staggered continuous batching, and with speculative
  decoding stacked on top;
* ONE mixed executable serves every prompt length (the pow-2 prefill
  bucket zoo collapses: ``prefill_compiles == 0`` chunked), with zero
  warm retraces;
* TTFT is stamped when a request's LAST prompt chunk lands (not at
  admission, not at the first chunk), TPOT stays exact when a prompt
  spans several chunks;
* decoding slots keep emitting one token per step while another slot's
  prompt streams in (the stall legacy prefill imposed);
* eviction mid-prefill returns every page and zeroes the reservation;
* RNG fold_in domains (decode vs legacy prefill) can never alias, no
  matter the counter values;
* `Request.cancel` removes still-queued requests with
  ``finished{reason="cancelled"}`` accounting;
* the admission free-slot heap replaces the per-request slot scan.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (DecodeEngine, Request,
                                          decode_stats,
                                          reset_decode_stats)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    reset_decode_stats()
    obs.reset()
    obs.clear_spans()
    yield
    obs.reset()
    obs.clear_spans()


TINY = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 max_seq_len=128, use_parallel_layers=False, dropout=0.0)


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    m = GPT(TINY)
    m.eval()
    return m


def _engine(m, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 16)
    return DecodeEngine(m, **kw)


def _step(eng):
    """One step on the synchronous schedule: dispatched and landed in
    this call (`step` alone leaves it in flight; tests/test_lookahead.py
    pins the two schedules' tokens equal)."""
    eng.step()
    eng.drain()


# ---------------------------------------------------------------------------
# RNG stream domains (satellite: fold_in counters can never alias)
# ---------------------------------------------------------------------------
class TestRngDomains:
    def test_windows_disjoint_and_wrapping(self):
        from paddle_tpu.inference.serving import (_RNG_DOMAIN,
                                                  RNG_DECODE_DOMAIN,
                                                  RNG_PREFILL_DOMAIN,
                                                  _fold_counter)

        dec_lo, dec_hi = 1, _RNG_DOMAIN
        pre_lo, pre_hi = _RNG_DOMAIN + 1, 2 * _RNG_DOMAIN
        # small counters keep the historical values (stream-compatible)
        assert _fold_counter(1, RNG_DECODE_DOMAIN) == 1
        assert _fold_counter(7, RNG_DECODE_DOMAIN) == 7
        assert _fold_counter(1, RNG_PREFILL_DOMAIN) == _RNG_DOMAIN + 1
        # the old code ((1 << 30) + n for prefill, raw step_no for
        # decode) aliased once a counter crossed 2^30 — the fold value
        # now WRAPS inside its own window instead
        for counter in (_RNG_DOMAIN, _RNG_DOMAIN + 1, 3 * _RNG_DOMAIN,
                        5 * _RNG_DOMAIN + 17, 2**40 + 123):
            d = _fold_counter(counter, RNG_DECODE_DOMAIN)
            p = _fold_counter(counter, RNG_PREFILL_DOMAIN)
            assert dec_lo <= d <= dec_hi, (counter, d)
            assert pre_lo <= p <= pre_hi, (counter, p)
        # wrap is exact: counter 2^30 + 1 reuses the value of counter 1
        assert _fold_counter(_RNG_DOMAIN + 1, RNG_DECODE_DOMAIN) == 1

    def test_rejects_unstarted_counter(self):
        from paddle_tpu.inference.serving import (RNG_DECODE_DOMAIN,
                                                  _fold_counter)

        with pytest.raises(ValueError, match="counter"):
            _fold_counter(0, RNG_DECODE_DOMAIN)


# ---------------------------------------------------------------------------
# greedy parity: chunked == legacy == eager, bit for bit
# ---------------------------------------------------------------------------
class TestChunkedParity:
    # 16 = page-aligned, 64 = whole-prompt chunks, 24/10 straddle page
    # boundaries (page_size is 16 here)
    @pytest.mark.parametrize("chunk", [16, 64, 24, 10])
    def test_matches_legacy_across_chunk_sizes(self, chunk):
        m = _tiny_gpt(seed=5)
        rng = np.random.RandomState(3)
        # prompts shorter than, equal to, and spanning several chunks
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (5, 16, 37)]
        legacy = _engine(m, chunked_prefill=False).generate(
            prompts, max_new_tokens=8)
        outs = _engine(m, prefill_chunk_tokens=chunk).generate(
            prompts, max_new_tokens=8)
        assert outs == legacy, chunk

    def test_matches_eager_concat(self):
        m = _tiny_gpt(seed=0)
        rng = np.random.RandomState(1)
        p = rng.randint(0, 64, (1, 23)).astype(np.int32)
        ref = np.asarray(m.generate(paddle.to_tensor(p), max_new_tokens=8,
                                    use_cache="concat").numpy())[0]
        out = _engine(m, prefill_chunk_tokens=8).generate(
            [p[0]], max_new_tokens=8)[0]
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_one_mixed_executable_no_bucket_zoo(self):
        """Ragged prompt lengths across pow-2 buckets: legacy compiles
        one prefill executable per bucket, chunked compiles ONE mixed
        program total — and never retraces it warm."""
        m = _tiny_gpt(seed=6)
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (3, 9, 17, 33)]  # buckets 16/16/32/64
        eng = _engine(m, chunked_prefill=False)
        eng.generate(prompts, max_new_tokens=4)
        st = decode_stats(reset=True)
        assert st["prefill_compiles"] == 3  # buckets {16, 32, 64}
        assert st["mixed_steps"] == 0
        eng = _engine(m, prefill_chunk_tokens=16)
        outs = eng.generate(prompts, max_new_tokens=4)
        st = decode_stats()
        assert st["prefill_compiles"] == 0
        assert st["mixed_compiles"] == 1
        assert st["retraces_after_warmup"] == 0
        assert st["prefills"] == 4  # every request still prefilled
        assert outs == _engine(m, chunked_prefill=False).generate(
            prompts, max_new_tokens=4)

    def test_spec_decode_shares_chunk_path(self):
        """Speculative decoding over chunked prefill: chunks flow while
        decoding slots run verify rounds, for both drafters, bit-exact
        against the plain engine."""
        from paddle_tpu.inference.speculative import DraftModelDrafter

        m = _tiny_gpt(seed=5)
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                   for n in (21, 9, 13)]
        refs = _engine(m).generate(prompts, max_new_tokens=9)
        outs = _engine(m, spec_decode_k=3, prefill_chunk_tokens=8
                       ).generate(prompts, max_new_tokens=9)
        assert outs == refs
        paddle.seed(17)
        dm = GPT(TINY.draft_config())
        dm.eval()
        reset_decode_stats()
        eng = _engine(m, spec_decode_k=3, prefill_chunk_tokens=8,
                      drafter=DraftModelDrafter(dm))
        outs = eng.generate(prompts, max_new_tokens=9)
        assert outs == refs
        st = decode_stats()
        # catch-up + decode-step + chunk-ingest draft executables, all
        # warm after the first use
        assert st["draft_compiles"] == 3
        assert st["retraces_after_warmup"] == 0
        assert eng.pool.available_count == eng.pool.num_pages


# ---------------------------------------------------------------------------
# scheduling: TTFT on the last chunk, no decode stalls, budget respected
# ---------------------------------------------------------------------------
class TestChunkedScheduling:
    def test_ttft_stamped_when_last_chunk_lands(self):
        m = _tiny_gpt(seed=8)
        rng = np.random.RandomState(5)
        p = rng.randint(0, 64, (40,)).astype(np.int32)
        eng = _engine(m, max_batch_size=1, prefill_chunk_tokens=16)
        req = eng.add_request(p, max_new_tokens=4)
        # chunks land at steps 1..3 (16 + 16 + 8): no first token, no
        # TTFT observation until the LAST one
        for expect_pos in (16, 32):
            _step(eng)
            assert req.output_ids == []
            assert req.t_first_token_ns is None
            assert int(eng._prefill_pos[0]) == expect_pos
            assert obs.REQUEST_TTFT.series_state()["count"] == 0
        _step(eng)
        assert int(eng._prefill_pos[0]) == 40
        assert len(req.output_ids) == 1
        assert req.t_first_token_ns is not None
        assert obs.REQUEST_TTFT.series_state()["count"] == 1
        assert req.prefill_chunks == 3
        st = decode_stats()
        assert st["prefill_chunks"] == 3 and st["prefills"] == 1
        # chunk-size histogram saw exactly the three chunks
        hs = obs.PREFILL_CHUNK_TOKENS.series_state()
        assert hs["count"] == 3 and hs["sum"] == 40
        eng.run()
        assert req.finish_reason == "length"
        # TPOT over a multi-chunk prompt: measured from the FIRST token
        # (last chunk), not from admission
        tp = obs.REQUEST_TPOT.series_state()
        want = (req.t_finish_ns - req.t_first_token_ns) / 1e9 \
            / (len(req.output_ids) - 1)
        assert tp["count"] == 1
        np.testing.assert_allclose(tp["sum"], want, rtol=1e-6)
        # TTFT histogram recorded enqueue -> last chunk
        np.testing.assert_allclose(
            obs.REQUEST_TTFT.series_state()["sum"],
            (req.t_first_token_ns - req.t_enqueue_ns) / 1e9, rtol=1e-6)

    def test_decoding_slot_advances_during_prefill(self):
        """The tentpole's point: a running request keeps emitting one
        token per step while another slot's long prompt streams in —
        legacy would stall it for the whole prompt pass."""
        m = _tiny_gpt(seed=9)
        rng = np.random.RandomState(6)
        a = eng = None
        eng = _engine(m, prefill_chunk_tokens=8)
        ra = eng.add_request(rng.randint(0, 64, (4,)).astype(np.int32),
                             max_new_tokens=20)
        _step(eng)  # consumes ra's prompt, first token
        assert len(ra.output_ids) == 1
        rb = eng.add_request(rng.randint(0, 64, (24,)).astype(np.int32),
                             max_new_tokens=6)
        for i in range(3):  # rb needs 3 chunks of 8
            _step(eng)
            assert len(ra.output_ids) == 2 + i  # ra never stalled
        assert len(rb.output_ids) == 1  # rb's first token on chunk 3
        st = decode_stats()
        assert st["stalled_decode_steps"] == 0
        assert st["mixed_steps"] == 4  # ra's prompt step + rb's 3 chunks

    def test_budget_fair_shared_across_prefilling_slots(self):
        """Two prompts streaming together split the step's token budget
        evenly (fair-share, remainder to the lower slot) — at most
        `prefill_chunk_tokens` prompt tokens per step total — and both
        requests still finish with bit-parity."""
        m = _tiny_gpt(seed=10)
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, 64, (12,)).astype(np.int32),
                   rng.randint(0, 64, (12,)).astype(np.int32)]
        legacy = _engine(m, chunked_prefill=False).generate(
            prompts, max_new_tokens=5)
        eng = _engine(m, prefill_chunk_tokens=8)
        r0 = eng.add_request(prompts[0], max_new_tokens=5)
        r1 = eng.add_request(prompts[1], max_new_tokens=5)
        _step(eng)  # 8-token budget splits 4 + 4
        assert int(eng._prefill_pos[0]) == 4
        assert int(eng._prefill_pos[1]) == 4
        _step(eng)
        assert int(eng._prefill_pos[0]) == 8
        assert int(eng._prefill_pos[1]) == 8
        _step(eng)  # both prompts land, both first tokens sampled
        assert len(r0.output_ids) == 1 and len(r1.output_ids) == 1
        eng.run()
        assert [list(r0.output_ids), list(r1.output_ids)] == legacy
        hs = obs.PREFILL_CHUNK_TOKENS.series_state()
        assert hs["sum"] == 24  # every prompt token fed exactly once

    def test_short_prompt_not_starved_by_long_one(self):
        """Fair share is the TTFT lever: a short prompt admitted next
        to a long streaming one gets its first token in ONE step
        instead of waiting out the long prompt's whole chunk stream."""
        m = _tiny_gpt(seed=10)
        rng = np.random.RandomState(13)
        eng = _engine(m, prefill_chunk_tokens=8)
        long_r = eng.add_request(
            rng.randint(0, 64, (40,)).astype(np.int32), max_new_tokens=4)
        short_r = eng.add_request(
            rng.randint(0, 64, (4,)).astype(np.int32), max_new_tokens=4)
        _step(eng)  # long gets ceil(8/2)=4, short gets its whole 4
        assert len(short_r.output_ids) == 1
        assert long_r.output_ids == []
        assert int(eng._prefill_pos[0]) == 4

    def test_spec_round_observes_each_step_once(self):
        """Spec + chunked: every engine step lands in the step-latency
        histogram exactly once — chunk-only steps observe their own
        wall, and a round that follows a chunk step opens its window
        BEFORE the chunk so ingestion time is never dropped."""
        m = _tiny_gpt(seed=11)
        rng = np.random.RandomState(14)
        p = rng.randint(0, 64, (21,)).astype(np.int32)
        eng = _engine(m, max_batch_size=1, spec_decode_k=2,
                      prefill_chunk_tokens=8)
        req = eng.add_request(p, max_new_tokens=4)
        for expect in (1, 2, 3):  # 8 + 8 + 5-token chunks (+1 round)
            eng.step()
            assert obs.STEP_SECONDS.series_state()["count"] == expect
        assert len(req.output_ids) >= 1  # round 3 emitted tokens
        eng.run()
        # chunk steps' wall is inside the histogram: its sum covers at
        # least the prefill executable time the stats recorded
        assert obs.STEP_SECONDS.series_state()["sum"] >= \
            decode_stats()["prefill_time_s"]

    def test_legacy_path_counts_stalls(self):
        m = _tiny_gpt(seed=11)
        rng = np.random.RandomState(9)
        eng = _engine(m, chunked_prefill=False)
        eng.add_request(rng.randint(0, 64, (4,)).astype(np.int32),
                        max_new_tokens=8)
        eng.step()
        eng.add_request(rng.randint(0, 64, (9,)).astype(np.int32),
                        max_new_tokens=4)
        eng.run()
        # the second admission prefilled while slot 0 was decoding
        assert decode_stats()["stalled_decode_steps"] == 1


# ---------------------------------------------------------------------------
# eviction mid-prefill
# ---------------------------------------------------------------------------
class TestEvictMidPrefill:
    def test_pages_and_reservation_return(self):
        m = _tiny_gpt(seed=12)
        rng = np.random.RandomState(10)
        p = rng.randint(0, 64, (30,)).astype(np.int32)
        eng = _engine(m, max_batch_size=1, prefill_chunk_tokens=8)
        req = eng.add_request(p, max_new_tokens=4)
        eng.step()  # one chunk in: 2 prompt pages held, 1 reserved
        assert req.output_ids == [] and eng.pool.reserved == 1
        eng.evict(req)
        assert req.finish_reason == "evicted"
        assert eng.pool.available_count == eng.pool.num_pages
        assert eng.pool.reserved == 0
        assert not eng._active.any()
        assert int(eng._prefill_pos[0]) == 0
        # no token was ever sampled for it, and no TTFT recorded
        assert req.output_ids == []
        assert obs.REQUEST_TTFT.series_state()["count"] == 0
        # the slot is immediately reusable and serves correctly
        q = rng.randint(0, 64, (6,)).astype(np.int32)
        ref = _engine(m, max_batch_size=1).generate(
            [q], max_new_tokens=4)[0]
        assert eng.generate([q], max_new_tokens=4)[0] == ref


# ---------------------------------------------------------------------------
# Request.cancel (satellite)
# ---------------------------------------------------------------------------
class TestCancel:
    def test_cancel_queued(self):
        m = _tiny_gpt(seed=13)
        eng = _engine(m, max_batch_size=1)
        p = np.arange(4).astype(np.int32)
        r1 = eng.add_request(p, max_new_tokens=4)
        r2 = eng.add_request(p, max_new_tokens=4)
        r2.cancel()
        assert r2.state == "done" and r2.finish_reason == "cancelled"
        assert r2.output_ids == []
        assert len(eng._queue) == 1
        assert decode_stats()["cancelled"] == 1
        assert obs.REQUESTS_FINISHED.value(reason="cancelled") == 1
        assert obs.REQUEST_E2E.series_state()["count"] == 1
        r2.cancel()  # idempotent on a finished request
        assert decode_stats()["cancelled"] == 1
        eng.run()
        assert r1.finish_reason == "length"

    def test_cancel_running_routes_through_teardown(self):
        # cancel() is uniform across queued/running (PR 7): a RUNNING
        # request gives its slot and pages back through the same
        # teardown as evict, but keeps the distinct "cancelled" reason
        m = _tiny_gpt(seed=14)
        eng = _engine(m, max_batch_size=1)
        req = eng.add_request(np.arange(4).astype(np.int32),
                              max_new_tokens=8)
        eng.step()
        assert req.state == "running"
        req.cancel()
        assert req.state == "done"
        assert req.finish_reason == "cancelled"
        assert decode_stats()["cancelled"] == 1
        assert eng.pool.available_count == eng.pool.num_pages
        req.cancel()  # done: no-op
        assert req.finish_reason == "cancelled"

    def test_cancel_never_enqueued_refused(self):
        with pytest.raises(ValueError, match="never enqueued"):
            Request(np.arange(3), 4).cancel()


# ---------------------------------------------------------------------------
# free-slot heap (satellite)
# ---------------------------------------------------------------------------
class TestFreeSlotHeap:
    def test_lowest_slot_first_and_conserved(self):
        m = _tiny_gpt(seed=15)
        rng = np.random.RandomState(11)
        eng = _engine(m, max_batch_size=3)
        assert sorted(eng._free_slots) == [0, 1, 2]
        reqs = [eng.add_request(rng.randint(0, 64, (4,)).astype(np.int32),
                                max_new_tokens=12) for _ in range(2)]
        eng.step()
        # admission drained the heap lowest-first
        assert reqs[0].slot == 0 and reqs[1].slot == 1
        assert eng._free_slots == [2]
        eng.evict(reqs[0])
        assert sorted(eng._free_slots) == [0, 2]
        # the freed low slot is reused by the next admission
        r3 = eng.add_request(rng.randint(0, 64, (5,)).astype(np.int32),
                             max_new_tokens=2)
        eng.run()
        assert r3.slot is None and r3.finish_reason == "length"
        assert sorted(eng._free_slots) == [0, 1, 2]

    def test_waves_keep_heap_consistent(self):
        m = _tiny_gpt(seed=16)
        rng = np.random.RandomState(12)
        eng = _engine(m, max_batch_size=2, prefill_chunk_tokens=8)
        for _ in range(3):
            prompts = [rng.randint(0, 64, (n,)).astype(np.int32)
                       for n in (4, 11, 7)]
            eng.generate(prompts, max_new_tokens=3)
            assert sorted(eng._free_slots) == [0, 1]
            assert eng.pool.available_count == eng.pool.num_pages
