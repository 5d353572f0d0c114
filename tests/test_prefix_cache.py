"""Prefix caching with copy-on-write KV pages (FLAGS_prefix_cache).

Contracts pinned here (ISSUE 6 acceptance):

* admission maps the LONGEST PAGE-ALIGNED cached prefix into the
  request's block table at refcount+1 and chunked prefill starts at
  the first novel token (a whole-prompt match is capped one page short
  — the first sampled token needs the last position's logits);
* greedy output is BIT-IDENTICAL with the cache on vs off (the
  FLAGS_prefix_cache=0 parity oracle), including prompts whose shared
  prefix ends mid-page (copy-on-write divergence) and across cache
  eviction/reuse cycles;
* cached pages are NEVER written in place: a mid-page divergence
  recomputes into a fresh private page while the cached page's device
  bytes stay bit-identical;
* freeing is unref — pages with live refs never return to the free
  list, refcount-zero cached pages park on an LRU and are evicted
  least-recently-released-first under pool pressure, and allocation
  raises cleanly when every page is referenced;
* `DraftModelDrafter` shares the mapping: a prefix hit skips the
  draft-side prompt ingestion too (the cached page holds BOTH models'
  K/V under the same page id);
* `KVBlockPool.free_pages` raises on a double free / unallocated page
  (satellite), `assert_consistent` audits the free+private+cached
  partition (satellite, FLAGS_kv_pool_debug wires it into the serve
  loop), and `Request` ids are race-free under concurrent enqueues
  (satellite).
"""
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (DecodeEngine, KVBlockPool,
                                          Request, decode_stats,
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

PAGE = 4


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    m = GPT(TINY)
    m.eval()
    return m


def _engine(m, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("prefill_chunk_tokens", 8)
    return DecodeEngine(m, **kw)


def _serve_one(eng, prompt, max_new_tokens=6):
    req = eng.add_request(prompt, max_new_tokens)
    eng.run()
    assert req.state == "done"
    return req


def _serve_track(eng, prompt, max_new_tokens=6):
    """Serve one request to completion, snapshotting its page list at
    first-token time (``_finish`` drops ownership and clears
    ``req.pages``)."""
    req = eng.add_request(prompt, max_new_tokens)
    while not req.output_ids:
        eng.step()
    pages = list(req.pages)
    eng.run()
    assert req.state == "done"
    return req, pages


def _prompts_sharing(rng, shared_len, tail_len, n):
    shared = rng.randint(0, 64, (shared_len,)).astype(np.int32)
    return [np.concatenate(
        [shared, rng.randint(0, 64, (tail_len,)).astype(np.int32)])
        for _ in range(n)]


# ---------------------------------------------------------------------------
# KVBlockPool: allocator + content-addressing unit contracts
# ---------------------------------------------------------------------------
class TestPoolCache:
    def test_double_free_raises(self):
        pool = KVBlockPool(4)
        p = pool.alloc_page()
        pool.free_pages([p])
        with pytest.raises(ValueError, match="double free"):
            pool.free_pages([p])
        pool.assert_consistent()

    def test_free_unallocated_or_oob_raises(self):
        pool = KVBlockPool(4)
        with pytest.raises(ValueError, match="double free"):
            pool.free_pages([2])  # never allocated: still on the free list
        with pytest.raises(ValueError, match="outside pool"):
            pool.free_pages([7])
        with pytest.raises(ValueError, match="outside pool"):
            pool.free_pages([-1])
        pool.assert_consistent()

    def test_free_cached_page_raises(self):
        pool = KVBlockPool(4)
        p = pool.alloc_page()
        assert pool.register_page(p, b"k0")
        with pytest.raises(ValueError, match="cached"):
            pool.free_pages([p])
        pool.assert_consistent(live_pages=[p])

    def test_register_lookup_ref_unref_lifecycle(self):
        pool = KVBlockPool(4)
        p = pool.alloc_page()
        assert pool.lookup(b"k0") is None
        assert pool.register_page(p, b"k0")  # owner's hold -> refcount 1
        assert pool.lookup(b"k0") == p
        assert pool.refcount(p) == 1
        pool.ref_page(p)  # a second request maps it
        assert pool.refcount(p) == 2
        pool.assert_consistent(live_pages=[p, p])
        pool.unref_page(p)
        pool.unref_page(p)  # last ref -> parked on the LRU, still cached
        assert pool.refcount(p) == 0
        assert pool.cached_unreferenced_count == 1
        assert pool.lookup(b"k0") == p
        assert pool.free_count == 3 and pool.available_count == 4
        with pytest.raises(ValueError, match="without a live ref"):
            pool.unref_page(p)
        with pytest.raises(ValueError, match="not cached"):
            pool.ref_page(pool.alloc_page())
        with pytest.raises(ValueError, match="free page"):
            pool.register_page(pool._free[-1], b"k1")

    def test_duplicate_hash_first_writer_wins(self):
        pool = KVBlockPool(4)
        a, b = pool.alloc_page(), pool.alloc_page()
        assert pool.register_page(a, b"k")
        assert not pool.register_page(b, b"k")  # stays private
        assert pool.lookup(b"k") == a
        pool.free_pages([b])  # private page frees normally
        pool.assert_consistent(live_pages=[a])

    def test_alloc_prefers_free_then_evicts_lru_oldest(self):
        pool = KVBlockPool(3)
        pages = [pool.alloc_page() for _ in range(3)]
        for i, p in enumerate(pages):
            assert pool.register_page(p, b"k%d" % i)
        pool.unref_page(pages[1])  # released first -> evicted first
        pool.unref_page(pages[0])
        got = pool.alloc_page()
        assert got == pages[1] and pool.evictions == 1
        assert pool.lookup(b"k1") is None  # deregistered on eviction
        assert pool.lookup(b"k0") == pages[0]  # newer survivor intact
        pool.assert_consistent(live_pages=[pages[2], got])

    def test_alloc_raises_when_all_pages_referenced(self):
        pool = KVBlockPool(2)
        for i in range(2):
            assert pool.register_page(pool.alloc_page(), b"k%d" % i)
        assert pool.available_count == 0
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.alloc_page()  # live refs are never evicted

    def test_lru_order_refreshed_by_reuse(self):
        pool = KVBlockPool(2)
        a, b = pool.alloc_page(), pool.alloc_page()
        pool.register_page(a, b"ka")
        pool.register_page(b, b"kb")
        pool.unref_page(a)
        pool.unref_page(b)  # LRU order: a, b
        pool.ref_page(a)
        pool.unref_page(a)  # a re-released: now b is the oldest
        got = pool.alloc_page()
        assert got == b
        pool.assert_consistent(live_pages=[got])

    def test_release_pages_dispatches_cached_vs_private(self):
        pool = KVBlockPool(4)
        cached, private = pool.alloc_page(), pool.alloc_page()
        pool.register_page(cached, b"k")
        pool.release_pages([cached, private])
        assert pool.lookup(b"k") == cached  # retained (unreffed)
        assert pool.refcount(cached) == 0
        assert pool.free_count == 3  # private truly freed
        assert pool.available_count == 4
        pool.assert_consistent(live_pages=[])

    def test_assert_consistent_catches_corruption(self):
        pool = KVBlockPool(4)
        p = pool.alloc_page()
        pool.register_page(p, b"k")
        pool._free.append(p)  # cached page smuggled onto the free list
        pool._free_set.add(p)
        with pytest.raises(AssertionError):
            pool.assert_consistent()


# ---------------------------------------------------------------------------
# admission: longest page-aligned hit, COW divergence, parity
# ---------------------------------------------------------------------------
class TestPrefixAdmission:
    def test_page_aligned_hit_skips_prefill(self):
        m = _tiny_gpt(seed=1)
        rng = np.random.RandomState(2)
        pa, pb = _prompts_sharing(rng, 12, 5, 2)  # 3 shared full pages
        eng = _engine(m, prefix_cache=True)
        ra, pages_a = _serve_track(eng, pa)
        rb, pages_b = _serve_track(eng, pb)
        assert ra.cached_prefix_len == 0
        assert rb.cached_prefix_len == 12 and rb.cached_page_count == 3
        # the mapped pages ARE the first request's prompt pages
        assert pages_b[:3] == pages_a[:3]
        # and the second prefill consumed only the novel tail
        assert rb.prefill_chunks < ra.prefill_chunks
        st = decode_stats()
        assert st["prefix_hits"] == 3
        assert st["prefix_cached_tokens"] == 12
        # identical engine, cache off: bit-identical tokens
        eng0 = _engine(m, prefix_cache=False)
        assert [list(_serve_one(eng0, p).output_ids) for p in (pa, pb)] \
            == [list(ra.output_ids), list(rb.output_ids)]

    def test_whole_prompt_hit_capped_one_page_short(self):
        m = _tiny_gpt(seed=2)
        rng = np.random.RandomState(3)
        p = rng.randint(0, 64, (8,)).astype(np.int32)  # exactly 2 pages
        eng = _engine(m, prefix_cache=True)
        ra = _serve_one(eng, p)
        rb = _serve_one(eng, p.copy())
        # page 2 is registered but never mapped whole: the last prompt
        # token must be recomputed to sample the first output token
        assert rb.cached_prefix_len == 4 and rb.cached_page_count == 1
        assert list(rb.output_ids) == list(ra.output_ids)

    def test_mid_page_divergence_is_copy_on_write(self):
        m = _tiny_gpt(seed=3)
        rng = np.random.RandomState(4)
        shared = rng.randint(0, 64, (6,)).astype(np.int32)  # 1.5 pages
        pa = np.concatenate([shared, rng.randint(0, 64, (6,))
                             .astype(np.int32)])
        pb = np.concatenate([shared, rng.randint(0, 64, (6,))
                             .astype(np.int32)])
        eng = _engine(m, prefix_cache=True)
        # keep A running so its pages cannot be recycled into B
        ra = eng.add_request(pa, max_new_tokens=12)
        while not ra.output_ids:
            eng.step()
        pages_a = list(ra.pages)
        rb = eng.add_request(pb, max_new_tokens=4)
        while not rb.output_ids:
            eng.step()
        pages_b = list(rb.pages)
        eng.run()
        # only the FULL shared page is mapped; the divergence page is a
        # fresh private copy, not A's partially-matching page
        assert rb.cached_prefix_len == 4 and rb.cached_page_count == 1
        assert pages_b[0] == pages_a[0]
        assert pages_b[1] != pages_a[1]
        assert eng.pool.refcount(pages_a[0]) == 0  # both done: unreffed
        # parity against the cache-off engine for the same pair
        eng0 = _engine(m, prefix_cache=False)
        r0a = eng0.add_request(pa, max_new_tokens=12)
        while not r0a.output_ids:
            eng0.step()
        r0b = eng0.add_request(pb, max_new_tokens=4)
        eng0.run()
        assert list(ra.output_ids) == list(r0a.output_ids)
        assert list(rb.output_ids) == list(r0b.output_ids)

    def test_cached_page_device_bytes_never_mutated(self):
        import jax

        m = _tiny_gpt(seed=4)
        rng = np.random.RandomState(5)
        pa, pb = _prompts_sharing(rng, 8, 6, 2)
        eng = _engine(m, prefix_cache=True)
        _, pages_a = _serve_track(eng, pa)
        page = pages_a[0]
        before_k = np.asarray(jax.device_get(eng._kv.k[:, :, page]))
        before_v = np.asarray(jax.device_get(eng._kv.v[:, :, page]))
        _, pages_b = _serve_track(eng, pb)
        assert pages_b[0] == page  # served from cache...
        after_k = np.asarray(jax.device_get(eng._kv.k[:, :, page]))
        after_v = np.asarray(jax.device_get(eng._kv.v[:, :, page]))
        np.testing.assert_array_equal(before_k, after_k)  # ...read-only
        np.testing.assert_array_equal(before_v, after_v)

    def test_parity_across_eviction_and_reuse_cycles(self):
        """Greedy bit-parity cache on vs off vs legacy one-shot, over a
        workload that exercises aligned hits, mid-page divergence, and
        LRU eviction + re-admission of a previously-cached family."""
        m = _tiny_gpt(seed=5)

        def workload():
            out = []
            for seed in (10, 11, 12, 10, 11):  # 10/11 re-served
                r = np.random.RandomState(seed)
                sh = r.randint(0, 64, (10,)).astype(np.int32)  # mid-page
                out += [np.concatenate(
                    [sh, r.randint(0, 64, (4,)).astype(np.int32)])
                    for _ in range(2)]
            return out

        def serve(**kw):
            eng = _engine(m, max_batch_size=1, max_seq_len=24,
                          num_pages=8, **kw)
            return [list(_serve_one(eng, p, max_new_tokens=4).output_ids)
                    for p in workload()]

        ref = serve(prefix_cache=False)
        assert serve(prefix_cache=True) == ref
        assert serve(chunked_prefill=False) == ref
        st = decode_stats()
        assert st["prefix_evictions"] > 0  # the pressure was real
        assert st["prefix_hits"] > 0
        assert st["retraces_after_warmup"] == 0

    def test_refcount_lifecycle_finish_evict_cancel(self):
        m = _tiny_gpt(seed=6)
        rng = np.random.RandomState(7)
        pa, pb = _prompts_sharing(rng, 8, 5, 2)
        eng = _engine(m, prefix_cache=True)
        ra, pages_a = _serve_track(eng, pa)
        shared = pages_a[:2]
        assert all(eng.pool.refcount(p) == 0 for p in shared)  # parked
        # a running request holds the mapped pages at refcount 1
        rb = eng.add_request(pb, max_new_tokens=8)
        while not rb.output_ids:
            eng.step()
        assert [eng.pool.refcount(p) for p in shared] == [1, 1]
        assert rb.cached_page_count == 2
        # evicting the running request unrefs (never frees) the shared
        # pages and truly frees its private ones
        eng.evict(rb)
        assert [eng.pool.refcount(p) for p in shared] == [0, 0]
        assert eng.pool.lookup(ra._page_hashes[0]) == shared[0]
        assert eng.pool.available_count == eng.pool.num_pages
        eng._debug_check_pool()
        # cancel of a never-admitted request touches no pages
        eng2 = _engine(m, max_batch_size=1, prefix_cache=True)
        r1 = eng2.add_request(pa, max_new_tokens=4)
        r2 = eng2.add_request(pb, max_new_tokens=4)
        r2.cancel()
        eng2.run()
        assert r1.state == "done" and r2.finish_reason == "cancelled"
        assert eng2.pool.available_count == eng2.pool.num_pages

    def test_eviction_is_lru_and_never_touches_live_refs(self):
        m = _tiny_gpt(seed=7)

        def fam(seed):
            return np.random.RandomState(seed).randint(
                0, 64, (12,)).astype(np.int32)

        # 12 pages; each request needs 4 (12 prompt + 3 decode rows)
        # and parks its 3 full prompt pages in the cache at finish
        eng = _engine(m, max_batch_size=1, max_seq_len=24, num_pages=12,
                      prefix_cache=True)
        for s in (20, 21, 22):
            _serve_one(eng, fam(s), max_new_tokens=4)
        assert eng.pool.cached_count == 9 and eng.pool.evictions == 0
        # the 4th family finds 3 free pages: exactly ONE eviction, and
        # it takes the least-recently-released page — family 20's first
        _serve_one(eng, fam(23), max_new_tokens=4)
        assert eng.pool.evictions == 1
        # family 20's chain is broken at page 0: probe misses entirely
        # (its surviving descendants are unreachable by construction);
        # newer families still hit both probeable pages
        assert eng._probe_prefix(Request(fam(20))) == []
        assert len(eng._probe_prefix(Request(fam(22)))) == 2
        assert len(eng._probe_prefix(Request(fam(23)))) == 2
        st = decode_stats()
        assert st["prefix_evictions"] == 1
        eng._debug_check_pool()

    def test_admission_waits_while_all_pages_referenced(self):
        m = _tiny_gpt(seed=8)
        rng = np.random.RandomState(9)
        p = rng.randint(0, 64, (8,)).astype(np.int32)
        # pool sized for exactly one request (8 prompt + 7 decode = 4
        # pages): the second stays QUEUED until the first releases
        eng = _engine(m, max_seq_len=16, num_pages=4, prefix_cache=True)
        r1 = eng.add_request(p, max_new_tokens=8)
        r2 = eng.add_request(p.copy(), max_new_tokens=8)
        eng.step()
        assert r1.state == "running" and r2.state == "queued"
        eng.run()
        assert r1.state == "done" and r2.state == "done"
        # r2 was admitted AFTER r1 parked its pages: it hits the cache
        assert r2.cached_prefix_len == 4
        assert list(r2.output_ids) == list(r1.output_ids)

    def test_counters_gauges_and_histogram(self):
        m = _tiny_gpt(seed=9)
        rng = np.random.RandomState(11)
        pa, pb, pc = _prompts_sharing(rng, 8, 5, 3)
        eng = _engine(m, prefix_cache=True)
        for p in (pa, pb, pc):
            _serve_one(eng, p)
        st = decode_stats()
        # pa (13 tokens, 3 probeable pages): 0 hits / 3 misses; pb, pc
        # share 8 tokens: pages 0-1 hit, page 2 (divergent tail) misses
        assert st["prefix_hits"] == 4
        assert st["prefix_misses"] == 5
        assert st["prefix_cached_tokens"] == 16
        assert obs.PREFIX_HITS.value() == 4
        assert obs.PREFIX_MISSES.value() == 5
        hist = obs.PREFIX_CACHED_TOKENS.series_state()
        assert hist["count"] == 3 and hist["sum"] == 16
        eid = eng._engine_id
        assert obs.PREFIX_CACHED_PAGES.value(engine=eid) == \
            eng.pool.cached_count > 0
        txt = obs.prometheus_text()
        for needle in ("paddle_prefix_cache_page_hits_total",
                       "paddle_prefix_cache_page_misses_total",
                       "paddle_prefix_cache_evictions_total",
                       "paddle_prefix_cached_tokens_bucket",
                       "paddle_prefix_cached_pages"):
            assert needle in txt, needle

    def test_flag_gates_and_legacy_guard(self):
        from paddle_tpu.core import flags as _flags

        m = _tiny_gpt(seed=10)
        rng = np.random.RandomState(12)
        pa, pb = _prompts_sharing(rng, 8, 5, 2)
        # explicit prefix_cache on the legacy path is refused loudly
        with pytest.raises(ValueError, match="chunked"):
            _engine(m, prefix_cache=True, chunked_prefill=False)
        # legacy + flag default: silently off, still serves
        eng = _engine(m, chunked_prefill=False)
        assert not eng._prefix_cache
        # flag off: no probe, no hits, pool fully freed at idle
        prev = paddle.get_flags("prefix_cache")["prefix_cache"]
        try:
            paddle.set_flags({"prefix_cache": False})
            eng = _engine(m)
            assert not eng._prefix_cache
            for p in (pa, pb):
                _serve_one(eng, p)
            assert decode_stats()["prefix_hits"] == 0
            assert eng.pool.free_count == eng.pool.num_pages
            paddle.set_flags({"prefix_cache": True})
            assert _engine(m)._prefix_cache
        finally:
            paddle.set_flags({"prefix_cache": prev})
        _ = _flags  # imported for symmetry with other flag tests

    def test_kv_pool_debug_flag_audits_every_step(self):
        m = _tiny_gpt(seed=11)
        rng = np.random.RandomState(13)
        prev = paddle.get_flags("kv_pool_debug")["kv_pool_debug"]
        try:
            paddle.set_flags({"kv_pool_debug": True})
            eng = _engine(m, prefix_cache=True)
            assert eng._pool_debug
            for p in _prompts_sharing(rng, 8, 5, 2):
                _serve_one(eng, p)  # every step runs the audit
        finally:
            paddle.set_flags({"kv_pool_debug": prev})


# ---------------------------------------------------------------------------
# speculative decoding: the draft cache shares the mapping
# ---------------------------------------------------------------------------
class TestDraftCacheSharing:
    def test_draft_model_skips_cached_prefix_bit_exactly(self):
        from paddle_tpu.inference.speculative import DraftModelDrafter

        m = _tiny_gpt(seed=12)
        rng = np.random.RandomState(14)
        prompts = _prompts_sharing(rng, 12, 5, 3)

        def serve(**kw):
            if kw.pop("draft", False):
                paddle.seed(17)
                dm = GPT(TINY.draft_config())
                dm.eval()
                kw.update(spec_decode_k=3, drafter=DraftModelDrafter(dm))
            eng = _engine(m, **kw)
            reqs = [_serve_one(eng, p, max_new_tokens=8) for p in prompts]
            return eng, reqs

        _, ref = serve(prefix_cache=False)
        ref = [list(r.output_ids) for r in ref]
        reset_decode_stats()
        eng, reqs = serve(prefix_cache=True, draft=True)
        assert [list(r.output_ids) for r in reqs] == ref
        # the draft genuinely skipped the cached prefix: hits landed...
        assert reqs[1].cached_prefix_len == 12
        st = decode_stats()
        assert st["prefix_hits"] == 6
        # ...with the usual executable hygiene (catch-up + step + chunk
        # ingest compile once; nothing retraces warm)
        assert st["draft_compiles"] == 3
        assert st["retraces_after_warmup"] == 0
        # and the draft cursor agrees with the engine everywhere
        assert (eng._spec.drafter._lens == 0).all()  # all finished
        # prompt-lookup drafter (host-side) is equally unaffected
        reset_decode_stats()
        _, reqs = serve(prefix_cache=True, spec_decode_k=3)
        assert [list(r.output_ids) for r in reqs] == ref


# ---------------------------------------------------------------------------
# satellite (fleet PR): generated-page registration is opt-in
# ---------------------------------------------------------------------------
class TestGeneratedPageFlag:
    """FLAGS_cache_generated_pages gates registering GENERATED full KV
    pages as decode crosses page boundaries — default OFF (the PR 17
    behavior becomes opt-in); on or off, greedy output is untouched."""

    def test_default_off_and_parity(self):
        m = _tiny_gpt()
        p = np.arange(1, 9, dtype=np.int32)  # 2 full pages
        off = _engine(m, prefix_cache=True)
        assert off._cache_generated is False  # flag default
        out_off = list(off.generate([p], max_new_tokens=10)[0])
        on = _engine(m, prefix_cache=True, cache_generated_pages=True)
        out_on = list(on.generate([p], max_new_tokens=10)[0])
        assert out_on == out_off  # registration never alters sampling

        # fanout prompt extending prompt+output: with the flag ON the
        # generated pages hit; OFF they're novel (prompt pages only)
        p2 = np.concatenate([p, np.asarray(out_off[:8], np.int32)])
        outs = {}
        for name, eng, expect in (("off", off, 2), ("on", on, 3)):
            reset_decode_stats()
            outs[name] = list(eng.generate([p2], max_new_tokens=4)[0])
            assert decode_stats()["prefix_hits"] == expect
        # parity on the fanout too: hits change work, never tokens
        assert outs["on"] == outs["off"]

    def test_flag_without_prefix_cache_resolves_off(self):
        m = _tiny_gpt()
        eng = _engine(m, prefix_cache=False,
                      cache_generated_pages=True)
        assert eng._cache_generated is False
        p = np.arange(1, 9, dtype=np.int32)
        eng.generate([p], max_new_tokens=8)
        assert decode_stats()["prefix_hits"] == 0


# ---------------------------------------------------------------------------
# satellite: request ids are race-free
# ---------------------------------------------------------------------------
class TestRequestIds:
    def test_concurrent_construction_yields_unique_ids(self):
        ids = []
        lock = threading.Lock()

        def worker():
            got = [Request([1]).request_id for _ in range(200)]
            with lock:
                ids.extend(got)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(ids) == len(set(ids)) == 1600
