"""The unified ragged step (FLAGS_ragged_step) + adaptive per-slot
speculation depth (FLAGS_spec_adaptive_k) + generated-page prefix
registration.

Contracts pinned here (ISSUE 16 acceptance):

* greedy ragged serving is BIT-IDENTICAL to the pre-unification engine
  on every phase mix — plain decode, chunked mixed prefill+decode,
  speculative verify, int8 KV, int8 + spec — including staggered
  continuous batching;
* steady-state ragged serving dispatches exactly ONE step executable
  per KV mode, asserted by counter (`ragged_compiles == 1`, the legacy
  step counters zero) — and never retraces it (`ragged_retraces == 0`,
  attributable per executable via the `<kind>_retraces` counters);
* a warm retrace of the ragged step fails LOUDLY under FLAGS_sanitize,
  naming the site;
* adaptive K converges: a rejection streak halves a slot's depth
  toward `spec_k_min`, an acceptance run regrows it (cost-gated) back
  to K, without ever changing the emitted tokens;
* decode crossing a page boundary registers the newly full GENERATED
  page in the prefix cache — fanout requests map it — with the pool's
  refcount partition audited via `PagePool.assert_consistent`;
* tracecheck's jit-site discovery covers the unified executable: both
  ragged twins are found with the full pool-donation contract.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import decode_stats, reset_decode_stats
from paddle_tpu.inference.speculative import Drafter

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


def _prompts(rng, lens):
    return [rng.randint(0, 64, (n,)).astype(np.int32) for n in lens]


class TestRaggedGreedyParity:
    def test_decode_only_parity_one_executable(self):
        """Plain decode through the ragged step ≡ the legacy engine,
        bit for bit, under staggered continuous batching — and the step
        compiles exactly ONE executable (the unification claim as a
        counter assertion, not a log grep)."""
        m = _tiny_gpt(seed=5)
        prompts = _prompts(np.random.RandomState(3), (5, 9, 13))
        refs = _engine(m).generate(prompts, max_new_tokens=10)
        reset_decode_stats()
        outs = _engine(m, ragged_step=True).generate(
            prompts, max_new_tokens=10)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["decode_compiles"] == 0
        assert st["mixed_compiles"] == 0
        assert st["verify_compiles"] == 0
        assert st["ragged_retraces"] == 0
        assert st["retraces_after_warmup"] == 0

    def test_chunked_mixed_parity_one_executable(self):
        """Chunked prefill + decode mixed batches ride the same single
        ragged executable: no mixed step, no decode step, no one-shot
        prefill buckets — and the tokens still match the legacy
        engine."""
        m = _tiny_gpt(seed=6)
        prompts = _prompts(np.random.RandomState(4), (5, 19, 11))
        refs = _engine(m).generate(prompts, max_new_tokens=8)
        reset_decode_stats()
        eng = _engine(m, ragged_step=True, chunked_prefill=True,
                      prefill_q_max=8)
        outs = eng.generate(prompts, max_new_tokens=8)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["decode_compiles"] == 0
        assert st["mixed_compiles"] == 0
        assert st["prefill_compiles"] == 0
        assert st["ragged_retraces"] == 0
        assert st["retraces_after_warmup"] == 0

    def test_spec_verify_parity_one_executable(self):
        """Speculative rounds verify through the ragged step (no
        dedicated verify executable) and greedy emission still matches
        the plain engine."""
        m = _tiny_gpt(seed=7)
        prompts = _prompts(np.random.RandomState(5), (5, 9, 13))
        refs = _engine(m).generate(prompts, max_new_tokens=10)
        reset_decode_stats()
        eng = _engine(m, ragged_step=True, spec_decode_k=3)
        outs = eng.generate(prompts, max_new_tokens=10)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["verify_compiles"] == 0
        assert st["decode_compiles"] == 0
        assert st["spec_steps"] > 0
        assert st["ragged_retraces"] == 0
        assert st["retraces_after_warmup"] == 0

    @pytest.mark.slow  # tier-1 budget: covered by the fast-lane siblings
    def test_int8_parity_one_executable(self):
        """The quantized twin: ragged int8 serving ≡ legacy int8
        serving (bit parity is per KV mode), one `_q` executable."""
        m = _tiny_gpt(seed=8)
        prompts = _prompts(np.random.RandomState(6), (6, 11))
        refs = _engine(m, kv_quant="int8").generate(
            prompts, max_new_tokens=8)
        reset_decode_stats()
        outs = _engine(m, kv_quant="int8", ragged_step=True).generate(
            prompts, max_new_tokens=8)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["decode_compiles"] == 0
        assert st["ragged_retraces"] == 0

    @pytest.mark.slow  # tier-1 budget: covered by the fast-lane siblings
    def test_int8_spec_parity(self):
        m = _tiny_gpt(seed=9)
        prompts = _prompts(np.random.RandomState(7), (5, 9))
        refs = _engine(m, kv_quant="int8").generate(
            prompts, max_new_tokens=8)
        reset_decode_stats()
        eng = _engine(m, kv_quant="int8", ragged_step=True,
                      spec_decode_k=3)
        outs = eng.generate(prompts, max_new_tokens=8)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["verify_compiles"] == 0

    def test_flag_enables_ragged_and_arg_wins(self):
        m = _tiny_gpt(seed=10)
        p = _prompts(np.random.RandomState(8), (6,))[0]
        ref = _engine(m).generate([p], max_new_tokens=6)[0]
        paddle.set_flags({"FLAGS_ragged_step": 1})
        try:
            eng = _engine(m)
            assert eng._ragged
            assert eng.generate([p], max_new_tokens=6)[0] == ref
            # explicit arg beats the flag
            assert not _engine(m, ragged_step=False)._ragged
        finally:
            paddle.set_flags({"FLAGS_ragged_step": 0})

    def test_statusz_and_fingerprint(self):
        """Ragged mode is visible in /statusz and folded into the
        executable-identity fingerprint; the OFF path's fingerprint is
        byte-identical to an engine that never heard of the feature."""
        m = _tiny_gpt(seed=11)
        on = _engine(m, ragged_step=True)
        off = _engine(m, ragged_step=False)
        default = _engine(m)
        assert on.statusz()["config"]["ragged_step"] is True
        assert off.statusz()["config"]["ragged_step"] is False
        assert on.config_fingerprint() != off.config_fingerprint()
        assert off.config_fingerprint() == default.config_fingerprint()

    def test_grid_defaults_to_page_span(self):
        """Steady-state rounds pay the full [slots, Q_r] grid, so an
        unpinned prefill_q_max must not leak the legacy chunk width
        into the ragged grid: the default is one KV page of query span
        per slot (never narrower than the verify window), and an
        explicit prefill_q_max wins verbatim."""
        m = _tiny_gpt(seed=12)
        eng = _engine(m, ragged_step=True, spec_decode_k=3)
        if eng._chunked:
            assert eng._q_max == max(eng._page, 4)
        assert eng._q_ragged == max(eng._page, 4,
                                    eng._q_max if eng._chunked else 1)
        # explicit width wins, and the verify window still fits
        wide = _engine(m, ragged_step=True, spec_decode_k=3,
                       chunked_prefill=True, prefill_q_max=48)
        assert wide._q_max == 48 and wide._q_ragged == 48
        narrow = _engine(m, ragged_step=True, spec_decode_k=3,
                         chunked_prefill=True, prefill_q_max=2)
        assert narrow._q_max == 2 and narrow._q_ragged == 4
        # the legacy (split-executable) engine keeps its historical
        # chunk width: the clamp is a property of the unified grid
        legacy = _engine(m, spec_decode_k=3)
        if legacy._chunked:
            assert legacy._q_max == min(legacy._chunk_budget, 64)


# ---------------------------------------------------------------------------
# Adaptive per-slot speculation depth
# ---------------------------------------------------------------------------
class _RegimeDrafter(Drafter):
    """Deterministic acceptance-regime drafter: in the accept regime it
    proposes the TRUE greedy continuation (precomputed reference), so
    every usable draft lands; in the reject regime it proposes
    off-by-one tokens, so every round fully rejects."""

    name = "regime"

    def __init__(self, refs):
        self.refs = refs  # prompt tuple -> full greedy continuation
        self.accept = False

    def propose(self, write_caps):
        eng = self.engine
        out = np.zeros((eng._slots, self.k), np.int32)
        for s in range(eng._slots):
            req = eng._by_slot[s]
            if req is None or not eng._active[s]:
                continue
            ref = self.refs[tuple(int(t) for t in req.prompt_ids)]
            pos = len(req.output_ids)
            cont = np.asarray(
                (list(ref) + [0] * self.k)[pos:pos + self.k], np.int32)
            out[s] = cont if self.accept else (cont + 1) % 64
        return out


class TestAdaptiveK:
    def test_convergence_shrink_then_regrow(self):
        """Regime change end-to-end on the ragged path: a rejection
        streak walks K down 4 -> 2 -> 1 (multiplicative), an acceptance
        run walks it back 1 -> 2 -> 3 -> 4 (additive) — counters count
        each move, and every emitted token still matches the plain
        engine (depth adaptation is invisible in token space)."""
        m = _tiny_gpt(seed=21)
        p = _prompts(np.random.RandomState(9), (6,))[0]
        ref = _engine(m, max_batch_size=1, max_seq_len=96).generate(
            [p], max_new_tokens=60)[0]
        drafter = _RegimeDrafter({tuple(int(t) for t in p): ref})
        reset_decode_stats()
        eng = _engine(m, max_batch_size=1, max_seq_len=96,
                      spec_decode_k=4, spec_adaptive_k=True,
                      drafter=drafter, ragged_step=True,
                      cost_model=False)
        sd = eng._spec
        assert sd.adaptive and sd.k_min == 1
        req = eng.add_request(p, max_new_tokens=58)
        # reject regime: shrink streaks of 2 halve the depth
        for _ in range(4):  # admit+round, round(4->2), round, round(2->1)
            eng.step()
        assert int(sd.k_slot[0]) == 1
        assert decode_stats()["spec_k_shrinks"] == 2
        assert req.output_ids == ref[:len(req.output_ids)]
        # accept regime: grow streaks of 2 walk the depth back to K
        drafter.accept = True
        for _ in range(6):  # (streak, grow) x3: 1->2->3->4
            eng.step()
        assert int(sd.k_slot[0]) == 4
        st = decode_stats()
        assert st["spec_k_grows"] == 3
        assert st["spec_k_shrinks"] == 2
        assert req.output_ids == ref[:len(req.output_ids)]
        assert len(req.output_ids) > 10
        eng.evict(req)

    @pytest.mark.slow  # tier-1 budget: covered by the fast-lane siblings
    def test_legacy_path_shrinks_too(self):
        """Adaptive K is not ragged-only: the split verify path runs
        the same per-slot controller."""
        m = _tiny_gpt(seed=22)
        p = _prompts(np.random.RandomState(10), (5,))[0]
        ref = _engine(m, max_batch_size=1).generate(
            [p], max_new_tokens=20)[0]
        drafter = _RegimeDrafter({tuple(int(t) for t in p): ref})
        eng = _engine(m, max_batch_size=1, spec_decode_k=4,
                      spec_adaptive_k=True, drafter=drafter,
                      cost_model=False)
        req = eng.add_request(p, max_new_tokens=18)
        for _ in range(4):
            eng.step()
        assert int(eng._spec.k_slot[0]) == 1
        assert req.output_ids == ref[:len(req.output_ids)]
        eng.evict(req)

    @pytest.mark.slow  # tier-1 budget: covered by the fast-lane siblings
    def test_depth_resets_when_slot_changes_hands(self):
        """A learned depth belongs to the request that earned it:
        finish resets the slot to the configured K."""
        m = _tiny_gpt(seed=23)
        p = _prompts(np.random.RandomState(11), (5,))[0]
        ref = _engine(m, max_batch_size=1).generate(
            [p], max_new_tokens=8)[0]
        drafter = _RegimeDrafter({tuple(int(t) for t in p): ref})
        reset_decode_stats()
        eng = _engine(m, max_batch_size=1, spec_decode_k=4,
                      spec_adaptive_k=True, drafter=drafter,
                      cost_model=False)
        out = eng.generate([p], max_new_tokens=8)[0]
        assert out == ref
        assert decode_stats()["spec_k_shrinks"] >= 2
        assert int(eng._spec.k_slot[0]) == 4  # reset at finish

    def test_grow_gate_cost_model(self):
        """`_grow_ok`: no cost model -> allow; a cost model whose
        verify round costs more than the K+1 decode steps it replaces
        -> veto (the streak fires, the depth stays put)."""

        class _FakeCost:
            def __init__(self, v, d):
                self._v, self._d = v, d

            def profile_for(self, kind):
                return self._v if kind == "verify" else self._d

            def raw_seconds(self, p):
                return float(p)

            def calibration_wire(self):
                return {}

        m = _tiny_gpt(seed=24)
        eng = _engine(m, max_batch_size=1, spec_decode_k=4,
                      spec_adaptive_k=True, cost_model=False)
        sd = eng._spec
        assert eng._cost is None and sd._grow_ok()
        # verify 100x the cost of k+1 decodes: growth vetoed
        eng._cost = _FakeCost(v=100.0, d=1.0)
        assert not sd._grow_ok()
        sd.k_slot[0] = 1
        sd._acc_streak[0] = sd._grow_after - 1
        sd._adapt_k(0, m=1, usable=1)
        assert int(sd.k_slot[0]) == 1  # streak fired, gate held
        # cheap verify: growth allowed
        eng._cost = _FakeCost(v=1.0, d=1.0)
        assert sd._grow_ok()
        sd._acc_streak[0] = sd._grow_after - 1
        sd._adapt_k(0, m=1, usable=1)
        assert int(sd.k_slot[0]) == 2

        class _Broken(_FakeCost):
            def profile_for(self, kind):
                raise RuntimeError("no profile")

        eng._cost = _Broken(0, 0)
        assert sd._grow_ok()  # extraction failure -> ungated, not dead

    def test_adaptive_without_spec_refused(self):
        m = _tiny_gpt(seed=25)
        with pytest.raises(ValueError, match="spec_adaptive_k"):
            _engine(m, spec_adaptive_k=True)


# ---------------------------------------------------------------------------
# Generated-page prefix registration (satellite: decode fills the cache)
# ---------------------------------------------------------------------------
class TestGeneratedPagePrefix:
    def _cache_engine(self, m, **kw):
        kw.setdefault("max_batch_size", 2)
        kw.setdefault("max_seq_len", 64)
        kw.setdefault("page_size", 4)
        kw.setdefault("prefix_cache", True)
        # generated-page registration went flag-gated (default off) in
        # the fleet PR; this class exists to pin its on-behavior
        kw.setdefault("cache_generated_pages", True)
        return _engine(m, **kw)

    def test_fanout_hits_generated_pages(self):
        """A fanout prompt extending another request's prompt+OUTPUT
        stream maps the generated full pages from the cache — and the
        continuation is bit-identical to the original stream."""
        m = _tiny_gpt(seed=31)
        p = _prompts(np.random.RandomState(12), (8,))[0]
        eng = self._cache_engine(m)
        out1 = eng.generate([p], max_new_tokens=12)[0]
        eng._debug_check_pool()
        # prompt (2 pages) + out1[:8] (2 GENERATED pages) = 16 tokens;
        # pages 0-2 come from the cache (the last full page stays
        # uncached-by-policy: at least one prompt token must prefill)
        p2 = np.concatenate([p, np.asarray(out1[:8], np.int32)])
        reset_decode_stats()
        out2 = eng.generate([p2], max_new_tokens=4)[0]
        st = decode_stats()
        assert st["prefix_hits"] == 3, st["prefix_hits"]
        assert st["prefix_cached_tokens"] == 12
        assert out2 == out1[8:12]  # cached generated KV is correct
        eng._debug_check_pool()
        eng.pool.assert_consistent(live_pages=[])

    def test_refcounts_consistent_across_boundaries(self):
        """The pool partition (free / private / cached / referenced)
        stays consistent at EVERY page-boundary crossing, with a live
        request pinning pages mid-flight."""
        m = _tiny_gpt(seed=32)
        p = _prompts(np.random.RandomState(13), (6,))[0]
        eng = self._cache_engine(m, max_batch_size=1)
        req = eng.add_request(p, max_new_tokens=14)
        while req.state != "done":
            eng.step()
            eng._debug_check_pool()  # PagePool.assert_consistent
        assert len(req.output_ids) == 14
        eng._debug_check_pool()

    def test_spec_accept_registers_generated_pages(self):
        """The speculative accept loop registers full pages too (multi-
        token emission can cross several boundaries in one round)."""
        m = _tiny_gpt(seed=33)
        p = _prompts(np.random.RandomState(14), (8,))[0]
        eng = self._cache_engine(m, max_batch_size=1, spec_decode_k=3)
        out1 = eng.generate([p], max_new_tokens=12)[0]
        eng._debug_check_pool()
        p2 = np.concatenate([p, np.asarray(out1[:8], np.int32)])
        reset_decode_stats()
        out2 = eng.generate([p2], max_new_tokens=4)[0]
        assert decode_stats()["prefix_hits"] == 3
        assert out2 == out1[8:12]
        eng._debug_check_pool()

    def test_ragged_step_registers_generated_pages(self):
        m = _tiny_gpt(seed=34)
        p = _prompts(np.random.RandomState(15), (8,))[0]
        eng = self._cache_engine(m, max_batch_size=1, ragged_step=True)
        out1 = eng.generate([p], max_new_tokens=12)[0]
        p2 = np.concatenate([p, np.asarray(out1[:8], np.int32)])
        reset_decode_stats()
        out2 = eng.generate([p2], max_new_tokens=4)[0]
        assert decode_stats()["prefix_hits"] == 3
        assert out2 == out1[8:12]
        eng._debug_check_pool()

    @pytest.mark.slow  # tier-1 budget: covered by the fast-lane siblings
    def test_cache_off_is_unchanged(self):
        """prefix_cache=False: no registration, tokens identical."""
        m = _tiny_gpt(seed=35)
        p = _prompts(np.random.RandomState(16), (8,))[0]
        ref = self._cache_engine(m, prefix_cache=False).generate(
            [p], max_new_tokens=12)[0]
        out = self._cache_engine(m).generate([p], max_new_tokens=12)[0]
        assert out == ref


# ---------------------------------------------------------------------------
# Per-executable retrace attribution + loud warm-retrace (sanitize)
# ---------------------------------------------------------------------------
@pytest.fixture
def sanitize_flag():
    from paddle_tpu.analysis import sanitizer
    from paddle_tpu.core import flags as _flags

    prior = bool(_flags.flag("sanitize"))
    paddle.set_flags({"sanitize": True})
    sanitizer.reset()
    yield sanitizer.get()
    paddle.set_flags({"sanitize": prior})
    sanitizer.reset()


class TestRetraceAttribution:
    def test_per_key_counter(self):
        """A warm retrace lands in the aggregate AND the per-executable
        counter named by the tracker's compile_key."""
        from paddle_tpu.inference.serving import _JitTracker

        reset_decode_stats()
        fn = _JitTracker(jax.jit(lambda x: x * 2), "decode_compiles",
                         site="fixture step")
        fn(jnp.ones((2,), jnp.float32))
        fn(jnp.ones((2,), jnp.float32))  # warm
        fn(jnp.ones((2,), jnp.int32))    # dtype flap -> retrace
        st = decode_stats()
        assert st["retraces_after_warmup"] == 1
        assert st["decode_retraces"] == 1
        assert st["ragged_retraces"] == 0

    def test_every_compile_key_has_a_retrace_counter(self):
        """The attribution schema is closed: every `<kind>_compiles`
        counter has its `<kind>_retraces` sibling, so no tracker's warm
        retrace can fall through to the aggregate alone."""
        from paddle_tpu.profiler import DECODE_STAT_COUNTERS

        compiles = [k for k in DECODE_STAT_COUNTERS
                    if k.endswith("_compiles")]
        assert "ragged_compiles" in compiles
        for k in compiles:
            assert k.replace("_compiles", "_retraces") \
                in DECODE_STAT_COUNTERS, k

    def test_ragged_warm_retrace_fails_loudly(self, sanitize_flag):
        """FLAGS_sanitize: a clean ragged serve reports zero warm
        retraces; an operand-width flap on the SAME tracker raises
        WarmRetraceError naming the ragged site."""
        from paddle_tpu.analysis import sanitizer

        m = _tiny_gpt(seed=41)
        p = _prompts(np.random.RandomState(17), (6,))[0]
        eng = _engine(m, max_batch_size=1, ragged_step=True)
        eng.generate([p], max_new_tokens=6)
        assert sanitize_flag.report()["warm_retraces"] == 0
        fn = eng._ragged_fn
        assert fn is not None and fn.compile_key == "ragged_compiles"
        slots = eng._slots
        zeros = jnp.zeros((slots,), jnp.int32)
        bad = jnp.zeros((slots, eng._q_ragged + 1), jnp.int32)
        with pytest.raises(sanitizer.WarmRetraceError,
                           match="ragged step"):
            fn(eng._params, eng._kv,
               jnp.asarray(eng._bt), zeros, bad, zeros,
               jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Static-analysis coverage of the unified executable
# ---------------------------------------------------------------------------
class TestTracecheckCoverage:
    def test_ragged_sites_discovered_with_pool_donation(self):
        """The ragged step is AST-discovered as ONE tracker-owned jit
        site (both storage kinds of the pool run it) donating the pool
        — the DonationPass contract that a function taking ``kv``
        donates it covers the executable for free."""
        from paddle_tpu.analysis import repo_root
        from paddle_tpu.analysis.passes import (collect_jit_sites,
                                                scan_paths)

        mods = scan_paths(["paddle_tpu/inference/serving.py"],
                          repo_root())
        by = {}
        for s in collect_jit_sites(mods):
            by.setdefault(s.fn_name, []).append(s)
        (site,) = by["_gpt_ragged_step"]
        assert site.donate_argnums == (1,)
        assert "_gpt_ragged_step_q" not in by

    def test_serving_stack_scan_clean(self):
        """The touched serving modules carry zero NEW tracecheck
        findings (donation, trace hazards, engine mutation, lock
        discipline) against the shipped (empty) baseline."""
        import os

        from paddle_tpu import analysis as A

        findings = A.run_tracecheck(
            paths=["paddle_tpu/inference/serving.py",
                   "paddle_tpu/inference/speculative.py"])
        base = A.load_baseline(os.path.join(
            A.repo_root(), "tools", "tracecheck_baseline.json"))
        new, _ = A.split_baselined(findings, base)
        assert new == [], [f.message for f in new]

    def test_generated_page_registration_is_sanctioned_mutator(self):
        """The new cache-registration entry point is part of the
        machine-readable engine-mutation spec."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        assert "_register_generated_pages" in REPO_ENGINE_RULE.mutators
