"""Fault-injected serving (inference.resilience): failure containment,
retry/backoff, degradation, and crash recovery over the prefix cache.

Contracts pinned here (ISSUE 9 acceptance):

* under an armed fault plan — every site individually AND combined —
  no request is ever lost: every submitted request finishes with
  eos/length or an explicit "fault" reason, and the KV pool leaks
  nothing;
* the engine-recovery leg (fatal step fault -> `recover` rebuild ->
  replay with generated tokens folded into the prompt) produces
  bit-identical greedy tokens vs the fault-free run;
* with FLAGS_fault_inject OFF, serving is bit-exact vs the
  pre-resilience engine, warm retraces stay 0, and `tracecheck` stays
  clean against the shipped (empty) baseline;
* NaN/inf logit rows quarantine ONLY the offending slot; pool
  exhaustion during admission means "stay queued", never a crash;
* repeated drafter faults degrade speculation off (re-enable probe
  after clean steps), repeated mixed-step faults fall back to the
  legacy prefill oracle path — with parity throughout;
* `TokenStream` surfaces terminal state as ``finish_reason`` + a
  structured `FaultInfo` instead of a bare raised exception
  mid-iteration, and streams survive an engine rebuild without ever
  re-emitting an already-streamed token.
"""
import asyncio

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference import resilience
from paddle_tpu.inference.errors import (DegradedMode, FaultInfo,
                                         InjectedFault, PoolExhausted,
                                         ServingError, StepFault)
from paddle_tpu.inference.frontend import ServingFrontend
from paddle_tpu.inference.resilience import (EngineSnapshot, FaultPlan,
                                             serve_with_recovery)
from paddle_tpu.inference.serving import (DecodeEngine, KVBlockPool,
                                          decode_stats,
                                          reset_decode_stats)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    reset_decode_stats()
    obs.reset()
    obs.clear_spans()
    yield
    reset_decode_stats()
    obs.reset()
    obs.clear_spans()


TINY = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 max_seq_len=256, use_parallel_layers=False, dropout=0.0)

PROMPTS = [[1, 2, 3, 4, 5, 1, 2, 3, 4, 5, 1, 2],
           [7, 8, 9, 7, 8, 9, 7, 8]]
NEW = 16


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    m = GPT(TINY)
    m.eval()
    return m


def _engine(m, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("page_size", 4)
    return DecodeEngine(m, **kw)


@pytest.fixture(scope="module")
def model():
    return _tiny_gpt()


@pytest.fixture(scope="module")
def reference(model):
    """Fault-free greedy outputs — the parity oracle every contained /
    recovered leg must reproduce bit for bit."""
    return _engine(model).generate(PROMPTS, max_new_tokens=NEW)


def _run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _assert_no_loss(reqs, pool=None):
    """The zero-request-loss invariant: every submitted request
    reached a terminal state with an explicit reason, and the pool
    got every page back."""
    for r in reqs:
        assert r.state == "done", (r.request_id, r.state)
        assert r.finish_reason in ("eos", "length", "fault"), \
            (r.request_id, r.finish_reason)
        if r.finish_reason == "fault":
            assert r.fault_info is not None and not r.fault_info.recovered
    if pool is not None:
        assert pool.available_count == pool.num_pages


# ---------------------------------------------------------------------------
# the plan + taxonomy
# ---------------------------------------------------------------------------
class TestFaultPlan:
    def test_parse_grammar(self):
        plan = FaultPlan.parse("step@3,7-9; pool@2 ;poison@55;slow_ms=1.5")
        assert plan.schedule["step"] == frozenset({3, 7, 8, 9})
        assert plan.schedule["pool"] == frozenset({2})
        assert plan.poison_token == 55
        assert plan.slow_ms == 1.5

    def test_parse_empty_is_disarmed(self):
        assert FaultPlan.parse("") is None
        assert FaultPlan.parse("  ") is None

    def test_unknown_site_raises(self):
        with pytest.raises(ValueError, match="unknown fault site"):
            FaultPlan.parse("warp_core@1")
        with pytest.raises(ValueError, match="1-based"):
            FaultPlan({"step": [0]})

    def test_consult_is_occurrence_counted(self):
        plan = FaultPlan({"step": [2]})
        assert [plan.consult("step") for _ in range(3)] == \
            [False, True, False]
        assert plan.consults("step") == 3

    def test_seeded_is_deterministic(self):
        a = FaultPlan.seeded(7, ("step", "pool"), 0.3, 50)
        b = FaultPlan.seeded(7, ("step", "pool"), 0.3, 50)
        assert a.schedule == b.schedule
        assert any(a.schedule.values())  # rate 0.3 over 50: fires

    def test_flag_arms_engine(self, model):
        paddle.set_flags({"fault_inject": "step@1"})
        try:
            eng = _engine(model)
            assert eng._fault is not None
            assert eng._fault.schedule["step"] == frozenset({1})
        finally:
            paddle.set_flags({"fault_inject": ""})
        assert _engine(model)._fault is None


class TestErrorTaxonomy:
    def test_hierarchy(self):
        # pre-taxonomy callers caught RuntimeError: must keep working
        assert issubclass(PoolExhausted, ServingError)
        assert issubclass(StepFault, ServingError)
        assert issubclass(InjectedFault, StepFault)
        assert issubclass(DegradedMode, ServingError)
        assert issubclass(ServingError, RuntimeError)

    def test_pool_raises_typed(self):
        pool = KVBlockPool(1)
        pool.alloc_page()
        with pytest.raises(PoolExhausted, match="exhausted"):
            pool.alloc_page()

    def test_step_fault_fields(self):
        e = StepFault("boom", site="verify", attempts=3, fatal=True)
        assert (e.site, e.attempts, e.fatal) == ("verify", 3, True)
        info = FaultInfo(site="step", attempts=2, recovered=True)
        assert info.as_dict()["recovered"] is True


# ---------------------------------------------------------------------------
# containment: retry, NaN quarantine, bisect, pool
# ---------------------------------------------------------------------------
class TestRetry:
    def test_transient_fault_retried_with_parity(self, model, reference):
        eng = _engine(model, fault_plan="step@2")
        outs = eng.generate(PROMPTS, max_new_tokens=NEW)
        st = decode_stats()
        assert outs == reference
        assert st["step_retries"] == 1
        assert st["faults_injected"] == 1
        assert st["finished_fault"] == 0
        assert st["retraces_after_warmup"] == 0
        _assert_no_loss([], eng.pool)
        snap = obs.snapshot()
        assert snap["paddle_step_retries_total"]["series"][0]["value"] \
            == 1
        # zero-valued series from earlier suites survive obs.reset()
        # by contract (label sets persist) — only live counts matter
        sites = {s["labels"]["site"]: s["value"] for s in
                 snap["paddle_faults_injected_total"]["series"]
                 if s["value"]}
        assert sites == {"step": 1}

    def test_backoff_ticks_capped_exponential(self, model):
        paddle.set_flags({"step_retries": 6})
        try:
            eng = _engine(model, fault_plan="step@2-7")
            eng.generate(PROMPTS, max_new_tokens=NEW)
            # attempts 1..6 -> ticks 1,2,4,8,8,8 (capped at 8)
            assert eng._resilience.backoff_ticks == 31
        finally:
            paddle.set_flags({"step_retries": 2})


class TestNaNQuarantine:
    def test_only_offending_slot_dies(self, model, reference):
        eng = _engine(model, fault_plan="nan_logits@3")
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        eng.run()
        reasons = [r.finish_reason for r in reqs]
        assert reasons.count("fault") == 1
        assert reasons.count("length") == 1
        survivor = reqs[reasons.index("length")]
        assert list(survivor.generated_ids) == \
            reference[reasons.index("length")]
        victim = reqs[reasons.index("fault")]
        assert victim.fault_info.site == "nan_logits"
        assert victim.fault_info.recovered is False
        _assert_no_loss(reqs, eng.pool)
        st = decode_stats()
        assert st["finished_fault"] == 1
        snap = obs.snapshot()
        finished = {s["labels"]["reason"]: s["value"] for s in
                    snap["paddle_requests_finished_total"]["series"]}
        assert finished.get("fault") == 1

    def test_nan_during_prefill_never_registers_pages(self, model):
        """First-token NaN: the slot quarantines BEFORE its prompt
        pages enter the prefix cache — poisoned K/V must never be
        reusable."""
        eng = _engine(model, fault_plan="nan_logits@1")
        r = eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        eng.run()
        assert r.finish_reason == "fault"
        assert r.output_ids == []
        assert eng.pool.cached_count == 0
        _assert_no_loss([r], eng.pool)

    def test_nan_in_spec_verify_quarantines_slot(self, model, reference):
        eng = _engine(model, spec_decode_k=3, fault_plan="nan_logits@3")
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        eng.run()
        reasons = [r.finish_reason for r in reqs]
        assert reasons.count("fault") == 1 and reasons.count("length") == 1
        survivor = reqs[reasons.index("length")]
        assert list(survivor.generated_ids) == \
            reference[reasons.index("length")]
        _assert_no_loss(reqs, eng.pool)


class TestBisectQuarantine:
    def test_poisoned_request_isolated(self, model, reference):
        """The batch-content fault: the step fails while the poisoned
        request is in the batch.  Bisection (retry without the newest
        admits first) must quarantine exactly it; the innocent request
        finishes with full parity."""
        eng = _engine(model, fault_plan="poison@55")
        good = eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        bad = eng.add_request([55] + PROMPTS[1], max_new_tokens=NEW)
        eng.run()
        assert bad.finish_reason == "fault"
        assert bad.fault_info is not None and bad.fault_info.attempts > 0
        assert good.finish_reason == "length"
        assert list(good.generated_ids) == reference[0]
        st = decode_stats()
        assert st["finished_fault"] == 1
        assert st["step_retries"] >= 1
        # the innocent was preempted during bisection and resumed
        assert st["preemptions"] >= 1
        _assert_no_loss([good, bad], eng.pool)
        # spans are (track, name, start, dur, tid, args) tuples
        spans = [s for s in obs.spans() if s[1] == "quarantine"]
        assert spans and spans[-1][5]["request"] == bad.request_id

    def test_poison_arriving_late_still_isolated(self, model, reference):
        """The poisoned request admits mid-serve: the healthy batch
        keeps its tokens, the suspect is quarantined on arrival's
        first faulty step."""
        eng = _engine(model, fault_plan="poison@55")
        good = eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        for _ in range(4):
            eng.step()
        bad = eng.add_request([55, 3, 1], max_new_tokens=NEW)
        eng.run()
        assert bad.finish_reason == "fault"
        assert good.finish_reason == "length"
        assert list(good.generated_ids) == reference[0]
        _assert_no_loss([good, bad], eng.pool)


class TestPoolExhaustion:
    def test_injected_admission_exhaustion_stays_queued(self, model,
                                                        reference):
        """PoolExhausted during admission = backpressure: the request
        stays queued (no crash, no fault verdict) and admits once the
        fault clears."""
        eng = _engine(model, fault_plan="pool@1-2")
        outs = eng.generate(PROMPTS, max_new_tokens=NEW)
        assert outs == reference
        assert decode_stats()["finished_fault"] == 0
        _assert_no_loss([], eng.pool)

    def test_unwound_admission_leaves_pool_consistent(self, model):
        eng = _engine(model, fault_plan="pool@1")
        r = eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        eng.step()  # admission hits the injected exhaustion
        assert r.state in ("queued", "running")
        eng.pool.assert_consistent(
            live_pages=[p for q in eng._by_slot if q is not None
                        for p in q.pages])
        assert r.t_admit_ns is None or r.state == "running"
        eng.run()
        assert r.finish_reason == "length"
        assert decode_stats()["resumes"] == 0  # unwind is not a resume

    def test_mid_step_exhaustion_contained(self, model, reference):
        """PoolExhausted inside the step (block-table growth) rides
        the containment ladder instead of killing the batch."""
        eng = _engine(model, fault_plan="pool@3-4")
        outs = eng.generate(PROMPTS, max_new_tokens=NEW)
        assert outs == reference
        assert decode_stats()["step_retries"] >= 1
        _assert_no_loss([], eng.pool)


# ---------------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------------
class TestDegradation:
    def test_drafter_faults_disable_spec_then_probe(self, model,
                                                    reference):
        paddle.set_flags({"degraded_probe_steps": 6})
        try:
            eng = _engine(model, spec_decode_k=3,
                          fault_plan="drafter@1-3")
            outs = eng.generate(PROMPTS, max_new_tokens=NEW)
            assert outs == reference  # contained rounds stay exact
            st = decode_stats()
            assert st["spec_disables"] == 1
            assert st["finished_fault"] == 0
            # serve more work: the probe (FLAGS_degraded_probe_steps
            # clean steps) re-enables speculation, schedule exhausted
            outs2 = eng.generate(PROMPTS, max_new_tokens=NEW)
            assert outs2 == reference
            assert not eng._resilience.spec_disabled
            snap = obs.snapshot()
            modes = {s["labels"]["mode"]: s["value"] for s in
                     snap["paddle_degraded_mode"]["series"]}
            assert modes.get("spec_off") == 0  # probed back on
        finally:
            paddle.set_flags({"degraded_probe_steps": 16})

    def test_stateful_drafter_stays_degraded(self, model):
        """A stateful drafter (per-slot draft K/V cursors) cannot be
        probed back on mid-serve — its state went stale while spec was
        off."""
        from paddle_tpu.inference.speculative import PromptLookupDrafter

        class StatefulLookup(PromptLookupDrafter):
            stateful = True

        eng = _engine(model, spec_decode_k=3, drafter=StatefulLookup(),
                      fault_plan="drafter@1-3")
        eng.generate(PROMPTS, max_new_tokens=NEW)
        eng.generate(PROMPTS, max_new_tokens=NEW)  # plenty of clean steps
        assert eng._resilience.spec_disabled  # never re-enabled

    def test_mixed_faults_fall_back_to_legacy_prefill(self, model,
                                                      reference):
        paddle.set_flags({"degraded_probe_steps": 6})
        try:
            eng = _engine(model, fault_plan="mixed_step@1-9")
            outs = eng.generate(PROMPTS, max_new_tokens=NEW)
            assert outs == reference
            st = decode_stats()
            assert st["legacy_fallbacks"] == 1
            assert st["prefill_compiles"] >= 1  # legacy path really ran
            assert st["finished_fault"] == 0
            # probe restores chunked mode + the prefix cache
            outs2 = eng.generate(PROMPTS, max_new_tokens=NEW)
            assert outs2 == reference
            assert eng._chunked and eng._prefix_cache
            assert not eng._resilience.legacy_mode
        finally:
            paddle.set_flags({"degraded_probe_steps": 16})

    def test_verify_faults_degrade_spec(self, model, reference):
        eng = _engine(model, spec_decode_k=3, fault_plan="verify@1-9")
        outs = eng.generate(PROMPTS, max_new_tokens=NEW)
        assert outs == reference
        st = decode_stats()
        assert st["spec_disables"] >= 1
        assert st["step_retries"] >= 1


# ---------------------------------------------------------------------------
# crash recovery
# ---------------------------------------------------------------------------
class TestRecovery:
    def test_snapshot_captures_inflight_state(self, model):
        eng = _engine(model)
        r1 = eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        r2 = eng.add_request(PROMPTS[1], max_new_tokens=NEW)
        for _ in range(6):
            eng.step()
        snap = EngineSnapshot(eng)
        assert len(snap) == 2
        # the last step landed: the one in flight is dispatched again
        assert eng._inflight is not None
        assert snap.step_no == eng._step_no - 1
        rec = {id(x.request): x for x in snap.records}
        assert rec[id(r1)].output_ids == list(r1.output_ids)
        assert rec[id(r2)].max_new == NEW

    def test_recovery_is_greedy_bit_identical(self, model, reference):
        """THE acceptance leg: a fatal step fault mid-serve, engine
        rebuilt, every in-flight request re-admitted with its
        generated tokens folded into the replay prompt — final greedy
        outputs bit-identical to the fault-free run, nothing lost."""
        eng = _engine(model, fault_plan="step@4-10")
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        eng2, recoveries = serve_with_recovery(eng)
        assert recoveries >= 1
        assert [list(r.generated_ids) for r in reqs] == reference
        _assert_no_loss(reqs, eng2.pool)
        for r in reqs:
            assert r.finish_reason == "length"
            assert r.fault_info is not None and r.fault_info.recovered
        st = decode_stats()
        assert st["recoveries"] == recoveries
        assert st["retraces_after_warmup"] == 0
        snap = obs.snapshot()
        assert snap["paddle_recoveries_total"]["series"][0]["value"] == \
            recoveries
        assert any(s[1] == "recovery" for s in obs.spans())

    def test_recovery_rides_prefix_cache(self, model):
        """Two recovered requests sharing a long prompt prefix: the
        first replay registers its pages, the second maps them — the
        recovery path really does ride the content-addressed cache."""
        shared = [3, 1, 4, 1, 5, 9, 2, 6] * 3
        prompts = [shared + [11], shared + [12]]
        # one slot: the serve is serial, so the second request's probe
        # runs AFTER the first replay registered the shared pages
        ref = _engine(model, max_batch_size=1).generate(
            prompts, max_new_tokens=8)
        reset_decode_stats()
        # the burst ends AT the fatal fault: a burst outlasting the
        # rebuild would (correctly) degrade the recovered engine to
        # legacy prefill, which turns the prefix cache off
        eng = _engine(model, max_batch_size=1, fault_plan="step@6-9")
        reqs = [eng.add_request(p, max_new_tokens=8) for p in prompts]
        eng2, recoveries = serve_with_recovery(eng, max_recoveries=8)
        assert recoveries >= 1
        assert [list(r.generated_ids) for r in reqs] == ref
        assert decode_stats()["prefix_hits"] >= 1

    def test_recovery_budget_exhausts_to_degraded_mode(self, model):
        eng = _engine(model, fault_plan="step@2-500")
        eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        with pytest.raises(DegradedMode, match="recovery budget"):
            serve_with_recovery(eng, max_recoveries=1)

    def test_recovery_preserves_rng_counters(self, model):
        eng = _engine(model)
        eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        for _ in range(5):
            eng.step()
        new = resilience.recover(eng)
        # the rebuilt engine dispatches the step left in flight again,
        # under the same number (the same key)
        assert eng._inflight is not None
        assert new._step_no == eng._step_no - 1
        assert new is not eng and new.pool is not eng.pool

    def test_recovery_with_spec_engine(self, model, reference):
        # burst long enough that the ladder (retries -> spec off ->
        # legacy -> bisect) exhausts into a fatal fault, short enough
        # that the rebuilt engine clears it within its retry budget
        eng = _engine(model, spec_decode_k=3, fault_plan="step@4-16")
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        eng2, recoveries = serve_with_recovery(eng, max_recoveries=8)
        assert recoveries >= 1
        assert [list(r.generated_ids) for r in reqs] == reference
        _assert_no_loss(reqs, eng2.pool)


# ---------------------------------------------------------------------------
# frontend: streams across recovery, structured terminal state
# ---------------------------------------------------------------------------
class TestFrontendRecovery:
    def test_streams_survive_engine_rebuild(self, model, reference):
        """The driver supervises the worker: a fatal fault rebuilds
        the engine and the SAME TokenStreams keep producing — with no
        token ever re-emitted (streamed == generated == fault-free
        reference)."""
        async def go():
            eng = _engine(model, fault_plan="step@3-9")
            async with ServingFrontend(eng, step_in_thread=False) as fe:
                s1 = await fe.submit(PROMPTS[0], max_new_tokens=NEW)
                s2 = await fe.submit(PROMPTS[1], max_new_tokens=NEW)
                t1, t2 = await s1.collect(), await s2.collect()
            return fe, s1, s2, t1, t2

        fe, s1, s2, t1, t2 = _run(go())
        assert fe._recoveries >= 1
        assert fe.engine is not None
        assert [t1, t2] == reference
        assert s1.finish_reason == "length"
        assert s1.fault_info is not None and s1.fault_info.recovered

    def test_dead_driver_surfaces_structured_fault(self, model):
        """Recovery budget exhausted: streams END (no mid-iteration
        raise) with finish_reason="fault" + FaultInfo; the driver's
        exception re-raises on close()."""
        async def go():
            eng = _engine(model, fault_plan="step@3-500")
            fe = ServingFrontend(eng, step_in_thread=False,
                                 max_recoveries=1)
            await fe.start()
            s = await fe.submit(PROMPTS[0], max_new_tokens=NEW)
            toks = await s.collect()  # ends cleanly, never raises
            err = None
            try:
                await fe.close(drain=False)
            except StepFault as e:
                err = e
            return s, toks, err

        s, toks, err = _run(go())
        assert s.finish_reason == "fault"
        assert s.fault_info is not None
        assert s.fault_info.recovered is False
        assert isinstance(err, StepFault) and err.fatal

    def test_host_callback_fault_contained(self, model, reference):
        """A raising on_token callback is dropped, not propagated:
        generation completes in full, the request records the fault."""
        got = []

        def cb(t):
            got.append(t)

        eng = _engine(model, fault_plan="host_callback@3")
        r = eng.add_request(PROMPTS[0], max_new_tokens=NEW, on_token=cb)
        eng.add_request(PROMPTS[1], max_new_tokens=NEW)
        eng.run()
        assert r.finish_reason == "length"
        assert list(r.generated_ids) == reference[0]
        assert len(got) < NEW  # stream went quiet after the drop
        assert r.fault_info.site == "host_callback"
        assert r.fault_info.recovered is True


# ---------------------------------------------------------------------------
# the acceptance sweep: every site, individually and combined
# ---------------------------------------------------------------------------
class TestNoRequestLost:
    SITE_PLANS = {
        "step": "step@2",
        "mixed_step": "mixed_step@1-9",
        "decode_step": "decode_step@5-6",
        "pool": "pool@1-3",
        "nan_logits": "nan_logits@2",
        "slow_step": "slow_step@2;slow_ms=0.5",
        "host_callback": "host_callback@2",
        "poison": "poison@55",
    }

    @pytest.mark.parametrize("site", sorted(SITE_PLANS))
    def test_single_site_no_loss(self, model, site):
        eng = _engine(model, fault_plan=self.SITE_PLANS[site])
        prompts = list(PROMPTS) + [[55, 2, 4]]  # one poison candidate
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in prompts]
        eng2, _ = serve_with_recovery(eng)
        _assert_no_loss(reqs, eng2.pool)

    @pytest.mark.parametrize("site", ["drafter", "verify"])
    def test_spec_sites_no_loss(self, model, site):
        eng = _engine(model, spec_decode_k=3,
                      fault_plan=f"{site}@1-8")
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        eng2, _ = serve_with_recovery(eng)
        _assert_no_loss(reqs, eng2.pool)

    def test_combined_storm_no_loss(self, model):
        """Every site armed at once over a multi-wave workload — the
        combined acceptance leg: nothing lost, pool clean, every
        terminal state explicit."""
        plan = FaultPlan.parse(
            "step@3;mixed_step@5;decode_step@9;pool@2,6;nan_logits@4;"
            "slow_step@7;host_callback@3;poison@55;slow_ms=0.5")
        eng = _engine(model, fault_plan=plan)
        prompts = list(PROMPTS) + [[55, 2, 4], [9, 9, 1, 1, 2]]
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in prompts]
        eng2, _ = serve_with_recovery(eng)
        _assert_no_loss(reqs, eng2.pool)
        st = decode_stats()
        assert st["faults_injected"] >= 5

    def test_combined_storm_seeded(self, model):
        plan = FaultPlan.seeded(11, ("step", "pool", "nan_logits"),
                                rate=0.08, horizon=120)
        eng = _engine(model, fault_plan=plan)
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        eng2, _ = serve_with_recovery(eng, max_recoveries=8)
        _assert_no_loss(reqs, eng2.pool)


# ---------------------------------------------------------------------------
# the disarmed contract: bit-exact, zero overhead observable
# ---------------------------------------------------------------------------
class TestDisarmedBitExact:
    def test_off_is_bit_exact_with_zero_retraces(self, model, reference):
        """FLAGS_fault_inject off: serving is bit-exact vs the
        pre-resilience engine (the reference fixture), zero warm
        retraces, zero fault/retry/recovery counters."""
        eng = _engine(model)
        assert eng._fault is None
        outs = eng.generate(PROMPTS, max_new_tokens=NEW)
        assert outs == reference
        st = decode_stats()
        assert st["retraces_after_warmup"] == 0
        assert st["faults_injected"] == 0
        assert st["step_retries"] == 0
        assert st["finished_fault"] == 0
        assert st["recoveries"] == 0
        assert st["spec_disables"] == 0
        assert st["legacy_fallbacks"] == 0

    def test_off_spec_and_slo_paths_bit_exact(self, model, reference):
        outs = _engine(model, spec_decode_k=3).generate(
            PROMPTS, max_new_tokens=NEW)
        assert outs == reference
        outs = _engine(model, scheduler="slo").generate(
            PROMPTS, max_new_tokens=NEW)
        assert outs == reference

    def test_tracecheck_stays_clean(self):
        """The resilience/recovery code paths scan clean against the
        shipped (EMPTY) baseline — recovery's engine mutation is
        sanctioned in the spec, not grandfathered."""
        from paddle_tpu.analysis import run_tracecheck

        assert run_tracecheck() == []
