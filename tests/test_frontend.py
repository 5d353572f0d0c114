"""SLO-aware serving front-end (inference.frontend): pluggable
admission schedulers + the asyncio streaming entry point.

Contracts pinned here (ISSUE 7 acceptance):

* with scheduling off (FIFO, the default) the engine is BIT-EXACT vs
  the pre-scheduler greedy path and warm retraces stay 0 — and the SLO
  scheduler adds ZERO new executables (scheduling is host-side);
* deadline expiry retires still-queued requests without ever taking a
  slot (``finish_reason="deadline"``), priority orders admission under
  slot exhaustion, head-of-line skip admits smaller requests past a
  capacity-blocked head but its anti-starvation fence bounds the skips;
* preempt -> resume is greedy-output-equivalent: the resumed request's
  final tokens match the never-preempted run (replay rides the prefix
  cache) and the pool leaks nothing;
* ``Request.cancel()`` is uniform across queued/running, with
  "cancelled" staying distinct from "evicted" in finish reasons and
  finished-counter labels;
* `ServingFrontend.submit()` streams per token, an interactive
  request's first token lands before any batch request completes under
  overload, cancellation mid-stream frees the slot and pages, stream
  backpressure bounds the buffer, and close(drain=True) serves every
  outstanding request.
"""
import asyncio
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import now_ns as _obs_now_ns
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (DecodeEngine, PRIORITY_BATCH,
                                          PRIORITY_INTERACTIVE, Request,
                                          decode_stats,
                                          reset_decode_stats)
from paddle_tpu.inference.frontend import (FIFOScheduler, Scheduler,
                                           SLOScheduler, ServingFrontend,
                                           make_scheduler)


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


def _landed_step(eng):
    eng.step()
    eng.drain()


def _prompt(rng, n=8):
    return rng.randint(0, TINY.vocab_size, (n,)).astype(np.int32)


def _counter_value(snap, name, **labels):
    for row in snap.get(name, {}).get("series", []):
        if all(row["labels"].get(k) == str(v)
               for k, v in labels.items()):
            return row["value"]
    return 0


def _run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# ---------------------------------------------------------------------------
# scheduler plumbing
# ---------------------------------------------------------------------------
class TestSchedulerPlumbing:
    def test_default_is_fifo_and_flag_resolution(self):
        m = _tiny_gpt()
        eng = _engine(m)
        assert isinstance(eng._scheduler, FIFOScheduler)
        eng2 = _engine(m, scheduler="slo")
        assert isinstance(eng2._scheduler, SLOScheduler)
        sched = SLOScheduler(hol_skip_limit=1)
        eng3 = _engine(m, scheduler=sched)
        assert eng3._scheduler is sched

    def test_make_scheduler_rejects_unknown(self):
        with pytest.raises(ValueError, match="unknown scheduler"):
            make_scheduler("lifo")

    def test_rebind_refused(self):
        m = _tiny_gpt()
        sched = SLOScheduler()
        _engine(m, scheduler=sched)
        with pytest.raises(ValueError, match="already bound"):
            _engine(m, scheduler=sched)

    def test_base_scheduler_is_abstract(self):
        s = Scheduler()
        with pytest.raises(NotImplementedError):
            s.schedule()

    def test_request_validation(self):
        with pytest.raises(ValueError, match="deadline_ms"):
            Request(np.arange(4), deadline_ms=0)
        with pytest.raises(ValueError, match="hol_skip_limit"):
            SLOScheduler(hol_skip_limit=-1)
        with pytest.raises(ValueError, match="preempt_min_output"):
            SLOScheduler(preempt_min_output=0)
        r = Request(np.arange(4), priority=None)
        assert r.priority == PRIORITY_BATCH


# ---------------------------------------------------------------------------
# FIFO parity: scheduling off == pre-scheduler engine, zero new
# executables either way
# ---------------------------------------------------------------------------
class TestParity:
    def test_fifo_vs_slo_greedy_parity_and_no_new_executables(self):
        m = _tiny_gpt(seed=3)
        rng = np.random.RandomState(7)
        prompts = [_prompt(rng, 6 + i) for i in range(4)]
        eng_f = _engine(m)
        outs_f = eng_f.generate(prompts, max_new_tokens=8)
        st_f = decode_stats(reset=True)
        eng_s = _engine(m, scheduler="slo")
        outs_s = eng_s.generate(prompts, max_new_tokens=8)
        st_s = decode_stats()
        assert outs_f == outs_s  # greedy tokens don't depend on order
        for k in ("mixed_compiles", "decode_compiles",
                  "prefill_compiles"):
            assert st_s[k] == st_f[k], k  # zero NEW executables
        assert st_f["retraces_after_warmup"] == 0
        assert st_s["retraces_after_warmup"] == 0
        assert st_s["preemptions"] == 0  # no pressure -> no preemption


# ---------------------------------------------------------------------------
# deadline expiry
# ---------------------------------------------------------------------------
class TestDeadline:
    def test_queued_expiry_never_takes_a_slot(self):
        m = _tiny_gpt(seed=4)
        rng = np.random.RandomState(0)
        eng = _engine(m, max_batch_size=1, scheduler="slo")
        busy = eng.add_request(_prompt(rng), max_new_tokens=6)
        doomed = eng.add_request(_prompt(rng), max_new_tokens=6,
                                 deadline_ms=0.01)
        time.sleep(0.001)  # > 0.01 ms: the deadline is already gone
        eng.run()
        assert busy.finish_reason == "length"
        assert doomed.finish_reason == "deadline"
        assert doomed.t_admit_ns is None  # no slot ever taken
        assert doomed.output_ids == []
        assert not doomed.slo_met
        st = decode_stats()
        assert st["deadline_expired"] == 1
        snap = obs.snapshot()
        assert _counter_value(
            snap, "paddle_sched_deadline_expired_total") == 1
        assert _counter_value(snap, "paddle_requests_finished_total",
                              reason="deadline") == 1
        assert eng.pool.available_count == eng.pool.num_pages

    def test_fifo_never_expires(self):
        m = _tiny_gpt(seed=4)
        rng = np.random.RandomState(0)
        eng = _engine(m, max_batch_size=1)  # fifo
        eng.add_request(_prompt(rng), max_new_tokens=4)
        late = eng.add_request(_prompt(rng), max_new_tokens=4,
                               deadline_ms=0.01)
        time.sleep(0.001)
        eng.run()
        # FIFO ignores deadlines at admission; the miss is recorded as
        # a violation at finish instead of an expiry
        assert late.finish_reason == "length"
        assert "deadline" in late.slo_violations
        assert not late.slo_met

    def test_resumed_request_is_exempt_from_expiry(self):
        # a preempted request already held a slot: it must resume, not
        # expire, even if its deadline lapsed while re-queued
        m = _tiny_gpt(seed=5)
        rng = np.random.RandomState(1)
        eng = _engine(m, max_batch_size=1, scheduler="slo")
        # 5 ms: admission happens within microseconds of enqueue (the
        # first step's expiry sweep runs before the deadline), but the
        # deadline is long gone by the time the preempted victim is
        # re-queued (the first step compiles the mixed executable)
        victim = eng.add_request(_prompt(rng), max_new_tokens=12,
                                 deadline_ms=5.0)
        for _ in range(6):
            eng.step()
        assert victim.state == "running" and victim.output_ids
        assert (_obs_now_ns() - victim.t_enqueue_ns) / 1e6 > 5.0
        urgent = eng.add_request(_prompt(rng), max_new_tokens=2,
                                 priority=PRIORITY_INTERACTIVE)
        eng.run()
        assert urgent.finish_reason == "length"
        assert victim.preemptions == 1
        assert victim.finish_reason == "length"  # resumed, not expired
        assert "deadline" in victim.slo_violations


# ---------------------------------------------------------------------------
# priority ordering under slot exhaustion
# ---------------------------------------------------------------------------
class TestPriorityOrdering:
    """Admission order, one landed step a call (`step(); drain()`: the
    synchronous schedule), so a finish and the next admission fall in
    separate calls as the assertions read them."""

    def test_interactive_admitted_before_earlier_batch(self):
        m = _tiny_gpt(seed=6)
        rng = np.random.RandomState(2)
        eng = _engine(m, max_batch_size=1, scheduler="slo")
        # the runner is interactive too, so the later candidates can
        # only WAIT (preemption needs a strictly less urgent victim)
        runner = eng.add_request(_prompt(rng), max_new_tokens=6,
                                 priority=PRIORITY_INTERACTIVE)
        batch = eng.add_request(_prompt(rng), max_new_tokens=4)
        inter = eng.add_request(_prompt(rng), max_new_tokens=4,
                                priority=PRIORITY_INTERACTIVE)
        while runner.state != "done":
            _landed_step(eng)
        assert batch.state == "queued" and inter.state == "queued"
        _landed_step(eng)  # one admission: priority beats arrival order
        assert inter.state == "running"
        assert batch.state == "queued"
        eng.run()
        assert batch.finish_reason == "length"

    def test_edf_inside_a_class(self):
        m = _tiny_gpt(seed=6)
        rng = np.random.RandomState(3)
        eng = _engine(m, max_batch_size=1, scheduler="slo")
        # the runner is interactive so it admits first; the two batch-
        # class candidates then compete on deadline alone (the no-
        # deadline case sorts last inside a class)
        runner = eng.add_request(_prompt(rng), max_new_tokens=6,
                                 priority=PRIORITY_INTERACTIVE)
        none = eng.add_request(_prompt(rng), max_new_tokens=4)
        loose = eng.add_request(_prompt(rng), max_new_tokens=4,
                                deadline_ms=60_000.0)
        tight = eng.add_request(_prompt(rng), max_new_tokens=4,
                                deadline_ms=30_000.0)
        while runner.state != "done":
            _landed_step(eng)
            assert loose.state == "queued" and tight.state == "queued"
        _landed_step(eng)
        assert tight.state == "running"  # earliest deadline first
        assert loose.state == "queued" and none.state == "queued"
        while tight.state != "done":
            _landed_step(eng)
        _landed_step(eng)
        assert loose.state == "running"  # deadline beats no-deadline
        assert none.state == "queued"
        eng.run()
        assert none.finish_reason == "length"


# ---------------------------------------------------------------------------
# preempt -> resume
# ---------------------------------------------------------------------------
class TestPreemptResume:
    def test_resume_matches_never_preempted_run(self):
        m = _tiny_gpt(seed=7)
        rng = np.random.RandomState(4)
        prompt = _prompt(rng, 10)
        ref = _engine(m, max_batch_size=1).generate(
            [prompt], max_new_tokens=20)[0]

        eng = _engine(m, max_batch_size=1, scheduler="slo")
        victim = eng.add_request(prompt, max_new_tokens=20)
        for _ in range(8):
            eng.step()
        assert victim.state == "running" and len(victim.output_ids) >= 2
        urgent = eng.add_request(_prompt(rng, 6), max_new_tokens=3,
                                 priority=PRIORITY_INTERACTIVE)
        eng.step()
        assert victim.state == "queued" and victim.preemptions == 1
        assert urgent.state == "running"
        eng.run()
        st = decode_stats()
        assert st["preemptions"] == 1 and st["resumes"] == 1
        assert st["retraces_after_warmup"] == 0
        assert victim.finish_reason == "length"
        # the whole point: preemption is invisible in the tokens
        assert victim.generated_ids == ref
        assert len(victim.output_ids) < len(victim.generated_ids)
        # resume rode the prefix cache: the replay mapped cached pages
        assert st["prefix_hits"] >= 1
        assert eng.pool.available_count == eng.pool.num_pages
        snap = obs.snapshot()
        assert _counter_value(snap,
                              "paddle_sched_preemptions_total") == 1

    def test_no_preemption_when_it_cannot_admit_the_candidate(self):
        # feasibility gate: when even preempting EVERY eligible victim
        # could not free enough pages for the candidate, nobody is
        # preempted — evicting for zero gain would thrash (victims
        # resume, emit a token, get preempted again, every step)
        m = _tiny_gpt(seed=9)
        rng = np.random.RandomState(8)
        eng = _engine(m, scheduler="slo", num_pages=16,
                      max_seq_len=48)
        # A (interactive, never a victim) pins 10 pages; B (batch, the
        # only eligible victim) holds 4 — freeing B leaves 2+4=6 < 7
        a = eng.add_request(_prompt(rng), max_new_tokens=30,
                            priority=PRIORITY_INTERACTIVE)
        b = eng.add_request(_prompt(rng), max_new_tokens=6)
        for _ in range(4):
            eng.step()
        assert a.state == "running" and b.state == "running"
        assert len(b.output_ids) >= 1  # B is an eligible victim
        # candidate needs 7 pages (8 prompt + 17 new -> 25 KV tokens)
        c = eng.add_request(_prompt(rng), max_new_tokens=18,
                            priority=PRIORITY_INTERACTIVE)
        eng.step()
        assert b.state == "running"  # NOT preempted: gate held
        eng.run()
        assert decode_stats()["preemptions"] == 0
        assert c.finish_reason == "length"  # admitted once A freed

    def test_legacy_prefill_resume_keeps_ttft_and_tokens(self):
        # the non-chunked one-shot prefill path must honor the same
        # stamp-TTFT-once contract as _on_first_token: a resume's
        # replay prefill is mid-generation, not a first token (it
        # restamped + double-observed before the fix)
        m = _tiny_gpt(seed=9)
        rng = np.random.RandomState(7)
        prompt = _prompt(rng, 10)
        ref = _engine(m, max_batch_size=1,
                      chunked_prefill=False).generate(
            [prompt], max_new_tokens=12)[0]

        eng = _engine(m, max_batch_size=1, chunked_prefill=False)
        req = eng.add_request(prompt, max_new_tokens=12)
        eng.step()
        assert req.state == "running"
        t_first = req.t_first_token_ns
        assert t_first is not None
        ttft_count = obs.REQUEST_TTFT.series_state()["count"]
        eng.preempt(req)
        eng.run()
        assert req.t_first_token_ns == t_first
        assert obs.REQUEST_TTFT.series_state()["count"] == ttft_count
        assert req.generated_ids == ref
        assert decode_stats()["resumes"] == 1

    def test_no_preemption_without_better_priority(self):
        m = _tiny_gpt(seed=7)
        rng = np.random.RandomState(5)
        eng = _engine(m, max_batch_size=1, scheduler="slo")
        runner = eng.add_request(_prompt(rng), max_new_tokens=10)
        for _ in range(6):
            eng.step()
        eng.add_request(_prompt(rng), max_new_tokens=2)  # same class
        eng.run()
        assert decode_stats()["preemptions"] == 0
        assert runner.finish_reason == "length"

    def test_streaming_sees_each_token_once_across_preemption(self):
        m = _tiny_gpt(seed=8)
        rng = np.random.RandomState(6)
        eng = _engine(m, max_batch_size=1, scheduler="slo")
        seen = []
        victim = eng.add_request(_prompt(rng), max_new_tokens=16,
                                 on_token=seen.append)
        for _ in range(8):
            eng.step()
        eng.add_request(_prompt(rng, 6), max_new_tokens=2,
                        priority=PRIORITY_INTERACTIVE)
        eng.run()
        assert victim.preemptions == 1
        assert seen == victim.generated_ids  # no replays, no gaps

    def test_spec_decode_composes_with_preemption(self):
        m = _tiny_gpt(seed=9)
        rng = np.random.RandomState(7)
        base = _prompt(rng, 6)
        prompt = np.concatenate([base, base])  # repetitive: drafts hit
        ref = _engine(m, max_batch_size=1).generate(
            [prompt], max_new_tokens=24)[0]
        eng = _engine(m, max_batch_size=1, scheduler="slo",
                      spec_decode_k=2)
        victim = eng.add_request(prompt, max_new_tokens=24)
        for _ in range(4):  # spec emits up to K+1/step: stay mid-flight
            eng.step()
        assert victim.state == "running" and victim.output_ids
        eng.add_request(_prompt(rng, 4), max_new_tokens=2,
                        priority=PRIORITY_INTERACTIVE)
        eng.run()
        st = decode_stats()
        assert st["preemptions"] == 1
        assert st["retraces_after_warmup"] == 0
        assert victim.generated_ids == ref
        assert eng.pool.available_count == eng.pool.num_pages


# ---------------------------------------------------------------------------
# head-of-line skip + anti-starvation fence
# ---------------------------------------------------------------------------
class TestHeadOfLine:
    def _pressure_engine(self, m):
        # pool sized so a long request at the queue head cannot be
        # seen through while the runner holds its reservation, but
        # short requests still fit
        return DecodeEngine(m, max_batch_size=2, max_seq_len=48,
                            page_size=PAGE, num_pages=10,
                            prefill_chunk_tokens=8,
                            scheduler=SLOScheduler(hol_skip_limit=2))

    def test_skip_admits_smaller_then_fence_stops_starvation(self):
        m = _tiny_gpt(seed=10)
        rng = np.random.RandomState(8)
        eng = self._pressure_engine(m)
        runner = eng.add_request(_prompt(rng, 4), max_new_tokens=13)
        eng.step()  # runner holds ceil(16/4)=4 pages of 10
        # big needs ceil((8+20-1)/4)=7 pages > 6 available -> blocked
        big = eng.add_request(_prompt(rng, 8), max_new_tokens=20)
        smalls = [eng.add_request(_prompt(rng, 4), max_new_tokens=2)
                  for _ in range(4)]
        for _ in range(3):
            eng.step()
        # head-of-line skip let smaller requests past the blocked big
        assert big.state == "queued"
        assert any(s.state != "queued" for s in smalls)
        eng.run()
        # the fence kept big from starving: it finished, and at most
        # hol_skip_limit smalls ever jumped it
        assert big.finish_reason == "length"
        assert big._hol_skips <= 2
        assert all(s.finish_reason == "length" for s in smalls)
        assert runner.finish_reason == "length"
        assert eng.pool.available_count == eng.pool.num_pages

    def test_fence_freezes_admission_past_blocked_head(self):
        m = _tiny_gpt(seed=10)
        rng = np.random.RandomState(9)
        eng = self._pressure_engine(m)
        runner = eng.add_request(_prompt(rng, 4), max_new_tokens=13)
        eng.step()
        big = eng.add_request(_prompt(rng, 8), max_new_tokens=20)
        smalls = [eng.add_request(_prompt(rng, 4), max_new_tokens=2)
                  for _ in range(6)]
        # drive while the runner still blocks big's capacity
        while runner.state == "running":
            eng.step()
            assert big.state == "queued" or big.state == "running"
            if big._hol_skips >= 2:
                break
        # once the fence tripped, NO small may be admitted while big
        # stays queued — even with a free slot and fitting capacity
        if big.state == "queued" and big._hol_skips >= 2:
            queued_before = [s for s in smalls if s.state == "queued"]
            eng.step()
            still_queued = [s for s in queued_before
                            if s.state == "queued"]
            if big.state == "queued":
                assert still_queued == queued_before
        eng.run()
        assert big.finish_reason == "length"


# ---------------------------------------------------------------------------
# cancellation (queued + running) and finish-reason labels
# ---------------------------------------------------------------------------
class TestCancel:
    def test_cancel_labels_queued_vs_running_vs_evicted(self):
        m = _tiny_gpt(seed=11)
        rng = np.random.RandomState(10)
        eng = _engine(m, max_batch_size=1)
        running = eng.add_request(_prompt(rng), max_new_tokens=8)
        queued = eng.add_request(_prompt(rng), max_new_tokens=8)
        evictee = eng.add_request(_prompt(rng), max_new_tokens=8)
        eng.step()
        assert running.state == "running"
        queued.cancel()
        running.cancel()
        eng.evict(evictee)
        assert queued.finish_reason == "cancelled"
        assert running.finish_reason == "cancelled"
        assert evictee.finish_reason == "evicted"
        st = decode_stats()
        assert st["cancelled"] == 2
        assert st["evicted"] == 1
        snap = obs.snapshot()
        assert _counter_value(snap, "paddle_requests_finished_total",
                              reason="cancelled") == 2
        assert _counter_value(snap, "paddle_requests_finished_total",
                              reason="evicted") == 1
        assert eng.pool.available_count == eng.pool.num_pages

    def test_running_cancel_keeps_partial_output(self):
        m = _tiny_gpt(seed=11)
        rng = np.random.RandomState(11)
        eng = _engine(m, max_batch_size=1)
        req = eng.add_request(_prompt(rng), max_new_tokens=16)
        for _ in range(6):
            eng.step()
        n = len(req.output_ids)
        assert n >= 1
        req.cancel()
        assert req.finish_reason == "cancelled"
        assert len(req.output_ids) == n  # tokens so far survive
        assert not req.slo_met


# ---------------------------------------------------------------------------
# queue-pressure gauges (observability gap fix)
# ---------------------------------------------------------------------------
class TestQueueGauges:
    def test_depth_and_oldest_age_sampled_in_step(self):
        m = _tiny_gpt(seed=12)
        rng = np.random.RandomState(12)
        eng = _engine(m, max_batch_size=1)
        eng.add_request(_prompt(rng), max_new_tokens=6)
        eng.add_request(_prompt(rng), max_new_tokens=6)
        eng.add_request(_prompt(rng), max_new_tokens=6)
        eng.step()
        eid = eng._engine_id
        snap = obs.snapshot()
        assert _counter_value(snap, "paddle_queue_depth",
                              engine=eid) == 2
        assert _counter_value(snap, "paddle_queue_oldest_age_seconds",
                              engine=eid) > 0
        eng.run()
        eng.step()  # one idle step samples the drained queue
        snap = obs.snapshot()
        assert _counter_value(snap, "paddle_queue_depth",
                              engine=eid) == 0
        assert _counter_value(snap, "paddle_queue_oldest_age_seconds",
                              engine=eid) == 0


# ---------------------------------------------------------------------------
# SLO accounting
# ---------------------------------------------------------------------------
class TestSLOAccounting:
    def test_ttft_violation_recorded_never_aborts(self):
        m = _tiny_gpt(seed=13)
        rng = np.random.RandomState(13)
        eng = _engine(m, max_batch_size=1, scheduler="slo")
        req = eng.add_request(_prompt(rng), max_new_tokens=4,
                              slo_ttft_ms=1e-6, slo_tpot_ms=1e-6)
        eng.run()
        assert req.finish_reason == "length"  # completed anyway
        assert "ttft" in req.slo_violations
        assert "tpot" in req.slo_violations
        assert not req.slo_met
        st = decode_stats()
        assert st["slo_violations"] >= 2
        snap = obs.snapshot()
        assert _counter_value(snap, "paddle_sched_slo_violations_total",
                              kind="ttft") == 1

    def test_ttft_violation_on_legacy_prefill_path(self):
        # the non-chunked one-shot prefill stamps TTFT on its own path
        # — it must run the same SLO check (silently never violated
        # before the fix)
        m = _tiny_gpt(seed=13)
        rng = np.random.RandomState(17)
        eng = _engine(m, max_batch_size=1, chunked_prefill=False,
                      scheduler="slo")
        req = eng.add_request(_prompt(rng), max_new_tokens=4,
                              slo_ttft_ms=1e-6)
        eng.run()
        assert req.finish_reason == "length"
        assert "ttft" in req.slo_violations
        assert not req.slo_met

    def test_slo_met_when_targets_hold(self):
        m = _tiny_gpt(seed=13)
        rng = np.random.RandomState(14)
        eng = _engine(m, max_batch_size=1, scheduler="slo")
        req = eng.add_request(_prompt(rng), max_new_tokens=4,
                              slo_ttft_ms=60_000.0, slo_tpot_ms=60_000.0,
                              deadline_ms=60_000.0)
        eng.run()
        assert req.slo_met
        assert decode_stats()["slo_violations"] == 0


# ---------------------------------------------------------------------------
# adaptive chunk budget
# ---------------------------------------------------------------------------
class TestAdaptiveChunkBudget:
    def test_budget_shrinks_under_tpot_pressure_and_recovers(self):
        m = _tiny_gpt(seed=14)
        rng = np.random.RandomState(15)
        eng = _engine(m, scheduler=SLOScheduler(chunk_budget_min=2))
        sched = eng._scheduler
        base = eng._chunk_budget
        # a running request declaring an impossible TPOT target + a
        # fresh TPOT observation -> the controller halves the budget
        req = eng.add_request(_prompt(rng), max_new_tokens=4,
                              slo_tpot_ms=1e-9)
        eng.step()
        obs.REQUEST_TPOT.observe(0.5)  # 500 ms/token >> target
        sched._adapt_budget()
        assert eng._chunk_budget == base // 2
        # pressure gone (no targets) + queued work -> grows back
        req.cancel()
        eng.add_request(_prompt(rng), max_new_tokens=2)
        obs.REQUEST_TPOT.observe(0.001)
        sched._adapt_budget()
        assert eng._chunk_budget == base
        eng.run()
        assert decode_stats()["retraces_after_warmup"] == 0

    def test_budget_controller_survives_registry_reset(self):
        # an observability reset between looks (bench warmup, test
        # fixtures) rewinds the histogram under the delta cursor: the
        # controller must re-anchor, not stall on d_count <= 0 forever
        m = _tiny_gpt(seed=14)
        rng = np.random.RandomState(18)
        eng = _engine(m, scheduler=SLOScheduler(chunk_budget_min=2))
        sched = eng._scheduler
        base = eng._chunk_budget
        req = eng.add_request(_prompt(rng), max_new_tokens=4,
                              slo_tpot_ms=1e-9)
        eng.step()
        obs.REQUEST_TPOT.observe(0.5)
        sched._adapt_budget()
        assert eng._chunk_budget == base // 2
        obs.reset()  # cursor now ahead of the histogram
        sched._adapt_budget()  # re-anchors, acts on nothing
        assert eng._chunk_budget == base // 2
        req.cancel()
        eng.add_request(_prompt(rng), max_new_tokens=2)
        obs.REQUEST_TPOT.observe(0.001)
        sched._adapt_budget()  # fresh post-reset delta works again
        assert eng._chunk_budget == base
        eng.run()

    def test_budget_never_below_floor(self):
        m = _tiny_gpt(seed=14)
        rng = np.random.RandomState(16)
        eng = _engine(m, scheduler=SLOScheduler(chunk_budget_min=4))
        sched = eng._scheduler
        eng.add_request(_prompt(rng), max_new_tokens=4,
                        slo_tpot_ms=1e-9)
        eng.step()
        for _ in range(6):
            obs.REQUEST_TPOT.observe(0.5)
            sched._adapt_budget()
        assert eng._chunk_budget >= 4


# ---------------------------------------------------------------------------
# run() / generate() satellite
# ---------------------------------------------------------------------------
class TestRunGenerate:
    def test_run_raises_at_step_cap_instead_of_truncating(self):
        m = _tiny_gpt(seed=15)
        rng = np.random.RandomState(17)
        eng = _engine(m, max_batch_size=1)
        eng.add_request(_prompt(rng), max_new_tokens=16)
        with pytest.raises(RuntimeError, match="max_steps"):
            eng.run(max_steps=2)
        eng.run()  # recoverable: the cap is a backstop, not a state

    def test_generate_returns_preemption_stable_ids(self):
        m = _tiny_gpt(seed=15)
        rng = np.random.RandomState(18)
        prompts = [_prompt(rng, 6) for _ in range(3)]
        eng = _engine(m)
        outs, reasons = eng.generate(prompts, max_new_tokens=5,
                                     return_meta=True)
        assert all(len(o) == 5 for o in outs)
        assert reasons == ["length"] * 3


# ---------------------------------------------------------------------------
# the asyncio front-end
# ---------------------------------------------------------------------------
class TestServingFrontend:
    def test_stream_matches_blocking_generate(self):
        m = _tiny_gpt(seed=16)
        rng = np.random.RandomState(20)
        prompt = _prompt(rng)
        ref = _engine(m).generate([prompt], max_new_tokens=8)[0]

        async def go():
            eng = _engine(m)
            async with ServingFrontend(eng) as fe:
                stream = await fe.submit(prompt, max_new_tokens=8)
                toks = await stream.collect()
            assert stream.finish_reason == "length"
            assert stream.generated_ids == toks
            return toks

        assert _run(go()) == ref

    def test_interactive_first_token_before_batch_completion(self):
        m = _tiny_gpt(seed=17)
        rng = np.random.RandomState(21)
        prompts = [_prompt(rng) for _ in range(3)]

        async def go():
            eng = _engine(m, scheduler="slo")
            events = []

            async def consume(name, stream):
                first = True
                async for _ in stream:
                    if first:
                        events.append(("first", name))
                        first = False
                events.append(("done", name))

            async with ServingFrontend(eng) as fe:
                tasks = []
                for i in range(2):  # overload: both slots busy
                    s = await fe.submit(prompts[i], max_new_tokens=20)
                    tasks.append(asyncio.create_task(
                        consume(f"batch{i}", s)))
                await asyncio.sleep(0.05)  # batches mid-generation
                s = await fe.submit(prompts[2], max_new_tokens=4,
                                    priority=PRIORITY_INTERACTIVE)
                tasks.append(asyncio.create_task(consume("inter", s)))
                await asyncio.gather(*tasks)
            first_inter = events.index(("first", "inter"))
            batch_done = min(i for i, e in enumerate(events)
                             if e == ("done", "batch0")
                             or e == ("done", "batch1"))
            assert first_inter < batch_done, events
            assert eng.pool.available_count == eng.pool.num_pages

        _run(go())

    def test_cancel_midstream_frees_slot_and_pages(self):
        m = _tiny_gpt(seed=18)
        rng = np.random.RandomState(22)
        prompt = _prompt(rng)

        async def go():
            eng = _engine(m, max_batch_size=1)
            async with ServingFrontend(eng) as fe:
                stream = await fe.submit(prompt, max_new_tokens=30)
                got = []
                async for tok in stream:
                    got.append(tok)
                    if len(got) == 3:
                        await stream.cancel()
                assert stream.finish_reason == "cancelled"
                assert 3 <= len(got) < 30
                # the freed slot serves the next request immediately
                nxt = await fe.submit(prompt, max_new_tokens=2)
                assert len(await nxt.collect()) == 2
            assert eng.pool.available_count == eng.pool.num_pages
            assert decode_stats()["cancelled"] == 1

        _run(go())

    def test_cancel_while_queued(self):
        m = _tiny_gpt(seed=18)
        rng = np.random.RandomState(23)

        async def go():
            eng = _engine(m, max_batch_size=1)
            async with ServingFrontend(eng) as fe:
                s1 = await fe.submit(_prompt(rng), max_new_tokens=10)
                s2 = await fe.submit(_prompt(rng), max_new_tokens=10)
                await s2.cancel()
                assert await s2.collect() == []
                assert s2.finish_reason == "cancelled"
                assert len(await s1.collect()) == 10

        _run(go())

    def test_stream_backpressure_pauses_engine(self):
        m = _tiny_gpt(seed=19)
        rng = np.random.RandomState(24)

        async def go():
            eng = _engine(m, max_batch_size=1)
            async with ServingFrontend(eng, stream_buffer=2) as fe:
                stream = await fe.submit(_prompt(rng),
                                         max_new_tokens=12)
                # no consumer: the driver must pause between steps
                for _ in range(60):
                    await asyncio.sleep(0.005)
                    if stream.pending >= 2:
                        break
                await asyncio.sleep(0.05)  # would overshoot if unpaused
                # one step may land one more token after the check
                assert stream.pending <= 3
                toks = await stream.collect()
                assert len(toks) == 12

        _run(go())

    def test_submit_backpressure_bounds_admission_queue(self):
        m = _tiny_gpt(seed=19)
        rng = np.random.RandomState(25)

        async def go():
            eng = _engine(m, max_batch_size=1)
            async with ServingFrontend(eng, max_queue_depth=1) as fe:
                streams = []
                for _ in range(4):
                    s = await fe.submit(_prompt(rng), max_new_tokens=4)
                    assert len(eng._queue) <= 1
                    streams.append(s)
                outs = [await s.collect() for s in streams]
            assert all(len(o) == 4 for o in outs)

        _run(go())

    def test_close_drain_serves_everything(self):
        m = _tiny_gpt(seed=20)
        rng = np.random.RandomState(26)

        async def go():
            eng = _engine(m)
            fe = ServingFrontend(eng)
            s1 = await fe.submit(_prompt(rng), max_new_tokens=6)
            s2 = await fe.submit(_prompt(rng), max_new_tokens=6)
            await fe.close(drain=True)  # nobody consumed yet
            assert s1.finish_reason == "length"
            assert s2.finish_reason == "length"
            # buffered tokens stay readable after close
            assert len(await s1.collect()) == 6
            assert len(await s2.collect()) == 6
            with pytest.raises(RuntimeError, match="clos"):
                await fe.submit(_prompt(rng))

        _run(go())

    def test_close_no_drain_cancels_outstanding(self):
        m = _tiny_gpt(seed=20)
        rng = np.random.RandomState(27)

        async def go():
            eng = _engine(m, max_batch_size=1)
            fe = ServingFrontend(eng)
            s1 = await fe.submit(_prompt(rng), max_new_tokens=38)
            s2 = await fe.submit(_prompt(rng), max_new_tokens=38)
            await asyncio.sleep(0.05)
            await fe.close(drain=False)
            assert s1.finish_reason == "cancelled"
            assert s2.finish_reason == "cancelled"
            assert eng.pool.available_count == eng.pool.num_pages

        _run(go())

    def test_submit_raises_on_dead_driver_with_full_queue(self):
        # when the driver dies with the admission queue still at the
        # bound, submit() must surface the dead driver instead of
        # parking on the backpressure wait forever (nothing will ever
        # drain the queue again)
        m = _tiny_gpt(seed=24)
        rng = np.random.RandomState(33)

        async def go():
            eng = _engine(m, max_batch_size=1)
            calls = {"n": 0}
            orig_step = eng.step

            def step():
                calls["n"] += 1
                if calls["n"] >= 3:
                    raise RuntimeError("boom")
                return orig_step()

            eng.step = step
            fe = ServingFrontend(eng, max_queue_depth=1)
            s1 = await fe.submit(_prompt(rng), max_new_tokens=10)
            s2 = await fe.submit(_prompt(rng), max_new_tokens=10)
            await asyncio.wait_for(s1.collect(), 10)  # driver dies
            assert s2.request.state == "queued"  # bound still consumed
            with pytest.raises(RuntimeError, match="driver"):
                await asyncio.wait_for(fe.submit(_prompt(rng)), 10)
            with pytest.raises(RuntimeError, match="boom"):
                await fe.close()

        _run(go(), timeout=30)

    def test_close_no_drain_rejects_unapplied_submission(self):
        # a submission still sitting in the control queue when
        # close(drain=False) lands must not be applied and served to
        # completion — it either fails with the closing error or (if
        # the driver won the race) is cancelled like every other
        # outstanding request
        m = _tiny_gpt(seed=24)
        rng = np.random.RandomState(32)

        async def go():
            eng = _engine(m, max_batch_size=1)
            fe = ServingFrontend(eng)
            s1 = await fe.submit(_prompt(rng), max_new_tokens=30)
            racer = asyncio.create_task(
                fe.submit(_prompt(rng), max_new_tokens=30))
            await asyncio.sleep(0)  # control appended, not yet applied
            await asyncio.wait_for(fe.close(drain=False), 10)
            assert s1.finish_reason == "cancelled"
            try:
                s2 = await racer
            except RuntimeError as e:
                assert "closing" in str(e)
            else:  # driver applied it before close: cancelled instead
                assert s2.finish_reason == "cancelled"
            assert eng.pool.available_count == eng.pool.num_pages

        _run(go(), timeout=30)

    def test_submit_surfaces_validation_errors(self):
        m = _tiny_gpt(seed=21)

        async def go():
            eng = _engine(m)
            async with ServingFrontend(eng) as fe:
                with pytest.raises(ValueError, match="empty prompt"):
                    await fe.submit(np.zeros((0,), np.int32))

        _run(go())

    def test_constructor_validation(self):
        m = _tiny_gpt(seed=21)
        eng = _engine(m)
        with pytest.raises(ValueError, match="max_queue_depth"):
            ServingFrontend(eng, max_queue_depth=0)
        with pytest.raises(ValueError, match="stream_buffer"):
            ServingFrontend(eng, stream_buffer=0)

    def test_cancel_while_paused_on_backpressure(self):
        # a cancel aimed at the very stream the driver is paused on
        # must interrupt the pause (control kicks _drained too, not
        # just _wake) — this deadlocked before the fix
        m = _tiny_gpt(seed=22)
        rng = np.random.RandomState(28)

        async def go():
            eng = _engine(m, max_batch_size=1)
            async with ServingFrontend(eng, stream_buffer=1) as fe:
                stream = await fe.submit(_prompt(rng),
                                         max_new_tokens=20)
                for _ in range(200):  # wait for the pause to engage
                    await asyncio.sleep(0.005)
                    if stream.pending >= 1:
                        break
                await asyncio.wait_for(stream.cancel(), 10)
                got = await stream.collect()  # buffered tokens drain
                assert stream.finish_reason == "cancelled"
                assert 1 <= len(got) < 20
            assert eng.pool.available_count == eng.pool.num_pages

        _run(go(), timeout=30)

    def test_close_no_drain_while_paused_on_backpressure(self):
        m = _tiny_gpt(seed=22)
        rng = np.random.RandomState(29)

        async def go():
            eng = _engine(m, max_batch_size=1)
            fe = ServingFrontend(eng, stream_buffer=1)
            stream = await fe.submit(_prompt(rng), max_new_tokens=20)
            for _ in range(200):
                await asyncio.sleep(0.005)
                if stream.pending >= 1:
                    break
            await asyncio.wait_for(fe.close(drain=False), 10)
            assert stream.finish_reason == "cancelled"
            assert eng.pool.available_count == eng.pool.num_pages

        _run(go(), timeout=30)

    def test_concurrent_submits_respect_queue_bound(self):
        # N submits racing ahead of the driver's next control pass must
        # still respect max_queue_depth: pending not-yet-applied
        # submissions count against the bound
        m = _tiny_gpt(seed=23)
        rng = np.random.RandomState(30)

        async def go():
            eng = _engine(m, max_batch_size=1)
            depth_seen = []
            orig_step = eng.step

            def step():
                depth_seen.append(len(eng._queue))
                out = orig_step()
                depth_seen.append(len(eng._queue))
                return out

            eng.step = step
            async with ServingFrontend(eng, max_queue_depth=2) as fe:
                streams = await asyncio.gather(
                    *[fe.submit(_prompt(rng), max_new_tokens=3)
                      for _ in range(6)])
                outs = await asyncio.gather(
                    *[s.collect() for s in streams])
            assert all(len(o) == 3 for o in outs)
            assert max(depth_seen) <= 2, max(depth_seen)

        _run(go())

    def test_step_exception_ends_streams_and_surfaces(self):
        # an exception out of step() must not strand anyone: open
        # streams end, later submits see the dead driver, close()
        # re-raises the original error
        m = _tiny_gpt(seed=23)
        rng = np.random.RandomState(31)

        async def go():
            eng = _engine(m, max_batch_size=1)
            calls = {"n": 0}
            orig_step = eng.step

            def step():
                calls["n"] += 1
                if calls["n"] >= 2:
                    raise RuntimeError("boom")
                return orig_step()

            eng.step = step
            fe = ServingFrontend(eng)
            stream = await fe.submit(_prompt(rng), max_new_tokens=10)
            got = await asyncio.wait_for(stream.collect(), 10)
            assert len(got) < 10  # died mid-generation, stream ended
            with pytest.raises(RuntimeError, match="driver"):
                await fe.submit(_prompt(rng))
            with pytest.raises(RuntimeError, match="boom"):
                await fe.close()

        _run(go(), timeout=30)


class TestSanitizedFrontend:
    """tier-1 sanitizer coverage (tests/conftest.py `sanitize` marker):
    the asyncio front-end — engine stepping on a worker thread, token
    callbacks crossing threads, telemetry locks taken from both sides —
    serves clean under FLAGS_sanitize: no lock-order cycle, no warm
    retrace, no use-after-donate."""

    @pytest.mark.sanitize
    def test_streaming_serve_clean_under_sanitizer(self):
        from paddle_tpu.analysis import sanitizer

        m = _tiny_gpt(seed=11)
        eng = _engine(m, scheduler="slo")
        rng = np.random.RandomState(2)

        async def go():
            async with ServingFrontend(eng) as fe:
                s1 = await fe.submit(_prompt(rng), max_new_tokens=6,
                                     priority=PRIORITY_INTERACTIVE)
                s2 = await fe.submit(_prompt(rng, n=5), max_new_tokens=6)
                return await s1.collect(), await s2.collect()

        t1, t2 = _run(go())
        assert len(t1) == 6 and len(t2) == 6
        rep = sanitizer.get().report()
        assert rep["steps"] > 0
        assert rep["warm_retraces"] == 0
        # the engine's host-sync discipline holds across the worker
        # thread: at most one blocking fetch per step (a capacity-
        # blocked step runs no batch and fetches nothing)
        assert 0 < rep["host_syncs"] <= rep["steps"]
        assert rep["tombstoned_buffers"] > 0  # donation tracked
