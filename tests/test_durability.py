"""Durable serving (inference.durability): write-ahead journal +
on-disk snapshots + fresh-process restore, executable handoff for fast
recovery, and the hung-step watchdog.

Contracts pinned here (ISSUE 10 acceptance):

* `restore_from_dir` rebuilds an engine from journal + snapshot after
  process death with zero request loss, greedy outputs bit-identical
  to the uninterrupted run, and no already-streamed token ever
  re-fired at a stream (the emitted-token watermark gates `_emit`);
* a truncated journal tail record and a torn snapshot both restore
  from the last consistent state — never a crash, never a re-emission
  of anything the surviving journal covers;
* `EngineSnapshot` splits a picklable wire form (`RequestWire` /
  `SnapshotWire`) from the in-process by-reference form, round-trip
  equal through JSON;
* in-process `recover` hands the dead engine's compiled executables to
  the rebuilt engine (fingerprint-gated) — recovery recompiles
  NOTHING when the config matches;
* a `slow_step`-injected hang trips the watchdog: `paddle_engine_health`
  transitions live -> hung -> recovering -> live, and open frontend
  streams survive the abandon-and-rebuild with bit-identical tokens;
* with FLAGS_journal_dir unset and FLAGS_step_timeout_ms zero, serving
  is bit-exact vs the PR 9 engine and every new counter stays 0.
"""
import asyncio
import json
import os
import pickle

import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference import durability, resilience
from paddle_tpu.inference.durability import (DurabilityManager,
                                             RequestWire, SnapshotWire,
                                             load_snapshot,
                                             read_journal,
                                             restore_from_dir)
from paddle_tpu.inference.errors import HungStep, StepFault
from paddle_tpu.inference.frontend import ServingFrontend
from paddle_tpu.inference.resilience import (EngineSnapshot,
                                             serve_with_recovery)
from paddle_tpu.inference.serving import (DecodeEngine, decode_stats,
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
    """Uninterrupted greedy outputs — what every restored/recovered
    serve must reproduce bit for bit."""
    return _engine(model).generate(PROMPTS, max_new_tokens=NEW)


def _run(coro, timeout=120):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def _streamed_serve(eng, prompts=PROMPTS, max_new=NEW):
    """Submit ``prompts`` with per-token capture; returns
    (requests, streamed) where streamed[request_id] accumulates every
    on_token firing."""
    streamed = {}
    reqs = []
    for p in prompts:
        req = eng.add_request(p, max_new_tokens=max_new)
        req.on_token = (lambda rid: lambda t: streamed.setdefault(
            rid, []).append(t))(req.request_id)
        reqs.append(req)
    return reqs, streamed


def _rewire(rmap, streamed):
    for rid, req in rmap.items():
        req.on_token = (lambda r: lambda t: streamed.setdefault(
            r, []).append(t))(rid)


# ---------------------------------------------------------------------------
# wire forms: the serialization-safe EngineSnapshot split
# ---------------------------------------------------------------------------
class TestWireForms:
    def test_request_wire_round_trip_equality(self, model):
        eng = _engine(model)
        r = eng.add_request(PROMPTS[0], max_new_tokens=NEW,
                            deadline_ms=5000.0, slo_ttft_ms=100.0)
        for _ in range(5):
            eng.step()
        w = RequestWire.from_request(r)
        back = RequestWire.from_obj(json.loads(json.dumps(w.to_obj())))
        assert back == w
        assert w.generated == list(r.generated_ids)
        assert w.prompt == PROMPTS[0]

    def test_materialize_folds_replay(self):
        w = RequestWire(request_id=42, prompt=[1, 2, 3],
                        generated=[9, 8], max_new=10, streamed=4,
                        eos=None, priority=0)
        req = w.materialize()
        assert req.prompt_ids == [1, 2, 3, 9, 8]
        assert req.max_new_tokens == 8
        assert req.orig_prompt_len == 3
        assert req._absorbed == 2
        # streamed watermark 4 > 2 known values: two replay tokens must
        # recompute behind the gate, never re-fire at the stream
        assert req._emit_gate == 2
        assert req.request_id == 42
        assert list(req.generated_ids) == [9, 8]

    def test_snapshot_wire_round_trip_and_picklable(self, model):
        eng = _engine(model)
        for p in PROMPTS:
            eng.add_request(p, max_new_tokens=NEW)
        for _ in range(6):
            eng.step()
        snap = EngineSnapshot(eng)
        wire = snap.to_wire(journal_pos=7)
        assert wire.journal_pos == 7
        # the last step landed: the one still in flight (the engine's
        # lookahead) is dispatched again by an engine rebuilt from this
        assert eng._inflight is not None
        assert wire.step_no == eng._step_no - 1
        assert len(wire.records) == 2
        back = SnapshotWire.from_obj(
            json.loads(json.dumps(wire.to_obj())))
        assert back == wire
        # the in-process form holds Requests BY REFERENCE (streams
        # survive a rebuild) — the wire form must not
        pickle.loads(pickle.dumps(wire))
        assert all(not hasattr(r, "request") for r in wire.records)


# ---------------------------------------------------------------------------
# the journal
# ---------------------------------------------------------------------------
class TestJournal:
    def test_records_written_and_read_back(self, model, tmp_path):
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d)
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        eng.run()
        events, _ = read_journal(os.path.join(d, "journal.wal"))
        kinds = [e["t"] for e in events]
        assert kinds[0] == "cfg"
        assert kinds.count("a") == 2
        assert kinds.count("f") == 2
        assert kinds.count("e") > 0
        admits = {e["id"]: e for e in events if e["t"] == "a"}
        assert admits[reqs[0].request_id]["p"] == PROMPTS[0]
        # the final watermark per request covers the whole generation
        marks = {}
        for e in events:
            if e["t"] == "e":
                marks[e["id"]] = e["n"]
        for r in reqs:
            assert marks[r.request_id] == len(r.generated_ids)
        assert decode_stats()["journal_records"] == len(events)

    def test_fsync_policy_validated(self, model, tmp_path):
        eng = _engine(model)
        with pytest.raises(ValueError, match="journal_fsync"):
            DurabilityManager(eng, str(tmp_path / "x"), fsync="bogus")

    def test_reopen_truncates_torn_tail(self, model, tmp_path):
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d)
        eng.add_request(PROMPTS[0], max_new_tokens=4)
        eng.run()
        path = os.path.join(d, "journal.wal")
        n_clean, _ = read_journal(path)
        with open(path, "ab") as f:
            f.write(b"deadbeef {torn json garbage")  # no newline: torn
        # a new life over the same dir truncates the torn tail, then
        # appends records that stay parseable
        eng2 = _engine(model, journal_dir=d)
        eng2.add_request(PROMPTS[1], max_new_tokens=4)
        eng2.run()
        events, _ = read_journal(path)
        assert len(events) > len(n_clean)
        assert all("t" in e for e in events)


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------
class TestSnapshots:
    def test_periodic_snapshot_written(self, model, tmp_path):
        d = str(tmp_path / "j")
        paddle.set_flags({"snapshot_interval_steps": 4})
        try:
            eng = _engine(model, journal_dir=d)
            for p in PROMPTS:
                eng.add_request(p, max_new_tokens=NEW)
            eng.run()
        finally:
            paddle.set_flags({"snapshot_interval_steps": 32})
        assert os.path.exists(os.path.join(d, "snapshot.json"))
        wire = load_snapshot(d)
        assert wire is not None and wire.journal_pos > 0
        assert decode_stats()["journal_snapshots"] >= 1

    def test_torn_snapshot_falls_back_to_journal(self, model, tmp_path,
                                                 reference):
        d = str(tmp_path / "j")
        paddle.set_flags({"snapshot_interval_steps": 3})
        try:
            eng = _engine(model, journal_dir=d)
            reqs, streamed = _streamed_serve(eng)
            for _ in range(8):
                eng.step()
        finally:
            paddle.set_flags({"snapshot_interval_steps": 32})
        eng._durability.flush()
        # tear the snapshot: flip bytes mid-file — the crc fails and
        # restore must fall back to replaying the whole journal
        snap_path = os.path.join(d, "snapshot.json")
        assert os.path.exists(snap_path)
        data = bytearray(open(snap_path, "rb").read())
        data[len(data) // 2] ^= 0xFF
        open(snap_path, "wb").write(bytes(data))
        assert load_snapshot(d) is None
        eng2, rmap = restore_from_dir(d, model)
        _rewire(rmap, streamed)
        eng2.run()
        order = sorted(rmap)
        assert [list(rmap[r].generated_ids) for r in order] == reference
        # never a re-emission, never a gap: each stream saw the full
        # generation exactly once across both lives
        assert [streamed[r] for r in order] == reference


# ---------------------------------------------------------------------------
# fresh-process restore (the durable-recovery acceptance)
# ---------------------------------------------------------------------------
class TestRestore:
    def test_restore_bit_identical_no_reemission(self, model, tmp_path,
                                                 reference):
        """THE durable-recovery leg, in-process stand-in for the kill
        -9 bench: serve partway with journal + snapshot armed, drop the
        engine without any shutdown, rebuild from disk, finish —
        outputs bit-identical, streams gap- and duplicate-free."""
        d = str(tmp_path / "j")
        paddle.set_flags({"snapshot_interval_steps": 4})
        try:
            eng = _engine(model, journal_dir=d)
            reqs, streamed = _streamed_serve(eng)
            for _ in range(9):
                eng.step()
        finally:
            paddle.set_flags({"snapshot_interval_steps": 32})
        eng._durability.flush()
        pre_counts = {rid: len(v) for rid, v in streamed.items()}
        assert any(pre_counts.values())  # mid-generation, not done
        eng2, rmap = restore_from_dir(d, model)
        assert sorted(rmap) == sorted(r.request_id for r in reqs)
        for req in rmap.values():
            assert req.fault_info is not None
            assert req.fault_info.site == "restore"
            assert req.fault_info.recovered
        _rewire(rmap, streamed)
        eng2.run()
        order = sorted(rmap)
        assert [list(rmap[r].generated_ids) for r in order] == reference
        assert [streamed[r] for r in order] == reference
        assert [rmap[r].finish_reason for r in order] == \
            ["length", "length"]
        st = decode_stats()
        assert st["restores"] == 1
        assert any(s[1] == "restore" for s in obs.spans())

    def test_truncated_tail_record_restores_last_consistent(
            self, model, tmp_path, reference):
        """Cut the journal mid-record (a torn write at crash time):
        restore must use the surviving prefix — no crash, outputs
        still bit-identical (the lost suffix recomputes), and nothing
        the surviving journal covers re-fires at a stream."""
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d)
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        for _ in range(8):
            eng.step()
        eng._durability.flush()
        path = os.path.join(d, "journal.wal")
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[:-7])  # tear the final record
        events, _ = read_journal(path)
        marks = {}
        for e in events:
            if e["t"] == "e":
                marks[e["id"]] = max(marks.get(e["id"], 0), e["n"])
        streamed = {}
        eng2, rmap = restore_from_dir(d, model)
        _rewire(rmap, streamed)
        eng2.run()
        order = sorted(rmap)
        assert [list(rmap[r].generated_ids) for r in order] == reference
        # replay streamed exactly the tokens past each surviving
        # watermark: everything the journal covers was suppressed
        for i, rid in enumerate(order):
            assert streamed[rid] == reference[i][marks.get(rid, 0):]

    def test_finished_requests_never_readmitted(self, model, tmp_path):
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d)
        eng.generate(PROMPTS, max_new_tokens=4)
        eng._durability.flush()
        eng2, rmap = restore_from_dir(d, model)
        assert rmap == {}
        assert not eng2._queue

    def test_double_death_double_restore(self, model, tmp_path,
                                         reference):
        """The restored serve keeps journaling: a second death and a
        second restore still reproduce the reference bit for bit."""
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d)
        reqs, streamed = _streamed_serve(eng)
        for _ in range(5):
            eng.step()
        eng._durability.flush()
        eng2, rmap = restore_from_dir(d, model)
        _rewire(rmap, streamed)
        for _ in range(5):
            eng2.step()
        eng2._durability.flush()
        eng3, rmap2 = restore_from_dir(d, model)
        _rewire(rmap2, streamed)
        eng3.run()
        order = sorted(r.request_id for r in reqs)
        final = {**rmap, **rmap2}  # the LATEST restore's objects win
        assert [list(final[r].generated_ids) for r in order] == reference
        assert [streamed[r] for r in order] == reference
        assert decode_stats()["restores"] == 2

    def test_wrong_model_fingerprint_raises(self, model, tmp_path):
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d)
        eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        for _ in range(3):
            eng.step()
        eng._durability.flush()
        with pytest.raises(ValueError, match="fingerprint"):
            restore_from_dir(d, _tiny_gpt(seed=123))

    def test_missing_journal_raises(self, model, tmp_path):
        with pytest.raises(FileNotFoundError):
            restore_from_dir(str(tmp_path / "nope"), model)


# ---------------------------------------------------------------------------
# journal compaction on restore (fleet PR satellite)
# ---------------------------------------------------------------------------
class TestJournalCompaction:
    def _dead_serve(self, model, tmp_path, steps=9):
        d = str(tmp_path / "j")
        paddle.set_flags({"snapshot_interval_steps": 4})
        try:
            eng = _engine(model, journal_dir=d)
            reqs, streamed = _streamed_serve(eng)
            for _ in range(steps):
                eng.step()
        finally:
            paddle.set_flags({"snapshot_interval_steps": 32})
        eng._durability.flush()
        return d, reqs, streamed

    def test_restore_compacts_with_size_assertion(self, model,
                                                  tmp_path, reference):
        """A long-lived serve accretes one watermark record per emit
        round; `restore_from_dir` (FLAGS_journal_compact, default on)
        rewrites the journal down to cfg + one admission + one
        watermark per in-flight request — bounded by LIVE work, not by
        history — while the restored serve stays bit-identical."""
        d, reqs, streamed = self._dead_serve(model, tmp_path)
        path = os.path.join(d, "journal.wal")
        bytes_before = os.path.getsize(path)
        recs_before = len(read_journal(path)[0])
        eng2, rmap = restore_from_dir(d, model)
        bytes_after = os.path.getsize(path)
        recs_after = len(read_journal(path)[0])
        assert bytes_after < bytes_before  # the satellite's bar
        # compacted floor: cfg + ("a" + "e") per live request, plus
        # the re-admission records the restored engine itself appends
        assert recs_before > 1 + 4 * len(rmap)
        assert recs_after <= 1 + 4 * len(rmap)
        assert decode_stats()["journal_compactions"] == 1
        _rewire(rmap, streamed)
        eng2.run()
        order = sorted(rmap)
        assert [list(rmap[r].generated_ids) for r in order] == reference
        assert [streamed[r] for r in order] == reference

    def test_compacted_ids_keep_monotonic(self, model, tmp_path):
        """Compaction drops finished requests' records, but their ids
        must stay burned (the compacted cfg carries the id high-water)
        — a fresh admission after TWO restores can never collide with
        a pre-death id."""
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d)
        finished = eng.add_request(PROMPTS[0], max_new_tokens=2)
        live = eng.add_request(PROMPTS[1], max_new_tokens=NEW)
        while finished.state != "done":
            eng.step()
        eng._durability.flush()
        eng2, rmap = restore_from_dir(d, model)
        assert sorted(rmap) == [live.request_id]
        eng2._durability.flush()
        eng3, _ = restore_from_dir(d, model)  # from a compacted file
        fresh = eng3.add_request(PROMPTS[0], max_new_tokens=2)
        assert fresh.request_id > finished.request_id
        assert fresh.request_id > live.request_id

    def test_compact_flag_off_appends_only(self, model, tmp_path,
                                           reference):
        """``compact=False`` (or FLAGS_journal_compact=0) must leave
        the journal strictly append-only: the pre-death bytes survive
        verbatim and the serve is still bit-identical."""
        d, reqs, streamed = self._dead_serve(model, tmp_path)
        path = os.path.join(d, "journal.wal")
        raw_before = open(path, "rb").read()
        eng2, rmap = restore_from_dir(d, model, compact=False)
        raw_after = open(path, "rb").read()
        assert raw_after[:len(raw_before)] == raw_before
        assert decode_stats()["journal_compactions"] == 0
        _rewire(rmap, streamed)
        eng2.run()
        order = sorted(rmap)
        assert [list(rmap[r].generated_ids) for r in order] == reference

    def test_compact_journal_public_api(self, model, tmp_path,
                                        reference):
        """`compact_journal` works standalone (an operator trimming a
        dead replica's journal before hand-off) and reports the
        before/after sizes it achieved."""
        d, reqs, streamed = self._dead_serve(model, tmp_path)
        path = os.path.join(d, "journal.wal")
        stats = durability.compact_journal(d)
        assert stats["bytes_after"] < stats["bytes_before"]
        assert stats["bytes_after"] == os.path.getsize(path)
        assert stats["records_after"] < stats["records_before"]
        eng2, rmap = restore_from_dir(d, model, compact=False)
        _rewire(rmap, streamed)
        eng2.run()
        order = sorted(rmap)
        assert [list(rmap[r].generated_ids) for r in order] == reference
        assert [streamed[r] for r in order] == reference


# ---------------------------------------------------------------------------
# executable handoff (fast in-process recovery)
# ---------------------------------------------------------------------------
class TestExecutableHandoff:
    def _fatal_serve(self, model, **kw):
        eng = _engine(model, fault_plan="step@6-12", **kw)
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        while True:
            try:
                eng.step()
            except StepFault as e:
                return eng, reqs, e

    def test_recovery_recompiles_nothing(self, model, reference):
        eng, reqs, fault = self._fatal_serve(model)
        before = decode_stats()
        new = resilience.recover(eng, fault=fault)
        new.run()
        after = decode_stats()
        # the rebuilt engine adopted every live executable: zero new
        # compiles, zero warm retraces, full parity
        for key in ("mixed_compiles", "decode_compiles",
                    "prefill_compiles", "verify_compiles"):
            assert after[key] == before[key], key
        assert after["exec_handoffs"] >= 1
        assert after["retraces_after_warmup"] == 0
        assert [list(r.generated_ids) for r in reqs] == reference

    def test_cold_recovery_still_works(self, model, reference):
        eng, reqs, fault = self._fatal_serve(model)
        before = decode_stats()
        new = resilience.recover(eng, fault=fault, handoff=False)
        new.run()
        after = decode_stats()
        assert after["exec_handoffs"] == 0
        assert after["mixed_compiles"] > before["mixed_compiles"]
        assert [list(r.generated_ids) for r in reqs] == reference

    def test_fingerprint_gates_handoff(self, model):
        a = _engine(model)
        a.generate([PROMPTS[0]], max_new_tokens=4)
        mismatched = _engine(model, page_size=8)
        assert mismatched.adopt_executables(a) == 0
        matched = _engine(model)
        assert matched.adopt_executables(a) >= 1
        assert matched._mixed_fn is a._mixed_fn

    def test_spec_verify_hands_off(self, model):
        eng = _engine(model, spec_decode_k=3)
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        for _ in range(4):
            eng.step()
        assert eng._spec._verify_fn is not None
        snap = EngineSnapshot(eng)
        new = resilience.recover(eng, snapshot=snap)
        assert new._spec._verify_fn is eng._spec._verify_fn


# ---------------------------------------------------------------------------
# the hung-step watchdog
# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_disarmed_by_default(self, model):
        eng = _engine(model)
        assert eng._watchdog is None
        assert eng._durability is None

    def test_compile_steps_exempt(self, model):
        """A step that built an executable is never classified hung —
        a first compile can dwarf any sane timeout."""
        eng = _engine(model, step_timeout_ms=1000.0)
        wd = eng._watchdog
        eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        eng._admit()
        wd.arm()
        eng._resilience.run_step()  # first step: compiles the mixed fn
        assert not wd.classify(999.0)  # over any budget, but compiling
        wd.arm()
        eng._resilience.run_step()  # prefill done: compiles decode fn
        assert not wd.classify(999.0)
        wd.arm()
        eng._resilience.run_step()  # fully warm: no compile to excuse
        assert wd.classify(999.0)
        assert not wd.classify(1e-6)

    def test_posthoc_hang_recovers_with_parity(self, model, reference):
        """The blocking-supervisor leg: a slow_step stall past the
        budget raises HungStep AFTER the step completes;
        serve_with_recovery rebuilds (executables handed off) and the
        health gauge walks live -> hung -> recovering -> live."""
        eng = _engine(model, fault_plan="slow_step@6;slow_ms=400",
                      step_timeout_ms=150.0)
        reqs = [eng.add_request(p, max_new_tokens=NEW) for p in PROMPTS]
        eng2, recoveries = serve_with_recovery(eng)
        assert recoveries == 1
        assert [list(r.generated_ids) for r in reqs] == reference
        st = decode_stats()
        assert st["hung_steps"] == 1
        assert st["recoveries"] == 1
        seq = [s[1] for s in obs.spans() if s[1].startswith("health:")]
        assert seq == ["health:hung", "health:recovering",
                       "health:live"]
        snap = obs.snapshot()
        states = {(x["labels"]["engine"], x["labels"]["state"]):
                  x["value"]
                  for x in snap["paddle_engine_health"]["series"]}
        # recovery RETIRES the dead engine from the WHOLE gauge
        # catalog (ISSUE 11 strengthened PR 10's health-only clear):
        # no series of ANY metric still carries the dead id — the hung
        # alert cannot stay latched and nothing scrapes stale levels
        assert not any(e == str(eng._engine_id) for e, _ in states)
        assert states[(str(eng2._engine_id), "live")] == 1
        dead = str(eng._engine_id)
        for name, m in snap.items():
            if "engine" not in m["labels"]:
                continue
            assert not any(s["labels"]["engine"] == dead
                           for s in m["series"]), name

    def test_hung_step_is_fatal_step_fault(self):
        e = HungStep("boom")
        assert isinstance(e, StepFault) and e.fatal
        assert e.site == "hung"

    def test_abandon_detaches_durability(self, model, tmp_path):
        """An abandoned engine must never write the shared journal
        again: a late-returning hung step flushing stale records — or
        snapshotting its now-EMPTY state over the successor's — would
        lose every in-flight request on a later restore."""
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d, step_timeout_ms=500.0)
        eng.add_request(PROMPTS[0], max_new_tokens=4)
        eng.step()
        eng._abandon_inflight()
        assert eng._abandoned
        assert eng._durability is None and eng._watchdog is None
        eng.step()  # the late/no-op step touches neither file
        events, _ = read_journal(os.path.join(d, "journal.wal"))
        assert events[0]["t"] == "cfg"  # journal intact and parseable

    def test_recover_retires_dead_journal_writer(self, model, tmp_path):
        """recover() closes the dead engine's journal handle — exactly
        one live writer per journal directory, no fd leak per
        recovery."""
        d = str(tmp_path / "j")
        eng = _engine(model, journal_dir=d, fault_plan="step@4-10")
        eng.add_request(PROMPTS[0], max_new_tokens=NEW)
        fault = None
        while fault is None:
            try:
                eng.step()
            except StepFault as e:
                fault = e
        new = resilience.recover(eng, fault=fault)
        assert eng._durability is None
        assert new._durability is not None
        assert new._durability._fh.closed is False
        new.run()
        events, _ = read_journal(os.path.join(d, "journal.wal"))
        assert any(e["t"] == "f" for e in events)

    def test_frontend_abandons_hung_worker_streams_survive(
            self, model, reference):
        """The frontend leg: the worker thread stalls well past the
        budget, the driver ABANDONS it mid-flight (no await on the
        hung thread), rebuilds from the pre-step snapshot, and the
        same TokenStreams finish with bit-identical tokens — nothing
        re-emitted, nothing lost."""
        async def go():
            eng = _engine(model,
                          fault_plan="slow_step@12;slow_ms=1500",
                          step_timeout_ms=300.0)
            async with ServingFrontend(eng) as fe:
                warm = await fe.submit(PROMPTS[0], max_new_tokens=4)
                await warm.collect()
                s1 = await fe.submit(PROMPTS[0], max_new_tokens=NEW)
                s2 = await fe.submit(PROMPTS[1], max_new_tokens=NEW)
                t1, t2 = await s1.collect(), await s2.collect()
            return fe, s1, s2, t1, t2

        fe, s1, s2, t1, t2 = _run(go())
        assert fe._recoveries == 1
        assert [t1, t2] == reference
        assert s1.finish_reason == "length"
        assert s2.finish_reason == "length"
        seq = [s[1] for s in obs.spans() if s[1].startswith("health:")]
        assert seq == ["health:hung", "health:recovering",
                       "health:live"]
        st = decode_stats()
        assert st["recoveries"] == 1
        assert st["hung_steps"] == 1  # the abandon path counts too


# ---------------------------------------------------------------------------
# the disarmed contract
# ---------------------------------------------------------------------------
class TestDisarmedParity:
    def test_disarmed_bit_exact_zero_counters(self, model, reference):
        """journal_dir unset + step_timeout_ms 0: every new hook is one
        `is None` check and serving is bit-exact vs the PR 9 engine."""
        eng = _engine(model)
        outs = eng.generate(PROMPTS, max_new_tokens=NEW)
        assert outs == reference
        st = decode_stats()
        for key in ("journal_records", "journal_snapshots", "restores",
                    "exec_handoffs", "hung_steps"):
            assert st[key] == 0, key
        assert st["retraces_after_warmup"] == 0

    def test_flag_arms_journal(self, model, tmp_path, reference):
        d = str(tmp_path / "flagged")
        paddle.set_flags({"journal_dir": d})
        try:
            eng = _engine(model)
            assert eng._durability is not None
            outs = eng.generate(PROMPTS, max_new_tokens=NEW)
        finally:
            paddle.set_flags({"journal_dir": ""})
        assert outs == reference  # journaling never perturbs outputs
        assert os.path.exists(os.path.join(d, "journal.wal"))
        assert _engine(model)._durability is None

    def test_flag_arms_watchdog(self, model):
        paddle.set_flags({"step_timeout_ms": 250.0})
        try:
            eng = _engine(model)
            assert eng._watchdog is not None
            assert eng._watchdog.timeout_ms == 250.0
        finally:
            paddle.set_flags({"step_timeout_ms": 0.0})

    def test_tracecheck_stays_clean(self):
        """durability.py's engine mutation (restore re-admission,
        watchdog abandonment, executable handoff) is sanctioned in the
        spec, not grandfathered."""
        from paddle_tpu.analysis import run_tracecheck

        assert run_tracecheck() == []
