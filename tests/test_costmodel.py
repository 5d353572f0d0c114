"""Serving cost observatory (observability.costmodel): compile-time
FLOP/byte profiles, calibrated step-cost prediction, the HBM ledger,
roofline gauges, cost-gated admission, and the calibration wire across
recover/restore.  The disarmed path (cost_model=0) is pinned bit-exact
with zero profiles extracted; ratio GATES (median error, overhead)
live in tools/bench_cost.py where the step sizes make them meaningful.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (DecodeEngine, decode_stats,
                                          reset_decode_stats)
from paddle_tpu.observability import costmodel


def _model(vocab=64, hidden=32, layers=1, heads=2, max_seq=256):
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=heads,
                    max_seq_len=max_seq, use_parallel_layers=False,
                    dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


def _prompts(n, length=12, vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (length,)).astype(np.int32)
            for _ in range(n)]


@pytest.fixture(scope="module")
def model():
    return _model()


def _engine(model, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    return DecodeEngine(model, **kw)


# ---------------------------------------------------------------------------
# static profiles
# ---------------------------------------------------------------------------
class TestProfiles:
    def test_profiles_extracted_at_compile_time(self, model):
        reset_decode_stats()
        eng = _engine(model)
        eng.generate(_prompts(3), max_new_tokens=6)
        st = decode_stats()
        assert st["cost_profiles"] >= 2  # decode + mixed at least
        profs = eng._cost.statusz()["profiles"]
        assert any("mixed" in k for k in profs)
        assert any("decode" in k for k in profs)
        for p in profs.values():
            assert p["source"] == "hlo"
            assert p["flops"] > 0
            assert p["bytes_accessed"] > 0

    def test_trackers_stamp_cost_sig(self, model):
        eng = _engine(model)
        eng.generate(_prompts(2), max_new_tokens=4)
        assert eng._mixed_fn.cost_sig is not None
        assert eng._decode_fn.cost_sig is not None
        assert eng._mixed_fn.cost_sig != eng._decode_fn.cost_sig
        # the signature scheme is the dispatch cache's: per-arg
        # (shape, dtype, weak_type), rooted at the site label
        site, sig = eng._decode_fn.cost_sig
        assert "decode" in site and len(sig) >= 5

    def test_signature_keys_like_dispatch(self):
        import jax.numpy as jnp

        a = jnp.zeros((4, 8), jnp.float32)
        b = jnp.zeros((4, 8), jnp.int8)
        s1 = costmodel.profile_signature("site", (a,))
        assert s1 == costmodel.profile_signature("site", (a,))
        assert s1 != costmodel.profile_signature("other", (a,))
        assert s1 != costmodel.profile_signature("site", (b,))
        assert s1 != costmodel.profile_signature(
            "site", (jnp.zeros((4, 9), jnp.float32),))

    def test_signature_walks_a_pool_and_never_prints_it(self, monkeypatch):
        """The K/V pool is one pytree operand: it keys by its leaves'
        shapes and dtypes, as the weights do.  Falling back to `repr`
        would fetch both pools to the host (1.4 s of the serve cell's
        set-up on the chip, PR 31)."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import paged_attention as pa

        def no_repr(self):
            raise AssertionError("profile_signature printed the pool")

        monkeypatch.setattr(pa.KVPool, "__repr__", no_repr)
        f32 = pa.KVPool.zeros(2, 2, 4, 16, 8, jnp.float32)
        s1 = costmodel.profile_signature("site", ({}, f32, 3))
        assert s1 == costmodel.profile_signature(
            "site", ({}, pa.KVPool.zeros(2, 2, 4, 16, 8, jnp.float32), 3))
        assert s1 != costmodel.profile_signature(
            "site", ({}, pa.KVPool.zeros(2, 2, 8, 16, 8, jnp.float32), 3))
        assert s1 != costmodel.profile_signature(
            "site", ({}, pa.KVPool.zeros(2, 2, 4, 16, 8, jnp.int8), 3))
        assert s1 != costmodel.profile_signature("site", ({}, f32, 4))

    def test_profile_extraction_never_compiles(self, model):
        """The lower()+cost_analysis() path must not touch the jit's
        executable cache — zero new executables is the armed-mode
        contract."""
        eng = _engine(model)
        eng.generate(_prompts(2), max_new_tokens=4)
        assert eng._decode_fn.fn._cache_size() == 1
        assert eng._mixed_fn.fn._cache_size() == 1
        assert decode_stats()["retraces_after_warmup"] == 0

    def test_analytical_fallback_formula(self):
        c = costmodel.analytical_gpt_cost(
            batch=4, q=1, kv_len=128, layers=2, hidden=64, vocab=100,
            num_heads=4)
        assert c["flops"] > 0 and c["bytes_accessed"] > 0
        c2 = costmodel.analytical_gpt_cost(
            batch=8, q=1, kv_len=128, layers=2, hidden=64, vocab=100,
            num_heads=4)
        assert c2["flops"] > c["flops"]  # more rows, more work

    def test_peaks_resolve_pinned_on_cpu(self):
        peaks = costmodel.resolve_peaks()
        assert peaks["flops"] > 0 and peaks["bytes_per_s"] > 0
        assert peaks["ici_bytes_per_s"] > 0
        assert peaks["source"] in ("cpu-pinned", "flags") or \
            peaks["source"].startswith("autodetect")
        # explicit flags override autodetection (ici keeps its pinned
        # default unless FLAGS_peak_ici_gbps is set too)
        paddle.set_flags({"peak_flops": 123.0, "peak_hbm_gbps": 4.0})
        try:
            p2 = costmodel.resolve_peaks()
            assert p2 == {"flops": 123.0, "bytes_per_s": 4.0e9,
                          "ici_bytes_per_s": costmodel._CPU_PEAK_ICI,
                          "source": "flags"}
        finally:
            paddle.set_flags({"peak_flops": 0.0, "peak_hbm_gbps": 0.0})

    def test_profile_comes_from_the_compiled_twin_where_lowering_has_none(
            self):
        """The TPU backend's `Lowered.cost_analysis()` is None (seen on
        the chip in PR 21: every profile was the analytical fallback);
        its `Compiled.cost_analysis()` is a dict."""
        class Compiled:
            def cost_analysis(self):
                return {"flops": 12.0, "bytes accessed": 34.0}

        class Lowered:
            compiles = 0

            def cost_analysis(self):
                return None

            def compile(self):
                Lowered.compiles += 1
                return Compiled()

        class Fn:
            def lower(self, *args):
                return Lowered()

        assert costmodel._extract_cost_analysis(Fn(), ()) == {
            "flops": 12.0, "bytes_accessed": 34.0}
        assert Lowered.compiles == 1

    def test_compiled_profile_is_the_steps_one_compile(self, model):
        """Where the profile is read from the COMPILED program (every
        step on a TPU; forced here by FLAGS_cost_memory_analysis), the
        jit call that follows reuses that executable: a serve asks the
        backend for exactly as many compiles as with the observatory
        disarmed."""
        import jax.monitoring

        asked = [0]

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                asked[0] += 1

        def serve():
            eng = _engine(model)
            before = asked[0]
            eng.generate(_prompts(2), max_new_tokens=4)
            return eng, asked[0] - before

        forced = costmodel._forced_engines
        costmodel._forced_engines = 0
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            paddle.set_flags({"cost_model": False})
            serve()  # the eager helpers compile once per process
            eng, disarmed = serve()
            assert eng._decode_fn.cost_sig is None
            paddle.set_flags({"cost_model": True,
                              "cost_memory_analysis": True})
            costmodel.clear_profiles()
            eng, armed = serve()
        finally:
            paddle.set_flags({"cost_model": True,
                              "cost_memory_analysis": False})
            costmodel._forced_engines = forced
            jax.monitoring.unregister_event_duration_listener(on_duration)
        for tracker in (eng._decode_fn, eng._mixed_fn):
            # the compiled path was taken
            assert costmodel.profile_by_key(tracker.cost_sig).temp_bytes > 0
        assert disarmed == 2  # the decode and the mixed executable
        assert armed == disarmed

    def test_one_peak_table_keyed_by_device_kind(self):
        import types

        v5e = types.SimpleNamespace(platform="tpu",
                                    device_kind="TPU v5 lite")
        row = costmodel.device_peaks(v5e)
        assert row is costmodel.DEVICE_PEAKS["TPU v5 lite"]
        assert row["flops_bf16"] == 197e12
        assert row["hbm_bytes_per_s"] == 819e9
        # a TPU that is not in the table is an error, never the CPU pin
        other = types.SimpleNamespace(platform="tpu",
                                      device_kind="TPU v9 imaginary")
        with pytest.raises(ValueError, match="TPU v9 imaginary"):
            costmodel.device_peaks(other)
        cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
        assert costmodel.device_peaks(cpu) is costmodel.DEVICE_PEAKS["cpu"]


# ---------------------------------------------------------------------------
# calibrated prediction
# ---------------------------------------------------------------------------
class TestCalibration:
    def test_records_carry_predicted_vs_actual(self, model):
        eng = _engine(model, flight_window=256)
        eng.generate(_prompts(3), max_new_tokens=8)
        costs = [r["cost"] for r in eng._flight.records()
                 if r.get("kind") == "step" and r.get("cost")]
        assert costs, "no cost records"
        for c in costs:
            assert c["predicted_s"] > 0
            assert c["actual_s"] > 0
            assert c["fn"] in ("decode", "mixed", "spec")

    def test_compile_steps_never_calibrate(self, model):
        """A step whose wall includes an XLA compile must not poison
        the calibration — the first record of each kind predicts from
        1.0 (calibrated=False) and the factor is learned only from
        compile-free steps."""
        eng = _engine(model, flight_window=256)
        eng.generate(_prompts(3), max_new_tokens=8)
        by_fn = {}
        for r in eng._flight.records():
            c = r.get("cost")
            if c:
                by_fn.setdefault(c["fn"], []).append(c)
        for fn, cs in by_fn.items():
            assert cs[0]["calibrated"] is False, fn
        # decode steps dominate the serve: once the compile-bearing
        # first step is skipped, the rest calibrate
        assert by_fn["decode"][-1]["calibrated"] is True
        calib = eng._cost.calibration_wire()
        # the compile (hundreds of ms against a sub-ms raw cost) would
        # have pushed the factor orders of magnitude higher
        assert 0 < calib["decode"] < 1e4

    def test_predict_step_cost_and_error_gauge(self, model):
        obs.reset()
        eng = _engine(model)
        eng.generate(_prompts(4), max_new_tokens=12)
        pred = eng._cost.predict_step_cost()
        assert 0 < pred < 10.0  # seconds; sane for a toy CPU step
        # explicit composition: a spec-less engine predicts decode
        p2 = eng._cost.predict_step_cost(
            {"active": 2, "prefilling": 0, "decoding": 2,
             "spec": False, "chunked": True})
        assert p2 > 0
        snap = obs.snapshot()
        series = snap["paddle_step_cost_error_ratio"]["series"]
        assert any(s["labels"] == {"fn": "decode"} for s in series)

    def test_roofline_gauges_set(self, model):
        obs.reset()
        eng = _engine(model)
        eng.generate(_prompts(3), max_new_tokens=8)
        snap = obs.snapshot()
        mfu = {tuple(s["labels"].items()): s["value"]
               for s in snap["paddle_phase_mfu"]["series"]}
        bw = {tuple(s["labels"].items()): s["value"]
              for s in snap["paddle_phase_hbm_util"]["series"]}
        assert (("phase", "decode"),) in mfu
        assert (("phase", "decode"),) in bw
        assert all(v >= 0 for v in mfu.values())

    def test_spec_round_calibrates_spec_kind(self, model):
        eng = _engine(model, spec_decode_k=2, flight_window=256)
        eng.generate(_prompts(3), max_new_tokens=8)
        assert "spec" in eng._cost.calibration_wire()
        profs = eng._cost.statusz()["profiles"]
        assert any("verify" in k for k in profs)


# ---------------------------------------------------------------------------
# the HBM ledger
# ---------------------------------------------------------------------------
class TestLedger:
    def test_reconciles_against_live_arrays(self, model):
        obs.reset()
        eng = _engine(model)
        eng.generate(_prompts(2), max_new_tokens=4)
        led = eng._cost.hbm_ledger(set_gauges=True)
        cats = led["categories"]
        assert cats["weights"] > 0
        assert cats["kv_pages"] == eng._kv.k.nbytes + \
            eng._kv.v.nbytes
        # the reconciliation identity: attributed + unattributed is
        # EXACTLY the live total (temp_scratch sits outside it)
        live_cats = sum(v for k, v in cats.items()
                        if k != "temp_scratch")
        assert live_cats == led["attributed_bytes"]
        assert led["attributed_bytes"] + led["unattributed_bytes"] \
            == led["total_live_bytes"]
        snap = obs.snapshot()
        rows = snap["paddle_hbm_ledger_bytes"]["series"]
        got = {s["labels"]["category"] for s in rows}
        assert got == set(costmodel.LEDGER_CATEGORIES)
        assert snap["paddle_hbm_ledger_unattributed_bytes"]["series"]

    def test_quantized_pool_attributes_scales(self, model):
        eng = _engine(model, kv_quant="int8")
        eng.generate(_prompts(2), max_new_tokens=4)
        led = eng._cost.hbm_ledger()
        assert led["categories"]["kv_scales"] == \
            eng._kv.k_scales.nbytes + eng._kv.v_scales.nbytes

    def test_draft_pool_category(self, model):
        from paddle_tpu.inference.speculative import DraftModelDrafter

        draft = _model(hidden=16, heads=2)
        eng = _engine(model, spec_decode_k=2,
                      drafter=DraftModelDrafter(draft))
        eng.generate(_prompts(2), max_new_tokens=4)
        led = eng._cost.hbm_ledger()
        assert led["categories"]["draft_pool"] > 0


# ---------------------------------------------------------------------------
# headroom + cost-gated admission
# ---------------------------------------------------------------------------
class TestHeadroomAndAdmission:
    def test_headroom_fields_and_bounds(self, model):
        eng = _engine(model)
        reqs = eng.generate(_prompts(2), max_new_tokens=4)
        hr = eng._cost.headroom()
        assert 0 <= hr["admissible_slots"] <= hr["free_slots"] == 2
        assert hr["predicted_step_s"] > 0
        assert hr["slo_ok"] is True and hr["tightest_tpot_ms"] is None
        assert hr["free_pool_bytes"] > 0

    def test_slo_ceiling_zeroes_headroom(self, model):
        # a 1-FLOP/s "device" makes every predicted step astronomically
        # slow: a declared tpot target can never be met, headroom reads 0
        paddle.set_flags({"peak_flops": 1.0, "peak_hbm_gbps": 1e-9})
        try:
            eng = _engine(model)
            r = eng.add_request(_prompts(1)[0], max_new_tokens=8,
                                slo_tpot_ms=0.001)
            eng.step()
            assert eng._cost.headroom()["slo_ok"] is False
            assert eng._cost.headroom()["admissible_slots"] == 0
        finally:
            paddle.set_flags({"peak_flops": 0.0, "peak_hbm_gbps": 0.0})

    def test_admission_gate_defers_until_affordable(self, model):
        """FLAGS_sched_cost_admission: with an impossible predicted
        cost, an SLO-carrying candidate waits while the engine is
        busy, and the idle guard admits it once the engine drains —
        the gate shapes load, it never livelocks a drain loop."""
        paddle.set_flags({"sched_cost_admission": True,
                          "peak_flops": 1.0, "peak_hbm_gbps": 1e-9})
        try:
            eng = _engine(model, max_batch_size=1)
            runner = eng.add_request(_prompts(1)[0], max_new_tokens=6)
            cand = eng.add_request(_prompts(1, seed=1)[0],
                                   max_new_tokens=4, slo_tpot_ms=0.001)
            # the synchronous schedule: the runner's last token lands
            # before the next admission (under the lookahead its slot
            # is free, and the candidate in, while that token is in
            # flight)
            while eng._busy():
                eng.step()
                eng.drain()
            assert runner.finish_reason == "length"
            assert cand.finish_reason == "length"
            # the candidate entered only after the runner left
            assert cand.t_admit_ns > runner.t_finish_ns
        finally:
            paddle.set_flags({"sched_cost_admission": False,
                              "peak_flops": 0.0, "peak_hbm_gbps": 0.0})

    def test_gate_off_is_admission_order_neutral(self, model):
        """Default FLAGS_sched_cost_admission=0: SLO-carrying requests
        admit in arrival order even when the predictor would have
        deferred them."""
        paddle.set_flags({"peak_flops": 1.0, "peak_hbm_gbps": 1e-9})
        try:
            eng = _engine(model, max_batch_size=1)
            runner = eng.add_request(_prompts(1)[0], max_new_tokens=6)
            cand = eng.add_request(_prompts(1, seed=1)[0],
                                   max_new_tokens=4, slo_tpot_ms=0.001)
            eng.step()
            assert runner.state == "running"
            eng.run()
            assert cand.t_admit_ns < runner.t_finish_ns or \
                eng._slots == 1  # 1-slot engine: admitted at drain
        finally:
            paddle.set_flags({"peak_flops": 0.0, "peak_hbm_gbps": 0.0})


# ---------------------------------------------------------------------------
# statusz / artifacts / explain
# ---------------------------------------------------------------------------
class TestSurfaces:
    def test_statusz_cost_section(self, model):
        eng = _engine(model)
        eng.generate(_prompts(2), max_new_tokens=4)
        z = eng.statusz()
        c = z["cost"]
        for key in ("peaks", "profiles", "calibration", "error_ratio",
                    "ledger", "headroom"):
            assert key in c, key
        json.dumps(z)  # JSON-serializable end to end
        assert "cost:" in eng.statusz_text()

    def test_statusz_thread_safe_midserve(self, model):
        import threading

        eng = _engine(model)
        reqs = [eng.add_request(p, max_new_tokens=12)
                for p in _prompts(3)]
        stop = threading.Event()
        errs = []

        def hammer():
            while not stop.is_set():
                try:
                    json.dumps(eng.statusz()["cost"])
                except Exception as e:  # noqa: BLE001
                    errs.append(repr(e))

        t = threading.Thread(target=hammer)
        t.start()
        try:
            eng.run()
        finally:
            stop.set()
            t.join()
        assert not errs, errs[:3]

    def test_explain_request_renders_pred_vs_actual(self, model):
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "tools"))
        from explain_request import explain

        eng = _engine(model, flight_window=256)
        reqs = eng.generate(_prompts(2), max_new_tokens=6)
        window = eng._flight.snapshot()
        # the last record with a batch (the engine's last call lands
        # the step in flight with its slots already empty)
        rid = next((rec["slots"][0]["request"]
                    for rec in reversed(window["records"])
                    if rec.get("slots")), 0)
        lines = explain(window, rid)
        assert any("pred=" in ln and "/act=" in ln for ln in lines), \
            lines[:10]


# ---------------------------------------------------------------------------
# the calibration wire: recover / restore
# ---------------------------------------------------------------------------
class TestWire:
    def test_wire_config_carries_live_calibration(self, model):
        eng = _engine(model)
        eng.generate(_prompts(3), max_new_tokens=8)
        wc = eng.wire_config()
        assert wc["cost_model"] is True
        assert wc["cost_calibration"] == eng._cost.calibration_wire()
        assert wc["cost_calibration"].get("decode", 0) > 0

    def test_ctor_seed_loads_calibration(self, model):
        eng = _engine(model, cost_calibration={"decode": 7.5})
        assert eng._cost.calibration_wire() == {"decode": 7.5}

    def test_recover_carries_calibration(self, model):
        from paddle_tpu.inference import resilience

        eng = _engine(model)
        eng.generate(_prompts(3), max_new_tokens=8)
        calib = eng._cost.calibration_wire()
        assert calib
        new = resilience.recover(eng)
        assert new._cost.calibration_wire() == calib

    def test_restore_rebuilds_calibration(self, model, tmp_path):
        from paddle_tpu.inference.durability import restore_from_dir

        jd = str(tmp_path / "journal")
        eng = _engine(model, journal_dir=jd)
        eng.generate(_prompts(3), max_new_tokens=8)
        calib = eng._cost.calibration_wire()
        assert calib
        eng._durability.write_snapshot()
        eng._durability.close()
        eng2, reqs = restore_from_dir(jd, model)
        assert eng2._cost.calibration_wire() == calib
        eng2._durability.close()


# ---------------------------------------------------------------------------
# disarmed: bit-exact, zero profiles
# ---------------------------------------------------------------------------
class TestDisarmed:
    def test_off_engine_bit_exact_and_quiet(self, model):
        reset_decode_stats()
        eng_on = _engine(model, cost_model=True)
        outs_on = eng_on.generate(_prompts(3), max_new_tokens=8)
        reset_decode_stats()
        eng_off = _engine(model, cost_model=False)
        outs_off = eng_off.generate(_prompts(3), max_new_tokens=8)
        st = decode_stats()
        assert outs_on == outs_off
        assert eng_off._cost is None
        assert st["cost_profiles"] == 0 and st["cost_updates"] == 0
        assert "cost" not in eng_off.statusz()
        assert all("cost" not in r for r in eng_off._flight.records())
        # profile EXTRACTION follows the global flag (process-wide
        # observability, shared table); the engine kwarg disarms this
        # engine's predictor/ledger/calibration — so the tracker may
        # still stamp a signature here, and the flag-disarmed test
        # below pins the zero-extraction path

    def test_flag_disarms_globally(self, model):
        # isolate the pure-flag path: earlier tests in this process
        # armed engines EXPLICITLY (cost_model=True), which latches
        # extraction on by design — park that latch for this test
        forced = costmodel._forced_engines
        costmodel._forced_engines = 0
        paddle.set_flags({"cost_model": False})
        try:
            reset_decode_stats()
            eng = _engine(model)
            eng.generate(_prompts(2), max_new_tokens=4)
            assert eng._cost is None
            assert decode_stats()["cost_profiles"] == 0
            assert eng._decode_fn.cost_sig is None
        finally:
            paddle.set_flags({"cost_model": True})
            costmodel._forced_engines = forced

    def test_flag_armed_engines_never_latch_extraction(self, model):
        """An engine armed by the FLAG default (or by recover()
        re-passing the resolved cost_model=True) must not pin
        extraction past a later FLAGS_cost_model=0 — only an explicit
        opt-in AGAINST a disabled flag latches."""
        from paddle_tpu.inference import resilience

        before = costmodel._forced_engines
        eng = _engine(model)                      # flag-defaulted
        eng.generate(_prompts(1), max_new_tokens=2)
        new = resilience.recover(eng)             # explicit resolved arg
        assert costmodel._forced_engines == before
        assert new._cost is not None

    def test_explicit_arm_overrides_disabled_flag(self, model):
        """flags.py promises 'engines constructed with an explicit
        cost_model= ignore the flag' — with the flag OFF, an
        explicitly armed engine still extracts HLO profiles and
        predicts from them."""
        costmodel.clear_profiles()
        paddle.set_flags({"cost_model": False})
        try:
            eng = _engine(model, cost_model=True)
            eng.generate(_prompts(2), max_new_tokens=4)
            assert eng._cost is not None
            assert eng._decode_fn.cost_sig is not None
            profs = eng._cost.statusz()["profiles"]
            assert any(p["source"] == "hlo" for p in profs.values())
        finally:
            paddle.set_flags({"cost_model": True})
