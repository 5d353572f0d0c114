"""Profiling plane (observability.profiling): sampled device-sync
probes, hot-op attribution, bounded capture sessions, the /profilez +
/tracez ops endpoints, and the dropped-span counter.  The disarmed
path (profile=0, the default) is pinned bit-exact with zero probes.
Ratios (overhead, attribution, drift) are asserted for sanity only:
CPU steps are too small for a gate on them.
"""
import json
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (DecodeEngine, decode_stats,
                                          reset_decode_stats)
from paddle_tpu.observability import profiling, tracing
from paddle_tpu.observability.alerts import SIGNALS, default_rules


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


@pytest.fixture(scope="module")
def reference(model):
    """Profile-off greedy outputs — the bit-exact parity oracle."""
    eng = _engine(model)
    return eng.generate(_prompts(3), max_new_tokens=6)


@pytest.fixture(scope="module")
def served(model, reference):
    """ONE armed engine (probe every step) serving the reference
    workload, shared by the read-only assertions below — the module's
    compile budget is the suite's dominant cost."""
    reset_decode_stats()
    eng = _engine(model, profile=True, profile_sample_steps=1)
    outs = eng.generate(_prompts(3), max_new_tokens=6)
    return eng, outs, decode_stats()


# ---------------------------------------------------------------------------
# disarmed: the default path is bit-exact with zero probes
# ---------------------------------------------------------------------------
class TestDisarmed:
    def test_off_by_default_and_quiet(self, model, reference):
        reset_decode_stats()
        eng = _engine(model)
        assert eng._profiling is None
        outs = eng.generate(_prompts(3), max_new_tokens=6)
        assert outs == reference
        st = decode_stats()
        assert st["profile_probes"] == 0
        assert st["profile_captures"] == 0
        # no probe keys ever land on the flight records
        assert all("probe" not in r for r in eng._flight.records())
        assert "profiling" not in eng.statusz()

    def test_explicit_false_beats_flag(self, model):
        paddle.set_flags({"profile": True})
        try:
            eng = _engine(model, profile=False)
        finally:
            paddle.set_flags({"profile": False})
        assert eng._profiling is None

    def test_flag_arms(self, model):
        paddle.set_flags({"profile": True,
                          "profile_sample_steps": 5})
        try:
            eng = _engine(model)
        finally:
            paddle.set_flags({"profile": False,
                              "profile_sample_steps": 64})
        assert eng._profiling is not None
        assert eng._profiling.sample_steps == 5


# ---------------------------------------------------------------------------
# armed: probes, parity, gauges, records
# ---------------------------------------------------------------------------
class TestProbes:
    def test_parity_and_zero_new_executables(self, served, reference):
        eng, outs, st = served
        assert outs == reference  # blocking changes no numerics
        assert eng._decode_fn.fn._cache_size() == 1
        assert eng._mixed_fn.fn._cache_size() == 1
        assert st["retraces_after_warmup"] == 0

    def test_every_step_probed_with_device_host_split(self, served):
        eng, _, st = served
        recs = [r for r in eng._flight.records()
                if r.get("kind") == "step"]
        assert recs and all("probe" in r for r in recs)
        assert st["profile_probes"] == len(recs)
        for r in recs:
            pr = r["probe"]
            assert pr["device_s"] > 0
            assert pr["host_s"] >= 0
            # the split is exhaustive against the step wall
            assert pr["device_s"] + pr["host_s"] == \
                pytest.approx(r["dur_s"], rel=1e-6, abs=1e-9)
            # probes key by DISPATCHED executable kind, never the
            # flight phase (a chunkless full mixed step runs the
            # mixed program under the "decode" phase)
            assert set(pr["device"]) <= set(profiling.PROBE_KINDS)

    def test_gauges_set(self, served):
        eng, _, _ = served
        eid = eng._engine_id
        assert obs.EXEC_DEVICE_SECONDS.value(fn="decode") > 0
        ratio = obs.HOST_OVERHEAD_RATIO.value(engine=eid)
        assert 0.0 <= ratio < 1.0
        assert obs.PHASE_MFU_MEASURED.value(phase="decode") > 0
        drift = obs.MFU_DRIFT.value(phase="decode")
        # sub-ms CPU dispatches are timer-noise dominated, so only
        # sanity is asserted here
        assert drift >= 0.0 and np.isfinite(drift)

    def test_statusz_section(self, served):
        eng, _, _ = served
        z = eng.statusz()["profiling"]
        json.dumps(z)  # the whole section is JSON-serializable
        assert z["sample_steps"] == 1
        assert z["probes"] > 0
        assert z["probe_seconds"] > 0
        assert "decode" in z["device_seconds"]
        d = z["device_seconds"]["decode"]
        assert d["probes"] > 0 and d["mean_s"] > 0
        assert z["host_overhead_ratio"] is not None
        assert z["mfu_drift"]

    def test_sampling_cadence(self, model):
        reset_decode_stats()
        eng = _engine(model, profile=True, profile_sample_steps=3)
        eng.generate(_prompts(2), max_new_tokens=9)
        recs = [r for r in eng._flight.records()
                if r.get("kind") == "step"]
        probed = [r for r in recs if "probe" in r]
        # every 3rd step probes (the profiler's own step counter)
        assert 0 < len(probed) < len(recs)
        assert len(probed) == len(recs) // 3

    def test_spec_verify_probed(self, model):
        eng = _engine(model, profile=True, profile_sample_steps=1,
                      spec_decode_k=2)
        eng.generate(_prompts(2, seed=3), max_new_tokens=6)
        tab = eng._profiling.device_table()
        assert "verify" in tab and tab["verify"]["probes"] > 0


# ---------------------------------------------------------------------------
# hot-op attribution
# ---------------------------------------------------------------------------
class TestHotOps:
    def test_hot_ops_on_this_engines_profiles(self, served):
        """Every executable THIS engine compiled while armed carries a
        top-K table, resolved by exact signature — robust against
        other engines in the process sharing a site label at
        different shapes (the site-keyed profiles() view is
        last-writer-wins and may be shadowed)."""
        from paddle_tpu.observability import costmodel

        eng, _, _ = served
        for tracker in (eng._decode_fn, eng._mixed_fn):
            prof = costmodel.profile_by_key(tracker.cost_sig)
            assert prof is not None and prof.hot_ops, tracker.site
            rows = [dict(r) for r in prof.hot_ops]
            assert len(rows) <= profiling.HOT_OP_TOP_K
            flops = [r["flops"] for r in rows]
            assert flops == sorted(flops, reverse=True)
            # a GPT step; rows key dot_general by operand dtypes
            assert rows[0]["op"] == "dot_general[f32xf32]"
            for r in rows:
                assert 0.0 <= r["flops_frac"] <= 1.0
                assert 0.0 <= r["bytes_frac"] <= 1.0
                assert r["count"] >= 1

    def test_statusz_surfaces_hot_ops(self, served):
        eng, _, _ = served
        hot = eng._profiling.statusz()["hot_ops"]
        assert any("decode" in site for site in hot)
        assert any("mixed" in site for site in hot)

    def test_hot_op_table_direct(self):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda a, b: jnp.tanh(a @ b) + 1.0)
        rows = profiling.hot_op_table(
            f, (jnp.ones((8, 16)), jnp.ones((16, 4))))
        by_op = {r["op"]: r for r in rows}
        assert rows[0]["op"] == "dot_general[f32xf32]"
        assert by_op["dot_general[f32xf32]"]["flops"] == \
            pytest.approx(2 * 8 * 16 * 4)
        assert "tanh" in by_op

    def test_hot_op_table_splits_dot_dtypes(self):
        """The satellite bugfix this PR rides on: an int8-weight dot
        and an f32 dot in ONE executable must land in SEPARATE rows —
        aggregated, the weight-quant before/after instrument is
        blind."""
        import jax
        from jax import lax
        import jax.numpy as jnp

        def f(x, w_f32, w_q, s):
            a = x @ w_f32
            b = lax.dot_general(
                x, w_q,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * s
            return a + b

        M, K, N = 8, 16, 4
        rows = profiling.hot_op_table(jax.jit(f), (
            jnp.ones((M, K)), jnp.ones((K, N)),
            jnp.ones((K, N), jnp.int8), jnp.ones((N,))))
        by_op = {r["op"]: r for r in rows}
        assert "dot_general[f32xf32]" in by_op
        assert "dot_general[f32xs8]" in by_op
        assert by_op["dot_general[f32xf32]"]["flops"] == \
            pytest.approx(2 * M * K * N)
        assert by_op["dot_general[f32xs8]"]["flops"] == \
            pytest.approx(2 * M * K * N)
        # the s8 operand is the byte win: the int8 dot's traffic must
        # be smaller than the f32 dot's by about the weight shrink
        assert by_op["dot_general[f32xs8]"]["bytes"] < \
            by_op["dot_general[f32xf32]"]["bytes"]

    def test_hot_op_table_grouped_conv_flops(self):
        """Grouping is already folded into the kernel's in-channel
        dim: a depthwise conv must count its real MACs per output
        element, not be divided by the group count a second time."""
        import jax
        from jax import lax
        import jax.numpy as jnp

        C, K = 16, 3
        x = jnp.ones((1, C, 12, 12))
        w = jnp.ones((C, 1, K, K))  # depthwise: groups == C

        f = jax.jit(lambda a, b: lax.conv_general_dilated(
            a, b, (1, 1), "VALID", feature_group_count=C))
        rows = profiling.hot_op_table(f, (x, w))
        conv = {r["op"]: r for r in rows}["conv_general_dilated"]
        out_elems = 1 * C * 10 * 10
        assert conv["flops"] == pytest.approx(2 * out_elems * K * K)


# ---------------------------------------------------------------------------
# capture sessions
# ---------------------------------------------------------------------------
class TestCapture:
    def test_bounded_capture_with_device_track(self, model):
        obs.clear_spans()
        reset_decode_stats()
        eng = _engine(model, profile=True,
                      profile_sample_steps=1000)  # sampling ~never
        st0 = profiling.request_capture(3, engine=eng)
        assert st0["pending_steps"] == 3
        eng.generate(_prompts(2, seed=5), max_new_tokens=8)
        st = eng._profiling.capture_status()
        assert st["captured_steps"] == 3
        assert st["remaining_steps"] == 0
        assert st["captures_completed"] == 1
        assert decode_stats()["profile_captures"] == 1
        # exactly the captured steps probed (cadence never fires)
        probed = [r for r in eng._flight.records() if "probe" in r]
        assert len(probed) == 3
        # probe spans landed on the device track
        trace = obs.merged_chrome_trace()
        pids = {e["args"]["name"]: e["pid"]
                for e in trace["traceEvents"]
                if e.get("ph") == "M" and e.get("name") == "process_name"}
        assert "device" in pids
        spans = [e for e in trace["traceEvents"]
                 if e.get("ph") == "X" and e["pid"] == pids["device"]]
        assert len(spans) == 3
        assert all(e["tid"] == eng._engine_id for e in spans)

    def test_mixed_executable_probes_attribute_as_mixed(self, model):
        """A chunked engine whose prompts outlive one chunk runs
        mixed-executable steps under several flight phases — every
        one of those probes must land on the 'mixed' kind, or the
        decode calibration would interleave samples from two
        different programs and whipsaw the drift."""
        eng = _engine(model, profile=True, profile_sample_steps=1,
                      prefill_chunk_tokens=4)
        eng.generate(_prompts(2, length=12, seed=13),
                     max_new_tokens=4)
        recs = [r for r in eng._flight.records()
                if r.get("kind") == "step" and r.get("probe")]
        mixed_phases = {ph for r in recs
                        for ph in r["phases"]
                        if ph in ("prefill", "mixed")}
        assert mixed_phases  # chunked prefill steps actually ran
        kinds = {k for r in recs for k in r["probe"]["device"]}
        assert kinds <= {"decode", "mixed"}
        assert "mixed" in eng._profiling.device_table()

    def test_deregister_stops_inflight_jax_trace(self, model):
        """A capture interrupted by engine retirement must not leak
        the process-global jax profiler trace (the engine thread that
        would have disarmed it is gone)."""
        from paddle_tpu.inference.durability import \
            retire_engine_series

        eng = _engine(model, profile=True)
        prof = eng._profiling
        prof._jax_trace = True  # as if a capture armed the trace
        retire_engine_series(eng._engine_id)
        assert prof._jax_trace is False

    def test_request_capture_validation_and_resolution(self, model):
        with pytest.raises(ValueError, match="steps >= 1"):
            profiling.request_capture(0)
        eng = _engine(model, profile=True)
        assert profiling.profiler_for(eng) is eng._profiling
        assert profiling.profiler_for(eng._engine_id) \
            is eng._profiling
        with pytest.raises(ValueError, match="no armed profiler"):
            profiling.profiler_for(10 ** 9)

    @pytest.mark.slow
    def test_jax_trace_wrapping_tolerant(self, model, tmp_path):
        """FLAGS_profile_dir wraps the capture in a jax profiler
        trace when the backend supports it; the capture itself must
        complete either way.  Slow lane: jax.profiler's collection /
        write dominates (~6s) and the capture machinery itself is
        pinned tier-1 by test_bounded_capture_with_device_track —
        tier-1 sits within ~2s of its 870s budget."""
        paddle.set_flags({"profile_dir": str(tmp_path)})
        try:
            eng = _engine(model, profile=True,
                          profile_sample_steps=1000)
            eng._profiling.request_capture(2)
            eng.generate(_prompts(1, seed=7), max_new_tokens=6)
        finally:
            paddle.set_flags({"profile_dir": ""})
        st = eng._profiling.capture_status()
        assert st["captures_completed"] == 1
        if st["trace_path"]:
            import os

            assert os.path.isdir(st["trace_path"])


# ---------------------------------------------------------------------------
# ops endpoints: /profilez + /tracez
# ---------------------------------------------------------------------------
class TestEndpoints:
    def test_profilez_and_tracez(self, served):
        from paddle_tpu.observability import opsserver

        eng, _, _ = served
        port = opsserver.start_ops_server(port=0, host="127.0.0.1")
        try:
            def get(path):
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}",
                        timeout=10) as r:
                    return r.status, json.loads(r.read().decode())

            code, z = get(f"/profilez?engine={eng._engine_id}")
            assert code == 200
            assert z["engine"] == eng._engine_id
            assert {"capture", "device_seconds", "hot_ops",
                    "mfu_drift"} <= set(z)
            code, tr = get("/tracez?n=50")
            assert code == 200
            metas = [e for e in tr["traceEvents"]
                     if e.get("ph") == "M"]
            rest = [e for e in tr["traceEvents"]
                    if e.get("ph") != "M"]
            assert metas and len(rest) <= 50
            assert tr["total_events"] >= len(rest)
            assert tr["dropped_spans"] == tracing.dropped_span_count()
            # a clipped payload keeps the NEWEST events by timestamp
            # (the merged trace concatenates whole tracks, so a
            # positional tail would drop the host track wholesale)
            if tr["clipped_events"]:
                kept = min(e.get("ts", 0.0) for e in rest)
                assert kept >= 0
                ts = [e.get("ts", 0.0) for e in rest]
                assert ts == sorted(ts)
        finally:
            opsserver.stop_ops_server()

    def test_profilez_404_when_disarmed(self, model):
        from urllib.error import HTTPError

        from paddle_tpu.observability import opsserver

        eng = _engine(model)  # profile off
        port = opsserver.start_ops_server(port=0, host="127.0.0.1")
        try:
            with pytest.raises(HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/profilez"
                    f"?engine={eng._engine_id}", timeout=10)
            assert ei.value.code == 404
            assert "profiling" in json.loads(
                ei.value.read().decode())["error"]
        finally:
            opsserver.stop_ops_server()


# ---------------------------------------------------------------------------
# the dropped-span counter (satellite: tracing overflow surfaced)
# ---------------------------------------------------------------------------
def test_dropped_span_counter(monkeypatch):
    obs.clear_spans()
    before = obs.TRACE_SPANS_DROPPED.value()
    monkeypatch.setattr(tracing, "MAX_SPANS", 2)
    for i in range(5):
        tracing.record_span("t", f"s{i}", 0, 10)
    assert tracing.span_count() == 2
    assert tracing.dropped_span_count() == 3
    assert obs.TRACE_SPANS_DROPPED.value() == before + 3
    obs.clear_spans()


# ---------------------------------------------------------------------------
# alert rule + signal
# ---------------------------------------------------------------------------
class TestMfuRegressionRule:
    def test_rule_in_catalog(self):
        rules = {r.name: r for r in default_rules()}
        r = rules["mfu_regression"]
        assert r.signal == "mfu_drift_max"
        assert r.severity == "ticket"
        assert r.threshold == 0.5

    def test_drift_scores_independent_prediction(self, model):
        """The drift is a PREDICTION error (raw roofline seconds x a
        learned per-phase factor vs measured device seconds), not two
        timers of the same dispatch: a steady device converges to
        zero drift, and a device suddenly running 4x its calibrated
        cost moves the gauge.  Driven with synthetic probe records so
        the sequence is deterministic."""
        eng = _engine(model, profile=True, profile_sample_steps=1000)
        prof = eng._profiling
        raw = eng._cost.raw_seconds(eng._cost.profile_for("decode"))

        def observe(dv):
            prof._pending_sig = prof._tracker_sig()
            prof.observe({"kind": "step", "dur_s": dv * 1.1,
                          "probe": {"device": {"decode": dv},
                                    "device_s": dv,
                                    "host_s": dv * 0.1},
                          "phases": {"decode": dv}})

        steady = raw * 2.0  # the "hardware" runs at half the peaks
        for _ in range(6):
            observe(steady)
        assert prof._dev_calib["decode"] == pytest.approx(2.0)
        assert prof.drift_table()["decode"] == pytest.approx(0.0)
        observe(steady * 4.0)  # a 4x device slowdown
        moved = prof.drift_table()["decode"]
        assert moved > 0.15  # the regime change registered
        # and a probe on a compile-bearing step (sig mismatch) never
        # moves the calibration or the drift
        before = dict(prof._dev_calib), prof.drift_table()
        prof._pending_sig = ("stale", 0)
        prof.observe({"kind": "step", "dur_s": steady,
                      "probe": {"device": {"decode": steady * 50},
                                "device_s": steady * 50, "host_s": 0},
                      "phases": {"decode": steady * 50}})
        assert (dict(prof._dev_calib), prof.drift_table()) == before

    def test_compile_steps_never_calibrate(self, model):
        """The first probe of each executable kind blocks on its XLA
        compile — the tracker-sig trick must keep that wall out of
        the device calibration (the costmodel/watchdog contract)."""
        eng = _engine(model, profile=True, profile_sample_steps=1)
        eng.generate(_prompts(1, seed=11), max_new_tokens=3)
        calib = dict(eng._profiling._dev_calib)
        # the mixed executable ran exactly once (the compile step):
        # probed, but never calibrated
        assert "mixed" not in calib
        # decode ran compile + clean steps: calibrated from the clean
        # ones — the factor describes execution, not XLA
        assert "decode" in calib

    def test_signal_no_evidence_then_reads_own_table(self, model,
                                                     served):
        sig = SIGNALS["mfu_drift_max"]
        eng_off = _engine(model)
        assert sig(eng_off) is None  # plane disarmed: no evidence
        eng, _, _ = served
        v = sig(eng)
        assert v is not None
        assert v == max(eng._profiling.drift_table().values())


# ---------------------------------------------------------------------------
# wire config + retirement
# ---------------------------------------------------------------------------
class TestWireAndRetire:
    def test_wire_config_carries_probe_config(self, model):
        eng = _engine(model, profile=True, profile_sample_steps=7)
        kw = eng.wire_config()
        assert kw["profile"] is True
        assert kw["profile_sample_steps"] == 7
        json.dumps(kw)
        # a rebuilt engine (recover/restore path) probes at the same
        # cadence without any flag armed
        kw.pop("dtype", None)
        rebuilt = DecodeEngine(model, **kw)
        assert rebuilt._profiling is not None
        assert rebuilt._profiling.sample_steps == 7

    def test_retire_clears_registry_and_series(self, model):
        from paddle_tpu.inference.durability import \
            retire_engine_series

        eng = _engine(model, profile=True, profile_sample_steps=1)
        eng.generate(_prompts(1, seed=9), max_new_tokens=4)
        eid = eng._engine_id
        assert obs.HOST_OVERHEAD_RATIO.value(engine=eid) >= 0
        assert profiling.profiler_for(eng) is eng._profiling
        retire_engine_series(eid)
        snap = obs.snapshot()
        rows = snap.get("paddle_host_overhead_ratio", {}).get(
            "series", [])
        assert all(row["labels"].get("engine") != str(eid)
                   for row in rows)
        with pytest.raises(ValueError):
            profiling.profiler_for(eid)


# ---------------------------------------------------------------------------
# explain_request: the dev=/host= column
# ---------------------------------------------------------------------------
def test_explain_renders_dev_host_column(served):
    import importlib.util
    import os

    eng, _, _ = served
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "explain_request_t15",
        os.path.join(root, "tools", "explain_request.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    window = eng._flight.snapshot()
    rid = mod.request_ids(window)[0]
    text = "\n".join(mod.explain(window, rid))
    assert "dev=" in text and "/host=" in text
