"""Unified observability layer: metrics registry (bucket math, labels,
Prometheus golden format), merged chrome-trace tracks, request-level
TTFT/TPOT instrumentation on a deterministic engine run, view
backward-compatibility, reset invariants, and the shared-lock
thread-safety contract (ISSUE 4)."""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.core import native
from paddle_tpu.observability.metrics import (DEFAULT_TIME_BUCKETS,
                                              MetricRegistry, log_buckets)


@pytest.fixture(autouse=True)
def _clean_slate():
    obs.reset()
    obs.clear_spans()
    obs.enable()
    yield
    obs.reset()
    obs.clear_spans()
    obs.enable()


def _tiny_engine(batch=2, vocab=64, max_seq_len=64, **kw):
    from paddle_tpu.inference.serving import DecodeEngine
    from paddle_tpu.models.gpt import GPT, GPTConfig

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=128,
                    use_parallel_layers=False, dropout=0.0)
    model = GPT(cfg)
    model.eval()
    return DecodeEngine(model, max_batch_size=batch,
                        max_seq_len=max_seq_len, page_size=16, **kw)


# ---------------------------------------------------------------------------
# histogram bucket math
# ---------------------------------------------------------------------------
class TestHistogramMath:
    def test_log_buckets(self):
        b = log_buckets(0.001, 10.0, 4)
        np.testing.assert_allclose(b, (0.001, 0.01, 0.1, 1.0))
        with pytest.raises(ValueError):
            log_buckets(0.0, 2.0, 3)
        with pytest.raises(ValueError):
            log_buckets(1.0, 1.0, 3)

    def test_default_buckets_are_log_spaced(self):
        r = np.diff(np.log(DEFAULT_TIME_BUCKETS))
        np.testing.assert_allclose(r, r[0])

    def test_observe_lands_in_le_bucket(self):
        reg = MetricRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 4.0, 9.0):  # boundaries INCLUDED (le)
            h.observe(v)
        s = h.series_state()
        assert s["counts"] == [2, 1, 1, 1]  # last slot = overflow (+Inf)
        assert s["count"] == 5
        assert s["sum"] == pytest.approx(16.0)

    def test_cumulative_prometheus_counts(self):
        reg = MetricRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0))
        for v in (0.5, 1.5, 3.0):
            h.observe(v)
        txt = reg.prometheus_text()
        assert 'h_bucket{le="1"} 1' in txt
        assert 'h_bucket{le="2"} 2' in txt
        assert 'h_bucket{le="+Inf"} 3' in txt
        assert "h_count 3" in txt

    def test_quantile_estimator(self):
        """ISSUE-14 satellite: `Histogram.quantile` — linear
        interpolation within the winning bucket; the overflow bucket
        clamps to the largest finite bound; empty series answer 0."""
        reg = MetricRegistry()
        h = reg.histogram("h", buckets=(1.0, 2.0, 4.0))
        # empty: no evidence, no estimate
        assert h.quantile(0.5) == 0.0
        # single bucket: 10 observations land in (1, 2]; the median
        # interpolates to the bucket midpoint-ish (rank 5 of 10)
        for _ in range(10):
            h.observe(1.5)
        assert h.quantile(0.5) == pytest.approx(1.5)
        assert h.quantile(1.0) == pytest.approx(2.0)
        # first bucket interpolates from 0
        h2 = reg.histogram("h2", buckets=(1.0, 2.0))
        h2.observe(0.5)
        h2.observe(0.6)
        assert h2.quantile(0.5) == pytest.approx(0.5)
        # all in overflow: clamp to the largest finite bound
        h3 = reg.histogram("h3", buckets=(1.0, 2.0))
        for _ in range(5):
            h3.observe(100.0)
        assert h3.quantile(0.5) == 2.0
        assert h3.quantile(0.99) == 2.0
        # mixed: quantiles walk the cumulative counts (rank q*N lands
        # at the END of its observation, the histogram_quantile rule:
        # rank 1 of the 1-observation first bucket reads its bound)
        h4 = reg.histogram("h4", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.5, 3.0):
            h4.observe(v)
        assert h4.quantile(0.25) == pytest.approx(1.0)
        assert h4.quantile(0.5) == pytest.approx(1.5)
        assert h4.quantile(1.0) == pytest.approx(4.0)
        with pytest.raises(ValueError):
            h4.quantile(1.5)

    def test_quantile_labeled_series(self):
        reg = MetricRegistry()
        h = reg.histogram("h", labels=("k",), buckets=(1.0, 2.0))
        h.observe(1.5, k="a")
        assert h.quantile(0.9, k="a") > 1.0
        assert h.quantile(0.9, k="missing") == 0.0

    def test_unsorted_buckets_rejected(self):
        reg = MetricRegistry()
        with pytest.raises(ValueError):
            reg.histogram("h", buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            reg.histogram("h2", buckets=(1.0, 1.0))


# ---------------------------------------------------------------------------
# labels
# ---------------------------------------------------------------------------
class TestLabels:
    def test_labeled_series_are_distinct(self):
        reg = MetricRegistry()
        c = reg.counter("c", labels=("op",))
        c.inc(op="matmul")
        c.inc(2, op="softmax")
        assert c.value(op="matmul") == 1
        assert c.value(op="softmax") == 2
        txt = reg.prometheus_text()
        assert 'c{op="matmul"} 1' in txt
        assert 'c{op="softmax"} 2' in txt

    def test_wrong_labels_raise(self):
        reg = MetricRegistry()
        c = reg.counter("c", labels=("op",))
        with pytest.raises(ValueError):
            c.inc()  # missing label
        with pytest.raises(ValueError):
            c.inc(shape="x")  # wrong name
        with pytest.raises(ValueError):
            c.inc(op="a", extra="b")  # extra label

    def test_cardinality_backstop(self, monkeypatch):
        from paddle_tpu.observability import metrics as m

        monkeypatch.setattr(m, "MAX_SERIES_PER_METRIC", 4)
        reg = MetricRegistry()
        c = reg.counter("c", labels=("rid",))
        for i in range(4):
            c.inc(rid=i)
        c.inc(rid=0)  # existing series still fine
        with pytest.raises(ValueError, match="cardinality"):
            c.inc(rid=99)

    def test_label_value_escaping(self):
        reg = MetricRegistry()
        g = reg.gauge("g", labels=("p",))
        g.set(1, p='a"b\\c\nd')
        assert r'g{p="a\"b\\c\nd"} 1' in reg.prometheus_text()

    def test_conflicting_reregistration_rejected(self):
        reg = MetricRegistry()
        reg.counter("m", labels=("a",))
        assert reg.counter("m", labels=("a",)) is reg.counter(
            "m", labels=("a",))
        with pytest.raises(ValueError):
            reg.gauge("m", labels=("a",))
        with pytest.raises(ValueError):
            reg.counter("m", labels=("b",))


# ---------------------------------------------------------------------------
# Prometheus golden format
# ---------------------------------------------------------------------------
class TestPrometheusGolden:
    def test_exact_text(self):
        reg = MetricRegistry()
        c = reg.counter("app_requests_total", help="total requests",
                        labels=("reason",))
        g = reg.gauge("app_level", help="a level")
        h = reg.histogram("app_latency_seconds", help="latency",
                          buckets=(0.1, 1.0))
        c.inc(3, reason="eos")
        c.inc(1, reason="length")
        g.set(0.5)
        h.observe(0.05)
        h.observe(0.5)
        h.observe(2.0)
        assert reg.prometheus_text() == (
            "# HELP app_latency_seconds latency\n"
            "# TYPE app_latency_seconds histogram\n"
            'app_latency_seconds_bucket{le="0.1"} 1\n'
            'app_latency_seconds_bucket{le="1"} 2\n'
            'app_latency_seconds_bucket{le="+Inf"} 3\n'
            "app_latency_seconds_sum 2.55\n"
            "app_latency_seconds_count 3\n"
            "# HELP app_level a level\n"
            "# TYPE app_level gauge\n"
            "app_level 0.5\n"
            "# HELP app_requests_total total requests\n"
            "# TYPE app_requests_total counter\n"
            'app_requests_total{reason="eos"} 3\n'
            'app_requests_total{reason="length"} 1\n'
        )

    def test_hostile_labels_and_nonfinite_values(self):
        """ISSUE-14 satellite golden refresh: label values carrying
        every escape-worthy character (backslash, double quote,
        newline) render per the exposition format, and non-finite
        gauge values spell +Inf/-Inf/NaN instead of crashing the
        scrape."""
        reg = MetricRegistry()
        g = reg.gauge("hostile", help='line1\nline2 \\ "q"',
                      labels=("p",))
        g.set(1, p='a\\b"c\nd')
        g.set(float("inf"), p="hi")
        g.set(float("-inf"), p="lo")
        g.set(float("nan"), p="nn")
        txt = reg.prometheus_text()
        # HELP escapes backslash + newline (quotes stay raw there)
        assert '# HELP hostile line1\\nline2 \\\\ "q"' in txt
        assert "# TYPE hostile gauge" in txt
        # label value: backslash, quote and newline all escaped
        assert 'hostile{p="a\\\\b\\"c\\nd"} 1' in txt
        assert 'hostile{p="hi"} +Inf' in txt
        assert 'hostile{p="lo"} -Inf' in txt
        assert 'hostile{p="nn"} NaN' in txt
        # every value line still splits cleanly on the last space
        for line in txt.splitlines():
            if line.startswith("#") or not line:
                continue
            name_part, value = line.rsplit(" ", 1)
            assert value  # parseable exposition shape

    def test_phase_and_burn_series_render(self):
        """ISSUE-11 golden refresh: the flight recorder's phase
        histogram (label `phase`, incl. the batch-observe path) and
        the SLO-burn gauge/counter render as ordinary labeled
        Prometheus series."""
        obs.STEP_PHASE_SECONDS.observe(0.002, phase="decode")
        obs.STEP_PHASE_SECONDS.observe_batch(
            [({"phase": "decode"}, 0.004),
             ({"phase": "emit"}, 0.00005)])
        obs.SLO_BURN.set(1.25, engine=3, kind="tpot")
        obs.SLO_BURN_EXCEEDED.inc(kind="tpot")
        obs.ENGINE_TOKENS_PER_SECOND.set(123.5, engine=3)
        txt = obs.prometheus_text()
        assert ('paddle_step_phase_seconds_bucket{phase="decode",'
                'le="+Inf"} 2') in txt
        assert 'paddle_step_phase_seconds_count{phase="emit"} 1' in txt
        assert 'paddle_step_phase_seconds_sum{phase="decode"} 0.006' \
            in txt
        assert 'paddle_slo_burn{engine="3",kind="tpot"} 1.25' in txt
        assert 'paddle_slo_burn_exceeded_total{kind="tpot"} 1' in txt
        assert ('paddle_engine_tokens_per_second{engine="3"} 123.5'
                ) in txt
        # observe() and observe_batch() agree on bucket math
        st = obs.STEP_PHASE_SECONDS.series_state(phase="decode")
        assert st["count"] == 2
        assert st["sum"] == pytest.approx(0.006)


# ---------------------------------------------------------------------------
# doc drift: the registry catalog and docs/OBSERVABILITY.md move together
# ---------------------------------------------------------------------------
def test_metric_catalog_matches_docs():
    """Every first-class metric registered in observability/__init__.py
    has a row in docs/OBSERVABILITY.md's catalog table and vice versa —
    a PR adding a series without documenting it (or documenting a
    series that no longer exists) fails here, not in review."""
    import os
    import re

    doc_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "OBSERVABILITY.md")
    with open(doc_path) as f:
        docs = f.read()
    # catalog rows look like: | `paddle_foo` | counter | ... — the view
    # table's patterned names (`paddle_decode_<counter>_total`, brace
    # expansions) deliberately do not match
    doc_names = set(re.findall(r"^\| `(paddle_[a-z0-9_]+)` \|", docs,
                               re.M))
    reg_names = {n for n in obs.registry._metrics
                 if n.startswith("paddle_")}
    undocumented = sorted(reg_names - doc_names)
    assert not undocumented, (
        f"metrics registered but missing from docs/OBSERVABILITY.md's "
        f"catalog table: {undocumented}")
    stale = sorted(doc_names - reg_names)
    assert not stale, (
        f"docs/OBSERVABILITY.md documents metrics that are not "
        f"registered: {stale}")


def test_alert_catalog_matches_docs():
    """Every shipped `AlertRule` (observability.alerts.default_rules)
    has a row in docs/OBSERVABILITY.md's alert-rule table and vice
    versa — the same both-directions contract as the metric catalog
    test, so the catalog and its documentation can never drift."""
    import os
    import re

    from paddle_tpu.observability.alerts import default_rules

    doc_path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "docs", "OBSERVABILITY.md")
    with open(doc_path) as f:
        docs = f.read()
    # alert rows look like: | `slo_burn_rate` | page | ... — ONLY
    # inside the table whose second column is a severity
    doc_rules = {
        m.group(1)
        for m in re.finditer(
            r"^\| `([a-z0-9_]+)` \| (?:page|ticket) \|", docs, re.M)}
    shipped = {r.name for r in default_rules()}
    undocumented = sorted(shipped - doc_rules)
    assert not undocumented, (
        f"alert rules shipped but missing from docs/OBSERVABILITY.md's "
        f"alert-rule table: {undocumented}")
    stale_rules = sorted(doc_rules - shipped)
    assert not stale_rules, (
        f"docs/OBSERVABILITY.md documents alert rules that are not "
        f"shipped: {stale_rules}")


# ---------------------------------------------------------------------------
# snapshot / reset invariants
# ---------------------------------------------------------------------------
class TestSnapshotReset:
    def test_snapshot_after_reset_keeps_series_at_zero(self):
        reg = MetricRegistry()
        c = reg.counter("c", labels=("k",))
        h = reg.histogram("h", buckets=(1.0,))
        c.inc(5, k="a")
        h.observe(0.5)
        reg.reset()
        snap = reg.snapshot()
        # series survive (same scrape shape), values are zero
        assert snap["c"]["series"] == [{"labels": {"k": "a"}, "value": 0}]
        hs = snap["h"]["series"][0]
        assert hs["counts"] == [0, 0] and hs["count"] == 0
        assert hs["sum"] == 0.0
        # and the series keep working after the reset
        c.inc(k="a")
        h.observe(2.0)
        assert c.value(k="a") == 1
        assert h.series_state()["counts"] == [0, 1]

    def test_snapshot_is_json_serializable(self):
        obs.REQUEST_TTFT.observe(0.01)
        obs.KV_UTIL.set(0.5, engine=0)
        json.dumps(obs.snapshot())

    def test_disabled_records_nothing(self):
        obs.disable()
        obs.REQUEST_TTFT.observe(1.0)
        obs.REQUESTS_ENQUEUED.inc()
        obs.record_span("engine", "x", 0, 10)
        obs.enable()
        assert obs.REQUEST_TTFT.series_state()["count"] == 0
        assert obs.REQUESTS_ENQUEUED.value() == 0
        assert obs.span_count() == 0


# ---------------------------------------------------------------------------
# merged chrome trace
# ---------------------------------------------------------------------------
class TestMergedChromeTrace:
    def test_span_tracks_have_named_processes(self, tmp_path):
        obs.record_span("engine", "decode_step", 1000, 500, tid=0,
                        args={"step": 1})
        obs.record_span("requests", "prefill", 1000, 200, tid=7)
        path = str(tmp_path / "trace.json")
        data = obs.export_chrome_trace(path)
        assert json.load(open(path)) == data
        meta = {e["args"]["name"]: e["pid"] for e in data["traceEvents"]
                if e.get("ph") == "M" and e["name"] == "process_name"}
        assert set(meta) == {"host", "engine", "requests"}
        evs = [e for e in data["traceEvents"] if e.get("ph") == "X"]
        step = next(e for e in evs if e["name"] == "decode_step")
        assert step["pid"] == meta["engine"]
        assert step["ts"] == 1.0 and step["dur"] == 0.5  # ns -> us
        assert step["args"]["step"] == 1
        pre = next(e for e in evs if e["name"] == "prefill")
        assert pre["pid"] == meta["requests"] and pre["tid"] == 7

    @pytest.mark.usefixtures("native_runtime")
    def test_host_events_merge_on_host_track(self):
        profiler.start_profiler()
        with profiler.RecordEvent("host_evt"):
            time.sleep(0.001)
        native.tracer_disable()
        with obs.span("engine", "py_span"):
            time.sleep(0.001)
        data = obs.merged_chrome_trace()
        host = next(e for e in data["traceEvents"]
                    if e.get("name") == "host_evt")
        assert host["pid"] == 0
        py = next(e for e in data["traceEvents"]
                  if e.get("name") == "py_span")
        assert py["pid"] != 0
        profiler.reset_profiler()

    def test_span_buffer_cap_counts_drops(self, monkeypatch):
        from paddle_tpu.observability import tracing

        monkeypatch.setattr(tracing, "MAX_SPANS", 2)
        obs.record_span("t", "a", 0, 1)
        obs.record_span("t", "b", 0, 1)
        obs.record_span("t", "c", 0, 1)
        assert obs.span_count() == 2
        assert obs.dropped_span_count() == 1


# ---------------------------------------------------------------------------
# engine instrumentation (the ISSUE-4 acceptance run)
# ---------------------------------------------------------------------------
class TestEngineInstrumentation:
    def test_two_request_run_records_request_metrics(self):
        profiler.reset_decode_stats()
        eng = _tiny_engine()
        prompts = [np.arange(8, dtype=np.int32),
                   np.arange(1, 6, dtype=np.int32)]
        outs = eng.generate(prompts, max_new_tokens=6)
        assert [len(o) for o in outs] == [6, 6]

        for hist in (obs.REQUEST_TTFT, obs.REQUEST_QUEUE_WAIT,
                     obs.REQUEST_E2E, obs.REQUEST_TPOT):
            st = hist.series_state()
            assert st["count"] == 2, hist.name
            assert st["sum"] >= 0.0
        # TTFT includes queue wait; e2e includes everything
        assert obs.REQUEST_E2E.series_state()["sum"] >= \
            obs.REQUEST_TTFT.series_state()["sum"]
        # chunked prefill fuses prompt ingestion into the step stream:
        # step 1 is the mixed step that consumes both prompts and emits
        # each request's first token, steps 2..6 are pure decode
        assert obs.STEP_SECONDS.series_state()["count"] == 6
        assert obs.REQUESTS_ENQUEUED.value() == 2
        assert obs.REQUESTS_FINISHED.value(reason="length") == 2
        # pool/occupancy gauges are engine-labeled so several engines
        # in one process keep separate readings
        eid = eng._engine_id
        assert 0 < obs.KV_UTIL.value(engine=eid) <= 1
        assert obs.KV_FREE_PAGES.value(engine=eid) >= 0
        assert obs.SLOT_OCCUPANCY.value(engine=eid) == 1.0

    def test_prometheus_export_has_core_series(self):
        eng = _tiny_engine()
        eng.generate([np.arange(6, dtype=np.int32)], max_new_tokens=4)
        txt = obs.prometheus_text()
        for needle in (
                "paddle_request_ttft_seconds_bucket",
                "paddle_request_tpot_seconds_count",
                "paddle_request_queue_wait_seconds_sum",
                "paddle_request_e2e_seconds_bucket",
                "paddle_kv_pool_utilization",
                "paddle_kv_free_pages",
                "paddle_slot_occupancy",
                'paddle_requests_finished_total{reason="length"}',
                "paddle_decode_steps_total",
                "paddle_decode_tokens_total",
                "paddle_dispatch_calls_total",
        ):
            assert needle in txt, needle

    def test_merged_trace_has_all_three_tracks(self):
        profiler.start_profiler()  # host tracer on
        eng = _tiny_engine()
        eng.generate([np.arange(6, dtype=np.int32)], max_new_tokens=4)
        native.tracer_disable()
        data = obs.merged_chrome_trace()
        tracks = {e["args"]["name"] for e in data["traceEvents"]
                  if e.get("ph") == "M"}
        assert {"engine", "requests"} <= tracks
        if native.native_available():
            assert "host" in tracks
        # the decode dispatch is timed at ONE seam: the flight record's
        # phase, which a running profile shows as the engine.decode
        # span (tests/test_trace_seam.py) — the serve loop puts no
        # RecordEvent of its own around it
        assert not any(str(e.get("name", "")).startswith("serving.")
                       for e in data["traceEvents"])
        assert any("decode" in r["phases"]
                   for r in eng._flight.records() if r["kind"] == "step")
        names = {e["name"] for e in data["traceEvents"]
                 if e.get("ph") == "X"}
        assert {"prefill", "decode_step", "queued", "decode"} <= names
        profiler.reset_profiler()

    def test_ttft_tpot_ordering_deterministic(self):
        """TTFT >= queue wait, TPOT <= e2e, and a one-token request
        records no TPOT (no second token to measure)."""
        eng = _tiny_engine(batch=1)
        eng.generate([np.arange(4, dtype=np.int32)], max_new_tokens=1)
        assert obs.REQUEST_TTFT.series_state()["count"] == 1
        assert obs.REQUEST_TPOT.series_state()["count"] == 0
        assert obs.REQUEST_TTFT.series_state()["sum"] >= \
            obs.REQUEST_QUEUE_WAIT.series_state()["sum"]

    def test_eviction_paths_record_finish_reason(self):
        eng = _tiny_engine(batch=1)
        r1 = eng.add_request(np.arange(4, dtype=np.int32),
                             max_new_tokens=8)
        r2 = eng.add_request(np.arange(4, dtype=np.int32),
                             max_new_tokens=8)
        eng.step()  # admits r1 (one slot), r2 stays queued
        eng.evict(r2)  # queued eviction
        eng.evict(r1)  # running eviction
        assert obs.REQUESTS_FINISHED.value(reason="evicted") == 2
        assert obs.REQUEST_E2E.series_state()["count"] == 2

    def test_speculative_run_records_spec_metrics(self):
        profiler.reset_decode_stats()
        eng = _tiny_engine(spec_decode_k=2)
        prompts = [np.tile(np.arange(4, dtype=np.int32), 4)]
        outs = eng.generate(prompts, max_new_tokens=6)
        assert len(outs[0]) == 6
        assert obs.REQUEST_TTFT.series_state()["count"] == 1
        assert obs.REQUEST_TPOT.series_state()["count"] == 1
        assert obs.SPEC_ACCEPTED_LAST.value(engine=eng._engine_id) >= 1
        evs = [e for e in obs.merged_chrome_trace()["traceEvents"]
               if e.get("ph") == "X"]
        names = {e["name"] for e in evs}
        assert {"draft", "verify", "spec_step"} <= names
        # draft/verify spans NEST inside their round's spec_step span
        # (chrome trace cannot stack overlapping duration events)
        steps = [e for e in evs if e["name"] == "spec_step"]
        for child in (e for e in evs if e["name"] in ("draft", "verify")):
            assert any(s["ts"] <= child["ts"] and
                       child["ts"] + child["dur"] <= s["ts"] + s["dur"]
                       for s in steps), child


# ---------------------------------------------------------------------------
# views: backward compatibility of the telemetry islands
# ---------------------------------------------------------------------------
class TestViews:
    def test_decode_stats_keys_unchanged(self):
        from paddle_tpu.profiler import (DECODE_STAT_COUNTERS,
                                         DECODE_STAT_DERIVED)

        st = profiler.decode_stats()
        assert set(st) == set(DECODE_STAT_COUNTERS) | \
            set(DECODE_STAT_DERIVED)

    def test_dispatch_stats_keys_unchanged(self):
        paddle.to_tensor(np.ones(3)) + paddle.to_tensor(np.ones(3))
        st = paddle.dispatch_stats()
        assert st
        for row in st.values():
            assert set(row) == {"calls", "hits", "misses", "retraces",
                                "bypasses", "time_s"}

    def test_decode_view_matches_decode_stats(self):
        eng = _tiny_engine(batch=1)
        eng.generate([np.arange(4, dtype=np.int32)], max_new_tokens=3)
        st = profiler.decode_stats()
        snap = obs.snapshot()
        assert snap["paddle_decode_steps_total"]["series"][0]["value"] \
            == st["steps"]
        assert snap["paddle_decode_tokens_total"]["series"][0]["value"] \
            == st["tokens"]
        assert snap["paddle_decode_avg_step_ms"]["series"][0]["value"] \
            == pytest.approx(st["avg_step_ms"])

    def test_dispatch_view_is_op_labeled(self):
        paddle.to_tensor(np.ones(3)) + paddle.to_tensor(np.ones(3))
        snap = obs.snapshot()
        m = snap["paddle_dispatch_calls_total"]
        assert m["labels"] == ["op"]
        assert m["series"], "dispatch ops must appear as labeled series"
        total = sum(s["value"] for s in m["series"])
        assert total == sum(r["calls"]
                            for r in paddle.dispatch_stats().values())

    def test_decode_view_works_without_serving_import(self):
        """An engine-less process exports zero decode series without
        importing inference.serving (the zero-import contract)."""
        import subprocess
        import sys

        code = (
            "import sys, json\n"
            "import paddle_tpu.observability as obs\n"
            "assert 'paddle_tpu.inference.serving' not in sys.modules\n"
            "snap = obs.snapshot()\n"
            "assert 'paddle_tpu.inference.serving' not in sys.modules\n"
            "assert snap['paddle_decode_steps_total']['series'][0]"
            "['value'] == 0\n"
            "print('ok')\n"
        )
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=240,
                           env={"JAX_PLATFORMS": "cpu",
                                **__import__("os").environ})
        assert r.returncode == 0, r.stderr
        assert "ok" in r.stdout


# ---------------------------------------------------------------------------
# thread safety: the single shared lock
# ---------------------------------------------------------------------------
class TestThreadSafety:
    def test_stats_poller_never_tears_counts(self):
        """N writer threads bump a decode counter while a poller
        hammers decode_stats(reset=True): with the shared lock the
        polled total plus the residual equals exactly the number of
        increments — a torn read-modify-write would lose some."""
        from paddle_tpu.inference import serving

        serving.reset_decode_stats()
        N, PER = 4, 2000
        polled = []
        stop = threading.Event()

        def poll():
            while not stop.is_set():
                polled.append(serving.decode_stats(reset=True)["steps"])

        def write():
            for _ in range(PER):
                serving._stats_add(steps=1)

        poller = threading.Thread(target=poll)
        writers = [threading.Thread(target=write) for _ in range(N)]
        poller.start()
        for w in writers:
            w.start()
        for w in writers:
            w.join()
        stop.set()
        poller.join()
        residual = serving.decode_stats(reset=True)["steps"]
        assert sum(polled) + residual == N * PER

    def test_concurrent_histogram_observes(self):
        reg = MetricRegistry()
        h = reg.histogram("h", buckets=(0.5,))
        c = reg.counter("c")

        def work():
            for _ in range(1000):
                h.observe(0.1)
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert h.series_state()["count"] == 4000
        assert h.series_state()["counts"] == [4000, 0]
        assert c.value() == 4000

    def test_histogram_sum_count_consistent_across_reset(self):
        """ISSUE-11 regression: a histogram's _sum/_count (and bucket
        totals) must stay mutually consistent across `reset()` under
        concurrent bumps — every snapshot a scraper takes satisfies
        count == sum(bucket counts) and sum == count * v (constant-
        value observations), whether a reset landed before, after, or
        not at all.  A torn reset (zero counts, stale sum) would show
        up as a fractional mean out of thin air."""
        reg = MetricRegistry()
        h = reg.histogram("h", buckets=(0.5, 2.0))
        V = 1.0
        stop = threading.Event()
        bad = []

        def write():
            while not stop.is_set():
                h.observe(V)
                h.observe_batch([({}, V)])

        def churn():
            while not stop.is_set():
                reg.reset()

        def scrape():
            while not stop.is_set():
                st = h.series_state()
                if sum(st["counts"]) != st["count"]:
                    bad.append(("bucket/count tear", st))
                if abs(st["sum"] - st["count"] * V) > 1e-9:
                    bad.append(("sum/count tear", st))

        threads = [threading.Thread(target=write) for _ in range(2)] \
            + [threading.Thread(target=churn),
               threading.Thread(target=scrape)]
        for t in threads:
            t.start()
        time.sleep(0.4)
        stop.set()
        for t in threads:
            t.join()
        assert not bad, bad[:3]


# ---------------------------------------------------------------------------
# periodic reporter
# ---------------------------------------------------------------------------
class TestReporter:
    def test_reporter_collects_on_interval(self):
        got = []
        try:
            assert obs.start_reporter(interval_s=0.03,
                                      sink=got.append) is True
            assert obs.reporter_running()
            deadline = time.time() + 5
            while len(got) < 2 and time.time() < deadline:
                time.sleep(0.01)
        finally:
            obs.stop_reporter()
        assert len(got) >= 2
        assert "paddle_request_ttft_seconds" in got[0]
        assert not obs.reporter_running()

    def test_flag_zero_means_off(self):
        assert paddle.get_flags("metrics_report_interval_s")[
            "metrics_report_interval_s"] == 0.0
        assert obs.start_reporter() is False
        assert not obs.reporter_running()

    def test_flag_drives_engine_autostart(self):
        paddle.set_flags({"metrics_report_interval_s": 30.0})
        try:
            _tiny_engine(batch=1)
            assert obs.reporter_running()
        finally:
            obs.stop_reporter()
            paddle.set_flags({"metrics_report_interval_s": 0.0})
