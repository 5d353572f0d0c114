"""Profiler surface tests (reference platform/profiler + fluid/profiler.py)."""
import json
import os
import time

import pytest

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.core import native


needs_native = pytest.mark.usefixtures("native_runtime")


@needs_native
class TestProfiler:
    def test_record_and_summary(self):
        profiler.start_profiler()
        with profiler.RecordEvent("matmul_step"):
            time.sleep(0.002)
        with profiler.RecordEvent("matmul_step"):
            time.sleep(0.001)
        with profiler.RecordEvent("io"):
            time.sleep(0.001)
        native.tracer_disable()
        text = profiler.summary_string(sorted_key="total")
        assert "matmul_step" in text and "io" in text
        assert "Calls" in text
        # matmul_step called twice
        line = next(l for l in text.splitlines() if l.startswith("matmul_step"))
        assert "2" in line.split()[1]
        profiler.reset_profiler()

    def test_chrome_trace_export(self, tmp_path):
        profiler.start_profiler()
        with profiler.RecordEvent("evt"):
            time.sleep(0.001)
        path = str(tmp_path / "timeline.json")
        profiler.stop_profiler(profile_path=path)
        data = json.loads(open(path).read())
        evts = [e for e in data["traceEvents"] if e.get("name") == "evt"]
        assert evts and evts[0]["ph"] == "X" and evts[0]["dur"] > 0
        profiler.reset_profiler()

    def test_context_manager(self, capsys):
        with profiler.profiler():
            with profiler.RecordEvent("inside"):
                pass
        out = capsys.readouterr().out
        assert "Profiling Report" in out
        profiler.reset_profiler()

    def test_disabled_records_nothing(self):
        profiler.reset_profiler()
        native.tracer_disable()
        with profiler.RecordEvent("ghost"):
            pass
        assert "ghost" not in profiler.summary_string()


class TestStopProfilerPrintTable:
    def test_print_table_false_collects_silently(self, capsys):
        """Tests and the periodic reporter collect the table without
        spamming stdout; the default keeps reference behavior."""
        profiler.start_profiler()
        text = profiler.stop_profiler(print_table=False)
        assert "Profiling Report" in text
        assert capsys.readouterr().out == ""
        profiler.reset_profiler()

    def test_default_still_prints(self, capsys):
        profiler.start_profiler()
        text = profiler.stop_profiler()
        assert "Profiling Report" in capsys.readouterr().out
        assert "Profiling Report" in text
        profiler.reset_profiler()


class TestMergedChromeExport:
    def test_export_includes_observability_tracks(self, tmp_path):
        """profiler.export_chrome_tracing now writes the MERGED
        timeline: span tracks ride along with the host events."""
        from paddle_tpu import observability as obs

        obs.clear_spans()
        obs.record_span("engine", "step", 1000, 500, tid=3)
        path = str(tmp_path / "merged.json")
        profiler.export_chrome_tracing(path)
        data = json.loads(open(path).read())
        tracks = {e["args"]["name"] for e in data["traceEvents"]
                  if e.get("ph") == "M"}
        assert {"host", "engine"} <= tracks
        step = next(e for e in data["traceEvents"]
                    if e.get("name") == "step")
        assert step["tid"] == 3
        obs.clear_spans()
