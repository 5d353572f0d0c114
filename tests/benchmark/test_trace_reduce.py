"""The reduction from a profiler trace to busy/idle time, per-operation
sums and attributed idle gaps: on a small trace recorded on one v5e chip
(`small_trace.xplane.pb`, made by `record_small_trace.py`: three calls of
a program of four fusions, a 20 ms host stall before the third) and on
hand-made traces with known stalls."""
import os

import pytest

from conftest_paths import ROOT  # noqa: F401  (puts the root on sys.path)

from benchmarks import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "small_trace.xplane.pb")
US = 1e-6


# ------------------------------------------------------------- by hand
def test_union_clip_subtract():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.length([(0, 2), (3, 4)]) == 3
    assert tr.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


def test_operation_names_lose_their_decoration():
    assert tr.op_key("%fusion.12 = bf16[8]{0} fusion(%p), kind=kLoop") == \
        "fusion.12"
    assert tr.op_key("fusion.12") == "fusion.12"
    assert tr.stem("fusion.12") == "fusion" and tr.stem("copy") == "copy"


def test_a_known_stall_is_found_measured_and_attributed():
    # one device: 1 s of work, a 2 s hole while the host "loads", 1 s of
    # work overlapping itself, then idle to the window's end
    dev = {0: [("%a.1 = f32[] fusion()", 0.0, 1.0),
               ("%a.2 = f32[] fusion()", 3.0, 1.0),
               ("%b = f32[] fusion()", 3.5, 0.5)]}
    host = [("dispatch", 0.0, 0.1), ("load_batch", 1.0, 1.9),
            ("fence", 2.9, 1.1)]
    r = tr.reduce_events(dev, host, (0.0, 5.0))
    assert r["window_s"] == 5.0 and r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(2.0)        # the union, not 2.5
    assert r["fullest_busy_s"] == pytest.approx(2.0)
    assert r["op_seconds"] == {"a.1": 1.0, "a.2": 1.0, "b": 0.5}
    assert r["op_counts"] == {"a.1": 1, "a.2": 1, "b": 1}
    assert r["device_ops"][0] == ["a", 2.0]         # a.1 + a.2
    assert r["idle_gaps"][0] == ["load_batch", pytest.approx(2.0)]
    assert r["idle_gaps"][1][1] == pytest.approx(1.0)  # the tail
    assert r["idle_by_host_span"]["load_batch"] == pytest.approx(2.0)


def test_the_window_clips_and_an_unspanned_gap_reads_engine_loop():
    dev = {0: [("x", -1.0, 2.0), ("y", 4.0, 2.0)]}
    r = tr.reduce_events(dev, [], (0.0, 5.0))
    assert r["busy_s"] == pytest.approx(2.0)
    assert r["op_seconds"] == {"x": 1.0, "y": 1.0}
    assert r["idle_gaps"] == [[tr.UNATTRIBUTED, pytest.approx(3.0)]]


def test_collectives_exposed_and_the_fullest_of_four_devices():
    def chip(extra):
        return [("%fusion.1 = f32[] fusion()", 0.0, 1.0),
                ("%all-reduce.7 = f32[] all-reduce(%x)", 0.5, 1.0),
                ("%fusion.2 = f32[] fusion()", 2.0, extra)]
    dev = {i: chip(0.25 * i) for i in range(4)}
    r = tr.reduce_events(dev, [], (0.0, 4.0))
    assert r["n_devices"] == 4
    assert r["collective_s"] == pytest.approx(1.0)
    assert r["collective_exposed_s"] == pytest.approx(0.5)
    assert r["fullest_busy_s"] == pytest.approx(1.5 + 0.75)
    assert r["busy_s"] == pytest.approx(1.5 + 0.375)    # the mean
    assert r["op_seconds"]["all-reduce.7"] == pytest.approx(1.0)


def test_an_empty_window_is_not_busy():
    r = tr.reduce_events({}, [], (0.0, 1.0))
    assert r["busy_s"] == 0.0 and r["fullest_busy_s"] == 0.0
    assert r["device_ops"] == [] and r["idle_gaps"] == []


# ------------------------------------------------------- the recording
def test_recorded_trace_first_operation_to_last():
    """No window span asked for: the three calls, 21.9 ms end to end."""
    r = tr.reduce_xplane(RECORDED, whole=True)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(21897.2 * US, rel=1e-3)
    # three runs of copy + four fusions: 47.4 us each
    assert r["busy_s"] == pytest.approx(3 * 47.4 * US, rel=2e-3)
    assert r["op_counts"]["fusion.3"] == 3 and r["op_counts"]["fusion"] == 3
    assert r["op_seconds"]["fusion.1"] == pytest.approx(3 * 11.58 * US,
                                                        rel=2e-3)
    assert r["device_ops"][0][0] == "fusion"
    assert r["device_ops"][0][1] == pytest.approx(
        (3 * 11.58 * 3 + 3 * 12.62) * US, rel=2e-3)
    assert r["op_text"]["fusion.2"].startswith("%fusion.2 = bf16[1024,1024]")
    # the longest hole is the host's stall before the third call
    what, seconds = r["idle_gaps"][0]
    assert what == "stall" and seconds == pytest.approx(20.9e-3, rel=1e-2)
    idle_share = 1 - r["fullest_busy_s"] / r["window_s"]
    assert idle_share == pytest.approx(0.9935, abs=1e-3)


def test_recorded_trace_inside_the_benchmarks_window_span():
    """The device's clock runs 1.0-1.3 ms ahead of the host's in this
    recording, so of the three calls only the third starts inside the
    host's `bench_window` span: the reduction keeps to the span."""
    r = tr.reduce_xplane(RECORDED)
    assert r["window_s"] == pytest.approx(22921.4 * US, rel=1e-4)
    assert r["busy_s"] == pytest.approx(47.4 * US, rel=2e-3)
    assert set(r["op_counts"].values()) == {1}
    assert r["idle_gaps"][0] == ["stall", pytest.approx(20.83e-3, rel=1e-2)]


def test_describe_lists_planes_and_lines():
    d = tr.describe(RECORDED, per_line=1)
    assert "XLA Ops" in d["/device:TPU:0"]
    assert d["/device:TPU:0"]["XLA Ops"]["events"] == 18
    assert any(line.startswith("python") for line in d["/host:CPU"])
