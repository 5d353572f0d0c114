"""The chip's idle split by the program's innermost span (`trace_split`):
on hand-made traces whose pieces are known, a hand-made trace whose
device clock runs a known offset from the host's, and the small trace
recorded on one v5e chip (`small_trace.xplane.pb`), where the keys
`trace_reduce` gives stay as it gives them."""
import json
import os

import numpy as np
import pytest

from conftest_paths import ROOT  # noqa: F401  (puts the root on sys.path)

from benchmarks import trace_reduce as tr  # noqa: E402
from benchmarks import trace_split as ts  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "small_trace.xplane.pb")
DRIVE, LOOP, HANDLER, LOADGEN = 0, 1, 2, 3
MS = 1e-3


def ev(name, start, end, thread, **stats):
    return (name, start, end - start, thread, stats)


def one_gap(host, gap=(1.0, 2.0), window=(0.0, 3.0)):
    """One device: busy up to the gap and after it, idle inside it; the
    driving thread dispatches on both sides."""
    dev = {0: [("%a = f32[] fusion()", window[0], gap[0] - window[0]),
               ("%b = f32[] fusion()", gap[1], window[1] - gap[1])]}
    host = host + [ev("engine.decode", 0.0, 0.01, DRIVE, step=1, engine=1),
                   ev("engine.decode", 2.0, 2.01, DRIVE, step=2, engine=1)]
    return ts.split_events(dev, host, window)


# ------------------------------------------------------------ the split
def test_a_collection_wins_over_everything():
    r = one_gap([ev("engine.fetch", 0.5, 1.5, DRIVE, step=1),
                 ev("edge.write", 1.0, 2.0, HANDLER, request=4),
                 ev("host.gc", 1.2, 1.4, LOOP, generation=0)])
    by = r["idle_by_innermost_span"]
    assert by["host.gc"] == pytest.approx(0.2)
    assert by["engine.fetch"] == pytest.approx(0.3)
    assert by["edge.write"] == pytest.approx(0.5)
    assert r["idle_split"]["fetch_s"] == pytest.approx(0.3)
    assert r["idle_split"]["host_s"] == pytest.approx(0.7)


def test_the_driving_threads_innermost_span_wins_over_a_write_elsewhere():
    r = one_gap([ev("engine.admit", 0.8, 2.1, DRIVE, step=2),
                 ev("engine.cache", 1.5, 1.7, DRIVE, step=2),
                 ev("edge.write", 1.2, 1.6, HANDLER, request=4)])
    by = r["idle_by_innermost_span"]
    assert by == {"engine.admit": pytest.approx(0.8),
                  "engine.cache": pytest.approx(0.2)}
    assert r["idle_split"]["dark_s"] == 0.0


def test_outside_the_driving_threads_spans_the_latest_started_wins():
    r = one_gap([ev("frontend.flush", 0.5, 1.5, LOOP),
                 ev("edge.write", 1.2, 1.8, HANDLER, request=4),
                 ev("send", 1.7, 2.5, LOADGEN)])
    by = r["idle_by_innermost_span"]
    assert by["frontend.flush"] == pytest.approx(0.2)
    assert by["edge.write"] == pytest.approx(0.6)
    # dark, listed under the benchmark's span that was open
    assert r["idle_split"]["dark_s"] == pytest.approx(0.2)
    assert r["idle_dark_under"] == {"send": pytest.approx(0.2)}


def test_a_wait_is_empty_and_an_unspanned_piece_is_dark():
    r = one_gap([ev("wait.empty", 1.1, 1.6, LOOP, engine=1)])
    s = r["idle_split"]
    assert s["empty_s"] == pytest.approx(0.5)
    assert s["dark_s"] == pytest.approx(0.5)
    assert r["idle_dark_under"] == {ts.NO_SPAN: pytest.approx(0.5)}
    assert r["idle_dark_tail_s"] == 0.0  # spans were recorded after it


def test_dark_idle_after_the_last_recorded_span_is_told_apart():
    """A wait still open when the profile stops is not recorded: the
    idle after the last program span is dark, and said to be the
    trace's tail."""
    dev = {0: [("%a = f32[] fusion()", 0.0, 1.0)]}
    host = [ev("engine.decode", 0.0, 0.01, DRIVE, step=1, engine=1),
            ev("engine.fetch", 0.02, 1.1, DRIVE, step=1),
            ev("frontend.flush", 1.1, 1.2, LOOP)]
    r = ts.split_events(dev, host, (0.0, 3.0))
    assert r["idle_split"]["dark_s"] == pytest.approx(1.8)
    assert r["idle_dark_tail_s"] == pytest.approx(1.8)


@pytest.fixture(scope="module")
def busy_trace():
    """Sixty steps on one device with host work, waits, writes, loads
    and collections scattered over five threads."""
    rng = np.random.default_rng(41)
    dev, host, t = [], [], 0.0
    for k in range(60):
        ds = t
        host.append(ev("engine.assemble", ds - 1 * MS, ds, DRIVE, step=k))
        host.append(ev("engine.decode", ds, ds + 0.3 * MS, DRIVE, step=k))
        work = rng.uniform(2, 9) * MS
        dev.append((f"%fusion.{k} = f32[] fusion()", ds + 0.4 * MS, work))
        fe = ds + 0.4 * MS + work + rng.uniform(0.02, 0.3) * MS
        host.append(ev("engine.fetch", ds + 0.35 * MS, fe, DRIVE, step=k))
        host.append(ev("engine.emit", fe, fe + 0.5 * MS, DRIVE, step=k))
        w = ds + rng.uniform(0, 5) * MS
        host.append(ev("edge.write", w, w + 0.2 * MS, HANDLER + k % 2))
        if k % 7 == 0:
            g = ds + rng.uniform(0, 8) * MS
            host.append(ev("host.gc", g, g + 0.4 * MS, LOOP, generation=0))
        if k % 5 == 0:
            host.append(ev("wait.empty", fe + 0.6 * MS, fe + 3 * MS, LOOP))
            t = fe + 4 * MS
        else:
            t = fe + rng.uniform(1.2, 3) * MS
        host.append(ev("send", ds + 1 * MS, ds + 1.5 * MS, 5))
    return {0: dev}, host, (0.0, t)


def test_the_four_buckets_partition_the_idle(busy_trace):
    dev, host, window = busy_trace
    r = ts.split_events(dev, host, window)
    s = r["idle_split"]
    total = sum(s[b + "_s"] for b in ts.BUCKETS)
    assert total == pytest.approx(s["idle_s"], abs=1e-9)
    assert s["idle_s"] == pytest.approx(
        r["window_s"] - r["fullest_busy_s"], abs=1e-9)
    assert sum(r["idle_by_innermost_span"].values()) + \
        sum(r["idle_dark_under"].values()) == \
        pytest.approx(s["idle_s"], abs=1e-9)
    assert min(s.values()) > 0  # every bucket is met in this trace
    assert r["traced_steps"] == 60


def test_the_readings_add_up_to_the_idle_share(busy_trace):
    dev, host, window = busy_trace
    r = ts.split_events(dev, host, window)
    got = ts.readings(r, {})
    steps = r["traced_steps"]
    whole = got["idle_dark_share.serve"] + got["idle_empty_share.serve"] \
        + 1e-3 * (got["idle_host_ms.serve"] + got["idle_fetch_ms.serve"]) \
        * steps / r["window_s"] * 100.0
    assert whole == pytest.approx(tr.idle_share_percent(r), abs=0.5)


# ------------------------------------------------------------ one clock
@pytest.mark.parametrize("offset", [1.137 * MS, -0.83 * MS, 0.0])
def test_a_shifted_trace_gives_its_offset_back(busy_trace, offset):
    dev, host, window = busy_trace
    # the key folds between two steps: tiny device work no step waits on
    keys = [("%threefry = u32[] fusion()", s - 0.5 * MS, 4e-6)
            for n, s, _, _, _ in host if n == "engine.decode"]
    shifted = {0: [(n, s + offset, d) for n, s, d in dev[0] + keys]}
    r = ts.split_events(shifted, host, window)
    # the fastest fetch returned 20-300 us after its work ended
    assert r["clock_offset_s"] == pytest.approx(offset, abs=0.1 * MS)
    assert r["clock_offset_s"] <= offset
    assert r["clock_offset_bounds"] == 60
    assert r["clock_offset_source"] == "fetch"
    assert r["clock_offset_slack_s"] >= 0
    # shifted back, the split reads as on the unshifted trace
    plain = ts.split_events(dev, host, window)["idle_split"]
    for b in ts.BUCKETS:
        assert r["idle_split"][b + "_s"] == pytest.approx(
            plain[b + "_s"], abs=0.35 * MS * 60)


@pytest.mark.parametrize("offset", [-0.44 * MS, 1.6 * MS])
def test_the_runtimes_run_ids_pin_the_offset(busy_trace, offset):
    """Each step's program run enqueued 50-400 us before it starts on
    the device and completed 10-80 us after it ends there: the runtime's
    bounds hold the offset within their middle's reach, tighter than the
    fetches' bound, and win over it."""
    dev, host, window = busy_trace
    rng = np.random.default_rng(7)
    runs, runtime = [], []
    for rid, (_, s, d) in enumerate(dev[0]):
        runs.append((rid, s + offset, s + d + offset))
        runtime.append(ev("DoEnqueueProgram", s - rng.uniform(50, 400) * 1e-6,
                          s - 20e-6, 7, run_id=rid, device_ordinal=0))
        done = s + d + rng.uniform(10, 80) * 1e-6
        runtime.append(ev("CompleteCallbacks", done, done + 30e-6, 8,
                          run_id=rid, device_ordinal=0))
    shifted = {0: [(n, s + offset, d) for n, s, d in dev[0]]}
    r = ts.split_events(shifted, host + runtime, window, runs={0: runs})
    assert r["clock_offset_source"] == "runtime"
    assert r["clock_offset_bounds"] == 60
    assert r["clock_offset_s"] == pytest.approx(offset, abs=0.1 * MS)
    assert 0 <= r["clock_offset_slack_s"] < 0.1 * MS
    # another device's runs, or none paired, leave it to the fetches
    r = ts.split_events(shifted, host + runtime, window, runs={1: runs})
    assert r["clock_offset_source"] == "fetch"


def test_no_fetch_no_offset():
    r = one_gap([ev("frontend.flush", 0.5, 1.5, LOOP)])
    assert r["clock_offset_s"] is None and r["clock_offset_bounds"] == 0
    # a fetch that returned half a second after its work bounds nothing
    r = one_gap([ev("engine.fetch", 0.5, 1.5, DRIVE, step=1)])
    assert r["clock_offset_s"] is None
    assert r["idle_split"]["fetch_s"] == pytest.approx(0.5)


# ----------------------------------------------- table, steps, old keys
def test_span_table_counts_totals_and_self_time():
    r = one_gap([ev("engine.admit", 0.0, 1.0, 4, step=1),
                 ev("engine.cache", 0.2, 0.5, 4, step=1),
                 ev("engine.admit", 2.0, 2.5, 4, step=2),
                 ev("send", 0.1, 0.9, 4)])
    admit = r["span_table"]["engine.admit"]
    assert admit["count"] == 2
    assert admit["total_s"] == pytest.approx(1.5)
    assert admit["self_s"] == pytest.approx(1.2)  # less the cache span
    assert (admit["p50_s"], admit["p95_s"]) == (0.5, 1.0)
    assert "send" not in r["span_table"]  # the program's spans alone


def test_traced_steps_are_distinct_steps_that_start_inside():
    host = [ev("engine.prefill", 0.5, 0.6, DRIVE, step=7, engine=1),
            ev("engine.verify", 0.7, 0.8, DRIVE, step=7, engine=1),
            ev("engine.decode", 0.9, 1.0, DRIVE, step=8, engine=1),
            ev("engine.decode", 0.9, 1.0, 4, step=8, engine=2),
            ev("engine.decode", 3.5, 3.6, DRIVE, step=9, engine=1)]
    assert ts.traced_steps(host, (0.0, 3.0)) == 3


def test_the_old_keys_stay_as_trace_reduce_gives_them(busy_trace):
    dev, host, window = busy_trace
    old = tr.reduce_events(dev, [h[:3] for h in host], window)
    new = ts.split_events(dev, host, window)
    assert {k: new[k] for k in old} == old


def test_recorded_trace_keeps_its_keys_and_reads_dark():
    old = tr.reduce_xplane(RECORDED)
    new = ts.split_xplane(RECORDED)
    assert {k: new[k] for k in old} == old
    # the recording holds no span of the program: every idle piece is
    # dark, and the longest of them lies under the host's stall
    assert new["idle_split"]["dark_s"] == new["idle_split"]["idle_s"] > 0
    under = new["idle_dark_under"]
    assert max(under, key=under.get) == "stall"
    assert under["stall"] == pytest.approx(20.05e-3, rel=1e-2)
    # the runtime's enqueues and completions of the three runs bound
    # the offset: -1.771 to -1.292 ms
    assert new["clock_offset_source"] == "runtime"
    assert new["clock_offset_bounds"] == 3
    assert -1.772e-3 < new["clock_offset_s"] < -1.291e-3
    assert new["clock_offset_slack_s"] == pytest.approx(0.479e-3, abs=2e-6)
    assert new["traced_steps"] == 0
    assert new["span_table"] == {}
    assert ts.readings(new, None) == dict.fromkeys(ts.readings({}, {}))


def test_detail_reader_keeps_read_xplanes_events():
    dev, host = tr.read_xplane(RECORDED)
    dev2, host2 = ts.read_xplane_detail(RECORDED)
    assert dev2 == dev and [h[:3] for h in host2] == host
    # the profiler names many threads alike: lines are numbered
    assert len({h[3] for h in host2}) >= 2


# ------------------------------------------------------------ readings
def test_each_reading_is_none_without_its_input():
    got = ts.readings(None, None)
    assert set(got) == {"idle_dark_share.serve", "idle_empty_share.serve",
                        "idle_host_ms.serve", "idle_fetch_ms.serve",
                        "hop_ms.serve", "relay_ms.serve"}
    assert all(v is None for v in got.values())
    # counters of a program without the relay and hop counters
    assert all(v is None for v in ts.readings({}, {"steps": 3}).values())


def test_counter_readings_are_means():
    got = ts.readings(None, {"hop_s": 0.5, "hops": 400, "relay_s": 2.0,
                             "relayed_tokens": 800})
    assert got["hop_ms.serve"] == pytest.approx(1.25)
    assert got["relay_ms.serve"] == pytest.approx(2.5)


def test_split_readings_need_the_new_spans_unless_asked(busy_trace):
    dev, host, window = busy_trace
    older = [h for h in host if h[0] != ts.NEW_SPAN]
    r = ts.split_events(dev, older, window)
    assert ts.readings(r, {})["idle_dark_share.serve"] is None
    loose = ts.readings(r, {}, strict=False)
    assert loose["idle_dark_share.serve"] == pytest.approx(
        100 * r["idle_split"]["dark_s"] / r["window_s"])
    assert loose["idle_fetch_ms.serve"] == pytest.approx(
        1e3 * r["idle_split"]["fetch_s"] / 60)


def test_the_command_prints_the_split(capsys, tmp_path):
    counters = tmp_path / "c.json"
    counters.write_text(json.dumps({"hops": 2, "hop_s": 0.004}))
    assert ts.main([RECORDED, "--counters", str(counters), "--any"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["readings"]["hop_ms.serve"] == pytest.approx(2.0)
    assert out["clock_offset_ms"] == pytest.approx(-1.5316, abs=1e-3)
    assert out["clock_offset_source"] == "runtime"
    assert set(out["idle_split"]) == {"idle_s", "fetch_s", "empty_s",
                                      "host_s", "dark_s"}


def test_steps_handed_to_two_workers_both_drive():
    """The executor may run one step on one worker and the next on
    another: both are driving threads, and a write elsewhere does not
    take a fetch from either."""
    r = one_gap([ev("engine.decode", 0.3, 0.4, 4, step=3, engine=1),
                 ev("engine.fetch", 0.4, 1.6, 4, step=3),
                 ev("edge.write", 1.2, 1.4, HANDLER, request=4)])
    assert r["idle_by_innermost_span"] == {"engine.fetch":
                                           pytest.approx(0.6)}
    assert ts.driving_threads(
        [ev("engine.decode", 0.3, 0.4, 4), ev("engine.mixed", 0.5, 0.6, 0),
         ev("engine.fetch", 0.6, 0.7, 5)], (0.0, 1.0)) == {0, 4}
