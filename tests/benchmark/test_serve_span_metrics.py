"""The five serve metrics that read the program's own spans and counters
(`queue_wait_ms`, `prefill_wait_ms`, `mixed_step_ms`, `host_per_step_ms`,
`host_loop_idle_share`, each `.serve`): every reader on a hand-made `ctx`
(its value; nothing where its denominator is 0 or where the program has
no such counter, as the commit before them has not), the idle share on a
hand-made trace with one gap under each kind of span, and that every
counter a reader names is one the program really keeps."""
import pytest

from conftest_paths import ROOT  # noqa: F401  (puts the root on sys.path)

from benchmarks import harness  # noqa: E402
from benchmarks import trace_reduce as tr  # noqa: E402

COUNTERS = {"steps": 200, "mixed_steps": 50, "decode_time_s": 11.6,
            "admissions": 36, "queue_wait_s": 9.0,
            "first_tokens": 30, "first_token_wait_s": 18.0,
            "mixed_time_s": 4.5, "host_in_step_s": 0.5,
            "between_steps_s": 0.3}
# metric -> (its value on COUNTERS, the counter that is its denominator,
# a counter the commit before this metric does not keep)
BY_COUNTER = {
    "queue_wait_ms.serve": (250.0, "admissions", "admissions"),
    "prefill_wait_ms.serve": (600.0, "first_tokens", "first_tokens"),
    "mixed_step_ms.serve": (90.0, "mixed_steps", "mixed_time_s"),
    "host_per_step_ms.serve": (4.0, "steps", "host_in_step_s"),
}
NEW = sorted(list(BY_COUNTER) + ["host_loop_idle_share.serve"])


def reader(name):
    return harness.load_module("layer_metrics", name).read


@pytest.mark.parametrize("name", sorted(BY_COUNTER))
def test_counter_metric_reads_its_mean(name):
    value, _, _ = BY_COUNTER[name]
    assert reader(name)({"counters": dict(COUNTERS)}) == \
        pytest.approx(value)


@pytest.mark.parametrize("name", sorted(BY_COUNTER))
def test_counter_metric_finds_nothing_on_a_zero_denominator(name):
    _, denominator, _ = BY_COUNTER[name]
    assert reader(name)({"counters": dict(COUNTERS, **{denominator: 0})}) \
        is None
    assert reader(name)({"counters": None}) is None
    assert reader(name)({}) is None


@pytest.mark.parametrize("name", sorted(BY_COUNTER))
def test_counter_metric_finds_nothing_in_a_program_without_its_counter(
        name):
    _, _, added = BY_COUNTER[name]
    older = {k: v for k, v in COUNTERS.items() if k != added}
    assert reader(name)({"counters": older}) is None


def hand_made_trace():
    """A 10 s window on one device: four stretches of work and three
    gaps between them, each with its own host story.

    * 1.0–1.5: token delivery (`engine.emit`, 0.4 s) after a short
      blocking read: the program's own work holds the gap;
    * 3.0–3.6: the blocking read itself, JAX's `np.asarray` span nested
      in `engine.fetch`, which ends a moment later and so overlaps the
      gap more: the wait, not the loop;
    * 6.0–6.3: the load generator's `send`, nothing of the program's;
    * 9.0–10: no span at all."""
    dev = {0: [("%step.1 = f32[] fusion()", 0.0, 1.0),
               ("%step.2 = f32[] fusion()", 1.5, 1.5),
               ("%step.3 = f32[] fusion()", 3.6, 2.4),
               ("%step.4 = f32[] fusion()", 6.3, 2.7)]}
    host = [("engine.fetch", 0.2, 0.85),
            ("np.asarray_jax.Array_", 0.21, 0.83),
            ("engine.emit", 1.05, 0.4),
            ("engine.step_tail", 1.45, 0.02),
            ("frontend.flush", 1.47, 0.01),
            ("engine.decode", 1.49, 0.02),
            ("engine.fetch", 1.6, 1.98),
            ("np.asarray_jax.Array_", 1.61, 1.96),
            ("engine.emit", 3.58, 0.01),
            ("engine.mixed", 3.59, 0.02),
            ("send", 5.9, 0.45)]
    return tr.reduce_events(dev, host, (0.0, 10.0))


def test_hand_made_gaps_go_to_the_span_that_holds_them():
    by_span = hand_made_trace()["idle_by_host_span"]
    assert by_span["engine.emit"] == pytest.approx(0.5)
    assert by_span["engine.fetch"] == pytest.approx(0.6)
    assert by_span["send"] == pytest.approx(0.3)
    assert by_span[tr.UNATTRIBUTED] == pytest.approx(1.0)
    assert "np.asarray_jax.Array_" not in by_span  # out-overlapped


def test_idle_share_counts_the_programs_own_work_and_not_the_wait():
    trace = hand_made_trace()
    read = reader("host_loop_idle_share.serve")
    # of 2.4 s idle in 10 s (24%), 0.5 s lay under engine.emit
    assert read({"trace": trace}) == pytest.approx(5.0)
    assert tr.idle_share_percent(trace) == pytest.approx(24.0)
    assert read({"trace": trace}) <= tr.idle_share_percent(trace)


def test_idle_share_where_the_read_is_jaxs_span_alone():
    """A gap that starts before the engine's own span does (the device's
    clock runs ahead of the host's) can go to JAX's span: still the
    wait."""
    dev = {0: [("a", 0.0, 1.0), ("b", 2.0, 1.0)]}
    host = [("np.asarray_jax.Array_", 0.1, 1.8),
            ("frontend.control", 1.9, 0.1)]
    trace = tr.reduce_events(dev, host, (0.0, 4.0))
    assert trace["idle_by_host_span"]["np.asarray_jax.Array_"] == \
        pytest.approx(1.0)
    assert reader("host_loop_idle_share.serve")({"trace": trace}) is None


@pytest.mark.parametrize("trace", [None, {}, {"window_s": 0.0,
                                             "idle_by_host_span": {}},
                                   {"window_s": 5.0,
                                    "idle_by_host_span": {"send": 1.0}}],
                         ids=["no-trace", "empty", "no-window",
                              "no-program-span"])
def test_idle_share_finds_nothing_without_the_programs_spans(trace):
    assert reader("host_loop_idle_share.serve")({"trace": trace}) is None


def test_the_five_are_entries_of_the_serve_cell_and_one_layer():
    bench = harness.load_benchmark()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert sorted(mine) == NEW
    cell = harness.Cell(bench, "gpt2s-serve-chat")
    reported = {m["name"] for m in cell.per_layer()}
    for name, m in mine.items():
        assert m["workloads"] == ["gpt2s-serve-chat"]
        assert m["layer"] == "scheduler and engine host loop"
        assert m["better"] == "lower" and name in reported
    assert mine["host_loop_idle_share.serve"]["source"] == "program_span"
    assert {mine[n]["moves"] for n in ("queue_wait_ms.serve",
                                       "prefill_wait_ms.serve")} == \
        {"ttft_p90_ms"}
    # appended: the accepted entries stand before them, in their order
    names = [m["name"] for m in bench["per_layer"]]
    assert sorted(names[-5:]) == NEW


def test_every_counter_a_reader_names_is_one_the_program_keeps():
    from paddle_tpu import profiler

    zero = profiler.decode_stats()
    assert set(COUNTERS) <= set(profiler.DECODE_STAT_COUNTERS)
    assert set(COUNTERS) <= set(zero)
    for name in sorted(BY_COUNTER):
        # a fresh process has served nothing: nothing to read, no error
        assert reader(name)({"counters": {k: 0 for k in zero}}) is None
