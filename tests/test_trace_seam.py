"""The serve loop's phases as spans on the profiler's clock (ISSUE 26).

One seam: a flight-recorder phase is, at the same call site, an
``engine.<phase>`` `jax.profiler.TraceAnnotation`; `_host_fetch` is
``engine.fetch``; what `DecodeEngine.step` does after the batch is
``engine.step_tail``; the frontend's own work between two steps is
``frontend.control`` / ``frontend.flush``.  Pinned here, on the CPU:

* a profiled tiny engine behind `ServingFrontend` yields every span at
  least once, read back with `benchmarks.trace_reduce.read_xplane`;
  names stay clean (``step=`` / ``engine=`` ride as stats);
* on one thread spans nest only under admit / draft / emit, a step's
  spans share ``step=``, and steps do not interleave;
* the spans are there with ``flight_window=0`` too, and serving is
  bit-equal with and without a profile running;
* the seven counters the benchmark's serve metrics read
  (`profiler.DECODE_STAT_COUNTERS`): zero before traffic, one admission
  and one first token a request, mixed steps inside the blended sums,
  the time sums count each second once, nothing booked across a wait,
  cleared by ``reset=True``.
"""
import asyncio
import glob
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.inference.frontend import ServingFrontend
from paddle_tpu.inference.serving import (DecodeEngine, decode_stats,
                                          reset_decode_stats)
from paddle_tpu.models.gpt import GPT, GPTConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks import trace_reduce  # noqa: E402

TINY = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                 num_heads=4, max_seq_len=256,
                 use_parallel_layers=False, dropout=0.0)
PROMPTS = [[1, 2, 3, 4, 5, 1, 2, 3, 4, 5, 1, 2],
           [7, 8, 9, 7, 8, 9, 7, 8]]
NEW = 6
SPANS = ("engine.admit", "engine.cache", "engine.decode", "engine.mixed",
         "engine.prefill", "engine.verify", "engine.draft", "engine.fetch",
         "engine.emit", "engine.step_tail", "frontend.control",
         "frontend.flush")
DISPATCH = ("engine.decode", "engine.mixed", "engine.prefill",
            "engine.verify")
HOLDERS = ("engine.admit", "engine.draft", "engine.emit")
NEW_COUNTERS = ("queue_wait_s", "admissions", "first_token_wait_s",
                "first_tokens", "mixed_time_s", "host_in_step_s",
                "between_steps_s")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    reset_decode_stats()
    obs.reset()
    obs.clear_spans()
    yield
    reset_decode_stats()
    obs.reset()
    obs.clear_spans()


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPT(TINY)
    m.eval()
    return m


def _engine(model, **kw):
    kw.setdefault("prefill_chunk_tokens", 4)
    return DecodeEngine(model, max_batch_size=2, max_seq_len=64,
                        page_size=16, **kw)


def _serve(eng, prompts=PROMPTS, new=NEW, pause_s=0.0):
    """``prompts`` through `ServingFrontend` (steps on the executor's
    thread, as shipped); with ``pause_s`` the second half is submitted
    after the first has finished and the driver has waited that long."""
    async def go():
        outs = []
        async with ServingFrontend(eng) as fe:
            halves = [prompts] if not pause_s else \
                [prompts[:1], prompts[1:]]
            for k, half in enumerate(halves):
                if k:
                    await asyncio.sleep(pause_s)
                streams = [await fe.submit(p, max_new_tokens=new)
                           for p in half]
                for s in streams:
                    outs.append([t async for t in s])
        return outs

    return asyncio.run(go())


def _profiled(fn, d):
    """``fn()`` under the profiler as the benchmark's `TracedWindow`
    sets it up, the profile written under ``d``; returns (fn's result,
    path of the `.xplane.pb`)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(d), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(d), "**", "*.xplane.pb"),
                        recursive=True)
    return out, path


def _program_events(path):
    """[(thread, name, start_ns, end_ns, stats)] of the program's own
    spans in a trace file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "frontend.")):
                    out.append((line.name, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One profile over a chunked engine and a speculative one, each
    behind its frontend and warmed up before the profile starts."""
    plain = _engine(model)
    spec = _engine(model, spec_decode_k=2)
    for eng in (plain, spec):
        eng.generate([PROMPTS[0][:9]], max_new_tokens=3)
    outs, path = _profiled(lambda: (_serve(plain), _serve(spec)),
                           tmp_path_factory.mktemp("trace_seam"))
    return {"outs": outs, "path": path, "events": _program_events(path),
            "engines": (plain._engine_id, spec._engine_id)}


# ---------------------------------------------------------------------------
# the spans
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", SPANS)
def test_profile_holds_every_span_of_the_seam(traced, name):
    _, host_spans = trace_reduce.read_xplane(traced["path"])
    mine = [h for h in host_spans if h[0] == name]
    assert mine, f"no {name} in {sorted({h[0] for h in host_spans})}"
    assert all(dur > 0 for _, _, dur in mine)


def test_span_names_stay_clean_and_carry_step_and_engine(traced):
    for _, name, _, _, stats in traced["events"]:
        assert name in SPANS, name  # no `#step=..#` decoration
        assert stats["engine"] in traced["engines"]
        if name.startswith("engine."):
            assert stats["step"] >= 1


def test_spans_of_one_thread_nest_only_under_admit_draft_emit(traced):
    by_thread = {}
    for thread, name, s, e, _ in traced["events"]:
        if name.startswith("engine."):
            by_thread.setdefault(thread, []).append((s, e, name))
    assert by_thread
    for spans in by_thread.values():
        spans.sort()
        for i, (s, e, name) in enumerate(spans):
            for s2, e2, name2 in spans[i + 1:]:
                if s2 >= e:
                    break
                # overlap on one thread is containment, and only a
                # composite phase holds another span
                assert e2 <= e, (name, name2)
                assert name in HOLDERS, (name, name2)


def test_a_steps_spans_share_its_number_and_steps_do_not_interleave(
        traced):
    for eid in traced["engines"]:
        steps = {}
        for _, name, s, e, stats in traced["events"]:
            if name.startswith("engine.") and stats["engine"] == eid:
                steps.setdefault(stats["step"], []).append((name, s, e))
        ran = {k: v for k, v in steps.items()
               if any(n in DISPATCH for n, _, _ in v)}
        assert len(ran) >= NEW
        for spans in ran.values():
            names = [n for n, _, _ in spans]
            for needed in ("engine.admit", "engine.fetch", "engine.emit",
                           "engine.step_tail"):
                assert needed in names, (needed, names)
            # one engine step dispatches one batch (a speculative
            # round may feed prompt chunks first)
            assert sum(n in DISPATCH for n in names) <= 2
        order = sorted(steps)
        for a, b in zip(order, order[1:]):
            assert max(e for _, _, e in steps[a]) <= \
                min(s for _, s, _ in steps[b]), (a, b)


def test_flight_record_and_spans_agree_on_the_step_number(model):
    eng = _engine(model)
    eng.generate(PROMPTS, max_new_tokens=NEW)
    assert eng._span_step == eng._step_no
    assert eng._flight.records()[-1]["step"] == eng._span_step


def test_spans_appear_with_the_flight_recorder_off(model, tmp_path):
    eng = _engine(model, flight_window=0)
    assert eng._flight is None
    eng.generate([PROMPTS[0][:9]], max_new_tokens=3)
    _, path = _profiled(lambda: _serve(eng), tmp_path)
    names = {name for _, name, _, _, _ in _program_events(path)}
    assert set(SPANS) - names <= {"engine.prefill", "engine.verify",
                                  "engine.draft"}, names
    assert decode_stats()["flight_records"] == 0


def test_outputs_are_bit_equal_with_and_without_a_profile(model, traced):
    plain = _serve(_engine(model))
    spec = _serve(_engine(model, spec_decode_k=2))
    assert (plain, spec) == traced["outs"]
    assert [len(o) for o in plain] == [NEW] * len(PROMPTS)


# ---------------------------------------------------------------------------
# the counters
# ---------------------------------------------------------------------------
def test_counters_are_in_the_schema_and_zero_before_traffic(model):
    assert set(NEW_COUNTERS) <= set(profiler.DECODE_STAT_COUNTERS)
    _engine(model)
    st = profiler.decode_stats()
    assert [st[k] for k in NEW_COUNTERS] == [0] * len(NEW_COUNTERS)
    # none may trip the benchmark's zero-counter check
    assert not [k for k in NEW_COUNTERS if "retrace" in k]


def test_one_admission_and_one_first_token_a_request(model):
    _serve(_engine(model))
    st = decode_stats()
    assert st["admissions"] == st["first_tokens"] == len(PROMPTS)
    assert st["queue_wait_s"] > 0 and st["first_token_wait_s"] > 0
    # the request histograms saw the same waits
    assert obs.REQUEST_QUEUE_WAIT.series_state()["sum"] == \
        pytest.approx(st["queue_wait_s"])
    assert obs.REQUEST_TTFT.series_state()["sum"] == pytest.approx(
        st["queue_wait_s"] + st["first_token_wait_s"])


@pytest.mark.parametrize("kw", [{}, {"spec_decode_k": 2}],
                         ids=["chunked", "speculative"])
def test_mixed_time_lies_inside_the_blended_sums(model, kw):
    _serve(_engine(model, **kw))
    st = decode_stats()
    assert st["mixed_steps"] > 0
    assert 0 < st["mixed_time_s"] <= \
        st["decode_time_s"] + st["prefill_time_s"]
    if not kw:
        # chunk-carrying steps are engine steps there: the rest of
        # decode_time_s is the plain decode steps'
        assert st["mixed_steps"] < st["steps"]
        assert st["mixed_time_s"] < st["decode_time_s"]


def test_host_time_and_between_steps_fit_the_wall(model):
    eng = _engine(model)
    eng.generate([PROMPTS[0][:9]], max_new_tokens=3)
    decode_stats(reset=True)
    t0 = time.perf_counter()
    _serve(eng)
    wall = time.perf_counter() - t0
    st = decode_stats()
    assert st["host_in_step_s"] > 0 and st["between_steps_s"] > 0
    # the four sums count each second of the busy engine once: the
    # dispatch-to-fetched walls, the rest of the steps' walls and what
    # lies between two steps all happened inside the serve
    assert st["decode_time_s"] + st["prefill_time_s"] + \
        st["host_in_step_s"] + st["between_steps_s"] < wall


def test_engine_alone_books_no_time_between_steps(model):
    _engine(model).generate(PROMPTS, max_new_tokens=NEW)
    st = decode_stats()
    assert st["host_in_step_s"] > 0
    assert st["between_steps_s"] == 0  # the frontend's counter


def test_a_wait_for_work_is_not_time_between_steps(model):
    eng = _engine(model)
    eng.generate([PROMPTS[0][:9]], max_new_tokens=3)
    decode_stats(reset=True)
    pause = 0.5
    _serve(eng, pause_s=pause)
    st = decode_stats()
    assert st["admissions"] == len(PROMPTS)
    assert 0 < st["between_steps_s"] < pause


def test_reset_clears_the_counters(model):
    _serve(_engine(model))
    st = decode_stats(reset=True)
    assert all(st[k] > 0 for k in NEW_COUNTERS), st
    st = decode_stats()
    assert [st[k] for k in NEW_COUNTERS] == [0] * len(NEW_COUNTERS)


# ---------------------------------------------------------------------------
# the train step's call
# ---------------------------------------------------------------------------
def test_train_step_call_splits_into_three_spans(tmp_path):
    from paddle_tpu import jit, nn, optimizer

    paddle.seed(0)
    net = nn.Linear(8, 4)
    step = jit.train_step(
        net, lambda m, x, y: ((m(x) - y) ** 2).mean(),
        optimizer.SGD(0.1, parameters=net.parameters()))
    x = paddle.to_tensor(np.ones((2, 8), np.float32))
    y = paddle.to_tensor(np.zeros((2, 4), np.float32))
    first = float(step(x, y))  # compiles outside the profile
    (second, third), path = _profiled(
        lambda: (float(step(x, y)), float(step(x, y))), tmp_path)
    assert third < second < first
    _, host_spans = trace_reduce.read_xplane(path)
    for name in ("train_step.operands", "train_step.enqueue",
                 "train_step.rebind"):
        mine = sorted((s, s + d) for n, s, d in host_spans if n == name)
        assert len(mine) == 2, (name, mine)
    spans = sorted((s, s + d, n) for n, s, d in host_spans
                   if n.startswith("train_step."))
    assert [n.split(".")[1] for _, _, n in spans] == \
        ["operands", "enqueue", "rebind"] * 2
    for (_, e, _), (s, _, _) in zip(spans, spans[1:]):
        assert e <= s  # one after the other, none holds another
