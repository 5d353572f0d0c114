"""The serve loop's phases as spans on the profiler's clock.

One seam: a flight-recorder phase is, at the same call site, an
``engine.<phase>`` `jax.profiler.TraceAnnotation`; `_host_fetch` is
``engine.fetch``; batch assembly before a dispatch is
``engine.assemble``; what `DecodeEngine.step` does after the batch is
``engine.step_tail``; the frontend's own work between two steps is
``frontend.control`` / ``frontend.flush``, its waits for work and for a
slow consumer ``wait.empty`` / ``wait.backpressure``; the edge's
hand-off of a token is ``edge.pump`` (event loop) and ``edge.write``
(SSE handler); a garbage collection is ``host.gc``.  Pinned here, on the
CPU:

* a profiled tiny engine behind `ServingFrontend` yields every span at
  least once, read back with `benchmarks.trace_reduce.read_xplane`;
  names stay clean (``step=`` / ``engine=`` ride as stats);
* on one thread spans nest only under admit / assemble / draft /
  emit, a step's spans share ``step=``, and steps do not interleave;
  dispatch spans carry the batch's rows; no new span holds a fetch;
* annotations that overlap without nesting keep their true ends;
* the spans are there with ``flight_window=0`` too, and serving is
  bit-equal with and without a profile running;
* the seven counters the benchmark's serve metrics read
  (`profiler.DECODE_STAT_COUNTERS`): zero before traffic, one admission
  and one first token a request, mixed steps inside the blended sums,
  the time sums count each second once, nothing booked across a wait,
  cleared by ``reset=True``;
* the relay, hop and collector counters: zero before traffic, one
  relayed token per SSE token, one hop per step's round trip to the
  executor, one collection per collection.
"""
import asyncio
import gc
import glob
import http.client
import json
import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.fleet import EdgeServer
from paddle_tpu.inference.frontend import ServingFrontend
from paddle_tpu.observability import flight
from paddle_tpu.inference.serving import (DecodeEngine, decode_stats,
                                          reset_decode_stats)
from paddle_tpu.models.gpt import GPT, GPTConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks import trace_reduce  # noqa: E402
from benchmarks import trace_split  # noqa: E402

TINY = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                 num_heads=4, max_seq_len=256,
                 use_parallel_layers=False, dropout=0.0)
PROMPTS = [[1, 2, 3, 4, 5, 1, 2, 3, 4, 5, 1, 2],
           [7, 8, 9, 7, 8, 9, 7, 8]]
NEW = 6
SPANS = ("engine.admit", "engine.cache", "engine.decode", "engine.mixed",
         "engine.prefill", "engine.verify", "engine.draft", "engine.fetch",
         "engine.emit", "engine.step_tail", "frontend.control",
         "frontend.flush", "engine.assemble", "wait.empty")
DISPATCH = ("engine.decode", "engine.mixed", "engine.prefill",
            "engine.verify")
HOLDERS = ("engine.admit", "engine.assemble", "engine.draft",
           "engine.emit")
# the spans that name the serve loop's idle stretches, none of which may
# hold a wait for the device
IDLE_NAMERS = ("engine.assemble", "wait.empty", "wait.backpressure",
               "edge.pump", "edge.write", "host.gc")
NEW_COUNTERS = ("queue_wait_s", "admissions", "first_token_wait_s",
                "first_tokens", "mixed_time_s", "host_in_step_s",
                "between_steps_s")
RELAY_COUNTERS = ("relay_s", "relayed_tokens", "hop_s", "hops",
                  "gc_pause_s", "gc_collections", "gc_gen2_collections")


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


def _program_events(path, prefixes=("engine.", "frontend.", "wait.")):
    """[(thread, name, start_ns, end_ns, stats)] of the program's own
    spans in a trace file; ``thread`` numbers the host lines, as
    `trace_split.read_xplane_detail` does."""
    _, host = trace_split.read_xplane_detail(path)
    return [(t, n, round(s * 1e9), round((s + d) * 1e9), st)
            for n, s, d, t, st in host if n.startswith(prefixes)]


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
        # no `#step=..#` decoration
        assert name in SPANS + ("wait.backpressure",), name
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
        landed = []
        for k, spans in ran.items():
            names = [n for n, _, _ in spans]
            for needed in ("engine.admit", "engine.step_tail"):
                assert needed in names, (needed, names)
            # a call lands a step (the one in flight, or its own when
            # it drains) in every call but a batch's first
            if "engine.fetch" in names:
                assert "engine.emit" in names, names
                landed.append(k)
            # one engine step dispatches one batch (a speculative
            # round may feed prompt chunks first)
            assert sum(n in DISPATCH for n in names) <= 2
        assert len(landed) >= len(ran) - 1
        order = sorted(steps)
        for a, b in zip(order, order[1:]):
            assert max(e for _, _, e in steps[a]) <= \
                min(s for _, s, _ in steps[b]), (a, b)


def test_flight_record_and_spans_agree_on_the_step_number(model):
    eng = _engine(model)
    for p in PROMPTS:
        eng.add_request(p, max_new_tokens=NEW)
    landing_only = 0
    while eng._busy():
        eng.step()
        assert eng._flight.records()[-1]["step"] == eng._step_no
        if eng._span_step != eng._step_no:
            # nothing dispatched: the call only landed the step in
            # flight, and like an idle pass its spans carry the next
            # step's number
            assert eng._span_step == eng._step_no + 1
            landing_only += 1
    assert landing_only == 1


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


def test_dispatch_spans_carry_the_batch_composition(traced):
    seen_chunk = False
    for _, name, _, _, stats in traced["events"]:
        if name in ("engine.decode", "engine.mixed", "engine.prefill"):
            rows = (stats["decode_rows"], stats["chunk_rows"],
                    stats["chunk_tokens"])
            assert min(rows) >= 0 and sum(rows[:2]) >= 1, (name, stats)
            assert stats["decode_rows"] + stats["chunk_rows"] <= 2
            # the chunk budget caps a step's prompt tokens
            assert stats["chunk_tokens"] <= 4
            assert (stats["chunk_rows"] > 0) == (stats["chunk_tokens"] > 0)
            if name == "engine.mixed":
                assert stats["chunk_rows"] >= 1
            if name == "engine.decode":
                assert stats["chunk_rows"] == 0
            seen_chunk |= stats["chunk_rows"] > 0
    assert seen_chunk


def test_assembly_ends_before_its_steps_dispatch(traced):
    """Each dispatch follows an ``engine.assemble`` of its step (a
    speculative round assembles before its draft, and again before its
    verify dispatch)."""
    by_step = {}
    for _, name, s, e, st in traced["events"]:
        if name in ("engine.assemble", "engine.draft") or name in DISPATCH:
            by_step.setdefault((st["engine"], st["step"]), []).append(
                (s, e, name))
    assert by_step
    for spans in by_step.values():
        spans.sort()
        names = [n for _, _, n in spans]
        for k, (s, _, n) in enumerate(spans):
            if n in DISPATCH:
                assert k and spans[k - 1][2] == "engine.assemble", names
                assert spans[k - 1][1] <= s


def _holds_a_fetch(events, same_thread):
    fetches = [(t, s, e) for t, n, s, e, _ in events if n == "engine.fetch"]
    held = []
    for t, n, s, e, _ in events:
        if n not in IDLE_NAMERS:
            continue
        for t2, s2, e2 in fetches:
            if (t2 == t or not same_thread) and s <= s2 and e2 <= e:
                held.append((n, s, e))
    return held


def test_no_new_span_holds_a_fetch(traced):
    events = _program_events(traced["path"],
                             prefixes=("engine.", "wait.", "host."))
    assert {n for _, n, _, _, _ in events} >= {"engine.assemble",
                                               "wait.empty"}
    assert not _holds_a_fetch(events, same_thread=True)
    # the driver's waits sit on the event loop, and no step runs
    # while it waits
    waits = [e for e in events if e[1].startswith("wait.")]
    assert not _holds_a_fetch(
        waits + [e for e in events if e[1] == "engine.fetch"],
        same_thread=False)


def test_overlapping_annotations_keep_their_true_ends(tmp_path):
    """A ``wait.*`` span is open across an await; the pump's spans open
    and close on the same thread meanwhile, and one may outlive it.
    The profile keeps each annotation's own start and end."""
    # each span's wall from stamps inside its bracket (a floor) and
    # outside it (a ceiling): a thread preempted there moves no bound
    inner, outer = {}, {}

    async def waiter(ev):
        o0 = time.perf_counter()
        with flight.annotation("wait.empty", engine=1):
            i0 = time.perf_counter()
            await ev.wait()
            inner["wait.empty"] = time.perf_counter() - i0
        outer["wait.empty"] = time.perf_counter() - o0

    async def pumps(ev):
        for k in range(3):
            o0 = time.perf_counter()
            with flight.annotation("edge.pump", request=k):
                i0 = time.perf_counter()
                time.sleep(0.002)
                inner[f"pump{k}"] = time.perf_counter() - i0
            outer[f"pump{k}"] = time.perf_counter() - o0
            await asyncio.sleep(0.002)
        # opened inside the wait, closed after it: not nested
        span = flight.annotation("edge.write", request=9)
        o0 = time.perf_counter()
        span.__enter__()
        i0 = time.perf_counter()
        ev.set()
        await asyncio.sleep(0.004)
        inner["write"] = time.perf_counter() - i0
        span.__exit__(None, None, None)
        outer["write"] = time.perf_counter() - o0

    async def go():
        ev = asyncio.Event()
        await asyncio.gather(waiter(ev), pumps(ev))

    _, path = _profiled(lambda: asyncio.run(go()), tmp_path)
    events = _program_events(path, prefixes=("wait.", "edge."))
    got = {}
    for t, n, s, e, st in events:
        key = n if n == "wait.empty" else (
            "write" if n == "edge.write" else f"pump{st['request']}")
        got[key] = (t, (e - s) * 1e-9)
    assert set(got) == set(inner)
    assert len({t for t, _ in got.values()}) == 1  # one thread
    for key, (_, wall) in got.items():
        assert inner[key] - 50e-6 <= wall <= outer[key] + 50e-6, key
    (_, _, ws, we, _), = [e for e in events if e[1] == "wait.empty"]
    (_, _, xs, xe, _), = [e for e in events if e[1] == "edge.write"]
    assert ws < xs < we < xe


def test_a_slow_consumer_is_a_backpressure_wait(model, tmp_path):
    """With a one-token stream buffer and a consumer that sleeps between
    reads, the driver pauses between steps: ``wait.backpressure`` spans
    on the loop's thread, with ``engine=``, holding no fetch."""
    eng = _engine(model)
    eng.generate([PROMPTS[0][:9]], max_new_tokens=3)

    async def go():
        async with ServingFrontend(eng, stream_buffer=1) as fe:
            s = await fe.submit(PROMPTS[0], max_new_tokens=NEW)
            out = []
            async for t in s:
                out.append(t)
                await asyncio.sleep(0.05)
            return out

    out, path = _profiled(lambda: asyncio.run(go()), tmp_path)
    assert len(out) == NEW
    events = _program_events(path)
    waits = [e for e in events if e[1] == "wait.backpressure"]
    assert waits and all(e[4]["engine"] == eng._engine_id for e in waits)
    # a pause lasts what a step leaves of the consumer's 50 ms sleep
    assert max(e[3] - e[2] for e in waits) > 20e6
    assert not _holds_a_fetch(events, same_thread=False)


# ---------------------------------------------------------------------------
# the edge
# ---------------------------------------------------------------------------
def _sse(port, prompt, new):
    """One `POST /v1/generate`: (request id, tokens)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request("POST", "/v1/generate", body=json.dumps(
            {"prompt_ids": prompt, "max_new_tokens": new}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        rid, toks = None, []
        for line in resp:
            if line.startswith(b"data: "):
                ev = json.loads(line[6:])
                if "request_id" in ev:
                    rid = ev["request_id"]
                elif "t" in ev:
                    toks.append(ev["t"])
        return rid, toks
    finally:
        conn.close()


def _serve_edge(eng, prompts=PROMPTS, new=NEW):
    edge = EdgeServer(eng)
    port = edge.start()
    try:
        return [_sse(port, p, new) for p in prompts]
    finally:
        edge.close()


@pytest.fixture(scope="module")
def edge_traced(model, tmp_path_factory):
    """One profile over a chunked engine behind `EdgeServer`, warmed up
    before; the counters of the served window beside it."""
    eng = _engine(model)
    eng.generate([PROMPTS[0][:9]], max_new_tokens=3)
    reset_decode_stats()
    served, path = _profiled(lambda: _serve_edge(eng),
                             tmp_path_factory.mktemp("edge_seam"))
    return {"served": served, "path": path, "counters": decode_stats(),
            "events": _program_events(path, prefixes=("edge.",))}


@pytest.mark.parametrize("name", ["edge.pump", "edge.write"])
def test_edge_spans_one_a_token_named_by_request(edge_traced, name):
    mine = [e for e in edge_traced["events"] if e[1] == name]
    for rid, toks in edge_traced["served"]:
        assert len(toks) == NEW
        assert sum(e[4]["request"] == rid for e in mine) == len(toks)
    assert len(mine) == NEW * len(PROMPTS)
    assert all(e[3] > e[2] for e in mine)
    # the pump runs on the event loop, each write on its handler's
    # thread (a later handler may reuse an earlier one's)
    pumps = {e[0] for e in edge_traced["events"] if e[1] == "edge.pump"}
    writes = {e[0] for e in edge_traced["events"] if e[1] == "edge.write"}
    assert len(pumps) == 1 and not pumps & writes


def test_edge_spans_hold_no_fetch(edge_traced):
    events = _program_events(edge_traced["path"],
                             prefixes=("engine.", "edge.", "wait."))
    assert not _holds_a_fetch(events, same_thread=True)


def test_relay_counts_each_token_once(edge_traced):
    c = edge_traced["counters"]
    assert c["relayed_tokens"] == NEW * len(PROMPTS)
    assert 0 < c["relay_s"] < 60.0
    assert c["hops"] >= c["steps"] > 0 and c["hop_s"] > 0


def test_edge_outputs_are_bit_equal_with_and_without_a_profile(
        model, edge_traced):
    plain = _serve_edge(_engine(model))
    assert [t for _, t in plain] == [t for _, t in edge_traced["served"]]


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


# ---------------------------------------------------------------------------
# the relay, the hops and the collector
# ---------------------------------------------------------------------------
@pytest.fixture
def no_automatic_gc():
    """Collections only where a test asks for them."""
    gc.disable()
    try:
        yield
    finally:
        gc.enable()


def test_relay_hop_and_gc_counters_are_in_the_schema_and_zero_before(
        model, no_automatic_gc):
    assert set(RELAY_COUNTERS) <= set(profiler.DECODE_STAT_COUNTERS)
    _engine(model)
    reset_decode_stats()
    st = profiler.decode_stats()
    assert [st[k] for k in RELAY_COUNTERS] == [0] * len(RELAY_COUNTERS)
    assert not [k for k in RELAY_COUNTERS if "retrace" in k]


def test_one_hop_a_steps_round_trip_to_the_executor(model):
    eng = _engine(model)
    eng.generate([PROMPTS[0][:9]], max_new_tokens=3)
    calls = []
    real = eng.step

    def counted():
        calls.append(1)
        return real()
    eng.step = counted
    reset_decode_stats()
    _serve(eng)
    st = decode_stats()
    assert st["hops"] == len(calls) > 0
    assert 0 < st["hop_s"] < st["hops"] * 1.0
    assert st["relayed_tokens"] == 0  # no edge, no relay


def test_steps_on_the_loop_take_no_hop(model):
    eng = _engine(model)

    async def go():
        async with ServingFrontend(eng, step_in_thread=False) as fe:
            s = await fe.submit(PROMPTS[0], max_new_tokens=3)
            return [t async for t in s]

    assert len(asyncio.run(go())) == 3
    st = decode_stats()
    assert st["steps"] > 0 and st["hops"] == 0 and st["hop_s"] == 0


def test_one_collection_counts_once_by_generation(no_automatic_gc):
    reset_decode_stats()
    gc.collect()
    st = decode_stats()
    assert st["gc_collections"] == 1 and st["gc_gen2_collections"] == 1
    assert st["gc_pause_s"] > 0
    gc.collect(0)
    gc.collect(1)
    st = decode_stats(reset=True)
    assert st["gc_collections"] == 3 and st["gc_gen2_collections"] == 1
    st = decode_stats()
    assert st["gc_collections"] == 0 and st["gc_pause_s"] == 0


def test_a_collection_is_a_span_on_the_thread_that_ran_it(tmp_path):
    def collect():
        with flight.annotation("engine.admit", step=1, engine=1):
            gc.collect(1)
        t = __import__("threading").Thread(target=gc.collect)
        t.start()
        t.join()

    _, path = _profiled(collect, tmp_path)
    events = _program_events(path, prefixes=("host.", "engine."))
    gcs = [e for e in events if e[1] == "host.gc"]
    assert {e[4]["generation"] for e in gcs} >= {1, 2}
    (admit,) = [e for e in events if e[1] == "engine.admit"]
    inside = [e for e in gcs if e[4]["generation"] == 1
              and admit[2] <= e[2] and e[3] <= admit[3]]
    assert inside and inside[0][0] == admit[0]
    assert any(e[4]["generation"] == 2 and e[0] != admit[0] for e in gcs)
