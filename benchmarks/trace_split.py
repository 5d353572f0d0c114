"""The chip's idle in a profiler trace, split by what the program's host
was doing in each piece of it; beside `trace_reduce`, which it leaves as
it is (its keys come back unchanged, the new ones beside them).

    python benchmarks/trace_split.py <trace.xplane.pb> [--counters <json>]

prints the split of a trace kept with `run.py --keep-trace`, and with a
dump of `profiler.decode_stats()` the six serve readings of `readings`.

* **One clock.**  The device's clock runs apart from the host's.  The
  offset (device less host) is estimated from the trace by causality: a
  program's run cannot end on the device after the runtime's completion
  callback for it starts on the host, nor start before the runtime
  enqueued it (the device's ``XLA Modules`` and the host's
  ``CompleteCallbacks`` / ``DoEnqueueProgram`` share ``run_id``); the
  estimate is the middle of the two bounds, some tens of microseconds
  apart on the chip.  A trace without those events falls back to the
  program's spans: a fetch cannot return before the device work it waits
  on has ended (the fetches bound the offset from below, the estimate),
  and a step's work cannot start before its dispatch span begins.  The
  device's events are shifted by it for the split alone.
* **Innermost split.**  Every idle gap of the fullest chip is cut at each
  boundary of the program's spans (`PROGRAM`) on every thread, and each
  piece goes to one span: an open ``host.gc`` first (a collection holds
  every thread); else the innermost open span of the threads that drive
  the chip (those whose dispatch spans carry the steps: the executor may
  hand steps to more than one worker); else the latest-started open
  program span of any other thread.  A piece under
  none is dark.  The spans go to four buckets that partition the idle:
  ``engine.fetch`` fetch, ``wait.*`` empty, any other program span host,
  none dark.  The benchmark's own spans and JAX's enter no bucket; the
  dark pieces are listed by the latest-started of them that is open.  A
  span is recorded when it closes, so one still open when the profile
  stops is not in the trace: the dark idle after the last program span
  ended is reported apart (``idle_dark_tail_s``).
* **Per-span table and steps.**  Count, total, self time (less the
  program's spans nested inside on the same thread), p50 and p95 of
  duration, for each program span that starts inside the window; the
  traced steps are the distinct ``step=`` of the dispatch spans that
  start there.

Times are seconds.  A host event is ``(name, start_s, duration_s, thread,
stats)``; ``thread`` numbers the host planes' lines (the profiler names
many threads alike).
"""
from __future__ import annotations

import bisect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace_reduce as tr  # noqa: E402

PROGRAM = ("engine.", "frontend.", "wait.", "edge.", "host.",
           "train_step.")
DISPATCH = ("engine.decode", "engine.mixed", "engine.prefill",
            "engine.verify")
DRIVE = DISPATCH + ("train_step.enqueue",)
FETCH = "engine.fetch"
GC = "host.gc"
WAIT = "wait."
BUCKETS = ("fetch", "empty", "host", "dark")
NO_SPAN = "(no span)"
# a program that keeps the split's spans opens one of these every step
NEW_SPAN = "engine.assemble"
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
MODULES_LINE = "XLA Modules"
SEARCH_S = 10e-3   # the offsets tried, either way
GRID_S = 20e-6     # their spacing
MIN_BURST_S = 30e-6  # shorter device work is no step's (keys, uploads)


def is_program(name: str) -> bool:
    return name.startswith(PROGRAM)


def bucket(name) -> str:
    if name is None:
        return "dark"
    if name == FETCH:
        return "fetch"
    if name.startswith(WAIT):
        return "empty"
    return "host"


def nearest_rank(values, q):
    v = sorted(values)
    if not v:
        return None
    i = -(-len(v) * q // 100) - 1
    return v[min(len(v) - 1, max(0, int(i)))]


# -------------------------------------------------------- the file format
def read_xplane_detail(path: str, ops_line: str = tr.OPS_LINE):
    """`trace_reduce.read_xplane`'s events, in its order and under its
    filter, with each host event's thread and stats beside it."""
    return _read(path, ops_line)[:2]


def _read(path, ops_line=tr.OPS_LINE):
    """`read_xplane_detail`'s two, and each device's program runs:
    device -> [(run_id, start_s, end_s)]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events, host_events, runs = {}, [], {}
    thread = 0
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            events = device_events.setdefault(dev, [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        rid = dict(ev.stats).get("run_id")
                        if rid is not None:
                            s = ev.start_ns * 1e-9
                            runs.setdefault(dev, []).append(
                                (rid, s, s + ev.duration_ns * 1e-9))
                if line.name != ops_line:
                    continue
                for ev in line.events:
                    events.append((ev.name, ev.start_ns * 1e-9,
                                   ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0 and "::" not in ev.name:
                        host_events.append((
                            ev.name, ev.start_ns * 1e-9,
                            ev.duration_ns * 1e-9, thread,
                            {k: v for k, v in ev.stats}))
                thread += 1
    return device_events, host_events, runs


# ------------------------------------------------------------ the pieces
def driving_threads(host_events, window) -> set:
    """The threads on which dispatch spans start inside the window."""
    lo, hi = window
    return {t for n, s, _, t, _ in host_events
            if n in DRIVE and lo <= s < hi}


def traced_steps(host_events, window) -> int:
    """Distinct (engine, step) of the dispatch spans starting inside the
    window: a speculative step's chunk and verify dispatches are one."""
    lo, hi = window
    return len({(st.get("engine"), st.get("step"))
                for n, s, _, _, st in host_events
                if n in DISPATCH and lo <= s < hi})


def span_table(host_events, window) -> dict:
    """name -> count, total_s, self_s, p50_s, p95_s over the program's
    spans that start inside the window."""
    lo, hi = window
    by_thread = {}
    for n, s, d, t, _ in host_events:
        if is_program(n):
            by_thread.setdefault(t, []).append((s, -d, n))
    rows = {}
    for spans in by_thread.values():
        spans.sort()
        for i, (s, neg_d, n) in enumerate(spans):
            if not lo <= s < hi:
                continue
            e = s - neg_d
            inner = []
            for s2, neg_d2, _ in spans[i + 1:]:
                if s2 >= e:
                    break
                inner.append((s2, min(e, s2 - neg_d2)))
            r = rows.setdefault(n, {"count": 0, "total_s": 0.0,
                                    "self_s": 0.0, "_d": []})
            r["count"] += 1
            r["total_s"] += -neg_d
            r["self_s"] += -neg_d - tr.length(tr.union(inner))
            r["_d"].append(-neg_d)
    for r in rows.values():
        d = r.pop("_d")
        r["p50_s"] = nearest_rank(d, 50)
        r["p95_s"] = nearest_rank(d, 95)
    return rows


def _busy(events, lo, hi, shift=0.0):
    return tr.union(tr.clip([(s - shift, s - shift + d)
                             for _, s, d in events], lo, hi))


def fullest_device(device_events, window, shift=0.0):
    lo, hi = window
    best, busy = None, []
    for dev, events in sorted(device_events.items()):
        b = _busy(events, lo, hi, shift)
        if best is None or tr.length(b) > tr.length(busy):
            best, busy = dev, b
    return best, busy


def _bursts(busy, min_gap=tr.MIN_GAP_S, min_len=MIN_BURST_S):
    """Busy intervals joined across holes shorter than launch slack;
    those shorter than a step's work dropped."""
    out = []
    for s, e in busy:
        if out and s - out[-1][1] < min_gap:
            out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out if e - s >= min_len]


def _steps_of(host_events, threads, window):
    """(dispatch start, end of the first fetch after it on its thread),
    for the dispatch spans starting inside the window."""
    lo, hi = window
    by_thread = {}
    for n, s, d, t, _ in host_events:
        if t in threads and (n in DISPATCH or n == FETCH):
            by_thread.setdefault(t, []).append((s, d, n))
    out = []
    for mine in by_thread.values():
        mine.sort()
        for i, (s, _, n) in enumerate(mine):
            if n not in DISPATCH or not lo <= s < hi:
                continue
            for s2, d2, n2 in mine[i + 1:]:
                if n2 in DISPATCH:
                    break
                if n2 == FETCH:
                    out.append((s, s2 + d2))
                    break
    return sorted(out)


def runtime_offset(runs, host_events, device: int):
    """(offset_s, completions, slack_s) from ``device``'s program runs
    (`_read`) and the runtime's host events of the same ``run_id``: the
    middle of the completion callbacks' bound from below and the
    enqueues' bound from above, how many completions bounded it, and the
    room between the bounds (negative where they contradict).  None
    without a run that both name."""
    enq, done = {}, {}
    for n, s, _, _, st in host_events:
        rid = st.get("run_id")
        if rid is None or st.get("device_ordinal", device) != device:
            continue
        if n == ENQUEUE:
            enq[rid] = min(enq.get(rid, s), s)
        elif n == COMPLETE:
            done[rid] = min(done.get(rid, s), s)
    mine = runs.get(device, [])
    lows = [e - done[r] for r, _, e in mine if r in done]
    highs = [s - enq[r] for r, s, _ in mine if r in enq]
    if not lows or not highs:
        return None
    low, high = max(lows), min(highs)
    return (low + high) / 2 if high >= low else low, len(lows), high - low


def clock_offset(device_events, host_events, window, runs=None):
    """(offset_s, bounds, slack_s, source): the device's clock less the
    host's.  From the runtime's events where the trace has them
    (`runtime_offset`, source ``"runtime"``); else (``"fetch"``) as the
    program's fetches bound it from below, how many fetches were paired
    with device work, and the room the dispatches leave above it
    (negative where the pairing contradicts itself).  None where neither
    bounds it, or where the bound lies beyond the offsets searched."""
    dev, _ = fullest_device(device_events, window)
    if runs and dev is not None:
        est = runtime_offset(runs, host_events, dev)
        if est is not None and abs(est[0]) <= SEARCH_S:
            return est + ("runtime",)
    steps = _steps_of(host_events, driving_threads(host_events, window),
                      window)
    if not steps:
        return None
    lo, hi = window
    if dev is None:
        return None
    bursts = _bursts(_busy(device_events[dev], lo - 2 * SEARCH_S,
                           hi + 2 * SEARCH_S))
    if not bursts:
        return None
    starts = [s for s, _ in bursts]
    ends = [e for _, e in bursts]
    run = [0.0]
    for s, e in bursts:
        run.append(run[-1] + e - s)

    def covered(a, b):
        """Busy (of the bursts) inside [a, b]."""
        i = bisect.bisect_right(ends, a)
        j = bisect.bisect_left(starts, b)
        if j <= i:
            return 0.0
        total = run[j] - run[i]
        total -= max(0.0, a - starts[i])
        total -= max(0.0, ends[j - 1] - b)
        return total

    n = int(SEARCH_S / GRID_S)
    score = []
    for k in range(-n, n + 1):
        shift = k * GRID_S
        score.append((sum(covered(ds + shift, fe + shift)
                          for ds, fe in steps), -abs(k), shift))
    shift0 = max(score)[2]
    low, high, paired = None, None, 0
    for ds, fe in steps:
        i = bisect.bisect_right(ends, ds + shift0)
        j = bisect.bisect_left(starts, fe + shift0)
        if j <= i:
            continue
        paired += 1
        b = max(ends[i:j]) - fe
        a = min(starts[i:j]) - ds
        low = b if low is None else max(low, b)
        high = a if high is None else min(high, a)
    if not paired or abs(low) > SEARCH_S:
        return None  # no fetch came near its work: no bound worth one
    return low, paired, high - low, "fetch"


def innermost_split(device_events, host_events, window, offset=0.0):
    """The idle of the fullest chip, its device events shifted onto the
    host's clock by ``offset``, split as the module docstring says."""
    lo, hi = window
    _, busy = fullest_device(device_events, window, offset)
    idle = tr.gaps(busy, lo, hi)
    drive = driving_threads(host_events, window)
    marks = []
    for i, (n, s, d, t, _) in enumerate(host_events):
        if s + d <= lo or s >= hi:
            continue
        marks.append((s, 1, i))
        marks.append((s + d, 0, i))
    marks.sort()
    open_prog = {}   # thread -> [(start, index)] of open program spans
    open_other = []  # [(start, index)] of the others, any thread
    open_gc = [0]
    by_span, dark_under = {}, {}
    out = dict.fromkeys(BUCKETS, 0.0)

    def apply(kind, i):
        n, s, _, t, _ = host_events[i]
        if is_program(n):
            mine = open_prog.setdefault(t, [])
            if kind:
                mine.append((s, i))
            else:
                mine.remove((s, i))
            if n == GC:
                open_gc[0] += 1 if kind else -1
        elif kind:
            open_other.append((s, i))
        else:
            open_other.remove((s, i))

    def owner():
        if open_gc[0]:
            return GC
        driving = [max(v) for t, v in open_prog.items() if v and t in drive]
        if driving:
            return host_events[max(driving)[1]][0]
        late = [max(v) for t, v in open_prog.items() if v]
        return host_events[max(late)[1]][0] if late else None

    def book(a, b):
        if b <= a:
            return
        name = owner()
        out[bucket(name)] += b - a
        if name is None:
            under = host_events[max(open_other)[1]][0] if open_other \
                else NO_SPAN
            dark_under[under] = dark_under.get(under, 0.0) + b - a
        else:
            by_span[name] = by_span.get(name, 0.0) + b - a

    k = 0
    for gs, ge in idle:
        while k < len(marks) and marks[k][0] <= gs:
            apply(marks[k][1], marks[k][2])
            k += 1
        cur = gs
        while k < len(marks) and marks[k][0] < ge:
            book(cur, marks[k][0])
            cur = marks[k][0]
            apply(marks[k][1], marks[k][2])
            k += 1
        book(cur, ge)
    split = {b + "_s": out[b] for b in BUCKETS}
    split["idle_s"] = tr.length(idle)
    last = max((host_events[i][1] + host_events[i][2] for _, _, i in marks
                if is_program(host_events[i][0])), default=hi)
    return {"idle_split": split, "idle_by_innermost_span": by_span,
            "idle_dark_under": dark_under,
            "idle_dark_tail_s": tr.length(tr.clip(idle, last, hi))}


def split_events(device_events, host_events, window, top: int = 10,
                 runs=None):
    """`trace_reduce.reduce_events` of the events, with the new keys
    beside its own: ``clock_offset_s`` (None where nothing bounds it),
    ``clock_offset_bounds`` (the completions or fetches that bounded
    it), ``clock_offset_slack_s``, ``clock_offset_source``,
    ``idle_split``, ``idle_by_innermost_span``, ``idle_dark_under``,
    ``idle_dark_tail_s``, ``span_table``, ``traced_steps``.  ``runs``:
    `_read`'s program runs, for the runtime's bound of the offset."""
    out = tr.reduce_events(device_events,
                           [(n, s, d) for n, s, d, _, _ in host_events],
                           window, top)
    est = clock_offset(device_events, host_events, window, runs)
    offset, bounds, slack, source = est if est else (None, 0, None, None)
    out.update(clock_offset_s=offset, clock_offset_bounds=bounds,
               clock_offset_slack_s=slack, clock_offset_source=source,
               span_table=span_table(host_events, window),
               traced_steps=traced_steps(host_events, window))
    out.update(innermost_split(device_events, host_events, window,
                               offset or 0.0))
    return out


def split_xplane(path: str, window_span: str = "bench_window",
                 top: int = 10, whole: bool = False) -> dict:
    """`split_events` of a file, on the window `trace_reduce.reduce_xplane`
    takes: the host span ``window_span``, else (or with ``whole``) first
    device operation to last."""
    device_events, host_events, runs = _read(path)
    spans = [(s, s + d) for n, s, d, _, _ in host_events
             if n == window_span]
    if spans and not whole:
        window = spans[0]
    else:
        starts = [s for ev in device_events.values() for _, s, _ in ev]
        ends = [s + d for ev in device_events.values() for _, s, d in ev]
        if not starts:
            raise ValueError(f"{path}: no window span {window_span!r} and "
                             f"no device operation")
        window = (min(starts), max(ends))
    host = [h for h in host_events if h[0] != window_span]
    return split_events(device_events, host, window, top, runs)


# ------------------------------------------------------------ the readings
def readings(split, counters, strict: bool = True) -> dict:
    """The serve loop's six readings, each None where its spans or
    counters are absent (a program without them; with ``strict`` false
    the split is read from the spans a trace has, as on a program that
    keeps only the older ones):

    * ``idle_dark_share.serve`` (%): dark idle over the window;
    * ``idle_empty_share.serve`` (%): idle under ``wait.*`` over it;
    * ``idle_host_ms.serve``: idle under the program's host work, a
      traced step;
    * ``idle_fetch_ms.serve``: idle under ``engine.fetch``, a traced step;
    * ``hop_ms.serve``: the two executor hand-offs of a step;
    * ``relay_ms.serve``: a token's wall from emission to SSE flush."""
    out = dict.fromkeys(("idle_dark_share.serve", "idle_empty_share.serve",
                         "idle_host_ms.serve", "idle_fetch_ms.serve",
                         "hop_ms.serve", "relay_ms.serve"))
    c = counters or {}
    if c.get("hops"):
        out["hop_ms.serve"] = 1e3 * c["hop_s"] / c["hops"]
    if c.get("relayed_tokens"):
        out["relay_ms.serve"] = 1e3 * c["relay_s"] / c["relayed_tokens"]
    if not split or split.get("window_s", 0) <= 0 \
            or split.get("fullest_busy_s", 0) <= 0:
        return out
    if strict and NEW_SPAN not in (split.get("span_table") or {}):
        return out  # a program without this split's spans
    s, w = split["idle_split"], split["window_s"]
    out["idle_dark_share.serve"] = 100.0 * s["dark_s"] / w
    out["idle_empty_share.serve"] = 100.0 * s["empty_s"] / w
    steps = split.get("traced_steps") or 0
    if steps:
        out["idle_host_ms.serve"] = 1e3 * s["host_s"] / steps
        out["idle_fetch_ms.serve"] = 1e3 * s["fetch_s"] / steps
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--counters", default=None,
                    help="a JSON dump of profiler.decode_stats()")
    ap.add_argument("--top", type=int, default=12)
    ap.add_argument("--any", action="store_true",
                    help="read the split from a trace of a program "
                         "without its spans too")
    args = ap.parse_args(argv)
    split = split_xplane(args.trace)
    counters = None
    if args.counters:
        with open(args.counters) as f:
            counters = json.load(f)
    table = sorted(split["span_table"].items(),
                   key=lambda kv: -kv[1]["total_s"])[:args.top]
    print(json.dumps({
        "window_s": split["window_s"],
        "idle_share": tr.idle_share_percent(split),
        "clock_offset_ms": None if split["clock_offset_s"] is None
        else 1e3 * split["clock_offset_s"],
        "clock_offset_bounds": split["clock_offset_bounds"],
        "clock_offset_source": split["clock_offset_source"],
        "clock_offset_slack_ms": None if split["clock_offset_slack_s"]
        is None else 1e3 * split["clock_offset_slack_s"],
        "traced_steps": split["traced_steps"],
        "idle_split": split["idle_split"],
        "idle_by_innermost_span": dict(sorted(
            split["idle_by_innermost_span"].items(), key=lambda kv: -kv[1])),
        "idle_dark_tail_s": split["idle_dark_tail_s"],
        "idle_dark_under": dict(sorted(
            split["idle_dark_under"].items(), key=lambda kv: -kv[1])[:8]),
        "span_table": dict(table),
        "readings": readings(split, counters, strict=not args.any),
        "idle_gaps": split["idle_gaps"],
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
