"""From a profiler trace (`.xplane.pb`, read with `jax.profiler.ProfileData`)
to the numbers the per-layer metrics read: device busy and idle time, device
time by operation name, collective time not hidden behind compute, and the
longest idle gaps with what the host was doing over them.

The arithmetic (`reduce_events`) works on plain lists, so a hand-made trace
tests it; `read_xplane` is the only part that knows the file format.

Times are seconds.  An event is ``(name, start_s, duration_s)``.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|send|recv)")
UNATTRIBUTED = "engine loop / unattributed host"
MIN_GAP_S = 20e-6  # shorter holes between two operations are launch slack


# ------------------------------------------------------------- intervals
def union(intervals):
    """Merged, sorted, disjoint intervals of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, holes):
    """The parts of the (disjoint, sorted) ``intervals`` outside the
    (disjoint, sorted) ``holes``."""
    out = []
    for s, e in intervals:
        cur = s
        for hs, he in holes:
            if he <= cur:
                continue
            if hs >= e:
                break
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    """The idle intervals of a window given its merged busy intervals."""
    return subtract([(lo, hi)], busy)


def stem(key: str) -> str:
    """An operation's key without its number: the twelve layers' copies of
    one fusion (``fusion.12``, ``fusion.13``) read ``fusion``."""
    return re.sub(r"\.\d+$", "", key)


def op_key(name: str) -> str:
    """An operation's name without its SSA decoration: ``%fusion.12 = ...``
    and ``fusion.12`` both read ``fusion.12``."""
    return name.split(" = ")[0].lstrip("%").strip()


# ----------------------------------------------------------- the reduction
def reduce_events(device_events: dict, host_spans: list, window: tuple,
                  top: int = 10) -> dict:
    """``device_events``: device id -> [(name, start, dur), ...] of leaf
    operations; ``host_spans``: [(name, start, dur), ...] on the same
    clock; ``window``: (start, end).  See the module docstring for what
    comes back; a window in which no device ran anything gives busy 0."""
    lo, hi = window
    window_s = hi - lo
    per_dev, op_s, op_n, op_text = {}, {}, {}, {}
    for dev, events in sorted(device_events.items()):
        busy = union(clip([(s, s + d) for _, s, d in events], lo, hi))
        comp = union(clip([(s, s + d) for n, s, d in events
                           if not COLLECTIVE.match(op_key(n))], lo, hi))
        coll = union(clip([(s, s + d) for n, s, d in events
                           if COLLECTIVE.match(op_key(n))], lo, hi))
        per_dev[dev] = {"busy_s": length(busy),
                        "collective_s": length(coll),
                        "collective_exposed_s": length(subtract(coll, comp)),
                        "busy": busy}
        for n, s, d in events:
            inside = length(clip([(s, s + d)], lo, hi))
            if inside > 0:
                k = op_key(n)
                op_s[k] = op_s.get(k, 0.0) + inside
                op_n[k] = op_n.get(k, 0) + 1
                op_text.setdefault(k, n[:800])
    n_dev = max(len(per_dev), 1)
    op_s = {k: v / n_dev for k, v in op_s.items()}
    fullest = max(per_dev, key=lambda d: per_dev[d]["busy_s"], default=None)

    # idle gaps of the fullest device, by what the host was doing
    idle = []
    if fullest is not None:
        for s, e in gaps(per_dev[fullest]["busy"], lo, hi):
            if e - s < MIN_GAP_S:
                continue
            what, most = UNATTRIBUTED, 0.0
            for n, hs, hd in host_spans:
                over = min(e, hs + hd) - max(s, hs)
                # the innermost span wins a tie: it says more
                if over > most or (over == most and over > 0):
                    what, most = n, over
            idle.append((what, e - s))
    by_stem = {}
    for k, v in op_s.items():
        by_stem[stem(k)] = by_stem.get(stem(k), 0.0) + v
    by_what = {}
    for what, sec in idle:
        by_what[what] = by_what.get(what, 0.0) + sec
    out = {
        "window_s": window_s,
        "n_devices": len(per_dev),
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n_dev,
        "fullest_busy_s": per_dev[fullest]["busy_s"] if per_dev else 0.0,
        "collective_s": max((d["collective_s"] for d in per_dev.values()),
                            default=0.0),
        "collective_exposed_s": max(
            (d["collective_exposed_s"] for d in per_dev.values()),
            default=0.0),
        "op_seconds": op_s,
        "op_counts": {k: v / n_dev for k, v in op_n.items()},
        "op_text": op_text,
        "device_ops": [[k, v] for k, v in sorted(
            by_stem.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[w, s] for w, s in sorted(
            idle, key=lambda ws: -ws[1])[:top]],
        "idle_by_host_span": by_what,
    }
    return out


def idle_share_percent(reduced):
    """The share of the window in which no operation ran on the fullest
    device, in percent; None where there is no trace or nothing ran."""
    if not reduced or reduced["window_s"] <= 0 \
            or reduced["fullest_busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["fullest_busy_s"] / reduced["window_s"])


# -------------------------------------------------------- the file format
def read_xplane(path: str, ops_line: str = OPS_LINE):
    """(device_events, host_spans) of an `.xplane.pb`, in seconds on the
    trace's own clock.  Host spans are the events of the host planes'
    lines that are not XLA's own bookkeeping."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_events, host_spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = device_events.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != ops_line:
                    continue
                for ev in line.events:
                    events.append((ev.name, ev.start_ns * 1e-9,
                                   ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0 and "::" not in ev.name:
                        host_spans.append((ev.name, ev.start_ns * 1e-9,
                                           ev.duration_ns * 1e-9))
    return device_events, host_spans


def reduce_xplane(path: str, window_span: str = "bench_window",
                  top: int = 10, whole: bool = False) -> dict:
    """`reduce_events` of a file.  The window is the host span named
    ``window_span``; without one in the file, or with ``whole``, first
    device operation to last."""
    device_events, host_spans = read_xplane(path)
    spans = [(s, s + d) for n, s, d in host_spans if n == window_span]
    if spans and not whole:
        window = spans[0]
    else:
        starts = [s for ev in device_events.values() for _, s, _ in ev]
        ends = [s + d for ev in device_events.values() for _, s, d in ev]
        if not starts:
            raise ValueError(f"{path}: no window span {window_span!r} and "
                             f"no device operation")
        window = (min(starts), max(ends))
    host = [h for h in host_spans if h[0] != window_span]
    return reduce_events(device_events, host, window, top)


def describe(path: str, per_line: int = 6) -> dict:
    """What is in a trace file, for a first look by hand: every plane,
    its lines, their event counts and a few events with their stats."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {"events": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "duration_ns": e.duration_ns,
                 "stats": {k: str(v)[:120] for k, v in e.stats}}
                for e in evs[:per_line]]}
        out[plane.name] = lines
    return out
