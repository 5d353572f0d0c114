"""Profiling plane: measured device-time attribution, HLO hot-op
breakdown, and bounded capture sessions.

The cost observatory (observability.costmodel) PREDICTS step cost and
reports *roofline* MFU from static FLOP/byte profiles; the flight
recorder (observability.flight) times *host* phases.  Neither measures
actual device time — predicted-vs-measured MFU drift, host-dispatch
overhead, and per-HLO-op hot spots were all invisible.  This module is
the measurement half of that observatory:

* **Sampled device-sync probes** — every
  ``FLAGS_profile_sample_steps``-th step (and every step during an
  armed capture) the engine BLOCKS on each dispatched executable's
  output (`Profiler.probe`, called inside the flight recorder's device
  phase so the phase wall absorbs the wait): the blocked wall is the
  executable's measured device seconds, and the step wall minus the
  device total is the host overhead.  Probes feed
  ``paddle_executable_device_seconds{fn}``,
  ``paddle_host_overhead_ratio{engine}``, and MEASURED
  ``paddle_phase_mfu_measured{phase}`` beside the cost model's
  roofline ``paddle_phase_mfu{phase}``.  Each probe is also scored
  against an INDEPENDENT device-time prediction — the executable's
  raw roofline seconds times a per-kind factor learned from earlier
  probes (the costmodel EWMA scheme at device granularity,
  compile-bearing steps excluded) — and the prediction-error EWMA is
  ``paddle_mfu_drift{phase}``, the signal the ``mfu_regression``
  alert rule (observability.alerts) debounces: a stale profile or a
  device-level slowdown moves it, a quiet steady state does not.
  Blocking changes no numerics and compiles nothing: probe-on serving
  is bit-exact with probe-off.

* **HLO hot-op attribution** — `hot_op_table` walks the SAME traced
  computation the cost observatory already lowers at the `_JitTracker`
  chokepoint (``fn.trace(*args)`` — tracing only, no second compile,
  no new executable) and aggregates per-primitive FLOP/byte estimates
  into a top-K table stored on each executable's `CostProfile`
  (``hot_ops``).  This is the table the vision/fusion work consumes:
  you cannot pick what to fuse or re-lay-out until you can rank the
  operators a step actually spends on.  Loop bodies (scan/while) are
  counted once per trace — the table ranks operators, it does not
  integrate trip counts.

* **Bounded capture sessions** — `request_capture(steps=N)` (any
  thread) arms a capture at the next step boundary ON the engine
  thread: for the next N served steps every dispatch is probed and its
  span lands on a ``device`` track in the merged chrome trace
  (observability.tracing), and — when ``FLAGS_profile_dir`` is set —
  the window is additionally wrapped in
  ``jax.profiler.start_trace/stop_trace`` so the XLA-level timeline
  lands beside the probe spans.  Captures are bounded by construction:
  the session disarms itself after N steps, so a forgotten capture can
  never trace forever.

* The read-only ``/profilez`` ops endpoint (observability.opsserver)
  serves `Profiler.statusz` — capture status, the per-executable
  device-time table, and the hot-op top-K — and
  ``tools/telemetry_dump.py`` pulls it into ``telemetry_profile.json``.

Arming: ``FLAGS_profile`` (default OFF) or the engine's ``profile=``
argument.  Disarmed, every serve-loop hook is one ``is None`` check,
zero probes run, zero new executables exist, and serving is bit-exact
with the pre-profiling engine.  The probe/sample config rides
`DecodeEngine.wire_config`, so recover/restore rebuild an armed
engine with the same cadence.

Threading: the open-step probe dict (``_probe`` / ``_probe_now``) is
engine-thread-private like the flight recorder's open record and
deliberately lock-free; everything CROSS-THREAD — the capture state
`/profilez` and `request_capture` touch, the device-time table, the
measured-MFU/drift tables — mutates under the module's designated
``_lock`` (tracecheck's lock-discipline pass enforces this).  Metric
updates happen outside the lock.

The profiler READS engine state and never mutates it — the
engine-mutation pass sanctions exactly `Profiler`'s read sites (the
capture-arming site runs on the engine thread between steps), and a
rogue profiler that mutates the engine ("just preempt the slot whose
dispatch keeps blocking longest") is a known-bad fixture in
tests/test_analysis.py.
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Dict, Optional

from .metrics import _state
from ..analysis.sanitizer import TrackedLock as _TrackedLock

__all__ = ["Profiler", "enabled", "hot_op_table", "HOT_OP_TOP_K",
           "request_capture", "capture_status", "profiler_for",
           "deregister"]

# THE profiling-plane lock: capture state, the per-executable
# device-time table, and the measured-MFU/drift tables mutate under it
# (/profilez and request_capture touch them from arbitrary threads).
# RLock so statusz helpers can nest; TrackedLock so FLAGS_sanitize
# records acquisition order.
_lock = _TrackedLock(threading.RLock(), "profiling._lock")

# engine_id -> weakref(Profiler): the module registry request_capture /
# capture_status resolve through (the opsserver pattern — a dropped
# engine leaves with its weakref, retirement deregisters explicitly)
_PROFILERS: Dict[int, "weakref.ref"] = {}

# top-K rows kept per executable's hot-op table
HOT_OP_TOP_K = 8

# EWMA smoothing for the per-kind device-time calibration and drift
# (the costmodel scheme at device granularity)
_EWMA_ALPHA = 0.25

# the executable kinds probes attribute device time to (the cost
# observatory's profile_for vocabulary).  Probes key by the DISPATCHED
# executable, never the flight phase: a chunkless full mixed step runs
# the mixed executable under the "decode" phase, and scoring it
# against the decode profile would whipsaw the calibration
PROBE_KINDS = ("decode", "mixed", "verify", "ragged")

_obs_mod = None


def _obs():
    # lazy catalog resolution (the flight-recorder pattern): this
    # module never participates in the package import cycle
    global _obs_mod
    if _obs_mod is None:
        from paddle_tpu import observability

        _obs_mod = observability
    return _obs_mod


def _stats_add(**kw):
    from ..inference.serving import _stats_add as add

    add(**kw)


# engines explicitly constructed with profile=True while the flag is
# OFF: hot-op extraction at the costmodel chokepoint must serve them
# too (the flag doc promises the explicit argument wins), so `enabled`
# reads flag OR this count — the costmodel._forced_engines pattern.
_forced_engines = 0


def _force_enable():
    global _forced_engines
    with _lock:
        _forced_engines += 1


def enabled() -> bool:
    """Is the profiling plane armed anywhere in the process?  True
    when FLAGS_profile is on (read from the registry directly so a
    set_flags flip is observed immediately) OR any engine was
    explicitly constructed with ``profile=True`` — hot-op extraction
    follows the union because the profile table is process-global."""
    if _forced_engines:
        return True
    from ..core import flags as _flags

    try:
        return bool(_flags.flag("profile"))
    except KeyError:  # pragma: no cover - registry not seeded (tests)
        return False


# ---------------------------------------------------------------------------
# HLO hot-op attribution (the costmodel lowering chokepoint's second
# product: same traced computation, per-op instead of aggregate)
# ---------------------------------------------------------------------------
def _aval_size(v):
    """(elements, bytes) of one jaxpr var's aval, 0 for non-arrays."""
    import numpy as np

    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0, 0
    n = 1
    for d in shape:
        n *= int(d)
    return n, n * np.dtype(dtype).itemsize


def _eqn_cost(eqn):
    """(flops, bytes) estimate for ONE jaxpr equation: dot/conv get
    their real MAC counts from the dimension numbers, everything else
    is unit-cost per output element; bytes = operand + result aval
    bytes (the streaming cost of the op in isolation — fusion makes
    the absolute number an upper bound, the RANKING is what the table
    is for)."""
    out_elems = out_bytes = 0
    for v in eqn.outvars:
        n, b = _aval_size(v)
        out_elems += n
        out_bytes += b
    in_bytes = sum(_aval_size(v)[1] for v in eqn.invars)
    name = eqn.primitive.name
    flops = float(out_elems)
    try:
        if name == "dot_general":
            (lc, _rc), _batch = eqn.params["dimension_numbers"]
            lhs_shape = eqn.invars[0].aval.shape
            contract = 1
            for d in lc:
                contract *= int(lhs_shape[d])
            flops = 2.0 * out_elems * contract
        elif name == "conv_general_dilated":
            rhs = eqn.invars[1].aval.shape
            k = 1
            for d in rhs:
                k *= int(d)
            # the kernel holds out_ch x in_ch/groups x spatial
            # elements (grouping is already folded into its in-channel
            # dim), so MACs per output element = k / out_ch — find
            # out_ch through the dimension numbers' rhs_spec, never a
            # positional guess (NHWC puts a spatial dim at shape[1])
            dn = eqn.params.get("dimension_numbers")
            rhs_spec = getattr(dn, "rhs_spec", None)
            out_ch = int(rhs[rhs_spec[0]]) if rhs_spec else 1
            flops = 2.0 * out_elems * (k / max(out_ch, 1))
    except Exception:  # pragma: no cover - exotic dim numbers
        pass
    return flops, float(in_bytes + out_bytes)


def _sub_jaxprs(params):
    for v in params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(
                    getattr(x, "jaxpr"), "eqns"):
                yield x.jaxpr


# short dtype names for the per-op table keys — the same spelling the
# partition plane's byte table uses (f32/bf16/s8/...), so a row reads
# `dot_general[f32xs8]` rather than the numpy long form
_SHORT_DTYPE = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int8": "s8", "int16": "s16", "int32": "s32",
    "int64": "s64", "uint8": "u8", "uint16": "u16", "uint32": "u32",
    "uint64": "u64", "bool": "pred",
}


def _short_dtype(dtype) -> str:
    return _SHORT_DTYPE.get(str(dtype), str(dtype))


def _op_key(eqn) -> str:
    """Aggregation key for one eqn.  `dot_general` rows key by operand
    dtypes (``dot_general[f32xs8]``): a serve_weights=int8 engine runs
    mixed f32×s8 weight dots NEXT TO f32×f32 activation math, and
    aggregating them into one row would blind the exact before/after
    instrument the weight-quant bench reads."""
    name = eqn.primitive.name
    if name == "dot_general":
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        return (f"{name}[{_short_dtype(lhs.dtype)}"
                f"x{_short_dtype(rhs.dtype)}]")
    return name


def _walk_jaxpr(jaxpr, agg):
    for eqn in jaxpr.eqns:
        subs = list(_sub_jaxprs(eqn.params))
        if subs:
            # structural eqn (pjit / scan / while / cond / custom_*):
            # recurse into the bodies, count the wrapper itself as free
            for sub in subs:
                _walk_jaxpr(sub, agg)
            continue
        f, b = _eqn_cost(eqn)
        row = agg.setdefault(_op_key(eqn), [0.0, 0.0, 0])
        row[0] += f
        row[1] += b
        row[2] += 1


def hot_op_table(fn, args, top_k: int = HOT_OP_TOP_K) -> tuple:
    """Top-``top_k`` per-op FLOP/byte rows for one jitted executable,
    traced against ``args`` — tracing only (``fn.trace``), never a
    compile, never a new executable.  Rows are sorted by FLOPs then
    bytes, each carrying its fraction of the executable's totals, so
    the fusion/layout work reads 'where this program's work lives'
    straight off the table."""
    closed = fn.trace(*args).jaxpr
    agg: Dict[str, list] = {}
    _walk_jaxpr(closed.jaxpr, agg)
    total_f = sum(r[0] for r in agg.values()) or 1.0
    total_b = sum(r[1] for r in agg.values()) or 1.0
    rows = sorted(agg.items(), key=lambda kv: (-kv[1][0], -kv[1][1],
                                               kv[0]))
    return tuple(
        {"op": name, "count": int(c), "flops": f, "bytes": b,
         "flops_frac": round(f / total_f, 6),
         "bytes_frac": round(b / total_b, 6)}
        for name, (f, b, c) in rows[:int(top_k)])


# ---------------------------------------------------------------------------
# the per-engine profiler
# ---------------------------------------------------------------------------
class Profiler:
    """One engine's profiling plane: probe cadence, capture sessions,
    and the measured device-time / MFU-drift tables.  Constructed by
    `DecodeEngine.__init__` when armed; reads the engine, never
    mutates it."""

    def __init__(self, engine, sample_steps: Optional[int] = None):
        from ..core import flags as _flags

        self.engine = engine
        if sample_steps is None:
            sample_steps = int(_flags.flag("profile_sample_steps"))
        # <= 1 probes every step (the bench attribution mode)
        self.sample_steps = max(int(sample_steps), 1)
        # engine-thread-private open-step state (the flight recorder's
        # open-record pattern: nobody else ever reads these, which is
        # what keeps the unprobed-step cost at one `is None` + one
        # modulo) — deliberately outside the lock discipline
        self._steps = 0
        self._probe_now = False
        self._probe: Optional[Dict[str, float]] = None
        self._probe_skew: Optional[float] = None
        self.probes = 0
        self.probe_seconds = 0.0  # accounted blocking cost (bench)
        # cross-thread state (under profiling._lock): capture session
        # + the tables /profilez renders
        with _lock:
            self._capture_pending = 0
            self._capture_remaining = 0
            self._capture_total = 0
            self._captures = 0
            self._device_s: Dict[str, dict] = {}
            # per-chip completion skew of probed SHARDED steps
            # (FLAGS_serve_mesh); None until the first sharded probe
            self._skew: Optional[dict] = None
            self._host_ratio: Optional[float] = None
            self._mfu: Dict[str, float] = {}
            # per-kind device-time calibration (EWMA of measured /
            # raw-roofline seconds, log space — the costmodel scheme)
            # and the drift it scores: EWMA of |predicted - measured|
            # / measured device seconds, predictions made only from an
            # already-learned factor
            self._dev_calib: Dict[str, float] = {}
            self._drift: Dict[str, float] = {}
            _PROFILERS[int(engine._engine_id)] = weakref.ref(self)
        self._jax_trace = False
        self._trace_path: Optional[str] = None
        # compile detector (the watchdog/costmodel tracker-sig trick):
        # a probe on a compile-bearing step measures XLA, not the
        # executable — it must never poison the device calibration
        self._pending_sig = None

    # -- capture sessions (any thread arms, engine thread consumes) ----------
    def request_capture(self, steps: int) -> dict:
        """Arm a bounded capture: the next ``steps`` SERVED steps are
        all probed, probe spans land on the ``device`` chrome-trace
        track, and — with ``FLAGS_profile_dir`` set — the window is
        wrapped in a jax profiler trace.  Callable from any thread;
        the engine thread arms it at its next step boundary.  Repeated
        requests extend to the larger remaining count (captures never
        stack unboundedly)."""
        steps = int(steps)
        if steps < 1:
            raise ValueError(
                f"capture needs steps >= 1, got {steps}")
        with _lock:
            self._capture_pending = max(self._capture_pending, steps)
        return self.capture_status()

    def capture_status(self) -> dict:
        with _lock:
            return {
                "pending_steps": int(self._capture_pending),
                "remaining_steps": int(self._capture_remaining),
                "capturing": bool(self._capture_remaining > 0),
                "captured_steps": int(self._capture_total),
                "captures_completed": int(self._captures),
                "jax_trace": bool(self._jax_trace),
                "trace_path": self._trace_path,
            }

    def _start_jax_trace(self):
        if self._jax_trace:
            # a capture EXTENDED while one is running must not call
            # start_trace again: the raise would clobber the flag and
            # leave the running trace unstoppable forever
            return
        from ..core import flags as _flags

        d = str(_flags.flag("profile_dir"))
        if not d:
            return
        try:
            import jax

            path = os.path.join(
                d, f"eng{self.engine._engine_id}"
                   f"_capture{self._captures}")
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
            self._jax_trace = True
            with _lock:
                self._trace_path = path
        except Exception:  # pragma: no cover - backend w/o profiler
            self._jax_trace = False

    def _stop_jax_trace(self):
        if not self._jax_trace:
            return
        self._jax_trace = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception:  # pragma: no cover - stop after backend loss
            pass

    # -- engine-thread hooks (DecodeEngine.step) -----------------------------
    @property
    def probing(self) -> bool:
        """Does the current step probe (`note_step_begin` decided)?  A
        probing step's dispatch blocks on its output, so the engine
        lands the step in flight before it."""
        return self._probe_now

    def note_step_begin(self):
        """Between-steps hook, engine thread, BEFORE admission: arm a
        pending capture and decide whether this step probes.  The
        unarmed-capture cost is one plain read + one modulo."""
        self._steps += 1
        if self._capture_pending:  # plain read; arming takes the lock
            with _lock:
                pend, self._capture_pending = self._capture_pending, 0
                self._capture_remaining = max(
                    self._capture_remaining, pend)
            self._start_jax_trace()
        capturing = self._capture_remaining > 0
        self._probe_now = capturing or \
            (self._steps % self.sample_steps == 0)
        self._probe = {} if self._probe_now else None
        self._probe_skew = None
        self._pending_sig = self._tracker_sig() if self._probe_now \
            else None

    def _tracker_sig(self):
        """Compile signature over the engine's live trackers (the
        watchdog's scheme): any change across a step means an
        executable compiled during it — that probe's blocked wall
        includes XLA compile time and must not calibrate."""
        ts = self.engine._trackers()
        return (len(ts), sum(t._seen for t in ts))

    def probe(self, kind: str, arrays, t0: float, t0_ns: int):
        """Dispatch-site hook, called INSIDE the flight recorder's
        device phase right after the dispatch returns: block until the
        executable's output is ready — one output suffices, a
        computation's results materialize together — and attribute
        dispatch-start -> ready as the executable's device seconds.
        ``kind`` names the DISPATCHED executable ("decode" | "mixed" |
        "verify" — the profile_for vocabulary), which is not always
        the surrounding flight phase: a chunkless full mixed step
        dispatches the mixed executable under the "decode" phase.
        During a capture the span additionally lands on the
        ``device`` trace track."""
        if not self._probe_now:
            return
        import jax

        p0 = time.perf_counter()
        skew = self._block_and_skew(arrays)
        now = time.perf_counter()
        dev = now - t0
        if skew is not None:
            self._probe_skew = max(self._probe_skew or 0.0, skew)
        self.probe_seconds += now - p0
        self._probe[kind] = self._probe.get(kind, 0.0) + dev
        if self._capture_remaining > 0 and _state["enabled"]:
            _obs().record_span(
                "device", kind, t0_ns, int(dev * 1e9),
                tid=self.engine._engine_id,
                args={"step": int(self.engine._step_no),
                      "device_ms": round(dev * 1e3, 4)})

    def _block_and_skew(self, arrays) -> Optional[float]:
        """Block until the probed outputs are ready.  On a single-chip
        engine this is one `block_until_ready`.  When an output is
        laid out across a mesh (FLAGS_serve_mesh) the per-device
        sync happens shard by shard, completion-stamped in order —
        max-minus-min is the step's observed chip skew (a lower
        bound: shards that finish while an earlier one is blocking
        stamp at the moment they are OBSERVED ready, not the moment
        they finished).  Returns None on unsharded outputs."""
        import jax

        lead = None
        for x in jax.tree_util.tree_leaves(arrays):
            sh = getattr(x, "sharding", None)
            try:
                if sh is not None and len(sh.device_set) > 1:
                    lead = x
                    break
            except Exception:
                continue
        if lead is None:
            jax.block_until_ready(arrays)
            return None
        times = []
        try:
            for s in lead.addressable_shards:
                jax.block_until_ready(s.data)
                times.append(time.perf_counter())
        except Exception:  # pragma: no cover - exotic layouts
            times = []
        jax.block_until_ready(arrays)
        if len(times) > 1:
            return max(times) - min(times)
        return None

    def note_step_end(self, fr):
        """Engine thread, after the step's dispatches and before the
        flight record seals: stamp the probe onto the open record,
        retire one captured step, and refresh the device-time table.
        ``fr`` may be None (recorder off) — the table and gauges still
        update."""
        probe, self._probe = self._probe, None
        skew, self._probe_skew = self._probe_skew, None
        probed, self._probe_now = self._probe_now, False
        if self._capture_remaining > 0:
            with _lock:
                self._capture_remaining -= 1
                self._capture_total += 1
                done = self._capture_remaining == 0
                if done:
                    self._captures += 1
            if done:
                self._stop_jax_trace()
                _stats_add(profile_captures=1)
        if not probed or not probe:
            return
        self.probes += 1
        _stats_add(profile_probes=1)
        with _lock:
            for k, v in probe.items():
                e = self._device_s.setdefault(
                    k, {"last_s": 0.0, "total_s": 0.0, "probes": 0})
                e["last_s"] = v
                e["total_s"] += v
                e["probes"] += 1
            if skew is not None:
                if self._skew is None:
                    self._skew = {"last_s": 0.0, "max_s": 0.0,
                                  "total_s": 0.0, "probes": 0}
                self._skew["last_s"] = skew
                self._skew["max_s"] = max(self._skew["max_s"], skew)
                self._skew["total_s"] += skew
                self._skew["probes"] += 1
        if fr is not None:
            pr = {"device": {k: round(v, 9) for k, v in probe.items()}}
            if skew is not None:
                pr["chip_skew_s"] = round(skew, 9)
            fr.note_probe(pr)
        if _state["enabled"] and not self.engine._abandoned:
            obs = _obs()
            for k, v in probe.items():
                obs.EXEC_DEVICE_SECONDS.set(v, fn=k)
            if skew is not None:
                obs.CHIP_SKEW.set(skew, engine=self.engine._engine_id)

    def observe(self, rec: dict) -> None:
        """Score the sealed flight record's probe against its wall:
        host-overhead ratio, measured per-executable MFU, and the
        predicted-vs-measured device-time drift the
        ``mfu_regression`` rule watches.  The prediction is
        INDEPENDENT of the measurement — the cost observatory's raw
        roofline seconds for the executable times a per-kind factor
        learned from EARLIER probes (the costmodel EWMA scheme at
        device granularity) — so a stale profile or a device-level
        slowdown moves the drift, where comparing two timers of the
        same dispatch would cancel to zero.  Compile-bearing steps
        never calibrate (the tracker-sig trick).  Engine thread;
        mutates only this profiler's tables (under the module lock —
        statusz renders them from other threads)."""
        import math

        pr = rec.get("probe") if rec.get("kind") == "step" else None
        pending, self._pending_sig = self._pending_sig, None
        if pr is None:
            return
        wall = float(rec.get("dur_s", 0.0))
        dev = float(pr.get("device_s", 0.0))
        if wall <= 0.0 or dev <= 0.0:
            return
        ratio = max(wall - dev, 0.0) / wall
        eng = self.engine
        cost = eng._cost
        # an executable compiled during this step: its blocked wall is
        # XLA compile time — gauges may render, calibration must not
        # learn from it
        calibrate = pending is not None and \
            pending == self._tracker_sig()
        mfus: Dict[str, float] = {}
        samples = []  # (kind, raw roofline s, measured device s)
        if cost is not None:
            for kind, dv in pr.get("device", {}).items():
                if kind not in PROBE_KINDS or dv <= 0.0:
                    continue
                prof = cost.profile_for(kind)
                mfus[kind] = prof.flops / dv / cost.peaks["flops"]
                raw = cost.raw_seconds(prof)
                if calibrate and raw > 0.0:
                    samples.append((kind, raw, dv))
        drifts: Dict[str, float] = {}
        with _lock:
            self._host_ratio = ratio
            self._mfu.update(mfus)
            for kind, raw, dv in samples:
                sample = dv / raw
                prev = self._dev_calib.get(kind)
                if prev is None:
                    # first clean sample sets the factor outright; the
                    # drift scores only predictions made from an
                    # already-learned factor (cold start is not drift)
                    self._dev_calib[kind] = sample
                    continue
                err = abs(raw * prev - dv) / dv
                # EWMA in LOG space (geometric mean): stall outliers
                # nudge the factor, never yank it
                self._dev_calib[kind] = prev * math.exp(
                    _EWMA_ALPHA * math.log(max(sample, 1e-12) / prev))
                prev_e = self._drift.get(kind)
                self._drift[kind] = err if prev_e is None else \
                    prev_e + _EWMA_ALPHA * (err - prev_e)
            drifts = dict(self._drift)
        if not _state["enabled"] or eng._abandoned:
            return
        obs = _obs()
        obs.HOST_OVERHEAD_RATIO.set(ratio, engine=eng._engine_id)
        for p, v in mfus.items():
            obs.PHASE_MFU_MEASURED.set(v, phase=p)
        for p, v in drifts.items():
            obs.MFU_DRIFT.set(v, phase=p)

    # -- any-thread readers --------------------------------------------------
    def drift_table(self) -> Dict[str, float]:
        """Copy of the per-kind predicted-vs-measured device-time drift — the
        ``mfu_regression`` alert signal reads THIS engine's own table,
        never the phase-only global gauge."""
        with _lock:
            return dict(self._drift)

    def device_table(self) -> Dict[str, dict]:
        with _lock:
            out = {}
            for k, e in self._device_s.items():
                out[k] = {
                    "last_s": e["last_s"],
                    "mean_s": e["total_s"] / max(e["probes"], 1),
                    "probes": e["probes"],
                }
            return out

    def statusz(self) -> dict:
        """The `/profilez` payload (and `DecodeEngine.statusz`'s
        profiling section): probe config/accounting, capture status,
        the per-executable device-time table, measured MFU + drift,
        and the hot-op top-K per profiled executable.  Read-only and
        thread-safe."""
        with _lock:
            host_ratio = self._host_ratio
            mfu = dict(self._mfu)
            drift = dict(self._drift)
            dev_calib = dict(self._dev_calib)
            skew = None
            if self._skew is not None:
                skew = {
                    "last_s": self._skew["last_s"],
                    "max_s": self._skew["max_s"],
                    "mean_s": self._skew["total_s"]
                    / max(self._skew["probes"], 1),
                    "probes": self._skew["probes"],
                }
        hot = {}
        try:
            from . import costmodel

            # THIS engine's executables only, resolved by exact
            # signature through its trackers' cost_sig keys — the
            # site-keyed costmodel.profiles() view is last-writer-wins
            # across the whole process, so another engine at different
            # shapes sharing a site label would shadow this one's
            # tables there
            for t in self.engine._trackers():
                key = getattr(t, "cost_sig", None)
                if key is None:
                    continue
                prof = costmodel.profile_by_key(key)
                if prof is not None and prof.hot_ops:
                    hot[t.site] = [dict(r) for r in prof.hot_ops]
        except Exception:  # pragma: no cover - costmodel unavailable
            pass
        return {
            "engine": self.engine._engine_id,
            "sample_steps": self.sample_steps,
            "steps": int(self._steps),
            "probes": int(self.probes),
            "probe_seconds": round(self.probe_seconds, 9),
            "capture": self.capture_status(),
            "device_seconds": self.device_table(),
            "chip_skew_seconds": skew,
            "host_overhead_ratio": host_ratio,
            "mfu_measured": mfu,
            "device_calibration": dev_calib,
            "mfu_drift": drift,
            "hot_ops": hot,
        }


# ---------------------------------------------------------------------------
# the module registry (request_capture / /profilez resolve engines here)
# ---------------------------------------------------------------------------
def profiler_for(engine=None) -> Profiler:
    """Resolve a live `Profiler`: by engine (object or id), or the
    single armed engine in the process; raises when none or several
    qualify (name one)."""
    want = None
    if engine is not None:
        want = int(getattr(engine, "_engine_id", engine))
    with _lock:
        items = sorted(_PROFILERS.items())
    live = []
    for eid, ref in items:
        p = ref()
        if p is None:
            continue
        if want is not None and eid == want:
            return p
        live.append((eid, p))
    if want is not None:
        raise ValueError(
            f"no armed profiler for engine {want} "
            f"(have {[e for e, _ in live]})")
    if len(live) == 1:
        return live[0][1]
    raise ValueError(
        f"need an explicit engine: {len(live)} armed profilers "
        f"({[e for e, _ in live]})")


def request_capture(steps: int, engine=None) -> dict:
    """Module-level capture entry: arm a bounded capture session on
    the (single, or named) armed engine's profiler.  Returns the
    capture status dict."""
    if int(steps) < 1:
        # validate BEFORE resolving: a bad steps argument must not
        # report "which engine?" on a multi-engine process
        raise ValueError(f"capture needs steps >= 1, got {steps}")
    return profiler_for(engine).request_capture(steps)


def capture_status(engine=None) -> dict:
    return profiler_for(engine).capture_status()


def deregister(engine_id: int):
    """`durability.retire_engine_series` chokepoint: a retired
    engine's profiler leaves the capture registry with its gauges,
    and an in-flight capture's jax trace is STOPPED — the engine
    thread that would have disarmed it is dead or stuck, and a leaked
    process-global trace would both record forever and make every
    successor capture's start_trace fail."""
    with _lock:
        ref = _PROFILERS.pop(int(engine_id), None)
    p = ref() if ref is not None else None
    if p is not None:
        p._stop_jax_trace()
