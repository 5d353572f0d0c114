"""Serving cost observatory: compile-time FLOP/byte profiles, calibrated
step-cost prediction, an HBM ledger, and roofline accounting.

The flight recorder (observability.flight) answers *what happened* per
step; this module answers *what a step will cost*, *where the device
bytes live*, and *how far from the hardware ceiling we run* — the
measurement substrate the fleet-router's cost-model admission, the
adaptive-speculation work, and the vision-MFU refactor all consume.
Four layers:

* **Static cost profiles** — every serving executable passes through
  the `_JitTracker` chokepoint (inference.serving); on its FIRST
  invocation the tracker calls `note_executable`, which lowers the
  SAME traced call (`jitted.lower(*args)`, never a new jit-cache
  entry) and reads XLA's HLO cost analysis: FLOPs and HBM bytes
  accessed.  The CPU backend analyses the lowering; the TPU backend
  analyses compiled programs only, so there the lowering is compiled
  first.  That is the executable's ONE compile, not a second: the jit
  call that follows finds the same lowering in jit's in-memory cache
  and runs the executable it already holds (backend compiles counted
  equal with the observatory on and off, tests/test_costmodel.py).
  Profiles are keyed by the executable's **call signature** — the
  per-argument ``(shape, dtype, weak_type)`` tuple scheme the eager
  dispatch cache (core.dispatch) keys executables by — and stored in
  the process-global `_PROFILES` table under the module lock.  Peak
  temp allocation is read from the compiled program
  (`lowered.compile().memory_analysis()`) behind
  ``FLAGS_cost_memory_analysis`` (default off).  Backends whose HLO cost
  analysis is unavailable fall back to `analytical_gpt_cost`, a
  closed-form GPT FLOP/byte formula parameterized by
  batch/Q/kv-len/dims.

* **Calibrated step-cost prediction** — `CostModel.predict_step_cost`
  turns a batch composition into seconds: the raw roofline time of the
  executables the step will run (``max(flops/peak_flops,
  bytes/peak_bw)``, summed) times a per-executable EWMA calibration
  factor learned online from the flight recorder's measured step
  times.  Predicted-vs-actual error is tracked per executable as
  ``paddle_step_cost_error_ratio{fn}`` so calibration drift is an
  alertable signal, and each flight record carries its
  ``predicted_s`` / ``actual_s`` pair (tools/explain_request.py
  renders the column).

* **HBM ledger** — `CostModel.hbm_ledger` attributes every live
  device byte to a category (weights, kv_pages, kv_scales,
  draft_pool, misc) by array identity and reconciles the sum against
  ``jax.live_arrays()``: bytes nothing claims surface as the
  ``paddle_hbm_ledger_unattributed_bytes`` gauge instead of drifting
  silently.  Executables' peak temp scratch (when the memory-analysis
  flag armed it) is reported as its own category — it is XLA-owned
  scratch, not a live array, so it sits beside the reconciliation
  rather than inside it.

* **Roofline accounting** — per-phase MFU and HBM-bandwidth
  utilization (``paddle_phase_mfu{phase}`` /
  ``paddle_phase_hbm_util{phase}``) computed each step from profile ÷
  measured phase time against the peak FLOP/s and bytes/s the flags
  pin (``FLAGS_peak_flops`` / ``FLAGS_peak_hbm_gbps``; 0 =
  autodetect from the device kind, with deliberately fixed CPU test
  values so CPU CI numbers are stable and meaningless-but-consistent).

Arming: ``FLAGS_cost_model`` (default on) or the engine's
``cost_model=`` argument.  Disarmed, the serving hot path pays one
``is None`` check per step and ZERO profiles are extracted — bit-exact
with the pre-observatory engine.  Calibration updates ride the flight
recorder's sealed records, so a recorder-off engine predicts from raw
(or restored) calibration but never updates it.

Threading: profile extraction and every calibration mutation happen on
the engine thread, but `DecodeEngine.statusz` (any thread) reads the
calibration and error tables — all shared state (`_PROFILES`,
`CostModel._calib` / `_err`) therefore mutates under the module's
designated ``_lock`` (tracecheck's lock-discipline pass enforces
this).  The per-step ``_pending`` prediction is engine-thread-private
like the flight recorder's open record and deliberately unlisted.

The cost model READS engine state and never mutates it — the
engine-mutation pass sanctions exactly `CostModel`'s read sites, and a
rogue cost model that mutates the engine (the tempting bug: "just
preempt the slot my prediction says is over budget") is a known-bad
fixture in tests/test_analysis.py.
"""
from __future__ import annotations

import math
import threading
from dataclasses import dataclass
from typing import Dict, Optional

from .metrics import _state
from ..analysis.sanitizer import TrackedLock as _TrackedLock

__all__ = ["CostProfile", "CostModel", "enabled", "note_executable",
           "profile_signature", "analytical_gpt_cost", "profiles",
           "profile_by_key", "clear_profiles", "resolve_peaks",
           "DEVICE_PEAKS", "device_peaks",
           "LEDGER_CATEGORIES"]

# THE cost-observatory lock: the process-global profile table and every
# CostModel's calibration/error tables mutate under it (statusz reads
# them from arbitrary threads).  RLock so statusz helpers can nest;
# TrackedLock so FLAGS_sanitize records acquisition order.
_lock = _TrackedLock(threading.RLock(), "costmodel._lock")

# signature -> CostProfile, shared across engines (two engines with
# byte-identical executables — a recovery handoff pair, say — share one
# profile, exactly as they share the compiled program)
_PROFILES: Dict[tuple, "CostProfile"] = {}

# HBM ledger category vocabulary (the paddle_hbm_ledger_bytes label
# set).  ``temp_scratch`` is XLA-owned executable scratch — reported,
# but outside the live-array reconciliation (see hbm_ledger).  Weight
# bytes itemize by STORAGE dtype: serve_weights=int8 engines carry
# their matmul payloads under ``weights_int8`` and the per-out-channel
# dequant scales under ``weight_scales``, so the bytes the fold
# reclaimed read straight off the ledger (f32 leaves — embeddings,
# norms, biases, and everything on an off-mode engine — stay under
# ``weights``).
LEDGER_CATEGORIES = ("weights", "weights_int8", "weight_scales",
                     "kv_pages", "kv_scales", "draft_pool",
                     "temp_scratch", "misc")

# steps between error/roofline gauge refreshes (see CostModel.observe)
_GAUGE_EVERY = 8

# EWMA smoothing for the calibration factor and the error gauge: heavy
# enough to converge within a flight window, light enough that a real
# regime change (quantization flipped on, page size retuned) re-learns
# in tens of steps
_EWMA_ALPHA = 0.25

# The ONE table of device peaks, keyed by ``jax.Device.device_kind``:
# the cost observatory, bench.py and chip_smoke.py all read it through
# `device_peaks`.  A TPU whose kind has no row here is an error — add the
# row with its source; never fall back to another device's numbers.
#
# "TPU v5 lite" is what a v5e chip reports.  Source: Google Cloud
# documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s (200 GB/s)
# of chip-to-chip interconnect.
#
# The "cpu" row is not a datasheet: CPU MFU numbers are meaningless as
# absolutes, and pinning them makes CPU CI gauges deterministic and
# comparable run over run (tests assert presence and sane ranges, never
# absolute truth).  It serves ``platform == "cpu"`` and nothing else.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9},
    "cpu": {"flops_bf16": 5.0e10, "ops_int8": 5.0e10,
            "hbm_bytes_per_s": 2.0e10, "ici_bytes_per_s": 1.0e10},
}
_CPU_PEAK_ICI = DEVICE_PEAKS["cpu"]["ici_bytes_per_s"]


def device_peaks(device=None) -> Dict[str, float]:
    """The `DEVICE_PEAKS` row of ``device`` (default: JAX's first)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform == "cpu":
        return DEVICE_PEAKS["cpu"]
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}): add a row with its source "
            f"to observability.costmodel.DEVICE_PEAKS") from None


# engines explicitly constructed with cost_model=True while the flag
# is OFF: profile extraction must serve them too (the flag doc
# promises the explicit argument wins), so `enabled` reads flag OR
# this count.  Never decremented — engines have no close(), and once
# any engine wanted profiles the table staying warm costs nothing.
_forced_engines = 0


def _force_enable():
    global _forced_engines
    with _lock:
        _forced_engines += 1


def enabled() -> bool:
    """Is profile extraction armed?  True when FLAGS_cost_model is on
    (read from the REGISTRY directly, the sanitizer.active pattern, so
    a set_flags flip is observed immediately) OR any engine was
    explicitly constructed with ``cost_model=True`` — the explicit
    argument wins in both directions for the engine's own
    predictor/ledger, and extraction follows the union because the
    profile table is process-global."""
    if _forced_engines:
        return True
    from ..core import flags as _flags

    try:
        return bool(_flags.flag("cost_model"))
    except KeyError:  # pragma: no cover - registry not seeded (tests)
        return False


def resolve_peaks() -> Dict[str, float]:
    """The roofline ceilings: ``FLAGS_peak_flops`` /
    ``FLAGS_peak_hbm_gbps`` / ``FLAGS_peak_ici_gbps`` where positive,
    else the default device's row of `DEVICE_PEAKS` (an unlisted TPU
    kind raises).  ``ici_bytes_per_s`` divides the collective-bytes
    term of sharded executables."""
    from ..core import flags as _flags

    flops = float(_flags.flag("peak_flops"))
    gbps = float(_flags.flag("peak_hbm_gbps"))
    ici = float(_flags.flag("peak_ici_gbps"))
    if flops > 0 and gbps > 0 and ici > 0:
        row, source = {}, "flags"  # nothing left to look up
    else:
        import jax

        dev = jax.devices()[0]
        row = device_peaks(dev)
        if flops > 0 and gbps > 0:
            source = "flags"
        elif dev.platform == "cpu":
            source = "cpu-pinned"
        else:
            source = f"autodetect:{dev.device_kind}"
    return {"flops": flops if flops > 0 else row["flops_bf16"],
            "bytes_per_s": gbps * 1e9 if gbps > 0
            else row["hbm_bytes_per_s"],
            "ici_bytes_per_s": ici * 1e9 if ici > 0
            else row["ici_bytes_per_s"],
            "source": source}


@dataclass
class CostProfile:
    """Static cost of ONE compiled executable, extracted at compile
    time (or derived analytically): total FLOPs, total HBM bytes
    accessed (reads + writes as XLA's HLO cost analysis counts them),
    and — when ``FLAGS_cost_memory_analysis`` is on — the executable's
    peak temp-buffer allocation.  When the profiling
    plane (observability.profiling) is armed, ``hot_ops`` carries the
    top-K per-op FLOP/byte rows from the same traced computation — the
    table the vision/fusion work ranks candidates from."""

    site: str            # the _JitTracker site label (human-readable)
    flops: float
    bytes_accessed: float
    temp_bytes: float = 0.0
    source: str = "hlo"  # "hlo" | "analytical"
    hot_ops: tuple = ()  # profiling.hot_op_table rows (top-K per op)
    collective_bytes: float = 0.0  # interconnect volume (sharded only)

    def to_obj(self) -> dict:
        return {"site": self.site, "flops": self.flops,
                "bytes_accessed": self.bytes_accessed,
                "temp_bytes": self.temp_bytes, "source": self.source,
                "collective_bytes": self.collective_bytes,
                "hot_ops": [dict(r) for r in self.hot_ops]}


def profile_signature(site: str, args) -> tuple:
    """The profile key: the same per-argument ``(shape, dtype,
    weak_type)`` signature scheme the eager dispatch cache keys its
    executables by (core.dispatch), rooted at the tracker's site label
    (two different step functions over identical operand shapes are
    different programs).  Non-array operands key by type+value, the
    dispatch scheme's static-scalar rule.  A mesh-sharded operand
    (FLAGS_serve_mesh) additionally keys by its PartitionSpec — the
    jit cache re-keys on input shardings for the same reason: a
    single-chip and a sharded engine at identical shapes run DIFFERENT
    programs (the sharded one carries collectives), and sharing a
    profile between them would attribute one's collective bytes (or
    their absence) to the other.  Single-chip keys are unchanged."""
    def _shard_tag(x):
        sh = getattr(x, "sharding", None)
        try:
            if sh is not None and len(sh.device_set) > 1:
                return str(getattr(sh, "spec", sh))
        except Exception:
            pass
        return None

    sig = []
    for a in args:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None and dtype is not None:
            row = (tuple(shape), str(dtype),
                   bool(getattr(a, "weak_type", False)))
            tag = _shard_tag(a)
            sig.append(row if tag is None else row + (tag,))
            continue
        import jax

        leaves = jax.tree_util.tree_leaves(a)
        if not leaves or (len(leaves) == 1 and leaves[0] is a):
            sig.append(("s", type(a).__name__, repr(a)[:32]))
            continue
        # pytree operand (the step fns' params dict, the K/V pool):
        # flatten to leaf shapes/dtypes so weight-shape changes re-key
        # — never `repr`, which would fetch every array to the host
        rows = []
        for x in leaves:
            if not hasattr(x, "shape"):
                continue
            row = (tuple(x.shape), str(x.dtype))
            tag = _shard_tag(x)
            rows.append(row if tag is None else row + (tag,))
        sig.append(tuple(rows))
    return (site, tuple(sig))


def _args_sharded(args) -> bool:
    """True when any operand leaf is laid out across more than one
    device — the signal that this executable runs under a mesh and its
    optimized HLO carries collectives worth accounting."""
    import jax

    for leaf in jax.tree_util.tree_leaves(args):
        sh = getattr(leaf, "sharding", None)
        if sh is None:
            continue
        try:
            if len(sh.device_set) > 1:
                return True
        except Exception:
            continue
    return False


def _extract_cost_analysis(fn, args) -> Optional[dict]:
    """Lower the jitted callable against ``args`` and read XLA's HLO
    cost analysis — no new jit-cache entry (pinned: ``_cache_size`` is
    untouched).  The CPU backend analyses the lowering itself; the TPU
    backend answers None there and analyses COMPILED programs only, so
    on a chip the lowering is compiled here.  That compile is not a
    second one: the jit call that follows gets the same lowering from
    jit's in-memory cache and reuses its executable (backend compiles
    counted equal with the observatory on and off).  A compile that
    fails raises — the jit call would fail on the same program.  None
    only when the backend gives no analysis in either form."""
    lowered = fn.lower(*args)
    ca = lowered.cost_analysis()
    from ..core import flags as _flags

    want_mem = bool(_flags.flag("cost_memory_analysis"))
    # Collective accounting needs the OPTIMIZED (post-SPMD-partitioner)
    # HLO, which only exists in the compiled program.  Always-on for
    # sharded executables — the interconnect term is first-class there.
    want_coll = _args_sharded(args)
    compiled = None
    if not isinstance(ca, dict) or want_mem or want_coll:
        compiled = lowered.compile()
    if not isinstance(ca, dict):
        ca = compiled.cost_analysis()
        if not isinstance(ca, dict):
            return None
    out = {"flops": float(ca.get("flops", 0.0)),
           "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    if want_mem:
        out["temp_bytes"] = float(
            compiled.memory_analysis().temp_size_in_bytes)
    if want_coll:
        from ..parallel.partition import collective_bytes

        out["collective_bytes"] = float(
            collective_bytes(compiled.as_text()))
    return out


def _hot_ops(fn, args) -> tuple:
    """The profiling plane's per-op table for this executable — same
    traced computation, no second compile; empty when the plane is
    disarmed (`FLAGS_profile`) or the walk fails."""
    from . import profiling

    if not profiling.enabled():
        return ()
    try:
        return profiling.hot_op_table(fn, args)
    except Exception:
        return ()


def note_executable(site: str, fn, args) -> Optional[tuple]:
    """`_JitTracker` chokepoint hook: called once per tracker on its
    FIRST invocation (compile time — the call that follows pays the
    XLA compile) when the observatory is armed.  Extracts and stores
    the static profile under the call signature; returns the signature
    key (the tracker memoizes it as ``cost_sig``), or None where the
    backend has no HLO cost analysis — the engine then predicts from
    the analytical formula.  A failure to lower or compile raises."""
    key = profile_signature(site, args)
    with _lock:
        existing = _PROFILES.get(key)
    if existing is not None:
        if not existing.hot_ops:
            # a profile cached by an earlier profiling-off engine:
            # backfill the hot-op table now that the plane wants it
            # (the signature proves the traced computation matches)
            hot = _hot_ops(fn, args)
            if hot:
                with _lock:
                    existing.hot_ops = hot
        return key
    ca = _extract_cost_analysis(fn, args)
    if ca is None:
        return None  # backend without HLO cost analysis: analytical
    prof = CostProfile(site=site, flops=ca["flops"],
                       bytes_accessed=ca["bytes_accessed"],
                       temp_bytes=ca.get("temp_bytes", 0.0),
                       source="hlo", hot_ops=_hot_ops(fn, args),
                       collective_bytes=ca.get("collective_bytes", 0.0))
    with _lock:
        _PROFILES[key] = prof
    if prof.collective_bytes > 0:
        from . import COLLECTIVE_BYTES

        COLLECTIVE_BYTES.set(prof.collective_bytes, fn=site)
    from ..inference.serving import _stats_add

    _stats_add(cost_profiles=1)
    return key


def profile_by_key(key: tuple) -> Optional[CostProfile]:
    """Exact profile lookup by signature key (a tracker's
    ``cost_sig``) — the per-engine view `Profiler.statusz` renders its
    hot-op tables from: the site-keyed `profiles()` view is
    last-writer-wins across every engine in the process, so two
    engines sharing a site label at different shapes would shadow
    each other there."""
    with _lock:
        return _PROFILES.get(key)


def profiles() -> Dict[str, dict]:
    """Snapshot of the process-global profile table, keyed by site
    (JSON-friendly; the tuple signature stays internal)."""
    with _lock:
        items = list(_PROFILES.items())
    out: Dict[str, dict] = {}
    for (site, _sig), prof in items:
        # several signatures may share a site label (prefill buckets
        # rebuilt after a config change); last writer wins the
        # human-readable view, the internal table keeps both
        out[site] = prof.to_obj()
    return out


def clear_profiles():
    """Drop every stored profile (tests / bench legs isolating runs)."""
    with _lock:
        _PROFILES.clear()


def analytical_gpt_cost(*, batch: int, q: int, kv_len: int,
                        layers: int, hidden: int, vocab: int,
                        kv_heads: Optional[int] = None,
                        num_heads: Optional[int] = None,
                        weight_bytes: int = 4,
                        kv_bytes: int = 4) -> Dict[str, float]:
    """Closed-form GPT step cost — the fallback when the backend's HLO
    cost analysis is unavailable.  ``batch`` rows of ``q`` query tokens
    attending over ``kv_len`` cached positions through ``layers``
    transformer blocks of width ``hidden`` (qkv + out projections +
    4x MLP = 12·H² MACs per token) plus one lm-head row per batch
    element; bytes = the weight stream (read once per step — the
    serving regime is weight/KV-bandwidth-bound, the premise of the
    quantized-KV work) + the KV pages read and written."""
    tokens = batch * q
    h = float(hidden)
    dense_flops = 2.0 * tokens * 12.0 * layers * h * h
    attn_flops = 4.0 * batch * q * kv_len * h * layers
    head_flops = 2.0 * batch * h * vocab
    weight_count = 12.0 * layers * h * h + h * vocab + 2.0 * vocab * h
    kvh = float(kv_heads if kv_heads is not None
                else (num_heads or 1))
    nh = float(num_heads or kvh)
    head_dim = h / max(nh, 1.0)
    kv_read = 2.0 * batch * kv_len * layers * kvh * head_dim * kv_bytes
    kv_write = 2.0 * tokens * layers * kvh * head_dim * kv_bytes
    act_bytes = 4.0 * tokens * h * layers * 4
    return {
        "flops": dense_flops + attn_flops + head_flops,
        "bytes_accessed": weight_count * weight_bytes + kv_read +
        kv_write + act_bytes,
    }


class CostModel:
    """One engine's cost observatory: profile lookup, the calibrated
    step-cost predictor, the HBM ledger, and the roofline gauges.
    Constructed by `DecodeEngine.__init__` when armed; reads the
    engine, never mutates it."""

    def __init__(self, engine, calibration: Optional[dict] = None):
        self.engine = engine
        self.peaks = resolve_peaks()
        # per-executable EWMA calibration: fn label -> factor mapping
        # raw roofline seconds onto measured wall seconds (captures
        # dispatch overhead, the host emit loop, everything the static
        # profile cannot see).  Seeded from a prior life's wire state
        # (recover / restore_from_dir) so a rebuilt engine predicts
        # accurately from its very first step.
        self._calib: Dict[str, float] = {}
        self._err: Dict[str, float] = {}
        if calibration:
            self.load_calibration(calibration)
        # engine-thread-private per-step prediction (the open-record
        # pattern: nobody else ever reads it) — deliberately outside
        # the lock discipline
        self._pending: Optional[dict] = None
        self._steps_since_ledger = 0
        # gauge refresh cadence: the EWMA tables update EVERY step
        # (cheap math under the lock), but the error/roofline gauges
        # re-render only every `_GAUGE_EVERY` steps — scrapes are
        # seconds apart, and per-step label-resolution on four gauges
        # is the single biggest accounting cost at small step sizes.
        # Seeded to render on the FIRST calibrated step.
        self._steps_since_gauges = _GAUGE_EVERY - 1
        from ..core import flags as _flags

        self._ledger_interval = int(
            _flags.flag("cost_ledger_interval_steps"))

    # -- calibration wire (durability / recovery) ----------------------------
    def calibration_wire(self) -> Dict[str, float]:
        """JSON-safe calibration state: what `DecodeEngine.wire_config`
        carries so recover/restore rebuild the predictor warm."""
        with _lock:
            return dict(self._calib)

    def load_calibration(self, wire: Dict[str, float]):
        with _lock:
            for k, v in dict(wire).items():
                self._calib[str(k)] = float(v)

    # -- static profiles -----------------------------------------------------
    def _tracker_profile(self, tracker) -> Optional[CostProfile]:
        if tracker is None:
            return None
        key = getattr(tracker, "cost_sig", None)
        if key is None:
            return None
        with _lock:
            return _PROFILES.get(key)

    def _analytical(self, *, batch: int, q: int,
                    kv_len: float) -> CostProfile:
        eng = self.engine
        p = eng._params
        hidden = eng._num_heads * eng._head_dim
        vocab = int(p["wte"].shape[0])
        # serve_weights=int8 stores every matmul weight at one byte
        # (the f32 wte would overstate the stream 4x; the per-channel
        # scale overhead is noise at 1/in_features of the payload)
        wb = 1 if getattr(eng, "_weight_quant", False) \
            else p["wte"].dtype.itemsize
        c = analytical_gpt_cost(
            batch=batch, q=q, kv_len=max(int(kv_len), 1),
            layers=eng._num_layers, hidden=hidden, vocab=vocab,
            num_heads=eng._num_heads,
            weight_bytes=wb,
            kv_bytes=eng._kv.dtype.itemsize)
        return CostProfile(site="analytical", flops=c["flops"],
                           bytes_accessed=c["bytes_accessed"],
                           source="analytical")

    def profile_for(self, kind: str) -> CostProfile:
        """The static profile of the executable a step of ``kind``
        runs ("decode" | "mixed" | "ragged" | "verify" |
        "draft_step"): the HLO-extracted profile when the tracker has
        compiled and the backend supports cost analysis, else the
        analytical GPT formula at the executable's fixed shapes."""
        eng = self.engine
        tracker = None
        batch, q = eng._slots, 1
        if kind == "decode":
            tracker = eng._decode_fn
        elif kind == "mixed":
            tracker = eng._mixed_fn
            q = eng._q_max
        elif kind == "ragged":
            tracker = eng._ragged_fn
            q = eng._q_ragged
        elif kind == "verify" and eng._spec is not None:
            tracker = eng._spec._verify_fn
            q = eng._spec.k + 1
        elif kind == "draft_step" and eng._spec is not None:
            tracker = getattr(eng._spec.drafter, "_step_fn", None)
        prof = self._tracker_profile(tracker)
        if prof is not None:
            return prof
        kv = float(eng._lens.mean()) if eng._lens.any() \
            else eng._max_seq_len / 2
        return self._analytical(batch=batch, q=q, kv_len=kv)

    def raw_seconds(self, prof: CostProfile) -> float:
        """Roofline time of one executable invocation: whichever of
        the compute and bandwidth ceilings binds, plus the serialized
        interconnect term (collective bytes over the ICI ceiling —
        zero on single-chip profiles, where no collectives exist)."""
        t = max(prof.flops / self.peaks["flops"],
                prof.bytes_accessed / self.peaks["bytes_per_s"])
        cb = getattr(prof, "collective_bytes", 0.0)
        if cb > 0:
            t += cb / self.peaks["ici_bytes_per_s"]
        return t

    # -- the predictor -------------------------------------------------------
    def _composition(self) -> Dict[str, object]:
        """The engine's CURRENT post-admission batch composition in
        predictor terms."""
        eng = self.engine
        prefilling = sum(
            1 for s in range(eng._slots)
            if eng._active[s] and eng._is_prefilling(s))
        active = int(eng._active.sum())
        return {
            "active": active,
            "prefilling": prefilling,
            "decoding": active - prefilling,
            "spec": eng._spec is not None and
            eng._resilience.spec_active(),
            "chunked": bool(eng._chunked),
        }

    def _step_plan(self, comp: Dict[str, object]):
        """(fn label, [(kind, invocations)]) for the step this
        composition dispatches to — mirrors `_step_inner`'s dispatch
        exactly.  On a ragged-step engine (FLAGS_ragged_step) every
        phase runs the ONE ragged executable, so the plan's kinds (and
        the calibration label of non-spec steps) collapse to
        "ragged"."""
        eng = self.engine
        ragged = bool(getattr(eng, "_ragged", False))
        if comp.get("spec"):
            plan = [("ragged" if ragged else "verify", 1)]
            if getattr(eng._spec.drafter, "_step_fn", None) is not None:
                # draft-model drafter: K greedy draft steps per round
                # (catch-up multi-query pass folded into the factor)
                plan.append(("draft_step", eng._spec.k))
            if comp.get("prefilling"):
                plan.append(("ragged" if ragged else "mixed", 1))
            return "spec", plan
        if ragged:
            return "ragged", [("ragged", 1)]
        if comp.get("chunked") and comp.get("prefilling"):
            return "mixed", [("mixed", 1)]
        return "decode", [("decode", 1)]

    def _predict_parts(self, composition: Optional[dict] = None):
        """(fn label, raw roofline seconds, calibration factor,
        calibrated?) for the step this composition dispatches to —
        the one computation `predict_step_cost` and `note_step_begin`
        both render."""
        comp = composition if composition is not None \
            else self._composition()
        fn, plan = self._step_plan(comp)
        raw = sum(self.raw_seconds(self.profile_for(kind)) * n
                  for kind, n in plan)
        with _lock:
            calibrated = fn in self._calib
            factor = self._calib.get(fn, 1.0)
        return fn, raw, factor, calibrated

    def predict_step_cost(self,
                          composition: Optional[dict] = None) -> float:
        """Predicted wall seconds of the engine's next step given a
        batch composition (None = the engine's current one): the raw
        roofline sum of the executables the step will run, times the
        learned per-executable calibration factor (1.0 until the first
        measured step of that kind)."""
        _fn, raw, factor, _cal = self._predict_parts(composition)
        return raw * factor

    def _tracker_sig(self):
        """Compile signature over the engine's live trackers (the
        watchdog's scheme): any change across a step means an
        executable compiled during it — that step's wall includes
        compile time and must not poison the calibration."""
        ts = self.engine._trackers()
        return (len(ts), sum(t._seen for t in ts))

    def note_step_begin(self, flight) -> None:
        """Stamp this step's prediction onto the flight recorder's
        OPEN record (engine thread, pre-dispatch — the prediction is
        honest: it never sees the measured time it will be scored
        against).  `observe` completes the pair at seal time."""
        fn, raw, factor, calibrated = self._predict_parts()
        info = {"fn": fn, "raw_s": raw, "predicted_s": raw * factor,
                "calibrated": calibrated}
        self._pending = {"sig": self._tracker_sig()}
        if flight is not None:
            flight.note_cost(info)

    def observe(self, rec: dict) -> None:
        """Score the sealed flight record's prediction against its
        measured wall, update the per-executable EWMA calibration and
        error, and refresh the roofline / ledger gauges.  THE
        calibration update site — engine thread only; reads the engine
        and the record, mutates only this model's tables (under the
        module lock: statusz renders them from other threads)."""
        pending, self._pending = self._pending, None
        cost = rec.get("cost")
        if cost is None or rec.get("kind") != "step":
            return
        if pending is None or pending.get("sig") != self._tracker_sig():
            # an executable compiled during this step (warmup, a new
            # prefill bucket, a degraded-mode rebuild): the measured
            # wall includes compile time — skip the update, the next
            # compile-free step calibrates cleanly
            return
        actual = float(rec.get("dur_s", 0.0))
        raw = float(cost.get("raw_s", 0.0))
        fn = str(cost.get("fn", "step"))
        if actual <= 0.0 or raw <= 0.0:
            return
        predicted = float(cost.get("predicted_s", 0.0))
        err = abs(predicted - actual) / actual
        sample = actual / raw
        calibrated = bool(cost.get("calibrated"))
        with _lock:
            prev = self._calib.get(fn)
            # EWMA in LOG space (a geometric mean): host-side stall
            # noise is right-skewed — a 3x outlier step must nudge the
            # factor, not yank it, or the predictor chases stalls and
            # mis-prices every quiet step that follows
            self._calib[fn] = sample if prev is None else \
                prev * math.exp(
                    _EWMA_ALPHA * math.log(max(sample, 1e-12) / prev))
            err_ewma = None
            if calibrated:
                # the error gauge scores only predictions made from an
                # already-learned factor — the very first sample of a
                # kind necessarily predicted from 1.0 and would read
                # as drift when it is just cold start
                prev_e = self._err.get(fn)
                self._err[fn] = err if prev_e is None else \
                    prev_e + _EWMA_ALPHA * (err - prev_e)
                err_ewma = self._err[fn]
        from ..inference.serving import _stats_add

        _stats_add(cost_updates=1)
        eng = self.engine
        if not _state["enabled"] or eng._abandoned:
            return
        # the ledger audit counts EVERY calibrated step against its
        # own interval (FLAGS_cost_ledger_interval_steps is engine
        # steps, not gauge refreshes — nesting it under the gauge
        # cadence would stretch it 8x past what the flag promises)
        if self._ledger_interval > 0:
            self._steps_since_ledger += 1
            if self._steps_since_ledger >= self._ledger_interval:
                self._steps_since_ledger = 0
                self.hbm_ledger(set_gauges=True)
                _obs().CAPACITY_HEADROOM.set(
                    self.headroom()["admissible_slots"],
                    engine=eng._engine_id)
        self._steps_since_gauges += 1
        if self._steps_since_gauges < _GAUGE_EVERY:
            return
        self._steps_since_gauges = 0
        obs = _obs()
        if err_ewma is None:
            with _lock:
                err_ewma = self._err.get(fn)
        if err_ewma is not None:
            obs.STEP_COST_ERROR.set(err_ewma, fn=fn)
        # roofline: each device leaf phase with a known profile scores
        # its measured time against the ceilings.  Flight phases keep
        # their historical names on a ragged engine, but every one of
        # them ran the ragged executable — score against its profile.
        ragged = bool(getattr(eng, "_ragged", False))
        for phase, kind in (("decode", "decode"), ("mixed", "mixed"),
                            ("verify", "verify")):
            dt = rec.get("phases", {}).get(phase)
            if not dt:
                continue
            prof = self.profile_for("ragged" if ragged else kind)
            obs.PHASE_MFU.set(
                prof.flops / dt / self.peaks["flops"], phase=phase)
            obs.PHASE_HBM_UTIL.set(
                prof.bytes_accessed / dt / self.peaks["bytes_per_s"],
                phase=phase)

    # -- the HBM ledger ------------------------------------------------------
    def hbm_ledger(self, set_gauges: bool = False) -> dict:
        """Live device bytes by category, reconciled against
        ``jax.live_arrays()``: every live array this engine can name
        (weights, KV pages, quant scales, the draft pool, the PRNG
        key) is attributed by identity; live bytes nothing claims are
        the ``unattributed`` residue (another engine's arrays, leaked
        temporaries, anything this ledger forgot) — a growing residue
        is the drift alarm.  ``temp_scratch`` is the executables' peak
        XLA scratch from the profiles (populated when
        ``FLAGS_cost_memory_analysis`` is on);
        scratch is XLA-owned, not a live array, so it reports beside
        the reconciliation, never inside it."""
        import jax

        eng = self.engine
        owner: Dict[int, str] = {}

        def claim(arr, cat: str):
            if arr is not None and hasattr(arr, "nbytes"):
                owner.setdefault(id(arr), cat)

        def claim_weights(tree):
            # itemized by storage dtype: serve_weights=int8 payloads
            # -> weights_int8, their `*_s` dequant scales ->
            # weight_scales, every f32 leaf (and the whole tree of an
            # off-mode engine) -> weights.  Keyed by dtype + leaf name
            # so a future bf16 scale would still land as a scale.
            leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
            for path, leaf in leaves:
                name = str(getattr(path[-1], "key", "")) if path else ""
                if str(getattr(leaf, "dtype", "")) == "int8":
                    claim(leaf, "weights_int8")
                elif name.endswith("_s"):
                    claim(leaf, "weight_scales")
                else:
                    claim(leaf, "weights")

        claim_weights(eng._params)
        for arr in eng._kv.pages:
            claim(arr, "kv_pages")
        for arr in eng._kv.scales:
            claim(arr, "kv_scales")
        claim(eng._key, "misc")
        if eng._spec is not None:
            d = eng._spec.drafter
            claim_weights(getattr(d, "_params", None) or {})
            for arr in jax.tree_util.tree_leaves(getattr(d, "_kv", None)):
                claim(arr, "draft_pool")
        cats = {c: 0 for c in LEDGER_CATEGORIES}
        unattributed = 0
        total = 0
        for a in jax.live_arrays():
            try:
                if a.is_deleted():
                    continue
                n = int(a.nbytes)
            except Exception:  # pragma: no cover - exotic array types
                continue
            total += n
            cat = owner.get(id(a))
            if cat is None:
                unattributed += n
            else:
                cats[cat] += n
        with _lock:
            cats["temp_scratch"] = int(sum(
                p.temp_bytes for p in _PROFILES.values()))
        out = {
            "categories": cats,
            "attributed_bytes": total - unattributed,
            "unattributed_bytes": unattributed,
            "total_live_bytes": total,
        }
        if set_gauges and _state["enabled"] and not eng._abandoned:
            obs = _obs()
            eid = eng._engine_id
            for cat, n in cats.items():
                obs.HBM_LEDGER.set(n, engine=eid, category=cat)
            obs.HBM_UNATTRIBUTED.set(unattributed, engine=eid)
        return out

    # -- capacity headroom ---------------------------------------------------
    def headroom(self) -> dict:
        """Admissible extra slots RIGHT NOW given predicted step cost
        and the pool's reclaimable bytes — the number a fleet router
        reads before routing more work here.  Three ceilings, the
        minimum binds: free slots, pool pages (free + evictable minus
        outstanding reservations, at the running requests' mean page
        need), and the SLO ceiling (an extra slot is only admissible
        while the predicted step cost stays under the tightest
        declared per-token target — with fixed-shape executables a
        step costs what it costs regardless of occupancy, so the SLO
        ceiling is all-or-nothing)."""
        eng = self.engine
        pool = eng.pool
        free_slots = len(eng._free_slots)
        avail_pages = max(
            pool.free_count + pool.cached_unreferenced_count -
            pool.reserved, 0)
        per_page = eng._kv_byte_occupancy()["bytes_per_token"] * \
            eng._page
        running = [r for r in eng._by_slot if r is not None]
        if running:
            need = max(int(sum(
                eng._pages_for(r.total_kv_tokens())
                for r in running) / len(running)), 1)
        else:
            need = eng._pages_per_seq
        by_pages = avail_pages // need
        predicted = self.predict_step_cost()
        # the queue copy goes through the engine's retrying snapshot:
        # headroom() serves statusz from arbitrary threads, and a
        # deque iterated while the engine thread mutates it raises
        targets = [r.slo_tpot_ms
                   for r in running + eng._snapshot_queue()
                   if r is not None and r.slo_tpot_ms is not None]
        tightest = min(targets) if targets else None
        slo_ok = tightest is None or predicted * 1e3 <= tightest
        admissible = min(free_slots, by_pages) if slo_ok else 0
        return {
            "admissible_slots": int(admissible),
            "free_slots": int(free_slots),
            "slots_by_pool_pages": int(by_pages),
            "free_pool_bytes": int(avail_pages * per_page),
            "predicted_step_s": predicted,
            "tightest_tpot_ms": tightest,
            "slo_ok": bool(slo_ok),
        }

    # -- cost-model admission (FLAGS_sched_cost_admission) -------------------
    def admission_ok(self, req) -> bool:
        """Cost-gated admission: admit ``req`` only while the
        predicted step cost stays within the tightest per-token SLO
        among it and the running set.  A request declaring no target
        always passes against an unconstrained batch — the gate
        protects declared SLOs from overload, it is not a quota.
        Consulted by `DecodeEngine._admit_one` only when
        ``FLAGS_sched_cost_admission`` armed (default off =
        bit-exact admission)."""
        eng = self.engine
        if not eng._active.any():
            # an idle engine always admits: refusing the only
            # admissible work protects nobody (the candidate's own
            # target cannot be met by queueing longer) and would
            # livelock a drain loop
            return True
        targets = [r.slo_tpot_ms for r in eng._by_slot
                   if r is not None and r.slo_tpot_ms is not None]
        if req.slo_tpot_ms is not None:
            targets.append(req.slo_tpot_ms)
        if not targets:
            return True
        comp = self._composition()
        comp["active"] = comp["active"] + 1
        # the candidate arrives with an UNCONSUMED prompt: on a
        # chunked engine its admission turns the next steps into mixed
        # prefill+decode steps — pricing it as a decode row would
        # underestimate exactly the step the gate exists to bound
        comp["prefilling"] = comp["prefilling"] + 1
        return self.predict_step_cost(comp) * 1e3 <= min(targets)

    # -- introspection -------------------------------------------------------
    def statusz(self) -> dict:
        """The cost section of `DecodeEngine.statusz`: profiles,
        calibration, error, peaks, ledger, headroom.  Read-only and
        thread-safe (tables copied under the lock; the ledger walks
        live arrays without touching engine state)."""
        with _lock:
            calib = dict(self._calib)
            err = dict(self._err)
        return {
            "peaks": dict(self.peaks),
            "profiles": profiles(),
            "calibration": calib,
            "error_ratio": err,
            "ledger": self.hbm_ledger(),
            "headroom": self.headroom(),
        }


_obs_mod = None


def _obs():
    # lazy catalog resolution, cached (the flight.py pattern): this
    # module must not participate in the observability package's
    # import cycle
    global _obs_mod
    if _obs_mod is None:
        from paddle_tpu import observability

        _obs_mod = observability
    return _obs_mod
