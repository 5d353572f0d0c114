"""Profiler surface.

Reference: `paddle/fluid/platform/profiler.{h,cc}` (`EnableProfiler`,
`DisableProfiler`, RAII `RecordEvent`, aggregated event tables, chrome-trace
timeline via `profiler.proto`) + `platform/device_tracer.cc` (CUPTI device
activity) + Python context managers `python/paddle/fluid/profiler.py`.

TPU-native split:
- **Host events** go through the native C++ tracer (csrc/runtime.cc Tracer:
  lock-free-ish append buffer, chrome-trace JSON export) via RecordEvent.
- **Device timeline** is XLA/PJRT's own tracing (TraceMe/xplane): wrapped by
  `start_trace`/`stop_trace` below (`jax.profiler`), viewable in
  TensorBoard/XProf — the moral replacement for the CUPTI DeviceTracer.
"""
from __future__ import annotations

import contextlib
import json
from collections import defaultdict

from .core import native
from .core.native import RecordEvent, now_ns  # re-export  # noqa: F401
# Eager dispatch telemetry (core/dispatch.py): per-op call/hit/miss/
# retrace counters + wall time for the signature-keyed executable cache —
# the dispatch-level complement of the host-event tables below.
from .core.dispatch import (  # noqa: F401
    clear_dispatch_cache, dispatch_cache_size, dispatch_stats,
    dispatch_summary_string, reset_dispatch_stats,
)

__all__ = [
    "RecordEvent", "profiler", "start_profiler", "stop_profiler",
    "reset_profiler", "start_trace", "stop_trace", "trace",
    "summary_string", "export_chrome_tracing",
    "dispatch_stats", "dispatch_summary_string", "reset_dispatch_stats",
    "clear_dispatch_cache", "dispatch_cache_size",
    "decode_stats", "reset_decode_stats",
]


# Decode-telemetry schema, shared with inference.serving (which builds
# its live counter dict from these) so the not-imported fallback below
# can never silently diverge from the real key set.
DECODE_STAT_COUNTERS = (
    "steps", "tokens", "prefills", "decode_time_s", "prefill_time_s",
    "decode_compiles", "prefill_compiles", "retraces_after_warmup",
    "occupancy_sum", "kv_util_sum",
    # chunked prefill (FLAGS_chunked_prefill): prompt chunks fused into
    # the decode step through the mixed-batch executable.
    # ``stalled_decode_steps`` counts legacy one-shot prefills that ran
    # while other slots were decoding (the stall chunking removes) —
    # it must stay 0 on the chunked path.
    "mixed_steps", "mixed_compiles", "prefill_chunks",
    "stalled_decode_steps",
    # prefix caching (FLAGS_prefix_cache): pages mapped from /
    # missing in the content-addressed cache at admission, prompt
    # tokens skipped, and unreferenced cached pages recycled under
    # pool pressure
    "prefix_hits", "prefix_misses", "prefix_cached_tokens",
    "prefix_evictions",
    # speculative decoding (inference.speculative): propose/verify loop
    "spec_steps", "spec_slot_steps", "spec_proposed", "spec_accepted",
    "spec_emitted",
    "draft_time_s", "verify_time_s", "verify_compiles", "draft_compiles",
    # request-completion accounting (Request.finish_reason; "cancelled"
    # counts queued AND running requests removed via Request.cancel())
    "finished_eos", "finished_length", "evicted", "cancelled",
    # SLO-aware scheduling (inference.frontend.SLOScheduler):
    # preempt/resume cycles, still-queued requests retired at their
    # deadline, and declared TTFT/TPOT/deadline targets missed
    "preemptions", "resumes", "deadline_expired", "slo_violations",
    # fault containment + crash recovery (inference.resilience):
    # injected faults fired, same-step retries spent, requests
    # quarantined with finish_reason="fault", engine rebuilds, and
    # degraded-mode transitions (speculation disabled / chunked
    # prefill fallen back to the legacy oracle path)
    "faults_injected", "step_retries", "finished_fault", "recoveries",
    "spec_disables", "legacy_fallbacks",
    # durable serving (inference.durability): write-ahead journal
    # records appended, on-disk snapshots written, fresh-process
    # restores performed, executables handed from a dead engine to its
    # rebuilt successor (recompiles avoided), and steps the watchdog
    # classified as hung (FLAGS_step_timeout_ms)
    "journal_records", "journal_snapshots", "restores", "exec_handoffs",
    "hung_steps",
    # fleet serving (paddle_tpu.fleet): a dead replica's journal
    # replayed into a LIVE survivor engine (zero-loss failover), and
    # journals rewritten down to their live state during restore
    # (FLAGS_journal_compact)
    "adoptions", "journal_compactions",
    # flight recorder (observability.flight): sealed per-step records
    # pushed into the bounded ring, and crash-safe window auto-dumps
    # (fatal fault / hung step / watchdog abandonment black boxes)
    "flight_records", "flight_dumps",
    # quantized KV pages (FLAGS_kv_quant=int8): pages whose quant
    # scale was (re)initialized on allocation ("pages quantized"),
    # (page, head) scale entries re-quantized after an absmax growth
    # (the write-path "refold"), and the tiny scale-reset executable's
    # compiles (target pool + draft pool, one signature each)
    "kv_quant_pages", "kv_quant_refolds", "kv_quant_compiles",
    # quantized weight storage (FLAGS_serve_weights=int8): matmul
    # weight matrices folded to int8 + per-out-channel f32 scales at
    # engine construction / drafter bind ("mats"), and the HBM bytes
    # that fold reclaimed net of the scale leaves it added — both stay
    # 0 on serve_weights=off engines (the off-mode-quiet proof the
    # bench's parity leg pins)
    "weight_quant_mats", "weight_quant_bytes_saved",
    # cost observatory (observability.costmodel): static FLOP/byte
    # profiles extracted at executable compile time, and calibration
    # updates scored against the flight recorder's measured steps
    "cost_profiles", "cost_updates",
    # profiling plane (observability.profiling): steps whose device
    # dispatches were sync-probed (FLAGS_profile_sample_steps cadence
    # or an armed capture), and bounded capture sessions completed
    "profile_probes", "profile_captures",
    # ragged unified step (FLAGS_ragged_step): compiles of the ONE
    # executable that serves decode, mixed prefill+decode, and
    # speculative verify traffic alike (every row carries its own
    # query span), and the adaptive per-slot speculation depth's
    # shrink/grow transitions (FLAGS_spec_adaptive_k)
    "ragged_compiles", "spec_k_shrinks", "spec_k_grows",
    # per-executable retrace attribution: ``retraces_after_warmup``
    # aggregates every site; these split the same events by the
    # tracker's compile_key (<kind>_compiles -> <kind>_retraces), so
    # "the ragged path compiles exactly one step executable and never
    # retraces it" is a counter assertion, not a log grep
    "decode_retraces", "prefill_retraces", "mixed_retraces",
    "verify_retraces", "draft_retraces", "kv_quant_retraces",
    "ragged_retraces",
    # where a request's first second goes, and what the host does while
    # the chip waits (read by the benchmark's serve metrics; the same
    # sites open the ``engine.*`` / ``frontend.*`` profiler spans):
    # enqueue -> first admission and admission -> first token, summed
    # over the requests that got there; the dispatch-to-fetched wall of
    # mixed steps alone (``decode_time_s`` / ``prefill_time_s`` hold
    # them blended with plain decode steps); `DecodeEngine.step`'s wall
    # outside the dispatch-to-fetched walls those two sums hold, on
    # steps that ran a batch; and the frontend's wall from one step's
    # return to the next step's call when it did not wait in between
    # (so ``decode_time_s + prefill_time_s + host_in_step_s +
    # between_steps_s`` is the wall a busy engine spent, counted once)
    "queue_wait_s", "admissions", "first_token_wait_s", "first_tokens",
    "mixed_time_s", "host_in_step_s", "between_steps_s",
    # where the serve loop's host time goes between the chip's steps
    # (read by `benchmarks/trace_split.py` beside the ``engine.*`` /
    # ``wait.*`` / ``edge.*`` / ``host.gc`` profiler spans): a token's
    # wall from its emission in the engine to the flush of its SSE
    # bytes at the edge, summed over the tokens relayed; the frontend's
    # two executor hand-offs a step (loop -> worker thread -> loop),
    # one hop per round trip; and Python's garbage collector, any
    # thread (wall inside collections, collections, and the full
    # generation-2 ones among them)
    "relay_s", "relayed_tokens", "hop_s", "hops",
    "gc_pause_s", "gc_collections", "gc_gen2_collections",
    # the serve loop's one-step lookahead (`DecodeEngine.step`): steps
    # dispatched while the step before them was still in flight; steps
    # in flight landed before the next dispatch (a speculative round,
    # the ragged step, the legacy prefill, an armed watchdog, a probing
    # step, the sanitizer, fault injection, an engine going empty, or
    # the caller's `drain`; the cause is on the flight record); and rows
    # landed for a request that had left meanwhile (eos, quarantine,
    # cancel, preemption), whose tokens are thrown away
    "lookahead_steps", "lookahead_drains", "lookahead_dropped_rows",
)
DECODE_STAT_DERIVED = ("avg_step_ms", "batch_occupancy",
                       "kv_block_utilization",
                       "acceptance_rate", "mean_accepted_per_step",
                       "lookahead_share")


def _decode_stat_zero(key):
    return 0.0 if key.endswith(("_s", "_sum", "_ms")) or \
        key in DECODE_STAT_DERIVED else 0


def decode_stats(reset=False):
    """Serving-loop telemetry (inference.serving.DecodeEngine): decode
    step latency, batch occupancy, KV-block utilization, executable
    compile/retrace counts.  If no engine was ever created in this
    process, returns all-zero counters WITHOUT importing the serving
    module (a telemetry poller must not pay the engine's import)."""
    import sys

    mod = sys.modules.get("paddle_tpu.inference.serving")
    if mod is None:
        return {k: _decode_stat_zero(k)
                for k in DECODE_STAT_COUNTERS + DECODE_STAT_DERIVED}
    return mod.decode_stats(reset)


def reset_decode_stats():
    import sys

    mod = sys.modules.get("paddle_tpu.inference.serving")
    if mod is not None:
        mod.reset_decode_stats()


_state = {"device": False}


def start_profiler(state="All", tracer_option="Default"):
    """Begin host event collection (reference `EnableProfiler`,
    `fluid/profiler.py start_profiler`).  `state`/`tracer_option` are
    accepted for API compatibility; device-side tracing is a separate
    concern on TPU — use `start_trace`/`trace` for the XLA timeline."""
    native.trace_clear()
    native.tracer_enable()


def stop_profiler(sorted_key="total", profile_path=None, print_table=True):
    """Stop collection; print the aggregated table; optionally dump a
    chrome-trace timeline json to `profile_path` (reference
    `DisableProfiler` + timeline proto export).  ``print_table=False``
    returns the table without writing stdout — for tests and the
    periodic observability reporter, which collect rather than spam;
    the default keeps the reference's print-on-stop behavior."""
    native.tracer_disable()
    text = summary_string(sorted_key=sorted_key)
    if print_table:
        print(text)
    if profile_path:
        export_chrome_tracing(profile_path)
    return text


def reset_profiler():
    native.trace_clear()


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None):
    """Context-manager form (reference `fluid/profiler.py profiler`)."""
    start_profiler(state)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


# ---------------------------------------------------------------------------
# aggregation / export
# ---------------------------------------------------------------------------
def _events():
    data = json.loads(native.trace_export_json())
    return data.get("traceEvents", [])


def summary_string(sorted_key="total") -> str:
    """Aggregated per-event table: calls, total/avg/min/max ms, ratio —
    the layout of the reference's `PrintProfiler` table."""
    agg = defaultdict(lambda: [0, 0.0, float("inf"), 0.0])  # n, tot, mn, mx
    for ev in _events():
        if ev.get("ph") != "X":
            continue
        dur_ms = ev.get("dur", 0) / 1000.0  # chrome trace dur is us
        a = agg[ev.get("name", "?")]
        a[0] += 1
        a[1] += dur_ms
        a[2] = min(a[2], dur_ms)
        a[3] = max(a[3], dur_ms)
    total = sum(a[1] for a in agg.values()) or 1.0
    keyfn = {
        "total": lambda kv: -kv[1][1],
        "calls": lambda kv: -kv[1][0],
        "max": lambda kv: -kv[1][3],
        "min": lambda kv: kv[1][2],
        "ave": lambda kv: -(kv[1][1] / max(kv[1][0], 1)),
    }.get(sorted_key, lambda kv: -kv[1][1])
    lines = [
        "-------------------------     Profiling Report     "
        "-------------------------",
        f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>10}"
        f"{'Min(ms)':>10}{'Max(ms)':>10}{'Ratio':>8}",
    ]
    for name, (n, tot, mn, mx) in sorted(agg.items(), key=keyfn):
        lines.append(
            f"{name:<40}{n:>8}{tot:>12.4f}{tot / max(n, 1):>10.4f}"
            f"{mn if n else 0:>10.4f}{mx:>10.4f}{tot / total:>8.2%}")
    return "\n".join(lines)


def export_chrome_tracing(path: str):
    """Write the MERGED chrome://tracing JSON: host tracer events plus
    the observability span tracks (engine decode/prefill/verify step
    spans, per-request lifecycle spans) on separately named process
    lanes (reference timeline proto → `tools/timeline.py` equivalent,
    now one timeline for all three telemetry sources).  A process that
    recorded no spans gets exactly the old host-only timeline plus its
    track label."""
    from .observability import tracing as _tracing

    _tracing.export_chrome_trace(path)


# ---------------------------------------------------------------------------
# device (XLA) tracing
# ---------------------------------------------------------------------------
def start_trace(log_dir: str):
    """Start an XLA/PJRT device trace (xplane, TensorBoard-viewable) —
    the TPU replacement for the reference's CUPTI DeviceTracer
    (`platform/device_tracer.cc:57`)."""
    import jax

    jax.profiler.start_trace(log_dir)
    _state["device"] = True


def stop_trace():
    import jax

    if _state["device"]:
        jax.profiler.stop_trace()
        _state["device"] = False


@contextlib.contextmanager
def trace(log_dir: str):
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()
