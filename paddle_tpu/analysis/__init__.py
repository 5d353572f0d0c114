"""Static trace-safety / donation / lock-discipline analysis + the
runtime sanitizer for the serving stack.

Two halves:

* `passes` — AST lint passes over the repo's own source (no code is
  executed, no jax import).  `tools/tracecheck.py` is the CLI;
  `run_tracecheck()` is the library entry.  The **repo spec** below
  names the designated locks, guarded registries, engine-mutation
  sanction sites, the fleet-trace control-plane allowlist, and the
  default scan targets — the invariants the serving stack's
  docstrings promise, made machine-checkable.
* `sanitizer` — runtime mode (``FLAGS_sanitize``): donated-buffer
  tombstones with use-after-donate errors naming the donation site,
  lock-order cycle detection over the designated locks, warm retraces
  raising instead of counting, a host-sync sentinel, and the
  `KVBlockPool.assert_consistent` audit every engine step.

Baseline workflow: ``tracecheck --write-baseline`` grandfathers the
current findings into a JSON file keyed by content fingerprint (pass +
file + source-line text), so pre-existing debt never blocks CI while
any TOUCHED line resurfaces immediately.  The shipped baseline
(`tools/tracecheck_baseline.json`) is empty: every finding the passes
surfaced was fixed, not grandfathered.

See docs/STATIC_ANALYSIS.md for the pass catalog and workflow.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from .passes import (  # noqa: F401
    DonationPass, EngineMutationPass, EngineRule, Finding,
    FleetTracePass, FleetTraceRule, LockRule,
    LockDisciplinePass, SourceModule, TraceHazardPass, run_passes,
    scan_paths,
)
from . import sanitizer  # noqa: F401

__all__ = [
    "Finding", "LockRule", "EngineRule", "FleetTraceRule",
    "SourceModule",
    "TraceHazardPass", "LockDisciplinePass", "EngineMutationPass",
    "DonationPass", "FleetTracePass", "run_passes", "scan_paths",
    "run_tracecheck",
    "REPO_LOCK_RULES", "REPO_ENGINE_RULE", "REPO_FLEET_TRACE_RULE",
    "DEFAULT_TARGETS",
    "load_baseline", "write_baseline", "split_baselined", "sanitizer",
]


# ---------------------------------------------------------------------------
# The repo spec: designated locks, guarded registries, sanction sites.
# This is the machine-readable form of the serving stack's concurrency
# contracts — keep it in sync with the module docstrings it encodes.
# ---------------------------------------------------------------------------
REPO_LOCK_RULES: Dict[str, LockRule] = {
    # ONE telemetry lock: every registry series mutation and every
    # serving._STATS read-modify-write happens under observability.LOCK
    "observability/metrics.py": LockRule(
        locks=("LOCK",),
        roots=("_state",),
        self_attrs=("_series", "_metrics", "_views"),
    ),
    "observability/tracing.py": LockRule(
        locks=("_lock",),
        roots=("_spans", "_dropped"),
    ),
    # flight recorder: every CROSS-THREAD surface — the sealed-record
    # ring, the window totals, the goodput counters — mutates under
    # the module's designated lock (statusz and dump read from
    # arbitrary threads while the engine thread appends).  The OPEN
    # record (`_cur`'s contents) is deliberately engine-thread-private
    # and lock-free, so it is not listed here.
    "observability/flight.py": LockRule(
        locks=("_lock",),
        self_attrs=("_ring", "_win_tokens", "_win_time",
                    "_fin_total", "_fin_met", "dumps"),
    ),
    "observability/reporter.py": LockRule(
        locks=("_lock",),
        roots=("_thread", "_stop"),
    ),
    # cost observatory: the process-global profile table and every
    # CostModel's calibration/error tables mutate under the module's
    # designated lock (statusz renders them from arbitrary threads).
    # The per-step `_pending` prediction is engine-thread-private like
    # the flight recorder's open record and deliberately unlisted.
    "observability/costmodel.py": LockRule(
        locks=("_lock",),
        roots=("_PROFILES", "_forced_engines"),
        self_attrs=("_calib", "_err"),
    ),
    # alert engine: the per-rule state table and the transitions list
    # (/alertz reads them from the ops server's handler threads while
    # the engine thread evaluates) mutate under the module's
    # designated lock.  Per-rule evaluation HISTORIES are engine-
    # thread-private like the flight recorder's open record and
    # deliberately unlisted.
    "observability/alerts.py": LockRule(
        locks=("_lock",),
        self_attrs=("_state", "_transitions"),
    ),
    # ops-plane registry: engine/frontend registration and the server
    # handle swap mutate under the module lock (handlers snapshot
    # under it and render outside it)
    "observability/opsserver.py": LockRule(
        locks=("_lock",),
        roots=("_ENGINES", "_FRONTENDS", "_SERVER"),
    ),
    # profiling plane: capture state, the device-time table and the
    # measured-MFU/drift tables mutate under the module's designated
    # lock (/profilez and request_capture touch them from arbitrary
    # threads).  The open-step probe dict (_probe/_probe_now) is
    # engine-thread-private like the flight recorder's open record
    # and deliberately unlisted.
    "observability/profiling.py": LockRule(
        locks=("_lock",),
        roots=("_PROFILERS", "_forced_engines"),
        self_attrs=("_capture_pending", "_capture_remaining",
                    "_capture_total", "_captures", "_device_s",
                    "_host_ratio", "_mfu", "_dev_calib", "_drift"),
    ),
    "inference/serving.py": LockRule(
        locks=("_TELEMETRY_LOCK", "LOCK"),
        roots=("_STATS",),
    ),
    "inference/speculative.py": LockRule(
        locks=("_TELEMETRY_LOCK", "LOCK"),
        roots=("_STATS",),
    ),
    # dispatch keeps its own two locks: per-op stats under _STATS_LOCK
    # (including the _OpStats objects aliased out of the registry), the
    # executable cache under _CACHE_LOCK
    "core/dispatch.py": LockRule(
        locks=("_STATS_LOCK", "_CACHE_LOCK"),
        roots=("_STATS", "_CACHE"),
        alias_fns=("_stats_for",),
        alias_attrs=("stats",),
        guarded_classes=("_OpStats",),
    ),
}

# DecodeEngine is single-threaded by contract: every mutation happens
# between steps on the driver.  serving.py / speculative.py ARE the
# engine; resilience.py is the containment ladder + crash recovery
# (engine-called between steps, and recovery mutates the engine
# between steps BY DESIGN — the fold/re-admit in `recover` and the
# bisect-quarantine preempt/retire in `ResilienceManager` are
# sanctioned recovery sites); durability.py is the write-ahead
# journal + fresh-process restore + hung-step watchdog (restore
# re-admits into a just-built idle engine, the watchdog abandons and
# neutralizes a hung one — both sanctioned recovery-class mutation);
# in frontend.py only the schedulers (engine-called, between steps),
# the driver's control-application points, and the driver's recovery
# supervision may mutate.
REPO_ENGINE_RULE = EngineRule(
    mutators=(
        "add_request", "evict", "preempt", "step", "run", "generate",
        "_admit", "_admit_one", "_finish", "_emit", "_bind_slot",
        "_prefill_into", "_cancel_queued", "_cancel_running",
        "_retire_queued", "_grow_block_tables", "_mixed_step",
        "_stamp_admit", "_stamp_first_token", "_land_row",
        "_register_prompt_pages", "_register_generated_pages",
        "_debug_check_pool",
        # the one-step lookahead: a step dispatched, landed, dropped,
        # or read back early; a slot released ahead of its last token
        "_dispatch", "_retire", "_drain", "drain", "_drop",
        "_settle_inflight", "_release_finishing_slots", "_requeue",
        # fault containment / recovery (inference.resilience): the
        # ladder's retry unit, slot quarantine, and admission unwind
        # mutate the engine — callable only from sanctioned sites
        "_step_inner", "_quarantine_slot", "_unwind_failed_admit",
        "_release_slot",
        # durable serving (inference.durability): executable handoff
        # to a rebuilt engine and watchdog abandonment of a hung one
        "adopt_executables", "_abandon_inflight",
        # quantized weight storage (FLAGS_serve_weights=int8): the
        # construction-time fold replacing the engine's f32 matmul
        # leaves with int8+scale pairs — a param-tree mutation no
        # observer (cost model, profiler, alert evaluator) may ever
        # invoke: re-quantizing a live tree would silently re-trace
        # every warm executable
        "_fold_weight_quant",
    ),
    receivers=("eng", "engine", "self.engine", "self._engine"),
    sanctioned={
        "inference/serving.py": ("*",),
        "inference/speculative.py": ("*",),
        "inference/resilience.py": ("*",),
        "inference/durability.py": ("*",),
        "inference/frontend.py": (
            "Scheduler.", "FIFOScheduler.", "SLOScheduler.",
            "ServingFrontend._apply_control", "ServingFrontend._drive",
            "ServingFrontend._recover_engine",
            # the driver's one step call, with the clock pair around it
            # that books the time between two steps
            "ServingFrontend._step",
        ),
        # the flight recorder READS engine state (batch composition,
        # pool occupancy, SLO burn) from inside the step — sanctioned
        # for exactly the recorder class so a rogue recorder that
        # MUTATES the engine (the tempting bug: "just retire the slow
        # request from here") still flags
        "observability/flight.py": ("FlightRecorder.",),
        # the cost observatory likewise READS the engine (batch
        # composition for prediction, pool/params for the ledger,
        # the calibration update site scoring sealed records) —
        # sanctioned for exactly the CostModel class, so a rogue cost
        # model that mutates the engine ("just preempt the slot my
        # prediction says is over budget") still flags
        "observability/costmodel.py": ("CostModel.",),
        # the alert evaluator READS the engine between steps (pool
        # pressure, health, burn gauges for its signals) — sanctioned
        # for exactly the AlertEngine class, so a rogue evaluator that
        # mutates the engine ("just preempt the request burning the
        # budget from inside evaluate()") still flags.  The ops
        # server's handlers are NOT sanctioned at all: every endpoint
        # is read-only by contract, and an endpoint that grows a
        # mutating call flags the moment it is written.
        "observability/alerts.py": ("AlertEngine.",),
        # the profiling plane READS the engine (blocking on dispatch
        # outputs, scoring sealed records, the between-steps capture-
        # arming site) — sanctioned for exactly the Profiler class, so
        # a rogue profiler that mutates the engine ("just preempt the
        # slot whose dispatch keeps blocking longest") still flags
        "observability/profiling.py": ("Profiler.",),
    },
)

# Fleet trace propagation (docs/FLEET_TRACING.md): every HTTP site
# under paddle_tpu/fleet/ must carry the x-paddle-trace plumbing or
# sit on this allowlist — control-plane endpoints that carry no
# request identity, so there is nothing to trace:
#   _get_json / _post_json    router's generic JSON fetch/post helpers
#   FleetRouter._fetch_text   /metrics scrape for the /fleetz rollup
#   ReplicaHandle.fetch_info  /v1/info identity card at add_replica
#   ReplicaHandle.poll        /readyz admission poll (its t0/t1/now_ns
#                             FEED the clock sync, but the poll itself
#                             belongs to no request)
#   ReplicaHandle.alertz      /alertz scrape for the fleet rollup
REPO_FLEET_TRACE_RULE = FleetTraceRule(
    path_markers=("paddle_tpu/fleet/",),
    allowlist=(
        "_get_json", "_post_json", "FleetRouter._fetch_text",
        "ReplicaHandle.fetch_info", "ReplicaHandle.poll",
        "ReplicaHandle.alertz",
    ),
)

# What `tools/tracecheck.py` scans by default (repo-root relative):
# the serving stack plus the dispatch cache and the fleet's network
# plane — the modules whose invariants the passes encode.
DEFAULT_TARGETS: Tuple[str, ...] = (
    "paddle_tpu/inference",
    "paddle_tpu/observability",
    "paddle_tpu/core/dispatch.py",
    "paddle_tpu/fleet",
)


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def run_tracecheck(paths: Optional[Sequence[str]] = None,
                   root: Optional[str] = None,
                   lock_rules: Optional[Dict[str, LockRule]] = None,
                   engine_rule: Optional[EngineRule] = None,
                   fleet_rule: Optional[FleetTraceRule] = None
                   ) -> List[Finding]:
    """Run every static pass over ``paths`` (default: the repo's
    serving-stack targets) and return the sorted findings."""
    root = root or repo_root()
    modules = scan_paths(paths or DEFAULT_TARGETS, root)
    return run_passes(
        modules,
        lock_rules=REPO_LOCK_RULES if lock_rules is None else lock_rules,
        engine_rule=REPO_ENGINE_RULE if engine_rule is None
        else engine_rule,
        fleet_rule=REPO_FLEET_TRACE_RULE if fleet_rule is None
        else fleet_rule)


# ---------------------------------------------------------------------------
# Baseline (grandfather) file
# ---------------------------------------------------------------------------
def load_baseline(path: str) -> Dict[str, dict]:
    """fingerprint -> entry dict.  A missing file is an empty
    baseline."""
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        data = json.load(f)
    return {e["fingerprint"]: e for e in data.get("entries", [])}


def write_baseline(path: str, findings: Sequence[Finding]):
    entries = [{
        "fingerprint": f.fingerprint,
        "pass": f.pass_id,
        "path": f.path,
        "line": f.line,       # informational; the fingerprint is the key
        "message": f.message,
    } for f in findings]
    with open(path, "w") as fh:
        json.dump({"version": 1, "entries": entries}, fh, indent=2,
                  sort_keys=True)
        fh.write("\n")


def split_baselined(findings: Sequence[Finding],
                    baseline: Dict[str, dict]
                    ) -> Tuple[List[Finding], List[Finding]]:
    """(new, grandfathered) split by content fingerprint."""
    new, old = [], []
    for f in findings:
        (old if f.fingerprint in baseline else new).append(f)
    return new, old
