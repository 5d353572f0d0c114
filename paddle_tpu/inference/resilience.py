"""Fault injection, failure containment, and crash recovery for the
serving stack.

At the ROADMAP's scale — heavy traffic from millions of users —
transient faults are the steady state, not the exception: a preempted
device, an OOM spike, NaN logits out of a bad batch, a step that
stalls, a user callback that throws.  Before this module one raising
step executable killed the whole continuous batch, pool exhaustion was
a bare ``RuntimeError``, and a dead driver failed every open stream.
This module makes every one of those survivable, and — just as
important — makes every recovery path *testable on CPU in tier-1*
through a deterministic fault-injection harness.

Three pieces:

* **`FaultPlan`** (armed via ``FLAGS_fault_inject`` or
  ``DecodeEngine(fault_plan=...)``) — a deterministic, occurrence-
  count-driven schedule of failures at named sites (`FAULT_SITES`):
  step-executable raise (generic ``step`` or per-executable
  ``mixed_step`` / ``decode_step`` / ``verify``), ``pool`` exhaustion
  on alloc, ``nan_logits`` row corruption, ``drafter`` raise,
  ``slow_step`` stall, ``host_callback`` raise, plus a
  ``poison@TOKEN`` mode where the step site fails exactly while a
  request whose prompt contains TOKEN is in the batch (the bisect
  containment must isolate it).  No wall-clock anywhere: the Nth
  consult of a site fires, every run replays identically.

* **`ResilienceManager`** — per-engine containment ladder
  `DecodeEngine.step` runs under:

  1. **retry** the failed step with capped exponential backoff
     (``FLAGS_step_retries`` attempts; deterministic backoff *ticks*
     1, 2, 4 ... capped at 8, each tick optionally sleeping
     ``FLAGS_step_backoff_ms``);
  2. **degrade** the failing subsystem after
     ``FLAGS_degrade_after`` consecutive failures — speculation
     disables (verify-only rounds already contained drafter raises),
     chunked prefill falls back to the legacy one-shot oracle path —
     with a re-enable probe after ``FLAGS_degraded_probe_steps``
     clean steps and ``paddle_degraded_mode`` gauges either way;
  3. **bisect-quarantine**: preempt the newest-admitted request and
     retry; repeat until the step succeeds — the last removal is the
     suspect and is retired with ``finish_reason="fault"`` (a
     structured `errors.FaultInfo` on the request), while the
     innocents it was preempted with resume from the queue (their
     replay rides the prefix cache);
  4. still failing with an empty batch → re-raise as a FATAL
     `errors.StepFault` — the engine itself is broken.

* **`EngineSnapshot` / `recover`** — crash recovery over the prefix
  cache.  A snapshot is pure host state captured between steps: every
  in-flight request's prompt + generated ids, remaining budget, and
  the engine's RNG fold counters.  `recover(engine)` rebuilds a fresh
  engine from the dead one's resolved constructor config and
  re-admits every request with its generated tokens FOLDED into the
  prompt (the same fold `DecodeEngine.preempt` uses), so replay is an
  ordinary prompt: chunked prefill recomputes it deterministically,
  requests sharing prefixes hit the rebuilt cache against each other,
  and greedy outputs are bit-identical to a fault-free run.  Tokens
  already emitted live in the folded prompt — they are never
  re-emitted, which is what keeps `frontend.ServingFrontend` streams
  alive across a rebuild.  `serve_with_recovery` is the blocking
  supervisor (the frontend's ``_drive`` embeds the same loop).

Everything here is host-side control between steps: no executable
shape ever changes, and with no plan armed every hook in the serve
loop is a single ``is None`` check — the
``FLAGS_fault_inject``-off path is bit-exact with the pre-resilience
engine (pinned by tests/test_resilience.py).

See docs/RELIABILITY.md for the operator-facing walk-through.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import observability as _obs
from ..analysis import sanitizer as _san
from .errors import (DegradedMode, FaultInfo, InjectedFault,
                     PoolExhausted, StepFault)

__all__ = ["FAULT_SITES", "FaultPlan", "ResilienceManager",
           "EngineSnapshot", "recover", "serve_with_recovery"]


FAULT_SITES = ("step", "mixed_step", "decode_step", "verify", "drafter",
               "pool", "nan_logits", "slow_step", "host_callback")


# ---------------------------------------------------------------------------
# The fault plan
# ---------------------------------------------------------------------------
class FaultPlan:
    """Deterministic fault schedule: ``schedule[site]`` is the set of
    1-based occurrence indices at which the site fires (the engine
    consults a site's counter every time execution passes the hook;
    the Nth consult fires iff N is scheduled).  ``poison_token`` arms
    the batch-content fault: the generic ``step`` site fails whenever
    a request whose PROMPT contains the token occupies an active slot
    — deterministic, and only the bisect containment can clear it.
    ``slow_ms`` is the stall the ``slow_step`` site injects.

    No wall-clock, no RNG at consult time: two runs over the same
    workload replay the same faults at the same steps.  Counters are
    carried across an engine rebuild (`recover` passes the same plan
    object), so a schedule never re-fires after recovery."""

    def __init__(self, schedule: Optional[Dict[str, Sequence[int]]] = None,
                 poison_token: Optional[int] = None, slow_ms: float = 5.0):
        self.schedule: Dict[str, frozenset] = {}
        for site, occs in (schedule or {}).items():
            if site not in FAULT_SITES:
                raise ValueError(
                    f"unknown fault site {site!r}: one of {FAULT_SITES}")
            occs = frozenset(int(o) for o in occs)
            if any(o < 1 for o in occs):
                raise ValueError(
                    f"occurrence indices are 1-based, got {sorted(occs)} "
                    f"for site {site!r}")
            self.schedule[site] = occs
        self.poison_token = None if poison_token is None \
            else int(poison_token)
        self.slow_ms = float(slow_ms)
        self._counts: Dict[str, int] = {}

    def consult(self, site: str) -> bool:
        """Advance ``site``'s occurrence counter; True iff this
        occurrence is scheduled to fire."""
        n = self._counts.get(site, 0) + 1
        self._counts[site] = n
        return n in self.schedule.get(site, ())

    def poisoned(self, engine) -> bool:
        """Batch-content fault: True while any ACTIVE slot's request
        has the poison token in its prompt."""
        tok = self.poison_token
        if tok is None:
            return False
        for s in range(engine._slots):
            if not engine._active[s]:
                continue
            req = engine._by_slot[s]
            if req is not None and tok in req.prompt_ids:
                return True
        return False

    def consults(self, site: str) -> int:
        """How many times ``site`` has been consulted (telemetry)."""
        return self._counts.get(site, 0)

    @classmethod
    def parse(cls, spec: str) -> Optional["FaultPlan"]:
        """Parse the FLAGS_fault_inject grammar; None for an empty
        spec (harness disarmed, zero hot-path cost).

        ``spec`` is ';'-separated entries:

        * ``site@occs`` — ``occs`` is a ','-separated list of 1-based
          occurrence indices and ``a-b`` inclusive ranges, e.g.
          ``step@3,7-9``;
        * ``poison@TOKEN`` — arm the batch-content fault;
        * ``slow_ms=X`` — the ``slow_step`` stall duration.
        """
        spec = (spec or "").strip()
        if not spec:
            return None
        schedule: Dict[str, List[int]] = {}
        poison = None
        slow_ms = 5.0
        for entry in spec.split(";"):
            entry = entry.strip()
            if not entry:
                continue
            if entry.startswith("slow_ms="):
                slow_ms = float(entry.split("=", 1)[1])
                continue
            if "@" not in entry:
                raise ValueError(
                    f"bad fault_inject entry {entry!r}: expected "
                    f"'site@occurrences', 'poison@TOKEN' or 'slow_ms=X'")
            site, _, occs = entry.partition("@")
            site = site.strip()
            if site == "poison":
                poison = int(occs)
                continue
            out = schedule.setdefault(site, [])
            for part in occs.split(","):
                part = part.strip()
                if "-" in part:
                    a, _, b = part.partition("-")
                    out.extend(range(int(a), int(b) + 1))
                else:
                    out.append(int(part))
        return cls(schedule, poison_token=poison, slow_ms=slow_ms)

    @classmethod
    def seeded(cls, seed: int, sites: Sequence[str], rate: float,
               horizon: int, slow_ms: float = 5.0,
               poison_token: Optional[int] = None) -> "FaultPlan":
        """A pseudo-random — but fully deterministic given ``seed`` —
        schedule: each of the first ``horizon`` consults of every site
        fires with probability ``rate`` (drawn once, at construction,
        from a seeded RandomState; nothing is random at consult
        time).  The chaos bench (tools/bench_chaos.py) builds its
        storms with this."""
        rng = np.random.RandomState(seed)
        schedule = {
            site: [i + 1 for i in range(int(horizon))
                   if rng.random_sample() < rate]
            for site in sites
        }
        return cls(schedule, poison_token=poison_token, slow_ms=slow_ms)


# ---------------------------------------------------------------------------
# The containment ladder
# ---------------------------------------------------------------------------
class ResilienceManager:
    """Per-engine fault containment: injection hooks, the
    retry -> degrade -> bisect-quarantine ladder around
    `DecodeEngine._step_inner`, and the degraded-mode state machine.
    Constructed unconditionally (one per engine); with no plan armed
    and no faults raised it costs one ``try`` per step."""

    # never retried, never contained: these mean the PROCESS state is
    # suspect (sanitizer invariants, audit asserts), not the step
    NONRETRYABLE = (_san.SanitizerError, AssertionError)

    def __init__(self, engine):
        self.engine = engine
        # consecutive failures per subsystem kind ("spec" | "mixed" |
        # "decode"), cleared by any clean step
        self._fail: Dict[str, int] = {}
        # contained drafter faults leave the STEP successful (the
        # round completes verify-only), so they carry their own
        # consecutive counter — cleared only by a round with no
        # drafter fault, not by mere step completion
        self._drafter_fail = 0
        self._drafter_faulted = False
        self.spec_disabled = False
        self.legacy_mode = False
        self._clean_since_degrade = 0
        self.backoff_ticks = 0  # deterministic, cumulative (telemetry)

    # -- injection hooks -----------------------------------------------------
    def _count_injection(self, site: str):
        from .serving import _stats_add

        _stats_add(faults_injected=1)
        _obs.FAULTS_INJECTED.inc(site=site)

    def fault_point(self, site: str):
        """Consult one named site.  Fires according to the plan:
        ``pool`` raises `PoolExhausted`, ``slow_step`` stalls for
        ``plan.slow_ms``, everything else raises `InjectedFault`.
        Callers guard with ``engine._fault is not None`` so the
        disarmed hot path never enters here."""
        plan = self.engine._fault
        if plan is None or not plan.consult(site):
            return
        self._count_injection(site)
        if site == "slow_step":
            time.sleep(plan.slow_ms / 1e3)
            if self.engine._abandoned:
                # the frontend watchdog abandoned this engine while we
                # stalled: die here instead of running a step whose
                # requests already belong to the rebuilt engine
                raise StepFault(
                    "engine abandoned by the hung-step watchdog "
                    "mid-stall", site="hung", fatal=True)
            return
        if site == "pool":
            raise PoolExhausted(
                "injected: KV page pool exhausted (fault site 'pool')")
        raise InjectedFault(
            f"injected fault at site {site!r}", site=site)

    def step_fault_point(self, kind_site: str):
        """The guard in front of every step executable: consults the
        generic ``step`` site (plus the poison-token batch fault),
        then the executable-specific site (``mixed_step`` /
        ``decode_step`` / ``verify``)."""
        plan = self.engine._fault
        if plan is None:
            return
        if plan.consult("step") or plan.poisoned(self.engine):
            self._count_injection("step")
            raise InjectedFault(
                "injected fault at site 'step'", site=kind_site)
        self.fault_point(kind_site)

    def corrupt_tokens(self, toks, eligible_slots):
        """The ``nan_logits`` site, host half: when scheduled, replace
        the lowest eligible slot's sampled token with the NaN sentinel
        the in-graph `serving._guard_tokens` guard produces for a
        genuinely non-finite row — injection and organic NaN take the
        exact same quarantine path from here on.  ``toks`` is the
        fetched [B] token vector (or [B, Q] verify-target matrix:
        position 0 is corrupted)."""
        plan = self.engine._fault
        if plan is None or not eligible_slots or \
                not plan.consult("nan_logits"):
            return toks
        self._count_injection("nan_logits")
        toks = np.array(toks)  # the fetched buffer may be read-only
        s = min(eligible_slots)
        if toks.ndim == 1:
            toks[s] = -1
        else:
            toks[s, 0] = -1
        return toks

    # -- degraded-mode state machine -----------------------------------------
    def spec_active(self) -> bool:
        return self.engine._spec is not None and not self.spec_disabled

    def on_drafter_fault(self, err: Exception):
        """A contained drafter raise (the round proceeds verify-only).
        Counts toward the spec-degradation threshold on its own
        consecutive counter — the step itself completes, so the
        generic per-step failure accounting never sees it."""
        self._drafter_faulted = True
        self._drafter_fail += 1
        self._maybe_disable_spec(err)

    def _maybe_disable_spec(self, err: Exception) -> bool:
        from ..core import flags as _flags
        from .serving import _stats_add

        eng = self.engine
        consecutive = max(self._fail.get("spec", 0), self._drafter_fail)
        if eng._spec is None or self.spec_disabled or \
                consecutive < int(_flags.flag("degrade_after")):
            return False
        self.spec_disabled = True
        self._clean_since_degrade = 0
        self._fail.pop("spec", None)
        self._drafter_fail = 0
        _stats_add(spec_disables=1)
        _obs.DEGRADED_MODE.set(1, engine=eng._engine_id, mode="spec_off")
        from .durability import set_health

        set_health(eng._engine_id, "degraded")
        _obs.record_span("engine", "degrade:spec_off", _obs.now_ns(), 0,
                         tid=eng._engine_id,
                         args={"error": str(err)[:200]})
        if eng._flight is not None:
            eng._flight.event("degrade", mode="spec_off",
                              error=str(err)[:120])
        return True

    def _maybe_degrade_legacy(self, err: Exception) -> bool:
        """Persistent mixed-step failure: fall back to the legacy
        one-shot prefill oracle path.  Mid-prefill slots are preempted
        (their partially consumed prompts replay through the legacy
        prefill), chunked mode and the prefix cache switch off; the
        re-enable probe restores both after clean steps."""
        from ..core import flags as _flags
        from .serving import _stats_add

        eng = self.engine
        if not eng._chunked or \
                self._fail.get("mixed", 0) < int(_flags.flag(
                    "degrade_after")):
            return False
        for s in range(eng._slots):
            if eng._active[s] and eng._is_prefilling(s):
                eng.preempt(eng._by_slot[s])
        eng._chunked = False
        eng._prefix_cache = False
        self.legacy_mode = True
        self._clean_since_degrade = 0
        self._fail.pop("mixed", None)
        _stats_add(legacy_fallbacks=1)
        _obs.DEGRADED_MODE.set(1, engine=eng._engine_id,
                               mode="legacy_prefill")
        from .durability import set_health

        set_health(eng._engine_id, "degraded")
        _obs.record_span("engine", "degrade:legacy_prefill",
                         _obs.now_ns(), 0, tid=eng._engine_id,
                         args={"error": str(err)[:200]})
        if eng._flight is not None:
            eng._flight.event("degrade", mode="legacy_prefill",
                              error=str(err)[:120])
        return True

    def _note_success(self):
        from ..core import flags as _flags

        self._fail.clear()
        if not self._drafter_faulted:
            self._drafter_fail = 0  # a round with a healthy drafter
        self._drafter_faulted = False
        if not (self.spec_disabled or self.legacy_mode):
            return
        self._clean_since_degrade += 1
        if self._clean_since_degrade < int(_flags.flag(
                "degraded_probe_steps")):
            return
        eng = self.engine
        self._clean_since_degrade = 0
        if self.spec_disabled and \
                not getattr(eng._spec.drafter, "stateful", False):
            # probe: try speculation again; a fresh failure re-degrades
            self.spec_disabled = False
            _obs.DEGRADED_MODE.set(0, engine=eng._engine_id,
                                   mode="spec_off")
            if eng._flight is not None:
                eng._flight.event("degrade_end", mode="spec_off")
        if self.legacy_mode:
            eng._chunked = eng._chunked_cfg
            eng._prefix_cache = eng._prefix_cache_cfg
            self.legacy_mode = False
            _obs.DEGRADED_MODE.set(0, engine=eng._engine_id,
                                   mode="legacy_prefill")
            if eng._flight is not None:
                eng._flight.event("degrade_end", mode="legacy_prefill")
        if not (self.spec_disabled or self.legacy_mode):
            from .durability import set_health

            set_health(eng._engine_id, "live")

    # -- the ladder ----------------------------------------------------------
    def _mode_kind(self) -> str:
        eng = self.engine
        if self.spec_active():
            return "spec"
        if eng._chunked and eng._prefilling_any():
            return "mixed"
        return "decode"

    def _backoff(self, attempt: int):
        """Capped exponential backoff between same-step retries:
        deterministic tick accounting (1, 2, 4, ... capped at 8) —
        the wall sleep is tick * FLAGS_step_backoff_ms and defaults to
        ZERO, so tier-1 tests replay instantly while production can
        give a transient device fault room to clear."""
        from ..core import flags as _flags
        from .serving import _stats_add

        ticks = min(1 << (attempt - 1), 8)
        self.backoff_ticks += ticks
        _stats_add(step_retries=1)
        _obs.STEP_RETRIES.inc()
        fl = self.engine._flight
        if fl is not None:
            fl.event("retry", attempt=attempt, ticks=ticks)
        base_ms = float(_flags.flag("step_backoff_ms"))
        if base_ms > 0:
            time.sleep(ticks * base_ms / 1e3)

    def run_step(self) -> bool:
        """Run `DecodeEngine._step_inner` under the containment
        ladder.  See the module docstring for the rungs; any step that
        completes clears the consecutive-failure counters and advances
        the degraded-mode re-enable probe."""
        from ..core import flags as _flags

        eng = self.engine
        retries = int(_flags.flag("step_retries"))
        attempt = 0
        last = None
        while True:
            kind = self._mode_kind()
            try:
                out = eng._step_inner()
            except self.NONRETRYABLE:
                raise
            except Exception as e:
                if eng._abandoned:
                    # the watchdog abandoned this engine: its requests
                    # live on the rebuilt one — containment here would
                    # mutate state nobody owns anymore
                    raise
                last = e
                self._fail[kind] = self._fail.get(kind, 0) + 1
                if attempt < retries:
                    attempt += 1
                    self._backoff(attempt)
                    continue
                # retries exhausted: degrade the failing subsystem —
                # the degraded path gets its own retry budget
                if kind == "spec" and self._maybe_disable_spec(e):
                    attempt = 0
                    continue
                if kind == "mixed" and self._maybe_degrade_legacy(e):
                    attempt = 0
                    continue
                return self._bisect_quarantine(e, attempt)
            self._note_success()
            return out

    def _newest_running(self):
        """The most recently admitted running request (bisect order:
        newest admits are the most likely suspects — they are what
        changed about the batch)."""
        eng = self.engine
        live = [r for r in eng._by_slot if r is not None]
        if not live:
            return None
        return max(live, key=lambda r: (r.t_admit_ns or 0, r.request_id))

    def _bisect_quarantine(self, err: Exception, attempts: int) -> bool:
        """Isolate the suspect: preempt the newest-admitted request
        and retry, repeating until the step succeeds.  The LAST
        removal is the suspect — retired with ``finish_reason="fault"``
        and a structured `FaultInfo` — while the innocents preempted
        along the way resume from the queue (their replay rides the
        prefix cache, so the detour costs at most one partial page of
        recompute each).  An empty batch that still fails re-raises as
        a FATAL `StepFault`: the engine itself is broken and only
        `recover` can continue."""
        eng = self.engine
        removed = []
        while True:
            victim = self._newest_running()
            if victim is None:
                site = getattr(err, "site", "step")
                raise StepFault(
                    f"step fault survived retry, degradation and "
                    f"batch bisection — the engine is broken "
                    f"(last error: {err})", site=site,
                    attempts=attempts + len(removed), fatal=True) \
                    from err
            eng.preempt(victim)
            removed.append(victim)
            attempt_ns = _obs.now_ns()
            try:
                out = eng._step_inner()
            except self.NONRETRYABLE:
                raise
            except Exception as e:
                err = e
                continue
            suspect = removed[-1]
            # the suspect was preempted back into the queue: retire it
            # from there with the fault verdict; everyone else stays
            # queued and resumes on the following steps
            suspect.fault_info = FaultInfo(
                site=getattr(err, "site", "step"),
                attempts=attempts + len(removed), step=eng._step_no,
                recovered=False, message=str(err))
            eng._retire_queued(suspect, "fault")
            _obs.record_span(
                "engine", "quarantine", attempt_ns,
                _obs.now_ns() - attempt_ns, tid=eng._engine_id,
                args={"request": suspect.request_id,
                      "site": suspect.fault_info.site,
                      "bisected": len(removed)})
            if eng._flight is not None:
                eng._flight.event("quarantine",
                                  request=suspect.request_id,
                                  site=suspect.fault_info.site,
                                  bisected=len(removed))
            self._note_success()
            return out


# ---------------------------------------------------------------------------
# Crash recovery
# ---------------------------------------------------------------------------
class _ReqRecord:
    __slots__ = ("request", "prompt_ids", "output_ids", "max_new",
                 "absorbed", "orig_len", "streamed")

    def __init__(self, request):
        self.request = request
        self.prompt_ids = list(request.prompt_ids)
        self.output_ids = list(request.output_ids)
        self.max_new = int(request.max_new_tokens)
        self.absorbed = int(request._absorbed)
        self.orig_len = int(request.orig_prompt_len)
        # emitted-token watermark at capture: generated tokens the
        # stream has consumed, plus any still-pending emit gate (a
        # gated token was streamed by an earlier life)
        self.streamed = self.absorbed + len(self.output_ids) + \
            int(request._emit_gate)


class EngineSnapshot:
    """Pure host state of every in-flight request, captured between
    steps: prompt + generated ids, remaining token budget, preemption
    fold accounting, and the engine's RNG fold counters.  Sampling
    parameters, SLO metadata and streaming hooks live ON the `Request`
    objects, which the snapshot keeps by reference — recovery re-admits
    the same objects, so `TokenStream`s and schedulers keep working
    without re-wiring.

    Capture order is admission order (running requests by admit stamp,
    then the queue front-to-back), so a FIFO engine replays in the
    same order it originally served."""

    def __init__(self, engine):
        self.engine_id = engine._engine_id
        # the last step landed: one still in flight is dispatched again
        self.step_no = int(engine._step_no) - \
            (engine._inflight is not None)
        self.prefill_no = int(engine._prefill_no)
        running = sorted(
            (r for r in engine._by_slot if r is not None),
            key=lambda r: (r.t_admit_ns or 0, r.request_id))
        self.records = [_ReqRecord(r) for r in running] + \
            [_ReqRecord(r) for r in engine._queue]

    def __len__(self):
        return len(self.records)

    def to_wire(self, journal_pos: int = 0):
        """The serialization-safe split (`durability.SnapshotWire`):
        the in-process form keeps `Request` objects BY REFERENCE so
        streams/hooks survive a rebuild, which is exactly wrong on
        disk — the wire form carries only picklable/JSON-able state
        (original prompt, generated values, budgets, the emitted-token
        watermark) a fresh process can re-admit from."""
        from .durability import RequestWire, SnapshotWire

        return SnapshotWire(
            engine_id=self.engine_id, step_no=self.step_no,
            prefill_no=self.prefill_no, journal_pos=int(journal_pos),
            records=[RequestWire.from_record(rec)
                     for rec in self.records])


def recover(engine, snapshot: Optional[EngineSnapshot] = None,
            fault: Optional[BaseException] = None,
            handoff: bool = True):
    """Rebuild a fresh engine after a fatal fault and re-admit every
    in-flight request.  The dead engine's resolved constructor config
    (`engine._ctor`) rebuilds an identical engine — same weights, same
    shapes, same seed; the scheduler/drafter instances are unbound and
    re-bound (their per-engine state rebuilds), and the SAME fault
    plan object carries its occurrence counters over so an injected
    schedule cannot re-fire after the rebuild.

    ``handoff=True`` (default) additionally hands the dead engine's
    live compiled executables to the rebuilt engine
    (`DecodeEngine.adopt_executables`): the config fingerprints match
    by construction (same `_ctor`), so the signature keys are
    identical and the rebuilt engine's first step reuses the warm jit
    caches instead of recompiling — recompile DOMINATED recovery
    latency before this (tools/bench_recovery.py pins the ratio).
    Any fingerprint mismatch falls back to recompile silently.

    Each request's generated tokens fold into its prompt (exactly the
    `DecodeEngine.preempt` fold: ``max_new_tokens`` shrinks one for
    one, ``generated_ids`` stays complete), so replay is an ordinary
    prompt the chunked prefill recomputes deterministically — greedy
    outputs are bit-identical to a fault-free serve, recovered
    requests sharing prefixes hit the rebuilt prefix cache against
    each other, and already-emitted tokens are never re-emitted (the
    streaming hook only ever sees novel tokens).  When recovering from
    an OLDER snapshot than the live request state (the watchdog's
    abandon path hands the pre-step snapshot), tokens the live request
    emitted past the snapshot are recomputed behind the `_emit` gate —
    streamed once, never twice.

    The OLD engine is retired: its scheduler/drafter now belong to the
    new engine and its device buffers are garbage."""
    from .durability import retire_engine_series, set_health
    from .serving import DecodeEngine, _stats_add

    snap = snapshot if snapshot is not None else EngineSnapshot(engine)
    dead_dur, engine._durability = engine._durability, None
    if dead_dur is not None:
        # a fatal fault escaped step() BEFORE its boundary flush:
        # records buffered during the failing step (e.g. a bisect
        # quarantine's finish) must reach disk, or a later process
        # death would restore a request this recovery already retired.
        # close() retires the handle too — the SUCCESSOR engine owns
        # the journal from here, never two live writers
        try:
            dead_dur.close()
        except Exception:
            pass  # best effort — the old handle may already be dead
    t0 = time.perf_counter()
    t0_ns = _obs.now_ns()
    kw = dict(engine._ctor)
    for key in ("scheduler", "drafter"):
        obj = kw.get(key)
        if obj is not None and hasattr(obj, "engine"):
            obj.engine = None  # unbind: bind() rebuilds per-engine state
    if engine._cost is not None:
        # carry the dead engine's LIVE cost calibration (the _ctor
        # holds only the construction-time seed): the rebuilt engine's
        # step-cost predictor starts warm, like its executables
        kw["cost_calibration"] = engine._cost.calibration_wire()
    new = DecodeEngine(**kw)
    set_health(new._engine_id, "recovering")
    if handoff:
        new.adopt_executables(engine)
    # RNG fold counters carry over so the rebuilt engine's sampling
    # streams continue where the dead engine's stopped (greedy ignores
    # them; stochastic streams must not restart from fold 1)
    new._step_no = snap.step_no
    new._prefill_no = snap.prefill_no
    site = getattr(fault, "site", "engine")
    n_readmitted = 0
    for rec in snap.records:
        req = rec.request
        if req.state == "done":
            continue  # quarantined/finished between capture and recover
        # tokens the live request streamed PAST the captured record
        # (the watchdog abandoned a step that had already emitted):
        # replay recomputes them deterministically, the gate keeps
        # them from re-firing at the stream
        live_streamed = req._absorbed + len(req.output_ids) + \
            req._emit_gate
        n_gen = len(rec.output_ids)
        req.prompt_ids = list(rec.prompt_ids) + list(rec.output_ids)
        req.max_new_tokens = rec.max_new - n_gen
        req._absorbed = rec.absorbed + n_gen
        req._emit_gate = max(0, live_streamed - rec.absorbed - n_gen)
        req.output_ids = []
        req.pages = []
        req.slot = None
        req.cached_page_count = 0
        req.cached_prefix_len = 0
        req._page_hashes = None
        req.state = "queued"
        req._engine = new
        if req.fault_info is None:
            req.fault_info = FaultInfo(
                site=site, step=snap.step_no, recovered=True,
                message=str(fault) if fault is not None else
                "rode an engine recovery")
        else:
            req.fault_info.history.append(req.fault_info.site)
            req.fault_info.site = site
            req.fault_info.recovered = True
        new._queue.append(req)
        n_readmitted += 1
    _stats_add(recoveries=1)
    _obs.RECOVERIES.inc()
    _obs.RECOVERY_SECONDS.observe(time.perf_counter() - t0)
    _obs.record_span("engine", "recovery", t0_ns,
                     _obs.now_ns() - t0_ns, tid=new._engine_id,
                     args={"from_engine": snap.engine_id,
                           "requests": n_readmitted, "site": site})
    if new._flight is not None:
        new._flight.event("recovery", from_engine=snap.engine_id,
                          requests=n_readmitted, site=site)
    set_health(new._engine_id, "live")
    # retire the dead engine from the WHOLE gauge catalog, not just
    # health: a recovered hang must not leave {state="hung"} latched
    # at 1 forever, and the dead id's pool/occupancy/queue/burn gauges
    # must stop reading stale levels on every scrape after it
    retire_engine_series(engine._engine_id)
    return new


def serve_with_recovery(engine, max_recoveries: Optional[int] = None,
                        max_steps: int = 100000
                        ) -> Tuple[object, int]:
    """Blocking serve loop with crash recovery: drive ``engine`` to
    completion like `DecodeEngine.run`, rebuilding it via `recover`
    whenever a step fault survives the containment ladder.  Returns
    ``(final_engine, recoveries)`` — the caller must use the RETURNED
    engine (a recovery retires the one passed in).  More than
    ``max_recoveries`` (default FLAGS_engine_recoveries) rebuilds
    raises `DegradedMode` chained from the last fatal fault."""
    from ..core import flags as _flags

    limit = int(_flags.flag("engine_recoveries")) \
        if max_recoveries is None else int(max_recoveries)
    recoveries = 0
    steps = 0
    while engine._busy():
        if steps >= max_steps:
            raise RuntimeError(
                f"serve_with_recovery(max_steps={max_steps}) exhausted "
                f"with work pending after {recoveries} recoveries")
        try:
            engine.step()
        except StepFault as e:
            if recoveries >= limit:
                raise DegradedMode(
                    f"engine recovery budget exhausted "
                    f"({limit} rebuilds): {e}") from e
            engine = recover(engine, fault=e)
            recoveries += 1
        steps += 1
    return engine, recoveries
