"""tracecheck static passes + the FLAGS_sanitize runtime sanitizer.

Three layers:

* fixture snippets per static lint — a known-bad snippet triggers the
  finding, the known-good twin is clean (the pass itself can't rot);
* the repo gate — the real serving-stack targets scan clean with the
  shipped (empty) baseline, and the baseline workflow round-trips;
* runtime sanitizer — a seeded use-after-donate bug and a lock-order
  cycle each fail loudly under FLAGS_sanitize=1, while a real
  `DecodeEngine.generate` run under the sanitizer passes with zero
  findings and bit-identical tokens.
"""
import os
import textwrap

import numpy as np
import pytest

import paddle_tpu
from paddle_tpu.analysis import (
    DonationPass, EngineMutationPass, EngineRule, FleetTracePass,
    FleetTraceRule, LockRule,
    LockDisciplinePass, TraceHazardPass, load_baseline, run_passes,
    run_tracecheck, sanitizer, scan_paths, split_baselined,
    write_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scan_snippet(tmp_path, source, name="fixture_mod.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return scan_paths([str(p)], str(tmp_path))


# ---------------------------------------------------------------------------
# trace-hazard lint
# ---------------------------------------------------------------------------
class TestTraceHazardLint:
    def test_branch_on_traced_value(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import jax

            def step(x, y):
                if x > 0:
                    return y
                return x

            fn = jax.jit(step)
        """)
        found = TraceHazardPass().run(mods)
        assert len(found) == 1
        assert found[0].pass_id == "trace-hazard"
        assert "`if` on a traced value" in found[0].message
        assert "step" in found[0].message

    def test_coercion_and_item(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import jax

            def step(x):
                n = int(x)
                v = x.item()
                return n + v

            fn = jax.jit(step)
        """)
        found = TraceHazardPass().run(mods)
        kinds = sorted(f.message.split(" on")[0] for f in found)
        assert len(found) == 2
        assert any("`int()`" in f.message for f in found), kinds
        assert any(".item()" in f.message for f in found), kinds

    def test_while_and_ternary(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import jax

            def step(x):
                while x > 0:
                    x = x - 1
                return x if x > 0 else -x

            fn = jax.jit(step)
        """)
        found = TraceHazardPass().run(mods)
        assert any("`while`" in f.message for f in found)
        assert any("conditional expression" in f.message for f in found)

    def test_taint_flows_through_assignment(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import jax
            import jax.numpy as jnp

            def step(x):
                y = x * 2
                z = jnp.sum(y)
                if z > 0:          # z is derived from the traced x
                    return y
                return x

            fn = jax.jit(step)
        """)
        assert len(TraceHazardPass().run(mods)) == 1

    def test_shape_access_launders_taint(self, tmp_path):
        """Control flow on .shape/.dtype is trace-time-static — the
        repo's jitted step functions do this everywhere and must stay
        clean."""
        mods = _scan_snippet(tmp_path, """
            import jax
            import jax.numpy as jnp

            def step(x):
                b, n = x.shape
                if n > 4:                    # static: shapes are baked
                    x = x[:, :4]
                for i in range(int(b)):      # int() of a static too
                    x = x + i
                return x

            fn = jax.jit(step)
        """)
        assert TraceHazardPass().run(mods) == []

    def test_partial_kwargs_are_static(self, tmp_path):
        """The repo convention: statics ride functools.partial keywords
        onto keyword-only params; branching on them is fine."""
        mods = _scan_snippet(tmp_path, """
            import functools
            import jax

            def step(x, *, mode, scale):
                if mode == "fast":
                    return x * scale
                return x

            fn = jax.jit(functools.partial(step, mode="fast", scale=2.0))
        """)
        assert TraceHazardPass().run(mods) == []

    def test_static_argnums_respected(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import jax

            def step(x, n):
                if n > 4:
                    return x * n
                return x

            fn = jax.jit(step, static_argnums=(1,))
        """)
        assert TraceHazardPass().run(mods) == []

    def test_static_argnums_with_partial_positional_shift(self, tmp_path):
        """static_argnums index the JITTED signature: with a partial
        binding one positional arg, jit arg 0 is def param 1.  The
        static param must not be tainted (no false finding) and the
        traced one must stay tainted (real finding kept)."""
        mods = _scan_snippet(tmp_path, """
            import functools
            import jax

            def step(cfg, mode, x):
                if mode == "fast":     # static: jit argnum 0
                    return x * 2
                if x.sum() > 0:        # traced: the real hazard
                    return x
                return -x

            CFG = {}
            fn = jax.jit(functools.partial(step, CFG),
                         static_argnums=(0,))
        """)
        found = TraceHazardPass().run(mods)
        assert len(found) == 1
        assert "x.sum() > 0" in found[0].snippet

    def test_traced_kwonly_arg_still_tainted(self, tmp_path):
        """A partial that binds SOME keyword-only params leaves the
        rest as traced runtime kwargs — branching on one is a
        hazard."""
        mods = _scan_snippet(tmp_path, """
            import functools
            import jax

            def step(x, *, num_heads, mask):
                if num_heads > 4:      # partial-bound: static
                    x = x * 2
                if mask.sum() > 0:     # runtime kwarg: traced
                    return x
                return -x

            fn = jax.jit(functools.partial(step, num_heads=8))
        """)
        found = TraceHazardPass().run(mods)
        assert len(found) == 1
        assert "mask.sum()" in found[0].snippet

    def test_jittracker_wrapped_site_is_scanned(self, tmp_path):
        """jax.jit nested inside a tracker wrapper (the serving
        pattern) is still found."""
        mods = _scan_snippet(tmp_path, """
            import functools
            import jax

            def step(x):
                return bool(x)

            tracker = _JitTracker(jax.jit(functools.partial(step)),
                                  "decode_compiles")
        """)
        found = TraceHazardPass().run(mods)
        assert len(found) == 1 and "`bool()`" in found[0].message

    def test_same_def_two_static_configs_both_analyzed(self, tmp_path):
        """A def jitted twice with different static bindings must be
        analyzed under EACH config — a hazard traced in one config is
        not excused by being static in the other."""
        mods = _scan_snippet(tmp_path, """
            import jax

            def step(x, n):
                if n > 4:
                    return x * n
                return x

            fast = jax.jit(step, static_argnums=(1,))  # n static: clean
            slow = jax.jit(step)                       # n traced: hazard
        """)
        found = TraceHazardPass().run(mods)
        assert len(found) == 1 and "`if` on a traced value" in \
            found[0].message

    def test_flags_read_in_trace(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import jax
            from paddle_tpu.core import flags as _flags

            def step(x):
                if _flags.flag("use_pallas_layernorm"):
                    return x * 2
                return x

            fn = jax.jit(step)
        """)
        found = TraceHazardPass().run(mods)
        assert any(f.pass_id == "flags-in-trace" for f in found)

    def test_suppression_comment(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import jax

            def step(x):
                return int(x)  # tracecheck: ok

            fn = jax.jit(step)
        """)
        assert TraceHazardPass().run(mods) == []


# ---------------------------------------------------------------------------
# lock-discipline lint
# ---------------------------------------------------------------------------
_LOCK_RULES = {"fixture_mod.py": LockRule(
    locks=("LOCK",), roots=("_STATS",), alias_fns=("_stats_for",),
    alias_attrs=("stats",), guarded_classes=("_OpStats",))}


class TestLockDisciplineLint:
    def test_unguarded_registry_write(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import threading
            LOCK = threading.Lock()
            _STATS = {}

            def bad(k, v):
                _STATS[k] = _STATS.get(k, 0) + v

            def good(k, v):
                with LOCK:
                    _STATS[k] = _STATS.get(k, 0) + v
        """)
        found = LockDisciplinePass(_LOCK_RULES).run(mods)
        assert len(found) == 1
        assert "bad" in found[0].message and found[0].pass_id == \
            "lock-discipline"

    def test_mutating_call_and_alias(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import threading
            LOCK = threading.Lock()
            _STATS = {}

            def _stats_for(name):
                with LOCK:
                    return _STATS.setdefault(name, object())

            def bad_alias(name):
                s = _stats_for(name)
                s.calls = 1            # alias write, no lock

            def bad_mutator():
                _STATS.clear()         # mutating call, no lock

            def good(name):
                with LOCK:
                    s = _stats_for(name)
                    s.calls = 1
                    _STATS.pop(name, None)
        """)
        found = LockDisciplinePass(_LOCK_RULES).run(mods)
        where = sorted(f.message for f in found)
        assert len(found) == 2, where
        assert any("bad_alias" in m for m in where)
        assert any("bad_mutator" in m for m in where)

    def test_guarded_class_self_writes(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import threading
            LOCK = threading.Lock()

            class _OpStats:
                def __init__(self):
                    self.calls = 0     # construction: exempt

                def bad(self):
                    self.calls += 1

                def good(self):
                    with LOCK:
                        self.calls += 1
        """)
        found = LockDisciplinePass(_LOCK_RULES).run(mods)
        assert len(found) == 1 and "_OpStats.bad" in found[0].message

    def test_for_loop_alias_taint(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import threading
            LOCK = threading.Lock()
            _STATS = {}

            def bad_reset():
                for s in _STATS.values():
                    s.calls = 0

            def good_reset():
                with LOCK:
                    for s in _STATS.values():
                        s.calls = 0
        """)
        found = LockDisciplinePass(_LOCK_RULES).run(mods)
        assert len(found) == 1 and "bad_reset" in found[0].message


# ---------------------------------------------------------------------------
# engine-mutation lint
# ---------------------------------------------------------------------------
_ENGINE_RULE = EngineRule(
    mutators=("add_request", "step", "preempt", "_finish"),
    sanctioned={"sanctioned_mod.py": ("*",),
                "fixture_mod.py": ("GoodScheduler.",)})


class TestEngineMutationLint:
    def test_unsanctioned_call_flagged(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            class GoodScheduler:
                def schedule(self):
                    self.engine.step()

            class RogueThread:
                def run(self):
                    self.engine.add_request([1])
                    self.engine._chunk_budget = 1
        """)
        found = EngineMutationPass(_ENGINE_RULE).run(mods)
        msgs = [f.message for f in found]
        assert len(found) == 2, msgs
        assert all("RogueThread.run" in m for m in msgs)
        assert any(".add_request()" in m for m in msgs)
        assert any("attribute store" in m for m in msgs)

    def test_sanctioned_module_clean(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            def drive(eng):
                eng.add_request([1])
                eng.step()
        """, name="sanctioned_mod.py")
        assert EngineMutationPass(_ENGINE_RULE).run(mods) == []

    def test_unsanctioned_recovery_mutation_flags(self, tmp_path):
        """The REPO rule sanctions recovery's engine mutation ONLY in
        inference/resilience.py (and the frontend's supervision
        sites): a rogue module replaying the recovery moves —
        `_step_inner` retries, quarantine, counter restores — must
        still flag."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        mods = _scan_snippet(tmp_path, """
            class RogueRecovery:
                def heal(self, engine):
                    engine._step_no = 0
                    engine._quarantine_slot(0, "step")
                    self.engine._step_inner()
        """, name="rogue_recovery.py")
        found = EngineMutationPass(REPO_ENGINE_RULE).run(mods)
        msgs = sorted(f.message for f in found)
        assert len(found) == 3, msgs
        assert any("._quarantine_slot()" in m for m in msgs)
        assert any("._step_inner()" in m for m in msgs)
        assert any("attribute store" in m for m in msgs)
        assert all("RogueRecovery.heal" in m for m in msgs)

    def test_repo_rule_sanctions_resilience_module(self, tmp_path):
        """The same recovery-style mutation inside a module named like
        the sanctioned recovery site scans clean — the spec encodes
        'recovery mutates the engine between steps by design'."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        (tmp_path / "inference").mkdir()
        mods = _scan_snippet(tmp_path, """
            def recover_step(engine):
                engine._step_no = 0
                return engine._step_inner()
        """, name="inference/resilience.py")
        assert EngineMutationPass(REPO_ENGINE_RULE).run(mods) == []

    def test_unsanctioned_restore_mutation_flags(self, tmp_path):
        """The REPO rule sanctions durable-restore / watchdog engine
        mutation ONLY in inference/durability.py (and the frontend's
        supervision sites): a rogue module replaying the restore moves
        — executable handoff, watchdog abandonment, counter restores —
        must still flag."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        mods = _scan_snippet(tmp_path, """
            class RogueRestore:
                def resurrect(self, engine):
                    engine.adopt_executables(self.donor)
                    engine._abandon_inflight()
                    self.engine._step_no = 3
        """, name="rogue_restore.py")
        found = EngineMutationPass(REPO_ENGINE_RULE).run(mods)
        msgs = sorted(f.message for f in found)
        assert len(found) == 3, msgs
        assert any(".adopt_executables()" in m for m in msgs)
        assert any("._abandon_inflight()" in m for m in msgs)
        assert any("attribute store" in m for m in msgs)
        assert all("RogueRestore.resurrect" in m for m in msgs)

    def test_repo_rule_sanctions_durability_module(self, tmp_path):
        """The identical restore-style mutation inside the sanctioned
        durability module scans clean."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        (tmp_path / "inference").mkdir()
        mods = _scan_snippet(tmp_path, """
            def restore(engine, donor):
                engine.adopt_executables(donor)
                engine._step_no = 3
                engine._abandon_inflight()
        """, name="inference/durability.py")
        assert EngineMutationPass(REPO_ENGINE_RULE).run(mods) == []

    def test_rogue_weight_quant_fold_flags(self, tmp_path):
        """The serve_weights=int8 param fold (`_fold_weight_quant`) is
        a sanctioned construction-time engine mutation: a rogue module
        invoking it on a LIVE engine — the tempting bug being 'just
        re-quantize the tree after the weights moved' — must flag
        (re-folding a live tree silently re-traces every warm
        executable)."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        mods = _scan_snippet(tmp_path, """
            class RogueQuantizer:
                def densify(self, engine):
                    engine._fold_weight_quant()
                    self.engine._params = self.f32_tree
        """, name="rogue_quantizer.py")
        found = EngineMutationPass(REPO_ENGINE_RULE).run(mods)
        msgs = sorted(f.message for f in found)
        assert len(found) == 2, msgs
        assert any("._fold_weight_quant()" in m for m in msgs)
        assert any("attribute store" in m for m in msgs)
        assert all("RogueQuantizer.densify" in m for m in msgs)

    def test_repo_rule_sanctions_weight_quant_fold(self, tmp_path):
        """The identical fold inside the sanctioned serving module
        scans clean — the construction-time call site itself."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        (tmp_path / "inference").mkdir()
        mods = _scan_snippet(tmp_path, """
            def construct(engine):
                engine._fold_weight_quant()
        """, name="inference/serving.py")
        assert EngineMutationPass(REPO_ENGINE_RULE).run(mods) == []

    def test_rogue_flight_recorder_mutation_flags(self, tmp_path):
        """The REPO rule sanctions the flight recorder's engine READS
        only inside `FlightRecorder` in observability/flight.py: a
        rogue recorder that mutates the engine from its step hooks —
        the tempting bug being 'just retire the slow request from
        inside end_step' — must flag."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        mods = _scan_snippet(tmp_path, """
            class RogueRecorder:
                def end_step(self):
                    self.engine._finish(0, "evicted")
                    self.engine._step_no = 9

                def seal(self, engine):
                    engine.preempt(self.victim)
        """, name="rogue_recorder.py")
        found = EngineMutationPass(REPO_ENGINE_RULE).run(mods)
        msgs = sorted(f.message for f in found)
        assert len(found) == 3, msgs
        assert any("._finish()" in m for m in msgs)
        assert any(".preempt()" in m for m in msgs)
        assert any("attribute store" in m for m in msgs)
        assert all("RogueRecorder" in m for m in msgs)

    def test_repo_rule_sanctions_flight_recorder_reads(self, tmp_path):
        """The sanctioned twin: the same shapes of code inside
        `FlightRecorder` in observability/flight.py scan clean — the
        spec encodes 'the recorder may read (and is trusted) from
        inside the step'."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            class FlightRecorder:
                def end_step(self):
                    self.engine._finish(0, "evicted")
                    self.engine._step_no = 9
        """, name="observability/flight.py")
        assert EngineMutationPass(REPO_ENGINE_RULE).run(mods) == []

    def test_rogue_costmodel_mutation_flags(self, tmp_path):
        """The REPO rule sanctions the cost observatory's engine READS
        only inside `CostModel` in observability/costmodel.py: a rogue
        cost model that mutates the engine from its hooks — the
        tempting bug being 'just preempt the slot my prediction says
        is over budget from inside observe()' — must flag."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        mods = _scan_snippet(tmp_path, """
            class RogueCostModel:
                def observe(self, rec):
                    self.engine.preempt(self.victim)
                    self.engine._chunk_budget = 1

                def admission_ok(self, engine, req):
                    return engine._admit_one(req)
        """, name="rogue_costmodel.py")
        found = EngineMutationPass(REPO_ENGINE_RULE).run(mods)
        msgs = sorted(f.message for f in found)
        assert len(found) == 3, msgs
        assert any(".preempt()" in m for m in msgs)
        assert any("._admit_one()" in m for m in msgs)
        assert any("attribute store" in m for m in msgs)
        assert all("RogueCostModel" in m for m in msgs)

    def test_repo_rule_sanctions_costmodel_reads(self, tmp_path):
        """The sanctioned twin: the same shapes inside `CostModel` in
        observability/costmodel.py scan clean — the spec encodes 'the
        cost model may read (and is trusted) from inside the step'."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            class CostModel:
                def observe(self, rec):
                    self.engine.preempt(self.victim)
                    self.engine._chunk_budget = 1
        """, name="observability/costmodel.py")
        assert EngineMutationPass(REPO_ENGINE_RULE).run(mods) == []

    def test_costmodel_lock_discipline_enforced(self, tmp_path):
        """The cost observatory's calibration table is in the lock-
        discipline spec: an unguarded `_calib` mutation in a module
        named like costmodel.py flags, the locked form scans clean."""
        from paddle_tpu.analysis import REPO_LOCK_RULES
        from paddle_tpu.analysis.passes import LockDisciplinePass

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            class CostModel:
                def bad_update(self, fn, v):
                    self._calib[fn] = v

                def good_update(self, fn, v):
                    with _lock:
                        self._calib[fn] = v
        """, name="observability/costmodel.py")
        found = LockDisciplinePass(REPO_LOCK_RULES).run(mods)
        assert len(found) == 1, [f.message for f in found]
        assert "bad_update" in found[0].message

    def test_flight_lock_discipline_enforced(self, tmp_path):
        """The flight-recorder ring is in the lock-discipline spec: an
        unguarded ring mutation in a module named like flight.py
        flags, the locked form scans clean."""
        from paddle_tpu.analysis import REPO_LOCK_RULES
        from paddle_tpu.analysis.passes import LockDisciplinePass

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            class FlightRecorder:
                def bad_push(self, rec):
                    self._ring.append(rec)

                def good_push(self, rec):
                    with _lock:
                        self._ring.append(rec)
        """, name="observability/flight.py")
        found = LockDisciplinePass(REPO_LOCK_RULES).run(mods)
        assert len(found) == 1, [f.message for f in found]
        assert "bad_push" in found[0].message
        assert ".append()" in found[0].message

    def test_rogue_alert_evaluator_mutation_flags(self, tmp_path):
        """The REPO rule sanctions the alert evaluator's engine READS
        only inside `AlertEngine` in observability/alerts.py: a rogue
        evaluator that mutates the engine from evaluate() — the
        tempting bug being 'just preempt the request burning the
        budget' — must flag."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        mods = _scan_snippet(tmp_path, """
            class RogueAlerts:
                def evaluate(self):
                    self.engine.preempt(self.worst)
                    self.engine._chunk_budget = 1

                def shed(self, engine):
                    engine.evict(0)
        """, name="rogue_alerts.py")
        found = EngineMutationPass(REPO_ENGINE_RULE).run(mods)
        msgs = sorted(f.message for f in found)
        assert len(found) == 3, msgs
        assert any(".preempt()" in m for m in msgs)
        assert any(".evict()" in m for m in msgs)
        assert any("attribute store" in m for m in msgs)
        assert all("RogueAlerts" in m for m in msgs)

    def test_repo_rule_sanctions_alert_engine_reads(self, tmp_path):
        """The sanctioned twin: the same shapes inside `AlertEngine`
        in observability/alerts.py scan clean — the spec encodes 'the
        evaluator may read (and is trusted) between steps'."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            class AlertEngine:
                def evaluate(self):
                    self.engine.preempt(self.worst)
                    self.engine._chunk_budget = 1
        """, name="observability/alerts.py")
        assert EngineMutationPass(REPO_ENGINE_RULE).run(mods) == []

    def test_alerts_lock_discipline_enforced(self, tmp_path):
        """The alert engine's cross-thread state table and transitions
        list are in the lock-discipline spec: unguarded mutations in a
        module named like alerts.py flag, the locked forms scan
        clean."""
        from paddle_tpu.analysis import REPO_LOCK_RULES
        from paddle_tpu.analysis.passes import LockDisciplinePass

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            class AlertEngine:
                def bad_transition(self, e):
                    self._transitions.append(e)
                    self._state["r"] = e

                def good_transition(self, e):
                    with _lock:
                        self._transitions.append(e)
                        self._state["r"] = e
        """, name="observability/alerts.py")
        found = LockDisciplinePass(REPO_LOCK_RULES).run(mods)
        assert len(found) == 2, [f.message for f in found]
        assert all("bad_transition" in f.message for f in found)

    def test_rogue_profiler_mutation_flags(self, tmp_path):
        """The REPO rule sanctions the profiling plane's engine READS
        only inside `Profiler` in observability/profiling.py: a rogue
        profiler that mutates the engine from its hooks — the
        tempting bug being 'just preempt the slot whose dispatch
        keeps blocking longest' — must flag."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        mods = _scan_snippet(tmp_path, """
            class RogueProfiler:
                def observe(self, rec):
                    self.engine.preempt(self.slowest)
                    self.engine._chunk_budget = 1

                def throttle(self, engine):
                    engine.evict(0)
        """, name="rogue_profiler.py")
        found = EngineMutationPass(REPO_ENGINE_RULE).run(mods)
        msgs = sorted(f.message for f in found)
        assert len(found) == 3, msgs
        assert any(".preempt()" in m for m in msgs)
        assert any(".evict()" in m for m in msgs)
        assert any("attribute store" in m for m in msgs)
        assert all("RogueProfiler" in m for m in msgs)

    def test_repo_rule_sanctions_profiler_reads(self, tmp_path):
        """The sanctioned twin: the same shapes inside `Profiler` in
        observability/profiling.py scan clean — the spec encodes 'the
        profiler may read (and block on) engine state from inside the
        step, and the capture-arming site runs between steps'."""
        from paddle_tpu.analysis import REPO_ENGINE_RULE

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            class Profiler:
                def observe(self, rec):
                    self.engine.preempt(self.slowest)
                    self.engine._chunk_budget = 1
        """, name="observability/profiling.py")
        assert EngineMutationPass(REPO_ENGINE_RULE).run(mods) == []

    def test_profiling_lock_discipline_enforced(self, tmp_path):
        """The profiling plane's capture state and device-time table
        are in the lock-discipline spec: unguarded mutations in a
        module named like profiling.py flag, the locked forms scan
        clean."""
        from paddle_tpu.analysis import REPO_LOCK_RULES
        from paddle_tpu.analysis.passes import LockDisciplinePass

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            class Profiler:
                def bad_arm(self, dev, mfu):
                    self._device_s["decode"] = dev
                    self._mfu.update(mfu)

                def good_arm(self, dev, mfu):
                    with _lock:
                        self._device_s["decode"] = dev
                        self._mfu.update(mfu)
        """, name="observability/profiling.py")
        found = LockDisciplinePass(REPO_LOCK_RULES).run(mods)
        assert len(found) == 2, [f.message for f in found]
        assert all("bad_arm" in f.message for f in found)

    def test_opsserver_lock_discipline_enforced(self, tmp_path):
        """The ops registry (engines/frontends/server handle) is in
        the lock-discipline spec: unguarded registration in a module
        named like opsserver.py flags, the locked form scans clean."""
        from paddle_tpu.analysis import REPO_LOCK_RULES
        from paddle_tpu.analysis.passes import LockDisciplinePass

        (tmp_path / "observability").mkdir()
        mods = _scan_snippet(tmp_path, """
            def bad_register(engine):
                _ENGINES[engine._engine_id] = engine

            def good_register(engine):
                with _lock:
                    _ENGINES[engine._engine_id] = engine
        """, name="observability/opsserver.py")
        found = LockDisciplinePass(REPO_LOCK_RULES).run(mods)
        assert len(found) == 1, [f.message for f in found]
        assert "bad_register" in found[0].message


# ---------------------------------------------------------------------------
# donation analysis
# ---------------------------------------------------------------------------
class TestDonationLint:
    def test_missing_pool_donation_flagged(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import functools
            import jax

            def step(params, kv, tokens):
                return kv, tokens

            bad = jax.jit(functools.partial(step), donate_argnums=(2,))
            worse = jax.jit(step)
            good = jax.jit(step, donate_argnums=(1,))
        """)
        found = DonationPass().run(mods)
        msgs = sorted(f.message for f in found)
        # bad donates the tokens instead; worse donates nothing
        assert len(found) == 2, msgs
        assert all("`kv` (argnum 1)" in m for m in msgs)
        assert sum("no donate_argnums at all" in m for m in msgs) == 1

    def test_pool_found_by_name_wherever_it_sits(self, tmp_path):
        """The pool is ONE argument whatever it holds (an int8 pool's
        quant scales travel inside it), so "pages donated, scales
        copied" cannot be written any more; what can still go wrong is
        donating the wrong position — prefill takes the pool fifth.
        Functions that take no pool (the scale reset works on bare
        scale arrays) are not this pass's business."""
        mods = _scan_snippet(tmp_path, """
            import functools
            import jax

            def prefill(params, ids, true_len, bt_row, kv, key):
                return kv, ids

            bad = jax.jit(functools.partial(prefill),
                          donate_argnums=(1,))
            good = jax.jit(prefill, donate_argnums=(4,))

            def reset(k_scales, v_scales, idx):
                return k_scales, v_scales

            reset_some = jax.jit(reset, donate_argnums=(0,))
            reset_all = jax.jit(reset, donate_argnums=(0, 1))
        """)
        found = DonationPass().run(mods)
        msgs = sorted(f.message for f in found)
        assert len(found) == 1, msgs
        assert "`prefill`" in msgs[0] and "`kv` (argnum 4)" in msgs[0]

    def test_tracker_owned_jit_site(self, tmp_path):
        """The serving pattern after the single-source-of-truth
        refactor: _JitTracker(callable, key, donate_argnums=...) IS
        the jit site — donation coverage and trace hazards are checked
        through the tracker's own donate tuple."""
        mods = _scan_snippet(tmp_path, """
            import functools

            def step(params, kv, tokens):
                if tokens.sum() > 0:
                    return kv, tokens
                return kv, -tokens

            good = _JitTracker(functools.partial(step), "decode_compiles",
                               donate_argnums=(1,), site="good")
            bad = _JitTracker(functools.partial(step), "decode_compiles",
                              donate_argnums=(), site="bad")
        """)
        donation = DonationPass().run(mods)
        assert len(donation) == 1 and "`kv`" in donation[0].message
        hazards = TraceHazardPass().run(mods)
        assert len(hazards) == 1 and "tokens.sum()" in hazards[0].snippet

    def test_mesh_wrapped_step_sharded_pool_not_donated(self, tmp_path):
        """The multichip serving pattern (FLAGS_serve_mesh): the ragged
        step is partial-bound with a ``mesh=`` kwarg and its pool
        operand holds mesh-sharded arrays — donation coverage must
        see straight through the wrapper, because an undonated SHARDED
        pool is worse than the single-chip bug (every chip copies its
        page shard every step).  Known-bad fixture: the mesh step
        donates its tokens but not the pool → finding; the good one
        donating the pool is clean."""
        mods = _scan_snippet(tmp_path, """
            import functools

            MESH = object()

            def ragged_step(params, kv, tokens, mesh=None):
                return kv, tokens

            bad = _JitTracker(
                functools.partial(ragged_step, mesh=MESH),
                "ragged_compiles", donate_argnums=(2,),
                site="bad mesh step")
            good = _JitTracker(
                functools.partial(ragged_step, mesh=MESH),
                "ragged_compiles", donate_argnums=(1,),
                site="good mesh step")
        """)
        found = DonationPass().run(mods)
        assert len(found) == 1, [f.message for f in found]
        assert "`kv`" in found[0].message

    def test_partial_positional_shift(self, tmp_path):
        """Positionally-bound partial args shift the donate indices."""
        mods = _scan_snippet(tmp_path, """
            import functools
            import jax

            def step(params, kv, tokens):
                return kv, tokens

            PARAMS = {}
            good = jax.jit(functools.partial(step, PARAMS),
                           donate_argnums=(0,))
            bad = jax.jit(functools.partial(step, PARAMS),
                          donate_argnums=(1,))
        """)
        found = DonationPass().run(mods)
        assert len(found) == 1
        assert "`kv` (argnum 0)" in found[0].message


# ---------------------------------------------------------------------------
# fleet-trace lint
# ---------------------------------------------------------------------------
# fixture rule: every file is "fleet plane" so tmp-path snippets scan
_ANY_FLEET = FleetTraceRule(path_markers=("",))


class TestFleetTraceLint:
    def test_client_leg_without_trace_flags(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import urllib.request

            def fetch_result(url, timeout):
                with urllib.request.urlopen(url, timeout=timeout) as r:
                    return r.read()
        """)
        found = FleetTracePass(_ANY_FLEET).run(mods)
        assert len(found) == 1
        assert found[0].pass_id == "fleet-trace"
        assert "HTTP client leg `fetch_result`" in found[0].message

    def test_handler_without_trace_flags(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            class Handler:
                def do_GET(self):
                    self._send_json({"ok": True})

                def _send_json(self, doc):
                    pass
        """)
        found = FleetTracePass(_ANY_FLEET).run(mods)
        assert len(found) == 1
        assert "HTTP handler `Handler.do_GET`" in found[0].message

    def test_propagating_sites_are_clean(self, tmp_path):
        """Direct TRACE_HEADER use, the literal header string, and a
        handler whose helper reads the header (the call-closure walk)
        all count as carrying the trace."""
        mods = _scan_snippet(tmp_path, """
            import urllib.request
            from paddle_tpu.observability import fleettrace

            def generate(url, trace):
                req = urllib.request.Request(
                    url, headers={fleettrace.TRACE_HEADER: trace})
                return urllib.request.urlopen(req)

            def resume(url, trace):
                req = urllib.request.Request(
                    url, headers={"x-paddle-trace": trace})
                return urllib.request.urlopen(req)

            class Handler:
                def do_POST(self):
                    self._generate(self._trace_in())

                def _trace_in(self):
                    return self.headers.get(fleettrace.TRACE_HEADER)

                def _generate(self, trace):
                    pass
        """)
        found = FleetTracePass(_ANY_FLEET).run(mods)
        assert found == [], [f.render() for f in found]

    def test_allowlist_is_exact_qualname(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import urllib.request

            def _get_json(url, timeout):
                with urllib.request.urlopen(url, timeout=timeout) as r:
                    return r.read()

            class ReplicaHandle:
                def poll(self):
                    return urllib.request.urlopen(self.url)

            class Other:
                def poll(self):
                    return urllib.request.urlopen(self.url)
        """)
        rule = FleetTraceRule(path_markers=("",),
                              allowlist=("_get_json",
                                         "ReplicaHandle.poll"))
        found = FleetTracePass(rule).run(mods)
        assert len(found) == 1, [f.render() for f in found]
        assert "`Other.poll`" in found[0].message

    def test_scope_is_fleet_only(self, tmp_path):
        """The default rule only scans the fleet plane: the same bad
        client leg in a non-fleet module is out of scope."""
        src = """
            import urllib.request

            def fetch_result(url):
                return urllib.request.urlopen(url)
        """
        mods = _scan_snippet(tmp_path, src)  # relpath: fixture_mod.py
        assert FleetTracePass(FleetTraceRule()).run(mods) == []
        assert len(FleetTracePass(_ANY_FLEET).run(mods)) == 1


# ---------------------------------------------------------------------------
# the repo gate + baseline workflow
# ---------------------------------------------------------------------------
class TestRepoGate:
    def test_repo_targets_scan_clean(self):
        """The acceptance bar: inference/, observability/ and
        core/dispatch.py carry zero unbaselined findings (the shipped
        baseline is empty, so this asserts zero findings outright)."""
        findings = run_tracecheck(root=REPO)
        baseline = load_baseline(
            os.path.join(REPO, "tools", "tracecheck_baseline.json"))
        new, _old = split_baselined(findings, baseline)
        assert new == [], "\n".join(f.render() for f in new)

    def test_baseline_roundtrip_and_resurface(self, tmp_path):
        mods = _scan_snippet(tmp_path, """
            import jax

            def step(x):
                return int(x)

            fn = jax.jit(step)
        """)
        found = run_passes(mods)
        assert found, "fixture must produce findings"
        bl_path = str(tmp_path / "baseline.json")
        write_baseline(bl_path, found)
        # grandfathered: same findings all filter out
        new, old = split_baselined(found, load_baseline(bl_path))
        assert new == [] and len(old) == len(found)
        # the offending line changes -> the finding resurfaces even at
        # the same location (content fingerprint, not line number)
        p = tmp_path / "fixture_mod.py"
        p.write_text(p.read_text().replace("int(x)", "int(x * 3)"))
        refound = run_passes(scan_paths([str(p)], str(tmp_path)))
        new2, _ = split_baselined(refound, load_baseline(bl_path))
        assert len(new2) == len(refound) > 0

    def test_duplicated_bad_line_gets_fresh_fingerprint(self, tmp_path):
        """A NEW copy of a baselined bad line (identical text, same
        file) must surface: occurrence ordinals disambiguate the
        content fingerprint."""
        src = """
            import jax

            def step(x):
                return int(x)

            fn = jax.jit(step)
        """
        mods = _scan_snippet(tmp_path, src)
        found = run_passes(mods)
        bl_path = str(tmp_path / "baseline.json")
        write_baseline(bl_path, found)
        # duplicate the offending pattern in a second jitted fn
        p = tmp_path / "fixture_mod.py"
        p.write_text(p.read_text() + textwrap.dedent("""
            def step2(x):
                return int(x)

            fn2 = jax.jit(step2)
        """))
        refound = run_passes(scan_paths([str(p)], str(tmp_path)))
        assert len(refound) == 2
        new, old = split_baselined(refound, load_baseline(bl_path))
        assert len(old) == 1 and len(new) == 1  # the copy surfaces


# ---------------------------------------------------------------------------
# runtime sanitizer
# ---------------------------------------------------------------------------
def _tiny_model():
    from paddle_tpu.models.gpt import GPT, GPTConfig

    cfg = GPTConfig(vocab_size=89, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


def _tiny_engine(model=None, **kw):
    from paddle_tpu.inference.serving import DecodeEngine

    return DecodeEngine(model or _tiny_model(), max_batch_size=2,
                        max_seq_len=64, **kw)


@pytest.fixture
def sanitize_flag():
    from paddle_tpu.core import flags as _flags

    prior = bool(_flags.flag("sanitize"))
    paddle_tpu.set_flags({"sanitize": True})
    sanitizer.reset()
    yield sanitizer.get()
    paddle_tpu.set_flags({"sanitize": prior})
    sanitizer.reset()


class TestSanitizer:
    def test_clean_generate_run(self, sanitize_flag):
        """A short DecodeEngine.generate under FLAGS_sanitize=1: zero
        findings, pool audited every step, one host sync per step, and
        the tokens match the unsanitized run bit for bit."""
        model = _tiny_model()
        paddle_tpu.set_flags({"sanitize": False})
        reference = _tiny_engine(model).generate(
            [[1, 2, 3, 4, 5], [7, 8]], max_new_tokens=6)
        paddle_tpu.set_flags({"sanitize": True})
        sanitizer.reset()
        eng = _tiny_engine(model)
        outs = eng.generate([[1, 2, 3, 4, 5], [7, 8]], max_new_tokens=6)
        assert outs == reference
        rep = sanitize_flag.report()
        assert rep["steps"] > 0
        assert rep["warm_retraces"] == 0
        assert rep["host_syncs"] == rep["steps"]  # ONE sync per step
        assert rep["tombstoned_buffers"] > 0      # donation was tracked

    def test_seeded_use_after_donate_raises(self, sanitize_flag):
        """Hold the pre-step page pool reference, step, then feed the
        stale buffer back — the detector names the donation site.  On
        CPU, XLA ignores donation entirely, so only the sanitizer can
        catch this class before TPU hardware does."""
        eng = _tiny_engine()
        stale = eng._kv.k
        eng.add_request([1, 2, 3], max_new_tokens=4)
        eng.run()
        site = sanitizer.get().donation_site(stale)
        assert site is not None and "_gpt_" in site
        # the raw host access raises jax's own deleted-buffer error
        with pytest.raises(RuntimeError):
            np.asarray(stale)
        # feeding it back into a tracked executable raises OUR error,
        # naming the donation site
        with pytest.raises(sanitizer.UseAfterDonateError) as ei:
            eng._decode_fn(stale) if eng._decode_fn else \
                eng._mixed_fn(stale)
        assert site in str(ei.value)

    def test_no_site_attribution_without_sanitizer(self):
        """The control: without FLAGS_sanitize nothing is tombstoned —
        a stale read either works silently (backends that ignore
        donation) or raises jax's bare deleted-array error with no
        donation site, which is exactly the debugging gap the
        sanitizer closes."""
        eng = _tiny_engine()
        stale = eng._kv.k
        eng.add_request([1, 2, 3], max_new_tokens=4)
        eng.run()
        assert sanitizer.get().donation_site(stale) is None

    def test_lock_order_cycle_raises(self, sanitize_flag):
        import threading

        a = sanitizer.TrackedLock(threading.Lock(), "fixture.A")
        b = sanitizer.TrackedLock(threading.Lock(), "fixture.B")
        with a:
            with b:
                pass
        with pytest.raises(sanitizer.LockOrderError) as ei:
            with b:
                with a:
                    pass
        assert "fixture.A" in str(ei.value) and \
            "fixture.B" in str(ei.value)
        # the cycle-closing edge is NOT recorded: the same inverted
        # order must raise again (not sail past into a real deadlock)
        with pytest.raises(sanitizer.LockOrderError):
            with b:
                with a:
                    pass
        # the thread's held-stack survives the failed acquisitions
        with a:
            with b:
                pass

    def test_flag_flip_mid_hold_does_not_poison_stack(self, sanitize_flag):
        """Disabling the sanitizer while a lock is held must still pop
        the held-stack entry on release — otherwise a phantom entry
        haunts every later sanitized run on this thread with bogus
        edges."""
        import threading

        a = sanitizer.TrackedLock(threading.Lock(), "fixture.flip")
        b = sanitizer.TrackedLock(threading.Lock(), "fixture.other")
        a.acquire()
        paddle_tpu.set_flags({"sanitize": False})
        a.release()  # bookkeeping must run even while disabled
        paddle_tpu.set_flags({"sanitize": True})
        with b:
            pass
        assert sanitizer.get().lock_edges == {}  # no phantom flip->other

    def test_failed_nonblocking_acquire_not_recorded_as_held(
            self, sanitize_flag):
        import threading

        inner = threading.Lock()
        a = sanitizer.TrackedLock(inner, "fixture.busy")
        b = sanitizer.TrackedLock(threading.Lock(), "fixture.free")
        inner.acquire()  # someone else holds it
        try:
            assert a.acquire(blocking=False) is False
        finally:
            inner.release()
        with b:
            pass
        assert sanitizer.get().lock_edges == {}  # busy was never held

    def test_reentrant_rlock_is_not_a_cycle(self, sanitize_flag):
        import threading

        a = sanitizer.TrackedLock(threading.RLock(), "fixture.R")
        with a:
            with a:
                pass
        assert sanitizer.get().lock_edges == {}

    def test_plain_lock_self_deadlock_raises(self, sanitize_flag):
        """Re-acquiring a NON-reentrant Lock on the same thread blocks
        forever — the sanitizer must raise instead of letting the
        simplest deadlock shape through."""
        import threading

        a = sanitizer.TrackedLock(threading.Lock(), "fixture.plain")
        with a:
            with pytest.raises(sanitizer.LockOrderError,
                               match="self-deadlock"):
                a.acquire()
        # the held stack unwound cleanly: the lock is reusable
        with a:
            pass

    def test_warm_retrace_raises(self, sanitize_flag):
        """A jitted step whose operand dtype flaps after warmup must
        raise WarmRetraceError instead of counting."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.inference.serving import _JitTracker

        fn = _JitTracker(jax.jit(lambda x: x * 2), "decode_compiles",
                         site="fixture step")
        fn(jnp.ones((2,), jnp.float32))
        fn(jnp.ones((2,), jnp.float32))  # warm: same signature
        with pytest.raises(sanitizer.WarmRetraceError) as ei:
            fn(jnp.ones((2,), jnp.int32))  # dtype flap -> retrace
        assert "fixture step" in str(ei.value)

    def test_telemetry_locks_are_tracked(self, sanitize_flag):
        """The designated locks really are TrackedLock instances — the
        sanitizer can see every acquisition."""
        from paddle_tpu import observability as obs
        from paddle_tpu.core import dispatch
        from paddle_tpu.observability import tracing

        for lock in (obs.LOCK, dispatch._STATS_LOCK,
                     dispatch._CACHE_LOCK, tracing._lock):
            assert isinstance(lock, sanitizer.TrackedLock), lock
        names = {obs.LOCK.name, dispatch._STATS_LOCK.name,
                 dispatch._CACHE_LOCK.name, tracing._lock.name}
        assert len(names) == 4  # distinct order-graph nodes
