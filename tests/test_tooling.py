"""Build-system / CI tooling (reference: paddle_build.sh + tools/):
packaging metadata, op micro-bench harness, and the perf regression gate.

Bench smokes each spawn a fresh process and compile a full engine
stack (~10-30s apiece); the tier-1 `-m 'not slow'` run keeps the cheap
representatives (eager, decode, cost, telemetry, tracecheck) and marks
the rest ``slow`` — their machinery is pinned by dedicated tier-1
suites (test_spec_decode, test_chunked_prefill, test_prefix_cache,
test_frontend, test_resilience, test_durability, test_flight,
test_kv_quant), so the smokes' marginal tier-1 value is the bench
SCRIPT not rotting, which the slow lane still covers."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_setup_metadata_parses():
    r = subprocess.run([sys.executable, "setup.py", "--name"], cwd=REPO,
                       capture_output=True, text=True, env=ENV, timeout=120)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == "paddle-tpu"


def test_op_bench_and_gate(tmp_path):
    base = str(tmp_path / "base.json")
    r = subprocess.run(
        [sys.executable, "tools/op_bench.py", "--iters", "2",
         "--ops", "matmul,elementwise_add", "--out", base],
        cwd=REPO, capture_output=True, text=True, env=ENV, timeout=300)
    assert r.returncode == 0, r.stderr
    with open(base) as f:
        data = json.load(f)
    assert {x["op"] for x in data["results"]} == {"matmul",
                                                  "elementwise_add"}

    # gate passes against itself...
    ok = subprocess.run(
        [sys.executable, "tools/check_op_benchmark_result.py", base, base],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert ok.returncode == 0, ok.stdout
    # ...and fails on a fabricated 10x regression
    data["results"][0]["mean_us"] *= 10
    worse = str(tmp_path / "worse.json")
    with open(worse, "w") as f:
        json.dump(data, f)
    bad = subprocess.run(
        [sys.executable, "tools/check_op_benchmark_result.py", base, worse],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert bad.returncode == 1 and "FAIL" in bad.stdout

    # dropped coverage fails; empty results refuse to pass
    data["results"] = data["results"][1:]
    dropped = str(tmp_path / "dropped.json")
    with open(dropped, "w") as f:
        json.dump(data, f)
    miss = subprocess.run(
        [sys.executable, "tools/check_op_benchmark_result.py", base,
         dropped], cwd=REPO, capture_output=True, text=True, timeout=60)
    assert miss.returncode == 1 and "[missing]" in miss.stdout
    empty = str(tmp_path / "empty.json")
    with open(empty, "w") as f:
        json.dump({"results": []}, f)
    e = subprocess.run(
        [sys.executable, "tools/check_op_benchmark_result.py", base,
         empty], cwd=REPO, capture_output=True, text=True, timeout=60)
    assert e.returncode == 2


@pytest.mark.slow
def test_bench_eager_smoke(tmp_path):
    """tools/bench_eager.py --smoke runs end-to-end: the eager dispatch
    bench can't rot.  Asserts the emitted JSON shape and that the cached
    leg reports a warm hit-rate of ~100% with zero steady-state
    retraces (the ISSUE-1 acceptance signal, at smoke scale)."""
    out = str(tmp_path / "bench_eager.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_eager.py", "--smoke", "--out",
         out], cwd=REPO, capture_output=True, text=True, env=ENV,
        timeout=300)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        data = json.load(f)
    assert set(data["configs"]) == {"mlp", "gpt_block"}
    for name, cfg in data["configs"].items():
        for leg in ("cached", "uncached"):
            for field in ("us_per_op", "ops_per_s", "dispatches",
                          "hit_rate", "retraces", "wall_s"):
                assert field in cfg[leg], (name, leg, field)
        assert cfg["cached"]["dispatches"] > 0
        assert cfg["cached"]["hit_rate"] > 0.99, (
            name, cfg["cached"])
        assert cfg["cached"]["retraces"] == 0
        assert cfg["uncached"]["bypasses"] == \
            cfg["uncached"]["dispatches"]
        assert cfg["per_op_speedup"] > 0


@pytest.mark.slow
def test_bench_decode_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_decode.py runs end-to-end: the decode
    bench can't rot.  Asserts the emitted JSON shape, greedy parity
    across all three decode paths, and the serving loop's steady-state
    contract (zero retraces after warmup) at smoke scale."""
    out = str(tmp_path / "bench_decode.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_decode.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    assert data["parity"] is True
    legs = data["legs"]
    assert set(legs) == {"concat", "prealloc", "paged_engine"}
    for leg in legs.values():
        assert leg["tokens_per_s"] > 0 and leg["wall_s"] > 0
    assert legs["prealloc"]["speedup_vs_concat"] > 0
    assert legs["paged_engine"]["speedup_vs_concat"] > 0
    tel = legs["paged_engine"]["telemetry"]
    assert tel["retraces_after_warmup"] == 0
    assert tel["steps"] > 0
    assert 0 < tel["batch_occupancy"] <= 1
    assert 0 < tel["kv_block_utilization"] <= 1
    assert data["page_size_sweep"], "page-size sweep must record rows"
    # the embedded observability snapshot records latency DISTRIBUTIONS
    snap = data["observability"]
    ttft = snap["paddle_request_ttft_seconds"]["series"][0]
    assert ttft["count"] > 0 and sum(ttft["counts"]) == ttft["count"]
    assert snap["paddle_request_tpot_seconds"]["series"][0]["count"] > 0


@pytest.mark.slow
def test_bench_spec_decode_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_spec_decode.py runs end-to-end: the
    speculative-decode bench can't rot.  Asserts the emitted JSON shape,
    greedy token parity of every speculative leg against the baseline
    engine, acceptance-rate telemetry, and zero warm retraces on the
    verify executable at smoke scale."""
    out = str(tmp_path / "bench_spec.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_spec_decode.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    assert data["parity"] is True
    assert data["drafter"] == "prompt_lookup"
    legs = data["legs"]
    assert "engine" in legs and legs["engine"]["tokens_per_s"] > 0
    spec_legs = [v for k, v in legs.items() if k.startswith("spec_k")]
    assert spec_legs, "speculative legs must record rows"
    for leg in spec_legs:
        assert leg["tokens_per_s"] > 0 and leg["wall_s"] > 0
        assert 0 <= leg["acceptance_rate"] <= 1
        assert leg["mean_accepted_per_step"] >= 1
        assert leg["retraces_after_warmup"] == 0
        assert leg["draft_time_s"] >= 0 and leg["verify_time_s"] > 0
    # per-leg observability snapshots: every leg records TTFT/TPOT
    # distributions, not just aggregate throughput
    snaps = data["observability"]
    assert set(snaps) == set(legs)
    for name, snap in snaps.items():
        assert snap["paddle_request_ttft_seconds"]["series"][0][
            "count"] > 0, name
        assert snap["paddle_request_tpot_seconds"]["series"][0][
            "count"] > 0, name


@pytest.mark.slow
def test_bench_ragged_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_ragged.py runs end-to-end: the
    unified-ragged-step bench can't rot.  Asserts the emitted JSON
    shape, greedy parity of every leg against the legacy engine, the
    ONE-step-executable contract on the ragged legs (counter-asserted,
    zero retraces), a nonzero MEASURED mixed-batch MFU, and the
    trajectory-facing summary scalars."""
    out = str(tmp_path / "bench_ragged.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_ragged.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    assert data["parity"] is True
    legs = data["legs"]
    assert set(legs) == {"legacy_mixed", "ragged_mixed",
                         "spec_fixed_legacy", "spec_fixed_ragged",
                         "spec_adaptive_ragged"}
    for name, leg in legs.items():
        assert leg["tokens_per_s"] > 0 and leg["wall_s"] > 0, name
        assert leg["warmup_s"] > 0, name
        assert leg["step_compiles_timed"] == 0, name  # steady state
        assert leg["retraces_after_warmup"] == 0, name
    # the unification claim: ONE step executable on every ragged leg
    for name in ("ragged_mixed", "spec_fixed_ragged",
                 "spec_adaptive_ragged"):
        assert legs[name]["step_executables"] == 1, name
        assert legs[name]["ragged_retraces"] == 0, name
    assert legs["legacy_mixed"]["step_executables"] > 1
    for name in ("spec_fixed_ragged", "spec_adaptive_ragged"):
        assert 0 <= legs[name]["acceptance_rate"] <= 1
    s = data["summary"]
    assert s["step_executables_ragged"] == 1
    assert s["mfu_measured_ragged"] > 0  # paddle_phase_mfu_measured
    assert s["parity"] == 1.0
    assert s["tokens_per_s_spec_adaptive"] > 0


@pytest.mark.slow
def test_bench_sharded_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_sharded.py runs end-to-end: the
    MULTICHIP_serving leg can't rot.  Asserts the emitted JSON shape,
    greedy parity of every sharded leg (mp=2, mp=4, mp=2+spec) vs the
    single-chip engine, the one-executable/zero-retrace contract under
    the mesh, the serve_mesh-off leg bit-exact with identical
    counters, collective bytes nonzero exactly on sharded legs, a
    recorded chip-skew probe, and the MULTICHIP artifact's rc=0."""
    out = str(tmp_path / "bench_sharded.json")
    mc = str(tmp_path / "multichip_serving.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_sharded.py", "--out", out,
         "--multichip-out", mc],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    assert data["parity"] is True
    assert data["n_devices"] >= 2
    legs = data["legs"]
    assert {"single_chip", "mesh_off", "mp2", "mp2_spec",
            "single_spec"} <= set(legs)
    for name, leg in legs.items():
        assert leg["tokens_per_s"] > 0 and leg["wall_s"] > 0, name
        assert leg["step_executables"] == 1, name
        assert leg["step_compiles_timed"] == 0, name  # steady state
        assert leg["ragged_retraces"] == 0, name
    for name in [n for n in legs if n.startswith("mp")]:
        assert legs[name]["collective_bytes"] > 0, name
        assert legs[name]["mesh_devices"] > 1, name
    assert legs["single_chip"]["collective_bytes"] == 0.0
    assert legs["mp2"]["chip_skew_max_s"] >= 0.0
    s = data["summary"]
    assert s["parity"] == 1.0
    assert s["mesh_off_bit_exact"] == 1.0
    assert s["step_executables_mp2"] == 1
    assert s["ragged_retraces_mp2"] == 0
    with open(mc) as f:
        art = json.load(f)
    assert art["ok"] is True and art["rc"] == 0
    assert art["skipped"] is False
    assert "parity=OK" in art["tail"]


@pytest.mark.slow
def test_bench_prefill_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_prefill.py runs end-to-end: the
    chunked-prefill bench can't rot.  Asserts the emitted JSON shape,
    greedy parity between the legacy and chunked legs, the one-mixed-
    executable contract (no prefill bucket zoo, zero warm retraces),
    and that the chunked leg never stalls decodes while legacy does —
    all at smoke scale (latency RATIOS are asserted only at full
    scale; smoke shapes are too noise-dominated to pin them)."""
    out = str(tmp_path / "bench_prefill.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_prefill.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    assert data["parity"] is True
    legs = data["legs"]
    assert set(legs) == {"legacy", "chunked"}
    for leg in legs.values():
        inter = leg["interference"]
        assert inter["baseline_step_ms_p50"] > 0
        assert inter["max_step_ms_during_admission"] > 0
        st = leg["staggered"]
        assert st["ttft_mean_s"] > 0 and st["serve_steps"] > 0
        assert st["retraces_after_warmup"] == 0
    # the whole point: chunked admission never stalls running decodes,
    # and one mixed executable replaces the pow-2 prefill bucket zoo
    assert legs["legacy"]["interference"]["stalled_decode_steps"] > 0
    assert legs["chunked"]["interference"]["stalled_decode_steps"] == 0
    assert legs["chunked"]["staggered"]["mixed_compiles"] == 1
    assert legs["chunked"]["staggered"]["prefill_compiles"] == 0
    assert legs["chunked"]["staggered"]["prefill_chunks"] > 0
    assert legs["legacy"]["staggered"]["prefill_compiles"] > 0
    assert data["summary"]["zero_warm_retraces"] is True
    assert data["summary"]["one_mixed_executable"] is True
    # per-leg observability snapshots embed latency distributions,
    # including the chunk-size histogram on the chunked leg
    snaps = data["observability"]
    assert set(snaps) == {"legacy", "chunked"}
    for name, snap in snaps.items():
        assert snap["paddle_request_ttft_seconds"]["series"][0][
            "count"] > 0, name
    chunk_hist = snaps["chunked"]["paddle_prefill_chunk_tokens"]
    assert chunk_hist["series"][0]["count"] > 0
    # legacy never feeds chunks: its histogram stays empty
    assert snaps["legacy"]["paddle_prefill_chunk_tokens"]["series"] == []


@pytest.mark.slow
def test_bench_prefix_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_prefix.py runs end-to-end: the
    prefix-cache bench can't rot.  Asserts the emitted JSON shape,
    greedy parity between the cache-off and cache-on legs (including
    the eviction/reuse cycle), at least one prefix hit and one LRU
    eviction under pressure, zero warm retraces, and that hit requests
    prefilled strictly fewer tokens than the cache-off baseline —
    latency RATIOS are asserted only at full scale (smoke shapes are
    too noise-dominated to pin them)."""
    out = str(tmp_path / "bench_prefix.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_prefix.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    assert data["parity"] is True
    legs = data["legs"]
    assert set(legs) == {"off", "on"}
    for leg in legs.values():
        sh = leg["shared"]
        assert sh["ttft_cold_s"] > 0 and sh["ttft_hit_mean_s"] > 0
        assert sh["retraces_after_warmup"] == 0
        assert leg["eviction"]["retraces_after_warmup"] == 0
    # the whole point: cache-hit requests skip the shared prefix...
    on, off = legs["on"], legs["off"]
    assert on["shared"]["prefix_hits"] >= 1
    assert on["shared"]["tokens_prefilled_hit_mean"] < \
        off["shared"]["tokens_prefilled_hit_mean"]
    # ...the off leg never probes, and pressure really evicted (LRU)
    assert off["shared"]["prefix_hits"] == 0
    assert off["shared"]["prefix_misses"] == 0
    assert on["eviction"]["prefix_evictions"] >= 1
    assert data["summary"]["zero_warm_retraces"] is True
    # per-leg observability snapshots embed the prefix series on the
    # cache leg (hit counter + cached-tokens histogram)
    snaps = data["observability"]
    assert set(snaps) == {"off", "on"}
    hits = snaps["on"]["paddle_prefix_cache_page_hits_total"]["series"]
    assert hits and hits[0]["value"] >= 1
    hist = snaps["on"]["paddle_prefix_cached_tokens"]["series"][0]
    assert hist["count"] >= 1
    assert snaps["off"]["paddle_prefix_cache_page_hits_total"][
        "series"] == []


@pytest.mark.slow
def test_bench_slo_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_slo.py runs end-to-end: the SLO
    scheduling bench can't rot.  Asserts the emitted JSON shape,
    cross-leg greedy token parity (scheduling changes WHEN a request
    runs, never WHAT it emits), at least one preempt->resume cycle
    whose resumed request matched the never-preempted reference, at
    least one queued-deadline expiry, and zero warm retraces —
    goodput/latency RATIOS are asserted only at full scale (smoke
    shapes are too noise-dominated to pin them)."""
    out = str(tmp_path / "bench_slo.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_slo.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    assert data["parity"] is True
    legs = data["legs"]
    assert set(legs) == {"fifo", "slo"}
    # FIFO is the no-op oracle: strict arrival order, no preemption,
    # no expiry — and host-side scheduling never retraces either leg
    assert legs["fifo"]["preemptions"] == 0
    assert legs["fifo"]["deadline_expired"] == 0
    for leg in legs.values():
        assert leg["retraces_after_warmup"] == 0
        assert leg["offered"] == len(leg["finish_reasons"])
        assert 0 <= leg["met"] <= leg["offered"]
    # the point of the scheduler: pressure actually exercised it
    assert legs["slo"]["preemptions"] >= 1
    assert legs["slo"]["resumes"] >= 1
    assert legs["slo"]["deadline_expired"] >= 1
    assert data["summary"]["preempt_resume_parity"] is True
    assert data["summary"]["zero_warm_retraces"] is True
    assert legs["slo"]["finish_reasons"]["doomed"] == "deadline"
    # queue-pressure gauges surfaced in the embedded snapshot
    snap = data["observability"]["slo"]
    assert snap["paddle_sched_preemptions_total"]["series"][0][
        "value"] >= 1
    assert "paddle_queue_depth" in snap


@pytest.mark.slow
def test_bench_chaos_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_chaos.py runs end-to-end: the
    fault-injection bench can't rot.  Asserts the emitted JSON shape
    and the robustness acceptance bar at smoke scale: zero request
    loss under the chaos schedule, greedy parity of every normally-
    finished request vs the clean leg, >=1 same-step retry, >=1
    quarantine (finish_reason="fault"), >=1 full engine recovery, a
    leak-free pool in both legs, and an injection-free clean leg with
    zero warm retraces (latency RATIOS are asserted only at full
    scale)."""
    out = str(tmp_path / "bench_chaos.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_chaos.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    s = data["summary"]
    assert s["zero_request_loss"] is True
    assert s["parity"] is True
    assert s["step_retries"] >= 1
    assert s["quarantined"] >= 1
    assert s["recoveries"] >= 1
    assert s["pool_clean_both_legs"] is True
    assert s["clean_leg_injection_free"] is True
    legs = data["legs"]
    assert set(legs) == {"clean", "chaos"}
    # the poisoned request is the quarantine the bisect must find
    assert legs["chaos"]["finish_reasons"]["poisoned"] == "fault"
    assert legs["clean"]["finish_reasons"]["poisoned"] in ("eos",
                                                          "length")
    info = legs["chaos"]["fault_info"]["poisoned"]
    assert info["recovered"] is False and info["attempts"] >= 1
    # recovered requests carry the structured record too
    assert any(v["recovered"] for v in legs["chaos"]["fault_info"]
               .values())
    assert legs["chaos"]["faults_injected"] >= 3


@pytest.mark.slow
def test_bench_fleet_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_fleet.py runs end-to-end: the fleet
    chaos bench can't rot.  Asserts the fleet acceptance bar at smoke
    scale (2 replica child processes): prefix-affinity routing lands a
    strictly higher fleet-wide prefix-cache hit rate than round-robin,
    and a kill -9'd replica's inflight streams migrate to the survivor
    with zero request loss, token-for-token SSE continuity vs the
    greedy oracle, a bounded post-failover TTFT, and the /alertz
    rollup narrating the failover.  Slow lane: multi-replica chaos
    spawns + compiles several engine processes."""
    out = str(tmp_path / "bench_fleet.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_fleet.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    s = data["summary"]
    assert s["affinity_wins"] is True
    assert s["affinity_hit_rate"] > s["round_robin_hit_rate"]
    assert s["killed_by_sigkill"] is True
    assert s["zero_request_loss"] is True
    assert s["token_continuity"] is True
    assert s["streams_migrated"] >= 1
    assert s["ttft_after_kill_bounded"] is True
    assert s["rollup_narrates_failover"] is True
    chaos = data["legs"]["chaos"]
    assert chaos["victim_exit"] == -9
    assert chaos["inflight_on_victim"] >= 1
    assert chaos["failovers"] >= 1


@pytest.mark.slow
def test_bench_recovery_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_recovery.py runs end-to-end: the
    durable-serving bench can't rot.  Asserts the acceptance bar at
    smoke scale: in-process recovery with executable handoff >= 5x
    faster than cold recompile recovery with greedy parity in both
    legs, and a kill -9'd serve resumed in a FRESH process from
    journal+snapshot with zero request loss, no re-emitted stream
    tokens, and bit-identical greedy outputs vs the uninterrupted
    reference."""
    out = str(tmp_path / "bench_recovery.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_recovery.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    s = data["summary"]
    assert s["handoff_speedup"] >= 5.0
    assert s["in_process_parity"] is True
    assert s["killed_by_sigkill"] is True
    assert s["zero_request_loss"] is True
    assert s["no_reemitted_tokens"] is True
    assert s["bit_identical"] is True
    legs = data["legs"]
    # handoff really did skip the recompiles the cold leg paid
    assert legs["in_process"]["exec_handoffs"] >= 1
    assert legs["in_process"]["handoff_leg_recompiles"] == 0
    assert legs["in_process"]["cold_leg_recompiles"] >= 1
    assert legs["in_process"]["retraces_after_warmup"] == 0
    cross = legs["cross_process"]
    assert cross["serve_exit"] == -9  # SIGKILL, not a clean exit
    assert cross["tokens_streamed_before_kill"] >= 1
    assert cross["snapshot_present"] is True
    assert cross["journal_events"] >= 3


@pytest.mark.slow
def test_bench_kv_quant_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_kv_quant.py runs end-to-end: the
    quantized-KV bench can't rot.  Asserts the ISSUE-12 acceptance bar
    at smoke scale: >=1.8x concurrent slots at fixed pool bytes,
    teacher-forced greedy token match >= 99% with the logit-drift
    probe self-checked against the engine, the kv_quant=off leg
    bit-exact with ZERO new executables and zero quant counters, and
    0 warm retraces in every leg (the tokens/s ratio is gated at full
    scale only — smoke batches are too small to pin it)."""
    out = str(tmp_path / "bench_kvquant.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_kv_quant.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    s = data["summary"]
    assert s["slot_density_ratio"] >= 1.8
    assert s["token_match_rate"] >= 0.99
    assert s["probe_self_check"] is True
    assert s["max_logit_drift"] <= s["drift_bound"]
    assert s["parity_off_bit_exact"] is True
    assert s["zero_new_executables_off"] is True
    assert s["zero_warm_retraces"] is True
    legs = data["legs"]
    # the density leg really ran quantized: pages entered int8 service
    # at a fraction of the fp32 bytes per token
    assert legs["density"]["int8"]["kv_quant_pages"] > 0
    assert legs["density"]["int8"]["bytes_per_token"] < \
        0.3 * legs["density"]["off"]["bytes_per_token"]
    assert legs["parity_off"]["quant_counters_zero"] is True
    assert legs["quality"]["total"] > 0


@pytest.mark.slow
def test_bench_wquant_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_wquant.py runs end-to-end: the
    int8-weight bench can't rot.  Asserts the ISSUE-20 acceptance bar
    at smoke scale: >=3x matmul-weight bytes reclaimed (cross-checked
    against the HBM ledger's weights_int8/weight_scales rows),
    teacher-forced greedy token match >= 99% with the logit-drift
    probe self-checked against the engine, the serve_weights=off leg
    bit-exact with ZERO new executables and zero weight-quant
    counters, and 0 warm retraces in every leg (the tokens/s and
    streaming ratios are gated at full scale only — smoke shapes are
    too small to pin wall-clock)."""
    out = str(tmp_path / "bench_wquant.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_wquant.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    s = data["summary"]
    assert s["weight_bytes_ratio"] >= 3.0
    assert s["token_match_rate"] >= 0.99
    assert s["probe_self_check"] is True
    assert s["ledger_matches_tree"] is True
    assert s["max_logit_drift"] <= s["drift_bound"]
    assert s["parity_off_bit_exact"] is True
    assert s["zero_new_executables_off"] is True
    assert s["quant_counters_zero_off"] is True
    assert s["zero_warm_retraces"] is True
    legs = data["legs"]
    # the budget leg really served quantized: every matmul weight
    # folded, reclaimed bytes counted, and the reclaimed bytes bought
    # strictly more concurrent slots at the same budget
    assert legs["budget"]["int8"]["weight_quant_mats"] > 0
    assert legs["budget"]["int8"]["weight_quant_bytes_saved"] > 0
    assert legs["budget"]["int8"]["slots"] > legs["budget"]["off"]["slots"]
    assert legs["budget"]["int8"]["ledger"]["weights_int8"] > 0
    assert legs["parity_off"]["fingerprint_identical"] is True
    assert legs["quality"]["total"] > 0


def test_telemetry_dump_smoke(tmp_path):
    """tools/telemetry_dump.py runs a small engine workload end-to-end
    and every export format parses: Prometheus text has the core
    request-latency and KV-pool series, the JSON snapshot is
    structured, and the merged chrome trace carries the host / engine /
    requests tracks (the ISSUE-4 acceptance check)."""
    outdir = str(tmp_path / "tel")
    r = subprocess.run(
        [sys.executable, "tools/telemetry_dump.py", "--outdir", outdir],
        cwd=REPO, capture_output=True, text=True, env=ENV, timeout=600)
    assert r.returncode == 0, r.stderr

    prom = open(os.path.join(outdir, "telemetry.prom")).read()
    for needle in ("paddle_request_ttft_seconds_bucket",
                   "paddle_request_tpot_seconds_count",
                   "paddle_request_queue_wait_seconds_sum",
                   "paddle_kv_pool_utilization",
                   "paddle_decode_steps_total",
                   "paddle_dispatch_calls_total",
                   "# TYPE paddle_request_ttft_seconds histogram"):
        assert needle in prom, needle

    with open(os.path.join(outdir, "telemetry.json")) as f:
        snap = json.load(f)
    m = snap["metrics"]
    assert m["paddle_request_ttft_seconds"]["series"][0]["count"] == 2
    assert m["paddle_requests_finished_total"]["series"]
    assert snap["workload"]["tokens_out"] > 0

    with open(os.path.join(outdir, "telemetry_trace.json")) as f:
        trace = json.load(f)
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("ph") == "M"}
    assert {"host", "engine", "requests"} <= tracks
    assert any(e.get("name") == "prefill" for e in trace["traceEvents"])

    # ISSUE-11 artifacts: the flight window parses and carries the
    # serve's step records, and statusz ships in both JSON and text
    with open(os.path.join(outdir, "telemetry_flight.json")) as f:
        flight = json.load(f)
    assert flight["records"]
    steps = [r for r in flight["records"] if r["kind"] == "step"]
    assert steps and all("phases" in r and "slots" in r for r in steps)
    assert flight["totals"]["tokens"] > 0
    with open(os.path.join(outdir, "telemetry_statusz.json")) as f:
        statusz = json.load(f)
    for key in ("engine", "step", "health", "queue", "slots", "pool",
                "flight"):
        assert key in statusz, key
    assert statusz["health"] == "live"
    txt = open(os.path.join(outdir, "telemetry_statusz.txt")).read()
    assert "engine 0" in txt and "flight:" in txt
    # ISSUE-13 artifact: the cost-observatory export parses and its
    # keys match the statusz cost section (same dict, two surfaces)
    with open(os.path.join(outdir, "telemetry_cost.json")) as f:
        cost = json.load(f)
    for key in ("peaks", "profiles", "calibration", "error_ratio",
                "ledger", "headroom"):
        assert key in cost, key
    assert set(cost) == set(statusz["cost"]), (
        set(cost) ^ set(statusz["cost"]))
    assert cost["profiles"], "no executable profiles extracted"
    assert cost["ledger"]["categories"]["weights"] > 0
    assert "admissible_slots" in cost["headroom"]
    # and explain_request renders a timeline from the flight artifact
    rid = statusz["flight"]["records"][-1]["slots"][0]["request"] \
        if statusz["flight"]["records"][-1].get("slots") else 0
    r2 = subprocess.run(
        [sys.executable, "tools/explain_request.py",
         os.path.join(outdir, "telemetry_flight.json"),
         "--request", str(rid),
         "--trace", os.path.join(outdir, "telemetry_trace.json")],
        cwd=REPO, capture_output=True, text=True, env=ENV, timeout=120)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert f"request {rid}" in r2.stdout


def test_telemetry_dump_url_mode(tmp_path):
    """ISSUE-14 satellite: telemetry_dump --url pulls /metrics,
    /statusz and /flightz from a LIVE ops server (started in this
    process, polled by the subprocess over real HTTP) and writes the
    same artifact files as the in-process path — and the statusz JSON
    the two paths produce is key-identical."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.inference.serving import DecodeEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=64,
                    use_parallel_layers=False, dropout=0.0)
    model = GPT(cfg)
    model.eval()
    eng = DecodeEngine(model, max_batch_size=2, max_seq_len=40,
                       page_size=8, alerts=True)
    eng.generate([np.arange(1, 13, dtype=np.int32)],
                 max_new_tokens=6)
    port = obs.start_ops_server(port=0, host="127.0.0.1")
    outdir = str(tmp_path / "tel_url")
    try:
        # --engine pins the pull to OUR engine: other suites' module-
        # scoped engines may still be registered in this process, and
        # a multi-engine /statusz answers the map form
        r = subprocess.run(
            [sys.executable, "tools/telemetry_dump.py",
             "--url", f"http://127.0.0.1:{port}",
             "--engine", str(eng._engine_id),
             "--outdir", outdir],
            cwd=REPO, capture_output=True, text=True, env=ENV,
            timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
    finally:
        obs.stop_ops_server()
    prom = open(os.path.join(outdir, "telemetry.prom")).read()
    assert "paddle_decode_step_seconds" in prom
    assert "# TYPE paddle_alerts_firing gauge" in prom
    with open(os.path.join(outdir, "telemetry_statusz.json")) as f:
        pulled = json.load(f)
    local = eng.statusz()
    # the key-identity contract: a dump taken over the wire describes
    # the same surface as one taken in-process
    assert set(pulled) == set(local), set(pulled) ^ set(local)
    assert pulled["engine"] == eng._engine_id
    assert pulled["alerts"]["firing"] == []
    txt = open(os.path.join(outdir, "telemetry_statusz.txt")).read()
    assert f"engine {eng._engine_id}" in txt
    with open(os.path.join(outdir, "telemetry_flight.json")) as f:
        flight = json.load(f)
    assert flight["records"] and "alerts" in flight


@pytest.mark.slow
def test_bench_cost_smoke(tmp_path):
    """BENCH_SMOKE=1 tools/bench_cost.py runs end-to-end: the cost-
    observatory bench can't rot.  Asserts the ISSUE-13 acceptance bar
    at smoke scale: profiles extracted for every executable kind
    (decode + mixed + spec all calibrated), flight records carrying
    predicted/actual pairs, the HBM ledger reconciling against
    jax.live_arrays() with <= 5% unattributed, and the cost_model=off
    leg bit-exact with identical compile counters and 0 warm retraces
    (the accuracy and overhead RATIOS are gated at full scale only —
    smoke steps are sub-millisecond and timer-noise dominated)."""
    out = str(tmp_path / "bench_cost.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_cost.py", "--out", out],
        cwd=REPO, capture_output=True, text=True,
        env={**ENV, "BENCH_SMOKE": "1"}, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["smoke"] is True
    s = data["summary"]
    assert s["profiles_extracted"] is True
    assert s["mixed_and_spec_calibrated"] is True
    assert s["ledger_within_bound"] is True
    assert s["unattributed_frac"] <= 0.05
    assert s["ledger_categories_found"] is True
    assert s["parity_cost_off"] is True
    assert s["zero_new_executables"] is True
    assert s["zero_warm_retraces"] is True
    cal = data["legs"]["calibration"]
    assert cal["calibrated_records"] >= 1
    assert cal["median_error"] is not None
    assert cal["profile_sources"] == ["hlo"]
    led = data["legs"]["ledger"]
    assert led["categories"]["weights"] > 0
    assert led["categories"]["kv_pages"] > 0
    assert led["gauge_series"] >= len(led["categories"])


def test_bench_trajectory_smoke(tmp_path):
    """tools/bench_trajectory.py over the repo's real bench artifacts:
    the aggregate parses, covers every BENCH_*.json (the repo ships
    9+), carries a machine stamp, and each entry exposes a headline
    dict of scalars.  jax-free and sub-second — rides tier-1."""
    out = str(tmp_path / "BENCH_trajectory.json")
    r = subprocess.run(
        [sys.executable, "tools/bench_trajectory.py", "--root", REPO,
         "--out", out],
        cwd=REPO, capture_output=True, text=True, env=ENV, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    with open(out) as f:
        data = json.load(f)
    assert data["trajectory"] == 1
    assert data["count"] >= 9
    assert data["count"] == len(data["benches"])
    assert "trajectory" not in data["benches"]  # never self-aggregates
    m = data["machine"]
    assert m["platform"] and m["python"] and m["cpu_count"] >= 1
    assert data["generated_unix"] > 0
    for key, entry in data["benches"].items():
        assert entry["file"] == f"BENCH_{key}.json"
        assert isinstance(entry["headline"], dict)
        for v in entry["headline"].values():
            assert isinstance(v, (int, float, bool, str))
    # the serving benches' summary scalars surface as headlines
    assert "median_error" in data["benches"]["cost"]["headline"]
    assert data["skipped"] == []
    # the shipped aggregate stays fresh: same bench set as a rebuild
    with open(os.path.join(REPO, "BENCH_trajectory.json")) as f:
        shipped = json.load(f)
    assert set(shipped["benches"]) == set(data["benches"])


def test_tracecheck_smoke(tmp_path):
    """tools/tracecheck.py end-to-end: the serving-stack targets scan
    CLEAN against the shipped (empty) baseline — the ISSUE-8
    acceptance gate — a seeded-bad fixture exits 1 with the finding
    printed, and the --write-baseline grandfather workflow
    round-trips."""
    r = subprocess.run(
        [sys.executable, "tools/tracecheck.py"], cwd=REPO,
        capture_output=True, text=True, env=ENV, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "clean" in r.stdout

    # a seeded trace hazard + missing donation must be caught...
    bad = tmp_path / "bad_mod.py"
    bad.write_text(
        "import jax\n\n"
        "def step(params, kv, x):\n"
        "    if x > 0:\n"
        "        return kv, int(x)\n"
        "    return kv, 0\n\n"
        "fn = jax.jit(step)\n")
    r = subprocess.run(
        [sys.executable, "tools/tracecheck.py", str(bad),
         "--no-baseline"], cwd=REPO, capture_output=True, text=True,
        env=ENV, timeout=300)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "[trace-hazard]" in r.stdout and "[donation]" in r.stdout

    # ...and --write-baseline grandfathers exactly those findings
    bl = str(tmp_path / "bl.json")
    w = subprocess.run(
        [sys.executable, "tools/tracecheck.py", str(bad),
         "--baseline", bl, "--write-baseline"],
        cwd=REPO, capture_output=True, text=True, env=ENV, timeout=300)
    assert w.returncode == 0, w.stdout + w.stderr
    clean = subprocess.run(
        [sys.executable, "tools/tracecheck.py", str(bad),
         "--baseline", bl], cwd=REPO, capture_output=True, text=True,
        env=ENV, timeout=300)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "baselined" in clean.stdout


def test_op_bench_gate_device_mismatch(tmp_path):
    """Cross-device comparisons are incommensurable (a CPU run vs a TPU
    baseline); the checker must refuse rather than mis-gate."""
    import json
    import subprocess
    import sys

    a = str(tmp_path / "a.json")
    b = str(tmp_path / "b.json")
    with open(a, "w") as f:
        json.dump({"device": "TFRT_CPU_0",
                   "results": [{"op": "matmul", "mean_us": 10.0}]}, f)
    with open(b, "w") as f:
        json.dump({"device": "TPU v5 lite0",
                   "results": [{"op": "matmul", "mean_us": 10.0}]}, f)
    r = subprocess.run(
        [sys.executable, "tools/check_op_benchmark_result.py", a, b],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert r.returncode == 2 and "device mismatch" in r.stdout


class TestTpuOpGate:
    """Round-4 VERDICT #8: the TPU op-perf gate (matmul-normalized
    units, tools/op_bench_tpu_baseline.json + bench._tpu_op_gate)."""

    def _fake_results(self, flash_units):
        import json

        base = json.load(open(os.path.join(REPO, "tools",
                                           "op_bench_tpu_baseline.json")))
        res = []
        for r in base["results"]:
            u = flash_units if r["op"] == "flash_attention" else \
                r["matmul_units"]
            res.append({"op": r["op"], "mean_us": u * 1000.0,
                        "iters": 8, "matmul_units": u})
        return {"device": base["device"], "results": res}

    def test_deoptimized_flash_trips_gate(self, tmp_path):
        """A flash kernel collapsing to >2x its baseline units (falling
        back to composed attention at S=2048 is ~2.8-3.7x) must FAIL
        the gate."""
        import json
        import subprocess
        import sys

        base_path = os.path.join(REPO, "tools",
                                 "op_bench_tpu_baseline.json")
        base = json.load(open(base_path))
        flash_base = next(r["matmul_units"] for r in base["results"]
                          if r["op"] == "flash_attention")
        bad = tmp_path / "bad.json"
        json.dump(self._fake_results(flash_base * 3.2), open(bad, "w"))
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "check_op_benchmark_result.py"),
             base_path, str(bad), "--threshold", "2.0"],
            capture_output=True, text=True)
        assert r.returncode == 1, r.stdout + r.stderr
        assert "flash_attention" in r.stdout

    def test_healthy_run_passes_gate(self, tmp_path):
        import json
        import subprocess
        import sys

        base_path = os.path.join(REPO, "tools",
                                 "op_bench_tpu_baseline.json")
        base = json.load(open(base_path))
        flash_base = next(r["matmul_units"] for r in base["results"]
                          if r["op"] == "flash_attention")
        ok = tmp_path / "ok.json"
        # 1.3x = the measured session-to-session swing: must NOT trip
        json.dump(self._fake_results(flash_base * 1.3), open(ok, "w"))
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "check_op_benchmark_result.py"),
             base_path, str(ok), "--threshold", "2.0"],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr

    def test_missing_op_trips_gate(self, tmp_path):
        import json
        import subprocess
        import sys

        base_path = os.path.join(REPO, "tools",
                                 "op_bench_tpu_baseline.json")
        data = self._fake_results(1.0)
        data["results"] = [r for r in data["results"]
                           if r["op"] != "flash_attention"]
        new = tmp_path / "short.json"
        json.dump(data, open(new, "w"))
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "tools", "check_op_benchmark_result.py"),
             base_path, str(new), "--threshold", "2.0"],
            capture_output=True, text=True)
        assert r.returncode == 1
