"""Global runtime flag registry.

Reference: gflags knobs in `paddle/fluid/platform/flags.cc:33-603` exposed to
Python through `pybind/global_value_getter_setter.cc` as
`paddle.set_flags`/`get_flags`.  Here flags are a plain process-global
registry; flags may also be seeded from the environment as ``FLAGS_<name>``.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable

_REGISTRY: Dict[str, Any] = {}

# invalidation hooks: traced-executable caches bake flag values read at
# trace time (e.g. FLAGS_use_pallas_layernorm inside a dispatched op), so
# a flag change must drop them or set_flags would be silently ignored for
# already-cached signatures
_ON_CHANGE = []


def on_flags_changed(callback):
    _ON_CHANGE.append(callback)


def define_flag(name: str, default, help_str: str = ""):
    env = os.environ.get(f"FLAGS_{name}")
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    _REGISTRY[name] = value


def set_flags(flags: Dict[str, Any]):
    # validate every key BEFORE mutating: a partial apply that raised on
    # a later unknown key would skip the invalidation callbacks below,
    # leaving cached executables replaying the old value of the flags
    # that did change
    items = [(k[len("FLAGS_"):] if k.startswith("FLAGS_") else k, v)
             for k, v in flags.items()]
    for k, _ in items:
        if k not in _REGISTRY:
            raise KeyError(f"unknown flag {k!r}")
    changed = False
    for k, v in items:
        if _REGISTRY[k] != v:
            changed = True
        _REGISTRY[k] = v
    if changed:
        for cb in _ON_CHANGE:
            cb()


def get_flags(names) -> Dict[str, Any]:
    if isinstance(names, str):
        names = [names]
    out = {}
    for k in names:
        key = k[len("FLAGS_"):] if k.startswith("FLAGS_") else k
        out[k] = _REGISTRY[key]
    return out


def flag(name: str):
    return _REGISTRY[name]


# Core flags (subset of reference's platform/flags.cc that is meaningful on
# TPU; CUDA/cudnn-specific knobs are intentionally absent).
define_flag("check_nan_inf", False,
            "check every op output for NaN/Inf (debug only: forces a host "
            "sync per op, serializing the device)")
define_flag("benchmark", False, "sync + log after every eager op")
define_flag("deterministic", False, "force deterministic reductions")
define_flag("eager_jit_ops", True,
            "enable the signature-keyed eager dispatch cache (jitted "
            "fwd/vjp executables memoized per op signature; off = legacy "
            "per-call tracing)")
define_flag("eager_cache_size", 4096,
            "LRU bound on memoized dispatch executables (<=0 = unbounded); "
            "shape-polymorphic loops should also call "
            "clear_dispatch_cache() between phases")
define_flag("eager_dispatch_report", False,
            "print the per-op dispatch telemetry table (calls, cache "
            "hits/misses, retraces, wall time) at interpreter exit")
define_flag("amp_dtype", "bfloat16", "autocast compute dtype (TPU: bfloat16)")
define_flag("allocator_strategy", "pjrt", "memory is managed by PJRT")
define_flag("log_level", 0, "VLOG-style verbosity")
define_flag("use_pallas_attention", "auto",
            "attention kernel policy: auto (seq threshold), 1 force, 0 off")
define_flag("pallas_attention_min_seq", 512,
            "sequence length at/above which 'auto' picks the Pallas kernel "
            "(measured crossover vs XLA on v5e: see BENCH_kernels.json; "
            "round 3's causal dead-block DMA clamps moved it 1024 -> 512)")
define_flag("use_pallas_layernorm", False,
            "use the Pallas fused layer_norm kernel instead of XLA fusion")
define_flag("interp_tensor_array_capacity", 0,
            "fallback capacity for TensorArrays written inside an "
            "interpreted `while` when the loop bound cannot be inferred "
            "from the Condition (0 = raise instead)")
define_flag("chunked_prefill", True,
            "serving engine prefill policy: 1 (default) fuses prompt "
            "ingestion into the decode step — each step feeds every "
            "prefilling slot a prompt chunk and every decoding slot its "
            "usual token through ONE mixed-batch executable, so an "
            "admission never stalls running decodes for a full prompt "
            "pass.  0 restores the legacy one-shot bucket-padded prefill "
            "(the greedy-parity oracle; see docs/DECODE_PERF.md)")
define_flag("prefill_chunk_tokens", 64,
            "per-step prompt-token budget of the chunked-prefill "
            "scheduler (FLAGS_chunked_prefill): each engine step consumes "
            "at most this many prompt tokens across all prefilling slots "
            "(a single slot's chunk is also capped here — it is the Q_max "
            "of the fixed-shape mixed-step executable).  Smaller values "
            "bound per-step latency (TPOT of running requests) tighter at "
            "the cost of more steps to finish a prompt")
define_flag("prefix_cache", True,
            "serving-engine prefix caching (chunked prefill only): full "
            "prompt KV pages are content-addressed by a chain hash "
            "(rolling per-page digest keyed by a sampling-invariant "
            "model fingerprint) and reused across requests at "
            "refcount+1 — admission maps the longest page-aligned "
            "cached prefix into the request's block table and chunked "
            "prefill starts at the first novel token; a mid-page "
            "divergence recomputes into a fresh copy-on-write page "
            "(cached pages are never written in place), and refcount-"
            "zero cached pages are retained on an LRU and evicted "
            "least-recently-released-first under pool pressure.  0 "
            "restores prefill-from-scratch bit-exactly (the parity "
            "oracle; see docs/DECODE_PERF.md)")
define_flag("kv_quant", "off",
            "serving KV-page storage quantization "
            "(inference.serving.DecodeEngine): 'int8' stores K/V pages "
            "as int8 with per-page, per-head symmetric scales in "
            "parallel donated f32 arrays — half/quarter the bytes per "
            "page means proportionally more concurrent slots at fixed "
            "pool memory; dequantization fuses into the paged-"
            "attention K/V loads (Pallas kernel: in-register after the "
            "page DMA, scale rows scalar-prefetched with the block "
            "tables) and the write path quantizes each scattered "
            "chunk in-graph, folding its per-head absmax into the "
            "running page scale (existing rows re-quantize when the "
            "scale grows — the 'refold').  'off' (default) is the "
            "bit-exact full-precision path and constructs the exact "
            "same executables as before the feature existed.  Output "
            "quality is gated by measurement, not just plumbing: see "
            "tools/bench_kv_quant.py / docs/DECODE_PERF.md.  Engines "
            "constructed with an explicit kv_quant= ignore the flag")
define_flag("serve_weights", "off",
            "serving weight-storage quantization "
            "(inference.serving.DecodeEngine): 'int8' folds every "
            "matmul weight of the step executables — qkv/out/fc1/fc2 "
            "projections, the untied LM head, and a bound draft "
            "model's weights — to per-out-channel symmetric int8 "
            "(quantization.int8.quantize_weight) with f32 scales in "
            "parallel `*_q`/`*_s` param leaves; embeddings, position "
            "tables, layernorms and biases stay f32.  The matmul sites "
            "dequantize fused at use (mixed f32xs8 dot + scale in the "
            "dot epilogue), so weights stream from HBM as int8 — ~4x "
            "less weight traffic per step on the bandwidth-bound "
            "decode path.  'off' (default) is the bit-exact "
            "full-precision path and constructs the exact same "
            "executables as before the feature existed.  Output "
            "quality is gated by measurement, not just plumbing: see "
            "tools/bench_wquant.py / docs/INT8_PERF.md.  Engines "
            "constructed with an explicit serve_weights= ignore the "
            "flag")
define_flag("snapshot_kv", True,
            "serialize the content-addressed (prefix-cached) KV page "
            "payloads — int8 + scales under FLAGS_kv_quant — into a "
            "crc-validated sidecar (kv_pages.npz) beside each "
            "durability snapshot: durability.restore_from_dir installs "
            "them into the fresh pool and registers their chain "
            "hashes, so replay re-admission prefix-hits the installed "
            "pages instead of recomputing the whole prompt (and a "
            "quantized snapshot is a fraction of the fp32 bytes).  A "
            "missing/torn sidecar falls back to full recompute — "
            "restores stay bit-identical either way.  0 = snapshot "
            "host state only, as before")
define_flag("cache_generated_pages", False,
            "content-address GENERATED full KV pages as decode "
            "crosses page boundaries (requires FLAGS_prefix_cache): "
            "the prompt's chain hash extends over the generated "
            "tokens, so beam/agent fanout sharing a DECODE prefix — "
            "and the fleet router's prefix-affinity key — prefix-hit "
            "the generated region too, not just the prompt.  0 "
            "(default) registers prompt pages only: pool occupancy "
            "and eviction order are bit-exact with the pre-fleet "
            "engine (the parity oracle tests/test_prefix_cache.py "
            "pins).  Engines constructed with an explicit "
            "cache_generated_pages= ignore the flag")
define_flag("kv_pool_debug", False,
            "audit KVBlockPool consistency (free/private/cached page "
            "partition, refcounts vs live request holds, eviction-LRU "
            "membership) at every DecodeEngine step boundary — debug "
            "only, adds host-side cost per step")
define_flag("sched_policy", "fifo",
            "serving-engine admission scheduler "
            "(inference.frontend.make_scheduler): 'fifo' (default) "
            "admits in strict arrival order — bit-exact with the "
            "historical behavior, never preempts; 'slo' orders by "
            "priority class then earliest-deadline-first, expires "
            "still-queued requests past their deadline_ms, skips a "
            "head-of-line blocker when a smaller request behind it "
            "fits (bounded by an anti-starvation fence), and under "
            "slot/pool pressure preempts the lowest-priority running "
            "request for resume via the prefix cache.  Engines "
            "constructed with an explicit scheduler ignore the flag")
define_flag("spec_decode_k", 0,
            "speculative decoding draft length for the serving engine "
            "(inference.serving.DecodeEngine): propose K tokens per step "
            "and verify them in one multi-query pass (0 = off, classic "
            "one-token-per-step decode).  Engines constructed with an "
            "explicit spec_decode_k ignore the flag")
define_flag("spec_drafter", "prompt_lookup",
            "drafter the engine builds when speculative decoding is on "
            "and no Drafter instance is passed: 'prompt_lookup' (model-"
            "free n-gram lookup over each request's own token history; "
            "see inference.speculative.PromptLookupDrafter).  A draft-"
            "model drafter must be passed as an instance (it needs the "
            "draft GPT's weights)")
define_flag("ragged_step", False,
            "unified ragged serving step (inference.serving."
            "DecodeEngine): decode, mixed prefill+decode, and "
            "speculative-verify traffic all dispatch ONE step "
            "executable whose rows each carry their own query span "
            "(decode=1, prefill chunk=C, verify window=K+1) instead "
            "of three phase-split executables per KV mode.  Greedy "
            "tokens are bit-identical to the split path (the off "
            "path compiles the exact same executables as before and "
            "stays the parity oracle).  Engines constructed with an "
            "explicit ragged_step ignore the flag")
define_flag("serve_mesh", "",
            "tensor-parallel serving mesh spec for inference.serving."
            "DecodeEngine, e.g. 'mp=2' or 'mp=4': the engine builds a "
            "Mesh over that many devices, shards params by the regex "
            "partition rules in parallel.partition (column-split "
            "qkv/fc1, row-split out/fc2, replicated norms/embeddings) "
            "and shards the KV page pool on the head axis (each chip "
            "holds its head-slice of every page; block tables and the "
            "page allocator stay host-global).  Implies the unified "
            "ragged step — the mesh shards the ONE step executable "
            "per KV mode.  Greedy tokens stay token-identical to the "
            "single-chip engine; '' (default) = single-chip path, "
            "bit-exact, zero sharding machinery touched.  Engines "
            "constructed with an explicit serve_mesh ignore the flag")
define_flag("spec_adaptive_k", False,
            "adaptive per-slot speculation depth (inference."
            "speculative.SpeculativeDecoder): each slot's draft "
            "length starts at the configured spec_decode_k, halves "
            "toward spec_k_min after spec_k_shrink_streak fully-"
            "rejected rounds, and grows back one step after "
            "spec_k_grow_streak fully-accepted rounds (growth is "
            "additionally gated by the cost model's per-kind "
            "calibration when armed).  Per-slot K only narrows a "
            "row's span on the already-compiled verify window — no "
            "new executable shapes.  Greedy tokens stay exactly the "
            "target model's.  Needs spec_decode_k >= 1")
define_flag("spec_k_min", 1,
            "adaptive-K floor (FLAGS_spec_adaptive_k): a slot's "
            "speculation depth never shrinks below this many drafted "
            "tokens — 1 keeps at least classic+1 emission potential "
            "while a drafter is cold")
define_flag("spec_k_shrink_streak", 2,
            "adaptive-K shrink trigger: consecutive verify rounds in "
            "which a slot accepted NONE of its drafts before its "
            "depth halves (multiplicative decrease)")
define_flag("spec_k_grow_streak", 2,
            "adaptive-K grow trigger: consecutive verify rounds in "
            "which a slot accepted EVERY usable draft before its "
            "depth grows by one (additive increase, capped at "
            "spec_decode_k)")
define_flag("metrics_report_interval_s", 0.0,
            "interval of the periodic observability reporter "
            "(paddle_tpu.observability.start_reporter): every interval a "
            "metrics snapshot is handed to the reporter sink on a daemon "
            "thread.  0 (default) = off.  DecodeEngine construction "
            "auto-starts the reporter when the flag is positive")
define_flag("sanitize", False,
            "serving sanitizer mode (paddle_tpu.analysis.sanitizer): "
            "warm retraces RAISE instead of counting, donated step "
            "buffers are tombstoned after every jitted call and any "
            "later host access raises naming the donation site, the "
            "designated telemetry locks record acquisition order (a "
            "lock-order cycle fails at the acquisition that would have "
            "deadlocked), KVBlockPool.assert_consistent runs at every "
            "DecodeEngine step boundary, and blocking device syncs "
            "inside the step span are counted.  Debug/CI only — adds "
            "host-side cost per step and per lock acquisition")
define_flag("fault_inject", "",
            "arm the serving fault-injection harness "
            "(inference.resilience.FaultPlan.parse): a "
            "';'-separated list of site@occurrences entries — e.g. "
            "'step@3,5;pool@2-4;drafter@1' injects a step-executable "
            "raise at the 3rd and 5th consult of the step site, pool "
            "exhaustion at alloc consults 2..4, and a drafter raise at "
            "its 1st consult — plus 'poison@TOKEN' (every step fails "
            "while a request whose prompt contains TOKEN is in the "
            "batch; the bisect containment must find it).  "
            "Deterministic: occurrence counters, never wall-clock.  "
            "Empty (default) = off, zero hooks on the hot path.  "
            "Engines constructed with an explicit fault_plan= ignore "
            "the flag")
define_flag("step_retries", 2,
            "same-step retries of a failed step executable before the "
            "containment ladder escalates (degrade the failing "
            "subsystem, then bisect-quarantine the suspect request; "
            "see docs/RELIABILITY.md).  Each retry backs off "
            "exponentially in deterministic ticks (1, 2, 4, ... capped "
            "at 8) and sleeps tick * FLAGS_step_backoff_ms")
define_flag("step_backoff_ms", 0.0,
            "wall-clock milliseconds per backoff tick between step "
            "retries (0 = count ticks but never sleep — the "
            "deterministic default tier-1 tests rely on)")
define_flag("degrade_after", 3,
            "consecutive failures of one subsystem (speculative "
            "drafter/verify, mixed prefill+decode executable) before "
            "the engine degrades it away — speculation disables, "
            "chunked prefill falls back to the legacy one-shot "
            "prefill oracle path (paddle_degraded_mode gauge flips)")
define_flag("degraded_probe_steps", 16,
            "clean engine steps in degraded mode before the engine "
            "probes re-enabling the degraded subsystem (speculation / "
            "chunked prefill); a fresh failure degrades it again")
define_flag("engine_recoveries", 2,
            "engine rebuilds (inference.resilience.recover: fresh "
            "engine, every in-flight request re-admitted with its "
            "generated tokens folded into the prompt for replay) the "
            "frontend driver / serve_with_recovery may spend before "
            "declaring the fault unrecoverable (DegradedMode)")
define_flag("journal_dir", "",
            "arm durable serving (inference.durability): directory for "
            "the append-only write-ahead request journal (one record "
            "per admission / emitted-token watermark / finish) plus "
            "periodic on-disk engine snapshots — "
            "durability.restore_from_dir rebuilds the engine in a "
            "FRESH process after a SIGKILL/OOM with zero request loss "
            "and no re-emitted stream tokens.  Empty (default) = off; "
            "every hook on the serve path is then one `is None` check")
define_flag("journal_fsync", "step",
            "journal durability policy: 'always' fsyncs after every "
            "record (strongest no-re-emission guarantee, one fsync per "
            "emit), 'step' (default) buffers and fsyncs once per "
            "engine step, 'never' flushes to the OS without fsync "
            "(survives process death, not power loss).  See "
            "docs/RELIABILITY.md for the trade-offs")
define_flag("snapshot_interval_steps", 32,
            "engine steps between on-disk EngineSnapshot serializations "
            "when FLAGS_journal_dir is armed; the snapshot bounds how "
            "much of the journal a restore must replay (and how many "
            "tokens it must recompute).  <= 0 disables periodic "
            "snapshots — restore then replays the whole journal")
define_flag("journal_compact", True,
            "rewrite the write-ahead journal during durability."
            "restore_from_dir: the compacted journal carries one cfg "
            "record plus one admission + one watermark per request "
            "still in flight (finished requests and superseded "
            "watermarks drop), and the snapshot is re-anchored to it "
            "— so a serve that restores N times starts each life from "
            "a bounded file instead of replaying every previous "
            "life's records (the journal_growth alert's failure "
            "mode).  0 = append to the historical journal unmodified, "
            "as before")
define_flag("compile_cache_dir", "",
            "directory for JAX's persistent compilation cache: a "
            "rebuilt engine in a FRESH process (durability."
            "restore_from_dir) warm-starts its executables from disk "
            "instead of recompiling.  Process-global (jax config), "
            "applied at the first engine construction that sees it; "
            "JAX_COMPILATION_CACHE_DIR in the environment wins over it "
            "(core.compile_cache)")
define_flag("step_timeout_ms", 0.0,
            "hung-step watchdog (inference.durability.StepWatchdog): a "
            "DecodeEngine.step exceeding this wall-clock budget is "
            "classified hung — paddle_engine_health flips to 'hung' "
            "and a fatal HungStep routes the supervisor "
            "(serve_with_recovery / ServingFrontend._drive) through "
            "engine recovery; the frontend additionally abandons a "
            "worker thread still stuck past the budget.  Steps that "
            "compiled an executable are exempt (compiles are expected "
            "warmup stalls, not hangs).  0 (default) = disarmed")
define_flag("flight_window", 64,
            "serving flight recorder (observability.flight): number of "
            "per-step records the bounded ring buffer retains — one "
            "structured record per DecodeEngine.step (batch "
            "composition, phase-time breakdown, ladder events, pool "
            "occupancy, SLO burn).  Always-on and always-cheap by "
            "design; 0 disables the recorder entirely (statusz then "
            "serves engine state without flight history)")
define_flag("flight_dir", "",
            "directory for crash-safe flight-window auto-dumps (tmp+"
            "rename, same discipline as durability snapshots): every "
            "fatal StepFault, hung-step classification and watchdog "
            "abandonment leaves a black-box JSON the "
            "tools/explain_request.py timeline reconstructor reads.  "
            "Empty (default) = beside the journal "
            "(<journal_dir>/flight) when FLAGS_journal_dir is armed, "
            "else auto-dump is off (the in-memory ring and statusz "
            "still work)")
define_flag("cost_model", True,
            "serving cost observatory (observability.costmodel): "
            "extract a static FLOP/byte profile per compiled step "
            "executable at compile time (HLO cost analysis of the "
            "lowered computation on the CPU backend, of the compiled "
            "program on a TPU — the jit call that follows reuses that "
            "executable, so no step compiles twice), predict step "
            "cost from the profiles with a "
            "per-executable EWMA calibration learned from the flight "
            "recorder's measured step times, account live device "
            "bytes in the HBM ledger, and compute per-phase MFU / "
            "HBM-bandwidth roofline gauges.  0 = fully disarmed: one "
            "`is None` check per step, no profiles extracted, "
            "bit-exact serving.  Engines constructed with an explicit "
            "cost_model= ignore the flag")
define_flag("sched_cost_admission", False,
            "cost-model admission gate (observability.costmodel."
            "CostModel.admission_ok): DecodeEngine._admit_one "
            "additionally refuses a bind while the predicted step "
            "cost exceeds the tightest declared slo_tpot_ms among the "
            "candidate and the running set — admit against a latency "
            "budget instead of a slot count.  Default 0 = bit-exact "
            "historical admission; requires FLAGS_cost_model")
define_flag("peak_flops", 0.0,
            "roofline compute ceiling in FLOP/s for the cost "
            "observatory's MFU gauges (paddle_phase_mfu) and step-"
            "cost predictor; 0 (default) = autodetect from the device "
            "kind (datasheet table in observability.costmodel; CPU "
            "pins fixed test values so CI gauges are deterministic)")
define_flag("peak_hbm_gbps", 0.0,
            "roofline memory-bandwidth ceiling in GB/s for the cost "
            "observatory's paddle_phase_hbm_util gauges and step-cost "
            "predictor; 0 (default) = autodetect from the device kind "
            "(CPU pins fixed test values)")
define_flag("peak_ici_gbps", 0.0,
            "roofline interconnect ceiling in GB/s for the cost "
            "observatory's collective-bytes term (sharded executables "
            "under FLAGS_serve_mesh): predict_step_cost adds "
            "collective_bytes / ici_bytes_per_s to the roofline "
            "seconds of any profile whose HLO contains collectives; "
            "0 (default) = autodetect from the device kind (CPU pins "
            "a fixed test value so CI gauges are deterministic)")
define_flag("cost_memory_analysis", False,
            "additionally record each executable's peak temp-buffer "
            "allocation (Compiled.memory_analysis of the lowered "
            "computation, compiled at profile extraction; the jit "
            "call that follows reuses the executable) into its cost "
            "profile and the HBM ledger's temp_scratch category")
define_flag("cost_ledger_interval_steps", 128,
            "engine steps between HBM-ledger audits "
            "(observability.costmodel.CostModel.hbm_ledger: attribute "
            "every live device byte to weights / kv_pages / kv_scales "
            "/ draft_pool / misc and surface the unattributed residue "
            "as paddle_hbm_ledger_unattributed_bytes); the audit "
            "walks jax.live_arrays() — cost scales with the process's "
            "live-array count — so it is periodic rather than "
            "per-step (128 steps is still sub-second against any "
            "scrape interval).  <= 0 = audit only on demand "
            "(statusz / telemetry dump)")
define_flag("ops_port", 0,
            "ops-plane HTTP endpoint (observability.opsserver): a "
            "stdlib ThreadingHTTPServer daemon thread serving "
            "/metrics (Prometheus text), /statusz (JSON, ?format="
            "text), /flightz (flight window, ?request=<id> timeline), "
            "/healthz + /readyz (the fleet router's routing key: "
            "live AND capacity headroom > 0 AND no page-severity "
            "alert firing AND no watchdog-overdue step), and /alertz "
            "(declarative alert states + transitions).  Arms the "
            "between-steps alert engine (observability.alerts) on "
            "every DecodeEngine constructed while set.  0 (default) "
            "= fully off: zero listening sockets, zero alert "
            "counters, bit-exact serving; -1 = alert engine armed "
            "WITHOUT the HTTP listener (in-process /alertz state "
            "only).  Ports bind all interfaces — the endpoint is "
            "read-only introspection")
define_flag("alert_interval_steps", 32,
            "engine steps between alert-engine evaluations "
            "(observability.alerts.AlertEngine): each evaluation "
            "samples ~a dozen gauges and walks the rule table on the "
            "engine thread BETWEEN steps — no new hot-path locks, so "
            "the cadence is the only cost knob.  Evaluation also "
            "fires unconditionally on a fatal step fault and at "
            "watchdog abandonment so the crash dump records the "
            "alerts firing at death.  <= 0 falls back to 32")
define_flag("profile", False,
            "profiling plane (observability.profiling): sampled "
            "device-sync probes split each probed step's wall into "
            "device seconds vs host overhead (the engine blocks on "
            "the dispatched executable's output), MEASURED "
            "per-executable MFU lands beside the cost observatory's "
            "roofline gauges with a predicted-vs-measured drift "
            "gauge, compile-time profiles grow a top-K per-op "
            "FLOP/byte table, and bounded capture sessions "
            "(profiling.request_capture) record probe spans on a "
            "'device' chrome-trace track.  The device/host split, "
            "measured MFU and drift ride the flight record, so they "
            "need FLAGS_flight_window > 0 (the default); with the "
            "recorder off, probes still feed the device-seconds "
            "table and capture spans.  0 (default) = fully disarmed: "
            "one `is None` check per step hook, zero probes, zero "
            "new executables, bit-exact serving.  Engines "
            "constructed with an explicit profile= ignore the flag")
define_flag("profile_sample_steps", 64,
            "engine steps between device-sync probes while "
            "FLAGS_profile is armed (every step during an armed "
            "capture session): each probe blocks the engine thread on "
            "the step executable's output, trading one pipeline "
            "bubble for a measured device-vs-host split — sampling "
            "keeps the amortized cost negligible.  <= 1 probes every "
            "step (the bench attribution mode)")
define_flag("profile_dir", "",
            "directory for capture-session device traces: while set, "
            "profiling.request_capture additionally wraps the capture "
            "window in jax.profiler.start_trace/stop_trace so the "
            "XLA-level timeline lands beside the probe spans.  Empty "
            "(default) = probe spans only (the merged chrome trace's "
            "'device' track still works)")
define_flag("fleet_trace", False,
            "fleet-scope distributed tracing (observability."
            "fleettrace): FleetRouter.submit mints a trace id that "
            "rides every /v1/generate, /v1/adopt and /v1/resume leg "
            "as an x-paddle-trace header, the edge threads it into "
            "the frontend so engine-side request spans and flight "
            "records carry it, a failover leg reuses the donor's id "
            "(two segments of one trace), routing / SSE-delivery / "
            "failover decisions become spans on router+edge tracks, "
            "each edge serves /tracez/spans, and the router's "
            "/fleetz rollup merges replica span sets into one "
            "clock-offset-corrected chrome trace.  False (default) "
            "= fully off: zero new wire headers, zero new spans, "
            "zero extra probes, bit-exact serving")
define_flag("use_rbg_rng", True,
            "on TPU, use the hardware RBG PRNG for the framework's random "
            "ops instead of threefry (measured: recovers ~60% of dropout's "
            "train-step cost on ViT-B/16; draws differ from CPU/threefry "
            "runs). Read once at the first key creation — set it via env "
            "or set_flags before any random op / parameter init")
