"""Multi-chip sharded serving (FLAGS_serve_mesh): tensor-parallel
ragged decode over a mesh with head-partitioned KV pages.

Contracts pinned here (ISSUE 17 acceptance):

* greedy sharded serving over a virtual mesh (mp=2, mp=4) is
  TOKEN-IDENTICAL to the single-chip engine on every phase mix —
  plain decode, chunked mixed prefill+decode, speculative verify,
  int8 KV — the replicated LM head keeps the argmax bit-exact;
* steady state still dispatches exactly ONE step executable per KV
  mode (`ragged_compiles == 1`) and never retraces it
  (`ragged_retraces == 0`) — in particular the donated page pool's
  executable-output sharding round-trips into the next step's input
  without re-keying the jit cache;
* the optimized (post-SPMD-partitioner) HLO of the sharded step
  carries `all-reduce` ops at the row-parallel (out/fc2) boundaries —
  asserted against the HLO text via `parallel.partition
  .hlo_collectives` — and the cost observatory's profile carries their
  byte volume (`collective_bytes` > 0 exactly on sharded profiles);
* `FLAGS_serve_mesh` unset is the single-chip path, bit-exact with an
  engine that never heard of the feature: equal config fingerprints,
  no mesh in statusz, zero collective bytes;
* the mesh is part of the executable identity (`config_fingerprint`
  on != off) and of the wire config — `wire_config` round-trips the
  mesh spec and `restore_from_dir` rebuilds a SHARDED engine that
  finishes interrupted generations bit-identically;
* the profiling plane measures per-chip completion skew on probed
  sharded steps (`paddle_chip_skew_seconds{engine}`, /profilez).
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import decode_stats, reset_decode_stats

TINY = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 max_seq_len=128, use_parallel_layers=False, dropout=0.0)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs the 8-device virtual CPU mesh (conftest)")
needs_mesh4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 virtual devices (conftest)")


def _tiny_gpt(seed=0, cfg=TINY):
    paddle.seed(seed)
    m = GPT(cfg)
    m.eval()
    return m


def _engine(m, **kw):
    from paddle_tpu.inference.serving import DecodeEngine

    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 16)
    return DecodeEngine(m, **kw)


def _prompts(rng, lens):
    return [rng.randint(0, 64, (n,)).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# token parity + the one-executable / zero-retrace contract
# ---------------------------------------------------------------------------
@needs_mesh
class TestShardedParity:
    def test_mp2_decode_parity_one_executable(self):
        """Plain decode on mp=2 ≡ the single-chip engine token for
        token, through ONE sharded ragged executable that never
        retraces — the donated sharded page pool round-trips
        executable-output -> next-step-input on the warm cache."""
        m = _tiny_gpt(seed=21)
        prompts = _prompts(np.random.RandomState(11), (5, 9, 13))
        refs = _engine(m).generate(prompts, max_new_tokens=10)
        reset_decode_stats()
        eng = _engine(m, serve_mesh="mp=2")
        assert eng._ragged  # the mesh implies the unified step
        outs = eng.generate(prompts, max_new_tokens=10)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["decode_compiles"] == 0
        assert st["mixed_compiles"] == 0
        assert st["ragged_retraces"] == 0
        assert st["retraces_after_warmup"] == 0
        assert eng._ragged_fn.fn._cache_size() == 1

    def test_mp2_chunked_mixed_parity(self):
        m = _tiny_gpt(seed=22)
        prompts = _prompts(np.random.RandomState(12), (5, 19, 11))
        refs = _engine(m).generate(prompts, max_new_tokens=8)
        reset_decode_stats()
        eng = _engine(m, serve_mesh="mp=2", chunked_prefill=True,
                      prefill_q_max=8)
        outs = eng.generate(prompts, max_new_tokens=8)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["prefill_compiles"] == 0
        assert st["ragged_retraces"] == 0

    def test_mp2_spec_verify_parity(self):
        m = _tiny_gpt(seed=23)
        prompts = _prompts(np.random.RandomState(13), (5, 9, 13))
        refs = _engine(m).generate(prompts, max_new_tokens=10)
        reset_decode_stats()
        eng = _engine(m, serve_mesh="mp=2", spec_decode_k=3)
        outs = eng.generate(prompts, max_new_tokens=10)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["verify_compiles"] == 0
        assert st["spec_steps"] > 0
        assert st["ragged_retraces"] == 0
        assert st["retraces_after_warmup"] == 0

    @needs_mesh4
    @pytest.mark.slow  # tier-1 budget: mp=2 fast lane pins the contract
    def test_mp4_parity_one_executable(self):
        m = _tiny_gpt(seed=24)
        prompts = _prompts(np.random.RandomState(14), (5, 9, 13))
        refs = _engine(m).generate(prompts, max_new_tokens=10)
        reset_decode_stats()
        eng = _engine(m, serve_mesh="mp=4", chunked_prefill=True,
                      prefill_q_max=8)
        outs = eng.generate(prompts, max_new_tokens=10)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["ragged_retraces"] == 0
        assert st["retraces_after_warmup"] == 0

    @pytest.mark.slow  # tier-1 budget: bit parity is per KV mode
    def test_mp2_int8_kv_parity(self):
        """The quantized twin shards too: pages AND per-page scales
        partition on the head axis, parity against single-chip int8."""
        m = _tiny_gpt(seed=25)
        prompts = _prompts(np.random.RandomState(15), (6, 11))
        refs = _engine(m, kv_quant="int8").generate(
            prompts, max_new_tokens=8)
        reset_decode_stats()
        eng = _engine(m, kv_quant="int8", serve_mesh="mp=2")
        outs = eng.generate(prompts, max_new_tokens=8)
        for o, r in zip(outs, refs):
            assert o == r, (o, r)
        st = decode_stats()
        assert st["ragged_compiles"] == 1
        assert st["ragged_retraces"] == 0


# ---------------------------------------------------------------------------
# the sharded program: HLO collectives + the costmodel's ICI term
# ---------------------------------------------------------------------------
@needs_mesh
class TestShardedProgram:
    def test_hlo_all_reduce_at_row_parallel_boundaries(self):
        """The partitioned step's OPTIMIZED HLO must communicate where
        the math says it must: a row-split matmul (out_w / fc2_w)
        yields partial sums that only an all-reduce can finish.
        Asserted against the compiled HLO text, not a counter."""
        from paddle_tpu.parallel.partition import hlo_collectives

        m = _tiny_gpt(seed=26)
        prompts = _prompts(np.random.RandomState(16), (5, 9))
        eng = _engine(m, serve_mesh="mp=2")
        eng.generate(prompts, max_new_tokens=4)
        tr = eng._ragged_fn
        q = eng._q_ragged
        tokens = eng._dev(np.zeros((eng._slots, q), np.int32))
        caps = eng._dev(np.zeros((eng._slots,), np.int32))
        key = eng._dev(jax.random.PRNGKey(0))
        lowered = tr.fn.lower(
            eng._params, eng._kv,
            eng._dev(eng._bt), eng._dev(eng._lens), tokens, caps, key)
        hlo = lowered.compile().as_text()
        colls = hlo_collectives(hlo)
        assert "all-reduce" in colls, sorted(colls)
        assert colls["all-reduce"]["count"] >= 1
        assert colls["all-reduce"]["bytes"] > 0
        # lowering an AOT twin must not have touched the warm cache
        assert tr.fn._cache_size() == 1

    def test_collective_bytes_on_sharded_profiles_only(self):
        """The cost observatory's interconnect term: nonzero exactly on
        profiles extracted from mesh-sharded executables, and the
        roofline picks up the ICI addend only there."""
        from paddle_tpu.observability import costmodel

        m = _tiny_gpt(seed=27)
        prompts = _prompts(np.random.RandomState(17), (5, 9))
        costmodel.clear_profiles()
        eng = _engine(m, serve_mesh="mp=2")
        eng.generate(prompts, max_new_tokens=4)
        prof = eng._cost.profile_for("ragged")
        assert prof.collective_bytes > 0
        base = max(prof.flops / eng._cost.peaks["flops"],
                   prof.bytes_accessed / eng._cost.peaks["bytes_per_s"])
        assert eng._cost.raw_seconds(prof) == pytest.approx(
            base + prof.collective_bytes
            / eng._cost.peaks["ici_bytes_per_s"])
        assert eng._cost.peaks["ici_bytes_per_s"] > 0

        costmodel.clear_profiles()
        one = _engine(m, ragged_step=True)
        one.generate(prompts, max_new_tokens=4)
        p1 = one._cost.profile_for("ragged")
        assert p1.collective_bytes == 0
        assert one._cost.raw_seconds(p1) == pytest.approx(
            max(p1.flops / one._cost.peaks["flops"],
                p1.bytes_accessed / one._cost.peaks["bytes_per_s"]))

    def test_peak_ici_flag_moves_the_term(self):
        from paddle_tpu.observability.costmodel import resolve_peaks

        assert resolve_peaks()["ici_bytes_per_s"] == 1.0e10
        paddle.set_flags({"FLAGS_peak_ici_gbps": 25.0})
        try:
            assert resolve_peaks()["ici_bytes_per_s"] == 25.0e9
        finally:
            paddle.set_flags({"FLAGS_peak_ici_gbps": 0.0})

    def test_chip_skew_probe_on_sharded_engine(self):
        """A probed sharded step records per-chip completion skew;
        /profilez (Profiler.statusz) surfaces the table and the
        single-chip engine stays skew-silent."""
        m = _tiny_gpt(seed=28)
        prompts = _prompts(np.random.RandomState(18), (5, 9))
        eng = _engine(m, serve_mesh="mp=2", profile=True,
                      profile_sample_steps=1)
        eng.generate(prompts, max_new_tokens=4)
        sk = eng._profiling.statusz()["chip_skew_seconds"]
        assert sk is not None and sk["probes"] > 0
        assert sk["max_s"] >= sk["last_s"] >= 0.0
        one = _engine(m, ragged_step=True, profile=True,
                      profile_sample_steps=1)
        one.generate(prompts, max_new_tokens=4)
        assert one._profiling.statusz()["chip_skew_seconds"] is None


# ---------------------------------------------------------------------------
# identity, config plumbing, and the strict OFF path
# ---------------------------------------------------------------------------
@needs_mesh
class TestMeshLifecycle:
    def test_off_path_bit_exact_and_fingerprint(self):
        """serve_mesh unset IS the pre-mesh engine: same fingerprint
        as an engine that never heard of the feature, no mesh objects,
        and the mesh folds into the fingerprint when armed."""
        m = _tiny_gpt(seed=29)
        on = _engine(m, serve_mesh="mp=2")
        off = _engine(m, serve_mesh="", ragged_step=True)
        default = _engine(m, ragged_step=True)
        assert on.config_fingerprint() != off.config_fingerprint()
        assert off.config_fingerprint() == default.config_fingerprint()
        assert off._mesh is None and default._mesh is None
        assert off.statusz()["config"]["serve_mesh"] == ""
        assert off.statusz()["config"]["mesh_devices"] == 1
        assert on.statusz()["config"]["serve_mesh"] == "mp=2"
        assert on.statusz()["config"]["mesh_devices"] == 2

    def test_flag_arms_mesh_and_arg_wins(self):
        m = _tiny_gpt(seed=30)
        p = _prompts(np.random.RandomState(19), (6,))[0]
        ref = _engine(m).generate([p], max_new_tokens=6)[0]
        paddle.set_flags({"FLAGS_serve_mesh": "mp=2"})
        try:
            eng = _engine(m)
            assert eng._mesh is not None and eng._mesh_mp == 2
            assert eng.generate([p], max_new_tokens=6)[0] == ref
            # explicit arg beats the flag
            assert _engine(m, serve_mesh="")._mesh is None
        finally:
            paddle.set_flags({"FLAGS_serve_mesh": ""})

    def test_wire_config_round_trip_rebuilds_sharded(self):
        """The journal's config record carries the mesh: rebuilding
        from `wire_config` arms the SAME mesh (equal fingerprints) and
        serves identically."""
        from paddle_tpu.inference.serving import DecodeEngine

        m = _tiny_gpt(seed=31)
        prompts = _prompts(np.random.RandomState(20), (5, 9))
        eng = _engine(m, serve_mesh="mp=2")
        refs = eng.generate(prompts, max_new_tokens=6)
        cfg = eng.wire_config()
        assert cfg["serve_mesh"] == "mp=2"
        import json

        cfg = json.loads(json.dumps(cfg))  # the journal's wire trip
        eng2 = DecodeEngine(m, **cfg)
        assert eng2._mesh is not None and eng2._mesh_mp == 2
        assert eng2.config_fingerprint() == eng.config_fingerprint()
        assert eng2.generate(prompts, max_new_tokens=6) == refs

    def test_validation_errors(self):
        m = _tiny_gpt(seed=32)
        with pytest.raises(ValueError, match="bad mesh spec"):
            _engine(m, serve_mesh="mp=two")
        with pytest.raises(ValueError, match="single tensor-parallel"):
            _engine(m, serve_mesh="dp=2,mp=2")
        with pytest.raises(ValueError, match="not divisible"):
            _engine(m, serve_mesh="mp=8")  # 4 heads over 8 chips
        with pytest.raises(ValueError, match="devices"):
            _engine(m, serve_mesh="mp=16")
        with pytest.raises(ValueError, match="ragged"):
            _engine(m, serve_mesh="mp=2", ragged_step=False)

    @pytest.mark.slow  # compile-heavy: serve, kill, sharded rebuild
    def test_restore_rebuilds_sharded_engine(self, tmp_path):
        """Durable recovery of a SHARDED engine: journal + snapshot
        written mid-serve rebuild an engine with the mesh armed (the
        config record carries the spec) and the finished generations
        are bit-identical to an uninterrupted serve."""
        from paddle_tpu.inference.durability import restore_from_dir

        m = _tiny_gpt(seed=33)
        prompts = _prompts(np.random.RandomState(21), (5, 9))
        reference = _engine(m).generate(prompts, max_new_tokens=8)
        d = str(tmp_path / "j")
        paddle.set_flags({"snapshot_interval_steps": 3})
        try:
            eng = _engine(m, serve_mesh="mp=2", journal_dir=d)
            reqs = [eng.add_request(list(map(int, p)), max_new_tokens=8)
                    for p in prompts]
            for _ in range(6):
                eng.step()
        finally:
            paddle.set_flags({"snapshot_interval_steps": 32})
        eng._durability.flush()
        eng2, rmap = restore_from_dir(d, m)
        assert eng2._mesh is not None and eng2._mesh_mp == 2
        assert eng2.config_fingerprint() == eng.config_fingerprint()
        eng2.run()
        order = sorted(rmap)
        assert sorted(r.request_id for r in reqs) == order
        assert [list(rmap[r].generated_ids) for r in order] == reference
