"""Quantized KV serving (FLAGS_kv_quant=int8) — ISSUE 12 acceptance.

Contracts pinned here:

* ``kv_quant="off"`` (the default) is BIT-EXACT with the historical
  engine and constructs the exact same executables (zero new
  executables, zero quant counters) — the parity oracle;
* int8 mode stores pages as int8 with per-page, per-head scales in
  parallel donated ``*_scales`` arrays, serves greedy decode
  deterministically (same engine config twice -> identical tokens),
  and token output tracks the fp32 engine closely (the hard >=99%
  quality gate lives in tools/bench_kv_quant.py where the workload is
  controlled; here the bar is structural);
* a RECYCLED page's stale quant scale can never leak into its next
  owner: the allocation-time scale reset makes an evict/realloc cycle
  reproduce the original serve bit for bit;
* the write path counts refolds and fresh pages
  (``decode_stats kv_quant_*``, ``paddle_kv_quant_*`` metrics), the
  flight recorder stamps the pool's byte occupancy per step, and the
  page-size autotune cache keys on the quantized STORAGE dtype (an
  int8 pool never reuses an fp32-picked page size);
* the quantized Pallas decode kernel (interpret mode) matches the
  quantized XLA reference within the same tolerance envelope as the
  existing fp32 kernel-vs-reference parity, and the dequantized
  operands themselves are bit-identical between the two backends;
* durability round-trip: snapshot + ``restore_from_dir`` of a
  quantized engine restores the cached pages' int8 payloads AND
  scales exactly (sidecar install), the restored greedy continuation
  matches the uninterrupted quantized reference, and the quantized
  snapshot is <= 0.6x the fp32 snapshot bytes on the same workload.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.ops.pallas import flash_attention as FA
from paddle_tpu.ops.pallas import paged_attention as PA
from paddle_tpu.inference.serving import (DecodeEngine, decode_stats,
                                          reset_decode_stats)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    reset_decode_stats()
    obs.reset()
    obs.clear_spans()
    yield
    obs.reset()
    obs.clear_spans()


@pytest.fixture
def interpret_pallas(monkeypatch):
    orig = pl.pallas_call

    def patched(*a, **k):
        k["interpret"] = True
        return orig(*a, **k)

    monkeypatch.setattr(pl, "pallas_call", patched)
    yield


TINY = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
                 max_seq_len=128, use_parallel_layers=False, dropout=0.0)
PAGE = 4


def _tiny_gpt(seed=0):
    paddle.seed(seed)
    m = GPT(TINY)
    m.eval()
    return m


def _engine(m, **kw):
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("prefill_chunk_tokens", 8)
    return DecodeEngine(m, **kw)


def _prompts(n=3, ln=14, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, TINY.vocab_size, (ln,)).astype(np.int32)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# the write/read primitive
# ---------------------------------------------------------------------------
class TestPagedQuantWrite:
    def _pool(self, L=2, H=2, P=6, page=4, D=8):
        return (jnp.zeros((L, H, P, page, D), jnp.int8),
                jnp.zeros((L, H, P), jnp.float32))

    def test_roundtrip_within_quant_noise(self):
        pages, scales = self._pool()
        rng = np.random.RandomState(0)
        vals = jnp.asarray(rng.randn(4, 2, 8).astype(np.float32))
        page_idx = jnp.asarray([0, 0, 1, 1], jnp.int32)
        slot = jnp.asarray([0, 1, 0, 1], jnp.int32)
        pages, scales, refolds = PA.paged_quant_write(
            pages, scales, 0, vals, page_idx, slot)
        # dequantize what landed and compare against the source rows
        for r in range(4):
            p, s = int(page_idx[r]), int(slot[r])
            for h in range(2):
                sc = float(scales[0, h, p])
                deq = np.asarray(pages[0, h, p, s], np.float32) * sc
                err = np.abs(deq - np.asarray(vals[r, h]))
                assert err.max() <= sc * 0.5 + 1e-7
        # a fresh pool: nothing previously established, so no refolds
        assert int(refolds) == 0

    def test_refold_requantizes_existing_rows(self):
        pages, scales = self._pool()
        small = jnp.full((1, 2, 8), 0.5, jnp.float32)
        big = jnp.full((1, 2, 8), 4.0, jnp.float32)
        idx = jnp.asarray([0], jnp.int32)
        pages, scales, r0 = PA.paged_quant_write(
            pages, scales, 0, small, idx, jnp.asarray([0], jnp.int32))
        s_before = float(scales[0, 0, 0])
        pages, scales, r1 = PA.paged_quant_write(
            pages, scales, 0, big, idx, jnp.asarray([1], jnp.int32))
        assert int(r0) == 0 and int(r1) > 0
        assert float(scales[0, 0, 0]) > s_before
        # the earlier row re-quantized at the grown scale still
        # dequantizes to ~0.5
        sc = float(scales[0, 0, 0])
        deq = float(pages[0, 0, 0, 0, 0]) * sc
        assert abs(deq - 0.5) <= sc * 0.5 + 1e-7

    def test_oob_rows_dropped_and_scale_preserved(self):
        pages, scales = self._pool()
        vals = jnp.full((2, 2, 8), 3.0, jnp.float32)
        # row 1 targets the OOB page (num_pages): dropped entirely
        pages, scales, _ = PA.paged_quant_write(
            pages, scales, 0, vals, jnp.asarray([2, 6], jnp.int32),
            jnp.asarray([0, 0], jnp.int32))
        assert float(jnp.abs(scales[0, :, :2]).max()) == 0.0
        assert float(scales[0, 0, 2]) > 0
        assert int(jnp.abs(pages[0, :, 3:]).max()) == 0

    def test_fresh_page_wipes_stale_garbage(self):
        pages, scales = self._pool()
        # stale garbage on page 0, but its scale is 0 (freshly reset):
        # the first write must deterministically zero the stale rows
        pages = pages.at[0, :, 0, 3, :].set(77)
        vals = jnp.full((1, 2, 8), 1.0, jnp.float32)
        pages, scales, _ = PA.paged_quant_write(
            pages, scales, 0, vals, jnp.asarray([0], jnp.int32),
            jnp.asarray([0], jnp.int32))
        assert int(jnp.abs(pages[0, :, 0, 3]).max()) == 0


class TestQuantPagedAttention:
    def _quant_pool(self, seed=0, b=3, hq=4, hkv=2, d=32, page=16,
                    pages_max=8, lens=(37, 0, 128)):
        rng = np.random.RandomState(seed)
        npages = b * pages_max + 3
        kf = rng.randn(hkv, npages, page, d).astype(np.float32)
        vf = rng.randn(hkv, npages, page, d).astype(np.float32)
        ks = np.abs(kf).max(axis=(2, 3)) / PA.Q_MAX
        vs = np.abs(vf).max(axis=(2, 3)) / PA.Q_MAX
        k8 = np.clip(np.round(kf / ks[:, :, None, None]),
                     -127, 127).astype(np.int8)
        v8 = np.clip(np.round(vf / vs[:, :, None, None]),
                     -127, 127).astype(np.int8)
        bt = jnp.asarray(
            rng.permutation(npages)[:b * pages_max].reshape(b, pages_max)
            .astype(np.int32))
        q = jnp.asarray(rng.randn(b, hq, d).astype(np.float32))
        return (q, jnp.asarray(k8), jnp.asarray(v8), bt,
                jnp.asarray(np.asarray(lens, np.int32)),
                jnp.asarray(ks), jnp.asarray(vs))

    def test_pallas_matches_xla_reference(self, interpret_pallas):
        """The two quantized backends agree within the SAME envelope as
        the fp32 kernel-vs-reference parity (the online softmax is the
        only divergence; the dequant itself is bit-identical)."""
        q, k8, v8, bt, lens, ks, vs = self._quant_pool(0)
        out = PA._pallas_paged_attention(q, k8, v8, bt, lens,
                                         k_scales=ks, v_scales=vs)
        ref = PA._xla_paged_attention(q, k8, v8, bt, lens,
                                      k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_dequant_values_bit_identical(self):
        """Both backends dequantize a page as exactly ``q8 * scale`` in
        f32 — pin the reference's gathered dequant against the direct
        elementwise product so the contract can't drift."""
        _, k8, _, bt, _, ks, _ = self._quant_pool(1)
        gathered = np.asarray(k8[:, bt].astype(jnp.float32)
                              * ks[:, bt][..., None, None])
        direct = np.asarray(k8, np.float32) * \
            np.asarray(ks)[:, :, None, None]
        np.testing.assert_array_equal(
            gathered, direct[:, np.asarray(bt)])

    def test_quant_multi_query_matches_reference(self, interpret_pallas):
        q, k8, v8, bt, lens, ks, vs = self._quant_pool(
            2, lens=(40, 17, 96))
        rng = np.random.RandomState(9)
        qm = jnp.asarray(rng.randn(3, 4, 4, 32).astype(np.float32))
        out = PA._pallas_paged_attention(qm, k8, v8, bt, lens,
                                         k_scales=ks, v_scales=vs)
        ref = PA._xla_paged_attention(qm, k8, v8, bt, lens,
                                      k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("qn", [1, 4])
    @pytest.mark.parametrize("hb", [4, 1])
    def test_quant_heads_per_block_matches_reference(
            self, interpret_pallas, monkeypatch, hb, qn):
        """The int8 kernel with every K/V head of a page in one grid step
        (``hb`` = Hkv) and with one head a step: each head reads its own
        row of the gathered scales."""
        q, k8, v8, bt, lens, ks, vs = self._quant_pool(
            4, hq=8, hkv=4, lens=(40, 0, 96))
        rng = np.random.RandomState(qn)
        qm = jnp.asarray(rng.randn(3, qn, 8, 32).astype(np.float32))
        rows = -(-qn * 2 // 8) * 8
        monkeypatch.setattr(PA, "_PAGED_VMEM_BUDGET", hb *
                            PA._head_vmem_bytes(rows, 16, 32, jnp.float32,
                                                jnp.int8))
        assert PA.heads_per_block(4, rows, 16, 32, jnp.float32,
                                  jnp.int8) == hb
        out = PA._pallas_paged_attention(qm, k8, v8, bt, lens,
                                         k_scales=ks, v_scales=vs)
        ref = PA._xla_paged_attention(qm, k8, v8, bt, lens,
                                      k_scales=ks, v_scales=vs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_entry_point_validates_scales(self):
        q, k8, v8, bt, lens, ks, vs = self._quant_pool(3)
        with pytest.raises(ValueError, match="int8 KV pages need"):
            PA.paged_attention(q, k8, v8, bt, lens)
        with pytest.raises(ValueError, match="k_scales shape"):
            PA.paged_attention(q, k8, v8, bt, lens,
                               k_scales=ks[:, :4], v_scales=vs)
        with pytest.raises(ValueError, match="v_scales shape"):
            PA.paged_attention(q, k8, v8, bt, lens,
                               k_scales=ks, v_scales=vs[:, :4])
        kf = jnp.asarray(np.zeros(k8.shape, np.float32))
        with pytest.raises(ValueError, match="non-int8"):
            PA.paged_attention(q, kf, kf, bt, lens,
                               k_scales=ks, v_scales=vs)


# ---------------------------------------------------------------------------
# page-size autotune keying (satellite)
# ---------------------------------------------------------------------------
class TestAutotuneStorageDtypeKey:
    def test_entries_keyed_and_validated_independently(self, monkeypatch):
        monkeypatch.setattr(FA, "_AUTOTUNE_LOADED", True)
        kf = PA._paged_key(1024, 64, jnp.float32)
        k8 = PA._paged_key(1024, 64, jnp.int8)
        assert kf != k8
        monkeypatch.setitem(FA._AUTOTUNE, kf, 64)
        monkeypatch.setitem(FA._AUTOTUNE, k8, 32)
        assert PA.cached_page_size(1024, 64, jnp.float32) == 64
        assert PA.cached_page_size(1024, 64, jnp.int8) == 32
        # a bad int8 entry degrades ONLY the int8 lookup
        monkeypatch.setitem(FA._AUTOTUNE, k8, 48)
        assert PA.cached_page_size(1024, 64, jnp.int8) is None
        assert PA.cached_page_size(1024, 64, jnp.float32) == 64

    def test_engine_picks_page_size_by_storage_dtype(self, monkeypatch):
        """An int8 pool must consult the int8 autotune entry, never the
        fp32 one — the regression the satellite pins."""
        monkeypatch.setattr(FA, "_AUTOTUNE_LOADED", True)
        m = _tiny_gpt()
        monkeypatch.setitem(
            FA._AUTOTUNE, PA._paged_key(64, TINY.hidden_size // 4,
                                        jnp.float32), 64)
        monkeypatch.setitem(
            FA._AUTOTUNE, PA._paged_key(64, TINY.hidden_size // 4,
                                        jnp.int8), 32)
        e_f = DecodeEngine(m, max_batch_size=1, max_seq_len=64)
        e_q = DecodeEngine(m, max_batch_size=1, max_seq_len=64,
                           kv_quant="int8")
        assert e_f._page == 64
        assert e_q._page == 32


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
class TestQuantEngine:
    def test_off_mode_bit_exact_and_quiet(self):
        m = _tiny_gpt()
        prompts = _prompts()
        default = _engine(m)
        out_default = default.generate(prompts, max_new_tokens=4)
        assert default._kv_quant is False and not default._kv.quantized
        reset_decode_stats()
        off = _engine(m, kv_quant="off")
        out_off = off.generate(prompts, max_new_tokens=4)
        assert out_off == out_default
        st = decode_stats()
        assert st["kv_quant_pages"] == 0
        assert st["kv_quant_refolds"] == 0
        assert st["kv_quant_compiles"] == 0  # zero new executables
        assert st["retraces_after_warmup"] == 0

    @pytest.mark.parametrize("which", ["_decode_fn", "_mixed_fn"])
    def test_float_step_takes_the_pool_as_two_donated_operands(self, which):
        """What the compile cache and the benchmark's `classify` lean on:
        a float engine's step executables take the weights, then the
        pool as exactly two arrays (K, V — no scale operand), donated
        and nothing else donated, and hand the pool back first."""
        eng = _engine(_tiny_gpt())
        eng.generate(_prompts(), max_new_tokens=4)
        tracker = getattr(eng, which)
        assert tracker.donate_argnums == (1,)
        lowered = tracker.lower()
        args, _ = lowered.args_info
        pool = jax.tree_util.tree_leaves(args[1])
        assert [tuple(a.shape) for a in pool] == \
            [tuple(eng._kv.k.shape), tuple(eng._kv.v.shape)]
        assert all(a.donated for a in pool)
        n_weights = len(jax.tree_util.tree_leaves(args[0]))
        flat = jax.tree_util.tree_leaves(args)
        assert flat[n_weights:n_weights + 2] == pool
        assert sum(a.donated for a in flat) == 2
        text = lowered.as_text()
        assert text.count("tf.aliasing_output") == 2
        assert 'jax.result_info = "result[0].k"' in text
        assert "xi8>" not in text  # no int8 storage in a float engine

    def test_invalid_mode_rejected(self):
        with pytest.raises(ValueError, match="kv_quant"):
            _engine(_tiny_gpt(), kv_quant="fp4")

    def test_quant_serve_deterministic_and_counted(self):
        m = _tiny_gpt()
        prompts = _prompts(2)
        e1 = _engine(m, kv_quant="int8")
        out1 = e1.generate(prompts, max_new_tokens=4)
        st = decode_stats()
        assert st["kv_quant_pages"] > 0
        assert st["kv_quant_compiles"] == 1  # the scale-reset exec
        assert st["retraces_after_warmup"] == 0
        e2 = _engine(m, kv_quant="int8")
        out2 = e2.generate(prompts, max_new_tokens=4)
        assert out1 == out2
        assert e1._kv.dtype == jnp.int8
        assert e1._kv.k_scales.shape == (TINY.num_layers, TINY.num_heads,
                                      e1.pool.num_pages)

    def test_quant_tracks_f32_outputs(self):
        """Token-level agreement with the fp32 engine.  The hard >=99%
        gate lives in tools/bench_kv_quant.py (teacher-forced, cascade-
        free); here the bar is that quantization is not nonsense."""
        m = _tiny_gpt()
        prompts = _prompts(3, 14)
        ref = _engine(m).generate(prompts, max_new_tokens=6)
        out = _engine(m, kv_quant="int8").generate(prompts,
                                                   max_new_tokens=6)
        total = sum(len(s) for s in ref)
        match = sum(int(a == b) for sr, so in zip(ref, out)
                    for a, b in zip(sr, so))
        assert match / total >= 0.5, (match, total, ref, out)

    def test_recycled_page_scale_reset_reproduces(self):
        """Evict/realloc cycles must not make quantization history-
        dependent: serving family A, then enough families to recycle
        every page, then A again yields bit-identical tokens for A."""
        m = _tiny_gpt()
        pages_per_req = -(-(20 + 6 - 1) // PAGE)
        eng = _engine(m, kv_quant="int8", max_batch_size=1,
                      num_pages=pages_per_req + 2)

        def serve(seed):
            rng = np.random.RandomState(seed)
            p = rng.randint(0, TINY.vocab_size, (20,)).astype(np.int32)
            return eng.generate([p], max_new_tokens=6)[0]

        first = serve(7)
        for s in (8, 9, 10):
            serve(s)  # distinct families: recycle the pool
        assert eng.pool.evictions > 0
        again = serve(7)
        assert again == first

    def test_spec_quant_serves_and_stays_clean(self):
        m = _tiny_gpt()
        prompts = _prompts(2)
        base = _engine(m, kv_quant="int8").generate(prompts,
                                                    max_new_tokens=6)
        spec = _engine(m, kv_quant="int8", spec_decode_k=3)
        out = spec.generate(prompts, max_new_tokens=6)
        st = decode_stats()
        assert st["retraces_after_warmup"] == 0
        assert st["spec_steps"] > 0
        # greedy agreement (the fp32 bit-parity oracle weakens to
        # token agreement under quantization: a rejected draft row's
        # absmax may grow a page scale before rollback)
        total = sum(len(s) for s in base)
        match = sum(int(a == b) for sb, so in zip(base, out)
                    for a, b in zip(sb, so))
        assert match / total >= 0.5, (base, out)

    def test_quant_telemetry_surfaces(self):
        m = _tiny_gpt()
        eng = _engine(m, kv_quant="int8")
        eng.generate(_prompts(2), max_new_tokens=4)
        snap = obs.snapshot()
        assert snap["paddle_kv_quant_pages_total"]["series"][0][
            "value"] > 0
        # registry label sets persist across obs.reset(): pick THIS
        # engine's series, not a zeroed predecessor's
        bpt = next(
            s["value"]
            for s in snap["paddle_kv_quant_bytes_per_token"]["series"]
            if s["labels"].get("engine") == str(eng._engine_id)
            or s["labels"].get("engine") == eng._engine_id)
        occ = eng._kv_byte_occupancy()
        assert bpt == occ["bytes_per_token"]
        # int8 + f32 scales per token vs 4 bytes/elem fp32: ~0.26x
        f32_bpt = _engine(m)._kv_byte_occupancy()["bytes_per_token"]
        assert bpt < 0.3 * f32_bpt
        # flight records stamp the byte occupancy
        rec = [r for r in eng._flight.records() if r["kind"] == "step"]
        assert rec and rec[-1]["pool"]["kv_bytes"]["dtype"] == "int8"
        assert rec[-1]["pool"]["kv_bytes"]["payload_bytes"] > 0
        assert eng.statusz()["config"]["kv_quant"] == "int8"

    def test_wire_config_carries_kv_quant(self):
        eng = _engine(_tiny_gpt(), kv_quant="int8")
        assert eng.wire_config()["kv_quant"] == "int8"
        assert _engine(_tiny_gpt()).wire_config()["kv_quant"] == "off"

    def test_fingerprints_differ_by_mode(self):
        m = _tiny_gpt()
        assert _engine(m).config_fingerprint() != \
            _engine(m, kv_quant="int8").config_fingerprint()


# ---------------------------------------------------------------------------
# durability round-trip (satellite)
# ---------------------------------------------------------------------------
class TestQuantDurability:
    def _serve_and_snapshot(self, m, prompts, mode, d):
        eng = _engine(m, kv_quant=mode, journal_dir=str(d))
        reqs = [eng.add_request(p, max_new_tokens=12) for p in prompts]
        for _ in range(8):
            eng.step()  # partial serve: every request still in flight
        assert all(r.state != "done" for r in reqs)
        eng._durability.flush()
        eng._durability.write_snapshot()
        return eng, reqs

    def test_round_trip_restores_payloads_and_continuation(self,
                                                           tmp_path):
        """Round trip + the snapshot-byte gate in ONE pair of serves
        (both modes snapshot the same workload; the int8 one restores
        and must continue bit-identically)."""
        from paddle_tpu.inference.durability import (KV_PAGES_NAME,
                                                     SNAPSHOT_NAME,
                                                     load_snapshot,
                                                     restore_from_dir)

        m = _tiny_gpt()
        prompts = _prompts(3, 14)
        sizes = {}
        for mode in ("off", "int8"):
            d = tmp_path / mode
            eng, reqs = self._serve_and_snapshot(m, prompts, mode, d)
            sizes[mode] = sum(
                os.path.getsize(os.path.join(str(d), f))
                for f in (SNAPSHOT_NAME, KV_PAGES_NAME))
        # the quantized snapshot (payload sidecar included) is a
        # fraction of the fp32 one on the same workload
        assert sizes["int8"] <= 0.6 * sizes["off"], sizes
        d = tmp_path / "int8"
        snap = load_snapshot(str(d))
        assert snap is not None and snap.kv is not None
        assert snap.kv["dtype"] == "int8"
        eng2, rmap = restore_from_dir(str(d), m)
        # the installed cached pages carry the DEAD engine's exact
        # int8 payloads and scales
        installed = sorted(eng2.pool._page_hash.items())
        assert installed, "sidecar install must map the cached pages"
        ids_new = [p for p, _ in installed]
        ids_old = [eng.pool._hash_to_page[h] for _, h in installed]
        for new_arr, old_arr in (
                (eng2._kv.k, eng._kv.k),
                (eng2._kv.v, eng._kv.v),
                (eng2._kv.k_scales, eng._kv.k_scales),
                (eng2._kv.v_scales, eng._kv.v_scales)):
            np.testing.assert_array_equal(
                np.asarray(jax.device_get(new_arr[:, :, ids_new])),
                np.asarray(jax.device_get(old_arr[:, :, ids_old])))
        eng2.run()
        ref = _engine(m, kv_quant="int8").generate(prompts,
                                                   max_new_tokens=12)
        got = [list(rmap[r.request_id].generated_ids) for r in reqs]
        assert got == ref  # identical to the uninterrupted reference

    def test_torn_sidecar_falls_back_to_recompute(self, tmp_path):
        from paddle_tpu.inference.durability import (KV_PAGES_NAME,
                                                     restore_from_dir)

        m = _tiny_gpt()
        prompts = _prompts(2, 14)
        d = tmp_path / "torn"
        _, reqs = self._serve_and_snapshot(m, prompts, "int8", d)
        path = os.path.join(str(d), KV_PAGES_NAME)
        data = bytearray(open(path, "rb").read())
        data[len(data) // 2] ^= 0xFF
        open(path, "wb").write(bytes(data))
        eng2, rmap = restore_from_dir(str(d), m)
        assert not eng2.pool._page_hash  # crc failed: nothing installed
        eng2.run()
        for r in reqs:
            assert rmap[r.request_id].state == "done"

    def test_stateful_drafter_skips_install(self, tmp_path):
        """A draft-MODEL engine must NOT install sidecar pages: the
        sidecar carries only the target pool, and a prefix hit over an
        empty draft cache would silently collapse acceptance.  Full
        recompute (which feeds the drafter via ingest_chunks) runs
        instead, and the restored serve still completes."""
        from paddle_tpu.inference.durability import restore_from_dir
        from paddle_tpu.inference.speculative import DraftModelDrafter

        m = _tiny_gpt()
        dm = GPT(TINY.draft_config())
        dm.eval()
        d = tmp_path / "draft"
        eng = _engine(m, kv_quant="int8", journal_dir=str(d),
                      spec_decode_k=2, drafter=DraftModelDrafter(dm))
        reqs = [eng.add_request(p, max_new_tokens=12)
                for p in _prompts(2)]
        for _ in range(6):
            eng.step()
        eng._durability.flush()
        eng._durability.write_snapshot()
        eng2, rmap = restore_from_dir(
            str(d), m, drafter=DraftModelDrafter(dm))
        assert not eng2.pool._page_hash  # install skipped
        eng2.run()
        for r in reqs:
            assert rmap[r.request_id].state == "done"

    def test_sidecar_can_be_disabled(self, tmp_path):
        from paddle_tpu.inference.durability import (KV_PAGES_NAME,
                                                     load_snapshot)

        m = _tiny_gpt()
        paddle.set_flags({"snapshot_kv": False})
        try:
            d = tmp_path / "nokv"
            self._serve_and_snapshot(m, _prompts(1), "int8", d)
        finally:
            paddle.set_flags({"snapshot_kv": True})
        assert not os.path.exists(os.path.join(str(d), KV_PAGES_NAME))
        assert load_snapshot(str(d)).kv is None
