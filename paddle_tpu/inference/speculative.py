"""Speculative decoding: draft-propose / multi-token-verify on the
paged KV engine.

The continuous-batching engine (`inference.serving.DecodeEngine`)
advances every slot by exactly one token per step, so per-token latency
is one full target-model pass.  Speculative decoding amortizes that
pass: a cheap **drafter** proposes K tokens per slot, and one batched
**verify step** — a single donated jitted executable, the multi-query
sibling of the engine's decode step — scores all K+1 positions at once
through the ragged multi-query paged-attention kernel
(`ops.pallas.paged_attention`, per-sequence causal offsets).

Accept/resample rule (Leviathan et al., specialized to this engine's
samplers): the verify pass draws a *target token* at every position with
the exact `sample_logits` the engine uses (argmax under greedy), then
accepts drafted tokens while they match the targets and emits the first
mismatching target as the correction — or, when every draft survives,
the last target as a bonus token.  Because the emitted tokens ARE the
target model's samples, the output distribution is the target
distribution by construction: token-identical to the non-speculative
engine under greedy, and distribution-preserving under temperature /
top-k / top-p sampling.  For a point-mass drafter (prompt-lookup) this
is exactly the Leviathan rule: accept with probability p(d), resample
from norm(p - p(d)·δ_d) otherwise.

Memory protocol: speculative K/V rows are written into pages the
request already owns (`DecodeEngine._grow_block_tables(writes=...)`
reserves the verify window up front, clamped to the request's token
budget), so rejection is a pure host-side ``seq_lens`` rollback — no
allocation, no free, no retrace.  The page pool cannot distinguish a
speculative serve from a classic one.  Prefix caching
(FLAGS_prefix_cache) carries over for free: a cached prompt page holds
BOTH models' K/V (same page ids, same block tables), so a prefix hit
skips the draft-side prompt ingestion too — `DraftModelDrafter`'s
chunk cursor simply starts at the cached length.

Drafters:

* `PromptLookupDrafter` — model-free n-gram lookup over each request's
  own token history (prompt + generated).  Zero device cost; shines on
  repetition-friendly workloads (code, extraction, chat with quoting).
* `DraftModelDrafter` — a small GPT (see `GPTConfig.draft_config`)
  sharing the engine's page pool: its K/V pages are indexed by the SAME
  block tables and page ids as the target model's, so one allocator
  governs both and the rollback invariants transfer unchanged.

Preemption (`DecodeEngine.preempt`, SLO scheduler) composes for free:
it fires between steps, so a speculative round never sees a half-torn
slot — the preempted slot goes inactive (``on_finish`` resets the
drafter's cursor) and a resume re-enters through ``on_admit`` exactly
like a fresh admission.  Cached replay pages may hold draft K/V the
draft model never wrote (the bonus token of the round before the
preemption, say): drafts over such a page can only be WRONG, never
unsound — the verify pass still emits target-model samples only, so
acceptance may dip after a resume but correctness cannot.

Telemetry lands in `profiler.decode_stats`: ``acceptance_rate``,
``mean_accepted_per_step``, ``draft_time_s`` / ``verify_time_s``, and
the zero-warm-retrace contract extends to the draft and verify
executables via the shared `_JitTracker`.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from .serving import (RNG_DECODE_DOMAIN, _JitTracker,
                      _extract_gpt_params, _fold_counter,
                      _gpt_decode_step, _gpt_layer_bq, _gpt_mixed_step,
                      _gpt_prefill, _guard_tokens, _ln, _logits_of,
                      _pack_refolds, _quantize_gpt_params,
                      _reset_kv_scales, _stats_add, sample_logits)
from .. import observability as _obs
from ..observability import flight as _flight
from ..ops.pallas import paged_attention as pa

__all__ = ["Drafter", "PromptLookupDrafter", "DraftModelDrafter",
           "SpeculativeDecoder"]


# ---------------------------------------------------------------------------
# The multi-token verify step (pure, jit-compiled once per engine)
# ---------------------------------------------------------------------------
def _gpt_spec_verify(params, kv, block_tables, seq_lens, tokens,
                     write_caps, key, *, num_heads, head_dim, eps,
                     sampler, temperature, top_k, top_p):
    """Score Q = K+1 incoming tokens per slot in ONE pass: write their
    K/V into the slots' already-reserved pages (`pa.KVPool.write`,
    write-capped per sequence so rows past a request's token budget
    are left out), run ragged multi-query paged attention with per-sequence
    causal offsets, and draw a target token at every position with the
    engine's own `sample_logits`.

    tokens: [B, Q] int32 — position ``seq_lens[b] + i`` holds
    ``tokens[b, i]`` (the last sampled token followed by the K drafts);
    write_caps: [B] int32 in [0, Q] — rows ``i < write_caps[b]`` are
    written and attendable (0 = inactive slot -> zero logits, target 0
    ignored by the host); kv donated: the K/V write is in
    place, and a later rejection only shrinks the host's ``seq_lens``.
    Returns (kv, targets [B, Q] int32).

    Quantized pools: a REJECTED draft row's absmax may have grown a
    page scale before the host rolled ``seq_lens`` back, so a
    speculative quantized serve can quantize slightly differently than
    a non-speculative quantized serve over the same tokens (greedy
    equality holds for float pools and for non-speculative quantized
    engines; speculative quantized mode is gated on measured
    token-match instead).
    """
    b, qn = tokens.shape
    h = num_heads * head_dim

    pos = seq_lens[:, None] + jnp.arange(qn, dtype=jnp.int32)[None, :]
    wpe_max = params["wpe"].shape[0] - 1
    x = params["wte"][tokens] + params["wpe"][jnp.minimum(pos, wpe_max)]
    lens_now = seq_lens + write_caps
    refolds = 0

    for li, blk in enumerate(params["blocks"]):
        x, kv, r = _gpt_layer_bq(
            blk, li, x, kv, block_tables, seq_lens, write_caps, lens_now,
            num_heads=num_heads, head_dim=head_dim, eps=eps)
        refolds += r

    xf = _ln(x.reshape(b * qn, h), params["lnf_w"], params["lnf_b"], eps)
    logits = _logits_of(params, xf).astype(jnp.float32)
    logits = logits.reshape(b, qn, -1)
    # one target draw per position, through the exact engine sampler —
    # the emitted tokens ARE these draws, which is what makes the accept
    # rule distribution-preserving (greedy ignores the key)
    targets = [
        _guard_tokens(
            logits[:, i],
            sample_logits(logits[:, i], sampler=sampler,
                          temperature=temperature, top_k=top_k,
                          top_p=top_p, key=jax.random.fold_in(key, i)))
        for i in range(qn)
    ]
    return kv, _pack_refolds(kv, jnp.stack(targets, axis=1), refolds)


# ---------------------------------------------------------------------------
# Drafters
# ---------------------------------------------------------------------------
class Drafter:
    """Proposes K draft tokens per active slot each speculative round.

    Lifecycle: ``bind(engine, k)`` once at engine construction, then
    per-request ``on_admit``/``on_finish`` and per-round
    ``propose``/``on_accept``.  ``propose`` runs between engine steps
    (host time there is drafting budget, not device idle time)."""

    name = "base"
    # stateful drafters carry per-slot device state (draft K/V lens);
    # after speculation degrades off (inference.resilience) only a
    # STATELESS drafter can be probed back on mid-serve — a stateful
    # one would need a full per-slot resync its fixed-frame catch-up
    # cannot express, so it stays degraded until recovery/restart
    stateful = False

    def bind(self, engine, k: int):
        if getattr(self, "engine", None) is not None and \
                self.engine is not engine:
            # a drafter carries per-engine state (draft pages, lens
            # bookkeeping); silently rebinding would cross-wire two
            # engines' slot state
            raise ValueError(
                "drafter is already bound to another engine: construct "
                "one drafter per DecodeEngine")
        self.engine = engine
        self.k = int(k)

    def on_admit(self, slot: int, req):
        pass

    def on_finish(self, slot: int, req):
        pass

    def ingest_chunks(self, tokens, caps):
        """Chunked prefill (FLAGS_chunked_prefill): the engine just fed
        these prompt chunks to the target model — ``tokens`` is the
        [slots, Q_max] mixed batch, ``caps[s]`` the chunk length slot
        ``s`` consumed (0 = not prefilling this step).  Model-backed
        drafters ingest the same chunks into their own K/V here; host
        drafters need nothing."""
        pass

    def propose(self, write_caps) -> np.ndarray:
        """Return [slots, K] int32 draft tokens (inactive rows ignored).
        ``write_caps[s]`` is the verify window (K/V writes) slot ``s``
        gets this round — at most ``write_caps[s] - 1`` drafts of it can
        be accepted, so drafters may stop early.  ``write_caps[s] == 0``
        means the slot sits this round out (still prefilling its prompt
        chunks): its row is ignored and must not be advanced."""
        raise NotImplementedError

    def on_accept(self, slot: int, pos_before: int, n_emitted: int):
        """Called per slot after the verify: ``n_emitted`` tokens were
        appended and the slot's KV length moved to
        ``pos_before + n_emitted`` (the rollback, if any, already
        happened on the engine's side)."""
        pass


class PromptLookupDrafter(Drafter):
    """Model-free prompt-lookup (n-gram) drafter: propose the
    continuation of the most recent earlier occurrence of the sequence's
    current n-gram suffix, longest n first.  The LLM serving analog of
    "assume the text repeats itself" — free to compute, surprisingly
    strong on extraction/code/chat workloads, and the q-distribution is
    a point mass so the accept rule is exactly Leviathan's."""

    name = "prompt_lookup"

    def __init__(self, ngram_max: int = 3, ngram_min: int = 1):
        if ngram_min < 1 or ngram_max < ngram_min:
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max, got "
                f"[{ngram_min}, {ngram_max}]")
        self.ngram_max = int(ngram_max)
        self.ngram_min = int(ngram_min)

    def _lookup(self, hist: np.ndarray) -> np.ndarray:
        k = self.k
        ln = len(hist)
        for n in range(min(self.ngram_max, ln - 1), self.ngram_min - 1,
                       -1):
            suffix = hist[ln - n:]
            # candidate starts s <= ln-n-1: the window is strictly
            # earlier than the suffix itself, so a continuation exists
            wins = np.lib.stride_tricks.sliding_window_view(
                hist, n)[:ln - n]
            hits = np.nonzero((wins == suffix).all(axis=1))[0]
            if hits.size:
                s = int(hits[-1])  # most recent occurrence
                cont = hist[s + n: s + n + k]
                if cont.size < k:
                    cont = np.concatenate(
                        [cont, np.full(k - cont.size, hist[-1],
                                       hist.dtype)])
                return cont
        # no n-gram recurs yet: propose a flat repeat of the last token
        # (wrong drafts cost nothing beyond the verify row they ride in)
        return np.full(k, hist[-1], hist.dtype)

    def propose(self, write_caps) -> np.ndarray:
        eng = self.engine
        write_caps = np.asarray(write_caps)
        out = np.zeros((eng._slots, self.k), np.int32)
        for s in range(eng._slots):
            if not eng._active[s] or write_caps[s] == 0:
                continue  # cap 0: still prefilling — skip the slot
            req = eng._by_slot[s]
            hist = np.asarray(req.prompt_ids + req.output_ids, np.int32)
            out[s] = self._lookup(hist)
        return out


class DraftModelDrafter(Drafter):
    """Small-GPT drafter sharing the engine's page pool: the draft
    model's K/V pages are indexed by the SAME block tables and page ids
    the target uses — one allocator governs both caches, so admission,
    growth, rollback, and eviction need no drafter-specific accounting.

    The draft decodes greedily (argmax maximizes the match probability
    against the verify targets).  Per round it runs ONE multi-query
    catch-up pass (ingest the tokens the verify accepted last round —
    the same `_gpt_spec_verify` executable shape, over the draft
    weights) followed by K-1 single-token steps (the engine's own
    `_gpt_decode_step`, over the draft weights).  All draft executables
    ride the `_JitTracker` retrace contract."""

    name = "draft_model"
    stateful = True  # per-slot draft K/V cursors: see Drafter.stateful

    def __init__(self, draft_model):
        cfg = draft_model.cfg
        if getattr(cfg, "dropout", 0.0) and draft_model.training:
            raise ValueError(
                "draft model must be in eval mode (cfg.dropout > 0)")
        self._params = _extract_gpt_params(draft_model)
        self._num_heads = cfg.num_heads
        self._head_dim = cfg.hidden_size // cfg.num_heads
        self._eps = float(getattr(draft_model.ln_f, "_epsilon", 1e-5))
        self._vocab = cfg.vocab_size
        self._max_pos = cfg.max_seq_len

    def bind(self, engine, k: int):
        super().bind(engine, k)
        if self._vocab != engine._params["wte"].shape[0]:
            raise ValueError(
                f"draft vocab {self._vocab} != target vocab "
                f"{engine._params['wte'].shape[0]}: the drafter must "
                f"propose over the target's token space")
        if self._max_pos < engine._max_seq_len:
            raise ValueError(
                f"draft position table ({self._max_pos}) shorter than "
                f"the engine horizon ({engine._max_seq_len})")
        # the draft weights quantize WITH the engine: a serve_weights=
        # int8 target with an f32 drafter would leave the drafter's
        # K-1 steps per round streaming 4-byte weights on the same
        # bandwidth-bound path the fold just relieved.  Guarded so a
        # rebound drafter never quantizes already-int8 leaves.
        if engine._weight_quant and \
                "qkv_w" in self._params["blocks"][0]:
            self._params, mats, saved = _quantize_gpt_params(self._params)
            _stats_add(weight_quant_mats=mats,
                       weight_quant_bytes_saved=saved)
            _obs.WEIGHT_QUANT_SAVED_BYTES.inc(
                saved, engine=engine._engine_id)
        n_layers = len(self._params["blocks"])
        # the draft cache is stored as the engine's is (same page ids,
        # same storage dtype, its own scale arrays when quantized): the
        # density win covers both pools
        self._kv = pa.KVPool.zeros(
            n_layers, self._num_heads, engine.pool.num_pages, engine._page,
            self._head_dim, engine._kv.dtype)
        self._scale_reset_fn = None
        self._lens = np.zeros(engine._slots, np.int32)
        greedy = dict(sampler="greedy", temperature=1.0, top_k=0,
                      top_p=1.0)
        self._greedy = greedy
        self._chunk_fn = None  # chunked prefill ingest (lazy)
        self._catch_fn = _JitTracker(
            functools.partial(_gpt_spec_verify,
                              num_heads=self._num_heads,
                              head_dim=self._head_dim,
                              eps=self._eps, **greedy),
            "draft_compiles", donate_argnums=(1,),
            site="DraftModelDrafter catch-up (_gpt_spec_verify)")
        self._step_fn = _JitTracker(
            functools.partial(_gpt_decode_step,
                              num_heads=self._num_heads,
                              head_dim=self._head_dim,
                              eps=self._eps, **greedy),
            "draft_compiles", donate_argnums=(1,),
            site="DraftModelDrafter step (_gpt_decode_step)")
        self._prefill_fns = {}

    def _scale_reset_tracker(self) -> _JitTracker:
        """The drafter's OWN scale-reset executable (its layer count
        may differ from the engine's — sharing one tracker across the
        two signatures would read as a warm retrace)."""
        fn = self._scale_reset_fn
        if fn is None:
            fn = self._scale_reset_fn = _JitTracker(
                _reset_kv_scales, "kv_quant_compiles",
                donate_argnums=(0, 1),
                site="DraftModelDrafter scale reset (_reset_kv_scales)")
        return fn

    # -- request lifecycle --------------------------------------------------
    def on_admit(self, slot: int, req):
        """Draft-side prefill: ingest the prompt into the draft's pages
        through the slot's block-table row (the pages the engine just
        allocated for the target's prompt K/V).  Under chunked prefill
        the prompt arrives chunk by chunk via `ingest_chunks` instead —
        admission only resets the slot's draft cursor — to the cached
        prefix length on a prefix-cache hit: the shared pages' DRAFT
        K/V was written when the original request streamed those very
        chunks through `ingest_chunks` (same block-table page ids, and
        greedy draft ingestion is deterministic in the token prefix),
        so the draft cache skips the cached prefix exactly like the
        target does and `ingest_chunks` only ever sees the novel
        tail."""
        eng = self.engine
        if eng._chunked:
            self._lens[slot] = req.cached_prefix_len
            return
        p_len = len(req.prompt_ids)
        bucket = eng._prefill_bucket(p_len)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :p_len] = req.prompt_ids
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            fn = self._prefill_fns[bucket] = _JitTracker(
                functools.partial(_gpt_prefill,
                                  num_heads=self._num_heads,
                                  head_dim=self._head_dim,
                                  eps=self._eps, **self._greedy),
                "draft_compiles", donate_argnums=(4,),
                site=f"DraftModelDrafter prefill bucket {bucket} "
                     f"(_gpt_prefill)")
        t0 = time.perf_counter()
        # the sampled token (and a quantized pool's refold count behind
        # it) is deliberately NOT fetched — the draft's sample is
        # unused — so no extra host sync: draft-side prefill refolds go
        # uncounted by design
        self._kv, _ = fn(
            self._params, jnp.asarray(ids), jnp.int32(p_len),
            jnp.asarray(eng._bt[slot]), self._kv, eng._key)
        _stats_add(draft_time_s=time.perf_counter() - t0)
        self._lens[slot] = p_len

    def on_finish(self, slot: int, req):
        self._lens[slot] = 0

    def ingest_chunks(self, tokens, caps):
        """Chunked prefill: run the SAME mixed-step program shape the
        target just ran, over the draft weights — the chunk K/V lands in
        the draft's pages through the shared block tables, no sampling
        (mask all-false), and the draft cursor tracks the engine's
        prefill cursor chunk for chunk."""
        eng = self.engine
        fn = self._chunk_fn
        if fn is None:
            fn = self._chunk_fn = _JitTracker(
                functools.partial(_gpt_mixed_step,
                                  num_heads=self._num_heads,
                                  head_dim=self._head_dim,
                                  eps=self._eps, **self._greedy),
                "draft_compiles", donate_argnums=(1,),
                site="DraftModelDrafter chunk ingest (_gpt_mixed_step)")
        caps = np.asarray(caps, np.int32)
        t0 = time.perf_counter()
        self._kv, _ = fn(
            self._params, self._kv,
            jnp.asarray(eng._bt), jnp.asarray(self._lens),
            jnp.asarray(tokens), jnp.asarray(caps),
            jnp.zeros(eng._slots, jnp.int32),
            jnp.zeros(eng._slots, bool), eng._key)
        _stats_add(draft_time_s=time.perf_counter() - t0)
        self._lens = self._lens + caps

    # -- per-round propose ---------------------------------------------------
    def propose(self, write_caps) -> np.ndarray:
        eng = self.engine
        slots = eng._slots
        k = self.k
        # cap 0 = the slot is still prefilling (its chunks flow through
        # ingest_chunks): it must not be caught up or stepped this round
        active = eng._active & (np.asarray(write_caps) > 0)
        drafts = np.zeros((slots, k), np.int32)

        # catch-up: feed the tokens accepted since the draft last saw
        # this slot (positions lens_d .. L, where L = engine seq_len is
        # the last sampled token's position) — at most K+1 of them, in
        # the same fixed [slots, K+1] frame the verify uses, so this is
        # one warm executable, not a shape zoo
        catch = np.zeros((slots, k + 1), np.int32)
        caps = np.zeros(slots, np.int32)
        for s in range(slots):
            if not active[s]:
                continue
            req = eng._by_slot[s]
            full = req.prompt_ids + req.output_ids
            pend = int(eng._lens[s]) + 1 - int(self._lens[s])
            assert 1 <= pend <= k + 1, (pend, k)
            catch[s, :pend] = full[self._lens[s]: self._lens[s] + pend]
            caps[s] = pend
        bt = jnp.asarray(eng._bt)  # invariant across the round
        self._kv, targets = self._catch_fn(
            self._params, self._kv, bt, jnp.asarray(self._lens),
            jnp.asarray(catch), jnp.asarray(caps), eng._key)
        targets = eng._note_refolds(eng._host_fetch(targets))
        self._lens[active] += caps[active]
        cur = np.where(
            active,
            np.take_along_axis(
                targets, np.maximum(caps - 1, 0)[:, None], axis=1)[:, 0],
            0).astype(np.int32)
        drafts[:, 0] = cur

        # K-1 greedy single-token steps; a slot only participates while
        # its draft write position stays inside the verify window the
        # engine reserved (write_caps), so the draft can never touch a
        # page the request does not own
        write_caps = np.asarray(write_caps)
        for i in range(1, k):
            step_active = active & (i <= write_caps - 1)
            if not step_active.any():
                break
            self._kv, nxt = self._step_fn(
                self._params, self._kv, bt, jnp.asarray(self._lens),
                jnp.asarray(cur), jnp.asarray(step_active), eng._key)
            nxt = eng._note_refolds(eng._host_fetch(nxt)).astype(np.int32)
            self._lens[step_active] += 1
            cur = np.where(step_active, nxt, cur).astype(np.int32)
            drafts[:, i] = np.where(step_active, nxt, 0)
        return drafts

    def on_accept(self, slot: int, pos_before: int, n_emitted: int):
        # draft K/V rows for the accepted drafts (positions
        # pos_before+1 .. pos_before+min(n_emitted, K)-? ) were computed
        # under the accepted prefix, so they are correct and stay; the
        # rejected tail rolls back by the same seq_lens trick as the
        # target cache.  The bonus/correction token was never fed to the
        # draft — next round's catch-up ingests it.
        self._lens[slot] = pos_before + min(n_emitted, self.k)


_DRAFTERS = {"prompt_lookup": PromptLookupDrafter}


def make_drafter(spec) -> Drafter:
    """Resolve a drafter: an instance passes through, a name constructs
    (FLAGS_spec_drafter supplies the default name).  `draft_model`
    drafters cannot be named — they need weights, pass an instance."""
    if isinstance(spec, Drafter):
        return spec
    try:
        return _DRAFTERS[spec]()
    except KeyError:
        raise ValueError(
            f"unknown drafter {spec!r}: pass one of "
            f"{sorted(_DRAFTERS)} or a Drafter instance") from None


# ---------------------------------------------------------------------------
# The propose -> verify -> accept loop
# ---------------------------------------------------------------------------
class SpeculativeDecoder:
    """One speculative round per engine step: reserve the verify window,
    draft K tokens per slot, score them in one donated jitted verify
    call, accept the matching prefix + one target token, roll the rest
    back by shrinking ``seq_lens``.  Every emitted token is a target-
    model sample, so greedy output is bit-identical to the
    non-speculative engine and stochastic output follows the target
    distribution exactly."""

    def __init__(self, engine, k: int, drafter=None, adaptive=False):
        from ..core import flags as _flags

        if k < 1:
            raise ValueError(f"spec_decode_k must be >= 1, got {k}")
        self.engine = engine
        self.k = int(k)
        if drafter is None:
            drafter = str(_flags.flag("spec_drafter"))
        self.drafter = make_drafter(drafter)
        self.drafter.bind(engine, self.k)
        self._verify_fn: Optional[_JitTracker] = None
        # adaptive per-slot speculation depth (FLAGS_spec_adaptive_k):
        # ``k_slot`` is each slot's LIVE depth, capped at the
        # configured k — drafter frames, verify windows, and the
        # ragged grid are all sized by k, so a per-slot depth is just
        # a smaller per-row span, never a new executable shape.
        # Multiplicative decrease on rejection streaks, +1 growth on
        # acceptance runs (gated by the cost model's per-kind
        # calibration via `_grow_ok`).
        self.adaptive = bool(adaptive)
        self.k_min = min(self.k,
                         max(1, int(_flags.flag("spec_k_min"))))
        self._shrink_after = max(
            1, int(_flags.flag("spec_k_shrink_streak")))
        self._grow_after = max(
            1, int(_flags.flag("spec_k_grow_streak")))
        self.k_slot = np.full(engine._slots, self.k, np.int32)
        self._rej_streak = np.zeros(engine._slots, np.int32)
        self._acc_streak = np.zeros(engine._slots, np.int32)

    # engine lifecycle hooks (DecodeEngine._prefill_into / _finish)
    def on_admit(self, slot: int, req):
        self._reset_k(slot)
        self.drafter.on_admit(slot, req)

    def on_finish(self, slot: int, req):
        self._reset_k(slot)
        self.drafter.on_finish(slot, req)

    def _reset_k(self, slot: int):
        """A slot changed hands: its acceptance history (and therefore
        its learned depth) belongs to the request that generated it."""
        self.k_slot[slot] = self.k
        self._rej_streak[slot] = 0
        self._acc_streak[slot] = 0

    def _grow_ok(self) -> bool:
        """Cost-model gate on depth growth: growing a slot's K only
        pays while one verify round costs less than the K+1 decode
        steps it replaces at full acceptance (the only regime growth
        triggers in).  Calibrated per-label seconds when the model has
        learned them ("spec" vs the decode-shaped label), raw roofline
        otherwise; no cost model (or an extraction failure) -> allow —
        the streak policy alone is still safe, just ungated."""
        eng = self.engine
        cost = eng._cost
        if cost is None:
            return True
        try:
            verify_kind = "ragged" if eng._ragged else "verify"
            decode_kind = "ragged" if eng._ragged else "decode"
            v = cost.raw_seconds(cost.profile_for(verify_kind))
            d = cost.raw_seconds(cost.profile_for(decode_kind))
            calib = cost.calibration_wire()
            v *= calib.get("spec", 1.0)
            d *= calib.get("ragged" if eng._ragged else "decode", 1.0)
        except Exception:
            return True
        return v <= d * (self.k + 1)

    def _adapt_k(self, slot: int, m: int, usable: int):
        """Per-slot depth controller, fed by this round's acceptance
        (``m`` of ``usable`` drafts matched): a full rejection extends
        the slot's rejection streak and, at ``spec_k_shrink_streak``,
        halves its depth toward ``spec_k_min`` (multiplicative
        decrease — a mispredicting regime stops paying for dead draft
        rows fast); a full acceptance extends the acceptance run and,
        at ``spec_k_grow_streak``, grows the depth by one (additive,
        cost-gated) back toward the configured K; a partial acceptance
        resets both streaks (the depth is about right)."""
        if usable <= 0:
            return  # depth-0 round (token budget exhausted): no signal
        if m == 0:
            self._acc_streak[slot] = 0
            self._rej_streak[slot] += 1
            if self._rej_streak[slot] >= self._shrink_after and \
                    int(self.k_slot[slot]) > self.k_min:
                self.k_slot[slot] = max(self.k_min,
                                        int(self.k_slot[slot]) // 2)
                self._rej_streak[slot] = 0
                _stats_add(spec_k_shrinks=1)
        elif m >= usable:
            self._rej_streak[slot] = 0
            self._acc_streak[slot] += 1
            if self._acc_streak[slot] >= self._grow_after and \
                    int(self.k_slot[slot]) < self.k:
                self._acc_streak[slot] = 0
                if self._grow_ok():
                    self.k_slot[slot] += 1
                    _stats_add(spec_k_grows=1)
        else:
            self._rej_streak[slot] = 0
            self._acc_streak[slot] = 0

    def step(self) -> bool:
        """One propose->verify->accept round over every active slot.
        Called by `DecodeEngine.step` after admission."""
        eng = self.engine
        slots = eng._slots

        # the round's observation window opens BEFORE any chunk step:
        # paddle_decode_step_seconds must account every engine step's
        # full wall time, chunk ingestion included
        t_round0 = time.perf_counter()
        t_round0_ns = _obs.now_ns()
        if eng._chunked and eng._prefilling_any():
            # feed prompt chunks through the engine's mixed executable
            # first (decoding slots sit that call out — their tokens
            # come from the verify round below); the drafter ingests
            # the same chunks inside _mixed_step.  A slot whose LAST
            # chunk lands there flips to decoding and joins this very
            # round.
            eng._mixed_step(decode_rows=False)

        # batch assembly before the draft, and again before the verify
        # dispatch (`DecodeEngine._mixed_step`'s ``engine.assemble``)
        with _flight.engine_span(eng, "assemble"):
            # verify window per slot, clamped to the request's remaining
            # token budget: KV rows past position prompt+max_new-2 are never
            # needed, and writing them would outrun the pool reservation.
            # Slots still mid-prefill keep cap 0 and skip the round.
            caps = np.zeros(slots, np.int32)
            for s in range(slots):
                if not eng._active[s] or eng._is_prefilling(s):
                    continue
                req = eng._by_slot[s]
                need = req.max_new_tokens - len(req.output_ids)
                k_s = int(self.k_slot[s]) if self.adaptive else self.k
                caps[s] = min(k_s + 1, need)
            if not caps.any():
                # every live slot is still prefilling: the chunk step above
                # WAS this engine step — it owns the latency observation
                _obs.STEP_SECONDS.observe(time.perf_counter() - t_round0)
                return True
            eng._grow_block_tables(writes=caps)
            # quantized pools: freshly granted pages' scales zero BEFORE
            # the draft catch-up / verify write into them
            eng._flush_fresh_scales()
        pos_before = eng._lens.copy()

        fr = eng._flight
        t0 = time.perf_counter()
        t0_ns = _obs.now_ns()
        try:
            if eng._fault is not None:
                eng._resilience.fault_point("drafter")
            # "draft" is EXCLUSIVE of the blocking fetches the drafter
            # pays inside propose (those land on the "fetch" phase)
            with eng._excl_phase("draft"):
                drafts = self.drafter.propose(caps)
        except eng._resilience.NONRETRYABLE:
            raise
        except Exception as e:
            # drafter containment: a raising drafter costs this round
            # its speculation, never the step — the verify below runs
            # over zero drafts (all rejected, one genuine target token
            # per slot emitted: exactly a decode step through the
            # verify executable, no new shapes).  Repeated faults
            # degrade speculation off entirely (re-enable probe after
            # FLAGS_degraded_probe_steps clean steps).
            drafts = np.zeros((slots, self.k), np.int32)
            eng._resilience.on_drafter_fault(e)
        t_draft = time.perf_counter() - t0
        _obs.record_span("engine", "draft", t0_ns, int(t_draft * 1e9),
                         tid=eng._engine_id,
                         args={"drafter": self.drafter.name, "k": self.k})

        with _flight.engine_span(eng, "assemble"):
            if eng._ragged:
                # FLAGS_ragged_step: the verify window is just a per-row
                # span on the engine's ONE ragged executable — same
                # program, same shapes as its decode/mixed dispatches, so
                # a speculative engine still compiles exactly one step
                # executable
                fn = eng._ragged_fn_tracker()
            else:
                fn = self._verify_fn
                if fn is None:
                    fn = self._verify_fn = _JitTracker(
                        functools.partial(_gpt_spec_verify,
                                          num_heads=eng._num_heads,
                                          head_dim=eng._head_dim,
                                          eps=eng._eps, **eng._sampling),
                        "verify_compiles", donate_argnums=(1,),
                        site="SpeculativeDecoder verify (_gpt_spec_verify)")

            tokens = np.concatenate(
                [eng._last[:, None].astype(np.int32), drafts], axis=1)
            if eng._ragged and tokens.shape[1] < eng._q_ragged:
                # pad the window out to the ragged grid's fixed Q_r (the
                # chunked-prefill width may exceed K+1); padding columns
                # sit past every cap and are never written or read
                tokens = np.concatenate(
                    [tokens, np.zeros((slots, eng._q_ragged -
                                       tokens.shape[1]), np.int32)],
                    axis=1)
            if eng._fault is not None:
                eng._resilience.step_fault_point("verify")
            eng._step_no += 1
            key = jax.random.fold_in(
                eng._key, _fold_counter(eng._step_no, RNG_DECODE_DOMAIN))
        t0 = time.perf_counter()
        tv_ns = _obs.now_ns()
        with eng._phase("verify", decode_rows=int((caps > 0).sum()),
                        chunk_rows=0, chunk_tokens=0):
            eng._kv, targets = fn(
                eng._params, eng._kv,
                eng._dev(eng._bt), eng._dev(eng._lens),
                eng._dev(tokens), eng._dev(caps), eng._dev(key))
            if eng._profiling is not None:
                # sampled device-sync probe (observability.
                # profiling): the verify executable's measured
                # device seconds, blocked inside the phase
                eng._profiling.probe(
                    "ragged" if eng._ragged else "verify",
                    targets, t0, tv_ns)
        targets = eng._note_refolds(eng._host_fetch(targets))
        t_verify = time.perf_counter() - t0
        if eng._fault is not None:
            targets = eng._resilience.corrupt_tokens(
                targets, [s for s in range(slots) if caps[s] > 0])
        _obs.record_span("engine", "verify", tv_ns, int(t_verify * 1e9),
                         tid=eng._engine_id, args={"k": self.k})

        n_active = int(eng._active.sum())
        n_verify = int((caps > 0).sum())  # slots this round advanced
        emitted_total = 0
        proposed_total = 0
        accepted_total = 0
        with eng._excl_phase("emit"):
            for s in range(slots):
                if not eng._active[s] or caps[s] == 0:
                    continue
                req = eng._by_slot[s]
                w = int(caps[s])
                usable = min(self.k, w - 1)  # drafts acceptable
                m = 0
                while m < usable and \
                        int(drafts[s, m]) == int(targets[s, m]):
                    m += 1
                emit = [int(t) for t in drafts[s, :m]] + \
                    [int(targets[s, m])]
                if any(t < 0 for t in emit):
                    # non-finite logits somewhere in this slot's verify
                    # window: quarantine the slot without emitting
                    # (lens never advances over the poisoned rows, the
                    # drafter's on_finish resets its cursor) — the
                    # other slots' rounds are untouched
                    eng._quarantine_slot(s, "nan_logits")
                    continue
                if req.eos_token_id is not None:
                    for j, t in enumerate(emit):
                        if t == req.eos_token_id:
                            emit = emit[:j + 1]
                            break
                n_emit = len(emit)
                # accounted AFTER eos truncation so acceptance_rate
                # stays consistent with spec_emitted: drafts that
                # matched but were cut by an earlier eos never reached
                # the output
                proposed_total += usable
                accepted_total += min(m, n_emit)
                # through the engine's single emission point: the
                # streaming on_token hook fires per accepted token
                # exactly like on the classic decode path
                eng._emit(req, emit)
                # accepted rows keep their K/V; the rejected tail is
                # rolled back purely by NOT advancing seq_lens over it
                eng._lens[s] += n_emit
                eng._last[s] = emit[-1]
                emitted_total += n_emit
                eng._register_generated_pages(req, int(eng._lens[s]))
                self.drafter.on_accept(s, int(pos_before[s]), n_emit)
                if self.adaptive:
                    self._adapt_k(s, m, usable)
                reason = eng._done(req, emit[-1])
                if reason:
                    eng._finish(s, reason)

        eng._batch_s += t_draft + t_verify
        _stats_add(spec_steps=1, spec_slot_steps=n_verify, steps=1,
                   spec_proposed=proposed_total,
                   spec_accepted=accepted_total,
                   spec_emitted=emitted_total, tokens=emitted_total,
                   draft_time_s=t_draft, verify_time_s=t_verify,
                   decode_time_s=t_draft + t_verify,
                   occupancy_sum=n_active / slots,
                   kv_util_sum=eng.pool.utilization())
        _obs.SPEC_ACCEPTED_LAST.set(emitted_total, engine=eng._engine_id)
        # the round span opens at t_round0 (before any chunk-ingest
        # mixed step) and runs to NOW (draft + verify + the accept
        # loop): measured end-to-end so the chunk/draft/verify child
        # spans nest inside it and STEP_SECONDS sees the whole step
        eng._observe_step(t_round0_ns,
                          (_obs.now_ns() - t_round0_ns) / 1e9, n_active,
                          "spec_step",
                          extra_args={"k": self.k,
                                      "emitted": emitted_total})
        return True
