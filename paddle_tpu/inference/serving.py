"""LLM decode serving: paged KV-cache pool + continuous-batching engine.

The serving-side analog of `jit.TrainStep`: the per-step decode —
embedding, per-layer paged-attention over block-table-indexed KV pages,
in-place cache write, sampling — is ONE donated jitted executable with
signature-keyed reuse, so steady-state serving never retraces and the KV
pool buffers are updated in place.  Scheduling (admitting queued
requests into free slots, evicting finished sequences, growing a
sequence's block table page by page) happens on the host *between*
steps, changing only array contents — never shapes — which is what keeps
the executable cache warm.

Layers:

* `KVBlockPool` — host-side page allocator over the device-resident
  K/V page pools (`[layers, kv_heads, num_pages, page_size, head_dim]`),
  doubling as a content-addressed prefix cache (FLAGS_prefix_cache):
  full prompt pages are registered under a chain hash, shared across
  requests at refcount+1, retained on an LRU after their last ref
  drops, and recycled least-recently-released-first under pressure.
  Admission maps the longest page-aligned cached prefix into a new
  request's block table and chunked prefill starts at the first novel
  token; a mid-page divergence is copy-on-write — the partial page is
  recomputed into a fresh private page, cached pages are never
  written;
* `Request` / `DecodeEngine` — continuous batching over a fixed slot
  grid.  With chunked prefill (FLAGS_chunked_prefill, the default)
  admission binds a request to a slot immediately and its prompt is
  consumed chunk by chunk INSIDE the decode step: each step runs one
  fixed-shape ``[slots, Q_max]`` mixed batch (prefilling slots carry a
  prompt chunk as Q>1 ragged rows, decoding slots their usual Q=1 row)
  through a single donated executable, so an admission never stalls
  running decodes and TTFT lands when the last chunk does.  The legacy
  one-shot bucket-padded prefill stays behind ``chunked_prefill=0`` as
  the greedy-parity oracle.  With ``spec_decode_k > 0`` (or
  FLAGS_spec_decode_k) each step becomes a speculative
  propose->verify->accept round (`inference.speculative`) emitting up
  to K+1 tokens per slot;
* telemetry — step latency, batch occupancy, KV-block utilization and
  executable (re)compilation counts, plus speculative acceptance rates
  and per-request finish reasons, surfaced through
  `paddle_tpu.profiler.decode_stats`.

Admission ordering is pluggable (`inference.frontend`): the engine
delegates its between-steps admission decision to a `Scheduler` —
`FIFOScheduler` (the default, bit-exact with the historical strict-
arrival-order behavior) or `SLOScheduler` (priority classes + earliest-
deadline-first + deadline expiry + preempt/resume).  Requests carry
``priority`` / ``deadline_ms`` / TTFT/TPOT SLO targets and an optional
per-token ``on_token`` callback (the streaming hook
`inference.frontend.ServingFrontend` rides).  Preemption
(`DecodeEngine.preempt`) releases a running request's slot and pages
between steps and re-enqueues it with ``prompt_ids + output_ids`` as
the replay prompt — with the prefix cache on, every full page of that
replay was registered at preemption, so resume costs at most one page
of recompute.  All of it is host-side bookkeeping: executable shapes
never change and the zero-warm-retrace contract is untouched.

Numerics deliberately mirror the eager GPT path op for op (same
layer_norm kernel, same sdpa reference, same sampling), so greedy decode
through the engine reproduces `GPT.generate`'s tokens exactly — the
parity contract tests/test_paged_decode.py pins.
"""
from __future__ import annotations

import functools
import hashlib
import heapq
import itertools
import os
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..core.tensor import unwrap
from ..ops.pallas import paged_attention as pa
from .errors import FaultInfo, PoolExhausted, StepFault

__all__ = ["KVBlockPool", "Request", "DecodeEngine", "sample_logits",
           "decode_stats", "reset_decode_stats",
           "PRIORITY_INTERACTIVE", "PRIORITY_BATCH"]


# ---------------------------------------------------------------------------
# Telemetry (profiler.decode_stats).  The key schema lives in profiler
# (DECODE_STAT_COUNTERS) so profiler's not-imported zero fallback and
# this live dict can never diverge.  Mutation and atomic read+reset go
# through the observability registry's lock — the ONE telemetry lock —
# so a stats poller thread can never tear a serve loop's
# read-modify-write updates (or vice versa).
# ---------------------------------------------------------------------------
from ..profiler import (DECODE_STAT_COUNTERS, _decode_stat_zero)
from .. import observability as _obs
from ..analysis import sanitizer as _san
from ..observability import LOCK as _TELEMETRY_LOCK
from ..observability import costmodel as _costmodel
from ..observability import flight as _flight

_STATS = {k: _decode_stat_zero(k) for k in DECODE_STAT_COUNTERS}
# the collector's counters are the seam's process totals less this
# baseline, moved on every reset (the gc hook itself takes no lock)
_GC_BASE = _flight.gc_totals()


def _gc_since_base(totals) -> dict:
    n = totals["collections"]
    base = _GC_BASE["collections"]
    return {"gc_pause_s": totals["pause_s"] - _GC_BASE["pause_s"],
            "gc_collections": sum(n) - sum(base),
            "gc_gen2_collections": n[2] - base[2]}


def _stats_add(**deltas):
    """Apply counter deltas atomically (one lock round per engine step,
    not one per counter)."""
    with _TELEMETRY_LOCK:
        for k, v in deltas.items():
            _STATS[k] += v


def decode_stats(reset=False):
    """Serving-loop telemetry: decode step latency, batch occupancy,
    KV-block utilization and executable compile counts.
    ``retraces_after_warmup`` must stay 0 in steady state — any nonzero
    value means a step signature changed mid-serve.

    Counters are PROCESS-WIDE aggregates across every DecodeEngine (the
    same contract as ``dispatch_stats``); serving several engines
    concurrently blends their occupancy/utilization averages.
    ``reset=True`` is atomic with the read: counts a concurrent serve
    adds after the snapshot are never lost to the reset."""
    with _TELEMETRY_LOCK:
        out = dict(_STATS)
        out.update(_gc_since_base(_flight.gc_totals()))
        if reset:
            reset_decode_stats()
    steps = max(out["steps"], 1)
    out["avg_step_ms"] = out["decode_time_s"] / steps * 1e3
    out["batch_occupancy"] = out["occupancy_sum"] / steps
    out["kv_block_utilization"] = out["kv_util_sum"] / steps
    # speculative decoding: fraction of drafted tokens the verify pass
    # accepted, and tokens emitted per active slot per verify step
    # (1.0 == a classic non-speculative step, K+1 is the ceiling; this
    # number IS the speedup lever)
    out["acceptance_rate"] = out["spec_accepted"] / max(
        out["spec_proposed"], 1)
    out["mean_accepted_per_step"] = out["spec_emitted"] / max(
        out["spec_slot_steps"], 1)
    # share of the steps dispatched while the one before was in flight
    out["lookahead_share"] = out["lookahead_steps"] / steps
    return out


def reset_decode_stats():
    global _GC_BASE
    with _TELEMETRY_LOCK:
        for k in _STATS:
            _STATS[k] = 0.0 if isinstance(_STATS[k], float) else 0
        _GC_BASE = _flight.gc_totals()


# Sampling lives in nn.decode (neutral layer — eager GPT.generate must
# not depend on the serving module); re-exported here for the engine's
# public surface.
from ..nn.decode import sample_logits  # noqa: E402


# ---------------------------------------------------------------------------
# PRNG stream domains.  Every sampling key is
# ``fold_in(engine_key, _fold_counter(counter, domain))``: decode /
# mixed steps fold values in (0, 2^30], legacy one-shot prefill in
# (2^30, 2^31].  The counters themselves are unbounded — after ~2^30
# steps a naive ``fold_in(key, step_no)`` would walk into the prefill
# window and alias its stream, so the fold value WRAPS inside its own
# window (and asserts it stayed there).  Regression-pinned by
# tests/test_chunked_prefill.py::TestRngDomains.
# ---------------------------------------------------------------------------
_RNG_DOMAIN = 1 << 30
RNG_DECODE_DOMAIN = 0   # decode / mixed steps (and speculative rounds)
RNG_PREFILL_DOMAIN = 1  # legacy one-shot prefill


def _fold_counter(counter: int, domain: int) -> int:
    """Map an unbounded 1-based counter into its domain's fold_in
    window ``(domain * 2^30, (domain + 1) * 2^30]``."""
    if counter < 1:
        raise ValueError(f"stream counter must be >= 1, got {counter}")
    v = domain * _RNG_DOMAIN + 1 + (counter - 1) % _RNG_DOMAIN
    assert domain * _RNG_DOMAIN < v <= (domain + 1) * _RNG_DOMAIN, \
        (counter, domain, v)
    return v


class _JitTracker:
    """Retrace telemetry + donation tracking for one jitted step
    executable.  Counts ACTUAL XLA compiles (the jit's own trace-cache
    size) — a dtype/weak_type flapping in the step operands would
    recompile inside the same jitted wrapper and must not go unnoticed.
    Growth after the first call lands in ``retraces_after_warmup``; the
    contract covers the decode step AND the speculative draft/verify
    executables (inference.speculative) identically.

    Invoke the tracker itself (``tracker(*args)``) rather than
    ``tracker.fn``: the call path runs the retrace check after every
    invocation, and under FLAGS_sanitize additionally (a) rejects any
    argument that was DONATED to an earlier tracked call (use-after-
    donate, the error names the donation site), (b) tombstones this
    call's ``donate_argnums`` arguments afterwards — on backends that
    silently ignore donation only the sanitizer makes the "donated
    buffers are dead" contract observable before TPU does — and (c)
    raises `WarmRetraceError` instead of counting a warm retrace."""

    def __init__(self, fn, compile_key, donate_argnums=(), site=None):
        """``fn`` is the PYTHON step callable: the tracker owns the
        ``jax.jit`` wrapping so ``donate_argnums`` has exactly ONE
        source of truth — the tuple XLA donates and the tuple the
        sanitizer tombstones can never drift apart.  (A pre-jitted
        callable is accepted for tests; it must carry no donation or
        the tombstones would not match.)"""
        self.donate_argnums = tuple(donate_argnums)
        is_jitted = hasattr(fn, "lower")  # PjitFunction duck-type
        self.fn = fn if is_jitted else \
            jax.jit(fn, donate_argnums=self.donate_argnums)
        if is_jitted and self.donate_argnums:
            raise ValueError(
                "pass the un-jitted callable when donate_argnums is "
                "set: _JitTracker owns the jax.jit so the donated and "
                "tombstoned argument sets cannot drift")
        self.site = site or compile_key
        # compile_key doubles as the retrace-attribution key:
        # "<kind>_compiles" -> "<kind>_retraces" (decode_stats), so a
        # warm retrace is attributable to ONE executable by counter
        self.compile_key = compile_key
        self._seen = 0
        self._warm = False
        # abstract (shape, dtype, sharding) signature of the first call
        # — the one shape this executable serves; `lower` rebuilds the
        # program from it after the donated operands are gone
        self.signature = None
        # cost observatory (observability.costmodel): the profile key
        # of this executable's static FLOP/byte profile, stamped at
        # compile time (first invocation) when FLAGS_cost_model is on
        self.cost_sig = None
        _stats_add(**{compile_key: 1})

    def __call__(self, *args):
        san = _san.active()
        if san is not None:
            for a in jax.tree_util.tree_leaves(args):
                san.check_live(a, context=f"argument of {self.site}")
        if not self._warm:
            self.signature = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=a.sharding,
                    weak_type=a.weak_type), args)
            if _costmodel.enabled():
                # compile-time profile extraction, once per executable:
                # lower the same traced call and read the HLO cost
                # analysis (_cache_size untouched; where the backend
                # analyses compiled programs only, the compile happens
                # there and the call below reuses its executable).
                # BEFORE the call: donated operands are still live
                # here, deleted after.
                self.cost_sig = _costmodel.note_executable(
                    self.site, self.fn, args)
        out = self.fn(*args)
        self.check_retrace()
        if san is not None:
            for i in self.donate_argnums:
                if i < len(args):
                    for a in jax.tree_util.tree_leaves(args[i]):
                        san.tombstone(a, self.site)
        return out

    def lower(self):
        """The step program lowered against its first call's signature:
        ``.compile()`` of the result gives the executable's HLO text
        (is the Pallas kernel in it?) and its memory analysis, without
        touching live buffers.  Only after the tracker's first call."""
        if self.signature is None:
            raise RuntimeError(f"{self.site} has not been called yet")
        return self.fn.lower(*self.signature)

    def check_retrace(self):
        """Runs after every invocation (``__call__`` does it)."""
        n = self.fn._cache_size()
        grew = n - self._seen if self._warm else 0
        was = self._seen
        self._seen = n
        self._warm = True
        if grew > 0:
            san = _san.active()
            if san is not None:
                san.count_warm_retrace(grew)
                raise _san.WarmRetraceError(
                    f"warm retrace of {self.site}: the executable "
                    f"cache grew {was} -> {n} after warmup — a step "
                    f"operand's shape/dtype/weak_type changed "
                    f"mid-serve")
            # aggregate counter + per-executable attribution keyed by
            # compile_key ("<kind>_compiles" -> "<kind>_retraces"); a
            # key outside the schema (tests passing ad-hoc keys) still
            # lands in the aggregate
            per_key = self.compile_key.replace("_compiles", "_retraces")
            if per_key in _STATS:
                _stats_add(retraces_after_warmup=grew,
                           **{per_key: grew})
            else:
                _stats_add(retraces_after_warmup=grew)


# ---------------------------------------------------------------------------
# KV page pool (host-side allocator; device arrays live on the engine)
# ---------------------------------------------------------------------------
class KVBlockPool:
    """Free-list allocator over ``num_pages`` KV pages, extended with a
    content-addressed prefix cache.  Allocation and reservation
    accounting are host-side bookkeeping; the page payloads are the
    engine's donated device arrays.

    A page is in exactly one of four states:

    * **free** — on the free list, payload meaningless;
    * **private** — allocated to exactly one request, writable;
    * **cached, referenced** — registered under a chain-hash key
      (`register_page`), refcount >= 1 requests map it READ-ONLY;
    * **cached, unreferenced** — refcount 0: the payload is retained
      for future prefix hits and the page sits on the eviction LRU.

    `alloc_page` serves from the free list first and falls back to
    evicting the least-recently-released unreferenced cached page; a
    page with a live reference is never evicted and never returns to
    the free list.  Cached pages are immutable by contract: a request
    done with its pages goes through `release_pages` (cached -> unref,
    private -> free), and `free_pages` raises on a cached or already-
    free page — the double-free guard."""

    def __init__(self, num_pages: int):
        self.num_pages = int(num_pages)
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._free_set = set(self._free)
        self.reserved = 0  # pages promised to running requests
        # prefix cache: chain hash <-> page, per-page refcounts, and the
        # LRU of refcount-zero cached pages (OrderedDict, oldest first)
        self._hash_to_page: Dict[bytes, int] = {}
        self._page_hash: Dict[int, bytes] = {}
        self._refs: Dict[int, int] = {}
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self.evictions = 0  # cached pages recycled under pressure

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def used_count(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def cached_count(self) -> int:
        """Pages currently content-addressed (referenced or not)."""
        return len(self._page_hash)

    @property
    def cached_unreferenced_count(self) -> int:
        """Cached pages with no live reference — reclaimable via the
        eviction LRU."""
        return len(self._lru)

    @property
    def available_count(self) -> int:
        """Pages `alloc_page` can hand out right now: the free list
        plus the evictable (unreferenced cached) LRU."""
        return len(self._free) + len(self._lru)

    def utilization(self) -> float:
        """Fraction of the pool a new request CANNOT claim: private +
        cached-referenced pages.  Unreferenced cached pages are
        reclaimable on demand (LRU eviction), so a warm-but-idle cache
        reads 0.0 — an operator alerting on pool pressure sees real
        pressure, not retained prefixes.  With the prefix cache off
        this is exactly used/num_pages, as before."""
        return (self.num_pages - self.available_count) \
            / max(self.num_pages, 1)

    def alloc_page(self) -> int:
        if self._free:
            p = self._free.pop()
            self._free_set.discard(p)
            return p
        if self._lru:
            # eviction under pressure: recycle the least-recently
            # released unreferenced cached page.  Pages with live refs
            # are not in the LRU by invariant, so they can never be
            # handed out from under a running request.
            p, _ = self._lru.popitem(last=False)
            del self._hash_to_page[self._page_hash.pop(p)]
            del self._refs[p]
            self.evictions += 1
            return p
        raise PoolExhausted(
            "KV page pool exhausted: no free page and every cached "
            "page is referenced by a live request")

    def free_pages(self, pages):
        """Return PRIVATE pages to the free list.  Raises on a page
        that is not currently allocated-private: a double free would
        put the same page on the free list twice (handed to two
        requests -> cache corruption), and a cached page must be
        released via `release_pages` (unref) instead."""
        for p in pages:
            p = int(p)
            if not 0 <= p < self.num_pages:
                raise ValueError(
                    f"page {p} outside pool [0, {self.num_pages})")
            if p in self._free_set:
                raise ValueError(f"double free of KV page {p}")
            if p in self._page_hash:
                raise ValueError(
                    f"page {p} is cached (refcount {self._refs[p]}); "
                    f"release_pages unrefs cached pages")
            self._free.append(p)
            self._free_set.add(p)

    def release_pages(self, pages):
        """A request is done with ``pages``: cached pages are unreffed
        (payload retained; refcount 0 parks them on the eviction LRU),
        private pages go back to the free list."""
        for p in pages:
            p = int(p)
            if p in self._page_hash:
                self.unref_page(p)
            else:
                self.free_pages([p])

    # -- content addressing --------------------------------------------------
    def lookup(self, key: bytes) -> Optional[int]:
        """Page registered under chain-hash ``key``, or None."""
        return self._hash_to_page.get(key)

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def register_page(self, page: int, key: bytes) -> bool:
        """Content-address a full, finally-written PRIVATE page under
        ``key``; the owner's hold becomes refcount 1 (released through
        `release_pages` -> unref, like any other cached ref).  Returns
        False without registering when the key is already taken (a
        concurrent identical prefill computed the same content — the
        duplicate page stays private) or the page is already cached."""
        p = int(page)
        if p in self._free_set:
            raise ValueError(f"cannot register free page {p}")
        if key in self._hash_to_page or p in self._page_hash:
            return False
        self._hash_to_page[key] = p
        self._page_hash[p] = key
        self._refs[p] = 1
        return True

    def ref_page(self, page: int):
        """Map a cached page into one more request (refcount + 1); a
        referenced page leaves the eviction LRU."""
        p = int(page)
        if p not in self._refs:
            raise ValueError(f"page {p} is not cached")
        self._refs[p] += 1
        self._lru.pop(p, None)

    def unref_page(self, page: int):
        """Drop one reference; at zero the page parks on the eviction
        LRU (most-recently released = evicted last), payload intact."""
        p = int(page)
        r = self._refs.get(p)
        if r is None or r <= 0:
            raise ValueError(f"unref of page {p} without a live ref")
        self._refs[p] = r - 1
        if r == 1:
            self._lru[p] = None

    def assert_consistent(self, live_pages=None):
        """Audit the allocator invariants (tests / FLAGS_kv_pool_debug):
        the page universe partitions exactly into free + private +
        cached-referenced + cached-unreferenced, the hash maps are
        mutual inverses, and the LRU is exactly the refcount-zero
        cached set.  With ``live_pages`` — every live request's page
        list, concatenated, WITH multiplicity — additionally checks
        that refcounts equal the number of requests actually holding
        each cached page and every private used page has exactly one
        owner (the ``free + used + cached-unreferenced == num_pages``
        identity made real)."""
        assert len(self._free) == len(self._free_set) == \
            len(set(self._free)), "free list / free set diverged"
        assert len(self._hash_to_page) == len(self._page_hash), \
            "hash->page / page->hash maps diverged"
        for h, p in self._hash_to_page.items():
            assert self._page_hash.get(p) == h, \
                (p, "hash maps are not mutual inverses")
        assert set(self._refs) == set(self._page_hash), \
            "refcounts must exist exactly for cached pages"
        assert not (self._free_set & set(self._page_hash)), \
            "cached page on the free list"
        for p, r in self._refs.items():
            assert r >= 0, (p, r, "negative refcount")
        unref = {p for p, r in self._refs.items() if r == 0}
        assert set(self._lru) == unref, \
            "LRU is not exactly the refcount-zero cached set"
        referenced = len(self._refs) - len(unref)
        private = self.num_pages - self.free_count - self.cached_count
        assert private >= 0, "more free+cached pages than the pool holds"
        assert self.free_count + private + referenced + \
            self.cached_unreferenced_count == self.num_pages
        if live_pages is None:
            return
        from collections import Counter as _Counter

        counts = _Counter(int(p) for p in live_pages)
        for p, c in counts.items():
            assert 0 <= p < self.num_pages, p
            assert p not in self._free_set, (p, "live page is free")
            if p in self._refs:
                assert self._refs[p] == c, \
                    (p, self._refs[p], c, "refcount != live holders")
            else:
                assert c == 1, (p, c, "private page held twice")
        for p, r in self._refs.items():
            if r > 0:
                assert counts.get(p, 0) == r, \
                    (p, r, "referenced page with no live holder")
        live_private = {p for p in counts if p not in self._refs}
        assert len(live_private) == private, \
            (live_private, private, "private page with no owner")


def _chain_hash(prev: bytes, tokens) -> bytes:
    """One link of a prompt's page chain hash: fold the previous page's
    digest with this page's token run.  Page i's key therefore commits
    to tokens 0 .. (i+1)*page-1, so a lookup hit at page i implies the
    whole prefix matched — KV content is a pure function of (model,
    token prefix), which is what makes the cached page bit-reusable."""
    h = hashlib.blake2b(prev, digest_size=16)
    h.update(np.asarray(tokens, np.int32).tobytes())
    return h.digest()


# Priority classes (lower value = more urgent; any int works — these
# two name the ends the SLO scheduler is designed around).  The default
# is BATCH so that plain `add_request` calls sort behind explicitly
# interactive traffic under the SLO scheduler while staying pure
# arrival-order under FIFO.
PRIORITY_INTERACTIVE = 0
PRIORITY_BATCH = 10


class Request:
    """One generation request moving through the engine:
    queued -> running (bound to a slot + pages) -> done.

    ``finish_reason`` records WHY a request left the engine — "eos"
    (hit its eos token), "length" (max_new_tokens exhausted),
    "evicted" (`DecodeEngine.evict`), "cancelled" (`Request.cancel`,
    queued or running), "deadline" (its ``deadline_ms`` expired
    while still queued; the SLO scheduler retires it without ever
    taking a slot), or "fault" (the containment ladder quarantined it
    — non-finite logits on its slot, or the batch bisection isolated
    it as the suspect of a persistent step fault; ``fault_info``
    carries the structured record) — so callers can tell a completed
    generation from a truncated one.

    Scheduling metadata: ``priority`` (lower = more urgent;
    `PRIORITY_INTERACTIVE` / `PRIORITY_BATCH` name the classes),
    ``deadline_ms`` (budget from enqueue for the WHOLE request),
    ``slo_ttft_ms`` / ``slo_tpot_ms`` (latency targets — missing one
    increments the SLO-violation counters and flips ``slo_violations``,
    it never aborts the request).  ``on_token`` is the streaming hook:
    called with each generated token id the moment the engine lands it
    (from inside the serve loop — it must be cheap and MUST NOT raise).

    A preempted request (`DecodeEngine.preempt`) goes back to
    "queued" with its generated tokens folded into ``prompt_ids`` for
    replay; ``generated_ids`` always reads the full generation
    regardless of how many times the request was preempted.

    Lifecycle timestamps (``now_ns`` clock, shared with the host
    tracer) are stamped as the request moves enqueue -> admit -> first
    token -> finish; they feed the observability TTFT / TPOT /
    queue-wait / e2e histograms and the per-request chrome-trace
    spans."""

    # itertools.count: id draws are atomic under the GIL, so concurrent
    # enqueues from several threads can never collide (the old
    # read-increment-write raced)
    _next_id = itertools.count()

    def __init__(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
                 priority=None, deadline_ms=None, slo_ttft_ms=None,
                 slo_tpot_ms=None, on_token=None):
        self.prompt_ids = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.priority = PRIORITY_BATCH if priority is None else \
            int(priority)
        if deadline_ms is not None and float(deadline_ms) <= 0:
            raise ValueError(
                f"deadline_ms must be > 0, got {deadline_ms}")
        self.deadline_ms = None if deadline_ms is None else \
            float(deadline_ms)
        self.slo_ttft_ms = None if slo_ttft_ms is None else \
            float(slo_ttft_ms)
        self.slo_tpot_ms = None if slo_tpot_ms is None else \
            float(slo_tpot_ms)
        self.on_token = on_token
        # stamped at enqueue (t_enqueue_ns + deadline): the instant the
        # request stops being worth admitting
        self._deadline_ns: Optional[int] = None
        # preempt/resume bookkeeping: original prompt length (before
        # any replay folding), generated tokens absorbed into the
        # prompt by preemptions, preemption count, and the scheduler's
        # head-of-line skip counter (anti-starvation fence)
        self.orig_prompt_len = len(self.prompt_ids)
        self._absorbed = 0
        self.preemptions = 0
        self._hol_skips = 0
        # emitted-token gate (inference.durability): > 0 while replay
        # is recomputing tokens an earlier life (pre-crash process, or
        # a watchdog-abandoned step) already streamed — `_emit` lands
        # them on output_ids but never re-fires on_token for them
        self._emit_gate = 0
        # SLO accounting: violation kinds recorded for this request
        # ("ttft" | "tpot" | "deadline")
        self.slo_violations: List[str] = []
        # SLO burn accounting (observability.flight): kinds whose
        # budget burn already crossed 1.0 while live, so the
        # paddle_slo_burn_exceeded counter fires once per kind
        self._burn_noted: set = set()
        self.output_ids: List[int] = []
        self.state = "queued"
        self.finish_reason: Optional[str] = None
        # structured fault record (inference.errors.FaultInfo): set when
        # containment quarantined this request (finish_reason="fault"),
        # when it rode an engine recovery (recovered=True), or when its
        # on_token callback raised and was dropped — instead of a bare
        # exception unwinding through a stream iterator
        self.fault_info: Optional[FaultInfo] = None
        self.slot: Optional[int] = None
        self.pages: List[int] = []
        # prefix cache (FLAGS_prefix_cache): the leading
        # ``cached_page_count`` entries of ``pages`` are shared cached
        # pages (held at refcount+1, never written); chunked prefill
        # starts at token ``cached_prefix_len`` instead of 0
        self.cached_prefix_len = 0
        self.cached_page_count = 0
        # chain hashes of the prompt's full pages, computed lazily at
        # the FIRST admission probe and memoized: a request waiting at
        # the queue head is re-probed every step, and re-hashing a long
        # prompt each time would put O(prompt) host work in the loop
        self._page_hashes: Optional[List[bytes]] = None
        # prefix-cache registration high-water mark: how many of this
        # request's leading FULL pages are content-addressed in the
        # pool — prompt pages at first token, then GENERATED pages as
        # decode crosses page boundaries.  A count of hashes known to
        # the pool, not of pages this life owns, so it survives
        # preempt/resume.
        self._reg_pages = 0
        self.request_id = next(Request._next_id)
        # fleet-scope trace id (observability.fleettrace): minted by
        # the router, carried on every HTTP leg, preserved across
        # failover by the durability journal.  None unless
        # FLAGS_fleet_trace propagated one — span args and flight
        # records tag themselves with it only when set.
        self.trace_id: Optional[str] = None
        self.t_enqueue_ns: Optional[int] = None
        self.t_admit_ns: Optional[int] = None
        self.t_first_token_ns: Optional[int] = None
        self.t_finish_ns: Optional[int] = None
        # chunked prefill: mixed steps that carried one of this
        # request's prompt chunks (1 on the legacy one-shot path)
        self.prefill_chunks = 0
        self._engine = None  # set by DecodeEngine.add_request

    def total_kv_tokens(self) -> int:
        # KV rows ever written: prompt + all generated-token writes except
        # the final sampled token (its KV is never needed).  Invariant
        # under preemption: the replay fold moves tokens from max_new
        # into the prompt one for one.
        return len(self.prompt_ids) + max(self.max_new_tokens - 1, 0)

    @property
    def generated_ids(self) -> List[int]:
        """Every token this request generated, in order — stable across
        preemptions (``output_ids`` only holds the tokens generated
        since the last resume; the earlier ones live in the replay
        prompt)."""
        return self.prompt_ids[self.orig_prompt_len:] + self.output_ids

    def slo_burn(self, now_ns: int) -> Dict[str, float]:
        """Fraction of each declared latency budget this request has
        consumed as of ``now_ns`` — the live SLO burn the flight
        recorder samples every step and `paddle_slo_burn` reports:

        * ``ttft``     — elapsed since enqueue / ``slo_ttft_ms``,
          while the first token is still pending (once it lands the
          budget is settled — met or violated — and stops burning);
        * ``tpot``     — observed per-output-token latency /
          ``slo_tpot_ms``, once at least two tokens exist;
        * ``deadline`` — elapsed since enqueue / the ``deadline_ms``
          budget, while unfinished.

        1.0 means the budget is exactly spent; > 1.0 means the target
        is already missed (the violation counters confirm at finish).
        Empty for a request that declared no targets."""
        out: Dict[str, float] = {}
        if self.t_enqueue_ns is None:
            return out
        if self.slo_ttft_ms is not None and \
                self.t_first_token_ns is None:
            out["ttft"] = ((now_ns - self.t_enqueue_ns) / 1e6) \
                / self.slo_ttft_ms
        if self.slo_tpot_ms is not None and \
                self.t_first_token_ns is not None:
            n_out = len(self.output_ids) + self._absorbed
            if n_out > 1:
                tpot_ms = (now_ns - self.t_first_token_ns) / 1e6 \
                    / (n_out - 1)
                out["tpot"] = tpot_ms / self.slo_tpot_ms
        if self._deadline_ns is not None and self.state != "done":
            budget = self._deadline_ns - self.t_enqueue_ns
            if budget > 0:
                out["deadline"] = (now_ns - self.t_enqueue_ns) / budget
        return out

    @property
    def slo_met(self) -> bool:
        """Did this request complete its generation within every SLO it
        declared?  False while unfinished, for any truncating finish
        (evicted/cancelled/deadline), or when a declared TTFT / TPOT /
        deadline target was missed — the per-request bit behind the
        goodput number `tools/bench_slo.py` reports."""
        return self.state == "done" and \
            self.finish_reason in ("eos", "length") and \
            not self.slo_violations

    def cancel(self):
        """Cancel this request: a still-QUEUED request leaves the
        admission queue without ever taking a slot; a RUNNING request
        gives its slot and pages back between steps (routed through the
        same teardown as `DecodeEngine.evict`).  Either way
        ``finish_reason`` reads "cancelled" — the
        ``finished{reason="cancelled"}`` counter stays distinct from
        "evicted", which is reserved for engine-initiated eviction.
        Cancelling an already-finished request is a no-op."""
        if self.state == "done":
            return
        if self._engine is None:
            raise ValueError("request was never enqueued on an engine")
        if self.state == "queued":
            self._engine._cancel_queued(self)
        else:
            self._engine._cancel_running(self)


def _req_span_args(req: "Request", **extra) -> dict:
    """Span args for a request-carrying span: always the engine
    request id, plus the fleet trace id when one propagated
    (observability.fleettrace) — the key `/tracez/spans` and the
    fleet merge filter on.  No trace id -> byte-identical args to the
    pre-fleet-trace layout."""
    args = {"request": req.request_id}
    if req.trace_id is not None:
        args["trace"] = req.trace_id
    args.update(extra)
    return args


# ---------------------------------------------------------------------------
# Functional GPT forward (pure, jit-compiled once per signature)
# ---------------------------------------------------------------------------
def _extract_gpt_params(model):
    """Pull the weight arrays out of a models.gpt.GPT into a plain pytree
    for the pure step functions."""
    def arr(t):
        return None if t is None else unwrap(t)

    blocks = []
    for blk in model.blocks:
        blocks.append({
            "ln1_w": arr(blk.ln1.weight), "ln1_b": arr(blk.ln1.bias),
            "ln2_w": arr(blk.ln2.weight), "ln2_b": arr(blk.ln2.bias),
            "qkv_w": arr(blk.qkv.weight), "qkv_b": arr(blk.qkv.bias),
            "out_w": arr(blk.out_proj.weight),
            "out_b": arr(blk.out_proj.bias),
            "fc1_w": arr(blk.fc1.weight), "fc1_b": arr(blk.fc1.bias),
            "fc2_w": arr(blk.fc2.weight), "fc2_b": arr(blk.fc2.bias),
        })
    params = {
        "wte": arr(model.wte.weight), "wpe": arr(model.wpe.weight),
        "lnf_w": arr(model.ln_f.weight), "lnf_b": arr(model.ln_f.bias),
        "blocks": blocks,
    }
    if not model.cfg.tie_embeddings:
        params["head_w"] = arr(model.lm_head.weight)
        params["head_b"] = arr(getattr(model.lm_head, "bias", None))
    return params


# Weight leaves `_quantize_gpt_params` folds to int8 storage: every
# [in, out] matmul weight of the step functions.  Embeddings (wte is
# gathered, and the tied head needs its f32 transpose), position
# tables, layernorm params and biases stay f32 — they are a rounding
# error of the per-step weight traffic and some (wte) are read by
# non-matmul ops.
_QUANT_WEIGHT_KEYS = ("qkv_w", "out_w", "fc1_w", "fc2_w")


def _quantize_gpt_params(params):
    """The quantizing twin of `_extract_gpt_params`'s output
    (FLAGS_serve_weights=int8): every matmul weight leaf ``name`` is
    REPLACED by a ``name + "_q"`` int8 leaf (per-out-channel symmetric,
    `quantization.int8.quantize_weight` with quant_axis=1) and a
    ``name + "_s"`` f32 scale leaf holding ``absmax / Q_MAX`` — the
    multiplier the use-site dequant applies AFTER the int8 dot, so
    ``(x @ q) * s == x @ dequant(q)`` exactly (the per-out-channel
    scale commutes past the contraction).  Everything else passes
    through untouched, f32.  Returns ``(params, mats, bytes_saved)``:
    the new tree, the number of weight matrices folded, and the HBM
    bytes the fold reclaimed net of the scale leaves it added."""
    from ..quantization.int8 import Q_MAX, quantize_weight

    def fold(d):
        mats = 0
        saved = 0
        out = dict(d)
        for name in _QUANT_WEIGHT_KEYS + ("head_w",):
            w = out.get(name)
            if w is None:
                continue
            q, scale = quantize_weight(w, quant_axis=1)
            s = (scale / Q_MAX).astype(jnp.float32)
            del out[name]
            out[name + "_q"] = q
            out[name + "_s"] = s
            mats += 1
            saved += w.size * w.dtype.itemsize \
                - q.size * q.dtype.itemsize - s.size * s.dtype.itemsize
        return out, mats, saved

    top, mats, saved = fold(params)
    blocks = []
    for blk in params["blocks"]:
        b, m, s = fold(blk)
        blocks.append(b)
        mats += m
        saved += s
    top["blocks"] = blocks
    return top, mats, saved


def _wmm(x, container, name):
    """Weight matmul, storage-dtype-polymorphic: the ONE use-site shape
    every step function routes its weight matmuls through.  With the
    f32 leaf present (serve_weights=off) this is literally
    ``jnp.matmul`` — the trace emits the exact op it always emitted, so
    off-mode executables stay byte-identical.  With the quantized pair
    present, the dot runs MIXED f32×s8 (`preferred_element_type`
    keeps the accumulator f32) and the per-out-channel scale applies in
    the dot epilogue, where XLA fuses it — the weight streams from HBM
    as int8, and `hot_op_table` sees a distinct ``dot_general[f32xs8]``
    row.  The branch is Python-level on dict membership, resolved at
    trace time: one mode per executable, no in-graph select."""
    w = container.get(name)
    if w is not None:
        return jnp.matmul(x, w)
    acc = jax.lax.dot_general(
        x, container[name + "_q"],
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc * container[name + "_s"]


def _ln(x2d, w, b, eps):
    # the SAME layer_norm implementation the eager path runs on CPU
    # (ops/pallas/layer_norm._fwd_xla) — row-local, so applying it to a
    # single decode row matches the batched eager call bit for bit
    from ..ops.pallas.layer_norm import _fwd_xla

    return _fwd_xla(x2d, w, b, eps)


def _logits_of(params, h):
    if "head_w" in params or "head_w_q" in params:
        out = _wmm(h, params, "head_w")
        if params.get("head_b") is not None:
            out = out + params["head_b"]
        return out
    # tied head: wte stays f32 in every serve_weights mode (it is
    # gathered by the embedding lookup), so the tied logits matmul is
    # always the full-precision transpose
    return jnp.matmul(h, params["wte"].T)


# NaN/inf containment sentinel: a sampled-token value no real vocab can
# produce.  The in-graph guard below replaces the sample of any row
# whose logits went non-finite with it; the host side quarantines
# exactly that slot (finish_reason="fault") instead of streaming
# garbage or killing the batch (inference.resilience).
NAN_TOKEN = -1


def _guard_tokens(logits, tokens):
    """In-graph NaN/inf detection: rows whose logits are not all
    finite sample `NAN_TOKEN` instead of whatever argmax-of-NaN
    returns.  Healthy rows pass through bit-identically, so the guard
    never perturbs parity; the reduce is one pass over logits the
    sampler already read."""
    ok = jnp.isfinite(logits).all(axis=-1)
    return jnp.where(ok, tokens, NAN_TOKEN)


def _pack_refolds(kv, out, refolds):
    """A step's sampled tokens as the host fetches them.  A quantized
    pool's step packs its refold count (`KVPool.write`) behind the
    tokens — one more int32 element of a scalar or a row, one more row
    (count in column 0) of a ``[B, Q]`` block — so the host learns both
    from the single blocking fetch the step already pays
    (`DecodeEngine._note_refolds` takes it off again).  A float pool's
    tokens pass through: nothing is traced."""
    if not kv.quantized:
        return out
    out = out.astype(jnp.int32)
    if out.ndim == 2:
        pack = jnp.zeros((1, out.shape[1]), jnp.int32).at[0, 0].set(refolds)
        return jnp.concatenate([out, pack], axis=0)
    return jnp.concatenate([out.reshape(-1), refolds[None]])


def _gpt_prefill(params, ids, true_len, bt_row, kv, key, *,
                 num_heads, head_dim, eps, sampler, temperature, top_k,
                 top_p):
    """Prompt pass for ONE request: full causal attention over the
    (bucket-padded) prompt's in-flight K/V, its ``true_len`` K/V rows
    written into the request's pages (`pa.KVPool.write`; padding rows
    are not), first token sampled from the last valid position's logits.

    ids: [1, S_pad] int32; true_len: scalar int32; bt_row: [pages_max]
    int32; kv: the pool (donated).  Returns ``(kv, token)``.
    """
    from ..nn.functional.attention import _sdpa_reference

    s_pad = ids.shape[1]
    h = num_heads * head_dim
    pos = jnp.arange(s_pad, dtype=jnp.int32)
    x = params["wte"][ids[0]] + params["wpe"][pos]  # [S, h]

    # the one-request form of the batched write: a run of true_len rows
    # from position 0 (rows past it are bucket padding and are not written)
    bt, start, cap = bt_row[None], jnp.zeros((1,), jnp.int32), true_len[None]
    refolds = 0

    for li, blk in enumerate(params["blocks"]):
        y = _ln(x, blk["ln1_w"], blk["ln1_b"], eps)
        qkv = _wmm(y, blk, "qkv_w") + blk["qkv_b"]
        qkv = qkv.reshape(s_pad, 3, num_heads, head_dim)
        q = qkv[:, 0].transpose(1, 0, 2)[None]  # [1, H, S, D]
        k = qkv[:, 1].transpose(1, 0, 2)[None]
        v = qkv[:, 2].transpose(1, 0, 2)[None]
        kv, r = kv.write(li, qkv[None, :, 1], qkv[None, :, 2], bt, start,
                         cap)
        refolds += r
        attn = _sdpa_reference(q, k, v, None, 0.0, None, True)[0]
        attn = attn.transpose(1, 0, 2).reshape(s_pad, h)
        x = x + _wmm(attn, blk, "out_w") + blk["out_b"]
        y = _ln(x, blk["ln2_w"], blk["ln2_b"], eps)
        y = jax.nn.gelu(_wmm(y, blk, "fc1_w") + blk["fc1_b"],
                        approximate=True)
        x = x + _wmm(y, blk, "fc2_w") + blk["fc2_b"]

    h_last = jnp.take(x, true_len - 1, axis=0)[None]  # [1, h]
    h_last = _ln(h_last, params["lnf_w"], params["lnf_b"], eps)
    logits = _logits_of(params, h_last).astype(jnp.float32)
    token = sample_logits(logits, sampler=sampler, temperature=temperature,
                          top_k=top_k, top_p=top_p, key=key)
    token = _guard_tokens(logits, token)[0]
    return kv, _pack_refolds(kv, token, refolds)


def _prev_tokens(tokens, prev, from_prev):
    """The incoming token of each slot (``tokens`` [B], or row 0 of
    [B, Q]): the host's, or where ``from_prev`` is set the token the step
    before sampled for the slot, read from that step's output ``prev``
    as it lies on the device (its first ``B`` elements: a quantized
    pool's refold count rides behind them).  ``prev=None`` (the
    drafter's steps) leaves the text as it was."""
    if prev is None:
        return tokens
    b = tokens.shape[0]
    if tokens.ndim == 1:
        return jnp.where(from_prev, prev[:b], tokens)
    return tokens.at[:, 0].set(
        jnp.where(from_prev, prev[:b], tokens[:, 0]))


def _gpt_decode_step(params, kv, block_tables, seq_lens, tokens, active,
                     key, prev=None, from_prev=None, *, num_heads,
                     head_dim, eps, sampler, temperature, top_k, top_p):
    """One batched decode step over every slot: write the incoming
    token's K/V into its page, ragged paged attention over the pool,
    sample the next token.  The pool (`pa.KVPool`) is donated; a float
    one has the page of each live slot, K and V, rewritten where it
    lies by one `paged_kv_write` kernel a layer: on the chip nothing
    pool-sized moves but the per-layer slice the attention kernel takes
    (tests/test_tpu_compile.py).  Inactive slots write nothing (cap 0)
    and read length 0.  A slot with ``from_prev`` set takes its
    incoming token from ``prev``, the output of the step dispatched
    before this one (`_prev_tokens`).  Returns ``(kv, next tokens
    [B])``."""
    b = tokens.shape[0]
    h = num_heads * head_dim

    tokens = _prev_tokens(tokens, prev, from_prev)
    pos = seq_lens  # the incoming token's position
    x = params["wte"][tokens] + params["wpe"][pos]  # [B, h]
    caps = active.astype(jnp.int32)  # one row a live slot, none otherwise
    lens_now = seq_lens + caps
    refolds = 0

    for li, blk in enumerate(params["blocks"]):
        y = _ln(x, blk["ln1_w"], blk["ln1_b"], eps)
        qkv = _wmm(y, blk, "qkv_w") + blk["qkv_b"]
        qkv = qkv.reshape(b, 3, num_heads, head_dim)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [B, H, D]
        kv, r = kv.write(li, k[:, None], v[:, None], block_tables, seq_lens,
                         caps)
        refolds += r
        attn = kv.attend(q, li, block_tables, lens_now)
        x = x + _wmm(attn.reshape(b, h), blk, "out_w") + blk["out_b"]
        y = _ln(x, blk["ln2_w"], blk["ln2_b"], eps)
        y = jax.nn.gelu(_wmm(y, blk, "fc1_w") + blk["fc1_b"],
                        approximate=True)
        x = x + _wmm(y, blk, "fc2_w") + blk["fc2_b"]

    x = _ln(x, params["lnf_w"], params["lnf_b"], eps)
    logits = _logits_of(params, x).astype(jnp.float32)
    nxt = sample_logits(logits, sampler=sampler, temperature=temperature,
                        top_k=top_k, top_p=top_p, key=key)
    nxt = _guard_tokens(logits, nxt)
    return kv, _pack_refolds(kv, jnp.where(active, nxt, 0), refolds)


def _gpt_layer_bq(blk, li, x, kv, block_tables, seq_lens, write_caps,
                  lens_now, *, num_heads, head_dim, eps, mesh=None):
    """Block ``li`` over a ``[B, Q]`` grid of incoming tokens — the one
    layer `_gpt_mixed_step`, `_gpt_ragged_step` and
    `speculative._gpt_spec_verify` run; they differ in what they sample
    afterwards.  x: [B, Q, h]; rows ``i < write_caps[b]`` of slot ``b``
    are written at positions ``seq_lens[b] + i`` and attend causally
    from there (``lens_now = seq_lens + write_caps``).  Returns
    ``(x, kv, refolds)``.  ``mesh`` (the ragged step's alone) makes it
    the tensor-parallel layer; ``None`` constrains nothing."""
    b, qn, h = x.shape
    cst = pa.mesh_constrain(mesh)
    y = _ln(x.reshape(b * qn, h), blk["ln1_w"], blk["ln1_b"], eps)
    qkv = _wmm(y, blk, "qkv_w") + blk["qkv_b"]
    # head axis sharded over 'mp' from here: the K/V write and the
    # paged-attention gather stay chip-local (each chip owns its
    # head-slice of every page, and of a quantized pool's scales)
    qkv = cst(qkv.reshape(b, qn, 3, num_heads, head_dim),
              None, None, None, "mp", None)
    q = qkv[:, :, 0]                                     # [B, Q, H, D]
    kv, refolds = kv.write(li, qkv[:, :, 1], qkv[:, :, 2], block_tables,
                           seq_lens, write_caps, mesh=mesh)
    attn = cst(kv.attend(q, li, block_tables, lens_now,
                         q_offsets=seq_lens, mesh=mesh),
               None, None, "mp", None)
    # row-parallel out proj: replicating the residual forces the
    # cross-chip all-reduce exactly here (heads fuse head-major
    # into h, so the reshape keeps the 'mp' shards contiguous)
    x = cst(x + _wmm(attn.reshape(b, qn, h), blk, "out_w")
            + blk["out_b"])
    y = _ln(x.reshape(b * qn, h), blk["ln2_w"], blk["ln2_b"], eps)
    y = cst(jax.nn.gelu(_wmm(y, blk, "fc1_w") + blk["fc1_b"],
                        approximate=True),
            None, "mp")
    # row-parallel fc2: second all-reduce of the block
    x = cst(x + (_wmm(y, blk, "fc2_w") + blk["fc2_b"]).reshape(b, qn, h))
    return x, kv, refolds


def _gpt_mixed_step(params, kv, block_tables, seq_lens, tokens,
                    write_caps, sample_idx, sample_mask, key, prev=None,
                    from_prev=None, *, num_heads, head_dim, eps, sampler,
                    temperature, top_k, top_p):
    """ONE mixed prefill+decode step over every slot: prefilling slots
    contribute a prompt chunk (rows 0..cap-1 of their ``tokens`` row),
    decoding slots contribute their last sampled token (cap 1), stalled
    or inactive slots contribute nothing (cap 0).  K/V for every
    contributed row is written into the slot's already-reserved pages
    (`pa.KVPool.write`: rows past the cap keep what the page held),
    attention runs through
    the ragged multi-query paged kernel with per-sequence causal
    offsets (``q_offsets = seq_lens``: each chunk starts at the slot's
    current KV length), and ONE token per slot is sampled from the row
    ``sample_idx`` picks — the last prompt row for a slot finishing its
    prefill this step, row 0 for a decoding slot.  ``sample_mask``
    zeroes the draw for slots still mid-prefill.

    tokens: [B, Q_max] int32; write_caps/sample_idx: [B] int32;
    sample_mask: [B] bool; kv donated (the update is in
    place, on the chip too: see `_gpt_decode_step`); ``from_prev`` [B]
    bool takes row 0 of a slot from ``prev`` (`_prev_tokens`).
    Returns (kv, sampled [B] int32).

    The shapes are fixed per engine, so this compiles ONCE — the pow-2
    bucket zoo of legacy prefill executables collapses into this single
    program, and the `_JitTracker` retrace contract covers it.
    """
    tokens = _prev_tokens(tokens, prev, from_prev)
    b, qn = tokens.shape

    offs = jnp.arange(qn, dtype=jnp.int32)
    pos = seq_lens[:, None] + offs[None, :]              # [B, Q]
    wpe_max = params["wpe"].shape[0] - 1
    x = params["wte"][tokens] + params["wpe"][jnp.minimum(pos, wpe_max)]
    lens_now = seq_lens + write_caps
    refolds = 0

    for li, blk in enumerate(params["blocks"]):
        x, kv, r = _gpt_layer_bq(
            blk, li, x, kv, block_tables, seq_lens, write_caps, lens_now,
            num_heads=num_heads, head_dim=head_dim, eps=eps)
        refolds += r

    # sample ONE row per slot (not all Q like the verify step): the
    # lm-head matmul runs over [B, h], so mixed-step sampling costs the
    # same as a classic decode step's
    sel = x[jnp.arange(b), sample_idx]                   # [B, h]
    sel = _ln(sel, params["lnf_w"], params["lnf_b"], eps)
    logits = _logits_of(params, sel).astype(jnp.float32)
    nxt = sample_logits(logits, sampler=sampler, temperature=temperature,
                        top_k=top_k, top_p=top_p, key=key)
    nxt = _guard_tokens(logits, nxt)
    return kv, _pack_refolds(kv, jnp.where(sample_mask, nxt, 0), refolds)


# ---------------------------------------------------------------------------
# The unified ragged step (FLAGS_ragged_step).
#
# ONE executable serves every phase of a speculative,
# chunk-prefilling, continuously-batched serve: each slot's row in the
# fixed ``[slots, Q_r]`` grid carries its own query span via
# ``write_caps`` — 1 for a decoding slot, C for a prompt chunk, K+1
# for a verify window, 0 to sit the step out — and the ragged
# multi-query paged-attention kernel (``q_offsets = seq_lens``) gives
# every row its own causal offset.  The host interprets the
# per-position targets by phase: row 0 for a decode slot, row C-1 for
# a slot finishing its prefill, the accept loop for a verify window.
# Collapsing `_gpt_decode_step` / `_gpt_mixed_step` /
# `_gpt_spec_verify` into this one program means
# one compile, one retrace contract, no compile-time phase branch —
# the "ragged_compiles == 1, {decode,mixed,verify}_compiles == 0"
# counter assertion tests/test_ragged_step.py pins.  The split-path
# functions above are the FLAGS_ragged_step=off path and the
# greedy-parity oracle (which of the two goes: ROADMAP Queue 3).
# ---------------------------------------------------------------------------
def _gpt_ragged_step(params, kv, block_tables, seq_lens, tokens,
                     write_caps, key, *, num_heads, head_dim, eps,
                     sampler, temperature, top_k, top_p, mesh=None):
    """The unified ragged step: score up to Q_r incoming tokens per
    slot in ONE pass — write rows ``i < write_caps[b]`` into the slot's
    already-reserved pages (`pa.KVPool.write` leaves capped rows out),
    run ragged multi-query paged attention with per-sequence causal
    offsets, and draw a target token at EVERY position with the
    engine's own `sample_logits`.

    tokens: [B, Q_r] int32 — position ``seq_lens[b] + i`` holds
    ``tokens[b, i]``; write_caps: [B] int32 in [0, Q_r] — the row's
    span (0 = the slot sits this step out; its targets are garbage the
    host ignores); kv donated (in-place cache update; a
    speculative rejection only shrinks the host's ``seq_lens``).
    Returns (kv, targets [B, Q_r] int32).

    Positions sample with ``fold_in(key, i)`` (the verify convention);
    greedy ignores the key, which is why greedy tokens are
    bit-identical to the split path — the oracle the parity tests pin.
    Rows past a slot's span cost dense FLOPs but no extra KV traffic
    (K/V pages are gathered once per slot for all Q_r rows), so size
    ``prefill_q_max`` / K to the traffic when decode dominates."""
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
            num_heads=num_heads, head_dim=head_dim, eps=eps, mesh=mesh)
        refolds += r

    xf = _ln(x.reshape(b * qn, h), params["lnf_w"], params["lnf_b"], eps)
    logits = _logits_of(params, xf).astype(jnp.float32)
    logits = logits.reshape(b, qn, -1)
    targets = [
        _guard_tokens(
            logits[:, i],
            sample_logits(logits[:, i], sampler=sampler,
                          temperature=temperature, top_k=top_k,
                          top_p=top_p, key=jax.random.fold_in(key, i)))
        for i in range(qn)
    ]
    return kv, _pack_refolds(kv, jnp.stack(targets, axis=1), refolds)


def _reset_kv_scales(k_scales, v_scales, fresh_idx):
    """Zero the quant-scale entries of freshly (re)allocated pages —
    one small donated executable the engine runs between steps whenever
    the allocator handed out pages since the last device call, so a
    recycled page's stale scale can never leak into its new owner's
    quantization (the determinism contract `pa.paged_quant_write`
    documents).  ``fresh_idx`` is a fixed-size [num_pages] int32
    buffer padded with ``num_pages`` (out-of-bounds: dropped by the
    scatter)."""
    k_scales = k_scales.at[:, :, fresh_idx].set(0.0)
    v_scales = v_scales.at[:, :, fresh_idx].set(0.0)
    return k_scales, v_scales


class _Step:
    """One dispatched step executable, from its dispatch until the host
    has landed its tokens (`DecodeEngine._retire`).  It keeps what it
    was dispatched with — for each slot the request of its row
    (``reqs``; None: no row) and that request's preemption count at the
    time (a re-admitted request is another binding), the row's write
    (``caps``: a chunk's length, 1 for a decode row), the chunks, which
    rows sample, and the KV lengths it started from — so the host can
    predict the next step from it and land it after the slots have moved
    on.  ``final`` are the slots whose row samples its request's last
    token by length: released before the next admission, their request
    lands here without a slot.  ``release`` holds the page lists of
    requests that left while this step wrote into them; they go back to
    the pool once its tokens are read (``host``)."""

    __slots__ = ("step_no", "exe", "decode_rows", "ahead", "toks", "host",
                 "reqs", "leases", "caps", "chunk_of", "sample_idx",
                 "sample_mask", "lens", "tokens", "final", "release",
                 "n_active", "t0", "t0_ns", "dispatch_s", "fetch_s")

    def carries(self, s: int, req) -> bool:
        """Is the row of slot ``s`` this binding of ``req``?"""
        return self.reqs[s] is req and self.leases[s] == req.preemptions

    def writes_for(self, req) -> bool:
        return self.host is None and any(r is req for r in self.reqs)


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------
class DecodeEngine:
    """Continuous-batching decode over a paged KV cache.

    ``model`` is a `models.gpt.GPT` (dropout must be inactive — call
    ``model.eval()``).  Requests are admitted into ``max_batch_size``
    slots as they arrive and evicted the step they finish; the per-step
    decode is one donated jitted executable reused across the whole
    serve (signature-keyed: shapes never change, so it compiles once).
    """

    # itertools.count for the same reason as Request._next_id: ids
    # label per-engine gauges and trace lanes, and a concurrent
    # construction race would merge two engines onto one lane
    _next_engine_id = itertools.count()

    def __init__(self, model, max_batch_size=4, max_seq_len=None,
                 page_size=None, num_pages=None, sampler="greedy",
                 temperature=1.0, top_k=0, top_p=1.0, seed=0,
                 eos_token_id=None, dtype=None, spec_decode_k=None,
                 drafter=None, chunked_prefill=None,
                 prefill_chunk_tokens=None, prefill_q_max=None,
                 prefix_cache=None, scheduler=None, fault_plan=None,
                 journal_dir=None, step_timeout_ms=None,
                 flight_window=None, flight_dir=None, kv_quant=None,
                 cost_model=None, cost_calibration=None, alerts=None,
                 profile=None, profile_sample_steps=None,
                 ragged_step=None, spec_adaptive_k=None,
                 serve_mesh=None, cache_generated_pages=None,
                 serve_weights=None):
        cfg = model.cfg
        if getattr(cfg, "dropout", 0.0) and model.training:
            # don't silently flip the caller's train/eval mode — dropout
            # is simply not part of the decode step functions
            raise ValueError(
                "DecodeEngine serves inference only: call model.eval() "
                "first (cfg.dropout > 0 and the model is in train mode)")
        self._params = _extract_gpt_params(model)
        self._num_heads = cfg.num_heads
        self._head_dim = cfg.hidden_size // cfg.num_heads
        self._eps = float(getattr(model.ln_f, "_epsilon", 1e-5))
        self._num_layers = cfg.num_layers
        self._slots = int(max_batch_size)
        self._max_seq_len = int(max_seq_len or cfg.max_seq_len)
        if self._max_seq_len > cfg.max_seq_len:
            # positions past the wpe table would silently CLAMP in the
            # embedding gather (wrong logits, no error) — refuse instead
            raise ValueError(
                f"max_seq_len {self._max_seq_len} exceeds the model's "
                f"position table ({cfg.max_seq_len})")
        kv_dtype = jnp.dtype(dtype) if dtype is not None else \
            self._params["wte"].dtype
        # quantized KV pages (explicit arg wins, else FLAGS_kv_quant):
        # "int8" stores pages as int8 with per-page, per-head symmetric
        # scales in parallel donated arrays; "off" (default) is the
        # bit-exact full-precision path — it constructs the exact same
        # executables as before the feature existed.
        from ..core import flags as _early_flags

        if kv_quant is None:
            kv_quant = str(_early_flags.flag("kv_quant"))
        kv_quant = str(kv_quant)
        if kv_quant not in ("off", "int8"):
            raise ValueError(
                f"kv_quant must be 'off' or 'int8', got {kv_quant!r}")
        self._kv_quant = kv_quant == "int8"
        self._kv_quant_mode = kv_quant
        # quantized weight storage (explicit arg wins, else
        # FLAGS_serve_weights): "int8" folds every matmul weight of the
        # step executables to per-out-channel symmetric int8 + f32
        # scales (`_quantize_gpt_params`) so weights stream from HBM at
        # a quarter the bytes; "off" (default) keeps the f32 leaves and
        # the step functions trace the exact same ops as before the
        # feature existed — zero new executables, bit-exact tokens.
        if serve_weights is None:
            serve_weights = str(_early_flags.flag("serve_weights"))
        serve_weights = str(serve_weights)
        if serve_weights not in ("off", "int8"):
            raise ValueError(
                f"serve_weights must be 'off' or 'int8', got "
                f"{serve_weights!r}")
        self._weight_quant = serve_weights == "int8"
        self._serve_weights_mode = serve_weights
        # fingerprint sample rows, captured from the F32 tree before
        # any weight fold: `_model_fingerprint`/`config_fingerprint`
        # hash one qkv row per block, and quantization RENAMES that
        # leaf — sampling here keeps both fingerprints a pure function
        # of the model's weights, identical across serve_weights modes
        # (the mode itself folds into config_fingerprint separately)
        self._fp_wrows = [
            np.asarray(jax.device_get(blk["qkv_w"][0]),
                       np.float32).tobytes()
            for blk in self._params["blocks"]]
        # the page-size autotune cache keys on the STORAGE dtype of the
        # pages — an int8 pool must never reuse an fp32-picked page
        # size (a quarter the bytes per page changes the VMEM-fit
        # winner), so the quantized storage dtype drives the pick
        storage_dtype = jnp.dtype(jnp.int8) if self._kv_quant else kv_dtype
        self._page = int(page_size or pa.default_page_size(
            self._max_seq_len, self._head_dim, storage_dtype))
        # block tables round UP: a horizon that doesn't tile just leaves
        # the last page partially used (ragged lengths mask the rest)
        self._pages_per_seq = -(-self._max_seq_len // self._page)
        n_pages = int(num_pages or self._slots * self._pages_per_seq)
        self.pool = KVBlockPool(n_pages)
        # the K/V pages, and the per-page, per-head dequant scales of a
        # quantized pool: ONE donated argument of every step executable
        self._kv = pa.KVPool.zeros(self._num_layers, self._num_heads,
                                   n_pages, self._page, self._head_dim,
                                   storage_dtype)
        self._scale_reset_fn = None
        # pages the allocator handed out since the last scale reset —
        # their (possibly stale) scale entries zero on the next
        # between-steps flush, BEFORE any quantized write sees them
        self._fresh_pages: List[int] = []

        self._bt = np.zeros((self._slots, self._pages_per_seq), np.int32)
        self._lens = np.zeros(self._slots, np.int32)
        self._active = np.zeros(self._slots, bool)
        self._last = np.zeros(self._slots, np.int32)
        self._by_slot: List[Optional[Request]] = [None] * self._slots
        # prompt tokens already consumed per slot (chunked prefill
        # cursor); a slot is mid-prefill while the cursor trails its
        # request's prompt length
        self._prefill_pos = np.zeros(self._slots, np.int32)
        # min-heap of free slot indices: admission pops the lowest slot,
        # _finish pushes it back — O(log slots) per event instead of the
        # old scan over every slot per admitted request
        self._free_slots = list(range(self._slots))
        heapq.heapify(self._free_slots)

        self._sampling = dict(sampler=sampler,
                              temperature=float(temperature),
                              top_k=int(top_k), top_p=float(top_p))
        self._eos = eos_token_id
        self._key = jax.random.PRNGKey(seed)
        self._step_no = 0  # the last step dispatched
        # the step dispatched and not yet landed (`step`'s lookahead),
        # and the ``prev`` operand of a step dispatched with none
        self._inflight: Optional[_Step] = None
        self._no_prev = jnp.zeros(self._slots + int(self._kv_quant),
                                  jnp.int32)
        # what the profiler spans of the current `step()` call carry as
        # ``step=``, and the wall of that call which ``decode_time_s``
        # / ``prefill_time_s`` already hold (dispatch to fetched tokens)
        self._span_step = 0
        self._batch_s = 0.0
        self._prefill_no = 0
        self._queue: "deque[Request]" = deque()
        self._decode_fn = None  # shapes are fixed: ONE jitted step
        self._mixed_fn = None   # ONE mixed prefill+decode executable
        self._prefill_fns = {}
        # engine id = the chrome-trace tid of this engine's step spans
        # (several engines in one process stay on separate lanes)
        self._engine_id = next(DecodeEngine._next_engine_id)
        # FLAGS_metrics_report_interval_s > 0 -> periodic snapshot
        # reporter, started once per process
        _obs.maybe_start_reporter()
        # fold the weights to int8 storage now, before anything
        # downstream consumes the tree: the drafter quantizes against
        # `engine._weight_quant` at bind, and the mesh block shards
        # whatever leaves exist (`gpt_serving_rules` carries the
        # `*_q`/`*_s` pairs on the same axes as their f32 originals)
        if self._weight_quant:
            self._fold_weight_quant()

        from ..core import flags as _flags

        # chunked prefill (explicit args win, else the flags): prompt
        # ingestion rides the decode step as fixed-shape [slots, Q_max]
        # mixed batches instead of one-shot bucket-padded prefills, so
        # an admission never stalls running decodes.  The legacy path
        # (chunked_prefill=0) stays as the greedy-parity oracle.
        if chunked_prefill is None:
            chunked_prefill = bool(_flags.flag("chunked_prefill"))
        self._chunked = bool(chunked_prefill)
        if prefill_chunk_tokens is None:
            prefill_chunk_tokens = int(_flags.flag("prefill_chunk_tokens"))
        if prefill_chunk_tokens < 1:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1, got "
                f"{prefill_chunk_tokens}")
        # per-step prompt-token budget (never wider than the horizon: a
        # chunk cannot outsize a prompt)
        self._chunk_budget = min(int(prefill_chunk_tokens),
                                 self._max_seq_len)
        # Q_max: the mixed executable's per-slot row width.  Defaults to
        # the budget; setting it SMALLER caps the step's compute (the
        # executable always pays slots x Q_max rows) while the budget
        # still spreads across several prefilling slots per step —
        # decoupling per-step latency from aggregate prefill throughput
        q_max_explicit = prefill_q_max is not None
        if prefill_q_max is None:
            prefill_q_max = self._chunk_budget
        if prefill_q_max < 1:
            raise ValueError(
                f"prefill_q_max must be >= 1, got {prefill_q_max}")
        self._q_max = min(int(prefill_q_max), self._chunk_budget)

        # prefix caching (explicit arg wins, else FLAGS_prefix_cache):
        # full prompt KV pages are content-addressed by a chain hash and
        # shared across requests at refcount+1; admission maps the
        # longest page-aligned cached prefix and chunked prefill starts
        # at the first novel token.  Requires chunked prefill — the
        # legacy one-shot executable cannot start at a nonzero offset
        # (it is the prefix_cache=0 parity oracle's other half).
        if prefix_cache is None:
            prefix_cache = bool(_flags.flag("prefix_cache")) and \
                self._chunked
        elif prefix_cache and not self._chunked:
            raise ValueError(
                "prefix_cache needs chunked prefill: the legacy one-"
                "shot prefill executable cannot start mid-prompt (set "
                "chunked_prefill=1, or drop prefix_cache)")
        self._prefix_cache = bool(prefix_cache)
        self._model_salt = self._model_fingerprint() \
            if self._prefix_cache else b""
        # generated-page registration (explicit arg wins, else
        # FLAGS_cache_generated_pages): extend the prompt's chain hash
        # over the DECODE stream and content-address each generated
        # page the moment it fills, so fanout sharing a decode prefix
        # (and the fleet router's affinity key) prefix-hits it.  Off
        # (default) keeps pool occupancy bit-exact with the
        # prompt-pages-only engine; meaningless without the prefix
        # cache, so it resolves False there rather than refusing (the
        # flag must not break prefix_cache=0 engines).
        if cache_generated_pages is None:
            cache_generated_pages = bool(
                _flags.flag("cache_generated_pages"))
        self._cache_generated = bool(cache_generated_pages) and \
            self._prefix_cache
        self._evictions_seen = 0
        # FLAGS_kv_pool_debug: audit the pool partition + refcounts at
        # every step boundary (engine idle point — host-only cost)
        self._pool_debug = bool(_flags.flag("kv_pool_debug"))

        # speculative decoding (propose K / verify in one multi-query
        # pass): explicit arg wins, else FLAGS_spec_decode_k.  The
        # subsystem lives in inference.speculative; constructed lazily
        # so non-speculative engines never import it.
        if spec_decode_k is None:
            spec_decode_k = int(_flags.flag("spec_decode_k"))
        self._spec = None
        if drafter is not None and not spec_decode_k:
            # a drafter with K == 0 would be silently ignored and the
            # engine would serve classic one-token steps — refuse loudly
            raise ValueError(
                "drafter passed but speculative decoding is off: set "
                "spec_decode_k >= 1 (or FLAGS_spec_decode_k)")
        # adaptive per-slot speculation depth (FLAGS_spec_adaptive_k):
        # an explicit True without speculation is refused like a
        # drafter without K; the flag-resolved value is simply ignored
        # on non-speculative engines (it modifies speculation, it does
        # not imply it)
        if spec_adaptive_k and not spec_decode_k:
            raise ValueError(
                "spec_adaptive_k passed but speculative decoding is "
                "off: set spec_decode_k >= 1 (or FLAGS_spec_decode_k)")
        if spec_adaptive_k is None:
            spec_adaptive_k = bool(_flags.flag("spec_adaptive_k"))
        if spec_decode_k:
            from .speculative import SpeculativeDecoder

            self._spec = SpeculativeDecoder(self, k=int(spec_decode_k),
                                            drafter=drafter,
                                            adaptive=bool(spec_adaptive_k))

        # unified ragged step (explicit arg wins, else
        # FLAGS_ragged_step): decode, mixed prefill+decode, and
        # speculative-verify traffic all dispatch the ONE
        # `_gpt_ragged_step` executable, each row carrying its own
        # query span.  Off (the default) keeps the split executables
        # byte-identical — the greedy-parity oracle.
        ragged_explicit = ragged_step is not None
        if ragged_step is None:
            ragged_step = bool(_flags.flag("ragged_step"))
        self._ragged = bool(ragged_step)
        self._ragged_fn = None

        # tensor-parallel serving mesh (explicit arg wins, else
        # FLAGS_serve_mesh): 'mp=N' builds a Mesh over N devices,
        # shards the params by the shared regex partition rules
        # (parallel.partition.gpt_serving_rules: column-split qkv/fc1,
        # row-split out/fc2, replicated norms/embeddings/head) and the
        # KV page pool on the HEAD axis — each chip holds its
        # head-slice of every page, so page ids stay logical and the
        # allocator / block tables stay host-global, untouched.  The
        # mesh implies the unified ragged step: it shards the ONE step
        # executable per KV mode rather than three.  '' (default) is
        # the single-chip path: no mesh, no shardings, bit-exact.
        if serve_mesh is None:
            serve_mesh = str(_flags.flag("serve_mesh"))
        serve_mesh = str(serve_mesh or "").strip()
        self._serve_mesh = serve_mesh
        self._mesh = None
        self._mesh_mp = 1
        self._repl_sharding = None
        if serve_mesh:
            from ..parallel.partition import (build_mesh,
                                              gpt_serving_rules,
                                              match_partition_rules,
                                              parse_mesh_spec)

            axes = parse_mesh_spec(serve_mesh)
            if [a for a, _ in axes] != ["mp"]:
                raise ValueError(
                    f"serve_mesh supports a single tensor-parallel "
                    f"axis 'mp=N', got {serve_mesh!r}")
            mp = axes[0][1]
            if len(jax.devices()) < mp:
                raise ValueError(
                    f"serve_mesh {serve_mesh!r} needs {mp} devices, "
                    f"have {len(jax.devices())}")
            if self._num_heads % mp:
                raise ValueError(
                    f"serve_mesh {serve_mesh!r}: num_heads "
                    f"{self._num_heads} not divisible by mp={mp}")
            if ragged_explicit and not self._ragged:
                raise ValueError(
                    "serve_mesh requires the unified ragged step (the "
                    "mesh shards the ONE step executable per KV "
                    "mode): drop ragged_step=0, or the mesh")
            self._ragged = True
            self._mesh = build_mesh(serve_mesh)
            self._mesh_mp = mp
            self._repl_sharding = NamedSharding(self._mesh,
                                                PartitionSpec())
            specs = match_partition_rules(gpt_serving_rules(),
                                          self._params)
            self._params = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self._mesh, s)),
                self._params, specs)
            self._kv = self._kv.sharded(self._mesh)
        # the unified executable's per-slot row width: wide enough for
        # the widest span any phase contributes — a decode row (1), a
        # prompt chunk (Q_max), a verify window (K+1).  Rows past a
        # slot's span cost dense FLOPs but no extra KV traffic — but
        # EVERY round pays the full grid, so a wide chunk width taxes
        # the steady state (all-decode / all-verify rounds, which
        # dominate any long serve) to speed the transient prefill
        # phase.  When the caller did not pin prefill_q_max, a ragged
        # engine therefore chunks prompts at one KV page of query span
        # per slot (never narrower than the verify window): chunks stay
        # page-aligned for the prefix cache and the steady-state
        # padding is bounded.  An explicit prefill_q_max always wins —
        # it sizes the grid verbatim.
        if self._ragged and self._chunked and not q_max_explicit:
            self._q_max = min(self._q_max, max(
                self._page,
                (self._spec.k + 1) if self._spec is not None else 1))
        self._q_ragged = max(1,
                             self._q_max if self._chunked else 1,
                             (self._spec.k + 1) if self._spec is not None
                             else 1)

        # admission scheduler (explicit arg wins, else FLAGS_sched_policy):
        # owns the between-steps admission ORDER and the preemption /
        # deadline-expiry decisions.  "fifo" reproduces the historical
        # strict-arrival-order admission bit for bit; "slo" adds priority
        # + earliest-deadline-first + preempt/resume (inference.frontend).
        from .frontend import make_scheduler

        if scheduler is None:
            scheduler = str(_flags.flag("sched_policy"))
        self._scheduler = make_scheduler(scheduler)
        self._scheduler.bind(self)

        # fault injection + containment (inference.resilience):
        # explicit arg wins (a FaultPlan or a spec string), else
        # FLAGS_fault_inject.  The manager owns the containment ladder
        # `step()` runs under (retry -> degrade -> bisect-quarantine)
        # and the degraded-mode state; with no plan armed every hook is
        # a single `is None` check.
        from .resilience import FaultPlan, ResilienceManager

        if fault_plan is None:
            fault_plan = FaultPlan.parse(str(_flags.flag("fault_inject")))
        elif isinstance(fault_plan, str):
            fault_plan = FaultPlan.parse(fault_plan)
        self._fault = fault_plan
        self._resilience = ResilienceManager(self)
        # construction-time config the degradation ladder may flip at
        # runtime (legacy fallback) and the re-enable probe restores
        self._chunked_cfg = self._chunked
        self._prefix_cache_cfg = self._prefix_cache

        # durable serving + hung-step watchdog (inference.durability):
        # explicit args win, else the flags.  Disarmed, both are None
        # and every hook on the serve path is a single `is None` check.
        if journal_dir is None:
            journal_dir = str(_flags.flag("journal_dir")) or None
        if step_timeout_ms is None:
            step_timeout_ms = float(_flags.flag("step_timeout_ms"))
        self._journal_dir = journal_dir
        self._step_timeout_ms = float(step_timeout_ms)
        # set True by the watchdog's abandon path: a step still blocked
        # in a worker thread must mutate nothing when it returns
        self._abandoned = False
        self._config_fp: Optional[bytes] = None
        self._durability = None
        self._watchdog = None
        compile_cache = str(_flags.flag("compile_cache_dir"))
        if compile_cache:
            from ..core.compile_cache import enable_compile_cache

            enable_compile_cache(compile_cache)

        # everything `resilience.recover` needs to rebuild THIS engine
        # after a fatal fault: the resolved construction config (flag
        # lookups already applied, so a flag flip mid-serve cannot
        # change the rebuilt engine).  Scheduler/drafter instances are
        # reused — recover() unbinds them first and retires the old
        # engine; the fault plan keeps its occurrence counters so an
        # injected schedule never re-fires after the rebuild.
        self._ctor = dict(
            model=model, max_batch_size=self._slots,
            max_seq_len=self._max_seq_len, page_size=self._page,
            num_pages=self.pool.num_pages,
            sampler=self._sampling["sampler"],
            temperature=self._sampling["temperature"],
            top_k=self._sampling["top_k"],
            top_p=self._sampling["top_p"],
            seed=seed, eos_token_id=self._eos, dtype=kv_dtype,
            spec_decode_k=(self._spec.k if self._spec else 0),
            drafter=(self._spec.drafter if self._spec else None),
            chunked_prefill=self._chunked,
            prefill_chunk_tokens=self._chunk_budget,
            prefill_q_max=self._q_max,
            prefix_cache=self._prefix_cache,
            cache_generated_pages=self._cache_generated,
            scheduler=self._scheduler, fault_plan=self._fault,
            journal_dir=self._journal_dir,
            step_timeout_ms=self._step_timeout_ms,
            kv_quant=self._kv_quant_mode,
            serve_weights=self._serve_weights_mode,
            ragged_step=self._ragged,
            spec_adaptive_k=(self._spec.adaptive
                             if self._spec is not None else False),
            serve_mesh=self._serve_mesh)

        # flight recorder (observability.flight): always-cheap bounded
        # ring of per-step records — batch composition, phase
        # breakdown, ladder events, SLO burn.  flight_window=0 turns
        # it off entirely (the parity/overhead oracle); the dump
        # directory defaults beside the journal.
        if flight_window is None:
            flight_window = int(_flags.flag("flight_window"))
        if flight_dir is None:
            flight_dir = str(_flags.flag("flight_dir")) or None
        self._flight = None
        if int(flight_window) > 0:
            from ..observability.flight import FlightRecorder

            fdir = flight_dir or (
                os.path.join(self._journal_dir, "flight")
                if self._journal_dir else None)
            self._flight = FlightRecorder(self, window=int(flight_window),
                                          flight_dir=fdir)
        self._ctor["flight_window"] = int(flight_window)
        self._ctor["flight_dir"] = flight_dir

        # cost observatory (observability.costmodel): static profiles
        # + calibrated step-cost prediction + HBM ledger + roofline.
        # Explicit arg wins, else FLAGS_cost_model; disarmed = one
        # `is None` check per step and bit-exact serving.
        # ``cost_calibration`` seeds the per-executable calibration
        # from a prior life (recover / restore_from_dir), so a rebuilt
        # engine predicts accurately from its first step.
        if cost_model is not None and bool(cost_model) and \
                not bool(_flags.flag("cost_model")):
            # explicit opt-in AGAINST a disabled flag: arm profile
            # extraction too (the process-global table serves this
            # engine's predictor).  Not latched when the flag is on —
            # recover()/restore pass the resolved cost_model=True of a
            # flag-defaulted engine explicitly, and that must not pin
            # extraction past a later FLAGS_cost_model=0
            _costmodel._force_enable()
        if cost_model is None:
            cost_model = bool(_flags.flag("cost_model"))
        self._cost = None
        if bool(cost_model):
            self._cost = _costmodel.CostModel(
                self, calibration=cost_calibration)
        # cost-gated admission (FLAGS_sched_cost_admission): resolved
        # at construction like every other serving flag — default off
        # keeps _admit_one's decision sequence bit-exact
        self._cost_admission = self._cost is not None and \
            bool(_flags.flag("sched_cost_admission"))
        self._ctor["cost_model"] = bool(cost_model)
        self._ctor["cost_calibration"] = None

        # profiling plane (observability.profiling): sampled device-
        # sync probes + hot-op tables + bounded capture sessions.
        # Explicit arg wins, else FLAGS_profile; disarmed = one
        # `is None` check per serve-loop hook, zero probes, bit-exact.
        from ..observability import profiling as _profiling_mod

        if profile is not None and bool(profile) and \
                not bool(_flags.flag("profile")):
            # explicit opt-in AGAINST a disabled flag: arm hot-op
            # extraction at the costmodel chokepoint too (the
            # costmodel._force_enable pattern — not latched when the
            # flag is on, so recover()/restore re-passing a resolved
            # profile=True cannot pin extraction past a later
            # FLAGS_profile=0)
            _profiling_mod._force_enable()
        if profile is None:
            profile = bool(_flags.flag("profile"))
        self._profiling = None
        if bool(profile):
            self._profiling = _profiling_mod.Profiler(
                self, sample_steps=profile_sample_steps)
        self._ctor["profile"] = bool(profile)
        # the RESOLVED cadence rides wire_config so recover/restore
        # rebuild an armed engine probing at the same rate
        self._ctor["profile_sample_steps"] = (
            self._profiling.sample_steps
            if self._profiling is not None
            else profile_sample_steps)

        # ops plane (observability.opsserver + observability.alerts):
        # the engine always registers with the process-global ops
        # registry (one locked dict insert; retirement deregisters),
        # while the HTTP listener and the between-steps alert engine
        # arm only when FLAGS_ops_port is set — or when ``alerts=``
        # opts in explicitly (True = the shipped default catalog, a
        # rule sequence = a custom table).  Disarmed, the serve loop
        # pays one `is None` check per step and zero alert counters.
        # Resolved BEFORE the durability manager below: the journal's
        # cfg record snapshots wire_config at construction, and a
        # restored engine must rebuild with the same alert table.
        from ..observability import alerts as _alerts_mod
        from ..observability import opsserver as _opsserver

        if alerts is None:
            alerts = int(_flags.flag("ops_port")) != 0
        self._alerts = None
        if alerts is not False and alerts != 0:
            rules = None if alerts is True else alerts
            self._alerts = _alerts_mod.AlertEngine(self, rules=rules)
            self._ctor["alerts"] = tuple(self._alerts.rules)
        else:
            self._ctor["alerts"] = False

        if self._journal_dir:
            from .durability import DurabilityManager

            self._durability = DurabilityManager(self, self._journal_dir)
        if self._step_timeout_ms > 0:
            from .durability import StepWatchdog

            self._watchdog = StepWatchdog(self, self._step_timeout_ms)
        from .durability import set_health

        set_health(self._engine_id, "live", span=False)
        _opsserver.register_engine(self)
        _opsserver.maybe_start_ops_server()

    def _phase(self, name: str, **args):
        """Context manager timing a LEAF flight-recorder phase (device
        dispatch, fetch, cache ops) and opening its ``engine.<name>``
        span in the profiler's trace — the span alone when the
        recorder is off, so call sites read `with self._phase("x"):`
        without repeating the None check.  ``args`` ride on the span
        as stats."""
        fr = self._flight
        return fr.phase(name, **args) if fr is not None else \
            _flight.engine_span(self, name, **args)

    def _excl_phase(self, name: str):
        """Like `_phase` for COMPOSITE host phases (admit/draft/emit):
        recorded exclusive of the leaf phases nested inside them."""
        fr = self._flight
        return fr.exclusive_phase(name) if fr is not None else \
            _flight.engine_span(self, name)

    def _fold_weight_quant(self) -> None:
        """Fold this engine's matmul weights to int8 storage
        (serve_weights=int8): every f32 ``*_w`` matmul leaf of
        ``self._params`` is replaced by the ``*_q``/``*_s`` pair the
        `_wmm` use sites dequantize fused at the dot — the sanctioned
        construction-time param-tree mutation `analysis`'s
        engine-mutation pass names.  Runs ONCE, before any executable
        traces (and before the mesh shards the tree); the counters it
        bumps are how the off mode's zero stays provable."""
        self._params, mats, saved = _quantize_gpt_params(self._params)
        _stats_add(weight_quant_mats=mats,
                   weight_quant_bytes_saved=saved)
        _obs.WEIGHT_QUANT_SAVED_BYTES.set(saved,
                                          engine=self._engine_id)

    def _model_fingerprint(self) -> bytes:
        """Sampling-invariant model identity — the chain-hash root.
        Cached KV is a function of the weights and the token prefix
        ONLY, so sampler/temperature/top-k/top-p are deliberately NOT
        keyed: engines serving different sampling configs over the same
        weights would share prefixes soundly (the pool is per-engine
        today; the key keeps the scheme honest if pools are ever
        shared).  Weight content is represented by the embedding
        table's first row plus one row of EVERY block's qkv projection
        and the architecture dims — a few small host transfers at
        construction.  Two fine-tunes sharing frozen embeddings still
        key differently (their attention weights diverge); this is a
        fingerprint, not a proof — a full-weights digest belongs in
        any future cross-process cache tier."""
        h = hashlib.blake2b(digest_size=16)
        p = self._params
        h.update(np.asarray(jax.device_get(p["wte"][0]),
                            np.float32).tobytes())
        for row in self._fp_wrows:
            # f32 qkv rows sampled at construction, BEFORE any
            # serve_weights fold renamed the leaf — the fingerprint is
            # a function of the model, not of the storage dtype
            h.update(row)
        h.update(str((tuple(p["wte"].shape), len(p["blocks"]),
                      self._num_heads, self._head_dim,
                      self._page)).encode())
        return h.digest()

    def config_fingerprint(self) -> bytes:
        """Digest of everything that determines this engine's
        executable SIGNATURES and numerics: a weight-content sample
        (the `_model_fingerprint` scheme — wte row 0 + one qkv row per
        block), the architecture dims, every shape-determining
        constructor knob, and the sampling config.  Two engines with
        equal fingerprints compile byte-identical step programs, which
        is the gate for `adopt_executables` handoff and for
        `durability.restore_from_dir` validating a rebuilt engine
        against its journal.  Memoized (a few small host transfers on
        first call)."""
        if self._config_fp is None:
            h = hashlib.blake2b(digest_size=16)
            p = self._params
            h.update(np.asarray(jax.device_get(p["wte"][0]),
                                np.float32).tobytes())
            for row in self._fp_wrows:
                # construction-time f32 samples (see _model_fingerprint)
                h.update(row)
            h.update(str((
                tuple(p["wte"].shape), len(p["blocks"]),
                self._num_heads, self._head_dim, self._eps,
                self._slots, self._max_seq_len, self._page,
                self.pool.num_pages, self._q_max,
                int(self._ctor["prefill_chunk_tokens"]),
                # the page STORAGE dtype already separates quantized
                # from full-precision engines (int8 <-> kv_quant is
                # one-to-one); adding the mode string would break
                # fingerprint compatibility with pre-quant journals
                # for off-mode engines whose executables ARE identical
                str(self._kv.dtype),
                tuple(sorted(self._sampling.items())),
                self._spec.k if self._spec else 0,
                self._chunked_cfg)).encode())
            if self._ragged:
                # folded CONDITIONALLY so off-path fingerprints stay
                # byte-identical with pre-ragged journals/donors (their
                # executables ARE identical); a ragged engine can never
                # adopt a split-path engine's executables or vice versa
                h.update(str(("ragged", self._q_ragged)).encode())
            if self._mesh is not None:
                # same conditional-fold reason: single-chip
                # fingerprints stay byte-identical with pre-mesh
                # journals/donors, and a sharded engine (whose
                # executables carry mesh shardings) can never adopt a
                # single-chip engine's executables or vice versa
                h.update(str(("mesh", self._serve_mesh)).encode())
            if self._weight_quant:
                # same conditional-fold reason again: off-mode
                # fingerprints stay byte-identical with pre-feature
                # journals/donors (their executables ARE identical),
                # while an int8-weight engine (whose dots read s8
                # operands) can never adopt an f32 engine's
                # executables or vice versa
                h.update(str(("serve_weights",
                              self._serve_weights_mode)).encode())
            self._config_fp = h.digest()
        return self._config_fp

    def wire_config(self) -> dict:
        """The serializable subset of the resolved constructor config —
        what the journal's config record carries so
        `durability.restore_from_dir` can rebuild this engine in a
        fresh process (the caller supplies the model; scheduler /
        drafter / fault-plan objects are process-local and excluded)."""
        kw = {k: v for k, v in self._ctor.items()
              if k not in ("model", "scheduler", "drafter",
                           "fault_plan", "journal_dir")}
        if kw.get("dtype") is not None:
            kw["dtype"] = str(jnp.dtype(kw["dtype"]))
        if kw.get("eos_token_id") is not None:
            kw["eos_token_id"] = int(kw["eos_token_id"])
        if kw.get("alerts"):
            # AlertRule dataclasses -> wire dicts (the ctor accepts
            # either form back); False stays False — a restored engine
            # keeps the resolved arming decision, not the flag's
            kw["alerts"] = [r.to_wire() for r in kw["alerts"]]
        if self._cost is not None:
            # LIVE calibration state, not the construction-time seed:
            # recover() and the durability snapshot carry the learned
            # factors across rebuilds so the successor predicts warm
            kw["cost_calibration"] = self._cost.calibration_wire()
        return kw

    def _trackers(self) -> List[_JitTracker]:
        """Every live `_JitTracker` this engine (and its speculative
        subsystem) currently holds — the watchdog's compile detector
        and the handoff's donor surface."""
        ts = [self._decode_fn, self._mixed_fn, self._ragged_fn,
              self._scale_reset_fn, *self._prefill_fns.values()]
        if self._spec is not None:
            ts.append(self._spec._verify_fn)
            d = self._spec.drafter
            for name in ("_catch_fn", "_step_fn", "_chunk_fn",
                         "_scale_reset_fn"):
                ts.append(getattr(d, name, None))
            ts.extend(getattr(d, "_prefill_fns", {}).values())
        return [t for t in ts if t is not None]

    def adopt_executables(self, donor) -> int:
        """Executable handoff: take a retired engine's live compiled
        step executables instead of recompiling them.  Safe ONLY when
        the config fingerprints match — identical fingerprints mean
        identical executable signatures, so the donor's warm jit
        caches serve this engine's shapes without a retrace; on any
        mismatch nothing is adopted and the executables compile lazily
        as usual (the cold fallback).  Returns the number adopted.
        The drafter instance is REUSED across a recovery (not
        reconstructed), so its executables carry over without passing
        through here."""
        if donor is self or \
                donor.config_fingerprint() != self.config_fingerprint():
            return 0
        n = 0
        if self._decode_fn is None and donor._decode_fn is not None:
            self._decode_fn = donor._decode_fn
            n += 1
        if self._mixed_fn is None and donor._mixed_fn is not None:
            self._mixed_fn = donor._mixed_fn
            n += 1
        if self._ragged_fn is None and \
                getattr(donor, "_ragged_fn", None) is not None:
            self._ragged_fn = donor._ragged_fn
            n += 1
        if self._scale_reset_fn is None and \
                donor._scale_reset_fn is not None:
            self._scale_reset_fn = donor._scale_reset_fn
            n += 1
        for bucket, fn in donor._prefill_fns.items():
            if bucket not in self._prefill_fns:
                self._prefill_fns[bucket] = fn
                n += 1
        if self._spec is not None and donor._spec is not None and \
                self._spec._verify_fn is None and \
                donor._spec._verify_fn is not None:
            self._spec._verify_fn = donor._spec._verify_fn
            n += 1
        if n:
            _stats_add(exec_handoffs=n)
        return n

    def _abandon_inflight(self):
        """Watchdog abandonment: neutralize this engine so a step
        still blocked in a worker thread mutates nothing visible when
        it finally returns — its requests now belong to the rebuilt
        engine.  The host loop after a late-returning executable sees
        no active slot and emits nothing; the slow_step fault site
        (and the containment ladder) re-raise instead of containing.
        Device buffers and pool state are garbage from here on.

        The durability manager detaches FIRST: the successor engine
        owns the journal directory from here, and a late-returning
        step on this engine must neither flush stale records nor
        overwrite the successor's snapshot with this engine's (now
        empty) state."""
        self._abandoned = True
        dur, self._durability = self._durability, None
        if dur is not None:
            try:
                dur.close()
            except Exception:
                pass  # best effort: the hung worker may hold the handle
        self._watchdog = None
        # black box first: the hung worker may never return, so this is
        # the last consistent look at what the engine was doing.  Best
        # effort on BOTH sides: a full disk must not block recovery,
        # and a merely-SLOW (not dead) worker still holding a
        # reference to the open record can mutate it lock-free while
        # the dump serializes — a torn dump is acceptable, a dead
        # driver is not
        if self._alerts is not None:
            # last alert evaluation before the black box dumps: the
            # overload/pressure that preceded the hang should read as
            # FIRING rules in the post-mortem, not raw gauges the
            # reader must re-derive.  (The engine is already marked
            # abandoned, so transitions update the /alertz rule states
            # the dump snapshots but repopulate no retired gauges.)
            try:
                self._alerts.evaluate()
            except Exception:
                pass
        fl = self._flight
        if fl is not None:
            fl.event("abandon", step=int(self._step_no))
            fl.end_step()
            try:
                fl.dump("abandoned")
            except Exception:
                pass
        # close the dead lane: a terminal marker span on this engine's
        # trace track, then retire EVERY engine-labeled series from the
        # scrape surface (the whole-catalog mirror of PR 10's
        # clear_health fix — a dead engine's gauges otherwise read
        # stale levels forever).  The frontend re-flips health to
        # "hung" right after, so an unrecovered abandonment still
        # alerts; a successful recovery retires that too.
        _obs.record_span("engine", "abandoned", _obs.now_ns(), 0,
                         tid=self._engine_id,
                         args={"step": int(self._step_no)})
        from .durability import retire_engine_series

        retire_engine_series(self._engine_id)
        self._inflight = None
        self._by_slot = [None] * self._slots
        self._active = np.zeros(self._slots, bool)
        self._queue.clear()
        self._free_slots = list(range(self._slots))
        heapq.heapify(self._free_slots)

    # -- request lifecycle ---------------------------------------------------
    def add_request(self, prompt_ids, max_new_tokens=32,
                    eos_token_id=..., priority=None, deadline_ms=None,
                    slo_ttft_ms=None, slo_tpot_ms=None,
                    on_token=None, trace_id=None) -> Request:
        # sentinel default: eos_token_id=None is a real per-request
        # opt-out of the engine-level eos, not "use the default"
        req = Request(prompt_ids, max_new_tokens,
                      self._eos if eos_token_id is ... else eos_token_id,
                      priority=priority, deadline_ms=deadline_ms,
                      slo_ttft_ms=slo_ttft_ms, slo_tpot_ms=slo_tpot_ms,
                      on_token=on_token)
        if trace_id is not None:
            req.trace_id = str(trace_id)
        if not req.prompt_ids:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(req.prompt_ids) + req.max_new_tokens > self._max_seq_len:
            raise ValueError(
                f"prompt ({len(req.prompt_ids)}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_seq_len "
                f"{self._max_seq_len}")
        if self._pages_for(req.total_kv_tokens()) > self.pool.num_pages:
            raise ValueError(
                "request needs more KV pages than the pool holds")
        req._engine = self
        req.t_enqueue_ns = _obs.now_ns()
        if req.deadline_ms is not None:
            req._deadline_ns = req.t_enqueue_ns + \
                int(req.deadline_ms * 1e6)
        _obs.REQUESTS_ENQUEUED.inc()
        self._queue.append(req)
        if self._durability is not None:
            self._durability.on_admit(req)
        return req

    def admit_restored(self, req: Request, on_token=None) -> Request:
        """Admit a request another engine's journal materialized
        (`durability.adopt_from_dir` — fleet failover into a LIVE
        survivor).  Unlike the in-place `restore_from_dir` path, the
        adopting engine has its own journal and its own id space: the
        request gets a FRESH id here (the donor's id may collide with
        one this engine already journaled), is validated like any
        admission, and is journaled under its restored identity — the
        ORIGINAL prompt/budget split plus the streamed watermark — so
        a second death of THIS engine replays it correctly too."""
        if req.state == "done":
            raise ValueError(
                "admit_restored takes an in-flight materialized "
                "request, not a finished one")
        if not req.prompt_ids:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(req.prompt_ids) + req.max_new_tokens > self._max_seq_len:
            raise ValueError(
                f"prompt ({len(req.prompt_ids)}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_seq_len "
                f"{self._max_seq_len}")
        if self._pages_for(req.total_kv_tokens()) > self.pool.num_pages:
            raise ValueError(
                "request needs more KV pages than the pool holds")
        req.request_id = next(Request._next_id)
        req.on_token = on_token
        req._engine = self
        req.t_enqueue_ns = _obs.now_ns()
        if req.deadline_ms is not None:
            req._deadline_ns = req.t_enqueue_ns + \
                int(req.deadline_ms * 1e6)
        _obs.REQUESTS_ENQUEUED.inc()
        self._queue.append(req)
        if self._durability is not None:
            self._durability.on_admit(req)
            if req._absorbed + req._emit_gate:
                # the adopted watermark must be durable HERE too: a
                # crash of this engine before the next emit would
                # otherwise replay the donor's already-streamed tokens
                # straight into the stream
                self._durability.on_emit(req)
        return req

    # -- fleet export hooks ---------------------------------------------------
    def route_prefix_hashes(self, prompt_ids) -> List[str]:
        """The fleet router's affinity key: hex chain hashes of every
        FULL page of ``prompt_ids`` under THIS engine's salt (same
        digests `_probe_prefix` matches against, so a router keyed on
        them lands a request exactly where its pages are cached).
        Empty when the prefix cache is off or the prompt spans no full
        page."""
        if not self._prefix_cache:
            return []
        return [h.hex() for h in self._prefix_hashes(list(prompt_ids))]

    def journal_info(self) -> Optional[dict]:
        """Where this engine journals (the fleet failover donor
        surface) — directory, record count, on-disk bytes, fsync
        policy; None when durability is off."""
        if self._durability is None:
            return None
        d = self._durability
        try:
            size = os.path.getsize(d.path)
        except OSError:
            size = 0
        return {"dir": d.journal_dir, "path": d.path,
                "records": int(d.seq), "bytes": int(size),
                "fsync": d.fsync}

    def _pages_for(self, tokens: int) -> int:
        return -(-tokens // self._page)  # ceil

    def _alloc_page(self) -> int:
        """THE engine's page-allocation chokepoint: every page the
        engine claims (admission prompt pages, between-steps growth)
        comes through here so quantized mode can mark it fresh — its
        quant-scale entry zeroes on the next `_flush_fresh_scales`
        BEFORE any quantized write folds into it.  A recycled page's
        stale scale leaking into a new owner would silently change the
        quantization (history-dependent outputs: the restore/recovery
        bit-exactness contract breaks)."""
        p = self.pool.alloc_page()
        if self._kv_quant:
            self._fresh_pages.append(p)
        return p

    def _scale_reset_tracker(self) -> _JitTracker:
        fn = self._scale_reset_fn
        if fn is None:
            fn = self._scale_reset_fn = _JitTracker(
                _reset_kv_scales, "kv_quant_compiles",
                donate_argnums=(0, 1),
                site="DecodeEngine scale reset (_reset_kv_scales)")
        return fn

    def _flush_fresh_scales(self):
        """Zero the quant-scale entries of pages allocated since the
        last device call (one fixed-shape donated scatter; the fresh
        buffer pads with an out-of-bounds id so the executable never
        retraces).  Runs between steps, right before the quantized
        step executable — a no-op dict check on every step that
        allocated nothing, and never on the off path."""
        if not self._kv_quant or not self._fresh_pages:
            return
        # churn inside one window (alloc -> unwind -> realloc) can
        # repeat an id; the reset is idempotent but dedupe keeps the
        # fixed-size buffer sufficient by construction
        ids = list(dict.fromkeys(self._fresh_pages))
        self._fresh_pages = []
        buf = np.full(self.pool.num_pages, self.pool.num_pages,
                      np.int32)
        buf[:len(ids)] = ids
        fn = self._scale_reset_tracker()
        with self._phase("cache"):
            self._kv = self._kv.with_scales(
                *fn(*self._kv.scales, self._dev(buf)))
            d = self._spec.drafter if self._spec is not None else None
            if getattr(d, "_kv", None) is not None:
                # the draft model's pool quantizes with the engine's
                d._kv = d._kv.with_scales(
                    *d._scale_reset_tracker()(
                        *d._kv.scales, jnp.asarray(buf)))
        _stats_add(kv_quant_pages=len(ids))
        _obs.KV_QUANT_PAGES.inc(len(ids))

    def _note_refolds(self, out):
        """A step's fetched output as its tokens: a quantized pool's
        step packed its scale-refold count behind them
        (`_pack_refolds`) — account it and take it off."""
        if not self._kv_quant:
            return out
        n = int(out[-1].flat[0])
        if n:
            _stats_add(kv_quant_refolds=n)
            _obs.KV_QUANT_REFOLDS.inc(n)
        return out[:-1]

    def _kv_byte_occupancy(self) -> dict:
        """Device bytes the KV pool currently holds in non-free pages
        (payload + quant scales), plus the per-token storage cost —
        the density numbers the flight recorder stamps per step and
        tools/bench_kv_quant.py gates on."""
        per_page_payload = 2 * self._num_layers * self._num_heads * \
            self._page * self._head_dim * self._kv.dtype.itemsize
        per_page_scales = 0
        if self._kv_quant:
            per_page_scales = 2 * self._num_layers * self._num_heads * 4
        used = self.pool.used_count
        return {
            "dtype": str(self._kv.dtype),
            "payload_bytes": used * per_page_payload,
            "scale_bytes": used * per_page_scales,
            "bytes_per_token": (per_page_payload + per_page_scales)
            / self._page,
        }

    def _prefill_bucket(self, p_len: int) -> int:
        """Pow-2 prompt-length bucket (floor 16, capped at the horizon)
        so prompt lengths share prefill executables.  The draft-model
        drafter buckets with THIS method so target and draft prefill
        always compile the same executable set."""
        bucket = 16
        while bucket < p_len:
            bucket *= 2
        return min(bucket, self._max_seq_len)

    def _prefix_hashes(self, prompt_ids) -> List[bytes]:
        """Chain hashes for every FULL page of the prompt (page i's key
        folds page i-1's digest, so a hit at page i implies the whole
        page-aligned prefix 0..i matched)."""
        page = self._page
        hashes = []
        h = self._model_salt
        for i in range(len(prompt_ids) // page):
            h = _chain_hash(h, prompt_ids[i * page:(i + 1) * page])
            hashes.append(h)
        return hashes

    def _probe_prefix(self, req: Request):
        """Longest page-aligned cached prefix for ``req`` — read-only:
        nothing is referenced until `_bind_slot` commits, so a failed
        admission (capacity) leaves the cache untouched.  At least one
        prompt token is always recomputed (the first sampled token
        needs the last position's logits), so a whole-prompt match is
        capped one page short.  The chain hashes are memoized on the
        request (``req._page_hashes``) for registration and for the
        re-probes a capacity-blocked admission retries every step."""
        if not self._prefix_cache:
            return []
        hashes = req._page_hashes
        if hashes is None:
            hashes = req._page_hashes = \
                self._prefix_hashes(req.prompt_ids)
        limit = (len(req.prompt_ids) - 1) // self._page
        hit_pages = []
        for h in hashes[:limit]:
            p = self.pool.lookup(h)
            if p is None:
                break
            hit_pages.append(p)
        return hit_pages

    def _admit(self):
        """Between-steps admission: delegated to the pluggable
        scheduler (`inference.frontend.Scheduler`).  The default FIFO
        scheduler reproduces the historical strict-arrival-order loop
        exactly; the SLO scheduler re-orders, expires, and preempts.
        Either way the actual bind goes through `_admit_one`, so the
        capacity arithmetic lives in exactly one place."""
        self._scheduler.schedule()

    def _capacity_ok(self, req: Request, extra_pages: int = 0) -> bool:
        """Would the pool see ``req`` through to completion if
        ``extra_pages`` more pages were reclaimable?  ``extra_pages=0``
        is exactly `_admit_one`'s capacity test; a scheduler weighing a
        preemption passes the pages its victims would free to ask
        whether evicting them can possibly admit ``req`` — if not,
        preemption is pure waste.  Read-only (the prefix probe is
        memoized and references nothing)."""
        total_pages = self._pages_for(req.total_kv_tokens())
        hit_pages = self._probe_prefix(req)
        need = total_pages - len(hit_pages)
        avail = self.pool.free_count + \
            self.pool.cached_unreferenced_count + extra_pages - \
            sum(1 for p in hit_pages if self.pool.refcount(p) == 0)
        return avail - self.pool.reserved >= need

    def _admit_one(self, req: Request) -> bool:
        """Admit ONE specific queued request if a slot is free and the
        pool can see it through to completion; returns False (request
        stays queued, cache untouched) otherwise.

        Conservative admission: never admit a request the pool cannot
        see through (running requests' not-yet-allocated pages are
        reserved).  Cached-prefix hits need no allocation, and
        unreferenced cached pages are reclaimable via the eviction LRU
        — but the hit pages themselves must not double-count as
        evictable capacity (`_capacity_ok` carries that arithmetic)."""
        if not self._free_slots:
            return False
        if not self._capacity_ok(req):
            return False
        if self._cost_admission and \
                not self._cost.admission_ok(req):
            # cost-model admission (FLAGS_sched_cost_admission):
            # predicted step cost would blow the tightest declared
            # per-token SLO — the request stays queued and re-probes
            # next step, exactly like a capacity refusal.  Default
            # off: the decision sequence above is bit-exact historical.
            return False
        total_pages = self._pages_for(req.total_kv_tokens())
        hit_pages = self._probe_prefix(req)  # memoized: re-probe is cheap
        if self._queue and self._queue[0] is req:
            self._queue.popleft()  # FIFO fast path (O(1), not a scan)
        else:
            self._queue.remove(req)
        slot = heapq.heappop(self._free_slots)
        try:
            if self._chunked:
                self._bind_slot(req, slot, total_pages, hit_pages)
            else:
                self._prefill_into(req, slot, total_pages)
        except PoolExhausted:
            # typed containment: the pool could not actually deliver
            # what the (conservative) capacity probe promised — or the
            # "pool" fault site fired.  Admission backpressure, never a
            # crash: unwind the partial claim and keep the request
            # QUEUED at the head; it re-probes next step.
            self._unwind_failed_admit(req, slot)
            return False
        return True

    def _unwind_failed_admit(self, req: Request, slot: int):
        """Roll back a bind that raised `PoolExhausted` mid-way: give
        back every page the partial `_alloc_prompt_pages` claimed
        (cached hits unref, fresh allocs free — the reservation is
        only taken after the loop completes, so it was never touched),
        clear the slot, and put the request back at the queue head
        still in state "queued"."""
        self.pool.release_pages(req.pages)
        req.pages = []
        req.cached_page_count = 0
        req.cached_prefix_len = 0
        req.slot = None
        req.state = "queued"
        self._release_slot(slot)
        self._queue.appendleft(req)

    def _release_slot(self, slot: int):
        """Clear every per-slot array for ``slot`` and push it back on
        the free heap — the ONE slot teardown, shared by `_finish`,
        `preempt`, and the admission unwind, so a new per-slot array
        only ever needs resetting here."""
        self._by_slot[slot] = None
        self._active[slot] = False
        self._lens[slot] = 0
        self._last[slot] = 0
        self._bt[slot] = 0
        self._prefill_pos[slot] = 0
        heapq.heappush(self._free_slots, slot)

    def _stamp_admit(self, req: Request):
        first = req.t_admit_ns is None
        req.t_admit_ns = _obs.now_ns()
        if not first:
            # re-admission after a preemption: the request already
            # recorded its queue wait — count the resume instead
            _stats_add(resumes=1)
            if self._flight is not None:
                self._flight.event("resume", request=req.request_id)
            return
        if req.t_enqueue_ns is not None:
            wait_s = (req.t_admit_ns - req.t_enqueue_ns) / 1e9
            _stats_add(admissions=1, queue_wait_s=wait_s)
            _obs.REQUEST_QUEUE_WAIT.observe(wait_s)
            _obs.record_span("requests", "queued", req.t_enqueue_ns,
                             req.t_admit_ns - req.t_enqueue_ns,
                             tid=req.request_id,
                             args=_req_span_args(req))

    def _alloc_prompt_pages(self, req: Request, slot: int,
                            total_pages: int, hit_pages=()):
        """Map the cached prefix (refcount+1, read-only) and allocate
        fresh pages for the rest of the prompt (chunks scatter into
        already-owned pages), reserve the decode tail, and point the
        slot's block-table row at all of them.

        May raise `PoolExhausted` (organically, or via the "pool"
        fault site) — `_admit_one` contains it: the partial claim is
        unwound and the request stays queued."""
        if self._fault is not None:
            self._resilience.fault_point("pool")
        for p in hit_pages:
            self.pool.ref_page(p)
            req.pages.append(p)
        req.cached_page_count = len(req.pages)
        req.cached_prefix_len = len(req.pages) * self._page
        p_len = len(req.prompt_ids)
        for _ in range(len(req.pages), self._pages_for(p_len)):
            req.pages.append(self._alloc_page())
        self.pool.reserved += total_pages - len(req.pages)
        row = np.zeros(self._pages_per_seq, np.int32)
        row[:len(req.pages)] = req.pages
        self._bt[slot] = row

    def _bind_slot(self, req: Request, slot: int, total_pages: int,
                   hit_pages=()):
        """Chunked admission: bind the request to a slot WITHOUT running
        any prompt pass — the next mixed steps feed its prompt chunk by
        chunk under the FLAGS_prefill_chunk_tokens budget (admit-on-
        first-chunk), so running decodes never stall.  With a cached
        prefix mapped, the prefill cursor and KV length start at the
        first NOVEL token: the cached pages' KV is already bit-identical
        to what the chunks would have recomputed.  A divergence that
        lands mid-page is copy-on-write by construction — the partially
        matching page is never mapped, its tokens are recomputed into a
        fresh private page, and the cached page is never written."""
        # alloc BEFORE the admit stamp: a PoolExhausted unwind must
        # leave the request looking never-admitted (a stamped t_admit
        # would make its real admission later count as a resume)
        self._alloc_prompt_pages(req, slot, total_pages, hit_pages)
        self._stamp_admit(req)
        req.state = "running"
        req.slot = slot
        self._by_slot[slot] = req
        start = req.cached_prefix_len
        self._lens[slot] = start
        self._last[slot] = 0
        self._prefill_pos[slot] = start
        self._active[slot] = True
        if self._prefix_cache:
            n_probe = (len(req.prompt_ids) - 1) // self._page
            _stats_add(prefix_hits=len(hit_pages),
                       prefix_misses=n_probe - len(hit_pages),
                       prefix_cached_tokens=start)
            if hit_pages:
                _obs.PREFIX_HITS.inc(len(hit_pages))
            if n_probe > len(hit_pages):
                _obs.PREFIX_MISSES.inc(n_probe - len(hit_pages))
            _obs.PREFIX_CACHED_TOKENS.observe(start)
        if self._spec is not None:
            self._spec.on_admit(slot, req)

    def _is_prefilling(self, slot: int) -> bool:
        req = self._by_slot[slot]
        return req is not None and \
            int(self._prefill_pos[slot]) < len(req.prompt_ids)

    def _prefilling_any(self) -> bool:
        return any(self._is_prefilling(s) for s in range(self._slots)
                   if self._active[s])

    def _prefill_into(self, req: Request, slot: int, total_pages: int):
        # alloc first: a PoolExhausted unwind must see no admit stamp
        # and no stall accounting for an admission that never happened
        self._alloc_prompt_pages(req, slot, total_pages)
        if self._active.any():
            # legacy one-shot prefill runs BETWEEN decode steps: every
            # already-running slot stalls for this whole prompt pass —
            # the cost chunked prefill exists to remove
            _stats_add(stalled_decode_steps=1)
        self._stamp_admit(req)
        p_len = len(req.prompt_ids)

        bucket = self._prefill_bucket(p_len)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :p_len] = req.prompt_ids

        fn = self._prefill_fns.get(bucket)
        if fn is None:
            # prefill buckets compile on first use by design (a new
            # prompt-length bucket is an expected warmup event, not a
            # steady-state retrace) — only per-bucket recompiles count
            # toward retraces_after_warmup
            fn = self._prefill_fns[bucket] = _JitTracker(
                functools.partial(_gpt_prefill,
                                  num_heads=self._num_heads,
                                  head_dim=self._head_dim,
                                  eps=self._eps, **self._sampling),
                "prefill_compiles", donate_argnums=(4,),
                site=f"DecodeEngine prefill bucket {bucket} "
                     f"(_gpt_prefill)")
        t0 = time.perf_counter()
        t0_ns = _obs.now_ns()
        # prefill keys live in the upper fold_in window (decode steps
        # use (0, 2^30]), derived from a PER-ENGINE counter so `seed`
        # actually pins the sampling stream regardless of process-global
        # state; _fold_counter wraps inside the window so the streams
        # can never alias, no matter the uptime
        self._prefill_no += 1
        key = jax.random.fold_in(
            self._key, _fold_counter(self._prefill_no,
                                     RNG_PREFILL_DOMAIN))
        fr = self._flight
        self._flush_fresh_scales()
        with self._phase("prefill"):
            self._kv, tok = fn(
                self._params, self._dev(ids), jnp.int32(p_len),
                self._dev(self._bt[slot]), self._kv, self._dev(key))
        tok = int(self._note_refolds(self._host_fetch(tok)).flat[0])
        # the pass's wall time is real either way; the token count,
        # prefill count and TTFT stamp wait for the NaN-sentinel check
        # below — a quarantined prefill emitted nothing (mirrors the
        # chunked path, where _land_row checks before stamping)
        dt = time.perf_counter() - t0
        self._batch_s += dt
        _stats_add(prefill_time_s=dt)
        _obs.record_span("engine", "prefill", t0_ns,
                         _obs.now_ns() - t0_ns,
                         tid=self._engine_id,
                         args=_req_span_args(req, bucket=bucket,
                                             slot=slot))

        req.state = "running"
        req.slot = slot
        self._by_slot[slot] = req
        self._lens[slot] = p_len
        self._prefill_pos[slot] = p_len  # legacy: prompt consumed whole
        self._last[slot] = max(tok, 0)
        self._active[slot] = True
        if tok < 0:
            # non-finite logits in the prompt pass: quarantine this
            # request only — nothing was emitted, the batch lives on
            self._quarantine_slot(slot, "nan_logits")
            return
        _stats_add(prefills=1, tokens=1)
        self._stamp_first_token(req, prompt_len=p_len, bucket=bucket)
        self._emit(req, [tok])
        if self._spec is not None:
            self._spec.on_admit(slot, req)
        reason = self._done(req, tok)
        if reason:
            self._finish(slot, reason)

    def _done(self, req: Request, tok: int) -> Optional[str]:
        """Finish reason if the request is done after emitting ``tok``,
        else None."""
        if req.eos_token_id is not None and tok == req.eos_token_id:
            return "eos"
        if len(req.output_ids) >= req.max_new_tokens:
            return "length"
        return None

    def _emit(self, req: Request, toks):
        """Land generated tokens on the request and fire its streaming
        callback — the ONE place output_ids grows, so every emission
        path (prefill first token, mixed step, classic decode,
        speculative accept) streams identically.  The callback runs
        inside the serve loop: it must be cheap, and a callback that
        RAISES is contained here (the "host_callback" fault site) —
        the exception is recorded on ``req.fault_info``, the callback
        is dropped for the rest of the request, and the serve loop
        never unwinds mid-step.  Generation continues; only the
        streaming side goes quiet (``output_ids`` stays complete).

        Durable serving rides this chokepoint too: the journal's
        emitted-token watermark is appended (write-ahead — durable
        before the stream sees the token under ``journal_fsync=
        always``), and ``req._emit_gate`` suppresses the callback for
        replay tokens an earlier life already streamed."""
        req.output_ids.extend(toks)
        if self._flight is not None and toks:
            self._flight.note_emit(req.request_id, len(toks))
        gate = req._emit_gate
        if gate:
            skip = min(gate, len(toks))
            req._emit_gate = gate - skip
            toks = toks[skip:]
        if self._durability is not None:
            self._durability.on_emit(req)
        cb = req.on_token
        if cb is None:
            return
        for t in toks:
            try:
                if self._fault is not None:
                    self._resilience.fault_point("host_callback")
                cb(int(t))
            except Exception as e:  # containment, not policy: see above
                req.on_token = None
                if req.fault_info is None:
                    req.fault_info = FaultInfo(
                        site="host_callback", step=self._step_no,
                        recovered=True, message=str(e))
                break

    def _slo_violation(self, req: Request, kind: str):
        """Record one SLO miss ("ttft" | "tpot" | "deadline") — pure
        accounting, the request itself is never aborted for missing a
        latency target."""
        req.slo_violations.append(kind)
        _stats_add(slo_violations=1)
        _obs.SCHED_SLO_VIOLATIONS.inc(kind=kind)

    def _stamp_first_token(self, req: Request, **span_args):
        """Stamp TTFT exactly ONCE per request — shared by the legacy
        one-shot prefill and the chunked first-token path.  A RESUMED
        request (preempted earlier) keeps its original stamp: its
        replay token is mid-generation, not a first token.  Also runs
        the declared-TTFT SLO check and records the per-request
        prefill span."""
        if req.t_first_token_ns is not None:
            return
        req.t_first_token_ns = _obs.now_ns()
        if req.t_enqueue_ns is not None:
            ttft_s = (req.t_first_token_ns - req.t_enqueue_ns) / 1e9
            _obs.REQUEST_TTFT.observe(ttft_s)
            if req.slo_ttft_ms is not None and \
                    ttft_s * 1e3 > req.slo_ttft_ms:
                self._slo_violation(req, "ttft")
        if req.t_admit_ns is not None:
            _stats_add(first_tokens=1, first_token_wait_s=(
                req.t_first_token_ns - req.t_admit_ns) / 1e9)
            _obs.record_span("requests", "prefill", req.t_admit_ns,
                             req.t_first_token_ns - req.t_admit_ns,
                             tid=req.request_id,
                             args=_req_span_args(req, **span_args))

    def _register_prompt_pages(self, req: Request):
        """Prefill complete: content-address every freshly computed
        FULL prompt page (beyond the mapped cached prefix) so later
        requests can map it.  The payload is final — all subsequent
        writes for this slot land at positions past the prompt — so
        registering freezes it safely.  First writer wins a hash: a
        concurrent identical prefill keeps its duplicate page private
        (freed normally at finish)."""
        if not self._prefix_cache:
            return
        fr = self._flight
        with self._phase("cache"):
            for i in range(req.cached_page_count, len(req._page_hashes)):
                self.pool.register_page(req.pages[i],
                                        req._page_hashes[i])
        req._reg_pages = len(req._page_hashes)

    def _register_generated_pages(self, req: Request, kv_len: int):
        """Decode just advanced ``req`` to ``kv_len`` KV rows: content-
        address any GENERATED page that became full (ROADMAP quantized-
        serving rung (d)), so beam/agent fanout sharing a decode prefix
        maps it instead of
        recomputing.  Safe to freeze: KV rows ``< lens`` are final (a
        speculative rejection only ever shrinks lens back to the
        accepted point BEFORE new rows are written, and every later
        write lands at positions ``>= lens`` — past every full page).
        The chain hashes extend the prompt's memoized chain over
        ``prompt_ids + output_ids``; the emit-loop invariant
        ``len(prompt + outputs) == lens + 1`` guarantees the token
        content of every full page is on hand.  O(1) early-out keeps
        the per-token cost of the common (mid-page) case negligible.
        Gated by ``cache_generated_pages`` (default off): prompt-only
        registration is the bit-exact-occupancy parity oracle."""
        if not self._cache_generated or not self._prefix_cache or \
                req.t_first_token_ns is None:
            return
        full = int(kv_len) // self._page
        if full <= req._reg_pages:
            return
        toks = req.prompt_ids + req.output_ids
        hashes = req._page_hashes
        if hashes is None:
            hashes = req._page_hashes = self._prefix_hashes(
                req.prompt_ids)
        while len(hashes) < full:
            i = len(hashes)
            prev = hashes[-1] if hashes else self._model_salt
            hashes.append(_chain_hash(
                prev, toks[i * self._page:(i + 1) * self._page]))
        with self._phase("cache"):
            for i in range(max(req._reg_pages, req.cached_page_count),
                           full):
                self.pool.register_page(req.pages[i], hashes[i])
        req._reg_pages = full

    def _finish(self, slot: Optional[int], reason: str, req=None):
        """``req`` (default: the request of ``slot``) leaves the engine;
        ``slot=None`` for one whose slot was already released
        (`_release_finishing_slots`)."""
        if req is None:
            req = self._by_slot[slot]
        self._release_pages(req)
        self.pool.reserved -= max(
            self._pages_for(req.total_kv_tokens()) - len(req.pages), 0)
        req.state = "done"
        req.finish_reason = reason
        req.slot = None
        req.pages = []
        if slot is not None:
            self._release_slot(slot)
        _stats_add(**{{"eos": "finished_eos", "length": "finished_length",
                       "evicted": "evicted", "cancelled": "cancelled",
                       "fault": "finished_fault"}[reason]: 1})
        req.t_finish_ns = _obs.now_ns()
        _obs.REQUESTS_FINISHED.inc(reason=reason)
        if self._durability is not None:
            self._durability.on_finish(req)
        # generated-token count is preemption-stable: tokens folded
        # into the replay prompt still count toward TPOT
        n_out = len(req.output_ids) + req._absorbed
        if req.t_enqueue_ns is not None:
            _obs.REQUEST_E2E.observe(
                (req.t_finish_ns - req.t_enqueue_ns) / 1e9)
        if req.t_first_token_ns is not None:
            if n_out > 1:
                tpot_s = (req.t_finish_ns - req.t_first_token_ns) / 1e9 \
                    / (n_out - 1)
                _obs.REQUEST_TPOT.observe(tpot_s)
                if reason in ("eos", "length") and \
                        req.slo_tpot_ms is not None and \
                        tpot_s * 1e3 > req.slo_tpot_ms:
                    self._slo_violation(req, "tpot")
            _obs.record_span(
                "requests", "decode", req.t_first_token_ns,
                req.t_finish_ns - req.t_first_token_ns,
                tid=req.request_id,
                args=_req_span_args(req, tokens=n_out,
                                    finish_reason=reason))
        if reason in ("eos", "length") and req._deadline_ns is not None \
                and req.t_finish_ns > req._deadline_ns:
            # it ran to completion, but past its deadline: a violation,
            # distinct from queued-expiry (which never takes a slot)
            self._slo_violation(req, "deadline")
        if self._spec is not None and slot is not None:
            self._spec.on_finish(slot, req)
        if self._flight is not None:
            # after the SLO checks above: slo_met is final here
            self._flight.note_finish(req)

    def _release_pages(self, req: Request):
        """Give ``req``'s pages back to the pool: at once, or, while the
        step in flight writes into them, when its tokens are read (no
        page is handed out under a write that may still be running)."""
        rec = self._inflight
        if rec is not None and rec.writes_for(req):
            rec.release.append(req.pages)
        else:
            self.pool.release_pages(req.pages)

    def _settle_inflight(self):
        """A request left between steps (evict, cancel, preempt) while
        the step in flight writes its pages: read that step's tokens now
        (they still land at the next step) so the pages go back before
        anything else is admitted."""
        rec = self._inflight
        if rec is not None and rec.release:
            self._fetch(rec)

    def evict(self, req: Request):
        """Cancel a request: a queued request leaves the queue, a
        running one gives its slot and pages back between steps.  The
        tokens generated so far stay on ``req.output_ids`` and
        ``req.finish_reason`` reads "evicted" — callers can finally tell
        a cancelled generation from one that hit eos."""
        if req.state == "queued":
            self._retire_queued(req, "evicted")
            return
        if req.state == "running" and req.slot is not None and \
                0 <= req.slot < self._slots and \
                self._by_slot[req.slot] is req:
            self._finish(req.slot, "evicted")
            self._settle_inflight()
            return
        if req.state == "done":
            return  # already finished; nothing to release
        raise ValueError("request is not owned by this engine")

    def preempt(self, req: Request):
        """Preempt a RUNNING request: release its slot and pages
        between steps and re-enqueue it for resume.  The generated
        tokens fold into ``prompt_ids`` (``max_new_tokens`` shrinks one
        for one, so the KV budget is invariant) and the next admission
        replays them as a prompt — with the prefix cache on, every FULL
        page of (prompt + generated) KV is registered here first, so
        the replay maps those pages at refcount+1 and recomputes at
        most one partial page plus the last token.  Streaming is
        seamless: the already-emitted tokens became prompt, so
        ``on_token`` only ever fires for novel tokens, and
        ``generated_ids`` reads the full generation throughout.

        Host-side only — no device transfer, no shape change; the
        preempted KV pages either enter the prefix cache (retained
        payloads) or return to the free list."""
        if req.state != "running" or req.slot is None or \
                self._by_slot[req.slot] is not req:
            raise ValueError(
                f"preempt() is for running requests; this one is "
                f"{req.state!r}")
        self._requeue(req, int(self._lens[req.slot]), req.slot)

    def _requeue(self, req: Request, kv_len: int,
                 slot: Optional[int] = None):
        """`preempt`'s fold of a running request with ``kv_len`` KV rows
        back into the queue; ``slot=None`` for one whose slot was
        already released (`_drop`)."""
        total_pages = self._pages_for(req.total_kv_tokens())
        n_gen = len(req.output_ids)
        replay_hashes = None
        if self._prefix_cache and req.t_first_token_ns is not None:
            # content-address every fully written page of the replay
            # prompt (prompt pages registered at first token stay; this
            # adds the GENERATED region's full pages).  KV rows
            # < kv_len are final — speculative rollback only ever
            # shrinks lens — so the payloads are safe to freeze.
            replay_hashes = self._prefix_hashes(
                req.prompt_ids + req.output_ids)
            for i in range(req.cached_page_count,
                           min(kv_len // self._page, len(replay_hashes))):
                self.pool.register_page(req.pages[i], replay_hashes[i])
            req._reg_pages = max(
                req._reg_pages,
                min(kv_len // self._page, len(replay_hashes)))
        # fold the generation into the prompt for replay; the KV-budget
        # identity (total_kv_tokens) is preserved exactly
        req.prompt_ids = req.prompt_ids + req.output_ids
        req.max_new_tokens -= n_gen
        req._absorbed += n_gen
        req.output_ids = []
        # the hashes just computed ARE the replay prompt's hashes —
        # keep them memoized so the resume probe (and every re-probe
        # while capacity-blocked) skips the O(prompt+generated) re-hash
        req._page_hashes = replay_hashes
        req.preemptions += 1
        # release the device-side claim (pages + outstanding
        # reservation) and the slot — the same teardown as _finish,
        # minus the finished bookkeeping; a row of it in flight is
        # dropped where that step lands
        self._release_pages(req)
        self._settle_inflight()
        self.pool.reserved -= max(total_pages - len(req.pages), 0)
        req.pages = []
        req.cached_page_count = 0
        req.cached_prefix_len = 0
        req.slot = None
        req.state = "queued"
        if slot is not None:
            self._release_slot(slot)
            if self._spec is not None:
                self._spec.on_finish(slot, req)
        # back of the line position-wise, but schedulers order by
        # (priority, deadline, id) anyway and the id is the original
        # (oldest-first within its class); FIFO resumes it first
        self._queue.appendleft(req)
        _stats_add(preemptions=1)
        _obs.SCHED_PREEMPTIONS.inc()
        if self._flight is not None:
            self._flight.event("preempt", request=req.request_id,
                               slot=slot, generated=n_gen)
        if req.t_admit_ns is not None:
            _obs.record_span("requests", "preempted", req.t_admit_ns,
                             _obs.now_ns() - req.t_admit_ns,
                             tid=req.request_id,
                             args=_req_span_args(req, generated=n_gen))

    def _cancel_running(self, req: Request):
        if req.state != "running" or req.slot is None or \
                self._by_slot[req.slot] is not req:
            raise ValueError("request is not running on this engine")
        self._finish(req.slot, "cancelled")
        self._settle_inflight()

    def _retire_queued(self, req: Request, reason: str):
        """Take a still-queued request out of the admission queue
        (``reason``: "evicted" via `evict`, "cancelled" via
        `Request.cancel`, "deadline" via the SLO scheduler's expiry
        sweep, "fault" via the containment ladder's bisect-quarantine
        — the suspect is preempted back to the queue first, then
        retired here) — it never held a slot or pages at retire time,
        so this is pure queue + telemetry bookkeeping."""
        try:
            self._queue.remove(req)
        except ValueError:
            raise ValueError(
                "request is not queued on this engine") from None
        req.state = "done"
        req.finish_reason = reason
        req.t_finish_ns = _obs.now_ns()
        _stats_add(**{{"evicted": "evicted", "cancelled": "cancelled",
                       "deadline": "deadline_expired",
                       "fault": "finished_fault"}[reason]: 1})
        _obs.REQUESTS_FINISHED.inc(reason=reason)
        if self._durability is not None:
            self._durability.on_finish(req)
        if reason == "deadline":
            _obs.SCHED_DEADLINE_EXPIRED.inc()
        if req.t_enqueue_ns is not None:
            _obs.REQUEST_E2E.observe(
                (req.t_finish_ns - req.t_enqueue_ns) / 1e9)
            _obs.record_span("requests", "queued", req.t_enqueue_ns,
                             req.t_finish_ns - req.t_enqueue_ns,
                             tid=req.request_id,
                             args=_req_span_args(req,
                                                 finish_reason=reason))
        if self._flight is not None:
            self._flight.note_finish(req)

    def _cancel_queued(self, req: Request):
        if req.state != "queued":
            raise ValueError(
                f"cancel() is for still-queued requests; this one is "
                f"{req.state!r} — use DecodeEngine.evict to cancel a "
                f"running request")
        self._retire_queued(req, "cancelled")

    def _grow_block_tables(self, writes=None, lens=None):
        """Ensure pages exist for every KV row the next step will write:
        positions ``lens[slot] .. lens[slot] + writes[slot] - 1``
        (``writes`` defaults to one token per slot; the speculative
        verify step writes up to K+1; ``lens`` defaults to the host's
        KV lengths, a step assembled while another is in flight passes
        the lengths that step leaves).  Slot reuse keeps this a pop from
        the free list, not an allocation; the pages stay with the
        request until it finishes, so a speculative rejection rolls back
        ``seq_lens`` WITHOUT touching the pool.

        May raise `PoolExhausted` ("pool" fault site, or a genuinely
        dry pool): the containment ladder retries and, if pressure
        persists, quarantines a request — which frees pages.  Partial
        growth is consistent state (grown pages belong to their
        requests), so the retry re-enters here idempotently."""
        if lens is None:
            lens = self._lens
        with self._phase("cache"):
            if self._fault is not None:
                self._resilience.fault_point("pool")
            for slot in range(self._slots):
                if not self._active[slot]:
                    continue
                req = self._by_slot[slot]
                w = 1 if writes is None else int(writes[slot])
                if w == 0:
                    continue  # nothing written this step
                pidx = (int(lens[slot]) + w - 1) // self._page
                while pidx >= len(req.pages):
                    req.pages.append(self._alloc_page())
                    self.pool.reserved -= 1
                    self._bt[slot, len(req.pages) - 1] = req.pages[-1]

    def _observe_step(self, t0_ns: int, dt: float, n_active: int,
                      name: str, extra_args=None, observe_hist=True):
        """Per-step observability: a step span on this engine's trace
        lane, the step-latency histogram, and the pool/occupancy
        gauges (levels as of the step that just ran).
        ``observe_hist=False`` skips the step-latency histogram — used
        by the chunk-only mixed step inside a speculative round: the
        round observes a window that OPENS before the chunk step (or,
        when every slot is still prefilling, the chunk step's wall is
        observed directly), so each engine step lands in
        paddle_decode_step_seconds exactly once, chunk time included."""
        if self._abandoned:
            # a late-returning step on a watchdog-abandoned engine must
            # not repopulate the retired gauges or extend the dead lane
            return
        args = {"step": self._step_no, "active": n_active}
        if extra_args:
            args.update(extra_args)
        _obs.record_span("engine", name, t0_ns, int(dt * 1e9),
                         tid=self._engine_id, args=args)
        if observe_hist:
            _obs.STEP_SECONDS.observe(dt)
        # level gauges are engine-labeled: several engines in one
        # process must not clobber each other's pool/occupancy reading
        eid = self._engine_id
        _obs.KV_FREE_PAGES.set(self.pool.free_count, engine=eid)
        _obs.KV_UTIL.set(self.pool.utilization(), engine=eid)
        _obs.SLOT_OCCUPANCY.set(n_active / self._slots, engine=eid)
        _obs.KV_QUANT_BYTES_PER_TOKEN.set(
            self._kv_byte_occupancy()["bytes_per_token"], engine=eid)
        if self._prefix_cache:
            _obs.PREFIX_CACHED_PAGES.set(self.pool.cached_count,
                                         engine=eid)
            d = self.pool.evictions - self._evictions_seen
            if d:
                self._evictions_seen = self.pool.evictions
                _stats_add(prefix_evictions=d)
                _obs.PREFIX_EVICTIONS.inc(d)

    # -- the mixed prefill+decode step ---------------------------------------
    def _mixed_fn_tracker(self) -> _JitTracker:
        fn = self._mixed_fn
        if fn is None:
            fn = self._mixed_fn = _JitTracker(
                functools.partial(_gpt_mixed_step,
                                  num_heads=self._num_heads,
                                  head_dim=self._head_dim,
                                  eps=self._eps, **self._sampling),
                "mixed_compiles", donate_argnums=(1,),
                site="DecodeEngine mixed step (_gpt_mixed_step)")
        return fn

    def _ragged_fn_tracker(self) -> _JitTracker:
        """The ONE step executable of the ragged path
        (FLAGS_ragged_step): decode rows, prefill chunks, and
        speculative verify windows all dispatch through this tracker,
        so steady-state serving compiles exactly one executable
        (counter: ``ragged_compiles``) and a warm retrace of it is
        attributed to ``ragged_retraces``."""
        fn = self._ragged_fn
        if fn is None:
            fn = self._ragged_fn = _JitTracker(
                functools.partial(_gpt_ragged_step,
                                  num_heads=self._num_heads,
                                  head_dim=self._head_dim,
                                  eps=self._eps,
                                  mesh=self._mesh, **self._sampling),
                "ragged_compiles", donate_argnums=(1,),
                site="DecodeEngine ragged step (_gpt_ragged_step)")
        return fn

    # -- one step: assemble, dispatch, land ----------------------------------
    def _drain_cause(self) -> Optional[str]:
        """Why the next step must not be dispatched before the one in
        flight has landed, or None when it may: each cause is a path
        that reads fetched values before its next dispatch, or that
        times or audits one step at a time."""
        if self._spec is not None:
            return "speculative"  # acceptance sizes the next windows
        if self._ragged:
            return "ragged"  # the host picks each span's sampled row
        if not self._chunked:
            return "legacy_prefill"  # admission runs a prompt pass
        if self._watchdog is not None:
            return "watchdog"  # its snapshot is taken before each step
        if self._profiling is not None and self._profiling.probing:
            return "probe"  # the probe blocks on the step's output
        if _san.active() is not None:
            return "sanitizer"  # host syncs are counted per step
        if self._fault is not None:
            return "fault_injection"  # faults fire at a step's sites
        return None

    def _final_by_length(self, rec: _Step, s: int, req: Request) -> bool:
        """Does the row of slot ``s`` in ``rec`` sample ``req``'s last
        token by length?"""
        return rec.carries(s, req) and bool(rec.sample_mask[s]) and \
            len(req.output_ids) + 1 >= req.max_new_tokens

    def _release_finishing_slots(self):
        """Before admission, while a step is in flight: a slot whose row
        there samples its request's last token by length has nothing
        left to run, so it is free for this step's admission at once,
        as it would be after that step landed.  The request keeps its
        pages; its token lands without a slot (`_retire`)."""
        rec = self._inflight
        if rec is None:
            return
        for s in range(self._slots):
            req = self._by_slot[s]
            if req is not None and self._final_by_length(rec, s, req):
                rec.final.append(s)
                req.slot = None
                self._release_slot(s)

    def _dispatch(self, decode_rows=True) -> Optional[_Step]:
        """Assemble the next step and dispatch it, or return None when no
        slot has a row and a step is in flight (with none in flight, an
        empty batch still dispatches: the containment ladder's last
        probe of an engine it has emptied).  The rows come from the
        host's state advanced by the step in flight, if one is: a slot
        that step carries starts at the KV length and prefill cursor it
        leaves, and where it samples, the slot's incoming token is the
        one it samples, read on the device (``from_prev``) without a
        trip through the host.
        A prefilling slot runs the fixed-shape [slots, Q_max] mixed
        executable: prefilling slots carry a prompt chunk under the
        chunk-token budget, decoding slots their one row; with no slot
        prefilling it is the decode executable (the ragged one, where
        the engine has it, in both cases).  ``decode_rows=False`` (the
        speculative path) feeds ONLY prompt chunks — decoding slots
        advance through the spec round that follows in the same engine
        step."""
        prev = self._inflight
        # batch assembly, from here to the dispatch span: rows and
        # chunks, page growth, the step's key, fresh quant scales
        with _flight.engine_span(self, "assemble"):
            slots, qmax = self._slots, self._q_max
            lens = self._lens.copy()
            cursor = self._prefill_pos.copy()
            from_prev = np.zeros(slots, bool)
            live = [s for s in range(slots) if self._active[s]]
            if not live and prev is not None:
                return None
            if prev is not None:
                for s in live:
                    if prev.carries(s, self._by_slot[s]):
                        lens[s] += prev.caps[s]
                        cursor[s] += prev.chunk_of.get(s, 0)
                        from_prev[s] = prev.sample_mask[s]
            prefilling = [s for s in live if int(cursor[s]) <
                          len(self._by_slot[s].prompt_ids)]
            exe = "ragged" if self._ragged else \
                "mixed" if prefilling or not decode_rows else "decode"
            # ragged mode widens the grid to Q_r >= Q_max so the ONE
            # executable's token shape also fits verify windows (K+1);
            # chunk spans stay capped by Q_max (the chunk-budget
            # invariant)
            width = self._q_ragged if self._ragged else qmax
            tokens = np.zeros((slots, width), np.int32)
            caps = np.zeros(slots, np.int32)
            sample_idx = np.zeros(slots, np.int32)
            sample_mask = np.zeros(slots, bool)
            # fair-share chunking: the step's token budget splits
            # evenly across prefilling slots (remainder to the lower
            # slots), so a short prompt admitted next to a long one
            # finishes its prefill in one step instead of queueing
            # behind the long prompt's whole stream — bounded TTFT for
            # everyone, not just slot 0
            budget = self._chunk_budget
            chunk_of = {}
            for i, s in enumerate(prefilling if exe != "decode" else ()):
                req = self._by_slot[s]
                cur = int(cursor[s])
                share = -(-budget // (len(prefilling) - i))  # ceil
                c = min(len(req.prompt_ids) - cur, share, qmax)
                if c == 0:
                    continue  # budget spent: the slot waits one step
                budget -= c
                tokens[s, :c] = req.prompt_ids[cur:cur + c]
                caps[s] = c
                chunk_of[s] = c
                if cur + c == len(req.prompt_ids):
                    # last chunk: this step produces the first token
                    sample_idx[s] = c - 1
                    sample_mask[s] = True
            decoding = 0
            if decode_rows:
                for s in live:
                    if s in prefilling:
                        continue
                    tokens[s, 0] = 0 if from_prev[s] else self._last[s]
                    caps[s] = 1
                    sample_mask[s] = True
                    decoding += 1
            from_prev &= caps > 0
            self._grow_block_tables(writes=caps, lens=lens)

            if exe == "ragged":
                fn = self._ragged_fn_tracker()
            elif exe == "mixed":
                fn = self._mixed_fn_tracker()
            else:
                fn = self._decode_fn_tracker()
            if self._fault is not None:
                # fault site BEFORE the invocation (and the step
                # counter): an injected raise leaves no half-donated
                # state, so the containment ladder's retry re-enters
                # cleanly
                self._resilience.step_fault_point(
                    "decode_step" if exe == "decode" else "mixed_step")
            self._step_no += 1
            key = jax.random.fold_in(
                self._key,
                _fold_counter(self._step_no, RNG_DECODE_DOMAIN))
            # phase attribution: chunk-only mixed steps are prompt
            # work ("prefill"), chunk-carrying full steps are fused
            # ("mixed"), chunkless full steps are plain decode
            phase_name = "prefill" if not decode_rows else \
                ("mixed" if chunk_of else "decode")
            self._flush_fresh_scales()
        rec = _Step()
        rec.step_no, rec.exe = self._step_no, exe
        rec.decode_rows, rec.ahead = decode_rows, prev is not None
        rec.reqs = [self._by_slot[s] if caps[s] else None
                    for s in range(slots)]
        rec.leases = [r.preemptions if r is not None else 0
                      for r in rec.reqs]
        rec.caps, rec.chunk_of, rec.lens = caps, chunk_of, lens
        rec.sample_idx, rec.sample_mask = sample_idx, sample_mask
        rec.tokens, rec.final, rec.release = tokens, [], []
        rec.host, rec.fetch_s = None, 0.0
        rec.n_active = len(live)
        prev_toks = self._no_prev if prev is None else prev.toks
        bt = self._bt.copy()  # the host grows the next step's meanwhile
        t0 = time.perf_counter()
        t0_ns = _obs.now_ns()
        with self._phase(phase_name, decode_rows=decoding,
                         chunk_rows=len(chunk_of),
                         chunk_tokens=sum(chunk_of.values()),
                         lookahead=int(rec.ahead)):
            if exe == "ragged":
                # the unified executable takes no sample_idx /
                # sample_mask operands — every position draws a
                # target and the host selects each slot's span-end
                # row after the fetch (`_fetch`)
                self._kv, toks = fn(
                    self._params, self._kv, self._dev(bt),
                    self._dev(lens), self._dev(tokens),
                    self._dev(caps), self._dev(key))
            elif exe == "mixed":
                self._kv, toks = fn(
                    self._params, self._kv, jnp.asarray(bt),
                    jnp.asarray(lens), jnp.asarray(tokens),
                    jnp.asarray(caps), jnp.asarray(sample_idx),
                    jnp.asarray(sample_mask), key, prev_toks,
                    jnp.asarray(from_prev))
            else:
                self._kv, toks = fn(
                    self._params, self._kv, jnp.asarray(bt),
                    jnp.asarray(lens), jnp.asarray(tokens[:, 0]),
                    jnp.asarray(caps > 0), key, prev_toks,
                    jnp.asarray(from_prev))
            if self._profiling is not None:
                # sampled device-sync probe: block on the step's
                # output INSIDE the phase (the phase wall absorbs the
                # wait) so dispatch-start -> ready is the executable's
                # measured device seconds.  Attributed to the
                # DISPATCHED executable regardless of the flight phase
                # this step ran under — a chunkless mixed step runs
                # the program under the "decode" phase, and scoring it
                # against the decode profile would poison the
                # calibration
                self._profiling.probe(exe, toks, t0, t0_ns)
        rec.toks = toks
        rec.t0, rec.t0_ns = t0, t0_ns
        rec.dispatch_s = time.perf_counter() - t0
        self._batch_s += rec.dispatch_s
        return rec

    def _decode_fn_tracker(self) -> _JitTracker:
        fn = self._decode_fn
        if fn is None:
            fn = self._decode_fn = _JitTracker(
                functools.partial(_gpt_decode_step,
                                  num_heads=self._num_heads,
                                  head_dim=self._head_dim,
                                  eps=self._eps, **self._sampling),
                "decode_compiles", donate_argnums=(1,),
                site="DecodeEngine decode step (_gpt_decode_step)")
        return fn

    def _fetch(self, rec: _Step, *also: _Step):
        """Read ``rec``'s tokens back, once — THE blocking wait of a
        step — and give back the pages that waited for it.  A read that
        fails drops ``rec`` and ``also`` (`_drop`) before it raises."""
        if rec.host is not None:
            return
        t = time.perf_counter()
        try:
            toks = self._note_refolds(self._host_fetch(rec.toks))
        except BaseException:
            self._drop(rec, *also)
            raise
        if rec.exe == "ragged":
            # host-side span-end selection: a decode row's token sits
            # at column 0, a finishing chunk's at column c-1; padding
            # columns (and sat-out slots) are garbage.  np.where keeps
            # NAN_TOKEN (-1) for masked slots, so per-row quarantine
            # still fires
            toks = np.where(rec.sample_mask,
                            toks[np.arange(self._slots), rec.sample_idx],
                            0)
        rec.host = toks
        rec.fetch_s = time.perf_counter() - t
        self._batch_s += rec.fetch_s
        for pages in rec.release:
            self.pool.release_pages(pages)
        rec.release = []

    def _drop(self, *recs: Optional[_Step]):
        """Throw dispatched steps away after waiting for them: an
        exception with a step in flight leaves the host state at the
        last step landed, and the step numbers after it are dispatched
        again.  A request whose last token was in a dropped step, and
        whose slot was already released for it, goes back to the head
        of the queue with its generation folded into its prompt (as a
        preemption would), so it is neither lost nor short a token."""
        recs = [r for r in recs if r is not None]
        if not recs:
            return
        if self._inflight in recs:
            self._inflight = None
        for rec in recs:
            try:
                jax.block_until_ready(rec.toks)
            except Exception:
                pass  # a failed step: nothing of it is read
            for pages in rec.release:
                self.pool.release_pages(pages)
            rec.release = []
            for s in rec.final:
                req = rec.reqs[s]
                if req.state == "running" and req.slot is None:
                    self._requeue(req, kv_len=int(rec.lens[s]))
        self._step_no = min(r.step_no for r in recs) - 1
        if self._flight is not None:
            self._flight.event("lookahead_drop",
                               steps=[r.step_no for r in recs])

    def _drain(self, cause: str):
        """Land the step in flight before anything else runs; ``cause``
        (`_drain_cause`, or "empty": no slot has a row for the next
        step, or "caller": `drain`) goes on the flight record."""
        rec, self._inflight = self._inflight, None
        if rec is None:
            return
        _stats_add(lookahead_drains=1)
        if self._flight is not None:
            self._flight.event("lookahead_drain", cause=cause,
                               step=rec.step_no)
        self._retire(rec)

    def drain(self):
        """Land the step still in flight, if any.  ``step(); drain()``
        is the synchronous schedule: every step read back before the
        next is dispatched, as before lookahead (tests compare the two
        schedules through it)."""
        self._drain("caller")

    def _retire(self, rec: _Step, *also: _Step):
        """Read ``rec`` back (`_fetch`) and land its tokens: chunks
        advance their slots, first and decode tokens are emitted,
        finishes checked.  A row whose request left since ``rec`` was
        dispatched (eos, a quarantine, an evict or cancel, a
        preemption) is dropped."""
        self._fetch(rec, *also)
        toks = rec.host
        slots = self._slots
        dt = rec.dispatch_s + rec.fetch_s
        if self._fault is not None:
            toks = self._resilience.corrupt_tokens(
                toks, [s for s in range(slots) if rec.sample_mask[s]])

        # the drafter sees the SAME chunks through the same executable
        # shape (speculative path: caps carry only prompt chunks there)
        if self._spec is not None and rec.chunk_of:
            self._spec.drafter.ingest_chunks(rec.tokens, rec.caps)

        kv_util = self.pool.utilization()
        if not rec.decode_rows:
            # chunk-only (speculative path): the spec round that follows
            # accounts the engine step; this wall is prefill work
            _stats_add(mixed_steps=1, prefill_chunks=len(rec.chunk_of),
                       prefill_time_s=dt, mixed_time_s=dt)
        else:
            # a full mixed step IS this engine-step's decode step
            mixed = rec.exe != "decode"
            _stats_add(mixed_steps=int(mixed),
                       prefill_chunks=len(rec.chunk_of), steps=1,
                       decode_time_s=dt, mixed_time_s=dt if mixed else 0.0,
                       occupancy_sum=rec.n_active / slots,
                       kv_util_sum=kv_util,
                       lookahead_steps=int(rec.ahead))
        for c in rec.chunk_of.values():
            _obs.PREFILL_CHUNK_TOKENS.observe(c)
        if rec.exe == "decode":
            self._observe_step(rec.t0_ns, dt, rec.n_active, "decode_step")
        else:
            self._observe_step(
                rec.t0_ns, dt, rec.n_active, "mixed_step",
                extra_args={"prefilling": len(rec.chunk_of),
                            "chunk_tokens": sum(rec.chunk_of.values())},
                observe_hist=rec.decode_rows)

        emitted = dropped = 0
        with self._excl_phase("emit"):
            for s in range(slots):
                req = rec.reqs[s]
                if req is None:
                    continue
                if req.state != "running" or \
                        rec.leases[s] != req.preemptions:
                    dropped += 1
                    continue
                emitted += self._land_row(rec, s, req, int(toks[s]))
        _stats_add(tokens=emitted, lookahead_dropped_rows=dropped)

    def _land_row(self, rec: _Step, s: int, req: Request, tok: int) -> int:
        """Land row ``s`` of ``rec`` on ``req``; returns the tokens
        emitted (0 or 1).  A slot's LAST prompt chunk brings its first
        token: TTFT is stamped then (not at admission, not at the first
        chunk), and the prompt's full pages enter the prefix cache
        before any finish-path release can park them — unless the token
        is the NaN sentinel: the request is quarantined and its pages
        are NOT registered (K/V computed under non-finite activations
        must never enter the prefix cache).  A RESUMED request keeps its
        original TTFT.  A decode token with non-finite logits
        quarantines this row only, never the batch."""
        slot = None if s in rec.final else s
        kv_len = int(rec.lens[s]) + int(rec.caps[s])
        c = rec.chunk_of.get(s)
        if slot is not None:
            self._lens[s] = kv_len
            if c is not None:
                self._prefill_pos[s] += c
        if c is not None:
            req.prefill_chunks += 1
            if not rec.sample_mask[s]:
                return 0  # mid-prompt: nothing sampled yet
        if tok < 0:
            self._quarantine_slot(slot, "nan_logits", req=req)
            return 0
        if c is not None:
            self._register_prompt_pages(req)
        if slot is not None:
            self._last[s] = tok
        self._emit(req, [tok])
        if c is not None:
            _stats_add(prefills=1)
            self._stamp_first_token(req, prompt_len=len(req.prompt_ids),
                                    chunks=req.prefill_chunks)
        else:
            self._register_generated_pages(req, kv_len)
        reason = self._done(req, tok)
        if reason:
            self._finish(slot, reason, req=req)
        return 1

    def _mixed_step(self, decode_rows=True) -> bool:
        """One step dispatched and landed at once (the speculative
        round's prompt chunks: ``decode_rows=False``)."""
        rec = self._dispatch(decode_rows=decode_rows)
        if rec is not None:
            self._retire(rec)
        return True

    def _quarantine_slot(self, slot: Optional[int], site: str,
                         message: str = "", req=None):
        """Containment verdict for ONE slot: its request leaves the
        engine with ``finish_reason="fault"`` and a structured
        `FaultInfo`, its pages and slot are released through the
        normal `_finish` teardown, and every other slot keeps serving.
        Used by the NaN/inf logit guard (only the offending row is
        poisoned — evicting the batch for one sick request would be
        the availability bug this PR exists to remove).  ``slot=None``
        with ``req``: a request whose slot was already released."""
        if req is None:
            req = self._by_slot[slot]
        if req.fault_info is None:
            req.fault_info = FaultInfo(
                site=site, step=self._step_no, recovered=False,
                message=message or
                "non-finite logits: slot quarantined")
        else:
            req.fault_info.history.append(req.fault_info.site)
            req.fault_info.site = site
            req.fault_info.recovered = False
        _obs.record_span("engine", "quarantine", _obs.now_ns(), 0,
                         tid=self._engine_id,
                         args=_req_span_args(req, slot=slot, site=site))
        if self._flight is not None:
            self._flight.event("quarantine", request=req.request_id,
                               slot=slot, site=site)
        self._finish(slot, "fault", req=req)

    def _debug_check_pool(self):
        """FLAGS_kv_pool_debug / FLAGS_sanitize: full pool-consistency
        audit at an engine idle point (between steps, no device call in
        flight) — every live request's page list cross-checked against
        the pool's free/private/cached partition and refcounts.  Pages
        waiting for the step in flight to land (`_release_pages`) are
        still live."""
        live = [p for r in self._by_slot if r is not None for p in r.pages]
        rec = self._inflight
        if rec is not None:
            live += [p for pages in rec.release for p in pages]
        self.pool.assert_consistent(live_pages=live)

    def _dev(self, x):
        """Host->device for step-executable operands.  Single-chip:
        plain `jnp.asarray` — the bit-exact historical behavior.
        Under a serving mesh: the operand commits to the mesh
        REPLICATED, so every call presents the step executable the
        same input shardings (the jit cache keys on them; uncommitted
        operands would leave placement to GSPMD's per-call whim and
        risk a warm retrace)."""
        if self._mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._repl_sharding)

    def _host_fetch(self, x):
        """THE engine's blocking device->host read.  Every place the
        serve loop materializes device data (sampled tokens, verify
        targets) routes through here so the sanitizer's host-sync
        sentinel (FLAGS_sanitize) can count blocking syncs inside the
        step span — a step that silently grew a second sync shows up as
        ``host_syncs > steps`` in `analysis.sanitizer.get().report()`."""
        san = _san.active()
        if san is not None:
            san.count_host_sync()
        with _flight.engine_span(self, "fetch"):
            fr = self._flight
            if fr is None:
                return np.asarray(x)
            t0 = time.perf_counter()
            out = np.asarray(x)
            fr.add_phase("fetch", time.perf_counter() - t0)
            return out

    # -- live introspection ---------------------------------------------------
    def _snapshot_queue(self) -> List[Request]:
        """Best-effort copy of the admission queue, safe from a
        non-engine thread (a deque mutated mid-iteration raises; the
        retry makes statusz robust instead of crashy)."""
        for _ in range(8):
            try:
                return list(self._queue)
            except RuntimeError:
                continue
        return []

    def statusz(self, flight_records: int = 8) -> dict:
        """Live JSON-serializable state snapshot: queue, slots,
        degraded modes, health, pool/cache occupancy, SLO burn, and
        the last ``flight_records`` flight records.  Callable
        MID-SERVE from any thread — it only reads (per-field reads are
        atomic under the GIL, the queue copy retries around concurrent
        mutation, and the flight ring is read under its lock), so a
        statusz poller can never perturb outputs.  The fields are the
        machine-readable form of `statusz_text`; `ServingFrontend
        .debug_dump` wraps both with the frontend's own state."""
        from .durability import _health_state

        now = _obs.now_ns()

        def _req(r: Request, slot=None) -> dict:
            d = {
                "request": r.request_id,
                "state": r.state,
                "priority": r.priority,
                "prompt_len": len(r.prompt_ids),
                "out_tokens": len(r.output_ids) + r._absorbed,
                "max_new": r.max_new_tokens,
                # total generation cap, stable across preemption folds
                # (the fold moves budget into _absorbed one for one)
                "out_cap": r._absorbed + r.max_new_tokens,
                "preemptions": r.preemptions,
            }
            if r.t_enqueue_ns is not None:
                d["age_s"] = round((now - r.t_enqueue_ns) / 1e9, 6)
            if slot is not None:
                d["slot"] = slot
                d["phase"] = "prefill" \
                    if int(self._prefill_pos[slot]) < len(r.prompt_ids) \
                    else "decode"
                d["kv_len"] = int(self._lens[slot])
            burn = r.slo_burn(now)
            if burn:
                d["slo_burn"] = {k: round(v, 4)
                                 for k, v in burn.items()}
            if r.finish_reason is not None:
                d["finish_reason"] = r.finish_reason
            return d

        by_slot = list(self._by_slot)
        res = self._resilience
        pool = self.pool
        out = {
            "engine": self._engine_id,
            "step": int(self._step_no),
            "time_ns": now,
            "health": _health_state.get(self._engine_id, "live"),
            "abandoned": bool(self._abandoned),
            "scheduler": self._scheduler.name,
            "degraded": {"spec_off": bool(res.spec_disabled),
                         "legacy_prefill": bool(res.legacy_mode)},
            "config": {
                "slots": self._slots,
                "max_seq_len": self._max_seq_len,
                "page_size": self._page,
                "chunked_prefill": bool(self._chunked),
                "prefix_cache": bool(self._prefix_cache),
                "kv_quant": self._kv_quant_mode,
                "serve_weights": self._serve_weights_mode,
                "chunk_budget": int(self._chunk_budget),
                "spec_k": self._spec.k if self._spec is not None else 0,
                "spec_adaptive_k": bool(
                    self._spec.adaptive if self._spec is not None
                    else False),
                "ragged_step": bool(self._ragged),
                "serve_mesh": self._serve_mesh,
                "mesh_devices": self._mesh_mp if self._mesh is not None
                else 1,
                "sampling": dict(self._sampling),
            },
            "queue": [_req(r) for r in self._snapshot_queue()],
            "slots": [_req(r, slot=s) for s, r in enumerate(by_slot)
                      if r is not None],
            "pool": {
                "num_pages": pool.num_pages,
                "free": pool.free_count,
                "reserved": pool.reserved,
                "cached": pool.cached_count,
                "cached_unreferenced": pool.cached_unreferenced_count,
                "utilization": round(pool.utilization(), 4),
                "evictions": pool.evictions,
            },
            "durability": {
                "journal_dir": self._journal_dir,
                "armed": self._durability is not None,
            },
            "watchdog": {
                "armed": self._watchdog is not None,
                "timeout_ms": self._step_timeout_ms,
            },
        }
        fl = self._flight
        if fl is not None:
            out["flight"] = {
                "totals": fl.window_stats(),
                "records": fl.records(flight_records),
            }
        if self._alerts is not None:
            # the alert engine: rule states, firing set, recent
            # transitions — the same dict /alertz serves
            out["alerts"] = self._alerts.snapshot()
        if self._cost is not None:
            # the cost observatory: static profiles, calibration +
            # error tables, roofline peaks, the HBM ledger, and the
            # capacity-headroom estimate a fleet router admits on
            out["cost"] = self._cost.statusz()
        if self._profiling is not None:
            # the profiling plane: probe accounting, capture status,
            # measured device time / MFU drift, hot-op tables — the
            # same dict the /profilez endpoint serves
            out["profiling"] = self._profiling.statusz()
        return out

    def statusz_text(self, flight_records: int = 4) -> str:
        """Human-readable rendering of `statusz` — the text half of
        the JSON+text introspection surface."""
        z = self.statusz(flight_records=flight_records)
        lines = [
            f"engine {z['engine']} — step {z['step']} — "
            f"health {z['health']}"
            + (" (ABANDONED)" if z["abandoned"] else ""),
            f"scheduler {z['scheduler']} | chunked="
            f"{int(z['config']['chunked_prefill'])} prefix_cache="
            f"{int(z['config']['prefix_cache'])} spec_k="
            f"{z['config']['spec_k']} | degraded: spec_off="
            f"{int(z['degraded']['spec_off'])} legacy="
            f"{int(z['degraded']['legacy_prefill'])}",
            f"pool: {z['pool']['free']}/{z['pool']['num_pages']} free, "
            f"{z['pool']['cached']} cached "
            f"({z['pool']['cached_unreferenced']} reclaimable), "
            f"util {z['pool']['utilization']}, "
            f"{z['pool']['evictions']} evictions",
            f"queue ({len(z['queue'])}):",
        ]
        for q in z["queue"]:
            lines.append(
                f"  req {q['request']} prio {q['priority']} "
                f"age {q.get('age_s', 0):.3f}s "
                f"out {q['out_tokens']}"
                + (f" burn {q['slo_burn']}" if "slo_burn" in q else ""))
        lines.append(f"slots ({len(z['slots'])}/"
                     f"{z['config']['slots']}):")
        for s in z["slots"]:
            lines.append(
                f"  slot {s['slot']} req {s['request']} {s['phase']} "
                f"kv {s['kv_len']} out {s['out_tokens']}/"
                f"{s['out_cap']}"
                + (f" burn {s['slo_burn']}" if "slo_burn" in s else ""))
        fl = z.get("flight")
        if fl:
            t = fl["totals"]
            lines.append(
                f"flight: {t['records']}/{t['window']} records, "
                f"{t['tokens_per_second']:.1f} tok/s over window, "
                f"goodput {t['goodput']}, {t['dumps']} dumps")
            for rec in fl["records"]:
                phases = " ".join(
                    f"{k}={v * 1e3:.2f}ms"
                    for k, v in sorted(rec.get("phases", {}).items()))
                evs = "".join(f" [{e['kind']}]"
                              for e in rec.get("events", []))
                lines.append(
                    f"  step {rec.get('step')} {rec.get('kind')} "
                    f"{rec.get('dur_s', 0) * 1e3:.2f}ms "
                    f"emitted {sum(rec.get('emitted', {}).values())} "
                    f"{phases}{evs}")
        cost = z.get("cost")
        if cost:
            hr = cost["headroom"]
            led = cost["ledger"]
            lines.append(
                f"cost: predicted "
                f"{hr['predicted_step_s'] * 1e3:.2f}ms/step, "
                f"headroom {hr['admissible_slots']} slots, ledger "
                f"{led['attributed_bytes']}B attributed + "
                f"{led['unattributed_bytes']}B unattributed")
        return "\n".join(lines)

    # -- the serve loop ------------------------------------------------------
    def step(self) -> bool:
        """Admit what fits, run one batched step — a fused mixed
        prefill+decode step while any slot is mid-prefill (chunked
        mode), a classic decode step otherwise, or one speculative
        propose->verify->accept round when spec decoding is on.
        Returns False when there is nothing left to do.

        The step dispatched here stays in flight when this returns, and
        the call lands the step dispatched before it (`_step_inner`):
        tokens reach requests one call after their step's dispatch,
        while the chip already runs the next step.  A slot whose
        request's last token (by length) is in flight is free for this
        call's admission.  `drain` lands the step in flight; engines
        that must see every step's tokens first run synchronously
        (`_drain_cause`).

        The device step runs under the containment ladder
        (`inference.resilience.ResilienceManager.run_step`): a raising
        step executable is retried with capped exponential backoff,
        then the failing subsystem degrades (speculation off / legacy
        prefill), then the batch is bisected and the suspect request
        quarantined with ``finish_reason="fault"`` — one sick request
        never kills the batch.  A fault that survives the whole ladder
        re-raises as a FATAL `errors.StepFault`; only
        `resilience.recover` (engine rebuild + replay re-admission)
        continues from there."""
        san = _san.active()
        if san is not None:
            # sanitizer mode: audit the pool partition every step and
            # open the step's host-sync accounting window
            san.count_step()
            self._debug_check_pool()
        elif self._pool_debug:
            self._debug_check_pool()
        # every profiler span of this call carries the step number its
        # dispatch will take (an idle pass shares the next step's)
        self._span_step = self._step_no + 1
        self._batch_s = 0.0
        t_step = time.perf_counter()
        fr = self._flight
        if fr is not None:
            fr.begin_step()
        if self._profiling is not None:
            # profiling plane: arm any pending capture session (the
            # between-steps engine-thread arming site) and decide
            # whether this step's dispatches probe device time
            self._profiling.note_step_begin()
        try:
            # "admit" phase is EXCLUSIVE of nested leaf phases: a
            # legacy one-shot prefill runs INSIDE admission, and its
            # device/fetch time must not double-count
            with self._excl_phase("admit"):
                self._release_finishing_slots()
                self._admit()
            # admission-pressure gauges, sampled every step AFTER
            # admission (what is left queued is the backlog the
            # pool/slots could not absorb).  Not on an ABANDONED
            # engine: a late-returning worker calling step() must not
            # repopulate gauges its retirement just removed.
            if not self._abandoned:
                eid = self._engine_id
                _obs.QUEUE_DEPTH.set(len(self._queue), engine=eid)
                _obs.QUEUE_OLDEST_AGE.set(
                    (_obs.now_ns() - min(r.t_enqueue_ns
                                         for r in self._queue))
                    / 1e9 if self._queue else 0.0, engine=eid)
            if fr is not None:
                fr.note_batch()
            if self._cost is not None and fr is not None and \
                    self._active.any():
                # pre-dispatch cost prediction: stamped onto the open
                # flight record BEFORE the device step runs, so the
                # record's predicted/actual pair is an honest forecast
                self._cost.note_step_begin(fr)
            if not self._active.any() and self._inflight is None:
                if self._durability is not None:
                    self._durability.on_step_boundary()
                if fr is not None:
                    fr.end_step(idle=True)
                if self._alerts is not None:
                    # idle steps keep the cadence: a pool wedged so
                    # badly nothing admits must still reach an
                    # evaluation round
                    self._alerts.maybe_step()
                return bool(self._queue)
            wd = self._watchdog
            if wd is not None:
                wd.arm()
                t0_wd = time.perf_counter()
            try:
                out = self._resilience.run_step()
                if self._durability is not None:
                    with _flight.engine_span(self, "step_tail"):
                        self._durability.on_step_boundary()
            finally:
                # the armed window closes on EVERY exit — /readyz's
                # overdue probe must never read a completed (or
                # journal-fault-aborted) step as a live stall
                if wd is not None:
                    dt_wd = time.perf_counter() - t0_wd
                    wd.disarm()
            if wd is not None:
                if wd.classify(dt_wd):
                    # post-hoc hang verdict: the step DID complete (its
                    # tokens are emitted and journaled — recovery folds
                    # them, nothing re-emits), but an engine this slow
                    # is suspect: flip health to hung and hand the
                    # fatal HungStep to the recovery supervision
                    wd.on_hung(dt_wd)
        except StepFault as e:
            # a fault that survived the whole containment ladder is
            # escaping: leave the black box BEFORE the supervisor
            # tears this engine down.  A watchdog-ABANDONED engine
            # skips this — its recorder already dumped at abandonment
            # and its requests belong to the successor.
            if self._alerts is not None and not self._abandoned:
                # forced evaluation on the way out: health already
                # reads hung/the burn gauges already read the overload
                # that killed this step, so the fire transitions land
                # in the ring BEFORE note_fault seals and dumps it —
                # the post-mortem window then SHOWS the alerts firing
                # at death.  Best-effort: an alert bug must never
                # replace the StepFault the supervision is waiting for.
                try:
                    self._alerts.evaluate()
                except Exception:
                    pass
            if fr is not None and not self._abandoned:
                fr.note_fault(e)
            raise
        # "step_tail" is a profiler span only — everything `step` does
        # after the batch that no flight phase times (flight.PHASES is
        # the label set of paddle_step_phase_seconds and stays as is)
        with _flight.engine_span(self, "step_tail"):
            if self._profiling is not None:
                # stamp the step's probe onto the open record (and
                # retire one captured step) BEFORE the record seals
                self._profiling.note_step_end(fr)
            if fr is not None:
                rec = fr.end_step()
                if self._cost is not None and rec is not None:
                    # score the sealed record's prediction against its
                    # measured wall: EWMA calibration + error gauge +
                    # roofline / periodic ledger gauges (the
                    # calibration update site — engine thread, reads
                    # the record)
                    self._cost.observe(rec)
                if self._profiling is not None and rec is not None:
                    # device/host split, measured MFU, and the
                    # predicted-vs-measured drift the mfu_regression
                    # rule watches
                    self._profiling.observe(rec)
            if self._alerts is not None:
                # between-steps alert cadence
                # (FLAGS_alert_interval_steps): the engine thread walks
                # the rule table AFTER the step's record sealed, so
                # every signal it reads is step-boundary consistent and
                # the hot path gained no locks
                self._alerts.maybe_step()
        # the engine's own host time this step: its wall less the
        # dispatch-to-fetched walls the step counters already hold
        # (admit, cache, batch assembly, emit, tail), so that with
        # ``decode_time_s`` it adds up to the step's wall and to no
        # more — only steps that ran a batch get here
        _stats_add(host_in_step_s=time.perf_counter() - t_step
                   - self._batch_s)
        return out

    def _step_inner(self) -> bool:
        """ONE batched device step over the already-admitted batch —
        the containment ladder's unit of retry (`step` wraps it; never
        call it from outside the ladder): the speculative round, or one
        mixed prefill+decode or decode step (`_dispatch`).

        With nothing that forces a drain (`_drain_cause`) the step is
        dispatched BEFORE the one in flight is read back, and stays in
        flight when this returns: the host's fetch and landing of step
        N, the frontend's hops and the next call's admission and
        assembly run while the chip works on step N+1.  The step's key
        is still ``fold_in(key, _fold_counter(n, RNG_DECODE_DOMAIN))``
        for step number ``n``.  An exception with a step in flight
        drops that step (`_drop`) before the ladder sees it."""
        if self._fault is not None:
            # "slow_step" site: a deterministic injected stall (the
            # latency-fault class — SLO metrics see it, nothing raises)
            self._resilience.fault_point("slow_step")
        cause = self._drain_cause()
        if cause is not None:
            self._drain(cause)
        if self._spec is not None and self._resilience.spec_active():
            return self._spec.step()
        prev = self._inflight
        try:
            cur = self._dispatch()
        except BaseException:
            self._drop(prev)
            raise
        if cur is None:
            self._drain("empty")
        elif cause is not None:
            self._retire(cur)
        else:
            self._inflight = cur
            if prev is not None:
                self._retire(prev, cur)
        return True

    def _busy(self) -> bool:
        """Work left: a queued or running request, or a step in
        flight."""
        return bool(self._queue) or bool(self._active.any()) or \
            self._inflight is not None

    def run(self, max_steps=100000):
        """Drive the loop until every queued/running request finishes.
        ``max_steps`` is a runaway backstop, not a truncation knob:
        exhausting it with work still pending raises instead of
        silently returning half-served requests (every step advances
        each active slot by at least one token, so a healthy serve
        always terminates on its own)."""
        steps = 0
        while self._busy():
            if steps >= max_steps:
                raise RuntimeError(
                    f"run(max_steps={max_steps}) exhausted with "
                    f"{len(self._queue)} queued and "
                    f"{int(self._active.sum())} running requests — "
                    f"raise the cap (or find the scheduling livelock)")
            self.step()
            steps += 1
        return steps

    def generate(self, prompts, max_new_tokens=32, return_meta=False):
        """Convenience batch API: submit all prompts, serve to
        completion, return one token list per prompt (in order).
        ``run()`` already drains the queue (and raises at its step cap
        rather than truncating), so one call is the whole serve.
        Outputs read ``generated_ids`` — stable even if the scheduler
        preempted and resumed a request mid-generation.
        ``return_meta=True`` additionally returns the per-request
        ``finish_reason`` list ("eos" | "length" | "evicted" | ...)."""
        reqs = [self.add_request(p, max_new_tokens) for p in prompts]
        self.run()
        outs = [list(r.generated_ids) for r in reqs]
        if return_meta:
            return outs, [r.finish_reason for r in reqs]
        return outs
