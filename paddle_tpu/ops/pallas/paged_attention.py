"""Ragged paged-attention decode kernel (TPU Pallas) + XLA reference.

The serving-side complement of flash_attention.py: decode-step attention
over a paged KV cache ("Ragged Paged Attention", PAPERS.md).  K/V live in
HBM as fixed-size pages indexed by a per-sequence block table; each
sequence in the batch has its own length (ragged batch), and query heads
may outnumber KV heads (grouped-query attention).

Queries are ragged too: ``q`` may carry **multiple query tokens per
sequence** (``[B, Q, Hq, D]``) with a per-sequence causal offset
(``q_offsets``) — query ``i`` of sequence ``b`` sits at absolute position
``q_offsets[b] + i`` and attends to KV positions ``<= q_offsets[b] + i``.
This is the capability the Ragged Paged Attention paper treats as table
stakes: it is what the speculative-decoding verify step (score K drafted
tokens in one pass) and chunked prefill ride on.  ``Q == 1`` with
``q_offsets == seq_lens - 1`` reduces exactly to classic single-token
decode.

Kernel shape (the TPU paged-decode idiom):

* grid ``(batch, kv_heads // hb, pages_max)`` with the page axis
  fastest: a step takes one page of ``hb`` K/V heads, a
  ``(hb, page, d)`` block of each pool.  ``hb`` is worked out from the
  shapes (`heads_per_block`: the largest divisor of the K/V heads whose
  blocks fit a VMEM budget), so a model of a dozen heads of 64 takes
  them all in one step — a grid step has a fixed cost (~0.17 us on a
  v5e), and most steps of a serving call are dead;
* the block table and sequence lengths ride in as **scalar-prefetch**
  operands (`pltpu.PrefetchScalarGridSpec`) so the K/V BlockSpec index
  maps can translate the streamed page number through the block table —
  the gather indirection happens in the DMA engine, not in compute;
* pages past a sequence's live range clamp their fetch index to the last
  live page (Pallas skips the re-fetch when the index repeats — same
  dead-block trick as flash_attention's causal clamps) and gate compute
  off with ``pl.when``;
* per-(batch, kv-head) online-softmax state (f32 acc / running max /
  running sum) stays resident in VMEM scratch across the page stream, so
  VMEM usage is constant in sequence length.  Each head of a block runs
  the arithmetic of a block of one, so ``hb`` never changes a bit.

The XLA reference (`_xla_paged_attention`) is the numerics ground truth
and the CPU path; it mirrors `nn.functional.attention._sdpa_reference`'s
cast discipline exactly (scale in input dtype, f32 softmax) so the paged
engine bit-matches the eager concat-cache decode path.

Quantized KV pages (FLAGS_kv_quant=int8): pages may be stored as int8
with per-page, per-head symmetric scales (``scale = absmax / 127``,
the `quantization.int8` convention) in a parallel ``[Hkv, num_pages]``
f32 array per pool.  Dequantization is FUSED into the K/V loads — the
Pallas kernel reads each streamed page's scale from a per-(sequence,
head) SMEM row gathered through the block table before the call, and
multiplies the page tile by it in-register after the DMA (the Tensix/TPP in-kernel-fusion framing: no separate
dequant materialization pass ever exists), and the XLA reference
dequantizes the gathered pages before the identical attention math so
the two backends stay bit-identical to each other.  The write side of a
float pool is the `paged_kv_write` kernel (the K and V pages a write
touches, read and written back where the pool lies; its rows are
`kv_pool_width` wide); that of an int8 pool is `paged_quant_write`: the
serving step executables quantize every scattered K/V chunk in-graph
(per-head absmax folded into the running page scale, existing page rows
re-quantized when the scale grows — the "refold").

`KVPool` is the one place that knows which of the two a pool is: the
serving step bodies hold a pool and ask it to `write` rows and to
`attend`; the engine builds it with `KVPool.zeros` and moves pages
through the host with `export_pages` / `import_pages`.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from . import flash_attention as fa

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# minimum query-group rows per compute tile: pad the GQA group dim up to
# the f32 sublane tile (8) so the [group, head_dim] blocks map onto the
# VPU/MXU without sub-tile layouts
_MIN_GROUP_ROWS = 8
# page floor: below 16 tokens the per-page DMA descriptor overhead beats
# the payload (the page-axis analog of pick_blocks' 128 floor)
_MIN_PAGE_SIZE = 16
_DEFAULT_PAGE_SIZE = 64


# ---------------------------------------------------------------------------
# Page-size selection — the pick_blocks/cached_blocks machinery from
# flash_attention applied to the page axis: explicit caller values win,
# then a measured winner from the shared autotune cache (validated through
# the same shrink rules so a stale/hand-edited entry degrades instead of
# crashing the pool constructor), then the power-of-two shrink default.
# ---------------------------------------------------------------------------
def pick_page_size(max_len: int, page_size: int = _DEFAULT_PAGE_SIZE):
    """Largest power-of-two page (floor _MIN_PAGE_SIZE) that tiles
    ``max_len`` — pick_blocks' shrink rule on the page axis; None when no
    page size tiles the budget."""
    while page_size > _MIN_PAGE_SIZE and max_len % page_size:
        page_size //= 2
    if page_size < _MIN_PAGE_SIZE or max_len % page_size:
        return None
    return page_size


def _paged_key(max_len, d, dtype):
    # keyed on the STORAGE dtype of the pages (int8 for a quantized
    # pool, FLAGS_kv_quant) — an int8 pool reusing an fp32-picked page
    # size would silently lose the VMEM-fit reasoning the measured
    # entry encoded (a quarter the bytes per page changes the winner),
    # so each storage dtype autotunes and validates independently
    return f"paged:{max_len}x{d}:{jnp.dtype(dtype).name}"


def cached_page_size(max_len, d, dtype):
    """Measured page size for this (max_len, head_dim, dtype) from the
    shared flash autotune cache (tools/bench_decode.py commits winners),
    or None.  Entries must survive `pick_page_size`'s floor/tiling rules
    — the same validation discipline as flash_attention.cached_blocks."""
    ent = fa._load_autotune().get(_paged_key(max_len, d, dtype))
    try:
        ps = int(ent[0]) if isinstance(ent, (list, tuple)) else int(ent)
    except (TypeError, ValueError, IndexError):
        return None
    if pick_page_size(max_len, ps) != ps:
        return None
    return ps


def default_page_size(max_len, d, dtype=jnp.float32):
    """The page size the serving pool uses when the caller doesn't pick:
    measured winner if cached, else the shrink default."""
    return cached_page_size(max_len, d, dtype) or \
        pick_page_size(max_len) or _MIN_PAGE_SIZE


# ---------------------------------------------------------------------------
# Write-capped K/V row coordinates: the int8 pool's row scatter
# (`paged_quant_write`) and the oracle of tests/test_paged_kv_write.py.
# Float pools are written a page a grid step, by the `paged_kv_write`
# kernel below.
# ---------------------------------------------------------------------------
def paged_write_indices(block_tables, seq_lens, write_caps, qn,
                        num_pages_total, page):
    """Batched scatter coordinates for writing up to ``qn`` new K/V rows
    per sequence into its pages: row ``i`` of sequence ``b`` lands at
    absolute position ``seq_lens[b] + i``.

    block_tables: [B, pages_max] int32; seq_lens: [B] int32 (KV rows
    already valid); write_caps: [B] int32 in [0, qn] — rows
    ``i >= write_caps[b]`` (padding past a prompt chunk / verify window,
    or an inactive slot with cap 0) get page index ``num_pages_total``,
    one past the pool, so an ``.at[...].set`` scatter drops them.

    Returns ``(page_idx, slot)``, both [B, qn] int32: the page id and
    the within-page offset of every row.
    """
    b = block_tables.shape[0]
    pages_max = block_tables.shape[1]
    offs = jnp.arange(qn, dtype=jnp.int32)
    pos = seq_lens[:, None] + offs[None, :]              # [B, qn]
    writable = offs[None, :] < write_caps[:, None]
    # capped rows may sit past the block table's horizon: clamp the
    # LOOKUP index (the row is dropped via the OOB page id anyway)
    bt_idx = jnp.minimum(pos // page, pages_max - 1)
    page_idx = jnp.where(
        writable, block_tables[jnp.arange(b)[:, None], bt_idx],
        num_pages_total)
    return page_idx, pos % page


# ---------------------------------------------------------------------------
# Quantized KV pages (FLAGS_kv_quant=int8): symmetric per-page,
# per-head int8 with the quantization.int8 convention (q_max 127,
# scale = absmax / 127, dequant = q * scale).
# ---------------------------------------------------------------------------
Q_MAX = 127.0  # quantization.int8.Q_MAX (kept local: no layer imports)


def paged_write_spans(block_tables, seq_lens, write_caps, qn,
                      num_pages_total, page):
    """The DISTINCT pages a write of up to ``qn`` rows per sequence
    (rows ``i < write_caps[b]`` at positions ``seq_lens[b] + i``)
    touches — the deduplicated refold set for `paged_quant_write`.
    Rows within one page share a scale, so refolding once per (seq,
    span page) instead of once per row cuts the refold gather traffic
    by up to ``page``x (the difference between the quantized mixed
    step paying ~the attention gather's bandwidth and paying 8x it).

    Returns [B * n_span] int32 page ids with ``num_pages_total`` (one
    past the pool — dropped by scatters, clamped by gathers) for
    inactive sequences and span slots past the write's last page;
    ``n_span = (qn + page - 2) // page + 1`` is the static worst case
    (an unaligned ``qn``-row run)."""
    b = block_tables.shape[0]
    pages_max = block_tables.shape[1]
    n_span = (qn + page - 2) // page + 1
    j = jnp.arange(n_span, dtype=jnp.int32)
    first = seq_lens // page                                  # [B]
    last = (seq_lens + jnp.maximum(write_caps, 1) - 1) // page
    valid = (write_caps[:, None] > 0) & \
        (first[:, None] + j[None, :] <= last[:, None])
    bt_idx = jnp.minimum(first[:, None] + j[None, :], pages_max - 1)
    span = jnp.where(
        valid, block_tables[jnp.arange(b)[:, None], bt_idx],
        num_pages_total)
    return span.reshape(-1)


def kv_pool_width(head_dim, dtype=jnp.float32):
    """The minor dimension of a page pool of ``dtype``: for a float pool
    ``head_dim`` rounded up to whole 128-lane rows (an int8 pool keeps
    ``head_dim``: `paged_quant_write` is another algorithm, ROADMAP
    Queue 3).  On the chip a 64-wide row fills half of a
    128-lane tile whatever its shape says, in the layout the attention
    kernel reads; saying so in the shape makes that layout the one the
    pool is stored in between steps.  (Left at 64 the runtime stores the
    pool with another dimension minor — the pages — and every step
    executable re-lays the whole pool out on the way in and on the way
    out; naming the layout instead of the shape does not survive the
    persistent compile cache: PERF.md section 6, PR 27.)  Lanes
    ``head_dim..`` hold zeros and are never read: `kv_layer` cuts them
    off for the kernel."""
    if jnp.dtype(dtype) == jnp.int8:
        return head_dim
    return -(-head_dim // _LANES) * _LANES


def kv_layer(pool, li, head_dim):
    """Layer ``li`` of a page pool as the attention kernel takes it:
    ``[Hkv, P, page, head_dim]``."""
    return pool[li, :, :, :, :head_dim]


def _kv_write_kernel(li_ref, spans_ref, src_ref, sl_ref, cap_ref, *refs,
                     page, n_span, num_pages, head_dim, n_chunks):
    # grid (b, j): span j of sequence b, one page of K and one of V a
    # step.  refs: K's rows (the one block, or the two chunks the page
    # straddles), V's likewise, the K and V pools' page blocks in, the
    # same out (aliased), then the padding scratch of a short run.
    del li_ref
    per = 1 if n_chunks == 1 else 2
    k_rows, v_rows = refs[:per], refs[per:2 * per]
    k_in, v_in, k_out, v_out = refs[2 * per:2 * per + 4]
    pad = refs[2 * per + 4:]
    b, j = pl.program_id(0), pl.program_id(1)
    i = b * n_span + j

    def page_of(s):
        return jnp.minimum(spans_ref[src_ref[s]], num_pages - 1)

    # the first step on a page takes it as the pool holds it; the steps
    # after it on the same page (dead spans that repeat it) keep what the
    # out block holds, since the pipeline neither refetches nor writes
    # back a block whose index repeats
    @pl.when((i == 0) | (page_of(i) != page_of(jnp.maximum(i - 1, 0))))
    def _take():
        k_out[...] = k_in[...]
        v_out[...] = v_in[...]

    @pl.when(spans_ref[i] < num_pages)
    def _write():
        sl = sl_ref[b]
        # run row r lands on page row (sl + r) % page: rolling a
        # page-aligned chunk of the run by s puts each row where it lands
        s = sl % page
        shape = k_out.shape[:2] + (head_dim,)
        t = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        row = j * page - s + t   # the run's row at page row t
        fresh = (row >= 0) & (row < cap_ref[b])

        def window(chunks):
            if pad:  # a run shorter than a page: make it one
                pad[0][:, :chunks[0].shape[1], :] = \
                    chunks[0][...].astype(jnp.float32)
                chunks = pad
            first, *second = (pltpu.roll(c[...].astype(jnp.float32), s, 1)
                              for c in chunks)
            # rows before page row s come from the chunk the page starts in,
            # the rest from the next one
            return first if not second else \
                jnp.where((t < s) | (s == 0), first, second[0])

        for rows, out in ((k_rows, k_out), (v_rows, v_out)):
            out[:, :, :head_dim] = jnp.where(
                fresh, window(rows).astype(out.dtype), out[:, :, :head_dim])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_kv_write(k_pool, v_pool, li, k_rows, v_rows, block_tables,
                    seq_lens, write_caps, *, interpret):
    _, hkv, num_pages, page, width = k_pool.shape
    b, qn, _, d = k_rows.shape
    n_span = (qn + page - 2) // page + 1
    n_chunks = -(-qn // page)
    spans = paged_write_spans(block_tables, seq_lens, write_caps, qn,
                              num_pages, page)            # [B * n_span]
    # the step whose blocks each step takes: itself where its span
    # writes, else the nearest step before it that writes, else the
    # first that does — a dead span repeats its neighbour's page, so it
    # moves no page of its own
    steps = jnp.arange(b * n_span, dtype=jnp.int32)
    live = spans < num_pages
    src = jax.lax.cummax(jnp.where(live, steps, -1), axis=0)
    src = jnp.where(src >= 0, src, jnp.argmax(live).astype(jnp.int32))
    seq_lens = seq_lens.astype(jnp.int32)

    def pool_map(bi, j, li, spans, src, sl, cap):
        page_id = jnp.minimum(spans[src[bi * n_span + j]], num_pages - 1)
        return (li[0], 0, page_id, 0, 0)

    def rows_map(k):
        def index(bi, j, li, spans, src, sl, cap):
            s = src[bi * n_span + j]
            bs, js = s // n_span, s % n_span
            # the chunk of the run this step's page starts in (k = 0) or
            # the next one (k = 1)
            c = js - jnp.where(sl[bs] % page > 0, 1, 0) + k
            return (bs, 0, jnp.clip(c, 0, n_chunks - 1), 0)
        return index

    chunk = qn if n_chunks == 1 else page
    rows_specs = [pl.BlockSpec((None, hkv, chunk, d), rows_map(k))
                  for k in range(1 if n_chunks == 1 else 2)]
    page_spec = pl.BlockSpec((None, hkv, None, page, width), pool_map)
    prefetch = (jnp.reshape(li, (1,)).astype(jnp.int32), spans, src,
                seq_lens, write_caps.astype(jnp.int32))
    n_in = len(prefetch) + 2 * len(rows_specs)
    # head-major rows at their own width: [B, Hkv, Q, D]
    k_rows, v_rows = (r.transpose(0, 2, 1, 3) for r in (k_rows, v_rows))
    return pl.pallas_call(
        functools.partial(_kv_write_kernel, page=page, n_span=n_span,
                          num_pages=num_pages, head_dim=d,
                          n_chunks=n_chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, n_span),
            in_specs=rows_specs * 2 + [page_spec, page_spec],
            out_specs=[page_spec, page_spec],
            scratch_shapes=[pltpu.VMEM((hkv, page, d), jnp.float32)]
            if qn < page else []),
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        input_output_aliases={n_in: 0, n_in + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_kv_write",
    )(*prefetch, *(k_rows,) * len(rows_specs), *(v_rows,) * len(rows_specs),
      k_pool, v_pool)


def paged_kv_write(k_pool, v_pool, li, k_rows, v_rows, block_tables,
                   seq_lens, write_caps):
    """In-place write of layer ``li``'s new K rows and V rows into a float
    page pool's K and V pages, in one Pallas kernel: row ``i <
    write_caps[b]`` of sequence ``b`` lands at position ``seq_lens[b] +
    i`` of its pages; every other row of the pools keeps its bytes.
    Returns ``(k_pool, v_pool)``.

    k_pool, v_pool: [L, Hkv, P, page, W] (donated by the caller's jit;
    W = `kv_pool_width` of D); k_rows, v_rows: [B, Q, Hkv, D];
    block_tables: [B, pages_max] int32; seq_lens, write_caps: [B] int32,
    caps in [0, Q] (0 = the slot writes nothing).

    Grid ``(B, n_span)``: a step for each page a sequence's run can
    touch (`paged_write_spans`).  The span's page id, through the layer
    ``li``, all scalar prefetch, picks a ``[Hkv, page, W]`` block of each
    pool in the index map; the pools are aliased onto the outputs, so
    the pages are read and written back where they lie, and nothing else
    of the pool moves.  The rows come at their own width: the chunk of
    the run a page straddles is rolled into place and selected in by a
    row mask.  A dead span (cap 0, sentinel ``P``, past the run's end)
    skips its body and repeats the page of the step before it (of the
    first live step, if none before it is live), so it costs no page
    transfer and can never write a stale copy over a live write: the
    pipeline writes a block back only when the next step names another.
    Pages written by different sequences are distinct, as the allocator
    hands them out.

    One kernel for every step: decode (Q = 1), mixed and ragged steps,
    verify, one-request prefill.  ``li`` is an operand, so a step body's
    L calls lower the kernel once.  Off the TPU the same kernel runs in
    interpret mode."""
    return _paged_kv_write(k_pool, v_pool, li, k_rows, v_rows, block_tables,
                           seq_lens, write_caps,
                           interpret=jax.default_backend() != "tpu")


def paged_quant_write(pages, scales, li, vals, page_idx, slot,
                      span_idx=None):
    """Quantized in-place write of new K/V rows into layer ``li``'s int8
    pages, folding the rows' per-head absmax into the running page
    scales and RE-QUANTIZING a written page's existing rows when its
    scale grows (the "refold" — pages stay self-consistent under one
    scale no matter how incrementally decode/prefill filled them).

    pages: [L, Hkv, P, page, D] int8 (donated by the caller's jit);
    scales: [L, Hkv, P] f32 running page scales (absmax / Q_MAX; 0 =
    never written since (re)allocation — the engine zeroes a page's
    scale entry when the allocator hands it out, so a recycled page's
    stale scale can never leak into a new owner's quantization);
    vals: [R, Hkv, D] new K or V rows (float); page_idx / slot: [R]
    int32 write coordinates — page index P (one past the pool) drops
    the row, exactly like the unquantized scatter sites; span_idx:
    the DEDUPLICATED page set of this write (`paged_write_spans`) the
    refold gathers/scatters over — defaults to ``page_idx`` (per-row:
    bit-identical result, up to ``page``x redundant page traffic).
    Multi-row writers (prefill/mixed/verify) pass the span form;
    single-row-per-sequence decode keeps the default, where per-row
    IS the deduplicated set.

    Returns ``(pages, scales, refolds)`` where ``refolds`` is the
    number of (page, head) scale entries that grew past a previously
    established value (each one re-quantized that page's rows).

    Determinism: the scale fold is a scatter-``max`` (order-free under
    duplicate rows), refold multiplies by ``s_old / s_new`` (exactly
    1.0 for untouched entries, so ``round`` returns the stored int8
    unchanged), and a fresh page (scale 0) deterministically zeroes
    whatever stale rows the recycled buffer held."""
    num_pages = pages.shape[2]
    if span_idx is None:
        span_idx = page_idx
    s_old = scales[li]                                   # [H, P]
    amax = jnp.max(jnp.abs(vals.astype(jnp.float32)), axis=-1)  # [R, H]
    # scatter-max the new rows' absmax/Q_MAX into a P+1 buffer whose
    # extra column absorbs dropped rows (page index P)
    ext = jnp.pad(s_old, ((0, 0), (0, 1)))               # [H, P+1]
    ext = ext.at[:, page_idx].max(amax.T / Q_MAX)
    s_new = ext[:, :num_pages]
    # refold: requantize the written pages' existing rows at the grown
    # scale.  ratio == s_old/s_new <= 1 (scales only grow within an
    # allocation), == 0 for a fresh page (wipes recycled garbage to
    # deterministic zeros), == 1.0 where nothing grew (bit no-op).
    gidx = jnp.minimum(span_idx, num_pages - 1)          # in-bounds gather
    so_g = s_old[:, gidx]                                # [H, S]
    sn_g = s_new[:, gidx]
    ratio = jnp.where(so_g > 0, so_g / jnp.where(sn_g > 0, sn_g, 1.0),
                      0.0)
    old = pages[li][:, gidx].astype(jnp.float32)         # [H, S, page, D]
    requant = jnp.round(old * ratio[..., None, None]).astype(jnp.int8)
    # advanced group (li, span_idx) leads: update shape [S, H, page, D];
    # OOB page index P drops the row, duplicates write identical bytes
    pages = pages.at[li, :, span_idx].set(
        requant.transpose(1, 0, 2, 3))
    # quantize the new rows at their page's (possibly grown) scale and
    # scatter them over the refolded content
    sn_rows = s_new[:, jnp.minimum(page_idx, num_pages - 1)]  # [H, R]
    qrows = jnp.clip(
        jnp.round(vals.astype(jnp.float32)
                  / jnp.maximum(sn_rows.T[..., None], 1e-30)),
        -Q_MAX, Q_MAX).astype(jnp.int8)                  # [R, H, D]
    pages = pages.at[li, :, page_idx, slot, :].set(qrows)
    refolds = jnp.sum((s_new > s_old) & (s_old > 0)).astype(jnp.int32)
    scales = scales.at[li].set(s_new)
    return pages, scales, refolds


# ---------------------------------------------------------------------------
# XLA reference — CPU path and parity ground truth
# ---------------------------------------------------------------------------
def _xla_paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                         scale=None, q_offsets=None, k_scales=None,
                         v_scales=None):
    """q: [B, Hq, D] (single query token) or [B, Q, Hq, D] (multi-query
    with per-sequence causal offset); k_pages/v_pages:
    [Hkv, num_pages, page, D]; block_tables: [B, pages_max] int32;
    seq_lens: [B] int32 (valid KV tokens per sequence; 0 = inactive slot
    -> zero output); q_offsets: [B] int32 absolute position of query row
    0 (default ``seq_lens - Q``: the queries are the newest tokens);
    k_scales/v_scales: [Hkv, num_pages] f32 per-page, per-head dequant
    scales when the pages are int8 (FLAGS_kv_quant) — the gathered
    pages dequantize (``q8 * scale``) before the identical attention
    math, mirroring the Pallas kernel's in-register dequant exactly.
    Returns the same rank as ``q``.

    Mirrors _sdpa_reference's numerics: logits scaled in the input dtype,
    masked + softmaxed in f32, probs cast back — a sequence's output is
    bit-identical to dense attention over its first ``seq_len`` tokens
    (bottom-right-aligned causal for the multi-query form).
    """
    from ...nn.functional.attention import multi_query_causal_mask

    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    hkv, _, page, d = k_pages.shape
    b, qn, hq, _ = q.shape
    g = hq // hkv
    if q_offsets is None:
        q_offsets = seq_lens - qn
    s = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    # gather each sequence's pages: [Hkv, B, pages_max, page, D]
    k = k_pages[:, block_tables]
    v = v_pages[:, block_tables]
    if k_scales is not None:
        # fused dequant: one multiply per gathered page element, in f32
        # (same product order as the Pallas kernel's per-tile dequant),
        # cast back to the query dtype for the shared cast discipline
        ks = k_scales[:, block_tables][..., None, None]
        vs = v_scales[:, block_tables][..., None, None]
        k = (k.astype(jnp.float32) * ks).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs).astype(q.dtype)
    k = jnp.moveaxis(k, 1, 0).reshape(b, hkv, -1, d)
    v = jnp.moveaxis(v, 1, 0).reshape(b, hkv, -1, d)
    qg = q.reshape(b, qn, hkv, g, d)
    logits = jnp.einsum("bqhgd,bhsd->bqhgs", qg, k) * s
    logits = logits.astype(jnp.float32)
    # [B, Q, S]: kv pos p visible to query i iff p < min(len, off + i + 1)
    valid = multi_query_causal_mask(q_offsets, qn, seq_lens, k.shape[2])
    logits = jnp.where(valid[:, :, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    # a fully-masked row (seq_len == 0) softmaxes to uniform; zero it so
    # inactive slots emit exact zeros instead of the page-pool mean
    probs = jnp.where(valid[:, :, None, None, :], probs,
                      jnp.zeros((), probs.dtype))
    out = jnp.einsum("bqhgs,bhsd->bqhgd", probs, v)
    out = out.reshape(b, qn, hq, d)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# Pallas decode kernel
# ---------------------------------------------------------------------------
# VMEM a grid step's blocks and state may take: under the 16 MiB every
# kernel gets unless it asks for more, with room for the compiler's own
# temporaries (the block's logits and probabilities, f32).
_PAGED_VMEM_BUDGET = 12 * 1024 * 1024


def _vmem_tile_bytes(rows, cols, dtype):
    """Bytes of a [rows, cols] VMEM tile of ``dtype`` as the chip lays it
    out: rows up to whole sublane tiles (8 of 32 bits, 16 of 16, 32 of
    8), columns up to whole 128-lane rows."""
    item = jnp.dtype(dtype).itemsize
    sub = 8 * (4 // item)
    return -(-rows // sub) * sub * -(-cols // _LANES) * _LANES * item


def _head_vmem_bytes(rows, page, d, q_dtype, kv_dtype):
    """VMEM one K/V head takes in a grid step of the paged kernel: its
    query and output tiles (``rows`` x ``d``) and its K and V page tiles
    (``page`` x ``d``), each in the pipeline's two buffers, and its f32
    online-softmax state (acc, running max, running sum)."""
    return (2 * (2 * _vmem_tile_bytes(rows, d, q_dtype)
                 + 2 * _vmem_tile_bytes(page, d, kv_dtype))
            + _vmem_tile_bytes(rows, d, jnp.float32)
            + 2 * _vmem_tile_bytes(rows, _LANES, jnp.float32))


def heads_per_block(hkv, rows, page, d, q_dtype, kv_dtype):
    """The K/V heads one grid step of the paged kernel takes: the largest
    divisor of ``hkv`` whose heads fit `_PAGED_VMEM_BUDGET` together (1
    where even one does not).  ``rows``: query rows a head, padded to
    the sublane tile."""
    head = _head_vmem_bytes(rows, page, d, q_dtype, kv_dtype)
    fit = max(1, _PAGED_VMEM_BUDGET // head)
    return max(h for h in range(1, min(hkv, fit) + 1) if hkv % h == 0)


def _attend_pages(sl_ref, qo_ref, q_ref, o_ref, acc, m_scr, l_scr, kv, *,
                  page, pages_max, scale, group, q_len):
    # grid (b, head block, p): one page of every head of the block
    # streams through VMEM per step while the (b, head block)-pinned
    # query tile and f32 softmax state stay resident.  Query rows are
    # (query_token, gqa_group) pairs: row r is query token r // group, at
    # absolute position qo + r // group, so each row carries its own
    # causal limit (a per-row ragged mask instead of the single-token
    # `pos < sl`).  ``kv()`` gives the block's K and V page tiles in f32,
    # [hb, page, d].  The block's heads are the leading (batch) dimension
    # of every operation, so each head runs the arithmetic of a block of
    # one: the same bits whatever hb is (a Python loop over the heads
    # gives them too, but is traced and lowered anew at every call site
    # of every step executable, 0.2 s a call at 12 heads, and runs
    # slower on the chip).
    b = pl.program_id(0)
    p = pl.program_id(2)
    sl = sl_ref[b]
    qo = qo_ref[b]
    rows = q_ref.shape[1]

    @pl.when(p == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(p * page < sl)
    def _compute():
        # per-row causal limit: row r sees kv pos < min(sl, qo + qi + 1)
        # (padded rows clamp to the last real query so their reads stay
        # inside the live range; their output is sliced away anyway)
        row_q = jnp.minimum(
            jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0) // group,
            q_len - 1)
        pos = p * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        masked = (pos < jnp.minimum(sl, qo + row_q + 1))[None]

        q = q_ref[...].astype(jnp.float32) * scale    # [hb, rows, d]
        k, v = kv()                                   # [hb, page, d]
        m = m_scr[...][:, :, 0]
        l = l_scr[...][:, :, 0]
        logits = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [hb, rows, page]
        logits = jnp.where(masked, logits, -1e30)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        pr = jnp.exp(logits - m_new[..., None])
        # a row fully masked on this page (early query, late page) has
        # m_new == -1e30 and exp(0) == 1 everywhere: zero it explicitly
        pr = jnp.where(masked, pr, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(pr, axis=-1)
        acc[...] = acc[...] * alpha[..., None] + jax.lax.dot_general(
            pr, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new[..., None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[..., None], l_scr.shape)

    @pl.when(p == pages_max - 1)
    def _flush():
        l = l_scr[...][:, :, 0]
        o_ref[...] = (acc[...] / jnp.maximum(l, 1e-30)[..., None]
                      ).astype(o_ref.dtype)


def _decode_kernel(bt_ref, sl_ref, qo_ref, q_ref, k_ref, v_ref, o_ref,
                   acc, m_scr, l_scr, **kw):
    def kv():
        return k_ref[...].astype(jnp.float32), v_ref[...].astype(jnp.float32)

    _attend_pages(sl_ref, qo_ref, q_ref, o_ref, acc, m_scr, l_scr, kv, **kw)


def _decode_kernel_q(bt_ref, sl_ref, qo_ref, q_ref, k_ref, v_ref, ks_ref,
                     vs_ref, o_ref, acc, m_scr, l_scr, *, page, **kw):
    # Quantized twin of `_decode_kernel`: the K/V page tiles stream in
    # as int8 and dequantize IN-REGISTER right after the DMA.  The
    # scales arrive already gathered through the block table — one
    # [pages_max] f32 row per (sequence, head), blocked into SMEM with
    # the query tile — so the per-page lookup is an SMEM read at the
    # streamed TABLE ENTRY and SMEM use is bounded by pages_max, not by
    # the pool (pool-sized [Hkv, num_pages] tables on the scalar-
    # prefetch channel overflowed the 1 MB of SMEM at 8192 pages x 12
    # heads).  Everything after the dequant multiply is the unquantized
    # kernel's (`_attend_pages`), which is what keeps the two paths'
    # numerics aligned with the XLA reference's dequant-then-attend.
    sl = sl_ref[pl.program_id(0)]
    live = jnp.maximum((sl + page - 1) // page, 1)
    ent = jnp.minimum(pl.program_id(2), live - 1)  # the page's table entry

    def kv():
        # dequant in-register, then round-trip through the QUERY dtype
        # exactly like the XLA reference's `.astype(q.dtype)` — for
        # sub-f32 models (bf16) the cast is lossy, and skipping it here
        # would make the two backends attend over different K/V values
        # (a no-op for f32, where the tests pin bit-identical operands)
        return tuple(
            jnp.stack([(ref[h].astype(jnp.float32) * s_ref[h, 0, ent]
                        ).astype(q_ref.dtype).astype(jnp.float32)
                       for h in range(ref.shape[0])])
            for ref, s_ref in ((k_ref, ks_ref), (v_ref, vs_ref)))

    _attend_pages(sl_ref, qo_ref, q_ref, o_ref, acc, m_scr, l_scr, kv,
                  page=page, **kw)


def _pallas_paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                            scale=None, q_offsets=None, k_scales=None,
                            v_scales=None):
    hkv, num_pages, page, d = k_pages.shape
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    b, qn, hq, _ = q.shape
    g = hq // hkv
    if q_offsets is None:
        q_offsets = seq_lens - qn
    # rows = (query token, gqa group) pairs, padded up to the f32
    # sublane tile so the [rows, d] blocks map onto the VPU/MXU
    rows = qn * g
    gp = -(-rows // _MIN_GROUP_ROWS) * _MIN_GROUP_ROWS
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    hb = heads_per_block(hkv, gp, page, d, q.dtype, k_pages.dtype)

    qg = q.reshape(b, qn, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qg = qg.reshape(b, hkv, rows, d)
    if gp != rows:
        # pad the query rows up to the sublane tile; padded rows compute
        # garbage that is sliced away after the call
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - rows), (0, 0)))
    pages_max = block_tables.shape[1]
    block_tables = block_tables.astype(jnp.int32)
    seq_lens = seq_lens.astype(jnp.int32)
    q_offsets = q_offsets.astype(jnp.int32)
    quant = k_scales is not None

    def q_map(bi, hi, p, *pref):
        return (bi, hi, 0, 0)

    def kv_map(bi, hi, p, bt, sl, *pref):
        # dead pages clamp to the last live page: the repeated index
        # skips the DMA (flash_attention's dead-block clamp, paged form).
        # max(live, 1) keeps a zero-length slot pointing at a real page.
        live = jnp.maximum((sl[bi] + page - 1) // page, 1)
        return (hi, bt[bi, jnp.minimum(p, live - 1)], 0, 0)

    in_specs = [
        pl.BlockSpec((None, hb, gp, d), q_map),
        pl.BlockSpec((hb, None, page, d), kv_map),
        pl.BlockSpec((hb, None, page, d), kv_map),
    ]
    operands = (block_tables, seq_lens, q_offsets, qg, k_pages, v_pages)
    if quant:
        # gather the page scales through the block table BEFORE the
        # call: [B, Hkv, 1, pages_max] f32, entry j of row (b, h) being
        # the scale of sequence b's j-th page — the same indirection
        # the DMA maps use, done once in XLA.  The block's rows ride
        # into SMEM beside the query tile, on the query's index map
        # (the unit dim makes the block's last two dims the array's
        # own, which the TPU lowering asks of a block that is no
        # multiple of 8x128).
        scale_spec = pl.BlockSpec((None, hb, 1, pages_max), q_map,
                                  memory_space=pltpu.SMEM)
        in_specs += [scale_spec, scale_spec]
        operands += tuple(
            sc.astype(jnp.float32)[:, block_tables]
            .transpose(1, 0, 2)[:, :, None, :]
            for sc in (k_scales, v_scales))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv // hb, pages_max),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, hb, gp, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hb, gp, d), jnp.float32),
            pltpu.VMEM((hb, gp, _LANES), jnp.float32),
            pltpu.VMEM((hb, gp, _LANES), jnp.float32),
        ],
    )
    kernel = _decode_kernel_q if quant else _decode_kernel
    out = pl.pallas_call(
        functools.partial(kernel, page=page, pages_max=pages_max,
                          scale=s, group=g, q_len=qn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype),
        name="paged_attention",
    )(*operands)
    out = out[:, :, :rows, :].reshape(b, hkv, qn, g, d)
    out = out.transpose(0, 2, 1, 3, 4).reshape(b, qn, hq, d)
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------
def paged_attention(q, k_pages, v_pages, block_tables, seq_lens,
                    scale=None, q_offsets=None, k_scales=None,
                    v_scales=None):
    """Decode-step attention over a paged KV cache.

    q: [B, Hq, D] (one query token per sequence) or [B, Q, Hq, D]
    (ragged multi-query: Q query tokens per sequence, each at absolute
    position ``q_offsets[b] + i`` — the speculative-decode verify /
    chunked-prefill form);
    k_pages/v_pages: [Hkv, num_pages, page_size, D];
    block_tables: [B, pages_max] int32 page ids in position order;
    seq_lens: [B] int32 valid KV tokens per sequence (0 = inactive slot);
    q_offsets: [B] int32 position of each sequence's first query row
    (default ``seq_lens - Q``: the queries are the newest tokens);
    k_scales/v_scales: [Hkv, num_pages] f32 per-page, per-head dequant
    scales, REQUIRED when the pages are int8 (FLAGS_kv_quant) —
    dequantization fuses into the K/V loads of whichever backend runs.

    Hq must be a multiple of Hkv (grouped-query attention).  Uses the
    Pallas kernel on TPU (FLAGS_use_pallas_attention '1'/'auto'; '0'
    forces the reference), the XLA reference elsewhere.
    """
    hkv, num_pages, page, d = k_pages.shape
    if q.ndim not in (3, 4):
        raise ValueError(f"q must be [B, Hq, D] or [B, Q, Hq, D], "
                         f"got rank {q.ndim}")
    hq, dq = q.shape[-2], q.shape[-1]
    if hq % hkv:
        raise ValueError(
            f"query heads {hq} not a multiple of kv heads {hkv}")
    if dq != d:
        raise ValueError(f"head_dim mismatch: q {dq} vs pages {d}")
    if jnp.dtype(k_pages.dtype) == jnp.int8:
        if k_scales is None or v_scales is None:
            raise ValueError(
                "int8 KV pages need k_scales/v_scales ([Hkv, num_pages]"
                " f32 per-page dequant scales)")
        if tuple(k_scales.shape) != (hkv, num_pages):
            raise ValueError(
                f"k_scales shape {tuple(k_scales.shape)} != "
                f"(Hkv, num_pages) = {(hkv, num_pages)}")
        if tuple(v_scales.shape) != (hkv, num_pages):
            # same check for V: a stale/mis-sized scale array would
            # otherwise mis-dequantize silently via clamped gathers
            raise ValueError(
                f"v_scales shape {tuple(v_scales.shape)} != "
                f"(Hkv, num_pages) = {(hkv, num_pages)}")
    elif k_scales is not None or v_scales is not None:
        raise ValueError(
            "k_scales/v_scales passed for non-int8 KV pages "
            f"(dtype {k_pages.dtype})")
    if _paged_kernel_wanted():
        return _pallas_paged_attention(q, k_pages, v_pages, block_tables,
                                       seq_lens, scale, q_offsets,
                                       k_scales, v_scales)
    return _xla_paged_attention(q, k_pages, v_pages, block_tables,
                                seq_lens, scale, q_offsets,
                                k_scales, v_scales)


def _paged_kernel_wanted() -> bool:
    # decode over pages has no composed-XLA crossover to respect (the
    # gather alone re-materializes the whole cache), so 'auto' means ON;
    # '0' still forces the reference for debugging
    from ...core import flags as _flags

    if jax.default_backend() != "tpu":
        return False
    pol = str(_flags.flag("use_pallas_attention"))
    return pol in ("1", "True", "true", "auto")


# ---------------------------------------------------------------------------
# The pool: K/V pages and how they are stored, behind one type
# ---------------------------------------------------------------------------
def mesh_constrain(mesh):
    """Sharding-constraint applicator for the serving mesh: ``None``
    (the single-chip path) returns an identity, so a step traces
    EXACTLY the ops it always traced — zero sharding machinery on the
    off path.  With a mesh, ``cst(x, *axes)`` pins ``x`` to
    ``PartitionSpec(*axes)`` over it (``cst(x)`` = replicated), the
    GSPMD boundary annotations that turn the one ragged executable
    into a tensor-parallel program: column-split qkv/fc1 compute runs
    head-/feature-local, row-split out/fc2 matmuls end in the
    all-reduce the replicated-residual constraint forces, and the
    pool's arrays stay split on their head axis."""
    if mesh is None:
        return lambda x, *spec: x

    def cst(x, *spec):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec(*spec)))

    return cst


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=("k", "v", "k_scales", "v_scales"),
                   meta_fields=("head_dim",))
@dataclasses.dataclass(frozen=True, repr=False)
class KVPool:
    """The K and V page pools of one model, and the storage format they
    are kept in.  A pytree: a float pool flattens to its two arrays
    ``(k, v)``, an int8 pool to four ``(k, v, k_scales, v_scales)`` (a
    ``None`` field is no leaf), so a step executable takes and donates
    it as ONE argument whatever it holds.  The format is chosen at trace
    time from what the pool holds, as `serving._wmm` does for weights:
    one mode per executable, no in-graph select.

    k, v: [L, Hkv, P, page, W] — float, W = `kv_pool_width` of
    ``head_dim`` (whole 128-lane rows, lanes ``head_dim..`` zero); or
    int8, W = ``head_dim``, with k_scales, v_scales: [L, Hkv, P] f32
    running page scales (`paged_quant_write`)."""

    k: jax.Array
    v: jax.Array
    head_dim: int
    k_scales: Optional[jax.Array] = None
    v_scales: Optional[jax.Array] = None

    @classmethod
    def zeros(cls, layers, heads, pages, page, head_dim, dtype):
        """An empty pool of ``dtype`` storage (int8: quantized, with
        zero scales — "never written")."""
        shape = (layers, heads, pages, page, kv_pool_width(head_dim, dtype))
        k, v = (jnp.zeros(shape, dtype) for _ in "kv")
        if jnp.dtype(dtype) != jnp.int8:
            return cls(k, v, head_dim)
        k_scales, v_scales = (
            jnp.zeros((layers, heads, pages), jnp.float32) for _ in "kv")
        return cls(k, v, head_dim, k_scales, v_scales)

    def __repr__(self):
        # shapes only: a dataclass's own repr prints its fields, and
        # printing a device array fetches it to the host (2.4 GB here)
        return (f"KVPool({self.dtype}{list(self.k.shape)}, "
                f"head_dim={self.head_dim}, quantized={self.quantized})")

    @property
    def quantized(self) -> bool:
        return self.k_scales is not None

    @property
    def dtype(self):
        """The storage dtype of a K/V element."""
        return self.k.dtype

    @property
    def pages(self):
        """The page arrays: what the pool's bytes are, bar the scales."""
        return (self.k, self.v)

    @property
    def scales(self):
        """``(k_scales, v_scales)`` of an int8 pool; ``()`` of a float one."""
        return (self.k_scales, self.v_scales) if self.quantized else ()

    def with_scales(self, k_scales, v_scales):
        """The pool with its scales replaced (the engine zeroes those of
        freshly allocated pages between steps)."""
        return dataclasses.replace(self, k_scales=k_scales,
                                   v_scales=v_scales)

    def sharded(self, mesh):
        """The pool laid out over the serving mesh: every array split
        on its head axis (`partition.kv_pages_spec`)."""
        from ...parallel.partition import kv_pages_spec

        by_head = NamedSharding(mesh, kv_pages_spec())
        return jax.tree_util.tree_map(
            lambda a: jax.device_put(a, by_head), self)

    # -- inside a step executable --------------------------------------------
    def write(self, li, k_rows, v_rows, block_tables, seq_lens, write_caps,
              mesh=None):
        """Layer ``li``'s new K rows and V rows in: row ``i <
        write_caps[b]`` of sequence ``b`` lands at position ``seq_lens[b]
        + i`` of its pages.  k_rows, v_rows: [B, Q, Hkv, D]; block_tables:
        [B, pages_max] int32; seq_lens, write_caps: [B] int32, caps in
        [0, Q].  Returns ``(pool, refolds)``: the count of page scales
        that grew (`paged_quant_write`) — the int 0 for a float pool,
        which traces nothing.

        A float pool takes both in one `paged_kv_write` kernel; under
        ``mesh`` the call sits in a `jax.shard_map` over ``mp`` with
        pools and rows split on their head axis, as `attend`'s does.
        An int8 pool's arrays stay split on their head axis by
        constraint."""
        if not self.quantized:
            def write(k, v, k_rows, v_rows, block_tables, seq_lens,
                      write_caps):
                return paged_kv_write(k, v, li, k_rows, v_rows,
                                      block_tables, seq_lens, write_caps)

            if mesh is not None:
                by_head = PartitionSpec(None, "mp")   # pools
                rows = PartitionSpec(None, None, "mp")
                rep = PartitionSpec()
                write = jax.shard_map(
                    write, mesh=mesh,
                    in_specs=(by_head, by_head, rows, rows, rep, rep, rep),
                    out_specs=(by_head, by_head), check_vma=False)
            k, v = write(self.k, self.v, k_rows, v_rows, block_tables,
                         seq_lens, write_caps)
            return dataclasses.replace(self, k=k, v=v), 0
        cst = mesh_constrain(mesh)
        b, qn, hkv, d = k_rows.shape
        num_pages, page = self.k.shape[2:4]
        page_idx, slot = paged_write_indices(
            block_tables, seq_lens, write_caps, qn, num_pages, page)
        spans = paged_write_spans(block_tables, seq_lens, write_caps, qn,
                                  num_pages, page)
        out, refolds = {}, 0
        for name, rows in (("k", k_rows), ("v", v_rows)):
            pages, scales, r = paged_quant_write(
                getattr(self, name), getattr(self, name + "_scales"), li,
                rows.reshape(b * qn, hkv, d), page_idx.reshape(-1),
                slot.reshape(-1), spans)
            out[name] = cst(pages, None, "mp", None, None, None)
            out[name + "_scales"] = cst(scales, None, "mp", None)
            refolds += r
        return dataclasses.replace(self, **out), refolds

    def attend(self, q, li, block_tables, seq_lens, q_offsets=None,
               mesh=None):
        """`paged_attention` of ``q`` over layer ``li`` (int8: dequant
        fused into the K/V loads).  Under ``mesh`` the call sits in a
        `jax.shard_map` over ``mp`` (q: [B, Q, Hq, D], ``q_offsets``
        given): heads are chip-local there, so each chip attends over
        its own head-slice of every page with no communication — but a
        Mosaic kernel cannot be partitioned by GSPMD from sharding
        constraints alone, it has to be told per chip.  Block tables
        and lengths are replicated host state."""
        k = kv_layer(self.k, li, self.head_dim)
        v = kv_layer(self.v, li, self.head_dim)
        scales = tuple(s[li] for s in self.scales)

        def direct(q, k, v, block_tables, seq_lens, q_offsets, *scales):
            k_scales, v_scales = scales or (None, None)
            return paged_attention(q, k, v, block_tables, seq_lens,
                                   q_offsets=q_offsets, k_scales=k_scales,
                                   v_scales=v_scales)

        if mesh is None:
            return direct(q, k, v, block_tables, seq_lens, q_offsets,
                          *scales)
        heads = PartitionSpec(None, None, "mp", None)  # q, out
        by_head = PartitionSpec("mp")  # a layer's pages, its scales
        rep = PartitionSpec()
        return jax.shard_map(
            direct, mesh=mesh,
            in_specs=(heads, by_head, by_head, rep, rep, rep)
            + (by_head,) * len(scales),
            out_specs=heads, check_vma=False,
        )(q, k, v, block_tables, seq_lens, q_offsets, *scales)

    # -- on the host ---------------------------------------------------------
    def export_pages(self, ids):
        """Pages ``ids`` off the device, as numpy arrays by name: "k",
        "v" [L, Hkv, n, page, head_dim] in the storage dtype (the K/V
        lanes of a row only) and, for an int8 pool, "ks", "vs"
        [L, Hkv, n].  Axis 2 is the page axis of every array."""
        ids = np.asarray(ids, np.int32)
        out = {"k": self.k[:, :, ids, :, :self.head_dim],
               "v": self.v[:, :, ids, :, :self.head_dim]}
        if self.quantized:
            out["ks"] = self.k_scales[:, :, ids]
            out["vs"] = self.v_scales[:, :, ids]
        return {n: np.asarray(jax.device_get(a)) for n, a in out.items()}

    def fits(self, arrays) -> bool:
        """Are ``arrays`` what `export_pages` of a pool of this geometry
        and storage format gives (for any number of pages)?"""
        layers, heads, _, page, _ = self.k.shape
        row = {"k": (page, self.head_dim), "v": (page, self.head_dim)}
        if self.quantized:
            row.update(ks=(), vs=())
        if set(arrays) != set(row):
            return False
        n = arrays["k"].shape[2:3]
        return all(arrays[name].shape == (layers, heads) + n + row[name]
                   for name in row)

    def import_pages(self, ids, arrays):
        """The pool with `export_pages`' ``arrays`` installed at pages
        ``ids`` (scales and all: an int8 page comes back bit for bit)."""
        idx = jnp.asarray(np.asarray(ids, np.int32))
        k = self.k.at[:, :, idx, :, :self.head_dim].set(
            jnp.asarray(arrays["k"]))
        v = self.v.at[:, :, idx, :, :self.head_dim].set(
            jnp.asarray(arrays["v"]))
        if not self.quantized:
            return KVPool(k, v, self.head_dim)
        return KVPool(
            k, v, self.head_dim,
            self.k_scales.at[:, :, idx].set(jnp.asarray(arrays["ks"])),
            self.v_scales.at[:, :, idx].set(jnp.asarray(arrays["vs"])))
