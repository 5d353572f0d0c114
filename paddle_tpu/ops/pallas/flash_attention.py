"""Flash attention Pallas kernels (TPU), forward and backward.

Replaces the reference's fused inference attention
(`operators/fused/multihead_matmul_op.cu`) and the composed
matmul+softmax+matmul training path with tiled online-softmax kernels that
keep the running statistics in VMEM (per /opt/skills/guides/pallas_guide.md).

The backward pass is one Pallas kernel (dq, dk and dv from the same
sub-tiles, P recomputed per sub-tile from the saved logsumexp — no S^2
tensor ever hits HBM), matching the memory behaviour the flash-attention
algorithm promises.  Falls back to the XLA composed form when shapes don't
tile or a dense mask is supplied.

Precision: every matrix product takes its operands in the dtype the caller
passed and accumulates in float32 — bf16 q, k, v, dO (the train cells under
autocast) give bf16 MXU products, float32 operands float32 ones
(`vmatmul.f32`), from the same code.  P is rounded to v's dtype before P V
and P^T dO, dS to q's before dS^T Q and dS K (what `_composed_attention`
does with its probabilities); the scale multiplies the float32 logits and
the float32 dk/dq accumulators, never an operand.  The softmax itself
(logits, mask, running max and sum, exp, delta, dS, log-sum-exp) is
float32: the v5e has no bf16 vector or transcendental unit.

Where the time goes (v5e, the compiler's bundle dumps and the chip, PR 30;
PERF.md section 6): the matrix unit, which takes one LHS row a cycle
whatever the operand dtype, and heads of 64 fill half of its 128 x 128 when
they are a product's contraction or its output width.  So the kernels (a)
do not compute what the mask removes: loops inside each kernel walk a grid
step's tile in `_SUB_Q` x `_SUB_K` sub-tiles and skip those above the
causal diagonal, so that one grid step can span the whole sequence; and
(b) put the 64 on the unit's streaming side wherever a product allows it:
O^T = V^T P^T, dV^T = dO^T P, dK^T = Q^T dS, dQ^T = K^T dS^T, accumulated
transposed and turned back once a grid step (the forward builds its scores
transposed for it, which also makes its statistics rows).  Q K^T and
dO V^T contract over the 64 and stay half full.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# TPU float32 tiling wants the lane (last) dimension to be 128; the per-row
# softmax statistics are stored broadcast across one lane tile.
_LANES = 128


def _composed_attention(q, k, v, mask, is_causal, scale, want_lse=False):
    """The single composed (O(S^2)) attention definition — the numerics
    ground truth for the Pallas kernels AND the recompute backward of the
    ring flash blocks.  Returns out (q.dtype) or (out, lse f32)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    logits = logits.astype(jnp.float32)
    if is_causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((ql, kl), dtype=bool), k=kl - ql)
        logits = jnp.where(causal, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    if want_lse:
        return out, jax.scipy.special.logsumexp(logits, axis=-1)
    return out


def _xla_reference(q, k, v, mask, is_causal, scale):
    return _composed_attention(q, k, v, mask, is_causal, scale)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b
_TT = (((0,), (1,)), ((), ()))   # a.T @ b.T


def _dot(a, b, dims):
    """One MXU product: the operands as they are, a float32 result."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


# The (block_q, block_k) tile a grid step holds in VMEM is worked through
# in [_SUB_Q, _SUB_K] sub-tiles by loops inside the kernel, so the causal
# skip engages per sub-tile at no grid-step cost (~0.35 us a step, and the
# accumulators' init and flush) and the blocks can be as large as the
# sequence.  512 x 512 keeps the matrix unit busiest: smaller sub-tiles
# skip more (10 of 16 at 256 against 3 of 4) and lose more than that to
# the phases of a sub-tile not overlapping (per 256 x 256 of scores, in
# bundles of the compiler's schedule: forward 289 / dk,dv 409 / dq 320 at
# 512, 427 / 579 / 522 at 256; on the chip those three kernels took
# 2.14 ms at 512 and 3.20 ms at 256, 16 x 12 heads of 1024: PR 30,
# PERF.md section 6; the one backward kernel since PR 33 is 472 at 512).
_SUB_Q = 512
_SUB_K = 512


def _mask_causal(logits, q_first, k_first, q_axis):
    """A sub-tile's logits with the keys after each query masked out; the
    queries run along ``q_axis`` from position ``q_first``, the keys along
    the other axis from ``k_first``.  (Masking only the sub-tiles that
    cross the diagonal was tried: the matrix unit holds these kernels, not
    the vector unit, and a second, unmasked loop body bought 3-5% of a
    third of the sub-tiles for twice the program: PERF.md section 6,
    PR 30.)"""
    def positions(first, axis):
        shape = [1, 1]
        shape[axis] = logits.shape[axis]
        return first + jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    return jnp.where(positions(q_first, q_axis)
                     >= positions(k_first, 1 - q_axis), logits, -1e30)


def _loop(lo, hi, fn):
    """``fn(i)`` for i in [lo, hi): a loop with no carry (the kernels'
    state lives in VMEM refs)."""
    def body(i, carry):
        fn(i)
        return carry

    jax.lax.fori_loop(lo, hi, body, 0)


def _for_live_subtiles(causal, qi, kj, block_q, block_k, tile,
                       k_outer=False):
    """Work through grid tile (q block ``qi``, K block ``kj``) in
    sub-tiles: ``tile(rows, cols, q_first, k_first)`` gets a sub-tile's
    index into the q-side and K-side refs and its first query / key
    position.  Full attention runs them all; under the causal mask all but
    those wholly above the diagonal (last q_pos < first k_pos).  The q
    sub-blocks are the outer loop, or with ``k_outer`` the K sub-blocks
    (the backward kernel: its dk/dv accumulators belong to a K
    sub-block)."""
    # a sub-tile's edge: _SUB_* where it divides the block, else the block
    sub_q = _SUB_Q if block_q % _SUB_Q == 0 else block_q
    sub_k = _SUB_K if block_k % _SUB_K == 0 else block_k
    nq, nk = block_q // sub_q, block_k // sub_k
    q0, k0 = qi * block_q, kj * block_k

    def index(i, n, total):
        # the whole block, statically, where it is one sub-block
        return slice(None) if n == total else pl.ds(
            pl.multiple_of(i * n, n), n)

    def run(r, c):
        tile(index(r, sub_q, block_q), index(c, sub_k, block_k),
             q0 + r * sub_q, k0 + c * sub_k)

    if k_outer:
        def per_k(c):
            first = 0
            if causal:      # the first q sub-block whose last row sees c
                first = jnp.minimum(jnp.maximum(
                    k0 + c * sub_k - q0 + sub_q, sub_q) // sub_q - 1, nq)
            _loop(first, nq, lambda r: run(r, c))
        _loop(0, nk, per_k)
    else:
        def per_q(r):
            live = nk
            if causal:      # the K sub-blocks r's last row sees
                live = jnp.minimum(jnp.maximum(
                    q0 + (r + 1) * sub_q - 1 - k0 + sub_k, 0) // sub_k, nk)
            _loop(0, live, lambda c: run(r, c))
        _loop(0, nq, per_q)


def _lanes(x, n):
    """A lane-replicated [rows, 128] statistic as [rows, n]: whole vregs
    repeated where n is a multiple of 128 (no lane broadcast)."""
    if n % _LANES:
        return x[:, :1]
    return x if n == _LANES else pltpu.repeat(x, n // _LANES, axis=1)


# Causal dead-block fetch clamps, of the forward and the backward
# kernel.  A tile wholly above the causal diagonal contributes nothing:
# `_for_live_subtiles` finds no live sub-tile there, and these index maps
# additionally skip the DMA by clamping the streamed block index to the
# live range (Pallas skips re-fetch when the index repeats).
def _stream_idx(i, j, r):
    return (i, r, 0)


def _causal_kv_clamp(block_q, block_k):
    """Fetch index for K/V streamed under a pinned q block j: clamp to the
    last live K block, ((j+1)*block_q - 1) // block_k."""
    def idx(i, j, r):
        return (i, jnp.minimum(r, ((j + 1) * block_q - 1) // block_k), 0)
    return idx


def _causal_q_clamp(block_q, block_k):
    """Fetch index for Q rows streamed under a pinned K block j: clamp to
    the first live q block, (j*block_k) // block_q."""
    def idx(i, j, r):
        return (i, jnp.maximum(r, (j * block_k) // block_q), 0)
    return idx


# The VMEM a kernel may use unless it asks for more, on this toolchain.
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024

# VMEM budget for holding a head's full K+V resident in the forward
# kernel: one K block spans the sequence, so a (batch*head, q block) pair
# is one grid step however long the sequence (the rest of the scoped
# limit is room for the q/o blocks and pipelining buffers).  Beyond it
# K/V stream in block_k pieces.
_RESIDENT_KV_BYTES = 6 * 1024 * 1024


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *,
                block_q, block_k, seq_k, scale, causal):
    # grid (bh, num_q, num_k): K/V blocks stream through VMEM (k is the
    # fastest grid dim; one block when K/V are resident) while the
    # (bh, q)-pinned output block and the f32 scratch accumulators stay
    # put — constant VMEM at any sequence length.  The scores are built
    # TRANSPOSED, [keys, queries]: the running max and sum are then rows
    # (lanes are queries; a reduction runs down the sublanes on the vector
    # unit, a rescale is a sublane broadcast), and O^T = V^T P^T puts the
    # head's 64 on the matrix unit's streaming side, where it wastes
    # nothing, with P^T the stationary operand as it lies.  acc is O^T
    # [d, block_q]; m and l are [8, block_q], sublanes equal.  The flush
    # transposes back.
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    num_k = seq_k // block_k

    @pl.when(kj == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)

    def tile(rows, cols, q_first, k_first):
        v_sub = v_ref[cols, :]                      # [sub_k, d]
        logits = _dot(k_ref[cols, :], q_ref[rows, :], _NT) * scale
        if causal:
            logits = _mask_causal(logits, q_first, k_first, q_axis=1)
        m = m_scr[:, rows]                          # [8, sub_q]
        m_new = jnp.maximum(m, jnp.max(logits, axis=0, keepdims=True))
        p = jnp.exp(logits - m_new[:1, :])
        alpha = jnp.exp(m - m_new)
        l_scr[:, rows] = l_scr[:, rows] * alpha + jnp.sum(
            p, axis=0, keepdims=True)
        m_scr[:, rows] = m_new
        acc[:, rows] = acc[:, rows] * alpha[:1, :] + _dot(
            v_sub, p.astype(v_sub.dtype), _TN)

    _for_live_subtiles(causal, qi, kj, block_q, block_k, tile)

    @pl.when(kj == num_k - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)          # [8, block_q]
        o_ref[...] = (acc[...] / l[:1, :]).T.astype(o_ref.dtype)
        lse = m_scr[...] + jnp.log(l)
        lse_ref[...] = jnp.broadcast_to(lse[:1, :], (_LANES, block_q)).T


def _out_struct(shape, dtype, *like):
    """A kernel output's ShapeDtypeStruct, varying over the mesh axes its
    inputs vary over: inside a `shard_map` with ``check_vma=True`` (the
    hybrid train step, models/gpt_spmd.py) a `pallas_call` has to say so
    itself.  Outside a shard_map the set is empty and changes nothing."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# The two entry points below are jitted so that a model's layers share one
# traced and lowered copy of each kernel: tracing the kernel bodies at
# every call site cost a train step's set-up 2-4 s (24 layers; PR 30).
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _pallas_forward(q, k, v, is_causal, scale, block_q, block_k):
    """Returns (out [B,H,Sq,D], lse [B*H, Sq] fp32)."""
    b, h, sq, d = q.shape
    sk = k.shape[-2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    out_shape = [_out_struct((b * h, sq, d), q.dtype, q, k, v),
                 _out_struct((b * h, sq, _LANES), jnp.float32, q, k, v)]

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)

    if 2 * sk * d * q.dtype.itemsize <= _RESIDENT_KV_BYTES:
        block_k = sk    # K/V resident: the kernel's loops do the skipping
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, seq_k=sk, scale=s, causal=is_causal,
        block_q=block_q,
    )
    kv_idx = (_causal_kv_clamp(block_q, block_k) if is_causal
              else _stream_idx)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), kv_idx),
            pl.BlockSpec((None, block_k, d), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_q, _LANES),
                         lambda i, j, r: (i, j, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32),
                        pltpu.VMEM((8, block_q), jnp.float32),
                        pltpu.VMEM((8, block_q), jnp.float32)],
        name="flash_attention_fwd",
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d), lse[:, :, 0]


# ---------------------------------------------------------------------------
# Backward kernel
# ---------------------------------------------------------------------------
#
# One kernel gives dQ, dK and dV.  S = Q K^T and dP = dO V^T are what every
# gradient is made from, and the matrix unit holds the backward (module
# docstring), so a live sub-tile builds P and dS once and feeds all three
# accumulators from them: five products,
#   S = Q K^T    dP = dO V^T    dV^T += dO^T P    dK^T += Q^T dS
#   dQ^T += K^T dS^T
# where a dk/dv kernel and a dq kernel that each rebuilt S and dP spent
# seven (until PR 33; PERF.md section 6).  The accumulators are transposed,
# [d, rows]: the head's 64 is then the matrix unit's streaming side and
# P / dS its stationary operand, half the passes of P^T dO with 64 of 128
# output columns used; the flush transposes back and applies the scale to
# the float32 accumulator.  P is recomputed per sub-tile from the saved
# logsumexp and dS = P * (dP - delta).  delta = rowsum(dO * O) is computed
# in-kernel from the saved O (cheap VPU reduce) rather than precomputed —
# passing O (input dtype, D lanes) costs 1/8 the HBM traffic of a broadcast
# f32 128-lane delta array.  lse stays in the 128-lane broadcast layout
# (upstream jax's flash kernel convention); the compact
# (sq//128, 128)-packed alternative needs a cross-lane reshape in-kernel,
# which Mosaic fails to lower.
#
# Who writes what: dK and dV belong to a K block, which the grid pins
# while the q side streams past it (q is the fastest axis), so each has one
# writer.  dQ belongs to a q block and sums over the K blocks, grid steps
# that are not consecutive, and an output block can only be revisited
# while it stays put: dQ's block is therefore the head's whole [seq_q, d],
# resident with its [d, seq_q] float32 accumulator from the head's first
# grid step to its last.  Where the blocks span the sequence (the train
# cells: (1024, 1024) at 1024) a head is one grid step.  The kernel's VMEM
# therefore grows with seq_q (768 bytes a row for bf16 heads of 64), and
# `_bwd_vmem_limit` asks for what the shapes need once that passes what
# every kernel gets.  The chip's 128 MiB end it: the chip's compiler takes
# 131,072 rows of bf16 heads of 64 and 65,536 of float32 ones or of heads
# of 128 (tests/test_tpu_compile.py), and refuses twice that.


def _p_and_ds(q_sub, k_sub, v_sub, do_sub, o_sub, lse, scale, q_first,
              k_first, causal):
    """One sub-tile of the backward pass, float32: the probabilities
    rebuilt from the saved log-sum-exp (``lse``: lane-replicated
    [rows, 128]) and dS = P * (dP - delta)."""
    logits = _dot(q_sub, k_sub, _NT) * scale
    if causal:
        logits = _mask_causal(logits, q_first, k_first, q_axis=0)
    p = jnp.exp(logits - _lanes(lse, k_sub.shape[0]))
    delta = jnp.sum(do_sub.astype(jnp.float32) * o_sub.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dp = _dot(do_sub, v_sub, _NT)
    return p, p * (dp - delta)


def _bwd_vmem_limit(seq_q, block_q, block_k, d, itemsize):
    """The scoped VMEM the backward kernel has to ask for, or None while
    what it holds fits in what every kernel gets.  Counted from the shapes,
    with room: the streamed blocks in their two buffers (q, dO, O and the
    log-sum-exp a q block; k, v, dk, dv a K block; a row of d < 128 takes
    a whole lane tile), the dk/dv accumulators, a sub-tile's float32
    intermediates, and what dQ keeps for a head: its float32 accumulator
    and its output block's two buffers.  Not stated where it is not
    needed: a stated limit is VMEM the compiler keeps free around the
    kernel, whatever the kernel then uses of it (64 MiB stated at the
    train cells' shape, where 8 are used, cost gpt2m-train's step 2.7%:
    PR 33, PERF.md section 6)."""
    row = itemsize * max(d, _LANES)
    need = (2 * row * (3 * block_q + 4 * block_k)
            + 2 * 4 * _LANES * block_q + 2 * 4 * d * block_k
            + 6 * 4 * min(block_q, _SUB_Q) * min(block_k, _SUB_K)
            + seq_q * (4 * d + 2 * row))
    return need if need > _SCOPED_VMEM_BYTES else None


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, acc_dq, acc_dk, acc_dv, *,
                block_q, block_k, seq_q, seq_k, scale, causal):
    # grid (bh, num_k, num_q): the q axis is the FASTEST grid dim, so the
    # (bh, k)-pinned dk/dv blocks and their f32 accumulators stay resident
    # while q/do/o/lse blocks stream through VMEM; dq's block and
    # accumulator span the head's rows and stay through all of its steps.
    # A row's dQ sums its K sub-blocks in ascending order, whatever the
    # blocks: ki is the slower axis and the sub-tile walk is K-outer.
    ki = pl.program_id(1)
    qj = pl.program_id(2)
    first_q = qj == 0
    last_q = qj == seq_q // block_q - 1

    @pl.when(first_q)
    def _init():
        acc_dk[...] = jnp.zeros_like(acc_dk)
        acc_dv[...] = jnp.zeros_like(acc_dv)

    @pl.when(first_q & (ki == 0))
    def _init_dq():
        acc_dq[...] = jnp.zeros_like(acc_dq)

    def tile(rows, cols, q_first, k_first):
        q_sub = q_ref[rows, :]                          # [sub_q, d]
        k_sub = k_ref[cols, :]                          # [sub_k, d]
        do_sub = do_ref[rows, :]
        p, ds = _p_and_ds(
            q_sub, k_sub, v_ref[cols, :], do_sub, o_ref[rows, :],
            lse_ref[rows, :], scale, q_first, k_first, causal)
        ds = ds.astype(q_sub.dtype)
        acc_dv[:, cols] += _dot(do_sub, p.astype(do_sub.dtype), _TN)
        acc_dk[:, cols] += _dot(q_sub, ds, _TN)
        # the sub-tile's rows in the head, not in the streamed q block
        head_rows = rows if block_q == seq_q else pl.ds(
            pl.multiple_of(q_first, q_sub.shape[0]), q_sub.shape[0])
        acc_dq[:, head_rows] += _dot(k_sub, ds, _TT)

    _for_live_subtiles(causal, qj, ki, block_q, block_k, tile, k_outer=True)

    @pl.when(last_q)
    def _flush():
        dk_ref[...] = (acc_dk[...].T * scale).astype(dk_ref.dtype)
        dv_ref[...] = acc_dv[...].T.astype(dv_ref.dtype)

    @pl.when(last_q & (ki == seq_k // block_k - 1))
    def _flush_dq():
        dq_ref[...] = (acc_dq[...].T * scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _pallas_backward(q, k, v, out, lse, g, is_causal, scale, block_q,
                     block_k):
    b, h, sq, d = q.shape
    sk = k.shape[-2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    dor = g.reshape(b * h, sq, d)
    outr = out.reshape(b * h, sq, d)
    lse_b = jnp.broadcast_to(lse[:, :, None], (b * h, sq, _LANES))

    q_idx = (_causal_q_clamp(block_q, block_k) if is_causal
             else _stream_idx)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, block_q=block_q, block_k=block_k,
                          seq_q=sq, seq_k=sk, scale=s, causal=is_causal),
        grid=(b * h, sk // block_k, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_idx),
            pl.BlockSpec((None, block_k, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_q, d), q_idx),
            pl.BlockSpec((None, block_q, d), q_idx),
            pl.BlockSpec((None, block_q, _LANES), q_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, sq, d), lambda i, j, r: (i, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j, r: (i, j, 0)),
        ],
        out_shape=[
            _out_struct((b * h, sq, d), q.dtype, q, k, v, g),
            _out_struct((b * h, sk, d), k.dtype, q, k, v, g),
            _out_struct((b * h, sk, d), v.dtype, q, k, v, g),
        ],
        scratch_shapes=[pltpu.VMEM((d, sq), jnp.float32),
                        pltpu.VMEM((d, block_k), jnp.float32),
                        pltpu.VMEM((d, block_k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_vmem_limit(sq, block_q, block_k, d,
                                             q.dtype.itemsize)),
        name="flash_attention_bwd",
    )(qr, kr, vr, dor, outr, lse_b)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# ---------------------------------------------------------------------------
# Differentiable wrapper + public entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_diff(q, k, v, is_causal, scale, block_q, block_k):
    out, _ = _pallas_forward(q, k, v, is_causal, scale, block_q, block_k)
    return out


def _flash_diff_fwd(q, k, v, is_causal, scale, block_q, block_k):
    out, lse = _pallas_forward(q, k, v, is_causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(is_causal, scale, block_q, block_k, res, g):
    q, k, v, out, lse = res
    return _pallas_backward(q, k, v, out, lse, g, is_causal, scale,
                            block_q, block_k)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention_fwd(q, k, v, mask=None, is_causal=False, scale=None,
                        block_q=None, block_k=None):
    """q,k,v: [B,H,S,D].  Uses the Pallas kernels when mask is None and shapes
    tile; otherwise the XLA composed reference.  Fully differentiable with a
    Pallas backward (one kernel for dq, dk and dv, recomputing P from the
    saved logsumexp).  Block sizes (what one grid step holds; the kernels
    work through it in sub-tiles): explicit arguments win; otherwise the
    per-shape measured winners from flash_autotune_cache.json, falling
    back to 512x512 shrunk by `pick_blocks` for sequences they don't
    divide.

    Causal cross-length attention (seq_q != seq_k) always takes the XLA
    reference: its causal mask is bottom-right aligned (tril offset
    kl-ql), while the kernels mask top-left (q_pos >= k_pos) — the two
    only agree at seq_q == seq_k."""
    # explicit caller blocks win; the measured cache only fills the
    # default case, then the divisibility heuristic
    if block_q is not None or block_k is not None:
        picked = pick_blocks(q.shape[-2], k.shape[-2],
                             block_q or 512, block_k or 512)
    else:
        picked = cached_blocks(q.shape[-2], k.shape[-2], q.shape[-1],
                               q.dtype, is_causal) or \
            pick_blocks(q.shape[-2], k.shape[-2])
    if (mask is not None or picked is None
            or (is_causal and q.shape[-2] != k.shape[-2])
            or jax.default_backend() != "tpu"):
        return _xla_reference(q, k, v, mask, is_causal, scale)
    block_q, block_k = picked
    # Policy: flag FLAGS_use_pallas_attention: "auto" (default; threshold
    # from the measured crossover vs XLA's fused attention, see
    # BENCH_kernels.json), "1"/"0" force on/off.
    if not pallas_attention_wanted(q.shape[-2], is_causal):
        return _xla_reference(q, k, v, mask, is_causal, scale)
    return _flash_diff(q, k, v, is_causal, scale, block_q, block_k)


def _auto_threshold(is_causal: bool):
    from ...core import flags as _flags

    base = int(_flags.flag("pallas_attention_min_seq"))
    # the S=512 crossover was measured causal-only (the dead-block DMA
    # clamps do nothing for full attention); non-causal keeps the round-2
    # crossover of 1024
    return base if is_causal else max(base, 1024)


def pick_blocks(seq_q: int, seq_k: int, block_q: int = 512,
                block_k: int = 512):
    """Largest power-of-two blocks (floor 128) that tile the sequences;
    None when no tiling exists — the one block-selection policy shared by
    the single-device entry point and the ring blocks."""
    while block_q > 128 and seq_q % block_q:
        block_q //= 2
    while block_k > 128 and seq_k % block_k:
        block_k //= 2
    if seq_q % block_q or seq_k % block_k:
        return None
    return block_q, block_k


# -- measured block-size cache ----------------------------------------------
# (block_q, block_k) per (seq_q, seq_k, d, dtype, causal), measured on the
# chip as the device time of the forward and the backward kernel through
# `_flash_diff` (PR 33's sweep, PERF.md section 6; tools/bench_kernels.py
# times the same call by the wall clock and writes the file too).  The
# entry point prefers a cached winner over the divisibility default when
# the caller left the blocks at their defaults.  A cache measured on other
# kernels is not a measurement: measure again after a change to the
# kernels.
_AUTOTUNE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "flash_autotune_cache.json")
_AUTOTUNE: dict = {}
_AUTOTUNE_LOADED = False


def _autotune_key(seq_q, seq_k, d, dtype, causal):
    return f"{seq_q}x{seq_k}x{d}:{jnp.dtype(dtype).name}:" \
           f"{'causal' if causal else 'full'}"


def _load_autotune():
    global _AUTOTUNE_LOADED
    if _AUTOTUNE_LOADED:
        return _AUTOTUNE
    _AUTOTUNE_LOADED = True
    try:
        import json

        with open(_AUTOTUNE_FILE) as f:
            _AUTOTUNE.update(json.load(f).get("entries", {}))
    except (OSError, ValueError, AttributeError, TypeError):
        # a missing/truncated/corrupt cache must degrade to the
        # divisibility default, never crash the attention hot path
        pass
    return _AUTOTUNE


def cached_blocks(seq_q, seq_k, d, dtype, causal):
    """Measured (block_q, block_k) for this shape, or None.  A stale
    or malformed entry (wrong arity, sub-tile block, no longer tiling
    the sequences) is ignored — cached values must survive the same
    minimum-tile/shrink rules `pick_blocks` enforces before they reach
    the Pallas kernel, degrading to the default rather than failing the
    hot path on a hand-edited or stale cache file (ADVICE round 5)."""
    ent = _load_autotune().get(
        _autotune_key(seq_q, seq_k, d, dtype, causal))
    try:
        bq, bk = int(ent[0]), int(ent[1])
    except (TypeError, ValueError, IndexError, KeyError):
        return None
    if bq < 128 or bk < 128:
        # below the kernel's minimum tile (pick_blocks' shrink floor)
        return None
    if pick_blocks(seq_q, seq_k, bq, bk) != (bq, bk):
        # pick_blocks would have shrunk or rejected these blocks — the
        # entry no longer tiles this shape; fall back to the default
        return None
    return bq, bk


def pallas_attention_wanted(seq_len: int, is_causal: bool = True) -> bool:
    """Shared FLAGS_use_pallas_attention policy ('1'/'0' force, 'auto'
    applies the measured seq threshold) — the single gate used by both the
    single-device kernel and the ring-attention blocks."""
    from ...core import flags as _flags

    if jax.default_backend() != "tpu":
        return False
    pol = str(_flags.flag("use_pallas_attention"))
    if pol in ("1", "True", "true"):
        return True
    return pol == "auto" and seq_len >= _auto_threshold(is_causal)
