"""Flash attention Pallas kernels (TPU), forward and backward.

Replaces the reference's fused inference attention
(`operators/fused/multihead_matmul_op.cu`) and the composed
matmul+softmax+matmul training path with tiled online-softmax kernels that
keep the running statistics in VMEM (per /opt/skills/guides/pallas_guide.md).

The backward pass is a real pair of Pallas kernels (dq and dk/dv tiles,
recomputing P per tile from the saved logsumexp — no S^2 tensor ever hits
HBM), matching the memory behaviour the flash-attention algorithm promises.
Falls back to the XLA composed form when shapes don't tile or a dense mask
is supplied.
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


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                         seq_k, scale, causal, block_q):
    # grid: (batch*heads, num_q_blocks); whole K/V for the head resident
    # in VMEM, looped over in block_k slices.  Fastest form (no acc
    # scratch traffic, K block count can be clipped under the causal
    # mask), used while 2*seq_k*d fits the VMEM budget; the streaming
    # kernel below takes over beyond it.
    q = q_ref[...].astype(jnp.float32) * scale  # [block_q, d]
    m = jnp.full((block_q,), -1e30, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    qi = pl.program_id(1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)[:, 0]

    num_k = seq_k // block_k
    if causal:
        # Only K blocks intersecting the lower triangle contribute.
        num_k = jnp.minimum(num_k,
                            ((qi + 1) * block_q + block_k - 1) // block_k)

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)[0]
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask, logits, -1e30)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, num_k, body, (m, l, acc))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    lse_ref[...] = jnp.broadcast_to(lse[:, None], (block_q, _LANES))


# Causal dead-block fetch clamps, shared by the streaming forward and both
# backward kernels.  A tile wholly above the causal diagonal contributes
# nothing: compute there is pl.when-gated off in the kernels, and these
# index maps additionally skip the DMA by clamping the streamed block
# index to the live range (Pallas skips re-fetch when the index repeats).
# Keep the formulas in sync with the kernels' `live` predicates.
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


# VMEM budget for holding a head's full K+V resident in the forward
# kernel (the scoped limit on this toolchain is 16MB; leave room for the
# q/o blocks and pipelining buffers).  Measured: resident beats streaming
# by 5-20% where it fits (S<=8192 at d=64), so both kernels are kept.
_RESIDENT_KV_BYTES = 6 * 1024 * 1024


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *,
                block_k, seq_k, scale, causal, block_q):
    # grid (bh, num_q, num_k): K/V blocks STREAM through VMEM (k is the
    # fastest grid dim) while the (bh, q)-pinned output block and the f32
    # scratch accumulators (acc / running max / running sum) stay resident
    # — constant VMEM at any sequence length, same scheme as the backward
    # kernels (the earlier all-of-K/V-resident form hit the 16MB scoped
    # VMEM limit around S=16k at d=128 bf16; advisor round-2 finding).
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    num_k = seq_k // block_k

    @pl.when(kj == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)

    # causal: a K block entirely above the diagonal contributes nothing
    live = ((qi + 1) * block_q - 1 >= kj * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale  # [block_q, d]
        k_blk = k_ref[...].astype(jnp.float32)      # [block_k, d]
        v_blk = v_ref[...].astype(jnp.float32)
        m = m_scr[...][:, 0]
        l = l_scr[...][:, 0]
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)[:, 0]
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)[0]
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask, logits, -1e30)
        m_blk = jnp.max(logits, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc[...] = acc[...] * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    @pl.when(kj == num_k - 1)
    def _flush():
        m = m_scr[...][:, 0]
        l = l_scr[...][:, 0]
        o_ref[...] = (acc[...] / jnp.maximum(l, 1e-30)[:, None]
                      ).astype(o_ref.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[...] = jnp.broadcast_to(lse[:, None], (block_q, _LANES))


def _out_struct(shape, dtype, *like):
    """A kernel output's ShapeDtypeStruct, varying over the mesh axes its
    inputs vary over: inside a `shard_map` with ``check_vma=True`` (the
    hybrid train step, models/gpt_spmd.py) a `pallas_call` has to say so
    itself.  Outside a shard_map the set is empty and changes nothing."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in like))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


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
        out, lse = pl.pallas_call(
            functools.partial(
                _fwd_kernel_resident, block_k=block_k, seq_k=sk, scale=s,
                causal=is_causal, block_q=block_q),
            grid=(b * h, sq // block_q),
            in_specs=[
                pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, sk, d), lambda i, j: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, block_q, _LANES),
                             lambda i, j: (i, j, 0)),
            ],
            out_shape=out_shape,
            name="flash_attention_fwd",
        )(qr, kr, vr)
        return out.reshape(b, h, sq, d), lse[:, :, 0]

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
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        name="flash_attention_fwd",
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d), lse[:, :, 0]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------
#
# Standard flash-attention backward split into two kernels so each output
# tile has a single writer:
#   dkv kernel: grid over K blocks, loops over Q blocks, accumulates
#               dV = P^T dO and dK = dS^T (Q*scale)
#   dq  kernel: grid over Q blocks, loops over K blocks, accumulates
#               dQ = scale * dS K
# with P recomputed per tile from the saved logsumexp and
# dS = P * (dP - delta).  delta = rowsum(dO * O) is computed in-kernel
# from the saved O (cheap VPU reduce) rather than precomputed — passing O
# (input dtype, D lanes) costs 1/8 the HBM traffic of a broadcast f32
# 128-lane delta array.  lse stays in the 128-lane broadcast layout
# (upstream jax's flash kernel convention); the compact
# (sq//128, 128)-packed alternative needs a cross-lane reshape in-kernel,
# which Mosaic fails to lower.


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                    dk_ref, dv_ref, acc_dk, acc_dv, *, block_q, block_k,
                    seq_q, scale, causal):
    # grid (bh, num_k, num_q): the q axis is the FASTEST grid dim, so the
    # (bh, k)-pinned output blocks and f32 scratch accumulators stay
    # resident while q/do/o/lse blocks stream through VMEM — constant VMEM
    # at any sequence length (the all-rows-in-VMEM form topped out ~4k)
    ki = pl.program_id(1)
    qj = pl.program_id(2)
    num_q = seq_q // block_q

    @pl.when(qj == 0)
    def _init():
        acc_dk[...] = jnp.zeros_like(acc_dk)
        acc_dv[...] = jnp.zeros_like(acc_dv)

    # causal: a tile entirely above the diagonal contributes nothing —
    # skip its matmuls (max q_pos < min k_pos)
    live = ((qj + 1) * block_q - 1 >= ki * block_k) if causal else True

    @pl.when(live)
    def _compute():
        k_blk = k_ref[...].astype(jnp.float32)          # [block_k, d]
        v_blk = v_ref[...].astype(jnp.float32)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)[0]
        q_blk = q_ref[...].astype(jnp.float32) * scale  # [block_q, d]
        do_blk = do_ref[...].astype(jnp.float32)
        o_blk = o_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, 0]
        delta = jnp.sum(do_blk * o_blk, axis=-1)
        logits = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if causal:
            q_pos = qj * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)[:, 0]
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask, logits, -1e30)
        p = jnp.exp(logits - lse[:, None])           # [block_q, block_k]
        acc_dv[...] += jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None])
        acc_dk[...] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qj == num_q - 1)
    def _flush():
        dk_ref[...] = acc_dk[...].astype(dk_ref.dtype)
        dv_ref[...] = acc_dv[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                   dq_ref, acc_dq, *, block_q, block_k, seq_k, scale,
                   causal):
    # grid (bh, num_q, num_k): k blocks stream while the dq accumulator
    # stays pinned (same streaming scheme as the dkv kernel)
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    num_k = seq_k // block_k

    @pl.when(kj == 0)
    def _init():
        acc_dq[...] = jnp.zeros_like(acc_dq)

    live = ((qi + 1) * block_q - 1 >= kj * block_k) if causal else True

    @pl.when(live)
    def _compute():
        q_blk = q_ref[...].astype(jnp.float32) * scale   # [block_q, d]
        do_blk = do_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, 0]
        delta = jnp.sum(do_blk * o_ref[...].astype(jnp.float32), axis=-1)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)[:, 0]
        k_blk = k_ref[...].astype(jnp.float32)
        v_blk = v_ref[...].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)[0]
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask, logits, -1e30)
        p = jnp.exp(logits - lse[:, None])
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None])
        acc_dq[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == num_k - 1)
    def _flush():
        dq_ref[...] = (acc_dq[...] * scale).astype(dq_ref.dtype)


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
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
                          seq_q=sq, scale=s, causal=is_causal),
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
            pl.BlockSpec((None, block_k, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j, r: (i, j, 0)),
        ],
        out_shape=[
            _out_struct((b * h, sk, d), k.dtype, q, k, v, g),
            _out_struct((b * h, sk, d), v.dtype, q, k, v, g),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        name="flash_attention_bwd_dkv",
    )(qr, kr, vr, dor, outr, lse_b)

    kv_idx = (_causal_kv_clamp(block_q, block_k) if is_causal
              else _stream_idx)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          seq_k=sk, scale=s, causal=is_causal),
        grid=(b * h, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), kv_idx),
            pl.BlockSpec((None, block_k, d), kv_idx),
            pl.BlockSpec((None, block_q, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda i, j, r: (i, j, 0)),
            pl.BlockSpec((None, block_q, _LANES),
                         lambda i, j, r: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda i, j, r: (i, j, 0)),
        out_shape=_out_struct((b * h, sq, d), q.dtype, q, k, v, g),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_attention_bwd_dq",
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
    Pallas backward (dq/dk/dv kernels recomputing P from the saved
    logsumexp).  Block sizes: explicit arguments win; otherwise the
    per-shape measured winners from flash_autotune_cache.json (written
    by tools/bench_kernels.py — deep-K blocks like 512x1024 win past
    S=1024), falling back to 512x512 shrunk by `pick_blocks` for
    sequences they don't divide.

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


# -- measured block-size cache (round-5 VERDICT #6) -------------------------
# tools/bench_kernels.py sweeps (block_q, block_k) per
# (seq_q, seq_k, d, dtype, causal) on the live chip and commits the
# winners here; the entry point prefers a cached winner over the
# divisibility default when the caller left the blocks at their
# defaults.  Re-run the bench after kernel changes.
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
