"""Ring attention — sequence/context parallelism.

Net-new capability (reference has NO SP/CP — SURVEY.md §2.3 row "Sequence/
context parallel": the TPU build must add it).  Design: Q/K/V are sharded
over the 'sp' mesh axis on the sequence dim; each device holds its local Q
block and rotates K/V blocks around the ring with `lax.ppermute`, folding
each visiting block into a numerically-stable online softmax (flash-style
running max / running sum), so attention over a sequence of length S uses
O(S/sp) memory per chip and the K/V transfers ride the ICI ring concurrently
with compute.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def _block_attn(q, k, v, scale, mask):
    # q:[B,H,Sq,D] k,v:[B,H,Sk,D]; returns (out_unnorm, row_max, row_sum)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    s = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
    return o, m, s


def _use_flash_blocks(q, scale):
    """Whether the Pallas flash kernel should compute each ring block —
    the single shared gate (`pallas_attention_wanted`) + block policy
    (`pick_blocks`) and a static-scale requirement (the kernel bakes
    scale as a compile-time constant)."""
    from ..ops.pallas.flash_attention import (pallas_attention_wanted,
                                              pick_blocks)

    if pick_blocks(q.shape[-2], q.shape[-2]) is None or q.shape[-1] % 64:
        return False
    if not isinstance(scale, (int, float)):
        return False  # traced scale can't be baked into the kernel
    return pallas_attention_wanted(q.shape[-2])


def _flash_or_skip(q, k, v, scale, causal, rank, src):
    """Causal ring block via flash: a block strictly below the diagonal
    (src < rank) is fully visible, the diagonal block (src == rank) is
    causal, and a block strictly above (src > rank) contributes nothing
    — selected with lax.cond since rank/src are traced."""
    if not causal:
        return _flash_block(q, k, v, scale, False)
    b, h, sq = q.shape[0], q.shape[1], q.shape[2]

    def masked():
        # constants do not vary over the mesh, the flash branches' out
        # and lse do (as q does), and under the hybrid step's
        # check_vma=True `cond` wants one type from all branches; the
        # row sum is a constant in `_flash_block` too
        vary = tuple(jax.typeof(q).vma)
        return (lax.pcast(jnp.zeros(q.shape, jnp.float32), vary,
                          to="varying"),
                lax.pcast(jnp.full((b, h, sq), -jnp.inf, jnp.float32),
                          vary, to="varying"),
                jnp.zeros((b, h, sq), jnp.float32))

    return lax.cond(
        src > rank, masked,
        lambda: lax.cond(src == rank,
                         lambda: _flash_block(q, k, v, scale, True),
                         lambda: _flash_block(q, k, v, scale, False)))


def _flash_block(q, k, v, scale, causal):
    """One ring block via the Pallas flash kernel.  The kernel returns the
    NORMALIZED block output plus the row logsumexp; that maps onto the
    online-softmax carry as (o_unnorm=out, m=lse, l=1), since
    exp(logits - lse) sums to exactly 1.  Differentiable: the custom VJP
    recomputes the block in composed form (the same O(S_local^2) the
    pre-flash ring used, but only during backward)."""
    out, lse = _flash_block_diff(q, k, v, causal, float(scale))
    b, h, sq, _ = q.shape
    return out, lse, jnp.ones((b, h, sq), jnp.float32)


def _composed_block(q, k, v, causal, scale):
    """(normalized out f32, lse) of one block — delegates to the ONE
    composed attention definition (`_composed_attention`), so the VJP
    ground truth can never drift from the single-device reference."""
    from ..ops.pallas.flash_attention import _composed_attention

    out, lse = _composed_attention(q, k, v, None, causal, scale,
                                   want_lse=True)
    return out.astype(jnp.float32), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_block_diff(q, k, v, causal, scale):
    from ..ops.pallas.flash_attention import _pallas_forward, pick_blocks

    bq, bk = pick_blocks(q.shape[-2], k.shape[-2])
    out, lse = _pallas_forward(q, k, v, causal, scale, bq, bk)
    b, h, sq, _ = q.shape
    return out.astype(jnp.float32), lse.reshape(b, h, sq)


def _flash_block_diff_fwd(q, k, v, causal, scale):
    return _flash_block_diff(q, k, v, causal, scale), (q, k, v)


def _flash_block_diff_bwd(causal, scale, res, cots):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda a, b, c: _composed_block(a, b, c, causal, scale), q, k, v)
    return vjp(cots)


_flash_block_diff.defvjp(_flash_block_diff_fwd, _flash_block_diff_bwd)


def ring_attention_local(q, k, v, axis_name: str = "sp", causal: bool = False,
                         scale=None):
    """Per-device body; call inside shard_map with q/k/v sharded on the seq
    dim over `axis_name`.  q,k,v: [B, H, S_local, D]."""
    import math

    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    s_local = q.shape[2]
    use_flash = _use_flash_blocks(q, s)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        o_acc, m_acc, l_acc, k_cur, v_cur = carry
        # K/V block currently held came from rank (rank - i) mod n
        src = (rank - i) % n
        if use_flash:
            o_blk, m_blk, l_blk = _flash_or_skip(q, k_cur, v_cur, s,
                                                 causal, rank, src)
        else:
            if causal:
                q_pos = rank * s_local + jnp.arange(s_local)
                k_pos = src * s_local + jnp.arange(s_local)
                mask = q_pos[:, None] >= k_pos[None, :]
                mask = mask[None, None]
            else:
                mask = None
            o_blk, m_blk, l_blk = _block_attn(q, k_cur, v_cur, s, mask)
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_blk - m_new)
        o_acc = o_acc * alpha[..., None].astype(o_acc.dtype) + \
            o_blk * beta[..., None].astype(o_blk.dtype)
        l_acc = l_acc * alpha + l_blk * beta
        if i < n - 1:
            # N-1 rotations suffice: after the last block is consumed a
            # further rotation's result is never read (round-4 comm fix
            # — also what the schedule-accounting test pins)
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
        return o_acc, m_new, l_acc, k_cur, v_cur

    b, h = q.shape[0], q.shape[1]
    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full((b, h, s_local), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    o, m, l, _, _ = _unrolled(step, n, (o0, m0, l0, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _unrolled(step, n, carry):
    # unrolled ring (n is a static mesh size; unrolling lets XLA overlap the
    # ppermute of step i+1 with the matmuls of step i)
    for i in range(n):
        carry = step(i, carry)
    return carry


def ring_attention(q, k, v, mesh, axis_name: str = "sp", causal: bool = False,
                   scale=None):
    """Global entry: q,k,v are global arrays [B,H,S,D]; returns attention
    computed with the ring schedule, sharded over `axis_name` on S."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    spec = P(None, None, axis_name, None)
    fn = shard_map(
        partial(ring_attention_local, axis_name=axis_name, causal=causal,
                scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
