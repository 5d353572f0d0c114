"""Attention functionals.

Reference: the inference-only fused `multihead_matmul` op
(`operators/fused/multihead_matmul_op.cu`) and the python-composed attention
in `python/paddle/nn/layer/transformer.py`.  TPU-native: a single fused
scaled-dot-product attention that XLA maps onto MXU matmuls; on TPU the inner
kernel is replaced by the Pallas flash-attention kernel
(`paddle_tpu/ops/pallas/flash_attention.py`) when shapes allow.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import WHITE, dispatch
from ...core.tensor import unwrap


def _sdpa_reference(q, k, v, mask, dropout_p, scale, is_causal):
    # q,k,v: [B, N, H, D] (paddle transformer convention is [B, H, N, D] after
    # transpose; we accept [B, H, N, D] here)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
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
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def multi_query_causal_mask(q_offsets, q_len, seq_lens, kv_len):
    """Ragged multi-query causal visibility, shared by the paged
    attention reference (`ops.pallas.paged_attention`) and the
    speculative-decode verify step so the two can never disagree.

    Query ``i`` of sequence ``b`` sits at absolute position
    ``q_offsets[b] + i`` and may see KV position ``p`` iff
    ``p < min(seq_lens[b], q_offsets[b] + i + 1)`` — bottom-right-aligned
    causality clamped to the sequence's live KV range (``seq_lens`` may
    be below the last query's position when trailing KV writes were
    suppressed, e.g. past a request's token budget).

    q_offsets/seq_lens: [B] int32; returns bool [B, q_len, kv_len].
    A sequence with ``seq_lens == 0`` is fully masked (inactive slot).
    """
    pos = jnp.arange(kv_len, dtype=jnp.int32)
    qi = jnp.arange(q_len, dtype=jnp.int32)
    limit = jnp.minimum(seq_lens[:, None],
                        q_offsets[:, None] + qi[None, :] + 1)  # [B, Q]
    return pos[None, None, :] < limit[:, :, None]


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, scale=None,
                                 training=True, name=None):
    """query/key/value: [batch, num_heads, seq, head_dim]."""
    if unwrap(query).ndim == 4 and jax.default_backend() == "tpu":
        from ...ops.pallas.flash_attention import flash_attention_fwd

        def f(q, k, v, *m):
            return flash_attention_fwd(q, k, v, m[0] if m else None, is_causal, scale)

    else:
        def f(q, k, v, *m):
            return _sdpa_reference(q, k, v, m[0] if m else None, dropout_p, scale, is_causal)

    if attn_mask is not None:
        return dispatch(f, query, key, value, attn_mask, nondiff=(3,), amp_policy=WHITE)
    return dispatch(f, query, key, value, amp_policy=WHITE)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, name=None):
    out = scaled_dot_product_attention(query, key, value, is_causal=causal,
                                       dropout_p=dropout)
    if return_softmax:
        return out, None
    return out
