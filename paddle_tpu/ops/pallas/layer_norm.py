"""Fused LayerNorm Pallas kernel (TPU).

Replaces the reference's dedicated fused norm kernels
(`operators/fused/fused_fc_elementwise_layernorm_op.cu`,
`operators/fused/skip_layernorm_op.*`, `operators/layer_norm_op.cu`'s
Welford block kernels): one VMEM-resident pass computes mean/rstd and the
normalized output per row tile, keeping the feature dim in lanes
(pallas_guide.md: last dim multiple of 128 maps onto the VPU lanes).

Gradient: custom_vjp that saves only x/w and recomputes the row statistics
in the backward (cheap bandwidth-bound reductions, XLA-fused) — the kernel
itself emits just the normalized output, which keeps its Mosaic layout
trivially valid (2-D blocks only) and avoids writing stats to HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)  # [block_rows, d]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    y = y * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _fwd_pallas(x2d, w, b, eps, block_rows=256):
    n, d = x2d.shape
    if n == 0:
        return _fwd_xla(x2d, w, b, eps)
    rows = min(block_rows, n)
    while n % rows:
        rows //= 2
    rows = max(rows, 1)
    grid = (n // rows,)
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2d.dtype),
        name="layer_norm",
    )(x2d, w, b)


def _fwd_xla(x2d, w, b, eps):
    x32 = x2d.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * w + b).astype(x2d.dtype)


def _use_pallas(d: int) -> bool:
    # opt-in: measured on v5e, XLA's own LN fusion is faster at common
    # shapes; the kernel is kept for the cases (very wide d, bf16 HBM
    # pressure) where explicit tiling wins — enable via the flag
    from ...core import flags as _flags

    if not _flags.flag("use_pallas_layernorm"):
        return False
    return jax.default_backend() == "tpu" and d % 128 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x2d, w, b, eps=1e-5):
    """x2d: [rows, d]; w/b: [d].  Returns normalized [rows, d]."""
    fwd = _fwd_pallas if _use_pallas(x2d.shape[-1]) else _fwd_xla
    return fwd(x2d, w, b, eps)


def _vjp_fwd(x2d, w, b, eps):
    fwd = _fwd_pallas if _use_pallas(x2d.shape[-1]) else _fwd_xla
    return fwd(x2d, w, b, eps), (x2d, w)


def _vjp_bwd(eps, res, g):
    x2d, w = res
    x32 = x2d.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x32 - mean) * rstd
    dw = jnp.sum(g32 * xhat, axis=0).astype(w.dtype)
    db = jnp.sum(g32, axis=0).astype(w.dtype)
    gy = g32 * w.astype(jnp.float32)
    dx = (gy - jnp.mean(gy, axis=-1, keepdims=True) -
          xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
    dx = (dx * rstd).astype(x2d.dtype)
    return dx, dw, db


fused_layer_norm.defvjp(_vjp_fwd, _vjp_bwd)
