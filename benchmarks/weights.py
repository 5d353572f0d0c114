"""GPT-2 weights from a seed, made on the device in one jitted call.

The benchmark owns its weights: the runner loads them into the program and
the plain reference makes the same ones from the same seed, so neither takes
anything the other has made.  Layout follows the published GPT-2 checkpoint
(`c_attn` is q|k|v, weights are [in, out]), with the per-layer leaves
stacked on a leading layer axis.  Init is the GPT-2 convention: N(0, 0.02)
for embeddings and matrices, N(0, 0.02 / sqrt(2 L)) for the two residual
projections, unit LayerNorm gains, zero biases.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

STACKED = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
           "ln2_g", "ln2_b", "fc_w", "fc_b", "fc2_w", "fc2_b")


def key_of(seed: int, stream: int = 0):
    """A threefry key from any whole-number seed (the driver's pass 2**31)
    and a stream number that keeps weights, data and samples apart."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must not be negative, got {seed}")
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     dtype=np.uint32)
    return jax.random.fold_in(jax.random.wrap_key_data(jnp.asarray(words)),
                              stream)


def shapes(cfg: dict) -> dict:
    """Leaf name -> shape for a configuration file's keys."""
    h, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg.get("n_inner") or 4 * h
    vocab = cfg["padded_vocab_size"]
    head = {} if cfg.get("tie_word_embeddings", True) else \
        {"lm_head": (h, vocab)}  # an output head of its own
    return {
        **head,
        "wte": (vocab, h), "wpe": (cfg["n_positions"], h),
        "ln1_g": (layers, h), "ln1_b": (layers, h),
        "qkv_w": (layers, h, 3 * h), "qkv_b": (layers, 3 * h),
        "proj_w": (layers, h, h), "proj_b": (layers, h),
        "ln2_g": (layers, h), "ln2_b": (layers, h),
        "fc_w": (layers, h, inner), "fc_b": (layers, inner),
        "fc2_w": (layers, inner, h), "fc2_b": (layers, h),
        "lnf_g": (h,), "lnf_b": (h,),
    }


def param_count(cfg: dict) -> int:
    return sum(math.prod(s) for s in shapes(cfg).values())


def _init(key, shp: dict, n_layer: int, dtype):
    out = {}
    resid = 0.02 / math.sqrt(2 * n_layer)
    for i, (name, shape) in enumerate(sorted(shp.items())):
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith("_b"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            std = resid if name in ("proj_w", "fc2_w") else 0.02
            out[name] = (std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


@functools.lru_cache(maxsize=None)
def _jitted_init(shape_items, n_layer, dtype):
    shp = dict(shape_items)
    return jax.jit(lambda key: _init(key, shp, n_layer, dtype))


def init_weights(cfg: dict, seed: int, dtype=jnp.float32) -> dict:
    """All weights of ``cfg`` from ``seed``: one jitted call, on the
    default device, in ``dtype``."""
    shp = shapes(cfg)
    fn = _jitted_init(tuple(sorted(shp.items())), cfg["n_layer"], dtype)
    return fn(key_of(seed, 0))


def init_fn(cfg: dict, dtype=jnp.float32):
    """The traceable init (key -> weights), for callers that want it inside
    a jitted function of their own."""
    shp = shapes(cfg)
    return lambda key: _init(key, shp, cfg["n_layer"], dtype)
