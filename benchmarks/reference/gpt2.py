"""The plain reference: GPT-2 forward, loss, gradients and AdamW in
float32 `jax.numpy`, at `default_matmul_precision("highest")`.

Follows the published model (Radford et al. 2019; openai-community/gpt2
`config.json`): learned positions, pre-LayerNorm blocks, q|k|v in one
projection, causal softmax attention over heads of `n_embd / n_head`,
tanh-GELU MLP, final LayerNorm, the output head tied to the token
embedding, mean next-token cross entropy; AdamW as Loshchilov & Hutter
(decay decoupled, applied to every leaf).  No kernels, no cache, no
batching tricks.  It imports nothing of the program.

It is computed in blocks of rows (gradients accumulated), layer by layer
under `jax.checkpoint`, so that it fits beside nothing else on one chip.

`quant` is the control: the same mathematics with every matrix product's
two inputs rounded to a lower precision first ("bf16", "fp8" = float8
e4m3 with one scale a tensor, "int8" the same on 8-bit integers); with
"+stream" after it (as "fp8+stream") the residual stream is rounded too
after every addition, for a configuration that states its precision for
the stream as well.  The benchmark's runs never use it; `prove.py` and the
tests do.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _round(x, quant):
    """``x`` rounded to the control's precision, straight through: the
    rounding has no gradient of its own."""
    if quant is None:
        return x
    quant = quant.split("+")[0]
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if quant == "bf16":
        r = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif quant == "fp8":
        scale = 448.0 / amax  # the largest float8_e4m3fn
        r = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    elif quant == "int8":
        scale = 127.0 / amax
        r = jnp.round(x * scale) / scale
    else:
        raise ValueError(f"unknown control precision {quant!r}")
    return x + jax.lax.stop_gradient(r - x)


def _mm(a, b, quant):
    return jnp.matmul(_round(a, quant), _round(b, quant))


def _layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _stream(x, quant):
    return _round(x, quant) if quant and quant.endswith("+stream") else x


def _block(x, lw, n_head, quant):
    b, s, h = x.shape
    d = h // n_head
    y = _layer_norm(x, lw["ln1_g"], lw["ln1_b"])
    qkv = _mm(y, lw["qkv_w"], quant) + lw["qkv_b"]
    q, k, v = (qkv[..., i * h:(i + 1) * h].reshape(b, s, n_head, d)
               .transpose(0, 2, 1, 3) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", _round(q, quant),
                        _round(k, quant)) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bhqk,bhkd->bhqd", _round(probs, quant),
                      _round(v, quant))
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, h)
    x = _stream(x + _mm(attn, lw["proj_w"], quant) + lw["proj_b"], quant)
    y = _layer_norm(x, lw["ln2_g"], lw["ln2_b"])
    y = _gelu(_mm(y, lw["fc_w"], quant) + lw["fc_b"])
    return _stream(x + _mm(y, lw["fc2_w"], quant) + lw["fc2_b"], quant)


UNSTACKED = ("wte", "wpe", "lnf_g", "lnf_b", "lm_head")


def _stacked(w):
    return {k: v for k, v in w.items() if k not in UNSTACKED}


def hidden(w, tokens, n_head, quant=None):
    """Final-LayerNorm activations [B, S, h] of ``tokens`` [B, S]."""
    s = tokens.shape[1]
    x = _stream(w["wte"][tokens] + w["wpe"][:s], quant)
    body = jax.checkpoint(
        lambda x, lw: (_block(x, lw, n_head, quant), None))
    x, _ = jax.lax.scan(body, x, _stacked(w))
    return _layer_norm(x, w["lnf_g"], w["lnf_b"])


def logits(w, tokens, n_head, quant=None):
    """[B, S, padded vocabulary] float32 logits; the head is tied to
    `wte` unless the weights hold an `lm_head` of their own."""
    head = w["lm_head"] if "lm_head" in w else w["wte"].T
    return _mm(hidden(w, tokens, n_head, quant), head, quant)


def loss_sum(w, tokens, labels, n_head, quant=None):
    """Summed next-token cross entropy over every position of the block."""
    lg = logits(w, tokens, n_head, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


@functools.partial(jax.jit, static_argnames=("n_head", "quant"))
def _block_grads(w, tokens, labels, n_head, quant):
    return jax.value_and_grad(loss_sum)(w, tokens, labels, n_head, quant)


def loss_and_grads(w, tokens, labels, n_head, rows_per_block, quant=None,
                   rows=None):
    """Mean loss and its gradients over ``rows`` (default: all rows), the
    rows taken ``rows_per_block`` at a time."""
    rows = list(range(tokens.shape[0])) if rows is None else list(rows)
    total, grads = 0.0, None
    for i in range(0, len(rows), rows_per_block):
        idx = jnp.asarray(rows[i:i + rows_per_block])
        ls, g = _block_grads(w, tokens[idx], labels[idx], n_head, quant)
        total = total + ls
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    n = len(rows) * tokens.shape[1]
    return total / n, jax.tree_util.tree_map(lambda g: g / n, grads)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 2))
def adamw(w, grads, state, step, lr, b1, b2, eps, wd):
    """One AdamW update of every leaf; ``state`` is (m, v)."""
    m, v = state
    m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                               v, grads)
    c1, c2 = 1 - b1 ** step, 1 - b2 ** step

    def upd(p, m, v):
        return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps) - lr * wd * p

    return jax.tree_util.tree_map(upd, w, m, v), (m, v)


@jax.jit
def sgd(w, grads, lr):
    return jax.tree_util.tree_map(lambda p, g: p - lr * g, w, grads)


@jax.jit
def leaf_norms(tree):
    """L2 norm of every leaf; a leaf stacked over layers gives one norm a
    layer.  The q|k|v projection counts as three leaves (its key bias has
    no gradient under softmax, its query and value biases have)."""
    def norm(x, axes):
        return jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2, axis=axes))

    out = {}
    for k, x in tree.items():
        if k == "qkv_w":
            n = norm(x.reshape(x.shape[0], x.shape[1], 3, -1), (1, 3))
            out.update({f"{p}_w": n[:, i] for i, p in enumerate("qkv")})
        elif k == "qkv_b":
            n = norm(x.reshape(x.shape[0], 3, -1), (2,))
            out.update({f"{p}_b": n[:, i] for i, p in enumerate("qkv")})
        elif k in UNSTACKED:
            out[k] = norm(x, None)
        else:
            out[k] = norm(x, tuple(range(1, x.ndim)))
    return out


@jax.jit
def _diff(a, b):
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def train_readings(w, batches, n_head, optim: dict, rows_per_block,
                   quant=None, rows=None, steps=3):
    """Follow the first ``steps`` train steps from weights ``w`` (consumed)
    on ``batches`` = [(tokens, labels), ...].  Returns the losses, the
    leaf norms of the first gradient, and of the parameters' change."""
    with jax.default_matmul_precision("highest"):
        w0 = jax.tree_util.tree_map(jnp.copy, w)
        state = None
        if optim["name"] == "adamw":
            zeros = jax.tree_util.tree_map(jnp.zeros_like, w)
            state = (zeros, jax.tree_util.tree_map(jnp.zeros_like, w))
        losses, grad_norms = [], None
        for i in range(steps):
            tokens, labels = batches[i]
            loss, grads = loss_and_grads(w, tokens, labels, n_head,
                                         rows_per_block, quant, rows)
            losses.append(float(loss))
            if optim["name"] == "adamw":
                if i == 0:
                    grad_norms = jax.device_get(leaf_norms(grads))
                w, state = adamw(w, grads, state, i + 1, optim["lr"],
                                 optim["beta1"], optim["beta2"],
                                 optim["eps"], optim["weight_decay"])
            elif optim["name"] == "sgd":
                w = sgd(w, grads, optim["lr"])
                if i == 0:
                    # SGD keeps no state but the weights: the gradient as
                    # the optimizer got it is the first step's change over
                    # the learning rate, read on both sides alike (a gain
                    # of 1.0 moves by about one float32 step at a time)
                    grad_norms = {k: v / optim["lr"] for k, v in
                                  jax.device_get(leaf_norms(
                                      _diff(w, w0))).items()}
            else:
                raise ValueError(f"unknown optimizer {optim['name']!r}")
        change_norms = jax.device_get(leaf_norms(_diff(w, w0)))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms}


@functools.partial(jax.jit, static_argnames=("n_head", "quant", "vocab"))
def _gaps_jit(w, ids, targets, n_head, quant, vocab):
    lg = logits(w, ids, n_head, None)[0, :, :vocab]
    if quant is None:
        tok = targets[0]
    else:
        tok = jnp.argmax(logits(w, ids, n_head, quant)[0, :, :vocab], -1)
    got = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    return jnp.max(lg, axis=-1) - got


def served_gaps(w, prompt, served, n_head, vocab, quant=None, pad_to=None):
    """One pass over ``prompt + served``: at each served position, how far
    the served token's logit lies below the reference's best (>= 0), over
    the first ``vocab`` ids.  With ``quant``, the token judged at each
    position is the one the lower precision puts first instead of the
    served one.  ``pad_to`` pads the sequence (the mask is causal, so what
    follows a position cannot reach it) so that every length shares one
    compiled program."""
    seq = list(prompt) + list(served)
    first, n = len(prompt) - 1, len(served)
    pad = [0] * max((pad_to or 0) - (len(seq) - 1), 0)
    ids = jnp.asarray(seq[:-1] + pad, jnp.int32)[None]
    targets = jnp.asarray(seq[1:] + pad, jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        gaps = jax.device_get(_gaps_jit(w, ids, targets, n_head, quant,
                                        vocab))
    return gaps[first:first + n]
