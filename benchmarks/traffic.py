"""The one traffic generator: a cell's `traffic` parameters and a seed in,
the inputs of a run out.  A new mix is a new data file, never new code.

Two kinds:

* ``train_batches`` — a ring of ``ring`` batches of ``batch`` rows of
  ``seq`` tokens (labels are the next token), made on the device in one
  jitted call; every row differs.
* ``open_loop`` — requests on a schedule that does not wait for answers.
  Every seed gets the same multiset of gaps between arrivals and the same
  multisets of prompt and output lengths (the quantiles of the stated
  distributions, mid-point rule), each in another order: the seed moves the
  work about and never changes its amount.  A mix that states `order_seed`
  fixes the order too and leaves the seed the token ids: a tail over some
  tens of requests swings with which long prompts meet, and a replayed
  schedule keeps that out of the spread between runs.
"""
from __future__ import annotations

import functools
import math
from statistics import NormalDist

import numpy as np


# ---------------------------------------------------------------- training
@functools.lru_cache(maxsize=None)
def _jitted_batches(ring, batch, seq, vocab):
    import jax

    def make(key):
        rows = jax.random.randint(key, (ring, batch, seq + 1), 0, vocab,
                                  dtype="int32")
        return [(rows[i, :, :-1], rows[i, :, 1:]) for i in range(ring)]
    return jax.jit(make)


def train_batches(traffic: dict, vocab: int, seed: int):
    """``ring`` pairs (tokens, labels), each [batch, seq] int32 on the
    device."""
    from benchmarks.weights import key_of

    fn = _jitted_batches(traffic["ring"], traffic["batch"], traffic["seq"],
                         vocab)
    return fn(key_of(seed, 1))


# --------------------------------------------------------------- open loop
def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n mid-point quantiles of the stated distribution, clipped, whole."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif spec["dist"] == "fixed":
        vals = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(vals), spec.get("min", 1),
                   spec.get("max", np.inf)).astype(np.int64)


def _gaps(traffic: dict, n: int) -> np.ndarray:
    rate = traffic["rate_per_s"]
    u = (np.arange(n) + 0.5) / n
    if traffic["arrivals"] == "poisson":
        return -np.log1p(-u) / rate
    if traffic["arrivals"] == "uniform":
        return np.full(n, 1.0 / rate)
    raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")


def open_loop(traffic: dict, vocab: int, seed: int, seconds: float) -> list:
    """The requests due in a window of ``seconds``: dicts with ``due_s``
    (from the window's start), ``prompt`` (token ids), ``max_new_tokens``
    and ``greedy``.  Ids avoid the first four, as special tokens would."""
    n = int(math.floor(traffic["rate_per_s"] * seconds))
    if n < 1:
        raise ValueError("the window holds no request at this rate")
    ids = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 7])
    # a mix that states `order_seed` replays one fixed schedule (which gap
    # and which lengths go together) and the run's seed draws the token
    # ids alone; without it the run's seed draws the order too
    rng = ids if traffic.get("order_seed") is None else \
        np.random.default_rng([int(traffic["order_seed"]), 7])
    # the first request is due at the window's start and the last at the
    # same moment for every seed: the n - 1 gaps are one multiset
    due = np.concatenate([[0.0], np.cumsum(
        rng.permutation(_gaps(traffic, n - 1)))]) if n > 1 else np.zeros(1)
    due *= min(1.0, (seconds * (n - 1) / n) / max(due[-1], 1e-9))
    plens = rng.permutation(_quantiles(traffic["prompt_tokens"], n))
    olens = rng.permutation(_quantiles(traffic["output_tokens"], n))
    shared = int(traffic.get("shared_prefix_tokens", 0))
    prefix = ids.integers(4, vocab, shared).tolist()
    out = []
    for t, p, o in zip(due, plens, olens):
        body = ids.integers(4, vocab, max(int(p) - shared, 1)).tolist()
        out.append({"due_s": float(t), "prompt": prefix + body,
                    "max_new_tokens": int(o), "greedy": True})
    return out
