"""Process-global framework state: grad mode, trace mode, RNG.

The reference keeps equivalent state in `imperative::Tracer` (has_grad flag,
`imperative/tracer.cc:144`) and the dygraph/static mode switch in
`python/paddle/fluid/framework.py`.  Here there are two orthogonal modes:

* **grad mode** — whether eager ops record onto the autograd tape
  (`no_grad` disables, like `tracer.has_grad=False`).
* **trace mode** — set while a `to_static`/jit trace is being captured.  In
  trace mode ops do NOT build the eager tape (gradients come from `jax.grad`
  over the captured pure function) and randomness draws from an explicitly
  threaded key so the captured program is a pure function.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np

from . import flags as _flags


class _State(threading.local):
    def __init__(self):
        self.grad_enabled = True
        self.trace_mode = False
        self.trace_rng_key = None  # threaded PRNG key during jit tracing
        # buffer mutations captured during a trace (id(tensor) -> traced array)
        # so that e.g. BatchNorm running-stat updates become explicit outputs
        # of the compiled program instead of leaking tracers (reference:
        # batch_norm_op writes MeanOut/VarianceOut in-kernel).
        self.trace_writes = None
        self.amp_enabled = False
        self.amp_dtype = None
        self.amp_level = "O1"


_state = _State()


def grad_enabled() -> bool:
    return _state.grad_enabled and not _state.trace_mode


def in_trace() -> bool:
    return _state.trace_mode


@contextlib.contextmanager
def no_grad_guard():
    prev = _state.grad_enabled
    _state.grad_enabled = False
    try:
        yield
    finally:
        _state.grad_enabled = prev


@contextlib.contextmanager
def enable_grad_guard():
    prev = _state.grad_enabled
    _state.grad_enabled = True
    try:
        yield
    finally:
        _state.grad_enabled = prev


@contextlib.contextmanager
def trace_guard(rng_key=None, writes=None):
    prev = (_state.trace_mode, _state.trace_rng_key, _state.trace_writes)
    _state.trace_mode = True
    _state.trace_rng_key = rng_key
    _state.trace_writes = writes if writes is not None else {}
    try:
        yield
    finally:
        _state.trace_mode, _state.trace_rng_key, _state.trace_writes = prev


def record_trace_write(tensor, array):
    if _state.trace_writes is not None:
        _state.trace_writes[id(tensor)] = array
        return True
    return False


def get_trace_write(tensor):
    if _state.trace_writes is not None:
        return _state.trace_writes.get(id(tensor))
    return None


# ---------------------------------------------------------------------------
# RNG.  Eager mode: a stateful splitting generator (paddle.seed semantics).
# Trace mode: keys are split off the threaded trace key so that the captured
# program stays pure (a fresh key is fed per invocation by the jit wrapper).
# ---------------------------------------------------------------------------
_RNG_IMPL = None


def _rng_impl() -> str:
    """Framework PRNG impl, decided once at first key creation (NOT at
    import — probing the backend at import would force JAX backend init as
    a side effect of `import paddle_tpu`): the hardware RBG generator on
    TPU (threefry mask generation measurably slows dropout-bearing train
    steps — ViT-B/16 630 -> 719 imgs/s switching to rbg, round-3 probe),
    threefry elsewhere.  Only paddle_tpu's own keys are affected; the
    process-global jax default impl is never touched."""
    global _RNG_IMPL
    if _RNG_IMPL is None:
        on_tpu = _flags.flag("use_rbg_rng") and \
            jax.default_backend() == "tpu"
        _RNG_IMPL = "rbg" if on_tpu else "threefry2x32"
    return _RNG_IMPL


def make_rng_key(seed: int = 0):
    """Typed PRNG key with the framework's impl (see `_rng_impl`).  All
    key-creation sites that feed the jit trace machinery must use this so
    trace-time and run-time keys agree in impl and shape."""
    return jax.random.key(int(seed), impl=_rng_impl())


class Generator:
    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        self._key = None  # created lazily via make_rng_key

    def seed(self, seed: int):
        self._seed = int(seed)
        self._key = None

    def next_key(self):
        if _state.trace_mode:
            if _state.trace_rng_key is None:
                raise RuntimeError(
                    "random op inside a jit trace but no rng key was threaded; "
                    "call the compiled function through paddle_tpu.jit"
                )
            _state.trace_rng_key, sub = jax.random.split(_state.trace_rng_key)
            return sub
        if self._key is None:
            self._key = make_rng_key(self._seed)
        self._key, sub = jax.random.split(self._key)
        return sub


default_generator = Generator(np.random.SeedSequence().entropy % (2**31))


def seed(s: int):
    default_generator.seed(int(s))
    return default_generator


def get_rng_key():
    return default_generator.next_key()


# AMP state accessors (used by core.dispatch autocast and paddle_tpu.amp)
def amp_state():
    return _state


def amp_sig():
    """(enabled, compute_dtype) pair for dispatch cache keying: the
    autocast white/black-list pass is folded into the cached traced
    computation (core/dispatch.py), so the AMP state must be part of the
    executable cache key rather than a per-call Python pass."""
    return _state.amp_enabled, _state.amp_dtype
