"""jit: whole-program capture and compilation.

Reference: the dygraph→static stack — `ProgramTranslator`/`StaticFunction`
(`fluid/dygraph/dygraph_to_static/program_translator.py:759,232`),
`PartialProgramLayer` running the captured program as one `run_program` op
(`partial_program.py:110`), and `paddle.jit.save/load` (`fluid/dygraph/jit.py`).

TPU-native design (SURVEY.md §7 idiom table row 1): instead of AST rewriting
into a ProgramDesc, the python function is traced with JAX abstract values —
Layer parameters are temporarily rebound to tracers, ops skip the eager tape,
and the result is a pure function ``f(params, buffers, rng, *inputs)``
compiled once per input signature by `jax.jit` and cached.  The compiled
callable is itself dispatched as ONE eager op, so `.backward()` still works
through it (the whole model becomes a single tape node — the generalization
of the reference's run_program op, which appends its backward the same way,
`partial_program.py:177`).

`TrainStep` goes further and stages forward+backward+optimizer into a single
donated XLA executable — the benchmark hot path.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import framework
from ..core.dispatch import dispatch
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from ..static.input_spec import InputSpec


def _tree_arrays(x):
    return jax.tree_util.tree_map(
        lambda t: t._array if isinstance(t, Tensor) else t, x
    )


class _SwappedState:
    """Temporarily rebind Layer params/buffers to given arrays (tracers)."""

    def __init__(self, tensors: Dict[str, Tensor]):
        self.tensors = tensors
        self._saved = {}

    def __enter__(self):
        self._saved = {k: t._array for k, t in self.tensors.items()}
        return self

    def bind(self, arrays: Dict[str, Any]):
        for k, t in self.tensors.items():
            if k in arrays:
                t._array = arrays[k]

    def __exit__(self, *exc):
        for k, t in self.tensors.items():
            t._array = self._saved[k]
        return False


class StaticFunction:
    """Compiled-function cache keyed by input signature (reference
    `ProgramCache` `program_translator.py:692`)."""

    def __init__(self, function: Callable, layer: Optional[Layer] = None,
                 input_spec=None):
        self._function = function
        self._layer = layer
        self._input_spec = input_spec
        self._compiled = {}
        functools.update_wrapper(self, function)

    @property
    def concrete_programs(self):
        return list(self._compiled.values())

    def _get_state(self) -> Tuple[Dict[str, Tensor], Dict[str, Tensor]]:
        if self._layer is None:
            return {}, {}
        return self._layer.functional_state()

    def __call__(self, *args, **kwargs):
        params, buffers = self._get_state()
        in_tensors = [a for a in args if isinstance(a, Tensor)]
        in_arrays = [t._array for t in in_tensors]
        static_args = tuple(
            a if not isinstance(a, Tensor) else None for a in args
        )

        pnames = sorted(params)
        bnames = sorted(buffers)

        sig = (
            tuple((a.shape, str(a.dtype)) for a in in_arrays),
            static_args,
            tuple(kwargs.items()) if kwargs else (),
            bool(self._layer.training) if self._layer is not None else None,
        )
        entry = self._compiled.get(sig)
        if entry is None:
            try:
                entry = self._build(args, kwargs, params, buffers, pnames,
                                    bnames)
            except jax.errors.ConcretizationTypeError as e:
                # data-dependent Python control flow (`if tensor:` /
                # tensor-bounded loop): fall back to the AST pass that
                # lowers it onto ops.cond/while_loop (reference
                # ProgramTranslator, dygraph_to_static/
                # program_translator.py:759), then retrace
                from .dy2static import ast_transform

                transformed = ast_transform(self._function)
                if transformed is None:
                    raise
                self._function = transformed
                try:
                    entry = self._build(args, kwargs, params, buffers,
                                        pnames, bnames)
                except jax.errors.ConcretizationTypeError:
                    raise e from None
            self._compiled[sig] = entry
        jitted, buf_targets = entry

        parrs = [params[k]._array for k in pnames]
        barrs = [buffers[k]._array for k in bnames]
        rng = framework.make_rng_key(0) if framework.in_trace() else framework.default_generator.next_key()

        n_out = [None]

        def run(*flat):
            # flat = (*parrs, *in_arrays) ; barrs+rng closed over via jit args
            return jitted(flat[: len(pnames)], flat[len(pnames):], barrs, rng)

        outs_and_writes = dispatch(run, *[params[k] for k in pnames], *in_tensors)
        if not isinstance(outs_and_writes, tuple):
            outs_and_writes = (outs_and_writes,)
        # split: the last len(buf_targets) outputs are buffer writes
        nb = len(buf_targets)
        outs = outs_and_writes[: len(outs_and_writes) - nb]
        writes = outs_and_writes[len(outs_and_writes) - nb:] if nb else ()
        with framework.no_grad_guard():
            for tgt, w in zip(buf_targets, writes):
                tgt._array = w._array if isinstance(w, Tensor) else w
        if len(outs) == 1:
            return outs[0]
        return outs

    def _build(self, args, kwargs, params, buffers, pnames, bnames):
        tensor_positions = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
        const_args = list(args)
        layer = self._layer
        function = self._function
        buf_tensors = [buffers[k] for k in bnames]
        buf_targets_holder: List[Tensor] = []

        def pure(parrs, in_arrays, barrs, rng):
            writes: Dict[int, Any] = {}
            call_args = list(const_args)
            for pos, arr in zip(tensor_positions, in_arrays):
                call_args[pos] = Tensor(arr)
            swap_map = {k: params[k] for k in pnames}
            swap_map.update({f"__buf__{k}": buffers[k] for k in bnames})
            with _SwappedState(swap_map) as sw:
                sw.bind({k: a for k, a in zip(pnames, parrs)})
                sw.bind({f"__buf__{k}": a for k, a in zip(bnames, barrs)})
                with framework.trace_guard(rng_key=rng, writes=writes):
                    out = function(*call_args, **kwargs)
            flat_out = out if isinstance(out, (list, tuple)) else (out,)
            out_arrays = tuple(
                o._array if isinstance(o, Tensor) else jnp.asarray(o)
                for o in flat_out
            )
            # ordered buffer writes: only for known buffer tensors
            buf_targets_holder.clear()
            write_arrays = []
            for t in buf_tensors:
                if id(t) in writes:
                    buf_targets_holder.append(t)
                    write_arrays.append(writes[id(t)])
            return out_arrays + tuple(write_arrays)

        jitted = jax.jit(pure)
        # trigger trace once to discover buffer writes (fills holder)
        parrs = [params[k]._array for k in pnames]
        barrs = [buffers[k]._array for k in bnames]
        in_arrays = [args[i]._array for i in tensor_positions]
        _ = jitted.lower(parrs, in_arrays, barrs, framework.make_rng_key(0))
        return jitted, list(buf_targets_holder)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator/wrapper (reference `paddle.jit.to_static`)."""

    def wrap(fn):
        if isinstance(fn, Layer):
            sf = StaticFunction(fn.forward, layer=fn, input_spec=input_spec)
            fn.forward = sf
            return fn
        layer = kwargs.get("layer")
        if layer is None and hasattr(fn, "__self__") and isinstance(fn.__self__, Layer):
            layer = fn.__self__
        return StaticFunction(fn, layer=layer, input_spec=input_spec)

    if function is not None:
        return wrap(function)
    return wrap


declarative = to_static


def not_to_static(fn):
    fn._paddle_not_to_static = True
    return fn


class TrainStep:
    """Fused forward+backward+optimizer step compiled to one XLA executable
    with donated params/opt-state (the TPU replacement for the reference's
    per-op dygraph training loop)."""

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 donate: bool = True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._compiled = None
        self._step = 0
        params, buffers = model.functional_state()
        self._pnames = sorted(params)
        self._bnames = sorted(buffers)
        self._params = params
        self._buffers = buffers
        self._opt_state = None
        self._donate = donate
        self._buf_order: List[str] = []

    def _build(self):
        model, loss_fn, optimizer = self.model, self.loss_fn, self.optimizer
        params, buffers = self._params, self._buffers
        pnames, bnames = self._pnames, self._bnames
        buf_order_holder = self._buf_order
        from ..optimizer.optimizer import collect_lr_mults
        lr_mults = collect_lr_mults(params)

        def pure(parr: Dict[str, Any], opt_state, barr: Dict[str, Any], lr,
                 step, rng, batch):
            def loss_of(pa):
                writes: Dict[int, Any] = {}
                swap = {k: params[k] for k in pnames}
                swap.update({f"__buf__{k}": buffers[k] for k in bnames})
                with _SwappedState(swap) as sw:
                    sw.bind(pa)
                    sw.bind({f"__buf__{k}": barr[k] for k in bnames})
                    with framework.trace_guard(rng_key=rng, writes=writes):
                        batch_t = [Tensor(b) for b in batch]
                        loss = loss_fn(model, *batch_t)
                loss_arr = loss._array if isinstance(loss, Tensor) else loss
                buf_order_holder.clear()
                wmap = {}
                for k in bnames:
                    t = buffers[k]
                    if id(t) in writes:
                        buf_order_holder.append(k)
                        wmap[k] = writes[id(t)]
                return loss_arr.astype(jnp.float32), wmap

            (loss, wmap), grads = jax.value_and_grad(loss_of, has_aux=True)(parr)
            new_params, new_opt = optimizer.apply_gradients(
                parr, grads, opt_state, lr, step, lr_mults=lr_mults
            )
            new_bufs = dict(barr)
            new_bufs.update(wmap)
            return loss, new_params, new_opt, new_bufs

        donate = (1, 2) if self._donate else ()
        return jax.jit(pure, donate_argnums=donate)

    def _operands(self, batch, step, rng):
        """The jitted step's operand tuple for ``batch`` (builds the
        step function and the optimizer state on first use)."""
        if self._compiled is None:
            self._compiled = self._build()
        if self._opt_state is None:
            self._opt_state = self.optimizer.init_state(self._params)
        parr = {k: self._params[k]._array for k in self._pnames}
        barr = {k: self._buffers[k]._array for k in self._bnames}
        batch_arrs = [b._array if isinstance(b, Tensor) else jnp.asarray(b)
                      for b in batch]
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        return (parr, self._opt_state, barr, lr, step, rng,
                tuple(batch_arrs))

    def lower(self, *batch):
        """Lower the step against ``batch`` without running it or moving
        the step/RNG state: ``.compile()`` of the result gives the
        executable's HLO text and memory analysis."""
        ops = self._operands(batch, self._step + 1,
                             framework.make_rng_key(0))
        return self._compiled.lower(*ops)

    def __call__(self, *batch) -> Tensor:
        # three host spans in the profiler's trace (free while none is
        # taken): the Python walk over the parameters before and after
        # the one enqueue, which a device trace otherwise shows as one
        # unnamed gap
        span = jax.profiler.TraceAnnotation
        self._step += 1
        with span("train_step.operands", step=self._step):
            ops = self._operands(batch, self._step,
                                 framework.default_generator.next_key())
        with span("train_step.enqueue", step=self._step):
            loss, new_params, new_opt, new_bufs = self._compiled(*ops)
        with span("train_step.rebind", step=self._step):
            with framework.no_grad_guard():
                for k in self._pnames:
                    self._params[k]._array = new_params[k]
                for k in self._bnames:
                    self._buffers[k]._array = new_bufs[k]
            self._opt_state = new_opt
        return Tensor(loss)


def train_step(model, loss_fn, optimizer, donate=True):
    return TrainStep(model, loss_fn, optimizer, donate)


# ---------------------------------------------------------------------------
# save / load — deployment format (reference `paddle.jit.save/load`,
# `fluid/dygraph/jit.py:515,851`).  The portable program format is
# jax.export's serialized StableHLO plus a numpy state dict, replacing the
# reference's ProgramDesc+params files.
# ---------------------------------------------------------------------------
def save(layer, path, input_spec=None, **config):
    import os
    import pickle

    import numpy as np

    from jax import export as jexport

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    params, buffers = layer.functional_state()
    pnames, bnames = sorted(params), sorted(buffers)

    if input_spec is None:
        raise ValueError("paddle_tpu.jit.save requires input_spec")
    specs = []
    for s in input_spec:
        if isinstance(s, InputSpec):
            shape = [1 if d is None or d < 0 else d for d in s.shape]
            specs.append(jax.ShapeDtypeStruct(tuple(shape), s.dtype))
        elif isinstance(s, Tensor):
            specs.append(jax.ShapeDtypeStruct(s._array.shape, s._array.dtype))

    was_training = layer.training
    layer.eval()

    def infer(parrs, barrs, *inputs):
        swap = {k: params[k] for k in pnames}
        swap.update({f"__buf__{k}": buffers[k] for k in bnames})
        with _SwappedState(swap) as sw:
            sw.bind({k: a for k, a in zip(pnames, parrs)})
            sw.bind({f"__buf__{k}": a for k, a in zip(bnames, barrs)})
            with framework.trace_guard(rng_key=framework.make_rng_key(0), writes={}):
                out = layer(*[Tensor(i) for i in inputs])
        outs = out if isinstance(out, (list, tuple)) else (out,)
        return tuple(o._array for o in outs)

    parr_specs = [jax.ShapeDtypeStruct(params[k]._array.shape, params[k]._array.dtype) for k in pnames]
    barr_specs = [jax.ShapeDtypeStruct(buffers[k]._array.shape, buffers[k]._array.dtype) for k in bnames]
    exported = jexport.export(jax.jit(infer))(parr_specs, barr_specs, *specs)
    blob = exported.serialize()

    state = {k: np.asarray(params[k]._array) for k in pnames}
    bufs = {k: np.asarray(buffers[k]._array) for k in bnames}
    with open(path + ".pdmodel", "wb") as f:
        f.write(blob)
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump({"params": state, "buffers": bufs,
                     "pnames": pnames, "bnames": bnames}, f)
    if was_training:
        layer.train()


class TranslatedLayer(Layer):
    """Deserialized deployable module (reference TranslatedLayer,
    `fluid/dygraph/io.py`)."""

    def __init__(self, exported, params, buffers, pnames, bnames):
        super().__init__()
        self._exported = exported
        self._pnames = pnames
        self._bnames = bnames
        from ..nn.layer.layers import Parameter

        for k in pnames:
            self.add_parameter(k.replace(".", "__"), Parameter(params[k]))
        for k in bnames:
            self.register_buffer(k.replace(".", "__"), Tensor(buffers[k]))
        self._param_map = {k: self._parameters[k.replace(".", "__")] for k in pnames}
        self._buf_map = {k: self._buffers[k.replace(".", "__")] for k in bnames}

    def forward(self, *inputs):
        parrs = [self._param_map[k]._array for k in self._pnames]
        barrs = [self._buf_map[k]._array for k in self._bnames]
        in_arrs = [i._array if isinstance(i, Tensor) else jnp.asarray(i)
                   for i in inputs]
        outs = self._exported.call(parrs, barrs, *in_arrs)
        outs = tuple(Tensor(o) for o in outs)
        return outs[0] if len(outs) == 1 else outs


def load(path, **config):
    import pickle

    from jax import export as jexport

    with open(path + ".pdmodel", "rb") as f:
        exported = jexport.deserialize(bytearray(f.read()))
    with open(path + ".pdiparams", "rb") as f:
        meta = pickle.load(f)
    return TranslatedLayer(exported, meta["params"], meta["buffers"],
                           meta["pnames"], meta["bnames"])


class TracedLayer:
    """reference `fluid/dygraph/jit.py:49` TracedLayer (trace+run)."""

    def __init__(self, static_fn, layer):
        self._fn = static_fn
        self._layer = layer

    @staticmethod
    def trace(layer, inputs):
        sf = StaticFunction(layer.forward, layer=layer)
        out = sf(*inputs)
        return out, TracedLayer(sf, layer)

    def __call__(self, *inputs):
        return self._fn(*inputs)
