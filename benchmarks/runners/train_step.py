"""Runner `train_step`: one chip, `paddle_tpu.jit.train_step` on a GPT-2
configuration — forward, flash-attention backward and the optimizer update
as the program's one donated executable.

Set-up builds ONE step object, loads the seed's weights into it, drives it
through its first three steps by the window's own call and feed, and hands
that same object to the window.  What those three steps did (each loss, the
norm of the first gradient as the optimizer got it, the norm of the
parameters' change) is compared with the plain reference once the window
has closed, the peak memory has been read and the program's state is freed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import compare, harness, traffic, train_common, weights
from benchmarks.train_common import CHECKED_STEPS

# the program's parameter names for the reference's stacked leaves
BLOCK_NAMES = {"ln1_g": "ln1.weight", "ln1_b": "ln1.bias",
               "qkv_w": "qkv.weight", "qkv_b": "qkv.bias",
               "proj_w": "out_proj.weight", "proj_b": "out_proj.bias",
               "ln2_g": "ln2.weight", "ln2_b": "ln2.bias",
               "fc_w": "fc1.weight", "fc_b": "fc1.bias",
               "fc2_w": "fc2.weight", "fc2_b": "fc2.bias"}
TOP_NAMES = {"wte": "wte.weight", "wpe": "wpe.weight",
             "lnf_g": "ln_f.weight", "lnf_b": "ln_f.bias"}


def leaf_name(program_name: str) -> str:
    """The reference's flat leaf for a program parameter; the q|k|v
    projection gives a stem that `split_norms` makes three leaves of."""
    if program_name in _TOP:
        return _TOP[program_name]
    _, layer, rest = program_name.split(".", 2)
    return f"{_BLOCK[rest]}.{layer}"


_TOP = {v: k for k, v in TOP_NAMES.items()}
_BLOCK = {v: k for k, v in BLOCK_NAMES.items()}


def split_norms(tree: dict) -> dict:
    """Traceable: program parameter name -> array in, the reference's flat
    leaf name -> L2 norm out (q|k|v as three leaves, as the reference)."""
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))

    out = {}
    for name, x in tree.items():
        leaf = leaf_name(name)
        if leaf.startswith("qkv_"):
            kind, layer = leaf[4:].split(".")  # "w" or "b", layer
            parts = x.reshape(x.shape[:-1] + (3, x.shape[-1] // 3))
            for i, p in enumerate("qkv"):
                out[f"{p}_{kind}.{layer}"] = norm(parts[..., i, :])
        else:
            out[leaf] = norm(x)
    return out


def to_program(w: dict) -> dict:
    """The reference's stacked weights under the program's names."""
    out = {v: w[k] for k, v in TOP_NAMES.items()}
    for leaf, name in BLOCK_NAMES.items():
        for i in range(w[leaf].shape[0]):
            out[f"blocks.{i}.{name}"] = w[leaf][i]
    return out


def build_model(cfg: dict):
    """The program's GPT for a configuration file's keys."""
    from paddle_tpu.models.gpt import GPT, GPTConfig

    return GPT(GPTConfig(
        vocab_size=cfg["padded_vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        max_seq_len=cfg["n_positions"],
        intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"],
        use_parallel_layers=False))


def build(cfg: dict, optim: dict, autocast):
    """The program's model and its fused train step, as `bench.py` and
    `chip_smoke.py` build them."""
    from paddle_tpu import amp, jit, nn, optimizer

    model = build_model(cfg)
    if optim["name"] != "adamw":
        raise ValueError(f"runner train_step drives AdamW, not "
                         f"{optim['name']!r}")
    opt = optimizer.AdamW(learning_rate=optim["lr"], beta1=optim["beta1"],
                          beta2=optim["beta2"], epsilon=optim["eps"],
                          weight_decay=optim["weight_decay"],
                          parameters=model.parameters())

    def loss_fn(m, tokens, labels):
        with amp.auto_cast(enable=autocast is not None,
                           dtype=autocast or "bfloat16"):
            logits = m(tokens)
        return nn.functional.cross_entropy(logits, labels, reduction="mean")

    return model, jit.train_step(model, loss_fn, opt)


def load_weights(params: dict, cfg: dict, seed: int):
    """The seed's weights, made in one jitted call under the program's
    names, into ``params`` (name -> the program's tensor)."""
    import jax

    init = weights.init_fn(cfg)
    arrays = jax.jit(lambda key: to_program(init(key)))(
        weights.key_of(seed, 0))
    for name, tensor in params.items():
        if tensor._array.shape != arrays[name].shape:
            raise RuntimeError(f"{name}: program {tensor._array.shape}, "
                               f"benchmark {arrays[name].shape}")
        tensor._array = arrays[name]


def first_gradient_norms(step, beta1: float) -> dict:
    """Leaf norms of the first gradient as the optimizer got it: Adam's
    first moment after one step is (1 - beta1) times it."""
    import jax

    moments = {k: v["moment1"] for k, v in step._opt_state.items()}
    norms = jax.device_get(jax.jit(split_norms)(moments))
    return {k: float(v) / (1 - beta1) for k, v in norms.items()}


def change_norms(step, cfg: dict, seed: int) -> dict:
    """Leaf norms of (parameters now - the seed's weights), the latter made
    anew inside the same jitted call instead of kept."""
    import jax

    init = weights.init_fn(cfg)

    def norms(params, key):
        start = to_program(init(key))
        return split_norms({k: params[k] - start[k] for k in params})

    now = {k: t._array for k, t in step._params.items()}
    return {k: float(v) for k, v in jax.device_get(
        jax.jit(norms)(now, weights.key_of(seed, 0))).items()}


def reference_readings(cell, seed: int, quant=None, rows=None) -> dict:
    """The reference's own three steps from the seed, flat leaf names."""
    from benchmarks.reference import gpt2

    cfg = cell.config
    w = weights.init_weights(cfg, seed)
    batches = traffic.train_batches(cell.traffic, cfg["vocab_size"], seed)
    r = gpt2.train_readings(w, batches[:CHECKED_STEPS], cfg["n_head"],
                            cell.spec["optimizer"],
                            cell.spec["reference"]["rows_per_block"],
                            quant=quant, rows=rows, steps=CHECKED_STEPS)
    return {"losses": r["losses"],
            "grad_norms": compare.flat_norms(r["grad_norms"]),
            "change_norms": compare.flat_norms(r["change_norms"])}


def program_readings(step, feed, cell, seed: int) -> dict:
    """Drive ``step`` through its first three steps and read what the
    comparison wants, under the reference's leaf names."""
    losses, grad = [], None
    for i in range(CHECKED_STEPS):
        loss = step(*feed(i))
        losses.append(float(np.asarray(loss._array)))
        if i == 0:
            grad = first_gradient_norms(step,
                                        cell.spec["optimizer"]["beta1"])
    return {"losses": losses, "grad_norms": grad,
            "change_norms": change_norms(step, cell.config, seed)}


def run(cell, *, seed, seconds, trace, device, keep_trace=None):
    import paddle_tpu as paddle

    counter = harness.CompileCounter()
    cfg, spec = cell.config, cell.spec
    marks = {"imports": time.perf_counter() - harness.T0}

    # ---- set-up: one step object, the seed's weights, the seed's batches
    paddle.seed(seed % (2 ** 31))
    model, step = build(cfg, spec["optimizer"], spec.get("autocast"))
    load_weights(step._params, cfg, seed)
    ring = [(paddle.to_tensor(t), paddle.to_tensor(l)) for t, l in
            traffic.train_batches(cell.traffic, cfg["vocab_size"], seed)]

    def feed(i):
        return ring[i % len(ring)]

    marks["built"] = time.perf_counter() - harness.T0
    compiled = step.lower(*feed(0)).compile()
    temp_bytes = compiled.memory_analysis().temp_size_in_bytes
    hlo_has_kernel = "tpu_custom_call" in compiled.as_text()
    del compiled
    marks["compiled"] = time.perf_counter() - harness.T0
    got = program_readings(step, feed, cell, seed)
    setup_compiles = counter.count

    # ---- the window: the same object, the same call, the same feed
    win = train_common.window(lambda t, l: step(t, l)._array, feed, seconds,
                              trace, counter)

    # ---- read the peak, free the program, then the reference
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(
        temp_bytes))
    reduced = win["tracer"].reduce(keep_to=keep_trace)
    del model, step, ring
    gc.collect()
    return train_common.finish(
        cell, win, trace=trace, device=device, reduced=reduced, got=got,
        reference=lambda: reference_readings(cell, seed),
        notes={"kernel_in_step": hlo_has_kernel,
               "setup_programs": setup_compiles, "setup_marks_s": marks})


def prove(cell, seed: int, control: bool) -> dict:
    """The readings a limit is set from, for one seed, at the cell's own
    size: the program's numbers against the reference and, with
    ``control``, the control's (the reference in the next lower precision,
    in the program's place) and the planted fault's (half of the batch
    left out, the mean taken over the rest).  A step that returns its
    state unchanged reads 1 in both norms by the measure and needs no
    run."""
    import paddle_tpu as paddle

    paddle.seed(seed % (2 ** 31))
    model, step = build(cell.config, cell.spec["optimizer"],
                        cell.spec.get("autocast"))
    load_weights(step._params, cell.config, seed)
    ring = [(paddle.to_tensor(t), paddle.to_tensor(l)) for t, l in
            traffic.train_batches(cell.traffic, cell.config["vocab_size"],
                                  seed)[:CHECKED_STEPS]]
    got = program_readings(step, lambda i: ring[i], cell, seed)
    del model, step, ring
    gc.collect()
    return train_common.prove_row(
        cell, seed, got,
        lambda **kw: reference_readings(cell, seed, **kw), control)
