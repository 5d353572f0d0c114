"""Runner `spmd_train`: four chips, `models.gpt_spmd.build_spmd_train_step`
at dp x mp — vocab-parallel embedding and loss, the `mp` all-reduces, the
gradient sum over `dp`, the SGD update on local shards, in one program.

The step takes and returns its parameters (nothing is donated), so the
runner holds the one tree and feeds it back.  Set-up builds ONE step with
its sharded parameters from the seed, drives it through three steps by the
window's own call and feed, and hands both to the window.  What those
steps did is compared with the plain reference once the window has closed:
each loss, the first gradient's norm by leaf (the optimizer is SGD, so the
first step's change over the learning rate is the gradient as it got it),
and the norm of the change after three steps.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import compare, harness, traffic, train_common, weights
from benchmarks.train_common import CHECKED_STEPS

# gpt_spmd's leaf names for the reference's
NAMES = {"wte": "wte", "wpe": "wpe", "ln1_g": "ln1_w", "ln1_b": "ln1_b",
         "qkv_w": "w_qkv", "qkv_b": "b_qkv", "proj_w": "w_out",
         "proj_b": "b_out", "ln2_g": "ln2_w", "ln2_b": "ln2_b",
         "fc_w": "w_fc1", "fc_b": "b_fc1", "fc2_w": "w_fc2",
         "fc2_b": "b_fc2", "lnf_g": "lnf_w", "lnf_b": "lnf_b",
         "lm_head": "lm_head"}


def to_program(w: dict, n_head: int) -> dict:
    """The reference's weights under `gpt_spmd`'s names.  Its packed
    q|k|v axis is head-major ((heads, 3, d), so that an `mp` shard holds
    whole heads); the published layout is (3, heads, d)."""
    out = {NAMES[k]: v for k, v in w.items()}
    for name in ("w_qkv", "b_qkv"):
        x = out[name]
        lead = x.shape[:-1]
        out[name] = x.reshape(lead + (3, n_head, -1)).swapaxes(-3, -2) \
            .reshape(x.shape)
    return out


def split_norms(tree: dict, n_head: int) -> dict:
    """Traceable: `gpt_spmd` leaves in, the reference's flat leaf names ->
    L2 norm out, one a layer for stacked leaves, q|k|v as three."""
    import jax.numpy as jnp

    back = {v: k for k, v in NAMES.items()}
    out = {}
    for name, x in tree.items():
        leaf = back[name]
        x = x.astype(jnp.float32)
        if leaf in ("qkv_w", "qkv_b"):
            parts = x.reshape(x.shape[:-1] + (n_head, 3, -1))
            axes = tuple(range(1, parts.ndim - 2)) + (parts.ndim - 1,)
            n = jnp.sqrt(jnp.sum(parts ** 2, axis=axes))  # [L, 3]
            for i, p in enumerate("qkv"):
                for layer in range(n.shape[0]):
                    out[f"{p}_{leaf[-1]}.{layer}"] = n[layer, i]
        elif leaf in ("wte", "wpe", "lnf_g", "lnf_b", "lm_head"):
            out[leaf] = jnp.sqrt(jnp.sum(x ** 2))
        else:
            n = jnp.sqrt(jnp.sum(x ** 2, axis=tuple(range(1, x.ndim))))
            for layer in range(n.shape[0]):
                out[f"{leaf}.{layer}"] = n[layer]
    return out


def build(cell):
    """(mesh, step, parameter shardings, batch sharding)."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import gpt_spmd
    from paddle_tpu.models.gpt import GPTConfig

    cfg, par = cell.config, cell.spec["parallel"]
    gcfg = GPTConfig(
        vocab_size=cfg["padded_vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        max_seq_len=cfg["n_positions"],
        intermediate_size=cfg.get("n_inner") or 4 * cfg["n_embd"])
    mesh = build_mesh(dp=par["dp"], mp=par["mp"])
    if cell.spec["optimizer"]["name"] != "sgd":
        raise ValueError("gpt_spmd's step updates by SGD")
    step = gpt_spmd.build_spmd_train_step(
        gcfg, mesh, lr=cell.spec["optimizer"]["lr"],
        compute_dtype=jnp.dtype(cell.spec["compute_dtype"]))
    specs = gpt_spmd.param_specs(gcfg)
    shardings = {k: NamedSharding(mesh, specs[k]) for k in specs}
    return mesh, step, shardings, NamedSharding(mesh, P("dp", "sp"))


def make_params(cell, seed: int, shardings: dict):
    """The seed's weights made sharded on the devices in one jitted call."""
    import jax

    init = weights.init_fn(cell.config)
    n_head = cell.config["n_head"]
    fn = jax.jit(lambda key: to_program(init(key), n_head),
                 out_shardings=shardings)
    return fn(weights.key_of(seed, 0))


def norms_against_start(cell, seed, shardings, params, scale=1.0) -> dict:
    """Leaf norms of (params - the seed's weights) * scale."""
    import jax

    init = weights.init_fn(cell.config)
    n_head = cell.config["n_head"]

    def norms(now, key):
        start = to_program(init(key), n_head)
        return split_norms({k: (now[k] - start[k]) * scale for k in now},
                           n_head)

    fn = jax.jit(norms, in_shardings=(shardings, None))
    return {k: float(v) for k, v in jax.device_get(
        fn(params, weights.key_of(seed, 0))).items()}


def make_ring(cell, seed, batch_sharding):
    import jax

    return [(jax.device_put(t, batch_sharding),
             jax.device_put(l, batch_sharding))
            for t, l in traffic.train_batches(
                cell.traffic, cell.config["vocab_size"], seed)]


def program_readings(step, params, feed, cell, seed, shardings):
    """Three steps through ``step``; returns (readings, params after)."""
    lr = cell.spec["optimizer"]["lr"]
    losses, grad = [], None
    for i in range(CHECKED_STEPS):
        loss, params = step(params, *feed(i))
        losses.append(float(loss))
        if i == 0:
            grad = norms_against_start(cell, seed, shardings, params,
                                       scale=1.0 / lr)
    change = norms_against_start(cell, seed, shardings, params)
    return {"losses": losses, "grad_norms": grad,
            "change_norms": change}, params


def reference_readings(cell, seed: int, quant=None, rows=None) -> dict:
    """The reference's three steps, its weights spread over the chips'
    memory (the mathematics is placed, not changed)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.reference import gpt2

    cfg = cell.config
    mesh = Mesh(np.asarray(jax.devices()[:cell.chips]), ("x",))
    shp = weights.shapes(cfg)
    place = {k: NamedSharding(mesh, P(*([None] * (len(s) - 1) + ["x"]))
                              if len(s) >= 2 and s[-1] % cell.chips == 0
                              else P()) for k, s in shp.items()}
    w = jax.jit(weights.init_fn(cfg), out_shardings=place)(
        weights.key_of(seed, 0))
    batches = traffic.train_batches(cell.traffic, cfg["vocab_size"], seed)
    r = gpt2.train_readings(w, batches[:CHECKED_STEPS], cfg["n_head"],
                            cell.spec["optimizer"],
                            cell.spec["reference"]["rows_per_block"],
                            quant=quant, rows=rows, steps=CHECKED_STEPS)
    return {"losses": r["losses"],
            "grad_norms": compare.flat_norms(r["grad_norms"]),
            "change_norms": compare.flat_norms(r["change_norms"])}


def run(cell, *, seed, seconds, trace, device, keep_trace=None):
    counter = harness.CompileCounter()
    marks = {"imports": time.perf_counter() - harness.T0}
    mesh, step, shardings, batch_sharding = build(cell)
    params = make_params(cell, seed, shardings)
    ring = make_ring(cell, seed, batch_sharding)

    def feed(i):
        return ring[i % len(ring)]

    compiled = step.lower(params, *feed(0)).compile()
    temp_bytes = compiled.memory_analysis().temp_size_in_bytes
    text = compiled.as_text()
    hlo = {"kernel": "tpu_custom_call" in text,
           "all_reduce": " all-reduce" in text}
    del compiled, text
    marks["compiled"] = time.perf_counter() - harness.T0
    got, params = program_readings(step, params, feed, cell, seed, shardings)
    setup_compiles = counter.count

    # ---- the window: the step takes and returns its parameters, so the
    # one tree is fed back; its loss is what a step is fenced on
    held = {"params": params}
    del params

    def call(tokens, labels):
        loss, held["params"] = step(held["params"], tokens, labels)
        return loss

    win = train_common.window(call, feed, seconds, trace, counter)

    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(
        temp_bytes))
    reduced = win["tracer"].reduce(keep_to=keep_trace)
    held.clear()
    del step, ring
    gc.collect()
    return train_common.finish(
        cell, win, trace=trace, device=device, reduced=reduced, got=got,
        reference=lambda: reference_readings(cell, seed),
        notes={"hlo": hlo, "setup_programs": setup_compiles,
               "setup_marks_s": marks})


def prove(cell, seed: int, control: bool) -> dict:
    """As `train_step.prove`: the program's numbers against the reference
    at the cell's own size and, with ``control``, the control's and the
    half batch's (both the reference in the program's place)."""
    mesh, step, shardings, batch_sharding = build(cell)
    params = make_params(cell, seed, shardings)
    ring = make_ring(cell, seed, batch_sharding)[:CHECKED_STEPS]
    got, params = program_readings(step, params, lambda i: ring[i], cell,
                                   seed, shardings)
    del step, params, ring
    gc.collect()
    return train_common.prove_row(
        cell, seed, got,
        lambda **kw: reference_readings(cell, seed, **kw), control)
