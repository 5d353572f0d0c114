"""What the two train runners share: the measured window (one call a step,
at most two steps in flight), and what follows it — the reference, the
comparison, the per-layer metrics and the result line."""
from __future__ import annotations

import time

import numpy as np

from benchmarks import compare, harness

CHECKED_STEPS = 3
IN_FLIGHT = 2
TRACED_SECONDS = 3.0


def window(call, feed, seconds: float, trace: bool, counter) -> dict:
    """Drive ``call(tokens, labels) -> device scalar loss`` on
    ``feed(i)`` from step `CHECKED_STEPS` on for ``seconds``; a step counts
    once its loss is fenced, and the window closes at the first fence at or
    past ``seconds``.  With ``trace`` the first `TRACED_SECONDS` are
    profiled.  Raises where anything compiled inside the window."""
    import jax

    compiles_before = counter.count
    tracer = harness.TracedWindow(trace)
    tracer.start()
    losses, dispatch_s = [], []
    i, done = CHECKED_STEPS, 0
    traced = None  # (steps fenced, steps dispatched, seconds) while traced
    t0 = time.perf_counter()
    setup_s = t0 - harness.T0
    while True:
        with harness.span("make_batch"):
            tokens, labels = feed(i)
        with harness.span("dispatch"):
            t = time.perf_counter()
            loss = call(tokens, labels)
            dispatch_s.append(time.perf_counter() - t)
        losses.append(loss)
        i += 1
        if len(losses) - done >= IN_FLIGHT:
            with harness.span("fence"):
                losses[done].block_until_ready()
            done += 1
            now = time.perf_counter()
            if tracer.running and now - t0 >= min(TRACED_SECONDS, seconds):
                traced = (done, len(dispatch_s), now - t0)
                tracer.stop()
            if now - t0 >= seconds:
                break
    elapsed = now - t0
    compiles = counter.count - compiles_before
    jax.block_until_ready(losses)
    if compiles:
        raise RuntimeError(f"{compiles} compilation(s) inside the measured "
                           f"window")
    host_losses = np.array([np.asarray(x) for x in losses[:done]])
    return {"setup_s": setup_s, "steps": done, "elapsed": elapsed,
            "failed": int(np.sum(~np.isfinite(host_losses))),
            "dispatch_s": dispatch_s, "traced": traced, "tracer": tracer}


def finish(cell, win: dict, *, trace, device, reduced, got, reference,
           notes: dict):
    """After the peak is read and the program freed: run ``reference()``,
    compare, read the per-layer metrics of a traced run, print the line."""
    tokens = cell.traffic["batch"] * cell.traffic["seq"]
    t_ref = time.perf_counter()
    ref = reference()
    reference_s = time.perf_counter() - t_ref
    numbers = compare.train_numbers(got, ref)
    compared = {k: (v[0], cell.limits[k]) for k, v in numbers.items()
                if k in cell.limits}
    print("readings", numbers, "losses", got["losses"], ref["losses"],
          f"reference_s {reference_s:.1f} steps {win['steps']} "
          f"elapsed {win['elapsed']:.3f}", flush=True)

    layer, breakdown = {}, None
    if trace:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"][:10],
                     "idle_gaps": reduced["idle_gaps"][:10]}
        # the traced stretch's own rate and calls: stopping the profiler
        # stalls the loop, so the whole window's rate is not the step's
        steps, calls, seconds = win["traced"]
        ctx = {"cell": cell, "trace": reduced, "device": device,
               "peaks": harness.peaks_of(device["kind"])
               if device["platform"] == "tpu" else None,
               "tokens_per_s": steps * tokens / seconds,
               "dispatch_seconds": win["dispatch_s"][:calls], "counters": {}}
        layer = harness.read_layer_metrics(cell, ctx)
    return harness.emit_result(
        cell, trace=trace, device=device,
        end_to_end={"train_tokens_per_s":
                    win["steps"] * tokens / win["elapsed"],
                    "setup_s": win["setup_s"]},
        layer=layer, attempted=win["steps"], failed=win["failed"],
        compared=compared, breakdown=breakdown,
        notes=dict(notes, steps=win["steps"], reference_s=reference_s))


def prove_row(cell, seed: int, got: dict, reference, control: bool) -> dict:
    """One seed's readings for `prove.py`: the program's numbers against
    ``reference()`` and, with ``control``, the control's (``reference(
    quant=...)``) and the half batch's (``reference(rows=...)``), both the
    reference in the program's place."""
    ref = reference()
    out = {"seed": seed, "losses": got["losses"],
           "reference_losses": ref["losses"],
           "program": compare.train_numbers(got, ref)}
    if control:
        out["control"] = compare.train_numbers(
            reference(quant=cell.spec["control"]), ref)
        half = range(cell.traffic["batch"] // 2)
        out["half_batch"] = compare.train_numbers(reference(rows=half), ref)
    return out
