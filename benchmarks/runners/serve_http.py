"""Runner `serve_http`: one chip, a GPT-2 configuration behind the
program's front door: `POST /v1/generate` on `fleet.EdgeServer`, through
`ServingFrontend` into `DecodeEngine` (chunked prefill, paged K/V writes,
the paged-attention kernel, decode through the cache), tokens streamed
back over SSE.

Open loop: requests leave on the schedule `traffic.open_loop` draws from
the seed whether or not earlier ones have finished; each is timed from the
moment it was due.  Once the window has closed and every request has
ended, a sample of the finished requests (the longest among them) is
checked against the plain reference: one pass over each prompt with its
served tokens, and the widest gap by which a served token's logit lies
below the reference's best.
"""
from __future__ import annotations

import gc
import http.client
import json
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks import harness, traffic, weights
from benchmarks.runners.train_step import build_model, load_weights

TRACED_SECONDS = 6.0
GRACE_SECONDS = 60.0  # how long past the close an answer is waited for
# decode_stats counters of the engine's containment ladder and retraces: a
# serve that needed any of them did not serve as shipped
MUST_STAY_ZERO = ("step_retries", "finished_fault", "recoveries",
                  "spec_disables", "legacy_fallbacks", "hung_steps",
                  "evicted", "cancelled")


def build_engine(cfg: dict, engine: dict, seed: int):
    """The program's model with the seed's weights, and its engine."""
    from paddle_tpu.inference.serving import DecodeEngine

    model = build_model(cfg)
    model.eval()
    load_weights(model.functional_state()[0], cfg, seed)
    eng = DecodeEngine(model, max_batch_size=engine["slots"],
                       max_seq_len=cfg["n_positions"],
                       num_pages=engine["num_pages"],
                       **engine.get("options", {}))
    return model, eng


def http_generate(port: int, req: dict, t0: float, clock=time.perf_counter):
    """One `POST /v1/generate`, sent now.  Returns the request's record:
    when it was due and sent, and when each token came, all in seconds
    from ``t0``; ``error`` where it failed or was refused."""
    rec = {"due_s": req["due_s"], "sent_s": clock() - t0, "tokens": [],
           "token_s": [], "error": None,
           "max_new_tokens": req["max_new_tokens"]}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        with harness.span("send"):
            conn.request("POST", "/v1/generate", body=json.dumps(
                {"prompt_ids": req["prompt"],
                 "max_new_tokens": req["max_new_tokens"]}),
                headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"status {resp.status}: {resp.read()[:200]!r}"
            return rec
        done = None
        for line in resp:
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[6:])
            if "t" in ev:
                if ev["i"] != len(rec["tokens"]):
                    rec["error"] = f"token index {ev['i']} out of order"
                    return rec
                rec["tokens"].append(int(ev["t"]))
                rec["token_s"].append(clock() - t0)
            elif ev.get("done"):
                done = ev
        if done is None:
            rec["error"] = "stream ended without a terminal event"
        else:
            rec["finish_reason"] = done.get("finish_reason")
            if len(rec["tokens"]) != req["max_new_tokens"]:
                rec["error"] = (f"{len(rec['tokens'])} tokens of "
                                f"{req['max_new_tokens']}: {done}")
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    return rec


def offer(port: int, requests: list, seconds: float, on_tick=None):
    """Send ``requests`` on their schedule from now; wait for every answer,
    `GRACE_SECONDS` past the close at most.  Returns (records, t0)."""
    records = [None] * len(requests)
    with ThreadPoolExecutor(max_workers=max(8, len(requests))) as pool:
        t0 = time.perf_counter()
        futures = []
        for i, req in enumerate(requests):
            wait = t0 + req["due_s"] - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(http_generate, port, req, t0))
            if on_tick:
                on_tick(time.perf_counter() - t0)
        rest = t0 + seconds - time.perf_counter()
        while rest > 0:
            time.sleep(min(rest, 0.25))
            if on_tick:
                on_tick(time.perf_counter() - t0)
            rest = t0 + seconds - time.perf_counter()
        deadline = t0 + seconds + GRACE_SECONDS
        for i, f in enumerate(futures):
            try:
                records[i] = f.result(
                    timeout=max(deadline - time.perf_counter(), 0.01))
            except TimeoutError:
                records[i] = {"due_s": requests[i]["due_s"], "sent_s": None,
                              "tokens": [], "token_s": [],
                              "error": "no answer a minute past the close",
                              "max_new_tokens":
                              requests[i]["max_new_tokens"]}
    return records, t0


def percentile(values, q):
    """The q-th percentile, nearest rank upward: a value that was seen."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, int(np.ceil(q / 100.0 * len(v))) - 1))]


def window_metrics(records: list, seconds: float) -> dict:
    """The end-to-end numbers of one window, over every request of it.  A
    failed or refused request misses: its time to first token counts as the
    whole wait it was given."""
    miss = seconds + GRACE_SECONDS
    ttft = [(r["token_s"][0] - r["due_s"]) if r["token_s"] and not
            r["error"] else miss for r in records]
    gaps = [b - a for r in records
            for a, b in zip(r["token_s"], r["token_s"][1:])]
    inside = sum(1 for r in records for t in r["token_s"] if t <= seconds)
    late = [r["sent_s"] - r["due_s"] for r in records
            if r["sent_s"] is not None]
    return {
        "ttft_p90_ms": 1e3 * percentile(ttft, 90),
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else miss * 1e3,
        "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else miss * 1e3,
        "serve_tokens_per_s": inside / seconds,
        "gen_late_p95_ms": 1e3 * percentile(late, 95) if late else None,
        "requests": len(records),
        "failed": sum(1 for r in records if r["error"]),
        "unfinished_at_close": sum(
            1 for r in records if not r["token_s"]
            or r["token_s"][-1] > seconds),
    }


def served_sample(records, requests, seed: int, count: int):
    """Indices of ``count`` finished requests, drawn from the seed, the
    longest (prompt + served tokens) always among them."""
    ok = [i for i, r in enumerate(records) if not r["error"] and r["tokens"]]
    if not ok:
        return []
    longest = max(ok, key=lambda i: len(requests[i]["prompt"])
                  + len(records[i]["tokens"]))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 11])
    rest = [i for i in ok if i != longest]
    picked = rng.permutation(rest)[:max(count - 1, 0)].tolist()
    return [longest] + picked


def reference_gaps(cfg: dict, seed: int, pairs: list, quant=None):
    """For each (prompt, served tokens): the widest gap by which a served
    token's logit lies below the reference's best, every sequence padded
    to the model's positions so that one program serves them all."""
    from benchmarks.reference import gpt2

    w = weights.init_weights(cfg, seed)
    out = []
    for prompt, served in pairs:
        gaps = gpt2.served_gaps(w, prompt, served, cfg["n_head"],
                                cfg["padded_vocab_size"], quant=quant,
                                pad_to=cfg["n_positions"])
        out.append(float(np.max(gaps)))
    return out


def quiet(stats: dict) -> dict:
    return {k: v for k, v in stats.items()
            if (("retrace" in k) or k in MUST_STAY_ZERO) and v}


def set_up(cell, seed: int):
    """Engine built, warmed up on this cell's shapes, the edge listening.
    Returns what the window and the tear-down need."""
    from paddle_tpu import profiler
    from paddle_tpu.fleet import EdgeServer

    cfg = cell.config
    model, eng = build_engine(cfg, cell.spec["engine"], seed)
    # a prefill chunk beside a decode row, then decode alone: every step
    # executable of this engine compiles here, on other prompts
    warm = cell.spec["engine"]["warm_up"]
    rng = np.random.default_rng(1)
    eng.generate([rng.integers(4, cfg["vocab_size"], n).tolist()
                  for n in warm["prompt_tokens"]],
                 max_new_tokens=warm["new_tokens"])
    temp = 0
    for t in eng._trackers():
        if t is not None:
            temp = max(temp, t.lower().compile().memory_analysis()
                       .temp_size_in_bytes)
    profiler.decode_stats(reset=True)
    edge = EdgeServer(eng)
    port = edge.start()
    return {"model": model, "engine": eng, "edge": edge, "port": port,
            "temp_bytes": temp}


def run(cell, *, seed, seconds, trace, device, keep_trace=None):
    from paddle_tpu import profiler

    counter = harness.CompileCounter()
    cfg = cell.config
    state = set_up(cell, seed)
    requests = traffic.open_loop(cell.traffic, cfg["vocab_size"], seed,
                                 seconds)
    setup_compiles = counter.count

    tracer = harness.TracedWindow(trace)

    def on_tick(now_s):
        if tracer.running and now_s >= min(TRACED_SECONDS, seconds):
            tracer.stop(aside=True)

    tracer.start()
    setup_s = time.perf_counter() - harness.T0
    try:
        records, _ = offer(state["port"], requests, seconds, on_tick)
    finally:
        tracer.stop()
        stats = profiler.decode_stats()
        state["edge"].close()
    window_compiles = counter.count - setup_compiles
    if window_compiles:
        raise RuntimeError(f"{window_compiles} compilation(s) inside the "
                           f"measured window")
    m = window_metrics(records, seconds)
    contained = quiet(stats)
    if contained:
        raise RuntimeError(f"the engine retraced or contained a fault: "
                           f"{contained}")

    # ---- read the peak, free the program, then the reference
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(
        state["temp_bytes"]))
    reduced = tracer.reduce(keep_to=keep_trace)
    state.clear()
    gc.collect()
    t_ref = time.perf_counter()
    picked = served_sample(records, requests, seed,
                           cell.spec["reference"]["requests"])
    gaps = reference_gaps(cfg, seed, [
        (requests[i]["prompt"], records[i]["tokens"]) for i in picked])
    reference_s = time.perf_counter() - t_ref
    checked = sum(len(records[i]["tokens"]) for i in picked)
    compared = {"served_logit_gap": (max(gaps) if gaps else float("nan"),
                                     cell.limits["served_logit_gap"])}
    print("window", json.dumps(m), "checked_tokens", checked,
          f"reference_s {reference_s:.1f}", "steps", stats["steps"],
          "avg_step_ms", stats["avg_step_ms"], flush=True)

    layer, breakdown = {}, None
    if trace:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"][:10],
                     "idle_gaps": reduced["idle_gaps"][:10]}
        prompt_tokens = sum(len(q["prompt"]) for q, r in
                            zip(requests, records) if not r["error"])
        ctx = {"cell": cell, "trace": reduced, "device": device,
               "peaks": harness.peaks_of(device["kind"])
               if device["platform"] == "tpu" else None,
               "counters": stats, "window": m, "seconds": seconds,
               "records": records, "requests": requests,
               "prompt_tokens": prompt_tokens}
        layer = harness.read_layer_metrics(cell, ctx)
    return harness.emit_result(
        cell, trace=trace, device=device,
        end_to_end={"ttft_p90_ms": m["ttft_p90_ms"],
                    "itl_p95_ms": m["itl_p95_ms"],
                    "serve_tokens_per_s": m["serve_tokens_per_s"],
                    "setup_s": setup_s},
        layer=layer, attempted=m["requests"], failed=m["failed"],
        compared=compared, breakdown=breakdown,
        notes={"window": m, "checked_tokens": checked,
               "reference_s": reference_s, "engine_steps": stats["steps"],
               "avg_step_ms": stats["avg_step_ms"]})


def sweep(cell, seed: int, rates: list, seconds: float) -> list:
    """The one search for the knee, made when the cell is defined: the same
    engine, one window at each offered rate, and how the backlog stood at
    the close.  The benchmark's own runs never call this."""
    from paddle_tpu import profiler

    state = set_up(cell, seed)
    rows = []
    try:
        for k, rate in enumerate(rates):
            mix = dict(cell.traffic, rate_per_s=rate)
            requests = traffic.open_loop(mix, cell.config["vocab_size"],
                                         seed + k, seconds)
            profiler.decode_stats(reset=True)
            records, _ = offer(state["port"], requests, seconds)
            stats = profiler.decode_stats()
            m = window_metrics(records, seconds)
            half = [r for r in records if r["due_s"] >= seconds / 2]
            m["ttft_p50_second_half_ms"] = 1e3 * percentile(
                [r["token_s"][0] - r["due_s"] for r in half
                 if r["token_s"]] or [0.0], 50)
            m.update(rate_per_s=rate, avg_step_ms=stats["avg_step_ms"],
                     occupancy=stats["batch_occupancy"],
                     steps=stats["steps"])
            rows.append(m)
            print(json.dumps(m), flush=True)
    finally:
        state["edge"].close()
    return rows


def prove(cell, seed: int, control: bool) -> dict:
    """The readings the limit is set from, for one seed, at the cell's own
    load: a short window (long enough to finish the mix's longest requests
    and to compare as many as a run does), the program's widest gap, and
    with ``control`` the control's on the same prompts and tokens (the
    reference in the next lower precision: at each position the gap of the
    token it puts first) and the planted fault's (one served token
    altered where it is produced)."""
    from paddle_tpu import profiler

    cfg = cell.config
    seconds = cell.spec["reference"]["prove_seconds"]
    state = set_up(cell, seed)
    requests = traffic.open_loop(cell.traffic, cfg["vocab_size"], seed,
                                 seconds)
    try:
        records, _ = offer(state["port"], requests, seconds)
    finally:
        stats = profiler.decode_stats()
        state["edge"].close()
    state.clear()
    gc.collect()
    picked = served_sample(records, requests, seed,
                           cell.spec["reference"]["requests"])
    pairs = [(requests[i]["prompt"], records[i]["tokens"]) for i in picked]
    out = {"seed": seed, "requests": len(records),
           "failed": sum(1 for r in records if r["error"]),
           "checked_tokens": sum(len(p[1]) for p in pairs),
           "contained": quiet(stats),
           "program": {"served_logit_gap": [
               max(reference_gaps(cfg, seed, pairs)), "widest"]}}
    if control:
        out["control"] = {"served_logit_gap": [
            max(reference_gaps(cfg, seed, pairs,
                               quant=cell.spec["control"])), "widest"]}
        altered = [(p, alter_one(s, cfg["vocab_size"], seed))
                   for p, s in pairs[:1]]
        out["altered_token"] = {"served_logit_gap": [
            max(reference_gaps(cfg, seed, altered)), "widest"]}
    return out


def alter_one(served: list, vocab: int, seed: int) -> list:
    """``served`` with one token, drawn from the seed, replaced by the id
    after it: the fault of a token altered where it is produced."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 13])
    i = int(rng.integers(0, len(served)))
    out = list(served)
    out[i] = (out[i] + 1) % vocab
    return out
