"""chip_smoke.py — the quickest proof that the program still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the full width of GPT-base (12L / 768H / 12 heads / vocab 50304 / S=1024,
the model `bench.py` trains), with seeded random weights and data:

* train — `jit.train_step(model, loss_fn, AdamW)` under bf16 autocast,
  batch 16 x 1024, a few steps on a fixed batch: losses finite and falling,
  the flash kernel in the compiled step;
* serve — `DecodeEngine` with its defaults as shipped, behind
  `ServingFrontend` and `paddle_tpu.fleet.EdgeServer` on 127.0.0.1: a few
  concurrent `POST /v1/generate` requests stream their tokens over SSE,
  the tokens equal `engine.generate()` on the same prompts, every step
  executable holds the paged-attention kernel, nothing retraces after
  warm-up.  A second, short pass runs the same with ``ragged_step=True``.

`--four-chips` runs instead, and only, the two paths that exist across
chips and what each is compared with: the `models.gpt_spmd` hybrid train
step at dp=2 x mp=2 against the same step on one device, and
`DecodeEngine(serve_mesh="mp=4")` against the one-device engine.

It needs a TPU and says so: without one it exits non-zero and prints no
result.  Every phase prints one JSON line; any phase that fails raises, and
the run ends there.  The last line of a good run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
One process, no child processes, no network beyond 127.0.0.1.

    python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import gc
import http.client
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import jax

SEED = 0
GPT_BASE = dict(vocab_size=50304, hidden_size=768, num_layers=12,
                num_heads=12, max_seq_len=1024)
TRAIN = dict(batch=16, seq=1024, steps=4)
# The pool the benchmark's serve cell holds: 16 sequences of 1024 tokens,
# 256 pages of 64, 1.2 GB of f32 K/V (2.4 GB as it lies on the chip, where a
# 64-wide row fills half of a 128-lane row: `pa.kv_pool_width`).  A step's
# temp is a tenth of that since the K/V write is made in place (PR 27).
SERVE = dict(slots=16, num_pages=256, prompt_lens=(192, 301, 420, 256),
             new_tokens=32, ragged_prompt_lens=(210, 333), warm_lens=(70, 9))
FOUR = dict(train_batch=8, train_steps=3, loss_rtol=1e-3,  # tests/test_gpt.py
            slots=8, num_pages=128, prompt_lens=(150, 260, 90),
            new_tokens=16)
KERNEL = "tpu_custom_call"  # how a Pallas (Mosaic) kernel reads in TPU HLO
# decode_stats counters of the engine's containment ladder (retry, quarantine,
# rebuild, degrade to the legacy path) and of retraces: a serve that needed
# any of them did not pass, whatever tokens came out
MUST_STAY_ZERO = ("step_retries", "finished_fault", "recoveries",
                  "spec_disables", "legacy_fallbacks", "hung_steps",
                  "evicted", "cancelled")


def emit(**row):
    print(json.dumps(row), flush=True)


def require_tpu(count: int) -> dict:
    """The device as JAX reports it — and the end of the run where that is
    not a TPU, or there are fewer chips than the path needs."""
    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if found["platform"] != "tpu" or found["count"] < count:
        raise SystemExit(f"chip_smoke needs {count} TPU chip(s); "
                         f"JAX found {found}")
    return found


def has_kernel(compiled) -> bool:
    return KERNEL in compiled.as_text()


def memory_of(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {"argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes}


def device_memory() -> list:
    """bytes_in_use / peak_bytes_in_use of every device (the peak is the
    process's, not the phase's)."""
    rows = []
    for d in jax.devices():
        stats = d.memory_stats() or {}  # the CPU backend reports none
        rows.append({"id": d.id,
                     "bytes_in_use": stats.get("bytes_in_use"),
                     "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return rows


def inspect_executable(name: str, lowered):
    """Compile ``lowered`` (a persistent-cache hit where the step has run
    already), report its compile seconds and memory on a line of their own,
    and require the Pallas kernel in it.  Returns the compiled program."""
    t0 = time.perf_counter()
    compiled = lowered.compile()
    found = has_kernel(compiled)
    emit(executable=name,
         compile_seconds=round(time.perf_counter() - t0, 3),
         has_kernel=found, **memory_of(compiled))
    if not found:
        raise RuntimeError(f"no {KERNEL} in the executable of {name}: the "
                           f"Pallas kernel was bypassed")
    return compiled


# ---------------------------------------------------------------------------
# train: jit.train_step on one chip, exactly as bench.py drives it
# ---------------------------------------------------------------------------
def train_phase():
    import paddle_tpu as paddle
    from bench import gpt_train_step
    from paddle_tpu.models.gpt import GPTConfig

    paddle.seed(SEED)
    cfg = GPTConfig(use_parallel_layers=False, **GPT_BASE)
    _model, step = gpt_train_step(cfg)
    rng = np.random.default_rng(SEED)
    shape = (TRAIN["batch"], TRAIN["seq"])
    tokens = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, shape).astype(np.int32))
    labels = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, shape).astype(np.int32))

    inspect_executable("train_step", step.lower(tokens, labels))
    losses, seconds = [], []
    for _ in range(TRAIN["steps"]):
        t0 = time.perf_counter()
        loss = step(tokens, labels)
        jax.block_until_ready(loss._array)
        seconds.append(round(time.perf_counter() - t0, 4))
        losses.append(float(np.asarray(loss._array)))
    emit(phase="train", model=GPT_BASE, batch=list(shape),
         autocast="bfloat16", losses=losses, step_seconds=seconds,
         first_step_includes_compile=True, memory=device_memory())
    if not all(np.isfinite(losses)):
        raise RuntimeError(f"train losses not finite: {losses}")
    if not all(b < a for a, b in zip(losses, losses[1:])):
        raise RuntimeError(f"train losses not falling on a fixed batch: "
                           f"{losses}")


# ---------------------------------------------------------------------------
# serve: DecodeEngine + ServingFrontend behind the HTTP/SSE edge
# ---------------------------------------------------------------------------
def _prompts(lens, vocab, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(4, vocab, (n,)).astype(np.int32).tolist()
            for n in lens]


def _quiet(stats: dict, when: str) -> dict:
    """The retrace and containment counters of ``stats`` — all zero, or
    the serve failed behind the engine's own error handling."""
    watched = {k: v for k, v in stats.items()
               if "retrace" in k or k in MUST_STAY_ZERO}
    if any(watched.values()):
        raise RuntimeError(f"{when}: the engine retraced or contained a "
                           f"fault: {watched}")
    return watched


def _http_generate(port: int, prompt, new_tokens: int) -> dict:
    """One `POST /v1/generate`: the streamed tokens, the time to the first
    and the time to the last."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        t0 = time.perf_counter()
        conn.request("POST", "/v1/generate",
                     body=json.dumps({"prompt_ids": prompt,
                                      "max_new_tokens": new_tokens}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"/v1/generate answered {resp.status}: "
                               f"{resp.read()[:300]!r}")
        tokens, ttft, done = [], None, None
        for line in resp:
            if not line.startswith(b"data: "):
                continue
            ev = json.loads(line[6:])
            if "t" in ev:
                if ev["i"] != len(tokens):
                    raise RuntimeError(f"SSE token index {ev['i']} out of "
                                       f"order after {len(tokens)} tokens")
                if ttft is None:
                    ttft = time.perf_counter() - t0
                tokens.append(ev["t"])
            elif ev.get("done"):
                done = ev
        if done is None:
            raise RuntimeError("SSE stream ended without a terminal event")
        return {"tokens": tokens, "finish_reason": done["finish_reason"],
                "ttft_seconds": round(ttft, 4),
                "total_seconds": round(time.perf_counter() - t0, 4)}
    finally:
        conn.close()


def serve_phase(model, ragged: bool):
    """One engine, as shipped (``ragged`` flips the one option the roadmap
    wants decided): warm up, look inside the step executables, answer HTTP
    requests, and compare with `generate()` on the same engine."""
    from paddle_tpu import profiler
    from paddle_tpu.fleet import EdgeServer
    from paddle_tpu.inference.serving import DecodeEngine

    name = "serve_ragged" if ragged else "serve"
    vocab = model.cfg.vocab_size
    lens = SERVE["ragged_prompt_lens"] if ragged else SERVE["prompt_lens"]
    prompts = _prompts(lens, vocab, SEED + 2)
    eng = DecodeEngine(model, max_batch_size=SERVE["slots"],
                       max_seq_len=model.cfg.max_seq_len,
                       num_pages=SERVE["num_pages"],
                       ragged_step=ragged or None)  # None: as shipped

    # warm-up on other prompts: a prefill chunk next to a decode row, then
    # decode alone — every step executable of this engine compiles here
    t0 = time.perf_counter()
    eng.generate(_prompts(SERVE["warm_lens"], vocab, SEED + 1),
                 max_new_tokens=4)
    warm_seconds = round(time.perf_counter() - t0, 3)
    trackers = [t for t in eng._trackers() if t is not None]
    for t in trackers:
        inspect_executable(f"{name}: {t.site}", t.lower())
        if t.cost_sig is None:
            raise RuntimeError(f"{t.site}: no HLO cost profile was "
                               f"extracted at compile time (the cost "
                               f"model fell back to its analytical form)")
    _quiet(profiler.decode_stats(reset=True), f"{name} warm-up")

    edge = EdgeServer(eng)
    port = edge.start()
    try:
        with ThreadPoolExecutor(len(prompts)) as pool:
            futures = [pool.submit(_http_generate, port, p,
                                   SERVE["new_tokens"]) for p in prompts]
            answers = [f.result() for f in futures]
    finally:
        edge.close()
    stats = profiler.decode_stats()
    reference = eng.generate(prompts, max_new_tokens=SERVE["new_tokens"])
    sites = [t.site for t in eng._trackers() if t is not None]
    counters = _quiet(stats, name)
    emit(phase=name, num_pages=SERVE["num_pages"],
         config=eng.statusz()["config"],
         weights_dtype=str(eng._params["wte"].dtype),
         kv_pages_dtype=str(eng._kv.dtype),
         kv_pool_bytes=sum(a.nbytes for a in eng._kv.pages),
         warmup_seconds_with_compile=warm_seconds,
         executables=[t.site for t in trackers],
         prompt_lens=list(lens), new_tokens=SERVE["new_tokens"],
         answers=answers, steps=stats["steps"],
         avg_step_ms=round(stats["avg_step_ms"], 3), counters=counters,
         memory=device_memory())
    for got, want in zip(answers, reference):
        if got["tokens"] != list(want):
            raise RuntimeError(f"{name}: HTTP tokens differ from "
                               f"generate(): {got['tokens']} vs {want}")
        if len(got["tokens"]) != SERVE["new_tokens"]:
            raise RuntimeError(f"{name}: a request ended early: {got}")
    if sites != [t.site for t in trackers]:
        raise RuntimeError(f"{name}: new step executables appeared after "
                           f"warm-up: {sites}")


def serve_phases():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT, GPTConfig

    paddle.seed(SEED + 1)
    model = GPT(GPTConfig(use_parallel_layers=False, **GPT_BASE))
    model.eval()
    serve_phase(model, ragged=False)
    gc.collect()  # the first engine's pool goes before the second's comes
    serve_phase(model, ragged=True)


# ---------------------------------------------------------------------------
# --four-chips: the paths that exist only across chips
# ---------------------------------------------------------------------------
def _spread(name: str, array, n: int):
    """Require ``array`` to live in ``n`` distinct shards on ``n`` devices
    (not all of it on the first) and report the shard shape."""
    shards = array.addressable_shards
    devices = {s.device.id for s in shards}
    shapes = {tuple(s.data.shape) for s in shards}
    if len(devices) != n or shapes == {tuple(array.shape)}:
        raise RuntimeError(f"{name} {array.shape} is not spread over {n} "
                           f"devices: shards {shapes} on {devices}")
    return {"array": name, "global": list(array.shape),
            "shard": list(next(iter(shapes))), "devices": sorted(devices)}


def hybrid_train_phase():
    """models.gpt_spmd at dp=2 x mp=2 against the same step on one device,
    same weights, same batch."""
    from jax.sharding import NamedSharding
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import gpt_spmd
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(**GPT_BASE)
    specs = gpt_spmd.param_specs(cfg)
    host_params = jax.device_get(
        gpt_spmd.init_params(cfg, jax.random.PRNGKey(SEED)))
    rng = np.random.default_rng(SEED)
    shape = (FOUR["train_batch"], cfg.max_seq_len)
    tokens = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)
    labels = rng.integers(0, cfg.vocab_size, shape).astype(np.int32)

    def run(dp, mp):
        mesh = build_mesh(dp=dp, mp=mp)
        step = gpt_spmd.build_spmd_train_step(cfg, mesh, lr=1e-2)
        params = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                  for k, v in host_params.items()}
        spread = [_spread(k, params[k], dp * mp)
                  for k in ("w_qkv", "w_fc2", "wte")] if dp * mp > 1 else []
        inspect_executable(f"gpt_spmd dp={dp} mp={mp}",
                           step.lower(params, tokens, labels))
        losses, seconds = [], []
        for _ in range(FOUR["train_steps"]):
            t0 = time.perf_counter()
            loss, params = step(params, tokens, labels)
            jax.block_until_ready(params)
            seconds.append(round(time.perf_counter() - t0, 4))
            losses.append(float(loss))
        return {"losses": losses, "step_seconds": seconds,
                "spread": spread, "memory": device_memory()}

    four = run(dp=2, mp=2)
    one = run(dp=1, mp=1)
    emit(phase="hybrid_train", model=GPT_BASE, batch=list(shape),
         compute_dtype="bfloat16", dp2_mp2=four, one_device=one,
         loss_rtol=FOUR["loss_rtol"])
    if not np.allclose(four["losses"], one["losses"],
                       rtol=FOUR["loss_rtol"], atol=0):
        raise RuntimeError(f"dp=2 x mp=2 losses {four['losses']} differ "
                           f"from one device {one['losses']}")
    if not four["losses"][-1] < four["losses"][0]:
        raise RuntimeError(f"hybrid losses not falling: {four['losses']}")


def sharded_serve_phase():
    """DecodeEngine(serve_mesh="mp=4") against the one-device engine."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import DecodeEngine
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.parallel.partition import hlo_collectives

    paddle.seed(SEED + 1)
    model = GPT(GPTConfig(use_parallel_layers=False, **GPT_BASE))
    model.eval()
    prompts = _prompts(FOUR["prompt_lens"], model.cfg.vocab_size, SEED + 2)
    kw = dict(max_batch_size=FOUR["slots"],
              max_seq_len=model.cfg.max_seq_len,
              num_pages=FOUR["num_pages"])

    one = DecodeEngine(model, **kw)
    want = one.generate(prompts, max_new_tokens=FOUR["new_tokens"])

    eng = DecodeEngine(model, serve_mesh="mp=4", **kw)
    t0 = time.perf_counter()
    got = eng.generate(prompts, max_new_tokens=FOUR["new_tokens"])
    seconds = round(time.perf_counter() - t0, 3)
    spread = [_spread("qkv_w", eng._params["blocks"][0]["qkv_w"], 4),
              _spread("fc2_w", eng._params["blocks"][0]["fc2_w"], 4),
              _spread("k_pages", eng._kv.k, 4),
              _spread("v_pages", eng._kv.v, 4)]
    (tracker,) = [t for t in eng._trackers() if t is not None]
    text = inspect_executable(f"serve mp=4: {tracker.site}",
                              tracker.lower()).as_text()
    collectives = hlo_collectives(text)
    # the pool must stay where it is: no all-gather may touch an array
    # whose trailing dims are the pool's [num_pages, page, head_dim]
    pool_dims = "{},{},{}]".format(*eng._kv.k.shape[2:])
    gathers = [ln.strip()[:200] for ln in text.splitlines()
               if " all-gather(" in ln or " all-gather-start(" in ln]
    emit(phase="sharded_serve", serve_mesh="mp=4", **kw,
         prompt_lens=list(FOUR["prompt_lens"]),
         new_tokens=FOUR["new_tokens"], tokens=[list(t) for t in got],
         generate_seconds_with_compile=seconds, spread=spread,
         collectives=collectives, all_gathers=gathers,
         memory=device_memory())
    if [list(t) for t in got] != [list(t) for t in want]:
        raise RuntimeError(f"mp=4 tokens {got} differ from one device "
                           f"{want}")
    if "all-reduce" not in collectives:
        raise RuntimeError("no all-reduce in the mp=4 step executable")
    for ln in gathers:
        if pool_dims in ln:
            raise RuntimeError(f"the mp=4 step all-gathers KV pages: {ln}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the dp=2 x mp=2 train step and the mp=4 "
                         "engine, each against one device, and nothing else")
    args = ap.parse_args(argv)
    device = require_tpu(4 if args.four_chips else 1)

    from paddle_tpu.core.compile_cache import enable_compile_cache

    emit(phase="start", device=device, jax=jax.__version__,
         compile_cache=enable_compile_cache())
    if args.four_chips:
        hybrid_train_phase()
        gc.collect()
        sharded_serve_phase()
    else:
        train_phase()
        gc.collect()  # the train state goes before the KV pool comes
        serve_phases()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
