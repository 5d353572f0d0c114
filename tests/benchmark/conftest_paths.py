"""Shared by the benchmark's tests: the repository root on `sys.path` and a
throw-away cell made of new files only."""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_CONFIG = {"source": "none: a toy for control flow on the CPU",
               "n_embd": 64, "n_head": 2, "n_layer": 2, "n_positions": 32,
               "n_inner": None, "vocab_size": 250, "padded_vocab_size": 256}
TINY_OPTIM = {"name": "adamw", "lr": 1e-4, "beta1": 0.9, "beta2": 0.999,
              "eps": 1e-8, "weight_decay": 0.01}
TINY_TRAIN = {
    "config": "tiny", "runner": "train_step", "chips": 1,
    "traffic": {"name": "toy_batches", "kind": "train_batches", "batch": 4,
                "seq": 32, "ring": 4},
    "autocast": "bfloat16", "control": "fp8", "optimizer": TINY_OPTIM,
    "reference": {"rows_per_block": 2},
    # set from CPU readings at this size (test_control.py says which)
    "limits": {"loss_gap": 5e-5, "grad_norm_gap": 0.05,
               "change_norm_gap": 0.017},
    "why": "throw-away"}
TINY_SERVE = {
    "config": "tiny", "runner": "serve_http", "chips": 1,
    "traffic": {"name": "toy_chat", "kind": "open_loop",
                "arrivals": "poisson", "rate_per_s": 4.0,
                "prompt_tokens": {"dist": "lognormal", "median": 10,
                                  "sigma": 0.5, "min": 4, "max": 20},
                "output_tokens": {"dist": "lognormal", "median": 5,
                                  "sigma": 0.5, "min": 2, "max": 8}},
    "engine": {"slots": 4, "num_pages": 16, "options": {"page_size": 8},
               "warm_up": {"prompt_tokens": [12, 3], "new_tokens": 3}},
    "control": "fp8", "reference": {"requests": 3, "prove_seconds": 2},
    "limits": {"served_logit_gap": 0.01}, "why": "throw-away"}
TINY4_CONFIG = dict(TINY_CONFIG, n_head=4, n_inner=256,
                    tie_word_embeddings=False)
TINY_SPMD = {
    "config": "tiny4", "runner": "spmd_train", "chips": 4,
    "traffic": {"name": "toy_batches4", "kind": "train_batches", "batch": 4,
                "seq": 32, "ring": 4},
    "parallel": {"dp": 2, "mp": 2}, "head_shards": 2, "batch_shards": 2,
    "compute_dtype": "bfloat16", "control": "fp8",
    "optimizer": {"name": "sgd", "lr": 0.001},
    "reference": {"rows_per_block": 2},
    "limits": {"loss_gap": 1e-3, "grad_norm_gap": 0.05,
               "change_norm_gap": 0.05},
    "why": "throw-away"}
CONFIGS = {"tiny": TINY_CONFIG, "tiny4": TINY4_CONFIG}
TINY_METRIC = '''"""A throw-away per-layer metric: steps the window finished."""


def read(ctx):
    from benchmarks import harness

    unit = harness.load_module("kernels", "toy_kernel").UNIT
    return unit * len(ctx.get("dispatch_seconds") or [])
'''
TINY_KERNEL = "UNIT = 1.0\n"


def throw_away_cell(tmp_path, monkeypatch, spec, name):
    """A configuration, a cell, a kernel count and a per-layer metric as
    new files under ``tmp_path`` plus one entry each in a copy of
    `BENCHMARK.json`: no file of the benchmark is edited.  Returns the
    `harness.Cell`."""
    from benchmarks import harness

    base = tmp_path / "benchmarks"
    for sub in ("configs", "workloads", "layer_metrics", "kernels"):
        (base / sub).mkdir(parents=True, exist_ok=True)
    config = spec["config"]
    (base / "configs" / f"{config}.json").write_text(
        json.dumps(CONFIGS[config]))
    (base / "workloads" / f"{name}.json").write_text(json.dumps(spec))
    (base / "layer_metrics" / "toy_steps.py").write_text(TINY_METRIC)
    (base / "kernels" / "toy_kernel.py").write_text(TINY_KERNEL)
    monkeypatch.setattr(harness, "SEARCH", [str(base)] + harness.SEARCH)
    bench = harness.load_benchmark()
    bench["configs"].append({"name": config, "source": "none",
                             "file": f"benchmarks/configs/{config}.json",
                             "reduced": [], "why": "throw-away"})
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": spec["traffic"]["name"],
                               "chips": spec["chips"],
                               "why": "throw-away"})
    reported = {"train_step": ["train_tokens_per_s"],
                "spmd_train": ["train_tokens_per_s"],
                "serve_http": ["ttft_p90_ms", "itl_p95_ms",
                               "serve_tokens_per_s"]}[spec["runner"]]
    have = {m["name"]: m for m in bench["end_to_end"]}
    for metric in reported:
        if metric in have:
            have[metric].setdefault("workloads", []).append(name)
        else:
            bench["end_to_end"].append({"name": metric, "unit": "x",
                                        "better": "lower", "bound": 0.1,
                                        "source": "host_clock",
                                        "workloads": [name]})
    for m in bench["per_layer"]:
        if m["moves"] in reported and "workloads" in m:
            m["workloads"].append(name)
    bench["per_layer"].append({"name": "toy_steps", "unit": "steps",
                               "better": "higher",
                               "source": "program_counter", "layer": "toy",
                               "moves": reported[0], "workloads": [name]})
    return harness.Cell(bench, name, root=str(tmp_path))


CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
