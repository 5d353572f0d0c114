"""The harness is driven by data: every name in `BENCHMARK.json` finds its
file, the metrics hang together, the runners run in-process at a toy size
on the CPU (control flow and the result line's keys only — no device-metric
value is asserted, and none is reported), the generator repeats from a
seed, and a cell is added with new files and one entry alone."""
import io
import json
import os
import re
import subprocess
import sys
from contextlib import redirect_stderr, redirect_stdout

import pytest

from conftest_paths import (CPU, ROOT, TINY_SERVE, TINY_SPMD, TINY_TRAIN,
                            throw_away_cell)

from benchmarks import harness, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = harness.load_benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_exactly_the_contracts_keys():
    assert sorted(BENCH) == ["command", "configs", "end_to_end", "paths",
                             "per_layer", "run_seconds", "workloads"]
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file_is_found_and_used(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    path = os.path.join(ROOT, cfg["file"])
    assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
    with open(path) as f:
        body = json.load(f)
    assert body["source"] == cfg["source"]
    assert sorted(body["reduced"]) == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert not re.search(r"(_dim|_rank|n_embd|n_inner|hidden|head)", key)
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_are_found_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    c = harness.Cell(BENCH, cell["name"])
    assert c.spec["config"] == cell["config"]
    assert c.spec["chips"] == cell["chips"] and cell["chips"] in (1, 4)
    assert c.spec["traffic"]["name"] == cell["traffic"]
    assert len(cell["why"]) <= 200
    runner = harness.load_module("runners", c.spec["runner"])
    assert callable(runner.run) and callable(runner.prove)
    names = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer(), "every cell reports a per-layer metric"
    for k, limit in c.limits.items():
        assert 0 <= limit < 1, f"{k}: set from readings, not a guess"


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_its_reader_and_moves_what_its_cells_report(
        metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert callable(harness.load_module("layer_metrics",
                                        metric["name"]).read)
    moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1
    for cell in metric["workloads"]:
        assert "workloads" not in moved[0] or cell in moved[0]["workloads"]
        assert any(w["name"] == cell for w in BENCH["workloads"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_names_units_and_sources_hold_only_what_is_allowed(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1


def test_every_name_is_a_name_and_none_is_used_twice():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in BENCH[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    assert all(NAME.match(w["traffic"]) for w in BENCH["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel


# ------------------------------------------------------------ the generator
CHAT = {"kind": "open_loop", "arrivals": "poisson", "rate_per_s": 1.5,
        "prompt_tokens": {"dist": "lognormal", "median": 200, "sigma": 0.6,
                          "min": 32, "max": 640},
        "output_tokens": {"dist": "lognormal", "median": 100, "sigma": 0.5,
                          "min": 16, "max": 256}}


def test_open_loop_repeats_exactly_from_a_seed():
    a = traffic.open_loop(CHAT, 50257, 2 ** 31 + 17, 40)
    b = traffic.open_loop(CHAT, 50257, 2 ** 31 + 17, 40)
    assert a == b and len(a) == 60
    assert all(0 <= r["due_s"] < 40 for r in a)
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    assert all(32 <= len(r["prompt"]) <= 640 for r in a)
    assert all(16 <= r["max_new_tokens"] <= 256 for r in a)
    assert all(4 <= t < 50257 for r in a for t in r["prompt"])


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.open_loop(CHAT, 50257, 1, 40)
    b = traffic.open_loop(CHAT, 50257, 2, 40)
    assert a != b
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert abs(a[-1]["due_s"] - b[-1]["due_s"]) < 1e-9


def test_an_order_seed_replays_one_schedule_with_other_token_ids():
    mix = dict(CHAT, order_seed=20260930)
    a = traffic.open_loop(mix, 50257, 1, 40)
    b = traffic.open_loop(mix, 50257, 2 ** 31 + 5, 40)
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in a] \
        == [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert a == traffic.open_loop(mix, 50257, 1, 40)


def test_shared_prefix_is_shared():
    mix = dict(CHAT, shared_prefix_tokens=24)
    reqs = traffic.open_loop(mix, 50257, 3, 10)
    assert len({tuple(r["prompt"][:24]) for r in reqs}) == 1


def test_train_batches_repeat_and_rows_all_differ():
    import numpy as np

    mix = {"kind": "train_batches", "batch": 4, "seq": 16, "ring": 3}
    a = traffic.train_batches(mix, 250, 2 ** 31 + 5)
    b = traffic.train_batches(mix, 250, 2 ** 31 + 5)
    c = traffic.train_batches(mix, 250, 2 ** 31 + 6)
    assert len(a) == 3
    for (ta, la), (tb, lb), (tc, _) in zip(a, b, c):
        assert np.array_equal(ta, tb) and np.array_equal(la, lb)
        assert not np.array_equal(ta, tc)
        assert np.array_equal(np.asarray(ta)[:, 1:], np.asarray(la)[:, :-1])
    rows = np.concatenate([np.asarray(t) for t, _ in a])
    assert len({tuple(r) for r in rows}) == len(rows)
    assert rows.min() >= 0 and rows.max() < 250


# ------------------------------------------------- the runners, in-process
def _run(cell, **kw):
    """`runner.run` with its two streams caught: (returned, last stdout
    line parsed, stderr text)."""
    runner = harness.load_module("runners", cell.spec["runner"])
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        ok = runner.run(cell, device=dict(CPU, count=cell.chips), **kw)
    return ok, json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("spec,name,e2e", [
    (TINY_TRAIN, "tiny-train", {"train_tokens_per_s", "setup_s"}),
    (TINY_SERVE, "tiny-serve", {"ttft_p90_ms", "itl_p95_ms",
                                "serve_tokens_per_s", "setup_s"}),
    (TINY_SPMD, "tiny-spmd", {"train_tokens_per_s", "setup_s"}),
], ids=["train_step", "serve_http", "spmd_train"])
def test_runner_runs_a_throw_away_cell_and_prints_the_line(
        tmp_path, monkeypatch, spec, name, e2e):
    cell = throw_away_cell(tmp_path, monkeypatch, spec, name)
    ok, line, err = _run(cell, seed=2 ** 31 + 12345, seconds=1.0,
                         trace=False)
    assert LINE_KEYS <= set(line) and list(line)[-1] == "compared"
    assert set(line["metrics"]) == e2e
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" in line["device"]
    for k, v in line["compared"].items():
        assert set(v) == {"value", "limit"}
        assert f"compared {k}:" in err
    assert ok == line["correct"]

    # the traced run reports per-layer metrics: the throw-away one, read
    # through the throw-away kernel file, and nothing of a device
    ok, line, _ = _run(cell, seed=7, seconds=1.0, trace=True)
    assert "toy_steps" in line["metrics"] or spec is TINY_SERVE
    assert not any("roofline" in k or "mfu" in k or "idle" in k
                   for k in line["metrics"])
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_run_refuses_without_a_chip():
    """On this machine JAX finds no TPU: non-zero exit, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr
