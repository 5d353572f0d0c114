"""chip_smoke.py's control flow at a tiny width on the CPU, and where the
compile cache goes.

The script itself has no option or variable that lets it pass without a
TPU: the tests steer it by monkeypatching — a pretended device, tiny sizes,
and the kernel check answered for it (on the CPU the Pallas kernels are
not in the executables, which one test uses to see the check fail)."""
import json
import os
import subprocess
import sys

import pytest

import jax

import chip_smoke
from paddle_tpu.core import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_MODEL = dict(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                  max_seq_len=128)


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(chip_smoke, "GPT_BASE", TINY_MODEL)
    monkeypatch.setattr(chip_smoke, "TRAIN", dict(batch=2, seq=128, steps=3))
    monkeypatch.setattr(chip_smoke, "SERVE", dict(
        slots=4, num_pages=8, prompt_lens=(40, 70, 20), new_tokens=6,
        ragged_prompt_lens=(33, 65), warm_lens=(70, 9)))
    monkeypatch.setattr(chip_smoke, "FOUR", dict(
        train_batch=4, train_steps=2, loss_rtol=1e-3, slots=4, num_pages=8,
        prompt_lens=(20, 45), new_tokens=5))
    monkeypatch.setattr(
        chip_smoke, "require_tpu",
        lambda count: {"platform": "tpu", "kind": "pretended", "count": count})
    # a smoke under test must not switch this worker's JAX to a disk cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda cache_dir=None: "(not enabled under test)")


def _rows(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    return [json.loads(ln) for ln in lines]


def test_one_chip_control_flow(tiny, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "has_kernel", lambda compiled: True)
    assert chip_smoke.main([]) == 0
    rows = _rows(capsys)
    assert rows[-1] == {"ok": True, "device": {
        "platform": "tpu", "kind": "pretended", "count": 1}}
    phases = {r["phase"]: r for r in rows if "phase" in r}
    assert list(phases) == ["start", "train", "serve", "serve_ragged"]
    losses = phases["train"]["losses"]
    assert len(losses) == 3 and losses == sorted(losses, reverse=True)
    for name, n_req, n_exe in (("serve", 3, 2), ("serve_ragged", 2, 1)):
        ph = phases[name]
        assert len(ph["answers"]) == n_req
        assert all(len(a["tokens"]) == 6 and a["finish_reason"] == "length"
                   for a in ph["answers"])
        assert len(ph["executables"]) == n_exe
    # every executable reported its memory on a line of its own
    exes = [r for r in rows if "executable" in r]
    assert len(exes) == 1 + 2 + 1
    assert all(r["temp_bytes"] >= 0 and r["argument_bytes"] > 0
               for r in exes)


def test_four_chip_option_runs_only_the_cross_chip_paths(tiny, monkeypatch,
                                                         capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual CPU devices")
    monkeypatch.setattr(chip_smoke, "has_kernel", lambda compiled: True)
    assert chip_smoke.main(["--four-chips"]) == 0
    rows = _rows(capsys)
    assert rows[-1]["ok"] is True and rows[-1]["device"]["count"] == 4
    phases = {r["phase"]: r for r in rows if "phase" in r}
    assert list(phases) == ["start", "hybrid_train", "sharded_serve"]
    hy = phases["hybrid_train"]
    assert hy["dp2_mp2"]["losses"] == pytest.approx(
        hy["one_device"]["losses"], rel=1e-3)
    assert {tuple(s["devices"]) for s in hy["dp2_mp2"]["spread"]} == \
        {(0, 1, 2, 3)}
    sh = phases["sharded_serve"]
    assert {tuple(s["devices"]) for s in sh["spread"]} == {(0, 1, 2, 3)}
    assert sh["collectives"]["all-reduce"]["count"] > 0
    assert sh["collectives"]["all-reduce"]["bytes"] > 0


def test_a_contained_fault_or_a_retrace_fails_the_serve():
    quiet = {"retraces_after_warmup": 0, "legacy_fallbacks": 0, "steps": 9}
    assert chip_smoke._quiet(quiet, "x") == {
        "retraces_after_warmup": 0, "legacy_fallbacks": 0}
    for key in ("mixed_retraces", "legacy_fallbacks", "step_retries",
                "recoveries"):
        with pytest.raises(RuntimeError, match=key):
            chip_smoke._quiet({**quiet, key: 1}, "x")


def test_an_executable_without_its_kernel_fails_the_run(tiny, capsys):
    # the real check, on the CPU, where no executable holds a Pallas kernel
    with pytest.raises(RuntimeError, match="Pallas kernel was bypassed"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_plain_run_without_a_tpu_exits_nonzero():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs 1 TPU chip" in r.stderr


class TestCompileCachePlacement:
    @pytest.fixture
    def updates(self, monkeypatch):
        """Record what the function would set, and set nothing."""
        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        from jax.experimental.compilation_cache import compilation_cache

        monkeypatch.setattr(compilation_cache, "reset_cache", lambda: None)
        return seen

    def test_the_environment_variable_wins(self, updates, monkeypatch,
                                           tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
        # neither the default nor a caller's directory (the engine passes
        # FLAGS_compile_cache_dir) may override it
        for arg in (None, str(tmp_path / "flag")):
            assert compile_cache.enable_compile_cache(arg) == \
                str(tmp_path / "env")
        assert "jax_compilation_cache_dir" not in updates
        assert not (tmp_path / "flag").exists()

    def test_otherwise_one_fixed_path_inside_the_checkout(self, updates,
                                                          monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert compile_cache.DEFAULT_DIR == want
        assert compile_cache.enable_compile_cache() == want
        assert updates["jax_compilation_cache_dir"] == want
        ignored = subprocess.run(["git", "check-ignore", "-q", want],
                                 cwd=REPO).returncode
        assert ignored in (0, 128)  # 128: this copy is not a git checkout
