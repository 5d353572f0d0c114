"""The comparison that decides `correct` is one that has been shown to
fail: at a toy size on the CPU, the control (the plain reference in the
next lower precision, put in the program's place) and each fault the cell
can have come out as not correct, and the program as it stands comes out
correct.  On the chip the same readings were taken at the cells' own sizes
(`benchmarks/prove.py`; PERF.md gives them).

The toy cell's limits (`conftest_paths.TINY_TRAIN`) were set as the real
ones were, from eight seeds on the CPU at this size: above the largest the
program gave (loss gap 3.6e-5, gradient-norm gap 0.0077, change-norm gap
0.0133) and below the smallest the fp8 control gave in the number that
fails it (change-norm gap 0.0204) and the half batch gave in its
(gradient-norm gap 0.43).  At two layers of 64 the control stands only
1.5x above the program; at the cells' own sizes it is 5-7x (PERF.md).
"""
import pytest

from conftest_paths import (TINY_SERVE, TINY_SPMD, TINY_TRAIN,
                            throw_away_cell)
from test_harness import _run

from benchmarks import compare, harness  # noqa: E402

SEEDS = (5, 2 ** 31 + 77, 11)


def _within(numbers, limits):
    return all(numbers[k][0] <= limits[k] for k in limits)


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_and_half_batch_fail_where_the_program_passes(
        tmp_path, monkeypatch, seed):
    cell = throw_away_cell(tmp_path, monkeypatch, TINY_TRAIN, "tiny-train")
    runner = harness.load_module("runners", "train_step")
    row = runner.prove(cell, seed, control=True)
    assert _within(row["program"], cell.limits), row["program"]
    assert not _within(row["control"], cell.limits), row["control"]
    assert not _within(row["half_batch"], cell.limits), row["half_batch"]
    # a fault has to read ten times the program's number to count
    assert row["half_batch"]["grad_norm_gap"][0] > \
        10 * row["program"]["grad_norm_gap"][0]


def test_a_state_left_unchanged_reads_one():
    ref = {"losses": [1.0], "grad_norms": {"a": 2.0, "b": 1.0, "c": 3.0},
           "change_norms": {"a": 0.5, "b": 0.1, "c": 0.2}}
    still = {"losses": [1.0], "grad_norms": {k: 0.0 for k in "abc"},
             "change_norms": {k: 0.0 for k in "abc"}}
    numbers = compare.train_numbers(still, ref)
    assert numbers["grad_norm_gap"][0] == pytest.approx(1.0)
    assert numbers["change_norm_gap"][0] == pytest.approx(1.0)
    twice = dict(ref, change_norms={k: 2 * v for k, v in
                                    ref["change_norms"].items()})
    assert compare.train_numbers(twice, ref)["change_norm_gap"][0] == \
        pytest.approx(1.0)


def test_dead_leaves_are_left_out_by_the_references_gradient():
    ref = {"losses": [1.0],
           "grad_norms": {"a": 1.0, "b": 1.0, "k_b": 1e-9, "c": 2.0},
           "change_norms": {"a": 1.0, "b": 1.0, "k_b": 1.0, "c": 1.0}}
    got = dict(ref, change_norms=dict(ref["change_norms"], k_b=3.0))
    assert compare.train_numbers(got, ref)["change_norm_gap"][0] == 0.0
    got = dict(ref, change_norms=dict(ref["change_norms"], b=3.0))
    assert compare.train_numbers(got, ref)["change_norm_gap"] == (2.0, "b")


def _unchanged_state(monkeypatch):
    """The fault of a step that returns its state unchanged."""
    from paddle_tpu import optimizer

    monkeypatch.setattr(optimizer.AdamW, "apply_gradients",
                        lambda self, params, grads, state, lr, step=1,
                        lr_mults=None: (params, state))


def _half_batch(monkeypatch):
    """The fault of half of the batch left out, the mean over the rest."""
    from paddle_tpu import nn

    whole = nn.functional.cross_entropy

    def half(logits, labels, **kw):
        n = logits.shape[0] // 2
        return whole(logits[:n], labels[:n], **kw)

    monkeypatch.setattr(nn.functional, "cross_entropy", half)


@pytest.mark.parametrize("plant", [_unchanged_state, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_train_step_runs_and_comes_out_not_correct(
        tmp_path, monkeypatch, plant):
    cell = throw_away_cell(tmp_path, monkeypatch, TINY_TRAIN, "tiny-train")
    plant(monkeypatch)
    ok, line, err = _run(cell, seed=2 ** 31 + 9, seconds=0.3, trace=False)
    assert ok is False and line["correct"] is False
    assert "OVER" in err
    assert line["attempted"] > 0  # the window ran all the same


def _exchange_left_out(monkeypatch):
    """The fault of the exchange between chips left out: of every sum over
    `mp` only the first chip's part gets through."""
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.models import gpt_spmd

    class Lax:
        def __getattr__(self, name):
            return getattr(lax, name)

        @staticmethod
        def psum(x, axis):
            if axis != "mp":
                return lax.psum(x, axis)
            first = lax.axis_index("mp") == 0
            return lax.psum(jnp.where(first, x, jnp.zeros_like(x)), axis)

    monkeypatch.setattr(gpt_spmd, "lax", Lax())


def _spmd_state_unchanged(monkeypatch):
    """`gpt_spmd`'s step with a learning rate of nought: the parameters it
    was given come back unchanged."""
    from paddle_tpu.models import gpt_spmd

    whole = gpt_spmd.build_spmd_train_step
    monkeypatch.setattr(
        gpt_spmd, "build_spmd_train_step",
        lambda cfg, mesh, **kw: whole(cfg, mesh, **{**kw, "lr": 0.0}))


@pytest.mark.parametrize("plant", [_exchange_left_out, _spmd_state_unchanged],
                         ids=["exchange_left_out", "state_unchanged"])
def test_a_broken_hybrid_step_runs_and_comes_out_not_correct(
        tmp_path, monkeypatch, plant):
    cell = throw_away_cell(tmp_path, monkeypatch, TINY_SPMD, "tiny-spmd")
    plant(monkeypatch)
    ok, line, err = _run(cell, seed=2 ** 31 + 9, seconds=0.3, trace=False)
    assert ok is False and line["correct"] is False and "OVER" in err
    assert line["attempted"] > 0


def test_the_hybrid_step_as_it_stands_comes_out_correct(tmp_path,
                                                        monkeypatch):
    cell = throw_away_cell(tmp_path, monkeypatch, TINY_SPMD, "tiny-spmd")
    row = harness.load_module("runners", "spmd_train").prove(
        cell, 5, control=True)
    assert _within(row["program"], cell.limits), row["program"]
    assert not _within(row["half_batch"], cell.limits)


def test_an_altered_token_comes_out_not_correct(tmp_path, monkeypatch):
    """A token altered where it is produced: the engine's sampler answers
    the id after the one it chose, inside the step executables."""
    from paddle_tpu.inference import serving

    cell = throw_away_cell(tmp_path, monkeypatch, TINY_SERVE, "tiny-serve")
    ok, line, _ = _run(cell, seed=2 ** 31 + 9, seconds=1.0, trace=False)
    assert ok is True and line["compared"]["served_logit_gap"]["value"] <= \
        cell.limits["served_logit_gap"]

    whole = serving.sample_logits
    monkeypatch.setattr(
        serving, "sample_logits",
        lambda logits, **kw: (whole(logits, **kw) + 1) % logits.shape[-1])
    ok, line, err = _run(cell, seed=2 ** 31 + 9, seconds=1.0, trace=False)
    assert ok is False and "OVER" in err
    assert line["attempted"] > 0 and line["failed"] == 0
