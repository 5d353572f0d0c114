"""The serve loop's one-step lookahead (`DecodeEngine.step`).

With nothing that forces a drain, `step()` dispatches step N+1 from the
state step N leaves before it reads step N's tokens back; each decoding
slot's incoming token is read from step N's output on the device.  The
contracts pinned here:

* greedy tokens are the synchronous schedule's (`step(); drain()`) token
  for token: chunked prefills joining a running batch, finishes by
  length (whose slots admit the next request at the step the
  synchronous schedule does), an eos mid-batch, a cancel with the
  cancelled request's next row in flight;
* sampled tokens too, on a schedule where no admission waits on a slot
  an eos freed (step numbers, and so keys, keep their meaning);
* the pool partitions after every step with the pages that wait for
  the step in flight counted live, and no page the step in flight
  writes is free or another request's;
* a non-finite row with a step in flight quarantines only its slot; a
  fault at dispatch or at fetch drops the step in flight and leaves the
  host state at the last step landed, and the ladder's retry serves the
  same tokens;
* `lookahead_share` >= 0.9 on a steady decode run, and
  `lookahead_dropped_rows` counts the rows computed for requests that
  had finished by eos.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.inference.serving import (DecodeEngine, decode_stats,
                                          reset_decode_stats)


@pytest.fixture(autouse=True)
def _clean_telemetry():
    reset_decode_stats()
    obs.reset()
    yield
    obs.reset()


TINY = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                 max_seq_len=128, use_parallel_layers=False, dropout=0.0)

# (prompt length, new tokens) a request; three slots, so requests wait
# for the slots the first ones free by length
MIX = [(5, 8), (30, 5), (17, 12), (9, 3), (40, 7), (3, 10)]


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPT(TINY)
    m.eval()
    return m


def _prompts(mix=MIX, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, (n,)).astype(np.int32) for n, _ in mix]


def _engine(m, **kw):
    kw.setdefault("max_batch_size", 3)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 16)
    kw.setdefault("prefill_chunk_tokens", 8)
    return DecodeEngine(m, **kw)


def _serve(eng, sync, mix=MIX, join=None, on_step=None, seed=0):
    """Serve ``mix`` to the end, ``step(); drain()`` when ``sync``;
    requests of ``join`` ({call index: [(prompt, new)]}) are added before
    that call.  Returns the requests in order of submission."""
    reqs = [eng.add_request(p, max_new_tokens=n)
            for p, (_, n) in zip(_prompts(mix, seed), mix)]
    join = dict(join or {})
    calls = 0
    while eng._busy() or join:
        for p, n in join.pop(calls, ()):
            reqs.append(eng.add_request(p, max_new_tokens=n))
        eng.step()
        if sync:
            eng.drain()
        calls += 1
        if on_step is not None:
            on_step(eng, reqs, calls)
    return reqs


def _outputs(reqs):
    return [(list(r.generated_ids), r.finish_reason) for r in reqs]


def _joiners():
    rng = np.random.RandomState(7)
    return {3: [(rng.randint(0, 64, (21,)).astype(np.int32), 6)],
            6: [(rng.randint(0, 64, (12,)).astype(np.int32), 4),
                (rng.randint(0, 64, (33,)).astype(np.int32), 9)]}


def _eos_token(m):
    """A token the model emits mid-generation on MIX (greedy), so an
    eos set to it ends some requests early."""
    reqs = _serve(_engine(m), sync=True)
    for r in reqs:
        for t in r.generated_ids[1:-1]:
            return int(t)
    raise AssertionError("no mid-generation token to use as eos")


# ---------------------------------------------------------------------------
# parity with the synchronous schedule
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case", ["length", "joining", "eos", "int8_kv"])
def test_greedy_tokens_match_the_synchronous_schedule(model, case):
    kw = {}
    if case == "eos":
        kw["eos_token_id"] = _eos_token(model)
    if case == "int8_kv":
        # the step's output carries the refold count behind the tokens:
        # the next step reads its first B elements on the device
        kw["kv_quant"] = "int8"
    join = _joiners if case == "joining" else dict
    sync = _outputs(_serve(_engine(model, **kw), True, join=join()))
    reset_decode_stats()
    look = _outputs(_serve(_engine(model, **kw), False, join=join()))
    assert look == sync
    st = decode_stats()
    assert st["lookahead_steps"] > 0 and st["retraces_after_warmup"] == 0
    if case == "eos":
        assert any(reason == "eos" for _, reason in look)


def test_sampled_tokens_match_where_no_admission_waits_on_eos(model):
    kw = dict(sampler="top_k", top_k=8, temperature=1.0, seed=11)
    sync = _outputs(_serve(_engine(model, **kw), True))
    look = _outputs(_serve(_engine(model, **kw), False))
    assert look == sync
    assert [reason for _, reason in look] == ["length"] * len(MIX)


def test_cancel_with_the_next_row_in_flight(model):
    """The request is cancelled once its client holds 3 tokens; its next
    row is in flight then and is dropped where that step lands."""
    def cancel_at_three(eng, reqs, calls):
        r = reqs[2]
        if r.state == "running" and len(r.output_ids) == 3:
            if eng._inflight is not None:
                assert eng._inflight.carries(r.slot, r)
            eng.evict(r)
            # the pages are back at once: the step that writes them was
            # read back first
            eng._debug_check_pool()
            assert eng._inflight is None or not eng._inflight.release

    sync = _outputs(_serve(_engine(model), True, on_step=cancel_at_three))
    reset_decode_stats()
    look = _outputs(_serve(_engine(model), False, on_step=cancel_at_three))
    assert look == sync
    assert look[2] == (sync[2][0], "evicted") and len(look[2][0]) == 3
    assert decode_stats()["lookahead_dropped_rows"] == 1


# ---------------------------------------------------------------------------
# the pool while a step is in flight
# ---------------------------------------------------------------------------
def test_pool_partition_and_pages_under_a_write_in_flight(model):
    eng = _engine(model, eos_token_id=_eos_token(model))
    written = {}
    dispatch = eng._dispatch

    def spy(*a, **k):
        rec = dispatch(*a, **k)
        if rec is not None:
            pages = set()
            for s in range(eng._slots):
                for pos in range(int(rec.lens[s]),
                                 int(rec.lens[s]) + int(rec.caps[s])):
                    pages.add(int(eng._bt[s, pos // eng._page]))
            written[rec.step_no] = pages
        return rec

    eng._dispatch = spy
    waited = []

    def audit(eng, reqs, calls):
        eng._debug_check_pool()
        rec = eng._inflight
        if rec is None:
            return
        waited.append(sum(len(p) for p in rec.release))
        owners = {}
        for r in eng._by_slot:
            if r is not None:
                owners.update({p: r for p in r.pages})
        for p in written[rec.step_no]:
            assert p not in eng.pool._free_set, (p, "free under a write")
            owner = owners.get(p)
            assert owner is None or any(
                rec.carries(s, owner) for s in range(eng._slots)), p

    reqs = _serve(eng, False, on_step=audit)
    assert any(r.finish_reason == "eos" for r in reqs)
    assert max(waited) > 0  # an eos finish's pages waited for a step
    assert eng.pool.available_count == eng.pool.num_pages
    assert eng.pool.reserved == 0


# ---------------------------------------------------------------------------
# faults with a step in flight
# ---------------------------------------------------------------------------
def test_nan_row_in_flight_quarantines_only_its_slot(model):
    clean = _outputs(_serve(_engine(model), False))
    victim = []

    def poison(eng, reqs, calls):
        # the first decoding request off page 0 (block-table padding
        # points there): NaN in row 0 of its first page, which any
        # later owner of the page writes before it reads it
        if victim:
            return
        for i, r in enumerate(reqs):
            if r.state == "running" and r.output_ids and r.pages[0]:
                kv = eng._kv
                eng._kv = dataclasses.replace(
                    kv, k=kv.k.at[:, :, r.pages[0], 0].set(jnp.nan))
                victim.append(i)
                return

    reset_decode_stats()
    got = _outputs(_serve(_engine(model), False, on_step=poison))
    i = victim[0]
    assert got[i][1] == "fault"
    assert got[i][0] == clean[i][0][:len(got[i][0])]
    assert got[:i] + got[i + 1:] == clean[:i] + clean[i + 1:]
    st = decode_stats()
    assert st["finished_fault"] == 1
    assert st["lookahead_dropped_rows"] == 1  # its row in flight


class _Raising:
    """A step's jitted callable that consults ``fire`` first."""

    def __init__(self, fn, fire):
        self._fn, self._fire = fn, fire

    def __call__(self, *args):
        self._fire("dispatch")
        return self._fn(*args)

    def __getattr__(self, name):
        return getattr(self._fn, name)


@pytest.mark.parametrize("site", ["dispatch", "fetch"])
def test_fault_with_a_step_in_flight_leaves_the_last_landed_state(
        model, site):
    clean = _outputs(_serve(_engine(model), False))
    eng = _engine(model)
    state = {"armed": False}

    def arm(eng, reqs, calls):
        if calls == 5:
            assert eng._inflight is not None
            state.update(armed=True, reqs=reqs, landed=eng._step_no - 1,
                         outs=[list(r.output_ids) for r in reqs])

    def fire(where):
        if state["armed"]:
            state["armed"] = False
            raise RuntimeError(f"injected at {where}")

    if site == "dispatch":
        for tracker in (eng._mixed_fn_tracker(), eng._decode_fn_tracker()):
            tracker.fn = _Raising(tracker.fn, fire)
    else:
        host_fetch = eng._host_fetch

        def fetch(x):
            fire("fetch")
            return host_fetch(x)

        eng._host_fetch = fetch
    backoff = eng._resilience._backoff

    def at_the_ladder(attempt):
        # the ladder sees the fault only once the steps in flight were
        # dropped: the host state is the last step landed
        assert eng._inflight is None
        assert eng._step_no == state["landed"]
        assert [list(r.output_ids) for r in state["reqs"]] == state["outs"]
        state["ladder"] = attempt
        return backoff(attempt)

    eng._resilience._backoff = at_the_ladder
    got = _outputs(_serve(eng, False, on_step=arm))
    assert state.get("ladder") == 1
    assert got == clean
    assert decode_stats()["step_retries"] == 1


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------
def test_share_on_a_steady_decode_run(model):
    mix = [(6, 40), (9, 40), (4, 40)]
    _serve(_engine(model), False, mix=mix)
    st = decode_stats()
    assert st["lookahead_share"] >= 0.9, st["lookahead_share"]
    assert st["lookahead_drains"] == 1  # the engine going empty


def test_dropped_rows_are_the_eos_finishes(model):
    eos = _eos_token(model)
    mix = [(n, 60 - n) for n, _ in MIX]  # eos comes before the length
    reqs = _serve(_engine(model, eos_token_id=eos), False, mix=mix)
    st = decode_stats()
    n_eos = sum(r.finish_reason == "eos" for r in reqs)
    assert n_eos > 0 and st["finished_eos"] == n_eos
    assert st["lookahead_dropped_rows"] == n_eos
