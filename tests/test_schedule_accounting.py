"""Schedule accounting for ring-attention SP and ZeRO-3 (round-4
VERDICT #6) — the `test_pipeline_parallel.py::TestScheduleAccounting`
pattern extended to the other two distributed schedules: exact
collective COUNT and BYTE VOLUME per step, so a comms regression
(doubled gather, extra rotation) fails without TPU hardware.

Ring attention: explicit `lax.ppermute` calls — counted by patching.
ZeRO-3: GSPMD (XLA inserts the collectives) — counted from the compiled
HLO text, the ground truth of what the step actually executes.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer


class TestRingAttentionAccounting:
    B, H, S, D = 1, 2, 64, 8

    def _count_ppermutes(self, monkeypatch, fn):
        from jax import lax

        calls = []
        real = lax.ppermute

        def counting(x, axis_name, perm):
            if axis_name == "sp":
                calls.append((tuple(np.shape(x)),
                              np.dtype(x.dtype).itemsize))
            return real(x, axis_name, perm)

        import importlib

        ra = importlib.import_module("paddle_tpu.parallel.ring_attention")
        monkeypatch.setattr(ra.lax, "ppermute", counting)
        fn()
        return calls

    def test_forward_rotations_exact(self, monkeypatch):
        """N-1 rotations of K and of V — not N: the last block needs no
        onward send (the round-4 comm fix this test pins)."""
        from paddle_tpu.parallel.ring_attention import ring_attention

        n = 8
        mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
        q = jnp.zeros((self.B, self.H, self.S, self.D), jnp.float32)

        calls = self._count_ppermutes(
            monkeypatch,
            lambda: ring_attention(q, q, q, mesh, causal=True))

        assert len(calls) == 2 * (n - 1), len(calls)  # K and V each
        blk = (self.B, self.H, self.S // n, self.D)
        assert all(s == blk for s, _ in calls), calls[:3]
        total = sum(int(np.prod(s)) * b for s, b in calls)
        assert total == 2 * (n - 1) * int(np.prod(blk)) * 4

    def test_backward_hlo_rotation_count(self):
        """Count what actually EXECUTES: the compiled HLO's
        collective-permutes.  Forward = 2(N-1) (K and V, N-1 each).
        The grad step is ALSO exactly 2(N-1): the per-block custom vjp
        saves (q, k_blk, v_blk) residuals, so the backward recomputes
        attention blocks locally and only the residual-producing
        forward rotations remain after XLA DCEs the transposed chain.
        A doubled rotation (or a vjp that re-rotates) changes either
        count."""
        from paddle_tpu.parallel.ring_attention import \
            ring_attention_local
        from jax import shard_map

        n = 8
        mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
        q = jnp.ones((self.B, self.H, self.S, self.D), jnp.float32)

        def global_loss(qq, kk, vv):
            per = shard_map(
                lambda a, b, c: jnp.reshape(
                    ring_attention_local(a, b, c, "sp").sum(), (1,)),
                mesh=mesh, in_specs=(P(None, None, "sp"),) * 3,
                out_specs=P("sp"), check_vma=False)
            return per(qq, kk, vv).sum()

        hlo_f = jax.jit(global_loss).lower(q, q, q).compile().as_text()
        hlo_g = jax.jit(jax.grad(global_loss)).lower(
            q, q, q).compile().as_text()
        assert len(re.findall(r"collective-permute\(", hlo_f)) == \
            2 * (n - 1)
        assert len(re.findall(r"collective-permute\(", hlo_g)) == \
            2 * (n - 1)

    def test_doubling_a_rotation_would_trip(self, monkeypatch):
        """Negative control: an implementation that rotates N times
        (the pre-round-4 schedule) produces MORE calls than the pinned
        count — proving the counter counts what it claims."""
        from jax import lax
        from jax import shard_map

        n = 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
        x = jnp.ones((n * 2, 2), jnp.float32)
        calls = []
        real = lax.ppermute

        def counting(v, axis_name, perm):
            calls.append(tuple(np.shape(v)))
            return real(v, axis_name, perm)

        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(xs):
            cur = xs
            for i in range(n):  # deliberate: N rotations, not N-1
                cur = counting(cur, "sp", perm)
            return cur

        shard_map(body, mesh=mesh, in_specs=P("sp"), out_specs=P("sp"),
                  check_vma=False)(x)
        assert len(calls) == n  # > n - 1: the exact-count assert trips


class TestZero3Accounting:
    """ZeRO-3 per-step collective accounting from the compiled HLO.

    Model: Linear(16,32) + ReLU + Linear(32,16) on an 8-way dp mesh,
    zero_stage=3 — params and optimizer state sharded over dp.
    """

    IN, HID, OUT, NDEV = 16, 32, 16, 8

    @pytest.fixture()
    def compiled_hlo(self):
        from paddle_tpu.core import framework
        from paddle_tpu.distributed.fleet.sharded_step import \
            ShardedTrainStep

        model = nn.Sequential(nn.Linear(self.IN, self.HID), nn.ReLU(),
                              nn.Linear(self.HID, self.OUT))
        opt = optimizer.Momentum(0.1, parameters=model.parameters())
        mesh = Mesh(np.array(jax.devices()[:self.NDEV]), ("dp",))
        step = ShardedTrainStep(
            model, lambda m, x, y: ((m(x) - y) ** 2).mean(), opt, mesh,
            zero_stage=3)
        x = paddle.to_tensor(np.zeros((16, self.IN), np.float32))
        y = paddle.to_tensor(np.zeros((16, self.OUT), np.float32))
        step(x, y)

        parr = {k: step._params[k]._array for k in step._pnames}
        barr = {k: step._buffers[k]._array for k in step._bnames}
        batch = tuple(jax.device_put(v, step._batch_sharding)
                      for v in (np.zeros((16, self.IN), np.float32),
                                np.zeros((16, self.OUT), np.float32)))
        rng = framework.default_generator.next_key()
        with step.mesh:
            lowered = step._compiled.lower(
                parr, step._opt_state, barr,
                jnp.asarray(0.1, jnp.float32), step._step, rng, batch)
            return lowered.compile().as_text()

    @staticmethod
    def _collect(hlo, kind):
        """(shape-elements, bytes-per-element) of each `kind` op."""
        out = []
        # HLO line form: %name = f32[16,32]{1,0} all-gather(...)
        for m in re.finditer(
                r"=\s*\(?(\w+)\[([\d,]*)\][^\n(]*?" + kind + r"\(",
                hlo):
            dty, dims = m.group(1), m.group(2)
            numel = int(np.prod([int(d) for d in dims.split(",")])) \
                if dims else 1
            size = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4,
                    "f16": 2}.get(dty, 4)
            out.append((numel, size))
        return out

    def test_param_allgather_count_and_bytes(self, compiled_hlo):
        """EXACTLY one all-gather per parameter per step (XLA reuses the
        gathered copy between forward and backward) plus one activation
        gather for the replicated loss — a doubled gather (e.g. broken
        CSE or a second forward) fails the == immediately."""
        ags = self._collect(compiled_hlo, "all-gather")
        n_params = 4  # w1, b1, w2, b2
        assert len(ags) == n_params + 1, \
            (len(ags), re.findall(r"all-gather\([^\n]*", compiled_hlo))
        param_numels = [self.IN * self.HID, self.HID,
                        self.HID * self.OUT, self.OUT]
        act_numel = 16 * self.OUT  # batch x out, the replicated-loss path
        assert sorted(n for n, _ in ags) == sorted(
            param_numels + [act_numel]), sorted(n for n, _ in ags)
        total_bytes = sum(n * s for n, s in ags)
        assert total_bytes == (sum(param_numels) + act_numel) * 4

    def test_grad_reduction_is_single_fused_collective(self,
                                                      compiled_hlo):
        """All four gradients reduce in ONE variadic all-reduce (XLA's
        lowering of the reduce+keep-own-shard pattern on this mesh).
        A second reduction — e.g. grads reduced per-layer, or the loss
        reduced separately from the grads — changes the count."""
        ars = re.findall(r"all-reduce(?:\.\d+)?\s*=|all-reduce\(",
                         compiled_hlo)
        n_ar = len(re.findall(r"= \S+ all-reduce", compiled_hlo)) or \
            len(re.findall(r"all-reduce\(", compiled_hlo))
        assert n_ar == 1, re.findall(r"all-reduce[^\n]*",
                                     compiled_hlo)[:4]
        assert len(re.findall(r"reduce-scatter\(", compiled_hlo)) == 0

    def test_no_hidden_collectives(self, compiled_hlo):
        """Nothing else moves real data between devices: no
        collective-permute, and the single all-to-all XLA emits for the
        backward select_n resharding stays byte-bounded (8 pieces of
        [1,2,4] f32 = 256B — growth would mean activations started
        moving through it)."""
        assert not re.findall(r"collective-permute\(", compiled_hlo)
        a2a_lines = re.findall(r"all-to-all\([^\n]*", compiled_hlo)
        assert len(a2a_lines) <= 1, a2a_lines
        for m in re.finditer(
                r"=\s*\(((?:\w+\[[\d,]*\]\{[^}]*\},?\s*(?:/\*[^*]*\*/)?\s*)+)\)\s*all-to-all\(",
                compiled_hlo):
            pieces = re.findall(r"\w+\[([\d,]*)\]", m.group(1))
            total = sum(int(np.prod([int(d) for d in p.split(",")])) * 4
                        for p in pieces if p)
            assert total <= 512, total
