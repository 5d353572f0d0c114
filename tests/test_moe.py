"""MoE / expert-parallel tests (net-new capability — no reference
counterpart; see SURVEY.md §2.3 EP row).

Checks: gating math (capacity, top-k, combine normalization), single-device
MoELayer learning, and expert parallelism over an 8-device 'ep' mesh via
shard_map matching the single-device result.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.meta_parallel import (MoELayer, moe_forward,
                                                        moe_gating)


class TestGating:
    def test_top1_dispatch(self):
        logits = jnp.asarray(np.array([
            [5.0, 0.0], [4.0, 0.0], [0.0, 3.0], [0.0, 2.0]], np.float32))
        mask, combine, aux = moe_gating(logits, k=1, capacity=2)
        m = np.asarray(mask, np.float32)
        # tokens 0,1 -> expert 0 slots 0,1; tokens 2,3 -> expert 1 slots 0,1
        assert m[0, 0, 0] == 1 and m[1, 0, 1] == 1
        assert m[2, 1, 0] == 1 and m[3, 1, 1] == 1
        # k=1 keeps the raw gate prob as scale (Switch) so the router gets
        # task-loss gradient; each token's combine mass == its top-1 prob
        c = np.asarray(combine)
        logits_np = np.asarray(logits)
        probs = np.exp(logits_np) / np.exp(logits_np).sum(-1, keepdims=True)
        np.testing.assert_allclose(c.sum(axis=(1, 2)), probs.max(-1),
                                   rtol=1e-5)

    def test_capacity_drops_overflow(self):
        logits = jnp.asarray(np.array([[5.0, 0.0]] * 4, np.float32))
        mask, combine, aux = moe_gating(logits, k=1, capacity=2)
        c = np.asarray(combine)
        # only 2 of 4 tokens fit expert 0
        assert (c.sum(axis=(1, 2)) > 0).sum() == 2

    def test_top2_uses_two_experts(self):
        logits = jnp.asarray(np.array([[2.0, 1.0, -5.0]], np.float32))
        mask, combine, aux = moe_gating(logits, k=2, capacity=2)
        m = np.asarray(mask, np.float32)
        assert m[0, 0].sum() == 1 and m[0, 1].sum() == 1 and m[0, 2].sum() == 0
        assert float(np.asarray(combine).sum()) == pytest.approx(1.0, rel=1e-5)


class TestMoELayer:
    def test_forward_shape_and_aux(self):
        paddle.seed(0)
        layer = MoELayer(hidden_size=16, intermediate_size=32, num_experts=4,
                         k=2)
        x = paddle.randn([2, 6, 16])
        y = layer(x)
        assert y.shape == [2, 6, 16]
        assert layer.aux_loss is not None
        assert float(layer.aux_loss.numpy()) > 0

    @pytest.mark.slow
    def test_learns(self):
        paddle.seed(0)
        from paddle_tpu.optimizer import Adam

        layer = MoELayer(hidden_size=8, intermediate_size=16, num_experts=2,
                         k=1, capacity_factor=2.0)
        opt = Adam(learning_rate=1e-2, parameters=layer.parameters())
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(32, 8).astype(np.float32))
        target = paddle.to_tensor(rng.randn(32, 8).astype(np.float32))
        first = None
        for i in range(30):
            y = layer(x)
            loss = ((y - target) ** 2).mean() + 0.01 * layer.aux_loss
            loss.backward()
            opt.step()
            opt.clear_grad()
            if first is None:
                first = float(loss.numpy())
        assert float(loss.numpy()) < first * 0.7


class TestFleetGSPMD:
    def test_moe_under_sharded_train_step(self):
        """MoELayer with experts sharded over 'mp' compiles + runs through
        fleet.build_train_step (GSPMD path: partitioner inserts a2a)."""
        import paddle_tpu.nn as nn
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.optimizer import SGD

        paddle.seed(0)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.moe = MoELayer(hidden_size=8, intermediate_size=16,
                                    num_experts=4, k=2, capacity_factor=4.0,
                                    ep_axis="mp")
                self.head = nn.Linear(8, 4)

            def forward(self, x):
                return self.head(self.moe(x))

        st = DistributedStrategy()
        st.hybrid_configs = {"dp_degree": 4, "mp_degree": 2, "pp_degree": 1,
                             "sp_degree": 1, "sharding_degree": 1}
        fleet.init(strategy=st)
        model = Net()
        opt = SGD(learning_rate=0.01, parameters=model.parameters())

        def loss_fn(m, x, y):
            out = m(x)
            return ((out - y) ** 2).mean()

        step = fleet.build_train_step(model, loss_fn, opt)
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 6, 8).astype(np.float32))
        y = paddle.to_tensor(rng.randn(8, 6, 4).astype(np.float32))
        l0 = float(step(x, y).numpy())
        for _ in range(5):
            loss = step(x, y)
        assert np.isfinite(l0)
        assert float(loss.numpy()) < l0


class TestExpertParallel:
    def test_ep_matches_single_device(self):
        """shard_map over 'ep' with 8 devices == single-device moe_forward."""
        n = 8
        devices = jax.devices()[:n]
        mesh = Mesh(np.array(devices), ("ep",))
        rng = np.random.RandomState(1)
        t, h, f, e = 16, 8, 16, 8  # one expert per device
        x = rng.randn(t, h).astype(np.float32)
        gate_w = rng.randn(h, e).astype(np.float32)
        w1 = rng.randn(e, h, f).astype(np.float32) * 0.1
        b1 = np.zeros((e, f), np.float32)
        w2 = rng.randn(e, f, h).astype(np.float32) * 0.1
        b2 = np.zeros((e, h), np.float32)

        ref, ref_aux = moe_forward(jnp.asarray(x), jnp.asarray(gate_w),
                                   jnp.asarray(w1), jnp.asarray(b1),
                                   jnp.asarray(w2), jnp.asarray(b2),
                                   k=2, capacity_factor=8.0)

        from jax import shard_map

        def per_device(xv, gw, w1v, b1v, w2v, b2v):
            # tokens replicated over ep; experts sharded
            out, aux = moe_forward(xv, gw, w1v, b1v, w2v, b2v, k=2,
                                   capacity_factor=8.0, axis_name="ep")
            return out, aux

        fn = shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P(), P("ep"), P("ep"), P("ep"), P("ep")),
            out_specs=(P(), P()), check_vma=False)
        got, aux = jax.jit(fn)(x, gate_w, w1, b1, w2, b2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_ep_gradients_flow(self):
        n = 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("ep",))
        rng = np.random.RandomState(2)
        t, h, f, e = 8, 4, 8, 4
        args = (rng.randn(t, h).astype(np.float32),
                rng.randn(h, e).astype(np.float32),
                rng.randn(e, h, f).astype(np.float32) * 0.1,
                np.zeros((e, f), np.float32),
                rng.randn(e, f, h).astype(np.float32) * 0.1,
                np.zeros((e, h), np.float32))

        from jax import shard_map

        def loss_fn(x, gw, w1, b1, w2, b2):
            def per_device(xv, gwv, w1v, b1v, w2v, b2v):
                out, aux = moe_forward(xv, gwv, w1v, b1v, w2v, b2v, k=1,
                                       capacity_factor=4.0, axis_name="ep")
                return out, aux

            out, aux = shard_map(
                per_device, mesh=mesh,
                in_specs=(P(), P(), P("ep"), P("ep"), P("ep"), P("ep")),
                out_specs=(P(), P()), check_vma=False)(x, gw, w1, b1, w2, b2)
            return (out ** 2).mean() + 0.01 * aux.mean()

        grads = jax.jit(jax.grad(loss_fn, argnums=(1, 2)))(*args)
        assert all(np.isfinite(np.asarray(g)).all() for g in grads)
        assert float(np.abs(np.asarray(grads[1])).sum()) > 0
