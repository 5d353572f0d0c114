"""In-program TRAINING ops (round-4 VERDICT #2): optimizer family, AMP
protocol ops, and collective ops executing from a ProgramDesc.

Reference capabilities matched:
- `operators/optimizers/adam_op.cc:1` (+ the optimizer family) — a
  reference training program's update ops run in-program;
- `operators/amp/check_finite_and_unscale_op.cc:1`,
  `update_loss_scaling_op.cc` — the static AMP protocol;
- `operators/collective/c_allreduce_op.h:1` — data-parallel programs
  with explicit collective ops (RawProgramOptimizer-style) run on a mesh.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import static
from paddle_tpu.static.interp import OP_TRANSLATORS, Scope, \
    blocks_context, run_block
from paddle_tpu.static.op_bridge import collective_axes
from test_op_bridge import bridge_run, bridge_run_lod, check, r, _encode_attr


class TestOptimizerOps:
    """Each optimizer translator vs an independent numpy step."""

    def test_adam_step(self):
        p, g = r(3), r(3, seed=1)
        lr = np.array([0.1], np.float32)
        m, v = np.zeros(3, np.float32), np.zeros(3, np.float32)
        got = bridge_run("adam",
                         {"Param": p, "Grad": g, "LearningRate": lr,
                          "Moment1": m, "Moment2": v,
                          "Beta1Pow": np.array([0.9], np.float32),
                          "Beta2Pow": np.array([0.999], np.float32)},
                         {"beta1": 0.9, "beta2": 0.999,
                          "epsilon": 1e-8},
                         outs=("ParamOut", "Moment1Out", "Moment2Out",
                               "Beta1PowOut", "Beta2PowOut"))
        m_n = 0.1 * g
        v_n = 0.001 * g * g
        lr_t = 0.1 * np.sqrt(1 - 0.999) / (1 - 0.9)
        exp = p - lr_t * m_n / (np.sqrt(v_n) + 1e-8 * np.sqrt(1 - 0.999))
        np.testing.assert_allclose(got["ParamOut"], exp, rtol=1e-5)
        np.testing.assert_allclose(got["Beta1PowOut"], [0.81], rtol=1e-5)

    def test_adamw_decoupled_decay(self):
        p, g = r(3) + 1.0, np.zeros(3, np.float32)
        lr = np.array([0.1], np.float32)
        got = bridge_run("adamw",
                         {"Param": p, "Grad": g, "LearningRate": lr},
                         {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
                          "coeff": 0.5, "with_decay": True},
                         outs=("ParamOut", "Moment1Out", "Moment2Out"))
        # zero grad => only the decoupled decay moves the param
        np.testing.assert_allclose(got["ParamOut"], p * (1 - 0.1 * 0.5),
                                   rtol=1e-5)

    def test_adagrad_rmsprop_adadelta(self):
        p, g = r(4), r(4, seed=1) + 0.1
        lr = np.array([0.5], np.float32)
        got = bridge_run("adagrad",
                         {"Param": p, "Grad": g, "LearningRate": lr},
                         {"epsilon": 1e-6},
                         outs=("ParamOut", "MomentOut"))
        np.testing.assert_allclose(
            got["ParamOut"], p - 0.5 * g / (np.abs(g) + 1e-6), rtol=1e-4)
        got = bridge_run("rmsprop",
                         {"Param": p, "Grad": g, "LearningRate": lr},
                         {"decay": 0.9, "epsilon": 1e-6, "momentum": 0.0,
                          "centered": False},
                         outs=("ParamOut", "MeanSquareOut", "MomentOut"))
        ms = 0.1 * g * g
        np.testing.assert_allclose(
            got["ParamOut"], p - 0.5 * g / np.sqrt(ms + 1e-6), rtol=1e-4)
        got = bridge_run("adadelta", {"Param": p, "Grad": g},
                         {"rho": 0.95, "epsilon": 1e-6},
                         outs=("ParamOut", "AvgSquaredGradOut",
                               "AvgSquaredUpdateOut"))
        asg = 0.05 * g * g
        upd = -np.sqrt(1e-6 / (asg + 1e-6)) * g
        np.testing.assert_allclose(got["ParamOut"], p + upd, rtol=1e-4)

    def test_lamb_lars(self):
        p = r(4) + 0.5
        g = r(4, seed=1) + 0.1
        lr = np.array([0.01], np.float32)
        got = bridge_run("lamb",
                         {"Param": p, "Grad": g, "LearningRate": lr},
                         {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6,
                          "weight_decay": 0.01},
                         outs=("ParamOut", "Moment1Out", "Moment2Out",
                               "Beta1PowOut", "Beta2PowOut"))
        m = 0.1 * g
        v = 0.001 * g * g
        m_hat = m / (1 - 0.9 * 0.9)  # input pow defaults to beta1
        v_hat = v / (1 - 0.999 * 0.999)
        # translator uses the DEFAULTED input pows (beta values)
        m_hat = m / (1 - 0.9)
        v_hat = v / (1 - 0.999)
        rr = m_hat / (np.sqrt(v_hat) + 1e-6) + 0.01 * p
        trust = np.linalg.norm(p) / np.linalg.norm(rr)
        np.testing.assert_allclose(got["ParamOut"], p - 0.01 * trust * rr,
                                   rtol=1e-4)
        got = bridge_run("lars_momentum",
                         {"Param": p, "Grad": g, "LearningRate": lr},
                         {"mu": 0.9, "lars_coeff": 0.001,
                          "lars_weight_decay": [0.0005]},
                         outs=("ParamOut", "VelocityOut"))
        pn, gn = np.linalg.norm(p), np.linalg.norm(g)
        llr = 0.01 * 0.001 * pn / (gn + 0.0005 * pn + 1e-30)
        vel = llr * (g + 0.0005 * p)
        np.testing.assert_allclose(got["ParamOut"], p - vel, rtol=1e-3)

    def test_ftrl_proximal_dpsgd(self):
        p, g = r(3), r(3, seed=1) + 0.1
        lr = np.array([0.1], np.float32)
        got = bridge_run("proximal_gd",
                         {"Param": p, "Grad": g, "LearningRate": lr},
                         {"l1": 0.0, "l2": 0.0}, outs=("ParamOut",))
        np.testing.assert_allclose(got["ParamOut"], p - 0.1 * g,
                                   rtol=1e-5)
        got = bridge_run("ftrl",
                         {"Param": p, "Grad": g, "LearningRate": lr},
                         {"l1": 0.0, "l2": 0.0, "lr_power": -0.5},
                         outs=("ParamOut", "SquaredAccumOut",
                               "LinearAccumOut"))
        assert np.isfinite(got["ParamOut"]).all()
        got = bridge_run("dpsgd",
                         {"Param": p, "Grad": g, "LearningRate": lr},
                         {"clip": 1e6, "sigma": 0.0, "batch_size": 1.0},
                         outs=("ParamOut",))
        np.testing.assert_allclose(got["ParamOut"], p - 0.1 * g,
                                   rtol=1e-4)

    def test_average_accumulates_window_roll(self):
        p = np.ones(3, np.float32)
        got = bridge_run(
            "average_accumulates",
            {"param": p,
             "in_num_accumulates": np.array([4], np.int64),
             "in_num_updates": np.array([4], np.int64)},
            {"average_window": 1.0, "max_average_window": 5,
             "min_average_window": 5},
            outs=("out_sum_1", "out_sum_2", "out_sum_3",
                  "out_num_accumulates", "out_old_num_accumulates",
                  "out_num_updates"))
        # 5th accumulate hits the window: sums roll into sum_3
        np.testing.assert_allclose(got["out_sum_3"], p, rtol=1e-6)
        assert int(got["out_num_accumulates"][0]) == 0
        assert int(got["out_num_updates"][0]) == 5


class TestReviewRegressionsR4:
    def test_adamax_minimize_runs(self):
        """Round-4 review: Adamax static lowering crashed on first run
        (beta1-pow var read before any write)."""
        from paddle_tpu.optimizer import Adamax

        prog = static.Program()
        b = prog.global_block()
        b.append_op("feed", {"X": "feed"}, {"Out": "x"}, {"col": 0})
        b.create_var("x", [4, 2], "float32")
        b.create_var("w", [2, 1], "float32", persistable=True)
        b.append_op("matmul_v2", {"X": "x", "Y": "w"}, {"Out": "pred"},
                    {})
        b.append_op("reduce_mean", {"X": "pred"}, {"Out": "loss"},
                    {"reduce_all": True})
        b.create_var("loss", [1], "float32")
        opt = Adamax(learning_rate=0.1)
        with static.program_guard(prog):
            opt.minimize(b.var("loss"))
        exe = static.Executor()
        exe.scope["w"] = jnp.ones((2, 1), jnp.float32)
        for _ in range(2):  # second run reads the written beta1 pow
            exe.run(prog, feed={"x": np.ones((4, 2), np.float32)},
                    fetch_list=["loss"])
        assert "w_beta1_pow_acc_0" in exe.scope
        # run t consumes pow=0.9^t and stores 0.9^(t+1): after 2 runs
        np.testing.assert_allclose(
            np.asarray(exe.scope["w_beta1_pow_acc_0"]),
            [0.9 ** 3], rtol=1e-5)

    def test_allreduce_prod_signs_and_zeros(self):
        """exp(psum(log)) would NaN on negatives; the sign/zero-safe
        reduction must not."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from paddle_tpu.static.op_bridge import _psum_prod

        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
        x = np.array([[-2.0, 0.0, 4.0], [3.0, 5.0, -1.0]], np.float32)
        f = shard_map(lambda v: _psum_prod(v, "dp"), mesh=mesh,
                      in_specs=P("dp"), out_specs=P("dp"),
                      check_vma=False)
        out = np.asarray(f(jnp.asarray(x)))
        np.testing.assert_allclose(out[0], [-6.0, 0.0, -4.0], rtol=1e-4)

    def test_batch_size_like_randoms_distinct_per_op(self):
        """Two same-seed random ops in one program draw DIFFERENT
        samples (per-op output-name key folding)."""
        x = np.zeros((6, 2), np.float32)
        a = bridge_run("gaussian_random_batch_size_like", {"Input": x},
                       {"shape": [1, 4], "seed": 0, "dtype": 5,
                        "input_dim_idx": 0, "output_dim_idx": 0})["Out"]
        scope = Scope({"input_v": jnp.asarray(x)})
        desc = {"type": "gaussian_random_batch_size_like",
                "inputs": [{"parameter": "Input",
                            "arguments": ["input_v"]}],
                "outputs": [{"parameter": "Out",
                             "arguments": ["other_name"]}],
                "attrs": [_encode_attr("shape", [1, 4]),
                          _encode_attr("dtype", 5)]}
        with blocks_context([{"ops": [desc]}]):
            run_block([desc], scope, {}, {})
        assert not np.allclose(a, np.asarray(scope["other_name"]))


class TestAmpOps:
    def test_check_finite_and_unscale(self):
        xs = {"X": [np.array([2.0, 4.0], np.float32),
                    np.array([6.0], np.float32)],
              "Scale": np.array([2.0], np.float32)}
        got = bridge_run("check_finite_and_unscale", xs, None,
                         outs=("Out*2", "FoundInfinite"))
        np.testing.assert_allclose(got["Out"][0], [1.0, 2.0])
        np.testing.assert_allclose(got["Out"][1], [3.0])
        assert not bool(got["FoundInfinite"][0])
        xs["X"][0][0] = np.inf
        got = bridge_run("check_finite_and_unscale", xs, None,
                         outs=("Out*2", "FoundInfinite"))
        assert bool(got["FoundInfinite"][0])

    def test_update_loss_scaling_decr_and_incr(self):
        base = {"PrevLossScaling": np.array([1024.0], np.float32),
                "InGoodSteps": np.array([0], np.int32),
                "InBadSteps": np.array([1], np.int32)}
        attrs = {"incr_every_n_steps": 2, "decr_every_n_nan_or_inf": 2,
                 "incr_ratio": 2.0, "decr_ratio": 0.5,
                 "stop_update": False}
        g = np.array([1.0, 2.0], np.float32)
        # overflow: second bad step halves the scale, grads zeroed
        got = bridge_run("update_loss_scaling",
                         {"X": [g],
                          "FoundInfinite": np.array([True]), **base},
                         attrs,
                         outs=("Out*1", "LossScaling", "OutGoodSteps",
                               "OutBadSteps"))
        np.testing.assert_allclose(got["LossScaling"], [512.0])
        np.testing.assert_allclose(got["Out"][0], [0.0, 0.0])
        # good step streak doubles it
        got = bridge_run("update_loss_scaling",
                         {"X": [g], "FoundInfinite": np.array([False]),
                          "PrevLossScaling": np.array([1024.0],
                                                      np.float32),
                          "InGoodSteps": np.array([1], np.int32),
                          "InBadSteps": np.array([0], np.int32)},
                         attrs,
                         outs=("Out*1", "LossScaling", "OutGoodSteps",
                               "OutBadSteps"))
        np.testing.assert_allclose(got["LossScaling"], [2048.0])
        np.testing.assert_allclose(got["Out"][0], g)


def _linreg_program(optype, opt_attrs, opt_extra_ins=(),
                    opt_extra_outs=(), amp=False):
    """y = x @ w training program in the reference style: forward +
    grads + (optionally the AMP protocol) + one optimizer op."""
    prog = static.Program()
    b = prog.global_block()
    b.append_op("feed", {"X": "feed"}, {"Out": "x"}, {"col": 0})
    b.append_op("feed", {"X": "feed"}, {"Out": "y"}, {"col": 1})
    for name, shape in [("x", [8, 4]), ("y", [8, 1])]:
        b.create_var(name, shape, "float32")
    b.create_var("w", [4, 1], "float32", persistable=True)
    b.append_op("matmul_v2", {"X": "x", "Y": "w"}, {"Out": "pred"}, {})
    b.append_op("elementwise_sub", {"X": "pred", "Y": "y"},
                {"Out": "diff"}, {})
    b.append_op("elementwise_mul", {"X": "diff", "Y": "diff"},
                {"Out": "sq"}, {})
    b.append_op("reduce_mean", {"X": "sq"}, {"Out": "loss"},
                {"reduce_all": True})
    # analytic grad of mse wrt w: 2/N * x^T diff  — written as program ops
    b.append_op("transpose2", {"X": "x"}, {"Out": "xT"},
                {"axis": [1, 0]})
    b.append_op("matmul_v2", {"X": "xT", "Y": "diff"}, {"Out": "gw_raw"},
                {})
    b.append_op("scale", {"X": "gw_raw"}, {"Out": "w@GRAD"},
                {"scale": 2.0 / 8.0, "bias": 0.0,
                 "bias_after_scale": True})
    b.append_op("fill_constant", {}, {"Out": "lr"},
                {"shape": [1], "dtype": 5, "value": 0.05})
    grad_name = "w@GRAD"
    if amp:
        b.create_var("loss_scaling", [1], "float32", persistable=True)
        b.create_var("good_steps", [1], "int32", persistable=True)
        b.create_var("bad_steps", [1], "int32", persistable=True)
        b.append_op("fill_constant", {}, {"Out": "scale_init"},
                    {"shape": [1], "dtype": 5, "value": 8.0})
        # pretend grads were computed under scale 8: scale then unscale
        b.append_op("scale", {"X": "w@GRAD"}, {"Out": "w@GRAD@scaled"},
                    {"scale": 8.0, "bias": 0.0,
                     "bias_after_scale": True})
        b.append_op("check_finite_and_unscale",
                    {"X": ["w@GRAD@scaled"], "Scale": "scale_init"},
                    {"Out": ["w@GRAD@unscaled"],
                     "FoundInfinite": "found_inf"}, {})
        b.append_op("update_loss_scaling",
                    {"X": ["w@GRAD@unscaled"],
                     "FoundInfinite": "found_inf",
                     "PrevLossScaling": "scale_init",
                     "InGoodSteps": "good_steps",
                     "InBadSteps": "bad_steps"},
                    {"Out": ["w@GRAD@final"],
                     "LossScaling": "loss_scaling",
                     "OutGoodSteps": "good_steps",
                     "OutBadSteps": "bad_steps"},
                    {"incr_every_n_steps": 1000,
                     "decr_every_n_nan_or_inf": 2, "incr_ratio": 2.0,
                     "decr_ratio": 0.5, "stop_update": False})
        grad_name = "w@GRAD@final"
    ins = {"Param": "w", "Grad": grad_name, "LearningRate": "lr"}
    outs = {"ParamOut": "w"}
    for pname, vname in opt_extra_ins:
        b.create_var(vname, [4, 1] if "Pow" not in pname else [1],
                     "float32", persistable=True)
        ins[pname] = vname
    for pname, vname in opt_extra_outs:
        outs[pname] = vname
    b.append_op(optype, ins, outs, opt_attrs)
    b.append_op("fetch", {"X": "loss"}, {"Out": "fetch"}, {"col": 0})
    return prog


ADAM_SLOTS = ([("Moment1", "w_m1"), ("Moment2", "w_m2"),
               ("Beta1Pow", "w_b1p"), ("Beta2Pow", "w_b2p")],
              [("Moment1Out", "w_m1"), ("Moment2Out", "w_m2"),
               ("Beta1PowOut", "w_b1p"), ("Beta2PowOut", "w_b2p")])


class TestInProgramTraining:
    """The VERDICT #2 acceptance: reference-style programs containing
    adam (+ AMP ops) train to DESCENDING loss through static.Executor."""

    @pytest.mark.parametrize("amp", [False, True])
    def test_adam_amp_program_descends(self, amp):
        prog = _linreg_program(
            "adam", {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
            *ADAM_SLOTS, amp=amp)
        exe = static.Executor()
        exe.scope["w"] = jnp.zeros((4, 1), jnp.float32)
        rng = np.random.RandomState(0)
        xv = rng.rand(8, 4).astype(np.float32)
        true_w = rng.rand(4, 1).astype(np.float32)
        yv = xv @ true_w
        losses = []
        for _ in range(30):
            loss = exe.run(prog, feed={"x": xv, "y": yv},
                           fetch_list=["loss"])[0]
            losses.append(float(np.asarray(loss)))
        assert losses[-1] < 0.1 * losses[0], losses[::6]

    def test_minimize_with_adam_roundtrips(self):
        """minimize() now lowers Adam into the program; the program
        must also SERIALIZE and reload (interchange contract)."""
        from paddle_tpu.optimizer import Adam

        prog = static.Program()
        b = prog.global_block()
        b.append_op("feed", {"X": "feed"}, {"Out": "x"}, {"col": 0})
        b.create_var("x", [4, 2], "float32")
        b.create_var("w", [2, 1], "float32", persistable=True)
        b.append_op("matmul_v2", {"X": "x", "Y": "w"}, {"Out": "pred"},
                    {})
        b.append_op("reduce_mean", {"X": "pred"}, {"Out": "loss"},
                    {"reduce_all": True})
        loss_var = b.var("loss") if b.has_var("loss") else \
            b.create_var("loss", [1], "float32")
        opt = Adam(learning_rate=0.1)
        with static.program_guard(prog):
            opt.minimize(loss_var)
        types = [o["type"] for o in prog.desc["blocks"][0]["ops"]]
        assert "adam" in types
        raw = prog.serialize_to_string()
        prog2 = static.Program.parse_from_string(raw)
        exe = static.Executor()
        exe.scope["w"] = jnp.ones((2, 1), jnp.float32)
        w0 = np.asarray(exe.scope["w"]).copy()
        exe.run(prog2, feed={"x": np.ones((4, 2), np.float32)},
                fetch_list=["loss"])
        assert not np.allclose(np.asarray(exe.scope["w"]), w0)

    @pytest.mark.parametrize("optype,attrs,slots", [
        ("rmsprop", {"decay": 0.9, "epsilon": 1e-6, "momentum": 0.9,
                     "centered": False},
         ([("MeanSquare", "w_ms"), ("Moment", "w_mom")],
          [("MeanSquareOut", "w_ms"), ("MomentOut", "w_mom")])),
        ("adagrad", {"epsilon": 1e-6},
         ([("Moment", "w_mom")], [("MomentOut", "w_mom")])),
        ("lamb", {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6,
                  "weight_decay": 0.0},
         ([("Moment1", "w_m1"), ("Moment2", "w_m2")],
          [("Moment1Out", "w_m1"), ("Moment2Out", "w_m2")])),
    ])
    def test_other_optimizers_descend(self, optype, attrs, slots):
        prog = _linreg_program(optype, attrs, *slots)
        exe = static.Executor()
        exe.scope["w"] = jnp.zeros((4, 1), jnp.float32)
        rng = np.random.RandomState(0)
        xv = rng.rand(8, 4).astype(np.float32)
        yv = xv @ rng.rand(4, 1).astype(np.float32)
        losses = [float(np.asarray(exe.run(
            prog, feed={"x": xv, "y": yv}, fetch_list=["loss"])[0]))
            for _ in range(40)]
        assert losses[-1] < 0.5 * losses[0], (optype, losses[::8])


class TestCollectiveOps:
    """c_* ops lowered onto mesh axes (reference
    operators/collective/c_allreduce_op.h:1)."""

    def _run_on_mesh(self, optype, x, attrs, n=2, extra_ins=None,
                     outs=("Out",), out_name="Out"):
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        devs = np.array(jax.devices()[:n])
        mesh = Mesh(devs, ("dp",))
        desc_in = [{"parameter": "X", "arguments": ["xin"]}]
        for pname, _ in (extra_ins or {}).items():
            desc_in.append({"parameter": pname,
                            "arguments": [pname.lower() + "_v"]})
        desc = {"type": optype, "inputs": desc_in,
                "outputs": [{"parameter": o, "arguments": [o.lower()]}
                            for o in outs],
                "attrs": [_encode_attr(k, v) for k, v in attrs.items()]}

        def per_device(xs):
            scope = Scope({"xin": xs})
            for pname, v in (extra_ins or {}).items():
                scope[pname.lower() + "_v"] = jnp.asarray(v)
            with blocks_context([{"ops": [desc]}]), \
                    collective_axes(default="dp"):
                run_block([desc], scope, {}, {})
            return scope[out_name.lower()]

        f = shard_map(per_device, mesh=mesh, in_specs=P("dp"),
                      out_specs=P("dp"), check_vma=False)
        return np.asarray(f(jnp.asarray(x)))

    def test_c_allreduce_sum(self):
        x = np.arange(8, dtype=np.float32).reshape(2, 4)
        out = self._run_on_mesh("c_allreduce_sum", x, {"ring_id": 0})
        # every shard row holds the cross-shard sum of its slice
        exp = np.tile(x.sum(0, keepdims=True), (2, 1))
        np.testing.assert_allclose(out, exp)

    def test_c_allgather_and_reducescatter(self):
        x = np.arange(8, dtype=np.float32).reshape(4, 2)
        out = self._run_on_mesh("c_allgather", x,
                                {"ring_id": 0, "nranks": 2})
        # each shard gathers both [2,2] slices -> [4,2] per shard,
        # stacked over the dp dim -> [8,2] global
        assert out.shape == (8, 2)
        np.testing.assert_allclose(out[:4], x)
        out = self._run_on_mesh("c_reducescatter", x,
                                {"ring_id": 0, "nranks": 2})
        # [2,2] per shard reduced+scattered -> [1,2] per shard
        assert out.shape == (2, 2)
        np.testing.assert_allclose(out[0], x[0] + x[2])

    def test_c_broadcast(self):
        x = np.stack([np.zeros(3, np.float32),
                      np.ones(3, np.float32)])
        out = self._run_on_mesh("c_broadcast", x,
                                {"ring_id": 0, "root": 1})
        np.testing.assert_allclose(out, np.ones((2, 3), np.float32))

    def test_identity_outside_mesh(self):
        # single-process: collectives are identity (world size 1)
        x = r(3)
        got = bridge_run("c_allreduce_sum", {"X": x}, {"ring_id": 0})
        np.testing.assert_allclose(got["Out"], x)

    def test_dp2_program_matches_single_process(self):
        """RawProgramOptimizer-style data-parallel program: grads
        all-reduced via c_allreduce_sum + averaged, sgd step — dp=2 on
        the CPU mesh must match the fused single-process batch."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        prog = static.Program()
        b = prog.global_block()
        b.append_op("feed", {"X": "feed"}, {"Out": "x"}, {"col": 0})
        b.append_op("feed", {"X": "feed"}, {"Out": "y"}, {"col": 1})
        b.create_var("x", [4, 3], "float32")
        b.create_var("y", [4, 1], "float32")
        b.create_var("w", [3, 1], "float32", persistable=True)
        b.append_op("matmul_v2", {"X": "x", "Y": "w"}, {"Out": "pred"},
                    {})
        b.append_op("elementwise_sub", {"X": "pred", "Y": "y"},
                    {"Out": "diff"}, {})
        b.append_op("transpose2", {"X": "x"}, {"Out": "xT"},
                    {"axis": [1, 0]})
        b.append_op("matmul_v2", {"X": "xT", "Y": "diff"},
                    {"Out": "gw_local"}, {})
        b.append_op("c_allreduce_sum", {"X": "gw_local"},
                    {"Out": "gw_sum"}, {"ring_id": 0})
        b.append_op("scale", {"X": "gw_sum"}, {"Out": "w@GRAD"},
                    {"scale": 2.0 / 8.0, "bias": 0.0,
                     "bias_after_scale": True})
        b.append_op("fill_constant", {}, {"Out": "lr"},
                    {"shape": [1], "dtype": 5, "value": 0.1})
        b.append_op("sgd", {"Param": "w", "Grad": "w@GRAD",
                            "LearningRate": "lr"},
                    {"ParamOut": "w"}, {})

        rng = np.random.RandomState(0)
        xv = rng.rand(8, 3).astype(np.float32)
        yv = rng.rand(8, 1).astype(np.float32)
        w0 = np.zeros((3, 1), np.float32)

        ops = prog.desc["blocks"][0]["ops"]

        def one_step(xs, ys, w):
            scope = Scope({"w": w})
            with blocks_context([{"ops": ops}]), \
                    collective_axes(default="dp"):
                run_block(ops, scope, {"x": xs, "y": ys}, {})
            return scope["w"]

        devs = np.array(jax.devices()[:2])
        mesh = Mesh(devs, ("dp",))
        stepped = shard_map(
            one_step, mesh=mesh,
            in_specs=(P("dp"), P("dp"), P()), out_specs=P(),
            check_vma=False)
        w_dp = np.asarray(stepped(jnp.asarray(xv), jnp.asarray(yv),
                                  jnp.asarray(w0)))

        # single-process fused batch: same math, collective = identity
        diff = xv @ w0 - yv
        gw = 2.0 / 8.0 * (xv.T @ diff)
        w_ref = w0 - 0.1 * gw
        np.testing.assert_allclose(w_dp, w_ref, rtol=1e-5, atol=1e-6)

    def test_full_raw_program_op_set(self):
        """The FULL RawProgramOptimizer output (SURVEY §3.3 steps 3-4):
        startup bootstrap ops (c_gen_nccl_id + c_comm_init), main-program
        sync/marker ops, and coalesce_tensor whose Output vars ALIAS the
        fused buffer — the optimizer reads each grad through the alias
        AFTER the single fused c_allreduce_sum, so wrong aliasing gives
        a numerically wrong step, not just a load failure."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map

        startup = static.Program()
        sb = startup.global_block()
        sb.append_op("c_gen_nccl_id", {}, {"Out": "nccl_id_0"},
                     {"ring_id": 0})
        sb.append_op("c_comm_init", {"X": "nccl_id_0"}, {},
                     {"ring_id": 0, "nranks": 2, "rank": 0})

        prog = static.Program()
        b = prog.global_block()
        b.append_op("feed", {"X": "feed"}, {"Out": "x"}, {"col": 0})
        b.append_op("feed", {"X": "feed"}, {"Out": "y"}, {"col": 1})
        b.create_var("x", [4, 3], "float32")
        b.create_var("y", [4, 1], "float32")
        for w in ("w1", "w2"):
            b.create_var(w, [3, 1], "float32", persistable=True)
        b.append_op("marker", {}, {}, {"marker_role": "forward",
                                       "marker_pos": "B"})
        b.append_op("matmul_v2", {"X": "x", "Y": "w1"}, {"Out": "p1"},
                    {})
        b.append_op("matmul_v2", {"X": "x", "Y": "w2"}, {"Out": "p2"},
                    {})
        b.append_op("elementwise_add", {"X": "p1", "Y": "p2"},
                    {"Out": "pred"}, {})
        b.append_op("elementwise_sub", {"X": "pred", "Y": "y"},
                    {"Out": "diff"}, {})
        b.append_op("transpose2", {"X": "x"}, {"Out": "xT"},
                    {"axis": [1, 0]})
        b.append_op("matmul_v2", {"X": "xT", "Y": "diff"},
                    {"Out": "g1"}, {})
        b.append_op("matmul_v2", {"X": "xT", "Y": "diff"},
                    {"Out": "g2"}, {})
        b.append_op("c_sync_calc_stream", {"X": ["g1", "g2"]},
                    {"Out": ["g1", "g2"]}, {})
        b.append_op("coalesce_tensor", {"Input": ["g1", "g2"]},
                    {"Output": ["g1", "g2"],
                     "FusedOutput": "fused_grad"},
                    {"copy_data": True, "dtype": 5, "use_align": True})
        b.append_op("c_allreduce_sum", {"X": "fused_grad"},
                    {"Out": "fused_grad"}, {"ring_id": 0})
        b.append_op("c_sync_comm_stream", {"X": "fused_grad"},
                    {"Out": "fused_grad"}, {"ring_id": 0})
        b.append_op("fill_constant", {}, {"Out": "lr"},
                    {"shape": [1], "dtype": 5, "value": 0.1})
        for w, g in (("w1", "g1"), ("w2", "g2")):
            b.append_op("scale", {"X": g}, {"Out": w + "@GRAD"},
                        {"scale": 2.0 / 8.0, "bias": 0.0,
                         "bias_after_scale": True})
            b.append_op("sgd", {"Param": w, "Grad": w + "@GRAD",
                                "LearningRate": "lr"},
                        {"ParamOut": w}, {})

        rng = np.random.RandomState(1)
        xv = rng.rand(8, 3).astype(np.float32)
        yv = rng.rand(8, 1).astype(np.float32)
        w0 = {"w1": rng.rand(3, 1).astype(np.float32),
              "w2": rng.rand(3, 1).astype(np.float32)}

        sops = startup.desc["blocks"][0]["ops"]
        mops = prog.desc["blocks"][0]["ops"]

        def one_step(xs, ys, w1, w2):
            scope = Scope({"w1": w1, "w2": w2})
            with blocks_context([{"ops": sops + mops}]), \
                    collective_axes(default="dp"):
                run_block(sops, scope, {}, {})
                run_block(mops, scope, {"x": xs, "y": ys}, {})
            return scope["w1"], scope["w2"]

        devs = np.array(jax.devices()[:2])
        mesh = Mesh(devs, ("dp",))
        stepped = shard_map(
            one_step, mesh=mesh,
            in_specs=(P("dp"), P("dp"), P(), P()),
            out_specs=(P(), P()), check_vma=False)
        w1_dp, w2_dp = stepped(jnp.asarray(xv), jnp.asarray(yv),
                               jnp.asarray(w0["w1"]),
                               jnp.asarray(w0["w2"]))

        # single-process fused batch reference
        diff = xv @ w0["w1"] + xv @ w0["w2"] - yv
        gw = 2.0 / 8.0 * (xv.T @ diff)
        np.testing.assert_allclose(np.asarray(w1_dp),
                                   w0["w1"] - 0.1 * gw,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(w2_dp),
                                   w0["w2"] - 0.1 * gw,
                                   rtol=1e-5, atol=1e-6)

    def test_coalesce_alias_reads_post_write_values(self):
        """FusedSlice semantics in isolation: after coalesce, a write to
        the fused buffer is observed by reads of the component vars."""
        from paddle_tpu.static.interp import Scope, run_block, \
            blocks_context

        a = np.arange(6, dtype=np.float32).reshape(2, 3)
        c = np.arange(4, dtype=np.float32).reshape(4) + 100
        desc = [
            {"type": "coalesce_tensor",
             "inputs": [{"parameter": "Input", "arguments": ["a", "c"]}],
             "outputs": [
                 {"parameter": "Output", "arguments": ["a", "c"]},
                 {"parameter": "FusedOutput", "arguments": ["fused"]}],
             "attrs": [_encode_attr("copy_data", True),
                       _encode_attr("dtype", 5)]},
            {"type": "scale",
             "inputs": [{"parameter": "X", "arguments": ["fused"]}],
             "outputs": [{"parameter": "Out", "arguments": ["fused"]}],
             "attrs": [_encode_attr("scale", 2.0),
                       _encode_attr("bias", 0.0),
                       _encode_attr("bias_after_scale", True)]},
        ]
        scope = Scope({"a": jnp.asarray(a), "c": jnp.asarray(c)})
        with blocks_context([{"ops": desc}]):
            run_block(desc, scope, {}, {})
        np.testing.assert_allclose(np.asarray(scope["fused"]),
                                   np.concatenate([a.ravel(),
                                                   c.ravel()]) * 2)
        np.testing.assert_allclose(np.asarray(scope["a"]), a * 2)
        np.testing.assert_allclose(np.asarray(scope["c"]), c * 2)

    def test_coalesce_component_writes_land_in_fused_buffer(self):
        """The fuse-grad-space layout: coalesce_tensor(set_constant)
        runs BEFORE the grad-producing ops, which then write the
        component vars — the writes must land in the fused buffer
        (reference sub-tensors share storage) so the later fused
        allreduce reads live gradients, not the initial constant."""
        from paddle_tpu.static.interp import Scope, run_block, \
            blocks_context

        g1 = np.arange(6, dtype=np.float32).reshape(2, 3)
        g2 = np.arange(4, dtype=np.float32) + 100
        desc = [
            {"type": "coalesce_tensor",
             "inputs": [{"parameter": "Input",
                         "arguments": ["g1", "g2"]}],
             "outputs": [
                 {"parameter": "Output", "arguments": ["g1", "g2"]},
                 {"parameter": "FusedOutput", "arguments": ["fused"]}],
             "attrs": [_encode_attr("set_constant", True),
                       _encode_attr("constant", 0.0),
                       _encode_attr("dtype", 5)]},
            # "backward": writes the component vars after coalescing
            {"type": "scale",
             "inputs": [{"parameter": "X", "arguments": ["src1"]}],
             "outputs": [{"parameter": "Out", "arguments": ["g1"]}],
             "attrs": [_encode_attr("scale", 1.0),
                       _encode_attr("bias", 0.0),
                       _encode_attr("bias_after_scale", True)]},
            {"type": "scale",
             "inputs": [{"parameter": "X", "arguments": ["src2"]}],
             "outputs": [{"parameter": "Out", "arguments": ["g2"]}],
             "attrs": [_encode_attr("scale", 1.0),
                       _encode_attr("bias", 0.0),
                       _encode_attr("bias_after_scale", True)]},
            # fused "allreduce" stand-in reads the buffer
            {"type": "scale",
             "inputs": [{"parameter": "X", "arguments": ["fused"]}],
             "outputs": [{"parameter": "Out", "arguments": ["fused"]}],
             "attrs": [_encode_attr("scale", 2.0),
                       _encode_attr("bias", 0.0),
                       _encode_attr("bias_after_scale", True)]},
        ]
        # like a real program: g1/g2 have NO value yet when coalesce
        # runs — their sizes come from the block var descs
        def _vdesc(name, dims):
            return {"name": name,
                    "type": {"lod_tensor": {"tensor": {
                        "data_type": 5, "dims": list(dims)}}}}

        scope = Scope({"src1": jnp.asarray(g1),
                       "src2": jnp.asarray(g2)})
        with blocks_context([{"ops": desc,
                              "vars": [_vdesc("g1", g1.shape),
                                       _vdesc("g2", g2.shape)]}]):
            run_block(desc, scope, {}, {})
        np.testing.assert_allclose(
            np.asarray(scope["fused"]),
            np.concatenate([g1.ravel(), g2.ravel()]) * 2)
        np.testing.assert_allclose(np.asarray(scope["g1"]), g1 * 2)
        np.testing.assert_allclose(np.asarray(scope["g2"]), g2 * 2)


class TestQuantFakeOps:
    def test_fake_quantize_abs_max(self):
        x = (r(3, 4) - 0.5).astype(np.float32)
        got = bridge_run("fake_quantize_abs_max", {"X": x},
                         {"bit_length": 8}, outs=("Out", "OutScale"))
        scale = np.abs(x).max()
        np.testing.assert_allclose(got["OutScale"], [scale], rtol=1e-6)
        np.testing.assert_allclose(got["Out"],
                                   np.round(x / scale * 127), atol=0.5)

    def test_fake_quant_dequant_roundtrip(self):
        x = (r(3, 4) - 0.5).astype(np.float32)
        got = bridge_run("fake_quantize_dequantize_abs_max", {"X": x},
                         {"bit_length": 8}, outs=("Out", "OutScale"))
        np.testing.assert_allclose(got["Out"], x, atol=np.abs(x).max()
                                   / 127 + 1e-6)

    def test_fake_channel_wise(self):
        x = (r(4, 3) - 0.5).astype(np.float32)
        got = bridge_run("fake_channel_wise_quantize_abs_max", {"X": x},
                         {"bit_length": 8, "quant_axis": 0},
                         outs=("Out", "OutScale"))
        np.testing.assert_allclose(got["OutScale"],
                                   np.abs(x).max(1), rtol=1e-6)

    def test_fake_dequantize(self):
        q = np.array([[-127, 0, 127]], np.float32)
        got = bridge_run("fake_dequantize_max_abs",
                         {"X": q, "Scale": np.array([0.5], np.float32)},
                         {"max_range": 127.0})
        np.testing.assert_allclose(got["Out"], [[-0.5, 0, 0.5]],
                                   rtol=1e-6)


class TestPersistenceOps:
    def test_save_load_roundtrip(self, tmp_path):
        x = r(3, 4)
        path = str(tmp_path / "x.pdtensor")
        bridge_run("save", {"X": x}, {"file_path": path}, outs=())
        got = bridge_run("load", None, {"file_path": path})
        np.testing.assert_allclose(got["Out"], x)

    def test_save_combine_roundtrip(self, tmp_path):
        a, bb = r(2, 2), r(3, seed=1)
        path = str(tmp_path / "combined")
        scope = Scope({"a": jnp.asarray(a), "b": jnp.asarray(bb)})
        desc = {"type": "save_combine",
                "inputs": [{"parameter": "X", "arguments": ["a", "b"]}],
                "outputs": [],
                "attrs": [_encode_attr("file_path", path)]}
        with blocks_context([{"ops": [desc]}]):
            run_block([desc], scope, {}, {})
        desc2 = {"type": "load_combine", "inputs": [],
                 "outputs": [{"parameter": "Out",
                              "arguments": ["a2", "b2"]}],
                 "attrs": [_encode_attr("file_path", path)]}
        scope2 = Scope()
        with blocks_context([{"ops": [desc2]}]):
            run_block([desc2], scope2, {}, {})
        np.testing.assert_allclose(np.asarray(scope2["a2"]), a)
        np.testing.assert_allclose(np.asarray(scope2["b2"]), bb)


class TestMetricOps:
    def test_auc(self):
        pred = np.array([[0.9, 0.1], [0.2, 0.8], [0.3, 0.7],
                         [0.6, 0.4]], np.float32)
        label = np.array([[0], [1], [1], [0]], np.int64)
        got = bridge_run("auc", {"Predict": pred, "Label": label},
                         {"num_thresholds": 4095, "curve": "ROC"},
                         outs=("AUC", "StatPosOut", "StatNegOut"))
        # positives score {0.8, 0.7} both above negatives {0.1, 0.4}
        np.testing.assert_allclose(float(got["AUC"]), 1.0, atol=1e-3)

    def test_precision_recall(self):
        idx = np.array([0, 1, 1, 0], np.int64)
        lab = np.array([0, 1, 0, 0], np.int64)
        got = bridge_run("precision_recall",
                         {"Indices": idx, "Labels": lab},
                         {"class_number": 2},
                         outs=("BatchMetrics", "AccumMetrics",
                               "AccumStatesInfo"))
        # micro precision = 3/4
        np.testing.assert_allclose(got["BatchMetrics"][3], 0.75,
                                   rtol=1e-5)

    def test_positive_negative_pair(self):
        score = np.array([0.9, 0.2, 0.8, 0.3], np.float32)
        label = np.array([1, 0, 1, 0], np.float32)
        qid = np.array([0, 0, 1, 1], np.int64)
        got = bridge_run("positive_negative_pair",
                         {"Score": score, "Label": label,
                          "QueryID": qid},
                         None, outs=("PositivePair", "NegativePair",
                                     "NeutralPair"))
        assert float(got["PositivePair"][0]) == 2.0
        assert float(got["NegativePair"][0]) == 0.0


class TestChunkEval:
    """chunk_eval translator (operators/metrics/chunk_eval_op.h):
    IOB chunk extraction vs hand-counted spans."""

    def test_iob_counts_and_f1(self):
        # 2 chunk types, IOB: label = type*2 + {B:0, I:1}; 4 = outside
        #          B0 I0 O  B1 I1   (label row: two chunks)
        lab = np.array([[0, 1, 4, 2, 3]], np.int64)
        #          B0 I0 O  B1 B1   (inference: chunk (3,5,1) broken)
        inf = np.array([[0, 1, 4, 2, 2]], np.int64)
        got = bridge_run("chunk_eval",
                         {"Inference": inf, "Label": lab},
                         {"num_chunk_types": 2, "chunk_scheme": "IOB"},
                         outs=("Precision", "Recall", "F1-Score",
                               "NumInferChunks", "NumLabelChunks",
                               "NumCorrectChunks"))
        assert int(got["NumLabelChunks"][0]) == 2
        assert int(got["NumInferChunks"][0]) == 3  # B0I0, B1, B1
        assert int(got["NumCorrectChunks"][0]) == 1  # only (0,2,0)
        np.testing.assert_allclose(got["Precision"], [1 / 3], rtol=1e-5)
        np.testing.assert_allclose(got["Recall"], [0.5], rtol=1e-5)

    def test_exact_match_and_seq_length(self):
        lab = np.array([[0, 1, 1, 4, 4]], np.int64)
        got = bridge_run("chunk_eval",
                         {"Inference": lab, "Label": lab,
                          "SeqLength": np.array([3], np.int64)},
                         {"num_chunk_types": 2, "chunk_scheme": "IOB"},
                         outs=("Precision", "Recall", "F1-Score"))
        np.testing.assert_allclose(got["F1-Score"], [1.0], rtol=1e-6)

    def test_iobes_and_plain(self):
        # IOBES 1 type: B=0 I=1 E=2 S=3, outside=4
        lab = np.array([[0, 1, 2, 4, 3]], np.int64)  # chunks (0,3),(4,5)
        got = bridge_run("chunk_eval",
                         {"Inference": lab, "Label": lab},
                         {"num_chunk_types": 1,
                          "chunk_scheme": "IOBES"},
                         outs=("Precision", "Recall", "F1-Score",
                               "NumInferChunks", "NumLabelChunks",
                               "NumCorrectChunks"))
        assert int(got["NumLabelChunks"][0]) == 2
        np.testing.assert_allclose(got["F1-Score"], [1.0], rtol=1e-6)
        # plain: every in-range position is its own single-token chunk
        lab2 = np.array([[0, 1, 9]], np.int64)
        got = bridge_run("chunk_eval",
                         {"Inference": lab2, "Label": lab2},
                         {"num_chunk_types": 2, "chunk_scheme": "plain"},
                         outs=("Precision", "Recall", "F1-Score",
                               "NumInferChunks", "NumLabelChunks",
                               "NumCorrectChunks"))
        assert int(got["NumLabelChunks"][0]) == 2  # 9 out of range


class TestDetectionMap:
    """detection_map translator (detection/detection_map_op.cc) on the
    padded+lengths representation with fixed-capacity states."""

    def _run(self, det, gt, states=None, attrs=None):
        from test_op_bridge import bridge_run_lod

        ins = {"DetectRes": det, "Label": gt}
        if states:
            ins.update(states)
        return bridge_run_lod(
            "detection_map", ins, {},
            {"class_num": 2, "overlap_threshold": 0.5,
             "ap_type": "11point", "state_capacity": 8,
             **(attrs or {})},
            outs=("MAP", "AccumPosCount", "AccumTruePos",
                  "AccumTruePosCount", "AccumFalsePos",
                  "AccumFalsePosCount"))

    def test_perfect_detections_map_1(self):
        # one image, two gt (class 0 and 1), two exact detections
        gt = np.array([[[0, 0, 0, 0, 2, 2],
                        [1, 0, 4, 4, 6, 6]]], np.float32)
        det = np.array([[[0, 0.9, 0, 0, 2, 2],
                         [1, 0.8, 4, 4, 6, 6]]], np.float32)
        got = self._run(det, gt)
        np.testing.assert_allclose(got["MAP"], [1.0], rtol=1e-5)
        np.testing.assert_array_equal(got["AccumPosCount"], [1, 1])
        np.testing.assert_array_equal(got["AccumTruePosCount"], [1, 1])

    def test_false_positive_halves_class_ap(self):
        gt = np.array([[[0, 0, 0, 0, 2, 2]]], np.float32)
        det = np.array([[[0, 0.9, 10, 10, 12, 12],   # miss (fp)
                         [0, 0.8, 0, 0, 2, 2]]], np.float32)  # hit
        got = self._run(det, gt)
        # 11-point AP with prec curve [0, .5]: recall>=t all hit p=0.5
        np.testing.assert_allclose(got["MAP"], [0.5], atol=0.06)
        np.testing.assert_array_equal(got["AccumFalsePosCount"][0], 1)


    def test_integral_ap_is_natural_not_interpolated(self):
        """Reference detection_map_op.h:472-481: integral AP is the raw
        sum(prec * delta_recall), NOT the VOC right-maxed variant.
        fp(.9), tp(.8), tp(.7) over 2 gt: rec=[0,.5,1], prec=[0,.5,.667]
        -> natural AP = .5*.5 + .667*.5 = .583 (interpolated would give
        .667)."""
        gt = np.array([[[0, 0, 0, 0, 2, 2],
                        [0, 0, 4, 4, 6, 6]]], np.float32)
        det = np.array([[[0, 0.9, 10, 10, 12, 12],
                         [0, 0.8, 0, 0, 2, 2],
                         [0, 0.7, 4, 4, 6, 6]]], np.float32)
        got = self._run(det, gt, attrs={"ap_type": "integral",
                                        "class_num": 1})
        np.testing.assert_allclose(got["MAP"], [0.5 * 0.5 + (2 / 3) * 0.5],
                                   rtol=1e-3)

    def test_state_accumulates_across_calls(self):
        gt = np.array([[[0, 0, 0, 0, 2, 2]]], np.float32)
        hit = np.array([[[0, 0.9, 0, 0, 2, 2]]], np.float32)
        miss = np.array([[[0, 0.8, 10, 10, 12, 12]]], np.float32)
        first = self._run(hit, gt)
        states = {"PosCount": first["AccumPosCount"],
                  "TruePos": first["AccumTruePos"],
                  "TruePosCount": first["AccumTruePosCount"],
                  "FalsePos": first["AccumFalsePos"],
                  "FalsePosCount": first["AccumFalsePosCount"]}
        second = self._run(miss, gt, states=states)
        # 2 gt total, 1 tp + 1 fp accumulated
        np.testing.assert_array_equal(second["AccumPosCount"], [2, 0])
        np.testing.assert_array_equal(second["AccumTruePosCount"][0], 1)
        np.testing.assert_array_equal(second["AccumFalsePosCount"][0], 1)
        assert 0.0 < float(second["MAP"][0]) < 1.0


class TestHostOps:
    """read_file/decode_jpeg/py_func translators (host-side ops the
    reference executes in the imperative op loop)."""

    def test_read_file_decode_jpeg(self, tmp_path):
        from PIL import Image

        # smooth gradient (random noise is pathological for JPEG)
        gy, gx = np.mgrid[0:8, 0:6]
        img = np.stack([gy * 30, gx * 40, gy * 10 + gx * 10],
                       -1).astype(np.uint8)
        path = str(tmp_path / "x.jpg")
        Image.fromarray(img).save(path, quality=95)
        got = bridge_run("read_file", None, {"filename": path})
        assert got["Out"].dtype == np.uint8 and got["Out"].ndim == 1
        dec = bridge_run("decode_jpeg", {"X": got["Out"]},
                         {"mode": "rgb"})
        assert dec["Out"].shape == (3, 8, 6)
        # lossy codec: channels should still correlate strongly
        assert np.abs(dec["Out"].transpose(1, 2, 0).astype(int)
                      - img.astype(int)).mean() < 16

    def test_py_func_registry(self):
        from paddle_tpu.static.op_bridge import register_py_func

        cid = register_py_func(lambda a, b: (a + b, a * b))
        x, y = r(3), r(3, seed=1)
        got = bridge_run_lod("py_func", {"X": [x, y]}, {},
                             {"forward_callable_id": cid},
                             outs=("Out*2",))
        np.testing.assert_allclose(got["Out"][0], x + y, rtol=1e-6)
        np.testing.assert_allclose(got["Out"][1], x * y, rtol=1e-6)

    def test_py_func_unregistered_raises(self):
        with pytest.raises(NotImplementedError, match="process-local"):
            bridge_run("py_func", {"X": r(2)},
                       {"forward_callable_id": 12345})


def sigmoid(x):
    return 1 / (1 + np.exp(-x))


class TestCudnnLstm:
    """cudnn_lstm translator: the flat cuDNN-canonical packed weight
    (matrices for all layer/dirs, then biases; gates i,f,g,o) unpacked
    and run as lax.scan — parity vs a numpy LSTM built from the SAME
    sub-weights."""

    @staticmethod
    def _np_lstm(x, w_ih, w_hh, b, h0, c0):
        T, B, _ = x.shape
        h, c = h0.copy(), c0.copy()
        ys = []
        for t in range(T):
            gates = x[t] @ w_ih.T + h @ w_hh.T + b
            i, f, g, o = np.split(gates, 4, axis=-1)
            c = sigmoid(f) * c + sigmoid(i) * np.tanh(g)
            h = sigmoid(o) * np.tanh(c)
            ys.append(h)
        return np.stack(ys), h, c

    def test_single_layer_parity_and_states(self):
        rng = np.random.RandomState(0)
        T, B, I, H = 4, 2, 3, 5
        w_ih = rng.randn(4 * H, I).astype(np.float32) * 0.3
        w_hh = rng.randn(4 * H, H).astype(np.float32) * 0.3
        b_ih = rng.randn(4 * H).astype(np.float32) * 0.1
        b_hh = rng.randn(4 * H).astype(np.float32) * 0.1
        flat = np.concatenate([w_ih.ravel(), w_hh.ravel(), b_ih, b_hh])
        x = rng.randn(T, B, I).astype(np.float32)
        h0 = rng.randn(1, B, H).astype(np.float32) * 0.1
        c0 = rng.randn(1, B, H).astype(np.float32) * 0.1
        got = bridge_run("cudnn_lstm",
                         {"Input": x, "W": flat, "InitH": h0,
                          "InitC": c0},
                         {"hidden_size": H, "num_layers": 1,
                          "is_bidirec": False, "is_test": True},
                         outs=("Out", "LastH", "LastC"))
        ys, hT, cT = self._np_lstm(x, w_ih, w_hh, b_ih + b_hh,
                                   h0[0], c0[0])
        np.testing.assert_allclose(got["Out"], ys, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(got["LastH"][0], hT, rtol=1e-4,
                                   atol=1e-5)
        np.testing.assert_allclose(got["LastC"][0], cT, rtol=1e-4,
                                   atol=1e-5)

    def test_bidirectional_two_layer_shapes(self):
        rng = np.random.RandomState(1)
        T, B, I, H, L, ND = 3, 2, 4, 5, 2, 2
        size = 0
        for layer in range(L):
            isz = I if layer == 0 else H * ND
            size += (isz * H + H * H) * 4 * ND
            size += H * 8 * ND
        flat = (rng.randn(size) * 0.1).astype(np.float32)
        x = rng.randn(T, B, I).astype(np.float32)
        got = bridge_run("cudnn_lstm", {"Input": x, "W": flat},
                         {"hidden_size": H, "num_layers": L,
                          "is_bidirec": True, "is_test": True},
                         outs=("Out", "LastH", "LastC"))
        assert got["Out"].shape == (T, B, H * ND)
        assert got["LastH"].shape == (L * ND, B, H)


    def test_sequence_length_masks(self):
        """Delegation to the unified rnn runner brings cudnn's
        variable-length semantics: states freeze and outputs zero past
        each row's length."""
        rng = np.random.RandomState(2)
        T, B, I, H = 5, 2, 3, 4
        w_ih = rng.randn(4 * H, I).astype(np.float32) * 0.3
        w_hh = rng.randn(4 * H, H).astype(np.float32) * 0.3
        b = rng.randn(8 * H).astype(np.float32) * 0.1
        flat = np.concatenate([w_ih.ravel(), w_hh.ravel(), b])
        x = rng.randn(T, B, I).astype(np.float32)
        lens = np.array([3, 5], np.int32)
        got = bridge_run("cudnn_lstm",
                         {"Input": x, "W": flat,
                          "SequenceLength": lens},
                         {"hidden_size": H, "num_layers": 1,
                          "is_bidirec": False, "is_test": True},
                         outs=("Out", "LastH", "LastC"))
        # row 0 finished at t=3: outputs beyond are zero, LastH equals
        # the t=2 output
        np.testing.assert_allclose(got["Out"][3:, 0], 0.0, atol=1e-7)
        np.testing.assert_allclose(got["LastH"][0, 0],
                                   got["Out"][2, 0], rtol=1e-5)

    def test_train_dropout_refused(self):
        x = np.zeros((2, 1, 3), np.float32)
        H = 4
        size = (3 * H + H * H) * 4 + H * 8
        with pytest.raises(NotImplementedError, match="dropout"):
            bridge_run("cudnn_lstm",
                       {"Input": x,
                        "W": np.zeros(size, np.float32)},
                       {"hidden_size": H, "num_layers": 1,
                        "is_bidirec": False, "is_test": False,
                        "dropout_prob": 0.5},
                       outs=("Out",))

    def test_wrong_weight_size_raises(self):
        x = np.zeros((2, 1, 3), np.float32)
        with pytest.raises(ValueError, match="flat weight"):
            bridge_run("cudnn_lstm",
                       {"Input": x,
                        "W": np.zeros(7, np.float32)},
                       {"hidden_size": 4, "num_layers": 1,
                        "is_bidirec": False},
                       outs=("Out",))
