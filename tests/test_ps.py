"""Parameter-server tests.

Reference pattern: `distributed/test/brpc_service_dense_sgd_test.cc`,
`sparse_table_test.cc`, `barrier_table_test.cc` spin real brpc servers
in-process; here the native TCP server runs on its own C++ threads and
multiple clients emulate trainers (TestDistBase-style localhost
simulation, SURVEY.md §4.2).
"""
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.ps import Communicator, PSClient, PSServer

pytestmark = pytest.mark.usefixtures("native_runtime")


@pytest.fixture
def server():
    srv = PSServer()
    srv.create_dense_table(0, 8, lr=0.1, optimizer="sgd")
    srv.create_dense_table(1, 4, lr=0.1, optimizer="sum")
    srv.create_sparse_table(2, dim=3, lr=0.5)
    port = srv.start(0, n_trainers=2)
    yield srv, port
    srv.stop()


class TestDenseTable:
    def test_set_pull_roundtrip(self, server):
        _, port = server
        c = PSClient(port=port)
        v = np.arange(8, dtype=np.float32)
        c.set_dense(0, v)
        np.testing.assert_allclose(c.pull_dense(0, 8), v)
        c.close()

    def test_sgd_update(self, server):
        _, port = server
        c = PSClient(port=port)
        c.set_dense(0, np.ones(8, np.float32))
        c.push_dense_grad(0, np.full(8, 2.0, np.float32))
        # p -= lr * g = 1 - 0.1*2
        np.testing.assert_allclose(c.pull_dense(0, 8), 0.8, rtol=1e-6)
        c.close()

    def test_two_trainers_accumulate(self, server):
        _, port = server
        c1, c2 = PSClient(port=port), PSClient(port=port)
        c1.set_dense(0, np.zeros(8, np.float32))
        c1.push_dense_grad(0, np.ones(8, np.float32))
        c2.push_dense_grad(0, np.ones(8, np.float32))
        np.testing.assert_allclose(c1.pull_dense(0, 8), -0.2, rtol=1e-5)
        c1.close(); c2.close()

    def test_delta_table(self, server):
        _, port = server
        c = PSClient(port=port)
        c.push_dense_delta(1, np.full(4, 3.0, np.float32))
        c.push_dense_delta(1, np.full(4, -1.0, np.float32))
        np.testing.assert_allclose(c.pull_dense(1, 4), 2.0)
        c.close()


class TestSparseTable:
    def test_pull_initializes_and_push_updates(self, server):
        _, port = server
        c = PSClient(port=port)
        ids = np.array([5, 9, 5], np.uint64)
        rows = c.pull_sparse(2, ids, dim=3)
        np.testing.assert_allclose(rows, 0.0)
        c.push_sparse_grad(2, np.array([5], np.uint64),
                           np.full((1, 3), 1.0, np.float32))
        rows = c.pull_sparse(2, np.array([5, 9], np.uint64), dim=3)
        np.testing.assert_allclose(rows[0], -0.5)  # lr 0.5
        np.testing.assert_allclose(rows[1], 0.0)
        c.close()


class TestBarrier:
    def test_barrier_blocks_until_all(self, server):
        _, port = server
        c1, c2 = PSClient(port=port), PSClient(port=port)
        order = []

        def t1():
            c1.barrier(trainer_id=0)
            order.append("released")

        th = threading.Thread(target=t1)
        th.start()
        time.sleep(0.2)
        assert order == []  # c1 still blocked
        c2.barrier(trainer_id=1)
        th.join(timeout=5)
        assert order == ["released"]
        c1.close(); c2.close()

    def test_rearrival_of_same_trainer_does_not_release(self, server):
        """A restarted trainer re-entering the barrier must not count as a
        second distinct participant (reference barrier_table semantics)."""
        _, port = server
        c1, c1b = PSClient(port=port), PSClient(port=port)
        order = []

        def t1():
            c1.barrier(trainer_id=0)
            order.append("released")

        th = threading.Thread(target=t1)
        th.start()
        time.sleep(0.2)
        # same trainer id arrives again on a new connection
        th2 = threading.Thread(target=lambda: c1b.barrier(trainer_id=0))
        th2.start()
        time.sleep(0.2)
        assert order == []  # still only one distinct id
        c2 = PSClient(port=port)
        c2.barrier(trainer_id=1)
        th.join(timeout=5)
        th2.join(timeout=5)
        assert order == ["released"]
        c1.close(); c1b.close(); c2.close()


class TestCommunicator:
    def test_async_merge_and_pull(self, server):
        _, port = server
        c = PSClient(port=port)
        c.set_dense(0, np.ones(8, np.float32))
        comm = Communicator(c, mode="async", send_interval_s=0.02)
        comm.register_dense(0, 8)
        comm.start()
        comm.send(0, np.full(8, 1.0, np.float32))
        comm.send(0, np.full(8, 1.0, np.float32))
        time.sleep(0.5)
        comm.stop()
        got = c.pull_dense(0, 8)
        # merged or separate pushes: total grad 2.0 applied at lr 0.1
        np.testing.assert_allclose(got, 0.8, rtol=1e-5)
        c.close()

    def test_geo_mode(self, server):
        _, port = server
        c = PSClient(port=port)
        comm = Communicator(c, mode="geo", k_steps=2)
        local = np.zeros(4, np.float32)
        local = comm.geo_step(1, local + 1.0)  # tick 1: local only
        np.testing.assert_allclose(local, 1.0)
        local = comm.geo_step(1, local + 1.0)  # tick 2: push delta=2, pull
        np.testing.assert_allclose(local, 2.0)
        np.testing.assert_allclose(c.pull_dense(1, 4), 2.0)
        c.close()


class TestFleetPSIntegration:
    def test_role_and_runtime(self, monkeypatch):
        from paddle_tpu.distributed.fleet.base import Fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy

        # server side
        monkeypatch.setenv("TRAINING_ROLE", "PSERVER")
        monkeypatch.setenv("PADDLE_PORT", "0")
        f_srv = Fleet()
        st = DistributedStrategy()
        st.a_sync = True
        f_srv.init(strategy=st)
        assert f_srv._role_maker.is_server()
        port = f_srv.init_server(
            tables={0: ("dense", 4, 0.1, "sgd")}, n_trainers=1)
        assert port > 0

        # trainer side
        monkeypatch.setenv("TRAINING_ROLE", "TRAINER")
        monkeypatch.setenv("PADDLE_PSERVERS_IP_PORT_LIST",
                           f"127.0.0.1:{port}")
        f_tr = Fleet()
        f_tr.init(strategy=st)
        client = f_tr.init_worker()
        client.set_dense(0, np.zeros(4, np.float32))
        client.push_dense_grad(0, np.ones(4, np.float32))
        np.testing.assert_allclose(client.pull_dense(0, 4), -0.1, rtol=1e-5)
        f_tr._ps_communicator.stop()
        client.close()
        f_srv.stop_server()

    def test_remote_stop_releases_run_server(self):
        srv = PSServer()
        srv.create_dense_table(0, 4, lr=0.1)
        port = srv.start(0, n_trainers=1)
        released = []

        def run():
            while not srv.is_stopped():
                time.sleep(0.05)
            released.append(True)

        th = threading.Thread(target=run)
        th.start()
        c = PSClient(port=port)
        c.stop_server()
        th.join(timeout=5)
        assert released == [True]
        c.close()
        srv.stop()

    def test_ps_linear_regression_converges(self, server):
        """End-to-end: trainer computes grads on device, PS owns the
        weights (sync mode) — the loss must drop (TestDistBase check)."""
        _, port = server
        import paddle_tpu as paddle

        c = PSClient(port=port)
        rng = np.random.RandomState(0)
        w_true = rng.randn(8).astype(np.float32)
        x_np = rng.randn(64, 8).astype(np.float32)
        y_np = x_np @ w_true
        c.set_dense(0, np.zeros(8, np.float32))
        losses = []
        for _ in range(60):
            w = paddle.to_tensor(c.pull_dense(0, 8))
            w.stop_gradient = False
            x = paddle.to_tensor(x_np)
            y = paddle.to_tensor(y_np)
            loss = ((x.matmul(w) - y) ** 2).mean()
            loss.backward()
            c.push_dense_grad(0, np.asarray(w.grad.numpy()))
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0] * 0.05
        c.close()


class TestServerAdam:
    """Server-side adam optimizer (reference server accessor rules beyond
    sgd/adagrad — brpc_ps table accessors)."""

    def test_dense_adam_matches_numpy(self):
        srv = PSServer()
        srv.create_dense_table(0, 4, lr=0.1, optimizer="adam")
        port = srv.start(0, n_trainers=1)
        c = PSClient(port=port)
        p = np.ones(4, np.float32)
        c.set_dense(0, p)
        m = np.zeros(4); v = np.zeros(4)
        b1, b2, eps = 0.9, 0.999, 1e-8
        for t in range(1, 4):
            g = np.full(4, 0.5, np.float32)
            c.push_dense_grad(0, g)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            p = p - 0.1 * (m / (1 - b1**t)) / (np.sqrt(v / (1 - b2**t)) + eps)
        np.testing.assert_allclose(c.pull_dense(0, 4), p, rtol=1e-5)
        c.close()
        srv.stop()

    def test_sparse_adagrad(self):
        srv = PSServer()
        srv.create_sparse_table(0, dim=2, lr=0.5, optimizer="adagrad")
        port = srv.start(0, n_trainers=1)
        c = PSClient(port=port)
        ids = np.array([7], np.uint64)
        g = np.array([[2.0, 2.0]], np.float32)
        c.push_sparse_grad(0, ids, g)
        acc = 1e-6 + 4.0
        expect = -0.5 * 2.0 / np.sqrt(acc)
        np.testing.assert_allclose(c.pull_sparse(0, ids, 2)[0], expect,
                                   rtol=1e-5)
        c.close()
        srv.stop()


class TestShardedPS:
    """Multi-server table sharding (reference brpc_ps_client request fan-out
    + common_sparse_table block partitioning)."""

    def _spin_up(self, n_servers, total_dense=10, sparse_dim=3):
        from paddle_tpu.distributed.ps import shard_dense_sizes
        sizes = shard_dense_sizes(total_dense, n_servers)
        servers = []
        endpoints = []
        for i in range(n_servers):
            s = PSServer()
            s.create_dense_table(0, sizes[i], lr=0.1, optimizer="sgd")
            s.create_sparse_table(1, dim=sparse_dim, lr=0.5)
            port = s.start(0, n_trainers=1)
            servers.append(s)
            endpoints.append(("127.0.0.1", port))
        return servers, endpoints

    def test_dense_blocks_route_to_both(self):
        from paddle_tpu.distributed.ps import ShardedPSClient
        servers, eps = self._spin_up(2)
        c = ShardedPSClient(eps)
        c.register_dense(0, 10)
        v = np.arange(10, dtype=np.float32)
        c.set_dense(0, v)
        np.testing.assert_allclose(c.pull_dense(0, 10), v)
        # each server holds only its contiguous block (5 each)
        c0 = PSClient(port=eps[0][1])
        c1 = PSClient(port=eps[1][1])
        np.testing.assert_allclose(c0.pull_dense(0, 5), v[:5])
        np.testing.assert_allclose(c1.pull_dense(0, 5), v[5:])
        c.push_dense_grad(0, np.ones(10, np.float32))
        np.testing.assert_allclose(c.pull_dense(0, 10), v - 0.1, rtol=1e-5)
        for x in (c0, c1):
            x.close()
        c.close()
        for s in servers:
            s.stop()

    def test_sparse_ids_route_by_modulo(self):
        from paddle_tpu.distributed.ps import ShardedPSClient
        servers, eps = self._spin_up(2)
        c = ShardedPSClient(eps)
        ids = np.array([2, 3, 5, 8], np.uint64)  # evens->srv0, odds->srv1
        g = np.tile(np.array([[1.0, 2.0, 3.0]], np.float32), (4, 1))
        c.push_sparse_grad(1, ids, g)
        out = c.pull_sparse(1, ids, 3)
        np.testing.assert_allclose(out, -0.5 * g, rtol=1e-5)
        # verify each server actually owns its id subset
        c0 = PSClient(port=eps[0][1])
        r0 = c0.pull_sparse(1, np.array([2, 8], np.uint64), 3)
        assert np.abs(r0).sum() > 0  # evens landed on server 0
        c1 = PSClient(port=eps[1][1])
        r1 = c1.pull_sparse(1, np.array([3, 5], np.uint64), 3)
        assert np.abs(r1).sum() > 0  # odds landed on server 1
        # cross-check: ids NOT owned by a server were never touched there
        r_cross = c0.pull_sparse(1, np.array([3, 5], np.uint64), 3)
        np.testing.assert_allclose(r_cross, 0.0)
        for x in (c0, c1):
            x.close()
        c.close()
        for s in servers:
            s.stop()

    def test_save_kill_restart_resumes(self, tmp_path):
        """Persistence across a server restart (reference
        _save_distributed_persistables + table load)."""
        from paddle_tpu.distributed.ps import ShardedPSClient, \
            shard_dense_sizes
        servers, eps = self._spin_up(2)
        c = ShardedPSClient(eps)
        c.register_dense(0, 10)
        v = np.arange(10, dtype=np.float32)
        c.set_dense(0, v)
        ids = np.array([4, 9], np.uint64)
        c.push_sparse_grad(1, ids, np.ones((2, 3), np.float32))
        prefix = str(tmp_path / "ps_ckpt")
        c.save_tables(prefix)
        c.close()
        for s in servers:   # kill
            s.stop()
        # restart from the snapshots
        sizes = shard_dense_sizes(10, 2)
        new_eps = []
        new_servers = []
        for i in range(2):
            s = PSServer()
            s.load(f"{prefix}.shard{i}")
            port = s.start(0, n_trainers=1)
            new_servers.append(s)
            new_eps.append(("127.0.0.1", port))
        c2 = ShardedPSClient(new_eps)
        c2.register_dense(0, 10)
        np.testing.assert_allclose(c2.pull_dense(0, 10), v)
        np.testing.assert_allclose(c2.pull_sparse(1, ids, 3), -0.5,
                                   rtol=1e-5)
        assert sizes == [5, 5]
        c2.close()
        for s in new_servers:
            s.stop()


class TestSSDSparseTable:
    """reference `distributed/table/ssd_sparse_table.cc`: tables larger
    than the memory budget spill to disk, keep training correctly, and
    survive a save/restart/load cycle."""

    def test_spill_beyond_budget_and_restart(self, tmp_path):
        from paddle_tpu.distributed.ps import PSClient, PSServer

        dim, budget, n_rows = 4, 8, 64
        spill = str(tmp_path / "table2.spill")
        snap = str(tmp_path / "ps.snap")

        srv = PSServer()
        srv.create_sparse_table_ssd(0, dim=dim, mem_budget_rows=budget,
                                    spill_path=spill, lr=0.5,
                                    optimizer="sgd")
        port = srv.start(0, n_trainers=1)
        cli = PSClient(port=port)
        try:
            ids = np.arange(1, n_rows + 1, dtype=np.uint64)
            # push distinct grads row by row (well beyond the budget)
            for i, rid in enumerate(ids):
                g = np.full((1, dim), float(i + 1), np.float32)
                cli.push_sparse_grad(0, np.array([rid], np.uint64), g)
            # every row is readable back (spilled ones fault in) with
            # the sgd update applied: row = -lr * grad
            got = cli.pull_sparse(0, ids, dim)
            want = -0.5 * np.arange(1, n_rows + 1,
                                    dtype=np.float32)[:, None] * \
                np.ones((1, dim), np.float32)
            np.testing.assert_allclose(got, want, rtol=1e-6)
            # the spill file actually holds the overflow
            import os

            assert os.path.exists(spill)
            assert os.path.getsize(spill) > 0
            cli.save_tables(snap)
        finally:
            cli.stop_server()
            time.sleep(0.1)
            srv.stop()

        # restart: fresh server, same SSD config, load the snapshot
        srv2 = PSServer()
        srv2.create_sparse_table_ssd(0, dim=dim, mem_budget_rows=budget,
                                     spill_path=spill, lr=0.5,
                                     optimizer="sgd")
        srv2.load(snap)
        port2 = srv2.start(0, n_trainers=1)
        cli2 = PSClient(port=port2)
        try:
            got2 = cli2.pull_sparse(0, ids, dim)
            want2 = -0.5 * np.arange(1, n_rows + 1,
                                     dtype=np.float32)[:, None] * \
                np.ones((1, dim), np.float32)
            np.testing.assert_allclose(got2, want2, rtol=1e-6)
        finally:
            cli2.stop_server()
            time.sleep(0.1)
            srv2.stop()


def _sample_hash_np(seed, node, j):
    """numpy replay of the server's SampleHash (splitmix64 finalizer) —
    python ints with explicit 64-bit wrapping."""
    mask = (1 << 64) - 1
    h = (seed * 0x9E3779B97F4A7C15) & mask
    h ^= (node + 0xD1B54A32D192ED03 + ((h << 6) & mask) + (h >> 2)) & mask
    h ^= ((j * 0x94D049BB133111EB) & mask) + ((h << 6) & mask) + (h >> 2)
    h &= mask
    h ^= h >> 33
    h = (h * 0xFF51AFD7ED558CCD) & mask
    h ^= h >> 33
    return h & 0xFFFFFFFF


class TestGraphTable:
    """reference `distributed/table/common_graph_table.cc` +
    `graph_brpc_server.cc` — GNN neighbor sampling over the PS."""

    def _start(self, feat_dim=3):
        from paddle_tpu.distributed.ps import PSClient, PSServer

        srv = PSServer()
        srv.create_graph_table(0, feat_dim=feat_dim)
        port = srv.start(0, n_trainers=1)
        return srv, PSClient(port=port)

    def test_full_neighborhood_and_feats(self):
        srv, cli = self._start()
        try:
            src = np.array([1, 1, 1, 2], np.uint64)
            dst = np.array([10, 11, 12, 20], np.uint64)
            cli.add_graph_edges(0, src, dst)
            # sample_size >= degree returns the whole neighborhood
            nbrs, counts = cli.sample_neighbors(
                0, np.array([1, 2, 3], np.uint64), sample_size=5)
            assert counts.tolist() == [3, 1, 0]
            assert set(nbrs[0, :3].tolist()) == {10, 11, 12}
            assert nbrs[1, 0] == 20
            feats = np.array([[1, 2, 3], [4, 5, 6]], np.float32)
            cli.set_node_feat(0, np.array([10, 20], np.uint64), feats)
            got = cli.get_node_feat(
                0, np.array([10, 99, 20], np.uint64), dim=3)
            np.testing.assert_allclose(got[0], [1, 2, 3])
            np.testing.assert_allclose(got[1], [0, 0, 0])
            np.testing.assert_allclose(got[2], [4, 5, 6])
        finally:
            cli.stop_server()
            time.sleep(0.1)
            srv.stop()

    def test_sampling_parity_with_numpy(self):
        """The weighted sample must equal the numpy replay of the
        documented Efraimidis-Spirakis draw (deterministic hash keys)."""
        srv, cli = self._start()
        try:
            deg = 10
            node = 7
            dst = np.arange(100, 100 + deg, dtype=np.uint64)
            w = np.linspace(0.5, 5.0, deg).astype(np.float32)
            cli.add_graph_edges(0, np.full(deg, node, np.uint64), dst, w)
            seed, k = 42, 4
            nbrs, counts = cli.sample_neighbors(
                0, np.array([node], np.uint64), sample_size=k, seed=seed)
            assert counts[0] == k
            # numpy replay
            keys = []
            for j in range(deg):
                u = (float(_sample_hash_np(seed, node, j)) + 1.0) / 2**32
                keys.append((-(u ** (1.0 / float(w[j]))), j))
            keys.sort()
            want = [int(dst[j]) for _, j in keys[:k]]
            assert nbrs[0, :k].tolist() == want
        finally:
            cli.stop_server()
            time.sleep(0.1)
            srv.stop()

    def test_graph_survives_snapshot(self, tmp_path):
        srv, cli = self._start()
        snap = str(tmp_path / "g.snap")
        try:
            cli.add_graph_edges(0, np.array([5], np.uint64),
                                np.array([6], np.uint64))
            cli.set_node_feat(0, np.array([5], np.uint64),
                              np.array([[9, 9, 9]], np.float32))
            cli.save_tables(snap)
        finally:
            cli.stop_server()
            time.sleep(0.1)
            srv.stop()
        from paddle_tpu.distributed.ps import PSClient, PSServer

        srv2 = PSServer()
        srv2.create_graph_table(0, feat_dim=3)
        srv2.load(snap)
        port = srv2.start(0, n_trainers=1)
        cli2 = PSClient(port=port)
        try:
            nbrs, counts = cli2.sample_neighbors(
                0, np.array([5], np.uint64), sample_size=2)
            assert counts[0] == 1 and nbrs[0, 0] == 6
            np.testing.assert_allclose(
                cli2.get_node_feat(0, np.array([5], np.uint64), 3)[0],
                [9, 9, 9])
        finally:
            cli2.stop_server()
            time.sleep(0.1)
            srv2.stop()


class TestHeterService:
    """reference heter_client.cc/heter_server.cc: offload a named dense
    section to a peer process service."""

    def test_roundtrip_and_error(self):
        from paddle_tpu.distributed.ps import HeterClient, HeterServer

        srv = HeterServer()
        srv.register("dense_fwd", lambda x, w: x @ w + 1.0)
        port = srv.start()
        cli = HeterClient(port=port)
        try:
            x = np.random.RandomState(0).rand(3, 4).astype(np.float32)
            w = np.random.RandomState(1).rand(4, 2).astype(np.float32)
            out = cli.run("dense_fwd", x, w)
            np.testing.assert_allclose(out, x @ w + 1.0, rtol=1e-6)
            with pytest.raises(RuntimeError, match="missing"):
                cli.run("missing", x)
        finally:
            cli.close()
            srv.stop()
