"""Native runtime (csrc/ libpaddle_tpu_rt.so) unit tests.

Mirrors the reference's colocated C++ gtests for allocator / executor /
reader (SURVEY.md §4.5: memory/allocation/*_test.cc, details/*_test.cc,
buffered_reader tests) — here driven through the ctypes binding.
"""
import threading

import numpy as np
import pytest

from paddle_tpu.core import native

pytestmark = pytest.mark.usefixtures("native_runtime")


class TestArena:
    def test_alloc_free_reuse(self):
        a = native.Arena(1 << 20)
        p1 = a.alloc(1000)
        p2 = a.alloc(2000)
        assert p1 != p2
        assert p1 % 256 == 0 and p2 % 256 == 0
        stats = a.stats()
        assert stats["in_use"] >= 3000
        a.free(p1)
        a.free(p2)
        assert a.stats()["in_use"] == 0
        # coalesced block should satisfy a larger request without growth
        reserved = a.stats()["reserved"]
        p3 = a.alloc(2500)
        assert a.stats()["reserved"] == reserved
        a.free(p3)
        a.close()

    def test_best_fit_and_growth(self):
        a = native.Arena(4096)
        big = a.alloc(1 << 20)  # dedicated growth chunk
        assert a.stats()["reserved"] >= 1 << 20
        a.free(big)
        a.close()

    def test_buffer_numpy_roundtrip(self):
        a = native.Arena()
        n = 1024
        ptr = a.alloc(n * 4)
        arr = np.frombuffer(a.buffer(ptr, n * 4), dtype=np.float32)
        arr[:] = np.arange(n, dtype=np.float32)
        arr2 = np.frombuffer(a.buffer(ptr, n * 4), dtype=np.float32)
        np.testing.assert_array_equal(arr2, np.arange(n, dtype=np.float32))
        a.free(ptr)
        a.close()

    def test_double_free_raises(self):
        a = native.Arena()
        p = a.alloc(64)
        a.free(p)
        with pytest.raises(RuntimeError):
            a.free(p)
        a.close()


class TestTaskGraph:
    def test_diamond_ordering(self):
        order = []
        lock = threading.Lock()

        def mk(name):
            def fn():
                with lock:
                    order.append(name)
            return fn

        g = native.TaskGraph(4)
        a = g.add_node(mk("a"))
        b = g.add_node(mk("b"))
        c = g.add_node(mk("c"))
        d = g.add_node(mk("d"))
        g.add_edge(a, b)
        g.add_edge(a, c)
        g.add_edge(b, d)
        g.add_edge(c, d)
        g.run()
        assert order[0] == "a" and order[-1] == "d"
        assert set(order) == {"a", "b", "c", "d"}
        # prepared graph reruns
        order.clear()
        g.run()
        assert order[0] == "a" and order[-1] == "d"
        g.close()

    def test_wide_fanout(self):
        hits = []
        lock = threading.Lock()
        g = native.TaskGraph(8)
        root = g.add_node(lambda: None)
        for i in range(50):
            n = g.add_node(lambda i=i: (lock.acquire(), hits.append(i),
                                        lock.release()))
            g.add_edge(root, n)
        g.run()
        assert sorted(hits) == list(range(50))
        g.close()


class TestPrefetchQueue:
    def test_ordered_delivery(self):
        n_items = 20

        def producer(index):
            if index >= n_items:
                return None
            return bytes([index % 256]) * (index + 1)

        q = native.PrefetchQueue(producer, capacity=4, n_workers=3,
                                 ordered=True)
        got = []
        while True:
            item = q.pop()
            if item is None:
                break
            got.append(item)
        assert len(got) == n_items
        for i, item in enumerate(got):
            assert item == bytes([i % 256]) * (i + 1)
        q.close()

    def test_numpy_batches(self):
        batches = [np.random.RandomState(i).rand(8, 4).astype(np.float32)
                   for i in range(5)]

        def producer(index):
            if index >= len(batches):
                return None
            return batches[index].tobytes()

        q = native.PrefetchQueue(producer, capacity=2, n_workers=2)
        for i in range(5):
            raw = q.pop()
            arr = np.frombuffer(raw, np.float32).reshape(8, 4)
            np.testing.assert_array_equal(arr, batches[i])
        assert q.pop() is None
        q.close()


class TestFlagsStatsTracer:
    def test_flags_roundtrip(self):
        native.flag_set("check_nan_inf", True)
        assert native.flag_get("check_nan_inf") == "True"
        assert native.flag_get("missing_flag", "dflt") == "dflt"

    def test_stats(self):
        native.stat_add("test_stat", 5)
        native.stat_add("test_stat", 7)
        assert native.stat_value("test_stat") == 12

    def test_tracer_export(self):
        native.tracer_enable()
        with native.RecordEvent("op:matmul"):
            pass
        native.tracer_disable()
        j = native.trace_export_json()
        assert "op:matmul" in j and "traceEvents" in j
        import json
        events = json.loads(j)["traceEvents"]
        assert any(e["name"] == "op:matmul" for e in events)


class TestBuildOnce:
    """`native._build` with the tools faked: what matters is that many
    callers at once make one build, and that a failure is kept, shown
    and not latched."""

    @pytest.fixture
    def fake_checkout(self, tmp_path, monkeypatch):
        (tmp_path / "csrc").mkdir()
        monkeypatch.setattr(native, "_REPO_ROOT", str(tmp_path))
        monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "build"))
        monkeypatch.setattr(native, "_LIB_CANDIDATES",
                            (str(tmp_path / "build" / native._LIB_NAME),))
        monkeypatch.setattr(native, "_build_error", None)
        return tmp_path

    def test_concurrent_callers_build_once(self, fake_checkout,
                                           monkeypatch):
        import os
        import time

        ninja_runs = []

        def fake_run(cmd, timeout):
            if cmd[0] == "ninja":
                ninja_runs.append(cmd)
                time.sleep(0.05)  # hold the lock while others queue up
                with open(os.path.join(cmd[2], native._LIB_NAME), "w"):
                    pass
            else:
                os.makedirs(cmd[cmd.index("-B") + 1])

        monkeypatch.setattr(native, "_run", fake_run)
        got, errs = [], []

        def worker():
            try:
                got.append(native._build())
            except Exception as e:  # noqa: BLE001 - reported below
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        assert not errs, errs
        final = str(fake_checkout / "build" / native._LIB_NAME)
        assert got == [final] * 8
        assert len(ninja_runs) == 1
        assert sorted(os.listdir(fake_checkout)) == [
            ".native_build.lock", "build", "csrc"]

    def test_failure_is_kept_shown_and_not_latched(self, fake_checkout,
                                                   monkeypatch):
        def failing_run(cmd, timeout):
            raise RuntimeError("cmake exited 1:\nno compiler")

        monkeypatch.setattr(native, "_run", failing_run)
        with pytest.warns(RuntimeWarning, match="no compiler"):
            assert native._find_or_build() is None
        assert "no compiler" in native.build_error()
        # nothing of the failed attempt is left behind ...
        assert not (fake_checkout / "build").exists()
        # ... and a library that another process finishes later is found
        (fake_checkout / "build").mkdir()
        lib = fake_checkout / "build" / native._LIB_NAME
        lib.write_bytes(b"")
        assert native._find_or_build() == str(lib)
