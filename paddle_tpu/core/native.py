"""ctypes binding to the native runtime (libpaddle_tpu_rt.so, csrc/).

The native layer provides the C++ substrate that the reference implements in
`paddle/fluid/platform` + `memory` + `framework/details` (SURVEY.md §2.1):

* ``Arena``        — auto-growth best-fit host staging allocator
                     (reference AutoGrowthBestFitAllocator,
                     memory/allocation/auto_growth_best_fit_allocator.h:29)
* ``ThreadPool`` / ``TaskGraph`` — dependency-counted DAG scheduler
                     (reference FastThreadedSSAGraphExecutor,
                     framework/details/fast_threaded_ssa_graph_executor.h:32)
* ``PrefetchQueue`` — background batch prefetcher
                     (reference buffered_reader.cc / reader_py.cc)
* flags / stats / tracer — platform/flags.cc, monitor.cc, profiler.h

Build: the shared library is built from ``csrc/`` on first use, once per
checkout (`_build`): under a cross-process file lock, into a scratch
directory whose products are renamed into ``build/`` only when complete.
A failed build is kept in `build_error()` and shown once as a warning;
consumers that can do without the library check ``native_available()``.
"""
from __future__ import annotations

import ctypes
import fcntl
import os
import shutil
import subprocess
import tempfile
import threading
import warnings

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_BUILD_DIR = os.path.join(_REPO_ROOT, "build")
_LIB_NAME = "libpaddle_tpu_rt.so"
_LIB_CANDIDATES = (
    # source-tree builds first so a rebuild is never shadowed by a stale
    # packaged copy; the packaged location (setup.py puts the lib there
    # for wheels) is the fallback when no source build exists
    os.path.join(_BUILD_DIR, _LIB_NAME),
    os.path.join(_REPO_ROOT, "csrc", _LIB_NAME),
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "lib", _LIB_NAME),
)

_lib = None
_lib_lock = threading.Lock()
# why the one build attempt of this process failed (None: not tried, or
# it succeeded).  Only the BUILD is attempted once; the library itself is
# looked for again on every call, so one that another process finishes
# later is still picked up.
_build_error: str | None = None


def build_error() -> str | None:
    """The captured cmake/ninja failure of this process's build attempt."""
    return _build_error


def _run(cmd, timeout):
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(
            f"{' '.join(cmd)} exited {r.returncode}:\n"
            f"{(r.stdout + r.stderr)[-2000:]}")


def _build() -> str:
    """Build ``csrc/`` into ``build/`` — one build per checkout however
    many processes ask at once.  The file lock serialises them; whoever
    holds it first compiles in a scratch directory and renames the
    finished libraries into place, so ``build/`` never holds a partial
    product and everyone after finds it there.  Raises with the tool's
    own output on failure."""
    src = os.path.join(_REPO_ROOT, "csrc")
    final = os.path.join(_BUILD_DIR, _LIB_NAME)
    if not os.path.isdir(src):
        raise RuntimeError(f"no native sources at {src}")
    with open(os.path.join(_REPO_ROOT, ".native_build.lock"), "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # released when the file closes
        if os.path.exists(final):
            return final
        work = tempfile.mkdtemp(prefix="build.tmp.", dir=_REPO_ROOT)
        try:
            tree = os.path.join(work, "cmake")
            _run(["cmake", "-S", src, "-B", tree, "-G", "Ninja"], 120)
            _run(["ninja", "-C", tree], 300)
            out = os.path.join(work, "out")
            os.mkdir(out)
            for name in os.listdir(tree):
                if name.endswith(".so"):
                    os.rename(os.path.join(tree, name),
                              os.path.join(out, name))
            # a build/ without the library is an interrupted older
            # attempt (we hold the lock, so nobody is writing it)
            shutil.rmtree(_BUILD_DIR, ignore_errors=True)
            os.rename(out, _BUILD_DIR)
        finally:
            shutil.rmtree(work, ignore_errors=True)
    return final


def _find_or_build() -> str | None:
    global _build_error
    path = next((p for p in _LIB_CANDIDATES if os.path.exists(p)), None)
    if path is not None or _build_error is not None:
        return path
    try:
        return _build()
    except (OSError, RuntimeError, subprocess.TimeoutExpired) as e:
        _build_error = f"{type(e).__name__}: {e}"
        warnings.warn(f"native runtime not built: {_build_error}",
                      RuntimeWarning, stacklevel=3)
        return None


def _load():
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        path = _find_or_build()
        if path is None:
            return None
        lib = ctypes.CDLL(path)
        # ---- signatures ----
        lib.ptrt_arena_create.restype = ctypes.c_void_p
        lib.ptrt_arena_create.argtypes = [ctypes.c_size_t]
        lib.ptrt_arena_destroy.argtypes = [ctypes.c_void_p]
        lib.ptrt_arena_alloc.argtypes = [ctypes.c_void_p, ctypes.c_size_t,
                                         ctypes.POINTER(ctypes.c_void_p)]
        lib.ptrt_arena_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
        lib.ptrt_arena_stats.argtypes = [ctypes.c_void_p] + \
            [ctypes.POINTER(ctypes.c_size_t)] * 3

        lib.ptrt_last_error_message.restype = ctypes.c_char_p
        lib.ptrt_flag_set.argtypes = [ctypes.c_char_p, ctypes.c_char_p]
        lib.ptrt_flag_get.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                      ctypes.c_size_t]
        lib.ptrt_stat_add.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.ptrt_stat_value.argtypes = [ctypes.c_char_p]
        lib.ptrt_stat_value.restype = ctypes.c_int64

        lib.ptrt_now_ns.restype = ctypes.c_uint64
        lib.ptrt_trace_record.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                          ctypes.c_uint64]
        lib.ptrt_trace_export.restype = ctypes.c_size_t
        lib.ptrt_trace_export.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
        lib.ptrt_trace_count.restype = ctypes.c_size_t

        lib.ptrt_pool_create.restype = ctypes.c_void_p
        lib.ptrt_pool_create.argtypes = [ctypes.c_int]
        lib.ptrt_pool_destroy.argtypes = [ctypes.c_void_p]
        lib.ptrt_pool_size.argtypes = [ctypes.c_void_p]
        lib.ptrt_graph_create.restype = ctypes.c_void_p
        lib.ptrt_graph_destroy.argtypes = [ctypes.c_void_p]
        lib.ptrt_graph_add_node.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                            ctypes.c_void_p]
        lib.ptrt_graph_add_edge.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                            ctypes.c_int]
        lib.ptrt_graph_run.argtypes = [ctypes.c_void_p, ctypes.c_void_p]

        lib.ptrt_prefetch_create.restype = ctypes.c_void_p
        lib.ptrt_prefetch_create.argtypes = [
            ctypes.c_size_t, ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int]
        lib.ptrt_prefetch_destroy.argtypes = [ctypes.c_void_p]
        lib.ptrt_prefetch_pop.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_size_t), ctypes.POINTER(ctypes.c_int64)]
        lib.ptrt_prefetch_shutdown.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


def _require():
    """The loaded library, or an error that says why there is none."""
    lib = _load()
    if lib is None:
        raise RuntimeError(
            f"native runtime unavailable: {_build_error or 'not found'}")
    return lib


def _check(rc: int):
    if rc != 0:
        lib = _load()
        raise RuntimeError(
            f"native runtime error {rc}: "
            f"{lib.ptrt_last_error_message().decode()}")


# ---------------------------------------------------------------------------
# Python wrappers
# ---------------------------------------------------------------------------
class Arena:
    """Best-fit auto-growth host arena (see csrc/allocator.cc)."""

    def __init__(self, chunk_size: int = 64 << 20):
        self._lib = _require()
        self._h = self._lib.ptrt_arena_create(chunk_size)

    def alloc(self, size: int) -> int:
        out = ctypes.c_void_p()
        _check(self._lib.ptrt_arena_alloc(self._h, size, ctypes.byref(out)))
        return out.value

    def free(self, ptr: int):
        _check(self._lib.ptrt_arena_free(self._h, ptr))

    def buffer(self, ptr: int, size: int) -> memoryview:
        """Zero-copy view over an arena allocation (for numpy frombuffer)."""
        return memoryview((ctypes.c_char * size).from_address(ptr))

    def stats(self) -> dict:
        a, b, c = (ctypes.c_size_t(), ctypes.c_size_t(), ctypes.c_size_t())
        self._lib.ptrt_arena_stats(self._h, ctypes.byref(a), ctypes.byref(b),
                                   ctypes.byref(c))
        return {"in_use": a.value, "peak": b.value, "reserved": c.value}

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ptrt_arena_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


_NODE_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p)


class TaskGraph:
    """Dependency-counted DAG run on a native thread pool."""

    def __init__(self, n_threads: int = 0):
        self._lib = _require()
        self._pool = self._lib.ptrt_pool_create(n_threads)
        self._g = self._lib.ptrt_graph_create()
        self._cbs = []  # keep trampolines alive

    def add_node(self, fn) -> int:
        cb = _NODE_CB(lambda _ud: fn())
        self._cbs.append(cb)
        return self._lib.ptrt_graph_add_node(
            self._g, ctypes.cast(cb, ctypes.c_void_p), None)

    def add_edge(self, src: int, dst: int):
        _check(self._lib.ptrt_graph_add_edge(self._g, src, dst))

    def run(self):
        _check(self._lib.ptrt_graph_run(self._g, self._pool))

    def close(self):
        if getattr(self, "_g", None):
            self._lib.ptrt_graph_destroy(self._g)
            self._lib.ptrt_pool_destroy(self._pool)
            self._g = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


_PRODUCER_CB = ctypes.CFUNCTYPE(
    ctypes.c_int, ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p),
    ctypes.POINTER(ctypes.c_size_t), ctypes.c_void_p)


class PrefetchQueue:
    """Background prefetcher over a Python producer.

    ``producer(index) -> bytes | None`` runs on native worker threads
    (ctypes releases the GIL around pops, producers re-acquire it); returned
    byte payloads are copied into arena storage owned by the queue consumer.
    """

    def __init__(self, producer, capacity: int = 4, n_workers: int = 1,
                 ordered: bool = True, arena: Arena | None = None):
        self._lib = _require()
        self._arena = arena or Arena(16 << 20)
        self._producer = producer
        self._error = None  # first producer exception, re-raised in pop()
        self._outstanding = set()  # arena ptrs handed to the queue, not yet popped

        def _produce(index, out_data, out_size, _ud):
            try:
                payload = producer(index)
            except Exception as e:  # noqa: BLE001 — surfaced via pop()
                if self._error is None:
                    self._error = e
                return 1
            if payload is None:
                return 1
            buf = bytes(payload)
            ptr = self._arena.alloc(len(buf))
            ctypes.memmove(ptr, buf, len(buf))
            self._outstanding.add(ptr)
            out_data[0] = ptr
            out_size[0] = len(buf)
            return 0

        self._cb = _PRODUCER_CB(_produce)
        self._h = self._lib.ptrt_prefetch_create(
            capacity, n_workers, ctypes.cast(self._cb, ctypes.c_void_p),
            None, 1 if ordered else 0)

    def pop(self) -> bytes | None:
        data = ctypes.c_void_p()
        size = ctypes.c_size_t()
        index = ctypes.c_int64()
        ok = self._lib.ptrt_prefetch_pop(self._h, ctypes.byref(data),
                                         ctypes.byref(size),
                                         ctypes.byref(index))
        if not ok:
            if self._error is not None:
                err, self._error = self._error, None
                raise err
            return None
        out = ctypes.string_at(data.value, size.value)
        self._outstanding.discard(data.value)
        self._arena.free(data.value)
        return out

    def close(self):
        if getattr(self, "_h", None):
            # shutdown joins workers, so no producer callback is running
            # after it returns; safe to release batches never popped.
            self._lib.ptrt_prefetch_shutdown(self._h)
            self._lib.ptrt_prefetch_destroy(self._h)
            self._h = None
            for ptr in self._outstanding:
                self._arena.free(ptr)
            self._outstanding.clear()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# flags / stats / tracing module-level facade
# ---------------------------------------------------------------------------
def flag_set(key: str, value) -> None:
    lib = _load()
    if lib is None:
        return
    lib.ptrt_flag_set(key.encode(), str(value).encode())


def flag_get(key: str, default=None):
    lib = _load()
    if lib is None:
        return default
    buf = ctypes.create_string_buffer(4096)
    if not lib.ptrt_flag_get(key.encode(), buf, len(buf)):
        return default
    return buf.value.decode()


def stat_add(key: str, value: int) -> None:
    lib = _load()
    if lib is not None:
        lib.ptrt_stat_add(key.encode(), int(value))


def stat_value(key: str) -> int:
    lib = _load()
    return 0 if lib is None else int(lib.ptrt_stat_value(key.encode()))


def tracer_enable():
    lib = _load()
    if lib is not None:
        lib.ptrt_tracer_enable()


def tracer_disable():
    lib = _load()
    if lib is not None:
        lib.ptrt_tracer_disable()


def trace_record(name: str, start_ns: int, dur_ns: int):
    lib = _load()
    if lib is not None:
        lib.ptrt_trace_record(name.encode(), start_ns, dur_ns)


def trace_clear():
    lib = _load()
    if lib is not None:
        lib.ptrt_trace_clear()


def now_ns() -> int:
    lib = _load()
    if lib is None:
        import time
        return time.monotonic_ns()
    return int(lib.ptrt_now_ns())


def trace_export_json() -> str:
    lib = _load()
    if lib is None:
        return '{"traceEvents":[]}'
    n = lib.ptrt_trace_export(None, 0)
    buf = ctypes.create_string_buffer(n)
    lib.ptrt_trace_export(buf, n)
    return buf.value.decode()


class RecordEvent:
    """RAII trace annotation (reference platform/profiler.h RecordEvent)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._t0 = now_ns()
        return self

    def __exit__(self, *exc):
        trace_record(self.name, self._t0, now_ns() - self._t0)
        return False
