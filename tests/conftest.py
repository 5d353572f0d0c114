"""Test config: the tests run on the CPU, on a deterministic mesh of 8
virtual devices (SURVEY.md §4 — multi-device tests simulated like the
reference's multi-process localhost simulation in test_dist_base.py).
Run them with ``JAX_PLATFORMS=cpu`` in the environment."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
# in XLA_FLAGS for the child processes that tests start (they inherit the
# environment); this process gets its 8 devices from the config below
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# Tier-1 runs at XLA backend optimization level 0: the suite is
# compile-bound on the CPU CI box (tiny models, hundreds of fresh
# executables) and level 0 roughly halves compile time while leaving
# semantics alone — every parity test compares two paths compiled under
# the same flag, and the SPMD partitioner/collective insertion (what the
# sharded HLO assertions inspect) runs regardless of backend opt level.
# Respect an explicit caller override.
if "xla_backend_optimization_level" not in flags:
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def native_runtime():
    """The C++ runtime library (csrc/), built on first use.  Asked for
    from inside a test, never while a module is imported: every xdist
    worker imports every test file, and none of them should start a
    build to decide what to collect.  cmake, ninja and g++ are part of
    the installation, so a library that is not there is a failure."""
    from paddle_tpu.core import native

    if not native.native_available():
        pytest.fail(f"native runtime not built: {native.build_error()}")
    return native


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu

    paddle_tpu.seed(42)
    np.random.seed(42)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "sanitize: run under FLAGS_sanitize=1 (paddle_tpu.analysis."
        "sanitizer): warm retraces raise, donated buffers tombstone, "
        "lock order is recorded, the KV pool is audited every step")
    config.addinivalue_line(
        "markers",
        "slow: long-running, non-tier-1 tests (full-scale bench legs, "
        "redundant compile-heavy subprocess smokes) — excluded by the "
        "tier-1 `-m 'not slow'` run so the suite fits its time "
        "budget; run them with `-m slow` (or no marker filter)")


@pytest.fixture(autouse=True)
def _sanitize_marker(request):
    """Tests marked @pytest.mark.sanitize run with the runtime
    sanitizer armed; its state is reset on both sides so one test's
    tombstones/lock edges can never fail another."""
    if request.node.get_closest_marker("sanitize") is None:
        yield
        return
    import paddle_tpu
    from paddle_tpu.analysis import sanitizer
    from paddle_tpu.core import flags as _flags

    prior = bool(_flags.flag("sanitize"))  # honor a suite-wide opt-in
    paddle_tpu.set_flags({"sanitize": True})
    sanitizer.reset()
    try:
        yield
    finally:
        paddle_tpu.set_flags({"sanitize": prior})
        sanitizer.reset()
