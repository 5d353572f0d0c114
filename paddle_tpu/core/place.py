"""Device identity ("Place") system.

Reference: `paddle/fluid/platform/place.h:26-150` defines CPUPlace / CUDAPlace
/ XPUPlace / NPUPlace as a tagged union.  Here the accelerator is the TPU and
device handles are `jax.Device` objects; a Place is a thin named handle that
resolves to one.  Unlike the reference there is no per-place kernel registry —
placement is expressed to XLA via shardings / `jax.device_put`.
"""
from __future__ import annotations

import functools

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            type(self) is type(other) and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs:
            raise RuntimeError(
                f"{self!r} asked for a {self.device_type!r} device and JAX "
                f"has none (devices: {jax.devices()})")
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "tpu"


# Alias kept so reference-era code written against CUDAPlace keeps running:
# the accelerator place in this framework is the TPU.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace


_EXPECTED_PLACE = [None]


@functools.lru_cache(maxsize=None)
def _default_place() -> Place:
    platforms = {d.platform for d in jax.devices()}
    if "tpu" in platforms:
        return TPUPlace(0)
    return CPUPlace(0)


def set_device(device) -> Place:
    """paddle.set_device equivalent: 'cpu', 'tpu', 'tpu:0', Place."""
    if isinstance(device, Place):
        _EXPECTED_PLACE[0] = device
        return device
    name, _, idx = str(device).partition(":")
    idx = int(idx) if idx else 0
    cls = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": TPUPlace, "xpu": TPUPlace}.get(
        name
    )
    if cls is None:
        raise ValueError(f"unknown device {device!r}")
    _EXPECTED_PLACE[0] = cls(idx)
    return _EXPECTED_PLACE[0]


def get_device() -> str:
    p = _EXPECTED_PLACE[0] or _default_place()
    return f"{p.device_type}:{p.device_id}"


def expected_place() -> Place:
    return _EXPECTED_PLACE[0] or _default_place()


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())
