"""Device/Place layer over JAX devices.

Capability-parity with the reference Place variants
(/root/reference/paddle/fluid/platform/place.h:103 — CPUPlace, CUDAPlace,
XPUPlace, CUDAPinnedPlace) and DeviceContextPool
(/root/reference/paddle/fluid/platform/device_context.h:96,695), redesigned
TPU-first: a Place names a jax.Device; there are no streams or contexts to
manage (XLA owns them); the "pool" is jax.devices(). Meshes for SPMD live in
paddle_tpu.parallel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax


class Place:
    """Base place: names a logical device kind + index."""

    kind = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    # -- JAX bridge ---------------------------------------------------------
    def get_device(self) -> jax.Device:
        # jax raises "Unknown backend" when the kind this place names
        # is absent: a TPUPlace on a CPU host is an error, not a CPU
        devs = jax.devices(self.kind)
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return (isinstance(other, Place) and other.kind == self.kind
                and other.device_id == self.device_id)

    def __hash__(self):
        return hash((self.kind, self.device_id))

    def __repr__(self):
        return f"Place({self.kind}:{self.device_id})"


class CPUPlace(Place):
    kind = "cpu"


class TPUPlace(Place):
    """The headline device of this framework (reference: CUDAPlace)."""
    kind = "tpu"


class CUDAPlace(Place):  # capability alias: JAX gpu backend
    kind = "gpu"


class CUDAPinnedPlace(Place):
    """Pinned-host place (reference CUDAPinnedPlace): host staging
    memory; on TPU all host arrays are staged by the runtime, so this
    is CPU-kind for placement purposes."""

    def __init__(self):
        super().__init__("cpu", 0)


class XPUPlace(Place):
    kind = "xpu"


@functools.lru_cache(maxsize=None)
def _default_place() -> Place:
    kinds = {d.platform for d in jax.devices()}
    if "tpu" in kinds:
        return TPUPlace(0)
    if "gpu" in kinds:
        return CUDAPlace(0)
    return CPUPlace(0)


_current_place: Optional[Place] = None


def set_device(device) -> Place:
    """paddle.set_device equivalent. Accepts 'tpu', 'tpu:1', 'cpu', Place."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return device
    name, _, idx = str(device).partition(":")
    idx = int(idx) if idx else 0
    cls = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": CUDAPlace,
           "xpu": XPUPlace}.get(name)
    if cls is None:
        raise ValueError(f"unknown device '{device}'")
    _current_place = cls(idx)
    return _current_place


def get_device() -> str:
    p = current_place()
    return f"{p.kind}:{p.device_id}"


def current_place() -> Place:
    return _current_place if _current_place is not None else _default_place()


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False


def device_count(kind: Optional[str] = None) -> int:
    if kind is None:
        return len(jax.devices())
    return len([d for d in jax.devices() if d.platform == kind])
