"""Device abstraction.

TPU-native analogue of Place / DeviceContext / DeviceContextPool
(reference: paddle/fluid/platform/place.h:26-103, device_context.h:104-691).

On TPU there are no per-device user streams or vendor handles — XLA owns the
execution stream — so a Place is simply an identity wrapper over a
``jax.Device`` plus helpers to pick the current device. The DeviceContextPool
collapses into jax's device list.
"""
from __future__ import annotations

from typing import Optional

import jax


class Place:
    """Device identity (reference place.h Place tagged union)."""

    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if d.platform == self.device_type] or \
            jax.devices()
        return devs[self._device_id % len(devs)]

    def __eq__(self, other):
        return (type(self) is type(other)
                and self._device_id == other._device_id)

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self._device_id})"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    """The accelerator place (reference CUDAPlace, place.h:37)."""

    device_type = "tpu"


# Alias so code written against the reference API keeps working.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace


class CUDAPinnedPlace(CPUPlace):
    """Compat: host-pinned memory is managed by the XLA transfer manager."""


_expected_place: Optional[Place] = None


def target_platform() -> str:
    """Platform the current computation is being COMPILED FOR — not the
    process's default backend. It is ``jax.default_backend()`` ("tpu" on
    the chip: every Pallas gate then compiles with Mosaic), unless
    PADDLE_TPU_TARGET_PLATFORM overrides it. The override is for AOT
    lowering against a TPU topology (jax.experimental.topologies with
    the bundled libtpu) from a CPU-only process, the pre-flight
    tests/test_tpu_lowering.py runs: the CPU-backend workarounds
    (bf16-collective promotion, pallas interpret mode) must key off the
    target, or the AOT artifact would bake the workarounds into the TPU
    program."""
    import os

    forced = os.environ.get("PADDLE_TPU_TARGET_PLATFORM")
    if forced:
        return forced
    return jax.default_backend()


def device_count() -> int:
    """Number of local accelerator devices (reference gpu_info GetCUDADeviceCount)."""
    return len([d for d in jax.devices() if d.platform != "cpu"]) or 0


def is_compiled_with_tpu() -> bool:
    return device_count() > 0


# Reference API names kept for switchers.
is_compiled_with_cuda = is_compiled_with_tpu


def set_device(device) -> Place:
    """paddle.set_device: 'tpu', 'tpu:0', 'cpu'."""
    global _expected_place
    if isinstance(device, Place):
        _expected_place = device
        return _expected_place
    name = str(device).lower()
    if name.startswith("cpu"):
        _expected_place = CPUPlace()
    else:
        idx = int(name.split(":")[1]) if ":" in name else 0
        _expected_place = TPUPlace(idx)
    return _expected_place


def get_device() -> str:
    p = expected_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"tpu:{p.get_device_id()}"


def expected_place() -> Place:
    global _expected_place
    if _expected_place is None:
        _expected_place = TPUPlace(0) if device_count() > 0 else CPUPlace()
    return _expected_place
