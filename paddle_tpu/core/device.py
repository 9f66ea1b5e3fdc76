"""Device / place management.

Reference parity: python/paddle/device/__init__.py (set_device, get_device,
CPUPlace/CUDAPlace/XPUPlace). TPU-first: the native accelerator place is
``TPUPlace``; ``CUDAPlace`` is accepted as an alias for the accelerator so
reference scripts run unmodified.
"""
from __future__ import annotations

import jax


class Place:
    _kind = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    def __repr__(self):
        return f"Place({self._kind}:{self._device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self._kind == other._kind
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self._kind, self._device_id))

    @property
    def jax_device(self):
        """The jax device this place names; raises when the backend has
        no such device (a place never resolves to some other device)."""
        devs = jax.devices(self._kind)
        if not 0 <= self._device_id < len(devs):
            raise ValueError(
                f"{self!r}: the {self._kind} backend has {len(devs)} "
                f"device(s)")
        return devs[self._device_id]


class CPUPlace(Place):
    _kind = "cpu"


class TPUPlace(Place):
    _kind = "tpu"


# Alias so reference code using CUDAPlace targets the accelerator.
class CUDAPlace(TPUPlace):
    pass


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


_current_place = [None]


def _default_place() -> Place:
    if _current_place[0] is None:
        plat = jax.default_backend()
        _current_place[0] = CPUPlace(0) if plat == "cpu" else TPUPlace(0)
    return _current_place[0]


def set_device(device: str) -> Place:
    """set_device("tpu"), set_device("tpu:0"), set_device("cpu"), "gpu" aliases tpu."""
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        place = CPUPlace(idx)
    elif name in ("tpu", "gpu", "cuda", "xpu", "npu"):
        place = TPUPlace(idx)
    else:
        raise ValueError(f"Unknown device {device!r}")
    _current_place[0] = place
    return place


def get_device() -> str:
    p = _default_place()
    return f"{p._kind}:{p._device_id}"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def device_count() -> int:
    return jax.device_count()
