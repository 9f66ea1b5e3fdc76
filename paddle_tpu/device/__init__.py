"""paddle.device parity namespace (reference: python/paddle/device/).

The reference hosts CUDA stream/event control here; the TPU analogue of
"synchronize" is draining the async XLA dispatch queue.
"""
from __future__ import annotations

from paddle_tpu.core.device import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    TPUPlace,
    XPUPlace,
    get_device,
    device_count,
    is_compiled_with_cuda,
    is_compiled_with_npu,
    is_compiled_with_rocm,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    set_device,
)

from . import cuda  # noqa: F401


class IPUPlace:
    def __init__(self, *a):
        raise RuntimeError("IPU is not a TPU-system device; use TPUPlace")


class MLUPlace:
    def __init__(self, *a):
        raise RuntimeError("MLU is not a TPU-system device; use TPUPlace")


def get_cudnn_version():
    return None


def is_compiled_with_cinn():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_mlu():
    return False

__all__ = [
    "get_device", "set_device", "device_count", "synchronize",
    "is_compiled_with_cuda", "is_compiled_with_rocm",
    "is_compiled_with_xpu", "is_compiled_with_npu",
    "is_compiled_with_tpu", "get_all_device_type",
    "get_all_custom_device_type", "get_available_device",
    "get_available_custom_device",
    "CPUPlace", "CUDAPlace", "CUDAPinnedPlace", "TPUPlace", "XPUPlace",
    "IPUPlace", "MLUPlace", "get_cudnn_version",
    "is_compiled_with_cinn", "is_compiled_with_ipu",
    "is_compiled_with_mlu",
]


def synchronize(device=None):
    """Block until all queued device work completes (the reference's
    cuda.synchronize; XLA's dispatch is async the same way)."""
    import jax
    jax.block_until_ready(
        jax.device_put(0, jax.devices()[0] if device is None else device))


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device():
    import jax
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if not d.startswith(("cpu", "gpu"))]
