"""Pallas TPU kernels (flash attention, fused norms, ring attention)."""
from __future__ import annotations

import jax


def compute_platform() -> str:
    """Platform the computation will actually run on: the installed mesh's
    devices if any (a CPU mesh can be active while the default backend is a
    real TPU chip — e.g. the driver's virtual-device dryrun), else the
    default backend."""
    from paddle_tpu.distributed.mesh import get_mesh
    m = get_mesh()
    if m is not None:
        return m.devices.flat[0].platform
    return jax.devices()[0].platform


def on_tpu() -> bool:
    return compute_platform() == "tpu"


def kernel_default() -> bool:
    """Whether ``nn.functional`` takes the Pallas kernel rather than the
    XLA composition when the caller states no preference.

    Kernels are the default on a TPU, except in a program GSPMD has to
    partition: a Mosaic custom call cannot be partitioned automatically
    (jax refuses to lower one in a multi-device jit), so under an
    installed multi-device mesh the sharding-propagation models take the
    XLA path, which GSPMD does partition.  Inside a ``shard_map`` body
    (``collective_axis`` set) every axis is manual and the kernels run on
    the per-device shards.
    """
    from paddle_tpu.distributed.mesh import (current_collective_axis,
                                             get_mesh)
    m = get_mesh()
    if m is not None and m.devices.size > 1 \
            and current_collective_axis() is None:
        return False
    return on_tpu()
