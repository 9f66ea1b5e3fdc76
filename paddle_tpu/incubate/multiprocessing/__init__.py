"""paddle.incubate.multiprocessing parity (reference:
incubate/multiprocessing/reductions.py): make Tensors picklable across
process boundaries for DataLoader workers.

The reference registers CUDA-IPC reductions; device memory here is not
process-shareable (a chip belongs to one process), so tensors reduce
through host numpy buffers — correct everywhere, zero-copy nowhere.
"""
from __future__ import annotations

import copyreg

__all__ = ["init_reductions"]

_installed = [False]


def _rebuild_tensor(array, stop_gradient):
    import paddle_tpu
    t = paddle_tpu.to_tensor(array)
    t.stop_gradient = stop_gradient
    return t


def _reduce_tensor(t):
    return _rebuild_tensor, (t.numpy(), t.stop_gradient)


def init_reductions():
    """Register pickle reductions for Tensor (idempotent)."""
    if _installed[0]:
        return
    from paddle_tpu.core.tensor import Parameter, Tensor
    copyreg.pickle(Tensor, _reduce_tensor)
    copyreg.pickle(Parameter, _reduce_tensor)
    _installed[0] = True
