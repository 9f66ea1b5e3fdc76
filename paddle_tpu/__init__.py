"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capabilities, built from scratch on JAX/XLA/Pallas.

API surface mirrors `import paddle` (reference: python/paddle/__init__.py);
execution is TPU-first: eager ops run as JAX primitives with a VJP-tape
autograd, and `paddle_tpu.jit.to_static` compiles whole train steps (forward +
backward + optimizer) into a single XLA program over a `jax.sharding.Mesh`.
"""
from __future__ import annotations

__version__ = "0.1.0"

from paddle_tpu.core.tensor import Parameter, Tensor  # noqa: F401
from paddle_tpu.core import dtype as _dtype_mod
from paddle_tpu.core.dtype import (  # noqa: F401
    bfloat16,
    bool,  # noqa: A004
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from paddle_tpu.core.device import (  # noqa: F401
    CPUPlace,
    CUDAPinnedPlace,
    CUDAPlace,
    TPUPlace,
    XPUPlace,
    device_count,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_rocm,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    set_device,
)

# tensor ops into the root namespace (paddle.add, paddle.reshape, ...)
from paddle_tpu.tensor import *  # noqa: F401,F403
from paddle_tpu.tensor import einsum  # noqa: F401

from paddle_tpu.core import ops_binding as _ops_binding

_ops_binding.bind_all()

from paddle_tpu.autograd import enable_grad, grad, no_grad, set_grad_enabled  # noqa: F401,E402
from paddle_tpu.framework.state import get_flags, seed, set_flags  # noqa: F401,E402
from paddle_tpu.framework.io import load, save  # noqa: F401,E402

from paddle_tpu import (  # noqa: F401,E402
    amp,
    audio,
    autograd,
    callbacks,
    cost_model,
    dataset,
    device,
    distributed,
    distribution,
    fft,
    framework,
    geometric,
    hub,
    incubate,
    inference,
    io,
    jit,
    linalg,
    metric,
    nn,
    optimizer,
    onnx,
    profiler,
    quantization,
    reader,
    regularizer,
    signal,
    static,
    sparse,
    sysconfig,
    tensor,
    text,
    utils,
    vision,
)
# the function shadows its module at the package root, as in the
# reference (paddle/__init__.py imports and calls it at import time —
# we only call when scipy is actually bundled)
from paddle_tpu.check_import_scipy import check_import_scipy  # noqa: F401,E402,E501
from paddle_tpu.batch import batch  # noqa: F401,E402
from paddle_tpu.hapi.model import Model  # noqa: F401,E402
from paddle_tpu.jit.api import to_static  # noqa: F401,E402
from paddle_tpu.nn.layer.layers import disable_static, enable_static  # noqa: F401,E402


def is_grad_enabled():
    from paddle_tpu.core import engine
    return engine.is_grad_enabled()


def in_dynamic_mode():
    return framework.in_dynamic_mode()


# `paddle.Tensor`-style namespace helpers
def numel(x, name=None):
    return tensor.numel(x)


def is_tensor(x):
    return isinstance(x, Tensor)


def get_cudnn_version():
    return None


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Rough analytic FLOPs counter (reference: python/paddle/hapi/dynamic_flops.py)."""
    import numpy as _np
    total = [0]
    from paddle_tpu.nn.layer import layers as _L

    def hook(layer, inp, out):
        import paddle_tpu.nn as _nn
        if isinstance(layer, _nn.Linear):
            total[0] += 2 * _np.prod(inp[0].shape) * layer.weight.shape[-1]
        elif isinstance(layer, _nn.Conv2D):
            oshape = out.shape
            k = _np.prod(layer.weight.shape[1:])
            total[0] += 2 * _np.prod(oshape) * k
    hooks = [l.register_forward_post_hook(hook) for l in net.sublayers()]
    import paddle_tpu as _p
    x = _p.zeros(input_size)
    net(x)
    for h in hooks:
        h.remove()
    if print_detail:
        print(f"Total FLOPs: {total[0]}")
    return total[0]


# ---- remaining reference top-level surface (python/paddle/__init__.py) ----
from paddle_tpu.distributed.parallel import DataParallel  # noqa: E402,F401
from paddle_tpu.nn.initializer import ParamAttr  # noqa: E402,F401


def cast(x, dtype):
    """paddle.cast(x, dtype) (the method form is Tensor.cast)."""
    return x.cast(dtype)


def reverse(x, axis, name=None):
    """Legacy alias of flip (reference keeps both)."""
    return flip(x, axis)


def tolist(x):
    return x.tolist()


def index_add_(x, index, axis, value, name=None):
    """In-place index_add (reference index_add_): same tape semantics as
    the out-of-place op — _inplace_assign adopts the new autograd node so
    gradients flow to `value` (a raw value rebind would drop them)."""
    out = index_add(x, index, axis, value)
    x._inplace_assign(out)
    return x


def frexp(x, name=None):
    """(mantissa, exponent) with x = mantissa * 2**exponent,
    0.5 <= |mantissa| < 1 (reference tensor/math.py frexp)."""
    import jax.numpy as jnp

    from paddle_tpu.core.dispatch import apply
    def fn(v):
        exp = jnp.where(v == 0, 0.0, jnp.floor(jnp.log2(jnp.abs(v))) + 1.0)
        mant = v / jnp.exp2(exp)
        return mant, exp.astype(v.dtype)
    return apply(fn, x)


class iinfo:
    """Integer dtype limits (reference paddle.iinfo)."""

    def __init__(self, dtype):
        import numpy as _np
        info = _np.iinfo(_dtype_mod.convert_dtype(dtype))
        self.min = int(info.min)
        self.max = int(info.max)
        self.bits = int(info.bits)
        self.dtype = str(info.dtype)


class finfo:
    """Float dtype limits (reference paddle.finfo)."""

    def __init__(self, dtype):
        import jax.numpy as jnp
        import numpy as _np
        name = str(dtype).split(".")[-1]
        info = jnp.finfo(jnp.bfloat16 if name == "bfloat16"
                         else _np.dtype(name))
        self.min = float(info.min)
        self.max = float(info.max)
        self.eps = float(info.eps)
        self.tiny = float(info.tiny)
        self.smallest_normal = float(info.tiny)
        self.bits = int(info.bits)
        self.dtype = name


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Numpy-backed print options (Tensor repr renders through numpy)."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def check_shape(shape):
    """Validate a shape argument the way reference creation ops do."""
    if isinstance(shape, Tensor):
        return
    for s in shape:
        if not isinstance(s, (int, Tensor)) or (
                isinstance(s, int) and s < -1):
            raise ValueError(f"invalid shape entry {s!r}")


def disable_signal_handler():
    """The reference unhooks its C++ crash handlers; there are none."""
    return None


def summary(net, input_size=None, dtypes=None, input=None):
    """paddle.summary parity: delegate to hapi Model.summary; a sample
    `input` tensor is forwarded AS-IS so its dtype survives (integer ids
    feed embedding networks correctly)."""
    from paddle_tpu.hapi.model import Model
    return Model(net).summary(input_size=input_size,
                              dtype=dtypes[0] if dtypes else None,
                              input=input)


class LazyGuard:
    """Reference LazyGuard defers parameter materialization; init here is
    host-side numpy (already cheap/lazy-friendly), so the guard is a
    compatibility context manager."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class NPUPlace:
    """Reference NPUPlace; no NPU exists on this backend."""

    def __init__(self, device_id=0):
        raise RuntimeError("NPU devices do not exist on the TPU backend; "
                           "use paddle.set_device('tpu')")


def get_cuda_rng_state():
    """No CUDA RNG: the global PRNG key covers every device; returned
    value round-trips through set_cuda_rng_state."""
    from paddle_tpu.framework import state as _state
    return [_state.get_rng_state()] if hasattr(_state, "get_rng_state") \
        else []


def set_cuda_rng_state(state_list):
    from paddle_tpu.framework import state as _state
    if state_list and hasattr(_state, "set_rng_state"):
        _state.set_rng_state(state_list[0])


# paddle.dtype is the dtype TYPE (paddle.dtype('float32') etc.); dtypes
# here are numpy dtypes, so the type is np.dtype
import numpy as _np_mod  # noqa: E402

dtype = _np_mod.dtype


def __getattr__(name):
    # paddle_tpu.onnx loads lazily: its protoc-generated binding needs
    # google.protobuf, which only ONNX exporters should have to carry.
    # paddle_tpu.analysis (tracelint) loads lazily too: it is pure
    # stdlib and the CLI imports it without this package __init__.
    # paddle_tpu.serving lazily as well: the engine compiles nothing at
    # import time, but serving is an opt-in subsystem like onnx export.
    if name in ("onnx", "analysis", "serving", "observability",
                "resilience"):
        import importlib
        return importlib.import_module(f"paddle_tpu.{name}")
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")
