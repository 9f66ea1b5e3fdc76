"""paddle_tpu.Tensor — a paddle-compatible eager tensor over ``jax.Array``.

Reference parity: paddle's eager Tensor (paddle/fluid/pybind/eager_method.cc,
python/paddle/fluid/dygraph/varbase_patch_methods.py). TPU-first design:
values are immutable jax.Arrays; "in-place" ops rebind ``_value`` and bump a
version counter (used by the autograd engine for correctness). Every op flows
through :func:`apply`, which optionally records a ``jax.vjp`` pullback Node so
``loss.backward()`` works in eager mode and — because the same code path runs
on JAX tracers — whole train steps compile to one XLA program under
``paddle_tpu.jit.to_static``.
"""
from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import engine
from paddle_tpu.core import dtype as dtypes
from paddle_tpu.core.device import CPUPlace, Place, TPUPlace, _default_place

_tree = jax.tree_util


def _is_diff_dtype(dt) -> bool:
    return jnp.issubdtype(dt, jnp.floating) or jnp.issubdtype(dt, jnp.complexfloating)


class Tensor:
    __slots__ = (
        "_value",
        "stop_gradient",
        "grad",
        "name",
        "persistable",
        "_node",
        "_version",
        "_uid",
        "__weakref__",
        "__dict__",
    )

    _tensor_id = [0]

    def __init__(self, value, stop_gradient=True, name=None, place=None):
        if isinstance(value, Tensor):
            value = value._value
        if not isinstance(value, jax.Array) and not isinstance(value, jax.core.Tracer):
            value = jnp.asarray(value)
        self._value = value
        self._init_meta(stop_gradient, name)

    def _init_meta(self, stop_gradient, name=None):
        """Non-storage field init, shared with subclasses that manage
        their own storage (SparseCooTensor's lazy dense mirror)."""
        self.stop_gradient = bool(stop_gradient)
        self.grad = None
        Tensor._tensor_id[0] += 1
        self._uid = Tensor._tensor_id[0]   # never reused (id() can be)
        self.name = name or f"tensor_{Tensor._tensor_id[0]}"
        self.persistable = False
        self._node = None
        self._version = 0

    # ---- metadata ----
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def dim(self):
        return self._value.ndim

    @property
    def rank(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self):
        return np.dtype(self._value.dtype) if self._value.dtype != dtypes.bfloat16 else dtypes.bfloat16

    @property
    def place(self):
        try:
            dev = list(self._value.devices())[0]
            return CPUPlace(dev.id) if dev.platform == "cpu" else TPUPlace(dev.id)
        except Exception:
            return _default_place()

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def T(self):
        from paddle_tpu.tensor.linalg import t
        return t(self)

    def dims(self):
        return self.shape

    # ---- conversion ----
    def numpy(self):
        return np.asarray(self._value)

    def __array__(self, dtype=None):
        a = np.asarray(self._value)
        return a.astype(dtype) if dtype is not None else a

    def item(self, *args):
        if args:
            return np.asarray(self._value).item(*args)
        return np.asarray(self._value).item()

    def tolist(self):
        return np.asarray(self._value).tolist()

    def astype(self, dt):
        from paddle_tpu.core.dispatch import apply
        dt = dtypes.convert_dtype(dt)
        return apply(lambda v: v.astype(dt), self)

    def cast(self, dt):
        return self.astype(dt)

    def detach(self):
        t = Tensor(self._value, stop_gradient=True, name=self.name)
        return t

    def detach_(self):
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self):
        from paddle_tpu.core.dispatch import apply
        return apply(lambda v: v + 0 if v.dtype != np.dtype("bool") else v, self)

    def cpu(self):
        return Tensor(jax.device_put(self._value, CPUPlace(0).jax_device),
                      stop_gradient=self.stop_gradient)

    def cuda(self, device_id=0):
        return Tensor(jax.device_put(self._value, TPUPlace(device_id).jax_device),
                      stop_gradient=self.stop_gradient)

    tpu = cuda

    def pin_memory(self):
        return self

    def to(self, *args, **kwargs):
        dt = kwargs.get("dtype")
        device = kwargs.get("device")
        for a in args:
            if isinstance(a, str) and a.split(":")[0] in ("cpu", "gpu", "tpu", "cuda"):
                device = a
            elif isinstance(a, Place):
                device = a
            else:
                dt = a
        out = self
        if dt is not None:
            out = out.astype(dt)
        if device is not None:
            if isinstance(device, str):
                from paddle_tpu.core.device import set_device
                place = CPUPlace(0) if device.startswith("cpu") else TPUPlace(0)
            else:
                place = device
            out = Tensor(jax.device_put(out._value, place.jax_device),
                         stop_gradient=out.stop_gradient)
        return out

    def block_until_ready(self):
        self._value.block_until_ready()
        return self

    # ---- autograd ----
    def backward(self, grad_tensor=None, retain_graph=False):
        engine.backward(self, grad_tensor, retain_graph)

    def _accumulate_grad(self, g):
        for h in self.__dict__.get("_grad_hooks", ()):
            r = h(Tensor(g, stop_gradient=True))
            if r is not None:
                g = r._value if isinstance(r, Tensor) else jnp.asarray(r)
        if self.grad is None:
            self.grad = Tensor(g, stop_gradient=True, name=self.name + "@GRAD")
        else:
            self.grad._value = self.grad._value + g

    def clear_grad(self):
        from paddle_tpu.jit.api import note_grad_cleared
        note_grad_cleared(self._uid)
        self.grad = None

    clear_gradient = clear_grad

    def zero_grad(self):
        if self.grad is not None:
            self.grad._value = jnp.zeros_like(self.grad._value)

    @property
    def gradient(self):
        return None if self.grad is None else self.grad.numpy()

    def register_hook(self, hook):
        """Grad hook applied when this (leaf) tensor's grad is accumulated."""
        hooks = self.__dict__.setdefault("_grad_hooks", [])
        hooks.append(hook)
        return _HookHandle(self, hook)

    # ---- in-place machinery ----
    def _inplace_assign(self, new_tensor):
        """Adopt new value + node, bump version (in-place op semantics)."""
        self._value = new_tensor._value
        self._version += 1
        node = new_tensor._node
        if node is not None:
            node.out_uids = (self._uid,)
            node.out_versions = (self._version,)
            self._node = node
            self.stop_gradient = new_tensor.stop_gradient
        return self

    def _set_value(self, value):
        """Raw rebind (optimizer/buffer updates, under no_grad)."""
        if isinstance(value, Tensor):
            value = value._value
        self._value = value
        self._version += 1
        return self

    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._value
        value = jnp.asarray(value)
        if tuple(value.shape) != tuple(self._value.shape):
            raise ValueError(
                f"set_value shape mismatch {value.shape} vs {self._value.shape}")
        return self._set_value(value.astype(self._value.dtype))

    def get_tensor(self):
        return self

    # ---- indexing ----
    def _convert_index(self, idx):
        def conv(x):
            if isinstance(x, Tensor):
                return x._value
            return x
        if isinstance(idx, tuple):
            return tuple(conv(i) for i in idx)
        return conv(idx)

    def __getitem__(self, idx):
        from paddle_tpu.core.dispatch import apply
        idx = self._convert_index(idx)
        return apply(lambda v: v[idx], self)

    def __setitem__(self, idx, value):
        from paddle_tpu.core.dispatch import apply
        idx = self._convert_index(idx)

        def fn(v, val):
            val = jnp.asarray(val, dtype=v.dtype) if not hasattr(val, "dtype") else val.astype(v.dtype)
            return v.at[idx].set(val)

        out = apply(fn, self, value)
        self._inplace_assign(out)

    # ---- python protocol ----
    def __len__(self):
        if not self._value.shape:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __bool__(self):
        return bool(np.asarray(self._value))

    def __int__(self):
        return int(np.asarray(self._value))

    def __float__(self):
        return float(np.asarray(self._value))

    def __index__(self):
        return int(np.asarray(self._value))

    def __format__(self, spec):
        if self.size == 1:
            return format(self.item(), spec)
        return format(str(self), spec)

    def __hash__(self):
        return id(self)

    def __repr__(self):
        try:
            vals = np.asarray(self._value)
            body = np.array2string(vals, precision=8, separator=", ")
        except Exception:
            body = "<traced>"
        return (
            f"Tensor(shape={self.shape}, dtype={self._value.dtype}, "
            f"stop_gradient={self.stop_gradient},\n       {body})"
        )

    __str__ = __repr__

    def __dlpack__(self, *a, **kw):
        return self._value.__dlpack__(*a, **kw)


class _HookHandle:
    def __init__(self, tensor, hook):
        self._ref = weakref.ref(tensor)
        self._hook = hook

    def remove(self):
        t = self._ref()
        if t is not None:
            hooks = t.__dict__.get("_grad_hooks", [])
            if self._hook in hooks:
                hooks.remove(self._hook)


class Parameter(Tensor):
    """Trainable tensor (stop_gradient=False), auto-registered for to_static
    state lifting. Reference: python/paddle/fluid/framework.py Parameter."""

    def __init__(self, value, trainable=True, name=None):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True
        from paddle_tpu.framework.state import register_state_tensor
        register_state_tensor(self)

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v


def register_tensor_method(name, fn=None):
    """Attach a free function from paddle_tpu.tensor.* as a Tensor method."""
    def deco(f):
        setattr(Tensor, name, f)
        return f
    if fn is not None:
        return deco(fn)
    return deco
