"""Normalization functionals. Reference: python/paddle/nn/functional/norm.py.

batch_norm follows paddle semantics: in training mode it normalizes with
batch statistics and updates running stats in-place (value rebind — captured
functionally under to_static); in eval mode it uses running stats.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.dispatch import apply
from paddle_tpu.core.engine import no_grad


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def fn(v):
        norm = jnp.sum(jnp.abs(v) ** p, axis=axis, keepdims=True) ** (1.0 / p)
        return v / jnp.maximum(norm, epsilon)
    return apply(fn, x)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-05,
               data_format="NCHW", use_global_stats=None, name=None):
    channel_axis = 1 if data_format.startswith("NC") else -1
    use_batch_stats = training and not use_global_stats

    if use_batch_stats:
        # compute batch stats and update running stats (paddle: r = m*r + (1-m)*b)
        def fn(v, rm, rv, w, b):
            axes = tuple(i for i in range(v.ndim) if i != channel_axis % v.ndim)
            # centered two-pass variance: E[(x-m)²], NOT E[x²]-E[x]² — the
            # one-pass form catastrophically cancels in fp32 when |mean| >>
            # std (e.g. un-centered raw features), and the corrupted var
            # would poison running_var for eval. fp32 accumulation
            # regardless of activation dtype; output keeps v.dtype.
            vf = v.astype(jnp.float32)
            mean = jnp.mean(vf, axis=axes)
            var = jnp.var(vf, axis=axes)
            shape = [1] * v.ndim
            shape[channel_axis % v.ndim] = -1
            # subtract the mean BEFORE scaling (fold only the affine into
            # the per-channel scale): vf*scale - mean*scale would cancel
            # catastrophically when |mean| >> std; (vf - mean) keeps the
            # bits and still fuses into one elementwise pass
            inv = jax.lax.rsqrt(var + epsilon)
            scale = inv if w is None else inv * w.astype(jnp.float32)
            out = (vf - mean.reshape(shape)) * scale.reshape(shape)
            if b is not None:
                out = out + b.astype(jnp.float32).reshape(shape)
            return out.astype(v.dtype), mean, var
        out, mean_t, var_t = apply(fn, x, running_mean, running_var, weight, bias)
        with no_grad():
            n = int(np.prod([s for i, s in enumerate(x.shape)
                             if i != channel_axis % x.ndim]))
            unbias = n / max(n - 1, 1)
            # update in fp32, then cast BACK to the buffer dtype — the fp32
            # stats must not silently promote bf16 (O2) running buffers
            rm_dt = running_mean._value.dtype
            rv_dt = running_var._value.dtype
            running_mean._set_value(
                (momentum * running_mean._value.astype(jnp.float32) +
                 (1 - momentum) * mean_t._value).astype(rm_dt))
            running_var._set_value(
                (momentum * running_var._value.astype(jnp.float32) +
                 (1 - momentum) * var_t._value * unbias).astype(rv_dt))
        return out

    def fn_eval(v, rm, rv, w, b):
        shape = [1] * v.ndim
        shape[channel_axis % v.ndim] = -1
        # per-channel scale computed on (C,) vectors in fp32 (stats/affine
        # may be bf16 under O2 decorate); mean subtracted before scaling
        # (see training path: the folded form cancels for |mean| >> std)
        inv = jax.lax.rsqrt(rv.astype(jnp.float32) + epsilon)
        scale = inv if w is None else inv * w.astype(jnp.float32)
        out = (v.astype(jnp.float32) - rm.astype(jnp.float32)
               .reshape(shape)) * scale.reshape(shape)
        if b is not None:
            out = out + b.astype(jnp.float32).reshape(shape)
        return out.astype(v.dtype)
    return apply(fn_eval, x, running_mean, running_var, weight, bias)


# opt-in global flag for the Pallas fused-norm paths off-TPU (CPU runs
# them in interpret mode — same numerics, and whole-program cost models
# see the fused call boundary instead of the op-by-op composition).
# On TPU the fused path is the default regardless.  Per-call `fused=`
# (and nn.LayerNorm(fused=...)) overrides in either direction.
_FUSED_NORM = [False]


def set_fused_norm(flag=True):
    """Globally enable/disable the Pallas fused LN/RMS-norm paths off
    TPU; returns the previous value (docs/performance_guide.md,
    "Cutting bytes/step")."""
    prev = _FUSED_NORM[0]
    _FUSED_NORM[0] = bool(flag)
    return prev


def fused_norm_enabled():
    return _FUSED_NORM[0]


def _use_fused(fused):
    if fused is not None:
        return bool(fused)
    if _FUSED_NORM[0]:
        return True
    from paddle_tpu.ops.pallas import kernel_default
    return kernel_default()


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-05,
               name=None, fused=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    nd = len(tuple(normalized_shape))

    if nd == 1 and _use_fused(fused):
        # last-axis layernorm: fused Pallas kernel (custom VJP whose
        # backward recomputes the stats; interpret mode off-TPU)
        from paddle_tpu.ops.pallas.norm import fused_layer_norm
        return apply(lambda v, w, b: fused_layer_norm(
            v, w, b, epsilon), x, weight, bias)

    def fn(v, w, b):
        from paddle_tpu.amp.auto_cast import downcast_inputs
        from paddle_tpu.amp.policy import residency_dtype
        orig_dtype = v.dtype
        (v,) = downcast_inputs(v, opname="layer_norm")
        axes = tuple(range(v.ndim - nd, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) / jnp.sqrt(var + epsilon)
        if w is not None:
            out = out * w
        if b is not None:
            out = out + b
        # bf16 activation residency: the blacklist upcast computed the
        # norm in f32 for stability, but STORING the result f32 is what
        # shardlint SL303 flags — under a policy the output returns to
        # the residency-dtype stream
        if residency_dtype() is not None and out.dtype != orig_dtype:
            out = out.astype(orig_dtype)
        return out
    return apply(fn, x, weight, bias)


def fused_ln_residual(x, residual, weight=None, bias=None, epsilon=1e-5,
                      act=None, name=None, fused=None):
    """``h = x + residual; y = act(LN(h))`` in one pass, returning
    ``(h, y)`` — the residual-stream update and the next sublayer's
    normalized input.  On the fused path (Pallas kernel, interpret mode
    off-TPU) the custom VJP recomputes the normalized intermediate in
    backward instead of materializing it; the pure-JAX composition is
    the unfused path (weight-free norms always use it).  ``act`` is None
    or ``"gelu"`` (tanh approximation)."""
    if _use_fused(fused) and weight is not None:
        from paddle_tpu.ops.pallas.norm import (
            fused_ln_residual as _pallas_ln_res)
        return apply(lambda a, r, w, b: _pallas_ln_res(
            a, r, w, b, epsilon, act), x, residual, weight, bias)

    def fn(a, r, w, b):
        h = a + r
        hf = h.astype(jnp.float32)
        mean = jnp.mean(hf, axis=-1, keepdims=True)
        var = jnp.var(hf, axis=-1, keepdims=True)
        out = (hf - mean) / jnp.sqrt(var + epsilon)
        if w is not None:
            out = out * w.astype(jnp.float32)
        if b is not None:
            out = out + b.astype(jnp.float32)
        if act == "gelu":
            out = jax.nn.gelu(out, approximate=True)
        return h, out.astype(h.dtype)
    return apply(fn, x, residual, weight, bias)


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-05,
                  data_format="NCHW", name=None):
    def fn(v, w, b):
        axes = tuple(range(2, v.ndim))
        mean = jnp.mean(v, axis=axes, keepdims=True)
        var = jnp.var(v, axis=axes, keepdims=True)
        out = (v - mean) / jnp.sqrt(var + eps)
        if w is not None:
            shape = [1, -1] + [1] * (v.ndim - 2)
            out = out * w.reshape(shape)
        if b is not None:
            shape = [1, -1] + [1] * (v.ndim - 2)
            out = out + b.reshape(shape)
        return out
    return apply(fn, x, weight, bias)


def group_norm(x, num_groups, epsilon=1e-05, weight=None, bias=None,
               data_format="NCHW", name=None):
    def fn(v, w, b):
        cl = not data_format.startswith("NC")
        if cl:
            v = jnp.moveaxis(v, -1, 1)
        n, c = v.shape[:2]
        g = num_groups
        vv = v.reshape((n, g, c // g) + v.shape[2:])
        axes = tuple(range(2, vv.ndim))
        mean = jnp.mean(vv, axis=axes, keepdims=True)
        var = jnp.var(vv, axis=axes, keepdims=True)
        out = ((vv - mean) / jnp.sqrt(var + epsilon)).reshape(v.shape)
        shape = [1, -1] + [1] * (v.ndim - 2)
        if w is not None:
            out = out * w.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        if cl:
            out = jnp.moveaxis(out, 1, -1)
        return out
    return apply(fn, x, weight, bias)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def fn(v):
        cl = not data_format.startswith("NC")
        if cl:
            v = jnp.moveaxis(v, -1, 1)
        sq = jnp.square(v)
        c = v.shape[1]
        half = size // 2
        pad_lo, pad_hi = half, size - half - 1
        sqp = jnp.pad(sq, [(0, 0), (pad_lo, pad_hi)] + [(0, 0)] * (v.ndim - 2))
        acc = jnp.zeros_like(v)
        for i in range(size):
            acc = acc + sqp[:, i:i + c]
        out = v / (k + alpha * acc) ** beta
        if cl:
            out = jnp.moveaxis(out, 1, -1)
        return out
    return apply(fn, x)


def rms_norm(x, weight=None, epsilon=1e-6, name=None, fused=None):
    """RMSNorm (TPU-friendly LLM building block; also via pallas kernel)."""
    if _use_fused(fused):
        from paddle_tpu.ops.pallas.norm import fused_rms_norm
        return apply(lambda v, w: fused_rms_norm(v, w, epsilon),
                     x, weight)

    def fn(v, w):
        ms = jnp.mean(jnp.square(v.astype(jnp.float32)), axis=-1, keepdims=True)
        out = (v.astype(jnp.float32) / jnp.sqrt(ms + epsilon)).astype(v.dtype)
        if w is not None:
            out = out * w
        return out
    return apply(fn, x, weight)


def spectral_norm(weight, weight_u, weight_v, dim=0, power_iters=1, eps=1e-12,
                  name=None):
    def fn(w, u, v):
        wm = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
        for _ in range(power_iters):
            v = wm.T @ u
            v = v / jnp.maximum(jnp.linalg.norm(v), eps)
            u = wm @ v
            u = u / jnp.maximum(jnp.linalg.norm(u), eps)
        sigma = u @ wm @ v
        return w / sigma, u, v
    out, u_new, v_new = apply(fn, weight, weight_u, weight_v)
    # persist the power iteration so u/v converge across steps
    with no_grad():
        weight_u._set_value(u_new._value)
        weight_v._set_value(v_new._value)
    return out
