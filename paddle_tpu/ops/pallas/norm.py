"""Fused LayerNorm / RMSNorm Pallas kernels.

Reference parity: paddle/phi/kernels/gpu/layer_norm_kernel.cu (fused CUDA
layernorm). TPU-native: one VMEM pass per row block — mean/var/normalize/
affine fused in a single kernel (XLA already fuses these well; the kernel
removes the leftover HBM round-trips between the reduction and the scale).
The affine LayerNorm backward is a Pallas kernel too (custom VJP); RMSNorm
and the weight-free LayerNorm use the analytic formula in jnp.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_ROWS = 128
# per-block partial sums leave the kernel as one full f32 sublane tile
# (the row sum replicated down 8 sublanes): Mosaic rejects a (1, hidden)
# block of a (grid, hidden) array, and (8, hidden) is the smallest legal
_PARTIAL_ROWS = 8


def _vmem_spec(*args, **kwargs):
    kwargs["memory_space"] = pltpu.VMEM
    return pl.BlockSpec(*args, **kwargs)


def _on_tpu():
    from paddle_tpu.ops.pallas import on_tpu
    return on_tpu()


def _sublane(dtype):
    """MXU/VPU sublane count for a dtype (8 for f32, 16 for bf16) —
    the row granularity SL302-clean tile shapes are multiples of."""
    return max(8, 32 // max(1, jnp.dtype(dtype).itemsize))


def _auto_block_rows(rows, dtype, requested):
    """Block-row choice: the caller's request, else the smallest
    sublane multiple covering `rows` capped at DEFAULT_BLOCK_ROWS —
    small inputs then pay (at most) sublane-1 rows of padding instead
    of blowing up to a full 128-row block."""
    if requested:
        return int(requested)
    sub = _sublane(dtype)
    return min(DEFAULT_BLOCK_ROWS, -(-int(rows) // sub) * sub)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps, has_affine):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    if has_affine:
        y = y * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps, has_affine):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    if has_affine:
        y = y * w_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _pad_rows(x, block):
    pad = (-x.shape[0]) % block
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _run_rows_kernel(kernel, name, x2, extras, block_rows, interpret):
    """Run a row-block kernel over [rows, hidden] (rows padded to block);
    ``name`` is the kernel's name in the compiled program and the trace."""
    rows, hidden = x2.shape
    xp = _pad_rows(x2, block_rows)
    grid = (xp.shape[0] // block_rows,)
    in_specs = [_vmem_spec((block_rows, hidden), lambda i: (i, 0))]
    for e in extras:
        in_specs.append(_vmem_spec((1, hidden), lambda i: (0, 0)))
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=grid,
        in_specs=in_specs,
        out_specs=_vmem_spec((block_rows, hidden), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x2.dtype),
        interpret=interpret,
    )(xp, *[e[None, :] for e in extras])
    return out[:rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_layer_norm(x, weight, bias, eps=1e-5, block_rows=None,
                     interpret=None):
    """LayerNorm over the last axis. weight/bias may be None."""
    y, _, _ = _ln_fwd_impl(x, weight, bias, eps, block_rows, interpret)
    return y


def _ln_stats(x, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
    return mean, jax.lax.rsqrt(var + eps)


def _ln_fwd_impl(x, weight, bias, eps, block_rows, interpret):
    if interpret is None:
        interpret = not _on_tpu()
    hidden = x.shape[-1]
    x2 = x.reshape(-1, hidden)
    has_affine = weight is not None
    kernel = functools.partial(_ln_kernel, eps=eps, has_affine=has_affine)
    if has_affine:
        b = bias if bias is not None else jnp.zeros_like(weight)
        extras = [weight, b]
    else:
        def kernel(x_ref, o_ref, *, _k=functools.partial(
                _ln_kernel, eps=eps, has_affine=False)):
            _k(x_ref, None, None, o_ref)
        extras = []
    y2 = _run_rows_kernel(kernel, "layer_norm_fwd", x2, extras,
                          _auto_block_rows(x2.shape[0], x2.dtype,
                                           block_rows), interpret)
    return y2.reshape(x.shape), None, None


def _ln_fwd_rule(x, weight, bias, eps, block_rows, interpret):
    y = fused_layer_norm(x, weight, bias, eps, block_rows, interpret)
    return y, (x, weight, bias)


def _ln_bwd_jnp(x, weight, bias, g, eps):
    """Analytic LN backward in plain jnp (the no-affine / fallback
    path; the affine path runs the Pallas backward kernel below)."""
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    mean, rstd = _ln_stats(x, eps)
    xhat = (xf - mean) * rstd
    if weight is not None:
        gy = gf * weight.astype(jnp.float32)
    else:
        gy = gf
    # d/dx of layernorm (standard analytic form)
    dx = rstd * (gy - jnp.mean(gy, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
    dx = dx.astype(x.dtype)
    red = tuple(range(x.ndim - 1))
    dw = (gf * xhat).sum(axis=red).astype(weight.dtype) \
        if weight is not None else None
    db = gf.sum(axis=red).astype(bias.dtype) if bias is not None else None
    return dx, dw, db


def _ln_bwd_rule(eps, block_rows, interpret, res, g):
    x, weight, bias = res
    if weight is None:
        return _ln_bwd_jnp(x, weight, bias, g, eps)
    # Pallas backward: recompute mean/rstd/xhat in-kernel from the
    # saved input (nothing normalized was materialized by the forward),
    # one fused pass producing dx + per-block dw/db partial sums
    dx, dw, db = _ln_bwd_pallas(x, weight, bias, g, None, eps, None,
                                block_rows, interpret)
    return dx, dw, db


fused_layer_norm.defvjp(_ln_fwd_rule, _ln_bwd_rule)


# ------------------------------------------------ fused residual + LN
def _gelu_grad(u):
    """(gelu(u), d gelu/du) — tanh approximation (the one F.gelu
    approximate=True uses)."""
    k = 0.7978845608028654   # sqrt(2/pi)
    c = 0.044715
    t = jnp.tanh(k * (u + c * u * u * u))
    y = 0.5 * u * (1.0 + t)
    dy = 0.5 * (1.0 + t) + 0.5 * u * (1.0 - t * t) * k \
        * (1.0 + 3.0 * c * u * u)
    return y, dy


def _ln_res_kernel(x_ref, r_ref, w_ref, b_ref, h_ref, y_ref, *, eps, act):
    """h = x + r; y = act(LN(h) * w + b) — one VMEM pass."""
    h = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)
    mean = jnp.mean(h, axis=-1, keepdims=True)
    xc = h - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    if act == "gelu":
        y, _ = _gelu_grad(y)
    h_ref[:] = h.astype(h_ref.dtype)
    y_ref[:] = y.astype(y_ref.dtype)


def _ln_bwd_core(h, w, b, gy, gh, dx_ref, dwp_ref, dbp_ref, *, eps, act):
    mean = jnp.mean(h, axis=-1, keepdims=True)
    xc = h - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xc * rstd
    if act == "gelu":
        u = xhat * w + b
        _, du = _gelu_grad(u)
        gy = gy * du
    gw = gy * w
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = rstd * (gw - m1 - xhat * m2)
    if gh is not None:
        dx = dx + gh
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dwp_ref[:] = jnp.broadcast_to(
        jnp.sum(gy * xhat, axis=0, keepdims=True), dwp_ref.shape)
    dbp_ref[:] = jnp.broadcast_to(
        jnp.sum(gy, axis=0, keepdims=True), dbp_ref.shape)


def _ln_bwd_kernel_plain(h_ref, gy_ref, w_ref, b_ref, dx_ref, dwp_ref,
                         dbp_ref, *, eps, act):
    # operand order = _run_ln_multi's: row-blocked inputs, then vectors
    _ln_bwd_core(h_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
                 b_ref[:].astype(jnp.float32), gy_ref[:].astype(jnp.float32),
                 None, dx_ref, dwp_ref, dbp_ref, eps=eps, act=act)


def _ln_bwd_kernel_res(h_ref, gy_ref, gh_ref, w_ref, b_ref, dx_ref,
                       dwp_ref, dbp_ref, *, eps, act):
    _ln_bwd_core(h_ref[:].astype(jnp.float32), w_ref[:].astype(jnp.float32),
                 b_ref[:].astype(jnp.float32), gy_ref[:].astype(jnp.float32),
                 gh_ref[:].astype(jnp.float32), dx_ref, dwp_ref, dbp_ref,
                 eps=eps, act=act)


def _run_ln_multi(kernel, name, rows_in, vecs, rows_out_dtypes, n_partials,
                  block_rows, interpret):
    """Row-block kernel with several [rows, hidden] inputs/outputs plus
    per-block f32 partial-sum outputs, returned as (grid, hidden) and
    summed by the caller — the cross-block reduction is one tiny eqn."""
    rows, hidden = rows_in[0].shape
    xp = [_pad_rows(a, block_rows) for a in rows_in]
    prows = xp[0].shape[0]
    grid = (prows // block_rows,)
    in_specs = [_vmem_spec((block_rows, hidden), lambda i: (i, 0))
                for _ in rows_in]
    in_specs += [_vmem_spec((1, hidden), lambda i: (0, 0)) for _ in vecs]
    out_specs = [_vmem_spec((block_rows, hidden), lambda i: (i, 0))
                 for _ in rows_out_dtypes]
    out_specs += [_vmem_spec((_PARTIAL_ROWS, hidden), lambda i: (i, 0))
                  for _ in range(n_partials)]
    out_shape = [jax.ShapeDtypeStruct((prows, hidden), dt)
                 for dt in rows_out_dtypes]
    out_shape += [jax.ShapeDtypeStruct((grid[0] * _PARTIAL_ROWS, hidden),
                                       jnp.float32)
                  for _ in range(n_partials)]
    outs = pl.pallas_call(
        kernel, name=name, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(*xp, *[v[None, :] for v in vecs])
    n_rows_out = len(rows_out_dtypes)
    return ([o[:rows] for o in outs[:n_rows_out]]
            + [o[::_PARTIAL_ROWS] for o in outs[n_rows_out:]])


def _ln_bwd_pallas(h, weight, bias, gy, gh, eps, act, block_rows,
                   interpret):
    """Shared Pallas LN backward: dx (+gh when given), dw, db."""
    if interpret is None:
        interpret = not _on_tpu()
    hidden = h.shape[-1]
    h2 = h.reshape(-1, hidden)
    gy2 = gy.reshape(-1, hidden)
    b = bias if bias is not None else jnp.zeros_like(weight)
    br = _auto_block_rows(h2.shape[0], h2.dtype, block_rows)
    if gh is None:
        kernel = functools.partial(_ln_bwd_kernel_plain, eps=eps, act=act)
        name, rows_in = "layer_norm_bwd", [h2, gy2]
    else:
        kernel = functools.partial(_ln_bwd_kernel_res, eps=eps, act=act)
        name, rows_in = "ln_residual_bwd", [h2, gy2, gh.reshape(-1, hidden)]
    dx2, dwp, dbp = _run_ln_multi(kernel, name, rows_in, [weight, b],
                                  [h.dtype], 2, br, interpret)
    dw = dwp.sum(axis=0).astype(weight.dtype)
    db = dbp.sum(axis=0).astype(bias.dtype) if bias is not None else None
    return dx2.reshape(h.shape), dw, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_ln_residual(x, residual, weight, bias, eps=1e-5, act=None,
                      block_rows=None, interpret=None):
    """One-kernel ``h = x + residual; y = act(LN(h))`` returning
    ``(h, y)`` — the residual-stream update and the normalized input of
    the next sublayer in a single HBM pass.  The custom VJP saves only
    ``h`` (live on the forward path anyway) and RECOMPUTES mean/rstd in
    the backward kernel: no normalized intermediate is ever
    materialized.  ``weight`` is required (fall back to the pure-JAX
    composition for weight-free norms); ``act`` is None or ``"gelu"``
    (tanh approximation, for blocks whose norm feeds an activation
    directly)."""
    h, y = _ln_res_fwd_impl(x, residual, weight, bias, eps, act,
                            block_rows, interpret)
    return h, y


def _ln_res_fwd_impl(x, residual, weight, bias, eps, act, block_rows,
                     interpret):
    if interpret is None:
        interpret = not _on_tpu()
    hidden = x.shape[-1]
    x2 = x.reshape(-1, hidden)
    r2 = residual.reshape(-1, hidden)
    out_dtype = jnp.promote_types(x.dtype, residual.dtype)
    b = bias if bias is not None else jnp.zeros_like(weight)
    br = _auto_block_rows(x2.shape[0], jnp.dtype(out_dtype), block_rows)
    kernel = functools.partial(_ln_res_kernel, eps=eps, act=act)
    h2, y2 = _run_ln_multi(kernel, "ln_residual_fwd", [x2, r2], [weight, b],
                           [out_dtype, out_dtype], 0, br, interpret)
    return h2.reshape(x.shape), y2.reshape(x.shape)


def _ln_res_fwd_rule(x, residual, weight, bias, eps, act, block_rows,
                     interpret):
    # through the custom_vjp function, not the raw kernel: a nested
    # differentiation (recompute's backward) must meet a call it can
    # linearize, never a raw pallas_call it would have to JVP
    h, y = fused_ln_residual(x, residual, weight, bias, eps, act,
                             block_rows, interpret)
    # scalar zero sentinels carry the primal dtypes into the bwd rule
    # (residual pytree leaves must be jax values, not dtype objects)
    return (h, y), (h, weight, bias, jnp.zeros((), x.dtype),
                    jnp.zeros((), residual.dtype))


def _ln_res_bwd_rule(eps, act, block_rows, interpret, res, g):
    h, weight, bias, x_proto, r_proto = res
    gh, gy = g
    dh, dw, db = _ln_bwd_pallas(h, weight, bias, gy, gh, eps, act,
                                block_rows, interpret)
    dx = dh if dh.dtype == x_proto.dtype else dh.astype(x_proto.dtype)
    dres = dh if dh.dtype == r_proto.dtype else dh.astype(r_proto.dtype)
    return dx, dres, dw, db


fused_ln_residual.defvjp(_ln_res_fwd_rule, _ln_res_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_add_layer_norm(x, residual, weight, bias, eps=1e-5, act=None,
                         block_rows=None, interpret=None):
    """Post-LN join ``y = act(LN(x + residual))`` returning ONLY y.

    Same kernels as :func:`fused_ln_residual`, for call sites where the
    summed stream is not consumed downstream (post-norm transformer
    blocks: the normalized value IS the stream).  Returning y alone
    means backward never materializes a zeros cotangent for an unused h
    output — h is still computed once and saved as the residual the
    backward kernel recomputes stats from."""
    _h, y = _ln_res_fwd_impl(x, residual, weight, bias, eps, act,
                             block_rows, interpret)
    return y


def _add_ln_fwd_rule(x, residual, weight, bias, eps, act, block_rows,
                     interpret):
    h, y = fused_ln_residual(x, residual, weight, bias, eps, act,
                             block_rows, interpret)
    return y, (h, weight, bias, jnp.zeros((), x.dtype),
               jnp.zeros((), residual.dtype))


def _add_ln_bwd_rule(eps, act, block_rows, interpret, res, gy):
    h, weight, bias, x_proto, r_proto = res
    dh, dw, db = _ln_bwd_pallas(h, weight, bias, gy, None, eps, act,
                                block_rows, interpret)
    dx = dh if dh.dtype == x_proto.dtype else dh.astype(x_proto.dtype)
    dres = dh if dh.dtype == r_proto.dtype else dh.astype(r_proto.dtype)
    return dx, dres, dw, db


fused_add_layer_norm.defvjp(_add_ln_fwd_rule, _add_ln_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_rms_norm(x, weight, eps=1e-6, block_rows=None, interpret=None):
    """RMSNorm over the last axis. weight may be None."""
    if interpret is None:
        interpret = not _on_tpu()
    hidden = x.shape[-1]
    x2 = x.reshape(-1, hidden)
    has_affine = weight is not None
    if has_affine:
        kernel = functools.partial(_rms_kernel, eps=eps, has_affine=True)
        extras = [weight]
    else:
        def kernel(x_ref, o_ref):
            _rms_kernel(x_ref, None, o_ref, eps=eps, has_affine=False)
        extras = []
    y2 = _run_rows_kernel(kernel, "rms_norm_fwd", x2, extras,
                          block_rows or DEFAULT_BLOCK_ROWS, interpret)
    return y2.reshape(x.shape)


def _rms_fwd_rule(x, weight, eps, block_rows, interpret):
    y = fused_rms_norm(x, weight, eps, block_rows, interpret)
    return y, (x, weight)


def _rms_bwd_rule(eps, block_rows, interpret, res, g):
    x, weight = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    xhat = xf * rstd
    gy = gf * weight.astype(jnp.float32) if weight is not None else gf
    dx = rstd * (gy - xhat * jnp.mean(gy * xhat, axis=-1, keepdims=True))
    dx = dx.astype(x.dtype)
    dw = (gf * xhat).sum(axis=tuple(range(x.ndim - 1))).astype(weight.dtype) \
        if weight is not None else None
    return dx, dw


fused_rms_norm.defvjp(_rms_fwd_rule, _rms_bwd_rule)
