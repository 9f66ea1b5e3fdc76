"""The training-path kernels of ops/pallas/{norm,optim}.py, compiled by
Mosaic on a real TPU, against the pure-jnp references the modules carry —
LayerNorm backward, residual+LayerNorm forward/backward and the fused
AdamW update had only ever run in interpret mode.

Shapes are GPT-355M's (hidden 1024, several row blocks): the per-block
partial-sum outputs only exist with more than one block.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.norm import (_ln_bwd_jnp, fused_add_layer_norm,
                                        fused_layer_norm, fused_ln_residual)
from paddle_tpu.ops.pallas.optim import fused_adam_update

ROWS, HIDDEN = 1024, 1024          # 8 row blocks of 128


def _close(got, want, tol):
    got = np.asarray(got.astype(jnp.float32))
    want = np.asarray(want.astype(jnp.float32))
    scale = float(np.max(np.abs(want))) + 1e-6
    err = float(np.max(np.abs(got - want))) / scale
    assert err < tol, err


def _ln_inputs(dtype, seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(ROWS, HIDDEN), dtype)
    r = jnp.asarray(rng.randn(ROWS, HIDDEN), dtype)
    w = jnp.asarray(1.0 + 0.1 * rng.randn(HIDDEN), jnp.float32)
    b = jnp.asarray(0.1 * rng.randn(HIDDEN), jnp.float32)
    g = jnp.asarray(rng.randn(ROWS, HIDDEN), dtype)
    return x, r, w, b, g


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_layer_norm_backward_compiled(dtype):
    x, _, w, b, g = _ln_inputs(dtype, 0)

    def f(x, w, b):
        y = fused_layer_norm(x, w, b, 1e-5, None, False)
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32))

    dx, dw, db = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(x, w, b)
    rx, rw, rb = _ln_bwd_jnp(x, w, b, g, 1e-5)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    _close(dx, rx, tol)
    # dw/db reduce 1024 rows in f32 on both sides
    _close(dw, rw, 1e-3)
    _close(db, rb, 1e-3)


def _ln_res_ref(x, r, w, b, act):
    h = x + r
    hf = h.astype(jnp.float32)
    mean = jnp.mean(hf, axis=-1, keepdims=True)
    var = jnp.mean((hf - mean) ** 2, axis=-1, keepdims=True)
    y = (hf - mean) * jax.lax.rsqrt(var + 1e-5) * w + b
    if act == "gelu":
        y = jax.nn.gelu(y, approximate=True)
    return h, y.astype(h.dtype)


@pytest.mark.parametrize("act", [None, "gelu"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ln_residual_compiled(dtype, act):
    x, r, w, b, g = _ln_inputs(dtype, 1)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4

    h, y = jax.jit(lambda *a: fused_ln_residual(
        *a, 1e-5, act, None, False))(x, r, w, b)
    rh, ry = _ln_res_ref(x, r, w, b, act)
    _close(h, rh, tol)
    _close(y, ry, tol)

    def loss(fn):
        def f(x, r, w, b):
            h, y = fn(x, r, w, b)
            gf = g.astype(jnp.float32)
            return jnp.sum(y.astype(jnp.float32) * gf) + jnp.sum(
                h.astype(jnp.float32) * gf)
        return jax.jit(jax.grad(f, argnums=(0, 1, 2, 3)))

    got = loss(lambda *a: fused_ln_residual(
        *a, 1e-5, act, None, False))(x, r, w, b)
    want = loss(lambda *a: _ln_res_ref(*a, act))(x, r, w, b)
    for gv, wv, t in zip(got, want, (tol, tol, 1e-2, 1e-2)):
        _close(gv, wv, t)


def test_add_layer_norm_compiled():
    x, r, w, b, g = _ln_inputs(jnp.bfloat16, 2)

    def loss(fn):
        def f(x, r, w, b):
            return jnp.sum(fn(x, r, w, b).astype(jnp.float32)
                           * g.astype(jnp.float32))
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))

    v, got = loss(lambda *a: fused_add_layer_norm(
        *a, 1e-5, None, None, False))(x, r, w, b)
    rv, want = loss(lambda *a: _ln_res_ref(*a, None)[1])(x, r, w, b)
    assert abs(float(v) - float(rv)) < 2e-2 * abs(float(rv)) + 1.0
    for gv, wv, t in zip(got, want, (3e-2, 3e-2, 1e-2, 1e-2)):
        _close(gv, wv, t)


# ------------------------------------------------------------ fused AdamW
HYPER = dict(beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01)


def _adam_ref(p, g, m, v, lr, c1, c2):
    """The unfused AdamW._update_param math, op for op."""
    pf = p.astype(jnp.float32) * (1.0 - lr * HYPER["weight_decay"])
    gf = g.astype(jnp.float32)
    nm = HYPER["beta1"] * m.astype(jnp.float32) + (1 - HYPER["beta1"]) * gf
    nv = HYPER["beta2"] * v.astype(jnp.float32) + (
        1 - HYPER["beta2"]) * gf * gf
    upd = lr * (nm / c1) / (jnp.sqrt(nv / c2) + HYPER["eps"])
    return ((pf - upd).astype(p.dtype), nm.astype(m.dtype),
            nv.astype(v.dtype))


def _adam_inputs(shape, moment_dtype, seed):
    rng = np.random.RandomState(seed)
    p = jnp.asarray(rng.randn(*shape) * 0.02, jnp.float32)
    g = jnp.asarray(rng.randn(*shape) * 1e-2, jnp.bfloat16)
    m = jnp.asarray(rng.randn(*shape) * 1e-3, moment_dtype)
    v = jnp.asarray(np.abs(rng.randn(*shape)) * 1e-5, moment_dtype)
    return p, g, m, v


@pytest.mark.parametrize("shape", [(1024, 4096), (50304, 1024), (1000, 256)])
@pytest.mark.parametrize("moment_dtype", [jnp.float32, jnp.bfloat16])
def test_fused_adamw_compiled(shape, moment_dtype):
    p, g, m, v = _adam_inputs(shape, moment_dtype, 3)
    lr, c1, c2 = 1e-3, 0.1, 0.001
    want = jax.jit(_adam_ref)(p, g, m, v, lr, c1, c2)
    got = jax.jit(lambda *a: fused_adam_update(
        *a, lr, c1, c2, interpret=False, **HYPER))(p, g, m, v)
    mtol = 1e-2 if moment_dtype == jnp.bfloat16 else 1e-5
    for gv, wv, t in zip(got, want, (1e-5, mtol, mtol)):
        assert gv.dtype == wv.dtype and gv.shape == wv.shape
        _close(gv, wv, t)


def test_fused_adamw_guard_compiled():
    """guard=True: per-block gradient sum-of-squares come back, and a
    block with a non-finite gradient commits nothing."""
    shape = (1024, 4096)
    p, g, m, v = _adam_inputs(shape, jnp.bfloat16, 4)
    g = g.at[40, 7].set(jnp.nan)
    lr, c1, c2 = 1e-3, 0.1, 0.001
    np_, nm, nv, parts = jax.jit(lambda *a: fused_adam_update(
        *a, lr, c1, c2, interpret=False, guard=True, **HYPER))(p, g, m, v)
    blocks = parts.shape[0]
    rows = shape[0] // blocks
    assert blocks > 1 and parts.shape == (blocks, 128)
    want = jnp.sum(jnp.square(g.astype(jnp.float32)).reshape(
        blocks, rows * shape[1]), axis=1)
    got = parts[:, 0]
    bad = 40 // rows
    assert not bool(jnp.isfinite(got[bad]))
    good = np.asarray(jnp.isfinite(want))
    assert good.sum() == blocks - 1
    np.testing.assert_allclose(np.asarray(got)[good],
                               np.asarray(want)[good], rtol=1e-3)
    sl = slice(bad * rows, (bad + 1) * rows)
    assert bool(jnp.array_equal(np_[sl], p[sl]))
    assert bool(jnp.array_equal(nm[sl], m[sl]))
    assert bool(jnp.array_equal(nv[sl], v[sl]))
    rp, rm, rv = jax.jit(_adam_ref)(p, g, m, v, lr, c1, c2)
    keep = np.ones(shape[0], bool)
    keep[sl] = False
    _close(np_[keep], rp[keep], 1e-5)
    _close(nm[keep], rm[keep], 1e-2)
    assert bool(jnp.all(jnp.isfinite(np_)))


def test_fused_adamw_optimizer_step_on_tpu():
    """optimizer.AdamW(fused=True) inside one to_static step — donated
    state feeding the kernel's input_output_aliases — tracks the
    unfused optimizer."""
    import paddle_tpu as P

    def train(fused):
        P.seed(0)
        lin = P.nn.Linear(256, 512)
        opt = P.optimizer.AdamW(learning_rate=1e-2, weight_decay=0.01,
                                parameters=lin.parameters(), fused=fused)

        @P.jit.to_static
        def step(x):
            opt.clear_grad()
            loss = (lin(x) ** 2).mean()
            loss.backward()
            opt.step()
            return loss

        x = P.to_tensor(np.random.RandomState(0)
                        .randn(64, 256).astype(np.float32))
        losses = [float(step(x).numpy()) for _ in range(4)]
        return losses, np.asarray(lin.weight._value)

    l_f, w_f = train(True)
    l_u, w_u = train(False)
    np.testing.assert_allclose(l_f, l_u, rtol=1e-3)
    np.testing.assert_allclose(w_f, w_u, atol=1e-4)
