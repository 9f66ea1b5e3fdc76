"""Flash-attention + fused-norm kernels, compiled by Mosaic on real TPU.

Tolerances are TPU-native: fp32 matmuls at default precision run bf16
passes on the MXU (~1e-3 relative), so oracles compare at bf16-scale
tolerance even for fp32 inputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.flash_attention import _flash_bhsd
from paddle_tpu.ops.pallas.norm import fused_layer_norm, fused_rms_norm


def ref_attn(q, k, v, causal, scale):
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if causal:
            mask = jnp.tril(jnp.ones((s.shape[-2], s.shape[-1]), bool))
            s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v.astype(jnp.float32)).astype(q.dtype)


CASES = [
    ((2, 3, 192, 512, 64), jnp.float32, False),
    ((2, 3, 192, 512, 64), jnp.float32, True),
    ((1, 2, 512, 512, 128), jnp.bfloat16, True),
    ((1, 2, 200, 333, 64), jnp.float32, False),   # ragged, needs edge mask
    ((1, 1, 64, 64, 64), jnp.float32, True),      # single-block path
]


@pytest.mark.parametrize("shape,dtype,causal", CASES)
def test_flash_forward_compiled(shape, dtype, causal):
    b, h, sq, sk, d = shape
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, sq, d), dtype)
    k = jnp.asarray(rng.randn(b, h, sk, d), dtype)
    v = jnp.asarray(rng.randn(b, h, sk, d), dtype)
    scale = 1.0 / np.sqrt(d)
    o = _flash_bhsd(q, k, v, causal, scale, 1024, 1024, False)
    o_ref = ref_attn(q, k, v, causal, scale)
    denom = float(jnp.max(jnp.abs(o_ref.astype(jnp.float32)))) + 1e-6
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - o_ref.astype(jnp.float32)))) / denom
    assert err < (2e-2 if dtype == jnp.bfloat16 else 6e-3), err


@pytest.mark.parametrize("shape,dtype,causal", CASES)
def test_flash_grads_compiled(shape, dtype, causal):
    b, h, sq, sk, d = shape
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, sq, d), dtype)
    k = jnp.asarray(rng.randn(b, h, sk, d), dtype)
    v = jnp.asarray(rng.randn(b, h, sk, d), dtype)
    scale = 1.0 / np.sqrt(d)
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def f(q, k, v):
        return jnp.sum(
            _flash_bhsd(q, k, v, causal, scale, 1024, 1024,
                        False).astype(jnp.float32) * w)

    def g(q, k, v):
        return jnp.sum(ref_attn(q, k, v, causal, scale).astype(
            jnp.float32) * w)

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        denom = float(jnp.max(jnp.abs(b_.astype(jnp.float32)))) + 1e-6
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b_.astype(jnp.float32)))) / denom
        assert err < (5e-2 if dtype == jnp.bfloat16 else 2e-2), err


# the train cells' attention shapes and one shape for each other schedule
# `_pick_blocks` can return, no block named
CELL_CASES = [
    ((4, 16, 2048, 2048, 64), jnp.bfloat16, True),     # gpt355m_train
    ((48, 12, 512, 512, 64), jnp.bfloat16, False),     # bert_base_train
    ((2, 4, 1024, 1024, 64), jnp.bfloat16, True),      # the lone 1024 tile
    ((2, 2, 1000, 1000, 64), jnp.bfloat16, False),     # ... ragged
    ((2, 2, 1300, 1300, 64), jnp.bfloat16, True),      # a walk that ends
    #                                                    inside a tile
    ((1, 4, 4096, 4096, 64), jnp.bfloat16, True),      # the longest walk
    ((2, 2, 512, 2048, 64), jnp.bfloat16, False),      # seq_q != seq_k
    ((1, 2, 8192, 8192, 64), jnp.bfloat16, True),      # streamed key-major
    ((2, 2, 2048, 2048, 128), jnp.bfloat16, True),     # query-major forward
    ((2, 2, 2048, 2048, 64), jnp.float32, True),       # f32: streamed
]


@pytest.mark.parametrize("shape,dtype,causal", CELL_CASES)
def test_flash_cell_shapes_compiled(shape, dtype, causal):
    """Forward and all three gradients, no block named: `_pick_blocks`
    chooses from the shape."""
    b, h, sq, sk, d = shape
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(b, h, sq, d), dtype)
    k = jnp.asarray(rng.randn(b, h, sk, d), dtype)
    v = jnp.asarray(rng.randn(b, h, sk, d), dtype)
    scale = 1.0 / np.sqrt(d)
    w = jnp.cos(jnp.arange(d, dtype=jnp.float32))

    def f(q, k, v):
        o = _flash_bhsd(q, k, v, causal, scale, None, None, False)
        return jnp.sum(o.astype(jnp.float32) * w), o

    def g(q, k, v):
        o = ref_attn(q, k, v, causal, scale)
        return jnp.sum(o.astype(jnp.float32) * w), o

    got_g, got_o = jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(
        q, k, v)
    want_g, want_o = jax.jit(jax.grad(g, argnums=(0, 1, 2), has_aux=True))(
        q, k, v)
    for a, b_, tol in [(got_o, want_o, 2e-2)] + [
            (a, b_, 5e-2) for a, b_ in zip(got_g, want_g)]:
        denom = float(jnp.max(jnp.abs(b_.astype(jnp.float32)))) + 1e-6
        err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                    - b_.astype(jnp.float32)))) / denom
        assert err < tol, err


def test_flash_long_sequence_16k():
    """16k-token causal attention: K/V must stream through VMEM (the r2
    kernel pinned the whole K/V per (batch,head) and could not even hold
    4k tokens); output and grads must be finite."""
    b, h, s, d = 1, 4, 16384, 128
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: _flash_bhsd(
        q, k, v, True, float(d) ** -0.5, 1024, 1024, False))
    o = f(q, k, v)
    assert o.shape == (b, h, s, d)
    assert bool(jnp.all(jnp.isfinite(o.astype(jnp.float32))))
    # spot-check rows against the oracle on a slice (full 16k² oracle
    # would materialize 4*16384² bytes per head — slice keeps it cheap)
    o_head = ref_attn(q[:, :1, :256], k[:, :1, :256], v[:, :1, :256],
                      True, float(d) ** -0.5)
    err = float(jnp.max(jnp.abs(
        o[:, :1, :256].astype(jnp.float32) - o_head.astype(jnp.float32))))
    assert err < 3e-2, err

    grads = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(_flash_bhsd(
            q, k, v, True, float(d) ** -0.5, 1024, 1024,
            False).astype(jnp.float32)), argnums=(0, 1, 2)))(q, k, v)
    for gx in grads:
        assert bool(jnp.all(jnp.isfinite(gx.astype(jnp.float32))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_layer_norm_compiled(dtype):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(64, 384), dtype)
    w = jnp.asarray(rng.randn(384), dtype)
    b = jnp.asarray(rng.randn(384), dtype)
    y = fused_layer_norm(x, w, b, 1e-5, None, False)
    xf = x.astype(jnp.float32)
    mean = xf.mean(-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(-1, keepdims=True)
    want = (xf - mean) * jax.lax.rsqrt(var + 1e-5) * w.astype(
        jnp.float32) + b.astype(jnp.float32)
    # bf16 tol is one output ulp at max |want| (~4 * 2^-8 here)
    tol = 4e-2 if dtype == jnp.bfloat16 else 1e-4
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - want))) < tol


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_rms_norm_compiled(dtype):
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(64, 256), dtype)
    w = jnp.asarray(rng.randn(256), dtype)
    y = fused_rms_norm(x, w, 1e-6, None, False)
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(-1, keepdims=True)
    want = xf * jax.lax.rsqrt(ms + 1e-6) * w.astype(jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - want))) < tol
