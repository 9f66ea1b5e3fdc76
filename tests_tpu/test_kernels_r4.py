"""Round-4 compiled-mode coverage (VERDICT #4): the kernels that had
never been compiled on silicon — Pallas ring attention blocks, the int8
quantized-linear MXU dot, and the fused incubate ops.

These run the REAL Mosaic compiler / MXU int8 path; interpret-mode
passes do not count (the r2 lesson). Without a TPU the suite errors
(conftest).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import _flash_bhsd
from paddle_tpu.ops.pallas.ring_attention import (_flash_block, _merge,
                                                 ring_flash_attention)


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


def _rel_err(a, b):
    d = float(jnp.max(jnp.abs(b.astype(jnp.float32)))) + 1e-6
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32)))) / d


# --------------------------------------------------- ring attention blocks
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ring_block_and_merge_compiled(dtype):
    """The ring's per-chunk flash block + online-softmax merge, Mosaic-
    compiled: two half-sequence blocks merged must equal full attention."""
    b, h, s, d = 1, 2, 256, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, s, d), dtype)
    k = jnp.asarray(rng.randn(b, h, 2 * s, d), dtype)
    v = jnp.asarray(rng.randn(b, h, 2 * s, d), dtype)
    scale = float(d) ** -0.5

    o1, lse1 = _flash_block(q, k[:, :, :s], v[:, :, :s], False, scale,
                            1024, 1024, False)
    o2, lse2 = _flash_block(q, k[:, :, s:], v[:, :, s:], False, scale,
                            1024, 1024, False)
    o, _ = _merge(o1, lse1, o2, lse2)
    want = ref_attn(q, k, v, False, scale)
    assert _rel_err(o, want) < (3e-2 if dtype == jnp.bfloat16 else 6e-3)


def test_ring_block_grads_compiled():
    b, h, s, d = 1, 2, 256, 64
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    scale = float(d) ** -0.5

    def f(q, k, v):
        o, _ = _flash_block(q, k, v, True, scale, 1024, 1024, False)
        return jnp.sum(o.astype(jnp.float32))

    def g(q, k, v):
        return jnp.sum(ref_attn(q, k, v, True, scale).astype(jnp.float32))

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(got, want):
        assert _rel_err(a, b_) < 2e-2


def test_ring_attention_shard_map_single_chip():
    """The exact compile environment the flagship uses: shard_map over an
    sp mesh (size 1 on a single chip) with the Pallas blocks inside —
    must Mosaic-compile and match full attention."""
    from jax.sharding import Mesh, PartitionSpec as P

    b, h, s, d = 1, 2, 512, 64
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ring_flash_attention(
            q, k, v, axis_name="sp", causal=True, axis_size=1,
            interpret=False),
        mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False))
    o = fn(q, k, v)
    want = ref_attn(q, k, v, True, float(d) ** -0.5)
    assert _rel_err(o, want) < 4e-2


# --------------------------------------------------------- int8 MXU dot
def test_quantized_linear_int8_dot_compiled():
    """The converted linear's int8 x int8 -> int32 dot must run compiled
    (the MXU executes int8 at 2x bf16 rate) and match the fp oracle to
    quantization tolerance."""
    import paddle_tpu as p
    from paddle_tpu.quantization import QuantizedLinear

    rng = np.random.RandomState(3)
    lin = p.nn.Linear(256, 512)
    w = rng.randn(256, 512).astype(np.float32) * 0.1
    lin.weight._set_value(jnp.asarray(w))
    lin.bias._set_value(jnp.asarray(np.zeros(512, np.float32)))
    w_scales = np.abs(w).max(axis=0) / 127.0
    act_scale = 3.0 / 127.0
    qlin = QuantizedLinear(lin, w_scales, act_scale)

    x = np.clip(rng.randn(64, 256), -3, 3).astype(np.float32)
    got = qlin(p.to_tensor(x)).numpy()
    want = x @ w
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 0.05, rel

    # the compiled HLO must contain a non-fp dot (s32/s8 operands)
    def raw(v):
        q = jnp.clip(jnp.round(v / act_scale), -127, 127).astype(jnp.int8)
        return jax.lax.dot_general(
            q, qlin.w_int8._value, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    txt = jax.jit(raw).lower(jnp.asarray(x)).compile().as_text()
    assert "s32" in txt and ("s8" in txt or "convert" in txt)


def test_int8_dot_throughput_sanity():
    """int8 MXU dot should not be SLOWER than the bf16 dot at the same
    shape (it is rated 2x; allow generous slack for small shapes)."""
    import time

    m = k_ = n = 2048
    rng = np.random.RandomState(4)
    a8 = jnp.asarray(rng.randint(-127, 127, (m, k_)), jnp.int8)
    b8 = jnp.asarray(rng.randint(-127, 127, (k_, n)), jnp.int8)
    abf = jnp.asarray(rng.randn(m, k_), jnp.bfloat16)
    bbf = jnp.asarray(rng.randn(k_, n), jnp.bfloat16)

    f8 = jax.jit(lambda a, b: jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32))
    fbf = jax.jit(lambda a, b: a @ b)

    f8(a8, b8).block_until_ready()
    fbf(abf, bbf).block_until_ready()

    def bench(f, a, b, iters=50):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = f(a, b)
        out.block_until_ready()
        return (time.perf_counter() - t0) / iters

    t8, tbf = bench(f8, a8, b8), bench(fbf, abf, bbf)
    assert t8 < tbf * 1.5, (t8, tbf)


# ------------------------------------------------------ fused incubate ops
def test_fused_feedforward_compiled():
    import paddle_tpu as p
    import paddle_tpu.incubate.nn.functional as IF

    rng = np.random.RandomState(5)
    x = rng.randn(8, 32, 128).astype(np.float32)
    w1 = rng.randn(128, 512).astype(np.float32) * 0.05
    w2 = rng.randn(512, 128).astype(np.float32) * 0.05
    g = np.ones(128, np.float32)
    b = np.zeros(128, np.float32)
    out = IF.fused_feedforward(
        p.to_tensor(x), p.to_tensor(w1), p.to_tensor(w2),
        ln1_scale=p.to_tensor(g), ln1_bias=p.to_tensor(b),
        dropout1_rate=0.0, dropout2_rate=0.0, activation="gelu",
        pre_layer_norm=True, training=False)
    xf = x.astype(np.float32)
    mean = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    h = (xf - mean) / np.sqrt(var + 1e-5)
    a = h @ w1
    # tanh-approx gelu (the fused kernels' convention)
    a = 0.5 * a * (1 + np.tanh(0.79788456 * a * (1 + 0.044715 * a * a)))
    want = xf + a @ w2
    rel = np.abs(out.numpy() - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 5e-3, rel


def test_fused_mha_flash_path_compiled():
    """No mask + no attention dropout routes through the Pallas flash
    kernel — must compile and match the dense oracle."""
    import paddle_tpu as p
    import paddle_tpu.incubate.nn.functional as IF

    rng = np.random.RandomState(6)
    b, s, e, n = 2, 128, 128, 4
    hd = e // n
    x = rng.randn(b, s, e).astype(np.float32) * 0.3
    qkvw = rng.randn(3, n, hd, e).astype(np.float32) * 0.05
    lw = rng.randn(e, e).astype(np.float32) * 0.05
    out = IF.fused_multi_head_attention(
        p.to_tensor(x), p.to_tensor(qkvw), p.to_tensor(lw),
        pre_layer_norm=True,
        pre_ln_scale=p.to_tensor(np.ones(e, np.float32)),
        pre_ln_bias=p.to_tensor(np.zeros(e, np.float32)),
        dropout_rate=0.0, attn_dropout_rate=0.0, training=False)
    assert out.shape == [b, s, e]
    assert np.isfinite(out.numpy()).all()


def test_fused_multi_transformer_compiled():
    import paddle_tpu as p
    import paddle_tpu.incubate.nn.functional as IF

    rng = np.random.RandomState(7)
    b, s, e, n, hd, L, f = 2, 64, 128, 4, 32, 2, 256
    x = rng.randn(b, s, e).astype(np.float32) * 0.3

    def mk(shape):
        return rng.randn(*shape).astype(np.float32) * 0.05

    out = IF.fused_multi_transformer(
        p.to_tensor(x),
        [np.ones(e, np.float32)] * L, [np.zeros(e, np.float32)] * L,
        [mk((3, n, hd, e)) for _ in range(L)],
        [mk((3, n, hd)) for _ in range(L)],
        [mk((n * hd, e)) for _ in range(L)], [mk((e,)) for _ in range(L)],
        [np.ones(e, np.float32)] * L, [np.zeros(e, np.float32)] * L,
        [mk((e, f)) for _ in range(L)], [mk((f,)) for _ in range(L)],
        [mk((f, e)) for _ in range(L)], [mk((e,)) for _ in range(L)])
    assert out.shape == [b, s, e]
    assert np.isfinite(out.numpy()).all()


def test_fused_bias_dropout_residual_ln_compiled():
    import paddle_tpu as p
    import paddle_tpu.incubate.nn.functional as IF

    rng = np.random.RandomState(8)
    x = rng.randn(16, 256).astype(np.float32)
    r = rng.randn(16, 256).astype(np.float32)
    out = IF.fused_bias_dropout_residual_layer_norm(
        p.to_tensor(x), p.to_tensor(r),
        ln_scale=p.to_tensor(np.ones(256, np.float32)),
        ln_bias=p.to_tensor(np.zeros(256, np.float32)),
        dropout_rate=0.0, training=False)
    h = x + r
    want = (h - h.mean(-1, keepdims=True)) / \
        np.sqrt(h.var(-1, keepdims=True) + 1e-5)
    assert np.abs(out.numpy() - want).max() < 1e-3


# --------------------------------------------- block-sparse attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_sparse_attention_compiled(dtype):
    """Splash-style table-driven kernel through Mosaic: scalar-prefetch
    index maps must lower and the active-block walk must match the
    dense-masked oracle."""
    from paddle_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention, make_sliding_window_mask)

    b, h, s, d = 1, 2, 1024, 64
    bq = bk = 256
    rng = np.random.RandomState(10)
    q = jnp.asarray(rng.randn(b, h, s, d), dtype)
    k = jnp.asarray(rng.randn(b, h, s, d), dtype)
    v = jnp.asarray(rng.randn(b, h, s, d), dtype)
    nq = s // bq
    bm = make_sliding_window_mask(nq, nq, 2, causal=True)
    out = block_sparse_attention(q, k, v, bm, block_q=bq, block_k=bk,
                                 interpret=False)
    big = jnp.asarray(np.kron(bm, np.ones((bq, bk))).astype(bool))
    sc = jnp.einsum("bhid,bhjd->bhij", q.astype(jnp.float32),
                    k.astype(jnp.float32)) / np.sqrt(d)
    sc = jnp.where(big, sc, -1e30)
    ref = jnp.einsum("bhij,bhjd->bhid", jax.nn.softmax(sc, -1),
                     v.astype(jnp.float32))
    assert _rel_err(out, ref) < (3e-2 if dtype == jnp.bfloat16 else 6e-3)


def test_block_sparse_attention_grads_compiled():
    from paddle_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention, make_sliding_window_mask)

    b, h, s, d = 1, 1, 512, 64
    bq = bk = 128
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    bm = make_sliding_window_mask(s // bq, s // bq, 2, causal=True)
    big = jnp.asarray(np.kron(bm, np.ones((bq, bk))).astype(bool))

    def f(q, k, v):
        return jnp.sum(block_sparse_attention(
            q, k, v, bm, block_q=bq, block_k=bk,
            interpret=False).astype(jnp.float32))

    def g(q, k, v):
        sc = jnp.einsum("bhid,bhjd->bhij", q, k) / np.sqrt(d)
        sc = jnp.where(big, sc, -1e30)
        return jnp.sum(jnp.einsum("bhij,bhjd->bhid",
                                  jax.nn.softmax(sc, -1), v))

    got = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        assert _rel_err(a, w) < 2e-2
