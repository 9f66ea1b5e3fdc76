"""Pallas kernels vs XLA references (interpret mode on the CPU test mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.transformer import _sdpa_ref
from paddle_tpu.ops.pallas.flash_attention import flash_attention_bshd
from paddle_tpu.ops.pallas.norm import fused_layer_norm, fused_rms_norm


def _qkv(b, s, h, d, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    return mk(), mk(), mk()


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv(2, 256, 4, 64)
        out = flash_attention_bshd(q, k, v, causal=causal, interpret=True)
        ref = _sdpa_ref(q, k, v, None, 0.0, causal, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_unaligned_seq_and_head_dim(self):
        q, k, v = _qkv(1, 200, 2, 80)
        out = flash_attention_bshd(q, k, v, causal=True, interpret=True)
        ref = _sdpa_ref(q, k, v, None, 0.0, True, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match(self, causal):
        q, k, v = _qkv(1, 128, 2, 64)

        def f(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        ours = jax.grad(f(lambda q, k, v: flash_attention_bshd(
            q, k, v, causal=causal, interpret=True)), argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(f(lambda q, k, v: _sdpa_ref(
            q, k, v, None, 0.0, causal, None)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(ours, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_bf16(self):
        q, k, v = [t.astype(jnp.bfloat16) for t in _qkv(1, 128, 2, 64)]
        out = flash_attention_bshd(q, k, v, causal=True, interpret=True)
        ref = _sdpa_ref(q, k, v, None, 0.0, True, None)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=0.05, atol=0.05)


class TestFusedNorms:
    def test_layer_norm(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((37, 256)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(256), jnp.float32)
        b = jnp.asarray(rng.standard_normal(256), jnp.float32)

        def ref(x, w, b):
            mu = x.mean(-1, keepdims=True)
            return (x - mu) / jnp.sqrt(x.var(-1, keepdims=True) + 1e-5) * w + b

        y = fused_layer_norm(x, w, b, 1e-5, None, True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w, b)),
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda *a: (fused_layer_norm(*a, 1e-5, None, True) ** 2
                                 ).sum(), argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(lambda *a: (ref(*a) ** 2).sum(),
                      argnums=(0, 1, 2))(x, w, b)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)

    def test_rms_norm(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((16, 128)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(128), jnp.float32)

        def ref(x, w):
            return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w

        y = fused_rms_norm(x, w, 1e-6, None, True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref(x, w)),
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda *a: (fused_rms_norm(*a, 1e-6, None, True) ** 2
                                 ).sum(), argnums=(0, 1))(x, w)
        gr = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1))(x, w)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-4)


class TestKernelsUnderRecompute:
    """recompute()'s backward takes a vjp of a region whose ops each took
    their own vjp on the tape.  That outer differentiation must meet the
    kernels' custom_vjp calls, never a raw pallas_call (which has no usable
    JVP): the first GPT train step on a chip died exactly there, and the
    CPU gate never saw it because attention runs the XLA path off-TPU."""

    def test_recompute_grads_match_plain(self):
        import paddle_tpu as P
        import paddle_tpu.nn.functional as F
        from paddle_tpu.core.dispatch import apply
        from paddle_tpu.distributed.recompute import recompute

        class Block(P.nn.Layer):
            def __init__(self):
                super().__init__()
                self.ln1 = P.nn.LayerNorm(32)
                self.ln2 = P.nn.LayerNorm(32)
                self.qkv = P.nn.Linear(32, 96)

            def forward(self, x):
                b, s, h = x.shape
                n = F.layer_norm(x, 32, self.ln1.weight, self.ln1.bias,
                                 fused=True)
                q, k, v = (t.reshape([b, s, 2, 16])
                           for t in self.qkv(n).split(3, axis=-1))
                a = apply(lambda q, k, v: flash_attention_bshd(
                    q, k, v, causal=True, interpret=True), q, k, v)
                x, y = F.fused_ln_residual(
                    a.reshape([b, s, h]), x, self.ln2.weight,
                    self.ln2.bias, fused=True)
                return x + y

        def grads(use_recompute):
            P.seed(0)
            blk = Block()
            x = P.to_tensor(np.random.default_rng(0).standard_normal(
                (2, 16, 32)).astype(np.float32))
            x.stop_gradient = False
            out = recompute(blk, x) if use_recompute else blk(x)
            (out ** 2).mean().backward()
            return [x.grad.numpy()] + [p.grad.numpy()
                                       for p in blk.parameters()]

        for got, want in zip(grads(True), grads(False)):
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
