"""Cross-platform TPU lowering of every Pallas kernel — no chip needed.

`jax.export(..., platforms=["tpu"])` runs the pallas -> Mosaic-dialect
serialization on a CPU-only host: it catches the malformed-grid /
BlockSpec / layout class of errors at the dialect level (the full
Mosaic -> TPU binary compile still needs silicon — tests_tpu/ covers
that), so a kernel that cannot even lower fails HERE, in the gate,
rather than in the first on-silicon run.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import export


def _lower_tpu(fn, *args):
    exp = export.export(jax.jit(fn), platforms=["tpu"])(*args)
    txt = exp.mlir_module()
    assert "tpu_custom_call" in txt, "no Mosaic kernel in the lowering"
    return txt


def _sd(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_lowers(self, causal):
        from paddle_tpu.ops.pallas.flash_attention import _flash_bhsd

        b, h, s, d = 1, 2, 2048, 128
        _lower_tpu(
            lambda q, k, v: _flash_bhsd(q, k, v, causal, d ** -0.5,
                                        1024, 1024, False),
            _sd((b, h, s, d)), _sd((b, h, s, d)), _sd((b, h, s, d)))

    def test_bwd_lowers(self):
        from paddle_tpu.ops.pallas.flash_attention import _flash_bhsd

        b, h, s, d = 1, 1, 1024, 64

        def f(q, k, v):
            return jnp.sum(_flash_bhsd(q, k, v, True, d ** -0.5, 512,
                                       512, False).astype(jnp.float32))

        _lower_tpu(jax.grad(f, argnums=(0, 1, 2)),
                   _sd((b, h, s, d)), _sd((b, h, s, d)),
                   _sd((b, h, s, d)))

    def test_16k_lowers(self):
        from paddle_tpu.ops.pallas.flash_attention import _flash_bhsd

        b, h, s, d = 1, 1, 16384, 128
        _lower_tpu(
            lambda q, k, v: _flash_bhsd(q, k, v, True, d ** -0.5,
                                        1024, 1024, False),
            _sd((b, h, s, d)), _sd((b, h, s, d)), _sd((b, h, s, d)))

    def test_gpt355m_fwd_bwd_lowers(self):
        """The shape GPT-355M trains at (batch 4, 16 heads of 64, seq
        2048), through the public entry with the default blocks."""
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bhsd)

        shape = (4, 16, 2048, 64)

        def f(q, k, v):
            return jnp.sum(flash_attention_bhsd(
                q, k, v, causal=True, interpret=False).astype(jnp.float32))

        txt = _lower_tpu(jax.grad(f, argnums=(0, 1, 2)),
                         _sd(shape), _sd(shape), _sd(shape))
        for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
            assert kernel in txt, f"{kernel} missing from the lowering"


class TestNorms:
    def test_layer_norm_lowers(self):
        from paddle_tpu.ops.pallas.norm import fused_layer_norm

        _lower_tpu(lambda x, w, b: fused_layer_norm(x, w, b, 1e-5, None,
                                                    False),
                   _sd((256, 1024), jnp.float32),
                   _sd((1024,), jnp.float32), _sd((1024,), jnp.float32))

    def test_rms_norm_lowers(self):
        from paddle_tpu.ops.pallas.norm import fused_rms_norm

        _lower_tpu(lambda x, w: fused_rms_norm(x, w, 1e-6, None, False),
                   _sd((256, 1024), jnp.float32),
                   _sd((1024,), jnp.float32))

    # the backward kernels write per-block partial sums: a multi-block
    # shape (8192 rows = 64 blocks) is what exposes an illegal block
    _ROWS, _HIDDEN = 8192, 1024

    def test_layer_norm_bwd_lowers(self):
        from paddle_tpu.ops.pallas.norm import fused_layer_norm

        def f(x, w, b):
            return jnp.sum(fused_layer_norm(x, w, b, 1e-5, None, False)
                           .astype(jnp.float32))

        txt = _lower_tpu(jax.grad(f, argnums=(0, 1, 2)),
                         _sd((self._ROWS, self._HIDDEN)),
                         _sd((self._HIDDEN,), jnp.float32),
                         _sd((self._HIDDEN,), jnp.float32))
        assert "layer_norm_bwd" in txt

    @pytest.mark.parametrize("act", [None, "gelu"])
    def test_ln_residual_bwd_lowers(self, act):
        from paddle_tpu.ops.pallas.norm import fused_ln_residual

        def f(x, r, w, b):
            h, y = fused_ln_residual(x, r, w, b, 1e-5, act, None, False)
            return jnp.sum(h.astype(jnp.float32)) + jnp.sum(
                y.astype(jnp.float32))

        txt = _lower_tpu(jax.grad(f, argnums=(0, 1, 2, 3)),
                         _sd((self._ROWS, self._HIDDEN)),
                         _sd((self._ROWS, self._HIDDEN)),
                         _sd((self._HIDDEN,), jnp.float32),
                         _sd((self._HIDDEN,), jnp.float32))
        assert "ln_residual_fwd" in txt and "ln_residual_bwd" in txt


class TestFusedAdam:
    @pytest.mark.parametrize("guard", [False, True])
    def test_adam_update_lowers(self, guard):
        from paddle_tpu.ops.pallas.optim import fused_adam_update

        def f(p, g, m, v, lr):
            return fused_adam_update(
                p, g, m, v, lr, 0.1, 0.001, beta1=0.9, beta2=0.999,
                eps=1e-8, weight_decay=0.01, guard=guard, interpret=False)

        shape = (1024, 4096)          # a multi-block GPT-355M fc1 weight
        txt = _lower_tpu(f, _sd(shape, jnp.float32), _sd(shape),
                         _sd(shape), _sd(shape), _sd((), jnp.float32))
        assert "adamw_fused" in txt


class TestRingBlocks:
    def test_ring_block_lowers(self):
        from paddle_tpu.ops.pallas.ring_attention import _flash_block

        b, h, s, d = 1, 2, 512, 64

        def f(q, k, v):
            o, lse = _flash_block(q, k, v, True, d ** -0.5, 512, 512,
                                  False)
            return o

        _lower_tpu(f, _sd((b, h, s, d)), _sd((b, h, s, d)),
                   _sd((b, h, s, d)))


class TestBlockSparse:
    def test_fwd_lowers(self):
        from paddle_tpu.ops.pallas.block_sparse_attention import (
            block_sparse_attention, make_sliding_window_mask)

        b, h, s, d = 1, 2, 1024, 64
        bq = bk = 256
        bm = make_sliding_window_mask(s // bq, s // bq, 2, causal=True)
        _lower_tpu(
            lambda q, k, v: block_sparse_attention(
                q, k, v, bm, block_q=bq, block_k=bk, interpret=False),
            _sd((b, h, s, d)), _sd((b, h, s, d)), _sd((b, h, s, d)))

    def test_ragged_tail_lowers(self):
        from paddle_tpu.ops.pallas.block_sparse_attention import (
            block_sparse_attention)

        b, h, s, d = 1, 1, 300, 64
        bm = np.ones((2, 2), bool)
        _lower_tpu(
            lambda q, k, v: block_sparse_attention(
                q, k, v, bm, block_q=256, block_k=256, interpret=False),
            _sd((b, h, s, d), jnp.float32), _sd((b, h, s, d), jnp.float32),
            _sd((b, h, s, d), jnp.float32))
