"""Cross-platform TPU lowering of every Pallas kernel — no chip needed.

`jax.export(..., platforms=["tpu"])` runs the pallas -> Mosaic-dialect
serialization on a CPU-only host: it catches the malformed-grid /
BlockSpec / layout class of errors at the dialect level (the full
Mosaic -> TPU binary compile still needs silicon — tests_tpu/ covers
that), so a kernel that cannot even lower fails HERE, in the gate,
rather than in the first on-silicon run.
"""
import re
from collections import Counter

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

    @pytest.mark.parametrize("shape,causal", [
        ((4, 16, 2048, 64), True), ((48, 12, 512, 64), False)],
        ids=["gpt355m", "bert_base"])
    def test_cell_shape_fwd_bwd_lowers(self, shape, causal):
        """The shapes the train cells run attention at (GPT-355M: batch 4,
        16 heads of 64, seq 2048, causal; BERT-base: batch 48, 12 heads
        of 64, seq 512, dense), through the public entry under the
        schedule their shape picks."""
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bhsd)

        def f(q, k, v):
            return jnp.sum(flash_attention_bhsd(
                q, k, v, causal=causal,
                interpret=False).astype(jnp.float32))

        txt = _lower_tpu(jax.grad(f, argnums=(0, 1, 2)),
                         _sd(shape), _sd(shape), _sd(shape))
        for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
            assert kernel in txt, f"{kernel} missing from the lowering"


def _flash_calls(txt):
    """{kernel name: Mosaic calls of that name} among a module's flash
    kernels (a Mosaic call carries its `pallas_call` name)."""
    return Counter(re.findall(r'kernel_name = "(flash_\w+)"', txt))


class TestFlashLoweredOnceAShape:
    """What set-up pays for the flash kernels must not grow with depth:
    a step program traces and lowers each kernel once a shape, however
    many layers call it (`_flash_fwd` / `_flash_bwd_impl` are `jax.jit`ted;
    PR 36's richer kernels, lowered at each of the GPT step's 96 call
    sites in each of set-up's two passes, cost 15 s of `setup_s` and the
    PR).  The benchmark's `setup_s` spreads 6-7%, so this is guarded here,
    by counting the Mosaic calls in the lowered module."""

    @staticmethod
    def _chain_calls(n):
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bhsd)

        def f(q, k, v):
            for _ in range(n):
                q = flash_attention_bhsd(q, k, v, causal=True,
                                         interpret=False)
            return jnp.sum(q.astype(jnp.float32))

        x = _sd((1, 2, 2048, 64))
        return _flash_calls(_lower_tpu(jax.grad(f, argnums=(0, 1, 2)),
                                       x, x, x))

    def test_chained_attentions_share_their_kernels(self):
        two, six = self._chain_calls(2), self._chain_calls(6)
        assert two == six == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}

    @staticmethod
    def _step_calls(layers):
        """The Mosaic calls of a small GPT's `to_static` train step with
        `recompute()` round every block, bf16 autocast and AdamW — traced
        as on a TPU (`nn.functional` takes the kernels there)."""
        import paddle_tpu as P
        from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                           GPTPretrainingCriterion)
        P.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=128, num_layers=layers, num_heads=2,
            ffn_hidden_size=512, max_seq_len=256, dropout=0.0,
            attention_dropout=0.0, use_recompute=True))
        crit = GPTPretrainingCriterion()
        opt = P.optimizer.AdamW(learning_rate=1e-4,
                                parameters=model.parameters())

        @P.jit.to_static
        def train_step(ids, labels):
            opt.clear_grad()
            with P.amp.auto_cast(level="O1", dtype="bfloat16"):
                loss = crit(model(ids), labels)
            loss.backward()
            opt.step()
            return loss

        ids = P.to_tensor(np.zeros((2, 256), np.int32))
        program, _ = train_step.traced_program(ids, ids)
        args = [_sd(v.aval.shape, v.aval.dtype)
                for v in program.jaxpr.invars]
        return _flash_calls(_lower_tpu(
            jax.extend.core.jaxpr_as_fun(program), *args))

    def test_train_step_lowers_a_kernel_once_not_once_a_layer(
            self, monkeypatch):
        import paddle_tpu.ops.pallas as kernels
        monkeypatch.setattr(kernels, "compute_platform", lambda: "tpu")
        two, four = self._step_calls(2), self._step_calls(4)
        # the forward twice: a block's first run drops the lse it does not
        # need, the run `recompute()` differentiates keeps it
        assert two == four == {"flash_fwd": 2, "flash_dq": 1,
                               "flash_dkv": 1}


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


class TestGroupedMatmul:
    @pytest.mark.parametrize("rows,experts", [
        ((1024, 2048), (128, 2048, 1536)), ((1024, 768), (128, 768, 2048)),
        ((192, 2048), (128, 2048, 1536)), ((640, 4096), (36, 4096, 1536))],
        ids=["sdar_w13", "sdar_w2", "kanana_w13", "granite_w13"])
    def test_cell_decode_shape_lowers(self, rows, experts):
        """The expert cells' decode products (SDAR's block pass, Kanana's
        decode, Granite's decode over 36 held experts) under the tiles
        the path rule gives them on a TPU."""
        from paddle_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                          pick_tiles)

        (m, k), (g, _, n) = rows, experts
        tiles = pick_tiles(m, g, k, n, jnp.bfloat16, kernel=True)
        assert tiles is not None
        txt = _lower_tpu(
            lambda x, w, c: grouped_matmul(x, w, c, tiles, interpret=False),
            _sd((m, k)), _sd((g, k, n)), _sd((g,), jnp.int32))
        assert "grouped_matmul" in txt
