"""SDAR-MoE decoder (``model_type: sdar_moe``; JetLM publishes
SDAR-30B-A3B-Chat in this form): a Qwen3-MoE decoder adapted to BLOCK
DIFFUSION.  RMSNorm pre-norm blocks of grouped-query attention — per-head
RMSNorm on queries and keys, then rotary in the rotate-half form over the
whole head — and softmax-routed dropless experts in every layer; an untied
head.

With ``B = block_length`` and ``b(i) = i // B``: ``x0 = Embed[ids]``; layer:
``x' = x + Attn(RMSNorm(x))``, ``y = x' + Experts(RMSNorm(x'))``; ``logits =
RMSNorm(x_L) W_head``.  The logits at position ``i`` are for the token AT
``i`` (no shift: a masked position predicts itself).

- attention: ``q = h W_q`` as ``H x d_h``, ``k, v`` as ``H_kv x d_h`` (``H
  d_h`` need not be the hidden size); ``q <- RMSNorm_{d_h}(q) * w_qn``, ``k
  <- RMSNorm_{d_h}(k) * w_kn`` a head (one weight vector for all heads),
  THEN ``x cos + rotate_half(x) sin`` at the absolute position
  (frequencies ``theta^(-2j/d_h)``); ``score = q . k / sqrt(d_h)``, query
  head ``i`` reads K/V head ``i // (H / H_kv)``; float32 softmax; ``W_o``;
  no bias.  The mask is BLOCK-causal: position ``i`` sees ``j`` iff ``b(j)
  <= b(i)`` — every position of its own block and every earlier block.
- experts: the top ``k`` of the router's float32 logits, a softmax over the
  chosen (``distributed.moe.softmax_topk_route``) — equal to the published
  order (softmax over all, top ``k``, divided by their sum:
  ``norm_topk_prob``); no shared expert, no token dropped.

Generation is by diffusion over blocks of ``B`` positions
(:meth:`SdarMoeForCausalLM.generation_spec`; ``serving/generation.py`` has
the passes, docs/serving.md "Kinds of generation" the lifecycle): a block
starts as mask tokens, each denoising pass runs the block over the stored
prefix and itself and fixes some of its positions, a last pass over the
final ids stores the block's K/V.  :meth:`kv_cache_spec` declares grouped
K/V pages for the whole model and the block its prefill's mask has.  Without
a context the forward is the block-causal pass over the whole sequence.  The
plain reference is benchmark/reference/sdar_moe.py.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.distributed.moe import DroplessMoELayer
from paddle_tpu.incubate.nn.paged_attention import grouped_causal_attention
from paddle_tpu.models.deepseek_v3 import _Leaves, _Norm, _mm, _rms

__all__ = ["SdarMoeConfig", "SdarMoeForCausalLM", "rope_rotate_half"]


class SdarMoeConfig:
    """Hyperparameters under the engine's names (``num_layers``,
    ``num_heads``, ``max_seq_len``, ``n_routed_experts``) with the published
    key beside each in :meth:`from_published`.  ``block_length`` and
    ``mask_token_id`` are the family's generation code's, not
    ``config.json``'s.  ``init_weights=False`` makes every parameter an
    empty placeholder for a loader."""

    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 num_heads=32, num_key_value_heads=4, head_dim=128,
                 moe_intermediate_size=768, n_routed_experts=128,
                 num_experts_per_tok=8, rms_norm_eps=1e-6, rope_theta=1e6,
                 max_seq_len=32768, block_length=4, mask_token_id=151669,
                 initializer_range=0.02, init_weights=True):
        if num_heads % num_key_value_heads:
            raise ValueError("num_heads must divide by num_key_value_heads")
        if not 0 <= mask_token_id < vocab_size:
            raise ValueError(f"mask_token_id {mask_token_id} is no row of a "
                             f"vocabulary of {vocab_size}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_seq_len = max_seq_len
        self.block_length = block_length
        self.mask_token_id = mask_token_id
        self.initializer_range = initializer_range
        self.init_weights = init_weights

    @classmethod
    def from_published(cls, cfg: dict, **kw):
        """From a ``config.json`` of ``model_type: sdar_moe``."""
        if cfg.get("rope_scaling") is not None:
            raise NotImplementedError("rope_scaling")
        if cfg.get("use_sliding_window"):
            raise NotImplementedError("use_sliding_window: true")
        if cfg.get("mlp_only_layers"):
            raise NotImplementedError("mlp_only_layers: every layer is "
                                      "built sparse")
        if cfg.get("decoder_sparse_step", 1) != 1:
            raise NotImplementedError("decoder_sparse_step != 1")
        if cfg.get("attention_bias"):
            raise NotImplementedError("attention_bias: true")
        if not cfg.get("norm_topk_prob", True):
            raise NotImplementedError("norm_topk_prob: false")
        return cls(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            n_routed_experts=cfg["num_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            max_seq_len=cfg["max_position_embeddings"], **kw)


def rope_rotate_half(x, positions, theta):
    """Rotary embedding in the ROTATE-HALF form over the whole head: ``x
    cos + [-x2 | x1] sin`` on ``x [..., s, heads, d]`` at ``positions [...,
    s]``, dimension ``j`` and ``j + d/2`` turning by ``positions *
    theta^(-2j/d)``.  Angles in float32, the result in ``x``'s dtype."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32)                                  # [..., s, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


class SdarAttention(_Leaves):
    """Grouped-query attention with QK-norm and rotary.  Leaves: ``q [d, H
    d_h]``, ``k`` / ``v [d, H_kv d_h]``, ``q_norm`` / ``k_norm [d_h]``, ``o
    [H d_h, d]``; no bias."""

    def __init__(self, config):
        super().__init__(config)
        c = config
        std = c.initializer_range
        kv = c.num_key_value_heads * c.head_dim
        self.q = self.leaf((c.hidden_size, c.num_heads * c.head_dim), std)
        self.k = self.leaf((c.hidden_size, kv), std)
        self.v = self.leaf((c.hidden_size, kv), std)
        self.q_norm = self.leaf((c.head_dim,), "ones")
        self.k_norm = self.leaf((c.head_dim,), "ones")
        self.o = self.leaf((c.num_heads * c.head_dim, c.hidden_size), std)

    def forward(self, hidden, position_ids, kv_ctx=None):
        c = self._cfg
        b, s = hidden.shape[0], hidden.shape[1]

        def project(h, pos, w, wn, heads):
            x = _mm(h, w).reshape(b, s, heads, c.head_dim)
            return rope_rotate_half(_rms(x, wn, c.rms_norm_eps), pos,
                                    c.rope_theta)

        q = apply(lambda h, pos, w, wn: project(h, pos, w, wn, c.num_heads),
                  hidden, position_ids, self.q, self.q_norm)
        k = apply(lambda h, pos, w, wn: project(
            h, pos, w, wn, c.num_key_value_heads),
            hidden, position_ids, self.k, self.k_norm)
        v = apply(_mm, hidden, self.v).reshape(
            [b, s, c.num_key_value_heads, c.head_dim])
        if kv_ctx is not None:
            out = kv_ctx.attend(q, k, v)
        else:
            out = apply(lambda q, k, v: grouped_causal_attention(
                q, k, v, c.head_dim ** -0.5, block=c.block_length), q, k, v)
        return apply(_mm, out.reshape([b, s, c.num_heads * c.head_dim]),
                     self.o)


class SdarMoeDecoderLayer(_Leaves):
    def __init__(self, config):
        super().__init__(config)
        c = config
        self.ln1 = _Norm(c, c.hidden_size)
        self.attn = SdarAttention(c)
        self.ln2 = _Norm(c, c.hidden_size)
        self.mlp = DroplessMoELayer(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok, route="softmax",
            initializer_range=c.initializer_range, make_parameter=self.leaf)

    def forward(self, x, position_ids, kv_ctx=None):
        x = x + self.attn(self.ln1(x), position_ids, kv_ctx=kv_ctx)
        x = x + self.mlp(self.ln2(x))
        if kv_ctx is not None:
            kv_ctx.note_expert_counts(self.mlp.last_counts._value)
        return x


class SdarMoeForCausalLM(_Leaves):
    def __init__(self, config: SdarMoeConfig):
        super().__init__(config)
        self.config = config
        c = config
        self.embed = self.leaf((c.vocab_size, c.hidden_size),
                               c.initializer_range)
        self.layers = nn.LayerList(
            [SdarMoeDecoderLayer(c) for _ in range(c.num_layers)])
        self.norm = _Norm(c, c.hidden_size)
        self.head = self.leaf((c.hidden_size, c.vocab_size),
                              c.initializer_range)

    def generation_spec(self):
        """How ``serving.LLMEngine`` generates with this model: by
        diffusion over blocks of ``block_length`` positions, a masked
        position fed as ``mask_token_id``."""
        return {"kind": "block_diffusion",
                "block_length": self.config.block_length,
                "mask_token_id": self.config.mask_token_id}

    def kv_cache_spec(self):
        """What the whole model caches: pages of K and V at ``H_kv`` heads,
        read by ``H`` query heads; a prefill's mask is causal over blocks of
        ``causal_block`` positions."""
        c = self.config
        return {"kind": "kv", "num_heads": c.num_key_value_heads,
                "head_dim": c.head_dim, "query_heads": c.num_heads,
                "causal_block": c.block_length}

    @property
    def num_expert_layers(self):
        return len(self.layers)

    def forward(self, input_ids, position_ids=None, kv_ctx=None,
                logits_positions=None):
        """``logits_positions [b]``: the head runs on that one position a
        row — ``[b, 1, vocab]``."""
        if position_ids is None:
            position_ids = paddle_tpu.arange(input_ids.shape[-1],
                                             dtype="int32").unsqueeze(0)
        h = apply(lambda ids, table: table[ids], input_ids, self.embed)
        for layer in self.layers:
            h = layer(h, position_ids, kv_ctx=kv_ctx)
        if logits_positions is not None:
            h = apply(lambda v, at: jnp.take_along_axis(
                v, at.astype(jnp.int32)[:, None, None], axis=1),
                h, logits_positions)
        return apply(lambda v, w: jnp.matmul(
            v, w, preferred_element_type=jnp.float32),
            self.norm(h), self.head)
