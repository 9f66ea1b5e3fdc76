"""DeepSeek-V3-style decoder (``model_type: deepseek_v3``; Kanana-2-30B-A3B
is published in this form): RMSNorm pre-norm blocks, latent attention
(MLA, no query low-rank) with interleaved rotary on a slice of each head,
SwiGLU, ``first_k_dense_replace`` leading dense layers and sigmoid-routed
dropless experts with shared experts after them, untied head.

Serving is the path this model is built for: called by
``serving.LLMEngine`` exactly as GPT is (``model(ids, position_ids=,
kv_ctx=)``).  It declares what a layer caches (:meth:`kv_cache_spec`: ONE
latent row ``[c | k_r]`` of ``kv_lora_rank + qk_rope_head_dim`` values a
token) and attends two ways through the context:

- prefill, expanded: ``[k_n | v] = c W_kvb`` for the whole prompt, dense
  causal attention, the latent rows scattered into the pool;
- decode, absorbed: ``q~_h = q_n,h (W_kvb^K,h)^T`` so that scores and the
  weighted sum run over the cached latent rows themselves, ``o_h = u_h
  W_kvb^V,h`` afterwards — the same numbers, and ``W_kvb`` never touches
  the history.

Without a context (``kv_ctx=None``) the forward is the expanded form over
the whole sequence.  Equations: docs/serving.md, "Latent pool and
dropless experts"; the plain reference is
benchmark/reference/deepseek_v3.py.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.core.tensor import Parameter
from paddle_tpu.distributed.moe import DroplessMoELayer
from paddle_tpu.nn import initializer as I

__all__ = ["DeepseekV3Config", "DeepseekV3ForCausalLM", "rope_interleave"]


class DeepseekV3Config:
    """Hyperparameters under the engine's names (``num_layers``,
    ``num_heads``, ``max_seq_len``) with the published key beside each in
    :meth:`from_published`.

    ``init_weights=False`` creates every parameter as an empty
    placeholder (no host-side normals, no device memory): a loader then
    lays each leaf in with ``_set_value`` — at 30 B parameters a random
    copy that is thrown away is a second model's worth of memory.
    """

    def __init__(self, vocab_size=128256, hidden_size=2048, num_layers=48,
                 num_heads=32, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, kv_lora_rank=512, intermediate_size=6144,
                 moe_intermediate_size=768, n_routed_experts=128,
                 n_shared_experts=2, num_experts_per_tok=6,
                 first_k_dense_replace=1, routed_scaling_factor=2.448,
                 norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1e6,
                 max_seq_len=32768, initializer_range=0.02,
                 init_weights=True):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.kv_lora_rank = kv_lora_rank
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.init_weights = init_weights

    @classmethod
    def from_published(cls, cfg: dict, **kw):
        """From a ``config.json`` of ``model_type: deepseek_v3``."""
        if cfg.get("q_lora_rank") is not None:
            raise NotImplementedError("q_lora_rank: the query low-rank "
                                      "projection is not built")
        if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
            raise NotImplementedError("group-limited routing (n_group > 1)")
        if cfg.get("rope_scaling") is not None:
            raise NotImplementedError("rope_scaling")
        if not cfg.get("rope_interleave", True):
            raise NotImplementedError("rope_interleave: false")
        return cls(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
            intermediate_size=cfg["intermediate_size"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            n_routed_experts=cfg["n_routed_experts"],
            n_shared_experts=cfg["n_shared_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            first_k_dense_replace=cfg["first_k_dense_replace"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            norm_topk_prob=cfg["norm_topk_prob"],
            rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            max_seq_len=cfg["max_position_embeddings"], **kw)

    @property
    def latent_row_width(self):
        return self.kv_lora_rank + self.qk_rope_head_dim


def rope_interleave(x, positions, theta):
    """Rotary embedding in the INTERLEAVED pairing ``(x0,x1),(x2,x3)...``
    on ``x [..., s, heads, d]`` at ``positions [..., s]`` (broadcast over
    the leading axes): the pairs are de-interleaved to ``[x0,x2,.. |
    x1,x3,..]`` and the halves rotated, as the published
    ``apply_rotary_pos_emb_interleave`` does.  The result stays in the
    de-interleaved order — the same for queries and keys, so their
    product is that of the pairwise rotation.  Angles in float32, the
    result in ``x``'s dtype."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(
        inv, jnp.float32)                                  # [..., s, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    xf = x.astype(jnp.float32)
    xf = jnp.concatenate([xf[..., 0::2], xf[..., 1::2]], -1)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., :d // 2]], -1)
    return (xf * cos + half * sin).astype(x.dtype)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                             + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def _causal_attention(q, k, v, scale):
    """Plain causal attention ``[b, s, H, d_qk] x [b, s, H, d_v]`` (the
    value head is narrower than the query-key head, which the flash
    kernel does not take); float32 scores and softmax."""
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(jnp.tril(jnp.ones((s, s), jnp.bool_)), scores,
                       jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _mm(a, b):
    """Product with float32 accumulation, rounded once to ``a``'s dtype."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(
        a.dtype)


class _Leaves(nn.Layer):
    """Parameter creation honouring the config: placeholders in place of
    values when a loader will lay them in."""

    def __init__(self, config):
        super().__init__()
        self._cfg = config

    def leaf(self, shape, std):
        if not self._cfg.init_weights:
            return Parameter(jnp.zeros((0,) * len(shape), self._dtype))
        init = I.Constant(1.0) if std == "ones" else (
            I.Normal(0.0, std) if std else I.Constant(0.0))
        return self.create_parameter(list(shape), default_initializer=init)


class MLAttention(_Leaves):
    """Latent attention.  Leaves: ``q [d, H (d_n + d_r)]``, ``kva [d, r +
    d_r]``, ``kv_norm [r]``, ``kvb [r, H (d_n + d_v)]``, ``o [H d_v,
    d]``."""

    def __init__(self, config):
        super().__init__(config)
        c = config
        std = c.initializer_range
        H = c.num_heads
        self.q = self.leaf((c.hidden_size,
                            H * (c.qk_nope_head_dim + c.qk_rope_head_dim)),
                           std)
        self.kva = self.leaf((c.hidden_size, c.latent_row_width), std)
        self.kv_norm = self.leaf((c.kv_lora_rank,), "ones")
        self.kvb = self.leaf((c.kv_lora_rank,
                              H * (c.qk_nope_head_dim + c.v_head_dim)), std)
        self.o = self.leaf((H * c.v_head_dim, c.hidden_size), std)

    def _project(self, h, positions, wq, wkva, wn):
        """(q_n [b,s,H,d_n], q_r rotated [b,s,H,d_r], rows [b,s,r+d_r])."""
        c = self._cfg
        b, s, _ = h.shape
        dn, dr, r = c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank
        q = _mm(h, wq).reshape(b, s, c.num_heads, dn + dr)
        q_r = rope_interleave(q[..., dn:], positions, c.rope_theta)
        ckr = _mm(h, wkva)
        lat = _rms(ckr[..., :r], wn, c.rms_norm_eps)
        k_r = rope_interleave(ckr[..., None, r:], positions,
                              c.rope_theta)[..., 0, :]
        return q[..., :dn], q_r, jnp.concatenate([lat, k_r], -1)

    def forward(self, hidden, position_ids, kv_ctx=None):
        c = self._cfg
        H, dn, dv = c.num_heads, c.qk_nope_head_dim, c.v_head_dim
        r = c.kv_lora_rank
        scale = 1.0 / float(dn + c.qk_rope_head_dim) ** 0.5

        if kv_ctx is not None and kv_ctx.mode == "decode":
            def absorb(h, pos, wq, wkva, wn, wkvb):
                q_n, q_r, rows = self._project(h, pos, wq, wkva, wn)
                wk = wkvb.reshape(r, H, dn + dv)[..., :dn]     # [r, H, d_n]
                q_lat = jnp.einsum(
                    "bshd,rhd->bshr", q_n, wk,
                    preferred_element_type=jnp.float32).astype(h.dtype)
                return jnp.concatenate([q_lat, q_r], -1), rows

            q_abs, rows = apply(absorb, hidden, position_ids, self.q,
                                self.kva, self.kv_norm, self.kvb)
            u = kv_ctx.latent_decode(q_abs, rows, r, scale)   # [b,1,H,r]

            def expand(u, wkvb, wo):
                wv = wkvb.reshape(r, H, dn + dv)[..., dn:]     # [r, H, d_v]
                o = jnp.einsum(
                    "bshr,rhd->bshd", u, wv,
                    preferred_element_type=jnp.float32).astype(u.dtype)
                return _mm(o.reshape(o.shape[0], o.shape[1], H * dv), wo)

            return apply(expand, u, self.kvb, self.o)

        def expanded(h, pos, wq, wkva, wn, wkvb):
            q_n, q_r, rows = self._project(h, pos, wq, wkva, wn)
            b, s = h.shape[0], h.shape[1]
            kv = _mm(rows[..., :r], wkvb).reshape(b, s, H, dn + dv)
            k_r = jnp.broadcast_to(rows[:, :, None, r:],
                                   (b, s, H, rows.shape[-1] - r))
            q = jnp.concatenate([q_n, q_r], -1)
            k = jnp.concatenate([kv[..., :dn], k_r], -1)
            return q, k, kv[..., dn:], rows

        q, k, v, rows = apply(expanded, hidden, position_ids, self.q,
                              self.kva, self.kv_norm, self.kvb)
        if kv_ctx is not None:
            out = kv_ctx.latent_prefill(q, k, v, rows)
        else:
            out = apply(lambda q, k, v: _causal_attention(q, k, v, scale),
                        q, k, v)
        b, s = out.shape[0], out.shape[1]
        return apply(_mm, out.reshape([b, s, H * dv]), self.o)


class SwiGLU(_Leaves):
    """``(silu(x W1) * (x W3)) W2`` with ``w13 = [W1 | W3]``."""

    def __init__(self, config, width):
        super().__init__(config)
        std = config.initializer_range
        self.w13 = self.leaf((config.hidden_size, 2 * width), std)
        self.w2 = self.leaf((width, config.hidden_size), std)

    def forward(self, x):
        def fn(v, w13, w2):
            gate, up = jnp.split(jnp.matmul(
                v, w13, preferred_element_type=jnp.float32), 2, -1)
            return _mm((jax.nn.silu(gate) * up).astype(v.dtype), w2)
        return apply(fn, x, self.w13, self.w2)


class _Norm(_Leaves):
    def __init__(self, config, width):
        super().__init__(config)
        self.weight = self.leaf((width,), "ones")

    def forward(self, x):
        return apply(lambda v, w: _rms(v, w, self._cfg.rms_norm_eps), x,
                     self.weight)


class DeepseekV3DecoderLayer(_Leaves):
    def __init__(self, config, layer_idx):
        super().__init__(config)
        c = config
        self.ln1 = _Norm(c, c.hidden_size)
        self.attn = MLAttention(c)
        self.ln2 = _Norm(c, c.hidden_size)
        self.is_moe = layer_idx >= c.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, n_shared=c.n_shared_experts,
                routed_scaling_factor=c.routed_scaling_factor,
                norm_topk_prob=c.norm_topk_prob,
                initializer_range=c.initializer_range,
                make_parameter=self.leaf)
        else:
            self.mlp = SwiGLU(c, c.intermediate_size)

    def forward(self, x, position_ids, kv_ctx=None):
        x = x + self.attn(self.ln1(x), position_ids, kv_ctx=kv_ctx)
        x = x + self.mlp(self.ln2(x))
        if self.is_moe and kv_ctx is not None:
            kv_ctx.note_expert_counts(self.mlp.last_counts._value)
        return x


class DeepseekV3ForCausalLM(_Leaves):
    def __init__(self, config: DeepseekV3Config):
        super().__init__(config)
        self.config = config
        c = config
        self.embed = self.leaf((c.vocab_size, c.hidden_size),
                               c.initializer_range)
        self.layers = nn.LayerList(
            [DeepseekV3DecoderLayer(c, i) for i in range(c.num_layers)])
        self.norm = _Norm(c, c.hidden_size)
        self.head = self.leaf((c.hidden_size, c.vocab_size),
                              c.initializer_range)

    def kv_cache_spec(self):
        """What one layer caches, for ``serving.LLMEngine``: one latent
        row ``[c | k_r]`` a token, whose first ``value_width`` columns are
        what the weighted sum runs over."""
        return {"kind": "latent", "row_width": self.config.latent_row_width,
                "value_width": self.config.kv_lora_rank,
                "num_layers": self.config.num_layers}

    @property
    def num_expert_layers(self):
        return sum(1 for layer in self.layers if layer.is_moe)

    def forward(self, input_ids, position_ids=None, kv_ctx=None,
                logits_positions=None):
        """``logits_positions [b]`` (the engine's prefill passes it):
        the head runs on that one position a row only — ``[b, 1,
        vocab]`` — and not on a whole prompt of 128 k-wide rows."""
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
