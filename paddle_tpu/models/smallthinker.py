"""SmallThinker decoder (PowerInfer publishes SmallThinker-21BA3B-Instruct in
this form): RMSNorm pre-norm blocks of grouped-query attention of TWO kinds
— full attention without positions, and sliding-window attention with
rotary — each followed by softmax-routed dropless ReGLU experts whose router
reads the attention block's input; an untied head.

With ``W`` the window and ``x`` a layer's input: ``a = RMSNorm(x)``; the
router's float32 logits ``z = a W_r``; ``x' = x + Attn(a)``; ``b =
RMSNorm(x')``; ``y = x' + sum_{e in top k(z)} g_e E_e(b)``, ``g`` a softmax
over the chosen logits (equal to the published order: softmax over all,
top ``k``, divided by their sum, ``norm_topk_prob``); ``E_e(b) =
(relu(b W1_e) * (b W3_e)) W2_e``; ``logits = RMSNorm(x_L) W_head``.

- attention: ``q = a W_q`` as ``H x d_h`` (``H d_h`` need not be the hidden
  size), ``k, v`` as ``H_kv x d_h``, no bias, no QK-norm; ``score = q . k /
  sqrt(d_h)``, query head ``i`` reads K/V head ``i // (H / H_kv)``; float32
  softmax; ``W_o``.  A layer is, by the published ``rope_layout`` /
  ``sliding_window_layout`` (equal, one flag a layer): FULL (flag 0) — no
  rotation, ``i`` sees ``j`` iff ``j <= i`` — or WINDOW (flag 1) — ``q``
  and ``k`` rotated in the rotate-half form at the absolute position
  (:func:`~paddle_tpu.models.sdar_moe.rope_rotate_half`), ``i`` sees ``j``
  iff ``i - W < j <= i``.

Serving is the path this model is built for.  :meth:`kv_cache_spec`
declares a cache kind PER LAYER: ``kv`` pages for a full layer (its prefill
through the flash kernel where that runs) and a ``window`` ring of ``W``
rows a slot for a window layer (serving/kv_pool.py ``WindowKV``).  Without
a context the forward is the plain pass over the whole sequence.  The plain
reference is benchmark/reference/smallthinker.py.
"""
from __future__ import annotations

import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.distributed.moe import DroplessMoELayer
from paddle_tpu.incubate.nn.paged_attention import grouped_causal_attention
from paddle_tpu.models.deepseek_v3 import _Leaves, _Norm, _mm
from paddle_tpu.models.sdar_moe import rope_rotate_half

__all__ = ["SmallThinkerConfig", "SmallThinkerForCausalLM"]


class SmallThinkerConfig:
    """Hyperparameters under the engine's names (``num_layers``,
    ``num_heads``, ``max_seq_len``, ``n_routed_experts``) with the published
    key beside each in :meth:`from_published`.  ``window_layers``: one bool
    a layer, True for a sliding-window layer with rotary.
    ``init_weights=False`` makes every parameter an empty placeholder for a
    loader."""

    def __init__(self, vocab_size=151936, hidden_size=2560, num_layers=52,
                 num_heads=28, num_key_value_heads=4, head_dim=128,
                 moe_intermediate_size=768, n_routed_experts=64,
                 num_experts_per_tok=6, rms_norm_eps=1e-6,
                 rope_theta=1.5e6, sliding_window=4096, window_layers=None,
                 max_seq_len=16384, initializer_range=0.02,
                 init_weights=True):
        if window_layers is None:
            window_layers = [i % 4 != 0 for i in range(num_layers)]
        if len(window_layers) != num_layers:
            raise ValueError(f"window_layers names {len(window_layers)} "
                             f"layers, num_layers is {num_layers}")
        if num_heads % num_key_value_heads:
            raise ValueError("num_heads must divide by num_key_value_heads")
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
        self.sliding_window = sliding_window
        self.window_layers = [bool(w) for w in window_layers]
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.init_weights = init_weights

    @classmethod
    def from_published(cls, cfg: dict, **kw):
        """From a SmallThinker ``config.json``; the layouts' first
        ``num_hidden_layers`` flags are this model's layers."""
        if cfg.get("rope_scaling") is not None:
            raise NotImplementedError("rope_scaling")
        secondary = sorted(k for k in cfg if "secondary" in k)
        if secondary:
            raise NotImplementedError(
                f"{secondary[0]}: secondary experts are not built")
        if cfg["rope_layout"] != cfg["sliding_window_layout"]:
            raise NotImplementedError(
                "rope_layout != sliding_window_layout: a layer is built "
                "either full without positions or windowed with rotary")
        if not cfg.get("moe_primary_router_apply_softmax", True):
            raise NotImplementedError(
                "moe_primary_router_apply_softmax: false")
        if not cfg.get("norm_topk_prob", True):
            raise NotImplementedError("norm_topk_prob: false")
        if cfg.get("tie_word_embeddings"):
            raise NotImplementedError("tie_word_embeddings: true")
        n = cfg["num_hidden_layers"]
        return cls(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=n, num_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            moe_intermediate_size=cfg["moe_ffn_hidden_size"],
            n_routed_experts=cfg["moe_num_primary_experts"],
            num_experts_per_tok=cfg["moe_num_active_primary_experts"],
            rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
            sliding_window=cfg["sliding_window_size"],
            window_layers=[bool(f) for f in cfg["sliding_window_layout"][:n]],
            max_seq_len=cfg["max_position_embeddings"], **kw)


class SmallThinkerAttention(_Leaves):
    """Grouped-query attention, full without positions or windowed with
    rotary.  Leaves: ``q [d, H d_h]``, ``k`` / ``v [d, H_kv d_h]``, ``o [H
    d_h, d]``; no bias."""

    def __init__(self, config, window):
        super().__init__(config)
        c = config
        std = c.initializer_range
        kv = c.num_key_value_heads * c.head_dim
        self.window = window
        self.q = self.leaf((c.hidden_size, c.num_heads * c.head_dim), std)
        self.k = self.leaf((c.hidden_size, kv), std)
        self.v = self.leaf((c.hidden_size, kv), std)
        self.o = self.leaf((c.num_heads * c.head_dim, c.hidden_size), std)

    def forward(self, hidden, position_ids, kv_ctx=None):
        c = self._cfg
        b, s = hidden.shape[0], hidden.shape[1]

        def project(h, pos, w, heads):
            x = _mm(h, w).reshape(b, s, heads, c.head_dim)
            return rope_rotate_half(x, pos, c.rope_theta) if self.window \
                else x

        q, k = (apply(lambda h, pos, w, n=n: project(h, pos, w, n),
                      hidden, position_ids, w)
                for w, n in ((self.q, c.num_heads),
                             (self.k, c.num_key_value_heads)))
        v = apply(_mm, hidden, self.v).reshape(
            [b, s, c.num_key_value_heads, c.head_dim])
        if kv_ctx is not None:
            out = kv_ctx.attend(q, k, v)
        else:
            window = c.sliding_window if self.window else None
            out = apply(lambda q, k, v: grouped_causal_attention(
                q, k, v, c.head_dim ** -0.5, window=window), q, k, v)
        return apply(_mm, out.reshape([b, s, c.num_heads * c.head_dim]),
                     self.o)


class SmallThinkerDecoderLayer(_Leaves):
    def __init__(self, config, layer_idx):
        super().__init__(config)
        c = config
        self.ln1 = _Norm(c, c.hidden_size)
        self.attn = SmallThinkerAttention(c, c.window_layers[layer_idx])
        self.ln2 = _Norm(c, c.hidden_size)
        self.mlp = DroplessMoELayer(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok, route="softmax", act="relu",
            initializer_range=c.initializer_range, make_parameter=self.leaf)

    def forward(self, x, position_ids, kv_ctx=None):
        a = self.ln1(x)
        x = x + self.attn(a, position_ids, kv_ctx=kv_ctx)
        # the router sits BEFORE attention: it reads the block's input
        x = x + self.mlp(self.ln2(x), route_from=a)
        if kv_ctx is not None:
            kv_ctx.note_expert_counts(self.mlp.last_counts._value)
        return x


class SmallThinkerForCausalLM(_Leaves):
    def __init__(self, config: SmallThinkerConfig):
        super().__init__(config)
        self.config = config
        c = config
        self.embed = self.leaf((c.vocab_size, c.hidden_size),
                               c.initializer_range)
        self.layers = nn.LayerList([SmallThinkerDecoderLayer(c, i)
                                    for i in range(c.num_layers)])
        self.norm = _Norm(c, c.hidden_size)
        self.head = self.leaf((c.hidden_size, c.vocab_size),
                              c.initializer_range)

    def kv_cache_spec(self):
        """What EACH layer caches, for ``serving.LLMEngine``: pages of K and
        V at ``H_kv`` heads for a full layer (28 query heads read 4 of
        them; its prefill through the flash kernel where that runs), a ring
        of the last ``sliding_window`` positions a slot for a window
        layer."""
        c = self.config
        heads = {"num_heads": c.num_key_value_heads, "head_dim": c.head_dim,
                 "query_heads": c.num_heads}
        full = dict(heads, kind="kv")
        window = dict(heads, kind="window", window=c.sliding_window)
        return {"kind": "layers",
                "layers": [dict(window if w else full)
                           for w in c.window_layers]}

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
