"""Granite-4.0-H decoder (``model_type: granitemoehybrid``; IBM publishes
granite-4.0-h-small in this form): RMSNorm pre-norm blocks whose mixer is,
by ``layer_types``, a Mamba-2 state-space layer or grouped-query attention
WITHOUT positions, each followed by softmax-routed dropless experts plus a
shared SwiGLU; four scalar multipliers; a tied head.

With ``d`` hidden and ``m = residual_multiplier``: ``x0 = Embed[ids] *
embedding_multiplier``; layer ``l``: ``x' = x + m Mixer_l(RMSNorm(x))``,
``y = x' + m (Experts(h) + Shared(h))``, ``h = RMSNorm(x')``; ``logits =
RMSNorm(x_L) Embed^T / logits_scaling``.

- attention: ``q = h W_q`` as ``H x d_h``, ``k, v`` as ``H_kv x d_h``,
  no rotation, ``score = attention_multiplier * q . k`` (query head ``i``
  reads K/V head ``i // (H / H_kv)``), causal, float32 softmax, ``W_o``.
- mamba (``H_m`` heads of ``P``, state ``N``, one group, ``D_i = H_m P``):
  ``[z | xBC | dt] = h W_in``; ``xBC = silu(conv1d(xBC) + b)`` (causal,
  depthwise, ``d_conv`` taps); ``[x | B | C] = xBC``; ``dt = softplus(dt +
  dt_bias)``, ``a = -exp(A_log)``; a head's state ``S`` (``P x N``,
  float32): ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t
  + D x_t``; ``out = (RMSNorm(y * silu(z)) * w_n) W_out``.
  :func:`mamba2_scan` runs a prompt in chunks (inside a chunk the quadratic
  form, between chunks the state) and ends in the state decode starts from;
  :func:`mamba2_step` is the one-token update.
- experts: the top ``k`` of the router's LOGITS, a softmax over the chosen
  (``distributed.moe.softmax_topk_route``), no token dropped; the model may
  hold a contiguous share of each layer's experts (``held``).

Serving is the path this model is built for.  :meth:`kv_cache_spec` declares
a cache kind PER LAYER: ``kv`` pages of ``H_kv x d_h`` for an attention
layer, a per-SLOT ``state`` (the convolution's last ``d_conv - 1`` inputs
and ``S``) for a mamba layer, which the mixer reaches through
``kv_ctx.recur``.  Without a context the forward is the chunked scan from
a zero state over the whole sequence.  The plain reference is
benchmark/reference/granitemoehybrid.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from paddle_tpu import nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.distributed.moe import DroplessMoELayer
from paddle_tpu.incubate.nn.paged_attention import grouped_causal_attention
from paddle_tpu.models.deepseek_v3 import _Leaves, _Norm, _mm, _rms

__all__ = ["GraniteMoeHybridConfig", "GraniteMoeHybridForCausalLM",
           "mamba2_scan", "mamba2_step"]


class GraniteMoeHybridConfig:
    """Hyperparameters under the engine's names (``num_layers``,
    ``num_heads``, ``max_seq_len``, ``n_routed_experts``) with the
    published key beside each in :meth:`from_published`.  ``held =
    (first, count)`` is the share of every layer's experts this model
    holds (default all); ``init_weights=False`` makes every parameter an
    empty placeholder for a loader."""

    def __init__(self, vocab_size=100352, hidden_size=4096, num_layers=40,
                 layer_types=None, num_heads=32, num_key_value_heads=8,
                 mamba_n_heads=128, mamba_d_head=64, mamba_d_state=128,
                 mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=256,
                 intermediate_size=768, shared_intermediate_size=1536,
                 n_routed_experts=72, num_experts_per_tok=10, held=None,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 attention_multiplier=0.0078125, logits_scaling=16.0,
                 rms_norm_eps=1e-5, max_seq_len=131072,
                 initializer_range=0.02, init_weights=True):
        if layer_types is None:
            layer_types = ["mamba"] * num_layers
        if len(layer_types) != num_layers:
            raise ValueError(f"layer_types names {len(layer_types)} layers, "
                             f"num_layers is {num_layers}")
        if mamba_n_groups != 1:
            raise NotImplementedError("mamba_n_groups > 1")
        if num_heads % num_key_value_heads:
            raise ValueError("num_heads must divide by num_key_value_heads")
        if shared_intermediate_size % intermediate_size:
            raise NotImplementedError(
                "shared_intermediate_size must be a multiple of "
                "intermediate_size (DroplessMoELayer's shared experts)")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.layer_types = list(layer_types)
        self.num_heads = num_heads
        self.num_key_value_heads = num_key_value_heads
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size
        self.intermediate_size = intermediate_size
        self.shared_intermediate_size = shared_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.held = tuple(held) if held is not None else (0, n_routed_experts)
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.attention_multiplier = attention_multiplier
        self.logits_scaling = logits_scaling
        self.rms_norm_eps = rms_norm_eps
        self.max_seq_len = max_seq_len
        self.initializer_range = initializer_range
        self.init_weights = init_weights

    @classmethod
    def from_published(cls, cfg: dict, **kw):
        """From a ``config.json`` of ``model_type: granitemoehybrid``."""
        if cfg.get("position_embedding_type", "nope") != "nope":
            raise NotImplementedError("position_embedding_type: only "
                                      "'nope' (no rotation) is built")
        if cfg.get("attention_bias") or cfg.get("mamba_conv_bias") is False \
                or cfg.get("mamba_proj_bias"):
            raise NotImplementedError("attention_bias / mamba_proj_bias / "
                                      "a convolution without bias")
        if not cfg.get("tie_word_embeddings", True):
            raise NotImplementedError("an untied head")
        inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
        if inner != cfg["mamba_expand"] * cfg["hidden_size"]:
            raise ValueError("mamba_n_heads x mamba_d_head must equal "
                             "mamba_expand x hidden_size")
        return cls(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            layer_types=cfg["layer_types"],
            num_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            mamba_n_heads=cfg["mamba_n_heads"],
            mamba_d_head=cfg["mamba_d_head"],
            mamba_d_state=cfg["mamba_d_state"],
            mamba_d_conv=cfg["mamba_d_conv"],
            mamba_n_groups=cfg["mamba_n_groups"],
            mamba_chunk_size=cfg["mamba_chunk_size"],
            intermediate_size=cfg["intermediate_size"],
            shared_intermediate_size=cfg["shared_intermediate_size"],
            # the router keeps its published width whatever share is held
            n_routed_experts=cfg.get("published", {}).get(
                "num_local_experts", cfg["num_local_experts"]),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            held=cfg.get("held"),
            embedding_multiplier=cfg["embedding_multiplier"],
            residual_multiplier=cfg["residual_multiplier"],
            attention_multiplier=cfg["attention_multiplier"],
            logits_scaling=cfg["logits_scaling"],
            rms_norm_eps=cfg["rms_norm_eps"],
            max_seq_len=cfg["max_position_embeddings"], **kw)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def mamba_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self):
        """Channels the convolution runs over: ``[x | B | C]``."""
        return self.mamba_inner + 2 * self.mamba_d_state


# ------------------------------------------------------------ the mixer
def _conv_silu(taps, conv_w, conv_b):
    """``silu(sum_j w[j] * taps[j] + b)`` in float32: the causal depthwise
    convolution from its ``d_conv`` inputs, oldest first."""
    w = conv_w.astype(jnp.float32)
    out = sum(t.astype(jnp.float32) * w[j] for j, t in enumerate(taps))
    return jax.nn.silu(out + conv_b.astype(jnp.float32))


def mamba2_scan(x, dt, Bm, Cm, a, D, chunk, lens=None):
    """The state-space recurrence over a whole prompt from a zero state,
    chunk by chunk.  ``x [b, s, H, P]``, ``dt [b, s, H]`` (after the
    softplus, float32), ``Bm`` / ``Cm [b, s, N]``, ``a`` / ``D [H]``
    float32.  Positions at or past ``lens [b]`` get ``dt = 0``: they leave
    the state as it is, so a prompt ends in the same state in every
    padded length.  Returns (``y [b, s, H, P]`` float32, final state
    ``[b, H, P, N]`` float32).

    Inside a chunk of ``Q`` positions, with ``L_t`` the running sum of
    ``dt a``: ``y_t = sum_{u<=t} exp(L_t - L_u) dt_u (C_t . B_u) x_u +
    exp(L_t) S_in C_t``; the chunk hands on ``exp(L_Q) S_in + sum_u
    exp(L_Q - L_u) dt_u x_u B_u^T``.  Decays and the carried state are
    float32; the two products over positions take ``x``'s dtype operands
    and accumulate in float32.
    """
    b, s, H, P = x.shape
    N = Bm.shape[-1]
    dt = dt.astype(jnp.float32)
    if lens is not None:
        real = jnp.arange(s, dtype=jnp.int32)[None, :] < lens[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
    Q = min(chunk, s)
    pad = -s % Q
    if pad:
        x, dt, Bm, Cm = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (
            v.ndim - 2)) for v in (x, dt, Bm, Cm))
    nc = (s + pad) // Q

    def chunks(v):       # [b, nc * Q, ...] -> [nc, b, Q, ...]
        return jnp.moveaxis(v.reshape((b, nc, Q) + v.shape[2:]), 1, 0)

    tri = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    hi = jax.lax.Precision.HIGHEST

    def body(S, inp):
        xc, dtc, Bc, Cc = inp
        L = jnp.cumsum(dtc * a, axis=1)                       # [b, Q, H]
        Lh = jnp.swapaxes(L, 1, 2)                            # [b, H, Q]
        decay = jnp.exp(jnp.where(
            tri, Lh[..., :, None] - Lh[..., None, :], -jnp.inf))
        G = jnp.einsum("bqn,bkn->bqk", Cc, Bc,
                       preferred_element_type=jnp.float32)
        W = G[:, None] * decay * jnp.swapaxes(dtc, 1, 2)[:, :, None, :]
        y = jnp.einsum("bhqk,bkhp->bqhp", W.astype(xc.dtype), xc,
                       preferred_element_type=jnp.float32)
        # the carried state is float32 and is read at full precision
        y = y + jnp.exp(L)[..., None] * jnp.einsum(
            "bhpn,bqn->bqhp", S, Cc.astype(jnp.float32), precision=hi)
        to_end = jnp.exp(L[:, -1:, :] - L) * dtc              # [b, Q, H]
        xw = (xc.astype(jnp.float32) * to_end[..., None]).astype(xc.dtype)
        S = S * jnp.exp(L[:, -1])[..., None, None] + jnp.einsum(
            "bkhp,bkn->bhpn", xw, Bc, preferred_element_type=jnp.float32)
        return S, y

    S, y = jax.lax.scan(body, jnp.zeros((b, H, P, N), jnp.float32),
                        tuple(chunks(v) for v in (x, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, nc * Q, H, P)[:, :s]
    return y + D[:, None] * x[:, :s].astype(jnp.float32), S


def mamba2_step(S, x, dt, Bm, Cm, a, D):
    """One token a row: ``S [b, H, P, N]`` float32, ``x [b, H, P]``, ``dt
    [b, H]`` float32, ``Bm`` / ``Cm [b, N]``.  Returns (``y [b, H, P]``
    float32, the advanced state)."""
    xf, Bf, Cf = (v.astype(jnp.float32) for v in (x, Bm, Cm))
    S = (S * jnp.exp(dt * a)[..., None, None]
         + (dt[..., None] * xf)[..., None] * Bf[:, None, None, :])
    y = jnp.sum(S * Cf[:, None, None, :], axis=-1)
    return y + D[:, None] * xf, S


class Mamba2Mixer(_Leaves):
    """Leaves: ``in_proj [d, 2 D_i + 2 N + H]``, ``conv_w [d_conv, D_i + 2
    N]`` (tap ``d_conv - 1`` meets the current position), ``conv_b``,
    ``dt_bias`` / ``A_log`` / ``D [H]``, ``norm [D_i]``, ``out_proj [D_i,
    d]``."""

    def __init__(self, config):
        super().__init__(config)
        c = config
        std = c.initializer_range
        H, Di, C = c.mamba_n_heads, c.mamba_inner, c.conv_width
        self.in_proj = self.leaf((c.hidden_size, Di + C + H), std)
        self.conv_w = self.leaf((c.mamba_d_conv, C), std)
        self.conv_b = self.leaf((C,), 0.0)
        self.dt_bias = self.leaf((H,), "ones")
        self.A_log = self.leaf((H,), 0.0)
        self.D = self.leaf((H,), "ones")
        self.norm = self.leaf((Di,), "ones")
        self.out_proj = self.leaf((Di, c.hidden_size), std)

    def _split(self, h, w_in, dt_bias):
        """``h [b, s, d]`` -> (z ``[b, s, D_i]``, xBC before the
        convolution ``[b, s, C]``, dt after the softplus, float32)."""
        c = self._cfg
        Di, C = c.mamba_inner, c.conv_width
        zxd = _mm(h, w_in)
        dt = jax.nn.softplus(zxd[..., Di + C:].astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        return zxd[..., :Di], zxd[..., Di:Di + C], dt

    def _finish(self, y, z, w_n, w_out):
        """The gate BEFORE the norm, the norm over all of ``D_i``."""
        c = self._cfg
        g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
        return _mm(_rms(g, w_n, c.rms_norm_eps).astype(z.dtype), w_out)

    def _prompt(self, lens, h, w_in, conv_w, conv_b, dt_bias, A_log, D,
                w_n, w_out):
        """The whole (padded) prompt ``h [b, s, d]`` from a zero state.
        Returns (out, window ``[b, d_conv - 1, C]``: the inputs of the
        convolution at the prompt's real end, final state)."""
        c = self._cfg
        b, s, _ = h.shape
        K, Di, N = c.mamba_d_conv, c.mamba_inner, c.mamba_d_state
        z, xbc, dt = self._split(h, w_in, dt_bias)
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        act = _conv_silu([padded[:, j:j + s] for j in range(K)], conv_w,
                         conv_b).astype(h.dtype)
        x = act[..., :Di].reshape(b, s, c.mamba_n_heads, c.mamba_d_head)
        y, S = mamba2_scan(
            x, dt, act[..., Di:Di + N], act[..., Di + N:],
            -jnp.exp(A_log.astype(jnp.float32)), D.astype(jnp.float32),
            c.mamba_chunk_size, lens)
        ends = jnp.full((b,), s, jnp.int32) if lens is None else lens
        # padded[len : len + K - 1] are positions len - K + 1 .. len - 1
        window = jax.vmap(lambda p, at: jax.lax.dynamic_slice_in_dim(
            p, at, K - 1, axis=0))(padded, ends.astype(jnp.int32))
        return self._finish(y, z, w_n, w_out), window, S

    def _token(self, window, S, h, w_in, conv_w, conv_b, dt_bias, A_log, D,
               w_n, w_out):
        """One token a row ``h [b, 1, d]`` from each row's own state."""
        c = self._cfg
        Di, N = c.mamba_inner, c.mamba_d_state
        z, xbc, dt = self._split(h, w_in, dt_bias)
        taps = jnp.concatenate([window, xbc.astype(window.dtype)], axis=1)
        act = _conv_silu([taps[:, j] for j in range(c.mamba_d_conv)],
                         conv_w, conv_b).astype(h.dtype)
        x = act[:, :Di].reshape(-1, c.mamba_n_heads, c.mamba_d_head)
        y, S = mamba2_step(
            S, x, dt[:, 0], act[:, Di:Di + N], act[:, Di + N:],
            -jnp.exp(A_log.astype(jnp.float32)), D.astype(jnp.float32))
        return self._finish(y[:, None], z, w_n, w_out), taps[:, 1:], S

    def forward(self, hidden, kv_ctx=None):
        leaves = (self.in_proj, self.conv_w, self.conv_b, self.dt_bias,
                  self.A_log, self.D, self.norm, self.out_proj)
        if kv_ctx is None:
            return apply(lambda *v: self._prompt(None, *v)[0], hidden,
                         *leaves)
        if kv_ctx.mode == "decode":
            return kv_ctx.recur(
                lambda window, S, _lens, *v: self._token(window, S, *v),
                hidden, *leaves)
        return kv_ctx.recur(
            lambda _window, _S, lens, *v: self._prompt(lens, *v),
            hidden, *leaves)


class GroupedQueryAttention(_Leaves):
    """Attention without positions.  Leaves: ``q [d, H d_h]``, ``k`` / ``v
    [d, H_kv d_h]``, ``o [H d_h, d]``; no bias."""

    def __init__(self, config):
        super().__init__(config)
        c = config
        std = c.initializer_range
        kv = c.num_key_value_heads * c.head_dim
        self.q = self.leaf((c.hidden_size, c.hidden_size), std)
        self.k = self.leaf((c.hidden_size, kv), std)
        self.v = self.leaf((c.hidden_size, kv), std)
        self.o = self.leaf((c.hidden_size, c.hidden_size), std)

    def forward(self, hidden, kv_ctx=None):
        c = self._cfg
        b, s = hidden.shape[0], hidden.shape[1]
        q, k, v = (apply(_mm, hidden, w).reshape([b, s, heads, c.head_dim])
                   for w, heads in ((self.q, c.num_heads),
                                    (self.k, c.num_key_value_heads),
                                    (self.v, c.num_key_value_heads)))
        if kv_ctx is not None:
            out = kv_ctx.attend(q, k, v)
        else:
            out = apply(lambda q, k, v: grouped_causal_attention(
                q, k, v, c.attention_multiplier), q, k, v)
        return apply(_mm, out.reshape([b, s, c.hidden_size]), self.o)


class GraniteMoeHybridDecoderLayer(_Leaves):
    def __init__(self, config, layer_idx):
        super().__init__(config)
        c = config
        self.is_state = c.layer_types[layer_idx] == "mamba"
        if not self.is_state and c.layer_types[layer_idx] != "attention":
            raise ValueError(f"layer_types[{layer_idx}] = "
                             f"{c.layer_types[layer_idx]!r}")
        self.ln1 = _Norm(c, c.hidden_size)
        self.mixer = (Mamba2Mixer(c) if self.is_state
                      else GroupedQueryAttention(c))
        self.ln2 = _Norm(c, c.hidden_size)
        self.mlp = DroplessMoELayer(
            c.hidden_size, c.intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok, route="softmax", held=c.held,
            n_shared=c.shared_intermediate_size // c.intermediate_size,
            initializer_range=c.initializer_range, make_parameter=self.leaf)

    def forward(self, x, kv_ctx=None):
        m = self._cfg.residual_multiplier
        x = x + self.mixer(self.ln1(x), kv_ctx=kv_ctx) * m
        x = x + self.mlp(self.ln2(x)) * m
        if kv_ctx is not None:
            kv_ctx.note_expert_counts(self.mlp.last_counts._value)
        return x


class GraniteMoeHybridForCausalLM(_Leaves):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__(config)
        self.config = config
        c = config
        self.embed = self.leaf((c.vocab_size, c.hidden_size),
                               c.initializer_range)
        self.layers = nn.LayerList([GraniteMoeHybridDecoderLayer(c, i)
                                    for i in range(c.num_layers)])
        self.norm = _Norm(c, c.hidden_size)

    def kv_cache_spec(self):
        """What EACH layer caches, for ``serving.LLMEngine``: pages of K
        and V at the attention layers' own head count (4 query heads read
        one of them, scores scaled by ``attention_multiplier``), a
        per-slot state at the mamba layers."""
        c = self.config
        kv = {"kind": "kv", "num_heads": c.num_key_value_heads,
              "head_dim": c.head_dim,
              "query_heads": c.num_heads, "scale": c.attention_multiplier}
        state = {"kind": "state",
                 "conv": (c.mamba_d_conv - 1, c.conv_width),
                 "ssm": (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state),
                 "recurrence": "mamba2", "chunk": c.mamba_chunk_size}
        return {"kind": "layers",
                "layers": [dict(state if t == "mamba" else kv)
                           for t in c.layer_types]}

    @property
    def num_expert_layers(self):
        return len(self.layers)

    def forward(self, input_ids, position_ids=None, kv_ctx=None,
                logits_positions=None):
        """``position_ids`` is taken for the engine's sake and not read
        (no layer has positions).  ``logits_positions [b]``: the head
        runs on that one position a row — ``[b, 1, vocab]``."""
        c = self.config
        h = apply(lambda ids, table: table[ids] * jnp.asarray(
            c.embedding_multiplier, table.dtype), input_ids, self.embed)
        for layer in self.layers:
            h = layer(h, kv_ctx=kv_ctx)
        if logits_positions is not None:
            h = apply(lambda v, at: jnp.take_along_axis(
                v, at.astype(jnp.int32)[:, None, None], axis=1),
                h, logits_positions)
        return apply(lambda v, w: jnp.einsum(
            "bsd,vd->bsv", v, w, preferred_element_type=jnp.float32)
            / c.logits_scaling, self.norm(h), self.embed)
