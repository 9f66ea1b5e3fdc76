"""Qwen3-Next decoder (``model_type: qwen3_next``; Qwen publishes
Qwen3-Next-80B-A3B-Instruct in this form): pre-norm blocks whose mixer is,
by ``full_attention_interval``, a Gated DeltaNet linear-attention layer or
gated softmax attention, each followed by softmax-routed dropless SwiGLU
experts plus one shared expert scaled by its own sigmoid gate; an untied
head.

Every norm but the delta rule's output norm is ZERO-CENTRED: ``N(x) =
rms(x) * (1 + w)`` in float32, ``w`` initialised to 0.  With ``d`` hidden:
``x0 = Embed[ids]``; layer: ``x' = x + Mixer(N1(x))``, ``y = x' +
MoE(N2(x'))``; ``logits = N(x_L) W_head``.

- gated delta rule (``H_k`` key heads, ``H_v`` value heads, both of ``d_k
  = d_v``; value head ``j`` reads key head ``j // (H_v / H_k)``): ``W_qkvz``
  maps ``d`` to columns grouped by key head, ``[q d_k | k d_k | v r d_v | z
  r d_v]`` a key head with ``r = H_v / H_k``; ``W_ba`` to ``[b r | a r]`` a
  key head.  ``[q | k | v]`` (all heads of each, in that order) pass through
  a causal depthwise convolution of ``K`` taps without bias, then SiLU; ``q``
  and ``k`` are L2-normalised a head (eps 1e-6), ``q`` scaled by ``d_k^-1/2``;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``.  A value
  head's state ``S`` (``d_k x d_v``, float32), a token at a time: ``S <-
  exp(g) S``, ``delta = beta (v - S^T k)``, ``S <- S + k delta^T``, ``o =
  S^T q``.  The output is ``rms(o) w_n silu(z)`` a head (``w_n`` initialised
  to 1), then ``W_out``.  :func:`gated_delta_chunked` runs a prompt in
  chunks (inside a chunk the WY form: one unit-triangular inverse; between
  chunks the state), :func:`gated_delta_step` is the one-token update.
- gated attention (``H`` query heads, ``H_kv`` K/V heads of ``d_h``): ``q =
  h W_q`` as ``H x 2 d_h``, each head ``[query d_h | gate d_h]``; ``k, v`` as
  ``H_kv x d_h``; ``N`` a head on the query and the key (zero-centred, one
  weight for all heads), then rotate-half rotary over the first
  ``partial_rotary_factor d_h`` dimensions alone; causal softmax at
  ``d_h^-1/2``, query head ``i`` reading K/V head ``i // (H / H_kv)``; the
  result times ``sigmoid(gate)``, then ``W_o``.
- experts: the top ``k`` of the router's float32 logits, a softmax over the
  chosen (``distributed.moe.softmax_topk_route``: equal to the published
  softmax over all, top ``k``, divided by their sum); the model may hold a
  contiguous share of each layer's experts (``held``); the shared SwiGLU
  expert's output is multiplied by ``sigmoid(h W_sg)``.

Serving is the path this model is built for.  :meth:`kv_cache_spec`
declares a cache kind PER LAYER: ``kv`` pages of ``H_kv x d_h`` for a full
attention layer, a per-SLOT ``state`` (the convolution's last ``K - 1``
inputs and ``S`` of every value head) for a delta-rule layer, which the
mixer reaches through ``kv_ctx.recur``.  Without a context the forward is
the chunked form from a zero state over the whole sequence.  The plain
reference is benchmark/reference/qwen3_next.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import paddle_tpu
from paddle_tpu import nn
from paddle_tpu.core.dispatch import apply
from paddle_tpu.distributed.moe import DroplessMoELayer
from paddle_tpu.incubate.nn.paged_attention import grouped_causal_attention
from paddle_tpu.models.deepseek_v3 import _Leaves, _mm
from paddle_tpu.models.sdar_moe import rope_rotate_half

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM",
           "gated_delta_chunked", "gated_delta_step"]

_HI = jax.lax.Precision.HIGHEST


class Qwen3NextConfig:
    """Hyperparameters under the engine's names (``num_layers``,
    ``num_heads``, ``max_seq_len``, ``n_routed_experts``) with the
    published key beside each in :meth:`from_published`.  ``layer_types``:
    ``"linear_attention"`` or ``"full_attention"`` a layer.  ``held =
    (first, count)`` is the share of every layer's experts this model holds
    (default all).  ``chunk`` is the prompt's chunk of the delta rule (the
    program's choice, not the checkpoint's).  ``init_weights=False`` makes
    every parameter an empty placeholder for a loader."""

    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 layer_types=None, num_heads=16, num_key_value_heads=2,
                 head_dim=256, partial_rotary_factor=0.25, rope_theta=1e7,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, n_routed_experts=512,
                 num_experts_per_tok=10, held=None, rms_norm_eps=1e-6,
                 max_seq_len=262144, chunk=64, initializer_range=0.02,
                 init_weights=True):
        if layer_types is None:
            layer_types = ["full_attention" if (i + 1) % 4 == 0
                           else "linear_attention" for i in range(num_layers)]
        if len(layer_types) != num_layers:
            raise ValueError(f"layer_types names {len(layer_types)} layers, "
                             f"num_layers is {num_layers}")
        unknown = set(layer_types) - {"linear_attention", "full_attention"}
        if unknown:
            raise ValueError(f"unknown layer type {sorted(unknown)[0]!r}")
        if num_heads % num_key_value_heads:
            raise ValueError("num_heads must divide by num_key_value_heads")
        if linear_num_value_heads % linear_num_key_heads:
            raise ValueError("linear_num_value_heads must divide by "
                             "linear_num_key_heads")
        if shared_expert_intermediate_size % moe_intermediate_size:
            raise NotImplementedError(
                "shared_expert_intermediate_size must be a multiple of "
                "moe_intermediate_size (DroplessMoELayer's shared experts)")
        rotary = int(head_dim * partial_rotary_factor)
        if rotary % 2 or not 0 < rotary <= head_dim:
            raise ValueError(f"partial_rotary_factor {partial_rotary_factor}"
                             f" of {head_dim} is no even rotary width")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.layer_types = list(layer_types)
        self.num_heads = num_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rotary_dim = rotary
        self.rope_theta = rope_theta
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.held = tuple(held) if held is not None else (0, n_routed_experts)
        self.rms_norm_eps = rms_norm_eps
        self.max_seq_len = max_seq_len
        self.chunk = chunk
        self.initializer_range = initializer_range
        self.init_weights = init_weights

    @classmethod
    def from_published(cls, cfg: dict, **kw):
        """From a ``config.json`` of ``model_type: qwen3_next``.  The
        router keeps its published width (``published.num_experts``)
        whatever share ``held`` names; ``delta_chunk`` (no published key)
        is the prompt's chunk of the delta rule."""
        if cfg.get("mlp_only_layers"):
            raise NotImplementedError("mlp_only_layers: every layer is "
                                      "built sparse")
        if cfg.get("decoder_sparse_step", 1) != 1:
            raise NotImplementedError("decoder_sparse_step != 1")
        if cfg.get("use_sliding_window"):
            raise NotImplementedError("use_sliding_window: true")
        if cfg.get("rope_scaling") is not None:
            raise NotImplementedError("rope_scaling")
        if cfg.get("tie_word_embeddings"):
            raise NotImplementedError("tie_word_embeddings: true")
        if cfg.get("attention_bias"):
            raise NotImplementedError("attention_bias: true")
        if not cfg.get("norm_topk_prob", True):
            raise NotImplementedError("norm_topk_prob: false")
        n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
        return cls(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=n,
            layer_types=cfg.get("layer_types") or [
                "full_attention" if (i + 1) % every == 0
                else "linear_attention" for i in range(n)],
            num_heads=cfg["num_attention_heads"],
            num_key_value_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"],
            partial_rotary_factor=cfg["partial_rotary_factor"],
            rope_theta=cfg["rope_theta"],
            linear_num_key_heads=cfg["linear_num_key_heads"],
            linear_num_value_heads=cfg["linear_num_value_heads"],
            linear_key_head_dim=cfg["linear_key_head_dim"],
            linear_value_head_dim=cfg["linear_value_head_dim"],
            linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
            moe_intermediate_size=cfg["moe_intermediate_size"],
            shared_expert_intermediate_size=cfg[
                "shared_expert_intermediate_size"],
            n_routed_experts=cfg.get("published", {}).get(
                "num_experts", cfg["num_experts"]),
            num_experts_per_tok=cfg["num_experts_per_tok"],
            held=cfg.get("held"), rms_norm_eps=cfg["rms_norm_eps"],
            max_seq_len=cfg["max_position_embeddings"],
            chunk=cfg.get("delta_chunk", 64), **kw)

    @property
    def key_dim(self):
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_width(self):
        """Channels the convolution runs over: ``[q | k | v]``."""
        return 2 * self.key_dim + self.value_dim


def _centred_rms(x, w, eps):
    """``rms(x) * (1 + w)`` in float32, rounded to ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True)
                             + eps)
    return (out * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


class _CentredNorm(_Leaves):
    def __init__(self, config, width):
        super().__init__(config)
        self.weight = self.leaf((width,), 0.0)

    def forward(self, x):
        return apply(lambda v, w: _centred_rms(v, w, self._cfg.rms_norm_eps),
                     x, self.weight)


# ------------------------------------------------------- the delta rule
def gated_delta_chunked(q, k, v, g, beta, chunk, lens=None, state=None):
    """The gated delta rule over a whole prompt, chunk by chunk.  ``q`` /
    ``k [b, s, H, d_k]`` (normalised, ``q`` scaled; one key per VALUE
    head), ``v [b, s, H, d_v]``, ``g`` / ``beta [b, s, H]`` (the log decay
    and the write strength).  ``state [b, H, d_k, d_v]`` is the state
    before the first position (zero when None).  Positions at or past
    ``lens [b]`` get ``g = 0`` and ``beta = 0``: they leave the state as it
    is, so a prompt ends in the same state in every padded length.  Returns
    (``o [b, s, H, d_v]`` float32, final state ``[b, H, d_k, d_v]``
    float32).

    Inside a chunk, with ``G_t`` the running sum of ``g`` from the chunk's
    start and ``S0`` the state entering it, the writes ``u_t`` (``S_t = e^G_t
    S0 + sum_{j<=t} e^(G_t - G_j) k_j u_j^T``) solve ``(I + A) U = beta V -
    (beta e^G K) S0`` with ``A_tj = beta_t (k_t . k_j) e^(G_t - G_j)`` for
    ``j < t``: ONE unit-lower-triangular inverse a chunk
    (:func:`_unit_lower_inverse`), applied to the two parts of the right
    side, neither of which depends on ``S0``, for every chunk at once;
    ``o_t = e^G_t S0^T q_t + sum_{j<=t} e^(G_t - G_j) (q_t . k_j) u_j``; the
    chunk hands on ``e^G_Q S0 + sum_j e^(G_Q - G_j) k_j u_j^T``.  Everything
    is float32 at full precision.
    """
    f32 = jnp.float32
    b, s, H, dk = k.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if lens is not None:
        real = (jnp.arange(s, dtype=jnp.int32)[None, :]
                < lens.astype(jnp.int32)[:, None])[..., None]
        g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    Q = min(chunk, s)
    pad = -s % Q
    if pad:
        q, k, v, g, beta = (jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (
            x.ndim - 2)) for x in (q, k, v, g, beta))
    nc = (s + pad) // Q

    def chunks(x):       # [b, nc * Q, H, ...] -> [b, H, nc, Q, ...]
        return jnp.moveaxis(x.reshape((b, nc, Q, H) + x.shape[3:]), 3, 1)

    qc, kc, vc, bc = chunks(q), chunks(k), chunks(v), chunks(beta)
    G = jnp.cumsum(chunks(g), axis=-1)                       # [b,H,nc,Q]
    causal = jnp.tril(jnp.ones((Q, Q), jnp.bool_))
    decay = jnp.exp(jnp.where(causal, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                     # [..., Q, Q]
    eye = jnp.eye(Q, dtype=f32)
    A = bc[..., :, None] * jnp.einsum(
        "...td,...jd->...tj", kc, kc, precision=_HI) * decay * (1.0 - eye)
    rhs = jnp.concatenate([vc * bc[..., None],
                           kc * (bc * jnp.exp(G))[..., None]], -1)
    sol = jnp.einsum("...tj,...jc->...tc", _unit_lower_inverse(A), rhs,
                     precision=_HI)
    w_v, w_k = sol[..., :dv], sol[..., dv:]
    qk = jnp.einsum("...td,...jd->...tj", qc, kc, precision=_HI) * decay
    q_in = qc * jnp.exp(G)[..., None]
    k_out = kc * jnp.exp(G[..., -1:] - G)[..., None]
    hand_on = jnp.exp(G[..., -1])                            # [b,H,nc]

    def body(S, inp):
        wv, wk, qk_c, qi, ko, ho = inp
        u = wv - jnp.einsum("bhtk,bhkv->bhtv", wk, S, precision=_HI)
        o = (jnp.einsum("bhtk,bhkv->bhtv", qi, S, precision=_HI)
             + jnp.einsum("bhtj,bhjv->bhtv", qk_c, u, precision=_HI))
        S = S * ho[..., None, None] + jnp.einsum(
            "bhtk,bhtv->bhkv", ko, u, precision=_HI)
        return S, o

    S0 = (jnp.zeros((b, H, dk, dv), f32) if state is None
          else state.astype(f32))
    S, o = jax.lax.scan(body, S0, tuple(
        jnp.moveaxis(x, 2, 0) for x in (w_v, w_k, qk, q_in, k_out,
                                        hand_on)))
    # o [nc, b, H, Q, dv] -> [b, nc * Q, H, dv]
    o = jnp.moveaxis(o, 0, 2).reshape(b, H, nc * Q, dv)
    return jnp.swapaxes(o, 1, 2)[:, :s], S


def _unit_lower_inverse(A):
    """``(I + A)^-1`` of a strictly lower triangular ``A [..., Q, Q]``:
    ``N = -A`` is nilpotent (``N^Q = 0``), so the inverse ``sum_k N^k`` is
    the product ``(I + N)(I + N^2)(I + N^4)...`` — ``2 log2 Q`` products of
    ``Q x Q`` matrices, float32 at full precision, no solver call."""
    Q = A.shape[-1]
    eye = jnp.eye(Q, dtype=A.dtype)
    power = -A
    inv = eye + power
    reach = 2                       # inv holds sum_{k < reach} N^k
    while reach < Q:
        power = jnp.einsum("...ij,...jk->...ik", power, power, precision=_HI)
        inv = jnp.einsum("...ij,...jk->...ik", inv, eye + power,
                         precision=_HI)
        reach *= 2
    return inv


def gated_delta_step(S, q, k, v, g, beta):
    """One token a row: ``S [b, H, d_k, d_v]`` float32, ``q`` / ``k [b, H,
    d_k]``, ``v [b, H, d_v]``, ``g`` / ``beta [b, H]``.  Returns (``o [b, H,
    d_v]`` float32, the advanced state)."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    S = S * jnp.exp(g.astype(jnp.float32))[..., None, None]
    delta = beta.astype(jnp.float32)[..., None] * (
        vf - jnp.einsum("bhkv,bhk->bhv", S, kf, precision=_HI))
    S = S + kf[..., :, None] * delta[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", S, qf, precision=_HI), S


def _l2(x, eps=1e-6):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(jnp.square(xf), -1, keepdims=True)
                              + eps)


class GatedDeltaNet(_Leaves):
    """Leaves: ``qkvz [d, 2 H_k d_k + 2 H_v d_v]`` and ``ba [d, 2 H_v]``
    (columns grouped by key head), ``conv_w [K, 2 H_k d_k + H_v d_v]`` (tap
    ``K - 1`` meets the current position; no bias), ``dt_bias`` / ``A_log
    [H_v]``, ``norm [d_v]``, ``out_proj [H_v d_v, d]``."""

    def __init__(self, config):
        super().__init__(config)
        c = config
        std = c.initializer_range
        Hv = c.linear_num_value_heads
        self.qkvz = self.leaf(
            (c.hidden_size, 2 * c.key_dim + 2 * c.value_dim), std)
        self.ba = self.leaf((c.hidden_size, 2 * Hv), std)
        self.conv_w = self.leaf((c.linear_conv_kernel_dim, c.conv_width),
                                std)
        self.dt_bias = self.leaf((Hv,), "ones")
        self.A_log = self.leaf((Hv,), 0.0)
        self.norm = self.leaf((c.linear_value_head_dim,), "ones")
        self.out_proj = self.leaf((c.value_dim, c.hidden_size), std)

    def _split(self, h, w_qkvz, w_ba, dt_bias, A_log):
        """``h [b, s, d]`` -> (``[q | k | v]`` before the convolution ``[b,
        s, C]``, z ``[b, s, H_v, d_v]``, g and beta ``[b, s, H_v]``
        float32)."""
        c = self._cfg
        b, s, _ = h.shape
        Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv, r = c.linear_key_head_dim, c.linear_value_head_dim, \
            Hv // Hk
        qkvz = _mm(h, w_qkvz).reshape(b, s, Hk, 2 * dk + 2 * r * dv)
        q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], -1)
        ba = _mm(h, w_ba).reshape(b, s, Hk, 2 * r).astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :r]).reshape(b, s, Hv)
        g = -jnp.exp(A_log.astype(jnp.float32)) * jax.nn.softplus(
            ba[..., r:].reshape(b, s, Hv) + dt_bias.astype(jnp.float32))
        qkv = jnp.concatenate([x.reshape(b, s, -1) for x in (q, k, v)], -1)
        return qkv, z.reshape(b, s, Hv, dv), g, beta

    def _heads(self, act):
        """The convolution's output ``[..., C]`` -> q, k (normalised, q
        scaled, one a VALUE head) ``[..., H_v, d_k]`` and v ``[..., H_v,
        d_v]``."""
        c = self._cfg
        Hk, Hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        lead = act.shape[:-1]
        q, k, v = jnp.split(act, [c.key_dim, 2 * c.key_dim], -1)
        q = _l2(q.reshape(lead + (Hk, dk))) * dk ** -0.5
        k = _l2(k.reshape(lead + (Hk, dk)))
        # value head j reads key head j // (H_v / H_k)
        q, k = (jnp.repeat(x, Hv // Hk, axis=-2) for x in (q, k))
        return q, k, v.reshape(lead + (Hv, dv))

    def _finish(self, o, z, w_n, w_out):
        """``rms(o) w_n silu(z)`` a value head, then ``W_out``."""
        c = self._cfg
        y = o.astype(jnp.float32)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + c.rms_norm_eps)
        y = y * w_n.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        return _mm(y.reshape(z.shape[:-2] + (-1,)).astype(z.dtype), w_out)

    def _conv(self, taps, conv_w, dtype):
        w = conv_w.astype(jnp.float32)
        return jax.nn.silu(sum(t.astype(jnp.float32) * w[j]
                               for j, t in enumerate(taps))).astype(dtype)

    def _prompt(self, lens, h, w_qkvz, w_ba, conv_w, dt_bias, A_log, w_n,
                w_out):
        """The whole (padded) prompt ``h [b, s, d]`` from a zero state.
        Returns (out, window ``[b, K - 1, C]``: the convolution's inputs at
        the prompt's real end, final state)."""
        c = self._cfg
        b, s, _ = h.shape
        K = c.linear_conv_kernel_dim
        qkv, z, g, beta = self._split(h, w_qkvz, w_ba, dt_bias, A_log)
        padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
        q, k, v = self._heads(self._conv(
            [padded[:, j:j + s] for j in range(K)], conv_w, h.dtype))
        o, S = gated_delta_chunked(q, k, v, g, beta, c.chunk, lens)
        ends = jnp.full((b,), s, jnp.int32) if lens is None else lens
        # padded[len : len + K - 1] are positions len - K + 1 .. len - 1
        window = jax.vmap(lambda p, at: jax.lax.dynamic_slice_in_dim(
            p, at, K - 1, axis=0))(padded, ends.astype(jnp.int32))
        return self._finish(o, z, w_n, w_out), window, S

    def _token(self, window, S, h, w_qkvz, w_ba, conv_w, dt_bias, A_log,
               w_n, w_out):
        """One token a row ``h [b, 1, d]`` from each row's own state."""
        c = self._cfg
        qkv, z, g, beta = self._split(h, w_qkvz, w_ba, dt_bias, A_log)
        taps = jnp.concatenate([window, qkv.astype(window.dtype)], axis=1)
        q, k, v = self._heads(self._conv(
            [taps[:, j] for j in range(c.linear_conv_kernel_dim)], conv_w,
            h.dtype))
        o, S = gated_delta_step(S, q, k, v, g[:, 0], beta[:, 0])
        return self._finish(o[:, None], z, w_n, w_out), taps[:, 1:], S

    def forward(self, hidden, kv_ctx=None):
        leaves = (self.qkvz, self.ba, self.conv_w, self.dt_bias, self.A_log,
                  self.norm, self.out_proj)
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


class GatedAttention(_Leaves):
    """Leaves: ``q [d, H 2 d_h]`` (a head ``[query | gate]``), ``k`` / ``v
    [d, H_kv d_h]``, ``q_norm`` / ``k_norm [d_h]`` (zero-centred), ``o [H
    d_h, d]``; no bias."""

    def __init__(self, config):
        super().__init__(config)
        c = config
        std = c.initializer_range
        kv = c.num_key_value_heads * c.head_dim
        self.q = self.leaf((c.hidden_size, 2 * c.num_heads * c.head_dim),
                           std)
        self.k = self.leaf((c.hidden_size, kv), std)
        self.v = self.leaf((c.hidden_size, kv), std)
        self.q_norm = self.leaf((c.head_dim,), 0.0)
        self.k_norm = self.leaf((c.head_dim,), 0.0)
        self.o = self.leaf((c.num_heads * c.head_dim, c.hidden_size), std)

    def _rotate(self, x, pos):
        """Rotary over the first ``rotary_dim`` dimensions of a head; the
        rest pass as they are."""
        r = self._cfg.rotary_dim
        return jnp.concatenate(
            [rope_rotate_half(x[..., :r], pos, self._cfg.rope_theta),
             x[..., r:]], -1)

    def forward(self, hidden, position_ids, kv_ctx=None):
        c = self._cfg
        b, s = hidden.shape[0], hidden.shape[1]
        H, Hk, dh, eps = (c.num_heads, c.num_key_value_heads, c.head_dim,
                          c.rms_norm_eps)

        def project(h, pos, wq, wk, nq, nk):
            qg = _mm(h, wq).reshape(b, s, H, 2 * dh)
            q = self._rotate(_centred_rms(qg[..., :dh], nq, eps), pos)
            k = self._rotate(_centred_rms(
                _mm(h, wk).reshape(b, s, Hk, dh), nk, eps), pos)
            return q, k, qg[..., dh:]

        q, k, gate = apply(project, hidden, position_ids, self.q, self.k,
                           self.q_norm, self.k_norm)
        v = apply(_mm, hidden, self.v).reshape([b, s, Hk, dh])
        if kv_ctx is not None:
            out = kv_ctx.attend(q, k, v)
        else:
            out = apply(lambda q, k, v: grouped_causal_attention(
                q, k, v, dh ** -0.5), q, k, v)
        return apply(lambda o, gt, w: _mm(
            (o.astype(jnp.float32) * jax.nn.sigmoid(gt.astype(jnp.float32))
             ).astype(o.dtype).reshape(b, s, H * dh), w), out, gate, self.o)


class Qwen3NextDecoderLayer(_Leaves):
    def __init__(self, config, layer_idx):
        super().__init__(config)
        c = config
        self.is_state = c.layer_types[layer_idx] == "linear_attention"
        self.ln1 = _CentredNorm(c, c.hidden_size)
        self.mixer = GatedDeltaNet(c) if self.is_state else GatedAttention(c)
        self.ln2 = _CentredNorm(c, c.hidden_size)
        self.mlp = DroplessMoELayer(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok, route="softmax", held=c.held,
            n_shared=(c.shared_expert_intermediate_size
                      // c.moe_intermediate_size), shared_gate=True,
            initializer_range=c.initializer_range, make_parameter=self.leaf)

    def forward(self, x, position_ids, kv_ctx=None):
        h = self.ln1(x)
        x = x + (self.mixer(h, kv_ctx=kv_ctx) if self.is_state
                 else self.mixer(h, position_ids, kv_ctx=kv_ctx))
        x = x + self.mlp(self.ln2(x))
        if kv_ctx is not None:
            kv_ctx.note_expert_counts(self.mlp.last_counts._value)
        return x


class Qwen3NextForCausalLM(_Leaves):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__(config)
        self.config = config
        c = config
        self.embed = self.leaf((c.vocab_size, c.hidden_size),
                               c.initializer_range)
        self.layers = nn.LayerList([Qwen3NextDecoderLayer(c, i)
                                    for i in range(c.num_layers)])
        self.norm = _CentredNorm(c, c.hidden_size)
        self.head = self.leaf((c.hidden_size, c.vocab_size),
                              c.initializer_range)

    def kv_cache_spec(self):
        """What EACH layer caches, for ``serving.LLMEngine``: pages of K
        and V at the full layers' ``H_kv`` heads (``H`` query heads read
        them at ``d_h^-1/2``), a per-slot state at the delta-rule layers:
        the convolution's last ``K - 1`` inputs and every value head's
        float32 ``d_k x d_v`` matrix, named as the recurrence it holds,
        with the chunk its prompts run in."""
        c = self.config
        kv = {"kind": "kv", "num_heads": c.num_key_value_heads,
              "head_dim": c.head_dim, "query_heads": c.num_heads,
              "scale": c.head_dim ** -0.5}
        state = {"kind": "state",
                 "conv": (c.linear_conv_kernel_dim - 1, c.conv_width),
                 "ssm": (c.linear_num_value_heads, c.linear_key_head_dim,
                         c.linear_value_head_dim),
                 "recurrence": "gated_delta", "chunk": c.chunk}
        return {"kind": "layers",
                "layers": [dict(state if t == "linear_attention" else kv)
                           for t in c.layer_types]}

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
