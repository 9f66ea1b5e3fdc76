"""DeepSeek-V3-style decoder (``model_type: deepseek_v3``, as Kanana-2-30B-A3B
publishes it) in plain jax.numpy: latent attention (MLA) without a query
low-rank, a leading dense SwiGLU layer, then sigmoid-routed experts with
shared experts.  No kernels, no cache, no batching; every product goes
through ``common.mm`` (float32 at HIGHEST, or the control's precision).

The equations, with ``d`` hidden, ``H`` heads, ``d_n`` / ``d_r`` the
no-position / rotary part of a query-key head, ``d_v`` the value head,
``r`` the latent rank, ``E`` experts, ``k`` experts a token, ``s`` the
routed scaling factor:

- Block ``l``: ``x' = x + MLA(RMSNorm(x))``, ``y = x' + FFN_l(RMSNorm(x'))``;
  ``FFN_l`` is a SwiGLU MLP of ``intermediate_size`` for ``l <
  first_k_dense_replace`` and the expert layer after.  ``RMSNorm(x) = w * x
  / sqrt(mean(x^2) + eps)``.  Final RMSNorm, then ``logits = x W_head``
  (untied).
- MLA on ``h = RMSNorm(x)``: ``q = h W_q`` as ``H x (d_n + d_r)``, split
  ``q_n | q_r``; ``[c | k_r] = h W_kva`` (``d -> r + d_r``); ``c =
  RMSNorm_r(c)``; ``q_r`` and ``k_r`` rotated by RoPE(theta, ``d_r`` wide)
  in the INTERLEAVED pairing ``(x0,x1),(x2,x3)...``: the pairs are
  de-interleaved to ``[x0,x2,.. | x1,x3,..]`` and the halves rotated (the
  published ``apply_rotary_pos_emb_interleave``; the rotated vector stays
  in the de-interleaved order, the same for q and k, so their product is
  that of the pairwise rotation).  ``k_r`` is one head shared by all.
  ``[k_n | v] = c W_kvb`` as ``H x (d_n + d_v)``; ``score = (q_n . k_n +
  q_r . k_r) / sqrt(d_n + d_r)``, causal, softmax in float32; ``o_h =
  sum_j p_j v_j``; ``out = concat_h(o_h) W_o``.  What a cache would hold of
  a token is ``[c | k_r]`` after the norm and the rotation
  (:func:`latent_rows`, which the tests compare the program's pool with).
- Expert layer on ``h = RMSNorm(x')``: ``g = f32(h) f32(W_g)`` (the router
  in float32 as published, in every precision mode); ``sc = sigmoid(g)``;
  the top ``k`` of ``sc + b`` (``b`` = ``e_score_correction_bias``, for the
  choice only; ``n_group = topk_group = 1`` keeps every expert in play);
  ``w_i = s * sc_i / (sum_top sc + 1e-20)``; ``out = sum_top w_i E_i(h) +
  Shared(h)``, ``E_i(h) = (silu(h W1_i) * (h W3_i)) W2_i``, ``Shared`` a
  SwiGLU MLP of ``n_shared_experts x moe_intermediate_size``.  No token is
  dropped.  :func:`expert_layer` takes the contiguous range of experts
  held (a chip's share): it routes over all ``E`` and adds only the held
  experts' terms.

Departures from the published code: none in the equations.  Experts run as
a ``lax.scan`` over the experts held with a one-hot weight a token (every
expert on every token), one expert's float32 copy in flight at a time, so
the reference fits beside the bf16 weights.  ``n_group > 1`` is refused.
Leaf layout is the benchmark's own (``benchmark/models/deepseek_v3.py``
maps it onto the program): ``w13`` holds ``[W1 | W3]`` side by side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"])


def is_dense(cfg, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"]


def weight_spec(cfg: dict) -> dict:
    d, H, dn, dr, dv, r = _sizes(cfg)
    std = cfg["initializer_range"]
    E, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the reference routes with n_group = topk_group = 1")
    spec = {"embed": ((cfg["vocab_size"], d), std),
            "head": ((d, cfg["vocab_size"]), std),
            "norm": ((d,), "ones")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        spec.update({
            p + "ln1": ((d,), "ones"), p + "ln2": ((d,), "ones"),
            p + "q": ((d, H * (dn + dr)), std),
            p + "kva": ((d, r + dr), std), p + "kvn": ((r,), "ones"),
            p + "kvb": ((r, H * (dn + dv)), std),
            p + "o": ((H * dv, d), std)})
        if is_dense(cfg, i):
            inter = cfg["intermediate_size"]
            spec.update({p + "mlp.w13": ((d, 2 * inter), std),
                         p + "mlp.w2": ((inter, d), std)})
        else:
            spec.update({
                p + "gate": ((d, E), std),
                p + "gate_bias": ((E,), cfg["e_score_correction_bias_std"]),
                p + "experts.w13": ((E, d, 2 * f), std),
                p + "experts.w2": ((E, f, d), std),
                p + "shared.w13": ((d, 2 * fs), std),
                p + "shared.w2": ((fs, d), std)})
    return spec


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return w.astype(jnp.float32) * x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def rope_interleave(x, positions, theta):
    """x [..., s, heads, d_r] at ``positions [s]``: de-interleave the
    pairs, then rotate the halves."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]                       # [s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def swiglu(x, w13, w2, mode):
    a = C.mm("...d,df->...f", x, w13, mode)
    gate, up = jnp.split(a, 2, -1)
    return C.mm("...f,fd->...d", jax.nn.silu(gate) * up, w2, mode)


def latent_rows(cfg, w, h, positions, mode="f32"):
    """``[c | k_r]`` of every token, ``[b, s, r + d_r]``: what the cache
    holds (after the norm and the rotation)."""
    d, H, dn, dr, dv, r = _sizes(cfg)
    ckr = C.mm("bsd,dk->bsk", h, w["kva"], mode)
    c = rms_norm(ckr[..., :r], w["kvn"], cfg["rms_norm_eps"])
    k_r = rope_interleave(ckr[..., None, r:], positions,
                          cfg["rope_theta"])[..., 0, :]
    return jnp.concatenate([c, k_r], -1)


def mla(cfg, w, h, mode):
    d, H, dn, dr, dv, r = _sizes(cfg)
    b, s, _ = h.shape
    positions = jnp.arange(s)
    q = C.mm("bsd,dk->bsk", h, w["q"], mode).reshape(b, s, H, dn + dr)
    q_r = rope_interleave(q[..., dn:], positions, cfg["rope_theta"])
    rows = latent_rows(cfg, w, h, positions, mode)
    c, k_r = rows[..., :r], rows[..., r:]
    kv = C.mm("bsr,rk->bsk", c, w["kvb"], mode).reshape(b, s, H, dn + dv)
    k_n, v = kv[..., :dn], kv[..., dn:]
    score = (C.mm("bqhd,bkhd->bhqk", q[..., :dn], k_n, mode)
             + C.mm("bqhd,bkd->bhqk", q_r, k_r, mode)) / np.sqrt(dn + dr)
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    p = jax.nn.softmax(score, axis=-1)
    o = C.mm("bhqk,bkhd->bqhd", p, v, mode).reshape(b, s, H * dv)
    return C.mm("bsk,kd->bsd", o, w["o"], mode)


def route(cfg, w, h):
    """(weights [n, k], experts [n, k]) of tokens ``h [n, d]``; float32
    whatever the mode."""
    g = C.mm("nd,de->ne", h, w["gate"], "f32")
    sc = jax.nn.sigmoid(g)
    _, idx = jax.lax.top_k(sc + w["gate_bias"].astype(jnp.float32),
                           cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(sc, idx, -1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, -1, keepdims=True) + 1e-20)
    return top * cfg["routed_scaling_factor"], idx


def expert_layer(cfg, w, h, mode="f32", held=None, shared=True):
    """The expert layer on ``h [..., d]``.  ``held = (first, count)`` keeps
    only that range of routed experts' terms (a chip's share of the layer;
    the router still sees all of them); ``shared`` adds the shared
    experts."""
    shape = h.shape
    x = h.reshape(-1, shape[-1]).astype(jnp.float32)
    weights, idx = route(cfg, w, x)
    E = cfg["n_routed_experts"]
    first, count = held if held is not None else (0, E)
    # [n, E]: a token's weight for each expert, 0 where it was not chosen
    dense = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].add(weights)

    def body(acc, args):
        w13, w2, col = args
        return acc + swiglu(x, w13, w2, mode) * col[:, None], None

    out, _ = jax.lax.scan(
        body, jnp.zeros_like(x),
        (w["experts.w13"][first:first + count],
         w["experts.w2"][first:first + count],
         dense.T[first:first + count]))
    if shared:
        out = out + swiglu(x, w["shared.w13"], w["shared.w2"], mode)
    return out.reshape(shape)


def layer_weights(weights, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def hidden_states(cfg, weights, ids, mode="f32"):
    x = weights["embed"][ids].astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(weights, i)
        x = x + mla(cfg, w, rms_norm(x, w["ln1"], eps), mode)
        h = rms_norm(x, w["ln2"], eps)
        if is_dense(cfg, i):
            x = x + swiglu(h, w["mlp.w13"], w["mlp.w2"], mode)
        else:
            x = x + expert_layer(cfg, w, h, mode)
    return rms_norm(x, weights["norm"], eps)


def logits(cfg, weights, ids, mode="f32"):
    return C.mm("bsd,dv->bsv", hidden_states(cfg, weights, ids, mode),
                weights["head"], mode)


def loss(cfg, weights, ids, labels, mode="f32"):
    """Mean next-token cross entropy over every position."""
    return C.cross_entropy_mean(logits(cfg, weights, ids, mode), labels)
