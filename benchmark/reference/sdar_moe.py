"""SDAR-MoE decoder (``model_type: sdar_moe``, as JetLM publishes
SDAR-30B-A3B-Chat) in plain jax.numpy: a Qwen3-MoE decoder adapted to block
diffusion.  No kernels, no cache, no batching; every product goes through
``common.mm`` (float32 at HIGHEST, or the control's precision).  Imports
nothing of the program.

The equations, with ``d`` hidden, ``H`` / ``H_kv`` query / key-value heads of
``d_h``, ``E`` experts of width ``f``, ``k`` experts a token, ``B`` the block
length and ``b(i) = i // B``:

- ``x0 = Embed[ids]``; block ``l``: ``x' = x + Attn(RMSNorm(x))``, ``y = x' +
  Experts(RMSNorm(x'))``; ``logits = RMSNorm(x_L) W_head`` (untied).  The
  logits at position ``i`` are for the token AT ``i``: no shift.
- Attn on ``h``: ``q = h W_q`` as ``H x d_h``, ``k = h W_k`` and ``v = h
  W_v`` as ``H_kv x d_h``; ``q <- RMSNorm_{d_h}(q) * w_qn``, ``k <-
  RMSNorm_{d_h}(k) * w_kn`` a head (one vector of ``d_h`` for all heads),
  THEN rotary in the rotate-half form over all ``d_h`` dimensions (``x cos +
  rotate_half(x) sin``, frequencies ``theta^(-2j/d_h)``) at the absolute
  position; ``score = q . k / sqrt(d_h)``, query head ``i`` reads K/V head
  ``i // (H / H_kv)``; float32 softmax; ``W_o``; no bias.  The mask is
  BLOCK-causal: ``i`` sees ``j`` iff ``b(j) <= b(i)``.
- Experts on ``h``: ``g = f32(h) f32(W_g)`` (``E`` logits, float32 in every
  mode); the PUBLISHED order: ``p = softmax(g)``, the top ``k`` of ``p``, ``w
  = p_top / sum(p_top)`` (``norm_topk_prob``); ``sum_i w_i E_i(h)``, ``E_i(h)
  = (silu(h W1_i) * (h W3_i)) W2_i``; no shared expert, no token dropped.

Generation (the family's ``block_diffusion_generate``): blocks of ``B``
positions start as the mask token ``M``; a denoising pass runs the block
over the clean prefix and itself and fixes some positions; when none is
masked, one more pass stores the block's K/V.  :func:`denoise_logits` is the
published TRAINING layout of block diffusion, which checks one denoising
pass of EVERY block of a sequence in one forward: the clean sequence
followed by a second copy of its positions from ``first`` on in which the
blocks are noisy; clean ``i`` sees clean ``j`` iff ``b(j) <= b(i)``; noisy
``i`` of block ``b`` sees clean ``j`` with ``b(j) < b`` and noisy ``j`` of
block ``b``; the copy's rotary positions are the originals'.  Row ``i`` of
its result equals the naive pass (one forward of the clean prefix and block
``b(first + i)``'s noisy state alone).

Departures from the published code: which positions of a block are masked is
the caller's record (the engine's own, per slot), not a comparison of the
ids with ``M`` — a drawn id equal to ``M`` stays what it is; sampler keys
are counter-based (the program's).  Experts run as a ``lax.scan`` over the
experts with a one-hot weight a token, one expert's float32 copy in flight
at a time, as ``reference/deepseek_v3.py`` runs them.  Leaf layout is the
benchmark's own (``benchmark/models/sdar_moe.py`` maps it onto the
program): ``w13`` holds ``[W1 | W3]`` side by side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def block_length(cfg) -> int:
    return int(cfg["block_length"])


def weight_spec(cfg: dict) -> dict:
    d, H, Hk, dh = _sizes(cfg)
    std = cfg["initializer_range"]
    E, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    spec = {"embed": ((cfg["vocab_size"], d), std),
            "head": ((d, cfg["vocab_size"]), cfg.get("head_std", std)),
            "norm": ((d,), "ones")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        spec.update({
            p + "ln1": ((d,), "ones"), p + "ln2": ((d,), "ones"),
            p + "q": ((d, H * dh), std), p + "k": ((d, Hk * dh), std),
            p + "v": ((d, Hk * dh), std), p + "o": ((H * dh, d), std),
            p + "qn": ((dh,), "ones"), p + "kn": ((dh,), "ones"),
            p + "gate": ((d, E), std),
            p + "experts.w13": ((E, d, 2 * f), std),
            p + "experts.w2": ((E, f, d), std)})
    return spec


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return w.astype(jnp.float32) * x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def rotate_half(x, positions, theta):
    """``x [b, s, heads, d_h]`` at ``positions [s]``: ``x cos + [-x2 | x1]
    sin``, dimension ``j`` and ``j + d_h/2`` turning by ``positions *
    theta^(-2j/d_h)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float64) / d))
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]                       # [s, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def attention(cfg, w, h, positions, seen, mode):
    """``h [b, s, d]`` at rotary ``positions [s]`` under the mask ``seen [s,
    s]`` (row ``i`` sees column ``j``)."""
    d, H, Hk, dh = _sizes(cfg)
    b, s, _ = h.shape
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = C.mm("bsd,dk->bsk", h, w["q"], mode).reshape(b, s, H, dh)
    k = C.mm("bsd,dk->bsk", h, w["k"], mode).reshape(b, s, Hk, dh)
    v = C.mm("bsd,dk->bsk", h, w["v"], mode).reshape(b, s, Hk, dh)
    q = rotate_half(rms_norm(q, w["qn"], eps), positions, theta)
    k = rotate_half(rms_norm(k, w["kn"], eps), positions, theta)
    g = H // Hk
    score = C.mm("bqhgd,bkhd->bhgqk", q.reshape(b, s, Hk, g, dh), k,
                 mode) / np.sqrt(dh)
    score = jnp.where(seen, score, -jnp.inf)
    p = jax.nn.softmax(score, axis=-1)
    o = C.mm("bhgqk,bkhd->bqhgd", p, v, mode).reshape(b, s, H * dh)
    return C.mm("bsk,kd->bsd", o, w["o"], mode)


def route(cfg, w, h):
    """(weights [n, k], experts [n, k]) of tokens ``h [n, d]`` in the
    published order: softmax over all experts, the top ``k``, divided by
    their sum; float32 whatever the mode."""
    g = C.mm("nd,de->ne", h, w["gate"], "f32")
    p = jax.nn.softmax(g, axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    return top, idx


def swiglu(x, w13, w2, mode):
    a = C.mm("...d,df->...f", x, w13, mode)
    gate, up = jnp.split(a, 2, -1)
    return C.mm("...f,fd->...d", jax.nn.silu(gate) * up, w2, mode)


def expert_layer(cfg, w, h, mode="f32"):
    shape = h.shape
    x = h.reshape(-1, shape[-1]).astype(jnp.float32)
    weights, idx = route(cfg, w, x)
    # [n, E]: a token's weight for each expert, 0 where it was not chosen
    dense = jnp.zeros((x.shape[0], cfg["num_experts"]), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].add(weights)

    def body(acc, args):
        w13, w2, col = args
        return acc + swiglu(x, w13, w2, mode) * col[:, None], None

    out, _ = jax.lax.scan(body, jnp.zeros_like(x),
                          (w["experts.w13"], w["experts.w2"], dense.T))
    return out.reshape(shape)


def layer_weights(weights, i):
    p = f"l{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def hidden_states(cfg, weights, ids, positions, seen, mode="f32"):
    x = weights["embed"][ids].astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(weights, i)
        x = x + attention(cfg, w, rms_norm(x, w["ln1"], eps), positions,
                          seen, mode)
        x = x + expert_layer(cfg, w, rms_norm(x, w["ln2"], eps), mode)
    return rms_norm(x, weights["norm"], eps)


def logits(cfg, weights, ids, mode="f32"):
    """The full forward of ``ids [b, s]`` under the block-causal mask."""
    positions = jnp.arange(ids.shape[1])
    at = positions // block_length(cfg)
    seen = at[None, :] <= at[:, None]
    return C.mm("bsd,dv->bsv",
                hidden_states(cfg, weights, ids, positions, seen, mode),
                weights["head"], mode)


def denoise_logits(cfg, weights, clean_ids, noisy_ids, first, mode="f32"):
    """One denoising pass of every block from position ``first`` (a multiple
    of ``B``; may be traced) on, in ONE forward: ``clean_ids [b, n]`` followed
    by ``noisy_ids [b, m]``, the noisy copy of positions ``first .. first + m
    - 1``.  Returns the logits of the copy, ``[b, m, vocab]``."""
    B = block_length(cfg)
    n, m = clean_ids.shape[1], noisy_ids.shape[1]
    positions = jnp.concatenate([jnp.arange(n), first + jnp.arange(m)])
    at = positions // B
    noisy = jnp.arange(n + m) >= n
    # row i sees column j: a clean column of an earlier block for a noisy
    # row, of the same or an earlier block for a clean row; a noisy column
    # of the same block for a noisy row, never for a clean one
    seen = jnp.where(
        noisy[None, :], noisy[:, None] & (at[None, :] == at[:, None]),
        jnp.where(noisy[:, None], at[None, :] < at[:, None],
                  at[None, :] <= at[:, None]))
    ids = jnp.concatenate([clean_ids, noisy_ids], axis=1)
    x = hidden_states(cfg, weights, ids, positions, seen, mode)
    return C.mm("bsd,dv->bsv", x[:, n:], weights["head"], mode)
