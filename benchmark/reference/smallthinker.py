"""SmallThinker decoder (as PowerInfer publishes SmallThinker-21BA3B-Instruct)
in plain jax.numpy: full attention without positions and sliding-window
attention with rotary, layer by layer as the published layouts say, and
softmax-routed ReGLU experts whose router reads the attention block's input.
No kernels, no cache, no batching; every product goes through ``common.mm``
(float32 at HIGHEST, or the control's precision).  Imports nothing of the
program.

The equations, with ``d`` hidden, ``H`` / ``H_kv`` query / key-value heads of
``d_h``, ``E`` experts of width ``f``, ``k`` experts a token, ``W`` the
window:

- ``x0 = Embed[ids]``; layer ``l``: ``a = RMSNorm(x)``; ``x' = x +
  Attn_l(a)``; ``b = RMSNorm(x')``; ``y = x' + Experts(b; routed by a)``;
  ``logits = RMSNorm(x_L) W_head`` (untied).
- Attn on ``a``: ``q = a W_q`` as ``H x d_h``, ``k = a W_k`` and ``v = a
  W_v`` as ``H_kv x d_h``, no bias, no QK-norm; a FULL layer (layout flag
  0) rotates nothing and ``i`` sees ``j`` iff ``j <= i``; a WINDOW layer
  (flag 1) rotates ``q`` and ``k`` in the rotate-half form at the absolute
  position (``theta^(-2j/d_h)``) and ``i`` sees ``j`` iff ``i - W < j <=
  i``; ``score = q . k / sqrt(d_h)``, query head ``i`` reads K/V head ``i //
  (H / H_kv)``; float32 softmax; ``W_o``.
- Experts: ``g = f32(a) f32(W_r)`` (``E`` logits, float32 in every mode);
  the PUBLISHED order: ``p = softmax(g)``, the top ``k`` of ``p``, ``w =
  p_top / sum(p_top)``; ``sum_i w_i E_i(b)``, ``E_i(b) = (relu(b W1_i) * (b
  W3_i)) W2_i``; no shared expert, no secondary experts, no token dropped.

Computed in blocks so that a 16,384-position sequence fits one chip beside
nothing else: attention a block of queries at a time (each against the
whole sequence, masked), the experts a block of tokens at a time as a
``lax.scan`` over the experts with a one-hot weight a token (one expert's
float32 copy in flight), and the head only at the positions asked for
(:func:`logits_at`).  Leaf layout is the benchmark's own
(``benchmark/models/smallthinker.py`` maps it onto the program): ``w13``
holds ``[W1 | W3]`` side by side.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C
from benchmark.reference.sdar_moe import layer_weights, rms_norm, rotate_half

QUERY_BLOCK = 512
TOKEN_BLOCK = 4096


def _sizes(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def window_layers(cfg) -> list:
    """One bool a layer: True for a sliding-window layer with rotary."""
    n = cfg["num_hidden_layers"]
    return [bool(f) for f in cfg["sliding_window_layout"][:n]]


def weight_spec(cfg: dict) -> dict:
    d, H, Hk, dh = _sizes(cfg)
    std = cfg["initializer_range"]
    E, f = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    spec = {"embed": ((cfg["vocab_size"], d), std),
            "head": ((d, cfg["vocab_size"]), std),
            "norm": ((d,), "ones")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        spec.update({
            p + "ln1": ((d,), "ones"), p + "ln2": ((d,), "ones"),
            p + "q": ((d, H * dh), std), p + "k": ((d, Hk * dh), std),
            p + "v": ((d, Hk * dh), std), p + "o": ((H * dh, d), std),
            p + "gate": ((d, E), std),
            p + "experts.w13": ((E, d, 2 * f), std),
            p + "experts.w2": ((E, f, d), std)})
    return spec


def _blocks(n, size):
    size = min(size, n)
    if n % size:
        raise ValueError(f"{n} positions do not split into blocks of {size}")
    return n // size, size


def attention(cfg, w, a, window, mode):
    """``a [s, d]`` of one sequence -> ``[s, d]``; ``window`` None for a
    full layer without positions, else the window of a rotary layer."""
    d, H, Hk, dh = _sizes(cfg)
    s = a.shape[0]
    q = C.mm("sd,dk->sk", a, w["q"], mode).reshape(s, H, dh)
    k = C.mm("sd,dk->sk", a, w["k"], mode).reshape(s, Hk, dh)
    v = C.mm("sd,dk->sk", a, w["v"], mode).reshape(s, Hk, dh)
    positions = jnp.arange(s)
    if window is not None:
        theta = cfg["rope_theta"]
        q = rotate_half(q[None], positions, theta)[0]
        k = rotate_half(k[None], positions, theta)[0]
    g = H // Hk
    n, size = _blocks(s, QUERY_BLOCK)

    def block(i):
        rows = i * size + jnp.arange(size)
        qb = jax.lax.dynamic_slice_in_dim(q, i * size, size).reshape(
            size, Hk, g, dh)
        score = C.mm("qhgd,khd->hgqk", qb, k, mode) / np.sqrt(dh)
        gap = rows[:, None] - positions[None, :]
        seen = gap >= 0
        if window is not None:
            seen = seen & (gap < window)
        p = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
        return C.mm("hgqk,khd->qhgd", p, v, mode).reshape(size, H * dh)

    o = jax.lax.map(block, jnp.arange(n)).reshape(s, H * dh)
    return C.mm("sk,kd->sd", o, w["o"], mode)


def route(cfg, w, a):
    """(weights [n, k], experts [n, k]) of tokens ``a [n, d]`` in the
    published order: softmax over all experts, the top ``k``, divided by
    their sum; float32 whatever the mode."""
    g = C.mm("nd,de->ne", a, w["gate"], "f32")
    p = jax.nn.softmax(g, axis=-1)
    top, idx = jax.lax.top_k(p, cfg["moe_num_active_primary_experts"])
    return top / jnp.sum(top, -1, keepdims=True), idx


def reglu(x, w13, w2, mode):
    h = C.mm("nd,df->nf", x, w13, mode)
    gate, up = jnp.split(h, 2, -1)
    return C.mm("nf,fd->nd", jax.nn.relu(gate) * up, w2, mode)


def expert_layer(cfg, w, b, a, mode="f32"):
    """The experts on ``b [s, d]``, routed by ``a [s, d]``."""
    s, E = b.shape[0], cfg["moe_num_primary_experts"]
    n, size = _blocks(s, TOKEN_BLOCK)

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(b, i * size, size)
        weights, idx = route(cfg, w,
                             jax.lax.dynamic_slice_in_dim(a, i * size, size))
        # [size, E]: a token's weight for each expert, 0 where not chosen
        dense = jnp.zeros((size, E), jnp.float32).at[
            jnp.arange(size)[:, None], idx].add(weights)

        def body(acc, args):
            w13, w2, col = args
            return acc + reglu(xb, w13, w2, mode) * col[:, None], None

        out, _ = jax.lax.scan(body, jnp.zeros_like(xb),
                              (w["experts.w13"], w["experts.w2"], dense.T))
        return out

    return jax.lax.map(block, jnp.arange(n)).reshape(s, -1)


def hidden_states(cfg, weights, ids, mode="f32"):
    """The final-normed hidden states of ONE sequence ``ids [s]``."""
    x = weights["embed"][ids].astype(jnp.float32)
    eps, W = cfg["rms_norm_eps"], cfg["sliding_window_size"]
    for i, windowed in enumerate(window_layers(cfg)):
        w = layer_weights(weights, i)
        a = rms_norm(x, w["ln1"], eps)
        x = x + attention(cfg, w, a, W if windowed else None, mode)
        x = x + expert_layer(cfg, w, rms_norm(x, w["ln2"], eps), a, mode)
    return rms_norm(x, weights["norm"], eps)


def logits_at(cfg, weights, ids, positions, mode="f32"):
    """``ids [b, s]``, ``positions [b, p]`` -> the logits at those positions
    alone, ``[b, p, vocab]``."""
    def one(seq, at):
        h = hidden_states(cfg, weights, seq, mode)[at]
        return C.mm("pd,dv->pv", h, weights["head"], mode)
    return jnp.stack([one(ids[i], positions[i])
                      for i in range(ids.shape[0])])


def logits(cfg, weights, ids, mode="f32"):
    """The full forward of ``ids [b, s]`` -> ``[b, s, vocab]``."""
    s = ids.shape[1]
    return logits_at(cfg, weights, ids,
                     jnp.broadcast_to(jnp.arange(s), ids.shape), mode)
