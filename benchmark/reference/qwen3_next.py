"""Qwen3-Next decoder (``model_type: qwen3_next``, as Qwen publishes
Qwen3-Next-80B-A3B-Instruct) in plain jax.numpy: by
``full_attention_interval`` a Gated DeltaNet mixer or gated softmax
attention, then softmax-routed SwiGLU experts plus a sigmoid-gated shared
expert in every layer, an untied head.  No kernels, no cache, no batching,
no chunks; every product of a weight goes through ``common.mm`` (float32 at
HIGHEST, or the control's precision).  Imports nothing of the program.

With ``d`` hidden and every norm but the delta rule's ZERO-CENTRED, ``N(x)
= rms(x) (1 + w)`` (eps ``rms_norm_eps``, float32):

- ``x0 = Embed[ids]``; layer ``l``: ``x' = x + Mixer_l(N1(x))``, ``y = x' +
  MoE(N2(x'))``; ``logits = N(x_L) W_head``.
- Gated DeltaNet (``H_k`` key heads, ``H_v`` value heads, ``r = H_v / H_k``,
  heads of ``d_k = d_v``): ``h W_qkvz`` is laid out by key head, ``[q d_k |
  k d_k | v r d_v | z r d_v]`` each; ``h W_ba`` by key head, ``[b r | a
  r]``.  ``[q | k | v]`` (all heads of q, then of k, then of v) pass a
  causal depthwise convolution of ``K`` taps without bias, written out as
  shifts (tap ``K - 1`` meets the current position), then SiLU.  ``q`` and
  ``k`` are L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q``
  times ``d_k^-1/2``; value head ``j`` reads key head ``j // r``.  ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``.  A value head's
  state ``S`` (``d_k x d_v``) runs THE RECURRENCE ITSELF, one position at
  a time under ``lax.scan`` over time (:func:`mixer`; the program runs the
  chunked WY form, so the two derivations check each other): ``S <- e^g
  S``; ``delta = beta (v - S^T k)``; ``S <- S + k delta^T``; ``o = S^T q``.
  Out: ``(rms(o) w_n silu(z)) W_out`` a head (``w_n`` plain, not centred).
- gated attention (``H`` query heads, ``H_kv`` K/V heads of ``d_h``): ``h
  W_q`` a head ``[query d_h | gate d_h]``; ``k = h W_k``, ``v = h W_v``;
  ``N`` a head on query and key (one weight vector each), then rotate-half
  rotary over the first ``partial_rotary_factor d_h`` dimensions
  (frequencies ``theta^(-2j / rotary)``), the rest untouched; causal
  softmax at ``d_h^-1/2``, query head ``i`` reading K/V head ``i // (H /
  H_kv)``; the result times ``sigmoid(gate)``, then ``W_o``.
- experts: ``g = f32(h) f32(W_r)`` over ALL the router's experts; the
  PUBLISHED order: ``p = softmax(g)``, the top ``k`` of ``p``, ``w = p_top
  / sum(p_top)``; ``sum w_i E_i(h)`` over the HELD experts alone (a chip's
  share: :func:`expert_layer`), ``E_i(h) = (silu(h W1_i) * (h W3_i))
  W2_i``; plus ``sigmoid(h W_sg) E_shared(h)``.  No token is dropped.

The weights hold experts ``held`` (the share the configuration holds) of
the router's ``published.num_experts``.

Departures from the published code: none in the equations.  (1) Experts run
as a ``lax.scan`` over the experts held with a one-hot weight a token, a
block of tokens at a time; attention a block of queries at a time; the head
only at the positions asked for (:func:`logits_at`): a 16,384-position
sequence fits one chip.  (2) The recurrence is the definition and not the
published chunked kernel.  (3) ``common.make_weights`` draws normals only,
so ``A_log`` is drawn as a standard normal ``z`` and mapped here
(:func:`assumed_leaf`) to the assumed ``log U[1, 16]``: ``log(1 + 15
Phi(z))``, rounded to the weights' dtype as a checkpoint holds it;
``benchmark/models/qwen3_next.py`` lays the same values into the program.
(4) The MTP module is not built (serving here does no speculation).
Leaf layout is the benchmark's own: ``w13`` holds ``[W1 | W3]``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import common as C
from benchmark.reference.sdar_moe import rotate_half

QUERY_BLOCK = 512
TOKEN_BLOCK = 4096
HI = jax.lax.Precision.HIGHEST


def is_linear(cfg, layer: int) -> bool:
    """A Gated DeltaNet layer (else full attention)."""
    return (layer + 1) % cfg["full_attention_interval"] != 0


def router_width(cfg) -> int:
    """Experts the router chooses among (all of the layer's, wherever
    they are held)."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def held_range(cfg):
    """(first, count) of the experts the weights hold."""
    return tuple(cfg.get("held") or (0, cfg["num_experts"]))


def linear_sizes(cfg):
    """(H_k, H_v, d_k, d_v, channels of the convolution)."""
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return Hk, Hv, dk, dv, 2 * Hk * dk + Hv * dv


def weight_spec(cfg: dict) -> dict:
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    Hk, Hv, dk, dv, Cw = linear_sizes(cfg)
    H, Hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f, fs = cfg["moe_intermediate_size"], cfg[
        "shared_expert_intermediate_size"]
    E = held_range(cfg)[1]
    spec = {"embed": ((cfg["vocab_size"], d), std),
            "head": ((d, cfg["vocab_size"]), std),
            "norm": ((d,), "zeros")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        spec.update({
            p + "ln1": ((d,), "zeros"), p + "ln2": ((d,), "zeros"),
            p + "gate": ((d, router_width(cfg)), std),
            p + "experts.w13": ((E, d, 2 * f), std),
            p + "experts.w2": ((E, f, d), std),
            p + "shared.w13": ((d, 2 * fs), std),
            p + "shared.w2": ((fs, d), std),
            p + "shared_gate": ((d, 1), std)})
        if is_linear(cfg, i):
            spec.update({
                p + "qkvz": ((d, 2 * Hk * dk + 2 * Hv * dv), std),
                p + "ba": ((d, 2 * Hv), std),
                p + "conv_w": ((cfg["linear_conv_kernel_dim"], Cw),
                               cfg["conv_kernel_std"]),
                p + "A_log": ((Hv,), 1.0),        # a standard normal,
                #                                   mapped by assumed_leaf
                p + "dt_bias": ((Hv,), "ones"),
                p + "mixer_norm": ((dv,), "ones"),
                p + "out_proj": ((Hv * dv, d), std)})
        else:
            spec.update({
                p + "q": ((d, 2 * H * dh), std), p + "k": ((d, Hkv * dh), std),
                p + "v": ((d, Hkv * dh), std), p + "o": ((H * dh, d), std),
                p + "q_norm": ((dh,), "zeros"),
                p + "k_norm": ((dh,), "zeros")})
    return spec


def assumed_leaf(name: str, z):
    """The value a leaf drawn as a standard normal ``z`` stands for (see
    the docstring, departure 3); every other leaf is itself."""
    if not name.endswith("A_log"):
        return z
    u = 0.5 * (1.0 + jax.lax.erf(z.astype(jnp.float32) / math.sqrt(2.0)))
    return jnp.log(1.0 + 15.0 * u).astype(z.dtype)


def layer_weights(weights, i):
    p = f"l{i}."
    return {k[len(p):]: assumed_leaf(k, v) for k, v in weights.items()
            if k.startswith(p)}


def centred_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + w.astype(jnp.float32))


def _blocks(n, size):
    size = min(size, n)
    if n % size:
        raise ValueError(f"{n} positions do not split into blocks of {size}")
    return n // size, size


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + 1e-6)


def mixer(cfg, w, h, mode="f32"):
    """The Gated DeltaNet mixer on ONE sequence ``h [s, d]``.  Returns
    (out ``[s, d]``, final state ``[H_v, d_k, d_v]``)."""
    Hk, Hv, dk, dv, Cw = linear_sizes(cfg)
    r, K, s = Hv // Hk, cfg["linear_conv_kernel_dim"], h.shape[0]
    qkvz = C.mm("sd,dk->sk", h, w["qkvz"], mode).reshape(
        s, Hk, 2 * dk + 2 * r * dv)
    q, k, v = (qkvz[..., :dk], qkvz[..., dk:2 * dk],
               qkvz[..., 2 * dk:2 * dk + r * dv])
    z = qkvz[..., 2 * dk + r * dv:].reshape(s, Hv, dv)
    ba = C.mm("sd,dk->sk", h, w["ba"], mode).reshape(s, Hk, 2 * r)
    beta = jax.nn.sigmoid(ba[..., :r]).reshape(s, Hv)
    g = -jnp.exp(w["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., r:].reshape(s, Hv) + w["dt_bias"].astype(jnp.float32))
    x = jnp.concatenate([q.reshape(s, -1), k.reshape(s, -1),
                         v.reshape(s, -1)], -1)
    # the causal depthwise convolution as explicit shifts
    padded = jnp.pad(x, ((K - 1, 0), (0, 0)))
    cw = w["conv_w"].astype(jnp.float32)
    act = jax.nn.silu(sum(padded[j:j + s] * cw[j] for j in range(K)))
    q = _l2(act[:, :Hk * dk].reshape(s, Hk, dk)) / math.sqrt(dk)
    k = _l2(act[:, Hk * dk:2 * Hk * dk].reshape(s, Hk, dk))
    v = act[:, 2 * Hk * dk:].reshape(s, Hv, dv)
    q, k = (jnp.repeat(t, r, axis=1) for t in (q, k))   # j reads j // r

    def step(S, inp):
        q_t, k_t, v_t, g_t, b_t = inp        # [H,dk] [H,dk] [H,dv] [H] [H]
        S = S * jnp.exp(g_t)[:, None, None]
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                                 precision=HI))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HI)

    S, o = jax.lax.scan(step, jnp.zeros((Hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg["rms_norm_eps"])
    o = o * w["mixer_norm"].astype(jnp.float32) * jax.nn.silu(z)
    return C.mm("sk,kd->sd", o.reshape(s, Hv * dv), w["out_proj"], mode), S


def attention(cfg, w, h, mode="f32"):
    """Gated attention on ONE sequence ``h [s, d]`` -> ``[s, d]``."""
    H, Hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, s = cfg["rms_norm_eps"], h.shape[0]
    rot = int(dh * cfg["partial_rotary_factor"])
    qg = C.mm("sd,dk->sk", h, w["q"], mode).reshape(s, H, 2 * dh)
    gate = qg[..., dh:]
    q = centred_norm(qg[..., :dh], w["q_norm"], eps)
    k = centred_norm(C.mm("sd,dk->sk", h, w["k"], mode).reshape(s, Hkv, dh),
                     w["k_norm"], eps)
    v = C.mm("sd,dk->sk", h, w["v"], mode).reshape(s, Hkv, dh)
    positions = jnp.arange(s)
    theta = cfg["rope_theta"]
    q = jnp.concatenate([rotate_half(q[None, ..., :rot], positions,
                                     theta)[0], q[..., rot:]], -1)
    k = jnp.concatenate([rotate_half(k[None, ..., :rot], positions,
                                     theta)[0], k[..., rot:]], -1)
    G = H // Hkv
    n, size = _blocks(s, QUERY_BLOCK)

    def block(i):
        rows = i * size + jnp.arange(size)
        qb = jax.lax.dynamic_slice_in_dim(q, i * size, size).reshape(
            size, Hkv, G, dh)
        score = C.mm("qhgd,khd->hgqk", qb, k, mode) / math.sqrt(dh)
        seen = rows[:, None] >= positions[None, :]
        p = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
        return C.mm("hgqk,khd->qhgd", p, v, mode).reshape(size, H * dh)

    o = jax.lax.map(block, jnp.arange(n)).reshape(s, H, dh)
    o = (o * jax.nn.sigmoid(gate)).reshape(s, H * dh)
    return C.mm("sk,kd->sd", o, w["o"], mode)


def route(cfg, w, h):
    """(weights [n, k], experts [n, k]) of tokens ``h [n, d]`` in the
    published order: softmax over all experts, the top ``k``, divided by
    their sum; float32 whatever the mode."""
    g = C.mm("nd,de->ne", h, w["gate"], "f32")
    p = jax.nn.softmax(g, axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return top / jnp.sum(top, -1, keepdims=True), idx


def swiglu(x, w13, w2, mode):
    a = C.mm("nd,df->nf", x, w13, mode)
    gate, up = jnp.split(a, 2, -1)
    return C.mm("nf,fd->nd", jax.nn.silu(gate) * up, w2, mode)


def expert_layer(cfg, w, h, mode="f32", held=None, shared=True):
    """The experts on ``h [n, d]``.  ``w["experts.*"]`` hold the share
    :func:`held_range` names (or every expert of the router); ``held =
    (first, count)`` keeps only that range's terms (the router still sees
    all of them) and must lie inside the weights' share; ``shared`` adds the
    gated shared expert."""
    n, E = h.shape[0], router_width(cfg)
    have = w["experts.w13"].shape[0]
    base = held_range(cfg)[0] if have != E else 0
    first, count = held if held is not None else (base, have)
    lo = first - base
    nb, size = _blocks(n, TOKEN_BLOCK)

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(h, i * size, size)
        weights, idx = route(cfg, w, xb)
        # [size, E]: a token's weight for each expert, 0 where not chosen
        dense = jnp.zeros((size, E), jnp.float32).at[
            jnp.arange(size)[:, None], idx].add(weights)

        def body(acc, args):
            w13, w2, col = args
            return acc + swiglu(xb, w13, w2, mode) * col[:, None], None

        out, _ = jax.lax.scan(
            body, jnp.zeros_like(xb),
            (w["experts.w13"][lo:lo + count], w["experts.w2"][lo:lo + count],
             dense.T[first:first + count]))
        if shared:
            out = out + jax.nn.sigmoid(C.mm(
                "nd,do->no", xb, w["shared_gate"], mode)) * swiglu(
                xb, w["shared.w13"], w["shared.w2"], mode)
        return out

    return jax.lax.map(block, jnp.arange(nb)).reshape(n, -1)


def hidden_states(cfg, weights, ids, mode="f32"):
    """The final-normed hidden states of ONE sequence ``ids [s]``."""
    x = weights["embed"][ids].astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(weights, i)
        h = centred_norm(x, w["ln1"], eps)
        x = x + (mixer(cfg, w, h, mode)[0] if is_linear(cfg, i)
                 else attention(cfg, w, h, mode))
        x = x + expert_layer(cfg, w, centred_norm(x, w["ln2"], eps), mode)
    return centred_norm(x, weights["norm"], eps)


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
