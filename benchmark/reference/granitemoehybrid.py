"""Granite-4.0-H decoder (``model_type: granitemoehybrid``, as IBM publishes
granite-4.0-h-small) in plain jax.numpy: by ``layer_types`` a Mamba-2
state-space mixer or grouped-query attention without positions, then
softmax-routed experts plus a shared SwiGLU in every layer, four scalar
multipliers, a tied head.  No kernels, no cache, no batching, no chunks;
every product goes through ``common.mm`` (float32 at HIGHEST, or the
control's precision).  Imports nothing of the program.

With ``d`` hidden, ``m = residual_multiplier``, RMSNorm eps as published:

- ``x0 = Embed[ids] * embedding_multiplier``; layer ``l``: ``x' = x + m
  Mixer_l(RMSNorm(x))``, ``y = x' + m (Experts(h) + Shared(h))`` with ``h =
  RMSNorm(x')``; ``logits = RMSNorm(x_L) Embed^T / logits_scaling``.
- attention: ``q = h W_q`` as ``H x d_h``, ``k = h W_k``, ``v = h W_v`` as
  ``H_kv x d_h``; NO rotation (``position_embedding_type: nope``); ``score =
  attention_multiplier * q . k``, query head ``i`` reading K/V head ``i //
  (H / H_kv)``; causal, softmax, ``W_o``; no bias.
- mamba (``H_m`` heads of ``P``, state ``N``, one group, ``D_i = H_m P``):
  ``[z | xBC | dt] = h W_in`` (widths ``D_i | D_i + 2N | H_m``); ``xBC =
  silu(conv1d(xBC) + b_c)``, causal and depthwise over ``d_conv`` taps,
  written out as shifts (tap ``d_conv - 1`` meets the current position);
  ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)`` a head (no clamp:
  ``time_step_limit`` (0, inf)); ``a = -exp(A_log)``.  Per head the state
  ``S`` (``P x N``): ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t =
  S_t C_t + D x_t`` — run as that recurrence, one position at a time, under
  ``lax.scan`` over TIME (:func:`mixer`; the program runs the chunked form,
  so the two derivations check each other).  ``y = RMSNorm(y * silu(z)) *
  w_n`` over all of ``D_i`` (the gate BEFORE the norm), ``out = y W_out``.
- experts: ``g = f32(h) f32(W_g)``; the top ``k`` of ``g``; ``w =
  softmax(g_top)`` over the chosen; ``sum_top w_i E_i(h)``, ``E_i(h) =
  (silu(h W1_i) * (h W3_i)) W2_i``; ``Shared`` the same form.  No token is
  dropped.  :func:`expert_layer` takes the contiguous range of experts held
  (a chip's share): it routes over ALL experts of the router and adds only
  the held experts' terms.

The weights hold experts ``0 .. num_local_experts - 1`` (the share the
configuration holds: ``held``); the router is ``published.num_local_experts``
wide.

Departures from the published code: none in the equations.  (1) Experts run
as a ``lax.scan`` over the experts held with a one-hot weight a token, one
expert's float32 copy in flight at a time.  (2) The recurrence is the
definition and not the published chunked kernel.  (3) ``common.make_weights``
draws normals only, so ``A_log`` and ``dt_bias`` are drawn as standard
normals ``z`` and mapped here (:func:`assumed_leaf`) to the family's
initialisers: ``A_log = log(1 + 15 Phi(z))`` (``A`` uniform on [1, 16]),
``dt_bias = softplus^-1(exp(U))`` with ``U = log 1e-3 + Phi(z) (log 1e-1 -
log 1e-3)``, rounded to the weights' dtype as a checkpoint holds them;
``benchmark/models/granitemoehybrid.py`` lays the same values into the
program.  (4) The tied table is drawn at its own ``embedding_std`` (the
configuration's ``assumed`` says why).  (5) A mode ``"f32/state_bf16"`` rounds the carried state to
bfloat16 after every position: a control for the state's precision.
Leaf layout is the benchmark's own: ``w13`` holds ``[W1 | W3]``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import common as C


def is_mamba(cfg, layer: int) -> bool:
    return cfg["layer_types"][layer] == "mamba"


def router_width(cfg) -> int:
    """Experts the router chooses among (all of the layer's, wherever
    they are held)."""
    return cfg.get("published", {}).get("num_local_experts",
                                        cfg["num_local_experts"])


def held_range(cfg):
    """(first, count) of the experts the weights hold."""
    return tuple(cfg.get("held") or (0, cfg["num_local_experts"]))


def sizes(cfg):
    """(H, P, N, D_i, channels of the convolution) of the mamba mixer."""
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return H, P, N, H * P, H * P + 2 * N


def weight_spec(cfg: dict) -> dict:
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    Hm, P, N, Di, Cw = sizes(cfg)
    dh = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * dh
    f, fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    E = cfg["num_local_experts"]
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("the reference has one group of B and C")
    spec = {"embed": ((cfg["vocab_size"], d), cfg["embedding_std"]),
            "norm": ((d,), "ones")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        spec.update({
            p + "ln1": ((d,), "ones"), p + "ln2": ((d,), "ones"),
            p + "gate": ((d, router_width(cfg)), std),
            p + "experts.w13": ((E, d, 2 * f), std),
            p + "experts.w2": ((E, f, d), std),
            p + "shared.w13": ((d, 2 * fs), std),
            p + "shared.w2": ((fs, d), std)})
        if is_mamba(cfg, i):
            spec.update({
                p + "in_proj": ((d, Di + Cw + Hm), std),
                p + "conv_w": ((cfg["mamba_d_conv"], Cw),
                               cfg["conv_kernel_std"]),
                p + "conv_b": ((Cw,), "zeros"),
                p + "A_log": ((Hm,), 1.0),        # standard normals,
                p + "dt_bias": ((Hm,), 1.0),      # mapped by assumed_leaf
                p + "D": ((Hm,), "ones"),
                p + "mixer_norm": ((Di,), "ones"),
                p + "out_proj": ((Di, d), std)})
        else:
            spec.update({p + "q": ((d, d), std), p + "k": ((d, kv), std),
                         p + "v": ((d, kv), std), p + "o": ((d, d), std)})
    return spec


def assumed_leaf(name: str, z):
    """The value a leaf drawn as a standard normal ``z`` stands for (see
    the docstring, departure 3); every other leaf is itself."""
    if not name.endswith(("A_log", "dt_bias")):
        return z
    u = 0.5 * (1.0 + jax.lax.erf(z.astype(jnp.float32) / math.sqrt(2.0)))
    if name.endswith("A_log"):
        out = jnp.log(1.0 + 15.0 * u)
    else:
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        out = dt + jnp.log(-jnp.expm1(-dt))        # softplus^-1(dt)
    return out.astype(z.dtype)


def layer_weights(weights, i):
    p = f"l{i}."
    return {k[len(p):]: assumed_leaf(k, v) for k, v in weights.items()
            if k.startswith(p)}


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return w.astype(jnp.float32) * x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def swiglu(x, w13, w2, mode):
    a = C.mm("...d,df->...f", x, w13, mode)
    gate, up = jnp.split(a, 2, -1)
    return C.mm("...f,fd->...d", jax.nn.silu(gate) * up, w2, mode)


def attention(cfg, w, h, mode):
    b, s, d = h.shape
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // H
    q = C.mm("bsd,dk->bsk", h, w["q"], mode).reshape(b, s, Hkv, H // Hkv, dh)
    k = C.mm("bsd,dk->bsk", h, w["k"], mode).reshape(b, s, Hkv, dh)
    v = C.mm("bsd,dk->bsk", h, w["v"], mode).reshape(b, s, Hkv, dh)
    score = C.mm("bqhgd,bkhd->bhgqk", q, k, mode) * cfg["attention_multiplier"]
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    p = jax.nn.softmax(score, axis=-1)
    o = C.mm("bhgqk,bkhd->bqhgd", p, v, mode).reshape(b, s, d)
    return C.mm("bsk,kd->bsd", o, w["o"], mode)


def mixer(cfg, w, h, mode="f32", length=None):
    """The mamba mixer on ``h [b, s, d]``.  Returns (out ``[b, s, d]``,
    final state ``[b, H, P, N]``, the convolution's last ``d_conv - 1``
    inputs ``[b, d_conv - 1, C]``); with ``length`` the state and the
    inputs are those after ``length`` positions."""
    mode, _, state_mode = mode.partition("/")
    Hm, P, N, Di, Cw = sizes(cfg)
    K = cfg["mamba_d_conv"]
    b, s, _ = h.shape
    zxd = C.mm("bsd,dk->bsk", h, w["in_proj"], mode)
    z, xbc, dt = zxd[..., :Di], zxd[..., Di:Di + Cw], zxd[..., Di + Cw:]
    # the causal depthwise convolution as explicit shifts
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    cw = w["conv_w"].astype(jnp.float32)
    conv = sum(padded[:, j:j + s] * cw[j] for j in range(K))
    act = jax.nn.silu(conv + w["conv_b"].astype(jnp.float32))
    x = act[..., :Di].reshape(b, s, Hm, P)
    Bm, Cm = act[..., Di:Di + N], act[..., Di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(jnp.float32))   # [b,s,H]
    a = -jnp.exp(w["A_log"].astype(jnp.float32))
    D = w["D"].astype(jnp.float32)
    live = (jnp.arange(s) < (s if length is None else length))

    def step(S, inp):
        x_t, B_t, C_t, dt_t, on = inp              # [b,H,P] [b,N] [b,N] [b,H]
        new = (jnp.exp(dt_t * a)[..., None, None] * S
               + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :])
        if state_mode == "state_bf16":
            new = new.astype(jnp.bfloat16).astype(jnp.float32)
        y_t = jnp.sum(new * C_t[:, None, None, :], -1) + D[:, None] * x_t
        return jnp.where(on, new, S), y_t

    S, y = jax.lax.scan(
        step, jnp.zeros((b, Hm, P, N), jnp.float32),
        (jnp.moveaxis(x, 1, 0), jnp.moveaxis(Bm, 1, 0),
         jnp.moveaxis(Cm, 1, 0), jnp.moveaxis(dt, 1, 0), live))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, Di)
    g = rms_norm(y * jax.nn.silu(z), w["mixer_norm"], cfg["rms_norm_eps"])
    end = s if length is None else length
    window = jax.lax.dynamic_slice_in_dim(padded, end, K - 1, axis=1)
    return C.mm("bsk,kd->bsd", g, w["out_proj"], mode), S, window


def route(cfg, w, h):
    """(weights [n, k], experts [n, k]) of tokens ``h [n, d]``; float32
    whatever the mode."""
    g = C.mm("nd,de->ne", h, w["gate"], "f32")
    top, idx = jax.lax.top_k(g, cfg["num_experts_per_tok"])
    return jax.nn.softmax(top, axis=-1), idx


def expert_layer(cfg, w, h, mode="f32", held=None, shared=True):
    """``Experts(h) + Shared(h)`` on ``h [..., d]``.  ``w["experts.*"]``
    hold every expert of the router, or the share :func:`held_range`
    names; ``held = (first, count)`` keeps only that range's terms (the
    router still sees all of them) and must lie inside the weights'
    share; ``shared`` adds the shared experts."""
    shape = h.shape
    x = h.reshape(-1, shape[-1]).astype(jnp.float32)
    weights, idx = route(cfg, w, x)
    E = w["gate"].shape[-1]
    have = w["experts.w13"].shape[0]
    base = held_range(cfg)[0] if have != E else 0
    first, count = held if held is not None else (base, have)
    # [n, E]: a token's weight for each expert, 0 where it was not chosen
    dense = jnp.zeros((x.shape[0], E), jnp.float32).at[
        jnp.arange(x.shape[0])[:, None], idx].add(weights)

    def body(acc, args):
        w13, w2, col = args
        return acc + swiglu(x, w13, w2, mode) * col[:, None], None

    lo = first - base
    out, _ = jax.lax.scan(
        body, jnp.zeros_like(x),
        (w["experts.w13"][lo:lo + count], w["experts.w2"][lo:lo + count],
         dense.T[first:first + count]))
    if shared:
        out = out + swiglu(x, w["shared.w13"], w["shared.w2"], mode)
    return out.reshape(shape)


def hidden_states(cfg, weights, ids, mode="f32"):
    x = weights["embed"][ids].astype(jnp.float32) * cfg[
        "embedding_multiplier"]
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    plain = mode.partition("/")[0]
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(weights, i)
        h = rms_norm(x, w["ln1"], eps)
        x = x + m * (mixer(cfg, w, h, mode)[0] if is_mamba(cfg, i)
                     else attention(cfg, w, h, plain))
        x = x + m * expert_layer(cfg, w, rms_norm(x, w["ln2"], eps), plain)
    return rms_norm(x, weights["norm"], eps)


def logits(cfg, weights, ids, mode="f32"):
    return C.mm("bsd,vd->bsv", hidden_states(cfg, weights, ids, mode),
                weights["embed"], mode.partition("/")[0]) / cfg[
        "logits_scaling"]


def loss(cfg, weights, ids, labels, mode="f32"):
    """Mean next-token cross entropy over every position."""
    return C.cross_entropy_mean(logits(cfg, weights, ids, mode), labels)
