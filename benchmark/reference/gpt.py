"""GPT-3 (Brown et al. 2020, arXiv:2005.14165 §2.1) in plain jax.numpy.

Pre-LayerNorm decoder, learned positions, GELU (tanh form, as GPT-2/3)
MLP of 4x width, causal softmax attention, output head tied to the token
table.  Departure from the paper: none in the equations; the fused qkv
matrix is laid out ``[hidden, heads, (q|k|v), head_dim]`` flattened — the
benchmark's own choice of layout, which ``benchmark/models/gpt.py`` maps
onto the program.  No kernels, no cache, no batching tricks; layers run
under ``jax.checkpoint`` only so that the backward fits the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common as C


def weight_spec(cfg: dict) -> dict:
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg["initializer_range"]
    spec = {"wte": ((cfg["padded_vocab_size"], h), std),
            "wpe": ((cfg["max_position_embeddings"], h), std),
            "lnf.w": ((h,), "ones"), "lnf.b": ((h,), "zeros")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"h{i}."
        spec.update({
            p + "ln1.w": ((h,), "ones"), p + "ln1.b": ((h,), "zeros"),
            p + "qkv.w": ((h, 3 * h), std), p + "qkv.b": ((3 * h,), "zeros"),
            p + "out.w": ((h, h), std), p + "out.b": ((h,), "zeros"),
            p + "ln2.w": ((h,), "ones"), p + "ln2.b": ((h,), "zeros"),
            p + "fc1.w": ((h, inter), std), p + "fc1.b": ((inter,), "zeros"),
            p + "fc2.w": ((inter, h), std), p + "fc2.b": ((h,), "zeros")})
    return spec


def views(cfg, tree):
    return C.split_qkv(cfg, tree)


def _block(cfg, mode, x, w):
    heads = cfg["num_attention_heads"]
    b, s, h = x.shape
    eps = cfg["layer_norm_epsilon"]
    a = C.layer_norm(x, w["ln1.w"], w["ln1.b"], eps)
    qkv = C.mm("bsh,hk->bsk", a, w["qkv.w"], mode) + w["qkv.b"]
    q, k, v = jnp.split(qkv.reshape(b, s, heads, 3 * (h // heads)), 3, -1)
    o = C.attention(q, k, v, True, mode).reshape(b, s, h)
    x = x + C.mm("bsh,hk->bsk", o, w["out.w"], mode) + w["out.b"]
    a = C.layer_norm(x, w["ln2.w"], w["ln2.b"], eps)
    a = C.gelu_tanh(C.mm("bsh,hk->bsk", a, w["fc1.w"], mode) + w["fc1.b"])
    return x + C.mm("bsk,kh->bsh", a, w["fc2.w"], mode) + w["fc2.b"]


def hidden_states(cfg, weights, ids, mode="f32"):
    s = ids.shape[1]
    x = weights["wte"][ids] + weights["wpe"][:s]
    x = x.astype(jnp.float32)
    block = jax.checkpoint(lambda x, w: _block(cfg, mode, x, w))
    for i in range(cfg["num_hidden_layers"]):
        p = f"h{i}."
        x = block(x, {k[len(p):]: v for k, v in weights.items()
                      if k.startswith(p)})
    return C.layer_norm(x, weights["lnf.w"], weights["lnf.b"],
                        cfg["layer_norm_epsilon"])


def logits(cfg, weights, ids, mode="f32"):
    return C.mm("bsh,vh->bsv", hidden_states(cfg, weights, ids, mode),
                weights["wte"], mode)


def loss(cfg, weights, ids, labels, mode="f32"):
    """Mean next-token cross entropy over every position."""
    return C.cross_entropy_mean(logits(cfg, weights, ids, mode), labels)
