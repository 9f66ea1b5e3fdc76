"""BERT (Devlin et al. 2018, arXiv:1810.04805 §3) in plain jax.numpy.

Post-LayerNorm encoder: token + position + segment embeddings through a
LayerNorm, bidirectional softmax attention, GELU (erf form) MLP of 4x
width; the masked-LM head is dense -> GELU -> LayerNorm -> the tied token
table plus a bias.  As the cell runs it (``tools/profile_bert.py``): all
segment ids 0, no padding mask, the MLM loss taken over every position,
no next-sentence loss — so the pooler and the next-sentence classifier
get no gradient and are not part of the reference's leaves.
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
            "wtt": ((cfg["type_vocab_size"], h), std),
            "emb_ln.w": ((h,), "ones"), "emb_ln.b": ((h,), "zeros"),
            "mlm.w": ((h, h), std), "mlm.b": ((h,), "zeros"),
            "mlm_ln.w": ((h,), "ones"), "mlm_ln.b": ((h,), "zeros"),
            "mlm_bias": ((cfg["padded_vocab_size"],), "zeros")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"h{i}."
        spec.update({
            p + "qkv.w": ((h, 3 * h), std), p + "qkv.b": ((3 * h,), "zeros"),
            p + "out.w": ((h, h), std), p + "out.b": ((h,), "zeros"),
            p + "ln1.w": ((h,), "ones"), p + "ln1.b": ((h,), "zeros"),
            p + "fc1.w": ((h, inter), std), p + "fc1.b": ((inter,), "zeros"),
            p + "fc2.w": ((inter, h), std), p + "fc2.b": ((h,), "zeros"),
            p + "ln2.w": ((h,), "ones"), p + "ln2.b": ((h,), "zeros")})
    return spec


def views(cfg, tree):
    return C.split_qkv(cfg, tree)


def _block(cfg, mode, x, w):
    heads = cfg["num_attention_heads"]
    b, s, h = x.shape
    eps = cfg["layer_norm_epsilon"]
    qkv = C.mm("bsh,hk->bsk", x, w["qkv.w"], mode) + w["qkv.b"]
    q, k, v = jnp.split(qkv.reshape(b, s, heads, 3 * (h // heads)), 3, -1)
    o = C.attention(q, k, v, False, mode).reshape(b, s, h)
    x = C.layer_norm(
        x + C.mm("bsh,hk->bsk", o, w["out.w"], mode) + w["out.b"],
        w["ln1.w"], w["ln1.b"], eps)
    a = C.gelu_erf(C.mm("bsh,hk->bsk", x, w["fc1.w"], mode) + w["fc1.b"])
    return C.layer_norm(
        x + C.mm("bsk,kh->bsh", a, w["fc2.w"], mode) + w["fc2.b"],
        w["ln2.w"], w["ln2.b"], eps)


def logits(cfg, weights, ids, mode="f32"):
    s = ids.shape[1]
    eps = cfg["layer_norm_epsilon"]
    x = weights["wte"][ids] + weights["wpe"][:s] + weights["wtt"][0]
    x = C.layer_norm(x.astype(jnp.float32), weights["emb_ln.w"],
                     weights["emb_ln.b"], eps)
    block = jax.checkpoint(lambda x, w: _block(cfg, mode, x, w))
    for i in range(cfg["num_hidden_layers"]):
        p = f"h{i}."
        x = block(x, {k[len(p):]: v for k, v in weights.items()
                      if k.startswith(p)})
    a = C.gelu_erf(C.mm("bsh,hk->bsk", x, weights["mlm.w"], mode)
                   + weights["mlm.b"])
    a = C.layer_norm(a, weights["mlm_ln.w"], weights["mlm_ln.b"], eps)
    return C.mm("bsh,vh->bsv", a, weights["wte"], mode) + weights["mlm_bias"]


def loss(cfg, weights, ids, labels, mode="f32"):
    """Mean masked-LM cross entropy over every position."""
    return C.cross_entropy_mean(logits(cfg, weights, ids, mode), labels)
