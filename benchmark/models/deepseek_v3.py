"""Family ``deepseek_v3``: builds the program's DeepSeek-V3-style decoder
(``paddle_tpu/models/deepseek_v3.py``) from a configuration file, maps the
benchmark's leaf names onto the program's parameters, and counts the
family's own serving FLOPs (ACTIVE parameters only).

A program without this family (the parent of the PR that brought it) cannot
run the cell: importing this file there prints ``correct: false`` and exits
non-zero at once, before any device is touched."""
from __future__ import annotations

import sys

from benchmark.reference import deepseek_v3 as reference  # noqa: F401

try:
    from paddle_tpu.models import deepseek_v3 as _program
except ImportError as e:                       # pragma: no cover
    print(f"[bench] the program has no deepseek_v3 model: {e}",
          file=sys.stderr)
    print("[bench] correct: false", file=sys.stderr, flush=True)
    raise SystemExit(1)


def build(cfg: dict, training: bool, init_weights: bool = False):
    """The program's model with every parameter an empty placeholder
    (the runner lays each leaf in), created in the dtype the weights
    will have: nothing is drawn and nothing is held twice."""
    if training:
        raise NotImplementedError("the deepseek_v3 family is served only")
    config = _program.DeepseekV3Config.from_published(
        cfg, initializer_range=cfg["initializer_range"],
        init_weights=init_weights)
    return _program.DeepseekV3ForCausalLM(config)


def leaf_names(cfg: dict) -> dict:
    """benchmark leaf name -> the program's parameter name."""
    names = {"embed": "embed", "head": "head", "norm": "norm.weight"}
    attn = {"ln1": "ln1.weight", "ln2": "ln2.weight", "q": "attn.q",
            "kva": "attn.kva", "kvn": "attn.kv_norm", "kvb": "attn.kvb",
            "o": "attn.o"}
    dense = {"mlp.w13": "mlp.w13", "mlp.w2": "mlp.w2"}
    moe = {"gate": "mlp.gate_weight", "gate_bias": "mlp.gate_bias",
           "experts.w13": "mlp.w13", "experts.w2": "mlp.w2",
           "shared.w13": "mlp.shared_w13", "shared.w2": "mlp.shared_w2"}
    for i in range(cfg["num_hidden_layers"]):
        parts = dict(attn, **(dense if reference.is_dense(cfg, i) else moe))
        for mine, theirs in parts.items():
            names[f"l{i}.{mine}"] = f"layers.{i}.{theirs}"
    return names


# ------------------------------------------------------------------ FLOPs
def active_body_params(cfg: dict) -> int:
    """Parameters one token multiplies in the layers (head apart).  The
    same in both phases: prefill expands ``c W_kvb`` a token, decode
    absorbs ``W_kvb`` into the query and the output — both halves of
    ``W_kvb`` once a token either way.  Routed experts: the
    ``num_experts_per_tok`` chosen, never all."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    attn = (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
            + H * dv * d)
    f = cfg["moe_intermediate_size"]
    moe = (d * cfg["n_routed_experts"]                      # the router
           + 3 * d * f * (cfg["num_experts_per_tok"]
                          + cfg["n_shared_experts"]))
    dense = 3 * d * cfg["intermediate_size"]
    n_dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    n_moe = cfg["num_hidden_layers"] - n_dense
    return (cfg["num_hidden_layers"] * attn + n_dense * dense
            + n_moe * moe)


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs of one request.  Prompt positions attend in the
    expanded form (``d_n + d_r`` wide scores, ``d_v`` wide values a head,
    causal); decoded positions in the absorbed form over latent rows
    (``r + d_r`` wide scores, ``r`` wide values a head); the head runs
    only where a token is sampled."""
    H, L = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    n_dec = new_tokens - 1                     # the last token is not fed
    body = 2.0 * active_body_params(cfg) * (prompt_len + n_dec)
    prefill_keys = prompt_len * (prompt_len + 1) / 2.0
    decode_keys = sum(prompt_len + j + 1 for j in range(n_dec))
    attn = 2.0 * L * H * (prefill_keys * (dn + dr + dv)
                          + decode_keys * (r + dr + r))
    return body + attn + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * (
        new_tokens)
