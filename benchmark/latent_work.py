"""Bytes and operations a DeepSeek-V3-style model's decode NEEDS, from
shapes alone (the twin of ``flops.py`` for the ``deepseek_v3`` family):
what the roofline shares of ``decode_hbm_roofline.serve`` and
``mla_decode_roofline.serve`` divide by a measured time."""
from __future__ import annotations

from benchmark.reference import deepseek_v3 as ref


def latent_row_width(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_decode_work(cfg: dict, live_rows: float,
                    itemsize: int) -> tuple[float, float]:
    """(FLOPs, bytes) that absorbed decode attention needs to read
    ``live_rows`` cached latent rows (summed over slots and steps) in
    every layer, whatever runs it: each row's ``rank + rope`` values ONCE
    (keys and values are the same row); a head's score is ``rank + rope``
    multiply-adds a row and its weighted sum ``rank``.  The query, the
    output and the new row's append are a slot's one row each and are not
    counted."""
    r, w = cfg["kv_lora_rank"], latent_row_width(cfg)
    rows = cfg["num_hidden_layers"] * float(live_rows)
    return (2.0 * rows * cfg["num_attention_heads"] * (w + r),
            rows * w * itemsize)


def resident_params(cfg: dict) -> int:
    """Parameters EVERY decode step multiplies whatever the router does:
    attention, the dense layers' MLP, shared experts, routers, the head.
    (Norm vectors and the batch's few embedding rows are left out: under a
    thousandth.)"""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    attn = (d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv)
            + H * dv * d)
    layers = cfg["num_hidden_layers"]
    n_dense = sum(1 for i in range(layers) if ref.is_dense(cfg, i))
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    return (layers * attn + n_dense * 3 * d * cfg["intermediate_size"]
            + (layers - n_dense) * (shared + d * cfg["n_routed_experts"])
            + d * cfg["vocab_size"])


def expert_params(cfg: dict) -> int:
    """Parameters of ONE routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def decode_step_bytes(cfg: dict, experts_hit: float, live_rows: float,
                      itemsize: int) -> float:
    """Bytes one decode step has to read from HBM: every resident matrix
    once, each routed expert that got a token once (``experts_hit``,
    summed over layers), each live latent row once a layer."""
    return itemsize * (resident_params(cfg)
                       + float(experts_hit) * expert_params(cfg)) + (
        mla_decode_work(cfg, live_rows, itemsize)[1])
