"""Bytes and operations an SDAR-MoE (``sdar_moe``) model NEEDS, from shapes
alone (the twin of ``flops.py``, ``latent_work.py`` and ``ssm_work.py`` for
this family): what ``block_decode_hbm_roofline.serve`` divides by a
measured time, and what the family's ``serve_flops`` counts.
"""
from __future__ import annotations


def _kv_width(cfg):
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def attention_params(cfg: dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` of one layer."""
    d, q = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    return 2 * d * q + 2 * d * _kv_width(cfg)


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def expert_params(cfg: dict) -> int:
    """Parameters of ONE routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg: dict) -> int:
    """Every parameter of one layer: attention, the two head norms, the two
    layer norms, the router, all experts."""
    return (attention_params(cfg) + 2 * cfg["head_dim"]
            + 2 * cfg["hidden_size"] + router_params(cfg)
            + cfg["num_experts"] * expert_params(cfg))


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def params(cfg: dict) -> int:
    """Every parameter held: the layers, the embedding, the untied head and
    the final norm."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * embedding_params(cfg) + cfg["hidden_size"])


def active_body_params(cfg: dict) -> int:
    """Parameters one position multiplies in the layers (head apart):
    attention, the router, ``num_experts_per_tok`` experts."""
    return cfg["num_hidden_layers"] * (
        attention_params(cfg) + router_params(cfg)
        + cfg["num_experts_per_tok"] * expert_params(cfg))


def resident_params(cfg: dict) -> int:
    """Parameters EVERY decode pass multiplies whatever the router does:
    attention, routers, the head.  Norm vectors and the pass's few embedding
    rows are left out: under a thousandth."""
    return (cfg["num_hidden_layers"] * (attention_params(cfg)
                                        + router_params(cfg))
            + embedding_params(cfg))


def attention_flops(cfg: dict, keys: float) -> float:
    """Scores and weighted sums of all layers over `keys` (query position,
    key position) pairs: ``H`` heads of ``d_h``, two products."""
    return (2.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * 2 * cfg["head_dim"] * keys)


def kv_row_bytes(cfg: dict, itemsize: int) -> int:
    """One position's K and V rows in ONE layer."""
    return 2 * _kv_width(cfg) * itemsize


def decode_pass_bytes(cfg: dict, experts_hit: int, live_rows: int,
                      block_rows: int, itemsize: int) -> dict:
    """Bytes one decode pass has to move, term by term: the resident
    matrices and the head once; each expert that got a token once
    (``experts_hit``, summed over layers); each live K/V row once a layer
    (``live_rows``: the positions the live slots attend over, prefix and
    block); the ``block_rows`` new K/V rows written a layer."""
    L = cfg["num_hidden_layers"]
    return {
        "resident": resident_params(cfg) * itemsize,
        "experts": experts_hit * expert_params(cfg) * itemsize,
        "kv_read": L * live_rows * kv_row_bytes(cfg, itemsize),
        "kv_write": L * block_rows * kv_row_bytes(cfg, itemsize),
    }


def decode_pass_needed(cfg, experts_hit, live_rows, block_rows, itemsize):
    return sum(decode_pass_bytes(cfg, experts_hit, live_rows, block_rows,
                                 itemsize).values())
