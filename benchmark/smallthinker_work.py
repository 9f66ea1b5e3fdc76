"""Bytes and operations a SmallThinker (``smallthinker``) model NEEDS, from
shapes alone (the twin of ``sdar_work.py`` for this family): what
``window_prefill_flash_roofline.serve`` and
``window_decode_hbm_roofline.serve`` divide by a measured time, and what
the family's ``serve_flops`` counts.

Full layers (layout flag 0) attend over every earlier position, window
layers (flag 1) over the last ``sliding_window_size`` alone.
"""
from __future__ import annotations


def _layers(cfg):
    """(full layers, window layers) of the configuration."""
    flags = cfg["sliding_window_layout"][:cfg["num_hidden_layers"]]
    return flags.count(0), flags.count(1)


def _kv_width(cfg):
    return cfg["num_key_value_heads"] * cfg["head_dim"]


def attention_params(cfg: dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` of one layer."""
    d, q = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    return 2 * d * q + 2 * d * _kv_width(cfg)


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["moe_num_primary_experts"]


def expert_params(cfg: dict) -> int:
    """Parameters of ONE routed expert (``W1``, ``W3``, ``W2``)."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def layer_params(cfg: dict) -> int:
    """Every parameter of one layer: attention, the two norms, the router,
    all experts."""
    return (attention_params(cfg) + 2 * cfg["hidden_size"]
            + router_params(cfg)
            + cfg["moe_num_primary_experts"] * expert_params(cfg))


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def params(cfg: dict) -> int:
    """Every parameter held: the layers, the embedding, the untied head and
    the final norm."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * embedding_params(cfg) + cfg["hidden_size"])


def active_body_params(cfg: dict) -> int:
    """Parameters one position multiplies in the layers (head apart):
    attention, the router, ``moe_num_active_primary_experts`` experts."""
    return cfg["num_hidden_layers"] * (
        attention_params(cfg) + router_params(cfg)
        + cfg["moe_num_active_primary_experts"] * expert_params(cfg))


def resident_params(cfg: dict) -> int:
    """Parameters EVERY decode pass multiplies whatever the router does:
    attention, routers, the head.  Norm vectors and the pass's few embedding
    rows are left out: under a thousandth."""
    return (cfg["num_hidden_layers"] * (attention_params(cfg)
                                        + router_params(cfg))
            + embedding_params(cfg))


def keys_seen(n: int, window: int | None = None) -> int:
    """(query, key) pairs of a causal pass over ``n`` positions: ``sum_i
    (i + 1)``, or under a window ``sum_i min(i + 1, window)``."""
    if window is None or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def attention_flops(cfg: dict, n: int) -> float:
    """Scores and weighted sums of all layers of a causal pass over ``n``
    positions: ``4 H d_h`` a (query, key) pair, full layers over every
    earlier key, window layers over the window's."""
    full, windowed = _layers(cfg)
    pair = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return pair * (full * keys_seen(n)
                   + windowed * keys_seen(n, cfg["sliding_window_size"]))


def kv_row_bytes(cfg: dict, itemsize: int) -> int:
    """One position's K and V rows in ONE layer."""
    return 2 * _kv_width(cfg) * itemsize


def decode_pass_bytes(cfg: dict, experts_hit: int, full_rows: int,
                      window_rows: int, itemsize: int) -> dict:
    """Bytes decode passes have to move, term by term: the resident
    matrices and the head once; each expert that got a token once
    (``experts_hit``, summed over layers); each live K/V row once a layer —
    ``full_rows`` in every full layer, ``window_rows`` in every window
    layer (a slot's rows of the window alone)."""
    full, windowed = _layers(cfg)
    row = kv_row_bytes(cfg, itemsize)
    return {
        "resident": resident_params(cfg) * itemsize,
        "experts": experts_hit * expert_params(cfg) * itemsize,
        "kv_full": full * full_rows * row,
        "kv_window": windowed * window_rows * row,
    }


def decode_pass_needed(cfg, experts_hit, full_rows, window_rows, itemsize):
    return sum(decode_pass_bytes(cfg, experts_hit, full_rows, window_rows,
                                 itemsize).values())
