"""Bytes and operations a Granite-4.0-H (``granitemoehybrid``) model NEEDS,
from shapes alone (the twin of ``flops.py`` and ``latent_work.py`` for this
family): what ``ssm_decode_hbm_roofline.serve`` and
``ssm_prefill_roofline.serve`` divide by a measured time, and what the
family's ``serve_flops`` counts.  Everything is counted for the experts HELD
here (``num_local_experts`` of the router's ``published.num_local_experts``).
"""
from __future__ import annotations

from benchmark.reference import granitemoehybrid as ref


def _layers(cfg):
    mamba = sum(1 for i in range(cfg["num_hidden_layers"])
                if ref.is_mamba(cfg, i))
    return mamba, cfg["num_hidden_layers"] - mamba


def mixer_matrices(cfg: dict) -> int:
    """``W_in`` and ``W_out`` of one mamba layer."""
    H, _P, _N, Di, Cw = ref.sizes(cfg)
    return cfg["hidden_size"] * (Di + Cw + H) + Di * cfg["hidden_size"]


def mixer_params(cfg: dict) -> int:
    """Every parameter of one mamba mixer: the two matrices, the
    convolution's taps and bias, ``A_log`` / ``D`` / ``dt_bias``, the gated
    norm."""
    H, _P, _N, Di, Cw = ref.sizes(cfg)
    return (mixer_matrices(cfg) + (cfg["mamba_d_conv"] + 1) * Cw + 3 * H
            + Di)


def attention_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    return 2 * d * d + 2 * d * kv


def shared_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * ref.router_width(cfg)


def expert_params(cfg: dict) -> int:
    """Parameters of ONE routed expert."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def params(cfg: dict) -> int:
    """Every parameter held here (norm vectors included)."""
    mamba, attn = _layers(cfg)
    layers = cfg["num_hidden_layers"]
    d = cfg["hidden_size"]
    return (mamba * mixer_params(cfg) + attn * attention_params(cfg)
            + layers * (shared_params(cfg) + router_params(cfg) + 2 * d
                        + cfg["num_local_experts"] * expert_params(cfg))
            + embedding_params(cfg) + d)


def resident_params(cfg: dict) -> int:
    """Parameters EVERY decode step multiplies whatever the router does:
    mixers, attention, shared MLPs, routers, the (tied) head.  Norm
    vectors, the convolution's taps and the batch's few embedding rows are
    left out: under a thousandth."""
    mamba, attn = _layers(cfg)
    return (mamba * mixer_matrices(cfg) + attn * attention_params(cfg)
            + cfg["num_hidden_layers"] * (shared_params(cfg)
                                          + router_params(cfg))
            + embedding_params(cfg))


def state_bytes(cfg: dict, itemsize: int) -> int:
    """Bytes of ONE slot's state in ONE mamba layer: the float32 matrix
    and the convolution's last ``d_conv - 1`` inputs at the engine's
    dtype."""
    H, P, N, _Di, Cw = ref.sizes(cfg)
    return 4 * H * P * N + (cfg["mamba_d_conv"] - 1) * Cw * itemsize


def kv_row_bytes(cfg: dict, itemsize: int) -> int:
    """K and V of one token in one attention layer."""
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_key_value_heads"] * dh * itemsize


def decode_step_terms(cfg: dict, experts_hit: float, state_rows: float,
                      kv_rows: float, itemsize: int) -> dict:
    """Bytes one decode step has to move, term by term: every resident
    matrix once; each held expert that got a token once (``experts_hit``,
    summed over layers); each live slot's state once read and once
    written in each mamba layer (``state_rows`` = live slots x mamba
    layers); each live K/V row once in each attention layer (``kv_rows``
    = live rows of ONE layer)."""
    _mamba, attn = _layers(cfg)
    return {
        "resident": itemsize * resident_params(cfg),
        "experts": itemsize * float(experts_hit) * expert_params(cfg),
        "state": 2.0 * float(state_rows) * state_bytes(cfg, itemsize),
        "kv": float(kv_rows) * attn * kv_row_bytes(cfg, itemsize),
    }


def decode_step_bytes(cfg, experts_hit, state_rows, kv_rows, itemsize):
    return sum(decode_step_terms(cfg, experts_hit, state_rows, kv_rows,
                                 itemsize).values())


# ------------------------------------------------------------------ FLOPs
def active_params(cfg: dict) -> float:
    """Parameters one token multiplies HERE in the layers (head apart): of
    the ``num_experts_per_tok`` experts a token is sent to, the share held
    here (``num_local_experts`` of the router's width) on average."""
    mamba, attn = _layers(cfg)
    here = (cfg["num_experts_per_tok"] * cfg["num_local_experts"]
            / ref.router_width(cfg))
    return (mamba * mixer_matrices(cfg) + attn * attention_params(cfg)
            + cfg["num_hidden_layers"] * (
                shared_params(cfg) + router_params(cfg)
                + here * expert_params(cfg)))


def scan_flops_a_token(cfg: dict) -> float:
    """What the recurrence itself needs for one token in all mamba layers,
    whatever runs it (the chunked form spends more): the state's update
    ``dt x B^T`` and its read-out ``S C``, 2 each an element of ``S``, and
    the convolution's taps."""
    H, P, N, _Di, Cw = ref.sizes(cfg)
    mamba, _attn = _layers(cfg)
    return mamba * (4.0 * H * P * N + 2.0 * cfg["mamba_d_conv"] * Cw)


def attention_flops(cfg: dict, keys: float) -> float:
    """Scores and weighted sums over ``keys`` query-key pairs a head, in
    all attention layers (every query head has its own scores)."""
    _mamba, attn = _layers(cfg)
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 4.0 * attn * cfg["num_attention_heads"] * dh * float(keys)


def prefill_flops(cfg: dict, tokens: int) -> float:
    """FLOPs a prefill of ``tokens`` REAL prompt tokens needs: the body a
    token, causal attention, and the head on the last position alone."""
    return ((2.0 * active_params(cfg) + scan_flops_a_token(cfg)) * tokens
            + attention_flops(cfg, tokens * (tokens + 1) / 2.0)
            + 2.0 * embedding_params(cfg))
