"""Bytes and operations a Qwen3-Next (``qwen3_next``) model NEEDS, from
shapes alone (the twin of ``ssm_work.py`` for this family): what
``gdn_decode_hbm_roofline.serve`` and ``gdn_prefill_roofline.serve`` divide
by a measured time, and what the family's ``serve_flops`` counts.
Everything is counted for the experts HELD here (``num_experts`` of the
router's ``published.num_experts``) and the vocabulary rows held here
(``vocab_size``).
"""
from __future__ import annotations

from benchmark.reference import qwen3_next as ref


def _layers(cfg):
    """(Gated DeltaNet layers, full attention layers)."""
    linear = sum(1 for i in range(cfg["num_hidden_layers"])
                 if ref.is_linear(cfg, i))
    return linear, cfg["num_hidden_layers"] - linear


def mixer_matrices(cfg: dict) -> int:
    """``W_qkvz``, ``W_ba`` and ``W_out`` of one Gated DeltaNet layer."""
    Hk, Hv, dk, dv, _Cw = ref.linear_sizes(cfg)
    d = cfg["hidden_size"]
    return d * (2 * Hk * dk + 2 * Hv * dv) + d * 2 * Hv + Hv * dv * d


def mixer_params(cfg: dict) -> int:
    """Every parameter of one Gated DeltaNet mixer: the three matrices, the
    convolution's taps, ``A_log`` / ``dt_bias``, the output norm."""
    _Hk, Hv, _dk, dv, Cw = ref.linear_sizes(cfg)
    return (mixer_matrices(cfg) + cfg["linear_conv_kernel_dim"] * Cw
            + 2 * Hv + dv)


def attention_matrices(cfg: dict) -> int:
    """``W_q`` (query and gate), ``W_k``, ``W_v``, ``W_o`` of the full
    layer."""
    d, H, dh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * dh
    return d * 2 * H * dh + 2 * d * kv + H * dh * d


def attention_params(cfg: dict) -> int:
    return attention_matrices(cfg) + 2 * cfg["head_dim"]


def shared_params(cfg: dict) -> int:
    """The shared expert and its gate ``W_sg [d, 1]``."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["shared_expert_intermediate_size"] + d


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * ref.router_width(cfg)


def expert_params(cfg: dict) -> int:
    """Parameters of ONE routed expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def embedding_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["hidden_size"]


def layer_params(cfg: dict, layer: int) -> int:
    """Every parameter of one layer held here: its mixer, the router, the
    held experts, the shared expert and its gate, the two norms."""
    mixer = (mixer_params(cfg) if ref.is_linear(cfg, layer)
             else attention_params(cfg))
    return (mixer + router_params(cfg) + shared_params(cfg)
            + 2 * cfg["hidden_size"]
            + ref.held_range(cfg)[1] * expert_params(cfg))


def params(cfg: dict) -> int:
    """Every parameter held here: the layers, the embedding, the untied
    head, the final norm."""
    return (sum(layer_params(cfg, i) for i in range(cfg["num_hidden_layers"]))
            + 2 * embedding_params(cfg) + cfg["hidden_size"])


def resident_params(cfg: dict) -> int:
    """Parameters EVERY decode pass multiplies whatever the router does:
    the mixers' and the attention's matrices, the shared experts and their
    gates, the routers, the head.  Norm vectors, the convolution's taps and
    the pass's few embedding rows are left out: under a thousandth."""
    linear, full = _layers(cfg)
    return (linear * mixer_matrices(cfg) + full * attention_matrices(cfg)
            + cfg["num_hidden_layers"] * (shared_params(cfg)
                                          + router_params(cfg))
            + embedding_params(cfg))


def state_bytes(cfg: dict, itemsize: int) -> int:
    """Bytes of ONE slot's state in ONE Gated DeltaNet layer: the float32
    ``d_k x d_v`` matrix of every value head and the convolution's last
    ``K - 1`` inputs at the engine's dtype."""
    _Hk, Hv, dk, dv, Cw = ref.linear_sizes(cfg)
    return 4 * Hv * dk * dv + (cfg["linear_conv_kernel_dim"] - 1) * Cw * \
        itemsize


def kv_row_bytes(cfg: dict, itemsize: int) -> int:
    """K and V of one position in the full layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def decode_pass_bytes(cfg: dict, experts_hit: float, state_rows: float,
                      kv_rows: float, itemsize: int) -> dict:
    """Bytes decode passes have to move, term by term: every resident
    matrix and the head once; each held expert that got a token once
    (``experts_hit``, summed over layers); each live slot's state and
    convolution rows once read and once written a Gated DeltaNet layer
    (``state_rows`` = live slots x those layers); each live K/V row once
    in each full layer (``kv_rows`` = live rows of ONE layer)."""
    _linear, full = _layers(cfg)
    return {
        "resident": itemsize * resident_params(cfg),
        "experts": itemsize * float(experts_hit) * expert_params(cfg),
        "state": 2.0 * float(state_rows) * state_bytes(cfg, itemsize),
        "kv": float(kv_rows) * full * kv_row_bytes(cfg, itemsize),
    }


def decode_pass_needed(cfg, experts_hit, state_rows, kv_rows, itemsize):
    return sum(decode_pass_bytes(cfg, experts_hit, state_rows, kv_rows,
                                 itemsize).values())


def prefill_bytes(cfg: dict, tokens: int, itemsize: int) -> int:
    """Bytes a prefill of ``tokens`` REAL prompt tokens has to move: every
    resident matrix and the head once, every held expert once, the K/V
    rows it writes to the full layers' pages and the state it writes to
    each Gated DeltaNet layer."""
    linear, full = _layers(cfg)
    return (itemsize * (resident_params(cfg) + cfg["num_hidden_layers"]
                        * ref.held_range(cfg)[1] * expert_params(cfg))
            + tokens * full * kv_row_bytes(cfg, itemsize)
            + linear * state_bytes(cfg, itemsize))


# ------------------------------------------------------------------ FLOPs
def active_params(cfg: dict) -> float:
    """Parameters one token multiplies HERE in the layers (head apart): of
    the ``num_experts_per_tok`` experts a token is sent to, the share held
    here on average."""
    linear, full = _layers(cfg)
    here = (cfg["num_experts_per_tok"] * ref.held_range(cfg)[1]
            / ref.router_width(cfg))
    return (linear * mixer_matrices(cfg) + full * attention_matrices(cfg)
            + cfg["num_hidden_layers"] * (
                shared_params(cfg) + router_params(cfg)
                + here * expert_params(cfg)))


def delta_flops_a_token(cfg: dict) -> float:
    """What the delta rule itself needs for one token in all Gated DeltaNet
    layers, whatever runs it (the chunked form spends more): the read ``S^T
    k``, the write ``k delta^T`` and the read-out ``S^T q``, 2 each an
    element of a value head's ``d_k x d_v`` state."""
    _Hk, Hv, dk, dv, _Cw = ref.linear_sizes(cfg)
    return _layers(cfg)[0] * 6.0 * Hv * dk * dv


def attention_flops(cfg: dict, keys: float) -> float:
    """Scores and weighted sums over ``keys`` query-key pairs a query head,
    in all full layers."""
    full = _layers(cfg)[1]
    return 4.0 * full * cfg["num_attention_heads"] * cfg["head_dim"] * \
        float(keys)


def prefill_flops(cfg: dict, tokens: int) -> float:
    """FLOPs a prefill of ``tokens`` REAL prompt tokens needs: the body a
    token, the delta rule, causal attention, and the head on the last
    position alone."""
    return ((2.0 * active_params(cfg) + delta_flops_a_token(cfg)) * tokens
            + attention_flops(cfg, tokens * (tokens + 1) / 2.0)
            + 2.0 * embedding_params(cfg))
