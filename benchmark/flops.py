"""Operations and bytes the algorithm needs, from shapes alone.

Model FLOPs count every matrix product the architecture requires once
(forward) or three times (forward + backward); recomputation is never
counted.  Attention is counted as the two products QK^T and PV over the
keys a query may see: all of them (bidirectional) or half (causal).
The arithmetic is ``tools/profile_gpt.py``'s (6 N + attention per token),
with N restricted to the parameters that sit in a matrix product
(position tables, biases and LayerNorm vectors multiply nothing).
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product once per token."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    inter = cfg["intermediate_size"]
    per_layer = 4 * h * h + 2 * h * inter        # qkv + out + fc1 + fc2
    head = cfg["padded_vocab_size"] * h           # tied table, used as head
    n = layers * per_layer + head
    if cfg["family"] == "bert":
        n += h * h                                # MLM transform
    return n


def attention_flops_per_token(cfg: dict, seq: int, passes: int) -> float:
    """QK^T and PV for one query against ``seq`` positions, over all
    layers; ``passes`` is 1 (forward) or 3 (forward + backward)."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    keys = seq / 2 if cfg["causal"] else seq
    return passes * 4.0 * layers * h * keys


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg, seq, 3)


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs of one request: every prompt and output position
    through the layers against the keys before it, the head only where a
    token is sampled."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    body = matmul_params(cfg) - cfg["padded_vocab_size"] * h
    n_pos = prompt_len + new_tokens - 1          # the last token is not fed
    keys = n_pos * (n_pos + 1) / 2.0             # sum of (pos + 1)
    return (2.0 * body * n_pos + 4.0 * layers * h * keys
            + 2.0 * cfg["padded_vocab_size"] * h * new_tokens)


def flash_step_work(cfg: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) that attention needs in one train step over all
    layers, whatever kernel runs it: forward QK^T, PV; backward dV, dP,
    dQ, dK — six products of 2 b heads s keys d each (no recomputed
    product is counted).  Bytes at the kernels' boundary in bf16: forward
    reads q k v and writes o; backward reads q k v o do and writes
    dq dk dv."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    keys = seq / 2 if cfg["causal"] else seq
    flops = layers * 6 * 2.0 * batch * seq * keys * h
    nbytes = layers * 12 * batch * seq * h * 2.0
    return flops, nbytes


def paged_decode_work(cfg: dict, live_tokens: float,
                      itemsize: int) -> tuple[float, float]:
    """(FLOPs, bytes) that decode attention needs to read ``live_tokens``
    cached positions (summed over slots and steps) in every layer, whatever
    kernel runs it: the K and the V row of each position, heads x head_dim
    values of ``itemsize`` bytes each, once; QK^T and PV are 2 x head_dim
    multiply-adds a head and position.  The query, the output and the new
    row's append are a slot's one row each and are not counted."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    rows = cfg["num_hidden_layers"] * float(live_tokens)
    return 4.0 * rows * width, 2.0 * rows * width * itemsize
