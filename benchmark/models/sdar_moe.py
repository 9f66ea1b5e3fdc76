"""Family ``sdar_moe``: builds the program's SDAR-MoE decoder
(``paddle_tpu/models/sdar_moe.py``) from a configuration file, maps the
benchmark's leaf names onto the program's parameters, and counts the
family's own serving FLOPs (ACTIVE parameters, ONE forward a position).

A program without this family (the parent of the PR that brought it) cannot
run the cell: importing this file there prints ``correct: false`` and exits
non-zero at once, before any device is touched."""
from __future__ import annotations

import sys

from benchmark import sdar_work
from benchmark.reference import sdar_moe as reference  # noqa: F401

try:
    from paddle_tpu.models import sdar_moe as _program
except ImportError as e:                       # pragma: no cover
    print(f"[bench] the program has no sdar_moe model: {e}",
          file=sys.stderr)
    print("[bench] correct: false", file=sys.stderr, flush=True)
    raise SystemExit(1)


def build(cfg: dict, training: bool, init_weights: bool = False):
    """The program's model with every parameter an empty placeholder (the
    runner lays each leaf in)."""
    if training:
        raise NotImplementedError("the sdar_moe family is served only")
    config = _program.SdarMoeConfig.from_published(
        cfg, block_length=cfg["block_length"],
        mask_token_id=cfg["mask_token_id"],
        initializer_range=cfg["initializer_range"],
        init_weights=init_weights)
    return _program.SdarMoeForCausalLM(config)


def leaf_names(cfg: dict) -> dict:
    """benchmark leaf name -> the program's parameter name."""
    names = {"embed": "embed", "head": "head", "norm": "norm.weight"}
    layer = {"ln1": "ln1.weight", "ln2": "ln2.weight", "q": "attn.q",
             "k": "attn.k", "v": "attn.v", "o": "attn.o",
             "qn": "attn.q_norm", "kn": "attn.k_norm",
             "gate": "mlp.gate_weight", "experts.w13": "mlp.w13",
             "experts.w2": "mlp.w2"}
    for i in range(cfg["num_hidden_layers"]):
        for mine, theirs in layer.items():
            names[f"l{i}.{mine}"] = f"layers.{i}.{theirs}"
    return names


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs of one request counting ONE forward a position — the
    least any implementation of this model does, whatever its denoising
    passes and commits cost: the active parameters a position multiplies
    (``sdar_work.active_body_params``), the attention's scores and sums over
    the keys each position sees under the block-causal mask (its own block
    whole and every earlier one), and the head once a GENERATED position,
    never for a prompt position."""
    B = cfg["block_length"]
    n = prompt_len + new_tokens
    keys = sum((i // B + 1) * B for i in range(n))
    return (2.0 * sdar_work.active_body_params(cfg) * n
            + sdar_work.attention_flops(cfg, keys)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * new_tokens)
