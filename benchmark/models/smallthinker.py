"""Family ``smallthinker``: builds the program's SmallThinker decoder
(``paddle_tpu/models/smallthinker.py``) from a configuration file, maps the
benchmark's leaf names onto the program's parameters, and counts the
family's own serving FLOPs (ACTIVE parameters, attention over the keys each
layer's mask lets a position see).

A program without this family (the parent of the PR that brought it) cannot
run the cell: importing this file there prints ``correct: false`` and exits
non-zero at once, before any device is touched."""
from __future__ import annotations

import sys

from benchmark import smallthinker_work
from benchmark.reference import smallthinker as reference  # noqa: F401

try:
    from paddle_tpu.models import smallthinker as _program
except ImportError as e:                       # pragma: no cover
    print(f"[bench] the program has no smallthinker model: {e}",
          file=sys.stderr)
    print("[bench] correct: false", file=sys.stderr, flush=True)
    raise SystemExit(1)


def build(cfg: dict, training: bool, init_weights: bool = False):
    """The program's model with every parameter an empty placeholder (the
    runner lays each leaf in)."""
    if training:
        raise NotImplementedError("the smallthinker family is served only")
    config = _program.SmallThinkerConfig.from_published(
        cfg, initializer_range=cfg["initializer_range"],
        init_weights=init_weights)
    return _program.SmallThinkerForCausalLM(config)


def leaf_names(cfg: dict) -> dict:
    """benchmark leaf name -> the program's parameter name."""
    names = {"embed": "embed", "head": "head", "norm": "norm.weight"}
    layer = {"ln1": "ln1.weight", "ln2": "ln2.weight", "q": "attn.q",
             "k": "attn.k", "v": "attn.v", "o": "attn.o",
             "gate": "mlp.gate_weight", "experts.w13": "mlp.w13",
             "experts.w2": "mlp.w2"}
    for i in range(cfg["num_hidden_layers"]):
        for mine, theirs in layer.items():
            names[f"l{i}.{mine}"] = f"layers.{i}.{theirs}"
    return names


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs of one request: the active parameters a position
    multiplies (``smallthinker_work.active_body_params``), the attention's
    scores and sums over the keys each layer lets a position see (every
    earlier one in a full layer, the window's in a window layer), and the
    head once a GENERATED position, never for a prompt position."""
    n = prompt_len + new_tokens
    return (2.0 * smallthinker_work.active_body_params(cfg) * n
            + smallthinker_work.attention_flops(cfg, n)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * new_tokens)
