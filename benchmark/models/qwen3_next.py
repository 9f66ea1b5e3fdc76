"""Family ``qwen3_next``: builds the program's Qwen3-Next decoder
(``paddle_tpu/models/qwen3_next.py``) from a configuration file, maps the
benchmark's leaf names onto the program's parameters, and counts the
family's own serving FLOPs (ACTIVE parameters held here, the full layer's
attention over every earlier key, the delta rule's own terms).

A program without this family (the parent of the PR that brought it) cannot
run the cell: importing this file there prints ``correct: false`` and exits
non-zero at once, before any device is touched."""
from __future__ import annotations

import sys

from benchmark import qwen3next_work
from benchmark.reference import qwen3_next as reference  # noqa: F401

try:
    from paddle_tpu.models import qwen3_next as _program
except ImportError as e:                       # pragma: no cover
    print(f"[bench] the program has no qwen3_next model: {e}",
          file=sys.stderr)
    print("[bench] correct: false", file=sys.stderr, flush=True)
    raise SystemExit(1)


def build(cfg: dict, training: bool, init_weights: bool = False):
    """The program's model with every parameter an empty placeholder (the
    runner lays each leaf in).  ``A_log`` is drawn as a standard normal
    (``reference.weight_spec``) and stands for the assumed initialiser:
    what is laid into it is ``reference.assumed_leaf`` of the draw, the
    values the reference itself computes with."""
    if training:
        raise NotImplementedError("the qwen3_next family is served only")
    config = _program.Qwen3NextConfig.from_published(
        cfg, initializer_range=cfg["initializer_range"],
        init_weights=init_weights)
    model = _program.Qwen3NextForCausalLM(config)
    for name, p in model.named_parameters():
        if name.endswith("A_log"):
            p._set_value = _lay_assumed(p, name)
    return model


def _lay_assumed(param, name):
    lay = type(param)._set_value
    return lambda value: lay(param, reference.assumed_leaf(name, value))


def leaf_names(cfg: dict) -> dict:
    """benchmark leaf name -> the program's parameter name."""
    names = {"embed": "embed", "head": "head", "norm": "norm.weight"}
    every = {"ln1": "ln1.weight", "ln2": "ln2.weight",
             "gate": "mlp.gate_weight", "experts.w13": "mlp.w13",
             "experts.w2": "mlp.w2", "shared.w13": "mlp.shared_w13",
             "shared.w2": "mlp.shared_w2",
             "shared_gate": "mlp.shared_gate_weight"}
    linear = {k: f"mixer.{k}" for k in ("qkvz", "ba", "conv_w", "A_log",
                                        "dt_bias", "out_proj")}
    linear["mixer_norm"] = "mixer.norm"
    full = {k: f"mixer.{k}" for k in ("q", "k", "v", "o", "q_norm",
                                      "k_norm")}
    for i in range(cfg["num_hidden_layers"]):
        parts = dict(every, **(linear if reference.is_linear(cfg, i)
                               else full))
        for mine, theirs in parts.items():
            names[f"l{i}.{mine}"] = f"layers.{i}.{theirs}"
    return names


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs of one request on THIS chip: the parameters a token
    multiplies here (``qwen3next_work.active_params``: of the
    ``num_experts_per_tok`` experts a token is sent to, the share held
    here), the delta rule's own terms, the full layer's scores and sums
    over every earlier key, and the head only where a token is
    sampled."""
    n_dec = new_tokens - 1                     # the last token is not fed
    body = (2.0 * qwen3next_work.active_params(cfg)
            + qwen3next_work.delta_flops_a_token(cfg)) * (prompt_len + n_dec)
    keys = prompt_len * (prompt_len + 1) / 2.0 + sum(
        prompt_len + j + 1 for j in range(n_dec))
    return (body + qwen3next_work.attention_flops(cfg, keys)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * new_tokens)
