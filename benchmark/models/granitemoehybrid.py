"""Family ``granitemoehybrid``: builds the program's Granite-4.0-H decoder
(``paddle_tpu/models/granitemoehybrid.py``) from a configuration file, maps
the benchmark's leaf names onto the program's parameters, and counts the
family's own serving FLOPs (ACTIVE parameters held here only).

A program without this family (the parent of the PR that brought it) cannot
run the cell: importing this file there prints ``correct: false`` and exits
non-zero at once, before any device is touched."""
from __future__ import annotations

import sys

from benchmark import ssm_work
from benchmark.reference import granitemoehybrid as reference  # noqa: F401

try:
    from paddle_tpu.models import granitemoehybrid as _program
except ImportError as e:                       # pragma: no cover
    print(f"[bench] the program has no granitemoehybrid model: {e}",
          file=sys.stderr)
    print("[bench] correct: false", file=sys.stderr, flush=True)
    raise SystemExit(1)


def build(cfg: dict, training: bool, init_weights: bool = False):
    """The program's model with every parameter an empty placeholder (the
    runner lays each leaf in).  ``A_log`` and ``dt_bias`` are drawn as
    standard normals (``reference.weight_spec``) and stand for the family's
    initialisers: what is laid into those two parameters is
    ``reference.assumed_leaf`` of the draw, the values the reference itself
    computes with."""
    if training:
        raise NotImplementedError("the granitemoehybrid family is served "
                                  "only")
    config = _program.GraniteMoeHybridConfig.from_published(
        cfg, initializer_range=cfg["initializer_range"],
        init_weights=init_weights)
    model = _program.GraniteMoeHybridForCausalLM(config)
    for name, p in model.named_parameters():
        if name.endswith(("A_log", "dt_bias")):
            p._set_value = _lay_assumed(p, name)
    return model


def _lay_assumed(param, name):
    lay = type(param)._set_value
    return lambda value: lay(param, reference.assumed_leaf(name, value))


def leaf_names(cfg: dict) -> dict:
    """benchmark leaf name -> the program's parameter name."""
    names = {"embed": "embed", "norm": "norm.weight"}
    every = {"ln1": "ln1.weight", "ln2": "ln2.weight",
             "gate": "mlp.gate_weight", "experts.w13": "mlp.w13",
             "experts.w2": "mlp.w2", "shared.w13": "mlp.shared_w13",
             "shared.w2": "mlp.shared_w2"}
    mamba = {k: f"mixer.{k}" for k in ("in_proj", "conv_w", "conv_b",
                                       "A_log", "dt_bias", "D", "out_proj")}
    mamba["mixer_norm"] = "mixer.norm"
    attn = {k: f"mixer.{k}" for k in "qkvo"}
    for i in range(cfg["num_hidden_layers"]):
        parts = dict(every, **(mamba if reference.is_mamba(cfg, i) else attn))
        for mine, theirs in parts.items():
            names[f"l{i}.{mine}"] = f"layers.{i}.{theirs}"
    return names


def serve_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """Forward FLOPs of one request on THIS chip: the parameters a token
    multiplies here (``ssm_work.active_params``: of the
    ``num_experts_per_tok`` experts a token is sent to, the share held
    here), the state-space recurrence's own terms, the attention layers'
    scores and sums over the keys each position sees, and the head only
    where a token is sampled."""
    n_dec = new_tokens - 1                     # the last token is not fed
    body = (2.0 * ssm_work.active_params(cfg) + ssm_work.scan_flops_a_token(
        cfg)) * (prompt_len + n_dec)
    keys = prompt_len * (prompt_len + 1) / 2.0 + sum(
        prompt_len + j + 1 for j in range(n_dec))
    return (body + ssm_work.attention_flops(cfg, keys)
            + 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * new_tokens)
