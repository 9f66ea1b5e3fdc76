"""Family ``gpt``: builds the program's GPT from a configuration file and
maps the benchmark's leaf names onto the program's parameters."""
from __future__ import annotations

from benchmark.reference import gpt as reference  # noqa: F401


def program_config(cfg: dict, training: bool):
    from paddle_tpu.models.gpt import GPTConfig
    return GPTConfig(
        vocab_size=cfg["padded_vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        ffn_hidden_size=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        dropout=0.0, attention_dropout=0.0,
        initializer_range=cfg["initializer_range"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        use_recompute=bool(training and cfg.get("use_recompute")))


def build(cfg: dict, training: bool):
    from paddle_tpu.models.gpt import GPTForCausalLM
    return GPTForCausalLM(program_config(cfg, training))


def make_loss(model):
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    crit = GPTPretrainingCriterion()
    return lambda ids, labels: crit(model(ids), labels)


def leaf_names(cfg: dict) -> dict:
    """benchmark leaf name -> the program's parameter name."""
    names = {"wte": "gpt.embeddings.word_embeddings.weight",
             "wpe": "gpt.embeddings.position_embeddings.weight",
             "lnf.w": "gpt.final_ln.weight", "lnf.b": "gpt.final_ln.bias"}
    parts = {"ln1": "ln1", "qkv": "attn.qkv_proj", "out": "attn.out_proj",
             "ln2": "ln2", "fc1": "mlp.fc1", "fc2": "mlp.fc2"}
    for i in range(cfg["num_hidden_layers"]):
        for mine, theirs in parts.items():
            for short, long in (("w", "weight"), ("b", "bias")):
                names[f"h{i}.{mine}.{short}"] = (
                    f"gpt.layers.{i}.{theirs}.{long}")
    return names
