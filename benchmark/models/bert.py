"""Family ``bert``: builds the program's BertForPretraining from a
configuration file and maps the benchmark's leaf names onto its
parameters.  The loss is the masked-LM cross entropy over every position,
as ``tools/profile_bert.py`` runs it."""
from __future__ import annotations

from benchmark.reference import bert as reference  # noqa: F401


def build(cfg: dict, training: bool):
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    return BertForPretraining(BertConfig(
        vocab_size=cfg["padded_vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        ffn_hidden_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout=0.0, attention_dropout=0.0,
        initializer_range=cfg["initializer_range"],
        layer_norm_epsilon=cfg["layer_norm_epsilon"]))


def make_loss(model):
    import paddle_tpu.nn.functional as F
    vocab = model.bert.config.vocab_size

    def loss(ids, labels):
        pred = model(ids)[0]
        return F.cross_entropy(pred.reshape([-1, vocab]),
                               labels.reshape([-1]))
    return loss


def leaf_names(cfg: dict) -> dict:
    names = {"wte": "bert.embeddings.word_embeddings.weight",
             "wpe": "bert.embeddings.position_embeddings.weight",
             "wtt": "bert.embeddings.token_type_embeddings.weight",
             "emb_ln.w": "bert.embeddings.layer_norm.weight",
             "emb_ln.b": "bert.embeddings.layer_norm.bias",
             "mlm.w": "cls.transform.weight", "mlm.b": "cls.transform.bias",
             "mlm_ln.w": "cls.layer_norm.weight",
             "mlm_ln.b": "cls.layer_norm.bias",
             "mlm_bias": "cls.decoder_bias"}
    parts = {"qkv": "attention.qkv_proj", "out": "attention.out_proj",
             "ln1": "ln1", "fc1": "fc1", "fc2": "fc2", "ln2": "ln2"}
    for i in range(cfg["num_hidden_layers"]):
        for mine, theirs in parts.items():
            for short, long in (("w", "weight"), ("b", "bias")):
                names[f"h{i}.{mine}.{short}"] = (
                    f"bert.encoder.{i}.{theirs}.{long}")
    return names
