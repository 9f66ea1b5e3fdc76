"""Flagship model families (parity targets from BASELINE.json configs).

Reference counterparts live in PaddleNLP/PaddleClas model zoos built on the
reference framework's fleet meta-parallel layers
(python/paddle/distributed/fleet/meta_parallel/parallel_layers/mp_layers.py);
here each model is built TPU-first on paddle_tpu's mesh-sharded layers.
"""
from paddle_tpu.models import bert, ernie, gpt, vit  # noqa: F401
from paddle_tpu.models.bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    BertPretrainingCriterion,
    bert_base,
    bert_large,
    bert_tiny,
)
from paddle_tpu.models.ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForPretraining,
    ErnieForSequenceClassification,
    ErnieModel,
    ernie_3_0_base,
    ernie_3_0_medium,
    ernie_tiny,
)
from paddle_tpu.models.gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    GPTPretrainingCriterion,
    gpt3_1p3b,
    gpt3_tiny,
)
from paddle_tpu.models.vit import (  # noqa: F401
    ViT,
    ViTConfig,
    VisionTransformer,
    vit_b_16,
    vit_l_16,
    vit_tiny,
)
from paddle_tpu.models.deepfm import DeepFM, DeepFMCriterion, SparseEmbeddingBag  # noqa: F401
from paddle_tpu.models.granitemoehybrid import (  # noqa: F401
    GraniteMoeHybridConfig,
    GraniteMoeHybridForCausalLM,
)
from paddle_tpu.models.qwen3_next import (  # noqa: F401
    Qwen3NextConfig,
    Qwen3NextForCausalLM,
)
from paddle_tpu.models.sdar_moe import (  # noqa: F401
    SdarMoeConfig,
    SdarMoeForCausalLM,
)
from paddle_tpu.models.smallthinker import (  # noqa: F401
    SmallThinkerConfig,
    SmallThinkerForCausalLM,
)
