"""Model zoo — the framework's flagship model families.

Covers the reference's benchmark configs (BASELINE.md): GPT (hybrid
DP×TP×PP, config 3), BERT/ERNIE (DP pretrain, config 2 — the ≥35% MFU
north star), plus the vision zoo re-exported from `paddle_tpu.vision`
(ResNet/LeNet, config 1). The reference hosts these in PaddleNLP /
paddle.vision; here they are in-tree because they double as the perf
harness (`bench.py`) and the multi-chip dry-run (`__graft_entry__.py`).
"""
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForPretraining,
    GPTModel,
    GPTPretrainingCriterion,
    build_train_step,
    gpt_tiny,
    gpt_345m,
    gpt_760m,
    gpt_1p3b,
    gpt_2p6b,
    gpt_6p7b,
    ernie_10b,
)
from .keye import (  # noqa: F401
    KeyeConfig,
    KeyeForCausalLM,
    KeyeModel,
    keye_tiny,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertModel,
    bert_base,
    bert_tiny,
)
from .transformer import (  # noqa: F401
    TransformerModel,
    sinusoid_position_encoding,
)
from .ctr import DeepFM, WideDeep, build_ctr_train_step  # noqa: F401
