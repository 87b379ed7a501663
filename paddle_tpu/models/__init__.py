"""Model zoo — the framework's flagship model families.

Covers the reference's benchmark configs (BASELINE.md): GPT (hybrid
DP×TP×PP, config 3), BERT/ERNIE (DP pretrain, config 2 — the ≥35% MFU
north star), plus the vision zoo re-exported from `paddle_tpu.vision`
(ResNet/LeNet, config 1). The reference hosts these in PaddleNLP /
paddle.vision; here they are in-tree because the benchmark's cells
(`benchmarks/families/`) and the multi-chip dry-run (`__graft_entry__.py`)
train them. A model file holds a model: what trains it is
`paddle_tpu.trainer`, which no file here imports.
"""
# the one re-export, kept while `benchmarks/families/*.py` import it from
# here (a `benchmark` issue moves them: ROADMAP D1)
from ..trainer import build_train_step  # noqa: F401
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForPretraining,
    GPTModel,
    GPTPretrainingCriterion,
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
from .deepseek_v3 import (  # noqa: F401
    DeepseekV3Config,
    DeepseekV3ForCausalLM,
    DeepseekV3Model,
    deepseek_v3_tiny,
)
from .afmoe import (  # noqa: F401
    AfmoeConfig,
    AfmoeForCausalLM,
    AfmoeModel,
    afmoe_tiny,
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
