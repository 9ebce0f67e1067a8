"""Flagship model families (the capability ladder).

Analog of the PaddleNLP/PaddleClas model zoos the reference's configs target
(`llm/` Llama pretrain, BERT finetune, ResNet-50) — built here as first-class
framework models so the capability rungs are runnable in-repo.
"""

from . import (  # noqa: F401
    bert,
    eva,
    gated_delta_moe_mla,
    gpt,
    hc_moe_mla,
    llama,
    mamba_hybrid,
    moe_mla,
    window_moe,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForQuestionAnswering,
    BertForSequenceClassification,
    BertModel,
)
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForSequenceClassification,
    ErnieModel,
)
from .eva import (  # noqa: F401
    EvaConfig,
    EvaDecoderLayer,
)
from .gated_delta_moe_mla import (  # noqa: F401
    GatedDeltaDecoderLayer,
    GatedDeltaMixer,
    GatedDeltaMoEMLAConfig,
    ZeroCenteredGatedNorm,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    GPTPretrainingCriterion,
)
from .hc_moe_mla import (  # noqa: F401
    HCMLAMoEDecoderLayer,
    HCMoEMLAConfig,
    HyperConnection,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    LlamaMoEBlock,
    LlamaPretrainingCriterion,
)
from .mamba_hybrid import (  # noqa: F401
    HybridMambaConfig,
    MambaDecoderLayer,
    MambaMixer,
)
from .moe_mla import (  # noqa: F401
    LatentAttention,
    MLAMoEDecoderLayer,
    MoEMLAConfig,
    RoutedExperts,
)
from .window_moe import (  # noqa: F401
    HeldExperts,
    ParallelWindowMoELayer,
    WindowedAttention,
    WindowMoEConfig,
)
