"""Model exemplars.

The reference keeps models out-of-tree (PaddleNLP/PaddleFleetX); this package
ships the exemplars the north-star metric is measured on (BASELINE.json):
GPT-3 345M, Llama-2 7B/70B, an ERNIE-style MoE, and an SD UNet — plus
the BERT/ERNIE encoder family (MLM/NSP pretraining + classification).
"""

from .afmoe import AfmoeConfig, AfmoeForCausalLM  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForPretraining,
    BertForSequenceClassification, BertModel, BertPretrainingCriterion,
    ErnieModel,
)
from .gpt import (  # noqa: F401
    GPTConfig, GPTForCausalLM, GPTForCausalLMPipe, GPTModel,
    GPTPretrainingCriterion,
)
from .granite_hybrid import (  # noqa: F401
    GraniteHybridConfig, GraniteHybridForCausalLM,
)
from .llama import (LlamaConfig, LlamaForCausalLM,  # noqa: F401
                    LlamaForCausalLMPipe, LlamaModel, annotate_llama_tp)
from .mellum import MellumConfig, MellumForCausalLM  # noqa: F401
from .moe_gpt import MoEGPTConfig, MoEGPTForCausalLM  # noqa: F401
from .sdar_moe import SDARMoEConfig, SDARMoEForCausalLM  # noqa: F401
from .unet import (  # noqa: F401
    UNet2DConditionModel, UNetConfig, UNetDenoiseLoss,
)
