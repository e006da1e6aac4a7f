"""Model zoo (reference: PaddleNLP llm/ recipes + python/paddle/vision/models).

Flagship families, all built on paddlepaddle_tpu.nn Layers so the same
define-by-run code runs eagerly and traces to one XLA program via
``Layer.bind_state`` (see jit/train.py / parallel/):

* llama  — Llama-3-style decoder LM (BASELINE config 3 flagship)
* bert   — BERT-base encoder for sequence classification (config 1)
* resnet — ResNet family (config 2; also in vision.models)
* moe    — Mixtral/DeepSeekMoE-style expert-parallel LM (config 5)
* longcat_flash — latent attention (MLA), zero-compute experts and the
  shortcut-connected double layer; served with one chip's share of the experts
* kimi_k2 — the same latent block (latent_attention.py), a dense leading layer,
  sigmoid-routed experts with a shared expert, YaRN positions; served likewise
"""

from .bert import (  # noqa: F401
    BertConfig,
    BertForSequenceClassification,
    BertModel,
)
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTForCausalLM,
    GPTModel,
    gpt_sharding_rules,
)
from .kimi_k2 import (  # noqa: F401
    KimiK2Config,
    KimiK2ForCausalLM,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama_sharding_rules,
)
from .longcat_flash import (  # noqa: F401
    LongcatFlashConfig,
    LongcatFlashForCausalLM,
)
from .moe import (  # noqa: F401
    MoEConfig,
    MoEForCausalLM,
)
from .resnet import (  # noqa: F401
    ResNet,
    resnet18,
    resnet34,
    resnet50,
    resnet101,
    resnet152,
)
