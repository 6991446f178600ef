"""Model families built on the transformer toolkit.

The reference keeps its standalone GPT/BERT under
``apex/transformer/testing`` because they exist only to exercise the
tensor/pipeline toolkit; here they are first-class models (and the
flagship benchmark drivers).
"""

from apex_tpu.models.bert import BertConfig, BertModel
from apex_tpu.models.deepseek_v32 import DeepSeekV32Config, DeepSeekV32Model
from apex_tpu.models.gpt import GPTConfig, GPTModel
from apex_tpu.models.resnet import ResNet, ResNetConfig, resnet50
from apex_tpu.models.t5 import T5Config, T5Model

__all__ = [
    "GPTConfig",
    "GPTModel",
    "BertConfig",
    "BertModel",
    "DeepSeekV32Config",
    "DeepSeekV32Model",
    "ResNet",
    "ResNetConfig",
    "resnet50",
    "T5Config",
    "T5Model",
]
