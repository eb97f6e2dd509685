"""Model zoo (reference: python/paddle/vision/models/ + PaddleNLP model
families the fork serves).  Flagship: ERNIE/BERT-base (bert.py)."""
from .lenet import LeNet
from .transformer_block import (ParallelMLP, ParallelSelfAttention,
                                ParallelTransformerLayer)
from .ernie import (ERNIE_PRESETS, ErnieConfig, ErnieForMaskedLM,
                    ErnieForPretraining, ErnieForSequenceClassification,
                    ErnieModel, ernie_pretrain_loss)
from .gpt import (GPT_PRESETS, GPTConfig, GPTForCausalLM, GPTModel,
                  gpt_lm_loss)

__all__ = [
    "LeNet", "ParallelMLP", "ParallelSelfAttention",
    "ParallelTransformerLayer", "ERNIE_PRESETS", "ErnieConfig",
    "ErnieForMaskedLM", "ErnieForPretraining",
    "ErnieForSequenceClassification", "ErnieModel", "ernie_pretrain_loss",
    "GPT_PRESETS", "GPTConfig", "GPTForCausalLM", "GPTModel", "gpt_lm_loss",
    # lazy (__getattr__) exports — listed so the API guard covers them
    "BertModel", "BertForSequenceClassification", "BertForPretraining",
    "BertConfig", "ResNet", "resnet18", "resnet50",
    "LlamaModel", "LlamaForCausalLM", "LlamaConfig", "LlamaDecoderLayer",
    "LlamaMLP", "LLAMA_PRESETS", "llama_lm_loss",
    "GPTMoEModel", "GPTMoEForCausalLM", "MoEConfig",
    "LatentMoEConfig", "LatentMoEModel", "LatentMoEForCausalLM",
    "LongcatFlashConfig", "LongcatFlashModel", "LongcatFlashForCausalLM",
    "AutoModel", "AutoConfig", "PretrainedMixin",
]


def __getattr__(name):
    if name in ("BertModel", "BertForSequenceClassification",
                "BertForPretraining", "BertConfig"):
        from . import bert

        return getattr(bert, name)
    if name in ("ResNet", "resnet18", "resnet50"):
        from ..vision import models as _vm

        return getattr(_vm, name)
    if name in ("LlamaModel", "LlamaForCausalLM", "LlamaConfig",
                "LlamaDecoderLayer", "LlamaMLP", "LLAMA_PRESETS",
                "llama_lm_loss"):
        from . import llama

        return getattr(llama, name)
    if name in ("GPTMoEModel", "GPTMoEForCausalLM", "MoEConfig"):
        from . import gpt_moe

        return getattr(gpt_moe, name)
    if name in ("LatentMoEConfig", "LatentMoEModel",
                "LatentMoEForCausalLM"):
        from . import latent_moe

        return getattr(latent_moe, name)
    if name in ("LongcatFlashConfig", "LongcatFlashModel",
                "LongcatFlashForCausalLM"):
        from . import longcat_flash

        return getattr(longcat_flash, name)
    if name in ("AutoModel", "AutoConfig", "PretrainedMixin"):
        from . import pretrained

        return getattr(pretrained, name)
    raise AttributeError(name)
