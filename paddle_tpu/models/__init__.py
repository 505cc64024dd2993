"""Flagship model families (the reference ships these via PaddleNLP/PaddleClas;
the benchmark configs in BASELINE.md name Llama, BERT, ResNet, ERNIE —
they live in-tree here so the framework is benchmarkable standalone)."""
from . import bert, ernie, generation, latent_moe, llama  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieForPretraining, ErnieForPretrainingPipe,
    ErnieForSequenceClassification, ErnieModel,
)
from .generation import generate  # noqa: F401
from .latent_moe import LatentMoEConfig, LatentMoEForCausalLM  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaForCausalLMPipe, LlamaModel,
)

__all__ = [
    "llama", "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "LlamaForCausalLMPipe",
    "bert", "BertConfig", "BertModel", "BertForMaskedLM",
    "BertForSequenceClassification",
    "generation", "generate",
    "latent_moe", "LatentMoEConfig", "LatentMoEForCausalLM",
    "ernie", "ErnieConfig", "ErnieModel", "ErnieForPretraining",
    "ErnieForPretrainingPipe", "ErnieForSequenceClassification",
]
