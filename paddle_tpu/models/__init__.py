"""Flagship model families (the reference ships these via PaddleNLP/PaddleClas;
the benchmark configs in BASELINE.md name Llama, BERT, ResNet, ERNIE —
they live in-tree here so the framework is benchmarkable standalone)."""
from . import (  # noqa: F401
    bert, conv_moe, ernie, generation, hybrid_ssm, latent_moe,
    linear_latent_moe, llama, window_moe,
)
from .bert import (  # noqa: F401
    BertConfig, BertForMaskedLM, BertForSequenceClassification, BertModel,
)
from .conv_moe import ConvMoEConfig, ConvMoEForCausalLM  # noqa: F401
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieForPretraining, ErnieForPretrainingPipe,
    ErnieForSequenceClassification, ErnieModel,
)
from .generation import generate  # noqa: F401
from .hybrid_ssm import HybridSSMConfig, HybridSSMForCausalLM  # noqa: F401
from .latent_moe import LatentMoEConfig, LatentMoEForCausalLM  # noqa: F401
from .linear_latent_moe import (  # noqa: F401
    LinearLatentMoEConfig, LinearLatentMoEForCausalLM,
)
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaForCausalLMPipe, LlamaModel,
)
from .window_moe import WindowMoEConfig, WindowMoEForCausalLM  # noqa: F401

__all__ = [
    "llama", "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "LlamaForCausalLMPipe",
    "bert", "BertConfig", "BertModel", "BertForMaskedLM",
    "BertForSequenceClassification",
    "generation", "generate",
    "latent_moe", "LatentMoEConfig", "LatentMoEForCausalLM",
    "hybrid_ssm", "HybridSSMConfig", "HybridSSMForCausalLM",
    "linear_latent_moe", "LinearLatentMoEConfig",
    "LinearLatentMoEForCausalLM",
    "window_moe", "WindowMoEConfig", "WindowMoEForCausalLM",
    "conv_moe", "ConvMoEConfig", "ConvMoEForCausalLM",
    "ernie", "ErnieConfig", "ErnieModel", "ErnieForPretraining",
    "ErnieForPretrainingPipe", "ErnieForSequenceClassification",
]
