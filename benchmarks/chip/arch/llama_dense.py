"""Adapter for the dense Llama-family decoder (``paddle_tpu/models/llama.py``:
grouped-query attention, SwiGLU, untied head; Mistral-7B-v0.3 is one).

Everything the harness knows about ONE architecture is in its adapter; a
configuration names it (``"arch"``), ``manifest.Files.arch`` finds it by
that name, and the runners, ``weights.py`` and the metric readers name no
architecture. An adapter gives:

  build_model(cfg, layers, max_positions, **flags)   the program's model,
      through the program's public classes
  param_name(layer, name)    the program's parameter for a leaf
  leaf_specs(model_cfg, layers)    (layer or -1, name, shape, kind) of
      every leaf; layers may differ, shapes may have any rank
  train_flops_per_token, flash_fwd_bwd_flops, flash_fwd_bwd_bytes,
  decode_round_bytes       what the algorithm REQUIRES, for the readers
      of the cells its configurations are in (a serving-only architecture
      needs ``decode_round_bytes`` alone)

Its plain reference is ``reference/llama_dense.py``.
"""
from __future__ import annotations

from chiplib.costs import (decode_round_bytes, flash_fwd_bwd_bytes,  # noqa: F401
                           flash_fwd_bwd_flops, train_flops_per_token)

_PARAM_OF = {
    "embed": "llama.embed_tokens.weight",
    "ln1": "llama.layers.{}.input_layernorm.weight",
    "qkv": "llama.layers.{}.self_attn.qkv_proj.weight",
    "o": "llama.layers.{}.self_attn.o_proj.weight",
    "ln2": "llama.layers.{}.post_attention_layernorm.weight",
    "gate_up": "llama.layers.{}.mlp.gate_up_proj.weight",
    "down": "llama.layers.{}.mlp.down_proj.weight",
    "norm": "llama.norm.weight",
    "lm_head": "lm_head.weight",
}


def param_name(layer: int, name: str) -> str:
    return _PARAM_OF[name].format(layer)


def config_kwargs(cfg, layers, max_positions):
    """``LlamaConfig``'s arguments at the configuration's widths."""
    m = cfg["model"]
    if m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise ValueError("models/llama.py takes head_dim = hidden / heads")
    return dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=max_positions,
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        tie_word_embeddings=m["tie_word_embeddings"],
        dtype=m["torch_dtype"])


def build_model(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if cfg["model"].get("sliding_window"):
        raise ValueError("full causal attention only: the reference "
                         "beside this adapter has no window")
    return LlamaForCausalLM(LlamaConfig(
        **config_kwargs(cfg, layers, max_positions), **flags))


def leaf_specs(model_cfg: dict, layers: int) -> list:
    """The layout ``models/llama.py`` uses: W is [in, out]; q, k, v fused
    into one ``qkv`` (q first), gate and up fused into ``gate_up`` (gate
    first)."""
    h = model_cfg["hidden_size"]
    nh = model_cfg["num_attention_heads"]
    nkv = model_cfg["num_key_value_heads"]
    d = model_cfg["head_dim"]
    ffn = model_cfg["intermediate_size"]
    v = model_cfg["vocab_size"]
    out = [(-1, "embed", (v, h), "matrix")]
    for li in range(layers):
        out += [
            (li, "ln1", (h,), "norm"),
            (li, "qkv", (h, (nh + 2 * nkv) * d), "matrix"),
            (li, "o", (nh * d, h), "matrix"),
            (li, "ln2", (h,), "norm"),
            (li, "gate_up", (h, 2 * ffn), "matrix"),
            (li, "down", (ffn, h), "matrix"),
        ]
    out += [(-1, "norm", (h,), "norm"), (-1, "lm_head", (h, v), "matrix")]
    return out
