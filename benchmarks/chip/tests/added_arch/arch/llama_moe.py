"""Adapter for ``models/llama.py`` with ``moe_num_experts`` > 1: every
layer's MLP is the GShard expert layer (``MoELayer``: a router, experts
stacked [E, in, out], top-2 with capacity dropping). A test's example of
an architecture added by files alone: its leaves differ from
``llama_dense``'s in name, count and rank. Training only (the serving
engine refuses expert layers)."""
from __future__ import annotations

from chiplib import manifest
from chiplib.costs import (attn_flops_fwd, flash_fwd_bwd_bytes,  # noqa: F401
                           flash_fwd_bwd_flops)

_dense = manifest.Files().arch("llama_dense")
_MLP = {"router": "gate_weight", "w_in": "w_in", "w_out": "w_out"}


def param_name(layer, name):
    if name in _MLP:
        return f"llama.layers.{layer}.mlp.{_MLP[name]}"
    return _dense.param_name(layer, name)


def build_model(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    m = cfg["model"]
    return LlamaForCausalLM(LlamaConfig(
        **_dense.config_kwargs(cfg, layers, max_positions),
        moe_num_experts=m["num_local_experts"],
        moe_top_k=m["num_experts_per_tok"],
        moe_aux_loss_coeff=m["router_aux_loss_coef"], **flags))


def leaf_specs(model_cfg, layers):
    h, ffn = model_cfg["hidden_size"], model_cfg["intermediate_size"]
    e = model_cfg["num_local_experts"]
    out = []
    for li, name, shape, kind in _dense.leaf_specs(model_cfg, layers):
        if name == "gate_up":
            out += [(li, "router", (h, e), "matrix"),
                    (li, "w_in", (e, h, ffn), "matrix")]
        elif name == "down":
            out.append((li, "w_out", (e, ffn, h), "matrix"))
        else:
            out.append((li, name, shape, kind))
    return out


def train_flops_per_token(m, layers, seq):
    """Required: a token meets the router and ``num_experts_per_tok``
    un-gated two-matrix experts, whatever the capacity buffers hold."""
    h, d = m["hidden_size"], m["head_dim"]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    per_layer = (h * (nh + 2 * nkv) * d + nh * d * h
                 + h * m["num_local_experts"]
                 + m["num_experts_per_tok"] * 2 * h * m["intermediate_size"])
    fwd = 2 * (layers * per_layer + h * m["vocab_size"]) \
        + attn_flops_fwd(m, layers, seq) / seq
    return 3 * fwd
