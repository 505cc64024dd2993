"""Adapter for ``models/llama.py`` with a model-wide ``sliding_window``:
the dense adapter's leaves and parameter names, the window handed to
``LlamaConfig``. A test's example of a second configuration family on
the dense path (``arch/llama_dense.py`` refuses a window because its
reference has none). Its cell reports no roofline, so it carries no cost
function: live K/V under a window is per lane, which the rounds' total
does not give."""
from __future__ import annotations

from chiplib import manifest

_dense = manifest.Files().arch("llama_dense")
param_name, leaf_specs = _dense.param_name, _dense.leaf_specs


def build_model(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig(
        **_dense.config_kwargs(cfg, layers, max_positions),
        sliding_window=cfg["model"]["sliding_window"], **flags))
