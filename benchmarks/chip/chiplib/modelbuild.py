"""Builds the program's model at a configuration's widths and hands it
the benchmark's seeded weights. The model goes through the program's
public classes (``LlamaConfig``, ``LlamaForCausalLM``); only the values
of its parameters are replaced."""
from __future__ import annotations

from . import weights as W

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


def llama_config(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import LlamaConfig

    m = cfg["model"]
    assert m["head_dim"] * m["num_attention_heads"] == m["hidden_size"]
    assert not m.get("sliding_window"), "full causal attention only"
    return LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=layers,
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=max_positions,
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        tie_word_embeddings=m["tie_word_embeddings"],
        dtype=m["torch_dtype"], **flags)


def build(cfg, layers, max_positions, seed, **flags):
    """(model, specs, keys): the program's model with every parameter
    replaced, in ONE jitted call, by the seeded weights in the served
    type. Under a mesh each leaf lands in its parameter's own sharding."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models import LlamaForCausalLM

    dtype = cfg["model"]["torch_dtype"]
    pt.set_default_dtype(dtype)  # parameters are born in the served type
    try:
        model = LlamaForCausalLM(llama_config(cfg, layers, max_positions,
                                              **flags))
    finally:
        pt.set_default_dtype("float32")
    specs = W.leaf_specs(cfg["model"], layers)
    named = dict(model.named_parameters())
    params = [named[_PARAM_OF[name].format(li)] for li, name, _, _ in specs]
    assert len(params) == len(named), (len(params), len(named))
    for p, (_, name, shape, _) in zip(params, specs):
        assert tuple(p.shape) == tuple(shape), (name, p.shape, shape)
    shardings = [p._data.sharding for p in params]
    keys = W.keys_for(seed, specs)

    def make(keys):
        return [W.make_leaf(keys[i], shape, kind, dtype)
                for i, (_, _, shape, kind) in enumerate(specs)]

    # the model's own initial values go before the seeded ones are made:
    # two sets alive at once would be the run's memory peak, and
    # ``memory_peak_bytes`` is to be the program's, not the benchmark's
    for p in params:
        p._data.delete()
    leaves = jax.jit(make, out_shardings=shardings)(jnp.asarray(keys))
    for p, a in zip(params, leaves):
        p._data = a
    return model, specs, keys, params


def reference_params(cfg, specs, keys):
    """The same weights for the reference: float32 values of the
    bf16-rounded leaves, as {'embed', 'layers': [{...}], 'norm',
    'lm_head'}."""
    import jax
    import jax.numpy as jnp

    dtype = cfg["model"]["torch_dtype"]
    out = {"layers": []}
    for i, (li, name, shape, kind) in enumerate(specs):
        fn = jax.jit(
            lambda k, shape=shape, kind=kind: W.make_leaf(
                k, shape, kind, dtype).astype(jnp.float32))
        leaf = fn(jnp.uint32(keys[i]))
        if li < 0:
            out[name] = leaf
        else:
            while len(out["layers"]) <= li:
                out["layers"].append({})
            out["layers"][li][name] = leaf
    return out
