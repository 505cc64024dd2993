"""Adapter for the short-convolution / grouped-query sparse-expert decoder
(``paddle_tpu/models/conv_moe.py``: gated depthwise convolutions of a few
taps beside rotary grouped-query attention with q/k head norms, a dense
first layer then expert layers of which this chip holds EVERY expert, no
shared expert, the head tied to the embedding; LFM2-24B-A2B's
``config.json`` is one). See ``arch/llama_dense.py`` for what an adapter
gives. Serving only: no training cell, so no train / flash functions; its
cell reports ``conv_round_roofline`` where the dense and latent cells
report ``decode_step_roofline`` (whose hook cannot count bytes that scale
with live LANES: the tails), ``conv_chunk_roofline`` for the prefill
chunk that takes most of its window, and ``sconv_roofline`` /
``full_attend_roofline`` / ``swa_expert_mm_roofline`` for its three kinds
of scope. What is the same as in ``arch/swa_gqa_moe.py`` (the expert
products' cost, the reduction by scope) is that file's, loaded from beside
this one.

Its plain reference is ``reference/conv_gqa_moe.py``. The leaf names below
are the program's own leaf names, the keys of the reference's ``lw`` and
the words the seeded weights are keyed on.
"""
from __future__ import annotations

import importlib.util
import math
import os

ITEM = 2     # bfloat16: weights, K/V pools, tails
BLOCK = 16   # tokens a pool block (the engine's)
CONV, FULL = "conv", "full_attention"
_NORMS = ("ln_in", "ln_post", "q_norm", "k_norm", "conv_w")
_EXPERT = ("router", "router_bias", "experts_gate_up", "experts_down")


def _beside(name):
    spec = importlib.util.spec_from_file_location(
        "chip_arch_" + name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_SWA = _beside("swa_gqa_moe")
expert_mm_flops_bytes = _SWA.expert_mm_flops_bytes
scope_roofline = _SWA.scope_roofline


def param_name(layer: int, name: str) -> str:
    if layer < 0:
        return name
    if name in _EXPERT:
        return f"layers.{layer}.mlp.{name}"
    return f"layers.{layer}.{name}"


def config_kwargs(cfg, layers, max_positions):
    """``ConvMoEConfig``'s arguments at the configuration's widths (the
    class itself raises on a published flag it is not)."""
    m = cfg["model"]
    if not m["tie_word_embeddings"] or len(m["layer_types"]) != layers:
        raise ValueError(
            f"models/conv_moe.py ties its head to the embedding and runs "
            f"len(layer_types) layers; the configuration says "
            f"tie_word_embeddings {m['tie_word_embeddings']}, "
            f"{len(m['layer_types'])} layer types, {layers} layers")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "layer_types", "num_dense_layers",
            "num_attention_heads", "num_key_value_heads", "conv_L_cache",
            "conv_bias", "num_experts", "router_experts",
            "first_held_expert", "num_experts_per_tok", "norm_topk_prob",
            "use_expert_bias", "routed_scaling_factor", "rope_parameters",
            "norm_eps")
    return dict({k: m[k] for k in keys}, num_hidden_layers=layers,
                max_position_embeddings=max_positions,
                dtype=m["torch_dtype"])


def build_model(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import ConvMoEConfig, ConvMoEForCausalLM

    # initializer_range 0: the matrices are born zero at no cost; the
    # harness replaces every value with the seeded ones
    return ConvMoEForCausalLM(ConvMoEConfig(
        **config_kwargs(cfg, layers, max_positions), initializer_range=0.0,
        **flags))


def _is_conv(m, li):
    return m["layer_types"][li] == CONV


def _is_expert(m, li):
    return li >= m["num_dense_layers"]


def _head_dim(m):
    return m["hidden_size"] // m["num_attention_heads"]


def _operator_shapes(m, li):
    h, nh, g, d = (m["hidden_size"], m["num_attention_heads"],
                   m["num_key_value_heads"], _head_dim(m))
    if _is_conv(m, li):
        return [("in_proj", (h, 3 * h)), ("conv_w", (h, m["conv_L_cache"])),
                ("out_proj", (h, h))]
    return [("qkv", (h, (nh + 2 * g) * d)), ("o", (nh * d, h)),
            ("q_norm", (d,)), ("k_norm", (d,))]


def _layer_shapes(m, li):
    h = m["hidden_size"]
    out = _operator_shapes(m, li) + [("ln_in", (h,)), ("ln_post", (h,))]
    if not _is_expert(m, li):
        f = m["intermediate_size"]
        return out + [("gate_up", (h, 2 * f)), ("down", (f, h))]
    w, held = m["moe_intermediate_size"], m["num_experts"]
    return out + [("router", (h, m["router_experts"])),
                  ("experts_gate_up", (held, h, 2 * w)),
                  ("experts_down", (held, w, h)),
                  ("router_bias", (m["router_experts"],))]


def leaf_specs(model_cfg: dict, layers: int) -> list:
    """W is [in, out]; the in-projection's thirds ``B | C | z``; q, k, v
    fused (q first); gate and up fused (gate first), per expert too; no
    ``lm_head``: the head is ``embed``. The norm weights AND the taps are
    of kind ``norm`` (1 +- 0.1), everything else — the router's selection
    bias too — ``matrix`` (std 0.02). The taps are not ``matrix``: three
    taps of 0.02 +- 0.02 make the operator's output ~1/50 of the residual
    it is added to, below what the comparison can see, and taps near 1
    weigh each of the three positions (the configuration's
    ``assumed.weights`` has the measurement)."""
    m = model_cfg
    h, v = m["hidden_size"], m["vocab_size"]
    out = [(-1, "embed", (v, h), "matrix")]
    for li in range(layers):
        out += [(li, n, s, "norm" if n in _NORMS else "matrix")
                for n, s in _layer_shapes(m, li)]
    return out + [(-1, "norm", (h,), "norm")]


# -- what the algorithm requires, for the readers -------------------------------

def _count(shapes):
    return sum(math.prod(shape) for _, shape in shapes)


def conv_layers(m: dict, layers: int) -> int:
    return sum(_is_conv(m, li) for li in range(layers))


def attn_layers(m: dict, layers: int) -> int:
    return layers - conv_layers(m, layers)


def expert_layers(m: dict, layers: int) -> int:
    return sum(_is_expert(m, li) for li in range(layers))


def kv_bytes_per_token(m: dict, layers: int) -> int:
    """Pool bytes a token takes: K and V of the attention layers alone
    (2,048 B a layer at the published widths)."""
    return attn_layers(m, layers) * m["num_key_value_heads"] * 2 \
        * _head_dim(m) * ITEM


def tail_bytes_per_lane(m: dict, layers: int) -> int:
    """What one sequence keeps outside the pool, whatever its length:
    ``conv_L_cache - 1`` rows of ``hidden`` a conv layer."""
    return conv_layers(m, layers) * (m["conv_L_cache"] - 1) \
        * m["hidden_size"] * ITEM


def sconv_weights(m: dict, layers: int) -> int:
    """Parameters of the conv operators: in-projection, taps,
    out-projection."""
    return sum(_count(_operator_shapes(m, li)) for li in range(layers)
               if _is_conv(m, li))


def sconv_flops_bytes(m: dict, layers: int, lanes: float, fed: float):
    """(FLOP, bytes) everything under ``sconv`` requires in a round that
    fed ``fed`` positions over ``lanes`` running lanes: the operators'
    weights read once, every running lane's tails read once and written
    once; 2 FLOP a (fed position, weight)."""
    n = sconv_weights(m, layers)
    return 2.0 * fed * n, \
        n * ITEM + 2 * lanes * tail_bytes_per_lane(m, layers)


def full_attend_flops_bytes(m: dict, layers: int, live_kv_tokens: float,
                            fed_per_lane: float):
    """(FLOP, bytes) the attention layers' read requires: every running
    lane's live K/V once; 4 x head FLOP a (query head, fed position of the
    lane, live token of the lane)."""
    nbytes = kv_bytes_per_token(m, layers) * live_kv_tokens
    flops = 4.0 * attn_layers(m, layers) * m["num_attention_heads"] \
        * fed_per_lane * live_kv_tokens * _head_dim(m)
    return flops, nbytes


def round_experts_hit(m: dict, layers: int, counters: dict):
    """Held experts that got at least one assignment, a round's
    expert-layer call on average: the engine's ``moe_round_experts_hit``
    (counted on the device in decode and verify rounds) over those
    rounds' expert-layer calls. None without the counter."""
    calls = (counters.get("decode_steps", 0)
             + counters.get("verify_steps", 0)) * expert_layers(m, layers)
    if not calls or "moe_round_experts_hit" not in counters:
        return None
    return counters["moe_round_experts_hit"] / calls


def chunk_experts_hit(m: dict, tokens: float) -> float:
    """Held experts a call of ``tokens`` real tokens is expected to hit
    under even routing: ``E (1 - (1 - 1/E)^(tokens x k))`` of the router's
    ``E``, the held share of it (the engine counts the hit experts in
    rounds alone)."""
    E = m["router_experts"]
    return m["num_experts"] * (
        1.0 - (1.0 - 1.0 / E) ** (tokens * m["num_experts_per_tok"]))


def _expert_params(m):
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def weight_bytes(m: dict, layers: int, experts_hit: float) -> float:
    """Bytes a call has to read of the weights held: the operators, norms,
    the dense layer and the routers whole, of each expert layer's held
    experts the ``experts_hit`` that got a token, the head — the tied
    table, read whole by the head (the embedding's rows are gathered)."""
    n = sum(_count(_layer_shapes(m, li)) for li in range(layers)) \
        - expert_layers(m, layers) * m["num_experts"] * _expert_params(m) \
        + m["hidden_size"] * (m["vocab_size"] + 1)
    return (n + expert_layers(m, layers) * experts_hit
            * _expert_params(m)) * ITEM


def active_params(m: dict, layers: int) -> float:
    """Weights a fed position multiplies by, the head left out: the
    operators, the dense layer, the routers, ``num_experts_per_tok``
    experts an expert layer."""
    n = sum(_count(s for s in _layer_shapes(m, li) if len(s[1]) == 2)
            for li in range(layers))
    return n + expert_layers(m, layers) * m["num_experts_per_tok"] \
        * _expert_params(m)


def conv_round_bytes(m: dict, layers: int, live_kv_tokens: float,
                     lanes: float, experts_hit: float) -> float:
    """Weights once (``weight_bytes``), every running lane's live K/V of
    the attention layers once, every running lane's tails once. The fed
    positions' writes and the activations are left out: the least, not
    what the program happens to move."""
    return weight_bytes(m, layers, experts_hit) \
        + kv_bytes_per_token(m, layers) * live_kv_tokens \
        + tail_bytes_per_lane(m, layers) * lanes


def conv_chunk_flops_bytes(m: dict, layers: int, tokens: float,
                           live: float):
    """(FLOP, bytes) one lane's prefill chunk of ``tokens`` real tokens
    requires with ``live`` tokens of the lane in the pool: the weights
    with the experts such a chunk hits, the lane's live K/V, its tail read
    and written; 2 FLOP a (token, active weight), the attention layers'
    scores and sums, the head at ONE position."""
    nbytes = weight_bytes(m, layers, chunk_experts_hit(m, tokens)) \
        + kv_bytes_per_token(m, layers) * live \
        + 2 * tail_bytes_per_lane(m, layers)
    flops = 2.0 * tokens * active_params(m, layers) \
        + full_attend_flops_bytes(m, layers, live, tokens)[0] \
        + 2.0 * m["hidden_size"] * m["vocab_size"]
    return flops, nbytes


def cache_bytes_held(m: dict, layers: int, live_kv_tokens: float,
                     lanes: float) -> float:
    """Device bytes of cache the running lanes hold: their pool blocks as
    stored (whole blocks: a lane's last block is half full on average)
    and their tails."""
    return kv_bytes_per_token(m, layers) \
        * (live_kv_tokens + lanes * BLOCK / 2) \
        + tail_bytes_per_lane(m, layers) * lanes
