"""Adapter for the linear-attention / latent-attention sparse-expert
decoder (``paddle_tpu/models/linear_latent_moe.py``: gated delta-rule
mixers with a matrix state and a conv tail per sequence, a latent
attention layer every fourth layer without position embedding, a dense
first layer then expert layers of which this chip holds a share;
Kimi-Linear-48B-A3B's ``config.json`` is one). See ``arch/llama_dense.py``
for what an adapter gives. Serving only: no training cell, so no train /
flash functions; its cells report ``linear_round_roofline`` where the
dense and latent cells report ``decode_step_roofline`` (whose hook cannot
count bytes that scale with live LANES), and ``linear_expert_mm_roofline``
/ ``kda_update_roofline`` for its two heaviest kinds of layer.

Its plain reference is ``reference/kda_mla_moe.py``. The leaf names below
are the program's own leaf names, the keys of the reference's ``lw`` and
the words the seeded weights are keyed on.
"""
from __future__ import annotations

import math

ITEM = 2        # bfloat16: weights, latent entries, conv tail
STATE_ITEM = 4  # the recurrent state is float32
_NORMS = ("ln_in", "ln_post", "o_norm", "kv_norm", "conv_w")
_EXPERT = ("router", "router_bias", "experts_gate_up", "experts_down",
           "shared_gate_up", "shared_down")
# what the published ``config.json`` states as flags is what the model is
_FLAGS = {"mla_use_nope": True, "moe_renormalize": True,
          "moe_router_activation_func": "sigmoid", "q_lora_rank": None,
          "tie_word_embeddings": False, "num_expert_group": 1,
          "topk_group": 1, "moe_layer_freq": 1,
          "num_nextn_predict_layers": 0, "hidden_act": "silu"}


def param_name(layer: int, name: str) -> str:
    if layer < 0:
        return name
    if name in _EXPERT:
        return f"layers.{layer}.mlp.{name}"
    return f"layers.{layer}.{name}"


def _linear(m, layers):
    """``linear_attn_config`` cut to the first ``layers`` layers."""
    la = m["linear_attn_config"]
    return dict(la, kda_layers=[i for i in la["kda_layers"] if i <= layers],
                full_attn_layers=[i for i in la["full_attn_layers"]
                                  if i <= layers])


def config_kwargs(cfg, layers, max_positions):
    """``LinearLatentMoEConfig``'s arguments at the configuration's
    widths. A configuration that states the family's flags otherwise has
    no program here."""
    m = cfg["model"]
    bad = {k: m[k] for k, v in _FLAGS.items() if m[k] != v}
    if bad:
        raise ValueError(f"models/linear_latent_moe.py is {_FLAGS}; the "
                         f"configuration says {bad}")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "first_k_dense_replace",
            "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_experts",
            "router_experts", "first_held_expert", "num_shared_experts",
            "num_experts_per_token", "routed_scaling_factor",
            "kda_chunk_size", "rms_norm_eps")
    return dict({k: m[k] for k in keys}, num_hidden_layers=layers,
                linear_attn_config=_linear(m, layers),
                max_position_embeddings=max_positions,
                dtype=m["torch_dtype"])


def build_model(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import (
        LinearLatentMoEConfig, LinearLatentMoEForCausalLM,
    )

    # initializer_range 0: the matrices are born zero at no cost; the
    # harness replaces every value with the seeded ones
    return LinearLatentMoEForCausalLM(LinearLatentMoEConfig(
        **config_kwargs(cfg, layers, max_positions), initializer_range=0.0,
        **flags))


def _kda_sizes(m):
    la = m["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def _mixer_shapes(m, kda):
    h = m["hidden_size"]
    if kda:
        H, d, K = _kda_sizes(m)
        w = H * d
        return [("qkv", (h, 3 * w)), ("conv_w", (3 * w, 1, K)),
                ("f_a", (h, d)), ("f_b", (d, w)), ("dt_bias", (w,)),
                ("A_log", (H,)), ("b", (h, H)), ("g_a", (h, d)),
                ("g_b", (d, w)), ("o_norm", (d,)), ("o", (w, h))]
    nh, dc = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    return [("q", (h, nh * (dn + dr))), ("kv_a", (h, dc + dr)),
            ("kv_norm", (dc,)), ("kv_b", (dc, nh * (dn + dv))),
            ("o", (nh * dv, h))]


def _ffn_shapes(m, dense):
    h = m["hidden_size"]
    if dense:
        f = m["intermediate_size"]
        return [("gate_up", (h, 2 * f)), ("down", (f, h))]
    w, held = m["moe_intermediate_size"], m["num_experts"]
    ws = w * m["num_shared_experts"]
    return [("router", (h, m["router_experts"])),
            ("experts_gate_up", (held, h, 2 * w)),
            ("experts_down", (held, w, h)), ("shared_gate_up", (h, 2 * ws)),
            ("shared_down", (ws, h)),
            ("router_bias", (m["router_experts"],))]


def _layer_shapes(m, li):
    kda = li + 1 in m["linear_attn_config"]["kda_layers"]
    h = m["hidden_size"]
    return _mixer_shapes(m, kda) + [("ln_in", (h,)), ("ln_post", (h,))] \
        + _ffn_shapes(m, li < m["first_k_dense_replace"])


def leaf_specs(model_cfg: dict, layers: int) -> list:
    """W is [in, out]; q, k, v fused (q first) and their three depthwise
    convolutions one ``[3 x channels, 1, taps]`` weight; gate and up fused
    (gate first), per expert too. The norm weights AND the conv weight
    are of kind ``norm`` (1 +- 0.1), everything else — ``A_log``,
    ``dt_bias`` and the router's selection bias too — ``matrix`` (so
    seeded ``A`` is about 1 and the log-decay about -0.7 a token: a state
    halves with every token, PERF.md section 7). The conv weight is not
    ``matrix`` for the reason ``arch/hybrid_ssm.py`` gives: with taps of
    std 0.02 the values and so the state's read-out ``o`` are ~1e-3, below
    the per-head norm's eps (``o^2`` ~1e-6 against 1e-5), and the layer's
    output would scale with the state instead of being normed; with taps
    of 1 +- 0.1 ``o`` is ~0.1 and the layer is the published one."""
    m = model_cfg
    h, v = m["hidden_size"], m["vocab_size"]
    out = [(-1, "embed", (v, h), "matrix")]
    for li in range(layers):
        out += [(li, n, s, "norm" if n in _NORMS else "matrix")
                for n, s in _layer_shapes(m, li)]
    return out + [(-1, "norm", (h,), "norm"), (-1, "lm_head", (h, v),
                                               "matrix")]


# -- what the algorithm requires, for the readers -------------------------------

def _count(shapes):
    return sum(math.prod(shape) for _, shape in shapes)


def kda_layers(m: dict, layers: int) -> int:
    return sum(i <= layers for i in m["linear_attn_config"]["kda_layers"])


def latent_layers(m: dict, layers: int) -> int:
    return layers - kda_layers(m, layers)


def expert_layers(m: dict, layers: int) -> int:
    return layers - min(layers, m["first_k_dense_replace"])


def lin_state_bytes_per_lane(m: dict, layers: int) -> int:
    """The matrix state one sequence keeps, whatever its length: heads x
    d x d float32 a linear-attention layer (41.9 MB at the published
    sizes and depth)."""
    H, d, _ = _kda_sizes(m)
    return kda_layers(m, layers) * H * d * d * STATE_ITEM


def kda_update_bytes(m: dict, layers: int, lanes: float) -> float:
    """The least a round moves of the state: each live lane's read once
    and written once."""
    return 2.0 * lin_state_bytes_per_lane(m, layers) * lanes


def latent_bytes_per_token(m: dict, layers: int) -> int:
    """Cache bytes a token takes in the latent layers: the latent and the
    position-free key columns, 1152 B a layer at the published widths
    (the pool stores 1280)."""
    return latent_layers(m, layers) \
        * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * ITEM


def round_experts_hit(m: dict, layers: int, counters: dict):
    """Held experts that got at least one assignment, a round's
    expert-layer call on average: the engine's ``moe_round_experts_hit``
    (counted on the device in decode and verify rounds) over those
    rounds' expert-layer calls. Seeded weights' outputs repeat and
    repeated tokens route alike, so this is measured, not taken from an
    even spread (which read 15.6 of 16 where the rounds hit far fewer).
    None without the counter."""
    calls = (counters.get("decode_steps", 0)
             + counters.get("verify_steps", 0)) * expert_layers(m, layers)
    if not calls or "moe_round_experts_hit" not in counters:
        return None
    return counters["moe_round_experts_hit"] / calls


def weight_bytes(m: dict, layers: int, experts_hit: float) -> float:
    """Bytes a round has to read of the weights held: mixers, norms, the
    dense layer, routers and shared experts whole, of each expert layer's
    held experts the ``experts_hit`` that got a token, the head (the
    embedding is gathered by row, not read)."""
    per_expert = 3 * m["hidden_size"] * m["moe_intermediate_size"]
    n = sum(_count(_layer_shapes(m, li)) for li in range(layers)) \
        - expert_layers(m, layers) * m["num_experts"] * per_expert \
        + m["hidden_size"] * (m["vocab_size"] + 1)
    return (n + expert_layers(m, layers) * experts_hit * per_expert) * ITEM


def linear_round_bytes(m: dict, layers: int, live_kv_tokens: float,
                       lanes: float, experts_hit: float) -> float:
    """Weights once (``weight_bytes``), every running lane's live latent
    entries once, every running lane's state read once and written once.
    The conv tails (3% of the state) and the activations are left out:
    the least, not what the program happens to move."""
    return weight_bytes(m, layers, experts_hit) \
        + latent_bytes_per_token(m, layers) * live_kv_tokens \
        + kda_update_bytes(m, layers, lanes)


def expert_mm_flops_bytes(m: dict, calls: float, assignments_held: float,
                          experts_hit: float):
    """(FLOP, bytes) the held experts' two grouped products require over
    ``calls`` expert-layer calls that together routed ``assignments_held``
    token-expert pairs to held experts and hit ``experts_hit`` held
    experts a call. FLOP: 2 x 3 x hidden x width a pair. Bytes: the
    weights of the experts that got at least one pair, plus each pair's
    input and output row."""
    h, w = m["hidden_size"], m["moe_intermediate_size"]
    per_expert = 3 * h * w
    flops = 2.0 * per_expert * assignments_held
    nbytes = calls * experts_hit * per_expert * ITEM \
        + assignments_held * 2 * h * ITEM
    return flops, nbytes
