"""Adapter for the window-attention / full-attention sparse-expert decoder
(``paddle_tpu/models/window_moe.py``: window layers with a learned sink
and full layers with other key/value head counts, keys wider than values,
a rotary embedding on part of each head, a dense first layer then expert
layers of which this chip holds a share and no shared expert; MiMo-V2.5's
``config.json`` is one). See ``arch/llama_dense.py`` for what an adapter
gives. Serving only: no training cell, so no train / flash functions; its
cell reports ``swa_round_roofline`` where the dense and latent cells
report ``decode_step_roofline`` (whose hook cannot count bytes that scale
with live LANES: the rings), and ``window_attend_roofline`` /
``full_attend_roofline`` / ``swa_expert_mm_roofline`` for its three
heaviest scopes.

Its plain reference is ``reference/swa_gqa_moe.py``. The leaf names below
are the program's own leaf names, the keys of the reference's ``lw`` and
the words the seeded weights are keyed on.
"""
from __future__ import annotations

import math

ITEM = 2     # bfloat16: weights, K/V pools, rings
BLOCK = 16   # tokens a pool block (the engine's)
_NORMS = ("ln_in", "ln_post", "sink")
_EXPERT = ("router", "router_bias", "experts_gate_up", "experts_down")
# what the published ``config.json`` states as flags is what the model is
_FLAGS = {"add_swa_attention_sink_bias": True,
          "add_full_attention_sink_bias": False, "attention_bias": False,
          "attention_projection_layout": "fused_qkv",
          "scoring_func": "sigmoid", "topk_method": "noaux_tc",
          "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
          "n_shared_experts": None, "tie_word_embeddings": False,
          "hidden_act": "silu"}


def param_name(layer: int, name: str) -> str:
    if layer < 0:
        return name
    if name in _EXPERT:
        return f"layers.{layer}.mlp.{name}"
    return f"layers.{layer}.{name}"


def config_kwargs(cfg, layers, max_positions):
    """``WindowMoEConfig``'s arguments at the configuration's widths. A
    configuration that states the family's flags otherwise, or a window
    under two keys that differ, has no program here."""
    m = cfg["model"]
    bad = {k: m[k] for k, v in _FLAGS.items() if m[k] != v}
    if m["sliding_window"] != m["sliding_window_size"] \
            or m["attention_chunk_size"] != m["sliding_window"]:
        bad["sliding_window"] = (m["sliding_window"],
                                 m["sliding_window_size"],
                                 m["attention_chunk_size"])
    if bad:
        raise ValueError(f"models/window_moe.py is {_FLAGS} under ONE "
                         f"window; the configuration says {bad}")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
            "v_head_dim", "swa_num_attention_heads", "swa_head_dim",
            "swa_v_head_dim", "partial_rotary_factor", "rope_theta",
            "swa_rope_theta", "sliding_window", "attention_value_scale",
            "n_routed_experts", "router_experts", "first_held_expert",
            "num_experts_per_tok", "routed_scaling_factor",
            "window_ring_len", "layernorm_epsilon")
    return dict({k: m[k] for k in keys}, num_hidden_layers=layers,
                hybrid_layer_pattern=m["hybrid_layer_pattern"][:layers],
                moe_layer_freq=m["moe_layer_freq"][:layers],
                max_position_embeddings=max_positions,
                dtype=m["torch_dtype"])


def build_model(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import WindowMoEConfig, WindowMoEForCausalLM

    # initializer_range 0: the matrices are born zero at no cost; the
    # harness replaces every value with the seeded ones
    return WindowMoEForCausalLM(WindowMoEConfig(
        **config_kwargs(cfg, layers, max_positions), initializer_range=0.0,
        **flags))


def _is_window(m, li):
    return bool(m["hybrid_layer_pattern"][li])


def _is_expert(m, li):
    return bool(m["moe_layer_freq"][li])


def _kv_heads(m, window):
    return m["swa_num_key_value_heads" if window else "num_key_value_heads"]


def _layer_shapes(m, li):
    h, nh = m["hidden_size"], m["num_attention_heads"]
    dk, dv = m["head_dim"], m["v_head_dim"]
    window = _is_window(m, li)
    g = _kv_heads(m, window)
    out = [("qkv", (h, nh * dk + g * (dk + dv))), ("o", (nh * dv, h)),
           ("ln_in", (h,)), ("ln_post", (h,))]
    if window:
        out.append(("sink", (nh,)))
    if not _is_expert(m, li):
        f = m["intermediate_size"]
        return out + [("gate_up", (h, 2 * f)), ("down", (f, h))]
    w, held = m["moe_intermediate_size"], m["n_routed_experts"]
    return out + [("router", (h, m["router_experts"])),
                  ("experts_gate_up", (held, h, 2 * w)),
                  ("experts_down", (held, w, h)),
                  ("router_bias", (m["router_experts"],))]


def leaf_specs(model_cfg: dict, layers: int) -> list:
    """W is [in, out]; q, k, v fused (q first); gate and up fused (gate
    first), per expert too. The norm weights AND the sink are of kind
    ``norm`` (1 +- 0.1), everything else — the router's selection bias
    too — ``matrix`` (std 0.02). The sink is not ``matrix``: a learned
    sink is a logit of the scores' own size, and one of 0.02 +- 0.02
    beside seeded scores of std ~1.6 is a sink that says nothing (the
    configuration's ``assumed.weights`` has the measurement)."""
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


def window_layers(m: dict, layers: int) -> int:
    return sum(m["hybrid_layer_pattern"][:layers])


def full_layers(m: dict, layers: int) -> int:
    return layers - window_layers(m, layers)


def expert_layers(m: dict, layers: int) -> int:
    return sum(m["moe_layer_freq"][:layers])


def _kv_row_bytes(m, window):
    """One position's K and V in one layer of a kind."""
    return _kv_heads(m, window) * (m["head_dim"] + m["v_head_dim"]) * ITEM


def full_kv_bytes_per_token(m: dict, layers: int) -> int:
    """Pool bytes a token takes: the full layers alone (2,560 B a layer
    at the published widths)."""
    return full_layers(m, layers) * _kv_row_bytes(m, False)


def every_layer_kv_bytes_per_token(m: dict, layers: int) -> int:
    """What a token would take if the window layers kept every token
    too, each kind in a pool of its own shape."""
    return full_kv_bytes_per_token(m, layers) \
        + window_layers(m, layers) * _kv_row_bytes(m, True)


def ring_bytes_per_lane(m: dict, layers: int) -> int:
    """The rings one sequence keeps, whatever its length:
    ``window_ring_len`` slots a window layer."""
    return window_layers(m, layers) * m["window_ring_len"] \
        * _kv_row_bytes(m, True)


def window_live_bytes(m: dict, layers: int, live_kv_tokens: float,
                      lanes: float) -> float:
    """Ring bytes a round's window layers have to read: each running
    lane's LIVE slots, at most ``sliding_window`` (a lane shorter than
    the window has fewer; ``live_kv_tokens / lanes`` is the mean
    length)."""
    if not lanes:
        return 0.0
    live = min(m["sliding_window"], live_kv_tokens / lanes)
    return window_layers(m, layers) * lanes * live * _kv_row_bytes(m, True)


def window_attend_flops_bytes(m: dict, layers: int, live_kv_tokens: float,
                              lanes: float, fed: float):
    """(FLOP, bytes) the window layers' ring write, band read and sink
    softmax require in a round that fed ``fed`` positions: the live slots
    read once, the fed positions written once; 2 x (head_dim +
    v_head_dim) FLOP a (query head, fed position, live slot)."""
    nbytes = window_live_bytes(m, layers, live_kv_tokens, lanes) \
        + window_layers(m, layers) * fed * _kv_row_bytes(m, True)
    live = min(m["sliding_window"], live_kv_tokens / lanes) if lanes else 0
    flops = 2.0 * window_layers(m, layers) * m["num_attention_heads"] \
        * fed * live * (m["head_dim"] + m["v_head_dim"])
    return flops, nbytes


def full_attend_flops_bytes(m: dict, layers: int, live_kv_tokens: float,
                            fed_per_lane: float):
    """(FLOP, bytes) the full layers' read requires: every running
    lane's live K/V once; 2 x (head_dim + v_head_dim) FLOP a (query
    head, fed position of the lane, live token of the lane)."""
    nbytes = full_kv_bytes_per_token(m, layers) * live_kv_tokens
    flops = 2.0 * full_layers(m, layers) * m["num_attention_heads"] \
        * fed_per_lane * live_kv_tokens * (m["head_dim"] + m["v_head_dim"])
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


def weight_bytes(m: dict, layers: int, experts_hit: float) -> float:
    """Bytes a round has to read of the weights held: attention, norms,
    sinks, the dense layer and the routers whole, of each expert layer's
    held experts the ``experts_hit`` that got a token, the head (the
    embedding is gathered by row, not read)."""
    per_expert = 3 * m["hidden_size"] * m["moe_intermediate_size"]
    n = sum(_count(_layer_shapes(m, li)) for li in range(layers)) \
        - expert_layers(m, layers) * m["n_routed_experts"] * per_expert \
        + m["hidden_size"] * (m["vocab_size"] + 1)
    return (n + expert_layers(m, layers) * experts_hit * per_expert) * ITEM


def swa_round_bytes(m: dict, layers: int, live_kv_tokens: float,
                    lanes: float, experts_hit: float) -> float:
    """Weights once (``weight_bytes``), every running lane's live K/V of
    the full layers once, every running lane's live ring slots once. The
    fed positions' writes and the activations are left out: the least,
    not what the program happens to move."""
    return weight_bytes(m, layers, experts_hit) \
        + full_kv_bytes_per_token(m, layers) * live_kv_tokens \
        + window_live_bytes(m, layers, live_kv_tokens, lanes)


def expert_mm_flops_bytes(m: dict, calls: float, assignments_held: float,
                          experts_hit: float):
    """(FLOP, bytes) the held experts' two grouped products require over
    ``calls`` expert-layer calls that together routed ``assignments_held``
    token-expert pairs to held experts and hit ``experts_hit`` held
    experts a call (``arch/kda_mla_moe.py``'s)."""
    h, w = m["hidden_size"], m["moe_intermediate_size"]
    per_expert = 3 * h * w
    flops = 2.0 * per_expert * assignments_held
    nbytes = calls * experts_hit * per_expert * ITEM \
        + assignments_held * 2 * h * ITEM
    return flops, nbytes


def cache_bytes_held(m: dict, layers: int, live_kv_tokens: float,
                     lanes: float) -> float:
    """Device bytes of cache the running lanes hold: their pool blocks as
    stored (whole blocks: a lane's last block is half full on average)
    and their rings."""
    return full_kv_bytes_per_token(m, layers) \
        * (live_kv_tokens + lanes * BLOCK / 2) \
        + ring_bytes_per_lane(m, layers) * lanes


def scope_roofline(obs, path, need):
    """100 x the least time the chip could take for what the round
    programs did under scope ``path`` over the device seconds their
    operations under that scope took, in a traced backlog run: the
    operations are found BY SCOPE (``chiplib/devscopes.py``: instruction
    name within module, joined to the program's ``jax.named_scope``
    names), in every decode and verify execution that starts in the
    traced window. ``need(round record) -> (FLOP, bytes)`` of one round;
    the traced round records' mean stands for each execution (the
    profiler may lose a session's first events, so the two counts can
    differ by a few). None without a trace, the program's scope registry
    or an operation under the scope."""
    from chiplib import devscopes

    red = devscopes.table(obs) if obs.get("loop") == "backlog" else None
    rounds = [r for r in obs.get("rounds", ()) if r["traced"]
              and r["decode_steps"] + r["verify_steps"]]
    if red is None or not red["rounds"] or not rounds:
        return None
    seconds = sum(v for (kind, p), v in red["by_path"].items()
                  if kind in devscopes.ROUND_KINDS
                  and (p == path or p.startswith(path + "/")))
    if seconds <= 0:
        return None
    least = 0.0
    for r in rounds:
        flops, nbytes = need(r)
        least += max(flops / obs["peaks"]["bf16_flops"],
                     nbytes / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * red["rounds"] / len(rounds) / seconds
