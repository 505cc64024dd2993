"""Adapter for the latent-attention, sparse-expert decoder
(``paddle_tpu/models/latent_moe.py``: low-rank query and key/value paths
with a latent cache, sandwich norm, leading dense layers then expert
layers of which this chip holds a share; openPangu-Ultra-MoE-718B's
``config.json`` is one). See ``arch/llama_dense.py`` for what an adapter
gives. Serving only: no training cell, so no train / flash functions.

Its plain reference is ``reference/mla_moe.py``. The leaf names below are
the program's own leaf names, the keys of the reference's ``lw`` and the
words the seeded weights are keyed on.
"""
from __future__ import annotations

import math

NORMS = ("ln_in", "ln_attn_out", "ln_mlp_in", "ln_mlp_out")
_ATTN = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o")
_TOP = {"embed": "embed", "norm": "norm", "lm_head": "lm_head"}
ITEM = 2  # bfloat16


def param_name(layer: int, name: str) -> str:
    if layer < 0:
        return _TOP[name]
    if name in NORMS:
        return f"layers.{layer}.{name}"
    if name in _ATTN:
        return f"layers.{layer}.attn.{name}"
    return f"layers.{layer}.mlp.{name}"


def config_kwargs(cfg, layers, max_positions):
    """``LatentMoEConfig``'s arguments at the configuration's widths. The
    three flags of the published ``config.json`` are what the model is,
    not arguments of it: a configuration that states them otherwise has
    no program here."""
    m = cfg["model"]
    if not (m["sandwich_norm"] and m["norm_topk_prob"]) \
            or m["tie_word_embeddings"]:
        raise ValueError("models/latent_moe.py is sandwich-normed, "
                         "normalises its top-k gates and has an untied head")
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "first_k_dense_replace",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "router_experts", "first_held_expert",
            "n_shared_experts", "num_experts_per_tok",
            "routed_scaling_factor", "num_nextn_predict_layers",
            "rms_norm_eps", "rope_theta")
    return dict({k: m[k] for k in keys}, num_hidden_layers=layers,
                max_position_embeddings=max_positions,
                dtype=m["torch_dtype"])


def build_model(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import LatentMoEConfig, LatentMoEForCausalLM

    if cfg["model"]["num_nextn_predict_layers"]:
        raise ValueError("the leaf list has no next-token module: a chip "
                         "configuration holds none")
    # initializer_range 0: the matrices are born zero at no cost; the
    # harness replaces every value with the seeded ones
    return LatentMoEForCausalLM(LatentMoEConfig(
        **config_kwargs(cfg, layers, max_positions), initializer_range=0.0,
        **flags))


def _attn_shapes(m):
    h, nh = m["hidden_size"], m["num_attention_heads"]
    ql, dc = m["q_lora_rank"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    return [("q_a", (h, ql), "matrix"), ("q_norm", (ql,), "norm"),
            ("q_b", (ql, nh * (dn + dr)), "matrix"),
            ("kv_a", (h, dc + dr), "matrix"), ("kv_norm", (dc,), "norm"),
            ("kv_b", (dc, nh * (dn + dv)), "matrix"),
            ("o", (nh * dv, h), "matrix")]


def _mlp_shapes(m, dense):
    h = m["hidden_size"]
    if dense:
        f = m["intermediate_size"]
        return [("gate_up", (h, 2 * f), "matrix"),
                ("down", (f, h), "matrix")]
    w, held = m["moe_intermediate_size"], m["n_routed_experts"]
    ws = w * m["n_shared_experts"]
    return [("router", (h, m["router_experts"]), "matrix"),
            ("experts_gate_up", (held, h, 2 * w), "matrix"),
            ("experts_down", (held, w, h), "matrix"),
            ("shared_gate_up", (h, 2 * ws), "matrix"),
            ("shared_down", (ws, h), "matrix")]


def leaf_specs(model_cfg: dict, layers: int) -> list:
    """W is [in, out]; gate and up fused (gate first), per expert too;
    the first ``first_k_dense_replace`` layers are dense."""
    m = model_cfg
    h, v = m["hidden_size"], m["vocab_size"]
    out = [(-1, "embed", (v, h), "matrix")]
    for li in range(layers):
        out += [(li, n, (h,), "norm") for n in NORMS]
        out += [(li, n, s, k) for n, s, k in _attn_shapes(m)]
        out += [(li, n, s, k) for n, s, k in
                _mlp_shapes(m, li < m["first_k_dense_replace"])]
    out += [(-1, "norm", (h,), "norm"), (-1, "lm_head", (h, v), "matrix")]
    return out


# -- what the algorithm requires, for the readers -------------------------------

def _count(shapes):
    return sum(math.prod(shape) for _, shape, _ in shapes)


def latent_bytes_per_token(m: dict) -> int:
    """Cache bytes a token takes in ONE layer: the latent and the rotary
    key (1152 at the published widths)."""
    return (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * ITEM


def weight_bytes(m: dict, layers: int) -> int:
    """Bytes a decode round has to read of the weights held: attention,
    norms, shared expert, router and ALL held experts of every expert
    layer, the dense layers whole, the head's slice (the embedding is
    gathered by row, not read)."""
    dense = min(layers, m["first_k_dense_replace"])
    per_attn = _count(_attn_shapes(m)) + 4 * m["hidden_size"]
    n = layers * per_attn + dense * _count(_mlp_shapes(m, True)) \
        + (layers - dense) * _count(_mlp_shapes(m, False)) \
        + m["hidden_size"] * (m["vocab_size"] + 1)
    return n * ITEM


def decode_round_bytes(m: dict, layers: int, live_kv_tokens: float) -> float:
    """Weights once plus every running lane's live latent cache once
    (``latent_bytes_per_token`` x layers x live tokens). At 64 lanes a
    plain round's 512 assignments hit 87% of a layer's 16 held experts in
    expectation (1 - (255/256)^512), a verify round's 2560 all of them,
    so the bytes a PLAIN round requires are over-stated by at most 8.5%
    (the experts are 6.0 of the 9.25 GB) and a verify round's not at all;
    ``decode_step_roofline`` reads up to that much high on plain rounds."""
    return weight_bytes(m, layers) \
        + latent_bytes_per_token(m) * layers * live_kv_tokens


def expert_mm_flops_bytes(m: dict, calls: float, assignments_held: float):
    """(FLOP, bytes) the held experts' two grouped products require over
    ``calls`` expert-layer calls that together routed ``assignments_held``
    token-expert pairs to held experts. FLOP: 2 x 3 x hidden x width a
    pair. Bytes: the weights of the experts that got at least one pair —
    in expectation ``held x (1 - (1 - 1/held)^(pairs a call))`` of them a
    call, pairs spread evenly over the held experts (never more than the
    call read) — plus each pair's input and output row."""
    h, w, held = (m["hidden_size"], m["moe_intermediate_size"],
                  m["n_routed_experts"])
    per_expert = 3 * h * w
    pairs = assignments_held / max(calls, 1e-9)
    hit = held * (1.0 - (1.0 - 1.0 / held) ** pairs)
    flops = 2.0 * per_expert * assignments_held
    nbytes = calls * hit * per_expert * ITEM \
        + assignments_held * 2 * h * ITEM
    return flops, nbytes


def mla_attend_flops_bytes(m: dict, queries: float, query_slots: float,
                           live_kv_tokens: float):
    """(FLOP, bytes) of ONE layer's latent read and attention:
    ``queries`` query tokens, ``query_slots`` = sum over queries of the
    cached positions each may see, ``live_kv_tokens`` distinct cached
    tokens read. FLOP: absorbing ``kv_b`` into the query and out of the
    output (2 x heads x latent x (nope + v) a query) plus scores and the
    weighted sum over the latent (2 x heads x (2 latent + rope) a visible
    slot). Bytes: every live token's cache entry once, and ``kv_b``."""
    nh, dc = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    flops = 2.0 * nh * dc * (dn + dv) * queries \
        + 2.0 * nh * (2 * dc + dr) * query_slots
    nbytes = latent_bytes_per_token(m) * live_kv_tokens \
        + dc * nh * (dn + dv) * ITEM
    return flops, nbytes
