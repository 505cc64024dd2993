"""Adapter for the hybrid state-space / attention decoder
(``paddle_tpu/models/hybrid_ssm.py``: state-space mixers with a recurrent
state and a conv tail per sequence, a grouped-query attention layer every
few layers without position embedding, SwiGLU MLPs, constant multipliers
on embedding, residual branches and logits, a tied head;
granite-4.0-h-micro's ``config.json`` is one). See ``arch/llama_dense.py``
for what an adapter gives. Serving only: no training cell, so no train /
flash functions; its cells report ``hybrid_round_roofline`` where the
dense cells report ``decode_step_roofline``, whose
``decode_round_bytes(m, layers, live_kv_tokens)`` cannot count bytes that
scale with live LANES.

Its plain reference is ``reference/hybrid_ssm.py``. The leaf names below
are the program's own leaf names, the keys of the reference's ``lw`` and
the words the seeded weights are keyed on.
"""
from __future__ import annotations

import math

ITEM = 2        # bfloat16: weights, K/V, conv tail
STATE_ITEM = 4  # the recurrent state is float32
_NORMS = ("ln_in", "ln_post", "gate_norm", "D", "conv_w")


def param_name(layer: int, name: str) -> str:
    return name if layer < 0 else f"layers.{layer}.{name}"


def config_kwargs(cfg, layers, max_positions):
    """``HybridSSMConfig``'s arguments at the configuration's widths. The
    flags of the published ``config.json`` are what the model is, not
    arguments of it: a configuration that states them otherwise has no
    program here."""
    m = cfg["model"]
    flags = {"position_embedding_type": "nope", "tie_word_embeddings": True,
             "num_local_experts": 0, "mamba_conv_bias": True,
             "mamba_proj_bias": False, "attention_bias": False,
             "normalization_function": "rmsnorm", "hidden_act": "silu"}
    bad = {k: m[k] for k, v in flags.items() if m[k] != v}
    if bad or m["mamba_expand"] * m["hidden_size"] \
            != m["mamba_n_heads"] * m["mamba_d_head"]:
        raise ValueError(f"models/hybrid_ssm.py is {flags} with d_inner = "
                         f"expand x hidden; the configuration says {bad}")
    keys = ("vocab_size", "hidden_size", "shared_intermediate_size",
            "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups",
            "mamba_d_conv", "mamba_chunk_size", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "rms_norm_eps")
    return dict({k: m[k] for k in keys}, num_hidden_layers=layers,
                layer_types=m["layer_types"][:layers],
                max_position_embeddings=max_positions,
                dtype=m["torch_dtype"])


def build_model(cfg, layers, max_positions, **flags):
    from paddle_tpu.models import HybridSSMConfig, HybridSSMForCausalLM

    # initializer_range 0: the matrices are born zero at no cost; the
    # harness replaces every value with the seeded ones
    return HybridSSMForCausalLM(HybridSSMConfig(
        **config_kwargs(cfg, layers, max_positions), initializer_range=0.0,
        **flags))


def _sizes(m):
    H, P, N, G = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                  m["mamba_n_groups"])
    return H, P, N, H * P, H * P + 2 * G * N


def _layer_shapes(m, kind):
    h, f = m["hidden_size"], m["shared_intermediate_size"]
    if kind == "mamba":
        H, _, _, d_inner, conv = _sizes(m)
        mixer = [("in_proj", (h, d_inner + conv + H)),
                 ("conv_w", (conv, 1, m["mamba_d_conv"])),
                 ("conv_b", (conv,)), ("dt_bias", (H,)), ("A_log", (H,)),
                 ("D", (H,)), ("gate_norm", (d_inner,)),
                 ("out_proj", (d_inner, h))]
    else:
        nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
        d = h // nh
        mixer = [("qkv", (h, (nh + 2 * nkv) * d)), ("o", (nh * d, h))]
    return mixer + [("ln_in", (h,)), ("ln_post", (h,)),
                    ("gate_up", (h, 2 * f)), ("down", (f, h))]


def leaf_specs(model_cfg: dict, layers: int) -> list:
    """W is [in, out]; q, k, v fused (q first), gate and up fused (gate
    first); ``in_proj``'s output is gate | conv channels | step sizes; the
    conv weight is the checkpoint's [channels, 1, kernel]. ``D``, the norm
    weights AND the conv weight are of kind ``norm`` (1 +- 0.1),
    everything else — ``A_log``, ``dt_bias``, the conv bias too —
    ``matrix`` (so seeded ``A`` is about -1 and ``dt`` about 0.8: a state
    halves with every token, PERF.md section 7). The conv weight is NOT
    ``matrix`` as ISSUE 31 had it: with taps of std 0.02 the convolved
    ``x``, ``B``, ``C`` are ~0.02, the state's part of a layer's output,
    ``S C``, is 0.5% of the skip ``D x`` (one layer at these widths on
    the CPU: rms 9.8e-5 against 0.020), and the comparison that decides
    ``correct`` could not see a state that was not reset or not rolled
    back. With taps of 1 +- 0.1 the state's part is 44x the skip."""
    m = model_cfg
    out = [(-1, "embed", (m["vocab_size"], m["hidden_size"]), "matrix")]
    for li in range(layers):
        out += [(li, n, s, "norm" if n in _NORMS else "matrix")
                for n, s in _layer_shapes(m, m["layer_types"][li])]
    return out + [(-1, "norm", (m["hidden_size"],), "norm")]


# -- what the algorithm requires, for the readers -------------------------------

def ssm_layers(m: dict, layers: int) -> int:
    return sum(k == "mamba" for k in m["layer_types"][:layers])


def ssm_state_bytes_per_lane(m: dict, layers: int) -> int:
    """The recurrent state one sequence keeps, whatever its length:
    heads x d_head x d_state float32 a state-space layer (75.5 MB at the
    published sizes and depth)."""
    H, P, N, _, _ = _sizes(m)
    return ssm_layers(m, layers) * H * P * N * STATE_ITEM


def ssm_update_bytes(m: dict, layers: int, lanes: float) -> float:
    """The least a round moves of the state: each live lane's read once
    and written once."""
    return 2.0 * ssm_state_bytes_per_lane(m, layers) * lanes


def weight_bytes(m: dict, layers: int) -> int:
    """Bytes a decode round has to read of the weights: every layer, the
    final norm and the embedding ONCE (as the head; its rows for the
    round's tokens are a gather beside that)."""
    n = sum(math.prod(s) for li in range(layers)
            for _, s in _layer_shapes(m, m["layer_types"][li]))
    return (n + m["hidden_size"] * (m["vocab_size"] + 1)) * ITEM


def kv_bytes_per_token(m: dict, layers: int) -> int:
    """K and V a token takes in the attention layers (8 KB at the
    published sizes and depth)."""
    d = m["hidden_size"] // m["num_attention_heads"]
    return (layers - ssm_layers(m, layers)) * 2 \
        * m["num_key_value_heads"] * d * ITEM


def hybrid_round_bytes(m: dict, layers: int, live_kv_tokens: float,
                       lanes: float) -> float:
    """Weights once, every running lane's live K/V once, every running
    lane's state read once and written once. The conv tails (0.8% of the
    state) and the activations are left out: the least, not what the
    program happens to move."""
    return weight_bytes(m, layers) \
        + kv_bytes_per_token(m, layers) * live_kv_tokens \
        + ssm_update_bytes(m, layers, lanes)
