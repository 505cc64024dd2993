"""Short-convolution / grouped-query sparse-expert causal LM: a stack in
which most layers mix tokens by a GATED DEPTHWISE CONVOLUTION over their
last few positions and nothing else, and every few layers one is rotary
grouped-query attention with an RMSNorm on every query and key head; the
first layers' MLP is a dense SwiGLU, every other layer's the expert layer
of ``incubate/distributed/models/moe/held_experts.py`` with a per-expert
selection bias and NO shared expert. Plain pre-norm, the head tied to the
embedding. LFM2-24B-A2B's ``config.json`` (``model_type`` ``lfm2_moe``)
describes one such model; key names below are that file's.

Layer equations (``N(.; w)`` is RMSNorm with its own weight): ``x <- x +
Op(N(x; ln_in))`` then ``x <- x + FFN(N(x; ln_post))``; logits ``N(x;
norm) embed^T``.

- ``Op`` where ``layer_types[l]`` is ``conv``, on ``u`` [T, hidden]: ``[B |
  C | z] = u in_proj`` (three thirds of ``hidden``, in that order), ``g =
  B * z``, ``c_t = sum_j conv_w[:, j] * g_{t - (L-1) + j}`` over the
  ``L = conv_L_cache`` taps (depthwise, causal, ``g`` zero before the
  sequence's start, NO activation and no bias), ``y = C * c``, out ``y
  out_proj``. What a sequence carries from position to position is its
  last ``L - 1`` rows of ``g``: the TAIL (:func:`sconv_conv` takes ``[tail
  | positions]``).
- ``Op`` where it is ``full_attention``: ``[q | k | v] = u qkv`` with ``H``
  query heads and ``G`` key/value heads of ``hidden / H``; RMSNorm over
  the columns of every q head (``q_norm``) and every k head (``k_norm``),
  THEN the rotary embedding on all columns (rotate-half, base
  ``rope_parameters["rope_theta"]``); scores ``q.k / sqrt(head)``,
  causal, plain softmax; output ``[H x head] o``.
- ``FFN``: SwiGLU of ``intermediate_size`` in the first
  ``num_dense_layers`` layers; else ``s = sigmoid(u router)`` (float32),
  the ``num_experts_per_tok`` largest of ``s + router_bias``, gates ``s /
  (sum(s) + 1e-6)`` over the chosen times ``routed_scaling_factor``, the
  HELD experts' share.

Served through :class:`paddle_tpu.serving.ServingEngine` (the model hands
it its family, ``serving/families/conv_moe.py``: the attention layers' K/V
on the paged pool, the conv layers' tails by LANE);
``models.generation.generate`` raises for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..incubate.distributed.models.moe.held_experts import (
    HeldExperts, sparse_expert_block, swiglu,
)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.layers import Layer
from ..ops.dispatch import apply
from .generation import _rms
from .latent_moe import _token_loss
from .window_moe import attend, partial_rope

__all__ = ["ConvMoEConfig", "ConvMoEForCausalLM"]

CONV, FULL = "conv", "full_attention"  # ``layer_types``' two values
F32 = jnp.float32
GATE_EPS = 1e-6  # under the chosen scores' sum (the family's gate)


class ConvMoEConfig:
    """Key names follow the published ``config.json`` of the family.
    ``num_experts`` is how many experts are HELD here (``first_held_expert``
    on); ``router_experts`` how many the router scores (default: the same,
    i.e. the whole layer). What that file states as flags is what this
    model IS, and another value raises: no convolution bias
    (``conv_bias``), sigmoid scores renormalised over the chosen experts
    (``norm_topk_prob``), a selection bias (``use_expert_bias``), a
    rotary embedding without scaling (``rope_parameters["rope_type"]``
    ``default``), a tied head."""

    def __init__(self, vocab_size=1024, hidden_size=128,
                 intermediate_size=256, moe_intermediate_size=64,
                 num_hidden_layers=4, layer_types=None, num_dense_layers=1,
                 num_attention_heads=4, num_key_value_heads=1,
                 conv_L_cache=3, conv_bias=False, num_experts=8,
                 router_experts=None, first_held_expert=0,
                 num_experts_per_tok=2, norm_topk_prob=True,
                 use_expert_bias=True, routed_scaling_factor=1.0,
                 rope_parameters=None, norm_eps=1e-5,
                 max_position_embeddings=4096, initializer_range=0.02,
                 dtype="float32"):
        n = num_hidden_layers
        if layer_types is None:  # one period: conv, conv, full, conv
            layer_types = [(CONV, CONV, FULL, CONV)[i % 4] for i in range(n)]
        if len(layer_types) != n or set(layer_types) - {CONV, FULL}:
            raise ValueError(f"layer_types gives {CONV!r} or {FULL!r} for "
                             f"each of the {n} layers; got "
                             f"{list(layer_types)}")
        rope = dict(rope_parameters or {"rope_theta": 1e6,
                                        "rope_type": "default"})
        for name, got, want in (
                ("conv_bias", conv_bias, False),
                ("norm_topk_prob", norm_topk_prob, True),
                ("use_expert_bias", use_expert_bias, True),
                ("rope_parameters['rope_type']", rope.get("rope_type"),
                 "default")):
            if got != want:
                raise ValueError(f"{name} {got!r}: models/conv_moe.py is "
                                 f"the model with {want!r}")
        if hidden_size % num_attention_heads \
                or num_attention_heads % num_key_value_heads:
            raise ValueError(
                f"{num_attention_heads} query heads over "
                f"{num_key_value_heads} key/value heads in {hidden_size}")
        if conv_L_cache < 2:
            raise ValueError(f"conv_L_cache {conv_L_cache}: a convolution "
                             f"of one tap keeps no tail")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = n
        self.layer_types = tuple(layer_types)
        self.num_dense_layers = num_dense_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.conv_L_cache = int(conv_L_cache)
        self.num_experts = num_experts
        self.router_experts = router_experts or num_experts
        self.first_held_expert = first_held_expert
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rope_parameters = rope
        self.rope_theta = float(rope["rope_theta"])
        self.norm_eps = float(norm_eps)
        self.max_position_embeddings = max_position_embeddings
        # std of every matrix's initial values (0: born zero at no cost)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype

    def static(self):
        """The hashable view the compiled programs are keyed on."""
        return _Static(self)


class _Static:
    """Value-hashable static view of the fields a traced layer reads (a
    config object hashes by identity; see ``generation._GenCfg``)."""

    __slots__ = ("layer_types", "hidden_size", "num_attention_heads",
                 "num_key_value_heads", "head_dim", "conv_L_cache",
                 "rope_theta", "first_held_expert", "num_experts_per_tok",
                 "routed_scaling_factor", "norm_eps", "dtype")

    def __init__(self, cfg):
        for f in self.__slots__:
            setattr(self, f, getattr(cfg, f))
        self.dtype = str(cfg.dtype)

    def _key(self):
        return tuple(getattr(self, f) for f in self.__slots__)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, _Static) and self._key() == other._key()


# -- the short convolution, on arrays (the model's and the serving family's) ----

def is_conv(lp):
    """A conv layer is the one that has taps."""
    return "conv_w" in lp


def sconv_project(u, lp):
    """Normed ``u`` [b, T, hidden] -> (``g`` = ``B * z``, what the
    convolution runs over and the tail keeps; ``C``, the gate on its
    output), both [b, T, hidden] in ``u``'s dtype."""
    with jax.named_scope("sconv/in_proj"):
        B, C, z = jnp.split(u @ lp["in_proj"], 3, axis=-1)
    with jax.named_scope("sconv/conv"):
        return B * z, C


def sconv_conv(window, lp):
    """The depthwise causal convolution over ``window`` [b, L-1 + T,
    hidden] — the ``L - 1`` rows of ``g`` before the first position (the
    tail; zeros at a sequence's start), then the T positions' — with taps
    ``conv_w`` [hidden, L]: ``c_t = sum_j conv_w[:, j] window[t + j]``, no
    activation, no bias. Sums in float32; ``c`` [b, T, hidden] in the
    window's dtype. ``T = 1`` is the decode step, operation for
    operation."""
    taps = lp["conv_w"].shape[1]
    T = window.shape[1] - (taps - 1)
    with jax.named_scope("sconv/conv"):
        w = lp["conv_w"].astype(F32)
        acc = w[:, 0] * window[:, :T].astype(F32)
        for j in range(1, taps):
            acc = acc + w[:, j] * window[:, j:j + T].astype(F32)
        return acc.astype(window.dtype)


def sconv_gate_out(C, c, lp):
    """``(C * c) out_proj``: [b, T, hidden]."""
    with jax.named_scope("sconv/conv"):
        y = C * c
    with jax.named_scope("sconv/out_proj"):
        return y @ lp["out_proj"]


def sconv_mix(u, lp):
    """The whole operator over whole sequences ``u`` [b, T, hidden]
    (normed), from zeros before the start."""
    g, C = sconv_project(u, lp)
    with jax.named_scope("sconv/conv"):
        window = jnp.pad(g, ((0, 0), (lp["conv_w"].shape[1] - 1, 0), (0, 0)))
    return sconv_gate_out(C, sconv_conv(window, lp), lp)


# -- attention --------------------------------------------------------------------

def attention_qkv(u, lp, pos, cfg):
    """Normed ``u`` [b, s, hidden] at positions ``pos`` [b, s] -> q [b, s,
    H, d], k and v [b, s, G, d]: every q and k head normed over its ``d``
    columns (``q_norm`` / ``k_norm``), THEN rotated whole."""
    b, s, _ = u.shape
    nh, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("attn/qkv"):
        q, k, v = jnp.split(u @ lp["qkv"], [nh * d, (nh + g) * d], axis=-1)
        q = _rms(q.reshape(b, s, nh, d), lp["q_norm"], cfg.norm_eps)
        k = _rms(k.reshape(b, s, g, d), lp["k_norm"], cfg.norm_eps)
        v = v.reshape(b, s, g, d)
    with jax.named_scope("attn/rope"):
        return (partial_rope(q, pos, cfg.rope_theta, d),
                partial_rope(k, pos, cfg.rope_theta, d), v)


def attention_mix(u, lp, cfg):
    """Causal attention over whole sequences ``u`` [b, T, hidden]
    (normed), no cache."""
    b, T, _ = u.shape
    at = jnp.arange(T, dtype=jnp.int32)
    q, k, v = attention_qkv(u, lp, jnp.broadcast_to(at[None], (b, T)), cfg)
    with jax.named_scope("attn/rows"):
        vis = jnp.broadcast_to((at[None, :] <= at[:, None])[None], (b, T, T))
        out = attend(q, k, v, vis)
    with jax.named_scope("attn/out"):
        return out @ lp["o"]


def ffn_block(u, lp, cfg, valid=None):
    """Dense SwiGLU or the expert layer, told apart by the layer's leaves.
    ``u`` [b, s, h]. Returns (y, counts or None)."""
    if "router" not in lp:
        with jax.named_scope("mlp"):
            return swiglu(u, lp["gate_up"], lp["down"]), None
    b, s, h = u.shape
    with jax.named_scope("moe/dispatch"):
        u = u.reshape(b * s, h)
        valid = None if valid is None else valid.reshape(b * s)
    y, counts = sparse_expert_block(
        u, lp, top_k=cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor,
        first_held=cfg.first_held_expert, valid=valid, eps=GATE_EPS)
    with jax.named_scope("moe/combine"):
        return y.reshape(b, s, h), counts


def layer_on_sequence(x, lp, cfg):
    """One layer over whole sequences ``x`` [b, T, hidden] (no cache); its
    kinds told by the layer's leaves."""
    u = _rms(x, lp["ln_in"], cfg.norm_eps)
    x = x + (sconv_mix(u, lp) if is_conv(lp) else attention_mix(u, lp, cfg))
    return x + ffn_block(_rms(x, lp["ln_post"], cfg.norm_eps), lp, cfg)[0]


# -- the Layer graph ----------------------------------------------------------

class ConvMoEDecoderLayer(Layer):
    """``conv``: a short-convolution layer or an attention layer;
    ``expert``: the expert layer or a dense SwiGLU. Its parameters by leaf
    name (``leaves()``) are what the layer functions take."""

    def __init__(self, c: ConvMoEConfig, conv: bool, expert: bool):
        super().__init__(dtype=c.dtype)  # parameters are born in it
        h, nh, g, d = (c.hidden_size, c.num_attention_heads,
                       c.num_key_value_heads, c.head_dim)
        normal = I.Normal(std=c.initializer_range)
        one = I.Constant(1.0)
        if conv:
            own = [("in_proj", (h, 3 * h), normal),
                   ("conv_w", (h, c.conv_L_cache), normal),
                   ("out_proj", (h, h), normal)]
        else:
            own = [("qkv", (h, (nh + 2 * g) * d), normal),
                   ("o", (nh * d, h), normal),
                   ("q_norm", (d,), one), ("k_norm", (d,), one)]
        own += [("ln_in", (h,), one), ("ln_post", (h,), one)]
        if expert:
            self.mlp = HeldExperts(
                h, c.moe_intermediate_size, c.router_experts, c.num_experts,
                first_held=c.first_held_expert, top_k=c.num_experts_per_tok,
                n_shared=0, scaling=c.routed_scaling_factor, dtype=c.dtype,
                init_std=c.initializer_range, selection_bias=True,
                eps=GATE_EPS)
        else:
            f = c.intermediate_size
            own += [("gate_up", (h, 2 * f), normal), ("down", (f, h), normal)]
            self.mlp = None
        for name, shape, init in own:
            setattr(self, name, self.create_parameter(
                list(shape), default_initializer=init))
        self._own = tuple(n for n, _, _ in own)
        self._static = c.static()

    def leaves(self) -> dict:
        out = {n: getattr(self, n) for n in self._own}
        if self.mlp is not None:
            out.update({n: getattr(self.mlp, n) for n in self.mlp._NAMES})
        return out

    def forward(self, x):
        leaves = self.leaves()
        names, cfg = tuple(leaves), self._static

        def kernel(xa, *ws):
            return layer_on_sequence(xa, dict(zip(names, ws)), cfg)

        return apply("conv_moe_layer", kernel, (x, *leaves.values()))


class ConvMoEForCausalLM(Layer):
    """``forward(ids)`` gives logits [b, s, vocab]; with ``labels`` (same
    shape, already shifted, -100 ignored) the mean cross-entropy."""

    def __init__(self, config: ConvMoEConfig):
        super().__init__(dtype=config.dtype)
        c = self.config = config
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size],
            default_initializer=I.Normal(std=c.initializer_range))
        self.layers = []
        for i, kind in enumerate(c.layer_types):
            blk = ConvMoEDecoderLayer(c, kind == CONV,
                                      i >= c.num_dense_layers)
            self.add_sublayer(f"layers.{i}", blk)
            self.layers.append(blk)
        self.norm = self.create_parameter(
            [c.hidden_size], default_initializer=I.Constant(1.0))

    def forward(self, input_ids, labels=None):
        c = self.config
        x = F.embedding(input_ids, self.embed).astype(c.dtype)
        for blk in self.layers:
            x = blk(x)
        logits = apply("conv_moe_head",
                       lambda xa, n, e: _rms(xa, n, c.norm_eps) @ e.T,
                       (x, self.norm, self.embed))
        return logits if labels is None else _token_loss(logits, labels)

    # -- serving ---------------------------------------------------------------

    serving_family_name = "conv_moe"

    def serving_family(self, serving_config):
        """What :class:`paddle_tpu.serving.ServingEngine` asks a model
        for: its caches, its collected parameters, its step programs."""
        from ..serving.families.conv_moe import ConvMoEFamily

        return ConvMoEFamily(self, serving_config)

    def generate(self, *args, **kwargs):
        from .generation import generate as _generate

        return _generate(self, *args, **kwargs)
