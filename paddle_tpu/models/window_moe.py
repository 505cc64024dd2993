"""Window-attention / full-attention sparse-expert causal LM: a stack in
which most layers attend over a SLIDING WINDOW of their last positions
with a learned SINK logit a head, and every few layers one attends over
everything before it — the two kinds with their own numbers of key/value
heads and rotary bases — with keys WIDER than values and a rotary
embedding on part of each head; the first layer's MLP is a dense SwiGLU,
every other layer's the expert layer of
``incubate/distributed/models/moe/held_experts.py`` with a per-expert
selection bias and NO shared expert. Plain pre-norm, an untied head.
MiMo-V2.5's ``config.json`` (``model_type`` ``mimo_v2``) describes one
such model; key names below are that file's (``hybrid_layer_pattern`` and
``moe_layer_freq`` the per-layer lists they are).

Layer equations (``N(.; w)`` is RMSNorm with its own weight): ``x <- x +
Attn(N(x; ln_in))`` then ``x <- x + FFN(N(x; ln_post))``; logits ``N(x;
norm) lm_head``.

- ``Attn`` on ``u`` [T, hidden], ``H`` query heads, ``G`` key/value heads
  (``num_key_value_heads`` in a full layer, ``swa_num_key_value_heads`` in
  a window layer): ``[q | k | v] = u qkv`` with q ``[H x head_dim]``, k
  ``[G x head_dim]``, v ``[G x v_head_dim]``. The first ``int(head_dim x
  partial_rotary_factor)`` columns of every q and k head are rotated
  (rotate-half, base ``rope_theta`` in a full layer, ``swa_rope_theta``
  in a window layer), the rest pass as they are; ``v <-
  attention_value_scale v``. Scores ``q.k / sqrt(head_dim)``, causal.
  A FULL layer (``hybrid_layer_pattern[l]`` 0): plain softmax over every
  earlier position. A WINDOW layer (1): position ``t`` sees ``j`` with ``0
  <= t - j < sliding_window``, and the softmax runs over those scores AND
  one learned logit a head (``sink``), whose column is dropped afterwards:
  the sink takes probability mass and gives no value
  (:func:`softmax_with_sink`). Output ``[H x v_head_dim] o``.
- ``FFN``: SwiGLU of ``intermediate_size`` where ``moe_layer_freq[l]`` is
  0; else ``s = sigmoid(u router)`` (float32), the ``num_experts_per_tok``
  largest of ``s + router_bias``, gates ``s / sum(s)`` over the chosen
  times ``routed_scaling_factor`` (null: 1), the HELD experts' share.

Served through :class:`paddle_tpu.serving.ServingEngine` (the model hands
it its family, ``serving/families/window_moe.py``: the full layers' K/V on
the paged pool, the window layers' on a ring of their last positions per
LANE); ``models.generation.generate`` raises for it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..incubate.distributed.models.moe.held_experts import (
    HeldExperts, sparse_expert_block, swiglu,
)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.layers import Layer
from ..ops.dispatch import apply
from .generation import _rms
from .latent_moe import _token_loss

__all__ = ["WindowMoEConfig", "WindowMoEForCausalLM"]

FULL, WINDOW = 0, 1  # ``hybrid_layer_pattern``'s two values
F32 = jnp.float32
MASKED = -1e30


class WindowMoEConfig:
    """Key names follow the published ``config.json`` of the family.
    ``n_routed_experts`` is how many experts are HELD here
    (``first_held_expert`` on); ``router_experts`` how many the router
    scores (default: the same, i.e. the whole layer). What that file
    states as flags is what this model IS and takes no argument: a sink
    in the window layers and none in the full ones
    (``add_swa_attention_sink_bias`` / ``add_full_attention_sink_bias``),
    sigmoid scores renormalised over the chosen experts
    (``norm_topk_prob``) in one group, a selection bias (``noaux_tc``), no
    shared expert, no attention bias, an untied head. The window layers'
    ``swa_num_attention_heads`` / ``swa_head_dim`` / ``swa_v_head_dim``
    are the full layers' (as published) and raise where they differ.
    ``window_ring_len`` is the serving family's (the model is the same at
    any)."""

    def __init__(self, vocab_size=1024, hidden_size=128,
                 intermediate_size=256, moe_intermediate_size=64,
                 num_hidden_layers=4, hybrid_layer_pattern=None,
                 moe_layer_freq=None, num_attention_heads=4,
                 num_key_value_heads=1, swa_num_key_value_heads=2,
                 head_dim=48, v_head_dim=32, swa_num_attention_heads=None,
                 swa_head_dim=None, swa_v_head_dim=None,
                 partial_rotary_factor=0.334, rope_theta=1e7,
                 swa_rope_theta=1e4, sliding_window=8,
                 attention_value_scale=0.707, n_routed_experts=8,
                 router_experts=None, first_held_expert=0,
                 num_experts_per_tok=2, routed_scaling_factor=None,
                 window_ring_len=None, layernorm_epsilon=1e-5,
                 max_position_embeddings=4096, initializer_range=0.02,
                 dtype="float32"):
        n = num_hidden_layers
        if hybrid_layer_pattern is None:  # one full layer opens the stack
            hybrid_layer_pattern = [FULL] + [WINDOW] * (n - 1)
        if moe_layer_freq is None:        # ... over a dense SwiGLU
            moe_layer_freq = [0] + [1] * (n - 1)
        for name, per_layer in (("hybrid_layer_pattern",
                                 hybrid_layer_pattern),
                                ("moe_layer_freq", moe_layer_freq)):
            if len(per_layer) != n or set(per_layer) - {0, 1}:
                raise ValueError(f"{name} gives 0 or 1 for each of the {n} "
                                 f"layers; got {list(per_layer)}")
        for name, got, want in (
                ("swa_num_attention_heads", swa_num_attention_heads,
                 num_attention_heads),
                ("swa_head_dim", swa_head_dim, head_dim),
                ("swa_v_head_dim", swa_v_head_dim, v_head_dim)):
            if got is not None and got != want:
                raise ValueError(f"{name} {got} differs from the full "
                                 f"layers' {want}: not this model")
        for g in (num_key_value_heads, swa_num_key_value_heads):
            if num_attention_heads % g:
                raise ValueError(f"{num_attention_heads} query heads over "
                                 f"{g} key/value heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = n
        self.hybrid_layer_pattern = tuple(hybrid_layer_pattern)
        self.moe_layer_freq = tuple(moe_layer_freq)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.swa_num_key_value_heads = swa_num_key_value_heads
        self.head_dim = head_dim
        self.v_head_dim = v_head_dim
        self.partial_rotary_factor = float(partial_rotary_factor)
        self.rope_theta = float(rope_theta)
        self.swa_rope_theta = float(swa_rope_theta)
        self.sliding_window = int(sliding_window)
        self.attention_value_scale = float(attention_value_scale)
        self.n_routed_experts = n_routed_experts
        self.router_experts = router_experts or n_routed_experts
        self.first_held_expert = first_held_expert
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = float(
            1.0 if routed_scaling_factor is None else routed_scaling_factor)
        # slots of a served lane's ring of last keys (None: the serving
        # family's choice, ``serving/families/window_moe.ring_len``)
        self.window_ring_len = window_ring_len
        self.layernorm_epsilon = float(layernorm_epsilon)
        self.max_position_embeddings = max_position_embeddings
        # std of every matrix's initial values (0: born zero at no cost)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        if self.rotary_dim % 2:
            raise ValueError(f"int({head_dim} x {partial_rotary_factor}) = "
                             f"{self.rotary_dim} rotary columns: not even")

    @property
    def rotary_dim(self):
        """Columns of a q or k head that are rotated (the first ones)."""
        return int(self.head_dim * self.partial_rotary_factor)

    def kv_heads(self, window):
        return (self.swa_num_key_value_heads if window
                else self.num_key_value_heads)

    def static(self):
        """The hashable view the compiled programs are keyed on."""
        return _Static(self)


class _Static:
    """Value-hashable static view of the fields a traced layer reads (a
    config object hashes by identity; see ``generation._GenCfg``)."""

    __slots__ = ("hybrid_layer_pattern", "num_attention_heads",
                 "num_key_value_heads", "swa_num_key_value_heads",
                 "head_dim", "v_head_dim", "rotary_dim", "rope_theta",
                 "swa_rope_theta", "sliding_window", "attention_value_scale",
                 "first_held_expert", "num_experts_per_tok",
                 "routed_scaling_factor", "layernorm_epsilon", "dtype")

    kv_heads = WindowMoEConfig.kv_heads

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


# -- attention, on arrays (the model's and the serving family's) ----------------

def is_window(lp):
    """A window layer is the one that has a sink."""
    return "sink" in lp


def partial_rope(x, pos, theta, n_rot):
    """``x`` [b, s, heads, d] with its first ``n_rot`` columns rotated by
    ``pos`` [b, s] (rotate-half: the pairs are ``(x_i, x_{i + n_rot/2})``,
    the angle ``pos theta^(-2i / n_rot)``, in float32) and the other ``d -
    n_rot`` as they are."""
    half = n_rot // 2
    inv = 1.0 / (theta ** (np.arange(0, n_rot, 2, dtype=np.float32) / n_rot))
    ang = pos.astype(F32)[..., None] * inv                 # [b, s, half]
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half].astype(F32), x[..., half:n_rot].astype(F32)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.astype(x.dtype), x[..., n_rot:]], -1)


def attention_qkv(u, lp, pos, cfg):
    """Normed ``u`` [b, s, hidden] at positions ``pos`` [b, s] -> q [b, s,
    H, head_dim], k [b, s, G, head_dim] (both partly rotated), v [b, s, G,
    v_head_dim] (scaled); ``G`` and the rotary base by the layer's kind."""
    b, s, _ = u.shape
    window = is_window(lp)
    nh, g = cfg.num_attention_heads, cfg.kv_heads(window)
    dk, dv = cfg.head_dim, cfg.v_head_dim
    with jax.named_scope("attn/qkv"):
        qkv = u @ lp["qkv"]
        q, k, v = jnp.split(qkv, [nh * dk, (nh + g) * dk], axis=-1)
        q = q.reshape(b, s, nh, dk)
        k = k.reshape(b, s, g, dk)
        v = (v * cfg.attention_value_scale).astype(u.dtype).reshape(
            b, s, g, dv)
    with jax.named_scope("attn/rope"):
        theta = cfg.swa_rope_theta if window else cfg.rope_theta
        return (partial_rope(q, pos, theta, cfg.rotary_dim),
                partial_rope(k, pos, theta, cfg.rotary_dim), v)


def band_mask(q_pos, k_pos, window):
    """Which key positions a query position sees in a window layer: ``0 <=
    q_pos - k_pos < window``, and no key before the sequence's start
    (``k_pos < 0``: a ring slot its lane's request has not written)."""
    back = q_pos - k_pos
    return (back >= 0) & (back < window) & (k_pos >= 0)


def softmax_with_sink(scores, sink):
    """Softmax over the last axis of ``scores`` (float32; masked entries
    ``MASKED``) AND one more logit, ``sink`` (broadcastable to ``scores[...,
    :1]``), whose column is dropped: the weights sum to less than 1. With
    every score masked the weights are 0."""
    m = jnp.maximum(jnp.max(scores, -1, keepdims=True), sink)
    p = jnp.exp(scores - m)
    return p / (jnp.sum(p, -1, keepdims=True) + jnp.exp(sink - m))


def attend(q, k, v, vis, sink=None):
    """q [b, s, H, dk] against keys k [b, L, G, dk] / values v [b, L, G,
    dv] under the mask ``vis`` [b, s, L]; scores in float32 over ``sqrt(dk)``;
    with ``sink`` [H] the window layers' softmax. Returns [b, s, H x dv]
    in q's dtype."""
    b, s, nh, dk = q.shape
    g = k.shape[2]
    qg = q.reshape(b, s, g, nh // g, dk)
    scores = jnp.einsum("bskgd,blkd->bskgl", qg, k,
                        preferred_element_type=F32) / np.sqrt(dk)
    scores = jnp.where(vis[:, :, None, None, :], scores, MASKED)
    if sink is None:
        p = jax.nn.softmax(scores, axis=-1)
    else:
        p = softmax_with_sink(
            scores, sink.astype(F32).reshape(g, nh // g, 1))
    out = jnp.einsum("bskgl,blkd->bskgd", p.astype(v.dtype), v,
                     preferred_element_type=F32)
    return out.astype(q.dtype).reshape(b, s, -1)


def attention_mix(u, lp, cfg):
    """Either kind of attention over whole sequences ``u`` [b, T, hidden]
    (normed), no cache."""
    b, T, _ = u.shape
    at = jnp.arange(T, dtype=jnp.int32)
    q, k, v = attention_qkv(u, lp, jnp.broadcast_to(at[None], (b, T)), cfg)
    if is_window(lp):
        with jax.named_scope("attn/window"):
            vis = band_mask(at[:, None], at[None, :], cfg.sliding_window)
            out = attend(q, k, v, jnp.broadcast_to(vis[None], (b, T, T)),
                         lp["sink"])
    else:
        with jax.named_scope("attn/rows"):
            vis = at[None, :] <= at[:, None]
            out = attend(q, k, v, jnp.broadcast_to(vis[None], (b, T, T)))
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
        first_held=cfg.first_held_expert, valid=valid)
    with jax.named_scope("moe/combine"):
        return y.reshape(b, s, h), counts


def layer_on_sequence(x, lp, cfg):
    """One layer over whole sequences ``x`` [b, T, hidden] (no cache); its
    kinds told by the layer's leaves."""
    eps = cfg.layernorm_epsilon
    x = x + attention_mix(_rms(x, lp["ln_in"], eps), lp, cfg)
    return x + ffn_block(_rms(x, lp["ln_post"], eps), lp, cfg)[0]


# -- the Layer graph ----------------------------------------------------------

class WindowMoEDecoderLayer(Layer):
    """``window``: a window layer (with a sink) or a full one; ``expert``:
    the expert layer or a dense SwiGLU. Its parameters by leaf name
    (``leaves()``) are what the layer functions take."""

    def __init__(self, c: WindowMoEConfig, window: bool, expert: bool):
        super().__init__(dtype=c.dtype)  # parameters are born in it
        h, nh, g = c.hidden_size, c.num_attention_heads, c.kv_heads(window)
        normal = I.Normal(std=c.initializer_range)
        one = I.Constant(1.0)
        own = [("qkv", (h, nh * c.head_dim + g * (c.head_dim
                                                  + c.v_head_dim)), normal),
               ("o", (nh * c.v_head_dim, h), normal),
               ("ln_in", (h,), one), ("ln_post", (h,), one)]
        if window:
            own.append(("sink", (nh,), I.Constant(0.0)))
        if expert:
            self.mlp = HeldExperts(
                h, c.moe_intermediate_size, c.router_experts,
                c.n_routed_experts, first_held=c.first_held_expert,
                top_k=c.num_experts_per_tok, n_shared=0,
                scaling=c.routed_scaling_factor, dtype=c.dtype,
                init_std=c.initializer_range, selection_bias=True)
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

        return apply("window_moe_layer", kernel, (x, *leaves.values()))


class WindowMoEForCausalLM(Layer):
    """``forward(ids)`` gives logits [b, s, vocab]; with ``labels`` (same
    shape, already shifted, -100 ignored) the mean cross-entropy."""

    def __init__(self, config: WindowMoEConfig):
        super().__init__(dtype=config.dtype)
        c = self.config = config
        init = I.Normal(std=c.initializer_range)
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size], default_initializer=init)
        self.layers = []
        for i, (kind, moe) in enumerate(zip(c.hybrid_layer_pattern,
                                            c.moe_layer_freq)):
            blk = WindowMoEDecoderLayer(c, kind == WINDOW, bool(moe))
            self.add_sublayer(f"layers.{i}", blk)
            self.layers.append(blk)
        self.norm = self.create_parameter(
            [c.hidden_size], default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            [c.hidden_size, c.vocab_size], default_initializer=init)

    def forward(self, input_ids, labels=None):
        c = self.config
        x = F.embedding(input_ids, self.embed).astype(c.dtype)
        for blk in self.layers:
            x = blk(x)
        logits = apply("window_moe_head",
                       lambda xa, n, w: _rms(xa, n, c.layernorm_epsilon) @ w,
                       (x, self.norm, self.lm_head))
        return logits if labels is None else _token_loss(logits, labels)

    # -- serving ---------------------------------------------------------------

    serving_family_name = "window_moe"

    def serving_family(self, serving_config):
        """What :class:`paddle_tpu.serving.ServingEngine` asks a model
        for: its caches, its collected parameters, its step programs."""
        from ..serving.families.window_moe import WindowMoEFamily

        return WindowMoEFamily(self, serving_config)

    def generate(self, *args, **kwargs):
        from .generation import generate as _generate

        return _generate(self, *args, **kwargs)
