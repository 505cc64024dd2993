"""Latent-attention, sparse-expert causal LM: low-rank (latent) attention
whose cache holds a compressed key/value and one rotary key a token, a
norm on each sub-layer's input AND output (sandwich norm), leading dense
SwiGLU layers followed by expert layers (a dropless sigmoid top-k router
over ``router_experts`` experts of which this chip holds ``n_held``, plus
a shared expert), and an optional next-next-token prediction module.
openPangu-Ultra-MoE-718B's ``config.json`` describes one such model; the
DeepSeek-V2/V3 family is another.

Layer equations (``N(.; w)`` is RMSNorm with its own weight):

- attention on ``a = N(x; ln_in)``: ``c_q = N(a q_a; q_norm)``,
  ``q = c_q q_b`` -> heads x (nope | rope); ``[c_kv | k_r] = a kv_a``,
  ``c_kv = N(c_kv; kv_norm)``, ``k_rope = RoPE(k_r)`` (one for all
  heads), ``q_rope = RoPE(q_rope)``; ``[k_nope | v] = c_kv kv_b`` ->
  heads x (nope | v). Scores ``(q_nope.k_nope + q_rope.k_rope) /
  sqrt(nope + rope)``, causal, float32 softmax; output
  ``concat_heads(P v) o``. The CACHE is ``[c_kv | k_rope]``: ``kv_lora_rank
  + qk_rope_head_dim`` numbers a token a layer.
- ``x <- x + N(Attn(N(x; ln_in)); ln_attn_out)``, then
  ``x <- x + N(MLP(N(x; ln_mlp_in)); ln_mlp_out)``.
- MLP: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers, after that the expert layer of
  ``incubate/distributed/models/moe/held_experts.py``.
- next-next-token module (depth 1): ``h' = proj [N(emb(t_{i+1}); e_norm) ;
  N(h_i; h_norm)]``, one expert-kind layer, the model's final norm and
  head, predicting token i+2; ``h_i`` is the stack's output before the
  final norm.

Two forms of the attention are here. ``attend_upprojected`` is the
published one (keys and values expanded from the latent for every cached
position): the model's own ``forward`` runs it over the sequence it is
given. ``attend_absorbed`` folds ``kv_b``'s key half into the query and
its value half into the output, so scores and the weighted sum read the
latent directly: what the serving step programs run over the block pool
(``serving/families/latent_moe.py``). They compute the same function
(tests/test_latent_moe.py).

Served through :class:`paddle_tpu.serving.ServingEngine` (the model
hands it its family, :meth:`LatentMoEForCausalLM.serving_family`);
``models.generation.generate`` raises for it: its contiguous K/V cache
has no latent form.
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
from .llama import _rope

__all__ = ["LatentMoEConfig", "LatentMoEForCausalLM"]

ATTN_LEAVES = ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o")
NORM_LEAVES = ("ln_in", "ln_attn_out", "ln_mlp_in", "ln_mlp_out")
DENSE_LEAVES = ("gate_up", "down")
EXPERT_LEAVES = HeldExperts._NAMES


class LatentMoEConfig:
    """Key names follow the published ``config.json`` of the family.
    ``n_routed_experts`` is how many experts are HELD here
    (``first_held_expert`` on); ``router_experts`` how many the router
    scores (default: the same, i.e. the whole layer). What the family's
    ``config.json`` states as flags is what this model IS and takes no
    argument: a norm on each sub-layer's input and output
    (``sandwich_norm``), gates normalised over the chosen experts
    (``norm_topk_prob``), an untied head (``tie_word_embeddings`` false)."""

    def __init__(self, vocab_size=1024, hidden_size=256,
                 intermediate_size=512, moe_intermediate_size=64,
                 num_hidden_layers=3, first_k_dense_replace=1,
                 num_attention_heads=4, q_lora_rank=96, kv_lora_rank=64,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
                 n_routed_experts=8, router_experts=None,
                 first_held_expert=0, n_shared_experts=1,
                 num_experts_per_tok=2, routed_scaling_factor=1.0,
                 num_nextn_predict_layers=0, mtp_loss_weight=0.1,
                 max_position_embeddings=4096, rms_norm_eps=1e-5,
                 rope_theta=10000.0, initializer_range=0.02,
                 dtype="float32"):
        if not 0 <= first_k_dense_replace <= num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the stack")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_routed_experts = n_routed_experts
        self.router_experts = router_experts or n_routed_experts
        self.first_held_expert = first_held_expert
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.num_nextn_predict_layers = num_nextn_predict_layers
        self.mtp_loss_weight = float(mtp_loss_weight)
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = float(rms_norm_eps)
        self.rope_theta = float(rope_theta)
        # std of every matrix's initial values (0: born zero at no cost)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype
        if num_nextn_predict_layers not in (0, 1):
            raise ValueError("one next-token module at most")

    @property
    def latent_width(self):
        """Numbers cached a token a layer: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def static(self):
        """The hashable view the compiled programs are keyed on."""
        return _Static(self)


class _Static:
    """Value-hashable static view of the fields a traced layer reads (a
    config object hashes by identity; see ``generation._GenCfg``)."""

    __slots__ = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
                 "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                 "first_held_expert", "num_experts_per_tok",
                 "routed_scaling_factor", "rms_norm_eps", "rope_theta",
                 "dtype")

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


# -- the layer's mathematics, on arrays ---------------------------------------

def latent_qkv(a, lp, pos, cfg, rope=True):
    """From normed input ``a`` [b, s, h] at positions ``pos`` [b, s]:
    (q_nope [b, s, nh, dn], q_rope [b, s, nh, dr] rotated, and the cache
    entry ``[c_kv | k_rope]`` [b, s, dc + dr], normed and rotated). A
    layer with a ``q`` leaf in place of ``q_a`` / ``q_norm`` / ``q_b``
    projects its query directly; ``rope`` False leaves the ``dr`` columns
    of query and key as projected (no position embedding)."""
    b, s, _ = a.shape
    nh, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.qk_rope_head_dim)
    with jax.named_scope("mla/q"):
        if "q" in lp:
            q = (a @ lp["q"]).reshape(b, s, nh, dn + dr)
        else:
            c_q = _rms(a @ lp["q_a"], lp["q_norm"], cfg.rms_norm_eps)
            q = (c_q @ lp["q_b"]).reshape(b, s, nh, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
    with jax.named_scope("mla/kv_write"):
        kv = a @ lp["kv_a"]
        c_kv = _rms(kv[..., :cfg.kv_lora_rank], lp["kv_norm"],
                    cfg.rms_norm_eps)
        k_rope = kv[..., None, cfg.kv_lora_rank:]
        if rope:
            q_rope, k_rope = _rope(q_rope, k_rope, cfg.rope_theta, a.dtype,
                                   pos=pos)
        entry = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)
    return q_nope, q_rope, entry


def _kvb_halves(lp, cfg):
    nh, dn, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.v_head_dim)
    w = lp["kv_b"].reshape(cfg.kv_lora_rank, nh, dn + dv)
    return w[..., :dn], w[..., dn:]


def _masked_softmax(scores, vis, cfg):
    scores = scores / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    scores = jnp.where(vis[:, :, None, :], scores, -1e30)
    return jax.nn.softmax(scores, axis=-1)


def attend_upprojected(q_nope, q_rope, cache, vis, lp, cfg):
    """The published form: keys and values expanded from the latent for
    every cached position. ``cache`` [b, L, dc + dr]; ``vis`` [b, s, L]
    marks the slots each query may see. Returns [b, s, nh * dv]."""
    b, s, nh, _ = q_nope.shape
    dc = cfg.kv_lora_rank
    w_k, w_v = _kvb_halves(lp, cfg)
    f32 = jnp.float32
    c_kv, k_rope = cache[..., :dc], cache[..., dc:]
    k_nope = jnp.einsum("blc,chd->blhd", c_kv, w_k)
    v = jnp.einsum("blc,chd->blhd", c_kv, w_v)
    scores = (jnp.einsum("bshd,blhd->bshl", q_nope, k_nope,
                         preferred_element_type=f32)
              + jnp.einsum("bshr,blr->bshl", q_rope, k_rope,
                           preferred_element_type=f32))
    p = _masked_softmax(scores, vis, cfg).astype(v.dtype)
    out = jnp.einsum("bshl,blhd->bshd", p, v, preferred_element_type=f32)
    return out.astype(q_nope.dtype).reshape(b, s, nh * cfg.v_head_dim)


def absorb_query(q_nope, q_rope, lp, cfg, stored):
    """``kv_b``'s key half folded into the query: ``[q_nope W_k | q_rope
    | 0]`` per head, ``stored`` wide — a cache entry's width as it is
    kept (the serving pool pads it to whole lane tiles: zeros on the
    query's side too)."""
    b, s, nh, _ = q_nope.shape
    dt = q_nope.dtype
    w_k, _ = _kvb_halves(lp, cfg)
    q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w_k,
                       preferred_element_type=jnp.float32).astype(dt)
    pad = jnp.zeros(
        (b, s, nh, stored - cfg.kv_lora_rank - q_rope.shape[-1]), dt)
    return jnp.concatenate([q_lat, q_rope, pad], axis=-1)


def unabsorb_output(o_lat, lp, cfg):
    """``kv_b``'s value half applied to the per-head weighted sums of the
    latent ``o_lat`` [b, s, nh, dc]. Returns [b, s, nh * dv] in
    ``o_lat``'s dtype."""
    b, s, nh, _ = o_lat.shape
    _, w_v = _kvb_halves(lp, cfg)
    out = jnp.einsum("bshc,chd->bshd", o_lat, w_v,
                     preferred_element_type=jnp.float32)
    return out.astype(o_lat.dtype).reshape(
        b, s, nh * cfg.v_head_dim)


def attend_absorbed(q_nope, q_rope, cache, vis, lp, cfg):
    """The same function with ``kv_b`` absorbed: ``q_lat = q_nope W_k``
    per head, scores over the latent and the rotary key as cached, ``o =
    (P c_kv) W_v``. No key or value is ever expanded: per cached slot the
    work is ``2 * nh * (2 dc + dr)`` FLOP on ``dc + dr`` numbers read.
    Matmuls in the model dtype with float32 accumulation, float32
    softmax. The serving programs run this arithmetic over the rows
    their lanes hold (``serving/families/latent_moe.py:attend_pool``:
    the row kernel's one-pool form, held to this function by
    tests/test_serving_rows.py)."""
    dc = cfg.kv_lora_rank
    f32 = jnp.float32
    dt = q_nope.dtype
    with jax.named_scope("mla/attend"):
        qq = absorb_query(q_nope, q_rope, lp, cfg, cache.shape[-1])
        scores = jnp.einsum("bshe,ble->bshl", qq, cache,
                            preferred_element_type=f32)
        p = _masked_softmax(scores, vis, cfg).astype(dt)
        o_lat = jnp.einsum("bshl,blc->bshc", p, cache[..., :dc],
                           preferred_element_type=f32).astype(dt)
        return unabsorb_output(o_lat, lp, cfg)


def mlp_block(u, lp, cfg, valid=None):
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
    """One decoder layer over whole sequences ``x`` [b, s, h] (no cache:
    the sequence is its own), causal, attention in the published form."""
    b, s, _ = x.shape
    eps = cfg.rms_norm_eps
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    q_nope, q_rope, entry = latent_qkv(_rms(x, lp["ln_in"], eps), lp, pos,
                                       cfg)
    vis = jnp.broadcast_to(
        (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])[None], (b, s, s))
    att = attend_upprojected(q_nope, q_rope, entry, vis, lp, cfg) @ lp["o"]
    x = x + _rms(att, lp["ln_attn_out"], eps)
    y, _ = mlp_block(_rms(x, lp["ln_mlp_in"], eps), lp, cfg)
    return x + _rms(y, lp["ln_mlp_out"], eps)


# -- the Layer graph ----------------------------------------------------------

class _Leaves(Layer):
    """A layer whose parameters are named leaves created from a
    (name, shape, is_norm) list."""

    def __init__(self, leaves, c):
        super().__init__(dtype=c.dtype)  # parameters are born in it
        for name, shape, is_norm in leaves:
            setattr(self, name, self.create_parameter(
                list(shape), default_initializer=I.Constant(1.0) if is_norm
                else I.Normal(std=c.initializer_range)))


class LatentAttention(_Leaves):
    def __init__(self, c: LatentMoEConfig):
        h, nh = c.hidden_size, c.num_attention_heads
        super().__init__([
            ("q_a", (h, c.q_lora_rank), False),
            ("q_norm", (c.q_lora_rank,), True),
            ("q_b", (c.q_lora_rank,
                     nh * (c.qk_nope_head_dim + c.qk_rope_head_dim)), False),
            ("kv_a", (h, c.latent_width), False),
            ("kv_norm", (c.kv_lora_rank,), True),
            ("kv_b", (c.kv_lora_rank,
                      nh * (c.qk_nope_head_dim + c.v_head_dim)), False),
            ("o", (nh * c.v_head_dim, h), False)], c)


class GatedMLP(_Leaves):
    def __init__(self, c):
        h, f = c.hidden_size, c.intermediate_size
        super().__init__([("gate_up", (h, 2 * f), False),
                          ("down", (f, h), False)], c)


class LatentMoEDecoderLayer(_Leaves):
    """``kind`` is ``"dense"`` or ``"expert"``. Its parameters by leaf
    name (``leaves()``) are what the layer functions take."""

    def __init__(self, c: LatentMoEConfig, kind: str):
        super().__init__([(n, (c.hidden_size,), True) for n in NORM_LEAVES],
                         c)
        self.kind = kind
        self.attn = LatentAttention(c)
        if kind == "dense":
            self.mlp = GatedMLP(c)
        else:
            self.mlp = HeldExperts(
                c.hidden_size, c.moe_intermediate_size, c.router_experts,
                c.n_routed_experts, first_held=c.first_held_expert,
                top_k=c.num_experts_per_tok, n_shared=c.n_shared_experts,
                scaling=c.routed_scaling_factor, dtype=c.dtype,
                init_std=c.initializer_range)
        self._static = c.static()

    def leaves(self) -> dict:
        out = {n: getattr(self, n) for n in NORM_LEAVES}
        out.update({n: getattr(self.attn, n) for n in ATTN_LEAVES})
        names = DENSE_LEAVES if self.kind == "dense" else EXPERT_LEAVES
        out.update({n: getattr(self.mlp, n) for n in names})
        return out

    def forward(self, x):
        leaves = self.leaves()
        names, cfg = tuple(leaves), self._static

        def kernel(xa, *ws):
            return layer_on_sequence(xa, dict(zip(names, ws)), cfg)

        return apply("latent_moe_layer", kernel, (x, *leaves.values()))


class NextTokenModule(_Leaves):
    """Depth-1 multi-token prediction: from the stack's output at
    position i and the embedding of token i+1, the hidden state that the
    model's final norm and head turn into a prediction of token i+2."""

    def __init__(self, c: LatentMoEConfig):
        h = c.hidden_size
        super().__init__([("e_norm", (h,), True), ("h_norm", (h,), True),
                          ("proj", (2 * h, h), False)], c)
        self.layer = LatentMoEDecoderLayer(c, "expert")
        self._eps = c.rms_norm_eps

    def forward(self, hidden, next_emb):
        eps = self._eps

        def mix(ha, ea, e_norm, h_norm, proj):
            return jnp.concatenate([_rms(ea, e_norm, eps),
                                    _rms(ha, h_norm, eps)], -1) @ proj

        return self.layer(apply(
            "latent_moe_mtp_mix", mix,
            (hidden, next_emb, self.e_norm, self.h_norm, self.proj)))


class LatentMoEForCausalLM(Layer):
    """``forward(ids)`` gives logits [b, s, vocab]; with ``labels`` (same
    shape, already shifted, -100 ignored) the mean cross-entropy, plus
    ``mtp_loss_weight`` x the next-token module's loss on token i+2 when
    the model has one."""

    def __init__(self, config: LatentMoEConfig):
        super().__init__(dtype=config.dtype)
        c = self.config = config
        init = I.Normal(std=c.initializer_range)
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size], default_initializer=init)
        self.layers = []
        for i in range(c.num_hidden_layers):
            blk = LatentMoEDecoderLayer(
                c, "dense" if i < c.first_k_dense_replace else "expert")
            self.add_sublayer(f"layers.{i}", blk)
            self.layers.append(blk)
        self.norm = self.create_parameter(
            [c.hidden_size], default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            [c.hidden_size, c.vocab_size], default_initializer=init)
        self.mtp = NextTokenModule(c) if c.num_nextn_predict_layers else None

    # -- forward ---------------------------------------------------------------

    def _embed(self, ids):
        return F.embedding(ids, self.embed).astype(self.config.dtype)

    def hidden(self, input_ids):
        """The stack's output, before the final norm."""
        x = self._embed(input_ids)
        for blk in self.layers:
            x = blk(x)
        return x

    def _head(self, x):
        eps = self.config.rms_norm_eps
        return apply("latent_moe_head",
                     lambda xa, n, w: _rms(xa, n, eps) @ w,
                     (x, self.norm, self.lm_head))

    def mtp_logits(self, input_ids, hidden=None):
        """[b, s-1, vocab]: at position i the next-token module's logits
        for token i+2, from the stack's ``hidden`` at i and token i+1."""
        if self.mtp is None:
            raise ValueError("the model has no next-token module "
                             "(num_nextn_predict_layers=0)")
        if hidden is None:
            hidden = self.hidden(input_ids)
        return self._head(self.mtp(hidden[:, :-1],
                                   self._embed(input_ids[:, 1:])))

    def forward(self, input_ids, labels=None):
        hidden = self.hidden(input_ids)
        logits = self._head(hidden)
        if labels is None:
            return logits
        loss = _token_loss(logits, labels)
        if self.mtp is not None and self.config.mtp_loss_weight > 0:
            loss = loss + self.config.mtp_loss_weight * _token_loss(
                self.mtp_logits(input_ids, hidden), labels[:, 1:])
        return loss

    # -- serving ---------------------------------------------------------------

    serving_family_name = "latent_moe"

    def serving_family(self, serving_config):
        """What :class:`paddle_tpu.serving.ServingEngine` asks a model
        for: its cache, its collected parameters, its step programs."""
        from ..serving.families.latent_moe import LatentMoEFamily

        return LatentMoEFamily(self, serving_config)

    def generate(self, *args, **kwargs):
        from .generation import generate as _generate

        return _generate(self, *args, **kwargs)


def _token_loss(logits, labels):
    from ..distributed.fleet.meta_parallel import masked_token_mean

    per_tok = F.cross_entropy(logits.astype("float32"),
                              labels.unsqueeze(-1), reduction="none")
    return masked_token_mean(per_tok, labels, -100)
