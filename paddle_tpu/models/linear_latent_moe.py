"""Linear-attention / latent-attention sparse-expert causal LM: a stack in
which most layers mix tokens through a GATED DELTA-RULE recurrence (a
matrix state a head that decays per key channel and is corrected, not
just added to: ``S <- Diag(alpha) S``, then ``S <- S + beta k (v - S^T
k)^T``) fed by three short depthwise causal convolutions and read out
under a per-head gated RMSNorm, and every fourth layer through latent
(low-rank key/value) attention with a direct query projection and NO
position embedding; the first layer's MLP is a dense SwiGLU, every other
layer's the expert layer of
``incubate/distributed/models/moe/held_experts.py`` with a per-expert
selection bias. Plain pre-norm, an untied head, no multipliers.
Kimi-Linear-48B-A3B's ``config.json`` (``model_type`` ``kimi_linear``)
describes one such model; key names below are that file's
(``linear_attn_config`` the nested dict it is, its layer lists 1-based).

Layer equations (``N(.; w)`` is RMSNorm with its own weight): ``x <- x +
Mix(N(x; ln_in))`` then ``x <- x + FFN(N(x; ln_post))``; logits ``N(x;
norm) lm_head``.

- ``Mix`` for a layer in ``kda_layers``, on ``u`` [T, hidden], ``H`` heads
  of ``d``: ``[q~ | k~ | v~] = u qkv``; each channel through its own
  depthwise causal convolution of ``short_conv_kernel_size`` taps (zeros
  before the start, no bias) and ``silu`` — the K-1 rows of ``[q~ | k~ |
  v~]`` before ``t`` are the CONV TAIL a served lane keeps; per head ``q =
  q' / ||q'|| * d^-1/2``, ``k = k' / ||k'||`` (1e-6 under the root), ``v =
  v'``. Log-decay per head AND key channel, float32: ``g = -exp(A_log[h])
  softplus((u f_a) f_b + dt_bias)``, ``alpha = exp(g)``; step size ``beta
  = sigmoid(u b)`` per head. State ``S`` [d key, d value] a head, float32
  (the STATE a served lane keeps): ``S' = Diag(alpha_t) S_{t-1}``, ``S_t =
  S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``. Output
  ``(N_head(o; o_norm) * sigmoid((u g_a) g_b)) o`` (the norm over each
  head's ``d``, then the gate).
- ``Mix`` for a layer in ``full_attn_layers``: the latent attention of
  ``models/latent_moe.py`` with ``q = u q`` (``q_lora_rank`` null) and the
  ``qk_rope_head_dim`` columns of query and key NOT rotated
  (``mla_use_nope``); the cache entry is ``[c_kv | k_pe]`` as there.
- ``FFN``: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; after that ``s = sigmoid(u router)``
  (float32), the ``num_experts_per_token`` largest of ``s + router_bias``,
  gates ``s / sum(s) * routed_scaling_factor`` over the chosen (the bias
  chooses, it does not weigh), the HELD experts' share, one shared SwiGLU.

Over more than one token the recurrence runs in its CHUNKED form
(:func:`kda_chunk`): with ``G`` the running sum of ``g`` inside a chunk,
the state after position ``t`` is ``Diag(exp G_t) S_0 + sum_{s<=t}
Diag(exp(G_t - G_s)) k_s u_s^T`` for pseudo-values ``u`` that solve the
unit lower-triangular system ``(I + Diag(beta) tril(A, -1)) u = beta (v -
(k exp G) S_0)``, ``A_ts = sum_c k_tc k_sc exp(G_tc - G_sc)`` (the WY form
of the product of the chunk's ``(I - beta k k^T) Diag(alpha)`` factors;
every exponent is <= 0, so nothing overflows at any decay); between chunks
the carried state. A position with ``g = 0`` and ``beta = 0`` is the
identity on the state, bit for bit (``exp(0) S = S``, ``0 * k (..)^T =
0``): how pads, and a verify round's rejected drafts, are kept out of it.

Served through :class:`paddle_tpu.serving.ServingEngine` (the model hands
it its family, ``serving/families/linear_latent_moe.py``: a latent block
pool beside a matrix state and a conv tail per LANE);
``models.generation.generate`` raises for it.
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
from .latent_moe import _token_loss, attend_upprojected, latent_qkv

__all__ = ["LinearLatentMoEConfig", "LinearLatentMoEForCausalLM"]

KDA, LATENT = "kda", "latent"
F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


class LinearLatentMoEConfig:
    """Key names follow the published ``config.json`` of the family.
    ``num_experts`` is how many experts are HELD here
    (``first_held_expert`` on); ``router_experts`` how many the router
    scores (default: the same, i.e. the whole layer). What that file
    states as flags is what this model IS and takes no argument: no
    position embedding in the latent layers (``mla_use_nope``), a direct
    query projection (``q_lora_rank`` null), sigmoid scores renormalised
    over the chosen experts (``moe_renormalize``) in one group, an expert
    layer in every layer past the dense ones (``moe_layer_freq`` 1), an
    untied head. ``kda_chunk_size`` is this program's (the recurrence is
    the same at any)."""

    def __init__(self, vocab_size=1024, hidden_size=128,
                 intermediate_size=256, moe_intermediate_size=64,
                 num_hidden_layers=4, first_k_dense_replace=1,
                 linear_attn_config=None, num_attention_heads=4,
                 kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
                 v_head_dim=32, num_experts=8, router_experts=None,
                 first_held_expert=0, num_shared_experts=1,
                 num_experts_per_token=2, routed_scaling_factor=1.0,
                 kda_chunk_size=32, max_position_embeddings=4096,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 dtype="float32"):
        if linear_attn_config is None:  # one latent layer closes the stack
            linear_attn_config = {
                "kda_layers": list(range(1, num_hidden_layers)),
                "full_attn_layers": [num_hidden_layers],
                "num_heads": 4, "head_dim": 32, "short_conv_kernel_size": 4}
        la = dict(linear_attn_config)
        kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
        if kda & full or kda | full != set(range(1, num_hidden_layers + 1)):
            raise ValueError(
                f"linear_attn_config names each of the layers 1.."
                f"{num_hidden_layers} once, in kda_layers or "
                f"full_attn_layers; got {sorted(kda)} and {sorted(full)}")
        if not 0 <= first_k_dense_replace <= num_hidden_layers:
            raise ValueError("first_k_dense_replace outside the stack")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.first_k_dense_replace = first_k_dense_replace
        self.linear_attn_config = la
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.num_experts = num_experts
        self.router_experts = router_experts or num_experts
        self.first_held_expert = first_held_expert
        self.num_shared_experts = num_shared_experts
        self.num_experts_per_token = num_experts_per_token
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.kda_chunk_size = kda_chunk_size
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = float(rms_norm_eps)
        # std of every matrix's initial values (0: born zero at no cost)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype

    @property
    def layer_kinds(self):
        """``"kda"`` or ``"latent"`` for layers 0.. (the lists are 1-based)."""
        kda = set(self.linear_attn_config["kda_layers"])
        return tuple(KDA if i + 1 in kda else LATENT
                     for i in range(self.num_hidden_layers))

    kda_heads = property(lambda self: self.linear_attn_config["num_heads"])
    kda_head_dim = property(lambda self: self.linear_attn_config["head_dim"])
    kda_taps = property(
        lambda self: self.linear_attn_config["short_conv_kernel_size"])

    @property
    def kda_width(self):
        """Channels of one of q, k, v: heads x head size."""
        return self.kda_heads * self.kda_head_dim

    @property
    def latent_width(self):
        """Numbers cached a token a latent layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def static(self):
        """The hashable view the compiled programs are keyed on."""
        return _Static(self)


class _Static:
    """Value-hashable static view of the fields a traced layer reads (a
    config object hashes by identity; see ``generation._GenCfg``)."""

    __slots__ = ("layer_kinds", "kda_heads", "kda_head_dim", "kda_taps",
                 "kda_width", "kda_chunk_size", "num_attention_heads",
                 "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                 "v_head_dim", "first_held_expert", "num_experts_per_token",
                 "routed_scaling_factor", "rms_norm_eps", "dtype")

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


# -- the gated delta-rule mixer, on arrays --------------------------------------

def kda_project(u, lp, cfg):
    """``u`` [b, T, hidden] normed -> the conv input ``[q~ | k~ | v~]``
    [b, T, 3 x heads x d]."""
    with jax.named_scope("kda/proj"):
        return u @ lp["qkv"]


def kda_conv(window, lp, cfg):
    """The three depthwise causal convolutions (side by side: one over
    all the channels) and their silu over ``window`` [b, K-1 + T, 3 x
    width] — the K-1 rows before the first position (the conv tail; zeros
    at a sequence's start), then the T positions' ``[q~ | k~ | v~]``.
    Returns q, k, v [b, T, H, d] float32, q and k L2-normalised per head
    and q scaled by ``d^-1/2``; sums in float32."""
    K, H, d = cfg.kda_taps, cfg.kda_heads, cfg.kda_head_dim
    b, T = window.shape[0], window.shape[1] - (K - 1)
    with jax.named_scope("kda/conv"):
        w = lp["conv_w"].astype(F32)                       # [C, 1, K]
        acc = w[:, 0, 0] * window[:, 0:T].astype(F32)
        for j in range(1, K):
            acc = acc + w[:, 0, j] * window[:, j:j + T].astype(F32)
        q, k, v = jnp.split(jax.nn.silu(acc).reshape(b, T, 3 * H, d), 3,
                            axis=2)

        def unit(a):
            return a * jax.lax.rsqrt(
                jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)

        return unit(q) * d ** -0.5, unit(k), v


def kda_gates(u, lp, cfg):
    """(g [b, T, H, d] float32: the log-decay ``-exp(A_log) softplus((u
    f_a) f_b + dt_bias)`` per head and key channel, <= 0; beta [b, T, H]
    float32 = ``sigmoid(u b)``)."""
    b, T, _ = u.shape
    H, d = cfg.kda_heads, cfg.kda_head_dim
    with jax.named_scope("kda/gates"):
        f = ((u @ lp["f_a"]) @ lp["f_b"]).astype(F32) \
            + lp["dt_bias"].astype(F32)
        g = -jnp.exp(lp["A_log"].astype(F32))[:, None] \
            * jax.nn.softplus(f).reshape(b, T, H, d)
        return g, jax.nn.sigmoid((u @ lp["b"]).astype(F32))


def kda_step(S, q, k, v, g, beta):
    """One position of the recurrence for every row: ``S`` [b, H, d, d]
    (key x value) -> (``S' + beta k (v - S'^T k)^T`` with ``S' = Diag(exp
    g) S``, ``o = S_t^T q`` [b, H, d]); q, k, g [b, H, d], v [b, H, d],
    beta [b, H]. The two products with the old state come from ONE read
    of it (``o = S'^T q + (k.q) beta (v - S'^T k)``), the update from a
    second; float32 multiply-and-sum, no matrix unit. ``g`` 0 and ``beta``
    0 leave ``S`` as it is, bit for bit."""
    alpha = jnp.exp(g)
    pred = jnp.sum(S * (alpha * k)[..., None], axis=-2)
    read = jnp.sum(S * (alpha * q)[..., None], axis=-2)
    u = beta[..., None] * (v - pred)
    S = S * alpha[..., None] + k[..., None] * u[..., None, :]
    return S, read + jnp.sum(k * q, -1, keepdims=True) * u


def _unit_lower_inverse(L):
    """``(I + L)^-1`` for strictly lower-triangular ``L`` [..., C, C]:
    ``L^C = 0``, so the inverse is the finite product ``(I - L)(I +
    L^2)(I + L^4)...`` — log2(C) small matrix products."""
    C = L.shape[-1]
    eye = jnp.eye(C, dtype=L.dtype)
    x = -L
    inv, n = eye + x, 2
    while n < C:
        x = jnp.matmul(x, x, precision=_HI)
        inv = jnp.matmul(inv, eye + x, precision=_HI)
        n *= 2
    return inv


def kda_wy(q, k, v, g, beta):
    """What ONE chunk's outputs and end state need that does not depend
    on the state it starts from. q, k, v, g [b, H, C, d], beta [b, H, C],
    float32. Returns a dict: ``G`` the running sum of ``g``; ``Wv`` [.., C,
    d] and ``Wk`` [.., C, d] with ``u = Wv - Wk S_0`` the pseudo-values;
    ``qg`` = ``q exp G`` and ``B`` [.., C, C] with ``o = qg S_0 + B u``."""
    C = q.shape[-2]
    G = jnp.cumsum(g, axis=-2)
    # exp(G_t - G_s) for s <= t, per key channel: every exponent <= 0
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower[..., None],
                              G[..., :, None, :] - G[..., None, :, :],
                              -jnp.inf))
    kk = k[..., None, :, :] * decay
    A = jnp.sum(k[..., :, None, :] * kk, -1)
    B = jnp.sum(q[..., :, None, :] * kk, -1)
    T = _unit_lower_inverse(
        beta[..., None] * jnp.where(jnp.tril(lower, -1), A, 0.0))
    eG = jnp.exp(G)
    rhs = beta[..., None] * jnp.concatenate([v, k * eG], -1)
    W = jnp.matmul(T, rhs, precision=_HI)
    d = v.shape[-1]
    return {"G": G, "Wv": W[..., :d], "Wk": W[..., d:], "qg": q * eG,
            "B": B}


def kda_read(wy, S):
    """One read of the state ``S`` [b, H, d, d] for a chunk's ``wy``:
    (outputs o [b, H, C, d], pseudo-values u [b, H, C, d])."""
    C = wy["Wk"].shape[-2]
    both = jnp.matmul(jnp.concatenate([wy["Wk"], wy["qg"]], -2), S)
    u = wy["Wv"] - both[..., :C, :]
    return both[..., C:, :] + jnp.matmul(wy["B"], u), u


def kda_apply(S, k, g, u):
    """The state after a chunk whose pseudo-values are ``u``: ``Diag(exp
    G_C) S + sum_s (k_s exp(G_C - G_s)) u_s^T`` with ``G`` the running sum
    of ``g``. ONE pass over ``S`` [b, H, d, d]: k, g, u [b, H, C, d]. A
    position with ``g`` 0 and ``u`` 0 adds nothing, bit for bit — a short
    chunk (a verify round's) is summed position by position in float32,
    a long one as a matrix product."""
    G = jnp.cumsum(g, axis=-2)
    end = G[..., -1:, :]
    kd = k * jnp.exp(end - G)
    S = S * jnp.exp(end[..., 0, :])[..., None]
    C = k.shape[-2]
    if C > 8:
        return S + jnp.einsum("bhck,bhcv->bhkv", kd, u)
    for s in range(C):
        S = S + kd[..., s, :, None] * u[..., s, None, :]
    return S


def kda_chunk(q, k, v, g, beta, S0, chunk):
    """The recurrence over T positions in its chunked form, float32: q, k,
    v, g [b, T, H, d], beta [b, T, H], ``S0`` [b, H, d, d] the state before
    the first position. Returns (o [b, T, H, d], S_T). A length that is no
    multiple of the chunk is padded with ``g`` 0 and ``beta`` 0."""
    b, T, H, d = q.shape
    C = min(chunk, T)
    nc = -(-T // C)
    pad = nc * C - T

    def cut(a):  # [b, T, H, ...] -> [nc, b, H, C, ...]
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(b, nc, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    qc, kc, vc, gc, bc = (cut(a) for a in (q, k, v, g, beta))
    wy = kda_wy(qc, kc, vc, gc, bc)

    def one(S, inp):
        w, kk, gg = inp
        o, u = kda_read(w, S)
        return kda_apply(S, kk, gg, u), o

    if nc == 1:
        S, o = one(S0, (jax.tree_util.tree_map(lambda a: a[0], wy), kc[0],
                        gc[0]))
        o = o[None]
    else:
        S, o = jax.lax.scan(one, S0, (wy, kc, gc))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, nc * C, H, d)
    return o[:, :T], S


def kda_gate_out(o, u, lp, cfg):
    """``(N_head(o; o_norm) * sigmoid((u g_a) g_b)) o``: o [b, T, H, d]
    float32, u [b, T, hidden] the mixer's normed input; the norm runs over
    each head's ``d``, in float32, then the gate."""
    b, T = u.shape[:2]
    with jax.named_scope("kda/gate_norm"):
        gate = jax.nn.sigmoid(((u @ lp["g_a"]) @ lp["g_b"]).astype(F32))
        y = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + cfg.rms_norm_eps) \
            * lp["o_norm"].astype(F32)
        y = (y.reshape(b, T, -1) * gate).astype(u.dtype)
    with jax.named_scope("kda/out_proj"):
        return y @ lp["o"]


def kda_mix(u, lp, cfg):
    """The mixer over whole sequences ``u`` [b, T, hidden] (normed) from a
    zero state and a zero conv tail."""
    raw = kda_project(u, lp, cfg)
    with jax.named_scope("kda/conv"):
        window = jnp.pad(raw, ((0, 0), (cfg.kda_taps - 1, 0), (0, 0)))
    q, k, v = kda_conv(window, lp, cfg)
    g, beta = kda_gates(u, lp, cfg)
    with jax.named_scope("kda/state_update"):
        S0 = jnp.zeros((u.shape[0], cfg.kda_heads, cfg.kda_head_dim,
                        cfg.kda_head_dim), F32)
        o, _ = kda_chunk(q, k, v, g, beta, S0, cfg.kda_chunk_size)
    return kda_gate_out(o, u, lp, cfg)


def latent_mix(u, lp, cfg):
    """Latent attention over whole sequences, causal, in the published
    (up-projected) form, no position embedding."""
    b, s, _ = u.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    q_nope, q_pe, entry = latent_qkv(u, lp, pos, cfg, rope=False)
    vis = jnp.broadcast_to(
        (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])[None], (b, s, s))
    with jax.named_scope("mla/attend"):
        att = attend_upprojected(q_nope, q_pe, entry, vis, lp, cfg)
    with jax.named_scope("mla/out"):
        return att @ lp["o"]


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
        u, lp, top_k=cfg.num_experts_per_token,
        scaling=cfg.routed_scaling_factor,
        first_held=cfg.first_held_expert, valid=valid)
    with jax.named_scope("moe/combine"):
        return y.reshape(b, s, h), counts


def layer_on_sequence(x, lp, cfg):
    """One layer over whole sequences ``x`` [b, T, hidden] (no cache, no
    carried state); the kind of mixer told by the layer's leaves."""
    eps = cfg.rms_norm_eps
    u = _rms(x, lp["ln_in"], eps)
    x = x + (kda_mix if "qkv" in lp else latent_mix)(u, lp, cfg)
    return x + ffn_block(_rms(x, lp["ln_post"], eps), lp, cfg)[0]


# -- the Layer graph ----------------------------------------------------------

def _published_kda_init(H, d, seed):
    """The decay leaves as the published code is born: ``A`` uniform in
    [1, 16] a head, ``dt`` log-uniform in [1e-3, 1e-1] a channel
    (``dt_bias`` its inverse softplus) — slow decay, a memory of tens to
    hundreds of tokens."""
    rng = np.random.default_rng([0x4DA, seed])
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), H * d))
    return {"A_log": np.log(rng.uniform(1.0, 16.0, H)),
            "dt_bias": dt + np.log(-np.expm1(-dt))}


class LinearLatentMoEDecoderLayer(Layer):
    """``kind`` is ``"kda"`` or ``"latent"``, ``ffn`` ``"dense"`` or
    ``"expert"``. Its parameters by leaf name (``leaves()``) are what the
    layer functions take."""

    def __init__(self, c: LinearLatentMoEConfig, kind: str, ffn: str,
                 index: int = 0):
        super().__init__(dtype=c.dtype)  # parameters are born in it
        h = c.hidden_size
        normal = I.Normal(std=c.initializer_range)
        one = I.Constant(1.0)
        if kind == KDA:
            H, d, w = c.kda_heads, c.kda_head_dim, c.kda_width
            born = _published_kda_init(H, d, index)
            mixer = [("qkv", (h, 3 * w), normal),
                     ("conv_w", (3 * w, 1, c.kda_taps), normal),
                     ("f_a", (h, d), normal), ("f_b", (d, w), normal),
                     ("dt_bias", (w,), I.Assign(born["dt_bias"])),
                     ("A_log", (H,), I.Assign(born["A_log"])),
                     ("b", (h, H), normal),
                     ("g_a", (h, d), normal), ("g_b", (d, w), normal),
                     ("o_norm", (d,), one), ("o", (w, h), normal)]
        else:
            nh = c.num_attention_heads
            mixer = [("q", (h, nh * (c.qk_nope_head_dim
                                     + c.qk_rope_head_dim)), normal),
                     ("kv_a", (h, c.latent_width), normal),
                     ("kv_norm", (c.kv_lora_rank,), one),
                     ("kv_b", (c.kv_lora_rank,
                               nh * (c.qk_nope_head_dim + c.v_head_dim)),
                      normal),
                     ("o", (nh * c.v_head_dim, h), normal)]
        own = mixer + [("ln_in", (h,), one), ("ln_post", (h,), one)]
        self.kind, self.ffn = kind, ffn
        if ffn == "dense":
            f = c.intermediate_size
            own += [("gate_up", (h, 2 * f), normal), ("down", (f, h), normal)]
            self.mlp = None
        else:
            self.mlp = HeldExperts(
                h, c.moe_intermediate_size, c.router_experts, c.num_experts,
                first_held=c.first_held_expert,
                top_k=c.num_experts_per_token, n_shared=c.num_shared_experts,
                scaling=c.routed_scaling_factor, dtype=c.dtype,
                init_std=c.initializer_range, selection_bias=True)
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

        return apply("linear_latent_moe_layer", kernel,
                     (x, *leaves.values()))


class LinearLatentMoEForCausalLM(Layer):
    """``forward(ids)`` gives logits [b, s, vocab]; with ``labels`` (same
    shape, already shifted, -100 ignored) the mean cross-entropy."""

    def __init__(self, config: LinearLatentMoEConfig):
        super().__init__(dtype=config.dtype)
        c = self.config = config
        init = I.Normal(std=c.initializer_range)
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size], default_initializer=init)
        self.layers = []
        for i, kind in enumerate(c.layer_kinds):
            blk = LinearLatentMoEDecoderLayer(
                c, kind, "dense" if i < c.first_k_dense_replace
                else "expert", i)
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
        logits = apply("linear_latent_moe_head",
                       lambda xa, n, w: _rms(xa, n, c.rms_norm_eps) @ w,
                       (x, self.norm, self.lm_head))
        return logits if labels is None else _token_loss(logits, labels)

    # -- serving ---------------------------------------------------------------

    serving_family_name = "linear_latent_moe"

    def serving_family(self, serving_config):
        """What :class:`paddle_tpu.serving.ServingEngine` asks a model
        for: its caches, its collected parameters, its step programs."""
        from ..serving.families.linear_latent_moe import (
            LinearLatentMoEFamily,
        )

        return LinearLatentMoEFamily(self, serving_config)

    def generate(self, *args, **kwargs):
        from .generation import generate as _generate

        return _generate(self, *args, **kwargs)
