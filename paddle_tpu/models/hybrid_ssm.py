"""Hybrid state-space / attention causal LM: a stack in which most layers
mix tokens through a selective state-space recurrence (a per-head decayed
outer-product state, fed through a short depthwise causal convolution and
read out under a gated RMSNorm) and every few layers through grouped-query
attention WITHOUT rotary embedding and with a stated score multiplier;
each layer is followed by a SwiGLU MLP, the residual branches, the
embedding and the logits carry constant multipliers, and the head is the
embedding transposed. granite-4.0-h-micro's ``config.json``
(``model_type`` ``granitemoehybrid`` with no routed experts) describes one
such model; key names below are that file's.

Layer equations (``N(.; w)`` is RMSNorm with its own weight):

- ``x0 = embedding_multiplier * embed[ids]``; per layer ``x <- x +
  residual_multiplier * Mix(N(x; ln_in))`` then ``x <- x +
  residual_multiplier * MLP(N(x; ln_post))``; ``MLP(u) = (silu(g) * v)
  down`` with ``[g | v] = u gate_up``; logits ``= N(x; norm) embed^T /
  logits_scaling``.
- ``Mix`` by ``layer_types[i]``: ``"attention"`` — ``[q | k | v] = u
  qkv`` (heads x d, kv heads x d twice), no position embedding, scores
  ``q.k * attention_multiplier``, causal, float32 softmax, ``concat(P v)
  o``. ``"mamba"`` — the state-space mixer on ``u`` [T, hidden]:
  ``[z | xBC | dt_raw] = u in_proj`` (d_inner | d_inner + 2 groups x
  d_state | heads); ``c_t = silu(conv_b + sum_j conv_w[:, 0, j] *
  xBC_{t-(K-1)+j})`` (depthwise over the channels, causal, zeros before
  the start: the K-1 rows before ``t`` are the CONV TAIL a served lane
  keeps); ``[x | B | C] = c`` (heads x d_head | groups x d_state twice: a
  group's B and C are shared by its heads); ``dt = softplus(dt_raw +
  dt_bias)``, ``A = -exp(A_log)`` per head; per head in float32 the
  recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t`` (``S``
  [d_head, d_state]: the STATE a served lane keeps), ``y_t = S_t C_t + D
  x_t``; ``y = N(y * silu(z); gate_norm)`` per group of channels (gate
  first, then the norm); out ``y out_proj``.

Over more than one token the recurrence runs in its CHUNKED form
(:func:`ssm_scan`, chunks of ``mamba_chunk_size``): inside a chunk the
masked quadratic product ``sum_{s<=t} exp(sum_{s<r<=t} dt_r A) (C_t.B_s)
dt_s x_s``, between chunks the carried state. A position whose ``dt`` is
set to 0 is the identity on the state (``exp(0) = 1``, ``0 x (outer) B =
0``): how pads, and a verify round's rejected drafts, are kept out of it.

Served through :class:`paddle_tpu.serving.ServingEngine` (the model hands
it its family, ``serving/families/hybrid_ssm.py``: paged K/V for the
attention layers beside a state and a conv tail per LANE);
``models.generation.generate`` raises for it: its contiguous K/V cache has
nowhere to keep a recurrent state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.layers import Layer
from ..ops.dispatch import apply
from .generation import _rms

__all__ = ["HybridSSMConfig", "HybridSSMForCausalLM"]

SSM, ATTENTION = "mamba", "attention"  # the published layer_types' words
SHARED_LEAVES = ("ln_in", "ln_post", "gate_up", "down")
F32 = jnp.float32


class HybridSSMConfig:
    """Key names follow the published ``config.json`` of the family. What
    that file states as flags is what this model IS and takes no
    argument: no position embedding (``position_embedding_type``
    ``"nope"``), a tied head, RMSNorm, no projection bias, a convolution
    bias, no routed experts (``shared_intermediate_size`` is the one
    MLP's width)."""

    def __init__(self, vocab_size=1024, hidden_size=128,
                 shared_intermediate_size=256, num_hidden_layers=4,
                 layer_types=None, num_attention_heads=4,
                 num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=32,
                 mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4,
                 mamba_chunk_size=256, attention_multiplier=None,
                 embedding_multiplier=1.0, residual_multiplier=1.0,
                 logits_scaling=1.0, max_position_embeddings=4096,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 dtype="float32"):
        if layer_types is None:  # one attention layer closes the stack
            layer_types = [SSM] * (num_hidden_layers - 1) + [ATTENTION]
        layer_types = tuple(layer_types)
        if len(layer_types) != num_hidden_layers \
                or set(layer_types) - {SSM, ATTENTION}:
            raise ValueError(
                f"layer_types names each of the {num_hidden_layers} "
                f"layers '{SSM}' or '{ATTENTION}', got {layer_types}")
        if hidden_size % num_attention_heads \
                or num_attention_heads % num_key_value_heads \
                or mamba_n_heads % mamba_n_groups:
            raise ValueError("heads must divide the hidden size, KV heads "
                             "the heads, groups the state-space heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.shared_intermediate_size = shared_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = layer_types
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_n_groups = mamba_n_groups
        self.mamba_d_conv = mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size
        self.attention_multiplier = float(
            attention_multiplier if attention_multiplier is not None
            else self.head_dim ** -0.5)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = float(rms_norm_eps)
        # std of every matrix's initial values (0: born zero at no cost)
        self.initializer_range = float(initializer_range)
        self.dtype = dtype

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        """Channels the convolution runs over: ``[x | B | C]``."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def static(self):
        """The hashable view the compiled programs are keyed on."""
        return _Static(self)


class _Static:
    """Value-hashable static view of the fields a traced layer reads (a
    config object hashes by identity; see ``generation._GenCfg``)."""

    __slots__ = ("hidden_size", "layer_types", "num_attention_heads",
                 "num_key_value_heads", "head_dim", "mamba_n_heads",
                 "mamba_d_head", "mamba_d_state", "mamba_n_groups",
                 "mamba_d_conv", "mamba_chunk_size", "d_inner", "conv_dim",
                 "attention_multiplier", "embedding_multiplier",
                 "residual_multiplier", "logits_scaling", "rms_norm_eps",
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

def ssm_project(u, lp, cfg):
    """``u`` [b, T, hidden] normed -> (gate z [b, T, d_inner], conv input
    xBC [b, T, conv_dim], dt_raw [b, T, heads])."""
    with jax.named_scope("ssm/in_proj"):
        p = u @ lp["in_proj"]
        d, c = cfg.d_inner, cfg.conv_dim
        return p[..., :d], p[..., d:d + c], p[..., d + c:]


def ssm_conv(window, lp, cfg):
    """The depthwise causal convolution and its silu over ``window`` [b,
    K-1 + T, conv_dim] — the K-1 rows before the first position (the conv
    tail; zeros at a sequence's start), then the T positions' ``xBC``.
    Returns ``c`` [b, T, conv_dim] in the window's dtype; sums in
    float32."""
    K = cfg.mamba_d_conv
    T = window.shape[1] - (K - 1)
    with jax.named_scope("ssm/conv"):
        w = lp["conv_w"].astype(F32)                       # [C, 1, K]
        acc = lp["conv_b"].astype(F32)
        for j in range(K):
            acc = acc + w[:, 0, j] * window[:, j:j + T].astype(F32)
        return jax.nn.silu(acc).astype(window.dtype)


def ssm_inputs(c, dt_raw, lp, cfg):
    """From the convolved ``c`` [b, T, conv_dim] and ``dt_raw`` [b, T,
    heads]: (x [b, T, H, P], B and C [b, T, G, N], all float32; dt [b, T,
    H] = softplus(dt_raw + dt_bias); A [H] = -exp(A_log))."""
    b, T, _ = c.shape
    H, P = cfg.mamba_n_heads, cfg.mamba_d_head
    G, N = cfg.mamba_n_groups, cfg.mamba_d_state
    with jax.named_scope("ssm/inputs"):
        c = c.astype(F32)
        x = c[..., :H * P].reshape(b, T, H, P)
        B = c[..., H * P:H * P + G * N].reshape(b, T, G, N)
        C = c[..., H * P + G * N:].reshape(b, T, G, N)
        dt = jax.nn.softplus(dt_raw.astype(F32)
                             + lp["dt_bias"].astype(F32))
        return x, B, C, dt, -jnp.exp(lp["A_log"].astype(F32))


def ssm_step(S, x, B, dt, A):
    """One position of the recurrence for every row: ``S`` [b, H, P, N]
    -> ``exp(dt A) S + dt x (outer) B``; x [b, H, P], B [b, G, N], dt [b,
    H]. ``dt`` 0 leaves ``S`` as it is, bit for bit."""
    b, H, P, N = S.shape
    G = B.shape[1]
    Bh = jnp.broadcast_to(B[:, :, None], (b, G, H // G, N)).reshape(
        b, H, 1, N)
    return S * jnp.exp(dt * A)[:, :, None, None] \
        + (dt[:, :, None] * x)[..., None] * Bh


def ssm_read(S, C):
    """``y = S C`` per head: S [b, H, P, N], C [b, G, N] -> [b, H, P]."""
    b, H, P, N = S.shape
    G = C.shape[1]
    return jnp.einsum("bgrpn,bgn->bgrp", S.reshape(b, G, H // G, P, N),
                      C).reshape(b, H, P)


def ssm_scan(x, B, C, dt, A, S0, chunk, slab=False):
    """The recurrence over T positions in its chunked form, float32. x
    [b, T, H, P], B / C [b, T, G, N], dt [b, T, H], A [H], S0 [b, H, P,
    N] the state before the first position. Returns (y [b, T, H, P] with
    ``y_t = S_t C_t``, the ``D x`` term NOT added; S_T). Inside a chunk
    the masked quadratic product, between chunks the carried state; a
    length that is no multiple of the chunk is padded with ``dt`` 0.
    ``slab``: ``S0`` and ``S_T`` in the serving kernel's layout [b, G, N,
    H/G x P] (``ops/pallas/ssm_state.py``), in which the state's two
    products are plain matrix products — the same sums, with no
    transpose of the state in or out."""
    b, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    R = H // G
    Q = min(chunk, T)
    nc = -(-T // Q)
    pad = nc * Q - T
    if pad:
        x, B, C, dt = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                               * (a.ndim - 2)) for a in (x, B, C, dt))
    xs = (x * dt[..., None]).reshape(b, nc, Q, G, R, P)
    Bc, Cc = B.reshape(b, nc, Q, G, N), C.reshape(b, nc, Q, G, N)
    cum = jnp.cumsum((dt * A).reshape(b, nc, Q, H), axis=2)
    # inside a chunk: position t reads s <= t decayed by exp(cum_t - cum_s)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]     # [b,nc,t,s,H]
    lower = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf)).reshape(
        b, nc, Q, Q, G, R)
    cb = jnp.einsum("bctgn,bcsgn->bctsg", Cc, Bc)
    y = jnp.einsum("bctsgr,bcsgrp->bctgrp", decay * cb[..., None], xs)
    # what each chunk adds to the state at its end, and the carry
    to_end = jnp.exp(cum[:, :, -1:, :] - cum).reshape(b, nc, Q, G, R)
    grow = xs * to_end[..., None]
    if slab:
        add = jnp.einsum("bcsgn,bcsgm->bcgnm", Bc,
                         grow.reshape(b, nc, Q, G, R * P))
    else:
        add = jnp.einsum("bcsgrp,bcsgn->bcgrpn", grow, Bc)
    whole = jnp.exp(cum[:, :, -1, :]).reshape(b, nc, G, R)
    if slab:  # a head's number over its channels, as the state has them
        whole = jnp.repeat(whole, P, axis=-1)

    def carry(S, inp):
        d, a = inp
        return S * (d[..., None, :] if slab else d[..., None, None]) + a, S

    S_T, starts = jax.lax.scan(
        carry, S0 if slab else S0.reshape(b, G, R, P, N),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(add, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)
    if slab:
        from_start = jnp.einsum("bctgn,bcgnm->bctgm", Cc, starts).reshape(
            b, nc, Q, G, R, P)
    else:
        from_start = jnp.einsum("bctgn,bcgrpn->bctgrp", Cc, starts)
    y = y + from_start * jnp.exp(cum).reshape(b, nc, Q, G, R)[..., None]
    return (y.reshape(b, nc * Q, H, P)[:, :T],
            S_T if slab else S_T.reshape(b, H, P, N))


def ssm_gate_out(y, z, lp, cfg):
    """``N(y * silu(z); gate_norm) out_proj``: y [b, T, H, P] float32, z
    [b, T, d_inner]; the norm runs per group of channels, in float32."""
    b, T = z.shape[:2]
    G = cfg.mamba_n_groups
    with jax.named_scope("ssm/gate_norm"):
        g = (y.reshape(b, T, -1) * jax.nn.silu(z.astype(F32))).reshape(
            b, T, G, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + cfg.rms_norm_eps)
        g = g.reshape(b, T, -1).astype(z.dtype) * lp["gate_norm"]
    with jax.named_scope("ssm/out_proj"):
        return g @ lp["out_proj"]


def ssm_mix(u, lp, cfg):
    """The state-space mixer over whole sequences ``u`` [b, T, hidden]
    (normed) from a zero state and a zero conv tail."""
    z, xBC, dt_raw = ssm_project(u, lp, cfg)
    b = u.shape[0]
    with jax.named_scope("ssm/conv"):
        window = jnp.pad(xBC, ((0, 0), (cfg.mamba_d_conv - 1, 0), (0, 0)))
    x, B, C, dt, A = ssm_inputs(ssm_conv(window, lp, cfg), dt_raw, lp, cfg)
    with jax.named_scope("ssm/state_update"):
        S0 = jnp.zeros((b, cfg.mamba_n_heads, cfg.mamba_d_head,
                        cfg.mamba_d_state), F32)
        y, _ = ssm_scan(x, B, C, dt, A, S0, cfg.mamba_chunk_size)
        y = y + lp["D"].astype(F32)[:, None] * x
    return ssm_gate_out(y, z, lp, cfg)


def attention_qkv(u, lp, cfg):
    """(q [b, T, nh, d], k and v [b, T, nkv, d]) — no rotary embedding."""
    b, T, _ = u.shape
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    with jax.named_scope("attn/qkv"):
        q, k, v = jnp.split(u @ lp["qkv"], [nh * d, (nh + nkv) * d],
                            axis=-1)
        return (q.reshape(b, T, nh, d), k.reshape(b, T, nkv, d),
                v.reshape(b, T, nkv, d))


def attention_mix(u, lp, cfg):
    """Causal grouped-query attention over whole sequences, scores times
    ``attention_multiplier``, float32 softmax."""
    b, T, _ = u.shape
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    q, k, v = attention_qkv(u, lp, cfg)
    with jax.named_scope("attn/rows"):
        qg = q.reshape(b, T, nkv, nh // nkv, d).astype(F32)
        s = jnp.einsum("btkgd,bskd->btkgs", qg, k.astype(F32)) \
            * cfg.attention_multiplier
        vis = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        p = jax.nn.softmax(
            jnp.where(vis[None, :, None, None, :], s, -1e30), -1)
        out = jnp.einsum("btkgs,bskd->btkgd", p, v.astype(F32))
    with jax.named_scope("attn/out"):
        return out.reshape(b, T, nh * d).astype(u.dtype) @ lp["o"]


def mlp(u, lp):
    with jax.named_scope("mlp"):
        gate, up = jnp.split(u @ lp["gate_up"], 2, axis=-1)
        return (jax.nn.silu(gate.astype(F32)).astype(u.dtype)
                * up) @ lp["down"]


def layer_on_sequence(x, lp, cfg):
    """One layer over whole sequences ``x`` [b, T, hidden] (no cache, no
    carried state); the kind of mixer told by the layer's leaves."""
    eps, rm = cfg.rms_norm_eps, cfg.residual_multiplier
    u = _rms(x, lp["ln_in"], eps)
    mix = ssm_mix(u, lp, cfg) if "in_proj" in lp else attention_mix(u, lp,
                                                                    cfg)
    x = x + (rm * mix).astype(x.dtype)
    return x + (rm * mlp(_rms(x, lp["ln_post"], eps), lp)).astype(x.dtype)


# -- the Layer graph ----------------------------------------------------------

def _published_ssm_init(H, seed):
    """The state-space leaves as the published code is born: ``A`` uniform
    in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1] (``dt_bias`` its
    inverse softplus), ``D`` 1 — slow decay, a memory of hundreds of
    tokens."""
    rng = np.random.default_rng([0x55D, seed])
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), H))
    return {"A_log": np.log(rng.uniform(1.0, 16.0, H)),
            "dt_bias": dt + np.log(-np.expm1(-dt)), "D": np.ones(H)}


class HybridSSMDecoderLayer(Layer):
    """``kind`` is ``"mamba"`` or ``"attention"``. Its parameters by leaf
    name (``leaves()``) are what the layer functions take."""

    def __init__(self, c: HybridSSMConfig, kind: str, index: int = 0):
        super().__init__(dtype=c.dtype)  # parameters are born in it
        h, f = c.hidden_size, c.shared_intermediate_size
        normal = I.Normal(std=c.initializer_range)
        one, zero = I.Constant(1.0), I.Constant(0.0)
        H = c.mamba_n_heads
        if kind == SSM:
            born = _published_ssm_init(H, index)
            mixer = [
                ("in_proj", (h, c.d_inner + c.conv_dim + H), normal),
                ("conv_w", (c.conv_dim, 1, c.mamba_d_conv), normal),
                ("conv_b", (c.conv_dim,), zero),
                ("dt_bias", (H,), I.Assign(born["dt_bias"])),
                ("A_log", (H,), I.Assign(born["A_log"])),
                ("D", (H,), I.Assign(born["D"])),
                ("gate_norm", (c.d_inner,), one),
                ("out_proj", (c.d_inner, h), normal)]
        else:
            d = c.head_dim
            mixer = [("qkv", (h, (c.num_attention_heads
                                  + 2 * c.num_key_value_heads) * d), normal),
                     ("o", (c.num_attention_heads * d, h), normal)]
        self.kind = kind
        self._names = tuple(n for n, _, _ in mixer) + SHARED_LEAVES
        for name, shape, init in mixer + [
                ("ln_in", (h,), one), ("ln_post", (h,), one),
                ("gate_up", (h, 2 * f), normal), ("down", (f, h), normal)]:
            setattr(self, name, self.create_parameter(
                list(shape), default_initializer=init))
        self._static = c.static()

    def leaves(self) -> dict:
        return {n: getattr(self, n) for n in self._names}

    def forward(self, x):
        leaves = self.leaves()
        names, cfg = tuple(leaves), self._static

        def kernel(xa, *ws):
            return layer_on_sequence(xa, dict(zip(names, ws)), cfg)

        return apply("hybrid_ssm_layer", kernel, (x, *leaves.values()))


class HybridSSMForCausalLM(Layer):
    """``forward(ids)`` gives logits [b, s, vocab]; with ``labels`` (same
    shape, already shifted, -100 ignored) the mean cross-entropy."""

    def __init__(self, config: HybridSSMConfig):
        super().__init__(dtype=config.dtype)
        c = self.config = config
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size],
            default_initializer=I.Normal(std=c.initializer_range))
        self.layers = []
        for i, kind in enumerate(c.layer_types):
            blk = HybridSSMDecoderLayer(c, kind, i)
            self.add_sublayer(f"layers.{i}", blk)
            self.layers.append(blk)
        self.norm = self.create_parameter(
            [c.hidden_size], default_initializer=I.Constant(1.0))

    def forward(self, input_ids, labels=None):
        c = self.config
        x = (F.embedding(input_ids, self.embed)
             * c.embedding_multiplier).astype(c.dtype)
        for blk in self.layers:
            x = blk(x)
        logits = apply(
            "hybrid_ssm_head",
            lambda xa, n, e: (_rms(xa, n, c.rms_norm_eps) @ e.T)
            / c.logits_scaling, (x, self.norm, self.embed))
        if labels is None:
            return logits
        from .latent_moe import _token_loss

        return _token_loss(logits, labels)

    # -- serving ---------------------------------------------------------------

    serving_family_name = "hybrid_ssm"

    def serving_family(self, serving_config):
        """What :class:`paddle_tpu.serving.ServingEngine` asks a model
        for: its caches, its collected parameters, its step programs."""
        from ..serving.families.hybrid_ssm import HybridSSMFamily

        return HybridSSMFamily(self, serving_config)

    def generate(self, *args, **kwargs):
        from .generation import generate as _generate

        return _generate(self, *args, **kwargs)
