"""A dropless sparse-expert layer that knows which experts it holds.

The expert-parallel cut of a large mixture-of-experts layer: the router
keeps its published width (``router_experts``, e.g. 256) and its experts
per token (e.g. 8); THIS chip holds ``n_held`` of them, from
``first_held`` on. It routes every token over all the experts, computes
the part of the result that its own experts give, and leaves out what
the absent ones would add — what an expert-parallel rank computes before
its exchange. On one chip it runs without that exchange; nothing here
stands in for the absent chips. Where NONE is absent (``n_held ==
router_experts`` from ``first_held`` 0: a chip that holds the whole
layer, ``models/conv_moe.py``) nothing is left out and the result is the
layer's: every assignment is held, so the held-mask is all true and the
sort puts no assignment past the last group.

Against the older :class:`.moe_layer.MoELayer` (GShard top-1/top-2,
dense ``[T, E, C]`` dispatch, capacity dropping, un-gated experts): no
capacity and no dropped token — assignments are sorted by expert and the
experts run as grouped matrix products over ragged groups (the megablox
``gmm`` Pallas kernel: one ``[rows, in] x [experts, in, out]`` product
whose row groups are the experts' token lists; chosen over
``jax.lax.ragged_dot`` and over a masked dense product of every held
expert by measurement on the chip, PERF.md section 6, PR 27); sigmoid
scores, top-k
normalised over ALL chosen experts (held or not) times a scaling factor;
SwiGLU experts; a shared expert every token passes through, or none
(``n_shared=0``: no shared leaves, no ``moe/shared`` work in the program).

Everything is a pure function of arrays, so the serving step programs
(``serving/families/latent_moe.py``) and the model's ``forward`` (one
recorded op, differentiated by ``jax.vjp``) run the same code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from .....framework.device import on_tpu
from .....nn import initializer as I
from .....nn.layer.layers import Layer
from .....ops.dispatch import apply

__all__ = ["HeldExperts", "route_top_k", "held_experts", "swiglu",
           "sparse_expert_block"]


def swiglu(x, w_gate_up, w_down):
    """SwiGLU with gate and up fused ``[in, 2 * width]`` (gate first)."""
    gate, up = jnp.split(x @ w_gate_up, 2, axis=-1)
    return (jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
            * up) @ w_down


def route_top_k(u, w_router, top_k, scaling, bias=None, eps=1e-20):
    """Sigmoid-scored top-k over ALL the router's experts, in float32 as
    the published gate computes it: ``s = sigmoid(float32(u) W_r)``, the
    ``top_k`` largest, ``g = s_top / (sum(s_top) + eps) * scaling``
    (``eps``: the published gate's own, 1e-20 in most, 1e-6 in some).
    With a per-expert selection ``bias`` [E] the ``top_k`` largest of ``s
    + bias`` are chosen and the gates are still their ``s``: the bias
    chooses, it does not weigh.
    Returns (expert ids ``[T, k]`` int32, gates ``[T, k]`` float32)."""
    with jax.named_scope("moe/route"):
        s = jax.nn.sigmoid(jnp.dot(
            u.astype(jnp.float32), w_router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        if bias is None:
            top_s, top_i = jax.lax.top_k(s, top_k)
        else:
            _, top_i = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
            top_s = jnp.take_along_axis(s, top_i, axis=-1)
        g = top_s / (jnp.sum(top_s, -1, keepdims=True) + eps) * scaling
        return top_i.astype(jnp.int32), g


# rows, contraction and output tile of the grouped product: of the tilings
# timed at the published widths (7680 x 4096 and 2048 x 7680 a held expert,
# 256-2560 rows) the fastest at every row count (PERF.md section 6, PR 27)
_TILE_ROWS, _TILE_K, _TILE_N = 128, 1024, 1024


def grouped_matmul(rows, w, group_sizes):
    """``rows`` [m, k] against stacked ``w`` [groups, k, n]: rows
    ``sum(group_sizes[:e]) .. sum(group_sizes[:e+1])`` times ``w[e]``.
    Rows past the last group come back zero."""
    m, k = rows.shape
    # the kernel (and its transpose, in the backward pass) never writes
    # the tiles past the last group: select them away on both sides, so
    # neither the product nor the rows' gradient carries what was there
    grouped = jnp.arange(m)[:, None] < jnp.sum(group_sizes)
    rows = jnp.where(grouped, rows, 0)
    pad = -m % _TILE_ROWS
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = gmm(rows, w, group_sizes, preferred_element_type=rows.dtype,
              tiling=(_TILE_ROWS, min(_TILE_K, k),
                      min(_TILE_N, w.shape[-1])),
              interpret=not on_tpu())[:m]
    return jnp.where(grouped, out, 0)


def held_experts(u, idx, g, w_gate_up, w_down, first_held, valid=None):
    """The held experts' part of ``sum_e g_e SwiGLU_e(u)``.

    ``u`` [T, H] tokens, ``idx`` / ``g`` [T, k] the router's choice,
    ``w_gate_up`` [n_held, H, 2F] and ``w_down`` [n_held, F, H] the held
    experts ``first_held .. first_held + n_held - 1``. ``valid`` [T]
    (optional) marks real tokens: a pad's assignments are treated as not
    held, so they cost no expert rows and are not counted.

    Assignments are sorted by held expert (those to absent experts sort
    last and form no group), the tokens gathered in that order, and the
    two products run as grouped matrix products over the experts' row
    groups: no capacity, nothing dropped — one expert may take every
    row. Returns (y [T, H], counts [n_held] int32: assignments each held
    expert got in this call)."""
    T, H = u.shape
    K = idx.shape[1]
    n = w_gate_up.shape[0]
    with jax.named_scope("moe/dispatch"):
        local = idx - first_held
        held = (local >= 0) & (local < n)
        if valid is not None:
            held = held & valid[:, None]
        flat = jnp.where(held, local, n).reshape(T * K)
        order = jnp.argsort(flat, stable=True)
        counts = jnp.sum(flat[:, None] == jnp.arange(n)[None, :], axis=0,
                         dtype=jnp.int32)
        rows = u[order // K]  # [T*K, H], the held assignments first
    with jax.named_scope("moe/experts"):
        gu = grouped_matmul(rows, w_gate_up, counts)
        gate, up = jnp.split(gu, 2, axis=-1)
        act = jax.nn.silu(gate.astype(jnp.float32)).astype(u.dtype) * up
        out = grouped_matmul(act, w_down, counts)
    with jax.named_scope("moe/combine"):
        # back to token order by a gather (the inverse permutation), then
        # the gated sum over each token's k rows in float32; rows past the
        # last group belong to absent experts or pads: weight 0
        back = out[jnp.argsort(order)].reshape(T, K, H)
        y = jnp.einsum("tkh,tk->th", back.astype(jnp.float32),
                       jnp.where(held, g, 0.0))
        return y.astype(u.dtype), counts


def sparse_expert_block(u, p, *, top_k, scaling, first_held, valid=None,
                        eps=1e-20):
    """The whole expert layer on normed tokens ``u`` [T, H]: route, the
    held experts' share, plus the shared expert (whole, on every chip)
    where the layer has one. ``p``: ``router`` [H, E], ``experts_gate_up``,
    ``experts_down``, ``shared_gate_up`` and ``shared_down`` (a layer
    without a shared expert has neither leaf) and, where the router has
    one, its selection bias ``router_bias`` [E]; ``eps`` is
    ``route_top_k``'s. Returns (y, counts)."""
    idx, g = route_top_k(u, p["router"], top_k, scaling,
                         p.get("router_bias"), eps)
    y, counts = held_experts(u, idx, g, p["experts_gate_up"],
                             p["experts_down"], first_held, valid)
    if "shared_gate_up" in p:
        with jax.named_scope("moe/shared"):
            y = y + swiglu(u, p["shared_gate_up"], p["shared_down"])
    return y, counts


class HeldExperts(Layer):
    """``sparse_expert_block`` as a layer: ``router_experts`` experts are
    routed over, ``n_held`` of them (``first_held`` on) live here, stacked
    ``[n_held, in, out]``; ``n_shared`` shared experts are one SwiGLU of
    ``n_shared * width`` (``n_shared=0``: a layer without one, and without
    its two leaves). ``selection_bias`` adds the router's per-expert
    ``router_bias`` (born zero; ``route_top_k`` says what it does, and
    what ``eps`` is).
    ``forward`` returns the output; the call's per-held-expert assignment
    counts are left on ``last_counts``."""

    _NAMES = ("router", "experts_gate_up", "experts_down",
              "shared_gate_up", "shared_down")

    def __init__(self, hidden, width, router_experts, n_held, first_held=0,
                 top_k=8, n_shared=1, scaling=1.0, dtype="float32",
                 init_std=0.02, selection_bias=False, eps=1e-20):
        super().__init__(dtype=dtype)  # parameters are born in it
        if not 0 <= first_held <= router_experts - n_held:
            raise ValueError(
                f"experts {first_held}..{first_held + n_held - 1} are not "
                f"among the router's {router_experts}")
        if top_k > router_experts:
            raise ValueError(f"top_k {top_k} > {router_experts} experts")
        self.top_k, self.scaling, self.eps = top_k, float(scaling), eps
        self.first_held, self.n_held = first_held, n_held
        init = I.Normal(std=init_std)
        self.router = self.create_parameter(
            [hidden, router_experts], default_initializer=init)
        self.experts_gate_up = self.create_parameter(
            [n_held, hidden, 2 * width], default_initializer=init)
        self.experts_down = self.create_parameter(
            [n_held, width, hidden], default_initializer=init)
        names = ["router", "experts_gate_up", "experts_down"]
        if n_shared:  # else no zero-width product is left in the program
            self.shared_gate_up = self.create_parameter(
                [hidden, 2 * n_shared * width], default_initializer=init)
            self.shared_down = self.create_parameter(
                [n_shared * width, hidden], default_initializer=init)
            names += ["shared_gate_up", "shared_down"]
        if selection_bias:
            self.router_bias = self.create_parameter(
                [router_experts], default_initializer=I.Constant(0.0))
            names.append("router_bias")
        self._NAMES = tuple(names)
        self.last_counts = None

    def arrays(self):
        return {k: getattr(self, k)._data for k in self._NAMES}

    def forward(self, x):
        shape = x.shape

        def kernel(xa, *ws):
            y, counts = sparse_expert_block(
                xa.reshape(-1, shape[-1]), dict(zip(self._NAMES, ws)),
                top_k=self.top_k, scaling=self.scaling,
                first_held=self.first_held, eps=self.eps)
            return y.reshape(xa.shape), counts

        y, counts = apply("held_experts", kernel,
                          (x, *(getattr(self, k) for k in self._NAMES)))
        self.last_counts = counts
        return y
