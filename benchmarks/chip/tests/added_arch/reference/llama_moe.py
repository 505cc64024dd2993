"""Plain reference for ``models/llama.py`` with GShard expert layers:
the dense reference's attention, norms, rotary embedding, AdamW and
fake-quantised matmul; the expert layer and the loss are this file's.
Training path only (``train_step``).

The expert layer as the program's ``MoELayer`` defines it, written
without capacity buffers: all tokens of the batch in row-major order,
softmax router, first and second choice, each expert's queue filled
first come first served (every first choice before any second one), a
choice past ``capacity = ceil(2 T / E x capacity_factor)`` dropped, the
two kept gates renormalised; un-gated experts ``silu(x W_in) W_out``.
The loss adds ``router_aux_loss_coef`` x the sum over layers of
E x sum_e(mean router probability x share of first choices)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chiplib import manifest

_d = manifest.Files().reference("llama_dense")


def _q(x, quant):
    return _d.fp8(x) if quant else x


def experts(x, lw, m, quant):
    """x [T, hidden]: every token of the batch. Returns (y, aux)."""
    T, E = x.shape[0], m["num_local_experts"]
    assert m["num_experts_per_tok"] == 2
    cap = math.ceil(2 * T / E * m["capacity_factor"])
    probs = jax.nn.softmax(_d._mm(x, lw["router"], quant), axis=-1)
    first = jax.nn.one_hot(jnp.argmax(probs, -1), E)
    second = jax.nn.one_hot(jnp.argmax(probs * (1 - first), -1), E)
    place1 = (jnp.cumsum(first, 0) - 1) * first
    place2 = (jnp.cumsum(second, 0) - 1 + jnp.sum(first, 0)) * second
    keep1, keep2 = first * (place1 < cap), second * (place2 < cap)
    g1, g2 = jnp.sum(probs * keep1, -1), jnp.sum(probs * keep2, -1)
    total = jnp.maximum(g1 + g2, 1e-9)
    weight = (g1 / total)[:, None] * keep1 + (g2 / total)[:, None] * keep2
    mid = jax.nn.silu(jnp.einsum("th,ehf->etf", _q(x, quant),
                                 _q(lw["w_in"], quant), precision="highest"))
    out = jnp.einsum("etf,efh->eth", _q(mid, quant), _q(lw["w_out"], quant),
                     precision="highest")
    aux = jnp.sum(jnp.mean(probs, 0) * jnp.mean(first, 0)) * E
    return jnp.einsum("te,eth->th", weight, out, precision="highest"), aux


def attend(x, lw, m, quant):
    """The attention half of a layer on one row x [T, hidden]."""
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _d.rms_norm(x, lw["ln1"], m["rms_norm_eps"])
    q, k, v = jnp.split(_d._mm(h, lw["qkv"], quant),
                        [nh * d, (nh + nkv) * d], axis=-1)
    q = _d.rope(q.reshape(T, nh, d), pos, m["rope_theta"])
    k = _d.rope(k.reshape(T, nkv, d), pos, m["rope_theta"])
    return x + _d._mm(_d.attention(q, k, v.reshape(T, nkv, d)), lw["o"],
                      quant)


def loss_fn(params, ids, labels, m, quant=False):
    B, T = ids.shape
    x = params["embed"][ids]                                  # [B, T, h]
    aux = 0.0
    for lw in params["layers"]:
        x = jnp.stack([attend(x[r], lw, m, quant) for r in range(B)])
        h = _d.rms_norm(x, lw["ln2"], m["rms_norm_eps"])
        y, a = experts(h.reshape(B * T, -1), lw, m, quant)
        x, aux = x + y.reshape(x.shape), aux + a
    x = _d.rms_norm(x, params["norm"], m["rms_norm_eps"])
    logits = _d._mm(x, params["lm_head"], quant)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]
    return jnp.mean(nll) + m["router_aux_loss_coef"] * aux


def train_step(params, mom, var, ids, labels, step, *, m, o, quant=False):
    return _d.train_step(params, mom, var, ids, labels, step, m=m, o=o,
                         quant=quant, loss=loss_fn)
