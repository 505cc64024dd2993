"""Plain reference for a dense Llama-family decoder (Mistral-7B-v0.3):
forward pass, next-token loss, gradients and AdamW in ``jax.numpy``,
float32, matmul precision "highest". No kernels, no cache, no batching
beyond a loop over rows, nothing imported from the program.

It follows the published description (pre-norm residual blocks, RMSNorm,
rotary embedding in the half-split pairing of the HF implementation,
grouped-query causal attention, SwiGLU, untied output head). Departures,
both exact re-arrangements: q, k, v arrive as one matrix ``qkv`` (q, then
k, then v along the output axis) and gate, up as one ``gate_up`` (gate
first), because that is how the benchmark makes the weights; W is
[in, out], applied as ``x @ W``.

``quant`` swaps every linear layer's two operands for a fake-quantised
copy (per-tensor scaled 8-bit float, e4m3, straight-through gradient). That is
the CONTROL: the nearest precision below the configuration's bfloat16.
It has to fail the comparison that the program passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def fp8(x):
    """Fake-quantise to an 8-bit float (4 exponent bits, 3 mantissa bits,
    largest finite value 240) with a per-tensor scale; the gradient
    passes straight through. ``reduce_precision`` and not a cast pair:
    the TPU compiler may elide float32 -> float8 -> float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    q = jax.lax.reduce_precision(x / s, exponent_bits=4,
                                 mantissa_bits=3) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    if quant:
        x, w = fp8(x), fp8(w)
    return jnp.matmul(x, w, precision="highest")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [T, H, d]; pairs (x_i, x_{i+d/2}) rotated by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped-query attention, one sequence. q [T, nh, d];
    k, v [T, nkv, d]. One kv head (and its group of q heads) at a time,
    scores recomputed in the backward pass, so a 4096-token row needs
    [group, T, T] floats and not [nh, T, T]."""
    T, nh, d = q.shape
    nkv = k.shape[1]
    g = nh // nkv
    qg = q.reshape(T, nkv, g, d).transpose(1, 2, 0, 3)  # [nkv, g, T, d]
    kg = k.transpose(1, 0, 2)                           # [nkv, T, d]
    vg = v.transpose(1, 0, 2)
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one(args):
        qh, kh, vh = args
        s = jnp.einsum("gtd,sd->gts", qh, kh, precision="highest") \
            / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gts,sd->gtd", p, vh, precision="highest")

    out = jax.lax.map(one, (qg, kg, vg))                # [nkv, g, T, d]
    return out.transpose(2, 0, 1, 3).reshape(T, nh * d)


def block(x, lw, m, quant=False):
    """One decoder layer on one sequence x [T, hidden]."""
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    T = x.shape[0]
    pos = jnp.arange(T)
    h = rms_norm(x, lw["ln1"], m["rms_norm_eps"])
    qkv = _mm(h, lw["qkv"], quant)
    q, k, v = jnp.split(qkv, [nh * d, (nh + nkv) * d], axis=-1)
    q = rope(q.reshape(T, nh, d), pos, m["rope_theta"])
    k = rope(k.reshape(T, nkv, d), pos, m["rope_theta"])
    a = attention(q, k, v.reshape(T, nkv, d))
    x = x + _mm(a, lw["o"], quant)
    h = rms_norm(x, lw["ln2"], m["rms_norm_eps"])
    gate, up = jnp.split(_mm(h, lw["gate_up"], quant), 2, axis=-1)
    return x + _mm(jax.nn.silu(gate) * up, lw["down"], quant)


def hidden_states(params, ids, m, quant=False):
    """Final-norm hidden states [T, hidden] of one sequence ids [T]."""
    x = params["embed"][ids]
    blk = jax.checkpoint(functools.partial(block, m=m, quant=quant))
    for lw in params["layers"]:
        x = blk(x, lw)
    return rms_norm(x, params["norm"], m["rms_norm_eps"])


def row_loss(params, ids, labels, m, quant=False):
    """Sum of next-token cross-entropies over one row."""
    @jax.checkpoint
    def head(x, w):
        logits = _mm(x, w, quant)
        return jnp.sum(jax.nn.logsumexp(logits, -1)
                       - jnp.take_along_axis(logits, labels[:, None],
                                             -1)[:, 0])

    return head(hidden_states(params, ids, m, quant), params["lm_head"])


def loss_fn(params, ids, labels, m, quant=False):
    """Mean cross-entropy over all tokens of ids/labels [B, T]."""
    total = 0.0
    for r in range(ids.shape[0]):
        total = total + row_loss(params, ids[r], labels[r], m, quant)
    return total / (ids.shape[0] * ids.shape[1])


def adamw(p, g, mom, var, step, o):
    """Decoupled weight decay, then Adam with bias correction."""
    b1, b2 = o["beta1"], o["beta2"]
    mom = b1 * mom + (1 - b1) * g
    var = b2 * var + (1 - b2) * g * g
    p = p * (1.0 - o["learning_rate"] * o["weight_decay"])
    p = p - o["learning_rate"] * (mom / (1 - b1 ** step)) / (
        jnp.sqrt(var / (1 - b2 ** step)) + o["epsilon"])
    return p, mom, var


def train_step(params, mom, var, ids, labels, step, *, m, o, quant=False,
               loss=None):
    """One AdamW step on ``loss`` (``loss_fn``, unless a reference that
    builds on this one brings its own). Returns (params, mom, var, loss,
    per-leaf gradient norms)."""
    loss, grads = jax.value_and_grad(loss or loss_fn)(
        params, ids, labels, m, quant)
    gnorm = jax.tree_util.tree_map(
        lambda g: jnp.sqrt(jnp.sum(g * g)), grads)
    out = jax.tree_util.tree_map(
        lambda p, g, a, b: adamw(p, g, a, b, step, o),
        params, grads, mom, var)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda p, t: t[i], params, out)
    return pick(0), pick(1), pick(2), loss, gnorm


def layer_forward(x, lw, *, li, m, quant=False):
    """Layer ``li`` on one sequence — the serving check runs the stack
    layer by layer so that only one layer's float32 weights are alive.
    ``lw`` holds the leaves the specs list for that layer; ``li`` arrives
    traced (every layer of this architecture is alike, so it is unused)."""
    del li
    return block(x, lw, m, quant)


def head_logits(x, top, *, m, quant=False):
    """Logits [K, vocab] of the rows x [K, hidden] (pre-final-norm);
    ``top`` holds the top-level leaves."""
    return _mm(rms_norm(x, top["norm"], m["rms_norm_eps"]), top["lm_head"],
               quant)
