"""Plain reference for the dense decoder with a sliding window: query
row r sees keys in (r - window, r]. Serving path only (``layer_forward``,
``head_logits``): a reference needs the functions of the paths its cells
use. Norm, rotary embedding and the fake-quantised matmul are the dense
reference's; the attention and its mask are this file's own."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chiplib import manifest

_d = manifest.Files().reference("llama_dense")
head_logits = _d.head_logits


def attention(q, k, v, window):
    """q [T, nh, d]; k, v [T, nkv, d]; every head's [T, T] scores at once
    (a test's size)."""
    T, nh, d = q.shape
    g = nh // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k, precision="highest") / jnp.sqrt(
        jnp.float32(d))
    r, c = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (c <= r) & (c > r - window)
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v,
                      precision="highest").reshape(T, nh * d)


def layer_forward(x, lw, *, li, m, quant=False):
    del li  # one window for every layer
    nh, nkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _d.rms_norm(x, lw["ln1"], m["rms_norm_eps"])
    q, k, v = jnp.split(_d._mm(h, lw["qkv"], quant),
                        [nh * d, (nh + nkv) * d], axis=-1)
    q = _d.rope(q.reshape(T, nh, d), pos, m["rope_theta"])
    k = _d.rope(k.reshape(T, nkv, d), pos, m["rope_theta"])
    a = attention(q, k, v.reshape(T, nkv, d), m["sliding_window"])
    x = x + _d._mm(a, lw["o"], quant)
    h = _d.rms_norm(x, lw["ln2"], m["rms_norm_eps"])
    gate, up = jnp.split(_d._mm(h, lw["gate_up"], quant), 2, axis=-1)
    return x + _d._mm(jax.nn.silu(gate) * up, lw["down"], quant)
