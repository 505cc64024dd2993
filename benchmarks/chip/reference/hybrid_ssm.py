"""Plain reference for a hybrid state-space / attention decoder
(granite-4.0-h-micro's ``config.json``, ``model_type`` ``granitemoehybrid``
with no routed experts; the layer equations are ISSUE 31's, written out in
``paddle_tpu/models/hybrid_ssm.py``'s docstring too): the forward pass in
``jax.numpy``, float32, matmul precision "highest". No kernels, no cache,
no chunking, no batching, nothing imported from the program.

The state-space mixer is the RECURRENCE itself, one position at a time
under ``lax.scan``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``,
``y_t = S_t C_t + D x_t`` per head, so the program's chunked form (a
masked quadratic product inside a chunk, a carried state between chunks,
a state pool indexed by lane across calls) is checked against what it has
to equal. Attention has no position embedding and multiplies its scores
by ``attention_multiplier`` (1/64 at the published sizes, not
1/sqrt(64)); it runs in blocks of query rows so a 3584-token sequence
fits. The embedding multiplier is applied in layer 0 (the harness embeds
as ``top["embed"][ids]``), the residual multiplier on both branches of
every layer, the logits are divided by ``logits_scaling`` and the head is
the embedding transposed.

Departures from the published code, all exact re-arrangements or stated
assumptions (the configuration file lists the latter under ``assumed``):
W is ``[in, out]``, applied as ``x @ W``; q, k, v arrive as one matrix
``qkv`` (q first) and gate, up as one ``gate_up`` (gate first); the
``in_proj`` output splits gate | conv channels | step sizes; the conv
weight keeps the checkpoint's ``[channels, 1, kernel]`` shape; the
recurrent state is float32 (as the published code computes it); no clamp
on ``dt`` (``time_step_limit`` (0, inf)).

``quant`` is the CONTROL: every linear layer's two operands
fake-quantised to an 8-bit float (e4m3, per-tensor scale) — the nearest
precision below the configuration's bfloat16: ``in_proj``, ``out_proj``,
``qkv``, ``o``, ``gate_up``, ``down`` and the head. The depthwise
convolution (4 taps a channel), the recurrence and the attention products
stay float32, as in the other references. It has to fail the comparison
that the program passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 512  # query rows a block of attention


def fp8(x):
    """Fake-quantise to an 8-bit float (4 exponent bits, 3 mantissa bits,
    largest finite value 240) with a per-tensor scale.
    ``reduce_precision`` and not a cast pair: the TPU compiler may elide
    float32 -> float8 -> float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


def _mm(x, w, quant):
    if quant:
        x, w = fp8(x), fp8(w)
    return jnp.matmul(x, w, precision="highest")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def mlp(u, lw, quant):
    gate, up = jnp.split(_mm(u, lw["gate_up"], quant), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, lw["down"], quant)


def attention(u, lw, m, quant):
    """Causal grouped-query attention of one sequence ``u`` [T, hidden]
    (normed): no position embedding, scores x ``attention_multiplier``."""
    T = u.shape[0]
    nh, nkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // nh
    q, k, v = jnp.split(_mm(u, lw["qkv"], quant),
                        [nh * d, (nh + nkv) * d], axis=-1)
    q = q.reshape(T, nkv, nh // nkv, d)
    k, v = k.reshape(T, nkv, d), v.reshape(T, nkv, d)

    def block(start):
        r = jnp.minimum(start + jnp.arange(ROWS), T - 1)
        s = jnp.einsum("rkgd,tkd->kgrt", q[r], k, precision="highest") \
            * m["attention_multiplier"]
        s = jnp.where(jnp.arange(T)[None, None, None, :]
                      <= r[None, None, :, None], s, -jnp.inf)
        return jnp.einsum("kgrt,tkd->rkgd", jax.nn.softmax(s, -1), v,
                          precision="highest")

    n_blocks = -(-T // ROWS)
    out = jax.lax.map(block, jnp.arange(n_blocks) * ROWS)
    return _mm(out.reshape(n_blocks * ROWS, nh * d)[:T], lw["o"], quant)


def state_space(u, lw, m, quant):
    """The state-space mixer of one sequence ``u`` [T, hidden] (normed),
    from a zero state: projection, depthwise causal convolution + silu,
    the recurrence position by position, gated RMSNorm, projection."""
    T = u.shape[0]
    H, P, N, G = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                  m["mamba_n_groups"])
    K, d_inner = m["mamba_d_conv"], H * P
    conv_dim = d_inner + 2 * G * N
    p = _mm(u, lw["in_proj"], quant)
    z, xBC, dt_raw = (p[:, :d_inner], p[:, d_inner:d_inner + conv_dim],
                      p[:, d_inner + conv_dim:])
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_dim), F32), xBC])
    c = lw["conv_b"] + sum(lw["conv_w"][:, 0, j] * padded[j:j + T]
                           for j in range(K))
    c = jax.nn.silu(c)
    x = c[:, :d_inner].reshape(T, H, P)
    B = jnp.repeat(c[:, d_inner:d_inner + G * N].reshape(T, G, N), H // G,
                   axis=1)                                    # [T, H, N]
    C = jnp.repeat(c[:, d_inner + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt_raw + lw["dt_bias"])              # [T, H]
    A = -jnp.exp(lw["A_log"])

    def step(S, inp):
        x_t, B_t, C_t, dt_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        return S, jnp.sum(S * C_t[:, None, :], -1) + lw["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, B, C, dt))
    g = (y.reshape(T, d_inner) * jax.nn.silu(z)).reshape(T, G, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + m["rms_norm_eps"])
    return _mm(g.reshape(T, d_inner) * lw["gate_norm"], lw["out_proj"],
               quant)


def layer_forward(x, lw, *, li, m, quant):
    """Layer ``li`` on one sequence ``x`` [T, hidden]: a state-space
    layer has ``in_proj``, an attention layer ``qkv``. ``li`` arrives
    traced; layer 0 takes the embedding and multiplies it first."""
    x = jnp.where(li == 0, x * m["embedding_multiplier"], x)
    eps, rm = m["rms_norm_eps"], m["residual_multiplier"]
    u = rms_norm(x, lw["ln_in"], eps)
    mix = state_space(u, lw, m, quant) if "in_proj" in lw \
        else attention(u, lw, m, quant)
    x = x + rm * mix
    return x + rm * mlp(rms_norm(x, lw["ln_post"], eps), lw, quant)


def head_logits(x, top, *, m, quant):
    """Logits [K, vocab] of rows ``x`` [K, hidden] of the stack's output:
    the final norm, the embedding transposed, over ``logits_scaling``."""
    return _mm(rms_norm(x, top["norm"], m["rms_norm_eps"]), top["embed"].T,
               quant) / m["logits_scaling"]


def forward(params, ids, m, quant=False):
    """Logits [T, vocab] of one sequence (the CPU tests' whole-model
    form). ``params``: top-level leaves and ``layers`` (a list of leaf
    dicts), as ``chiplib.modelbuild.reference_params`` builds them."""
    x = params["embed"][ids]
    for li, lw in enumerate(params["layers"]):
        x = layer_forward(x, lw, li=li, m=m, quant=quant)
    return head_logits(x, params, m=m, quant=quant)
