"""Attention functional ops.

Reference parity: `paddle.nn.functional.scaled_dot_product_attention` and the
flash-attention PHI kernel (`paddle/phi/kernels/gpu/flash_attn_kernel.cu`,
external `cmake/external/flashattn.cmake`).

TPU-first design: the default implementation is plain jnp (XLA fuses it
well at short seq-len); the op name "flash_attention" is a Pallas override
point — `paddle_tpu.ops.pallas.flash_attention` registers a fused
tiled-softmax kernel for TPU via the kernel registry, exactly how the
reference swaps in the flashattn CUDA library.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...framework.device import on_tpu
from ...ops.dispatch import apply


def _sdpa_reference(q, k, v, *rest, causal=False, dropout=0.0, scale=None,
                    dropout_key=None):
    """q,k,v: [batch, seq, heads, head_dim] (paddle flash-attn layout).
    GQA/MQA: kv_heads may divide q heads — KV is repeated here (the
    Pallas kernel instead streams shared KV blocks without the repeat)."""
    hd = q.shape[-1]
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = scale if scale is not None else 1.0 / math.sqrt(hd)
    # [b, h, sq, sk]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * s
    if rest:
        mask = rest[0]
        if mask.dtype == jnp.bool_:
            # paddle attn_mask semantics: bool True = KEEP (an additive
            # 0/1 cast would be silently wrong)
            logits = jnp.where(mask, logits,
                               jnp.asarray(-1e30, logits.dtype))
        else:
            logits = logits + mask.astype(logits.dtype)
    row_valid = None
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, jnp.asarray(-1e30, logits.dtype))
        row_valid = cm.any(-1)  # rows with no visible key (sq > sk head rows)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if row_valid is not None:
        # flash-attn >= 2.1: a query row that attends to nothing outputs 0
        probs = jnp.where(row_valid[..., None], probs,
                          jnp.zeros((), probs.dtype))
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), jnp.zeros((), probs.dtype))
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0,
    is_causal=False, training=True, name=None,
):
    """Inputs [batch, seq, num_heads, head_dim] — same layout as the
    reference's flash_attn op. Routed through op name "flash_attention" so a
    Pallas kernel can take over on TPU."""
    import jax

    from ...framework import random as rng

    operands = [query, key, value]
    if attn_mask is not None:
        operands.append(attn_mask)
    p = dropout_p if training else 0.0
    has_key = p > 0.0
    if has_key:
        # the key rides as an OPERAND (raw uint32 words) so the Pallas
        # kernel can seed its in-kernel dropout mask under jit tracing;
        # the composite fallback re-wraps it into a typed key
        operands.append(jax.random.key_data(rng.next_key()))

    def default(*arrs, causal=False, dropout=0.0, has_key=False):
        dkey = None
        if has_key:
            *arrs, kd = arrs
            dkey = jax.random.wrap_key_data(kd)
        return _sdpa_reference(*arrs, causal=causal, dropout=dropout,
                               dropout_key=dkey)

    return apply(
        "flash_attention",
        default,
        tuple(operands),
        causal=is_causal,
        dropout=p,
        has_key=has_key,
    )


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, name=None):
    """Parity: paddle.nn.functional.flash_attention.flash_attention."""
    out = scaled_dot_product_attention(
        query, key, value, None, dropout, causal
    )
    if return_softmax:
        return out, None
    return out, None


def sliding_window_attention(query, key, value, window_size, name=None):
    """Mistral-style causal local attention: query row r attends keys in
    ``(r - window_size, r]``. EXCEEDS the reference (its flash_attn
    binding has no windowing in this snapshot). Runs the Pallas flash
    kernel with the band mask — fully-masked tiles skip their MXU work,
    so cost is O(seq·window) — and falls back to the banded XLA
    composite where the kernel's shape contract fails. GQA/MQA
    supported (kv heads divide q heads).

    A dedicated dispatch entry rather than a kwarg on the registered
    'flash_attention' kernel: scaled_dot_product_attention (that
    registry's consumer) has no window parameter, so threading one
    through would dead-end; the shape contract below mirrors
    flash_attention_kernel's."""
    if not isinstance(window_size, int) or window_size <= 0:
        raise ValueError(
            f"window_size must be a positive int, got {window_size!r}")
    from ...ops.pallas import autotune as _tune
    from ...ops.pallas import flash_attention as fa

    def fn(q, k, v):
        b, sq, h, d = q.shape
        sk, h_kv = k.shape[1], k.shape[2]
        scale = 1.0 / math.sqrt(d)
        bq, bk = fa._pick_block(sq), fa._pick_block(sk)
        ok_blocks = (bq == sq or bq % 8 == 0) and (bk == sk or bk % 8 == 0)
        kernel_ok = (sq >= 16 and sk >= 16 and d % 8 == 0
                     and h % h_kv == 0 and v.shape[2] == h_kv
                     and ok_blocks)
        if kernel_ok:
            interpret = not on_tpu()
            bq_t = bk_t = None
            if not interpret:  # measured block sizes transfer here too
                bq_t, bk_t = _tune.best_blocks(sq, sk, d, True)
            qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
            kt = k.transpose(0, 2, 1, 3).reshape(b * h_kv, sk, d)
            vt = v.transpose(0, 2, 1, 3).reshape(b * h_kv, sk, d)
            out = fa._flash_bhsd(qt, kt, vt, True, scale, interpret,
                                 bq_t, bk_t, window_size)
            return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
        # banded composite (bottom-right aligned like _sdpa_reference;
        # GQA repeat + exact-zero rows with no visible key)
        if h_kv != h:
            rep = h // h_kv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                            k.astype(jnp.float32)) * scale
        rows = jnp.arange(sq)[:, None] + (sk - sq)
        cols = jnp.arange(sk)[None, :]
        keep = (rows >= cols) & (cols > rows - window_size)
        logits = jnp.where(keep[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
        row_valid = keep.any(-1)  # [sq]
        out = out * row_valid[None, :, None, None]
        return out.astype(q.dtype)

    return apply("sliding_window_attention", fn, (query, key, value))


_seq_parallel_cache: dict = {}


def _seq_parallel_attention(op_name, make_fn, query, key, value, axis,
                            causal):
    """Shared wiring for the sequence-parallel attention variants: mesh
    lookup, degree-1 fallback to the single-device attention path, and a
    per-(mesh, axis, causal) cache of the built shard_map program."""
    from ...distributed import env as env_mod

    e = env_mod.ensure_env()
    if e.degree(axis) <= 1:
        return scaled_dot_product_attention(query, key, value,
                                            is_causal=causal)
    key_ = (op_name, e.mesh, axis, causal)
    fn = _seq_parallel_cache.get(key_)
    if fn is None:
        fn = make_fn(e.mesh, axis=axis, causal=causal)
        _seq_parallel_cache[key_] = fn
    return apply(op_name, fn, (query, key, value))


def ring_flash_attention(query, key, value, axis="sep", causal=True,
                         name=None):
    """Context-parallel exact attention: sequence sharded over mesh ``axis``,
    KV blocks rotating on the ICI ring (`ops/ring_attention.py`). Exceeds the
    reference (SURVEY §5.7: no ring/context parallelism in the snapshot).
    Degree-1 axes fall back to the single-device attention path."""
    from ...ops.ring_attention import make_ring_attention

    return _seq_parallel_attention("ring_flash_attention",
                                   make_ring_attention, query, key, value,
                                   axis, causal)


def ulysses_attention(query, key, value, axis="sep", causal=True,
                      name=None):
    """DeepSpeed-Ulysses sequence parallelism: two all-to-alls re-shard
    heads across ``axis`` so each device attends over the FULL sequence
    with h/n heads (`ops/ulysses_attention.py`). Exceeds the reference
    (SURVEY §2.6 lists Ulysses as absent). Complements
    :func:`ring_flash_attention`: prefer Ulysses when heads are
    plentiful, the ring at extreme sequence lengths. Degree-1 axes fall
    back to the single-device attention path."""
    from ...ops.ulysses_attention import make_ulysses_attention

    return _seq_parallel_attention("ulysses_attention",
                                   make_ulysses_attention, query, key,
                                   value, axis, causal)
