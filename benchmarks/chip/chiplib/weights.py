"""Seeded weights, made by the benchmark and handed to program and
reference alike.

A leaf is a counter-based hash of (key, element index): a murmur3
finaliser over an iota, mapped to a uniform in [-a, a) with the leaf's
standard deviation. No PRNG state and a dozen integer ops per element, so
two billion bf16 weights take one short jitted call on the chip, and the
reference can regenerate any single layer later (after the program's
state is freed) and get the same numbers bit for bit, on any backend.

The key of a leaf is mixed on the host from (seed, layer, leaf name) and
passed as a runtime uint32, so one compiled program serves every seed.
"""
from __future__ import annotations

import zlib

import numpy as np

STD = 0.02  # every matrix; norm weights are 1 + small, see leaf_specs
_MASK = 0xFFFFFFFF


def _mix(*words) -> int:
    """64-bit splitmix over the words, folded to a uint32 key."""
    h = 0x9E3779B97F4A7C15
    for w in words:
        h = (h ^ (int(w) & 0xFFFFFFFFFFFFFFFF)) & 0xFFFFFFFFFFFFFFFF
        h = (h * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
        h ^= h >> 31
        h = (h * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
        h ^= h >> 29
    return int((h ^ (h >> 32)) & _MASK)


def leaf_key(seed: int, layer: int, name: str) -> int:
    return _mix(seed, layer + 1, zlib.crc32(name.encode()))


def leaf_specs(model_cfg: dict, layers: int) -> list:
    """(layer index or -1, name, shape, kind) for every leaf, in the
    layout ``models/llama.py`` uses: W is [in, out]; q, k, v fused into
    one ``qkv`` (q first), gate and up fused into ``gate_up`` (gate
    first)."""
    h = model_cfg["hidden_size"]
    nh = model_cfg["num_attention_heads"]
    nkv = model_cfg["num_key_value_heads"]
    d = model_cfg["head_dim"]
    ffn = model_cfg["intermediate_size"]
    v = model_cfg["vocab_size"]
    out = [(-1, "embed", (v, h), "matrix")]
    for li in range(layers):
        out += [
            (li, "ln1", (h,), "norm"),
            (li, "qkv", (h, (nh + 2 * nkv) * d), "matrix"),
            (li, "o", (nh * d, h), "matrix"),
            (li, "ln2", (h,), "norm"),
            (li, "gate_up", (h, 2 * ffn), "matrix"),
            (li, "down", (ffn, h), "matrix"),
        ]
    out += [(-1, "norm", (h,), "norm"), (-1, "lm_head", (h, v), "matrix")]
    return out


def hashed_uniform(key, shape):
    """float32 uniform in [-1, 1) from (key, flat element index)."""
    import jax
    import jax.numpy as jnp

    n = int(np.prod(shape))
    assert n < 2 ** 32, shape
    idx = jax.lax.iota(jnp.uint32, n).reshape(shape) if len(shape) == 1 \
        else (jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
              * jnp.uint32(shape[1])
              + jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    x = idx ^ key
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    x = (x ^ key) * jnp.uint32(0x27D4EB2F)
    x = x ^ (x >> 15)
    u = (x >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))
    return u * 2.0 - 1.0


def make_leaf(key, shape, kind, dtype):
    """One leaf in ``dtype``. Matrices: uniform with std STD. Norm
    weights: 1 + uniform(-0.1, 0.1), so a dropped norm weight shows."""
    import jax
    import jax.numpy as jnp

    u = hashed_uniform(key, shape)
    w = u * (STD * 3.0 ** 0.5) if kind == "matrix" else 1.0 + 0.1 * u
    if jnp.dtype(dtype) == jnp.bfloat16:
        # an explicit rounding the compiler must keep: the TPU compiler
        # may otherwise elide a float32 -> bfloat16 -> float32 round trip
        # ("excess precision"), and the reference would then start from
        # other weights than the program (found on the chip, PR 23)
        w = jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=7)
    return w.astype(jnp.dtype(dtype))


def keys_for(seed: int, specs) -> np.ndarray:
    return np.asarray([leaf_key(seed, li, name) for li, name, _, _ in specs],
                      np.uint32)
