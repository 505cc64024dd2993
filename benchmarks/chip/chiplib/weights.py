"""Seeded weights, made by the benchmark and handed to program and
reference alike.

Which leaves a model has, and of what shape, is its architecture's
(``arch/<name>.py``: ``leaf_specs``). A leaf is a counter-based hash of
(key, element index): a murmur3 finaliser over an iota, mapped to a
uniform in [-a, a) with the leaf's standard deviation. No PRNG state and
a dozen integer ops per element, so two billion bf16 weights take one
short jitted call on the chip, and the
reference can regenerate any single layer later (after the program's
state is freed) and get the same numbers bit for bit, on any backend.

The key of a leaf is mixed on the host from (seed, layer, leaf name) and
passed as a runtime uint32, so one compiled program serves every seed.
"""
from __future__ import annotations

import zlib

import numpy as np

STD = 0.02  # every matrix; norm weights are 1 + small, see make_leaf
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


def hashed_uniform(key, shape):
    """float32 uniform in [-1, 1) from (key, row-major flat element
    index), for a shape of any rank (experts stacked [E, in, out])."""
    import jax
    import jax.numpy as jnp

    n = int(np.prod(shape))
    assert n < 2 ** 32, shape  # the index is a uint32
    idx = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    for axis in range(1, len(shape)):
        idx = idx * jnp.uint32(shape[axis]) \
            + jax.lax.broadcasted_iota(jnp.uint32, shape, axis)
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
