"""Ulysses (DeepSpeed-style) sequence-parallel attention.

EXCEEDS the reference (SURVEY §2.6: "ring-attention/Ulysses are a gap to
surpass the reference"): activations arrive sequence-sharded over a mesh
axis; an all-to-all re-shards heads across that axis so every device runs
FULL-sequence attention over ``h/n`` heads, then a second all-to-all
restores the sequence sharding. Communication is two all-to-alls of the
activations (O(b·s·h·d/n) per device, riding ICI) versus ring attention's
n rotating KV exchanges — Ulysses wins when heads are plentiful and the
sequence fits one device's attention working set; ring wins at extreme
lengths. Both compose with the Pallas flash kernel for the local compute.

Layout: [batch, seq, heads, head_dim], seq sharded on the chosen axis.
Requires heads % axis_degree == 0 (the reference constraint of Ulysses).
Differentiable by construction: the all-to-alls are linear and jax
transposes them; the local attention is the registered flash kernel's
custom_vjp (or the jnp composite where the kernel's contract fails).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..framework.device import on_tpu
from ..framework.jax_compat import shard_map as _shard_map


def _local_attention(q, k, v, causal, scale, interpret, flash):
    """Full-sequence attention on local heads: [b, s, h_loc, d]."""
    b, s, h, d = q.shape
    if flash:
        from .pallas import flash_attention as fa

        def to_bh(x):
            return jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)

        out = fa._flash_bhsd(to_bh(q), to_bh(k), to_bh(v), causal, scale,
                             interpret)
        return jnp.moveaxis(out.reshape(b, h, s, d), 1, 2)
    from ..nn.functional.attention import _sdpa_reference

    return _sdpa_reference(q, k, v, causal=causal, scale=scale)


def make_ulysses_attention(mesh, axis="sep", causal=True, use_flash=None):
    """Build a differentiable Ulysses attention fn over ``axis``.

    Returns fn(q, k, v) on [b, s, h, d] arrays with s sharded over
    ``axis`` (replicated inputs accepted; outputs carry the sharding).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis])
    seq_spec = P(None, axis, None, None)
    interpret = not on_tpu()

    def make_shard_fn(flash):
        def shard_fn(q, k, v):
            scale = 1.0 / math.sqrt(q.shape[-1])

            def seq_to_heads(x):
                # [b, s_loc, h, d] -> [b, s, h/n, d]
                return jax.lax.all_to_all(x, axis, split_axis=2,
                                          concat_axis=1, tiled=True)

            def heads_to_seq(x):
                return jax.lax.all_to_all(x, axis, split_axis=1,
                                          concat_axis=2, tiled=True)

            q2, k2, v2 = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
            out = _local_attention(q2, k2, v2, causal, scale, interpret,
                                   flash)
            return heads_to_seq(out.astype(q.dtype))

        return shard_fn

    # like ring attention: the jnp variant keeps shard_map's varying-mask
    # analysis; the Pallas variant cannot (kernel out_shapes carry no vma)
    mapped = _shard_map(
        make_shard_fn(False), mesh=mesh, in_specs=(seq_spec,) * 3,
        out_specs=seq_spec, check_vma=True, axis_names=frozenset({axis}))
    mapped_flash = _shard_map(
        make_shard_fn(True), mesh=mesh, in_specs=(seq_spec,) * 3,
        out_specs=seq_spec, check_vma=False)

    def place(x):
        # same trap as ring_attention.place: under a trace, device_put
        # would silently drop the seq sharding (PTL001)
        from ..distributed.shard import constrain_or_put

        return constrain_or_put(x, NamedSharding(mesh, seq_spec))

    def ulysses(q, k, v):
        if not (q.shape[2] == k.shape[2] == v.shape[2]):
            raise ValueError(
                "ulysses attention requires equal q/k/v head counts "
                f"(got {q.shape[2]}/{k.shape[2]}/{v.shape[2]}); GQA/MQA "
                "would shard kv heads below 1 per device — repeat KV "
                "heads first or use ring_flash_attention")
        if q.shape[2] % n:
            raise ValueError(
                f"ulysses attention needs heads % axis degree == 0, got "
                f"h={q.shape[2]} over {axis}={n}")
        from .ring_attention import _flash_serves

        # local attention sees the FULL sequence with h/n heads
        m = (mapped_flash
             if _flash_serves(q.shape[1], q.shape[-1], use_flash)
             else mapped)
        return m(place(q), place(k), place(v))

    return ulysses
