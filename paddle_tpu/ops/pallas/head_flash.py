"""Head-BATCHED flash attention: native ``[b, s, h, d]`` Pallas kernels.

The round-5 negative result (PERF.md "native [b,s,h,d] flash blocks
don't lower") established that a per-head singleton BlockSpec
``(1, block_q, 1, d)`` violates Mosaic's last-two-dims tiling rule, so
the bhsd kernels in ``flash_attention.py`` require a structural
``[b,s,h,d] -> [b·h,s,d]`` transpose pair around every attention call —
part of the profiled 8.4% data-movement slice. This module implements
the remaining idea from that write-up: a head-batched kernel whose grid
drops the head dimension entirely. Blocks carry ALL heads
(``(1, block_q, h, d)`` — the last two dims equal the array dims, which
Mosaic accepts), and every head's streaming-softmax state lives in VMEM
scratch at once. Heads are sliced STATICALLY inside the kernel (an
unrolled per-head loop of strided sublane reads and 2-D dots): the
``[h, bq, d] × [h, bk, d]`` batched-dot formulation PERF.md sketched
needs an in-kernel major-dim transpose, and Mosaic (jax 0.4.37) lowers
only 2-D transposes — the same physical-layout constraint class as the
original negative result, dodged rather than fought. The HBM-level
transposes disappear; the price is strided per-head VMEM access and an
h-times-larger VMEM footprint — exactly the trade only a hardware
measurement can judge, so the kernel ships **disengaged by default**
and flips on only via a persisted ``flash_headbatch`` row in the search
harness's tune table (``ops/pallas/search.py``; engagement =
measured-faster-than-the-best-current-path only).

Feature parity with the bhsd kernels: causal (bottom-right aligned),
sliding window, GQA (grouped in-tile — no KV repeat materialization),
in-kernel dropout (the SAME counter-hash mask bits as
``flash_attention._keep_mask``, so the two kernels drop identical
elements for one seed), and the additive key-padding mask (``[b,1,sk]``
— per batch row here; its cotangent reduces over heads in-kernel).
Parity is proven in interpret mode against the XLA composite
(tests/test_head_flash.py) and the dropout variant against the bhsd
kernel's identical mask.

What the chip's compiler takes (v5e, compiled for a described topology
— tests/test_chip_compile.py; ``jax.export`` alone does not see either
limit):

- ``head_dim % 128 == 0`` only. A d=64 head slice of the all-heads
  block is half a lane tile and Mosaic refuses the per-head store
  (``unsupported shape cast vector<256x64xbf16> ->
  vector<1x256x1x64xbf16>``), so the BERT-shape encoder is out of this
  family; :func:`hb_flash` raises on it instead of handing Mosaic a
  kernel it cannot build.
- every head's state is resident and the per-head loop is unrolled, so
  scoped VMEM (16 MiB) grows with the head count whatever the block:
  256-wide blocks overflow from 12 heads, 64-wide ones at 32 heads.
  The default block is 128 (12 heads x s1024 compiles); the 32-head
  7B geometry fits only at 32-wide blocks, which starve the MXU, and
  is not in the family's search shapes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu
from ...framework.jax_compat import export as _jax_export, tpu_compiler_params
from .. import registry
from . import search
from .flash_attention import (
    NEG_INF, _LANES, _causal_mask, _keep_mask, _pick_block, _tile_live,
    _unpack,
)

__all__ = ["hb_flash", "shape_key", "lowering_cases", "check_lowering",
           "register"]


# default (block_q, block_k) target: 256 overflows scoped VMEM in the
# dk/dv kernel from 12 heads up (module docstring)
_DEFAULT_BLOCK = 128


def _hb_fwd_kernel(*refs, causal, scale, offset, n_kb, h, h_kv, window=0,
                   dropout=0.0, has_kmask=False):
    (seed_ref, km_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
     acc_ref, m_ref, l_ref) = _unpack(refs, dropout, has_kmask, 3)
    b_idx = pl.program_id(0)
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    block_q, d = q_ref.shape[1], q_ref.shape[3]
    block_k = k_ref.shape[1]
    g = h // h_kv

    @pl.when(k_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        # heads are sliced STATICALLY from the all-heads block (strided
        # sublane reads — Mosaic lowers these; in-kernel major-dim
        # transposes to an [h, bq, d]-batched-dot layout do NOT (only
        # 2-D transposes have a lowering rule), the same physical-layout
        # constraint class as the round-5 negative result). The loop is
        # unrolled at trace time; every head's state stays resident.
        for i in range(h):
            q = q_ref[0, :, i, :].astype(jnp.float32) * scale  # [bq, d]
            k = k_ref[0, :, i // g, :].astype(jnp.float32)     # [bk, d]
            v = v_ref[0, :, i // g, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [bq, bk]
            if causal:
                s = _causal_mask(s, q_idx, k_idx, block_q, block_k,
                                 offset, window)
            if has_kmask:
                s = s + km_ref[0]  # [1, bk] additive row
            m_prev = m_ref[i, :, :1]
            l_prev = l_ref[i, :, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_ref[i] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[i] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            if dropout > 0.0:
                # the bhsd kernel's grid row is the flattened b·h + i
                # head index; feeding the same index reproduces its
                # exact mask bits (pure function of global coords)
                keep = _keep_mask(seed_ref, b_idx * h + i, q_idx, k_idx,
                                  block_q, block_k, dropout)
                p_acc = jnp.where(keep, p * (1.0 / (1.0 - dropout)),
                                  0.0)
            else:
                p_acc = p
            acc_ref[i] = alpha * acc_ref[i] + jax.lax.dot_general(
                p_acc, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        pl.when(_tile_live(q_idx, k_idx, block_q, block_k, offset,
                           window))(_step)
    else:
        _step()

    @pl.when(k_idx == n_kb - 1)
    def _fini():
        for i in range(h):
            m = m_ref[i, :, :1]
            l_safe = jnp.maximum(l_ref[i, :, :1], 1e-30)
            valid = m > NEG_INF * 0.5
            o_ref[0, :, i, :] = jnp.where(
                valid, acc_ref[i] / l_safe, 0.0).astype(o_ref.dtype)
            lse_ref[0, :, i, :] = jnp.broadcast_to(
                m + jnp.log(l_safe), (block_q, _LANES))


def _hb_dq_kernel(*refs, causal, scale, offset, n_kb, h, h_kv, window=0,
                  dropout=0.0, has_kmask=False):
    (seed_ref, km_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dq_ref, dq_acc_ref) = _unpack(refs, dropout, has_kmask, 6)
    b_idx = pl.program_id(0)
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    block_q, d = q_ref.shape[1], q_ref.shape[3]
    block_k = k_ref.shape[1]
    g = h // h_kv

    @pl.when(k_idx == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def _step():
        for i in range(h):
            q = q_ref[0, :, i, :].astype(jnp.float32)
            k = k_ref[0, :, i // g, :].astype(jnp.float32)
            v = v_ref[0, :, i // g, :].astype(jnp.float32)
            do = do_ref[0, :, i, :].astype(jnp.float32)
            lse = lse_ref[0, :, i, :1]
            delta = delta_ref[0, :, i, :1]
            s = scale * jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if causal:
                s = _causal_mask(s, q_idx, k_idx, block_q, block_k,
                                 offset, window)
            if has_kmask:
                s = s + km_ref[0]
            p = jnp.where(lse > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dropout > 0.0:
                keep = _keep_mask(seed_ref, b_idx * h + i, q_idx, k_idx,
                                  block_q, block_k, dropout)
                dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout)), 0.0)
            ds = p * (dp - delta) * scale
            dq_acc_ref[i] += jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        pl.when(_tile_live(q_idx, k_idx, block_q, block_k, offset,
                           window))(_step)
    else:
        _step()

    @pl.when(k_idx == n_kb - 1)
    def _fini():
        for i in range(h):
            dq_ref[0, :, i, :] = dq_acc_ref[i].astype(dq_ref.dtype)


def _hb_dkv_kernel(*refs, causal, scale, offset, n_qb, h, h_kv, window=0,
                   dropout=0.0, has_kmask=False):
    """dk/dv accumulate over the q-minor grid dim; GQA reduces in-tile
    (all g query heads of a KV head sit in the same block). The kmask
    cotangent additionally reduces over heads — the mask is per BATCH
    row here, unlike the bhsd kernel's per-query-head broadcast."""
    if has_kmask:
        (seed_ref, km_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
         delta_ref, dk_ref, dv_ref, dm_ref, dk_acc_ref, dv_acc_ref,
         dm_acc_ref) = _unpack(refs, dropout, True, 6)
    else:
        (seed_ref, km_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
         delta_ref, dk_ref, dv_ref, dk_acc_ref, dv_acc_ref) = _unpack(
            refs, dropout, False, 6)
        dm_ref = dm_acc_ref = None
    b_idx = pl.program_id(0)
    k_idx = pl.program_id(1)
    q_idx = pl.program_id(2)
    block_q, d = q_ref.shape[1], q_ref.shape[3]
    block_k = k_ref.shape[1]
    g = h // h_kv

    @pl.when(q_idx == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)
        if has_kmask:
            dm_acc_ref[...] = jnp.zeros_like(dm_acc_ref)

    def _step():
        for i in range(h):
            q = q_ref[0, :, i, :].astype(jnp.float32)
            k = k_ref[0, :, i // g, :].astype(jnp.float32)
            v = v_ref[0, :, i // g, :].astype(jnp.float32)
            do = do_ref[0, :, i, :].astype(jnp.float32)
            lse = lse_ref[0, :, i, :1]
            delta = delta_ref[0, :, i, :1]
            s = scale * jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if causal:
                s = _causal_mask(s, q_idx, k_idx, block_q, block_k,
                                 offset, window)
            if has_kmask:
                s = s + km_ref[0]
            p = jnp.where(lse > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
            if dropout > 0.0:
                keep = _keep_mask(seed_ref, b_idx * h + i, q_idx, k_idx,
                                  block_q, block_k, dropout)
                dmask = jnp.where(keep, 1.0 / (1.0 - dropout), 0.0)
                pd = p * dmask
            else:
                dmask = None
                pd = p
            # GQA reduces in-tile: the g query heads of kv head i//g
            # accumulate into the same scratch slice
            dv_acc_ref[i // g] += jax.lax.dot_general(
                pd, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dropout > 0.0:
                dp = dp * dmask
            ds = p * (dp - delta) * scale
            dk_acc_ref[i // g] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if has_kmask:
                # additive-bias cotangent summed over heads AND rows
                # (the mask rides per BATCH row here):
                # d(mask_j) = sum_{h,i} ds_hij / scale
                dm_acc_ref[0:1, :] += jnp.sum(ds / scale, axis=0,
                                              keepdims=True)

    if causal:
        pl.when(_tile_live(q_idx, k_idx, block_q, block_k, offset,
                           window))(_step)
    else:
        _step()

    @pl.when(q_idx == n_qb - 1)
    def _fini():
        for j in range(h_kv):
            dk_ref[0, :, j, :] = dk_acc_ref[j].astype(dk_ref.dtype)
            dv_ref[0, :, j, :] = dv_acc_ref[j].astype(dv_ref.dtype)
        if has_kmask:
            dm_ref[0] = dm_acc_ref[0:1, :].astype(dm_ref.dtype)


# -- pallas_call plumbing -----------------------------------------------------

def _hb_fwd(q, k, v, causal, scale, interpret, block_q=None,
            block_k=None, window=0, seed=None, dropout=0.0, kmask=None):
    """q: [b, sq, h, d]; k/v: [b, sk, h_kv, d] with h % h_kv == 0.
    Returns (out [b, sq, h, d], lse [b, sq, h, _LANES])."""
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    block_q = block_q or _pick_block(sq, _DEFAULT_BLOCK)
    block_k = block_k or _pick_block(sk, _DEFAULT_BLOCK)
    n_kb = sk // block_k
    grid = (b, sq // block_q, n_kb)
    kernel = functools.partial(
        _hb_fwd_kernel, causal=causal, scale=scale, offset=sk - sq,
        n_kb=n_kb, h=h, h_kv=h_kv, window=window, dropout=dropout,
        has_kmask=kmask is not None)
    in_specs = [
        pl.BlockSpec((1, block_q, h, d), lambda bb, i, j: (bb, i, 0, 0)),
        pl.BlockSpec((1, block_k, h_kv, d),
                     lambda bb, i, j: (bb, j, 0, 0)),
        pl.BlockSpec((1, block_k, h_kv, d),
                     lambda bb, i, j: (bb, j, 0, 0)),
    ]
    args = (q, k, v)
    if kmask is not None:
        in_specs = in_specs + [
            pl.BlockSpec((1, 1, block_k), lambda bb, i, j: (bb, 0, j))]
        args = args + (kmask,)
    if dropout > 0.0:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        args = (seed,) + args
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, h, d),
                         lambda bb, i, j: (bb, i, 0, 0)),
            pl.BlockSpec((1, block_q, h, _LANES),
                         lambda bb, i, j: (bb, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
            jax.ShapeDtypeStruct((b, sq, h, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, block_q, d), jnp.float32),
            pltpu.VMEM((h, block_q, _LANES), jnp.float32),
            pltpu.VMEM((h, block_q, _LANES), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * h * sq * sk * d * (0.5 if causal else 1.0)),
            bytes_accessed=int(q.size * 2 + k.size * 2 + v.size * 2),
            transcendentals=int(b * h * sq * sk),
        ),
    )(*args)
    return out, lse


def _hb_bwd_impl(q, k, v, out, lse, g_out, causal, scale, interpret,
                 block_q, block_k, window, seed, dropout, kmask=None):
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    block_q = block_q or _pick_block(sq, _DEFAULT_BLOCK)
    block_k = block_k or _pick_block(sk, _DEFAULT_BLOCK)
    n_qb = sq // block_q
    n_kb = sk // block_k
    offset = sk - sq
    g_out = g_out.astype(q.dtype)
    delta = jnp.broadcast_to(
        jnp.sum(g_out.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1, keepdims=True),
        (b, sq, h, _LANES))

    q_spec = pl.BlockSpec((1, block_q, h, d),
                          lambda bb, i, j: (bb, i, 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, h_kv, d),
                           lambda bb, i, j: (bb, j, 0, 0))
    row_spec = pl.BlockSpec((1, block_q, h, _LANES),
                            lambda bb, i, j: (bb, i, 0, 0))
    dq_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    dq_args = (q, k, v, g_out, lse, delta)
    if kmask is not None:
        km_spec = pl.BlockSpec((1, 1, block_k),
                               lambda bb, i, j: (bb, 0, j))
        dq_specs = dq_specs + [km_spec]
        dq_args = dq_args + (kmask,)
    if dropout > 0.0:
        dq_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + dq_specs
        dq_args = (seed,) + dq_args
    dq = pl.pallas_call(
        functools.partial(_hb_dq_kernel, causal=causal, scale=scale,
                          offset=offset, n_kb=n_kb, h=h, h_kv=h_kv,
                          window=window, dropout=dropout,
                          has_kmask=kmask is not None),
        grid=(b, n_qb, n_kb),
        in_specs=dq_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((h, block_q, d), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*dq_args)

    # dkv grid: (b, k-blocks, q-minor); q heads reduce in-tile
    q_spec_t = pl.BlockSpec((1, block_q, h, d),
                            lambda bb, j, i: (bb, i, 0, 0))
    kv_spec_t = pl.BlockSpec((1, block_k, h_kv, d),
                             lambda bb, j, i: (bb, j, 0, 0))
    row_spec_t = pl.BlockSpec((1, block_q, h, _LANES),
                              lambda bb, j, i: (bb, i, 0, 0))
    dkv_specs = [q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                 row_spec_t]
    dkv_args = (q, k, v, g_out, lse, delta)
    dkv_out_specs = [kv_spec_t, kv_spec_t]
    dkv_out_shape = [
        jax.ShapeDtypeStruct((b, sk, h_kv, d), k.dtype),
        jax.ShapeDtypeStruct((b, sk, h_kv, d), v.dtype),
    ]
    dkv_scratch = [
        pltpu.VMEM((h_kv, block_k, d), jnp.float32),
        pltpu.VMEM((h_kv, block_k, d), jnp.float32),
    ]
    if kmask is not None:
        km_spec_t = pl.BlockSpec((1, 1, block_k),
                                 lambda bb, j, i: (bb, 0, j))
        dkv_specs = dkv_specs + [km_spec_t]
        dkv_args = dkv_args + (kmask,)
        dkv_out_specs = dkv_out_specs + [km_spec_t]
        dkv_out_shape = dkv_out_shape + [
            jax.ShapeDtypeStruct((b, 1, sk), jnp.float32)]
        dkv_scratch = dkv_scratch + [
            pltpu.VMEM((8, block_k), jnp.float32)]
    if dropout > 0.0:
        dkv_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + dkv_specs
        dkv_args = (seed,) + dkv_args
    outs = pl.pallas_call(
        functools.partial(_hb_dkv_kernel, causal=causal, scale=scale,
                          offset=offset, n_qb=n_qb, h=h, h_kv=h_kv,
                          window=window, dropout=dropout,
                          has_kmask=kmask is not None),
        grid=(b, n_kb, n_qb),
        in_specs=dkv_specs,
        out_specs=dkv_out_specs,
        out_shape=dkv_out_shape,
        scratch_shapes=dkv_scratch,
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*dkv_args)
    if kmask is not None:
        dk, dv, dmask = outs
        return dq, dk, dv, dmask
    dk, dv = outs
    return dq, dk, dv, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _hb_call(q, k, v, seed, kmask, causal, scale, interpret,
             block_q=None, block_k=None, window=0, dropout=0.0):
    out, _ = _hb_fwd(q, k, v, causal, scale, interpret, block_q, block_k,
                     window, seed=seed, dropout=dropout, kmask=kmask)
    return out


def _hb_call_fwd_rule(q, k, v, seed, kmask, causal, scale, interpret,
                      block_q=None, block_k=None, window=0, dropout=0.0):
    out, lse = _hb_fwd(q, k, v, causal, scale, interpret, block_q,
                       block_k, window, seed=seed, dropout=dropout,
                       kmask=kmask)
    return out, (q, k, v, seed, kmask, out, lse)


def _hb_call_bwd_rule(causal, scale, interpret, block_q, block_k, window,
                      dropout, res, g_out):
    q, k, v, seed, kmask, out, lse = res
    dq, dk, dv, dmask = _hb_bwd_impl(q, k, v, out, lse, g_out, causal,
                                     scale, interpret, block_q, block_k,
                                     window, seed, dropout, kmask=kmask)
    return dq, dk, dv, None, dmask


_hb_call.defvjp(_hb_call_fwd_rule, _hb_call_bwd_rule)


def hb_flash(q, k, v, seed=None, kmask=None, causal=False, scale=None,
             interpret=False, block_q=None, block_k=None, window=0,
             dropout=0.0):
    """The head-batched flash entry: q [b, sq, h, d], k/v
    [b, sk, h_kv, d], additive ``kmask`` [b, 1, sk] or None, ``seed``
    int32[2] or None (in-kernel dropout). Returns [b, sq, h, d] — no
    layout transposes anywhere."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if not interpret and q.shape[-1] % _LANES:
        raise ValueError(
            f"hb_flash: head_dim {q.shape[-1]} is not a multiple of "
            f"{_LANES}; Mosaic cannot store a per-head slice narrower "
            f"than a lane tile (use flash_attention_kernel's bhsd path)")
    return _hb_call(q, k, v, seed, kmask, causal, scale, interpret,
                    block_q, block_k, window, dropout)


# -- search-harness family ----------------------------------------------------

def shape_key(b, sq, sk, h, h_kv, d, causal, dropout=False,
              kmask=False) -> str:
    """Exact engagement key. Variant markers (dropout / key mask) are
    part of the key: a base-shape measurement says nothing about the
    variant's extra VPU/HBM work, so variants stay disengaged until
    their own rows exist (measurement-first, like the flash dropout
    variant rows)."""
    key = f"b{b}_s{sq}x{sk}_h{h}"
    if h_kv != h:
        key += f"kv{h_kv}"
    key += f"_d{d}_{'c' if causal else 'f'}"
    if dropout:
        key += "_drop"
    if kmask:
        key += "_km"
    return key


def vmem_bytes(shape, config, dtype_bytes=2) -> int:
    """Forward-pass VMEM footprint estimate for a candidate: all heads'
    streaming state + double-buffered operand tiles. The candidate
    pruner's feasibility bound (the whole reason small block_q exists in
    this family's space — PERF.md round-5 conclusion (b))."""
    b, sq, sk, h, h_kv, d, causal = shape
    bq, bk = config["block_q"], config["block_k"]
    scratch = h * bq * (d + 2 * _LANES) * 4
    tiles = (bq * h * d + 2 * bk * h_kv * d) * dtype_bytes * 2  # dbl-buf
    outs = bq * h * d * dtype_bytes + bq * h * _LANES * 4
    return scratch + tiles + outs


class HeadBatchFlashFamily(search.KernelFamily):
    """Search space: (block_q, block_k) under a VMEM-budget prune —
    with every head's state resident, feasibility (not preference)
    bounds block_q."""

    name = "flash_headbatch"
    grad = True
    parity_atol = 2e-5
    vmem_budget = 12 * 2 ** 20  # leave headroom of the ~16 MB VMEM

    def shapes(self):
        # (b, sq, sk, h, h_kv, d, causal): the 0.44B Llama proxy. The
        # 32-head 7B geometry and the d=64 BERT encoder are NOT here:
        # the chip's compiler refuses them (module docstring), so the
        # search must never be able to write a row for them
        return [(8, 1024, 1024, 12, 12, 128, True)]

    def smoke_shapes(self):
        return [(2, 64, 64, 4, 2, 128, True)]

    def key(self, shape):
        b, sq, sk, h, h_kv, d, causal = shape
        return shape_key(b, sq, sk, h, h_kv, d, causal)

    def shape_info(self, shape):
        b, sq, sk, h, h_kv, d, causal = shape
        return {"b": b, "sq": sq, "sk": sk, "h": h, "h_kv": h_kv,
                "d": d, "causal": causal}

    def candidates(self, shape):
        """The VMEM prune is a cheap pre-filter, not a proof: a config
        the chip's compiler still refuses fails the search's timing
        step, and the harness writes rows only for configs it timed.
        Nothing is appended when nothing fits — an empty space is an
        error there, never a guess."""
        b, sq, sk, h, h_kv, d, causal = shape
        out = []
        if d % _LANES:
            return out
        for bq in (64, 128, 256, 512):
            if bq > sq or sq % bq:
                continue
            for bk in (64, 128, 256, 512):
                if bk > sk or sk % bk:
                    continue
                cand = {"block_q": bq, "block_k": bk}
                if vmem_bytes(shape, cand) <= self.vmem_budget:
                    out.append(cand)
        return out

    def _inputs(self, shape, dtype):
        b, sq, sk, h, h_kv, d, causal = shape
        q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, h, d),
                              dtype)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, sk, h_kv, d),
                              dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, sk, h_kv, d),
                              dtype)
        return q, k, v

    def make_inputs(self, shape):
        return self._inputs(shape, jnp.bfloat16)

    def make_parity_inputs(self, shape):
        # fp32 parity: the filter must see math errors, not bf16
        # quantization noise
        return self._inputs(shape, jnp.float32)

    def build(self, shape, config, interpret):
        b, sq, sk, h, h_kv, d, causal = shape
        scale = 1.0 / math.sqrt(d)

        def run(q, k, v):
            return _hb_call(q, k, v, None, None, causal, scale,
                            interpret, config.get("block_q"),
                            config.get("block_k"), 0, 0.0)

        return run

    def build_composite(self, shape):
        """The path head-batching actually replaces at this shape — the
        CURRENT production route through `flash_attention_kernel`:
        where the bhsd kernel has a measured win, that's transpose ->
        tuned bhsd flash -> transpose (the structural data movement
        this family exists to kill); elsewhere it's the XLA composite
        on the native layout. Beating this (not just the XLA fallback)
        is the engagement bar, so a head-batch row can never engage a
        slower-than-bhsd path."""
        b, sq, sk, h, h_kv, d, causal = shape
        g = h // h_kv
        scale = 1.0 / math.sqrt(d)
        from . import autotune as _tune
        from .flash_attention import _flash_bhsd

        if _tune.kernel_beats_composite(sq, sk, d, causal):
            bq, bk = _tune.best_blocks(sq, sk, d, causal)
            interpret = not on_tpu()

            def composite(q, k, v):
                qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
                kt = k.transpose(0, 2, 1, 3).reshape(b * h_kv, sk, d)
                vt = v.transpose(0, 2, 1, 3).reshape(b * h_kv, sk, d)
                out = _flash_bhsd(qt, kt, vt, causal, scale, interpret,
                                  bq, bk)
                return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

            return composite

        def composite(q, k, v):
            qg = q.astype(jnp.float32).reshape(b, sq, h_kv, g, d)
            s = jnp.einsum("bskgd,btkd->bkgst", qg,
                           k.astype(jnp.float32)) * scale
            if causal:
                mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
                s = jnp.where(mask, s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bkgst,btkd->bskgd", p,
                             v.astype(jnp.float32))
            return out.reshape(b, sq, h, d).astype(q.dtype)

        return composite


search.register_family(HeadBatchFlashFamily())


# -- lowering self-check + registry hookup ------------------------------------

def lowering_cases():
    """``(label, fn, arg_specs)``: fwd+bwd (one ``jax.grad`` each — its
    program holds the forward, dq and dk/dv kernels) for the two ends of
    the feature set, so every in-kernel path lowers once: MHA +
    additive key mask, non-causal; GQA in-tile grouping + in-kernel
    dropout, causal. Two cases, not one per feature: each compiles for
    the described chip in ~6 s (the per-head loop is unrolled), against
    ~1 s for the other families' cases. :func:`check_lowering` lowers
    them with ``jax.export``; ``tests/test_chip_compile.py`` compiles
    the same list for a described v5e."""
    sds = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    scale = 1.0 / math.sqrt(128.0)

    def masked_bwd(q, k, v, km):
        return jax.grad(
            lambda *a: hb_flash(*a, kmask=km, causal=False,
                                scale=scale).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    def gqa_drop_bwd(q, k, v, seed):
        return jax.grad(
            lambda *a: hb_flash(*a, seed, causal=True, scale=scale,
                                dropout=0.1).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    q = sds((2, 512, 8, 128), bf16)
    return [
        ("b2_s512_h8_d128_keymask", masked_bwd,
         (q, q, q, sds((2, 1, 512), jnp.float32))),
        ("b2_s512_h8kv4_d128_causal_dropout", gqa_drop_bwd,
         (q, sds((2, 512, 4, 128), bf16), sds((2, 512, 4, 128), bf16),
          sds((2,), jnp.int32))),
    ]


def check_lowering():
    """Mosaic-lower every :func:`lowering_cases` entry for platform
    'tpu' — runs on any host via jax.export, no chip needed. It stops
    before the Mosaic compile, which is where this family's two limits
    (module docstring) show: the described-topology compile is the
    check that counts."""
    for _label, fn, specs in lowering_cases():
        _jax_export.export(jax.jit(fn), platforms=["tpu"])(*specs)


def register(platform="tpu"):
    """Registry entry exists for the lowering pre-flight only: the
    head-batched kernel is dispatched from `flash_attention_kernel`
    (behind its `flash_headbatch` engagement row), never looked up by
    op name."""
    fn = hb_flash
    fn.check_lowering = check_lowering
    fn.lowering_cases = lowering_cases
    registry.register_kernel("flash_attention_headbatch", platform)(fn)
    return fn
