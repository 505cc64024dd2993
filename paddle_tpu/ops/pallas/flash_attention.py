"""FlashAttention forward/backward as Pallas TPU kernels.

Reference parity: the reference binds the external FlashAttention CUDA
library as a PHI kernel (`paddle/phi/kernels/gpu/flash_attn_kernel.cu`,
`cmake/external/flashattn.cmake`). Here the same role is played by a
tiled streaming-softmax kernel pair written in Pallas (SURVEY §5.7:
"implement splash/flash attention in Pallas").

Algorithm: FlashAttention-2. The grid iterates over BOTH q-blocks and
k-blocks — the (max, sum, acc) streaming-softmax state lives in VMEM
scratch and is carried across the k-minor grid dimension, so VMEM usage is
O(block_q·block_k + block_q·d) regardless of sequence length (the whole
point of flash attention; round-1 kept full K/V rows in VMEM which capped
seq at a few K). Backward recomputes scores per block pair (dq kernel with
k-minor grid, dkv kernel with q-minor grid), also block-local VMEM only.

Causal masking is bottom-right aligned (rows of the score matrix count
back from the last key), matching flash-attn >= 2.1 and `_sdpa_reference`
in nn/functional/attention.py (`jnp.tril(..., k=sk-sq)`).

Layout: [batch, seq, heads, head_dim] — paddle's flash-attn layout —
processed as one (batch·head) per grid row.

Registered as the 'flash_attention' kernel override for platform 'tpu', so
`paddle.nn.functional.scaled_dot_product_attention` transparently uses it
on TPU. Dropout runs IN-KERNEL (counter-hash mask), and key-PADDING
masks ([b, 1, 1, sk] bool-keep or additive — the BERT/ERNIE pattern)
run in-kernel as an additive row; row-varying masks fall back to the
XLA composite with the caller's dropout PRNG key preserved.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework.jax_compat import export as _jax_export, tpu_compiler_params

from .. import registry

NEG_INF = -1e30
# lane width for the m/l scratch rows and the lse/delta side outputs.
# Mosaic requires the last block dim to be 128-divisible (or equal to the
# array dim), so per-row scalars are carried lane-broadcast — the same
# layout the splash/flash kernels in jax.experimental.pallas.ops.tpu use
# (fp32 VMEM tiles are (8, 128)).
_LANES = 128


def _keep_mask(seed_ref, head, q_idx, k_idx, block_q, block_k, rate):
    """Per-element dropout keep-mask for one [block_q, block_k] tile.

    Counter-based hash PRNG (murmur3 fmix32 avalanche over global
    (head, row, col) + two seed words) in plain uint32 VPU ops rather
    than `pltpu.prng_random_bits`: the bits are a pure function of the
    GLOBAL element coordinates, so the forward and both backward kernels
    reproduce the identical mask with no per-tile seeding protocol (and
    with any block shape), and the CPU interpret-mode tests see the same
    numbers the hardware does (the TPU-interpret PRNG stub returns
    zeros). Reference parity: in-kernel dropout of
    `phi/kernels/gpu/flash_attn_kernel.cu` (philox counter PRNG).
    """
    rows = (q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)).astype(jnp.uint32)
    cols = (k_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)).astype(jnp.uint32)
    s0 = seed_ref[0].astype(jnp.uint32)
    s1 = seed_ref[1].astype(jnp.uint32)
    h = (s0 * jnp.uint32(0x9E3779B9)
         + (head + 1).astype(jnp.uint32) * jnp.uint32(0x85EBCA6B) + s1)
    x = rows * jnp.uint32(0x27D4EB2F) + cols * jnp.uint32(0x165667B1) + h
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    threshold = jnp.uint32(min(int(rate * 4294967296.0), 4294967295))
    return x >= threshold


def _causal_mask(s, q_idx, k_idx, block_q, block_k, offset, window=0):
    """Bottom-right-aligned causal mask for one [block_q, block_k] tile.

    Global query row r may attend key col c iff  r + offset >= c,
    where offset = seq_k - seq_q. ``window > 0`` additionally bounds the
    lookback (sliding-window / Mistral-style local attention): c must
    also satisfy  c > r + offset - window.
    """
    rows = q_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = k_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = rows + offset >= cols
    if window > 0:
        keep &= cols > rows + offset - window
    return jnp.where(keep, s, NEG_INF)


def _tile_live(q_idx, k_idx, block_q, block_k, offset, window):
    """Whether a [block_q, block_k] tile intersects the (causal, window)
    band at all — fully-masked tiles skip their MXU work."""
    below_diag = k_idx * block_k < (q_idx + 1) * block_q + offset
    if window <= 0:
        return below_diag
    in_window = (k_idx + 1) * block_k > q_idx * block_q + offset - window + 1
    return below_diag & in_window


def _unpack(refs, dropout, has_kmask, n_main):
    """refs = [seed?] + main inputs + [kmask?] + outputs/scratch."""
    i = 0
    seed_ref = None
    if dropout > 0.0:
        seed_ref = refs[0]
        i = 1
    main = refs[i:i + n_main]
    i += n_main
    km_ref = None
    if has_kmask:
        km_ref = refs[i]
        i += 1
    return (seed_ref, km_ref) + tuple(main) + tuple(refs[i:])


def _fwd_kernel(*refs, causal, scale, offset, n_kb, window=0, dropout=0.0,
                has_kmask=False):
    (seed_ref, km_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
     acc_ref, m_ref, l_ref) = _unpack(refs, dropout, has_kmask, 3)
    b_idx = pl.program_id(0)
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    block_k = k_ref.shape[1]

    @pl.when(k_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        if causal:
            s = _causal_mask(s, q_idx, k_idx, block_q, block_k, offset,
                             window)
        if has_kmask:
            s = s + km_ref[0]  # [1, bk] additive key mask, row-broadcast
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        if dropout > 0.0:
            # dropout acts on the POST-softmax probs: the denominator l
            # keeps the undropped sum, only the value-accumulator sees
            # the masked + 1/(1-rate)-rescaled probs
            keep = _keep_mask(seed_ref, b_idx, q_idx, k_idx,
                              block_q, block_k, dropout)
            p_acc = jnp.where(keep, p * (1.0 / (1.0 - dropout)), 0.0)
        else:
            p_acc = p
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p_acc, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # tiles fully outside the (causal, window) band are entirely
        # masked — skip their compute (their HBM fetch still happens;
        # the win is MXU time, which is the bottleneck here).
        pl.when(_tile_live(q_idx, k_idx, block_q, block_k, offset,
                           window))(_step)
    else:
        _step()

    @pl.when(k_idx == n_kb - 1)
    def _fini():
        m = m_ref[:, :1]
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        # rows with no valid key (bottom-right causal with sq > sk) output
        # exactly 0 — flash-attn >= 2.1 semantics, matched by the composite
        # fallback; m stays at NEG_INF iff every score was masked/skipped
        valid = m > NEG_INF * 0.5
        o_ref[0] = jnp.where(
            valid, acc_ref[...] / l_safe, 0.0).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l_safe),
                                      lse_ref.shape[1:])


def _bwd_dq_kernel(*refs, causal, scale, offset, n_kb, window=0,
                   dropout=0.0, has_kmask=False):
    (seed_ref, km_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dq_ref, dq_acc_ref) = _unpack(refs, dropout, has_kmask, 6)
    b_idx = pl.program_id(0)
    q_idx = pl.program_id(1)
    k_idx = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]

    @pl.when(k_idx == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    def _step():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_idx, k_idx, block_q, block_k, offset,
                             window)
        if has_kmask:
            s = s + km_ref[0]
        # no-valid-key rows have lse ~ NEG_INF; exp(s - lse) would blow up
        p = jnp.where(lse > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout > 0.0:
            # ds_ij = P_ij (D_ij dp_ij - delta_i) with D the keep/(1-r)
            # mask; delta already carries the dropped-out forward
            keep = _keep_mask(seed_ref, b_idx, q_idx, k_idx,
                              block_q, block_k, dropout)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout)), 0.0)
        ds = p * (dp - delta) * scale
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(_tile_live(q_idx, k_idx, block_q, block_k, offset,
                           window))(_step)
    else:
        _step()

    @pl.when(k_idx == n_kb - 1)
    def _fini():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, causal, scale, offset, n_qb, n_iters, window=0,
                    dropout=0.0, has_kmask=False):
    """dk/dv accumulate over the q-minor grid dim, which iterates
    group × q-blocks under GQA (the same KV block serves every q head of
    its group; q_idx below is the position within one head's q blocks)."""
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
    q_iter = pl.program_id(2)
    q_idx = q_iter % n_qb
    block_k = k_ref.shape[1]
    block_q = q_ref.shape[1]

    @pl.when(q_iter == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    if has_kmask:
        # the mask cotangent accumulates PER Q HEAD (the mask rides per
        # query head): reset at each head's first q-block, write at its
        # last — q_iter sweeps group x q-blocks head-major
        @pl.when(q_idx == 0)
        def _dm_init():
            dm_acc_ref[...] = jnp.zeros_like(dm_acc_ref)

    def _step():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, bk]
        if causal:
            s = _causal_mask(s, q_idx, k_idx, block_q, block_k, offset,
                             window)
        if has_kmask:
            s = s + km_ref[0]
        p = jnp.where(lse > NEG_INF * 0.5, jnp.exp(s - lse), 0.0)
        if dropout > 0.0:
            # GQA: the mask was drawn per QUERY head in the forward
            head = b_idx * (n_iters // n_qb) + q_iter // n_qb
            keep = _keep_mask(seed_ref, head, q_idx, k_idx,
                              block_q, block_k, dropout)
            dmask = jnp.where(keep, 1.0 / (1.0 - dropout), 0.0)
            pd = p * dmask
        else:
            dmask = None
            pd = p
        dv_acc_ref[...] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout > 0.0:
            dp = dp * dmask
        ds = p * (dp - delta) * scale
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if has_kmask:
            # d(mask_j) = sum_i ds_ij / scale (the mask adds to s AFTER
            # the scale multiply, and ds above carries one scale factor
            # from d(s_pre_mask)/dq path — the additive-bias cotangent
            # is sum_i dL/ds_ij = sum_i p*(dp - delta))
            dm_acc_ref[0:1, :] += jnp.sum(ds / scale, axis=0,
                                          keepdims=True)

    if causal:
        pl.when(_tile_live(q_idx, k_idx, block_q, block_k, offset,
                           window))(_step)
    else:
        _step()

    @pl.when(q_iter == n_iters - 1)
    def _fini():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)

    if has_kmask:
        @pl.when(q_idx == n_qb - 1)
        def _dm_fini():
            dm_ref[0] = dm_acc_ref[0:1, :].astype(dm_ref.dtype)


def _pick_block(seq, target=512):
    b = min(seq, target)
    while seq % b:
        b //= 2
    return max(b, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_call(q, k, v, seed, kmask, causal, scale, interpret,
                block_q=None, block_k=None, window=0, dropout=0.0):
    """The one differentiable entry all variants route through.
    ``seed`` (int32[2] or None) enables in-kernel dropout; ``kmask``
    ([bh, 1, sk] additive fp32 or None) enables the in-kernel key
    mask."""
    out, _ = _flash_fwd(q, k, v, causal, scale, interpret, block_q,
                        block_k, window, seed=seed, dropout=dropout,
                        kmask=kmask)
    return out


def _flash_call_fwd_rule(q, k, v, seed, kmask, causal, scale, interpret,
                         block_q=None, block_k=None, window=0,
                         dropout=0.0):
    out, lse = _flash_fwd(q, k, v, causal, scale, interpret, block_q,
                          block_k, window, seed=seed, dropout=dropout,
                          kmask=kmask)
    return out, (q, k, v, seed, kmask, out, lse)


def _flash_call_bwd_rule(causal, scale, interpret, block_q, block_k,
                         window, dropout, res, g):
    q, k, v, seed, kmask, out, lse = res
    dq, dk, dv, dmask = _flash_bwd_impl(q, k, v, out, lse, g, causal,
                                        scale, interpret, block_q,
                                        block_k, window, seed, dropout,
                                        kmask=kmask)
    return dq, dk, dv, None, dmask


_flash_call.defvjp(_flash_call_fwd_rule, _flash_call_bwd_rule)


def _flash_bhsd(q, k, v, causal, scale, interpret, block_q=None,
                block_k=None, window=0):
    return _flash_call(q, k, v, None, None, causal, scale, interpret,
                       block_q, block_k, window, 0.0)


def _flash_fwd(q, k, v, causal, scale, interpret, block_q=None,
               block_k=None, window=0, seed=None, dropout=0.0,
               kmask=None):
    """q: [bh, s, d], k/v: [bh_kv, s, d] with bh % bh_kv == 0 (GQA: each
    group of bh//bh_kv query heads shares one KV head — the K/V BlockSpec
    index maps divide the bh program index, so grouped heads stream the
    same KV blocks without materializing repeated KV, matching the
    reference flash_attn kernel's num_heads_k support).

    Returns (out [bh, s, d], lse [bh, s, _LANES]) — lse lane-broadcast so
    its BlockSpec satisfies Mosaic's lane-divisibility rule; consumers
    read [..., :1].
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    group = bh // k.shape[0]
    block_q = block_q or _pick_block(sq)
    block_k = block_k or _pick_block(sk)
    n_kb = sk // block_k
    grid = (bh, sq // block_q, n_kb)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               offset=sk - sq, n_kb=n_kb, window=window,
                               dropout=dropout,
                               has_kmask=kmask is not None)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j: (b // group, j, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j: (b // group, j, 0)),
    ]
    args = (q, k, v)
    if kmask is not None:
        # additive key mask [bh, 1, sk]: middle singleton keeps the
        # block 3-D so Mosaic's last-two-dims rule is satisfied
        in_specs = in_specs + [
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j))]
        args = args + (kmask,)
    if dropout > 0.0:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        args = (seed,) + args
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * bh * sq * sk * d * (0.5 if causal else 1.0)),
            bytes_accessed=int(q.size * 2 + k.size * 2 + v.size * 2),
            transcendentals=int(bh * sq * sk),
        ),
    )(*args)
    return out, lse


def _flash_bwd_impl(q, k, v, out, lse, g, causal, scale, interpret,
                    block_q, block_k, window, seed, dropout, kmask=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    bh_kv = k.shape[0]
    group = bh // bh_kv
    block_q = block_q or _pick_block(sq)
    block_k = block_k or _pick_block(sk)
    n_qb = sq // block_q
    n_kb = sk // block_k
    offset = sk - sq
    g = g.astype(q.dtype)
    # delta_i = sum_d(do * o) per row (FlashAttention-2 eq. for ds),
    # lane-broadcast to match the lse layout (see _flash_fwd docstring)
    delta = jnp.broadcast_to(
        jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1, keepdims=True),
        (bh, sq, _LANES))

    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j: (b // group, j, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j: (b // group, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
    ]
    dq_args = (q, k, v, g, lse, delta)
    if kmask is not None:
        dq_specs = dq_specs + [
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, j))]
        dq_args = dq_args + (kmask,)
    if dropout > 0.0:
        dq_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + dq_specs
        dq_args = (seed,) + dq_args
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                          offset=offset, n_kb=n_kb, window=window,
                          dropout=dropout,
                          has_kmask=kmask is not None),
        grid=(bh, n_qb, n_kb),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*dq_args)

    # dkv grid runs per KV head; the minor dim sweeps group × q-blocks so
    # grouped q heads accumulate into one dk/dv block (GQA)
    dkv_specs = [
        pl.BlockSpec((1, block_q, d),
                     lambda b, j, i: (b * group + i // n_qb,
                                      i % n_qb, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d),
                     lambda b, j, i: (b * group + i // n_qb,
                                      i % n_qb, 0)),
        pl.BlockSpec((1, block_q, _LANES),
                     lambda b, j, i: (b * group + i // n_qb,
                                      i % n_qb, 0)),
        pl.BlockSpec((1, block_q, _LANES),
                     lambda b, j, i: (b * group + i // n_qb,
                                      i % n_qb, 0)),
    ]
    dkv_args = (q, k, v, g, lse, delta)
    dkv_out_specs = [
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
    ]
    dkv_out_shape = [
        jax.ShapeDtypeStruct((bh_kv, sk, d), k.dtype),
        jax.ShapeDtypeStruct((bh_kv, sk, d), v.dtype),
    ]
    dkv_scratch = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, d), jnp.float32),
    ]
    if kmask is not None:
        dkv_specs = dkv_specs + [
            pl.BlockSpec((1, 1, block_k),
                         lambda b, j, i: (b * group + i // n_qb, 0, j))]
        dkv_args = dkv_args + (kmask,)
        # third output: the mask cotangent, accumulated per q head
        dkv_out_specs = dkv_out_specs + [
            pl.BlockSpec((1, 1, block_k),
                         lambda b, j, i: (b * group + i // n_qb, 0, j))]
        dkv_out_shape = dkv_out_shape + [
            jax.ShapeDtypeStruct((bh, 1, sk), jnp.float32)]
        dkv_scratch = dkv_scratch + [
            pltpu.VMEM((8, block_k), jnp.float32)]
    if dropout > 0.0:
        dkv_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + dkv_specs
        dkv_args = (seed,) + dkv_args
    outs = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                          offset=offset, n_qb=n_qb,
                          n_iters=group * n_qb, window=window,
                          dropout=dropout,
                          has_kmask=kmask is not None),
        grid=(bh_kv, n_kb, group * n_qb),
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


def _flash_bhsd_drop(q, k, v, seed, causal, scale, interpret,
                     block_q=None, block_k=None, window=0, dropout=0.0):
    """Dropout variant: `seed` is an int32[2] array (derived from the
    caller's dropout PRNG key) feeding the counter-hash mask — the same
    mask is regenerated in the backward kernels (see _keep_mask)."""
    return _flash_call(q, k, v, seed, None, causal, scale, interpret,
                       block_q, block_k, window, dropout)


def flash_attention_kernel(q, k, v, *rest, causal=False, dropout=0.0,
                           has_key=False, default_fn=None,
                           interpret=False):
    """Kernel-registry entry: [b, s, h, d] inputs, same signature as the
    default XLA implementation in nn/functional/attention.py. When
    ``has_key`` the trailing operand is the dropout PRNG key's raw
    uint32 data; dropout then runs IN-KERNEL (reference
    flash_attn_kernel.cu supports in-kernel dropout — the round-4 gap
    that forced every dropout>0 call onto the composite). Key-padding
    masks run in-kernel too (_key_padding_additive); row-varying masks
    and odd shapes fall back to ``default_fn``."""
    dkey = None
    if has_key and rest:
        *head_rest, dkey = rest
        rest = tuple(head_rest)

    from . import search as _search

    def fallback(dp):
        _search.note_fallback("flash")
        arrs = (q, k, v) + rest + ((dkey,) if dkey is not None else ())
        if default_fn is not None:
            return default_fn(*arrs, causal=causal, dropout=dp,
                              has_key=dkey is not None)
        from ...nn.functional.attention import _sdpa_reference

        key_arr = (jax.random.wrap_key_data(dkey)
                   if dkey is not None else None)
        return _sdpa_reference(q, k, v, *rest, causal=causal, dropout=dp,
                               dropout_key=key_arr)

    kadd = None
    if rest:
        # key-PADDING masks ([b, 1, 1, sk], bool keep or additive float
        # — the BERT/ERNIE right-pad pattern) run IN-KERNEL as an
        # additive row; anything row-varying ([.., sq, sk]) falls back
        if len(rest) == 1:
            kadd = _key_padding_additive(rest[0], q.shape, k.shape)
        if kadd is None:
            return fallback(dropout)
    if dropout > 0.0 and dkey is None:
        return fallback(dropout)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    h_kv = k.shape[2]
    # d is never blocked, so any 8-multiple head_dim lowers (block dim ==
    # array dim); d=64 (BERT-base) engages the kernel, matching the
    # reference flash_attn kernel's head_dim support. GQA/MQA (h_kv < h)
    # streams shared KV blocks via index-map division. The seq blocks
    # must be sublane-aligned when they tile the sequence.
    bq, bk = _pick_block(sq), _pick_block(sk)
    ok_blocks = (bq == sq or bq % 8 == 0) and (bk == sk or bk % 8 == 0)
    if (sq < 16 or sk < 16 or d % 8 or h % h_kv or v.shape[2] != h_kv
            or not ok_blocks):
        return fallback(dropout)
    # engagement is measurement-driven: the autotune cache stores the
    # kernel-vs-composite fwd+bwd ratio per shape (tools/flash_autotune.py
    # on hardware). Where no measurement applies, fall back to the round-4
    # measured crossover (PERF.md, TPU v5e, DCE-free differential timing):
    # the kernel wins from seq >= 1024 at every measured head_dim (3.4-5.2x);
    # the composite wins below (0.37x at s=512 d=64).
    from . import autotune as _tune

    scale = 1.0 / math.sqrt(d)
    # Under a multi-device mesh the kernel runs once per shard (see
    # _mesh_partition): the engagement keys below see the LOCAL batch
    # and head counts, the shapes the kernel will really be built for.
    part = _mesh_partition(b, h, h_kv)
    b_l, h_l, hkv_l = part[3:] if part is not None else (b, h, h_kv)
    seed = None
    if dropout > 0.0:
        seed = jax.lax.bitcast_convert_type(
            jnp.asarray(dkey).reshape(2), jnp.int32)
    if not interpret:
        # head-BATCHED variant (head_flash.py — no transpose pair):
        # exact-key measured engagement only, from the search harness's
        # flash_headbatch rows; the variant key markers keep dropout /
        # mask calls disengaged until their own rows exist
        from . import head_flash as _hb

        hb_key = _hb.shape_key(b_l, sq, sk, h_l, hkv_l, d, causal,
                               dropout > 0.0, kadd is not None)
        if _search.engaged("flash_headbatch", hb_key):
            cfg = _search.best_config("flash_headbatch", hb_key) or {}
            _search.note_engaged("flash_headbatch")

            def hb_local(q, k, v, kadd, seed):
                return _hb.hb_flash(q, k, v, seed, kadd, causal, scale,
                                    False, cfg.get("block_q"),
                                    cfg.get("block_k"), 0, dropout)

            return _per_shard(hb_local, part, q, k, v, kadd, seed)

    bq_t = bk_t = None
    if not interpret:
        # dropout/mask variants have no dedicated tune rows yet: demand
        # 20% measured headroom over the composite before engaging them
        # on an unmasked no-dropout measurement (dropout adds VPU
        # hash+select work; the mask adds an HBM operand per tile). The
        # >=1024 heuristic rows measured 3.4-6.1x, far above the margin.
        margin = 1.2 if (dropout > 0.0 or kadd is not None) else 1.0
        # the dropout-variant row was measured WITHOUT a mask operand:
        # it may replace the margin only when no mask rides along
        beats = _tune.kernel_beats_composite(
            sq, sk, d, causal, margin=margin,
            dropout=0.0 if kadd is not None else dropout)
        if beats is False:
            return fallback(dropout)
        if beats is None and (max(sq, sk) < 1024 or not causal):
            # the >=1024 crossover is extrapolated from CAUSAL
            # measurements only (flash_tune.json has no non-causal
            # >=1024 rows yet); unmeasured non-causal shapes stay on
            # the composite until tools/flash_autotune.py measures them.
            # (dropout inherits the no-dropout engagement decision: the
            # mask adds only VPU integer work.)
            return fallback(dropout)
        bq_t, bk_t = _tune.best_blocks(sq, sk, d, causal)
    _search.note_engaged("flash")

    def bhsd_local(q, k, v, kadd, seed):
        b, _, h, _ = q.shape
        h_kv = k.shape[2]
        qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        kt = k.transpose(0, 2, 1, 3).reshape(b * h_kv, sk, d)
        vt = v.transpose(0, 2, 1, 3).reshape(b * h_kv, sk, d)
        kmask = None
        if kadd is not None:
            # [b, 1, sk] -> per-query-head rows [bh, 1, sk]
            kmask = jnp.broadcast_to(kadd[:, None],
                                     (b, h, 1, sk)).reshape(b * h, 1, sk)
        out = _flash_call(qt, kt, vt, seed, kmask, causal, scale,
                          interpret, bq_t, bk_t, 0, dropout)
        return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

    return _per_shard(bhsd_local, part, q, k, v, kadd, seed)


def _mesh_partition(b, h, h_kv):
    """How one attention call splits over the active mesh, or None on a
    single device: ``(mesh, batch_axis, head_axis, b_local, h_local,
    h_kv_local)``. XLA cannot partition a Mosaic kernel by itself ("Mosaic
    kernels cannot be automatically partitioned"), so under a mesh the
    call is wrapped in a ``shard_map`` whose specs are the ones the
    model already constrains q/k/v to (``models/llama.py``: batch over
    'dp', heads over 'mp'). A dimension its axis does not divide stays
    whole — every device of that axis then computes all of it, which is
    redundant but right."""
    from ...distributed import env as env_mod

    e = env_mod.get_env()
    if e is None or e.mesh.size == 1:
        return None
    dp, mp = e.degree("dp"), e.degree("mp")
    bax = "dp" if dp > 1 and b % dp == 0 else None
    hax = "mp" if mp > 1 and h % mp == 0 and h_kv % mp == 0 else None
    return (e.mesh, bax, hax, b // dp if bax else b,
            h // mp if hax else h, h_kv // mp if hax else h_kv)


def _per_shard(local, part, q, k, v, kadd, seed):
    """Run ``local(q, k, v, kadd, seed)`` on [b, s, h, d] operands —
    directly on one device, once per (batch, head) shard under a mesh."""
    if part is None:
        return local(q, k, v, kadd, seed)
    from jax.sharding import PartitionSpec as P

    from ...framework.jax_compat import shard_map

    mesh, bax, hax = part[:3]
    qkv = P(bax, None, hax, None)

    def body(q, k, v, kadd, seed):
        if seed is not None and (bax or hax):
            # the in-kernel mask hashes LOCAL (head, row, col): fold the
            # shard's coordinates into the seed so shards draw
            # different masks
            shard = jnp.int32(0)
            for ax in (bax, hax):
                if ax is not None:
                    shard = shard * mesh.shape[ax] + jax.lax.axis_index(ax)
            seed = seed.at[1].add(shard)
        return local(q, k, v, kadd, seed)

    return shard_map(
        body, mesh=mesh,
        in_specs=(qkv, qkv, qkv,
                  None if kadd is None else P(bax, None, None),
                  None if seed is None else P()),
        out_specs=qkv, check_vma=False)(q, k, v, kadd, seed)


def _key_padding_additive(mask, q_shape, k_shape):
    """[b, 1, 1, sk] (or [b, 1, sk] / [b, sk]) key-padding mask ->
    additive fp32 [b, 1, sk], or None when the mask is row-varying /
    head-varying (those fall back to the composite). Bool means KEEP;
    floats are additive and clamped to NEG_INF so a fully-masked row
    cannot produce inf - inf in the streaming softmax."""
    b = q_shape[0]
    sk = k_shape[1]
    # ONLY [b, 1, 1, sk]: the composite's `logits + mask` broadcast
    # gives 3-D/2-D shapes different (head-bound) semantics, so
    # accepting them here would make semantics depend on which path
    # engages
    if tuple(mask.shape) != (b, 1, 1, sk):
        return None
    m = mask.reshape(b, 1, sk)
    if m.dtype == jnp.bool_:
        return jnp.where(m, 0.0, NEG_INF).astype(jnp.float32)
    if not jnp.issubdtype(m.dtype, jnp.floating):
        return None
    return jnp.maximum(m.astype(jnp.float32), NEG_INF)


def lowering_cases():
    """``(label, fn, arg_specs)`` for every shape the kernel answers
    for: fwd+bwd (one ``jax.grad`` — its program holds the forward
    kernel and both backward kernels) at the main path's widths
    (Llama-2-7B training b4 x 32 heads at s1024/d128 causal, at the
    default and at the tuned 1024 blocks; BERT-base b64 x 12 heads at
    s512/d64 non-causal), d=64 causal, cross-length, and the sliding
    window, key-mask and dropout variants. :func:`check_lowering`
    lowers them with ``jax.export``; ``tests/test_chip_compile.py``
    compiles the same list for a described v5e."""
    bf16 = jnp.bfloat16

    def grad_of(f):
        def g(q, k, v, *rest):
            return jax.grad(
                lambda *a: f(*a, *rest).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(q, k, v)
        return g

    def qkv(bh, sq, sk, d):
        return (jax.ShapeDtypeStruct((bh, sq, d), bf16),
                jax.ShapeDtypeStruct((bh, sk, d), bf16),
                jax.ShapeDtypeStruct((bh, sk, d), bf16))

    def plain(causal, d, bq=None, bk=None, window=0):
        return lambda q, k, v: _flash_bhsd(
            q, k, v, causal, 1.0 / math.sqrt(d), False, bq, bk, window)

    def masked(q, k, v, km):
        return _flash_call(q, k, v, None, km, False,
                           1.0 / math.sqrt(128.0), False, None, None, 0,
                           0.0)

    def drop(q, k, v, seed):
        return _flash_bhsd_drop(q, k, v, seed, True,
                                1.0 / math.sqrt(128.0), False, None, None,
                                0, 0.1)

    return [
        ("bh128_s1024_d128_causal", grad_of(plain(True, 128)),
         qkv(128, 1024, 1024, 128)),
        ("bh128_s1024_d128_causal_blk1024",
         grad_of(plain(True, 128, 1024, 1024)), qkv(128, 1024, 1024, 128)),
        ("bh768_s512_d64_full", grad_of(plain(False, 64)),
         qkv(768, 512, 512, 64)),
        ("bh8_s1024_d64_causal", grad_of(plain(True, 64)),
         qkv(8, 1024, 1024, 64)),
        ("bh4_s512x1024_d128_causal", grad_of(plain(True, 128)),
         qkv(4, 512, 1024, 128)),
        ("bh8_s1024_d128_window256",
         grad_of(plain(True, 128, window=256)), qkv(8, 1024, 1024, 128)),
        ("bh8_s1024_d128_keymask", grad_of(masked),
         qkv(8, 1024, 1024, 128)
         + (jax.ShapeDtypeStruct((8, 1, 1024), jnp.float32),)),
        ("bh8_s1024_d128_dropout", grad_of(drop),
         qkv(8, 1024, 1024, 128)
         + (jax.ShapeDtypeStruct((2,), jnp.int32),)),
    ]


def check_lowering():
    """Mosaic-lower every :func:`lowering_cases` entry for platform
    'tpu' — runs on any host via jax.export, no chip needed."""
    for _label, fn, specs in lowering_cases():
        _jax_export.export(jax.jit(fn), platforms=["tpu"])(*specs)


def register(platform="tpu", interpret=False):
    fn = functools.partial(flash_attention_kernel, interpret=interpret)
    # ask dispatch to pass the caller's composite closure as default_fn so
    # fallback paths keep caller state (the live dropout PRNG key).
    fn.wants_default = True
    # the lowering self-check travels with the kernel so the pre-flight
    # (ops.pallas.check_tpu_lowering) covers every registered kernel
    fn.check_lowering = check_lowering
    fn.lowering_cases = lowering_cases
    registry.register_kernel("flash_attention", platform)(fn)
    return fn
