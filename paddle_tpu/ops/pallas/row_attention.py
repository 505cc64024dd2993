"""The live-rows K/V read as one fused call a layer: the serving families'
``attn/rows`` scope wherever the pool is not int8
(``serving/families/{dense_gqa,hybrid_ssm,window_moe}.py``; PERF.md section
6, PR 39).

**Contract.** ``row_attention(q, pos, rows, kpool, vpool, layer, nkv, ...)``
is ``dense_gqa._attend_lanes`` over LIVE ROWS: ``rows`` [R, 2 + W] int32 is
the engine's packed operand (``serving/engine.pack_rows``: the lane a row
answers to, the position of its first slot, its ``W`` block ids; live rows
first, each lane's rows ADJACENT, then pad rows of lane -1), ``kpool`` /
``vpool`` the STACKED pools as they are stored, ``[layers, blocks, block,
kv_heads x head_dim]`` (V ``kv_heads x dv``), of which the call reads
``layer`` — a number the kernel is TOLD (scalar prefetch), so that a
program's calls are one kernel, traced and lowered once. It returns the
attention output ``[b, s, heads, dv]`` in ``q``'s dtype; a lane with no row
reads 0.

**What stays on the chip.** Grid step ``r`` is live row ``r``: the kernel
copies the row's ``W`` blocks of K and of V from the pools into VMEM by
(layer, block) id itself (``make_async_copy`` a block, ids from SMEM; row
``r + 1``'s copies are started before row ``r`` is computed: two buffers),
so no gathered tile, no float32 copy of K/V, no relayout of 64- or 192-wide
heads and no score tensor reaches HBM. The GRID IS BOUNDED BY THE LIVE ROWS
(a dynamic grid bound, counted from ``rows`` by the caller's program): no
grid step is walked for a pad row, and a call's cost follows what the lanes
hold.

**Arithmetic** (``_attend_lanes`` is the definition; the XLA read it
replaces rounded the same operands the same way on the chip, PERF.md
section 6, PR 39): ``q``, K and V enter the matrix unit as stored (bfloat16
on the chip), products accumulated in float32; the scores times ``scale``,
the mask ``first + slot <= pos`` (and the sliding window's lower edge), the
running max, ``exp`` and the running sum in float32; ``p`` enters ``p x V``
in the pool's dtype. A lane's rows fold into ONE softmax in VMEM scratch
(running max / sum / weighted V a KV head, rescaled by ``exp(m_old -
m_new)``: the flash recurrence), written out — divided by the sum — at the
lane's last row. A masked slot weighs ``exp(-1e30 - max) = 0`` exactly; a
wholly masked row met before any visible slot weighs 1 a slot until the
first visible one rescales it by ``exp(-1e30 - max) = 0``.

**Layout.** Per KV head ``h`` the queries are ``[s x group, d]`` rows
(position-major), K ``kbuf[:, h d : (h + 1) d]`` and V likewise: slices of
the flat last axis, at lane offsets that are multiples of 64. The heads'
chains (product, max, exp, sum, product) are independent and a round's few
query rows a head make each chain latency, not work: they are written a
phase at a time over the heads, so that the scheduler overlaps them (head
after head the same kernel was 1.2-2x slower on the chip). Query rows go
through in chunks of ``_Q_ROWS`` (a prefill chunk of 128 positions at
group 16 is 2,048 rows a KV head), ``_TOGETHER_ROWS`` of them side by side.

**VMEM** at the served geometries (MiMo full layers: 4 KV heads x 192 / 128,
5 positions x group 16): K and V buffers 2 x (256 x 768 + 256 x 512) x 2 B =
1.3 MB, queries and output blocks double-buffered under 1 MB, running
statistics padded to 128 lanes 0.5 MB; the prefill chunk: queries 2 x 4 MB,
statistics 8 MB, accumulator and output 8 MB. ``vmem_limit_bytes`` states
64 MiB of the chip's 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu
from . import search

__all__ = ["row_attention"]

F32 = jnp.float32
_VMEM_LIMIT = 64 << 20
# Query rows of one KV head scored at a time, and the rows scored side by
# side over several KV heads (a round's 4-80 rows a head: all heads at once;
# a 128-position chunk: 128 rows of 4 heads), as the chip ran them fastest
# (PERF.md section 6, PR 39)
_Q_ROWS = 128
_TOGETHER_ROWS = 512
_NEG = -1e30


def _kernel(lay_ref, lane_ref, first_ref, blk_ref, pos_ref, q_ref, k_hbm,
            v_hbm, _, o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr, *,
            W, B, nkv, d, dv, scale, window):
    """One grid step: live row ``r`` of the lane ``lane_ref[r]``."""
    r, n = pl.program_id(0), pl.num_programs(0)
    lay, lane = lay_ref[0], lane_ref[r]
    M, S = q_ref.shape[2], W * B
    slot = r % 2

    def copies(row, into, start):
        """Start, or wait for, the copies of ``row``'s W blocks of K and
        of V into buffer ``into`` (a loop, not W descriptors written out:
        the kernel is traced and lowered in every process that serves)."""
        def block(j, _):
            b = blk_ref[row * W + j]
            at = pl.ds(pl.multiple_of(j * B, B), B)
            for i, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                copy = pltpu.make_async_copy(
                    pool.at[lay, b], buf.at[into, at], sem.at[i, into])
                copy.start() if start else copy.wait()

        jax.lax.fori_loop(0, W, block, None)

    @pl.when(r == 0)
    def _():
        copies(0, 0, True)

    @pl.when(r + 1 < n)
    def _():
        copies(r + 1, 1 - slot, True)

    opens = jnp.logical_or(r == 0, lane_ref[jnp.maximum(r - 1, 0)] != lane)
    closes = jnp.logical_or(
        r + 1 == n, lane_ref[jnp.minimum(r + 1, lane_ref.shape[0] - 1)]
        != lane)

    @pl.when(opens)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG, F32)
        l_scr[...] = jnp.zeros(l_scr.shape, F32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, F32)

    copies(r, slot, False)
    first = first_ref[r]
    # (the widest chunk that divides the rows: a chunk width x group)
    step = max(c for c in range(1, min(M, _Q_ROWS) + 1) if M % c == 0)
    together = max(1, min(nkv, _TOGETHER_ROWS // step))

    def chunk(hs, c0):
        """Query rows ``c0 .. c0 + step`` of the KV heads ``hs`` against
        the row's slots, a phase at a time over the heads: their chains
        (product, max, exp, sum, product) are independent, and written
        side by side the scheduler overlaps them."""
        at = pl.ds(c0, step)
        held = first + jax.lax.broadcasted_iota(jnp.int32, (step, S), 1)
        p_own = pos_ref[0, at, :]                            # [step, 1]
        vis = held <= p_own
        if window > 0:
            vis = jnp.logical_and(vis, held > p_own - window)
        ss = [jax.lax.dot_general(
            q_ref[0, h, at, :], kbuf[slot, :, h * d:(h + 1) * d],
            (((1,), (1,)), ((), ())), preferred_element_type=F32)
            for h in hs]                                     # [step, S]
        ss = [jnp.where(vis, s * scale, _NEG) for s in ss]
        m_old = [m_scr[h, at, :] for h in hs]
        m_new = [jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                 for m, s in zip(m_old, ss)]
        ps = [jnp.exp(s - m) for s, m in zip(ss, m_new)]
        keep = [jnp.exp(a - b) for a, b in zip(m_old, m_new)]
        pv = [jnp.dot(p.astype(vbuf.dtype),
                      vbuf[slot, :, h * dv:(h + 1) * dv],
                      preferred_element_type=F32) for h, p in zip(hs, ps)]
        for i, h in enumerate(hs):
            m_scr[h, at, :] = m_new[i]
            l_scr[h, at, :] = l_scr[h, at, :] * keep[i] + jnp.sum(
                ps[i], axis=-1, keepdims=True)
            acc_scr[h, at, :] = acc_scr[h, at, :] * keep[i] + pv[i]

    for h0 in range(0, nkv, together):
        hs = range(h0, min(h0 + together, nkv))
        if step == M:
            chunk(hs, 0)
        else:
            jax.lax.fori_loop(
                0, M // step, lambda c, _, hs=hs: chunk(
                    hs, pl.multiple_of(c * step, step)), None)

    @pl.when(closes)
    def _():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def row_attention(q, pos, rows, kpool, vpool, layer, nkv, scale,
                  sliding_window=0):
    """``q`` [b, s, heads, d], ``pos`` [b, s], ``rows`` [R, 2 + W] (module
    docstring), ``kpool`` [layers, blocks, block, nkv x d], ``vpool`` [..,
    nkv x dv], ``layer`` a number or a traced scalar. Returns [b, s, heads,
    dv] in ``q``'s dtype."""
    search.note_engaged("row_attention")  # pallas/engaged/..., at trace
    return _rows(q, pos, rows, kpool, vpool,
                 jnp.asarray(layer, jnp.int32).reshape(1), nkv=nkv,
                 scale=float(scale), window=int(sliding_window),
                 interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("nkv", "scale", "window",
                                             "interpret"))
def _rows(q, pos, rows, kpool, vpool, layer, *, nkv, scale, window,
          interpret):
    """``row_attention`` behind one trace a program: a program's layers
    differ in ``layer`` alone, which is data."""
    b, s, nh, d = q.shape
    g = nh // nkv
    B = kpool.shape[2]
    dv = vpool.shape[3] // nkv
    W = rows.shape[1] - 2
    M = s * g
    assert kpool.shape[3] == nkv * d, (kpool.shape, nkv, d)
    # a KV head's queries as rows, position-major: [b, nkv, s x g, d]
    qh = q.reshape(b, s, nkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, nkv, M, d)
    posq = jnp.repeat(pos.astype(jnp.int32), g, axis=1)[..., None]
    lane = rows[:, 0]
    n_live = jnp.sum(lane >= 0, dtype=jnp.int32)

    def by_lane(*block):
        return pl.BlockSpec((1, *block), lambda r, lay, ln, *_: (
            ln[r], *(0,) * len(block)))

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_kernel, W=W, B=B, nkv=nkv, d=d, dv=dv,
                          scale=scale, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n_live,),
            in_specs=[by_lane(M, 1), by_lane(nkv, M, d), anywhere, anywhere,
                      anywhere],
            out_specs=by_lane(nkv, M, dv),
            scratch_shapes=[
                pltpu.VMEM((2, W * B, nkv * d), kpool.dtype),
                pltpu.VMEM((2, W * B, nkv * dv), vpool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((nkv, M, 1), F32), pltpu.VMEM((nkv, M, 1), F32),
                pltpu.VMEM((nkv, M, dv), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, nkv, M, dv), q.dtype),
        # a lane no row answers to is no grid step's: it reads the zeros
        # the output's buffer came in with (operand 8: after the 4 prefetched)
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="row_attention",
        interpret=interpret,
    )(layer, lane, rows[:, 1], rows[:, 2:].reshape(-1), posq, qh, kpool,
      vpool, jnp.zeros((b, nkv, M, dv), q.dtype))
    return out.reshape(b, nkv, s, g, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, s, nh, dv)
