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
hold. A call whose lanes each bring more than ``_Q_TILE_ROWS`` query rows a
KV head (a 512-position prefill chunk at group 16) walks its live rows once
a QUERY TILE — a leading grid axis over tiles of at most that many rows,
each with its own copy pipeline, statistics and output block, so K/V is
read once a tile (4 x 21 MB a full layer of an 8k context at 512
positions) — and what one grid step holds in VMEM is bounded by the tile,
not by the call's width. Which form a call takes is its SHAPE's:
every round, and every chunk of up to 128 positions at group 16 (512 at
group 4), is one tile and the one-axis grid.

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
after head the same kernel was 1.2-2x slower on the chip). A grid step's
query rows (its tile's: at most ``_Q_TILE_ROWS`` a KV head, position-major,
so a tile is a run of whole positions) go through in chunks of ``_Q_ROWS``,
``_TOGETHER_ROWS`` of them side by side.

**VMEM** follows the query rows ONE GRID STEP holds (queries and output
double-buffered, the running max and sum, the float32 accumulator, the
positions), so it is bounded by the query tile and not by the call. What
the chip's compiler takes at the served geometries, by bisecting
``vmem_limit_bytes`` for a described v5e with the call INSIDE a program
(its queries a product's result and its output a product's operand, which
the compiler may keep in VMEM too): a round of 5 positions at MiMo's full
layers (4 KV heads x 192 / 128, group 16) — K and V buffers 2 x (256 x
768 + 256 x 512) x 2 B = 1.3 MB, queries, output and statistics under 2
MB; a 128-position chunk there (2,048 rows a KV head, one tile) compiles
from 15 MiB, its family's 512-position chunk in four tiles from 27 MiB;
LFM2's and granite's 8 KV heads x 64 at group 4 bring 2,048 rows at 512
positions: one tile, from 26 MiB. As ONE tile of 8,192 rows MiMo's 512
positions are REFUSED under the stated limit: the family's prefill program
then wants 170 MB of VMEM (the kernel handed its operands as parameters
of their own compiles alone from 51 MiB, and ran so on the chip 3.5%
faster than four tiles: what a program can hold is what counts).
``vmem_limit_bytes`` states 64 MiB of the chip's 128;
``tests/test_chip_compile.py`` compiles every family's round and chunk
under it for a described v5e, and holds the one-tile refusal.
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
# Query rows of one KV head that one grid step may hold (module docstring,
# "VMEM"): a lane's rows beyond it go a tile a walk of the live rows. The
# widest that was served before a call could be wider (128 positions at
# group 16), so every call of up to that many rows is the kernel it was.
_Q_TILE_ROWS = 2048
_NEG = -1e30


def _query_tile(M, bound):
    """The query rows (of ``M`` a KV head) one grid step holds: all of them
    up to ``bound``, else the largest whole number of sublane tiles (8
    rows) that divides ``M`` under it; ``M`` where nothing divides."""
    if M <= bound:
        return M
    return max((c for c in range(8, bound + 1, 8) if M % c == 0),
               default=M)


def _kernel(lay_ref, lane_ref, first_ref, blk_ref, pos_ref, q_ref, k_hbm,
            v_hbm, _, o_ref, kbuf, vbuf, sem, m_scr, l_scr, acc_scr, *,
            W, B, nkv, d, dv, scale, window, rows_axis):
    """One grid step: live row ``r`` of the lane ``lane_ref[r]`` (against
    one tile of that lane's query rows where the grid has a leading axis
    of query tiles: ``rows_axis`` 1)."""
    r, n = pl.program_id(rows_axis), pl.num_programs(rows_axis)
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
                 q_tile=_query_tile(q.shape[1] * (q.shape[2] // nkv),
                                    _Q_TILE_ROWS),
                 interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("nkv", "scale", "window",
                                             "q_tile", "interpret"))
def _rows(q, pos, rows, kpool, vpool, layer, *, nkv, scale, window, q_tile,
          interpret):
    """``row_attention`` behind one trace a program: a program's layers
    differ in ``layer`` alone, which is data. ``q_tile``: the query rows a
    KV head that one grid step holds (:func:`_query_tile`)."""
    b, s, nh, d = q.shape
    g = nh // nkv
    B = kpool.shape[2]
    dv = vpool.shape[3] // nkv
    W = rows.shape[1] - 2
    M = s * g
    T = q_tile
    assert kpool.shape[3] == nkv * d, (kpool.shape, nkv, d)
    assert M % T == 0, (M, T)
    # a KV head's queries as rows, position-major: [b, nkv, s x g, d]
    qh = q.reshape(b, s, nkv, g, d).transpose(0, 2, 1, 3, 4).reshape(
        b, nkv, M, d)
    posq = jnp.repeat(pos.astype(jnp.int32), g, axis=1)[..., None]
    lane = rows[:, 0]
    n_live = jnp.sum(lane >= 0, dtype=jnp.int32)

    # one tile: the grid is the live rows alone, as it was before a call
    # could be wider than a tile; more: a leading axis of query tiles, each
    # walking the live rows (the query-row axis of a block: ``rows_at``)
    tiled = T < M
    grid = (M // T, n_live) if tiled else (n_live,)

    def by_lane(*block, rows_at):
        """The block of the lane that grid row ``r`` answers to (``ln``,
        the second prefetched operand) and, along the block's query-row
        axis ``rows_at``, of the grid's tile."""
        def index(*ids):
            at_grid, ln = ids[:len(grid)], ids[len(grid) + 1]
            at = [0] * len(block)
            at[rows_at] = at_grid[0] if tiled else 0
            return (ln[at_grid[-1]], *at)

        return pl.BlockSpec((1, *block), index)

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_kernel, W=W, B=B, nkv=nkv, d=d, dv=dv,
                          scale=scale, window=window,
                          rows_axis=len(grid) - 1),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid,
            in_specs=[by_lane(T, 1, rows_at=0),
                      by_lane(nkv, T, d, rows_at=1), anywhere, anywhere,
                      anywhere],
            out_specs=by_lane(nkv, T, dv, rows_at=1),
            scratch_shapes=[
                pltpu.VMEM((2, W * B, nkv * d), kpool.dtype),
                pltpu.VMEM((2, W * B, nkv * dv), vpool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((nkv, T, 1), F32), pltpu.VMEM((nkv, T, 1), F32),
                pltpu.VMEM((nkv, T, dv), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, nkv, M, dv), q.dtype),
        # a lane no row answers to is no grid step's: it reads the zeros
        # the output's buffer came in with (operand 8: after the 4 prefetched)
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="row_attention",
        interpret=interpret,
    )(layer, lane, rows[:, 1], rows[:, 2:].reshape(-1), posq, qh, kpool,
      vpool, jnp.zeros((b, nkv, M, dv), q.dtype))
    return out.reshape(b, nkv, s, g, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, s, nh, dv)
