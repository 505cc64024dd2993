"""The live-rows K/V read as one fused call a layer: the serving families'
``attn/rows`` scope wherever the pool is not int8
(``serving/families/{dense_gqa,hybrid_ssm,window_moe,conv_moe}.py``;
PERF.md section 6, PR 39) and the latent families' ``mla/attend``
(``serving/families/latent_moe.py``, "One pool" below; PR 47).

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

**One pool.** ``vpool`` None and ``dv`` say that ONE pool holds a slot's
key and its value: the value is the first ``dv`` numbers of its head's
key. That is the latent pool under the absorbed query — ``[layers, blocks,
block, 640]``, a slot the 512-wide normed latent and the 64-wide rotary
key padded to whole lane tiles; ``nkv`` 1, ``d`` the stored width, ``dv``
``kv_lora_rank`` — and the kernel then keeps no value buffer and makes no
second copy: ``p x V`` reads ``kbuf[slot, :, :dv]``. Everything else
(grid, scalar prefetch, the two-buffer copy pipeline, the bound on live
rows, the fold, the query tiles) is the same code. One shared head brings
a verify round ``positions x heads`` = 640 query rows a lane (a K/V
head's group brings 4-80): its chunks of ``_Q_ROWS`` go through ``chunk``
``_SIDE_ROWS`` rows side by side, as several KV heads' do ("Layout"); a
128-position prefill chunk's 16,384 rows eight query tiles.

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
positions), so it is bounded by the query tile and not by the call (the
latent round: queries 640 x 640 and output 640 x 512 bfloat16 twice, 3
MB, one buffer of 2 x 256 x 640, the accumulator 1.3 MB; its chunk's
2,048-row tile ~17 MB). What
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
# ... and the rows of ONE KV head's chunks scored side by side where the
# heads do not fill ``_TOGETHER_ROWS`` — the latent pool's one shared head
# brings a round 640 query rows a lane: all five chunks of 128 at once
# read 0.72 ms a layer on the chip where two of 320 one after another
# read 0.87 and one product of 640 rows 0.78 (PERF.md section 6, PR 47).
# No K/V family's served call has room for a second chunk under it.
_SIDE_ROWS = 640
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


def _kernel(lay_ref, lane_ref, first_ref, blk_ref, pos_ref, q_ref, *refs,
            W, B, nkv, d, dv, scale, window, rows_axis):
    """One grid step: live row ``r`` of the lane ``lane_ref[r]`` (against
    one tile of that lane's query rows where the grid has a leading axis
    of query tiles: ``rows_axis`` 1). ``refs``: the pools (K and V, or
    the ONE pool a slot's key and value both lie in: the value is then
    the first ``dv`` numbers of its head's key), the output's zeros and
    the output, a VMEM buffer a pool, then the semaphores and the fold's
    scratch."""
    n = (len(refs) - 6) // 2  # pools: K and V, or the one with both
    pools, (_, o_ref), bufs = refs[:n], refs[n:n + 2], refs[n + 2:2 * n + 2]
    sem, m_scr, l_scr, acc_scr = refs[2 * n + 2:]
    copied = tuple(zip(pools, bufs))
    kbuf, vbuf = bufs[0], bufs[-1]
    vd = dv if n == 2 else d  # from one head's values to the next's
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
            for i, (pool, buf) in enumerate(copied):
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
    # where the KV heads leave room (ONE head: the latent pool), as many
    # of a head's chunks side by side as divide them
    chunks = M // step
    side = max(c for c in range(1, chunks + 1) if chunks % c == 0
               and c * together * step <= max(_SIDE_ROWS, together * step))

    def chunk(hs, c0s):
        """Query rows ``c0 .. c0 + step``, for each ``c0`` of ``c0s``, of
        the KV heads ``hs`` against the row's slots, a phase at a time
        over the chunks and heads: their chains (product, max, exp, sum,
        product) are independent, and written side by side the scheduler
        overlaps them."""
        ats = [pl.ds(c0, step) for c0 in c0s]
        viss = []
        for at in ats:
            held = first + jax.lax.broadcasted_iota(jnp.int32, (step, S), 1)
            p_own = pos_ref[0, at, :]                        # [step, 1]
            vis = held <= p_own
            if window > 0:
                vis = jnp.logical_and(vis, held > p_own - window)
            viss.append(vis)
        each = [(at, vis, h) for at, vis in zip(ats, viss) for h in hs]
        ss = [jax.lax.dot_general(
            q_ref[0, h, at, :], kbuf[slot, :, h * d:(h + 1) * d],
            (((1,), (1,)), ((), ())), preferred_element_type=F32)
            for at, _, h in each]                            # [step, S]
        ss = [jnp.where(vis, s * scale, _NEG)
              for s, (_, vis, _) in zip(ss, each)]
        m_old = [m_scr[h, at, :] for at, _, h in each]
        m_new = [jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                 for m, s in zip(m_old, ss)]
        ps = [jnp.exp(s - m) for s, m in zip(ss, m_new)]
        keep = [jnp.exp(a - b) for a, b in zip(m_old, m_new)]
        pv = [jnp.dot(p.astype(vbuf.dtype),
                      vbuf[slot, :, h * vd:h * vd + dv],
                      preferred_element_type=F32)
              for (_, _, h), p in zip(each, ps)]
        for i, (at, _, h) in enumerate(each):
            m_scr[h, at, :] = m_new[i]
            l_scr[h, at, :] = l_scr[h, at, :] * keep[i] + jnp.sum(
                ps[i], axis=-1, keepdims=True)
            acc_scr[h, at, :] = acc_scr[h, at, :] * keep[i] + pv[i]

    def chunks_at(c, hs):
        """Loop step ``c``'s ``side`` chunks of the heads ``hs``."""
        c0 = pl.multiple_of(c * (side * step), step)
        chunk(hs, [c0, *(c0 + i * step for i in range(1, side))])

    for h0 in range(0, nkv, together):
        hs = range(h0, min(h0 + together, nkv))
        if side == chunks:
            chunk(hs, [i * step for i in range(side)])
        else:
            jax.lax.fori_loop(
                0, chunks // side,
                lambda c, _, hs=hs: chunks_at(c, hs), None)

    @pl.when(closes)
    def _():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def row_attention(q, pos, rows, kpool, vpool, layer, nkv, scale,
                  sliding_window=0, dv=None):
    """``q`` [b, s, heads, d], ``pos`` [b, s], ``rows`` [R, 2 + W] (module
    docstring), ``kpool`` [layers, blocks, block, nkv x d], ``vpool`` [..,
    nkv x dv], ``layer`` a number or a traced scalar. ``vpool`` None: a
    slot's value is the first ``dv`` numbers of its head's key (the
    latent pool: module docstring, "One pool"). Returns [b, s, heads,
    dv] in ``q``'s dtype."""
    search.note_engaged("row_attention")  # pallas/engaged/..., at trace
    pools = (kpool,) if vpool is None else (kpool, vpool)
    return _rows(q, pos, rows, pools,
                 jnp.asarray(layer, jnp.int32).reshape(1), nkv=nkv,
                 dv=int(dv) if vpool is None else vpool.shape[3] // nkv,
                 scale=float(scale), window=int(sliding_window),
                 q_tile=_query_tile(q.shape[1] * (q.shape[2] // nkv),
                                    _Q_TILE_ROWS),
                 interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("nkv", "dv", "scale", "window",
                                             "q_tile", "interpret"))
def _rows(q, pos, rows, pools, layer, *, nkv, dv, scale, window, q_tile,
          interpret):
    """``row_attention`` behind one trace a program: a program's layers
    differ in ``layer`` alone, which is data. ``pools``: (K, V), or the
    one pool that holds both; ``q_tile``: the query rows a KV head that
    one grid step holds (:func:`_query_tile`)."""
    b, s, nh, d = q.shape
    g = nh // nkv
    kpool = pools[0]
    B = kpool.shape[2]
    W = rows.shape[1] - 2
    M = s * g
    T = q_tile
    assert kpool.shape[3] == nkv * d, (kpool.shape, nkv, d)
    assert dv <= d or len(pools) == 2, (dv, d)
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
                      by_lane(nkv, T, d, rows_at=1),
                      *(anywhere for _ in pools), anywhere],
            out_specs=by_lane(nkv, T, dv, rows_at=1),
            scratch_shapes=[
                *(pltpu.VMEM((2, W * B, p.shape[3]), p.dtype)
                  for p in pools),
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.VMEM((nkv, T, 1), F32), pltpu.VMEM((nkv, T, 1), F32),
                pltpu.VMEM((nkv, T, dv), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, nkv, M, dv), q.dtype),
        # a lane no row answers to is no grid step's: it reads the zeros
        # the output's buffer came in with (the last operand, after the 4
        # prefetched, the positions, the queries and the pools)
        input_output_aliases={6 + len(pools): 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="row_attention",
        interpret=interpret,
    )(layer, lane, rows[:, 1], rows[:, 2:].reshape(-1), posq, qh, *pools,
      jnp.zeros((b, nkv, M, dv), q.dtype))
    return out.reshape(b, nkv, s, g, dv).transpose(0, 2, 1, 3, 4).reshape(
        b, s, nh, dv)
