"""The latent-attention sparse-expert family (``models/latent_moe.py``)
as the serving engine sees it.

- **Cache**: ONE pool ``[layers, blocks, block, kv_lora_rank +
  qk_rope_head_dim, padded to whole lane tiles]`` holding, a token a
  layer, the normed latent and the rotated rotary key (576 numbers = 1152
  B in bf16 at the published widths, 640 as stored, where 128 full heads
  of K and V would take 81,920 B); no V pool. Written by (layer, block,
  offset) with the null-block redirect.
- **The read follows what the lanes hold** (``_attend_rows``; PERF.md
  section 6, PR 35): the engine's ``pack`` phase cuts each running lane's
  block list into rows of ``ROW_BLOCKS`` blocks and lays all lanes' rows
  end to end (``engine.pack_rows``, the dense family's operand); a
  program gathers the live rows a tile at a time from the stacked pool by
  (layer, block), attends row by row and recombines per lane as one
  softmax. No program gathers a table slot that holds nothing, so a
  call's cost follows the live tokens, not ``max_seq_len``.
- **Attention** reads the latent directly, ``kv_b`` absorbed
  (``models/latent_moe.attend_absorbed``'s arithmetic, which stays the
  definition the row read is held to: tests/test_serving_rows.py), in
  all three programs: at a 32-token chunk the up-projection of a whole
  block table costs ~13x the absorbed scores (PERF.md section 6, PR 27).
  A prefill chunk wider than ``QUERY_TILE`` attends its positions a tile
  at a time and skips the tiles that are all pad (below).
- **Weights once**: ``params`` is a tuple of per-layer dicts whose leaves
  ARE the model's arrays, and each program is a Python loop over the
  layers (a dense layer followed by expert layers cannot be one scan
  body; a chip of such a deployment holds a handful of layers, so compile
  time stays bounded).
- **Counters** ride on the round's token array: every program adds its
  expert layers' assignment counts to a small device accumulator that is
  threaded through the programs like the pool, and decode / verify (and
  the final prefill chunk) return ``[tokens..., accumulator]`` as one
  int32 vector — the engine's one fetch a round brings them along.

``kv_int8`` and ``int8_weights`` raise ``UnimplementedError``: the scale
pools assume ``kv_heads x head_dim``, and an int8 weight pack would be a
second copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...models.generation import _rms
from ...models.latent_moe import (
    absorb_query, latent_qkv, mlp_block, unabsorb_output,
)
from .common import MOE_ACC as ACC  # the accumulator: the expert layers'
from .common import Family, _out, expert_counts, greedy_head, write_slots

__all__ = ["LatentMoEFamily"]

LANES = 128  # the TPU's lane tile: the pool's last axis is padded to it

# The latent read's constants, chosen on the chip (PERF.md section 6, PR
# 35: single verify calls at the published widths, 64 lanes): a row is
# ROW_BLOCKS blocks of one lane (16 read 27.3 ms a call where 20, 24 and
# 28 read 30.4, 28.8 and 30.1 and the whole table 47.9), and a round
# program runs its live rows ROW_TILE at a time — as many rows as the
# benchmark's deployments have lanes, which is also what its reader of
# this attention's device time finds the operations by (PERF.md section 7
# (r): a debt); the prefill chunk, all rows one lane's: PREFILL_TILE (4
# read 13.0-14.7 ms a 128-wide call, 2 and 8 more).
ROW_BLOCKS = 16
ROW_TILE = 64
PREFILL_TILE = 4

# A prefill chunk attends QUERY_TILE of its positions at a time, under a
# loop that runs the tiles holding a real token: the absorbed attention's
# float32 scores are [positions, heads, a tile of rows' slots], written,
# reduced and read again, which the chunk's weight reads do not amortise
# (0.08 ms a position over a whole table at the published widths: a
# 128-wide call on a 20-token prompt cost 18.6 ms where a 32-wide one cost
# 9.8, PERF.md section 6, PR 32). The matmuls run at the call's width; the
# attention at what the call was fed.
QUERY_TILE = 32


def _attend_rows(qq, pos, rows, gather, tile, lp, cfg):
    """``models/latent_moe.attend_absorbed`` after ``absorb_query``, over
    LIVE ROWS: what each lane holds, cut into rows of ``W`` blocks, and
    nothing else of its table. ``qq`` [b, s, heads, stored] the absorbed
    queries, ``pos`` [b, s]; ``rows`` [R, 2 + W] int32 as
    ``engine.pack_rows`` lays them (lane, -1 a pad row; the position of
    the row's first slot; its ``W`` block ids); ``gather(blocks [T, W])``
    returns those blocks' entries as ``[T, W * B, stored]``.

    Rows run ``tile`` at a time under a device-side loop whose trip count
    is data (the live rows, counted here): per row the scores of its
    lane's queries against its slots (model dtype, float32 accumulation),
    the mask ``first + slot <= pos``, the row's max, its sum of
    exponentials, its weighted sum of the latent and that through ``W_v``
    (``unabsorb_output``, kept in float32); per tile those are folded into
    each lane's running max / sum / output as a softmax over the union of
    the lane's slots (``dense_gqa._attend_rows``' fold: rescaled by
    ``exp(row_max - lane_max)``, added up by lane). ``W_v`` goes before
    the fold because the fold's operands are float32 and a row's weighted
    sum is ``kv_lora_rank`` wide a head where its output is
    ``v_head_dim``: folded first, the float32 ``[rows, positions, heads,
    512]`` tensor's round trips were 10 of a verify call's 34.6 ms at the
    published widths (PERF.md section 6, PR 35). A masked slot weighs
    exp(-1e30 - max) = 0 exactly; a lane with no row reads 0. Returns
    [b, s, heads * dv] in the queries' dtype."""
    b, s, nh, _ = qq.shape
    dc, dv = cfg.kv_lora_rank, cfg.v_head_dim
    f32, dt = jnp.float32, qq.dtype
    scale = np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    lane, first, blocks = rows[:, 0], rows[:, 1], rows[:, 2:]
    tile = min(tile, rows.shape[0])  # engine.fit_rows: fewer rows, one tile
    assert rows.shape[0] % tile == 0, (rows.shape, tile)

    def one_tile(t, carry):
        m, l, o = carry  # [b, s, nh] twice, [b, s, nh, dv]
        r0 = t * tile
        ln = jax.lax.dynamic_slice_in_dim(lane, r0, tile)
        own = jnp.maximum(ln, 0)
        cache = gather(jax.lax.dynamic_slice_in_dim(blocks, r0, tile))
        at = jax.lax.dynamic_slice_in_dim(first, r0, tile)[:, None, None] \
            + jnp.arange(cache.shape[1])[None, None, :]     # [T, 1, S]
        vis = at <= pos[own][:, :, None]                    # [T, s, S]
        scores = jnp.einsum("tshe,tle->tshl", qq[own], cache,
                            preferred_element_type=f32) / scale
        scores = jnp.where(vis[:, :, None, :], scores, -1e30)
        rm = jnp.max(scores, axis=-1)                       # [T, s, nh]
        p = jnp.exp(scores - rm[..., None])
        rl = jnp.sum(p, axis=-1)
        ro = jnp.einsum("tshl,tlc->tshc", p.astype(dt), cache[..., :dc],
                        preferred_element_type=f32).astype(dt)
        rv = unabsorb_output(ro, lp, cfg, f32).reshape(tile, s, nh, dv)
        mine = ln[None, :] == jnp.arange(b)[:, None]        # [b, T]
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(mine[:, :, None, None], rm[None], -1e30), axis=1))
        # (a pad row may outscore lane 0's max: its weight is 0, not inf)
        w = jnp.exp(jnp.where((ln >= 0)[:, None, None],
                              rm - m_new[own], -1e30))
        keep = jnp.exp(m - m_new)
        hot = mine.astype(f32)
        exact = jax.lax.Precision.HIGHEST  # the one-hot sum is a sum
        l = l * keep + jnp.einsum("bt,tsh->bsh", hot, rl * w,
                                  precision=exact)
        o = o * keep[..., None] + jnp.einsum(
            "bt,tshd->bshd", hot, rv * w[..., None], precision=exact)
        return m_new, l, o

    n_tiles = (jnp.sum(lane >= 0, dtype=jnp.int32) + tile - 1) // tile
    _, l, o = jax.lax.fori_loop(
        0, n_tiles, one_tile,
        (jnp.full((b, s, nh), -1e30, f32), jnp.zeros((b, s, nh), f32),
         jnp.zeros((b, s, nh, dv), f32)))
    out = o / jnp.where(l > 0, l, 1.0)[..., None]
    return out.astype(dt).reshape(b, s, nh * dv)


def _attend_tiles(qq, pos, n_tiles, attend, width):
    """``attend(qq, pos)`` [b, s, ``width``] over the first ``n_tiles``
    (data) tiles of ``QUERY_TILE`` positions; the positions past them
    read 0."""
    def one(t, out):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, t * QUERY_TILE,
                                                QUERY_TILE, axis=1)

        return jax.lax.dynamic_update_slice_in_dim(
            out, attend(cut(qq), cut(pos)), t * QUERY_TILE, axis=1)

    return jax.lax.fori_loop(
        0, n_tiles, one, jnp.zeros((*qq.shape[:2], width), qq.dtype))


def attend_pool(u, lp, li, pool, rows, pos, blk, off, cfg, tile,
                n_tiles=None, rope=True):
    """Latent layer ``li``'s attention on normed ``u`` [b, s, h] against
    the block pool: write each token's cache entry at (``li``, ``blk``,
    ``off``), then attend over the lanes' LIVE ROWS (``_attend_rows``),
    gathered ``tile`` at a time from the stacked pool — every position
    at once, or (``n_tiles``, the prefill chunk's) the tiles of
    ``QUERY_TILE`` positions that hold a real token. Returns (att [b, s,
    heads x v], pool)."""
    nb, B, W = pool.shape[1:]
    q_nope, q_rope, entry = latent_qkv(u, lp, pos, cfg, rope)
    with jax.named_scope("mla/kv_write"):
        pool = pool.at[li, blk, off].set(
            jnp.pad(entry, ((0, 0), (0, 0), (0, W - entry.shape[-1]))))

    def gather(blocks):
        # from the STACKED pool, by (layer, block): pool[li] would make
        # the TPU materialise the layer's whole pool first
        return pool.reshape(-1, B, W)[blocks + li * nb].reshape(
            blocks.shape[0], -1, W)

    attend = functools.partial(_attend_rows, rows=rows, gather=gather,
                               tile=tile, lp=lp, cfg=cfg)
    with jax.named_scope("mla/attend"):
        qq = absorb_query(q_nope, q_rope, lp, cfg, W)
        if n_tiles is None:
            return attend(qq, pos), pool
        return _attend_tiles(
            qq, pos, n_tiles, attend,
            cfg.num_attention_heads * cfg.v_head_dim), pool


def chunk_tiles(C, start, ctx_len):
    """How many query tiles of a ``C``-wide prefill chunk at ``start``
    hold a real token; ``None`` where the chunk is not several whole
    tiles (it then attends all its positions at once)."""
    if C > QUERY_TILE and C % QUERY_TILE == 0:
        with jax.named_scope("mla/attend"):
            return (jnp.clip(ctx_len - start, 0, C) + QUERY_TILE - 1) \
                // QUERY_TILE
    return None


def _pool_forward(params, pool, acc, read, ids, pos, wlimit, valid, cfg,
                  tile, n_tiles=None):
    """Forward ``ids`` [b, s] at absolute positions ``pos`` [b, s] against
    the latent block pool: per layer, write each token's cache entry into
    its lane's block at ``pos`` (positions >= ``wlimit[b]`` go to null
    block 0), then attend over the blocks the lanes HOLD: ``read`` is
    ``(rows, wblk)``, the engine's live rows and, per token, the block
    its position falls in (``attend_pool``). ``valid`` [b, s] marks real
    tokens for the expert layers' counts. Returns (x [b, s, hidden],
    pool, acc)."""
    eps = cfg.rms_norm_eps
    scope = jax.named_scope  # the scopes: monitor/scopes.py
    with scope("embed"):
        x = params["embed"][ids].astype(jnp.dtype(cfg.dtype))
    rows, wblk = read
    blk, off = write_slots(wblk, pos, wlimit, pool.shape[2], "mla/kv_write")
    with scope("acc"):
        n_valid = jnp.sum(valid, dtype=jnp.int32)
    for li, lp in enumerate(params["layers"]):
        att, pool = attend_pool(_rms(x, lp["ln_in"], eps), lp, li, pool,
                                rows, pos, blk, off, cfg, tile, n_tiles)
        with scope("mla/out"):
            att = att @ lp["o"]
        att = _rms(att, lp["ln_attn_out"], eps)
        with scope("norm"):  # a residual add: its producer's scope
            x = x + att
        y, counts = mlp_block(_rms(x, lp["ln_mlp_in"], eps), lp, cfg,
                              valid=valid)
        y = _rms(y, lp["ln_mlp_out"], eps)
        with scope("norm"):
            x = x + y
        if counts is not None:
            with scope("acc"):
                acc = acc + expert_counts(n_valid, counts,
                                          cfg.num_experts_per_tok)
    return x, pool, acc


def read_form(kind):
    """Program ``kind``'s ``(W, tile)``: this family's and the
    linear-attention family's, whose latent layers read through the same
    functions."""
    return ROW_BLOCKS, PREFILL_TILE if kind == "prefill" else ROW_TILE


def _prefill_chunk(params, pool, acc, read, ids, start, ctx_len, last_idx,
                   *, cfg, tile):
    """One lane's prefill chunk ``ids`` [1, C] at [start, start + C),
    ``read`` its lane's rows live up to the chunk's end; greedy-samples
    at ``last_idx``. Returns ([token, *acc], pool, acc)."""
    C = ids.shape[1]
    with jax.named_scope("embed"):  # the fed positions
        pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
    # a chunk of several whole query tiles attends the fed ones alone
    n_tiles = chunk_tiles(C, start, ctx_len)
    with jax.named_scope("embed"):  # ... how far they go, which are real
        fed = jnp.reshape(ctx_len, (1,)), pos < ctx_len
    x, pool, acc = _pool_forward(params, pool, acc, read, ids, pos, *fed,
                                 cfg, tile, n_tiles=n_tiles)
    with jax.named_scope("head"):
        h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1,
                                         keepdims=False)
    return _out(greedy_head(h, params, cfg.rms_norm_eps), acc), pool, acc


def _decode_step(params, pool, acc, read, cur_len, last_tok, *, cfg, tile):
    """Every lane feeds its pending token at ``cur_len`` (write, then
    attend) and greedy-samples the next; idle lanes (``cur_len`` 0, no
    row) write to the null block and count for nothing. Returns ([L
    tokens, *acc], pool, acc)."""
    with jax.named_scope("embed"):  # the fed tokens, where, which are real
        fed = (last_tok[:, None], cur_len[:, None], cur_len + 1,
               (cur_len > 0)[:, None])
    x, pool, acc = _pool_forward(params, pool, acc, read, *fed, cfg, tile)
    with jax.named_scope("head"):
        x = x[:, -1]
    return _out(greedy_head(x, params, cfg.rms_norm_eps), acc), pool, acc


def _verify_step(params, pool, acc, read, cur_len, toks, wlimit, *, cfg,
                 tile):
    """``toks`` [L, k+1]: each lane's pending token and its draft at
    ``cur_len + j``; writes at positions >= ``wlimit[b]`` go to the null
    block. Returns ([L * (k+1) greedy picks row-major, *acc], pool,
    acc)."""
    S = toks.shape[1]
    with jax.named_scope("embed"):
        pos = cur_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        valid = pos < wlimit[:, None]
    x, pool, acc = _pool_forward(params, pool, acc, read, toks, pos,
                                 wlimit, valid, cfg, tile)
    return _out(greedy_head(x, params, cfg.rms_norm_eps), acc), pool, acc


class LatentMoEFamily(Family):
    """See ``families/__init__.py`` for what the engine asks of it."""

    name = "latent_moe"
    title = "the latent-attention family"
    ACC = ACC
    programs = {"prefill": _prefill_chunk, "decode": _decode_step,
                "verify": _verify_step}
    tiled = True  # ``_attend_rows`` runs its rows ``tile`` at a time
    donate_argnums = (1, 2)

    def __init__(self, model, config):
        c = model.config
        self.refuse(config, {
            "kv_int8": "the int8 scale pools are [.., kv_heads] beside "
            "[.., kv_heads, head_dim] pools"},
            tail=f"; its cache is one [layers, blocks, block, "
            f"{c.latent_width}] latent pool")
        super().__init__(model, config)
        self.layers = c.num_hidden_layers
        self._width = c.latent_width

    def make_pools(self, num_blocks, block_size):
        """(latent pool, the counters' device accumulator). The pool's
        last axis is the cache entry padded to whole 128-lane tiles (576
        -> 640): the TPU pads a row-major ``[.., 16, 576]`` to that
        anyway, but left to choose it lays such a pool out blocks-minor
        (less padding), and the scatter and the gather, which want it
        row-major, then copied the whole pool in and out in every program
        call (2 x 1.95 ms at 0.57 GB: PERF.md section 6, PR 27;
        tests/test_chip_compile.py holds the programs to no such copy).
        A padded pool's own layout IS row-major."""
        return (jnp.zeros((self.layers, num_blocks, block_size,
                           -(-self._width // LANES) * LANES),
                          jnp.dtype(self.gcfg.dtype)),
                jnp.zeros((len(ACC),), jnp.int32))

    def kv_pool_bytes(self, pools):
        return int(pools[0].nbytes)

    def read_form(self, kind):
        """How program ``kind`` is told where its lanes' entries lie
        (``ServingEngine._pack_read`` builds it): ``(W, tile)`` — live
        rows of ``W`` blocks, run ``tile`` at a time."""
        return read_form(kind)

    def stats(self):
        itemsize = jnp.dtype(self.gcfg.dtype).itemsize
        return {"latent_kv_bytes_per_token": self._width * itemsize}
