"""The latent-attention sparse-expert family (``models/latent_moe.py``)
as the serving engine sees it.

- **Cache**: ONE pool ``[layers, blocks, block, kv_lora_rank +
  qk_rope_head_dim, padded to whole lane tiles]`` holding, a token a
  layer, the normed latent and the rotated rotary key (576 numbers = 1152
  B in bf16 at the published widths, 640 as stored, where 128 full heads
  of K and V would take 81,920 B); no V pool. Written by (layer, block, offset) with the null-block redirect;
  read in ONE gather from the stacked pool by (layer, block)
  (``dense_gqa._pool_forward``'s form).
- **Attention** reads the latent directly (``attend_absorbed``) in all
  three programs: at a 32-token chunk the up-projection of a whole block
  table costs ~13x the absorbed scores (PERF.md section 6, PR 27). A
  prefill chunk wider than ``QUERY_TILE`` attends its positions a tile
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

from ...models.generation import _rms
from ...models.latent_moe import attend_absorbed, latent_qkv, mlp_block
from . import absorb_accumulator

__all__ = ["LatentMoEFamily"]

LANES = 128  # the TPU's lane tile: the pool's last axis is padded to it

# A prefill chunk attends QUERY_TILE of its positions at a time, under a
# loop that runs the tiles holding a real token: the absorbed attention's
# float32 scores are [positions, heads, every table slot], written,
# reduced and read again, which the chunk's weight reads do not amortise —
# 0.08 ms a position at the published widths, so a 128-wide call on a
# 20-token prompt cost 18.6 ms where a 32-wide one cost 9.8 (PERF.md
# section 6, PR 32). The matmuls run at the call's width; the attention
# at what the call was fed.
QUERY_TILE = 32

# the device accumulator's slots, in the order the programs fill them
ACC = ("moe_assignments", "moe_assignments_held", "moe_expert_calls",
       "moe_load_max_sum")


def _attend_tiles(q_nope, q_rope, cache, vis, lp, cfg, *, n_tiles):
    """``attend_absorbed`` over the first ``n_tiles`` (data) tiles of
    ``QUERY_TILE`` positions; the positions past them read 0."""
    b, s, nh, _ = q_nope.shape

    def one(t, out):
        def cut(a):
            return jax.lax.dynamic_slice_in_dim(a, t * QUERY_TILE,
                                                QUERY_TILE, axis=1)

        return jax.lax.dynamic_update_slice_in_dim(
            out, attend_absorbed(cut(q_nope), cut(q_rope), cache, cut(vis),
                                 lp, cfg), t * QUERY_TILE, axis=1)

    return jax.lax.fori_loop(
        0, n_tiles, one,
        jnp.zeros((b, s, nh * cfg.v_head_dim), q_nope.dtype))


def table_slots(tables, pos, wlimit, block):
    """Where the fed positions ``pos`` [b, s] are written and what each
    may see of its lane's gathered table ``tables`` [b, M]: (write block
    and offset [b, s] — positions >= ``wlimit[b]`` go to null block 0 —
    and ``vis`` [b, s, M * block])."""
    M = tables.shape[1]
    idx = jnp.minimum(pos // block, M - 1)  # pad pos can run past the table
    blk = jnp.take_along_axis(tables, idx, axis=1)
    ok = pos < wlimit[:, None]
    blk = jnp.where(ok, blk, 0)
    off = jnp.where(ok, pos % block, 0)
    vis = jnp.arange(M * block)[None, None, :] <= pos[:, :, None]
    return blk, off, vis


def attend_pool(u, lp, li, pool, tables, pos, blk, off, vis, cfg, attend,
                rope=True):
    """Latent layer ``li``'s attention on normed ``u`` [b, s, h] against
    the block pool: write each token's cache entry at (``li``, ``blk``,
    ``off``), gather the lanes' whole tables, attend. Returns (att [b, s,
    heads x v], pool)."""
    b = u.shape[0]
    B, W = pool.shape[2], pool.shape[3]
    q_nope, q_rope, entry = latent_qkv(u, lp, pos, cfg, rope)
    pool = pool.at[li, blk, off].set(
        jnp.pad(entry, ((0, 0), (0, 0), (0, W - entry.shape[-1]))))
    # ONE gather on the stacked pool, by (layer, block): pool[li]
    # would make the TPU materialise the layer's whole pool first
    rows = tables + li * pool.shape[1]
    cache = pool.reshape(-1, B, W)[rows].reshape(b, tables.shape[1] * B, W)
    return attend(q_nope, q_rope, cache, vis, lp, cfg), pool


def chunk_attend(n_tiles):
    """The attention a program's positions go through: every position at
    once, or (``n_tiles``, the prefill chunk's) the tiles of
    ``QUERY_TILE`` positions that hold a real token."""
    return attend_absorbed if n_tiles is None \
        else functools.partial(_attend_tiles, n_tiles=n_tiles)


def chunk_tiles(C, start, ctx_len):
    """How many query tiles of a ``C``-wide prefill chunk at ``start``
    hold a real token; ``None`` where the chunk is not several whole
    tiles (it then attends all its positions at once)."""
    if C > QUERY_TILE and C % QUERY_TILE == 0:
        return (jnp.clip(ctx_len - start, 0, C) + QUERY_TILE - 1) \
            // QUERY_TILE
    return None


def _pool_forward(params, pool, acc, tables, ids, pos, wlimit, valid, cfg,
                  n_tiles=None):
    """Forward ``ids`` [b, s] at absolute positions ``pos`` [b, s] against
    the latent block pool: per layer, write each token's cache entry into
    its lane's block at ``pos`` (positions >= ``wlimit[b]`` go to null
    block 0), then attend over the lane's whole gathered table — every
    position at once, or (``n_tiles``, the prefill chunk's) the tiles of
    ``QUERY_TILE`` positions that hold a real token. ``valid``
    [b, s] marks real tokens for the expert layers' counts. Returns
    (x [b, s, hidden], pool, acc)."""
    eps = cfg.rms_norm_eps
    x = params["embed"][ids].astype(jnp.dtype(cfg.dtype))
    blk, off, vis = table_slots(tables, pos, wlimit, pool.shape[2])
    n_valid = jnp.sum(valid, dtype=jnp.int32)
    attend = chunk_attend(n_tiles)
    for li, lp in enumerate(params["layers"]):
        att, pool = attend_pool(_rms(x, lp["ln_in"], eps), lp, li, pool,
                                tables, pos, blk, off, vis, cfg, attend)
        x = x + _rms(att @ lp["o"], lp["ln_attn_out"], eps)
        y, counts = mlp_block(_rms(x, lp["ln_mlp_in"], eps), lp, cfg,
                              valid=valid)
        x = x + _rms(y, lp["ln_mlp_out"], eps)
        if counts is not None:
            acc = acc + expert_counts(n_valid, counts,
                                      cfg.num_experts_per_tok)
    return x, pool, acc


def expert_counts(n_valid, counts, top_k):
    """What one expert-layer call adds to the accumulator's ``ACC``."""
    return jnp.stack([n_valid * top_k, jnp.sum(counts), jnp.int32(1),
                      jnp.max(counts)])


def _head(x, params, cfg):
    x = _rms(x, params["norm"], cfg.rms_norm_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _prefill_chunk(params, pool, acc, table, ids, start, ctx_len, last_idx,
                   *, cfg):
    """One lane's prefill chunk ``ids`` [1, C] at [start, start + C);
    greedy-samples at ``last_idx``. Returns ([token, *acc], pool, acc)."""
    C = ids.shape[1]
    pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
    # a chunk of several whole query tiles attends the fed ones alone
    n_tiles = chunk_tiles(C, start, ctx_len)
    x, pool, acc = _pool_forward(
        params, pool, acc, table, ids, pos, jnp.reshape(ctx_len, (1,)),
        pos < ctx_len, cfg, n_tiles=n_tiles)
    h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1, keepdims=False)
    return jnp.concatenate([_head(h, params, cfg), acc]), pool, acc


def _decode_step(params, pool, acc, tables, cur_len, last_tok, *, cfg):
    """Every lane feeds its pending token at ``cur_len`` (write, then
    attend) and greedy-samples the next; idle lanes (``cur_len`` 0) write
    to the null block and count for nothing. Returns ([L tokens, *acc],
    pool, acc)."""
    x, pool, acc = _pool_forward(
        params, pool, acc, tables, last_tok[:, None], cur_len[:, None],
        cur_len + 1, (cur_len > 0)[:, None], cfg)
    return jnp.concatenate([_head(x[:, -1], params, cfg), acc]), pool, acc


def _verify_step(params, pool, acc, tables, cur_len, toks, wlimit, *, cfg):
    """``toks`` [L, k+1]: each lane's pending token and its draft at
    ``cur_len + j``; writes at positions >= ``wlimit[b]`` go to the null
    block. Returns ([L * (k+1) greedy picks row-major, *acc], pool,
    acc)."""
    S = toks.shape[1]
    pos = cur_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x, pool, acc = _pool_forward(params, pool, acc, tables, toks, pos,
                                 wlimit, pos < wlimit[:, None], cfg)
    return (jnp.concatenate([_head(x, params, cfg).reshape(-1), acc]),
            pool, acc)


class LatentMoEFamily:
    """See ``families/__init__.py`` for what the engine asks of it."""

    name = "latent_moe"
    donate_argnums = (1, 2)
    lane_state = False   # the one pool is indexed by (layer, block, offset)
    prefix_reuse = True  # a prefix's latent blocks are all a request needs

    def __init__(self, model, config):
        from ...framework.errors import UnimplementedError

        for flag, why in (
                (config.kv_int8, "kv_int8: the int8 scale pools are "
                 "[.., kv_heads] beside [.., kv_heads, head_dim] pools"),
                (config.int8_weights, "int8_weights: the pack would be a "
                 "second copy of the weights")):
            if flag:
                raise UnimplementedError(
                    f"the latent-attention family does not serve with "
                    f"{why}; its cache is one [layers, blocks, block, "
                    f"{model.config.latent_width}] latent pool")
        c = model.config
        self.gcfg = c.static()
        self.layers = c.num_hidden_layers
        self.max_position_embeddings = c.max_position_embeddings
        self._width = c.latent_width
        # the model's own arrays: ONE copy of the weights on the device
        self.params = {
            "embed": model.embed._data, "norm": model.norm._data,
            "lm_head": model.lm_head._data,
            "layers": tuple({k: p._data for k, p in blk.leaves().items()}
                            for blk in model.layers)}
        self.counters = dict.fromkeys(ACC, 0)
        self._seen = [0] * len(ACC)

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

    def lane_pool_bytes(self, pools):
        return 0

    def read_form(self, kind):
        """Every program takes a ``[lanes, M]`` block table and gathers
        all of it (what waits on the dense family's row read: PERF.md 7)."""
        return None

    def program(self, kind):
        return {"prefill": _prefill_chunk, "decode": _decode_step,
                "verify": _verify_step}[kind], {"cfg": self.gcfg}

    def exec_key(self, pools):
        from ...jit import exec_cache

        return {"family": self.name, "gen_cfg": self.gcfg._key(),
                "params": [exec_cache.array_spec(a) for a in
                           jax.tree_util.tree_leaves(self.params)],
                "pool": (tuple(int(x) for x in pools[0].shape),
                         str(pools[0].dtype))}

    def absorb(self, out, counters):
        """Strip the accumulator off the fetched vector into ``counters``."""
        return absorb_accumulator(out, ACC, self._seen, counters)

    def stats(self):
        itemsize = jnp.dtype(self.gcfg.dtype).itemsize
        return {"latent_kv_bytes_per_token": self._width * itemsize}
