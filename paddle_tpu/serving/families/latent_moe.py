"""The latent-attention sparse-expert family (``models/latent_moe.py``)
as the serving engine sees it.

- **Cache**: ONE pool ``[layers, blocks, block, kv_lora_rank +
  qk_rope_head_dim, padded to whole lane tiles]`` holding, a token a
  layer, the normed latent and the rotated rotary key (576 numbers = 1152
  B in bf16 at the published widths, 640 as stored, where 128 full heads
  of K and V would take 81,920 B); no V pool. Written by (layer, block,
  offset) with the null-block redirect.
- **The read follows what the lanes hold** (PERF.md section 6, PR 35 and
  PR 47): the engine's ``pack`` phase cuts each running lane's block list
  into rows of ``ROW_BLOCKS`` blocks and lays all lanes' rows end to end
  (``engine.pack_rows``, the dense family's operand); a latent layer's
  read is ONE call of the fused kernel ``ops/pallas/row_attention.py``,
  whose grid is the live rows: it copies a row's blocks out of the
  stacked pool by (layer, block) into fast memory itself and folds a
  lane's rows into one softmax there, so no gathered tile, no score
  tensor and no row's weighted sum is a value of a program, and a call's
  cost follows the live tokens, not ``max_seq_len``.
- **Attention** reads the latent directly, ``kv_b`` absorbed
  (``models/latent_moe.attend_absorbed``'s arithmetic, which stays the
  definition the row read is held to: tests/test_serving_rows.py), in
  all three programs: at a 32-token chunk the up-projection of a whole
  block table costs ~13x the absorbed scores (PERF.md section 6, PR 27).
  Under the absorbed query the pool is ONE shared KV head whose value is
  the first ``kv_lora_rank`` numbers of its key: a round's 5 x 128 query
  rows a lane go through the kernel as one head's, a prefill chunk's
  128 x 128 a query tile at a time.
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

import jax
import jax.numpy as jnp

from ...models.generation import _rms
from ...models.latent_moe import (
    absorb_query, latent_qkv, mlp_block, unabsorb_output,
)
from ...ops.pallas.row_attention import row_attention
from .common import MOE_ACC as ACC  # the accumulator: the expert layers'
from .common import (
    PREFILL_TILE, ROW_TILE, Family, _out, expert_counts, greedy_head,
    write_slots,
)

__all__ = ["LatentMoEFamily"]

LANES = 128  # the TPU's lane tile: the pool's last axis is padded to it

# A row of the latent read is ROW_BLOCKS blocks of one lane: one grid step
# of the kernel (256 slots: 328 KB of cache against a round's 640 query
# rows a lane). Chosen on the chip (PERF.md section 6, PR 47); the kernel's
# grid is the live rows, so ``common.ROW_TILE`` / ``PREFILL_TILE`` only
# round the operand's length.
ROW_BLOCKS = 16


def attend_pool(u, lp, li, pool, rows, pos, blk, off, cfg, rope=True):
    """Latent layer ``li``'s attention on normed ``u`` [b, s, h] against
    the block pool: write each token's cache entry at (``li``, ``blk``,
    ``off``), then attend over the lanes' LIVE ROWS — ONE call of the
    fused kernel, which copies a row's blocks out of the stacked pool by
    (layer, block) itself: under the absorbed query the pool is ONE
    shared KV head as wide as an entry is stored, whose value is the
    entry's first ``kv_lora_rank`` numbers (no value pool), masked
    ``first + slot <= pos``; the scores' scale is the published head's.
    ``W_v`` (``unabsorb_output``) runs once, on the folded sums. Returns
    (att [b, s, heads x v], pool)."""
    W = pool.shape[3]
    q_nope, q_rope, entry = latent_qkv(u, lp, pos, cfg, rope)
    with jax.named_scope("mla/kv_write"):
        pool = pool.at[li, blk, off].set(
            jnp.pad(entry, ((0, 0), (0, 0), (0, W - entry.shape[-1]))))
    with jax.named_scope("mla/attend"):
        qq = absorb_query(q_nope, q_rope, lp, cfg, W)
        o_lat = row_attention(
            qq, pos, rows, pool, None, li, 1,
            (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5,
            dv=cfg.kv_lora_rank)
        return unabsorb_output(o_lat, lp, cfg), pool


def _pool_forward(params, pool, acc, read, ids, pos, wlimit, valid, cfg):
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
                                rows, pos, blk, off, cfg)
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
                   *, cfg):
    """One lane's prefill chunk ``ids`` [1, C] at [start, start + C),
    ``read`` its lane's rows live up to the chunk's end; greedy-samples
    at ``last_idx``. Returns ([token, *acc], pool, acc)."""
    C = ids.shape[1]
    with jax.named_scope("embed"):  # the fed positions
        pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
    with jax.named_scope("embed"):  # ... how far they go, which are real
        fed = jnp.reshape(ctx_len, (1,)), pos < ctx_len
    x, pool, acc = _pool_forward(params, pool, acc, read, ids, pos, *fed,
                                 cfg)
    with jax.named_scope("head"):
        h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1,
                                         keepdims=False)
    return _out(greedy_head(h, params, cfg.rms_norm_eps), acc), pool, acc


def _decode_step(params, pool, acc, read, cur_len, last_tok, *, cfg):
    """Every lane feeds its pending token at ``cur_len`` (write, then
    attend) and greedy-samples the next; idle lanes (``cur_len`` 0, no
    row) write to the null block and count for nothing. Returns ([L
    tokens, *acc], pool, acc)."""
    with jax.named_scope("embed"):  # the fed tokens, where, which are real
        fed = (last_tok[:, None], cur_len[:, None], cur_len + 1,
               (cur_len > 0)[:, None])
    x, pool, acc = _pool_forward(params, pool, acc, read, *fed, cfg)
    with jax.named_scope("head"):
        x = x[:, -1]
    return _out(greedy_head(x, params, cfg.rms_norm_eps), acc), pool, acc


def _verify_step(params, pool, acc, read, cur_len, toks, wlimit, *, cfg):
    """``toks`` [L, k+1]: each lane's pending token and its draft at
    ``cur_len + j``; writes at positions >= ``wlimit[b]`` go to the null
    block. Returns ([L * (k+1) greedy picks row-major, *acc], pool,
    acc)."""
    S = toks.shape[1]
    with jax.named_scope("embed"):
        pos = cur_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        valid = pos < wlimit[:, None]
    x, pool, acc = _pool_forward(params, pool, acc, read, toks, pos,
                                 wlimit, valid, cfg)
    return _out(greedy_head(x, params, cfg.rms_norm_eps), acc), pool, acc


class LatentMoEFamily(Family):
    """See ``families/__init__.py`` for what the engine asks of it."""

    name = "latent_moe"
    title = "the latent-attention family"
    ACC = ACC
    programs = {"prefill": _prefill_chunk, "decode": _decode_step,
                "verify": _verify_step}
    row_read = "kernel"  # the latent layers' live rows: row_attention
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
        rows of ``W`` blocks, the operand's length in whole ``tile``s."""
        return read_form(kind)

    def stats(self):
        itemsize = jnp.dtype(self.gcfg.dtype).itemsize
        return {"latent_kv_bytes_per_token": self._width * itemsize}
