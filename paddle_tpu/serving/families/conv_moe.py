"""The short-convolution / grouped-query sparse-expert family
(``models/conv_moe.py``) as the serving engine sees it: a cache of two
kinds by LAYER TYPE, of which the second is nearly nothing.

- **By token, the ATTENTION layers alone**: a K pool and a V pool
  ``[attention layers, blocks, block, kv_heads x head_dim]`` (8 x 64 = 512:
  4 lane tiles, the heads merged into the last axis so that the pools' own
  layout is row-major and no call copies them — ``families/hybrid_ssm.py``
  says what the unmerged form cost), written by (layer, block, offset) with
  the null-block redirect and read over the lanes' live rows
  (``common.paged_attention``: the fused kernel
  ``ops/pallas/row_attention.py``), the q/k head norms
  and the rotary embedding in front of it. A conv layer takes NOTHING in
  the block pool.
- **By LANE, the CONV layers**: ONE pool ``[conv layers, lanes, (L - 1) x
  hidden]`` in the model's dtype — a lane's TAIL, the last ``L - 1`` rows
  of ``g = B * z`` a conv layer (a lane's rows side by side, as the hybrid
  family's conv pool). That is the whole of a lane's state: no recurrent
  state, no slab, no deferred commit, no kernel — at the published sizes 2
  x 2048 numbers a layer, 8 KB, where the hybrid state-space family keeps
  2 MB a layer.

  - **a prefill chunk** is told its request's lane (``lane_state``: the
    engine gives it ``(rows, wblk, slot [1])``), starts from a ZERO tail
    where it is its request's first (``start`` 0: an admitted or
    re-admitted request never sees its lane's predecessor), convolves
    ``[tail | C positions]`` and writes the last ``L - 1`` rows OF ITS REAL
    TOKENS: rows ``n_real .. n_real + L - 2`` of that window, so a padded
    last chunk writes no pad row, and a chunk of one real token keeps one
    row of the tail it was handed;
  - **a plain round** convolves ``[tail | 1]`` and shifts the tail by one
    row. An idle lane's tail takes what nobody reads;
  - **a verify round's rejected drafts leave no trace** (the contract of
    ``ServingEngine._verify_round``): the program convolves ``[tail | k+1
    positions]`` and KEEPS each conv layer's window until the head has
    given the lane's ``n_keep`` — its pending token and the longest prefix
    of its draft that equals the program's own picks (the engine's
    ``_accept`` rule) — then sets the tail to rows ``n_keep .. n_keep + L -
    2`` of the window: the rows that end at the last kept position. A
    rejected position's ``g`` is in no tail; an idle lane (``n_keep`` 0)
    gets its own tail back (``common._keeps`` / ``_take_rows`` /
    ``_carried``: the rules every ``lane_state`` family's tail follows).
- **No prefix reuse** (``prefix_reuse`` False): a prefix hit hands over
  block-aligned K/V of the attention layers, and the conv layers would need
  their tails as they stood at that boundary, which nothing keeps. It is
  the cheapest snapshot of any ``lane_state`` family: one tail a conv
  layer, 8 KB, at a block boundary (ROADMAP B-m4).
- **Weights once**: ``params`` references the model's arrays; each
  program is a Python loop over the layers. The head is the embedding.
- **Counters** ride on the round's token array (the latent family's way):
  the expert layer's four (``common.MOE_ACC``), then ``CONV_ACC``.

``kv_int8`` and ``int8_weights`` raise ``UnimplementedError``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.errors import UnimplementedError
from ...models import conv_moe as M
from ...models.generation import _rms
from .common import (
    MOE_ACC, PREFILL_TILE, ROW_BLOCKS, ROW_TILE, Family, _carried, _keeps,
    _out, _take_rows, accept, bump, expert_counts, greedy_head, lane_tails,
    paged_attention, rolled_back, write_slots,
)

__all__ = ["ConvMoEFamily"]

# the device accumulator's slots after the expert layer's: prefill chunks
# that started a lane's tails from zero (position 0); drafted positions
# whose ``g`` entered no tail; the held experts that got at least one
# assignment, summed over the expert-layer calls of decode and verify
# rounds (``families/linear_latent_moe.py``)
CONV_ACC = ("conv_slot_resets", "spec_rolled_back_tokens",
            "moe_round_experts_hit")
ACC = MOE_ACC + CONV_ACC
N_POOLS = 4  # K pool, V pool, the accumulator, the tails
# The prefill call's width (``ConvMoEFamily.prefill_chunk``; the engine
# fits it to whole blocks under ``max_seq_len``, and a width a deployer
# gives wins): ``families/window_moe.PREFILL_CHUNK``'s arithmetic — a
# call's FLOPs meet its bytes at ~240 positions x (weights read / weights
# a position uses). This family holds EVERY expert and a position uses its
# top-k: at the served cut (LFM2-24B-A2B, top-4 of 64) a call reads 5.2 G
# weights for the 0.5 G a position uses, FLOPs under bytes until ~2,300
# positions, and a 128-position call was 16 ms of which 14.5 read experts
# for 8 rows each. 512, the widest measured (PERF.md section 6, PR 42: the
# sweep 128 / 256 / 512); not wider: a call holds every decoding lane for
# its length (ROADMAP A2).
PREFILL_CHUNK = 512


def _attention(u, lp, ai, kpool, vpool, rows, pos, blk, off, cfg):
    """An attention layer against the block pool (``paged_attention``).
    Returns (out [b, s, hidden], kpool, vpool)."""
    att, kpool, vpool = paged_attention(
        *M.attention_qkv(u, lp, pos, cfg), ai, kpool, vpool, rows, pos, blk,
        off, cfg.num_key_value_heads, cfg.head_dim ** -0.5)
    with jax.named_scope("attn/out"):
        return att.reshape(*pos.shape, -1) @ lp["o"], kpool, vpool


def _stack(params, ids, pos, wlimit, valid, read, kpool, vpool, acc, cfg,
           conv):
    """The layer stack over ``ids`` [b, s] at positions ``pos``: attention
    layers against the block pool here (``read`` = the engine's live rows
    and the fed positions' blocks), each conv layer through ``conv(ci, g)
    -> window`` (the program's own: where the tail comes from and what
    becomes of the window differs by program). Returns (x, kpool, vpool,
    acc, the held experts hit summed over the expert layers)."""
    eps = cfg.norm_eps
    scope = jax.named_scope  # the scopes: monitor/scopes.py
    with scope("embed"):
        x = params["embed"][ids].astype(jnp.dtype(cfg.dtype))
    rows, wblk = read
    blk, off = write_slots(wblk, pos, wlimit, kpool.shape[2],
                           "attn/kv_write")
    with scope("acc"):
        n_valid = jnp.sum(valid, dtype=jnp.int32)
        hit = jnp.int32(0)
    ci = ai = 0
    for lp in params["layers"]:
        u = _rms(x, lp["ln_in"], eps)
        if M.is_conv(lp):
            g, gate = M.sconv_project(u, lp)
            mix = M.sconv_gate_out(gate, M.sconv_conv(conv(ci, g), lp), lp)
            ci += 1
            out = "sconv/out_proj"
        else:
            mix, kpool, vpool = _attention(u, lp, ai, kpool, vpool, rows,
                                           pos, blk, off, cfg)
            ai += 1
            out = "attn/out"
        with scope(out):  # a residual add: its producer's scope
            x = x + mix
        y, counts = M.ffn_block(_rms(x, lp["ln_post"], eps), lp, cfg,
                                valid=valid)
        with scope("mlp" if counts is None else "moe/combine"):
            x = x + y
        if counts is not None:
            with scope("acc"):
                acc = acc.at[:len(MOE_ACC)].add(expert_counts(
                    n_valid, counts, cfg.num_experts_per_tok))
                hit = hit + jnp.sum(counts > 0, dtype=jnp.int32)
    return x, kpool, vpool, acc, hit


def _prefill_chunk(params, kpool, vpool, acc, tpool, read, ids, start,
                   ctx_len, last_idx, *, cfg):
    """One request's prefill chunk ``ids`` [1, C] at [start, start + C),
    ``read`` = (its lane's rows live up to the chunk's end, the fed
    positions' blocks, ``slot`` [1]: the lane it holds). The lane's tails
    carry on from the previous chunk, or from ZERO where ``start`` is 0,
    and take the last rows of the chunk's REAL positions (module
    docstring). Greedy-samples at ``last_idx``. Returns ([token, *acc],
    pools...)."""
    *read, slot = read
    C, K1 = ids.shape[1], cfg.conv_L_cache - 1
    with jax.named_scope("embed"):  # the fed positions, which are real
        pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
        real = pos < ctx_len
    with jax.named_scope("sconv/conv"):
        slot = slot[0]
        fresh = start == 0
        n_real = jnp.clip(ctx_len - start, 0, C)
    tails = [tpool]

    def conv(ci, g):
        with jax.named_scope("sconv/conv"):
            at = (ci, slot, 0)
            tail = _carried(fresh, jax.lax.dynamic_slice(
                tails[0], at, (1, 1, tpool.shape[2]))[0])
            window = jnp.concatenate([tail.reshape(1, K1, -1), g], axis=1)
            tails[0] = jax.lax.dynamic_update_slice(
                tails[0], _take_rows(window, n_real[None], K1).reshape(
                    1, 1, -1), at)
        return window

    x, kpool, vpool, acc, _ = _stack(
        params, ids, pos, jnp.reshape(ctx_len, (1,)), real, read, kpool,
        vpool, acc, cfg, conv)
    acc = bump(acc, CONV_ACC, len(MOE_ACC), conv_slot_resets=fresh)
    with jax.named_scope("head"):
        h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1,
                                         keepdims=False)
    picks = greedy_head(h, params, cfg.norm_eps)
    return _out(picks, acc), kpool, vpool, acc, tails[0]


def _decode_step(params, kpool, vpool, acc, tpool, read, cur_len, last_tok,
                 *, cfg):
    """Every lane feeds its pending token at ``cur_len``: K/V written then
    attended, each conv layer over ``[tail | 1]`` and its tail shifted by
    one row. Idle lanes (``cur_len`` 0) write K/V to the null block; their
    tails hold nothing anyone reads (a slot starts from zero at its next
    request's first chunk). Returns ([L tokens, *acc], pools...)."""
    with jax.named_scope("embed"):  # the fed tokens, where, which are real
        pos = cur_len[:, None]
        live = (cur_len > 0)[:, None]
    tails = [tpool]

    def conv(ci, g):
        with jax.named_scope("sconv/conv"):
            window = jnp.concatenate(
                [lane_tails(tails[0], ci, cfg.conv_L_cache), g], axis=1)
            tails[0] = tails[0].at[ci].set(
                window[:, 1:].reshape(window.shape[0], -1))
        return window

    x, kpool, vpool, acc, n_hit = _stack(
        params, last_tok[:, None], pos, cur_len + 1, live, read, kpool,
        vpool, acc, cfg, conv)
    acc = bump(acc, CONV_ACC, len(MOE_ACC), moe_round_experts_hit=n_hit)
    with jax.named_scope("head"):
        x = x[:, -1]
    picks = greedy_head(x, params, cfg.norm_eps)
    return _out(picks, acc), kpool, vpool, acc, tails[0]


def _verify_step(params, kpool, vpool, acc, tpool, read, cur_len, toks,
                 wlimit, *, cfg):
    """``toks`` [L, k+1]: each lane's pending token and its draft at
    ``cur_len + j``; positions >= ``wlimit[b]`` are pad. Each conv layer's
    window ``[tail | k+1 positions]`` is held until the head has given the
    lane's ``n_keep``; the tail then takes the rows that end at the last
    kept position, nothing of a rejected one (module docstring). Returns
    ([L * (k+1) picks row-major, *acc], pools...)."""
    S1, K1 = toks.shape[1], cfg.conv_L_cache - 1
    with jax.named_scope("embed"):
        pos = cur_len[:, None] + jnp.arange(S1, dtype=jnp.int32)[None, :]
        valid = pos < wlimit[:, None]
    windows = []  # per conv layer: the round's window

    def conv(ci, g):
        with jax.named_scope("sconv/conv"):
            windows.append(jnp.concatenate(
                [lane_tails(tpool, ci, cfg.conv_L_cache), g], axis=1))
        return windows[-1]

    x, kpool, vpool, acc, n_hit = _stack(
        params, toks, pos, wlimit, valid, read, kpool, vpool, acc, cfg, conv)
    picks = greedy_head(x, params, cfg.norm_eps)
    live, n_draft, accepted = accept(picks, toks, cur_len, wlimit)
    with jax.named_scope("spec"):
        n_keep = _keeps(live, accepted)
        rolled = rolled_back(live, n_draft, accepted)
    with jax.named_scope("sconv/conv"):
        for ci, window in enumerate(windows):
            tpool = tpool.at[ci].set(_take_rows(window, n_keep, K1).reshape(
                window.shape[0], -1))
    acc = bump(acc, CONV_ACC, len(MOE_ACC), moe_round_experts_hit=n_hit,
               spec_rolled_back_tokens=rolled)
    return _out(picks, acc), kpool, vpool, acc, tpool


class ConvMoEFamily(Family):
    """See ``families/__init__.py`` for what the engine asks of it."""

    name = "conv_moe"
    title = "the short-convolution family"
    ACC = ACC
    programs = {"prefill": _prefill_chunk, "decode": _decode_step,
                "verify": _verify_step}
    prefill_chunk = PREFILL_CHUNK
    lane_state = True
    prefix_reuse = False
    row_read = "kernel"  # the attention layers' live rows: row_attention
    prefix_reuse_why = (
        "a prefix hit hands over block-aligned K/V of the attention "
        "layers, and this family's conv layers would need their tail (the "
        "last conv_L_cache - 1 rows of the convolution's input) as it "
        "stood at that boundary, which nothing keeps: one tail a conv "
        "layer a boundary, the cheapest snapshot of any lane_state family "
        "(ROADMAP B-m4)")

    def __init__(self, model, config):
        self.refuse(config, {
            "kv_int8": "the int8 scale pools pair with [.., kv_heads, "
            "head_dim] pools, and these merge the heads into the last "
            "axis"})
        super().__init__(model, config)
        c = model.config
        self.n_conv = sum(k == M.CONV for k in c.layer_types)
        self.n_attn = c.num_hidden_layers - self.n_conv
        if not self.n_attn:
            raise UnimplementedError(
                "a stack with no attention layer has no K/V pool: the "
                "engine's block pool would manage nothing")
        self.donate_argnums = tuple(range(1, 1 + N_POOLS))

    def make_pools(self, num_blocks, block_size):
        """(K pool, V pool by (attention layer, block, offset), the
        counters' device accumulator, the tails by (conv layer, LANE))."""
        g = self.gcfg
        dt = jnp.dtype(g.dtype)
        kpool = jnp.zeros((self.n_attn, num_blocks, block_size,
                           g.num_key_value_heads * g.head_dim), dt)
        return (kpool, jnp.zeros_like(kpool),
                jnp.zeros((len(ACC),), jnp.int32),
                jnp.zeros((self.n_conv, self.lanes,
                           (g.conv_L_cache - 1) * g.hidden_size), dt))

    def kv_pool_bytes(self, pools):
        return int(pools[0].nbytes + pools[1].nbytes)

    def lane_pool_bytes(self, pools):
        return int(pools[3].nbytes)

    def read_form(self, kind):
        """The paged layer's live rows ``(W, tile)`` (the kernel's grid is
        the live rows: ``tile`` only rounds the operand's length);
        ``lane_state`` adds the request's lane to the prefill chunk's."""
        return ROW_BLOCKS, PREFILL_TILE if kind == "prefill" else ROW_TILE

    def stats(self):
        g = self.gcfg
        item = jnp.dtype(g.dtype).itemsize
        return {"conv_tail_bytes_per_lane": self.n_conv
                * (g.conv_L_cache - 1) * g.hidden_size * item,
                "kv_bytes_per_token": self.n_attn * g.num_key_value_heads
                * 2 * g.head_dim * item,
                "prefix_reuse_why": self.prefix_reuse_why}
