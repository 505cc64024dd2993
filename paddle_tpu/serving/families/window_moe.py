"""The window-attention / full-attention sparse-expert family
(``models/window_moe.py``) as the serving engine sees it: a cache of TWO
kinds by LAYER TYPE.

- **By token, the FULL layers alone**: a K pool ``[full layers, blocks,
  block, kv_heads x head_dim]`` and a V pool ``[.., kv_heads x
  v_head_dim]`` — keys wider than values, so two pools of different last
  axis, the heads merged into it (4 x 192 = 6 and 4 x 128 = 4 lane tiles:
  the pools' own layout is row-major and no call copies them,
  ``families/hybrid_ssm.py`` says what the unmerged form cost) — written by
  (layer, block, offset) with the null-block redirect and read over the
  lanes' live rows (``common.paged_attention``): a call's
  cost follows the live blocks (the fused kernel
  ``ops/pallas/row_attention.py``, which copies a row's blocks out of the
  stacked pools itself). A window layer takes NOTHING in the block pool.
- **By LANE, the WINDOW layers**: a RING of the lane's last ``R``
  positions, one K array ``[lanes, R, swa_kv_heads x head_dim]`` and one V
  array ``[lanes, R, swa_kv_heads x v_head_dim]`` a window layer (one
  array a layer, not one stacked pool: a program reads a whole layer's
  ring as it lies); position ``p`` lives in slot ``p mod R``. As large
  for 16 tokens as for 16,000.

  *What a slot holds is arithmetic, not state*: to a query at position
  ``t`` slot ``s`` holds ``held(t, s) = t - ((t - s) mod R)``, the latest
  position ``<= t`` that falls in it, and the band mask ``0 <= t - held <
  sliding_window`` with ``held >= 0`` decides whether it is seen
  (``models/window_moe.band_mask``). So:

  - **a slot starts empty**: to a request's first chunk (position 0)
    every slot holds a position below 0 and none is seen, whatever the
    lane's predecessor or the request's own life before a preemption left
    there — every position ``0 .. t`` is written, in order, before a query
    at ``t`` reads, so a slot that reads ``held >= 0`` was last written by
    this admission of this request;
  - **a verify round's rejected drafts cost nothing to roll back** (the
    contract of ``ServingEngine._verify_round``): a round writes its fed
    positions ``c .. c + k`` before it attends (write-then-attend, as the
    token pools do). What position ``c + j'`` wrote is, to the query at
    ``c + j`` with ``j < j'`` — a later draft inside the round, or a
    rejected draft left from the round before — slot ``held = c + j' -
    R``, and ``t - held = R - (j' - j) >= R - k``: outside the band as
    long as ``R >= sliding_window + k``. What it OVERWROTE is that same
    position ``c + j' - R``, which no query at ``t >= c`` sees either. A
    masked position (pad tail of a short draft, an idle lane) is not
    written at all. After the round the lane's length rewinds and the
    next accepted write overwrites the slot before the band reaches it:
    a masked position is the identity on what any later read sees. ``R``
    is the model's ``window_ring_len`` or, left to the family,
    ``sliding_window + spec_k + 1`` rounded up to whole 16-row tiles (144
    at a window of 128 and ``k`` 4); less than ``sliding_window + spec_k
    + 1`` raises;
  - **the one-lane prefill chunk** is told its request's lane
    (``lane_state``: the engine gives it ``(rows, wblk, slot [1])``),
    attends to the ring as the chunk BEFORE it left it plus its own keys
    (read, then write: a 128-token chunk overwrites most of a 144-slot
    ring) and then writes the last ``min(C, R)`` of its REAL positions —
    a pad position is never written: junk above a lane's length would lie
    inside the band of the first rounds.
- **No prefix reuse** (``prefix_reuse`` False): a prefix hit hands over
  block-aligned K/V of the full layers, and the window layers would need
  the ring as it stood at that boundary, which nothing keeps (ROADMAP
  B-m2: paged window layers).
- **Weights once**: ``params`` references the model's arrays; each
  program is a Python loop over the layers.
- **Counters** ride on the round's token array (the latent family's way):
  the expert layer's four (``common.MOE_ACC``), then ``WIN_ACC``.

``kv_int8`` and ``int8_weights`` raise ``UnimplementedError``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.errors import UnimplementedError
from ...models import window_moe as M
from ...models.generation import _rms
from .common import (
    MOE_ACC, PREFILL_TILE, ROW_BLOCKS, ROW_TILE, Family, _out, accept, bump,
    expert_counts, greedy_head, paged_attention, rolled_back, write_slots,
)

__all__ = ["WindowMoEFamily"]

RING_TILE = 16  # a ring is whole 16-row tiles of the model dtype
# The prefill call's width (``WindowMoEFamily.prefill_chunk``; the engine
# fits it to whole blocks under ``max_seq_len``, and a width a deployer
# gives wins). A call reads every weight the chip holds whatever its width,
# and on the v5e a weight's 2 FLOP a position meet its 2 bytes at ~240
# positions (197 TFLOP/s over 819 GB/s), so a call's FLOPs meet its bytes
# at ~240 x (weights read / weights a position uses). A dense stack uses
# all it reads: ~240, and the dense call costs +27% at 256 (PERF.md section
# 6, PR 32: 128 for a family that says nothing). Here a call reads every
# HELD expert and a position uses its top-k of ALL the experts — at the
# served cut (MiMo-V2.5, 16 of 256 held, top-8: half a held expert a layer)
# 3.4 G weights read for 0.9 G used: ~900 positions — and the prompts are
# long, so the calls of a prompt, each a read of every weight, are what the
# width buys down. 512, the widest measured (PERF.md section 6, PR 42: the
# sweep 128 / 256 / 512); a call also holds every decoding lane for its
# length (ROADMAP A2), so not wider.
PREFILL_CHUNK = 512

# the device accumulator's slots after the expert layer's: prefill chunks
# that started a lane's rings empty (position 0); drafted positions whose
# ring entries were left above the lane's length; the held experts that
# got at least one assignment, summed over the expert-layer calls of
# decode and verify rounds (``families/linear_latent_moe.py``)
WIN_ACC = ("win_slot_resets", "spec_rolled_back_tokens",
           "moe_round_experts_hit")
ACC = MOE_ACC + WIN_ACC


def read_form(kind):
    """Program ``kind``'s ``(W, tile)`` for the full layers' live rows (the
    kernel's grid is the live rows: ``tile`` only rounds the operand's
    length)."""
    return ROW_BLOCKS, PREFILL_TILE if kind == "prefill" else ROW_TILE


def ring_len(cfg, spec_k):
    """The ring's length for a model's window under ``spec_k`` drafts."""
    need = cfg.sliding_window + spec_k + 1
    return -(-need // RING_TILE) * RING_TILE


def held_positions(t, R):
    """The position each of a ring's ``R`` slots holds to a query at
    position ``t`` [...]: ``[..., R]``, negative where the slot is empty."""
    t = t[..., None]
    return t - (t - jnp.arange(R, dtype=t.dtype)) % R


def _heads(a, g):
    """A ring or a chunk's merged last axis ``[.., g x d]`` as heads."""
    return a.reshape(*a.shape[:-1], g, a.shape[-1] // g)


def ring_round(q, k, v, pos, valid, rk, rv, lp, cfg):
    """A window layer in a decode or verify round: every lane's fed
    positions ``pos`` [L, s] written into its ring (those not ``valid``
    dropped), then each query over its lane's ring under the band mask,
    with the sink. Returns (att [L, s, H x dv], rk, rv)."""
    L, s = pos.shape
    R, g = rk.shape[1], cfg.swa_num_key_value_heads
    with jax.named_scope("attn/window"):
        slot = jnp.where(valid, pos % R, R)  # R: out of bounds, dropped
        lane = jnp.arange(L)[:, None]
        rk = rk.at[lane, slot].set(k.reshape(L, s, -1), mode="drop")
        rv = rv.at[lane, slot].set(v.reshape(L, s, -1), mode="drop")
        vis = M.band_mask(pos[..., None], held_positions(pos, R),
                          cfg.sliding_window)
        return (M.attend(q, _heads(rk, g), _heads(rv, g), vis, lp["sink"]),
                rk, rv)


def ring_chunk(q, k, v, pos, start, n_real, slot, rk, rv, lp, cfg):
    """A window layer in one lane's prefill chunk at ``pos`` [1, C] =
    ``start ..``: the queries over the lane's ring as the positions before
    ``start`` left it, plus the chunk's own keys (a block of queries at a
    time against the keys its band can reach); then the ring takes the
    chunk's last real positions (``n_real`` of the C are real). Returns
    (att [1, C, H x dv], rk, rv)."""
    C, T = pos.shape[1], cfg.sliding_window
    R, g = rk.shape[1], cfg.swa_num_key_value_heads
    with jax.named_scope("attn/window"):
        old_k = jax.lax.dynamic_slice_in_dim(rk, slot, 1)    # [1, R, ..]
        old_v = jax.lax.dynamic_slice_in_dim(rv, slot, 1)
        at = jnp.concatenate([held_positions(start - 1, R), pos[0]])
        keys = jnp.concatenate([_heads(old_k, g), k], 1)[0]  # [R + C, ..]
        vals = jnp.concatenate([_heads(old_v, g), v], 1)[0]
        # queries in blocks of ``w`` positions, block j against the R + w
        # keys that end with its own: a chunk of several whole windows a
        # window at a time — the ring and block 0 for the first; a later
        # block's band starts inside the chunk (R >= T - 1 keys back at
        # most), so scores are [C, R + T], not [C, R + C] —, any other
        # chunk as its one block
        w = T if C > T and C % T == 0 else C

        def blocks(a):  # [R + C, ..] -> [C // w, R + w, ..]
            return jnp.stack([a[j:j + R + w] for j in range(0, C, w)])

        q_at = pos.reshape(-1, w)
        # (pads among the chunk's keys lie after every real query)
        vis = M.band_mask(q_at[..., None], blocks(at)[:, None, :], T)
        att = M.attend(q.reshape(-1, w, *q.shape[2:]), blocks(keys),
                       blocks(vals), vis, lp["sink"]).reshape(1, C, -1)
        # slot s takes the latest real position that falls in it, if any
        takes = held_positions(start + n_real - 1, R)
        idx = jnp.clip(takes - start, 0, C - 1)
        new = takes >= start

        def take(ring, old, fed):
            rows = jnp.where(new[:, None], fed.reshape(C, -1)[idx], old[0])
            return jax.lax.dynamic_update_slice_in_dim(ring, rows[None],
                                                       slot, 0)

        rk, rv = take(rk, old_k, k), take(rv, old_v, v)
    return att, rk, rv


def _stack(params, ids, pos, wlimit, valid, read, kpool, vpool, acc, cfg,
           window):
    """The layer stack over ``ids`` [b, s] at positions ``pos``: full
    layers against the block pool here (``read`` = the engine's live rows
    and the fed positions' blocks), each window layer through ``window(wi,
    q, k, v, lp) -> att`` (the program's own: what it does with the rings
    differs by program). Returns (x, kpool, vpool, acc, the held experts
    hit summed over the expert layers)."""
    eps = cfg.layernorm_epsilon
    scope = jax.named_scope  # the scopes: monitor/scopes.py
    with scope("embed"):
        x = params["embed"][ids].astype(jnp.dtype(cfg.dtype))
    rows, wblk = read
    blk, off = write_slots(wblk, pos, wlimit, kpool.shape[2],
                           "attn/kv_write")
    with scope("acc"):
        n_valid = jnp.sum(valid, dtype=jnp.int32)
        hit = jnp.int32(0)
    wi = ai = 0
    for lp in params["layers"]:
        q, k, v = M.attention_qkv(_rms(x, lp["ln_in"], eps), lp, pos, cfg)
        if M.is_window(lp):
            att = window(wi, q, k, v, lp)
            wi += 1
        else:
            att, kpool, vpool = paged_attention(
                q, k, v, ai, kpool, vpool, rows, pos, blk, off,
                cfg.num_key_value_heads, cfg.head_dim ** -0.5)
            att = att.reshape(*pos.shape, -1)
            ai += 1
        with scope("attn/out"):
            x = x + att @ lp["o"]
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


def _unpack(args, cfg):
    """A program's positional operands after ``params``: (K pool, V pool,
    acc, [a K ring a window layer], [a V ring a window layer], the
    engine's operands)."""
    n = sum(cfg.hybrid_layer_pattern)
    return (*args[:3], list(args[3:3 + n]), list(args[3 + n:3 + 2 * n]),
            args[3 + 2 * n:])


def _prefill_chunk(params, *args, cfg):
    """One request's prefill chunk ``ids`` [1, C] at [start, start + C),
    ``read`` = (its lane's rows live up to the chunk's end, the fed
    positions' blocks, ``slot`` [1]: the lane it holds). The lane's rings
    carry on from the previous chunk; to a chunk at position 0 they are
    empty (module docstring). Greedy-samples at ``last_idx``. Returns
    ([token, *acc], pools...)."""
    kpool, vpool, acc, rks, rvs, ((*read, slot), ids, start, ctx_len,
                                  last_idx) = _unpack(args, cfg)
    C = ids.shape[1]
    with jax.named_scope("embed"):  # the fed positions, which are real
        pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
        real = pos < ctx_len
    with jax.named_scope("attn/window"):
        slot = slot[0]
        n_real = jnp.clip(ctx_len - start, 0, C)

    def window(wi, q, k, v, lp):
        att, rks[wi], rvs[wi] = ring_chunk(q, k, v, pos, start, n_real,
                                           slot, rks[wi], rvs[wi], lp, cfg)
        return att

    x, kpool, vpool, acc, _ = _stack(
        params, ids, pos, jnp.reshape(ctx_len, (1,)), real, read, kpool,
        vpool, acc, cfg, window)
    acc = bump(acc, WIN_ACC, len(MOE_ACC), win_slot_resets=start == 0)
    with jax.named_scope("head"):
        h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1,
                                         keepdims=False)
    picks = greedy_head(h, params, cfg.layernorm_epsilon)
    return _out(picks, acc), kpool, vpool, acc, *rks, *rvs


def _decode_step(params, *args, cfg):
    """Every lane feeds its pending token at ``cur_len``: written (pool or
    ring), then attended. Idle lanes (``cur_len`` 0) write the null block
    and nothing of their rings. Returns ([L tokens, *acc], pools...)."""
    kpool, vpool, acc, rks, rvs, (read, cur_len,
                                  last_tok) = _unpack(args, cfg)
    with jax.named_scope("embed"):  # the fed tokens, where, which are real
        pos = cur_len[:, None]
        live = (cur_len > 0)[:, None]

    def window(wi, q, k, v, lp):
        att, rks[wi], rvs[wi] = ring_round(q, k, v, pos, live, rks[wi],
                                           rvs[wi], lp, cfg)
        return att

    x, kpool, vpool, acc, n_hit = _stack(
        params, last_tok[:, None], pos, cur_len + 1, live, read, kpool,
        vpool, acc, cfg, window)
    acc = bump(acc, WIN_ACC, len(MOE_ACC), moe_round_experts_hit=n_hit)
    with jax.named_scope("head"):
        x = x[:, -1]
    picks = greedy_head(x, params, cfg.layernorm_epsilon)
    return _out(picks, acc), kpool, vpool, acc, *rks, *rvs


def _verify_step(params, *args, cfg):
    """``toks`` [L, k+1]: each lane's pending token and its draft at
    ``cur_len + j``; positions >= ``wlimit[b]`` are pad: written nowhere.
    Rejected drafts need no undoing (module docstring); the program
    counts them. Returns ([L * (k+1) picks row-major, *acc], pools...)."""
    kpool, vpool, acc, rks, rvs, (read, cur_len, toks,
                                  wlimit) = _unpack(args, cfg)
    S1 = toks.shape[1]
    with jax.named_scope("embed"):
        pos = cur_len[:, None] + jnp.arange(S1, dtype=jnp.int32)[None, :]
        valid = pos < wlimit[:, None]

    def window(wi, q, k, v, lp):
        att, rks[wi], rvs[wi] = ring_round(q, k, v, pos, valid, rks[wi],
                                           rvs[wi], lp, cfg)
        return att

    x, kpool, vpool, acc, n_hit = _stack(
        params, toks, pos, wlimit, valid, read, kpool, vpool, acc, cfg,
        window)
    picks = greedy_head(x, params, cfg.layernorm_epsilon)
    counted = accept(picks, toks, cur_len, wlimit)  # to count by
    with jax.named_scope("spec"):
        rolled = rolled_back(*counted)
    acc = bump(acc, WIN_ACC, len(MOE_ACC), moe_round_experts_hit=n_hit,
               spec_rolled_back_tokens=rolled)
    return _out(picks, acc), kpool, vpool, acc, *rks, *rvs


class WindowMoEFamily(Family):
    """See ``families/__init__.py`` for what the engine asks of it."""

    name = "window_moe"
    title = "the window-attention family"
    ACC = ACC
    programs = {"prefill": _prefill_chunk, "decode": _decode_step,
                "verify": _verify_step}
    prefill_chunk = PREFILL_CHUNK
    lane_state = True
    prefix_reuse = False
    row_read = "kernel"  # the full layers' live rows: row_attention
    prefix_reuse_why = (
        "a prefix hit hands over block-aligned K/V of the full-attention "
        "layers, and this family's window layers would need their ring "
        "of last keys as it stood at that boundary, which nothing keeps "
        "(ROADMAP B-m2)")

    def __init__(self, model, config):
        self.refuse(config, {
            "kv_int8": "the int8 scale pools pair with [.., kv_heads, "
            "head_dim] pools of one width, and the rings have none"})
        super().__init__(model, config)
        c = model.config
        self.n_window = sum(c.hybrid_layer_pattern)
        self.n_full = c.num_hidden_layers - self.n_window
        if not self.n_full:
            raise UnimplementedError(
                "a stack with no full-attention layer has no block pool: "
                "the engine's block pool would manage nothing")
        spec_k = config.spec_k if config.spec else 0
        self.ring = getattr(c, "window_ring_len", None) \
            or ring_len(c, spec_k)
        if self.ring < c.sliding_window + spec_k + 1:
            raise ValueError(
                f"a ring of {self.ring} slots under a window of "
                f"{c.sliding_window} and {spec_k} drafts a round: a "
                f"rejected draft would be seen; it takes "
                f"sliding_window + spec_k + 1 = "
                f"{c.sliding_window + spec_k + 1}")
        self.donate_argnums = tuple(range(1, 4 + 2 * self.n_window))

    def make_pools(self, num_blocks, block_size):
        """(K pool and V pool by (full layer, block, offset), the
        counters' device accumulator, then a K ring a window layer and a
        V ring a window layer, by LANE)."""
        g = self.gcfg
        dt = jnp.dtype(g.dtype)
        full, swa = g.num_key_value_heads, g.swa_num_key_value_heads
        return (jnp.zeros((self.n_full, num_blocks, block_size,
                           full * g.head_dim), dt),
                jnp.zeros((self.n_full, num_blocks, block_size,
                           full * g.v_head_dim), dt),
                jnp.zeros((len(ACC),), jnp.int32),
                *(jnp.zeros((self.lanes, self.ring, swa * g.head_dim), dt)
                  for _ in range(self.n_window)),
                *(jnp.zeros((self.lanes, self.ring, swa * g.v_head_dim), dt)
                  for _ in range(self.n_window)))

    def kv_pool_bytes(self, pools):
        return int(pools[0].nbytes + pools[1].nbytes)

    def lane_pool_bytes(self, pools):
        return int(sum(p.nbytes for p in pools[3:]))

    def read_form(self, kind):
        """The full layers read their lanes' live rows: ``(W, tile)``;
        ``lane_state`` adds the request's lane to the prefill chunk's
        operand."""
        return read_form(kind)

    def stats(self):
        g = self.gcfg
        item = jnp.dtype(g.dtype).itemsize
        wide = g.head_dim + g.v_head_dim
        return {"win_ring_len": self.ring,
                "win_ring_bytes_per_lane": self.n_window * self.ring
                * g.swa_num_key_value_heads * wide * item,
                "full_kv_bytes_per_token": self.n_full
                * g.num_key_value_heads * wide * item,
                "prefix_reuse_why": self.prefix_reuse_why}
