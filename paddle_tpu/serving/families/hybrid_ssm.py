"""The hybrid state-space / attention family (``models/hybrid_ssm.py``) as
the serving engine sees it: TWO kinds of cache in one family.

- **By token**: a K pool and a V pool ``[attention layers, blocks, block,
  kv_heads x head_dim]`` for the few attention layers, written by
  (layer, block, offset) with the null-block redirect and read by the
  dense family's live-rows read (``dense_gqa._attend_rows``; no rotary
  embedding here, and the model's score multiplier goes onto the query,
  since that read divides by ``sqrt(head_dim)`` itself). The dense
  family's pools end in ``[kv_heads, head_dim]``; with a head of 64, half
  a 128-lane tile, the TPU compiler lays such a pool out blocks-minor and
  every program call then copied both pools in and out (4 x 285 MB; the
  latent family's finding, PERF.md section 6, PR 27). With the heads
  merged into the last axis (512 = 4 lane tiles) the pool's own layout is
  row-major and no call copies it.
- **By LANE**: the recurrent state, ``[lanes, heads, d_head, d_state]``
  float32 a state-space layer, and a conv pool ``[state-space layers,
  lanes, (d_conv - 1) x conv channels]`` (a lane's rows side by side: 3
  rows would be padded to a whole sublane tile): what a request's state-space
  layers keep of everything it has read — as large for 16 tokens as for
  16,000. The state is ONE ARRAY A LAYER, not one stacked pool: on a
  stacked ``[layers, lanes, ...]`` pool the TPU compiler makes the
  update an in-place dynamic-update-slice fusion and reads the layer's
  state a second time for the layer's output (3 x 134 MB a layer a
  round), where an array of its own is read once and written once by one
  fusion with two results, the new state and ``S C`` (compiled for a
  described v5e: PERF.md section 6, PR 31; ``tests/test_chip_compile.py``
  holds it). Decode and verify index both by lane; the one-lane prefill chunk is told its
  request's lane (``lane_state``: the engine appends ``slot [1]`` to its
  read operand) and a chunk that starts at position 0 starts from ZERO
  state and tail, so an admitted or re-admitted request never sees its
  lane's predecessor.
- **A verify round's rejected drafts leave no trace in the state.** K/V
  written above a lane's valid length is masked out of every later read,
  but a recurrence advanced over five positions has the rejected ones
  folded in, and five copies of 75 MB a lane do not exist. So the verify
  program reads each layer's state ONCE (the chunked form over the k+1
  positions) and writes none of it; after the head it computes each
  lane's acceptance itself — the longest prefix of its draft equal to the
  program's own picks: the engine's ``_accept`` rule, which stays the
  judge of what is emitted — and only then applies the update, position
  by position, with ``dt`` set to 0 from the first rejected position on:
  ``exp(0 A) = 1`` and ``0 x (outer) B = 0``, a masked position is the
  identity on the state, bit for bit. What it keeps between the two
  passes is each layer's conv window and ``dt`` of the k+1 positions (a
  few KB a lane a layer); the conv tail becomes the window's rows that
  end at the last kept position. A verify round therefore reads the
  state twice and writes it once (``ssm_state_passes`` counts 2), a plain
  round once each (1).
- **No prefix reuse** (``prefix_reuse`` False): the prefix index hands a
  new request block-aligned K/V of another request's prompt, and without
  the recurrent state at that boundary the hit is unusable; the engine
  then keeps the scheduler from acquiring any (``cached_len`` 0). State
  snapshots at block boundaries are ROADMAP B-m4.
- **Weights once**: ``params`` references the model's arrays; each
  program is a Python loop over the layers with two bodies.
- **Counters** ride on the round's token array (the latent family's
  way): ``ACC`` below, threaded through the programs like a pool.

``kv_int8`` and ``int8_weights`` raise ``UnimplementedError``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...models import hybrid_ssm as M
from ...models.generation import _rms
from . import absorb_accumulator
from .dense_gqa import PREFILL_TILE, ROW_BLOCKS, ROW_TILE, _attend_rows

__all__ = ["HybridSSMFamily"]

F32 = jnp.float32

# the device accumulator's slots: times a round's program went through
# the lanes' state (1 a plain round: read and written; 2 a verify round:
# read, then read and written); live lanes summed over rounds; live lanes
# x the times their state was read or written (2 a plain round, 3 a verify
# round); prefill chunks that started a slot from zero; drafted positions
# whose state update was discarded
ACC = ("ssm_state_passes", "ssm_lane_rounds", "ssm_state_lane_moves",
       "ssm_slot_resets", "spec_rolled_back_tokens")


def _bump(acc, **by):
    with jax.named_scope("acc"):
        return acc + jnp.stack([jnp.asarray(by.get(n, 0), jnp.int32)
                                for n in ACC])


def _attention(u, lp, ai, kpool, vpool, read, pos, blk, off, cfg, tile):
    """An attention layer's mixer against the block pool: write the fed
    tokens' K/V by (layer, block, offset), then the dense family's
    live-rows read. Returns (out [b, s, hidden], kpool, vpool)."""
    b, s, _ = u.shape
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    B = kpool.shape[2]
    q, k, v = M.attention_qkv(u, lp, cfg)
    with jax.named_scope("attn/kv_write"):
        kpool = kpool.at[ai, blk, off].set(k.reshape(b, s, nkv * d))
        vpool = vpool.at[ai, blk, off].set(v.reshape(b, s, nkv * d))

    def gather(blocks):  # dense_gqa._pool_forward's bf16 form
        T, W = blocks.shape
        at = blocks + ai * kpool.shape[1]
        return tuple(c.reshape(-1, B, nkv * d)[at].reshape(
            T, W * B, nkv, d) for c in (kpool, vpool))

    with jax.named_scope("attn/rows"):
        # the read divides by sqrt(d); the model's multiplier is stated
        out = _attend_rows(
            q.astype(F32) * (cfg.attention_multiplier * np.sqrt(d)),
            pos, read[0], gather, tile, nkv)
    with jax.named_scope("attn/out"):
        return (out.reshape(b, s, nh * d).astype(u.dtype) @ lp["o"], kpool,
                vpool)


def _carried(fresh, kept):
    """What a prefill chunk starts from: what the slot kept, or zero
    where the chunk is its request's first."""
    return jnp.where(fresh, 0, kept)


def _keeps(live, accepted):
    """How many of a verify round's positions a lane's state and conv
    tail take up: its pending token and its accepted drafts; none where
    the lane is idle."""
    return jnp.where(live, 1 + accepted, 0)


def _tail(cpool, si, cfg):
    """Layer ``si``'s conv tails as ``[lanes, d_conv - 1, channels]``."""
    return cpool[si].reshape(cpool.shape[1], cfg.mamba_d_conv - 1, -1)


def _take_rows(window, first, n):
    """``window[b, first[b] : first[b] + n]`` for every row ``b``."""
    idx = first[:, None] + jnp.arange(n)[None, :]
    return jnp.take_along_axis(window, idx[:, :, None], axis=1)


def _stack(params, ids, pos, wlimit, read, kpool, vpool, cfg, tile, ssm):
    """The layer stack over ``ids`` [b, s] at positions ``pos``: attention
    layers against the block pool here, each state-space layer through
    ``ssm(si, u, lp) -> mix`` (the program's own: what it does with the
    lane-indexed pools differs by program). Returns (x, kpool, vpool)."""
    B = kpool.shape[2]
    eps, rm = cfg.rms_norm_eps, cfg.residual_multiplier
    dt = jnp.dtype(cfg.dtype)
    scope = jax.named_scope  # the scopes: monitor/scopes.py
    with scope("embed"):
        x = (params["embed"][ids] * cfg.embedding_multiplier).astype(dt)
    with scope("attn/kv_write"):
        ok = pos < wlimit[:, None]
        blk = jnp.where(ok, read[1], 0)
        off = jnp.where(ok, pos % B, 0)
    si = ai = 0
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        u = _rms(x, lp["ln_in"], eps)
        if kind == M.SSM:
            mix = ssm(si, u, lp)
            si += 1
            out = "ssm/out_proj"
        else:
            mix, kpool, vpool = _attention(u, lp, ai, kpool, vpool, read,
                                           pos, blk, off, cfg, tile)
            ai += 1
            out = "attn/out"
        with scope(out):  # a residual add: its producer's scope
            x = x + (rm * mix).astype(dt)
        y = M.mlp(_rms(x, lp["ln_post"], eps), lp)
        with scope("mlp"):
            x = x + (rm * y).astype(dt)
    return x, kpool, vpool


def _picks(x, params, cfg):
    with jax.named_scope("head"):
        x = _rms(x, params["norm"], cfg.rms_norm_eps)
        logits = (x @ params["embed"].T).astype(F32) / cfg.logits_scaling
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _out(picks, acc):
    """A program's fetched vector: its picks, then the accumulator."""
    with jax.named_scope("acc"):
        return jnp.concatenate([picks.reshape(-1), acc])


def _unpack(args, cfg):
    """A program's positional operands after ``params``: (kpool, vpool,
    cpool, acc, [one state array a state-space layer], the engine's
    operands)."""
    n = sum(k == M.SSM for k in cfg.layer_types)
    return (*args[:4], list(args[4:4 + n]), args[4 + n:])


def _prefill_chunk(params, *args, cfg, tile):
    """One request's prefill chunk ``ids`` [1, C] at [start, start + C),
    ``read`` = (its lane's live rows, write blocks, ``slot`` [1]: the
    lane it holds). The slot's state and conv tail carry on from the
    previous chunk, or from ZERO where ``start`` is 0; pad positions (>=
    ``ctx_len``) are the identity on both. Greedy-samples at
    ``last_idx``. Returns ([token, *acc], pools...)."""
    kpool, vpool, cpool, acc, states, (read, ids, start, ctx_len,
                                       last_idx) = _unpack(args, cfg)
    C, K1 = ids.shape[1], cfg.mamba_d_conv - 1
    with jax.named_scope("embed"):  # the fed positions
        pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
    with jax.named_scope("ssm/state_update"):
        slot = read[2][0]
        fresh = start == 0
        n_real = jnp.clip(ctx_len - start, 0, C)
    conv = [cpool]

    def ssm(si, u, lp):
        z, xBC, dt_raw = M.ssm_project(u, lp, cfg)
        with jax.named_scope("ssm/state_update"):
            S0 = _carried(fresh, jax.lax.dynamic_slice_in_dim(
                states[si], slot, 1))
            tail = _carried(fresh, jax.lax.dynamic_slice(
                conv[0], (si, slot, 0), (1, 1, cpool.shape[2]))[0]
            ).reshape(1, K1, -1)
        with jax.named_scope("ssm/conv"):
            window = jnp.concatenate([tail, xBC], axis=1)
        x, Bm, Cm, dt, A = M.ssm_inputs(M.ssm_conv(window, lp, cfg),
                                        dt_raw, lp, cfg)
        with jax.named_scope("ssm/state_update"):
            dt = jnp.where((pos < ctx_len)[..., None], dt, 0.0)
            y, S = M.ssm_scan(x, Bm, Cm, dt, A, S0, cfg.mamba_chunk_size)
            y = y + lp["D"].astype(F32)[:, None] * x
            states[si] = jax.lax.dynamic_update_slice_in_dim(
                states[si], S, slot, 0)
            conv[0] = jax.lax.dynamic_update_slice(
                conv[0], _take_rows(window, n_real[None], K1).reshape(
                    1, 1, -1), (si, slot, 0))
        return M.ssm_gate_out(y, z, lp, cfg)

    x, kpool, vpool = _stack(params, ids, pos, jnp.reshape(ctx_len, (1,)),
                             read, kpool, vpool, cfg, tile, ssm)
    acc = _bump(acc, ssm_slot_resets=fresh)
    with jax.named_scope("head"):
        h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1,
                                         keepdims=False)
    return (_out(_picks(h, params, cfg), acc), kpool, vpool, conv[0], acc,
            *states)


def _decode_step(params, *args, cfg, tile):
    """Every lane feeds its pending token at ``cur_len``: K/V written
    then attended, each lane's state advanced one position and its conv
    tail shifted by one row, in place. Idle lanes (``cur_len`` 0) write
    K/V to the null block; their slots hold nothing anyone reads (a slot
    starts from zero at its next request's first chunk). Returns ([L
    tokens, *acc], pools...)."""
    kpool, vpool, cpool, acc, states, (read, cur_len,
                                       last_tok) = _unpack(args, cfg)
    conv = [cpool]

    def ssm(si, u, lp):
        z, xBC, dt_raw = M.ssm_project(u, lp, cfg)
        with jax.named_scope("ssm/conv"):
            window = jnp.concatenate([_tail(conv[0], si, cfg), xBC],
                                     axis=1)
        x, Bm, Cm, dt, A = M.ssm_inputs(M.ssm_conv(window, lp, cfg),
                                        dt_raw, lp, cfg)
        with jax.named_scope("ssm/state_update"):
            S = states[si] = M.ssm_step(states[si], x[:, 0], Bm[:, 0],
                                        dt[:, 0], A)
            y = M.ssm_read(S, Cm[:, 0]) + lp["D"].astype(F32)[:, None] \
                * x[:, 0]
            conv[0] = conv[0].at[si].set(
                window[:, 1:].reshape(window.shape[0], -1))
        return M.ssm_gate_out(y[:, None], z, lp, cfg)

    with jax.named_scope("embed"):  # the fed tokens and where
        fed = last_tok[:, None], cur_len[:, None], cur_len + 1
    x, kpool, vpool = _stack(params, *fed, read, kpool, vpool, cfg, tile,
                             ssm)
    with jax.named_scope("acc"):
        live = jnp.sum(cur_len > 0)
        by = dict(ssm_lane_rounds=live, ssm_state_lane_moves=2 * live)
    acc = _bump(acc, ssm_state_passes=1, **by)
    with jax.named_scope("head"):
        x = x[:, -1]
    return (_out(_picks(x, params, cfg), acc), kpool, vpool, conv[0], acc,
            *states)


def _verify_step(params, *args, cfg, tile):
    """``toks`` [L, k+1]: each lane's pending token and its draft at
    ``cur_len + j``; positions >= ``wlimit[b]`` are pad. The forward
    reads every state-space layer's state once and writes none; after
    the head the lane's acceptance ``a`` (module docstring) decides what
    the state and the conv tail take: the pending token and the first
    ``a`` drafts, nothing else. Returns ([L * (k+1) picks row-major,
    *acc], pools...)."""
    kpool, vpool, cpool, acc, states, (read, cur_len, toks,
                                       wlimit) = _unpack(args, cfg)
    L, S1 = toks.shape
    K1 = cfg.mamba_d_conv - 1
    with jax.named_scope("embed"):
        pos = cur_len[:, None] + jnp.arange(S1, dtype=jnp.int32)[None, :]
    kept = []  # per state-space layer: (conv window, dt_raw) of the round

    def ssm(si, u, lp):
        z, xBC, dt_raw = M.ssm_project(u, lp, cfg)
        with jax.named_scope("ssm/conv"):
            window = jnp.concatenate([_tail(cpool, si, cfg), xBC], axis=1)
        kept.append((window, dt_raw))
        x, Bm, Cm, dt, A = M.ssm_inputs(M.ssm_conv(window, lp, cfg),
                                        dt_raw, lp, cfg)
        with jax.named_scope("ssm/state_update"):
            y, _ = M.ssm_scan(x, Bm, Cm, dt, A, states[si],
                              cfg.mamba_chunk_size)
            y = y + lp["D"].astype(F32)[:, None] * x
        return M.ssm_gate_out(y, z, lp, cfg)

    x, kpool, vpool = _stack(params, toks, pos, wlimit, read, kpool, vpool,
                             cfg, tile, ssm)
    picks = _picks(x, params, cfg)
    # a lane keeps its pending token and the longest prefix of its draft
    # that equals the program's own picks (engine._accept's rule)
    with jax.named_scope("spec"):
        n_draft = wlimit - cur_len - 1                  # -1: an idle lane
        hit = (picks[:, :-1] == toks[:, 1:]) \
            & (jnp.arange(S1 - 1)[None, :] < n_draft[:, None])
        accepted = jnp.sum(jnp.cumprod(hit.astype(jnp.int32), axis=1),
                           axis=1)
        live = n_draft >= 0
        n_keep = _keeps(live, accepted)
    with jax.named_scope("ssm/state_update"):
        si = 0
        for kind, lp in zip(cfg.layer_types, params["layers"]):
            if kind != M.SSM:
                continue
            window, dt_raw = kept[si]
            xv, Bm, _, dt, A = M.ssm_inputs(M.ssm_conv(window, lp, cfg),
                                            dt_raw, lp, cfg)
            S = states[si]
            for t in range(S1):
                # dt 0 from the first rejected position on: the identity
                S = M.ssm_step(S, xv[:, t], Bm[:, t], jnp.where(
                    (t < n_keep)[:, None], dt[:, t], 0.0), A)
            states[si] = S
            cpool = cpool.at[si].set(
                _take_rows(window, n_keep, K1).reshape(L, -1))
            si += 1
    with jax.named_scope("acc"):
        by = dict(ssm_lane_rounds=jnp.sum(live),
                  ssm_state_lane_moves=3 * jnp.sum(live),
                  spec_rolled_back_tokens=jnp.sum(
                      jnp.where(live, n_draft - accepted, 0)))
    acc = _bump(acc, ssm_state_passes=2, **by)
    return _out(picks, acc), kpool, vpool, cpool, acc, *states


class HybridSSMFamily:
    """See ``families/__init__.py`` for what the engine asks of it."""

    name = "hybrid_ssm"
    lane_state = True
    prefix_reuse = False
    prefix_reuse_why = (
        "a prefix hit hands over block-aligned K/V and this family's "
        "state-space layers would need their recurrent state at that "
        "boundary, which nothing snapshots yet (ROADMAP B-m4)")

    def __init__(self, model, config):
        from ...framework.errors import UnimplementedError

        for flag, why in (
                (config.kv_int8, "kv_int8: most of its device state is the "
                 "float32 recurrent state, which the int8 K/V scale pools "
                 "do not cover"),
                (config.int8_weights, "int8_weights: the pack would be a "
                 "second copy of the weights")):
            if flag:
                raise UnimplementedError(
                    f"the hybrid state-space family does not serve with "
                    f"{why}")
        c = model.config
        self.gcfg = c.static()
        self.max_position_embeddings = c.max_position_embeddings
        self.lanes = config.max_lanes
        self.n_ssm = sum(k == M.SSM for k in c.layer_types)
        self.n_attn = c.num_hidden_layers - self.n_ssm
        self.donate_argnums = tuple(range(1, 5 + self.n_ssm))
        if not self.n_attn:
            raise UnimplementedError(
                "a stack with no attention layer has no K/V pool: the "
                "engine's block pool would manage nothing")
        # the model's own arrays: ONE copy of the weights on the device
        self.params = {
            "embed": model.embed._data, "norm": model.norm._data,
            "layers": tuple({k: p._data for k, p in blk.leaves().items()}
                            for blk in model.layers)}
        self.counters = dict.fromkeys(ACC, 0)
        self._seen = [0] * len(ACC)

    def make_pools(self, num_blocks, block_size):
        """(K pool, V pool, conv pool, the counters' device accumulator,
        then one state array a state-space layer): the first two by
        (attention layer, block, offset), the conv pool by (state-space
        layer, LANE), each state array by LANE."""
        g = self.gcfg
        dt = jnp.dtype(g.dtype)
        kpool = jnp.zeros((self.n_attn, num_blocks, block_size,
                           g.num_key_value_heads * g.head_dim), dt)
        return (kpool, jnp.zeros_like(kpool),
                jnp.zeros((self.n_ssm, self.lanes,
                           (g.mamba_d_conv - 1) * g.conv_dim), dt),
                jnp.zeros((len(ACC),), jnp.int32),
                *(jnp.zeros((self.lanes, g.mamba_n_heads, g.mamba_d_head,
                             g.mamba_d_state), F32)
                  for _ in range(self.n_ssm)))

    def kv_pool_bytes(self, pools):
        return int(pools[0].nbytes + pools[1].nbytes)

    def lane_pool_bytes(self, pools):
        return int(pools[2].nbytes + sum(p.nbytes for p in pools[4:]))

    def read_form(self, kind):
        """The dense family's live rows ``(W, tile)``; ``lane_state``
        adds the request's lane to the prefill chunk's."""
        return ROW_BLOCKS, PREFILL_TILE if kind == "prefill" else ROW_TILE

    def program(self, kind):
        fn = {"prefill": _prefill_chunk, "decode": _decode_step,
              "verify": _verify_step}[kind]
        return fn, {"cfg": self.gcfg, "tile": self.read_form(kind)[1]}

    def exec_key(self, pools):
        from ...jit import exec_cache

        return {"family": self.name, "gen_cfg": self.gcfg._key(),
                "params": [exec_cache.array_spec(a) for a in
                           jax.tree_util.tree_leaves(self.params)],
                "pools": [(tuple(int(x) for x in p.shape), str(p.dtype))
                          for p in pools[:5]], "state_arrays": self.n_ssm}

    def absorb(self, out, counters):
        """Strip the accumulator off the fetched vector into ``counters``."""
        return absorb_accumulator(out, ACC, self._seen, counters)

    def stats(self):
        g = self.gcfg
        state = g.mamba_n_heads * g.mamba_d_head * g.mamba_d_state * 4
        tail = (g.mamba_d_conv - 1) * g.conv_dim \
            * jnp.dtype(g.dtype).itemsize
        return {"ssm_state_bytes_per_lane": self.n_ssm * state,
                "ssm_conv_bytes_per_lane": self.n_ssm * tail,
                "prefix_reuse_why": self.prefix_reuse_why}
