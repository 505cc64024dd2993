"""The linear-attention / latent-attention sparse-expert family
(``models/linear_latent_moe.py``) as the serving engine sees it: THREE
kinds of device state in one family.

- **By token**: the latent pool ``[latent layers, blocks, block, 640]`` of
  ``families/latent_moe.py`` — laid out, written and read by ITS functions
  (the lanes' live rows through the fused row kernel, the absorbed
  attention as one shared KV head: ``attend_pool``), for the few
  latent-attention layers only; no position embedding. This is the one
  import of a family by a family: the latent layer (``attend_pool``,
  ``read_form`` with the constants tests steer, the
  pool's ``LANES``) stays where its family is and is served from there,
  not copied into ``common.py``, which holds no latent arithmetic.
- **By LANE, float32**: the delta rule's matrix state, ``[lanes, heads, d,
  d]`` (key x value) a linear-attention layer — ONE ARRAY A LAYER, not one
  stacked pool (``families/hybrid_ssm.py`` says what a stacked one cost) —
  as large for 16 tokens as for 16,000; and what a verify round leaves
  PENDING (below): one pool ``[linear-attention layers, lanes, 3, k+1,
  heads, d]`` of its positions' keys, log-decays and pseudo-values (240 KB
  a lane a layer at the published sizes), which only the state kernel
  reads and writes, a layer at a time in place, and a count a lane.
- **By LANE, model dtype**: a conv pool ``[linear-attention layers, lanes,
  (K - 1) x 3 x heads x d]``: the last K - 1 rows of ``[q~ | k~ | v~]``.

Decode and verify index the lane pools by the batch row; the one-lane
prefill chunk is told its request's lane (``lane_state``: the engine gives
it ``(rows, wblk, slot [1])``) and a chunk that starts at
position 0 starts from ZERO state and tail.

- **A round touches a layer's state in ONE call**, the kernel
  ``ops/pallas/kda_state.py`` ``state_round``, which brings a lane's 2 MB
  into VMEM, applies what is owed, writes the tile back in place and
  reads for this round's outputs from the tile it holds
  (``tests/test_chip_compile.py`` holds the compiled programs to that:
  one kernel call a layer, no other operation over the state's shape, no
  ``[lanes, heads, k+1, k+1]`` value).
- **A verify round's rejected drafts leave no trace in the state, and
  the accepted ones enter it ONE CALL LATE** (the contract of
  ``ServingEngine._verify_round``; the hybrid family's order, for the
  same reason). Which positions the state may take up is known only
  after the head (the lane's acceptance), 27 layers after the layer read
  its state; writing then costs a second traversal to read the round's
  outputs and a third (read and write) to apply them. So the verify
  program writes NONE of its own positions: each layer's call leaves the
  positions' keys, log-decays and pseudo-values ``u`` (= ``beta`` x the
  correction; a kept position's do not depend on the later ones: the
  system they solve is lower-triangular) in the pending pool and, after
  the head, the program writes the count ``n_keep`` its acceptance
  allows (the engine's ``_accept`` rule; the engine stays the judge of
  what is emitted). The NEXT round's call applies them first, ``S <-
  Diag(exp G) S + sum_s (k_s exp(G - G_s)) u_s^T`` with ``g`` and ``u``
  taken as 0 from position ``n_keep`` on — such a position is the
  identity, bit for bit, whatever a rejected position held — inside the
  one pass that also reads for its own outputs: two traversals a verify
  round, not three (``lin_state_lane_moves`` 2 x live,
  ``lin_state_passes`` 1, ``lin_deferred_positions`` the positions
  committed a call late). A plain round has nothing to wait for: the
  same call applies what is owed, then its own position, and leaves its
  lanes owing nothing. A lane's state is therefore its array's entry
  WITH its pending positions applied. The conv tail is small and is
  still set at once: the window's rows that end at the last kept
  position; latent entries above a lane's valid length are masked as in
  the latent family. A prefill chunk keeps the chunked form
  (``kda_chunk``) and zeroes its lane's pending count: a request's
  chunks all precede its rounds (preemption recomputes from position 0),
  so a prefilling lane owes nothing of its own, and what its predecessor
  left (a finished request's last round is never applied) must not
  enter the new request's state.
- **No prefix reuse** (``prefix_reuse`` False): a prefix hit hands over
  block-aligned latent entries, and the state at that boundary is not
  kept (ROADMAP B-m4); preemption recomputes from the prompt.
- **Weights once**: ``params`` references the model's arrays; each
  program is a Python loop over the layers with three bodies (linear
  attention + dense, linear attention + experts, latent + experts).
- **Counters** ride on the round's token array: the expert layer's four
  (``common.MOE_ACC``), the state's six and the rounds' count
  of held experts hit (``LIN_ACC``), one accumulator.

``kv_int8`` and ``int8_weights`` raise ``UnimplementedError``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.errors import UnimplementedError
from ...models import linear_latent_moe as M
from ...models.generation import _rms
from ...ops.pallas import kda_state
from .common import (
    MOE_ACC, Family, _carried, _keeps, _out, _take_rows, accept, bump,
    expert_counts, greedy_head, lane_tails, rolled_back, write_slots,
)
from .latent_moe import LANES, attend_pool, read_form

__all__ = ["LinearLatentMoEFamily"]

F32 = jnp.float32

# the state's slots of the device accumulator: times a round's program
# went through the lanes' state (1 a round, plain or verify: read and
# written in one pass); live lanes summed over rounds; live lanes x the
# times their state was read or written (2 a round); prefill chunks that
# started a slot from zero; drafted positions whose update was discarded;
# of the expert layer, the held experts that got at least one assignment,
# summed over the expert-layer calls of decode and verify rounds (what
# such a round has to read of the held experts' weights: repeating outputs
# route alike, so it is far from every expert); and the positions of a
# verify round that entered their lane's state in the NEXT round's pass
LIN_ACC = ("lin_state_passes", "lin_lane_rounds", "lin_state_lane_moves",
           "lin_slot_resets", "spec_rolled_back_tokens",
           "moe_round_experts_hit", "lin_deferred_positions")
ACC = MOE_ACC + LIN_ACC


def _stack(params, ids, pos, wlimit, valid, read, pool, acc, cfg, kda):
    """The layer stack over ``ids`` [b, s] at positions ``pos``: latent
    layers against the block pool here (``read`` = the engine's live rows
    and the fed positions' blocks: the latent family's ``attend_pool``),
    each linear-attention layer
    through ``kda(ki, u, lp) -> mix`` (the program's own: what it does
    with the lane-indexed pools differs by program). Returns (x, pool,
    acc, the held experts hit summed over the expert layers)."""
    eps = cfg.rms_norm_eps
    scope = jax.named_scope  # the scopes: monitor/scopes.py
    with scope("embed"):
        x = params["embed"][ids].astype(jnp.dtype(cfg.dtype))
    rows, wblk = read
    blk, off = write_slots(wblk, pos, wlimit, pool.shape[2], "mla/kv_write")
    with scope("acc"):
        n_valid = jnp.sum(valid, dtype=jnp.int32)
        hit = jnp.int32(0)
    ki = ai = 0
    for kind, lp in zip(cfg.layer_kinds, params["layers"]):
        u = _rms(x, lp["ln_in"], eps)
        if kind == M.KDA:
            mix = kda(ki, u, lp)
            ki += 1
            out = "kda/out_proj"
        else:
            att, pool = attend_pool(u, lp, ai, pool, rows, pos, blk, off,
                                    cfg, rope=False)
            ai += 1
            out = "mla/out"
            with scope(out):
                mix = att @ lp["o"]
        with scope(out):  # a residual add: its producer's scope
            x = x + mix
        y, counts = M.ffn_block(_rms(x, lp["ln_post"], eps), lp, cfg,
                                valid=valid)
        with scope("mlp" if counts is None else "moe/combine"):
            x = x + y
        if counts is not None:
            with scope("acc"):
                acc = acc.at[:len(MOE_ACC)].add(expert_counts(
                    n_valid, counts, cfg.num_experts_per_token))
                hit = hit + jnp.sum(counts > 0, dtype=jnp.int32)
    return x, pool, acc, hit


def _unpack(args, cfg):
    """A program's positional operands after ``params``: (latent pool,
    acc, conv pool, [one state array a linear-attention layer], the
    pending pool, the lanes' pending counts, the engine's operands)."""
    n = sum(k == M.KDA for k in cfg.layer_kinds)
    return (*args[:3], list(args[3:3 + n]), *args[3 + n:5 + n],
            args[5 + n:])


def _round(S, pend, ki, n_owed, now, beta, own):
    """``kda_state.state_round`` for layer ``ki``'s positions ``now`` =
    (q, k, v, g) [lanes, T, H, d] as the model gives them: the kernel
    takes and gives positions first (a bitcast of what the compiler
    holds). Returns (o [lanes, T, H, d], S'[, the pending pool])."""
    o, *rest = kda_state.state_round(
        S, pend, ki, n_owed, *(jnp.swapaxes(a, 0, 1) for a in now), beta,
        own=own)
    return jnp.swapaxes(o, 0, 1), *rest


def _deferred(live, n_owed):
    """The positions this round's calls commit a call late."""
    return jnp.sum(jnp.where(live, n_owed, 0))


def _prefill_chunk(params, *args, cfg):
    """One request's prefill chunk ``ids`` [1, C] at [start, start + C),
    ``read`` = (its lane's rows live up to the chunk's end, the fed
    positions' blocks, ``slot`` [1]: the lane it holds).
    The slot's state and conv tail carry on from the previous chunk, or
    from ZERO where ``start`` is 0; pad positions (>= ``ctx_len``) are
    the identity on both; the lane's pending count is zeroed (module
    docstring). Greedy-samples at ``last_idx``. Returns ([token, *acc],
    pools...)."""
    pool, acc, cpool, states, pend, n_owed, (
        (*read, slot), ids, start, ctx_len, last_idx) = _unpack(args, cfg)
    C, K1 = ids.shape[1], cfg.kda_taps - 1
    with jax.named_scope("embed"):  # the fed positions, and which are real
        pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
        real = pos < ctx_len
    with jax.named_scope("kda/state_update"):
        slot = slot[0]
        fresh = start == 0
        n_real = jnp.clip(ctx_len - start, 0, C)
        # the lane owes nothing of its own, and not its predecessor's
        n_owed = jax.lax.dynamic_update_slice(
            n_owed, jnp.zeros((1,), n_owed.dtype), (slot,))
    conv = [cpool]

    def kda(ki, u, lp):
        raw = M.kda_project(u, lp, cfg)
        with jax.named_scope("kda/state_update"):
            S0 = _carried(fresh, jax.lax.dynamic_slice_in_dim(
                states[ki], slot, 1))
            tail = _carried(fresh, jax.lax.dynamic_slice(
                conv[0], (ki, slot, 0), (1, 1, cpool.shape[2]))[0]
            ).reshape(1, K1, -1)
        with jax.named_scope("kda/conv"):
            window = jnp.concatenate([tail, raw], axis=1)
        q, k, v = M.kda_conv(window, lp, cfg)
        g, beta = M.kda_gates(u, lp, cfg)
        with jax.named_scope("kda/state_update"):
            o, S = M.kda_chunk(
                q, k, v, jnp.where(real[..., None, None], g, 0.0),
                jnp.where(real[..., None], beta, 0.0), S0,
                cfg.kda_chunk_size)
            states[ki] = jax.lax.dynamic_update_slice_in_dim(
                states[ki], S, slot, 0)
            conv[0] = jax.lax.dynamic_update_slice(
                conv[0], _take_rows(window, n_real[None], K1).reshape(
                    1, 1, -1), (ki, slot, 0))
        return M.kda_gate_out(o, u, lp, cfg)

    x, pool, acc, _ = _stack(
        params, ids, pos, jnp.reshape(ctx_len, (1,)), real, read, pool,
        acc, cfg, kda)
    acc = bump(acc, LIN_ACC, len(MOE_ACC), lin_slot_resets=fresh)
    with jax.named_scope("head"):
        h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1,
                                         keepdims=False)
    picks = greedy_head(h, params, cfg.rms_norm_eps)
    return _out(picks, acc), pool, acc, conv[0], *states, pend, n_owed


def _decode_step(params, *args, cfg):
    """Every lane feeds its pending token at ``cur_len``: the latent
    entry written then attended; each linear-attention layer's one pass
    through the state applies what the lanes' last verify round left,
    reads for this position, then applies it; the conv tail shifted by
    one row, in place. The lanes are left owing nothing. Idle lanes
    (``cur_len`` 0) write the null block; their slots hold nothing
    anyone reads (a slot starts from zero at its next request's first
    chunk). Returns ([L tokens, *acc], pools...)."""
    pool, acc, cpool, states, pend, n_owed, (
        read, cur_len, last_tok) = _unpack(args, cfg)
    conv = [cpool]

    def kda(ki, u, lp):
        raw = M.kda_project(u, lp, cfg)
        with jax.named_scope("kda/conv"):
            window = jnp.concatenate(
                [lane_tails(conv[0], ki, cfg.kda_taps), raw], axis=1)
        q, k, v = M.kda_conv(window, lp, cfg)
        g, beta = M.kda_gates(u, lp, cfg)
        with jax.named_scope("kda/state_update"):
            o, states[ki] = _round(states[ki], pend, ki, n_owed,
                                   (q, k, v, g), beta, own=True)
            conv[0] = conv[0].at[ki].set(
                window[:, 1:].reshape(window.shape[0], -1))
        return M.kda_gate_out(o, u, lp, cfg)

    with jax.named_scope("embed"):  # the fed tokens, where, which are real
        live = cur_len > 0
        fed = (last_tok[:, None], cur_len[:, None], cur_len + 1,
               live[:, None])
    x, pool, acc, n_hit = _stack(params, *fed, read, pool, acc, cfg, kda)
    with jax.named_scope("acc"):
        n = jnp.sum(live)
        by = dict(lin_lane_rounds=n, lin_state_lane_moves=2 * n,
                  lin_deferred_positions=_deferred(live, n_owed))
    acc = bump(acc, LIN_ACC, len(MOE_ACC), lin_state_passes=1,
               moe_round_experts_hit=n_hit, **by)
    with jax.named_scope("head"):
        x = x[:, -1]
    picks = greedy_head(x, params, cfg.rms_norm_eps)
    return (_out(picks, acc), pool, acc, conv[0], *states, pend,
            jnp.zeros_like(n_owed))


def _verify_step(params, *args, cfg):
    """``toks`` [L, k+1]: each lane's pending token and its draft at
    ``cur_len + j``; positions >= ``wlimit[b]`` are pad. Each
    linear-attention layer's one pass through the state applies what the
    lanes' LAST verify round left and reads for this round's outputs; of
    this round's positions it writes none: what their update needs goes
    to the pending pool, and after the head the lane's acceptance ``a``
    (module docstring) says how many of them the next call applies and
    which rows the conv tail takes: the pending token and the first
    ``a`` drafts, nothing else. Returns ([L * (k+1) picks row-major,
    *acc], pools...)."""
    pool, acc, cpool, states, pend, n_owed, (
        read, cur_len, toks, wlimit) = _unpack(args, cfg)
    L, S1 = toks.shape
    K1 = cfg.kda_taps - 1
    with jax.named_scope("embed"):
        pos = cur_len[:, None] + jnp.arange(S1, dtype=jnp.int32)[None, :]
        valid = pos < wlimit[:, None]
    windows = []  # per linear-attention layer: the round's conv window
    left = [pend]  # this round's keys, log-decays, pseudo-values

    def kda(ki, u, lp):
        raw = M.kda_project(u, lp, cfg)
        with jax.named_scope("kda/conv"):
            window = jnp.concatenate(
                [lane_tails(cpool, ki, cfg.kda_taps), raw], axis=1)
        windows.append(window)
        q, k, v = M.kda_conv(window, lp, cfg)
        g, beta = M.kda_gates(u, lp, cfg)
        with jax.named_scope("kda/state_update"):
            o, states[ki], left[0] = _round(states[ki], left[0], ki, n_owed,
                                            (q, k, v, g), beta, own=False)
        return M.kda_gate_out(o, u, lp, cfg)

    x, pool, acc, n_hit = _stack(params, toks, pos, wlimit, valid, read,
                                 pool, acc, cfg, kda)
    picks = greedy_head(x, params, cfg.rms_norm_eps)
    live, n_draft, accepted = accept(picks, toks, cur_len, wlimit)
    with jax.named_scope("spec"):
        n_keep = _keeps(live, accepted)
    with jax.named_scope("kda/state_update"):
        for ki, window in enumerate(windows):
            cpool = cpool.at[ki].set(
                _take_rows(window, n_keep, K1).reshape(L, -1))
    with jax.named_scope("acc"):
        by = dict(lin_lane_rounds=jnp.sum(live),
                  lin_state_lane_moves=2 * jnp.sum(live),
                  lin_deferred_positions=_deferred(live, n_owed),
                  spec_rolled_back_tokens=rolled_back(live, n_draft,
                                                      accepted))
    acc = bump(acc, LIN_ACC, len(MOE_ACC), lin_state_passes=1,
               moe_round_experts_hit=n_hit, **by)
    return (_out(picks, acc), pool, acc, cpool, *states, *left,
            n_keep.astype(n_owed.dtype))


class LinearLatentMoEFamily(Family):
    """See ``families/__init__.py`` for what the engine asks of it."""

    name = "linear_latent_moe"
    title = "the linear-attention family"
    ACC = ACC
    programs = {"prefill": _prefill_chunk, "decode": _decode_step,
                "verify": _verify_step}
    row_read = "kernel"  # the latent layers' live rows: row_attention
    lane_state = True
    prefix_reuse = False
    prefix_reuse_why = (
        "a prefix hit hands over block-aligned latent entries and this "
        "family's linear-attention layers would need their recurrent "
        "state at that boundary, which nothing snapshots yet (ROADMAP "
        "B-m4)")

    def __init__(self, model, config):
        self.refuse(config, {
            "kv_int8": "most of its device state is the float32 recurrent "
            "state, and the int8 scale pools are [.., kv_heads] beside "
            "[.., kv_heads, head_dim] pools"})
        super().__init__(model, config)
        c = model.config
        self.n_kda = sum(k == M.KDA for k in c.layer_kinds)
        self.n_latent = c.num_hidden_layers - self.n_kda
        self._width = c.latent_width
        self.donate_argnums = tuple(range(1, 6 + self.n_kda))
        self.round_positions = config.spec_k + 1
        if not self.n_latent:
            raise UnimplementedError(
                "a stack with no latent-attention layer has no block pool: "
                "the engine's block pool would manage nothing")

    def make_pools(self, num_blocks, block_size):
        """(latent pool by (latent layer, block, offset), the counters'
        device accumulator, conv pool by (linear-attention layer, LANE),
        one state array a linear-attention layer by LANE, then the
        pending pool by (linear-attention layer, LANE) and the lanes'
        pending counts)."""
        g = self.gcfg
        dt = jnp.dtype(g.dtype)
        return (jnp.zeros((self.n_latent, num_blocks, block_size,
                           -(-self._width // LANES) * LANES), dt),
                jnp.zeros((len(ACC),), jnp.int32),
                jnp.zeros((self.n_kda, self.lanes,
                           (g.kda_taps - 1) * 3 * g.kda_width), dt),
                *(jnp.zeros((self.lanes, g.kda_heads, g.kda_head_dim,
                             g.kda_head_dim), F32)
                  for _ in range(self.n_kda)),
                jnp.zeros(self._pending(self.lanes), F32),
                jnp.zeros((self.lanes,), jnp.int32))

    def _pending(self, lanes):
        g = self.gcfg
        return kda_state.pending_shape(self.n_kda, lanes,
                                       self.round_positions, g.kda_heads,
                                       g.kda_head_dim)

    def kv_pool_bytes(self, pools):
        return int(pools[0].nbytes)

    def lane_pool_bytes(self, pools):
        return int(pools[2].nbytes + sum(p.nbytes for p in pools[3:]))

    def read_form(self, kind):
        """The latent family's ``(W, tile)``: the latent layers read
        their lanes' live rows through its functions; ``lane_state`` adds
        the request's lane to the prefill chunk's operand."""
        return read_form(kind)

    def stats(self):
        g = self.gcfg
        itemsize = jnp.dtype(g.dtype).itemsize
        state = g.kda_heads * g.kda_head_dim * g.kda_head_dim * 4
        tail = (g.kda_taps - 1) * 3 * g.kda_width * itemsize
        return {"lin_state_bytes_per_lane": self.n_kda * state,
                "lin_conv_bytes_per_lane": self.n_kda * tail,
                "lin_pending_bytes_per_lane":
                    4 * math.prod(self._pending(1)) + 4,
                "latent_kv_bytes_per_token": self._width * itemsize,
                "prefix_reuse_why": self.prefix_reuse_why}
