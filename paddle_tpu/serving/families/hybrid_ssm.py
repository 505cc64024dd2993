"""The hybrid state-space / attention family (``models/hybrid_ssm.py``) as
the serving engine sees it: TWO kinds of cache in one family.

- **By token**: a K pool and a V pool ``[attention layers, blocks, block,
  kv_heads x head_dim]`` for the few attention layers, written by
  (layer, block, offset) with the null-block redirect and read over the
  lanes' live rows (``common.paged_attention``: the fused kernel
  ``ops/pallas/row_attention.py``; no rotary embedding here, and the
  model's score multiplier is the read's ``scale``). A pool that ends in
  ``[kv_heads, head_dim]`` with a head of 64, half a 128-lane tile, the
  TPU compiler lays out
  blocks-minor, and every program call then copied both pools in and out
  (4 x 285 MB; the latent family's finding, PERF.md section 6, PR 27).
  With the heads merged into the last axis (512 = 4 lane tiles) the
  pool's own layout is row-major and no call copies it.
- **By LANE**: the recurrent state, one float32 array a state-space
  layer in the SLAB layout of ``ops/pallas/ssm_state.py`` (``[lanes,
  groups, d_state, heads/groups x d_head]``: the model's ``[lanes, heads,
  d_head, d_state]`` with each head's matrix transposed, so that what
  differs by head varies along a vector register's lanes), and a conv pool
  ``[state-space layers, lanes, (d_conv - 1) x conv channels]`` (a
  lane's rows side by side: 3 rows would be padded to a whole sublane
  tile): what a request's state-space layers keep of everything it has
  read — as large for 16 tokens as for 16,000. The state is ONE ARRAY A
  LAYER, not one stacked pool (on a stacked pool the TPU compiler turned
  the update into an in-place dynamic-update-slice over the whole pool:
  PERF.md section 6, PR 31), and a round touches it in ONE call a layer,
  the kernel ``ssm_state.state_round``, which brings a lane's 2 MB into
  VMEM, applies what is owed, reads for this round's outputs from the
  tile it has just written, and writes it back in place
  (``tests/test_chip_compile.py`` holds the compiled programs to that).
  Decode and verify index both by lane; the one-lane prefill chunk is
  told its request's lane (``lane_state``: the engine appends ``slot
  [1]`` to its read operand), keeps the chunked scan (on the slab as it
  lies: ``ssm_scan(..., slab=True)``) and a dynamic-update-slice of its
  lane's slab, and a chunk that starts at
  position 0 starts from ZERO state and tail, so an admitted or
  re-admitted request never sees its lane's predecessor.
- **A verify round's rejected drafts leave no trace in the state, and
  the accepted ones enter it ONE CALL LATE.** K/V written above a
  lane's valid length is masked out of every later read, but a
  recurrence advanced over five positions has the rejected ones folded
  in, and five copies of 75 MB a lane do not exist. Which positions the
  state may take up is known only after the head (the lane's
  acceptance), 40 layers after the layer read its state; writing then
  costs a second traversal to read the round's outputs and a third
  (read and write) to apply them. So the verify program writes NONE of
  its own positions: it leaves each layer's update inputs of the k+1
  positions PENDING, in the model's dtype, 43 KB a lane a layer — the
  convolved ``x`` as the kernel's planes, an array a layer, which the
  next call's kernel takes as it lies, and ``B | dt_raw`` in one small
  pool — and, after the head, the count
  ``n_keep`` its acceptance allows — the longest prefix of the
  lane's draft equal to the program's own picks: the engine's
  ``_accept`` rule, which stays the judge of what is emitted. The NEXT
  round's call applies them first, under the same mask as before (``dt``
  0 from position ``n_keep`` on: ``exp(0 A) = 1`` and ``0 x (outer) B =
  0``, the identity on the state, bit for bit, whatever the rejected
  positions held), inside the one pass that also reads for its own
  outputs (the whole ``y_t = S_t C_t + D x_t`` of its five positions,
  from the committed state decayed to ``t`` plus what the round's own
  positions add, ``_own_mix`` — the state itself is not advanced): two
  traversals a verify round, not three
  (``ssm_state_lane_moves`` 2 x live, ``ssm_state_passes`` 1,
  ``ssm_deferred_positions`` the positions committed a call late). A
  plain round has nothing to wait for: the same call applies what is
  owed, then its own position, reads from the result, and leaves its
  lanes owing nothing. A lane's state is therefore its array's slab
  WITH its pending positions applied. The conv tail is small and is
  still set at once (the window's rows that end at the last kept
  position). A prefill chunk zeroes its lane's pending count: a
  request's chunks all precede its rounds, so a prefilling lane owes
  nothing of its own, and what its predecessor left (a finished
  request's last round is never applied) must not enter the new
  request's state.
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

from ...framework.errors import UnimplementedError
from ...models import hybrid_ssm as M
from ...models.generation import _rms
from ...ops.pallas import ssm_state
from .common import (
    PREFILL_TILE, ROW_BLOCKS, ROW_TILE, Family, _carried, _keeps, _out,
    _take_rows, accept, bump, greedy_head, lane_tails, paged_attention,
    rolled_back, write_slots,
)

__all__ = ["HybridSSMFamily"]

F32 = jnp.float32

# the device accumulator's slots: times a round's program went through
# the lanes' state (1 a round, plain or verify: read and written in one
# pass); live lanes summed over rounds; live lanes x the times their state
# was read or written (2 a round); prefill chunks that started a slot from
# zero; drafted positions whose state update was discarded; positions of a
# verify round that entered their lane's state in the NEXT round's pass
ACC = ("ssm_state_passes", "ssm_lane_rounds", "ssm_state_lane_moves",
       "ssm_slot_resets", "spec_rolled_back_tokens",
       "ssm_deferred_positions")


def _attention(u, lp, ai, kpool, vpool, rows, pos, blk, off, cfg):
    """An attention layer's mixer against the block pool
    (``paged_attention``; the model states its own scale). Returns (out
    [b, s, hidden], kpool, vpool)."""
    b, s, _ = u.shape
    att, kpool, vpool = paged_attention(
        *M.attention_qkv(u, lp, cfg), ai, kpool, vpool, rows, pos, blk, off,
        cfg.num_key_value_heads, cfg.attention_multiplier)
    with jax.named_scope("attn/out"):
        return att.reshape(b, s, -1) @ lp["o"], kpool, vpool


def _stack(params, ids, pos, wlimit, read, kpool, vpool, cfg, ssm):
    """The layer stack over ``ids`` [b, s] at positions ``pos``: attention
    layers against the block pool here, each state-space layer through
    ``ssm(si, u, lp) -> mix`` (the program's own: what it does with the
    lane-indexed pools differs by program). Returns (x, kpool, vpool)."""
    eps, rm = cfg.rms_norm_eps, cfg.residual_multiplier
    dt = jnp.dtype(cfg.dtype)
    scope = jax.named_scope  # the scopes: monitor/scopes.py
    with scope("embed"):
        x = (params["embed"][ids] * cfg.embedding_multiplier).astype(dt)
    rows, wblk = read[:2]
    blk, off = write_slots(wblk, pos, wlimit, kpool.shape[2],
                           "attn/kv_write")
    si = ai = 0
    for kind, lp in zip(cfg.layer_types, params["layers"]):
        u = _rms(x, lp["ln_in"], eps)
        if kind == M.SSM:
            mix = ssm(si, u, lp)
            si += 1
            out = "ssm/out_proj"
        else:
            mix, kpool, vpool = _attention(u, lp, ai, kpool, vpool, rows,
                                           pos, blk, off, cfg)
            ai += 1
            out = "attn/out"
        with scope(out):  # a residual add: its producer's scope
            x = x + (rm * mix).astype(dt)
        y = M.mlp(_rms(x, lp["ln_post"], eps), lp)
        with scope("mlp"):
            x = x + (rm * y).astype(dt)
    return x, kpool, vpool


def _picks(x, params, cfg):
    """The tied head, the logits over the model's ``logits_scaling``."""
    return greedy_head(x, params, cfg.rms_norm_eps, cfg.logits_scaling)


N_POOLS = 6  # the pools before the arrays a state-space layer


def _unpack(args, cfg):
    """A program's positional operands after ``params``: (kpool, vpool,
    cpool, acc, the pending ``B | dt_raw`` pool, the lanes' pending
    counts, [one state array a state-space layer], [one array of pending
    ``x`` planes a state-space layer], the engine's operands)."""
    n = sum(k == M.SSM for k in cfg.layer_types)
    return (*args[:N_POOLS], list(args[N_POOLS:N_POOLS + n]),
            list(args[N_POOLS + n:N_POOLS + 2 * n]), args[N_POOLS + 2 * n:])


def _owed(params, pend_s, n_owed, cfg):
    """Every state-space layer's commit of what its lanes' last verify
    round left, ``(si, the layer's pending x) -> Commit``: the round's
    gains and ``B`` out of the small pending pool for ALL layers at once
    (``ssm_inputs``' ``dt`` and ``A``, with ``dt`` 0 from each lane's
    position ``n_owed`` on: the identity), of which the kernel picks its
    layer's; the positions' ``x`` reaches the kernel as the verify round
    left it."""
    G, N, H = cfg.mamba_n_groups, cfg.mamba_d_state, cfg.mamba_n_heads
    lps = [lp for kind, lp in zip(cfg.layer_types, params["layers"])
           if kind == M.SSM]
    with jax.named_scope("ssm/inputs"):
        B, dt_raw = ssm_state.pending_rows(pend_s.astype(F32), H, N, G)
        bias, A_log = (jnp.stack([lp[k] for lp in lps]).astype(F32)
                       for k in ("dt_bias", "A_log"))
        dt = jax.nn.softplus(dt_raw + bias[:, None, None])
        kept = jnp.arange(dt.shape[2])[None, :] < n_owed[:, None]
        g = ssm_state.head_rows(ssm_state.gains(
            jnp.where(kept[..., None], dt, 0.0), -jnp.exp(A_log)[:, None]),
            G, cfg.mamba_d_head)
        B = ssm_state.b_rows(B)
    return lambda si, x: ssm_state.Commit(g, x, B, si)


def _own_mix(B, C, dt, cum, D):
    """What a verify round's own positions add to its outputs, as weights
    a head [b, T x T, H]: ``y_t = exp(cum_t) (S C_t) + sum_s mix[t, s]
    x_s`` with ``S`` the state BEFORE the round and ``cum`` the running
    sum of ``dt A`` (``ssm_scan``'s terms, the round one chunk: position
    ``s <= t`` decayed to ``t``, times ``C_t . B_s``) — and the model's
    ``D x_t`` on the diagonal."""
    b, T, H = dt.shape
    seg = cum[:, :, None, :] - cum[:, None, :, :]             # [b, t, s, H]
    lower = jnp.tril(jnp.ones((T, T), bool))[None, :, :, None]
    cb = jnp.einsum("btgn,bsgn->btsg", C, B)
    mix = jnp.exp(jnp.where(lower, seg, -jnp.inf)) * dt[:, None] \
        * jnp.repeat(cb, H // B.shape[2], axis=-1)
    return (mix + jnp.eye(T)[:, :, None] * D.astype(F32)).reshape(
        b, T * T, H)


def _prefill_chunk(params, *args, cfg):
    """One request's prefill chunk ``ids`` [1, C] at [start, start + C),
    ``read`` = (its lane's live rows, write blocks, ``slot`` [1]: the
    lane it holds). The slot's state and conv tail carry on from the
    previous chunk, or from ZERO where ``start`` is 0; pad positions (>=
    ``ctx_len``) are the identity on both. Greedy-samples at
    ``last_idx``. Returns ([token, *acc], pools...)."""
    kpool, vpool, cpool, acc, pend_s, n_owed, states, pend_x, (
        read, ids, start, ctx_len, last_idx) = _unpack(args, cfg)
    C, K1 = ids.shape[1], cfg.mamba_d_conv - 1
    with jax.named_scope("embed"):  # the fed positions
        pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
    with jax.named_scope("ssm/state_update"):
        slot = read[2][0]
        fresh = start == 0
        n_real = jnp.clip(ctx_len - start, 0, C)
        # the lane owes nothing of its own, and not its predecessor's
        n_owed = jax.lax.dynamic_update_slice(
            n_owed, jnp.zeros((1,), n_owed.dtype), (slot,))
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
            y, S = M.ssm_scan(x, Bm, Cm, dt, A, S0, cfg.mamba_chunk_size,
                              slab=True)
            y = y + lp["D"].astype(F32)[:, None] * x
            states[si] = jax.lax.dynamic_update_slice_in_dim(
                states[si], S, slot, 0)
            conv[0] = jax.lax.dynamic_update_slice(
                conv[0], _take_rows(window, n_real[None], K1).reshape(
                    1, 1, -1), (si, slot, 0))
        return M.ssm_gate_out(y, z, lp, cfg)

    x, kpool, vpool = _stack(params, ids, pos, jnp.reshape(ctx_len, (1,)),
                             read, kpool, vpool, cfg, ssm)
    acc = bump(acc, ACC, 0, ssm_slot_resets=fresh)
    with jax.named_scope("head"):
        h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1,
                                         keepdims=False)
    return (_out(_picks(h, params, cfg), acc), kpool, vpool, conv[0], acc,
            pend_s, n_owed, *states, *pend_x)


def _decode_step(params, *args, cfg):
    """Every lane feeds its pending token at ``cur_len``: K/V written
    then attended; each layer's one pass through the state applies what
    the lanes' last verify round left, then this position, and reads
    from the result; the conv tail shifted by one row, in place. The
    lanes are left owing nothing. Idle lanes (``cur_len`` 0) write K/V
    to the null block; their slots hold nothing anyone reads (a slot
    starts from zero at its next request's first chunk). Returns ([L
    tokens, *acc], pools...)."""
    kpool, vpool, cpool, acc, pend_s, n_owed, states, pend_x, (
        read, cur_len, last_tok) = _unpack(args, cfg)
    conv = [cpool]
    owed = _owed(params, pend_s, n_owed, cfg)
    G, H, P = cfg.mamba_n_groups, cfg.mamba_n_heads, cfg.mamba_d_head

    def rows(a):
        return ssm_state.head_rows(a, G, P)

    def ssm(si, u, lp):
        z, xBC, dt_raw = M.ssm_project(u, lp, cfg)
        with jax.named_scope("ssm/conv"):
            window = jnp.concatenate(
                [lane_tails(conv[0], si, cfg.mamba_d_conv), xBC], axis=1)
        c = M.ssm_conv(window, lp, cfg)
        _, Bm, Cm, dt, A = M.ssm_inputs(c, dt_raw, lp, cfg)
        with jax.named_scope("ssm/state_update"):
            x = ssm_state.x_planes(c[..., :H * P].reshape(-1, 1, H, P), G)
            own = ssm_state.Commit(rows(ssm_state.gains(dt, A))[None], x,
                                   ssm_state.b_rows(Bm)[None])
            D = jnp.broadcast_to(lp["D"].astype(F32), dt.shape)
            y, states[si] = ssm_state.state_round(
                states[si], [owed(si, pend_x[si]), own], Cm,
                mix=(rows(D), x))
            conv[0] = conv[0].at[si].set(
                window[:, 1:].reshape(window.shape[0], -1))
        return M.ssm_gate_out(y, z, lp, cfg)

    with jax.named_scope("embed"):  # the fed tokens and where
        fed = last_tok[:, None], cur_len[:, None], cur_len + 1
    x, kpool, vpool = _stack(params, *fed, read, kpool, vpool, cfg,
                             ssm)
    with jax.named_scope("acc"):
        live = cur_len > 0
        by = dict(ssm_lane_rounds=jnp.sum(live),
                  ssm_state_lane_moves=2 * jnp.sum(live),
                  ssm_deferred_positions=jnp.sum(
                      jnp.where(live, n_owed, 0)))
    acc = bump(acc, ACC, 0, ssm_state_passes=1, **by)
    with jax.named_scope("head"):
        x = x[:, -1]
    return (_out(_picks(x, params, cfg), acc), kpool, vpool, conv[0], acc,
            pend_s, jnp.zeros_like(n_owed), *states, *pend_x)


def _verify_step(params, *args, cfg):
    """``toks`` [L, k+1]: each lane's pending token and its draft at
    ``cur_len + j``; positions >= ``wlimit[b]`` are pad. Each layer's one
    pass through the state applies what the lanes' LAST verify round
    left and reads for this round's outputs; of this round's positions
    it writes none: their update inputs go to the pending pools, and
    after the head the lane's acceptance ``a`` (module docstring) says
    how many of them the next call applies and which rows the conv tail
    takes: the pending token and the first ``a`` drafts, nothing else.
    Returns ([L * (k+1) picks row-major, *acc], pools...)."""
    kpool, vpool, cpool, acc, pend_s, n_owed, states, pend_x, (
        read, cur_len, toks, wlimit) = _unpack(args, cfg)
    L, S1 = toks.shape
    K1, G = cfg.mamba_d_conv - 1, cfg.mamba_n_groups
    H, P, GN = cfg.mamba_n_heads, cfg.mamba_d_head, G * cfg.mamba_d_state
    owed = _owed(params, pend_s, n_owed, cfg)
    with jax.named_scope("embed"):
        pos = cur_len[:, None] + jnp.arange(S1, dtype=jnp.int32)[None, :]
    windows = []  # per state-space layer: the round's conv window
    left = [pend_s]  # this round's B | dt_raw, layer by layer

    def rows(a):
        return ssm_state.head_rows(a, G, P)

    def ssm(si, u, lp):
        z, xBC, dt_raw = M.ssm_project(u, lp, cfg)
        with jax.named_scope("ssm/conv"):
            window = jnp.concatenate(
                [lane_tails(cpool, si, cfg.mamba_d_conv), xBC], axis=1)
        windows.append(window)
        c = M.ssm_conv(window, lp, cfg)
        _, Bm, Cm, dt, A = M.ssm_inputs(c, dt_raw, lp, cfg)
        with jax.named_scope("ssm/state_update"):
            x = ssm_state.x_planes(c[..., :H * P].reshape(L, S1, H, P), G)
            cum = jnp.cumsum(dt * A, axis=1)
            y, states[si] = ssm_state.state_round(
                states[si], [owed(si, pend_x[si])], Cm,
                scale=rows(jnp.exp(cum)),
                mix=(rows(_own_mix(Bm, Cm, dt, cum, lp["D"])), x))
            pend_x[si] = x
            left[0] = left[0].at[si].set(jnp.concatenate(
                [c[..., H * P:H * P + GN], dt_raw], axis=-1).reshape(L, -1))
        return M.ssm_gate_out(y, z, lp, cfg)

    x, kpool, vpool = _stack(params, toks, pos, wlimit, read, kpool, vpool,
                             cfg, ssm)
    picks = _picks(x, params, cfg)
    live, n_draft, accepted = accept(picks, toks, cur_len, wlimit)
    with jax.named_scope("spec"):
        n_keep = _keeps(live, accepted)
    with jax.named_scope("ssm/state_update"):
        for si, window in enumerate(windows):
            cpool = cpool.at[si].set(
                _take_rows(window, n_keep, K1).reshape(L, -1))
    with jax.named_scope("acc"):
        by = dict(ssm_lane_rounds=jnp.sum(live),
                  ssm_state_lane_moves=2 * jnp.sum(live),
                  ssm_deferred_positions=jnp.sum(
                      jnp.where(live, n_owed, 0)),
                  spec_rolled_back_tokens=rolled_back(live, n_draft,
                                                      accepted))
    acc = bump(acc, ACC, 0, ssm_state_passes=1, **by)
    return (_out(picks, acc), kpool, vpool, cpool, acc, *left,
            n_keep.astype(n_owed.dtype), *states, *pend_x)


class HybridSSMFamily(Family):
    """See ``families/__init__.py`` for what the engine asks of it."""

    name = "hybrid_ssm"
    title = "the hybrid state-space family"
    ACC = ACC
    programs = {"prefill": _prefill_chunk, "decode": _decode_step,
                "verify": _verify_step}
    lane_state = True
    prefix_reuse = False
    row_read = "kernel"  # the attention layers' live rows: row_attention
    prefix_reuse_why = (
        "a prefix hit hands over block-aligned K/V and this family's "
        "state-space layers would need their recurrent state at that "
        "boundary, which nothing snapshots yet (ROADMAP B-m4)")

    def __init__(self, model, config):
        self.refuse(config, {
            "kv_int8": "most of its device state is the float32 recurrent "
            "state, which the int8 K/V scale pools do not cover"})
        super().__init__(model, config)
        c = model.config
        self.n_ssm = sum(k == M.SSM for k in c.layer_types)
        self.n_attn = c.num_hidden_layers - self.n_ssm
        self.donate_argnums = tuple(
            range(1, 1 + N_POOLS + 2 * self.n_ssm))
        self.round_positions = config.spec_k + 1
        if not self.n_attn:
            raise UnimplementedError(
                "a stack with no attention layer has no K/V pool: the "
                "engine's block pool would manage nothing")

    def make_pools(self, num_blocks, block_size):
        """(K pool, V pool, conv pool, the counters' device accumulator,
        the pending ``B | dt_raw`` pool, the lanes' pending counts, then
        one state array a state-space layer and one array of pending
        ``x`` a state-space layer — a verify round's ``x`` as the
        kernel's planes): the first two by (attention layer, block,
        offset), the conv and the small pending pool by (state-space
        layer, LANE), the counts, each state array (the kernel's slab
        layout) and each array of pending ``x`` by LANE. The pending
        ``x`` is an array a layer because a verify round replaces a
        layer's whole: in one stacked pool the TPU compiler took to
        moving all 94 MB through its fast memory and back a layer
        (compiled for a described v5e: PERF.md section 6, PR 37)."""
        g = self.gcfg
        dt = jnp.dtype(g.dtype)
        sizes = (g.mamba_n_heads, g.mamba_d_head, g.mamba_d_state,
                 g.mamba_n_groups)
        slab = ssm_state.slab_shape(self.lanes, *sizes)
        planes, small = ssm_state.pending_shapes(
            self.lanes, self.round_positions, *sizes)
        kpool = jnp.zeros((self.n_attn, num_blocks, block_size,
                           g.num_key_value_heads * g.head_dim), dt)
        return (kpool, jnp.zeros_like(kpool),
                jnp.zeros((self.n_ssm, self.lanes,
                           (g.mamba_d_conv - 1) * g.conv_dim), dt),
                jnp.zeros((len(ACC),), jnp.int32),
                jnp.zeros((self.n_ssm, *small), dt),
                jnp.zeros((self.lanes,), jnp.int32),
                *(jnp.zeros(slab, F32) for _ in range(self.n_ssm)),
                *(jnp.zeros(planes, dt) for _ in range(self.n_ssm)))

    def kv_pool_bytes(self, pools):
        return int(pools[0].nbytes + pools[1].nbytes)

    def lane_pool_bytes(self, pools):
        return int(pools[2].nbytes + sum(p.nbytes for p in pools[4:]))

    def read_form(self, kind):
        """The paged layer's live rows ``(W, tile)`` (the kernel's grid is
        the live rows: ``tile`` only rounds the operand's length);
        ``lane_state`` adds the request's lane to the prefill chunk's."""
        return ROW_BLOCKS, PREFILL_TILE if kind == "prefill" else ROW_TILE

    def stats(self):
        g = self.gcfg
        state = g.mamba_n_heads * g.mamba_d_head * g.mamba_d_state * 4
        tail = (g.mamba_d_conv - 1) * g.conv_dim \
            * jnp.dtype(g.dtype).itemsize
        owed = sum(map(np.prod, ssm_state.pending_shapes(
            1, self.round_positions, g.mamba_n_heads, g.mamba_d_head,
            g.mamba_d_state, g.mamba_n_groups))) \
            * jnp.dtype(g.dtype).itemsize
        return {"ssm_state_bytes_per_lane": self.n_ssm * state,
                "ssm_conv_bytes_per_lane": self.n_ssm * tail,
                "ssm_pending_bytes_per_lane": int(self.n_ssm * owed) + 4,
                "prefix_reuse_why": self.prefix_reuse_why}
