"""The dense grouped-query decoder family (``models/llama.py``) as the
serving engine sees it: a K pool and a V pool ``[layers, blocks, block,
kv_heads x head_dim]`` (int8 mode: ``[.., kv_heads, head_dim]`` with paired
float32 scale pools), the weights stacked per leaf so each program scans
over layers, and the three step programs (``tests/test_chip_compile.py``
holds what they compile to).

**The K/V read follows what the lanes hold**: the engine's ``pack`` phase
cuts each running lane's block list into rows of ``ROW_BLOCKS`` blocks and
lays all lanes' rows end to end, and a program reads the LIVE rows and
nothing else of any table, so a call's cost follows the live blocks, not
``max_seq_len`` (PERF.md section 6, PR 28). Who reads them, by the pool's
dtype:

- **bf16 pools: the fused kernel** ``ops/pallas/row_attention.py`` (PR 39;
  behind ``common.paged_attention``, the grouped-query layer on the paged
  pool that the hybrid state-space, the window-attention and the
  short-convolution families' attention layers call too): one call a
  layer whose grid is the live rows; it
  copies a row's blocks out of the stacked pool into fast memory by
  (layer, block) id, scores them against the lane's queries with the
  operands as stored (bfloat16 on the chip, float32 products), masks, and
  folds a lane's rows into one softmax there: no gathered tile, float32
  copy of K/V or score tensor reaches HBM;
- **int8 pools: ``_attend_rows``**, the same read as plain XLA a tile of
  rows at a time (the paired scales are dequantized a gathered tile at a
  time; no benchmark cell serves it).

``_attend_lanes``, the read over a lane's whole gathered table, stays as
the definition both are held to (``tests/test_serving_rows.py``).

The attention/RoPE/MLP math reuses ``models/generation.py``'s helpers
(``_rms``/``_mm``/``_rope_at``) and mirrors its ``_attend`` — engine
outputs are token-identical to per-request ``generate()`` calls
(tests/test_serving.py and tests/test_serving_rows.py prove it, padding
included, because masked slots and padded rows contribute exactly-zero
softmax weight).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...models.generation import (
    _GenCfg, _collect_params, _mm, _rms, _rope_at,
)
from .common import (
    PREFILL_TILE, ROW_BLOCKS, ROW_TILE, Family, paged_attention, write_slots,
)

__all__ = ["DenseGQAFamily"]


# -- compiled phases ----------------------------------------------------------

def _attend_lanes(q, kc, vc, pos, nh, nkv, sliding_window=0):
    """``models/generation.py:_attend`` with PER-TOKEN positions: q
    [b, s, nh, d] against the gathered block slots kc/vc [b, L, nkv, d]
    (vc: as wide as a family's values are).
    Slot ``l`` is visible to the query at absolute position ``p =
    pos[b, t]`` iff ``l <= p`` — block tables lay a lane's positions out
    in order, so slot index == absolute position for every allocated
    slot, and unallocated/pad slots sit above every real ``p``. The math
    (fp32 einsum, 1/sqrt(d), -1e30 mask, fp32 softmax/AV) mirrors
    ``_attend`` exactly so masked slots carry exactly-zero weight and
    engine outputs stay token-identical to ``generate()``. The programs
    read by rows (``_attend_rows``); this is what a row read must equal."""
    b, s, _, d = q.shape
    L = kc.shape[1]
    g = nh // nkv
    qg = q.reshape(b, s, nkv, g, d)
    logits = jnp.einsum("bskgd,blkd->bskgl", qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) / np.sqrt(d)
    vis = jnp.arange(L)[None, None, :] <= pos[:, :, None]  # [b, s, L]
    if sliding_window > 0:
        vis &= jnp.arange(L)[None, None, :] > pos[:, :, None] \
            - sliding_window
    logits = jnp.where(vis[:, :, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgl,blkd->bskgd", p, vc.astype(jnp.float32))
    return out.reshape(b, s, nh, -1).astype(q.dtype)


def _attend_rows(q, pos, rows, gather, tile, nkv, sliding_window=0):
    """``_attend_lanes`` over LIVE ROWS: what each lane holds, cut into
    rows of ``W`` blocks, and nothing else of its table. ``rows``
    [R, 2 + W] int32 is one row a line, live rows first: the lane whose
    query the row answers to (-1: a pad row, run only to fill the last
    tile, answering to nobody), the absolute position of its first slot,
    its ``W`` block ids (a lane's last row padded with null block 0).
    ``gather(blocks [T, W])`` returns those blocks' K and V as
    ``[T, W * B, nkv, d]``. q [b, s, nh, d], pos [b, s].

    Rows run ``tile`` at a time under a device-side loop whose trip count
    is data (the live rows, counted here): per row the fp32 scores of its
    lane's query against its slots, the mask ``first + slot <= pos`` (and
    the sliding window's lower edge), the row's max, its sum of
    exponentials and its weighted sum of V; per tile those are folded into
    each lane's running max / sum / weighted sum as a softmax over the
    union of the lane's slots (rescaled by ``exp(row_max - lane_max)``;
    added up by lane with a [lanes, rows] one-hot product). A masked slot
    weighs exp(-1e30 - max) = 0 exactly, and so does every slot of a
    wholly masked row once its lane has met a visible slot, before or
    after it. A lane with no row (idle) reads 0."""
    b, s, nh, d = q.shape
    g = nh // nkv
    f32 = jnp.float32
    lane, first, blocks = rows[:, 0], rows[:, 1], rows[:, 2:]
    tile = min(tile, rows.shape[0])  # engine.fit_rows: fewer rows, one tile
    assert rows.shape[0] % tile == 0, (rows.shape, tile)
    qg = q.reshape(b, s, nkv, g, d).astype(f32)

    def one_tile(t, carry):
        m, l, o = carry  # [b, s, nkv, g] twice, [b, s, nkv, g, d]
        r0 = t * tile
        ln = jax.lax.dynamic_slice_in_dim(lane, r0, tile)
        own = jnp.maximum(ln, 0)
        kc, vc = gather(jax.lax.dynamic_slice_in_dim(blocks, r0, tile))
        S = kc.shape[1]
        at = jax.lax.dynamic_slice_in_dim(first, r0, tile)[:, None, None] \
            + jnp.arange(S)[None, None, :]                 # [T, 1, S]
        p_own = pos[own][:, :, None]                       # [T, s, 1]
        vis = at <= p_own
        if sliding_window > 0:
            vis &= at > p_own - sliding_window
        logits = jnp.einsum("tskgd,tlkd->tskgl", qg[own],
                            kc.astype(f32)) / np.sqrt(d)
        logits = jnp.where(vis[:, :, None, None, :], logits, -1e30)
        rm = jnp.max(logits, axis=-1)                      # [T, s, nkv, g]
        p = jnp.exp(logits - rm[..., None])
        rl = jnp.sum(p, axis=-1)
        ro = jnp.einsum("tskgl,tlkd->tskgd", p, vc.astype(f32))
        mine = ln[None, :] == jnp.arange(b)[:, None]       # [b, T]
        m_new = jnp.maximum(m, jnp.max(
            jnp.where(mine[:, :, None, None, None], rm[None], -1e30),
            axis=1))
        # (a pad row may outscore lane 0's max: its weight is 0, not inf)
        w = jnp.exp(jnp.where((ln >= 0)[:, None, None, None],
                              rm - m_new[own], -1e30))
        keep = jnp.exp(m - m_new)
        hot = mine.astype(f32)
        exact = jax.lax.Precision.HIGHEST  # the one-hot sum is a sum
        l = l * keep + jnp.einsum("bt,tskg->bskg", hot, rl * w,
                                  precision=exact)
        o = o * keep[..., None] + jnp.einsum(
            "bt,tskgd->bskgd", hot, ro * w[..., None], precision=exact)
        return m_new, l, o

    n_tiles = (jnp.sum(lane >= 0, dtype=jnp.int32) + tile - 1) // tile
    _, l, o = jax.lax.fori_loop(
        0, n_tiles, one_tile,
        (jnp.full((b, s, nkv, g), -1e30, f32),
         jnp.zeros((b, s, nkv, g), f32),
         jnp.zeros((b, s, nkv, g, d), f32)))
    out = o / jnp.where(l > 0, l, 1.0)[..., None]
    return out.reshape(b, s, nh, d).astype(q.dtype)


def _pool_forward(params, kpool, vpool, kscale, vscale, read, ids,
                  pos, wlimit, cfg, tile):
    """Forward ``ids`` [b, s] at absolute positions ``pos`` [b, s]
    against the block pool: per layer, write each token's K/V into its
    lane's block at ``pos`` (writes at positions >= ``wlimit[b]`` — pad
    tail of a final prefill chunk, idle decode lanes — are redirected to
    null block 0 so they can never clobber live KV), then attend over
    the blocks the lanes HOLD: ``read`` is ``(rows, wblk)`` — the live
    rows of ``_attend_rows`` and, per token, the block its position falls
    in (``wblk`` [b, s], the host's lookup in the lane's block list) —
    and each row's blocks come out of the stacked pool by (layer, block):
    no value of one layer's pool shape is produced, nor
    one of every lane's whole table (tests/test_chip_compile.py holds the
    compiled programs to both). What a call reads follows the live
    blocks, not ``max_seq_len``. Layer math is
    ``models/generation.py:_block`` on the pooled layout.

    ``kscale``/``vscale`` are the int8 mode's paired fp32 scale pools
    (``[layers, num_blocks, block_size, kv_heads]``; None in bf16 mode
    — None is an empty pytree): writes quantize K/V per position through
    the shared `quantization.quantize_kv` (scale writes ride the same
    null-redirected ``blk``/``off``, null block included), reads
    dequantize the gathered rows before the same fp32 attention — the
    ops of ``generate(kv_int8=True)``'s round-trip. Returns
    (x [b, s, hidden], kpool, vpool, kscale, vscale)."""
    b, s = ids.shape
    nh = cfg.num_attention_heads
    nkv = cfg.num_key_value_heads or nh
    d = cfg.hidden_size // nh
    B = kpool.shape[2]
    dt = jnp.dtype(cfg.dtype)
    quant = kscale is not None
    scope = jax.named_scope  # the scopes: monitor/scopes.py
    with scope("embed"):
        x = params["embed"][ids].astype(dt)
    rows, wblk = read
    blk, off = write_slots(wblk, pos, wlimit, B, "attn/kv_write")
    n_layers = params["ln1"].shape[0]

    def body(carry, li):
        if quant:
            x, kp, vp, ks, vs = carry
        else:
            x, kp, vp = carry
            ks = vs = None
        layer_p = {}
        for k, where in (("ln1", "norm"), ("qkv", "attn/qkv"),
                         ("o", "attn/out"), ("ln2", "norm"),
                         ("gate_up", "mlp"), ("down", "mlp")):
            with scope(where):  # a leaf's slice: its consumer's scope
                layer_p[k] = jax.tree_util.tree_map(lambda a: a[li],
                                                    params[k])
        h = _rms(x, layer_p["ln1"], cfg.rms_norm_eps)
        with scope("attn/qkv"):
            qkv = _mm(h, layer_p["qkv"])
            q, k, v = jnp.split(qkv, [nh * d, nh * d + nkv * d], axis=-1)
            q = q.reshape(b, s, nh, d)
            k = k.reshape(b, s, nkv, d)
            v = v.reshape(b, s, nkv, d)
            q, k = _rope_at(q, k, pos, cfg.rope_theta)
        # a row's blocks come from the STACKED pool by (layer, block):
        # kp[li][...] makes the TPU materialise kp[li], the layer's whole
        # pool, before every gather (PERF.md section 6, PR 25). The pool's
        # dtype says which read: the bf16 pool keeps the heads merged
        # into its last axis and the kernel copies a row's blocks out of
        # it (``common.paged_attention``); the int8 pool and its scales
        # are indexed by the (layer, block) pair and dequantized a
        # gathered tile at a time
        if quant:
            from ...quantization import dequantize_kv, quantize_kv

            with scope("attn/kv_write"):
                k, k_s = quantize_kv(k)
                v, v_s = quantize_kv(v)
                ks = ks.at[li, blk, off].set(k_s)
                vs = vs.at[li, blk, off].set(v_s)
                kp = kp.at[li, blk, off].set(k)
                vp = vp.at[li, blk, off].set(v)

            def gather(blocks):
                T, W = blocks.shape
                return tuple(dequantize_kv(
                    c[li, blocks].reshape(T, W * B, nkv, d),
                    sc[li, blocks].reshape(T, W * B, nkv), dt)
                    for c, sc in ((kp, ks), (vp, vs)))

            with scope("attn/rows"):
                out = _attend_rows(q, pos, rows, gather, tile, nkv,
                                   sliding_window=cfg.sliding_window)
        else:
            out, kp, vp = paged_attention(
                q, k, v, li, kp, vp, rows, pos, blk, off, nkv, d ** -0.5,
                sliding_window=cfg.sliding_window)
        with scope("attn/out"):
            x = x + _mm(out.reshape(b, s, nh * d), layer_p["o"])
        h2 = _rms(x, layer_p["ln2"], cfg.rms_norm_eps)
        with scope("mlp"):
            gu = _mm(h2, layer_p["gate_up"])
            gate, up = jnp.split(gu, 2, axis=-1)
            x = x + _mm(
                jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
                * up, layer_p["down"])
        if quant:
            return (x, kp, vp, ks, vs), None
        return (x, kp, vp), None

    if quant:
        (x, kpool, vpool, kscale, vscale), _ = jax.lax.scan(
            body, (x, kpool, vpool, kscale, vscale),
            jnp.arange(n_layers))
    else:
        (x, kpool, vpool), _ = jax.lax.scan(
            body, (x, kpool, vpool), jnp.arange(n_layers))
    return x, kpool, vpool, kscale, vscale


def _pick(h, params):
    """The head product on normed ``h`` and the greedy pick."""
    with jax.named_scope("head"):
        logits = _mm(h, params["lm_head"]).astype(jnp.float32)
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _prefill_chunk(params, kpool, vpool, kscale, vscale, read, ids,
                   start, ctx_len, last_idx, *, cfg, tile):
    """One lane's prefill chunk: ``ids`` [1, C] at positions
    [start, start+C), ``read`` its lane's rows live up to the chunk's
    end; greedy-samples from position ``last_idx`` within
    the chunk (the overall last real token on the final chunk; ignored
    by the caller otherwise). Returns
    (tok [1], kpool, vpool, kscale, vscale)."""
    C = ids.shape[1]
    pos = (start + jnp.arange(C, dtype=jnp.int32))[None, :]
    x, kpool, vpool, kscale, vscale = _pool_forward(
        params, kpool, vpool, kscale, vscale, read, ids, pos,
        jnp.reshape(ctx_len, (1,)), cfg, tile=tile)
    with jax.named_scope("head"):
        x = _rms(x, params["norm"], cfg.rms_norm_eps)
        h = jax.lax.dynamic_index_in_dim(x, last_idx, axis=1,
                                         keepdims=False)
    return _pick(h, params), kpool, vpool, kscale, vscale


def _decode_step(params, kpool, vpool, kscale, vscale, read, cur_len,
                 last_tok, *, cfg, tile):
    """The shared decode step: every lane feeds its pending token at
    position ``cur_len`` (write-then-attend, so the token sees itself
    like ``generate()``'s step does) and greedy-samples the next. Idle
    lanes (cur_len 0, no row, write block 0) write to the null block and
    their outputs are ignored host-side. Returns
    (tok [L], kpool, vpool, kscale, vscale)."""
    pos = cur_len[:, None]
    x, kpool, vpool, kscale, vscale = _pool_forward(
        params, kpool, vpool, kscale, vscale, read, last_tok[:, None],
        pos, cur_len + 1, cfg, tile=tile)
    with jax.named_scope("head"):
        x = _rms(x, params["norm"], cfg.rms_norm_eps)[:, -1]
    return _pick(x, params), kpool, vpool, kscale, vscale


def _verify_step(params, kpool, vpool, kscale, vscale, read, cur_len,
                 toks, wlimit, *, cfg, tile):
    """The speculative verify step: ``toks`` [L, k+1] holds each lane's
    pending token (column 0) followed by its draft, at absolute
    positions ``cur_len + j``. Writes at positions >= ``wlimit[b]`` (=
    ``cur_len + 1 + draft_len``: the pad tail of a short/empty draft,
    idle lanes) go to the null block, exactly like a prefill chunk's pad
    tail — draft length is data, never shape. Write-then-attend per
    layer means draft token ``j`` attends over slots ``<= cur_len + j``,
    the same causal view plain decode would give it, so the returned
    greedy argmaxes [L, k+1] are the tokens the decode step WOULD emit
    after each draft prefix — the host's acceptance rule compares
    drafts against them directly."""
    S = toks.shape[1]
    pos = cur_len[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x, kpool, vpool, kscale, vscale = _pool_forward(
        params, kpool, vpool, kscale, vscale, read, toks, pos, wlimit,
        cfg, tile=tile)
    with jax.named_scope("head"):
        x = _rms(x, params["norm"], cfg.rms_norm_eps)
    return _pick(x, params), kpool, vpool, kscale, vscale


# -- the family ---------------------------------------------------------------

class DenseGQAFamily(Family):
    """See ``families/__init__.py`` for what the engine asks of it. The one
    family that packs its weights (``_collect_params``: stacked per leaf,
    int8 on request) and serves an int8 pool, so its ``__init__`` and its
    ``exec_key`` are its own."""

    name = "dense_gqa"
    tiled = True  # the int8 pool's XLA read runs its rows ``tile`` at a time

    def __init__(self, model, config):
        if getattr(model.config, "moe_num_experts", 0) > 1:
            from ...framework.errors import UnimplementedError

            raise UnimplementedError(
                "ServingEngine does not decode MoE Llama configs yet "
                "(same gap as models/generation.generate)")
        self.config = config
        self.gcfg = _GenCfg(model.config)
        self.params = _collect_params(model,
                                      int8_weights=config.int8_weights)
        self.layers = self.params["ln1"].shape[0]
        self.max_position_embeddings = model.config.max_position_embeddings
        self.donate_argnums = (1, 2, 3, 4) if config.kv_int8 else (1, 2)
        self.counters = {}  # nothing beyond the engine's own

    def make_pools(self, num_blocks, block_size):
        """(kpool, vpool, kscale, vscale). int8 mode: paired per-position
        fp32 amax scales (null block included — masked writes land there
        like K/V pad writes do); None in bf16 mode so the compiled
        programs stay byte-identical to the pre-int8 engine (None is an
        empty pytree operand)."""
        nh = self.gcfg.num_attention_heads
        nkv = self.gcfg.num_key_value_heads or nh
        d = self.gcfg.hidden_size // nh
        if not self.config.kv_int8:
            # the heads merged into the last axis, as the kernel takes it
            kpool = jnp.zeros((self.layers, num_blocks, block_size, nkv * d),
                              jnp.dtype(self.gcfg.dtype))
            return kpool, jnp.zeros_like(kpool), None, None
        kpool = jnp.zeros((self.layers, num_blocks, block_size, nkv, d),
                          jnp.int8)
        vpool = jnp.zeros_like(kpool)
        kscale = jnp.zeros((self.layers, num_blocks, block_size, nkv),
                           jnp.float32)
        return kpool, vpool, kscale, jnp.zeros_like(kscale)

    def kv_pool_bytes(self, pools):
        return int(sum(a.nbytes for a in pools if a is not None))

    def read_form(self, kind):
        """How program ``kind`` is told where its lanes' K/V lies
        (``ServingEngine._pack_read`` builds it): ``(W, tile)`` — live
        rows of ``W`` blocks, run ``tile`` at a time."""
        return ROW_BLOCKS, PREFILL_TILE if kind == "prefill" else ROW_TILE

    @property
    def row_read(self):
        """What reads its programs' live rows: ``"kernel"``
        (``ops/pallas/row_attention.py``), or ``"xla"`` — the int8 pool's
        read (``_attend_rows``)."""
        return "xla" if self.config.kv_int8 else "kernel"

    programs = {"prefill": _prefill_chunk, "decode": _decode_step,
                "verify": _verify_step}

    def exec_key(self, pools):
        """The family's part of an exec-cache key: its own, which names
        the one pool's form and marks the int8 mode."""
        from ...jit import exec_cache

        kpool, kscale = pools[0], pools[2]
        k = {"gen_cfg": self.gcfg._key(),
             "params": [exec_cache.array_spec(a) for a in
                        jax.tree_util.tree_leaves(self.params)],
             "pool": (tuple(int(x) for x in kpool.shape),
                      str(kpool.dtype))}
        if self.config.kv_int8:
            # the pool dtype above already splits int8 from bf16
            # entries; the explicit marker + scale spec make the
            # cache key self-describing (meta sidecar, audits)
            k["kv_int8"] = True
            k["scale"] = (tuple(int(x) for x in kscale.shape),
                          str(kscale.dtype))
        return k

    def stats(self):
        return {}
