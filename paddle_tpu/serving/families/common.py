"""What more than one serving family uses, and nothing else: the family's
frame (:class:`Family`), the grouped-query layer on the paged pool
(:func:`paged_attention`) with the constants that size its read, where
a fed position is written (:func:`write_slots`), the greedy head, the
expert layers' device accumulator, the verify round's accept rule and the
lane-state rules. A family module imports from here and from its model;
none imports another family (``linear_latent_moe`` alone takes the latent
layer's own functions from ``latent_moe``).

The families' programs call these by their bare names: a name a test
patches in a family's module (``_carried``, ``_keeps``, the row constants)
is resolved through THAT module's globals at call time, so nothing here
calls ``_carried`` or ``_keeps`` itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...framework.errors import UnimplementedError
from ...models.generation import _rms
from ...ops.pallas.row_attention import row_attention
from . import PREFILL_CHUNK

# The paged K/V read's constants, chosen on the chip (PERF.md section 6, PR
# 28): a row is ROW_BLOCKS blocks of one lane (wider rows make a 5-position
# verify call cheaper, narrower ones pad a lane's last row less), and a
# program runs its live rows ROW_TILE at a time (a tile costs ~9 us a
# layer to start and pads a call by half of itself on average; the
# kernel's grid is the live rows: there ``tile`` only rounds the operand's
# length); the prefill chunk, all rows one lane's: PREFILL_TILE. A family
# imports them BY VALUE and its ``read_form`` reads its own module's (the
# latent read's row width is ``latent_moe``'s).
ROW_BLOCKS = 16
ROW_TILE = 16
PREFILL_TILE = 4

# the expert layers' slots of a device accumulator, in the order
# ``expert_counts`` fills them
MOE_ACC = ("moe_assignments", "moe_assignments_held", "moe_expert_calls",
           "moe_load_max_sum")


# -- the family's frame -------------------------------------------------------

class Family:
    """What :class:`~paddle_tpu.serving.ServingEngine` asks a model's
    family for (``families/__init__.py`` states the protocol), with the
    answers that do not differ. A family writes its pools (``make_pools``,
    ``kv_pool_bytes``, ``lane_pool_bytes`` where it keeps any by lane,
    ``donate_argnums``), its three step programs (``programs``), its
    ``read_form`` and its ``stats``; it inherits the rest."""

    name = None
    title = None          # "the ... family", as its refusals name it
    lane_state = False    # every pool is indexed by (layer, block, offset)
    prefix_reuse = True   # a prefix's blocks are all a new request needs
    prefill_chunk = PREFILL_CHUNK  # the prefill call's width
    row_read = "xla"      # "kernel": ops/pallas/row_attention.py reads
    ACC = ()              # the device accumulator's slots, if it rides one
    programs = {}         # kind -> the step program's function
    tiled = False         # the programs take ``tile`` (read_form's) too

    def __init__(self, model, config):
        c = model.config
        self.gcfg = c.static()
        self.max_position_embeddings = c.max_position_embeddings
        self.lanes = config.max_lanes
        # the model's own arrays: ONE copy of the weights on the device
        # (a model without ``lm_head`` ties its head to the embedding)
        self.params = {
            "embed": model.embed._data, "norm": model.norm._data,
            "layers": tuple({k: p._data for k, p in blk.leaves().items()}
                            for blk in model.layers)}
        if hasattr(model, "lm_head"):
            self.params["lm_head"] = model.lm_head._data
        self.counters = dict.fromkeys(self.ACC, 0)
        self._seen = [0] * len(self.ACC)

    def refuse(self, config, reasons, tail=""):
        """Raise for an option of ``reasons`` (option -> why not) that
        ``config`` turns on: the int8 K/V pools and the int8 weight pack
        are the dense family's alone."""
        reasons = {**reasons, "int8_weights":
                   "the pack would be a second copy of the weights"}
        for option, why in reasons.items():
            if getattr(config, option):
                raise UnimplementedError(
                    f"{self.title} does not serve with {option}: "
                    f"{why}{tail}")

    def lane_pool_bytes(self, pools):
        return 0

    def program(self, kind):
        """(function, static keyword arguments) of one step program."""
        static = {"cfg": self.gcfg}
        if self.tiled:
            static["tile"] = self.read_form(kind)[1]
        return self.programs[kind], static

    def exec_key(self, pools):
        """The family's part of an exec-cache key."""
        from ...jit import exec_cache

        return {"family": self.name, "gen_cfg": self.gcfg._key(),
                "params": [exec_cache.array_spec(a) for a in
                           jax.tree_util.tree_leaves(self.params)],
                "pools": [exec_cache.array_spec(p) for p in pools]}

    def absorb(self, out, counters):
        """The round's ONE fetched array goes through here. A family that
        rides a device accumulator on it (``ACC``: the vector's last
        entries, int32, running totals) has what each slot grew by since
        the last fetch added to ``counters`` (modulo 2^32; ``_seen`` holds
        the last totals) and gets the tokens back; without one the fetched
        output IS its tokens."""
        n = len(self.ACC)
        if not n:
            return out
        for i, name in enumerate(self.ACC):
            now = int(out[out.size - n + i])
            counters[name] += (now - self._seen[i]) & 0xFFFFFFFF
            self._seen[i] = now
        return out[:out.size - n]


# -- the grouped-query layer on the paged pool -------------------------------

def write_slots(wblk, pos, wlimit, block, scope):
    """Where the fed positions ``pos`` [b, s] are written: (block, offset)
    [b, s] — ``wblk`` the block each position falls in (the host's lookup
    in the lane's block list), positions >= ``wlimit[b]`` (the pad tail of
    a final prefill chunk or a short draft, idle lanes) redirected to null
    block 0, so they can never clobber live entries. ``scope``: the
    caller's write scope (``attn/kv_write`` | ``mla/kv_write``)."""
    with jax.named_scope(scope):
        ok = pos < wlimit[:, None]
        return jnp.where(ok, wblk, 0), jnp.where(ok, pos % block, 0)


def paged_attention(q, k, v, layer, kpool, vpool, rows, pos, blk, off, nkv,
                    scale, sliding_window=0):
    """A grouped-query layer against the block pool: write the fed tokens'
    K/V by (``layer``, block, offset) (``write_slots``' ``blk`` / ``off``),
    then attend over the lanes' LIVE ROWS — the fused kernel
    ``ops/pallas/row_attention.py``, which copies a row's blocks out of the
    STACKED pools by (layer, block) itself (``kpool[layer]`` would make the
    TPU materialise the layer's whole pool: PERF.md section 6, PR 25). The
    pools keep the heads merged into their last axis (``[layers, blocks,
    block, nkv x d]``; V may be narrower than K). The q/k/v projection in
    front and the ``o`` behind are the caller's: they differ by
    architecture. Returns (att [b, s, heads, dv], kpool, vpool)."""
    b, s = pos.shape
    with jax.named_scope("attn/kv_write"):
        kpool = kpool.at[layer, blk, off].set(k.reshape(b, s, -1))
        vpool = vpool.at[layer, blk, off].set(v.reshape(b, s, -1))
    with jax.named_scope("attn/rows"):
        att = row_attention(q, pos, rows, kpool, vpool, layer, nkv, scale,
                            sliding_window=sliding_window)
    return att, kpool, vpool


# -- the head, the accumulator ------------------------------------------------

def greedy_head(x, params, eps, divisor=None):
    """The final norm, the head product in float32 (``lm_head``, or the
    embedding where the family's ``params`` hold none: a tied head),
    divided by the model's logit ``divisor`` if it states one, and the
    greedy pick."""
    with jax.named_scope("head"):
        x = _rms(x, params["norm"], eps)
        head = params["lm_head"] if "lm_head" in params \
            else params["embed"].T
        logits = (x @ head).astype(jnp.float32)
        if divisor is not None:
            logits = logits / divisor
    with jax.named_scope("sample"):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def expert_counts(n_valid, counts, top_k):
    """What one expert-layer call adds to the accumulator's ``MOE_ACC``."""
    with jax.named_scope("acc"):
        return jnp.stack([n_valid * top_k, jnp.sum(counts), jnp.int32(1),
                          jnp.max(counts)])


def bump(acc, names, offset, **by):
    """``acc`` with its slots ``names`` (which start at ``offset``) grown
    ``by`` name; a slot not named grows by 0."""
    with jax.named_scope("acc"):
        grown = jnp.stack([jnp.asarray(by.get(n, 0), jnp.int32)
                           for n in names])
        return acc.at[offset:].add(grown) if offset else acc + grown


def _out(picks, acc):
    """A program's fetched vector: its picks, then the accumulator."""
    with jax.named_scope("acc"):
        return jnp.concatenate([picks.reshape(-1), acc])


# -- a verify round, and the state a family keeps by lane ---------------------
#
# A ``lane_state`` family's verify program owes the engine the rollback
# contract of ``ServingEngine._verify_round``: a masked position is the
# identity on the family's lane state. For a recurrent state that is a
# gate set to 0 from the first rejected position on (the family's own
# docstring). For a ring of ``R`` slots (position ``p`` in slot ``p mod
# R``) under a window of ``W`` positions and ``k`` drafts a round it is an
# inequality, not an update: with ``R >= W + k`` what a rejected draft
# wrote reads, to every later query, as a position outside the band, and
# is overwritten before the band reaches it (the family takes ``R >= W + k
# + 1``). For a conv tail it is a choice of rows: the verify program holds
# each conv layer's ``[tail | k+1 positions]`` window until the head has
# given the lane's ``n_keep`` (``_keeps``: its pending token and its
# accepted drafts; 0 for an idle lane) and sets the tail to the window's
# rows ``n_keep .. n_keep + L - 2`` (``_take_rows``) — the rows that end at
# the last kept position, the lane's own tail where nothing is kept — so a
# rejected position is in no tail. And a prefill chunk at position 0 starts
# its slot from zero (``_carried``), so an admitted or re-admitted request
# never sees its lane's predecessor.

def accept(picks, toks, cur_len, wlimit):
    """``engine._accept``'s rule on the device: a lane keeps its pending
    token and the longest prefix of its draft (``toks[:, 1:]``, of which
    ``wlimit - cur_len - 1`` are real) that equals the program's own
    ``picks``. The engine stays the judge of what is emitted; a program
    reads this to know which positions its lane state may take up, and to
    count. Returns (live [L], n_draft [L]: -1 an idle lane, accepted
    [L])."""
    with jax.named_scope("spec"):
        n_draft = wlimit - cur_len - 1
        hit = (picks[:, :-1] == toks[:, 1:]) \
            & (jnp.arange(toks.shape[1] - 1)[None, :] < n_draft[:, None])
        accepted = jnp.sum(jnp.cumprod(hit.astype(jnp.int32), axis=1),
                           axis=1)
        return n_draft >= 0, n_draft, accepted


def rolled_back(live, n_draft, accepted):
    """The round's drafted positions that no lane keeps."""
    return jnp.sum(jnp.where(live, n_draft - accepted, 0))


def _carried(fresh, kept):
    """What a prefill chunk starts from: what the slot kept, or zero
    where the chunk is its request's first."""
    return jnp.where(fresh, 0, kept)


def _keeps(live, accepted):
    """How many of a verify round's positions a lane's state and conv
    tail take up: its pending token and its accepted drafts; none where
    the lane is idle."""
    return jnp.where(live, 1 + accepted, 0)


def _take_rows(window, first, n):
    """``window[b, first[b] : first[b] + n]`` for every row ``b``."""
    idx = first[:, None] + jnp.arange(n)[None, :]
    return jnp.take_along_axis(window, idx[:, :, None], axis=1)


def lane_tails(pool, layer, taps):
    """Conv layer ``layer``'s tails of a ``[layers, lanes, (taps - 1) x
    channels]`` pool (a lane's rows side by side) as ``[lanes, taps - 1,
    channels]``."""
    return pool[layer].reshape(pool.shape[1], taps - 1, -1)
