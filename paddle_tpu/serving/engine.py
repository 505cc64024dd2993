"""Continuous-batching decode engine over the block KV pool.

The millions-of-users path (ROADMAP item 1): requests of unequal prompt
and output lengths share ONE compiled decode step — which blocks a lane
holds and its valid length are runtime *data*, so admission, eviction,
and growth never retrace. Every program call is told where its lanes'
K/V lies by the lanes' LIVE ROWS — each lane's block list cut into rows
of a few blocks (the family's ``read_form``), all lanes' rows end to end
(:func:`pack_rows`), so a call gathers what the lanes hold and not every
slot of every lane's table. A family that also keeps state per
LANE (``lane_state``: a recurrent state and conv tail, the hybrid
state-space family's and the linear-attention family's; a ring of a
window layer's last keys, the window-attention family's; a bare conv
tail, the short-convolution family's) has its one-lane
prefill chunk told which lane the request holds. Three compiled programs
serve the whole lifetime:

- **prefill chunk** ``[1, C]``: one lane's context enters the pool C
  tokens at a time (padded tail chunks write only below the context
  length — pads are redirected to the null block), and the final chunk
  samples the first generated token from the last real position. With
  the prefix cache on (``PT_SERVE_PREFIX_CACHE``, default) prefill
  starts at the first token not covered by acquired shared blocks —
  a fully cached system prompt costs zero prefill chunks beyond its
  private tail.
- **decode step** ``[L, 1]``: every occupied lane advances one token —
  write the pending token's K/V at ``pool_len``, attend over the blocks
  the lane holds masked to ``slot <= pos``, greedy-sample the next.
- **verify step** ``[L, k+1]`` (speculative decoding, ``PT_SERVE_SPEC``
  — docs/SERVING.md): when the host-side drafter
  (:mod:`.speculative`) proposed tokens for any lane, every lane's
  pending token plus its (possibly empty) draft is scored in one pass;
  the host accepts each lane's longest prefix matching the program's
  own greedy argmaxes, plus one bonus token. Draft length is DATA:
  short/empty drafts pad up to ``k`` with writes redirected to the
  null block (``wlimit``), so a no-draft lane verifies exactly one
  token and churn in draft lengths never retraces. Rejected positions
  roll back per kind of cache: K/V by rewinding ``pool_len`` only — the
  tail blocks are lane-private (shared prefix blocks are full + frozen),
  so over-written K/V was never shared and the next accepted write
  simply overwrites it; a family's LANE-indexed recurrent state by the
  verify program itself, which takes up the pending token and the
  accepted drafts and nothing else (:meth:`ServingEngine._verify_round`).

A call's operands — where its lanes' K/V lies, and the lengths, tokens
and limits above — are all ``int32`` and of static shape, and reach the
device as ONE array in ONE transfer (:class:`OperandLayout`; the compiled
program, :func:`packed_program` around the family's function, cuts it
apart by static slices): a transfer costs the host 0.2 ms whatever its
size, with the chip idle (PERF.md section 6, PR 43).

All three compile through :func:`paddle_tpu.jit.exec_cache.get_or_compile`
(keyed on generation config, param avals, pool geometry, lane count and
mesh), so a warm ``PT_EXEC_CACHE`` server start pays zero fresh XLA
compiles. The programs themselves — the layer math, the cache a token
takes in a layer, how the weights are collected — are the model's
FAMILY's (``serving/families``: the dense grouped-query decoder whose
outputs are token-identical to per-request ``generate()`` calls, the
latent-attention sparse-expert decoder, the hybrid state-space /
attention decoder, the linear-attention, the window-attention and the
short-convolution sparse-expert decoders); this module is what every
family shares and names no architecture.

Reference lineage: the static-graph serving surface this replaces is
`paddle_infer.Predictor` (`paddle/fluid/inference/api/
analysis_predictor.h:94` — see ``paddle_tpu/inference``); request-level
continuous batching + block KV follow the Orca/vLLM iteration-level
scheduling + PagedAttention memory model (docs/SERVING.md).

Monitor contract: this module carries ``_monitor``/``_spans``
None-slots (``serving/*`` counters + request-lifecycle spans,
``monitor.INSTRUMENTED_MODULES``) — when monitoring is off no monitor
callable is ever invoked; the always-on plain-int
``ServingEngine.counters`` and per-request latency attribution
(``Request.queue_ms``/``prefill_ms``/``decode_ms``/``preempted_ms``,
telescoped at the phase boundaries the engine already timestamps) feed
the serving bench instead. Every phase of :meth:`ServingEngine.step`
(admit, prefill, first-token fetch, grow, draft, pack, dispatch, token
fetch, emit) is one ``monitor/spans.Phase``: always a
``jax.profiler.TraceAnnotation`` (recorded only while a profiler
session is on, in the device trace's own file) and a float of wall
seconds in ``counters``; a constant number a round, none per lane,
token or chunk. With ``PT_MONITOR=1`` the phases land in the flight
recorder too, next to every request's journey on its own
``req/<trace_id>`` lane — queue/requeue waits (scheduler-side), prefill
chunks with their prefix-cache hit/miss split, preemptions, and a
whole-journey finish span carrying the attribution breakdown
(docs/OBSERVABILITY.md). On an engine raise the
blackbox postmortem (``monitor/blackbox.py``) serializes the last
spans + scheduler state to ``serving_blackbox.json`` before the error
propagates.

Greedy decode only for now: per-request sampling params would ride as
traced lane vectors (same no-retrace discipline); left for a later PR.
"""
from __future__ import annotations

import collections
import functools
import math
import os
import sys
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..framework.device import on_tpu
from ..monitor import _register as _monitor_register
from ..monitor import blackbox as _blackbox
from ..monitor import live as _live_telemetry
from ..monitor.spans import Phase
from .families import PREFILL_CHUNK, family_for
from .kv_cache import BlockPool, blocks_needed
from .scheduler import RUNNING, FCFSScheduler, Request
from .speculative import LaneContext, NgramDrafter

_EMPTY_DRAFT = np.zeros((0,), np.int32)

__all__ = ["ServingConfig", "ServingEngine"]

# telemetry slots (paddle_tpu.monitor None-slot contract): None unless
# PT_MONITOR wired them. `_live` is the streaming-SLO sibling
# (monitor/live.py): armed by PT_LIVE_TELEMETRY / PT_METRICS_PORT /
# PT_SLO_* independently of PT_MONITOR — its feeds ride the always-on
# Request attribution stamps, so arming it costs three guarded calls
# per step and nothing when off.
_monitor = None
_spans = None
_live = None


def _env_int(name, default):
    v = os.environ.get(name)
    return int(v) if v else default


class ServingConfig:
    """Engine geometry. Every field has a ``PT_SERVE_*`` env default so a
    server deploy tunes without code (CLAUDE.md knob table):

    - ``max_lanes`` (``PT_SERVE_LANES``, 8): decode-batch width — lanes
      are the compiled step's batch dimension.
    - ``block_size`` (``PT_SERVE_BLOCK``, 16): tokens per KV block.
    - ``num_blocks`` (``PT_SERVE_BLOCKS``): pool size incl. the reserved
      null block; default sizes every lane for ``max_seq_len`` (no
      preemption pressure — shrink it to trade HBM for requeues).
    - ``prefill_chunk`` (``PT_SERVE_PREFILL_CHUNK``; default: the
      model's FAMILY's ``prefill_chunk`` — 512 for the window-attention
      and the short-convolution families, whose call reads every held
      expert —, :data:`PREFILL_CHUNK`, 128, for a family that names
      none): prefill program width; prompts enter in ceil(len/chunk)
      calls, and a call reads every weight whatever its width, so the
      default is as wide as that read pays for on the chip (PERF.md
      section 6, PR 32 and PR 42). Left unset (``None`` here) the engine
      fits the default to its geometry — whole blocks, never past
      ``max_seq_len`` rounded down to whole blocks
      (:func:`default_prefill_chunk`; ``ServingEngine.prefill_chunk`` is
      the width in use); a width given here or in the environment is
      taken as given.
    - ``max_seq_len`` (``PT_SERVE_MAX_LEN``): per-request prompt+output
      ceiling; defaults to the model's max_position_embeddings.
    - ``int8_weights`` (``PT_DECODE_INT8``): weight-only int8 matmuls,
      same lever as ``generate()``.
    - ``kv_int8`` (``PT_SERVE_KV_INT8``, off): int8 block pool — K/V
      quantize on write (per-position symmetric amax over head_dim,
      `quantization.quantize_kv`; fp32 scales ride in paired
      ``[layers, num_blocks, block_size, kv_heads]`` scale pools) and
      dequantize on read, halving pool HBM at fixed ``num_blocks``.
      Token-identical to ``generate(kv_int8=True)`` — the quantize-
      aware reference (tests/test_serving_kv_int8.py); dtype is a
      static exec-cache key, so churn still never retraces and a fleet
      still pays exactly 3 fresh compiles. Off = today's engine, byte
      for byte. docs/SERVING.md "int8 KV".
    - ``prefix_cache`` (``PT_SERVE_PREFIX_CACHE``, on): ref-counted
      prefix sharing in the block pool — requests whose context starts
      with already-cached full blocks (shared system prompts, few-shot
      headers, recompute re-admissions) skip prefilling them
      (docs/SERVING.md). ``0`` restores the share-nothing pool.
    - ``spec`` (``PT_SERVE_SPEC``, auto): speculative decoding —
      ``"auto"`` engages it for the greedy path (which is all the
      engine decodes today), ``0``/``off`` disables. ``spec_k``
      (``PT_SERVE_SPEC_K``, 4) caps tokens proposed per lane per
      round; ``spec_k=0`` degenerates to plain decode (no verify
      program is compiled). docs/SERVING.md.
    """

    def __init__(self, max_lanes=None, block_size=None, num_blocks=None,
                 prefill_chunk=None, max_seq_len=None, int8_weights=None,
                 prefix_cache=None, spec=None, spec_k=None, kv_int8=None):
        self.max_lanes = max_lanes if max_lanes is not None \
            else _env_int("PT_SERVE_LANES", 8)
        self.block_size = block_size if block_size is not None \
            else _env_int("PT_SERVE_BLOCK", 16)
        self.num_blocks = num_blocks if num_blocks is not None \
            else _env_int("PT_SERVE_BLOCKS", 0) or None
        # None: the engine's own default, fitted to its geometry
        self.prefill_chunk = prefill_chunk if prefill_chunk is not None \
            else _env_int("PT_SERVE_PREFILL_CHUNK", 0) or None
        self.max_seq_len = max_seq_len if max_seq_len is not None \
            else _env_int("PT_SERVE_MAX_LEN", 0) or None
        if int8_weights is None:
            int8_weights = os.environ.get("PT_DECODE_INT8") == "1"
        self.int8_weights = bool(int8_weights)
        if kv_int8 is None:
            kv_int8 = os.environ.get("PT_SERVE_KV_INT8") == "1"
        self.kv_int8 = bool(kv_int8)
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "PT_SERVE_PREFIX_CACHE", "1") not in ("0", "off")
        self.prefix_cache = bool(prefix_cache)
        if spec is None:
            spec = os.environ.get("PT_SERVE_SPEC", "auto")
        # "auto" == on: the engine is greedy-only, and greedy is exactly
        # where verification preserves token identity for free
        self.spec = spec not in (False, 0, "0", "off")
        self.spec_k = spec_k if spec_k is not None \
            else _env_int("PT_SERVE_SPEC_K", 4)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_k == 0:
            self.spec = False  # k=0 IS plain decode; skip the program
        for name in ("max_lanes", "block_size", "prefill_chunk"):
            v = getattr(self, name)
            if v is not None and v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")


def default_prefill_chunk(max_seq_len, block_size, width=PREFILL_CHUNK):
    """``width`` (a family's ``prefill_chunk``, or :data:`PREFILL_CHUNK`)
    fitted to an engine's geometry: whole blocks (at least one), and no
    wider than ``max_seq_len`` rounded down to whole blocks — a model that
    serves 48 tokens gets a 48-token call, not 80 positions of padding in
    every one."""
    cap = min(width, max_seq_len) // block_size
    return max(cap, 1) * block_size


def fit_rows(form, lanes, blocks_per_lane):
    """A family's ``read_form`` ``(W, tile)`` fitted to an engine's
    geometry, as ``(W, tile, R)``: a row no wider than a lane's table, a
    tile no larger than every lane's whole table cut into rows, and
    ``R`` those rows in whole tiles (the ``rows`` operand's length)."""
    w = min(form[0], blocks_per_lane)
    rows = lanes * -(-blocks_per_lane // w)
    tile = min(form[1], rows)
    return w, tile, -(-rows // tile) * tile


def pack_rows(items, lanes, width, block, w, cap):
    """The live-rows read operand of one program call (the kernel
    ``ops/pallas/row_attention.py`` and the int8 pool's ``_attend_rows``
    read it), as numpy. ``items``: per
    occupied lane ``(lane, blocks, first, upto)`` — its block list (a
    list, or an ``int32`` array of it: ``scheduler.BlockList.ids``, which
    is copied where a list is converted), the
    position of its first token this call, and the slots ``[0, upto)``
    its queries may see. Returns ``(rows [cap, 2 + w], wblk [lanes,
    width], live rows, live blocks)``: each lane's blocks below ``upto``
    cut into rows of ``w`` (lane, first slot's position, ``w`` block
    ids; the last row null-padded), all lanes' rows end to end, then pad
    rows (lane -1); and the block each of the call's ``width`` positions
    from ``first`` falls in (0 past the lane's list: such writes are
    redirected anyway)."""
    ids = np.zeros((cap * w,), np.int32)
    wblk = np.zeros((lanes, width), np.int32)
    owners, counts = [], []
    n = live = 0
    for lane, blocks, first, upto in items:
        nb = -(-upto // block)
        ids[n * w:n * w + nb] = blocks[:nb]
        owners.append(lane)
        counts.append(-(-nb // w))
        n += counts[-1]
        live += nb
        # block by block, not position by position: block lo + k holds
        # the call's positions [(lo + k) * block - first, + block)
        lo = first // block
        at = lo * block - first
        for b in blocks[lo:(first + width - 1) // block + 1]:
            wblk[lane, max(at, 0):at + block] = b
            at += block
    rows = np.empty((cap, 2 + w), np.int32)
    rows[:n, 0] = np.repeat(owners, counts)
    rows[n:, 0] = -1
    # a row's place within its lane, times the slots a row spans
    starts = np.cumsum(counts) - counts
    rows[:n, 1] = (np.arange(n) - np.repeat(starts, counts)) * (w * block)
    rows[n:, 1] = 0
    rows[:, 2:] = ids.reshape(cap, w)
    return rows, wblk, n, live


class OperandLayout(typing.NamedTuple):
    """Where each operand of a step-program call lies in the call's ONE
    packed ``int32`` vector. A program's operands — its read operand
    (:meth:`ServingEngine._pack_read`) and its kind's own (lengths and
    tokens; a chunk, its start, the context length, the last index) — are
    all ``int32``, their shapes static, and a transfer costs the host the
    same whatever its size (PERF.md section 6, PR 28 and PR 43): they go
    up end to end in one array (:meth:`pack`), and the compiled program
    cuts it apart by static slices (:meth:`unpack`,
    :func:`packed_program`). Built once an engine and kind, from the
    operands' shapes (:meth:`of`); hashable, so two engines of one
    geometry share a traced program."""

    tree: typing.Any   # the operands' pytree structure
    cuts: tuple        # per leaf: (offset, shape)
    size: int          # the packed vector's length

    @classmethod
    def of(cls, spec):
        """The layout of operands shaped like ``spec`` (a pytree of
        ``jax.ShapeDtypeStruct``, in the order the program takes them)."""
        leaves, tree = jax.tree_util.tree_flatten(spec)
        cuts, n = [], 0
        for leaf in leaves:
            assert leaf.dtype == jnp.int32, leaf
            cuts.append((n, tuple(leaf.shape)))
            n += math.prod(leaf.shape)
        return cls(tree, tuple(cuts), n)

    def pack(self, operands) -> np.ndarray:
        """``operands`` (numpy; a scalar may be a plain int) laid end to
        end. A FRESH buffer a call: prefill calls are enqueued without a
        sync, and the CPU backend may alias host memory, so a reused
        buffer would be overwritten under a program in flight."""
        leaves = jax.tree_util.tree_leaves(operands)
        if [np.shape(a) for a in leaves] != [c[1] for c in self.cuts]:
            raise ValueError(
                f"operands shaped {[np.shape(a) for a in leaves]} do not "
                f"fit the program's {[c[1] for c in self.cuts]}")
        return np.concatenate([np.ravel(a) for a in leaves], dtype=np.int32)

    def unpack(self, packed):
        """Inside the program: the operands back out of ``packed``, by
        static slices, in the structure the family's function takes."""
        return self.tree.unflatten([
            packed[lo] if shape == () else jax.lax.slice(
                packed, (lo,), (lo + math.prod(shape),)).reshape(shape)
            for lo, shape in self.cuts])


@functools.lru_cache(maxsize=None)
def packed_program(fn, layout):
    """A family's step function ``fn(params, *pools, read, *operands,
    **static)`` as the engine compiles it: ``(params, *pools, packed,
    **static)`` — params and pools where they were (``donate_argnums``
    holds), the operands as ONE ``int32`` vector laid out by ``layout``.
    It wears ``fn``'s name: the compiled module (``jit__decode_step``),
    the registry of ``monitor/scopes.py`` and every scope path
    (``jit(_decode_step)/...``) are keyed by it. The slices run under
    ``embed`` (the fed tokens and positions: group ``head``). Cached, so
    engines of one geometry trace a program once."""
    def program(params, *args, **static):
        *pools, packed = args
        with jax.named_scope("embed"):
            operands = layout.unpack(packed)
        return fn(params, *pools, *operands, **static)

    program.__name__ = fn.__name__
    program.__qualname__ = fn.__qualname__
    return program


# -- the engine ---------------------------------------------------------------

class ServingEngine:
    """Submit requests, call :meth:`step` (or :meth:`run`) — the engine
    admits, prefills, decodes, and reclaims between steps. See the
    module docstring for the execution model and docs/SERVING.md for
    the operational guide."""

    def __init__(self, model, config: ServingConfig | None = None,
                 drafter=None):
        self.model = model
        self.config = config or ServingConfig()
        cfg = self.config
        # what differs between architectures — the cache a token takes,
        # the collected parameters, the step programs — is the model's
        # family's (serving/families); everything below is shared
        fam = self._family = family_for(model, cfg)
        self._gcfg = fam.gcfg
        self._params = fam.params
        self.max_seq_len = int(cfg.max_seq_len
                               or fam.max_position_embeddings)
        self.blocks_per_lane = blocks_needed(self.max_seq_len,
                                             cfg.block_size)
        # the width is the family's (what its call reads against what a
        # token uses is its own layers'), unless the deployer gave one
        self.prefill_chunk = int(cfg.prefill_chunk or default_prefill_chunk(
            self.max_seq_len, cfg.block_size,
            fam.prefill_chunk))
        num_blocks = int(cfg.num_blocks
                         or cfg.max_lanes * self.blocks_per_lane + 1)
        # the device state every step program threads through (the
        # family's pools; an entry may be None), replaced after each call
        self._pools = tuple(fam.make_pools(num_blocks, cfg.block_size))
        # device state by how it is indexed: by token (layer, block,
        # offset: what the block pool manages) and by lane
        self.kv_pool_bytes = fam.kv_pool_bytes(self._pools)
        self.lane_pool_bytes = fam.lane_pool_bytes(self._pools)
        # a family whose requests need more than a prefix's blocks to
        # start from it (recurrent state) acquires none: cached_len 0
        self.scheduler = FCFSScheduler(
            BlockPool(num_blocks, cfg.block_size), cfg.max_lanes,
            self.blocks_per_lane, self.max_seq_len,
            prefix_cache=cfg.prefix_cache and fam.prefix_reuse)
        # live (waiting/running) requests only; finished ones move to
        # _finished until collected — a long-running server must not
        # grow with its request history
        self._requests: dict = {}
        self._finished: dict = {}
        # newest finished journeys for the blackbox postmortem —
        # independent of _finished, which pop_finished() clears
        self._journeys: collections.deque = collections.deque(maxlen=16)
        self._prefill_exec = None
        self._decode_exec = None
        self._verify_exec = None
        # kind -> OperandLayout of the program's one packed operand
        self._layouts: dict = {}
        # speculative decoding (docs/SERVING.md): active iff configured
        # on AND k > 0; the drafter slot is pluggable (a draft model
        # would implement Drafter.propose) — default prompt-lookup
        self.spec_active = bool(cfg.spec and cfg.spec_k > 0)
        self.drafter = drafter if drafter is not None \
            else (NgramDrafter() if self.spec_active else None)
        # a drafter's optional hooks, looked up once. begin() opens the
        # state a request keeps from its first draft to its finish
        # (NgramDrafter: an index that grows with the context); without
        # it the drafter's propose() gets the lane's whole context a
        # round, from a buffer that grows in place
        self._begin_draft = getattr(self.drafter, "begin", None)
        if self._begin_draft is None and self.drafter is not None:
            self._begin_draft = functools.partial(
                LaneContext, self.drafter.propose)
        self._observe_draft = getattr(self.drafter, "observe", None)
        # always-on plain-int accounting (the serving bench's source of
        # truth; independent of the monitor like exec_cache._stats).
        # Per program call (rounds and prefill chunks): kv_read_tokens
        # the LIVE tokens its lanes hold, kv_gathered_tokens the slots
        # it gathers (rows run x row width), kv_dense_read_tokens what
        # a gather of every
        # lane's whole table reads (idle lanes' too: the full-table
        # read this engine had gathered those). gathered / read is the
        # read's amplification, gathered / dense the share of the table
        # still read (_pack_read bills all three, and kv_kernel_rows:
        # the live rows handed to a program whose read is the fused
        # kernel, ops/pallas/row_attention.py; 0 where XLA reads them).
        # prefix_{hit,miss}_tokens split every (re-)prefilled context:
        # hit = tokens served by acquired shared blocks (no compute),
        # miss = tokens actually pushed through the prefill program —
        # the bench's prefix_hit_rate numerator/denominator.
        # prefill_fed_tokens = prefill_chunks x the program's width, pad
        # positions included: miss / fed is how full the prefill calls
        # run, prefill_chunks / admits how many calls a prompt takes.
        # spec_{proposed,accepted}_tokens are post-trim (what the verify
        # step actually speculated) so accepted/proposed IS the accept
        # rate; bonus counts the +1 token a drafted lane's verification
        # emitted on top of its accepted prefix. draft_* are the n-gram
        # index's own (speculative.NgramIndex, handed this dict): every
        # context token indexed once, and each lookup (one per lane per
        # round with room to draft) by the length that matched, or missed.
        self.counters = {
            "admits": 0, "finished": 0, "preemptions": 0,
            "prefill_chunks": 0, "decode_steps": 0, "verify_steps": 0,
            "decoded_tokens": 0,
            "spec_proposed_tokens": 0, "spec_accepted_tokens": 0,
            "spec_bonus_tokens": 0,
            "draft_indexed_tokens": 0, "draft_hits_ngram3": 0,
            "draft_hits_ngram2": 0, "draft_hits_ngram1": 0,
            "draft_misses": 0,
            "prefix_hit_tokens": 0, "prefix_miss_tokens": 0,
            "prefill_fed_tokens": 0,
            "kv_read_tokens": 0, "kv_gathered_tokens": 0,
            "kv_dense_read_tokens": 0, "kv_kernel_rows": 0,
            "kv_quant_writes": 0, "kv_quant_tokens": 0,
            # arrays (and their bytes) handed to the device by step-program
            # calls: one a call (_operand), so operand_uploads ==
            # decode_steps + verify_steps + prefill_chunks
            "operand_uploads": 0, "operand_upload_bytes": 0,
            # wall seconds per phase of step() (monitor/spans.Phase):
            # they telescope to step_s up to the statements between
            # them; dispatch_s + fetch_s is a round's launch-to-tokens
            "step_s": 0.0, "admit_s": 0.0, "prefill_s": 0.0,
            "first_fetch_s": 0.0, "grow_s": 0.0, "draft_s": 0.0,
            "pack_s": 0.0, "dispatch_s": 0.0, "fetch_s": 0.0,
            "emit_s": 0.0,
            # the family's own (fetched with the round's tokens)
            **fam.counters,
        }
        # postmortem hook: on an engine raise (or an external crash
        # site) the blackbox dump snapshots scheduler + request state
        # through this weakly-held provider (monitor/blackbox.py)
        _blackbox.register("serving_engine", self._blackbox_state)
        # /statusz hook: same weak-provider pattern for the live
        # exporter's debug page (stats() is plain-int and read-only)
        _live_telemetry.register_status("serving_engine", self.stats)

    # -- intake --------------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
               request_id=None) -> Request:
        """Queue one request (prompt as a 1-D int Tensor/array/list).
        Returns the :class:`Request`; drive it with :meth:`step` /
        :meth:`run`."""
        if isinstance(prompt_ids, Tensor):
            prompt_ids = prompt_ids.numpy()
        req = Request(prompt_ids, max_new_tokens=max_new_tokens,
                      eos_token_id=eos_token_id, request_id=request_id)
        if (req.request_id in self._requests
                or req.request_id in self._finished):
            raise ValueError(
                f"duplicate request_id {req.request_id!r} (live or "
                f"finished-but-uncollected — pop_finished() first)")
        req.t_submit = time.perf_counter()
        req._t_mark = req.t_submit  # attribution clock starts here
        self.scheduler.submit(req)
        self._requests[req.request_id] = req
        return req

    # -- compilation ---------------------------------------------------------

    def warmup(self) -> None:
        """Compile (or exec-cache-load) both phase programs now, so the
        first request — and the bench's timed window — pays no XLA
        compile."""
        self._ensure_compiled()

    def _ensure_compiled(self) -> None:
        if self._decode_exec is not None:
            return
        from ..jit import exec_cache

        cfgv, fam = self.config, self._family
        L, M, C = cfgv.max_lanes, self.blocks_per_lane, self.prefill_chunk
        # donation halves pool HBM traffic; XLA:CPU can't donate these
        # and would warn per call. Which operands churn write-for-write
        # with the cache (int8 mode: the scale pools too) is the family's
        donate = on_tpu()
        pools = tuple(None if a is None
                      else jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for a in self._pools)
        fam_key = fam.exec_key(self._pools) if exec_cache.enabled() else None

        def key(kind, **extra):
            if fam_key is None:
                return None
            return {"kind": kind, **fam_key, "donate": donate,
                    "mesh": exec_cache.mesh_spec(), **extra}

        def extra(static):  # what of a program's statics its key names
            return {k: v for k, v in static.items() if k != "cfg"}

        def build(kind, **geometry):
            """Program ``kind`` (``geometry``: what its key names): the
            family's function behind ONE packed operand
            (:func:`packed_program`), laid out by :meth:`_layout`."""
            fn, static = fam.program(kind)
            layout = self._layout(kind)
            kw = {"static_argnames": tuple(static)}
            if donate:
                kw["donate_argnums"] = fam.donate_argnums
            program = jax.jit(packed_program(fn, layout), **kw)
            # the key names the operands' form: an executable serialized
            # for the tuple of arrays is never loaded for the packed one
            return exec_cache.get_or_compile(
                key("serving_" + kind, operands=("packed", layout.size),
                    **geometry, **extra(static)),
                lambda: program.lower(
                    self._params, *pools,
                    jax.ShapeDtypeStruct((layout.size,), jnp.int32),
                    **static),
                label="serving/" + kind)

        self._decode_exec = build("decode", lanes=L, m=M)
        self._prefill_exec = build("prefill", m=M, chunk=C)
        if self.spec_active:
            self._verify_exec = build("verify", lanes=L, m=M,
                                      k=cfgv.spec_k)

    # -- the step loop -------------------------------------------------------

    def step(self) -> bool:
        """One scheduling round: admit + prefill newly admitted lanes
        (they join this same round's decode — continuous batching), run
        the shared decode step, emit/reclaim. Returns whether any work
        was done. Admission is one lane at a time with the prefill (and
        its prefix publish) in between, so burst arrivals sharing a
        prompt hit the cache from the second lane on.

        On a raise (pool double-free, invariant break, a bad drafter)
        the blackbox postmortem is written BEFORE the error propagates
        — the artifact, not the traceback, is what holds the request
        journeys and scheduler state that explain the crash."""
        try:
            return self._step()
        except Exception as exc:
            _blackbox.maybe_dump(reason="serving_engine_raise",
                                 error=exc)
            raise

    def _phase(self, name, key, cat="serving_phase", lane="serve/rounds",
               **args):
        """One phase of :meth:`step` (``monitor/spans.Phase``): a
        ``serving/<name>`` annotation in any live ``jax.profiler``
        session, its wall seconds in ``counters[key]``, and the ring
        when ``PT_MONITOR`` filled the slot."""
        return Phase("serving/" + name, self.counters, key, _spans, cat,
                     lane, **args)

    def _step(self) -> bool:
        self._ensure_compiled()
        worked = False
        with self._phase("step", "step_s"):
            while True:
                with self._phase("admit", "admit_s"):
                    admitted = self.scheduler.admit(limit=1)
                if not admitted:
                    break
                req = admitted[0]
                worked = True
                self.counters["admits"] += 1
                m = _monitor
                if m is not None:
                    now = time.perf_counter()
                    m.on_serving_admit(
                        (now - req.t_submit) * 1e3 if req.t_submit
                        else 0.0)
                self._prefill(req)
            if self.scheduler.has_running():
                self._decode_round()
                worked = True
            lv = _live
            if lv is not None:
                # one engine step = one live window: roll + SLO watchdog
                lv.on_engine_step()
        return worked

    def run(self) -> dict:
        """Drain: step until no request is waiting or running, then
        collect-and-RETIRE — returns ``{request_id: np.ndarray(generated
        tokens)}`` for every request finished since the last collection,
        after which the engine drops its reference (callers keep the
        :class:`Request` handles :meth:`submit` returned). Drivers that
        call :meth:`step` directly get the same contract from
        :meth:`pop_finished`."""
        while self.scheduler.has_work():
            self.step()
        return self.pop_finished()

    def pop_finished(self) -> dict:
        """Collect + retire finished requests (see :meth:`run`) —
        the bound that keeps a continuously-fed engine's host memory
        flat."""
        out = {rid: np.asarray(r.output)
               for rid, r in self._finished.items()}
        self._finished.clear()
        return out

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    # -- phases --------------------------------------------------------------

    def _rows_form(self, kind, lanes):
        """:func:`fit_rows` of program ``kind``'s live-rows operand at
        ``lanes`` lanes."""
        return fit_rows(self._family.read_form(kind), lanes,
                        self.blocks_per_lane)

    @property
    def _row_read(self):
        """``"kernel"`` where the family's programs read their live rows
        through ``ops/pallas/row_attention.py``; ``"xla"`` otherwise
        (the int8 pool; a family with a read of its own)."""
        return self._family.row_read

    def _tells_slot(self, kind):
        """A ``lane_state`` family's one-lane prefill chunk is told the
        lane its request holds, as the last entry of its read operand."""
        return kind == "prefill" and self._family.lane_state

    def _read_spec(self, kind, lanes, width):
        """Shapes of program ``kind``'s read operand (:meth:`_pack_read`)
        at ``lanes`` lanes of ``width`` positions."""
        i32 = jnp.int32
        w, _, cap = self._rows_form(kind, lanes)
        spec = (jax.ShapeDtypeStruct((cap, 2 + w), i32),
                jax.ShapeDtypeStruct((lanes, width), i32))
        if self._tells_slot(kind):
            spec += (jax.ShapeDtypeStruct((1,), i32),)
        return spec

    def _layout(self, kind) -> OperandLayout:
        """Where program ``kind``'s operands lie in its ONE packed
        operand: the read operand (:meth:`_read_spec`), then the kind's
        own — ``cur``, ``last`` | ``cur``, ``toks``, ``wlim`` | ``chunk``,
        ``start``, ``ctx``, ``last_idx``. Derived once a kind."""
        if kind not in self._layouts:
            L, C = self.config.max_lanes, self.prefill_chunk
            S = self.config.spec_k + 1
            lanes, width, own = {
                "decode": (L, 1, ((L,), (L,))),
                "verify": (L, S, ((L,), (L, S), (L,))),
                "prefill": (1, C, ((1, C), (), (), ()))}[kind]
            self._layouts[kind] = OperandLayout.of(
                (self._read_spec(kind, lanes, width),
                 *(jax.ShapeDtypeStruct(shape, jnp.int32)
                   for shape in own)))
        return self._layouts[kind]

    def _pack_read(self, kind, lanes, width, items, ph=None, slot=None):
        """Program ``kind``'s read operand for one call, as numpy: LIVE
        ROWS ``(rows, wblk)`` (:func:`pack_rows`; ``items`` as there). A
        ``lane_state`` family's prefill chunk gets ``slot [1]`` as a last
        entry, ``(rows, wblk, slot)``: the lane its request holds (the
        chunk runs as lane 0 of a one-lane call). Bills the call to the
        three ``kv_*`` read counters."""
        B, M = self.config.block_size, self.blocks_per_lane
        c = self.counters
        c["kv_read_tokens"] += sum(it[3] for it in items)
        c["kv_dense_read_tokens"] += lanes * M * B
        w, tile, cap = self._rows_form(kind, lanes)
        rows, wblk, n, live = pack_rows(items, lanes, width, B, w, cap)
        # the kernel's grid is the live rows; the XLA read runs whole tiles
        by_kernel = n if self._row_read == "kernel" else 0
        c["kv_kernel_rows"] += by_kernel
        c["kv_gathered_tokens"] += (by_kernel
                                    or -(-n // tile) * tile) * w * B
        if ph is not None and _spans is not None:
            ph.args.update(rows=n, live_blocks=live,
                           kv_kernel_rows=by_kernel)
        if self._tells_slot(kind):
            return rows, wblk, np.asarray([slot], np.int32)
        return rows, wblk

    def _prefill(self, req) -> None:
        """Fill the lane's blocks chunk by chunk — starting at
        ``cached_len``, the span already covered by acquired prefix-
        cache blocks (block-aligned, capped at ctx-1, so at least one
        chunk always runs and every write lands in a private block) —
        and greedy-sample the first token on the final chunk. Once the
        context is in the pool its full blocks are published to the
        prefix index (they are frozen now: decode writes only positions
        >= ctx). A re-admitted (preempted) request only rebuilds the
        pool — its pending token is already known, and greedy recompute
        reproduces the continuation exactly as long as the prefill and
        decode programs round K/V (and a ``lane_state`` family's
        recurrent state, which a chunk at position 0 starts from zero)
        identically (proven token-identical
        on the CPU tier in tests/test_serving.py; the two programs fuse
        differently, so a TPU near-tie argmax flip is possible —
        hardware recompute-parity A/B queued in ROADMAP)."""
        toks = req.prefill_tokens
        ctx = int(toks.size)
        cached = int(req.cached_len)
        with self._phase("prefill", "prefill_s", "serving_prefill",
                         f"req/{req.trace_id}", request=req.trace_id,
                         hit_tokens=cached, miss_tokens=ctx - cached) as ph:
            C = self.prefill_chunk
            sp = _spans
            p_t0 = req._t_mark  # admission stamped it just before this call
            nchunks = 0
            tok = None
            for start in range(cached, ctx, C):
                c_t0 = time.perf_counter() if sp is not None else 0.0
                piece = toks[start:start + C]
                chunk = np.zeros((1, C), np.int32)
                chunk[0, :piece.size] = piece
                last_idx = ctx - 1 - start if start + C >= ctx else 0
                # the chunk sees its lane's slots below its own end
                read = self._pack_read(
                    "prefill", 1, C,
                    [(0, req.blocks.ids, start, min(start + C, ctx))],
                    slot=req.lane)
                tok, *self._pools = self._prefill_exec(
                    self._params, *self._pools, self._operand(
                        "prefill", read, chunk, start, ctx, last_idx))
                nchunks += 1
                if sp is not None:
                    # enqueue wall only (no per-chunk host sync — the one
                    # sync per admission stays the first-token fetch below)
                    sp.record("serving/prefill_chunk", "serving_prefill",
                              c_t0, time.perf_counter(),
                              lane=f"req/{req.trace_id}",
                              args={"request": req.request_id,
                                    "start": start,
                                    "tokens": min(C, ctx - start)})
            req.pool_len = ctx
            self.scheduler.publish_prefix(req)
            self.counters["prefill_chunks"] += nchunks
            self.counters["prefill_fed_tokens"] += nchunks * C
            self.counters["prefix_hit_tokens"] += cached
            self.counters["prefix_miss_tokens"] += ctx - cached
            if self.config.kv_int8:
                # quantize-on-write accounting: program launches that
                # quantized + the real (non-pad) tokens they wrote
                self.counters["kv_quant_writes"] += nchunks
                self.counters["kv_quant_tokens"] += ctx - cached
            m = _monitor
            if m is not None:
                m.on_serving_prefill(nchunks)
                pool = self.scheduler.pool
                m.on_serving_prefix(cached, ctx - cached,
                                    pool.shared_count, pool.cold_count)
                if self.config.kv_int8:
                    m.on_serving_kv_quant(nchunks, ctx - cached,
                                          self.kv_pool_bytes)
            # recompute-refund: cached tokens on a re-admission are context
            # the preemption forced us to rebuild but the prefix cache
            # served back for free
            refund = cached if req.output else 0
            req.prefill_refunded_tokens += refund
            if sp is not None:  # what the ring's rollup span adds at its end
                ph.args.update(chunks=nchunks, refunded_tokens=refund,
                               recompute=bool(req.output))
            if req.output:
                end = time.perf_counter()
            else:
                with self._phase("first_token_fetch", "first_fetch_s") as f:
                    # the TTFT host sync
                    first_tok = int(self._family.absorb(
                        np.asarray(tok), self.counters)[0])
                end = f.t1
            if p_t0 is not None:
                req.prefill_ms += (end - p_t0) * 1e3
                req._t_mark = end
            if req.output:
                return  # recompute path: the pending token is output[-1]
            self._emit(req, first_tok, end)

    def _decode_round(self) -> None:
        sched = self.scheduler
        with self._phase("grow", "grow_s"):
            # growth walks FCFS order so older requests claim blocks
            # first; a victim preempted mid-walk is skipped by the state
            # check
            for req in sched.running():
                if req.state == RUNNING:
                    sched.ensure_capacity(req,
                                          on_preempt=self._note_preempt)
            act = sched.running()
        if not act:
            return
        drafts = {}
        if self.spec_active:
            with self._phase("draft", "draft_s", lanes=len(act)):
                drafts = self._draft(act)
        if any(d.size for d in drafts.values()):
            self._verify_round(act, drafts)
        else:
            # no lane proposed anything: today's [L, 1] decode program
            # (and the k=0 / spec-off path, byte for byte)
            self._plain_decode_round(act)

    def _draft(self, act) -> dict:
        """Per-lane draft proposals for this round, keyed by ``id(req)``
        — trimmed to the request's remaining-token budget (drafting the
        final token is pointless: its verification could emit past
        ``max_new_tokens``) and to the blocks the pool can back WITHOUT
        preempting anyone (`scheduler.grow_for_draft`): speculation is
        opportunistic, it never evicts a runner. A request's draft state
        (a drafter's `begin` hook) opens here at its first draft, stays with it
        through preemption — its context comes back as it left — and is
        told only the tokens emitted since its last call."""
        k = self.config.spec_k
        drafts = {}
        for req in act:
            cap = min(k, req.max_new_tokens - len(req.output) - 1)
            d = _EMPTY_DRAFT
            if cap > 0:
                st = req._draft
                if st is None:
                    st = req._draft = self._begin_draft(
                        req.prompt, req.max_new_tokens, self.counters)
                got = st.propose(req.output[st.n - req.prompt.size:], cap)
                if len(got):
                    d = np.asarray(got, np.int32).reshape(-1)[:cap]
                    d = d[:self.scheduler.grow_for_draft(
                        req, int(d.size))]
            drafts[id(req)] = d
        return drafts

    def _operand(self, kind, *operands):
        """One call's operands (numpy, as program ``kind``'s family takes
        them after the pools) as the ONE array the compiled program
        takes: laid end to end (:class:`OperandLayout`). It is handed to
        the executable as numpy: the call itself makes the one transfer,
        0.19 ms of host time sooner than a ``jax.device_put`` before it
        (PERF.md section 6, PR 43)."""
        packed = self._layout(kind).pack(operands)
        self.counters["operand_uploads"] += 1
        self.counters["operand_upload_bytes"] += packed.nbytes
        return packed

    def _launch(self, kind, program, packed, lanes):
        """Run the round's program over the pools and fetch its tokens:
        returns them as numpy with the stamp of the fetch's end — the
        round's ONE host sync, and every lane's attribution mark."""
        with self._phase("dispatch", "dispatch_s", kind=kind, lanes=lanes):
            out, *self._pools = program(self._params, *self._pools, packed)
        with self._phase("token_fetch", "fetch_s") as fetch:
            out = np.asarray(out)
        # a family's own counters ride on the fetched array
        return self._family.absorb(out, self.counters), fetch.t1

    def _verify_round(self, act, drafts) -> None:
        """One [L, k+1] verify step for every occupied lane: score the
        pending token + draft, accept each lane's longest prefix that
        matches the program's own greedy picks plus one bonus token.
        The rollback contract, per kind of cache a family keeps:

        - TOKEN-indexed (K/V, the latent entry): rejected positions roll
          back by rewinding ``pool_len`` — their entries sit above the
          lane's valid length in lane-private blocks (masked out of
          every later attend) until the next accepted write overwrites
          them. The dense and latent families keep nothing else.
        - LANE-indexed (a recurrent state and its conv tail: the hybrid
          state-space and the linear-attention families): a rejected
          position folded into a state cannot be masked later, so the
          family's verify program applies the state update only after
          it has computed each lane's acceptance ITSELF, by this
          method's rule (:meth:`_accept`, the judge of what is emitted;
          the two agree or ``spec_rolled_back_tokens`` differs from
          proposed - accepted), with every position from the first
          rejected one on MASKED — and a masked position is the
          identity on the family's lane state, bit for bit, in whatever
          terms the family's recurrence has one (a step size of 0; a
          log-decay and a correction strength of 0). After the round
          the lane's slot holds the state after the pending token and
          the accepted drafts, nothing else.
        - LANE-indexed, a WINDOW of the lane's own K/V (a ring of ``R``
          slots, position ``p`` in slot ``p mod R``: the
          window-attention family): nothing is folded, so nothing needs
          undoing — a rejected position ``c + j'`` sits in a slot that
          every query at ``t >= c + j`` with ``j < j'`` reads as position
          ``c + j' - R``, outside a band of ``W`` positions as long as
          ``R >= W + k`` (``k`` = ``spec_k``), and the next accepted
          write to that slot comes before the band reaches it. A masked
          position (pad of a short draft, an idle lane) is not written.
          The family checks the inequality when it sizes its rings.
        - LANE-indexed, a bare CONV TAIL (the last ``L - 1`` inputs of a
          short convolution: the short-convolution family): the verify
          program keeps each layer's ``[tail | k+1 positions]`` window
          until it has the lane's acceptance, then sets the tail to the
          window's rows that end at the last kept position; an idle
          lane's tail is set to itself."""
        L, K = self.config.max_lanes, self.config.spec_k
        with self._phase("pack", "pack_s") as ph:
            cur = np.zeros((L,), np.int32)
            toks = np.zeros((L, K + 1), np.int32)
            wlim = np.zeros((L,), np.int32)
            items = []
            for req in act:
                d = drafts.get(id(req), _EMPTY_DRAFT)
                cur[req.lane] = req.pool_len
                toks[req.lane, 0] = req.output[-1]
                if d.size:
                    toks[req.lane, 1:1 + d.size] = d
                wlim[req.lane] = req.pool_len + 1 + d.size
                # rejected positions sit above pool_len in lane-private
                # blocks: the read covers the pending token and the draft
                items.append((req.lane, req.blocks.ids, req.pool_len,
                              req.pool_len + 1 + int(d.size)))
            packed = self._operand(
                "verify", self._pack_read("verify", L, K + 1, items, ph),
                cur, toks, wlim)
        preds, now = self._launch("verify", self._verify_exec, packed,
                                  len(act))
        preds = preds.reshape(L, K + 1)
        with self._phase("emit", "emit_s") as ph:
            self._accept(act, drafts, preds, now, ph)

    def _accept(self, act, drafts, preds, now, ph) -> None:
        c = self.counters
        c["verify_steps"] += 1
        proposed = accepted = bonus = emitted = 0
        for req in act:
            # attribution: everything since the lane's last phase
            # boundary (prefill end / previous round) is decode time
            if req._t_mark is not None:
                req.decode_ms += (now - req._t_mark) * 1e3
                req._t_mark = now
            d = drafts.get(id(req), _EMPTY_DRAFT)
            n = int(d.size)
            row = preds[req.lane]
            a = 0
            while a < n and row[a] == d[a]:
                a += 1
            proposed += n
            accepted += a
            if n:
                req.spec_rounds += 1
                req.accepted_tokens += a
            if n and self._observe_draft is not None:
                self._observe_draft(d, a)  # optional feedback hook
            # emit the a accepted drafts (== row[:a]) + the bonus token
            # row[a]; stop early when max_new_tokens/eos finishes the
            # request mid-prefix (the cap in _draft makes overshoot
            # impossible — a+1 <= remaining)
            got = 0
            for j in range(a + 1):
                req.pool_len += 1
                got += 1
                self._emit(req, int(row[j]), now)
                if req.finished:
                    break
            if n and got == a + 1:
                bonus += 1
            emitted += got
            # rejected-draft blocks go straight back to the pool
            # (no-op for finished lanes, whose blocks are already
            # freed): a failed speculation must leave no allocation
            # pressure behind to preempt someone later
            if req.state == RUNNING:
                self.scheduler.release_draft_blocks(req)
        c["decoded_tokens"] += emitted
        c["spec_proposed_tokens"] += proposed
        c["spec_accepted_tokens"] += accepted
        c["spec_bonus_tokens"] += bonus
        if self.config.kv_int8:
            # every non-pad write this round quantized: each lane's
            # pending token + its (possibly rejected) draft — rejected
            # positions still wrote int8+scale before the rewind
            c["kv_quant_writes"] += 1
            c["kv_quant_tokens"] += len(act) + proposed
        m = _monitor
        if m is not None:
            m.on_serving_verify(len(act), self.scheduler.pool.allocatable,
                                emitted)
            m.on_serving_spec(proposed, accepted, bonus)
            if self.config.kv_int8:
                m.on_serving_kv_quant(1, len(act) + proposed,
                                      self.kv_pool_bytes)
        lv = _live
        if lv is not None and proposed:
            lv.on_accept_rate(proposed, accepted)
        if _spans is not None:
            ph.args.update(lanes=len(act), proposed=proposed,
                           accepted=accepted, bonus=bonus, emitted=emitted)

    def _plain_decode_round(self, act) -> None:
        L = self.config.max_lanes
        with self._phase("pack", "pack_s") as ph:
            cur = np.zeros((L,), np.int32)
            last = np.zeros((L,), np.int32)
            for req in act:
                cur[req.lane] = req.pool_len
                last[req.lane] = req.output[-1]
            read = self._pack_read(
                "decode", L, 1,
                [(r.lane, r.blocks.ids, r.pool_len, r.pool_len + 1)
                 for r in act], ph)
            packed = self._operand("decode", read, cur, last)
        toks, now = self._launch("decode", self._decode_exec, packed,
                                 len(act))
        with self._phase("emit", "emit_s") as ph:
            if _spans is not None:
                ph.args.update(lanes=len(act), emitted=len(act))
            c = self.counters
            c["decode_steps"] += 1
            c["decoded_tokens"] += len(act)
            if self.config.kv_int8:
                c["kv_quant_writes"] += 1
                c["kv_quant_tokens"] += len(act)
            m = _monitor
            if m is not None:
                # allocatable = free list + revivable cold LRU — the
                # pre-sharing meaning of "free" (cold blocks are spare
                # capacity, not occupancy)
                m.on_serving_decode(len(act),
                                    self.scheduler.pool.allocatable)
                if self.config.kv_int8:
                    m.on_serving_kv_quant(1, len(act), self.kv_pool_bytes)
            for req in act:
                if req._t_mark is not None:
                    req.decode_ms += (now - req._t_mark) * 1e3
                    req._t_mark = now
                req.pool_len += 1
                self._emit(req, int(toks[req.lane]), now)

    def _emit(self, req, tok: int, now: float) -> None:
        req.output.append(tok)
        if req.t_first is None:
            req.t_first = now
        if (len(req.output) >= req.max_new_tokens
                or (req.eos_token_id is not None
                    and tok == req.eos_token_id)):
            req.t_done = now
            req._draft = None  # the draft state dies with the request
            self.scheduler.finish(req)
            self._finished[req.request_id] = \
                self._requests.pop(req.request_id, req)
            self._journeys.append({
                "request_id": req.request_id, "trace_id": req.trace_id,
                "tokens": len(req.output),
                "preemptions": req.preemptions,
                "total_ms": round((now - req.t_submit) * 1e3, 3)
                if req.t_submit is not None else None,
                **req.attribution()})
            self.counters["finished"] += 1
            m = _monitor
            if m is not None:
                m.on_serving_evict()
            lv = _live
            if lv is not None:
                # the always-on attribution stamps ARE the SLO feed —
                # no PT_MONITOR needed for live percentiles
                lv.on_request_finished(
                    (req.t_first - req.t_submit) * 1e3
                    if req.t_submit is not None else None,
                    (req.t_done - req.t_first) * 1e3
                    / (len(req.output) - 1)
                    if len(req.output) > 1 else None,
                    req.queue_ms)
            sp = _spans
            if sp is not None and req.t_submit is not None:
                # the whole journey as ONE span on the request's trace
                # lane, args carrying the attribution breakdown — what
                # monitor_report's "requests" section renders and what
                # survives ring eviction of the per-phase spans
                sp.record(
                    "serving/request", "serving_finish",
                    req.t_submit, now, lane=f"req/{req.trace_id}",
                    args={"request": req.request_id,
                          "trace_id": req.trace_id,
                          "tokens": len(req.output),
                          "preemptions": req.preemptions,
                          "total_ms": round(
                              (now - req.t_submit) * 1e3, 3),
                          "ttft_ms": round(
                              (req.t_first - req.t_submit) * 1e3, 3)
                          if req.t_first is not None else None,
                          **{k: round(v, 3) if isinstance(v, float)
                             else v
                             for k, v in req.attribution().items()}})

    def _note_preempt(self, req) -> None:
        self.counters["preemptions"] += 1
        m = _monitor
        if m is not None:
            m.on_serving_preempt()

    # -- introspection -------------------------------------------------------

    def _blackbox_state(self) -> dict:
        """State provider for the blackbox postmortem dump
        (``monitor/blackbox.py``): geometry, lifetime counters, the
        scheduler snapshot (queue/lanes/pool/events tail + every LIVE
        request's partial journey), and the newest finished journeys —
        enough to reconstruct what the engine was doing when it died.
        Read-only and exception-tolerant by contract (the dump swallows
        provider errors), so it never worsens a crash."""
        return {
            "config": {
                "max_lanes": self.config.max_lanes,
                "block_size": self.config.block_size,
                "num_blocks": self.scheduler.pool.num_blocks,
                "prefill_chunk": self.prefill_chunk,
                "max_seq_len": self.max_seq_len,
                "spec": self.spec_active,
                "spec_k": self.config.spec_k,
                "prefix_cache": self.config.prefix_cache,
                "kv_int8": self.config.kv_int8,
            },
            "counters": dict(self.counters),
            "scheduler": self.scheduler.debug_state(),
            "finished_tail": list(self._journeys),
        }

    def stats(self) -> dict:
        """Plain-int account of the engine's lifetime (always on):
        ``counters`` plus the geometry in use. ``prefill_chunk`` is the
        prefill program's width as the engine resolved it;
        ``prefix_miss_tokens / prefill_fed_tokens`` is how full its calls
        ran (fed = ``prefill_chunks`` x that width) and ``prefill_chunks
        / admits`` how many calls a prompt took."""
        out = dict(self.counters)
        out.update(
            decode_rounds=(self.counters["decode_steps"]
                           + self.counters["verify_steps"]),
            spec=self.spec_active,
            spec_k=self.config.spec_k if self.spec_active else 0,
            lanes=self.config.max_lanes,
            block_size=self.config.block_size,
            num_blocks=self.scheduler.pool.num_blocks,
            free_blocks=self.scheduler.pool.free_count,
            allocatable_blocks=self.scheduler.pool.allocatable,
            blocks_per_lane=self.blocks_per_lane,
            max_seq_len=self.max_seq_len,
            prefill_chunk=self.prefill_chunk,
            int8_weights=self.config.int8_weights,
            kv_int8=self.config.kv_int8,
            # device state by how it is indexed: by token (the block
            # pool's: K/V or latent entries and their scales) and by
            # lane (a family's recurrent state); the weights are neither
            kv_pool_bytes=self.kv_pool_bytes,
            lane_pool_bytes=self.lane_pool_bytes,
            device_state_bytes=self.kv_pool_bytes + self.lane_pool_bytes,
            # a constant: benchmarks/chip/chiplib/serve.py reads the key
            paged_attention=False,
            # what reads each program's live rows: "kernel" or "xla"
            row_read=dict.fromkeys(
                ("prefill", "decode") + ("verify",) * self.spec_active,
                self._row_read),
            prefix_cache=self.config.prefix_cache,
            # False: the family's requests cannot start from a prefix's
            # blocks alone, so none is acquired whatever prefix_cache says
            prefix_reuse=self._family.prefix_reuse,
            shared_blocks=self.scheduler.pool.shared_count,
            cold_blocks=self.scheduler.pool.cold_count,
            indexed_blocks=self.scheduler.pool.indexed_count,
            lanes_occupied=self.scheduler.lanes_occupied,
            waiting=len(self.scheduler.waiting),
            requests=len(self._requests),
            uncollected=len(self._finished),
            family=self._family.name,
            **self._family.stats(),
        )
        return out


_monitor_register(sys.modules[__name__])
