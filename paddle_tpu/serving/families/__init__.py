"""Model families, as the serving engine sees them.

:class:`~paddle_tpu.serving.ServingEngine` owns what every architecture
shares — requests, the FCFS scheduler, the block pool and its prefix
index, chunking, drafting and verification, preemption, the phase spans —
and asks the model's *family* for the three things that differ:

(a) the cache a token takes in a layer: ``make_pools(num_blocks,
    block_size)`` returns the device state every step program threads
    through (a tuple; ``None`` entries allowed), ``kv_pool_bytes`` what of
    it is cache, ``donate_argnums`` which operands a program may donate;
(b) the collected parameters: ``params`` (a pytree of device arrays; how
    it is laid out, and whether it copies the model's arrays, is the
    family's), ``gcfg`` the hashable static view the programs are keyed on;
(c) the step programs: ``program(kind)`` for ``kind`` in ``prefill`` /
    ``decode`` / ``verify`` gives ``(fn, static_kwargs)`` with
    ``fn(params, *pools, read, *operands, **static) -> (out, *pools)``;
    operands are the engine's (``[1, C]`` chunk, start, context length,
    last index | ``[L]`` lengths, ``[L]`` tokens | lengths, ``[L, k+1]``
    tokens, ``[L]`` write limits), and ``read`` says where the lanes' K/V
    lies: the lanes' live rows of ``W`` blocks, their count rounded up
    to whole ``tile``s (``read_form(kind)`` names ``(W, tile)``; the int8
    pool's XLA read runs them a tile at a time), and each
    fed token's write block: ``(rows [R, 2 + W], wblk [lanes, width])``
    (``engine.pack_rows``). That is the signature of the family's
    ``fn``, and it stays: tests and tools call it as it is. What the
    ENGINE compiles takes ``(params, *pools, packed, **static)`` — the
    read operand and the kind's own operands, all ``int32``, laid end to
    end in ONE vector that goes up in one transfer and is cut apart by
    static slices before ``fn`` is called (``engine.packed_program``,
    under ``fn``'s own name; ``engine.OperandLayout`` owns the layout,
    which ``ServingEngine._layout`` derives from ``_read_spec`` and the
    kind's own operands' shapes).

Two more kinds of state than (a) may live in a family, both told to the
engine by attributes: ``lane_state`` — besides its token-indexed pools the
family keeps pools indexed by LANE (``[layers, lanes, ...]``: a recurrent
state with its conv tail; a BARE conv tail and nothing else — the last
``L - 1`` inputs of a short convolution, ``families/conv_moe.py``; or a
WINDOW of the lane's own K/V — a ring of a window-attention layer's last
positions, ``families/window_moe.py``; ``lane_pool_bytes(pools)`` their
size, 0 elsewhere).
Decode and verify index them by the batch row; the one-lane prefill chunk
is told its request's lane, the STATE SLOT, as the last entry of its read
operand — ``(rows, wblk, slot [1])`` — and a chunk at position 0 starts
the slot from zero (a ring: empty), so an admitted or re-admitted request
never sees its predecessor's. Such a family's verify program owes the
engine the rollback contract of ``ServingEngine._verify_round``: a masked
position is the identity on the family's lane state (``common.py`` states
it, beside the rules that keep it: ``_carried``, ``_keeps``,
``_take_rows``). ``prefix_reuse`` —
False where a request cannot start from a prefix's blocks alone (it
would need the recurrent state, or the ring, at that boundary): the
engine then has the scheduler acquire none, and ``stats()`` says so.

``prefill_chunk`` — the width of the family's prefill call where the
engine's default (``engine.PREFILL_CHUNK``, 128) is not its own: what a
call reads against what a position uses is the family's layers', so a
family whose call reads every held expert for a few of them a position
says 512 (``families/window_moe.py``, ``families/conv_moe.py``). The
engine fits it to whole blocks under ``max_seq_len``
(``engine.default_prefill_chunk``); ``ServingConfig.prefill_chunk`` /
``PT_SERVE_PREFILL_CHUNK`` given win. ``common.Family``'s is that
default.

``row_read`` — ``"kernel"`` where the family's programs read their live
rows through ``ops/pallas/row_attention.py`` (the engine then bills
``kv_kernel_rows``, and ``stats()["row_read"]`` says so: every family's
bf16 programs); ``"xla"`` (``common.Family``'s) where a family reads them
itself (the dense family's int8 pool).

Plus ``absorb(out, counters)``: the round's ONE fetched array goes
through it — a family that rides its own counters on that array strips
them into ``counters`` (initial values: ``counters``) and returns the
tokens; ``exec_key(pools)`` and ``stats()``.

A model names its family by a ``serving_family(serving_config)`` method;
one without it is the dense grouped-query decoder the engine began with.

**A new family** is a module here with a class that inherits
``common.Family`` and writes what is its own: its pools (``make_pools``,
``kv_pool_bytes``, ``lane_pool_bytes`` if it keeps any by lane,
``donate_argnums``), its three step programs (``_prefill_chunk``,
``_decode_step``, ``_verify_step``, named in ``programs``) with its
``read_form``, and its ``stats``. It inherits the attributes' defaults,
``program``, ``exec_key``, ``absorb`` with the counters' bookkeeping, the
collected ``params`` and the one refusal of ``kv_int8`` / ``int8_weights``;
and its programs are built from ``common.py``'s parts — ``write_slots``
and ``paged_attention`` for a grouped-query layer on the block pool,
``greedy_head``, the expert accumulator (``MOE_ACC``, ``expert_counts``,
``bump``, ``_out``), the verify round's ``accept`` and the lane-state
rules. It imports no other family.
"""
from __future__ import annotations

# The prefill call's width for a family that names none of its own
# (``common.Family.prefill_chunk``; the engine imports it as
# ``engine.PREFILL_CHUNK``), chosen on the chip (PERF.md section 6, PR 32):
# a call reads all the weights to push its tokens, and up to about this
# width it costs what a 32-token call costs in the dense, hybrid and latent
# families.
PREFILL_CHUNK = 128


def family_for(model, serving_config):
    make = getattr(model, "serving_family", None)
    if make is not None:
        return make(serving_config)
    from .dense_gqa import DenseGQAFamily

    return DenseGQAFamily(model, serving_config)
