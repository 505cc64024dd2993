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
    lies, in the form ``read_form(kind)`` names: ``(W, tile)`` — the
    lanes' live rows of ``W`` blocks, which the program runs ``tile`` at a
    time, and each fed token's write block: ``(rows [R, 2 + W], wblk
    [lanes, width])`` (``engine.pack_rows``), the dense family's read;
    ``None`` — a ``[lanes, M]`` block table, the latent family's.

Plus ``absorb(out, counters)``: the round's ONE fetched array goes
through it — a family that rides its own counters on that array strips
them into ``counters`` (initial values: ``counters``) and returns the
tokens; ``exec_key(pools)`` and ``stats()``.

A model names its family by a ``serving_family(serving_config)`` method;
one without it is the dense grouped-query decoder the engine began with.
"""
from __future__ import annotations


def family_for(model, serving_config):
    make = getattr(model, "serving_family", None)
    if make is not None:
        return make(serving_config)
    from .dense_gqa import DenseGQAFamily

    return DenseGQAFamily(model, serving_config)
