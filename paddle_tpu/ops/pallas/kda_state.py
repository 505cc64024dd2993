"""One pass through a linear-attention layer's matrix state: commit what is
owed, then read for this round — the linear-attention family's ONLY state
path in its decode and verify rounds
(``serving/families/linear_latent_moe.py``; PERF.md section 6, PR 48).
The delta rule's counterpart of ``ssm_state.state_round``; the two
recurrences share no arithmetic (a scalar decay a head there; a decay a
key channel and a correction that depends on a product with the whole
state here), so each has its own kernel.

**Contract.** ``state_round(S, pend, layer, n_owed, q, k, v, g, beta,
own=...)`` takes a layer's state ``S`` [lanes, H, d, d] (key x value,
float32, the model's own layout), what the lanes' last verify round left
pending (``pend`` [layers, lanes, 3, Tp, H, d], ALL layers' — its
positions' keys, log-decays and pseudo-values ``u`` — of which the kernel
takes ``layer``: a number it is TOLD, not compiled for, so that a
program's calls, a layer each, are one kernel, traced and lowered once,
and the one pool is rewritten in place a layer at a time with no copy)
with the count ``n_owed`` [lanes] of them that were accepted, and this
round's ``T`` positions (q, k, v, g [T, lanes, H, d]: POSITIONS FIRST, the
order in which the TPU compiler lays a round's activations out for its
matmuls — handed ``[lanes, T, ..]`` every operand and the outputs were
copied into the other order, ~6 small copies a layer; beta [lanes, T,
H]). A grid step is one lane, its 32 x
128 x 128 numbers (2 MB) brought into VMEM once:

1. **Commit.** ``S <- Diag(exp G) S + sum_s (k_s exp(G - G_s)) u_s^T``
   over the pending positions (``kda_apply``'s closed form, ``G`` the
   running sum of ``g`` up to the last one), with ``g`` and ``u`` taken
   as 0 from position ``n_owed`` on: such a position multiplies by ``exp
   0`` and adds ``0 x k``, the identity bit for bit, so a lane that owes
   nothing gets its state back as it was. The result is written back in
   ``S``'s buffer (``input_output_aliases``: the caller donates it).
2. **Read**, from the tile that was just written and is still on the
   chip: the chunked (WY) form of ``kda_wy`` / ``kda_read`` without any
   array outside the kernel. ONE product a head with the committed tile,
   ``[k_t exp G_t | q_t exp G_t] S`` (2T rows, on the matrix unit, which
   holds the tile while the rows stream through: float32 operands at
   ``HIGHEST`` precision, six bfloat16 passes summed in float32); then,
   on ``[H, d]`` vectors, the T x T algebra by forward substitution —
   ``u_t = beta_t (v_t - P_t - sum_{s<t} A_ts u_s)``, ``o_t = R_t +
   sum_{s<=t} B_ts u_s`` with ``A_ts`` / ``B_ts`` the sums over the key
   channel of ``k_t`` / ``q_t`` times ``k_s exp(G_t - G_s)`` (every
   exponent <= 0). No ``[.., T, T]`` matrix, no inverse and no ``[.., T,
   T, d]`` decay tensor exists anywhere.
3. ``own`` False (a verify round): the round's ``k``, ``g`` and the
   ``u`` just computed take the layer's place in ``pend`` (the pool's
   own buffer); the state is NOT advanced over them — which of them it may
   take up is known after the head, and the next call's step 1 applies
   them. ``own`` True (a plain round): the same closed form applies the
   round's own positions at once, from the tile still in VMEM, and the
   lane owes nothing.

**Where the work runs.** The state stays ``[lanes, H, d key, d value]``:
key channels on a register's sublanes, value channels on its lanes. What
differs by VALUE channel (``u_s``, and every result) is then a row,
broadcast over sublanes for nothing, and a sum over the key channel is a
sum of registers. What differs by KEY channel must be spread along lanes,
a cross-lane permute a REGISTER of state (~2.9 cycles measured: 1.27 ms a
plane a round of 20 layers, where the whole traversal is 9.3), so the
vector unit keeps ONE such plane a head, the commit's decay. Everything
else per key channel rides into the matrix unit, which holds a tile while
rows stream through and costs next to nothing beside the traversal: the
read's factors as the streamed rows of its one product, and the commit's
rank-Tp term ``KD^T U`` as a product too — its operands split by hand
into three bfloat16 parts each (``_split3``) and the six pairs of parts a
six-pass product keeps (``_PAIRS``) laid side by side along a 48-deep
contraction, so that ONE bfloat16 pass gives the float32 product. (Values
on sublanes instead would make every read a cross-lane reduction a
register; the position-by-position recurrence needs three planes a
position.) The small operands come ``[T][H, d]``: heads on sublanes, full
registers for the T x T algebra over all heads at once; a head's rows are
gathered into its own aligned tiles — the commit's columns, the products'
streamed rows — by strided stores into VMEM scratch, and the products'
rows come back the same way. The heads are NOT looped over (``_kernel``
says why): 32 commits, then 32 reads, one block of code.

**VMEM.** The state block in and out, double buffered: 8 MB; the small
operands and the pending block ~1.5 MB; scratch 2.2 MB.
``vmem_limit_bytes`` states 32 MiB of the chip's 128. Measured on the
chip: PERF.md section 6, PR 48; ``tools/bench_kda_state.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu
from . import search

__all__ = ["pending_shape", "state_round"]

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
LANES = 128            # a vector register's minor axis
_VMEM_LIMIT = 32 << 20

# A float32 product as ONE bfloat16 pass: each operand in three bfloat16
# parts (0 the leading one), and the six pairs of parts that a six-pass
# product keeps laid side by side along the contraction, 8 positions a
# pair.
_PAIRS = ((0, 0), (0, 1), (1, 0), (0, 2), (1, 1), (2, 0))
_DEEP = 8 * len(_PAIRS)


def _split3(a):
    hi = a.astype(jnp.bfloat16).astype(F32)
    rest = a - hi
    mid = rest.astype(jnp.bfloat16).astype(F32)
    return hi, mid, (rest - mid).astype(jnp.bfloat16).astype(F32)


def _up8(n):
    return -(-n // 8) * 8


def pending_shape(layers, lanes, T, heads, d):
    """What a verify round leaves of its ``T`` positions for a later call
    to commit: ``[layers, lanes, (k, g, u), T, heads, d]`` float32."""
    return layers, lanes, 3, T, heads, d


def _kernel(n_ref, _, q_ref, k_ref, v_ref, g_ref, b_ref, p_ref, s_ref, o_ref,
            so_ref, *rest, own):
    """One grid step: a lane (after the lanes' counts, the layer number:
    the block specifications' business). ``rest``: [the new pending
    block,] then the scratch, each ``[H x rows, d]`` with a head's rows
    one aligned tile — a head's commit columns ``cm`` (the parts of ``k_s
    exp(G - G_s)`` pair by pair, then ``exp G``), the parts of its
    pseudo-value rows ``up``, the read product's streamed rows ``lhs`` and
    its results ``res``."""
    po_ref = None if own else rest[0]
    cm, up, lhs, res = rest[-4:]
    T, _, H, d = q_ref.shape
    Tp = p_ref.shape[3]
    half = lhs.shape[0] // H // 2
    n = n_ref[pl.program_id(0)]

    def every(ref, row):  # row ``row`` of every head's tile: [H, d]
        return pl.ds(row, H, stride=ref.shape[0] // H), slice(None)

    def tile(ref, h):     # head ``h``'s tile
        rows = ref.shape[0] // H
        return slice(h * rows, (h + 1) * rows), slice(None)

    def running(gs):
        G = [gs[0]]
        for x in gs[1:]:
            G.append(G[-1] + x)
        return G

    def stage(ks, gs, us):
        """The closed form of the positions (k, g, u)[t], as the heads'
        commit tiles: position ``t`` of pair ``p`` in row ``8 p + t``
        (zeros where there is no position), the decay in row ``_DEEP``."""
        G = running(gs)
        zero = jnp.zeros((H, d), F32)
        for t in range(8):
            live = t < len(ks)
            kd = _split3(ks[t] * jnp.exp(G[-1] - G[t])) if live else None
            uu = _split3(us[t]) if live else None
            for p, (a, b) in enumerate(_PAIRS):
                cm[every(cm, 8 * p + t)] = kd[a] if live else zero
                up[every(up, 8 * p + t)] = uu[b] if live else zero
        cm[every(cm, _DEEP)] = jnp.exp(G[-1])

    def commit(h, S):
        ct = cm[tile(cm, h)].T                                # [d, _DEEP + 8]
        return S * ct[:, _DEEP:_DEEP + 1] + jnp.dot(
            ct[:, :_DEEP].astype(jnp.bfloat16),
            up[tile(up, h)].astype(jnp.bfloat16), preferred_element_type=F32)

    # what is owed: g and u 0 from the first rejected position on
    kept = [t < n for t in range(Tp)]
    stage([p_ref[0, 0, 0, t] for t in range(Tp)],
          [jnp.where(kept[t], p_ref[0, 0, 1, t], 0.0) for t in range(Tp)],
          [jnp.where(kept[t], p_ref[0, 0, 2, t], 0.0) for t in range(Tp)])
    # this round: the read product's streamed rows
    q, k, v, g = ([r[t, 0] for t in range(T)]
                  for r in (q_ref, k_ref, v_ref, g_ref))
    G = running(g)
    for t in range(T):
        eG = jnp.exp(G[t])
        lhs[every(lhs, t)] = k[t] * eG
        lhs[every(lhs, half + t)] = q[t] * eG

    # NO loop over the heads: Mosaic schedules a loop's body as one block
    # with nothing in flight over the back edge, and a head's work is one
    # chain across the units (transpose -> permute -> multiply-add ->
    # store -> latch -> stream -> pop), so a loop of one head a trip ran
    # at the sum of their latencies — 3x the traversal; unrolled, with
    # every head's commit ahead of every head's read, the scheduler
    # overlaps the chains and the kernel runs at the traversal's rate
    # (PERF.md section 6, PR 48)
    for h in range(H):
        so_ref[0, h] = commit(h, s_ref[0, h])
    for h in range(H):
        res[tile(res, h)] = jnp.dot(lhs[tile(lhs, h)], so_ref[0, h],
                                    precision=_HI,
                                    preferred_element_type=F32)

    # the T x T algebra over all heads at once, by forward substitution
    bt = b_ref[0].T                                           # [128, T']
    u = []
    for t in range(T):
        acc = v[t] - res[every(res, t)]
        o = res[every(res, half + t)]
        for s in range(t):
            kd = k[s] * jnp.exp(G[t] - G[s])
            acc = acc - jnp.sum(k[t] * kd, axis=1, keepdims=True) * u[s]
            o = o + jnp.sum(q[t] * kd, axis=1, keepdims=True) * u[s]
        u.append(bt[:H, t:t + 1] * acc)
        o_ref[t, 0] = o + jnp.sum(q[t] * k[t], axis=1, keepdims=True) * u[t]

    if not own:
        for t in range(T):
            po_ref[0, 0, 0, t], po_ref[0, 0, 1, t], po_ref[0, 0, 2, t] = \
                k[t], g[t], u[t]
        return
    stage(k, g, u)
    for h in range(H):
        so_ref[0, h] = commit(h, so_ref[0, h])


def state_round(S, pend, layer, n_owed, q, k, v, g, beta, own):
    """``S`` [lanes, H, d, d] float32 (donated); ``pend`` [layers, lanes,
    3, Tp, H, d] float32 of which ``layer``'s are the positions still
    owed, and ``n_owed`` [lanes] int32 how many of them to apply; q, k, v,
    g [T, lanes, H, d], beta [lanes, T, H] float32: this round's. Returns
    ``(o [T, lanes, H, d], S')`` and, where ``own`` is False, ``pend``
    with the layer's entries replaced (in its own buffer, donated too):
    module docstring."""
    search.note_engaged("kda_state")  # pallas/engaged/kda_state, at trace
    return _round(S, pend, jnp.asarray([layer], jnp.int32),
                  n_owed.astype(jnp.int32), q, k, v, g, beta, own=own,
                  interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("own", "interpret"))
def _round(S, pend, layer, n_owed, q, k, v, g, beta, own, interpret):
    """``state_round`` behind one trace a program: the calls of a
    program's layers differ in ``layer`` alone, which is data."""
    T, lanes, H, d = q.shape
    Tp = pend.shape[3]
    assert own or T == Tp, (T, Tp)
    assert H <= LANES and max(T, Tp) < 8, (H, T, Tp)
    beta = jnp.pad(beta, ((0, 0), (0, -T % 8), (0, LANES - H)))

    def lane(*shape, of_layer=False):
        return pl.BlockSpec(
            (*(1,) * of_layer, 1, *shape),
            lambda i, n, lay: (*((lay[0],) if of_layer else ()), i,
                               *(0,) * len(shape)))

    now = pl.BlockSpec((T, 1, H, d), lambda i, n, lay: (0, i, 0, 0))
    state = lane(H, d, d)
    owed = lane(3, Tp, H, d, of_layer=True)
    out = pl.pallas_call(
        functools.partial(_kernel, own=own),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(lanes,),
            in_specs=[now] * 4 + [lane(*beta.shape[1:]), owed, state],
            out_specs=[now, state] + [owed] * (not own),
            scratch_shapes=[pltpu.VMEM((H * rows, d), F32) for rows in (
                _DEEP + 8, _DEEP, 2 * _up8(T), 2 * _up8(T))]),
        out_shape=[jax.ShapeDtypeStruct(x.shape, F32)
                   for x in [q, S] + [pend] * (not own)],
        # (the counts and the layer number are operands 0 and 1)
        input_output_aliases={8: 1} if own else {8: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="kda_state_round",
        interpret=interpret,
    )(n_owed, layer, q, k, v, g, beta, pend, S)
    return tuple(out)
