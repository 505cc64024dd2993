"""One pass through a state-space layer's recurrent state: commit what is
owed, then read for this round — the hybrid state-space family's ONLY
state path in its decode and verify rounds
(``serving/families/hybrid_ssm.py``; PERF.md section 6, PR 37).

**Contract.** ``state_round(S, commits, C, scale, mix)`` takes a layer's
state in its SLAB layout (below), the positions whose update is still
owed, this round's read vectors and what the round's outputs add to the
reads, and returns ``(Y, S')``:

- ``S' = commit(S)``: every position of ``commits`` applied in order,
  ``S <- exp(dt A) S + (dt x) (outer) B`` (``models.hybrid_ssm.ssm_step``),
  in its CLOSED FORM over the T positions of one commit,
  ``S' = d S + sum_t u_t (outer) B_t`` with ``d = exp(sum_r dt_r A)`` and
  ``u_t = g_t x_t``, ``g_t = exp(sum_{r>t} dt_r A) dt_t`` (``gains``: one
  number a head a position, computed outside; the product with ``x_t``
  is the kernel's). A position whose ``dt`` is 0 is the identity: ``d``
  takes a factor ``exp(0) = 1`` and ``g_t`` is 0, so ``S' = 1 S + 0 B_t``
  — the state as it was, whatever ``x_t`` and ``B_t`` held; with one
  position the closed form IS ``ssm_step``, operation for operation.
  ``commits`` is one commit or two in a row (a plain round: the verify
  round before it, then its own position). A commit's gains and ``B``
  rows come for ALL layers at once with a layer index (the kernel's
  block specifications pick the layer), its ``x`` as an array a layer,
  as the verify round left it, in the model's dtype.
- ``Y[t] = scale_t (S' C_t) + sum_s mix[t, s] x_s``: ``S' C_t`` is
  ``ssm_read`` from the tile that was just written and is still on the
  chip; ``scale`` (a factor a head a read) and ``mix`` (weights a head on
  planes ``x_s`` of the state's width) are how a verify round gets its
  whole ``y_t = S_t C_t + D x_t`` out of the one call without the state
  being advanced: the state decayed to position ``t``, and what the
  round's own positions ``s <= t`` add (``families.hybrid_ssm._own_mix``).
  Both are per-HEAD numbers; the kernel spreads them over a head's
  channels in VMEM (``head_rows`` / ``_plane``), where planes made
  outside cost a layer 6.3 MB written and read back, and every
  ``[lanes, T, heads, d_head]`` array the compiler met outside it laid
  out twice (a 64-wide minor axis is half a register).
- ``S'`` comes back in the buffer ``S`` came in (``input_output_aliases``):
  no second state array exists, and the caller's array must be donated.

State, coefficients and outputs are float32; nothing is approximated.

**The slab layout** ``[lanes, groups, d_state, heads/groups x d_head]``:
a lane's heads of one B/C group side by side on the minor axis and
``d_state`` on the second-minor one — the TRANSPOSE of the model's
``[lanes, heads, d_head, d_state]`` (``to_slab`` / ``from_slab``). The
kernel goes through a lane's block in CHUNKS of ``W`` = 128 numbers of
the minor axis (a vector register's lanes): ``[d_state, W]`` at a
computed lane offset. In this layout what differs by head and channel
(``d``, ``u_t``, ``scale``, ``mix``: PLANES ``[chunks, W]``) varies along
a register's lanes and is broadcast over its sublanes, which costs
nothing, and what differs by state column (``B_t``, ``C_t``: 128 numbers
a group) is broadcast along lanes ONCE a grid step into VMEM scratch; the
read's sum over ``d_state`` is a sum of registers. In the model's own
layout every register of the state would need its own lane broadcasts of
``d`` and ``u_t`` (cross-lane work as large as the traversal), and the
read a cross-lane reduction a register. The matrix unit is no way out: an
``[heads x d_head, d_state]`` result costs 4096 row pushes a pass and a
float32 product six passes, more than the traversal's HBM time. And the
prefill chunk's scan (``ssm_scan(..., slab=True)``), whose 128 positions
DO belong on the matrix unit, reads and writes this layout as plain
products ``[d_state, T] x [T, heads x d_head]``: with the chunks as a
major axis (``[chunks, d_state, W]``, this PR's first layout) the
compiler transposed a lane's 2 MB in and out a layer.

**Tile and VMEM.** One grid step moves one lane's group: at the published
sizes (64 heads x 64, state 128, one group) ``[128, 4096]`` float32 =
2 MB, 64 steps a layer. In VMEM: the state block in and out, double
buffered, 8 MB; gain rows, ``x`` planes, read vectors and outputs under
1 MB; the lane-broadcast ``B`` / ``C`` scratch ``(T_owed + T_read) x
64 KB`` and 16 KB a plane. ``vmem_limit_bytes`` states 32 MiB of the
chip's 128. Per state element a verify round with 5 owed and 5 read
positions is 21 vector operations (11 to commit, 10 to read) against the
~60 the vector unit can issue in the time HBM needs to move the element
in and out. Measured on the chip: PERF.md section 6, PR 37.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework.device import on_tpu
from . import search

__all__ = ["slab_shape", "to_slab", "from_slab", "x_planes", "b_rows",
           "gains", "head_rows", "pending_shapes", "pending_rows",
           "Commit", "state_round"]

F32 = jnp.float32
LANES = 128            # a vector register's minor axis
_VMEM_LIMIT = 32 << 20


def _chunks(heads, d_head, groups):
    """(chunks, W): a group's ``heads/groups x d_head`` numbers in chunks
    of a vector register's lanes (a group narrower than a register, or no
    multiple of one — the tests' sizes — is one chunk)."""
    rp = heads // groups * d_head
    w = LANES if rp % LANES == 0 else rp
    return rp // w, w


def slab_shape(lanes, heads, d_head, d_state, groups):
    """The slab layout's shape for a layer's ``[lanes, heads, d_head,
    d_state]`` state."""
    return lanes, groups, d_state, heads // groups * d_head


def to_slab(S, groups):
    """``S`` [b, H, P, N] (the model's layout) -> the slab layout."""
    b, H, P, N = S.shape
    return jnp.swapaxes(S.reshape(b, groups, H // groups * P, N), -1, -2)


def from_slab(slab, heads, d_head):
    """The slab layout -> ``[b, H, P, N]``."""
    return jnp.swapaxes(slab, -1, -2).reshape(
        slab.shape[0], heads, d_head, slab.shape[2])


def x_planes(x, groups):
    """Per-head-and-channel numbers ``x`` [..., H, P] as planes [..., G,
    chunks, W]: a reshape (``heads/groups x d_head`` side by side, in
    chunks of a register's lanes, as the kernel goes through a lane's
    state)."""
    *lead, H, P = x.shape
    return x.reshape(*lead, groups, *_chunks(H, P, groups))


def b_rows(B):
    """``B`` or ``C`` [..., T, G, N] -> [..., G, T', N] float32, ``T``
    padded to whole sublane tiles ``T'`` with zero rows: the kernel
    transposes a group's rows on the chip (state columns onto sublanes).
    Handing it ``[N, T]`` columns instead made the compiler lay the
    model's whole convolved ``[lanes, T, channels]`` array out
    positions-minor, 25x padded (compiled for a described v5e: PERF.md
    section 6, PR 37)."""
    T = B.shape[-3]
    pad = [(0, 0)] * (B.ndim - 2) + [(0, -T % 8), (0, 0)]
    return jnp.pad(jnp.swapaxes(B.astype(F32), -3, -2), pad)


def gains(dt, A):
    """One commit's closed-form gains from its positions' step sizes: dt
    [..., T, H] (0 where a position is not to be applied), A [..., H] ->
    [..., 1 + T, H]: row 0 the decay ``d = exp(sum_r dt_r A)``, row 1 + t
    ``g_t = exp(sum_{r>t} dt_r A) dt_t``."""
    la = dt * A[..., None, :]
    after = jnp.flip(jnp.cumsum(jnp.flip(la, -2), axis=-2), -2) - la
    d = jnp.exp(jnp.sum(la, axis=-2, keepdims=True))
    return jnp.concatenate([d, jnp.exp(after) * dt], axis=-2)


def pending_shapes(lanes, T, heads, d_head, d_state, groups):
    """What a round leaves its ``T`` positions' update inputs in for a
    later call to commit, a layer: (their ``x`` as the kernel's planes
    ``[lanes, T, G, chunks, W]``, which a ``Commit`` hands the kernel as
    they lie; a lane's ``T`` rows ``B | dt_raw`` side by side, ``[lanes,
    T x (G x d_state + heads)]`` — ``pending_rows`` takes them apart)."""
    return ((lanes, T, groups, *_chunks(heads, d_head, groups)),
            (lanes, T * (groups * d_state + heads)))


def pending_rows(rows, heads, d_state, groups):
    """``B | dt_raw`` rows ``[..., T x (G x N + H)]`` -> (B [..., T, G,
    N], dt_raw [..., T, H])."""
    row = rows.reshape(*rows.shape[:-1], -1, groups * d_state + heads)
    return (row[..., :groups * d_state].reshape(
        *row.shape[:-1], groups, d_state), row[..., groups * d_state:])


def head_rows(a, groups, d_head):
    """Per-head numbers ``a`` [..., lanes, T, H] as the kernel takes them,
    ``[..., lanes, G, R, T', 128 x]`` float32: a plane row (``W`` numbers
    of ``heads/groups x d_head``) holds ``R`` heads' channels side by
    side (or a part of one head's), and piece ``r`` of the rows is one
    short vector a position, zero padded to whole tiles. The kernel makes
    the ``[chunks, W]`` plane of it in VMEM (``_plane``): a plane a
    position a layer made outside was 6.3 MB written and read back a call
    at the published sizes."""
    *lead, T, H = a.shape
    C, W = _chunks(H, d_head, groups)
    assert W % d_head == 0 or d_head % W == 0, (W, d_head)
    R = max(W // d_head, 1)
    head = (np.arange(C)[None, :] * W
            + np.arange(R)[:, None] * (W // R)) // d_head       # [R, C]
    rows = jnp.moveaxis(
        a.astype(F32).reshape(*lead, T, groups, H // groups)[..., head],
        -4, -2)                                        # [..., G, R, T, C]
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 2)
                   + [(0, -T % 8), (0, -C % LANES)])


def _plane(cols, t, C, W):
    """Position ``t``'s ``[C, W]`` plane from the transposed pieces
    ``cols`` (R arrays ``[C', T']``): row ``c``'s piece ``r`` is
    ``cols[r][c, t]`` along ``W / R`` lanes."""
    R = len(cols)
    lane = jax.lax.broadcasted_iota(jnp.int32, (C, W), 1)
    plane = jnp.broadcast_to(cols[-1][:C, t:t + 1], (C, W))
    for r in range(R - 2, -1, -1):
        plane = jnp.where(lane < (r + 1) * (W // R), jnp.broadcast_to(
            cols[r][:C, t:t + 1], (C, W)), plane)
    return plane


class Commit(NamedTuple):
    """Consecutive positions to apply. ``g``: ``head_rows`` of their
    ``gains`` and ``B``: their ``b_rows``, both with a leading axis of
    LAYERS, ``[layers, lanes, G, R, T', 128 x]`` and ``[layers, lanes, G,
    T', N]``, of which the kernel takes ``layer`` — a number the kernel
    is TOLD, not compiled for, so that a program's 36 calls are one
    kernel, traced and lowered once, and every layer's gains are
    computed at once; ``x``: ``x_planes`` of their inputs, ``[lanes, T,
    G, chunks, W]`` in any float dtype — an array a layer."""
    g: jax.Array
    x: jax.Array
    B: jax.Array
    layer: int = 0


def _kernel(_, *refs, commits, reads, scaled, mixed):
    """One grid step: a lane's group. After the commits' layer numbers
    (the block specifications' business) ``refs``: (gain rows, x planes,
    B rows) a commit, [the reads' scale rows,] [the mix rows, the mixed
    planes,] C rows, the state block, then the outputs (Y, the state
    block) and the scratch: the decay plane, the ``u_t`` planes and the
    lane-broadcast ``B_t`` a commit, the lane-broadcast ``C_t``[, the
    reads' scale planes][, the planes the mix adds to the reads]."""
    n = len(commits)
    ins = [refs[3 * i:3 * i + 3] for i in range(n)]
    rest = list(refs[3 * n:])
    sc_ref = rest.pop(0) if scaled else None
    mx_ref, xo_ref = (rest.pop(0), rest.pop(0)) if mixed else (None, None)
    cr_ref, s_ref, y_ref, so_ref, *scr = rest
    ds, us, bbs = scr[0:3 * n:3], scr[1:3 * n:3], scr[2:3 * n:3]
    cb, *scr = scr[3 * n:]
    sc = scr.pop(0) if scaled else None
    ow = scr.pop(0) if mixed else None
    _, N, CW = s_ref.shape
    C, W = ds[0].shape

    def cols(ref):  # a block of ``head_rows``, positions onto lanes
        return [ref[(0,) * (ref.ndim - 3) + (r,)].T
                for r in range(ref.shape[-3])]

    # once a grid step: the per-head numbers spread over their heads'
    # channels (a [chunks, W] plane each: 4 registers), u_t = g_t x_t,
    # and a state column's B_t / C_t — the same for every head and
    # channel — broadcast along the lanes
    for (g_ref, x_ref, br_ref), d, u, bb, T in zip(ins, ds, us, bbs,
                                                   commits):
        gc = cols(g_ref)
        d[...] = _plane(gc, 0, C, W)
        bt = br_ref[0, 0, 0].T                               # [N, T']
        for t in range(T):
            u[t] = _plane(gc, 1 + t, C, W) * x_ref[0, t, 0].astype(F32)
            bb[t] = jnp.broadcast_to(bt[:, t:t + 1], (N, W))
    ct = cr_ref[0, 0].T
    for t in range(reads):
        cb[t] = jnp.broadcast_to(ct[:, t:t + 1], (N, W))
    if scaled:
        scc = cols(sc_ref)
        for t in range(reads):
            sc[t] = _plane(scc, t, C, W)
    if mixed:
        mc, Tx = cols(mx_ref), xo_ref.shape[1]
        for t in range(reads):
            ow[t] = sum(_plane(mc, t * Tx + s, C, W)
                        * xo_ref[0, s, 0].astype(F32) for s in range(Tx))

    def chunk(c, carry):
        row, col = pl.ds(c, 1), pl.ds(pl.multiple_of(c * W, W), W)
        S = s_ref[0, :, col]                                 # [N, W]
        for d, u, bb, T in zip(ds, us, bbs, commits):
            S = S * d[row, :]
            for t in range(T):
                S = S + u[t, row, :] * bb[t]
        so_ref[0, :, col] = S
        for t in range(reads):
            y = jnp.sum(S * cb[t], axis=0, keepdims=True)
            if scaled:
                y = y * sc[t, row, :]
            if mixed:
                y = y + ow[t, row, :]
            y_ref[t, 0, 0, row, :] = y
        return carry

    jax.lax.fori_loop(0, C, chunk, 0)


def state_round(S, commits, C, scale=None, mix=None):
    """``S`` [lanes, G, N, H/G x P] float32 (the slab layout; donated);
    ``commits``: one or two ``Commit``, applied in order; ``C`` [lanes,
    reads, G, N] this round's read vectors; ``scale``: ``head_rows`` of a
    factor a head on each read [lanes, reads, H], or None; ``mix``:
    (``head_rows`` of weights a head [lanes, reads x T, H], planes
    [lanes, T, G, chunks, W]) to add to the reads, or None. Returns (Y
    [lanes, reads, H x P] with ``Y[:, t] = scale_t (S' C_t) + sum_s
    mix[t T + s] planes_s``, ``S'`` in ``S``'s buffer)."""
    search.note_engaged("ssm_state")  # pallas/engaged/ssm_state, at trace
    layers = jnp.asarray([c.layer for c in commits], jnp.int32)
    return _round(S, layers, tuple(c[:3] for c in commits), C, scale, mix,
                  interpret=not on_tpu())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _round(S, layers, commits, C, scale, mix, interpret):
    """``state_round`` behind one trace a program: the calls of a
    program's layers differ in ``layers`` alone, which is data."""
    L, G, N, _ = S.shape
    C_, W = commits[0][1].shape[-2:]
    reads, Cr = C.shape[1], b_rows(C)
    Ts = tuple(x.shape[1] for _, x, _ in commits)

    def group(*tail, of=None):
        """A block of one lane's one group: grid step ``i`` is lane ``i
        // G``, group ``i % G``; ``tail`` names the block's other axes
        (None where the group's axis goes); ``of``: the commit whose
        layer of the array it is."""
        shape = tuple(1 if d is None else d for d in tail)
        return pl.BlockSpec(
            (*(1,) * (of is not None), 1, *shape),
            lambda i, lay: (*(() if of is None else (lay[of],)), i // G,
                            *(i % G if d is None else 0 for d in tail)))

    def rows(a, of=None):  # the block of a ``head_rows``
        return group(None, *a.shape[-3:], of=of)

    ins, specs, scratch = [], [], []
    for k, ((g, x, B), T) in enumerate(zip(commits, Ts)):
        ins += [g, x, B]
        specs += [rows(g, of=k), group(T, None, C_, W),
                  group(None, B.shape[-2], N, of=k)]
        scratch += [pltpu.VMEM((C_, W), F32), pltpu.VMEM((T, C_, W), F32),
                    pltpu.VMEM((T, N, W), F32)]
    scratch += [pltpu.VMEM((reads, N, W), F32)]
    if scale is not None:
        ins, specs = ins + [scale], specs + [rows(scale)]
        scratch += [pltpu.VMEM((reads, C_, W), F32)]
    if mix is not None:
        ins += [*mix]
        specs += [rows(mix[0]), group(mix[1].shape[1], None, C_, W)]
        scratch += [pltpu.VMEM((reads, C_, W), F32)]
    ins += [Cr, S.reshape(L * G, N, C_ * W)]
    slab = pl.BlockSpec((1, N, C_ * W), lambda i, lay: (i, 0, 0))
    specs += [group(None, Cr.shape[-2], N), slab]
    Y, S = pl.pallas_call(
        functools.partial(_kernel, commits=Ts, reads=reads,
                          scaled=scale is not None, mixed=mix is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(L * G,), in_specs=specs,
            out_specs=[pl.BlockSpec(
                (reads, 1, 1, C_, W),
                lambda i, lay: (0, i // G, i % G, 0, 0)), slab],
            scratch_shapes=scratch),
        # Y positions first, as the compiler lays a round's activations
        # out for its matmuls (``[lanes, reads, ..]`` was copied into
        # that order a layer)
        out_shape=[jax.ShapeDtypeStruct((reads, L, G, C_, W), F32),
                   jax.ShapeDtypeStruct((L * G, N, C_ * W), F32)],
        # (the layer numbers are operand 0)
        input_output_aliases={len(ins): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ssm_state_round",
        interpret=interpret,
    )(layers, *ins)
    return (jnp.swapaxes(Y.reshape(reads, L, G * C_ * W), 0, 1),
            S.reshape(L, G, N, C_ * W))
