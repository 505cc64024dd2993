"""Paged-attention decode kernel: gather KV straight from the block pool.

A Pallas read of each lane's live prefix for the serving engine's decode
step, walking a ``[lanes, M]`` block table: one grid row per (lane,
table-slot), the K/V BlockSpec index maps resolve through the lane's
block table (scalar-prefetch — the table and the per-lane lengths arrive
before the body runs), and iterations past the lane's live prefix REPEAT
the previous block index, which the Pallas pipeline recognizes as "block
unchanged" and elides the DMA — so HBM traffic is ``pool_len`` live
tokens per lane, not ``M·B``. The engine's own read
(``serving/families/dense_gqa.py:_attend_rows``) no longer gathers every
table slot either: it gathers the rows of blocks the lanes hold, in
plain XLA, in all three programs (PERF.md section 6, PR 28) — what this
kernel has to beat, at one grid step a 16-token block, dead steps
included, and for one position a lane only. Whether it is still worth
its code is ROADMAP C's question.

The math mirrors ``serving/families/dense_gqa.py:_attend_lanes`` (fp32 grouped-GQA
dots, 1/sqrt(d), -1e30 masking) as a streaming softmax over table
slots; masked slots carry exactly-zero weight, so engine outputs stay
token-identical to ``generate()`` (tests/test_serving.py extends the
token-identity proof to this path).

Ships **disengaged by default**: the engine's auto mode consults the
search harness's ``paged_attention`` tune-table row for this geometry
(``ops/pallas/search.py``; engagement = measured-faster-than-the-
gathered read only) and no hardware row exists yet (``kernel_tune.json`` is
tracked, and empty). ``PT_SERVE_PAGED=1/0`` forces it on/off
(docs/SERVING.md); forced on, it has run on the v5e inside the engine's
decode program at Llama-2-7B widths (chip_smoke.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...framework.jax_compat import export as _jax_export, tpu_compiler_params
from .. import registry
from . import search

__all__ = ["paged_attend", "paged_attend_int8", "family_key",
           "lowering_cases", "lowering_cases_int8", "check_lowering",
           "check_lowering_int8", "register"]

NEG_INF = -1e30
_LANES = 128


def _paged_kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, block_size, n_blocks,
                  nkv, g, window=0):
    """One (lane, table-slot) grid step of the streaming softmax.
    ``tab_ref``/``pos_ref`` are scalar-prefetch refs (also consumed by
    the K/V index maps); state lives in VMEM scratch across the
    slot-minor grid dim."""
    l_idx = pl.program_id(0)
    m_idx = pl.program_id(1)
    p = pos_ref[l_idx]
    nh = nkv * g
    B = block_size
    nb = p // B + 1  # live blocks: slots 0..p are visible

    @pl.when(m_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(m_idx < nb)
    def _step():
        # per-KV-head loop of 2-D dots (Mosaic lowers only 2-D dots;
        # a [nkv, g, B]-batched formulation does not) — the g grouped
        # query heads of each KV head are a CONTIGUOUS static row slice
        # of q, so GQA costs no relayout
        slots = m_idx * B + jax.lax.broadcasted_iota(jnp.int32, (g, B),
                                                     1)
        vis = slots <= p
        if window > 0:
            vis &= slots > p - window
        for j in range(nkv):
            q = q_ref[0, j * g:(j + 1) * g, :].astype(jnp.float32)
            k = k_ref[0, :, j, :].astype(jnp.float32)   # [B, d]
            v = v_ref[0, :, j, :].astype(jnp.float32)
            d = q.shape[-1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [g, B]
            s = jnp.where(vis, s * (1.0 / math.sqrt(d)), NEG_INF)
            rows = slice(j * g, (j + 1) * g)
            m_prev = m_ref[rows, :1]
            l_prev = l_ref[rows, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(pexp, axis=1,
                                             keepdims=True)
            m_ref[rows] = jnp.broadcast_to(m_new, (g, m_ref.shape[1]))
            l_ref[rows] = jnp.broadcast_to(l_new, (g, l_ref.shape[1]))
            acc_ref[rows] = alpha * acc_ref[rows] + jax.lax.dot_general(
                pexp, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(m_idx == n_blocks - 1)
    def _fini():
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attend(q, kpool, vpool, tables, pos, *, window=0,
                 dead="clamp", interpret=False):
    """Decode-phase paged attention.

    q: ``[L, nh, d]`` — each lane's single pending-token query (already
    RoPE'd); kpool/vpool: ``[num_blocks, B, nkv, d]`` — ONE layer's
    block pool; tables: ``[L, M]`` int32 block tables; pos: ``[L]``
    int32 — the pending token's absolute position (slot ``l`` is
    visible iff ``l <= pos``, matching `_attend_lanes`). Returns
    ``[L, nh, d]``.

    ``dead`` picks the dead-iteration indexing strategy (the family's
    candidate axis): ``"clamp"`` repeats the lane's last LIVE block
    index so every dead iteration elides its DMA entirely; ``"null"``
    redirects dead iterations to null block 0 (one extra block fetch,
    then elided). Both are compute-skipped by ``pl.when``.
    """
    L, nh, d = q.shape
    B, nkv = kpool.shape[1], kpool.shape[2]
    M = tables.shape[1]
    g = nh // nkv
    if dead == "clamp":
        def kv_index(l, m, tab, pos):  # noqa: ANN001 — pallas index map
            return (tab[l, jnp.minimum(m, pos[l] // B)], 0, 0, 0)
    elif dead == "null":
        def kv_index(l, m, tab, pos):  # noqa: ANN001
            return (jnp.where(m <= pos[l] // B, tab[l, m], 0), 0, 0, 0)
    else:
        raise ValueError(f"unknown dead-iteration strategy {dead!r}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, M),
        in_specs=[
            pl.BlockSpec((1, nh, d), lambda l, m, tab, pos: (l, 0, 0)),
            pl.BlockSpec((1, B, nkv, d), kv_index),
            pl.BlockSpec((1, B, nkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, nh, d),
                               lambda l, m, tab, pos: (l, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, d), jnp.float32),
            pltpu.VMEM((nh, _LANES), jnp.float32),
            pltpu.VMEM((nh, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, block_size=B, n_blocks=M,
                          nkv=nkv, g=g, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, nh, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, pos, q, kpool, vpool)


# -- int8 quantized-gather variant (PT_SERVE_KV_INT8 engines) -----------------

def _paged_kernel_int8(tab_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, acc_ref, m_ref, l_ref, *,
                       block_size, n_blocks, nkv, g, window=0):
    """:func:`_paged_kernel` over an int8 block pool: the K/V tiles
    arrive quantized with their per-position fp32 scale tiles (same
    scalar-prefetched block-table index maps, so dead-iteration DMA
    elision is unchanged) and dequantize in-register — the fp32
    ``int8 * scale`` product feeds the same streaming-softmax math, so
    outputs match the engine's dense dequant-then-attend read
    bit-for-bit at fp32 (`quantization.dequantize_kv` is the same two
    ops)."""
    l_idx = pl.program_id(0)
    m_idx = pl.program_id(1)
    p = pos_ref[l_idx]
    B = block_size
    nb = p // B + 1  # live blocks: slots 0..p are visible

    @pl.when(m_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(m_idx < nb)
    def _step():
        slots = m_idx * B + jax.lax.broadcasted_iota(jnp.int32, (g, B),
                                                     1)
        vis = slots <= p
        if window > 0:
            vis &= slots > p - window
        for j in range(nkv):
            q = q_ref[0, j * g:(j + 1) * g, :].astype(jnp.float32)
            # in-tile dequant: [B, d] int8 * [B, 1] fp32 scale
            k = k_ref[0, :, j, :].astype(jnp.float32) \
                * ks_ref[0, :, j:j + 1]
            v = v_ref[0, :, j, :].astype(jnp.float32) \
                * vs_ref[0, :, j:j + 1]
            d = q.shape[-1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [g, B]
            s = jnp.where(vis, s * (1.0 / math.sqrt(d)), NEG_INF)
            rows = slice(j * g, (j + 1) * g)
            m_prev = m_ref[rows, :1]
            l_prev = l_ref[rows, :1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(pexp, axis=1,
                                             keepdims=True)
            m_ref[rows] = jnp.broadcast_to(m_new, (g, m_ref.shape[1]))
            l_ref[rows] = jnp.broadcast_to(l_new, (g, l_ref.shape[1]))
            acc_ref[rows] = alpha * acc_ref[rows] + jax.lax.dot_general(
                pexp, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(m_idx == n_blocks - 1)
    def _fini():
        l_safe = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attend_int8(q, kpool, vpool, kscale, vscale, tables, pos, *,
                      window=0, dead="clamp", interpret=False):
    """:func:`paged_attend` for an int8 block pool: kpool/vpool are
    ``[num_blocks, B, nkv, d]`` int8, kscale/vscale their paired
    ``[num_blocks, B, nkv]`` fp32 amax scales (one per position per KV
    head — `quantization.quantize_kv`). Scale tiles gather through the
    SAME block-table index maps as their K/V tiles (one 3-D BlockSpec
    per scale pool) and dequantize in-tile; everything else — masking,
    dead-iteration strategies, streaming softmax — is the bf16 kernel
    unchanged. Returns ``[L, nh, d]`` in ``q.dtype``."""
    L, nh, d = q.shape
    B, nkv = kpool.shape[1], kpool.shape[2]
    M = tables.shape[1]
    g = nh // nkv
    if dead == "clamp":
        def kv_index(l, m, tab, pos):  # noqa: ANN001 — pallas index map
            return (tab[l, jnp.minimum(m, pos[l] // B)], 0, 0, 0)

        def sc_index(l, m, tab, pos):  # noqa: ANN001
            return (tab[l, jnp.minimum(m, pos[l] // B)], 0, 0)
    elif dead == "null":
        def kv_index(l, m, tab, pos):  # noqa: ANN001
            return (jnp.where(m <= pos[l] // B, tab[l, m], 0), 0, 0, 0)

        def sc_index(l, m, tab, pos):  # noqa: ANN001
            return (jnp.where(m <= pos[l] // B, tab[l, m], 0), 0, 0)
    else:
        raise ValueError(f"unknown dead-iteration strategy {dead!r}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, M),
        in_specs=[
            pl.BlockSpec((1, nh, d), lambda l, m, tab, pos: (l, 0, 0)),
            pl.BlockSpec((1, B, nkv, d), kv_index),
            pl.BlockSpec((1, B, nkv, d), kv_index),
            pl.BlockSpec((1, B, nkv), sc_index),
            pl.BlockSpec((1, B, nkv), sc_index),
        ],
        out_specs=pl.BlockSpec((1, nh, d),
                               lambda l, m, tab, pos: (l, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nh, d), jnp.float32),
            pltpu.VMEM((nh, _LANES), jnp.float32),
            pltpu.VMEM((nh, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel_int8, block_size=B, n_blocks=M,
                          nkv=nkv, g=g, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, nh, d), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tables, pos, q, kpool, vpool, kscale, vscale)


# -- search-harness family ----------------------------------------------------

def family_key(block_size, nkv, g, d, window=0) -> str:
    """Engagement key: the per-lane compute shape. Lane count and table
    length are deliberately OUT — per-lane work is O(live tokens)
    whatever M is, and the lane grid dim is embarrassingly parallel, so
    one measured geometry row serves any (lanes, max_seq_len) engine.
    A sliding window IS in (``_w<n>``): the windowed variant masks
    differently and its dead-DMA profile differs, so a window=0 row
    must not engage it (same variant-marker rule as
    `head_flash.shape_key`)."""
    key = f"B{block_size}_kv{nkv}_g{g}_d{d}"
    if window > 0:
        key += f"_w{window}"
    return key


class PagedAttentionFamily(search.KernelFamily):
    """Candidate axis: the dead-iteration strategy (see
    :func:`paged_attend`). Decode-phase kernel — fwd-only timing."""

    name = "paged_attention"
    grad = False
    parity_atol = 2e-5

    def shapes(self):
        # (L, M, B, nkv, g, d): the serving bench's non-smoke geometry
        # (0.44B-class decode model: 12 heads, d=128, PT_SERVE_BLOCK=16,
        # max_position_embeddings=2048 -> M=128)
        return [(8, 128, 16, 12, 1, 128)]

    def smoke_shapes(self):
        return [(3, 4, 8, 2, 2, 16)]

    def key(self, shape):
        L, M, B, nkv, g, d = shape
        return family_key(B, nkv, g, d)

    def shape_info(self, shape):
        L, M, B, nkv, g, d = shape
        return {"lanes": L, "blocks_per_lane": M, "block_size": B,
                "nkv": nkv, "group": g, "d": d}

    def candidates(self, shape):
        return [{"dead": "clamp"}, {"dead": "null"}]

    def _inputs(self, shape, dtype):
        L, M, B, nkv, g, d = shape
        nh = nkv * g
        nb = L * M + 1
        kq, kk, kv_, kp = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(kq, (L, nh, d), dtype)
        kpool = jax.random.normal(kk, (nb, B, nkv, d), dtype)
        vpool = jax.random.normal(kv_, (nb, B, nkv, d), dtype)
        # each lane owns a contiguous run of blocks; live lengths vary
        # across lanes so both dead strategies face real dead tails
        tables = (jnp.arange(L * M, dtype=jnp.int32).reshape(L, M) + 1)
        pos = (jax.random.randint(kp, (L,), 0, M * B)).astype(jnp.int32)
        return q, kpool, vpool, tables, pos

    def make_inputs(self, shape):
        return self._inputs(shape, jnp.bfloat16)

    def make_parity_inputs(self, shape):
        return self._inputs(shape, jnp.float32)

    def build(self, shape, config, interpret):
        def run(q, kpool, vpool, tables, pos):
            return paged_attend(q, kpool, vpool, tables, pos,
                                dead=config.get("dead", "clamp"),
                                interpret=interpret)

        return run

    def build_composite(self, shape):
        """The full-table gathered read: `_attend_lanes` on
        `kpool[tables]`, the definition this kernel and the engine's row
        read (`dense_gqa._attend_rows`) are both held to. It is what the
        engine ran until PR 28, not what it runs: a tune row measured
        against it over-states the kernel (PERF.md section 7)."""
        L, M, B, nkv, g, d = shape
        nh = nkv * g

        def composite(q, kpool, vpool, tables, pos):
            from ...serving.families.dense_gqa import _attend_lanes

            kc = kpool[tables].reshape(L, M * B, nkv, d)
            vc = vpool[tables].reshape(L, M * B, nkv, d)
            return _attend_lanes(q[:, None], kc, vc, pos[:, None], nh,
                                 nkv)[:, 0]

        return composite


search.register_family(PagedAttentionFamily())


class PagedAttentionInt8Family(PagedAttentionFamily):
    """The quantized-gather variant (`paged_attend_int8`) for int8
    block pools (``PT_SERVE_KV_INT8`` engines): int8 K/V blocks + fp32
    scale blocks gather through the same block tables and dequantize
    in-tile. Same candidate axis (dead-iteration strategy), same
    geometry keys — but its OWN tune-table family, so an int8 engine
    never engages on a bf16 measurement or vice versa. Ships
    disengaged until hwbench's ``kernel_search`` row lands hardware
    rows (docs/KERNELS.md)."""

    name = "paged_attention_int8"

    def _inputs(self, shape, dtype):
        from ...quantization import quantize_kv

        q, kpool, vpool, tables, pos = super()._inputs(shape, dtype)
        # quantize through THE shared helper — the tiles the kernel
        # dequantizes are exactly what the engine's write path produces
        kq, ks = quantize_kv(kpool)
        vq, vs = quantize_kv(vpool)
        return q, kq, vq, ks, vs, tables, pos

    def build(self, shape, config, interpret):
        def run(q, kpool, vpool, kscale, vscale, tables, pos):
            return paged_attend_int8(q, kpool, vpool, kscale, vscale,
                                     tables, pos,
                                     dead=config.get("dead", "clamp"),
                                     interpret=interpret)

        return run

    def build_composite(self, shape):
        """The int8 full-table read: gather int8 blocks + scales,
        `quantization.dequantize_kv`, then `_attend_lanes` (the bf16
        composite's caveat holds: the engine reads rows now)."""
        L, M, B, nkv, g, d = shape
        nh = nkv * g

        def composite(q, kpool, vpool, kscale, vscale, tables, pos):
            from ...quantization import dequantize_kv
            from ...serving.families.dense_gqa import _attend_lanes

            kc = dequantize_kv(
                kpool[tables].reshape(L, M * B, nkv, d),
                kscale[tables].reshape(L, M * B, nkv), q.dtype)
            vc = dequantize_kv(
                vpool[tables].reshape(L, M * B, nkv, d),
                vscale[tables].reshape(L, M * B, nkv), q.dtype)
            return _attend_lanes(q[:, None], kc, vc, pos[:, None], nh,
                                 nkv)[:, 0]

        return composite


search.register_family(PagedAttentionInt8Family())


# -- lowering self-check + registry hookup ------------------------------------

# (lanes, tables, block, kv-heads, group, d), dead-iteration strategy:
# the serving bench's 12-head geometry with both strategies, a
# lane-tile-friendly B=128 GQA pool, and the Llama-2-7B engine
# (chip_smoke.py: 8 lanes, 32 kv-heads x d128, block 16)
_LOWERING_GEOMETRIES = (
    ((8, 32, 16, 12, 1, 128), "clamp"),
    ((8, 32, 16, 12, 1, 128), "null"),
    ((4, 8, 128, 4, 2, 128), "clamp"),
    ((8, 32, 16, 32, 1, 128), "clamp"),
)


def _lowering_cases(int8: bool):
    cases = []
    sds = jax.ShapeDtypeStruct
    for (L, M, B, nkv, g, d), dead in _LOWERING_GEOMETRIES:
        q = sds((L, nkv * g, d), jnp.bfloat16)
        pool = sds((L * M + 1, B, nkv, d),
                   jnp.int8 if int8 else jnp.bfloat16)
        tables = sds((L, M), jnp.int32)
        pos = sds((L,), jnp.int32)
        label = f"L{L}_M{M}_B{B}_kv{nkv}_g{g}_d{d}_{dead}"
        if int8:
            scale = sds((L * M + 1, B, nkv), jnp.float32)

            def run(q, kpool, vpool, kscale, vscale, tables, pos,
                    _dead=dead):
                return paged_attend_int8(q, kpool, vpool, kscale, vscale,
                                         tables, pos, dead=_dead)

            cases.append((label, run,
                          (q, pool, pool, scale, scale, tables, pos)))
        else:
            def run(q, kpool, vpool, tables, pos, _dead=dead):
                return paged_attend(q, kpool, vpool, tables, pos,
                                    dead=_dead)

            cases.append((label, run, (q, pool, pool, tables, pos)))
    return cases


def lowering_cases():
    """``(label, fn, arg_specs)`` for the bf16 decode kernel at the
    serving geometries above. :func:`check_lowering` lowers them with
    ``jax.export``; ``tests/test_chip_compile.py`` compiles the same
    list for a described v5e."""
    return _lowering_cases(int8=False)


def lowering_cases_int8():
    """:func:`lowering_cases` for the quantized-gather kernel."""
    return _lowering_cases(int8=True)


def check_lowering():
    """Mosaic-lower the decode kernel for platform 'tpu' at
    :func:`lowering_cases` — any host, no chip."""
    for _label, fn, specs in lowering_cases():
        _jax_export.export(jax.jit(fn), platforms=["tpu"])(*specs)


def check_lowering_int8():
    """Mosaic-lower the quantized-gather kernel for platform 'tpu' at
    :func:`lowering_cases_int8` — any host, no chip."""
    for _label, fn, specs in lowering_cases_int8():
        _jax_export.export(jax.jit(fn), platforms=["tpu"])(*specs)


def register(platform="tpu"):
    """Registry entries exist for the lowering pre-flight only: the
    serving engine calls :func:`paged_attend` /
    :func:`paged_attend_int8` directly behind its measured-engagement
    gate, never by op-name dispatch."""
    fn = paged_attend
    fn.check_lowering = check_lowering
    fn.lowering_cases = lowering_cases
    registry.register_kernel("paged_attention", platform)(fn)
    fn8 = paged_attend_int8
    fn8.check_lowering = check_lowering_int8
    fn8.lowering_cases = lowering_cases_int8
    registry.register_kernel("paged_attention_int8", platform)(fn8)
    return fn
