"""The dense family's K/V read over LIVE ROWS (ISSUE 28): each lane's
blocks cut into rows of ``W``, all lanes' rows end to end, run a tile at
a time and recombined per lane as one softmax.

(a) ``_attend_rows`` — and the fused kernel that reads the bf16 pools
    since PR 39, ``ops/pallas/row_attention.py``, in interpret mode —
    against ``_attend_lanes`` over every lane's whole table, at fp32
    tolerance, over the ragged shapes that decide it;
(b) engine outputs token-identical to per-request ``generate()`` with the
    read's constants steered small, so that tiny engines cut lanes into
    several rows over several tiles: decode, verify with accepted and
    rejected drafts, chunked prefill over a prefix-cache hit,
    preempt-and-recompute, the int8 pool;
(c) the three read counters on a ragged run.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, generate
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.engine import fit_rows, pack_rows
from paddle_tpu.ops.pallas.row_attention import row_attention
from paddle_tpu.serving.families import dense_gqa as E

B, NKV, G, D = 4, 2, 2, 8  # block, kv heads, group, head dim
M = 12                     # blocks a lane: 48 slots

# lens: tokens each lane already holds (0: an idle lane); the call feeds
# `s` more a lane. W, tile: the read's constants. window: sliding window.
_CASES = {
    "ragged": dict(lens=[5, 17, 30, 2], W=2, tile=4),
    "exactly_k_rows": dict(lens=[15, 23, 7], W=2, tile=3),  # +1: 16, 24, 8
    "one_slot_into_a_new_row": dict(lens=[16, 24, 8], W=2, tile=3),
    "idle_lanes": dict(lens=[0, 9, 0, 21], W=2, tile=2),
    "all_pad_tile": dict(lens=[3, 0, 0, 0], W=4, tile=8),
    "one_lane_over_several_tiles": dict(lens=[43, 1], W=2, tile=2),
    "one_row_a_lane": dict(lens=[20, 9, 33], W=12, tile=3),
    "one_block_rows": dict(lens=[20, 9, 33], W=1, tile=5),
    "one_tile": dict(lens=[20, 9, 33], W=3, tile=12),
    "window": dict(lens=[40, 13, 26], W=2, tile=4, window=6),
    "window_wider_than_a_row": dict(lens=[40, 13, 26], W=2, tile=3,
                                    window=19),
}


def _operands(lens, s, seed):
    rng = np.random.RandomState(seed)
    L = len(lens)
    nb = 1 + L * M
    pools = [jnp.asarray(rng.randn(nb, B, NKV, D).astype(np.float32))
             for _ in range(2)]
    tables = rng.permutation(np.arange(1, nb)).reshape(L, M).astype(np.int32)
    q = jnp.asarray(rng.randn(L, s, NKV * G, D).astype(np.float32))
    pos = jnp.asarray(np.asarray(lens)[:, None] + np.arange(s)[None, :],
                      jnp.int32)
    return pools, tables, q, pos


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_rows_read_is_the_full_table_read(case, s):
    c = _CASES[case]
    lens, W, window = c["lens"], c["W"], c.get("window", 0)
    (kp, vp), tables, q, pos = _operands(lens, s, seed=len(case))
    L = len(lens)
    want = E._attend_lanes(
        q, kp[tables].reshape(L, M * B, NKV, D),
        vp[tables].reshape(L, M * B, NKV, D), pos, NKV * G, NKV,
        sliding_window=window)

    w, tile, cap = fit_rows((W, c["tile"]), L, M)
    rows, _, n, live = pack_rows(
        [(i, list(tables[i]), lens[i], lens[i] + s)
         for i in range(L) if lens[i]], L, s, B, w, cap)
    assert live == sum(-(-(n_ + s) // B) for n_ in lens if n_)
    assert (rows[:n, 0] >= 0).all() and (rows[n:, 0] == -1).all()

    def gather(blocks):
        T = blocks.shape[0]
        return tuple(p[blocks].reshape(T, w * B, NKV, D) for p in (kp, vp))

    got = E._attend_rows(q, pos, jnp.asarray(rows), gather, tile, NKV,
                         sliding_window=window)
    held = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[held],
                               np.asarray(want)[held], rtol=2e-5, atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()  # idle lanes read 0, not NaN
    assert (np.asarray(got)[~held] == 0).all()


# the served attention geometries (kv heads, group, head dim, value dim):
# Mistral-7B's, granite-4.0-h's, MiMo-V2.5's full layers (values narrower
# than keys), each at two kv heads
_GEOMETRIES = {"g4_d128": (2, 4, 128, 128), "g4_d64": (2, 4, 64, 64),
               "g16_d192_dv128": (2, 16, 192, 128)}


def _kernel_read(q, pos, rows, kp, vp, nkv, window=0):
    """The fused kernel over layer 1 of stacked pools whose layer 0 is
    poison, as the bf16 families hand them over: heads merged into the
    last axis."""
    def stacked(p):
        flat = p.reshape(*p.shape[:2], -1)
        return jnp.stack([jnp.full_like(flat, jnp.nan), flat])

    return row_attention(q, pos, jnp.asarray(rows), stacked(kp), stacked(vp),
                         1, nkv, q.shape[-1] ** -0.5, sliding_window=window)


@pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_kernel_read_is_the_full_table_read(case, s, geometry):
    """The kernel, which reads the pools by (layer, block) itself, masks
    and folds a lane's rows into one softmax on the chip: equal to the
    read over every lane's whole table; a lane with no row reads 0."""
    nkv, g, d, dv = _GEOMETRIES[geometry]
    c = _CASES[case]
    lens, window = c["lens"], c.get("window", 0)
    rng = np.random.RandomState(len(case) + s)
    L = len(lens)
    nb = 1 + L * M
    kp = jnp.asarray(rng.randn(nb, B, nkv, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(nb, B, nkv, dv).astype(np.float32))
    tables = rng.permutation(np.arange(1, nb)).reshape(L, M).astype(np.int32)
    q = jnp.asarray(rng.randn(L, s, nkv * g, d).astype(np.float32))
    pos = jnp.asarray(np.asarray(lens)[:, None] + np.arange(s)[None, :],
                      jnp.int32)
    want = E._attend_lanes(
        q, kp[tables].reshape(L, M * B, nkv, d),
        vp[tables].reshape(L, M * B, nkv, dv), pos, nkv * g, nkv,
        sliding_window=window)
    w, _, cap = fit_rows((c["W"], c["tile"]), L, M)
    rows, _, n, _ = pack_rows(
        [(i, list(tables[i]), lens[i], lens[i] + s)
         for i in range(L) if lens[i]], L, s, B, w, cap)
    got = np.asarray(_kernel_read(q, pos, rows, kp, vp, nkv, window))
    assert got.shape == (L, s, nkv * g, dv)
    held = np.asarray(lens) > 0
    np.testing.assert_allclose(got[held], np.asarray(want)[held],
                               rtol=2e-5, atol=2e-6)
    assert (got[~held] == 0).all()  # idle lanes read 0, not NaN


# the query-tiled grid (a call of more query rows a KV head than one grid
# step may hold: PERF.md section 6, PR 42), with the bound lowered so that
# tiny shapes cross it. lens / s / W as in _CASES; bound: query rows a KV
# head a tile; upto: the slots the rows cover (a prefill chunk's: its real
# tokens' end), real: the fed positions compared (a chunk's pads read what
# no one reads)
_TILED = {
    # 12 positions at group 2 in tiles of 4 over 6 rows of 8 slots: the
    # first two tiles' positions (30-37) end below the last row's first
    # slot (40), wholly masked to them
    "one_lane_several_rows": dict(lens=[30], s=12, W=2, bound=8),
    "lanes_and_an_idle_one": dict(lens=[20, 0, 9], s=8, W=2, bound=8),
    # a chunk at 24 of 16 fed positions, 9 of them real: rows to slot 33
    "part_padded_chunk": dict(lens=[24], s=16, W=2, bound=8, upto=33,
                              real=9),
    "one_block_rows": dict(lens=[17, 5], s=8, W=1, bound=8),
    "window": dict(lens=[30], s=12, W=2, bound=8, window=6),
    "window_wider_than_a_row": dict(lens=[26, 13], s=8, W=2, bound=8,
                                    window=19),
}


@pytest.mark.parametrize("geometry", ["tiny", *sorted(_GEOMETRIES)])
@pytest.mark.parametrize("case", sorted(_TILED))
def test_query_tiled_kernel_read_is_the_full_table_read(
        case, geometry, monkeypatch):
    """A lane's query rows beyond the bound go a tile a walk of the live
    rows — a leading grid axis, each tile with its own copy pipeline,
    running softmax and output block: equal to the read over every lane's
    whole table, at the served head geometries too."""
    from paddle_tpu.ops.pallas import row_attention as RA

    nkv, g, d, dv = (NKV, G, D, D) if geometry == "tiny" \
        else _GEOMETRIES[geometry]
    c = _TILED[case]
    lens, s, window = c["lens"], c["s"], c.get("window", 0)
    bound = c["bound"] * g // G  # the same positions a tile at any group
    monkeypatch.setattr(RA, "_Q_TILE_ROWS", bound)
    assert RA._query_tile(s * g, bound) == bound < s * g  # several tiles
    rng = np.random.RandomState(len(case))
    L = len(lens)
    nb = 1 + L * M
    kp = jnp.asarray(rng.randn(nb, B, nkv, d).astype(np.float32))
    vp = jnp.asarray(rng.randn(nb, B, nkv, dv).astype(np.float32))
    tables = rng.permutation(np.arange(1, nb)).reshape(L, M).astype(np.int32)
    q = jnp.asarray(rng.randn(L, s, nkv * g, d).astype(np.float32))
    pos = jnp.asarray(np.asarray(lens)[:, None] + np.arange(s)[None, :],
                      jnp.int32)
    want = np.asarray(E._attend_lanes(
        q, kp[tables].reshape(L, M * B, nkv, d),
        vp[tables].reshape(L, M * B, nkv, dv), pos, nkv * g, nkv,
        sliding_window=window))
    w, _, cap = fit_rows((c["W"], 4), L, M)
    rows, _, n, _ = pack_rows(
        [(i, list(tables[i]), lens[i], c.get("upto", lens[i] + s))
         for i in range(L) if lens[i]], L, s, B, w, cap)
    assert n > L  # a lane of several rows
    got = np.asarray(_kernel_read(q, pos, rows, kp, vp, nkv, window))
    assert got.shape == (L, s, nkv * g, dv)
    held = np.asarray(lens) > 0
    real = c.get("real", s)
    np.testing.assert_allclose(got[held, :real], want[held, :real],
                               rtol=2e-5, atol=2e-6)
    assert np.isfinite(got).all()
    assert (got[~held] == 0).all()  # idle lanes read 0, not NaN


@pytest.mark.parametrize("rows,bound,want", [
    (80, 2048, 80), (2048, 2048, 2048),  # a round, a 128-position chunk at
    #                                      group 16, 512 at group 4: one tile
    (8192, 2048, 2048), (4096, 2048, 2048),  # 512 / 256 positions, group 16
    (2560, 2048, 1280), (24, 8, 8),
    (36, 8, 36), (2 * 1031, 2048, 2 * 1031),  # nothing divides: one tile
])
def test_the_query_tile_is_the_shapes(rows, bound, want):
    """Every call of up to the bound is one tile (the kernel it was before
    a call could be wider); a wider one takes the largest whole number of
    8-row sublane tiles under the bound that divides its rows."""
    from paddle_tpu.ops.pallas.row_attention import _Q_TILE_ROWS, _query_tile

    assert _Q_TILE_ROWS == 2048
    assert _query_tile(rows, bound) == want
    assert rows % want == 0


def test_the_kernel_walks_no_pad_row():
    """Pad rows (lane -1, after the live ones) are no grid step of the
    kernel's: pointing them at a block of NaN moves nothing, and neither
    does what the null block holds under a lane's last, half-empty row."""
    lens, s = [9, 0, 21], 2
    (kp, vp), tables, q, pos = _operands(lens, s, seed=5)
    w, _, cap = fit_rows((2, 4), len(lens), M)
    rows, _, n, _ = pack_rows(
        [(i, list(tables[i]), lens[i], lens[i] + s)
         for i in (0, 2)], len(lens), s, B, w, cap)
    assert n < cap and (rows[n:, 0] == -1).all()
    want = np.asarray(_kernel_read(q, pos, rows, kp, vp, NKV))
    rows[n:, 2:] = tables[1, 0]  # the idle lane's: nobody's
    got = np.asarray(_kernel_read(
        q, pos, rows, kp.at[tables[1, 0]].set(jnp.nan).at[0].set(3e4),
        vp.at[tables[1, 0]].set(jnp.nan).at[0].set(-7e4), NKV))
    np.testing.assert_array_equal(got, want)
    assert (got[1] == 0).all()


@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_a_masked_row_weighs_nothing_whichever_side_it_lies(read):
    """A wholly masked row (under the window, or above the position)
    contributes exactly zero: poisoning its blocks with huge values moves
    nothing, before the lane's visible rows or after them."""
    lens, s, W, tile, window = [37], 2, 2, 2, 9
    (kp, vp), tables, q, pos = _operands(lens, s, seed=3)
    w, tile, cap = fit_rows((W, tile), 1, M)
    # rows over the lane's WHOLE table: the rows above its positions too
    rows, *_ = pack_rows([(0, list(tables[0]), lens[0], M * B)], 1, s, B,
                         w, cap)

    def read_xla(k, v):
        def gather(blocks):
            T = blocks.shape[0]
            return tuple(p[blocks].reshape(T, w * B, NKV, D)
                         for p in (k, v))
        return np.asarray(E._attend_rows(
            q, pos, jnp.asarray(rows), gather, tile, NKV,
            sliding_window=window))

    def read_kernel(k, v):
        return np.asarray(_kernel_read(q, pos, rows, k, v, NKV, window))

    read = {"xla": read_xla, "kernel": read_kernel}[read]

    # rows 0-2 (slots 0..23) lie under the window of positions 37-38
    # (slots > 28), rows 5 (slots 40..47) above them
    dead = np.concatenate([tables[0, :6], tables[0, 10:]])
    got = read(kp.at[dead].set(3e4), vp.at[dead].set(-7e4))
    np.testing.assert_array_equal(got, read(kp, vp))


# -- (b) the engine, with the constants steered small --------------------------

@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny())
    m.eval()
    return m


def _reference(model, prompt, new, **kw):
    return generate(model, pt.to_tensor(np.asarray(prompt)[None, :]),
                    max_new_tokens=new, **kw).numpy()[0]


@pytest.fixture(params=[(2, 2, 1), (3, 5, 2), (1, 3, 3)],
                ids=["W2_tile2", "W3_tile5", "W1_tile3"])
def small_rows(request, monkeypatch):
    """Rows of W blocks, tiles of a few rows: an engine of 3 lanes x 16
    blocks then runs 2-6 rows a lane over several tiles."""
    w, tile, ptile = request.param
    monkeypatch.setattr(E, "ROW_BLOCKS", w)
    monkeypatch.setattr(E, "ROW_TILE", tile)
    monkeypatch.setattr(E, "PREFILL_TILE", ptile)
    return request.param


@pytest.fixture(params=[4, 40], ids=["chunk4", "chunk40"])
def chunk(request):
    """The prefill program's width: one the prompts take several calls
    of, and one wider than every prompt, than a lane's whole table (16
    blocks of 2) and than ``max_seq_len`` — every call of it is padded,
    and its pad positions run past all three."""
    return request.param


class _OracleDrafter:
    """Proposes each request's true continuation, every second proposal
    with its second token wrong: accepted prefixes AND rejections."""

    def __init__(self, refs, vocab):
        self.refs, self.vocab, self.calls = refs, vocab, 0

    def propose(self, ctx, cap):
        ctx = np.asarray(ctx)
        for prompt, full in self.refs:
            if ctx.size >= prompt.size \
                    and (ctx[:prompt.size] == prompt).all():
                d = np.array(full[ctx.size:ctx.size + cap], np.int32)
                self.calls += 1
                if d.size > 1 and self.calls % 2:
                    d[1] = (d[1] + 1) % self.vocab
                return d
        return np.zeros((0,), np.int32)


def _requests(model, n, seed, lo=3, hi=14, new=(5, 13)):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        plen, k = int(rng.randint(lo, hi)), int(rng.randint(*new))
        out.append((rng.randint(0, model.config.vocab_size,
                                (plen,)).astype(np.int32), k))
    return out


def _serve(eng, reqs):
    handles = [eng.submit(p, max_new_tokens=k) for p, k in reqs]
    outs = eng.run()
    return [outs[h.request_id] for h in handles]


def _hold(model, got, reqs, **kw):
    for i, ((p, k), out) in enumerate(zip(reqs, got)):
        np.testing.assert_array_equal(
            out, _reference(model, p, k, **kw),
            err_msg=f"request {i} diverged from generate()")


def test_engine_decode_is_generate(model, small_rows, chunk):
    eng = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=2, prefill_chunk=chunk, max_seq_len=32,
        spec=False))
    reqs = _requests(model, 7, seed=1)
    _hold(model, _serve(eng, reqs), reqs)
    assert eng.counters["decode_steps"] > 0 == eng.counters["verify_steps"]
    w, tile, cap = eng._rows_form("decode", 3)
    assert (w, tile) == small_rows[:2] and cap > tile  # several tiles


def test_engine_verify_accepts_rejects_and_overwrites(model, small_rows, chunk):
    reqs = _requests(model, 6, seed=2, new=(8, 14))
    refs = [(p, np.concatenate([p, _reference(model, p, k)]))
            for p, k in reqs]
    drafter = _OracleDrafter(refs, model.config.vocab_size)
    eng = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=2, prefill_chunk=chunk, max_seq_len=32,
        spec_k=3), drafter=drafter)
    _hold(model, _serve(eng, reqs), reqs)
    c = eng.counters
    assert c["verify_steps"] > 0
    # accepted prefixes, and rejected tails whose K/V a later round
    # overwrote (rollback is a rewind of pool_len alone)
    assert 0 < c["spec_accepted_tokens"] < c["spec_proposed_tokens"]


def test_engine_prefill_over_a_prefix_hit(model, small_rows, chunk):
    rng = np.random.RandomState(4)
    system = rng.randint(0, model.config.vocab_size, (11,)).astype(np.int32)
    reqs = [(np.concatenate([system, rng.randint(
        0, model.config.vocab_size, (int(n),)).astype(np.int32)]), 6)
        for n in (3, 9, 1, 6)]
    eng = ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=2, prefill_chunk=chunk, max_seq_len=32))
    _hold(model, _serve(eng, reqs), reqs)
    # later requests started their chunks above the shared blocks
    assert eng.counters["prefix_hit_tokens"] >= 10


def test_engine_preempt_and_recompute(model, small_rows, chunk):
    eng = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=2, num_blocks=14, prefill_chunk=chunk,
        max_seq_len=24))
    reqs = _requests(model, 6, seed=5, lo=2, hi=9, new=(6, 12))
    _hold(model, _serve(eng, reqs), reqs)
    assert eng.counters["preemptions"] > 0, "never preempted: vacuous"


def test_engine_int8_pool_is_generate_kv_int8(model, small_rows, chunk):
    eng = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=2, prefill_chunk=chunk, max_seq_len=32,
        kv_int8=True))
    reqs = _requests(model, 5, seed=6)
    _hold(model, _serve(eng, reqs), reqs, kv_int8=True)
    assert eng.counters["kv_quant_tokens"] > 0


def test_engine_sliding_window_reads_rows(small_rows, chunk):
    pt.seed(7)
    m = LlamaForCausalLM(LlamaConfig.tiny(sliding_window=5))
    m.eval()
    eng = ServingEngine(m, ServingConfig(
        max_lanes=3, block_size=2, prefill_chunk=chunk, max_seq_len=32))
    reqs = _requests(m, 5, seed=8, lo=6, hi=15)
    _hold(m, _serve(eng, reqs), reqs)


# -- (c) the counters -----------------------------------------------------------

@pytest.mark.parametrize("spec", [False, True], ids=["decode", "verify"])
def test_read_counters_on_a_ragged_run(model, small_rows, spec):
    """Per program call: live tokens <= slots gathered < live tokens + a
    row's slack a lane + a tile's slack; and never more than a gather of
    every lane's whole table."""
    lanes, block = 3, 2
    eng = ServingEngine(model, ServingConfig(
        max_lanes=lanes, block_size=block, prefill_chunk=4, max_seq_len=32,
        spec=spec))
    reqs = [(p, k) for (p, k), n in zip(
        _requests(model, 6, seed=9), (3, 14, 5, 9, 2, 12)) for p in [p[:n]]]
    _serve(eng, reqs)
    c = eng.counters
    calls = c["decode_steps"] + c["verify_steps"] + c["prefill_chunks"]
    w, tile, _ = eng._rows_form("decode", lanes)
    slack = lanes * w * block + tile * w * block
    assert 0 < c["kv_read_tokens"] <= c["kv_gathered_tokens"] \
        < c["kv_read_tokens"] + calls * slack
    assert c["kv_gathered_tokens"] <= c["kv_dense_read_tokens"]
    assert c["kv_dense_read_tokens"] == eng.blocks_per_lane * block * (
        lanes * (c["decode_steps"] + c["verify_steps"])
        + c["prefill_chunks"])


def test_stats_say_who_reads_the_rows(model):
    """``stats()["row_read"]`` names each program's read and
    ``kv_kernel_rows`` counts the live rows the kernel was handed: all of
    a bf16 engine's, none of an int8 engine's (its pool and scales are the
    XLA read's) nor of a family with a read of its own."""
    reqs = _requests(model, 4, seed=11)
    eng = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=2, prefill_chunk=4, max_seq_len=32))
    _serve(eng, reqs)
    stats = eng.stats()
    assert stats["row_read"] == dict.fromkeys(
        ("prefill", "decode", "verify"), "kernel")
    assert stats["paged_attention"] is False
    rows = stats["kv_kernel_rows"]
    assert 0 < rows and stats["kv_gathered_tokens"] >= rows * 2
    quant = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=2, prefill_chunk=4, max_seq_len=32,
        kv_int8=True, spec=False))
    _serve(quant, reqs)
    assert quant.stats()["row_read"] == {"prefill": "xla", "decode": "xla"}
    assert quant.stats()["kv_kernel_rows"] == 0
    latent = ServingEngine(_tiny_latent(), ServingConfig(
        max_lanes=2, block_size=2, prefill_chunk=4, max_seq_len=16,
        spec=False))
    assert set(latent.stats()["row_read"].values()) == {"kernel"}


def _tiny_latent():
    from paddle_tpu.models import LatentMoEConfig, LatentMoEForCausalLM

    pt.seed(3)
    model = LatentMoEForCausalLM(LatentMoEConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=12, n_routed_experts=4, num_experts_per_tok=2))
    model.eval()
    return model


def test_the_latent_family_bills_its_live_rows():
    """The latent family reads by rows (PR 35): what its programs gather
    follows what the lanes hold, in every kind."""
    eng = ServingEngine(_tiny_latent(), ServingConfig(
        max_lanes=4, block_size=2, prefill_chunk=4, max_seq_len=1024,
        spec=False))
    # rows of 16 blocks: 32 a lane, the operand's length in whole 16s (a
    # prefill chunk's: 4s)
    assert eng._rows_form("decode", 4) == (16, 16, 128)
    assert eng._rows_form("prefill", 1) == (16, 4, 32)
    _serve(eng, _requests(eng.model, 3, seed=10, hi=8, new=(3, 6)))
    c = eng.counters
    assert c["kv_read_tokens"] < c["kv_gathered_tokens"] \
        < c["kv_dense_read_tokens"]


# -- (d) a prefill call wider than what it is fed (PR 32) ------------------------

W = 8  # the chunk the prompt lengths below sit around
_LENGTHS = [1, W - 1, W, W + 1, 3 * W + 5]


@pytest.fixture(scope="module", params=[W, 64], ids=["chunk8", "chunk64"])
def wide_engine(request, model):
    """One engine a width, every length through it: 64 is wider than
    the longest prompt, a lane's table (20 blocks of 2) and
    ``max_seq_len`` (40), so a call at ANY start runs past all three."""
    return ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=2, prefill_chunk=request.param,
        max_seq_len=40))


@pytest.mark.parametrize("length", _LENGTHS)
def test_engine_prompt_lengths_around_the_chunk(model, wide_engine, length):
    """Prompts of 1, W - 1, W, W + 1 and 3W + 5 tokens: ``last_idx``
    picks the last REAL position whatever the call's pad, and the fed /
    real counters say how full the calls ran."""
    eng = wide_engine
    rng = np.random.RandomState(40 + length)
    p = rng.randint(0, model.config.vocab_size, (length,)).astype(np.int32)
    before = dict(eng.counters)
    _hold(model, _serve(eng, [(p, 6)]), [(p, 6)])
    C = eng.prefill_chunk
    d = {k: eng.counters[k] - before[k] for k in
         ("prefill_chunks", "prefill_fed_tokens", "prefix_miss_tokens")}
    assert d == {"prefill_chunks": -(-length // C),
                 "prefill_fed_tokens": -(-length // C) * C,
                 "prefix_miss_tokens": length}
    eng.scheduler.pool.check_invariant()


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_pad_positions_write_the_null_block_alone(model, kv_int8):
    """A 5-token prompt through a 64-wide call on a fresh engine: the
    59 pad positions (past the prompt, the lane's table and
    ``max_seq_len``) land in block 0; every block the request does not
    hold stays as it was made, in K, V and the int8 scales."""
    eng = ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=2, prefill_chunk=64, max_seq_len=40,
        kv_int8=kv_int8))
    p = np.arange(5, dtype=np.int32)
    req = eng.submit(p, max_new_tokens=4)
    eng.step()  # the one prefill call, and a round
    assert eng.counters["prefill_chunks"] == 1
    held = set(req.blocks) | {0}
    others = [b for b in range(eng.scheduler.pool.num_blocks)
              if b not in held]
    for pool in eng._pools:
        if pool is not None:
            assert not np.asarray(pool)[:, others].any()
    assert np.asarray(eng._pools[0])[:, req.blocks[0]].any()
    eng.scheduler.pool.check_invariant()
    eng.run()
    _hold(model, [np.asarray(req.output)], [(p, 4)], kv_int8=kv_int8)


def test_a_prefix_hit_that_leaves_one_token(model, chunk):
    """The same 17-token prompt twice at block 2: the second acquires 16
    cached tokens and prefills ONE — a call fed a single real position,
    starting at 16, whose pad (at chunk 40) runs past the table's end."""
    rng = np.random.RandomState(12)
    p = rng.randint(0, model.config.vocab_size, (17,)).astype(np.int32)
    eng = ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=2, prefill_chunk=chunk, max_seq_len=32))
    first = eng.submit(p, max_new_tokens=5)
    eng.run()
    before = dict(eng.counters)
    second = eng.submit(p, max_new_tokens=5)
    eng.run()
    assert second.cached_len == 16
    assert eng.counters["prefix_miss_tokens"] \
        - before["prefix_miss_tokens"] == 1
    assert eng.counters["prefill_chunks"] - before["prefill_chunks"] == 1
    _hold(model, [np.asarray(first.output), np.asarray(second.output)],
          [(p, 5), (p, 5)])
    eng.scheduler.pool.check_invariant()


# -- (e) the latent family's read over live rows (PR 35) ----------------------

NH, DC, DR, DN, DV, STORED = 3, 16, 4, 8, 6, 24  # heads; latent, rope,
#                      nope, value widths; an entry as stored (padded)


class _LatentCfg:
    kv_lora_rank, qk_rope_head_dim, qk_nope_head_dim = DC, DR, DN
    v_head_dim, num_attention_heads = DV, NH


def _latent_operands(lens, s, seed):
    rng = np.random.RandomState(seed)
    L = len(lens)
    nb = 1 + L * M
    pool = np.zeros((nb, B, STORED), np.float32)
    pool[..., :DC + DR] = rng.randn(nb, B, DC + DR)
    tables = rng.permutation(np.arange(1, nb)).reshape(L, M).astype(np.int32)
    q_nope = jnp.asarray(rng.randn(L, s, NH, DN).astype(np.float32))
    q_rope = jnp.asarray(rng.randn(L, s, NH, DR).astype(np.float32))
    lp = {"kv_b": jnp.asarray(
        rng.randn(DC, NH * (DN + DV)).astype(np.float32) * 0.3)}
    pos = jnp.asarray(np.asarray(lens)[:, None] + np.arange(s)[None, :],
                      jnp.int32)
    return jnp.asarray(pool), tables, q_nope, q_rope, lp, pos


def _latent_rows_read(pool, rows, q_nope, q_rope, lp, pos):
    """The latent layer's read as ``families/latent_moe.attend_pool``
    makes it: the absorbed queries through the fused kernel as ONE shared
    KV head over layer 1 of a stacked pool whose layer 0 is poison — a
    slot's value the first ``DC`` numbers of its key, no value pool —
    then ``W_v`` once on the folded sums."""
    from paddle_tpu.models import latent_moe as model

    qq = model.absorb_query(q_nope, q_rope, lp, _LatentCfg, STORED)
    o_lat = row_attention(
        qq, pos, jnp.asarray(rows),
        jnp.stack([jnp.full_like(pool, jnp.nan), pool]), None, 1, 1,
        (DN + DR) ** -0.5, dv=DC)
    assert o_lat.shape == (*qq.shape[:3], DC)
    return np.asarray(model.unabsorb_output(o_lat, lp, _LatentCfg))


_LATENT_CASES = sorted(c for c in _CASES if "window" not in c)


@pytest.mark.parametrize("s", [1, 5], ids=["decode", "verify"])
@pytest.mark.parametrize("case", _LATENT_CASES)
def test_latent_rows_read_is_the_absorbed_table_read(case, s):
    """The latent read (the kernel's one-pool form, PR 47; the XLA
    ``_attend_rows`` of PR 35 before it) against the model's own
    ``attend_absorbed`` over every lane's whole gathered table."""
    from paddle_tpu.models import latent_moe as model

    c = _CASES[case]
    lens, L = c["lens"], len(c["lens"])
    pool, tables, q_nope, q_rope, lp, pos = _latent_operands(
        lens, s, seed=len(case))
    vis = jnp.arange(M * B)[None, None, :] <= pos[:, :, None]
    want = np.asarray(model.attend_absorbed(
        q_nope, q_rope, pool[tables].reshape(L, M * B, STORED), vis, lp,
        _LatentCfg))
    w, tile, cap = fit_rows((c["W"], c["tile"]), L, M)
    rows, _, n, _ = pack_rows(
        [(i, list(tables[i]), lens[i], lens[i] + s)
         for i in range(L) if lens[i]], L, s, B, w, cap)
    got = _latent_rows_read(pool, rows, q_nope, q_rope, lp, pos)
    held = np.asarray(lens) > 0
    np.testing.assert_allclose(got[held], want[held], rtol=2e-5, atol=2e-6)
    assert (got[~held] == 0).all()  # idle lanes read 0, not NaN


def test_a_latent_row_above_its_lanes_positions_weighs_nothing():
    """Rows over the lane's WHOLE table, the ones above its positions
    too: poisoning their blocks with huge values moves nothing."""
    lens, s = [21], 2
    pool, tables, q_nope, q_rope, lp, pos = _latent_operands(lens, s, 3)
    w, tile, cap = fit_rows((2, 2), 1, M)
    rows, *_ = pack_rows([(0, list(tables[0]), lens[0], M * B)], 1, s, B,
                         w, cap)
    dead = tables[0, 6:]  # slots 24.. lie above positions 21-22
    args = (rows, q_nope, q_rope, lp, pos)
    np.testing.assert_array_equal(
        _latent_rows_read(pool.at[dead].set(3e4), *args),
        _latent_rows_read(pool, *args))


# the latent layer itself (``families/latent_moe.attend_pool``: the new
# entries written, the absorbed query, the kernel's one-pool form, ``W_v``
# once) against the model's definition over every lane's whole table.
# lens: tokens a lane holds (0: idle, no row); s: positions fed a lane (a
# round's 1 or 5, a prefill chunk's); W, tile: the rows operand's form;
# steer: the kernel's constants made small, so that tiny shapes go several
# chunks side by side, under a loop, and over several query tiles
_LAYER = {
    "round_ragged": dict(lens=[5, 17, 30, 2], s=5, W=2, tile=4),
    "plain_round": dict(lens=[15, 23, 7, 40], s=1, W=2, tile=3),
    # lane 1's rows (3) start in the first tile of 2 rows and end in the
    # second; the last tile holds pad rows; lanes 0 and 3 hold nothing
    "rows_over_a_tile_boundary": dict(lens=[0, 19, 9, 0], s=5, W=2, tile=2),
    "contexts_end_inside_a_block": dict(lens=[1, 6, 13], s=5, W=3, tile=4),
    "chunks_side_by_side": dict(lens=[11, 29], s=5, W=2, tile=4,
                                steer=dict(_Q_ROWS=5, _SIDE_ROWS=10)),
    "chunks_under_a_loop": dict(lens=[11, 29], s=5, W=2, tile=4,
                                steer=dict(_Q_ROWS=5, _SIDE_ROWS=5)),
    "a_chunk": dict(lens=[24], s=16, W=2, tile=4),
    "a_chunk_in_query_tiles": dict(
        lens=[24], s=16, W=2, tile=4,
        steer=dict(_Q_TILE_ROWS=32, _Q_ROWS=8, _SIDE_ROWS=16)),
    "a_part_padded_chunk": dict(lens=[24], s=16, W=2, tile=4, real=9,
                                steer=dict(_Q_TILE_ROWS=32)),
}


@pytest.mark.parametrize("rope", [True, False], ids=["rope", "nope"])
@pytest.mark.parametrize("heads", [8, 4], ids=["heads8", "heads4"])
@pytest.mark.parametrize("case", sorted(_LAYER))
def test_the_latent_layer_reads_through_the_kernel(case, heads, rope,
                                                   monkeypatch):
    """128 and 32 heads cut to 8 and 4 (a head group is the whole head
    count: one shared KV head), with and without the rotary embedding."""
    from paddle_tpu.models import latent_moe as model
    from paddle_tpu.ops.pallas import row_attention as RA
    from paddle_tpu.serving.families import latent_moe as fam
    from paddle_tpu.serving.families.common import write_slots

    class cfg:
        kv_lora_rank, qk_rope_head_dim, qk_nope_head_dim = 32, 8, 16
        v_head_dim, num_attention_heads = 12, heads
        rms_norm_eps, rope_theta = 1e-5, 1e4

    c = _LAYER[case]
    for name, value in c.get("steer", {}).items():
        monkeypatch.setattr(RA, name, value)
    lens, s, L = c["lens"], c["s"], len(c["lens"])
    real = c.get("real", s)
    rng = np.random.RandomState(len(case) + heads)
    hid, dc, dr = 24, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dq = cfg.qk_nope_head_dim + dr

    def leaf(*shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)

    lp = {"q": leaf(hid, heads * dq), "kv_a": leaf(hid, dc + dr),
          "kv_norm": 1 + leaf(dc),
          "kv_b": leaf(dc, heads * (cfg.qk_nope_head_dim + cfg.v_head_dim))}
    nb = 1 + L * M
    pool = np.zeros((2, nb, B, fam.LANES), np.float32)
    pool[1, ..., :dc + dr] = rng.randn(nb, B, dc + dr)
    pool[0] = np.nan  # the other layer's: never read
    tables = rng.permutation(np.arange(1, nb)).reshape(L, M).astype(np.int32)
    u = leaf(L, s, hid)
    pos = jnp.asarray(np.asarray(lens)[:, None] + np.arange(s)[None, :],
                      jnp.int32)
    live = [i for i in range(L) if lens[i]]
    w, _, cap = fit_rows((c["W"], c["tile"]), L, M)
    rows, wblk, n, _ = pack_rows(
        [(i, list(tables[i]), lens[i], lens[i] + real) for i in live],
        L, s, B, w, cap)
    assert n < cap or case == "a_chunk"  # pad rows after the live ones
    wlimit = jnp.asarray([lens[i] + real if lens[i] else 0
                          for i in range(L)], jnp.int32)
    blk, off = write_slots(jnp.asarray(wblk), pos, wlimit, B,
                           "mla/kv_write")
    got, after = fam.attend_pool(u, lp, 1, jnp.asarray(pool),
                                 jnp.asarray(rows), pos, blk, off, cfg,
                                 rope=rope)
    assert got.shape == (L, s, heads * cfg.v_head_dim)

    q_nope, q_rope, _ = model.latent_qkv(u, lp, pos, cfg, rope)
    vis = jnp.arange(M * B)[None, None, :] <= pos[:, :, None]
    want = np.asarray(model.attend_absorbed(
        q_nope, q_rope, after[1][tables].reshape(L, M * B, fam.LANES), vis,
        lp, cfg))
    got = np.asarray(got)
    np.testing.assert_allclose(got[live, :real], want[live, :real],
                               rtol=3e-5, atol=3e-6)
    assert np.isfinite(got).all()
    idle = [i for i in range(L) if not lens[i]]
    assert (got[idle] == 0).all()  # a lane with no row reads 0, not NaN


def _kernel_call(b, s, nh, nkv, d, dv):
    """The traced ``pallas_call`` of one read: (grid, operand blocks,
    grid semantics)."""
    import jax

    from paddle_tpu.ops.pallas.row_attention import row_attention

    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (eqn,) = calls(jax.make_jaxpr(
        lambda q, pos, rows, kp, vp: row_attention(
            q, pos, rows, kp, vp, 0, nkv, d ** -0.5))(
        sd(jnp.bfloat16, b, s, nh, d), sd(jnp.int32, b, s),
        sd(jnp.int32, 6 * b, 18), sd(jnp.bfloat16, 2, 65, 16, nkv * d),
        sd(jnp.bfloat16, 2, 65, 16, nkv * dv)).jaxpr)
    at = eqn.params["grid_mapping"]
    blocks = [tuple(getattr(n, "block_size", n) for n in m.block_shape)
              for m in at.block_mappings]
    return (len(at.grid), blocks,
            eqn.params["compiler_params"]["mosaic_tpu"].dimension_semantics)


# (lanes, positions) of every call a cell serves, by head geometry: a plain
# and a verify round of 64 lanes (32: Mistral's), the family's prefill chunk
_SERVED_CALLS = [
    (geometry, b, s)
    for geometry, lanes, width in (("g4_d128", 32, 128), ("g4_d64", 64, 128),
                                   ("g4_d64", 64, 512),
                                   ("g16_d192_dv128", 64, 128))
    for b, s in ((lanes, 1), (lanes, 5), (1, width))]


@pytest.mark.parametrize("geometry,b,s", sorted(set(_SERVED_CALLS)))
def test_a_call_within_the_bound_is_the_one_axis_kernel(geometry, b, s):
    """Every call the cells served before a call could be wider (and
    LFM2's 512 at group 4) is traced to the kernel call it was: the grid
    the live rows alone, each block a lane's WHOLE query rows — so the
    cells that keep their width run the program they ran (PERF.md section
    6, PR 42: read here, not from compiled text by eye)."""
    nkv, g, d, dv = _GEOMETRIES[geometry]
    M = s * g
    axes, blocks, semantics = _kernel_call(b, s, nkv * g, nkv, d, dv)
    assert axes == 1 and semantics == ("arbitrary",)
    assert blocks[:2] == [(1, M, 1), (1, nkv, M, d)]
    assert blocks[-1] == (1, nkv, M, dv)


def test_the_widest_served_chunk_is_four_query_tiles():
    """MiMo's 512 positions at group 16: a leading grid axis of four tiles
    of the 2,048 rows a 128-position chunk brought."""
    nkv, g, d, dv = _GEOMETRIES["g16_d192_dv128"]
    axes, blocks, semantics = _kernel_call(1, 512, nkv * g, nkv, d, dv)
    assert axes == 2 and semantics == ("arbitrary", "arbitrary")
    assert blocks[:2] == [(1, 2048, 1), (1, nkv, 2048, d)]
    assert blocks[-1] == (1, nkv, 2048, dv)
