"""The latent read over LIVE ROWS through the engine (ISSUE 35): the
latent-attention family and the linear-attention family (whose latent
layers read through the same functions) held to their plain references
with the read's constants steered small, so that tiny engines cut a lane
into several rows over several tiles of the operand — decode, verify
with rejected drafts, a prefill chunk wider than what it is fed, a
shared prefix block, preempt-and-recompute, an idle lane, pad rows after
the live ones. Since PR 47 the rows go through the fused kernel
(``ops/pallas/row_attention.py``'s one-pool form, interpret mode here).

The layer's read itself against the model's ``attend_absorbed`` over
whole tables: tests/test_serving_rows.py (e). The helpers (tiny models,
seeded weights, the references) are the families' own test modules'.
"""
import numpy as np
import pytest

import test_latent_moe as LT
import test_linear_latent_moe as KT
from paddle_tpu.models import (
    LatentMoEForCausalLM, LinearLatentMoEForCausalLM,
)
from paddle_tpu.serving.families import latent_moe as fam

ref_latent = LT.ref
ref_linear = KT.ref


@pytest.fixture(params=[(2, 2, 1), (1, 3, 2), (3, 64, 4)],
                ids=["W2_tile2_p1", "W1_tile3_p2", "W3_one_tile"])
def small_rows(request, monkeypatch):
    """Rows of W blocks, the operand's length in whole tiles of a few
    (a prefill chunk's: ``p``): the engines below then hand the kernel
    2-10 rows a lane over several tiles; the last case every live row in
    ONE tile."""
    w, tile, ptile = request.param
    monkeypatch.setattr(fam, "ROW_BLOCKS", w)
    monkeypatch.setattr(fam, "ROW_TILE", tile)
    monkeypatch.setattr(fam, "PREFILL_TILE", ptile)
    return request.param


def _several_tiles(eng, small_rows, lanes):
    w, tile, ptile = small_rows
    got = eng._rows_form("verify", lanes)
    assert got[:2] == (w, min(tile, got[2]))
    assert eng._rows_form("prefill", 1)[:2] == (w, ptile)
    if tile < 64:
        assert got[2] > got[1]  # several tiles: a tile is not the table


# -- the latent-attention family ------------------------------------------------

@pytest.fixture(scope="module")
def latent_model():
    return LT.seeded(LatentMoEForCausalLM(LT.tiny_config()))


@pytest.mark.parametrize("chunk", [32, 128], ids=["chunk32", "chunk128"])
def test_latent_engine_reads_rows(ref_latent, latent_model, small_rows,
                                  chunk):
    """``test_latent_moe``'s traffic (a prompt of several blocks and
    chunks, a prefix-cache hit on 3 shared blocks, a repeating prompt the
    drafter proposes for — accepted and rejected drafts —, a short one;
    4 requests on 3 lanes, so lanes idle at the end; a pool of 14 blocks,
    so one is preempted and recomputed) against the reference's full
    forward. At chunk 128 a call is wider than any prompt is long, and
    its pad runs past ``max_seq_len``."""
    eng, handles = LT._serve(latent_model, LT._traffic(), num_blocks=15,
                             prefill_chunk=chunk)
    _several_tiles(eng, small_rows, 3)
    c = eng.counters
    assert c["verify_steps"] > 0 and c["decode_steps"] > 0
    assert 0 < c["spec_accepted_tokens"] < c["spec_proposed_tokens"]
    assert c["prefix_hit_tokens"] >= 48
    assert c["preemptions"] >= 1
    assert max(LT._served_gaps(ref_latent, latent_model, handles)) < LT.TOL
    # what the programs read follows what the lanes held (the kernel
    # walks the live rows: whole rows, so no less than the live tokens)
    assert c["kv_read_tokens"] <= c["kv_gathered_tokens"]
    if small_rows[1] < 64:
        assert c["kv_gathered_tokens"] < c["kv_dense_read_tokens"]
    # every kind's rows went through the kernel, and were billed so
    assert set(eng.stats()["row_read"].values()) == {"kernel"}
    assert c["kv_gathered_tokens"] == c["kv_kernel_rows"] * small_rows[0] \
        * eng.config.block_size
    eng.scheduler.pool.check_invariant()


def test_latent_engine_serves_what_the_table_sized_rows_serve(
        latent_model, small_rows):
    """The same requests through small rows and through rows as wide as
    a lane's table in one tile (the read the family had): the same
    tokens."""
    reqs = LT._traffic()
    _, got = LT._serve(latent_model, reqs, spec=False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fam, "ROW_BLOCKS", 10)  # GEOM: 160 / 16 blocks a lane
        mp.setattr(fam, "ROW_TILE", 64)
        mp.setattr(fam, "PREFILL_TILE", 4)
        eng, want = LT._serve(latent_model, reqs, spec=False)
        assert eng._rows_form("decode", 3) == (10, 3, 3)
    assert [h.output for h in got] == [h.output for h in want]


# -- the linear-attention family -------------------------------------------------

@pytest.fixture(scope="module")
def linear_model():
    return KT.seeded(LinearLatentMoEForCausalLM(KT.tiny_config()))


@pytest.mark.parametrize("chunk", [8, 128], ids=["chunk8", "chunk128"])
def test_linear_engine_prefill_and_decode_read_rows(
        ref_linear, linear_model, small_rows, chunk):
    """Prompts shorter than, equal to and several times the chunk,
    decoded plainly: every served token the reference's first choice.
    7 requests on 3 lanes (24 blocks of 4 a lane): lanes idle at the
    end, the last tile holds pad rows."""
    eng = KT.engine(linear_model, spec=False, prefill_chunk=chunk)
    _several_tiles(eng, small_rows, 3)
    work = KT.prompts(5) + [np.arange(8, dtype=np.int32),
                            np.arange(3, dtype=np.int32)]
    reqs = [eng.submit(p, max_new_tokens=12) for p in work]
    eng.run()
    for p, r in zip(work, reqs):
        assert KT.served_gap(ref_linear, linear_model, p,
                             np.asarray(r.output)) < KT.TOL
    c = eng.counters
    assert c["kv_read_tokens"] <= c["kv_gathered_tokens"]
    if small_rows[1] < 64:
        assert c["kv_gathered_tokens"] < c["kv_dense_read_tokens"]
    # every kind's rows went through the kernel, and were billed so
    assert set(eng.stats()["row_read"].values()) == {"kernel"}
    assert c["kv_gathered_tokens"] == c["kv_kernel_rows"] * small_rows[0] \
        * eng.config.block_size


def test_linear_engine_verify_and_preemption_read_rows(linear_model,
                                                       small_rows):
    """Repeating prompts (the n-gram drafter proposes and mostly misses)
    in a pool too small for them all: verify rounds with rejected drafts
    and a preempted request recomputed, token for token what a roomy
    engine decodes plainly."""
    rng = np.random.default_rng(7)
    work = [np.tile(rng.integers(0, KT.VOCAB, 4).astype(np.int32), 5)
            for _ in range(5)]
    outs = {}
    for name, kw in (("plain", dict(spec=False)),
                     ("tight", dict(num_blocks=17))):
        eng = KT.engine(linear_model, **kw)
        reqs = [eng.submit(p, max_new_tokens=14) for p in work]
        eng.run()
        outs[name] = [r.output for r in reqs]
    st = eng.stats()
    assert outs["tight"] == outs["plain"]
    assert st["verify_steps"] > 0 and st["preemptions"] >= 1
    assert st["spec_rolled_back_tokens"] \
        == st["spec_proposed_tokens"] - st["spec_accepted_tokens"] > 0
