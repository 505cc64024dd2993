"""A step program's operands go up as ONE array (PERF.md section 6, PR
43): ``engine.OperandLayout`` lays a call's read operand and its kind's
own operands end to end in one ``int32`` vector, and the program the
engine compiles (``engine.packed_program`` around the family's function)
cuts them back out by static slices — every operand bit for bit, for the
three kinds and the six families' read forms; one upload a program call,
whatever the call; and a buffer of its own for every call, so a prefill
call enqueued behind another reads its own ``start``."""
import jax
import numpy as np
import pytest

import test_program_scopes as PS
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.engine import OperandLayout, packed_program

KINDS = ("decode", "verify", "prefill")
# a family's usual geometry; a lane's whole table in ONE block (one row of
# one block, the slot entry its neighbour); a prompt whose last chunk is
# mostly padding
CASES = ("usual", "one_block", "short_tail")


@pytest.fixture(scope="module", params=sorted(PS.FAMILIES))
def built(request):
    model, geom = PS.FAMILIES[request.param]()
    model.eval()
    return request.param, model, geom


def _engine(built, case):
    family, model, geom = built
    if case == "one_block":
        geom = {**geom, "block_size": 16, "max_seq_len": 16,
                "prefill_chunk": 16, "num_blocks": None}
    eng = ServingEngine(model, ServingConfig(**geom))
    assert eng._family.name == family
    assert (eng.blocks_per_lane == 1) == (case == "one_block")
    return eng


def _operands(eng, kind, case, rng):
    """One call's operands as the engine's own methods make them: the
    read operand from ``_pack_read`` over lanes that hold blocks, the
    kind's own with every entry distinct."""
    cfg = eng.config
    B, M = cfg.block_size, eng.blocks_per_lane
    L, S, C = cfg.max_lanes, cfg.spec_k + 1, eng.prefill_chunk

    def blocks(n):
        return [int(b) for b in rng.choice(
            np.arange(1, eng.scheduler.pool.num_blocks), n, replace=False)]

    def fill(*shape):
        return rng.integers(1, 2 ** 31 - 1, shape).astype(np.int32)

    if kind == "prefill":
        ctx = min(M * B, C + 3) if case == "short_tail" else min(M * B, C)
        start = C if ctx > C else 0  # the tail chunk: 3 real tokens
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :ctx - start] = fill(ctx - start)
        read = eng._pack_read(
            "prefill", 1, C, [(0, blocks(-(-ctx // B)), start, ctx)],
            slot=L - 1)
        return read, chunk, np.int32(start), ctx, ctx - 1 - start
    width = 1 if kind == "decode" else S
    items = []
    for lane in range(0, L, 2):  # every other lane idle
        n = int(rng.integers(1, M * B - width + 1))
        items.append((lane, blocks(-(-(n + width) // B)), n, n + width))
    read = eng._pack_read(kind, L, width, items)
    if kind == "decode":
        return read, fill(L), fill(L)
    return read, fill(L), fill(L, S), fill(L)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("kind", KINDS)
def test_every_operand_comes_back_bit_for_bit(built, kind, case):
    """pack on the host, unpack inside a jitted program: what the
    family's function is handed is what the engine made, leaf for leaf,
    with the pools where they were."""
    eng = _engine(built, case)
    rng = np.random.default_rng(KINDS.index(kind) * 7 + CASES.index(case))
    operands = _operands(eng, kind, case, rng)
    spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.int32), operands)
    # the engine's own layout is that of the operands its methods make
    layout = eng._layout(kind)
    assert layout == OperandLayout.of(spec)
    assert hash(layout) == hash(OperandLayout.of(spec))  # one trace
    # a lane_state family's prefill is told its slot; nobody else is
    assert (len(operands[0]) == 3) == (kind == "prefill"
                                       and eng._family.lane_state)
    packed = layout.pack(operands)
    assert packed.dtype == np.int32 and packed.shape == (layout.size,)
    assert layout.size == sum(np.size(a) for a in
                              jax.tree_util.tree_leaves(operands))

    def _decode_step(params, pool, none, *operands, flag):
        assert flag == "static" and none is None
        return operands, pool + params

    program = packed_program(_decode_step, layout)
    assert program.__name__ == "_decode_step"
    assert packed_program(_decode_step, OperandLayout.of(spec)) is program
    got, pool = jax.jit(program, static_argnames=("flag",))(
        np.float32(2), np.float32(3), None, packed, flag="static")
    assert float(pool) == 5.0
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(operands)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(operands)):
        assert a.dtype == np.int32 and a.shape == np.shape(b)
        np.testing.assert_array_equal(np.asarray(a), b)


def test_operands_of_another_shape_are_refused(built):
    eng = _engine(built, "usual")
    rng = np.random.default_rng(3)
    read, cur, last = _operands(eng, "decode", "usual", rng)
    layout = OperandLayout.of(jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, np.int32),
        (read, cur, last)))
    with pytest.raises(ValueError, match="do not fit"):
        layout.pack((read, cur[:-1], last))
    with pytest.raises(ValueError, match="do not fit"):
        layout.pack((read, cur))


class _SometimesDrafts:
    """Proposes the context's last token, every other call: rounds with
    drafts (mostly rejected) and rounds without."""

    def __init__(self):
        self.calls = 0

    def propose(self, tokens, k):
        self.calls += 1
        return np.full((min(k, 2) * (self.calls % 2),), tokens[-1], np.int32)


def test_one_upload_a_program_call(built):
    """A pool too small for its load, drafts that come and go: prefill
    chunks (re-admissions' too), plain rounds and verify rounds each hand
    the device ONE array."""
    family, model, geom = built
    B = geom["block_size"]
    # three lanes that cannot all grow to their ends: the newest is
    # preempted and comes back
    geom = {**geom, "max_lanes": 3, "max_seq_len": 16 * B,
            "prefill_chunk": 2 * B, "num_blocks": 1 + 16 + 8,
            "prefix_cache": False}
    eng = ServingEngine(model, ServingConfig(**geom),
                        drafter=_SometimesDrafts())
    assert eng._family.name == family
    c = eng.counters
    assert c["operand_uploads"] == 0 == c["operand_upload_bytes"]
    rng = np.random.default_rng(5)
    reqs = [eng.submit(rng.integers(0, 256, 5 * B + i).astype(np.int32),
                       max_new_tokens=9 * B) for i in range(4)]
    eng.run()
    assert all(len(r.output) == 9 * B for r in reqs)
    assert c["preemptions"] > 0 and c["decode_steps"] > 0 \
        and c["verify_steps"] > 0 and c["prefill_chunks"] > 4, c
    assert c["operand_uploads"] == (c["decode_steps"] + c["verify_steps"]
                                    + c["prefill_chunks"])
    sizes = {k: eng._layout(k).size * 4 for k in KINDS}
    assert c["operand_upload_bytes"] == (
        c["decode_steps"] * sizes["decode"]
        + c["verify_steps"] * sizes["verify"]
        + c["prefill_chunks"] * sizes["prefill"])
    assert eng.stats()["operand_uploads"] == c["operand_uploads"]


def test_prefill_calls_enqueued_back_to_back_keep_their_own_operands(built):
    """No sync between a prompt's chunks, and the CPU backend may alias
    the host's memory: every call's buffer must be its own. The arrays
    the calls were handed, read AFTER the run, still hold each call's own
    start, chunk and last index."""
    family, model, geom = built
    eng = ServingEngine(model, ServingConfig(**geom))
    C = eng.prefill_chunk
    eng.warmup()
    handed = []
    run = eng._prefill_exec

    def spy(params, *args):
        handed.append((args[-1], req.lane))
        return run(params, *args)

    eng._prefill_exec = spy
    prompt = np.random.default_rng(11).integers(
        1, 256, 2 * C + 3).astype(np.int32)
    req = eng.submit(prompt, max_new_tokens=3)
    eng.step()
    assert len(handed) == 3 == eng.counters["prefill_chunks"]
    layout = eng._layout("prefill")
    for i, (packed, lane) in enumerate(handed):
        read, chunk, start, ctx, last_idx = layout.unpack(
            np.asarray(packed))
        assert int(start) == i * C and int(ctx) == prompt.size
        want = np.zeros((C,), np.int32)
        piece = prompt[i * C:(i + 1) * C]
        want[:piece.size] = piece
        np.testing.assert_array_equal(chunk[0], want)
        assert int(last_idx) == (2 if i == 2 else 0)
        if eng._family.lane_state:
            assert int(read[-1][0]) == lane is not None
    # and the served tokens are a one-call engine's
    wide = ServingEngine(model, ServingConfig(
        **{**geom, "prefill_chunk": 4 * C,
           "max_seq_len": max(geom["max_seq_len"], 4 * C)}))
    ref = wide.submit(prompt, max_new_tokens=3)
    wide.run()
    eng.run()
    assert req.output == ref.output


# -- the block ids a round packs (scheduler.BlockList, engine.pack_rows) --------

def _wblk_by_position(items, lanes, width, block):
    """``pack_rows``' write blocks as they were first written: position
    by position."""
    wblk = np.zeros((lanes, width), np.int32)
    for lane, blocks, first, _ in items:
        for j in range(width):
            k = (first + j) // block
            if k < len(blocks):
                wblk[lane, j] = blocks[k]
    return wblk


@pytest.mark.parametrize("width,block", [(1, 16), (5, 16), (5, 2), (8, 4),
                                         (32, 16), (48, 16), (512, 16)])
def test_pack_rows_takes_arrays_for_lists_byte_for_byte(width, block):
    """A lane's blocks as ``BlockList.ids`` or as a plain list: the same
    operand; and each position's write block, filled a block at a time,
    is what the walk over positions gave — a first position anywhere in
    its block, a list that ends before the call's last position (0
    there), one that goes on past it."""
    from paddle_tpu.serving.engine import fit_rows, pack_rows
    from paddle_tpu.serving.scheduler import BlockList

    rng = np.random.default_rng(width * 31 + block)
    lanes, per_lane = 6, 40
    w, _, cap = fit_rows((4, 8), lanes, per_lane)
    for _ in range(20):
        items = []
        for lane in rng.permutation(lanes)[:4]:
            first = int(rng.integers(0, per_lane * block - width))
            upto = first + int(rng.integers(1, width + 1))
            held = int(rng.integers(-(-upto // block), per_lane + 1))
            blocks = BlockList(int(b) for b in rng.integers(1, 999, held))
            items.append((int(lane), blocks, first, upto))
        got = pack_rows([(ln, b.ids, f, u) for ln, b, f, u in items],
                        lanes, width, block, w, cap)
        want = pack_rows([(ln, list(b), f, u) for ln, b, f, u in items],
                         lanes, width, block, w, cap)
        for a, b in zip(got[:2], want[:2]):
            assert a.dtype == b.dtype == np.int32
            np.testing.assert_array_equal(a, b)
        assert got[2:] == want[2:]
        np.testing.assert_array_equal(
            got[1], _wblk_by_position(items, lanes, width, block))


def test_a_block_list_keeps_an_array_of_itself():
    from paddle_tpu.serving.scheduler import BlockList

    rng = np.random.default_rng(2)
    blocks, plain = BlockList([7, 3]), [7, 3]
    assert blocks == plain and blocks.ids.dtype == np.int32
    for _ in range(200):  # grow and trim at the tail, as the scheduler does
        if rng.random() < 0.6 or len(plain) < 2:
            more = [int(b) for b in rng.integers(1, 10 ** 6,
                                                 int(rng.integers(1, 9)))]
            blocks.extend(more)
            plain.extend(more)
        else:
            n = int(rng.integers(1, len(plain)))
            del blocks[n:]
            del plain[n:]
        assert blocks == plain and len(blocks) == len(plain)
        np.testing.assert_array_equal(blocks.ids, plain)
    assert list(BlockList().ids) == [] and BlockList() == []
    assert blocks[1:3] == plain[1:3] and set(blocks) == set(plain)


@pytest.mark.parametrize("how", [
    lambda b: b.append(1), lambda b: b.insert(0, 1), lambda b: b.pop(),
    lambda b: b.remove(5), lambda b: b.sort(), lambda b: b.reverse(),
    lambda b: b.clear(), lambda b: b.__setitem__(0, 9),
    lambda b: b.__iadd__([1]), lambda b: b.__imul__(2),
    lambda b: b.__delitem__(0), lambda b: b.__delitem__(slice(0, 1)),
    lambda b: b.__delitem__(slice(None, None, 2))])
def test_a_block_list_changes_at_its_tail_only(how):
    from paddle_tpu.serving.scheduler import BlockList

    blocks = BlockList([5, 6, 7])
    with pytest.raises(TypeError, match="tail only"):
        how(blocks)
    assert blocks == [5, 6, 7] and list(blocks.ids) == [5, 6, 7]


def test_the_exec_cache_key_names_the_packed_form(tmp_path, monkeypatch):
    """An executable serialized for the tuple of operands must never be
    loaded for the packed one: each program's key says ``packed`` and the
    vector's length."""
    from paddle_tpu.jit import exec_cache as ec

    model, geom = PS.FAMILIES["dense_gqa"]()
    model.eval()
    keys = {}
    real = ec.get_or_compile

    def spy(key, lower_fn, label=None):
        keys[label] = key
        return real(key, lower_fn, label=label)

    monkeypatch.setattr(ec, "get_or_compile", spy)
    ec.enable(str(tmp_path))
    ec.clear()
    try:
        eng = ServingEngine(model, ServingConfig(**geom))
        eng.warmup()
    finally:
        ec.disable()
        ec.clear()
    assert set(keys) == {"serving/decode", "serving/prefill",
                         "serving/verify"}
    for label, key in keys.items():
        kind = label.split("/")[1]
        assert key["kind"] == "serving_" + kind
        assert key["operands"] == ("packed", eng._layout(kind).size)
    assert len({ec.key_hash(k)[1] for k in keys.values()}) == 3
