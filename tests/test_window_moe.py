"""The window-attention / full-attention sparse-expert model and its
serving family against the plain reference
(``benchmarks/chip/reference/swa_gqa_moe.py``, loaded by path: ONE copy).

Tiny widths with the published RATIOS, CPU, seeded weights: keys wider
than values (24 / 16), twice the key/value heads in the window layers (4
against 2), a third of each head rotated, a window (8) far shorter than
the sequences and a ring (16 slots at 4 drafts a round) shorter than a
prompt, so that every request wraps its ring several times. The program
runs in float32 here, so what separates it from the float32 reference is
the order of summation: ``TOL`` = 2e-4 on logits of magnitude ~1.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.errors import UnimplementedError
from paddle_tpu.incubate.distributed.models.moe.held_experts import (
    HeldExperts,
)
from paddle_tpu.models import (
    WindowMoEConfig, WindowMoEForCausalLM, generate, window_moe as M,
)
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.families import window_moe as FAM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 256
WINDOW = 8
PATTERN = [0, 1, 1, 1, 0]  # full, three window layers, full


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "swa_gqa_moe_reference",
        os.path.join(ROOT, "benchmarks/chip/reference/swa_gqa_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(**kw):
    """Layer 0 full over a dense SwiGLU, then window / window / window /
    full over expert layers holding experts 2-5 of 8."""
    base = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=80,
                moe_intermediate_size=24, num_hidden_layers=5,
                hybrid_layer_pattern=PATTERN, moe_layer_freq=[0, 1, 1, 1, 1],
                num_attention_heads=8, num_key_value_heads=2,
                swa_num_key_value_heads=4, head_dim=24, v_head_dim=16,
                sliding_window=WINDOW, n_routed_experts=4, router_experts=8,
                first_held_expert=2, num_experts_per_tok=3)
    base.update(kw)
    return WindowMoEConfig(**base)


def seeded(model, seed=0, dtype="float32"):
    """Matrices N(0, 0.1) (the router's selection bias too), norm weights
    1 +- 0.1 (so a dropped norm weight shows), the sinks N(0, 1) (so a
    dropped sink shows), from one generator in parameter order."""
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        leaf = name.rsplit(".", 1)[-1]
        if leaf in ("ln_in", "ln_post", "norm"):
            v = 1 + 0.1 * rng.uniform(-1, 1, p.shape)
        else:
            v = rng.normal(0, 1.0 if leaf == "sink" else 0.1, p.shape)
        p._data = jnp.asarray(v, dtype)
    model.eval()
    return model


def ref_params(model):
    out = {k: np.asarray(getattr(model, k)._data, np.float32)
           for k in ("embed", "norm", "lm_head")}
    out["layers"] = [{k: np.asarray(p._data, np.float32)
                      for k, p in blk.leaves().items()}
                     for blk in model.layers]
    return out


def ref_model(model):
    """The reference's ``m``: the configuration's keys as published (a
    null scaling factor)."""
    return dict(vars(model.config), routed_scaling_factor=None)


def ref_logits(ref, model, ids, quant=False):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            ref_params(model), jnp.asarray(ids), ref_model(model),
            quant=quant))


@pytest.fixture(scope="module")
def model():
    return seeded(WindowMoEForCausalLM(tiny_config()))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(1).integers(0, VOCAB, (2, 37)).astype(
        np.int32)


# -- the model against the reference -------------------------------------------

def test_whole_model_logits_match_the_reference(ref, model, ids):
    got = model(pt.to_tensor(ids)).numpy()
    for b in range(ids.shape[0]):
        want = ref_logits(ref, model, ids[b])
        assert np.abs(want).max() > 0.5
        assert np.abs(got[b] - want).max() < TOL


def test_each_layer_matches_the_reference(ref, model, ids):
    """Layer by layer, each kind from the same input: full + dense,
    window + experts, full + experts."""
    m = ref_model(model)
    x = np.random.default_rng(2).normal(0, 1, (1, 29, 64)).astype(np.float32)
    for blk, lw in zip(model.layers, ref_params(model)["layers"]):
        got = blk(pt.to_tensor(x)).numpy()[0]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref._layer(jnp.asarray(x[0]), lw, m, False)[0])
        assert np.abs(got - want).max() < TOL
        assert np.abs(want - x[0]).max() > 0.1


def test_a_lower_precision_fails_the_tolerance(ref, model, ids):
    """The same weights served in bfloat16, and the reference's own fp8
    control, miss ``TOL`` by an order of magnitude or more."""
    want = ref_logits(ref, model, ids[0])
    low = seeded(WindowMoEForCausalLM(tiny_config(dtype="bfloat16")),
                 dtype="bfloat16")
    got = low(pt.to_tensor(ids[:1])).numpy().astype(np.float32)[0]
    assert np.abs(got - want).max() > 10 * TOL
    assert np.abs(ref_logits(ref, model, ids[0], quant=True)
                  - want).max() > 10 * TOL


def test_the_layer_kinds_follow_the_published_lists():
    m = WindowMoEForCausalLM(tiny_config())
    assert ["sink" in b.leaves() for b in m.layers] \
        == [False, True, True, True, False]
    assert [b.mlp is not None for b in m.layers] == [False] + [True] * 4
    # twice the key/value heads in a window layer, keys wider than values
    assert m.layers[0].qkv.shape == [64, 8 * 24 + 2 * (24 + 16)]
    assert m.layers[1].qkv.shape == [64, 8 * 24 + 4 * (24 + 16)]
    assert m.config.rotary_dim == 8  # int(24 x 0.334)
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        tiny_config(hybrid_layer_pattern=[0, 1, 1])
    with pytest.raises(ValueError, match="swa_head_dim"):
        tiny_config(swa_head_dim=32)


def test_partial_rotary_rotates_the_first_columns_alone():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (1, 5, 2, 24)), jnp.float32)
    pos = jnp.arange(5, dtype=jnp.int32)[None] + 3
    y = np.asarray(M.partial_rope(x, pos, 1e4, 8))
    assert (y[..., 8:] == np.asarray(x)[..., 8:]).all()
    assert np.abs(y[..., :8] - np.asarray(x)[..., :8]).min() > 1e-4
    # a rotation: each rotated pair keeps its length
    for i in range(4):
        np.testing.assert_allclose(
            y[..., i] ** 2 + y[..., i + 4] ** 2,
            np.asarray(x)[..., i] ** 2 + np.asarray(x)[..., i + 4] ** 2,
            rtol=1e-5)


def test_the_sink_takes_mass_and_gives_no_value():
    rng = np.random.default_rng(4)
    s = jnp.asarray(rng.normal(0, 1, (3, 7)), jnp.float32)
    sink = jnp.asarray([[0.5], [-1.0], [2.0]], jnp.float32)
    p = np.asarray(M.softmax_with_sink(s, sink))
    full = np.asarray(jax.nn.softmax(jnp.concatenate([s, sink], -1), -1))
    np.testing.assert_allclose(p, full[:, :7], rtol=1e-6)
    assert (p.sum(-1) < 1).all()
    # every score masked: no weight at all
    p0 = np.asarray(M.softmax_with_sink(jnp.full((3, 7), M.MASKED), sink))
    assert (p0 == 0).all()


def test_what_a_ring_slot_holds_is_arithmetic():
    """``held_positions``: the latest position <= t in each slot; below 0
    where this request has not written the slot; a draft's slot reads as
    a position outside the band as long as R >= window + k."""
    R, k = 16, 4
    for t in (0, 5, 15, 16, 40, 1000):
        held = np.asarray(FAM.held_positions(jnp.int32(t), R))
        want = np.asarray([max(p for p in range(t - R + 1, t + 1)
                               if p % R == s) for s in range(R)])
        assert (held == want).all()
        seen = np.asarray(M.band_mask(t, held, WINDOW))
        assert sorted(held[seen]) == list(range(max(0, t - WINDOW + 1),
                                                t + 1))
        # what positions t+1 .. t+k wrote reads as t+j-R: not seen
        for j in range(1, k + 1):
            assert not seen[(t + j) % R] or R - j >= WINDOW
    assert FAM.ring_len(tiny_config(), 4) == 16
    assert FAM.ring_len(tiny_config(sliding_window=128), 4) == 144


def _expert_layer(first, held, seed=3, n_shared=0):
    layer = HeldExperts(64, 32, 16, held, first_held=first, top_k=4,
                        n_shared=n_shared, selection_bias=True)
    rng = np.random.default_rng(seed)  # the same draws for every share
    full = {"router": rng.normal(0, 0.3, (64, 16)),
            "experts_gate_up": rng.normal(0, 0.1, (16, 64, 64)),
            "experts_down": rng.normal(0, 0.1, (16, 32, 64)),
            "router_bias": rng.normal(0, 0.2, (16,))}
    for k, v in full.items():
        if k.startswith("experts"):
            v = v[first:first + held]
        getattr(layer, k)._data = jnp.asarray(v, jnp.float32)
    return layer, full


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's test of the expert-parallel cut: 16 shares of 1 expert
    each (ranks 0-15 of 16 experts; nothing is shared, so nothing is
    counted twice) add up to the uncut layer's output as the reference
    computes it."""
    u = np.random.default_rng(5).normal(0, 1, (18, 64)).astype(np.float32)
    whole, full = _expert_layer(0, 16)
    lw = {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}
    m = {"num_experts_per_tok": 4, "n_routed_experts": 16,
         "routed_scaling_factor": None}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(jnp.asarray(u), lw, m, False))
    total, counts = 0.0, []
    for rank in range(16):
        share, _ = _expert_layer(rank, 1)
        total = total + share(pt.to_tensor(u)).numpy()
        counts.append(share.last_counts.numpy())
    assert np.abs(total - want).max() < TOL
    # every token-expert assignment lands on exactly one share
    assert int(np.concatenate(counts).sum()) == 18 * 4
    # and one share alone is NOT the layer (the cut leaves something out)
    assert np.abs(whole(pt.to_tensor(u)).numpy() - want).max() < TOL
    assert np.abs(share(pt.to_tensor(u)).numpy() - want).max() > 0.01


@pytest.mark.parametrize("n_shared", [0, 1, 2])
def test_a_layer_without_a_shared_expert_has_no_shared_work(n_shared):
    """``n_shared=0``: no shared leaves and nothing under ``moe/shared``
    in the lowered program; ``n_shared >= 1``: the leaves and the scope
    are there as they were."""
    layer, _ = _expert_layer(0, 4, n_shared=n_shared)
    names = [n for n, _ in layer.named_parameters()]
    assert ("shared_gate_up" in names) == ("shared_down" in names) \
        == bool(n_shared)
    assert names[:3] == ["router", "experts_gate_up", "experts_down"]
    assert names[-1] == "router_bias"
    if n_shared:
        assert layer.shared_gate_up.shape == [64, 2 * n_shared * 32]
    arrays = layer.arrays()
    from paddle_tpu.incubate.distributed.models.moe.held_experts import (
        sparse_expert_block,
    )

    text = jax.jit(lambda u, p: sparse_expert_block(
        u, p, top_k=4, scaling=1.0, first_held=0)).lower(
        jnp.zeros((6, 64), jnp.float32), arrays).as_text(debug_info=True)
    assert ("moe/shared" in text) == bool(n_shared)
    assert "moe/experts" in text
    y = layer(pt.to_tensor(np.ones((6, 64), np.float32))).numpy()
    assert np.isfinite(y).all()


def test_train_step_runs_and_learns():
    from paddle_tpu.jit.train_step import TrainStep

    pt.seed(0)
    m = WindowMoEForCausalLM(tiny_config(initializer_range=0.05))
    m.train()
    opt = pt.optimizer.AdamW(learning_rate=3e-3, parameters=m.parameters())
    step = TrainStep(m, opt)
    seq = np.random.default_rng(0).integers(0, VOCAB, (4, 22))
    x = pt.to_tensor(seq[:, :-1].astype(np.int32))
    y = pt.to_tensor(seq[:, 1:].astype(np.int64))
    losses = [float(step(x, y).numpy()) for _ in range(12)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.3, losses


def test_generate_raises_and_names_the_family(model):
    with pytest.raises(UnimplementedError, match="window_moe"):
        generate(model, pt.to_tensor(np.zeros((1, 4), np.int32)),
                 max_new_tokens=2)


# -- through ServingEngine ------------------------------------------------------

GEOM = dict(max_lanes=3, block_size=4, prefill_chunk=8, max_seq_len=96)


def engine(model, drafter=None, **kw):
    return ServingEngine(model, ServingConfig(**{**GEOM, **kw}),
                         drafter=drafter)


def prompts(n, seed=5, lo=5, hi=60):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, int(k)).astype(np.int32)
            for k in rng.integers(lo, hi, n)]


def served_gap(ref, model, prompt, out):
    """How far each served token's logit lies below the reference's best
    at its position (the benchmark's ``served_logit_gap``), from the
    reference's ONE forward over prompt + served tokens."""
    full = np.concatenate([prompt, out])[:-1]
    logits = ref_logits(ref, model, full)[prompt.size - 1:]
    return (logits.max(-1) - logits[np.arange(len(out)), out]).max()


def rings(eng, lane=0):
    """A lane's K and V rings, every window layer's."""
    return np.concatenate([np.asarray(p[lane]).reshape(-1)
                           for p in eng._pools[3:]])


@pytest.mark.parametrize("chunk", [8, 12, 40, 128],
                         ids=["chunk8", "chunk12", "chunk40", "chunk128"])
def test_chunked_prefill_and_plain_decode_equal_the_full_forward(
        ref, model, chunk):
    """Prompts shorter than, equal to and several times the chunk, the
    window (8) and the ring (16), decoded with speculation off: every
    served token is the reference's first choice to within ``TOL`` at its
    position. Chunks of 8 put every chunk boundary inside a band; chunks
    of 12 leave the ring's slots out of step with the chunks; at 128
    every prompt is ONE padded call wider than the ring, of which the
    last 16 real positions are kept; at 40 — two and a half rings, five
    windows, a width no prompt length here divides, as the served 512 is
    over a ring of 144 — a long prompt is a FULL wide call and then a
    part-padded one that starts from the ring the first left, and both
    go through the band a window of queries at a time."""
    eng = engine(model, spec=False, prefill_chunk=chunk)
    assert eng.stats()["win_ring_len"] == 16
    work = prompts(5) + [np.arange(8, dtype=np.int32),
                         np.arange(3, dtype=np.int32),
                         np.arange(70, dtype=np.int32) % 251]
    reqs = [eng.submit(p, max_new_tokens=20) for p in work]
    eng.run()
    for p, r in zip(work, reqs):
        assert served_gap(ref, model, p, np.asarray(r.output)) < TOL
    st = eng.stats()
    assert st["win_slot_resets"] == len(work)
    assert st["verify_steps"] == 0 and st["spec_rolled_back_tokens"] == 0
    assert st["moe_expert_calls"] == 4 * (st["decode_steps"]
                                          + st["prefill_chunks"])
    # held experts hit, counted in the rounds' calls: 1-4 of 4 a call
    assert 4 * st["decode_steps"] <= st["moe_round_experts_hit"] \
        <= 16 * st["decode_steps"]


class Oracle:
    """A drafter that knows the true continuation and, in the rounds where
    the context is one of ``at``'s lengths, proposes that entry's ``right``
    true tokens followed by its ``wrong`` false ones."""

    def __init__(self, truth, at, shift=1):
        self.truth, self.at = np.asarray(truth, np.int32), at
        self.shift = shift

    def propose(self, context, k):
        n = len(context)
        if n not in self.at:
            return np.zeros((0,), np.int32)
        right, wrong = self.at[n]
        d = self.truth[n:n + right + wrong].copy()
        d[right:] = (d[right:] + self.shift) % VOCAB
        return d[:k]


K = 4


@pytest.fixture(scope="module")
def plain_run(model):
    """One request decoded plainly: its tokens."""
    prompt = prompts(1, seed=11, lo=21, hi=22)[0]
    eng = engine(model, spec=False)
    req = eng.submit(prompt, max_new_tokens=24)
    eng.run()
    return prompt, np.asarray(req.output)


@pytest.mark.parametrize("a", range(K + 1))
def test_rejected_drafts_are_never_seen(ref, model, plain_run, a):
    """A verify round whose draft is right for ``a`` of ``k`` tokens (all
    rejected at ``a`` 0), followed two tokens later by one whose drafts
    are all right: every token is plain decoding's and the reference's
    first choice — what the rejected positions wrote into the rings lies
    above the lane's length and is overwritten before the band reaches
    it. The engine's acceptance and the program's agree."""
    prompt, truth = plain_run
    seq = np.concatenate([prompt, truth])
    first = prompt.size + 3  # the round after 3 emitted tokens
    second = first + a + 1 + 2
    eng = engine(model, Oracle(seq, {first: (a, K - a), second: (K, 0)}),
                 spec_k=K)
    req = eng.submit(prompt, max_new_tokens=24)
    eng.run()
    assert (np.asarray(req.output) == truth).all()
    assert served_gap(ref, model, prompt, truth) < TOL
    c = eng.counters
    assert c["verify_steps"] == 2
    assert c["spec_accepted_tokens"] == a + K
    assert c["spec_rolled_back_tokens"] == K - a \
        == c["spec_proposed_tokens"] - c["spec_accepted_tokens"]


def test_a_reused_lane_gives_what_a_fresh_engine_gives(ref, model):
    """One lane, two requests one after the other, the first the longer:
    the second sees none of what the first left in the rings (its slots
    read as positions below 0), though the rings are NOT clean."""
    first = prompts(1, seed=21, lo=50, hi=51)[0]
    second = prompts(1, seed=22, lo=6, hi=7)[0]
    eng = engine(model, max_lanes=1, spec=False)
    eng.submit(first, max_new_tokens=10)
    eng.run()
    assert np.abs(rings(eng)).min() > 0  # every slot was written
    r2 = eng.submit(second, max_new_tokens=11)
    eng.run()
    fresh = engine(model, max_lanes=1, spec=False)
    f2 = fresh.submit(second, max_new_tokens=11)
    fresh.run()
    assert r2.output == f2.output
    assert served_gap(ref, model, second, np.asarray(r2.output)) < TOL
    # the second request fed positions 0-15, a 16-slot ring's every slot
    assert (rings(eng) == rings(fresh)).all()
    assert eng.stats()["win_slot_resets"] == 2


@pytest.mark.parametrize("chunk", [8, 40, 128],
                         ids=["chunk8", "chunk40", "chunk128"])
def test_a_preempted_request_resumes_token_identically(model, chunk):
    """A pool too small for three growing requests: the newest is
    preempted, its lane handed on, and its re-admission's prefill
    rebuilds the rings and the full layers' K/V from chunk 0."""
    work = prompts(3, seed=31, lo=9, hi=12)
    tight = engine(model, num_blocks=13, spec=False, prefill_chunk=chunk)
    roomy = engine(model, spec=False)
    out = {}
    for name, eng in (("tight", tight), ("roomy", roomy)):
        reqs = [eng.submit(p, max_new_tokens=20) for p in work]
        eng.run()
        out[name] = [r.output for r in reqs]
    assert tight.counters["preemptions"] >= 1
    assert roomy.counters["preemptions"] == 0
    assert out["tight"] == out["roomy"]
    assert tight.stats()["win_slot_resets"] \
        == len(work) + tight.counters["preemptions"]


def test_speculation_is_token_identical_to_plain_decoding(model):
    """The default n-gram drafter on repeating prompts (so that it
    proposes and mostly misses) over several lanes with churn."""
    rng = np.random.default_rng(7)
    work = [np.tile(rng.integers(0, VOCAB, 4).astype(np.int32), 5)
            for _ in range(5)]
    outs = {}
    for spec in (False, True):
        eng = engine(model, spec=spec)
        reqs = [eng.submit(p, max_new_tokens=14) for p in work]
        eng.run()
        outs[spec] = [r.output for r in reqs]
        st = eng.stats()
    assert outs[True] == outs[False]
    assert st["verify_steps"] > 0
    assert st["spec_rolled_back_tokens"] \
        == st["spec_proposed_tokens"] - st["spec_accepted_tokens"] > 0


def _no_sink(scores, sink):
    return jax.nn.softmax(scores, axis=-1)


def _band_off_by_one(q_pos, k_pos, window):
    back = q_pos - k_pos
    return (back >= 0) & (back <= window) & (k_pos >= 0)


def _band_never_reset(q_pos, k_pos, window):
    back = q_pos - k_pos
    return (back >= 0) & (back < window)


def _rope_on_the_last_columns(x, pos, theta, n_rot):
    return jnp.flip(_PARTIAL_ROPE(jnp.flip(x, -1), pos, theta, n_rot), -1)


_PARTIAL_ROPE = M.partial_rope

BROKEN = {
    "sink_left_out": ("softmax_with_sink", _no_sink),
    "band_off_by_one": ("band_mask", _band_off_by_one),
    "ring_slot_not_reset": ("band_mask", _band_never_reset),
    "rotary_on_the_wrong_columns": ("partial_rope",
                                    _rope_on_the_last_columns),
}


@pytest.fixture
def fresh_traces():
    """A traced program keeps the functions it was traced with: a break
    patched into the model's module reaches no program that an earlier
    test traced, and must reach none that a later one uses."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("case", [None, "values_not_scaled", *BROKEN])
def test_a_broken_path_fails_the_comparison(ref, model, monkeypatch,
                                            fresh_traces, case):
    """The comparison the sound path passes (``case`` None) fails by an
    order of magnitude or more where one of the architecture's parts is
    broken in the PROGRAM (the served tokens are then its own, and the
    reference scores them): one lane, a long request, then a short one
    that starts on the rings its predecessor left."""
    served = model
    if case == "values_not_scaled":
        served = seeded(WindowMoEForCausalLM(
            tiny_config(attention_value_scale=1.0)))
    elif case is not None:
        monkeypatch.setattr(M, *BROKEN[case])
    long_, short = prompts(1, seed=41, lo=40, hi=41)[0], \
        prompts(1, seed=42, lo=3, hi=4)[0]
    eng = engine(served, max_lanes=1, spec=False)
    gaps = []
    for p in (long_, short):
        r = eng.submit(p, max_new_tokens=12)
        eng.run()
        gaps.append(served_gap(ref, model, p, np.asarray(r.output)))
    if case is None:
        assert max(gaps) < TOL
    else:  # (a ring never reset: zeros, then the predecessor's keys)
        assert min(gaps) > 10 * TOL


def test_prefix_cache_on_acquires_nothing(model):
    """Two requests with the same prompt, prefix cache on (the default):
    a family whose window layers keep a ring acquires no shared block."""
    prompt = prompts(1, seed=41, lo=24, hi=25)[0]
    eng = engine(model, prefix_cache=True, spec=False)
    a = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    b = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert a.output == b.output
    assert a.cached_len == b.cached_len == 0
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 0
    assert st["prefix_miss_tokens"] == 2 * prompt.size
    assert st["prefix_cache"] is True and st["prefix_reuse"] is False
    assert "ring" in st["prefix_reuse_why"]
    assert st["indexed_blocks"] == 0


def test_stats_tell_pools_by_kind(model):
    eng = engine(model)
    st = eng.stats()
    ring = 3 * 16 * 4 * (24 + 16) * 4  # window layers x R x heads, float32
    assert st["family"] == "window_moe"
    assert st["win_ring_bytes_per_lane"] == ring
    assert st["lane_pool_bytes"] == GEOM["max_lanes"] * ring
    assert st["full_kv_bytes_per_token"] == 2 * 2 * (24 + 16) * 4
    blocks = eng.scheduler.pool.num_blocks
    # the TWO full layers alone: a window layer takes no block
    assert st["kv_pool_bytes"] == 2 * blocks * 4 * 2 * (24 + 16) * 4
    assert st["device_state_bytes"] \
        == st["kv_pool_bytes"] + st["lane_pool_bytes"]


def test_a_ring_too_short_for_the_drafts_raises(model):
    short = seeded(WindowMoEForCausalLM(tiny_config(window_ring_len=12)))
    with pytest.raises(ValueError, match="sliding_window \\+ spec_k \\+ 1"):
        engine(short, spec_k=4)
    assert engine(short, spec=False).stats()["win_ring_len"] == 12


@pytest.mark.parametrize("flag", ["kv_int8", "int8_weights"])
def test_unsupported_serving_modes_raise(model, flag):
    with pytest.raises(UnimplementedError, match=flag):
        engine(model, **{flag: True})
