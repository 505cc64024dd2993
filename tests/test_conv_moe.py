"""The short-convolution / grouped-query sparse-expert model and its
serving family against the plain reference
(``benchmarks/chip/reference/conv_gqa_moe.py``, loaded by path: ONE copy).

Tiny widths with the published RATIOS, CPU, seeded weights: three conv
layers to one attention layer behind a dense first layer, 4 query heads a
key/value head, top-4 of 16 experts with EVERY expert held, a convolution
of 3 taps (a tail of 2 rows). The taps are N(0, 0.5) so that each of the
three weighs; the program runs in float32 here, so what separates it from
the float32 reference is the order of summation: ``TOL`` = 2e-4 on logits
of magnitude ~1.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.errors import UnimplementedError
from paddle_tpu.incubate.distributed.models.moe import held_experts as HE
from paddle_tpu.models import (
    ConvMoEConfig, ConvMoEForCausalLM, conv_moe as M, generate,
)
from paddle_tpu.models.generation import _rms
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.families import conv_moe as FAM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 256
HIDDEN = 64
TYPES = ["conv", "conv", "conv", "full_attention", "conv"]
NORMS = ("ln_in", "ln_post", "norm", "q_norm", "k_norm")


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "conv_gqa_moe_reference",
        os.path.join(ROOT, "benchmarks/chip/reference/conv_gqa_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(**kw):
    """Layer 0 a convolution over a dense SwiGLU, then conv / conv /
    attention / conv over expert layers that hold all 16 experts."""
    base = dict(vocab_size=VOCAB, hidden_size=HIDDEN, intermediate_size=80,
                moe_intermediate_size=24, num_hidden_layers=5,
                layer_types=TYPES, num_dense_layers=1,
                num_attention_heads=8, num_key_value_heads=2,
                num_experts=16, num_experts_per_tok=4)
    base.update(kw)
    return ConvMoEConfig(**base)


def seeded(model, seed=0, dtype="float32", small_scores=False):
    """Matrices N(0, 0.1) (the router's selection bias too), the taps N(0,
    0.5), norm weights 1 +- 0.1 (so a dropped norm weight shows), from one
    generator in parameter order. ``small_scores``: one channel of the
    embedding constant and the router's row of it strongly negative, so
    that many tokens' sigmoid scores sum to about the gate's epsilon."""
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        leaf = name.rsplit(".", 1)[-1]
        if leaf in NORMS:
            v = 1 + 0.1 * rng.uniform(-1, 1, p.shape)
        else:
            v = rng.normal(0, 0.5 if leaf == "conv_w" else 0.1, p.shape)
        if small_scores and leaf == "embed":
            v[:, 0] = 1.0
        if small_scores and leaf == "router":
            v[0, :] = -10.0
        p._data = jnp.asarray(v, dtype)
    model.eval()
    return model


def ref_params(model):
    out = {k: np.asarray(getattr(model, k)._data, np.float32)
           for k in ("embed", "norm")}
    out["layers"] = [{k: np.asarray(p._data, np.float32)
                      for k, p in blk.leaves().items()}
                     for blk in model.layers]
    return out


def ref_model(model):
    """The reference's ``m``: the configuration's keys as published."""
    return dict(vars(model.config))


def ref_logits(ref, model, ids, quant=False):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            ref_params(model), jnp.asarray(ids), ref_model(model),
            quant=quant))


@pytest.fixture(scope="module")
def model():
    return seeded(ConvMoEForCausalLM(tiny_config()))


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


@pytest.mark.parametrize("li", range(len(TYPES)))
def test_each_layer_matches_the_reference(ref, model, li):
    """Layer by layer, each kind from the same input: conv + dense, conv
    + experts, attention + experts."""
    m = ref_model(model)
    x = np.random.default_rng(2).normal(0, 1, (1, 29, HIDDEN)).astype(
        np.float32)
    blk, lw = model.layers[li], ref_params(model)["layers"][li]
    assert ("conv_w" in lw) == (TYPES[li] == "conv") == ("q_norm" not in lw)
    assert ("router" in lw) == (li >= 1) == ("gate_up" not in lw)
    got = blk(pt.to_tensor(x)).numpy()[0]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._layer(jnp.asarray(x[0]), lw, m, False)[0])
    assert np.abs(got - want).max() < TOL
    assert np.abs(want - x[0]).max() > 0.1


def test_a_lower_precision_fails_the_tolerance(ref, model, ids):
    """The same weights served in bfloat16, and the reference's own fp8
    control, miss ``TOL`` by an order of magnitude or more."""
    want = ref_logits(ref, model, ids[0])
    low = seeded(ConvMoEForCausalLM(tiny_config(dtype="bfloat16")),
                 dtype="bfloat16")
    got = low(pt.to_tensor(ids[:1])).numpy().astype(np.float32)[0]
    assert np.abs(got - want).max() > 10 * TOL
    assert np.abs(ref_logits(ref, model, ids[0], quant=True)
                  - want).max() > 10 * TOL


def test_the_layer_kinds_follow_the_published_keys():
    m = ConvMoEForCausalLM(tiny_config())
    assert ["conv_w" in b.leaves() for b in m.layers] \
        == [True, True, True, False, True]
    assert [b.mlp is not None for b in m.layers] == [False] + [True] * 4
    assert m.layers[0].in_proj.shape == [HIDDEN, 3 * HIDDEN]
    assert m.layers[0].conv_w.shape == [HIDDEN, 3]
    assert m.layers[3].qkv.shape == [HIDDEN, (8 + 2 * 2) * 8]
    assert m.layers[3].q_norm.shape == m.layers[3].k_norm.shape == [8]
    assert m.layers[1].mlp.router_bias.shape == [16]
    # the head is the embedding: no second table
    assert [n for n, _ in m.named_parameters() if "." not in n] \
        == ["embed", "norm"]
    # the default pattern is the published period
    assert tiny_config(layer_types=None, num_hidden_layers=8).layer_types \
        == ("conv", "conv", "full_attention", "conv") * 2
    with pytest.raises(ValueError, match="layer_types"):
        tiny_config(layer_types=["conv", "mamba"])
    for flag in ({"conv_bias": True}, {"norm_topk_prob": False},
                 {"use_expert_bias": False},
                 {"rope_parameters": {"rope_theta": 1e6,
                                      "rope_type": "yarn"}}):
        with pytest.raises(ValueError, match=next(iter(flag)).split("_")[0]):
            tiny_config(**flag)


def test_the_convolution_is_a_causal_sum_of_three_taps():
    """``sconv_conv`` over ``[tail | T]`` against the sum written out; a
    sequence convolved whole equals the same sequence convolved in pieces
    that hand on their last two rows; ``T = 1`` is the decode step."""
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(0, 1, (2, 11, HIDDEN)), jnp.float32)
    lp = {"conv_w": jnp.asarray(rng.normal(0, 0.5, (HIDDEN, 3)),
                                jnp.float32)}
    w = np.asarray(lp["conv_w"])
    padded = np.concatenate([np.zeros((2, 2, HIDDEN)), np.asarray(g)], 1)
    want = sum(w[:, j] * padded[:, j:j + 11] for j in range(3))
    whole = np.asarray(M.sconv_conv(jnp.asarray(padded, jnp.float32), lp))
    np.testing.assert_allclose(whole, want, rtol=1e-5, atol=1e-6)
    tail, out = jnp.zeros((2, 2, HIDDEN)), []
    for lo, hi in ((0, 1), (1, 3), (3, 4), (4, 9), (9, 11)):
        window = jnp.concatenate([tail, g[:, lo:hi]], 1)
        out.append(np.asarray(M.sconv_conv(window, lp)))
        tail = window[:, -2:]
    assert (np.concatenate(out, 1) == whole).all()  # operation for operation


@pytest.mark.parametrize("eps,same", [(1e-20, True), (1e-6, False)])
def test_the_gates_epsilon_is_an_argument(eps, same):
    """``route_top_k``'s epsilon, handed down from ``sparse_expert_block``
    and ``HeldExperts``: the default leaves the gates as they were (and
    the three expert families' programs), 1e-6 shows where the chosen
    scores are small."""
    rng = np.random.default_rng(4)
    u = jnp.asarray(np.abs(rng.normal(0, 1, (9, 32))), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.2, (32, 16)) - 0.6, jnp.float32)
    s = np.asarray(jax.nn.sigmoid(u @ w))
    top = np.sort(s, -1)[:, -4:]
    assert top.sum(-1).min() < 1e-4  # small enough for 1e-6 to weigh
    idx, g = HE.route_top_k(u, w, 4, 1.0, eps=eps)
    idx0, g0 = HE.route_top_k(u, w, 4, 1.0)
    assert (np.asarray(idx) == np.asarray(idx0)).all()
    assert (np.asarray(g) == np.asarray(g0)).all() == same
    np.testing.assert_allclose(
        np.sort(np.asarray(g), -1),
        top / (top.sum(-1, keepdims=True) + eps), rtol=1e-5)
    layer = HE.HeldExperts(32, 8, 16, 16, top_k=4, n_shared=0, eps=eps)
    layer.router._data = w
    arrays = layer.arrays()
    y = layer(pt.to_tensor(np.asarray(u))).numpy()
    want, _ = HE.sparse_expert_block(u, arrays, top_k=4, scaling=1.0,
                                     first_held=0, eps=eps)
    assert (y == np.asarray(want)).all()
    base, _ = HE.sparse_expert_block(u, arrays, top_k=4, scaling=1.0,
                                     first_held=0)
    assert (np.abs(y - np.asarray(base)).max() == 0) == same


def _share(kind, first, held, seed=3):
    """One layer's leaves with the experts ``first .. first + held - 1``
    of 64 (the same draws for every share), and the static view that says
    so."""
    rng = np.random.default_rng(seed)
    h, f, E = HIDDEN, 16, 64
    lp = {"ln_in": 1 + 0.1 * rng.uniform(-1, 1, h),
          "ln_post": 1 + 0.1 * rng.uniform(-1, 1, h)}
    if kind == "conv":
        lp.update(in_proj=rng.normal(0, 0.1, (h, 3 * h)),
                  conv_w=rng.normal(0, 0.5, (h, 3)),
                  out_proj=rng.normal(0, 0.1, (h, h)))
    else:
        lp.update(qkv=rng.normal(0, 0.1, (h, 12 * 8)),
                  o=rng.normal(0, 0.1, (h, h)),
                  q_norm=1 + 0.1 * rng.uniform(-1, 1, 8),
                  k_norm=1 + 0.1 * rng.uniform(-1, 1, 8))
    lp.update(router=rng.normal(0, 0.3, (h, E)),
              experts_gate_up=rng.normal(0, 0.1, (E, h, 2 * f)),
              experts_down=rng.normal(0, 0.1, (E, f, h)),
              router_bias=rng.normal(0, 0.2, (E,)))
    full = {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}
    mine = dict(full)
    for k in ("experts_gate_up", "experts_down"):
        mine[k] = full[k][first:first + held]
    cfg = tiny_config(moe_intermediate_size=f, num_experts=held,
                      router_experts=E, first_held_expert=first)
    return full, mine, cfg


@pytest.mark.parametrize("kind", ["conv", "full_attention"])
def test_the_shares_add_up_to_the_uncut_layer(ref, kind):
    """The guide's test of the expert-parallel cut: 8 shares of 8 experts
    each (ranks 0-7 of a 64-wide router), with what every chip computes
    alike — the convolution or the attention, and the residual — counted
    ONCE, add up to the layer that holds all 64, which is the uncut
    reference's layer."""
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (1, 18, HIDDEN)),
                    jnp.float32)
    full, _, cfg = _share(kind, 0, 64)
    g = cfg.static()
    m = dict(vars(cfg))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._layer(x[0], full, m, False)[0])
    whole = np.asarray(M.layer_on_sequence(x, full, g))[0]
    assert np.abs(whole - want).max() < TOL
    # what every share computes alike
    u = _rms(x, full["ln_in"], g.norm_eps)
    alike = x + (M.sconv_mix(u, full) if kind == "conv"
                 else M.attention_mix(u, full, g))
    total, assigned = np.asarray(alike)[0], 0
    for rank in range(8):
        _, mine, cfg_r = _share(kind, 8 * rank, 8)
        out = M.layer_on_sequence(x, mine, cfg_r.static())
        part = np.asarray(out - alike)[0]
        total = total + part
        _, counts = M.ffn_block(_rms(alike, mine["ln_post"], g.norm_eps),
                                mine, cfg_r.static())
        assigned += int(np.asarray(counts).sum())
    assert np.abs(total - want).max() < TOL
    # every token-expert assignment lands on exactly one share
    assert assigned == 18 * 4
    # and one share alone is NOT the layer (the cut leaves something out)
    assert np.abs(np.asarray(out)[0] - want).max() > 0.01


def test_train_step_runs_and_learns():
    from paddle_tpu.jit.train_step import TrainStep

    pt.seed(0)
    m = ConvMoEForCausalLM(tiny_config(initializer_range=0.05))
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
    with pytest.raises(UnimplementedError, match="conv_moe"):
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


def tails(eng, lane=0):
    """A lane's tails, every conv layer's."""
    return np.asarray(eng._pools[3][:, lane]).reshape(-1)


@pytest.mark.parametrize("chunk", [8, 12, 40, 128],
                         ids=["chunk8", "chunk12", "chunk40", "chunk128"])
def test_chunked_prefill_and_plain_decode_equal_the_full_forward(
        ref, model, chunk):
    """Prompts of 1 and 2 tokens (shorter than the tail), of 8, 16 and 24
    (the last chunk full), of 9, 10, 11 and 17-19 (a last chunk of 1, 2
    and 3 real tokens: a chunk boundary at every offset of the three
    taps, and a tail that keeps 1 or 0 rows of the chunk before), and
    longer ones, decoded with speculation off: every served token is the
    reference's first choice to within ``TOL`` at its position. At 128
    every prompt is ONE padded call, of which the last 2 real rows are
    kept; at 40 (a width no prompt length here divides, as the served
    512) the 70-token prompt is a FULL wide call and then a part-padded
    one whose tail is taken at its 30 real tokens."""
    eng = engine(model, spec=False, prefill_chunk=chunk)
    work = prompts(3) + [np.arange(n, dtype=np.int32) * 7 % 251
                         for n in (1, 2, 3, 8, 9, 10, 11, 16, 17, 18, 19,
                                   24, 70)]
    reqs = [eng.submit(p, max_new_tokens=14) for p in work]
    eng.run()
    for p, r in zip(work, reqs):
        assert served_gap(ref, model, p, np.asarray(r.output)) < TOL
    st = eng.stats()
    assert st["conv_slot_resets"] == len(work)
    assert st["verify_steps"] == 0 and st["spec_rolled_back_tokens"] == 0
    assert st["moe_expert_calls"] == 4 * (st["decode_steps"]
                                          + st["prefill_chunks"])
    # every expert is held: every assignment is
    assert st["moe_assignments_held"] == st["moe_assignments"] > 0
    # held experts hit, counted in the rounds' calls: 4-12 of 16 a call
    assert 4 * 4 * st["decode_steps"] <= st["moe_round_experts_hit"] \
        <= 4 * 12 * st["decode_steps"]


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


class Junk:
    """A drafter whose every proposal is wrong (bar an accident): every
    round is a verify round, every draft rejected."""

    def propose(self, context, k):
        return (int(context[-1]) + 1 + np.arange(k, dtype=np.int32)) % VOCAB


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
def test_rejected_drafts_leave_no_trace(ref, model, plain_run, a):
    """A verify round whose draft is right for ``a`` of ``k`` tokens (all
    rejected at ``a`` 0), followed two tokens later by one whose drafts
    are all right: every token is plain decoding's and the reference's
    first choice, and after each round the lane's tails are what plain
    decoding of the same tokens leaves (to float32's rounding: a round of
    5 positions sums its products in another order than 5 rounds of 1; a
    row of another position would differ by ~1). The engine's acceptance
    and the program's agree."""
    prompt, truth = plain_run
    seq = np.concatenate([prompt, truth])
    first = prompt.size + 3  # the round after 3 emitted tokens
    second = first + a + 1 + 2
    eng = engine(model, Oracle(seq, {first: (a, K - a), second: (K, 0)}),
                 spec_k=K, max_lanes=1)
    plain = engine(model, spec=False, max_lanes=1)
    req = eng.submit(prompt, max_new_tokens=24)
    twin = plain.submit(prompt, max_new_tokens=24)
    while eng.scheduler.has_work():
        eng.step()
        while len(twin.output) < len(req.output):
            plain.step()
        # the tails follow the fed tokens: all but the last emitted one
        assert len(twin.output) == len(req.output)
        assert np.abs(tails(plain)).mean() > 0.1
        np.testing.assert_allclose(tails(eng), tails(plain), atol=1e-5)
    assert (np.asarray(req.output) == truth).all()
    assert served_gap(ref, model, prompt, truth) < TOL
    c = eng.counters
    assert c["verify_steps"] == 2
    assert c["spec_accepted_tokens"] == a + K
    assert c["spec_rolled_back_tokens"] == K - a \
        == c["spec_proposed_tokens"] - c["spec_accepted_tokens"]


def test_an_idle_lanes_tail_is_untouched_by_a_verify_round(model):
    """Two lanes, one request: the verify rounds of the busy lane leave
    the idle lane's tails as they lay."""
    eng = engine(model, Junk(), spec_k=K, max_lanes=2)
    marked = eng._pools[3].at[:, 1].set(0.5)
    eng._pools = (*eng._pools[:3], marked)
    req = eng.submit(prompts(1, seed=12, lo=9, hi=10)[0], max_new_tokens=9)
    while eng.counters["verify_steps"] < 3:
        eng.step()
    assert req.lane == 0 and eng.counters["decode_steps"] == 0
    assert eng.counters["spec_rolled_back_tokens"] >= 8
    assert (tails(eng, 1) == 0.5).all() and (tails(eng, 0) != 0.5).all()


def test_a_reused_lane_gives_what_a_fresh_engine_gives(ref, model):
    """One lane, two requests one after the other, the first the longer:
    the second starts from a ZERO tail, not from what the first left."""
    first = prompts(1, seed=21, lo=50, hi=51)[0]
    second = prompts(1, seed=22, lo=6, hi=7)[0]
    eng = engine(model, max_lanes=1, spec=False)
    eng.submit(first, max_new_tokens=10)
    eng.run()
    assert np.abs(tails(eng)).min() > 0  # every row was written
    r2 = eng.submit(second, max_new_tokens=11)
    eng.run()
    fresh = engine(model, max_lanes=1, spec=False)
    f2 = fresh.submit(second, max_new_tokens=11)
    fresh.run()
    assert r2.output == f2.output
    assert served_gap(ref, model, second, np.asarray(r2.output)) < TOL
    assert (tails(eng) == tails(fresh)).all()
    assert eng.stats()["conv_slot_resets"] == 2


@pytest.mark.parametrize("chunk", [8, 40, 128],
                         ids=["chunk8", "chunk40", "chunk128"])
def test_a_preempted_request_resumes_token_identically(model, chunk):
    """A pool too small for three growing requests: the newest is
    preempted, its lane handed on, and its re-admission's prefill
    rebuilds the tails and the attention layer's K/V from chunk 0."""
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
    assert tight.stats()["conv_slot_resets"] \
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


# -- broken paths ----------------------------------------------------------------

def _tail_not_reset(fresh, kept):
    return kept


def _tail_past_n_keep(live, accepted):
    return jnp.where(live, 2 + accepted, 0)


def _gates_swapped(u, lp):
    B, C, z = jnp.split(u @ lp["in_proj"], 3, axis=-1)
    return C * z, B


def _taps_reversed(window, lp):
    return _SCONV_CONV(window, {"conv_w": lp["conv_w"][:, ::-1]})


def _norm_after_the_rotary(u, lp, pos, cfg):
    b, s, _ = u.shape
    nh, g, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q, k, v = jnp.split(u @ lp["qkv"], [nh * d, (nh + g) * d], axis=-1)
    q = M.partial_rope(q.reshape(b, s, nh, d), pos, cfg.rope_theta, d)
    k = M.partial_rope(k.reshape(b, s, g, d), pos, cfg.rope_theta, d)
    return (_rms(q, lp["q_norm"], cfg.norm_eps),
            _rms(k, lp["k_norm"], cfg.norm_eps), v.reshape(b, s, g, d))


def _bias_weighs(u, w_router, top_k, scaling, bias=None, eps=1e-20):
    s = jax.nn.sigmoid(u.astype(jnp.float32) @ w_router.astype(jnp.float32))
    top_s, top_i = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    g = top_s / (jnp.sum(top_s, -1, keepdims=True) + eps) * scaling
    return top_i.astype(jnp.int32), g


_SCONV_CONV = M.sconv_conv

BROKEN = {
    "tail_not_reset": (FAM, "_carried", _tail_not_reset),
    "tail_taken_past_n_keep": (FAM, "_keeps", _tail_past_n_keep),
    "gates_B_and_C_swapped": (M, "sconv_project", _gates_swapped),
    "taps_reversed": (M, "sconv_conv", _taps_reversed),
    "qk_norm_after_the_rotary": (M, "attention_qkv", _norm_after_the_rotary),
    "bias_weighs_as_well_as_chooses": (HE, "route_top_k", _bias_weighs),
    "gate_epsilon_left_at_1e-20": (M, "GATE_EPS", 1e-20),
}
SMALL = ("small_scores", "gate_epsilon_left_at_1e-20")


@pytest.fixture
def fresh_traces():
    """A traced program keeps the functions it was traced with: a break
    patched into a module reaches no program that an earlier test traced,
    and must reach none that a later one uses."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def small_scores_model():
    return seeded(ConvMoEForCausalLM(tiny_config()), small_scores=True)


@pytest.mark.parametrize("case", [None, "small_scores", *BROKEN])
def test_a_broken_path_fails_the_comparison(ref, model, small_scores_model,
                                            monkeypatch, fresh_traces, case):
    """The comparison the sound path passes (``case`` None; and
    ``small_scores``, the model on which the gate's epsilon weighs) fails
    by an order of magnitude or more where one of the architecture's parts
    is broken in the PROGRAM (the served tokens are then its own, and the
    reference scores them): one lane, every round a verify round whose
    drafts are rejected, a long request, then a short one that starts on
    the tails its predecessor left."""
    served = small_scores_model if case in SMALL else model
    if case in BROKEN:
        monkeypatch.setattr(*BROKEN[case])
    long_, short = prompts(1, seed=41, lo=40, hi=41)[0], \
        prompts(1, seed=42, lo=3, hi=4)[0]
    eng = engine(served, Junk(), max_lanes=1, spec_k=K)
    gaps = []
    for p in (long_, short):
        r = eng.submit(p, max_new_tokens=12)
        eng.run()
        gaps.append(served_gap(ref, served, p, np.asarray(r.output)))
    assert eng.counters["spec_rolled_back_tokens"] > 20
    if case in BROKEN:  # (the short request: on every broken path)
        assert gaps[1] > 10 * TOL
    else:
        assert max(gaps) < TOL


def test_the_small_scores_model_has_small_scores(ref, small_scores_model):
    """What makes ``gate_epsilon_left_at_1e-20`` show: in every expert
    layer a good share of the tokens' chosen scores sum to less than a
    hundred epsilons."""
    model = small_scores_model
    m, p = ref_model(model), ref_params(model)
    x = p["embed"][prompts(1, seed=41, lo=40, hi=41)[0]]
    with jax.default_matmul_precision("highest"):
        for lw in p["layers"]:
            if "router" in lw:
                a = ref.rms_norm(x, lw["ln_in"], m["norm_eps"])
                mid = x + (ref.short_conv(a, lw, m, False) if "conv_w" in lw
                           else ref.attention(a, lw, m, False))
                s = jax.nn.sigmoid(ref.rms_norm(
                    mid, lw["ln_post"], m["norm_eps"]) @ lw["router"])
                top = np.sort(np.asarray(s), -1)[:, -4:].sum(-1)
                assert (top < 1e-4).mean() > 0.2
            x = ref._layer(x, lw, m, False)[0]


# -- what the engine is told -----------------------------------------------------

def test_prefix_cache_on_acquires_nothing(model):
    """Two requests with the same prompt, prefix cache on (the default):
    a family whose conv layers keep a tail acquires no shared block."""
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
    assert "tail" in st["prefix_reuse_why"] \
        and "B-m4" in st["prefix_reuse_why"]
    assert st["indexed_blocks"] == 0


def test_stats_tell_pools_by_kind(model):
    eng = engine(model)
    st = eng.stats()
    tail = 4 * 2 * HIDDEN * 4  # conv layers x rows x hidden, float32
    assert st["family"] == "conv_moe"
    assert st["conv_tail_bytes_per_lane"] == tail
    assert st["lane_pool_bytes"] == GEOM["max_lanes"] * tail
    assert st["kv_bytes_per_token"] == 1 * 2 * 2 * 8 * 4
    blocks = eng.scheduler.pool.num_blocks
    # the ONE attention layer alone: a conv layer takes no block
    assert st["kv_pool_bytes"] == 2 * blocks * 4 * 2 * 8 * 4
    assert st["device_state_bytes"] \
        == st["kv_pool_bytes"] + st["lane_pool_bytes"]
    assert set(st["row_read"].values()) == {"kernel"}
    # K and V pools, the accumulator, ONE pool of tails: all donated
    assert len(eng._pools) == FAM.N_POOLS == 4
    assert eng._family.donate_argnums == (1, 2, 3, 4)


def test_a_stack_with_no_attention_layer_raises():
    bare = ConvMoEForCausalLM(tiny_config(
        num_hidden_layers=2, layer_types=["conv", "conv"]))
    with pytest.raises(UnimplementedError, match="no attention layer"):
        engine(bare)


@pytest.mark.parametrize("flag", ["kv_int8", "int8_weights"])
def test_unsupported_serving_modes_raise(model, flag):
    with pytest.raises(UnimplementedError, match=flag):
        engine(model, **{flag: True})
