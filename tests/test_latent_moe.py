"""The latent-attention sparse-expert family against its plain reference
(``benchmarks/chip/reference/mla_moe.py``, loaded by path: ONE copy).

Tiny widths, CPU, seeded weights. The program runs in float32 here, so
what separates it from the float32 reference is the order of summation:
every comparison's tolerance is ``TOL`` = 2e-4 on logits of magnitude ~3
(read: 3e-6 to 4e-5 over the cases below), and the same model served in
bfloat16 misses it by two orders of magnitude
(``test_a_lower_precision_fails_the_tolerance``), as the reference's own
fp8 control does.
"""
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.errors import UnimplementedError
from paddle_tpu.incubate.distributed.models.moe.held_experts import (
    HeldExperts, held_experts, route_top_k,
)
from paddle_tpu.models import (
    LatentMoEConfig, LatentMoEForCausalLM, LlamaConfig, LlamaForCausalLM,
    generate, latent_moe,
)
from paddle_tpu.serving import ServingConfig, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "mla_moe_reference",
        os.path.join(ROOT, "benchmarks/chip/reference/mla_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(**kw):
    """1 dense + 2 expert layers; 16 experts routed over, top-4, experts
    4-7 held; every width differs from every other, so a transposed or
    swapped dimension cannot pass."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=24, n_routed_experts=4,
                router_experts=16, first_held_expert=4,
                num_experts_per_tok=4, routed_scaling_factor=2.5,
                rope_theta=25600000.0)
    base.update(kw)
    return LatentMoEConfig(**base)


def seeded(model, seed=0, dtype="float32"):
    """Matrices N(0, 0.1), norm weights 1 +- 0.1 (so a dropped norm
    weight shows), from one generator in parameter order."""
    rng = np.random.default_rng(seed)
    for _, p in model.named_parameters():
        v = 1 + 0.1 * rng.uniform(-1, 1, p.shape) if len(p.shape) == 1 \
            else rng.normal(0, 0.1, p.shape)
        p._data = jnp.asarray(v, dtype)
    model.eval()
    return model


def ref_params(model):
    def leaves(blk):
        return {k: np.asarray(p._data, np.float32)
                for k, p in blk.leaves().items()}

    out = {k: np.asarray(getattr(model, k)._data, np.float32)
           for k in ("embed", "norm", "lm_head")}
    out["layers"] = [leaves(b) for b in model.layers]
    if model.mtp is not None:
        out["mtp"] = {k: np.asarray(getattr(model.mtp, k)._data, np.float32)
                      for k in ("e_norm", "h_norm", "proj")}
        out["mtp"]["layer"] = leaves(model.mtp.layer)
    return out


def ref_logits(ref, model, ids, fn="forward", **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(getattr(ref, fn)(
            ref_params(model), jnp.asarray(ids), dict(vars(model.config)),
            **kw))


@pytest.fixture(scope="module")
def model():
    return seeded(LatentMoEForCausalLM(tiny_config(
        num_nextn_predict_layers=1)))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(1).integers(0, 256, (2, 40)).astype(
        np.int32)


# -- the model against the reference -------------------------------------------

def test_whole_model_logits_match_the_reference(ref, model, ids):
    got = model(pt.to_tensor(ids)).numpy()
    want = np.stack([ref_logits(ref, model, row) for row in ids])
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


def test_mtp_module_logits_match_the_reference(ref, model, ids):
    got = model.mtp_logits(pt.to_tensor(ids)).numpy()
    want = np.stack([ref_logits(ref, model, row, "mtp_logits")
                     for row in ids])
    assert got.shape == (2, 39, 256)
    assert np.abs(got - want).max() < TOL
    # it is another function than the model's own next-token head
    assert np.abs(got - model(pt.to_tensor(ids)).numpy()[:, :-1]).max() > 0.1


def test_train_step_loss_equals_the_references(ref, ids):
    """One ``jit.TrainStep`` step: the loss it reports is the
    reference's (main loss + 0.1 x the next-token module's), and the
    step moved the expert weights. 1e-5: one scalar, float32 both
    sides."""
    model = seeded(LatentMoEForCausalLM(tiny_config(
        num_nextn_predict_layers=1)), seed=3)
    model.train()
    labels = np.random.default_rng(2).integers(0, 256, ids.shape)
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss_fn(
            ref_params(model), jnp.asarray(ids), jnp.asarray(labels),
            dict(vars(model.config)), mtp_weight=0.1))
    before = np.asarray(model.layers[1].mlp.experts_down._data)
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    from paddle_tpu.jit.train_step import TrainStep

    step = TrainStep(model, opt)
    loss = float(step(pt.to_tensor(ids),
                      pt.to_tensor(labels.astype(np.int64))).numpy())
    assert abs(loss - want) < 1e-5 * max(1.0, abs(want))
    after = np.asarray(model.layers[1].mlp.experts_down._data)
    assert np.abs(after - before).max() > 0


def test_absorbed_attention_equals_the_published_form():
    """``attend_absorbed`` (what the step programs run) against
    ``attend_upprojected`` (keys and values expanded from the latent) on
    a random cache with a ragged visibility mask."""
    cfg = tiny_config().static()
    rng = np.random.default_rng(5)
    b, s, L, nh = 3, 5, 37, cfg.num_attention_heads

    def arr(*shape):
        return jnp.asarray(rng.normal(0, 1, shape), jnp.float32)

    lp = {"kv_b": arr(cfg.kv_lora_rank,
                      nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)) * 0.2}
    q_nope, q_rope = arr(b, s, nh, 16), arr(b, s, nh, 8)
    cache = arr(b, L, cfg.kv_lora_rank + 8)
    pos = jnp.asarray(rng.integers(4, L, (b, s)))
    vis = jnp.arange(L)[None, None, :] <= pos[:, :, None]
    with jax.default_matmul_precision("highest"):
        a = latent_moe.attend_absorbed(q_nope, q_rope, cache, vis, lp, cfg)
        u = latent_moe.attend_upprojected(q_nope, q_rope, cache, vis, lp,
                                          cfg)
    assert a.shape == (b, s, nh * cfg.v_head_dim)
    assert np.abs(np.asarray(u)).max() > 0.5
    assert np.abs(np.asarray(a) - np.asarray(u)).max() < 1e-5


# -- the expert layer ----------------------------------------------------------

def _expert_layer(first_held, n_held, seed=7):
    layer = HeldExperts(64, 32, router_experts=16, n_held=n_held,
                        first_held=first_held, top_k=4, scaling=2.5)
    rng = np.random.default_rng(seed)  # the same draws for every share
    full = {"router": rng.normal(0, 0.3, (64, 16)),
            "experts_gate_up": rng.normal(0, 0.1, (16, 64, 64)),
            "experts_down": rng.normal(0, 0.1, (16, 32, 64)),
            "shared_gate_up": rng.normal(0, 0.1, (64, 64)),
            "shared_down": rng.normal(0, 0.1, (32, 64))}
    for k, v in full.items():
        if k.startswith("experts_"):
            v = v[first_held:first_held + n_held]
        getattr(layer, k)._data = jnp.asarray(v, jnp.float32)
    return layer


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's test of the expert-parallel cut: 4 shares of 4 experts
    each (ranks 0-3 of 16 experts), the shared expert counted once, add
    up to what the uncut REFERENCE gives for the whole layer."""
    u = jnp.asarray(np.random.default_rng(8).normal(0, 1, (2, 9, 64)),
                    jnp.float32)
    whole = _expert_layer(0, 16)
    lw = {k: np.asarray(v) for k, v in whole.arrays().items()}
    m = {"num_experts_per_tok": 4, "n_routed_experts": 16,
         "first_held_expert": 0, "routed_scaling_factor": 2.5}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(u.reshape(18, 64), lw, m, False))
        shared = np.asarray(ref.swiglu(u.reshape(18, 64),
                                       lw["shared_gate_up"],
                                       lw["shared_down"], False))
    total, counts = -3 * shared, []
    for rank in range(4):
        share = _expert_layer(4 * rank, 4)
        total = total + share(pt.to_tensor(u)).numpy().reshape(18, 64)
        counts.append(share.last_counts.numpy())
    assert np.abs(want).max() > 0.05
    assert np.abs(total - want).max() < 1e-5
    # every token-expert assignment lands on exactly one share
    assert int(np.sum(counts)) == 18 * 4
    # and one share alone is NOT the layer (the cut leaves something out)
    assert np.abs(whole(pt.to_tensor(u)).numpy().reshape(18, 64)
                  - _expert_layer(0, 4)(pt.to_tensor(u)).numpy()
                  .reshape(18, 64)).max() > 0.01


def test_no_token_is_dropped_when_the_router_picks_one_expert():
    """A router forced onto expert 5 (of the held 4-7) for EVERY token:
    a capacity rule would drop most of them; here all 50 rows go through
    expert 5 and the result is that expert's SwiGLU of each token."""
    rng = np.random.default_rng(9)
    u = jnp.asarray(rng.normal(0, 1, (50, 64)), jnp.float32)
    w_gu = jnp.asarray(rng.normal(0, 0.1, (4, 64, 64)), jnp.float32)
    w_dn = jnp.asarray(rng.normal(0, 0.1, (4, 32, 64)), jnp.float32)
    router = np.zeros((64, 16), np.float32)
    idx, g = route_top_k(jnp.abs(u), jnp.asarray(router).at[:, 5].set(1.0),
                         1, 2.5)
    assert (np.asarray(idx) == 5).all() and np.allclose(np.asarray(g), 2.5)
    y, counts = held_experts(u, idx, g, w_gu, w_dn, first_held=4)
    assert counts.tolist() == [0, 50, 0, 0]
    gate, up = jnp.split(u @ w_gu[1], 2, axis=-1)
    want = 2.5 * ((jax.nn.silu(gate) * up) @ w_dn[1])
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-5
    # pads are neither computed nor counted
    valid = jnp.arange(50) < 20
    y2, counts2 = held_experts(u, idx, g, w_gu, w_dn, 4, valid=valid)
    assert counts2.tolist() == [0, 20, 0, 0]
    assert np.abs(np.asarray(y2)[20:]).max() == 0


def test_expert_layer_checks_what_it_is_told_it_holds():
    with pytest.raises(ValueError, match="not among"):
        HeldExperts(8, 4, router_experts=16, n_held=4, first_held=13)
    with pytest.raises(ValueError, match="top_k"):
        HeldExperts(8, 4, router_experts=2, n_held=2, top_k=3)


# -- through the serving engine ------------------------------------------------

GEOM = dict(max_lanes=3, block_size=16, prefill_chunk=32, max_seq_len=160)


def _serve(model, requests, **cfg):
    eng = ServingEngine(model, ServingConfig(**{**GEOM, **cfg}))
    handles = [eng.submit(p, max_new_tokens=n) for p, n in requests]
    eng.run()
    return eng, handles


def _served_gaps(ref, model, handles):
    """For every request: how far each served token's logit lies below
    the reference's best at that position, from the reference's ONE full
    forward over prompt + served tokens (the benchmark's comparison)."""
    out = []
    for h in handles:
        served = np.asarray(h.output, np.int32)
        full = np.concatenate([h.prompt, served])[:-1]
        lg = ref_logits(ref, model, full)[h.prompt.size - 1:]
        out.append(float((lg.max(-1)
                          - lg[np.arange(served.size), served]).max()))
    return out


def _traffic():
    rng = np.random.default_rng(11)
    system = rng.integers(0, 256, 48)           # 3 blocks, shared
    motif = rng.integers(0, 256, 8)
    return [
        # several blocks, three chunks
        (np.concatenate([system, rng.integers(0, 256, 37)]), 30),
        # shares the first 3 blocks: a prefix-cache hit
        (np.concatenate([system, rng.integers(0, 256, 9)]), 30),
        # a repeating prompt: the n-gram drafter proposes, verify rounds run
        (np.tile(motif, 6), 40),
        (rng.integers(0, 256, 5), 24),
    ]


@pytest.mark.parametrize("chunk", [32, 256], ids=["chunk32", "chunk256"])
def test_served_logits_match_the_references_full_forward(ref, chunk):
    """Prefill in chunks, then decode and verify rounds over the latent
    block pool, against the reference's full forward — with a prompt of
    several blocks and chunks, a prefix-cache hit, and (pool of 14
    blocks for 3 lanes) a request preempted and resumed. At chunk 256
    every prompt, each hit's remainder and each recompute is ONE padded
    call whose positions run past the table (10 blocks) and
    ``max_seq_len``."""
    model = seeded(LatentMoEForCausalLM(tiny_config()))
    eng, handles = _serve(model, _traffic(), num_blocks=15,
                          prefill_chunk=chunk)
    c = eng.counters
    assert c["prefill_chunks"] >= (6 if chunk == 32 else 5)
    assert c["prefill_fed_tokens"] == chunk * c["prefill_chunks"] \
        > c["prefix_miss_tokens"]
    assert c["verify_steps"] > 0
    assert c["decode_steps"] > 0
    assert c["prefix_hit_tokens"] >= 48
    assert c["preemptions"] >= 1
    assert max(_served_gaps(ref, model, handles)) < TOL
    # the counters that ride on the token fetch
    calls = c["prefill_chunks"] + c["decode_steps"] + c["verify_steps"]
    assert c["moe_expert_calls"] == 2 * calls
    assert c["moe_assignments"] % (4 * 2) == 0
    assert 0 < c["moe_assignments_held"] < c["moe_assignments"]
    assert c["moe_load_max_sum"] * 4 >= c["moe_assignments_held"]
    stats = eng.stats()
    assert stats["family"] == "latent_moe"
    assert stats["latent_kv_bytes_per_token"] == (32 + 8) * 4
    # the pool stores each 40-number entry padded to a 128-lane tile
    assert stats["kv_pool_bytes"] == 3 * 15 * 16 * 128 * 4
    # the weights are held ONCE: the collected parameters are the model's
    assert eng._params["layers"][1]["experts_down"] \
        is model.layers[1].mlp.experts_down._data
    assert eng._params["embed"] is model.embed._data


_LENGTHS = [1, 31, 32, 33, 101]  # around a chunk of W = 32: 1, W - 1, W, W + 1, 3W + 5


@pytest.fixture(scope="module")
def chunk_engines():
    """The same float32 model behind one engine a prefill width: a block
    (the narrowest), 32, and 256 — wider than every prompt, a lane's
    table and ``max_seq_len``."""
    model = seeded(LatentMoEForCausalLM(tiny_config()))
    return model, {c: ServingEngine(model, ServingConfig(
        **{**GEOM, "prefill_chunk": c})) for c in (16, 32, 256)}


@pytest.mark.parametrize("chunk", [32, 256], ids=["chunk32", "chunk256"])
@pytest.mark.parametrize("length", _LENGTHS)
def test_a_wider_prefill_call_serves_the_same_tokens(ref, chunk_engines,
                                                     chunk, length):
    """A prompt of ``length`` tokens through a ``chunk``-wide call and
    through block-wide ones: the same tokens, each the reference's first
    choice, whatever pad the call carries."""
    model, engines = chunk_engines
    prompt = np.random.default_rng(length).integers(0, 256, length)
    got = {}
    for c in (16, chunk):
        h = engines[c].submit(prompt, max_new_tokens=8)
        engines[c].run()
        got[c] = h
        engines[c].scheduler.pool.check_invariant()
    assert got[chunk].output == got[16].output
    assert max(_served_gaps(ref, model, [got[chunk]])) < TOL


def test_a_wide_calls_pad_writes_the_null_block_alone(chunk_engines):
    """5 real tokens in a 256-wide call on a fresh engine: the pad's
    cache entries (positions past the prompt, the 10-block table and
    ``max_seq_len``) land in block 0; no other block the request does
    not hold is written. Then a 17-token prompt twice: the second
    admission acquires a block of 16 and prefills ONE token, at position
    16, in a call 255 positions of which are pad."""
    model, _ = chunk_engines
    eng = ServingEngine(model, ServingConfig(
        **{**GEOM, "prefill_chunk": 256}))
    req = eng.submit(np.arange(5), max_new_tokens=3)
    eng.step()
    held = set(req.blocks) | {0}
    others = [b for b in range(eng.scheduler.pool.num_blocks)
              if b not in held]
    pool = np.asarray(eng._pools[0])
    assert not pool[:, others].any() and pool[:, req.blocks[0]].any()
    eng.run()
    prompt = np.random.default_rng(3).integers(0, 256, 17)
    first = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    before = eng.counters["prefix_miss_tokens"]
    second = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert second.cached_len == 16
    assert eng.counters["prefix_miss_tokens"] - before == 1
    assert second.output == first.output
    eng.scheduler.pool.check_invariant()


def test_a_lower_precision_fails_the_tolerance(ref):
    """The same traffic served in bfloat16: the comparison that float32
    passes at 2e-4 reads two orders of magnitude more."""
    model = seeded(LatentMoEForCausalLM(tiny_config(dtype="bfloat16")),
                   dtype="bfloat16")
    _, handles = _serve(model, _traffic()[:2])
    assert max(_served_gaps(ref, model, handles)) > 50 * TOL


def test_what_the_family_does_not_serve_raises(ref):
    model = seeded(LatentMoEForCausalLM(tiny_config()))
    for kw, word in (({"kv_int8": True}, "kv_int8"),
                     ({"int8_weights": True}, "int8_weights")):
        with pytest.raises(UnimplementedError, match=word):
            ServingEngine(model, ServingConfig(max_lanes=2, **kw))
    with pytest.raises(UnimplementedError, match="ServingEngine"):
        generate(model, pt.to_tensor(np.zeros((1, 4), np.int32)))


# -- the reference's alternates: what the chip cell's comparison rests on ------

def _walk(ref, params, ids, m):
    """The benchmark's walk (``chiplib/serve.py:reference_gaps``): the
    float32 chain layer by layer through ``layer_forward``."""
    x = jnp.asarray(params["embed"])[ids]
    for li, lw in enumerate(params["layers"]):
        x = ref.layer_forward(x, lw, li=li, m=m, quant=False)
    return x


@pytest.fixture(scope="module")
def walked():
    model = seeded(LatentMoEForCausalLM(tiny_config()))
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, 256),
                      jnp.int32)
    return ref_params(model), dict(vars(model.config)), ids


def test_the_walks_first_stream_is_the_plain_reference(ref, walked,
                                                       monkeypatch):
    """Stream 0 of the walk is the whole-model forward, number for
    number: the alternates are carried BESIDE the reference's own routing
    and never change it. With no margin nothing is undecided: no
    alternate, nothing left out, and the head gives ``logits - max``."""
    params, m, ids = walked
    with jax.default_matmul_precision("highest"):
        plain = ref.hidden_states(params, ids, m)
        monkeypatch.setattr(ref, "TIE_MARGIN", 0.1)
        x = _walk(ref, params, ids, m)
        assert x.shape == (256, ref.STREAMS, 64 + 1)
        np.testing.assert_array_equal(np.asarray(x[:, 0, :-1]),
                                      np.asarray(plain))
        n, with_alt, left = ref.coverage(x)
        assert 0 < left < with_alt < n
        monkeypatch.setattr(ref, "TIE_MARGIN", 0.0)
        x0 = _walk(ref, params, ids, m)
        assert ref.coverage(x0) == (256, 0, 0)
        logits = ref.head_logits(plain, params, m=m, quant=False)
        np.testing.assert_allclose(
            np.asarray(ref.head_logits(x0, params, m=m, quant=False)),
            np.asarray(logits - logits.max(-1, keepdims=True)), atol=1e-6)


def test_the_head_reads_a_token_under_the_stream_that_suits_it_best(ref):
    """``best - logits[token]``, as the benchmark computes it, on the
    head's output for rows with alternates: the smallest gap over the
    LIVE streams (a dead stream's values count for nothing), and 0 on a
    row that is left out."""
    rng = np.random.default_rng(3)
    K, S, H, V = 6, 4, 16, 32
    top = {"norm": jnp.ones(H), "lm_head": jnp.asarray(
        rng.normal(0, 1, (H, V)), jnp.float32)}
    m = {"rms_norm_eps": 1e-5}
    x = rng.normal(0, 1, (K, S, H + 1)).astype(np.float32)
    x[:, 0, H] = 1.0
    x[:, 1:, H] = [[1, 1, 0], [0, 0, 0], [1, 0, 0], [1, 1, 1], [0, 0, 0],
                   [1, 0, 0]]
    x[4, 0, H] = 2.0  # left out
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.head_logits(jnp.asarray(x), top, m=m,
                                         quant=False))
        each = np.stack([np.asarray(ref.head_logits(
            jnp.asarray(x[:, s, :H]), top, m=m, quant=False))
            for s in range(S)], 1)                          # [K, S, V]
    gaps = each.max(-1, keepdims=True) - each
    gaps = np.where((x[:, :, H] > 0)[..., None], gaps, np.inf).min(1)
    gaps[4] = 0.0
    np.testing.assert_allclose(got.max(-1, keepdims=True) - got, gaps,
                               atol=1e-5)
    assert (got.max(-1) == 0).all()


def test_a_router_moved_inside_the_margin_reads_no_gap(ref, walked,
                                                       monkeypatch):
    """What the alternates are for. A "program" that is the reference
    with every router weight moved by 1% of the weights' spread (what
    bfloat16 activations do to the logits at the published widths, and
    more) picks another expert where two scores nearly tie, and its
    first-choice token then lies far below the reference's best — a
    plain comparison reads a coin toss. Under the walk the same tokens
    read nothing: at the positions that flipped and are compared, the gap
    is a tenth of the plain one at most."""
    params, m, ids = walked
    rng = np.random.default_rng(7)
    moved = dict(params, layers=[
        dict(lw, router=lw["router"] + 0.01 * lw["router"].std()
             * rng.standard_normal(lw["router"].shape).astype(np.float32))
        if "router" in lw else lw for lw in params["layers"]])
    monkeypatch.setattr(ref, "TIE_MARGIN", 0.1)

    def gap(logits, tok):
        return np.asarray(logits.max(-1) - jnp.take_along_axis(
            logits, tok[:, None], -1)[:, 0])

    with jax.default_matmul_precision("highest"):
        served = jnp.argmax(ref.forward(moved, ids, m), -1)
        plain = gap(ref.forward(params, ids, m), served)
        x = _walk(ref, params, ids, m)
        walk = gap(ref.head_logits(x, params, m=m, quant=False), served)
    compared = np.asarray(x[:, 0, -1]) < 2
    flipped = (plain > 0.05) & compared
    assert flipped.sum() >= 2 and plain.max() > 0.2, plain
    assert (walk[flipped] <= plain[flipped] / 10).all()
    assert walk.max() < 0.01, walk.max()
    assert (walk <= plain + 1e-6).all()  # never reads higher than plainly


# -- the dense family's programs change only on purpose -------------------------

# sha256 (first 16 hex) of the lowered text of the three dense serving
# programs at the geometry below (JAX 0.9.0, matmul precision "highest" as
# tests/conftest.py sets it). PR 27 read them on its parent to prove that
# moving the programs to serving/families/dense_gqa.py changed nothing;
# PR 28 changed the programs on purpose (the K/V read goes by live rows)
# and read them again; so did PR 39 (the bf16 pool merges the heads into
# its last axis and the fused kernel reads it — lowered here as the CPU
# lowers it, interpreted; all six were read, and the int8 pool's three,
# whose read is the XLA one as before, came out as they were). A change
# to the programs' functions changes a hash: read them again when that is
# meant. So does another JAX.
_DENSE_PROGRAMS = {
    (False, "decode"): "1d44b334dad53699",
    (False, "verify"): "98c593b7c87432fa",
    (False, "prefill"): "29d03ef22fc13e55",
    (True, "decode"): "8e811a28ea173db7",
    (True, "verify"): "a35ff204101ef2f3",
    (True, "prefill"): "e80960dc3740385f",
}


@pytest.fixture(scope="module")
def dense_engines():
    out = {}
    for kv_int8 in (False, True):
        model = LlamaForCausalLM(LlamaConfig.tiny(
            num_hidden_layers=3, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=256,
            dtype="bfloat16"))
        for p in model.parameters():
            p._data = p._data.astype("bfloat16")
        model.eval()
        out[kv_int8] = ServingEngine(model, ServingConfig(
            max_lanes=4, block_size=16, num_blocks=37, prefill_chunk=32,
            max_seq_len=80, kv_int8=kv_int8))
    return out


@pytest.mark.parametrize("kind", ["decode", "verify", "prefill"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_dense_serving_programs_are_unchanged(dense_engines, kv_int8, kind):
    if jax.__version__ != "0.9.0":
        pytest.skip("the hashes were read under JAX 0.9.0")
    eng = dense_engines[kv_int8]
    fam = eng._family
    assert fam.name == "dense_gqa"

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    L = 4
    lanes, width, rest = {
        "decode": (L, 1, (i32(L), i32(L))),
        "verify": (L, 5, (i32(L), i32(L, 5), i32(L))),
        "prefill": (1, 32, (i32(1, 32), i32(), i32(), i32()))}[kind]
    fn, static = fam.program(kind)
    pools = jax.tree_util.tree_map(spec, (eng._params, *eng._pools))
    with jax.default_matmul_precision("highest"):
        text = jax.jit(fn, static_argnames=tuple(static)).lower(
            *pools, eng._read_spec(kind, lanes, width), *rest,
            **static).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _DENSE_PROGRAMS[kv_int8, kind]
